"""``bench/spans.py`` on synthetic intervals, and it and every
``bench/metrics`` reader on a small trace recorded on a TPU v5e with the
program's host spans: one aggregation period (5 rounds, one commit) of a
4-layer bert-shaped cell through the benchmark's own traced window."""
import gzip
import json
import shutil

import pytest

from bench import trace as T
from bench.run import load_reader
from bench.spans import reduce_spans
from bench_helpers import ROOT

DATA = ROOT / "tests/bench/data/tiny_spans.xplane.pb.gz"
OLD = ROOT / "tests/bench/data/tiny.xplane.pb.gz"
NS = 1e-9


# ---------------------------------------------------------------- synthetic

def test_self_idle_excludes_child_spans():
    host = [(0, 100, "fed.serve"), (20, 60, "fed.loss_read")]
    ops = [[(10, 30, "op"), (70, 80, "op")]]
    out = reduce_spans(host, ops, 0, 100)
    serve, read = out["fed.serve"], out["fed.loss_read"]
    assert serve["count"] == 1 and serve["host_s"] == pytest.approx(100 * NS)
    assert serve["idle_s"] == pytest.approx(70 * NS)
    assert serve["self_idle_s"] == pytest.approx(40 * NS)   # [0,20] + [60,100]
    assert read["idle_s"] == read["self_idle_s"] == pytest.approx(30 * NS)


def test_spans_and_ops_are_clipped_to_the_window():
    host = [(-50, 50, "fed.round"), (120, 130, "fed.round"),
            (-40, -10, "fed.serve")]
    ops = [[(-30, 20, "op")]]
    out = reduce_spans(host, ops, 0, 100)
    assert set(out) == {"fed.round"}
    rnd = out["fed.round"]
    assert rnd["count"] == 1
    assert rnd["durations"] == [pytest.approx(50 * NS)]
    assert rnd["idle_s"] == pytest.approx(30 * NS)


def test_nested_dispatch_pairs_count_once():
    host = [(0, 100, "fed.commit"), (50, 90, "commit.heads"),
            (10, 20, "PjitFunction(add)"), (11, 19, "PjitFunction(add)"),
            (30, 40, "PjitFunction(mul)"), (31, 39, "PjitFunction(mul)"),
            (60, 70, "PjitFunction(add)"), (61, 69, "PjitFunction(add)"),
            (110, 120, "PjitFunction(add)"), (12, 14, "DevicePut")]
    out = reduce_spans(host, [[]], 0, 200)
    assert out["fed.commit"]["dispatches"] == 3
    assert out["commit.heads"]["dispatches"] == 1


def test_idle_is_averaged_over_chips():
    host = [(0, 100, "fed.commit")]
    out = reduce_spans(host, [[(0, 50, "op")], []], 0, 100)
    assert out["fed.commit"]["idle_s"] == pytest.approx(75 * NS)


def test_no_program_span_gives_nothing():
    host = [(0, 10, "PjitFunction(step)"), (0, 100, "bench.other")]
    assert reduce_spans(host, [[(0, 5, "op")]], 0, 100) == {}


def _unpack(path, tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins/profile/run"
    d.mkdir(parents=True)
    with gzip.open(path) as src, open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return d.parents[2]


def _spans(trace_dir):
    """``reduce_spans`` over the trace's window, taken as ``reduce_trace``
    takes it: the Python thread's line that holds the ``bench.round``
    spans, and each chip's ``XLA Ops``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(T.find_xplane(trace_dir)))
    rounds, host, chips = [], [], []
    for plane in pd.planes:
        events = {line.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events] for line in plane.lines}
        if plane.name == "/host:CPU":
            for evs in events.values():
                if any(n == T.ROUND_SPAN for _, _, n in evs):
                    rounds += [e for e in evs if e[2] == T.ROUND_SPAN]
                    host += [e for e in evs if e[2] != T.ROUND_SPAN]
        elif T.DEVICE_PLANE.match(plane.name):
            chips.append(events.get("XLA Ops", []))
    lo = min(s for s, _, _ in rounds)
    hi = max(max(e for _, e, _ in rounds),
             max(e for ops in chips for _, e, _ in ops))
    return reduce_spans(host, chips, lo, hi)


def test_a_trace_without_program_spans_has_none(tmp_path_factory):
    assert _spans(_unpack(OLD, tmp_path_factory)) == {}


# ---------------------------------------------------------- recorded trace

@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    return _unpack(DATA, tmp_path_factory)


@pytest.fixture(scope="module")
def reduced(trace_dir):
    return T.reduce_trace(trace_dir)


def _ctx(reduced):
    c = json.loads((ROOT / "bench/configs/bert-base.json").read_text())
    from bench.flops import encoder
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    return {"rounds": reduced["rounds"], "window_s": reduced["window_s"],
            "durations": [0.01] * reduced["rounds"], "compiles_in_window": 0,
            "work": encoder.round_work(c, t), "trace": reduced,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_span_tree_of_one_period(trace_dir, reduced):
    sp = _spans(trace_dir)
    assert reduced["rounds"] == 5
    assert sp["fed.round"]["count"] == 5
    assert sp["fed.serve"]["count"] == 30      # six clients, one by one
    for child in ("fed.client_fwd", "fed.server_step", "fed.loss_read",
                  "fed.client_bwd"):
        assert sp[child]["count"] == 30
    assert sp["fed.commit"]["count"] == 1
    commit_parts = ("commit.aggregate", "commit.redistribute", "commit.heads",
                    "commit.opt_reset")
    for child in commit_parts:
        assert sp[child]["count"] == 1
        assert sp[child]["idle_s"] <= sp["fed.commit"]["idle_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert 0 < sp["fed.round"]["idle_s"] <= idle * (1 + 1e-9)
    # the round's serves and commit hold nearly all of its idle
    assert sp["fed.round"]["self_idle_s"] <= 0.1 * sp["fed.round"]["idle_s"]
    assert sp["fed.serve"]["dispatches"] == 3 * 30     # fwd, step, bwd
    assert sp["fed.commit"]["dispatches"] == sum(
        sp[c]["dispatches"] for c in commit_parts)


def test_the_server_step_is_named_step(reduced):
    progs = reduced["programs"]
    assert "jit_step" in progs and "jit__unknown" not in progs
    value = load_reader(ROOT, "server_step.device_ms_per_round")(_ctx(reduced))
    assert value == pytest.approx(1000 * progs["jit_step"] / reduced["rounds"])


@pytest.mark.parametrize("metric", [
    m["name"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]])
def test_every_reader_reads_the_spans_trace(reduced, metric):
    value = load_reader(ROOT, metric)(_ctx(reduced))
    assert value is not None and value >= 0
