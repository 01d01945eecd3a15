"""``bench/trace.py`` and every ``bench/metrics`` reader, on a small trace
recorded on a TPU v5e: one aggregation period (5 rounds) of a 4-layer
bert-shaped cell through the benchmark's own traced window."""
import gzip
import json
import shutil

import pytest

from bench import trace as T
from bench.run import load_reader
from bench_helpers import ROOT

DATA = ROOT / "tests/bench/data/tiny.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins/profile/run"
    d.mkdir(parents=True)
    with gzip.open(DATA) as src, open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return T.reduce_trace(d.parents[2])


def test_window_busy_and_programs(reduced):
    assert reduced["rounds"] == 5
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert 0 < reduced["dot_s"] <= reduced["busy_s"]
    progs = reduced["programs"]
    for name in ("jit_fwd", "jit_bwd", "jit__unknown"):
        assert progs[name] > 0
    assert sum(progs.values()) <= reduced["window_s"]


def test_breakdown_lists(reduced):
    bd = reduced["breakdown"]
    for key in ("device_ops", "idle_gaps"):
        rows = bd[key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
        assert [s for _, s in rows] == sorted((s for _, s in rows), reverse=True)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in bd["idle_gaps"]) <= idle * (1 + 1e-9)


def _ctx(reduced):
    c = json.loads((ROOT / "bench/configs/bert-base.json").read_text())
    from bench.flops import encoder
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    return {"rounds": reduced["rounds"], "window_s": reduced["window_s"],
            "durations": [0.01] * reduced["rounds"], "compiles_in_window": 0,
            "work": encoder.round_work(c, t), "trace": reduced,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the readers this trace was recorded for; it holds no program spans
TRACE_READERS = ["device.idle_share", "step_mfu", "driver.compiles_in_window",
                 "driver.round_max_ms", "server_step.device_ms_per_round",
                 "client_prefix.device_ms_per_round", "kernels.matmul_roofline"]
SPAN_READERS = ["driver.serve_idle_ms_per_round", "driver.serve_ms_p90",
                "aggregation.idle_ms_per_commit",
                "aggregation.dispatches_per_commit"]


@pytest.mark.parametrize("metric", TRACE_READERS)
def test_every_reader_reads_the_trace(reduced, metric):
    value = load_reader(ROOT, metric)(_ctx(reduced))
    assert value is not None and value >= 0


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_readers_are_silent_without_their_spans(reduced, metric):
    assert reduced["spans"] == {}
    assert load_reader(ROOT, metric)(_ctx(reduced)) is None


def test_device_readers_are_silent_without_a_trace():
    ctx = {"rounds": 3, "window_s": 1.0, "durations": [], "compiles_in_window": 0,
           "work": {"flops": 1.0, "bytes": 1.0, "tokens": 1}, "trace": None,
           "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    for metric in ("device.idle_share", "step_mfu", "kernels.matmul_roofline",
                   "server_step.device_ms_per_round",
                   "client_prefix.device_ms_per_round", "driver.round_max_ms",
                   *SPAN_READERS):
        assert load_reader(ROOT, metric)(ctx) is None


def test_renamed_program_reads_as_no_metric(reduced):
    renamed = dict(reduced, programs={"jit_other": 1.0})
    ctx = _ctx(renamed)
    assert load_reader(ROOT, "server_step.device_ms_per_round")(ctx) is None
    assert load_reader(ROOT, "client_prefix.device_ms_per_round")(ctx) is None


@pytest.mark.parametrize("hlo,cls,dot", [
    ("%fusion.12 = f32[16,128,768]{2,1,0} fusion(f32[16,128,768] %p), kind=kOutput, calls=%fc.3",
     "fusion(kOutput)", True),
    ("%convolution_add_fusion.65 = bf16[16,128,768] fusion(%a, %b), kind=kOutput, calls=%fc.804",
     "convolution_add_fusion(kOutput)", True),
    ("%convolution.3 = f32[8,8]{1,0} convolution(f32[8,8] %a, f32[8,8] %b)", "convolution", True),
    ("%multiply_reduce_fusion.2 = f32[768] fusion(%x), kind=kLoop, calls=%fc.9",
     "multiply_reduce_fusion(kLoop)", False),
    ("%copy-done = f32[12,16,768] copy-done((f32[12,16,768]) %copy-start)", "copy-done", False),
])
def test_op_classes(hlo, cls, dot):
    assert T.op_class(hlo) == cls
    assert T.has_dot(hlo) is dot


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6)]
    assert T.union_length(iv) == 4
    assert T._gaps(iv, 0, 8) == [(3, 5), (6, 8)]
    assert T._gaps(iv, -1, 6) == [(-1, 0), (3, 5)]
