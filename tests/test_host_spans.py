"""Host spans on the profiler's clock (``repro.obs.host``): the span tree
that ``Simulator`` emits into a ``jax.profiler`` trace, its names against
the ``HOST_SPANS`` taxonomy, and that recording them changes no number."""
import pathlib

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.data import make_emotion_dataset
from repro.fed import (PAPER_CLIENTS, AggConfig, EngineConfig, FedRunConfig,
                       Simulator)
from repro.obs.host import HOST_SPANS

PREFIXES = ("fed.", "commit.")
SERVE_CHILDREN = ["fed.client_fwd", "fed.server_step", "fed.loss_read",
                  "fed.client_bwd"]
COMMIT_CHILDREN = ["commit.aggregate", "commit.redistribute", "commit.heads",
                   "commit.opt_reset"]


class Node:
    def __init__(self, start, end, name, args):
        self.start, self.end, self.name, self.args = start, end, name, args
        self.children = []

    def named(self, name):
        return [c for c in self.children if c.name == name]


def _tree(events):
    """Roots of the program spans, each with its nested children (build
    it once per list of events: it fills their ``children``)."""
    roots, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= ev.start:
            stack.pop()
        (stack[-1].children if stack else roots).append(ev)
        stack.append(ev)
    return roots


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; the program spans it recorded."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    xplane = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Node(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("bert-base", n_layers=3, d_model=128).with_(
        vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(32, seq_len=16, vocab_size=4096, seed=1)
    return cfg, train, test


def _sim(setup, **engine):
    cfg, train, test = setup
    agg = engine.pop("agg", AggConfig(interval=2))
    run = FedRunConfig(scheme="ours", rounds=2, batch_size=4, seq_len=16,
                       lr=3e-3, eval_every=100, seed=5, agg=agg,
                       engine=EngineConfig(**engine))
    return Simulator(cfg, list(PAPER_CLIENTS[:3]), [1, 2, 2], train, test, run)


def _rounds(sim, n=2):
    for r in range(n):
        sim.run_round(r)


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    sim = _sim(setup)
    events = _profiled(tmp_path_factory.mktemp("prof"), lambda: _rounds(sim))
    return sim, events


def test_round_serve_and_commit_tree(traced):
    _, events = traced
    roots = _tree(events)
    assert [r.name for r in roots] == ["fed.round", "fed.round"]
    for rnd, root in enumerate(roots):
        assert root.args["round"] == rnd
        serves = root.named("fed.serve")
        assert sorted(s.args["uid"] for s in serves) == [0, 1, 2]
        for s in serves:
            assert s.args["round"] == rnd
            assert [c.name for c in s.children] == SERVE_CHILDREN
            assert s.children[0].args["uid"] == s.args["uid"]
            assert s.children[3].args["uid"] == s.args["uid"]
        commits = root.named("fed.commit")
        # agg_interval 2: the second round commits, the first does not
        assert len(commits) == (1 if rnd == 1 else 0)
        assert {c.name for c in root.children} == (
            {"fed.serve", "fed.commit"} if rnd == 1 else {"fed.serve"})
    commit = roots[1].named("fed.commit")[0]
    assert commit.args == {"round": 1, "clients": 3}
    assert [c.name for c in commit.children] == COMMIT_CHILDREN
    server_step = roots[0].named("fed.serve")[0].children[1]
    assert server_step.args["cut"] in (1, 2)


def test_every_span_name_is_in_the_taxonomy(traced):
    _, events = traced
    names = {e.name for e in events}
    assert names <= set(HOST_SPANS)
    assert names == set(HOST_SPANS)     # the analytic round emits them all


def test_profiling_changes_no_number(setup, traced):
    sim_on, _ = traced
    sim_off = _sim(setup)
    _rounds(sim_off)
    assert [h.mean_loss for h in sim_on.history] == \
        [h.mean_loss for h in sim_off.history]
    for attr in ("client_lora", "server_lora", "heads"):
        on, off = getattr(sim_on, attr), getattr(sim_off, attr)
        for a, b in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_event_engine_serves_and_commits_in_spans(setup, tmp_path):
    sim = _sim(setup, mode="event", cohort_chunk=2,
               agg=AggConfig(policy="buffered", interval=1, buffer_k=2))
    events = _profiled(tmp_path, sim.run_training)
    assert {e.name for e in events} <= set(HOST_SPANS)
    roots = _tree(events)
    serves = [e for e in roots if e.name == "fed.serve"]
    assert len(serves) == 6     # three clients, two rounds each
    for s in serves:
        assert [c.name for c in s.children] == SERVE_CHILDREN
    commits = [e for e in roots if e.name == "fed.commit"]
    assert commits
    for c in commits:
        assert "version" in c.args and c.args["clients"] >= 1
        assert [k.name for k in c.children] == [
            "commit.aggregate", "commit.heads", "commit.redistribute",
            "commit.opt_reset"]


def test_batched_group_is_one_serve(setup, tmp_path):
    sim = _sim(setup, cohort_chunk=3)
    events = _profiled(tmp_path, lambda: _rounds(sim, 1))
    (root,) = _tree(events)
    (serve,) = root.named("fed.serve")
    uids = [int(u) for u in str(serve.args["uids"]).split()]
    assert sorted(uids) == [0, 1, 2] and serve.args["round"] == 0
    assert [c.name for c in serve.children] == (
        ["fed.client_fwd"] * 3 + ["fed.server_step"]
        + ["fed.loss_read", "fed.client_bwd"] * 3)
    assert [c.args["uid"] for c in serve.named("fed.client_fwd")] == uids
    assert serve.named("fed.server_step")[0].args["cut"] == " ".join(
        str(sim.cuts[u]) for u in uids)
