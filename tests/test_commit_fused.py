"""The sync commit as jitted programs (``core/aggregation.commit_*``):
``Simulator._commit_sync`` against the eager reference sequence
(``aggregation_round`` + head FedAvg + ``opt.init``), with and without a
cut migration at the commit, and its dispatch count in a profiled commit."""
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core import aggregation as agg
from repro.core import lora as lora_lib
from repro.core.cost_model import lora_upload_bytes
from repro.data import make_emotion_dataset
from repro.fed import PAPER_CLIENTS, AggConfig, FedRunConfig, Simulator

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:       # ``bench`` is imported from the root
    sys.path.insert(0, str(ROOT))

CUTS = (1, 1, 2, 2, 3, 3)
SIZES = [5, 17, 3, 40, 11, 29]
# relative to each leaf's largest entry: the fused program may contract a
# multiply-add into one rounding, so entries where the weighted terms cancel
# differ from the op-by-op sum in the last bit of the terms, not of the sum
RTOL = 1e-6


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("bert-base", n_layers=4, d_model=128).with_(
        vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    return cfg, train


def _sim(setup):
    """Six clients whose adapters, heads and optimizer moments all differ
    (the server trees nonzero below their cuts too, which the commit must
    ignore), with unequal data sizes."""
    cfg, train = setup
    run = FedRunConfig(scheme="ours", rounds=1, batch_size=4, seq_len=16,
                       eval_every=100, seed=3, agg=AggConfig(interval=1))
    sim = Simulator(cfg, list(PAPER_CLIENTS), list(CUTS), train, None, run)
    sim.data_sizes = list(SIZES)
    key = jax.random.PRNGKey(11)

    def noise(tree):
        nonlocal key
        leaves, treedef = jax.tree.flatten(tree)
        out = []
        for leaf in leaves:
            key, sub = jax.random.split(key)
            out.append(jax.random.normal(sub, leaf.shape, leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    for u in range(sim.u):
        slot = sim.store.slot(u)
        for name in ("client_lora", "server_lora", "head"):
            slot[name] = noise(slot[name])
        for name in ("client_opt", "server_opt"):
            slot[name] = slot[name]._replace(mu=noise(slot[name].mu))
    return sim


def _eager(sim, new_cuts):
    """Today's commit, op by op: aggregate at the clients' cuts, re-split
    at ``new_cuts``, FedAvg the heads, reset every optimizer."""
    servers = [lora_lib.split_lora(s, k)[1]
               for s, k in zip(sim.server_lora, sim.cuts)]
    _, _, agg_full = agg.aggregation_round(sim.client_lora, servers,
                                           sim.cuts, sim.data_sizes)
    parts = [lora_lib.split_lora(agg_full, k) for k in new_cuts]
    clients = [c for c, _ in parts]
    servers = [lora_lib.embed_in_full_shape(s, sim.math.lora_spec, k, "server")
               for (_, s), k in zip(parts, new_cuts)]
    w = np.array(sim.data_sizes, np.float64)
    w /= w.sum()
    head = jax.tree.map(lambda *hs: sum(float(wi) * h for wi, h in zip(w, hs)),
                        *sim.heads)
    return {"client_lora": clients, "server_lora": servers,
            "heads": [head] * sim.u, "_global_full": agg_full,
            "_global_head": head,
            "client_opt": [sim.opt.init(c) for c in clients],
            "server_opt": [sim.opt.init({"lora": s, "head": head})
                           for s in servers]}


def _check(sim, want):
    for name in ("client_lora", "server_lora", "heads", "_global_full",
                 "_global_head"):
        got = getattr(sim, name)
        assert jax.tree.structure(got) == jax.tree.structure(want[name]), name
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want[name])):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            b = np.asarray(b)
            np.testing.assert_allclose(np.asarray(a), b, rtol=RTOL,
                                       atol=RTOL * np.abs(b).max(),
                                       err_msg=name)
    for name in ("client_opt", "server_opt"):
        got = getattr(sim, name)
        assert jax.tree.structure(got) == jax.tree.structure(want[name]), name
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want[name])):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert not np.any(np.asarray(a)), name
    # the shapes the steps and the harness read
    for u, cut in enumerate(sim.cuts):
        assert jax.tree.leaves(sim.client_lora[u])[0].shape[0] == cut
        assert set(sim.client_opt[u].mu) == {"layers"}
        assert set(sim.server_opt[u].mu) == {"lora", "head"}
        assert "layers" in sim.server_opt[u].mu["lora"]


def test_commit_matches_the_eager_sequence(setup):
    sim = _sim(setup)
    want = _eager(sim, CUTS)
    assert sim._commit_sync(None) == max(
        2 * sim.link.transfer_s(lora_upload_bytes(sim.cfg, k)) for k in CUTS)
    assert tuple(sim.cuts) == CUTS
    _check(sim, want)
    # one re-split and one fresh state per cut, shared by its clients
    assert len({id(c) for c in sim.client_lora}) == len(set(CUTS))
    assert len({id(o) for o in sim.client_opt}) == len(set(CUTS))
    assert len({id(o) for o in sim.server_opt}) == 1


class MoveOne:
    """A control loop that moves one client's cut at the first commit, as
    ``ControlLoop.decide`` does: in the live cuts list, returning the
    change and its migration charge."""

    def __init__(self, cuts, uid, new):
        self.cuts, self.uid, self.new = cuts, uid, new

    def decide(self, t, uids, version):
        old = self.cuts[self.uid]
        self.cuts[self.uid] = self.new
        return {self.uid: (old, self.new)}, {self.uid: 0.25}


def test_a_cut_migration_resplits_at_the_new_cut(setup):
    sim = _sim(setup)
    new_cuts = list(CUTS)
    new_cuts[0] = 3
    want = _eager(sim, new_cuts)     # aggregated at the OLD cuts
    sim._control = MoveOne(sim.cuts, 0, 3)
    charge = sim._commit_sync(types.SimpleNamespace(time=0.0, version=1))
    assert sim.cuts == new_cuts
    assert set(charge) == set(range(sim.u)) and charge[0] > charge[1]
    assert 3 in sim.math.per_cut
    _check(sim, want)
    prefix = sim.math.at_cut(sim.cuts[0]).prefix
    assert jax.tree.leaves(prefix["layers"])[0].shape[0] == 3


def test_a_commit_is_four_dispatches(setup, tmp_path):
    """Profiled on the CPU and reduced by ``bench/spans.py`` as the chip's
    trace is: one outermost ``PjitFunction`` dispatch in each ``commit.*``
    span, four in ``fed.commit``."""
    from bench.spans import reduce_spans
    sim = _sim(setup)
    sim._commit_sync(None)            # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sim._commit_sync(None)
        jax.block_until_ready((sim.client_lora, sim.server_opt))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    xplane = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    host = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if any(n == "fed.commit" for _, _, n in evs):
                host += evs
    lo = min(s for s, _, _ in host)
    hi = max(e for _, e, _ in host)
    sp = reduce_spans(host, [[]], lo, hi)
    assert sp["fed.commit"]["count"] == 1
    assert sp["fed.commit"]["dispatches"] == 4
    for part in ("commit.aggregate", "commit.redistribute", "commit.heads",
                 "commit.opt_reset"):
        assert sp[part]["dispatches"] == 1, part
