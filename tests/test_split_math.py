"""The one per-cut cache of ``fed.split_math.SplitMath``: clients at one cut
share one frozen client prefix, in both real-math drivers, and a client
that migrates to another cut joins that cut's prefix."""
import types

import pytest

from conftest import tiny
from repro.data import make_emotion_dataset
from repro.fed import (PAPER_CLIENTS, AggConfig, EngineConfig, FedRunConfig,
                       Simulator)
from repro.fed.fleet import FleetSpec
from repro.fed.population_training import PopulationTrainer
from test_commit_fused import CUTS, MoveOne


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("bert-base", n_layers=4, d_model=128).with_(
        vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    return cfg, train


def _record_prefixes(math):
    """Log ``(cut, id of the prefix)`` of every client forward."""
    seen, at_cut = [], math.at_cut

    def recording(cut):
        steps = at_cut(cut)

        def fwd(prefix, lora, batch):
            seen.append((cut, id(prefix["layers"])))
            return steps.client_fwd(prefix, lora, batch)
        return steps._replace(client_fwd=fwd)

    math.at_cut = recording
    return seen


def _one_prefix_per_cut(seen, cuts):
    by_cut = {}
    for cut, ident in seen:
        by_cut.setdefault(cut, set()).add(ident)
    assert sorted(by_cut) == sorted(set(cuts))
    assert all(len(ids) == 1 for ids in by_cut.values()), by_cut
    ids = [next(iter(v)) for v in by_cut.values()]
    assert len(set(ids)) == len(ids)
    return {cut: next(iter(v)) for cut, v in by_cut.items()}


def test_simulator_clients_share_one_prefix_per_cut(setup):
    cfg, train = setup
    run = FedRunConfig(scheme="ours", rounds=2, batch_size=4, seq_len=16,
                       eval_every=100, seed=3, agg=AggConfig(interval=5))
    sim = Simulator(cfg, list(PAPER_CLIENTS), list(CUTS), train, None, run)
    assert sorted(sim.math.per_cut) == sorted(set(CUTS))
    seen = _record_prefixes(sim.math)
    sim.run_round(0)
    assert len(seen) == len(CUTS)
    before = _one_prefix_per_cut(seen, CUTS)
    # client 0 migrates from cut 1 to cut 3 at a commit
    sim._control = MoveOne(sim.cuts, 0, 3)
    sim._commit_sync(types.SimpleNamespace(time=0.0, version=1))
    seen.clear()
    sim.run_round(1)
    assert sorted(c for c, _ in seen) == [1, 2, 2, 3, 3, 3]
    after = _one_prefix_per_cut(seen, sim.cuts)
    assert after == before          # nothing was re-sliced


@pytest.mark.parametrize("chunk", [1, 2])
def test_trainer_clients_share_one_prefix_per_cut(setup, chunk):
    cfg, train = setup
    spec = FleetSpec(n=6, seed=3, link_model="constant")
    run = FedRunConfig(scheme="ours", rounds=1, batch_size=4, seq_len=16,
                       eval_every=100, seed=3,
                       engine=EngineConfig(mode="event", cohort_chunk=chunk))
    tr = PopulationTrainer(cfg, spec.population(), run, train)
    cuts = [int(c) for c in spec.population().cuts]
    seen = _record_prefixes(tr.math)
    for lo in range(0, spec.n, chunk):
        tr.on_sync_serve(list(range(lo, lo + chunk)), 0, 0.0)
    assert len(seen) == spec.n
    _one_prefix_per_cut(seen, cuts)
    assert sorted(tr.math.per_cut) == sorted(set(cuts))
