"""Where the host reads the clients' losses: a serve only starts each
loss's copy to the host, and the analytic round reads them all once, after
its commit's dispatch.  The values are pinned bit for bit to those of the
per-client blocking read this replaced."""
import jax
import numpy as np
import pytest

from conftest import tiny
from repro.data import make_emotion_dataset
from repro.fed import (PAPER_CLIENTS, AggConfig, EngineConfig, FedRunConfig,
                       ObsConfig, Simulator)

# each serve's (uid, loss) and each round's mean_loss over three rounds
# with a commit after the second, read from the blocking-read driver
PINNED = {
    1: ([[(0, 2.120173931121826)], [(1, 1.4761943817138672)],
         [(2, 1.9816890954971313)], [(0, 1.8753302097320557)],
         [(1, 2.1095662117004395)], [(2, 2.4720089435577393)],
         [(0, 0.9024649858474731)], [(1, 3.55891752243042)],
         [(2, 2.0632100105285645)]],
        [1.859352469444275, 2.152301788330078, 2.174864172935486]),
    3: ([[(0, 2.120173931121826), (1, 1.4761943817138672),
          (2, 1.9816889762878418)],
         [(0, 1.8753302097320557), (1, 2.1095657348632812),
          (2, 2.47200870513916)],
         [(0, 0.9024648666381836), (1, 3.55891752243042),
          (2, 2.0632100105285645)]],
        [1.859352429707845, 2.152301549911499, 2.174864133199056]),
}
PINNED_EVENTS = [
    (0.0027273369195003052, 2, 0, 1.9816890954971313),
    (0.0027550219509540137, 1, 0, 1.4761943817138672),
    (0.002811599909881594, 0, 0, 2.120173931121826),
    (0.013607180364188657, 2, 1, 2.105170488357544),
    (0.013634865395642364, 1, 1, 3.1400980949401855),
    (0.02448823170489717, 0, 1, 2.4896843433380127),
]
PINNED_EVENT_MEANS = [1.859352469444275, 2.6226342916488647,
                      2.4896843433380127]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("bert-base", n_layers=3, d_model=128).with_(
        vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(32, seq_len=16, vocab_size=4096, seed=1)
    return cfg, train, test


def _sim(setup, rounds=3, agg=None, obs=None, **engine):
    cfg, train, test = setup
    run = FedRunConfig(scheme="ours", rounds=rounds, batch_size=4,
                       seq_len=16, lr=3e-3, eval_every=100, seed=5,
                       agg=agg or AggConfig(interval=2),
                       engine=EngineConfig(**engine), obs=obs or ObsConfig())
    return Simulator(cfg, list(PAPER_CLIENTS[:3]), [1, 2, 2], train, test, run)


def _served(sim):
    """Record each group's ``[(uid, returned loss)]`` as ``_serve_group``
    returns it."""
    served, serve = [], sim._serve_group

    def recording(grp):
        out = serve(grp)
        served.append(list(zip(grp, out)))
        return out

    sim._serve_group = recording
    return served


class _Watched:
    """A loss that logs every read of its value into ``log``."""

    def __init__(self, arr, log):
        self.arr, self.log = arr, log

    def __getitem__(self, i):
        return _Watched(self.arr[i], self.log)

    def copy_to_host_async(self):
        self.arr.copy_to_host_async()

    def is_ready(self):
        return self.arr.is_ready()

    def __float__(self):
        self.log.append("read")
        return float(self.arr)

    def __array__(self, *args, **kwargs):
        self.log.append("read")
        return np.asarray(self.arr, *args, **kwargs)


def _watch(sim, log):
    """Wrap the server steps so their losses log each read, and log each
    serve's and commit's return."""
    def watched(step):
        def call(*args):
            loss, *rest = step(*args)
            return (_Watched(loss, log), *rest)
        return call

    math = sim.math
    for c, steps in list(math.per_cut.items()):
        math.per_cut[c] = steps._replace(
            server_step=watched(steps.server_step))
    math._srv_step_batched = watched(math._srv_step_batched)
    serve, commit = sim._serve_group, sim._commit_sync

    def serving(grp):
        out = serve(grp)
        log.append("serve")
        return out

    def committing(ev):
        out = commit(ev)
        log.append("commit")
        return out

    sim._serve_group, sim._commit_sync = serving, committing


def _simulator_serves(setup, chunk):
    sim = _sim(setup, cohort_chunk=chunk)
    served = _served(sim)
    sim.run_round(0)
    return served


def _trainer_serves(setup, chunk):
    """The population trainer's serve (``SplitMath.serve``) on the same
    three-client fleet, one round's groups."""
    from repro.fed.fleet import FleetSpec
    from repro.fed.population_training import PopulationTrainer
    cfg, train, test = setup
    run = FedRunConfig(scheme="ours", rounds=1, batch_size=4, seq_len=16,
                       lr=3e-3, eval_every=100, seed=5,
                       engine=EngineConfig(mode="event", cohort_chunk=chunk))
    tr = PopulationTrainer(
        cfg, FleetSpec(n=3, seed=5, link_model="constant").population(),
        run, train, test)
    groups = [[0], [1], [2]] if chunk == 1 else [[0, 1, 2]]
    return [list(zip(g, tr.math.serve(g, tr._loader, 0))) for g in groups]


@pytest.mark.parametrize("driver,chunk", [
    pytest.param("simulator", 1, id="1"),
    pytest.param("simulator", 3, id="3"),
    pytest.param("trainer", 1, id="trainer-1"),
    pytest.param("trainer", 3, id="trainer-3")])
def test_serve_returns_unread_device_scalars(setup, driver, chunk):
    serves = {"simulator": _simulator_serves, "trainer": _trainer_serves}
    served = serves[driver](setup, chunk)
    assert [len(g) for g in served] == ([1, 1, 1] if chunk == 1 else [3])
    for grp in served:
        for _, loss in grp:
            assert isinstance(loss, jax.Array) and loss.shape == ()


@pytest.mark.parametrize("chunk", [1, 3])
def test_no_loss_is_read_before_the_commit_dispatch(setup, chunk):
    sim = _sim(setup, cohort_chunk=chunk)
    log = []
    _watch(sim, log)
    per_round = []
    for r in range(3):
        log.clear()
        sim.run_round(r)
        per_round.append(list(log))
    serves = ["serve"] * (3 if chunk == 1 else 1)
    reads = ["read"] * 3
    # agg interval 2: the second round commits, before any of its reads
    assert per_round == [serves + reads, serves + ["commit"] + reads,
                         serves + reads]


@pytest.mark.parametrize("chunk", [1, 3])
def test_losses_are_bitwise_those_of_the_blocking_read(setup, chunk):
    sim = _sim(setup, cohort_chunk=chunk)
    served = _served(sim)
    for r in range(3):
        sim.run_round(r)
    losses, means = PINNED[chunk]
    assert [[(u, float(v)) for u, v in g] for g in served] == losses
    assert [h.mean_loss for h in sim.history] == means


def test_event_engine_loss_events_are_pinned_floats(setup):
    sim = _sim(setup, rounds=2, mode="event", cohort_chunk=2,
               agg=AggConfig(policy="buffered", interval=1, buffer_k=2))
    sim.run_training()
    assert sim.loss_events == PINNED_EVENTS
    assert all(type(e[3]) is float for e in sim.loss_events)
    assert [h.mean_loss for h in sim.history] == PINNED_EVENT_MEANS


@pytest.mark.parametrize("chunk", [1, 3])
def test_loss_read_counters(setup, chunk):
    sim = _sim(setup, cohort_chunk=chunk, obs=ObsConfig(metrics=True))
    for r in range(3):
        sim.run_round(r)
    m = sim.obs.metrics
    reads, waited = m.counter_value("loss_reads"), m.counter_value(
        "loss_reads_waited")
    assert reads == 9           # three clients, three rounds
    assert 0 <= waited <= reads
    assert sim.obs_other_data()["metrics"]["counters"].keys() == {
        "loss_reads", "loss_reads_waited"}


def test_counters_leave_the_numbers_unchanged(setup):
    off, on = _sim(setup), _sim(setup, obs=ObsConfig(metrics=True))
    for sim in (off, on):
        for r in range(3):
            sim.run_round(r)
    assert [h.mean_loss for h in on.history] == \
        [h.mean_loss for h in off.history] == PINNED[1][1]

