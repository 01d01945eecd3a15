"""Observability is pure reads: obs-on == obs-off bit-exactness.

The contract from docs/observability.md — enabling any subset of
tracer / metrics / ledger cannot perturb a single float of the DES
timeline.  Pinned here across the PR-8 discipline parity grid
(vectorized round kernel), the SoA async kernel (whose bulk-emitted
spans must also equal the per-object engine's eagerly-emitted ones),
the shared-medium FederationClock, and a kill/resume run whose restored
trace must be JSON-identical to an uninterrupted one."""
import json

import numpy as np
import pytest

from conftest import tiny
from repro.core.cost_model import StepTimes
from repro.data import make_emotion_dataset
from repro.fed import (ClockConfig, FedRunConfig, FederationClock, ObsConfig,
                       PAPER_CLIENTS, Simulator)
from repro.fed.engine import Job
from repro.fed.population import JobArrays, vectorized_round
from repro.fed.population_async import run_async_vectorized
from repro.net import ConstantLink, NetworkPlane
from repro.obs import MemoryLedger, MetricsRegistry, Observability, Tracer

N = 10


def _jobs(seed):
    rng = np.random.default_rng(seed)
    return [Job(uid=u, t_f=float(rng.uniform(0.2, 2.0)),
                t_fc=float(rng.uniform(0.1, 1.0)),
                t_s=float(rng.uniform(0.3, 1.5)),
                t_bc=float(rng.uniform(0.1, 1.0)),
                t_b=float(rng.uniform(0.2, 1.0)),
                arrival=float(rng.uniform(0.0, 0.5)),
                priority=float(rng.uniform(0.0, 3.0)),
                fc_bytes=float(rng.uniform(1e5, 5e6)),
                bc_bytes=float(rng.uniform(1e5, 5e6)))
            for u in range(N)]


def _rates():
    return np.random.default_rng(99).uniform(20.0, 120.0, N)


def _plane(kind):
    if kind == "none":
        return None
    if kind == "constant":
        return NetworkPlane([ConstantLink(r) for r in _rates()])
    return NetworkPlane([ConstantLink(r) for r in _rates()],
                        shared=True, capacity_mbps=150.0)


def _full_obs(n=N):
    return Observability(
        tracer=Tracer(), metrics=MetricsRegistry(),
        ledger=MemoryLedger(np.full(n, 100.0), np.ones(n), np.ones(n),
                            50.0, local_baseline=1000.0))


def _same_result(a, b, ctx):
    assert a.round_time == b.round_time, ctx
    assert a.completion == b.completion, ctx
    assert a.waits == b.waits, ctx
    assert a.dropped == b.dropped, ctx
    assert a.events == b.events, ctx
    assert [(r.uids, r.start, r.end) for r in a.service] \
        == [(r.uids, r.start, r.end) for r in b.service], ctx


def _span_keys(tr):
    """Order-independent span identity: exact floats, no rounding."""
    return sorted((s.name, s.cat, s.t_start, s.t_end, s.track)
                  for s in tr.spans())


# ---------------------------------------------------------------------------
# vectorized round kernel — the PR-8 discipline grid
# ---------------------------------------------------------------------------

def test_vectorized_round_obs_is_pure_representative():
    """Tier-1 anchor: the live-plane "bw" discipline on a shared medium —
    the obs hooks' busiest path.  The policy x plane grid carries
    ``slow`` below."""
    test_vectorized_round_obs_is_pure("bw", "shared")


@pytest.mark.slow
@pytest.mark.parametrize("plane_kind", ["none", "constant", "shared"])
@pytest.mark.parametrize("policy", ["fifo", "wf", "priority", "bw"])
def test_vectorized_round_obs_is_pure(policy, plane_kind):
    jobs = _jobs(7)
    arrays = JobArrays.from_jobs(jobs)
    for slots, chunk, deadline in ((1, 1, None), (3, 2, 6.0)):
        kw = dict(policy=policy, slots=slots, cohort_chunk=chunk,
                  chunk_efficiency=0.8, deadline=deadline)
        off = vectorized_round(arrays, network=_plane(plane_kind), **kw)
        obs = _full_obs()
        on = vectorized_round(arrays, network=_plane(plane_kind), obs=obs,
                              rnd=3, **kw)
        _same_result(off, on, (policy, plane_kind, slots, chunk, deadline))
        n_served = len(on.completion)
        assert obs.metrics.hist_stats("queue_wait")["count"] == n_served
        served_spans = [s for s in obs.tracer.spans() if s.name == "bwd"]
        assert len(served_spans) == n_served
        for u in on.completion:
            assert obs.ledger.peak_memory(u) > 100.0   # act span recorded


# ---------------------------------------------------------------------------
# SoA async kernel — pure, and bulk spans == per-object engine spans
# ---------------------------------------------------------------------------

def _times(seed):
    rng = np.random.default_rng(seed)
    return {k: rng.uniform(*r, N) for k, r in (
        ("t_f", (0.2, 2.0)), ("t_fc", (0.1, 1.0)), ("t_s", (0.3, 1.5)),
        ("t_bc", (0.1, 1.0)), ("t_b", (0.2, 1.0)),
        ("fc_bytes", (1e5, 5e6)), ("bc_bytes", (1e5, 5e6)))}


def test_async_kernel_obs_is_pure_representative():
    """Tier-1 anchor: staleness aggregation under the wf heap; the
    two-cell grid carries ``slow`` below."""
    test_async_kernel_obs_is_pure_and_matches_engine("wf", "staleness")


@pytest.mark.slow
@pytest.mark.parametrize("policy,agg", [("fifo", "buffered"),
                                        ("wf", "staleness")])
def test_async_kernel_obs_is_pure_and_matches_engine(policy, agg):
    times = _times(11)
    rates = _rates()
    cfg = ClockConfig(policy=policy, slots=2, cohort_chunk=2,
                      chunk_efficiency=0.9, agg_policy=agg, agg_interval=1,
                      buffer_k=3, max_inflight_rounds=2)
    off, _ = run_async_vectorized(times, 2, cfg, up_rate_mbps=rates,
                                  down_rate_mbps=rates)
    obs_vec = Observability(tracer=Tracer(), metrics=MetricsRegistry())
    on, _ = run_async_vectorized(times, 2, cfg, up_rate_mbps=rates,
                                 down_rate_mbps=rates, obs=obs_vec)
    assert on.makespan == off.makespan
    assert on.serves == off.serves
    assert on.commits == off.commits
    assert on.events == off.events

    # the kernel's bulk-reconstructed spans equal the per-object engine's
    # eagerly-emitted ones, float for float
    st = [StepTimes(**{k: float(times[k][u]) for k in times})
          for u in range(N)]
    obs_obj = Observability(tracer=Tracer(), metrics=MetricsRegistry())
    clock = FederationClock(
        N, 2, cfg, times_fn=lambda u, r: st[u],
        network=NetworkPlane([ConstantLink(float(r)) for r in rates]),
        obs=obs_obj)
    res = clock.run()
    assert res.makespan == on.makespan
    assert _span_keys(obs_vec.tracer) == _span_keys(obs_obj.tracer)
    sv, so = obs_vec.metrics.summary(), obs_obj.metrics.summary()
    assert sv["counters"] == so["counters"]
    assert sv["histograms"].keys() == so["histograms"].keys()
    for k, hv in sv["histograms"].items():
        ho = so["histograms"][k]
        assert hv["count"] == ho["count"], k
        assert hv["min"] == ho["min"] and hv["max"] == ho["max"], k
        np.testing.assert_allclose(hv["sum"], ho["sum"], rtol=1e-12)


def test_engine_obs_is_pure_on_shared_medium():
    """Shared cells route through the mark/close table and emit occupancy
    counters — still zero timeline perturbation."""
    times = _times(21)
    st = [StepTimes(**{k: float(times[k][u]) for k in times})
          for u in range(N)]
    cfg = ClockConfig(policy="fifo", slots=2, agg_policy="buffered",
                      agg_interval=1, buffer_k=4, max_inflight_rounds=2)

    def run(obs):
        plane = NetworkPlane([ConstantLink(float(r)) for r in _rates()],
                             shared=True, capacity_mbps=150.0)
        clock = FederationClock(N, 2, cfg, times_fn=lambda u, r: st[u],
                                network=plane, obs=obs)
        return clock.run()

    off = run(None)
    obs = _full_obs()
    on = run(obs)
    assert on.makespan == off.makespan
    assert on.events == off.events
    assert on.serves == off.serves
    assert on.commits == off.commits
    assert obs.tracer.n_counters > 0          # cell occupancy samples
    assert obs.metrics.counter_value("cell_transfers") > 0
    assert not obs._marks                     # every transfer closed


# ---------------------------------------------------------------------------
# kill / resume trace continuity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim_setup():
    cfg = tiny("bert-base", n_layers=3, d_model=128)
    cfg = cfg.with_(vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(100, seq_len=16, vocab_size=4096, seed=1)
    return cfg, train, test


def test_kill_resume_trace_continuity(sim_setup, tmp_path):
    """A run killed mid-flight and resumed from its snapshot produces a
    trace / metrics / ledger JSON-identical to the uninterrupted run —
    including open shared-medium marks restored across the boundary."""
    cfg, train, test = sim_setup

    def mk(**extra):
        rc = FedRunConfig(scheme="ours", rounds=3, agg_interval=1,
                          batch_size=4, seq_len=16, lr=3e-3, eval_every=100,
                          engine="event", scheduler="fifo",
                          agg_policy="staleness", max_inflight_rounds=2,
                          staleness_alpha=0.5, shared_medium=True,
                          medium_capacity_mbps=150.0, agg_transport="plane",
                          obs=ObsConfig(trace=True, metrics=True,
                                        memory_ledger=True), **extra)
        return Simulator(cfg, PAPER_CLIENTS[:4], [1, 1, 1, 1],
                         train, test, rc)

    ref = mk()
    ref.run_training()
    span = ref._clock.now

    snap_dir = str(tmp_path / "snaps")
    killed = mk(snapshot_every=span / 7, snapshot_dir=snap_dir,
                preempt_at=span * 0.6)
    killed.run_training()
    assert killed.clock_result.preempted

    resumed = mk(resume_from=snap_dir)
    resumed.run_training()
    assert not resumed.clock_result.preempted
    assert json.dumps(resumed.obs.tracer.to_chrome(), sort_keys=True) == \
        json.dumps(ref.obs.tracer.to_chrome(), sort_keys=True)
    assert resumed.obs.metrics.summary() == ref.obs.metrics.summary()
    assert resumed.obs.ledger.report() == ref.obs.ledger.report()


def test_resume_into_obs_off_run_is_allowed(sim_setup, tmp_path):
    """obs is popped from the config fingerprint: a snapshot written with
    tracing on resumes into an obs-off run (and vice versa) — the
    timeline is the same either way."""
    cfg, train, test = sim_setup

    def mk(obs, **extra):
        rc = FedRunConfig(scheme="ours", rounds=2, agg_interval=1,
                          batch_size=4, seq_len=16, lr=3e-3, eval_every=100,
                          engine="event", scheduler="fifo",
                          agg_policy="buffered", agg_buffer_k=2,
                          max_inflight_rounds=2, obs=obs, **extra)
        return Simulator(cfg, PAPER_CLIENTS[:4], [1, 1, 1, 1],
                         train, test, rc)

    ref = mk(ObsConfig())
    ref.run_training()
    span = ref._clock.now

    snap_dir = str(tmp_path / "snaps")
    killed = mk(ObsConfig(trace=True, metrics=True),
                snapshot_every=span / 5, snapshot_dir=snap_dir,
                preempt_at=span * 0.5)
    killed.run_training()
    assert killed.clock_result.preempted

    resumed = mk(ObsConfig(), resume_from=snap_dir)
    resumed.run_training()
    assert resumed.obs is None
    assert [r.sim_time_s for r in resumed.history] == \
        [r.sim_time_s for r in ref.history]
    assert resumed.loss_events == ref.loss_events
