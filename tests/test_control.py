"""Adaptive control plane (repro/control): solver determinism + memory
repair, migration pricing, telemetry EWMAs, controller trigger policies,
engine-level static bit-parity, reactive-beats-static on a deterministic
deep fade, plane-routed aggregation, and the simulator-level knobs."""
import dataclasses

import numpy as np
import pytest

from conftest import tiny
from repro.configs import REGISTRY
from repro.control import (Assignment, ControlLoop, PeriodicController,
                           ReactiveController, StaticController,
                           TelemetryStore, make_controller, predicted_span,
                           solve_assignment)
from repro.core.cost_model import (StepTimes, LinkProfile, lora_upload_bytes,
                                   migration_bytes)
from repro.core.scheduling import refresh_priorities
from repro.data import make_emotion_dataset
from repro.fed import (ClockConfig, FedRunConfig, FederationClock,
                       PAPER_CLIENTS, RoundPlan, Simulator, jobs_from_times,
                       validate_run_config)
from repro.fed.devices import JETSON_NANO, SERVER
from repro.net import ConstantLink, NetworkPlane, TraceLink

CFG = REGISTRY["bert-base"]
RATE = 100.0


def _loaded_server(factor=8):
    return dataclasses.replace(SERVER, utilization=SERVER.utilization / factor)


# -- solver -------------------------------------------------------------------

def test_solver_deterministic_and_never_worse():
    devices = PAPER_CLIENTS
    base = Assignment.uniform([3] * 6, CFG.lora.rank, 16)
    rates = [100.0, 100.0, 5.0, 100.0, 40.0, 100.0]
    a1, s1 = solve_assignment(CFG, devices, _loaded_server(), rates, base, 128)
    a2, s2 = solve_assignment(CFG, devices, _loaded_server(), rates, base, 128)
    assert a1 == a2 and s1 == s2
    base_span = predicted_span(CFG, devices, _loaded_server(), rates, base, 128)
    assert s1 <= base_span + 1e-12


def test_solver_repairs_memory_infeasibility():
    """Zero headroom forces the cut down to min_cut even when the span
    worsens — memory is a hard constraint."""
    devices = [JETSON_NANO] * 2
    base = Assignment.uniform([3, 3], CFG.lora.rank, 16)
    asg, _ = solve_assignment(CFG, devices, SERVER, [RATE, RATE], base, 128,
                              mem_budget_bytes=[0.0, 1e18], min_cut=1)
    assert asg.cuts[0] == 1          # nothing fits: floor guarantee
    assert asg.cuts[1] >= 1


def test_solver_batch_moves_pay_their_throughput():
    """With healthy links, shrinking a batch shrinks the round span AND the
    data trained — the normalized objective must not reward it as a free
    win (cuts-only solution is not beaten by wholesale batch shrinking)."""
    devices = list(PAPER_CLIENTS[:4])
    base = Assignment.uniform([2] * 4, CFG.lora.rank, 16)
    rates = [RATE] * 4
    plain, s_plain = solve_assignment(CFG, devices, _loaded_server(), rates,
                                      base, 128)
    withb, s_withb = solve_assignment(CFG, devices, _loaded_server(), rates,
                                      base, 128, batch_candidates=(4, 8, 16))
    # the batch dimension may help, but never by simply dropping throughput:
    # normalized spans are comparable and the chosen batches stay sane
    assert s_withb <= s_plain + 1e-12
    assert all(b >= 4 for b in withb.batches)
    tiny_b = Assignment.uniform([2] * 4, CFG.lora.rank, 4)
    span_tiny = predicted_span(CFG, devices, _loaded_server(), rates, tiny_b,
                               128, ref_samples=sum(base.batches))
    raw_tiny = predicted_span(CFG, devices, _loaded_server(), rates, tiny_b,
                              128)
    assert span_tiny == pytest.approx(raw_tiny * 4.0)


def test_solver_rank_candidates_respected():
    base = Assignment.uniform([2] * 3, 8, 16)
    asg, _ = solve_assignment(CFG, PAPER_CLIENTS[:3], _loaded_server(),
                              [RATE] * 3, base, 128, rank_candidates=(4, 8))
    assert all(r in (4, 8) for r in asg.ranks)


# -- migration pricing --------------------------------------------------------

def test_migration_bytes_directions():
    down, up = migration_bytes(CFG, 1, 3)        # grow: weights+adapters down
    assert down > 0 and up == 0.0
    per_layer_adapters = lora_upload_bytes(CFG, 1)
    assert down > 2 * per_layer_adapters         # frozen weights dominate
    down2, up2 = migration_bytes(CFG, 3, 1)      # shrink: adapters up only
    assert down2 == 0.0 and up2 == pytest.approx(2 * per_layer_adapters)
    assert migration_bytes(CFG, 2, 2) == (0.0, 0.0)
    # growth monotone in the number of moved layers
    assert migration_bytes(CFG, 1, 4)[0] > down


# -- telemetry ----------------------------------------------------------------

def test_telemetry_ewma_and_memory_pressure():
    ts = TelemetryStore(CFG, 2, [RATE, RATE], [1e18, 1e18], alpha=0.5)
    ts.observe_rate(0, 50.0)
    assert ts.rate_mbps[0] == pytest.approx(75.0)   # 0.5*100 + 0.5*50
    ts.observe_transfer(0, 6.25e6, 1.0)             # realized 50 Mbps
    assert ts.rate_mbps[0] == pytest.approx(62.5)
    ts.observe_step(1, 2.0)
    ts.observe_step(1, 4.0)
    assert ts.step_s[1] == pytest.approx(3.0)
    assert ts.mem_headroom(0, 3, 16, 128) > 0
    ts.set_mem_budget(0, 1.0)                       # pressure event
    assert ts.mem_headroom(0, 1, 16, 128) < 0
    with pytest.raises(ValueError):
        TelemetryStore(CFG, 2, [RATE], [1e18, 1e18])


def test_telemetry_samples_plane_rates():
    plane = NetworkPlane([ConstantLink(40.0), ConstantLink(80.0)])
    ts = TelemetryStore(CFG, 2, [40.0, 80.0], [1e18] * 2, alpha=1.0)
    ts.sample_plane(plane, 3.0)
    assert ts.rate_mbps == [40.0, 80.0]


# -- controllers --------------------------------------------------------------

def _samples(ts, cuts, nominal):
    return [ts.snapshot(u, cuts[u], 16, 128, nominal[u])
            for u in range(len(cuts))]


def test_controller_policies():
    ts = TelemetryStore(CFG, 2, [RATE, RATE], [1e18] * 2, alpha=1.0)
    nominal = [RATE, RATE]

    static = StaticController()
    assert static.should_resolve(0.0, 1, _samples(ts, [2, 2], nominal)) is None

    per = PeriodicController(resolve_every=3)
    fires = [per.should_resolve(float(i), i, []) is not None
             for i in range(1, 10)]
    assert fires == [False, False, True] * 3

    rea = ReactiveController(hysteresis=0.25)
    # inside the band: no trigger
    assert rea.should_resolve(0.0, 1, _samples(ts, [2, 2], nominal)) is None
    ts.observe_rate(0, 50.0)                     # alpha=1 -> estimate 50
    trig = rea.should_resolve(1.0, 2, _samples(ts, [2, 2], nominal))
    assert trig.reason == "fade" and trig.uids == (0,)
    # baseline advances only for the re-planned clients
    rea.on_resolved(1.0, _samples(ts, [2, 2], nominal), [0])
    assert rea.should_resolve(2.0, 3, _samples(ts, [2, 2], nominal)) is None
    ts.observe_rate(0, 90.0)                     # recovery past +25% of 50
    trig = rea.should_resolve(3.0, 4, _samples(ts, [2, 2], nominal))
    assert trig.reason == "recovery" and trig.uids == (0,)
    # memory pressure outranks rate triggers and targets the squeezed client
    ts.set_mem_budget(1, 1.0)
    trig = rea.should_resolve(4.0, 5, _samples(ts, [2, 2], nominal))
    assert trig.reason == "memory" and trig.uids == (1,)

    with pytest.raises(KeyError):
        make_controller("bogus")
    with pytest.raises(ValueError):
        make_controller("reactive", hysteresis=0.0)
    with pytest.raises(ValueError):
        make_controller("periodic", resolve_every=0)


def test_refresh_priorities_in_place():
    pri = [0.0, 0.0]
    out = refresh_priorities(pri, [3, 1], [1.0, 2.0])
    assert out is pri and pri == [3.0, 0.5]


# -- engine: per-uid commit overheads ----------------------------------------

def test_commit_mapping_release_per_client():
    """A {uid: seconds} on_commit return delays each contributor by ITS
    charge: the cheap client re-enters earlier than the expensive one."""
    times = [StepTimes(t_f=0.1, t_fc=0.0, t_s=0.2, t_bc=0.0, t_b=0.1)] * 2
    def run(ret):
        clk = FederationClock(2, 2, ClockConfig(policy="fifo",
                                                agg_policy="buffered",
                                                buffer_k=2),
                              times_fn=lambda u, r: times[u])
        res = clk.run(on_commit=lambda ev: ret)
        return res
    flat = run(5.0)
    ragged = run({0: 5.0, 1: 0.0})
    assert ragged.makespan < flat.makespan
    assert ragged.commits[0].overhead == 5.0      # recorded as the max
    # second-round serve of the uncharged client starts before the charged
    # client's release
    starts = {}
    for ev in ragged.serves:
        for u, r in zip(ev.uids, ev.rounds):
            if r == 1:
                starts[u] = ev.start
    assert starts[1] < starts[0]


# -- engine-level static parity ----------------------------------------------

def test_static_control_loop_is_bitwise_noop():
    """Attaching a ControlLoop with the static controller must reproduce
    the bare clock's timeline bit-for-bit (engine-level PR-3 regression)."""
    devices = list(PAPER_CLIENTS[:5])
    cuts = [2, 1, 3, 2, 1]
    plane = NetworkPlane.constant(RATE, 5)
    loop = ControlLoop(CFG, devices, SERVER, plane, list(cuts), batch=16,
                       seq_len=128, controller="static")
    kw = dict(policy="priority", agg_policy="buffered", buffer_k=2,
              max_inflight_rounds=2)
    with_loop = FederationClock(5, 3, ClockConfig(**kw),
                                times_fn=loop.times_fn,
                                priorities=loop.pri,
                                network=plane).run(on_commit=loop.on_commit,
                                                   on_serve=loop.on_serve)
    from repro.core.scheduling import alg2_priorities
    times = [loop.times_fn(u) for u in range(5)]
    bare = FederationClock(5, 3, ClockConfig(**kw),
                           times_fn=lambda u, r: times[u],
                           priorities=alg2_priorities(cuts,
                                                      [d.tflops
                                                       for d in devices]),
                           network=NetworkPlane.constant(RATE, 5)).run()
    assert with_loop.makespan == bare.makespan
    assert with_loop.serves == bare.serves
    assert with_loop.events == bare.events
    assert [c.time for c in with_loop.commits] == \
           [c.time for c in bare.commits]
    assert loop.decisions == []


# -- reactive beats static on a deterministic deep fade ----------------------

def _fade_fleet():
    """Client 0's link collapses 100 -> 4 Mbps at t=5 and stays there;
    the rest are healthy.  Weak devices + a loaded server make the faded
    client's client-side tail worth shedding."""
    links = [TraceLink([0.0, 5.0], [RATE, 4.0])] + [ConstantLink(RATE)] * 3
    return [JETSON_NANO] * 4, NetworkPlane(links)


def _run_controlled(controller, **kw):
    devices, plane = _fade_fleet()
    loop = ControlLoop(CFG, devices, _loaded_server(), plane, [3] * 4,
                       batch=16, seq_len=128, controller=controller,
                       ewma_alpha=1.0, **kw)
    ccfg = ClockConfig(policy="priority", agg_policy="buffered",
                       buffer_k=2, max_inflight_rounds=1)
    clk = FederationClock(4, 6, ccfg, times_fn=loop.times_fn,
                          priorities=loop.pri, network=plane)
    res = clk.run(on_commit=loop.on_commit)
    return res, loop


def test_reactive_beats_static_on_deep_fade():
    static, _ = _run_controlled("static")
    reactive, loop = _run_controlled("reactive", hysteresis=0.25)
    assert reactive.makespan < static.makespan
    applied = [d for d in loop.decisions if d.applied]
    assert applied and all(list(d.cut_changes) == [0] for d in applied)
    assert loop.cuts[0] < 3                  # the faded client shed layers
    assert loop.cuts[1:] == [3, 3, 3]        # targeted: nobody else churned
    # migration was priced through the live (possibly faded) link
    for d in applied:
        assert d.migration_s[0] > 0.0


def test_memory_pressure_forces_shed():
    """Negative headroom migrates even when the span prediction says the
    move is not worth it."""
    devices, plane = _fade_fleet()
    loop = ControlLoop(CFG, devices, SERVER, plane, [3] * 4, batch=16,
                       seq_len=128, controller="reactive", ewma_alpha=1.0)
    loop.telemetry.set_mem_budget(2, 1.0)       # another app took the RAM
    changes, mig = loop.decide(1.0, [0, 1, 2, 3], 1)
    assert changes == {2: (3, 1)}
    assert loop.cuts == [3, 3, 1, 3]
    assert loop.decisions[-1].trigger == "memory"
    assert loop.decisions[-1].applied


# -- plane-routed aggregation -------------------------------------------------

def _sync_jobs(n=4):
    link = LinkProfile(RATE)
    nb = 2.5e6
    times = [StepTimes(t_f=0.1 * (u + 1), t_fc=link.transfer_s(nb), t_s=0.3,
                       t_bc=link.transfer_s(nb), t_b=0.2 * (u + 1),
                       fc_bytes=nb, bc_bytes=nb) for u in range(n)]
    return jobs_from_times(times, range(n))


def test_routed_sync_commit_hand_computed():
    """Dedicated constant links: the barrier resumes at
    round_end + slowest_upload + slowest_download."""
    jobs = _sync_jobs()
    agg_b = 5e5
    plane = NetworkPlane.constant(RATE, 4)
    legacy = FederationClock(4, 1, ClockConfig(agg_policy="sync",
                                               agg_interval=1),
                             network=plane)
    legacy.run(plan_fn=lambda r: RoundPlan(jobs=jobs, policy="fifo"))
    routed = FederationClock(4, 1, ClockConfig(agg_policy="sync",
                                               agg_interval=1),
                             network=plane, agg_bytes_fn=lambda u: agg_b)
    routed.run(plan_fn=lambda r: RoundPlan(jobs=jobs, policy="fifo"))
    xfer = agg_b * 8.0 / (RATE * 1e6)
    assert routed.now == pytest.approx(legacy.now + 2 * xfer, abs=1e-12)
    assert routed.commits[0].time == pytest.approx(legacy.now + xfer)


def test_routed_shared_medium_adapter_sync_contends():
    """Under a shared cell, the simultaneous adapter syncs of a barrier
    split the capacity — slower than dedicated links of the same rate."""
    jobs = _sync_jobs()
    agg_b = 5e5
    ded = NetworkPlane([ConstantLink(RATE)] * 4)
    sh = NetworkPlane([ConstantLink(RATE)] * 4, shared=True,
                      capacity_mbps=2 * RATE)
    spans = {}
    for name, plane in (("ded", ded), ("sh", sh)):
        clk = FederationClock(4, 1, ClockConfig(agg_policy="sync",
                                                agg_interval=1),
                              network=plane, agg_bytes_fn=lambda u: agg_b)
        clk.run(plan_fn=lambda r: RoundPlan(jobs=jobs, policy="fifo"))
        spans[name] = clk.now
    assert spans["sh"] > spans["ded"]


def test_routed_async_completes_and_is_slower_than_free():
    rng = np.random.default_rng(0)
    link = LinkProfile(RATE)
    times = []
    for _ in range(5):
        nb = 4e6 * rng.uniform(0.5, 1.5)
        t_f = rng.uniform(0.05, 0.3)
        times.append(StepTimes(t_f=t_f, t_fc=link.transfer_s(nb), t_s=0.4,
                               t_bc=link.transfer_s(nb), t_b=2 * t_f,
                               fc_bytes=nb, bc_bytes=nb))
    kw = dict(policy="fifo", agg_policy="buffered", buffer_k=2,
              max_inflight_rounds=2)
    for shared in (False, True):
        plane = NetworkPlane([ConstantLink(RATE)] * 5, shared=shared,
                             capacity_mbps=2 * RATE if shared else None)
        free = FederationClock(5, 3, ClockConfig(**kw),
                               times_fn=lambda u, r: times[u],
                               network=plane).run()
        routed = FederationClock(5, 3, ClockConfig(**kw),
                                 times_fn=lambda u, r: times[u],
                                 network=plane,
                                 agg_bytes_fn=lambda u: 8e5).run()
        assert routed.rounds_completed == {u: 3 for u in range(5)}
        assert routed.makespan > free.makespan
        assert len(routed.commits) >= len(free.commits) - 1
        # adapter sync landmarks are in the trace
        kinds = {k for _, k, _ in routed.events}
        assert "agg_uplink_done" in kinds and "agg_downlink_done" in kinds
    with pytest.raises(ValueError):   # routing needs a plane
        FederationClock(2, 1, ClockConfig(), agg_bytes_fn=lambda u: 1.0)


# -- FedRunConfig validation matrix -------------------------------------------

BAD_CONTROL_CONFIGS = [
    (KeyError, dict(controller="bogus")),
    (KeyError, dict(agg_transport="bogus")),
    (ValueError, dict(engine="event", resolve_every=0)),
    (ValueError, dict(engine="event", controller="reactive",
                      resolve_every=2)),          # periodic-only knob
    (ValueError, dict(engine="event", controller="periodic",
                      hysteresis=0.2)),           # reactive-only knob
    (ValueError, dict(engine="event", controller="reactive",
                      hysteresis=0.0)),
    (ValueError, dict(controller="reactive")),    # needs engine=event
]


@pytest.mark.parametrize("exc,kw", BAD_CONTROL_CONFIGS,
                         ids=[str(i) for i in range(len(BAD_CONTROL_CONFIGS))])
def test_control_knob_validation_rejects(exc, kw):
    with pytest.raises(exc):
        validate_run_config(FedRunConfig(**kw), n_clients=6)


def test_control_knob_validation_accepts():
    for kw in (dict(engine="event", controller="periodic", resolve_every=3),
               # analytic + plane-routed aggregation: the commit legs price
               # in closed form over the constant-rate plane (carried-over
               # ROADMAP item; the analytic guard moved to link variability)
               dict(agg_transport="plane"),
               dict(engine="event", controller="reactive", hysteresis=0.5,
                    link_model="gilbert"),
               dict(engine="event", agg_transport="plane"),
               dict(engine="event", controller="reactive",
                    agg_transport="plane", link_model="gilbert",
                    agg_policy="buffered", agg_interval=1,
                    max_inflight_rounds=2)):
        validate_run_config(FedRunConfig(**kw), n_clients=6)


# -- simulator integration ----------------------------------------------------

@pytest.fixture(scope="module")
def sim_setup():
    cfg = tiny("bert-base", n_layers=3, d_model=128)
    cfg = cfg.with_(vocab_size=4096, max_position=32)
    train = make_emotion_dataset(400, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(100, seq_len=16, vocab_size=4096, seed=1)
    return cfg, train, test


def _sim(sim_setup, rounds=3, cuts=(2, 2, 2, 2), **kw):
    cfg, train, test = sim_setup
    rc = FedRunConfig(scheme="ours", rounds=rounds, agg_interval=1,
                      batch_size=4, seq_len=16, lr=3e-3, eval_every=100,
                      engine="event", **kw)
    sim = Simulator(cfg, PAPER_CLIENTS[:4], list(cuts), train, test, rc)
    sim.run_training()
    return sim


def test_simulator_static_controller_is_parity(sim_setup):
    """controller='static' (the default) is the PR-3 code path: explicit
    static config reproduces the default run's timeline float-for-float,
    and no control machinery is attached."""
    a = _sim(sim_setup, scheduler="fifo", agg_policy="buffered",
             agg_buffer_k=2, max_inflight_rounds=2)
    b = _sim(sim_setup, scheduler="fifo", agg_policy="buffered",
             agg_buffer_k=2, max_inflight_rounds=2, controller="static")
    assert b._control is None and b.control_events == []
    assert [r.sim_time_s for r in a.history] == \
           [r.sim_time_s for r in b.history]
    assert [t for t, *_ in a.loss_events] == [t for t, *_ in b.loss_events]


def test_simulator_reactive_end_to_end_real_math(sim_setup):
    """Reactive controller on fading links: the run completes with finite
    losses, any applied migration changed the live cuts, and the jitted
    steps/adapter shapes followed."""
    sim = _sim(sim_setup, rounds=4, scheduler="ours", link_model="gilbert",
               controller="reactive", hysteresis=0.1,
               agg_policy="buffered", agg_buffer_k=2, max_inflight_rounds=1)
    assert len(sim.loss_events) == 4 * 4
    assert all(np.isfinite(ls) for _, _, _, ls in sim.loss_events)
    for ev in sim.control_events:
        if ev.applied:
            for u, (_old, new) in ev.cut_changes.items():
                assert sim.cuts[u] in range(1, sim.cfg.n_layers)
                assert new in sim.math.per_cut
    # adapters and client prefixes stay shape-consistent with the live cuts
    for u in range(4):
        prefix = sim.math.at_cut(sim.cuts[u]).prefix
        assert jax_leading_dim(prefix["layers"]) == sim.cuts[u]
        assert jax_leading_dim(sim.client_lora[u]) == sim.cuts[u]


def jax_leading_dim(tree):
    import jax
    return int(jax.tree.leaves(tree)[0].shape[0])


def test_simulator_plane_transport_sync(sim_setup):
    """agg_transport='plane' on constant links: same fleet, commit charge
    now upload+download through the plane — history stays finite and the
    timeline is within float noise of the nominal 2x-slowest-upload charge
    (identical arithmetic on symmetric constant links)."""
    a = _sim(sim_setup, scheduler="fifo")
    b = _sim(sim_setup, scheduler="fifo", agg_transport="plane")
    assert [r.sim_time_s for r in a.history] == \
           pytest.approx([r.sim_time_s for r in b.history], rel=1e-12)


def test_simulator_state_dict_roundtrips_cuts(sim_setup):
    cfg, train, test = sim_setup
    sim = _sim(sim_setup, rounds=2, scheduler="ours", link_model="gilbert",
               controller="periodic", agg_policy="buffered", agg_buffer_k=2,
               max_inflight_rounds=1)
    st = sim.state_dict()
    assert list(np.asarray(st["cuts"])) == sim.cuts
    rc = FedRunConfig(scheme="ours", rounds=2, agg_interval=1, batch_size=4,
                      seq_len=16, lr=3e-3, eval_every=100, engine="event",
                      scheduler="ours", link_model="gilbert",
                      controller="periodic", agg_policy="buffered",
                      agg_buffer_k=2, max_inflight_rounds=1)
    fresh = Simulator(cfg, PAPER_CLIENTS[:4], [2, 2, 2, 2], train, test, rc)
    fresh.load_state_dict(st)
    assert fresh.cuts == sim.cuts
    for u in range(4):
        assert jax_leading_dim(fresh.client_lora[u]) == sim.cuts[u]
        assert sim.cuts[u] in fresh.math.per_cut
