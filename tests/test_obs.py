"""Observability plane (repro/obs): tracer columns + ring buffer +
Chrome export, the (count,sum,min,max) metrics registry, the
time-resolved memory ledger, ObsConfig wiring through the Simulator
(16-client acceptance run: obs-on bit-identical to obs-off, exported
trace passes ``tools/trace_summary.py --validate``), and the golden
3-client Chrome trace."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core.cost_model import StepTimes
from repro.data import make_emotion_dataset
from repro.fed import (ClockConfig, FedRunConfig, FederationClock, ObsConfig,
                       Simulator, make_fleet, validate_run_config)
from repro.net import ConstantLink, NetworkPlane
from repro.obs import (MemoryLedger, MetricsRegistry, Observability,
                       TRACK_PIDS, Tracer)

REPO = Path(__file__).resolve().parent.parent
GOLDEN = Path(__file__).resolve().parent / "data" / "golden_trace_3client.json"


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_spans_counters_and_roundtrip():
    tr = Tracer()
    tr.span("fwd", "compute", 0.0, 1.5, "client", 3, attrs={"round": 0})
    tr.instant("dropped", "drop", 2.0, "client", 4)
    tr.add_spans("uplink", "net", [1.5, 2.5], [2.0, 3.0], "client", [3, 5])
    tr.counter("occupancy", 0.7, 2.0, "cell", 0)
    assert len(tr) == 4 and tr.n_counters == 1
    spans = tr.spans()
    assert [s.track[1] for s in spans] == [3, 4, 3, 5]
    assert spans[0].dur == 1.5 and spans[0].track == ("client", 3)
    assert spans[1].dur == 0.0

    tr2 = Tracer()
    tr2.load_state_dict(tr.state_dict())
    assert json.dumps(tr2.to_chrome(), sort_keys=True) == \
        json.dumps(tr.to_chrome(), sort_keys=True)


def test_tracer_ring_buffer_drops_oldest():
    tr = Tracer(max_events=3)
    for i in range(5):
        tr.span(f"s{i}", "compute", float(i), float(i) + 1, "client", i)
    assert len(tr) == 3 and tr.dropped_spans == 2
    assert [s.name for s in tr.spans()] == ["s2", "s3", "s4"]
    for i in range(5):
        tr.counter("c", float(i), 1.0, "cell", 0)
    assert tr.n_counters == 3 and tr.dropped_counters == 2
    with pytest.raises(ValueError):
        Tracer(max_events=0)


def test_tracer_loads_a_snapshot_with_open_spans():
    """Snapshots from before cross-event spans moved to
    ``Observability.mark`` carry an ``open`` table; it still loads."""
    tr = Tracer()
    tr.span("uplink", "net", 1.0, 2.5, "client", 3)
    st = dict(tr.state_dict(), open={"dl:1:0": 4.0})
    tr2 = Tracer()
    tr2.load_state_dict(st)
    assert json.dumps(tr2.to_chrome(), sort_keys=True) == \
        json.dumps(tr.to_chrome(), sort_keys=True)


def test_tracer_chrome_layout():
    tr = Tracer()
    tr.span("serve", "server", 0.25, 0.75, "slot", 1)
    tr.counter("occupancy", 0.5, 2.0, "cell", 0)
    doc = tr.to_chrome(other_data={"k": "v"})
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert metas == evs[:len(metas)]           # metadata first
    x = next(e for e in evs if e["ph"] == "X")
    assert x["pid"] == TRACK_PIDS["slot"]
    assert x["ts"] == 0.25e6 and x["dur"] == 0.5e6
    c = next(e for e in evs if e["ph"] == "C")
    assert c["pid"] == TRACK_PIDS["cell"] and c["args"]["value"] == 2.0
    assert doc["otherData"]["k"] == "v"
    assert doc["otherData"]["clock"] == "simulated-seconds"


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_metrics_counters_gauges_hists():
    mx = MetricsRegistry()
    mx.inc("commits")
    mx.inc("commits", 2.0)
    mx.gauge("inflight", 3.0)
    mx.gauge("inflight", 1.0)
    mx.observe("queue_wait", 2.0, round=1, slot=0)
    mx.observe("queue_wait", 4.0, slot=0, round=1)   # label order irrelevant
    assert mx.counter_value("commits") == 3.0
    assert mx.gauge_value("inflight") == 1.0
    st = mx.hist_stats("queue_wait", round=1, slot=0)
    assert st == {"count": 2, "sum": 6.0, "mean": 3.0, "min": 2.0, "max": 4.0}
    assert mx.hist_stats("missing") == {"count": 0, "sum": 0.0}
    assert mx.counter_value("missing") == 0.0
    assert np.isnan(mx.gauge_value("missing"))


def test_metrics_observe_bulk_matches_loop():
    rng = np.random.default_rng(0)
    v = rng.uniform(0.0, 5.0, 257)
    a, b = MetricsRegistry(), MetricsRegistry()
    a.observe_bulk("x", v)
    a.observe_bulk("x", np.empty(0))        # no-op
    for x in v:
        b.observe("x", float(x))
    sa, sb = a.hist_stats("x"), b.hist_stats("x")
    assert sa["count"] == sb["count"] == 257
    assert sa["min"] == sb["min"] and sa["max"] == sb["max"]
    np.testing.assert_allclose(sa["sum"], sb["sum"])


def test_metrics_summary_and_roundtrip():
    mx = MetricsRegistry()
    mx.inc("dropped", 4)
    mx.observe("serve_s", 0.25)
    doc = mx.summary()
    assert doc["counters"] == {"dropped": 4.0}
    assert doc["histograms"]["serve_s"]["mean"] == 0.25
    m2 = MetricsRegistry()
    m2.load_state_dict(mx.state_dict())
    assert m2.summary() == mx.summary()


# ---------------------------------------------------------------------------
# MemoryLedger
# ---------------------------------------------------------------------------

def test_ledger_peaks_from_overlap():
    lg = MemoryLedger(client_base=[100.0, 200.0], client_act=[10.0, 20.0],
                      server_act=[5.0, 7.0], server_base=1000.0,
                      local_baseline=400.0)
    # client 0 computes twice, disjoint; client 1 never computes
    lg.client_span(0, 0.0, 1.0)
    lg.client_span(0, 2.0, 3.0)
    assert lg.peak_memory(0) == 110.0
    assert lg.peak_memory(1) == 200.0
    # two overlapping server stacks: the peak sees both
    lg.server_span([0], 0.0, 2.0)
    lg.server_span([1], 1.0, 3.0)
    assert lg.server_peak() == 1012.0
    # peak concurrency: client 0's second span (10) + server stack 1 (7)
    _, fleet = lg.fleet_curve()
    assert fleet.max() == 100.0 + 200.0 + 1000.0 + 17.0
    rep = lg.report()
    assert rep["worst_client_peak_bytes"] == 200.0
    assert rep["client_reduction_vs_local"] == 1.0 - 200.0 / 400.0

    lg2 = MemoryLedger([0.0], [0.0], [0.0], 0.0)
    lg2.load_state_dict(lg.state_dict())
    assert lg2.report() == rep
    assert lg2.server_peak() == lg.server_peak()


def test_ledger_bulk_matches_scalar():
    a = MemoryLedger(np.full(5, 50.0), np.arange(5, dtype=float),
                     np.ones(5), 10.0)
    b = MemoryLedger(np.full(5, 50.0), np.arange(5, dtype=float),
                     np.ones(5), 10.0)
    t0 = np.array([0.0, 0.5, 1.0])
    t1 = np.array([2.0, 1.5, 3.0])
    a.client_span_bulk(np.array([1, 2, 3]), t0, t1)
    for u, x, y in zip((1, 2, 3), t0, t1):
        b.client_span(u, x, y)
    for u in range(5):
        assert a.peak_memory(u) == b.peak_memory(u)


def test_ledger_from_model_and_set_cut():
    cfg = tiny("bert-base", n_layers=4, d_model=128)
    lg = MemoryLedger.from_model(cfg, [1, 3], batch=4, seq_len=16)
    assert lg.client_base[1] > lg.client_base[0]     # deeper cut, more bytes
    assert lg.local_baseline > lg.client_base.max()
    lg.client_span(0, 0.0, 1.0)
    rep = lg.report()
    assert 0.0 < rep["client_reduction_vs_local"] < 1.0
    before = float(lg.client_base[0])
    lg.set_cut(0, 3)
    assert float(lg.client_base[0]) > before
    raw = MemoryLedger([1.0], [1.0], [1.0], 1.0)
    with pytest.raises(RuntimeError):
        raw.set_cut(0, 2)


# ---------------------------------------------------------------------------
# golden 3-client Chrome trace
# ---------------------------------------------------------------------------

def _golden_doc() -> dict:
    """Deterministic 3-client async run, every obs surface on — the
    export must stay byte-stable (schema + key order + float repr)."""
    st = [StepTimes(t_f=0.4, t_fc=0.2, t_s=0.6, t_bc=0.2, t_b=0.3,
                    fc_bytes=2e6, bc_bytes=2e6),
          StepTimes(t_f=0.8, t_fc=0.3, t_s=0.9, t_bc=0.3, t_b=0.5,
                    fc_bytes=3e6, bc_bytes=3e6),
          StepTimes(t_f=1.2, t_fc=0.4, t_s=1.2, t_bc=0.4, t_b=0.7,
                    fc_bytes=4e6, bc_bytes=4e6)]
    obs = Observability(
        tracer=Tracer(), metrics=MetricsRegistry(),
        ledger=MemoryLedger(client_base=[1e6, 2e6, 3e6],
                            client_act=[1e5, 2e5, 3e5],
                            server_act=[1e4, 2e4, 3e4],
                            server_base=5e6, local_baseline=1e7))
    cfg = ClockConfig(policy="fifo", slots=2, agg_policy="buffered",
                      agg_interval=1, buffer_k=2, max_inflight_rounds=1)
    net = NetworkPlane([ConstantLink(r) for r in (50.0, 80.0, 100.0)])
    clock = FederationClock(3, 2, cfg, times_fn=lambda u, r: st[u],
                            network=net, obs=obs)
    clock.run()
    return obs.tracer.to_chrome(other_data={
        "metrics": obs.metrics.summary(), "memory": obs.ledger.report()})


def test_golden_trace_3client():
    got = json.dumps(_golden_doc(), sort_keys=True)
    assert GOLDEN.exists(), "golden trace missing — regenerate via " \
        "tests/test_obs.py:_golden_doc()"
    assert got == GOLDEN.read_text()


# ---------------------------------------------------------------------------
# Simulator wiring (the 16-client acceptance run)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim16_setup():
    cfg = tiny("bert-base", n_layers=3, d_model=128)
    cfg = cfg.with_(vocab_size=4096, max_position=32)
    train = make_emotion_dataset(600, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(100, seq_len=16, vocab_size=4096, seed=1)
    return cfg, train, test


def _sim16(sim16_setup, obs, **kw):
    cfg, train, test = sim16_setup
    rc = FedRunConfig(scheme="ours", rounds=2, agg_interval=1, batch_size=4,
                      seq_len=16, lr=3e-3, eval_every=100, engine="event",
                      scheduler="fifo", agg_policy="buffered", agg_buffer_k=4,
                      max_inflight_rounds=2, obs=obs, **kw)
    devices = make_fleet(16, seed=0)
    cuts = [1 + (i % 2) for i in range(16)]
    sim = Simulator(cfg, devices, cuts, train, test, rc)
    sim.run_training()
    return sim


def test_sim16_obs_is_pure_and_trace_validates(sim16_setup, tmp_path):
    off = _sim16(sim16_setup, ObsConfig())
    on = _sim16(sim16_setup, ObsConfig(trace=True, metrics=True,
                                       memory_ledger=True))
    # bit-identical run: timeline, loss events and the global adapter
    assert off.obs is None and on.obs is not None
    assert [r.sim_time_s for r in off.history] == \
        [r.sim_time_s for r in on.history]
    assert off.loss_events == on.loss_events
    for x, y in zip(jax.tree.leaves(off._global_full),
                    jax.tree.leaves(on._global_full)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the exported trace passes the CI validator
    path = on.write_trace(str(tmp_path / "trace.json"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_summary.py"),
         path, "--validate"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    # every client track carried spans; the ledger priced all 16 peaks
    kinds = {s.track for s in on.obs.tracer.spans()}
    assert {("client", u) for u in range(16)} <= kinds
    rep = on.obs.ledger.report()
    assert len(rep["client_peaks_bytes"]) == 16
    assert 0.0 < rep["client_reduction_vs_local"] < 1.0
    assert on.obs.metrics.counter_value("commits") > 0
    # summary tool runs clean on the same file
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_summary.py"), path],
        capture_output=True, text=True)
    assert proc.returncode == 0 and "phase breakdown" in proc.stdout


def test_sim16_trace_dir_auto_export(sim16_setup, tmp_path):
    d = tmp_path / "auto"
    sim = _sim16(sim16_setup, ObsConfig(trace=True,
                                        trace_dir=str(d)))
    out = d / "trace.json"
    assert out.exists()
    doc = json.loads(out.read_text())
    assert doc["otherData"]["clock"] == "simulated-seconds"
    # metrics/ledger sections absent when those planes are off
    assert "metrics" not in doc["otherData"]
    assert "memory" not in doc["otherData"]
    assert sim.obs.metrics is None and sim.obs.ledger is None


def test_obsconfig_validation_accepts_event_mode():
    validate_run_config(
        FedRunConfig(engine="event",
                     obs=ObsConfig(trace=True, metrics=True,
                                   memory_ledger=True,
                                   trace_dir="/tmp/x", max_events=10)),
        n_clients=4)
    assert not ObsConfig().enabled
    assert ObsConfig(metrics=True).enabled
