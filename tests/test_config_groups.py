"""Grouped FedRunConfig: the sub-config split, the flat-kwarg/attribute
compatibility shims (every legacy spelling keeps working, with a
DeprecationWarning), and the cross-group validation matrix."""
import dataclasses

import pytest

from repro.fed.config import (AggConfig, ControlConfig, EngineConfig,
                              FedRunConfig, FleetConfig, NetConfig,
                              ObsConfig, _FLAT_SHIMS, validate_run_config)


# ---------------------------------------------------------------------------
# flat kwarg / attribute shims
# ---------------------------------------------------------------------------

SHIM_VALUES = {
    "scheduler": "wf", "cohort_chunk": 3, "chunk_efficiency": 0.7,
    "server_slots": 2, "round_deadline": 9.0, "agg_policy": "buffered",
    "agg_interval": 4, "agg_buffer_k": 2, "max_inflight_rounds": 3,
    "staleness_alpha": 0.25, "agg_transport": "plane",
    "link_model": "gilbert", "link_traces": None, "shared_medium": True,
    "medium_capacity_mbps": 120.0, "quantize_activations": True,
    "controller": "periodic", "resolve_every": 5, "hysteresis": 0.3,
    "straggler_prob": 0.2, "straggler_slowdown": 4.0,
}


@pytest.mark.parametrize("name", sorted(_FLAT_SHIMS))
def test_flat_kwarg_routes_into_group(name):
    val = SHIM_VALUES[name]
    if val is None:
        pytest.skip("no distinct legacy value")
    with pytest.deprecated_call():
        run = FedRunConfig(**{name: val})
    group, attr = _FLAT_SHIMS[name]
    assert getattr(getattr(run, group), attr) == val
    # the flat attribute read warns and round-trips
    with pytest.deprecated_call():
        assert getattr(run, name) == val


@pytest.mark.parametrize("name", sorted(_FLAT_SHIMS))
def test_flat_attribute_write_updates_group(name):
    val = SHIM_VALUES[name]
    if val is None:
        pytest.skip("no distinct legacy value")
    run = FedRunConfig()
    with pytest.deprecated_call():
        setattr(run, name, val)
    group, attr = _FLAT_SHIMS[name]
    assert getattr(getattr(run, group), attr) == val


def test_engine_string_kwarg_shim():
    with pytest.deprecated_call():
        run = FedRunConfig(engine="event")
    assert isinstance(run.engine, EngineConfig)
    assert run.engine.mode == "event"
    # grouped spelling does NOT warn
    run2 = FedRunConfig(engine=EngineConfig(mode="event"))
    assert run2.engine == run.engine
    # legacy string comparison of the group still works (warns)
    with pytest.deprecated_call():
        assert run.engine == "event"


def test_unknown_kwarg_rejected():
    with pytest.raises(TypeError):
        FedRunConfig(bogus_knob=1)


def test_participation_bridge():
    with pytest.deprecated_call():
        run = FedRunConfig(participation=0.4)
    assert run.fleet.sampling == "uniform" and run.fleet.rate == 0.4
    with pytest.deprecated_call():
        assert run.participation == 0.4
    with pytest.deprecated_call():
        full = FedRunConfig(participation=1.0)
    assert full.fleet.sampling == "full" and full.fleet.rate == 1.0
    with pytest.raises(ValueError):
        FedRunConfig(participation=0.0)
    with pytest.raises(ValueError):
        FedRunConfig(participation=1.5)


def test_grouped_construction_warns_nothing(recwarn):
    FedRunConfig(rounds=3, engine=EngineConfig(mode="event", scheduler="wf"),
                 agg=AggConfig(policy="buffered", interval=1),
                 net=NetConfig(link_model="gilbert"),
                 control=ControlConfig(policy="reactive"),
                 fleet=FleetConfig(sampling="pareto", rate=0.5))
    assert not [w for w in recwarn.list
                if issubclass(w.category, DeprecationWarning)]


def test_flat_and_grouped_spellings_agree():
    with pytest.deprecated_call():
        flat = FedRunConfig(scheme="ours", rounds=7, scheduler="wf",
                            agg_interval=3, server_slots=2,
                            link_model="gilbert", straggler_prob=0.1,
                            engine="event")
    grouped = FedRunConfig(
        scheme="ours", rounds=7,
        engine=EngineConfig(mode="event", scheduler="wf", slots=2),
        agg=AggConfig(interval=3), net=NetConfig(link_model="gilbert"),
        fleet=FleetConfig(straggler_prob=0.1))
    assert dataclasses.asdict(flat) == dataclasses.asdict(grouped)


# ---------------------------------------------------------------------------
# validation matrix (group-local + cross-group)
# ---------------------------------------------------------------------------

def test_analytic_plane_transport_is_now_valid():
    """Carried-over ROADMAP item: plane-routed aggregation under the
    analytic engine prices the commit legs in closed form."""
    validate_run_config(FedRunConfig(agg=AggConfig(transport="plane")), 6)


def test_analytic_obs_metrics_is_valid():
    """The analytic round counts its loss reads in the obs metrics."""
    validate_run_config(FedRunConfig(obs=ObsConfig(metrics=True)), 6)


BAD = [
    (KeyError, dict(fleet=FleetConfig(sampling="bogus"))),
    (ValueError, dict(fleet=FleetConfig(sampling="full", rate=0.5))),
    (ValueError, dict(fleet=FleetConfig(sampling="uniform", rate=0.0))),
    (ValueError, dict(fleet=FleetConfig(sampling="pareto", rate=0.5,
                                        pareto_alpha=0.0))),
    (ValueError, dict(fleet=FleetConfig(edge_cells=0))),
    (ValueError, dict(fleet=FleetConfig(backhaul_mbps=0.0))),
    (ValueError, dict(fleet=FleetConfig(edge_capacity_mbps=50.0))),
    (ValueError, dict(fleet=FleetConfig(population_threshold=0))),
    # time-varying links still need the event clock
    (ValueError, dict(net=NetConfig(link_model="gilbert"))),
    # async never composes with per-round notions
    (ValueError, dict(engine=EngineConfig(mode="event", scheduler="fifo"),
                      agg=AggConfig(policy="buffered", interval=1),
                      fleet=FleetConfig(sampling="uniform", rate=0.5))),
    (ValueError, dict(engine=EngineConfig(mode="event", scheduler="fifo"),
                      agg=AggConfig(policy="buffered", interval=1),
                      fleet=FleetConfig(edge_cells=2))),
    # sl has nothing to aggregate hierarchically
    (ValueError, dict(scheme="sl", fleet=FleetConfig(edge_cells=2))),
    # cohort_impl is a closed enum
    (KeyError, dict(engine=EngineConfig(cohort_impl="bogus"))),
    # observability knob pairings (ObsConfig.validate)
    (ValueError, dict(engine=EngineConfig(mode="event"),
                      obs=ObsConfig(trace_dir="/tmp/t"))),
    (ValueError, dict(engine=EngineConfig(mode="event"),
                      obs=ObsConfig(max_events=100))),
    (ValueError, dict(engine=EngineConfig(mode="event"),
                      obs=ObsConfig(trace=True, max_events=0))),
    # the closed-form engine has no event stream to trace or to ledger
    (ValueError, dict(obs=ObsConfig(trace=True))),
    (ValueError, dict(obs=ObsConfig(memory_ledger=True))),
]


@pytest.mark.parametrize("exc,kw", BAD,
                         ids=[str(i) for i in range(len(BAD))])
def test_validation_rejects(exc, kw):
    with pytest.raises(exc):
        validate_run_config(FedRunConfig(**kw), n_clients=6)


def test_cohort_impl_and_fused_lora_knobs_valid():
    """ragged cohort step + fused kernels are plain engine knobs — valid
    under both engines, no flat-kwarg shim required."""
    for mode in ("analytic", "event"):
        validate_run_config(
            FedRunConfig(engine=EngineConfig(mode=mode, cohort_impl="ragged",
                                             fused_lora=True)),
            n_clients=6)
    assert EngineConfig().cohort_impl == "vmap"      # padded vmap stays default
    assert EngineConfig().fused_lora is False


def test_fleet_size_dependent_rules():
    with pytest.raises(ValueError):
        validate_run_config(FedRunConfig(fleet=FleetConfig(size=8)),
                            n_clients=6)
    with pytest.raises(ValueError):
        validate_run_config(FedRunConfig(fleet=FleetConfig(edge_cells=7)),
                            n_clients=6)
    validate_run_config(FedRunConfig(fleet=FleetConfig(size=6,
                                                       edge_cells=3)),
                        n_clients=6)
