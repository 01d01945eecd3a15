from repro.fed.config import (AggConfig, ControlConfig, EngineConfig,
                              FleetConfig, NetConfig, ObsConfig,
                              SAMPLING_POLICIES)
from repro.fed.devices import (LINK, PAPER_CLIENTS, PAPER_CUTS, SERVER,
                               TPU_V5E, make_fleet, make_link_fleet)
from repro.fed.engine import (AGG_POLICIES, ClockConfig, ClockResult,
                              CommitEvent, EngineResult, FederationClock,
                              Job, RoundPlan, ServeEvent, ServiceRecord,
                              jobs_from_times, simulate_round)
from repro.fed.fleet import FleetSpec
from repro.fed.population import (PopulationClock, PopulationFleet,
                                  PopulationResult, sample_cohort,
                                  step_time_arrays, vectorized_round)
from repro.fed.simulator import (LINK_MODELS, FedRunConfig, Simulator,
                                 validate_run_config)
from repro.fed.split_math import RoundRecord

__all__ = ["AGG_POLICIES", "AggConfig", "ClockConfig", "ClockResult",
           "CommitEvent", "ControlConfig", "EngineConfig", "EngineResult",
           "FedRunConfig", "FederationClock", "FleetConfig", "FleetSpec",
           "Job", "LINK", "LINK_MODELS", "NetConfig", "ObsConfig",
           "PAPER_CLIENTS",
           "PAPER_CUTS", "PopulationClock", "PopulationFleet",
           "PopulationResult", "RoundPlan", "RoundRecord",
           "SAMPLING_POLICIES", "SERVER", "ServeEvent", "ServiceRecord",
           "Simulator", "TPU_V5E", "jobs_from_times", "make_fleet",
           "make_link_fleet", "sample_cohort", "simulate_round",
           "step_time_arrays", "validate_run_config", "vectorized_round"]
