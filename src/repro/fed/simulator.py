"""End-to-end federated simulation of the paper's three schemes (§V):

  ours : memory-efficient SFL — parallel clients, ONE full server model,
         sequential per-client server LoRA updates, Alg. 2 scheduling,
         Eq. 5-9 aggregation every I rounds.
  sfl  : FedBERT-style SFL — U parallel server-side submodels.  The
         *updates* are identical to ours (the paper reports identical
         accuracy/rounds); what differs is server memory and round time.
  sl   : split learning — one traveling adapter set, strictly sequential
         clients, no aggregation.

Model math runs for real in JAX (client forward, server resume-at-cut,
activation-gradient backprop, LoRA/Adam updates, FedAvg aggregation);
wall-clock and memory come from the §IV/§V analytical models
(docs/architecture.md).  The math itself, and the per-client state it
reads and writes, live in ``fed/split_math.py``.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.control import CONTROLLERS, ControlLoop
from repro.core import lora as lora_lib
from repro.core import memory_model
from repro.core.cost_model import (DeviceProfile, LinkProfile, StepTimes,
                                   client_step_times, dtype_nbytes,
                                   lora_upload_bytes, makespan)
from repro.net import (ConstantLink, GilbertElliottLink, LinkModel,
                       NetworkPlane, TraceLink)
from repro.core.scheduling import (ONLINE_DISCIPLINES, SCHEDULERS,
                                   alg2_priorities, refresh_priorities,
                                   resolve_online, resolve_order)
from repro.data import ClassificationLoader, EmotionDataset, dirichlet_partition
# the run configuration moved to fed/config.py (grouped sub-configs with
# flat-kwarg compatibility shims); re-exported here so every existing
# ``from repro.fed.simulator import FedRunConfig`` keeps working
from repro.fed.config import (AggConfig, ControlConfig, EngineConfig,  # noqa: F401
                              FedRunConfig, FleetConfig, LINK_MODELS,
                              NetConfig, ObsConfig, resolve_buffer_k,
                              validate_run_config)
from repro.fed.devices import LINK, SERVER
from repro.fed.engine import (AGG_POLICIES, ClockConfig, FederationClock,
                              RoundPlan, jobs_from_times)
from repro.fed.split_math import RoundRecord, SplitMath, round_id
from repro.net.topology import EdgeTopology
from repro.obs.host import span

SFL_FRAGMENTATION = 1.04   # multi-model GPU contention overhead (paper §V-B)

# Gilbert–Elliott defaults for link_model="gilbert": the bad state drops to
# a tenth of the nominal rate; dwell/transition values give ~1/3 bad time
# at the 100 Mbps / ~0.5 s-transfer scale of the paper's setup
GE_BAD_FRACTION = 0.1
GE_P_GB, GE_P_BG, GE_DWELL_S = 0.2, 0.4, 0.5


class Simulator:
    def __init__(self, cfg: ModelConfig, devices: Optional[Sequence[DeviceProfile]] = None,
                 cuts: Optional[Sequence[int]] = None,
                 train: EmotionDataset = None,
                 test: EmotionDataset = None, run: FedRunConfig = None,
                 link: LinkProfile = LINK, server: DeviceProfile = SERVER,
                 links: Optional[Sequence[LinkModel]] = None,
                 fleet: Optional["FleetSpec"] = None):
        if fleet is not None:
            # FleetSpec builder path: ONE seeded spec yields devices, cuts
            # and (under link_model="custom") the per-client LinkModels
            if devices is not None or cuts is not None:
                raise ValueError("pass either fleet=FleetSpec(...) or "
                                 "explicit devices/cuts, not both")
            devices, cuts = fleet.devices(), fleet.cuts()
            if links is None and run is not None \
                    and run.net.link_model == "custom":
                links = fleet.links()
        if devices is None or cuts is None or run is None:
            raise TypeError("Simulator needs devices+cuts (or fleet=) and run=")
        assert len(devices) == len(cuts)
        validate_run_config(run, len(devices))
        if run.fleet.size is not None and run.fleet.size != len(devices):
            raise ValueError(f"run.fleet.size={run.fleet.size} but "
                             f"{len(devices)} devices were materialized")
        self.run = run
        self.devices, self.cuts = list(devices), [int(c) for c in cuts]
        self._init_cuts = [int(c) for c in cuts]   # fingerprint anchor
        self.link, self.server_dev = link, server
        self.u = len(devices)
        # the network plane: per-client link models + optional shared medium
        # (run.net.link_model="constant" is byte-exact legacy parity)
        self.network = self._build_network(links)
        if run.engine.mode == "analytic" and not self.network.constant_rate:
            raise ValueError("the closed-form engine needs constant-rate "
                             "links (custom LinkModels must be ConstantLink);"
                             " set engine mode='event' for time-varying ones")
        # two-tier edge/cloud topology for hierarchical aggregation
        self._edges: Optional[EdgeTopology] = None
        if run.fleet.edge_cells > 1:
            if run.fleet.cell_assignment == "kmeans":
                if fleet is None:
                    raise ValueError(
                        "cell_assignment='kmeans' clusters per-client "
                        "coordinates, which only a FleetSpec carries — "
                        "pass fleet=FleetSpec(...) (or keep 'blocks')")
                self._edges = EdgeTopology.kmeans(
                    fleet.coords(), run.fleet.edge_cells, seed=run.seed,
                    backhaul_mbps=run.fleet.backhaul_mbps,
                    cell_capacity_mbps=run.fleet.edge_capacity_mbps)
            else:
                self._edges = EdgeTopology.grouped(
                    self.u, run.fleet.edge_cells,
                    backhaul_mbps=run.fleet.backhaul_mbps,
                    cell_capacity_mbps=run.fleet.edge_capacity_mbps)
        self._cap_ranks: Optional[np.ndarray] = None
        # the model, every client's adapter/head/optimizer state (all
        # materialized) and the jitted math; it reads the live cuts list,
        # which is only ever changed in place
        self.math = SplitMath(cfg, run, self.cuts.__getitem__, self.u,
                              test=test, dense=True)
        self.cfg = cfg = self.math.cfg
        self.store, self.params, self.opt = (self.math.store,
                                             self.math.params, self.math.opt)

        # non-IID data
        parts = dirichlet_partition(train.labels, self.u, run.alpha, run.seed)
        self.data_sizes = [len(p) for p in parts]
        self.loaders = [ClassificationLoader(train.subset(p), run.batch_size,
                                             seed=run.seed + i)
                        for i, p in enumerate(parts)]
        # the frozen client prefix and jitted steps, once per distinct cut
        for cut in sorted(set(self.cuts)):
            self.math.at_cut(cut)
        self._last_event = None   # EngineResult of the last event-driven round

        # analytic per-step Eq.10 terms (fixed per client); wireless terms
        # use each client's NOMINAL link rate — the event engines re-time
        # the transfers through the network plane from the payload bytes
        self.times: List[StepTimes] = [
            client_step_times(cfg, cut, dev, server,
                              LinkProfile(self.network.nominal_mbps(u)),
                              run.batch_size, run.seq_len)
            for u, (cut, dev) in enumerate(zip(self.cuts, self.devices))]
        # adaptive control plane: shares the LIVE self.cuts list, so an
        # accepted re-assignment is immediately visible to the wave planner,
        # the per-round times and the aggregation byte accounting.  The
        # static controller attaches nothing at all — the legacy code path
        # runs untouched (regression-tested bit-for-bit).
        self._control: Optional[ControlLoop] = None
        if run.control.policy != "static":
            self._control = ControlLoop(
                cfg, self.devices, server, self.network, self.cuts,
                batch=run.batch_size, seq_len=run.seq_len,
                controller=run.control.policy, resolve_every=run.control.resolve_every,
                hysteresis=run.control.hysteresis, scheduler=run.engine.scheduler,
                max_cut=cfg.n_layers - 1)
        # observability plane (docs/observability.md): tracing, metrics and
        # the time-resolved memory ledger are pure READS of the engines'
        # results — a run with obs enabled follows the identical timeline
        # (pinned by tests/test_obs_parity.py)
        self.obs = None
        if run.obs.enabled:
            from repro.obs import (MemoryLedger, MetricsRegistry,
                                   Observability, Tracer)
            self.obs = Observability(
                tracer=(Tracer(max_events=run.obs.max_events)
                        if run.obs.trace else None),
                metrics=MetricsRegistry() if run.obs.metrics else None,
                ledger=(MemoryLedger.from_model(cfg, self.cuts,
                                                run.batch_size, run.seq_len)
                        if run.obs.memory_ledger else None))
            if self._control is not None:
                self._control.obs = self.obs
            self.math.obs = self.obs
        self.history: List[RoundRecord] = []
        self.sim_clock = 0.0
        # beyond-paper transport/participation state
        self._round_rng = np.random.default_rng(run.seed + 7777)
        self._active: List[int] = list(range(self.u))
        # continuous-time engine state: the per-serve loss trace for
        # wall-clock curves and the per-client-round straggler rng (the
        # sync path re-rolls per barrier wave instead)
        self.loss_events: List[tuple] = []   # (t_server_done, uid, round, loss)
        self._clock: Optional[FederationClock] = None
        self._wave_losses: List[float] = []
        self._async_rng = np.random.default_rng(run.seed + 4242)
        self._quant_ratio: Optional[float] = None
        # the round (or rounds) being served: the host spans' ``round`` id
        self._serve_round: Union[int, str, None] = None
        # mid-flight checkpoint/resume plumbing (docs/checkpointing.md):
        # the periodic snapshotter rides the clock's tick callback, a
        # loaded clock snapshot waits here until _run_event builds the
        # clock, and clock_result records the last run (incl. preemption)
        self._snapshotter = None
        if run.snapshot_every is not None:
            from repro.checkpointing import PeriodicSnapshotter
            self._snapshotter = PeriodicSnapshotter(run.snapshot_dir,
                                                    run.snapshot_every)
        self._pending_clock_state: Optional[dict] = None
        self._resumed = False
        self.clock_result = None

    # --------------------------------------------------------------- network
    def _build_network(self, links: Optional[Sequence[LinkModel]]) -> NetworkPlane:
        """Materialize the run's network plane from the link knobs (or the
        caller-supplied LinkModels under link_model='custom')."""
        run = self.run
        if run.net.link_model == "custom":
            if links is None:
                raise ValueError("link_model='custom' needs Simulator("
                                 "links=[LinkModel, ...])")
            if len(links) != self.u:
                raise ValueError("need one LinkModel per client")
            ups = list(links)
        elif links is not None:
            raise ValueError("explicit links= require link_model='custom'")
        elif run.net.link_model == "constant":
            ups = [ConstantLink(self.link.rate_mbps) for _ in range(self.u)]
        elif run.net.link_model == "trace":
            # entries are (breakpoints, rates) tuples or bandwidth-CSV paths
            ups = [TraceLink.from_csv(tr) if isinstance(tr, (str, Path))
                   else TraceLink(tr[0], tr[1]) for tr in run.net.traces]
        else:   # gilbert
            base = self.link.rate_mbps
            ups = [GilbertElliottLink(base, base * GE_BAD_FRACTION,
                                      p_gb=GE_P_GB, p_bg=GE_P_BG,
                                      dwell_s=GE_DWELL_S,
                                      seed=run.seed * 7919 + u)
                   for u in range(self.u)]
        return NetworkPlane(ups, shared=run.net.shared,
                            capacity_mbps=run.net.capacity_mbps)

    # ------------------------------------------------------------------ time
    def _transport_ratio(self) -> float:
        """int8+EF wireless shrink factor (cached; same every round)."""
        if self._quant_ratio is None:
            from repro.comm import transport_bytes
            shape = (self.run.batch_size, self.run.seq_len, self.cfg.d_model)
            nb = dtype_nbytes(self.cfg.dtype)
            self._quant_ratio = (transport_bytes(shape, True, nb)
                                 / transport_bytes(shape, False, nb))
        return self._quant_ratio

    def _adjusted_times(self) -> List[StepTimes]:
        """Per-round Eq.10 terms: stragglers slow client compute; int8+EF
        transport shrinks both wireless transfers ~4x."""
        run = self.run
        out = []
        for u, st in enumerate(self.times):
            t_f, t_b, t_fc, t_bc = st.t_f, st.t_b, st.t_fc, st.t_bc
            fcb, bcb = st.fc_bytes, st.bc_bytes
            if run.fleet.straggler_prob > 0 and \
                    self._round_rng.random() < run.fleet.straggler_prob:
                t_f *= run.fleet.straggler_slowdown
                t_b *= run.fleet.straggler_slowdown
            if run.net.quantize:
                ratio = self._transport_ratio()
                t_fc *= ratio
                t_bc *= ratio
                fcb *= ratio    # the network plane integrates BYTES, so the
                bcb *= ratio    # int8+EF shrink applies to the payload too
            out.append(dataclasses.replace(st, t_f=t_f, t_b=t_b,
                                           t_fc=t_fc, t_bc=t_bc,
                                           fc_bytes=fcb, bc_bytes=bcb))
        return out

    def _async_times(self, u: int, rnd: int) -> StepTimes:
        """Eq.10 terms for ONE client's local round ``rnd`` — the async
        clock's per-(client, round) counterpart of ``_adjusted_times``
        (stragglers re-roll per local round on an independent stream)."""
        run = self.run
        st = self.times[u]
        t_f, t_b, t_fc, t_bc = st.t_f, st.t_b, st.t_fc, st.t_bc
        fcb, bcb = st.fc_bytes, st.bc_bytes
        if run.fleet.straggler_prob > 0 and \
                self._async_rng.random() < run.fleet.straggler_prob:
            t_f *= run.fleet.straggler_slowdown
            t_b *= run.fleet.straggler_slowdown
        if run.net.quantize:
            ratio = self._transport_ratio()
            t_fc *= ratio
            t_bc *= ratio
            fcb *= ratio
            bcb *= ratio
        return dataclasses.replace(st, t_f=t_f, t_b=t_b, t_fc=t_fc, t_bc=t_bc,
                                   fc_bytes=fcb, bc_bytes=bcb)

    def _service_plan(self):
        """Decide this round's server dispatch groups under the closed-form
        analytic engine (the event engine's dispatch groups come from the
        FederationClock's serve events instead).

        Returns a list of uid-chunks served in order — each chunk of size>1
        runs through the batched vmapped server step.
        """
        run = self.run
        t = self._times_this_round
        tfl = [d.tflops for d in self.devices]
        chunk = max(1, int(run.engine.cohort_chunk))
        order = resolve_order(run.engine.scheduler, t, self.cuts, tfl)
        order = [u for u in order if u in self._active]
        self._last_event = None
        return [order[i:i + chunk] for i in range(0, len(order), chunk)]

    def _sample_cohort(self) -> None:
        """Per-round cohort sampling into ``self._active`` via the fleet
        sampling policy (one rng draw per sampled round, shared by the
        analytic loop and the sync barrier waves for stream parity).
        ``uniform`` reproduces the legacy scalar-``participation`` stream
        bit-for-bit; ``pareto`` biases the same-size draw toward capable
        clients with rank-Pareto weights (Jung et al. 2024)."""
        run = self.run
        if run.fleet.sampling == "full" or run.scheme == "sl":
            self._active = list(range(self.u))
            return
        from repro.fed.population import sample_cohort
        self._active = sample_cohort(
            self._round_rng, self.u, run.fleet.sampling, run.fleet.rate,
            ranks=self._capability_ranks(),
            pareto_alpha=run.fleet.pareto_alpha)

    def _capability_ranks(self) -> np.ndarray:
        """Dense capability ranks (0 = fastest client, ties by uid) for the
        Pareto-biased sampler — cached; the fleet's TFLOPS never change."""
        if self._cap_ranks is None:
            tfl = np.array([d.tflops for d in self.devices])
            order = np.lexsort((np.arange(self.u), -tfl))
            ranks = np.empty(self.u, dtype=np.int64)
            ranks[order] = np.arange(self.u)
            self._cap_ranks = ranks
        return self._cap_ranks

    def _round_time(self, order: Sequence[int]) -> float:
        t = self._times_this_round
        if self.run.scheme == "ours":
            span, _, _ = makespan(t, order)
            return span
        if self.run.scheme == "sfl":
            # all participating server submodels train concurrently on one
            # GPU: fair-share finish at max(arrival) + contended total work
            active = [t[u] for u in self._active]
            start = max(st.ready for st in active)
            busy = sum(st.t_s for st in active) * SFL_FRAGMENTATION
            return start + busy + max(st.t_bc + st.t_b for st in active)
        if self.run.scheme == "sl":
            # strictly sequential + client-side model handoff between clients
            total = 0.0
            mb = memory_model.model_bytes(self.cfg)
            for u, st in enumerate(t):
                handoff = self.link.transfer_s(
                    mb.embed + self.cuts[u] * mb.per_layer)
                total += st.ready + st.t_s + st.t_bc + st.t_b + handoff
            return total
        raise KeyError(self.run.scheme)

    # ------------------------------------------------------------------ round
    def run_round(self, rnd: int) -> RoundRecord:
        """One closed-form (analytic-engine) barrier round.  Event-engine
        rounds are driven by the FederationClock inside ``run_training``."""
        run = self.run
        if run.engine.mode == "event":
            raise RuntimeError("engine='event' rounds are owned by the "
                               "FederationClock; call run_training()")
        with span("fed.round", round=rnd):
            self._serve_round = rnd
            self._times_this_round = self._adjusted_times()
            self._sample_cohort()
            if run.scheme == "sl":
                losses, order = self._round_sl()
            else:
                losses, order = self._round_parallel()
            self.sim_clock += self._round_time(order)

            # aggregation phase (not for SL)
            if run.scheme in ("ours", "sfl") and (rnd + 1) % run.agg.interval == 0:
                self.sim_clock += self._commit_sync(None)

            if run.scheme != "sl":
                # the round's one wait on the device, after the commit's
                # dispatch: the serves only started each loss's copy
                losses = self._read_losses(losses)
            # a deadline can cut every client out of a round -> no losses
            mean_loss = float(np.mean(losses)) if losses else float("nan")
        rec = RoundRecord(rnd, self.sim_clock, mean_loss)
        self.history.append(rec)
        return rec

    # -- round bodies ----------------------------------------------------------
    def _round_parallel(self):
        """ours / sfl: parallel client forwards, then scheduled server
        updates on the single full model — sequential per-client dispatches
        or cohort-chunked batched dispatches, per the service plan."""
        groups = self._service_plan()
        losses, order = [], []
        for grp in groups:
            if not grp:
                continue
            order.extend(grp)
            losses.extend(self._serve_group(list(grp)))
        return losses, order

    def _read_losses(self, losses: List[jax.Array]) -> List[float]:
        """The losses as Python floats.  With obs metrics on, counts them
        (``loss_reads``) and those whose step was still running when read
        (``loss_reads_waited``): there the host had got ahead of the
        device."""
        if self.obs is not None and self.obs.metrics is not None:
            self.obs.metrics.inc("loss_reads", len(losses))
            self.obs.metrics.inc("loss_reads_waited",
                                 sum(not loss.is_ready() for loss in losses))
        return [float(loss) for loss in losses]

    def _serve_group(self, grp: List[int]) -> List[jax.Array]:
        """One server dispatch group's real math (``SplitMath.serve``),
        shared by the analytic round body and the FederationClock's serve
        events: each client's loss as a device scalar whose copy to the
        host has started."""
        return self.math.serve(grp, self.loaders.__getitem__,
                               self._serve_round)

    # read-only views of the store: each client's trees as a list (the
    # checkpoint format, and callers that read one client's state)
    client_lora = property(lambda self: self._field("client_lora"))
    server_lora = property(lambda self: self._field("server_lora"))
    heads = property(lambda self: self._field("head"))
    client_opt = property(lambda self: self._field("client_opt"))
    server_opt = property(lambda self: self._field("server_opt"))
    _global_full = property(lambda self: self.store.global_full)
    _global_head = property(lambda self: self.store.global_head)
    discarded_updates = property(lambda self: self.math.discarded_updates)

    def _field(self, key: str) -> list:
        return [self.store.slot(u)[key] for u in range(self.u)]

    def _round_sl(self):
        """SL baseline: ONE traveling full adapter set (kept in slot 0 as a
        full-shape tree); clients run strictly sequentially, each re-splits
        the traveling adapters at its own cut, trains, and folds back."""
        order = list(range(self.u))
        losses = []
        travel = self.store.slot(0)
        for u in order:
            cut = self.cuts[u]
            batch = {k: jnp.asarray(v) for k, v in self.loaders[u].next_batch().items()}
            # hand-off: client receives the traveling client-side adapters
            cli_lo, _ = lora_lib.split_lora(travel["server_lora"], cut)
            steps = self.math.at_cut(cut)
            v = steps.client_fwd(steps.prefix, cli_lo, batch)
            loss, new_lora, new_head, new_opt, dv = steps.server_step(
                self.params, travel["server_lora"], travel["head"],
                travel["server_opt"], v, batch)
            travel["server_lora"], travel["head"], travel["server_opt"] = \
                new_lora, new_head, new_opt
            losses.append(float(loss))
            new_cli, _ = steps.client_bwd(steps.prefix, cli_lo,
                                          self.opt.init(cli_lo), batch, dv)
            self._sl_fold_back(travel, new_cli, cut)
        return losses, order

    def _sl_fold_back(self, travel: dict, client_part, cut: int):
        """Write the client's updated prefix back into the traveling set."""
        full = travel["server_lora"]
        merged = {}
        for key, sub in full.items():
            if key in lora_lib.STACKED_KEYS and key in client_part:
                merged[key] = jax.tree.map(
                    lambda f, c: jnp.concatenate([c.astype(f.dtype), f[cut:]], axis=0),
                    sub, client_part[key])
            else:
                merged[key] = sub
        travel["server_lora"] = merged

    # ---------------------------------------------------- event-engine driver
    # Under engine="event" the FederationClock owns time and the simulator is
    # a thin driver: the clock calls back into ``_serve_group`` for the real
    # jitted math at every server dispatch and into a commit handler at every
    # aggregation, and the driver folds the results into history/loss_events.

    def _summary_bytes(self) -> float:
        """One edge summary = the full-depth adapter set (every cell merges
        its members into one full LoRA tree before the backhaul hop)."""
        return lora_upload_bytes(self.cfg, self.cfg.n_layers)

    def _run_event(self, verbose: bool = False):
        run = self.run
        tfl = [d.tflops for d in self.devices]
        if run.agg.policy == "sync":
            policy = "fifo"              # per-wave RoundPlan carries the real
            pri = None                   # discipline / fixed order
        else:
            policy, needs_pri = resolve_online(run.engine.scheduler)
            if not needs_pri:
                pri = None
            elif self._control is not None:
                # the control loop refreshes this list IN PLACE on every
                # accepted re-assignment, so the online priority discipline
                # orders by the live N_c/C ratios
                pri = self._control.pri
            else:
                pri = alg2_priorities(self.cuts, tfl)
        ccfg = ClockConfig(policy=policy, slots=run.engine.slots,
                           cohort_chunk=max(1, int(run.engine.cohort_chunk)),
                           chunk_efficiency=run.engine.chunk_efficiency,
                           deadline=run.engine.deadline,
                           agg_policy=run.agg.policy,
                           agg_interval=run.agg.interval,
                           buffer_k=resolve_buffer_k(run.agg, self.u),
                           max_inflight_rounds=run.agg.max_inflight)
        agg_bytes_fn = None
        if run.agg.transport == "plane":
            # live cuts: a migrated client ships its NEW adapter payload.
            # With a control loop attached, use ITS accounting so the DES
            # benches and the Simulator charge identical payloads.
            if self._control is not None:
                agg_bytes_fn = self._control.agg_bytes
            else:
                agg_bytes_fn = lambda u: lora_upload_bytes(self.cfg, self.cuts[u])  # noqa: E731
        clock = FederationClock(self.u, run.rounds, ccfg,
                                times_fn=self._async_times, priorities=pri,
                                network=self.network,
                                agg_bytes_fn=agg_bytes_fn,
                                edges=(self._edges if agg_bytes_fn is not None
                                       else None),
                                summary_bytes=(self._summary_bytes()
                                               if self._edges is not None
                                               else 0.0),
                                obs=self.obs)
        self._clock = clock
        if self._pending_clock_state is not None:
            # resuming a mid-flight snapshot: the clock continues the
            # restored event loop instead of starting at t=0, and the
            # snapshot cadence continues past the resume point
            clock.load_state_dict(self._pending_clock_state)
            self._pending_clock_state = None
            if self._snapshotter is not None:
                self._snapshotter.fast_forward(clock.now)
        else:
            self._wave_losses = []
        tick = self._on_tick if (self._snapshotter is not None
                                 or run.preempt_at is not None) else None
        if run.agg.policy == "sync":
            res = clock.run(plan_fn=self._plan_wave, on_serve=self._on_serve,
                            on_commit=self._commit_sync,
                            on_round_end=lambda rnd, r:
                                self._on_round_end(rnd, r, verbose),
                            on_tick=tick)
        else:
            res = clock.run(on_serve=self._on_serve,
                            on_commit=lambda ev:
                                self._commit_async(ev, verbose),
                            on_round_start=lambda u, rnd, t:
                                self.math.pull(u, rnd),
                            on_tick=tick)
            # final-state evaluation (the async analogue of the sync path's
            # last-round eval) — not for preempted runs, which are resumed
            # from the last snapshot rather than finished here
            if not res.preempted and self.history \
                    and self.history[-1].accuracy is None:
                rec = self.history[-1]
                rec.accuracy, rec.f1 = self.evaluate()
                if verbose:
                    print(f"[{run.scheme}/{run.engine.scheduler}/{run.agg.policy}] "
                          f"final t={rec.sim_time_s:9.1f}s "
                          f"acc={rec.accuracy:.4f} f1={rec.f1:.4f}")
        self.clock_result = res
        self.sim_clock = clock.now
        if run.obs.trace_dir is not None and self.obs is not None \
                and self.obs.tracer is not None:
            self.write_trace()
        return self.history

    def _on_tick(self, now: float) -> bool:
        """Clock tick callback (every event under async policies, every
        barrier under sync): write a due snapshot, then apply the
        fault-injection preemption knob.  Snapshots are pure reads — a run
        with snapshotting enabled follows the identical timeline."""
        if self._snapshotter is not None:
            self._snapshotter.maybe_save(now, self.state_dict)
        if self.run.preempt_at is not None and now >= self.run.preempt_at:
            return False
        return True

    def _on_serve(self, ev):
        self._serve_round = round_id(ev.rounds)
        losses = self.math.serve_in_flight(ev.uids, ev.rounds,
                                           self._serve_group)
        self._wave_losses.extend(losses)
        for u, r, ls in zip(ev.uids, ev.rounds, losses):
            self.loss_events.append((ev.end, u, r, ls))

    def _plan_wave(self, rnd: int) -> RoundPlan:
        """One sync barrier wave: re-roll stragglers, sample the cohort, and
        hand the clock this round's jobs + discipline (or fixed order) —
        exactly the PR 1 per-round plan, so sync parity is by construction."""
        run = self.run
        self._times_this_round = self._adjusted_times()
        self._sample_cohort()
        t = self._times_this_round
        tfl = [d.tflops for d in self.devices]
        uids = sorted(self._active)
        if run.engine.scheduler in ONLINE_DISCIPLINES:
            policy, needs_pri = ONLINE_DISCIPLINES[run.engine.scheduler]
            pri = alg2_priorities(self.cuts, tfl) if needs_pri else None
            return RoundPlan(jobs=jobs_from_times(t, uids, priorities=pri),
                             policy=policy)
        # e.g. "optimal": no online form — replay its fixed order
        order = [u for u in resolve_order(run.engine.scheduler, t, self.cuts, tfl)
                 if u in self._active]
        return RoundPlan(jobs=jobs_from_times(t, uids), order=order)

    def _on_round_end(self, rnd: int, res, verbose: bool) -> bool:
        self._last_event = res
        self.sim_clock = self._clock.now
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        rec = RoundRecord(rnd, self.sim_clock, mean_loss)
        self.history.append(rec)
        return not self._maybe_eval(rnd, rec, verbose)

    def _commit_sync(self, ev) -> Union[float, Dict[int, float]]:
        """Barrier aggregation (Alg. 1 l.17-30, Eqs. 5-9) over the WHOLE
        fleet, as in the paper — returns the adapter up+download transfer
        time (scalar, or a per-client mapping once migrations apply; under
        ``agg_transport='plane'`` the clock routes the transfers itself and
        only the migration charges are returned).  Shared by the analytic
        round loop and the sync clock.

        A control-plane decision lands HERE, at the barrier commit: the
        aggregate is computed under the OLD cuts (that is what the clients
        trained), then cuts may move, then the aggregate is redistributed
        re-split at the NEW cuts."""
        ids = ({"round": self._serve_round} if ev is None
               else {"version": ev.version})
        with span("fed.commit", clients=self.u, **ids):
            return self._commit_sync_body(ev)

    def _commit_sync_body(self, ev) -> Union[float, Dict[int, float]]:
        # the UPLOAD leg ships the adapters the clients actually trained —
        # price it at the PRE-migration cuts, before any decision applies
        up_old = max(self.link.transfer_s(lora_upload_bytes(self.cfg, cut))
                     for cut in self.cuts)

        def decide():
            if self._control is None or ev is None:
                return {}, {}
            changes, mig = self._control.decide(ev.time, list(range(self.u)),
                                                ev.version)
            if changes:
                self._apply_cut_changes(changes)
            return changes, mig

        changes, mig = self.math.commit_sync(self.data_sizes, self._edges,
                                             decide)
        if self.run.agg.transport == "plane":
            if ev is not None:
                # the clock ships the adapters through the plane (two-tier
                # legs included); we only add the migration charges
                # (per-client extra past each download)
                return mig
            # ANALYTIC plane routing (closed form): the guard in __init__
            # pinned every link to a constant rate, so both legs price in
            # closed form from a barrier instant — per-client rates, and
            # the two-tier cell/backhaul composition when edges are on.
            # Controller is static under analytic, so old cuts == new cuts.
            bytes_of = lambda u: lora_upload_bytes(self.cfg, self.cuts[u])  # noqa: E731
            if self._edges is not None:
                from repro.net.topology import edge_commit_legs
                _, up_bar = edge_commit_legs(
                    self._edges, self.network, range(self.u), 0.0,
                    bytes_of, self._summary_bytes(), "up")
                _, down_bar = edge_commit_legs(
                    self._edges, self.network, range(self.u), up_bar,
                    bytes_of, self._summary_bytes(), "down")
                return down_bar
            up = max(self.network.uplinks[u].finish_time(0.0, bytes_of(u))
                     for u in range(self.u))
            return max(self.network.downlinks[u].finish_time(up, bytes_of(u))
                       for u in range(self.u))
        # aggregation transfer at the scalar nominal link: upload at the
        # old cuts, download (the redistribute) at the new ones; two-tier
        # topologies add one summary per direction over the backhaul
        hier = (2.0 * self._edges.backhaul_s(self._summary_bytes())
                if self._edges is not None else 0.0)
        if changes:
            down_new = max(self.link.transfer_s(
                lora_upload_bytes(self.cfg, cut)) for cut in self.cuts)
            return {u: up_old + down_new + hier + mig.get(u, 0.0)
                    for u in range(self.u)}
        return 2 * up_old + hier

    def _commit_async(self, ev, verbose: bool = False) -> float:
        """Async commit: fold the buffered contributors into the standing
        global adapters with staleness-discounted Eq. 6-8 weights, anchor
        the absent data mass on the current global, and redistribute to the
        contributors only (they re-enter at the new version; the rest keep
        training until their own next commit)."""
        with span("fed.commit", version=ev.version,
                  clients=len(ev.contributors)):
            return self._commit_async_body(ev, verbose)

    def _commit_async_body(self, ev, verbose: bool) -> float:
        run = self.run
        contribs = list(ev.contributors)
        # control decision: contributors stand at this commit boundary, but
        # only those with NO in-flight local round may migrate (an in-flight
        # round pulled client state shaped by the old cut).  The upload leg
        # shipped OLD-cut adapters — price it before the decision applies.
        up_old = max(self.link.transfer_s(lora_upload_bytes(self.cfg,
                                                            self.cuts[u]))
                     for u in contribs)

        def decide():
            if self._control is None:
                return {}, {}
            inflight = {u for (u, _r) in self.math.round_pull}
            changes, mig = self._control.decide(
                ev.time, contribs, ev.version,
                eligible=[u for u in contribs if u not in inflight])
            if changes:
                self._apply_cut_changes(changes)
            return changes, mig

        changes, mig = self.math.commit_async(contribs, ev.staleness,
                                              self.data_sizes, decide)
        if self.run.agg.transport == "plane":
            # the clock routes the adapter syncs; migrations ride as
            # per-client extras past each contributor's download
            ret: Union[float, Dict[int, float]] = mig
            effective = max(mig.values(), default=0.0)
        elif changes:
            # nominal charge: upload at the old cuts, redistribute at the new
            down_new = max(self.link.transfer_s(
                lora_upload_bytes(self.cfg, self.cuts[u])) for u in contribs)
            ret = {u: up_old + down_new + mig.get(u, 0.0) for u in contribs}
            effective = max(ret.values())
        else:
            ret = 2 * up_old
            effective = ret
        # one history record per commit (wall-clock-indexed, NOT per round)
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        self.sim_clock = ev.time + effective
        rec = RoundRecord(len(self.history), self.sim_clock, mean_loss)
        self.history.append(rec)
        if len(self.history) % run.eval_every == 0:
            rec.accuracy, rec.f1 = self.evaluate()
            if verbose:
                print(f"[{run.scheme}/{run.engine.scheduler}/{run.agg.policy}] "
                      f"commit {ev.version:4d} t={rec.sim_time_s:9.1f}s "
                      f"loss={rec.mean_loss:.4f} acc={rec.accuracy:.4f} "
                      f"f1={rec.f1:.4f} "
                      f"stale={float(np.mean(ev.staleness)):.2f}")
        return ret

    # ------------------------------------------------------- control plane
    @property
    def control_events(self):
        """ReassignEvents recorded by the control loop (empty when static)."""
        return [] if self._control is None else self._control.decisions

    def _apply_cut_changes(self, changes: Dict[int, Tuple[int, int]]) -> None:
        """Real-math side of a cut migration (commit boundaries only): the
        live ``self.cuts`` entries are already updated by the control loop;
        here the new cut's shared frozen prefix and jitted steps are
        ensured, and the analytic Eq. 10 terms are refreshed.
        Adapters and optimizer states are NOT touched — the calling commit
        body redistributes them from the aggregated global at the new cut,
        which is exactly the same operation a commit performs anyway."""
        run = self.run
        for u, (_old, new) in changes.items():
            self.math.at_cut(new)
            self.times[u] = client_step_times(
                self.cfg, new, self.devices[u], self.server_dev,
                LinkProfile(self.network.nominal_mbps(u)),
                run.batch_size, run.seq_len)
            if self.obs is not None and self.obs.ledger is not None:
                self.obs.ledger.set_cut(u, new)

    def _maybe_eval(self, rnd: int, rec: RoundRecord, verbose: bool) -> bool:
        """Shared per-round eval/early-stop; True means stop training."""
        return self.math.maybe_eval(
            rnd, rec, self.data_sizes, verbose,
            f"{self.run.scheme}/{self.run.engine.scheduler}")

    # ------------------------------------------------------------------ eval
    def evaluate(self, max_batches: int = 32):
        """Global model = aggregate of current full adapters (ours/sfl), the
        traveling set (sl), or the standing async global (buffered/staleness
        policies); evaluated centrally on the held-out set."""
        return self.math.evaluate(self.data_sizes, max_batches)

    # ------------------------------------------------------------------ driver
    def run_training(self, verbose: bool = False):
        run = self.run
        if run.resume_from is not None and not self._resumed:
            self.resume(run.resume_from)
        if run.engine.mode == "event":
            # time is owned by the FederationClock; this loop's per-round
            # stepping is the analytic closed-form path only
            return self._run_event(verbose)
        for rnd in range(run.rounds):
            rec = self.run_round(rnd)
            if self._maybe_eval(rnd, rec, verbose):
                break
        return self.history

    # ------------------------------------------------------------------ state
    def _fingerprint(self) -> str:
        """Identity hash of everything a snapshot is only valid against:
        model shape, initial assignment, fleet size, and every run knob
        except the snapshot/resume/preemption ones (the resuming config
        legitimately differs in exactly those)."""
        import hashlib
        import json
        run = dataclasses.asdict(self.run)
        for k in ("snapshot_every", "snapshot_dir", "resume_from",
                  "preempt_at", "obs"):
            # obs is popped too: observability is pure reads, so a resuming
            # run may legitimately turn tracing on or off
            run.pop(k, None)
        doc = {"model": self.cfg.name, "n_layers": self.cfg.n_layers,
               "d_model": self.cfg.d_model, "cuts": self._init_cuts,
               "n_clients": self.u, "run": run}
        return hashlib.sha256(json.dumps(doc, sort_keys=True,
                                         default=str).encode()).hexdigest()

    def _des_state(self) -> dict:
        """JSON-able discrete-event-side state for a mid-flight snapshot:
        the clock (event heap, buffers, credits, cells), the network
        plane's rate processes, the control plane, both RNG streams, and
        the run log (history, pending wave losses, discard log)."""
        return {
            "clock": (self._clock.state_dict()
                      if self._clock is not None else None),
            "net": self.network.state_dict(),
            "control": (self._control.state_dict()
                        if self._control is not None else None),
            "round_rng": self._round_rng.bit_generator.state,
            "async_rng": self._async_rng.bit_generator.state,
            "history": [[r.round, r.sim_time_s, r.mean_loss, r.accuracy,
                         r.f1] for r in self.history],
            "wave_losses": list(self._wave_losses),
            "discarded": [list(d) for d in self.math.discarded_updates],
            "obs": (self.obs.state_dict() if self.obs is not None else None),
        }

    def state_dict(self) -> dict:
        """Whole-fleet training state for CheckpointManager.save / resume —
        including, since snapshot schema 2, the MID-FLIGHT state of an
        event-engine run: the clock's event loop, in-flight round pulls,
        RNG stream positions, link/cell processes and the control plane.
        Loading such a snapshot into an identically configured Simulator
        and calling run_training continues the run bit-for-bit (see
        docs/checkpointing.md for the format and guarantees)."""
        from repro.checkpointing import pack_json
        st = {
            "schema_version": np.int64(2),
            "fingerprint": pack_json(self._fingerprint()),
            "round": np.int64(len(self.history)),
            "sim_clock": np.float64(self.sim_clock),
            "cuts": np.asarray(self.cuts, np.int64),
            "client_lora": self.client_lora,
            "server_lora": self.server_lora,
            "heads": self.heads,
            "client_opt": [tuple(o) for o in self.client_opt],
            "server_opt": [tuple(o) for o in self.server_opt],
            "loader_state": np.asarray([ld.state() for ld in self.loaders],
                                       np.int64),
            "global_full": self.store.global_full,
            "global_head": self.store.global_head,
            "loss_events": (np.asarray(self.loss_events, np.float64)
                            if self.loss_events
                            else np.zeros((0, 4), np.float64)),
            "des": pack_json(self._des_state()),
            "client_version": np.asarray(
                [self.math.version.get(u, 0) for u in range(self.u)],
                np.int64),
            # in-flight round pulls: the client-side state each live
            # (uid, round) snapshot at round start — pytrees, so they ride
            # the array checkpoint next to the adapters
            "round_pull": {
                f"{u}:{r}": {"lora": lora, "opt": tuple(opt),
                             "ver": np.int64(ver)}
                for (u, r), (lora, opt, ver) in self.math.round_pull.items()},
            "ef_residual": {str(u): arr
                            for u, arr in sorted(self.math.ef_residual.items())},
        }
        return st

    def load_state_dict(self, st: dict) -> int:
        from repro.optim import AdamWState
        self.sim_clock = float(st["sim_clock"])
        if "cuts" in st:    # a control plane may have migrated cuts mid-run
            saved = [int(c) for c in np.asarray(st["cuts"])]
            changes = {u: (self.cuts[u], c) for u, c in enumerate(saved)
                       if c != self.cuts[u]}
            if changes:
                for u, (_, c) in changes.items():
                    self.cuts[u] = c      # in place: shared with the loop
                self._apply_cut_changes(changes)
                if self._control is not None:
                    # the online priority discipline must order by the
                    # RESTORED cuts, not the setup-phase ratios
                    refresh_priorities(self._control.pri, self.cuts,
                                       [d.tflops for d in self.devices])
        if "global_full" in st:   # async-engine state (absent in old saves)
            self.store.set_global(st["global_full"], st["global_head"])
        for u in range(self.u):
            slot = self.store.slot(u)
            slot["client_lora"] = st["client_lora"][u]
            slot["server_lora"] = st["server_lora"][u]
            slot["head"] = st["heads"][u]
            slot["client_opt"] = AdamWState(*st["client_opt"][u])
            slot["server_opt"] = AdamWState(*st["server_opt"][u])
        if "loader_state" in st:
            for ld, s in zip(self.loaders, np.asarray(st["loader_state"])):
                ld.restore(s)
        if "loss_events" in st:
            self.loss_events = [(float(t), int(u), int(r), float(ls))
                                for t, u, r, ls in np.asarray(st["loss_events"])]
        # ---- mid-flight state (snapshot schema >= 2; docs/checkpointing.md)
        if "des" in st:
            from repro.checkpointing import unpack_json
            des = unpack_json(st["des"])
            self.network.load_state_dict(des["net"])
            if des["control"] is not None:
                if self._control is None:
                    raise ValueError("snapshot carries control-plane state "
                                     "but this run has controller='static'")
                self._control.load_state_dict(des["control"])
            self._round_rng.bit_generator.state = des["round_rng"]
            self._async_rng.bit_generator.state = des["async_rng"]
            self.history = [
                RoundRecord(int(r), float(t), float(l),
                            None if a is None else float(a),
                            None if f1 is None else float(f1))
                for r, t, l, a, f1 in des["history"]]
            self._wave_losses = [float(x) for x in des["wave_losses"]]
            self.math.discarded_updates[:] = [tuple(d)
                                              for d in des["discarded"]]
            if des.get("obs") is not None and self.obs is not None:
                # snapshots written without obs (or loaded into a run that
                # turned it off) skip this: obs never gates a resume
                self.obs.load_state_dict(des["obs"])
            # the clock is rebuilt by _run_event; its restored event loop
            # waits here until then
            self._pending_clock_state = des["clock"]
        if "client_version" in st:
            self.math.version = {
                u: int(v) for u, v in enumerate(np.asarray(st["client_version"]))}
        self.math.round_pull = {}
        for key, rec in (st.get("round_pull") or {}).items():
            u, r = (int(x) for x in key.split(":"))
            self.math.round_pull[(u, r)] = (rec["lora"],
                                            AdamWState(*rec["opt"]),
                                            int(np.asarray(rec["ver"])))
        for u_str, arr in (st.get("ef_residual") or {}).items():
            self.math.ef_residual[int(u_str)] = arr
        return int(st["round"])

    def resume(self, path: str) -> int:
        """Load a snapshot (checkpoint file, or a rotated snapshot
        directory — resolves to the latest) written by an identically
        configured run, and position this simulator to continue it.  The
        snapshot's config fingerprint must match; the snapshot/resume/
        preemption knobs are allowed to differ.  Returns the number of
        history records restored."""
        from repro.checkpointing import load_snapshot, unpack_json
        st = load_snapshot(path)
        if "fingerprint" in st:
            want = unpack_json(st["fingerprint"])
            if want != self._fingerprint():
                raise ValueError(
                    "snapshot fingerprint mismatch: it was written by a "
                    "differently configured run (model/fleet/knobs); "
                    "rebuild the Simulator with the original configuration "
                    "to resume")
        rnd = self.load_state_dict(st)
        self._resumed = True
        return rnd

    # ------------------------------------------------------------------ memory
    def server_memory_report(self):
        return memory_model.server_memory(
            self.cfg, self.run.scheme, self.cuts,
            self.run.batch_size, self.run.seq_len)

    # ------------------------------------------------------------------ obs
    def obs_other_data(self) -> dict:
        """Sidecar payload for the Chrome trace's ``otherData`` field:
        the metrics summary and the memory-ledger report (JSON-able)."""
        if self.obs is None:
            return {}
        out: dict = {}
        if self.obs.metrics is not None:
            out["metrics"] = self.obs.metrics.summary()
        if self.obs.ledger is not None:
            out["memory"] = self.obs.ledger.report()
        return out

    def write_trace(self, path: Optional[str] = None) -> str:
        """Write the Chrome/Perfetto trace JSON (plus the metrics/ledger
        sidecar under ``otherData``).  Default target is
        ``run.obs.trace_dir/trace.json``."""
        if self.obs is None or self.obs.tracer is None:
            raise ValueError("write_trace needs ObsConfig(trace=True)")
        if path is None:
            if self.run.obs.trace_dir is None:
                raise ValueError("pass path= or set ObsConfig(trace_dir=...)")
            d = Path(self.run.obs.trace_dir)
            d.mkdir(parents=True, exist_ok=True)
            path = str(d / "trace.json")
        else:
            Path(path).parent.mkdir(parents=True, exist_ok=True)
        self.obs.tracer.write_chrome(path, other_data=self.obs_other_data())
        return path


def run_federated_training(cfg: ModelConfig, fleet_spec, run: FedRunConfig,
                           train, test=None, *, verbose: bool = False):
    """Fleet-size router for real-math federated training.

    Below ``run.fleet.population_threshold`` the per-object
    :class:`Simulator` runs (the parity oracle: eager per-client state,
    every engine feature).  At or above it, building U client objects is
    exactly the wall this repo's population path removes, so the run is
    routed through the ``PopulationClock`` + ``PopulationTrainer`` pair
    instead of refusing at scale — same seeds, same sampling stream, and
    (sub-threshold, under the trainer's knob matrix) bit-identical
    history/loss events, pinned by tests/test_population_training.py.

    ``fleet_spec`` is a ``FleetSpec``; returns the driver object after
    training — ``Simulator`` or ``PopulationTrainer``, both carrying
    ``history`` / ``loss_events`` and a ``math`` whose ``evaluate`` scores
    the global model.
    """
    if fleet_spec.n < run.fleet.population_threshold:
        sim = Simulator(cfg, fleet=fleet_spec, train=train, test=test,
                        run=run)
        sim.run_training(verbose=verbose)
        return sim
    from repro.fed.population_training import train_population
    return train_population(cfg, fleet_spec.population(), run, train, test,
                            verbose=verbose)
