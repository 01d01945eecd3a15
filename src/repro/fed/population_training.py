"""Real-math training on sampled cohorts at population scale (ROADMAP 1).

``PopulationClock`` (fed/population.py) schedules 10^4-client rounds as
pure timing; a :class:`PopulationTrainer` attaches to it and runs the
training math for exactly the cohorts those kernels dispatch.  The math
is ``fed.split_math.SplitMath``, the same object the per-object
``Simulator`` holds: client forward at the cut (Eq. 3), the batched/ragged
server step (Eq. 4), client backward and the Eq. 5-9 commits.  Here it
holds per-client adapter/optimizer state ONLY for sampled clients: its
``CohortAdapterStore`` materializes a slot on first touch.  This module
keeps the clock's hooks, the lazy loaders, the anchored commit and the
resident-bytes accounting.

Two commit regimes, keyed on ``run.fleet.population_threshold``:

  * ``exact``    (fleet below the threshold): the Simulator's exact sync
    commit, folding every client of the fleet (an untouched client stands
    at its cut's template of the standing global) — bit-identical to the
    Simulator under matching seeds; the cross-engine parity grid in
    tests/test_population_training.py pins loss events, adapter trees and
    the timeline.
  * ``anchored`` (at/above the threshold): commits anchor the absent
    data mass on the standing global (``merge_into_global`` /
    ``anchored_hierarchical_aggregate``) — O(cohort) tree ops instead of
    O(fleet), float-equivalent to the exact fold but not bit-pinned.

RNG streams are shared with the Simulator by construction: model params
``PRNGKey(seed)``, base adapters ``PRNGKey(seed+1)``, the dirichlet
partition and per-client loader seeds, and the cohort sampling stream
``default_rng(seed+7777)`` (consumed by the clock).  Stragglers and
int8+EF quantization draw per-object streams the trainer does not
replicate — ``validate_population_training`` rejects those knobs.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import aggregation as agg_lib
from repro.core.cost_model import lora_upload_bytes
from repro.data import ClassificationLoader, dirichlet_partition, iid_partition
from repro.fed.config import FedRunConfig, validate_population_training
from repro.fed.devices import LINK
from repro.fed.population import PopulationClock, PopulationFleet
from repro.fed.split_math import RoundRecord, SplitMath, round_id

__all__ = ["PopulationTrainer", "train_population"]


class PopulationTrainer:
    """Cohort-resident training state behind the ``PopulationClock``'s
    serve/commit callbacks."""

    def __init__(self, cfg: ModelConfig, fleet: PopulationFleet,
                 run: FedRunConfig, train, test=None, *,
                 exact: Optional[bool] = None):
        validate_population_training(run, fleet.n)
        self.fleet, self.run = fleet, run
        self.exact = (fleet.n < run.fleet.population_threshold
                      if exact is None else bool(exact))
        cuts = fleet.cuts
        self.math = SplitMath(cfg, run, lambda u: int(cuts[u]), fleet.n,
                              test=test, exact=self.exact)
        self.cfg, self.model, self.store = (self.math.cfg, self.math.model,
                                            self.math.store)
        if self.exact:
            # bit-for-bit the Simulator's call (same min_per_client retry
            # loop, same rng stream) — the parity oracle depends on it
            parts = dirichlet_partition(train.labels, fleet.n, run.alpha,
                                        run.seed)
        else:
            # population scale: the dirichlet retry loop cannot satisfy
            # min_per_client across 10^4 clients; shard IID instead (equal
            # shard sizes also keep the batched serve shapes uniform)
            parts = iid_partition(len(train.labels), fleet.n, run.seed)
        self.data_sizes = [len(p) for p in parts]
        self._parts = parts
        self._train = train
        # per-client loaders materialize LAZILY (seed=run.seed+u consumes
        # no shared stream, so creation order cannot perturb parity)
        self._loaders: Dict[int, ClassificationLoader] = {}
        self._cuts = cuts
        self.link = LINK
        # the run products the Simulator leaves behind
        self.history: List[RoundRecord] = []
        self.loss_events: List[tuple] = []   # (t_server_done, uid, rnd, loss)
        self._wave_losses: List[float] = []
        self.sim_clock = 0.0
        # edge topology / obs arrive from the clock at attach time
        self._edges = None
        self.obs = None

    # ----------------------------------------------------------------- wiring
    def _bind(self, clock: "PopulationClock") -> None:
        """Called by ``PopulationClock(..., trainer=...)``: share the edge
        topology and the obs bundle so commit math and ledger pricing see
        exactly what the timing kernels see."""
        if clock.fleet is not self.fleet:
            raise ValueError("trainer and clock must share one "
                             "PopulationFleet")
        self._edges = clock._edges
        self.obs = self.math.obs = clock.obs

    def _loader(self, u: int) -> ClassificationLoader:
        ld = self._loaders.get(u)
        if ld is None:
            ld = ClassificationLoader(self._train.subset(self._parts[u]),
                                      self.run.batch_size,
                                      seed=self.run.seed + u)
            self._loaders[u] = ld
        return ld

    def _serve(self, uids, rnd) -> list:
        return self.math.serve([int(u) for u in uids], self._loader, rnd)

    # ------------------------------------------------------- sync callbacks
    def on_sync_serve(self, uids, rnd: int, t_end: float) -> None:
        """One sync dispatch group served at ``t_end`` (the clock replays
        the kernel's service records in event order, so loss events land
        exactly where Simulator._on_serve puts them)."""
        losses = [float(loss) for loss in self._serve(uids, rnd)]
        self._wave_losses.extend(losses)
        for u, ls in zip(uids, losses):
            self.loss_events.append((t_end, int(u), rnd, ls))

    def commit_sync(self) -> float:
        """Barrier Eq. 5-9 commit over the WHOLE fleet; returns the nominal
        up+download charge ``2*up_old (+ backhaul)`` exactly as
        Simulator._commit_sync does under a static controller."""
        resident = self.store.resident_nbytes()
        if self.exact:
            self.math.commit_sync(self.data_sizes, self._edges)
        else:
            self._commit_sync_anchored()
        if self.obs is not None and self.obs.metrics is not None:
            self.obs.metrics.observe("cohort_resident_bytes", resident)
        up_old = max(self.link.transfer_s(lora_upload_bytes(self.cfg, cut))
                     for cut in sorted(set(int(c) for c in self._cuts)))
        hier = (2.0 * self._edges.backhaul_s(
                    lora_upload_bytes(self.cfg, self.cfg.n_layers))
                if self._edges is not None else 0.0)
        return 2 * up_old + hier

    def _commit_sync_anchored(self) -> None:
        store = self.store
        touched = store.touched()
        if not touched:
            store.reset_global(store.global_full, store.global_head)
            return
        slots = [store.slot(u) for u in touched]
        fulls = [self.math.full_of(s, int(self._cuts[u]))
                 for u, s in zip(touched, slots)]
        w_t = [float(self.data_sizes[u]) for u in touched]
        absent = float(sum(self.data_sizes)) - sum(w_t)
        if self._edges is not None:
            cell_of = self._edges.cell_of()
            by_cell: Dict[int, List[int]] = {
                c: [] for c in range(len(self._edges.cells))}
            for i, u in enumerate(touched):
                by_cell[cell_of[u]].append(i)
            touched_set = set(touched)
            cell_absent = [
                sum(float(self.data_sizes[u]) for u in cell
                    if u not in touched_set)
                for cell in self._edges.cells]
            agg_full, _, _ = agg_lib.anchored_hierarchical_aggregate(
                store.global_full, fulls, w_t,
                [by_cell[c] for c in range(len(self._edges.cells))],
                cell_absent)
        else:
            agg_full = agg_lib.merge_into_global(store.global_full, fulls,
                                                 w_t, absent)
        head = agg_lib.aggregate_full_weighted(
            [store.global_head] + [s["head"] for s in slots], [absent] + w_t)
        store.reset_global(agg_full, head)

    def on_sync_round_end(self, rnd: int, now: float,
                          verbose: bool = False) -> bool:
        """Round record + eval cadence (Simulator._on_round_end); returns
        True to stop early (target accuracy reached)."""
        self.sim_clock = now
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        rec = RoundRecord(rnd, now, mean_loss)
        self.history.append(rec)
        return self.math.maybe_eval(
            rnd, rec, self.data_sizes, verbose,
            f"population/{self.run.engine.scheduler}")

    # ------------------------------------------------------ async callbacks
    def on_round_start(self, u: int, rnd: int, t: float) -> None:
        self.math.pull(u, rnd)

    def on_serve(self, ev) -> None:
        """Async ServeEvent: each member's round runs on the state it pulled
        at round start (Simulator._on_serve)."""
        losses = self.math.serve_in_flight(
            ev.uids, ev.rounds,
            lambda uids: self._serve(uids, round_id(ev.rounds)))
        self._wave_losses.extend(losses)
        for u, r, ls in zip(ev.uids, ev.rounds, losses):
            self.loss_events.append((ev.end, int(u), r, ls))

    def commit_async(self, ev) -> float:
        """Async commit (Simulator._commit_async under nominal transport):
        staleness-discounted anchored merge into the standing global,
        redistribute to the contributors only, one wall-clock-indexed
        history record per commit."""
        contribs = [int(u) for u in ev.contributors]
        up_old = max(self.link.transfer_s(
            lora_upload_bytes(self.cfg, int(self._cuts[u])))
            for u in contribs)
        self.math.commit_async(contribs, ev.staleness, self.data_sizes)
        ret = 2 * up_old
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        self.sim_clock = ev.time + ret
        rec = RoundRecord(len(self.history), self.sim_clock, mean_loss)
        self.history.append(rec)
        if len(self.history) % self.run.eval_every == 0 \
                and self.math.test is not None:
            rec.accuracy, rec.f1 = self.math.evaluate(self.data_sizes)
        return ret

    def finalize_async(self, preempted: bool = False) -> None:
        """Final-state evaluation, the async analogue of the sync path's
        last-round eval (Simulator._run_event's tail)."""
        if (not preempted and self.history and self.math.test is not None
                and self.history[-1].accuracy is None):
            rec = self.history[-1]
            rec.accuracy, rec.f1 = self.math.evaluate(self.data_sizes)

    # ------------------------------------------------------------ accounting
    def resident_nbytes(self) -> float:
        return self.store.resident_nbytes()


def train_population(cfg: ModelConfig, fleet: PopulationFleet,
                     run: FedRunConfig, train, test=None, *,
                     force: Optional[str] = None,
                     links=None, obs=None,
                     verbose: bool = False) -> PopulationTrainer:
    """Build a trainer + clock pair, run the federation, return the trainer
    (carrying ``history`` / ``loss_events`` / ``clock_result`` — the same
    surface ``Simulator.run_training`` leaves behind)."""
    trainer = PopulationTrainer(cfg, fleet, run, train, test)
    clock = PopulationClock(cfg, fleet, run, force=force, links=links,
                            obs=obs, trainer=trainer)
    trainer.clock_result = clock.run(verbose=verbose)
    return trainer
