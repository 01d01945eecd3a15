"""Real-math training on sampled cohorts at population scale (ROADMAP 1).

``PopulationClock`` (fed/population.py) schedules 10^4-client rounds as
pure timing; this module supplies the training math for exactly the
cohorts those kernels dispatch.  A :class:`PopulationTrainer` attaches to
the clock and mirrors the per-object ``Simulator`` expression for
expression — client forward at the cut (Eq. 3), the batched/ragged
server step (Eq. 4), client backward, and the Eq. 5-9 commits — but
holds per-client adapter/optimizer state ONLY for sampled clients, via
``core.splitfl.CohortAdapterStore``.

Two commit regimes, keyed on ``run.fleet.population_threshold``:

  * ``exact``    (fleet below the threshold): commits fold FULL-LENGTH
    uid-ordered adapter lists where every untouched client is a cached
    slice view of the standing global.  Since ``split_lora`` /
    ``embed_in_full_shape`` / ``assemble_full`` are pure slice/concat
    ops and ``opt.init`` is deterministic, the result is bit-identical
    to the eager per-object ``Simulator`` under matching seeds — the
    cross-engine parity grid in tests/test_population_training.py pins
    loss events, adapter trees and the timeline.
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
from repro.core import lora as lora_lib
from repro.core import splitfl
from repro.core.cost_model import lora_upload_bytes
from repro.data import ClassificationLoader, dirichlet_partition, iid_partition
from repro.fed import metrics as M
from repro.fed.config import FedRunConfig, validate_population_training
from repro.fed.devices import LINK
from repro.fed.population import PopulationClock, PopulationFleet
from repro.fed.simulator import RoundRecord
from repro.models import build_model
from repro.optim import AdamW

__all__ = ["PopulationTrainer", "train_population"]


class PopulationTrainer:
    """Cohort-resident training state + the Simulator-mirrored math that
    the ``PopulationClock`` drives through its serve/commit callbacks."""

    def __init__(self, cfg: ModelConfig, fleet: PopulationFleet,
                 run: FedRunConfig, train, test=None, *,
                 exact: Optional[bool] = None):
        import dataclasses

        import jax

        validate_population_training(run, fleet.n)
        if run.engine.fused_lora:
            cfg = cfg.with_(lora=dataclasses.replace(cfg.lora, impl="fused"))
        self.cfg, self.fleet, self.run = cfg, fleet, run
        self.exact = (fleet.n < run.fleet.population_threshold
                      if exact is None else bool(exact))
        self.model = build_model(cfg)
        rng = jax.random.PRNGKey(run.seed)
        self.params = self.model.init_params(rng)
        self.lora_spec = jax.eval_shape(self.model.init_lora, rng)
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
        self._train, self.test = train, test
        # per-client loaders materialize LAZILY (seed=run.seed+u consumes
        # no shared stream, so creation order cannot perturb parity)
        self._loaders: Dict[int, ClassificationLoader] = {}
        base_lora = self.model.init_lora(jax.random.PRNGKey(run.seed + 1))
        self.opt = AdamW(run.lr)
        head0 = self.params.get("cls_head")
        cuts = fleet.cuts
        self.store = splitfl.CohortAdapterStore(
            self.lora_spec, self.opt, base_lora, head0,
            lambda u: int(cuts[u]))
        self._cuts = cuts
        self.link = LINK
        # jit caches, filled per distinct cut on first dispatch
        self._client_params: Dict[int, dict] = {}
        self._srv_steps: Dict[int, object] = {}
        self._cli_steps: Dict[int, tuple] = {}
        self._srv_step_batched = splitfl.make_server_step_cls_batched(
            self.model, self.opt, impl=run.engine.cohort_impl)
        self._eval_fn = None
        # Simulator-mirrored run products
        self.history: List[RoundRecord] = []
        self.loss_events: List[tuple] = []   # (t_server_done, uid, rnd, loss)
        self._wave_losses: List[float] = []
        self._round_pull: dict = {}
        self._client_version: Dict[int, int] = {}
        self.discarded_updates: List[tuple] = []
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
        self.obs = clock.obs

    # ------------------------------------------------------------- jit caches
    def _client_params_for(self, cut: int) -> dict:
        pc = self._client_params.get(cut)
        if pc is None:
            pc = dict(self.params)
            pc["layers"] = lora_lib.slice_stack(self.params["layers"], 0, cut)
            self._client_params[cut] = pc
        return pc

    def _steps_for(self, cut: int):
        srv = self._srv_steps.get(cut)
        if srv is None:
            srv = splitfl.make_server_step_cls(
                self.model, self.opt, path="sliced", static_cut=cut)
            self._srv_steps[cut] = srv
            self._cli_steps[cut] = splitfl.make_client_step(
                self.model, self.opt, cut, path="sliced")
        return srv, self._cli_steps[cut]

    def _loader(self, u: int) -> ClassificationLoader:
        ld = self._loaders.get(u)
        if ld is None:
            ld = ClassificationLoader(self._train.subset(self._parts[u]),
                                      self.run.batch_size,
                                      seed=self.run.seed + u)
            self._loaders[u] = ld
        return ld

    # ------------------------------------------------------------- serve math
    def _serve_group(self, grp: List[int]) -> List[float]:
        """Simulator._serve_group, cohort-resident: per-client batch draw +
        client forward at the cut, then ONE batched/ragged server dispatch
        (or the sequential step for size-1 groups), then each client's
        backward."""
        import jax.numpy as jnp
        batches, acts = {}, {}
        for u in grp:
            slot = self.store.materialize(u)
            batch = {k: jnp.asarray(v)
                     for k, v in self._loader(u).next_batch().items()}
            batches[u] = batch
            cut = int(self._cuts[u])
            _, (fwd, _) = self._steps_for(cut)
            acts[u] = fwd(self._client_params_for(cut), slot["client_lora"],
                          batch)
        losses: List[float] = []
        if len(grp) == 1:
            u = grp[0]
            cut = int(self._cuts[u])
            slot = self.store.slot(u)
            srv, _ = self._steps_for(cut)
            loss, new_lora, new_head, new_opt, dv = srv(
                self.params, slot["server_lora"], slot["head"],
                slot["server_opt"], acts[u], batches[u])
            losses.append(float(loss))
            slot["server_lora"], slot["head"], slot["server_opt"] = \
                new_lora, new_head, new_opt
            self._client_backward(u, batches[u], dv)
            return losses
        slots = [self.store.slot(u) for u in grp]
        loss_g, nl, nh, no, dv_g = self._srv_step_batched(
            self.params,
            lora_lib.stack_trees([s["server_lora"] for s in slots]),
            jnp.stack([s["head"] for s in slots]),
            lora_lib.stack_trees([s["server_opt"] for s in slots]),
            jnp.stack([acts[u] for u in grp]),
            lora_lib.stack_trees([batches[u] for u in grp]),
            jnp.asarray([int(self._cuts[u]) for u in grp]))
        nls, nos = lora_lib.unstack_tree(nl), lora_lib.unstack_tree(no)
        for i, u in enumerate(grp):
            losses.append(float(loss_g[i]))
            slot = slots[i]
            slot["server_lora"], slot["head"], slot["server_opt"] = \
                nls[i], nh[i], nos[i]
            self._client_backward(u, batches[u], dv_g[i])
        return losses

    def _client_backward(self, u: int, batch, dv) -> None:
        cut = int(self._cuts[u])
        _, (_, bwd) = self._steps_for(cut)
        slot = self.store.slot(u)
        slot["client_lora"], slot["client_opt"] = bwd(
            self._client_params_for(cut), slot["client_lora"],
            slot["client_opt"], batch, dv)

    # ------------------------------------------------------- sync callbacks
    def on_sync_serve(self, uids, rnd: int, t_end: float) -> None:
        """One sync dispatch group served at ``t_end`` (the clock replays
        the kernel's service records in event order, so loss events land
        exactly where Simulator._on_serve puts them)."""
        losses = self._serve_group([int(u) for u in uids])
        self._wave_losses.extend(losses)
        for u, ls in zip(uids, losses):
            self.loss_events.append((t_end, int(u), rnd, ls))

    def commit_sync(self) -> float:
        """Barrier Eq. 5-9 commit over the WHOLE fleet; returns the nominal
        up+download charge ``2*up_old (+ backhaul)`` exactly as
        Simulator._commit_sync does under a static controller."""
        resident = self.store.resident_nbytes()
        charge = (self._commit_sync_exact() if self.exact
                  else self._commit_sync_anchored())
        if self.obs is not None and self.obs.metrics is not None:
            self.obs.metrics.observe("cohort_resident_bytes", resident)
        return charge

    def _commit_sync_exact(self) -> float:
        n = self.fleet.n
        cuts = [int(c) for c in self._cuts]
        # an untouched client holds the standing global, full shape
        client_loras, server_loras, heads = [], [], []
        for u in range(n):
            slot = self.store.peek(u)
            if slot is not None:
                client_loras.append(slot["client_lora"])
                server_loras.append(slot["server_lora"])
                heads.append(slot["head"])
            else:
                client_loras.append(self.store.fresh_views(cuts[u])[0])
                server_loras.append(self.store.global_full)
                heads.append(self.store.global_head)
        # the Simulator's jitted commit programs, so both engines round alike
        w = agg_lib.commit_weights(self.data_sizes)
        if self._edges is not None:
            fulls = [lora_lib.assemble_full(
                         client_loras[u],
                         lora_lib.split_lora(server_loras[u], cuts[u])[1],
                         cuts[u])
                     for u in range(n)]
            agg_full, self.edge_summaries, self.edge_masses = \
                agg_lib.hierarchical_aggregate(
                    fulls, [float(s) for s in self.data_sizes],
                    [list(cell) for cell in self._edges.cells])
        else:
            agg_full = agg_lib.commit_aggregate(client_loras, server_loras, w,
                                                tuple(cuts))
        head = agg_lib.commit_heads(heads, w)
        up_old = max(self.link.transfer_s(lora_upload_bytes(self.cfg, cut))
                     for cut in cuts)
        self.store.reset_global(agg_full, head)
        hier = (2.0 * self._edges.backhaul_s(self._summary_bytes())
                if self._edges is not None else 0.0)
        return 2 * up_old + hier

    def _commit_sync_anchored(self) -> float:
        touched = self.store.touched()
        cuts = [int(self._cuts[u]) for u in touched]
        fulls = [lora_lib.assemble_full(
                     self.store.slot(u)["client_lora"],
                     lora_lib.split_lora(self.store.slot(u)["server_lora"],
                                         cut)[1], cut)
                 for u, cut in zip(touched, cuts)]
        w_t = [float(self.data_sizes[u]) for u in touched]
        absent = float(sum(self.data_sizes)) - sum(w_t)
        if not touched:
            agg_full, head = self.store.global_full, self.store.global_head
        elif self._edges is not None:
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
            agg_full, self.edge_summaries, self.edge_masses = \
                agg_lib.anchored_hierarchical_aggregate(
                    self.store.global_full, fulls, w_t,
                    [by_cell[c] for c in range(len(self._edges.cells))],
                    cell_absent)
            head = agg_lib.aggregate_full_weighted(
                [self.store.global_head]
                + [self.store.slot(u)["head"] for u in touched],
                [absent] + w_t)
        else:
            agg_full = agg_lib.merge_into_global(
                self.store.global_full, fulls, w_t, absent)
            head = agg_lib.aggregate_full_weighted(
                [self.store.global_head]
                + [self.store.slot(u)["head"] for u in touched],
                [absent] + w_t)
        up_old = max(self.link.transfer_s(lora_upload_bytes(self.cfg, cut))
                     for cut in sorted(set(int(c) for c in self._cuts)))
        self.store.reset_global(agg_full, head)
        hier = (2.0 * self._edges.backhaul_s(self._summary_bytes())
                if self._edges is not None else 0.0)
        return 2 * up_old + hier

    def on_sync_round_end(self, rnd: int, now: float,
                          verbose: bool = False) -> bool:
        """Round record + eval cadence (Simulator._on_round_end); returns
        True to stop early (target accuracy reached)."""
        self.sim_clock = now
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        rec = RoundRecord(rnd, now, mean_loss)
        self.history.append(rec)
        return self._maybe_eval(rnd, rec, verbose)

    def _maybe_eval(self, rnd: int, rec: RoundRecord,
                    verbose: bool) -> bool:
        run = self.run
        if (rnd + 1) % run.eval_every == 0 or rnd == run.rounds - 1:
            if self.test is None:
                return False
            rec.accuracy, rec.f1 = self.evaluate()
            if verbose:
                print(f"[population/{run.engine.scheduler}] round {rnd+1:4d} "
                      f"t={rec.sim_time_s:9.1f}s loss={rec.mean_loss:.4f} "
                      f"acc={rec.accuracy:.4f} f1={rec.f1:.4f}")
            if (run.target_accuracy is not None
                    and rec.accuracy >= run.target_accuracy):
                return True
        return False

    # ------------------------------------------------------ async callbacks
    def on_round_start(self, u: int, rnd: int, t: float) -> None:
        slot = self.store.materialize(u)
        self._round_pull[(u, rnd)] = (slot["client_lora"],
                                      slot["client_opt"],
                                      self._client_version.get(u, 0))

    def on_serve(self, ev) -> None:
        """Async ServeEvent: run each member's round on the state it pulled
        at round start, discard updates that lost a commit race
        (Simulator._on_serve)."""
        swapped = {}
        for u, r in zip(ev.uids, ev.rounds):
            pull = self._round_pull.pop((u, r), None)
            if pull is not None:
                slot = self.store.materialize(u)
                swapped[u] = (r, pull[2], slot["client_lora"],
                              slot["client_opt"])
                slot["client_lora"], slot["client_opt"] = pull[0], pull[1]
        losses = self._serve_group([int(u) for u in ev.uids])
        for u, (r, pull_version, cur_lora, cur_opt) in swapped.items():
            if self._client_version.get(u, 0) != pull_version:
                slot = self.store.slot(u)
                slot["client_lora"], slot["client_opt"] = cur_lora, cur_opt
                self.discarded_updates.append((u, r))
                if self.obs is not None and self.obs.metrics is not None:
                    self.obs.metrics.inc("stale_discard")
        self._wave_losses.extend(losses)
        for u, r, ls in zip(ev.uids, ev.rounds, losses):
            self.loss_events.append((ev.end, int(u), r, ls))

    def commit_async(self, ev) -> float:
        """Async commit (Simulator._commit_async under nominal transport):
        staleness-discounted anchored merge into the standing global,
        redistribute to the contributors only, one wall-clock-indexed
        history record per commit."""
        run = self.run
        contribs = [int(u) for u in ev.contributors]
        fulls = []
        for u in contribs:
            slot = self.store.materialize(u)
            cut = int(self._cuts[u])
            fulls.append(lora_lib.assemble_full(
                slot["client_lora"],
                lora_lib.split_lora(slot["server_lora"], cut)[1], cut))
        alpha = 0.0
        if run.agg.policy == "staleness":
            alpha = (0.5 if run.agg.staleness_alpha is None
                     else run.agg.staleness_alpha)
        w = [self.data_sizes[u] * agg_lib.staleness_discount(s, alpha)
             for u, s in zip(contribs, ev.staleness)]
        anchor = float(sum(self.data_sizes)
                       - sum(self.data_sizes[u] for u in contribs))
        new_full = agg_lib.merge_into_global(
            self.store.global_full, fulls, w, anchor)
        new_head = agg_lib.aggregate_full_weighted(
            [self.store.global_head]
            + [self.store.slot(u)["head"] for u in contribs],
            [anchor] + w)
        up_old = max(self.link.transfer_s(
            lora_upload_bytes(self.cfg, int(self._cuts[u])))
            for u in contribs)
        self.store.set_global(new_full, new_head)
        for u in contribs:
            # redistribute == re-materialize from the new global; split +
            # embed + opt.init reproduce Simulator's per-field assignment
            self.store.drop(u)
            self.store.materialize(u)
            self._client_version[u] = self._client_version.get(u, 0) + 1
        ret = 2 * up_old
        effective = ret
        losses, self._wave_losses = self._wave_losses, []
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        self.sim_clock = ev.time + effective
        rec = RoundRecord(len(self.history), self.sim_clock, mean_loss)
        self.history.append(rec)
        if len(self.history) % run.eval_every == 0 and self.test is not None:
            rec.accuracy, rec.f1 = self.evaluate()
        return ret

    def finalize_async(self, preempted: bool = False) -> None:
        """Final-state evaluation, the async analogue of the sync path's
        last-round eval (Simulator._run_event's tail)."""
        if (not preempted and self.history and self.test is not None
                and self.history[-1].accuracy is None):
            rec = self.history[-1]
            rec.accuracy, rec.f1 = self.evaluate()

    # ------------------------------------------------------------------ eval
    def _summary_bytes(self) -> float:
        return lora_upload_bytes(self.cfg, self.cfg.n_layers)

    def _global_eval_state(self):
        """(full, head) the evaluator scores — the standing async global,
        or the sync aggregate of the CURRENT per-client state (untouched
        clients stand at the global, exactly like Simulator.evaluate)."""
        if self.run.agg.policy != "sync":
            return self.store.global_full, self.store.global_head
        touched = self.store.touched()
        if not touched:
            return self.store.global_full, self.store.global_head
        if self.exact:
            import jax
            n = self.fleet.n
            fulls, heads = [], []
            for u in range(n):
                cut = int(self._cuts[u])
                slot = self.store.peek(u)
                if slot is not None:
                    fulls.append(lora_lib.assemble_full(
                        slot["client_lora"],
                        lora_lib.split_lora(slot["server_lora"], cut)[1],
                        cut))
                    heads.append(slot["head"])
                else:
                    c, s = self.store.fresh_views(cut)
                    fulls.append(lora_lib.assemble_full(c, s, cut))
                    heads.append(self.store.global_head)
            full = agg_lib.aggregate_full(fulls, self.data_sizes)
            w = np.array(self.data_sizes, np.float64)
            w /= w.sum()
            head = jax.tree.map(
                lambda *hs: sum(float(wi) * h for wi, h in zip(w, hs)),
                *heads)
            return full, head
        fulls = []
        for u in touched:
            cut = int(self._cuts[u])
            slot = self.store.slot(u)
            fulls.append(lora_lib.assemble_full(
                slot["client_lora"],
                lora_lib.split_lora(slot["server_lora"], cut)[1], cut))
        w_t = [float(self.data_sizes[u]) for u in touched]
        absent = float(sum(self.data_sizes)) - sum(w_t)
        full = agg_lib.merge_into_global(self.store.global_full, fulls,
                                         w_t, absent)
        head = agg_lib.aggregate_full_weighted(
            [self.store.global_head]
            + [self.store.slot(u)["head"] for u in touched],
            [absent] + w_t)
        return full, head

    def evaluate(self, max_batches: int = 32):
        import jax
        import jax.numpy as jnp
        if self.test is None:
            raise ValueError("no held-out set was provided")
        full, head = self._global_eval_state()
        params = dict(self.params)
        params["cls_head"] = head
        if self._eval_fn is None:
            self._eval_fn = jax.jit(
                lambda p, lo, b: self.model.loss(p, lo, b, path="scan")[1])
        preds, golds = [], []
        loader = ClassificationLoader(self.test, self.run.batch_size, seed=0)
        for i, batch in enumerate(loader.all_batches()):
            if i >= max_batches:
                break
            logits = self._eval_fn(params, full,
                                   {k: jnp.asarray(v)
                                    for k, v in batch.items()})
            preds.append(np.argmax(np.asarray(logits), -1))
            golds.append(batch["label"])
        pred = np.concatenate(preds)
        gold = np.concatenate(golds)
        return M.accuracy(pred, gold), M.macro_f1(pred, gold)

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
