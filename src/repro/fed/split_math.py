"""The real math of split federated training, for both real-math drivers.

``Simulator`` (fed/simulator.py) and ``PopulationTrainer``
(fed/population_training.py) each hold one :class:`SplitMath`.  It owns
the per-client state and everything that reads or writes it:

  * the client state: one ``core.splitfl.CohortAdapterStore`` of adapter,
    head and optimizer slots — materialized for every client at once
    (``dense``, the Simulator) or on first touch (the population trainer);
  * one per-cut cache of the frozen client prefix (``params`` with
    ``layers[:cut]``) and the jitted server step and client fwd/bwd, so
    clients at one cut share one prefix (no step donates it);
  * the serve of one dispatch group: client forward (Eq. 3), the server
    step (Eq. 4, sequential or cohort-batched) and client backward;
  * the commit-race bookkeeping of in-flight async rounds;
  * the exact sync commit (Eqs. 5-9) and the async merge;
  * the held-out evaluation of the global model.

The drivers keep what differs between them: their clocks, what a transfer
costs, and the population regime's anchored commit.  See
docs/architecture.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg_lib
from repro.core import lora as lora_lib
from repro.core import splitfl
from repro.data import ClassificationLoader
from repro.fed import metrics as M
from repro.models import build_model
from repro.obs.host import span
from repro.optim import AdamW

__all__ = ["CutSteps", "RoundRecord", "SplitMath", "round_id"]


@dataclasses.dataclass
class RoundRecord:
    round: int
    sim_time_s: float
    mean_loss: float
    accuracy: Optional[float] = None
    f1: Optional[float] = None


def round_id(rounds: Sequence[int]) -> Union[int, str]:
    """The host spans' ``round`` id of a serve whose clients are in
    ``rounds``: the round, or the distinct rounds joined by spaces."""
    distinct = sorted(set(rounds))
    return (distinct[0] if len(distinct) == 1
            else " ".join(map(str, distinct)))


class CutSteps(NamedTuple):
    """What every client at one cut shares."""
    prefix: dict          # params with layers[:cut]: the frozen client model
    server_step: Callable
    client_fwd: Callable
    client_bwd: Callable


class SplitMath:
    """Per-client state and the jitted math of one federation.

    ``cut_of(u)`` reads client ``u``'s live cut.  ``dense`` materializes
    every client of ``range(n_clients)`` at init and after each sync
    commit; otherwise slots appear on first touch.  ``exact`` evaluates
    the fold of the whole fleet, not the anchored merge of the touched
    clients."""

    def __init__(self, cfg, run, cut_of: Callable[[int], int],
                 n_clients: int, *, test=None, dense: bool = False,
                 exact: bool = True):
        if run.engine.fused_lora:
            # the kernel choice rides the config, no process-global state
            cfg = cfg.with_(lora=dataclasses.replace(cfg.lora, impl="fused"))
        self.cfg, self.run, self.test = cfg, run, test
        self.cut_of, self.n, self.dense, self.exact = \
            cut_of, n_clients, dense, exact
        self.model = build_model(cfg)
        rng = jax.random.PRNGKey(run.seed)
        self.params = self.model.init_params(rng)
        self.lora_spec = jax.eval_shape(self.model.init_lora, rng)
        self.opt = AdamW(run.lr)
        self.store = splitfl.CohortAdapterStore(
            self.lora_spec, self.opt,
            self.model.init_lora(jax.random.PRNGKey(run.seed + 1)),
            self.params.get("cls_head"), cut_of)
        if dense:
            for u in range(n_clients):
                self.store.materialize(u)
        self.per_cut: Dict[int, CutSteps] = {}
        # cohort-batched server step: ONE vmapped executable with traced
        # per-client cuts serves any chunk (cohort_impl="ragged" instead
        # groups the chunk by cut and runs each group's [cut, L) suffix)
        self._srv_step_batched = splitfl.make_server_step_cls_batched(
            self.model, self.opt, impl=run.engine.cohort_impl)
        # held-out logits; built once so every evaluate() reuses one trace
        self._eval_logits = jax.jit(
            lambda p, lo, b: self.model.loss(p, lo, b, path="scan")[1])
        self.ef_residual: Dict[int, jax.Array] = {}   # int8 uplink feedback
        # causal consistency for in-flight async rounds: the client-side
        # state each (uid, round) pulled at round start, a per-client commit
        # counter, and the local updates discarded because a commit
        # refreshed the client while its round was still in flight
        self.round_pull: dict = {}
        self.version: Dict[int, int] = {}
        self.discarded_updates: List[tuple] = []   # (uid, round)
        self.obs = None

    # ---------------------------------------------------------- per-cut cache
    def at_cut(self, cut: int) -> CutSteps:
        steps = self.per_cut.get(cut)
        if steps is None:
            prefix = dict(self.params)
            prefix["layers"] = lora_lib.slice_stack(self.params["layers"], 0,
                                                    cut)
            fwd, bwd = splitfl.make_client_step(self.model, self.opt, cut,
                                                path="sliced")
            steps = CutSteps(prefix, splitfl.make_server_step_cls(
                self.model, self.opt, path="sliced", static_cut=cut),
                fwd, bwd)
            self.per_cut[cut] = steps
        return steps

    # ------------------------------------------------------------------ serve
    def serve(self, grp: List[int], loader_of: Callable, rnd) -> List[jax.Array]:
        """Run the real jitted math for one server dispatch group: per-client
        batch draw (``loader_of(u).next_batch``, looked up now) + client
        forward (with the int8+EF uplink when ``run.net.quantize``), then
        the sequential server step (size-1 group) or ONE batched dispatch
        (size>1), then each client's backward.  Returns each client's loss
        as a device scalar whose copy to the host has started: reading it
        here would drain the device's queue before the next dispatch.

        The host span ``fed.serve`` covers the group, from the batch draw to
        the last backward dispatch (``repro.obs.host``)."""
        ids = ({"uid": grp[0]} if len(grp) == 1
               else {"uids": " ".join(map(str, grp))})
        with span("fed.serve", round=rnd, **ids):
            return self._serve(grp, loader_of)

    def _serve(self, grp: List[int], loader_of: Callable) -> List[jax.Array]:
        batches, acts, slots = {}, {}, {}
        for u in grp:
            with span("fed.client_fwd", uid=u):
                slots[u] = slot = self.store.materialize(u)
                batch = {k: jnp.asarray(v)
                         for k, v in loader_of(u).next_batch().items()}
                batches[u] = batch
                steps = self.at_cut(self.cut_of(u))
                v = steps.client_fwd(steps.prefix, slot["client_lora"], batch)
                if self.run.net.quantize:
                    # int8 + error-feedback uplink (repro/comm)
                    from repro.comm import dequantize, quantize_with_feedback
                    qx, self.ef_residual[u] = quantize_with_feedback(
                        v, self.ef_residual.get(u))
                    v = dequantize(qx, v.dtype)
                acts[u] = v

        losses: List[jax.Array] = []
        if len(grp) == 1:
            u = grp[0]
            slot, cut = slots[u], self.cut_of(u)
            with span("fed.server_step", cut=cut):
                loss, new_lora, new_head, new_opt, dv = \
                    self.at_cut(cut).server_step(
                        self.params, slot["server_lora"], slot["head"],
                        slot["server_opt"], acts[u], batches[u])
            with span("fed.loss_read"):
                loss.copy_to_host_async()
                losses.append(loss)
            with span("fed.client_bwd", uid=u):
                slot["server_lora"], slot["head"], slot["server_opt"] = \
                    new_lora, new_head, new_opt
                self._client_backward(u, slot, batches[u], dv)
            return losses
        # batched cohort chunk: one vmapped dispatch for the whole group
        cuts = [self.cut_of(u) for u in grp]
        with span("fed.server_step", cut=" ".join(map(str, cuts))):
            loss_g, nl, nh, no, dv_g = self._srv_step_batched(
                self.params,
                lora_lib.stack_trees([slots[u]["server_lora"] for u in grp]),
                jnp.stack([slots[u]["head"] for u in grp]),
                lora_lib.stack_trees([slots[u]["server_opt"] for u in grp]),
                jnp.stack([acts[u] for u in grp]),
                lora_lib.stack_trees([batches[u] for u in grp]),
                jnp.asarray(cuts))
            nls, nos = lora_lib.unstack_tree(nl), lora_lib.unstack_tree(no)
        for i, u in enumerate(grp):
            with span("fed.loss_read"):
                loss = loss_g[i]
                loss.copy_to_host_async()
                losses.append(loss)
            with span("fed.client_bwd", uid=u):
                slot = slots[u]
                slot["server_lora"], slot["head"], slot["server_opt"] = \
                    nls[i], nh[i], nos[i]
                self._client_backward(u, slot, batches[u], dv_g[i])
        return losses

    def _client_backward(self, u: int, slot: dict, batch, dv) -> None:
        if self.run.net.quantize:
            from repro.comm import dequantize, quantize
            dv = dequantize(quantize(dv), dv.dtype)   # downlink int8
        steps = self.at_cut(self.cut_of(u))
        slot["client_lora"], slot["client_opt"] = steps.client_bwd(
            steps.prefix, slot["client_lora"], slot["client_opt"], batch, dv)

    # ------------------------------------------------------ commit race
    def pull(self, u: int, rnd: int) -> None:
        """A client pulls its model copy when it ENTERS a local round; the
        lazily-executed math must use that copy, not whatever a later commit
        redistributed mid-flight."""
        u = int(u)
        slot = self.store.materialize(u)
        self.round_pull[(u, rnd)] = (slot["client_lora"], slot["client_opt"],
                                     self.version.get(u, 0))

    def serve_in_flight(self, uids, rounds,
                        serve: Callable[[List[int]], list]) -> List[float]:
        """``serve(uids)`` with each client's round run on the state it
        pulled at round start; an update whose client a commit refreshed
        while the round was in flight loses the race and is discarded (the
        client continues from the redistributed global; its server-side
        half already serves from the post-commit state).  Returns the
        losses read as floats."""
        uids = [int(u) for u in uids]
        swapped = {}
        for u, r in zip(uids, rounds):
            pull = self.round_pull.pop((u, r), None)
            if pull is not None:
                slot = self.store.materialize(u)
                swapped[u] = (r, pull[2], slot["client_lora"],
                              slot["client_opt"])
                slot["client_lora"], slot["client_opt"] = pull[0], pull[1]
        losses = [float(loss) for loss in serve(uids)]
        for u, (r, pull_version, cur_lora, cur_opt) in swapped.items():
            if self.version.get(u, 0) != pull_version:
                slot = self.store.slot(u)
                slot["client_lora"], slot["client_opt"] = cur_lora, cur_opt
                self.discarded_updates.append((u, r))
                if self.obs is not None and self.obs.metrics is not None:
                    self.obs.metrics.inc("stale_discard")
        return losses

    # ---------------------------------------------------------------- commits
    def full_of(self, state: dict, cut: int):
        """Eq. 5: a client's full-depth adapters, joined at its cut."""
        return lora_lib.assemble_full(
            state["client_lora"],
            lora_lib.split_lora(state["server_lora"], cut)[1], cut)

    def commit_sync(self, data_sizes: Sequence[int], edges=None,
                    decide: Optional[Callable] = None):
        """Barrier Eq. 5-9 commit over the whole fleet (an untouched client
        stands at the global), as four jitted programs, one per ``commit.*``
        span: aggregate at the clients' cuts, re-split at the cuts after
        ``decide()`` (a control decision may move them), FedAvg the heads,
        fresh optimizer states.  The results become the store's per-cut
        templates, shared by the clients at each cut.  Returns what
        ``decide`` returned."""
        uids = range(self.n)
        cuts = [self.cut_of(u) for u in uids]
        states = [self.store.state_of(u) for u in uids]
        w = agg_lib.commit_weights(data_sizes)
        with span("commit.aggregate"):
            if edges is not None:
                # two-tier Eq. 6-8: edge cells partially merge their members,
                # the cloud merges the edge summaries (telescopes to the flat
                # weighted mean)
                agg_full, _, _ = agg_lib.hierarchical_aggregate(
                    [self.full_of(s, k) for s, k in zip(states, cuts)],
                    [float(s) for s in data_sizes],
                    [list(cell) for cell in edges.cells])
            else:
                agg_full = agg_lib.commit_aggregate(
                    [s["client_lora"] for s in states],
                    [s["server_lora"] for s in states], w, tuple(cuts))
        with span("commit.redistribute"):
            decision = decide() if decide is not None else None
            # re-split the aggregate at the (possibly new) cuts, once per
            # cut: its clients share the arrays, which no step donates
            new_cuts = tuple(sorted({self.cut_of(u) for u in uids}))
            clients, servers = agg_lib.commit_redistribute(agg_full, new_cuts)
        with span("commit.heads"):
            head = agg_lib.commit_heads([s["head"] for s in states], w)
        with span("commit.opt_reset"):
            client_opts, server_opt = agg_lib.commit_opt_reset(
                clients, servers[0], head, self.opt)
        self.store.reset_global(agg_full, head, {
            cut: {"client_lora": clients[i], "client_opt": client_opts[i],
                  "server_lora": servers[i], "head": head,
                  "server_opt": server_opt}
            for i, cut in enumerate(new_cuts)})
        if self.dense:
            for u in uids:
                self.store.materialize(u)
        return decision

    def commit_async(self, contribs: Sequence[int], staleness,
                     data_sizes: Sequence[int],
                     decide: Optional[Callable] = None):
        """Async commit: fold the contributors into the standing global with
        staleness-discounted Eq. 6-8 weights, the absent data mass anchored
        on the current global, then redistribute to the contributors only
        (at their cuts after ``decide()``); the rest keep training until
        their own next commit.  Returns what ``decide`` returned."""
        run = self.run
        alpha = 0.0
        if run.agg.policy == "staleness":
            alpha = (0.5 if run.agg.staleness_alpha is None
                     else run.agg.staleness_alpha)
        w = [data_sizes[u] * agg_lib.staleness_discount(s, alpha)
             for u, s in zip(contribs, staleness)]
        anchor = float(sum(data_sizes) - sum(data_sizes[u] for u in contribs))
        states = [self.store.materialize(u) for u in contribs]
        with span("commit.aggregate"):
            full = agg_lib.merge_into_global(
                self.store.global_full,
                [self.full_of(s, self.cut_of(u))
                 for u, s in zip(contribs, states)], w, anchor)
        with span("commit.heads"):
            head = agg_lib.aggregate_full_weighted(
                [self.store.global_head] + [s["head"] for s in states],
                [anchor] + w)
        with span("commit.redistribute"):
            decision = decide() if decide is not None else None
            self.store.set_global(full, head)
            for u in contribs:
                self.store.drop(u)
                # in-flight rounds of u now race
                self.version[u] = self.version.get(u, 0) + 1
        with span("commit.opt_reset"):
            # each contributor re-enters from its cut's template: the
            # re-split global with fresh optimizer states
            for u in contribs:
                self.store.materialize(u)
        return decision

    # ------------------------------------------------------------------- eval
    def global_eval_state(self, data_sizes: Sequence[int]):
        """``(full, head)`` the evaluator scores: the standing global (async
        policies, or no client touched since the last sync commit), the
        traveling set (sl), the weighted fold of the whole fleet
        (``exact``), or the touched clients merged into the global with the
        absent data mass anchored on it."""
        run, store = self.run, self.store
        if run.agg.policy != "sync":
            return store.global_full, store.global_head
        if run.scheme == "sl":
            slot = store.slot(0)
            return slot["server_lora"], slot["head"]
        touched = store.touched()
        if not touched:
            return store.global_full, store.global_head
        if self.exact:
            states = [store.state_of(u) for u in range(self.n)]
            full = agg_lib.aggregate_full(
                [self.full_of(s, self.cut_of(u)) for u, s in enumerate(states)],
                data_sizes)
            w = np.array(data_sizes, np.float64)
            w /= w.sum()
            head = jax.tree.map(
                lambda *hs: sum(float(wi) * h for wi, h in zip(w, hs)),
                *[s["head"] for s in states])
            return full, head
        slots = [store.slot(u) for u in touched]
        w_t = [float(data_sizes[u]) for u in touched]
        absent = float(sum(data_sizes)) - sum(w_t)
        full = agg_lib.merge_into_global(
            store.global_full,
            [self.full_of(s, self.cut_of(u)) for u, s in zip(touched, slots)],
            w_t, absent)
        head = agg_lib.aggregate_full_weighted(
            [store.global_head] + [s["head"] for s in slots], [absent] + w_t)
        return full, head

    def evaluate(self, data_sizes: Sequence[int], max_batches: int = 32):
        """Held-out accuracy and macro-F1 of the global model."""
        if self.test is None:
            raise ValueError("no held-out set was provided")
        full, head = self.global_eval_state(data_sizes)
        params = dict(self.params)
        params["cls_head"] = head
        preds, golds = [], []
        loader = ClassificationLoader(self.test, self.run.batch_size, seed=0)
        for i, batch in enumerate(loader.all_batches()):
            if i >= max_batches:
                break
            logits = self._eval_logits(
                params, full, {k: jnp.asarray(v) for k, v in batch.items()})
            preds.append(np.argmax(np.asarray(logits), -1))
            golds.append(batch["label"])
        pred = np.concatenate(preds)
        gold = np.concatenate(golds)
        return M.accuracy(pred, gold), M.macro_f1(pred, gold)

    def maybe_eval(self, rnd: int, rec: RoundRecord,
                   data_sizes: Sequence[int], verbose: bool, tag: str) -> bool:
        """Per-round eval cadence (every ``eval_every`` rounds and the last;
        none without a held-out set); True means stop training (target
        accuracy reached)."""
        run = self.run
        if ((rnd + 1) % run.eval_every == 0 or rnd == run.rounds - 1) \
                and self.test is not None:
            rec.accuracy, rec.f1 = self.evaluate(data_sizes)
            if verbose:
                print(f"[{tag}] round {rnd+1:4d} "
                      f"t={rec.sim_time_s:9.1f}s loss={rec.mean_loss:.4f} "
                      f"acc={rec.accuracy:.4f} f1={rec.f1:.4f}")
            if (run.target_accuracy is not None
                    and rec.accuracy >= run.target_accuracy):
                return True
        return False
