"""Algorithm 1 — the memory-efficient SFL training step, as pure JAX.

The three computational pieces of one round:

  client_forward   (Alg.1 l.4, Eq. 3): v_u = f(W_u, R_c^u; x_u)
  server_step      (Alg.1 l.9-11, Eq. 4): resume at the cut on the ONE full
                   model, update R_s^u, emit activation gradients
  client_backward  (Alg.1 l.15): update R_c^u from the activation gradients

Two execution paths, identical semantics (tested against each other):
  * path="sliced": static cut, python loop over owned layers only — what the
    federated simulator runs on CPU;
  * path="scan":   masked lax.scan with a *traced* cut — the production
    form: one compiled executable serves every client (docs/architecture.md).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.lora import STACKED_KEYS
from repro.models import layers as L
from repro.optim.adamw import AdamW

PyTree = Any


def client_forward(model, params_c: PyTree, lora_c: PyTree, batch: dict,
                   cut: int, *, path: str = "sliced"):
    """Eq. 3. ``params_c``/``lora_c`` hold only the client's prefix when
    path='sliced' (their stacked leaves have leading dim == cut)."""
    v, _ = model.forward_hidden(params_c, lora_c, batch, cut=cut,
                                side="client", path=path)
    return v


def client_forward_with_vjp(model, params_c: PyTree, lora_c: PyTree,
                            batch: dict, cut: int, *, path: str = "sliced"):
    """Returns (v, vjp_fn) where vjp_fn(dv) -> grads w.r.t. lora_c."""
    def f(lc):
        return client_forward(model, params_c, lc, batch, cut, path=path)

    v, vjp = jax.vjp(f, lora_c)
    return v, lambda dv: vjp(dv)[0]


def server_loss(model, params: PyTree, lora_s: PyTree, v: jax.Array,
                batch: dict, cut, *, path: str = "sliced"):
    """Eq. 4 + loss: resume the full model at the cut with R_s^u."""
    loss, logits = model.loss(params, lora_s, batch, cut=cut, side="server",
                              path=path, x0=v)
    return loss, logits


def make_server_step(model, opt: AdamW, *, path: str = "sliced",
                     static_cut: Optional[int] = None, donate: bool = True):
    """Build the jitted server step.

    signature: (params, lora_s, opt_state, v, batch, cut) ->
               (loss, new_lora_s, new_opt_state, dv)

    With path='scan' the cut is a traced int32 scalar: ONE executable per
    (arch, batch shape) serves every client — LoRA switching is argument
    swapping, never a recompile (the paper's server-side memory story).
    """
    def step(params, lora_s, opt_state, v, batch, cut):
        def loss_fn(lo, vv):
            loss, _ = server_loss(model, params, lo, vv, batch, cut, path=path)
            return loss

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(lora_s, v)
        g_lora, g_v = grads
        new_lora, new_opt = opt.update(g_lora, opt_state, lora_s)
        return loss, new_lora, new_opt, g_v

    if static_cut is not None:
        step = _bind_cut(step, static_cut)
        return jax.jit(step, donate_argnums=(1, 2) if donate else ())
    return jax.jit(step, donate_argnums=(1, 2) if donate else ())


def make_server_step_cls(model, opt: AdamW, *, path: str = "sliced",
                         static_cut: Optional[int] = None):
    """Server step for classification fine-tuning: the (new, randomly
    initialized) classifier head trains alongside the server-side adapters.

    signature: (params, lora_s, head, opt_state, v, batch[, cut]) ->
               (loss, new_lora_s, new_head, new_opt_state, dv)
    where opt_state is over the pytree {"lora": ..., "head": ...}.
    """
    def step(params, lora_s, head, opt_state, v, batch, cut):
        def loss_fn(trainable, vv):
            pp = dict(params)
            pp["cls_head"] = trainable["head"]
            loss, _ = server_loss(model, pp, trainable["lora"], vv, batch,
                                  cut, path=path)
            return loss

        trainable = {"lora": lora_s, "head": head}
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(trainable, v)
        g_tr, g_v = grads
        new_tr, new_opt = opt.update(g_tr, opt_state, trainable)
        return loss, new_tr["lora"], new_tr["head"], new_opt, g_v

    if static_cut is not None:
        step = _bind_cut(step, static_cut)
    return jax.jit(step)


def _bind_cut(step, cut: int):
    """``step`` with its ``cut`` bound, under its own name: the profiler
    then names the program ``jit_step`` (a bare ``functools.partial`` would
    read ``jit__unknown``)."""
    bound = functools.partial(step, cut=cut)
    bound.__name__ = step.__name__
    return bound


def _chunk_slices(u: int, cohort_chunk: Optional[int]):
    k = u if not cohort_chunk or cohort_chunk <= 0 else min(int(cohort_chunk), u)
    return [slice(lo, min(lo + k, u)) for lo in range(0, u, k)]


def _tree_slice(tree: PyTree, sl: slice) -> PyTree:
    return jax.tree.map(lambda a: a[sl], tree)


def _tree_concat(parts) -> PyTree:
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


# ---------------------------------------------------------------------------
# ragged cohort packing (impl="ragged" of the batched server steps)
# ---------------------------------------------------------------------------

def _cohort_to_layer_major(lora_s: PyTree) -> PyTree:
    """Swap cohort-stacked adapter leaves (G, L, ...) to layer-major
    (L, G, ...), so the sliced path's per-layer indexing hands every
    projection a grouped (G, r, K) adapter — the grouped-kernel dispatch
    contract of ``models.layers.lora_apply``.  Server-only keys (e.g.
    hybrid "shared") stay cohort-stacked: their leaves are already
    (G, r, K)."""
    out = {}
    for key, sub in lora_s.items():
        if key in STACKED_KEYS:
            out[key] = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), sub)
        else:
            out[key] = sub
    return out


def _flatten_cohort(tree: PyTree) -> PyTree:
    """(G, B, ...) leaves -> (G*B, ...): the ragged concat batch."""
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), tree)


def _concrete_cuts(cuts) -> np.ndarray:
    try:
        arr = np.asarray(cuts, dtype=np.int64)
    except Exception:
        raise ValueError(
            "impl='ragged' groups the cohort by CONCRETE cut values (each "
            "distinct cut compiles a static-slice step over only its owned "
            "layers); pass cuts as python ints / numpy — the vmap impl "
            "accepts traced cuts") from None
    if arr.ndim != 1:
        raise ValueError(f"cuts must be a 1-D cohort vector, got {arr.shape}")
    return arr


def _ragged_chunks(cuts: np.ndarray, cohort_chunk: Optional[int]):
    """Group lane indices by cut value (stable), split by cohort_chunk.
    Yields (orig_indices, cut) with indices as python int lists."""
    order = np.argsort(cuts, kind="stable")
    chunks = []
    lo = 0
    while lo < len(order):
        hi = lo
        while hi < len(order) and cuts[order[hi]] == cuts[order[lo]]:
            hi += 1
        grp = order[lo:hi].tolist()
        for sl in _chunk_slices(len(grp), cohort_chunk):
            chunks.append((grp[sl], int(cuts[order[lo]])))
        lo = hi
    return chunks


def _make_server_step_ragged(model, opt: AdamW, *,
                             cohort_chunk: Optional[int] = None,
                             with_head: bool = False):
    """impl="ragged" of the batched server steps: the cohort is grouped by
    cut value and each group runs ONE dispatch over the concatenated
    (G*B, S, d) activation batch — the sliced path executes only layers
    [cut, L) (no masked full-depth scan), and every adapted projection sees
    cohort-grouped (G, r, K) adapters, dispatching to the grouped ragged
    Pallas kernel when ``cfg.lora.impl == 'fused'``.

    Per-client losses are exact: row segments are computationally
    independent, so grad(sum of per-client mean xents) yields each client's
    own gradients; the per-client AdamW update is a vmap.  Known delta vs
    the vmap impl: the sliced path reports no MoE router aux loss (aux=0).
    """
    cfg = model.cfg

    def group_step(params, lora_g, heads_g, opt_g, v_g, batch_g, cut):
        gsz, bsz = v_g.shape[0], v_g.shape[1]
        v_flat = v_g.reshape((gsz * bsz,) + v_g.shape[2:])
        batch_flat = _flatten_cohort(batch_g)

        def loss_fn(trainable, vf):
            lo_lm = _cohort_to_layer_major(
                trainable["lora"] if with_head else trainable)
            if with_head:
                h, _ = model.forward_hidden(params, lo_lm, batch_flat,
                                            cut=cut, side="server",
                                            path="sliced", x0=vf)
                h = L.apply_norm(cfg, params["final_norm"], h)
                with jax.named_scope("cls_pool"):
                    pooled = model.pool(h, batch_flat.get("tokens"))
                    logits = jnp.einsum(
                        "gbd,gdc->gbc",
                        pooled.reshape(gsz, bsz, -1).astype(jnp.float32),
                        trainable["head"])   # per-client heads
                losses = jax.vmap(lambda lg, lb: L.softmax_xent(
                    lg[:, None, :], lb[:, None]))(logits, batch_g["label"])
            else:
                _, logits = model.loss(params, lo_lm, batch_flat, cut=cut,
                                       side="server", path="sliced", x0=vf)
                logits = logits.reshape((gsz, bsz) + logits.shape[1:])
                losses = jax.vmap(L.softmax_xent)(logits, batch_g["targets"])
            return losses.sum(), losses

        # opt_state mirrors the trainable tree: {"lora", "head"} for the
        # classification step, the bare adapter tree for the LM step
        trainable = {"lora": lora_g, "head": heads_g} if with_head else lora_g
        (_, losses), (g_tr, g_v) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(trainable, v_flat)
        new_tr, new_opt = jax.vmap(opt.update)(g_tr, opt_g, trainable)
        dv = g_v.reshape(v_g.shape)
        if with_head:
            return losses, new_tr["lora"], new_tr["head"], new_opt, dv
        return losses, new_tr, new_opt, dv

    jitted = jax.jit(group_step, static_argnames=("cut",))

    def take(tree, idx):
        return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), tree)

    def step(params, lora_s, *rest):
        if with_head:
            heads, opt_state, v, batch, cuts = rest
        else:
            opt_state, v, batch, cuts = rest
            heads = None
        cuts_np = _concrete_cuts(cuts)
        outs, perm = [], []
        for idx_list, cut in _ragged_chunks(cuts_np, cohort_chunk):
            idx = jnp.asarray(idx_list, jnp.int32)
            outs.append(jitted(
                params, take(lora_s, idx),
                jnp.take(heads, idx, axis=0) if with_head else None,
                take(opt_state, idx), jnp.take(v, idx, axis=0),
                take(batch, idx), cut=cut))
            perm.extend(idx_list)
        inv = jnp.asarray(np.argsort(np.asarray(perm)), jnp.int32)
        return take(_tree_concat(outs), inv)   # back to cohort order

    return step


def make_server_step_batched(model, opt: AdamW, *,
                             cohort_chunk: Optional[int] = None,
                             donate: bool = True, impl: str = "vmap"):
    """Cohort-batched server step: ONE vmapped executable advances a whole
    chunk of clients instead of U sequential dispatches.

    signature: (params, lora_s, opt_state, v, batch, cuts) ->
               (losses, new_lora_s, new_opt_state, dv)

    Every argument after ``params`` carries a leading cohort axis U: the
    per-client full-shape server adapters (``lora.embed_in_full_shape`` +
    ``lora.stack_trees``), optimizer states, activations, batches, and an
    int32 ``cuts`` vector.  The cut is *traced* per cohort lane (path='scan'),
    so heterogeneous cuts share the compiled executable.  ``cohort_chunk``
    bounds how many clients are materialized per dispatch — the paper's
    sequential server is exactly ``cohort_chunk=1``.

    ``impl`` selects the execution path (EngineConfig.cohort_impl):
      * "vmap" (default): the masked-scan lane-per-client form above — every
        lane computes all L layers and masks the client prefix;
      * "ragged": cut-grouped concat batches through
        :func:`_make_server_step_ragged` — each group computes only its own
        [cut, L) suffix (the padded-FLOPs win grows with cut spread) and
        feeds cohort-grouped adapters to the grouped Pallas kernel path.
    """
    if impl == "ragged":
        return _make_server_step_ragged(model, opt,
                                        cohort_chunk=cohort_chunk,
                                        with_head=False)
    if impl != "vmap":
        raise KeyError(f"unknown batched-server impl {impl!r}; "
                       f"choose 'vmap' or 'ragged'")
    def one(params, lora_s, opt_state, v, batch, cut):
        def loss_fn(lo, vv):
            loss, _ = server_loss(model, params, lo, vv, batch, cut,
                                  path="scan")
            return loss

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(lora_s, v)
        g_lora, g_v = grads
        new_lora, new_opt = opt.update(g_lora, opt_state, lora_s)
        return loss, new_lora, new_opt, g_v

    vstep = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0))
    jitted = jax.jit(vstep, donate_argnums=(1, 2) if donate else ())

    def step(params, lora_s, opt_state, v, batch, cuts):
        cuts = jnp.asarray(cuts, jnp.int32)
        outs = [jitted(params, _tree_slice(lora_s, sl), _tree_slice(opt_state, sl),
                       v[sl], _tree_slice(batch, sl), cuts[sl])
                for sl in _chunk_slices(int(cuts.shape[0]), cohort_chunk)]
        return _tree_concat(outs)

    return step


def make_server_step_cls_batched(model, opt: AdamW, *,
                                 cohort_chunk: Optional[int] = None,
                                 donate: bool = False, impl: str = "vmap"):
    """Cohort-batched classification server step (per-client heads train
    alongside the server adapters).

    signature: (params, lora_s, heads, opt_state, v, batch, cuts) ->
               (losses, new_lora_s, new_heads, new_opt_state, dv)
    with the same leading cohort axis conventions as
    :func:`make_server_step_batched`; ``opt_state`` is over the stacked
    pytree {"lora": ..., "head": ...}.  ``impl`` as in
    :func:`make_server_step_batched`.
    """
    if impl == "ragged":
        return _make_server_step_ragged(model, opt,
                                        cohort_chunk=cohort_chunk,
                                        with_head=True)
    if impl != "vmap":
        raise KeyError(f"unknown batched-server impl {impl!r}; "
                       f"choose 'vmap' or 'ragged'")
    def one(params, lora_s, head, opt_state, v, batch, cut):
        def loss_fn(trainable, vv):
            pp = dict(params)
            pp["cls_head"] = trainable["head"]
            loss, _ = server_loss(model, pp, trainable["lora"], vv, batch,
                                  cut, path="scan")
            return loss

        trainable = {"lora": lora_s, "head": head}
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(trainable, v)
        g_tr, g_v = grads
        new_tr, new_opt = opt.update(g_tr, opt_state, trainable)
        return loss, new_tr["lora"], new_tr["head"], new_opt, g_v

    vstep = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0))
    jitted = jax.jit(vstep, donate_argnums=(1, 2, 3) if donate else ())

    def step(params, lora_s, heads, opt_state, v, batch, cuts):
        cuts = jnp.asarray(cuts, jnp.int32)
        outs = [jitted(params, _tree_slice(lora_s, sl), heads[sl],
                       _tree_slice(opt_state, sl), v[sl],
                       _tree_slice(batch, sl), cuts[sl])
                for sl in _chunk_slices(int(cuts.shape[0]), cohort_chunk)]
        return _tree_concat(outs)

    return step


def make_client_step(model, opt: AdamW, cut: int, *, path: str = "sliced"):
    """Build the jitted client fwd+bwd pair for a fixed (static) cut.

    forward:  (params_c, lora_c, batch)              -> v
    backward: (params_c, lora_c, opt_state, batch, dv) -> (new_lora_c, new_opt)
    """
    @jax.jit
    def fwd(params_c, lora_c, batch):
        return client_forward(model, params_c, lora_c, batch, cut, path=path)

    @jax.jit
    def bwd(params_c, lora_c, opt_state, batch, dv):
        _, vjp_fn = client_forward_with_vjp(model, params_c, lora_c, batch,
                                            cut, path=path)
        g = vjp_fn(dv)
        new_lora, new_opt = opt.update(g, opt_state, lora_c)
        return new_lora, new_opt

    return fwd, bwd


def make_full_train_step(model, opt: AdamW, *, remat: bool = False,
                         path: str = "scan", donate: bool = True):
    """Centralized LoRA fine-tuning step (cut=0 oracle + production step).

    signature: (params, lora, opt_state, batch) -> (loss, lora, opt_state)
    """
    def step(params, lora, opt_state, batch):
        def loss_fn(lo):
            loss, _ = model.loss(params, lo, batch, cut=0, side="full",
                                 path=path, remat=remat)
            return loss

        loss, g = jax.value_and_grad(loss_fn)(lora)
        new_lora, new_opt = opt.update(g, opt_state, lora)
        return loss, new_lora, new_opt

    return jax.jit(step, donate_argnums=(1, 2) if donate else ())


class CohortAdapterStore:
    """Per-client adapter, head and optimizer state, one slot per client:
    ``(client_lora, client_opt, server_lora, head, server_opt)``.

    It keeps ONE standing global ``(full adapter, head)`` plus a dict of
    slots, and builds a slot from a per-cut TEMPLATE, shared by every
    client at that cut (slots are shallow copies, so their trees alias
    until a training step replaces them).  Who materializes when is the
    caller's choice: ``fed.split_math.SplitMath`` materializes every client
    of a small fleet at once, and at population scale only the clients a
    cohort touches, so 10^4 clients cost a cohort's memory.

    A template comes from the sync commit's jitted programs
    (``reset_global(..., templates)``), or else is built on first use from
    the standing global —

        client_lora = split_lora(global_full, cut)[0]
        server_lora = embed_in_full_shape(split[1], spec, cut, "server")
        opt states  = opt.init(...) on those trees

    — the same pure slice/scatter ops and deterministic ``opt.init``, so
    both give the same bits.

    Two global-update modes follow the two commit families:
      * ``reset_global``  (sync barrier): every client re-enters from the
        new global -> drop ALL slots;
      * ``set_global``    (async): non-contributors keep training on their
        in-flight state -> keep slots, drop only the templates; callers
        re-materialize the contributors via ``drop``.
    """

    def __init__(self, lora_spec, opt: AdamW, global_full, global_head,
                 cut_of):
        self.lora_spec = lora_spec
        self.opt = opt
        self.global_full = global_full
        self.global_head = global_head
        self._cut_of = cut_of            # uid -> cut
        self._slots: dict = {}           # uid -> slot dict
        self._templates: dict = {}       # cut -> template slot
        self._slot_nbytes: dict = {}     # cut -> bytes one slot holds

    # ----------------------------------------------------------- materialize
    def _template(self, cut: int) -> dict:
        tpl = self._templates.get(cut)
        if tpl is None:
            from repro.core import lora as lora_lib
            c, s = lora_lib.split_lora(self.global_full, cut)
            full_shape = lora_lib.embed_in_full_shape(
                s, self.lora_spec, cut, "server")
            tpl = {
                "client_lora": c,
                "client_opt": self.opt.init(c),
                "server_lora": full_shape,
                "head": self.global_head,
                "server_opt": self.opt.init({"lora": full_shape,
                                             "head": self.global_head}),
            }
            self._templates[cut] = tpl
        return tpl

    def materialize(self, u: int) -> dict:
        """The slot for client ``u``, built from the standing global on
        first touch (shallow copy of the cut's template)."""
        u = int(u)
        slot = self._slots.get(u)
        if slot is None:
            slot = dict(self._template(int(self._cut_of(u))))
            self._slots[u] = slot
        return slot

    def slot(self, u: int) -> dict:
        return self._slots[int(u)]

    def state_of(self, u: int) -> dict:
        """Client ``u``'s state: its slot, or for an untouched client its
        cut's template (read only; no slot is made)."""
        slot = self._slots.get(int(u))
        return slot if slot is not None \
            else self._template(int(self._cut_of(u)))

    def touched(self):
        """Materialized uids, ascending."""
        return sorted(self._slots)

    # ---------------------------------------------------------- global swaps
    def drop(self, u: int) -> None:
        self._slots.pop(int(u), None)

    def set_global(self, full, head) -> None:
        """Async commit: new standing global; in-flight slots survive."""
        self.global_full = full
        self.global_head = head
        self._templates.clear()

    def reset_global(self, full, head, templates=None) -> None:
        """Sync barrier commit: new global, every slot re-enters fresh,
        from ``templates`` (cut -> slot) where the commit built them."""
        self.set_global(full, head)
        self._templates.update(templates or {})
        self._slots.clear()

    # ------------------------------------------------------------ accounting
    def slot_nbytes(self, cut: int) -> float:
        """Bytes one materialized slot at ``cut`` holds (adapters + heads +
        optimizer state), measured on the actual template leaves."""
        nb = self._slot_nbytes.get(cut)
        if nb is None:
            tpl = self._template(cut)
            nb = float(sum(leaf.nbytes for leaf in jax.tree.leaves(tpl)))
            self._slot_nbytes[cut] = nb
        return nb

    def resident_nbytes(self) -> float:
        """Bytes all currently materialized slots hold — the cohort-resident
        figure the obs ledger prices per round."""
        return float(sum(self.slot_nbytes(int(self._cut_of(u)))
                         for u in self._slots))
