"""The first rounds of a federated run, replayed by the plain reference.

One round: every client trains its adapters and classifier on one batch,
each from its own state (the client prefix's forward, the server step's
loss and gradients, the client's backward are together one full-model
step); AdamW updates them; every ``agg_interval`` rounds the sync commit
replaces every client's adapters and classifier by their data-size-weighted
mean and restarts the optimizer.

The full-model step runs layer by layer, one client at a time, so that
a model of some billions of parameters fits beside its weights: the
forward keeps each layer's input, the backward recomputes one layer at a
time.

``fault="half_batch"`` leaves out the second half of every batch and takes
the mean over the rest: a fault planted in the reference put in the
program's place, to read what such a fault does to the compared numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import transformer as T

F32 = jnp.float32


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _pieces(c: dict, cdt):
    """Jitted embedding, layer forward, loss head and layer backward."""
    def fwd(weights, i, lora, x):
        return T.layer(c, _at(weights["layers"], i), _at(lora, i), x, cdt)

    def bwd(weights, i, lora, x, g):
        w = _at(weights["layers"], i)
        _, vjp = jax.vjp(lambda lo, x_: T.layer(c, w, lo, x_, cdt),
                         _at(lora, i), x)
        return vjp(g)

    def top(weights, head, x, labels):
        return jax.value_and_grad(
            lambda h, x_: T.head_loss(c, weights, h, x_, labels, cdt),
            argnums=(0, 1))(head, x)

    return (jax.jit(lambda w, t: T.embed(c, w, t)), jax.jit(fwd),
            jax.jit(top), jax.jit(bwd))


def _grads(c, pieces, weights, params, tokens, labels):
    """Loss and gradients of one client's full-model step."""
    emb, fwd, top, bwd = pieces
    x = emb(weights, tokens)
    inputs = []
    for i in range(T.dims(c)["L"]):
        inputs.append(x)
        x = fwd(weights, i, params["lora"], x)
    loss, (g_head, g_x) = top(weights, params["head"], x, labels)
    g_layers = []
    for i in reversed(range(len(inputs))):
        g_lo, g_x = bwd(weights, i, params["lora"], inputs[i], g_x)
        g_layers.append(g_lo)
    g_lora = jax.tree.map(lambda *ls: jnp.stack(ls[::-1]), *g_layers)
    return loss, {"lora": g_lora, "head": g_head}


def _adam(adam: dict, lr: float, step: int, params, m, v, grads):
    """AdamW without weight decay, as the configuration trains."""
    b1, b2, eps = adam["b1"], adam["b2"], adam["eps"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** step))
        / (jnp.sqrt(v_ / (1 - b2 ** step)) + eps), params, m, v)
    return params, m, v


def replay(c: dict, traffic: dict, seed: int, batches, data_sizes, rounds: int,
           cdt=F32, fault: str | None = None) -> dict:
    """Replay ``rounds`` rounds from the seed on the given batches.

    ``batches[r][u]`` is client u's ``(tokens, labels)`` in round r.
    ``cdt`` is the type matmul operands are rounded to: float32 for the
    reference, narrower for the control; everything else is float32.
    Returns host arrays: ``loss`` (rounds, U); ``grad1``, ``grad_max`` and
    ``change``, each ``{leaf: (U, L or 1)}`` of per-layer norms: the first
    round's gradient, each leaf's largest gradient over the rounds, and the
    change of the trainables after ``rounds`` rounds."""
    n_clients = len(batches[0])
    adam, lr = traffic["adam"], float(traffic["lr"])
    interval = int(traffic["agg_interval"])
    weights = T.init_weights(c, seed)
    start = {"lora": T.init_adapters(c, seed), "head": weights["head"]}
    zeros = jax.tree.map(jnp.zeros_like, start)
    params = [start] * n_clients
    m, v, step = [zeros] * n_clients, [zeros] * n_clients, 0
    pieces = _pieces(c, cdt)
    w = np.asarray(data_sizes, np.float64)
    w = w / w.sum()

    losses, grad1, grad_max = [], None, None
    for r in range(rounds):
        step += 1
        loss_r, grads_r = [], []
        for u, (tokens, labels) in enumerate(batches[r]):
            if fault == "half_batch":
                half = tokens.shape[0] // 2
                tokens, labels = tokens[:half], labels[:half]
            loss, grads = _grads(c, pieces, weights, params[u],
                                 jnp.asarray(tokens), jnp.asarray(labels))
            loss_r.append(float(loss))
            grads_r.append(grads)
            params[u], m[u], v[u] = _adam(adam, lr, step, params[u], m[u],
                                          v[u], grads)
        losses.append(loss_r)
        norms = leaf_norms(grads_r)
        grad1 = norms if r == 0 else grad1
        grad_max = norms if r == 0 else {k: np.maximum(grad_max[k], norms[k])
                                         for k in norms}
        if (r + 1) % interval == 0:
            mean = jax.tree.map(
                lambda *ps: sum(float(wu) * p for wu, p in zip(w, ps)), *params)
            params = [mean] * n_clients
            m, v, step = [zeros] * n_clients, [zeros] * n_clients, 0
    change = leaf_norms([jax.tree.map(lambda a, b: a - b, p, start)
                         for p in params])
    return {"loss": np.asarray(losses, np.float64), "grad1": grad1,
            "grad_max": grad_max, "change": change}


@jax.jit
def _norms(tree):
    """Per-layer Frobenius norms of one client's ``{"lora", "head"}``."""
    def per_layer(a):      # (L, ...) -> (L,)
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)),
                                axis=tuple(range(1, a.ndim))))
    out = {f"{p}.{ab}": per_layer(t) for p, ad in tree["lora"].items()
           for ab, t in ad.items()}
    out["head"] = jnp.sqrt(jnp.sum(jnp.square(tree["head"].astype(F32))))[None]
    return out


def leaf_norms(trees) -> dict:
    """``{leaf: (U, L or 1)}`` host arrays of per-layer norms, one row for
    each client's tree."""
    rows = [_norms(t) for t in trees]
    return {k: np.stack([np.asarray(r[k], np.float64) for r in rows])
            for k in rows[0]}
