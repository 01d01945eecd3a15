"""Plain reference of the transformer family that the benchmark's
configurations run: an encoder (bert-base), and a dense decoder with
RoPE, RMSNorm and a gated MLP (the granite-3-2b family).

Straightforward ``jax.numpy``, written from the configuration file alone
(``bench/configs/<name>.json``) and importing nothing of the program.  It
follows the configuration as it is run, departures included (pre-LN
blocks, tanh GELU, no Granite multipliers; the file lists them):

    x   = embed[tokens] (+ pos_embed[:S])
    per layer:  x += Wo·attn(q, k, v)  with q/k/v = norm1(x)·W + s·(norm1(x)·Aᵀ)·Bᵀ
                x += mlp(norm2(x))     (gelu(x·Wu)·Wd, or silu(x·Wg)⊙(x·Wu)·Wd)
    logits = norm_f(x)[:, 0] · head,   loss = mean cross-entropy

with LoRA adapters (rank r, scale alpha/r) on the projections the file
names.  ``bench/reference/replay.py`` loads it for a configuration whose
``"reference"`` is ``transformer``, through the interface written there.
It pools the first position, as an encoder's classifier does; under a
causal mask that position sees one token, so a decoder's classifier needs
a module of its own.  Computed in float32 under
``jax.default_matmul_precision("highest")`` by the caller.  ``cdt`` is the
type every matmul operand is rounded to, with a per-tensor scale and
float32 accumulation: float32 for the reference, a narrower type (float8
e4m3) for the control.

Weights are drawn from the seed on the device, in the type the
configuration serves them in, by the recipe the system under test uses
(``PRNGKey(seed)`` split eight ways: embedding, positions, layers, ...,
classifier; each layer's key split into attention and MLP keys; normal
draws scaled by 1/sqrt(fan_in)); the adapters from ``PRNGKey(seed + 1)``
(A ~ N(0, 1/r), B = 0).  Nothing the program made is read.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def dims(c: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    d = c["hidden_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return {"d": d, "L": c["num_hidden_layers"], "H": h, "KV": kv, "hd": hd,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "P": c["max_position_embeddings"], "C": c["num_labels"],
            "r": c["lora"]["rank"],
            "scale": c["lora"]["alpha"] / c["lora"]["rank"],
            "targets": tuple(c["lora"]["targets"]),
            "gated": bool(c["gated_mlp"]), "dtype": jnp.dtype(c["torch_dtype"])}


def proj_shapes(c: dict) -> dict:
    """(fan_in, fan_out) of every projection of one layer."""
    m = dims(c)
    shapes = {"wq": (m["d"], m["H"] * m["hd"]), "wk": (m["d"], m["KV"] * m["hd"]),
              "wv": (m["d"], m["KV"] * m["hd"]), "wo": (m["H"] * m["hd"], m["d"]),
              "wu": (m["d"], m["ff"]), "wd": (m["ff"], m["d"])}
    if m["gated"]:
        shapes["wg"] = (m["d"], m["ff"])
    return shapes


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _norm_params(c: dict, d: int) -> dict:
    if c["norm"] == "rmsnorm":
        return {"scale": jnp.ones((d,), F32)}
    return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}


def _layer_weights(c: dict, key) -> dict:
    sh = proj_shapes(c)
    dt = dims(c)["dtype"]
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    w = {name: _normal(k, sh[name], sh[name][0], dt)
         for name, k in zip(("wq", "wk", "wv", "wo"), ka)}
    w["wu"] = _normal(km[0], sh["wu"], sh["wu"][0], dt)
    w["wd"] = _normal(km[1], sh["wd"], sh["wd"][0], dt)
    if "wg" in sh:
        w["wg"] = _normal(km[2], sh["wg"], sh["wg"][0], dt)
    d = dims(c)["d"]
    w["ln1"], w["ln2"] = _norm_params(c, d), _norm_params(c, d)
    return w


def init_weights(c: dict, seed: int) -> dict:
    """Frozen weights and the initial classifier, in the served type; one
    jitted call on the device.  Layers are stacked on a leading axis."""
    m = dims(c)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, 8)
        w = {"embed": (jax.random.normal(keys[0], (m["V"], m["d"]), F32)
                       * 0.02).astype(m["dtype"])}
        if c["position_embedding_type"] == "learned":
            w["pos_embed"] = (jax.random.normal(keys[1], (m["P"], m["d"]), F32)
                              * 0.02).astype(m["dtype"])
        w["layers"] = jax.vmap(partial(_layer_weights, c))(
            jax.random.split(keys[2], m["L"]))
        w["final_norm"] = _norm_params(c, m["d"])
        w["head"] = _normal(keys[5], (m["d"], m["C"]), m["d"], F32)
        return w

    return draw(jax.random.PRNGKey(seed))


def init_adapters(c: dict, seed: int) -> dict:
    """Initial adapters ``{proj: {"a": (L, r, in), "b": (L, out, r)}}``,
    float32, from ``PRNGKey(seed + 1)``; the i-th adapted projection of a
    layer, in sorted order (attention wk, wo, wq, wv; then MLP wd, wg, wu),
    draws from that layer's key folded with i."""
    m = dims(c)
    sh = proj_shapes(c)
    # the order of a sorted walk of one layer's parameters
    order = [p for p in ("wk", "wo", "wq", "wv", "wd", "wg", "wu")
             if p in m["targets"] and p in sh]

    @jax.jit
    def draw(key):
        k_layers, _ = jax.random.split(key)

        def one(k):
            out = {}
            for i, p in enumerate(order):
                fan_in, fan_out = sh[p]
                out[p] = {"a": jax.random.normal(jax.random.fold_in(k, i),
                                                 (m["r"], fan_in), F32)
                               / math.sqrt(m["r"]),
                          "b": jnp.zeros((fan_out, m["r"]), F32)}
            return out

        return jax.vmap(one)(jax.random.split(k_layers, m["L"]))

    return draw(jax.random.PRNGKey(seed + 1))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _cast(x, cdt):
    """Round a matmul operand to ``cdt``: float32 leaves it; a narrower
    type scales it onto the type's range (the scale is returned), rounds
    it there, and passes the gradient through the rounding unchanged, as
    training in that type keeps a float32 gradient."""
    if cdt == F32:
        return x, None
    top = float(jnp.finfo(cdt).max)
    x = x.astype(F32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / top, 1.0)    # an all-zero B keeps scale 1
    xs = x / s
    q = jnp.clip(xs, -top, top).astype(cdt).astype(F32)
    return xs + jax.lax.stop_gradient(q - xs), s


def einsum(spec: str, x, w, cdt):
    """``einsum(spec, x, w)`` with operands rounded to ``cdt`` and float32
    accumulation."""
    xq, sx = _cast(x, cdt)
    wq, sw = _cast(w, cdt)
    y = jnp.einsum(spec, xq, wq, preferred_element_type=F32)
    if sx is not None:
        y = y * (sx * sw)
    return y


def matmul(x, w, cdt):
    return einsum("...i,ij->...j", x, w, cdt)


def _norm(c, p, x):
    x = x.astype(F32)
    if c["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["rms_norm_eps"])
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + c["layer_norm_eps"]) * p["scale"] + p["bias"]


def _proj(m, x, w, lo, cdt):
    y = matmul(x, w, cdt)
    if lo is not None:
        y = y + m["scale"] * matmul(matmul(x, lo["a"].T, cdt), lo["b"].T, cdt)
    return y


def _rope(x, theta):
    """Rotary embedding on (B, S, H, hd), rotating the two halves."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _act(c, x):
    if c["hidden_act"] == "silu":
        return jax.nn.silu(x)
    if c["hidden_act"] == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"hidden_act {c['hidden_act']!r}")


def layer(c: dict, w: dict, lo: dict, x, cdt, index):
    """One pre-LN block on x (B, S, d); every layer is of one kind, so
    ``index`` is not read."""
    m = dims(c)
    b, s, _ = x.shape
    g = m["H"] // m["KV"]
    get = lo.get
    h = _norm(c, w["ln1"], x)
    q = _proj(m, h, w["wq"], get("wq"), cdt).reshape(b, s, m["KV"], g, m["hd"])
    k = _proj(m, h, w["wk"], get("wk"), cdt).reshape(b, s, m["KV"], m["hd"])
    v = _proj(m, h, w["wv"], get("wv"), cdt).reshape(b, s, m["KV"], m["hd"])
    if c["position_embedding_type"] == "rope":
        q = _rope(q.reshape(b, s, m["H"], m["hd"]), c["rope_theta"]).reshape(q.shape)
        k = _rope(k, c["rope_theta"])
    scores = einsum("bskgd,btkd->bkgst", q, k, cdt) / math.sqrt(m["hd"])
    if c["causal"]:
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = einsum("bkgst,btkd->bskgd", probs, v, cdt)
    ctx = ctx.reshape(b, s, m["H"] * m["hd"])
    x = x.astype(F32) + _proj(m, ctx, w["wo"], get("wo"), cdt)
    h = _norm(c, w["ln2"], x)
    up = _proj(m, h, w["wu"], get("wu"), cdt)
    if m["gated"]:
        up = _act(c, _proj(m, h, w["wg"], get("wg"), cdt)) * up
    else:
        up = _act(c, up)
    down = _proj(m, up, w["wd"], get("wd"), cdt)
    return x + down


def embed(c: dict, w: dict, tokens):
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    if c["position_embedding_type"] == "learned":
        x = x + w["pos_embed"][: tokens.shape[1]].astype(F32)
    return x


def head_loss(c: dict, w: dict, head, x, labels, tokens, cdt):
    """Mean cross-entropy of the pooled first token's logits; the first
    position is the same in every row, so ``tokens`` is not read."""
    h = _norm(c, w["final_norm"], x)[:, 0, :]
    logits = matmul(h, head, cdt)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
