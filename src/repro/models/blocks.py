"""Per-family residual blocks: dense attention, MoE, RWKV6 (Finch), Mamba2.

Uniform functional interface used by ``repro.models.decoder``:

    init(rng, cfg)                      -> params for ONE layer (unstacked)
    train(cfg, p, lora, x, ctx)        -> (x, aux_loss)
    prefill(cfg, p, lora, x, ctx)      -> (x, cache, aux_loss)
    init_cache(cfg, batch, cache_len)  -> cache pytree for one layer
    decode(cfg, p, lora, x, cache, pos, ctx) -> (x, cache)

``ctx`` is a plain dict: positions, causal, window, moe_groups,
moe_dense_fallback.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Array = jax.Array

# ===========================================================================
# dense attention block (also the MoE attention half and zamba's shared blk)
# ===========================================================================

def dense_init(rng: Array, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.attn_init(k1, cfg),
        "ln2": L.init_norm(cfg),
        "mlp": L.mlp_init(k2, cfg, d_ff),
    }


def _attn_lora(lora):
    return (lora or {}).get("attn")


def dense_train(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    pos = ctx["positions"]
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.qkv_project(cfg, p["attn"], _attn_lora(lora), h, pos)
    a = L.attention_full(q, k, v, causal=ctx["causal"], window=ctx.get("window"),
                         q_pos=pos, k_pos=pos, impl=cfg.attn_impl,
                         chunk=cfg.attn_chunk)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    x = x + L.mlp_apply(cfg, p["mlp"], (lora or {}).get("mlp"), h)
    return x, jnp.float32(0.0)


def dense_init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    shp = (batch, cache_len, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        # quantized KV cache (§Perf, decode is cache-streaming-bound):
        # int8 payload + per-(token, head) f32 absmax scales = ~0.53x bytes
        sshp = (batch, cache_len, cfg.n_kv_heads)
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_scale": jnp.zeros(sshp, jnp.float32),
                "v_scale": jnp.zeros(sshp, jnp.float32)}
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}


def dense_prefill(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    """Same as train but returns the roped K/V as the cache contents."""
    pos = ctx["positions"]
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.qkv_project(cfg, p["attn"], _attn_lora(lora), h, pos)
    a = L.attention_full(q, k, v, causal=ctx["causal"], window=ctx.get("window"),
                         q_pos=pos, k_pos=pos, impl=cfg.attn_impl,
                         chunk=cfg.attn_chunk)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    x = x + L.mlp_apply(cfg, p["mlp"], (lora or {}).get("mlp"), h)
    return x, {"k": k, "v": v}, jnp.float32(0.0)


def _quant_rows(x: Array):
    """x: (B,1,K,D) -> (int8 payload, (B,1,K) scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _decode_attn(cfg: ModelConfig, p: dict, lora, h: Array, cache: dict,
                 pos: Array, ctx: dict):
    """Shared decode-attention body: write this token's K/V, attend, return ctx."""
    window = ctx.get("window")
    cache_len = cache["k"].shape[1]
    positions = pos[None].astype(jnp.int32) if pos.ndim == 0 else pos
    q, k, v = L.qkv_project(cfg, p, lora, h, positions)
    slot = (pos % cache_len) if window is not None else pos
    quantized = "k_scale" in cache
    if quantized:
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        k_new = jax.lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0))
        v_new = jax.lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0))
        ks_new = jax.lax.dynamic_update_slice(cache["k_scale"], ks, (0, slot, 0))
        vs_new = jax.lax.dynamic_update_slice(cache["v_scale"], vs, (0, slot, 0))
        k_read = (k_new.astype(jnp.float32) * ks_new[..., None]).astype(h.dtype)
        v_read = (v_new.astype(jnp.float32) * vs_new[..., None]).astype(h.dtype)
        new_cache = {"k": k_new, "v": v_new, "k_scale": ks_new,
                     "v_scale": vs_new}
    else:
        k_new = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                             (0, slot, 0, 0))
        v_new = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                             (0, slot, 0, 0))
        k_read, v_read = k_new, v_new
        new_cache = {"k": k_new, "v": v_new}
    idx = jnp.arange(cache_len)
    valid = idx < jnp.minimum(pos + 1, cache_len) if window is not None else idx <= pos
    a = L.attention_decode(q, k_read, v_read, valid)
    return a, new_cache


def dense_decode(cfg: ModelConfig, p: dict, lora, x: Array, cache: dict,
                 pos: Array, ctx: dict):
    h = L.apply_norm(cfg, p["ln1"], x)
    a, cache = _decode_attn(cfg, p["attn"], _attn_lora(lora), h, cache, pos, ctx)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    x = x + L.mlp_apply(cfg, p["mlp"], (lora or {}).get("mlp"), h)
    return x, cache


DENSE = dict(init=dense_init, train=dense_train, prefill=dense_prefill,
             decode=dense_decode, init_cache=dense_init_cache)


# ===========================================================================
# MoE block: dense attention + sorted capacity-based top-k expert dispatch
# ===========================================================================

def moe_init(rng: Array, cfg: ModelConfig) -> dict:
    m = cfg.moe
    k1, k2, k3 = jax.random.split(rng, 3)
    d, ff, e = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = jnp.dtype(cfg.dtype)
    gated = cfg.activation in ("silu", "geglu")
    ek = jax.random.split(k2, 3)
    experts = {
        "we_u": (jax.random.normal(ek[0], (e, d, ff), jnp.float32) / math.sqrt(d)).astype(dt),
        "we_d": (jax.random.normal(ek[1], (e, ff, d), jnp.float32) / math.sqrt(ff)).astype(dt),
    }
    if gated:
        experts["we_g"] = (jax.random.normal(ek[2], (e, d, ff), jnp.float32) / math.sqrt(d)).astype(dt)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.attn_init(k1, cfg),
        "ln2": L.init_norm(cfg),
        "wr_router": L.dense_init(k3, d, e, jnp.float32),
        "experts": experts,
    }


def _router(cfg: ModelConfig, p: dict, lora, xg: Array):
    """xg: (T, d) -> normalized top-k gates (T, k) + expert ids (T, k) + probs."""
    scale = cfg.lora.alpha / cfg.lora.rank
    logits = L.lora_apply(xg.astype(jnp.float32), p["wr_router"],
                          (lora or {}).get("wr_router"), scale, impl=cfg.lora.impl)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg.moe.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eidx, probs


def _expert_ffn(cfg: ModelConfig, ex: dict, xec: Array) -> Array:
    """xec: (E, C, d) -> (E, C, d)."""
    up = jnp.einsum("ecd,edf->ecf", xec, ex["we_u"].astype(xec.dtype))
    if "we_g" in ex:
        up = L._act(cfg, jnp.einsum("ecd,edf->ecf", xec, ex["we_g"].astype(xec.dtype))) * up
    else:
        up = L._act(cfg, up)
    return jnp.einsum("ecf,efd->ecd", up, ex["we_d"].astype(xec.dtype))


def _moe_group_sorted(cfg: ModelConfig, p: dict, lora, xg: Array):
    """Capacity-based sorted dispatch within one group. xg: (T, d)."""
    m = cfg.moe
    t, d = xg.shape
    k, e = m.top_k, m.num_experts
    gates, eidx, probs = _router(cfg, p, lora, xg)
    n = t * k
    cap = max(1, int(math.ceil(n / e * m.capacity_factor)))

    flat_e = eidx.reshape(-1)                         # (N,)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)          # (N,)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_in_seg = jnp.arange(n) - seg_start[sorted_e]
    keep = pos_in_seg < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_seg, e * cap)  # OOB -> dropped

    x_sel = xg[order // k]                             # (N, d)
    buf = jnp.zeros((e * cap, d), xg.dtype).at[dest].add(
        x_sel, mode="drop").reshape(e, cap, d)
    y = _expert_ffn(cfg, p["experts"], buf).reshape(e * cap, d)
    y_sorted = jnp.take(y, jnp.minimum(dest, e * cap - 1), axis=0)
    y_sorted = jnp.where(keep[:, None], y_sorted, 0.0)
    g_sorted = flat_g[order].astype(y_sorted.dtype)
    out = jnp.zeros_like(xg).at[order // k].add(y_sorted * g_sorted[:, None])

    # Switch-style load-balance auxiliary loss
    frac = jnp.bincount(flat_e, length=e).astype(jnp.float32) / n
    aux = e * jnp.dot(frac, probs.mean(0)) * m.router_aux_coef
    return out, aux


def _moe_group_dense(cfg: ModelConfig, p: dict, lora, xg: Array):
    """Compute-all-experts fallback for tiny token counts (decode)."""
    m = cfg.moe
    t, d = xg.shape
    gates, eidx, probs = _router(cfg, p, lora, xg)
    y_all = _expert_ffn(cfg, p["experts"], jnp.broadcast_to(xg, (m.num_experts, t, d)))
    onehot = jax.nn.one_hot(eidx, m.num_experts, dtype=xg.dtype)   # (T,k,E)
    comb = jnp.einsum("tke,tk->te", onehot, gates.astype(xg.dtype))
    out = jnp.einsum("etd,te->td", y_all, comb)
    frac = jnp.bincount(eidx.reshape(-1), length=m.num_experts).astype(jnp.float32) / (t * m.top_k)
    aux = m.num_experts * jnp.dot(frac, probs.mean(0)) * m.router_aux_coef
    return out, aux


def moe_mlp(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    if ctx.get("moe_mesh") is not None and not ctx.get("moe_dense_fallback"):
        return moe_mlp_sharded(cfg, p, lora, x, ctx)
    b, s, d = x.shape
    groups = max(1, ctx.get("moe_groups", 1))
    tokens = b * s
    if tokens % groups:
        groups = 1
    xg = x.reshape(groups, tokens // groups, d)
    fn = _moe_group_dense if ctx.get("moe_dense_fallback") else _moe_group_sorted
    out, aux = jax.vmap(lambda xx: fn(cfg, p, lora, xx))(xg)
    return out.reshape(b, s, d), aux.mean()


def moe_mlp_sharded(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    """§Perf shard_map MoE: routing/sort/dispatch stay LOCAL to each
    data shard (no cross-shard sort collectives), the expert FFN is
    column/row-parallel over "model", and the single all-reduce happens
    AFTER the top-k combine on (tokens, d) — ~(top_k*capacity_factor)x less
    wire traffic than reducing the (E*cap, d) expert buffers, and no
    replicated per-group compute."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx["moe_mesh"]
    dp = ctx["moe_dp_axes"]
    b, s, d = x.shape

    moe_p = {"wr_router": p["wr_router"], "experts": p["experts"]}
    moe_lora = {k: v for k, v in (lora or {}).items() if k == "wr_router"}
    p_specs = {
        "wr_router": P(None, None),
        "experts": {
            "we_u": P(None, None, "model"),
            "we_d": P(None, "model", None),
            **({"we_g": P(None, None, "model")} if "we_g" in p["experts"] else {}),
        },
    }
    l_specs = jax.tree.map(lambda _: P(None, None), moe_lora)

    def local_fn(xl, pl_, ll_):
        tl = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(tl, d)
        nchunks = cfg.moe_token_chunks
        if nchunks > 1 and tl % nchunks == 0:
            # scan over token blocks: capacity buffers live one block at a
            # time instead of all tokens at once (peak-memory §Perf knob)
            def blk(_, xb):
                ob, ab = _moe_group_sorted(cfg, pl_, ll_, xb)
                return None, (ob, ab)
            _, (out, aux) = jax.lax.scan(
                blk, None, xf.reshape(nchunks, tl // nchunks, d))
            out, aux = out.reshape(tl, d), aux.mean()
        else:
            out, aux = _moe_group_sorted(cfg, pl_, ll_, xf)
        out = jax.lax.psum(out, "model")      # combine-then-reduce (tokens, d)
        aux = jax.lax.pmean(aux, dp)
        return out.reshape(xl.shape), aux

    batch_ok = b % math.prod(mesh.shape[a] for a in dp) == 0
    x_spec = P(dp if batch_ok else None, None, None)
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, p_specs, l_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, moe_p, moe_lora)
    return out, aux


def moe_train(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    pos = ctx["positions"]
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.qkv_project(cfg, p["attn"], _attn_lora(lora), h, pos)
    a = L.attention_full(q, k, v, causal=ctx["causal"], window=ctx.get("window"),
                         q_pos=pos, k_pos=pos, impl=cfg.attn_impl,
                         chunk=cfg.attn_chunk)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    y, aux = moe_mlp(cfg, p, lora, h, ctx)
    return x + y, aux


def moe_prefill(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    pos = ctx["positions"]
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.qkv_project(cfg, p["attn"], _attn_lora(lora), h, pos)
    a = L.attention_full(q, k, v, causal=ctx["causal"], window=ctx.get("window"),
                         q_pos=pos, k_pos=pos, impl=cfg.attn_impl,
                         chunk=cfg.attn_chunk)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    y, aux = moe_mlp(cfg, p, lora, h, ctx)
    return x + y, {"k": k, "v": v}, aux


def moe_decode(cfg: ModelConfig, p: dict, lora, x: Array, cache: dict,
               pos: Array, ctx: dict):
    h = L.apply_norm(cfg, p["ln1"], x)
    a, cache = _decode_attn(cfg, p["attn"], _attn_lora(lora), h, cache, pos, ctx)
    x = x + L.attn_out(cfg, p["attn"], _attn_lora(lora), a)
    h = L.apply_norm(cfg, p["ln2"], x)
    ctx = dict(ctx, moe_dense_fallback=True)
    y, _ = moe_mlp(cfg, p, lora, h, ctx)
    return x + y, cache


MOE = dict(init=moe_init, train=moe_train, prefill=moe_prefill,
           decode=moe_decode, init_cache=dense_init_cache)


# ===========================================================================
# RWKV6 "Finch" block: time-mix (data-dependent decay WKV) + channel-mix
# ===========================================================================

def _rwkv_dims(cfg: ModelConfig):
    dh = cfg.ssm.head_dim
    return cfg.d_model // dh, dh  # (H, Dh)


def rwkv_init(rng: Array, cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    s = cfg.ssm
    h, dh = _rwkv_dims(cfg)
    ks = jax.random.split(rng, 12)
    dt = jnp.dtype(cfg.dtype)
    tm = {
        "ln": L.init_norm(cfg),
        "mu_x": jnp.zeros((d,), jnp.float32) + 0.5,
        "mu": jnp.zeros((5, d), jnp.float32) + 0.5,
        "w1": L.dense_init(ks[0], d, 5 * s.ddlerp_rank, jnp.float32),
        "w2": (jax.random.normal(ks[1], (5, s.ddlerp_rank, d), jnp.float32) * 0.01),
        "w0": jnp.full((d,), -6.0, jnp.float32),      # decay base (slow decay)
        "wd1": L.dense_init(ks[2], d, s.decay_rank, jnp.float32),
        "wd2": L.dense_init(ks[3], s.decay_rank, d, jnp.float32) * 0.1,
        "u": (jax.random.normal(ks[4], (h, dh), jnp.float32) * 0.5),
        "wr": L.dense_init(ks[5], d, d, dt),
        "wk": L.dense_init(ks[6], d, d, dt),
        "wv": L.dense_init(ks[7], d, d, dt),
        "wg": L.dense_init(ks[8], d, d, dt),
        "wo": L.dense_init(ks[9], d, d, dt),
        "ln_x_scale": jnp.ones((d,), jnp.float32),
        "ln_x_bias": jnp.zeros((d,), jnp.float32),
    }
    cm = {
        "ln": L.init_norm(cfg),
        "mu_k": jnp.zeros((d,), jnp.float32) + 0.5,
        "mu_r": jnp.zeros((d,), jnp.float32) + 0.5,
        "wk": L.dense_init(ks[10], d, ff, dt),
        "wv": L.dense_init(ks[11], ff, d, dt),
        "wr": L.dense_init(jax.random.fold_in(rng, 99), d, d, dt),
    }
    return {"tm": tm, "cm": cm}


def _ddlerp(p: dict, x: Array, x_prev: Array):
    """Data-dependent lerp producing the 5 mixed inputs (w,k,v,r,g)."""
    xx = x_prev - x
    xxx = x + xx * p["mu_x"].astype(x.dtype)
    proj = jnp.tanh(xxx.astype(jnp.float32) @ p["w1"])
    b, s, _ = proj.shape
    proj = proj.reshape(b, s, 5, -1)
    deltas = jnp.einsum("bsfr,frd->bsfd", proj, p["w2"])
    m = p["mu"][None, None] + deltas                   # (B,S,5,d)
    mixed = x[:, :, None, :] + xx[:, :, None, :] * m.astype(x.dtype)
    return [mixed[:, :, i, :] for i in range(5)]


def _tm_projections(cfg: ModelConfig, p: dict, lora, x: Array, x_prev: Array):
    """Everything in the time-mix up to (and excluding) the WKV recurrence."""
    scale = cfg.lora.alpha / cfg.lora.rank
    lget = (lora or {}).get
    h, dh = _rwkv_dims(cfg)
    xw, xk, xv, xr, xg = _ddlerp(p, x, x_prev)
    w = p["w0"] + jnp.tanh(xw.astype(jnp.float32) @ p["wd1"]) @ p["wd2"]
    decay = jnp.exp(-jnp.exp(w))                       # (B,S,d) in (0,1)
    r = L.lora_apply(xr, p["wr"], lget("wr"), scale, impl=cfg.lora.impl)
    k = L.lora_apply(xk, p["wk"], lget("wk"), scale, impl=cfg.lora.impl)
    v = L.lora_apply(xv, p["wv"], lget("wv"), scale, impl=cfg.lora.impl)
    g = jax.nn.silu(L.lora_apply(xg, p["wg"], lget("wg"), scale, impl=cfg.lora.impl))
    b, s, d = x.shape
    shp = (b, s, h, dh)
    return (r.reshape(shp), k.reshape(shp), v.reshape(shp),
            decay.reshape(shp), g)


def wkv_scan(r: Array, k: Array, v: Array, decay: Array, u: Array,
             state: Array):
    """Sequential WKV. r/k/v/decay: (B,S,H,Dh); u: (H,Dh); state: (B,H,Dh,Dh).

    out_t = r_t . (S_{t-1} + u*k_t (x) v_t);  S_t = diag(decay_t) S_{t-1} + k_t (x) v_t
    Returns (out (B,S,H,Dh), final_state).
    """
    def step(s, inp):
        rt, kt, vt, wt = inp                           # (B,H,Dh) each
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        s = wt[..., None] * s + kv
        return s, out

    xs = [jnp.moveaxis(a, 1, 0).astype(jnp.float32) for a in (r, k, v, decay)]
    state, outs = jax.lax.scan(step, state.astype(jnp.float32), tuple(xs))
    return jnp.moveaxis(outs, 0, 1), state             # (B,S,H,Dh)


def wkv_chunked(r: Array, k: Array, v: Array, decay: Array, u: Array,
                state: Array, chunk: int = 16):
    """Chunk-parallel WKV (§Perf): state reads/writes HBM once per CHUNK
    instead of once per step — the jnp mirror of the Pallas kernel's
    VMEM-resident formulation (kernels/rwkv6_scan.py).

    Within a chunk (log-space cumulative decay logP, all exponents of the
    stable factors are <= 0 except k_j * exp(-logP_j), which is bounded by
    the short chunk length):

      out_t = r_t.(P_{t-1} o S0)  +  sum_{j<t} (r_t o P_{t-1}).(k_j / P_j) v_j
              + r_t.(u o k_t) v_t
      S_end = P_C o S0 + sum_j (P_C / P_j o k_j) (x) v_j
    """
    b, s, h, d = r.shape
    pad = (-s) % chunk
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zeros(r), zeros(k), zeros(v)
        decay = 1.0 - zeros(1.0 - decay)               # pad decay with ONES
    nc = (s + pad) // chunk

    def to_chunks(a):   # (B,T,H,D) -> (nc, B, C, H, D)
        return a.reshape(b, nc, chunk, h, d).swapaxes(0, 1).astype(jnp.float32)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, decay))
    logw = jnp.log(jnp.maximum(wc, 1e-38))             # <= 0

    tri_lower = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)  # j < t
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def body(s0, xs):
        rr, kk, vv, lw = xs                            # (B,C,H,D)
        lp = jnp.cumsum(lw, axis=1)                    # logP_t (inclusive)
        lp_prev = lp - lw                              # logP_{t-1}
        a = rr * jnp.exp(lp_prev)                      # (B,C,H,D), stable
        bb = kk * jnp.exp(-lp)                         # bounded by short chunk
        # intra-chunk scores A[t,j] = (a_t . b_j) for j<t, + u-diag for j=t
        scores = jnp.einsum("bthd,bjhd->bhtj", a, bb) * tri_lower[None, None]
        diag = jnp.einsum("bthd,bthd->bht", rr * u[None, None], kk)
        scores = scores + diag[..., :, None] * eye[None, None]
        intra = jnp.einsum("bhtj,bjhd->bthd", scores, vv)
        # inter-chunk: r_t . (P_{t-1} o S0)
        inter = jnp.einsum("bthd,bhdv->bthv", a, s0)
        # state update: S_end = P_C o S0 + sum_j (P_C/P_j o k_j) (x) v_j
        pc = lp[:, -1]                                 # (B,H,D)
        kfac = kk * jnp.exp(pc[:, None] - lp)          # exponents <= 0
        s_new = jnp.exp(pc)[..., None] * s0 + jnp.einsum("bjhd,bjhv->bhdv",
                                                         kfac, vv)
        return s_new, intra + inter

    state, outs = jax.lax.scan(body, state.astype(jnp.float32),
                               (rc, kc, vc, logw))
    out = outs.swapaxes(0, 1).reshape(b, s + pad, h, d)
    return out[:, :s], state


def wkv_apply(cfg: ModelConfig, r, k, v, decay, u, state):
    if cfg.wkv_impl == "chunked":
        return wkv_chunked(r, k, v, decay, u, state, chunk=cfg.wkv_chunk)
    return wkv_scan(r, k, v, decay, u, state)


def _tm_out(cfg: ModelConfig, p: dict, lora, wkv_out: Array, g: Array):
    scale = cfg.lora.alpha / cfg.lora.rank
    b, s, h, dh = wkv_out.shape
    o = L.group_norm(wkv_out.reshape(b, s, h * dh).astype(g.dtype),
                     p["ln_x_scale"], p["ln_x_bias"], n_groups=h)
    return L.lora_apply(o * g, p["wo"], (lora or {}).get("wo"), scale, impl=cfg.lora.impl)


def _shift(x: Array, x_last: Optional[Array] = None):
    """Token shift: x_prev[t] = x[t-1]; first position uses x_last (or 0)."""
    pad = jnp.zeros_like(x[:, :1]) if x_last is None else x_last[:, None]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _cm_apply(cfg: ModelConfig, p: dict, lora, x: Array, x_prev: Array):
    scale = cfg.lora.alpha / cfg.lora.rank
    lget = (lora or {}).get
    xx = x_prev - x
    xk = x + xx * p["mu_k"].astype(x.dtype)
    xr = x + xx * p["mu_r"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(L.lora_apply(xk, p["wk"], lget("wk"), scale, impl=cfg.lora.impl)))
    vv = L.lora_apply(kk, p["wv"], lget("wv"), scale, impl=cfg.lora.impl)
    return jax.nn.sigmoid(L.lora_apply(xr, p["wr"], lget("wr"), scale, impl=cfg.lora.impl)) * vv


def rwkv_train(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    h, dh = _rwkv_dims(cfg)
    b = x.shape[0]
    tm, cm = p["tm"], p["cm"]
    ltm, lcm = (lora or {}).get("tm"), (lora or {}).get("cm")
    hx = L.apply_norm(cfg, tm["ln"], x)
    r, k, v, decay, g = _tm_projections(cfg, tm, ltm, hx, _shift(hx))
    state0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    out, _ = wkv_apply(cfg, r, k, v, decay, tm["u"], state0)
    x = x + _tm_out(cfg, tm, ltm, out.astype(x.dtype), g)
    hx = L.apply_norm(cfg, cm["ln"], x)
    x = x + _cm_apply(cfg, cm, lcm, hx, _shift(hx))
    return x, jnp.float32(0.0)


def rwkv_init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    h, dh = _rwkv_dims(cfg)
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    return {
        "shift_tm": jnp.zeros((batch, d), dt),
        "shift_cm": jnp.zeros((batch, d), dt),
        "s": jnp.zeros((batch, h, dh, dh), jnp.float32),
    }


def rwkv_prefill(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    h, dh = _rwkv_dims(cfg)
    b = x.shape[0]
    tm, cm = p["tm"], p["cm"]
    ltm, lcm = (lora or {}).get("tm"), (lora or {}).get("cm")
    hx = L.apply_norm(cfg, tm["ln"], x)
    shift_tm = hx[:, -1]
    r, k, v, decay, g = _tm_projections(cfg, tm, ltm, hx, _shift(hx))
    state0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    out, state = wkv_apply(cfg, r, k, v, decay, tm["u"], state0)
    x = x + _tm_out(cfg, tm, ltm, out.astype(x.dtype), g)
    hx = L.apply_norm(cfg, cm["ln"], x)
    shift_cm = hx[:, -1]
    x = x + _cm_apply(cfg, cm, lcm, hx, _shift(hx))
    cache = {"shift_tm": shift_tm.astype(jnp.dtype(cfg.dtype)),
             "shift_cm": shift_cm.astype(jnp.dtype(cfg.dtype)), "s": state}
    return x, cache, jnp.float32(0.0)


def rwkv_decode(cfg: ModelConfig, p: dict, lora, x: Array, cache: dict,
                pos: Array, ctx: dict):
    tm, cm = p["tm"], p["cm"]
    ltm, lcm = (lora or {}).get("tm"), (lora or {}).get("cm")
    hx = L.apply_norm(cfg, tm["ln"], x)                # (B,1,d)
    new_shift_tm = hx[:, -1]
    r, k, v, decay, g = _tm_projections(cfg, tm, ltm, hx, cache["shift_tm"][:, None])
    out, state = wkv_scan(r, k, v, decay, tm["u"], cache["s"])
    x = x + _tm_out(cfg, tm, ltm, out.astype(x.dtype), g)
    hx = L.apply_norm(cfg, cm["ln"], x)
    new_shift_cm = hx[:, -1]
    x = x + _cm_apply(cfg, cm, lcm, hx, cache["shift_cm"][:, None])
    cache = {"shift_tm": new_shift_tm.astype(cache["shift_tm"].dtype),
             "shift_cm": new_shift_cm.astype(cache["shift_cm"].dtype), "s": state}
    return x, cache


RWKV = dict(init=rwkv_init, train=rwkv_train, prefill=rwkv_prefill,
            decode=rwkv_decode, init_cache=rwkv_init_cache)


# ===========================================================================
# Mamba2 (SSD) block — zamba2 backbone
# ===========================================================================

def _mamba_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.d_state
    return d_in, nh, conv_ch


def mamba_init(rng: Array, cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in, nh, conv_ch = _mamba_dims(cfg)
    ks = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln": L.init_norm(cfg),
        "in_proj": L.dense_init(ks[0], d, 2 * d_in + 2 * s.d_state + nh, dt),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_ch), jnp.float32) / math.sqrt(s.d_conv)),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": L.init_norm(cfg, d_in),
        "out_proj": L.dense_init(ks[2], d_in, d, dt),
    }


def _mamba_split(cfg: ModelConfig, p: dict, lora, x: Array):
    scale = cfg.lora.alpha / cfg.lora.rank
    s = cfg.ssm
    d_in, nh, _ = _mamba_dims(cfg)
    proj = L.lora_apply(x, p["in_proj"], (lora or {}).get("in_proj"), scale, impl=cfg.lora.impl)
    z, xc, bmat, cmat, dt_raw = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + s.d_state, 2 * d_in + 2 * s.d_state], axis=-1)
    return z, xc, bmat, cmat, dt_raw


def _causal_conv(x: Array, w: Array, b: Array, x_hist: Optional[Array] = None):
    """Depthwise causal conv1d. x: (B,S,C); w: (K,C); x_hist: (B,K-1,C)."""
    kk = w.shape[0]
    pad = jnp.zeros_like(x[:, : kk - 1]) if x_hist is None else x_hist
    xp = jnp.concatenate([pad, x], axis=1).astype(jnp.float32)
    out = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(kk))
    return jax.nn.silu(out + b).astype(x.dtype), xp[:, -(kk - 1):]


def ssd_scan(xh: Array, bmat: Array, cmat: Array, dt: Array, a_log: Array,
             d_skip: Array, state: Array):
    """Mamba2 SSD recurrence.
    xh: (B,S,H,P); bmat/cmat: (B,S,N); dt: (B,S,H); state: (B,H,P,N)."""
    a = -jnp.exp(a_log)                                # (H,)

    def step(s, inp):
        xt, bt, ct, dtt = inp                          # (B,H,P) (B,N) (B,N) (B,H)
        da = jnp.exp(dtt * a)                          # (B,H)
        upd = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt)
        s = da[..., None, None] * s + upd
        yt = jnp.einsum("bhpn,bn->bhp", s, ct) + d_skip[None, :, None] * xt
        return s, yt

    xs = (jnp.moveaxis(xh, 1, 0).astype(jnp.float32),
          jnp.moveaxis(bmat, 1, 0).astype(jnp.float32),
          jnp.moveaxis(cmat, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32))
    state, ys = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), state               # (B,S,H,P)


def ssd_chunked(xh: Array, bmat: Array, cmat: Array, dt: Array, a_log: Array,
                d_skip: Array, state: Array, chunk: int = 16):
    """Chunk-parallel SSD (§Perf): the Mamba2 recurrence in its block
    1-semiseparable form — state hits HBM once per CHUNK instead of once per
    step. Numerically stable for any decay (the scalar per-head log-decay
    differences are always <= 0).

      y_t = exp(lp_t)(S0.C_t) + sum_{j<=t} exp(lp_t-lp_j) (C_t.B_j) dt_j x_j + D x_t
      S_C = exp(lp_C) S0 + sum_j exp(lp_C-lp_j) dt_j x_j (x) B_j
    """
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    a = -jnp.exp(a_log)                                # (H,)
    pad = (-s) % chunk
    if pad:
        z4 = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        xh, bmat, cmat, dt = z4(xh), z4(bmat), z4(cmat), z4(dt)
    nc = (s + pad) // chunk

    def chunks(t):   # (B,T,...) -> (nc,B,C,...)
        return t.reshape((b, nc, chunk) + t.shape[2:]).swapaxes(0, 1).astype(jnp.float32)

    xc, bc, cc, dtc = map(chunks, (xh, bmat, cmat, dt))
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))  # j <= t

    def body(s0, xs):
        xx, bb, ccm, dd = xs              # (B,C,H,P) (B,C,N) (B,C,N) (B,C,H)
        lda = dd * a[None, None]          # log da_t  (B,C,H)
        lp = jnp.cumsum(lda, axis=1)      # (B,C,H)
        decay = jnp.exp(lp)               # <= 1
        # A[t,j] = exp(lp_t - lp_j), j<=t — exponents <= 0, stable
        amat = jnp.exp(jnp.minimum(lp[:, :, None] - lp[:, None, :], 0.0)) \
            * tril[None, :, :, None]      # (B,C,C,H); exponents <= 0 on j<=t
        g = jnp.einsum("btn,bjn->btj", ccm, bb)          # (B,C,C) shared heads
        y_intra = jnp.einsum("btjh,btj,bjh,bjhp->bthp",
                             amat, g, dd, xx)
        y_inter = jnp.einsum("bth,bhpn,btn->bthp", decay, s0, ccm)
        y = y_intra + y_inter + d_skip[None, None, :, None] * xx
        # state: S_C = exp(lp_C) S0 + sum_j exp(lp_C - lp_j) dt_j x_j (x) B_j
        kdec = jnp.exp(lp[:, -1:, :] - lp)               # (B,C,H), <= 1
        s_new = jnp.exp(lp[:, -1])[:, :, None, None] * s0 + jnp.einsum(
            "bjh,bjh,bjhp,bjn->bhpn", kdec, dd, xx, bb)
        return s_new, y

    state, ys = jax.lax.scan(body, state.astype(jnp.float32),
                             (xc, bc, cc, dtc))
    y = ys.swapaxes(0, 1).reshape(b, s + pad, h, p)
    return y[:, :s], state


def ssd_apply(cfg: ModelConfig, xh, bmat, cmat, dt, a_log, d_skip, state):
    if cfg.wkv_impl == "chunked":   # wkv_impl governs both recurrent families
        return ssd_chunked(xh, bmat, cmat, dt, a_log, d_skip, state,
                           chunk=cfg.wkv_chunk)
    return ssd_scan(xh, bmat, cmat, dt, a_log, d_skip, state)


def _mamba_core(cfg: ModelConfig, p: dict, lora, x: Array,
                conv_hist=None, state=None):
    s = cfg.ssm
    d_in, nh, conv_ch = _mamba_dims(cfg)
    b, sq, _ = x.shape
    z, xc, bmat, cmat, dt_raw = _mamba_split(cfg, p, lora, x)
    conv_in = jnp.concatenate([xc, bmat, cmat], axis=-1)
    conv_out, new_hist = _causal_conv(conv_in, p["conv_w"], p["conv_b"], conv_hist)
    xc, bmat, cmat = jnp.split(conv_out, [d_in, d_in + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    xh = xc.reshape(b, sq, nh, s.head_dim)
    if state is None:
        state = jnp.zeros((b, nh, s.head_dim, s.d_state), jnp.float32)
    y, state = ssd_apply(cfg, xh, bmat, cmat, dt, p["a_log"], p["d_skip"], state)
    y = y.reshape(b, sq, d_in).astype(x.dtype)
    y = L.apply_norm(cfg.with_(norm="rmsnorm"), p["norm"], y * jax.nn.silu(z))
    scale = cfg.lora.alpha / cfg.lora.rank
    out = L.lora_apply(y, p["out_proj"], (lora or {}).get("out_proj"), scale, impl=cfg.lora.impl)
    return out, new_hist, state


def mamba_train(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    h = L.apply_norm(cfg, p["ln"], x)
    out, _, _ = _mamba_core(cfg, p, lora, h)
    return x + out, jnp.float32(0.0)


def mamba_init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    s = cfg.ssm
    d_in, nh, conv_ch = _mamba_dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_ch), jnp.float32),
        "s": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
    }


def mamba_prefill(cfg: ModelConfig, p: dict, lora, x: Array, ctx: dict):
    h = L.apply_norm(cfg, p["ln"], x)
    out, hist, state = _mamba_core(cfg, p, lora, h)
    return x + out, {"conv": hist.astype(jnp.float32), "s": state}, jnp.float32(0.0)


def mamba_decode(cfg: ModelConfig, p: dict, lora, x: Array, cache: dict,
                 pos: Array, ctx: dict):
    h = L.apply_norm(cfg, p["ln"], x)
    out, hist, state = _mamba_core(cfg, p, lora, h,
                                   conv_hist=cache["conv"], state=cache["s"])
    return x + out, {"conv": hist.astype(jnp.float32), "s": state}


MAMBA = dict(init=mamba_init, train=mamba_train, prefill=mamba_prefill,
             decode=mamba_decode, init_cache=mamba_init_cache)


BLOCKS = {"dense": DENSE, "moe": MOE, "ssm": RWKV, "hybrid": MAMBA,
          "vlm": DENSE, "encoder": DENSE, "encdec": DENSE}


def get_block(cfg: ModelConfig):
    return BLOCKS[cfg.family]
