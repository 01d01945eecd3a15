"""The control of the correctness check, at a size a test run holds: the
plain reference computed one precision step below what the configuration
states, put in the program's place, must come out not correct against the
configuration's own limits (on the chip, at the cells' sizes, its readings
are in PERF.md); the reference against itself comes out correct."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.check import readings, verdict
from bench.reference.replay import replay
from bench.traffic import emotion_corpus
from bench_helpers import ROOT

TINY = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "head_dim": 16, "intermediate_size": 128, "vocab_size": 4096,
        "max_position_embeddings": 64}


def _tiny(name, kv):
    c = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    c.update(TINY, num_key_value_heads=kv)
    c["lora"] = dict(c["lora"], rank=4, alpha=8.0)
    return c


@pytest.mark.parametrize("name,kv", [("bert-base", 4)])
def test_control_is_not_correct(name, kv):
    c = _tiny(name, kv)
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    rounds, n_clients, batch, seq = int(c["check"]["rounds"]), 6, 4, 32
    tokens, labels = emotion_corpus(t["corpus"], rounds * n_clients * batch,
                                    seq, c["vocab_size"], seed=7)
    rows = np.arange(len(labels)).reshape(rounds, n_clients, batch)
    batches = [[(tokens[i], labels[i]) for i in r] for r in rows]
    sizes = [40, 90, 30, 120, 60, 75]
    ctl = c["check"]["control"]
    with jax.default_matmul_precision("highest"):
        ref = replay(c, t, 11, batches, sizes, rounds)
        low = replay(c, t, 11, batches, sizes, rounds,
                     cdt=jnp.dtype(ctl["compute"]))
    limits = c["check"]["limits"]
    assert verdict(readings(ref, ref), limits)[0]
    ok, checks = verdict(readings(low, ref), limits)
    assert not ok, checks


def test_float8_rounding_keeps_the_range_and_the_gradient():
    """The control's rounding: the largest operand lands on the type's
    largest value (no overflow to NaN), every value is one of the type's,
    and the gradient passes through the rounding unchanged."""
    from bench.reference.transformer import _cast, matmul

    f8 = jnp.dtype("float8_e4m3fn")
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 3.0
    q, s = _cast(x, f8)
    assert bool(jnp.isfinite(q).all())
    assert float(jnp.max(jnp.abs(q))) == float(jnp.finfo(f8).max)
    np.testing.assert_array_equal(q, q.astype(f8).astype(jnp.float32))
    rel = jnp.abs(q * s - x) / jnp.maximum(jnp.abs(x), 1e-3)
    assert float(jnp.max(rel)) < 0.07          # 3 mantissa bits
    np.testing.assert_allclose(jax.grad(lambda v: jnp.sum(_cast(v, f8)[0] * s))(x),
                               jnp.ones_like(x), rtol=1e-6)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda v: jnp.sum(matmul(v, w, f8)))(x)
    assert float(jnp.min(jnp.abs(g).sum(1))) > 0    # no update lost


def test_float8_rounding_of_a_zero_operand_keeps_its_gradient():
    """LoRA's B starts at zero: its rounding must keep a usable scale, or
    the first step's gradient of B is lost under flush-to-zero."""
    from bench.reference.transformer import matmul

    f8 = jnp.dtype("float8_e4m3fn")
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (16, 4))) * 1e-2
    with jax.default_matmul_precision("highest"):
        # a mean over many tokens makes each cotangent small, as in training
        g = jax.grad(lambda b: 1e-6 * jnp.sum(matmul(h, b, f8)))(jnp.zeros((4, 8)))
        want = jax.grad(lambda b: 1e-6 * jnp.sum(h @ b))(jnp.zeros((4, 8)))
    assert float(jnp.linalg.norm(g - want)) < 0.1 * float(jnp.linalg.norm(want))
