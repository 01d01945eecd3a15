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


def _inputs(c):
    """paper6's traffic, five rounds of six clients' seeded batches of
    4 x 32 tokens, and unequal data sizes."""
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    rounds, n_clients, batch, seq = int(c["check"]["rounds"]), 6, 4, 32
    tokens, labels = emotion_corpus(t["corpus"], rounds * n_clients * batch,
                                    seq, c["vocab_size"], seed=7)
    rows = np.arange(len(labels)).reshape(rounds, n_clients, batch)
    batches = [[(tokens[i], labels[i]) for i in r] for r in rows]
    return t, batches, [40, 90, 30, 120, 60, 75], rounds


@pytest.mark.parametrize("name,kv", [("bert-base", 4)])
def test_control_is_not_correct(name, kv):
    c = _tiny(name, kv)
    t, batches, sizes, rounds = _inputs(c)
    ctl = c["check"]["control"]
    with jax.default_matmul_precision("highest"):
        ref = replay(c, t, 11, batches, sizes, rounds)
        low = replay(c, t, 11, batches, sizes, rounds,
                     cdt=jnp.dtype(ctl["compute"]))
    limits = c["check"]["limits"]
    assert verdict(readings(ref, ref), limits)[0]
    ok, checks = verdict(readings(low, ref), limits)
    assert not ok, checks


# the tiny bert's replay on seed 11, recorded on the CPU before the replay
# took its reference module from the configuration file
RECORDED_LOSS = [
    [2.051889657974243, 2.1683900356292725, 1.7983455657958984,
     2.2997350692749023, 2.178630828857422, 1.7617061138153076],
    [2.500826358795166, 2.768766403198242, 1.5941050052642822,
     1.8551976680755615, 2.2281291484832764, 1.7617425918579102],
    [3.007902145385742, 2.0869171619415283, 1.9318666458129883,
     2.1788315773010254, 1.6066839694976807, 1.9194362163543701],
    [2.0315475463867188, 2.542524576187134, 2.4489083290100098,
     2.917238712310791, 2.37558650970459, 3.1345603466033936],
    [1.893447995185852, 1.9637079238891602, 2.401541233062744,
     2.6645545959472656, 2.0337483882904053, 1.725048303604126]]
# each leaf's norms summed over clients and layers
RECORDED_NORMS = {
    "grad1": {"head": 26.246989965438843, "wk.a": 0.0,
              "wk.b": 31.369006760418415, "wo.a": 0.0,
              "wo.b": 111.03587245941162, "wq.a": 0.0,
              "wq.b": 42.798892229795456, "wv.a": 0.0,
              "wv.b": 132.68638706207275},
    "grad_max": {"head": 34.67106103897095, "wk.a": 0.005000197437766474,
                 "wk.b": 45.217983186244965, "wo.a": 0.022778144862968475,
                 "wo.b": 137.5441029071808, "wq.a": 0.003930512553779408,
                 "wq.b": 55.242202028632164, "wv.a": 0.024817120865918696,
                 "wv.b": 175.0553421974182},
    "change": {"head": 0.0029514612397179008, "wk.a": 0.004144201171584427,
               "wk.b": 0.006602457433473319, "wo.a": 0.006301422603428364,
               "wo.b": 0.008726602303795516, "wq.a": 0.006115915282862261,
               "wq.b": 0.006899687577970326, "wv.a": 0.007543099753092974,
               "wv.b": 0.009517676022369415}}


def test_replay_reads_as_recorded():
    """The reference module that the configuration names replays the tiny
    bert as the hard-wired ``transformer`` did; rtol 1e-6 leaves room only
    for float32's last bits."""
    c = _tiny("bert-base", 4)
    t, batches, sizes, rounds = _inputs(c)
    with jax.default_matmul_precision("highest"):
        ref = replay(c, t, 11, batches, sizes, rounds)
    np.testing.assert_allclose(ref["loss"], RECORDED_LOSS, rtol=1e-6)
    for key, leaves in RECORDED_NORMS.items():
        got = {leaf: float(v.sum()) for leaf, v in ref[key].items()}
        assert sorted(got) == sorted(leaves)
        np.testing.assert_allclose([got[k] for k in sorted(leaves)],
                                   [leaves[k] for k in sorted(leaves)],
                                   rtol=1e-6)


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
