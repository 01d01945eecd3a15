"""Compile the main path's Pallas kernels and the server step for a
described TPU v5e chip, at bert-base's published widths.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned blocks, too much fast memory, a program over 16 GiB), and the
compiled text shows whether the kernels went in as Mosaic calls
(``tpu_custom_call``) or as interpret-mode HLO.  The topology is described
only inside the fixtures below, never while the module is imported: one
process at a time may load the TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.core import splitfl
from repro.kernels import ops
from repro.models import build_model
from repro.optim import AdamW

HBM_BYTES = 16 * 2**30            # one v5e chip
CFG = REGISTRY["bert-base"]
K = N = CFG.d_model               # 768
R = CFG.lora.rank                 # 16
G = 6                             # the paper's fleet, one group per client
M = G * 16 * 128                  # 16 sequences of 128 tokens per client


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_compiled_for_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the kernels went in as interpret-mode HLO, not Mosaic calls"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total <= HBM_BYTES, total


def _fused(x, w, a, b):
    return ops.fused_lora_matmul(x, w, a, b, scale=2.0, interpret=False)


def _grouped(mode):
    def f(x, w, a, b):
        return ops.grouped_lora_matmul(x, w, a, b, group_sizes=(M // G,) * G,
                                       scale=2.0, mode=mode, interpret=False)
    return f


KERNELS = {
    "fused": (_fused, (R, K), (N, R)),
    "grouped-chunk": (_grouped("chunk"), (G, R, K), (G, N, R)),
    "grouped-direct": (_grouped("direct"), (G, R, K), (G, N, R)),
}


@pytest.mark.parametrize("direction", ["forward", "grad"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_lora_kernel_compiles_for_v5e(one_chip, kernel, direction):
    fn, a_shape, b_shape = KERNELS[kernel]
    if direction == "grad":
        fn = jax.grad(lambda *args, f=fn: f(*args).sum(), argnums=(0, 2, 3))
    args = [_spec(s, jnp.float32, one_chip)
            for s in ((M, K), (K, N), a_shape, b_shape)]
    _assert_compiled_for_chip(jax.jit(fn).lower(*args).compile())


def test_fused_server_step_compiles_for_v5e(one_chip, monkeypatch):
    """One client's static-cut classification server step with the fused
    kernels on every adapted projection.  ``_on_cpu`` is what the model path
    asks at trace time; here it must answer as the chip would."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    cfg = CFG.with_(lora=dataclasses.replace(CFG.lora, impl="fused"))
    model = build_model(cfg)
    opt = AdamW(1e-5)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init_params, key)
    lora = jax.eval_shape(model.init_lora, key)
    head = params["cls_head"]
    opt_state = jax.eval_shape(opt.init, {"lora": lora, "head": head})
    v = jax.ShapeDtypeStruct((16, 128, cfg.d_model), jnp.float32)
    batch = {"tokens": jax.ShapeDtypeStruct((16, 128), jnp.int32),
             "label": jax.ShapeDtypeStruct((16,), jnp.int32)}
    args = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                        (params, lora, head, opt_state, v, batch))
    step = splitfl.make_server_step_cls(model, opt, path="sliced",
                                        static_cut=4)
    _assert_compiled_for_chip(step.lower(*args).compile())
