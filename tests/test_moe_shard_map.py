"""shard_map MoE (§Perf path) vs the reference vmapped dispatch — numeric
equivalence under a real 8-device mesh, in a subprocess so the forced device
count never leaks into the main test process."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import REGISTRY, reduced
from repro.models import build_model

cfg = reduced(REGISTRY["qwen3-moe-30b-a3b"], n_layers=2, d_model=256)
model = build_model(cfg)
rng = jax.random.PRNGKey(0)
params = model.init_params(rng)
lora = model.init_lora(jax.random.PRNGKey(1))
B, S = 8, 16
batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}

mesh = jax.make_mesh((2, 4), ("data", "model"))

with mesh:
    ctx_ref = model.make_ctx(S, moe_groups=2)
    loss_ref, _ = jax.jit(lambda p, lo, b: model.loss(p, lo, b, ctx=ctx_ref))(
        params, lora, batch)
    ctx_sm = model.make_ctx(S, moe_mesh=mesh, moe_dp_axes=("data",))
    loss_sm, _ = jax.jit(lambda p, lo, b: model.loss(p, lo, b, ctx=ctx_sm))(
        params, lora, batch)

    # gradients through the shard_map path
    def gfn(lo):
        loss, _ = model.loss(params, lo, batch, ctx=ctx_sm)
        return loss
    g = jax.jit(jax.grad(gfn))(lora)
    gnorm = float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(g)))

print(json.dumps({"ref": float(loss_ref), "sm": float(loss_sm),
                  "gnorm": gnorm}))
"""


def test_moe_shard_map_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # host devices only; never the chip
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # dispatch grouping differs (2 groups vs per-shard); token order within
    # capacity buffers can drop different tokens only if over capacity —
    # the reduced config is under-capacity, so losses must match closely
    assert abs(rec["ref"] - rec["sm"]) < 5e-3, rec
    assert rec["gnorm"] > 0, "no gradient flow through shard_map MoE"
