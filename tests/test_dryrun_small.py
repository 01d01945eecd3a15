"""Small-mesh dry-run (deliverable e, test-sized): run the real lowering +
compile + roofline extraction in a SUBPROCESS with 8 forced host devices so
the device count never leaks into this test process."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import get_config, get_shape, reduced
from repro.launch.sharding import ShardingPolicy
from repro.launch.steps import build_step
from repro.launch import hlo_analysis

arch, shape_name = "%(arch)s", "%(shape)s"
cfg = reduced(get_config(arch), n_layers=2, d_model=256)
shape = get_shape(shape_name)
import dataclasses
shape = dataclasses.replace(shape, seq_len=64, global_batch=8)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
bundle = build_step(cfg, shape, mesh, ShardingPolicy())
lowered = bundle.lower()
compiled = lowered.compile()
mem = compiled.memory_analysis()
hlo = hlo_analysis.analyze(compiled.as_text())
print(json.dumps({
    "ok": True,
    "peak": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
    "flops": hlo.flops,
    "bytes": hlo.bytes_accessed,
    "coll": hlo.collective_bytes,
    "n_devices": len(jax.devices()),
}))
"""


def _run(arch, shape):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # host devices only; never the chip
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"arch": arch, "shape": shape}],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    return rec


@pytest.mark.parametrize("arch,shape", [
    ("granite-3-2b", "train_4k"),
    ("qwen3-moe-30b-a3b", "train_4k"),
    ("rwkv6-3b", "decode_32k"),
])
def test_small_mesh_dryrun(arch, shape):
    rec = _run(arch, shape)
    assert rec["ok"] and rec["n_devices"] == 8
    assert rec["flops"] > 0
    assert rec["bytes"] > 0
    assert rec["coll"] > 0          # tensor parallelism must communicate
    assert rec["peak"] > 0


def test_main_process_sees_one_device():
    import jax
    assert len(jax.devices()) == 1
