"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, extract memory/cost/roofline terms. No allocation —
inputs are ShapeDtypeStructs; the 512 host devices below are placeholders
for GSPMD partitioning only.
"""
# The VERY FIRST lines — before ANY other import (jax locks the platform and
# the device count on first init).  The placeholder devices are host (CPU)
# devices, so the tool never asks for an attached accelerator:
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, ASSIGNED_SHAPES, get_config,  # noqa: E402
                           get_shape)
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import ShardingPolicy  # noqa: E402
from repro.launch.steps import (build_server_resume_step, build_step,  # noqa: E402
                                resolve_cfg)

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s per link

# Per-arch baseline sharding necessities: grok-1 (314B) cannot hold its
# weights at model-parallel=16 alone (630GB bf16 / 16 = 39GB/chip > HBM),
# so FSDP over the data axis is part of its baseline scheme.
ARCH_BASE_POLICY = {
    "grok-1-314b": {"fsdp": True},
}


def should_skip(arch: str, shape_name: str) -> str | None:
    cfg = get_config(arch)
    if shape_name == "long_500k" and cfg.family == "encdec":
        return "enc-dec over 30s audio windows has no 500k-token decode (DESIGN.md §6)"
    if shape_name in ("decode_32k", "long_500k") and cfg.family == "encoder":
        return "encoder-only model has no decode step"
    return None


def model_flops_global(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D (train), 2*N*D (prefill), 2*N*B (decode);
    N = active params (MoE: routed top-k only)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            policy: ShardingPolicy, out_dir: str, lr: float = 1e-5,
            tag: str = "", cfg_overrides: dict | None = None) -> dict:
    shape = get_shape(shape_name)
    skip = should_skip(arch, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "policy": dataclasses.asdict(policy), "tag": tag,
        "cfg_overrides": cfg_overrides or {},
    }
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    base_cfg = get_config(arch)
    if cfg_overrides:
        base_cfg = base_cfg.with_(**cfg_overrides)
    cfg = resolve_cfg(base_cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size

    t0 = time.time()
    bundle = build_step(base_cfg, shape, mesh, policy, lr=lr)
    lowered = bundle.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = hlo_analysis.analyze(compiled.as_text())

    compute_s = hlo.flops / PEAK_FLOPS
    memory_s = hlo.bytes_accessed / HBM_BW
    collective_s = hlo.collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    mflops = model_flops_global(cfg, shape) / n_chips
    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
        },
        "cost_analysis_raw": {k: ca.get(k) for k in ("flops", "bytes accessed")
                              if k in ca},
        "hlo": {
            "flops_per_device": hlo.flops,
            "bytes_per_device": hlo.bytes_accessed,
            "collective_bytes_per_device": hlo.collective_bytes,
            "collective_breakdown": hlo.collective_breakdown,
            "n_collectives": hlo.n_collectives,
        },
        "roofline": {
            **terms,
            "dominant": dominant,
            "model_flops_per_device": mflops,
            "useful_flops_ratio": (mflops / hlo.flops) if hlo.flops else None,
            "step_time_lower_bound_s": max(terms.values()),
            "mfu_bound": mflops / PEAK_FLOPS / max(terms.values())
            if max(terms.values()) > 0 else None,
        },
    })

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = f"{arch}_{shape_name}_{mesh_name}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def run_server_resume(arch: str, *, batch: int, seq_len: int, multi_pod: bool,
                      policy: ShardingPolicy, out_dir: str, tag: str = "") -> dict:
    """Lower+compile the paper's Alg.1 server step (Eq. 4): resume at a
    TRACED cut from uploaded client activations; ONE executable serves every
    client — the paper's adapter-switching memory story on the pod."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    bundle = build_server_resume_step(cfg, mesh, policy, batch=batch,
                                      seq_len=seq_len)
    lowered = bundle.lower()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = hlo_analysis.analyze(compiled.as_text())
    terms = {"compute_s": hlo.flops / PEAK_FLOPS,
             "memory_s": hlo.bytes_accessed / HBM_BW,
             "collective_s": hlo.collective_bytes / ICI_BW}
    rec = {
        "arch": arch, "shape": f"server_resume_b{batch}_s{seq_len}",
        "mesh": mesh_name, "status": "ok", "tag": tag,
        "policy": dataclasses.asdict(policy),
        "t_compile_s": round(t_compile, 2),
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "output_bytes": mem.output_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "alias_bytes": mem.alias_size_in_bytes,
                   "peak_bytes": mem.argument_size_in_bytes
                   + mem.output_size_in_bytes + mem.temp_size_in_bytes},
        "hlo": {"flops_per_device": hlo.flops,
                "bytes_per_device": hlo.bytes_accessed,
                "collective_bytes_per_device": hlo.collective_bytes,
                "collective_breakdown": hlo.collective_breakdown},
        "roofline": {**terms, "dominant": max(terms, key=terms.get),
                     "model_flops_per_device": None,
                     "useful_flops_ratio": None,
                     "step_time_lower_bound_s": max(terms.values())},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        with open(os.path.join(out_dir,
                               f"{arch}_server-resume_{mesh_name}{suffix}.json"),
                  "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--moe-shard-map", action="store_true")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--attn-impl", default=None, choices=("naive", "chunked"))
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--wkv-impl", default=None, choices=("scan", "chunked"))
    ap.add_argument("--wkv-chunk", type=int, default=None)
    ap.add_argument("--moe-token-chunks", type=int, default=None)
    ap.add_argument("--server-resume", action="store_true",
                    help="lower the Alg.1 server step (traced cut) instead")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--tag", default="", help="suffix for output files")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.server_resume:
        policy = ShardingPolicy(fsdp=args.fsdp, seq_shard=args.seq_shard)
        for arch in ([args.arch] if args.arch else ["granite-3-2b"]):
            rec = run_server_resume(arch, batch=args.batch, seq_len=args.seq,
                                    multi_pod=args.multi_pod, policy=policy,
                                    out_dir=args.out, tag=args.tag)
            r = rec["roofline"]
            print(f"[ok] {arch} server_resume b{args.batch} s{args.seq}: "
                  f"compile={rec['t_compile_s']:.0f}s "
                  f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                  f"compute={r['compute_s']*1e3:.2f}ms mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms")
        return

    overrides = {}
    for key in ("attn_impl", "attn_chunk", "wkv_impl", "wkv_chunk",
                "moe_token_chunks"):
        val = getattr(args, key)
        if val is not None:
            overrides[key] = val

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(ASSIGNED_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        base = dict(fsdp=args.fsdp, seq_shard=args.seq_shard,
                    moe_shard_map=args.moe_shard_map,
                    microbatch=args.microbatch)
        base.update(ARCH_BASE_POLICY.get(arch, {}))
        policy = ShardingPolicy(**base)
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_one(arch, shape, multi_pod=mp, policy=policy,
                                  out_dir=args.out, tag=args.tag,
                                  cfg_overrides=overrides)
                except Exception:
                    failures += 1
                    print(f"[FAIL] {label}")
                    traceback.print_exc()
                    continue
                if rec["status"] == "skipped":
                    print(f"[skip] {label}: {rec['reason']}")
                    continue
                r = rec["roofline"]
                print(f"[ok] {label}: compile={rec['t_compile_s']:.0f}s "
                      f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                      f"compute={r['compute_s']*1e3:.2f}ms "
                      f"mem={r['memory_s']*1e3:.2f}ms "
                      f"coll={r['collective_s']*1e3:.2f}ms "
                      f"dom={r['dominant']} useful={r['useful_flops_ratio'] and round(r['useful_flops_ratio'],3)}")
    if failures:
        raise SystemExit(f"{failures} dry-run failures")


if __name__ == "__main__":
    main()
