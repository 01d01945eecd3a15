# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness (deliverable d):

  bench_table1      — paper Table I (memory / round time / convergence)
  bench_scheduling  — §V scheduling comparison (ours/FIFO/WF/optimal)
  bench_control     — adaptive cut control plane vs static on deep fades
  bench_population  — 10^4-client vectorized DES vs per-object (>= 20x)
  bench_kernels     — Pallas kernel wrappers + arithmetic-intensity deltas
  bench_fig2        — Fig. 2 accuracy/F1-vs-time curves (real reduced run)
  roofline          — §Roofline aggregation of the dry-run records

Run all: ``PYTHONPATH=src python -m benchmarks.run``
Skip the slow real-training bench: ``--fast``.

``--artifacts-dir DIR`` additionally writes one machine-readable
``BENCH_<name>.json`` per bench (rows + wall time + backend/device info) so
CI can archive the perf trajectory across commits.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def _git_sha() -> str:
    """HEAD commit of the working tree (with a -dirty suffix when local
    edits would make the number non-reproducible); "unknown" outside git."""
    import subprocess
    try:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=root,
                             capture_output=True, text=True, timeout=10,
                             check=True).stdout.strip()
        dirty = subprocess.run(["git", "status", "--porcelain"], cwd=root,
                               capture_output=True, text=True,
                               timeout=10).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def _config_hash() -> str:
    """Digest of the benchmark harness sources: two artifacts compare
    apples-to-apples iff their config hashes match (any change to what a
    bench measures changes the hash)."""
    import hashlib
    h = hashlib.sha256()
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    for name in sorted(os.listdir(bench_dir)):
        if name.endswith(".py"):
            h.update(name.encode())
            with open(os.path.join(bench_dir, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def _environment_info() -> dict:
    """Provenance fingerprint stamped into every bench artifact: backend/
    device info, git SHA and harness config hash, so the BENCH_*.json
    trajectory is comparable across commits."""
    info = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "git_sha": _git_sha(),
        "config_hash": _config_hash(),
    }
    try:
        import jax
        info["jax"] = jax.__version__
        info["backend"] = jax.default_backend()
        info["devices"] = [str(d) for d in jax.devices()]
    except Exception as e:  # keep artifacts writable even without jax
        info["jax_error"] = repr(e)
    return info


def _peak_rss_bytes() -> int:
    """Lifetime peak RSS of this process (``ru_maxrss`` is KiB on Linux,
    bytes on macOS).  Monotone across sections — per-bench deltas of 0
    mean the section stayed under an earlier section's high-water mark."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return peak if sys.platform == "darwin" else peak * 1024


def _write_artifact(dirpath: str, name: str, rows, elapsed: float,
                    env: dict, error: str | None,
                    peak_rss: int | None = None) -> None:
    os.makedirs(dirpath, exist_ok=True)
    doc = {
        "bench": name,
        "elapsed_s": round(elapsed, 3),
        "status": "failed" if error else "ok",
        "environment": env,
        "rows": [{"name": n, "us_per_call": us, "derived": derived}
                 for n, us, derived in rows],
    }
    if peak_rss is not None:
        doc["peak_rss_bytes"] = peak_rss
    if error:
        doc["error"] = error
    path = os.path.join(dirpath, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip bench_fig2 (real federated training)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--artifacts-dir", default=None,
                    help="write BENCH_<name>.json per bench here")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import (bench_ablations, bench_control, bench_fig2,
                            bench_kernels, bench_population,
                            bench_scheduling, bench_table1, roofline)
    benches = [
        ("table1", bench_table1.run),
        ("scheduling", bench_scheduling.run),
        ("network", bench_scheduling.run_network),
        ("control", bench_control.run),
        ("population", bench_population.run),
        ("kernels", bench_kernels.run),
        ("roofline", roofline.run),
    ]
    if not args.fast:
        benches.insert(3, ("fig2", bench_fig2.run))
        benches.insert(4, ("ablations", bench_ablations.run))
    if args.only:
        benches = [(n, f) for n, f in benches if n == args.only]

    env = _environment_info() if args.artifacts_dir else {}
    rows, failed = [], []
    for name, fn in benches:
        t0 = time.time()
        print(f"== {name} ==", file=sys.stderr)
        bench_rows, error = [], None
        try:
            bench_rows = fn(csv=True)
        except Exception as e:  # report, keep going
            error = repr(e)[:300]
            bench_rows = [(f"{name}_FAILED", 0.0, repr(e)[:120])]
            failed.append(name)
            import traceback
            traceback.print_exc()
        elapsed = time.time() - t0
        peak_rss = _peak_rss_bytes()
        rows.extend(bench_rows)
        if args.artifacts_dir:
            _write_artifact(args.artifacts_dir, name, bench_rows, elapsed,
                            env, error, peak_rss=peak_rss)
        print(f"== {name} done in {elapsed:.1f}s "
              f"(peak RSS {peak_rss / 2**20:.0f} MiB) ==", file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    if failed:   # every bench still ran, but CI must see the breakage
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
