#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13

For each seed, in one process: the program's first rounds as a benchmark
run records them (no measured window), the plain reference's replay, the
control (the reference one precision step below what the configuration
states, put in the program's place) and the reference with half of every batch left out (a
planted fault).  Prints one JSON line per seed with each candidate's
compared numbers.  A state left unchanged reads 1 by construction and needs
no run.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def calibrate(root: Path, workload: str, seeds):
    import jax
    import jax.numpy as jnp
    from bench import driver
    from bench.check import readings
    from bench.reference.replay import replay
    from bench.run import load_cell, use_compile_cache

    use_compile_cache(root)
    cell = load_cell(root, workload)
    c, traffic = cell["config"], cell["traffic"]
    rounds = int(c["check"]["rounds"])
    interval = int(traffic["agg_interval"])
    out = []
    for seed in seeds:
        rec = driver.Recorder(rounds)
        sim = driver.build(c, traffic, seed, rec)
        driver.warm_up(sim, rec, interval * math.ceil(rounds / interval))
        prog = driver.recorded(rec, sim)
        sizes = list(sim.data_sizes)
        del sim
        gc.collect()
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref = replay(c, traffic, seed, prog["batches"], sizes, rounds)
            ctl = replay(c, traffic, seed, prog["batches"], sizes, rounds,
                         cdt=jnp.dtype(c["check"]["control"]["compute"]))
            half = replay(c, traffic, seed, prog["batches"], sizes, rounds,
                          fault="half_batch")
        line = {"workload": workload, "seed": seed,
                "replays_s": time.perf_counter() - t0,
                "program": readings(prog, ref),
                "control": readings(ctl, ref),
                "half_batch": readings(half, ref)}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    calibrate(ROOT, args.workload, args.seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
