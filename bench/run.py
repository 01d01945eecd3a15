#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks for.
The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic file; nothing here is particular to a cell.  One run:

1. places JAX's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
   if set, else ``<checkout>/.jax_cache``) and refuses to run off a TPU;
2. builds one ``repro.fed.Simulator`` from the two files, weights and data
   drawn from ``--seed``, with the program's default engine;
3. warms up in whole aggregation periods through ``run_round``, so every
   program of the window, the commit's included, is compiled: set-up ends
   here (``setup_s`` counts from process start);
4. runs whole aggregation periods until ``--seconds`` have passed (the
   window), then waits for the adapters; with ``--trace 1`` the profiler
   records the first periods of the window, for the per-layer metrics of
   ``bench/metrics/<name>.py``;
5. frees the simulator and replays the first rounds with the plain float32
   reference that the configuration names (``bench/reference/replay.py``,
   ``bench/check.py``): ``correct`` is whether every compared
   number is within its limit.  The numbers, each with its limit, end
   standard error and the result line.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (rounds in the window, and those whose mean
loss was not finite), ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    # run as a script: import this checkout's ``bench`` and ``repro``, and
    # keep bench/ itself off the path (bench/trace.py is not stdlib trace)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    # the TPU runtime's logs would otherwise go to a fixed /tmp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Backend compiles (persistent-cache hits included) while entered,
    from ``jax.monitoring``'s compile spans."""

    def __init__(self):
        self.n = 0

    def _on_span(self, event, *_, **__):
        if event == BACKEND_COMPILE:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_time_span_listener(self._on_span)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_time_span_listener(self._on_span)


def use_compile_cache(root: Path) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    the fixed ``<root>/.jax_cache``, so a later run of the checkout finds
    what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def program_at(src: Path, anywhere: bool = False) -> bool:
    """Whether the system under test imports from ``src`` (from anywhere
    on the path, with ``anywhere``)."""
    try:
        spec = importlib.util.find_spec("repro.fed")
    except ImportError:
        return False
    if spec is None or spec.origin is None:
        return False
    return anywhere or Path(spec.origin).resolve().is_relative_to(src.resolve())


def load_cell(root: Path, name: str) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    from bench.traffic import load_traffic
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = load_traffic(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_reader(root: Path, metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peak_bytes(device) -> int:
    """An upper bound on the device's peak memory since the process began:
    its peak of live buffers plus its peak reservation for program
    temporaries.  ``peak_bytes_in_use`` alone misses temporaries: a program
    with 1 GiB of them left it unchanged on a v5e, while
    ``peak_bytes_reserved`` rose by exactly 1 GiB.  The two peaks need not
    fall at one instant, and set-up's are among them."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


class TracedRounds:
    """The profiler over the window's first whole aggregation periods that
    last ``TRACE_S`` or more: a trace of every round of a 30 s window would
    run to gigabytes (a round of a 2B-parameter decoder writes about 75 MB)."""

    TRACE_S = 2.0

    def __init__(self, period: int):
        self.period = period
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python tracing would slow the host
        opts.host_tracer_level = 2        # dispatches and our annotations
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def after_round(self, n: int, elapsed_s: float):
        if self.on and n % self.period == 0 and elapsed_s >= self.TRACE_S:
            self.stop()

    def stop(self):
        if self.on:
            import jax
            jax.block_until_ready(jax.live_arrays())
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> dict:
        from bench.trace import reduce_trace
        try:
            return reduce_trace(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
        peaks: dict | None = None, t_start: float = T_START) -> dict | None:
    """One run; returns the result (also printed), or None when refused."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be >= 0")

    if not program_at(root / "src", anywhere=not require_tpu):
        print(f"bench: the system under test (repro) is not importable from "
              f"{root / 'src'}", file=sys.stderr)
        return None
    cell = load_cell(root, args.workload)
    c, traffic = cell["config"], cell["traffic"]
    from bench.reference.replay import reference, replay
    reference(c, root)          # refuse an unknown reference before the run
    use_compile_cache(root)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < cell["cell"]["chips"]):
        print(f"bench: {args.workload} needs {cell['cell']['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return None
    from bench.peaks import peaks_for
    peaks = peaks if peaks is not None else peaks_for(dev.device_kind)

    from bench import driver
    flops_mod = importlib.import_module(f"bench.flops.{c['family']}")
    work = flops_mod.round_work(c, traffic)

    check_rounds = int(c["check"]["rounds"])
    interval = int(traffic["agg_interval"])
    warm_rounds = interval * math.ceil(check_rounds / interval)
    rec = driver.Recorder(check_rounds)
    counter = CompileCounter()
    with counter:
        sim = driver.build(c, traffic, args.seed, rec)
        driver.warm_up(sim, rec, warm_rounds)
        setup_s = time.perf_counter() - t_start
        compiles_setup = counter.n

        tracer = TracedRounds(interval) if args.trace else None
        if tracer:
            tracer.start()
        n_rounds, window_s, durations = driver.window(
            sim, warm_rounds, args.seconds, interval,
            after_round=tracer.after_round if tracer else None)
        if tracer:
            tracer.stop()
        compiles_window = counter.n - compiles_setup

    history = sim.history[warm_rounds:]
    failed = sum(1 for h in history if not math.isfinite(h.mean_loss))
    mem_peak = max(peak_bytes(d) for d in devices[:cell["cell"]["chips"]])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}

    result = {"correct": False, "attempted": n_rounds, "failed": failed,
              "metrics": {}, "device": device}
    ctx = {"rounds": n_rounds, "window_s": window_s, "durations": durations,
           "compiles_in_window": compiles_window, "work": work,
           "peaks": peaks, "trace": None}
    if tracer:
        ctx["trace"] = tracer.reduce()
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
        for m in cell["per_layer"]:
            value = load_reader(root, m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"tokens_per_s": work["tokens"] * n_rounds / window_s,
               "mfu": 100.0 * work["flops"] * n_rounds / window_s
                      / peaks["bf16_flops_per_s"],
               "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    # correctness: the program's first rounds against the reference
    program = driver.recorded(rec, sim)
    data_sizes = list(sim.data_sizes)
    del sim
    gc.collect()
    from bench.check import readings, verdict
    t_ref = time.perf_counter()
    if program is None:
        values = {k: math.inf for k in c["check"]["limits"]}
    else:
        with jax.default_matmul_precision("highest"):
            ref = replay(c, traffic, args.seed, program["batches"], data_sizes,
                         check_rounds, root=root)
        values = readings(program, ref)
    print(f"bench: set-up {setup_s:.1f} s, window {window_s:.1f} s "
          f"({n_rounds} rounds), reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    ok, checks = verdict(values, c["check"]["limits"])
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    for name, chk in checks.items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


def main() -> int:
    return 0 if run() is not None else 2


if __name__ == "__main__":
    sys.exit(main())
