"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers use.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program run, named ``jit_<fn>(<id>)``)
and an ``XLA Ops`` line (one event per HLO operation, named by its HLO
text), and a ``/host:CPU`` plane whose Python thread's line (named after
the executable) holds the harness's ``bench.round`` spans and JAX's
dispatches (``PjitFunction(<fn>)``, the blocking ``np.asarray(jax.Array)``
reads).  All share one clock.

An operation contains a dot or a convolution when it is one, or is a
fusion of kind ``kOutput``: on the TPU every output fusion is built around
a convolution, and loop fusions never hold one (checked on the compiled
server step for a described v5e).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
ROUND_SPAN = "bench.round"
_OP = re.compile(r"^%([\w\-\.]+?)(?:\.\d+)? = ")
_KIND = re.compile(r"kind=(k\w+)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
_DOT_OPS = ("convolution", "dot")


def op_class(hlo: str) -> str:
    """``fusion(kOutput)``, ``copy-done``, ... for an ``XLA Ops`` name."""
    m = _OP.match(hlo)
    op = m.group(1) if m else hlo.split(" ", 1)[0]
    kind = _KIND.search(hlo)
    return f"{op}({kind.group(1)})" if kind else op


def has_dot(hlo: str) -> bool:
    m = _OP.match(hlo)
    op = m.group(1) if m else ""
    return "kind=kOutput" in hlo or op in _DOT_OPS


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, hi = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > hi:
            total += end - max(start, hi)
            hi = end
    return total


def _gaps(intervals, lo: float, hi: float):
    """The uncovered stretches of [lo, hi]."""
    out, cur = [], lo
    for start, end in sorted(intervals):
        if start > cur:
            out.append((cur, min(start, hi)))
        cur = max(cur, end)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _host_label(spans, starts, t: float) -> str:
    """The innermost host span of the Python thread around time ``t``."""
    best = None
    i = bisect.bisect_right(starts, t)
    for start, end, name in reversed(spans[max(0, i - 64):i]):
        if start <= t < end and name != ROUND_SPAN:
            if best is None or end - start < best[1] - best[0]:
                best = (start, end, name)
    return best[2] if best else "host: no span"


def reduce_trace(trace_dir, top: int = 10) -> dict:
    """Window, device busy time, per-program and per-operation device
    time, and idle gaps by host activity, from the trace in ``trace_dir``.

    The window runs from the first ``bench.round`` span's start to the
    later of the last one's end and the last device operation's end.
    Times are seconds; device figures are averaged over the chips."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(trace_dir)))
    rounds, host = [], []
    devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:      # the Python thread's line is the
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events]      # one with our spans
                if any(name == ROUND_SPAN for _, _, name in spans):
                    rounds += [sp for sp in spans if sp[2] == ROUND_SPAN]
                    host += [sp for sp in spans if sp[2] != ROUND_SPAN]
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
            mods = [(e.start_ns, e.start_ns + e.duration_ns,
                     _MODULE.match(e.name).group(1))
                    for e in lines["XLA Modules"].events] if "XLA Modules" in lines else []
            devices.append((ops, mods))
    empty = {"rounds": len(rounds), "window_s": 0.0, "busy_s": 0.0,
             "programs": {}, "dot_s": 0.0,
             "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not rounds or not devices:
        return empty
    lo = min(s for s, _, _ in rounds)
    hi = max(max(e for _, e, _ in rounds),
             max((e for ops, _ in devices for _, e, _ in ops), default=lo))
    host.sort()
    starts = [s for s, _, _ in host]

    n = len(devices)
    busy = dot = 0.0
    programs, op_time, gap_time = defaultdict(float), defaultdict(float), defaultdict(float)
    for ops, mods in devices:
        ops = [o for o in ops if o[1] > lo and o[0] < hi]
        ops.sort()
        busy += union_length([(max(s, lo), min(e, hi)) for s, e, _ in ops])
        mods = sorted(m for m in mods if m[1] > lo and m[0] < hi)
        for s, e, name in mods:
            programs[name] += (e - s) / n
        mod_starts = [m[0] for m in mods]
        for s, e, name in ops:
            dur = e - s
            if has_dot(name):
                dot += dur
            j = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= e else "(no program)"
            op_time[f"{prog} {op_class(name)}"] += dur / n
        for a, b in _gaps([(s, e) for s, e, _ in ops], lo, hi):
            gap_time[_host_label(host, starts, (a + b) / 2)] += (b - a) / n

    def top_list(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"rounds": len(rounds), "window_s": (hi - lo) * 1e-9,
            "busy_s": busy / n * 1e-9,
            "programs": {k: v * 1e-9 for k, v in programs.items()},
            "dot_s": dot / n * 1e-9,
            "breakdown": {"device_ops": top_list(op_time),
                          "idle_gaps": top_list(gap_time)}}
