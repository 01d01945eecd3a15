"""Reduction of the program's own host spans (``repro.obs.host``) for
per-layer readers.

The program wraps its round, each client's serve and each commit in
``jax.profiler.TraceAnnotation`` spans named ``fed.*`` and ``commit.*``.
They sit on the Python thread's line of the host plane, beside JAX's
dispatches, on the device trace's clock.  For each span name this gives
how often it ran, its host time, and the device idle inside it; a span
that the trace lacks is absent, so a reader of a renamed span reads no
metric rather than zero.

``bench/trace.reduce_trace`` does not call it yet: it would pass the
Python thread's events, each chip's operations and its window, as
``tests/bench/test_bench_spans.py`` does.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

PREFIXES = ("fed.", "commit.")
DISPATCH = "PjitFunction("


def _merge(intervals):
    """``intervals`` as sorted, disjoint ``[(start, end), ...]``."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _length(merged) -> float:
    return sum(end - start for start, end in merged)


def _overlap(a, b) -> float:
    """Length common to two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _minus(a, b):
    """Merged list ``a`` with merged list ``b`` taken out."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _inside(points, merged) -> int:
    """How many of the sorted ``points`` fall in a merged interval list."""
    return sum(bisect.bisect_left(points, end) - bisect.bisect_left(points, start)
               for start, end in merged)


def _idle(region, busy) -> float:
    """Device idle inside ``region``, averaged over the chips' ``busy``."""
    if not busy:
        return 0.0
    length = _length(region)
    return sum(length - _overlap(region, b) for b in busy) / len(busy)


def reduce_spans(host_events, device_ops, lo: float, hi: float) -> dict:
    """Per program span name inside the window ``[lo, hi]``:

    - ``count``: spans that overlap the window;
    - ``host_s``: their summed duration, clipped to the window;
    - ``durations``: each one's clipped duration;
    - ``idle_s``: device idle inside them, the complement of the union of
      device operations, averaged over chips;
    - ``self_idle_s``: the part of that idle outside every child program
      span (a program span nested directly inside one of them);
    - ``dispatches``: the outermost ``PjitFunction(...)`` host events that
      start inside them (JAX nests these in pairs; a pair counts once).

    ``host_events`` are ``(start, end, name)`` of the Python thread in
    nanoseconds; ``device_ops`` holds each chip's ``(start, end, ...)``
    operations.  Seconds out; ``{}`` when no program span is in the window.
    """
    spans = sorted(((max(s, lo), min(e, hi), n) for s, e, n in host_events
                    if n.startswith(PREFIXES) and e > lo and s < hi),
                   key=lambda sp: (sp[0], -sp[1]))
    if not spans:
        return {}
    busy = [_merge((max(op[0], lo), min(op[1], hi)) for op in ops
                   if op[1] > lo and op[0] < hi) for ops in device_ops]

    # each span's direct children: walk the nesting with a stack
    children = defaultdict(list)
    stack = []
    for i, (start, end, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= spans[stack[-1]][1]:
            children[stack[-1]].append((start, end))
        stack.append(i)

    # outermost dispatches: those not inside an earlier dispatch
    starts, reach = [], float("-inf")
    for s, e, n in sorted((s, e, n) for s, e, n in host_events
                          if n.startswith(DISPATCH)):
        if s >= reach:
            starts.append(s)
        reach = max(reach, e)

    by_name = defaultdict(list)
    for i, sp in enumerate(spans):
        by_name[sp[2]].append(i)
    out = {}
    for name, idx in sorted(by_name.items()):
        region = _merge(spans[i][:2] for i in idx)
        own = _minus(region, _merge(c for i in idx for c in children[i]))
        durations = [(spans[i][1] - spans[i][0]) * 1e-9 for i in idx]
        out[name] = {
            "count": len(idx),
            "host_s": sum(durations),
            "durations": durations,
            "idle_s": _idle(region, busy) * 1e-9,
            "self_idle_s": _idle(own, busy) * 1e-9,
            "dispatches": _inside(starts, region),
        }
    return out
