"""Host spans on the profiler's clock.

The tracer of ``repro.obs.tracer`` records the modelled phones, links and
server slots in *simulated* seconds.  The spans here record what the host
really does on the real-math federated path (``fed/simulator.py``): the
round, each dispatch group's serve and each commit, with their parts.  They
are ``jax.profiler.TraceAnnotation`` spans, so they land in a profiler trace
beside the device's operations, on one clock, and are recorded exactly when
a profiler trace is running; otherwise a span costs one constructor call.
There is no option: the two clocks are never mixed in one export.

A span's arguments reuse the identifiers of the simulated tracer's client
track (``uid``, ``round``), so one client's round can be found in both
traces.  A list of ids is one string with spaces between them: the
profiler's encoding of arguments takes ``,``, ``=`` and ``#`` for itself.
``docs/observability.md`` gives the taxonomy and the benchmark metric that
reads each span.
"""
from __future__ import annotations

import jax

__all__ = ["HOST_SPANS", "span"]

# the taxonomy: every name ``span`` is called with, parents before children
HOST_SPANS = (
    "fed.round",            # Simulator.run_round
    "fed.serve",            # one dispatch group of _serve_group
    "fed.client_fwd",       # batch draw + upload + client forward (+ int8 uplink)
    "fed.server_step",      # dispatch of the server step
    "fed.loss_read",        # starts the copy of the client's loss to the host;
                            # the round reads all its losses once, after its commit
    "fed.client_bwd",       # server update applied + client backward
    "fed.commit",           # _commit_sync / _commit_async
    "commit.aggregate",     # split + aggregation of the adapters
    "commit.redistribute",  # control decisions, re-split, embed in full shape
    "commit.heads",         # FedAvg of the classifier heads
    "commit.opt_reset",     # opt.init of every redistributed optimizer
)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` (one of ``HOST_SPANS``) with ``ids`` as its
    arguments; use it as a context manager."""
    return jax.profiler.TraceAnnotation(name, **ids)
