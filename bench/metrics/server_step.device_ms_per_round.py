"""Device time of the server-step programs per round, in ms.

The programs are named as the trace shows them: the jitted ``step`` of
``core/splitfl.make_server_step_cls``, one per distinct cut.  The program
jits it as a ``functools.partial`` over the cut, which JAX names
``_unknown`` (``jit__unknown`` in the trace); ``jit_step`` is what a
stable name would read.  A rename reads as no metric, not as zero."""

PROGRAMS = ("jit__unknown", "jit_step")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["rounds"]:
        return None
    found = [s for name, s in tr["programs"].items() if name in PROGRAMS]
    if not found:
        return None
    return 1000.0 * sum(found) / tr["rounds"]
