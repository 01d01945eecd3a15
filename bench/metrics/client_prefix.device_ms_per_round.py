"""Device time of the client prefix's programs per round, in ms: the
jitted ``fwd`` and ``bwd`` of ``core/splitfl.make_client_step``, named as
the trace shows them.  A rename reads as no metric, not as zero."""

PROGRAMS = ("jit_fwd", "jit_bwd")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["rounds"]:
        return None
    found = [s for name, s in tr["programs"].items() if name in PROGRAMS]
    if not found:
        return None
    return 1000.0 * sum(found) / tr["rounds"]
