"""Device idle inside the program's ``fed.serve`` spans (one per client
served: batch draw, client forward, server step, loss read, client
backward), in ms per round traced: what the serve's host steps leave the
chip waiting.  No ``fed.serve`` span reads as no metric, not as zero."""


def read(ctx):
    tr = ctx["trace"]
    span = tr["spans"].get("fed.serve") if tr else None
    if not span or not tr["rounds"]:
        return None
    return 1000.0 * span["idle_s"] / tr["rounds"]
