"""Device idle inside the program's ``fed.commit`` spans, in ms per commit
traced: what the sync commit's host work (its dispatches and their output
buffers) leaves the chip waiting.  No ``fed.commit`` span in the traced
window reads as no metric, not as zero."""


def read(ctx):
    tr = ctx["trace"]
    span = tr["spans"].get("fed.commit") if tr else None
    if not span or not span["count"]:
        return None
    return 1000.0 * span["idle_s"] / span["count"]
