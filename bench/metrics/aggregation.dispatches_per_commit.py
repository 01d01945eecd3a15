"""Outermost ``PjitFunction`` dispatches inside the program's ``fed.commit``
spans, per commit traced: one per jitted program of the commit, many where
it runs eagerly.  No ``fed.commit`` span reads as no metric, not as zero."""


def read(ctx):
    tr = ctx["trace"]
    span = tr["spans"].get("fed.commit") if tr else None
    if not span or not span["count"]:
        return None
    return span["dispatches"] / span["count"]
