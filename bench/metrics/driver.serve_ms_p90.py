"""The 90th percentile, by nearest rank, of the program's ``fed.serve``
span durations in the traced window, one per client served, in ms.  No
``fed.serve`` span reads as no metric, not as zero."""

import math


def read(ctx):
    tr = ctx["trace"]
    span = tr["spans"].get("fed.serve") if tr else None
    if not span or not span["durations"]:
        return None
    durations = sorted(span["durations"])
    return 1000.0 * durations[math.ceil(0.9 * len(durations)) - 1]
