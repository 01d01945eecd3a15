"""The longest round of the window on the host clock around ``run_round``,
in ms: commits and stalls show here."""


def read(ctx):
    if not ctx["durations"]:
        return None
    return 1000.0 * max(ctx["durations"])
