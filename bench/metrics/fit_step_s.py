"""Seconds per Adam step of the fit: the window over the steps completed
in it (host clock; the window ends at a fit boundary)."""


def read(run):
    steps = run.counts.get("steps")
    return run.window_s / steps if steps else None
