"""Seconds per Thompson round: the window over the rounds completed in it
(host clock; the window starts at a refit round and ends at the end of a
round)."""


def read(run):
    rounds = run.counts.get("rounds")
    return run.window_s / rounds if rounds else None
