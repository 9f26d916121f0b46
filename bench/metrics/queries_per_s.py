"""Query requests completed in the window over the window (host clock)."""


def read(run):
    done = run.counts.get("completed_in_window")
    return done / run.window_s if done else None
