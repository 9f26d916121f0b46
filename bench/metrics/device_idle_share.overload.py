"""Share of the traced window in which no operation ran on the device."""


def read(run):
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
