"""CG iterations per Adam step, from the fits' own histories."""


def read(run):
    steps = run.counts.get("steps")
    return run.counts["cg_iters"] / steps if steps else None
