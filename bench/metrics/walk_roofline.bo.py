"""Share of the HBM roofline reached by the walk sampler in the pathwise
draw.

Least bytes of one draw's walks (``harness/rooflines.walk_least_bytes``):
the expected moves of every walker from each of the draw's start nodes of
degree >= 1 (``counts["walk_rows"]``), 12 B each, whatever layout holds
the graph.  The training rows' walks repeat rows of that pass and are not
counted again.  Time per draw: device time under the ``grf_walks`` name
scope inside ``_pathwise_samples_chunked`` over that program's runs, as
``walks_ms.bo`` reads it.
"""

from harness import program, rooflines

PROGRAM = "_pathwise_samples_chunked"
SCOPE = "grf_walks"


def read(run):
    n = run.trace.program_runs(PROGRAM)
    t = program.scope_s(run.trace, SCOPE, program=PROGRAM)
    rows = run.counts.get("walk_rows")
    if not n or t <= 0 or not rows:
        return None
    wk = run.config["walks"]
    least = rooflines.walk_least_bytes(
        rows, wk["n_walkers"], wk["p_halt"], wk["l_max"],
        wk.get("scheme", "iid"))
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / (t / n)
