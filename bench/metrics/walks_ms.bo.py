"""Device milliseconds of the walk sampler per pathwise draw: operations
under the ``grf_walks`` name scope inside ``_pathwise_samples_chunked``,
over the runs of that program, from the trace (0 on a program that names
no scopes: ``harness/program.py``)."""

from harness import program

PROGRAM = "_pathwise_samples_chunked"
SCOPE = "grf_walks"


def read(run):
    if not program.names_scopes():
        return 0.0
    n = run.trace.program_runs(PROGRAM)
    t = program.scope_s(run.trace, SCOPE, program=PROGRAM)
    return t / n * 1e3 if n and t > 0 else None
