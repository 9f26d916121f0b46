"""Device milliseconds of the Φ products per pathwise draw: operations
under the ``grf_phi`` (gather, Φu) or ``grf_phi_t`` (scatter, Φᵀv) name
scope inside ``_pathwise_samples_chunked``, over the runs of that program,
from the trace (0 on a program that names no scopes:
``harness/program.py``)."""

from harness import program

PROGRAM = "_pathwise_samples_chunked"
SCOPES = ("grf_phi", "grf_phi_t")


def read(run):
    if not program.names_scopes():
        return 0.0
    n = run.trace.program_runs(PROGRAM)
    t = program.scope_s(run.trace, SCOPES, program=PROGRAM)
    return t / n * 1e3 if n and t > 0 else None
