"""Device milliseconds of the K-hat matvec's scatter (Φᵀv) per CG
iteration of the fit: operations under the ``grf_phi_t`` name scope
nested inside ``cg_solve``, over the CG iterations the fits report (the
divisor of ``cg_iter_roofline.fit``), from the trace (0 on a program that
names no scopes: ``harness/program.py``)."""

from harness import program

SCOPE = "grf_phi_t"
WITHIN = "cg_solve"


def read(run):
    if not program.names_scopes():
        return 0.0
    iters = run.counts.get("cg_iters")
    t = program.scope_s(run.trace, SCOPE, within=WITHIN)
    return t / iters * 1e3 if iters and t > 0 else None
