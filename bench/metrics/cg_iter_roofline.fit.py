"""Share of the HBM roofline reached by one CG iteration of the fit.

Least bytes of one iteration, whatever implements it: Φ_x read once
(column index and value, 4 B each, per slot), the matvec's input and
output blocks [T, R], the CG updates of x, r and p (each read and
written once) and the Jacobi diagonal.  The N×R intermediate of the
scatter is left out: a correct kernel need not make it.  Time per
iteration: device time under the ``cg_solve`` name scope over the
iterations the fits report.
"""


def least_bytes(rows: int, slots: int, rhs: int) -> int:
    phi = rows * slots * (4 + 4)
    matvec_io = 2 * rows * rhs * 4
    updates = 6 * rows * rhs * 4
    return phi + matvec_io + updates + rows * 4


def read(run):
    c = run.counts
    t = run.trace.scope_s("cg_solve")
    if not c.get("cg_iters") or t <= 0:
        return None
    per_iter = t / c["cg_iters"]
    least = least_bytes(c["rows"], c["slots"], c["rhs"])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / per_iter
