"""Share of the HBM roofline reached by ``gram_block`` in the query wave.

Least bytes of one wave's call, whatever implements it: the q query rows
and the c cached train rows (column index and value, 4 B each, per slot)
read once, and the q×c block written once.  Time: device time of the
kernel's operations inside ``_engine_step`` programs, per wave.
"""

PROGRAM = "_engine_step"
KERNEL = "gram_block"


def least_bytes(q: int, c: int, slots: int) -> int:
    return (q + c) * slots * (4 + 4) + q * c * 4


def read(run):
    tr = run.trace
    waves = tr.program_runs(PROGRAM)
    t = tr.kernel_s(KERNEL, PROGRAM)
    if not waves or t <= 0:
        return None
    sv, wk = run.config["serving"], run.config["walks"]
    slots = wk["n_walkers"] * (wk["l_max"] + 1)
    least = least_bytes(sv["batch"], sv["capacity"], slots)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / (t / waves)
