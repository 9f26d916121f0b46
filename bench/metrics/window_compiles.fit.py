"""Compiles inside the traced window, as the program counts them
(``repro.obs.compiles``; a persistent-cache hit counts too).  Set-up warms
every program the window runs, so this should read 0 (0 too on a program
that counts no compiles: ``harness/program.py``)."""

from harness import program


def read(run):
    if not program.counts_compiles():
        return 0
    got = program.compiles(run)
    return None if got is None else len(got[1])
