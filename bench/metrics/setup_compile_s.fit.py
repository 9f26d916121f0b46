"""Seconds of the compiles that ended before the traced window — set-up's
— as the program counts them (``repro.obs.compiles``; with a warm
persistent cache mostly retrieval; 0 on a program that counts no
compiles: ``harness/program.py``)."""

from harness import program


def read(run):
    if not program.counts_compiles():
        return 0.0
    got = program.compiles(run)
    return None if got is None else sum(sec for _, sec, _ in got[0])
