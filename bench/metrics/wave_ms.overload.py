"""Device milliseconds of one query wave (the program ``_engine_step``)."""

PROGRAM = "_engine_step"


def read(run):
    n = run.trace.program_runs(PROGRAM)
    return run.trace.program_s(PROGRAM) / n * 1e3 if n else None
