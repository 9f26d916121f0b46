"""Device milliseconds of one pathwise draw (the program
``_pathwise_samples_chunked``), from the trace."""

PROGRAM = "_pathwise_samples_chunked"


def read(run):
    n = run.trace.program_runs(PROGRAM)
    return run.trace.program_s(PROGRAM) / n * 1e3 if n else None
