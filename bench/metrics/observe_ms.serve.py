"""Device milliseconds per appended row: the append programs
(``_observe_batch*``) and the forget programs (``_forget_batch*``) that
pair with them, over the appends the trace holds."""


def read(run):
    n = run.trace.program_runs("_observe_batch")
    if not n:
        return None
    t = (run.trace.program_s("_observe_batch")
         + run.trace.program_s("_forget_batch"))
    return t / n * 1e3
