"""Decode step: mean device time of one execution of the decode program
(``jit__step``, the engine's ``DecodeWorker`` step) in the traced
stretch, in milliseconds."""


def read(run):
    if run.trace is None:
        return None
    progs = run.trace.programs("jit__step")
    return sum(p.dur for p in progs) / len(progs) * 1e-6 if progs else None
