"""Prefill worker: mean device time of one execution of the whole-prompt
prefill program (``jit__lambda``, ``PrefillWorker``'s jitted
``Model.prefill``; one per request) in the traced stretch, in
milliseconds."""


def read(run):
    if run.trace is None:
        return None
    progs = run.trace.programs("jit__lambda")
    return sum(p.dur for p in progs) / len(progs) * 1e-6 if progs else None
