"""Prefill worker: host-visible time of one request's prefill, in
milliseconds: the mean duration of an ``engine.prefill`` span in the
traced stretch (program dispatch, eager pool writes, page handoff and the
first token's transfer; ``bench/spans.py``).  ``None`` where the stretch
holds no prefill, or the program records no such span."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.prefill_ms(run.trace)
