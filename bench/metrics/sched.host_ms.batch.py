"""Scheduler: host work per engine step, in milliseconds: the mean over
the traced stretch's ``engine.step`` spans of the span's time outside its
``engine.device_wait`` children (``bench/spans.py``).  That is the time
the synchronous loop spends on the host before and after its one wait for
the chip.  ``None`` where the program records no ``engine.step`` span."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.host_ms_per_step(run.trace)
