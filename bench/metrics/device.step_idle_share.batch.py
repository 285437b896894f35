"""Device: share of the time inside the harness's ``engine_step`` spans in
which no operation ran on the chip, in percent (the traced stretch).
Taken inside steps, so that waiting for arrivals is not counted."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.step_idle_share()
    return None if share is None else 100.0 * share
