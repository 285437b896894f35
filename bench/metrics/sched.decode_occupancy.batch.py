"""Scheduler: mean share of the slots that decoded in a step, over the
window's steps (``EngineStats`` step records), in percent."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * sum(s.decoding for s in run.steps) / (
        len(run.steps) * run.slots)
