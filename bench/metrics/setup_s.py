"""Set-up seconds: process start to the window's start (weights made,
programs built or loaded from the cache, shapes warmed, traffic served
until the slots are busy).  Host clock."""


def read(run):
    return run.setup_s
