"""95th percentile (nearest rank) of every gap between two consecutive
tokens of one request, both emitted inside the window, in milliseconds.
A token's time is the end of the step that produced it (each step ends in
one device-to-host transfer).  Host clock."""
from bench.harness import nearest_rank


def read(run):
    return nearest_rank(run.itl_s, 95) * 1e3 if run.itl_s else None
