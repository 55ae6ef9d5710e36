"""warm_seed_s: seconds per window epoch in the program's ``warm_seed``
span (``node/manager.py::Manager._warm_t0``: the previous epoch's
scores remapped onto this epoch's peers)."""

from benchmark.program_spans import span_seconds


def read(run):
    return span_seconds(run, "warm_seed")
