"""graph_prep_s: seconds per window epoch in the program's
``graph_prep`` span (``trust/backend.py``: self-edge drop, row
normalisation, fingerprint, pre-trust and dangling vectors of the
epoch's graph, on the host)."""

from benchmark.program_spans import span_seconds


def read(run):
    return span_seconds(run, "graph_prep")
