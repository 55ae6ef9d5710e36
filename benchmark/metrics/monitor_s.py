"""monitor_s: seconds per window epoch in the program's ``monitors``
span (``node/manager.py::Manager.converge_prepared``: everything after
the converge returned: recompile watch, state publish, residual
histogram, drift monitor, lineage, timeline)."""

from benchmark.program_spans import span_seconds


def read(run):
    return span_seconds(run, "monitors")
