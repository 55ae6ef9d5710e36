"""upload_s: seconds per window epoch in the program's ``upload`` span
(``trust/backend.py::WindowedJaxBackend.converge``: the plan arrays and
the start, pre-trust and dangling vectors put on the device, to their
arrival)."""

from benchmark.program_spans import span_seconds


def read(run):
    return span_seconds(run, "upload")
