"""gather_device_s: device seconds per window epoch of the windowed
gather kernel, the ops of the traced window whose label is
``gather_windowed`` (the name the program gives its Pallas call),
read from the newest trace (``benchmark.program_spans.newest_trace``)
with every op kept (``benchmark.trace.reduce`` with no cut).  None
where the window ran no such op."""

import sys

from benchmark.program_spans import newest_trace
from benchmark.trace import extract, reduce

#: The op label's first word, before its ``.<n>`` suffix.
KERNEL = "gather_windowed"


def is_gather(label: str) -> bool:
    return label.split(" ")[0].split(".")[0] == KERNEL


def gather_seconds(run) -> float | None:
    """Device seconds of the gather kernel over the whole window."""
    if run["trace"] is None or not run["epochs"]:
        return None
    path = newest_trace()
    if path is None:
        return None
    ops = reduce(extract(path, int(run["device"]["count"])), top=sys.maxsize)["device_ops"]
    found = [t for label, t in ops if is_gather(label)]
    return sum(found) if found else None


def read(run):
    seconds = gather_seconds(run)
    return None if seconds is None else seconds / len(run["epochs"])
