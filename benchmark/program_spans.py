"""The program's own spans in a traced window, for the per-layer metrics
that read them.

A metric reader gets only the reduced trace, which keeps the ten
largest idle gaps and no span durations.  This module re-reads the
window's ``.xplane.pb`` (the newest under ``benchmark.run.TRACE_DIR``)
with ``benchmark.trace.extract``, once per file, and sums it up:

- ``spans``: inclusive seconds of the ``program:<name>`` annotations
  that start inside ``bench:window``, by name;
- ``converge_host_s``: seconds of the ``program:converge`` annotations
  in the window less the device time of the converge executables
  inside them (averaged over the device planes);
- ``idle``: the device's idle seconds by the innermost host annotation
  open at the time, every one of them (``benchmark.trace.reduce`` with
  no cut).

A program that opens no such span gives a reader nothing to read: it
returns None.
"""

from __future__ import annotations

import glob
import os
import sys

from benchmark.run import TRACE_DIR
from benchmark.trace import BENCH, MODULES_LINE, PROGRAM, extract, reduce

#: Executables whose name holds this are the converge (as ``reduce``).
CONVERGE_MODULE = "converge"

_cache: dict[tuple[str, int], dict] = {}


def newest_trace() -> str | None:
    """The newest ``.xplane.pb`` under the benchmark's trace directory."""
    found = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _overlap(events: list[list], lo: float, hi: float) -> float:
    """Nanoseconds of ``events`` (``[name, start, duration]``) inside
    ``[lo, hi)``."""
    return sum(max(0.0, min(s + d, hi) - max(s, lo)) for _, s, d in events)


def summarize(extracted: dict) -> dict:
    """``spans``, ``converge_host_s`` and ``idle`` of one extracted
    trace (the lists ``benchmark.trace.extract`` returns)."""
    host = extracted["host"]
    (lo, hi), = [(s, s + d) for n, s, d in host if n == BENCH + "window"]
    spans: dict[str, float] = {}
    converge: list[tuple[float, float]] = []
    for name, s, d in host:
        if name.startswith(PROGRAM) and lo <= s < hi:
            key = name[len(PROGRAM):]
            spans[key] = spans.get(key, 0.0) + d / 1e9
            if key == "converge":
                converge.append((s, s + d))
    planes = extracted["devices"].values()
    device_ns = sum(
        _overlap([m for m in lines.get(MODULES_LINE, []) if CONVERGE_MODULE in m[0]], s, e)
        for lines in planes
        for s, e in converge
    ) / max(len(planes), 1)
    return {
        "spans": spans,
        "converge_host_s": sum(e - s for s, e in converge) / 1e9 - device_ns / 1e9
        if converge else None,
        "idle": dict(reduce(extracted, top=sys.maxsize)["idle_gaps"]),
    }


def summary(chips: int) -> dict | None:
    """``summarize`` of the newest trace, read once per file."""
    path = newest_trace()
    if path is None:
        return None
    key = (path, chips)
    if key not in _cache:
        _cache[key] = summarize(extract(path, chips))
    return _cache[key]


def _of_run(run: dict) -> dict | None:
    if run.get("trace") is None or not run["epochs"]:
        return None
    return summary(int(run["device"]["count"]))


def span_seconds(run: dict, name: str) -> float | None:
    """Seconds per window epoch in the program's ``name`` spans, or None
    where the window holds none."""
    found = _of_run(run)
    if found is None or name not in found["spans"]:
        return None
    return found["spans"][name] / len(run["epochs"])


def converge_host_seconds(run: dict) -> float | None:
    """Seconds per window epoch of ``converge`` spans outside the
    converge executable's device time, or None without such spans."""
    found = _of_run(run)
    if found is None or found["converge_host_s"] is None:
        return None
    return found["converge_host_s"] / len(run["epochs"])


def idle_unattributed_share(run: dict) -> float | None:
    """Percent of the device's idle time in which no program span was
    the innermost host annotation, or None without idle time."""
    found = _of_run(run)
    if found is None:
        return None
    total = sum(found["idle"].values())
    if total <= 0:
        return None
    unnamed = sum(t for n, t in found["idle"].items() if not n.startswith(PROGRAM))
    return 100.0 * unnamed / total
