"""Device trace of the measured window, and its reduction to numbers.

``Session`` runs JAX's profiler over the window, with the Python tracer
off, and mirrors every span into it: the program's own ``TRACER`` spans
(through its ``on_span_open``/``on_span_close`` hooks, chained to the
hooks the program installed) and the benchmark's own ``bench:*``
spans.  The host spans then sit on the device's clock, so an idle gap
on the device can be named by what the host was doing in it.

``extract`` turns the profiler's ``.xplane.pb`` into plain lists;
``reduce`` works on those lists alone, so a recorded trace checks it
(``benchmark/tests``).
"""

from __future__ import annotations

import glob
import shutil
import threading
from contextlib import contextmanager
from pathlib import Path

#: Line of a TPU device plane whose events are single operations, and
#: the line whose events are whole executables.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Prefix of the host annotations this benchmark writes.
PROGRAM = "program:"
BENCH = "bench:"


class Spans:
    """Collects the program's closed ``TRACER`` spans; with a profiler
    running, also mirrors each span into the trace as an annotation."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._prev_open = tracer.on_span_open
        self._prev_close = tracer.on_span_close
        self._lock = threading.Lock()
        self._open: dict[int, object] = {}
        self.closed: list[tuple[str, float, float]] = []  # (name, start, seconds)
        self.mirror = False
        tracer.on_span_open = self._on_open
        tracer.on_span_close = self._on_close

    def _on_open(self, span) -> None:
        if self._prev_open is not None:
            self._prev_open(span)
        if self.mirror:
            import jax

            ann = jax.profiler.TraceAnnotation(PROGRAM + span.name)
            ann.__enter__()
            with self._lock:
                self._open[span.span_id] = ann
        return None

    def _on_close(self, span) -> None:
        with self._lock:
            ann = self._open.pop(span.span_id, None)
            self.closed.append((span.name, span.start_monotonic, span.duration_s or 0.0))
        if ann is not None:
            ann.__exit__(None, None, None)
        if self._prev_close is not None:
            self._prev_close(span)

    def between(self, name: str, start: float, end: float) -> float:
        """Seconds of spans named ``name`` that opened in ``[start, end)``
        (``time.monotonic`` clock)."""
        with self._lock:
            return sum(d for n, s, d in self.closed if n == name and start <= s < end)

    def close(self) -> None:
        self._tracer.on_span_open = self._prev_open
        self._tracer.on_span_close = self._prev_close


@contextmanager
def bench_span(name: str, on: bool):
    """A ``bench:<name>`` annotation in the trace, when tracing."""
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(BENCH + name):
        yield


class Session:
    """The profiler over the measured window, writing under ``log_dir``."""

    def __init__(self, log_dir: Path, spans: Spans):
        self.log_dir = log_dir
        self.spans = spans

    def __enter__(self):
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self.log_dir), profiler_options=options)
        self.spans.mirror = True
        return self

    def __exit__(self, *exc):
        import jax

        self.spans.mirror = False
        jax.profiler.stop_trace()
        return False

    def path(self) -> str:
        found = sorted(glob.glob(str(self.log_dir / "**" / "*.xplane.pb"), recursive=True))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.log_dir}")
        return found[-1]


def extract(path: str, chips: int) -> dict:
    """Plain lists from an ``.xplane.pb``: for each of the first
    ``chips`` TPU device planes, the events of its ops and modules
    lines, and the host annotations this benchmark wrote.  Events are
    ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict[str, list]] = {}
    host: list[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            if int(plane.name[12:]) >= chips:
                continue
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events
                    ]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((PROGRAM, BENCH)):
                        host.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _host_activity(host: list[list], lo: float, hi: float) -> dict[str, float]:
    """Seconds of ``[lo, hi)`` (ns) under each innermost host annotation
    (``none`` where none is open)."""
    inside = [(s, s + d, d, n) for n, s, d in host if s < hi and s + d > lo]
    cuts = sorted({lo, hi, *(max(lo, min(hi, x)) for s, e, _, _ in inside for x in (s, e))})
    out: dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [(d, n) for s, e, d, n in inside if s <= mid < e]
        name = min(open_)[1] if open_ else "none"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def op_label(name: str) -> str:
    """``fusion.103 fusion`` from the HLO text an op event is named by."""
    lhs, _, rest = name.partition(" = ")
    rest = rest.lstrip()
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{lhs.lstrip('%')} {rest.strip().partition('(')[0]}".strip()


def _self_times(ops: list[list], lo: float, hi: float) -> dict[str, float]:
    """Seconds per op label in ``[lo, hi)``, less the time of the ops
    nested inside it (a ``while`` holds its body's ops)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, label, start, child_ns]

    def pop() -> None:
        end, label, start, child = stack.pop()
        out[label] = out.get(label, 0.0) + (end - start - child) / 1e9
        if stack:
            stack[-1][3] += end - start

    for name, s, d in sorted((o for o in ops if lo <= o[1] < hi), key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][0]:
            pop()
        stack.append([s + d, op_label(name), s, 0.0])
    while stack:
        pop()
    return out


def reduce(extracted: dict, *, top: int = 10, module_match: str = "converge") -> dict:
    """Numbers of the traced window, the ``bench:window`` annotation:
    ``window_s``; ``busy_s`` (the union of the op intervals in the
    window, averaged over the device planes); ``converge_s`` (device
    seconds of executables whose name holds ``module_match``, averaged
    over the planes); ``device_ops`` (the ``top`` ops by self time) and
    ``idle_gaps`` (the device's idle seconds split by the innermost host
    annotation open at the time, the ``top`` largest)."""
    windows = [h for h in extracted["host"] if h[0] == BENCH + "window"]
    if len(windows) != 1:
        raise ValueError(f"want one {BENCH}window annotation, found {len(windows)}")
    _, lo, dur = windows[0]
    hi = lo + dur
    planes = extracted["devices"]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy_s = converge_s = 0.0
    op_time: dict[str, float] = {}
    idle: dict[str, float] = {}
    for lines in planes.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_s += sum(e - s for s, e in busy) / 1e9
        for label, t in _self_times(ops, lo, hi).items():
            op_time[label] = op_time.get(label, 0.0) + t
        converge_s += sum(
            d for name, s, d in lines.get(MODULES_LINE, [])
            if module_match in name and lo <= s < hi
        ) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            for name, t in _host_activity(extracted["host"], a, b).items():
                idle[name] = idle.get(name, 0.0) + t
    k = len(planes)

    def ranked(d: dict[str, float]) -> list[list]:
        return sorted(([n, t / k] for n, t in d.items()), key=lambda x: -x[1])[:top]

    return {
        "window_s": dur / 1e9,
        "busy_s": busy_s / k,
        "converge_s": converge_s / k,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(idle),
    }
