"""Exporters: Prometheus text format, JSON, and the jax.profiler hook.

``prometheus_text`` renders the registry in the Prometheus exposition
format (text/plain; version=0.0.4) the node serves at ``GET /metrics``;
``metrics_json`` is the same state for tooling that prefers JSON.
``profile_session`` is the opt-in device-timeline capture around
``converge_epoch`` (``ProtocolConfig.profile_dir``): it wraps
``jax.profiler.trace``, mirrors the program's spans into it
(``mirror_spans``), and degrades to a no-op when jax is absent, so
importing this module never touches the device runtime.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Iterator

from .metrics import METRICS, Histogram, Metric, MetricsRegistry
from .trace import TRACER, Tracer

#: Name prefix of the profiler annotation that mirrors a span.
SPAN_ANNOTATION_PREFIX = "program:"


def _escape_label_value(value: str) -> str:
    """Exposition-format label-value escaping: backslash FIRST (or the
    other escapes' backslashes double), then quote and newline."""
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(text: str) -> str:
    """HELP-line escaping per the exposition format: backslash and
    newline only (quotes are legal in help text)."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _labels(names: tuple[str, ...], values: tuple[str, ...], extra: str = "") -> str:
    pairs = [
        f'{n}="{_escape_label_value(v)}"' for n, v in zip(names, values)
    ]
    if extra:
        pairs.append(extra)
    return "{" + ",".join(pairs) + "}" if pairs else ""


def _fmt(value: float) -> str:
    if value == math.inf:
        return "+Inf"
    if value == -math.inf:
        return "-Inf"
    if float(value).is_integer() and abs(value) < 1e15:
        return str(int(value))
    return repr(float(value))


def _render_metric(metric: Metric) -> list[str]:
    lines = []
    if metric.help:
        lines.append(f"# HELP {metric.name} {_escape_help(metric.help)}")
    lines.append(f"# TYPE {metric.name} {metric.kind}")
    if isinstance(metric, Histogram):
        snap = metric.snapshot()
        if not snap:
            # An unobserved histogram still advertises its series.
            snap = {
                tuple("" for _ in metric.labelnames): {
                    "buckets": [0] * len(metric.bucket_bounds),
                    "sum": 0.0,
                    "count": 0,
                }
            }
        for labelvalues, state in snap.items():
            for bound, count in zip(metric.bucket_bounds, state["buckets"]):
                le = f'le="{_fmt(bound)}"'
                lines.append(
                    f"{metric.name}_bucket"
                    f"{_labels(metric.labelnames, labelvalues, le)} {count}"
                )
            lines.append(
                f"{metric.name}_sum"
                f"{_labels(metric.labelnames, labelvalues)} {_fmt(state['sum'])}"
            )
            lines.append(
                f"{metric.name}_count"
                f"{_labels(metric.labelnames, labelvalues)} {state['count']}"
            )
        return lines
    samples = metric.samples()
    if not samples and not metric.labelnames:
        samples = [((), 0.0)]
    for labelvalues, value in samples:
        lines.append(
            f"{metric.name}{_labels(metric.labelnames, labelvalues)} {_fmt(value)}"
        )
    return lines


#: Content type of the exposition format, for HTTP servers.
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def prometheus_text(registry: MetricsRegistry | None = None) -> str:
    """The full registry in Prometheus exposition format."""
    registry = registry if registry is not None else METRICS
    lines: list[str] = []
    for metric in registry.collect():
        lines.extend(_render_metric(metric))
    return "\n".join(lines) + "\n"


def metrics_json(registry: MetricsRegistry | None = None) -> dict[str, Any]:
    """JSON-ready snapshot: metric name -> {kind, help, state}."""
    registry = registry if registry is not None else METRICS
    return {
        metric.name: {"kind": metric.kind, "help": metric.help, **metric.to_dict()}
        for metric in registry.collect()
    }


@contextlib.contextmanager
def mirror_spans(tracer: Tracer = TRACER) -> Iterator[None]:
    """While open, mirror every span of ``tracer`` into the running
    ``jax.profiler`` trace as a ``program:<name>`` annotation, so the
    device timeline names each idle gap by what the program was doing.
    The hooks are chained to the ones already installed and restored
    on exit; a span still open at exit is not mirrored."""
    import jax

    prev_open, prev_close = tracer.on_span_open, tracer.on_span_close
    lock = threading.Lock()
    annotations: dict[int, Any] = {}

    def on_open(span) -> None:
        if prev_open is not None:
            prev_open(span)
        ann = jax.profiler.TraceAnnotation(SPAN_ANNOTATION_PREFIX + span.name)
        ann.__enter__()
        with lock:
            annotations[span.span_id] = ann

    def on_close(span) -> None:
        with lock:
            ann = annotations.pop(span.span_id, None)
        if ann is not None:
            ann.__exit__(None, None, None)
        if prev_close is not None:
            prev_close(span)

    tracer.on_span_open, tracer.on_span_close = on_open, on_close
    try:
        yield
    finally:
        tracer.on_span_open, tracer.on_span_close = prev_open, prev_close
        with lock:
            annotations.clear()


@contextlib.contextmanager
def profile_session(log_dir: str | None) -> Iterator[None]:
    """Opt-in ``jax.profiler`` capture: a real device-timeline trace
    (view with tensorboard/xprof) around the wrapped region when
    ``log_dir`` is set, with the program's spans mirrored into it as
    ``program:<name>`` annotations; a no-op context when it is None or
    jax is missing.  The node wraps ``converge_epoch`` with this when
    ``ProtocolConfig.profile_dir`` is configured."""
    if not log_dir:
        yield
        return
    try:
        import jax
    except ImportError:  # pragma: no cover - jax ships in every image
        yield
        return
    with jax.profiler.trace(log_dir), mirror_spans():
        yield


__all__ = [
    "PROMETHEUS_CONTENT_TYPE",
    "SPAN_ANNOTATION_PREFIX",
    "metrics_json",
    "mirror_spans",
    "profile_session",
    "prometheus_text",
]
