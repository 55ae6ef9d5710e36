"""One run of one benchmark cell:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (its file of sizes) and a traffic mix
(``benchmark/traffic/<traffic>.json``); its limits for ``correct`` are
in ``benchmark/cells/<cell>.json`` and each per-layer metric is read by
``benchmark/metrics/<metric>.py``.  All are found by name.

What the window drives: the node's epoch device stage,
``Manager.converge_prepared``, on the configuration's backend, with
each ``PreparedEpoch`` built as ``Manager.prepare_epoch`` builds it
(the epoch's graph, the id order, the warm-start seed from the previous
epoch, the re-attesting rows as the churn hint under the
``plan_delta_max_churn`` rule).  The loop is closed: an epoch starts
when the previous one has returned its scores.  A thread assembles the
next epoch's graph meanwhile; waiting for it counts.  The window ends
at the first epoch end at or after ``--seconds``.

Set-up (``setup_s``): the base graph from the seed, one cold epoch on
it (the cold plan build a booting node pays, and the compiled converge
from the cache), and the traffic's warm-up epochs.

After the window: the device's peak memory is read, the program is
dropped, and the plain reference (``benchmark/reference.py``) replays
every epoch of the run from the same graphs; each window epoch's scores
are compared with it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: Fixed, inside the checkout: the path is part of the cache's key.
COMPILE_CACHE = ROOT / ".cache" / "jax"
TRACE_DIR = ROOT / ".cache" / "benchmark-trace"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclass
class Spec:
    """Everything one cell is made of, as read from its files."""

    workload: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _for_cell(metrics: list[dict], workload: str) -> list[dict]:
    return [m for m in metrics if "workloads" not in m or workload in m["workloads"]]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "cells" / f"{workload}.json").read_text())["limits"]
    return Spec(
        workload=workload,
        chips=int(cell["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=_for_cell(bench["end_to_end"], workload),
        per_layer=_for_cell(bench["per_layer"], workload),
    )


def prepare_environment() -> None:
    """Before JAX is imported: the compile cache inside the checkout
    (the program honours ``JAX_COMPILATION_CACHE_DIR``), and libtpu's
    logs off."""
    COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def cache_every_program() -> None:
    """Every compiled program goes to the persistent cache, with no
    eviction (an environment that turns eviction on finds entries it
    cannot age, and stops writing)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_devices(chips: int) -> dict:
    """The accelerator the cell asks for, or an error: never the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise SystemExit("JAX found no accelerator; this benchmark never runs on the CPU")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    from benchmark.roofline import peaks

    peaks(devices[0].device_kind)
    return {"platform": platform, "kind": devices[0].device_kind, "count": chips}


class CompileCounter:
    """Counts JAX traces, and compiles that the persistent cache did not
    serve (JAX reports a backend compile around every cache lookup)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.traces = self.lookups = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        self.traces += event == TRACE_EVENT
        self.lookups += event == COMPILE_EVENT

    def _event(self, event: str, **_kw) -> None:
        self.hits += event == CACHE_HIT_EVENT

    def take(self) -> dict:
        out = {"traces": self.traces, "compiles": self.lookups - self.hits,
               "cache_hits": self.hits}
        self.traces = self.lookups = self.hits = 0
        return out


@dataclass
class Epoch:
    """One epoch as run: what it took and what it produced."""

    number: int
    graph: object  # traffic.generate.Graph
    rows: int
    seconds: float = 0.0
    wait_s: float = 0.0
    plan_s: float = 0.0
    plan: str = "none"
    iterations: int = 0
    residual: float = 0.0
    scores: object = None
    edges: int = 0
    gaps: dict = field(default_factory=dict)
    ref: object = None  # the reference's scores, once checked

    def line(self) -> dict:
        return {
            "epoch": self.number, "seconds": self.seconds, "wait_s": self.wait_s,
            "plan": self.plan, "plan_s": self.plan_s, "rows": self.rows,
            "iterations": self.iterations, "residual": self.residual, **self.gaps,
        }


class Node:
    """The system under test: a ``Manager`` on the configuration's
    backend, driven one epoch at a time."""

    def __init__(self, config: dict):
        from protocol_tpu.node.manager import Manager, ManagerConfig
        from protocol_tpu.obs import TRACER

        from benchmark.trace import Spans

        self.manager = Manager(ManagerConfig(backend=config["backend"], prover="commitment"))
        self.trust = config["trust"]
        self.ids: list[int] | None = None
        self.spans = Spans(TRACER)

    def epoch(self, ep: Epoch, rows, tracing: bool) -> None:
        """Run ``ep`` through ``converge_prepared``; ``rows`` are the
        re-attesting senders (None for the cold epoch)."""
        import numpy as np

        from protocol_tpu.node.epoch import Epoch as NodeEpoch
        from protocol_tpu.node.manager import PreparedEpoch
        from protocol_tpu.obs.metrics import PLAN_OUTCOMES
        from protocol_tpu.trust.graph import TrustGraph

        from benchmark.trace import bench_span

        g, m = ep.graph, self.manager
        if self.ids is None:
            self.ids = list(range(g.n))
        start = time.monotonic()
        with bench_span("warm_seed", tracing):
            t0 = m._warm_t0(self.ids) if m.config.warm_start else None
        delta = None
        if m.window_plan is not None and rows is not None and rows.size:
            if rows.size <= m.config.plan_delta_max_churn * max(g.n, 1):
                delta = rows.astype(np.int64)
        prepared = PreparedEpoch(
            epoch=NodeEpoch(ep.number),
            graph=TrustGraph(g.n, g.src, g.dst, g.weight, g.pre_trusted),
            id_order=self.ids,
            t0=t0,
            delta_rows=delta,
            dirty_snapshot=set(),
        )
        before = {k: PLAN_OUTCOMES.value(outcome=k) for k in ("reuse", "delta", "rebuild")}
        with bench_span("converge_prepared", tracing):
            result = m.converge_prepared(prepared, **{
                k: self.trust[k] for k in ("alpha", "tol", "max_iter")
            })
        taken = [k for k in before if PLAN_OUTCOMES.value(outcome=k) > before[k]]
        ep.plan = "+".join(taken) or "none"
        ep.plan_s = self.spans.between("plan", start, time.monotonic())
        ep.iterations = int(result.iterations)
        ep.residual = float(result.residual)
        ep.scores = result.scores


def _emit(tag: str, payload: dict) -> None:
    print(f"{tag} {json.dumps(payload)}", flush=True)


def read_metric(name: str, run: dict):
    """The per-layer metric ``name``, from ``benchmark/metrics/<name>.py``:
    a number, or None where the run gave it nothing to read."""
    from benchmark.modules import load_module

    return load_module(BENCH / "metrics" / f"{name}.py", "benchmark_metric_").read(run)


def check(epochs: list[Epoch], setup: list[Epoch], trust: dict, limits: dict) -> dict:
    """Replay every epoch of the run through the reference chain and
    compare each window epoch's scores; the worst gap of each number,
    beside its limit."""
    from benchmark.reference import Chain, gaps

    chain = Chain(trust)
    worst = {k: 0.0 for k in limits}
    for i, ep in enumerate(setup + epochs):
        ref = chain.epoch(ep.graph)
        ep.ref = ref.scores
        if i < len(setup):
            continue
        ep.gaps = gaps(ep.scores, ref.scores)
        ep.gaps["ref_iterations"] = ref.iterations
        for k in limits:
            worst[k] = max(worst[k], ep.gaps[k])
    return {k: {"value": worst[k], "limit": limits[k]} for k in limits}


@dataclass
class Run:
    """What one run measured, before the check."""

    setup: list[Epoch]
    epochs: list[Epoch]
    setup_s: float
    window_s: float
    device: dict
    traced: dict | None


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, device: dict) -> dict:
    """Set up, measure, check; returns the result line's object."""
    return judge(spec, measure(spec, seed, seconds, trace, device))


def measure(spec: Spec, seed: int, seconds: float, trace: bool, device: dict) -> Run:
    """Set-up and the measured window; the program is dropped after."""
    import jax

    from benchmark.trace import Session, bench_span, extract, reduce
    from benchmark.traffic.generate import Ahead, Churn, base_graph

    compiles = CompileCounter()
    setup_start = time.perf_counter()
    graph = base_graph(spec.config, seed)
    node = Node(spec.config)
    churn = Churn(spec.traffic, seed)
    graph_s = time.perf_counter() - setup_start
    setup = [Epoch(0, graph, 0)]
    rows = None
    for k in range(1 + int(spec.traffic["warmup_epochs"])):
        if k:
            rows, graph = churn.step(graph)
            setup.append(Epoch(k, graph, int(rows.size)))
        start = time.perf_counter()
        node.epoch(setup[-1], rows, False)
        setup[-1].seconds = time.perf_counter() - start
    ahead = Ahead(churn, graph)
    ahead.ready()  # the first window epoch's graph is set-up's work
    setup_s = time.perf_counter() - setup_start
    _emit("setup", {"seconds": setup_s, "graph_s": graph_s, **compiles.take(),
                    "epochs": [e.line() for e in setup]})

    epochs: list[Epoch] = []
    session = Session(TRACE_DIR / spec.workload, node.spans) if trace else None
    try:
        if session:
            session.__enter__()
        with bench_span("window", trace):
            window_start = last = time.perf_counter()
            while True:
                with bench_span("epoch", trace):
                    with bench_span("wait_graph", trace):
                        rows, graph, wait_s = ahead.get()
                    ep = Epoch(len(setup) + len(epochs), graph, int(rows.size), wait_s=wait_s)
                    node.epoch(ep, rows, trace)
                now = time.perf_counter()
                ep.seconds, last = now - last, now
                epochs.append(ep)
                if now - window_start >= seconds:
                    break
        window_s = last - window_start
        in_window = compiles.take()
    finally:
        if session:
            session.__exit__(None, None, None)
        ahead.close()
    peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[: spec.chips]
    )
    device = dict(device, memory_peak_bytes=peak)
    node.spans.close()
    del node
    traced = None
    if session:
        traced = reduce(extract(session.path(), spec.chips))
        device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
    _emit("window", {
        "seconds": window_s, "epochs": len(epochs),
        "plan_paths": sorted({e.plan for e in epochs}),
        "compiles_in_window": in_window,
        "generator_wait_s": sum(e.wait_s for e in epochs),
    })
    return Run(setup, epochs, setup_s, window_s, device, traced)


def judge(spec: Spec, run: Run) -> dict:
    """The reference check of every window epoch, and the result line."""
    epochs, setup, device, traced = run.epochs, run.setup, run.device, run.traced
    for ep in epochs:
        ep.edges = int((ep.graph.src != ep.graph.dst).sum())
    start = time.perf_counter()
    compared = check(epochs, setup, spec.config["trust"], spec.limits)
    for ep in epochs:
        _emit("epoch", ep.line())
    _emit("check", {"seconds": time.perf_counter() - start})
    failed = sum(
        any(ep.gaps[k] > spec.limits[k] for k in spec.limits) for ep in epochs
    )
    if traced:
        readable = {"epochs": epochs, "trace": traced, "device": device, "config": spec.config}
        metrics = {}
        for m in spec.per_layer:
            value = read_metric(m["name"], readable)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"epoch_s": run.window_s / len(epochs), "setup_s": run.setup_s}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end
        }
    result = {
        "correct": bool(epochs) and failed == 0,
        "attempted": len(epochs),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = {
            "device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"],
        }
    result["check"] = compared
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    spec = load_spec(args.workload)
    prepare_environment()
    device = require_devices(spec.chips)
    import jax

    cache_every_program()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace), device)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
