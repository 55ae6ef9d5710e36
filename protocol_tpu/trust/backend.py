"""TrustBackend — the pluggable execution backend for trust convergence.

The north-star design: the node selects how the epoch's convergence runs
(BASELINE.json: "native-cpu | tpu-pjrt"), generalized here to six
backends along the scaling ladder:

- ``native-cpu``    exact field/rational math (parity with the reference)
- ``tpu-dense``     jit'd dense matmul power iteration (≤ ~10k peers)
- ``tpu-sparse``    COO segment-sum SpMV, single device
- ``tpu-csr``       gather-only CSR/compensated-cumsum SpMV (scatter-free)
- ``tpu-windowed``  fused fixed-slot pipeline: windowed Pallas gather from
  the score table + static bucket→dst bridge.  Needs a
  static graph layout (the one-time ``WindowPlan``, reusable across
  epochs/reboots while the graph fingerprint holds).  The table is read
  into VMEM in chunks of ``TABLE_CHUNK`` entries (4 MB f32), one
  resident block up to ~1M peers, so N is bounded by HBM, not VMEM.
  Prefer ``tpu-csr`` when the graph churns every epoch (plan cost is
  then per-epoch), or on toolchains where Mosaic is unavailable.
- ``tpu-sharded``   edge-sharded convergence + psum over a device mesh.
  Two per-shard kernels (``parallel/sharded.py::SHARDED_KERNELS``),
  selected with a ``:<kernel>`` suffix on the backend name:
  ``tpu-sharded:tpu-csr`` (default — per-shard CSR ``rowsum_sorted``
  via clipped row pointers) and ``tpu-sharded:tpu-windowed`` (the fused
  fixed-slot pipeline partitioned by window rows — the multi-chip path
  that keeps the windowed gather).

All float backends compute the damped EigenTrust fixed point over the
row-normalized graph; ``native-cpu`` additionally reproduces the
reference's field semantics for the proof layer.  Each host decision
is made in one place: the normalized graph (``normalize_graph``) and
the plan's reuse, delta or rebuild (``_resolve_plan``).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

from ..device import pallas_interpret
from ..ops.dense import converge_dense
from ..ops.gather_window import WindowPlan, converge_windowed, resolve_plan
from ..obs import TRACER
from ..obs.journal import JOURNAL
from ..obs.metrics import PLAN_OUTCOMES, PLAN_REBUILDS, PLAN_REUSES
from ..obs.watchers import RECOMPILES
from ..ops.sparse import converge_csr, converge_sparse
from .graph import NormalizedGraph, TrustGraph

# Register the jit'd converge entry points with the recompile tracker
# (obs/watchers.py): the epoch path brackets each converge with a
# cache-size snapshot, so every fresh XLA compilation is counted on
# eigentrust_jit_recompiles_total{fn} — and a steady-state delta epoch
# that recompiles (breaking PR 5's stable-shape guarantee) is flagged.
# Registration reads nothing from the device; it only keeps a
# reference for later _cache_size() reads at host boundaries.
RECOMPILES.register("converge_dense", converge_dense)
RECOMPILES.register("converge_sparse", converge_sparse)
RECOMPILES.register("converge_csr", converge_csr)
RECOMPILES.register("converge_windowed", converge_windowed)


@dataclass
class ConvergenceResult:
    """Scores plus convergence metadata — the aux bundle every backend
    returns so the node can export convergence health without touching
    device state again."""

    scores: np.ndarray  # (n,) float64, L1-normalized global trust
    iterations: int
    residual: float
    backend: str
    #: Per-iteration L1 residual trajectory (length == ``iterations``),
    #: captured device-side in the while-loop carry on the jax backends
    #: and fetched once after convergence.  None when the caller opted
    #: out (``record_residuals=False``).  The chunked ``tpu-dense``
    #: backend records one residual per host-checked chunk instead (its
    #: loop is host-driven between compiled scan chunks).
    residuals: np.ndarray | None = None

    def scaled(self, total: float) -> np.ndarray:
        """Rescale to reference-style score units (e.g. N·INITIAL_SCORE
        so a uniform result reads 1000 per peer)."""
        return self.scores * total


def _history(hist, iterations: int) -> np.ndarray:
    """The one post-convergence fetch of the device-side residual
    carry, sliced to the iterations actually run."""
    return np.asarray(hist, dtype=np.float64)[: int(iterations)]


def _fetch(out, backend: str, record_residuals: bool, span) -> ConvergenceResult:
    """The converge's one device-to-host fetch, inside its ``converge``
    span (which therefore closes on the result, not on the dispatch),
    stamped with the iteration count and the final residual."""
    t, it, resid = out[:3]
    result = ConvergenceResult(
        scores=np.asarray(t, dtype=np.float64),
        iterations=int(it),
        residual=float(resid),
        backend=backend,
        residuals=_history(out[3], it) if record_residuals else None,
    )
    span.attrs.update(iterations=result.iterations, residual=result.residual)
    return result


def normalize_graph(graph: TrustGraph) -> NormalizedGraph:
    """The host normalization of every edge-list backend (``tpu-sparse``,
    ``tpu-csr``, ``tpu-windowed``, both ``tpu-sharded`` kernels, the pod
    builder and the analyzers): self-edges dropped, weights
    row-normalized, the dangling mask and the pre-trust vector, each
    step under its own span inside the caller's ``graph_prep``.  The
    per-edge weights are divided where a caller reads them
    (``NormalizedGraph.w``, ``weights_of``): a plan delta reads only
    its re-attested rows, a rebuild the whole array under its ``plan``
    span.

    ``native-cpu`` (exact rationals) and ``tpu-dense`` (a dense matrix
    normalized by its own row sums) keep their own arithmetic: routing
    them through float32 per-edge weights would change their numbers."""
    with TRACER.span("drop_self_edges") as span:
        g = graph.drop_self_edges()
        span.attrs["self_edges"] = graph.nnz - g.nnz
    with TRACER.span("row_normalize"):
        safe, dangling = g.row_sums()
        dangling = dangling.astype(np.float32)
    return NormalizedGraph(
        g.n, g.src, g.dst, g.weight, safe, dangling, graph.pre_trust_vector()
    )


def _by_dst(ng: NormalizedGraph) -> TrustGraph:
    with TRACER.span("sort_by_dst"):
        return ng.by_dst()


def _fingerprint(ng: NormalizedGraph) -> str:
    with TRACER.span("fingerprint"):  # computed once: later reads are free
        return ng.fingerprint


def _plan_phase(plan_span):
    """The plan's sub-phase hook (``ops.gather_window.PlanPhase``): each
    sub-phase is a ``TRACER`` span under ``plan``, and the counts it
    reports (rows, touched windows, edges) land on the ``plan`` span."""

    def phase(name: str, **counts: int):
        plan_span.attrs.update(counts)
        return TRACER.span(name)

    return phase


def _resolve_plan(
    backend: str,
    candidate: WindowPlan | None,
    ng: NormalizedGraph,
    rows: np.ndarray | None,
    span=None,
) -> WindowPlan:
    """Reuse, delta-update or rebuild the cached plan for the normalized
    graph (``ops.gather_window.resolve_plan``), and count the outcome
    once.  Each attempt opens a ``plan`` span with its ``reason``, its
    sub-phases and the made plan's table layout (the chunks the kernel
    streams the score table in, and the rows padded so that no grid
    block reads two chunks) — or reports into ``span``, where the
    caller holds it open (the sharded backend's partition follows).
    Delta application is host-side, strictly before any device dispatch
    (graftlint's plan-mutation-in-converge rule pins the converse)."""
    reasons: list[str] = []

    def attempt(reason, make):
        reasons.append(reason)
        opened = (
            TRACER.span("plan", backend=backend, reason=reason)
            if span is None else contextlib.nullcontext(span)
        )
        with opened as s:
            s.attrs["reason"] = reason
            plan = make(_plan_phase(s))
            if plan is not None:
                s.attrs.update(table_chunks=plan.table_chunks, align_rows=plan.align_rows)
        return plan

    plan, outcome = resolve_plan(
        candidate, ng.src, ng.dst, ng.weights_of,
        n=ng.n, fingerprint=ng.fingerprint, rows=rows, attempt=attempt,
    )
    if outcome == "reuse":
        PLAN_REUSES.inc()
    elif outcome == "rebuild":
        PLAN_REBUILDS.inc()
    PLAN_OUTCOMES.inc(outcome=outcome)
    detail = {}
    if outcome == "delta":
        detail["rows"] = int(rows.size)
    elif outcome == "rebuild":
        detail["reason"] = reasons[-1]
    JOURNAL.record("plan", outcome=outcome, backend=backend, **detail)
    return plan


def _initial_vector(t0, p: np.ndarray) -> np.ndarray:
    """Resolve the iteration's starting vector: the caller's warm-start
    ``t0`` (the previous epoch's fixed point, remapped over
    joined/departed peers) L1-renormalized, or the pre-trust vector
    ``p`` — the cold start — when ``t0`` is absent, mis-shaped, or
    degenerate.  A near-fixed-point start is pure initial-carry data:
    the step function, and therefore the pinned kernel budgets, are
    untouched."""
    if t0 is None:
        return p
    t0 = np.asarray(t0, dtype=np.float32).reshape(-1)
    if t0.shape != p.shape or not np.isfinite(t0).all():
        return p
    # Converged score vectors carry ±1-ulp negative dust on zero-score
    # peers (compensated-sum differencing); clip rather than reject.
    t0 = np.maximum(t0, 0.0)
    s = float(t0.sum())
    if not np.isfinite(s) or s <= 0:
        return p
    return t0 / np.float32(s)


class TrustBackend:
    name = "abstract"

    def converge(
        self,
        graph: TrustGraph,
        *,
        alpha: float = 0.0,
        tol: float = 1e-6,
        max_iter: int = 50,
        record_residuals: bool = True,
        t0: np.ndarray | None = None,
    ) -> ConvergenceResult:
        raise NotImplementedError


class NativeCPUBackend(TrustBackend):
    """Exact rational dense power iteration — small sets only.

    With ``alpha=0`` and ``max_iter=I`` this is the reference kernel
    modulo normalization: it iterates the row-normalized matrix exactly
    like ``native()`` iterates the SCALE-summing ops matrix
    (circuit/src/circuit.rs:434-454), with dangling rows redirected to
    the pre-trust vector.
    """

    name = "native-cpu"

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        g = graph.drop_self_edges()
        dense = g.to_dense()
        n = g.n
        # Exact pre-trust vector (the float pre_trust_vector() is this
        # same distribution rounded to f32).
        if graph.pre_trusted is not None and graph.pre_trusted.any():
            cnt = int(graph.pre_trusted.sum())
            p = [
                Fraction(1, cnt) if graph.pre_trusted[i] else Fraction(0)
                for i in range(n)
            ]
        else:
            p = [Fraction(1, n)] * n
        # Exact rational row-normalized matrix with dangling → p.
        rows: list[list[Fraction]] = []
        row_sums = dense.sum(axis=1)
        for i in range(n):
            if row_sums[i] <= 0:
                rows.append([p[j] for j in range(n)])
            else:
                s = Fraction(row_sums[i])
                rows.append([Fraction(dense[i][j]) / s for j in range(n)])
        a = Fraction(alpha).limit_denominator(10**9)
        # Warm start: rationalize the seed exactly like alpha; the
        # fixed point is start-independent, only the path shortens.
        pf = np.array([float(x) for x in p], dtype=np.float32)
        start = _initial_vector(t0, pf)
        if start is pf:
            t = list(p)
        else:
            raw = [Fraction(float(x)).limit_denominator(10**12) for x in start]
            s = sum(raw)
            t = [x / s for x in raw] if s > 0 else list(p)
        it = 0
        resid = Fraction(0)
        history: list[float] = []
        for it in range(1, max_iter + 1):
            new_t = [
                (1 - a) * sum(rows[j][i] * t[j] for j in range(n)) + a * p[i]
                for i in range(n)
            ]
            resid = sum(abs(x - y) for x, y in zip(new_t, t))
            if record_residuals:
                history.append(float(resid))
            t = new_t
            if tol > 0 and resid < tol:
                break
        return ConvergenceResult(
            scores=np.array([float(x) for x in t], dtype=np.float64),
            iterations=it,
            residual=float(resid),
            backend=self.name,
            residuals=np.array(history) if record_residuals else None,
        )


class DenseJaxBackend(TrustBackend):
    name = "tpu-dense"

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        with TRACER.span("graph_prep", edges=graph.nnz):
            with TRACER.span("drop_self_edges"):
                g = graph.drop_self_edges()
            with TRACER.span("row_normalize"):
                dense = g.to_dense().astype(np.float32)
                row_sums = dense.sum(axis=1)
                p = graph.pre_trust_vector().astype(np.float32)
                dangling = row_sums <= 0
                norm = np.where(
                    dangling[:, None],
                    p[None, :],
                    dense / np.where(dangling, 1.0, row_sums)[:, None],
                )
                m = (1.0 - alpha) * norm.T + alpha * np.outer(p, np.ones(g.n, np.float32))
        with TRACER.span("converge", backend=self.name) as span:
            t = jnp.asarray(_initial_vector(t0, p))
            m = jnp.asarray(m.astype(np.float32))
            it = 0
            resid = np.inf
            history: list[float] = []
            # Fixed-size scan chunks with host-side residual checks
            # between chunks: keeps the hot loop compiled while honoring
            # tol.  The residual trajectory is therefore chunk-granular
            # here (one entry per host check), unlike the while-loop
            # backends.
            chunk = 8 if tol > 0 else max_iter
            while it < max_iter:
                steps = min(chunk, max_iter - it)
                t_new = converge_dense(m, t, steps)
                t_new = t_new / jnp.sum(t_new)
                resid = float(jnp.sum(jnp.abs(t_new - t)))
                if record_residuals:
                    history.append(resid)
                t = t_new
                it += steps
                if tol > 0 and resid < tol:
                    break
            scores = np.asarray(t, dtype=np.float64)
            span.attrs.update(iterations=it, residual=resid)
        return ConvergenceResult(
            scores=scores,
            iterations=it,
            residual=resid,
            backend=self.name,
            residuals=np.array(history) if record_residuals else None,
        )


class SparseJaxBackend(TrustBackend):
    name = "tpu-sparse"

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        with TRACER.span("graph_prep", edges=graph.nnz):
            ng = normalize_graph(graph)
            g = _by_dst(ng)
        with TRACER.span("converge", backend=self.name) as span:
            out = converge_sparse(
                jnp.asarray(g.src),
                jnp.asarray(g.dst),
                jnp.asarray(g.weight),
                jnp.asarray(_initial_vector(t0, ng.p)),
                jnp.asarray(ng.p),
                jnp.asarray(ng.dangling),
                n=g.n,
                alpha=jax.device_put(np.float32(alpha)),
                tol=tol,
                max_iter=max_iter,
                record_residuals=record_residuals,
            )
            return _fetch(out, self.name, record_residuals, span)


class CsrJaxBackend(TrustBackend):
    """Gather-only CSR/cumsum SpMV — the TPU-friendly formulation
    (scatter-free; see ops.sparse.power_step_csr)."""

    name = "tpu-csr"

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        with TRACER.span("graph_prep", edges=graph.nnz):
            ng = normalize_graph(graph)
            g = _by_dst(ng)
            row_ptr = g.row_ptr_by_dst()
        with TRACER.span("converge", backend=self.name) as span:
            out = converge_csr(
                jnp.asarray(g.src),
                jnp.asarray(row_ptr),
                jnp.asarray(g.weight),
                jnp.asarray(_initial_vector(t0, ng.p)),
                jnp.asarray(ng.p),
                jnp.asarray(ng.dangling),
                alpha=jax.device_put(np.float32(alpha)),
                tol=tol,
                max_iter=max_iter,
                record_residuals=record_residuals,
            )
            return _fetch(out, self.name, record_residuals, span)


class WindowedJaxBackend(TrustBackend):
    """Fused fixed-slot pipeline: windowed Pallas
    gather-multiply from the score table, streamed into VMEM by chunk, + a static
    bucket→dst bridge, so the per-iteration device step performs no
    O(E) random gather.

    The one-time ``WindowPlan`` (host bucketing + reduction layout) is
    cached on the instance and revalidated by graph fingerprint, so
    repeated epochs over a stable graph — and reboots that restore the
    plan from a checkpoint — skip construction entirely.  On a
    fingerprint miss with a churn hint (``delta_rows``: the source
    peers whose out-edges changed since the cached plan's graph), the
    plan is delta-updated in place of a full rebuild
    (``_resolve_plan``).
    """

    name = "tpu-windowed"

    def __init__(self, plan: WindowPlan | None = None, interpret: bool | None = None):
        #: Candidate plan to reuse (e.g. checkpoint-restored); replaced
        #: when its fingerprint doesn't match the converged graph.
        self.plan = plan
        #: Pallas interpret mode; default: ``device.pallas_interpret``
        #: (compiled on TPU, interpreted on the CPU test platform).
        self.interpret = interpret
        #: The plan the last converge actually used (for persistence).
        self.last_plan: WindowPlan | None = plan
        #: Churn hint for the NEXT converge: ids of every source peer
        #: whose out-edges changed since ``plan``'s graph (a superset is
        #: fine).  Consumed (reset to None) by the converge; when the
        #: fingerprint misses and the hint is present, the plan is
        #: delta-updated instead of rebuilt.
        self.delta_rows: np.ndarray | None = None

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        with TRACER.span("graph_prep", edges=graph.nnz):
            ng = normalize_graph(graph)
            _fingerprint(ng)
        rows, self.delta_rows = self.delta_rows, None
        plan = _resolve_plan(self.name, self.plan, ng, rows)
        self.plan = plan
        self.last_plan = plan
        interpret = (
            self.interpret if self.interpret is not None else pallas_interpret()
        )
        # The operands land on the device before the converge span
        # opens, so that span times the converge and its one fetch.
        with TRACER.span("upload") as span:
            args = jax.block_until_ready(
                (
                    *plan.device_args(),
                    jnp.asarray(_initial_vector(t0, ng.p)),
                    jnp.asarray(ng.p),
                    jnp.asarray(ng.dangling),
                )
            )
            span.attrs["bytes"] = sum(int(a.nbytes) for a in args)
        with TRACER.span("converge", backend=self.name) as span:
            out = converge_windowed(
                *args,
                **plan.static_kwargs(),
                alpha=jax.device_put(np.float32(alpha)),
                tol=tol,
                max_iter=max_iter,
                interpret=interpret,
                record_residuals=record_residuals,
            )
            return _fetch(out, self.name, record_residuals, span)


class ShardedJaxBackend(TrustBackend):
    """Mesh-sharded convergence, kernel-selectable
    (``parallel/sharded.py::SHARDED_KERNELS``): ``tpu-csr`` shards the
    edge list, ``tpu-windowed`` shards the fused-pipeline window rows
    and keeps the one-time ``WindowPlan`` cached/revalidated exactly
    like the single-device windowed backend (``plan``/``last_plan``
    carry it to and from the node's checkpoint store)."""

    name = "tpu-sharded"

    def __init__(self, mesh=None, kernel: str = "tpu-csr"):
        from ..parallel.sharded import SHARDED_KERNELS

        if kernel not in SHARDED_KERNELS:
            raise ValueError(
                f"unknown sharded kernel {kernel!r}; "
                f"available: {sorted(SHARDED_KERNELS)}"
            )
        self.mesh = mesh
        self.kernel = kernel
        #: Candidate WindowPlan to reuse (tpu-windowed kernel only).
        self.plan: WindowPlan | None = None
        #: The plan the last converge actually used (for persistence).
        self.last_plan: WindowPlan | None = None
        #: Churn hint consumed by the next converge — same contract as
        #: ``WindowedJaxBackend.delta_rows``.
        self.delta_rows: np.ndarray | None = None

    def converge(self, graph, *, alpha=0.0, tol=1e-6, max_iter=50,
                 record_residuals=True, t0=None):
        from ..parallel.mesh import default_mesh
        from ..parallel.sharded import (
            ShardedTrustProblem,
            ShardedWindowPlan,
            converge_sharded,
        )

        mesh = self.mesh if self.mesh is not None else default_mesh()
        name = (
            self.name if self.kernel == "tpu-csr" else f"{self.name}:{self.kernel}"
        )
        problem: ShardedTrustProblem | ShardedWindowPlan
        if self.kernel == "tpu-windowed":
            with TRACER.span("graph_prep", edges=graph.nnz):
                ng = normalize_graph(graph)
                _fingerprint(ng)
            rows, self.delta_rows = self.delta_rows, None
            # The partition and the device placement stay under the
            # one ``plan`` span the resolution reports into.
            with TRACER.span("plan", backend=name) as span:
                plan = _resolve_plan(name, self.plan, ng, rows, span=span)
                problem = ShardedWindowPlan.build(ng, mesh, plan=plan)
            self.plan = self.last_plan = plan
        else:
            with TRACER.span("graph_prep", edges=graph.nnz):
                ng = normalize_graph(graph)
                problem = ShardedTrustProblem.build(ng, mesh)
        start = None if t0 is None else _initial_vector(t0, ng.p)
        with TRACER.span("converge", backend=name) as span:
            out = converge_sharded(
                problem, alpha=alpha, tol=tol, max_iter=max_iter,
                record_residuals=record_residuals, t0=start,
            )
            return _fetch(out, name, record_residuals, span)


_BACKENDS = {
    "native-cpu": NativeCPUBackend,
    "tpu-dense": DenseJaxBackend,
    "tpu-sparse": SparseJaxBackend,
    "tpu-csr": CsrJaxBackend,
    "tpu-windowed": WindowedJaxBackend,
    "tpu-sharded": ShardedJaxBackend,
}


def registered_backends() -> list[str]:
    """Every constructible backend name, composites expanded — the
    enumeration the static analyzer hard-gates (each name must carry a
    ``KERNEL_INVARIANTS`` declaration next to its kernel AND a
    ``COMM_INVARIANTS`` declaration for graftlint pass 8, or
    ``python -m protocol_tpu.analysis`` fails the lint wall: an
    unregistered kernel budget is ``undeclared-backend``, an
    unregistered comm budget is ``undeclared-comm-budget`` — same
    policy, same gate).  Plain ``tpu-sharded`` is the
    ``tpu-sharded:tpu-csr`` composite."""
    from ..parallel.sharded import SHARDED_KERNELS

    names: list[str] = []
    for base in _BACKENDS:
        if base == "tpu-sharded":
            names.extend(f"{base}:{kernel}" for kernel in sorted(SHARDED_KERNELS))
        else:
            names.append(base)
    return names


def get_backend(name: str, **kwargs) -> TrustBackend:
    """Construct a backend by ladder name.  ``tpu-sharded`` accepts a
    per-shard kernel suffix — ``tpu-sharded:tpu-windowed`` — so config
    strings (ManagerConfig.backend / ProtocolConfig.trust_backend) can
    select the sharded kernel without code."""
    base, _, kernel = name.partition(":")
    if kernel:
        if base != "tpu-sharded":
            raise ValueError(
                f"unknown trust backend {name!r}; only tpu-sharded takes a "
                f":<kernel> suffix (available: {sorted(_BACKENDS)})"
            )
        kwargs.setdefault("kernel", kernel)
    try:
        return _BACKENDS[base](**kwargs)
    except KeyError:
        raise ValueError(
            f"unknown trust backend {name!r}; available: {sorted(_BACKENDS)}"
        ) from None
