"""Pass 1 — the jaxpr invariant analyzer.

For every backend in the ``trust/backend.py`` registry (composites
expanded, e.g. ``tpu-sharded:tpu-windowed`` under the virtual CPU
mesh), trace its per-iteration step function to a closed jaxpr on a
small synthetic graph, walk it with ``jaxpr_walk``, and check the
declarative :data:`~protocol_tpu.analysis.budget.KERNEL_INVARIANTS`
budget the kernel module declared for it:

- random-gather budget (gathers without ``indices_are_sorted``);
- size-classed gather budgets, including the single-pass boundary
  bridge's "exactly one streaming ``(S, 2)`` sorted+unique read, one
  ``S``-sized random permutation" contract (PERF.md §8);
- scatter budget (the windowed/CSR steps are scatter-free by design);
- no float64 avals (TPU f64 is emulated — a silent 10× rot);
- no host callbacks inside the jit'd loop;
- ``psum`` count and placement (exactly one, only under ``shard_map``,
  for the sharded composites; zero elsewhere);
- donated-argument aliasing actually materialized in the lowered
  computation (``tf.aliasing_output`` / ``jax.buffer_donor``).

A registered jax backend with no declared budget is itself an error —
the gate every future backend inherits.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

from .budget import KERNEL_INVARIANTS, NON_JAX_BACKENDS, KernelBudget
from .jaxpr_walk import (
    CALLBACK_PRIMITIVES,
    PSUM_PRIMITIVES,
    SCATTER_PRIMITIVES,
    EqnSite,
    collect_primitives,
    has_f64,
    iter_eqns,
    source_site,
)
from .report import Finding

#: Donation markers jax stamps on lowered (StableHLO) inputs.
_DONATION_MARKS = ("tf.aliasing_output", "jax.buffer_donor")


@dataclass
class TraceCase:
    """One backend's traced step plus the context to interpret it."""

    backend: str
    jaxpr: Any  # closed jaxpr of the per-iteration step (or full run)
    #: Named sizes resolving :class:`GatherBudget` dims, e.g.
    #: ``{"edges": 8993, "n_segments": 1575}``.
    dims: dict[str, int] = field(default_factory=dict)
    #: Lowered text of the jit'd converge entry point (donation check);
    #: None when the budget declares no donated args.
    lowered_text: str | None = None


def _synthetic_graph():
    """Small scale-free graph every trace shares: multi-window N, forced
    dangling peers, sizes chosen so the budget dimensions stay
    distinguishable (asserted in the windowed recipes)."""
    from .comm.lowering import _graph

    return _graph(1500, 9000)


def _trace_dense(graph) -> TraceCase:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.dense import converge_dense

    rng = np.random.default_rng(0)
    n = 64
    m = rng.random((n, n)).astype(np.float32)
    m /= m.sum(axis=0, keepdims=True)
    t = np.full(n, 1.0 / n, np.float32)
    jaxpr = jax.make_jaxpr(lambda mm, tt: converge_dense(mm, tt, 4))(
        jnp.asarray(m), jnp.asarray(t)
    )
    return TraceCase("tpu-dense", jaxpr, dims={"n": n})


def _trace_sparse(graph) -> TraceCase:
    import jax
    import jax.numpy as jnp

    from ..ops.sparse import converge_sparse, power_step_coo
    from ..trust.backend import normalize_graph
    from .comm.lowering import _operands

    ng = normalize_graph(graph)
    args = _operands(ng, "coo") + (jnp.asarray(0.1, jnp.float32),)
    jaxpr = jax.make_jaxpr(
        lambda s, d, wt, t, pp, dg, a: power_step_coo(s, d, wt, t, pp, dg, a, n=ng.n)
    )(*args)
    lowered = converge_sparse.lower(
        *args[:6], n=ng.n, alpha=args[6], tol=1e-6, max_iter=4
    ).as_text()
    return TraceCase(
        "tpu-sparse", jaxpr, dims={"edges": ng.nnz, "n": ng.n}, lowered_text=lowered
    )


def _trace_csr(graph) -> TraceCase:
    import jax
    import jax.numpy as jnp

    from ..ops.sparse import converge_csr, power_step_csr
    from ..trust.backend import normalize_graph
    from .comm.lowering import _operands

    ng = normalize_graph(graph)
    args = _operands(ng, "csr") + (jnp.asarray(0.1, jnp.float32),)
    jaxpr = jax.make_jaxpr(lambda *a: power_step_csr(*a))(*args)
    lowered = converge_csr.lower(
        *args[:6], alpha=args[6], tol=1e-6, max_iter=4
    ).as_text()
    return TraceCase(
        "tpu-csr", jaxpr, dims={"edges": ng.nnz, "n": ng.n}, lowered_text=lowered
    )


def _trace_windowed(graph) -> TraceCase:
    import jax
    import jax.numpy as jnp

    from ..ops.gather_window import (
        build_window_plan,
        converge_windowed,
        power_step_windowed,
    )
    from ..trust.backend import normalize_graph
    from .comm.lowering import _operands

    ng = normalize_graph(graph)
    plan = build_window_plan(ng.src, ng.dst, ng.w, n=ng.n)
    # Keep the budget dimensions distinguishable: the rowsum gathers are
    # (n+1)-sized, the bridge reads seg_capacity-sized (the device
    # length of the padded run table, >= n_segments live runs).
    assert plan.seg_capacity != ng.n + 1, "synthetic graph aliases budget dims"
    args = _operands(ng, plan) + (jnp.asarray(0.1, jnp.float32),)
    jaxpr = jax.make_jaxpr(
        lambda *a: power_step_windowed(
            *a,
            **plan.static_kwargs(),
            interpret=True,
        )
    )(*args)
    lowered = converge_windowed.lower(
        *args[:-1],
        **plan.static_kwargs(),
        alpha=args[-1],
        tol=1e-6,
        max_iter=4,
        interpret=True,
    ).as_text()
    return TraceCase(
        "tpu-windowed",
        jaxpr,
        dims={"n_segments": plan.seg_capacity, "n": ng.n},
        lowered_text=lowered,
    )


def _trace_sharded_csr(graph) -> TraceCase:
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ..parallel.mesh import SHARD_AXIS, default_mesh
    from ..parallel.sharded import ShardedTrustProblem, _get_runner
    from ..trust.backend import normalize_graph

    mesh = default_mesh()
    prob = ShardedTrustProblem.build(normalize_graph(graph), mesh)
    run = _get_runner(mesh, prob.n)
    args = (
        prob.src,
        prob.w,
        prob.row_ptr,
        prob.t0(),
        prob.p,
        prob.dangling,
        jnp.asarray(0.1, jnp.float32),
    )
    jaxpr = jax.make_jaxpr(partial(run, max_iter=4, tol=1e-6))(*args)
    lowered = run.lower(*args, max_iter=4, tol=1e-6).as_text()
    shard_edges = prob.src.shape[0] // mesh.shape[SHARD_AXIS]
    return TraceCase(
        "tpu-sharded:tpu-csr",
        jaxpr,
        dims={"edges": shard_edges, "n": prob.n},
        lowered_text=lowered,
    )


def _trace_sharded_windowed(graph) -> TraceCase:
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ..parallel.mesh import default_mesh
    from ..parallel.sharded import (
        ShardedWindowPlan,
        windowed_operands,
        windowed_runner,
    )
    from ..trust.backend import normalize_graph

    mesh = default_mesh()
    swp = ShardedWindowPlan.build(normalize_graph(graph), mesh)
    assert swp.s_max != swp.n + 1, "synthetic graph aliases budget dims"
    run = windowed_runner(swp)
    args = (
        *windowed_operands(swp),
        swp.t0(),
        swp.p,
        swp.dangling,
        jnp.asarray(0.1, jnp.float32),
    )
    jaxpr = jax.make_jaxpr(partial(run, max_iter=4, tol=1e-6))(*args)
    lowered = run.lower(*args, max_iter=4, tol=1e-6).as_text()
    return TraceCase(
        "tpu-sharded:tpu-windowed",
        jaxpr,
        dims={"n_segments": swp.s_max, "n": swp.n},
        lowered_text=lowered,
    )


#: Backend name -> trace recipe.  A budget with no recipe is an error
#: (the table must not claim coverage it cannot check).
TRACE_BUILDERS: dict[str, Callable[[Any], TraceCase]] = {
    "tpu-dense": _trace_dense,
    "tpu-sparse": _trace_sparse,
    "tpu-csr": _trace_csr,
    "tpu-windowed": _trace_windowed,
    "tpu-sharded:tpu-csr": _trace_sharded_csr,
    "tpu-sharded:tpu-windowed": _trace_sharded_windowed,
}


def _anchor(site: EqnSite | None) -> dict[str, Any]:
    if site is None:
        return {"file": None, "line": None}
    f, line = source_site(site.eqn)
    return {"file": f, "line": line}


def check_case(budget: KernelBudget, case: TraceCase) -> list[Finding]:
    """Evaluate one backend's budget against its traced step."""
    findings: list[Finding] = []
    jaxpr = case.jaxpr

    def err(rule: str, message: str, site: EqnSite | None = None) -> None:
        findings.append(
            Finding(
                pass_name="jaxpr",
                rule=rule,
                severity="error",
                message=message,
                backend=case.backend,
                **_anchor(site),
            )
        )

    # Gathers, excluding interpret-mode pallas bodies (not XLA gathers
    # on the real chip — the windowed resolve is Mosaic codegen there).
    gathers = collect_primitives(jaxpr, {"gather"}, exclude_under=("pallas_call",))
    random_gathers = [g for g in gathers if not g.sorted_indices]
    if len(random_gathers) > budget.max_random_gathers:
        err(
            "gather-budget",
            f"{len(random_gathers)} random gathers per step exceed the "
            f"declared budget of {budget.max_random_gathers}",
            random_gathers[-1],
        )

    for gb in budget.gather_budgets:
        size = case.dims.get(gb.dim)
        if size is None:
            err("gather-budget", f"trace reports no dimension {gb.dim!r}")
            continue
        sized = [g for g in gathers if g.out_shape[:1] == (size,)]
        sized_random = [g for g in sized if not g.sorted_indices]
        if len(sized) > gb.max_total:
            err(
                "sized-gather-budget",
                f"{len(sized)} {gb.dim}-sized gathers exceed the budget "
                f"of {gb.max_total}",
                sized[-1],
            )
        if len(sized_random) > gb.max_random:
            err(
                "random-gather-budget",
                f"{len(sized_random)} random {gb.dim}-sized gathers per "
                f"step exceed the budget of {gb.max_random} (the "
                f"single-pass bridge allows exactly one random pass)",
                sized_random[-1],
            )

    # Scatters (scatter-free is the whole point of the CSR/windowed
    # formulations — TPU scatter serializes on destination indices).
    scatters = collect_primitives(
        jaxpr, SCATTER_PRIMITIVES, exclude_under=("pallas_call",)
    )
    if len(scatters) > budget.max_scatters:
        err(
            "scatter-budget",
            f"{len(scatters)} scatter ops per step exceed the declared "
            f"budget of {budget.max_scatters}",
            scatters[-1],
        )

    # f64 leaks.
    if not budget.allow_f64:
        leaks = has_f64(jaxpr)
        if leaks:
            err(
                "f64-dtype",
                f"{len(leaks)} equation(s) produce float64 inside the "
                "jit'd step (TPU f64 is emulated; keep the double-single "
                "(hi, lo) form instead)",
                leaks[0],
            )

    # Host callbacks.
    callbacks = collect_primitives(jaxpr, CALLBACK_PRIMITIVES)
    if callbacks:
        err(
            "callback-in-jit",
            f"host callback primitive {callbacks[0].primitive!r} inside "
            "the jit'd step (one host round-trip per iteration)",
            callbacks[0],
        )

    # psum count and placement.
    psums = collect_primitives(jaxpr, PSUM_PRIMITIVES)
    if len(psums) != budget.psum_count:
        err(
            "psum-count",
            f"expected exactly {budget.psum_count} psum per step, found "
            f"{len(psums)}",
            psums[-1] if psums else None,
        )
    for site in psums:
        if not site.under("shard_map"):
            err(
                "psum-outside-shard-map",
                "psum outside shard_map: the collective has no mesh axis "
                "to reduce over",
                site,
            )

    # Required structural primitives (MXU matmul, Pallas kernel, ...).
    present = {s.primitive for s in iter_eqns(jaxpr)}
    for prim in budget.require_primitives:
        if prim not in present:
            err(
                "missing-primitive",
                f"required primitive {prim!r} absent from the step (the "
                "fast path has been rewritten away)",
            )

    # Donated-argument aliasing must materialize in the lowering.
    if budget.donated_args:
        text = case.lowered_text
        if text is None:
            err(
                "donation-not-materialized",
                "budget declares donated args but the trace recipe "
                "provides no lowered computation to verify against",
            )
        else:
            marks = sum(text.count(m) for m in _DONATION_MARKS)
            if marks < len(budget.donated_args):
                err(
                    "donation-not-materialized",
                    f"{len(budget.donated_args)} donated arg(s) declared "
                    f"({', '.join(budget.donated_args)}) but only {marks} "
                    "aliasing mark(s) in the lowered computation",
                )
    return findings


def run_jaxpr_pass(
    backends: list[str] | None = None,
) -> tuple[list[Finding], dict[str, dict[str, Any]]]:
    """Trace and check every registered backend (or the given subset).

    Returns ``(findings, per-backend metadata)`` — the metadata feeds
    ANALYSIS.json (budget summary, dims, invariants_checked).
    """
    # Importing the registry imports the kernel modules, which declare
    # their budgets; the sharded module only loads lazily elsewhere.
    from .. import parallel  # noqa: F401  (namespace anchor)
    from ..parallel import sharded  # noqa: F401  (declares sharded budgets)
    from ..trust.backend import registered_backends
    from .zk_lowering import register as _register_zk

    registry = registered_backends()
    # The zk.graft proving kernels ride the default gate here: tracing
    # them is cheap (their expensive leg is compile, gated behind
    # ``--zk`` in the later passes).
    zk_names = _register_zk()
    targets = registry + zk_names if backends is None else backends
    findings: list[Finding] = []
    meta: dict[str, dict[str, Any]] = {}
    graph = _synthetic_graph()

    for name in targets:
        if name in NON_JAX_BACKENDS:
            meta[name] = {"status": "skipped", "reason": "non-jax backend"}
            findings.append(
                Finding(
                    pass_name="jaxpr",
                    rule="non-jax-backend",
                    severity="info",
                    message=f"{name} runs outside jax; no jaxpr to check",
                    backend=name,
                )
            )
            continue
        budget = KERNEL_INVARIANTS.get(name)
        if budget is None:
            meta[name] = {"status": "undeclared"}
            findings.append(
                Finding(
                    pass_name="jaxpr",
                    rule="undeclared-backend",
                    severity="error",
                    message=(
                        f"registered backend {name!r} declares no kernel "
                        "budget; add a KERNEL_INVARIANTS declaration next "
                        "to its kernel (see PERF.md §9)"
                    ),
                    backend=name,
                )
            )
            continue
        builder = TRACE_BUILDERS.get(name)
        if builder is None:
            meta[name] = {"status": "no-recipe"}
            findings.append(
                Finding(
                    pass_name="jaxpr",
                    rule="no-trace-recipe",
                    severity="error",
                    message=(
                        f"budget declared for {name!r} but the analyzer "
                        "has no trace recipe; coverage would be vacuous"
                    ),
                    backend=name,
                )
            )
            continue
        try:
            case = builder(graph)
        except Exception as exc:  # noqa: BLE001 - report, don't crash the gate
            meta[name] = {"status": "trace-failed", "error": repr(exc)}
            findings.append(
                Finding(
                    pass_name="jaxpr",
                    rule="trace-failure",
                    severity="error",
                    message=f"tracing the step failed: {exc!r}",
                    backend=name,
                )
            )
            continue
        case_findings = check_case(budget, case)
        findings.extend(case_findings)
        meta[name] = {
            "status": "checked",
            "invariants_checked": budget.invariant_count,
            "violations": len(case_findings),
            "dims": case.dims,
            "budget": {
                "max_random_gathers": budget.max_random_gathers,
                "max_scatters": budget.max_scatters,
                "psum_count": budget.psum_count,
                "require_primitives": list(budget.require_primitives),
                "donated_args": list(budget.donated_args),
                "gather_budgets": [
                    {
                        "dim": gb.dim,
                        "max_total": gb.max_total,
                        "max_random": gb.max_random,
                    }
                    for gb in budget.gather_budgets
                ],
            },
        }

    # Budgets declared for names no longer in the registry rot silently.
    if backends is None:
        known = set(registry) | set(zk_names)
        for name in sorted(set(KERNEL_INVARIANTS) - known):
            findings.append(
                Finding(
                    pass_name="jaxpr",
                    rule="stale-budget",
                    severity="warning",
                    message=(
                        f"budget declared for {name!r} which is not a "
                        "registered backend"
                    ),
                    backend=name,
                )
            )
    return findings, meta


__all__ = ["TraceCase", "TRACE_BUILDERS", "check_case", "run_jaxpr_pass"]
