"""Per-backend lowering recipes for pass 8.

Each recipe builds the backend's real converge entry point on a
synthetic graph, lowers it through the real jit path, **compiles** it
under the 8-device CPU mesh (the SPMD partitioner only runs at
compile), and returns the module text plus the context needed to judge
it: the problem dims the byte budget is a function of, the entry-point
argument names (so ``donated_args`` resolve to parameter numbers in
the ``input_output_alias`` table), and the jaxpr-level psum count for
the lowering cross-check.

Scales: the sharded composites — the only backends whose lowering can
legally contain collectives — are compiled at **two** scales where the
edge count grows 4x but N only 2x, so a byte volume that follows E
breaks the (linear in N/S) budget at the second scale no matter how
the constants were padded.  Single-device backends compile once: their
budget is zero collectives at any scale, so a second compile proves
nothing and the (Pallas-interpret) windowed compile is the analyzer's
dominant cost.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

from ..jaxpr_walk import PSUM_PRIMITIVES, collect_primitives

#: (n_peers, n_edges) per scale: E x4, N x2 between the two.
COMM_SCALES: tuple[tuple[int, int], ...] = ((1024, 4096), (2048, 16384))

#: Shard count of the analysis mesh (tests/conftest.py doctrine).
N_SHARDS = 8


@dataclass
class CommCase:
    """One backend at one scale: the compiled module plus its context."""

    backend: str
    #: Budget dimensions: n, edges, n_shards, n_segments where the
    #: backend has a segment table, and n_rows where it has a windowed
    #: plan (per-shard vreg-rows — the pass-12 resident dimension).
    dims: dict[str, int]
    #: ``compiled.as_text()`` of the converge entry point.
    module_text: str
    #: Entry-point argument names, parameter order (donation mapping).
    arg_names: tuple[str, ...]
    #: psum/psum_invariant count in the traced jaxpr of the same entry point.
    jaxpr_psums: int = 0
    #: Buffer-assignment view of the same executable (pass 12):
    #: ``compiled.memory_analysis()`` per-device byte totals, or None
    #: when the runtime exposes no memory analysis — the memory checker
    #: then falls back to the conservative live-range walk over
    #: ``module_text``.
    mem: dict[str, int] | None = None
    #: Free-form per-scale metadata for ANALYSIS.json.
    meta: dict[str, Any] = field(default_factory=dict)


def _mem_stats(compiled: Any) -> dict[str, int] | None:
    """Per-device buffer-assignment totals of one executable, or None
    when the backend has no ``memory_analysis`` (older runtimes)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - absence degrades to the HLO walk
        return None
    if ma is None:
        return None
    try:
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
        }
    except AttributeError:
        return None


def _graph(n: int, e: int):
    import numpy as np

    from ...models.graphs import scale_free
    from ...trust.graph import TrustGraph

    g = scale_free(n, e, seed=2)
    keep = ~np.isin(g.src, np.asarray([0, 17, n - 1], dtype=np.int32))
    return TrustGraph(g.n, g.src[keep], g.dst[keep], g.weight[keep], g.pre_trusted)


def _operands(ng, layout) -> tuple:
    """A single-device converge's operands over the normalized graph
    (shared with ``analysis.invariants``): ``(src, dst, w)`` for
    ``layout`` ``"coo"``, ``(src, row_ptr, w)`` for ``"csr"``, or a
    ``WindowPlan``'s device arrays, then ``(t0, p, dangling)`` seeded at
    the pre-trust vector."""
    import jax.numpy as jnp

    vectors = tuple(jnp.asarray(a) for a in (ng.p, ng.p, ng.dangling))
    if not isinstance(layout, str):
        return layout.device_args() + vectors
    gs = ng.by_dst()
    index = gs.dst if layout == "coo" else gs.row_ptr_by_dst()
    return tuple(jnp.asarray(a) for a in (gs.src, index, gs.weight)) + vectors


def _jaxpr_psums(jaxpr: Any) -> int:
    return len(collect_primitives(jaxpr, PSUM_PRIMITIVES))


def _lower_dense(n: int, e: int) -> CommCase:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ...ops.dense import converge_dense

    rng = np.random.default_rng(0)
    size = 64
    m = rng.random((size, size)).astype(np.float32)
    m /= m.sum(axis=0, keepdims=True)
    t = jnp.asarray(np.full(size, 1.0 / size, np.float32))
    m = jnp.asarray(m)
    compiled = converge_dense.lower(m, t, 4).compile()
    jaxpr = jax.make_jaxpr(lambda mm, tt: converge_dense(mm, tt, 4))(m, t)
    return CommCase(
        backend="tpu-dense",
        dims={"n": size, "edges": size * size, "n_shards": 1},
        module_text=compiled.as_text(),
        arg_names=("ops_t", "s0"),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


def _lower_sparse(n: int, e: int) -> CommCase:
    import jax
    import jax.numpy as jnp

    from ...ops.sparse import converge_sparse
    from ...trust.backend import normalize_graph

    ng = normalize_graph(_graph(n, e))
    args = _operands(ng, "coo")
    kw = dict(n=ng.n, alpha=jnp.asarray(0.1, jnp.float32), tol=1e-6, max_iter=4)
    compiled = converge_sparse.lower(*args, **kw).compile()
    jaxpr = jax.make_jaxpr(
        lambda *a: converge_sparse(*a, **kw), static_argnums=()
    )(*args)
    return CommCase(
        backend="tpu-sparse",
        dims={"n": ng.n, "edges": ng.nnz, "n_shards": 1},
        module_text=compiled.as_text(),
        arg_names=("src", "dst", "w", "t0", "p", "dangling"),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


def _lower_csr(n: int, e: int) -> CommCase:
    import jax
    import jax.numpy as jnp

    from ...ops.sparse import converge_csr
    from ...trust.backend import normalize_graph

    ng = normalize_graph(_graph(n, e))
    args = _operands(ng, "csr")
    kw = dict(alpha=jnp.asarray(0.1, jnp.float32), tol=1e-6, max_iter=4)
    compiled = converge_csr.lower(*args, **kw).compile()
    jaxpr = jax.make_jaxpr(lambda *a: converge_csr(*a, **kw))(*args)
    return CommCase(
        backend="tpu-csr",
        dims={"n": ng.n, "edges": ng.nnz, "n_shards": 1},
        module_text=compiled.as_text(),
        arg_names=("src", "row_ptr", "w", "t0", "p", "dangling"),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


def _lower_windowed(n: int, e: int) -> CommCase:
    import jax
    import jax.numpy as jnp

    from ...ops.gather_window import build_window_plan, converge_windowed
    from ...trust.backend import normalize_graph

    ng = normalize_graph(_graph(n, e))
    plan = build_window_plan(ng.src, ng.dst, ng.w, n=ng.n)
    args = _operands(ng, plan)
    kw = dict(
        **plan.static_kwargs(),
        alpha=jnp.asarray(0.1, jnp.float32),
        tol=1e-6,
        max_iter=4,
        interpret=True,
    )
    compiled = converge_windowed.lower(*args, **kw).compile()
    jaxpr = jax.make_jaxpr(lambda *a: converge_windowed(*a, **kw))(*args)
    return CommCase(
        backend="tpu-windowed",
        dims={
            "n": ng.n,
            "edges": ng.nnz,
            "n_segments": plan.seg_capacity,
            "n_rows": plan.n_rows,
            "n_shards": 1,
        },
        module_text=compiled.as_text(),
        arg_names=(
            "wid", "local", "weight", "run_start", "run_end", "dst_ptr",
            "t0", "p", "dangling",
        ),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


def _lower_sharded_csr(n: int, e: int) -> CommCase:
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ...parallel.mesh import SHARD_AXIS, default_mesh
    from ...parallel.sharded import ShardedTrustProblem, _get_runner
    from ...trust.backend import normalize_graph

    mesh = default_mesh()
    prob = ShardedTrustProblem.build(normalize_graph(_graph(n, e)), mesh)
    run = _get_runner(mesh, prob.n)
    args = (
        prob.src, prob.w, prob.row_ptr, prob.t0(), prob.p, prob.dangling,
        jnp.asarray(0.1, jnp.float32),
    )
    kw = dict(max_iter=4, tol=1e-6)
    compiled = run.lower(*args, **kw).compile()
    jaxpr = jax.make_jaxpr(partial(run, **kw))(*args)
    return CommCase(
        backend="tpu-sharded:tpu-csr",
        dims={
            "n": prob.n,
            "edges": int(prob.src.shape[0]),
            "n_shards": mesh.shape[SHARD_AXIS],
        },
        module_text=compiled.as_text(),
        arg_names=("src", "w", "row_ptr", "t0", "p", "dangling", "alpha"),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


def _lower_sharded_windowed(n: int, e: int) -> CommCase:
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ...parallel.mesh import SHARD_AXIS, default_mesh
    from ...parallel.sharded import (
        ShardedWindowPlan,
        windowed_operands,
        windowed_runner,
    )
    from ...trust.backend import normalize_graph

    mesh = default_mesh()
    ng = normalize_graph(_graph(n, e))
    swp = ShardedWindowPlan.build(ng, mesh)
    run = windowed_runner(swp)
    args = (
        *windowed_operands(swp), swp.t0(), swp.p, swp.dangling,
        jnp.asarray(0.1, jnp.float32),
    )
    kw = dict(max_iter=4, tol=1e-6)
    compiled = run.lower(*args, **kw).compile()
    jaxpr = jax.make_jaxpr(partial(run, **kw))(*args)
    return CommCase(
        backend="tpu-sharded:tpu-windowed",
        dims={
            "n": swp.n,
            "edges": ng.nnz,
            "n_segments": swp.s_max,
            "n_rows": swp.rows_per_shard,
            "n_shards": mesh.shape[SHARD_AXIS],
        },
        module_text=compiled.as_text(),
        arg_names=(
            "wid", "local", "weight", "run_start", "run_end", "dst_ptr",
            "t0", "p", "dangling", "alpha",
        ),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(compiled),
    )


#: backend -> (recipe, compiled at both COMM_SCALES?).  Only the
#: sharded composites pay for the second scale — they are the backends
#: whose lowering may legally communicate.
COMM_BUILDERS: dict[str, tuple[Callable[[int, int], CommCase], bool]] = {
    "tpu-dense": (_lower_dense, False),
    "tpu-sparse": (_lower_sparse, False),
    "tpu-csr": (_lower_csr, False),
    "tpu-windowed": (_lower_windowed, False),
    "tpu-sharded:tpu-csr": (_lower_sharded_csr, True),
    "tpu-sharded:tpu-windowed": (_lower_sharded_windowed, True),
}


#: Per-process case memo: pass 8 and pass 12 judge the SAME executables
#: (comm walks the module text, memory the buffer assignment), so a
#: full ``--pass all`` run compiles each backend once, not twice — the
#: windowed Pallas-interpret compiles dominate the analyzer's wall
#: clock (the self-budget test).  Keyed by backend; the recipes are
#: deterministic in-process, and the synthetic graphs never change
#: under one run.
_CASE_CACHE: dict[str, list[CommCase]] = {}


def build_cases(backend: str) -> list[CommCase]:
    """Compile ``backend`` at its scale set and return one case per
    scale (memoized per process).  Raises KeyError for a backend
    without a recipe."""
    cached = _CASE_CACHE.get(backend)
    if cached is not None:
        return cached
    recipe, two_scale = COMM_BUILDERS[backend]
    scales = COMM_SCALES if two_scale else COMM_SCALES[:1]
    cases = [recipe(n, e) for n, e in scales]
    _CASE_CACHE[backend] = cases
    return cases


__all__ = ["COMM_BUILDERS", "COMM_SCALES", "CommCase", "N_SHARDS", "build_cases"]
