"""Edge-sharded trust convergence over a device mesh.

Layout (the scaling-book recipe: pick a mesh, annotate shardings, let
XLA place collectives):

- **edges** (src, w): sharded on the leading axis across the mesh —
  each device owns a contiguous dst-sorted slice, padded with w=0 to
  equal length.  50M edges over 8 chips = 6.25M edges/chip, streamed
  sequentially from HBM.
- **row_ptr**: per-shard CSR-by-dst pointers into the local edge slice
  (``(n_shards, n+1)`` sharded on axis 0), precomputed on the host by
  clipping the global pointer array to each shard's range.  This lets
  every shard run the same scatter-free ``rowsum_sorted`` cumsum kernel
  as the single-device ``tpu-csr`` path (the old per-shard
  ``segment_sum`` measured 2.4× slower end-to-end at full scale).
- **t, p, dangling**: replicated (a 1M-peer f32 vector is 4 MB — cheap
  to replicate, expensive to re-gather per step).
- per step, inside ``shard_map``: each device computes its partial
  ``Cᵀt`` by gather-multiply-``rowsum_sorted`` over its edge slice, then
  a single ``lax.psum`` over ICI produces the full product — boundary
  destinations whose edge runs straddle a shard cut are partially
  summed on each side and completed by that same psum; damping and L1
  renorm are elementwise on the replicated result so every device stays
  consistent without further communication.

Two kernels share this recipe (``SHARDED_KERNELS``, selectable as
``tpu-sharded:<kernel>`` in ManagerConfig/ProtocolConfig):

- ``tpu-csr`` — ``ShardedTrustProblem``: the gather-only CSR/cumsum
  SpMV above, with the O(E) random ``t[src]`` gather per shard.
- ``tpu-windowed`` — ``ShardedWindowPlan``: the fused fixed-slot
  pipeline taken multi-chip.  The one-time
  ``WindowPlan`` is partitioned by *window rows*: each shard owns a
  contiguous, BLOCK_ROWS-aligned slice of the plan's vreg-rows (runs
  never span rows, so the bucket-order segment table splits at the
  same cuts), rebased to shard-local slots and padded to the mesh
  maximum; each shard runs the identical ``windowed_ct`` step —
  windowed Pallas gather from the replicated score table (streamed by
  chunk: the cuts are grid-block cuts, so a shard's blocks keep the
  plan's chunk alignment), row-local prefix sum, single-pass boundary
  bridge — over its slice, and the
  per-shard partial Cᵀt vectors are completed by the same ``lax.psum``
  (dst rows whose runs land on several shards are partially summed on
  each side, exactly like CSR rows straddling a shard cut).

Both take the graph as ``trust.backend.normalize_graph`` leaves it
(``NormalizedGraph``); neither normalizes anything itself.

This is the distributed analog of the reference's single-threaded
5×5×10 loop (circuit/src/circuit.rs:434-454) at 10^6 peers.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..analysis.budget import (
    CollectiveBudget,
    CommBudget,
    GatherBudget,
    KernelBudget,
    MemBudget,
    declare,
    declare_comm,
    declare_mem,
)
from ..device import pallas_interpret
from ..ops.gather_window import (
    BLOCK_ROWS,
    ROW,
    WindowPlan,
    _counting_sort,
    resolve_plan,
    windowed_ct,
)
from ..trust.graph import NormalizedGraph
from .mesh import SHARD_AXIS


@dataclass
class ShardedTrustProblem:
    """Device-resident, mesh-sharded graph data ready for iteration."""

    mesh: Mesh
    n: int
    src: jax.Array  # (E_pad,) int32, sharded
    w: jax.Array  # (E_pad,) f32, sharded, row-normalized
    row_ptr: jax.Array  # (n_shards, n+1) int32, sharded on axis 0
    p: jax.Array  # (n,) f32, replicated
    dangling: jax.Array  # (n,) f32, replicated

    @classmethod
    def build(cls, graph: NormalizedGraph, mesh: Mesh) -> "ShardedTrustProblem":
        """Host-side assembly of the normalized graph: sort by dst, pad
        to the mesh size, derive per-shard row pointers, and place
        arrays with explicit shardings."""
        g = graph.by_dst()

        n_shards = mesh.shape[SHARD_AXIS]
        pad = (-g.nnz) % n_shards
        src = np.concatenate([g.src, np.zeros(pad, np.int32)])
        wpad = np.concatenate([g.weight, np.zeros(pad, np.float32)])
        # Per-shard CSR-by-dst pointers: clip the global pointer array
        # to each shard's slice.  A destination whose edges straddle a
        # shard cut gets a partial range on both sides — each shard
        # contributes its partial row sum and the psum completes it.
        # Pad-tail slots (w=0) sit beyond every clipped pointer and are
        # never differenced into any row.
        gptr = g.row_ptr_by_dst().astype(np.int64)
        m = (g.nnz + pad) // n_shards
        starts = np.arange(n_shards, dtype=np.int64)[:, None] * m
        row_ptr = (np.clip(gptr[None, :], starts, starts + m) - starts).astype(np.int32)

        edge_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        repl = NamedSharding(mesh, P())
        return cls(
            mesh=mesh,
            n=g.n,
            src=jax.device_put(src, edge_sharding),
            w=jax.device_put(wpad, edge_sharding),
            row_ptr=jax.device_put(row_ptr, NamedSharding(mesh, P(SHARD_AXIS, None))),
            p=jax.device_put(graph.p, repl),
            dangling=jax.device_put(graph.dangling, repl),
        )

    def t0(self) -> jax.Array:
        """Initial score vector: the pre-trust distribution (the scaled
        analog of everyone starting at INITIAL_SCORE).  A fresh device
        copy, not ``p`` itself: the runners donate ``t0`` and ``p``
        must survive the iteration it seeds."""
        return jnp.copy(self.p)


# Compiled runners keyed by (mesh, n) for the CSR kernel and by
# (mesh, n, rows_per_shard, table_entries, interpret, table_chunk) for
# the windowed kernel: jax's jit cache is keyed on function identity,
# so rebuilding the closures per call would recompile the whole
# while_loop every epoch.
_RUN_CACHE: dict = {}


def _get_runner(mesh: Mesh, n: int):
    key = (mesh, n)
    if key in _RUN_CACHE:
        return _RUN_CACHE[key]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS),
            P(SHARD_AXIS),
            P(SHARD_AXIS, None),
            P(),
            P(),
            P(),
            P(),
        ),
        out_specs=P(),
    )
    def step(src, w, row_ptr, t, p, dangling, alpha):
        from ..ops.sparse import rowsum_sorted

        # The same scatter-free cumsum rowsum as the single-device CSR
        # fast path (ops.sparse.power_step_csr); boundary rows split
        # across shards are completed by the psum below.
        contrib = w * t[src]
        partial_ct = rowsum_sorted(contrib, row_ptr[0])
        ct = lax.psum(partial_ct, SHARD_AXIS)
        dangling_mass = jnp.sum(t * dangling)
        t_new = (1.0 - alpha) * (ct + dangling_mass * p) + alpha * p
        return t_new / jnp.sum(t_new)

    @partial(
        jax.jit,
        static_argnames=("max_iter", "tol", "record_residuals"),
        donate_argnames=("t0",),
    )
    def run(
        src, w, row_ptr, t0, p, dangling, alpha,
        *, max_iter, tol, record_residuals=False,
    ):
        from ..ops.sparse import run_power_iteration

        # t0 is donated (same contract as converge_csr): the iteration
        # consumes the seed in place — callers stage a fresh replicated
        # buffer per converge (problem.t0() copies, converge_sharded
        # device_puts warm seeds).  Pass 8 pins the aliasing in the
        # compiled module, not just here.
        return run_power_iteration(
            lambda t: step(src, w, row_ptr, t, p, dangling, alpha),
            t0,
            tol=tol,
            max_iter=max_iter,
            record_residuals=record_residuals,
        )

    _RUN_CACHE[key] = run
    return run


def _partition_plan_arrays(
    plan: WindowPlan,
    n_shards: int,
    *,
    rows_per_shard: int | None = None,
    s_max: int | None = None,
) -> dict:
    """Host-side partition of one ``WindowPlan`` into ``n_shards``
    contiguous, BLOCK_ROWS-aligned vreg-row slices — the shared cut
    used by the single-host ``ShardedWindowPlan`` and, per host, by the
    pod builder (``parallel.pod``).  ``rows_per_shard``/``s_max`` may
    be forced upward by the caller: a pod must pad every host's
    partition to the pod-wide maxima so the global shard shapes (and
    the compiled runner) agree across processes.  Returns the numpy
    shard tables plus the resolved dimensions."""
    min_rps = -(-plan.n_rows // (n_shards * BLOCK_ROWS)) * BLOCK_ROWS
    if rows_per_shard is None:
        rows_per_shard = min_rps
    elif rows_per_shard < min_rps or rows_per_shard % BLOCK_ROWS:
        raise ValueError(
            f"rows_per_shard={rows_per_shard} cannot hold {plan.n_rows} "
            f"plan rows over {n_shards} shards (need >= {min_rps}, "
            f"BLOCK_ROWS-aligned)"
        )
    total_rows = n_shards * rows_per_shard
    wid = np.zeros(total_rows, np.int32)
    wid[: plan.n_rows] = plan.wid
    local = np.zeros((total_rows * 8, 128), np.int32)
    local[: plan.n_rows * 8] = plan.local
    weight = np.zeros((total_rows * 8, 128), np.float32)
    weight[: plan.n_rows * 8] = plan.weight
    run_start = np.zeros((total_rows * 8, 128), bool)
    run_start[: plan.n_rows * 8] = plan.run_start

    # Segment table: bucket order is slot order, so the row cuts give
    # contiguous per-shard slices.  Only the plan's live runs partition
    # — its device-capacity pads are regenerated here as per-shard
    # padding.
    live_end = plan.seg_end[: plan.n_segments]
    shard_of = (live_end // ROW) // rows_per_shard
    counts = np.bincount(shard_of, minlength=n_shards)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    # Quantized per-shard run capacity: small per-epoch deltas keep the
    # sharded array shapes (and the compiled runner) stable.
    min_smax = -(-max(int(counts.max()), 1) // 1024) * 1024
    if s_max is None:
        s_max = min_smax
    elif s_max < min_smax:
        raise ValueError(
            f"s_max={s_max} below this plan's per-shard run count "
            f"{min_smax}"
        )
    # Bucket-order run destinations: stored on the plan since layout v3
    # (the delta-update bookkeeping keeps it current).
    seg_dst = plan.seg_dst
    # Pad runs end at each shard's slot 0; they land beyond
    # dst_ptr[k, n] and are dropped.
    run_end = np.zeros((n_shards, s_max), np.int32)
    dst_ptr = np.zeros((n_shards, plan.n + 1), np.int32)
    for k in range(n_shards):
        beg, end = int(offsets[k]), int(offsets[k + 1])
        sk = end - beg
        if sk:
            shard_end = live_end[beg:end] - np.int32(k * rows_per_shard * ROW)
            _, dst_counts, ends = _counting_sort(
                seg_dst[beg:end], plan.n, payload=shard_end
            )
            run_end[k, :sk] = ends
            np.cumsum(dst_counts, out=dst_ptr[k, 1:])
    return {
        "rows_per_shard": rows_per_shard,
        "s_max": int(s_max),
        "wid": wid,
        "local": local,
        "weight": weight,
        "run_start": run_start,
        "run_end": run_end,
        "dst_ptr": dst_ptr,
    }


@dataclass
class ShardedWindowPlan:
    """Mesh-partitioned fused-pipeline layout: the ``tpu-windowed``
    kernel of ``converge_sharded``.

    Host construction slices the single-graph ``WindowPlan`` at
    BLOCK_ROWS-aligned vreg-row boundaries — the same cuts split the
    bucket-order segment table, because runs never span rows — then
    rebases each shard's run ends to shard-local slots, re-sorts each
    shard's runs by dst (per-shard ``run_end``/``dst_ptr``), and pads
    rows and runs to the mesh maximum.  Pad runs end at slot 0, and the
    per-shard ``dst_ptr`` never reaches them, so their garbage sums are
    read but never reduced into any destination.  The underlying ``plan`` is kept so
    the node's checkpoint store persists one format for both the
    single-device and sharded windowed backends.
    """

    mesh: Mesh
    n: int
    rows_per_shard: int  # BLOCK_ROWS-aligned vreg-rows per shard
    table_entries: int  # replicated score-table padding (WINDOW multiple)
    s_max: int  # padded per-shard run count
    interpret: bool  # Pallas interpret mode (CPU meshes)
    wid: jax.Array  # (n_shards*rows_per_shard,) int32, sharded
    local: jax.Array  # (n_shards*rows_per_shard*8, 128) int32, sharded
    weight: jax.Array  # (n_shards*rows_per_shard*8, 128) f32, sharded
    run_start: jax.Array  # (n_shards*rows_per_shard*8, 128) bool, sharded
    run_end: jax.Array  # (n_shards*s_max,) int32 shard-local, dst order, sharded
    dst_ptr: jax.Array  # (n_shards, n+1) int32, sharded on axis 0
    p: jax.Array  # (n,) f32, replicated
    dangling: jax.Array  # (n,) f32, replicated
    plan: WindowPlan  # the single-graph plan this partitions
    plan_outcome: str  # how the plan was resolved: reuse | delta | rebuild

    @classmethod
    def build(
        cls,
        graph: NormalizedGraph,
        mesh: Mesh,
        *,
        plan: WindowPlan | None = None,
        delta_rows: np.ndarray | None = None,
        interpret: bool | None = None,
    ) -> "ShardedWindowPlan":
        """Resolve the normalized graph's ``WindowPlan`` and partition it
        across the mesh.  A candidate ``plan`` (cached, checkpoint-
        restored, or resolved already by the sharded backend) goes
        through the single-device backend's decision
        (``ops.gather_window.resolve_plan``): reused while its layout
        version and fingerprint hold, delta-updated given a
        ``delta_rows`` churn hint, else rebuilt — the ``plan_outcome``
        field reports which path ran."""
        plan, outcome = resolve_plan(
            plan, graph.src, graph.dst, graph.weights_of,
            n=graph.n, fingerprint=graph.fingerprint, rows=delta_rows,
        )

        n_shards = mesh.shape[SHARD_AXIS]
        parts = _partition_plan_arrays(plan, n_shards)
        rows_per_shard, s_max = parts["rows_per_shard"], parts["s_max"]

        edge = NamedSharding(mesh, P(SHARD_AXIS))
        edge2d = NamedSharding(mesh, P(SHARD_AXIS, None))
        repl = NamedSharding(mesh, P())
        if interpret is None:
            interpret = pallas_interpret()
        return cls(
            mesh=mesh,
            n=plan.n,
            rows_per_shard=rows_per_shard,
            table_entries=plan.table_entries,
            s_max=s_max,
            interpret=bool(interpret),
            wid=jax.device_put(parts["wid"], edge),
            local=jax.device_put(parts["local"], edge2d),
            weight=jax.device_put(parts["weight"], edge2d),
            run_start=jax.device_put(parts["run_start"], edge2d),
            run_end=jax.device_put(parts["run_end"].reshape(-1), edge),
            dst_ptr=jax.device_put(parts["dst_ptr"], edge2d),
            p=jax.device_put(graph.p, repl),
            dangling=jax.device_put(graph.dangling, repl),
            plan=plan,
            plan_outcome=outcome,
        )

    def t0(self) -> jax.Array:
        """Fresh device copy of the pre-trust vector (the runner
        donates its seed; see ``ShardedTrustProblem.t0``)."""
        return jnp.copy(self.p)


def _get_windowed_runner(
    mesh: Mesh,
    n: int,
    rows_per_shard: int,
    table_entries: int,
    interpret: bool,
    table_chunk: int,
):
    key = (mesh, n, rows_per_shard, table_entries, interpret, table_chunk)
    if key in _RUN_CACHE:
        return _RUN_CACHE[key]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS),
            P(SHARD_AXIS, None),
            P(),
            P(),
            P(),
            P(),
        ),
        out_specs=P(),
        # pallas_call has no shard_map varying-axes rule; the step's
        # output replication is guaranteed by the trailing psum +
        # elementwise damping, so the static check is safely skipped.
        check_vma=False,
    )
    def step(
        wid, local, weight, run_start, run_end, dst_ptr, t, p, dangling, alpha,
    ):
        # The identical fused step as the single-device tpu-windowed
        # backend, over this shard's rows/runs; dst rows whose runs
        # live on several shards are completed by the psum below.
        partial_ct = windowed_ct(
            wid,
            local,
            weight,
            run_start,
            run_end,
            dst_ptr[0],
            t,
            n_rows=rows_per_shard,
            table_entries=table_entries,
            table_chunk=table_chunk,
            interpret=interpret,
        )
        ct = lax.psum(partial_ct, SHARD_AXIS)
        dangling_mass = jnp.sum(t * dangling)
        t_new = (1.0 - alpha) * (ct + dangling_mass * p) + alpha * p
        return t_new / jnp.sum(t_new)

    @partial(
        jax.jit,
        static_argnames=("max_iter", "tol", "record_residuals"),
        donate_argnames=("t0",),
    )
    def run(
        wid, local, weight, run_start, run_end, dst_ptr,
        t0, p, dangling, alpha, *, max_iter, tol, record_residuals=False,
    ):
        from ..ops.sparse import run_power_iteration

        return run_power_iteration(
            lambda t: step(
                wid, local, weight, run_start, run_end, dst_ptr, t, p, dangling, alpha,
            ),
            t0,
            tol=tol,
            max_iter=max_iter,
            record_residuals=record_residuals,
        )

    _RUN_CACHE[key] = run
    return run


def windowed_operands(problem) -> tuple:
    """The plan operands of a windowed problem, in the runner's order
    (its ``t0``, ``p``, ``dangling`` and ``alpha`` follow)."""
    return (
        problem.wid,
        problem.local,
        problem.weight,
        problem.run_start,
        problem.run_end,
        problem.dst_ptr,
    )


def windowed_runner(problem):
    """The compiled runner of a windowed problem (``ShardedWindowPlan``
    or ``parallel.pod.PodWindowPlan``), its static arguments taken from
    the problem and its plan in this one place."""
    return _get_windowed_runner(
        problem.mesh,
        problem.n,
        problem.rows_per_shard,
        problem.table_entries,
        problem.interpret,
        problem.plan.table_chunk,
    )


#: Kernels selectable under ``converge_sharded`` (ManagerConfig /
#: ProtocolConfig spell them ``tpu-sharded:<kernel>``): each value
#: builds the mesh-resident problem whose type the dispatch below
#: recognizes.
SHARDED_KERNELS: dict[str, type] = {
    "tpu-csr": ShardedTrustProblem,
    "tpu-windowed": ShardedWindowPlan,
}


def converge_sharded(
    problem: ShardedTrustProblem | ShardedWindowPlan,
    *,
    alpha: float = 0.1,
    tol: float = 1e-6,
    max_iter: int = 50,
    record_residuals: bool = False,
    t0: np.ndarray | None = None,
) -> tuple:
    """Damped power iteration to an L1 fixed point on the mesh, with
    the kernel selected by the problem type (``SHARDED_KERNELS``):
    ``ShardedTrustProblem`` runs the CSR/cumsum SpMV,
    ``ShardedWindowPlan`` the fused windowed pipeline.  ``t0`` warm
    starts the iteration (mesh-replicated like ``p``); None starts
    from the pre-trust vector — the cold path.

    Returns ``(t, iterations, final residual)`` — plus the device-side
    per-iteration residual history as a fourth element when
    ``record_residuals`` is set (the history rides the replicated
    while-loop carry *outside* shard_map, so the per-shard step and its
    single psum are untouched).  ``tol <= 0`` runs exactly ``max_iter``
    fixed steps (benchmark mode).

    ``alpha`` is staged explicitly with the mesh-replicated sharding:
    a bare ``jnp.float32`` scalar (numpy's scalar type) would pay an
    implicit host→device transfer every call, and a single-device
    array an implicit device→device re-replication — both rejected by
    the transfer guard the equivalence tests run under.
    """
    alpha_dev = jax.device_put(
        np.float32(alpha), NamedSharding(problem.mesh, P())
    )
    t0_dev = (
        problem.t0()
        if t0 is None
        else jax.device_put(
            np.asarray(t0, np.float32), NamedSharding(problem.mesh, P())
        )
    )
    # Dispatch on the CSR type and treat everything else as windowed-
    # shaped: the pod builder (``parallel.pod.PodWindowPlan``) carries
    # the same field layout as ShardedWindowPlan over a multi-process
    # mesh and rides the identical runner/cache.
    if not isinstance(problem, ShardedTrustProblem):
        run = windowed_runner(problem)
        out = run(
            *windowed_operands(problem),
            t0_dev,
            problem.p,
            problem.dangling,
            alpha_dev,
            max_iter=max_iter,
            tol=tol,
            record_residuals=record_residuals,
        )
    else:
        run = _get_runner(problem.mesh, problem.n)
        out = run(
            problem.src,
            problem.w,
            problem.row_ptr,
            t0_dev,
            problem.p,
            problem.dangling,
            alpha_dev,
            max_iter=max_iter,
            tol=tol,
            record_residuals=record_residuals,
        )
    t, it, resid = out[:3]
    if record_residuals:
        return t, int(it), float(resid), out[3]
    return t, int(it), float(resid)


# ---------------------------------------------------------------------------
# Pinned kernel invariants — checked per step by
# `python -m protocol_tpu.analysis` under the 8-device CPU mesh.
# ---------------------------------------------------------------------------

#: Per-shard CSR step under shard_map: the single-device CSR budget per
#: shard, plus EXACTLY ONE psum completing boundary destinations — and
#: that psum must sit under shard_map (outside, there is no mesh axis).
declare(
    KernelBudget(
        backend="tpu-sharded:tpu-csr",
        max_random_gathers=5,
        max_scatters=0,
        psum_count=1,
        gather_budgets=(GatherBudget(dim="edges", max_total=1, max_random=1),),
        donated_args=("t0",),
        notes="per-shard rowsum_sorted + one boundary-completing psum",
    )
)

#: Per-shard fused windowed step under shard_map: the single-device
#: windowed budget per shard (one random n_segments pass, Pallas kernel
#: present) plus the same single psum.
declare(
    KernelBudget(
        backend="tpu-sharded:tpu-windowed",
        max_random_gathers=5,
        max_scatters=0,
        psum_count=1,
        require_primitives=("pallas_call",),
        gather_budgets=(GatherBudget(dim="n_segments", max_total=1, max_random=1),),
        donated_args=("t0",),
        notes="sharded fused pipeline: per-shard windowed_ct + one psum",
    )
)


# ---------------------------------------------------------------------------
# Pinned communication budgets — checked against the
# COMPILED (SPMD-partitioned) module by graftlint pass 8 at two problem
# scales, and at runtime by the 2-process tools/comm_probe.py smoke.
# ---------------------------------------------------------------------------

#: CSR shards: exactly one f32[N] all-reduce per iteration (the
#: boundary-completing psum — destinations whose edge runs straddle a
#: shard cut ride the same reduce, so there is no separate boundary
#: collective).  Byte allowance 8·N = the 4·N wire volume with 2x
#: slack; NO term may scale with E — the whole point of the recipe is
#: that 50M edges cross zero wires.  t0's donation must survive into
#: the executable's input_output_alias table.
declare_comm(
    CommBudget(
        backend="tpu-sharded:tpu-csr",
        collectives=(CollectiveBudget(kind="all-reduce", max_count=1),),
        bytes_n=8.0,
        bytes_const=1024.0,
        max_host_round_trips=0,
        require_full_replica_group=True,
        donated_args=("t0",),
        notes="one boundary-completing f32[N] psum per step; comm is "
        "O(N), never O(E)",
    )
)

#: Windowed shards: identical wire shape — the per-shard fused pipeline
#: reduces its partial Cᵀt into the same single f32[N] all-reduce;
#: boundary segments are folded per shard before the reduce, so the
#: segment table contributes no collective bytes (bytes_segments stays
#: 0 as a declaration that boundary traffic rides the psum).
declare_comm(
    CommBudget(
        backend="tpu-sharded:tpu-windowed",
        collectives=(CollectiveBudget(kind="all-reduce", max_count=1),),
        bytes_n=8.0,
        bytes_segments=0.0,
        bytes_const=1024.0,
        max_host_round_trips=0,
        require_full_replica_group=True,
        donated_args=("t0",),
        notes="sharded fused pipeline: per-shard windowed_ct partials "
        "completed by one f32[N] psum; comm is O(N), never O(E)",
    )
)


# ---------------------------------------------------------------------------
# Pinned memory budgets — checked against the compiled
# module's buffer assignment by graftlint pass 12 at two problem
# scales (E x4 vs N x2), and at runtime by tools/mem_probe.py.  All
# numbers are PER DEVICE: the resident edge term is E/n_shards by
# construction, so a replicated edge operand busts the budget — the
# regression that turns into 2 GB/host at ROADMAP item 1's 500M-edge
# target.  The transient allowances were measured to track N across
# the 4x edge growth (the per-shard working set follows the replicated
# score vectors, never the edge slice), and the committed slack is
# below a 4 B/edge temporary at either scale (pinned by test).
# ---------------------------------------------------------------------------

declare_mem(
    MemBudget(
        backend="tpu-sharded:tpu-csr",
        resident_edge_bytes=8.0,  # per-shard src + w slice
        resident_n=16.0,  # replicated t0/p/dangling + clipped row_ptr
        resident_const=4096.0,
        transient_n=23.0,  # psum buffers + while carries: tracks N, not E
        transient_const=205504.0,  # runtime-fixed thunk arena, fitted
        donated_args=("t0",),
        notes="per-shard CSR slice resident; transient tracked N "
        "exactly across 4x edge growth (225→249 KB as N doubled)",
    )
)

declare_mem(
    MemBudget(
        backend="tpu-sharded:tpu-windowed",
        resident_rows=9220.0,  # per-shard local/weight/run_start/wid row tables
        resident_segments=4.0,  # per-shard run_end
        resident_n=16.0,  # replicated vectors + clipped dst_ptr
        resident_const=4096.0,
        transient_rows=52161.0,  # as tpu-windowed: interpret scratch, chunk ids, scan
        transient_n=36.0,
        transient_segments=5.0,
        transient_const=1122960.0,  # runtime-fixed thunk arena, fitted
        donated_args=("t0",),
        notes="per-shard plan slice resident; interpret scratch rides "
        "rows_per_shard, transient follows N across 4x edge growth",
    )
)
