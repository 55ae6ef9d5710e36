"""Pod scale-out: per-host partition plan build over a multi-process
``jax.distributed`` mesh.

``ShardedWindowPlan`` partitions ONE globally-built ``WindowPlan``
across one host's devices — at 500M edges that single host pays the
whole O(E) plan construction serially and holds the whole edge set in
host RAM.  The pod builder inverts the order of operations:

1. every process computes the identical peer→host partition
   (``parallel.partition.HostPartition`` — rendezvous hash, no
   coordination round) and keeps only the edges whose **source** peer
   it owns;
2. each host resolves a ``WindowPlan`` over its local edges only
   (``ops.gather_window.resolve_plan``, against its local fingerprint
   and its owned churn rows) — N hosts build N partial plans
   concurrently, so the pod's plan-build critical path is
   ``max_h(build(E_h))`` ≈ ``build(E)/N`` instead of ``build(E)``;
3. each host cuts its local plan across its local devices with the
   same BLOCK_ROWS-aligned row cut as the single-host path
   (``sharded._partition_plan_arrays``), padded to pod-wide maxima so
   every global shard has the same shape;
4. the per-host shards are assembled into global arrays with
   ``jax.make_array_from_process_local_data`` — no edge bytes ever
   cross a host boundary — and the pod runs the *identical*
   ``converge_sharded`` windowed runner: per-shard fused pipeline,
   one boundary-completing f32[N] psum per step, now spanning all
   ``n_hosts * local_devices`` shards.

Churn stays partition-local by construction: the protocol's churn unit
is a sender's out-row rewrite and a source peer's edges live on exactly
one host, so a host whose peers saw no churn revalidates its local
fingerprint and reuses its plan verbatim — steady-state churn never
forces a cross-host rebuild (``ops.gather_window.partition_delta``).

The ``dangling`` vector is the one globally-coupled input (a peer with
no out-edges anywhere): here every process derives it from its copy of
the full normalized graph (``trust.backend.normalize_graph``); a production pod exchanges per-host
out-degree bitmaps through the pod manifest (``node.pod``) — an O(N)
exchange, never O(E).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..device import pallas_interpret
from ..ops.gather_window import (
    WindowPlan,
    graph_fingerprint,
    partition_delta,
    resolve_plan,
)
from ..trust.graph import NormalizedGraph
from .mesh import SHARD_AXIS, default_mesh
from .partition import HostPartition
from .sharded import BLOCK_ROWS, ROW, _partition_plan_arrays


@dataclass(frozen=True)
class PodContext:
    """One process's view of the pod: its host id, the pod size, the
    global mesh, and the shared peer→host partition.  All processes
    construct identical contexts from their own ``jax.distributed``
    state — there is no leader election and no membership exchange."""

    host_id: int
    n_hosts: int
    mesh: Mesh
    partition: HostPartition

    @classmethod
    def current(cls, *, seed: int = 0) -> "PodContext":
        """The pod as the running jax runtime sees it: one host per
        process, the flat shard mesh over all global devices
        (``jax.devices()`` orders devices by process, so each host's
        local devices form a contiguous block of shards)."""
        return cls(
            host_id=jax.process_index(),
            n_hosts=jax.process_count(),
            mesh=default_mesh(),
            partition=HostPartition(jax.process_count(), seed=seed),
        )

    @property
    def local_shards(self) -> int:
        return self.mesh.shape[SHARD_AXIS] // self.n_hosts


def _pod_max(ctx: PodContext, values: np.ndarray) -> np.ndarray:
    """Elementwise max of an int64 vector across all pod hosts — the
    dimension-agreement exchange (every global shard must compile to
    one shape).  Single-host pods short-circuit; multi-host pods ride
    ``multihost_utils.process_allgather`` (gloo all-gather, host
    scale × 8 bytes on the wire)."""
    values = np.asarray(values, np.int64)
    if ctx.n_hosts == 1:
        return values
    from jax.experimental import multihost_utils

    gathered = np.asarray(multihost_utils.process_allgather(values))
    return gathered.max(axis=0)


@dataclass
class PodWindowPlan:
    """Pod-partitioned fused-pipeline layout.

    Field-compatible with ``ShardedWindowPlan`` (``converge_sharded``
    dispatches any non-CSR problem to ``windowed_runner``, which reads
    the same fields — identical code paths, multi-process mesh), plus
    the pod bookkeeping the dryrun and the node durability plane read:
    which host this is, the peer→host owner map, and how long the
    *local* plan build took (the pod's plan-build critical path is the
    max of these).
    """

    mesh: Mesh
    n: int
    rows_per_shard: int
    table_entries: int
    s_max: int
    interpret: bool
    wid: jax.Array
    local: jax.Array
    weight: jax.Array
    run_start: jax.Array
    run_end: jax.Array
    dst_ptr: jax.Array
    p: jax.Array
    dangling: jax.Array
    plan: WindowPlan  # this HOST's local-partition plan
    plan_outcome: str  # reuse | delta | rebuild
    host_id: int
    n_hosts: int
    owner: np.ndarray  # (n,) int32 peer→host owner map
    local_edges: int  # edges this host's partition holds
    build_seconds: float  # local plan construction wall-clock
    #: Pre-collective barrier probe: when this host entered
    #: the dimension-agreement allgather (caller's monotonic clock) and
    #: how long both agreement rounds blocked — the pod trace stitcher
    #: clock-aligns the arrival stamps into the barrier-arrival spread
    #: (eigentrust_pod_barrier_wait_seconds).  0.0 without a clock.
    barrier_enter_monotonic: float = 0.0
    barrier_wait_seconds: float = 0.0
    #: One monotonic↔wall clock-sync sample pair taken at build entry
    #: (both clocks read back-to-back) — one of the samples the
    #: stitcher's per-host offset estimation feeds on.  0.0 without
    #: injected clocks.
    sync_monotonic: float = 0.0
    sync_unix: float = 0.0

    @classmethod
    def build(
        cls,
        graph: NormalizedGraph,
        pod: PodContext,
        *,
        plan: WindowPlan | None = None,
        delta_rows: np.ndarray | None = None,
        interpret: bool | None = None,
        clock: Callable[[], float] | None = None,
        wall: Callable[[], float] | None = None,
    ) -> "PodWindowPlan":
        """Partition the normalized graph by source-peer owner, resolve
        this host's local plan (``resolve_plan``: reuse / delta /
        rebuild against the local fingerprint — churn owned by other
        hosts leaves it untouched), cut it across the local devices,
        and assemble the global sharded arrays.  ``plan`` is this host's cached *local* plan
        (checkpoint-shard restored); ``delta_rows`` is the global
        churn hint, clipped to owned rows here.  ``clock`` is the
        caller's monotonic clock for the ``build_seconds`` field and
        the barrier probe; ``wall`` is the caller's wall clock, read
        back-to-back with ``clock`` at entry for the pod stitcher's
        clock-sync sample — instrumentation wraps kernel trees from
        the outside (graftlint clock-in-kernel-tree doctrine), so
        without them the probe fields stay 0.0."""
        sync_monotonic = clock() if clock is not None else 0.0
        sync_unix = wall() if wall is not None else 0.0
        owner = pod.partition.assign_ids(graph.n)
        owned_rows, lsrc, ldst, lw = partition_delta(
            delta_rows, graph.src, graph.dst, graph.w, owner, pod.host_id
        )
        fp = graph_fingerprint(graph.n, lsrc, ldst, lw.__getitem__)
        t_build = clock() if clock is not None else 0.0
        plan, outcome = resolve_plan(
            plan, lsrc, ldst, lw.__getitem__, n=graph.n, fingerprint=fp, rows=owned_rows
        )
        build_seconds = 0.0
        if clock is not None and outcome != "reuse":
            build_seconds = clock() - t_build

        # Pod-wide dimension agreement: every global shard must carry
        # the same (rows_per_shard, s_max) so the compiled runner sees
        # one shape.  Two cheap rounds: row capacity first (the segment
        # cut depends on it), then per-shard run capacity.
        L = pod.local_shards
        min_rps = -(-plan.n_rows // (L * BLOCK_ROWS)) * BLOCK_ROWS
        # Barrier probe: the first allgather below is the pod's
        # pre-collective barrier — the first host to arrive blocks
        # until the last one does, so the clock-aligned enter stamps
        # across hosts ARE the arrival spread, and the elapsed time
        # over both agreement rounds is this host's wait.
        barrier_enter = clock() if clock is not None else 0.0
        rows_per_shard = int(_pod_max(pod, np.asarray([min_rps]))[0])
        live_end = plan.seg_end[: plan.n_segments]
        counts = np.bincount(
            (live_end // ROW) // rows_per_shard, minlength=L
        )
        min_smax = -(-max(int(counts.max()), 1) // 1024) * 1024
        s_max = int(_pod_max(pod, np.asarray([min_smax]))[0])
        barrier_wait = clock() - barrier_enter if clock is not None else 0.0

        parts = _partition_plan_arrays(
            plan, L, rows_per_shard=rows_per_shard, s_max=s_max
        )

        n_shards = pod.mesh.shape[SHARD_AXIS]
        edge = NamedSharding(pod.mesh, P(SHARD_AXIS))
        edge2d = NamedSharding(pod.mesh, P(SHARD_AXIS, None))
        repl = NamedSharding(pod.mesh, P())

        def shard1d(a: np.ndarray) -> jax.Array:
            return jax.make_array_from_process_local_data(
                edge, np.ascontiguousarray(a), (n_shards * (a.shape[0] // L),)
            )

        def shard2d(a: np.ndarray) -> jax.Array:
            return jax.make_array_from_process_local_data(
                edge2d,
                np.ascontiguousarray(a),
                (n_shards * (a.shape[0] // L), a.shape[1]),
            )

        if interpret is None:
            interpret = pallas_interpret()
        return cls(
            mesh=pod.mesh,
            n=plan.n,
            rows_per_shard=rows_per_shard,
            table_entries=plan.table_entries,
            s_max=s_max,
            interpret=bool(interpret),
            wid=shard1d(parts["wid"]),
            local=shard2d(parts["local"]),
            weight=shard2d(parts["weight"]),
            run_start=shard2d(parts["run_start"]),
            run_end=shard1d(parts["run_end"].reshape(-1)),
            dst_ptr=shard2d(parts["dst_ptr"]),
            p=jax.device_put(graph.p, repl),
            dangling=jax.device_put(graph.dangling, repl),
            plan=plan,
            plan_outcome=outcome,
            host_id=pod.host_id,
            n_hosts=pod.n_hosts,
            owner=owner,
            local_edges=int(lsrc.shape[0]),
            build_seconds=build_seconds,
            barrier_enter_monotonic=barrier_enter,
            barrier_wait_seconds=barrier_wait,
            sync_monotonic=sync_monotonic,
            sync_unix=sync_unix,
        )

    def t0(self) -> jax.Array:
        """Fresh device copy of the pre-trust vector (the runner
        donates its seed; same contract as ``ShardedWindowPlan``)."""
        return jnp.copy(self.p)


__all__ = ["PodContext", "PodWindowPlan"]
