"""Windowed Pallas gather and the fused fixed-slot pipeline built on it.

PERF.md §1 establishes that XLA's TPU gather runs at ~7 cycles/element
(386 ms per 50M-edge iteration, 86 % of the bench step) and that
Mosaic's general cross-vreg dynamic gather crashes the compiler.  What
*does* compile: dynamic sublane slicing of a VMEM ref, range-8 sublane
`take_along_axis`, range-128 lane `take_along_axis`, broadcasts, and
selects.  This module composes exactly those into a windowed gather and
— new in PERF.md §7 — a full power step that consumes it:

- Host side (`bucket_by_window`, one-time per graph): edges are
  grouped so every 1024-edge vreg-row shares one 1024-entry window of
  the table (`src // 1024`); rows are padded with window-local zeros
  and a weight mask.  The loop-free formulation (argsort + cumulative
  counts) buckets 50M edges in seconds, not the ~34 s of the original
  per-window Python loop (PERF.md §6).
- Kernel side (`gather_windowed`): the score table is read from HBM
  as (rows, 128) in blocks of at most ``TABLE_CHUNK`` entries (4 MB),
  one block per grid step, so its VMEM use does not grow with N; a
  table that fits one chunk is one block, resident for the whole
  call.  Every grid step's rows lie in one chunk (``bucket_by_window``
  aligns each chunk's rows to BLOCK_ROWS, ``apply_delta`` starts a
  window's overflow in a fresh block where the open one reads another
  chunk).  The rows a rebuild laid out ascend by chunk, so each chunk
  crosses into VMEM once for them; a delta's overflow blocks follow in
  the order windows outgrew their rows, and each change of chunk among
  them reads that chunk again.  Per vreg-row the
  kernel dynamic-slices the (8, 128) window and resolves the 1024
  local indices with an 8-way broadcast/lane-gather/select chain (~30
  vreg ops per 1024 edges).
- Bridge side (`power_step_windowed`): the kernel output is in
  *bucket order*, not the dst order the rowsum needs.  Bridging
  per-edge would itself be an O(E) random gather (the circularity that
  stalled PERF.md §1).  Instead `bucket_by_window` additionally sorts
  each window's edges by dst, so a (row, dst) run is consecutive slots,
  and the plan carries two bridge tables: ``run_start`` flags every
  run's first slot, and ``run_end`` holds each run's end slot in dst
  order.  The device sums each run in place with a segmented scan
  along the row (`bridge_partials`: log2(1024) shifted adds, no
  gather), and one gather at ``run_end`` lifts the run sums straight
  into dst order — the only data-randomly-addressed pass per
  iteration, of ``n_segments`` elements, with ``n_segments <=
  min(E, n_windows · N)``, which the hub-heavy bench graph compresses
  far below E (the plan records the measured ratio).  A run's sum is
  taken over its own slots, so no prefix is differenced and plain f32
  keeps it exact to the run's length.

Correctness is validated in interpret mode on CPU (tests); per-op
wall-clock on the real chip is in PERF.md §6 and the fused-pipeline
projection in §7.
"""

from __future__ import annotations

import contextlib
import hashlib
from dataclasses import dataclass
from functools import partial
from typing import Callable, ContextManager

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..analysis.budget import (
    CommBudget,
    GatherBudget,
    KernelBudget,
    MemBudget,
    declare,
    declare_comm,
    declare_mem,
)
from .sparse import rowsum_sorted, run_power_iteration

try:
    # The C two-pass kernel underneath scipy's COO→CSR conversion; the
    # coo_matrix wrapper around it re-validates indices with two extra
    # O(E) passes (~0.5 s at 50M edges on the bench host).
    from scipy.sparse import _sparsetools as _scipy_sparsetools
except ImportError:  # pragma: no cover - bench/prod images carry scipy
    _scipy_sparsetools = None

#: Window width in table entries: one (8, 128) VMEM tile.
WINDOW = 1024
#: Edges per vreg-row (must equal WINDOW for the two-step resolve).
ROW = 1024
#: Vreg-rows per grid step.
BLOCK_ROWS = 64
#: Score-table entries per VMEM block (4 MB of f32, 1024 windows): a
#: larger table is streamed in chunks of this many entries.
TABLE_CHUNK = 1 << 20


#: log2(WINDOW): window ids and window-local indices are shifts/masks.
_WIN_BITS = 10

#: A plan sub-phase hook: ``phase(name, **counts)`` returns a context
#: manager around one host sub-phase of plan construction; ``counts``
#: are sizes of the plan's work, for the caller's record.  Kernel trees
#: hold no clock (graftlint ``clock-in-kernel-tree``), so the caller
#: (``trust/backend.py``) injects the timing one.
PlanPhase = Callable[..., ContextManager]


def _no_phase(name: str, **counts: int) -> ContextManager:
    """The default ``PlanPhase``: records nothing."""
    return contextlib.nullcontext()


#: A plan-attempt hook: ``attempt(reason, make)`` returns ``make(phase)``
#: (a plan, or None where a delta does not apply) for one attempt at a
#: plan: ``delta``, or a rebuild for its reason (``cold``,
#: ``stale-layout``, ``fingerprint-miss``).  ``trust/backend.py`` wraps
#: each attempt in its ``plan`` span.
PlanAttempt = Callable[[str, Callable[[PlanPhase], object]], object]


def _no_attempt(reason: str, make: Callable[[PlanPhase], object]) -> object:
    return make(_no_phase)


#: The row-normalized edge weights, read by selection: ``weights(sel)``
#: returns float32 ``w[sel]`` for a boolean mask, an index array or a
#: slice, ``slice(None)`` being the whole array.  A caller that divides
#: on demand (``trust.graph.NormalizedGraph.weights_of``) lets a plan
#: delta and the fingerprint divide only the edges they read; an array
#: ``w`` is passed as ``w.__getitem__``.
EdgeWeights = Callable[[object], np.ndarray]


def _counting_sort(
    key: np.ndarray, n_keys: int, payload: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Stable counting sort by a small-domain non-negative integer key:
    returns ``(order, counts, sorted_payload)`` where ``order`` is the
    ``argsort(key, kind="stable")`` permutation and ``counts`` the
    per-key histogram.

    numpy's stable argsort costs ~8-10 s at 50M elements on the bench
    host — most of the old 34 s bucketing loop's replacement budget.
    scipy's COO→CSR conversion is the same counting sort as a two-pass
    C loop, O(E + n_keys): rows are the keys, columns the positions, so
    the CSR column indices come out key-grouped in stable position
    order, and the CSR data array carries ``payload`` through the sort
    without a separate O(E) random gather.  Falls back to numpy where
    scipy is missing.
    """
    e = key.shape[0]
    coo_tocsr = getattr(_scipy_sparsetools, "coo_tocsr", None)
    if coo_tocsr is None or e >= 2**31 or n_keys >= 2**31:  # pragma: no cover
        order = np.argsort(key, kind="stable")
        counts = np.bincount(key, minlength=n_keys)
        return order, counts, None if payload is None else payload[order]
    data = (
        np.ascontiguousarray(payload)
        if payload is not None
        else np.empty(e, np.int8)
    )
    key = np.ascontiguousarray(key, dtype=np.int32)
    indptr = np.empty(n_keys + 1, np.int32)
    order = np.empty(e, np.int32)
    sorted_data = np.empty(e, data.dtype)
    coo_tocsr(
        n_keys, e, e, key, np.arange(e, dtype=np.int32), data,
        indptr, order, sorted_data,
    )
    return order, np.diff(indptr), sorted_data if payload is not None else None


def _pack_lanes(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Pack two equal-length 4-byte arrays into one int64 array
    (bit-preserving), so one counting-sort pass carries both payloads
    at once instead of paying two O(E) permutations."""
    lanes = np.empty((a.shape[0], 2), np.int32)
    lanes[:, 0] = a if a.dtype == np.int32 else a.view(np.int32)
    lanes[:, 1] = b if b.dtype == np.int32 else b.view(np.int32)
    return lanes.view(np.int64)[:, 0]


def _unpack_lanes(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Bit-exact int32 lane views of a ``_pack_lanes`` array."""
    v = packed.view(np.int32).reshape(-1, 2)
    return v[:, 0], v[:, 1]


def table_blocks(table_entries: int, table_chunk: int) -> tuple[int, int]:
    """``(entries per block, blocks)`` of the score table as the kernel
    reads it: the whole table as one block where it fits one chunk,
    else chunks of ``table_chunk`` entries."""
    if table_entries <= table_chunk:
        return table_entries, 1
    return table_chunk, -(-table_entries // table_chunk)


def _align_chunks(rows_per: np.ndarray, table_chunk: int) -> int:
    """Pad, in place, each chunk's rows but the last to a multiple of
    BLOCK_ROWS by growing the chunk's last window; returns the rows
    added.  ``rows_per`` is the vreg-row count of every window."""
    chunk_windows = table_chunk // WINDOW
    n_windows = rows_per.shape[0]
    if n_windows <= chunk_windows:
        return 0
    starts = np.arange(0, n_windows, chunk_windows)
    pad = -np.add.reduceat(rows_per, starts) % BLOCK_ROWS
    pad[-1] = 0
    rows_per[starts[1:] - 1] += pad[:-1]
    return int(pad.sum())


def _skipped_rows(
    wid: np.ndarray, first: int, stop: int, chunk_windows: int
) -> dict[int, list[int]]:
    """Rows in ``[first, stop)`` a delta skipped (wid -1), by the chunk
    of their grid block: the chunk of the block's other rows."""
    rows = first + np.flatnonzero(wid[first:stop] < 0)
    top = wid.reshape(-1, BLOCK_ROWS).max(axis=1)
    chunk = top[rows // BLOCK_ROWS] // chunk_windows
    return {int(c): rows[chunk == c].tolist() for c in np.unique(chunk)}


def bucket_by_window(
    src: np.ndarray,
    w: np.ndarray,
    table_size: int | None = None,
    *,
    dst: np.ndarray | None = None,
    n_dst: int | None = None,
    spare_rows: int | None = 0,
) -> dict:
    """Group edges so each 1024-edge vreg-row shares one src window.

    Returns arrays shaped for ``gather_windowed`` plus the mapping back
    to input edges: for the k-th edge of the window-sorted order,
    ``contrib_input[order[k]] = contrib_bucketed[out_pos[k]]`` —
    ``out_pos`` accounts for the per-window padding, which carries
    weight 0.

    With ``dst`` (and ``n_dst``) given, edges are additionally sorted by
    destination *within* each window and the dict gains the run
    tables: ``seg_end`` flat end slots of every per-(vreg-row, dst)
    run in *bucket order* (strictly increasing), ``seg_first`` flagging
    row-leading runs, ``run_start`` flagging every row's first slot and
    every run's first slot (shaped like ``weight``), ``run_end`` the end
    slots in dst order, and ``dst_ptr`` delimiting each destination's
    runs in that order — everything ``power_step_windowed`` needs to
    reduce bucket-order contributions to a dense Cᵀt with one
    n_segments-sized random pass.

    Fully vectorized: stable counting sorts (scipy COO→CSR, O(E)) plus
    cumulative-count placement — the previous per-window Python loop
    was ~34 s at 50M edges; this formulation is bounded by the sort's
    payload movement (<5 s measured, PERF.md §7).

    ``spare_rows`` reserves that many zero-weight vreg-rows past the
    packed data (on top of the BLOCK_ROWS grid rounding) — headroom
    ``WindowPlan.apply_delta`` allocates overflow rows from (and where
    the inert segment-table pads end), so a window outgrowing its
    original padding doesn't force a full rebuild (PERF.md §11).
    None sizes it adaptively: one grid block, ~6% of the data rows, or
    (with ``dst``) the slots for 3/16 of the runs' count in pad runs,
    whichever is largest, plus a block per chunk past the first.

    Where the table spans several chunks of ``TABLE_CHUNK`` entries (as
    it stands at the call), each chunk's rows but the last are padded to
    a multiple of BLOCK_ROWS — the pad rows (``align_rows``) join the
    chunk's last window — so that no grid step reads two chunks.  A
    table of one chunk gets no pad rows.
    """
    e = src.shape[0]
    if e == 0:
        raise ValueError("no edges to bucket")
    src = np.asarray(src, dtype=np.int32)
    w = np.asarray(w, dtype=np.float32)
    smin, smax = int(src.min()), int(src.max())
    if smin < 0 or (table_size is not None and smax >= table_size):
        # Out-of-range (or negative) indices would be silently clamped
        # by the kernel's dynamic slice into a wrong but in-bounds
        # window; must survive python -O, so no assert.
        raise ValueError("src index outside [0, table_size)")
    n_src = table_size if table_size is not None else smax + 1
    n_windows = -(-n_src // WINDOW)

    if dst is None:
        o1, s1, w1, d1 = None, src, w, None
    else:
        if n_dst is None:
            raise ValueError("n_dst is required when dst is given")
        if int(dst.min()) < 0 or int(dst.max()) >= n_dst:
            raise ValueError("dst index outside [0, n_dst)")
        dst = np.asarray(dst, dtype=np.int32)
        # Within-window dst order = one stable counting sort by window
        # over a dst-sorted edge sequence.  The node/bench graphs arrive
        # dst-sorted (``TrustGraph.sorted_by_dst``), so the usual cost
        # is a single O(E) pass; unsorted input pays one extra
        # dst-keyed pass (LSD radix), with (src, w) riding the payload
        # lanes so no separate O(E) random gathers are needed.
        if np.any(dst[1:] < dst[:-1]):
            o1, dst_counts, packed = _counting_sort(
                dst, n_dst, payload=_pack_lanes(src, w)
            )
            if packed is None:  # pragma: no cover - numpy fallback
                s1, w1 = src[o1], w[o1]
            else:
                s1, w1raw = _unpack_lanes(packed)
                w1 = w1raw.view(np.float32)
            d1 = np.repeat(np.arange(n_dst, dtype=np.int32), dst_counts)
        else:
            o1, s1, w1, d1 = None, src, w, dst
    # The one window-keyed counting sort.  The small key domain
    # (E/1024 windows) matters: the placement pass advances one write
    # pointer per key, so with ~1000 keys the writes stream (measured
    # ~6× faster than a src-keyed pass whose 1M pointers scatter every
    # write to a cold cache line).  (local, w) ride the payload lanes;
    # ``order`` is the CSR column indices, for free.
    window = s1 >> _WIN_BITS
    order, counts, data = _counting_sort(
        window, n_windows, payload=_pack_lanes(s1 & (WINDOW - 1), w1)
    )
    if data is None:  # pragma: no cover - numpy fallback
        local_sorted = (s1 & (WINDOW - 1))[order]
        w_sorted = w1[order]
    else:
        local_sorted, wraw = _unpack_lanes(data)
        w_sorted = wraw.view(np.float32)
    ds = d1[order] if d1 is not None else None
    if o1 is not None:
        order = o1[order]

    # Rows per window, each padded to a full vreg-row; grid padded to
    # block granularity.  Windows with no edges contribute zero rows.
    rows_per = -(-counts // ROW)
    align_rows = _align_chunks(rows_per, TABLE_CHUNK)
    row_offset = np.concatenate([[0], np.cumsum(rows_per)]).astype(np.int64)
    n_data_rows = int(row_offset[-1])
    # Flat slot of each window-sorted edge: consecutive within its
    # window, starting at the window's first (fresh) vreg-row.  One
    # repeat over the per-window pad shift; the scatter below is
    # monotonic (sorted destinations), so it streams.  int32 throughout:
    # slot count < 2³¹ is already implied by the int32 edge arrays, and
    # the narrower lanes halve this pass's memory traffic (measured 6×
    # on the bench host).
    win_off = np.concatenate([[0], np.cumsum(counts)[:-1]])
    out_pos = np.repeat(
        (row_offset[:-1] * ROW - win_off).astype(np.int32), counts
    ) + np.arange(e, dtype=np.int32)
    if ds is not None:
        # Segments are maximal same-dst slot runs within one vreg-row:
        # edges are dst-sorted inside each window and packed into
        # consecutive slots, so a run breaks only at a dst change or a
        # row boundary (a window change always starts a fresh row, so it
        # needs no term).
        brk = np.empty(e, bool)
        brk[0] = True
        brk[1:] = (ds[1:] != ds[:-1]) | (out_pos[1:] & (ROW - 1) == 0)
    if spare_rows is None:
        # One grid block, ~6% of the data rows, or a spare slot for each
        # pad run of 3/16 of the runs (the 1/8 ``_segment_capacity``
        # pads, and half as much again for deltas to grow into), which
        # matters where runs are many per row; plus a block per further
        # chunk, as a delta that grows windows of several chunks may
        # skip to a fresh block at each.
        pad_slots = 0 if ds is None else 3 * int(np.count_nonzero(brk)) // 16
        spare_rows = max(BLOCK_ROWS, n_data_rows // 16, -(-pad_slots // ROW)) + (
            BLOCK_ROWS * (table_blocks(n_windows * WINDOW, TABLE_CHUNK)[1] - 1)
        )
    total_rows = -(-(n_data_rows + spare_rows) // BLOCK_ROWS) * BLOCK_ROWS
    local = np.zeros(total_rows * ROW, np.int32)
    weight = np.zeros(total_rows * ROW, np.float32)
    local[out_pos] = local_sorted
    weight[out_pos] = w_sorted
    wid = np.zeros(total_rows, np.int32)
    wid[:n_data_rows] = np.repeat(np.arange(n_windows, dtype=np.int32), rows_per)
    result = {
        "local": local.reshape(total_rows * 8, 128),
        "weight": weight.reshape(total_rows * 8, 128),
        "wid": wid,
        "order": order,
        "out_pos": out_pos,
        "n_rows": total_rows,
        "n_data_rows": n_data_rows,
        "row_offset": row_offset,
        "align_rows": align_rows,
        "table_chunk": TABLE_CHUNK,
    }
    if ds is None:
        return result

    # -- static single-pass reduction plan (PERF.md §7-8) ---------------
    end_mask = np.empty(e, bool)
    end_mask[-1] = True
    end_mask[:-1] = brk[1:]
    seg_dst = ds[brk]
    # The host keeps the run table in BUCKET order (the delta update
    # addresses runs by slot); the device reads run ends in dst order,
    # which the counting sort carries out as its payload.
    seg_end = np.ascontiguousarray(out_pos[end_mask])
    seg_first = np.ascontiguousarray(out_pos[brk] & (ROW - 1) == 0)
    run_start = np.zeros(total_rows * ROW, bool)
    run_start[::ROW] = True
    run_start[out_pos[brk]] = True
    _, seg_counts, run_end = _counting_sort(
        seg_dst, n_dst, payload=seg_end.astype(np.int32)
    )
    dst_ptr = np.zeros(n_dst + 1, np.int64)
    np.cumsum(seg_counts, out=dst_ptr[1:])
    result.update(
        seg_end=seg_end,
        seg_first=seg_first,
        run_start=run_start.reshape(total_rows * 8, 128),
        run_end=run_end,
        dst_ptr=dst_ptr.astype(np.int32),
        seg_dst=np.ascontiguousarray(seg_dst, dtype=np.int32),
        n_segments=int(seg_dst.shape[0]),
    )
    return result


def _kernel(wid_ref, chunk_ref, t_ref, local_ref, w_ref, out_ref, *, block_windows):
    """One grid step: BLOCK_ROWS vreg-rows of 1024 edges each, against
    the table block of the step's chunk.

    ``wid_ref`` and ``chunk_ref`` are the scalar-prefetch refs (SMEM) of
    the FULL wid and per-step chunk arrays — dynamic-slice starts must
    come from scalar memory, not a VMEM vector load, to lower on Mosaic.
    A row that holds no edge may name a window outside the step's chunk
    (or -1); the clamp keeps its read inside the block, and its weight
    is 0."""
    blk = pl.program_id(0)
    base = chunk_ref[blk] * block_windows
    for v in range(BLOCK_ROWS):
        off = jnp.minimum(
            jnp.maximum(wid_ref[blk * BLOCK_ROWS + v] - base, 0), block_windows - 1
        )
        win = t_ref[pl.ds(off * 8, 8), :]  # (8,128) window slice
        lidx = local_ref[pl.ds(v * 8, 8), :]
        sub = lidx // 128
        lane = lidx % 128
        acc = jnp.zeros((8, 128), jnp.float32)
        for k in range(8):
            rowk = jnp.broadcast_to(win[k : k + 1, :], (8, 128))
            g = jnp.take_along_axis(rowk, lane, axis=1)
            acc = jnp.where(sub == k, g, acc)
        out_ref[pl.ds(v * 8, 8), :] = acc * w_ref[pl.ds(v * 8, 8), :]


@partial(jax.jit, static_argnames=("n_rows", "table_chunk", "interpret"))
def gather_windowed(
    wid: jax.Array,
    table: jax.Array,
    local: jax.Array,
    weight: jax.Array,
    *,
    n_rows: int,
    table_chunk: int,
    interpret: bool = False,
) -> jax.Array:
    """``out[r, j] = weight[r, j] * table[wid[r//8]*1024 + local[r, j]]``
    with the table read into VMEM in blocks of ``table_blocks``: one
    resident block where it fits ``table_chunk`` entries, else one
    chunk per grid step.  ``table_chunk`` must be the one the rows were
    laid out for (``WindowPlan.table_chunk``).  A step's chunk is that
    of the largest window among its rows: a row that holds no edge names
    a window of the step's chunk, window 0 (the spare tail) or -1 (a row
    a delta skipped), so it never raises it."""
    assert table.size % WINDOW == 0 and table_chunk % WINDOW == 0
    assert n_rows % BLOCK_ROWS == 0, (
        f"n_rows must be a multiple of {BLOCK_ROWS} (bucket_by_window pads "
        "to this); a partial trailing block would be silently unwritten"
    )
    block, n_chunks = table_blocks(table.size, table_chunk)
    t2d = jnp.pad(table, (0, block * n_chunks - table.size)).reshape(-1, 128)
    n_blocks = n_rows // BLOCK_ROWS
    block_windows = block // WINDOW
    chunk = jnp.clip(
        jnp.max(wid.reshape(n_blocks, BLOCK_ROWS), axis=1) // block_windows,
        0,
        n_chunks - 1,
    )
    from jax.experimental.pallas import tpu as pltpu

    rows = lambda i, wid_ref, chunk_ref: (i, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block // 128, 128), lambda i, wid_ref, chunk_ref: (chunk_ref[i], 0)),
            pl.BlockSpec((BLOCK_ROWS * 8, 128), rows),
            pl.BlockSpec((BLOCK_ROWS * 8, 128), rows),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS * 8, 128), rows),
    )
    return pl.pallas_call(
        partial(_kernel, block_windows=block_windows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows * 8, 128), jnp.float32),
        interpret=interpret,
        name="gather_windowed",
    )(wid, chunk, t2d, local, weight)


# ---------------------------------------------------------------------------
# The fused fixed-slot pipeline (PERF.md §7)
# ---------------------------------------------------------------------------


#: WindowPlan on-disk/in-memory layout version.  v1 stored dst-sorted
#: ``seg_start``/``seg_end`` boundary pairs (4 random gathers per
#: iteration); v2 is the interleaved single-pass layout (bucket-order
#: ``seg_end`` + row-leading mask + folded dst permutation, PERF.md §8);
#: v3 adds the host-side delta-update bookkeeping (bucket-order
#: ``seg_dst``, per-window ``row_offset``, the live-row watermark, and
#: the fingerprint lineage chain, PERF.md §11); v4 aligns each table
#: chunk's rows to grid blocks and records ``table_chunk``, so a table
#: larger than one VMEM block is streamed, and stores the run ends in
#: dst order (``run_end``) in place of the bucket→dst permutation.  Checkpoint-restored plans
#: of any other version are discarded and rebuilt — the same path a
#: fingerprint mismatch takes.
PLAN_VERSION = 4

#: Ancestor fingerprints a delta-updated plan remembers (checkpoint
#: forensics: how many epochs of churn separate this layout from its
#: last from-scratch build).
LINEAGE_DEPTH = 16

#: Device segment tables are padded to a multiple of this, with at
#: least SEG_HEADROOM free entries, so per-epoch deltas that grow the
#: run count slightly keep every device array shape — and therefore
#: the compiled convergence kernel — stable.  Pad runs are inert: they
#: end in the zero-weight spare tail (partial ≡ 0) and the dst
#: permutation parks them beyond ``dst_ptr[n]``, so ``rowsum_sorted``
#: never differences them into any destination (the same trick the
#: sharded partition uses for its per-shard padding).
SEG_QUANTUM = 1024
SEG_HEADROOM = 256


class PlanDeltaError(ValueError):
    """The requested delta cannot be applied to this plan (peer set
    shrank, a deleted edge is absent, or the overflow headroom is
    exhausted) — callers fall back to a full ``build_window_plan``."""


def _pad_segment_tables(
    seg_end: np.ndarray,
    seg_first: np.ndarray,
    seg_dst: np.ndarray,
    *,
    capacity: int,
    n: int,
    n_rows: int,
    n_data_rows: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad the live bucket-order run tables to ``capacity`` device
    entries and fold the dst sort: pad runs end at the topmost
    zero-weight spare slots (strictly above every live run, so their
    sums are exact zeros) and carry sentinel dst ``n``, which the
    counting sort parks beyond ``dst_ptr[n]`` — never reduced into any
    destination.  Returns ``(seg_end, seg_first, run_end, dst_ptr)`` at
    device capacity, ``run_end`` being ``seg_end`` in dst order."""
    s = int(seg_end.shape[0])
    pad = capacity - s
    if pad < 0 or pad > (n_rows - n_data_rows) * ROW:
        raise PlanDeltaError(
            f"segment capacity {capacity} does not fit the spare-slot headroom"
        )
    total_slots = n_rows * ROW
    end = np.concatenate(
        [
            seg_end.astype(np.int32),
            np.arange(total_slots - pad, total_slots, dtype=np.int32),
        ]
    )
    first = np.concatenate([seg_first.astype(bool), np.ones(pad, bool)])
    key = np.concatenate([seg_dst.astype(np.int64), np.full(pad, n, np.int64)])
    _, counts, run_end = _counting_sort(
        np.ascontiguousarray(key, np.int32), n + 1, payload=end
    )
    dst_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts[:n], out=dst_ptr[1:])
    return end, first, run_end, dst_ptr.astype(np.int32)


def _segment_capacity(s: int, max_pad_slots: int) -> int:
    """Quantized device capacity for ``s`` live runs: proportional
    growth headroom (churn fragments hub runs into singletons, so the
    live count drifts up by roughly the per-epoch rewire count —
    ~12.5% absorbs several epochs between regrowths), rounded to
    SEG_QUANTUM, clamped to the spare-tail slots actually available
    for pad runs."""
    slack = max(SEG_HEADROOM, s // 8)
    return min(-(-(s + slack) // SEG_QUANTUM) * SEG_QUANTUM, s + max_pad_slots)


@dataclass
class WindowPlan:
    """Static per-graph layout for the fused windowed power step.

    Built once on the host (``build_window_plan``), reused every
    iteration and across epochs while the graph fingerprint matches;
    persisted by ``node/checkpoint.py`` so a node reboot doesn't re-pay
    construction.  Small per-epoch edge churn is folded in by
    ``apply_delta`` (touched windows repacked in place, everything else
    shared) instead of a full rebuild — the ``lineage`` chain records
    the ancestor fingerprints of such delta-updated plans.
    ``order``/``out_pos`` map bucket slots back to input edges — needed
    only by tests and diagnostics, so checkpoints omit them
    (``to_arrays(core_only=True)``); delta-updated plans drop them.
    """

    n: int  # peers (dense output length)
    n_rows: int  # padded vreg-rows
    table_entries: int  # score table padded to a WINDOW multiple
    n_segments: int  # per-(row, dst) runs crossing the bridge
    n_data_rows: int  # live vreg-rows (original packing + delta overflow)
    n_edges: int  # live edges encoded (delta-integrity tripwire)
    wid: np.ndarray  # (n_rows,) int32 window id per vreg-row
    local: np.ndarray  # (n_rows*8, 128) int32 window-local indices
    weight: np.ndarray  # (n_rows*8, 128) f32 slot weights (0 = padding)
    run_start: np.ndarray  # (n_rows*8, 128) bool slot starts a row or a run
    seg_end: np.ndarray  # (S,) int32 last slot of each run, bucket order
    seg_first: np.ndarray  # (S,) bool run is row-leading
    run_end: np.ndarray  # (S,) int32 last slot of each run, dst order
    dst_ptr: np.ndarray  # (n+1,) int32 run range per destination
    seg_dst: np.ndarray  # (S,) int32 run destination, bucket order (host-side)
    row_offset: np.ndarray  # (n_windows+1,) int64 original rows per window
    fingerprint: str  # graph identity for safe reuse
    table_chunk: int  # table entries per chunk the rows are aligned to
    version: int = PLAN_VERSION  # layout version (see PLAN_VERSION)
    #: Fingerprints of the plans this one was delta-derived from,
    #: oldest first, capped at LINEAGE_DEPTH; empty for a from-scratch
    #: build.  Persisted with checkpoints (delta provenance).
    lineage: tuple[str, ...] = ()
    order: np.ndarray | None = None  # (E,) bucket position k ← edge order[k]
    out_pos: np.ndarray | None = None  # (E,) slot of edge order[k]
    #: Rows the step that made this plan padded for chunk alignment:
    #: the rebuild's per-chunk pads, or the rows a delta skipped to
    #: start a window's overflow in a block of its own chunk.  Not
    #: persisted (a restored plan padded nothing).
    align_rows: int = 0

    #: Persisted layout arrays.
    _CORE = (
        "wid", "local", "weight", "run_start", "seg_end", "seg_first", "run_end",
        "dst_ptr",
    )
    #: Device operands, in ``converge_windowed`` order — exactly what
    #: crosses the host→HBM boundary.
    _DEVICE = ("wid", "local", "weight", "run_start", "run_end", "dst_ptr")
    #: Host-only bookkeeping for ``apply_delta`` (persisted, never
    #: shipped to the device).
    _HOST = ("seg_dst", "row_offset")
    _META = (
        "n", "n_rows", "table_entries", "n_segments", "n_data_rows", "n_edges",
        "table_chunk",
    )

    @property
    def compression(self) -> float:
        """Edge contributions per bridge partial (E / n_segments) —
        how much the run-level reduction shrinks the random-access
        volume vs a per-edge bucket→dst permutation."""
        return self.n_edges / max(self.n_segments, 1)

    def static_kwargs(self) -> dict:
        """The layout's static arguments of ``converge_windowed``,
        ``power_step_windowed`` and ``windowed_ct``: the one place a
        caller takes them from, so the kernel reads the rows with the
        chunk they were laid out for."""
        return {
            "n_rows": self.n_rows,
            "table_entries": self.table_entries,
            "table_chunk": self.table_chunk,
        }

    @property
    def table_chunks(self) -> int:
        """Blocks the kernel streams the score table in (1: resident)."""
        return table_blocks(self.table_entries, self.table_chunk)[1]

    @property
    def seg_capacity(self) -> int:
        """Device length of the segment tables: ``n_segments`` live
        runs plus inert pad runs (shape-stability headroom for
        ``apply_delta`` — see SEG_QUANTUM)."""
        return int(self.seg_end.shape[0])

    def device_args(self) -> tuple:
        """Device operands, in ``converge_windowed`` order."""
        return tuple(jnp.asarray(getattr(self, k)) for k in self._DEVICE)

    def to_arrays(self, *, core_only: bool = True) -> dict:
        """npz-ready mapping (checkpoint format)."""
        out = {k: np.int64(getattr(self, k)) for k in self._META}
        out["version"] = np.int64(self.version)
        out["fingerprint"] = np.bytes_(self.fingerprint.encode())
        out["lineage"] = np.array(list(self.lineage), dtype="S64")
        for k in self._CORE + self._HOST:
            out[k] = getattr(self, k)
        if not core_only and self.order is not None:
            out["order"] = self.order
            out["out_pos"] = self.out_pos
        return out

    @classmethod
    def from_arrays(cls, z) -> "WindowPlan":
        """Rehydrate a persisted plan; raises ``ValueError`` on a stale
        layout version (pre-v2 plans lack ``version`` entirely) so
        callers fall back to a rebuild instead of feeding the device
        mis-shaped boundary arrays."""
        version = int(z["version"]) if "version" in z else 1
        if version != PLAN_VERSION:
            raise ValueError(
                f"window plan layout v{version} is stale (current v{PLAN_VERSION}); rebuild"
            )
        return cls(
            **{k: int(z[k]) for k in cls._META},
            **{k: np.asarray(z[k]) for k in cls._CORE + cls._HOST},
            fingerprint=bytes(z["fingerprint"]).decode(),
            version=version,
            lineage=tuple(bytes(x).decode() for x in z["lineage"])
            if "lineage" in z
            else (),
            order=np.asarray(z["order"]) if "order" in z else None,
            out_pos=np.asarray(z["out_pos"]) if "out_pos" in z else None,
        )

    # -- delta updates (PERF.md §11) ------------------------------------

    def _window_vreg_rows(self, window: int) -> np.ndarray:
        """Live vreg-rows carrying ``window``'s slots, ascending: the
        original contiguous block plus any delta-appended overflow rows
        (overflow lives past ``row_offset[-1]``, identified by wid)."""
        n_orig = int(self.row_offset[-1])
        if window + 1 < len(self.row_offset):
            rows = np.arange(
                self.row_offset[window], self.row_offset[window + 1], dtype=np.int64
            )
        else:
            rows = np.empty(0, np.int64)
        if self.n_data_rows > n_orig:
            tail = np.arange(n_orig, self.n_data_rows, dtype=np.int64)
            rows = np.concatenate([rows, tail[self.wid[tail] == window]])
        return rows

    def _segments_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Indices into the bucket-order segment table of every run
        living in ``rows`` — seg_end is strictly increasing, so each
        row's runs are one searchsorted slice.  The queries match the
        stored int32 table: a mixed-dtype search would cast the whole
        table per call."""
        rows = np.asarray(rows, np.int64)
        lo = np.searchsorted(self.seg_end, (rows * ROW).astype(np.int32), side="left")
        hi = np.searchsorted(
            self.seg_end, ((rows + 1) * ROW - 1).astype(np.int32), side="right"
        )
        parts = [np.arange(a, b, dtype=np.int64) for a, b in zip(lo, hi) if b > a]
        if not parts:
            return np.empty(0, np.int64)
        return np.concatenate(parts)

    def _edges_of_segments(
        self, idx: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Recover ``(src, dst, w)`` of the edges inside the given runs
        by expanding each run's slot range — the inverse of the packing
        ``bucket_by_window`` performed."""
        if idx.size == 0:
            z = np.empty(0, np.int32)
            return z, z, np.empty(0, np.float32)
        # Widen only the gathered ends, never the table.
        end = self.seg_end[idx].astype(np.int64)
        prev = self.seg_end[np.maximum(idx, 1) - 1].astype(np.int64)
        start = np.where(self.seg_first[idx], (end // ROW) * ROW, prev + 1)
        lens = end - start + 1
        total = int(lens.sum())
        run_of = np.repeat(np.cumsum(lens) - lens, lens)
        slots = np.repeat(start, lens) + (np.arange(total, dtype=np.int64) - run_of)
        dst = np.repeat(self.seg_dst[idx], lens)
        rows = slots // ROW
        src = (
            self.wid[rows].astype(np.int64) * WINDOW
            + self.local.reshape(-1)[slots]
        ).astype(np.int32)
        return src, dst, self.weight.reshape(-1)[slots]

    def recovered_edges(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The full ``(src, dst, w)`` edge list this plan encodes, in
        slot (bucket) order — the layout-semantics ground truth the
        delta property tests compare against a from-scratch rebuild."""
        return self._edges_of_segments(np.arange(self.n_segments, dtype=np.int64))

    def apply_delta(
        self,
        inserts: tuple[np.ndarray, np.ndarray, np.ndarray] | None,
        deletes: tuple[np.ndarray, np.ndarray] | None,
        *,
        n: int | None = None,
        fingerprint: str,
        phase: PlanPhase = _no_phase,
    ) -> "WindowPlan":
        """Incrementally fold an edge delta into the layout, returning a
        NEW plan (arrays are copied where touched, shared elsewhere —
        the old plan stays valid for the in-flight epoch).

        ``inserts`` is ``(src, dst, w)`` of edges to add (normalized
        weights), ``deletes`` is ``(src, dst)`` of edges to remove; ``n``
        grows the peer set (new peers join with no plan presence until
        an insert names them).  Host-side cost: O(Δ log Δ) sorting over
        the delta; per touched window, two binary searches per vreg-row
        into the segment table and a gather and repack of that window's
        runs and slots alone; then a fixed set of whole-table passes,
        however many windows are touched — the copies of ``wid``,
        ``local``, ``weight`` and ``run_start`` (O(slots)), the kept-run compress
        (O(S)), the splice (a stable argsort of the S run ends) and the
        dst counting sort behind ``run_end``/``dst_ptr`` (O(S)) — far
        below the full rebuild's O(E) counting sorts.  The result's
        ``fingerprint`` is the caller-supplied identity of the
        post-delta graph and the predecessor chain lands in ``lineage``.

        Raises :class:`PlanDeltaError` when the delta cannot be folded
        (peer set shrank, a deleted edge is absent, or a window outgrew
        the spare-row headroom) — callers fall back to
        ``build_window_plan``.
        """
        empty_i = (np.empty(0, np.int32),) * 2 + (np.empty(0, np.float32),)
        ins_src, ins_dst, ins_w = (
            tuple(np.asarray(a) for a in inserts) if inserts is not None else empty_i
        )
        del_src, del_dst = (
            tuple(np.asarray(a, np.int64) for a in deletes)
            if deletes is not None
            else (np.empty(0, np.int64),) * 2
        )
        ins_src = np.asarray(ins_src, np.int64)
        ins_dst = np.asarray(ins_dst, np.int64)
        ins_w = np.asarray(ins_w, np.float32)
        n_new = self.n if n is None else int(n)
        if n_new < self.n:
            raise PlanDeltaError("peer set shrank; rebuild the plan")
        for a in (ins_src, ins_dst, del_src, del_dst):
            if a.size and (int(a.min()) < 0 or int(a.max()) >= n_new):
                raise PlanDeltaError("delta edge index outside [0, n)")
        table_entries = -(-n_new // WINDOW) * WINDOW
        n_windows = table_entries // WINDOW
        # Over several chunks, a grid block's rows must share one chunk:
        # a window's overflow starts a new block where the open one
        # reads another chunk, and the rows skipped get wid -1.  A later
        # window of the skipped block's chunk takes them first.
        chunk_windows = self.table_chunk // WINDOW
        chunked = table_blocks(table_entries, self.table_chunk)[1] > 1
        align_rows = 0
        skipped = (
            _skipped_rows(self.wid, int(self.row_offset[-1]), self.n_data_rows, chunk_windows)
            if chunked
            else {}
        )
        row_offset = self.row_offset
        if n_windows + 1 > len(row_offset):
            # New windows own no original rows; overflow allocation
            # below serves them like any outgrown window.
            row_offset = np.concatenate(
                [
                    row_offset,
                    np.full(n_windows + 1 - len(row_offset), row_offset[-1], np.int64),
                ]
            )

        with phase("plan_repack", delta_edges=int(ins_src.size)):
            touched = np.unique(np.concatenate([ins_src, del_src]) >> _WIN_BITS)
            wid = self.wid.copy()
            local = self.local.reshape(-1).copy()
            weight = self.weight.reshape(-1).copy()
            run_start = self.run_start.reshape(-1).copy()
            n_data_rows = self.n_data_rows

            # The touched windows' rows and the runs living in them — the
            # only part of the segment table the delta reads.  Runs in
            # every other row survive verbatim; the touched ones are
            # rebuilt below.  Only live runs participate — the inert
            # device pads (above every data row) are regenerated at exit.
            touched_rows = [self._window_vreg_rows(int(w)) for w in touched.tolist()]
            touched_segs = [self._segments_of_rows(r) for r in touched_rows]
            keep = np.ones(self.n_segments, bool)
            for idx in touched_segs:
                keep[idx] = False
            new_end: list[np.ndarray] = [self.seg_end[: self.n_segments][keep]]
            new_first: list[np.ndarray] = [self.seg_first[: self.n_segments][keep]]
            new_dst: list[np.ndarray] = [self.seg_dst[keep]]

            iw = ins_src >> _WIN_BITS
            dw = del_src >> _WIN_BITS
            for w, rows_w, idx in zip(touched.tolist(), touched_rows, touched_segs):
                osrc, odst, ow = self._edges_of_segments(idx)
                # Delete by (src, dst) identity; duplicate edges are a
                # multiset — each delete consumes one instance.
                dm = dw == w
                if dm.any():
                    okey = osrc.astype(np.int64) << 32 | odst.astype(np.int64)
                    dkey = np.sort(del_src[dm] << 32 | del_dst[dm])
                    order = np.argsort(okey, kind="stable")
                    sk = okey[order]
                    pos = np.searchsorted(sk, dkey, side="left")
                    # The i-th duplicate of a delete key consumes the i-th
                    # plan instance of that edge.
                    grp = np.concatenate([[True], dkey[1:] != dkey[:-1]])
                    first = np.nonzero(grp)[0][np.cumsum(grp) - 1]
                    take = pos + (np.arange(len(dkey)) - first)
                    if take.size and (
                        int(take.max()) >= len(sk) or not (sk[take] == dkey).all()
                    ):
                        raise PlanDeltaError("delete names an edge absent from the plan")
                    drop = np.zeros(len(okey), bool)
                    drop[order[take]] = True
                    osrc, odst, ow = osrc[~drop], odst[~drop], ow[~drop]
                im = iw == w
                if im.any():
                    osrc = np.concatenate([osrc, ins_src[im].astype(np.int32)])
                    odst = np.concatenate([odst, ins_dst[im].astype(np.int32)])
                    ow = np.concatenate([ow, ins_w[im]])
                count = osrc.shape[0]
                # Zero the window's slots, then repack dst-sorted from the
                # first row — the run differencing needs gap-free packing.
                if rows_w.size:
                    slots_w = (rows_w[:, None] * ROW + np.arange(ROW)[None, :]).reshape(-1)
                    local[slots_w] = 0
                    weight[slots_w] = 0.0
                    run_start[slots_w] = slots_w % ROW == 0
                if count > rows_w.size * ROW:
                    extra = -(-(count - rows_w.size * ROW) // ROW)
                    free = skipped.get(w // chunk_windows, [])
                    reused = np.asarray(free[:extra], np.int64)
                    del free[:extra]
                    extra -= reused.size
                    start = n_data_rows
                    blk = start - start % BLOCK_ROWS
                    if (
                        extra
                        and chunked
                        and start > blk
                        and int(wid[blk:start].max()) // chunk_windows
                        != w // chunk_windows
                    ):
                        start = blk + BLOCK_ROWS
                        skipped.setdefault(
                            int(wid[blk:n_data_rows].max()) // chunk_windows, []
                        ).extend(range(n_data_rows, start))
                    if start + extra > self.n_rows:
                        raise PlanDeltaError(
                            f"window {w} outgrew the spare-row headroom; rebuild"
                        )
                    wid[n_data_rows:start] = -1
                    align_rows += start - n_data_rows
                    grown = np.concatenate(
                        [reused, np.arange(start, start + extra, dtype=np.int64)]
                    )
                    wid[grown] = w
                    n_data_rows = start + extra
                    rows_w = np.sort(np.concatenate([rows_w, grown]))
                if count == 0:
                    continue
                order = np.argsort(odst, kind="stable")
                d = odst[order].astype(np.int64)
                slots = rows_w[np.arange(count) // ROW] * ROW + np.arange(count) % ROW
                local[slots] = (osrc[order] & (WINDOW - 1)).astype(np.int32)
                weight[slots] = ow[order]
                lead = np.arange(count) % ROW == 0
                brk = np.empty(count, bool)
                brk[0] = True
                brk[1:] = (d[1:] != d[:-1]) | lead[1:]
                endm = np.empty(count, bool)
                endm[-1] = True
                endm[:-1] = brk[1:]
                run_start[slots[brk]] = True
                new_end.append(slots[endm])
                new_first.append(lead[brk])
                new_dst.append(d[brk])

        with phase("plan_splice"):
            all_end = np.concatenate(new_end)
            order = np.argsort(all_end, kind="stable")
            live_end = all_end[order]
            if live_end.size > 1 and not (np.diff(live_end) > 0).all():
                raise AssertionError("delta produced overlapping runs (plan bug)")
            live_first = np.concatenate(new_first)[order]
            seg_dst = np.concatenate(new_dst)[order].astype(np.int32)
            # Keep the device capacity (and so every array shape + the
            # compiled kernel) whenever the new run count still fits; grow
            # by whole quanta otherwise — one recompile, then stable again.
            s_new = int(seg_dst.shape[0])
            max_pad = (self.n_rows - n_data_rows) * ROW
            capacity = self.seg_capacity
            if s_new > capacity or capacity - s_new > max_pad:
                capacity = _segment_capacity(s_new, max_pad)
        with phase("plan_seg_tables"):
            seg_end, seg_first, run_end, dst_ptr = _pad_segment_tables(
                live_end,
                live_first,
                seg_dst,
                capacity=capacity,
                n=n_new,
                n_rows=self.n_rows,
                n_data_rows=n_data_rows,
            )
        return WindowPlan(
            n=n_new,
            n_rows=self.n_rows,
            table_entries=table_entries,
            n_segments=int(seg_dst.shape[0]),
            n_data_rows=n_data_rows,
            n_edges=self.n_edges - int(del_src.size) + int(ins_src.size),
            wid=wid,
            local=local.reshape(self.local.shape),
            weight=weight.reshape(self.weight.shape),
            run_start=run_start.reshape(self.run_start.shape),
            seg_end=seg_end,
            seg_first=seg_first,
            run_end=run_end,
            dst_ptr=dst_ptr,
            seg_dst=seg_dst,
            row_offset=row_offset,
            fingerprint=fingerprint,
            table_chunk=self.table_chunk,
            lineage=(self.lineage + (self.fingerprint,))[-LINEAGE_DEPTH:],
            align_rows=align_rows,
        )

    def replace_rows(
        self,
        rows: np.ndarray,
        new_src: np.ndarray,
        new_dst: np.ndarray,
        new_w: np.ndarray,
        *,
        n: int | None = None,
        fingerprint: str,
        phase: PlanPhase = _no_phase,
    ) -> "WindowPlan":
        """Replace every out-edge of the given source peers with the
        supplied (normalized) edges — the natural delta unit, because
        row normalization makes any change to a peer's attestation
        rewrite that peer's whole out-row.  Deletes are recovered from
        the plan itself, so callers need no copy of the previous edge
        list.  Raises :class:`PlanDeltaError` like ``apply_delta``."""
        with phase("plan_extract"):
            rows = np.unique(np.asarray(rows, np.int64))
            new_src = np.asarray(new_src, np.int64)
            if new_src.size and not np.isin(new_src, rows).all():
                raise PlanDeltaError("replacement edge outside the replaced rows")
            parts = [
                self._edges_of_segments(
                    self._segments_of_rows(self._window_vreg_rows(int(w)))
                )
                for w in np.unique(rows >> _WIN_BITS).tolist()
            ]
            if parts:
                osrc = np.concatenate([p[0] for p in parts])
                odst = np.concatenate([p[1] for p in parts])
                m = np.isin(osrc.astype(np.int64), rows)
                deletes = (osrc[m], odst[m])
            else:
                deletes = None
        return self.apply_delta(
            (new_src, new_dst, new_w),
            deletes,
            n=n,
            fingerprint=fingerprint,
            phase=phase,
        )


def graph_fingerprint(
    n: int, src: np.ndarray, dst: np.ndarray, weights: EdgeWeights
) -> str:
    """Cheap identity for plan-reuse validation: exact (n, nnz) plus a
    sha1 over strided samples of the edge arrays (hashing all 600 MB at
    bench scale would cost a meaningful fraction of plan construction;
    a strided digest catches every realistic graph change).  The
    weights are read at the samples alone."""
    h = hashlib.sha1()
    h.update(np.asarray([n, src.shape[0]], np.int64).tobytes())
    samples = slice(None, None, max(1, src.shape[0] // (1 << 20)))
    for a in (src[samples], dst[samples], weights(samples)):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def build_window_plan(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    *,
    n: int,
    spare_rows: int | None = None,
    phase: PlanPhase = _no_phase,
) -> WindowPlan:
    """One-time host construction of the fused-pipeline layout for a
    row-normalized, self-edge-free edge list.  ``spare_rows`` of
    zero-weight tail headroom (adaptive by default: one grid block or
    ~6% of the data rows) lets ``apply_delta`` absorb window growth —
    and segment-table fragmentation — across epochs without a rebuild
    or a device-shape change.  ``phase`` wraps the two sub-phases,
    ``plan_bucket`` and ``plan_seg_tables``.  The rows are aligned to
    the table chunks of ``TABLE_CHUNK`` as it stands at the call."""
    table_chunk = TABLE_CHUNK
    with phase("plan_bucket", edges=int(src.shape[0])):
        b = bucket_by_window(
            src, w, table_size=n, dst=dst, n_dst=n, spare_rows=spare_rows
        )
    # Device segment tables at quantized capacity: the inert pads give
    # apply_delta shape-stability headroom (no recompile per epoch).
    max_pad = (b["n_rows"] - b["n_data_rows"]) * ROW
    with phase("plan_seg_tables"):
        seg_end, seg_first, run_end, dst_ptr = _pad_segment_tables(
            b["seg_end"],
            b["seg_first"],
            b["seg_dst"],
            capacity=_segment_capacity(b["n_segments"], max_pad),
            n=n,
            n_rows=b["n_rows"],
            n_data_rows=b["n_data_rows"],
        )
    return WindowPlan(
        n=n,
        n_rows=b["n_rows"],
        table_entries=-(-n // WINDOW) * WINDOW,
        n_segments=b["n_segments"],
        n_data_rows=b["n_data_rows"],
        n_edges=int(src.shape[0]),
        wid=b["wid"],
        local=b["local"],
        weight=b["weight"],
        run_start=b["run_start"],
        seg_end=seg_end,
        seg_first=seg_first,
        run_end=run_end,
        dst_ptr=dst_ptr,
        seg_dst=b["seg_dst"],
        row_offset=b["row_offset"],
        fingerprint=graph_fingerprint(n, src, dst, w.__getitem__),
        table_chunk=table_chunk,
        order=b["order"],
        out_pos=b["out_pos"],
        align_rows=b["align_rows"],
    )


def try_plan_delta(
    plan: WindowPlan,
    src: np.ndarray,
    dst: np.ndarray,
    weights: EdgeWeights,
    *,
    n: int,
    rows: np.ndarray,
    fingerprint: str,
    phase: PlanPhase = _no_phase,
) -> WindowPlan | None:
    """Fold per-epoch churn into a cached plan: replace the out-edges of
    the hinted ``rows`` (every source peer whose attestation changed
    since the plan's graph — row normalization rewrites exactly those
    rows) with their slice of the new normalized edge list
    ``(src, dst, weights)``, whose weights are read for those rows
    alone.  Returns the delta-updated plan, or None when the
    delta cannot be applied (overflow, shrink), when it would not pay
    (churn spread over too many windows — past the measured crossover
    a full rebuild's vectorized counting sorts beat the per-window
    repack), or when it fails the edge-count tripwire (a
    stale/incomplete ``rows`` hint would stamp the new fingerprint
    onto a layout that doesn't encode the new graph — in that case the
    caller must rebuild).  ``phase`` wraps the sub-phases:
    ``plan_select`` here, then ``plan_extract`` (``replace_rows``),
    ``plan_repack``, ``plan_splice`` and ``plan_seg_tables``
    (``apply_delta``).
    """
    rows = np.unique(np.asarray(rows, np.int64))
    if rows.size == 0:
        return None
    # Delta-vs-rebuild crossover: the repack loop costs ~constant per
    # touched window while the rebuild is one vectorized O(E) pass, so
    # window-spread churn (every window touched) runs ~5x SLOWER as a
    # delta.  The measured crossover sits near a quarter of the data
    # windows; the 64-window floor keeps small graphs (few windows
    # total, trivially all touched) on the delta path where the
    # absolute cost is noise.
    data_windows = max(1, int(np.count_nonzero(np.diff(plan.row_offset))))
    touched_windows = int(np.unique(rows >> _WIN_BITS).size)
    if touched_windows > max(64, data_windows // 4):
        return None
    with phase("plan_select", rows=int(rows.size), touched_windows=touched_windows):
        sel = np.flatnonzero(np.isin(src, rows.astype(src.dtype)))
        new_src, new_dst, new_w = src[sel], dst[sel], weights(sel)
    try:
        new_plan = plan.replace_rows(
            rows, new_src, new_dst, new_w, n=n, fingerprint=fingerprint, phase=phase
        )
    except PlanDeltaError:
        return None
    if new_plan.n_edges != src.shape[0]:
        # The hint missed a changed row: the delta edge count disagrees
        # with the target graph.  Never serve a mislabeled layout.
        return None
    return new_plan


def resolve_plan(
    candidate: WindowPlan | None,
    src: np.ndarray,
    dst: np.ndarray,
    weights: EdgeWeights,
    *,
    n: int,
    fingerprint: str,
    rows: np.ndarray | None = None,
    attempt: PlanAttempt = _no_attempt,
) -> tuple[WindowPlan, str]:
    """The plan for the normalized edge list ``(src, dst, weights)``:
    reuse ``candidate`` (cached or checkpoint-restored) while its layout
    version and ``fingerprint`` hold; else, given the churn hint
    ``rows``, delta-update it (``try_plan_delta``, which reads the
    hinted rows' weights alone); else rebuild from the whole weight
    array, read once inside the attempt.  Returns the plan and the
    outcome: ``reuse``, ``delta`` or ``rebuild``."""
    valid = candidate is not None and getattr(candidate, "version", 0) == PLAN_VERSION
    if valid and candidate.fingerprint == fingerprint:
        return candidate, "reuse"
    if valid and rows is not None:
        delta = attempt("delta", lambda phase: try_plan_delta(
            candidate, src, dst, weights, n=n, rows=rows, fingerprint=fingerprint,
            phase=phase,
        ))
        if delta is not None:
            return delta, "delta"
    reason = "cold" if candidate is None else "fingerprint-miss" if valid else "stale-layout"
    plan = attempt(reason, lambda phase: build_window_plan(
        src, dst, weights(slice(None)), n=n, phase=phase
    ))
    return plan, "rebuild"


def partition_delta(
    rows: np.ndarray | None,
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    owner: np.ndarray,
    host: int,
) -> tuple[np.ndarray | None, np.ndarray, np.ndarray, np.ndarray]:
    """Clip a churn hint and a row-normalized edge list to one pod
    host's partition (``owner[i]`` = host owning source peer ``i``,
    from ``parallel.partition.HostPartition``).

    Edges are owned by their **source** peer, so a dirty row (one
    sender's rewritten out-edges) is dirty on exactly one host: the
    returned ``owned_rows`` feed :func:`resolve_plan` against that
    host's *local* plan, and hosts owning none of the churn keep their
    plan verbatim — steady-state churn never forces a cross-host
    rebuild.  Returns ``(owned_rows, local_src, local_dst, local_w)``;
    ``owned_rows`` is None when the caller passed no hint (forcing
    fingerprint-only revalidation, same contract as the global path).
    """
    owner = np.asarray(owner)
    mask = owner[src] == host
    owned_rows = None
    if rows is not None:
        rows = np.unique(np.asarray(rows, np.int64))
        owned_rows = rows[owner[rows] == host]
    return owned_rows, src[mask], dst[mask], w[mask]


def bridge_partials(
    out: jax.Array, run_start: jax.Array, run_end: jax.Array
) -> jax.Array:
    """Dst-ordered per-(row, dst) run sums of the kernel's slot
    products ``out`` (``(n_rows*8, 128)``, bucket order): a segmented
    inclusive scan along each 1024-slot row — Hillis-Steele, log2(1024)
    shifted adds that stop at ``run_start`` flags, no gather — leaves
    each run's sum in its end slot, and one gather at ``run_end``
    (dst order) reads them out: the only n_segments-sized random access
    per iteration."""
    n_rows = out.shape[0] // 8
    v = out.reshape(n_rows, ROW)
    f = run_start.reshape(n_rows, ROW)
    shift = 1
    while shift < ROW:
        vs = jnp.pad(v, ((0, 0), (shift, 0)))[:, :-shift]
        fs = jnp.pad(f, ((0, 0), (shift, 0)))[:, :-shift]
        v = jnp.where(f, v, v + vs)
        f = f | fs
        shift <<= 1
    return v.reshape(-1)[run_end]


def windowed_ct(
    wid: jax.Array,
    local: jax.Array,
    weight: jax.Array,
    run_start: jax.Array,
    run_end: jax.Array,
    dst_ptr: jax.Array,
    t: jax.Array,
    *,
    n_rows: int,
    table_entries: int,
    table_chunk: int,
    interpret: bool = False,
) -> jax.Array:
    """Dense Cᵀt over the plan's slot set — the fused pipeline minus
    damping.  Shared verbatim by the single-device step and the
    per-shard step under ``shard_map`` (``parallel/sharded.py``), where
    the plan arrays cover one shard's rows/runs and the returned vector
    is that shard's partial product (completed by ``lax.psum``):

    1. windowed Pallas gather-multiply from the score table, read into
       VMEM in ``table_chunk`` blocks (bucket order — no random access,
       PERF.md §6: 7.9 ms at 50M edges);
    2. ``bridge_partials``: each run summed in place by a segmented
       scan along its row, and the run sums read out in dst order —
       one n_segments random gather;
    3. ``rowsum_sorted`` over the dst-delimited run sums → dense Cᵀt.
    """
    n = t.shape[0]
    table = jnp.pad(t, (0, table_entries - n))
    out = gather_windowed(
        wid,
        table,
        local,
        weight,
        n_rows=n_rows,
        table_chunk=table_chunk,
        interpret=interpret,
    )
    return rowsum_sorted(bridge_partials(out, run_start, run_end), dst_ptr)


def power_step_windowed(
    wid: jax.Array,
    local: jax.Array,
    weight: jax.Array,
    run_start: jax.Array,
    run_end: jax.Array,
    dst_ptr: jax.Array,
    t: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    alpha: jax.Array | float,
    *,
    n_rows: int,
    table_entries: int,
    table_chunk: int,
    interpret: bool = False,
) -> jax.Array:
    """One damped step of the fused fixed-slot pipeline: ``windowed_ct``
    then the shared damping + dangling redistribution + L1 renorm."""
    ct = windowed_ct(
        wid,
        local,
        weight,
        run_start,
        run_end,
        dst_ptr,
        t,
        n_rows=n_rows,
        table_entries=table_entries,
        table_chunk=table_chunk,
        interpret=interpret,
    )
    dangling_mass = jnp.sum(t * dangling)
    t_new = (1.0 - alpha) * (ct + dangling_mass * p) + alpha * p
    return t_new / jnp.sum(t_new)


@partial(
    jax.jit,
    static_argnames=(
        "n_rows", "table_entries", "table_chunk", "tol", "max_iter", "interpret",
        "record_residuals",
    ),
    donate_argnames=("t0",),
)
def converge_windowed(
    wid: jax.Array,
    local: jax.Array,
    weight: jax.Array,
    run_start: jax.Array,
    run_end: jax.Array,
    dst_ptr: jax.Array,
    t0: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    *,
    n_rows: int,
    table_entries: int,
    table_chunk: int,
    alpha: jax.Array | float = 0.1,
    tol: float = 1e-6,
    max_iter: int = 50,
    interpret: bool = False,
    record_residuals: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused-pipeline analog of ``converge_csr`` — same shared
    ``run_power_iteration`` driver, so early-exit semantics can't drift
    between formulations.  ``t0`` is donated (pass a fresh buffer);
    the plan arrays are not — they are reused across epochs.
    ``record_residuals`` appends the device-side residual history to
    the returned tuple (the telemetry path; no host sync, no new
    gathers — see ``run_power_iteration``).  The layout's static
    arguments are ``WindowPlan.static_kwargs()``."""
    return run_power_iteration(
        lambda t: power_step_windowed(
            wid,
            local,
            weight,
            run_start,
            run_end,
            dst_ptr,
            t,
            p,
            dangling,
            alpha,
            n_rows=n_rows,
            table_entries=table_entries,
            table_chunk=table_chunk,
            interpret=interpret,
        ),
        t0,
        tol=tol,
        max_iter=max_iter,
        record_residuals=record_residuals,
    )


# ---------------------------------------------------------------------------
# Pinned kernel invariants (PERF.md §9) — checked per step by
# `python -m protocol_tpu.analysis` against the traced jaxpr.
# ---------------------------------------------------------------------------

#: The tentpole contract of the fused fixed-slot pipeline: exactly one
#: n_segments-sized gather per step — the run sums read out in dst
#: order at ``run_end`` — plus the four (n+1)-sized rowsum pointer
#: reads; no scatter; the
#: windowed Pallas kernel must actually be present (gathers inside its
#: interpret body are excluded from the counts: on the real chip they
#: are Mosaic codegen, not XLA gathers).
declare(
    KernelBudget(
        backend="tpu-windowed",
        max_random_gathers=5,
        max_scatters=0,
        require_primitives=("pallas_call",),
        gather_budgets=(
            GatherBudget(dim="n_segments", max_total=1, max_random=1),
        ),
        donated_args=("t0",),
        notes=(
            "fused pipeline: 1 random n_segments pass (run sums in dst "
            "order), 4 rowsum pointer reads"
        ),
    )
)

#: Single-device fused pipeline (graftlint pass 8): zero collectives,
#: zero host round-trips — the Pallas windowed gather is VMEM-local by
#: construction — and the t0 donation must survive into the compiled
#: module's input_output_alias table (PERF.md §15).
declare_comm(
    CommBudget(
        backend="tpu-windowed",
        donated_args=("t0",),
        notes="single-device fused pipeline: no wire, no host traffic",
    )
)

#: Peak-HBM budget (graftlint pass 12, PERF.md §19).  Resident: the
#: plan's vreg-row tables — ``local`` + ``weight`` at (8, 128) slots x
#: 4 B each, the 1 B ``run_start`` flag per slot, plus ``wid`` = 9220
#: B/row — the dst-order run ends (``run_end``, 4 B/run), the (n+1)
#: dst pointers and the three f32[N] vectors.  Transient: the Pallas
#: INTERPRET compile (the analyzer's CPU mesh) re-expresses the Mosaic
#: kernel as XLA ops, so its VMEM-analog scratch (on the real chip
#: VMEM, not HBM), the kernel's per-block chunk ids and the segmented
#: scan's row-shaped values and flags ride ``transient_rows`` (fitted:
#: 52161 B/row, about 6.4 row-table copies); the run sums and the
#: rowsum's prefix lanes ride ``transient_segments``.  Rows are a
#: plan-layout dimension (1024 edge slots each) — there is still no
#: per-edge transient coefficient, and the committed slack is below a
#: 4 B/edge temporary (pinned by test).
declare_mem(
    MemBudget(
        backend="tpu-windowed",
        resident_rows=9220.0,
        resident_segments=4.0,
        resident_n=16.0,
        resident_const=4096.0,
        transient_rows=52161.0,
        transient_n=36.0,
        transient_segments=5.0,
        transient_const=4096.0,
        donated_args=("t0",),
        notes="fused pipeline: plan tables resident; interpret-mode "
        "kernel scratch and scan temporaries ~6.4x the 8 KB row tables",
    )
)
