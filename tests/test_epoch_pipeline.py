"""Incremental epoch engine (ISSUE 5): warm-start convergence, delta
WindowPlan updates, and the double-buffered host/device epoch pipeline.

Covers the acceptance properties:

- warm-start and cold-start reach the same fixed point within tolerance
  under random churn, including peer join/leave, on every backend rung;
- ``WindowPlan.apply_delta`` produces a plan identical in layout
  semantics to a from-scratch rebuild of the same graph (same edge
  multiset, same invariants, same device Cᵀt) while keeping the device
  array shapes (no recompile) and chaining fingerprint lineage;
- the manager's dirty-row tracking, plan-cache handoff (delta outcome
  metric), and checkpointed warm-start state survive a restart;
- the pipeline overlaps host/device stages behind a bounded queue and
  coalesces — never drops — ticks under backpressure.
"""

import time

import numpy as np
import pytest

from protocol_tpu.models.graphs import erdos_renyi, scale_free
from protocol_tpu.node.checkpoint import CheckpointStore
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.node.manager import Manager, ManagerConfig, PreparedEpoch
from protocol_tpu.node.pipeline import EpochPipeline
from protocol_tpu.obs.metrics import PEER_ALIGN, PLAN_OUTCOMES
from protocol_tpu.obs.watchers import DRIFT
from protocol_tpu.ops import gather_window as gw
from protocol_tpu.ops.gather_window import (
    ROW,
    WINDOW,
    PlanDeltaError,
    WindowPlan,
    build_window_plan,
    try_plan_delta,
)
from protocol_tpu.trust.backend import get_backend
from protocol_tpu.trust.graph import TrustGraph


def l1(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).sum())


def churn_graph(g: TrustGraph, fraction: float, rng, n_new: int = 0):
    """Rewire ``fraction``·E edges to random destinations/weights and
    optionally grow the peer set; returns ``(graph, changed_rows)``."""
    n = g.n + n_new
    k = max(1, int(g.nnz * fraction))
    idx = rng.choice(g.nnz, k, replace=False)
    dst = g.dst.copy()
    dst[idx] = rng.integers(0, n, k)
    while (bad := dst[idx] == g.src[idx]).any():
        dst[idx[bad]] = rng.integers(0, n, int(bad.sum()))
    w = g.weight.copy()
    w[idx] = rng.integers(1, 1000, k).astype(np.float32)
    pre = g.pre_trusted
    if n_new and pre is not None:
        pre = np.concatenate([pre, np.zeros(n_new, bool)])
    return TrustGraph(n, g.src, dst, w, pre), np.unique(g.src[idx])


def edge_multiset(src, dst, w):
    a = np.stack(
        [
            np.asarray(src, np.int64),
            np.asarray(dst, np.int64),
            np.asarray(w, np.float32).view(np.int32).astype(np.int64),
        ],
        axis=1,
    )
    return a[np.lexsort(a.T[::-1])]


class TestWarmStartFixedPoint:
    """Warm and cold starts land on the same fixed point — the property
    that makes warm starting free of correctness risk."""

    @pytest.mark.parametrize(
        "backend", ["tpu-csr", "tpu-windowed", "tpu-sharded:tpu-windowed"]
    )
    def test_same_fixed_point_under_churn(self, backend):
        rng = np.random.default_rng(41)
        g = scale_free(1800, 11000, seed=3)
        b = get_backend(backend)
        prev = b.converge(g, alpha=0.1, tol=1e-6, max_iter=80)
        g2, rows = churn_graph(g.drop_self_edges(), 0.02, rng)
        if hasattr(b, "delta_rows"):
            b.delta_rows = rows
        warm = b.converge(g2, alpha=0.1, tol=1e-6, max_iter=80, t0=prev.scores)
        cold = get_backend(backend).converge(g2, alpha=0.1, tol=1e-6, max_iter=80)
        assert l1(warm.scores, cold.scores) <= 1e-5
        assert warm.iterations < cold.iterations

    def test_same_fixed_point_with_join_and_leave(self):
        """Peers join (n grows) and leave (their edges vanish): the
        warm seed is renormalized over the survivors and still reaches
        the cold fixed point."""
        rng = np.random.default_rng(42)
        g = scale_free(1500, 9000, seed=5).drop_self_edges()
        b = get_backend("tpu-windowed")
        prev = b.converge(g, alpha=0.1, tol=1e-7, max_iter=80)
        # Leave: drop every edge touching 30 peers; join: 64 new peers
        # with edges in both directions.
        gone = rng.choice(g.n, 30, replace=False)
        keep = ~(np.isin(g.src, gone) | np.isin(g.dst, gone))
        n2 = g.n + 64
        new_src = rng.integers(g.n, n2, 200).astype(np.int32)
        new_dst = rng.integers(0, g.n, 200).astype(np.int32)
        g2 = TrustGraph(
            n2,
            np.concatenate([g.src[keep], new_src, new_dst]),
            np.concatenate([g.dst[keep], new_dst, new_src]),
            np.concatenate(
                [g.weight[keep], np.ones(400, np.float32) * 7.0]
            ),
            np.concatenate([g.pre_trusted, np.zeros(64, bool)])
            if g.pre_trusted is not None
            else None,
        ).drop_self_edges()
        # Remap the old fixed point onto the new id space (ids are
        # stable here; the survivors keep their score, joiners start 0).
        t0 = np.zeros(n2)
        t0[: g.n] = np.maximum(prev.scores, 0.0)
        t0[gone] = 0.0
        warm = b.converge(g2, alpha=0.1, tol=1e-7, max_iter=80, t0=t0 / t0.sum())
        cold = get_backend("tpu-windowed").converge(
            g2, alpha=0.1, tol=1e-7, max_iter=80
        )
        assert l1(warm.scores, cold.scores) <= 1e-5

    def test_degenerate_seed_falls_back_to_cold(self):
        g = erdos_renyi(400, avg_degree=5.0, seed=6)
        b = get_backend("tpu-csr")
        bad = np.zeros(400)  # zero mass: unusable seed
        res = b.converge(g, alpha=0.1, tol=1e-7, max_iter=50, t0=bad)
        cold = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-7, max_iter=50)
        assert l1(res.scores, cold.scores) <= 1e-6
        short = np.ones(17)  # mis-shaped seed
        res2 = b.converge(g, alpha=0.1, tol=1e-7, max_iter=50, t0=short)
        assert l1(res2.scores, cold.scores) <= 1e-6


class TestApplyDelta:
    def _normalized(self, g):
        g = g.drop_self_edges()
        w, _ = g.row_normalized()
        return g, w

    def _churned_rows(self, g, w, rng, rows_n=25):
        """Whole-row replacement delta in the normalized domain."""
        rows = rng.choice(g.n, rows_n, replace=False)
        ns, nd, nw = [], [], []
        for r in rows:
            deg = int(rng.integers(1, 6))
            tgt = rng.choice(g.n, deg, replace=False)
            ww = rng.random(deg)
            ww /= ww.sum()
            ns += [r] * deg
            nd += list(tgt)
            nw += list(ww)
        keep = ~np.isin(g.src, rows)
        full = (
            np.concatenate([g.src[keep], np.array(ns, np.int32)]),
            np.concatenate([g.dst[keep], np.array(nd, np.int32)]),
            np.concatenate([w[keep], np.array(nw, np.float32)]),
        )
        return rows, np.array(ns, np.int32), np.array(nd, np.int32), np.array(
            nw, np.float32
        ), full

    def test_delta_matches_rebuild_layout_semantics(self):
        """The acceptance property: a delta-updated plan is identical
        in layout semantics to a from-scratch rebuild of the same graph
        — same edge multiset, same layout invariants, and the same
        device Cᵀt bit pattern."""
        import jax.numpy as jnp

        from protocol_tpu.ops.gather_window import windowed_ct

        rng = np.random.default_rng(7)
        g, w = self._normalized(scale_free(2200, 14000, seed=9))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        rows, ns, nd, nw, full = self._churned_rows(g, w, rng)
        p2 = plan.replace_rows(rows, ns, nd, nw, fingerprint="post")
        ref = build_window_plan(*full, n=g.n)

        # Same edge multiset...
        assert (
            edge_multiset(*p2.recovered_edges()) == edge_multiset(*full)
        ).all()
        assert p2.n_edges == ref.n_edges == full[0].shape[0]
        # ...same layout invariants...
        live = p2.seg_end.astype(np.int64)[: p2.n_segments]
        assert (np.diff(live) > 0).all()
        rows_of = live // ROW
        expect_first = np.empty(len(live), bool)
        expect_first[0] = True
        expect_first[1:] = rows_of[1:] != rows_of[:-1]
        np.testing.assert_array_equal(p2.seg_first[: p2.n_segments], expect_first)
        np.testing.assert_array_equal(np.sort(p2.run_end), p2.seg_end)
        assert int(p2.dst_ptr[-1]) == p2.n_segments
        # ...and the same device product as the rebuilt plan.
        t = rng.random(g.n).astype(np.float32)

        def ct(pl):
            return np.asarray(
                windowed_ct(
                    *pl.device_args(),
                    jnp.asarray(t),
                    **pl.static_kwargs(),
                    interpret=True,
                )
            )

        np.testing.assert_allclose(ct(p2), ct(ref), atol=1e-6)

    def test_delta_keeps_device_shapes(self):
        """Steady-state churn must not change any device array shape —
        a shape change recompiles the whole convergence kernel."""
        rng = np.random.default_rng(8)
        g, w = self._normalized(scale_free(2200, 14000, seed=9))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        cur, cw = g, w
        p = plan
        for i in range(5):
            rows, ns, nd, nw, full = self._churned_rows(cur, cw, rng, rows_n=15)
            p = p.replace_rows(rows, ns, nd, nw, fingerprint=f"fp{i}")
            cur = TrustGraph(cur.n, full[0], full[1], full[2], cur.pre_trusted)
            cw = full[2]
            for k in WindowPlan._DEVICE:
                assert getattr(p, k).shape == getattr(plan, k).shape, (i, k)
        assert len(p.lineage) == 5
        assert p.lineage[0] == plan.fingerprint

    def test_n_growth_and_new_windows(self):
        rng = np.random.default_rng(10)
        g, w = self._normalized(scale_free(1200, 8000, seed=11))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        # Joiners far beyond the old table: new windows, bigger dst_ptr.
        ns = np.array([2500, 2500, 3, 2047], np.int32)
        nd = np.array([1, 2500 + 1, 2500, 5], np.int32)
        nw = np.array([0.5, 0.5, 1.0, 1.0], np.float32)
        p2 = plan.apply_delta((ns, nd, nw), None, n=2600, fingerprint="grown")
        assert p2.n == 2600
        assert p2.dst_ptr.shape == (2601,)
        assert p2.table_entries >= 2600
        full = (
            np.concatenate([g.src, ns]),
            np.concatenate([g.dst, nd]),
            np.concatenate([w, nw]),
        )
        assert (
            edge_multiset(*p2.recovered_edges()) == edge_multiset(*full)
        ).all()

    def test_delete_absent_edge_and_shrink_raise(self):
        g, w = self._normalized(scale_free(900, 5000, seed=12))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        missing = (np.array([int(g.src[0])]), np.array([(int(g.dst[0]) + 1) % g.n]))
        deleted = ~np.isin(
            np.arange(g.nnz), np.nonzero((g.src == g.src[0]) & (g.dst == missing[1][0]))[0]
        )
        if not deleted.all():  # the "absent" pair happens to exist: pick another
            missing = (np.array([g.n - 1]), np.array([g.n - 1]))
        with pytest.raises(PlanDeltaError):
            plan.apply_delta(None, missing, fingerprint="x")
        with pytest.raises(PlanDeltaError):
            plan.apply_delta(None, None, n=g.n - 1, fingerprint="x")

    def test_overflow_falls_back_via_try_plan_delta(self):
        """A delta bigger than the spare-row headroom returns None from
        try_plan_delta — the caller rebuilds instead of corrupting."""
        g, w = self._normalized(erdos_renyi(500, avg_degree=4.0, seed=13))
        plan = build_window_plan(g.src, g.dst, w, n=g.n, spare_rows=0)
        rng = np.random.default_rng(14)
        # Blow out one window far past its padding AND the (zero) spare.
        rows = np.arange(0, 400, dtype=np.int64)
        ns = np.repeat(rows, 600).astype(np.int32)
        nd = rng.integers(0, 500, ns.shape[0]).astype(np.int32)
        nw = np.full(ns.shape[0], 1e-3, np.float32)
        keep = ~np.isin(g.src, rows)
        full_src = np.concatenate([g.src[keep], ns])
        full_dst = np.concatenate([g.dst[keep], nd])
        full_w = np.concatenate([w[keep], nw])
        out = try_plan_delta(
            plan, full_src, full_dst, full_w.__getitem__, n=g.n, rows=rows, fingerprint="of"
        )
        assert out is None

    def test_stale_hint_tripwire(self):
        """An incomplete churn hint (edge counts disagree) must never
        produce a plan stamped with the new fingerprint."""
        g, w = self._normalized(scale_free(900, 5000, seed=15))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        # New graph deletes row 0's edges too, but the hint only names
        # row 1 — the delta cannot represent the target graph.
        keep = ~np.isin(g.src, [0, 1])
        out = try_plan_delta(
            plan,
            g.src[keep],
            g.dst[keep],
            w[keep].__getitem__,
            n=g.n,
            rows=np.array([1]),
            fingerprint="stale",
        )
        assert out is None

    def test_plan_v3_roundtrips_with_lineage(self, tmp_path):
        g, w = self._normalized(scale_free(900, 5000, seed=16))
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        p2 = plan.apply_delta(
            (np.array([3], np.int32), np.array([7], np.int32), np.array([0.5], np.float32)),
            None,
            fingerprint="child",
        )
        store = CheckpointStore(tmp_path)
        store.save(Epoch(2), erdos_renyi(20, seed=1), plan=p2)
        snap = store.load_latest()
        assert snap.plan is not None
        assert snap.plan.lineage == (plan.fingerprint,)
        assert snap.plan.n_edges == p2.n_edges
        assert snap.plan.n_data_rows == p2.n_data_rows
        for k in WindowPlan._CORE + WindowPlan._HOST:
            np.testing.assert_array_equal(getattr(snap.plan, k), getattr(p2, k))


# -- Oracle: the delta as it addressed the segment table before it read
# only the touched windows' run ranges (a full int64 cast of ``seg_end``
# per helper call, kept runs chosen by window id over every run).


def _old_segments_of_rows(plan, rows):
    end = plan.seg_end.astype(np.int64)
    lo = np.searchsorted(end, rows * ROW, side="left")
    hi = np.searchsorted(end, (rows + 1) * ROW - 1, side="right")
    parts = [np.arange(a, b, dtype=np.int64) for a, b in zip(lo, hi) if b > a]
    if not parts:
        return np.empty(0, np.int64)
    return np.concatenate(parts)


def _old_edges_of_segments(plan, idx):
    if idx.size == 0:
        z = np.empty(0, np.int32)
        return z, z, np.empty(0, np.float32)
    end = plan.seg_end.astype(np.int64)
    start = np.where(
        plan.seg_first[idx],
        (end[idx] // ROW) * ROW,
        end[np.maximum(idx, 1) - 1] + 1,
    )
    lens = end[idx] - start + 1
    total = int(lens.sum())
    run_of = np.repeat(np.cumsum(lens) - lens, lens)
    slots = np.repeat(start, lens) + (np.arange(total, dtype=np.int64) - run_of)
    dst = np.repeat(plan.seg_dst[idx], lens)
    rows = slots // ROW
    src = (
        plan.wid[rows].astype(np.int64) * WINDOW + plan.local.reshape(-1)[slots]
    ).astype(np.int32)
    return src, dst, plan.weight.reshape(-1)[slots]


def _old_apply_delta(plan, inserts, deletes, *, n=None, fingerprint, phase=None):
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
    n_new = plan.n if n is None else int(n)
    table_entries = -(-n_new // WINDOW) * WINDOW
    n_windows = table_entries // WINDOW
    row_offset = plan.row_offset
    if n_windows + 1 > len(row_offset):
        row_offset = np.concatenate(
            [
                row_offset,
                np.full(n_windows + 1 - len(row_offset), row_offset[-1], np.int64),
            ]
        )
    touched = np.unique(np.concatenate([ins_src, del_src]) >> gw._WIN_BITS)
    wid = plan.wid.copy()
    local = plan.local.reshape(-1).copy()
    weight = plan.weight.reshape(-1).copy()
    run_start = plan.run_start.reshape(-1).copy()
    n_data_rows = plan.n_data_rows
    row_window = wid.astype(np.int64).copy()
    row_window[plan.n_data_rows :] = -1
    end_live = plan.seg_end.astype(np.int64)[: plan.n_segments]
    first_live = plan.seg_first[: plan.n_segments]
    keep = ~np.isin(row_window[end_live // ROW], touched)
    new_end = [end_live[keep]]
    new_first = [first_live[keep]]
    new_dst = [plan.seg_dst.astype(np.int64)[keep]]
    iw = ins_src >> gw._WIN_BITS
    dw = del_src >> gw._WIN_BITS
    for w in touched.tolist():
        rows_w = plan._window_vreg_rows(int(w))
        osrc, odst, ow = _old_edges_of_segments(plan, _old_segments_of_rows(plan, rows_w))
        dm = dw == w
        if dm.any():
            okey = osrc.astype(np.int64) << 32 | odst.astype(np.int64)
            dkey = np.sort(del_src[dm] << 32 | del_dst[dm])
            order = np.argsort(okey, kind="stable")
            sk = okey[order]
            pos = np.searchsorted(sk, dkey, side="left")
            grp = np.concatenate([[True], dkey[1:] != dkey[:-1]])
            first = np.nonzero(grp)[0][np.cumsum(grp) - 1]
            take = pos + (np.arange(len(dkey)) - first)
            assert (sk[take] == dkey).all()
            drop = np.zeros(len(okey), bool)
            drop[order[take]] = True
            osrc, odst, ow = osrc[~drop], odst[~drop], ow[~drop]
        im = iw == w
        if im.any():
            osrc = np.concatenate([osrc, ins_src[im].astype(np.int32)])
            odst = np.concatenate([odst, ins_dst[im].astype(np.int32)])
            ow = np.concatenate([ow, ins_w[im]])
        count = osrc.shape[0]
        if rows_w.size:
            slots_w = (rows_w[:, None] * ROW + np.arange(ROW)[None, :]).reshape(-1)
            local[slots_w] = 0
            weight[slots_w] = 0.0
            run_start[slots_w] = slots_w % ROW == 0
        if count > rows_w.size * ROW:
            extra = -(-(count - rows_w.size * ROW) // ROW)
            grown = np.arange(n_data_rows, n_data_rows + extra, dtype=np.int64)
            wid[grown] = w
            n_data_rows += extra
            rows_w = np.concatenate([rows_w, grown])
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
    all_end = np.concatenate(new_end)
    order = np.argsort(all_end, kind="stable")
    live_end = all_end[order]
    live_first = np.concatenate(new_first)[order]
    seg_dst = np.concatenate(new_dst)[order].astype(np.int32)
    s_new = int(seg_dst.shape[0])
    max_pad = (plan.n_rows - n_data_rows) * ROW
    capacity = plan.seg_capacity
    if s_new > capacity or capacity - s_new > max_pad:
        capacity = gw._segment_capacity(s_new, max_pad)
    seg_end, seg_first, run_end, dst_ptr = gw._pad_segment_tables(
        live_end,
        live_first,
        seg_dst,
        capacity=capacity,
        n=n_new,
        n_rows=plan.n_rows,
        n_data_rows=n_data_rows,
    )
    return WindowPlan(
        n=n_new,
        n_rows=plan.n_rows,
        table_entries=table_entries,
        n_segments=s_new,
        n_data_rows=n_data_rows,
        n_edges=plan.n_edges - int(del_src.size) + int(ins_src.size),
        wid=wid,
        local=local.reshape(plan.local.shape),
        weight=weight.reshape(plan.weight.shape),
        run_start=run_start.reshape(plan.run_start.shape),
        seg_end=seg_end,
        seg_first=seg_first,
        run_end=run_end,
        dst_ptr=dst_ptr,
        seg_dst=seg_dst,
        row_offset=row_offset,
        fingerprint=fingerprint,
        table_chunk=plan.table_chunk,
        lineage=(plan.lineage + (plan.fingerprint,))[-gw.LINEAGE_DEPTH :],
    )


def _delta_chain(plan, rng):
    """Deltas that grow window 0 into overflow rows, grow ``n`` by new
    windows, delete every edge of window 1, then refill it by whole-row
    replacement; yields each step's name and plan."""
    n = plan.n
    src0 = rng.integers(0, WINDOW, 3 * ROW).astype(np.int32)
    dst0 = rng.integers(0, n, src0.size).astype(np.int32)
    ins = (src0, dst0, rng.random(src0.size).astype(np.float32))
    plan = plan.apply_delta(ins, None, fingerprint="grow-window")
    yield "grow-window", plan
    n2 = n + 2 * WINDOW
    src1 = rng.integers(n, n2, 500).astype(np.int32)
    dst1 = rng.integers(0, n2, 500).astype(np.int32)
    ins = (src1, dst1, rng.random(500).astype(np.float32))
    plan = plan.apply_delta(ins, None, n=n2, fingerprint="grow-n")
    yield "grow-n", plan
    src, dst, _ = plan._edges_of_segments(
        plan._segments_of_rows(plan._window_vreg_rows(1))
    )
    plan = plan.apply_delta(None, (src, dst), fingerprint="clear-window")
    yield "clear-window", plan
    rows = np.arange(WINDOW, WINDOW + 40)
    ns = np.repeat(rows, 7).astype(np.int32)
    nd = rng.integers(0, n2, ns.size).astype(np.int32)
    nw = rng.random(ns.size).astype(np.float32)
    plan = plan.replace_rows(rows, ns, nd, nw, fingerprint="refill")
    yield "refill", plan


def _assert_same_plan(a, b):
    for k in WindowPlan._META:
        assert getattr(a, k) == getattr(b, k), k
    for k in WindowPlan._CORE + WindowPlan._HOST:
        x, y = getattr(a, k), getattr(b, k)
        assert x.dtype == y.dtype, k
        np.testing.assert_array_equal(x, y, err_msg=k)
    assert a.lineage == b.lineage


class TestDeltaSegmentAddressing:
    """The delta reads the segment table only through the touched
    windows' run ranges; these pin it to the whole-table formulation."""

    @pytest.mark.parametrize("graph", ["scale_free", "erdos_renyi"])
    def test_helpers_match_full_table_formulation(self, graph):
        g = (
            scale_free(3500, 21000, seed=17)
            if graph == "scale_free"
            else erdos_renyi(3500, avg_degree=6.0, seed=17)
        ).drop_self_edges()
        w, _ = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        rng = np.random.default_rng(18)
        runs_of_window1 = {}
        for step, p in _delta_chain(plan, rng):
            for win in range(p.table_entries // WINDOW):
                rows = p._window_vreg_rows(win)
                idx = p._segments_of_rows(rows)
                old_idx = _old_segments_of_rows(p, rows)
                assert idx.dtype == old_idx.dtype, (step, win)
                np.testing.assert_array_equal(idx, old_idx, err_msg=f"{step} {win}")
                for a, b in zip(p._edges_of_segments(idx), _old_edges_of_segments(p, idx)):
                    assert a.dtype == b.dtype, (step, win)
                    np.testing.assert_array_equal(a, b, err_msg=f"{step} {win}")
                if win == 1:
                    runs_of_window1[step] = idx.size
            if step == "grow-window":
                # Window 0 spilled into overflow rows past the original packing.
                assert p.n_data_rows > plan.n_data_rows
                assert (p._window_vreg_rows(0) >= p.row_offset[-1]).any()
        assert runs_of_window1["clear-window"] == 0 < runs_of_window1["refill"]
        assert p.n == g.n + 2 * WINDOW

    @pytest.mark.parametrize("delta", ["replace_rows_chain", "explicit_deletes"])
    def test_delta_bit_identical_to_full_table_formulation(self, delta, monkeypatch):
        g = scale_free(3500, 21000, seed=19).drop_self_edges()
        w, _ = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        rng = np.random.default_rng(20)
        if delta == "replace_rows_chain":
            steps = []
            for i in range(5):
                rows = rng.choice(g.n, 12, replace=False)
                # One heavy row per epoch, so windows spill into overflow.
                deg = np.r_[rng.integers(1, 6, 11), 700 + 200 * i]
                ns = np.repeat(rows, deg).astype(np.int32)
                nd = rng.integers(0, g.n, ns.size).astype(np.int32)
                nw = rng.random(ns.size).astype(np.float32)
                steps.append(
                    lambda p, a=(rows, ns, nd, nw), i=i: p.replace_rows(
                        *a, fingerprint=f"fp{i}"
                    )
                )
        else:
            src, dst, _ = plan.recovered_edges()
            # Distinct plan instances, so a pair held twice may be named
            # twice (multiset delete).
            pick = rng.choice(src.size, 60, replace=False)
            dels = (src[pick], dst[pick])
            ins = (
                rng.integers(0, g.n, 40).astype(np.int32),
                rng.integers(0, g.n, 40).astype(np.int32),
                rng.random(40).astype(np.float32),
            )
            steps = [lambda p: p.apply_delta(ins, dels, fingerprint="explicit")]

        def chain():
            p, out = plan, []
            for step in steps:
                p = step(p)
                out.append(p)
            return out

        with monkeypatch.context() as mp:
            mp.setattr(WindowPlan, "_segments_of_rows", _old_segments_of_rows)
            mp.setattr(WindowPlan, "_edges_of_segments", _old_edges_of_segments)
            mp.setattr(WindowPlan, "apply_delta", _old_apply_delta)
            oracle = chain()
        changed = chain()
        if delta == "replace_rows_chain":
            assert oracle[-1].n_data_rows > plan.n_data_rows  # overflow rows in play
        for a, b in zip(changed, oracle):
            _assert_same_plan(a, b)


class TestManagerWarmState:
    def _manager(self, **kw):
        m = Manager(
            ManagerConfig(backend="tpu-windowed", prover="commitment", **kw)
        )
        m.generate_initial_attestations()
        return m

    def test_second_epoch_warm_starts_and_persists(self, tmp_path):
        m = self._manager()
        r1 = m.converge_epoch(Epoch(1), alpha=0.1)
        assert m.last_scores is not None and m.last_peer_hashes is not None
        assert len(m.last_peer_hashes) == len(r1.scores)
        prep = m.prepare_epoch(Epoch(2))
        assert prep.t0 is not None
        assert prep.t0.sum() == pytest.approx(1.0, rel=1e-6)
        # Remapped onto the same peer set: the warm seed IS the scores.
        np.testing.assert_allclose(prep.t0, r1.scores, atol=1e-9)
        r2 = m.converge_prepared(prep, alpha=0.1)
        np.testing.assert_allclose(r2.scores, r1.scores, rtol=1e-5)

    def test_warm_start_disabled_by_config(self):
        m = self._manager(warm_start=False)
        m.converge_epoch(Epoch(1), alpha=0.1)
        assert m.prepare_epoch(Epoch(2)).t0 is None

    def test_warm_t0_remaps_joins_and_leaves(self):
        m = self._manager()
        m.last_peer_hashes = [10, 20, 30]
        m.last_scores = np.array([0.5, 0.3, 0.2])
        # Peer 20 departed, peer 40 joined.
        t0 = m._warm_t0([10, 40, 30])
        np.testing.assert_allclose(t0, [0.5 / 0.7, 0.0, 0.2 / 0.7])
        # No overlap at all -> cold.
        assert m._warm_t0([7, 8]) is None

    def test_dirty_rows_feed_plan_delta(self):
        from tests.test_node import make_attestation

        m = self._manager(plan_delta_max_churn=1.0)
        m.converge_epoch(Epoch(1), alpha=0.1)
        assert not m._dirty_hashes  # consumed by the successful epoch
        plan1 = m.window_plan
        # Sender 0 re-attests with a different split: its row is dirty.
        att = make_attestation(sender_idx=0, scores=[400, 300, 150, 150, 0])
        m.add_attestation(att)
        assert m._dirty_hashes
        prep = m.prepare_epoch(Epoch(2))
        assert prep.delta_rows is not None and prep.delta_rows.size == 1
        before = PLAN_OUTCOMES.value(outcome="delta")
        m.converge_prepared(prep, alpha=0.1)
        assert PLAN_OUTCOMES.value(outcome="delta") == before + 1
        assert m.window_plan is not plan1
        assert plan1.fingerprint in m.window_plan.lineage
        assert not m._dirty_hashes

    def test_churn_threshold_disables_delta(self):
        from tests.test_node import make_attestation

        m = self._manager(plan_delta_max_churn=0.0)
        m.converge_epoch(Epoch(1), alpha=0.1)
        m.add_attestation(
            make_attestation(sender_idx=0, scores=[400, 300, 150, 150, 0])
        )
        assert m.prepare_epoch(Epoch(2)).delta_rows is None

    def test_checkpoint_restores_warm_state(self, tmp_path):
        m = self._manager()
        r1 = m.converge_epoch(Epoch(1), alpha=0.1)
        store = CheckpointStore(tmp_path)
        store.save(
            Epoch(1),
            m.last_graph,
            r1.scores,
            plan=m.window_plan,
            peer_hashes=m.last_peer_hashes,
        )
        snap = store.load_latest()
        assert snap.peer_hashes == m.last_peer_hashes
        # A fresh manager (reboot) seeded from the snapshot warm starts.
        m2 = self._manager()
        m2.last_scores = snap.scores
        m2.last_peer_hashes = snap.peer_hashes
        m2.window_plan = snap.plan
        prep = m2.prepare_epoch(Epoch(2))
        assert prep.t0 is not None
        np.testing.assert_allclose(prep.t0, r1.scores, atol=1e-9)


class TestPeerAlignCounter:
    """``eigentrust_peer_align_total``: each warm epoch aligns the
    previous peer column once at the warm seed and once in the drift
    monitor, by the prefix path while ids only grow."""

    SITES = [(site, path) for site in ("warm_seed", "drift") for path in ("prefix", "search")]

    def _counts(self):
        return {(s, p): PEER_ALIGN.value(site=s, path=p) for s, p in self.SITES}

    def _since(self, before):
        now = self._counts()
        return {k: now[k] - before[k] for k in self.SITES}

    def _only(self, path, n):
        return {(s, p): n if p == path else 0 for s, p in self.SITES}

    def test_benchmark_shaped_loop_counts_prefix_every_warm_epoch(self):
        DRIFT.reset()
        g = erdos_renyi(400, avg_degree=4.0, seed=5)
        m = Manager(ManagerConfig(backend="tpu-csr", prover="commitment"))
        ids = list(range(g.n))
        before = self._counts()
        for number in range(1, 5):
            t0 = m._warm_t0(ids)
            assert (t0 is None) == (number == 1)
            prepared = PreparedEpoch(Epoch(number), g, ids, t0, None, set())
            m.converge_prepared(prepared, alpha=0.1)
        assert self._since(before) == self._only("prefix", 3)

    def test_growing_attestation_set_counts_prefix(self):
        import random

        from protocol_tpu.crypto.eddsa import SecretKey
        from tests.test_node import make_attestation

        DRIFT.reset()
        m = Manager(ManagerConfig(backend="tpu-csr", prover="commitment"))
        m.generate_initial_attestations()
        m.converge_epoch(Epoch(1), alpha=0.1)
        att = make_attestation(sender_idx=0)
        att.pk = SecretKey.random(random.Random(28)).public()  # a sender new to the graph
        m.restore_attestation(att)
        before = self._counts()
        prepared = m.prepare_epoch(Epoch(2))
        assert prepared.id_order[:-1] == m.last_peer_hashes
        m.converge_prepared(prepared, alpha=0.1)
        assert self._since(before) == self._only("prefix", 1)
        assert DRIFT.last()["joined"] == 1 and DRIFT.last()["departed"] == 0

    def test_reattestation_reorders_the_node_column_and_counts_search(self):
        """``build_graph`` rebuilds first-seen order: a sender that drops
        the peer first seen through its row moves that peer, so the
        node's column is no prefix and both sites take the search,
        landing each surviving peer's score on that peer."""
        import dataclasses
        import random

        from protocol_tpu.crypto.eddsa import SecretKey
        from tests.test_node import make_attestation

        rng = random.Random(28)
        a, b, x, z = (SecretKey.random(rng).public() for _ in range(4))
        base = make_attestation(sender_idx=0)

        def attest(sender, *targets):
            return dataclasses.replace(
                base, pk=sender, neighbours=list(targets), scores=[50] * len(targets)
            )

        DRIFT.reset()
        m = Manager(ManagerConfig(backend="tpu-csr", prover="commitment"))
        m.generate_initial_attestations()
        m.restore_attestation(attest(a, x))  # x first seen through a's row
        m.restore_attestation(attest(b, x, a))
        m.converge_epoch(Epoch(1), alpha=0.1)
        old = list(m.last_peer_hashes)
        old_scores = np.asarray(m.last_scores, np.float64)
        m.restore_attestation(attest(a, z))  # a keeps its place, drops x
        before = self._counts()
        prepared = m.prepare_epoch(Epoch(2))
        new = prepared.id_order
        assert new[: len(old)] != old and sorted(set(old) - set(new)) == []
        m.converge_prepared(prepared, alpha=0.1)
        assert self._since(before) == self._only("search", 1)
        want = np.zeros(len(new))
        for i, h in enumerate(new):
            if h in old:
                want[i] = max(old_scores[old.index(h)], 0.0)
        np.testing.assert_array_equal(prepared.t0, want / want.sum())
        assert DRIFT.last()["joined"] == 1 and DRIFT.last()["departed"] == 0

    def test_shuffled_column_counts_search(self):
        import threading
        import types

        DRIFT.reset()
        ids = list(range(50))
        shuffled = [int(h) for h in np.random.default_rng(28).permutation(50)]
        state = types.SimpleNamespace(
            _state_lock=threading.Lock(), last_scores=np.ones(50), last_peer_hashes=ids
        )
        before = self._counts()
        assert Manager._warm_t0(state, shuffled) is not None
        DRIFT.observe(1, ids, np.ones(50))
        DRIFT.observe(2, shuffled, np.ones(50))
        assert self._since(before) == self._only("search", 1)


class TestEpochPipeline:
    def _manager(self):
        m = Manager(ManagerConfig(backend="tpu-sparse", prover="commitment"))
        m.generate_initial_attestations()
        return m

    def test_sequential_epochs_warm_start(self):
        m = self._manager()
        with EpochPipeline(m, alpha=0.1) as pipe:
            pipe.submit(Epoch(1))
            assert pipe.drain(60)
            pipe.submit(Epoch(2))
            assert pipe.drain(60)
        o1, o2 = pipe.outcomes[1], pipe.outcomes[2]
        assert o1.error is None and o2.error is None
        assert o2.result.iterations <= o1.result.iterations
        assert pipe.coalesced == 0 and pipe.completed == 2

    def test_backpressure_coalesces_instead_of_dropping(self):
        from protocol_tpu.obs import metrics as obs_metrics

        m = self._manager()

        def slow_stage(prepared):
            time.sleep(0.5)
            return m.converge_prepared(prepared, alpha=0.1)

        before = obs_metrics.EPOCH_TICKS_COALESCED.value()
        with EpochPipeline(m, device_stage=slow_stage, queue_depth=1) as pipe:
            for k in range(1, 6):
                pipe.submit(Epoch(k))
                time.sleep(0.05)
            assert pipe.drain(60)
        assert pipe.coalesced >= 1
        # Every tick is accounted for: it either ran or was coalesced.
        assert pipe.completed + pipe.coalesced == 5
        # The NEWEST epoch always lands (coalescing supersedes, never
        # drops the head of the line).
        assert 5 in pipe.outcomes
        assert (
            obs_metrics.EPOCH_TICKS_COALESCED.value() - before == pipe.coalesced
        )

    def test_device_failure_does_not_kill_the_pipeline(self):
        m = self._manager()
        calls = []

        def flaky_stage(prepared):
            calls.append(prepared.epoch.number)
            if prepared.epoch.number == 1:
                raise RuntimeError("prover exploded")
            return m.converge_prepared(prepared, alpha=0.1)

        with EpochPipeline(m, device_stage=flaky_stage) as pipe:
            pipe.submit(Epoch(1))
            assert pipe.drain(60)
            pipe.submit(Epoch(2))
            assert pipe.drain(60)
        assert isinstance(pipe.outcomes[1].error, RuntimeError)
        assert pipe.outcomes[2].error is None
        assert calls == [1, 2]

    def test_failed_epoch_keeps_dirty_accounting(self):
        """A failed device stage must not clear the dirty set — the
        next epoch's delta hint still covers the missed churn."""
        from tests.test_node import make_attestation

        m = Manager(
            ManagerConfig(
                backend="tpu-windowed", prover="commitment", plan_delta_max_churn=1.0
            )
        )
        m.generate_initial_attestations()
        m.converge_epoch(Epoch(1), alpha=0.1)
        m.add_attestation(
            make_attestation(sender_idx=1, scores=[0, 500, 300, 100, 100])
        )
        dirty = set(m._dirty_hashes)
        assert dirty
        prep = m.prepare_epoch(Epoch(2))
        # Simulate the device stage dying before converge finished.
        assert m._dirty_hashes == dirty
        # The retry (next epoch) still sees the churn.
        prep3 = m.prepare_epoch(Epoch(3))
        assert prep3.delta_rows is not None
        m.converge_prepared(prep3, alpha=0.1)
        assert not m._dirty_hashes


class TestPipelinedNode:
    def test_node_ticks_through_pipeline(self):
        """Full node wiring: `"epoch_pipeline": true` routes epoch
        boundaries through the double-buffered engine; the second tick
        warm starts and the shutdown drains in-flight work."""
        import asyncio

        from protocol_tpu.node.config import ProtocolConfig
        from protocol_tpu.node.server import Node
        from protocol_tpu.obs import TRACER

        async def scenario():
            cfg = ProtocolConfig(
                epoch_interval=1,
                endpoint=((127, 0, 0, 1), 0),
                prover="commitment",
                trust_backend="tpu-sparse",
                epoch_pipeline=True,
            )
            node = Node.from_config(cfg)
            await node.start()
            assert node._pipeline is not None
            deadline = 60.0
            while node._pipeline.completed < 2 and deadline > 0:
                await asyncio.sleep(0.2)
                deadline -= 0.2
            await node.stop()
            return node

        node = asyncio.run(scenario())
        assert node._pipeline.completed >= 2
        assert node.manager.last_scores is not None  # warm state advanced
        assert TRACER.latest_epoch() is not None

    def test_config_parses_pipeline_fields(self):
        from protocol_tpu.node.config import ProtocolConfig

        cfg = ProtocolConfig.from_json(
            '{"epoch_pipeline": true, "warm_start": false, '
            '"plan_delta_max_churn": 0.2}'
        )
        assert cfg.epoch_pipeline is True
        assert cfg.warm_start is False
        assert cfg.plan_delta_max_churn == 0.2
        base = ProtocolConfig.from_json("{}")
        assert base.epoch_pipeline is False and base.warm_start is True


class TestBenchEpochs:
    @pytest.mark.slow
    def test_epochs_entry_smoke(self):
        import bench

        entry = bench.epochs_entry(
            epochs=3, churn=0.02, n_peers=4000, n_edges=24000, max_iter=40
        )
        assert entry["steady_state_epoch_seconds"] > 0
        assert entry["cold_epoch_seconds"] > 0
        assert entry["iterations_saved_by_warm_start"] > 0
        assert entry["warm_vs_cold_l1"] < 1e-4
        assert entry["plan_outcomes"]["delta"] >= 1
        assert len(entry["per_epoch"]) == 2


class TestRecompileTracker:
    """ISSUE 6: PR 5's stable-shape guarantee, *watched* instead of
    asserted — steady-state delta epochs must report zero jit cache
    misses; a shape-changing epoch must report exactly one."""

    def test_steady_state_delta_epochs_report_zero_recompiles(self):
        from protocol_tpu.obs import metrics as obs_metrics
        from protocol_tpu.obs.watchers import RECOMPILES

        rng = np.random.default_rng(7)
        g = scale_free(1500, 9000, seed=11).drop_self_edges()
        b = get_backend("tpu-windowed")
        prev = b.converge(g, alpha=0.1, tol=1e-6, max_iter=60)  # cold compile
        scores = prev.scores
        cur = g
        counter_before = obs_metrics.JIT_RECOMPILES.value(fn="converge_windowed")
        for k in range(3):
            cur, rows = churn_graph(cur, 0.01, rng)
            b.delta_rows = rows
            snap = RECOMPILES.snapshot()
            res = b.converge(cur, alpha=0.1, tol=1e-6, max_iter=60, t0=scores)
            misses = RECOMPILES.observe(snap, steady_state=True, epoch=k)
            assert misses == {}, (
                f"steady-state delta epoch {k} recompiled: {misses}"
            )
            scores = res.scores
        # The delta epochs really took the delta path (shape-stable).
        assert obs_metrics.JIT_RECOMPILES.value(fn="converge_windowed") == (
            counter_before
        )

    def test_shape_changing_epoch_reports_exactly_one(self):
        from protocol_tpu.obs import metrics as obs_metrics
        from protocol_tpu.obs.watchers import RECOMPILES

        # A peer-count no other test in this module uses: guaranteed
        # novel device shapes for converge_windowed.
        g = scale_free(1777, 9300, seed=23).drop_self_edges()
        b = get_backend("tpu-windowed")
        counter_before = obs_metrics.JIT_RECOMPILES.value(fn="converge_windowed")
        snap = RECOMPILES.snapshot()
        b.converge(g, alpha=0.1, tol=1e-6, max_iter=30)
        misses = RECOMPILES.observe(snap, steady_state=False, epoch=0)
        assert misses.get("converge_windowed") == 1, misses
        assert obs_metrics.JIT_RECOMPILES.value(fn="converge_windowed") == (
            counter_before + 1
        )

    def test_registered_entry_points(self):
        from protocol_tpu.obs.watchers import RECOMPILES

        names = RECOMPILES.registered()
        for fn in ("converge_csr", "converge_sparse", "converge_windowed"):
            assert fn in names, names
