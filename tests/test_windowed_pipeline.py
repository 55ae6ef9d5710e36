"""Fused fixed-slot pipeline (PERF.md §7-8) — interpret-mode
equivalence of the ``tpu-windowed`` backend (single-device and sharded
across the 8-device CPU mesh) against ``tpu-csr``/``native-cpu``,
``bucket_by_window`` layout properties including the single-pass
boundary bridge, the one-random-gather acceptance bound, and WindowPlan
persistence/versioning through the checkpoint store.

Everything runs under the conftest CPU platform: the Pallas kernel
executes in interpret mode (the identical lowered computation, minus
Mosaic codegen), which is the test doctrine PERF.md §6 establishes for
the windowed gather.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from protocol_tpu.analysis.jaxpr_walk import collect_gathers
from protocol_tpu.models.graphs import erdos_renyi, scale_free
from protocol_tpu.node.checkpoint import CheckpointStore
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.ops.gather_window import (
    PLAN_VERSION,
    ROW,
    WINDOW,
    WindowPlan,
    bridge_partials,
    bucket_by_window,
    build_window_plan,
    graph_fingerprint,
    power_step_windowed,
)
from protocol_tpu.trust.backend import WindowedJaxBackend, get_backend, normalize_graph
from protocol_tpu.trust.graph import TrustGraph


def drop_out_edges(g: TrustGraph, peers) -> TrustGraph:
    """Make ``peers`` dangling by removing every edge they send."""
    keep = ~np.isin(g.src, np.asarray(peers, dtype=np.int32))
    return TrustGraph(g.n, g.src[keep], g.dst[keep], g.weight[keep], g.pre_trusted)


def l1(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).sum())


class TestWindowedBackendEquivalence:
    """Acceptance: tpu-windowed matches tpu-csr to ≤1e-5 L1 in CPU
    interpret mode, including dangling rows and non-aligned N."""

    def test_matches_csr_erdos_renyi_non_aligned(self):
        # 773 peers: not divisible by WINDOW (table padding in play),
        # plus forced dangling rows (out-edge-free peers).
        g = drop_out_edges(erdos_renyi(773, avg_degree=5.0, seed=1), [0, 17, 772])
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        win = get_backend("tpu-windowed").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        assert l1(win.scores, csr.scores) <= 1e-5
        assert win.backend == "tpu-windowed"
        assert win.scores.sum() == pytest.approx(1.0, rel=1e-5)

    def test_matches_csr_scale_free(self):
        g = scale_free(1500, 9000, seed=2)
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        win = get_backend("tpu-windowed").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        assert l1(win.scores, csr.scores) <= 1e-5

    def test_matches_csr_multi_window(self):
        # n > WINDOW so the kernel resolves across several table windows.
        g = drop_out_edges(scale_free(3 * WINDOW + 137, 20000, seed=3), [5, 2048])
        csr = get_backend("tpu-csr").converge(g, alpha=0.15, tol=0, max_iter=30)
        win = get_backend("tpu-windowed").converge(g, alpha=0.15, tol=0, max_iter=30)
        assert l1(win.scores, csr.scores) <= 1e-5
        assert win.iterations == 30  # fixed-iter mode drives the same driver

    def test_matches_exact_native(self):
        g = erdos_renyi(40, avg_degree=4.0, seed=2)
        exact = get_backend("native-cpu").converge(g, alpha=0.15, tol=0, max_iter=25)
        win = get_backend("tpu-windowed").converge(g, alpha=0.15, tol=0, max_iter=25)
        assert l1(win.scores, exact.scores) <= 1e-5

    def test_plan_reuse_and_rebuild(self):
        g = erdos_renyi(600, avg_degree=5.0, seed=4)
        backend = WindowedJaxBackend()
        backend.converge(g, alpha=0.1, max_iter=10)
        plan_first = backend.last_plan
        backend.converge(g, alpha=0.1, max_iter=10)
        assert backend.last_plan is plan_first  # fingerprint hit: no rebuild
        g2 = erdos_renyi(600, avg_degree=5.0, seed=5)
        backend.converge(g2, alpha=0.1, max_iter=10)
        assert backend.last_plan is not plan_first  # graph changed: rebuilt

    def test_registry_constructs_windowed(self):
        assert get_backend("tpu-windowed").name == "tpu-windowed"


class TestBucketByWindowProperties:
    def _random_edges(self, seed, n=3000, e=20000):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, e).astype(np.int32)
        dst = rng.integers(0, n, e).astype(np.int32)
        w = rng.random(e, dtype=np.float32)
        return n, src, dst, w

    def test_order_out_pos_round_trip(self):
        """``order``/``out_pos`` reconstruct the input edge list exactly:
        slot out_pos[k] carries edge order[k]'s window-local src and
        weight, every other slot is zero-weight padding."""
        n, src, dst, w = self._random_edges(7)
        b = bucket_by_window(src, w, table_size=n)
        order, out_pos = b["order"], b["out_pos"]
        assert sorted(order.tolist()) == list(range(len(src)))  # permutation
        local = b["local"].reshape(-1)
        weight = b["weight"].reshape(-1)
        np.testing.assert_array_equal(local[out_pos], src[order] % WINDOW)
        np.testing.assert_array_equal(weight[out_pos], w[order])
        pad = np.ones(weight.shape[0], bool)
        pad[out_pos] = False
        assert not weight[pad].any()
        # Each slot's global src index re-derives from wid + local.
        wid = np.repeat(b["wid"], WINDOW)
        np.testing.assert_array_equal(
            (wid[out_pos].astype(np.int64) * WINDOW + local[out_pos]), src[order]
        )

    def test_rejects_out_of_range_src(self):
        n, src, dst, w = self._random_edges(8, n=500, e=100)
        bad = src.copy()
        bad[3] = 500  # == table_size
        with pytest.raises(ValueError, match="src index"):
            bucket_by_window(bad, w, table_size=500)
        neg = src.copy()
        neg[0] = -1
        with pytest.raises(ValueError, match="src index"):
            bucket_by_window(neg, w, table_size=500)

    def test_rejects_out_of_range_dst(self):
        n, src, dst, w = self._random_edges(9, n=500, e=100)
        bad = dst.copy()
        bad[5] = 700
        with pytest.raises(ValueError, match="dst index"):
            bucket_by_window(src, w, table_size=500, dst=bad, n_dst=500)
        with pytest.raises(ValueError, match="n_dst"):
            bucket_by_window(src, w, table_size=500, dst=dst)

    def test_segment_plan_reduces_exactly(self):
        """Run sums over the slots the run-start flags delimit, read at
        the dst-order run ends — the device's recipe
        (``bridge_partials``) emulated in f64 — reproduce the direct
        per-dst sum of w·x[src] on random graphs: the flags plus the
        one dst-order read are a faithful reduction plan."""
        n, src, dst, w = self._random_edges(10)
        b = bucket_by_window(src, w, table_size=n, dst=dst, n_dst=n)
        rng = np.random.default_rng(11)
        x = rng.random(n).astype(np.float32)
        contrib = np.zeros(b["n_rows"] * WINDOW, np.float64)
        contrib[b["out_pos"]] = (w[b["order"]].astype(np.float64)
                                 * x[src[b["order"]]].astype(np.float64))
        # Each slot's run sum: the segmented scan's value at a run end.
        run_id = np.cumsum(b["run_start"].reshape(-1)) - 1
        run_sum = np.bincount(run_id, weights=contrib)[run_id]
        partial = run_sum[b["run_end"]]
        ptr = b["dst_ptr"].astype(np.int64)
        per_dst = np.add.reduceat(
            np.concatenate([partial, [0.0]]), np.minimum(ptr[:-1], len(partial))
        )
        per_dst[ptr[:-1] == ptr[1:]] = 0.0
        expect = np.zeros(n)
        np.add.at(expect, dst, w.astype(np.float64) * x[src].astype(np.float64))
        np.testing.assert_allclose(per_dst, expect, rtol=1e-5, atol=1e-12)

    def test_segment_plan_layout_invariants(self):
        """Layout invariants the bridge relies on: strictly increasing
        bucket-order run ends, a row-leading flag exactly at vreg-row
        changes, and ``run_end`` the same ends in dst order."""
        n, src, dst, w = self._random_edges(12)
        b = bucket_by_window(src, w, table_size=n, dst=dst, n_dst=n)
        seg_end, seg_first = b["seg_end"], b["seg_first"]
        assert (np.diff(seg_end.astype(np.int64)) > 0).all()
        rows = seg_end // ROW
        expect_first = np.empty(len(seg_end), bool)
        expect_first[0] = True
        expect_first[1:] = rows[1:] != rows[:-1]
        np.testing.assert_array_equal(seg_first, expect_first)
        np.testing.assert_array_equal(np.sort(b["run_end"]), seg_end)
        run_dst = b["seg_dst"][np.searchsorted(seg_end, b["run_end"])]
        assert (np.diff(run_dst) >= 0).all()
        assert int(b["dst_ptr"][-1]) == b["n_segments"]


    @pytest.mark.parametrize("mean_run", [1.2, 5.0, 300.0])
    def test_bridge_partials_sums_each_run(self, mean_run):
        """The device's segmented scan sums exactly the slots of each
        run — runs of one slot, short runs, and runs crossing the
        scan's every shift up to a whole row — and reads the sums out
        in the order ``run_end`` gives."""
        rng = np.random.default_rng(int(mean_run * 10))
        n_rows = 3
        slots = n_rows * ROW
        out = rng.random(slots).astype(np.float32)
        lengths = rng.geometric(1.0 / mean_run, size=slots)
        starts = np.unique(np.concatenate([np.cumsum(lengths) % slots, [0]]))
        flags = np.zeros(slots, bool)
        flags[starts] = True
        flags[::ROW] = True
        ends = np.flatnonzero(np.append(flags[1:], True))
        read = rng.permutation(ends).astype(np.int32)
        got = jax.jit(bridge_partials)(
            jnp.asarray(out.reshape(n_rows * 8, 128)),
            jnp.asarray(flags.reshape(n_rows * 8, 128)),
            jnp.asarray(read),
        )
        run_id = np.cumsum(flags) - 1
        run_sum = np.bincount(run_id, weights=out.astype(np.float64))[run_id]
        np.testing.assert_allclose(np.asarray(got), run_sum[read], rtol=2e-6)


class TestSinglePassBoundary:
    """ISSUE 2 acceptance: per-iteration boundary random volume in
    ``power_step_windowed`` is ONE n_segments-sized random gather."""

    def test_one_random_segment_gather_in_step(self):
        g = scale_free(1500, 9000, seed=2).drop_self_edges()
        w, dangling = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        p = g.pre_trust_vector()
        args = plan.device_args() + (
            jnp.asarray(p),
            jnp.asarray(p),
            jnp.asarray(dangling.astype(np.float32)),
            jax.device_put(np.float32(0.1)),
        )
        jaxpr = jax.make_jaxpr(
            lambda *a: power_step_windowed(
                *a,
                **plan.static_kwargs(),
                interpret=True,
            )
        )(*args)
        # The shared recursive walker (protocol_tpu.analysis.jaxpr_walk)
        # — the analyzer gate counts gathers with exactly this traversal.
        gathers = collect_gathers(jaxpr.jaxpr)
        # Device segment tables run at padded capacity (>= n_segments
        # live runs) so per-epoch deltas keep the compiled shape.
        s = plan.seg_capacity
        assert s != plan.n + 1  # keep the rowsum gathers distinguishable
        seg_sized = [e for e in gathers if e.outvars[0].aval.shape[:1] == (s,)]
        random_seg = [
            e for e in seg_sized if not e.params.get("indices_are_sorted")
        ]
        # Exactly one n_segments-sized gather: the run sums read out in
        # dst order — the one random pass the tentpole allows.
        assert len(seg_sized) == 1
        assert len(random_seg) == 1
        assert seg_sized[0].outvars[0].aval.shape == (s,)


class TestWindowPlanCheckpoint:
    def _plan(self, seed=12, n=900):
        g = scale_free(n, 5000, seed=seed).drop_self_edges()
        w, _ = g.row_normalized()
        return build_window_plan(g.src, g.dst, w, n=g.n)

    def test_round_trips_through_store(self, tmp_path):
        plan = self._plan()
        g = erdos_renyi(30, seed=13)
        store = CheckpointStore(tmp_path)
        store.save(Epoch(9), g, plan=plan)
        snap = store.load_latest()
        assert snap.plan is not None
        assert snap.plan.fingerprint == plan.fingerprint
        assert snap.plan.version == PLAN_VERSION
        assert (snap.plan.n, snap.plan.n_rows) == (plan.n, plan.n_rows)
        assert (snap.plan.table_entries, snap.plan.n_segments) == (
            plan.table_entries,
            plan.n_segments,
        )
        for k in WindowPlan._CORE:
            np.testing.assert_array_equal(getattr(snap.plan, k), getattr(plan, k))
        # Checkpoints persist only the core arrays (order/out_pos are
        # test/diagnostic-only and E-sized).
        assert snap.plan.order is None and snap.plan.out_pos is None

    def test_stale_plan_version_rejected_and_tolerated(self, tmp_path):
        """A v1-era sidecar (no ``version`` key, pre-interleave arrays)
        must not rehydrate: ``from_arrays`` raises, and the store
        degrades to ``plan=None`` so the next converge rebuilds."""
        plan = self._plan()
        g = erdos_renyi(30, seed=13)
        store = CheckpointStore(tmp_path)
        store.save(Epoch(3), g, plan=plan)
        # Rewrite the sidecar as an old-format plan: strip the version
        # stamp (v1 files never had one).
        arrays = plan.to_arrays(core_only=True)
        del arrays["version"]
        np.savez(tmp_path / "epoch_3.plan.npz", **arrays)
        with np.load(tmp_path / "epoch_3.plan.npz") as z:
            with pytest.raises(ValueError, match="stale"):
                WindowPlan.from_arrays(z)
        snap = store.load_latest()
        assert snap.plan is None  # graph snapshot still served
        assert snap.graph.n == g.n

    def test_stale_version_plan_triggers_rebuild(self):
        """A fingerprint-valid plan carrying an old layout version is
        rebuilt, not fed to the device."""
        g = scale_free(900, 5000, seed=12).drop_self_edges()
        w, _ = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        stale = dataclasses.replace(plan, version=1)
        backend = WindowedJaxBackend(plan=stale)
        backend.converge(g, alpha=0.1, tol=1e-9, max_iter=5)
        assert backend.last_plan is not stale
        assert backend.last_plan.version == PLAN_VERSION

    def test_restored_plan_skips_rebuild(self, tmp_path, monkeypatch):
        g = scale_free(900, 5000, seed=12).drop_self_edges()
        w, _ = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)
        store = CheckpointStore(tmp_path)
        store.save(Epoch(1), g, plan=plan)
        restored = store.load_latest().plan

        import protocol_tpu.ops.gather_window as gather_window_mod

        def boom(*a, **k):  # a fingerprint hit must not reconstruct
            raise AssertionError("plan rebuilt despite checkpoint restore")

        monkeypatch.setattr(gather_window_mod, "build_window_plan", boom)
        backend = WindowedJaxBackend(plan=restored)
        res = backend.converge(g, alpha=0.1, tol=1e-9, max_iter=40)
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=40)
        assert l1(res.scores, csr.scores) <= 1e-5

    def test_prune_removes_plan_files(self, tmp_path):
        store = CheckpointStore(tmp_path, keep=1)
        g = erdos_renyi(30, seed=14)
        plan = self._plan()
        store.save(Epoch(1), g, plan=plan)
        store.save(Epoch(2), g, plan=plan)
        assert not (tmp_path / "epoch_1.plan.npz").exists()
        assert (tmp_path / "epoch_2.plan.npz").exists()

    def test_no_plan_is_fine(self, tmp_path):
        store = CheckpointStore(tmp_path)
        store.save(Epoch(4), erdos_renyi(10, seed=15))
        assert store.load_latest().plan is None

    def test_fingerprint_sensitivity(self):
        g = scale_free(700, 4000, seed=16).drop_self_edges()
        w, _ = g.row_normalized()
        fp = graph_fingerprint(g.n, g.src, g.dst, w.__getitem__)
        assert fp == graph_fingerprint(g.n, g.src, g.dst, w.__getitem__)  # deterministic
        w2 = w.copy()
        w2[0] += 0.5
        assert fp != graph_fingerprint(g.n, g.src, g.dst, w2.__getitem__)
        assert fp != graph_fingerprint(g.n + 1, g.src, g.dst, w.__getitem__)


def _rewritten(g: TrustGraph, rows, per_row: int, seed: int) -> TrustGraph:
    """``g`` with the out-rows of ``rows`` re-attested, ``per_row``
    edges each (a self-edge among them is dropped by normalization)."""
    rng = np.random.default_rng(seed)
    rows = np.asarray(rows, np.int32)
    keep = ~np.isin(g.src, rows)
    k = per_row * rows.size
    return TrustGraph(
        g.n,
        np.concatenate([g.src[keep], np.repeat(rows, per_row)]),
        np.concatenate([g.dst[keep], rng.integers(0, g.n, k).astype(np.int32)]),
        np.concatenate([g.weight[keep], rng.integers(1, 100, k).astype(np.float32)]),
        g.pre_trusted,
    )


class TestWeightsOnDemand:
    """The windowed backend divides per-edge weights where the plan
    reads them: a delta epoch its re-attested rows and the fingerprint
    its samples, a rebuild the whole array.  The plans, fingerprints,
    scores and iteration counts equal those of the same chain read
    through the whole array."""

    OUTCOMES = ("reuse", "delta", "rebuild")
    PATHS = ("rows", "full")

    def _chain(self, monkeypatch):
        """cold, three deltas, a reuse, then a delta past the plan's
        spare rows that falls back to a rebuild: per epoch the outcome,
        the counter's growth by path, the normalized graph, the plan and
        the result."""
        import protocol_tpu.trust.backend as backend_mod

        from protocol_tpu.obs.metrics import EDGE_WEIGHTS, PLAN_OUTCOMES

        seen = []

        def recording(graph):
            ng = normalize_graph(graph)
            seen.append(ng)
            return ng

        monkeypatch.setattr(backend_mod, "normalize_graph", recording)
        g = erdos_renyi(2048, avg_degree=6.0, seed=41)
        backend = WindowedJaxBackend()
        out = []
        hints = [None, [7, 1500], [100, 2000], [5, 1024, 1025], None, None]
        for epoch, rows in enumerate(hints):
            if epoch in (1, 2, 3):
                g = _rewritten(g, rows, 6, seed=epoch)
            elif epoch == 4:  # the same graph again
                g = TrustGraph(g.n, g.src, g.dst, g.weight, g.pre_trusted)
            elif epoch == 5:  # more edges in window 0 than the plan has rows
                plan = backend.last_plan
                rows = np.arange(0, 100)
                g = _rewritten(g, rows, -(-(plan.n_rows + 1) * ROW // 100), seed=epoch)
            before = (
                {o: PLAN_OUTCOMES.value(outcome=o) for o in self.OUTCOMES},
                {p: EDGE_WEIGHTS.value(path=p) for p in self.PATHS},
            )
            backend.delta_rows = None if rows is None else np.asarray(rows)
            result = backend.converge(g, alpha=0.1, tol=1e-7, max_iter=30)
            (outcome,) = [
                o for o in self.OUTCOMES if PLAN_OUTCOMES.value(outcome=o) > before[0][o]
            ]
            grew = {p: EDGE_WEIGHTS.value(path=p) - before[1][p] for p in self.PATHS}
            out.append((outcome, grew, seen[-1], backend.last_plan, result))
        return out

    def test_delta_chain_divides_only_what_the_plan_reads(self, monkeypatch):
        from protocol_tpu.trust.graph import NormalizedGraph

        chain = self._chain(monkeypatch)
        assert [c[0] for c in chain] == [
            "rebuild", "delta", "delta", "delta", "reuse", "rebuild"
        ]
        for outcome, grew, ng, _, _ in chain:
            if outcome == "rebuild":
                assert grew["full"] == 1 and "w" in ng.__dict__
            else:  # the fingerprint's samples, and a delta's rows
                assert grew == {"rows": 1 + (outcome == "delta"), "full": 0}
                assert "w" not in ng.__dict__

        monkeypatch.setattr(NormalizedGraph, "weights_of", lambda self, sel: self.w[sel])
        whole = self._chain(monkeypatch)
        for (_, _, ng, plan, res), (_, _, ng_w, plan_w, res_w) in zip(chain, whole):
            assert ng.fingerprint == ng_w.fingerprint == plan.fingerprint
            for k in WindowPlan._CORE + WindowPlan._HOST:
                a, b = getattr(plan, k), getattr(plan_w, k)
                assert (a is None and b is None) or np.array_equal(a, b), k
            assert {k: getattr(plan, k) for k in WindowPlan._META} == {
                k: getattr(plan_w, k) for k in WindowPlan._META
            }
            assert res.scores.tobytes() == res_w.scores.tobytes()
            assert res.iterations == res_w.iterations


class TestShardedWindowedBackend:
    """ISSUE 2 acceptance: ``converge_sharded`` exposes a working
    ``tpu-windowed`` kernel matching ``converge_csr`` within renorm
    tolerance on the 8-device CPU mesh — with dangling rows,
    shard-straddling dst rows, and non-aligned N."""

    def _graph(self):
        # Non-WINDOW-aligned N across several table windows, enough
        # edges that the window rows span multiple shards (>64 data
        # vreg-rows), and forced dangling peers.
        g = scale_free(2 * WINDOW + 901, 70_000, seed=31)
        return drop_out_edges(g, [3, 700, 2948])

    def test_matches_csr_on_8_device_mesh(self):
        g = self._graph()
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=40)
        shw = get_backend("tpu-sharded:tpu-windowed").converge(
            g, alpha=0.1, tol=1e-9, max_iter=40
        )
        assert l1(shw.scores, csr.scores) <= 1e-5
        assert shw.backend == "tpu-sharded:tpu-windowed"
        assert shw.scores.sum() == pytest.approx(1.0, rel=1e-5)

    def test_partition_straddles_shards(self):
        """The row partition genuinely spreads data over several shards
        and splits at least one destination's runs across a shard cut —
        the case the psum must complete."""
        from protocol_tpu.parallel.mesh import default_mesh
        from protocol_tpu.parallel.sharded import ShardedWindowPlan

        swp = ShardedWindowPlan.build(normalize_graph(self._graph()), default_mesh())
        dst_ptr = np.asarray(swp.dst_ptr)  # (n_shards, n+1)
        runs_per_shard = dst_ptr[:, -1]
        assert (runs_per_shard > 0).sum() >= 2, runs_per_shard
        per_dst_per_shard = np.diff(dst_ptr, axis=1)  # (n_shards, n)
        straddling = ((per_dst_per_shard > 0).sum(axis=0) >= 2).sum()
        assert straddling > 0
        # Every shard's rebased run ends stay inside its row slice.
        run_end = np.asarray(swp.run_end).reshape(len(runs_per_shard), -1)
        assert run_end.min() >= 0
        assert run_end.max() < swp.rows_per_shard * ROW

    def test_explicit_small_mesh(self):
        from protocol_tpu.parallel.mesh import default_mesh

        g = scale_free(600, 4000, seed=33)
        res = get_backend("tpu-sharded", mesh=default_mesh(4), kernel="tpu-windowed")
        out = res.converge(g, alpha=0.1, max_iter=20)
        assert out.scores.shape == (600,)
        assert out.scores.sum() == pytest.approx(1.0, rel=1e-5)

    def test_restored_plan_skips_rebuild(self, monkeypatch):
        """A fingerprint-valid WindowPlan seeds the sharded build — the
        checkpoint-restore path shared with the single-device backend."""
        import protocol_tpu.ops.gather_window as gather_window_mod

        g = self._graph().drop_self_edges()
        w, _ = g.row_normalized()
        plan = build_window_plan(g.src, g.dst, w, n=g.n)

        def boom(*a, **k):
            raise AssertionError("plan rebuilt despite valid seed")

        monkeypatch.setattr(gather_window_mod, "build_window_plan", boom)
        backend = get_backend("tpu-sharded:tpu-windowed")
        backend.plan = plan
        res = backend.converge(g, alpha=0.1, tol=1e-9, max_iter=20)
        assert backend.last_plan is plan
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=20)
        assert l1(res.scores, csr.scores) <= 1e-5

    def test_rejects_unknown_kernel(self):
        with pytest.raises(ValueError, match="unknown sharded kernel"):
            get_backend("tpu-sharded:bogus")
        with pytest.raises(ValueError, match="unknown trust backend"):
            get_backend("tpu-csr:tpu-windowed")
