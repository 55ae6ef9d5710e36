"""The windowed gather streams a score table longer than one VMEM chunk.

``TABLE_CHUNK`` is cut to a few windows here, so a graph of a few
thousand ids spans two to four chunks, and the Pallas kernel runs in
interpret mode: the streamed gather against a plain table lookup, the
layout rule that no grid block reads two chunks (after a rebuild and
after deltas), delta against rebuild, and the node's windowed and
sharded windowed backends on a seeded scale-12 Kronecker graph against
the float64 reference and ``tpu-csr``.
"""

from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark.modules import load_module
from benchmark.reference import Chain
from benchmark.traffic.generate import Churn
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.node.manager import Manager, ManagerConfig, PreparedEpoch
from protocol_tpu.obs import TRACER
from protocol_tpu.ops import gather_window
from protocol_tpu.ops.gather_window import (
    BLOCK_ROWS,
    WINDOW,
    bucket_by_window,
    build_window_plan,
    converge_windowed,
    gather_windowed,
    graph_fingerprint,
    try_plan_delta,
)
from protocol_tpu.trust.backend import get_backend
from protocol_tpu.trust.graph import TrustGraph

KRONECKER = load_module(
    Path(__file__).resolve().parents[1] / "benchmark" / "graphs" / "graph500.py",
    "test_graph_",
)
TRICKLE = {"cohort": "recency", "senders": 10, "weight_low": 1, "weight_high": 101}


def l1(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).sum())


@pytest.fixture
def chunk(monkeypatch, request):
    """``TABLE_CHUNK`` cut to ``request.param`` windows (default 4)."""
    entries = getattr(request, "param", 4) * WINDOW
    monkeypatch.setattr(gather_window, "TABLE_CHUNK", entries)
    return entries


def _edges(n, e, seed, *, skip_windows=()):
    """Random edges over ``n`` ids whose sources avoid ``skip_windows``."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, 3 * e)
    src = src[~np.isin(src // WINDOW, skip_windows)][:e].astype(np.int32)
    dst = rng.integers(0, n, src.size).astype(np.int32)
    keep = src != dst
    w = rng.random(src.size, dtype=np.float32) + 0.01
    return src[keep], dst[keep], w[keep]


def _normalized(n, src, dst, w):
    sums = np.bincount(src, weights=w, minlength=n)
    return (w / sums[src]).astype(np.float32)


def _block_chunks(plan) -> list[set]:
    """The chunks each grid block's allocated rows (below the live-row
    watermark, wid >= 0) read."""
    cw = plan.table_chunk // WINDOW
    out = []
    for b in range(plan.n_rows // BLOCK_ROWS):
        rows = np.arange(b * BLOCK_ROWS, min((b + 1) * BLOCK_ROWS, plan.n_data_rows))
        wid = plan.wid[rows]
        out.append({int(x) // cw for x in wid[wid >= 0]})
    return out


def _assert_one_chunk_per_block(plan):
    for b, chunks in enumerate(_block_chunks(plan)):
        assert len(chunks) <= 1, f"grid block {b} reads chunks {chunks}"
    weight = plan.weight.reshape(plan.n_rows, -1)
    dead = plan.wid < 0
    assert not weight[dead].any(), "a skipped row holds an edge"
    assert not weight[plan.n_data_rows:].any(), "a spare row holds an edge"


@pytest.mark.parametrize(
    "chunk, skip_windows",
    [(4, ()), (1, ()), (4, (4, 5, 6, 7))],
    indirect=["chunk"],
    ids=["four-window-chunks", "one-window-chunks", "an-empty-chunk"],
)
def test_streamed_gather_equals_the_plain_lookup(chunk, skip_windows):
    n = 11 * WINDOW + 300  # 12 windows: three chunks of four, or twelve of one
    src, _, w = _edges(n, 20_000, seed=1, skip_windows=skip_windows)
    b = bucket_by_window(src, w, table_size=n, spare_rows=BLOCK_ROWS)
    assert b["align_rows"] > 0
    entries = -(-n // WINDOW) * WINDOW
    table = np.random.default_rng(2).random(entries, dtype=np.float32)
    out = gather_windowed(
        jnp.asarray(b["wid"]),
        jnp.asarray(table),
        jnp.asarray(b["local"]),
        jnp.asarray(b["weight"]),
        n_rows=b["n_rows"],
        table_chunk=b["table_chunk"],
        interpret=True,
    )
    rows = np.repeat(b["wid"], WINDOW).reshape(-1, 128)
    want = b["weight"] * table[rows * WINDOW + b["local"]]
    np.testing.assert_array_equal(np.asarray(out), want)
    # Edge by edge: the slot of edge order[k] holds its weighted score.
    got = np.asarray(out).reshape(-1)[b["out_pos"]]
    np.testing.assert_array_equal(got, w[b["order"]] * table[src[b["order"]]])


@pytest.mark.parametrize("chunk", [4, 2], indirect=True)
def test_no_grid_block_reads_two_chunks(chunk):
    n = 11 * WINDOW + 300
    src, dst, w = _edges(n, 30_000, seed=3)
    wn = _normalized(n, src, dst, w)
    plan = build_window_plan(src, dst, wn, n=n, spare_rows=8 * BLOCK_ROWS)
    assert plan.table_chunk == chunk and plan.table_chunks == -(-n // chunk)
    assert plan.align_rows > 0
    _assert_one_chunk_per_block(plan)
    # Deltas that grow one window of every chunk in turn: each grown
    # window's overflow has to open a block of its own chunk.
    rng = np.random.default_rng(4)
    skipped = 0
    for window in range(0, 12, 3):
        plan, src, dst, w = _grow_window(plan, src, dst, w, window, rng)
        skipped += plan.align_rows
        _assert_one_chunk_per_block(plan)
    assert skipped > 0, "no delta had to skip to a block of its own chunk"


def _grow_window(plan, src, dst, w, window, rng):
    """A delta in which 40 senders of ``window`` re-attest 76 edges each
    (three vreg-rows' worth more), checked to hold every edge."""
    n = plan.n
    rows = np.arange(window * WINDOW, window * WINDOW + 40)
    keep = ~np.isin(src, rows)
    k = 76 * rows.size
    src = np.concatenate([src[keep], np.repeat(rows, 76)]).astype(np.int32)
    dst = np.concatenate([dst[keep], rng.integers(0, n, k)]).astype(np.int32)
    w = np.concatenate([w[keep], rng.random(k, dtype=np.float32) + 0.01])
    dst[src == dst] = (dst[src == dst] + 1) % n
    wn = _normalized(n, src, dst, w)
    fp = graph_fingerprint(n, src, dst, wn.__getitem__)
    plan = try_plan_delta(plan, src, dst, wn.__getitem__, n=n, rows=rows, fingerprint=fp)
    assert plan is not None, f"growing window {window} fell back to a rebuild"
    rs, rd, _ = plan.recovered_edges()
    mine = np.sort(rs.astype(np.int64) << 32 | rd)
    np.testing.assert_array_equal(mine, np.sort(src.astype(np.int64) << 32 | dst))
    return plan, src, dst, w


def test_a_later_delta_takes_the_rows_an_earlier_one_skipped(chunk):
    """Growing a window of chunk 0 skips the rest of the open grid
    block, which reads the last chunk; the next delta that grows a
    window of the last chunk fills those rows before it opens any, and
    converges as a rebuild does."""
    n = 11 * WINDOW + 300
    src, dst, w = _edges(n, 30_000, seed=3)
    plan = build_window_plan(
        src, dst, _normalized(n, src, dst, w), n=n, spare_rows=8 * BLOCK_ROWS
    )
    rng = np.random.default_rng(8)
    first, src, dst, w = _grow_window(plan, src, dst, w, 0, rng)
    gap = np.flatnonzero(first.wid < 0)
    assert first.align_rows == gap.size > 3
    second, src, dst, w = _grow_window(first, src, dst, w, 11, rng)
    assert second.align_rows == 0
    assert second.n_data_rows == first.n_data_rows
    assert (second.wid[gap] == 11).sum() >= 3
    _assert_one_chunk_per_block(second)
    rebuilt = build_window_plan(src, dst, _normalized(n, src, dst, w), n=n)
    assert l1(_converge(second, src), _converge(rebuilt, src)) <= 1e-6


def _converge(plan, src, *, alpha=0.1, iters=15):
    """``iters`` steps from, and damped towards, the uniform vector."""
    p = np.full(plan.n, 1.0 / plan.n, np.float32)
    dangling = (np.bincount(src, minlength=plan.n) == 0).astype(np.float32)
    t, _, _ = converge_windowed(
        *plan.device_args(),
        jnp.asarray(p),
        jnp.asarray(p),
        jnp.asarray(dangling),
        **plan.static_kwargs(),
        alpha=alpha,
        tol=0.0,
        max_iter=iters,
        interpret=True,
    )
    return np.asarray(t)


@pytest.mark.parametrize("chunk", [4, 2], indirect=True)
def test_delta_across_chunk_boundaries_matches_a_rebuild(chunk):
    n = 11 * WINDOW + 300
    src, dst, w = _edges(n, 25_000, seed=5)
    plan = build_window_plan(src, dst, _normalized(n, src, dst, w), n=n)
    # Rewrite rows on both sides of every chunk boundary, growing them.
    rng = np.random.default_rng(6)
    edges = np.arange(chunk, n, chunk)
    rows = np.unique(np.concatenate([edges - 1, edges, [0, n - 1]]))
    keep = ~np.isin(src, rows)
    k = 300 * rows.size
    src = np.concatenate([src[keep], np.repeat(rows, 300)]).astype(np.int32)
    dst = np.concatenate([dst[keep], rng.integers(0, n, k)]).astype(np.int32)
    w = np.concatenate([w[keep], rng.random(k, dtype=np.float32) + 0.01])
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    wn = _normalized(n, src, dst, w)
    fp = graph_fingerprint(n, src, dst, wn.__getitem__)
    delta = try_plan_delta(plan, src, dst, wn.__getitem__, n=n, rows=rows, fingerprint=fp)
    assert delta is not None
    _assert_one_chunk_per_block(delta)
    rebuilt = build_window_plan(src, dst, wn, n=n)
    assert delta.table_chunks == rebuilt.table_chunks > 1
    a, b = _converge(delta, src), _converge(rebuilt, src)
    assert l1(a, b) <= 1e-6
    csr = get_backend("tpu-csr").converge(
        TrustGraph(n, src, dst, w, np.ones(n, bool)), alpha=0.1, tol=0.0, max_iter=15
    )
    assert l1(a, csr.scores) <= 1e-5


def test_peers_that_join_past_the_first_chunk_stream_the_table(chunk):
    """A plan of one chunk (one resident block, no aligned rows) takes
    a delta in which new peers push the table to three chunks: their
    windows' rows open blocks of their own chunks, and the result
    converges as a rebuild does."""
    n = 4 * WINDOW - 100
    src, dst, w = _edges(n, 12_000, seed=10)
    plan = build_window_plan(
        src, dst, _normalized(n, src, dst, w), n=n, spare_rows=8 * BLOCK_ROWS
    )
    assert (plan.table_chunks, plan.align_rows) == (1, 0)
    n_new = 9 * WINDOW + 50
    rng = np.random.default_rng(11)
    rows = np.sort(rng.choice(np.arange(n, n_new), 300, replace=False))
    k = 12 * rows.size
    src = np.concatenate([src, np.repeat(rows, 12)]).astype(np.int32)
    dst = np.concatenate([dst, rng.integers(0, n_new, k)]).astype(np.int32)
    w = np.concatenate([w, rng.random(k, dtype=np.float32) + 0.01])
    dst[src == dst] = (dst[src == dst] + 1) % n_new
    wn = _normalized(n_new, src, dst, w)
    fp = graph_fingerprint(n_new, src, dst, wn.__getitem__)
    delta = try_plan_delta(plan, src, dst, wn.__getitem__, n=n_new, rows=rows, fingerprint=fp)
    assert delta is not None and delta.table_chunks == 3
    _assert_one_chunk_per_block(delta)
    rebuilt = build_window_plan(src, dst, wn, n=n_new)
    assert l1(_converge(delta, src), _converge(rebuilt, src)) <= 1e-6


def test_a_delta_keeps_the_segment_capacity_where_runs_are_many_per_edge():
    """Two million uniform edges over a million ids: rows nearly full
    and nearly every edge a run of its own, as in the Graph500 plan.
    The spare tail still holds the capacity's pad runs, so a delta that
    takes runs away keeps every device shape (no recompile)."""
    n = 1 << 20
    src, dst, w = _edges(n, 2_000_000, seed=9)
    plan = build_window_plan(src, dst, _normalized(n, src, dst, w), n=n)
    assert plan.n_segments > 0.9 * src.size
    rows = np.unique(src)[:40]  # these senders drop all their edges
    keep = ~np.isin(src, rows)
    src, dst, w = src[keep], dst[keep], w[keep]
    wn = _normalized(n, src, dst, w)
    fp = graph_fingerprint(n, src, dst, wn.__getitem__)
    delta = try_plan_delta(plan, src, dst, wn.__getitem__, n=n, rows=rows, fingerprint=fp)
    assert delta is not None and delta.n_segments < plan.n_segments
    assert (delta.n_rows, delta.seg_capacity) == (plan.n_rows, plan.seg_capacity)


def test_a_table_of_one_chunk_is_one_resident_block(monkeypatch):
    """At most one chunk long, the layout is the unchunked one: no pad
    rows, one block, the same arrays whatever the chunk size."""
    n = 4 * WINDOW
    src, dst, w = _edges(n, 9_000, seed=7)
    wn = _normalized(n, src, dst, w)
    monkeypatch.setattr(gather_window, "TABLE_CHUNK", n)
    exact = build_window_plan(src, dst, wn, n=n)
    monkeypatch.setattr(gather_window, "TABLE_CHUNK", 1 << 20)
    big = build_window_plan(src, dst, wn, n=n)
    for plan in (exact, big):
        assert (plan.table_chunks, plan.align_rows) == (1, 0)
    for k in exact._CORE + exact._HOST:
        np.testing.assert_array_equal(getattr(exact, k), getattr(big, k))
    monkeypatch.setattr(gather_window, "TABLE_CHUNK", n - WINDOW)
    assert build_window_plan(src, dst, wn, n=n).table_chunks == 2


@pytest.fixture(scope="module")
def kronecker_epochs():
    """A seeded scale-12 Kronecker graph (4,096 ids, 65,536 tuples) and
    a trickle epoch after it, as the benchmark draws them."""
    g = KRONECKER.draw(1 << 12, 16 << 12, seed=12)
    churn = Churn(TRICKLE, seed=13)
    graphs, rows = [g], [None]
    for _ in range(1):
        r, g = churn.step(g)
        graphs.append(g)
        rows.append(r)
    return graphs, rows


def _trust(g) -> TrustGraph:
    return TrustGraph(g.n, g.src, g.dst, g.weight, g.pre_trusted)


#: The graph mixes fast: 40 steps pass 1e-13 in float64.
ALPHA, TOL, MAX_ITER = 0.1, 1e-8, 40


@pytest.mark.parametrize("chunk", [1], indirect=True)
def test_manager_on_a_kronecker_graph_matches_the_reference_and_csr(
    chunk, kronecker_epochs
):
    graphs, rows = kronecker_epochs
    m = Manager(ManagerConfig(backend="tpu-windowed", prover="commitment"))
    ids = list(range(graphs[0].n))
    chain = Chain({"alpha": ALPHA, "tol": TOL, "max_iter": MAX_ITER})
    for number, (g, r) in enumerate(zip(graphs, rows), start=1):
        prepared = PreparedEpoch(
            Epoch(number),
            _trust(g),
            ids,
            m._warm_t0(ids),
            None if r is None else r.astype(np.int64),
            set(),
        )
        with TRACER.epoch(number) as root:
            result = m.converge_prepared(prepared, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER)
        plan_span = root.find("plan")
        assert plan_span.attrs["reason"] == ("cold" if r is None else "delta")
        assert plan_span.attrs["table_chunks"] == 4
        assert plan_span.attrs["align_rows"] >= 0
        assert m.window_plan.table_chunks == 4
        _assert_one_chunk_per_block(m.window_plan)
        ref = chain.epoch(g)
        csr = get_backend("tpu-csr").converge(
            _trust(g), alpha=ALPHA, tol=TOL, max_iter=MAX_ITER
        )
        assert l1(result.scores, ref.scores) <= 1e-5
        assert l1(result.scores, csr.scores) <= 1e-5


@pytest.mark.parametrize("chunk", [1], indirect=True)
def test_sharded_windowed_streams_the_same_chunks(chunk, kronecker_epochs):
    graphs, _ = kronecker_epochs
    g = _trust(graphs[0])
    sharded = get_backend("tpu-sharded:tpu-windowed").converge(
        g, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER
    )
    single = get_backend("tpu-windowed").converge(g, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER)
    csr = get_backend("tpu-csr").converge(g, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER)
    assert l1(sharded.scores, single.scores) <= 1e-5
    assert l1(sharded.scores, csr.scores) <= 1e-5
