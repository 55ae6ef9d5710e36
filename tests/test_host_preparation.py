"""The epoch's two host decisions, each made in one place: the
normalized graph every edge-list backend iterates
(``trust.backend.normalize_graph``) and whether a cached plan is
reused, delta-updated or rebuilt (``ops.gather_window.resolve_plan``)."""

import contextlib
import dataclasses

import numpy as np
import pytest

from protocol_tpu.models.graphs import erdos_renyi, scale_free
from protocol_tpu.ops.gather_window import (
    WindowPlan,
    build_window_plan,
    graph_fingerprint,
    resolve_plan,
)
from protocol_tpu.trust.backend import normalize_graph
from protocol_tpu.trust.graph import TrustGraph

DELTA_PHASES = ["plan_select", "plan_extract", "plan_repack", "plan_splice", "plan_seg_tables"]
BUILD_PHASES = ["plan_bucket", "plan_seg_tables"]


def _erdos_renyi_with_self_edges() -> TrustGraph:
    g = erdos_renyi(300, avg_degree=6.0, seed=21)
    loops = np.arange(0, 300, 7, dtype=np.int32)
    return TrustGraph(
        g.n,
        np.concatenate([g.src, loops]),
        np.concatenate([g.dst, loops]),
        np.concatenate([g.weight, np.full(loops.size, 5.0, np.float32)]),
        g.pre_trusted,
    )


def _scale_free_dangling_unseeded() -> TrustGraph:
    g = scale_free(1500, 9000, seed=2)
    keep = ~np.isin(g.src, np.asarray([0, 17, 1499], dtype=np.int32))
    return TrustGraph(g.n, g.src[keep], g.dst[keep], g.weight[keep], None)


@pytest.mark.parametrize(
    "make", [_erdos_renyi_with_self_edges, _scale_free_dangling_unseeded],
    ids=["erdos-renyi-self-edges", "scale-free-dangling-no-pre-trust"],
)
def test_normalize_graph_is_the_recipe_bit_for_bit(make):
    graph = make()
    # The recipe every backend, builder and analyzer ran on its own.
    g = graph.drop_self_edges()
    w, dangling = g.row_normalized()
    want = {
        "src": g.src,
        "dst": g.dst,
        "w": w,
        "dangling": dangling.astype(np.float32),
        "p": graph.pre_trust_vector(),
    }
    ng = normalize_graph(graph)
    assert ng.n == graph.n and ng.nnz == g.nnz
    for name, a in want.items():
        b = getattr(ng, name)
        assert b.dtype == a.dtype and b.tobytes() == a.tobytes(), name
    # The case holds what it is named for.
    if graph.pre_trusted is None:
        assert dangling.any() and np.all(ng.p == ng.p[0])
    else:
        assert (graph.src == graph.dst).any() and ng.nnz < graph.nnz
    # The fingerprint and the dst-sorted layout, each derived once.
    assert ng.fingerprint == graph_fingerprint(g.n, g.src, g.dst, w)
    ref = TrustGraph(g.n, g.src, g.dst, w, graph.pre_trusted).sorted_by_dst()
    got = ng.by_dst()
    for name in ("src", "dst", "weight"):
        assert getattr(got, name).tobytes() == getattr(ref, name).tobytes(), name


def _arrays(graph: TrustGraph):
    ng = normalize_graph(graph)
    return ng.src, ng.dst, ng.w, ng.n, ng.fingerprint


def _churned(graph: TrustGraph, rows: np.ndarray, per_row: int, seed: int) -> TrustGraph:
    """``graph`` with the out-rows of ``rows`` rewritten, ``per_row``
    edges each."""
    rng = np.random.default_rng(seed)
    keep = ~np.isin(graph.src, rows)
    k = per_row * rows.size
    return TrustGraph(
        graph.n,
        np.concatenate([graph.src[keep], np.repeat(rows, per_row).astype(np.int32)]),
        np.concatenate([graph.dst[keep], rng.integers(0, graph.n, k).astype(np.int32)]),
        np.concatenate([graph.weight[keep], rng.integers(1, 100, k).astype(np.float32)]),
        graph.pre_trusted,
    )


@pytest.fixture(scope="module")
def base():
    graph = scale_free(3000, 15000, seed=22)
    src, dst, w, n, _ = _arrays(graph)
    return graph, build_window_plan(src, dst, w, n=n)


def _case(name, base):
    """``(candidate, target graph, rows)`` for one resolution case."""
    graph, plan = base
    rows = np.array([5, 1300, 2900], dtype=np.int64)
    if name == "cold":
        return None, graph, None
    if name == "stale-layout":
        return dataclasses.replace(plan, version=1), graph, None
    if name == "fingerprint-hit":
        return plan, graph, rows
    if name == "delta-with-hint":
        return plan, _churned(graph, rows, 4, seed=23), rows
    if name == "miss-without-hint":
        return plan, _churned(graph, rows, 4, seed=23), None
    # A delta past the headroom of a plan built with no spare rows: the
    # delta attempt gives up and the rebuild follows.
    small = erdos_renyi(500, avg_degree=4.0, seed=13)
    src, dst, w, n, _ = _arrays(small)
    tight = build_window_plan(src, dst, w, n=n, spare_rows=0)
    wide = np.arange(0, 400, dtype=np.int64)
    return tight, _churned(small, wide, 600, seed=14), wide


@pytest.mark.parametrize(
    "name, outcome, attempts",
    [
        ("cold", "rebuild", [("cold", BUILD_PHASES)]),
        ("stale-layout", "rebuild", [("stale-layout", BUILD_PHASES)]),
        ("fingerprint-hit", "reuse", []),
        ("delta-with-hint", "delta", [("delta", DELTA_PHASES)]),
        ("miss-without-hint", "rebuild", [("fingerprint-miss", BUILD_PHASES)]),
        ("delta-overflows", "rebuild", [("delta", None), ("fingerprint-miss", BUILD_PHASES)]),
    ],
)
def test_resolve_plan_outcome_and_reason(base, name, outcome, attempts):
    candidate, graph, rows = _case(name, base)
    src, dst, w, n, fp = _arrays(graph)
    seen: list = []

    def attempt(reason, make):
        phases: list[str] = []

        def phase(sub, **counts):
            phases.append(sub)
            return contextlib.nullcontext()

        seen.append((reason, phases))
        return make(phase)

    plan, got = resolve_plan(
        candidate, src, dst, w, n=n, fingerprint=fp, rows=rows, attempt=attempt
    )
    assert got == outcome
    assert [r for r, _ in seen] == [r for r, _ in attempts]
    for (_, phases), (_, want) in zip(seen, attempts):
        if want is not None:
            assert phases == want
    assert plan.fingerprint == fp
    if outcome == "reuse":
        assert plan is candidate
    elif outcome == "rebuild":
        fresh = build_window_plan(src, dst, w, n=n)
        for k in WindowPlan._CORE:
            np.testing.assert_array_equal(getattr(plan, k), getattr(fresh, k))
    else:
        assert plan.lineage[-1] == candidate.fingerprint
    # The default hook runs the same decision unrecorded.
    quiet, quiet_outcome = resolve_plan(candidate, src, dst, w, n=n, fingerprint=fp, rows=rows)
    assert quiet_outcome == outcome and quiet.fingerprint == fp

