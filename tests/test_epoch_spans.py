"""Spans inside the epoch's device stage: warm seed, host graph
preparation, the plan and its sub-phases, the upload, the converge
(closed on its result) and the monitors after it, at a CPU size."""

import ast
from pathlib import Path

import numpy as np
import pytest

from protocol_tpu.analysis.ast_rules import scan_file
from protocol_tpu.models.graphs import erdos_renyi
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.node.manager import Manager, ManagerConfig, PreparedEpoch
from protocol_tpu.obs import TRACER
from protocol_tpu.ops.gather_window import WindowPlan, build_window_plan, try_plan_delta
from protocol_tpu.trust.backend import get_backend
from protocol_tpu.trust.graph import TrustGraph

ROOT = Path(__file__).resolve().parents[1]
DELTA_PHASES = ["plan_select", "plan_extract", "plan_repack", "plan_splice", "plan_seg_tables"]


def _churned(g: TrustGraph, rows: np.ndarray, seed: int = 0) -> TrustGraph:
    """``g`` with the out-rows of ``rows`` rewritten (four edges each)."""
    rng = np.random.default_rng(seed)
    keep = ~np.isin(g.src, rows)
    k = 4 * rows.size
    return TrustGraph(
        g.n,
        np.concatenate([g.src[keep], np.repeat(rows, 4)]),
        np.concatenate([g.dst[keep], rng.integers(0, g.n, k)]),
        np.concatenate([g.weight[keep], rng.integers(1, 100, k).astype(np.float32)]),
        g.pre_trusted,
    )


@pytest.fixture(scope="module")
def epochs():
    """A cold and a delta epoch of a windowed manager, each under its
    own trace root: ``(manager, [(root, result, plan, prepared)] * 2)``."""
    g = erdos_renyi(2048, avg_degree=6.0, seed=3)
    m = Manager(ManagerConfig(backend="tpu-windowed", prover="commitment"))
    ids = list(range(g.n))
    rows = np.array([7, 1500])
    out = []
    for number, graph, delta in ((9101, g, None), (9102, _churned(g, rows), rows)):
        prepared = PreparedEpoch(
            Epoch(number), graph, ids, m._warm_t0(ids), delta, set()
        )
        with TRACER.epoch(number) as root:
            result = m.converge_prepared(prepared, alpha=0.1)
        out.append((root, result, m.window_plan, prepared))
    return m, out


def test_cold_epoch_opens_each_stage_in_order(epochs):
    _, [(root, _, cold_plan, _), _] = epochs
    assert root.child_names() == ["graph_prep", "plan", "upload", "converge", "monitors"]
    prep, plan, _, _, monitors = root.children
    assert prep.child_names() == ["drop_self_edges", "row_normalize", "fingerprint"]
    assert prep.attrs["edges"] > 0
    assert plan.attrs["reason"] == "cold"
    assert plan.child_names() == ["plan_bucket", "plan_seg_tables"]
    assert plan.attrs["edges"] == cold_plan.n_edges
    assert monitors.child_names() == ["drift"]
    assert monitors.attrs["epoch"] == 9101


def test_delta_epoch_plan_holds_the_delta_sub_phases(epochs):
    _, [_, (root, _, plan, prepared)] = epochs
    (span,) = [c for c in root.children if c.name == "plan"]
    assert span.attrs["reason"] == "delta"
    assert span.child_names() == DELTA_PHASES
    assert span.attrs["rows"] == prepared.delta_rows.size
    assert span.attrs["touched_windows"] == np.unique(prepared.delta_rows // 1024).size
    assert span.attrs["delta_edges"] == 4 * prepared.delta_rows.size
    assert plan.lineage  # the delta path, not a rebuild


@pytest.mark.parametrize("which", [0, 1])
def test_converge_closes_on_its_result_before_the_monitors(epochs, which):
    _, roots = epochs
    root, result, _, _ = roots[which]
    converge = root.find("converge")
    monitors = root.find("monitors")
    assert converge.attrs["iterations"] == result.iterations
    assert converge.attrs["residual"] == pytest.approx(result.residual)
    assert converge.start_offset_s + converge.duration_s <= monitors.start_offset_s


def test_upload_bytes_are_the_operands(epochs):
    _, roots = epochs
    for root, _, plan, _ in roots:
        core = sum(getattr(plan, k).nbytes for k in WindowPlan._DEVICE)
        # start vector, pre-trust and dangling: float32 over the peers
        assert root.find("upload").attrs["bytes"] == core + 3 * 4 * plan.n


def test_drift_span_names_the_alignment_path(epochs):
    _, [_, (root, _, _, _)] = epochs
    assert root.find("drift").attrs["align"] == "prefix"  # the same id list


def test_warm_t0_opens_warm_seed(epochs):
    m, _ = epochs
    ids = list(range(2048))
    with TRACER.epoch(9103) as root:
        t0 = m._warm_t0(ids)
    (span,) = root.children
    assert span.name == "warm_seed" and span.attrs["peers"] == 2048
    assert span.attrs["align"] == "prefix"  # an equal column
    assert "epoch" not in span.attrs
    assert t0 is not None and t0.shape == (2048,)


def test_prepare_epoch_tags_warm_seed_with_the_epoch():
    m = Manager(ManagerConfig(backend="tpu-csr", prover="commitment"))
    m.generate_initial_attestations()
    m.converge_epoch(Epoch(1), alpha=0.1)
    with TRACER.epoch(9104) as root:
        m.prepare_epoch(Epoch(2))
    span = root.find("warm_seed")
    assert span.attrs["epoch"] == 2 and span.attrs["peers"] == 5


def test_csr_backend_prepares_then_converges():
    g = erdos_renyi(500, avg_degree=4.0, seed=1)
    with TRACER.epoch(9105) as root:
        result = get_backend("tpu-csr").converge(g, alpha=0.1)
    assert root.child_names() == ["graph_prep", "converge"]
    assert root.children[0].child_names() == ["drop_self_edges", "row_normalize", "sort_by_dst"]
    assert root.children[1].attrs["iterations"] == result.iterations


def test_sharded_windowed_backend_prepares_then_plans():
    """``tpu-sharded:tpu-windowed`` opens the single-chip path's
    ``graph_prep``, then one ``plan`` span holding the reason, the
    sub-phases and the partition."""
    from protocol_tpu.parallel.mesh import default_mesh

    g = erdos_renyi(2048, avg_degree=6.0, seed=3)
    backend = get_backend("tpu-sharded:tpu-windowed", mesh=default_mesh(4))
    rows = np.array([7, 1500])
    for number, graph, delta, reason, phases in (
        (9106, g, None, "cold", ["plan_bucket", "plan_seg_tables"]),
        (9107, _churned(g, rows), rows, "delta", DELTA_PHASES),
    ):
        backend.delta_rows = delta
        with TRACER.epoch(number) as root:
            result = backend.converge(graph, alpha=0.1)
        assert root.child_names() == ["graph_prep", "plan", "converge"]
        prep, plan, converge = root.children
        assert prep.child_names() == ["drop_self_edges", "row_normalize", "fingerprint"]
        assert prep.attrs["edges"] == graph.nnz
        assert plan.attrs["backend"] == "tpu-sharded:tpu-windowed"
        assert plan.attrs["reason"] == reason
        assert plan.child_names() == phases
        assert plan.attrs["table_chunks"] == backend.last_plan.table_chunks
        assert converge.attrs["iterations"] == result.iterations


def _recording():
    names: list[str] = []
    counts: dict = {}

    def phase(name, **kw):
        names.append(name)
        counts.update(kw)
        return TRACER.span(name)

    return phase, names, counts


def test_phase_hook_leaves_the_plan_unchanged():
    g = erdos_renyi(3000, avg_degree=5.0, seed=4)
    keep = g.src != g.dst
    src, dst, w = g.src[keep], g.dst[keep], g.row_normalized()[0][keep]
    phase, names, _ = _recording()
    built = build_window_plan(src, dst, w, n=g.n)
    assert build_window_plan(src, dst, w, n=g.n, phase=phase).fingerprint == built.fingerprint
    assert names == ["plan_bucket", "plan_seg_tables"]

    rows = np.array([3, 1200, 2999])
    g2 = _churned(TrustGraph(g.n, src, dst, w, g.pre_trusted), rows)
    w2, _ = g2.row_normalized()
    args = dict(n=g.n, rows=rows, fingerprint="next")
    plain = try_plan_delta(built, g2.src, g2.dst, w2, **args)
    phase, names, counts = _recording()
    hooked = try_plan_delta(built, g2.src, g2.dst, w2, phase=phase, **args)
    assert names == DELTA_PHASES
    assert counts == {"rows": 3, "touched_windows": 3, "delta_edges": 12}
    for k in WindowPlan._CORE + WindowPlan._HOST:
        np.testing.assert_array_equal(getattr(hooked, k), getattr(plain, k))
    assert {k: getattr(hooked, k) for k in WindowPlan._META} == {
        k: getattr(plain, k) for k in WindowPlan._META
    }


def _imports(path: Path) -> set[str]:
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            out.add("." * node.level + (node.module or ""))
        elif isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
    return out


def test_kernel_trees_stay_free_of_clocks_and_obs():
    """The plan's sub-phases are timed through the injected hook: ops/
    and parallel/ import no clock, logger or obs, and the files this
    instrumentation touches lint clean."""
    for tree in ("ops", "parallel"):
        for path in sorted((ROOT / "protocol_tpu" / tree).rglob("*.py")):
            names = _imports(path)
            assert not {"time", "logging"} & names, path
            assert not any("obs" in n.split(".") for n in names), path
    for rel in ("ops/gather_window.py", "trust/backend.py", "node/manager.py",
                "obs/export.py"):
        errors = [
            f for f in scan_file(ROOT / "protocol_tpu" / rel, ROOT) if f.severity == "error"
        ]
        assert errors == [], errors
