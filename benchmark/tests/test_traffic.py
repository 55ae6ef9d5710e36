"""The generator: the same seed gives the same graphs, another seed
others; each mix rewrites what its file says; a graph kind is a file
found by name."""

import hashlib

import numpy as np
import pytest

from benchmark.tests.helpers import tiny_spec
from benchmark.traffic.generate import Churn, base_graph

#: ``c4-trickle``'s base graph and three churn steps at 4096 peers /
#: 40,000 attestations, seed 3, as the generator drew them before graph
#: kinds moved into files of their own: what the cell reads must not move.
TRICKLE_DIGEST = "3591d290d6feadbb2da306c93b22092342ef0a2c1cf30fa7a93ad185b13cfd12"


def _epochs(spec, seed, k=3):
    g = base_graph(spec.config, seed)
    churn = Churn(spec.traffic, seed)
    out = [g]
    for _ in range(k):
        _, g = churn.step(g)
        out.append(g)
    return out


def test_same_seed_same_graphs():
    spec = tiny_spec("c4-trickle", 4096, 40_000)
    seed = 2**31 + 5
    a, b = _epochs(spec, seed), _epochs(spec, seed)
    c = _epochs(spec, seed + 1)
    for x, y in zip(a, b):
        for f in ("src", "dst", "weight", "pre_trusted"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
    assert not np.array_equal(a[-1].dst, c[-1].dst)


def test_trickle_rewrites_recent_rows_at_mean_degree():
    spec = tiny_spec("c4-trickle", 4096, 40_000)
    g = base_graph(spec.config, 3)
    rows, h = Churn(spec.traffic, 3).step(g)
    assert 1 <= rows.size <= 10
    assert rows.min() > g.n // 2  # recency-biased toward new ids
    deg = round(g.nnz / g.n)
    new = np.isin(h.src, rows)
    assert new.sum() == rows.size * deg
    assert not (h.src[new] == h.dst[new]).any()
    assert ((h.weight[new] >= 1) & (h.weight[new] < 101)).all()


def test_graph_fixed_by_config_run_seed_draws_churn():
    """The configuration's seed fixes the base graph, so every run has
    the same plan shapes and finds its converge in the compile cache."""
    spec = tiny_spec("c4-trickle", 4096, 40_000)
    a, b = base_graph(spec.config, 1), base_graph(spec.config, 2**31 + 7)
    for f in ("src", "dst", "weight", "pre_trusted"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_trickle_graphs_are_pinned():
    h = hashlib.sha256()
    for g in _epochs(tiny_spec("c4-trickle", 4096, 40_000), 3):
        for f in ("src", "dst", "weight", "pre_trusted"):
            h.update(getattr(g, f).tobytes())
    assert h.hexdigest() == TRICKLE_DIGEST


def test_graph_kind_is_a_file_found_by_name(tmp_path):
    (tmp_path / "ring.py").write_text(
        "import numpy as np\n"
        "from benchmark.traffic.generate import Graph\n"
        "def draw(n, nnz, *, seed, weight=1.0):\n"
        "    src = np.arange(nnz, dtype=np.int32) % n\n"
        "    return Graph(n, src, (src + 1) % n, np.full(nnz, weight, np.float32),\n"
        "                 np.arange(n) == seed % n)\n"
    )
    config = {"graph": {"kind": "ring", "peers": 5, "attestations": 7, "weight": 2.0}}
    g = base_graph(config, 12, kinds=tmp_path)
    assert g.n == 5 and g.nnz == 7 and (g.weight == 2.0).all()
    np.testing.assert_array_equal(g.dst[:5], [1, 2, 3, 4, 0])
    assert g.pre_trusted.tolist() == [False, False, True, False, False]


def test_unknown_graph_kind_names_the_known():
    spec = tiny_spec("c4-trickle", 4096, 40_000)
    spec.config["graph"]["kind"] = "kronecker"
    with pytest.raises(SystemExit, match="'kronecker'.*scale_free"):
        base_graph(spec.config, 1)
