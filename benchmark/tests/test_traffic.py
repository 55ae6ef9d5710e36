"""The generator: the same seed gives the same graphs, another seed
others; each mix rewrites what its file says."""

import numpy as np

from benchmark.tests.helpers import tiny_spec
from benchmark.traffic.generate import Churn, base_graph


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
