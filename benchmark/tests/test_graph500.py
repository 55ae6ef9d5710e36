"""The ``graph500`` graph kind, Graph500's Kronecker generator: the seed fixes the graph, each
level's quadrant follows the initiator, and the graph is simple,
relabelled and sorted, as the ``graph500-s22`` configuration reads it."""

import json

import numpy as np
import pytest

from benchmark.modules import load_module
from benchmark.run import BENCH, load_spec
from benchmark.traffic.generate import GRAPH_KINDS, Churn, base_graph

graph500 = load_module(GRAPH_KINDS / "graph500.py", "test_kind_")
INITIATOR = (0.57, 0.19, 0.19, 0.05)


def _draw(scale, seed):
    return graph500.draw(1 << scale, 16 << scale, seed=seed, initiator=INITIATOR)


def test_the_seed_fixes_the_graph():
    a, b, c = _draw(10, 2**31 + 3), _draw(10, 2**31 + 3), _draw(10, 2**31 + 4)
    for f in ("src", "dst", "weight", "pre_trusted"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert a.nnz != c.nnz or not np.array_equal(a.dst, c.dst)


def test_quadrants_follow_the_initiator():
    """At scale 10 each of 10 levels of 16,384 tuples draws one
    quadrant; every quadrant's share over all 163,840 draws is within
    five binomial standard deviations of its probability."""
    scale, m = 10, 16 << 10
    cuts = np.cumsum(INITIATOR)[:3].astype(np.float32)
    start, end = graph500._tuples(np.random.default_rng(5), m, scale, cuts)
    bits = np.arange(scale)
    s = (start[:, None] >> bits) & 1
    e = (end[:, None] >> bits) & 1
    quadrant = (2 * s + e).reshape(-1)
    draws = quadrant.size
    for q, p in enumerate(INITIATOR):
        share = np.count_nonzero(quadrant == q) / draws
        assert abs(share - p) < 5 * np.sqrt(p * (1 - p) / draws), (q, share)


def test_the_graph_is_simple_sorted_and_weighted():
    g = _draw(11, 7)
    assert not (g.src == g.dst).any()
    key = g.src.astype(np.int64) << 11 | g.dst
    assert (np.diff(key) > 0).all()  # sorted, no pair twice
    assert g.nnz < 16 << 11  # the merged duplicates and dropped loops
    assert ((g.weight >= 1) & (g.weight <= 100)).all()
    assert g.weight.dtype == np.float32 and g.src.dtype == g.dst.dtype == np.int32
    assert g.pre_trusted.sum() == 64


def test_labels_are_permuted():
    """Unpermuted, an id's degree falls with its number of set bits (id
    0 is the hub); after the relabelling the two are unrelated."""
    scale = 12
    cuts = np.cumsum(INITIATOR)[:3].astype(np.float32)
    start, _ = graph500._tuples(np.random.default_rng(1), 16 << scale, scale, cuts)
    ids = np.arange(1 << scale)
    ones = np.array([bin(i).count("1") for i in ids])
    raw = np.log1p(np.bincount(start, minlength=ids.size))
    drawn = np.log1p(np.bincount(_draw(scale, 1).src, minlength=ids.size))
    assert np.corrcoef(ones, raw)[0, 1] < -0.5
    assert abs(np.corrcoef(ones, drawn)[0, 1]) < 0.1
    assert drawn.argmax() != 0


def test_the_configuration_reads_this_kind_and_trickles():
    config = json.loads((BENCH / "configs" / "graph500-s22.json").read_text())
    config["graph"].update(peers=1 << 12, attestations=16 << 12)
    traffic = json.loads((BENCH / "traffic" / "trickle.json").read_text())
    g = base_graph(config, 2**31 + 11)
    h = base_graph(config, 1)
    np.testing.assert_array_equal(g.dst, h.dst)  # fixed by the config's seed
    rows, g2 = Churn(traffic, 2**31 + 11).step(g)
    assert 1 <= rows.size <= 10 and rows.min() > g.n // 2
    assert np.isin(g2.src, rows).sum() == rows.size * round(g.nnz / g.n)


def test_a_scale_is_a_power_of_two():
    with pytest.raises(ValueError):
        graph500.draw(3000, 48_000, seed=1)


def test_the_cell_runs_this_configuration_on_the_trickle():
    spec = load_spec("g22-trickle")
    assert spec.config["graph"]["kind"] == "graph500"
    assert spec.config["graph"]["peers"] == 1 << 22
    assert spec.traffic == json.loads((BENCH / "traffic" / "trickle.json").read_text())
    assert spec.chips == 1
    assert set(spec.limits) == {"l1_gap"}
    assert {m["name"] for m in spec.per_layer} >= {"gather_device_s", "gather_roofline"}
