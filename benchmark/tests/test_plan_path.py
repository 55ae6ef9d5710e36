"""Which plan path the program takes under each mix, at a small size:
a trickle of re-attesting senders is a plan delta, while 1 % of the
senders drawn uniformly touch more windows than the crossover allows
(a quarter of the data windows, at least 64) and rebuild.  At 70k
peers the plan has 69 windows of 1,024 peers."""

import pytest

from benchmark.run import Epoch, Node
from benchmark.tests.helpers import tiny_spec
from benchmark.traffic.generate import Churn, base_graph

UNIFORM_1PCT = {"cohort": "uniform", "edge_fraction": 0.01, "weight_low": 1, "weight_high": 101}


@pytest.mark.parametrize("mix,path", [("trickle", "delta"), ("uniform_1pct", "rebuild")])
def test_plan_path(mix, path):
    spec = tiny_spec("c4-trickle", 70_000, 280_000)
    traffic = spec.traffic if mix == "trickle" else UNIFORM_1PCT
    g = base_graph(spec.config, 21)
    churn = Churn(traffic, 21)
    node = Node(spec.config)
    try:
        cold = Epoch(0, g, 0)
        node.epoch(cold, None, False)
        assert cold.plan == "rebuild"
        for k in (1, 2):
            rows, g = churn.step(g)
            ep = Epoch(k, g, int(rows.size))
            node.epoch(ep, rows, False)
            assert ep.plan == path, (k, rows.size)
    finally:
        node.spans.close()
