"""``correct`` holds for the program and fails for the control and for
each fault a cell can have, at a size a CPU test holds.  The limits are
the cells' own (``benchmark/cells``).

Faults are planted in the timed path, under the harness: the converge
stage returning its start vector (a step that leaves the state
unchanged), converging half of the edges (half of the batch left out),
and one answer altered where it is produced.  One chip exchanges
nothing, so there is no exchange between chips to leave out."""

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import Chain, gaps
from benchmark.tests.helpers import CPU_DEVICE, tiny_spec
from benchmark.traffic.generate import Churn, base_graph
from protocol_tpu.node.manager import Manager

SIZES = {"c4-trickle": (4096, 40_000)}


def _run(workload, seed=2**31 + 11):
    return run.run_cell(tiny_spec(workload, *SIZES[workload]), seed, 1.0, False, CPU_DEVICE)


def _state_unchanged(prepared, result):
    if prepared.t0 is not None:
        result.scores = np.asarray(prepared.t0, np.float64)


def _answer_altered(prepared, result):
    s = result.scores
    hi, lo = int(np.argmax(s)), int(np.argmin(s))
    s[hi], s[lo] = s[lo], s[hi]


def _half_batch(prepared):
    from protocol_tpu.trust.graph import TrustGraph

    g = prepared.graph
    prepared.graph = TrustGraph(g.n, g.src[::2], g.dst[::2], g.weight[::2], g.pre_trusted)


FAULTS = {
    "state_unchanged": (None, _state_unchanged),
    "half_batch": (_half_batch, None),
    "answer_altered": (None, _answer_altered),
}


@pytest.mark.parametrize("workload", sorted(SIZES))
def test_program_is_correct(workload):
    result = _run(workload)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", sorted(SIZES))
def test_fault_is_not_correct(workload, fault, monkeypatch):
    before, after = FAULTS[fault]
    original = Manager.converge_prepared
    state = {"window": False}

    def broken(self, prepared, **kw):
        # setup epochs (0 and the warm-ups) run sound; the fault hits
        # every epoch the generator thread delivered, i.e. the window
        if state["window"] and before:
            before(prepared)
        result = original(self, prepared, **kw)
        if state["window"] and after:
            after(prepared, result)
        return result

    from benchmark.traffic import generate

    class Marked(generate.Ahead):
        def get(self):
            state["window"] = True
            return super().get()

    monkeypatch.setattr(generate, "Ahead", Marked)
    monkeypatch.setattr(Manager, "converge_prepared", broken)
    result = _run(workload)
    assert not result["correct"], result["check"]
    assert result["failed"] >= 1


@pytest.mark.parametrize("workload", sorted(SIZES))
def test_control_is_not_correct(workload):
    """The reference in bfloat16, put in the program's place, over the
    cell's own epochs: it fails one of the cell's limits."""
    spec = tiny_spec(workload, *SIZES[workload])
    g = base_graph(spec.config, 5)
    churn = Churn(spec.traffic, 5)
    ref, control = Chain(spec.config["trust"]), Chain(spec.config["trust"], "bfloat16")
    worst = {k: 0.0 for k in spec.limits}
    for k in range(4):
        if k:
            _, g = churn.step(g)
        r, c = ref.epoch(g), control.epoch(g)
        read = gaps(c.scores, r.scores)
        for name in worst:
            worst[name] = max(worst[name], read[name])
    assert any(worst[k] > spec.limits[k] for k in spec.limits), worst
