"""The trace reducer, on hand-made events and on a recorded trace of a
``c4-trickle`` window (one v5e chip, 2 epochs, 3 power iterations): the
output of ``benchmark.trace.extract`` on the profiler's ``.xplane.pb``,
written with ``json.dump`` through ``gzip``."""

import gzip
import json
from pathlib import Path

import pytest

from benchmark.trace import op_label, reduce

RECORDED = Path(__file__).parent / "data" / "c4-trickle-trace.json.gz"


def _trace(ops, modules, host):
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
            "host": host}


def test_busy_is_a_union_and_idle_is_named_by_the_host():
    host = [["bench:window", 0, 100e9], ["bench:epoch", 0, 100e9],
            ["program:plan", 10e9, 30e9]]
    ops = [["%while.1 = (f32[8]) while(x)", 50e9, 20e9],   # holds the next two
           ["%fusion.1 = f32[8]{0} fusion(x)", 52e9, 5e9],
           ["%fusion.2 = f32[8]{0} fusion(x)", 60e9, 5e9],
           ["%copy.3 = f32[8]{0} copy(x)", 70e9, 5e9]]
    r = reduce(_trace(ops, [["jit_converge_csr(1)", 50e9, 25e9]], host))
    assert r["window_s"] == 100 and r["busy_s"] == 25 and r["converge_s"] == 25
    assert dict(r["idle_gaps"]) == pytest.approx({"program:plan": 30, "bench:epoch": 45})
    assert dict(r["device_ops"]) == pytest.approx(
        {"while.1 while": 10, "fusion.1 fusion": 5, "fusion.2 fusion": 5, "copy.3 copy": 5})


def test_events_outside_the_window_do_not_count():
    host = [["bench:window", 10e9, 10e9]]
    ops = [["%a = f32[1]{0} add(x)", 0, 5e9], ["%b = f32[1]{0} add(x)", 12e9, 1e9]]
    r = reduce(_trace(ops, [], host))
    assert r["busy_s"] == 1 and r["converge_s"] == 0


def test_op_label():
    assert op_label("%fusion.103 = f32[16536576,2]{0,1:T(2,128)} fusion(f32[2] %p)") == \
        "fusion.103 fusion"
    assert op_label("%while.4 = (f32[1000000]{0}, s32[]{:T(128)}) while((f32[1]) %t)") == \
        "while.4 while"


def test_recorded_window():
    with gzip.open(RECORDED, "rt") as f:
        r = reduce(json.load(f))
    assert r["window_s"] == pytest.approx(31.7206, abs=1e-3)
    # two converge executables, 3 iterations in all; nothing else ran
    assert r["converge_s"] == pytest.approx(1.5497, abs=1e-3)
    assert r["busy_s"] == pytest.approx(r["converge_s"], rel=1e-3)
    assert r["idle_gaps"][0][0] == "program:plan"
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
    # the bridge gather of the windowed step is the top op
    assert r["device_ops"][0][0] == "fusion.103 fusion"
