"""``gather_device_s`` and ``gather_roofline`` read the windowed gather
kernel out of the device trace: on hand-made events holding the op
label the chip's profiler gives the kernel, and on the recorded
``c4-trickle`` window."""

import gzip
import json
from dataclasses import dataclass
from pathlib import Path

import pytest

from benchmark import program_spans, run, trace
from benchmark.trace import reduce

RECORDED = Path(__file__).parent / "data" / "c4-trickle-trace.json.gz"
#: The gather kernel's op as the v5e profiler names it, in a
#: ``c4-trickle`` window: the table block, and the chunk-id operand
#: beside ``wid``.
KERNEL_OP = (
    "%gather_windowed.8 = f32[419328,128]{1,0:T(8,128)} custom-call(s32[52416]{0:T(1024)} "
    "%get-tuple-element.763, s32[819]{0:T(1024)S(1)} %fusion.109, "
    "f32[7816,128]{1,0:T(8,128)S(1)} %pad_bitcast_fusion.2, s32[419328,128]{1,0:T(8,128)} "
    "%get-tuple-element.764, f32[419328,128]{1,0:T(8,128)} %get-tuple-element.765), "
    "custom_call_target=\"tpu_custom_call\""
)


@dataclass
class _Graph:
    n: int


@dataclass
class _Epoch:
    graph: _Graph
    edges: int
    iterations: int


def _run(extracted, monkeypatch, epochs):
    """A run whose newest trace is ``extracted``."""
    monkeypatch.setattr(program_spans, "newest_trace", lambda: "window.xplane.pb")
    monkeypatch.setattr(trace, "extract", lambda path, chips: extracted)
    return {"epochs": epochs, "trace": reduce(extracted),
            "device": {"kind": "TPU v5 lite", "count": 1}}


def test_hand_made_kernel_events(monkeypatch):
    host = [["bench:window", 0, 100e9]]
    ops = [[KERNEL_OP, 10e9, 2e-3 * 1e9], [KERNEL_OP, 20e9, 2e-3 * 1e9],
           ["%fusion.105 = f32[8]{0} fusion(x)", 30e9, 1e9]]
    extracted = {"devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}},
                 "host": host}
    epochs = [_Epoch(_Graph(1_000_000), 50_000_000, 1)] * 2
    r = _run(extracted, monkeypatch, epochs)
    assert run.read_metric("gather_device_s", r) == pytest.approx(2e-3)
    least = 2 * (12 * 50_000_000 + 4 * 1_000_000) / 819e9
    assert run.read_metric("gather_roofline", r) == pytest.approx(100 * least / 4e-3)


def test_a_trace_without_the_kernel_gives_nothing(monkeypatch):
    host = [["bench:window", 0, 10e9]]
    ops = [["%fusion.1 = f32[8]{0} fusion(x)", 1e9, 1e9]]
    extracted = {"devices": {"/device:TPU:0": {"XLA Ops": ops}}, "host": host}
    r = _run(extracted, monkeypatch, [_Epoch(_Graph(10), 100, 1)])
    assert run.read_metric("gather_device_s", r) is None
    assert run.read_metric("gather_roofline", r) is None
    assert run.read_metric("gather_device_s", dict(r, trace=None)) is None


def test_the_recorded_window(monkeypatch):
    """Two epochs, three iterations at 1M peers / 50M edges: the kernel
    ran once an iteration, under the least time of its bytes."""
    extracted = json.loads(gzip.decompress(RECORDED.read_bytes()))
    epochs = [_Epoch(_Graph(1_000_000), 49_999_950, k) for k in (2, 1)]
    r = _run(extracted, monkeypatch, epochs)
    found = [o for o in r["trace"]["device_ops"] if o[0].startswith("gather_windowed")]
    assert found == [] or found[0][0] == "gather_windowed.8 custom-call"
    per_epoch = run.read_metric("gather_device_s", r)
    assert 0 < per_epoch < 0.05
    share = run.read_metric("gather_roofline", r)
    assert 0 < share < 100
