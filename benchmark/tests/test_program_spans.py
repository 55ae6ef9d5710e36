"""The program-span summary behind the host-stage and converge metrics,
on hand-made events, and its readers on a run with no trace file."""

import pytest

from benchmark import program_spans
from benchmark.program_spans import summarize


def _trace(host, modules, ops=None):
    ops = ops if ops is not None else [[n.replace("jit_", "%"), s, d] for n, s, d in modules]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
            "host": host}


def test_spans_converge_host_time_and_the_whole_idle_split():
    host = [["bench:window", 0, 100e9], ["bench:epoch", 0, 100e9],
            ["bench:converge_prepared", 5e9, 90e9],
            ["program:plan", 10e9, 30e9], ["program:plan_select", 10e9, 5e9],
            ["program:converge", 50e9, 12e9], ["program:monitors", 62e9, 8e9],
            ["program:plan", 120e9, 5e9]]           # after the window: not counted
    # The converge executable runs 10 s inside its span; 2 s of the span
    # are dispatch and fetch.
    r = summarize(_trace(host, [["jit_converge_windowed(1)", 51e9, 10e9]]))
    assert r["spans"] == pytest.approx(
        {"plan": 30, "plan_select": 5, "converge": 12, "monitors": 8})
    assert r["converge_host_s"] == pytest.approx(2)
    # 90 s idle: named by the innermost annotation, none cut off.
    assert r["idle"] == pytest.approx({
        "bench:epoch": 5 + 5, "bench:converge_prepared": 5 + 10 + 25,
        "program:plan": 25, "program:plan_select": 5,
        "program:converge": 2, "program:monitors": 8,
    })


def test_converge_device_time_is_clipped_to_the_span():
    # A span that closes on dispatch holds the start of the executable
    # only: its host time is never negative.
    host = [["bench:window", 0, 100e9], ["program:converge", 10e9, 1e9]]
    r = summarize(_trace(host, [["jit_converge_csr(1)", 10.5e9, 5e9]]))
    assert r["converge_host_s"] == pytest.approx(0.5)


def test_a_program_without_spans_gives_nothing_to_read():
    host = [["bench:window", 0, 10e9], ["bench:converge_prepared", 0, 10e9]]
    r = summarize(_trace(host, []))
    assert r["spans"] == {} and r["converge_host_s"] is None
    assert r["idle"] == {"bench:converge_prepared": 10}


def test_readers(monkeypatch):
    host = [["bench:window", 0, 100e9], ["bench:epoch", 0, 100e9],
            ["program:warm_seed", 0, 2e9], ["program:converge", 50e9, 12e9]]
    found = summarize(_trace(host, [["jit_converge_windowed(1)", 51e9, 10e9]]))
    monkeypatch.setattr(program_spans, "summary", lambda chips: found)
    run = {"trace": {}, "epochs": [0, 1], "device": {"count": 1}}
    assert program_spans.span_seconds(run, "warm_seed") == pytest.approx(1)
    assert program_spans.span_seconds(run, "monitors") is None
    assert program_spans.converge_host_seconds(run) == pytest.approx(1)
    # idle 90 s: 2 under warm_seed and 2 under converge are named
    assert program_spans.idle_unattributed_share(run) == pytest.approx(100 * 86 / 90)
    assert program_spans.span_seconds(dict(run, trace=None), "warm_seed") is None


def test_no_trace_file_reads_none(monkeypatch, tmp_path):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    run = {"trace": {}, "epochs": [0], "device": {"count": 1}}
    assert program_spans.span_seconds(run, "warm_seed") is None
    assert program_spans.idle_unattributed_share(run) is None
