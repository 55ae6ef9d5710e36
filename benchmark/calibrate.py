"""Readings that the limits in ``benchmark/cells/<cell>.json`` are set
from, on the chip, at the cell's own size, many seeds in one process:

    python3 -m benchmark.calibrate --workload <cell> --seeds 1,2,3 --control 2 --seconds 1

For every seed, a normal run of the cell (set-up, a window of
``--seconds``, the reference check) gives the program's numbers: the
lower readings.  For the first ``--control`` seeds it also reads, over
the same epochs and against the same reference answers:

- ``control``: the reference in bfloat16 put in the program's place
  (``reference.Chain(round_to="bfloat16")``): the upper readings;
- ``state_unchanged``: each window epoch answered with the previous
  epoch's scores, as a converge that returns its start vector would;
- ``half_batch``: the reference run over every second edge only.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run as bench


def _worst(values: list[dict]) -> dict:
    return {k: max(v[k] for v in values) for k in values[0]}


def readings(spec, seed: int, seconds: float, device: dict, faults: bool) -> dict:
    from benchmark.reference import Chain, gaps
    from benchmark.traffic.generate import Graph

    measured = bench.measure(spec, seed, seconds, False, device)
    result = bench.judge(spec, measured)
    keys = list(spec.limits)
    out = {
        "seed": seed,
        "correct": result["correct"],
        "epochs": len(measured.epochs),
        "iterations": [e.iterations for e in measured.epochs],
        "program": _worst([{k: e.gaps[k] for k in keys} for e in measured.epochs]),
    }
    if faults:
        trust = spec.config["trust"]
        all_epochs = measured.setup + measured.epochs
        window = range(len(measured.setup), len(all_epochs))
        control, half = Chain(trust, "bfloat16"), Chain(trust)
        c_gaps, h_gaps, s_gaps = [], [], []
        for i, ep in enumerate(all_epochs):
            g = ep.graph
            c = control.epoch(g)
            h = half.epoch(Graph(g.n, g.src[::2], g.dst[::2], g.weight[::2], g.pre_trusted))
            if i in window:
                c_gaps.append(gaps(c.scores, ep.ref))
                h_gaps.append(gaps(h.scores, ep.ref))
                s_gaps.append(gaps(all_epochs[i - 1].scores, ep.ref))
        out.update(
            control=_worst(c_gaps),
            control_min={k: min(v[k] for v in c_gaps) for k in keys},
            half_batch={k: min(v[k] for v in h_gaps) for k in keys},
            state_unchanged={k: min(v[k] for v in s_gaps) for k in keys},
        )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", type=int, default=3, help="seeds that also read faults")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    spec = bench.load_spec(args.workload)
    bench.prepare_environment()
    device = bench.require_devices(spec.chips)
    bench.cache_every_program()
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows.append(readings(spec, seed, args.seconds, device, i < args.control))
        print("reading " + json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in spec.limits:
        summary[k] = {
            "program_max": max(r["program"][k] for r in rows),
            **{
                f"{f}_min": min(r[f if f != "control" else "control_min"][k]
                                for r in rows if f in r)
                for f in ("control", "half_batch", "state_unchanged")
                if any(f in r for r in rows)
            },
        }
    print("summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
