"""gather_roofline: the least time of the window's gather-multiplies
over the gather kernel's device time (``gather_device_s``), in percent.

One gather-multiply must read each edge's source index and weight
(4 B each) and the score table once (4 B per peer), and write one
product per edge (4 B): ``12 E + 4 N`` bytes, with ``E`` the graph's
edges (self-edges dropped), never the plan's padded slots; at the
peak HBM rate of ``benchmark/peaks.json``."""

from pathlib import Path

from benchmark.modules import load_module
from benchmark.roofline import peaks


def gather_bytes(n: int, e: int) -> int:
    return 12 * e + 4 * n


def read(run):
    device_s = load_module(
        Path(__file__).with_name("gather_device_s.py"), "benchmark_metric_"
    ).gather_seconds(run)
    if not device_s:
        return None
    rate = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    least = sum(e.iterations * gather_bytes(e.graph.n, e.edges) for e in run["epochs"]) / rate
    return 100.0 * least / device_s
