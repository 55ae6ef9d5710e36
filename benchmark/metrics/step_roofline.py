"""step_roofline: the least time of the window's power iterations (each
over its epoch's graph, ``benchmark/roofline.py``) over the device time
of the converge executable, in percent."""

from benchmark.roofline import least_seconds, peaks


def read(run):
    trace = run["trace"]
    if trace is None or trace["converge_s"] <= 0:
        return None
    peak = peaks(run["device"]["kind"])
    least = sum(e.iterations * least_seconds(e.graph.n, e.edges, peak) for e in run["epochs"])
    return 100.0 * least / trace["converge_s"]
