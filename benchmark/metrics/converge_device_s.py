"""converge_device_s: device seconds per window epoch of the converge
executable (the trace's modules whose name holds ``converge``), not the
program's ``converge`` span, which closes before the device is done."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["converge_s"] <= 0:
        return None
    return trace["converge_s"] / len(run["epochs"])
