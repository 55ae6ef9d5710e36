"""converge_device_s: device seconds per window epoch of the converge
executable, read from the device trace (the modules whose name holds
``converge``); the host's share of the program's ``converge`` span is
``converge_host_s``."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["converge_s"] <= 0:
        return None
    return trace["converge_s"] / len(run["epochs"])
