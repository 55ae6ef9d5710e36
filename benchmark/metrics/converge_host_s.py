"""converge_host_s: seconds per window epoch of the program's
``converge`` spans less the converge executable's device time inside
them: dispatch, the fetch of the result and the host's conversions."""

from benchmark.program_spans import converge_host_seconds


def read(run):
    return converge_host_seconds(run)
