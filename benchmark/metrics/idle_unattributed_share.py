"""idle_unattributed_share: percent of the device's idle time in the
traced window during which the innermost host annotation was not one of
the program's spans (``program:*``): idle time the program does not
name."""

from benchmark.program_spans import idle_unattributed_share


def read(run):
    return idle_unattributed_share(run)
