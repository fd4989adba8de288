"""Share of the traced window in which no operation ran on the device."""
from bench.trace import idle_pct


def read(reduced, counters, spec):
    return idle_pct(reduced)
