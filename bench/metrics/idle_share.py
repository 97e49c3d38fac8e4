"""Share of the traced window in which no operation ran on the device, in
%: 1 - busy union / window."""
from bench.stats import idle_share


def read(run):
    return idle_share(run.trace)
