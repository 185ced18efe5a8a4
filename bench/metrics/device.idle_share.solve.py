"""Share of one traced warm solve in which no operation ran on the
device, the mean over the devices."""

from bench.metrics_common import idle_share


def read(facts):
    return idle_share(facts, "solve")
