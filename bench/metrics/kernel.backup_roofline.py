"""Share of the HBM roofline reached by one Bellman backup
(``ops.ell_backup`` through the dispatch point) on the cell's table: the
least bytes it must move over peak bandwidth, over its device time."""

from bench.metrics_common import kernel_roofline


def read(facts):
    return kernel_roofline(facts, "backup")
