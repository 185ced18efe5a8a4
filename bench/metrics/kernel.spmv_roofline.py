"""Share of the HBM roofline reached by one policy SpMV
(``ops.ell_matvec`` on the greedy policy's rows) on the cell's table."""

from bench.metrics_common import kernel_roofline


def read(facts):
    return kernel_roofline(facts, "spmv")
