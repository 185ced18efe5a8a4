"""Share of the HBM roofline reached by the policy SpMVs inside one
traced solve: the least bytes of one call on the cell's table
(``bench/counts.py``) times the calls counted in the trace, over peak
bandwidth, over their device time under ``repro.spmv``."""

from bench import scopes


def read(facts):
    return scopes.solve_roofline(facts, "spmv", scopes.SPMV)
