"""Policy SpMVs executed in one traced solve, counted on the device: the
executions of the gather under ``repro.spmv`` that reads the policy rows
(the most over the devices).  Masked GMRES steps count: they run."""

from bench import scopes


def read(facts):
    p = scopes.from_facts(facts)
    if p is None:
        return None
    lo, hi = scopes.window(p, facts)
    n = max(scopes.calls(p, scopes.SPMV, lo, hi).values(), default=0)
    return n or None
