"""Share of one traced warm solve in which every device was idle while
the host was inside the driver's control fetch or result readback
(``repro.driver.sync``, ``repro.driver.readback``)."""

from bench import scopes


def read(facts):
    p = scopes.from_facts(facts)
    if p is None:
        return None
    lo, hi = scopes.window(p, facts)
    idle = scopes.idle_inside_ns(p, scopes.HOST_WAITS, lo, hi)
    return None if idle is None else 100.0 * idle / (hi - lo)
