"""Share of the HBM roofline reached by the Bellman backups inside one
traced solve: the least bytes of one backup on the cell's table times the
calls counted in the trace, over peak bandwidth, over their device time
under ``repro.backup``."""

from bench import scopes


def read(facts):
    return scopes.solve_roofline(facts, "backup", scopes.BACKUP)
