"""Share of one traced warm solve in which a collective of the exchange
(device scope ``repro.exchange``: an operation, or an async one between
its start and done) ran on a device and no other operation ran there, the
mean over the devices; GMRES's cross-chip dots, in the solvers' scopes,
count as other operations.  None when no exchange collective ran."""

from bench import scopes


def read(facts):
    return scopes.exposed_share(facts, scopes.EXCHANGE)
