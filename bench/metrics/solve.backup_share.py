"""Share of one traced warm solve that the device spent in the Bellman
backup (device scope ``repro.backup``): self time of its operations over
the window, the mean over the devices."""

from bench import scopes


def read(facts):
    return scopes.share_of_window(facts, scopes.BACKUP)
