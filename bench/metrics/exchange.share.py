"""Share of one traced warm solve that the devices spent exchanging the
value vector (device scope ``repro.exchange``: the all-gather, or the halo
ring): self time of its operations over the window, the mean over the
devices."""

from bench import scopes


def read(facts):
    return scopes.share_of_window(facts, scopes.EXCHANGE)
