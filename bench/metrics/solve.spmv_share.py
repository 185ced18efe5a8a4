"""Share of one traced warm solve that the device spent in the policy
SpMV (device scope ``repro.spmv``): self time of its operations over the
window, the mean over the devices."""

from bench import scopes


def read(facts):
    return scopes.share_of_window(facts, scopes.SPMV)
