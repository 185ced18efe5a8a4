"""Share of the interconnect's peak (``ici_bits_per_s``) reached by the
all-gathers of the value vector inside one traced solve: the least bytes
one chip receives per all-gather (``bench/counts.py``) times the
all-gathers under ``repro.exchange``, over the peak, over their device
time, on the device that ran the most."""

from bench import scopes


def read(facts):
    return scopes.exchange_roofline(facts)
