"""Arithmetic that several per-layer readers share."""

from __future__ import annotations

from bench import counts
from bench import trace as tr


def kernel_roofline(facts: dict, kernel: str):
    """Percent of the roofline one call of ``kernel`` reached, from the
    device time of its named module in the trace; None when the run timed
    no such call (a run on a mesh times none)."""
    k = (facts.get("kernels") or {}).get(kernel)
    trace = facts.get("trace")
    if k is None or trace is None or "module" not in k:
        return None
    per_dev = tr.module_ns(trace, k["module"])
    if not per_dev:
        return None
    seconds = max(per_dev.values()) / 1e9 / k["calls"]
    share, _ = counts.roofline_share(k["flops"], k["bytes"], seconds,
                                     facts["peak"])
    return share


def idle_share(facts: dict, window: str):
    """Percent of the traced ``window`` in which the devices were idle."""
    if facts.get("window") != window:
        return None
    lo, hi = facts["window_ns"]
    busy = tr.busy_ns(facts["trace"], lo, hi)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
