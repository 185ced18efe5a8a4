"""Operations and bytes of the solver's kernels, from their shapes.

These are the least bytes each call must move, whatever implements it:
every table entry read once, the value vector read once, every output
written once.  A gather that re-reads values, or a layout that pads, moves
more; the roofline share then reads below 100%, never above.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def backup_bytes(n: int, m: int, k: int, *, idx_bytes: int = 4,
                 val_bytes: int = 4, v_bytes: int = 4) -> int:
    """Fused Bellman backup ``(min_a Q, argmin_a Q)`` over an ``(n, m, k)``
    ELL table: ``idx`` and ``val`` (n m k) and ``cost`` (n m) read once,
    ``v`` (n) read once, ``Tv`` and the int32 argmin (n each) written."""
    return (n * m * k * (idx_bytes + val_bytes) + n * m * val_bytes
            + n * v_bytes + n * v_bytes + n * 4)


def backup_flops(n: int, m: int, k: int) -> int:
    """A multiply and an add per table entry, then ``cost + gamma * .``
    and the comparison of the min per (state, action)."""
    return 2 * n * m * k + 3 * n * m


def spmv_bytes(n: int, k: int, *, idx_bytes: int = 4, val_bytes: int = 4,
               x_bytes: int = 4) -> int:
    """Policy-restricted SpMV ``y = P_pi x`` over ``(n, k)`` rows:
    ``idx`` and ``val`` read once, ``x`` read once, ``y`` written."""
    return n * k * (idx_bytes + val_bytes) + n * x_bytes + n * x_bytes


def spmv_flops(n: int, k: int) -> int:
    return 2 * n * k


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; a device missing from
    the table is an error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; add its published numbers")
    return table[device_kind]


def roofline_share(flops: int, nbytes: int, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """``(percent, bound)``: the least time the chip could take, the larger
    of operations over peak FLOP/s and bytes over peak bandwidth, as a
    share of ``seconds``; ``bound`` names which of the two it was."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
