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


def backup_bytes(n: int, m: int, k: int, *, values: int | None = None,
                 idx_bytes: int = 4, val_bytes: int = 4,
                 v_bytes: int = 4) -> int:
    """Fused Bellman backup ``(min_a Q, argmin_a Q)`` over an ``(n, m, k)``
    ELL table: ``idx`` and ``val`` (n m k) and ``cost`` (n m) read once,
    the ``values`` entries of ``v`` it gathers from read once (``n``,
    unless the rows are one chip's shard and gather from the whole
    exchanged vector), ``Tv`` and the int32 argmin (n each) written."""
    values = n if values is None else values
    return (n * m * k * (idx_bytes + val_bytes) + n * m * val_bytes
            + values * v_bytes + n * v_bytes + n * 4)


def backup_flops(n: int, m: int, k: int) -> int:
    """A multiply and an add per table entry, then ``cost + gamma * .``
    and the comparison of the min per (state, action)."""
    return 2 * n * m * k + 3 * n * m


def spmv_bytes(n: int, k: int, *, values: int | None = None,
               idx_bytes: int = 4, val_bytes: int = 4,
               x_bytes: int = 4) -> int:
    """Policy-restricted SpMV ``y = P_pi x`` over ``(n, k)`` rows:
    ``idx`` and ``val`` read once, the ``values`` entries of ``x`` read
    once (``n`` unless the rows are a shard), ``y`` written."""
    values = n if values is None else values
    return n * k * (idx_bytes + val_bytes) + values * x_bytes + n * x_bytes


def allgather_bytes(n: int, chips: int) -> int:
    """Least bytes one chip receives in a tiled all-gather of an ``n``
    float32 vector split evenly over ``chips``: every entry it does not
    hold."""
    return (n - n // chips) * 4


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


def roofline_share(flops: int, nbytes: int, seconds: float, peak: dict,
                   link: str = "hbm") -> tuple[float, str]:
    """``(percent, bound)``: the least time the chip could take, the larger
    of operations over peak FLOP/s and bytes over the peak bandwidth of
    ``link`` (``"hbm"``, or ``"ici"``: the interconnect, all of a chip's
    links), as a share of ``seconds``; ``bound`` names which of the two
    it was."""
    t_flops = flops / peak["flops_per_s"]
    per_s = peak["hbm_bytes_per_s"] if link == "hbm" \
        else peak["ici_bits_per_s"] / 8
    t_bytes = nbytes / per_s
    bound = link if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
