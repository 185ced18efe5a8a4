"""Byte and operation counts of the kernels, and the peak table."""

import pytest

from bench import counts


def test_backup_bytes_small_shape():
    # n=2, m=3, k=4: idx+val 2*3*4*(4+4)=192, cost 24, v 8, Tv 8, argmin 8
    assert counts.backup_bytes(2, 3, 4) == 192 + 24 + 8 + 8 + 8


def test_backup_bytes_scale_with_dtypes():
    base = counts.backup_bytes(10, 2, 2)
    half = counts.backup_bytes(10, 2, 2, val_bytes=2, v_bytes=2)
    assert half == base - 10 * 2 * 2 * 2 - 10 * 2 * 2 - 10 * 2 * 2


def test_spmv_bytes_small_shape():
    # n=5, k=3: idx+val 5*3*8=120, x 20, y 20
    assert counts.spmv_bytes(5, 3) == 160


@pytest.mark.parametrize("n,m,k", [(1, 1, 1), (7, 3, 2), (1 << 20, 16, 8)])
def test_flops(n, m, k):
    assert counts.backup_flops(n, m, k) == 2 * n * m * k + 3 * n * m
    assert counts.spmv_flops(n, k) == 2 * n * k


def test_table_bytes_of_the_main_cell():
    # the 1.14 GB table of garnet_1m: idx, val and cost
    n, m, k = 1 << 20, 16, 8
    table = n * m * k * 8 + n * m * 4
    assert counts.backup_bytes(n, m, k) == table + 3 * n * 4
    assert abs(table / 1e9 - 1.14) < 0.01


def test_counts_of_garnet_1m_are_pinned():
    # the single-chip cell's counts, to the byte: a shard argument that
    # defaults to the row count leaves them as they were
    n, m, k = 1 << 20, 16, 8
    assert counts.backup_bytes(n, m, k) == 1_153_433_600
    assert counts.spmv_bytes(n, k) == 75_497_472
    assert counts.backup_bytes(n, m, k, values=n) == 1_153_433_600
    assert counts.spmv_bytes(n, k, values=n) == 75_497_472


def test_counts_of_a_shard_gather_from_the_whole_vector():
    # garnet_16m_x4 under 1d: each chip holds n/4 rows and gathers from
    # the all-gathered n values
    n, m, k, chips = 1 << 24, 16, 8, 4
    rows = n // chips
    assert counts.backup_bytes(rows, m, k, values=n) == (
        rows * m * k * 8 + rows * m * 4 + n * 4 + rows * 4 + rows * 4)
    assert counts.backup_bytes(rows, m, k, values=n) == 4_664_066_048
    assert counts.spmv_bytes(rows, k, values=n) == 352_321_536
    assert counts.spmv_bytes(rows, k, values=n) - counts.spmv_bytes(
        rows, k) == (n - rows) * 4


def test_allgather_bytes():
    # each chip receives the three quarters of the vector it does not hold
    assert counts.allgather_bytes(1 << 24, 4) == 3 * (1 << 22) * 4
    assert counts.allgather_bytes(1000, 1) == 0


def test_exchange_share_against_the_interconnect_peak():
    # 1,600 Gbit/s is 200 GB/s: 50 MB in 1 ms is a quarter of it
    peak = counts.peaks("TPU v5 lite")
    share, bound = counts.roofline_share(0, 50_000_000, 1e-3, peak,
                                         link="ici")
    assert bound == "ici" and share == pytest.approx(25.0)


def test_peaks_v5e():
    p = counts.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("cpu")


def test_roofline_share_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = counts.roofline_share(10, 50, 10.0, peak)
    assert bound == "hbm" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(1000, 1, 20.0, peak)
    assert bound == "flops" and share == pytest.approx(50.0)
