"""The kernels' operation and byte counts against numbers worked by hand,
and the peaks table."""
from __future__ import annotations

import importlib

import pytest

from bench import roofline

V5E = roofline.peaks("TPU v5 lite")


def counts(kernel, R, W, T):
    return importlib.import_module(f"bench.roofline.{kernel}").ops_bytes(
        R, W, T)


def test_bulk_pass_small_by_hand():
    # 2 rows x 128 workers x 8 tags: two 2x8 by 8x128 contractions,
    # 2 * (2 * 2 * 128 * 8) = 8192 operations; bytes: occupancy 1024, tag
    # rows 16, mask + warmth 512, per-worker numbers 1536, per-row numbers
    # 32, winners 8
    assert counts("bulk_decide_kernel", 2, 128, 8) == (8192, 3128)


def test_item_pass_small_by_hand():
    # same contractions; bytes: 1024 + 16 + mask 256 + 1536 + per-row
    # numbers 24 + validity written 256
    assert counts("affinity_valid_kernel", 2, 128, 8) == (8192, 3112)


def test_full_size_wave_is_memory_bound():
    ops, nbytes = counts("bulk_decide_kernel", 600, 16384, 64)
    assert ops == 2_516_582_400
    assert nbytes == 1_048_576 + 38_400 + 19_660_800 + 196_608 + 9_600 \
        + 2_400
    t, which = roofline.bound_s("bulk_decide_kernel", 600, 16384, 64, V5E)
    assert which == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert ops / 197e12 < t


def test_peaks_table():
    assert V5E["flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in V5E["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
