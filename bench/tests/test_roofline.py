"""The scorer's byte count depends on shapes only; peaks are keyed by
device kind."""

import numpy as np
import pytest

from bench import roofline


def test_call_bytes_is_a_function_of_shapes_only():
    rng = np.random.default_rng(0)
    shapes = [(256, 18, 54), (16384, 18, 577), (1, 18, 1)]
    for n, v, o in shapes:
        a = rng.integers(1, 512, size=(n, v))
        b = rng.integers(1, 512, size=(n, v))
        assert roofline.call_bytes(*a.shape, o) == \
            roofline.call_bytes(*b.shape, o)
        assert roofline.call_bytes(n, v, o) == n * v + 44 * o + 4 * n


def test_call_bytes_grows_with_each_shape():
    base = roofline.call_bytes(256, 18, 54)
    assert roofline.call_bytes(512, 18, 54) > base
    assert roofline.call_bytes(256, 19, 54) > base
    assert roofline.call_bytes(256, 18, 55) > base


def test_peaks_of_v5e_and_unknown_device():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_roofline_share():
    # 819 bytes at 819 GB/s take 1 ns; a 100 ns kernel is at 1%
    assert roofline.hbm_roofline_pct(819, 100e-9, "TPU v5 lite") == \
        pytest.approx(1.0)
