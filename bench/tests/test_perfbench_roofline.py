"""Operations and bytes of a Parzen call against counts made by hand."""
import importlib.util

import pytest

import registry

_spec = importlib.util.spec_from_file_location(
    "roofline_parzen", registry.HERE / "roofline" / "parzen.py")
roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(roofline)


def test_hot_cell_shape_by_hand():
    # 64 candidates x 8,192 observations in 24 dims: a (64, 25) x (25, 8192)
    # contraction is 64 * 8192 * 25 multiply-adds; operands are read once
    # and 64 scores written, 4 bytes each
    assert roofline.flops(64, 8192, 24) == 26_214_400
    assert roofline.bytes_moved(64, 8192, 24) == 4 * (1600 + 204_800 + 64)
    t, bound = roofline.least_time(64, 8192, 24, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(825_856 / 819e9)


def test_small_tenant_shape_by_hand():
    # 64 x 32 in 4 dims: 2 * 64 * 32 * 5 operations, (320 + 160 + 64) * 4
    assert roofline.flops(64, 32, 4) == 20_480
    assert roofline.bytes_moved(64, 32, 4) == 2_176
    t, bound = roofline.least_time(64, 32, 4, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(2_176 / 819e9)


def test_compute_bound_when_operations_outweigh_bytes():
    # 4,096 candidates make the contraction the larger term
    t, bound = roofline.least_time(4096, 8192, 24, "TPU v5 lite")
    assert bound == "compute"
    assert t == pytest.approx(2 * 4096 * 8192 * 25 / 197e12)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_time(64, 8192, 24, "cpu")
