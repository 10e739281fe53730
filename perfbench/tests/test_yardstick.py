import json

import pytest
import roofline


def test_frozen_ops_per_hash_matches_todays_traced_kernel_body():
    """If this fails, the kernel's hot loop changed: the yardstick stays
    frozen; say in PERF.md what the new body counts."""
    counts = roofline.count_ops_per_hash()
    assert counts["ops_per_hash_ex_casts"] == roofline.OPS_PER_HASH == 3989
    assert counts["carry_casts"] == 414
    assert counts["tile_splats"] == 0


def test_peaks_are_keyed_by_device_kind_and_a_missing_kind_is_an_error():
    assert roofline.peak("TPU v5 lite") == 197e12 / 32
    with open(roofline.PEAKS) as f:
        table = json.load(f)
    v5e = table["TPU v5 lite"]
    assert "derived" in v5e["vpu_u32_ops_per_s_is"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(roofline.UnknownDevice):
        roofline.peak("cpu")
