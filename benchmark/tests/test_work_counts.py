"""Work counts against numbers worked out by hand."""

import pytest

import work_counts


def test_glm_counts_for_1000_by_8():
    c = work_counts.counts("glm")
    # 2*1000*64 + 4*1000*8 operations; the 1000 x 8 float32 design once
    # and three float32 vectors of 1000
    assert c.step({"rows": 1000, "coefficients": 8}) == {
        "ops": 160000.0, "bytes": 44000.0}


def test_least_time_names_the_bound_and_an_unknown_device_is_an_error():
    lt = work_counts.least_time({"ops": 197e12, "bytes": 819e9 / 2},
                                "TPU v5 lite")
    assert lt["bound"] == "ops" and lt["seconds"] == pytest.approx(1.0)
    lt = work_counts.least_time({"ops": 1.0, "bytes": 819e9}, "TPU v5 lite")
    assert lt["bound"] == "bytes" and lt["seconds"] == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work_counts.least_time({"ops": 1.0, "bytes": 1.0}, "cpu")
