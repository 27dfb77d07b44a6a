"""counts/gbm.py against numbers worked out by hand."""

import work_counts

SHAPES = {"rows": 1000, "features": 8, "code_bits": 5, "depth": 3}


def test_one_histogram_pass_for_1000_by_8():
    # the scatter's 3 adds per row and feature; 8 five-bit codes a row are
    # 5 bytes, and g, h and the node id 12 more
    assert work_counts.counts("gbm").hist(SHAPES) == {
        "ops": 24000.0, "bytes": 17000.0}


def test_one_tree_of_depth_3_for_1000_by_8():
    # three levels of (one pass + 5 partition bytes a row), then a row's
    # gradients (16 B, 10 ops), leaf totals (12 B, 3 ops), margin (8 B, 1 op)
    assert work_counts.counts("gbm").step(SHAPES) == {
        "ops": 3 * 24000.0 + 14000.0,
        "bytes": 3 * (17000.0 + 5000.0) + 36000.0}


def test_the_cell_is_bytes_bound_and_far_from_a_second():
    full = {"rows": 11_534_336, "features": 28, "code_bits": 5, "depth": 6}
    lt = work_counts.least_time(work_counts.counts("gbm").step(full),
                                "TPU v5 lite")
    assert lt["bound"] == "bytes" and 0.003 < lt["seconds"] < 0.004
