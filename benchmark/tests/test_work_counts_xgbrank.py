"""counts/xgbrank.py against numbers worked out by hand."""

import work_counts

SHAPES = {"rows": 1000, "rank_rows": 900, "features": 8, "code_bits": 8,
          "depth": 3, "pairs": 5000, "pair_slots": 10 ** 7}


def test_one_histogram_pass_is_the_gbm_cells():
    assert work_counts.counts("xgbrank").hist(SHAPES) == \
        work_counts.counts("gbm").hist(SHAPES)


def test_one_pairwise_pass_counts_real_pairs_only():
    # 12 operations an ordered pair with r_i > r_j; a row's margin read and
    # its (g, h) written: 12 bytes; the padded slots are not counted
    assert work_counts.counts("xgbrank").pairs(SHAPES) == {
        "ops": 60000.0, "bytes": 10800.0}
    more_padding = dict(SHAPES, pair_slots=10 ** 9)
    assert work_counts.counts("xgbrank").pairs(more_padding) == \
        work_counts.counts("xgbrank").pairs(SHAPES)


def test_one_round_of_depth_3_for_1000_by_8():
    # the pass, then three levels of (one histogram pass + 5 partition bytes
    # a row), leaf totals (12 B, 3 ops) and the margin update (8 B, 1 op)
    one = work_counts.counts("xgbrank").hist(SHAPES)
    assert work_counts.counts("xgbrank").step(SHAPES) == {
        "ops": 60000.0 + 3 * one["ops"] + 4000.0,
        "bytes": 10800.0 + 3 * (one["bytes"] + 5000.0) + 20000.0}


def test_the_cell_is_bytes_bound_and_its_pairs_are_microseconds():
    full = {"rows": 2_359_296, "rank_rows": 2_270_296, "features": 136,
            "code_bits": 8, "depth": 6, "pairs": 109_448_989,
            "pair_slots": 29_664_593_955}
    step = work_counts.least_time(work_counts.counts("xgbrank").step(full),
                                  "TPU v5 lite")
    assert step["bound"] == "bytes" and 0.002 < step["seconds"] < 0.004
    rank = work_counts.least_time(work_counts.counts("xgbrank").pairs(full),
                                  "TPU v5 lite")
    assert rank["bound"] == "bytes" and 2e-5 < rank["seconds"] < 5e-5
