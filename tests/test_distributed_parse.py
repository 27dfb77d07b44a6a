"""Distributed ingest: N processes × byte ranges must reproduce the
single-process parse bit-identically (ParseDataset.MultiFileParseTask +
Categorical merge semantics)."""

import csv
import os

import numpy as np
import pytest

from tests.multiproc_util import run_workers


def _write_tricky_csv(path, n=997, seed=3):
    """Numerics with NAs, categoricals with NAs, a column that is numeric in
    the first half but categorical later (forces the cross-process type
    vote), and a quoted-string column."""
    rng = np.random.default_rng(seed)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["num", "cat", "late_cat", "allnum", "biglate"])
        for i in range(n):
            num = "" if i % 53 == 0 else f"{rng.normal():.6f}"
            cat = "NA" if i % 41 == 0 else f"lvl{int(rng.integers(0, 23))}"
            late = (f"{i % 7}" if i < n // 2 + 11
                    else f"tag{int(rng.integers(0, 5))}")
            # big magnitudes only in the SECOND half: the f32-downcast
            # decision must be voted globally, not per shard
            big = str(100 + i) if i < n // 2 else str((1 << 25) + i)
            w.writerow([num, cat, late, str(i * 2), big])


def test_byte_range_semantics(tmp_path):
    from h2o3_tpu.frame.distributed_parse import byte_range, read_range_lines

    p = tmp_path / "t.csv"
    lines = [f"row{i},{i}" for i in range(100)]
    p.write_text("\n".join(lines) + "\n")
    size = os.path.getsize(p)
    got = []
    for r in range(3):
        s, e = byte_range(size, r, 3)
        got.extend(read_range_lines(str(p), s, e))
    assert got == lines  # every line exactly once, in order


def test_single_process_identical(tmp_path, cloud1):
    """1-process distributed path ≡ parse_csv exactly."""
    from h2o3_tpu.frame.distributed_parse import parse_csv_distributed
    from h2o3_tpu.frame.parse import parse_csv

    p = str(tmp_path / "t.csv")
    _write_tricky_csv(p)
    a = parse_csv(p)
    b = parse_csv_distributed(p)
    assert a.names == b.names
    for n in a.names:
        va, vb = a.vec(n), b.vec(n)
        assert va.type == vb.type, n
        assert va.data.dtype == vb.data.dtype, n
        assert (va.domain or []) == (vb.domain or []), n
        np.testing.assert_array_equal(
            np.asarray(va.data, np.float64), np.asarray(vb.data, np.float64))
    assert b.dist.global_nrow == a.nrow


def test_two_process_bit_identical(tmp_path):
    """2 processes under jax.distributed: concatenated shards ≡ the
    single-process Frame (codes AND domains), global row facts correct."""
    from h2o3_tpu.frame.parse import parse_csv

    p = str(tmp_path / "t.csv")
    _write_tricky_csv(p)
    ref = parse_csv(p)

    body = f"""
    import numpy as np
    from h2o3_tpu.frame.distributed_parse import parse_csv_distributed
    fr = parse_csv_distributed({p!r})
    rank = fr.dist.process_index
    np.savez({str(tmp_path)!r} + f"/shard{{rank}}.npz",
             offset=fr.dist.row_offset, gn=fr.dist.global_nrow,
             **{{f"c_{{n}}": np.asarray(fr.vec(n).data, np.float64)
                for n in fr.names}},
             **{{f"d_{{n}}": np.asarray(fr.vec(n).domain or [], dtype=object)
                for n in fr.names}},
             **{{f"t_{{n}}": np.asarray([str(fr.vec(n).data.dtype)])
                for n in fr.names}})
    print("rank", rank, "rows", fr.dist.local_nrow)
    """
    run_workers(2, body)

    sh = [np.load(tmp_path / f"shard{r}.npz", allow_pickle=True)
          for r in range(2)]
    assert int(sh[0]["gn"]) == ref.nrow == int(sh[1]["gn"])
    assert int(sh[1]["offset"]) == len(sh[0]["c_num"])
    assert ref.vec("biglate").data.dtype == np.float64  # the vote matters
    for r in range(2):
        assert str(sh[r]["t_biglate"][0]) == "float64", r
    for n in ref.names:
        whole = np.concatenate([sh[0][f"c_{n}"], sh[1][f"c_{n}"]])
        np.testing.assert_array_equal(
            whole, np.asarray(ref.vec(n).data, np.float64), err_msg=n)
        for r in range(2):
            assert list(sh[r][f"d_{n}"]) == (ref.vec(n).domain or []), n
