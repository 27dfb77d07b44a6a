"""Parallel chunked ingest (ISSUE 2): chunk-boundary correctness, NA/dtype
parity, vectorized-coercion parity, parse_setup fixes, and ingest
observability. The load-bearing invariant: `parse_csv` output (names,
types, dtypes, domains, codes, NaN placement) is BIT-IDENTICAL across
1-chunk, N-chunk, and the seed per-line (H2O3_INGEST_LEGACY) pipelines."""

import csv
import os
import time

import numpy as np
import pytest

from h2o3_tpu.frame import chunked, ingest_stats
from h2o3_tpu.frame.parse import (_split_lines, _tokenize_numpy, parse_csv,
                                  parse_setup)
from h2o3_tpu.frame.vec import bulk_try_numeric


def _cmp_frames(a, b, msg=""):
    assert a.names == b.names, msg
    assert a.nrow == b.nrow, msg
    for n in a.names:
        va, vb = a.vec(n), b.vec(n)
        assert va.type == vb.type, (msg, n, va.type, vb.type)
        assert (va.domain or []) == (vb.domain or []), (msg, n)
        if va.type == "string":
            assert [str(x) for x in va.to_numpy()] \
                == [str(x) for x in vb.to_numpy()], (msg, n)
            continue
        assert va.data.dtype == vb.data.dtype, (msg, n)
        np.testing.assert_array_equal(
            np.asarray(va.data, np.float64), np.asarray(vb.data, np.float64),
            err_msg=f"{msg}:{n}")


def _legacy_parse(path, **kw):
    os.environ["H2O3_INGEST_LEGACY"] = "1"
    try:
        return parse_csv(path, **kw)
    finally:
        del os.environ["H2O3_INGEST_LEGACY"]


# -- chunk planning ----------------------------------------------------------
def test_plan_chunks_partition_at_line_boundaries():
    data = b"".join(b"row%d,%d\n" % (i, i) for i in range(500))
    chunks = chunked.plan_chunks(data, 256)
    assert chunks[0][0] == 0 and chunks[-1][1] == len(data)
    for (_, e1), (s2, _) in zip(chunks, chunks[1:]):
        assert e1 == s2                       # no gaps, no overlap
        assert data[e1 - 1:e1] == b"\n"       # cut right after a newline


def test_plan_chunks_heals_quoted_newlines():
    # every record holds a quoted field with embedded newline + separator;
    # no boundary may land on the quoted (inner) newlines
    rec = b'1,"a,b\nc",2\n'
    data = rec * 200
    chunks = chunked.plan_chunks(data, 40)
    assert len(chunks) > 3
    for _, e in chunks[:-1]:
        assert (e - len(rec) * (e // len(rec))) == 0, \
            "boundary inside a quoted field"


def test_plan_chunks_unbalanced_quote_degrades_to_one_chunk():
    data = b'x,"unterminated\n' + b"1,2\n" * 100
    assert chunked.plan_chunks(data, 64) == [(0, len(data))]


# -- bit-identity across chunkings -------------------------------------------
def _write_tricky(path, n=400):
    rng = np.random.default_rng(7)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["num", "cat", "q", "big", "ws"])
        for i in range(n):
            num = "" if i % 37 == 0 else f"{rng.normal():.6f}"
            cat = "NA" if i % 29 == 0 else f"lvl{int(rng.integers(0, 17))}"
            if i % 11 == 0:
                q = f"with,{i} comma"              # quoted separator
            elif i % 83 == 5:
                q = f"line1\nline2_{i}"            # quoted embedded newline
            else:
                q = f"t{i % 5}"
            big = str((1 << 25) + i) if i > n // 2 else str(i)
            w.writerow([num, cat, q, big, f" pad{i % 3} "])
    return path


def test_chunked_vs_single_vs_legacy_bit_identical(tmp_path):
    p = _write_tricky(str(tmp_path / "t.csv"))
    single = parse_csv(p, chunk_bytes=1 << 30)
    for cb, nt in ((64, 1), (256, 4), (1024, 2)):
        _cmp_frames(single, parse_csv(p, chunk_bytes=cb, nthreads=nt),
                    f"cb={cb},nt={nt}")
    _cmp_frames(single, _legacy_parse(p), "legacy")


def test_quoted_token_wider_than_all_plain_tokens(tmp_path):
    """A quoted cell wider than every plain token used to overrun the
    fast tokenizer's byte pad (sized from OK-row token widths only) and
    IndexError the whole parse (PR 3 review repro)."""
    p = str(tmp_path / "wide.csv")
    with open(p, "w") as f:
        f.write('a,b\n"q,uoted,with,long,separators,inside",2\nx,3\n')
    fr = parse_csv(p)
    assert fr.nrow == 2
    _cmp_frames(fr, _legacy_parse(p), "legacy")


def test_quoted_field_straddling_chunk_split(tmp_path):
    """A quoted field containing the separator AND an embedded newline that
    straddles the chunk split must parse identically to the single-chunk
    path (the ISSUE acceptance pin)."""
    p = str(tmp_path / "q.csv")
    rows = ["h1,h2,h3"]
    for i in range(60):
        rows.append(f'{i},"x,{i}\nconts_{i}",tail{i}')
    with open(p, "w") as f:
        f.write("\n".join(rows) + "\n")
    single = parse_csv(p, chunk_bytes=1 << 30)
    # tiny chunks force boundaries into/around every quoted field
    for cb in (16, 32, 64, 128):
        _cmp_frames(single, parse_csv(p, chunk_bytes=cb, nthreads=3),
                    f"cb={cb}")
    _cmp_frames(single, _legacy_parse(p), "legacy")


def test_na_token_and_dtype_parity(tmp_path):
    p = str(tmp_path / "na.csv")
    with open(p, "w") as f:
        f.write("a,b,c,d\n")
        for i in range(100):
            a = ["", "NA", "na", "nan", str(i * 0.5)][i % 5]
            b = str((1 << 25) + i)       # forces float64 (no f32 downcast)
            c = ["", "NA", f"lv{i % 3}"][i % 3]
            f.write(f"{a},{b},{c},{i}\n")
    single = parse_csv(p, chunk_bytes=1 << 30)
    many = parse_csv(p, chunk_bytes=64, nthreads=4)
    legacy = _legacy_parse(p)
    _cmp_frames(single, many, "many")
    _cmp_frames(single, legacy, "legacy")
    assert single.vec("b").data.dtype == np.float64
    assert single.vec("d").data.dtype == np.float32
    assert single.vec("a").type == "real"
    assert single.vec("a").nacnt() == legacy.vec("a").nacnt() > 0
    assert single.vec("c").type == "enum"
    assert (np.asarray(single.vec("c").data) == -1).sum() > 0


def test_fast_path_fallbacks_stay_identical(tmp_path):
    # non-ASCII content, lone \r line breaks, and NUL bytes all route the
    # affected chunk to the generic tokenizer — results must not change
    cases = {
        "uni.csv": "x,y\n1,café\n2,naïve\n3,plain\n4,plain\n",
        "lone_cr.csv": "x,y\n1,a\r2,b\n3,c\n",
        "nul.csv": "x,y\n1,a\n2,b\x00b\n3,c\n",
    }
    for name, text in cases.items():
        p = str(tmp_path / name)
        with open(p, "w", newline="") as f:
            f.write(text)
        single = parse_csv(p, chunk_bytes=1 << 30)
        _cmp_frames(single, parse_csv(p, chunk_bytes=8, nthreads=2), name)
        _cmp_frames(single, _legacy_parse(p), name + ":legacy")


def test_crlf_and_whitespace_strip_parity(tmp_path):
    p = str(tmp_path / "ws.csv")
    with open(p, "w", newline="") as f:
        f.write('a,b,c\r\n 1 , x y ,"  keep  "\r\n2,\tz\t,w\r\n'
                '3, "qq" ,v\r\n,,\r\n9 , 8, 7 \r\n')
    single = parse_csv(p, chunk_bytes=1 << 30)
    _cmp_frames(single, parse_csv(p, chunk_bytes=8, nthreads=3), "crlf")
    _cmp_frames(single, _legacy_parse(p), "crlf:legacy")
    assert "  keep  " in (single.vec("c").domain or [])   # quoting preserved


def test_tokenize_lines_matches_split_lines():
    lines = ['1,2,3', 'a,"b,c",d', ' x ,y,', 'only', '1,2,3,4,5',
             '"q""uote",2,3']
    ref = _split_lines(lines, ",", 3)
    got, info = chunked.tokenize_lines(lines, ",", 3, nthreads=2,
                                       block_rows=2)
    assert info["n_chunks"] == 3
    for c in range(3):
        assert [str(v) for v in got[c]] == [str(v) for v in ref[c]], c


def test_tokenize_data_matches_tokenize_numpy(tmp_path):
    p = _write_tricky(str(tmp_path / "t.csv"), n=120)
    ref = _tokenize_numpy(p, ",", True, 5)
    with open(p, "rb") as f:
        data = f.read()
    got, info = chunked.tokenize_data(data, ",", True, 5, nthreads=2,
                                      chunk_bytes=512, use_native=False)
    assert info["n_chunks"] > 1

    def tok(v):   # fast chunks carry ASCII bytes tokens
        return v.decode() if isinstance(v, bytes) else str(v)

    for c in range(5):
        assert [tok(v) for v in got[c]] == [str(v) for v in ref[c]], c


# -- native per-chunk tokenizer ----------------------------------------------
def test_native_chunked_numeric_parity(tmp_path):
    from h2o3_tpu.native import loader

    if not loader.available():
        pytest.skip("native lib not built")
    p = str(tmp_path / "num.csv")
    with open(p, "w") as f:
        f.write("x,y\n")
        for i in range(1000):
            f.write(f"{i},{i * 0.5 if i % 7 else 'NA'}\n")
    single = parse_csv(p, chunk_bytes=1 << 30)
    many = parse_csv(p, chunk_bytes=128, nthreads=4)
    _cmp_frames(single, many, "native")
    assert ingest_stats.snapshot()["last"]["native"] is True


def test_native_agrees_with_python_semantics(tmp_path):
    """Native availability must not change results: quoted numerics route
    around the quote-blind C scanner, whitespace-only lines are blank on
    both paths, and wide NA markers ('?', 'null') make the column enum on
    both (C now fails them → python fallback)."""
    from h2o3_tpu.native import loader

    if not loader.available():
        pytest.skip("native lib not built")
    # quoted numeric holding the separator: must not take the native path
    p = str(tmp_path / "qn.csv")
    with open(p, "w") as f:
        f.write('a,b\n"1,234",5\n7,8\n')
    fr = parse_csv(p, chunk_bytes=1 << 30)
    _cmp_frames(fr, parse_csv(p, chunk_bytes=8, nthreads=2), "qnum")
    assert fr.nrow == 2
    assert fr.vec("a").type == "enum" and "1,234" in fr.vec("a").domain
    np.testing.assert_array_equal(fr.vec("b").numeric_np(), [5.0, 8.0])
    # whitespace-only line is blank on the native path too
    p2 = str(tmp_path / "ws.csv")
    with open(p2, "w") as f:
        f.write("a,b\n1,2\n \n3,4\n")
    fr2 = parse_csv(p2)
    assert fr2.nrow == 2
    assert ingest_stats.snapshot()["last"]["native"] is True
    # '?' NA marker: enum with or without the .so (C rejects it now)
    p3 = str(tmp_path / "na.csv")
    with open(p3, "w") as f:
        f.write("a,b\n1,2\n?,4\n5,6\n")
    fr3 = parse_csv(p3)
    assert fr3.vec("a").type == "enum"
    _cmp_frames(fr3, _legacy_parse(p3), "qmark")


# -- vectorized coercion parity ----------------------------------------------
def test_bulk_try_numeric_matches_elementwise_loop():
    na = ("", "NA", "na", "nan", None)
    toks = ["1.5", " 2e3 ", "-0.25", "NA", "", "inf", "-inf", "nan",
            "Infinity", "7", " 8 "]
    got = bulk_try_numeric(np.asarray(toks, dtype=object), na)
    ref = np.asarray([np.nan if v in na else float(v) for v in toks])
    np.testing.assert_array_equal(got, ref)
    # bytes + str certification path (the tokenizer's S columns)
    got_s = bulk_try_numeric(np.asarray([t.encode() for t in toks], "S10"),
                             na, assume_str=True)
    np.testing.assert_array_equal(got_s, ref)
    # non-numeric raises exactly like the loop
    with pytest.raises(ValueError):
        bulk_try_numeric(np.asarray(["1", "x"], dtype=object), na)
    # non-str objects keep float() semantics (np.float32 round-trip!)
    mixed = np.asarray([np.float32(0.1), "2.5", None], dtype=object)
    ref2 = np.asarray([float(np.float32(0.1)), 2.5, np.nan])
    np.testing.assert_array_equal(
        bulk_try_numeric(mixed, ("", None)), ref2)
    # strip_tokens applies the parser's wider NA rule
    got3 = bulk_try_numeric(np.asarray([" N/A ", "1"], dtype=object),
                            {"N/A"}, strip_tokens=True)
    np.testing.assert_array_equal(got3, [np.nan, 1.0])


# -- parse_setup fixes -------------------------------------------------------
def test_parse_setup_single_line_tiebreak(tmp_path):
    """The decided lone-line tiebreak (ROADMAP item, ISSUE 4): an all-text
    multi-column lone line is a HEADER over zero rows; any numeric token
    (or a single column) keeps the lone line as data (the ISSUE-2 rule)."""
    p = str(tmp_path / "one.csv")
    with open(p, "w") as f:
        f.write("alpha,beta,gamma\n")
    setup = parse_setup(p)
    assert setup["header"] is True            # all-text lone line = header
    fr = parse_csv(p)
    assert fr.nrow == 0 and fr.names == ["alpha", "beta", "gamma"]
    # single NUMERIC line was already data; stays so
    p2 = str(tmp_path / "one2.csv")
    with open(p2, "w") as f:
        f.write("1,2,3\n")
    assert parse_setup(p2)["header"] is False
    assert parse_csv(p2).nrow == 1
    # a lone MIXED line (text + numeric tokens) stays data
    p3 = str(tmp_path / "one3.csv")
    with open(p3, "w") as f:
        f.write("alpha,2,3\n")
    assert parse_setup(p3)["header"] is False
    assert parse_csv(p3).nrow == 1
    # a lone single-column word stays data (not a 1-column header)
    p4 = str(tmp_path / "one4.csv")
    with open(p4, "w") as f:
        f.write("hello\n")
    assert parse_setup(p4)["header"] is False
    assert parse_csv(p4).nrow == 1


def test_tokenize_block_long_line_skew(tmp_path):
    """A chunk mixing many short rows with ONE very long field must not
    materialize the (nrows × longest-line) fixed-width unicode matrix
    (ROADMAP item): 2000 short rows beside a ~100 KB cell would allocate
    ~800 MB there. The row-wise classification path produces identical
    tokens at O(total chars) memory."""
    import tracemalloc

    short = [f"{i},ab,{i * 0.5}" for i in range(2000)]
    long_cell = "x" * 100_000
    lines = short[:1000] + [f'7,"{long_cell}",1.5'] + short[1000:]
    tracemalloc.start()
    out = chunked.tokenize_block(lines, ",", 3)
    _cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert out.shape == (2001, 3)
    assert out[1000, 1] == long_cell        # RFC-4180 dequoted, intact
    assert out[0, 0] == "0" and out[2000, 2] == str(1999 * 0.5)
    # generous bound: ~10× the text itself, far under the ~8 GB matrix
    assert peak < 64 * 1024 * 1024, f"peak {peak / 1e6:.0f} MB"
    # and the skewed block tokenizes exactly like the per-line reference
    ref = np.empty_like(out)
    for i, ln in enumerate(lines):
        parts = chunked.split_csv_line(ln, ",")
        ref[i, :] = parts[:3] if len(parts) >= 3 else parts + [""] * (3 - len(parts))
    np.testing.assert_array_equal(out, ref)


def test_parse_header_only_csv_zero_rows(tmp_path):
    """`id,name\\n` with zero data rows parses as a named 0-row frame, not
    one DATA row named C1/C2 — pinned end to end through the chunked
    tokenizer and the column coercers."""
    p = str(tmp_path / "header_only.csv")
    with open(p, "w") as f:
        f.write("id,name\n")
    setup = parse_setup(p)
    assert setup["header"] is True
    assert setup["names"] == ["id", "name"]
    fr = parse_csv(p)
    assert fr.nrow == 0
    assert fr.names == ["id", "name"]


def test_parse_setup_quoted_first_line_sep_guess(tmp_path):
    # commas INSIDE the quoted cell must not elect ',' over the real ';'
    p = str(tmp_path / "q.csv")
    with open(p, "w") as f:
        f.write('"last, first, middle";age\n"doe, jane, q";41\n')
    setup = parse_setup(p)
    assert setup["sep"] == ";"
    assert setup["names"] == ["last, first, middle", "age"]
    fr = parse_csv(p)
    assert fr.ncol == 2 and fr.nrow == 1


def test_parse_setup_quoted_sample_types(tmp_path):
    # a quoted cell holding the separator must not shift the type guess
    p = str(tmp_path / "t.csv")
    with open(p, "w") as f:
        f.write('name,score\n"doe, jane",1.5\n"roe, rich",2.5\n')
    setup = parse_setup(p)
    assert setup["types"] == ["enum", "numeric"]
    fr = parse_csv(p)
    assert fr.vec("score").type in ("real", "int")
    assert sorted(fr.vec("name").domain) == ["doe, jane", "roe, rich"]


# -- observability -----------------------------------------------------------
def test_ingest_stats_and_profiler_surface(tmp_path):
    from h2o3_tpu.runtime import phases, profiler

    p = _write_tricky(str(tmp_path / "t.csv"), n=150)
    ingest_stats.reset()
    phases.reset()
    fr = parse_csv(p, chunk_bytes=512, nthreads=2)
    snap = ingest_stats.snapshot()
    assert snap["totals"]["parses"] == 1
    assert snap["totals"]["rows"] == fr.nrow
    assert snap["last"]["rows_per_s"] > 0
    assert snap["last"]["bytes_per_s"] > 0
    assert snap["last"]["n_chunks"] > 1
    assert set(snap["last"]["phases"]) <= set(ingest_stats.PHASE_ORDER)
    assert "tokenize" in snap["last"]["phases"]
    ph = phases.snapshot()
    assert "ingest_tokenize_s" in ph and ph["bytes_ingest_tokenize"] > 0
    prof = profiler.ingest_stats()
    assert prof["active"] is True and prof["totals"]["rows"] == fr.nrow


def test_ingest_metrics_schema():
    from h2o3_tpu.rest import schemas

    sch = schemas.ingest_metrics_schema()
    assert sch["name"] == schemas.INGEST_SCHEMA_NAME
    names = [f["name"] for f in sch["fields"]]
    assert "totals" in names and "last.rows_per_s" in names


# -- throughput smoke (tier-2) -----------------------------------------------
def _write_ingest_csv(path: str, target_mb: float, seed: int = 0) -> int:
    """Synthesize a mixed numeric/enum CSV of ~target_mb MB (16 numeric
    columns with NA holes + 4 enum columns, quoted cells in one — the
    HIGGS-like numeric-heavy shape) and return the row count. Built in
    vectorized blocks so generation stays a small fraction of the parse
    being measured."""
    rng = np.random.default_rng(seed)
    levels = np.asarray([f"lvl{i}" for i in range(40)])
    n_num = 16
    block = 50_000
    rows = 0
    with open(path, "w") as f:
        f.write(",".join([f"n{i}" for i in range(n_num)]
                         + ["e0", "e1", "e2", "e3"]) + "\n")
        while f.tell() < target_mb * 1e6:
            cols = []
            for j in range(n_num):
                c = rng.normal(scale=10.0 ** (j % 6), size=block) \
                    .round(4).astype(str)
                c[rng.random(block) < 0.03] = "NA"   # NA-token holes
                cols.append(c)
            cols.append(rng.integers(0, 7, block).astype(str))
            cols.append(rng.choice(levels, block))
            cols.append(np.char.add("city ",
                                    rng.integers(0, 200, block).astype(str)))
            # ~1% quoted cells carrying the separator — enough to exercise
            # the RFC-4180 fallback without drowning the bulk fast path
            e2 = np.char.add("tag", rng.integers(0, 9, block).astype(str))
            qm = rng.random(block) < 0.01
            e2 = np.where(qm, np.char.add(np.char.add('"q,', e2), '"'), e2)
            cols.append(e2)
            out = cols[0]
            for c in cols[1:]:
                out = np.char.add(np.char.add(out, ","), c)
            f.write("\n".join(out.tolist()) + "\n")
            rows += block
    return rows


@pytest.mark.slow
def test_ingest_throughput_floor(tmp_path):
    """Parallel chunked parse must not regress vs 1-thread (ISSUE floor:
    parallel ≥ 1.0× single-thread; 10% scheduler-noise margin) and must
    beat the seed per-line tokenizer."""
    p = str(tmp_path / "ingest.csv")
    _write_ingest_csv(p, 8)
    parse_csv(p)   # warm-up: page cache + numpy kernels

    def best(fn, reps=3):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times)

    t_legacy = best(lambda: _legacy_parse(p), reps=2)

    def measure():
        # interleave the modes so background-load drift hits both equally
        singles, pars = [], []
        for _ in range(3):
            t0 = time.perf_counter()
            parse_csv(p, nthreads=1)
            singles.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            parse_csv(p, nthreads=os.cpu_count() or 1)
            pars.append(time.perf_counter() - t0)
        return min(singles), min(pars)

    # ≥1.0× single-thread with a scheduler-noise margin (2-core CI hosts
    # run the pool and the pytest process on the same cores); one
    # re-measure damps transient-load flakes before calling it a
    # regression
    for _ in range(2):
        t_single, t_par = measure()
        if t_par <= t_single * 1.20:
            break
    assert t_par <= t_single * 1.20, (t_par, t_single)
    assert t_par <= t_legacy / 1.5, (t_par, t_legacy)
