"""Distributed parse — CSV/ARFF/SVMLight ingest into Frames.

Reference parity: `h2o-core/src/main/java/water/parser/ParseDataset.java`
(`MultiFileParseTask` MRTask over byte ranges), `ParseSetup.java` (format /
separator / column-type guessing on a sample), `CsvParser.java`,
`Categorical.java` (two-phase global categorical interning),
`SVMLightParser.java`, `ARFFParser.java`.

TPU-native shape of the same design: each host parses its own byte range of
the file(s) into numpy columns (phase 1, embarrassingly parallel), then
categorical domains are unioned globally and local codes renumbered
(phase 2 — the `Categorical` merge) before the columns are placed into HBM.
Single-process mode degenerates to "one byte range". Inside a process,
phase 1 is itself parallel: the byte range splits into RFC-4180-safe
chunks tokenized concurrently (`frame/chunked.py`), with the native C++
tokenizer (`h2o3_tpu/native/` via ctypes) slotting in per chunk when
built and a vectorized numpy path always available. Stage timings and
throughput counters land in `frame/ingest_stats.py` (surfaced at
/3/Profiler and /3/Ingest/metrics — see docs/ingest.md).
"""

from __future__ import annotations

import io
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .frame import Frame
from .vec import Vec

_NA_TOKENS = {"", "NA", "na", "N/A", "nan", "NaN", "null", "NULL", "?"}


def _count_unquoted(ln: str, ch: str) -> int:
    """Occurrences of `ch` OUTSIDE double-quoted regions — separator
    guessing must not count a comma inside `"last, first"`."""
    cnt, inq = 0, False
    for c in ln:
        if c == '"':
            inq = not inq
        elif c == ch and not inq:
            cnt += 1
    return cnt


def _split_sample_line(ln: str, sep: str) -> List[str]:
    """Quote-aware split for the setup sample — the tokenizer's own
    dispatch (chunked.split_csv_line), so the column count / type guess
    sees exactly the fields the parse phase will produce."""
    from .chunked import split_csv_line

    return split_csv_line(ln, sep)


def parse_setup(path: str, sample_bytes: int = 1 << 16, sep: Optional[str] = None):
    """Guess separator / header / column types from a sample — the
    `ParseSetup.guessSetup` step."""
    with open(path, "rb") as f:
        raw = f.read(sample_bytes)
    # a short read means the sample IS the whole file — the lone-line
    # header tiebreak below must not fire on a truncated first line of a
    # larger file (it would eat that file's first data row)
    sample_is_whole_file = len(raw) < sample_bytes
    sample = raw.decode("utf-8", errors="replace")
    lines = [ln for ln in sample.splitlines() if ln.strip()][:100]
    if not lines:
        raise ValueError(f"empty file {path}")
    if sep is None:
        counts = {c: _count_unquoted(lines[0], c)
                  for c in [",", "\t", ";", "|", " "]}
        sep = max(counts, key=counts.get)
        if counts[sep] == 0:
            sep = ","
    first = _split_sample_line(lines[0], sep)
    # header iff the first line holds a non-numeric token AND at least one
    # data line follows. Lone-line tiebreak: a single multi-column line
    # whose tokens are ALL non-numeric ("id,name\n") is a header over zero
    # rows — the header-only export case; any numeric token (or a single
    # column) keeps the lone line as DATA (the ISSUE-2 rule).
    header = (len(lines) > 1 and not all(_is_num_or_na(t) for t in first)) \
        or (len(lines) == 1 and sample_is_whole_file and len(first) > 1
            and not any(_is_num_or_na(t) for t in first))
    data_lines = lines[1:] if header else lines
    ncol = len(first)
    # split each sample line ONCE and index columns from the cached parts
    # (was O(lines·ncol²): a re-split of every line inside the column loop)
    parts = [_split_sample_line(ln, sep) for ln in data_lines]
    types = []
    for c in range(ncol):
        col = [p[c].strip() if c < len(p) else "" for p in parts]
        types.append("numeric" if all(_is_num_or_na(t) for t in col)
                     else "enum")
    names = [t.strip().strip('"') for t in first] if header else [f"C{i+1}" for i in range(ncol)]
    return {"sep": sep, "header": header, "names": names, "types": types}


def _is_num_or_na(tok: str) -> bool:
    tok = tok.strip().strip('"')
    if tok in _NA_TOKENS:
        return True
    try:
        float(tok)
        return True
    except ValueError:
        return False


def parse_csv(
    path: str,
    sep: Optional[str] = None,
    header: Optional[bool] = None,
    col_names: Optional[Sequence[str]] = None,
    col_types: Optional[Dict[str, str]] = None,
    nthreads: Optional[int] = None,
    chunk_bytes: Optional[int] = None,
) -> Frame:
    """Parse one CSV file into a Frame: chunked multithreaded phase-1
    tokenize (frame/chunked.py — RFC-4180-safe byte chunks on a thread
    pool, native tokenizer per chunk when built), vectorized column
    coercion, then the phase-2 categorical intern. Per-stage wall-clock is
    recorded under ``ingest_*`` in runtime/phases and in
    frame/ingest_stats (surfaced at /3/Profiler and /3/Ingest/metrics).

    `nthreads`/`chunk_bytes` override the H2O3_PARSE_THREADS /
    H2O3_PARSE_CHUNK_BYTES defaults; chunk count never changes the result
    (pinned bit-identical by tests/test_parse_parallel.py). Setting
    H2O3_INGEST_LEGACY=1 routes through the historical per-line tokenizer
    (the tests' reference)."""
    from . import chunked as _chunked
    from . import ingest_stats as _stats

    t_start = time.perf_counter()
    marks: Dict[str, float] = {}
    with _stats.stage(marks, "setup"):
        setup = parse_setup(path, sep=sep)
        if header is None:
            header = setup["header"]
        names = list(col_names) if col_names else setup["names"]
        sep = setup["sep"]

    legacy = os.environ.get("H2O3_INGEST_LEGACY", "") not in ("", "0")
    if legacy:
        from ..native import loader as native_loader  # late; optional .so

        nbytes = os.path.getsize(path)
        info = dict(n_chunks=1, n_threads=1, native=False)
        with _stats.stage(marks, "tokenize"):
            cols = native_loader.tokenize_csv(path, sep, header, len(names))
            if cols is None:
                cols = _tokenize_numpy(path, sep, header, len(names))
            else:
                info["native"] = True
    else:
        with _stats.stage(marks, "read"):
            with open(path, "rb") as f:
                data = f.read()
        nbytes = len(data)
        with _stats.stage(marks, "tokenize"):
            # the native pass is all-or-nothing numeric; when the sample
            # already guessed an enum column, don't scan-and-discard (the
            # gate only affects speed — python numerics match strtod)
            cols, info = _chunked.tokenize_data(
                data, sep, header, len(names),
                nthreads=nthreads, chunk_bytes=chunk_bytes,
                use_native=all(t == "numeric" for t in setup["types"]))

    col_types = col_types or {}
    # tokenizer columns are str by construction (native ones are float64 —
    # _column_to_vec short-circuits on dtype), so the coercers may skip
    # their per-element type scans
    assume_str = not info.get("native", False)

    def _coerce(arg):
        i, name = arg
        t_col = time.perf_counter()
        if legacy:   # the seed's sequential per-element coercion
            v = _legacy_tokens_to_vec(cols[i], col_types.get(name))
        else:
            v = _column_to_vec(cols[i], col_types.get(name),
                               assume_str=assume_str)
        return name, v, time.perf_counter() - t_col

    # columns coerce independently (numpy casts/sorts release the GIL), so
    # they share the tokenize pool's width; collectives don't exist here
    # (the distributed path stays sequential for rank-ordered collectives),
    # and the legacy comparator stays sequential like the seed
    nthr = 1 if legacy else (
        nthreads if nthreads is not None else _chunked.default_nthreads())
    idxs = list(enumerate(names))
    if nthr > 1 and len(idxs) > 1:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(nthr, len(idxs))) as ex:
            coerced = list(ex.map(_coerce, idxs))
    else:
        coerced = [_coerce(a) for a in idxs]
    vecs = {}
    for name, v, dt in coerced:
        # numeric/time columns book "coerce"; enum/string book "intern"
        # (the categorical merge) — same decomposition /3/Profiler shows.
        # Under the pool these per-column seconds overlap, so bucket sums
        # may exceed the parse wall-clock.
        bucket = "intern" if v.type in ("enum", "string") else "coerce"
        marks[bucket] = marks.get(bucket, 0.0) + dt
        vecs[name] = v
    with _stats.stage(marks, "place"):
        fr = Frame(vecs, key=os.path.basename(path))
    _stats.record(path, fr.nrow, nbytes, time.perf_counter() - t_start,
                  marks, legacy=legacy, **info)
    return fr


def _split_lines(lines: List[str], sep: str, ncol: int) -> List[np.ndarray]:
    """Shared line splitter for the python tokenize paths (whole-file and
    distributed byte-range) — one place for quoting/strip semantics.
    Lines containing a double quote take the RFC-4180 csv reader (so
    quoted cells may hold the separator — what `frame_to_csv` emits);
    everything else keeps the fast plain split."""
    import csv as _csv

    cols: List[list] = [[] for _ in range(ncol)]
    for ln in lines:
        if '"' in ln:
            # the csv reader dequotes; don't strip OR re-strip quotes —
            # quoting exists precisely to preserve edge whitespace and
            # literal quote characters (numeric conversion downstream
            # tolerates surrounding spaces on the rare mixed lines)
            parts = next(_csv.reader([ln], delimiter=sep))
        else:
            parts = [p.strip().strip('"') for p in ln.split(sep)]
        for c in range(ncol):
            cols[c].append(parts[c] if c < len(parts) else "")
    return [np.asarray(c, dtype=object) for c in cols]


def _tokenize_numpy(path: str, sep: str, header: bool, ncol: int) -> List[np.ndarray]:
    """LEGACY tokenizer: whole-file read + per-line split. The chunked
    pipeline (frame/chunked.py) replaced it as the default; it stays as the
    bit-exact reference the parallel path is pinned against
    (tests/test_parse_parallel.py, H2O3_INGEST_LEGACY=1)."""
    with open(path, "rb") as f:
        text = f.read().decode("utf-8", errors="replace")
    lines = text.splitlines()
    if header:
        lines = lines[1:]
    lines = [ln for ln in lines if ln.strip()]
    return _split_lines(lines, sep, ncol)


def _legacy_tokens_to_vec(col: np.ndarray, hint: Optional[str]) -> Vec:
    """The SEED coercion (pre-chunked-pipeline): per-element `float()`
    loops and object-array `np.unique` interning. Kept verbatim as the
    other half of the H2O3_INGEST_LEGACY reference:
    tests/test_parse_parallel.py pins the new path bit-identical to it."""
    from .vec import _all_int, _maybe_f32

    if hint in ("real", "int", "numeric", "float"):
        vals = np.asarray(
            [np.nan if str(v).strip() in _NA_TOKENS else float(v)
             for v in col], dtype=np.float64)
        return Vec(_maybe_f32(vals), "real")
    if hint == "string":
        return Vec(None, "string", strings=col)

    def _intern(values: np.ndarray) -> Vec:
        mask = np.asarray([v in ("", "NA", "na", None) for v in values])
        domain, codes = np.unique(np.asarray(values)[~mask],
                                  return_inverse=True)
        full = np.full(len(values), -1, dtype=np.int32)
        full[~mask] = codes.astype(np.int32)
        return Vec(full, "enum", domain=[str(d) for d in domain])

    if hint in ("enum", "factor", "categorical"):
        return _intern(col.astype(object))
    try:
        as_num = np.asarray(
            [np.nan if v in ("", "NA", "na", "nan", None) else float(v)
             for v in col], dtype=np.float64)
        return Vec(_maybe_f32(as_num),
                   "real" if not _all_int(as_num) else "int")
    except (TypeError, ValueError):
        return _intern(col)


def _column_to_vec(col: np.ndarray, hint: Optional[str],
                   assume_str: bool = False) -> Vec:
    if hint in ("real", "int", "numeric", "float"):
        from .vec import _maybe_f32, bulk_try_numeric

        if col.dtype.kind == "f":
            # native-tokenized column: already float64 with NaN NAs
            vals = np.asarray(col, dtype=np.float64)
        else:
            vals = bulk_try_numeric(col, _NA_TOKENS, strip_tokens=True,
                                    assume_str=assume_str)
        return Vec(_maybe_f32(vals), "real")
    if hint in ("enum", "factor", "categorical"):
        return Vec.from_numpy(col if col.dtype.kind in "US"
                              else col.astype(object), "enum",
                              assume_str=assume_str)
    if hint == "string":
        # the fast tokenizer's bytes columns decode for the string pool
        return Vec(None, "string",
                   strings=col.astype("U") if col.dtype.kind == "S" else col)
    return Vec.from_numpy(col, assume_str=assume_str)


def parse_svmlight(path: str) -> Frame:
    """SVMLight ingest (`water/parser/SVMLightParser.java`): sparse
    label qid? idx:val ... lines → dense Frame (labels in "C1")."""
    rows = []
    max_idx = 0
    labels = []
    qids = []
    with open(path) as f:
        for ln in f:
            ln = ln.split("#")[0].strip()
            if not ln:
                continue
            parts = ln.split()
            labels.append(float(parts[0]))
            feats = {}
            for p in parts[1:]:
                k, v = p.split(":")
                if k == "qid":
                    qids.append(int(v))
                    continue
                feats[int(k)] = float(v)
                max_idx = max(max_idx, int(k))
            rows.append(feats)
    X = np.zeros((len(rows), max_idx), dtype=np.float32)
    for r, feats in enumerate(rows):
        for k, v in feats.items():
            X[r, k - 1] = v
    vecs = {"C1": Vec(np.asarray(labels, np.float32), "real")}
    if qids:
        vecs["qid"] = Vec(np.asarray(qids, np.float32), "int")
    for j in range(max_idx):
        vecs[f"C{j+2}"] = Vec(X[:, j], "real")
    return Frame(vecs, key=os.path.basename(path))


def parse_arff(path: str) -> Frame:
    """ARFF ingest (`water/parser/ARFFParser.java`): @attribute declarations
    drive the column types (numeric/real/integer → numeric, {a,b,c} → enum,
    string/date → string); @data rows parse as CSV. Sparse `{i v, …}` data
    rows are expanded dense."""
    names: List[str] = []
    types: List[str] = []
    domains: List[Optional[List[str]]] = []
    data_lines: List[str] = []
    in_data = False
    with open(path, encoding="utf-8", errors="replace") as f:
        for ln in f:
            ln = ln.strip()
            if not ln or ln.startswith("%"):
                continue
            low = ln.lower()
            if in_data:
                data_lines.append(ln)
            elif low.startswith("@attribute"):
                rest = ln[len("@attribute"):].strip()
                if rest.startswith(("'", '"')):
                    q = rest[0]
                    end = rest.index(q, 1)
                    name, typ = rest[1:end], rest[end + 1:].strip()
                else:
                    parts = rest.split(None, 1)
                    name, typ = parts[0], (parts[1] if len(parts) > 1 else "numeric")
                names.append(name)
                tl = typ.strip()
                if tl.startswith("{"):
                    dom = [t.strip().strip("'\"") for t in tl.strip("{}").split(",")]
                    types.append("enum")
                    domains.append(dom)
                elif tl.lower() in ("numeric", "real", "integer"):
                    types.append("numeric")
                    domains.append(None)
                else:  # string / date / relational
                    types.append("string")
                    domains.append(None)
            elif low.startswith("@data"):
                in_data = True
    def _arff_split(ln: str) -> List[str]:
        """Comma split honouring ARFF's single- OR double-quoted values."""
        out, cur, q = [], [], None
        for ch in ln:
            if q:
                if ch == q:
                    q = None
                else:
                    cur.append(ch)
            elif ch in "'\"":
                q = ch
            elif ch == ",":
                out.append("".join(cur).strip())
                cur = []
            else:
                cur.append(ch)
        out.append("".join(cur).strip())
        return out

    ncol = len(names)
    # ARFF spec: omitted sparse entries are value 0 — numeric 0, or the
    # FIRST nominal value for enum columns
    defaults = [
        (domains[i][0] if types[i] == "enum" and domains[i] else "0")
        for i in range(ncol)
    ]
    cols: List[list] = [[] for _ in range(ncol)]
    for ln in data_lines:
        if ln.startswith("{"):  # sparse row: {idx val, idx val}
            vals = list(defaults)
            for pair in ln.strip("{}").split(","):
                pair = pair.strip()
                if not pair:
                    continue
                i, v = pair.split(None, 1)
                vals[int(i)] = v.strip().strip("'\"")
        else:
            vals = _arff_split(ln)
        for c in range(ncol):
            cols[c].append(vals[c] if c < len(vals) else "")
    vecs = {}
    for i, name in enumerate(names):
        col = np.asarray(cols[i], dtype=object)
        if types[i] == "numeric":
            vecs[name] = _column_to_vec(col, "numeric")
        elif types[i] == "enum":
            dom = domains[i]
            lookup = {d: j for j, d in enumerate(dom)}
            codes = np.asarray([lookup.get(str(v), -1) for v in col], np.int32)
            vecs[name] = Vec(codes, "enum", domain=dom)
        else:
            vecs[name] = Vec(None, "string", strings=col)
    return Frame(vecs, key=os.path.basename(path))


def _arrow_table_to_frame(table, key: Optional[str] = None) -> Frame:
    """Arrow table → Frame. Numerics stay floating (NaN = NA), strings/
    dictionaries become enum vecs built from Arrow's EXPLICIT null mask
    (unlike CSV, '' / 'NA' are legitimate values here), booleans become
    0/1, timestamps become ms-since-epoch 'time' columns (NaT → NaN)."""
    import pyarrow as pa

    vecs: Dict[str, Vec] = {}
    for name, col in zip(table.column_names, table.columns):
        arr = col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)
        pyt = arr.type
        if pa.types.is_dictionary(pyt):
            arr = arr.dictionary_decode()
            pyt = arr.type
        if pa.types.is_string(pyt) or pa.types.is_large_string(pyt):
            vals = arr.to_numpy(zero_copy_only=False)   # object, None=null
            valid = np.asarray([v is not None for v in vals])
            uniq = sorted({str(v) for v in vals[valid]})
            lut = {lbl: i for i, lbl in enumerate(uniq)}
            codes = np.asarray(
                [lut[str(v)] if ok else -1 for v, ok in zip(vals, valid)],
                np.int32)
            vecs[name] = Vec(codes, "enum", domain=uniq)
        elif pa.types.is_boolean(pyt):
            vals = arr.to_numpy(zero_copy_only=False)
            vecs[name] = Vec(np.asarray(
                [np.nan if v is None else float(v) for v in vals],
                np.float32), "int")
        elif pa.types.is_timestamp(pyt) or pa.types.is_date(pyt):
            v = arr.cast(pa.timestamp("ms")).to_numpy(zero_copy_only=False)
            nat = np.isnat(v)
            out = v.astype("datetime64[ms]").astype(np.float64)
            out[nat] = np.nan
            vecs[name] = Vec(out, "time")
        elif (pa.types.is_integer(pyt) or pa.types.is_floating(pyt)
              or pa.types.is_decimal(pyt)):
            np_col = arr.to_numpy(zero_copy_only=False).astype(np.float64)
            vecs[name] = Vec.from_numpy(np_col)
        else:
            raise ValueError(
                f"unsupported Arrow column type {pyt} in column {name!r} "
                "(binary/list/struct columns have no Frame representation)")
    return Frame(vecs, key=key)


def parse_parquet(path: str) -> Frame:
    """Parquet ingest via pyarrow — the `h2o-parsers/h2o-parquet-parser`
    extension's role (Parquet is columnar already; no tokenizing phase)."""
    import pyarrow.parquet as pq

    return _arrow_table_to_frame(pq.read_table(path),
                                 key=os.path.basename(path))


def parse_orc(path: str) -> Frame:
    """ORC ingest via pyarrow — the `h2o-parsers/h2o-orc-parser` role."""
    from pyarrow import orc

    return _arrow_table_to_frame(orc.read_table(path),
                                 key=os.path.basename(path))


def import_file(path: str, **kw) -> Frame:
    """`h2o.import_file` — dispatch by extension (`ParseDataset.parse`).
    Non-file URIs (http/s3/gs/hdfs) are fetched through the Persist SPI
    (`runtime/persist.py`, the water.persist backends) into a temp file
    first, then parsed by format as usual."""
    if path.startswith("file://"):
        path = path[len("file://"):]
    if "://" in path:
        import tempfile

        from ..runtime import persist as persist_spi

        import shutil

        backend = persist_spi.for_uri(path)
        suffix = os.path.splitext(path.split("?", 1)[0])[1] or ".csv"
        tmp = tempfile.NamedTemporaryFile(suffix=suffix, delete=False)
        try:
            with backend.open(path) as src:
                shutil.copyfileobj(src, tmp)   # streamed, not buffered
            tmp.close()
            fr = import_file(tmp.name, **kw)
            # key by basename like local parses, but uniquified: two URLs
            # ending in the same filename must not collide in the DKV
            from ..runtime.dkv import DKV

            base = os.path.basename(path.split("?", 1)[0]) or fr.key
            keyname, i = base, 0
            while DKV.get(keyname) is not None:
                i += 1
                keyname = f"{base}_{i}"
            fr.key = keyname
            return fr
        finally:
            os.unlink(tmp.name)
    if os.path.isdir(path):
        # directory import: parse every (non-hidden, optionally
        # pattern-filtered) file and rbind — ParseDataset's multi-file
        # import (`h2o.import_file(path=dir, pattern=...)`)
        import re as _re

        pattern = kw.pop("pattern", None)
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if not f.startswith(".")
            and os.path.isfile(os.path.join(path, f)))
        if pattern:
            files = [f for f in files
                     if _re.search(pattern, os.path.basename(f))]
        if not files:
            raise ValueError(f"no files to import under {path!r}"
                             + (f" matching {pattern!r}" if pattern else ""))
        out = Frame.rbind_all([import_file(f, **kw) for f in files])
        out.key = os.path.basename(os.path.normpath(path))
        return out
    kw.pop("pattern", None)   # pattern only filters directory imports
    if path.endswith((".svm", ".svmlight")):
        return parse_svmlight(path)
    if path.endswith(".arff"):
        return parse_arff(path)
    if path.endswith((".parquet", ".pq")):
        return parse_parquet(path)
    if path.endswith(".orc"):
        return parse_orc(path)
    import jax

    if jax.process_count() > 1:
        # multi-host cloud: every process parses its own byte range, then
        # the phase-2 collectives agree on types/domains (ParseDataset's
        # MultiFileParseTask + Categorical merge)
        from .distributed_parse import parse_csv_distributed

        return parse_csv_distributed(path, **kw)
    return parse_csv(path, **kw)
