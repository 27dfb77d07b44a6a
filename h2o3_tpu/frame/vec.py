"""Vec — one column of a distributed Frame.

Reference parity: `h2o-core/src/main/java/water/fvec/Vec.java` and the ~20
compressed `Chunk` encodings (`C0DChunk`…`CXIChunk`). The reference keeps a
Vec as a homed array of per-node compressed chunks read through
`Chunk.atd(row)`; on TPU a Vec is a single dense `jax.Array` whose leading
axis is (optionally) sharded over the ``hosts`` mesh axis. Compression is
XLA's problem (bf16/int8 casts at op boundaries), not the storage layer's —
dense HBM arrays feed the MXU; chunk decompression per element would not.

Type system (mirrors `Vec.get_type_str()`): ``real``, ``int``, ``enum``
(categorical with a string domain), ``time``, ``string``. NA encodings:
NaN for real/int (stored f32/f64), -1 for enum codes, None in string pool.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import numpy as np

TYPES = ("real", "int", "enum", "time", "string")

# numpy ≥2.0 ships ufunc-backed string ops that run at C speed and release
# the GIL; np.char is the semantically identical slow fallback
_S = np.strings if hasattr(np, "strings") else np.char


def _certified_str(arr: np.ndarray, assume_str: bool) -> bool:
    """May the vectorized string kernels touch this array? ``U`` always;
    ``S`` only under the tokenizer's `assume_str` certificate (its fast
    path is ASCII-gated, so bytes⇄str round-trips are lossless); object
    arrays when certified or verified all-`str` — any other element type
    (floats, None, np.str_, user bytes) keeps the exact per-element loop
    semantics. The single source of truth for every coercer's fast/slow
    dispatch, so NA/strip/intern parity can't drift between them."""
    kind = arr.dtype.kind
    if kind == "U":
        return True
    if kind == "S":
        return assume_str
    if kind == "O":
        return assume_str or all(type(v) is str for v in arr.tolist())
    return False


def bulk_try_numeric(col, na_tokens, strip_tokens: bool = False,
                     assume_str: bool = False) -> np.ndarray:
    """Vectorized `[nan if v in na_tokens else float(v) for v in col]` —
    one unicode cast + `np.isin` NA mask + a single bulk str→float64 cast
    (all of which numpy runs without the GIL) instead of a per-element
    `float()` loop. Raises TypeError/ValueError exactly when the
    per-element loop would, so callers' numeric-vs-categorical try/except
    decisions are unchanged.

    `strip_tokens` applies the parser's wider NA rule
    (`str(v).strip() in na_tokens`). `assume_str` (set by the tokenizer
    paths, whose columns are str by construction) skips the element-type
    scan; without it, columns holding any non-str element (python dicts
    can carry floats/None) drop to the exact per-element loop —
    `float(np.float32(0.1))` and `float("0.1")` differ in the last bits,
    and bit-identity with the historical path wins over speed there."""
    arr = np.asarray(col)
    n = arr.shape[0]
    if n == 0:
        return np.empty(0, np.float64)
    if not _certified_str(arr, assume_str):
        # non-str objects and bytes columns: the loop IS the semantics
        if strip_tokens:
            return np.asarray(
                [np.nan if str(v).strip() in na_tokens else float(v)
                 for v in arr], dtype=np.float64)
        return np.asarray(
            [np.nan if v in na_tokens else float(v) for v in arr],
            dtype=np.float64)
    u = arr.astype("U") if arr.dtype.kind == "O" else arr
    if u.dtype.kind == "S":
        na = [t.encode() for t in na_tokens if isinstance(t, str)]
    else:
        na = [t for t in na_tokens if isinstance(t, str)]
    key = _S.strip(u) if strip_tokens else u
    mask = np.isin(key, na)
    out = np.full(n, np.nan, np.float64)
    vals = u[~mask]
    if vals.size:
        try:
            conv = vals.astype(np.float64)
        except (TypeError, ValueError):
            # numpy's parser rejects a few forms float() accepts ("1_0",
            # non-ASCII digits); the loop is the semantics of record — and
            # it raises to the caller exactly like the historical path
            conv = np.asarray(
                [float(v.decode() if isinstance(v, bytes) else v)
                 for v in vals], dtype=np.float64)
        out[~mask] = conv
    return out


def _intern_enum(col: np.ndarray, na_tokens=("", "NA", "na", None),
                 assume_str: bool = False) -> Vec:
    """Categorical intern (`water/parser/Categorical.java`): NA-mask, then
    sorted uniques as the domain and positions as codes. Pure-str columns
    take a unicode-array route (`np.unique` over fixed-width unicode is a
    C sort; over object arrays it is a python-compare sort) — unicode
    code-point order equals python str ordering, so domains and codes are
    bit-identical either way."""
    arr = np.asarray(col)
    if _certified_str(arr, assume_str):
        u = arr.astype("U") if arr.dtype.kind == "O" else arr
        if u.dtype.kind == "S":
            # tokenizer bytes column (ASCII-gated): byte order equals
            # code-point order, so the sorted domain is identical
            na = [t.encode() for t in na_tokens if isinstance(t, str)]
        else:
            na = [t for t in na_tokens if isinstance(t, str)]
        mask = np.isin(u, na)
        domain, codes = np.unique(u[~mask], return_inverse=True)
        labels = ([d.decode() for d in domain] if u.dtype.kind == "S"
                  else [str(d) for d in domain])
    else:
        mask = np.asarray([v in na_tokens for v in arr])
        domain, codes = np.unique(np.asarray(arr)[~mask],
                                  return_inverse=True)
        labels = [str(d) for d in domain]
    full = np.full(len(arr), -1, dtype=np.int32)
    full[~mask] = codes.astype(np.int32)
    return Vec(full, "enum", domain=labels)


class Rollup(NamedTuple):
    """What a Vec remembers of its values (`water/fvec/RollupStats.java`):
    `min` and `max` over the non-NA values (NaN when there are none, and
    for a ``string`` Vec) and the NA count."""
    min: float
    max: float
    nacnt: int


_ROLLUP_BLOCK = 1 << 20
_ROLLUP_COUNTER = None


def _count_where(pred, a: np.ndarray) -> int:
    """How many elements of `a` satisfy `pred`, block by block: the mask
    is never as long as the column."""
    return sum(int(np.count_nonzero(pred(a[i:i + _ROLLUP_BLOCK])))
               for i in range(0, a.shape[0], _ROLLUP_BLOCK))


def _scan(v: "Vec") -> Rollup:
    """One Vec's rollup by reductions over `v.data` in its own dtype, with
    no column-sized temporary. A clean column costs two passes: `min()`
    propagates NaN, so a non-NaN minimum also says the NA count is 0."""
    nan = float("nan")
    if v.type == "string":
        return Rollup(nan, nan, sum(1 for s in v._strings if s is None))
    a = v.data
    if a.shape[0] == 0:
        return Rollup(nan, nan, 0)
    if v.type == "enum":
        lo, hi = int(a.min()), int(a.max())
        if hi < 0:
            return Rollup(nan, nan, int(a.shape[0]))
        if lo >= 0:
            return Rollup(float(lo), float(hi), 0)
        # NA codes are negative: as uint32 they sort above every level
        return Rollup(float(a.view(np.uint32).min()), float(hi),
                      _count_where(lambda c: c < 0, a))
    lo = a.min()
    if lo == lo:
        return Rollup(float(lo), float(a.max()), 0)
    # fmin / fmax skip NaN, and answer NaN only where nothing else is there
    return Rollup(float(np.fmin.reduce(a)), float(np.fmax.reduce(a)),
                  _count_where(np.isnan, a))


def _count_rollup(result: str) -> None:
    """Count one `Vec.rollup()` request, "computed" or "reused": in the
    registry, and as a tally on the open span (`train.resolve` opens with
    `rollups_computed` and `rollups_reused` at 0, so it shows both)."""
    global _ROLLUP_COUNTER
    from ..runtime import tracing

    c = _ROLLUP_COUNTER
    if c is None:
        from ..runtime import metrics_registry as _reg

        c = _ROLLUP_COUNTER = _reg.counter(
            "h2o3_vec_rollup",
            "Vec rollup (min, max, NA count) requests: computed = scanned "
            "the column, reused = read what the Vec remembered",
            labelnames=("result",))
    c.inc(1.0, result)
    tracing.tally("rollups_" + result)


class Vec:
    __slots__ = ("data", "type", "domain", "_strings", "_rollup")

    def __init__(
        self,
        data,
        type: str = "real",
        domain: Optional[List[str]] = None,
        strings: Optional[np.ndarray] = None,
    ):
        if type not in TYPES:
            raise ValueError(f"bad vec type {type!r}")
        self.type = type
        self.domain = list(domain) if domain is not None else None
        self._strings = strings  # host-side object array for type == "string"
        if type == "string":
            self.data = None
        else:
            # columns are HOST-resident numpy; device placement (HBM, row-
            # sharded) happens once per training run inside the algorithms —
            # eager per-column device_put would pay an H2D on every munging
            # op
            arr = np.asarray(data)
            if type == "enum":
                arr = arr.astype(np.int32)
            elif arr.dtype not in (np.float32, np.float64):
                arr = arr.astype(np.float32)
            self.data = arr

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_numpy(col: np.ndarray, type_hint: Optional[str] = None,
                   assume_str: bool = False) -> "Vec":
        """Build a Vec from a host column, inferring type like
        `water/parser/ParseSetup.java` column-type guessing. `assume_str`
        certifies every element is a python str (the tokenizer paths),
        skipping the per-element type scans of the vectorized coercers."""
        if col.dtype.kind in "OUS":
            work = col
            if col.dtype.kind == "O" and _certified_str(col, assume_str):
                # one unicode cast shared by the numeric try AND the intern
                # (each would otherwise pay its own object→U conversion)
                work = col.astype("U")
            if type_hint == "enum":
                return _intern_enum(work, assume_str=assume_str)
            # try numeric, else categorical intern (water/parser/Categorical.java)
            try:
                as_num = bulk_try_numeric(work, ("", "NA", "na", "nan", None),
                                          assume_str=assume_str)
                return Vec(_maybe_f32(as_num),
                           "real" if not _all_int(as_num) else "int")
            except (TypeError, ValueError):
                pass
            if type_hint == "string":
                return Vec(None, "string", strings=np.asarray(col, dtype=object))
            return _intern_enum(work, assume_str=assume_str)
        col = np.asarray(col)
        if type_hint == "time":
            return Vec(col.astype(np.float64), "time")
        if type_hint == "enum":
            valid = ~np.isnan(col.astype(np.float64))
            domain, codes = np.unique(col[valid], return_inverse=True)
            full = np.full(len(col), -1, dtype=np.int32)
            full[valid] = codes.astype(np.int32)
            # integral numeric levels print without the ".0" (h2o's asfactor)
            labels = [
                str(int(d)) if float(d) == int(d) else str(d) for d in domain
            ]
            return Vec(full, "enum", domain=labels)
        t = "int" if col.dtype.kind in "iub" or _all_int(col) else "real"
        return Vec(_maybe_f32(col.astype(np.float64)), t)

    # -- properties ---------------------------------------------------------
    def __len__(self) -> int:
        return len(self._strings) if self.type == "string" else int(self.data.shape[0])

    @property
    def nlevels(self) -> int:
        return len(self.domain) if self.domain else 0

    def isna_np(self) -> np.ndarray:
        if self.type == "string":
            return np.asarray([s is None for s in self._strings])
        a = np.asarray(self.data)
        return (a < 0) if self.type == "enum" else np.isnan(a)

    def to_numpy(self) -> np.ndarray:
        if self.type == "string":
            return self._strings
        return np.asarray(self.data)

    def numeric_np(self) -> np.ndarray:
        """Column as float64 with NaN NAs (enum -> code as float)."""
        a = np.asarray(self.data, dtype=np.float64)
        if self.type == "enum":
            a = np.where(a < 0, np.nan, a)
        return a

    # -- stats (the rollups of water/fvec/RollupStats.java) ------------------
    def _memo(self) -> dict:
        """What this Vec remembers of the array it holds. `data` is bound
        once, in `__init__`, and never written into (the rule
        `dataset_cache._frame_key` lives by): a new column is a new Vec,
        which starts empty. The memo is kept beside the array it was
        computed from and dropped if `data` is ever another object; a Vec
        unpickled from before the slot existed has none yet."""
        src = self._strings if self.type == "string" else self.data
        m = getattr(self, "_rollup", None)
        if m is None or m[0] is not src:
            m = self._rollup = (src, {})
        return m[1]

    def rollup(self) -> Rollup:
        """min, max and NA count: scanned on the first request, remembered
        for every later one."""
        memo = self._memo()
        r = memo.get("rollup")
        _count_rollup("computed" if r is None else "reused")
        if r is None:
            r = memo["rollup"] = _scan(self)
        return r

    def min(self) -> float:
        return self.rollup().min

    def max(self) -> float:
        return self.rollup().max

    def nacnt(self) -> int:
        return self.rollup().nacnt

    def mean(self) -> float:
        memo = self._memo()
        if "mean" not in memo:
            memo["mean"] = float(np.nanmean(self.numeric_np()))
        return memo["mean"]

    def sd(self) -> float:
        memo = self._memo()
        if "sd" not in memo:
            memo["sd"] = float(np.nanstd(self.numeric_np(), ddof=1))
        return memo["sd"]

    def take(self, idx: np.ndarray) -> "Vec":
        if self.type == "string":
            return Vec(None, "string", strings=self._strings[idx])
        return Vec(np.asarray(self.data)[idx], self.type, domain=self.domain)

    def __repr__(self):
        return f"Vec(type={self.type}, len={len(self)}, domain={self.nlevels or None})"


def _maybe_f32(col: np.ndarray) -> np.ndarray:
    """Downcast f64 → f32 unless magnitudes exceed f32's exact-integer
    range — epoch-ms timestamps ("time" columns) would lose minutes."""
    fin = col[np.isfinite(col)]
    big = float(np.abs(fin).max()) if fin.size else 0.0
    return col if big > (1 << 24) else col.astype(np.float32)


def _all_int(a: np.ndarray) -> bool:
    with np.errstate(invalid="ignore"):
        fin = a[np.isfinite(a)]
        return fin.size > 0 and bool(np.all(fin == np.round(fin)))
