"""h2o3_tpu — a TPU-native ML platform with H2O-3's capabilities.

The public surface mirrors `h2o-py/h2o/h2o.py` (`h2o.init`, `h2o.import_file`,
`h2o.H2OFrame`, …) so reference users can switch imports; the engine under it
is JAX/XLA/Pallas on TPU meshes instead of a JVM cloud — see SURVEY.md for
the layer-by-layer mapping.
"""

from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()

import os as _os
from typing import Optional, Sequence

import numpy as np


def compile_cache_dir() -> str:
    """Resolve JAX's persistent compilation cache — the ONE place that
    decides where it lives (package import, bench.py, chip_smoke.py and
    tests/conftest.py all come through here). A JAX_COMPILATION_CACHE_DIR
    in the environment wins: jax reads it itself and nothing is set in
    code. Otherwise the cache is `<checkout>/.jax_cache` — a fixed path,
    because the path is part of the cache key's lookup: a directory that
    moves never hits. Tree programs take seconds to compile; every process
    of one checkout (test workers, bench children) shares the entries."""
    import jax

    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache")
        _os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return jax.config.jax_compilation_cache_dir


compile_cache_dir()

from .frame.frame import Frame
from .frame.frame import Frame as H2OFrame
from .frame.parse import import_file as _import_file
from .frame.text import grep, tf_idf, tokenize  # noqa: F401  (h2o.tf_idf surface)
from . import tree_api as tree  # noqa: F401  (h2o.tree.H2OTree surface)
from .parallel import mesh as _mesh

__version__ = "0.1.0"

from .runtime.dkv import DKV as _DKV  # the keyed store (water/DKV.java)
from .runtime.log import Log as _Log
from . import client  # remote-attach REST client (h2o-py H2OConnection)


def _conn_kwargs(kw):
    """Shared connect-kwarg normalization (h2o-py spells the TLS opt-out
    `verify_ssl_certificates`)."""
    return dict(token=kw.get("token"), verbose=kw.get("verbose", True),
                verify_ssl=kw.get("verify_ssl",
                                  kw.get("verify_ssl_certificates", True)))


def init(url=None, ip=None, port=None, nthreads=-1, max_mem_size=None,
         strict_version_check=False, **kw):
    """`h2o.init()` — form the local cloud (mesh over visible devices), or,
    with `url=`/`ip=`/`port=`, attach to a RUNNING server as a thin REST
    client (h2o-py/h2o/h2o.py `init` → `H2OConnection.open`). An explicit
    endpoint that is unreachable raises — no silent local fallback."""
    if url is not None or ip is not None or port is not None:
        return client.connect(url=url, ip=ip, port=port, **_conn_kwargs(kw))
    return _mesh.init()


def connect(url=None, ip=None, port=None, **kw):
    """`h2o.connect(url=)` — attach to a running server by URL; with no
    endpoint, form the local in-process cloud (h2o-py parity)."""
    if url is not None or ip is not None or port is not None:
        return client.connect(url=url, ip=ip, port=port, **_conn_kwargs(kw))
    return init()


def connection():
    """The active remote connection, or None when in-process."""
    return client.current_connection()


def cluster():
    c = _mesh.cloud()

    class _ClusterInfo:
        cloud_size = c.size
        version = __version__

        def show_status(self):
            print(f"h2o3_tpu cloud: {c.size} device(s): {c.devices}")

    return _ClusterInfo()


def shutdown(prompt=False):
    if client.current_connection() is not None:
        client.disconnect()
        return
    _mesh.reset()
    _DKV.clear()


def import_file(path: str, destination_frame=None, header=0, sep=None,
                col_names=None, col_types=None, pattern=None, **kw):
    conn = client.current_connection()
    if conn is not None:
        return conn.import_file(path, destination_frame=destination_frame,
                                sep=sep, col_names=col_names,
                                col_types=col_types, pattern=pattern)
    fr = _import_file(
        path,
        sep=sep,
        header=None if header == 0 else bool(header > 0),
        col_names=col_names,
        col_types=col_types,
        pattern=pattern,
    )
    if destination_frame:
        fr.key = destination_frame
    _DKV.put(fr.key, fr)
    _Log.info(f"imported {path} -> {fr.key} ({fr.nrow}x{fr.ncol})")
    return fr


def upload_file(path: str, destination_frame=None, sep=None, col_names=None,
                col_types=None, **kw):
    conn = client.current_connection()
    if conn is not None:
        # client-side bytes travel to the server (PostFile + Parse)
        return conn.upload_file(path, destination_frame=destination_frame,
                                sep=sep, col_names=col_names,
                                col_types=col_types)
    return import_file(path, destination_frame=destination_frame, sep=sep,
                       col_names=col_names, col_types=col_types, **kw)


def H2OFrame_from_python(data, column_types=None, column_names=None):
    conn = client.current_connection()
    if conn is None:
        return Frame(data, column_names=column_names,
                     column_types=column_types)
    # connected: python data belongs ON the server (h2o-py H2OFrame(obj)
    # uploads to the cluster). Serialize through the local Frame builder
    # (type inference, NA handling), ship CSV bytes, parse with the
    # inferred/requested types; the local temporary never enters the DKV.
    from .frame.frame import frame_to_csv

    fr = Frame(data, column_names=column_names, column_types=column_types)
    _DKV.remove(fr.key)
    types = [fr.vec(n).type for n in fr.names]
    return conn.upload_bytes(frame_to_csv(fr).encode(), "pyframe.csv",
                             col_names=list(fr.names), col_types=types)


def get_frame(key: str):
    conn = client.current_connection()
    if conn is not None:
        return conn.get_frame(key)
    fr = _DKV.get(key)
    if not isinstance(fr, Frame):
        raise KeyError(key)
    return fr


def remove(obj) -> None:
    if isinstance(obj, str):
        key = obj
    else:  # frames carry .key; models are keyed by model_id
        key = getattr(obj, "key", None) or getattr(obj, "model_id", None)
    _DKV.remove(key)


def ls():
    return _DKV.keys()


def merge(x: Frame, y: Frame, all_x: bool = False, all_y: bool = False,
          by_x=None, by_y=None, method="auto") -> Frame:
    """`h2o.merge` — AstMerge radix join (see frame/rapids.py). by_x/by_y
    pair key columns with different names (right keys renamed pre-join)."""
    from .frame.rapids import merge as _m

    if by_y is not None:
        if by_x is None or len(by_x) != len(by_y):
            raise ValueError("merge: by_x and by_y must be same-length lists")
        renames = dict(zip(by_y, by_x))
        clash = [t for t in renames.values()
                 if t in y.names and t not in renames]
        if clash:
            raise ValueError(
                f"merge: renaming by_y→by_x would overwrite right-frame column(s) {clash}"
            )
        y = Frame({renames.get(n, n): v for n, v in zip(y.names, y.vecs())})
    return _m(x, y, by=by_x, all_x=all_x, all_y=all_y)


def assign(data: Frame, xid: str) -> Frame:
    """`h2o.assign` — rebind a frame to a new DKV key (water/rapids assign)."""
    if xid == data.key:
        raise ValueError("new key must differ from the current key")
    _DKV.remove(data.key)
    data.key = xid
    _DKV.put(xid, data)
    return data


def export_file(frame: Frame, path: str, force: bool = False, sep: str = ",",
                header: bool = True, quote_header: bool = False,
                format: Optional[str] = None) -> str:
    """`h2o.export_file` — write a Frame as CSV, or Parquet when
    format="parquet" (or, with no explicit format, the path ends in
    .parquet/.pq). An explicit format always wins over the extension.
    (water/api frames export; the reference's export_file parquet
    support.)"""
    import csv as _csv

    if _os.path.exists(path) and not force:
        raise FileExistsError(f"{path} exists; pass force=True")
    cols = frame.as_data_frame(use_pandas=False)
    if format == "parquet" or (format is None
                               and path.endswith((".parquet", ".pq"))):
        import pyarrow as pa
        import pyarrow.parquet as pq

        pq.write_table(
            pa.table({n: pa.array(cols[n]) for n in frame.names}), path)
        return path
    names = frame.names
    with open(path, "w", newline="") as f:
        wr = _csv.writer(f, delimiter=sep, quoting=_csv.QUOTE_MINIMAL)
        if header:
            if quote_header:  # reference quotes ONLY the header names
                _csv.writer(f, delimiter=sep,
                            quoting=_csv.QUOTE_ALL).writerow(names)
            else:
                wr.writerow(names)
        mats = [cols[n] for n in names]
        for i in range(frame.nrow):
            wr.writerow([
                "" if v is None or (isinstance(v, float) and np.isnan(v)) else v
                for v in (m[i] for m in mats)
            ])
    return path


def get_model(model_id: str):
    """`h2o.get_model` — fetch a trained model from the DKV by id (or from
    the attached server when connected remotely)."""
    conn = client.current_connection()
    if conn is not None:
        m = client.RemoteModel(conn, model_id)
        m._json()          # 404 now, not on first use
        return m
    m = _DKV.get(model_id)
    if m is None:
        raise KeyError(model_id)
    return m


def frames():
    return _DKV.keys(Frame)


def as_list(data, use_pandas: bool = False, header: bool = True):
    """`h2o.as_list` — frame contents as a pandas DataFrame or a list of
    row lists (header row first when header=True)."""
    if use_pandas:
        return data.as_data_frame(use_pandas=True)
    cols = data.as_data_frame(use_pandas=False)
    names = list(data.names)
    rows = [list(r) for r in zip(*(cols[n] for n in names))]
    return [names] + rows if header else rows


def cluster_status() -> None:
    """`h2o.cluster_status` — print cloud health (h2o-py cluster_status;
    reads the SERVER's /3/Cloud when connected)."""
    conn = client.current_connection()
    if conn is not None:
        info = conn.cluster_info()
        print(f"cloud {info.get('cloud_name')!r} v{info.get('version')}: "
              f"{info.get('cloud_size')} node(s), healthy="
              f"{info.get('cloud_healthy', True)}")
        return
    cluster().show_status()


def network_test():
    """`h2o.network_test` — transport microbenchmark (NetworkTestHandler;
    here the data plane is the host↔device link). Returns the per-size
    results table; runs SERVER-side when connected."""
    conn = client.current_connection()
    if conn is not None:
        return conn.get("/3/NetworkTest")["results"]
    from .runtime.nettest import run_network_test

    return run_network_test()


def log_and_echo(message: str = "") -> None:
    """`h2o.log_and_echo` — drop a marker line into the cluster log
    (LogAndEchoHandler)."""
    conn = client.current_connection()
    if conn is not None:
        conn.post("/3/LogAndEcho", message=message)
        return
    _Log.info(f"[LogAndEcho] {message}")


def download_all_logs(dirname: str = ".", filename: Optional[str] = None) -> str:
    """`h2o.download_all_logs` — write the cluster log ring as a zip
    (LogsHandler download; the remote form pulls the SERVER's log)."""
    import io as _io
    import zipfile as _zip

    conn = client.current_connection()
    if conn is not None:
        text = "\n".join(str(ln) for ln in conn.get("/3/Logs")["logs"])
    else:
        text = "\n".join(str(ln) for ln in _Log.get_logs())
    out = _os.path.join(dirname, filename or "h2o3_tpu_logs.zip")
    _os.makedirs(_os.path.dirname(out) or ".", exist_ok=True)
    buf = _io.BytesIO()
    with _zip.ZipFile(buf, "w", _zip.ZIP_DEFLATED) as z:
        z.writestr("h2o3_tpu.log", text)
    with open(out, "wb") as f:
        f.write(buf.getvalue())
    return out


def list_timezones() -> Frame:
    """`h2o.list_timezones` — one string column of zone names."""
    import zoneinfo

    names = sorted(zoneinfo.available_timezones())
    return Frame({"Timezones": np.asarray(names, dtype=object)},
                 column_types={"Timezones": "string"})


def estimate_cluster_mem(ncols: int, nrows: int, num_cols: int = 0,
                         string_cols: int = 0, cat_cols: int = 0,
                         time_cols: int = 0, uuid_cols: int = 0) -> float:
    """`h2o.estimate_cluster_mem` — recommended cluster memory (GB) for a
    dataset, the reference's rule of thumb: ~4× the in-memory data size,
    with per-type byte widths (numeric 8 B, categorical 2 B, time 8 B,
    UUID 16 B, string ~128 B). Unclassified columns count as numeric."""
    if ncols <= 0 or nrows <= 0:
        raise ValueError("ncols and nrows must be positive")
    typed = num_cols + string_cols + cat_cols + time_cols + uuid_cols
    if typed > ncols:
        raise ValueError("column type counts exceed ncols")
    plain = ncols - typed
    row_bytes = ((num_cols + plain) * 8 + string_cols * 128 + cat_cols * 2
                 + time_cols * 8 + uuid_cols * 16)
    gb = nrows * row_bytes / 1e9
    return round(4 * gb, 3)


def remove_all(retained=None) -> None:
    """`h2o.remove_all()` — clear the DKV, optionally keeping some keys
    (water/api RemoveAllHandler `retained_keys`). Connected remotely this
    clears the SERVER's DKV (`DELETE /3/DKV`), passing the retained keys
    through."""
    conn = client.current_connection()
    if conn is not None:
        names = [getattr(o, "key", None) or getattr(o, "model_id", None)
                 or str(o) for o in (retained or [])]
        if names:
            import json as _json

            conn.request("DELETE", "/3/DKV",
                         params={"retained_keys": _json.dumps(names)})
        else:
            conn.delete("/3/DKV")
        return
    keep = {getattr(o, "key", None) or getattr(o, "model_id", None) or o
            for o in (retained or [])}
    if not keep:
        _DKV.clear()
        return
    for k in list(_DKV.keys()):
        if k not in keep:
            _DKV.remove(k)


def insert_missing_values(frame: Frame, fraction: float = 0.1,
                          seed=None) -> Frame:
    """`h2o.insert_missing_values` — set a random fraction of each
    column's cells to NA IN PLACE (hex/CreateFrame MissingInserter). For a
    remote frame this runs server-side via `POST /3/MissingInserter`."""
    from .frame.vec import Vec

    if getattr(frame, "_is_remote", False):
        frame.conn.post("/3/MissingInserter", dataset=frame.key,
                        fraction=fraction, seed=seed)
        frame._cached = None
        return frame
    rng = np.random.default_rng(seed)
    for n in frame.names:
        v = frame.vec(n)
        mask = rng.random(frame.nrow) < fraction
        if v.type in ("real", "int", "time"):
            a = v.numeric_np().copy()
            a[mask] = np.nan
            frame[n] = Vec(a, v.type)       # keep the column's type label
        elif v.type == "enum":
            codes = np.asarray(v.data).copy()
            codes[mask] = -1
            frame[n] = Vec(codes, "enum", domain=v.domain)
        elif v.type == "string":
            strs = np.asarray(v.to_numpy(), dtype=object).copy()
            strs[mask] = None
            frame[n] = Vec(None, "string", strings=strs)
    return frame


def get_timezone() -> str:
    """`h2o.get_timezone` — the cluster datetime parsing zone (read from
    the SERVER when attached remotely)."""
    conn = client.current_connection()
    if conn is not None:
        return str(conn.rapids("(getTimeZone)").get("string"))
    from .frame.rapids_expr import _TIME_ZONE

    return _TIME_ZONE[0]


def set_timezone(tz: str) -> None:
    """`h2o.set_timezone` — honored by Rapids moment/asDate; applied on
    the SERVER when attached remotely (its Rapids session does the date
    parsing)."""
    import zoneinfo

    zoneinfo.ZoneInfo(tz)  # validate now, not at first use
    conn = client.current_connection()
    if conn is not None:
        conn.rapids(f'(setTimeZone "{tz}")')
        return
    from .frame.rapids_expr import _TIME_ZONE

    _TIME_ZONE[0] = tz


def download_csv(data, filename: str) -> str:
    """`h2o.download_csv` — write a frame as CSV client-side. Remote
    frames duck-type `frame_to_csv`'s surface (names/nrow/as_data_frame),
    so local and remote share the ONE serializer (and its guards)."""
    from .frame.frame import frame_to_csv

    with open(filename, "w") as f:
        f.write(frame_to_csv(data))
    return filename


def deep_copy(frame: Frame, dest: str) -> Frame:
    """`h2o.deep_copy` — independent copy of a frame's columns."""
    from .frame.vec import Vec

    out = {}
    for n, v in zip(frame.names, frame.vecs()):
        if v.type == "string":
            out[n] = Vec(None, "string", strings=np.asarray(v.to_numpy()).copy())
        else:
            out[n] = Vec(np.asarray(v.data).copy(), v.type, domain=v.domain)
    fr = Frame(out, key=dest)
    _DKV.put(dest, fr)
    return fr


def create_frame(rows: int = 10000, cols: int = 10, randomize: bool = True,
                 real_fraction: Optional[float] = None,
                 categorical_fraction: Optional[float] = None,
                 integer_fraction: Optional[float] = None,
                 binary_fraction: Optional[float] = None,
                 factors: int = 5, real_range: float = 100.0,
                 integer_range: int = 100, missing_fraction: float = 0.0,
                 has_response: bool = False, response_factors: int = 2,
                 seed: Optional[int] = None, frame_id: Optional[str] = None,
                 ):
    """`h2o.create_frame` — random synthetic frame (water/api CreateFrame),
    the generator many reference pyunits build fixtures with. Connected
    remotely the frame is generated ON the server (`POST /3/CreateFrame`)."""
    conn = client.current_connection()
    if conn is not None:
        out = conn.post(
            "/3/CreateFrame", rows=rows, cols=cols,
            randomize=int(randomize), real_fraction=real_fraction,
            categorical_fraction=categorical_fraction,
            integer_fraction=integer_fraction,
            binary_fraction=binary_fraction, factors=factors,
            real_range=real_range, integer_range=integer_range,
            missing_fraction=missing_fraction,
            has_response=int(has_response),
            response_factors=response_factors, seed=seed, dest=frame_id)
        return client.RemoteFrame(conn, out["destination_frame"]["name"])
    return _create_frame_local(
        rows, cols, randomize, real_fraction, categorical_fraction,
        integer_fraction, binary_fraction, factors, real_range,
        integer_range, missing_fraction, has_response, response_factors,
        seed, frame_id)


def _create_frame_local(rows, cols, randomize, real_fraction,
                        categorical_fraction, integer_fraction,
                        binary_fraction, factors, real_range, integer_range,
                        missing_fraction, has_response, response_factors,
                        seed, frame_id) -> Frame:
    """In-process generator core — what the server's /3/CreateFrame handler
    calls (never routes, so a process acting as both client and server
    can't loop back through its own connection)."""
    rng = np.random.default_rng(seed if seed is not None else 42)
    rf = 0.5 if real_fraction is None else real_fraction
    cf = 0.2 if categorical_fraction is None else categorical_fraction
    intf = 0.3 if integer_fraction is None else integer_fraction
    bf = 0.0 if binary_fraction is None else binary_fraction
    tot = max(rf + cf + intf + bf, 1e-12)
    # largest-remainder apportionment: exactly `cols` columns, and every
    # kind with a nonzero fraction keeps at least its floor share
    fracs = [("real", rf / tot), ("enum", cf / tot),
             ("int", intf / tot), ("bin", bf / tot)]
    floors = {k: int(np.floor(cols * f)) for k, f in fracs}
    rem = cols - sum(floors.values())
    by_rem = sorted(fracs, key=lambda kf: -(cols * kf[1] - floors[kf[0]]))
    for k, f in by_rem[:rem]:
        floors[k] += 1
    kinds = [k for k, _ in fracs for _ in range(floors[k])]
    d = {}
    types = {}
    for i, kind in enumerate(kinds):
        name = f"C{i+1}"
        if not randomize and kind != "enum":
            col = np.zeros(rows)  # CreateFrame randomize=false: constant 0
        elif kind == "real":
            col = rng.uniform(-real_range, real_range, rows)
        elif kind == "int":
            col = rng.integers(-integer_range, integer_range + 1, rows).astype(np.float64)
        elif kind == "bin":
            col = rng.integers(0, 2, rows).astype(np.float64)
        else:
            col = np.asarray([f"c{j}" for j in range(factors)], dtype=object)[
                rng.integers(0, factors, rows)]
            types[name] = "enum"
        if missing_fraction > 0 and kind != "enum":
            col = np.where(rng.uniform(size=rows) < missing_fraction, np.nan, col)
        d[name] = col
    if has_response:
        if response_factors > 1:
            d["response"] = np.asarray(
                [f"r{j}" for j in range(response_factors)], dtype=object)[
                rng.integers(0, response_factors, rows)]
            types["response"] = "enum"
        else:
            d["response"] = rng.normal(size=rows)
    fr = Frame.from_dict(d, column_types=types or None)
    if frame_id:
        fr.key = frame_id
    _DKV.put(fr.key, fr)
    return fr


def interaction(data, factors, pairwise: bool, max_factors: int,
                min_occurrence: int, destination_frame: Optional[str] = None):
    """`h2o.interaction` — interaction columns between categorical factors
    (hex/Interaction.java): combined levels, capped at max_factors most
    frequent (others pooled as 'other'), levels under min_occurrence
    dropped. For a remote frame this runs server-side
    (`POST /3/Interaction`)."""
    if getattr(data, "_is_remote", False):
        import json as _json

        # factors go over verbatim (ints included) — the server-side core
        # does the int→name mapping, so no metadata round-trip here
        out = data.conn.post(
            "/3/Interaction", source_frame=data.key,
            factor_columns=_json.dumps(list(factors)),
            pairwise=int(pairwise), max_factors=max_factors,
            min_occurrence=min_occurrence, dest=destination_frame)
        return client.RemoteFrame(data.conn,
                                  out["destination_frame"]["name"])
    return _interaction_local(data, factors, pairwise, max_factors,
                              min_occurrence, destination_frame)


def _interaction_local(data: Frame, factors, pairwise, max_factors,
                       min_occurrence, destination_frame=None) -> Frame:
    """In-process core — what the server's /3/Interaction handler calls."""
    from .frame.vec import Vec

    facs = [data.names[f] if isinstance(f, int) else f for f in factors]
    pairs = ([(a, b) for i, a in enumerate(facs) for b in facs[i + 1:]]
             if pairwise else [tuple(facs)])
    out = {}
    for combo in pairs:
        labels = []
        for c in combo:
            v = data.vec(c)
            dom = np.asarray((v.domain or []) + [None], dtype=object)
            labels.append(dom[np.asarray(v.data, np.int64)])
        joined = np.asarray(
            ["_".join("NA" if p is None else str(p) for p in row)
             for row in zip(*labels)], dtype=object)
        uniq, counts = np.unique(joined, return_counts=True)
        keep = uniq[counts >= max(min_occurrence, 1)]
        order = np.argsort(-counts[np.isin(uniq, keep)])
        kept = list(keep[order][:max_factors])
        lookup = {lbl: i for i, lbl in enumerate(kept)}
        other = len(kept)
        codes = np.asarray([lookup.get(s, other) for s in joined], np.int32)
        dom = kept + ["other"] if (codes == other).any() else kept
        name = "_".join(combo)
        out[name] = Vec(codes, "enum", domain=dom)
    fr = Frame(out, key=destination_frame)
    _DKV.put(fr.key, fr)
    return fr


from .explanation import (explain, explain_row,  # noqa: E402,F401
                          model_correlation_heatmap, pd_multi_plot,
                          residual_analysis, varimp_heatmap)


def batch():
    """`with h2o.batch():` — defer remote munging ops and ship them as one
    multi-statement Rapids program (see H2OConnection.batch). Requires an
    active remote connection."""
    conn = client.current_connection()
    if conn is None:
        raise client.H2OConnectionError(
            "h2o.batch() needs an active remote connection (h2o.connect)")
    return conn.batch()


def rapids(expr: str):
    """`h2o.rapids` — evaluate a Rapids sexpr against the DKV (routed over
    `/99/Rapids` when attached to a remote server)."""
    conn = client.current_connection()
    if conn is not None:
        return conn.rapids(expr)
    from .frame.rapids_expr import RapidsSession

    return RapidsSession(_DKV).execute(expr)


def no_progress():
    pass


def show_progress():
    pass


# model save/load (h2o.save_model / h2o.load_model → /3/Models.bin)
def save_model(model, path: str = ".", force: bool = False, filename=None) -> str:
    m = getattr(model, "_model", None) or model
    if getattr(m, "_is_remote", False):
        # REST-backed model: the artifact downloads from the server; the
        # local force= overwrite guard applies identically
        target = (path if _os.path.splitext(path)[1]
                  and not _os.path.isdir(path)
                  else _os.path.join(path, filename or f"{m.model_id}.h2o3"))
        if _os.path.exists(target) and not force:
            raise FileExistsError(f"{target} exists; pass force=True")
        return m.download_mojo(path, filename=filename)
    from .mojo import save_model as _save

    return _save(model, path, filename=filename, force=force)


def load_model(path: str):
    from .mojo import load_model as _load

    return _load(path)


def download_mojo(model, path: str = ".", **kw) -> str:
    return save_model(model, path)


def import_mojo(path: str):
    return load_model(path)


def api(endpoint: str, data: Optional[dict] = None):
    """`h2o.api("GET /3/Cloud")` — raw REST call against the attached
    server (h2o-py's escape hatch for routes without a wrapper)."""
    conn = client.current_connection()
    if conn is None:
        raise client.H2OConnectionError(
            "h2o.api needs an active remote connection (h2o.connect)")
    verb, _, path = endpoint.partition(" ")
    if not path.startswith("/"):
        raise ValueError(f"endpoint must be 'VERB /path', got {endpoint!r}")
    return conn.request(verb.upper(), path.strip(), params=data)


def download_model(model, path: str = ".", filename: Optional[str] = None) -> str:
    """`h2o.download_model` — fetch a model's artifact to local disk: a
    REST-backed model downloads from its server, an in-process model
    saves directly (one artifact format — MOJO ≡ binary here). Overwrites
    like h2o-py's download_model does."""
    return save_model(model, path, filename=filename, force=True)


def upload_model(path: str):
    """`h2o.upload_model` — push a LOCAL artifact to the attached server
    and load it there (returns the server-side model); in-process this is
    load_model."""
    conn = client.current_connection()
    if conn is None:
        return load_model(path)
    import urllib.parse as _up

    with open(path, "rb") as f:
        body = f.read()
    up = conn.request(
        "POST", "/3/PostFile?destination_frame="
                f"{_up.quote(_os.path.basename(path))}",
        data=body, content_type="application/octet-stream")
    # delete_source: the PostFile temp copy has served its purpose once
    # loaded — without this every upload leaks one zip in the server tmpdir
    out = conn.post("/99/Models.bin", path=up["destination_frame"],
                    delete_source=1)
    return client.RemoteModel(conn, out["models"][0]["model_id"]["name"])


# one artifact format: uploading a "MOJO" and a binary model are the same op
upload_mojo = upload_model


def print_mojo(mojo_path: str, format: str = "json"):
    """`h2o.print_mojo` — human-readable artifact dump: meta + array
    shapes (and per-forest tree counts for tree kinds). For full tree
    STRUCTURE use `h2o.tree.H2OTree` on the loaded model
    (hex/genmodel PrintMojo analog)."""
    import json as _json

    scorer = load_model(mojo_path)
    out = {"meta": {k: v for k, v in scorer.meta.items()},
           "arrays": {k: list(np.asarray(v).shape)
                      for k, v in scorer.arrays.items()}}
    if format == "json":
        return _json.dumps(out, indent=2, default=str)
    return out


def make_metrics(predicted, actuals, domain: Optional[Sequence] = None,
                 distribution: Optional[str] = None, **kw):
    """`h2o.make_metrics` — ModelMetrics from prediction and actual
    columns (water/api MakeMetricsHandler): regression when no domain,
    binomial for a 2-level domain (predicted = p1 column), multinomial
    for K levels (predicted = K probability columns)."""
    from .models.metrics import (ModelMetricsBinomial,
                                 ModelMetricsMultinomial,
                                 ModelMetricsRegression)

    def _cols(obj):
        if isinstance(obj, Frame):
            return np.column_stack([obj.vec(n).numeric_np()
                                    for n in obj.names])
        a = np.asarray(obj, np.float64)
        return a[:, None] if a.ndim == 1 else a

    pred = _cols(predicted)
    if isinstance(actuals, Frame):
        av = actuals.vec(actuals.names[0])
    else:
        av = actuals
    if domain is None:
        act = (av.numeric_np() if hasattr(av, "numeric_np")
               else np.asarray(av, np.float64))
        return ModelMetricsRegression.make(act, pred[:, 0])
    dom = [str(d) for d in domain]
    if hasattr(av, "data") and getattr(av, "type", None) == "enum":
        codes = np.asarray(av.data, np.int64)
        if av.domain and list(map(str, av.domain)) != dom:
            lookup = {d: i for i, d in enumerate(dom)}
            remap = np.asarray([lookup.get(str(d), -1) for d in av.domain])
            codes = np.where(codes >= 0, remap[np.maximum(codes, 0)], -1)
    else:
        vals = (av.to_numpy() if hasattr(av, "to_numpy")
                else np.asarray(av))
        lookup = {d: i for i, d in enumerate(dom)}
        codes = np.asarray([lookup.get(str(v), -1) for v in vals], np.int64)
    if (codes < 0).any():
        bad = int((codes < 0).sum())
        raise ValueError(
            f"make_metrics: {bad} actual value(s) are NA or outside the "
            f"given domain {dom} — metrics over unmatched rows would be "
            "silently wrong; clean the actuals or fix the domain")
    if len(dom) == 2:
        return ModelMetricsBinomial.make(codes, pred[:, -1])
    if pred.shape[1] != len(dom):
        raise ValueError(
            f"multinomial make_metrics needs {len(dom)} probability "
            f"columns, got {pred.shape[1]}")
    return ModelMetricsMultinomial.make(codes, pred)


def save_grid(grid, grid_directory: str,
              export_cross_validation_predictions: bool = False) -> str:
    """`h2o.save_grid` — export a trained grid (state + per-model
    artifacts) so `h2o.load_grid(grid_directory)` restores it."""
    if export_cross_validation_predictions:
        raise NotImplementedError(
            "export_cross_validation_predictions is not part of this "
            "artifact format (holdout predictions are recomputable from "
            "the restored models)")
    return grid.save(grid_directory)


def load_grid(grid_file_path: str, grid_id: Optional[str] = None):
    """`h2o.load_grid` — re-import a checkpointed grid from its
    recovery_dir (hex/grid recovery)."""
    import glob as _glob

    from .models.grid import H2OGridSearch

    if grid_id is None:
        hits = sorted(_glob.glob(_os.path.join(grid_file_path, "*.grid.json")))
        if not hits:
            raise FileNotFoundError(f"no grid state under {grid_file_path}")
        if len(hits) > 1:
            ids = [_os.path.basename(h)[: -len(".grid.json")] for h in hits]
            raise ValueError(
                f"multiple grids under {grid_file_path}: {ids}; pass grid_id"
            )
        grid_id = _os.path.basename(hits[0])[: -len(".grid.json")]
    return H2OGridSearch.load(grid_file_path, grid_id)


# the package's own import, as one retroactive span: it is inside a server's
# cold start and inside what a benchmark counts as set-up
from .runtime import tracing as _tracing

_tracing.record_span("program.import", _time.perf_counter() - _T_IMPORT,
                     kind="program")
