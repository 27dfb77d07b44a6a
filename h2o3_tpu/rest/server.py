"""RequestServer — the versioned JSON-over-HTTP route table.

Reference parity: `h2o-core/src/main/java/water/api/RequestServer.java`
(route registration, versioned paths), `ModelBuilderHandler.java` (train via
`POST /3/ModelBuilders/{algo}`), `FramesHandler`/`ModelsHandler`/
`JobsHandler`/`PredictionsHandler`/`LogsHandler`/`ProfilerHandler`, plus
`/99/Rapids` (`water/rapids/Rapids.java`). Jetty is replaced by the stdlib
ThreadingHTTPServer — the webserver-iface indirection exists so the server
can be swapped, same as `h2o-webserver-iface/`.

Training runs on a worker thread under a `Job` so `/3/Jobs/{id}` polling
behaves like the reference's async job keys.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional, Tuple

import numpy as np

from ..frame.frame import Frame
from ..frame.rapids_expr import RapidsSession
from ..models.model_base import H2OModel, Job
from ..runtime import metrics_registry as registry
from ..runtime import tracing
from ..runtime.dkv import DKV
from ..runtime.log import Log
from ..runtime.timeline import Timeline
from . import schemas

# per-route request accounting in the central registry: counter + latency
# histogram labeled by handler name (bounded cardinality — the route table
# is fixed), so the REST face itself is scrapable at GET /3/Metrics
_REQ_COUNT = registry.counter("h2o3_rest_requests",
                              "REST requests dispatched, per handler",
                              labelnames=("handler", "status"))
_REQ_MS = registry.histogram("h2o3_rest_request_ms",
                             "REST request wall time (ms), per handler",
                             labelnames=("handler",))


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        v = float(o)
        return v if np.isfinite(v) else None
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def _sanitize(o):
    """Replace non-finite floats with null BEFORE dumps — json.dumps never
    calls `default` for native floats, so NaN would otherwise serialize as a
    bare (invalid-JSON) NaN token."""
    if isinstance(o, float):
        return o if np.isfinite(o) else None
    if isinstance(o, dict):
        return {k: _sanitize(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_sanitize(v) for v in o]
    return o


def _frame_summary(fr: Frame, rows: int = 10) -> Dict:
    cols = []
    for n in fr.names:
        v = fr.vec(n)
        c = dict(label=n, type=v.type)
        if v.type in ("real", "int", "time"):
            c.update(mean=v.mean(), min=v.min(), max=v.max(), nacnt=v.nacnt())
        elif v.type == "enum":
            c.update(domain=v.domain, nacnt=v.nacnt())
        head = v.to_numpy()[:rows]
        c["data"] = [None if (isinstance(x, float) and np.isnan(x)) else x
                     for x in head.tolist()]
        cols.append(c)
    return dict(frame_id=dict(name=fr.key), rows=fr.nrow,
                num_columns=fr.ncol, columns=cols)


def _model_json(m: H2OModel) -> Dict:
    out = dict(
        model_id=dict(name=m.model_id),
        algo=m.algo,
        parameters=[dict(name=k, actual_value=v)
                    for k, v in m.parms.actual_params.items()
                    if not k.startswith("_")],
        output=dict(
            training_metrics=m.training_metrics._ser() if m.training_metrics else None,
            validation_metrics=m.validation_metrics._ser() if m.validation_metrics else None,
            cross_validation_metrics=(m.cross_validation_metrics._ser()
                                      if m.cross_validation_metrics else None),
            scoring_history=m.scoring_history,
            variable_importances=m.varimp_table,
            run_time=m.run_time,
        ),
    )
    return out


class _PayloadTooLarge(Exception):
    def __init__(self, n):
        super().__init__(f"request body of {n} bytes exceeds the "
                         "H2O3_MAX_BODY_MB cap")


class _Handler(BaseHTTPRequestHandler):
    server_version = "h2o3tpu"
    protocol_version = "HTTP/1.1"
    timeout = 120          # bounds slow-loris reads AND deferred TLS handshakes

    # route table (method, regex) → handler name — RequestServer.register
    ROUTES = [
        ("GET", r"^/(?:flow(?:/index\.html)?/?)?$", "flow"),
        ("GET", r"^/3/Cloud/?$", "cloud"),
        ("GET", r"^/3/About$", "about"),
        ("POST", r"^/3/ImportFiles$", "import_files"),
        ("POST", r"^/3/ParseSetup$", "parse_setup"),
        ("POST", r"^/3/Parse$", "parse"),
        ("GET", r"^/3/Frames$", "frames_list"),
        ("GET", r"^/3/Frames/([^/]+)/summary$", "frame_summary"),
        ("GET", r"^/3/Frames/([^/]+)$", "frame_get"),
        ("DELETE", r"^/3/Frames/([^/]+)$", "frame_delete"),
        ("POST", r"^/3/ModelBuilders/([^/]+)$", "train"),
        ("GET", r"^/3/ModelBuilders/([^/]+)$", "builder_schema"),
        ("GET", r"^/3/Models$", "models_list"),
        ("GET", r"^/3/Models/([^/]+)$", "model_get"),
        ("DELETE", r"^/3/Models/([^/]+)$", "model_delete"),
        ("POST", r"^/3/Predictions/models/([^/]+)/frames/([^/]+)$", "predict"),
        ("GET", r"^/3/Serving/metrics$", "serving_metrics"),
        ("GET", r"^/3/Faults$", "faults_get"),
        ("POST", r"^/3/Faults$", "faults_set"),
        ("DELETE", r"^/3/Faults$", "faults_delete"),
        ("GET", r"^/3/Ingest/metrics$", "ingest_metrics"),
        ("GET", r"^/3/Munge/metrics$", "munge_metrics"),
        ("GET", r"^/3/Training/metrics$", "training_metrics"),
        ("DELETE", r"^/3/Serving/cache$", "serving_cache_clear"),
        ("POST", r"^/3/ModelMetrics/models/([^/]+)/frames/([^/]+)$", "model_metrics"),
        ("GET", r"^/3/Jobs$", "jobs_list"),
        ("GET", r"^/3/Jobs/([^/]+)$", "job_get"),
        ("POST", r"^/99/Rapids$", "rapids"),
        ("GET", r"^/3/Logs(?:/download)?$", "logs"),
        ("GET", r"^/3/Timeline$", "timeline"),
        ("GET", r"^/3/Metrics$", "metrics"),
        ("GET", r"^/3/Memory$", "memory"),
        ("GET", r"^/3/Trace$", "trace"),
        ("GET", r"^/3/Supervisor$", "supervisor_get"),
        ("GET", r"^/3/Fleet$", "fleet_get"),
        ("POST", r"^/3/Fleet$", "fleet_set"),
        ("DELETE", r"^/3/Fleet$", "fleet_delete"),
        ("GET", r"^/3/Profiler$", "profiler"),
        ("GET", r"^/3/Metadata/schemas$", "metadata_schemas"),
        ("POST", r"^/3/Frames/([^/]+)/export$", "frame_export"),
        ("POST", r"^/99/Models\.bin/([^/]+)$", "model_save"),
        ("POST", r"^/99/Models\.bin$", "model_load"),
        ("POST", r"^/3/PostFile$", "post_file"),
        ("POST", r"^/99/Grid/([^/]+)$", "grid_train"),
        ("GET", r"^/99/Grids$", "grids_list"),
        ("GET", r"^/99/Grids/([^/]+)$", "grid_get"),
        ("POST", r"^/99/AutoMLBuilder$", "automl_build"),
        ("GET", r"^/99/AutoML/([^/]+)$", "automl_get"),
        ("GET", r"^/99/Leaderboards/([^/]+)$", "leaderboard_get"),
        ("POST", r"^/3/Recovery$", "recovery"),
        ("POST", r"^/3/Shutdown$", "shutdown"),
        ("GET", r"^/99/Flows$", "flows_list"),
        ("POST", r"^/99/Flows$", "flow_save"),
        ("GET", r"^/99/Flows/([^/]+)$", "flow_load"),
        ("DELETE", r"^/99/Flows/([^/]+)$", "flow_delete"),
        ("GET", r"^/3/Tree$", "tree"),
        ("GET", r"^/3/ModelMetrics$", "model_metrics_list"),
        ("GET", r"^/99/Typeahead/files$", "typeahead"),
        ("GET", r"^/3/WaterMeterCpuTicks/(\d+)$", "water_meter"),
        ("GET", r"^/3/NetworkTest$", "network_test"),
        ("POST", r"^/3/GarbageCollect$", "garbage_collect"),
        ("POST", r"^/3/ModelBuilders/([^/]+)/parameters$", "validate_params"),
        ("GET", r"^/3/Models/([^/]+)/mojo$", "model_mojo"),
        ("GET", r"^/3/DownloadDataset(?:\.bin)?$", "download_dataset"),
        ("POST", r"^/3/SplitFrame$", "split_frame"),
        ("POST", r"^/4/sessions$", "session_open"),
        ("DELETE", r"^/4/sessions/([^/]+)$", "session_close"),
        ("DELETE", r"^/3/DKV$", "remove_all"),
        ("DELETE", r"^/3/DKV/([^/]+)$", "remove_key"),
        ("POST", r"^/3/LogAndEcho$", "log_and_echo"),
        ("GET", r"^/3/Capabilities$", "capabilities"),
        ("GET", r"^/3/Ping$", "ping"),
        ("GET", r"^/3/Frames/([^/]+)/columns/([^/]+)/summary$",
         "column_summary"),
        ("POST", r"^/3/CreateFrame$", "create_frame"),
        ("POST", r"^/3/Interaction$", "interaction"),
        ("POST", r"^/3/MissingInserter$", "missing_inserter"),
        ("GET", r"^/3/ModelBuilders$", "builders_list"),
        ("POST", r"^/3/Jobs/([^/]+)/cancel$", "job_cancel"),
        ("GET", r"^/3/Frames/([^/]+)/columns$", "frame_columns"),
        ("GET", r"^/3/Frames/([^/]+)/columns/([^/]+)/domain$",
         "column_domain"),
        ("POST", r"^/3/Tabulate$", "tabulate"),
        ("GET", r"^/3/JStack$", "jstack"),
        ("POST", r"^/3/PartialDependence$", "pdp"),
        ("GET", r"^/3/PartialDependence/([^/]+)$", "pdp_get"),
        ("GET", r"^/3/Word2VecSynonyms$", "w2v_synonyms"),
        ("POST", r"^/3/Word2VecTransform$", "w2v_transform"),
        ("GET", r"^/3/Metadata/endpoints$", "metadata_endpoints"),
        ("POST", r"^/3/UnlockKeys$", "unlock_keys"),
        ("GET", r"^/3/Router$", "router_get"),
        ("POST", r"^/3/Router$", "router_post"),
        ("POST", r"^/3/Router/models/([^/]+)/frames/([^/]+)$",
         "router_predict"),
        ("POST", r"^/3/Serving/warm$", "serving_warm"),
    ]

    def log_message(self, fmt, *args):  # route access logs into our Log
        Log.debug("REST " + fmt % args)

    # -- plumbing ------------------------------------------------------------
    def _send(self, obj, status: int = 200,
              headers: Optional[Dict[str, str]] = None):
        body = json.dumps(_sanitize(obj), default=_json_default).encode()
        self._send_raw(body, "application/json", status=status,
                       headers=headers)

    def _send_raw(self, body: bytes, content_type: str, status: int = 200,
                  headers: Optional[Dict[str, str]] = None):
        self._status = status
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        tid = getattr(self, "_trace_id", None)
        if tid:
            # echo the request's trace id (minted server-side when the
            # client sent none) so callers can fetch GET /3/Trace?trace_id=
            self.send_header("X-H2O3-Trace-Id", tid)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _body_cap(self) -> int:
        """Request-size cap (413 beyond it): a hand-rolled HTTP face must
        not buffer unbounded bodies (Jetty's maxFormContentSize stance)."""
        return int(os.environ.get("H2O3_MAX_BODY_MB", 512)) << 20

    def _read_body(self) -> bytes:
        ln = int(self.headers.get("Content-Length") or 0)
        cap = self._body_cap()
        if ln > cap:
            # drain (bounded) so the client can read the 413 instead of a
            # broken pipe, then refuse; past 4x the cap, hard-close
            left = min(ln, 4 * cap)
            while left > 0:
                chunk = self.rfile.read(min(left, 1 << 20))
                if not chunk:
                    break
                left -= len(chunk)
            self.close_connection = True
            raise _PayloadTooLarge(ln)
        return self.rfile.read(ln) if ln else b""

    def _params(self) -> Dict[str, str]:
        q = urllib.parse.urlparse(self.path).query
        out = {k: v[0] for k, v in urllib.parse.parse_qs(q).items()}
        raw = self._read_body()
        if raw:
            raw = raw.decode()
            ctype = self.headers.get("Content-Type", "")
            if "json" in ctype:
                out.update(json.loads(raw))
            else:
                out.update({k: v[0] for k, v in urllib.parse.parse_qs(raw).items()})
        return out

    def _dispatch(self, method: str):
        path = urllib.parse.urlparse(self.path).path
        # observability spine: every request runs under a root span whose
        # trace id comes from the client's X-H2O3-Trace-Id header (minted
        # here when absent) and is echoed back by _send; child work — jobs,
        # candidates, batches, parses, munge ops — records into the same
        # trace. Assigned first thing, per request: the handler instance
        # persists across a keep-alive connection, so a stale id must never
        # leak into the next request's response (a 401/404 included).
        tid = (self.headers.get("X-H2O3-Trace-Id") or "")[:64]
        self._trace_id = tid or tracing.new_trace_id()
        token = getattr(self.server, "auth_token", None)
        if token:
            # bearer-token auth (the `-internal_security_conf` stance:
            # reject before any handler runs; /3/Cloud stays open so
            # clients can discover the cloud and fail with a clear 401)
            import hmac

            sent = self.headers.get("Authorization", "")
            ok = (hmac.compare_digest(sent, f"Bearer {token}")
                  or hmac.compare_digest(sent, f"Basic {token}"))
            if not ok and path not in ("/3/Cloud", "/3/Cloud/"):
                self._send(dict(__meta=dict(schema_type="H2OError"),
                                msg="unauthorized: missing or bad "
                                    "Authorization header",
                                http_status=401), 401)
                return
        for m, pat, name in self.ROUTES:
            if m != method:
                continue
            g = re.match(pat, path)
            if g:
                self._status = 200
                t0 = time.perf_counter()
                try:
                    Timeline.record("rest", f"{method} {path}",
                                    trace_id=self._trace_id)
                    with tracing.span(f"{method} {path}", kind="request",
                                      trace_id=self._trace_id,
                                      handler=name):
                        getattr(self, "h_" + name)(
                            *[urllib.parse.unquote(x) for x in g.groups()])
                except _PayloadTooLarge as e:
                    self._send(dict(__meta=dict(schema_type="H2OError"),
                                    msg=str(e), http_status=413), 413)
                except FileNotFoundError as e:
                    # missing server-side paths (ImportFiles, Models.bin,
                    # flows) are client errors, not server bugs
                    self._send(dict(__meta=dict(schema_type="H2OError"),
                                    msg=str(e), http_status=404), 404)
                except KeyError as e:
                    self._send(dict(__meta=dict(schema_type="H2OError"),
                                    msg=f"not found: {e}",
                                    http_status=404), 404)
                except (ValueError, TypeError) as e:
                    # client errors → 4xx (H2OErrorV3 with http_status)
                    self._send(dict(__meta=dict(schema_type="H2OError"),
                                    msg=str(e), http_status=400,
                                    exception_type=type(e).__name__), 400)
                except Exception as e:
                    # server bugs are 5xx, not blamed on the client
                    Log.err(f"REST {path}: {e}")
                    self._send(dict(__meta=dict(schema_type="H2OError"),
                                    msg=str(e), http_status=500,
                                    dev_msg=f"unhandled in h_{name}",
                                    exception_type=type(e).__name__), 500)
                finally:
                    _REQ_COUNT.inc(1, name, str(self._status))
                    _REQ_MS.observe((time.perf_counter() - t0) * 1e3, name)
                return
        self._send(dict(msg=f"no route for {method} {path}"), 404)

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    # -- handlers ------------------------------------------------------------
    def h_flow(self):
        """`/flow/` — the built-in web UI (h2o-web's Flow analog)."""
        from .flow import FLOW_HTML

        body = FLOW_HTML.encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/html; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def h_cloud(self):
        import h2o3_tpu
        from ..parallel import mesh

        try:
            c = mesh.cloud()
            size, healthy = c.size, True
        except Exception:
            size, healthy = 0, False
        self._send(dict(version=h2o3_tpu.__version__, cloud_name="h2o3_tpu",
                        cloud_size=size, cloud_healthy=healthy,
                        consensus=True, locked=True,
                        # store accounting (the reference's per-node
                        # free_mem/Cleaner bookkeeping, reported per cloud)
                        dkv=DKV.stats()))

    def h_about(self):
        import h2o3_tpu

        self._send(dict(entries=[dict(name="Build project version",
                                      value=h2o3_tpu.__version__)]))

    def h_import_files(self):
        # the internal parser, NOT h2o.import_file: the package-level surface
        # routes to an attached remote server, and a process acting as BOTH
        # server and client (notebook + local server) must not loop back
        from ..frame.parse import import_file as _parse_import

        p = self._params()
        fr = _parse_import(p["path"], pattern=p.get("pattern") or None)
        DKV.put(fr.key, fr)
        self._send(dict(destination_frames=[fr.key], fails=[], dels=[]))

    def h_parse_setup(self):
        p = self._params()
        paths = p.get("source_frames") or [p.get("path")]
        if isinstance(paths, str):
            paths = json.loads(paths) if paths.startswith("[") else [paths]
        from ..frame.parse import import_file

        fr = import_file(paths[0].strip('"'))
        self._send(dict(
            source_frames=paths,
            number_columns=fr.ncol,
            column_names=fr.names,
            column_types=[fr.vec(n).type for n in fr.names],
            separator=44,
        ))

    def h_parse(self):
        from ..frame.parse import import_file as _parse_import

        p = self._params()
        paths = p.get("source_frames")
        if isinstance(paths, str):
            paths = json.loads(paths) if paths.startswith("[") else [paths]
        # ParseSetup-style overrides (water/parser ParseSetupV3 fields):
        # separator/column_names/column_types ride the Parse request so
        # remote clients get the same parse control as in-process callers
        sep = p.get("separator") or None
        if isinstance(sep, str) and sep.isdigit():
            sep = chr(int(sep))                # upstream sends a byte value
        col_names = p.get("column_names")
        if isinstance(col_names, str):
            col_names = json.loads(col_names)
        col_types = p.get("column_types")
        if isinstance(col_types, str):
            col_types = json.loads(col_types)
        if isinstance(col_types, list):
            # ParseV3 sends types positionally; the parser wants name→type
            names_for_types = col_names
            if not names_for_types:
                probe = _parse_import(paths[0].strip('"'), sep=sep)
                names_for_types = probe.names
            col_types = dict(zip(names_for_types, col_types))
        fr = _parse_import(paths[0].strip('"'), sep=sep,
                           col_names=col_names, col_types=col_types)
        dest = p.get("destination_frame")
        if dest:
            fr.key = dest
        DKV.put(fr.key, fr)
        self._send(dict(job=dict(status="DONE", dest=dict(name=fr.key)),
                        destination_frame=dict(name=fr.key)))

    def h_frames_list(self):
        """`GET /3/Frames[?offset=&limit=]` — paginated like the reference's
        FramesHandler (water/api/FramesHandler list pagination)."""
        p = self._params()
        offset = max(0, int(p.get("offset", 0) or 0))
        limit = max(0, int(p.get("limit", 0) or 0))
        frames = [DKV.get(k) for k in DKV.keys(Frame)]
        total = len(frames)
        if offset:
            frames = frames[offset:]
        if limit:
            frames = frames[:limit]
        self._send(dict(total_frames=total, offset=offset,
                        frames=[dict(frame_id=dict(name=f.key), rows=f.nrow,
                                     columns=f.ncol) for f in frames]))

    def h_frame_get(self, key):
        """`GET /3/Frames/{id}[?row_offset=&row_count=]` — summary, plus a
        data page when row_count is given (FramesHandler.fetch paging)."""
        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        p = self._params()
        summ = _frame_summary(fr)
        if p.get("row_count") not in (None, ""):
            off = max(int(p.get("row_offset", 0)), 0)
            cnt = min(int(p["row_count"]), 10_000)   # bulk = DownloadDataset
            summ["row_offset"] = off
            summ["row_count"] = cnt
            for cmeta in summ["columns"]:
                v = fr.vec(cmeta["label"])
                if v.type == "enum":
                    dom = np.asarray((v.domain or []) + [None], dtype=object)
                    vals = dom[np.asarray(v.data[off:off + cnt], np.int64)]
                    cmeta["data"] = [None if x is None else str(x)
                                     for x in vals]
                elif v.type == "string":
                    vals = np.asarray(v.to_numpy(), dtype=object)[
                        off:off + cnt]
                    cmeta["data"] = [None if x is None else str(x)
                                     for x in vals]
                else:
                    a = v.numeric_np()[off:off + cnt]
                    cmeta["data"] = [None if np.isnan(x) else float(x)
                                     for x in a]
        self._send(dict(frames=[summ]))

    h_frame_summary = h_frame_get

    def h_frame_delete(self, key):
        DKV.remove(key)
        self._send(dict())

    @staticmethod
    def _flag(p, name) -> bool:
        """REST booleans arrive as strings — 'false'/'0' must be False."""
        v = p.get(name)
        if isinstance(v, str):
            return v.lower() in ("true", "t", "1")
        return bool(v)

    def h_frame_export(self, key):
        """/3/Frames/{id}/export — write a frame to a server-side path
        (water/api FramesHandler.export)."""
        import h2o3_tpu as h2o

        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        p = self._params()
        h2o.export_file(fr, p["path"], force=self._flag(p, "force"))
        self._send(dict(job=dict(status="DONE"), path=p["path"]))

    def h_model_save(self, model_id):
        """/99/Models.bin/{id} — persist a model artifact to a server-side
        directory (the reference's `h2o.save_model` → /99/Models.bin)."""
        import h2o3_tpu as h2o

        p = self._params()
        # DKV directly, NOT h2o.get_model: the package surface routes to an
        # attached remote connection (server+client in one process)
        m = DKV.get(model_id)
        if m is None:
            raise KeyError(model_id)
        path = h2o.save_model(m, p.get("dir") or ".",
                              force=self._flag(p, "force"))
        self._send(dict(path=path))

    def h_model_load(self):
        """/99/Models.bin — load a saved artifact. The offline scorer must
        NOT clobber a live model under the same id (every model route
        type-checks for H2OModel), so a taken id gets a _loaded suffix."""
        import h2o3_tpu as h2o

        p = self._params()
        src = p["dir"] if "dir" in p else p["path"]
        scorer = h2o.load_model(src)
        if self._flag(p, "delete_source"):
            # upload flow: the PostFile temp copy is spent once loaded —
            # keeping it would leak one zip per upload in the server tmpdir
            try:
                os.unlink(src)
            except OSError:
                pass
        mid = base = scorer.meta.get("model_id", "loaded_model")
        i = 0
        while DKV.get(mid) is not None:
            i += 1
            mid = f"{base}_loaded{i if i > 1 else ''}"
        DKV.put(mid, scorer)
        self._send(dict(models=[dict(model_id=dict(name=mid))]))

    def h_shutdown(self):
        """/3/Shutdown — stop the REST server (water/api ShutdownHandler)."""
        self._send(dict(result="shutting down"))
        import threading

        threading.Thread(target=self.server.shutdown, daemon=True).start()

    def h_builder_schema(self, algo):
        self._send(schemas.schema_for(algo))

    def h_train(self, algo):
        reg = schemas.algo_registry()
        if algo not in reg:
            raise KeyError(algo)
        p = self._params()
        train_key = p.pop("training_frame", None)
        valid_key = p.pop("validation_frame", None)
        y = p.pop("response_column", p.pop("y", None))
        x = p.pop("x", None)
        ignored = p.pop("ignored_columns", None)
        train = DKV.get(train_key) if train_key else None
        if train is None:
            raise ValueError(f"training_frame {train_key!r} not in DKV")
        valid = DKV.get(valid_key) if valid_key else None
        if isinstance(x, str):
            x = json.loads(x)
        if isinstance(ignored, str):
            ignored = json.loads(ignored)
        cls = reg[algo]
        known = {**cls._common_defaults, **cls._param_defaults}
        kwargs = {}
        for k, v in p.items():
            if k in known:
                if isinstance(v, str):
                    try:
                        v = json.loads(v)
                    except (ValueError, TypeError):
                        pass
                kwargs[k] = v
        if ignored:
            kwargs["ignored_columns"] = ignored
        est = cls(**kwargs)
        import uuid

        job = Job(dest=f"{algo}_rest_{uuid.uuid4().hex[:8]}",
                  description=f"{algo} train").start()
        job.trace_id = tracing.current_trace_id()
        job.result = None  # model key once DONE (the job's `dest` is stable)
        DKV.put(job.dest, job)
        # the estimator adopts THIS job, so /3/Jobs progress and
        # DELETE /3/Jobs/{id} cancellation act on the run itself
        est._external_job = job

        def run():
            from ..models.model_base import JobCancelled
            from ..parallel import mesh

            try:
                with tracing.attach(job.trace_id, name=f"job:{job.dest}",
                                    kind="job", algo=algo), \
                        mesh.training_guard():
                    est.train(x=x, y=y, training_frame=train,
                              validation_frame=valid)
                m = est.model
                DKV.put(m.model_id, m)
                job.result = m.model_id
                job.done()
            except JobCancelled:
                Log.info(f"train {algo}: cancelled")   # status already set
            except Exception as e:
                Log.err(f"train {algo}: {e}")
                job.status = "FAILED"
                job.warnings.append(str(e))
            finally:
                # leak canary: a FAILED/CANCELLED job that left its dest
                # model in the DKV surfaces in /3/Memory's leak report
                from ..runtime import memory_ledger

                memory_ledger.job_end(job.result or job.dest, job.status)

        threading.Thread(target=run, daemon=True).start()
        self._send(dict(job=dict(key=dict(name=job.dest), status=job.status)))

    # -- saved flows (h2o-web Flow notebooks: save/load named cell lists) ---
    @staticmethod
    def _flows_dir():
        d = os.environ.get("H2O3_FLOWS_DIR") or os.path.join(
            os.path.expanduser("~"), ".h2o3tpu_flows")
        os.makedirs(d, exist_ok=True)
        return d

    @staticmethod
    def _flow_path(name):
        # Distinct names must map to distinct files: substituting disallowed
        # characters would collide "my flow" with "my_flow" and silently
        # overwrite, so reject instead (400 via ValueError).
        if not name:
            raise ValueError("flow name required")
        if len(name) > 128 or re.search(r"[^A-Za-z0-9._-]", name):
            raise ValueError(
                "flow name must match [A-Za-z0-9._-]{1,128}: %r" % name)
        return os.path.join(_Handler._flows_dir(), name + ".flow.json")

    def h_flows_list(self):
        d = self._flows_dir()
        out = []
        for f in sorted(os.listdir(d)):
            if f.endswith(".flow.json"):
                out.append(dict(name=f[: -len(".flow.json")],
                                modified=os.path.getmtime(
                                    os.path.join(d, f))))
        self._send(dict(flows=out))

    def h_flow_save(self):
        p = self._params()
        name = p.get("name")
        cells = p.get("cells")
        if isinstance(cells, str):
            cells = json.loads(cells)
        if not isinstance(cells, list):
            raise ValueError("cells must be a list of {type, src}")
        path = self._flow_path(str(name or ""))
        with open(path, "w") as f:
            json.dump(dict(name=name, cells=cells), f)
        self._send(dict(name=name, saved=True, cells=len(cells)))

    def h_flow_load(self, name):
        path = self._flow_path(name)
        if not os.path.exists(path):
            raise KeyError(name)
        with open(path) as f:
            self._send(json.load(f))

    def h_flow_delete(self, name):
        path = self._flow_path(name)
        if not os.path.exists(path):
            raise KeyError(name)
        os.remove(path)
        self._send(dict(name=name, deleted=True))

    def h_tree(self):
        """`GET /3/Tree` — fetch one tree of a tree model (TreeV3 /
        `hex/tree/TreeHandler.java`): params model, tree_number,
        tree_class."""
        from ..tree_api import H2OTree

        p = self._params()
        mkey = p.get("model")
        m = DKV.get(mkey) if mkey else None
        if m is None:
            raise KeyError(f"model {mkey!r}")
        tree = H2OTree(m, int(p.get("tree_number", 0) or 0),
                       p.get("tree_class") or None)
        self._send(dict(
            model=dict(name=tree.model_id),
            tree_number=tree.tree_number,
            tree_class=tree.tree_class,
            root_node_id=tree.root_node_id,
            left_children=tree.left_children,
            right_children=tree.right_children,
            features=tree.features,
            thresholds=tree.thresholds,
            predictions=tree.predictions,
            nas=tree.nas,
            descriptions=tree.descriptions,
        ))

    def h_model_metrics_list(self):
        """`GET /3/ModelMetrics` — every stored model's metrics
        (ModelMetricsListSchemaV3 / water/api ModelMetricsHandler list)."""
        out = []
        for k in DKV.keys(H2OModel):
            m = DKV.get(k)
            for kind in ("training_metrics", "validation_metrics",
                         "cross_validation_metrics"):
                mm = getattr(m, kind, None)
                if mm is None:
                    continue
                d = {"model": dict(name=m.model_id), "kind": kind}
                for f in ("auc", "logloss", "rmse", "mse", "mean_residual_deviance"):
                    v = getattr(mm, f, None)
                    if v is not None:
                        try:
                            d[f] = float(v)
                        except (TypeError, ValueError):
                            pass
                out.append(d)
        self._send(dict(model_metrics=out))

    def h_typeahead(self):
        """`GET /99/Typeahead/files?src=...&limit=N` — filesystem path
        completion (water/api TypeaheadHandler)."""
        p = self._params()
        src = p.get("src", "") or ""
        limit = int(p.get("limit", 100) or 100)
        base = os.path.dirname(src) or "/"
        prefix = os.path.basename(src)
        matches = []
        try:
            for name in sorted(os.listdir(base)):
                if name.startswith(prefix):
                    full = os.path.join(base, name)
                    matches.append(full + ("/" if os.path.isdir(full) else ""))
                    if len(matches) >= limit:
                        break
        except OSError:
            pass
        self._send(dict(src=src, matches=matches, limit=limit))

    def h_network_test(self):
        """`GET /3/NetworkTest` — transport microbenchmark (water/api
        NetworkTestHandler analog). The reference measures node↔node RPC;
        the TPU framework's data plane is the host↔device link, so this
        times H2D+D2H round-trips per payload size (warm-up first — the
        first shape pays an XLA compile, which is not bandwidth). No
        collectives run here: a REST request reaches ONE rank, and a
        single-rank collective would hang the cloud (docs/distributed.md,
        concurrent-jobs section)."""
        import jax

        from ..runtime.nettest import run_network_test

        self._send(dict(nodes=jax.process_count(),
                        results=run_network_test()))

    def h_garbage_collect(self):
        """`POST /3/GarbageCollect` (water/api GarbageCollectHandler)."""
        import gc

        collected = gc.collect()
        self._send(dict(collected=collected, dkv=DKV.stats()))

    def h_water_meter(self, nodeidx):
        """`GET /3/WaterMeterCpuTicks/{node}` — per-cpu tick counters
        (water/util WaterMeterCpuTicks; Flow's CPU meter)."""
        ticks = []
        try:
            with open("/proc/stat") as f:
                for line in f:
                    if re.match(r"^cpu\d+ ", line):
                        parts = line.split()
                        user, nice, sys_, idle = (int(v) for v in parts[1:5])
                        ticks.append([user + nice, sys_, 0, idle])
        except OSError:
            pass
        self._send(dict(cpu_ticks=ticks))

    def h_models_list(self):
        models = [DKV.get(k) for k in DKV.keys(H2OModel)]
        self._send(dict(models=[_model_json(m) for m in models]))

    def h_model_get(self, key):
        from ..mojo import MojoScorer

        m = DKV.get(key)
        if isinstance(m, MojoScorer):
            # uploaded artifact: reduced schema from its stored metadata
            self._send(dict(models=[dict(
                model_id=dict(name=key), algo=m.algo,
                uploaded_artifact=True, kind=m.meta.get("kind"),
                response_column_name=m.y, output={})]))
            return
        if not isinstance(m, H2OModel):
            raise KeyError(key)
        self._send(dict(models=[_model_json(m)]))

    def h_model_delete(self, key):
        DKV.remove(key)
        # drop the model's compiled scorers too — cache hygiene on delete
        # (the identity check in ScorerCache already guarantees a re-created
        # model under this key can never hit the stale executable)
        from ..serving import peek_engine

        eng = peek_engine()
        if eng is not None:
            eng.cache.invalidate(key)
        self._send(dict())

    def h_predict(self, model_key, frame_key):
        from ..mojo import MojoScorer
        from ..serving import RejectedError, get_engine

        m = DKV.get(model_key)
        fr = DKV.get(frame_key)
        # uploaded/loaded artifacts (MojoScorer) serve predictions too —
        # that's the point of h2o.upload_model against a serving cluster
        if not isinstance(m, (H2OModel, MojoScorer)):
            raise KeyError(model_key)
        if not isinstance(fr, Frame):
            raise KeyError(frame_key)
        p = self._params()
        # upstream ModelMetricsHandler.predict options: SHAP contributions
        # and leaf indices ride the same route as plain predictions
        if self._flag(p, "predict_contributions"):
            kind, suffix = "contributions", "_contributions"
        elif self._flag(p, "leaf_node_assignment"):
            kind, suffix = "leaves", "_leaves"
        else:
            kind, suffix = "predict", ""
        # the serving path (docs/serving.md): admission → micro-batcher →
        # compiled-scorer cache. Concurrent requests for one model coalesce
        # into one device batch; repeats hit a warm executable.
        try:
            pred = get_engine().score(model_key, m, fr, output_kind=kind)
        except RejectedError as e:
            # backpressure, not failure: 429 + Retry-After so load
            # balancers and client retry loops back off instead of piling on
            retry = str(max(1, int(-(-e.retry_after_s // 1))))
            self._send(dict(__meta=dict(schema_type="H2OError"),
                            msg=str(e), http_status=429), 429,
                       headers={"Retry-After": retry})
            return
        # deterministic key: re-scoring the same (model, frame, kind)
        # OVERWRITES the previous prediction frame — the DKV must not
        # accumulate one leaked frame per repeat call (tested by the
        # DKV.keys() leak assertion in tests/test_rest_api.py)
        pred.key = f"prediction{suffix}_{model_key}_{frame_key}"
        DKV.put(pred.key, pred)
        self._send(dict(predictions_frame=dict(name=pred.key)))

    def h_serving_metrics(self):
        """`GET /3/Serving/metrics` — the scoring subsystem's counters +
        latency histograms (schema: schemas.serving_metrics_schema; also
        folded into /3/Profiler via runtime/profiler.serving_stats)."""
        from ..serving import peek_engine

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.serving_metrics_schema())
            return
        eng = peek_engine()
        body = (eng.snapshot() if eng is not None
                else dict(models={}, totals={}, cache=None, admission=None,
                          failover=None, config=None))
        self._send(dict(__meta=dict(schema_type=schemas.SERVING_SCHEMA_NAME),
                        **body))

    # -- fault injection (runtime/faults — docs/robustness.md) --------------
    def h_faults_get(self):
        """`GET /3/Faults` — armed fault points + fire counts, plus the
        shared retry-policy counters."""
        from ..runtime import profiler

        self._send(profiler.fault_stats())

    def h_faults_set(self):
        """`POST /3/Faults` — arm one fault point (the REST face of
        `faults.arm`): params point (required), error (io/conn/device/
        crash/none), rate, count, latency_ms, seed, lane, match (substring
        of the check detail — version-targeted faults). Chaos drills against a
        live serving cluster use this instead of a restart with
        H2O3_FAULT_* env vars."""
        from ..runtime import faults

        p = self._params()
        point = p.get("point")
        if not point:
            raise ValueError("point is required (e.g. serving.scorer)")
        out = faults.arm(
            str(point),
            error=str(p.get("error", "io")),
            rate=float(p.get("rate", 1.0) or 0.0),
            count=int(p["count"]) if p.get("count") not in (None, "")
            else None,
            latency_ms=float(p.get("latency_ms", 0.0) or 0.0),
            seed=int(p.get("seed", 0) or 0),
            lane=int(p["lane"]) if p.get("lane") not in (None, "")
            else None,
            match=str(p["match"]) if p.get("match") else None,
            after=int(p.get("after", 0) or 0))
        self._send(out)

    def h_faults_delete(self):
        """`DELETE /3/Faults[?point=]` — disarm one point, or all."""
        from ..runtime import faults

        p = self._params()
        point = p.get("point")
        if point:
            self._send(dict(disarmed=bool(faults.disarm(str(point))),
                            point=point))
        else:
            faults.reset()
            self._send(dict(disarmed=True, point=None))

    def h_ingest_metrics(self):
        """`GET /3/Ingest/metrics` — parse-pipeline throughput counters +
        per-phase timings (schema: schemas.ingest_metrics_schema; also
        folded into /3/Profiler via runtime/profiler.ingest_stats)."""
        from ..runtime import profiler

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.ingest_metrics_schema())
            return
        self._send(dict(__meta=dict(schema_type=schemas.INGEST_SCHEMA_NAME),
                        **profiler.ingest_stats()))

    def h_munge_metrics(self):
        """`GET /3/Munge/metrics` — munging-engine throughput counters +
        per-op stage timings (schema: schemas.munge_metrics_schema; also
        folded into /3/Profiler via runtime/profiler.munge_stats)."""
        from ..runtime import profiler

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.munge_metrics_schema())
            return
        self._send(dict(__meta=dict(schema_type=schemas.MUNGE_SCHEMA_NAME),
                        **profiler.munge_stats()))

    def h_training_metrics(self):
        """`GET /3/Training/metrics` — the multi-model training engine's
        scheduler occupancy, per-candidate timings, CV reuse counters and
        dataset-artifact cache stats (schema: schemas.training_metrics_
        schema; also folded into /3/Profiler via
        runtime/profiler.training_stats)."""
        from ..runtime import profiler

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.training_metrics_schema())
            return
        self._send(dict(__meta=dict(schema_type=schemas.TRAINING_SCHEMA_NAME),
                        **profiler.training_stats()))

    def h_serving_cache_clear(self):
        """`DELETE /3/Serving/cache[?model=key]` — evict compiled scorers
        (all, or one model's) so a hot-swapped artifact re-traces."""
        from ..serving import peek_engine

        p = self._params()
        eng = peek_engine()
        n = eng.cache.invalidate(p.get("model") or None) if eng else 0
        self._send(dict(invalidated=n))

    def h_model_metrics(self, model_key, frame_key):
        from ..mojo import MojoScorer

        m = DKV.get(model_key)
        fr = DKV.get(frame_key)
        if isinstance(m, MojoScorer):
            raise ValueError(
                f"{model_key!r} is an uploaded artifact (offline scorer): "
                "server-side metrics need a full model — run "
                "/3/Predictions and compute metrics from the actuals "
                "(h2o.make_metrics)")
        if not isinstance(m, H2OModel):
            raise KeyError(model_key)
        if not isinstance(fr, Frame):
            raise KeyError(frame_key)
        mm = m.model_performance(fr)
        self._send(dict(model_metrics=[dict(
            model=dict(name=model_key), frame=dict(name=frame_key),
            **(mm._ser() if mm else {}))]))

    @staticmethod
    def _job_json(j):
        return dict(key=dict(name=j.dest), status=j.status,
                    progress=j.progress, warnings=j.warnings,
                    dest=dict(name=getattr(j, "result", None) or j.dest))

    def h_jobs_list(self):
        jobs = [DKV.get(k) for k in DKV.keys(Job)]
        self._send(dict(jobs=[self._job_json(j) for j in jobs]))

    def h_job_get(self, key):
        j = DKV.get(key)
        if not isinstance(j, Job):
            raise KeyError(key)
        self._send(dict(jobs=[self._job_json(j)]))

    def h_rapids(self):
        p = self._params()
        # `rows` lets callers (e.g. Flow plot cells reading all hist bins)
        # ask for more than the 10-row preview; capped at 10k. Parsed BEFORE
        # evaluation so a malformed value cannot leak a computed frame into
        # DKV on the 400 path.
        rows = p.get("rows")
        rows = 10 if rows in (None, "") else min(max(0, int(rows)), 10_000)
        sess = RapidsSession(DKV)
        res = sess.execute(p["ast"])
        if isinstance(res, Frame):
            if not getattr(res, "key", None):
                res.key = f"rapids_{id(res)}"
            DKV.put(res.key, res)
            self._send(dict(key=dict(name=res.key),
                            **_frame_summary(res, rows=rows)))
        elif isinstance(res, (int, float)):
            self._send(dict(scalar=res))
        else:
            self._send(dict(string=str(res) if res is not None else None))

    def h_logs(self):
        self._send(dict(logs=Log.get_logs()))

    def h_timeline(self):
        """`GET /3/Timeline[?since=cursor&n=]` — the bounded event ring,
        plus recent span summaries. Every event carries a monotone `seq`;
        pass the returned `cursor` back as `since=` to tail
        incrementally."""
        p = self._params()
        try:
            since = p.get("since")
            since = int(since) if since not in (None, "") else None
            # n clamps to [1, 10000]: n=0 must not mean "the whole ring",
            # and with since= it must not return an empty page whose
            # cursor jumps past (and permanently loses) unread events
            n = min(max(int(p.get("n", 1000) or 1000), 1), 10_000)
        except ValueError as e:
            self._send(dict(__meta=dict(schema_type="H2OError"),
                            msg=f"bad since=/n= query param: {e}",
                            http_status=400), 400)
            return
        events, cursor = Timeline.tail(since, n=n)
        self._send(dict(events=events, cursor=cursor,
                        spans=tracing.summaries(min(n, 200))))

    def h_metrics(self):
        """`GET /3/Metrics` — the central registry in Prometheus text
        exposition format: every counter/gauge/histogram of every
        subsystem (serving, ingest, munge, training, retry, faults, REST,
        XLA compile/retrace) in one scrape. `?schema=1` returns the
        ObservabilityV3 field metadata as JSON instead (the sibling
        /3/*/metrics convention). `?format=json` returns the LOSSLESS
        family export (label tuples, raw histogram buckets) that fleet
        aggregators consume, and `?scope=fleet` answers for the WHOLE
        fleet: every registered peer scraped and merged (counters summed,
        histogram buckets summed, gauges per-replica, unreachable peers
        as explicit h2o3_fleet_peer_up 0 series — docs/observability.md
        "Fleet scope")."""
        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.observability_schema())
            return
        if p.get("scope") == "fleet":
            from ..runtime import fleet

            self._send_raw(fleet.fleet_metrics_text().encode(),
                           "text/plain; version=0.0.4; charset=utf-8")
            return
        if p.get("format") == "json":
            self._send(registry.export_state())
            return
        self._send_raw(registry.prometheus_text().encode(),
                       "text/plain; version=0.0.4; charset=utf-8")

    def h_memory(self):
        """`GET /3/Memory[?schema=1]` — the memory ledger: per-owner
        host/device bytes, by-kind totals, high watermarks + top owners at
        peak, leak report, pressure vs budget, and the device probe with
        the ledger-vs-runtime reconciliation (`unaccounted`). The same
        numbers scrape as `h2o3_memory_*` at GET /3/Metrics and fold into
        /3/Profiler."""
        from ..runtime import memory_ledger

        if self._flag(self._params(), "schema"):
            self._send(schemas.memory_schema())
            return
        self._send(dict(__meta=dict(schema_type=schemas.MEMORY_SCHEMA_NAME),
                        **memory_ledger.snapshot()))

    def h_trace(self):
        """`GET /3/Trace[?trace_id=][&scope=fleet]` — recorded spans as
        Chrome-trace/Perfetto JSON (load at ui.perfetto.dev). Without
        trace_id, the whole span ring exports; with it, one correlated
        request tree. `scope=fleet` pulls every registered peer's export
        too and merges them into one timeline with a process track per
        replica (X-H2O3-Trace-Id already crosses the client, so a
        trace_id-scoped fleet pull is one workflow across processes)."""
        p = self._params()
        tid = p.get("trace_id") or None
        if p.get("scope") == "fleet":
            from ..runtime import fleet

            self._send(fleet.fleet_trace(tid))
            return
        self._send(tracing.export_chrome(tid))

    # -- fleet aggregation (runtime/fleet — docs/observability.md) ----------
    def h_supervisor_get(self):
        """`GET /3/Supervisor[?schema=1]` — the elastic training
        supervisor: state machine, last abort/resume/checkpoint, counters,
        resolved config (runtime/supervisor; docs/robustness.md
        'Recovery matrix')."""
        from ..runtime import supervisor

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.supervisor_schema())
            return
        self._send(dict(
            __meta=dict(schema_type=schemas.SUPERVISOR_SCHEMA_NAME),
            **supervisor.snapshot()))

    def h_fleet_get(self):
        """`GET /3/Fleet[?probe=0]` — the fleet fold: per-replica liveness
        + serving counters + predict p99, fleet-merged totals. Scrapes
        peers by default; `probe=0` reports registration state only."""
        from ..runtime import fleet

        p = self._params()
        probe = p.get("probe") not in ("0", "false", "no")
        self._send(dict(__meta=dict(schema_type="FleetV3"),
                        **fleet.snapshot(scrape=probe)))

    def h_fleet_set(self):
        """`POST /3/Fleet` — register one peer replica: params `name`,
        `url` (REST origin). Replicas self-register through this (the
        launcher hook, fleet.register_with)."""
        from ..runtime import fleet

        p = self._params()
        self._send(fleet.register_peer(str(p.get("name") or ""),
                                       str(p.get("url") or "")))

    def h_fleet_delete(self):
        """`DELETE /3/Fleet?name=` — unregister one peer."""
        from ..runtime import fleet

        p = self._params()
        name = p.get("name")
        if not name:
            raise ValueError("name is required")
        self._send(dict(removed=bool(fleet.remove_peer(str(name))),
                        name=name))

    # -- serving fleet router (serving/router.py — docs/serving.md) ---------
    def h_router_get(self):
        """`GET /3/Router[?probe=1]` — the RouterV3 document: replica ring
        (liveness/drain/inflight/pressure/p99), per-model versions +
        live/canary/shadow pointers + split, canary health windows, shed/
        failover/rollback counters, config. `probe=1` forces a fleet
        scrape first; the default reads cached ring state (the
        metrics-consistency walk hits `?probe=0` — no HTTP fan-out)."""
        from ..serving import get_router

        p = self._params()
        if self._flag(p, "schema"):
            self._send(schemas.router_schema())
            return
        probe = self._flag(p, "probe")
        self._send(dict(__meta=dict(schema_type=schemas.ROUTER_SCHEMA_NAME),
                        **get_router().snapshot(probe=probe)))

    def h_router_post(self):
        """`POST /3/Router` — rollout control, one `action` per call:

        * ``publish`` (model, version[, path]) — export the DKV model (or
          copy the mojo at `path`) into the registry, atomically;
        * ``warm`` (model, version[, frame]) — fan the artifact out to
          every replica's scorer cache before any traffic flips;
        * ``canary`` (model, version[, pct]) — split pct% of traffic;
        * ``promote`` (model, version) — atomic hot-swap to live;
        * ``rollback`` (model[, reason]) — abort the canary (no-op with
          no canary, still timeline-logged);
        * ``shadow`` (model[, version]) — mirror-only scoring (empty
          version stops shadowing);
        * ``retire`` (model, version)."""
        from ..serving import get_router

        p = self._params()
        action = str(p.get("action") or "")
        model = str(p.get("model") or "")
        version = str(p.get("version") or "")
        if not action or not model:
            raise ValueError("action and model are required")
        router = get_router()
        reg = router.registry
        if action == "publish":
            path = p.get("path") or None
            out = reg.publish(model, version,
                              model=None if path else DKV.get(model),
                              source_path=path)
        elif action == "warm":
            out = router.warm(model, version, frame=p.get("frame") or None)
        elif action == "canary":
            pct = float(p.get("pct", router.config.canary_pct) or 0.0)
            out = reg.set_canary(model, version, pct)
        elif action == "promote":
            out = reg.promote(model, version)
        elif action == "rollback":
            out = reg.rollback(model, reason=str(p.get("reason") or ""))
        elif action == "shadow":
            out = reg.set_shadow(model, version or None)
        elif action == "retire":
            out = reg.retire(model, version)
        else:
            raise ValueError(f"unknown action {action!r} (publish/warm/"
                             "canary/promote/rollback/shadow/retire)")
        self._send(dict(action=action, **out))

    def h_router_predict(self, model_key, frame_key):
        """`POST /3/Router/models/{m}/frames/{f}` — the fleet scoring
        entry point: version split + least-loaded dispatch + failover.
        Mirrors the chosen replica's /3/Predictions response; sheds with
        429 + Retry-After; replica 4xx/exhausted-5xx pass through with
        their original status."""
        import urllib.error

        from ..serving import RejectedError, get_router

        p = self._params()
        try:
            doc = get_router().route(model_key, frame_key, params=p,
                                     trace_id=getattr(self, "_trace_id",
                                                      None))
        except RejectedError as e:
            retry = str(max(1, int(-(-e.retry_after_s // 1))))
            self._send(dict(__meta=dict(schema_type="H2OError"),
                            msg=str(e), http_status=429), 429,
                       headers={"Retry-After": retry})
            return
        except urllib.error.HTTPError as e:
            # mirror the replica's verdict (its body was already drained)
            self._send(dict(__meta=dict(schema_type="H2OError"),
                            msg=f"replica error: {e}",
                            http_status=e.code), e.code)
            return
        self._send(doc)

    def h_serving_warm(self):
        """`POST /3/Serving/warm` — the replica side of the router's warm
        fan-out: load the mojo artifact at `path` into the DKV under
        `model` (the versioned key) and, when `frame` names a DKV frame,
        prime the compiled-scorer cache by scoring it through the engine.
        Returns the XLA trace delta of the priming score — the registry
        records it per replica and the warm-load pin asserts the LIVE
        first predict traces nothing new."""
        from ..mojo import load_model
        from ..runtime import phases
        from ..serving import get_engine

        p = self._params()
        path, model_key = p.get("path"), p.get("model")
        if not path or not model_key:
            raise ValueError("path and model are required")
        scorer = load_model(str(path))
        DKV.put(str(model_key), scorer)
        out = dict(model=str(model_key), loaded=True, primed=False)
        frame_key = p.get("frame")
        fr = DKV.get(str(frame_key)) if frame_key else None
        if isinstance(fr, Frame):
            before = phases.xla_counts()
            pred = get_engine().score(str(model_key), scorer, fr)
            after = phases.xla_counts()
            pred.key = f"warm_{model_key}_{frame_key}"
            DKV.put(pred.key, pred)
            out.update(primed=True, frame=str(frame_key),
                       traces=after.get("traces", 0)
                       - before.get("traces", 0))
        self._send(out)

    def h_profiler(self):
        from ..runtime import profiler

        self._send(dict(nodes=[dict(node="local",
                                    entries=profiler.profile(nsamples=2,
                                                             interval=0.01))],
                        serving=profiler.serving_stats(),
                        ingest=profiler.ingest_stats(),
                        munge=profiler.munge_stats(),
                        training=profiler.training_stats(),
                        faults=profiler.fault_stats(),
                        tree=profiler.tree_stats(),
                        est=profiler.est_stats(),
                        xla=profiler.xla_stats(),
                        tracing=profiler.tracing_stats(),
                        memory=profiler.memory_stats(),
                        fleet=profiler.fleet_stats(),
                        router=profiler.router_stats(),
                        qos=profiler.qos_stats(),
                        metrics=profiler.registry_stats()))

    def h_metadata_schemas(self):
        self._send(dict(schemas=schemas.all_schemas()
                        + [schemas.observability_schema(),
                           schemas.memory_schema(),
                           schemas.router_schema()]))

    # -- uploads (PostFileHandler) ------------------------------------------
    def h_post_file(self):
        """`POST /3/PostFile` — raw or multipart upload to a server-side
        temp file; the returned destination key is a path usable as
        `source_frames` in ParseSetup/Parse (PostFileHandler semantics)."""
        import tempfile

        q = urllib.parse.urlparse(self.path).query
        qs = {k: v[0] for k, v in urllib.parse.parse_qs(q).items()}
        body = self._read_body()
        ctype = self.headers.get("Content-Type", "")
        if "multipart/form-data" in ctype and b"\r\n\r\n" in body:
            # minimal multipart: split on the boundary FIRST so a body with
            # several parts yields only the first part's payload instead of
            # embedding the later parts' headers (RFC 2046: the boundary
            # parameter may be quoted and need not be the last parameter)
            bpart = ctype.split("boundary=")[-1].split(";")[0].strip()
            boundary = b"--" + bpart.strip('"').encode()
            for part in body.split(boundary):
                if b"\r\n\r\n" not in part:
                    continue  # preamble / trailing "--\r\n"
                payload = part.split(b"\r\n\r\n", 1)[1]
                if payload.endswith(b"\r\n"):
                    payload = payload[:-2]
                body = payload
                break
        name = qs.get("destination_frame") or "upload"
        suffix = os.path.splitext(name)[1] or ".csv"
        tmp = tempfile.NamedTemporaryFile(
            prefix="h2o3_upload_", suffix=suffix, delete=False)
        tmp.write(body)
        tmp.close()
        self._send(dict(destination_frame=tmp.name,
                        total_bytes=len(body)))

    # -- grid search (GridSearchHandler, /99/Grids*) ------------------------
    def h_grid_train(self, algo):
        reg = schemas.algo_registry()
        if algo not in reg:
            raise KeyError(algo)
        p = self._params()
        train_key = p.pop("training_frame", None)
        y = p.pop("response_column", p.pop("y", None))
        x = p.pop("x", None)
        if isinstance(x, str):
            x = json.loads(x)
        train = DKV.get(train_key) if train_key else None
        if train is None:
            raise ValueError(f"training_frame {train_key!r} not in DKV")
        hyper = p.pop("hyper_parameters", None)
        if hyper is None:
            raise ValueError("hyper_parameters is required")
        if isinstance(hyper, str):
            hyper = json.loads(hyper)
        criteria = p.pop("search_criteria", None)
        if isinstance(criteria, str):
            criteria = json.loads(criteria)
        grid_id = p.pop("grid_id", None)
        parallelism = int(p.pop("parallelism", 1) or 1)
        cls = reg[algo]
        known = {**cls._common_defaults, **cls._param_defaults}
        base = {}
        for k, v in p.items():
            if k in known:
                if isinstance(v, str):
                    try:
                        v = json.loads(v)
                    except (ValueError, TypeError):
                        pass
                base[k] = v
        from ..models.grid import H2OGridSearch

        gs = H2OGridSearch(cls(**base), hyper, grid_id=grid_id,
                           search_criteria=criteria,
                           parallelism=parallelism)
        import uuid

        job = Job(dest=f"grid_rest_{uuid.uuid4().hex[:8]}",
                  description=f"{algo} grid").start()
        job.trace_id = tracing.current_trace_id()
        job.result = gs.grid_id
        # the sweep's parent job: POST /3/Jobs/{id}/cancel on it skips
        # unstarted combos and cancels in-flight candidates at their next
        # scoring boundary (runtime/trainpool.py child jobs)
        gs._external_job = job
        DKV.put(job.dest, job)
        DKV.put(gs.grid_id, gs)

        def run():
            from ..parallel import mesh

            try:
                with tracing.attach(job.trace_id, name=f"job:{job.dest}",
                                    kind="job", algo=algo), \
                        mesh.training_guard():
                    gs.train(x=x, y=y, training_frame=train)
                if job.cancel_requested:
                    job.status = "CANCELLED"
                    job.end_time = time.time()
                else:
                    job.done()
            except Exception as e:
                Log.err(f"grid {algo}: {e}")
                job.status = "FAILED"
                job.warnings.append(str(e))

        threading.Thread(target=run, daemon=True).start()
        self._send(dict(job=dict(key=dict(name=job.dest), status=job.status),
                        grid_id=gs.grid_id))

    @staticmethod
    def _grid_model_ids(gs):
        # live entries are estimators; recovered entries carry the artifact
        # path of the already-built model (grid recovery_dir semantics)
        return [e.model.model_id if hasattr(e, "model") else e.model_id
                for e in gs.models]

    def _grid_json(self, gs):
        return dict(
            grid_id=dict(name=gs.grid_id),
            model_ids=[dict(name=i) for i in self._grid_model_ids(gs)],
            hyper_names=list(gs.hyper_params),
            failure_details=[f.get("error", "") for f in gs.failed],
        )

    def h_grids_list(self):
        from ..models.grid import H2OGridSearch

        grids = [DKV.get(k) for k in DKV.keys(H2OGridSearch)]
        self._send(dict(grids=[self._grid_json(g) for g in grids]))

    def h_grid_get(self, grid_id):
        from ..models.grid import H2OGridSearch

        gs = DKV.get(grid_id)
        if not isinstance(gs, H2OGridSearch):
            raise KeyError(grid_id)
        self._send(self._grid_json(gs))

    # -- AutoML (/99/AutoMLBuilder, /99/Leaderboards) -----------------------
    def h_automl_build(self):
        p = self._params()
        spec = p.get("input_spec") or {}
        if isinstance(spec, str):
            spec = json.loads(spec)
        train_key = (spec.get("training_frame")
                     or p.get("training_frame"))
        y = spec.get("response_column") or p.get("response_column") or p.get("y")
        train = DKV.get(train_key) if train_key else None
        if train is None:
            raise ValueError(f"training_frame {train_key!r} not in DKV")
        if not y:
            raise ValueError("response_column is required")
        build = p.get("build_control") or {}
        if isinstance(build, str):
            build = json.loads(build)
        from ..automl.automl import H2OAutoML

        # 0 is meaningful for both (nfolds=0 disables CV, seed=0 is a valid
        # seed) — only fall back to the default when the key is truly absent
        seed = p.get("seed", build.get("seed"))
        nfolds = p.get("nfolds", build.get("nfolds"))
        kw = dict(seed=-1 if seed is None else int(seed),
                  nfolds=5 if nfolds is None else int(nfolds),
                  project_name=p.get("project_name"))
        max_models = int(p.get("max_models", build.get("max_models", 0)) or 0)
        if max_models:
            kw["max_models"] = max_models
        parallelism = int(p.get("parallelism",
                                build.get("parallelism", 1)) or 1)
        if parallelism != 1:
            kw["parallelism"] = parallelism
        # an EXPLICIT 0 means unlimited (the ctor default is 3600) — only
        # an absent key keeps the default
        max_rt = p.get("max_runtime_secs", build.get("max_runtime_secs"))
        if max_rt is not None and str(max_rt) != "":
            kw["max_runtime_secs"] = float(max_rt)
        if p.get("sort_metric"):
            kw["sort_metric"] = str(p["sort_metric"])
        for lk in ("exclude_algos", "include_algos"):
            v = p.get(lk, build.get(lk))
            if isinstance(v, str) and v:
                v = json.loads(v)
            if v:
                kw[lk] = list(v)
        aml = H2OAutoML(**kw)
        import uuid

        job = Job(dest=f"automl_rest_{uuid.uuid4().hex[:8]}",
                  description="AutoML").start()
        job.trace_id = tracing.current_trace_id()
        job.result = aml.project_name
        DKV.put(job.dest, job)
        DKV.put(aml.project_name, aml)
        x = spec.get("x") or p.get("x")
        if isinstance(x, str):
            x = json.loads(x)

        def run():
            from ..parallel import mesh

            try:
                with tracing.attach(job.trace_id, name=f"job:{job.dest}",
                                    kind="job", algo="automl"), \
                        mesh.training_guard():
                    aml.train(x=x, y=y, training_frame=train)
                job.done()
            except Exception as e:
                Log.err(f"automl: {e}")
                job.status = "FAILED"
                job.warnings.append(str(e))

        threading.Thread(target=run, daemon=True).start()
        self._send(dict(job=dict(key=dict(name=job.dest), status=job.status),
                        automl_id=dict(name=aml.project_name)))

    def _leaderboard_json(self, aml):
        # the build runs on a worker thread: leaderboard is None until
        # train() populates it — polling clients get an empty board, not 500
        rows = ([{k: v for k, v in r.items() if not k.startswith("_")}
                 for r in aml.leaderboard.rows]
                if aml.leaderboard is not None else [])
        lbm = (aml.leaderboard.sort_metric
               if aml.leaderboard is not None else None)
        return dict(project_name=aml.project_name,
                    leaderboard=dict(rows=rows, sort_metric=lbm))

    def h_automl_get(self, project):
        from ..automl.automl import H2OAutoML

        aml = DKV.get(project)
        if not isinstance(aml, H2OAutoML):
            raise KeyError(project)
        out = self._leaderboard_json(aml)
        leader = getattr(aml, "leader", None)
        out.update(leader=(dict(name=leader.model.model_id)
                           if leader is not None else None),
                   event_log=aml.event_log.events)
        self._send(out)

    def h_leaderboard_get(self, project):
        from ..automl.automl import H2OAutoML

        aml = DKV.get(project)
        if not isinstance(aml, H2OAutoML):
            raise KeyError(project)
        self._send(self._leaderboard_json(aml))

    # -- grid recovery (RecoveryHandler: POST /3/Recovery) ------------------
    def h_recovery(self):
        import h2o3_tpu as h2o

        p = self._params()
        rdir = p.get("recovery_dir")
        if not rdir:
            raise ValueError("recovery_dir is required")
        gs = h2o.load_grid(rdir, grid_id=p.get("grid_id"))
        DKV.put(gs.grid_id, gs)
        self._send(dict(grid_id=dict(name=gs.grid_id),
                        model_ids=[dict(name=i)
                                   for i in self._grid_model_ids(gs)]))


    # -- round-4 route tier --------------------------------
    def h_validate_params(self, algo):
        """`POST /3/ModelBuilders/{algo}/parameters` — validate WITHOUT
        training (ModelBuilderHandler validate_parameters)."""
        reg = schemas.algo_registry()
        if algo not in reg:
            raise KeyError(algo)
        p = self._params()
        cls = reg[algo]
        known = {**cls._common_defaults, **cls._param_defaults}
        skip = {"training_frame", "validation_frame", "response_column",
                "x", "y", "ignored_columns"}
        messages = []
        kwargs = {}
        for k, v in p.items():
            if k in skip:
                continue
            if k not in known:
                messages.append(dict(field_name=k, message_type="ERRR",
                                     message=f"unknown parameter {k!r}"))
                continue
            if isinstance(v, str):
                try:
                    v = json.loads(v)
                except (ValueError, TypeError):
                    pass
            kwargs[k] = v
        if not messages:
            try:
                est = cls(**kwargs)
                if hasattr(est, "_check_params"):
                    est._check_params()
            except (ValueError, TypeError) as e:
                messages.append(dict(field_name="", message=str(e),
                                     message_type="ERRR"))
        self._send(dict(
            messages=messages,
            error_count=sum(m["message_type"] == "ERRR" for m in messages)))

    def _send_bytes(self, data: bytes, ctype: str, filename: str):
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Disposition",
                         f'attachment; filename="{filename}"')
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def h_model_mojo(self, model_id):
        """`GET /3/Models/{id}/mojo` — download the MOJO artifact zip
        (ModelsHandler.fetchMojo)."""
        import tempfile

        from .. import mojo as mojolib

        from ..mojo import MojoScorer

        m = DKV.get(model_id)
        if not isinstance(m, (H2OModel, MojoScorer)):
            raise KeyError(model_id)
        with tempfile.TemporaryDirectory(prefix="h2o3_mojo_") as d:
            path = mojolib.save_model(m, d, force=True)
            with open(path, "rb") as f:
                data = f.read()
        self._send_bytes(data, "application/zip", f"{model_id}.zip")

    def h_download_dataset(self):
        """`GET /3/DownloadDataset?frame_id=` — stream a frame as CSV."""
        p = self._params()
        key = p.get("frame_id")
        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        from ..frame.frame import frame_to_csv

        self._send_bytes(frame_to_csv(fr).encode(), "text/csv",
                         f"{key}.csv")

    def h_split_frame(self):
        """`POST /3/SplitFrame` — ratios → destination frames
        (hex/SplitFrame)."""
        p = self._params()
        fr = DKV.get(p.get("dataset"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("dataset"))
        ratios = p.get("ratios")
        if isinstance(ratios, str):
            ratios = json.loads(ratios)
        dests = p.get("destination_frames")
        if isinstance(dests, str):
            dests = json.loads(dests)
        seed = int(p.get("seed") if p.get("seed") not in (None, "") else -1)
        parts = fr.split_frame(list(ratios),
                               seed=None if seed == -1 else seed)
        keys = []
        for i, part in enumerate(parts):
            part.key = (dests[i] if dests and i < len(dests)
                        else f"{fr.key}_part{i}")
            DKV.put(part.key, part)
            keys.append(part.key)
        self._send(dict(job=dict(status="DONE"),
                        destination_frames=[dict(name=k) for k in keys]))

    def h_session_open(self):
        """`POST /4/sessions` — h2o-py opens one per connection
        (InitIDHandler)."""
        import uuid

        sid = "_sid" + uuid.uuid4().hex[:12]
        DKV.put(sid, dict(type="session"))
        self._send(dict(session_key=sid))

    def h_session_close(self, sid):
        DKV.remove(sid)
        self._send(dict(session_key=sid))

    def h_remove_all(self):
        """`DELETE /3/DKV[?retained_keys=[...]]` — h2o.remove_all
        (RemoveAllHandler `retained_keys`): clear the DKV, keeping any
        listed keys."""
        p = self._params()
        retained = p.get("retained_keys")
        if isinstance(retained, str):
            retained = json.loads(retained) if retained else []
        keep = set(retained or [])
        keys = DKV.keys()
        if not keep:
            n = len(keys)
            DKV.clear()
        else:
            n = 0
            for k in list(keys):
                if k not in keep:
                    DKV.remove(k)
                    n += 1
        self._send(dict(removed=n, retained=sorted(keep)))

    @staticmethod
    def _opt(p, k, cast, dflt):
        """Optional request param: cast when present, default otherwise."""
        v = p.get(k)
        return dflt if v in (None, "") else cast(v)

    @staticmethod
    def _opt_bool(p, k, dflt=False):
        v = p.get(k)
        if v in (None, ""):
            return dflt
        return str(v).lower() in ("1", "true", "yes")

    def h_create_frame(self):
        """`POST /3/CreateFrame` — server-side synthetic frame generator
        (water/api CreateFrameHandler → hex/createframe); the REST face of
        `h2o.create_frame`."""
        import h2o3_tpu as _pkg

        p = self._params()
        _f = lambda k, cast, dflt: self._opt(p, k, cast, dflt)  # noqa: E731
        _b = lambda k, dflt: self._opt_bool(p, k, dflt)         # noqa: E731

        fr = _pkg._create_frame_local(
            rows=_f("rows", int, 10000), cols=_f("cols", int, 10),
            randomize=_b("randomize", True),
            real_fraction=_f("real_fraction", float, None),
            categorical_fraction=_f("categorical_fraction", float, None),
            integer_fraction=_f("integer_fraction", float, None),
            binary_fraction=_f("binary_fraction", float, None),
            factors=_f("factors", int, 5),
            real_range=_f("real_range", float, 100.0),
            integer_range=_f("integer_range", int, 100),
            missing_fraction=_f("missing_fraction", float, 0.0),
            has_response=_b("has_response", False),
            response_factors=_f("response_factors", int, 2),
            seed=_f("seed", int, None),
            frame_id=p.get("dest") or p.get("frame_id") or None)
        self._send(dict(job=dict(status="DONE"),
                        destination_frame=dict(name=fr.key),
                        rows=fr.nrow, cols=fr.ncol))

    def h_interaction(self):
        """`POST /3/Interaction` — pairwise/combined factor-interaction
        columns (water/api InteractionHandler → hex/Interaction.java)."""
        import h2o3_tpu as _pkg

        p = self._params()
        fr = DKV.get(p.get("source_frame") or p.get("dataset"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("source_frame") or p.get("dataset"))
        factors = p.get("factor_columns") or p.get("factors") or "[]"
        if isinstance(factors, str):
            factors = json.loads(factors)
        out = _pkg._interaction_local(
            fr, factors,
            pairwise=self._opt_bool(p, "pairwise"),
            max_factors=int(p.get("max_factors", 100)),
            min_occurrence=int(p.get("min_occurrence", 1)),
            destination_frame=p.get("dest") or None)
        self._send(dict(job=dict(status="DONE"),
                        destination_frame=dict(name=out.key),
                        cols=out.ncol))

    def h_builders_list(self):
        """`GET /3/ModelBuilders` — every registered algorithm + its
        parameter schema (ModelBuildersHandler.list; h2o-py algo
        discovery)."""
        reg = schemas.algo_registry()
        self._send(dict(model_builders={
            algo: dict(algo=algo, visibility="Stable",
                       can_build=["Supervised" if getattr(
                           cls, "supervised", True) else "Unsupervised"])
            for algo, cls in sorted(reg.items())}))

    def h_job_cancel(self, key):
        """`POST /3/Jobs/{id}/cancel` — request cancellation; the training
        driver honors it at its next scoring boundary (water.Job.stop)."""
        job = DKV.get(key)
        if not isinstance(job, Job):
            raise KeyError(key)
        job.cancel()
        self._send(dict(job=dict(key=dict(name=key), status=job.status,
                                 cancel_requested=job.cancel_requested)))

    def h_frame_columns(self, key):
        """`GET /3/Frames/{id}/columns` — column labels/types page
        (FramesHandler.columns)."""
        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        p = self._params()
        off = int(p.get("column_offset", 0))
        cnt = int(p.get("column_count", -1))
        names = fr.names[off:] if cnt < 0 else fr.names[off:off + cnt]
        self._send(dict(
            frame_id=dict(name=key), num_columns=fr.ncol,
            column_offset=off,
            columns=[dict(label=n, type=fr.vec(n).type) for n in names]))

    def h_column_domain(self, key, col):
        """`GET /3/Frames/{id}/columns/{col}/domain` — categorical levels
        (FramesHandler.columnDomain)."""
        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        if col not in fr.names:
            raise KeyError(col)
        v = fr.vec(col)
        dom = list(v.domain or [])
        self._send(dict(domain=[dom], map=list(range(len(dom)))))

    def h_tabulate(self):
        """`POST /3/Tabulate` — co-occurrence counts + mean response of a
        predictor × response column pair, binned (hex/Tabulate.java; the
        Flow 'tabulate' cell)."""
        p = self._params()
        fr = DKV.get(p.get("dataset"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("dataset"))
        pred, resp = p.get("predictor"), p.get("response")
        for c in (pred, resp):
            if c not in fr.names:
                raise KeyError(c)
        nbins_p = int(p.get("nbins_predictor", 20))
        nbins_r = int(p.get("nbins_response", 10))
        w = (fr.vec(p["weight"]).numeric_np().astype(np.float64)
             if p.get("weight") and p["weight"] in fr.names
             else np.ones(fr.nrow))
        w = np.nan_to_num(w, nan=0.0)   # NA-weight rows drop out, not NaN-ify

        def _codes(col, nbins):
            v = fr.vec(col)
            if v.type == "enum":
                labels = list(v.domain or [])
                return np.asarray(v.data, np.int64), labels
            a = v.numeric_np().astype(np.float64)
            fin = a[~np.isnan(a)]
            lo, hi = (float(fin.min()), float(fin.max())) if fin.size else (0, 1)
            span = max(hi - lo, 1e-12)
            c = np.clip(((a - lo) / span * nbins).astype(np.int64),
                        0, nbins - 1)
            c = np.where(np.isnan(a), -1, c)
            edges = [lo + span * i / nbins for i in range(nbins)]
            return c, [f"[{e:.4g},{lo + span * (i + 1) / nbins:.4g})"
                       for i, e in enumerate(edges)]

        cp, lp = _codes(pred, nbins_p)
        cr, lr = _codes(resp, nbins_r)
        ok = (cp >= 0) & (cr >= 0)
        counts = np.zeros((len(lp), len(lr)))
        np.add.at(counts, (cp[ok], cr[ok]), w[ok])
        # numeric_np maps enum NA codes (-1) to NaN, so NA responses are
        # excluded below instead of dragging bin means negative
        rnum = fr.vec(resp).numeric_np().astype(np.float64)
        rsum = np.zeros(len(lp))
        rcnt = np.zeros(len(lp))
        okr = (cp >= 0) & ~np.isnan(rnum)
        np.add.at(rsum, cp[okr], (rnum * w)[okr])
        np.add.at(rcnt, cp[okr], w[okr])
        with np.errstate(invalid="ignore"):
            rmean = np.where(rcnt > 0, rsum / np.maximum(rcnt, 1e-300),
                             np.nan)
        self._send(dict(
            predictor=pred, response=resp,
            predictor_labels=lp, response_labels=lr,
            count_table=[[float(x) for x in row] for row in counts],
            response_table=[None if np.isnan(m) else float(m)
                            for m in rmean]))

    def h_jstack(self):
        """`GET /3/JStack` — stack-trace samples of every live thread
        (water/api JStackHandler → util/JStack)."""
        from ..runtime.profiler import stack_samples

        self._send(dict(traces=stack_samples()))

    def h_pdp(self):
        """`POST /3/PartialDependence` — partial-dependence tables for a
        model × frame (hex/PartialDependence.java; h2o-py partial_plot's
        REST face). Computed synchronously, stored under a key for
        GET /3/PartialDependence/{id}."""
        import uuid

        p = self._params()
        model = DKV.get(p.get("model_id"))
        fr = DKV.get(p.get("frame_id"))
        if model is None:
            raise KeyError(p.get("model_id"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("frame_id"))
        cols = p.get("cols")
        if isinstance(cols, str):
            cols = json.loads(cols)
        if isinstance(cols, str):       # a bare JSON string names ONE column
            cols = [cols]
        tables = model.partial_plot(
            fr, cols=cols, nbins=int(p.get("nbins", 20)),
            include_na=str(p.get("include_na", "")).lower()
            in ("1", "true"))

        def _cell(x):
            # np.float32 is not a `float` — go through float() so every
            # numeric NaN (any width) becomes JSON null, never a NaN token
            if isinstance(x, str) or x is None:
                return x
            xf = float(x)
            return None if np.isnan(xf) else xf

        out = [{c: [_cell(x) for x in t.vec(c).to_numpy()]
                for c in t.names} for t in tables]
        key = p.get("destination_key") or f"pdp_{uuid.uuid4().hex[:8]}"
        DKV.put(key, dict(type="pdp", cols=list(cols),
                          partial_dependence_data=out))
        self._send(dict(destination_key=dict(name=key), cols=list(cols),
                        partial_dependence_data=out))

    def h_pdp_get(self, key):
        obj = DKV.get(key)
        if not isinstance(obj, dict) or obj.get("type") != "pdp":
            raise KeyError(key)
        self._send(dict(destination_key=dict(name=key),
                        cols=obj["cols"],
                        partial_dependence_data=obj[
                            "partial_dependence_data"]))

    def h_w2v_synonyms(self):
        """`GET /3/Word2VecSynonyms?model=&word=&count=` —
        Word2VecHandler.findSynonyms."""
        p = self._params()
        model = DKV.get(p.get("model"))
        if model is None or not hasattr(model, "find_synonyms"):
            raise KeyError(p.get("model"))
        syn = model.find_synonyms(str(p.get("word", "")),
                                  int(p.get("count", 20)))
        self._send(dict(synonyms=list(syn.keys()),
                        scores=[float(v) for v in syn.values()]))

    def h_w2v_transform(self):
        """`POST /3/Word2VecTransform?model=&words_frame=&aggregate_method=`
        — Word2VecHandler.transform: embed a words column."""
        p = self._params()
        model = DKV.get(p.get("model"))
        fr = DKV.get(p.get("words_frame"))
        if model is None or not hasattr(model, "transform"):
            raise KeyError(p.get("model"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("words_frame"))
        out = model.transform(
            fr, aggregate_method=str(p.get("aggregate_method", "NONE")))
        DKV.put(out.key, out)
        self._send(dict(vectors_frame=dict(name=out.key),
                        cols=out.ncol, rows=out.nrow))

    def h_metadata_endpoints(self):
        """`GET /3/Metadata/endpoints` — the live route table
        (MetadataHandler.listRoutes)."""
        self._send(dict(routes=[
            dict(http_method=m, url_pattern=rx, handler=h)
            for m, rx, h in self.ROUTES]))

    def h_unlock_keys(self):
        """`POST /3/UnlockKeys` — upstream force-unlocks wedged key locks
        (UnlockKeysHandler). This DKV has no lock table by design (pytree
        values, functional updates), so there is never anything to unlock —
        the route answers honestly for client compatibility."""
        self._send(dict(unlocked=0,
                        note="DKV is lock-free by design; nothing to unlock"))

    def h_missing_inserter(self):
        """`POST /3/MissingInserter` — set a random fraction of a frame's
        cells to NA in place (water/api MissingInserterHandler); the REST
        face of `h2o.insert_missing_values`."""
        from .. import insert_missing_values as _imv

        p = self._params()
        fr = DKV.get(p.get("dataset"))
        if not isinstance(fr, Frame):
            raise KeyError(p.get("dataset"))
        seed = p.get("seed")
        _imv(fr, fraction=float(p.get("fraction", 0.1)),
             seed=None if seed in (None, "") else int(seed))
        self._send(dict(job=dict(status="DONE"),
                        frame_id=dict(name=fr.key)))

    def h_remove_key(self, key):
        DKV.remove(key)
        self._send(dict(key=dict(name=key)))

    def h_log_and_echo(self):
        p = self._params()
        msg = str(p.get("message", ""))
        Log.info(f"[LogAndEcho] {msg}")
        self._send(dict(message=msg))

    def h_capabilities(self):
        """`GET /3/Capabilities` — registered extensions
        (CapabilitiesHandler)."""
        self._send(dict(capabilities=[
            dict(name=n, capability_type="rest")
            for n in ("Algos", "AutoML", "Grid", "Rapids", "Flow",
                      "MOJO", "TargetEncoder", "RemoteClient")]))

    def h_ping(self):
        import time as _t

        self._send(dict(status="healthy", timestamp=_t.time()))

    def h_column_summary(self, key, col):
        """`GET /3/Frames/{id}/columns/{col}/summary` — per-column stats +
        histogram (FramesHandler.columnSummary)."""
        fr = DKV.get(key)
        if not isinstance(fr, Frame):
            raise KeyError(key)
        if col not in fr.names:
            raise KeyError(col)
        v = fr.vec(col)
        out = dict(label=col, type=v.type, nacnt=v.nacnt())
        if v.type in ("real", "int", "time"):
            a = v.numeric_np()
            fin = a[~np.isnan(a)]
            if fin.size:
                cnt, edges = np.histogram(fin, bins=20)
                srt = np.sort(fin)
                out.update(
                    mean=float(fin.mean()), sigma=float(fin.std()),
                    mins=[float(x) for x in srt[:5]],
                    maxs=[float(x) for x in srt[-5:][::-1]],
                    percentiles=[float(np.percentile(srt, q)) for q in
                                 (1, 10, 25, 50, 75, 90, 99)],
                    histogram_bins=[int(c) for c in cnt],
                    histogram_base=float(edges[0]),
                    histogram_stride=float(edges[1] - edges[0]))
        elif v.type == "enum":
            codes = np.asarray(v.data)
            cnts = np.bincount(codes[codes >= 0],
                               minlength=len(v.domain or []))
            out.update(domain=v.domain,
                       domain_cardinality=len(v.domain or []),
                       histogram_bins=[int(c) for c in cnts])
        self._send(dict(frames=[dict(frame_id=dict(name=key),
                                     columns=[out])]))


class H2OApiServer:
    """webserver-iface: owns the listening socket + handler thread.

    TLS: pass `ssl_certfile`/`ssl_keyfile` to serve HTTPS — the
    `-internal_security_conf` stance (water/network/SocketChannelFactory
    wraps the socket; here it's `ssl.SSLContext.wrap_socket`)."""

    def __init__(self, port: int = 54321, host: str = "127.0.0.1",
                 auth_token: Optional[str] = None,
                 ssl_certfile: Optional[str] = None,
                 ssl_keyfile: Optional[str] = None):
        self.httpd = ThreadingHTTPServer((host, port), _Handler)
        # opt-in bearer-token auth (the reference's -internal_security_conf
        # hash-login analog); None = open, like the reference's default
        self.httpd.auth_token = auth_token
        self.scheme = "http"
        if ssl_certfile:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(ssl_certfile, ssl_keyfile)
            # handshake in the per-request thread, NOT the accept loop: a
            # client that trickles its ClientHello must not block accept()
            # for everyone else (do_handshake_on_connect=False defers the
            # handshake to the first read, which runs in the handler
            # thread; the handler timeout below bounds it)
            self.httpd.socket = ctx.wrap_socket(
                self.httpd.socket, server_side=True,
                do_handshake_on_connect=False)
            self.scheme = "https"
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "H2OApiServer":
        # a serving REST process always tracks XLA compiles/retraces — the
        # /3/Metrics retrace counters must not depend on bench env flags
        from ..runtime import phases

        phases.install_listener()
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="h2o3tpu-rest")
        self._thread.start()
        Log.info(f"REST server on {self.scheme}://{self.host}:{self.port}/3/")
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def start_server(port: int = 0, host: str = "127.0.0.1",
                 auth_token: Optional[str] = None,
                 ssl_certfile: Optional[str] = None,
                 ssl_keyfile: Optional[str] = None) -> H2OApiServer:
    return H2OApiServer(port=port, host=host, auth_token=auth_token,
                        ssl_certfile=ssl_certfile,
                        ssl_keyfile=ssl_keyfile).start()
