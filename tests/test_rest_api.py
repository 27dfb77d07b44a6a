"""REST API layer (L8) — /3 endpoint surface over a live loopback server.

Reference parity tests: the route table of `water/api/RequestServer.java`
driven the way `h2o-py/h2o/backend/connection.py` drives it (JSON over HTTP).
"""

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.rest import start_server
from h2o3_tpu.runtime.dkv import DKV


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    srv = start_server(port=0)
    # a small CSV on disk for import
    d = tmp_path_factory.mktemp("rest")
    csv = d / "t.csv"
    rng = np.random.default_rng(0)
    n = 500
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    with open(csv, "w") as f:
        f.write("a,b,c,y\n")
        for i in range(n):
            f.write(",".join(f"{v:.4f}" for v in X[i]) + f",{y[i]}\n")
    yield srv, str(csv)
    srv.stop()
    DKV.clear()


def _get(srv, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}") as r:
        return json.loads(r.read())


def _post(srv, apipath, **params):
    data = urllib.parse.urlencode(params).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{srv.port}{apipath}", data=data)
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_cloud_and_about(server):
    srv, _ = server
    c = _get(srv, "/3/Cloud")
    assert c["cloud_name"] == "h2o3_tpu"
    assert "version" in c
    a = _get(srv, "/3/About")
    assert a["entries"]


def test_import_parse_frames(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    fl = _get(srv, "/3/Frames")
    assert any(f["frame_id"]["name"] == key for f in fl["frames"])
    s = _get(srv, f"/3/Frames/{key}/summary")
    col = s["frames"][0]
    assert col["rows"] == 500 and col["num_columns"] == 4
    setup = _post(srv, "/3/ParseSetup", path=csv)
    assert setup["column_names"] == ["a", "b", "c", "y"]


def test_train_poll_predict_delete(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    # categorical response via Rapids (the h2o-py client flow: asfactor →
    # Rapids string → train), then train gbm via REST (async job)
    _post(srv, "/99/Rapids",
          ast=f"(assign train2 (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    r = _post(srv, "/3/ModelBuilders/gbm", training_frame="train2",
              response_column="y", ntrees="10", max_depth="3",
              distribution="bernoulli")
    job_key = r["job"]["key"]["name"]
    for _ in range(600):
        j = _get(srv, f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.25)
    assert j["status"] == "DONE", j
    model_key = j["dest"]["name"]
    m = _get(srv, f"/3/Models/{model_key}")["models"][0]
    assert m["algo"] == "gbm"
    assert m["output"]["training_metrics"]["rmse"] < 0.5
    # predictions
    p = _post(srv, f"/3/Predictions/models/{model_key}/frames/{key}")
    pf = p["predictions_frame"]["name"]
    s = _get(srv, f"/3/Frames/{pf}/summary")["frames"][0]
    assert s["rows"] == 500
    # schemas endpoint lists gbm params
    sch = _get(srv, "/3/ModelBuilders/gbm")
    names = [f["name"] for f in sch["parameters"]]
    assert "ntrees" in names and "learn_rate" in names
    # delete
    _del(srv, f"/3/Models/{model_key}")
    with pytest.raises(urllib.error.HTTPError):
        _get(srv, f"/3/Models/{model_key}")


def _del(srv, path):
    req = urllib.request.Request(f"http://127.0.0.1:{srv.port}{path}",
                                 method="DELETE")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_rapids_endpoint(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    # scalar reducer
    out = _post(srv, "/99/Rapids", ast=f"(mean (cols {key} [0]))")
    assert abs(out["scalar"]) < 0.2
    # arithmetic + assign
    out = _post(srv, "/99/Rapids", ast=f"(assign tmp1 (* (cols {key} [0]) 2))")
    assert out["key"]["name"] == "tmp1"
    m1 = _post(srv, "/99/Rapids", ast="(mean tmp1)")
    m0 = _post(srv, "/99/Rapids", ast=f"(mean (cols {key} [0]))")
    assert m1["scalar"] == pytest.approx(2 * m0["scalar"], abs=1e-6)
    # nrow / quantile
    out = _post(srv, "/99/Rapids", ast=f"(nrow {key})")
    assert out["scalar"] == 500
    q = _post(srv, "/99/Rapids", ast=f"(quantile (cols {key} [0]) [0.5])")
    assert "key" in q or "columns" in q


def test_logs_timeline_profiler_metadata(server):
    srv, _ = server
    logs = _get(srv, "/3/Logs")
    assert isinstance(logs["logs"], list)
    tl = _get(srv, "/3/Timeline")
    assert any(e["kind"] == "rest" for e in tl["events"])
    prof = _get(srv, "/3/Profiler")
    assert prof["nodes"][0]["entries"]
    meta = _get(srv, "/3/Metadata/schemas")
    # algo builder schemas plus non-algo ones (ObservabilityV3)
    algos = [s["algo"] for s in meta["schemas"] if "algo" in s]
    assert {"gbm", "glm", "deeplearning", "kmeans"} <= set(algos)


def test_error_handling(server):
    srv, _ = server
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv, "/3/Models/nonexistent")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(srv, "/3/ModelBuilders/nosuchalgo", training_frame="x")
    assert e.value.code == 404


def test_rapids_extended_prims(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    # sort by column 0 ascending → first value is the min
    out = _post(srv, "/99/Rapids", ast=f"(assign srt (sort {key} [0]))")
    mn = _post(srv, "/99/Rapids", ast=f"(min (cols {key} [0]))")["scalar"]
    first = out["columns"][0]["data"][0]
    assert abs(first - mn) < 1e-6
    # scale → mean 0
    _post(srv, "/99/Rapids", ast=f"(assign sc (scale (cols {key} [0]) 1 1))")
    m = _post(srv, "/99/Rapids", ast="(mean sc)")["scalar"]
    assert abs(m) < 1e-6
    # hist returns a table frame
    h = _post(srv, "/99/Rapids", ast=f"(hist (cols {key} [0]) 5)")
    names = [c["label"] for c in h["columns"]]
    assert set(names) == {"breaks", "counts", "mids"}
    # is.na
    na = _post(srv, "/99/Rapids", ast=f"(sum (is.na (cols {key} [0])))")
    assert na["scalar"] == 0.0


def test_model_metrics_endpoint(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign mmtrain (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    r = _post(srv, "/3/ModelBuilders/gbm", training_frame="mmtrain",
              response_column="y", ntrees="5", max_depth="3")
    jk = r["job"]["key"]["name"]
    for _ in range(400):
        j = _get(srv, f"/3/Jobs/{jk}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.25)
    assert j["status"] == "DONE", j
    mk = j["dest"]["name"]
    mm = _post(srv, f"/3/ModelMetrics/models/{mk}/frames/mmtrain")
    row = mm["model_metrics"][0]
    assert row["model"]["name"] == mk
    assert 0.5 <= row["auc"] <= 1.0


def test_model_save_load_and_frame_export(server, tmp_path):
    srv, csv = server
    imp = _post(srv, "/3/ImportFiles", path=csv)
    key = imp["destination_frames"][0]
    _post(srv, "/99/Rapids", ast=f"(tmp= expfr (cbind (cols {key} [0 1 2]) (as.factor (cols {key} [3]))))")
    out = _post(srv, "/3/ModelBuilders/gbm",
                training_frame="expfr", response_column="y",
                ntrees=3, max_depth=3)
    import time as _t
    for _ in range(200):
        jobs = _get(srv, "/3/Jobs")["jobs"]
        if all(j["status"] in ("DONE", "FAILED") for j in jobs):
            break
        _t.sleep(0.25)
    models = _get(srv, "/3/Models")["models"]
    assert models, "no model trained via REST"
    mid = models[-1]["model_id"]["name"]
    saved = _post(srv, f"/99/Models.bin/{mid}", dir=str(tmp_path))
    assert saved["path"].endswith(".h2o3")
    loaded = _post(srv, "/99/Models.bin", path=saved["path"])
    assert loaded["models"][0]["model_id"]["name"]
    exp = _post(srv, f"/3/Frames/{key}/export",
                path=str(tmp_path / "out.csv"), force=True)
    assert exp["job"]["status"] == "DONE"
    import os
    assert os.path.exists(tmp_path / "out.csv")


def test_post_file_upload_parse(server):
    srv, csv = server
    body = open(csv, "rb").read()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/3/PostFile?destination_frame=up.csv",
        data=body, headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req) as r:
        out = json.loads(r.read())
    assert out["total_bytes"] == len(body)
    dest = out["destination_frame"]
    # uploaded key works as a Parse source
    p = _post(srv, "/3/Parse", source_frames=dest,
              destination_frame="uploaded")
    assert p["destination_frame"]["name"] == "uploaded"
    s = _get(srv, "/3/Frames/uploaded/summary")["frames"][0]
    assert s["rows"] == 500 and s["num_columns"] == 4


def test_grid_endpoints(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign gtrain (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    r = _post(srv, "/99/Grid/gbm", training_frame="gtrain",
              response_column="y", grid_id="g1", ntrees="5",
              hyper_parameters=json.dumps({"max_depth": [2, 3]}))
    assert r["grid_id"] == "g1"
    job_key = r["job"]["key"]["name"]
    for _ in range(600):
        j = _get(srv, f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.25)
    assert j["status"] == "DONE", j
    g = _get(srv, "/99/Grids/g1")
    assert len(g["model_ids"]) == 2
    assert g["hyper_names"] == ["max_depth"]
    lst = _get(srv, "/99/Grids")
    assert any(x["grid_id"]["name"] == "g1" for x in lst["grids"])
    # 4xx for bad request, 404 for missing grid
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(srv, "/99/Grid/gbm", training_frame="gtrain",
              response_column="y")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv, "/99/Grids/nope")
    assert e.value.code == 404


def test_automl_endpoints(server):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign atrain (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    r = _post(srv, "/99/AutoMLBuilder", training_frame="atrain",
              response_column="y", max_models="2", nfolds="2",
              seed="1", project_name="aml_rest")
    job_key = r["job"]["key"]["name"]
    for _ in range(1200):
        j = _get(srv, f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.5)
    assert j["status"] == "DONE", j
    lb = _get(srv, "/99/Leaderboards/aml_rest")["leaderboard"]["rows"]
    assert len(lb) >= 2
    a = _get(srv, "/99/AutoML/aml_rest")
    assert a["leader"]["name"] == lb[0]["model_id"]


def test_recovery_endpoint(server, tmp_path):
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign rtrain (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    import h2o3_tpu as h2o_mod
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.grid import H2OGridSearch

    gs = H2OGridSearch(H2OGradientBoostingEstimator(ntrees=3),
                       {"max_depth": [2]}, grid_id="grec",
                       recovery_dir=str(tmp_path))
    gs.train(x=["a", "b", "c"], y="y", training_frame=DKV.get("rtrain"))
    out = _post(srv, "/3/Recovery", recovery_dir=str(tmp_path))
    assert out["grid_id"]["name"] == "grec"
    assert len(out["model_ids"]) == 1


def test_automl_poll_while_running(server):
    """Polling /99/AutoML and /99/Leaderboards mid-build must return the
    (possibly empty) board, not 500 (review r02)."""
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign ptrain (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    r = _post(srv, "/99/AutoMLBuilder", training_frame="ptrain",
              response_column="y", max_models="1", nfolds="2",
              seed="2", project_name="aml_poll")
    # immediately poll — build has barely started
    a = _get(srv, "/99/AutoML/aml_poll")
    assert "leaderboard" in a       # empty board, never a 500
    lb = _get(srv, "/99/Leaderboards/aml_poll")
    assert "leaderboard" in lb
    job_key = r["job"]["key"]["name"]
    for _ in range(1200):
        j = _get(srv, f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.5)
    assert j["status"] == "DONE", j


def test_flow_ui_served(server):
    srv, _ = server
    for path in ("/flow/", "/flow/index.html", "/"):
        req = urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}")
        body = req.read().decode()
        assert req.headers["Content-Type"].startswith("text/html")
        assert "H2O Flow" in body and "/99/Rapids" in body


def test_tree_endpoint(server):
    """`GET /3/Tree` (hex/tree/TreeHandler analog) over a freshly trained
    GBM."""
    srv, csv = server
    _post(srv, "/3/ImportFiles", path=csv)
    _post(srv, "/3/Parse", source_frames=csv, destination_frame="treefr",
          asfactor="y")
    _post(srv, "/3/ModelBuilders/gbm", training_frame="treefr",
          response_column="y", ntrees="3", max_depth="3",
          model_id="treegbm")
    for _ in range(200):
        jobs = _get(srv, "/3/Jobs")["jobs"]
        if all(j["status"] != "RUNNING" for j in jobs):
            break
        time.sleep(0.1)
    models = [m["model_id"]["name"] for m in _get(srv, "/3/Models")["models"]]
    mid = [m for m in models if "gbm" in m][0]
    t = _get(srv, f"/3/Tree?model={mid}&tree_number=1")
    assert t["model"]["name"] == mid
    assert len(t["left_children"]) == len(t["features"])
    assert t["root_node_id"] == 0
    assert any(c >= 0 for c in t["left_children"])  # actually split
    # out-of-range tree -> 4xx
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv, f"/3/Tree?model={mid}&tree_number=99")
    assert e.value.code == 400


def test_model_metrics_list_endpoint(server):
    srv, _ = server
    out = _get(srv, "/3/ModelMetrics")
    assert isinstance(out["model_metrics"], list)
    if out["model_metrics"]:
        row = out["model_metrics"][0]
        assert "model" in row and "kind" in row


def test_typeahead_endpoint(server, tmp_path):
    srv, _ = server
    (tmp_path / "data_a.csv").write_text("x\n1\n")
    (tmp_path / "data_b.csv").write_text("x\n2\n")
    (tmp_path / "other.txt").write_text("")
    q = urllib.parse.quote(str(tmp_path / "data"))
    out = _get(srv, f"/99/Typeahead/files?src={q}&limit=10")
    names = [p.rsplit("/", 1)[-1] for p in out["matches"]]
    assert names == ["data_a.csv", "data_b.csv"]


def test_water_meter_endpoint(server):
    srv, _ = server
    out = _get(srv, "/3/WaterMeterCpuTicks/0")
    assert isinstance(out["cpu_ticks"], list)
    if out["cpu_ticks"]:
        assert len(out["cpu_ticks"][0]) == 4


def test_auth_token():
    """Opt-in bearer auth: 401 without the token, 200 with it; /3/Cloud
    stays open for discovery."""
    import urllib.error

    from h2o3_tpu.rest import start_server as _start

    srv = _start(port=0, auth_token="sekrit")
    try:
        base = f"http://127.0.0.1:{srv.port}"
        # open cloud endpoint
        with urllib.request.urlopen(f"{base}/3/Cloud") as r:
            assert json.loads(r.read())["cloud_name"] == "h2o3_tpu"
        # protected endpoint without token
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/3/Models")
        assert e.value.code == 401
        # with token
        req = urllib.request.Request(
            f"{base}/3/Models",
            headers={"Authorization": "Bearer sekrit"})
        with urllib.request.urlopen(req) as r:
            assert "models" in json.loads(r.read())
    finally:
        srv.stop()


def test_flows_save_load_roundtrip(server, tmp_path, monkeypatch):
    """`/99/Flows` — the notebook save/load surface (h2o-web .flow docs)."""
    monkeypatch.setenv("H2O3_FLOWS_DIR", str(tmp_path / "flows"))
    srv, _ = server
    cells = [{"type": "rapids", "src": "(nrow x)"},
             {"type": "plot", "src": "fr 0"}]
    out = _post_json(srv, "/99/Flows", {"name": "myflow", "cells": cells})
    assert out["saved"] and out["cells"] == 2
    lst = _get(srv, "/99/Flows")["flows"]
    assert any(f["name"] == "myflow" for f in lst)
    got = _get(srv, "/99/Flows/myflow")
    assert got["cells"] == cells
    # delete
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/99/Flows/myflow", method="DELETE")
    with urllib.request.urlopen(req) as r:
        assert json.loads(r.read())["deleted"]
    with pytest.raises(urllib.error.HTTPError):
        _get(srv, "/99/Flows/myflow")


def _post_json(srv, path, obj):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_flow_ui_has_notebook(server):
    srv, _ = server
    with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/flow/") as r:
        html = r.read().decode()
    assert "Notebook" in html and "saveFlow" in html and "svgHist" in html


def test_frames_pagination(server):
    srv, _ = server
    all_f = _get(srv, "/3/Frames")
    assert "total_frames" in all_f
    if all_f["total_frames"] >= 2:
        page = _get(srv, "/3/Frames?offset=1&limit=1")
        assert len(page["frames"]) == 1
        assert page["offset"] == 1


def test_network_test_and_gc(server):
    srv, _ = server
    nt = _get(srv, "/3/NetworkTest")
    assert nt["results"] and all(r["mbytes_per_sec"] > 0 for r in nt["results"])
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/3/GarbageCollect", data=b"")
    with urllib.request.urlopen(req) as r:
        out = json.loads(r.read())
    assert "collected" in out and "dkv" in out


def test_frames_pagination_negative_clamped(server):
    """Negative offset/limit must not tail-slice (ADVICE r03)."""
    srv, _ = server
    all_f = _get(srv, "/3/Frames")
    page = _get(srv, "/3/Frames?offset=-1&limit=-5")
    assert page["offset"] == 0
    assert len(page["frames"]) == len(all_f["frames"])


def test_flow_name_with_disallowed_chars_rejected(server, tmp_path,
                                                  monkeypatch):
    """'my flow' and 'my_flow' must not collide on one file (ADVICE r03)."""
    monkeypatch.setenv("H2O3_FLOWS_DIR", str(tmp_path / "flows"))
    srv, _ = server
    with pytest.raises(urllib.error.HTTPError) as e:
        _post_json(srv, "/99/Flows",
                   {"name": "my flow", "cells": []})
    assert e.value.code == 400


def test_rapids_rows_param_returns_all_hist_bins(server):
    """Flow plot cells read every hist bin via rows= (ADVICE r03 medium)."""
    srv, csv = server
    imp = _post(srv, "/3/ImportFiles", path=csv)
    key = imp["destination_frames"][0]
    out = _post_json(srv, "/99/Rapids",
                     {"ast": f"(hist (cols {key} [0]) 20)", "rows": 64})
    counts = next(c for c in out["columns"] if "count" in c["label"].lower())
    assert len(counts["data"]) == 20  # all 20 bins, not the 10-row preview


def test_round5_functional_routes(server):
    """builders list, frame paging, column
    routes, Tabulate, JStack, PartialDependence, Metadata/endpoints,
    UnlockKeys."""
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]

    bl = _get(srv, "/3/ModelBuilders")
    assert "gbm" in bl["model_builders"] and "glm" in bl["model_builders"]

    page = _get(srv, f"/3/Frames/{key}?row_offset=10&row_count=5")
    fr0 = page["frames"][0]
    assert fr0["row_count"] == 5
    assert len(fr0["columns"][0]["data"]) == 5

    cols = _get(srv, f"/3/Frames/{key}/columns")
    assert [c["label"] for c in cols["columns"]] == ["a", "b", "c", "y"]

    tab = _post(srv, "/3/Tabulate", dataset=key, predictor="a",
                response="y", nbins_predictor=5)
    assert len(tab["count_table"]) == 5
    total = sum(sum(row) for row in tab["count_table"])
    assert total == 500
    # response means within [0,1] for the 0/1 response
    assert all(m is None or 0 <= m <= 1 for m in tab["response_table"])

    js = _get(srv, "/3/JStack")
    assert js["traces"] and "stack" in js["traces"][0]

    ep = _get(srv, "/3/Metadata/endpoints")
    assert any(rt["url_pattern"].startswith("^/3/Tabulate")
               for rt in ep["routes"])

    ul = _post(srv, "/3/UnlockKeys")
    assert ul["unlocked"] == 0

    # train a model, then PDP over the wire
    tr = _post(srv, "/3/ModelBuilders/gbm", training_frame=key,
               response_column="y", ntrees="5", max_depth="3")
    jid = tr["job"]["key"]["name"]
    for _ in range(120):
        j = _get(srv, f"/3/Jobs/{urllib.parse.quote(jid)}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.5)
    assert j["status"] == "DONE", j
    mid = j["dest"]["name"]
    # the response must be an enum for PDP mean_response to be a prob —
    # numeric y trains regression here, fine for the route contract
    pdp = _post(srv, "/3/PartialDependence", model_id=mid, frame_id=key,
                cols=json.dumps(["a"]), nbins=8)
    data = pdp["partial_dependence_data"][0]
    assert "mean_response" in data and len(data["mean_response"]) >= 8
    again = _get(srv, f"/3/PartialDependence/"
                      f"{pdp['destination_key']['name']}")
    assert again["partial_dependence_data"] == pdp[
        "partial_dependence_data"]

    dom = _get(srv, f"/3/Frames/{key}/columns/y/domain")
    assert dom["domain"] == [[]]            # numeric column: no levels


def test_job_cancel_route(server):
    """POST /3/Jobs/{id}/cancel stops a long training run at its next
    scoring boundary; the job ends CANCELLED and no model lands in DKV."""
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    tr = _post(srv, "/3/ModelBuilders/deeplearning", training_frame=key,
               response_column="y", hidden="[64,64]", epochs="500",
               mini_batch_size="8", score_interval="0")
    jid = tr["job"]["key"]["name"]
    time.sleep(1.0)
    c = _post(srv, f"/3/Jobs/{urllib.parse.quote(jid)}/cancel")
    assert c["job"]["cancel_requested"] or c["job"]["status"] == "CANCELLED"
    for _ in range(120):
        j = _get(srv, f"/3/Jobs/{urllib.parse.quote(jid)}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            break
        time.sleep(0.5)
    assert j["status"] == "CANCELLED", j
    assert j["dest"]["name"] == jid       # no model key: result never set


def test_prediction_frames_overwrite_not_accumulate(server, cloud1):
    """Repeat scoring of the same (model, frame) pair must OVERWRITE the
    deterministic prediction key, never accumulate one leaked frame per
    call — DKV.keys()-based leak assertion (serving-subsystem satellite).

    The model is trained in-process (cloud1) so the assertion isolates the
    predict route's DKV behavior from the training path."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    srv, csv = server
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 3))
    yb = (X[:, 0] + X[:, 1] > 0).astype(int)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "y": np.asarray(["no", "yes"], dtype=object)[yb]},
        column_types={"y": "enum"})
    fr.key = "leaktr"
    DKV.put(fr.key, fr)
    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1,
                                       model_id="leak_gbm")
    est.train(x=["a", "b", "c"], y="y", training_frame=fr)
    DKV.put("leak_gbm", est.model)
    p1 = _post(srv, "/3/Predictions/models/leak_gbm/frames/leaktr")
    pkey = p1["predictions_frame"]["name"]
    assert pkey == "prediction_leak_gbm_leaktr"   # deterministic key
    keys_after_first = set(DKV.keys())
    for _ in range(5):
        pn = _post(srv, "/3/Predictions/models/leak_gbm/frames/leaktr")
        assert pn["predictions_frame"]["name"] == pkey
    assert set(DKV.keys()) == keys_after_first, (
        "repeat /3/Predictions calls leaked DKV keys: "
        f"{sorted(set(DKV.keys()) - keys_after_first)}")


def test_predictions_route_options(server):
    """POST /3/Predictions with predict_contributions / leaf_node_assignment
    flags (ModelMetricsHandler.predict options)."""
    srv, csv = server
    r = _post(srv, "/3/ImportFiles", path=csv)
    key = r["destination_frames"][0]
    _post(srv, "/99/Rapids",
          ast=f"(assign ptr (cbind (cols {key} [0 1 2])"
              f" (as.factor (cols {key} [3]))))")
    tr = _post(srv, "/3/ModelBuilders/gbm", training_frame="ptr",
               response_column="y", ntrees="4", max_depth="3")
    jid = tr["job"]["key"]["name"]
    for _ in range(200):
        j = _get(srv, f"/3/Jobs/{jid}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED"):
            break
        time.sleep(0.25)
    assert j["status"] == "DONE", j
    mid = j["dest"]["name"]
    c = _post(srv, f"/3/Predictions/models/{mid}/frames/ptr",
              predict_contributions="true")
    cf = _get(srv, f"/3/Frames/{c['predictions_frame']['name']}/summary")
    labels = [col["label"] for col in cf["frames"][0]["columns"]]
    assert "BiasTerm" in labels
    l = _post(srv, f"/3/Predictions/models/{mid}/frames/ptr",
              leaf_node_assignment="true")
    lf = _get(srv, f"/3/Frames/{l['predictions_frame']['name']}/summary")
    assert lf["frames"][0]["rows"] == 500
