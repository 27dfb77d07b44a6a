"""Remote-attach client e2e: the server runs in a
SEPARATE process; the client connects by URL only and round-trips
upload → munge → train → predict → metrics without touching any
in-process state. Reference: `h2o-py/h2o/backend/connection.py` —
upstream's client is fundamentally a REST client."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.client import (H2OConnectionError, H2OServerError,
                             RemoteFrame, RemoteModel)
from h2o3_tpu.runtime.dkv import DKV

_SERVER_SRC = """
import sys, time
from h2o3_tpu.rest.server import start_server
import h2o3_tpu as h2o
h2o.init()
srv = start_server(port=0, auth_token={token!r})
print(srv.port, flush=True)
time.sleep(600)
"""


@pytest.fixture(scope="module")
def remote_server():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SRC.format(token=None)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env, text=True)
    try:
        port = int(proc.stdout.readline())
        yield f"http://127.0.0.1:{port}"
    finally:
        proc.kill()
        proc.wait()
    h2o.shutdown()


@pytest.fixture()
def csvfile(tmp_path):
    rng = np.random.default_rng(0)
    n = 400
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    p = tmp_path / "remote.csv"
    with open(p, "w") as f:
        f.write("a,b,c,y\n")
        for i in range(n):
            f.write(",".join(f"{v:.4f}" for v in X[i]) + f",{y[i]}\n")
    return str(p)


def test_connect_unreachable_raises():
    with pytest.raises(H2OConnectionError):
        h2o.connect(url="http://127.0.0.1:9", verbose=False)
    assert h2o.connection() is None


def test_remote_roundtrip_train_predict_metrics(remote_server, csvfile):
    conn = h2o.connect(url=remote_server)
    try:
        assert h2o.connection() is conn
        local_keys_before = set(DKV.keys())

        # upload: client-side bytes travel over PostFile + Parse
        fr = h2o.upload_file(csvfile, destination_frame="remote_train")
        assert isinstance(fr, RemoteFrame)
        assert fr.shape == (400, 4)
        assert fr.names == ["a", "b", "c", "y"]

        # munge: asfactor through Rapids assigns
        fr["y"] = fr["y"].asfactor()
        assert fr.types["y"] == "enum"

        # train through /3/ModelBuilders + /3/Jobs polling — the NORMAL
        # estimator surface, no in-process code path
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator

        m = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
        m.train(x=["a", "b", "c"], y="y", training_frame=fr)
        assert isinstance(m._model, RemoteModel)
        assert m.auc() > 0.8
        assert m.model_id.startswith("gbm")

        # predict on the server; fetch the head through the client
        pred = m.predict(fr)
        assert isinstance(pred, RemoteFrame)
        assert pred.names[0] == "predict"
        assert pred.nrow == 400

        # fresh-frame metrics via /3/ModelMetrics
        perf = m.model_performance(fr)
        assert perf.auc() > 0.8

        # h2o.get_model round-trips by id
        again = h2o.get_model(m.model_id)
        assert isinstance(again, RemoteModel)
        assert again.algo == "gbm"

        # nothing leaked into THIS process's DKV
        assert set(DKV.keys()) == local_keys_before
    finally:
        h2o.shutdown()   # disconnect; later tests are in-process again
    assert h2o.connection() is None


def test_remote_import_server_side_path(remote_server, csvfile):
    h2o.init(url=remote_server)
    try:
        fr = h2o.import_file(csvfile)   # path resolved ON the server
        assert isinstance(fr, RemoteFrame)
        assert fr.nrow == 400
        cols = fr[["a", "b"]]
        assert cols.ncol == 2
        fr.delete()
        with pytest.raises(H2OServerError):
            h2o.get_frame(fr.key)
    finally:
        h2o.shutdown()


def test_remote_auth_token(csvfile):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SRC.format(token="sekrit")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env, text=True)
    try:
        url = f"http://127.0.0.1:{int(proc.stdout.readline())}"
        # /3/Cloud stays open for discovery, so connect() itself succeeds;
        # every OTHER route 401s without the bearer token
        conn = h2o.connect(url=url, verbose=False)
        with pytest.raises(H2OServerError) as e:
            conn.get("/3/Models")
        assert e.value.status == 401
        conn = h2o.connect(url=url, token="sekrit", verbose=False)
        assert "models" in conn.get("/3/Models")
    finally:
        proc.kill()
        proc.wait()
        h2o.shutdown()


def test_remote_train_validates_args_locally(remote_server, csvfile):
    """Bad train() calls raise client-side (ValueError), not as a FAILED
    server job surfacing RuntimeError."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator

        fr = h2o.upload_file(csvfile)
        with pytest.raises(ValueError, match="response column"):
            H2OGradientBoostingEstimator(ntrees=2).train(training_frame=fr)
    finally:
        h2o.shutdown()


def test_remote_frame_from_python_and_parse_options(remote_server, tmp_path):
    """H2OFrame_from_python uploads to the server when connected; parse
    options (sep/col_types) ride /3/Parse instead of being dropped."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        fr = h2o.H2OFrame_from_python(
            {"a": [1.0, 2.0, 3.0], "lab": ["x", "y", "x"]},
            column_types={"lab": "enum"})
        assert isinstance(fr, RemoteFrame)
        assert fr.nrow == 3 and fr.types["lab"] == "enum"

        ssv = tmp_path / "t.ssv"
        ssv.write_text("a;b\n1;2\n3;4\n")
        fr2 = h2o.import_file(str(ssv), sep=";")
        assert fr2.names == ["a", "b"] and fr2.ncol == 2

        # local validation_frame with remote training_frame raises loudly
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator
        from h2o3_tpu.frame.frame import Frame
        import numpy as np

        with pytest.raises(TypeError, match="RemoteFrame"):
            est = H2OGradientBoostingEstimator(ntrees=2)
            est.train(y="lab", training_frame=fr,
                      validation_frame=Frame.from_numpy(
                          np.zeros((3, 2)), names=["a", "b"]))
    finally:
        h2o.shutdown()


def test_remote_automl_leaderboard(remote_server, csvfile):
    """AutoML drives /99/AutoMLBuilder + Jobs + /99/AutoML over the wire —
    the 'leaderboard' leg of the client contract."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        from h2o3_tpu.automl.automl import H2OAutoML

        fr = h2o.upload_file(csvfile, destination_frame="aml_remote")
        fr["y"] = fr["y"].asfactor()
        aml = H2OAutoML(max_models=2, seed=1, nfolds=2,
                        project_name="aml_rc")
        aml.train(x=["a", "b", "c"], y="y", training_frame=fr)
        assert aml.leaderboard.rows, "empty remote leaderboard"
        assert aml.leaderboard.rows[0]["auc"] > 0.7
        assert aml.leaderboard.sort_metric == "auc"
        assert isinstance(aml.leader, RemoteModel)
        best = aml.get_best_model()
        assert isinstance(best, RemoteModel)
        pred = aml.predict(fr)
        assert pred.nrow == 400
    finally:
        h2o.shutdown()


def test_remote_grid_search(remote_server, csvfile):
    """Grid search over the wire: /99/Grid/{algo} + Jobs + /99/Grids —
    h2o-py's grid REST choreography."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator
        from h2o3_tpu.models.grid import H2OGridSearch

        fr = h2o.upload_file(csvfile, destination_frame="grid_remote")
        fr["y"] = fr["y"].asfactor()
        gs = H2OGridSearch(H2OGradientBoostingEstimator(ntrees=4, seed=1),
                           hyper_params={"max_depth": [2, 4]},
                           grid_id="rgrid")
        gs.train(x=["a", "b", "c"], y="y", training_frame=fr)
        assert len(gs.models) == 2
        assert all(isinstance(m, RemoteModel) for m in gs.models)
        gs.get_grid(sort_by="auc")
        assert gs.models[0].auc() >= gs.models[1].auc()
    finally:
        h2o.shutdown()


def test_remote_mojo_download_and_frame_pull(remote_server, csvfile,
                                             tmp_path):
    """h2o.save_model on a REST-backed model downloads the artifact;
    RemoteFrame.as_data_frame pulls full contents over DownloadDataset."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator

        fr = h2o.upload_file(csvfile, destination_frame="dl_remote")
        fr["y"] = fr["y"].asfactor()
        m = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1)
        m.train(x=["a", "b", "c"], y="y", training_frame=fr)
        path = h2o.save_model(m, str(tmp_path))
        data = fr.as_data_frame()
        assert len(data["a"]) == 400 and isinstance(data["a"][0], float)
    finally:
        h2o.shutdown()
    # artifact loads and scores OFFLINE (no connection)
    scorer = h2o.load_model(path)
    import numpy as np
    from h2o3_tpu.frame.frame import Frame

    Xl = Frame.from_dict({"a": np.asarray(data["a"]),
                          "b": np.asarray(data["b"]),
                          "c": np.asarray(data["c"])})
    p1 = scorer.predict(Xl).vec("1").numeric_np()
    assert np.isfinite(p1).all() and len(p1) == 400


def test_remote_create_frame_interaction_missing_inserter(remote_server):
    """the functional route tail — synthetic frame
    generation (/3/CreateFrame), factor interactions (/3/Interaction) and
    NA insertion (/3/MissingInserter) all run SERVER-side, driven through
    the same package surface that works in-process."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        fr = h2o.create_frame(rows=300, cols=6, categorical_fraction=0.5,
                              integer_fraction=0.25, real_fraction=0.25,
                              factors=4, seed=7, frame_id="synth_remote")
        assert isinstance(fr, RemoteFrame)
        assert fr.shape == (300, 6)
        cat_cols = [n for n in fr.names if fr.types.get(n) == "enum"]
        assert len(cat_cols) >= 2, fr.types

        inter = h2o.interaction(fr, factors=cat_cols[:2], pairwise=True,
                                max_factors=100, min_occurrence=1,
                                destination_frame="synth_inter")
        assert isinstance(inter, RemoteFrame)
        assert inter.shape[0] == 300 and inter.shape[1] == 1
        assert inter.types[inter.names[0]] == "enum"

        # MissingInserter mutates the server-side frame in place
        num_col = next(n for n in fr.names if fr.types.get(n) != "enum")
        before = fr.as_data_frame(use_pandas=False)[num_col]
        h2o.insert_missing_values(fr, fraction=0.5, seed=1)
        after = fr.as_data_frame(use_pandas=False)[num_col]
        import math

        n_na = sum(1 for v in after if isinstance(v, float) and math.isnan(v))
        assert n_na > sum(1 for v in before
                          if isinstance(v, float) and math.isnan(v))
        assert 0.3 < n_na / 300 < 0.7
    finally:
        h2o.shutdown()


def test_remote_remove_all_retained(remote_server):
    """`h2o.remove_all(retained=[...])` over a connection clears the
    server DKV except the listed keys (RemoveAllHandler retained_keys)."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        a = h2o.create_frame(rows=50, cols=2, seed=1, frame_id="keepme")
        h2o.create_frame(rows=50, cols=2, seed=2, frame_id="dropme")
        h2o.remove_all(retained=[a])
        keys = [f["frame_id"]["name"] if isinstance(f.get("frame_id"), dict)
                else f.get("frame_id")
                for f in h2o.connection().get("/3/Frames")["frames"]]
        assert "keepme" in keys and "dropme" not in keys
    finally:
        h2o.shutdown()


def test_remote_batch_munging_round_trips(remote_server, csvfile):
    """a chained 10-op munge inside `with h2o.batch():`
    reaches the server as ONE multi-statement Rapids POST (plus one read),
    instead of 10 eager round-trips."""
    conn = h2o.connect(url=remote_server, verbose=False)
    try:
        fr = h2o.upload_file(csvfile, destination_frame="batch_src")
        calls = []
        orig = type(conn).request

        def counting(self, method, path, *a, **kw):
            calls.append((method, path))
            return orig(self, method, path, *a, **kw)

        type(conn).request = counting
        try:
            with h2o.batch():
                g = fr["a"]                 # slice + 10 chained derivations
                for _ in range(5):
                    g = g.asfactor()
                    g = g.asnumeric()
                nrows = g.nrow              # first read flushes the chain
            during = list(calls)
        finally:
            type(conn).request = orig
        assert nrows == 400
        rapids_posts = [c for c in during if c[1] == "/99/Rapids"]
        assert len(rapids_posts) == 1, during
        # 1 source-metadata read (fr["a"] name lookup) + 1 flush + 1 final
        # read — the 11 chained derivations themselves cost zero trips
        assert len(during) <= 3, during
        # the chain's final key really exists server-side with full contents
        data = g.as_data_frame(use_pandas=False)
        assert list(data) == ["a"] and len(data["a"]) == 400
    finally:
        h2o.shutdown()


def test_remote_batch_flushes_on_exception(remote_server, csvfile):
    """An exception inside `with h2o.batch():` still lands the assigns
    already chained, so returned RemoteFrame handles stay valid."""
    h2o.connect(url=remote_server, verbose=False)
    try:
        fr = h2o.upload_file(csvfile, destination_frame="batch_exc")
        g = None
        with pytest.raises(RuntimeError, match="boom"):
            with h2o.batch():
                g = fr["a"].asfactor()
                raise RuntimeError("boom")
        assert g.nrow == 400          # the deferred assign reached the server
        assert g.types[g.names[0]] == "enum"
        # and value-returning rapids stayed EAGER inside batch
        with h2o.batch():
            out = h2o.rapids("(+ 1 2)")
        assert out.get("scalar") == 3.0
    finally:
        h2o.shutdown()
