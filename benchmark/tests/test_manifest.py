"""The manifest and every data file it names load, and the character rules
of names, units and keys are enforced."""

import copy
import glob
import os

import pytest

import manifest
import traffic_gen


def test_every_named_file_loads():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        cell = manifest.cell(man, w["name"])
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        traffic_gen.check(manifest.traffic(cell["traffic"]))
    for c in man["configs"]:
        cfg = manifest.config(man, c["name"])
        assert {"source", "reduced", "assumed", "deployment", "limits"} <= set(cfg)
        algo = manifest.load_module("algos", cfg["algo"])
        manifest.load_module("counts", cfg["algo"])
        manifest.load_module("references", algo.REFERENCE)
    for m in man["per_layer"]:
        assert callable(manifest.load_module("metrics", m["name"]).read)


def test_every_file_under_the_data_directories_is_named_by_the_rules():
    for kind in ("configs", "workloads", "traffic", "metrics", "algos", "counts",
                 "references"):
        for path in glob.glob(os.path.join(manifest.HERE, kind, "*")):
            stem = os.path.splitext(os.path.basename(path))[0]
            if stem != "__pycache__":
                manifest.check_name(stem, kind)


def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_for(man, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_for(man, w["name"], "per_layer")


def test_the_same_seed_gives_the_same_requests_and_every_seed_the_same_set():
    tr = {"frame": "shared", "draw_estimator_seed": True, "trace_seconds": 1,
          "candidates": [{"learn_rate": r, "min_rows": m}
                         for r in (0.05, 0.1, 0.2) for m in (5, 10)]}
    n = len(tr["candidates"])

    def first(seed):
        g = traffic_gen.fits(tr, seed)
        return [next(g)["overrides"] for _ in range(n)]

    strip = lambda rs: sorted(sorted((k, v) for k, v in r.items() if k != "seed")
                              for r in rs)
    assert first(2 ** 31 + 3) == first(2 ** 31 + 3)
    assert first(5) != first(6)
    assert strip(first(5)) == strip(first(6))


BAD_METRICS = [
    {"name": "has space", "unit": "s"},
    {"name": "tokens,per", "unit": "s"},
    {"name": "a/b", "unit": "s"},
    {"name": "x" * 65, "unit": "s"},
    {"name": "ok", "unit": "tokens per second"},
    {"name": "ok", "unit": "µs"},
    {"name": "ok", "unit": "s", "better": "faster"},
    {"name": "ok", "unit": "s", "why": "a key the manifest does not allow"},
    {"name": "ok", "unit": "s", "source": "guess"},
]


@pytest.mark.parametrize("bad", BAD_METRICS)
def test_a_metric_outside_the_rules_is_refused(bad):
    m = {"name": "ok", "unit": "s", "better": "lower", "source": "host_clock",
         "bound": 0.03}
    m.update(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.check_metric(m, end_to_end=True)


def test_a_manifest_outside_the_rules_is_refused(tmp_path):
    import json

    good = manifest.load_manifest()
    for spoil in (
        lambda m: m["workloads"][0].__setitem__("name", "bad name"),
        lambda m: m["workloads"][0].__setitem__("chips", 2),
        lambda m: m["workloads"][0].__setitem__("why", "x" * 201),
        lambda m: m["workloads"].append(dict(m["workloads"][0], name="twice")),
        lambda m: m["configs"][0].__setitem__("file", "elsewhere/x.json"),
        lambda m: m["end_to_end"][1].__setitem__("bound", 0.2),
        lambda m: m["per_layer"][0].__setitem__("moves", "no_such_metric"),
        lambda m: m.__setitem__("run_seconds", 52),
        lambda m: m.__setitem__("extra", 1),
    ):
        man = copy.deepcopy(good)
        spoil(man)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
        with pytest.raises(manifest.ManifestError):
            manifest.load_manifest(str(tmp_path))
