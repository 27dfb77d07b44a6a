"""run.py end to end on the CPU at a tiny size: the look for a chip is
patched out HERE (run.py has no option for it), the sizes come from the
test-only configs beside this file."""

import json
import os
import subprocess
import sys

import pytest

import manifest
import run

HERE = os.path.dirname(os.path.abspath(__file__))
_load_manifest = manifest.load_manifest
CELLS = [w["name"] for w in _load_manifest()["workloads"]]


def tiny_manifest():
    man = _load_manifest()
    for c in man["configs"]:
        c["file"] = f"benchmark/tests/configs/{c['name']}.json"
        tiny = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        c["source"], c["reduced"] = tiny["source"], tiny["reduced"]
    return man


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    import work_counts

    # a CPU has no row in peaks.json, and must not get one: the test lends it
    # the v5e's so that the readers run; no number read here is a device's
    monkeypatch.setitem(work_counts._PEAKS, jax.devices()[0].device_kind,
                        work_counts._PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(manifest, "load_manifest", lambda root=None: tiny_manifest())
    # a tiny fit takes milliseconds: trace a few of them, not ten seconds' worth
    real_traffic = manifest.traffic
    monkeypatch.setattr(manifest, "traffic",
                        lambda name: dict(real_traffic(name), trace_seconds=0.2))


def last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(on_cpu, capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                   "--seconds", "2", "--trace", str(trace)])
    res, err = last_line(capsys)
    assert rc == 0
    assert list(res)[-1] == "compared" and res["correct"] is True, res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    man = tiny_manifest()
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        allowed = {m["name"] for m in manifest.metrics_for(man, cell, "per_layer")}
        assert set(res["metrics"]) <= allowed
        assert "compiles_in_window" in res["metrics"]
    else:
        want = {m["name"] for m in manifest.metrics_for(man, cell, "end_to_end")}
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())
    for name, (value, limit) in res["compared"].items():
        assert f"compared {name} = " in err


def test_no_chip_means_no_result():
    """JAX held to the CPU: nonzero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
