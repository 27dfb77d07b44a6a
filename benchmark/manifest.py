"""BENCHMARK.json and the data files it names: loading and the manifest's
character rules. Everything that belongs to one configuration, one traffic
mix, one cell or one per-layer metric is a file of its own, found by name:

    configs/<config>.json     sizes, source, estimator settings, `algo`
    traffic/<traffic>.json    parameters of a traffic mix (traffic_gen.py reads it)
    workloads/<cell>.json     why the cell exists, chips, config, traffic
    algos/<algo>.py           adapter from a config to the program's estimator
    counts/<algo>.py          required operations and bytes from shapes
    references/<algo>_reference.py   the plain reference and the comparison
    metrics/<metric>.py       one reader per per-layer metric

A later PR adds files and entries to BENCHMARK.json; it edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}


class ManifestError(ValueError):
    pass


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def check_name(s, what: str) -> str:
    _need(isinstance(s, str) and NAME_RE.match(s) is not None,
          f"{what}: {s!r} is not a name (letters, digits, _ . -, at most 64)")
    return s


def check_line(s, what: str) -> str:
    _need(isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
          and "\t" not in s and "\r" not in s,
          f"{what}: must be 1 to 200 characters on one line")
    return s


def check_metric(m: dict, end_to_end: bool) -> None:
    allowed = E2E_KEYS if end_to_end else LAYER_KEYS
    _need(set(m) <= allowed, f"metric {m.get('name')!r}: keys outside "
          f"{sorted(allowed)}: {sorted(set(m) - allowed)}")
    check_name(m.get("name"), "metric name")
    _need(isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"]),
          f"metric {m['name']}: unit {m.get('unit')!r} outside the allowed "
          "characters")
    _need(m.get("better") in ("lower", "higher"),
          f"metric {m['name']}: better must be lower or higher")
    _need(m.get("source") in SOURCES, f"metric {m['name']}: source")
    if end_to_end:
        _need(m["source"] in ("host_clock", "device_trace"),
              f"end-to-end metric {m['name']}: source")
        b = m.get("bound")
        _need(isinstance(b, (int, float)) and 0.01 <= b <= 0.1,
              f"end-to-end metric {m['name']}: bound {b!r}")
    else:
        check_line(m.get("layer"), f"metric {m['name']}: layer")
        check_name(m.get("moves"), f"metric {m['name']}: moves")
    for w in m.get("workloads", []):
        check_name(w, f"metric {m['name']}: workloads")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    """BENCHMARK.json, held to the rules a reader of it relies on."""
    path = os.path.join(root, "BENCHMARK.json")
    _need(os.path.getsize(path) <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    man = load_json(path)
    _need(set(man) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"},
          f"BENCHMARK.json keys: {sorted(man)}")
    for p in man["paths"]:
        _need(PATH_RE.match(p) and not p.startswith("/") and ".." not in p,
              f"path {p!r}")
    _need(isinstance(man["run_seconds"], int)
          and 1 <= man["run_seconds"] <= 51, "run_seconds")
    names = set()
    for c in man["configs"]:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config keys {sorted(c)}")
        check_name(c["name"], "config name")
        check_line(c["source"], "config source")
        check_line(c["why"], "config why")
        _need(PATH_RE.match(c["file"]) and any(
            c["file"].startswith(p.rstrip("/") + "/") for p in man["paths"]),
            f"config file {c['file']!r} not under paths")
        _need(len(c["reduced"]) <= 16, "reduced: at most 16 keys")
        for k in c["reduced"]:
            check_name(k, "reduced key")
        _need(c["name"] not in names, f"config {c['name']} twice")
        names.add(c["name"])
    cells, pairs = set(), set()
    for w in man["workloads"]:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload keys {sorted(w)}")
        check_name(w["name"], "workload name")
        check_name(w["traffic"], "traffic")
        check_line(w["why"], "workload why")
        _need(w["config"] in names, f"workload {w['name']}: config")
        _need(w["chips"] in (1, 4), f"workload {w['name']}: chips")
        _need(w["name"] not in cells, f"workload {w['name']} twice")
        _need((w["config"], w["traffic"]) not in pairs,
              f"workload {w['name']}: pair appears twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    seen = set()
    for m in man["end_to_end"]:
        check_metric(m, end_to_end=True)
    for m in man["per_layer"]:
        check_metric(m, end_to_end=False)
    e2e = {m["name"] for m in man["end_to_end"]}
    _need("setup_s" in e2e, "end_to_end lacks setup_s")
    for m in man["end_to_end"] + man["per_layer"]:
        _need(m["name"] not in seen, f"metric {m['name']} twice")
        seen.add(m["name"])
        for w in m.get("workloads", []):
            _need(w in cells, f"metric {m['name']}: unknown cell {w}")
        if "moves" in m:
            _need(m["moves"] in e2e, f"metric {m['name']}: moves")
    return man


def cell(man: dict, name: str) -> dict:
    """One cell by name: its manifest entry merged over its own file."""
    for w in man["workloads"]:
        if w["name"] == name:
            out = dict(load_json(os.path.join(HERE, "workloads",
                                              name + ".json")))
            for k, v in w.items():
                _need(out.get(k, v) == v, f"workloads/{name}.json disagrees "
                      f"with BENCHMARK.json on {k}")
                out[k] = v
            return out
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, name: str, root: str = ROOT) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            cfg = load_json(os.path.join(root, c["file"]))
            check_name(cfg.get("algo"), f"config {name}: algo")
            check_line(cfg.get("source"), f"config {name}: source")
            _need(cfg.get("source") == c["source"]
                  and list(cfg.get("reduced", [])) == list(c["reduced"]),
                  f"config file of {name} disagrees with BENCHMARK.json")
            _need("deployment" in cfg and "assumed" in cfg,
                  f"config {name}: states no deployment or assumed sizes")
            return cfg
    raise ManifestError(f"no config {name!r}")


def traffic(name: str) -> dict:
    check_name(name, "traffic")
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module, found by name."""
    check_name(name, kind)
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise ManifestError(f"no {kind}/{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(man: dict, cell_name: str, group: str) -> list:
    """The metrics of `group` that this cell reports."""
    return [m for m in man[group]
            if "workloads" not in m or cell_name in m["workloads"]]
