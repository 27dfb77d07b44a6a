#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Set-up: the cell's data on the host from --seed, the frame, ONE
warm-up train() at the cell's full shape (every program of the window is
compiled or loaded from the persistent cache under the checkout), all of it
counted as `setup_s`, from the moment jax holds the chips. Then the window: whole train() calls back to back
through the public estimator API, a further one started only if by the mean
of the fits so far it would end inside --seconds; `fit_wall_s` is the whole
window over its whole fits. Then, outside the window and after the peak
memory has been read and the program's state freed, the comparison with the
plain reference that decides `correct`. The last line of standard output is
the result; everything else (fit plan, cache hits, AUC, the bound a roofline
used) goes on earlier lines or to standard error.

It needs the chips the cell asks for and fails without them: no fallback.
Everything particular to a configuration, a traffic mix, a cell or a
per-layer metric is a file found by name (manifest.py)."""

from __future__ import annotations

import time

_T0 = time.time()

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

import manifest
import traffic_gen

TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # inside the checkout, ignored


def log(msg: str) -> None:
    print(f"[bench {time.time() - _T0:7.1f}s] {msg}", flush=True)


def require_chips(chips: int):
    """The devices the cell runs on, or SystemExit: never another platform,
    never another count than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: jax found platform {devs[0].platform!r}, not a "
                 "TPU; the benchmark does not fall back")
    if len(devs) != chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), jax holds "
                 f"{len(devs)}")
    return devs


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip, as jax reports them. On the TPU
    runtime `peak_bytes_in_use` counts buffers only; what a running program
    needs for its temporaries is held as a reservation that is counted apart
    (`peak_bytes_reserved`) and stays held once made. A probe program with
    7.34 GB of temporaries read 1.6 MB in use and 7.34 GB reserved (PERF.md
    section 2), so the peak is the sum of the two."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        log(f"memory_stats {d}: " + ", ".join(
            f"{k}={stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_reserved", "peak_bytes_reserved",
                                        "bytes_limit") if k in stats))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def counters() -> dict:
    """The program's own counts, read around the window."""
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.runtime import phases

    return {"xla": dict(phases.xla_counts()),
            "phases": dict(phases.snapshot()),
            "cache": {k: v for k, v in dataset_cache.snapshot().items()
                      if k.endswith(("_hits", "_misses")) or k == "evictions"}}


def delta(after: dict, before: dict) -> dict:
    return {g: {k: after[g].get(k, 0) - before[g].get(k, 0)
                for k in after[g] if isinstance(after[g][k], (int, float))}
            for g in after}


class Window:
    """Whole fits back to back; nothing but train() and the reading of its
    result is inside."""

    def __init__(self, algo, cfg, columns, requests, shared_frame):
        self.algo, self.cfg, self.columns = algo, cfg, columns
        self.requests, self.shared = requests, shared_frame
        self.results, self.attempted, self.failed, self.steps = [], 0, 0, 0
        self.last_est = None
        self.t_begin = self.t_end = None

    def one_fit(self) -> None:
        req = next(self.requests)
        frame = (self.algo.make_frame(self.columns) if req["fresh_frame"]
                 else self.shared)
        est = self.algo.make_estimator(self.cfg, req["overrides"])
        self.attempted += 1
        try:
            self.algo.train(est, frame)
            self.results.append(self.algo.result(self.cfg, est,
                                                 req["overrides"]))
            self.steps += self.algo.steps(est)
            self.last_est = est
        except Exception:
            self.failed += 1
            traceback.print_exc()
        self.t_end = time.perf_counter()

    def run(self, seconds: float, first_guess: float, max_fits=None) -> None:
        self.t_begin = time.perf_counter()
        while max_fits is None or self.attempted < max_fits:
            done = len(self.results)
            mean = ((self.t_end - self.t_begin) / done if done else first_guess)
            ahead = time.perf_counter() - self.t_begin + mean
            if self.attempted and ahead > seconds:
                break
            self.one_fit()

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_begin


def decide(cfg: dict, numbers: dict):
    """Each number compared beside its limit. The configuration's `limits`
    name the numbers that are compared; what the reference reads besides is
    logged and decides nothing (PERF.md says why each such number has no
    limit). A configuration with no limits, or a limit the reference does not
    read, cannot pass."""
    limits = cfg.get("limits", {})
    for name, value in numbers.items():
        if name not in limits:
            log(f"read, not compared: {name} = {value:.6g}")
    compared = {k: [float(numbers.get(k, float("nan"))), lim]
                for k, lim in limits.items()}
    ok = bool(compared) and all(np.isfinite(v) and v <= lim
                                for v, lim in compared.values())
    return bool(ok), compared


def verify(cfg: dict, algo, data: dict, results: list, seed: int):
    """The comparison with the plain reference, on a fit of the window drawn
    from the seed."""
    ref = manifest.load_module("references", algo.REFERENCE)
    pick = int(np.random.default_rng(
        np.random.SeedSequence([int(seed), 0xC0FFEE])).integers(len(results)))
    t = time.time()
    prep = ref.prepare(cfg, data)
    numbers = ref.compare(cfg, prep, results[pick])
    log(f"reference: compared fit {pick + 1} of {len(results)} in "
        f"{time.time() - t:.1f}s")
    return decide(cfg, numbers)


def start_trace() -> None:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # a whole fit of python frames is GBs
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def stop_trace() -> str:
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb, found {found}")
    return found[0]


def run_cell(args, man: dict) -> dict:
    cell = manifest.cell(man, args.workload)
    cfg = manifest.config(man, cell["config"])
    traffic = traffic_gen.check(manifest.traffic(cell["traffic"]))
    algo = manifest.load_module("algos", cfg["algo"])
    devs = require_chips(int(cell["chips"]))
    # set-up is counted from here: what came before is the interpreter, jax
    # and the TPU runtime's own start, 10 to 16 s that differ between two
    # identical runs by more than the set-up's bound and that neither the
    # program nor the benchmark can move (PERF.md section 2)
    t_setup = time.time()
    runtime_start_s = t_setup - _T0
    log(f"runtime start-up, not in setup_s: {runtime_start_s:.2f}s")
    import jax

    import h2o3_tpu

    log(f"cell {cell['name']}: config {cfg['name']}, traffic "
        f"{cell['traffic']}, {len(devs)} x {devs[0].device_kind}, compile "
        f"cache {h2o3_tpu.compile_cache_dir()}")

    # -- set-up ---------------------------------------------------------------
    data = algo.make_data(cfg, args.seed)
    columns = algo.make_columns(data)
    log("data and host columns made from the seed")
    requests = traffic_gen.fits(traffic, args.seed)
    shared = None if traffic["frame"] == "fresh" else algo.make_frame(columns)
    warm = Window(algo, cfg, columns, requests, shared)
    warm.run(0.0, 0.0, max_fits=1)
    if warm.failed:
        raise RuntimeError("the warm-up fit failed")
    log(f"warm-up fit {warm.seconds:.2f}s; xla {counters()['xla']}")
    del warm.results[:]
    gc.collect()
    setup_s = time.time() - t_setup

    # -- the window -----------------------------------------------------------
    from h2o3_tpu.runtime.timeline import Timeline

    win = Window(algo, cfg, columns, requests, shared)
    before = counters()
    cursor = Timeline.cursor()
    trace_file = None
    if args.trace:
        start_trace()
        t_trace = time.perf_counter()
        wall0 = time.time()
        with jax.profiler.TraceAnnotation("bench.window"):
            win.run(float(traffic["trace_seconds"]), warm.seconds)
        traced_s = time.perf_counter() - t_trace
        trace_file = stop_trace()
    else:
        win.run(float(args.seconds), warm.seconds)
    after = counters()
    # the program's own phase marks (runtime/timeline.py): name, wall-clock
    # start, seconds. A mark ends where the host got to, not the device.
    spans = [(e["detail"], e["ts"] - e["secs"], e["secs"])
             for e in Timeline.snapshot(n=4096, since=cursor)
             if e.get("kind") == "train_phase" and "secs" in e]
    if not win.results:
        raise RuntimeError("no fit of the window completed")
    moved = delta(after, before)
    log(f"window {win.seconds:.3f}s, {len(win.results)} fits, "
        f"{win.steps} steps, {win.failed} failed")
    for line in algo.info_lines(win.last_est):
        log(line)
    log(f"dataset_cache in window {moved['cache']}; phases {moved['phases']}; "
        f"xla {moved['xla']}")
    shapes = algo.shapes(cfg, win.last_est)
    peak = memory_peak_bytes()

    # -- free the program's state, then the reference ---------------------------
    results = win.results
    win.last_est = warm.last_est = None
    win.shared = warm.shared = shared = None
    del columns
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if args.trace:
        import reduce_trace

        t = time.time()
        trace = reduce_trace.load(trace_file)
        # the phase marks as host spans on the trace's clock, so that a gap
        # can be named by them
        at = trace.host_event_start("bench.window")
        if at is not None:
            trace.add_spans(spans, at - wall0)
        ctx = {"trace": trace, "cfg": cfg, "cell": cell, "algo": algo,
               "shapes": shapes, "device_kind": devs[0].device_kind,
               "chips": len(devs), "fits": len(results), "steps": win.steps,
               "window_s": win.seconds, "traced_s": traced_s,
               "runtime_start_s": runtime_start_s,
               "counters": moved, "program_spans": spans}
        for m in manifest.metrics_for(man, cell["name"], "per_layer"):
            got = manifest.load_module("metrics", m["name"]).read(ctx)
            if got is None:
                log(f"per-layer {m['name']}: nothing to read")
                continue
            value, note = got if isinstance(got, tuple) else (got, "")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
            log(f"per-layer {m['name']} = {value} {m['unit']} {note}")
        device["busy_s"] = trace.busy_seconds()
        device["window_s"] = traced_s
        breakdown = trace.breakdown(top=10, window="bench.window")
        log(f"trace read in {time.time() - t:.1f}s")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        values = {"setup_s": setup_s,
                  "fit_wall_s": win.seconds / len(results)}
        for m in manifest.metrics_for(man, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    correct, compared = verify(cfg, algo, data, results, args.seed)
    out = {"correct": correct, "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.load_manifest()
    out = run_cell(args, man)
    for name, (value, limit) in out["compared"].items():
        print(f"compared {name} = {value:.6g} (limit {limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
