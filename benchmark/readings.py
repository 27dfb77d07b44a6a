#!/usr/bin/env python3
"""Readings for setting the limits of `correct` (not part of a run).

    python3 benchmark/readings.py --workload <cell> --seeds 12 --below 3

In ONE process, at the cell's own size on the chip: for each of `--seeds`
seeds, the data, one train() of the program on a request of the cell's
traffic, and the numbers the comparison reads for it (the lower readings);
for the first `--below` seeds also the control and each planted fault (the
upper readings). The control is the program itself one precision below the
stated where its adapter can switch that on (`lower_precision`), and the
reference in the program's place at that precision besides. Every reading goes through run.py's own `decide` against the
limits the configuration file holds, so each line says what `correct` a run
with those numbers would have printed. One JSON line per reading on standard
output, and in chiprun_out/readings_<cell>.jsonl. PERF.md records what the
limits in the configuration files were set from."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import manifest
import run
import traffic_gen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--below", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--controls", default="")
    ap.add_argument("--only-below", action="store_true",
                    help="no program fit: the control and the faults only")
    args = ap.parse_args(argv)
    man = manifest.load_manifest()
    cell = manifest.cell(man, args.workload)
    cfg = manifest.config(man, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    algo = manifest.load_module("algos", cfg["algo"])
    ref = manifest.load_module("references", algo.REFERENCE)
    run.require_chips(int(cell["chips"]))
    from h2o3_tpu.models import dataset_cache

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(ROOT, "chiprun_out",
                             f"readings_{cell['name']}.jsonl"), "a")

    def emit(**rec) -> None:
        rec["correct"], rec["compared"] = run.decide(cfg, rec["numbers"])
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        data = algo.make_data(cfg, seed)
        columns = algo.make_columns(data)
        req = next(traffic_gen.fits(traffic, seed))
        result = {"params": {**cfg["estimator"], **req["overrides"]}}
        if not args.only_below:
            est = algo.make_estimator(cfg, req["overrides"])
            t1 = time.time()
            algo.train(est, algo.make_frame(columns))
            fit_s = time.time() - t1
            result = algo.result(cfg, est, req["overrides"])
            del est
        lowered = None
        if i < args.below and hasattr(algo, "lower_precision"):
            dataset_cache.clear()
            gc.collect()
            with algo.lower_precision():
                est = algo.make_estimator(cfg, req["overrides"])
                algo.train(est, algo.make_frame(columns))
                lowered = algo.result(cfg, est, req["overrides"])
                del est
        del columns
        dataset_cache.clear()
        gc.collect()
        t2 = time.time()
        prep = ref.prepare(cfg, data)
        if not args.only_below:
            emit(cell=cell["name"], seed=seed, kind="program",
                 overrides=req["overrides"], fit_s=fit_s,
                 numbers=ref.compare(cfg, prep, result),
                 reference_s=time.time() - t2, data_s=t1 - t0)
        if i < args.below:
            reference = lambda: ref.control(cfg, prep, result["params"])
            kinds = ([("control", lambda: lowered),
                      ("control:reference", reference)]
                     if lowered is not None else [("control", reference)])
            kinds += [(f"control:{p}", (lambda p=p: ref.control(
                cfg, prep, result["params"], p)))
                for p in args.controls.split(",") if p]
            kinds += [(f"fault:{f}", (lambda f=f: ref.faulty(
                cfg, prep, result["params"], f))) for f in ref.FAULTS]
            for kind, make in kinds:
                t3 = time.time()
                emit(cell=cell["name"], seed=seed, kind=kind,
                     numbers=ref.compare(cfg, prep, make()),
                     seconds=time.time() - t3)
        del prep, data
        gc.collect()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
