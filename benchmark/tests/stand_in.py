"""The plain reference put in the program's place, for the tests: an adapter
with the surface of algos/<algo>.py whose train() is the reference's own fit,
at a lower precision or with a fault planted. run.py then drives the rest of
a run over it as over the program."""

import types

import manifest


def adapter(algo_name: str, make_result):
    """`make_result(ref, cfg, prep, params)` → the result dict of one fit."""
    real = manifest.load_module("algos", algo_name)
    ref = manifest.load_module("references", real.REFERENCE)
    state = {}

    def make_data(cfg, seed):
        state["data"] = real.make_data(cfg, seed)
        state["prep"] = ref.prepare(cfg, state["data"])
        return state["data"]

    def make_estimator(cfg, overrides):
        return {"cfg": cfg, "params": {**cfg["estimator"], **overrides}}

    def train(est, frame):
        est["result"] = make_result(ref, est["cfg"], state["prep"],
                                    est["params"])

    return types.SimpleNamespace(
        REFERENCE=real.REFERENCE, make_data=make_data,
        make_columns=lambda data: {}, make_frame=lambda columns: object(),
        make_estimator=make_estimator, train=train,
        result=lambda cfg, est, overrides: est["result"],
        steps=lambda est: 1, shapes=lambda cfg, est: {},
        info_lines=lambda est: [])
