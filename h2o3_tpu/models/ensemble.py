"""H2OStackedEnsembleEstimator — super learning.

Reference parity: `h2o-algos/src/main/java/hex/ensemble/StackedEnsemble.java`
/ `StackedEnsembleModel.java` / `Metalearner*.java`: a metalearner (GLM with
non-negative weights by default) trained on the cross-validated holdout
predictions of the base models (which must share fold assignment and
`keep_cross_validation_predictions=True`); `metalearner_algorithm` ∈
{AUTO/glm/gbm/drf/deeplearning}. Client surface
`h2o-py/h2o/estimators/stackedensemble.py`.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..frame.frame import Frame
from .metrics import (
    ModelMetricsBinomial,
    ModelMetricsMultinomial,
    ModelMetricsRegression,
)
from .model_base import H2OEstimator, H2OModel, response_info


class StackedEnsembleModel(H2OModel):
    algo = "stackedensemble"

    def __init__(self, params, base_models, meta_est, problem, nclass, domain, y):
        super().__init__(params)
        self.base_models = base_models
        self.meta = meta_est
        self.problem = problem
        self.nclass = nclass
        self.domain = domain
        self.y = y
        self.x = base_models[0].model.x if base_models else []

    def _level_one(self, frame: Frame) -> Frame:
        cols = {}
        for i, bm in enumerate(self.base_models):
            # one base-model prediction per FRAME, shared across ensembles
            # (BestOfFamily ⊆ AllModels would otherwise re-predict every
            # model). Living on the frame object, the cache dies with the
            # frame, cannot collide across frames that reuse a DKV key, and
            # Frame._touch() clears it on any in-place mutation. Computed
            # BEFORE insertion so a failed predict can't poison it.
            preds = frame.__dict__.setdefault("_lvl1_preds", {})
            mid = bm.model.model_id
            if mid not in preds:
                preds[mid] = bm._cv_predict(bm.model, frame)
            p = preds[mid]
            if self.problem == "multinomial":
                for k in range(p.shape[1]):
                    cols[f"m{i}_p{k}"] = p[:, k]
            else:
                cols[f"m{i}"] = p if p.ndim == 1 else p[:, 0]
        return Frame.from_dict(cols)

    def predict(self, test_data: Frame) -> Frame:
        lvl1 = self._level_one(test_data)
        return self.meta.predict(lvl1)

    def _score_probs(self, frame: Frame) -> np.ndarray:
        lvl1 = self._level_one(frame)
        return self.meta._cv_predict(self.meta.model, lvl1)

    def _make_metrics(self, frame: Frame):
        out = self._score_probs(frame)
        yv = frame.vec(self.y)
        if self.problem == "binomial":
            return ModelMetricsBinomial.make(np.asarray(yv.data), out)
        if self.problem == "multinomial":
            return ModelMetricsMultinomial.make(np.asarray(yv.data), out)
        return ModelMetricsRegression.make(yv.numeric_np(), out)


class H2OStackedEnsembleEstimator(H2OEstimator):
    algo = "stackedensemble"
    _param_defaults = dict(
        base_models=None,
        metalearner_algorithm="AUTO",
        metalearner_nfolds=0,
        metalearner_params=None,
        metalearner_transform="NONE",
        blending_frame=None,
    )

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]):
        base_models: List = list(self._parms.get("base_models") or [])
        if not base_models:
            raise ValueError("stackedensemble: base_models is required")
        problem, nclass, domain = response_info(train.vec(y))

        blend = self._parms.get("blending_frame")
        cols = {}
        for i, bm in enumerate(base_models):
            if blend is not None:
                p = bm._cv_predict(bm.model, blend)
            else:
                p = bm.model._cv_holdout_pred
                if p is None:
                    raise ValueError(
                        f"base model {bm.model_id} lacks CV holdout predictions; "
                        "train with nfolds>=2 and keep_cross_validation_predictions=True"
                    )
            if problem == "multinomial":
                for k in range(p.shape[1]):
                    cols[f"m{i}_p{k}"] = p[:, k]
            else:
                cols[f"m{i}"] = p if p.ndim == 1 else p[:, 0]
        target_frame = blend if blend is not None else train
        lvl1 = Frame.from_dict(cols)
        yv = target_frame.vec(y)
        lvl1["__y__"] = yv

        algo = self._parms.get("metalearner_algorithm", "AUTO")
        mp = dict(self._parms.get("metalearner_params") or {})
        if algo in ("AUTO", "glm"):
            from .glm import H2OGeneralizedLinearEstimator

            fam = {"binomial": "binomial", "multinomial": "multinomial"}.get(
                problem, "gaussian"
            )
            mp.setdefault("family", fam)
            mp.setdefault("lambda_", 0.0)
            mp.setdefault("non_negative", True)
            meta = H2OGeneralizedLinearEstimator(**mp)
        elif algo == "gbm":
            from .gbm import H2OGradientBoostingEstimator

            meta = H2OGradientBoostingEstimator(**mp)
        elif algo == "drf":
            from .drf import H2ORandomForestEstimator

            meta = H2ORandomForestEstimator(**mp)
        elif algo == "deeplearning":
            from .deeplearning import H2ODeepLearningEstimator

            meta = H2ODeepLearningEstimator(**mp)
        else:
            raise ValueError(f"unknown metalearner_algorithm {algo!r}")
        meta.train(y="__y__", training_frame=lvl1)

        model = StackedEnsembleModel(self, base_models, meta, problem, nclass, domain, y)
        # the SE's training frame IS the level-one frame (out-of-fold base
        # predictions), so the metalearner's training metrics are exactly
        # the SE's cross-validated training metrics — no re-prediction of
        # every base model on the raw frame (which costs seconds per deep
        # forest; upstream StackedEnsemble scores on the level-one frame
        # too: hex/ensemble/StackedEnsemble.java)
        model.training_metrics = meta.model.training_metrics
        if valid is not None:
            model.validation_metrics = model._make_metrics(valid)
        return model

    def _cv_predict(self, model, frame: Frame) -> np.ndarray:
        return model._score_probs(frame)


StackedEnsemble = H2OStackedEnsembleEstimator
