"""Rehearsal of chip_smoke.py on the suite's forced CPU: the SAME phase
functions `main()` runs on the chip, at tiny sizes. On CPU `resolve_method`
takes `segment`, so the expected kernel is passed in — the phases never
branch on the platform. The four-chip phase runs on four of the suite's
virtual devices."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke


def test_refuses_without_tpu():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu exits nonzero before
    any fit and prints no result line."""
    p = subprocess.run([sys.executable, chip_smoke.__file__],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


@pytest.fixture()
def fitted(cloud1):
    return chip_smoke.phase_fit(
        n_rows=4000, ntrees=6, max_depth=3, expect_method="segment",
        min_auc=0.65, pair_rows=2000, pair_trees=3,
        pair_methods=("onehot", "segment"), pair_auc_tol=0.02)


def test_fit_score_refit_phases(fitted):
    gbm, fr = fitted
    chip_smoke.phase_score(gbm, n_rows=3000)
    chip_smoke.phase_refit(fr, ntrees=6, max_depth=3)


def test_serve_phase(cloud1):
    chip_smoke.phase_serve(train_rows=2000, score_rows=300, n_requests=32,
                           n_threads=8, expect_method="segment", ntrees=4,
                           max_depth=3)


def test_engine_phase(cloud1):
    chip_smoke.phase_engine(glm_rows=4000, dl_rows=1500, dl_width=32,
                            dl_hidden=[16, 16], dl_epochs=8,
                            max_dl_logloss=2.0)


def test_sharded_phase_on_four_virtual_devices():
    chip_smoke.phase_sharded(jax.devices()[:4], n_rows=2048, ntrees=6,
                             max_depth=3, expect_method="segment",
                             glm_rows=2048, check_memory=False)
