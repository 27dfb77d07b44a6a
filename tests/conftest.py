"""Test cloud bootstrap — the analog of `water.TestUtil` +
`@RunWith(H2ORunner)` spinning an in-process cloud (SURVEY.md §4): an
8-virtual-device CPU mesh stands in for an 8-host TPU pod, so every
distributed code path (shard_map + psum) runs the real collective lowering
on loopback, mirroring the reference's multi-JVM-on-one-host clouds."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the compile cache comes from the package's one resolver (import side
# effect): JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
import h2o3_tpu  # noqa: E402,F401

import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert len(jax.devices()) >= 8, "test cloud needs 8 virtual CPU devices"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 run "
        "(-m 'not slow')")


@pytest.fixture(scope="session")
def cloud8():
    """8-device cloud (all virtual CPU devices)."""
    import jax
    from h2o3_tpu.parallel import mesh

    c = mesh.init(jax.devices())
    yield c
    mesh.reset()


@pytest.fixture()
def cloud1():
    """Single-device cloud — resets the global cloud to 1 device."""
    import jax
    from h2o3_tpu.parallel import mesh

    c = mesh.init(jax.devices()[:1])
    yield c
    mesh.reset()


@pytest.fixture(autouse=True)
def _reset_cloud():
    yield
    from h2o3_tpu.parallel import mesh

    mesh.reset()


def make_classification(n=2000, f=10, seed=0, informative=5):
    """Synthetic binary problem (separable-ish) — TestFrameBuilder stand-in."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    beta = np.zeros(f)
    informative = min(informative, f)
    beta[:informative] = rng.uniform(0.5, 2.0, informative) * rng.choice([-1, 1], informative)
    logits = X @ beta + 0.5 * X[:, 0] * X[:, 1]
    p = 1 / (1 + np.exp(-logits))
    y = (rng.random(n) < p).astype(int)
    return X, y


def make_regression(n=2000, f=8, seed=0, noise=0.1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 3) + 0.5 * X[:, 2] ** 2 + noise * rng.normal(size=n)
    return X, y
