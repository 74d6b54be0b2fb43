"""Test harness: simulate an 8-device TPU mesh on CPU.

Multi-chip hardware is not available in CI; all mesh/pjit/collective code
paths are exercised on 8 virtual CPU devices (SURVEY §4 test-strategy note).
Env vars must be set before jax initializes, hence this file's import-time
side effects.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("KERAS_BACKEND", "jax")
# The suite is written against 8 virtual devices by default; replace any
# pre-existing count rather than deferring to it. DISTKERAS_FORCE_DEVICES
# overrides the count for lane variants (the CI sharded-serving job runs
# the mesh parity suite on a 4-device host platform; device-count-
# sensitive tests read len(jax.devices()) instead of assuming 8).
_n_devices = int(os.environ.get("DISTKERAS_FORCE_DEVICES", "8"))
_flags = os.environ.get("XLA_FLAGS", "")
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", _flags)
# Single-threaded Eigen: the virtual devices share one intra-op pool,
# and pool-parallel kernels inside collective programs can deadlock the
# all-reduce rendezvous (see utils/platform.ensure_virtual_cpu_flags).
os.environ["XLA_FLAGS"] = (
    _flags + f" --xla_force_host_platform_device_count={_n_devices}"
    " --xla_cpu_multi_thread_eigen=false"
).strip()

# JAX_PLATFORMS=cpu above is what pins the suite to the virtual CPU
# devices; the config update repeats it for a jax some plugin imported
# before this file ran.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == _n_devices, jax.devices()

# Persistent XLA compilation cache: the suite is compile-dominated (every
# parity test builds prefill/decode executables for the same tiny models),
# so repeat runs on a small CI box spend most of their wall clock
# recompiling programs that haven't changed. Cache keys cover the HLO,
# compile options, and backend, so hits are exact; the RecompileAuditor
# is unaffected (it counts jit-cache entries, which a disk hit still
# creates — only the XLA compile time is skipped). The min-compile-time
# threshold is deliberately left at its default: forcing it to 0 also
# caches sub-second multi-device trainer programs whose round-trip
# through the serializer aborts the process on reload (reproduced on
# tests/test_checkpoint.py). The location is the repo's one rule
# (utils.platform.use_compile_cache): JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache. Opt out with DISTKERAS_JAX_CACHE=0.
if os.environ.get("DISTKERAS_JAX_CACHE", "1") != "0":
    from distkeras_tpu.utils.platform import use_compile_cache  # noqa: E402

    use_compile_cache()
else:
    jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def artifact_dir(tmp_path):
    """Where observability artifacts (flight-recorder dumps, metrics
    snapshots, Chrome traces, training-health statusz snapshots) land.
    CI sets DISTKERAS_TEST_ARTIFACTS and uploads the directory when the
    suite fails, so a red serving test ships its black box — and a red
    async-trainer test its statusz worker table — with the failure;
    locally it is just tmp_path. Tests that exercise a multi-worker
    trainer should dump ``trainer.training_health.statusz()`` here
    (see tests/test_training_health.py)."""
    import pathlib

    out = os.environ.get("DISTKERAS_TEST_ARTIFACTS")
    if out:
        path = pathlib.Path(out)
        path.mkdir(parents=True, exist_ok=True)
        return path
    return tmp_path


@pytest.fixture
def toy_classification(rng):
    """Linearly separable 2-class problem: fast convergence sanity checks."""
    from distkeras_tpu.data.dataset import Dataset

    n, d = 512, 16
    w = rng.normal(size=(d,))
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    return Dataset.from_arrays(features=x, label=y)


@pytest.fixture
def toy_multiclass(rng):
    from distkeras_tpu.data.dataset import Dataset

    n, d, c = 768, 20, 4
    centers = rng.normal(size=(c, d)) * 3.0
    labels = rng.integers(0, c, size=n)
    x = (centers[labels] + rng.normal(size=(n, d))).astype(np.float32)
    return Dataset.from_arrays(features=x, label=labels.astype(np.float32))
