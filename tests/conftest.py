"""Test harness: simulate an 8-device TPU pod on CPU.

Mirrors the reference's test strategy (SURVEY.md §4): distributed paths
are exercised on a multi-partition local backend — here an 8-device
virtual CPU mesh via XLA_FLAGS, the analogue of `local[N]` Spark specs.
Env vars must be set before jax initialises.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# JAX's persistent compilation cache off for the suite and for every
# worker process a test spawns: tier-1 timing and the silence of the
# compile-for-the-described-TPU tests must not depend on what a
# developer's <checkout>/.jax_cache happens to hold.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_context():
    """Reset global state between tests: context and layer naming (so
    param init rng streams don't depend on test execution order)."""
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    Layer.reset_name_counters()
    yield
    from analytics_zoo_tpu.common.config import reset_config
    from analytics_zoo_tpu.common.zoo_context import reset_zoo_context
    reset_zoo_context()
    # also drop the config: programmatic sets now survive context
    # re-init by design, which across TESTS would leak one test's
    # knobs into the next
    reset_config()


@pytest.fixture
def f32_policy():
    """Full-f32 dtype policy for golden-oracle comparisons (default
    policy is bf16 compute, which would swamp 1e-4 tolerances)."""
    from analytics_zoo_tpu.ops import dtypes
    old = dtypes.get_policy()
    dtypes.set_policy(param_dtype="float32", compute_dtype="float32")
    yield
    dtypes.restore_policy(old)
