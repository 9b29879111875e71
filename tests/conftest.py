"""Test config: an 8-device virtual CPU mesh, set up BEFORE jax is
imported, so sharding/collective tests run without TPU hardware
(SURVEY.md §4.4: CI runs on CPU with
xla_force_host_platform_device_count)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# the tests place their compile caches themselves (tmp dirs through the
# flag, or this variable set by the test that checks its placement): a
# directory exported by whoever runs the suite would make every
# "cold compile" assertion depend on what it already holds
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + unique names."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.core import scope as scope_mod

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = scope_mod.Scope()
    with framework.unique_name_guard():
        yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope


@pytest.fixture
def rng():
    return np.random.RandomState(42)
