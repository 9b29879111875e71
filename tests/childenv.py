"""The one environment every test hands a child process."""
import atexit
import os
import shutil
import tempfile

_CACHE_ROOT = None


def _fresh_cache_dir():
    """A new, empty directory under one per-process root that goes when
    this process does."""
    global _CACHE_ROOT
    if _CACHE_ROOT is None:
        _CACHE_ROOT = tempfile.mkdtemp(prefix="paddle_tpu_test_cc_")
        atexit.register(shutil.rmtree, _CACHE_ROOT, ignore_errors=True)
    return tempfile.mkdtemp(dir=_CACHE_ROOT)


def cpu_child_env(extra=None, drop=()):
    """This process's environment for a child: `JAX_PLATFORMS=cpu`, no
    `XLA_FLAGS` (the child gets one CPU device, not the tests' 8-device
    mesh, unless it asks) and no `PADDLE_FAULTS` carried over; `drop`
    names further variables to leave out, `extra` is applied last.

    `JAX_COMPILATION_CACHE_DIR` names a fresh directory for every call:
    the launch supervisor exports `<checkout>/.jax_cache` where the
    variable is unset, and a test's cold compiles must not depend on
    what an earlier test or run left there. A test that places the
    cache itself passes the variable in `extra`."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("XLA_FLAGS", "PADDLE_FAULTS") + tuple(drop):
        env.pop(k, None)
    env["JAX_COMPILATION_CACHE_DIR"] = _fresh_cache_dir()
    env.update(extra or {})
    return env
