"""The one environment every test hands a child process."""
import os


def cpu_child_env(extra=None, drop=()):
    """This process's environment for a child: `JAX_PLATFORMS=cpu`, no
    `XLA_FLAGS` (the child gets one CPU device, not the tests' 8-device
    mesh, unless it asks) and no `PADDLE_FAULTS` carried over; `drop`
    names further variables to leave out, `extra` is applied last."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("XLA_FLAGS", "PADDLE_FAULTS") + tuple(drop):
        env.pop(k, None)
    env.update(extra or {})
    return env
