"""Worker script for the persistent compile-cache tests.

Two uses:
  - direct subprocess (warm-restart proof): the parent sets
    JAX_COMPILATION_CACHE_DIR (+ FLAGS_tpu_telemetry_dir) in the env
    and runs this twice — the second process must classify every fresh
    compile as a persistent-cache HIT and produce bit-identical
    losses;
  - under `python -m paddle_tpu.distributed.launch` (supervised
    elastic warm restart): with the "elastic" argv flag, rank 1 of
    attempt 0 exits 7 after its steps (the lost machine) and the
    survivor sleeps until the fail-fast teardown, so the supervisor
    shrinks the world and the respawned attempt-1 cohort re-compiles
    THROUGH the directory the supervisor exports.

argv: [<steps>] ["elastic"]. Prints one line:
RESULT {"losses": [...17-digit strs...], "hits": N, "misses": N, ...}
"""
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xla = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla:
    os.environ["XLA_FLAGS"] = (
        _xla + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    elastic = "elastic" in sys.argv[2:]
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework

    main_p, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main_p, startup), \
            framework.unique_name_guard():
        # fixed seeds: the cold and warm runs must be bit-identical
        main_p.random_seed = startup.random_seed = 7
        x = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="tanh")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - y))
        fluid.optimizer.SGDOptimizer(
            learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(42)
    feed = {"x": rng.randn(4, 8).astype("float32"),
            "y": rng.randn(4, 1).astype("float32")}
    losses = []
    for _ in range(steps):
        out = exe.run(main_p, feed=feed, fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    from paddle_tpu.fluid import compile_cache as cc

    st = cc.stats()
    print("RESULT " + json.dumps({
        "losses": ["%.17g" % v for v in losses],
        "hits": st["hits"], "misses": st["misses"],
        "enabled": st["enabled"], "dir": st["dir"]}), flush=True)
    if elastic:
        tid = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        attempt = int(os.environ.get("PADDLE_RESTART_NUM", "0"))
        if attempt == 0:
            if tid == 1:
                sys.exit(7)  # the lost machine
            time.sleep(60)  # survivor: await the fail-fast teardown
    sys.exit(0)


if __name__ == "__main__":
    main()
