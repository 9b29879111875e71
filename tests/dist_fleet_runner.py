"""Multi-host Fleet DP runner (spawned by paddle_tpu.distributed.launch
with the PADDLE_* env contract; reference pattern: test_dist_base.py
dist runners over nccl2 mode). Each "host" is one CPU-platform process
contributing one device to the global mesh via jax.distributed."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid import framework  # noqa: E402

LR = 0.5
STEPS = 5
BATCH = 32


def build(seed=21):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = seed
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(input=x, size=32, act="relu")
            logits = fluid.layers.fc(input=h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            opt = fluid.optimizer.SGDOptimizer(learning_rate=LR)
            opt.minimize(loss)
    return main, startup, loss


def data():
    r = np.random.RandomState(6)
    x = r.rand(BATCH, 16).astype("float32")
    y = r.randint(0, 4, (BATCH, 1)).astype("int64")
    return x, y


def main():
    single = len(sys.argv) > 1 and sys.argv[1] == "single"
    from paddle_tpu.core.scope import Scope

    if single:
        main_p, startup, loss = build()
    else:
        from paddle_tpu import fleet

        fleet.init(is_collective=True)  # jax.distributed over PADDLE_* env
        import jax

        nhosts = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        assert len(jax.devices()) == nhosts, (
            "jax.distributed did not form the global mesh: %s"
            % jax.devices())
        main_p, startup, loss = build()
        fleet.transpile_collective(main_p)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    x, y = data()
    for _ in range(STEPS):
        out = exe.run(main_p, feed={"x": x, "label": y},
                      fetch_list=[loss], scope=scope)
        v = np.asarray(out[0]).reshape(-1)
        print("LOSS %.6f" % float(np.mean(v)), flush=True)


if __name__ == "__main__":
    main()
