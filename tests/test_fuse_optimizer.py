"""`BuildStrategy.fuse_all_optimizer_ops` — the reference's
fuse_optimizer_ops_pass family (framework/ir/fuse_optimizer_ops_pass/).
Here the knob is accepted and changes nothing: XLA fuses the
per-parameter update loops itself, and the coalesced flat update vector
the reference builds is what the TPU compiler refused for a 133 M
parameter model. The program keeps its N optimizer ops and its losses."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.core import scope as scope_mod


def _build(opt_name, seed=13):
    main = framework.default_main_program()
    st = framework.default_startup_program()
    main.random_seed = st.random_seed = seed
    x = fluid.layers.data("x", shape=[16], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=32, act="relu")
    h = fluid.layers.fc(h, size=32, act="relu")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.loss.softmax_with_cross_entropy(logits, y))
    if opt_name == "sgd":
        opt = fluid.optimizer.SGDOptimizer(0.1)
    elif opt_name == "momentum":
        opt = fluid.optimizer.MomentumOptimizer(0.05, momentum=0.9)
    else:
        opt = fluid.optimizer.AdamOptimizer(1e-2)
    opt.minimize(loss)
    return loss


def _fresh():
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def _batch():
    r = np.random.RandomState(0)
    return {"x": r.randn(16, 16).astype("float32"),
            "y": r.randint(0, 4, (16, 1)).astype("int64")}


def _run_steps(loss, program=None, steps=5):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    return [float(np.asarray(exe.run(
        program, feed=_batch(), fetch_list=[loss])[0]).mean())
        for _ in range(steps)]


def _with_knob(prog, **kw):
    bs = fluid.BuildStrategy()
    bs.fuse_all_optimizer_ops = True
    return fluid.CompiledProgram(prog, build_strategy=bs, **kw)


@pytest.mark.parametrize("opt_name", ["sgd", "momentum", "adam"])
def test_knob_keeps_the_ops_and_the_losses(opt_name):
    with framework.unique_name_guard():
        base = _run_steps(_build(opt_name))

    _fresh()
    with framework.unique_name_guard():
        loss = _build(opt_name)
        prog = framework.default_main_program()
        ops_before = [op.type for op in prog.global_block().ops]
        got = _run_steps(loss, _with_knob(prog))
        assert [op.type for op in prog.global_block().ops] == ops_before
        assert ops_before.count(opt_name) > 1
    assert got == base


def test_knob_under_data_parallel_matches_single():
    """The knob x with_data_parallel: losses match the single-device
    run."""
    _fresh()
    with framework.unique_name_guard():
        base = _run_steps(_build("momentum"), steps=4)

    _fresh()
    with framework.unique_name_guard():
        loss = _build("momentum")
        compiled = _with_knob(
            framework.default_main_program()).with_data_parallel(
                loss_name=loss.name)
        dp = _run_steps(loss, compiled, steps=4)
    np.testing.assert_allclose(base, dp, rtol=2e-4, atol=1e-5)
