"""Legacy fluid public-surface stragglers (VERDICT r4 missing #4):
fluid.unique_name, require_version, ParallelExecutor compat,
is_compiled_with_cuda, memory_optimize/release_memory no-ops,
load_op_library, ComplexVariable. The reference idioms must run
unmodified (reference: python/paddle/fluid/__init__.py:79-129,
parallel_executor.py:29, framework.py:73,151)."""
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework


def test_unique_name_guard_idiom():
    # the multi-program idiom: counters reset inside each guard
    with fluid.unique_name.guard():
        a = fluid.unique_name.generate("fc")
    with fluid.unique_name.guard():
        b = fluid.unique_name.generate("fc")
    assert a == b == "fc_0"
    n1 = fluid.unique_name.generate("fc")
    n2 = fluid.unique_name.generate("fc")
    assert n1 != n2


def test_unique_name_prefix_and_switch():
    with fluid.unique_name.guard("pre_"):
        assert fluid.unique_name.generate("x").startswith("pre_x_")
    gen = fluid.unique_name.UniqueNameGenerator()
    old = fluid.unique_name.switch(gen)
    try:
        assert fluid.unique_name.generate("y") == "y_0"
    finally:
        fluid.unique_name.switch(old)
    assert fluid.unique_name.generate_with_ignorable_key("tmp") \
        .startswith("_generated_var_")


def test_require_version():
    fluid.require_version("0.0.1")
    fluid.require_version(min_version="0.0.1", max_version="99.0")
    with pytest.raises(Exception):
        fluid.require_version("99.0.0")
    with pytest.raises(TypeError):
        fluid.require_version(1)
    with pytest.raises(ValueError):
        fluid.require_version("not.a.version")


def test_is_compiled_with_cuda_false():
    assert fluid.is_compiled_with_cuda() is False


def test_memory_optimize_release_memory_warn_noop():
    main = framework.Program()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fluid.memory_optimize(main)
        fluid.release_memory(main)
    assert len(w) == 2
    assert all(issubclass(x.category, DeprecationWarning) for x in w)


def test_parallel_executor_compat_runs():
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(input=x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square(pred - y))
            fluid.optimizer.SGDOptimizer(
                learning_rate=0.01).minimize(loss)

    from paddle_tpu.core.scope import Scope, scope_guard

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False,
                                    loss_name=loss.name,
                                    main_program=main)
        r = np.random.RandomState(0)
        feed = {"x": r.rand(8, 4).astype("float32"),
                "y": r.rand(8, 1).astype("float32")}
        l0 = pe.run([loss.name], feed=feed)[0]
        # deprecated feed_dict alias + legacy positional fetch_list
        l1 = pe.run(fetch_list=[loss.name], feed_dict=feed)[0]
        # one loss per replica, each over its share of the batch: the
        # step lowers their mean, not every replica's own
        assert np.isfinite(np.asarray(l0)).all()
        assert float(np.mean(l1)) <= float(np.mean(l0)) + 1e-6
        pe.drop_local_exe_scopes()  # API-compat no-op
        assert pe.device_count >= 1


def test_load_op_library_loads_native_so():
    from paddle_tpu.core.native import build as native_build

    lib = fluid.load_op_library(native_build.build())
    assert lib is not None


def test_complex_variable_dygraph():
    from paddle_tpu.fluid.dygraph import base as dg

    with dg.guard():
        re = dg.to_variable(np.array([1.0, 2.0], "float32"))
        im = dg.to_variable(np.array([3.0, 4.0], "float32"))
        c = fluid.ComplexVariable(re, im)
        assert tuple(c.shape) == (2,)
        np.testing.assert_allclose(
            c.numpy(), np.array([1 + 3j, 2 + 4j]))
        assert "ComplexVariable" in repr(c)


def test_framework_unique_name_guard_prefix():
    # the framework-level guard must honor prefix like
    # fluid.unique_name.guard does (the two surfaces share state)
    with framework.unique_name_guard("fw_"):
        assert framework.unique_name("t").startswith("fw_t_")
