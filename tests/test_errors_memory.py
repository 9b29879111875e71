"""Enforce/error system with op callstack attribution (reference:
platform/enforce.h + op_call_stack.cc) and the memory facade
(memory/malloc.h + monitor.h stats)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core import errors, memory
from paddle_tpu.fluid import framework


def test_enforce_error_classes():
    with pytest.raises(errors.InvalidArgumentError):
        errors.enforce(False, "bad arg")
    with pytest.raises(errors.NotFoundError):
        errors.enforce_not_none(None, "thing")
    assert errors.UnimplementedError.code == "UNIMPLEMENTED"
    assert issubclass(errors.OutOfRangeError, errors.EnforceNotMet)


def test_op_error_carries_creation_site():
    """A failing op's error names THIS test file as the creation site
    (reference: InsertCallStackInfo in op_call_stack.cc)."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            # op created HERE with an impossible target shape
            y = fluid.layers.reshape(x, [3, 5])
    from paddle_tpu.core.scope import Scope

    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(Exception) as ei:
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[y], scope=Scope())
    msg = str(ei.value)
    assert "op created at" in msg
    assert "test_errors_memory.py" in msg


def test_memory_facade_host_alloc():
    a = memory.Alloc(fluid.CPUPlace(), 1024)
    assert a.size == 1024 and a.ptr
    memory.Free(a)

    with pytest.raises(errors.UnavailableError):
        memory.Alloc(fluid.TPUPlace(), 1024)


def test_memory_stats_surface():
    stats = memory.memory_stats()
    assert isinstance(stats, dict)
    # CPU backends may expose no PJRT stats; the API must still answer
    assert memory.memory_allocated() >= 0
    assert memory.max_memory_allocated() >= 0
