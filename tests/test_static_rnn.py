"""StaticRNN builder (reference: layers/control_flow.py StaticRNN +
recurrent_op.cc; here the step template unrolls at build time)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework


def test_static_rnn_matches_numpy(rng):
    T, B, D, H = 4, 3, 5, 6
    x_np = rng.rand(T, B, D).astype("float32")

    x = fluid.layers.data(name="x", shape=[B, D],
                          append_batch_size=False, dtype="float32")
    # feed provides the time-major [T, B, D] tensor
    x.shape = (T, B, D)

    rnn = fluid.layers.StaticRNN()
    with rnn.step():
        word = rnn.step_input(x)
        prev = rnn.memory(shape=[-1, H], batch_ref=word)
        hidden = fluid.layers.fc(
            input=[word, prev], size=H, act="relu",
            param_attr=fluid.ParamAttr(name="rnn_w"),
            bias_attr=fluid.ParamAttr(name="rnn_b"))
        rnn.update_memory(prev, hidden)
        rnn.step_output(hidden)
    out = rnn()
    assert tuple(out.shape) == (T, B, H)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got = np.asarray(exe.run(feed={"x": x_np}, fetch_list=[out])[0])

    from paddle_tpu.core.scope import global_scope

    # fc over [word, prev] = two weight matrices + shared bias; the
    # second weight auto-names (a named param_attr applies to the first
    # input only, reference multiple_param_attr semantics)
    mul_ws = [op.input_names["Y"][0]
              for op in fluid.default_main_program().global_block().ops
              if op.type == "mul"][:2]
    w1 = np.asarray(global_scope().find_var(mul_ws[0]))
    w2 = np.asarray(global_scope().find_var(mul_ws[1]))
    b = np.asarray(global_scope().find_var("rnn_b"))
    assert mul_ws[0] == "rnn_w" and mul_ws[1] != "rnn_w"

    h = np.zeros((B, H), "float32")
    want = []
    for t in range(T):
        h = np.maximum(x_np[t] @ w1 + h @ w2 + b, 0.0)
        want.append(h)
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-4,
                               atol=1e-5)


def test_static_rnn_trains(rng):
    T, B, D = 3, 4, 5
    # seeded: an unseeded program draws its init from numpy's global
    # stream, and eight Adam steps at 0.05 do not fall from every init
    fluid.default_main_program().random_seed = 11
    fluid.default_startup_program().random_seed = 11
    x = fluid.layers.data(name="x", shape=[B, D],
                          append_batch_size=False, dtype="float32")
    x.shape = (T, B, D)
    label = fluid.layers.data(name="y", shape=[B, 1],
                              append_batch_size=False, dtype="float32")

    rnn = fluid.layers.StaticRNN()
    with rnn.step():
        w = rnn.step_input(x)
        prev = rnn.memory(shape=[-1, 8], batch_ref=w)
        h = fluid.layers.fc(input=[w, prev], size=8, act="tanh")
        rnn.update_memory(prev, h)
        rnn.step_output(h)
    seq = rnn()
    last = fluid.layers.slice(seq, axes=[0], starts=[T - 1], ends=[T])
    last = fluid.layers.reshape(last, [B, 8])
    pred = fluid.layers.fc(input=last, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    fluid.optimizer.AdamOptimizer(0.05).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": rng.rand(T, B, D).astype("float32"),
            "y": rng.rand(B, 1).astype("float32")}
    losses = [float(np.asarray(exe.run(feed=feed,
                                       fetch_list=[loss])[0]).ravel()[0])
              for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_static_rnn_memory_init_and_errors(rng):
    T, B, H = 3, 2, 4
    x = fluid.layers.data(name="x", shape=[B, H],
                          append_batch_size=False, dtype="float32")
    x.shape = (T, B, H)
    init = fluid.layers.data(name="h0", shape=[B, H],
                             append_batch_size=False, dtype="float32")

    rnn = fluid.layers.StaticRNN()
    with rnn.step():
        w = rnn.step_input(x)
        prev = rnn.memory(init=init)
        nxt = fluid.layers.elementwise_add(w, prev)
        rnn.update_memory(prev, nxt)
        rnn.step_output(nxt)
    out = rnn()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    x_np = rng.rand(T, B, H).astype("float32")
    h0 = rng.rand(B, H).astype("float32")
    got = np.asarray(exe.run(feed={"x": x_np, "h0": h0},
                             fetch_list=[out])[0])
    want = np.stack([h0 + x_np[:t + 1].sum(0) for t in range(T)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    rnn2 = fluid.layers.StaticRNN()
    with pytest.raises(ValueError, match="step_input"):
        rnn2.step_input(x)  # outside step()
