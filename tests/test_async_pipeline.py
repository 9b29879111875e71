"""Async step pipeline tests: device prefetcher (depth bound, sharding,
error propagation, drain), overlap microbenchmark (steady-state step
time ~= max(feed, compute), not the sum), LazyFetch / deferred fetches
(hapi fit syncs <= ceil(steps/log_freq) times per epoch), step-phase
counters, donation audit through the executor path, and loss parity —
prefetch + deferred fetch on vs off must match bit for bit, including
the multi-device `with_data_parallel` path (PS-mode parity rides in
test_dist_ps.py)."""
import math
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, profiler
from paddle_tpu.reader import prefetch_to_device
from paddle_tpu.reader.prefetcher import is_donatable


# ---------------------------------------------------------------------------
# prefetcher unit tests
# ---------------------------------------------------------------------------

def test_prefetch_yields_device_arrays_in_order():
    import jax

    pf = prefetch_to_device(
        ({"x": np.full((2, 2), i, np.float32)} for i in range(5)))
    got = list(pf)
    assert len(got) == 5
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)
        assert float(np.asarray(b["x"])[0, 0]) == float(i)


def test_prefetch_list_and_bare_array_batches():
    import jax

    lists = list(prefetch_to_device(
        ([np.zeros(2, np.float32), np.ones(3, np.float32)]
         for _ in range(2))))
    assert all(isinstance(v, jax.Array) for b in lists for v in b)
    bare = list(prefetch_to_device(
        (np.full(4, i, np.float32) for i in range(3))))
    assert [float(np.asarray(a)[0]) for a in bare] == [0.0, 1.0, 2.0]


def test_prefetch_depth_bound():
    """The producer never runs more than `size` batches (+1 in hand)
    ahead of the consumer."""
    size = 2
    produced = []
    consumed = [0]
    max_lead = [0]

    def gen():
        for i in range(12):
            produced.append(i)
            max_lead[0] = max(max_lead[0],
                              len(produced) - consumed[0])
            yield {"x": np.zeros(4, np.float32)}

    pf = prefetch_to_device(gen(), size=size)
    for _ in pf:
        time.sleep(0.01)  # slow consumer: the producer must wait
        consumed[0] += 1
    # one batch in the producer's hand + `size` queued + the one the
    # consumer holds
    assert max_lead[0] <= size + 2, max_lead[0]


def test_prefetch_sharding():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    target = NamedSharding(mesh, P("dp"))
    pf = prefetch_to_device(
        ({"x": np.zeros((8, 4), np.float32)} for _ in range(2)),
        sharding=target)
    for batch in pf:
        assert batch["x"].sharding == target
    # dict sharding: named feeds shard, unknown names go to the default
    pf = prefetch_to_device(
        ({"x": np.zeros((8, 4), np.float32),
          "y": np.zeros((2,), np.float32)} for _ in range(1)),
        sharding={"x": target})
    (batch,) = list(pf)
    assert batch["x"].sharding == target


def test_prefetch_producer_error_propagates():
    def gen():
        yield {"x": np.zeros(2, np.float32)}
        yield {"x": np.zeros(2, np.float32)}
        raise ValueError("boom in producer")

    pf = prefetch_to_device(gen())
    it = iter(pf)
    next(it)
    next(it)
    # the ORIGINAL exception type surfaces (typed except clauses around
    # the consuming loop keep working)
    with pytest.raises(ValueError, match="boom in producer"):
        next(it)
    assert not pf._thread.is_alive()


def test_prefetch_drain_on_early_exit():
    """Breaking out of the loop + close() stops the producer thread and
    drains queued buffers."""
    stopped_at = [0]

    def gen():
        for i in range(1000):
            stopped_at[0] = i
            yield {"x": np.zeros(16, np.float32)}

    pf = prefetch_to_device(gen(), size=3)
    for i, _ in enumerate(pf):
        if i == 2:
            break
    pf.close()
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()
    assert pf._q.qsize() == 0
    assert stopped_at[0] < 999  # producer did NOT run the whole epoch
    # context-manager form drains too
    with prefetch_to_device(gen(), size=2) as pf2:
        next(iter(pf2))
    pf2._thread.join(timeout=5.0)
    assert not pf2._thread.is_alive()


def test_prefetched_buffers_marked_donatable():
    (batch,) = list(prefetch_to_device(
        ({"x": np.zeros(4, np.float32)} for _ in range(1))))
    assert is_donatable(batch["x"])
    import jax.numpy as jnp

    assert not is_donatable(jnp.zeros(4))  # caller-owned arrays are not


def test_dataloader_double_buffer_extends_to_device():
    """DataLoader.from_generator(use_double_buffer=True) with an
    accelerator place yields batches already on device; with a CPU
    place it keeps the host-numpy contract."""
    import jax

    def reader():
        for i in range(3):
            yield [np.full((2, 4), i, np.float32)]

    x = fluid.layers.data(name="xdl", shape=[4], dtype="float32")
    dl = fluid.DataLoader.from_generator(feed_list=[x], capacity=4,
                                         use_double_buffer=True)
    dl.set_batch_generator(reader, places=fluid.TPUPlace())
    batches = list(dl)
    assert len(batches) == 3
    assert all(isinstance(b["xdl"], jax.Array) for b in batches)

    dl2 = fluid.DataLoader.from_generator(feed_list=[x], capacity=4,
                                          use_double_buffer=True)
    dl2.set_batch_generator(reader, places=fluid.CPUPlace())
    batches2 = list(dl2)
    assert all(isinstance(b["xdl"], np.ndarray) for b in batches2)


# ---------------------------------------------------------------------------
# overlap microbenchmark (acceptance: step ~= max(feed, compute))
# ---------------------------------------------------------------------------

def test_overlap_microbenchmark_speedup():
    """Synthetic sleep-based producer + compute, feed ~= compute: the
    async pipeline must approach max(feed, compute) per steady-state
    step, not feed + compute (assert >= 1.4x vs the serial loop)."""
    feed_s = compute_s = 0.04
    steps = 8

    def produce():
        for _ in range(steps):
            time.sleep(feed_s)  # host-side parse/augment/copy cost
            yield {"x": np.zeros((4, 4), np.float32)}

    def compute(batch):
        time.sleep(compute_s)  # stands in for device step time

    t0 = time.perf_counter()
    for batch in produce():
        compute(batch)
    serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    pf = prefetch_to_device(produce(), size=2)
    for batch in pf:
        compute(batch)
    overlapped = time.perf_counter() - t0

    speedup = serial / overlapped
    assert speedup >= 1.4, (serial, overlapped, speedup)
    # steady state ~= max(feed, compute): allow generous CI jitter but
    # stay well under the serial sum
    assert overlapped < steps * (feed_s + compute_s) * 0.75, overlapped


# ---------------------------------------------------------------------------
# executor integration: LazyFetch, phases, donation audit, parity
# ---------------------------------------------------------------------------

def _build_mlp(seed):
    framework.default_main_program().random_seed = seed
    framework.default_startup_program().random_seed = seed
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=32, act="relu")
    logits = fluid.layers.fc(input=h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    return loss


def _batches(n=6, batch=16):
    r = np.random.RandomState(3)
    for _ in range(n):
        yield {"x": r.rand(batch, 16).astype("float32"),
               "label": r.randint(0, 4, (batch, 1)).astype("int64")}


def _fresh_world():
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def test_lazy_fetch_handle():
    from paddle_tpu.fluid.executor import LazyFetch

    loss = _build_mlp(5)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = next(_batches(1))
    (h,) = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    assert isinstance(h, LazyFetch)
    assert h.shape == () or h.shape == (1,)
    import jax

    assert isinstance(h.value, jax.Array)
    a = np.asarray(h)  # __array__ materializes
    assert a.dtype == np.float32
    assert float(h) == float(np.ravel(a)[0])
    assert h.block_until_ready() is h


def test_step_phases_recorded():
    loss = _build_mlp(6)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    profiler.reset_step_phases()
    for feed in _batches(3):
        exe.run(feed=feed, fetch_list=[loss])
    s = profiler.step_phase_summary()
    assert s["steps"] == 3
    for k in ("feed_ms", "dispatch_ms", "sync_ms", "host_ms",
              "total_ms"):
        assert k in s and s[k] >= 0.0
    assert s["dispatch_ms"] > 0.0
    line = profiler.step_phase_line()
    assert "feed" in line and "dispatch" in line
    # the executor's own spans reach the chrome-trace buffer when
    # tracing is live, each at its real start
    profiler.reset_profiler()
    profiler._trace_enabled = True
    try:
        exe.run(feed=next(_batches(1)), fetch_list=[loss])
    finally:
        profiler._trace_enabled = False
    names = [n for n, *_ in profiler._trace_events]
    for phase in ("feed", "bind", "dispatch", "writeback", "sync"):
        assert "phase/" + phase in names, names
    starts = {n: ts for n, ts, *_ in reversed(profiler._trace_events)}
    assert starts["phase/feed"] < starts["phase/dispatch"] \
        < starts["phase/writeback"] < starts["phase/sync"]


def test_donation_audit_executor_path():
    """FLAGS_tpu_donate_buffers must actually alias params/opt-state in
    the executor path (compiled-memory analysis of the CACHED entry)."""
    loss = _build_mlp(7)
    fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = next(_batches(1))
    exe.run(feed=feed, fetch_list=[loss])
    rep = exe.donation_report(feed=feed, fetch_list=[loss])
    assert rep is not None
    assert rep["mut_bytes"] > 0
    assert rep["aliases_state"], rep
    assert rep["feed_donate"] is True


def test_step_memory_is_the_compilers_account_of_the_cached_step():
    loss = _build_mlp(7)
    fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = next(_batches(1))
    # nothing has run: there is no compiled step to account for
    assert exe.step_memory(feed=feed, fetch_list=[loss]) is None
    exe.run(feed=feed, fetch_list=[loss])
    mem = exe.step_memory(feed=feed, fetch_list=[loss])
    assert set(mem) == {"argument", "output", "alias", "temp",
                        "generated_code"}
    assert all(isinstance(v, int) and v >= 0 for v in mem.values())
    rep = exe.donation_report(feed=feed, fetch_list=[loss])
    # the state (weights + Adam moments) is among the arguments, and
    # donated: the same alias bytes the donation audit reads
    assert mem["argument"] >= rep["mut_bytes"] > 0
    assert mem["alias"] == rep["alias_bytes"]


def test_parity_prefetch_and_lazy_vs_sync():
    """MNIST-style loop: prefetch + deferred fetch on == synchronous
    path, loss for loss (same seed)."""
    loss = _build_mlp(1234)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    sync = [float(exe.run(feed=f, fetch_list=[loss])[0][0])
            for f in _batches()]

    _fresh_world()
    with framework.unique_name_guard():
        loss2 = _build_mlp(1234)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss2)
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(framework.default_startup_program())
        handles = []
        pf = prefetch_to_device(_batches(), size=2)
        for f in pf:
            handles.append(exe2.run(feed=f, fetch_list=[loss2],
                                    return_numpy=False)[0])
        # ONE deferred sync at the end materializes every step's loss
        async_losses = [float(h) for h in handles]
    assert sync == async_losses, (sync, async_losses)


def test_parity_with_data_parallel():
    """Multi-device path: with_data_parallel + prefetched pre-sharded
    feeds == the same compiled program fed from host numpy."""
    loss = _build_mlp(77)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    cp = fluid.CompiledProgram(
        framework.default_main_program()).with_data_parallel(
            loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    base = [float(exe.run(cp, feed=f, fetch_list=[loss])[0].mean())
            for f in _batches(5, batch=16)]

    _fresh_world()
    with framework.unique_name_guard():
        loss2 = _build_mlp(77)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss2)
        cp2 = fluid.CompiledProgram(
            framework.default_main_program()).with_data_parallel(
                loss_name=loss2.name)
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(framework.default_startup_program())
        shard = exe2.feed_sharding(cp2)
        assert shard is not None  # 8-device mesh -> dp sharding
        pf = prefetch_to_device(_batches(5, batch=16), size=2,
                                sharding=shard)
        pre = []
        for f in pf:
            out = exe2.run(cp2, feed=f, fetch_list=[loss2],
                           return_numpy=False)[0]
            pre.append(float(np.asarray(out).mean()))
    assert base == pre, (base, pre)


def test_prefetch_uneven_tail_batch_falls_back_unsharded():
    """A tail batch whose rows don't divide the mesh must not crash in
    the producer: it lands unsharded and the executor's tail bucketing
    replicates it to the cached divisible batch (host-path parity)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    target = NamedSharding(mesh, P("dp"))

    def gen():
        yield {"x": np.zeros((16, 4), np.float32)}  # divisible by 8
        yield {"x": np.zeros((6, 4), np.float32)}   # uneven tail

    got = list(prefetch_to_device(gen(), sharding=target))
    assert got[0]["x"].sharding == target
    assert got[1]["x"].shape == (6, 4)  # landed, just unsharded


def test_trainer_prefetch_parity():
    """train_from_dataset (device-prefetching feeder) == a plain
    synchronous exe.run loop over the same dataset."""
    from paddle_tpu.fluid.dataset import InMemoryDataset

    r = np.random.RandomState(9)
    xs = r.rand(64, 16).astype("float32")
    ys = r.randint(0, 4, (64, 1)).astype("int64")

    loss = _build_mlp(55)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    sync_losses = []
    for i in range(0, 64, 16):
        out = exe.run(feed={"x": xs[i:i + 16], "label": ys[i:i + 16]},
                      fetch_list=[loss])
        sync_losses.append(float(np.asarray(out[0]).reshape(-1)[0]))

    _fresh_world()
    with framework.unique_name_guard():
        loss2 = _build_mlp(55)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss2)
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(framework.default_startup_program())

        class _DS:
            def _iter_batches(self):
                for i in range(0, 64, 16):
                    yield {"x": xs[i:i + 16],
                           "label": ys[i:i + 16]}

        final = exe2.train_from_dataset(
            program=framework.default_main_program(), dataset=_DS(),
            fetch_list=[loss2], print_period=0)
    assert float(np.ravel(final[0])[0]) == sync_losses[-1], \
        (final, sync_losses)


# ---------------------------------------------------------------------------
# hapi deferred fetches
# ---------------------------------------------------------------------------

def _hapi_model():
    import paddle_tpu as paddle
    from paddle_tpu.hapi import Model

    class FlattenLinear(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(64, 10)

        def forward(self, x):
            return self.fc(x.reshape((x.shape[0], 64)))

    m = Model(paddle.nn.Sequential(FlattenLinear()))
    m.prepare(
        optimizer=paddle.fluid.optimizer.AdamOptimizer(
            learning_rate=1e-2),
        loss_function=paddle.nn.CrossEntropyLoss())
    return m


def test_hapi_fit_sync_count_bounded():
    """Deferred fetches: fit performs <= ceil(steps/log_freq) host
    syncs per epoch (counted at the profiler's hapi/loss_sync event)."""
    from paddle_tpu.hapi.datasets import SyntheticImages

    np.random.seed(1234)
    m = _hapi_model()
    data = SyntheticImages(num_samples=96)
    batch_size, log_freq = 16, 4
    steps = 96 // batch_size
    profiler.reset_profiler()
    m.fit(data, batch_size=batch_size, epochs=1, verbose=0,
          shuffle=False, log_freq=log_freq)
    syncs = profiler.event_count("hapi/loss_sync")
    assert 0 < syncs <= math.ceil(steps / log_freq), \
        (syncs, steps, log_freq)


def test_hapi_fit_deferred_parity():
    """Same seed, deferred fetches on vs off: losses bit-identical."""
    from paddle_tpu.hapi.datasets import SyntheticImages
    from paddle_tpu.utils.flags import get_flag, set_flags

    def run():
        np.random.seed(99)
        m = _hapi_model()
        data = SyntheticImages(num_samples=64)
        return m.fit(data, batch_size=16, epochs=2, verbose=0,
                     shuffle=False, log_freq=3)

    old = get_flag("FLAGS_tpu_deferred_fetch", True)
    try:
        set_flags({"FLAGS_tpu_deferred_fetch": True})
        on = run()
        set_flags({"FLAGS_tpu_deferred_fetch": False})
        off = run()
    finally:
        set_flags({"FLAGS_tpu_deferred_fetch": old})
    assert [h["loss"] for h in on] == [h["loss"] for h in off]


def test_hapi_deferred_logs_fresh_for_callbacks():
    """A third-party callback reading logs['loss'] EVERY step must see
    fresh per-step values under deferral (reading forces the sync); it
    pays per-step syncs, the default callbacks keep the deferred
    cadence."""
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.hapi.datasets import SyntheticImages

    seen = []

    class Greedy(Callback):
        def on_train_batch_end(self, step, logs=None):
            seen.append(float(logs["loss"]))

    np.random.seed(5)
    m = _hapi_model()
    data = SyntheticImages(num_samples=64)
    hist = m.fit(data, batch_size=16, epochs=1, verbose=0,
                 shuffle=False, log_freq=3, callbacks=[Greedy()])
    assert len(seen) == 4  # one fresh loss per step
    assert len(set(seen)) > 1  # values actually change step to step
    assert seen[-1] == hist[-1]["loss"]


def test_hapi_fit_with_metrics_deferred():
    """Metrics still accumulate over EVERY step under deferral."""
    from paddle_tpu.hapi import Accuracy
    from paddle_tpu.hapi.datasets import SyntheticImages

    np.random.seed(7)
    m = _hapi_model()
    m._metrics = [Accuracy()]
    data = SyntheticImages(num_samples=64)
    hist = m.fit(data, batch_size=16, epochs=1, verbose=0,
                 shuffle=False, log_freq=3)
    assert "acc" in hist[-1]
    assert m._metrics[0].count == 64  # every sample counted
