"""ZeRO-1 sharded weight update (FLAGS_tpu_sharded_weight_update) —
parity vs the replicated update on the virtual CPU mesh, per-collective
byte evidence, sharded-state donation/HBM audit, off-by-flag HLO, the
hapi evaluate/predict deferral, the map-style DataLoader device buffer,
and cross-rank checkpoint-step agreement.

Reference: "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (Xu et al., 2020); the plan/trace machinery is
paddle_tpu/parallel/sharded_update.py.
"""
import os
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.utils.flags import set_flags


@pytest.fixture(autouse=True)
def _restore_flag():
    from paddle_tpu.utils.flags import get_flag

    old = get_flag("FLAGS_tpu_sharded_weight_update", True)
    yield
    set_flags({"FLAGS_tpu_sharded_weight_update": old})


def _fresh():
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def _mlp_loss(uneven=True):
    framework.default_main_program().random_seed = 1234
    framework.default_startup_program().random_seed = 1234
    img = fluid.layers.data(name="img", shape=[32], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    # size 31: not divisible by any mesh size — exercises flat-buffer
    # padding in every sharded tensor
    h = fluid.layers.fc(input=img, size=31 if uneven else 32, act="relu")
    logits = fluid.layers.fc(input=h, size=4)
    return fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))


def _batch():
    r = np.random.RandomState(0)
    return (r.rand(64, 32).astype("float32"),
            r.randint(0, 4, (64, 1)).astype("int64"))


def _train(opt_fn, flag, ndev=8, clip=False, reg=False,
           steps=8, want_plan=True):
    """Losses of `steps` steps of the MLP under with_data_parallel on an
    ndev-device mesh; returns (losses, executor, program, plan)."""
    import jax

    _fresh()
    set_flags({"FLAGS_tpu_sharded_weight_update": flag})
    x, y = _batch()
    with framework.unique_name_guard():
        loss = _mlp_loss()
        if clip:
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(0.5))
        kwargs = {}
        if reg:
            from paddle_tpu.fluid.regularizer import L2Decay

            kwargs["regularization"] = L2Decay(1e-3)
        opt_fn(**kwargs).minimize(loss)
        fluid.clip._clip_attr.clear()
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        if ndev != 8:
            from jax.sharding import Mesh

            prog._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        losses = [float(exe.run(prog, feed={"img": x, "label": y},
                                fetch_list=[loss])[0].mean())
                  for _ in range(steps)]
        plan = getattr(prog, "_shard_plan", None)
    if flag and want_plan:
        assert plan is not None, "sharded update did not engage"
    if not flag:
        assert plan is None
    return losses, exe, prog, loss, plan


O = fluid.optimizer


@pytest.mark.parametrize("name,opt_fn,kw,exact", [
    ("adam_clip", lambda **k: O.AdamOptimizer(learning_rate=0.01, **k),
     dict(clip=True), True),
    ("adam_reg",
     lambda **k: O.AdamOptimizer(learning_rate=0.01, **k),
     dict(reg=True), True),
    ("momentum_4dev",
     lambda **k: O.MomentumOptimizer(learning_rate=0.1, momentum=0.9,
                                     **k), dict(ndev=4), True),
    ("sgd_2dev", lambda **k: O.SGDOptimizer(learning_rate=0.1, **k),
     dict(ndev=2), True),
    ("lamb_clip_4dev",
     lambda **k: O.LambOptimizer(learning_rate=0.01, **k),
     dict(ndev=4, clip=True), False),
])
def test_sharded_vs_replicated_parity(name, opt_fn, kw, exact):
    """Sharded == replicated for Adam (+global-norm clip, +L2 reg),
    Momentum, SGD and LAMB (trust-ratio psum) across
    2/4/8-device meshes with an uneven (31-wide) parameter. SGD/
    Momentum/Adam are bit-identical; LAMB's psum'd norms match within
    fp32 reduction-order tolerance."""
    l_rep, *_ = _train(opt_fn, False, **kw)
    l_sh, *_ = _train(opt_fn, True, **kw)
    if exact:
        assert l_rep == l_sh, (name, l_rep, l_sh)
    else:
        np.testing.assert_allclose(l_rep, l_sh, rtol=2e-5, atol=1e-6)


def test_off_by_flag_reproduces_replicated_hlo():
    """FLAGS_tpu_sharded_weight_update=0 must lower to today's program:
    grad allreduce, NO reduce_scatter / all_gather anywhere. =1 swaps
    the grad sync to reduce_scatter + a param all_gather."""
    x, y = _batch()

    def text(flag):
        _, exe, prog, loss, _ = _train(
            lambda **k: O.AdamOptimizer(learning_rate=0.01, **k), flag,
            steps=1)
        got = exe._cached_lowerable(prog, {"img": x, "label": y},
                                    [loss], None)
        return got[1].as_text()

    t_off = text(False)
    t_on = text(True)
    assert "reduce_scatter" not in t_off and "all_gather" not in t_off
    assert "all_reduce" in t_off
    assert "reduce_scatter" in t_on and "all_gather" in t_on


def test_collective_bytes_grad_leg_halved():
    """Ring-modeled ICI bytes from the StableHLO census: the sharded
    grad exchange (reduce_scatter) costs ~half the replicated
    allreduce; the total stays ~equal (the other half moved to the
    param all_gather, off the gradient critical path)."""
    x, y = _batch()

    def census(flag):
        _, exe, prog, loss, _ = _train(
            lambda **k: O.AdamOptimizer(learning_rate=0.01, **k), flag,
            steps=1)
        return exe.collective_report(prog, feed={"img": x, "label": y},
                                     fetch_list=[loss])

    off = census(False)
    on = census(True)
    assert off["all_reduce"]["ici_bytes"] > 0
    assert "all_reduce" not in on
    rs = on["reduce_scatter"]["ici_bytes"]
    # ~half, allowing the 1/N padding overhead of uneven params
    assert rs <= 0.6 * off["all_reduce"]["ici_bytes"], (off, on)
    assert on["all_gather"]["ici_bytes"] > 0


def test_sharded_state_memory_and_donation():
    """donation_report audits the ZeRO-1 shard buffers: per-replica
    optimizer state ~1/N of the replicated footprint (within padding),
    and the sharded buffers still alias (donated) through the step."""
    x, y = _batch()
    _, exe, prog, loss, plan = _train(
        lambda **k: O.AdamOptimizer(learning_rate=0.01, **k), True,
        steps=2)
    rep = exe.donation_report(prog, feed={"img": x, "label": y},
                              fetch_list=[loss])
    assert rep is not None
    assert rep["aliases_state"], rep
    assert rep["opt_state_sharded_vars"] == len(plan.sharded_state) > 0
    logical = rep["opt_state_logical_bytes"]
    per_rep = rep["opt_state_per_replica_bytes"]
    # 8-way mesh: 1/8 plus padding (uneven 31-wide params pad each
    # flat buffer to a multiple of 8)
    assert per_rep < 0.2 * logical, rep

    # scope holds flat dp-sharded buffers between steps
    from paddle_tpu.core.scope import global_scope

    name, info = next(iter(plan.sharded_state.items()))
    v = global_scope().find_var(name)
    assert tuple(v.shape) == (info.padded,)
    assert "dp" in str(getattr(v, "sharding", ""))


def test_checkpoint_roundtrip_with_sharded_state(tmp_path):
    """save_persistables unshards optimizer state to logical shapes;
    a load + continued training matches an uninterrupted run."""
    x, y = _batch()
    adam = lambda **k: O.AdamOptimizer(learning_rate=0.01, **k)  # noqa
    # uninterrupted: 4 steps
    l_ref, *_ = _train(adam, True, steps=4)
    # interrupted: 2 steps, save, reload into a fresh scope, 2 more
    _, exe, prog, loss, plan = _train(adam, True, steps=2)
    from paddle_tpu.core.scope import global_scope

    fluid.io.save_persistables(exe, str(tmp_path), main_program=prog)
    name, info = next(iter(plan.sharded_state.items()))
    saved = np.load(os.path.join(str(tmp_path),
                                 name.replace("/", "%2F") + ".npy"))
    assert tuple(saved.shape) == info.shape, \
        "sharded state must persist at its LOGICAL shape"
    fluid.io.load_persistables(exe, str(tmp_path), main_program=prog)
    l_cont = [float(exe.run(prog, feed={"img": x, "label": y},
                            fetch_list=[loss])[0].mean())
              for _ in range(2)]
    np.testing.assert_allclose(l_ref[2:], l_cont, rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_bert_tiny_parity_20_steps():
    """Acceptance: BERT-tiny + Adam and LAMB on the mesh, 20 steps,
    global-norm clipping — sharded losses match replicated within fp32
    tolerance."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from __graft_entry__ import _bert_feed
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    seq_len, batch = 32, 16

    def run(opt_fn, flag):
        _fresh()
        set_flags({"FLAGS_tpu_sharded_weight_update": flag})
        with framework.unique_name_guard():
            framework.default_main_program().random_seed = 99
            framework.default_startup_program().random_seed = 99
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, seq_len, is_test=False)
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(1.0))
            opt_fn().minimize(total)
            fluid.clip._clip_attr.clear()
            prog = fluid.default_main_program()
            fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=total.name)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            feed = _bert_feed(cfg, batch, seq_len)
            out = [float(exe.run(prog, feed=feed,
                                 fetch_list=[total])[0].mean())
                   for _ in range(20)]
            assert (getattr(prog, "_shard_plan", None)
                    is not None) == flag
        return out

    for opt_fn in (lambda: O.AdamOptimizer(learning_rate=1e-3),
                   lambda: O.LambOptimizer(learning_rate=1e-3)):
        l_rep = run(opt_fn, False)
        l_sh = run(opt_fn, True)
        np.testing.assert_allclose(l_rep, l_sh, rtol=5e-5, atol=1e-5)


def test_single_element_param_stays_replica_consistent():
    """Regression: a (1,)-shaped parameter (scalar output head bias)
    must follow the SHARD layout — slot identity, not tensor size,
    decides. The size heuristic this replaces updated it on device 0
    only, silently diverging replicas (caught by test_elastic's
    resume)."""
    import jax

    _fresh()
    set_flags({"FLAGS_tpu_sharded_weight_update": True})
    from paddle_tpu import fleet
    from paddle_tpu.core.scope import global_scope

    r = np.random.RandomState(0)
    x = r.rand(16, 8).astype("float32")
    y = r.rand(16, 1).astype("float32")
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 11
        framework.default_startup_program().random_seed = 11
        xv = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        yv = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        pred = fluid.layers.fc(input=xv, size=1)  # (1,)-shaped bias
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - yv))
        fleet.init()
        fleet.distributed_optimizer(
            O.SGDOptimizer(learning_rate=0.1)).minimize(loss)
        prog = fluid.default_main_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        for _ in range(3):
            exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss])
        assert getattr(prog, "_shard_plan", None) is not None
        for v in prog.list_vars():
            if not v.persistable:
                continue
            val = global_scope().find_var(v.name)
            shards = [np.asarray(s.data)
                      for s in getattr(val, "addressable_shards", [])]
            for sh in shards[1:]:
                np.testing.assert_array_equal(shards[0], sh, err_msg=v.name)


def test_unsupported_program_falls_back():
    """An optimizer op the planner can't shard (dpsgd: per-element rng
    noise has no flat-shard rule) keeps the replicated update rather
    than failing. (Gradient merge — the old exemplar here — is now
    planned and sharded: tests/test_comm_overlap.py.)"""
    _fresh()
    set_flags({"FLAGS_tpu_sharded_weight_update": True})
    x, y = _batch()
    with framework.unique_name_guard():
        loss = _mlp_loss()
        opt = O.DpsgdOptimizer(learning_rate=0.1)
        opt.minimize(loss)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out = exe.run(prog, feed={"img": x, "label": y},
                      fetch_list=[loss])[0]
        assert getattr(prog, "_shard_plan", None) is None
        assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# hapi evaluate/predict deferral (satellite)
# ---------------------------------------------------------------------------

def _hapi_model():
    from paddle_tpu.fluid.dygraph import Linear
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.hapi.metrics import Accuracy

    net = Linear(16, 4)
    m = Model(net)
    m.prepare(
        fluid.optimizer.SGDOptimizer(
            learning_rate=0.1, parameter_list=net.parameters()),
        loss_function=lambda pred, label: fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(pred, label)),
        metrics=Accuracy(topk=(1,)))
    return m


class _EvalSet:
    def __init__(self, n=40):
        r = np.random.RandomState(3)
        self.x = r.rand(n, 16).astype("float32")
        self.y = r.randint(0, 4, (n, 1)).astype("int64")

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def test_hapi_evaluate_deferred_parity_and_sync_count():
    """evaluate() defers host syncs to every log_freq steps (ROADMAP
    open item): results identical to the synchronous path, and the
    sync event fires <= ceil(steps/log_freq) + 1 times."""
    from paddle_tpu.fluid import profiler
    from paddle_tpu.utils.flags import get_flag

    data = _EvalSet(40)
    m = _hapi_model()
    set_flags({"FLAGS_tpu_deferred_fetch": False})
    r_sync = m.evaluate(data, batch_size=8, verbose=0)
    set_flags({"FLAGS_tpu_deferred_fetch": True})
    profiler.reset_profiler()
    r_defer = m.evaluate(data, batch_size=8, log_freq=2, verbose=0)
    syncs = profiler.event_count("hapi/loss_sync")
    assert 0 < syncs <= 4, syncs  # 5 steps, log_freq 2 -> <= 3 (+tail)
    assert r_sync.keys() == r_defer.keys()
    np.testing.assert_allclose(r_sync["loss"], r_defer["loss"],
                               rtol=1e-6)
    assert r_sync["acc"] == r_defer["acc"]


def test_hapi_predict_deferred_parity():
    data = _EvalSet(40)
    m = _hapi_model()
    set_flags({"FLAGS_tpu_deferred_fetch": False})
    p_sync = m.predict(data, batch_size=8, stack_outputs=True)
    set_flags({"FLAGS_tpu_deferred_fetch": True})
    p_defer = m.predict(data, batch_size=8, stack_outputs=True)
    assert len(p_sync) == len(p_defer) == 1
    np.testing.assert_array_equal(p_sync[0], p_defer[0])


def test_map_style_dataloader_device_buffer():
    """Map-style DataLoader with use_buffer_reader + an accelerator
    place yields pre-put jax arrays (reader/prefetcher.py), and the
    dygraph/hapi loops consume them without a host round-trip."""
    import jax

    from paddle_tpu.core.place import TPUPlace
    from paddle_tpu.fluid.reader import DataLoader

    data = _EvalSet(32)
    host = DataLoader(data, batch_size=8, places=None)
    dev = DataLoader(data, batch_size=8, places=[TPUPlace()])
    host_batches = list(host)
    dev_batches = list(dev)
    assert len(host_batches) == len(dev_batches) == 4
    for hb, db in zip(host_batches, dev_batches):
        for h, d in zip(hb, db):
            assert isinstance(d, jax.Array), type(d)
            np.testing.assert_array_equal(np.asarray(h), np.asarray(d))
    # off switch: host numpy contract preserved
    off = DataLoader(data, batch_size=8, places=[TPUPlace()],
                     use_buffer_reader=False)
    assert isinstance(next(iter(off))[0], np.ndarray)
    # hapi fit consumes the pre-put batches (device passthrough)
    m = _hapi_model()
    hist = m.fit(dev, batch_size=8, epochs=1, verbose=0)
    assert np.isfinite(hist[-1]["loss"])


# ---------------------------------------------------------------------------
# cross-rank checkpoint-step agreement (satellite)
# ---------------------------------------------------------------------------

def _two_rank_group():
    import socket

    from paddle_tpu.distributed.host_collectives import \
        HostCollectiveGroup

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ep = "127.0.0.1:%d" % port
    out = {}

    def mk(rank):
        out[rank] = HostCollectiveGroup(rank, 2, ep, timeout_s=60,
                                        heartbeat_s=0)

    t = threading.Thread(target=mk, args=(1,), daemon=True)
    t.start()
    mk(0)
    t.join(timeout=30)
    return out[0], out[1]


def test_fluid_checkpoint_agreement_on_truncated_rank(tmp_path):
    """Fault injection: rank 1's NEWEST checkpoint dir is truncated.
    Without agreement each rank would pick a different step (silent
    divergence); with the allreduce-min protocol both ranks land on the
    newest step intact EVERYWHERE."""
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid import checkpoint as ckpt

    _fresh()
    with framework.unique_name_guard():
        loss = _mlp_loss()
        O.SGDOptimizer(learning_rate=0.1).minimize(loss)
        prog = fluid.default_main_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        x, y = _batch()
        roots = [str(tmp_path / "rank0"), str(tmp_path / "rank1")]
        for step in range(2):
            exe.run(prog, feed={"img": x, "label": y},
                    fetch_list=[loss])
            for root in roots:
                ckpt.save_checkpoint(
                    exe, root, ckpt.TrainStatus(epoch_no=step),
                    main_program=prog)
        # truncate rank 1's newest published dir's payload
        latest = ckpt.latest_checkpoint_dir(roots[1])
        payload = os.path.join(latest, "persistables.pkl")
        with open(payload, "wb") as f:
            f.write(b"\x00")

        g0, g1 = _two_rank_group()
        res = {}

        def load(rank, grp):
            res[rank] = ckpt.load_checkpoint(
                None, roots[rank], main_program=prog, scope=Scope(),
                group=grp)

        t = threading.Thread(target=load, args=(1, g1), daemon=True)
        t.start()
        load(0, g0)
        t.join(timeout=60)
        assert not t.is_alive()
        # both ranks agreed on the OLDER, everywhere-intact step —
        # rank 0's own newest dir was fine, yet it must not use it
        assert res[0].epoch_no == res[1].epoch_no == 0
        g1.shutdown()
        g0.shutdown()


def test_sharded_manager_agreement_on_truncated_rank(tmp_path):
    """Same protocol through ShardedCheckpointManager.restore(group=):
    one rank's newest orbax step truncated -> both agree on step 1."""
    import glob

    import jax.numpy as jnp

    from paddle_tpu.distributed import ShardedCheckpointManager

    trees = {}
    mgrs = {}
    for rank in (0, 1):
        d = str(tmp_path / ("r%d" % rank))
        mgr = ShardedCheckpointManager(d, max_to_keep=3)
        tree = {"w": jnp.arange(4.0) + rank}
        for step in (1, 2):
            mgr.save(step, dict(tree, step=jnp.int32(step)))
        trees[rank], mgrs[rank] = tree, mgr
    # truncate rank 1's step 2
    step_dir = str(tmp_path / "r1" / "2")
    files = [p for p in glob.glob(os.path.join(step_dir, "**"),
                                  recursive=True) if os.path.isfile(p)]
    assert files
    for p in files:
        open(p, "w").close()

    g0, g1 = _two_rank_group()
    res = {}

    def restore(rank, grp):
        res[rank] = mgrs[rank].restore(
            template=dict(trees[rank], step=jnp.int32(0)), group=grp)

    t = threading.Thread(target=restore, args=(1, g1), daemon=True)
    t.start()
    restore(0, g0)
    t.join(timeout=120)
    assert not t.is_alive()
    assert int(res[0]["step"]) == int(res[1]["step"]) == 1
    for mgr in mgrs.values():
        mgr.close()
    g1.shutdown()
    g0.shutdown()
