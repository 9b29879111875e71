"""Bucketed, backward-ordered gradient collectives
(FLAGS_tpu_comm_bucket_mb) — bucket planning, parity vs the
single-buffer (cap=0) lowering across bucket-size extremes, the
sharded gradient-merge path, the optimized-HLO overlap audit, the
per-bucket census/donation attribution, and the launch supervisor's
PADDLE_CKPT_AGREE default.

References: Kumar et al., arXiv:1909.09756 (overlapping gradient
summation with backprop at MLPerf scale); Wang et al., arXiv:2011.03641
(hiding inter-core traffic behind compute). Machinery:
paddle_tpu/parallel/sharded_update.py (plan_buckets,
bucket_reduce_scatter), fluid/lowering.py (collective_overlap_audit,
_run_gradient_merge), fluid/backward.py (grad_topo).
"""
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.utils.flags import get_flag, set_flags

O = fluid.optimizer


@pytest.fixture(autouse=True)
def _restore_flags():
    old = {k: get_flag(k) for k in ("FLAGS_tpu_sharded_weight_update",
                                    "FLAGS_tpu_comm_bucket_mb")}
    yield
    set_flags(old)


def _fresh():
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def _batch(width=32):
    r = np.random.RandomState(0)
    return (r.rand(64, width).astype("float32"),
            r.randint(0, 4, (64, 1)).astype("int64"))


def _mlp_loss(width=32, hidden=31, layers=1):
    framework.default_main_program().random_seed = 1234
    framework.default_startup_program().random_seed = 1234
    img = fluid.layers.data(name="img", shape=[width], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = img
    for _ in range(layers):
        h = fluid.layers.fc(input=h, size=hidden, act="relu")
    logits = fluid.layers.fc(input=h, size=4)
    return fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))


def _train(opt_fn, bucket_mb, ndev=8, steps=3, clip=False, width=32,
           hidden=31, layers=1, gm_k=None, sharded=True):
    """Losses over `steps` identical-feed steps; returns
    (losses, exe, prog, loss, plan)."""
    import jax

    _fresh()
    set_flags({"FLAGS_tpu_sharded_weight_update": sharded,
               "FLAGS_tpu_comm_bucket_mb": bucket_mb})
    x, y = _batch(width)
    with framework.unique_name_guard():
        loss = _mlp_loss(width, hidden, layers)
        if clip:
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(0.5))
        opt = opt_fn()
        if gm_k:
            opt = O.GradientMergeOptimizer(opt, k_steps=gm_k)
        opt.minimize(loss)
        fluid.clip._clip_attr.clear()
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        if ndev != 8:
            from jax.sharding import Mesh

            prog._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        losses = [exe.run(prog, feed={"img": x, "label": y},
                          fetch_list=[loss])[0].copy()
                  for _ in range(steps)]
        plan = getattr(prog, "_shard_plan", None)
    return losses, exe, prog, loss, plan


def _identical(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# bucket planning (unit level: synthetic ops, no tracing)
# ---------------------------------------------------------------------------

class _FakeVar:
    def __init__(self, shape, dtype="float32"):
        self.shape = shape
        self.dtype = dtype


class _FakeBlock:
    def __init__(self, vars_):
        self._vars = vars_

    def _find_var_recursive(self, name):
        return self._vars.get(name)


class _FakeOp:
    def __init__(self, params, grads):
        self.input_names = {"Grad": grads, "Param": params}
        self.output_names = {"ParamOut": params}


def _plan(entries, ndev, grad_topo, cap_bytes):
    """entries: [(param, shape, dtype)] -> plan_buckets result."""
    from paddle_tpu.parallel.sharded_update import plan_buckets

    block = _FakeBlock({p: _FakeVar(shape, dt)
                        for p, shape, dt in entries})
    ops = [_FakeOp([p], [p + "@GRAD"]) for p, _, _ in entries]
    return plan_buckets(ops, block, ndev, grad_topo, cap_bytes)


def test_plan_buckets_backward_production_order():
    """A param used LATER in the forward (larger grad_topo) gets its
    grad EARLIER in the vjp sweep — it must land in an earlier
    bucket."""
    buckets = _plan(
        [("a", (8,), "float32"), ("b", (8,), "float32"),
         ("c", (8,), "float32")],
        ndev=4, grad_topo={"a": 0, "b": 5, "c": 9}, cap_bytes=40)
    order = [e.grad for b in buckets for e in b.entries]
    assert order == ["c@GRAD", "b@GRAD", "a@GRAD"]
    # cap 40B: two 32B entries never share; one bucket per grad here
    assert [len(b.entries) for b in buckets] == [1, 1, 1]
    assert [b.index for b in buckets] == [0, 1, 2]


def test_plan_buckets_cap_and_oversize():
    """Greedy fill up to the cap; an oversize param gets its OWN
    bucket, still padded per-entry to 1/N divisibility."""
    buckets = _plan(
        [("big", (100,), "float32"),     # 400B > cap
         ("s1", (9,), "float32"), ("s2", (9,), "float32"),
         ("s3", (9,), "float32")],
        ndev=4, grad_topo={"big": 9, "s1": 8, "s2": 7, "s3": 6},
        cap_bytes=100)
    assert [sorted(e.param for e in b.entries) for b in buckets] == \
        [["big"], ["s1", "s2"], ["s3"]]
    big = buckets[0].entries[0]
    assert big.padded == 100  # 100 % 4 == 0: no pad needed
    s1 = buckets[1].entries[0]
    assert s1.padded == 12 and s1.numel == 9  # per-entry zero padding
    assert buckets[1].nbytes == 2 * 12 * 4


def test_plan_buckets_dtype_never_mixed():
    """fp32 and bf16 grads never share a bucket even when they fit."""
    buckets = _plan(
        [("f1", (8,), "float32"), ("h1", (8,), "bfloat16"),
         ("f2", (8,), "float32")],
        ndev=4, grad_topo={"f1": 9, "h1": 8, "f2": 7},
        cap_bytes=1 << 20)
    assert [str(b.dtype) for b in buckets] == \
        ["float32", "bfloat16", "float32"]
    assert [len(b.entries) for b in buckets] == [1, 1, 1]


# ---------------------------------------------------------------------------
# parity: bucketed == single-buffer (cap=0), incl. the extremes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,opt_fn,ndev", [
    ("sgd_2dev", lambda: O.SGDOptimizer(learning_rate=0.1), 2),
    ("momentum_4dev",
     lambda: O.MomentumOptimizer(learning_rate=0.1, momentum=0.9), 4),
    ("adam_8dev", lambda: O.AdamOptimizer(learning_rate=0.01), 8),
])
def test_bucketed_bit_identical_to_single_buffer(name, opt_fn, ndev):
    """SGD/Momentum/Adam: bucketed runs are BIT-identical to the cap=0
    per-variable lowering at both extremes — one bucket holding every
    grad (cap huge) and one bucket per param (cap ~ 1 byte)."""
    base, *_ , p0 = _train(opt_fn, 0.0, ndev=ndev)
    assert p0 is not None and not p0.buckets
    for mb, want in ((1000.0, 1), (1e-5, None)):
        got, _, _, _, plan = _train(opt_fn, mb, ndev=ndev)
        assert plan is not None and plan.buckets
        if want is not None:
            assert len(plan.buckets) == want
        else:  # bucket-per-param extreme
            assert len(plan.buckets) == \
                sum(len(b.entries) for b in plan.buckets)
        assert _identical(base, got), (name, mb)


def test_bucketed_adam_clip_parity_and_padding_zeroed():
    """Global-norm clipping on the bucketed path: bit-identical to
    cap=0, and the sharded moment buffers' zero-padding slots stay
    exactly zero across steps (shard-space elementwise ops re-zero
    them; the uneven 31-wide params pad every flat buffer)."""
    from paddle_tpu.core.scope import global_scope

    adam = lambda: O.AdamOptimizer(learning_rate=0.01)  # noqa: E731
    base, *_ = _train(adam, 0.0, clip=True)
    got, _, _, _, plan = _train(adam, 1000.0, clip=True)
    assert plan.buckets and plan.sharded_state
    assert _identical(base, got)
    padded_any = False
    for name, info in plan.sharded_state.items():
        buf = np.asarray(global_scope().find_var(name))
        assert buf.shape == (info.padded,)
        if info.padded > info.numel:
            padded_any = True
            np.testing.assert_array_equal(
                buf[info.numel:], 0.0, err_msg=name)
    assert padded_any, "test needs at least one padded state buffer"


def test_bucketed_lamb_tolerance():
    """LAMB's trust-ratio norms psum over shards: bucketed matches
    cap=0 within fp32 reduction-order tolerance."""
    lamb = lambda: O.LambOptimizer(learning_rate=0.01)  # noqa: E731
    base, *_ = _train(lamb, 0.0, ndev=4)
    got, *_ = _train(lamb, 0.002, ndev=4)
    np.testing.assert_allclose(
        [float(np.mean(v)) for v in base],
        [float(np.mean(v)) for v in got], rtol=2e-5, atol=1e-6)


def test_oversize_param_and_census_bucket_attribution():
    """A param bigger than the cap gets its own bucket; the census
    reduce_scatter count equals the bucket count (cap=0: one per grad),
    and collective/donation reports attribute bytes by SUMMING buckets."""
    adam = lambda: O.AdamOptimizer(learning_rate=0.01)  # noqa: E731
    # fc w: 64*63*4B ~ 15.8KB >> 4KB cap -> its own bucket
    kw = dict(width=64, hidden=63, layers=2, ndev=4, steps=2)
    x, y = _batch(64)
    base, *_ = _train(adam, 0.0, **kw)
    got, exe, prog, loss, plan = _train(adam, 0.004, **kw)
    assert _identical(base, got)
    cap = int(0.004 * (1 << 20))
    n_grads = sum(len(b.entries) for b in plan.buckets)
    assert len(plan.buckets) > 1
    oversize = [b for b in plan.buckets
                if len(b.entries) == 1 and b.nbytes > cap]
    assert oversize, "the 15.8KB fc weight must sit alone in a bucket"
    e = oversize[0].entries[0]
    assert e.padded % 4 == 0 and e.padded >= e.numel

    col = exe.collective_report(prog, feed={"img": x, "label": y},
                                fetch_list=[loss])
    assert col["reduce_scatter"]["count"] == len(plan.buckets)
    # bucket_cap_mb round-trips through the integer byte cap (4194 B)
    assert col["bucket_cap_mb"] == pytest.approx(0.004, rel=1e-3)
    assert len(col["buckets"]) == len(plan.buckets)
    assert col["bucket_bytes_total"] == \
        sum(b["bytes"] for b in col["buckets"])
    don = exe.donation_report(prog, feed={"img": x, "label": y},
                              fetch_list=[loss])
    assert don["grad_bucket_count"] == len(plan.buckets)
    assert don["grad_bucket_per_replica_bytes"] * 4 == \
        don["grad_bucket_logical_bytes"]

    # cap=0 attribution: per-variable collectives, no bucket keys
    _, exe0, prog0, loss0, _ = _train(adam, 0.0, **kw)
    col0 = exe0.collective_report(prog0, feed={"img": x, "label": y},
                                  fetch_list=[loss0])
    assert "buckets" not in col0
    assert col0["reduce_scatter"]["count"] == n_grads


# ---------------------------------------------------------------------------
# sharded gradient merge (satellite: ROADMAP open item)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_name,opt_fn", [
    ("sgd", lambda: O.SGDOptimizer(learning_rate=0.1)),
    ("adam", lambda: O.AdamOptimizer(learning_rate=0.01)),
])
def test_gradient_merge_sharded_parity(opt_name, opt_fn):
    """The once-per-k merged-grad sync now reduce-scatters (bucketed
    and not) inside the lax.cond apply branch: bit-identical to the
    replicated gradient-merge path, moments sharded across steps."""
    base, *_, p_off = _train(opt_fn, 0.0, gm_k=3, steps=6,
                             sharded=False)
    assert p_off is None
    for mb in (0.0, 1000.0):
        got, _, _, _, plan = _train(opt_fn, mb, gm_k=3, steps=6)
        assert plan is not None and plan.gradient_merge
        assert bool(plan.buckets) == (mb > 0)
        if opt_name == "adam":
            assert plan.sharded_state, \
                "gm must keep the ZeRO-1 sharded moments"
        assert _identical(base, got), (opt_name, mb)


def test_gradient_merge_collectives_visible_in_region_audit():
    """gm traces its bucketed merged-grad scatters inside the lax.cond
    branch (an HLO conditional region): the overlap audit must SEE
    them as region_collectives (fenced by construction) instead of
    reporting no collectives at all for the gm-sharded path."""
    sgd = lambda: O.SGDOptimizer(learning_rate=0.1)  # noqa: E731
    _, exe, prog, loss, plan = _train(sgd, 1000.0, gm_k=2, steps=2)
    assert plan is not None and plan.gradient_merge and plan.buckets
    x, y = _batch()
    rep = exe.overlap_report(prog, feed={"img": x, "label": y},
                             fetch_list=[loss])
    region = rep["region_collectives"]
    assert any(c["kind"] == "reduce-scatter" for c in region), region


# ---------------------------------------------------------------------------
# overlap audit (tentpole verification)
# ---------------------------------------------------------------------------

def _deep_mlp(bucket_mb, ndev=4):
    adam = lambda: O.AdamOptimizer(learning_rate=0.01)  # noqa: E731
    kw = dict(width=64, hidden=64, layers=4, ndev=ndev, steps=1)
    _, exe, prog, loss, plan = _train(adam, bucket_mb, **kw)
    x, y = _batch(64)
    rep = exe.overlap_report(prog, feed={"img": x, "label": y},
                             fetch_list=[loss])
    return rep, plan


def test_overlap_audit_buckets_straddle_single_buffer_fenced():
    """Tentpole verification. Bucketed: the first bucket's
    reduce-scatter is dataflow-ready BEFORE the final backward compute
    op (its ring transfer can overlap the remaining backward), and the
    buckets come in production order — earlier buckets leave MORE
    backward compute to hide behind. How MANY buckets straddle is the
    CPU scheduler's choice, not the program's: the audit reads a
    sequential XLA:CPU schedule, and jaxlib 0.9.0's scheduler sinks the
    pack+scatter of buckets 1..3 below the last backward op (ready
    184/191/203/218 against final backward 190) where the older one
    left two above it. Whether the transfers really hide behind
    compute is a chip measurement (collective time not overlapped, in
    a four-chip trace), not this count. cap=0 (the PR-3 lowering): under the collective-combiner
    model that governs real ICI, the combined grad exchange has
    NOTHING scheduled after it — the fully exposed gap bucketing
    removes."""
    # ~16KB per fc-weight grad; 20KB cap ~ one bucket per layer
    rep, plan = _deep_mlp(0.02)
    assert rep["is_scheduled"]
    assert rep["n_buckets"] == len(plan.buckets) >= 3
    rs = [c for c in rep["collectives"] if c["kind"] == "reduce-scatter"]
    assert len(rs) == len(plan.buckets)
    assert rep["overlappable_reduce_scatters"] >= 1
    after = [c["backward_after"] for c in sorted(rs,
                                                 key=lambda c: c["pos"])]
    assert after == sorted(after, reverse=True), \
        "production order: earlier buckets hide behind more backward"
    assert after[0] > 0 and after[-1] == 0

    rep0, plan0 = _deep_mlp(0.0)
    assert plan0 is not None and not plan0.buckets
    combined = rep0["combined"]["reduce-scatter"]
    assert combined["count"] > 1  # per-var collectives...
    assert combined["backward_after"] == 0  # ...combine into a fence
    assert rep0["n_backward_compute"] > 0


def test_cap_zero_reproduces_per_var_stablehlo():
    """FLAGS_tpu_comm_bucket_mb=0 lowers through the untouched
    per-variable path: no trace-level concatenate feeds the scatter
    (one reduce_scatter per optimizer grad), no bucket census keys."""
    adam = lambda: O.AdamOptimizer(learning_rate=0.01)  # noqa: E731
    _, exe, prog, loss, plan = _train(adam, 0.0, steps=1)
    x, y = _batch()
    got = exe._cached_lowerable(prog, {"img": x, "label": y}, [loss],
                                None)
    text = got[1].as_text()
    n_grads = len(plan.grad_names)
    assert text.count("reduce_scatter") == n_grads == 4
    # bucketed: exactly one scatter per bucket
    _, exe_b, prog_b, loss_b, plan_b = _train(adam, 1000.0, steps=1)
    got_b = exe_b._cached_lowerable(prog_b, {"img": x, "label": y},
                                    [loss_b], None)
    assert got_b[1].as_text().count("reduce_scatter") == \
        len(plan_b.buckets) == 1


# ---------------------------------------------------------------------------
# explicit-sync (fleet transpiler) pending-bucket path
# ---------------------------------------------------------------------------

def test_explicit_sync_buckets_parity():
    """Programs carrying their own c_allreduce_sum ops (fleet
    transpile_collective): each bucketed grad's allreduce holds pending
    until the bucket completes, then scatters as one collective —
    bit-identical to the per-variable explicit-sync lowering."""
    from paddle_tpu import fleet

    def run(bucket_mb):
        _fresh()
        set_flags({"FLAGS_tpu_sharded_weight_update": True,
                   "FLAGS_tpu_comm_bucket_mb": bucket_mb})
        r = np.random.RandomState(0)
        x = r.rand(16, 8).astype("float32")
        y = r.rand(16, 1).astype("float32")
        with framework.unique_name_guard():
            framework.default_main_program().random_seed = 11
            framework.default_startup_program().random_seed = 11
            xv = fluid.data(name="x", shape=[-1, 8], dtype="float32")
            yv = fluid.data(name="y", shape=[-1, 1], dtype="float32")
            pred = fluid.layers.fc(input=xv, size=3)
            pred = fluid.layers.fc(input=pred, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square(pred - yv))
            fleet.init()
            fleet.distributed_optimizer(
                O.SGDOptimizer(learning_rate=0.1)).minimize(loss)
            prog = fluid.default_main_program()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            losses = [exe.run(prog, feed={"x": x, "y": y},
                              fetch_list=[loss])[0].copy()
                      for _ in range(3)]
            plan = getattr(prog, "_shard_plan", None)
        return losses, plan

    base, p0 = run(0.0)
    assert p0 is not None and not p0.buckets
    got, plan = run(1000.0)
    assert plan is not None and plan.buckets
    if plan.explicit_sync:
        assert plan.rs_targets and plan.bucket_of
    assert _identical(base, got)


def test_gradient_merge_with_explicit_sync_now_planned():
    """ROADMAP carried-over gap, closed: a fleet-transpiled program
    (explicit c_allreduce_sum grad sync) under GradientMergeOptimizer
    now PLANS — the once-per-k merged-grad sync reduce-scatters through
    the pending-bucket path inside the lax.cond apply branch —
    bit-identical to the replicated gm+explicit path, per-var and
    bucketed."""
    from paddle_tpu import fleet

    def run(flag, bucket_mb):
        _fresh()
        set_flags({"FLAGS_tpu_sharded_weight_update": flag,
                   "FLAGS_tpu_comm_bucket_mb": bucket_mb})
        r = np.random.RandomState(0)
        x = r.rand(16, 8).astype("float32")
        y = r.rand(16, 1).astype("float32")
        with framework.unique_name_guard():
            framework.default_main_program().random_seed = 11
            framework.default_startup_program().random_seed = 11
            xv = fluid.data(name="x", shape=[-1, 8], dtype="float32")
            yv = fluid.data(name="y", shape=[-1, 1], dtype="float32")
            pred = fluid.layers.fc(input=xv, size=3)
            pred = fluid.layers.fc(input=pred, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square(pred - yv))
            fleet.init()
            gm = O.GradientMergeOptimizer(
                O.AdamOptimizer(learning_rate=0.05), k_steps=2)
            fleet.distributed_optimizer(gm).minimize(loss)
            prog = fluid.default_main_program()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            losses = [exe.run(prog, feed={"x": x, "y": y},
                              fetch_list=[loss])[0].copy()
                      for _ in range(6)]
            plan = getattr(prog, "_shard_plan", None)
        return losses, plan

    base, p_off = run(False, 0.0)
    assert p_off is None
    for mb in (0.0, 1000.0):
        got, plan = run(True, mb)
        assert plan is not None, "gm+explicit must plan now"
        assert plan.explicit_sync and plan.gradient_merge
        assert bool(plan.buckets) == (mb > 0)
        assert plan.sharded_state, "moments must stay sharded"
        assert _identical(base, got), mb


# ---------------------------------------------------------------------------
# launch supervisor: PADDLE_CKPT_AGREE default (satellite)
# ---------------------------------------------------------------------------

def test_launcher_defaults_ckpt_agree():
    from paddle_tpu.distributed.launch import _worker_env

    eps = ["127.0.0.1:6170", "127.0.0.1:6171"]
    env = _worker_env(eps, 1, 2, base_env={"PATH": "/bin"})
    assert env["PADDLE_CKPT_AGREE"] == "1"
    assert env["PADDLE_TRAINER_ID"] == "1"
    assert env["PADDLE_TRAINERS_NUM"] == "2"
    assert env["PADDLE_CURRENT_ENDPOINT"] == eps[1]
    assert env["PADDLE_TRAINER_ENDPOINTS"] == ",".join(eps)
    assert env["PADDLE_RESTART_NUM"] == "2"
    # explicit opt-out is respected, never overridden
    env0 = _worker_env(eps, 0, 0,
                       base_env={"PADDLE_CKPT_AGREE": "0"})
    assert env0["PADDLE_CKPT_AGREE"] == "0"


# ---------------------------------------------------------------------------
# acceptance: BERT-tiny (slow leg)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bert_tiny_bucketed_20_steps():
    """Acceptance: bucketed BERT-tiny Adam is bit-identical to the
    single-buffer path for 20 steps on the 8-dev mesh, and the audit
    shows >= 2 bucket reduce-scatters ready before the final backward
    compute op (vs a fenced combined exchange at cap=0)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from __graft_entry__ import _bert_feed
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    seq_len, batch = 32, 16

    def run(bucket_mb):
        _fresh()
        set_flags({"FLAGS_tpu_sharded_weight_update": True,
                   "FLAGS_tpu_comm_bucket_mb": bucket_mb})
        with framework.unique_name_guard():
            framework.default_main_program().random_seed = 99
            framework.default_startup_program().random_seed = 99
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, seq_len, is_test=False)
            O.AdamOptimizer(learning_rate=1e-3).minimize(total)
            prog = fluid.default_main_program()
            fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=total.name)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            feed = _bert_feed(cfg, batch, seq_len)
            out = [exe.run(prog, feed=feed,
                           fetch_list=[total])[0].copy()
                   for _ in range(20)]
            rep = exe.overlap_report(prog, feed=feed,
                                     fetch_list=[total])
        return out, rep

    base, rep0 = run(0.0)
    got, rep = run(0.25)
    assert _identical(base, got)
    assert rep["n_buckets"] >= 2
    assert rep["overlappable_reduce_scatters"] >= 1
    assert rep0["combined"]["reduce-scatter"]["backward_after"] == 0
