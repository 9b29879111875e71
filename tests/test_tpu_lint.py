"""tpu-lint: the static SPMD verifier (paddle_tpu/analysis).

Seeded-defect fixtures — each checker must trip with the expected
severity AND op/var location (the checkers themselves are the
regression surface): a rank-divergent collective schedule (checker 1),
a read-after-donate (checker 2), a fetch inside a scan body (checker
3), a non-zeroed padding slot / tampered shard layout (checker 4), a
drifted dtype contract + silent fp64 promotion (checker 5). Plus: the
`FLAGS_tpu_static_checks` Executor compile-time hook (error raises
BEFORE dispatch, warn warns, clean programs pass under =error), the
`collective_byte_census` region coverage for switch_case /
conditional_block collectives, the `_block_host_op_kinds` any-depth
recursion contract, and the exemplar lint-regression harness
(tools/tpu_lint.py: BERT-tiny DP step — plain and bf16 AMP + ZeRO-2
bucketed masters — resnet scan, 2-rank sync-PS — zero errors,
standing). Checker 6 (zero2-lifetimes) seeded defects: a full-grad
read after scatter, a fetch of a scattered grad, an early-flushed
pending bucket; dtype-contract gains redundant-cast round-trip
fixtures and the AMP-policy suppressions.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis
from paddle_tpu.fluid import framework, lowering
from paddle_tpu.fluid.framework import Operator
from paddle_tpu.utils.flags import get_flag, set_flags

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_flags():
    keys = ("FLAGS_tpu_donate_buffers", "FLAGS_tpu_donate_feed_buffers",
            "FLAGS_tpu_static_checks", "FLAGS_tpu_sharded_weight_update",
            "FLAGS_tpu_comm_bucket_mb")
    old = {k: get_flag(k) for k in keys}
    yield
    set_flags(old)


def _mlp_loss(width=8, classes=4):
    img = fluid.layers.data(name="img", shape=[width], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=img, size=8, act="relu")
    logits = fluid.layers.fc(input=h, size=classes)
    return fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))


def _batch(width=8, n=16):
    r = np.random.RandomState(0)
    return {"img": r.rand(n, width).astype("float32"),
            "label": r.randint(0, 4, (n, 1)).astype("int64")}


def _bwd_idx(block):
    return next(i for i, op in enumerate(block.ops)
                if op.type == "backward")


# ---------------------------------------------------------------------------
# checker 1 — collective divergence
# ---------------------------------------------------------------------------

def _transpiled_program(extra_allreduce=False):
    from paddle_tpu.fleet import transpile_collective

    p, st = framework.Program(), framework.Program()
    with framework.program_guard(p, st):
        loss = _mlp_loss()
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    transpile_collective(p, nranks=2)
    if extra_allreduce:
        # the classic rank-conditional bug: one rank emits an extra
        # collective the others never post — a deadlock on real ICI
        g = p.global_block()
        g.ops.append(Operator(
            g, "c_allreduce_sum", inputs={"X": [loss.name]},
            outputs={"Out": [loss.name]}, attrs={"ring_id": 0}))
    return p, loss


def test_collective_schedule_records_transpiled_allreduces():
    prog, _ = _transpiled_program()
    sched = analysis.collective_schedule(prog)
    grads = [op for op in prog.global_block().ops
             if op.type == "c_allreduce_sum"]
    assert len(sched) == len(grads) >= 2
    assert all(r["kind"] == "c_allreduce_sum" and r["ring_id"] == 0
               for r in sched)
    # records carry the op location the finding would anchor to
    assert all(r["block_idx"] == 0 and r["op_idx"] >= 0 for r in sched)


def test_cross_rank_divergence_trips_with_location():
    p0, _ = _transpiled_program()
    p1, _ = _transpiled_program(extra_allreduce=True)
    fs = analysis.check_collective_divergence([p0, p1],
                                              labels=["r0", "r1"])
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.checker == "collective-divergence"
    assert f.rank == "r1" and f.op_type == "c_allreduce_sum"
    assert "diverges" in f.message
    # identical ranks: clean
    assert not analysis.check_collective_divergence([p0, p0])
    # strict-prefix direction (r1 is MISSING the extra collective):
    # the finding still names the diverging rank, not the reference
    fs = analysis.check_collective_divergence([p1, p0],
                                              labels=["r0", "r1"])
    assert len(fs) == 1 and fs[0].rank == "r1"
    assert "<end of schedule>" in fs[0].message


def _program_with_barrier(group_world, group_ranks, nranks=None):
    """A transpiled DP program plus one host-tier barrier whose
    HostCollectiveGroup membership lives in op attrs."""
    p, loss = _transpiled_program()
    g = p.global_block()
    attrs = {"ring_id": 0, "group_world": group_world,
             "group_ranks": list(group_ranks)}
    if nranks is not None:
        attrs["nranks"] = nranks
    g.ops.append(Operator(g, "barrier", inputs={"X": [loss.name]},
                          outputs={}, attrs=attrs))
    return p


def test_divergent_host_group_membership_trips():
    """Seeded defect: two ranks agree on every opcode/dtype/shape AND
    ring_id, but the HostCollectiveGroup behind the barrier spans 2
    ranks on one and 3 on the other — rank 0 waits forever on the
    phantom member. ring_id-only comparison called this clean (the
    carried-over false negative); membership modeling must trip it."""
    p0 = _program_with_barrier(2, [0, 1])
    p1 = _program_with_barrier(3, [0, 1, 2])
    fs = analysis.check_collective_divergence([p0, p1],
                                              labels=["r0", "r1"])
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.checker == "collective-divergence"
    assert f.rank == "r1" and f.op_type == "barrier"
    # identical membership: clean
    assert not analysis.check_collective_divergence(
        [p0, _program_with_barrier(2, [0, 1])])
    # membership signature is part of the schedule record itself
    rec = analysis.collective_schedule(p0)[-1]
    assert rec["kind"] == "barrier"
    assert ("world", 2) in rec["group"] and \
        ("ranks", (0, 1)) in rec["group"]


def test_divergent_nranks_membership_trips():
    """Same ring_id, different `nranks` on a sized device collective
    (a c_allgather transpiled against different world sizes) must
    diverge too; ops without any membership attrs keep the
    pre-existing ring_id-only behavior (group=None)."""
    p0 = _program_with_barrier(2, [0, 1], nranks=2)
    p1 = _program_with_barrier(2, [0, 1], nranks=4)
    fs = analysis.check_collective_divergence([p0, p1])
    assert len(fs) == 1 and fs[0].severity == "error"
    plain, _ = _transpiled_program()
    assert all(r["group"] is None
               for r in analysis.collective_schedule(plain))


def test_branch_collective_divergence():
    from paddle_tpu.fluid.layers.collective import _c_allreduce

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.reduce_mean(x) > 0.0
    fluid.layers.cond(pred,
                      lambda: _c_allreduce(x, reduce_type="sum"),
                      lambda: x)
    prog = fluid.default_main_program()
    fs = analysis.check_branch_uniformity(prog)
    assert len(fs) == 1 and fs[0].severity == "error"
    assert fs[0].op_type == "cond" and fs[0].block_idx == 0


def test_branch_collective_nesting_divergence():
    """A collective inside a while body in one branch repeats per
    iteration; a bare one in the other branch fires once — flattening
    the loop away would compare them equal (deadlock-class false
    negative), so the branch keys must keep the region nesting."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    prog = fluid.default_main_program()
    blk = prog.global_block()
    t_blk = prog._create_block()
    w_body = prog._create_block()
    w_body.append_op(type="c_allreduce_sum", inputs={"X": [x.name]},
                     outputs={"Out": [x.name]}, attrs={"ring_id": 0})
    prog._rollback()
    t_blk.append_op(type="while", inputs={}, outputs={},
                    attrs={"sub_block": w_body.idx})
    prog._rollback()
    f_blk = prog._create_block()
    f_blk.append_op(type="c_allreduce_sum", inputs={"X": [x.name]},
                    outputs={"Out": [x.name]}, attrs={"ring_id": 0})
    prog._rollback()
    blk.append_op(type="cond", inputs={}, outputs={},
                  attrs={"sub_block_t": t_blk.idx,
                         "sub_block_f": f_blk.idx})
    fs = analysis.check_branch_uniformity(prog)
    assert len(fs) == 1 and fs[0].severity == "error"
    assert fs[0].op_type == "cond"


def test_branch_identical_schedules_clean():
    from paddle_tpu.fluid.layers.collective import _c_allreduce

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.reduce_mean(x) > 0.0
    fluid.layers.cond(pred,
                      lambda: _c_allreduce(x, reduce_type="sum"),
                      lambda: _c_allreduce(x * 2.0, reduce_type="sum"))
    assert not analysis.check_branch_uniformity(
        fluid.default_main_program())


_HLO_A = """\
module {
  %0 = "stablehlo.all_reduce"(%arg0) ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
    "stablehlo.return"(%a) : (tensor<f32>) -> ()
  }) {replica_groups = dense<[[0, 1]]>} : (tensor<8xf32>) -> tensor<8xf32>
  %1 = "stablehlo.all_gather"(%0) {replica_groups = dense<[[0, 1]]>} : (tensor<4xf32>) -> tensor<8xf32>
}
"""


def test_hlo_schedule_and_cross_rank_divergence():
    sched = analysis.hlo_collective_schedule(_HLO_A)
    assert [r["kind"] for r in sched] == ["all_reduce", "all_gather"]
    assert sched[0]["type"] == "8xf32"
    assert "0, 1" in sched[0]["replica_groups"]
    assert not analysis.check_hlo_divergence([_HLO_A, _HLO_A])
    # rank 1 lowered to a different schedule (missing the gather)
    hlo_b = _HLO_A.replace("all_gather", "all_reduce")
    fs = analysis.check_hlo_divergence([_HLO_A, hlo_b],
                                       labels=["r0", "r1"])
    assert len(fs) == 1 and fs[0].severity == "error"


# ---------------------------------------------------------------------------
# checker 2 — donation use-after-donate
# ---------------------------------------------------------------------------

def _seeded_read_after_donate():
    """A fetch op holds the param's buffer BEFORE its in-place sgd
    rebind: under state-buffer donation the fetched array observes the
    updated bytes."""
    loss = _mlp_loss()
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    prog = fluid.default_main_program()
    blk = prog.global_block()
    w = prog.all_parameters()[0].name
    blk.ops.insert(_bwd_idx(blk) + 1, Operator(
        blk, "fetch", inputs={"X": [w]}, outputs={}, attrs={}))
    return prog, loss, w


def test_read_after_donate_trips_at_the_rebinding_op():
    prog, _, w = _seeded_read_after_donate()
    fs = analysis.check_donation_safety(prog)
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1
    f = errs[0]
    assert f.checker == "donation-safety" and f.var == w
    assert f.op_type == "sgd"  # located at the donated (in-place) use
    assert "read-after-donate" in f.message
    # donation off: the buffer is never aliased — no hazard
    set_flags({"FLAGS_tpu_donate_buffers": False})
    assert not analysis.check_donation_safety(prog)


def test_read_after_donate_inside_loop_body():
    """A fetch buried in a scan body holding a donated param that the
    body rebinds per iteration: iteration i's held buffer is clobbered
    by iteration i+1's in-place update — the walk must descend into
    sub-blocks (and replay loop bodies) to see it."""
    H = 4
    x = fluid.layers.data(name="x", shape=[H], dtype="float32")
    w = fluid.layers.create_parameter(shape=[H, H], dtype="float32",
                                      name="loop.w")
    h = fluid.layers.fc(x, size=H)
    scan = fluid.layers.Scan(n=2)
    with scan.block():
        sub = fluid.default_main_program().current_block()
        sub.append_op(type="fetch", inputs={"X": [w]}, outputs={},
                      attrs={})
        nh = fluid.layers.relu(fluid.layers.matmul(h, w))
        sub.append_op(type="scale", inputs={"X": [w]},
                      outputs={"Out": [w]}, attrs={"scale": 0.5})
        fluid.layers.assign(nh, output=h)
    fluid.layers.mean(h)
    prog = fluid.default_main_program()
    fs = analysis.check_donation_safety(prog)
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1 and errs[0].var == "loop.w"
    assert errs[0].op_type == "scale"  # the rebinding actor, in-loop
    # the location names the sub-block op, not the enclosing scan
    sub_idx = errs[0].block_idx
    assert sub_idx >= 1
    assert prog.block(sub_idx).ops[errs[0].op_idx].type == "scale"


def test_executor_hook_error_does_not_cache_the_bad_entry():
    """A caught-and-retried run must re-check, not cache-hit past the
    lint and dispatch the known-bad program."""
    prog, loss, _ = _seeded_read_after_donate()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    set_flags({"FLAGS_tpu_static_checks": "error"})
    for _ in range(2):  # the second run is the regression
        with pytest.raises(RuntimeError, match="read-after-donate"):
            exe.run(prog, feed=_batch(), fetch_list=[loss])


def test_feed_overwrite_warning():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    prog = fluid.default_main_program()
    blk = prog.global_block()
    blk.append_op(type="scale", inputs={"X": [x]}, outputs={"Out": [x]},
                  attrs={"scale": 2.0})
    fs = analysis.check_donation_safety(prog, feed_names=["x"])
    assert [f.severity for f in fs] == ["warning"]
    assert fs[0].var == "x" and "overwrites feed var" in fs[0].message


def test_cross_check_donation_report():
    report = {"mut_bytes": 1024, "alias_bytes": 0,
              "aliases_state": False}
    fs = analysis.cross_check_donation_report([], report)
    assert len(fs) == 1 and fs[0].severity == "warning"
    assert "disengaged" in fs[0].message
    ok = {"mut_bytes": 1024, "alias_bytes": 1024, "aliases_state": True}
    assert not analysis.cross_check_donation_report([], ok)
    assert not analysis.cross_check_donation_report([], None)


def test_cross_check_against_live_donation_report():
    """The dynamic side of the cross-check: a clean program's compiled
    executable really does alias its donated state."""
    loss = _mlp_loss()
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _batch()
    exe.run(prog, feed=feed, fetch_list=[loss])
    rep = exe.donation_report(prog, feed=feed, fetch_list=[loss])
    assert rep is not None and rep["aliases_state"]
    fs = analysis.check_donation_safety(prog, feed_names=list(feed),
                                        fetch_names=[loss.name])
    assert not fs
    assert not analysis.cross_check_donation_report(fs, rep)


# ---------------------------------------------------------------------------
# checker 3 — host sync in hot loops
# ---------------------------------------------------------------------------

def _seeded_fetch_in_scan():
    H = 4
    x = fluid.layers.data(name="x", shape=[H], dtype="float32")
    w = fluid.layers.create_parameter(shape=[2, H, H], dtype="float32",
                                      name="lint.w")
    h = fluid.layers.fc(x, size=H)
    scan = fluid.layers.Scan(n=2)
    with scan.block():
        wi = scan.slice_input(w)
        nh = fluid.layers.relu(fluid.layers.matmul(h, wi))
        sub = fluid.default_main_program().current_block()
        sub.append_op(type="fetch", inputs={"X": [nh]}, outputs={},
                      attrs={})
        fluid.layers.Print(nh)
        fluid.layers.assign(nh, output=h)
    return fluid.default_main_program(), h


def test_fetch_in_scan_body_is_an_error_print_a_warning():
    prog, _ = _seeded_fetch_in_scan()
    fs = analysis.check_host_sync(prog)
    fetch = [f for f in fs if f.op_type == "fetch"]
    assert len(fetch) == 1 and fetch[0].severity == "error"
    assert fetch[0].block_idx == 1  # inside the scan sub-block
    assert "every iteration" in fetch[0].message
    prints = [f for f in fs if f.op_type == "print"]
    assert len(prints) == 1 and prints[0].severity == "warning"
    assert "pure_callback" in prints[0].message


def test_rpc_marker_in_while_body_is_an_error():
    one = fluid.layers.fill_constant([1], "int64", 1)
    i = fluid.layers.fill_constant([1], "int64", 0)
    n = fluid.layers.fill_constant([1], "int64", 3)
    c = fluid.layers.less_than(i, n)
    w = fluid.layers.While(c)
    with w.block():
        sub = fluid.default_main_program().current_block()
        sub.append_op(type="send", inputs={"X": [i]}, outputs={},
                      attrs={"endpoints": ["127.0.0.1:6174"]})
        fluid.layers.assign(i + one, output=i)
        fluid.layers.less_than(i, n, cond=c)
    fs = analysis.check_host_sync(fluid.default_main_program())
    send = [f for f in fs if f.op_type == "send"]
    assert len(send) == 1 and send[0].severity == "error"


def test_dynamic_shape_op_severity_by_loop_depth():
    prog = fluid.default_main_program()
    blk = prog.global_block()
    x = fluid.layers.data(name="x", shape=[4, 6], dtype="float32")
    blk.append_op(type="multiclass_nms",
                  inputs={"BBoxes": [x], "Scores": [x]},
                  outputs={"Out": [blk.create_var(
                      name="nms.out", shape=(-1, 6),
                      dtype="float32")]},
                  attrs={})
    fs = analysis.check_host_sync(prog)
    assert [f.severity for f in fs] == ["warning"]
    assert "unjitted" in fs[0].message
    # the same op inside a scan body: the whole block goes eager
    # EVERY step — error
    sub = prog._create_block()
    sub.append_op(type="multiclass_nms",
                  inputs={"BBoxes": [x], "Scores": [x]},
                  outputs={"Out": [sub.create_var(
                      name="nms.out2", shape=(-1, 6),
                      dtype="float32")]},
                  attrs={})
    prog._rollback()
    blk.append_op(type="scan", inputs={}, outputs={},
                  attrs={"sub_block": sub.idx, "n": 2})
    fs = analysis.check_host_sync(prog)
    assert sorted(f.severity for f in fs) == ["error", "warning"]


def test_block_host_op_kinds_recurses_to_any_depth():
    """Satellite audit of lowering._block_host_op_kinds: a host op
    buried inside a cond inside a while must still be found (checker 3
    and the jit/eager lowering split both depend on it)."""
    one = fluid.layers.fill_constant([1], "int64", 1)
    i = fluid.layers.fill_constant([1], "int64", 0)
    n = fluid.layers.fill_constant([1], "int64", 3)
    c = fluid.layers.less_than(i, n)
    w = fluid.layers.While(c)
    with w.block():
        pred = fluid.layers.less_than(i, one)
        fluid.layers.cond(pred,
                          lambda: fluid.layers.Print(i),
                          lambda: i)
        fluid.layers.assign(i + one, output=i)
        fluid.layers.less_than(i, n, cond=c)
    block = fluid.default_main_program().global_block()
    host, dynamic = lowering._block_host_op_kinds(block)
    assert host and not dynamic
    # and the checker locates it at depth 2 (while -> cond branch)
    fs = analysis.check_host_sync(fluid.default_main_program())
    prints = [f for f in fs if f.op_type == "print"]
    assert prints and prints[0].severity == "warning"
    assert prints[0].block_idx >= 2


# ---------------------------------------------------------------------------
# checker 4 — ZeRO-1 planner invariants
# ---------------------------------------------------------------------------

def _planned_dp_program():
    from paddle_tpu.parallel import sharded_update as su

    loss = _mlp_loss()
    fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    prog = fluid.default_main_program()
    fluid.CompiledProgram(prog).with_data_parallel(loss_name=loss.name)
    plan = su.plan_sharded_update(prog, prog.global_block(), 8, "dp")
    assert plan is not None
    prog._shard_plan = plan
    return prog, plan


def test_valid_plan_is_clean():
    prog, _ = _planned_dp_program()
    assert not analysis.check_shard_plan(prog)


def test_non_zeroed_padding_slot_trips():
    """An op without a shard-aware re-zeroing rule inserted AFTER
    planning: its output can carry nonzero values in the flat-buffer
    padding slots straight into the optimizer."""
    prog, plan = _planned_dp_program()
    blk = prog.global_block()
    g = sorted(plan.grad_names)[0]
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "elementwise_pow", inputs={"X": [g], "Y": [g]},
        outputs={"Out": [g]}, attrs={}))
    fs = analysis.check_shard_plan(prog)
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.op_type == "elementwise_pow"
    assert f.op_idx == idx and f.var == g
    assert "not provably zeroed" in f.message


def test_broadcasting_elementwise_after_planning_trips():
    """The planner DECLINES programs whose elementwise binary ops
    broadcast mismatched non-scalar operands over a sharded grad (no
    flat-shard analogue); the checker must mirror that rule, or a
    program mutated this way after planning lints clean and then
    mis-broadcasts at shard-space trace time."""
    prog, plan = _planned_dp_program()
    blk = prog.global_block()
    g = next(n for n in sorted(plan.grad_names)
             if int(np.prod(blk._find_var_recursive(n).shape)) > 8)
    vec = blk.create_var(name="lint.bcast.vec", shape=(8,),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "elementwise_mul", inputs={"X": [g], "Y": [vec.name]},
        outputs={"Out": [g]}, attrs={"axis": 0}))
    fs = analysis.check_shard_plan(prog)
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.op_type == "elementwise_mul"
    assert f.op_idx == idx and f.var == g
    assert "no flat-shard analogue" in f.message
    # and the planner really does decline the mutated program
    from paddle_tpu.parallel import sharded_update as su
    assert su.plan_sharded_update(prog, blk, 8, "dp") is None


def test_tampered_shard_layout_trips():
    prog, plan = _planned_dp_program()
    name, info = sorted(plan.sharded_state.items())[0]
    info.shape = tuple(d + 1 for d in info.shape)
    fs = analysis.check_shard_plan(prog)
    assert any(f.severity == "error" and f.var == name
               and "save" in f.message.lower() for f in fs)


def test_mixed_dtype_bucket_trips():
    from paddle_tpu.parallel.sharded_update import (BucketEntry,
                                                    GradBucket)

    prog, plan = _planned_dp_program()
    e32 = BucketEntry("g32", "p32", "p32", (8,), "float32", 8, 0)
    e16 = BucketEntry("g16", "p16", "p16", (8,), "bfloat16", 8, 1)
    plan.buckets = (GradBucket(0, [e32, e16]),)
    fs = analysis.check_shard_plan(prog)
    assert any(f.severity == "error" and "mixes dtypes" in f.message
               for f in fs)


def test_misaligned_bucket_padding_trips():
    from paddle_tpu.parallel.sharded_update import (BucketEntry,
                                                    GradBucket)

    prog, plan = _planned_dp_program()
    e = BucketEntry("g", "p", "p", (9,), "float32", 8, 0)
    e.padded = 9  # not a multiple of ndev=8
    plan.buckets = (GradBucket(0, [e]),)
    fs = analysis.check_shard_plan(prog)
    assert any(f.severity == "error" and "misalign" in f.message
               for f in fs)


# ---------------------------------------------------------------------------
# checker 4 extension — model-sharded (tensor-parallel) vocabulary
# ---------------------------------------------------------------------------

def _planned_tp_program():
    """MLP Adam step planned by the ONE parallel planner on a
    (1, 4, 2) (dcn, ici, model) mesh: both fc weights column-parallel
    over `model`, ZeRO state over the replica axis."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import env as penv
    from paddle_tpu.parallel import planner

    loss = _mlp_loss()
    fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    prog = fluid.default_main_program()
    fluid.CompiledProgram(prog).with_data_parallel(loss_name=loss.name)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(1, 4, 2),
                ("dcn", "ici", "model"))
    pplan = planner.plan_parallel(prog, prog.global_block(), mesh,
                                  penv.ICI_AXIS)
    prog._mesh = mesh
    prog._tp_plan = pplan.tp_plan
    prog._shard_plan = pplan.shard_plan
    assert pplan.tp_plan is not None and pplan.tp_plan.params
    return prog, pplan.tp_plan


def test_model_sharded_plan_is_clean():
    prog, _ = _planned_tp_program()
    assert not analysis.check_shard_plan(prog)


def test_model_sharded_norm_reader_trips():
    """A global-norm reader over a model-sharded grad inserted after
    planning: each model member holds a DISTINCT shard, so the norm
    would mix partial sums without a model-axis psum."""
    prog, tpp = _planned_tp_program()
    blk = prog.global_block()
    g = sorted(tpp.params)[0] + "@GRAD"
    out = blk.create_var(name="lint.tp.norm", shape=(1,),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "squared_l2_norm", inputs={"X": [g]},
        outputs={"Out": [out.name]}, attrs={}))
    fs = analysis.check_shard_plan(prog)
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1
    f = errs[0]
    assert f.checker == "zero1-invariants"
    assert f.op_type == "squared_l2_norm" and f.op_idx == idx
    assert f.var == g and "model-sharded" in f.message


def test_model_sharded_collective_trips():
    """A raw allreduce over a model-sharded grad would average
    DISTINCT shards together — grad sync belongs on (dcn, replica)."""
    prog, tpp = _planned_tp_program()
    blk = prog.global_block()
    g = sorted(tpp.params)[0] + "@GRAD"
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "c_allreduce_sum", inputs={"X": [g]},
        outputs={"Out": [g]}, attrs={"ring_id": 0}))
    fs = analysis.check_shard_plan(prog)
    errs = [f for f in fs if f.severity == "error"]
    # the classic ZeRO padding walk flags the same op (no re-zeroing
    # rule) — BOTH findings must land, on the same op
    assert errs and all(f.op_type == "c_allreduce_sum" for f in errs)
    assert any("DISTINCT shards" in f.message for f in errs)


def test_model_sharded_unknown_op_trips():
    """Any op outside the shard-space vocabulary touching a TP'd var
    post-backward: inside shard_map the value is one member's LOCAL
    block, not the logical tensor."""
    prog, tpp = _planned_tp_program()
    blk = prog.global_block()
    p = sorted(tpp.params)[0]
    out = blk.create_var(name="lint.tp.mm", shape=(8, 8),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "matmul", inputs={"X": [p], "Y": [p]},
        outputs={"Out": [out.name]}, attrs={}))
    fs = analysis.check_shard_plan(prog)
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1
    assert errs[0].op_type == "matmul"
    assert "without a shard-space rule" in errs[0].message


def test_model_sharded_tp_local_layout_tamper_trips():
    """A TP'd ShardInfo whose local shape no longer derives from
    (logical_shape, tp_dim, mp) would make the model-major flat
    restore reassemble a wrong tensor."""
    prog, tpp = _planned_tp_program()
    plan = prog._shard_plan
    name, info = next((n, i) for n, i in plan.sharded_state.items()
                      if getattr(i, "tp_dim", None) is not None)
    info.tp_dim = len(info.logical_shape)  # out of range
    fs = analysis.check_shard_plan(prog)
    assert any(f.severity == "error" and f.var == name
               and "reassemble" in f.message for f in fs)


def test_hierarchical_groups_model_axis_grammar():
    """check_hierarchical_groups on a model-parallel mesh (ici=2,
    mp=2, pod=4): within-pod groups must be one model block, one
    member per model block, or the full pod — a partial span would
    average DISTINCT TP shards."""
    tmpl = ('%%0 = "stablehlo.all_reduce"(%%a) {replica_groups = '
            'dense<%s> : tensor<%s>} : '
            '(tensor<4xf32>) -> tensor<4xf32>')
    legal = [
        ("[[0, 1], [2, 3]]", "2x2xi64"),    # model blocks
        ("[[0, 2], [1, 3]]", "2x2xi64"),    # replica axis
        ("[[0, 1, 2, 3]]", "1x4xi64"),      # full pod
    ]
    for groups, shape in legal:
        hlo = tmpl % (groups, shape)
        assert analysis.check_hierarchical_groups(
            hlo, 2, ndev=8, mp_size=2) == [], groups
    mixed = tmpl % ("[[0, 1, 2], [1, 2, 3]]", "2x3xi64")
    fs = analysis.check_hierarchical_groups(mixed, 2, ndev=8,
                                            mp_size=2)
    assert len(fs) == 1 and fs[0].severity == "error"
    assert "MODEL/REPLICA-mixed" in fs[0].message
    # mp grammar applies on the single-pod (dcn=1) TP mesh too: the
    # world is exactly one pod, no cross-pod tier to hide behind
    fs1 = analysis.check_hierarchical_groups(mixed, 2, ndev=4,
                                             mp_size=2)
    assert any("MODEL/REPLICA-mixed" in f.message for f in fs1)


# ---------------------------------------------------------------------------
# checker 6 — ZeRO-2 gradient lifetimes
# ---------------------------------------------------------------------------

def test_zero2_valid_plan_is_clean():
    prog, _ = _planned_dp_program()
    assert not analysis.check_zero2_lifetimes(prog)


def test_zero2_full_grad_read_after_scatter_trips():
    """An op without a shard-space rule reading a scattered gradient
    (inserted after planning) would all_gather the full buffer back —
    the ZeRO-2 lifetime violation, located at the offending op."""
    prog, plan = _planned_dp_program()
    blk = prog.global_block()
    g = sorted(plan.grad_names)[0]
    out = blk.create_var(name="lint.zero2.out", shape=(1,),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "elementwise_pow", inputs={"X": [g], "Y": [g]},
        outputs={"Out": [out.name]}, attrs={}))
    fs = [f for f in analysis.check_zero2_lifetimes(prog)]
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.op_type == "elementwise_pow"
    assert f.op_idx == idx and f.var == g
    assert "all_gather the full gradient" in f.message


def test_zero2_broadcasting_elementwise_after_planning_trips():
    """The elementwise vocabulary is shard-safe only for same-shape /
    scalar operands — a post-planning broadcast over a scattered grad
    must trip here too (mirrors the planner's and checker 4's decline),
    or a standalone zero2 run would bless a program whose shard-space
    lowering mis-broadcasts."""
    prog, plan = _planned_dp_program()
    blk = prog.global_block()
    g = next(n for n in sorted(plan.grad_names)
             if int(np.prod(blk._find_var_recursive(n).shape)) > 8)
    vec = blk.create_var(name="lint.zero2.bcast", shape=(8,),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "elementwise_mul", inputs={"X": [g], "Y": [vec.name]},
        outputs={"Out": [g]}, attrs={"axis": 0}))
    fs = analysis.check_zero2_lifetimes(prog)
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.op_type == "elementwise_mul"
    assert f.op_idx == idx and f.var == g
    assert "no flat-shard analogue" in f.message


def test_zero2_fetch_of_scattered_grad_warns():
    prog, plan = _planned_dp_program()
    g = sorted(plan.grad_names)[0]
    fs = analysis.check_zero2_lifetimes(prog, fetch_names=[g])
    assert len(fs) == 1
    assert fs[0].severity == "warning" and fs[0].var == g
    assert "gathers the FULL buffer" in fs[0].message


def test_zero2_pending_bucket_early_flush_warns():
    """Explicit-sync bucketed programs: an op reading a grad whose
    bucket is still pending forces a partial early flush — the bucket's
    full grads die in pieces."""
    from paddle_tpu import fleet
    from paddle_tpu.parallel import sharded_update as su

    loss = _mlp_loss()
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    prog = fluid.default_main_program()
    fleet.transpile_collective(prog, nranks=8)
    blk = prog.global_block()
    set_flags({"FLAGS_tpu_comm_bucket_mb": 1000.0,
               "FLAGS_tpu_sharded_weight_update": True})
    plan = su.plan_sharded_update(prog, blk, 8, "dp")
    assert plan is not None and plan.explicit_sync and plan.buckets
    prog._shard_plan = plan
    assert not analysis.check_zero2_lifetimes(prog)  # contiguous: clean
    # wedge a reader of the FIRST allreduced grad between the pending
    # c_allreduce_sum ops
    ar_idx = [i for i, op in enumerate(blk.ops)
              if op.type == "c_allreduce_sum"]
    assert len(ar_idx) >= 2
    first_g = blk.ops[ar_idx[0]].input_names["X"][0]
    out = blk.create_var(name="lint.zero2.flush", shape=(1,),
                         dtype="float32")
    blk.ops.insert(ar_idx[0] + 1, Operator(
        blk, "squared_l2_norm", inputs={"X": [first_g]},
        outputs={"Out": [out.name]}, attrs={}))
    fs = analysis.check_zero2_lifetimes(prog)
    wedge = [f for f in fs if f.op_idx == ar_idx[0] + 1]
    assert wedge and wedge[0].severity == "warning"
    assert wedge[0].var == first_g
    assert "reduce-scatters early" in wedge[0].message
    # the remaining grads then flush partially at the optimizer's own
    # read — the checker mirrors the runtime and flags that too
    assert all(f.severity == "warning" for f in fs)


# ---------------------------------------------------------------------------
# checker 5 — dtype/shape contracts
# ---------------------------------------------------------------------------

def _planned_sparse_program(opt="adagrad"):
    from paddle_tpu.embedding import plan_sparse_tables

    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(
        ids, size=[37, 8], is_sparse=True,
        param_attr=fluid.ParamAttr(name="lint_emb"))
    logits = fluid.layers.fc(input=emb, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    O = fluid.optimizer
    {"adagrad": lambda: O.AdagradOptimizer(0.1),
     "sgd": lambda: O.SGDOptimizer(0.1)}[opt]().minimize(loss)
    prog = fluid.default_main_program()
    fluid.CompiledProgram(prog).with_data_parallel(loss_name=loss.name)
    plan = plan_sparse_tables(prog, prog.global_block(), 8, "dp",
                              feed_names=["ids", "label"])
    assert plan is not None and "lint_emb" in plan.tables, \
        getattr(prog, "_sparse_embedding_fallback", None)
    prog._sparse_plan = plan
    return prog, plan


def test_sparse_update_valid_plan_is_clean():
    prog, _ = _planned_sparse_program()
    assert not analysis.check_sparse_update(prog)


def test_sparse_grad_consumed_by_foreign_op_trips():
    """A non-shard-aware op reading the table's SelectedRows gradient
    (inserted after planning) = error, located at the offending op —
    the static twin of the engine's trace-time raise."""
    prog, plan = _planned_sparse_program()
    blk = prog.global_block()
    g = sorted(plan.grad_of)[0]
    out = blk.create_var(name="lint.sparse.out", shape=(37, 8),
                         dtype="float32")
    idx = _bwd_idx(blk) + 1
    blk.ops.insert(idx, Operator(
        blk, "elementwise_mul", inputs={"X": [g], "Y": [g]},
        outputs={"Out": [out.name]}, attrs={}))
    fs = analysis.check_sparse_update(prog)
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error" and f.op_type == "elementwise_mul"
    assert f.op_idx == idx and f.var == g
    assert "no sparse-aware rule" in f.message


def test_sparse_table_touched_outside_engine_trips():
    prog, plan = _planned_sparse_program()
    blk = prog.global_block()
    out = blk.create_var(name="lint.sparse.scale", shape=(37, 8),
                         dtype="float32")
    blk.ops.insert(0, Operator(
        blk, "scale", inputs={"X": ["lint_emb"]},
        outputs={"Out": [out.name]}, attrs={"scale": 2.0}))
    fs = analysis.check_sparse_update(prog)
    assert any(f.severity == "error" and f.var == "lint_emb"
               and f.op_type == "scale" for f in fs)


def test_sparse_tampered_row_layout_trips():
    prog, plan = _planned_sparse_program()
    info = plan.tables["lint_emb"].info
    info.padded_rows = info.padded_rows + 1  # no longer ndev-aligned
    fs = analysis.check_sparse_update(prog)
    assert any(f.severity == "error" and f.var == "lint_emb"
               and "misalign" in f.message for f in fs)


def test_sparse_fetch_of_selectedrows_grad_warns():
    prog, plan = _planned_sparse_program()
    g = sorted(plan.grad_of)[0]
    fs = analysis.check_sparse_update(prog, fetch_names=[g])
    assert len(fs) == 1
    assert fs[0].severity == "warning" and fs[0].var == g
    assert "densifies" in fs[0].message


def test_rank_divergent_table_shard_schedule_trips():
    """Rank 0 shards the table (sparse plan), rank 1 does not (e.g. a
    per-rank flag skew): their collective schedules diverge at the
    lookup — the deadlock class the divergence checker exists for."""
    prog0, _ = _planned_sparse_program()
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    with framework.unique_name_guard():
        prog1, _ = _planned_sparse_program()
    prog1._sparse_plan = None  # rank 1 "planned" nothing
    recs = analysis.collective_schedule(prog0)
    assert any(r["kind"] == "sparse_lookup" and r["var"] == "lint_emb"
               for r in recs)
    fs = analysis.check_collective_divergence([prog0, prog1])
    assert any(f.severity == "error" for f in fs), fs


def test_zero1_skips_engine_owned_optimizer_ops():
    """The sparse table's optimizer op consumes a SelectedRows grad
    with its OWN schedule — the zero1 checker must not flag it as
    'never reduce-scattered' (the taint-vocabulary extension)."""
    from paddle_tpu.parallel import sharded_update as su

    prog, _ = _planned_sparse_program()
    prog._shard_plan = su.plan_sharded_update(
        prog, prog.global_block(), 8, "dp")
    assert prog._shard_plan is not None  # fc params still plan dense
    assert not analysis.check_shard_plan(prog)
    assert not analysis.check_zero2_lifetimes(prog)


def test_dtype_contract_drift_and_fp64_promotion():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.scale(x, scale=2.0)
    prog = fluid.default_main_program()
    assert not analysis.check_dtype_shape_contracts(prog)
    # drift the declaration after the op was appended
    prog.global_block()._find_var_recursive(y.name).dtype = "float16"
    fs = analysis.check_dtype_shape_contracts(prog)
    assert [f.severity for f in fs] == ["warning"]
    assert fs[0].var == y.name and "drifted" in fs[0].message
    prog.global_block()._find_var_recursive(y.name).dtype = "float32"
    # fp64 computed from non-fp64 inputs: flagged even when declared
    fluid.layers.cast(y, "float64")
    fs = analysis.check_dtype_shape_contracts(prog)
    assert any("fp64 promotion" in f.message and f.op_type == "cast"
               for f in fs)


def test_shape_contract_drift():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.scale(x, scale=2.0)
    prog = fluid.default_main_program()
    v = prog.global_block()._find_var_recursive(y.name)
    v.shape = (-1, 5)
    fs = analysis.check_dtype_shape_contracts(prog)
    assert any(f.var == y.name and "shape" in f.message for f in fs)


def _mark_amp(prog, dtype="bfloat16"):
    from paddle_tpu.fluid.contrib.mixed_precision import \
        AutoMixedPrecisionLists

    prog._amp = True
    prog._amp_lists = AutoMixedPrecisionLists()
    prog._amp_dtype = dtype
    return prog


def test_redundant_cast_round_trip_warns():
    """cast(cast(x bf16 -> f32) -> bf16) with a single-use intermediate
    is an identity round-trip the AMP pass should have elided."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    a = fluid.layers.cast(x, "bfloat16")
    b = fluid.layers.cast(a, "float32")
    c = fluid.layers.cast(b, "bfloat16")
    prog = fluid.default_main_program()
    fs = [f for f in analysis.check_dtype_shape_contracts(prog)
          if "redundant-cast" in f.message]
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "warning" and f.var == c.name
    assert "identity" in f.message
    # a consumer of the fp32 intermediate legitimizes the chain
    fluid.layers.scale(b, scale=2.0)
    fs = [f for f in analysis.check_dtype_shape_contracts(prog)
          if "redundant-cast" in f.message and f.var == c.name]
    assert not fs


def test_redundant_upcast_into_white_list_warns_under_amp():
    """AMP: an explicit bf16 -> fp32 cast whose every reader is a
    white-list op round-trips by construction (the policy casts those
    inputs straight back down)."""
    x = fluid.layers.data(name="x", shape=[4, 4], dtype="bfloat16")
    y = fluid.layers.cast(x, "float32")
    fluid.layers.mul(y, y)
    prog = _mark_amp(fluid.default_main_program())
    fs = [f for f in analysis.check_dtype_shape_contracts(prog)
          if "redundant-cast" in f.message]
    assert len(fs) == 1 and fs[0].var == y.name
    assert "white-list" in fs[0].message
    # without the AMP marking there is no policy to re-cast: clean
    prog._amp = False
    assert not [f for f in analysis.check_dtype_shape_contracts(prog)
                if "redundant-cast" in f.message]


def test_amp_policy_suppresses_mixed_dtype_drift_and_fp64_flag():
    """The trace-time AMP casts make a f32<->bf16 declaration
    disagreement legitimate (suppressed under _amp, a warning without
    it); the fp64-promotion check never fires on white-listed ops."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.scale(x, scale=2.0)
    prog = fluid.default_main_program()
    prog.global_block()._find_var_recursive(y.name).dtype = "bfloat16"
    fs = analysis.check_dtype_shape_contracts(prog)
    assert any(f.var == y.name and "drifted" in f.message for f in fs)
    _mark_amp(prog)
    assert not analysis.check_dtype_shape_contracts(prog)
    # white-listed op requesting f64 via attrs: mis-flag without the
    # policy, clean with it (the op runs in bf16 under AMP)
    from paddle_tpu.fluid.framework import Operator

    blk = prog.global_block()
    out = blk.create_var(name="amp.f64.out", shape=(4,),
                         dtype="float32")
    blk.ops.append(Operator(
        blk, "mul", inputs={"X": [x.name], "Y": [x.name]},
        outputs={"Out": [out.name]}, attrs={"dtype": "float64"}))
    assert not [f for f in analysis.check_dtype_shape_contracts(prog)
                if "fp64" in f.message]
    prog._amp = False
    assert [f for f in analysis.check_dtype_shape_contracts(prog)
            if "fp64" in f.message and f.op_type == "mul"]


# ---------------------------------------------------------------------------
# orchestrator + Executor hook
# ---------------------------------------------------------------------------

def test_run_static_checks_rejects_unknown_checker():
    with pytest.raises(ValueError, match="unknown checker"):
        analysis.run_static_checks(fluid.default_main_program(),
                                   checkers=["bogus"])


def test_run_static_checks_labels_cover_prepended_program():
    """A caller labeling only rank_programs must still get a Finding
    (naming the diverging rank), not an IndexError, when the LAST rank
    diverges from the prepended reference program."""
    p0, _ = _transpiled_program()
    p1, _ = _transpiled_program()
    p2, _ = _transpiled_program(extra_allreduce=True)
    fs = analysis.run_static_checks(
        p0, checkers=["collective-divergence"],
        rank_programs=[p1, p2], rank_labels=["rank1", "rank2"])
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1 and errs[0].rank == "rank2"


def test_executor_hook_error_raises_before_dispatch():
    prog, loss, _ = _seeded_read_after_donate()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    set_flags({"FLAGS_tpu_static_checks": "error"})
    with pytest.raises(RuntimeError, match="read-after-donate"):
        exe.run(prog, feed=_batch(), fetch_list=[loss])


def test_executor_hook_error_raises_before_the_xla_compile(monkeypatch):
    """IR-only findings must reject the program BEFORE the (potentially
    tens of seconds) compile_block call, not after it."""
    from paddle_tpu.fluid import lowering as lowering_mod

    prog, loss, _ = _seeded_read_after_donate()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    set_flags({"FLAGS_tpu_static_checks": "error"})

    def boom(*a, **k):
        raise AssertionError("compile_block ran before the lint")

    monkeypatch.setattr(lowering_mod, "compile_block", boom)
    with pytest.raises(RuntimeError, match="read-after-donate"):
        exe.run(prog, feed=_batch(), fetch_list=[loss])


def test_executor_hook_warn_mode_warns_and_runs():
    prog, loss, _ = _seeded_read_after_donate()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    set_flags({"FLAGS_tpu_static_checks": "warn"})
    with pytest.warns(UserWarning, match="tpu-lint"):
        out = exe.run(prog, feed=_batch(), fetch_list=[loss])
    assert np.isfinite(np.asarray(out[0])).all()


def test_executor_hook_clean_program_passes_under_error():
    """The acceptance contract: ordinary tier-1 programs lint clean
    under FLAGS_tpu_static_checks=error — the flag costs nothing."""
    set_flags({"FLAGS_tpu_static_checks": "error"})
    loss = _mlp_loss()
    fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out = exe.run(fluid.default_main_program(), feed=_batch(),
                  fetch_list=[loss])
    assert np.isfinite(np.asarray(out[0])).all()


# ---------------------------------------------------------------------------
# collective_byte_census region coverage (switch_case/conditional_block)
# ---------------------------------------------------------------------------

def _dp_mark(prog, nranks=8):
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import env as penv

    mesh = Mesh(np.array(jax.devices()[:nranks]), ("dp",))
    prog._data_parallel = True
    prog._mesh = mesh
    penv.set_global_mesh(mesh)
    penv.register_ring(0, "dp", nranks)


def test_census_counts_switch_case_region_collectives():
    """lax.switch branches live in non-entry StableHLO regions; the
    census must count their all_reduces (previously only the gm
    lax.cond path was regression-tested)."""
    from paddle_tpu.fluid.layers.collective import _c_allreduce

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    idx = fluid.layers.data(name="idx", shape=[1], dtype="int32")
    out = fluid.layers.switch_case(
        idx,
        [lambda: _c_allreduce(x, reduce_type="sum"),
         lambda: _c_allreduce(x * 2.0, reduce_type="sum")],
        default=lambda: _c_allreduce(x * 3.0, reduce_type="sum"))
    loss = fluid.layers.mean(out)
    prog = fluid.default_main_program()
    _dp_mark(prog)
    exe = fluid.Executor(fluid.TPUPlace())
    feed = {"x": np.ones((8, 4), np.float32),
            "idx": np.zeros((8, 1), np.int32)}
    exe.run(prog, feed=feed, fetch_list=[loss])
    col = exe.collective_report(prog, feed=feed, fetch_list=[loss])
    assert col is not None
    # one psum per traced branch (2 keyed + default), each inside its
    # switch region
    assert col["all_reduce"]["count"] == 3
    assert col["all_reduce"]["tensor_bytes"] > 0


def test_census_counts_conditional_block_region_collectives():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.scale(x, scale=2.0)
    c = fluid.layers.reduce_mean(x) > 0.0
    prog = fluid.default_main_program()
    blk = prog.global_block()
    sub = prog._create_block()
    sub.append_op(type="c_allreduce_sum", inputs={"X": [y]},
                  outputs={"Out": [y]}, attrs={"ring_id": 0})
    prog._rollback()
    blk.append_op(type="conditional_block", inputs={"Cond": [c]},
                  outputs={}, attrs={"sub_block": sub.idx})
    loss = fluid.layers.mean(y)
    _dp_mark(prog)
    exe = fluid.Executor(fluid.TPUPlace())
    feed = {"x": np.ones((8, 4), np.float32)}
    exe.run(prog, feed=feed, fetch_list=[loss])
    col = exe.collective_report(prog, feed=feed, fetch_list=[loss])
    assert col is not None
    assert col.get("all_reduce", {}).get("count", 0) >= 1


# ---------------------------------------------------------------------------
# exemplar lint-regression harness (tools/tpu_lint.py)
# ---------------------------------------------------------------------------

def _import_tpu_lint():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import tpu_lint
    finally:
        sys.path.pop(0)
    return tpu_lint


def test_exemplar_programs_lint_clean(tmp_path):
    """The standing tier-1 CI leg: tools/tpu_lint.py over the FULL
    exemplar corpus — BERT-tiny DP step (plain, and bf16 AMP + ZeRO-2
    bucketed masters), resnet scan, the serving decode loop, and the
    2-rank fleet-transpiled sync-PS programs — through main() with
    --fail-on error, so the exit code and artifact are exactly what CI
    sees."""
    tpu_lint = _import_tpu_lint()
    out = tmp_path / "static_checks.json"
    rc = tpu_lint.main(["--fail-on", "error", "--out", str(out)])
    report = json.loads(out.read_text())
    assert set(report["programs"]) == {
        "bert_tiny", "bert_tiny_amp", "bert_tiny_tp",
        "mlp_hier", "embedding_ctr", "resnet_scan", "serving_decode",
        "serving_decode_sampled", "fleet_ps_2rank"}
    assert rc == 0 and report["ok"] and report["total_errors"] == 0, \
        report


@pytest.mark.slow
def test_cli_end_to_end(tmp_path):
    out = tmp_path / "static_checks.json"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "tpu_lint.py"),
         "--fail-on", "error", "--out", str(out)],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(out.read_text())
    assert report["ok"] and report["total_errors"] == 0
    assert set(report["programs"]) == {"bert_tiny", "bert_tiny_amp",
                                       "bert_tiny_tp",
                                       "mlp_hier", "embedding_ctr",
                                       "resnet_scan", "serving_decode",
                                       "serving_decode_sampled",
                                       "fleet_ps_2rank"}
    assert "tpu-lint:" in r.stdout


@pytest.mark.slow
def test_perf_analysis_lint_alias(tmp_path):
    out = tmp_path / "static_checks.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tools", "perf_analysis.py"),
         "--lint", "--out", str(out), "--json"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(out.read_text())["ok"]


# ---------------------------------------------------------------------------
# checker — quantization-tier contracts (calibrated quantizer scales)
# ---------------------------------------------------------------------------

def test_quantizer_missing_scale_slot_trips():
    """A slim/PTQ dequantize op with an empty Scale slot would
    (de)quantize with no scale at all."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        blk = main.global_block()
        x = blk.create_var(name="q.x", shape=(4, 4), dtype="float32")
        out = blk.create_var(name="q.out", shape=(4, 4),
                             dtype="float32")
        blk.ops.append(Operator(
            blk, "fake_dequantize_max_abs", inputs={"X": [x.name]},
            outputs={"Out": [out.name]}, attrs={"max_range": 127.0}))
    fs = analysis.check_quantization_contracts(main)
    assert len(fs) == 1
    f = fs[0]
    assert f.severity == "error"
    assert f.op_type == "fake_dequantize_max_abs"
    assert "missing its calibrated scale input" in f.message
