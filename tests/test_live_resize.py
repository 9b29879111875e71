"""Zero-downtime elasticity (distributed/preemption + Executor.
live_resize + serving.Engine.drain): preemption notices (SIGTERM /
RPC / fault-injected) consumed at step boundaries, the ElasticWorld
group-agreed live seam, the device-tier in-place mesh resize whose
post-seam trajectory is BIT-IDENTICAL to an elastic cold restart
restored from the same snapshot (ZeRO-1 / AMP-O2 / vocab-sharded
embedding state), dygraph fp32 masters sharding over the mesh, the
serving drain/migrate protocol, the degrade-to-cohort-restart
breadcrumbs, and the supervised 4 -> 3 acceptance runs (live seam +
fault-during-recovery degrade)."""
import json
import os
import signal
import subprocess as _sp
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.distributed import faults
from paddle_tpu.distributed import preemption as pre
from paddle_tpu.fluid import checkpoint as ckpt
from paddle_tpu.fluid import framework
from paddle_tpu.utils.flags import get_flag, set_flags

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


@pytest.fixture(autouse=True)
def _clean_preempt_state(monkeypatch):
    """Notices and the launch-rank pin are process-global by design
    (one process == one rank in production); tests must not leak them
    into each other."""
    pre.clear_notice()
    monkeypatch.delenv("PADDLE_LAUNCH_RANK", raising=False)
    yield
    pre.clear_notice()
    faults.reset()


@pytest.fixture
def _restore_flags():
    keys = ("FLAGS_tpu_sharded_weight_update", "FLAGS_tpu_comm_bucket_mb",
            "FLAGS_tpu_sparse_embedding", "FLAGS_tpu_telemetry_dir")
    old = {k: get_flag(k) for k in keys}
    yield
    set_flags(old)


# -- notice delivery ---------------------------------------------------------

def test_deliver_notice_first_wins():
    n1 = pre.deliver_notice(grace_s=7.5, source="rpc", rank=3)
    # a racing second notice must not shorten or extend the armed window
    n2 = pre.deliver_notice(grace_s=99.0, source="sigterm")
    assert n2 is n1
    got = pre.pending_notice()
    assert got is n1 and got.grace_s == 7.5 and got.source == "rpc"
    assert got.rank == 3
    assert 0.0 <= got.remaining_s() <= 7.5
    assert got.as_dict()["source"] == "rpc"
    pre.clear_notice()
    assert pre.pending_notice() is None


def test_default_grace_env(monkeypatch):
    monkeypatch.setenv("PADDLE_PREEMPT_GRACE_S", "12.5")
    assert pre.default_grace_s() == 12.5
    monkeypatch.setenv("PADDLE_PREEMPT_GRACE_S", "nonsense")
    assert pre.default_grace_s() == 30.0


def test_sigterm_is_a_notice_not_a_death():
    """The FIRST SIGTERM arms a pending notice and the process keeps
    running — the grace window belongs to the step loop, not to the
    signal handler."""
    assert pre.install_sigterm(grace_s=11.0)
    os.kill(os.getpid(), signal.SIGTERM)
    n = pre.pending_notice()
    assert n is not None, "SIGTERM must deliver a notice, not kill"
    assert n.source == "sigterm" and n.grace_s == 11.0
    # idempotent re-install
    assert pre.install_sigterm()


def test_preempt_fault_kind_warns_without_disrupting_the_op():
    """faults.py `preempt`: deterministic notice injection at rank R /
    event K — unlike `kill` the op itself proceeds untouched."""
    with faults.inject("preempt", side="client", point="send",
                       method="hc_put_part", at=2, grace_s=3.0):
        faults.on_message("client", "send", "hc_put_part")  # 1: miss
        assert pre.pending_notice() is None
        faults.on_message("client", "send", "hc_put_part")  # 2: fire
        n = pre.pending_notice()
        assert n is not None and n.source == "fault"
        assert n.grace_s == 3.0
        # `at=` fires exactly once; and the op was never disrupted
        pre.clear_notice()
        faults.on_message("client", "send", "hc_put_part")
        assert pre.pending_notice() is None
    specs = faults.parse_spec(
        "preempt:side=client,point=send,at=14,grace_s=2.5")
    assert specs[0].kind == "preempt" and specs[0].grace_s == 2.5


def test_preempt_marker_roundtrip(tmp_path, _restore_flags):
    set_flags({"FLAGS_tpu_telemetry_dir": str(tmp_path)})
    path = pre.write_preempt_marker(2, step=9, grace_s=30.0,
                                    source="fault",
                                    extra={"group_rank": 1})
    assert path and os.path.basename(path) == "preempted.rank2.json"
    (tmp_path / "preempted.rank7.json").write_text("{torn")  # skipped
    (tmp_path / "preempted.rank0.json").write_text(
        json.dumps({"rank": 0, "ts": 1.0}))
    marks = pre.read_preempt_markers(str(tmp_path))
    assert [m["rank"] for m in marks] == [0, 2]
    assert marks[1]["step"] == 9 and marks[1]["group_rank"] == 1
    # the launch supervisor's view: the same markers name the shrink
    from paddle_tpu.distributed import launch as launch_mod

    assert launch_mod._preempt_marker_ranks(str(tmp_path)) == [0, 2]
    assert pre.read_preempt_markers(str(tmp_path / "missing")) == []


# -- ElasticWorld seam protocol (fake group: single-process units) ----------

class _FakeGroup:
    def __init__(self, rank, world, fail_barrier=False):
        self.rank, self.world = rank, world
        self.barriers = 0
        self.left = self.shut = False
        self.fail_barrier = fail_barrier

    def barrier(self):
        self.barriers += 1
        if self.fail_barrier:
            raise RuntimeError("rank 2 heartbeat stale")

    def all_reduce(self, arr, op="sum"):
        return arr

    def peek(self, key):
        return None

    def leave(self):
        self.left = True

    def shutdown(self):
        self.shut = True


def test_elastic_world_sync_agrees_on_doomed_set():
    ew = pre.ElasticWorld(_FakeGroup(1, 3), ["h:1", "h:2", "h:3"])
    assert ew.sync() == []
    pre.deliver_notice(grace_s=5.0, source="rpc", rank=1)
    assert ew.sync() == [1]
    assert ew.rank == 1 and ew.world == 3
    with pytest.raises(ValueError, match="endpoints"):
        pre.ElasticWorld(_FakeGroup(0, 3), ["h:1"])


def test_elastic_world_doomed_seam(tmp_path, _restore_flags):
    """The doomed rank's half: marker first, snapshot, barrier, clean
    leave, role report — never a survivor rebuild."""
    set_flags({"FLAGS_tpu_telemetry_dir": str(tmp_path)})
    g = _FakeGroup(1, 3)
    ew = pre.ElasticWorld(g, ["h:1", "h:2", "h:3"])
    pre.deliver_notice(grace_s=9.0, source="fault", rank=1)
    snaps = []
    report = ew.resize([1], snapshot=snaps.append, step=7)
    assert report["role"] == "doomed"
    assert report["old_world"] == 3 and report["new_world"] == 2
    assert snaps == [[1]]
    assert g.barriers == 1 and g.left and not g.shut
    assert pre.pending_notice() is None  # consumed
    marks = pre.read_preempt_markers(str(tmp_path))
    assert len(marks) == 1 and marks[0]["rank"] == 1
    assert marks[0]["step"] == 7 and marks[0]["group_rank"] == 1


def test_elastic_world_resize_validation():
    ew = pre.ElasticWorld(_FakeGroup(0, 2), ["h:1", "h:2"])
    with pytest.raises(ValueError, match="empty"):
        ew.resize([])
    with pytest.raises(pre.LiveResizeError, match="all 2 ranks"):
        ew.resize([0, 1])


def test_elastic_world_seam_failure_degrades_loudly(tmp_path,
                                                   _restore_flags):
    """A fault inside the seam (here: the agreement barrier) raises
    LiveResizeError — the runner's cue to exit DEGRADE_RC — and the
    doomed rank's marker survives it, so the cohort restart still
    drops the right rank."""
    set_flags({"FLAGS_tpu_telemetry_dir": str(tmp_path)})
    g = _FakeGroup(1, 4, fail_barrier=True)
    ew = pre.ElasticWorld(g, ["h:%d" % i for i in range(4)])
    with pytest.raises(pre.LiveResizeError, match="degrade"):
        ew.resize([1], step=4)
    assert pre.DEGRADE_RC == 98
    marks = pre.read_preempt_markers(str(tmp_path))
    assert [m["rank"] for m in marks] == [1]


def test_launch_rank_pins_across_resizes(monkeypatch):
    """Preempt markers speak the SUPERVISOR's tid space: after a first
    seam moved this process to contiguous rank 1, a second notice must
    still be attributed to the original launch rank."""
    monkeypatch.setenv("PADDLE_LAUNCH_RANK", "2")
    ew = pre.ElasticWorld(_FakeGroup(1, 3), ["h:1", "h:2", "h:3"],
                          generation=1)
    assert ew.launch_rank == 2 and ew.rank == 1


def test_survivor_rank_reassignment():
    from paddle_tpu.reader.resharding import survivor_rank

    assert survivor_rank(0, [1]) == 0
    assert survivor_rank(3, [1]) == 2
    assert survivor_rank(1, [1]) == -1
    assert survivor_rank(5, [0, 3]) == 3
    # matches the launch supervisor's contiguous reassignment rule
    doomed = [1, 4]
    world = 6
    expect = {o: n for n, o in enumerate(
        r for r in range(world) if r not in doomed)}
    for r in range(world):
        assert survivor_rank(r, doomed) == expect.get(r, -1)


# -- device tier: Executor.live_resize in-place bit-identity ----------------
#
# The tentpole acceptance: train sharded on 4 devices, snapshot, resize
# the SAME program/scope/executor in place to N', keep training — the
# post-seam losses must be BIT-IDENTICAL to a cold N'-device program
# restored from the snapshot (the PR 6/PR 8 elastic-restart ground
# truth). N'=3 exercises genuinely different flat padding (31 -> 33).

def _shrink_batch():
    r = np.random.RandomState(0)
    return (r.rand(24, 16).astype("float32"),
            r.randint(0, 4, (24, 1)).astype("int64"))


def _build_dp(ndev, zero1, amp=False, bucket_mb=0.0):
    import jax
    from jax.sharding import Mesh

    set_flags({"FLAGS_tpu_sharded_weight_update": zero1,
               "FLAGS_tpu_comm_bucket_mb": bucket_mb})
    main, startup = fluid.Program(), fluid.Program()
    with framework.unique_name_guard(), \
            fluid.program_guard(main, startup):
        main.random_seed = startup.random_seed = 77
        img = fluid.layers.data(name="img", shape=[16],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        h = fluid.layers.fc(input=img, size=31, act="relu")
        logits = fluid.layers.fc(input=h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        opt = fluid.optimizer.AdamOptimizer(learning_rate=0.01)
        if amp:
            from paddle_tpu.fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
        fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        main._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
    return main, startup, loss.name


def _steps(exe, prog, loss_name, scope, n):
    x, y = _shrink_batch()
    return [float(np.asarray(exe.run(
        prog, feed={"img": x, "label": y}, fetch_list=[loss_name],
        scope=scope)[0]).mean()) for _ in range(n)]


@pytest.mark.parametrize("amp", [False, True], ids=["zero1", "amp_o2"])
@pytest.mark.parametrize("new_ndev", [3, 2])
def test_live_resize_bit_identical_to_cold_restart(tmp_path,
                                                   _restore_flags,
                                                   amp, new_ndev):
    bucket_mb = 0.0 if amp else 0.25
    root = str(tmp_path / "seam")
    prog, st, ln = _build_dp(4, True, amp=amp, bucket_mb=bucket_mb)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st, scope=scope)
    _steps(exe, prog, ln, scope, 2)
    ckpt.save_checkpoint(exe, root,
                         ckpt.TrainStatus(epoch_no=0, step_no=1),
                         main_program=prog, scope=scope)

    report = exe.live_resize(prog, ndev=new_ndev, scope=scope)
    assert report["old_world"] == 4
    assert report["new_world"] == new_ndev
    assert report["n_state"] > 0, \
        "sharded moments/masters must re-shard through the seam"
    assert report["n_evicted"] >= 1, "old-mesh executables must evict"
    post = _steps(exe, prog, ln, scope, 3)

    # cold restart reference: fresh N'-device program restored from
    # the pre-seam checkpoint (the PR 6 elastic path)
    p2, st2, ln2 = _build_dp(new_ndev, True, amp=amp,
                             bucket_mb=bucket_mb)
    sc2 = Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(st2, scope=sc2)
    assert ckpt.load_checkpoint(exe2, root, main_program=p2,
                                scope=sc2) is not None
    ref = _steps(exe2, p2, ln2, sc2, 3)
    np.testing.assert_array_equal(
        np.asarray(post), np.asarray(ref),
        err_msg="live 4->%d seam not bit-identical to cold restart"
        % new_ndev)
    # the plan re-planned in place for N'
    plan = getattr(prog, "_shard_plan", None)
    if new_ndev > 1:
        assert plan is not None and plan.ndev == new_ndev
        if new_ndev == 3:
            assert any(info.numel == 31 and info.padded == 33
                       for info in plan.sharded_state.values())


def test_live_resize_requires_mesh_or_ndev(_restore_flags):
    prog, st, _ = _build_dp(4, True)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(ValueError, match="mesh= or ndev="):
        exe.live_resize(prog)


# -- device tier: vocab-sharded embedding state through the seam ------------

VOCAB, DIM = 37, 8


def _build_sparse():
    framework.default_main_program().random_seed = 7
    framework.default_startup_program().random_seed = 7
    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    dense = fluid.layers.data(name="dense", shape=[4],
                              dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, DIM], is_sparse=True, padding_idx=0,
        param_attr=fluid.ParamAttr(name="emb_w"))
    h = fluid.layers.concat([emb, dense], axis=1)
    h = fluid.layers.fc(input=h, size=16, act="relu")
    logits = fluid.layers.fc(input=h, size=2)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.AdagradOptimizer(learning_rate=0.1).minimize(loss)
    return loss


def _sparse_feed():
    r = np.random.RandomState(0)
    b = 48  # divisible by 4 and 3; covers most of the 37-row vocab
    return {"ids": r.randint(0, VOCAB, (b, 1)).astype("int64"),
            "dense": r.rand(b, 4).astype("float32"),
            "label": r.randint(0, 2, (b, 1)).astype("int64")}


def test_live_resize_embedding_tables_reshard_in_place(_restore_flags):
    """The PR 15 row-sharded tables (and their per-row moments) ride
    the same seam: unshard to logical (padded rows stripped), swap the
    mesh, re-plan at N' row padding — bit-identical to a cold N'
    engine seeded from the same logical snapshot."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.parallel.sharded_update import unshard_scope_value

    feed = _sparse_feed()
    set_flags({"FLAGS_tpu_sparse_embedding": True,
               "FLAGS_tpu_comm_bucket_mb": 0.0})
    with framework.unique_name_guard():
        loss = _build_sparse()
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        prog._mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        for _ in range(2):
            exe.run(prog, feed=feed, fetch_list=[loss])
        # logical snapshot for the reference BEFORE the seam
        sc = scope_mod._global_scope
        snap = {n: np.asarray(unshard_scope_value(
            prog, n, sc.find_var(n))).copy()
            for n in sorted(sc.local_var_names())
            if sc.find_var(n) is not None}
        assert getattr(prog, "_sparse_plan", None) is not None
        assert prog._sparse_plan.tables["emb_w"].info.padded_rows == 40

        rep = exe.live_resize(prog, ndev=3)
        assert rep["new_world"] == 3
        post = [float(exe.run(prog, feed=feed,
                              fetch_list=[loss])[0].mean())
                for _ in range(3)]
        # re-planned row padding: 37 -> 39 at N'=3 (was 40 at 4)
        assert prog._sparse_plan.tables["emb_w"].info.padded_rows == 39

    # cold N'=3 reference from the logical snapshot
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()
    with framework.unique_name_guard():
        loss = _build_sparse()
        p3 = fluid.default_main_program()
        fluid.CompiledProgram(p3).with_data_parallel(
            loss_name=loss.name)
        p3._mesh = Mesh(np.array(jax.devices()[:3]), ("dp",))
        exe3 = fluid.Executor(fluid.CPUPlace())
        exe3.run(fluid.default_startup_program())
        sc = scope_mod._global_scope
        for n, v in snap.items():
            if sc.find_var(n) is not None:
                sc.set_var(n, v.copy())
        ref = [float(exe3.run(p3, feed=feed,
                              fetch_list=[loss])[0].mean())
               for _ in range(3)]
    assert post == ref, "embedding live seam not bit-identical"


# -- dygraph: fp32 masters shard over the mesh ------------------------------

def test_eager_master_weights_shard_over_mesh(_restore_flags):
    """EagerMasterWeightOptimizer masters take the same P(ici) dim-0
    layout as the eager accumulators (divisibility-gated): memory off
    every replica, update partitioned by XLA — trajectory equal to the
    replicated masters (one transient bf16-ulp loss wobble allowed:
    the PR 4 CPU-fusion caveat; the MASTERS themselves must match
    exactly)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.fluid import optimizer as O
    from paddle_tpu.fluid.dygraph import Linear
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.parallel import env as penv

    def train(mesh):
        set_flags({"FLAGS_tpu_sharded_weight_update": True})
        penv.set_global_mesh(mesh)
        try:
            r = np.random.RandomState(3)
            x = r.rand(64, 16).astype("float32")
            y = r.randint(0, 4, (64, 1)).astype("int64")
            net = Linear(16, 4)
            m = Model(net)
            m.prepare(
                O.SGDOptimizer(learning_rate=0.5,
                               parameter_list=net.parameters()),
                loss_function=lambda pred, label: fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(pred,
                                                            label)),
                amp_level="O2")
            rs = np.random.RandomState(5)  # identical init both runs
            for p in net.parameters():
                p._assign_raw(jnp.asarray(
                    rs.rand(*p.shape).astype("float32")
                ).astype(jnp.bfloat16))
            losses = [float(m.train_batch([x], [y])[0][0])
                      for _ in range(6)]
            masters = [np.asarray(m._optimizer._masters[p.name],
                                  np.float32).copy()
                       for p in net.parameters()]
            shards = [m._optimizer._masters[p.name].sharding
                      for p in net.parameters()]
            return losses, masters, shards
        finally:
            penv.set_global_mesh(None)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ici",))
    l_sh, m_sh, shards = train(mesh)
    l_rep, m_rep, _ = train(None)
    # (16, 4) weight and (4,) bias both divide by 4: sharded dim 0
    assert all(not s.is_fully_replicated for s in shards), shards
    np.testing.assert_allclose(l_sh, l_rep, rtol=1e-5)
    for a, b in zip(m_sh, m_rep):
        np.testing.assert_array_equal(a, b)
    # divisibility gate: an indivisible dim 0 stays replicated
    from paddle_tpu.parallel.sharded_update import \
        eager_accumulator_sharding

    penv.set_global_mesh(mesh)
    try:
        set_flags({"FLAGS_tpu_sharded_weight_update": True})
        assert eager_accumulator_sharding((16, 4)) is not None
        assert eager_accumulator_sharding((31, 4)) is None
        set_flags({"FLAGS_tpu_sharded_weight_update": False})
        assert eager_accumulator_sharding((16, 4)) is None
    finally:
        penv.set_global_mesh(None)


# -- serving: drain on preemption notice ------------------------------------

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402

_MODEL_CFG = serving.TinyLMConfig(vocab=48, embed=24, layers=2,
                                  heads=2, kv_heads=2, head_dim=8,
                                  ffn=48, max_seq=48)
_MODEL = None
_PARAMS = None


def _engine(**over):
    global _MODEL, _PARAMS
    if _MODEL is None:
        _MODEL = serving.TinyDecoderLM(_MODEL_CFG)
        _PARAMS = _MODEL.init_params(seed=3)
    cfg = dict(num_pages=96, page_size=4, max_seqs=6)
    cfg.update(over)
    return serving.Engine(_MODEL, params=_PARAMS,
                          config=serving.EngineConfig(**cfg))


@pytest.fixture
def _fresh_registry():
    obs.reset_registry()
    yield
    obs.reset_registry()


def test_drain_completes_in_flight_within_grace(_fresh_registry):
    """A generous grace window: every in-flight request finishes on
    THIS engine (token streams untouched), nothing migrates, and
    admission stays closed for the doomed engine's remaining life."""
    r = np.random.RandomState(0)
    prompts = [r.randint(0, 48, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    refs = []
    for p in prompts:
        e = _engine()
        q = e.submit(p, max_new_tokens=6)
        e.run_until_idle()
        refs.append(list(q.output_tokens))

    eng = _engine()
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()  # mid-flight when the notice lands
    rep = eng.drain(grace_s=60.0)
    assert rep["completed"] == 3 and rep["migrated"] == []
    assert [list(q.output_tokens) for q in reqs] == refs
    assert all(q.state == serving.RequestState.FINISHED for q in reqs)
    with pytest.raises(RuntimeError, match="drain"):
        eng.submit(prompts[0], max_new_tokens=2)
    snap = obs.registry().snapshot()["counters"]
    assert snap["event.serving_drain"] == 1


def test_drain_migrates_unfinished_and_adopt_is_bit_identical(
        _fresh_registry):
    """Grace too short to finish: the drain exports continuation
    manifests (prompt + already-generated tokens, remaining budget)
    and cancels locally; a survivor engine adopt()s them and the
    stitched streams equal the uninterrupted reference EXACTLY —
    migrate-by-re-prefill under greedy decoding is lossless."""
    r = np.random.RandomState(1)
    prompts = [r.randint(0, 48, size=n).astype(np.int32)
               for n in (7, 4, 11)]
    maxnew = [10, 8, 12]
    refs = []
    for p, m in zip(prompts, maxnew):
        e = _engine()
        q = e.submit(p, max_new_tokens=m)
        e.run_until_idle()
        refs.append(list(q.output_tokens))

    eng = _engine()
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, maxnew)]
    for _ in range(4):
        eng.step()  # partial progress, then the notice
    rep = eng.drain(grace_s=0.0)
    assert rep["completed"] + len(rep["migrated"]) == len(reqs)
    assert rep["migrated"], "grace 0 must migrate the unfinished"
    for q in reqs:
        assert q.state in (serving.RequestState.FINISHED,
                           serving.RequestState.CANCELLED)

    survivor = _engine()
    adopted = survivor.adopt(rep["migrated"])
    survivor.run_until_idle()
    for entry, cont in zip(rep["migrated"], adopted):
        # stitch: tokens the doomed engine already emitted + the
        # survivor's continuation == the uninterrupted stream
        orig = next(q for q, p in zip(reqs, prompts)
                    if entry["prompt"] == [int(t) for t in p]
                    + [int(t) for t in q.output_tokens])
        i = reqs.index(orig)
        assert entry["already_emitted"] == len(orig.output_tokens)
        stitched = list(orig.output_tokens) + list(cont.output_tokens)
        assert stitched == refs[i], \
            "migrated stream differs from uninterrupted reference"
    snap = obs.registry().snapshot()["counters"]
    assert snap["event.serving_drain"] == 1


# -- telemetry contracts ----------------------------------------------------

def test_new_event_shapes_validate_against_schema():
    from paddle_tpu.observability import schema as tschema

    sch = tschema.load_schema()
    env = {"kind": "event", "rank": 0, "step": 4, "ts": 1.0}
    ok = [
        dict(env, event="preempt_notice", grace_s=30.0,
             source="sigterm"),
        dict(env, event="live_resize", old_world=4, new_world=3,
             coordination_s=0.4, mode="live", status="ok",
             generation=1, notice_s=0.01, snapshot_s=0.1,
             rebuild_s=0.3),
        dict(env, event="live_resize", old_world=4, new_world=3,
             coordination_s=4.0, mode="live", status="degraded",
             error="RpcRemoteError('...')"),
        dict(env, event="serving_drain", completed=3, migrated=2,
             grace_s=30.0, dur_ms=12.5),
        dict(env, event="elastic_transition", old_world=4, new_world=3,
             mode="live", coordination_s=0.4),
        dict(env, event="elastic_transition", old_world=4, new_world=3,
             mode="restart", degraded_from_live=True, recovery_s=2.0),
    ]
    for rec in ok:
        assert tschema.validate_record(rec, sch) == [], rec
    bad = [
        dict(env, event="preempt_notice", source="rpc"),   # no grace_s
        dict(env, event="live_resize", old_world=4,
             new_world=3),                         # no coordination_s
        dict(env, event="serving_drain", completed=1),     # no migrated
    ]
    for rec in bad:
        assert tschema.validate_record(rec, sch), rec


def test_perf_analysis_elastic_reports_live_seams(tmp_path):
    """--elastic picks worker-emitted live seams out of the per-rank
    telemetry streams (deduped across survivors) alongside the
    supervisor's restart transitions."""
    tdir = tmp_path / "logs" / "telemetry"
    tdir.mkdir(parents=True)
    seam = {"kind": "event", "event": "live_resize", "rank": 0,
            "step": 6, "ts": 2.0, "old_world": 4, "new_world": 3,
            "mode": "live", "status": "ok", "generation": 1,
            "notice_s": 0.01, "snapshot_s": 0.05, "rebuild_s": 0.4,
            "coordination_s": 0.46}
    trans = dict(seam, event="elastic_transition")
    for rank in (0, 2):
        with open(str(tdir / ("telemetry.rank%d.jsonl" % rank)),
                  "w") as f:
            f.write(json.dumps(dict(seam, rank=rank)) + "\n")
            f.write(json.dumps(dict(trans, rank=rank)) + "\n")
    proc = _sp.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "perf_analysis.py"),
         "--elastic", "--log-dir", str(tmp_path / "logs")],
        stdout=_sp.PIPE, stderr=_sp.STDOUT, text=True, timeout=120,
        cwd=_REPO)
    assert proc.returncode == 0, proc.stdout
    assert "live seam: world 4 -> 3 (ok)" in proc.stdout, proc.stdout
    assert proc.stdout.count("live seam:") == 1, \
        "survivor duplicates must dedup"
    assert "notice 0.010s" in proc.stdout
    assert "rebuild 0.400s" in proc.stdout


# -- supervised acceptance: live 4 -> 3, and degrade-to-restart -------------

def _launch_env():
    from childenv import cpu_child_env

    return cpu_child_env()


def _loss_map(text):
    out = {}
    for ln in text.splitlines():
        if ln.startswith("LOSS"):
            out[int(ln.split()[1])] = float(ln.split()[2])
    return out


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.dist
def test_supervised_live_resize_4_to_3_bit_identical(tmp_path):
    """Acceptance: rank 1 of a supervised 4-rank cohort receives a
    fault-injected preemption notice mid-step-4; the cohort executes
    the LIVE seam — checkpoint-on-signal, doomed rank exits 0 inside
    its grace window, survivors rebuild in place and keep training at
    world 3 — with NO supervisor restart, and the post-seam losses are
    BIT-IDENTICAL to an uninterrupted 3-rank run restored from the
    seam snapshot. The seam's coordination wall time must beat the
    PR 9 restart baseline (process teardown + respawn + rendezvous:
    multiple seconds) by construction — asserted < 5s."""
    import shutil as _shutil

    runner = os.path.join(_DIR, "live_resize_runner.py")
    root = str(tmp_path / "ckpt")
    log_dir = str(tmp_path / "logs")
    hosts = ",".join("127.0.0.1:%d" % p
                     for p in (6851, 6853, 6855, 6857))
    # rank 1's 14th hc_put_part send = step 4's allreduce (1 startup
    # agreement + 3 per step: allreduce, lockstep barrier, sync)
    proc = _sp.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", hosts, "--log_dir", log_dir,
         "--max_restarts", "1", "--min_ranks", "3",
         runner, root, "8", "2", "1", "14"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    # zero downtime: the supervisor never saw a failure, no restart
    assert "restart 1/" not in proc.stdout, proc.stdout
    assert "elastic shrink" not in proc.stdout, proc.stdout

    log0 = open(os.path.join(log_dir, "workerlog.0")).read()
    log1 = open(os.path.join(log_dir, "workerlog.1")).read()
    assert "RESIZED step=4 world=3 rank=0" in log0, log0
    assert "PREEMPTED rank=1 step=4" in log1, log1
    got = _loss_map(log0)
    assert sorted(got) == list(range(8)), log0

    # uninterrupted 3-rank reference restored from the SEAM snapshot
    # (the checkpoint-on-signal save at step 4)
    ref_root = str(tmp_path / "ref_ckpt")
    os.makedirs(ref_root)
    for name in os.listdir(root):
        d = os.path.join(root, name)
        if not os.path.isdir(d):
            continue
        try:
            if ckpt.read_status(d).step_no <= 4:
                _shutil.copytree(d, os.path.join(ref_root, name))
        except OSError:
            continue
    ref_logs = str(tmp_path / "ref_logs")
    ref_hosts = ",".join("127.0.0.1:%d" % p
                         for p in (6861, 6863, 6865))
    ref = _sp.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", ref_hosts, "--log_dir", ref_logs,
         runner, ref_root, "8", "2"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=600)
    assert ref.returncode == 0, ref.stdout
    ref_log0 = open(os.path.join(ref_logs, "workerlog.0")).read()
    assert "RESUME 5 world=3 rank=0" in ref_log0, ref_log0
    ref_losses = _loss_map(ref_log0)
    assert sorted(ref_losses) == [5, 6, 7], ref_log0
    for step in (5, 6, 7):
        assert got[step] == ref_losses[step], (
            "step %d not bit-identical: live %.17g vs 3-rank ref "
            "%.17g" % (step, got[step], ref_losses[step]))

    # the seam is observable: worker-emitted live_resize, schema-valid,
    # with sub-restart coordination time; perf_analysis renders it
    from paddle_tpu.observability import schema as tschema

    sch = tschema.load_schema()
    seams = []
    tdir = os.path.join(log_dir, "telemetry")
    for fname in sorted(os.listdir(tdir)):
        if not fname.startswith("telemetry.rank"):
            continue
        for line in open(os.path.join(tdir, fname)):
            if not line.strip():
                continue
            rec = json.loads(line)
            if rec.get("event") == "live_resize":
                assert tschema.validate_record(rec, sch) == [], rec
                seams.append(rec)
    assert len(seams) == 3, seams  # one per survivor
    for s in seams:
        assert s["old_world"] == 4 and s["new_world"] == 3
        assert s["status"] == "ok" and s["generation"] == 1
        assert 0.0 < s["coordination_s"] < 5.0, s
    pa = _sp.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "perf_analysis.py"),
         "--elastic", "--log-dir", log_dir],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=240)
    assert pa.returncode == 0, pa.stdout
    assert "live seam: world 4 -> 3 (ok)" in pa.stdout, pa.stdout


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.dist
def test_supervised_live_seam_fault_degrades_to_cohort_restart(
        tmp_path):
    """Fault DURING recovery: a second machine dies silently (kill
    exit_code=0 — no crash rc, no marker) inside the seam's agreement
    barrier. The survivors' rebuild fails FAST on the stale heartbeat
    (never a hang), every survivor exits DEGRADE_RC, and the
    supervisor falls back to the PR 9 cohort restart — shrinking by
    the preempt MARKER (the doomed rank exited 0 too) and stamping the
    transition degraded_from_live."""
    runner = os.path.join(_DIR, "live_resize_runner.py")
    root = str(tmp_path / "ckpt")
    log_dir = str(tmp_path / "logs")
    hosts = ",".join("127.0.0.1:%d" % p
                     for p in (6871, 6873, 6875, 6877))
    # preempt rank 1 at step 4 (event 14); rank 2's 17th send is its
    # SEAM barrier contribution (16 = startup + 5 steps x 3) — it dies
    # there, silently
    proc = _sp.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", hosts, "--log_dir", log_dir,
         "--max_restarts", "1", "--min_ranks", "3",
         runner, root, "8", "2", "1", "14", "2", "17"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    assert "live-resize degrade" in proc.stdout, proc.stdout
    assert "preempt marker(s) for rank(s) [1]" in proc.stdout
    assert "elastic shrink 4 -> 3" in proc.stdout, proc.stdout

    log0 = open(os.path.join(log_dir, "workerlog.0")).read()
    assert "DEGRADE step=4" in log0, log0
    # the restarted 3-rank cohort resumed from the seam snapshot and
    # finished the job
    got = _loss_map(log0)
    assert sorted(got) == list(range(8)), log0

    sup = os.path.join(log_dir, "telemetry",
                       "telemetry.supervisor.jsonl")
    evs = [json.loads(ln) for ln in open(sup) if ln.strip()]
    evs = [r for r in evs if r.get("event") == "elastic_transition"]
    assert len(evs) == 1, evs
    ev = evs[0]
    assert ev["old_world"] == 4 and ev["new_world"] == 3
    assert ev["mode"] == "restart"
    assert ev["degraded_from_live"] is True
    assert ev["preempted_ranks"] == [1]
    assert ev["failed_ranks"] == [1]
    from paddle_tpu.observability import schema as tschema

    assert tschema.validate_record(ev, tschema.load_schema()) == []
    # perf_analysis shows BOTH halves of the story: the degraded live
    # seam (from the postmortem bundle) and the restart it fell back to
    pa = _sp.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "perf_analysis.py"),
         "--elastic", "--log-dir", log_dir],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=240)
    assert pa.returncode == 0, pa.stdout
    assert "degraded from live seam" in pa.stdout, pa.stdout
    assert "(degraded)" in pa.stdout, pa.stdout
