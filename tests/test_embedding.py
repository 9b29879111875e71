"""Vocab-sharded embedding engine (paddle_tpu/embedding) — plan
engagement, bit-parity vs the replicated dense reference, 1/N HBM
layout, touched-rows collective bytes, padding_idx/OOV semantics, and
the elastic N' checkpoint round-trip.

Numerics reference: the dense path at PER-VARIABLE collectives
(FLAGS_tpu_comm_bucket_mb=0 — PR-3's lowering, the documented CPU
ground truth; the dense path's own bucketed lowering can drift 1 ulp
on tiny programs at small worlds, the PR-4 CPU-fusion caveat, which
is independent of this engine). The engine itself keeps the bucket
contract: sparse-bucketed == sparse-per-var is asserted below.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.utils.flags import get_flag, set_flags

VOCAB, DIM = 37, 8


@pytest.fixture(autouse=True)
def _flags():
    old = {k: get_flag(k) for k in
           ("FLAGS_tpu_sparse_embedding", "FLAGS_tpu_comm_bucket_mb",
            "FLAGS_tpu_static_checks")}
    yield
    set_flags(old)


def _fresh():
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def _scope():
    from paddle_tpu.core import scope as scope_mod

    return scope_mod._global_scope


def _build(opt="adagrad", two_sites=False, padding_idx=0, infer=False):
    framework.default_main_program().random_seed = 7
    framework.default_startup_program().random_seed = 7
    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    dense = fluid.layers.data(name="dense", shape=[4], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, DIM], is_sparse=True, padding_idx=padding_idx,
        param_attr=fluid.ParamAttr(name="emb_w"))
    parts = [emb, dense]
    if two_sites:
        ids2 = fluid.layers.data(name="ids2", shape=[1], dtype="int64")
        emb2 = fluid.layers.embedding(
            ids2, size=[VOCAB, DIM], is_sparse=True,
            padding_idx=padding_idx,
            param_attr=fluid.ParamAttr(name="emb_w"))
        parts.append(emb2)
    h = fluid.layers.concat(parts, axis=1)
    h = fluid.layers.fc(input=h, size=16, act="relu")
    logits = fluid.layers.fc(input=h, size=2)
    if infer:
        return fluid.layers.softmax(logits), emb
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    O = fluid.optimizer
    {"sgd": lambda: O.SGDOptimizer(learning_rate=0.1),
     "momentum": lambda: O.MomentumOptimizer(learning_rate=0.1,
                                             momentum=0.9),
     "adagrad": lambda: O.AdagradOptimizer(learning_rate=0.1),
     "adam": lambda: O.AdamOptimizer(learning_rate=0.05),
     }[opt]().minimize(loss)
    return loss, emb


def _mesh(prog, ndev, hybrid=False):
    import jax
    from jax.sharding import Mesh

    if hybrid:
        prog._mesh = Mesh(np.array(jax.devices()[:ndev]).reshape(
            ndev // 2, 2), ("dcn", "ici"))
    elif ndev != 8:
        prog._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))


def _batch(seed=0, full_cover=False, batch=48):
    # 48 divides every mesh size used here (2, 3, 4, 8) and covers
    # the 37-row vocab when full_cover asks for it
    r = np.random.RandomState(seed)
    if full_cover:
        # every row touched (incl. padding 0, whose grads mask out):
        # adam's dense update moves momentum-tail rows even at zero
        # grad, so exactness vs dense needs full coverage (the lazy
        # contract, documented in embedding/README.md)
        base = np.arange(VOCAB)
        extra = r.randint(0, VOCAB, (batch - VOCAB,))
        ids = np.concatenate([base, extra])
        r.shuffle(ids)
    else:
        ids = r.randint(0, VOCAB, (batch,))
    return {"ids": ids.reshape(batch, 1).astype("int64"),
            "ids2": r.randint(0, VOCAB, (batch, 1)).astype("int64"),
            "dense": r.rand(batch, 4).astype("float32"),
            "label": r.randint(0, 2, (batch, 1)).astype("int64")}


def _state_snapshot(prog):
    from paddle_tpu.parallel.sharded_update import unshard_scope_value

    out = {}
    for n in sorted(_scope().local_var_names()):
        v = _scope().find_var(n)
        if v is None:
            continue
        out[n] = np.asarray(unshard_scope_value(prog, n, v)).copy()
    return out


def _train(sparse, opt="adagrad", ndev=4, hybrid=False, steps=4,
           bucket_mb=0.0, two_sites=False, full_cover=None,
           feed=None, seed_state=None, want_plan=True):
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": sparse,
               "FLAGS_tpu_comm_bucket_mb": bucket_mb})
    if full_cover is None:
        full_cover = opt in ("adam", "momentum")
    feed = feed or _batch(full_cover=full_cover)
    if not two_sites:
        feed = {k: v for k, v in feed.items() if k != "ids2"}
    with framework.unique_name_guard():
        loss, emb = _build(opt, two_sites=two_sites)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, ndev, hybrid)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        if seed_state:
            for n, v in seed_state.items():
                if _scope().find_var(n) is not None:
                    _scope().set_var(n, v.copy())
        losses = [float(exe.run(prog, feed=feed,
                                fetch_list=[loss])[0].mean())
                  for _ in range(steps)]
        plan = getattr(prog, "_sparse_plan", None)
        snap = _state_snapshot(prog)
    if sparse and want_plan:
        assert plan is not None, \
            getattr(prog, "_sparse_embedding_fallback", None)
        assert "emb_w" in plan.tables
    if not sparse:
        assert plan is None
    return losses, snap, plan, exe, prog


def _assert_state_equal(a, b, moment_ulps=0, ulps=0):
    """Bit-identical state, except where a caller bounds a KNOWN
    reorder of jaxlib 0.9.0's XLA:CPU in float32 ulps: `moment_ulps`
    for optimizer moments only, `ulps` for every var."""
    keys = sorted(set(a) & set(b))
    assert keys
    for n in keys:
        bound = max(ulps, moment_ulps if "_moment" in n else 0)
        if bound:
            assert _ulp_dist(a[n], b[n]) <= bound, \
                "state %r drifts %d ulps (bound %d)" % (
                    n, _ulp_dist(a[n], b[n]), bound)
            continue
        assert np.array_equal(a[n], b[n]), \
            "state %r differs (max delta %g)" % (
                n, float(np.abs(a[n].astype(np.float64)
                                - b[n].astype(np.float64)).max()))


# -- plan engagement ---------------------------------------------------------

def test_plan_engagement_and_flag_off():
    _, _, plan, _, prog = _train(True, "adagrad", ndev=4)
    t = plan.tables["emb_w"]
    assert t.opt_type == "adagrad"
    assert list(t.row_state) == ["Moment"]
    assert plan.state_vars[t.row_state["Moment"]].shape == (VOCAB, DIM)
    # padded to a multiple of the shard count
    assert t.info.padded_rows == 40 and t.info.rows_local == 10
    _train(False, "adagrad", ndev=4)  # asserts plan is None


def test_declines_are_recorded_not_fatal():
    # global-norm clip reads every grad -> the table degrades to the
    # dense path with a structured reason, and training still runs
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True})
    feed = _batch()
    feed.pop("ids2")
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 7
        framework.default_startup_program().random_seed = 7
        ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
        dense = fluid.layers.data(name="dense", shape=[4],
                                  dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        emb = fluid.layers.embedding(ids, size=[VOCAB, DIM],
                                     is_sparse=True)
        h = fluid.layers.concat([emb, dense], axis=1)
        logits = fluid.layers.fc(input=h, size=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(0.5))
        fluid.optimizer.AdagradOptimizer(
            learning_rate=0.1).minimize(loss)
        fluid.clip._clip_attr.clear()
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert getattr(prog, "_sparse_plan", None) is None
        reasons = [f["reason"] for f in
                   prog._sparse_embedding_fallback]
        assert any("touched outside" in r for r in reasons), reasons


# -- bit-parity vs the replicated dense reference ----------------------------

@pytest.mark.parametrize("opt,ndev,hybrid", [
    ("sgd", 2, False),
    ("adagrad", 4, False),
    ("adam", 8, False),
    ("adagrad", 4, True),   # hybrid 2x2: table replicated over dcn
])
def test_parity_vs_dense(opt, ndev, hybrid):
    ls, ss, _, _, _ = _train(True, opt, ndev=ndev, hybrid=hybrid)
    ld, sd, _, _, _ = _train(False, opt, ndev=ndev, hybrid=hybrid)
    assert ls == ld
    # losses and every parameter stay bit-identical. Adagrad's moment
    # update `m + g*g` is a mul-add that XLA:CPU (jaxlib 0.9.0)
    # contracts into an FMA in one of the two programs and not the
    # other (their fusion boundaries differ): measured 1 f32 ulp on
    # the table's moment, never more, and nothing downstream moves
    _assert_state_equal(ss, sd, moment_ulps=1 if opt == "adagrad" else 0)


@pytest.mark.slow
@pytest.mark.parametrize("opt", ["sgd", "momentum", "adagrad", "adam"])
@pytest.mark.parametrize("ndev,hybrid", [(2, False), (4, False),
                                         (8, False), (4, True),
                                         (8, True)])
def test_parity_matrix_full(opt, ndev, hybrid):
    ls, ss, _, _, _ = _train(True, opt, ndev=ndev, hybrid=hybrid)
    ld, sd, _, _, _ = _train(False, opt, ndev=ndev, hybrid=hybrid)
    assert ls == ld
    _assert_state_equal(ss, sd)


def test_sparse_keeps_bucket_contract():
    # the engine composes with PR-4 bucketed collectives for the DENSE
    # params without breaking their bit-identity to per-var. ndev=4:
    # at ndev=2 this tiny program's DENSE fc-bias bucket drifts 1 ulp
    # off per-var on XLA:CPU with or without the sparse engine (the
    # PR-4 CPU-fusion caveat) — not an engine property
    lb, sb, _, _, _ = _train(True, "adagrad", ndev=4, bucket_mb=25.0)
    lp, sp, _, _, _ = _train(True, "adagrad", ndev=4, bucket_mb=0.0)
    assert lb == lp
    # parameters bit-identical; the fc bias's Adagrad moment sits 1 ulp
    # off under jaxlib 0.9.0 (the `m + g*g` FMA contraction of
    # test_parity_vs_dense, here between bucketed and per-var fusions)
    _assert_state_equal(sb, sp, moment_ulps=1)


def _ulp_dist(a, b):
    """Max elementwise distance in float32 ulps (int32 lexicographic
    view, monotone across the sign bit; both zeros map to 0)."""
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    ai = np.where(ai < 0, np.int64(-0x80000000) - ai, ai)
    bi = np.where(bi < 0, np.int64(-0x80000000) - bi, bi)
    return int(np.abs(ai - bi).max()) if a.size else 0


def test_dense_fc_bucket_cpu_drift_is_ulp_bounded_at_ndev2():
    """Regression pin for the PR 15 observation (see ROADMAP): at
    ndev=2 this tiny program's DENSE fc-bias bucket can drift off the
    per-var lowering on XLA:CPU by at most ONE float32 ulp (the PR-4
    CPU-fusion caveat — /N + cast regrouping past the optimization
    barriers). This pins the drift BOUNDED, per state var, per step:
    a >1-ulp delta means the bucketed dense lowering regressed, not
    the known fusion artifact. The sparse table and its moments stay
    bit-exact regardless — the caveat is not an engine property."""
    lb, sb, _, _, _ = _train(True, "adagrad", ndev=2, bucket_mb=25.0,
                             steps=1)
    lp, sp, _, _, _ = _train(True, "adagrad", ndev=2, bucket_mb=0.0,
                             steps=1)
    for n in sb:
        if n.startswith("emb_w"):
            assert np.array_equal(sb[n], sp[n]), \
                "sparse engine state must stay bit-exact: %s" % n
    worst = {n: _ulp_dist(sb[n], sp[n]) for n in sorted(sb)}
    assert max(worst.values()) <= 1, worst
    assert _ulp_dist(np.float32(lb), np.float32(lp)) <= 1, (lb, lp)


def test_two_sites_one_table_parity():
    ls, ss, plan, _, _ = _train(True, "adagrad", ndev=4,
                                two_sites=True)
    assert len(plan.tables["emb_w"].sites) == 2
    ld, sd, _, _, _ = _train(False, "adagrad", ndev=4, two_sites=True)
    assert ls == ld
    # two sites feed ONE table gradient: the sparse engine segment-sums
    # the concatenated rows of both sites, the dense reference adds two
    # scatter-added gradients — two orders of the same float32 sum.
    # Older XLA:CPU happened to order them alike; jaxlib 0.9.0 does not
    # (2 ulps on the table after ONE sgd-like step, measured), and four
    # Adagrad steps carry that to 64 ulps on fc_0.w. The losses stay
    # identical; the state is held to 128 ulps (1.5e-5 relative)
    _assert_state_equal(ss, sd, ulps=128)


# -- layout: 1/N HBM, touched-rows collective bytes --------------------------

def test_table_and_moment_hbm_is_one_over_n():
    _, _, plan, _, prog = _train(True, "adagrad", ndev=4)
    import jax

    for name, info in plan.state_vars.items():
        v = _scope().find_var(name)
        assert isinstance(v, jax.Array)
        assert tuple(v.shape) == (40, DIM)
        shards = v.addressable_shards
        per_dev = {s.device.id: s.data.shape for s in shards}
        on_mesh = [d.id for d in prog._mesh.devices.reshape(-1)]
        for did in on_mesh:
            assert per_dev[did] == (10, DIM), (name, per_dev)
        # replicated devices (off-mesh) hold nothing extra: the mesh
        # spans 4 of 8 devices here
    # save path: logical shape round-trips
    from paddle_tpu.parallel.sharded_update import unshard_scope_value

    w = unshard_scope_value(prog, "emb_w", _scope().find_var("emb_w"))
    assert w.shape == (VOCAB, DIM)


def test_collective_bytes_scale_with_batch_not_vocab():
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True,
               "FLAGS_tpu_comm_bucket_mb": 0.0})
    feed = _batch()
    feed.pop("ids2")
    with framework.unique_name_guard():
        loss, _ = _build("adagrad")
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(prog, feed=feed, fetch_list=[loss])
        col = exe.collective_report(prog, feed=feed,
                                    fetch_list=[loss])
    assert col["total_ici_bytes"] > 0
    # the dense path syncs a (VOCAB, DIM) fp32 grad per step: any
    # single collective that big would be vocab-proportional
    dense_grad_bytes = VOCAB * DIM * 4
    biggest = max(
        v["tensor_bytes"] / max(v["count"], 1)
        for k, v in col.items()
        if isinstance(v, dict) and "tensor_bytes" in v)
    assert biggest < dense_grad_bytes
    # the sparse schedule's signature collectives are present: ids/tap
    # all_gathers and the lookup psum_scatter
    assert col.get("all_gather", {}).get("count", 0) >= 2
    assert col.get("reduce_scatter", {}).get("count", 0) >= 1


# -- padding_idx / OOV semantics --------------------------------------------

def test_padding_idx_rows_zero_and_frozen():
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True,
               "FLAGS_tpu_comm_bucket_mb": 0.0})
    feed = _batch()
    feed.pop("ids2")
    feed["ids"][:8] = 0  # padding id
    with framework.unique_name_guard():
        loss, emb = _build("adagrad")
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        w0 = np.asarray(_scope().find_var("emb_w"))[0].copy()
        for _ in range(3):
            out = exe.run(prog, feed=feed, fetch_list=[loss, emb])
        emb_out = np.asarray(out[1])
        # padding positions look up exact zeros
        assert np.array_equal(emb_out[:8], np.zeros((8, DIM), "f"))
        # the padding row never trains (reference contract)
        from paddle_tpu.parallel.sharded_update import \
            unshard_scope_value

        w = unshard_scope_value(prog, "emb_w",
                                _scope().find_var("emb_w"))
        assert np.array_equal(np.asarray(w)[0], w0)


def test_oov_raises_under_static_checks():
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True,
               "FLAGS_tpu_static_checks": "error"})
    feed = _batch()
    feed.pop("ids2")
    with framework.unique_name_guard():
        loss, _ = _build("sgd")
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(prog, feed=feed, fetch_list=[loss])  # in-range: fine
        bad = dict(feed)
        bad["ids"] = feed["ids"].copy()
        bad["ids"][3] = VOCAB + 5
        with pytest.raises(ValueError, match="out-of-range"):
            exe.run(prog, feed=bad, fetch_list=[loss])
        # warn mode: non-fatal, like every other checker on the flag
        set_flags({"FLAGS_tpu_static_checks": "warn"})
        with pytest.warns(UserWarning, match="out-of-range"):
            exe.run(prog, feed=bad, fetch_list=[loss])
        # flag off: silent (sharded lookup yields a zero row)
        set_flags({"FLAGS_tpu_static_checks": "off"})
        exe.run(prog, feed=bad, fetch_list=[loss])


# -- elastic checkpoint round-trip (N' != N) --------------------------------

@pytest.mark.parametrize("new_ndev", [2, 3])
def test_checkpoint_reshard_roundtrip(new_ndev):
    # train at 4 devs, snapshot LOGICAL state, resume at N' devs ==
    # dense replicated resumed from the same snapshot, bit-identical
    # (incl. genuinely different row padding: vocab 37 -> 40 at 4,
    # 38 at 2, 39 at 3)
    _, snap, _, _, _ = _train(True, "adagrad", ndev=4, steps=3)
    ls, ss, plan, _, _ = _train(True, "adagrad", ndev=new_ndev,
                                steps=3, seed_state=snap)
    assert plan.tables["emb_w"].info.padded_rows == \
        -(-VOCAB // new_ndev) * new_ndev
    ld, sd, _, _, _ = _train(False, "adagrad", ndev=new_ndev, steps=3,
                             seed_state=snap)
    assert ls == ld
    _assert_state_equal(ss, sd)


def test_stale_world_padding_strips_on_restore():
    # a scope value arriving as the OLD world's padded (40, D) buffer
    # restores bit-identically at ndev=3 (padded 39)
    _, snap, _, _, _ = _train(True, "adagrad", ndev=4, steps=2)
    padded = {n: v for n, v in snap.items()}
    padded["emb_w"] = np.pad(snap["emb_w"], ((0, 3), (0, 0)))  # (40,D)
    ls, ss, _, _, _ = _train(True, "adagrad", ndev=3, steps=2,
                             seed_state=padded)
    lref, sref, _, _, _ = _train(True, "adagrad", ndev=3, steps=2,
                                 seed_state=snap)
    assert ls == lref
    _assert_state_equal(ss, sref)


# -- forward-only programs ---------------------------------------------------

def test_forward_only_table_stays_sharded():
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True})
    feed = _batch()
    with framework.unique_name_guard():
        prob, emb = _build(infer=True)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel()
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out_s = np.asarray(exe.run(
            prog, feed={"ids": feed["ids"], "dense": feed["dense"]},
            fetch_list=[emb])[0])
        assert getattr(prog, "_sparse_plan", None) is not None
        import jax

        w = _scope().find_var("emb_w")
        assert isinstance(w, jax.Array) and tuple(w.shape) == (40, DIM)
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": False})
    with framework.unique_name_guard():
        prob, emb = _build(infer=True)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel()
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out_d = np.asarray(exe.run(
            prog, feed={"ids": feed["ids"], "dense": feed["dense"]},
            fetch_list=[emb])[0])
    assert np.array_equal(out_s, out_d)


# -- bench block: registry-assembled + schema-valid telemetry ---------------

def test_embedding_block_is_registry_assembled(tmp_path):
    import json
    import os

    from paddle_tpu import observability as obs
    from paddle_tpu.observability import publish, schema

    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True})
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    feed = _batch()
    feed.pop("ids2")
    try:
        with framework.unique_name_guard():
            loss, _ = _build("adagrad")
            prog = fluid.default_main_program()
            fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name)
            _mesh(prog, 4)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            exe.run(prog, feed=feed, fetch_list=[loss])
            blocks = publish.bench_blocks(exe, prog, feed, [loss])
            # the registry is the source of truth: what bench attaches
            # IS what the registry holds
            assert blocks == obs.registry().blocks()
            emb = blocks["embedding"]
            assert "emb_w" in emb["tables"]
            t = emb["tables"]["emb_w"]
            assert t["vocab"] == VOCAB and t["rows_per_replica"] == 10
            assert emb["shards"] == 4
            # per-replica state is the 1/N shard of table + moment
            assert emb["state_per_replica_bytes"] == 2 * 10 * DIM * 4
            # dense reference: one vocab-sized grad allreduce — scales
            # with VOCAB; the sparse schedule scales with touched rows
            # (the < crossover needs real vocab sizes: bench.py
            # --embedding at vocab 20k shows 0.28MB vs 9.9MB)
            assert emb["modeled_dense_sync_bytes_per_step"] == \
                2 * VOCAB * DIM * 4
            assert emb["touched_rows_per_step"] == 48
            # the JSONL stream stays schema-valid with the new events
            jsonl = blocks["telemetry"]["jsonl"]
            assert jsonl and os.path.exists(jsonl)
            lines = [json.loads(ln) for ln in open(jsonl)]
            assert schema.validate_records(lines) == []
    finally:
        obs.reset_registry()


@pytest.mark.slow
def test_perf_analysis_embedding_cli(tmp_path):
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "tools", "perf_analysis.py"),
         "--embedding"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    diff = json.load(open(os.path.join(repo, "artifacts",
                                       "embedding_diff.json")))
    assert diff["tables_sharded"] == 4
    assert diff["state_bytes"]["per_replica"] * diff["ndev"] == \
        diff["state_bytes"]["logical"]
    assert diff["largest_sharded_collective_bytes"] < \
        diff["smallest_vocab_grad_bytes"]
    assert diff["row_cache"]["evicted_rows"] > 0


@pytest.mark.slow
def test_bench_embedding_leg_inprocess():
    """bench.py's --embedding body on the 8-device CPU mesh (the command
    line itself refuses a backend that is not tpu)."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        import bench
    finally:
        sys.path.pop(0)
    res = bench._bench_embedding(steps=4)
    assert res["tables_sharded"] == 8
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    emb = res["embedding"]
    assert emb["state_per_replica_bytes"] * emb["shards"] == \
        pytest.approx(emb["state_logical_bytes"], rel=0.01)
    assert emb["modeled_sparse_sync_bytes_per_step"] < \
        emb["modeled_dense_sync_bytes_per_step"]


# -- engine units ------------------------------------------------------------

def test_fetching_sparse_grad_densifies():
    # debug fetch of a planned table's gradient: the SelectedRows grad
    # stays bound past its optimizer op and densifies to the logical
    # (vocab, dim) mean gradient at fn exit (the checker warns, the
    # run must not crash)
    _fresh()
    set_flags({"FLAGS_tpu_sparse_embedding": True,
               "FLAGS_tpu_comm_bucket_mb": 0.0})
    feed = _batch()
    feed.pop("ids2")
    with framework.unique_name_guard():
        loss, _ = _build("sgd")
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        _mesh(prog, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out = exe.run(prog, feed=feed,
                      fetch_list=[loss, "emb_w@GRAD"])
        g = np.asarray(out[1])
        assert g.shape == (VOCAB, DIM)
        touched = np.unique(feed["ids"].reshape(-1))
        untouched = np.setdiff1d(np.arange(VOCAB), touched)
        assert np.abs(g[touched]).sum() > 0
        assert np.array_equal(g[untouched],
                              np.zeros((len(untouched), DIM), "f"))


def test_aggregate_rows_matches_dense_association():
    # duplicate ids across replicas: per-replica partials folded in
    # replica order, then /world — the pmean association, exactly
    import jax

    from paddle_tpu.embedding.engine import _aggregate_rows
    from paddle_tpu.embedding.planner import SparseTablePlan

    plan = SparseTablePlan.__new__(SparseTablePlan)
    plan.ndev = 2
    plan.dcn_size = 2  # world 4, hybrid fold (pods of 2)
    ids = np.array([3, 5, 3, 7, 5, 3, 9, 3], np.int32)  # 4 slices of 2
    vals = np.linspace(0.1, 1.7, 16).reshape(8, 2).astype("f")
    rows, grads = jax.jit(
        lambda i, v: _aggregate_rows(i, v, plan))(ids, vals)
    rows, grads = np.asarray(rows), np.asarray(grads)
    ref = {}
    for d in range(2):  # dense association: pod partials, then pods
        for r in range(2):
            part = {}
            for k in range(2):
                pos = (d * 2 + r) * 2 + k
                part[ids[pos]] = part.get(
                    ids[pos], np.zeros(2, "f")) + vals[pos]
            for i, v in part.items():
                ref[i] = ref.get(i, np.zeros(2, "f")) + v
    for i, v in ref.items():
        slot = list(rows).index(i)
        assert np.array_equal(grads[slot], v / 4.0), (i, grads[slot],
                                                      v / 4.0)


def test_foreign_op_on_engine_value_raises():
    # runtime twin of the sparse-update lint error: an op consuming a
    # TableShard/SparseRowGrad without a rule fails loudly at trace
    from paddle_tpu.embedding import engine as eng
    from paddle_tpu.embedding.planner import (RowShardInfo,
                                              SparseTablePlan)

    plan = SparseTablePlan(axis="dp", ndev=2, dcn_axis=None,
                           dcn_size=1, tables={})
    info = RowShardInfo("w", (8, 2), "float32", 2)

    class FakeOp:
        type = "elementwise_pow"
        input_names = {"X": ["w"]}
        output_names = {"Out": ["o"]}
        attrs = {}

    tok = eng._ACTIVE.set(plan)
    try:
        with pytest.raises(RuntimeError, match="sparse-aware rule"):
            eng.maybe_exec(FakeOp(), {"w": eng.TableShard(
                np.zeros((4, 2), "f"), info)})
    finally:
        eng._ACTIVE.reset(tok)
