"""Persistent compilation cache + AOT warmup (ROADMAP direction 4).

Covers: cross-process warm restart (bit-identical outputs, miss ->
hit), fingerprint invalidation on lowering-relevant flag flips and
mesh-shape changes, in-memory LRU eviction dropping AOT artifacts
while the persistent tier survives (re-admission is a HIT, not a fresh
compile), the `Executor.warmup` surface (feed-shape buckets + elastic
mesh variants, no state mutation), the registry-assembled
`compile_cache` bench block, telemetry-schema validity of the new
events, and the supervised elastic shrink's coordination/compile
recovery split.
"""
import json
import os as _os
import subprocess as _sp
import sys as _sys

import numpy as np
import pytest

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_RUNNER = _os.path.join(_REPO, "tests", "compile_cache_runner.py")


def _base_env(**extra):
    env = dict(_os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(extra)
    return env


@pytest.fixture
def cc_env(tmp_path, monkeypatch):
    """Arm the persistent tier at a tmp dir for one test; restore the
    environment, the size flag, jax config, module stats and registry
    afterwards."""
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid import compile_cache as cc
    from paddle_tpu.utils.flags import get_flag, set_flags

    old = {"FLAGS_tpu_compile_cache_size":
           get_flag("FLAGS_tpu_compile_cache_size")}
    cdir = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cdir)
    cc._reset_for_tests()
    obs.reset_registry()
    from paddle_tpu.observability import flight

    flight._reset_for_tests()
    yield cdir
    cc.disable()
    cc._reset_for_tests()
    set_flags(old)
    obs.reset_registry()
    flight._reset_for_tests()


def _build(width=16):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), \
            framework.unique_name_guard():
        main.random_seed = startup.random_seed = 7
        x = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        h = fluid.layers.fc(input=x, size=width, act="tanh")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - y))
        fluid.optimizer.SGDOptimizer(
            learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=4):
    rng = np.random.RandomState(42)
    return {"x": rng.randn(batch, 8).astype("float32"),
            "y": rng.randn(batch, 1).astype("float32")}


def _cc_events():
    from paddle_tpu.observability import flight

    return [e for e in flight.recorder().snapshot()["events"]
            if e.get("event") == "compile_cache"]


# -- cross-process warm restart (the acceptance proof) ------------------

def test_warm_restart_second_process_hits_bit_identical(tmp_path):
    """A second process running the same program must classify every
    fresh compile as a persistent-cache HIT, record compile_cache
    events saying so, and produce bit-identical losses."""
    cache = str(tmp_path / "cache")
    results, streams = [], []
    for i in (1, 2):
        tdir = str(tmp_path / ("telemetry%d" % i))
        proc = _sp.run(
            [_sys.executable, _RUNNER, "3"],
            env=_base_env(JAX_COMPILATION_CACHE_DIR=cache,
                          FLAGS_tpu_telemetry_dir=tdir),
            cwd=_REPO, stdout=_sp.PIPE, stderr=_sp.STDOUT, text=True,
            timeout=240)
        assert proc.returncode == 0, proc.stdout
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        results.append(json.loads(line[len("RESULT "):]))
        recs = []
        for fname in sorted(_os.listdir(tdir)):
            if fname.startswith("telemetry.rank") and \
                    fname.endswith(".jsonl"):
                with open(_os.path.join(tdir, fname)) as f:
                    recs.extend(json.loads(ln) for ln in f
                                if ln.strip())
        streams.append(recs)

    cold, warm = results
    assert cold["enabled"] and warm["enabled"]
    # bit-identical: the warm process deserialized, it did not diverge
    assert cold["losses"] == warm["losses"]
    assert cold["misses"] >= 1 and cold["hits"] == 0
    assert warm["hits"] >= 1 and warm["misses"] == 0

    cold_evs = [r for r in streams[0]
                if r.get("event") == "compile_cache"]
    warm_evs = [r for r in streams[1]
                if r.get("event") == "compile_cache"]
    assert cold_evs and all(e["status"] == "miss" for e in cold_evs)
    assert warm_evs and all(e["status"] == "hit" for e in warm_evs)
    # the hit's saved_ms is bookkept from the cold process's sentinel
    assert any(e["saved_ms"] >= 0.0 for e in warm_evs)
    # misses record the on-disk bytes they wrote
    assert any(e["bytes"] > 0 for e in cold_evs)
    # same fingerprints across processes (determinism of the key)
    assert sorted(e["key"] for e in cold_evs) == \
        sorted(e["key"] for e in warm_evs)
    # every record in both streams validates against the locked schema
    from paddle_tpu.observability import schema as tschema

    sch = tschema.load_schema()
    for recs in streams:
        assert tschema.validate_records(recs, sch) == []


# -- fingerprint semantics ----------------------------------------------

def test_fingerprint_invalidates_on_flags_and_mesh(cc_env):
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.fluid import compile_cache as cc
    from paddle_tpu.utils.flags import get_flag, set_flags

    text = "module @jit_f { func @main() { return } }"
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("dp",))
    base = cc.fingerprint(text, mesh2)
    assert base == cc.fingerprint(text, mesh2)  # deterministic
    assert base != cc.fingerprint(text, mesh4)  # mesh shape keys
    assert base != cc.fingerprint(text + " ", None)
    flips = {
        "FLAGS_tpu_comm_bucket_mb": 1.0,
        "FLAGS_tpu_amp_level": "O2",
        "FLAGS_tpu_dcn_replicas": 2,
        "FLAGS_tpu_sharded_weight_update": False,
    }
    for name, val in flips.items():
        old = get_flag(name)
        assert val != old, name
        set_flags({name: val})
        try:
            assert cc.fingerprint(text, mesh2) != base, \
                "flipping %s must invalidate the cache key" % name
        finally:
            set_flags({name: old})
    assert cc.fingerprint(text, mesh2) == base  # restored -> same key
    # loc() debug metadata is NOT part of the key (repo moves must not
    # cold-start the fleet)
    assert cc.fingerprint(
        'module @jit_f loc("/tmp/x.py":1:2) { }', mesh2) == \
        cc.fingerprint('module @jit_f loc("/elsewhere.py":9:9) { }',
                       mesh2)


def test_same_program_same_fingerprint_in_process_hit(cc_env):
    """An identical program rebuilt in the SAME process fingerprints
    identically and classifies as a hit via the index sentinel."""
    import paddle_tpu.fluid as fluid

    main1, startup1, loss1 = _build()
    exe = fluid.Executor()
    exe.run(startup1)
    exe.run(main1, feed=_feed(), fetch_list=[loss1.name])
    evs = _cc_events()
    assert evs and evs[-1]["status"] == "miss"

    main2, startup2, loss2 = _build()
    exe2 = fluid.Executor()
    exe2.run(startup2)
    exe2.run(main2, feed=_feed(), fetch_list=[loss2.name])
    evs2 = _cc_events()[len(evs):]
    by_status = [e["status"] for e in evs2]
    assert "hit" in by_status and "miss" not in by_status, evs2
    # identical structure -> identical fingerprint
    keys1 = {e["key"] for e in evs}
    keys2 = {e["key"] for e in evs2}
    assert keys2 <= keys1


# -- LRU eviction interplay ---------------------------------------------

def test_eviction_drops_aot_and_readmission_is_persistent_hit(cc_env):
    """FLAGS_tpu_compile_cache_size eviction drops entry.aot_compiled
    eagerly; the evicted program re-admitted later is a
    persistent-cache HIT, not a fresh compile."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.utils.flags import set_flags

    set_flags({"FLAGS_tpu_compile_cache_size": 1})
    main_a, startup_a, loss_a = _build(width=16)
    exe = fluid.Executor()
    exe.run(startup_a)  # evicted by the next insert (limit 1)
    exe.run(main_a, feed=_feed(), fetch_list=[loss_a.name])
    assert len(exe._cache) == 1
    entry_a = next(iter(exe._cache.values()))
    # populate the AOT artifact the report surfaces memoize
    assert exe.donation_report(main_a, feed=_feed(),
                               fetch_list=[loss_a.name]) is not None
    assert entry_a.aot_compiled is not None

    main_b, startup_b, loss_b = _build(width=24)
    exe.run(startup_b)  # evicts A's entry
    assert entry_a.aot_compiled is None, \
        "eviction must drop AOT artifacts eagerly"
    exe.run(main_b, feed=_feed(), fetch_list=[loss_b.name])

    n_before = len(_cc_events())
    exe.run(main_a, feed=_feed(), fetch_list=[loss_a.name])
    readmit = _cc_events()[n_before:]
    assert readmit and readmit[-1]["status"] == "hit", readmit


# -- warmup surface ------------------------------------------------------

def test_warmup_precompiles_without_mutating_state(cc_env):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.fluid import compile_cache as cc

    main, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)
    params = [p.name for p in main.all_parameters()]
    before = {n: np.asarray(global_scope().find_var(n)).copy()
              for n in params}
    seed_counter = main._seed_counter

    rep = exe.warmup(main, shapes=[{"x": (4, 8), "y": (4, 1)}],
                     fetch_list=[loss.name])
    assert len(rep["compiled"]) == 1 and not rep["skipped"], rep
    assert main._seed_counter == seed_counter  # RNG stream untouched
    for n in params:
        after = np.asarray(global_scope().find_var(n))
        assert (before[n] == after).all(), \
            "warmup mutated state %s" % n
    evs = _cc_events()
    assert any(e["source"] == "warmup" for e in evs)

    # the first REAL step of the warmed shape pays zero XLA compiles
    snap = cc.jax_stats()
    out = exe.run(main, feed=_feed(), fetch_list=[loss.name])
    assert np.isfinite(np.asarray(out[0])).all()
    assert cc.stats_delta(snap)["backend_compiles"] == 0, \
        "warmed shape must not recompile on first traffic"


def test_warmup_shape_validation_and_cached_report(cc_env):
    import paddle_tpu.fluid as fluid

    main, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)
    rep = exe.warmup(main, shapes=[{"x": (-1, 8), "y": (4, 1)}],
                     fetch_list=[loss.name])
    assert rep["skipped"] and \
        "concrete" in rep["skipped"][0]["error"]
    exe.warmup(main, shapes=[{"x": (4, 8), "y": (4, 1)}],
               fetch_list=[loss.name])
    rep2 = exe.warmup(main, shapes=[{"x": (4, 8), "y": (4, 1)}],
                      fetch_list=[loss.name])
    assert rep2["cached"] and not rep2["compiled"]


def test_warmup_mesh_variants_populate_persistent_tier(cc_env):
    """Data-parallel program: warmup(meshes=[...]) pre-compiles OTHER
    mesh topologies into the persistent tier via a program clone —
    the live program and in-memory LRU stay untouched."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache as cc

    main, startup, loss = _build()
    main._data_parallel = True
    exe = fluid.Executor()
    exe.run(startup)
    rep = exe.warmup(main, shapes=[{"x": (8, 8), "y": (8, 1)}],
                     meshes=[4, 2], fetch_list=[loss.name])
    n_cache = len(exe._cache)
    # base mesh + 2 variants compiled; batch 8 divides 8, 4 and 2
    assert len(rep["compiled"]) == 3, rep
    # the live program keeps ITS mesh (the full 8-device default its
    # own compile pinned); variant meshes only ever touch the clone
    import jax

    assert main._mesh is not None
    assert main._mesh.devices.size == len(jax.devices())
    # variant entries never land in the in-memory LRU (clone compiles
    # run with use_cache off): base bucket + startup only
    assert n_cache == 2, exe._cache.keys()
    st = cc.stats()
    assert st["index_entries"] >= 3
    assert st["persistent_entries"] > 0


def test_warmup_borrows_shapes_and_reports_oversized_variants(cc_env):
    """meshes= without shapes borrows the feed buckets of entries real
    traffic already compiled; an integer variant exceeding the local
    device count lands in report["skipped"], never silently drops."""
    import paddle_tpu.fluid as fluid

    main, startup, loss = _build()
    main._data_parallel = True
    exe = fluid.Executor()
    exe.run(startup)
    # no traffic yet and no shapes: nothing to borrow
    rep0 = exe.warmup(main, meshes=[4], fetch_list=[loss.name])
    assert rep0["skipped"] and "shapes" in rep0["skipped"][0]["reason"]
    exe.run(main, feed=_feed(batch=8), fetch_list=[loss.name])
    rep = exe.warmup(main, meshes=[4, 99], fetch_list=[loss.name])
    assert len(rep["compiled"]) == 1, rep  # borrowed (8, ...) bucket
    over = [s for s in rep["skipped"]
            if s.get("mesh_devices") == 99]
    assert over and "device count" in over[0]["reason"], rep


def test_warmup_enters_hbm_preflight_gate(cc_env):
    """A warmup-cached entry must not let the first real run cache-hit
    past FLAGS_tpu_hbm_budget_mb: an over-budget bucket is reported
    skipped and NOT cached."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.utils.flags import set_flags

    main, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)
    set_flags({"FLAGS_tpu_hbm_budget_mb": 1e-6})  # below any program
    try:
        rep = exe.warmup(main, shapes=[{"x": (4, 8), "y": (4, 1)}],
                         fetch_list=[loss.name])
    finally:
        set_flags({"FLAGS_tpu_hbm_budget_mb": 0.0})
    assert rep["skipped"] and not rep["compiled"], rep
    assert "HbmBudgetExceeded" in rep["skipped"][0]["error"] or \
        "budget" in rep["skipped"][0]["error"].lower(), rep
    # the rejected entry is NOT left in the LRU (startup's entry only)
    assert len(exe._cache) == 1


def test_elastic_mesh_variants_enumeration():
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import env as penv

    devs = jax.devices()
    flat = Mesh(np.array(devs), ("dp",))
    variants = penv.elastic_mesh_variants(flat, min_ranks=5)
    assert [n for n, _ in variants] == [7, 6, 5]
    assert all(m.axis_names == ("dp",) for _, m in variants)
    # pod-aware: a (2, 4) hybrid base stays rectangular where N'
    # divides dcn=2, else falls back flat — mirroring _pod_shrink
    hybrid = Mesh(np.array(devs).reshape(2, 4), ("dcn", "ici"))
    hv = dict(penv.elastic_mesh_variants(hybrid, min_ranks=4))
    assert hv[6].axis_names == ("dcn", "ici") and \
        hv[6].shape["ici"] == 3
    assert hv[7].axis_names == ("dp",)
    assert hv[4].axis_names == ("dcn", "ici") and \
        hv[4].shape["ici"] == 2
    # mesh_for_world: hybrid when the pod count divides, else flat
    m = penv.mesh_for_world(4, dcn=2)
    assert m.axis_names == ("dcn", "ici")
    m = penv.mesh_for_world(3, dcn=2)
    assert m.axis_names == ("dp",)
    assert penv.mesh_for_world(len(devs) + 1) is None


# -- bench block + schema (CI satellite) --------------------------------

def test_compile_cache_bench_block_registry_assembled(cc_env):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.observability import publish, registry
    from paddle_tpu.observability import schema as tschema

    main, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss.name])

    block = publish.compile_cache_block()
    assert block is not None
    assert block["enabled"] and block["misses"] >= 1
    assert block["dir"] == cc_env
    assert block["compile_ms_total"] > 0
    assert block["persistent_entries"] > 0
    # registry-assembled: the block must be readable back from the ONE
    # registry, exactly where bench.py's bench_blocks() reads it
    assert registry().blocks().get("compile_cache") == block
    snap = registry().snapshot()
    assert snap["counters"].get("compile_cache.miss", 0) >= 1
    assert "compile_cache.compile_ms_total" in snap["gauges"]

    # the new events validate against the locked telemetry schema,
    # which carries an explicit compile_cache contract
    sch = tschema.load_schema()
    assert "compile_cache" in sch["kinds"]["event"]["events"]
    evs = _cc_events()
    assert evs
    assert tschema.validate_records(evs, sch) == []
    # a compile_cache event missing its required fields is rejected
    bad = dict(evs[-1])
    bad.pop("status")
    assert tschema.validate_record(bad, sch) != []


def test_disabled_tier_emits_nothing():
    """No directory named (the library default): no events, no
    classification, entries carry no fingerprint — byte-identical to
    the pre-cache executor."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache as cc
    from paddle_tpu.observability import flight

    assert not cc.enabled()
    flight._reset_for_tests()
    main, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss.name])
    assert _cc_events() == []
    entry = list(exe._cache.values())[-1]
    assert entry.cc_fingerprint is None
    flight._reset_for_tests()


# -- supervised elastic shrink: warm restart + recovery split -----------

def test_supervised_elastic_shrink_warm_restart_splits_recovery(
        tmp_path):
    """2-rank cohort loses rank 1 for good; the supervisor shrinks to
    world 1 and respawns. The respawned worker compiles THROUGH the
    directory the supervisor passes on — the JAX_COMPILATION_CACHE_DIR
    of its own environment — so attempt 1 records HITS where attempt 0
    recorded misses, and the elastic_transition event splits recovery
    into coordination_s + compile_s."""
    log_dir = str(tmp_path / "logs")
    ccdir = str(tmp_path / "placed_from_outside")
    env = _base_env(JAX_COMPILATION_CACHE_DIR=ccdir)
    env.pop("FLAGS_tpu_telemetry_dir", None)
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6921,127.0.0.1:6922",
         "--log_dir", log_dir, "--max_restarts", "1",
         "--min_ranks", "1", _RUNNER, "3", "elastic"],
        env=env, cwd=_REPO, stdout=_sp.PIPE, stderr=_sp.STDOUT,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    assert "elastic shrink 2 -> 1" in proc.stdout, proc.stdout

    tdir = _os.path.join(log_dir, "telemetry")
    sup = _os.path.join(tdir, "telemetry.supervisor.jsonl")
    recs = [json.loads(ln) for ln in open(sup) if ln.strip()]
    evs = [r for r in recs if r.get("event") == "elastic_transition"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["old_world"] == 2 and ev["new_world"] == 1
    assert ev["coordination_s"] >= 0
    # the respawned worker's first-step compile, read from its
    # telemetry stream — reported SEPARATELY from coordination
    assert "compile_s" in ev, ev
    assert ev["compile_s"] > 0
    assert ev["recovery_s"] == pytest.approx(
        ev["coordination_s"] + ev["compile_s"], abs=1e-3)
    from paddle_tpu.observability import schema as tschema

    assert tschema.validate_record(ev, tschema.load_schema()) == []

    def _events_under(d):
        out = []
        for fname in sorted(_os.listdir(d)):
            if fname.startswith("telemetry.rank") and \
                    fname.endswith(".jsonl"):
                with open(_os.path.join(d, fname)) as f:
                    out.extend(json.loads(ln) for ln in f
                               if ln.strip())
        return [r for r in out if r.get("event") == "compile_cache"]

    # attempt 0 (collected into postmortem/) compiled cold
    pm0 = _os.path.join(log_dir, "postmortem", "attempt0")
    cold = _events_under(pm0)
    assert cold and any(e["status"] == "miss" for e in cold)
    # attempt 1 (live telemetry dir) compiled WARM from the shared dir
    warm = _events_under(tdir)
    assert warm and all(e["status"] == "hit" for e in warm), warm

    # the persistent tier lives where the environment put it — the
    # executables and the fingerprint index both — and nowhere else
    assert _os.path.isdir(_os.path.join(ccdir, "index"))
    assert any(f.endswith("-cache") for f in _os.listdir(ccdir))
    assert not _os.path.exists(_os.path.join(log_dir, "compile_cache"))

    # perf_analysis --compile-cache aggregates the whole run
    _sys.path.insert(0, _os.path.join(_REPO, "tools"))
    try:
        import perf_analysis

        rc = perf_analysis.compile_cache_report(log_dir=log_dir)
    finally:
        _sys.path.pop(0)
    assert rc == 0
