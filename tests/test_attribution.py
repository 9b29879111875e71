"""Per-op resource attribution (paddle_tpu/observability/attribution.py
+ Executor.attribution_report): provenance markers round-trip from the
fluid Program IR through lowered StableHLO and optimized HLO on every
lowering path (flat / bucketed / hierarchical / gradient-merge / AMP
masters / dygraph-to-static), the HBM class totals match the trusted
donation_report numbers EXACTLY, the OOM pre-flight
(FLAGS_tpu_hbm_budget_mb) rejects an over-budget program BEFORE its
first dispatch with a structured error naming the top consumers, a
seeded RESOURCE_EXHAUSTED in the dispatch path leaves a flight-recorder
dump whose memory breakdown parses and indexes, the live-HBM gauges
land schema-valid in the JSONL stream and render as a chrome-trace
counter lane, and model_stats' static estimate now has a ground-truth
cross-check."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid import framework
from paddle_tpu.fluid import optimizer as O
from paddle_tpu.observability import attribution as attr
from paddle_tpu.observability import capture, flight
from paddle_tpu.utils.flags import get_flag, set_flags

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)

_FLAGS = ("FLAGS_tpu_sharded_weight_update", "FLAGS_tpu_comm_bucket_mb",
          "FLAGS_tpu_dcn_replicas", "FLAGS_tpu_hbm_budget_mb",
          "FLAGS_tpu_op_provenance")


@pytest.fixture(autouse=True)
def _restore_flags():
    old = {f: get_flag(f) for f in _FLAGS}
    yield
    set_flags(old)


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()
    yield
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()


def _fresh():
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def _batch(width=32):
    r = np.random.RandomState(0)
    return (r.rand(16, width).astype("float32"),
            r.randint(0, 4, (16, 1)).astype("int64"))


def _train(flags, amp=False, gm_k=None, ndev=8, run=True,
           opt_fn=None):
    """One DP MLP Adam step under `flags`; returns (exe, prog, feed,
    loss)."""
    import jax

    _fresh()
    set_flags(flags)
    x, y = _batch()
    with framework.unique_name_guard():
        img = fluid.layers.data(name="img", shape=[32],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        h = fluid.layers.fc(input=img, size=31, act="relu")
        logits = fluid.layers.fc(input=h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        opt = (opt_fn or (lambda: O.AdamOptimizer(
            learning_rate=1e-3)))()
        if amp:
            from paddle_tpu.fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(
                opt, use_dynamic_loss_scaling=False)
        if gm_k:
            opt = O.GradientMergeOptimizer(opt, k_steps=gm_k)
        opt.minimize(loss)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        if ndev != 8:
            from jax.sharding import Mesh

            prog._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"img": x, "label": y}
        if run:
            exe.run(prog, feed=feed, fetch_list=[loss])
    return exe, prog, feed, loss


def _census_count(exe, prog, feed, loss):
    col = exe.collective_report(prog, feed=feed, fetch_list=[loss])
    return sum(v["count"] for v in col.values()
               if isinstance(v, dict) and "count" in v)


# ---------------------------------------------------------------------------
# marker grammar
# ---------------------------------------------------------------------------

def test_marker_roundtrip():
    class _Op:
        type = "elementwise_add"
        output_arg_names = ["fc_0.w_0@GRAD"]

        class block:
            idx = 2

    m = attr.op_marker(_Op(), 7)
    assert "@" not in m, "XLA truncates op_name metadata at '@'"
    got = attr.parse_marker(m)
    assert got == {"kind": "op", "block": 2, "op_idx": 7,
                   "op_type": "elementwise_add",
                   "var": "fc_0.w_0@GRAD"}
    assert attr.parse_marker(attr.bucket_marker(3, "gather")) == \
        {"kind": "bucket", "bucket": 3, "action": "gather"}
    assert attr.parse_marker(
        attr.grad_sync_marker("fc_0.b_0@GRAD"))["var"] == \
        "fc_0.b_0@GRAD"
    assert attr.parse_marker(attr.gather_marker("p"))["kind"] == \
        "gather"
    assert attr.parse_marker(attr.amp_marker("found_inf")) == \
        {"kind": "amp", "what": "found_inf"}


def test_provenance_of_takes_innermost():
    path = ("jit(merged)/jit(main)/jit(shmap_body)/pp[b0;o5;while;x]/"
            "pp[b2;o1;mul;y]/mul")
    got = attr.provenance_of(path)
    assert got["op_type"] == "mul" and got["block"] == 2
    assert attr.provenance_of("jit(f)/jit(main)/mul") is None


def test_layer_of():
    assert attr.layer_of("encoder_layer_3.tmp_2") == "encoder_layer_3"
    assert attr.layer_of("fc_0.w_0@GRAD") == "fc_0"
    assert attr.layer_of("loss") == "loss"


# ---------------------------------------------------------------------------
# provenance round-trip per lowering path (census <-> markers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kwargs", [
    ("flat_per_var", dict(flags={
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0})),
    ("bucketed", dict(flags={
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.001})),
    ("replicated_dp", dict(flags={
        "FLAGS_tpu_sharded_weight_update": False,
        "FLAGS_tpu_comm_bucket_mb": 0.0})),
    ("hierarchical_2x2", dict(flags={
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.001,
        "FLAGS_tpu_dcn_replicas": 2}, ndev=4)),
    ("amp_masters", dict(flags={
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.001}, amp=True)),
])
def test_every_census_collective_maps(name, kwargs):
    """The acceptance round-trip: on every lowering path that exists
    today, every collective the census counts maps back to a fluid op
    / bucket id / gradient through the provenance markers, and the
    attribution class totals equal donation_report's EXACTLY."""
    kwargs = dict(kwargs)
    flags = kwargs.pop("flags")
    exe, prog, feed, loss = _train(flags, **kwargs)
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    assert rep is not None
    colls = rep["collectives"]
    assert colls["count"] > 0
    assert colls["mapped"] == colls["count"], [
        c for c in colls["entries"] if c["provenance"] is None]
    # the census and the provenance scan count the SAME collectives
    assert colls["count"] == _census_count(exe, prog, feed, loss)
    assert rep["cross_check"]["ok"], rep["cross_check"]
    assert rep["memory"]["coverage"] >= 0.9, rep["memory"]


def test_bucket_ids_in_collective_provenance():
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.001})
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    kinds = {(c["provenance"]["kind"],
              c["provenance"].get("action"))
             for c in rep["collectives"]["entries"]}
    assert ("bucket", "scatter") in kinds
    assert ("bucket", "gather") in kinds
    assert "grad_bucket" in rep["classes"]


def test_gradient_merge_region_provenance():
    """gm traces its bucketed merged-grad scatters inside the lax.cond
    region: the StableHLO debug asm still carries their loc markers, so
    the round-trip holds for region collectives too."""
    exe, prog, feed, loss = _train(
        {"FLAGS_tpu_sharded_weight_update": True,
         "FLAGS_tpu_comm_bucket_mb": 1000.0},
        gm_k=2, opt_fn=lambda: O.SGDOptimizer(learning_rate=0.1))
    plan = getattr(prog, "_shard_plan", None)
    assert plan is not None and plan.gradient_merge and plan.buckets
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    colls = rep["collectives"]
    assert colls["count"] > 0 and colls["mapped"] == colls["count"], \
        [c for c in colls["entries"] if c["provenance"] is None]
    assert any(c["provenance"]["kind"] == "bucket"
               for c in colls["entries"])


def test_activation_attribution_names_layers():
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0})
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    layers = rep["activation"]["by_layer"]
    assert any(k.startswith("fc_") for k in layers), layers
    assert rep["activation"]["matched_bytes"] > 0
    # state rows carry layer keys too
    assert any(r["layer"].startswith("fc_")
               for r in rep["state_vars"])


def test_provenance_off_by_flag():
    """FLAGS_tpu_op_provenance=False lowers with no markers — the
    report degrades (collectives unmapped) instead of erroring."""
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0,
        "FLAGS_tpu_op_provenance": False})
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    assert rep["collectives"]["mapped"] == 0
    # class attribution is static — still exact
    assert rep["cross_check"]["ok"]


def test_dygraph_to_static_provenance():
    """The dygraph-to-static path lowers through the same executor:
    its ops carry provenance markers and the attribution report
    resolves them (single device — no collectives, but per-op
    activation blame must be present)."""
    import paddle_tpu as paddle
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.dygraph import declarative

    class Net(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(8, 4)

        @declarative
        def forward(self, x):
            return self.fc(x) * 2.0

    with dygraph.guard():
        net = Net()
        x = np.random.RandomState(0).rand(3, 8).astype("float32")
        with dygraph.no_grad():
            net(paddle.to_tensor(x))
            cp = net.forward.concrete_program(paddle.to_tensor(x))
        feed = {cp.feed_names[0]: x}
        rep = cp._exe.attribution_report(
            cp.main, feed=feed, fetch_list=list(cp.fetch_vars))
    assert rep is not None
    assert rep["activation"]["matched_bytes"] > 0
    tops = rep["activation"]["by_op_top"]
    assert tops and any(t["op"].startswith(("b0/", "state "))
                        for t in tops), tops


# ---------------------------------------------------------------------------
# OOM pre-flight
# ---------------------------------------------------------------------------

def test_preflight_rejects_over_budget_pre_dispatch():
    exe, prog, feed, loss = _train(
        {"FLAGS_tpu_sharded_weight_update": True,
         "FLAGS_tpu_comm_bucket_mb": 0.0}, run=False)
    steps_before = obs.registry().step
    set_flags({"FLAGS_tpu_hbm_budget_mb": 0.001})
    with pytest.raises(attr.HbmBudgetExceeded) as ei:
        exe.run(prog, feed=feed, fetch_list=[loss])
    e = ei.value
    assert e.predicted_bytes > e.budget_bytes
    assert e.top_consumers and e.top_consumers[0]["name"]
    assert "fc_" in str(e), str(e)  # names a real consumer
    # structured: also a ResourceExhaustedError for generic handlers
    from paddle_tpu.core.errors import ResourceExhaustedError

    assert isinstance(e, ResourceExhaustedError)
    # NO step was dispatched/recorded
    assert obs.registry().step == steps_before


def test_preflight_refires_on_retry_not_cache_hit():
    """A caught HbmBudgetExceeded must not leave the compiled entry in
    the cache: a retried run re-enters the gate (and a raised budget
    lets it through) instead of cache-hitting past it and dispatching
    the known-over-budget program."""
    exe, prog, feed, loss = _train(
        {"FLAGS_tpu_sharded_weight_update": True,
         "FLAGS_tpu_comm_bucket_mb": 0.0}, run=False)
    set_flags({"FLAGS_tpu_hbm_budget_mb": 0.001})
    for _ in range(2):  # still fires on the retry — no cache bypass
        with pytest.raises(attr.HbmBudgetExceeded):
            exe.run(prog, feed=feed, fetch_list=[loss])
    set_flags({"FLAGS_tpu_hbm_budget_mb": 10_000.0})
    exe.run(prog, feed=feed, fetch_list=[loss])


def test_preflight_passes_under_budget_and_off_by_default():
    exe, prog, feed, loss = _train(
        {"FLAGS_tpu_sharded_weight_update": True,
         "FLAGS_tpu_comm_bucket_mb": 0.0}, run=False)
    assert attr.budget_bytes() is None  # flag 0 = off
    set_flags({"FLAGS_tpu_hbm_budget_mb": 10_000.0})
    exe.run(prog, feed=feed, fetch_list=[loss])  # 10 GB: passes


# ---------------------------------------------------------------------------
# OOM forensics (flight recorder + postmortem index)
# ---------------------------------------------------------------------------

def test_oom_forensics_flight_dump_and_index(tmp_path):
    """A seeded RESOURCE_EXHAUSTED in the dispatch path must produce a
    flight dump whose memory breakdown parses, names the top consumer,
    and is indexed by postmortem/index.json."""
    obs.configure(telemetry_dir=str(tmp_path))
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0})

    # seed the fault on the CACHED entry's dispatch callable
    (entry,) = [e for e in exe._cache.values()
                if getattr(e, "feed_names", None)
                and "img" in e.feed_names]

    def _boom(*a, **k):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes")

    entry.jitted = _boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        exe.run(prog, feed=feed, fetch_list=[loss])

    dump_path = os.path.join(str(tmp_path), "flightrec.rank0.json")
    assert os.path.exists(dump_path)
    doc = json.load(open(dump_path))
    assert doc["reason"] == "resource-exhausted"
    fatal = doc["fatal_event"]
    bd = fatal["memory_breakdown"]
    assert bd["classes"].get("param", 0) > 0
    assert fatal["top_consumer"]
    assert any(c["name"] == fatal["top_consumer"]
               for c in bd["top_consumers"])
    # the oom event also rode the ring
    assert any(e.get("event") == "oom" for e in doc["events"])

    # supervisor-side indexing: the dump lands in an attempt dir and
    # postmortem/index.json names its reason + fatal event
    from paddle_tpu.distributed.launch import _write_postmortem_index

    pm = tmp_path / "postmortem" / "attempt0"
    pm.mkdir(parents=True)
    os.replace(dump_path, pm / "flightrec.rank0.json")
    _write_postmortem_index(str(tmp_path / "postmortem"))
    index = json.load(open(tmp_path / "postmortem" / "index.json"))
    assert index["dumps"][0]["reason"] == "resource-exhausted"
    assert index["dumps"][0]["fatal_event"]["memory_breakdown"]


def test_is_resource_exhausted():
    from paddle_tpu.core.errors import ResourceExhaustedError

    assert attr.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert attr.is_resource_exhausted(ValueError("Out of memory"))
    assert attr.is_resource_exhausted(ResourceExhaustedError("x"))
    assert not attr.is_resource_exhausted(RuntimeError("shape error"))


# ---------------------------------------------------------------------------
# live-HBM gauges (satellite 1) + timeline counter lane (satellite 6)
# ---------------------------------------------------------------------------

def test_hbm_gauges_land_in_jsonl_and_validate(tmp_path, monkeypatch):
    from paddle_tpu.core import memory as core_mem

    monkeypatch.setattr(
        core_mem, "memory_stats",
        lambda device=None: {"bytes_in_use": 1234,
                             "peak_bytes_in_use": 5678})
    obs.configure(telemetry_dir=str(tmp_path))
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0})
    exe.run(prog, feed=feed, fetch_list=[loss])
    reg = obs.registry()
    assert reg.gauge("hbm.bytes_in_use").value == 1234
    assert reg.gauge("hbm.peak_bytes_in_use").value == 5678
    recs = [json.loads(line)
            for line in open(reg.jsonl_path) if line.strip()]
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps and all(r["hbm_bytes_in_use"] == 1234 and
                         r["hbm_peak_bytes_in_use"] == 5678
                         for r in steps)
    assert obs.validate_records(recs) == []


def test_timeline_renders_hbm_counter_lane():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import timeline

    recs = [
        {"kind": "step", "rank": 0, "step": 1, "ts": 10.0,
         "feed_ms": 1.0, "dispatch_ms": 2.0, "comm_ms": 0.0,
         "sync_ms": 0.0, "host_ms": 0.0, "total_ms": 3.0,
         "hbm_bytes_in_use": 111, "hbm_peak_bytes_in_use": 222},
        {"kind": "step", "rank": 0, "step": 2, "ts": 11.0,
         "feed_ms": 1.0, "dispatch_ms": 2.0, "comm_ms": 0.0,
         "sync_ms": 0.0, "host_ms": 0.0, "total_ms": 3.0},
    ]
    evs = timeline.telemetry_lane_events(recs)
    counters = [e for e in evs if e["ph"] == "C"]
    assert len(counters) == 1  # only the record carrying the gauge
    c = counters[0]
    assert c["name"] == "hbm"
    assert c["args"] == {"bytes_in_use": 111, "peak_bytes_in_use": 222}
    # sampled in the step EPILOGUE -> stamped at the step's END
    assert c["ts"] == pytest.approx(10.0 * 1e6 + 3.0 * 1e3)
    # duration events unaffected
    assert sum(1 for e in evs if e["ph"] == "X") == 2


# ---------------------------------------------------------------------------
# device-time attribution
# ---------------------------------------------------------------------------

def _device_trace(ops, modules=(), extra=()):
    """Chrome-trace events as the profiler's sidecar lays them out: one
    `/device:TPU:0` process with its `Steps`, `XLA Modules` and `XLA
    Ops` threads and one host process. `ops` and `modules` are (name,
    ts, dur, tf_op or None)."""
    evs = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 1, "name": "thread_name",
         "args": {"name": "Steps"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 701, "tid": 9, "name": "thread_name",
         "args": {"name": "python3"}},
    ]
    for tid, rows in ((2, modules), (3, ops)):
        for name, ts, dur, tf_op in rows:
            ev = {"ph": "X", "pid": 3, "tid": tid, "ts": ts, "dur": dur,
                  "name": name}
            if tf_op is not None:
                ev["args"] = {"tf_op": tf_op}
            evs.append(ev)
    return evs + list(extra)


def test_time_attribution_folds_markers():
    events = _device_trace([
        ("fusion.3", 0.0, 100.0, None),
        ("jit(main)/pp[b0;o4;relu;enc_1.tmp_0]/max", 100.0, 50.0, None),
        ("pp[bucket;2;scatter]", 150.0, 25.0, None),
        ("unrelated-op", 175.0, 7.0, None),
    ], extra=[{"ph": "i", "pid": 3, "tid": 3, "name": "instant-ignored"}])
    # the marker may sit in any string of the event, as xplane exports
    # put the HLO op_name metadata in args
    events[6]["args"] = {"long_name": "jit(main)/pp[b0;o1;matmul;"
                                      "enc_0.tmp_1]/dot_general"}
    t = attr.time_attribution(events)
    assert t["total_us"] == 182.0
    assert t["matched_us"] == 175.0 and t["unmatched_us"] == 7.0
    assert t["unattributed_us"] == 7.0
    assert t["by_layer"] == {"enc_0": 100.0, "enc_1": 50.0}
    assert t["by_bucket"] == {2: 25.0}
    assert list(t["by_layer"])[0] == "enc_0"  # sorted by time desc
    assert t["by_op_type"] == {"matmul": 100.0, "relu": 50.0,
                               "bucket": 25.0}
    # nothing is differentiated in this trace: an inference program's
    # marked operations are its forward pass
    assert t["by_region"]["forward"] == 150.0
    assert t["by_region"]["collective"] == 25.0
    assert t["steps"] == 0 and t["devices"] == 1


def test_load_trace_events(tmp_path):
    import gzip

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    doc = {"traceEvents": _device_trace(
        [("pp[b0;o0;mul;x]", 0.0, 5.0, None)])}
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(doc, f)
    evs = attr.load_trace_events(str(tmp_path))
    assert sum(1 for e in evs if e["ph"] == "X") == 1
    assert attr.time_attribution(evs)["matched_us"] == 5.0


_FWD = "jit(fn)/jvp(pp[b0;o8;scan;])/while/body/closed_call/"
_BWD = "jit(fn)/transpose(jvp(pp[b0;o8;scan;]))/while/body/closed_call/"


def test_region_fold_counts_device_self_time_once():
    """A `while` with its body, a host event and the `Steps` and `XLA
    Modules` events of the same interval: the step's time is counted
    once, by region, and only inside complete executions."""
    module = "jit_fn(1)"
    ops = [
        # before the first traced execution: left out
        ("stray.0", 0.0, 5.0, "jit(fn)/jvp(pp[b0;o0;cast;a])/convert"),
        # step 1, [10, 110): a forward loop holding two operations
        ("while.1", 10.0, 40.0, "jit(fn)/jvp(pp[b0;o8;scan;])/while"),
        ("fusion.1", 12.0, 20.0,
         _FWD + "pp[b1;o2;matmul;enc.tmp_0]/dot_general"),
        ("fusion.2", 32.0, 10.0,
         _FWD + "pp[b1;o3;layer_norm;enc.tmp_1]/reduce"),
        # the backward loop: a recomputed forward, then its gradient
        ("while.2", 50.0, 50.0,
         "jit(fn)/transpose(jvp(pp[b0;o8;scan;]))/while"),
        ("fusion.3", 50.0, 15.0,
         _BWD + "checkpoint/rematted_computation/"
         "pp[b1;o2;matmul;enc.tmp_0]/dot_general"),
        ("fusion.4", 65.0, 35.0,
         _BWD + "pp[b1;o2;matmul;enc.tmp_0]/dot_general"),
        # the optimizer, outside the differentiated function, under
        # the AMP cast that is its root; and a copy with no path
        ("fusion.5", 100.0, 6.0, "jit(fn)/pp[b0;o80;cast;nsp.b]/mul"),
        ("copy-done.1", 106.0, 4.0, None),
        # step 2, [200, 230)
        ("fusion.1", 200.0, 30.0,
         _FWD + "pp[b1;o2;matmul;enc.tmp_0]/dot_general"),
    ]
    modules = [(module, 10.0, 100.0, None), (module, 200.0, 30.0, None),
               ("jit_convert(2)", 0.0, 6.0, None)]
    extra = [
        {"ph": "X", "pid": 3, "tid": 1, "ts": 10.0, "dur": 100.0,
         "name": "0"},
        {"ph": "X", "pid": 701, "tid": 9, "ts": 0.0, "dur": 300.0,
         "name": "exe.step", "args": {"tf_op": _FWD + "pp[b1;o9;mul;x]"}},
    ]
    t = attr.time_attribution(_device_trace(ops, modules, extra))
    assert t["steps"] == 2 and t["devices"] == 1
    assert t["total_us"] == pytest.approx(130.0)
    assert t["by_region"] == pytest.approx({
        "forward": 10.0 + 20.0 + 10.0 + 30.0, "recompute": 15.0,
        "backward": 35.0, "update": 6.0, "collective": 0.0,
        "unattributed": 4.0})
    assert sum(t["by_region"].values()) == pytest.approx(t["total_us"])
    assert t["unattributed_us"] == pytest.approx(4.0)
    assert t["by_op_type"] == pytest.approx({
        "matmul": 20.0 + 15.0 + 35.0 + 30.0, "scan": 10.0,
        "layer_norm": 10.0, "cast": 6.0})
    assert list(t["by_op_type"])[0] == "matmul"
    # the rows the benchmark's readers log from
    rows = attr.device_op_rows(_device_trace(ops, modules, extra))
    assert rows["steps"] == 2 and len(rows["rows"]) == 9
    assert ("while.1", "jit(fn)/jvp(pp[b0;o8;scan;])/while", 10.0) \
        in rows["rows"]


_GDR = "pp[b0;o5;gated_delta_rule;mix.tmp_3]"


@pytest.mark.parametrize("path,part", [
    # a part belongs to the innermost fluid op: one that stands before
    # the last marker is the enclosing op's (a `scan` over a sub-block)
    ("jit(fn)/jvp(pp[b0;o8;scan;])/pt[walk]/while/body/closed_call/"
     "pp[b1;o2;matmul;enc.tmp_0]/dot_general", None),
    # of two parts the inner one wins
    ("jit(fn)/jvp(" + _GDR + ")/pt[local]/pt[inverse]/dot_general",
     "inverse"),
    # jax keeps both through a `custom_vjp`'s backward rule, where the
    # marker stands twice, and under `jax.checkpoint`
    ("jit(fn)/transpose(jvp(" + _GDR + "))/pt[local]/transpose(" + _GDR
     + ")/jvp(pt[local])/pt[inverse]/jit(tril)/select_n", "inverse"),
    ("jit(fn)/transpose(jvp())/checkpoint/rematted_computation/" + _GDR
     + "/jvp(pt[local])/mul", "local"),
    ("jit(fn)/jvp(" + _GDR + ")/while/body/pt[walk]/while/body/dot_general",
     "walk"),
    # no part: under the op's marker alone, under no marker, no path
    ("jit(fn)/jvp(" + _GDR + ")/reshape", None),
    ("jit(fn)/pt[local]/mul", None),
    ("", None), (None, None),
    # a name outside `[a-z0-9_]+` is no part
    ("jit(fn)/jvp(" + _GDR + ")/pt[Local]/mul", None),
], ids=["outer_ops_part", "inner_of_two", "custom_vjp_backward_rule",
        "recompute", "inside_two_loops", "marker_alone", "no_marker",
        "empty", "none", "not_a_part_name"])
def test_part_of_reads_the_innermost_ops_innermost_part(path, part):
    assert attr.part_of(path) == part


def test_part_scope_follows_the_provenance_flag():
    import contextlib

    import jax
    import jax.numpy as jnp

    def names():
        def fn(x):
            with attr.marker_scope(_GDR), attr.part_scope("local"):
                with attr.part_scope("inverse"):
                    return jnp.sin(x) @ x
        text = jax.jit(jax.grad(lambda x: jnp.sum(fn(x)))).lower(
            jnp.ones((4, 4))).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    set_flags({"FLAGS_tpu_op_provenance": True})
    assert not isinstance(attr.part_scope("local"), contextlib.nullcontext)
    on = names()
    assert {attr.part_of(n) for n in on if _GDR in n} == {"inverse"}
    assert any("transpose(" in n and "pt[inverse]" in n for n in on)
    set_flags({"FLAGS_tpu_op_provenance": False})
    assert isinstance(attr.part_scope("local"), contextlib.nullcontext)
    assert not any("pt[" in n or "pp[" in n for n in names())


_OLD_KEYS = ("steps", "devices", "by_region", "by_op_type", "by_op",
             "by_layer", "by_bucket", "matched_us", "unmatched_us",
             "unattributed_us", "total_us")


def test_the_fold_crosses_op_type_with_region_and_part():
    """`by_op_type_region` and `by_op_part` each sum to `by_op_type`,
    op type by op type, and every key the fold had reads what it read
    with the parts' scopes taken out of the paths."""
    fwd = "jit(fn)/jvp(" + _GDR + ")/"
    bwd = "jit(fn)/transpose(jvp(" + _GDR + "))/"
    again = "jit(fn)/transpose(jvp())/checkpoint/rematted_computation/" \
        + _GDR + "/"
    module = "jit_fn(1)"
    ops = [
        ("fusion.1", 10.0, 8.0, fwd + "pt[groups]/reshape"),
        ("while.1", 18.0, 30.0, fwd + "while"),
        ("fusion.2", 19.0, 12.0, fwd + "while/body/pt[local]/dot_general"),
        ("fusion.3", 31.0, 9.0,
         fwd + "while/body/pt[local]/pt[inverse]/dot_general"),
        ("kernel.1", 40.0, 7.0, fwd + "while/body/pt[walk]/pallas_call"),
        ("fusion.4", 50.0, 11.0, again + "jvp(pt[local])/pt[inverse]/mul"),
        ("fusion.5", 61.0, 6.0, again + "jvp(pt[local])/mul"),
        ("fusion.6", 70.0, 20.0, bwd + "pt[local]/transpose(" + _GDR
         + ")/jvp(pt[local])/pt[inverse]/dot_general"),
        ("fusion.7", 90.0, 5.0, bwd + "pt[walk]/optimization_barrier"),
        ("fusion.8", 95.0, 4.0, bwd + "add_any"),
        # another op type, with no part anywhere
        ("fusion.9", 100.0, 10.0,
         "jit(fn)/jvp(pp[b0;o6;matmul;mix.tmp_4])/dot_general"),
        ("fusion.10", 110.0, 3.0, "jit(fn)/pp[b0;o80;cast;nsp.b]/mul"),
        ("copy-done.1", 113.0, 2.0, None),
    ]
    modules = [(module, 10.0, 110.0, None)]
    t = attr.time_attribution(_device_trace(ops, modules))
    assert t["by_op_type"] == pytest.approx({
        "gated_delta_rule": 84.0, "matmul": 10.0, "cast": 3.0})
    assert t["by_op_type_region"] == {
        "gated_delta_rule": pytest.approx({
            "forward": 38.0, "recompute": 17.0, "backward": 29.0}),
        "matmul": {"forward": 10.0}, "cast": {"update": 3.0}}
    assert t["by_op_part"]["gated_delta_rule"] == {
        "inverse": pytest.approx({
            "forward": 9.0, "recompute": 11.0, "backward": 20.0}),
        "local": pytest.approx({"forward": 12.0, "recompute": 6.0}),
        "walk": pytest.approx({"forward": 7.0, "backward": 5.0}),
        "groups": {"forward": 8.0},
        # the loop's own time and the cotangents' sum: under no part
        "": pytest.approx({"forward": 2.0, "backward": 4.0})}
    assert t["by_op_part"]["matmul"] == {"": {"forward": 10.0}}
    # parts and op types by time, regions in REGIONS' order
    assert list(t["by_op_part"]["gated_delta_rule"]) == [
        "inverse", "local", "walk", "groups", ""]
    assert list(t["by_op_type_region"]) == list(t["by_op_type"])
    assert list(t["by_op_type_region"]["gated_delta_rule"]) == [
        "forward", "recompute", "backward"]
    for kind, us in t["by_op_type"].items():
        assert sum(t["by_op_type_region"][kind].values()) \
            == pytest.approx(us)
        assert sum(sum(row.values())
                   for row in t["by_op_part"][kind].values()) \
            == pytest.approx(us)
    # what the fold read before it knew of parts, it reads still
    bare = [(name, ts, dur, path and re.sub(r"pt\[\w+\]", "", path))
            for name, ts, dur, path in ops]
    was = attr.time_attribution(_device_trace(bare, modules))
    for key in _OLD_KEYS:
        assert t[key] == was[key], key
    assert set(t) == set(_OLD_KEYS) | {"by_op_type_region", "by_op_part"}
    assert all(set(row) == {""} for row in was["by_op_part"].values())
    # the table an operator reads: a line an op type, one a part
    table = attr.op_part_table(t)
    assert table[1].split() == ["op", "type", "/", "part", "all",
                                "forward", "recompute", "backward",
                                "update"]
    assert table[2].split() == ["gated_delta_rule", "0.084", "0.038",
                                "0.017", "0.029", "-"]
    assert table[3].split() == ["pt[inverse]", "0.040", "0.009", "0.011",
                                "0.020", "-"]
    assert table[7].split()[:3] == ["(no", "part)", "0.006"]
    assert table[8].strip() == "92.86 % of gated_delta_rule under a part"
    assert table[9].split()[0] == "matmul" and len(table) == 11
    assert attr.op_part_table({"total_us": 0.0}) == []


def test_xplane_blame_prints_the_folds_own_table(tmp_path, capsys,
                                                 monkeypatch):
    """`perf_analysis.py --stragglers --xplane-dir` shows an operator
    the op types by region and a line a part, as the fold formats it."""
    import gzip

    fwd = "jit(fn)/jvp(" + _GDR + ")/"
    ops = [("fusion.1", 0.0, 30.0, fwd + "pt[local]/pt[inverse]/dot_general"),
           ("fusion.2", 30.0, 10.0, fwd + "pt[groups]/transpose"),
           ("fusion.3", 40.0, 10.0, "jit(fn)/transpose(jvp(" + _GDR
            + "))/pt[walk]/pallas_call")]
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": _device_trace(
            ops, [("jit_fn(1)", 0.0, 50.0, None)])}, f)
    monkeypatch.syspath_prepend(os.path.join(_REPO, "tools"))
    import perf_analysis

    t = perf_analysis.xplane_blame(str(tmp_path))
    said = capsys.readouterr().out.splitlines()
    assert t["by_op_part"]["gated_delta_rule"]["inverse"] == {
        "forward": 30.0}
    table = ["  " + line for line in attr.op_part_table(t)]
    at = said.index(table[0])
    assert said[at:at + len(table)] == table
    assert any(line.split()[:2] == ["pt[inverse]", "0.030"]
               for line in said)
    # below the op-type lines it had
    assert at > max(i for i, line in enumerate(said)
                    if line.startswith("  op type "))


@pytest.mark.parametrize("policy", [True, False])
def test_regions_follow_jax_name_stack_of_a_compiled_scanned_bert(
        monkeypatch, policy):
    """`jvp(`, `transpose(` and `rematted_computation` are jax's
    name-stack grammar, not ours: a tiny scanned BERT with per-layer
    recompute, compiled on the CPU, must show all four regions in the
    optimized HLO's op_name metadata. A jax upgrade that renames them
    fails here, not by zeroing a metric on the chip. The checkpoint
    that carries a policy (what `_exec_scan` builds) and the bare one
    must both leave a `recompute` region, or `device_recompute_ms`
    falls silent."""
    import jax

    from paddle_tpu.fluid import lowering
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import bert

    sys.path.insert(0, _REPO)
    from __graft_entry__ import _bert_feed

    if not policy:
        monkeypatch.setattr(jax.checkpoint_policies,
                            "save_only_these_names", lambda *names: None)
    _fresh()
    cfg = bert.BertConfig.tiny()
    main_p, startup_p = framework.Program(), framework.Program()
    with framework.program_guard(main_p, startup_p):
        with framework.unique_name_guard():
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, 16, is_test=False, scan_layers=True, scan_remat=True)
            mixed_precision.decorate(
                O.AdamOptimizer(learning_rate=1e-3),
                use_dynamic_loss_scaling=False).minimize(total)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    feed = _bert_feed(cfg, 4, 16, max_pred=2)
    exe.run(main_p, feed=feed, fetch_list=[total])
    entry, lowered, smut = exe._cached_lowerable(
        main_p, feed, [total], None)[:3]
    hlo = exe._aot_compile(entry, lowered, smut).as_text()
    regions = {}
    for op_name in lowering._HLO_OPNAME_RE.findall(hlo):
        region = attr.region_of(op_name, attr.provenance_of(op_name))
        regions[region] = regions.get(region, 0) + 1
    for region in ("forward", "recompute", "backward", "update"):
        assert regions.get(region, 0) > 0, (region, regions)
    # and the update is what the issue's trace showed: optimizer
    # fusions under a marker, outside `jvp(`
    assert any(attr.region_of(n, attr.provenance_of(n)) == "update"
               and attr.provenance_of(n) is not None
               for n in lowering._HLO_OPNAME_RE.findall(hlo))


# ---------------------------------------------------------------------------
# model_stats reconcile (satellite 2)
# ---------------------------------------------------------------------------

def test_model_stats_reconcile_warns_on_drift():
    from paddle_tpu.fluid.contrib import model_stats

    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.0})
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[loss])
    # ZeRO shards the moments: the static walk overestimates
    # persistable state by construction -> the drift warning fires
    with pytest.warns(UserWarning, match="drifts"):
        out = model_stats.reconcile_with_attribution(
            rep, program=prog, batch_size=16)
    assert not out["classes"]["persistable"]["ok"]
    assert out["classes"]["persistable"]["static_bytes"] > \
        out["classes"]["persistable"]["compiled_bytes"]
    # a faithful report reconciles clean
    fake = {"classes": {"param": 1000, "master": 0, "opt_state": 0,
                        "state_other": 0, "feed": 500},
            "memory": {"temp_bytes": 400, "output_bytes": 100},
            "activation": {"matched_bytes": 450}}
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        out2 = model_stats.reconcile_with_attribution(
            fake, program=_StaticProg(1000, 950), batch_size=1)
    assert out2["ok"]


class _FakeVar:
    def __init__(self, nbytes, persistable):
        self.shape = (max(nbytes // 4, 1),)  # float32 elements
        self.dtype = "float32"
        self.persistable = persistable


class _FakeBlock:
    def __init__(self, persistable_bytes, activation_bytes):
        self.vars = {"p": _FakeVar(persistable_bytes, True),
                     "a": _FakeVar(activation_bytes, False)}


class _StaticProg:
    """Minimal program whose memory_usage lands at the given bytes."""

    def __init__(self, persistable_bytes, activation_bytes):
        self._block = _FakeBlock(persistable_bytes, activation_bytes)

    def global_block(self):
        return self._block


# ---------------------------------------------------------------------------
# bench block + registry (satellite 5 tier-1 leg)
# ---------------------------------------------------------------------------

def test_bench_attribution_block_comes_from_registry(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path))
    exe, prog, feed, loss = _train({
        "FLAGS_tpu_sharded_weight_update": True,
        "FLAGS_tpu_comm_bucket_mb": 0.001})
    from paddle_tpu.observability import publish

    blocks = publish.bench_blocks(exe, prog, feed, [loss])
    assert "attribution" in blocks
    assert blocks == obs.registry().blocks()
    blk = blocks["attribution"]
    assert blk["cross_check_ok"] is True
    assert blk["collectives_mapped"] == blk["collectives_total"] > 0
    assert blk["coverage"] >= 0.9
    json.dumps(blk)  # JSON-serializable for the bench result file
    # the sink's records still validate against the locked schema
    recs = [json.loads(line)
            for line in open(obs.registry().jsonl_path)
            if line.strip()]
    assert obs.validate_records(recs) == []


# ---------------------------------------------------------------------------
# CLI (slow legs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_perf_analysis_attribution_cli():
    """`perf_analysis.py --attribution` is the acceptance audit:
    BERT-tiny DP + ZeRO-1 + AMP-O2 + buckets, >= 90% peak attributed,
    donation cross-check exact, every collective mapped, pre-flight
    raises pre-dispatch. rc 0 = all held."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "perf_analysis.py"),
         "--attribution"],
        capture_output=True, text=True, env=env, cwd=_REPO,
        timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.load(open(os.path.join(_REPO, "artifacts",
                                      "attribution.json")))
    assert doc["coverage"] >= 0.9
    assert doc["cross_check"]["ok"]
    assert doc["preflight"]["raised"]
    assert doc["preflight"]["top_consumers"]
