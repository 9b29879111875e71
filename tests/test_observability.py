"""Unified telemetry (paddle_tpu/observability): metrics registry +
JSONL sink + schema, cross-rank straggler aggregation, flight recorder,
capture hook — plus the thread-safety regression for the profiler's
step-phase counters (mutated from the prefetcher's background thread as
well as the main step loop) and the bench-smoke leg asserting the
registry-assembled blocks + sink records validate against the
checked-in contract (tools/telemetry_schema.json)."""
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import aggregate, capture, flight
from paddle_tpu.fluid import framework

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test gets a fresh registry/flight/capture world; the global
    singletons are process state the executor writes into."""
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()
    yield
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()


def _schema():
    return obs.load_schema(
        os.path.join(_REPO, "tools", "telemetry_schema.json"))


def _step_phases(total_ms=10.0, **over):
    ph = {"feed_ms": 1.0, "dispatch_ms": 5.0, "comm_ms": 0.0,
          "sync_ms": 2.0, "host_ms": 2.0, "compile_ms": 0.0,
          "total_ms": total_ms}
    ph.update(over)
    return ph


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = obs.configure(rank=3)
    assert reg.inc("rpc.retry") == 1
    assert reg.inc("rpc.retry", 2) == 3
    reg.set_gauge("amp.loss_scale.current", 1024.0)
    for v in (1.0, 2.0, 3.0, 100.0):
        reg.observe("step.total_ms", v)
    snap = reg.snapshot()
    assert snap["rank"] == 3
    assert snap["counters"]["rpc.retry"] == 3
    assert snap["gauges"]["amp.loss_scale.current"] == 1024.0
    h = snap["histograms"]["step.total_ms"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["p99"] == 100.0


def test_step_and_event_records_validate_against_schema(tmp_path):
    reg = obs.configure(telemetry_dir=str(tmp_path), rank=1)
    reg.record_step(_step_phases())
    reg.event("collective", op="barrier", key="barrier#1", dur_ms=0.5)
    reg.event("fault", fault="drop", side="client", point="recv", n=3)
    lines = [json.loads(ln) for ln in open(reg.jsonl_path)]
    assert len(lines) == 3
    assert obs.validate_records(lines, _schema()) == []
    step = lines[0]
    assert step["kind"] == "step" and step["rank"] == 1
    assert step["step"] == 1 and step["total_ms"] == 10.0
    # events are tagged with the step they happened at
    assert lines[1]["step"] == 1 and lines[1]["event"] == "collective"
    # and counters track events
    assert reg.snapshot()["counters"]["event.fault"] == 1


def test_schema_validator_rejects_drifted_records():
    schema = _schema()
    ok = {"kind": "step", "rank": 0, "step": 1, "ts": 1.0,
          "feed_ms": 0.0, "dispatch_ms": 1.0, "comm_ms": 0.0,
          "sync_ms": 0.0, "host_ms": 0.0, "total_ms": 1.0}
    assert obs.validate_record(ok, schema) == []
    missing = dict(ok)
    del missing["dispatch_ms"]
    assert any("dispatch_ms" in p
               for p in obs.validate_record(missing, schema))
    wrong_type = dict(ok, rank="zero")
    assert any("rank" in p
               for p in obs.validate_record(wrong_type, schema))
    extra = dict(ok, surprise=1)  # step records are a CLOSED shape
    assert any("surprise" in p
               for p in obs.validate_record(extra, schema))
    assert obs.validate_record({"kind": "wat"}, schema)
    # event detail fields are free-form (envelope + types only)
    ev = {"kind": "event", "event": "rpc_retry", "rank": 0, "step": 0,
          "ts": 1.0, "method": "hc_gather", "attempt": 2}
    assert obs.validate_record(ev, schema) == []


def test_jsonl_sink_rotates_atomically(tmp_path):
    reg = obs.configure(telemetry_dir=str(tmp_path), rank=0)
    reg._rotate_bytes = 512  # tiny threshold: force rotation
    reg.set_telemetry_dir(str(tmp_path))
    for _ in range(20):
        reg.record_step(_step_phases())
    names = sorted(os.listdir(tmp_path))
    gens = [n for n in names if ".g" in n and n.endswith(".jsonl")]
    assert gens, names  # rotation happened
    # every generation + the active file parse cleanly and ALL records
    # survive in order (nothing torn/lost across the os.replace)
    by_rank = aggregate.load_telemetry_dir(str(tmp_path))
    assert len(by_rank[0]) == 20
    assert [r["step"] for r in by_rank[0]] == list(range(1, 21))


def test_registry_thread_safety():
    reg = obs.configure(rank=0)
    n_threads, per = 8, 400
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(per):
            reg.inc("c")
            reg.observe("h", 1.0)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == n_threads * per
    assert snap["histograms"]["h"]["count"] == n_threads * per


# ---------------------------------------------------------------------------
# profiler step-phase counters: concurrent-recording regression
# ---------------------------------------------------------------------------

def test_profiler_step_phase_accumulation_is_thread_safe():
    """The phase counters are module-global and mutated from background
    threads (prefetcher producer, RPC handlers, hapi deferred sync) as
    well as the main step loop; the unlocked [count, total, max] list
    update lost increments under contention."""
    from paddle_tpu.fluid import profiler

    profiler.reset_step_phases()
    n_threads, per = 8, 500
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(per):
            profiler.record_step_phase("feed", 0.001)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    count = profiler._step_phases["feed"][0]
    total = profiler.step_phase_total("feed")
    profiler.reset_step_phases()
    assert count == n_threads * per
    np.testing.assert_allclose(total, 0.001 * n_threads * per,
                               rtol=1e-6)


def test_record_event_concurrent_with_reset():
    """RecordEvent from a worker thread racing reset_profiler must not
    corrupt the tables (the seed's defaultdict mutation had no lock)."""
    from paddle_tpu.fluid import profiler

    stop = threading.Event()
    errs = []

    def worker():
        try:
            while not stop.is_set():
                with profiler.RecordEvent("race/ev"):
                    pass
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=worker)
    t.start()
    for _ in range(50):
        profiler.reset_profiler()
    stop.set()
    t.join()
    assert not errs
    profiler.reset_profiler()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_keeps_last_n_steps(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0,
                  flight_steps=5)
    reg = obs.registry()
    for _ in range(12):
        reg.record_step(_step_phases())
    reg.event("checkpoint", action="save", path="x", step_no=3)
    path = obs.dump_flight_recorder("test-dump")
    doc = json.load(open(path))
    assert doc["reason"] == "test-dump"
    assert doc["n_steps"] == 5  # bounded: the LAST five
    assert [s["step"] for s in doc["steps"]] == [8, 9, 10, 11, 12]
    assert any(e["event"] == "checkpoint" for e in doc["events"])
    assert doc["metrics"]["counters"]["event.checkpoint"] == 1
    # no torn tmp files left beside the atomic dump
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


def test_flight_dump_once_suppresses_double_dump(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    assert obs.dump_flight_recorder("first") is not None
    assert obs.dump_flight_recorder("second") is None  # once=True
    assert json.load(open(os.path.join(
        tmp_path, "flightrec.rank0.json")))["reason"] == "first"


def test_excepthook_dump_names_the_crash(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    obs.registry().record_step(_step_phases())
    calls = []
    orig = sys.excepthook
    sys.excepthook = lambda *a: calls.append(a)
    try:
        flight.install()
        try:
            raise RuntimeError("boom at step 7")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        sys.excepthook = orig
    assert calls, "original excepthook must still run"
    doc = json.load(open(os.path.join(tmp_path, "flightrec.rank0.json")))
    assert doc["reason"] == "unhandled-exception"
    assert doc["fatal_event"]["type"] == "RuntimeError"
    assert "boom at step 7" in doc["fatal_event"]["message"]
    assert doc["n_steps"] == 1


@pytest.mark.faults
def test_fault_kill_dumps_flight_recorder(tmp_path):
    """PADDLE_FAULTS kill:= a preempted worker: the dying process must
    leave an atomic postmortem naming the fatal event with the last N
    step records intact (the in-process half of the supervised
    postmortem test in test_elastic.py)."""
    script = textwrap.dedent("""
        import os, sys
        sys.path.insert(0, %r)
        import numpy as np
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        main, startup = fluid.Program(), fluid.Program()
        with framework.program_guard(main, startup):
            x = fluid.data(name="x", shape=[-1, 4], dtype="float32")
            y = fluid.layers.fc(input=x, size=2)
            loss = fluid.layers.reduce_mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for i in range(4):
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[loss])
        # NOW arm the kill: the next RPC send dies mid-"step loop"
        os.environ["PADDLE_FAULTS"] = "kill:side=client,point=send,at=1"
        from paddle_tpu.distributed.rpc import RpcClient, RpcServer
        srv = RpcServer("127.0.0.1", 0, lambda m, a: [])
        srv.start()
        RpcClient("127.0.0.1:%%d" %% srv.port).call("ping")
        print("UNREACHABLE")
    """ % _REPO)
    from childenv import cpu_child_env

    env = cpu_child_env({"FLAGS_tpu_telemetry_dir": str(tmp_path)})
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=_REPO, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          timeout=180)
    assert proc.returncode == 137, proc.stdout  # the injected kill
    assert "UNREACHABLE" not in proc.stdout
    dump = os.path.join(tmp_path, "flightrec.rank0.json")
    assert os.path.exists(dump), os.listdir(tmp_path)
    doc = json.load(open(dump))
    assert doc["reason"] == "fault-kill"
    assert doc["fatal_event"]["event"] == "fault"
    assert doc["fatal_event"]["fault"] == "kill"
    # the last N step records rode along (startup + 4 train steps)
    assert doc["n_steps"] == 5
    assert [s["step"] for s in doc["steps"]] == [1, 2, 3, 4, 5]
    # and the fault also landed in the event ring + JSONL stream
    assert any(e["event"] == "fault" for e in doc["events"])
    lines = [json.loads(ln) for ln in open(
        os.path.join(tmp_path, "telemetry.rank0.jsonl"))]
    assert obs.validate_records(lines, _schema()) == []
    assert any(r.get("event") == "fault" for r in lines)


# ---------------------------------------------------------------------------
# aggregation + stragglers
# ---------------------------------------------------------------------------

def _mk_steps(rank, n, total_ms, host_ms=1.0, start=1):
    out = []
    for i in range(n):
        out.append({"kind": "step", "rank": rank, "step": start + i,
                    "ts": 100.0 + i, "feed_ms": 0.5, "dispatch_ms": 2.0,
                    "comm_ms": 0.0, "sync_ms": 0.5, "host_ms": host_ms,
                    "total_ms": total_ms})
    return out


def test_window_summary_and_cross_rank_aggregation():
    fast = aggregate.window_summary(records=_mk_steps(0, 10, 5.0))
    slow = aggregate.window_summary(
        records=_mk_steps(1, 10, 25.0, host_ms=21.0))
    assert fast["steps"] == 10 and fast["total_ms_mean"] == 5.0
    agg = aggregate.aggregate_summaries([fast, slow])
    assert agg["ranks"] == 2
    st = agg["straggler"]
    assert st["rank"] == 1 and st["fastest_rank"] == 0
    assert st["slack_ms"] == 20.0
    assert st["blame_phase"] == "host_ms"  # the 20ms lives in host
    assert agg["per_phase"]["total_ms"]["max"] == 25.0
    assert agg["per_phase"]["total_ms"]["min"] == 5.0


def test_offline_straggler_report_names_slow_rank_per_window():
    by_rank = {0: _mk_steps(0, 64, 5.0), 1: _mk_steps(1, 64, 9.0)}
    # rank 0 is slow ONLY in the second 32-step window
    for rec in by_rank[0][32:]:
        rec["total_ms"] = 50.0
    rep = aggregate.straggler_report(by_rank, window=32)
    assert rep["ranks"] == 2 and len(rep["windows"]) == 2
    assert rep["windows"][0]["slowest_rank"] == 1
    assert rep["windows"][1]["slowest_rank"] == 0
    assert rep["by_rank"] == {0: 1, 1: 1}
    # ragged tails (a dead rank) align on the common prefix
    by_rank[1] = by_rank[1][:40]
    rep = aggregate.straggler_report(by_rank, window=32)
    assert rep["common_steps"] == 40


def test_drain_window_resets():
    reg = obs.configure(rank=0)
    reg.record_step(_step_phases())
    reg.record_step(_step_phases())
    assert len(reg.peek_window()) == 2
    assert len(reg.drain_window()) == 2
    assert reg.drain_window() == []
    assert reg.step == 2  # the monotonic counter survives the drain


def test_online_aggregator_ticks_on_cadence_and_names_straggler():
    """Online straggler allgather on a CADENCE (carried-over ROADMAP
    item): two ranks with their own registries exchange window
    summaries every `window` steps over a real host-collective group;
    each rank gets a straggler_window event naming the heavy rank after
    every window — live degradation visibility, not just end-of-run."""
    from paddle_tpu.distributed.host_collectives import \
        HostCollectiveGroup
    from paddle_tpu.observability.registry import MetricsRegistry

    g0 = HostCollectiveGroup(0, 2, "127.0.0.1:0")
    g1 = HostCollectiveGroup(1, 2,
                             "127.0.0.1:%d" % g0._server.port)
    regs = [MetricsRegistry(rank=r) for r in range(2)]
    aggs = [aggregate.OnlineAggregator(g, window=4, reg=reg)
            for g, reg in zip((g0, g1), regs)]
    errs = []

    def run(r):
        try:
            for _ in range(8):
                regs[r].record_step(_step_phases(
                    total_ms=30.0 if r == 1 else 5.0,
                    dispatch_ms=25.0 if r == 1 else 5.0))
                aggs[r].maybe_tick()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errs, errs
        for r, reg in enumerate(regs):
            assert reg.counter("event.straggler_window").value == 2, \
                "rank %d: expected 2 window exchanges over 8 steps" % r
            assert reg.gauge("straggler.rank").value == 1
            assert reg.gauge("straggler.slack_ms").value == 25.0
            agg = aggs[r].last
            assert agg["straggler"]["rank"] == 1
            assert agg["straggler"]["blame_phase"] == "dispatch_ms"
        # the drain is real: the second window summarized only its own
        # 4 steps
        assert aggs[0].last["steps"] == 4
    finally:
        g1.shutdown()
        g0.shutdown()


def test_online_aggregator_wired_into_executor_epilogue():
    """observability.enable_online_stragglers arms the cadence in the
    executor step epilogue (on_executor_step) against the GLOBAL
    registry; a world-1 duck-typed group keeps it in-process."""

    class _SoloGroup:
        def all_gather(self, blob):
            return [np.asarray(blob)]

    reg = obs.configure(rank=0)
    try:
        agg = obs.enable_online_stragglers(_SoloGroup(), window=3)
        for _ in range(7):
            obs.on_executor_step(_step_phases(total_ms=8.0))
        assert reg.counter("event.straggler_window").value == 2
        assert agg.last is not None and agg.last["ranks"] == 1
        assert reg.step == 7
    finally:
        obs.disable_online_stragglers()


def test_online_aggregator_disarms_after_exchange_failure():
    """A dead rank mid-window must degrade the straggler view, not the
    step loop: the failed exchange lands ONE warning event and DISARMS
    the aggregator — re-running the collective every window would
    stall each survivor for the full dead-rank detection wait, over
    and over."""

    class _BrokenGroup:
        calls = 0

        def all_gather(self, blob):
            _BrokenGroup.calls += 1
            raise ConnectionError("peer gone")

    from paddle_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry(rank=0)
    agg = aggregate.OnlineAggregator(_BrokenGroup(), window=2, reg=reg)
    for _ in range(6):
        reg.record_step(_step_phases())
        agg.maybe_tick()  # must not raise
    assert agg.last is None and agg.dead
    assert _BrokenGroup.calls == 1, "disarm must stop the collective"
    assert reg.counter("event.straggler_window").value == 1  # one warn


def test_perf_analysis_stragglers_cli_logic(tmp_path, capsys):
    reg = obs.configure(telemetry_dir=str(tmp_path), rank=0)
    for _ in range(8):
        reg.record_step(_step_phases(total_ms=5.0))
    reg.close()
    obs.configure(telemetry_dir=str(tmp_path), rank=1)
    reg = obs.registry()
    for _ in range(8):
        reg.record_step(_step_phases(total_ms=42.0, host_ms=34.0))
    reg.close()
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import perf_analysis
    finally:
        sys.path.pop(0)
    rc = perf_analysis.stragglers(str(tmp_path), window=4)
    out = capsys.readouterr().out
    assert rc == 0
    assert "straggler: rank 1" in out
    assert "slowest rank 1" in out
    # single-rank dir: clean refusal, not a crash
    solo = tmp_path / "solo"
    solo.mkdir()
    obs.configure(telemetry_dir=str(solo), rank=0)
    obs.registry().record_step(_step_phases())
    obs.registry().close()
    assert perf_analysis.stragglers(str(solo)) == 2


# ---------------------------------------------------------------------------
# capture hook
# ---------------------------------------------------------------------------

class _FakeTrace:
    def __init__(self, ctl):
        self.started, self.stopped = [], 0
        ctl._start_trace = lambda d: self.started.append(d)
        ctl._stop_trace = lambda: setattr(
            self, "stopped", self.stopped + 1)


def test_capture_trigger_file_starts_and_stops(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    ctl = capture.CaptureController(out_dir=str(tmp_path),
                                    poll_interval_s=0.0)
    fake = _FakeTrace(ctl)
    ctl.poll()
    assert not ctl.tracing
    trig = os.path.join(str(tmp_path), "capture.trigger")
    open(trig, "w").close()
    ctl.poll()
    assert ctl.tracing and len(fake.started) == 1
    assert fake.started[0].startswith(
        os.path.join(str(tmp_path), "xplane"))
    ctl.poll()  # trigger still present: stays tracing, no re-start
    assert len(fake.started) == 1
    os.remove(trig)
    ctl.poll()
    assert not ctl.tracing and fake.stopped == 1
    # the capture window is locatable in the telemetry stream
    counters = obs.registry().snapshot()["counters"]
    assert counters["event.capture"] == 2


def test_capture_poll_is_throttled(tmp_path):
    ctl = capture.CaptureController(out_dir=str(tmp_path),
                                    poll_interval_s=3600.0)
    _FakeTrace(ctl)
    open(os.path.join(str(tmp_path), "capture.trigger"), "w").close()
    ctl.poll()          # first poll engages
    assert ctl.tracing
    ctl.stop()
    ctl.poll()          # inside the throttle window: no os.stat, no start
    assert not ctl.tracing


def test_capture_sigusr2_toggles(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    ctl = capture.controller()
    fake = _FakeTrace(ctl)
    assert capture.install()
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.05)
        assert ctl.tracing and len(fake.started) == 1
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.05)
        assert not ctl.tracing and fake.stopped == 1
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# tier-1 bench-smoke: registry-assembled blocks + schema-valid JSONL
# ---------------------------------------------------------------------------

def test_bench_blocks_come_from_registry(tmp_path):
    """The bench.py acceptance surface on a CPU program: phases /
    static_checks / telemetry blocks assembled by publish.bench_blocks,
    identical to registry().blocks(), and the JSONL sink's records
    validate against tools/telemetry_schema.json."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup):
        x = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    r = np.random.RandomState(0)
    feed = {"x": r.randn(4, 8).astype("float32"),
            "y": r.randn(4, 1).astype("float32")}
    from paddle_tpu.fluid import profiler

    profiler.reset_step_phases()
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    from paddle_tpu.observability import publish

    blocks = publish.bench_blocks(exe, main, feed, [loss])
    # the registry is the source of truth: what bench attaches IS what
    # the registry holds
    assert blocks == obs.registry().blocks()
    assert blocks["phases"]["steps"] == 3
    assert blocks["phases"]["dispatch_ms"] > 0
    assert blocks["static_checks"]["errors"] == 0
    tele = blocks["telemetry"]
    assert tele["rank"] == 0 and tele["steps"] >= 3
    assert tele["jsonl"] and os.path.exists(tele["jsonl"])
    assert tele["step_total_ms"]["count"] >= 3
    lines = [json.loads(ln) for ln in open(tele["jsonl"])]
    assert obs.validate_records(lines, _schema()) == []
    # single-chip program: no collectives / precision blocks claimed
    assert "collectives" not in blocks and "precision" not in blocks


# ---------------------------------------------------------------------------
# acceptance: 2-rank CPU run -> per-rank JSONL + straggler naming
# ---------------------------------------------------------------------------

_RANK_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %r)
    rank = int(sys.argv[1])
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["FLAGS_tpu_telemetry_dir"] = sys.argv[3]
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import aggregate
    from paddle_tpu.distributed.host_collectives import \\
        HostCollectiveGroup

    g = HostCollectiveGroup(rank, 2, "127.0.0.1:" + sys.argv[2])
    main, startup = fluid.Program(), fluid.Program()
    # rank 1 carries a MUCH heavier program (~6 GFLOP a step against
    # rank 0's microseconds of math): the designated straggler by a
    # margin no scheduling noise of a loaded test host can close
    width = 1024 if rank == 1 else 8
    batch = 1024 if rank == 1 else 8
    with framework.program_guard(main, startup):
        x = fluid.data(name="x", shape=[-1, width], dtype="float32")
        h = fluid.layers.fc(input=x, size=width, act="relu")
        loss = fluid.layers.reduce_mean(fluid.layers.fc(input=h, size=1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((batch, width), "float32")}
    exe.run(main, feed=feed, fetch_list=[loss])  # compile outside window
    obs.registry().drain_window()
    for i in range(6):
        exe.run(main, feed=feed, fetch_list=[loss])
        g.barrier()   # lockstep steps; also lands clock-sync anchors
    # end-of-window cross-rank aggregation over the host tier
    summaries = aggregate.allgather_window(
        g, aggregate.window_summary(obs.registry()))
    if rank == 0:
        print("AGG " + json.dumps(
            aggregate.aggregate_summaries(summaries)))
    g.barrier()
    g.shutdown()
    obs.registry().close()
    sys.stdout.flush()
    os._exit(0)
""" % _REPO)


@pytest.mark.dist
def test_two_rank_run_emits_jsonl_and_names_straggler(tmp_path):
    """Acceptance: a 2-rank CPU run produces schema-valid per-rank
    JSONL plus a straggler report naming the slow rank — online (the
    end-of-window allgather over the host-collective tier) AND offline
    (tools/perf_analysis.py --stragglers over the same JSONL)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    from childenv import cpu_child_env

    env = cpu_child_env()
    procs = [subprocess.Popen(
        [sys.executable, "-c", _RANK_SCRIPT, str(r), str(port),
         str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=_REPO) for r in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)

    # online: rank 0 printed the cross-rank aggregation — the straggler
    # verdict names rank 1 (the heavy program)
    agg_line = next(ln for ln in outs[0].splitlines()
                    if ln.startswith("AGG "))
    agg = json.loads(agg_line[4:])
    assert agg["ranks"] == 2 and agg["steps"] == 6
    assert agg["straggler"]["rank"] == 1
    assert agg["straggler"]["fastest_rank"] == 0
    assert agg["straggler"]["slack_ms"] > 0

    # per-rank JSONL exists and every record is schema-valid
    schema = _schema()
    by_rank = aggregate.load_telemetry_dir(str(tmp_path))
    assert set(by_rank) == {0, 1}
    for rank, recs in by_rank.items():
        assert obs.validate_records(recs, schema) == [], rank
        assert sum(1 for r in recs if r["kind"] == "step") >= 7
        # host-collective completions landed as clock-sync anchors
        keys = {r.get("key") for r in recs
                if r.get("event") == "collective"}
        assert any(k and k.startswith("barrier#") for k in keys)

    # offline: the --stragglers analysis over the same dir agrees
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import perf_analysis
    finally:
        sys.path.pop(0)
    # the six lockstep steps only: steps 1-2 are the startup run and the
    # compile, whose wall time is compile jitter, not the program (the
    # online path drains them the same way, with drain_window())
    steady = {r: [rec for rec in recs
                  if rec.get("kind") != "step" or rec["step"] > 2]
              for r, recs in by_rank.items()}
    rep = aggregate.straggler_report(steady, window=6)
    assert rep["straggler"] == 1, rep["windows"]
    assert all(w["slowest_rank"] == 1 for w in rep["windows"])
    assert perf_analysis.stragglers(str(tmp_path), window=6) == 0
