"""Runtime hang watchdog (paddle_tpu/observability/watchdog.py): the
in-flight collective trace, the watchdog thread's stack + table dump,
the offline desync analyzer, the `stall` fault kind, the torn-JSONL
tolerance of --stragglers, the hang/heartbeat schema contract — and
the supervised 2-rank acceptance: rank 1 stalls inside a barrier, the
watchdog dump names rank 1 and the collective key, the supervisor
escalates through the elastic restart, the run completes rc=0."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import capture, flight
from paddle_tpu.observability import watchdog as wd
from paddle_tpu.utils.flags import set_flags

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Fresh registry/flight/capture/watchdog singletons per test (the
    in-flight trace is process state every collective writes into)."""
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()
    wd._reset_for_tests()
    set_flags({"FLAGS_tpu_hang_timeout_s": 0.0,
               "FLAGS_tpu_hang_capture_s": 0.0})
    yield
    obs.reset_registry()
    flight._reset_for_tests()
    capture._reset_for_tests()
    wd._reset_for_tests()
    set_flags({"FLAGS_tpu_hang_timeout_s": 0.0,
               "FLAGS_tpu_hang_capture_s": 0.0})


# ---------------------------------------------------------------------------
# in-flight trace ring
# ---------------------------------------------------------------------------

def test_inflight_trace_lifecycle_and_snapshot_json():
    tr = wd.InflightTrace(capacity=8)
    tok = tr.begin("allreduce", "allreduce#1", world=4, rank=2,
                   dtype="float32", shape=(3, 2), nbytes=24)
    assert tr.oldest_inflight_age_s() is not None
    (open_e,) = tr.inflight()
    assert open_e["state"] == "inflight" and open_e["key"] == \
        "allreduce#1"
    tok.arrived()
    assert tr.inflight()[0]["state"] == "arrived"
    tok.done(True)
    assert tr.oldest_inflight_age_s() is None
    snap = tr.snapshot()
    json.dumps(snap)  # must be JSON-encodable (embedded in dumps)
    (rec,) = snap["recent"]
    assert rec["state"] == "done"
    assert rec["ts_begin"] <= rec["ts_arrived"] <= rec["ts_end"]
    assert rec["schedule_key"] == \
        ["allreduce", "float32", [3, 2], 0, [["world", 4]], ""]


def test_inflight_trace_ring_is_bounded_and_failure_recorded():
    tr = wd.InflightTrace(capacity=4)
    for i in range(10):
        tr.begin("barrier", "barrier#%d" % i, world=2).done(i % 2 == 0)
    snap = tr.snapshot()
    assert len(snap["recent"]) == 4 and not snap["inflight"]
    assert {e["state"] for e in snap["recent"]} == {"done", "failed"}


def test_runtime_schedule_key_matches_static_grammar():
    """The runtime trace and tpu-lint's static divergence checker key
    "the same collective" identically: runtime_schedule_key on a
    host-tier barrier equals _schedule_key over the static record the
    IR pass would emit for it."""
    from paddle_tpu.analysis.collectives import (_schedule_key,
                                                 runtime_schedule_key)

    static_rec = {"kind": "barrier", "dtype": None, "shape": None,
                  "ring_id": 0, "group": (("world", 2),), "region": ""}
    assert runtime_schedule_key("barrier", world=2) == \
        _schedule_key(static_rec)
    static_rec = {"kind": "allreduce", "dtype": "float32",
                  "shape": (4,), "ring_id": 0,
                  "group": (("world", 3), ("ranks", (0, 1, 2))),
                  "region": ""}
    assert runtime_schedule_key("allreduce", dtype="float32",
                                shape=[4], world=3,
                                ranks=[0, 1, 2]) == \
        _schedule_key(static_rec)


def test_thread_stacks_names_every_live_thread():
    started = threading.Event()
    release = threading.Event()

    def parked():
        started.set()
        release.wait(10)

    t = threading.Thread(target=parked, name="parked-worker",
                         daemon=True)
    t.start()
    started.wait(5)
    try:
        stacks = wd.thread_stacks()
        assert any(k.startswith("MainThread") for k in stacks)
        parked_key = next(k for k in stacks
                          if k.startswith("parked-worker"))
        assert "release.wait" in stacks[parked_key]
    finally:
        release.set()


# ---------------------------------------------------------------------------
# watchdog thread: fire, dump, re-arm
# ---------------------------------------------------------------------------

def test_watchdog_fires_once_dumps_stacks_and_table(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    w = wd.HangWatchdog(0.2, heartbeat_s=3600)
    tok = wd.trace().begin("barrier", "barrier#7", world=2, rank=0)
    assert w._tick() is None  # not stale yet
    time.sleep(0.3)
    ev = w._tick()
    assert ev is not None and ev["key"] == "barrier#7"
    assert ev["stalled_s"] >= 0.2 and ev["inflight_n"] == 1
    assert w._tick() is None, "must not re-fire while still wedged"

    dump = json.load(open(str(tmp_path / "flightrec.rank0.json")))
    assert dump["reason"] == "hang"
    assert dump["hang"]["key"] == "barrier#7"
    assert dump["inflight"]["inflight"][0]["state"] == "inflight"
    assert any(k.startswith("MainThread") for k in dump["stacks"])
    recs = [json.loads(ln) for ln in
            open(str(tmp_path / "telemetry.rank0.jsonl"))]
    hangs = [r for r in recs if r.get("event") == "hang"]
    assert len(hangs) == 1
    assert obs.validate_records(hangs) == []

    # progress re-arms; a NEW wedge fires again AND rewrites the dump
    # (a stale first-hang table must not feed a later real verdict)
    tok.done(True)
    w.note_progress()
    tok2 = wd.trace().begin("allreduce", "allreduce#8", world=2)
    time.sleep(0.3)
    ev2 = w._tick()
    assert ev2 is not None and ev2["key"] == "allreduce#8"
    dump2 = json.load(open(str(tmp_path / "flightrec.rank0.json")))
    assert dump2["hang"]["key"] == "allreduce#8"
    assert dump2["inflight"]["inflight"][0]["key"] == "allreduce#8"
    tok2.done(False)


def test_watchdog_rearms_on_collective_completion_without_step(
        tmp_path):
    """A transient first hang (the store recovered, the collective
    completed) must re-arm the watchdog even when the step epilogue
    never runs (the wedge was mid-step): a later REAL hang in the
    same step still fires with fresh forensics."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    w = wd.HangWatchdog(0.2, heartbeat_s=3600)
    a = wd.trace().begin("allreduce", "allreduce#1", world=2)
    time.sleep(0.3)
    assert w._tick() is not None  # transient hang fires
    a.done(True)  # store recovered; NO step epilogue in between
    assert w._tick() is None  # progress observed -> quietly re-armed
    b = wd.trace().begin("allreduce", "allreduce#2", world=2)
    time.sleep(0.3)
    ev = w._tick()
    assert ev is not None and ev["key"] == "allreduce#2"
    dump = json.load(open(str(tmp_path / "flightrec.rank0.json")))
    assert dump["hang"]["key"] == "allreduce#2"
    b.done(False)


def test_watchdog_quiet_while_other_collectives_progress(tmp_path):
    """An old open entry alone is not a hang: while OTHER collectives
    keep completing (progress), the watchdog stays quiet — the fire
    condition is in-flight age AND no progress, per the contract."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    w = wd.HangWatchdog(0.2, heartbeat_s=3600)
    stuck = wd.trace().begin("barrier", "barrier#1", world=2)
    time.sleep(0.3)
    wd.trace().begin("allreduce", "allreduce#2", world=2).done(True)
    assert w._tick() is None  # completion just advanced
    stuck.done(True)


def test_watchdog_install_is_flag_gated():
    assert wd.install() is None  # flag unset -> off
    assert wd.watchdog() is None
    set_flags({"FLAGS_tpu_hang_timeout_s": 30.0})
    w = wd.install()
    try:
        assert w is not None and wd.maybe_install() is w
        assert w.timeout_s == 30.0
    finally:
        wd.uninstall()


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------

def test_flag_off_telemetry_stream_has_no_watchdog_records(tmp_path):
    """FLAGS_tpu_hang_timeout_s unset: no watchdog thread, and the
    executor-driven telemetry stream carries exactly the record
    vocabulary it always did — no hang, no heartbeat (the
    zero-overhead-when-off acceptance regression)."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    from paddle_tpu.fluid import framework

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        loss = fluid.layers.reduce_mean(
            fluid.layers.fc(input=x, size=4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((2, 8), np.float32)}
    for _ in range(3):
        exe.run(main_p, feed=feed, fetch_list=[loss])
    assert wd.watchdog() is None, \
        "flag unset must not arm the watchdog"
    recs = [json.loads(ln) for ln in
            open(str(tmp_path / "telemetry.rank0.jsonl"))]
    # startup dispatch + 3 train steps
    assert sum(1 for r in recs if r["kind"] == "step") == 4
    events = {r.get("event") for r in recs if r["kind"] == "event"}
    assert "hang" not in events and "heartbeat" not in events
    assert obs.validate_records(recs) == []


def test_flag_armed_watchdog_heartbeats(tmp_path):
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    w = wd.HangWatchdog(5.0, heartbeat_s=0.05)
    w._tick()
    time.sleep(0.08)
    w._tick()
    recs = [json.loads(ln) for ln in
            open(str(tmp_path / "telemetry.rank0.jsonl"))]
    beats = [r for r in recs if r.get("event") == "heartbeat"]
    assert len(beats) >= 2
    assert obs.validate_records(beats) == []
    assert all(b["up_s"] >= 0 for b in beats)


# ---------------------------------------------------------------------------
# host-collective + RPC integration: the trace records real traffic
# ---------------------------------------------------------------------------

def _free_endpoint():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return "127.0.0.1:%d" % port


@pytest.mark.dist
def test_host_collectives_record_inflight_lifecycle():
    from paddle_tpu.distributed.host_collectives import \
        HostCollectiveGroup

    ep = _free_endpoint()
    groups = [None, None]
    errs = []

    def run(r):
        try:
            g = HostCollectiveGroup(r, 2, ep)
            groups[r] = g
            g.barrier()
            out = g.all_reduce(np.ones(3, np.float64))
            assert float(out.sum()) == 6.0
            g.broadcast(np.asarray([1.0]), root=0)
        except Exception as e:  # noqa: BLE001
            errs.append((r, e))

    t0 = threading.Thread(target=run, args=(0,))
    t0.start()
    time.sleep(0.2)
    t1 = threading.Thread(target=run, args=(1,))
    t1.start()
    t0.join(30)
    t1.join(30)
    for g in groups:
        if g is not None:
            g.shutdown()
    assert not errs, errs
    snap = wd.trace().snapshot()
    assert not snap["inflight"], snap["inflight"]
    done = {(e["op"], e["key"]) for e in snap["recent"]
            if e["state"] == "done"}
    assert ("barrier", "barrier#1") in done
    assert ("allreduce", "allreduce#2") in done
    assert ("broadcast", "bcast#3") in done
    ar = next(e for e in snap["recent"] if e["op"] == "allreduce")
    assert ar["dtype"] == "float64" and ar["shape"] == [3] \
        and ar["bytes"] == 24 and ar["world"] == 2
    # both ranks passed through "arrived" before completing
    assert all("ts_arrived" in e for e in snap["recent"]
               if e["op"] != "broadcast")


# ---------------------------------------------------------------------------
# `stall` fault kind
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_stall_fault_wedges_op_until_reset():
    from paddle_tpu.distributed import faults
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer, _Stop

    def handler(method, args):
        if method == "stop":
            raise _Stop()
        return [np.asarray([1])]

    srv = RpcServer("127.0.0.1", 0, handler)
    srv.start()
    cli = RpcClient("127.0.0.1:%d" % srv.port, call_retries=0)
    state = {}

    def wedged():
        try:
            cli.call("ping")
        except Exception as e:  # noqa: BLE001
            state["error"] = e

    faults.reset()
    faults.install(faults.FaultInjector(
        "stall", side="client", point="send", method="ping", at=1))
    try:
        t = threading.Thread(target=wedged, daemon=True)
        t.start()
        t.join(timeout=0.6)
        assert t.is_alive(), \
            "stall must hold the op, not bound it like delay"
        # reset() releases the parked thread with a FaultError into
        # the socket op (retries=0 -> it surfaces)
        faults.reset()
        t.join(timeout=5)
        assert not t.is_alive()
        assert isinstance(state.get("error"), Exception)
    finally:
        faults.reset()
        cli2 = RpcClient("127.0.0.1:%d" % srv.port, call_retries=0)
        try:
            cli2.call("stop")
        except Exception:  # noqa: BLE001
            pass
        cli2.close()
        cli.close()
        srv.shutdown()


def test_stall_spec_parses_from_env_syntax():
    from paddle_tpu.distributed import faults

    (inj,) = faults.parse_spec(
        "stall:side=client,point=send,method=hc_put_part,at=3")
    assert inj.kind == "stall" and inj.at == 3
    assert inj.method == "hc_put_part"


# ---------------------------------------------------------------------------
# desync analyzer
# ---------------------------------------------------------------------------

def _doc(entries, stacks=None, ts=100.0):
    return {"inflight": {
        "inflight": [e for e in entries
                     if e["state"] in ("inflight", "arrived")],
        "recent": [e for e in entries
                   if e["state"] not in ("inflight", "arrived")]},
        "stacks": stacks or {"MainThread (tid=1)":
                             "  File train.py line 10\n"},
        "ts": ts}


def _ent(key, state, world=2, op="barrier", skey=None, seq=1):
    return {"seq": seq, "op": op, "key": key, "state": state,
            "world": world, "ts_begin": 90.0,
            "schedule_key": skey
            or [op, None, None, 0, [["world", world]], ""]}


def test_analyzer_names_rank_stalled_inside_collective():
    v = wd.analyze_hang({
        0: _doc([_ent("barrier#3", "arrived")]),
        1: _doc([_ent("barrier#3", "inflight")])})
    assert v["verdict"] == "stall"
    assert v["collective"] == "barrier#3" and v["op"] == "barrier"
    assert v["guilty_ranks"] == [1] and v["waiting_ranks"] == [0]
    assert "stack_tail" in v["per_rank"][1]


def test_analyzer_names_rank_that_never_arrived():
    v = wd.analyze_hang({
        0: _doc([_ent("barrier#5", "arrived", seq=5)]),
        2: _doc([_ent("barrier#5", "arrived", seq=5)]),
        1: _doc([_ent("barrier#4", "done", seq=4)])})
    assert v["verdict"] == "desync" and v["guilty_ranks"] == [1]
    assert v["per_rank"][1]["state"] == "missing"
    assert v["per_rank"][1]["frontier_key"] == "barrier#4"
    assert sorted(v["waiting_ranks"]) == [0, 2]


def test_analyzer_open_rpc_barrier_not_masked_by_retired_calls():
    """RPC-tier keys are static per endpoint (send_barrier@host:port),
    so every call shares one key: the OPEN record (highest seq) must
    win over older retired ones — a rank wedged in its 5th PS barrier
    after 4 clean completions is a stall, not no-hang."""
    key = "send_barrier@127.0.0.1:6000"
    r1 = [_ent(key, "done", op="rpc_send_barrier", seq=s)
          for s in (1, 2, 3, 4)] \
        + [_ent(key, "inflight", op="rpc_send_barrier", seq=5)]
    r0 = [_ent(key, "done", op="rpc_send_barrier", seq=s)
          for s in (1, 2, 3, 4)] \
        + [_ent(key, "arrived", op="rpc_send_barrier", seq=5)]
    v = wd.analyze_hang({0: _doc(r0), 1: _doc(r1)})
    assert v["verdict"] == "stall", v
    assert v["guilty_ranks"] == [1] and v["collective"] == key


def test_analyzer_flags_membership_mismatch():
    v = wd.analyze_hang({
        0: _doc([_ent("barrier#2", "arrived", world=2)]),
        1: _doc([_ent("barrier#2", "arrived", world=3,
                      skey=["barrier", None, None, 0,
                            [["world", 3]], ""])])})
    assert v["verdict"] == "membership-mismatch"
    assert "0" in v["mismatched_keys"] and "1" in v["mismatched_keys"]


def test_analyzer_no_hang_and_hang_report_roundtrip(tmp_path):
    v = wd.analyze_hang({0: _doc([_ent("barrier#1", "done")])})
    assert v["verdict"] == "no-hang"

    # bundle on disk -> hang_report names the guilty rank + key
    for rank, doc in ((0, _doc([_ent("barrier#3", "arrived")])),
                      (1, _doc([_ent("barrier#3", "inflight")]))):
        with open(str(tmp_path / ("flightrec.rank%d.json" % rank)),
                  "w") as f:
            json.dump(doc, f)
    rep = wd.hang_report(str(tmp_path))
    assert rep["verdict"]["verdict"] == "stall"
    text = "\n".join(rep["lines"])
    assert "barrier#3" in text and "rank 1" in text \
        and "guilty" in text
    # unreadable dumps are skipped, not fatal
    with open(str(tmp_path / "flightrec.rank2.json"), "w") as f:
        f.write('{"torn')
    assert len(wd.load_hang_bundle(str(tmp_path))) == 2


# ---------------------------------------------------------------------------
# --stragglers torn-line tolerance (satellite)
# ---------------------------------------------------------------------------

def _write_rank_stream(path, rank, n_steps, torn_tail=False):
    with open(path, "w") as f:
        for i in range(1, n_steps + 1):
            f.write(json.dumps({
                "kind": "step", "rank": rank, "step": i,
                "ts": 100.0 + i, "feed_ms": 1.0, "dispatch_ms": 5.0,
                "comm_ms": 0.0, "sync_ms": 1.0, "host_ms": 1.0,
                "total_ms": 8.0 + rank}) + "\n")
        if torn_tail:
            # the exact artifact a killed rank leaves: a final line cut
            # mid-object, no trailing newline
            f.write('{"kind": "step", "rank": %d, "step": %d, "ts"'
                    % (rank, n_steps + 1))


def test_load_telemetry_dir_reports_torn_final_line(tmp_path):
    from paddle_tpu.observability import aggregate

    _write_rank_stream(str(tmp_path / "telemetry.rank0.jsonl"), 0, 4)
    _write_rank_stream(str(tmp_path / "telemetry.rank1.jsonl"), 1, 4,
                       torn_tail=True)
    errors = []
    by_rank = aggregate.load_telemetry_dir(str(tmp_path),
                                           errors=errors)
    assert len(by_rank[0]) == 4 and len(by_rank[1]) == 4
    (err,) = errors
    assert err["rank"] == 1 and err["final_line"] is True
    assert err["file"] == "telemetry.rank1.jsonl"


def test_stragglers_tolerates_truncated_stream(tmp_path, capsys):
    """Regression: a torn final JSONL line (killed rank) must not
    escape --stragglers with a JSON decode traceback — the report runs
    and the skip is surfaced."""
    _write_rank_stream(str(tmp_path / "telemetry.rank0.jsonl"), 0, 8)
    _write_rank_stream(str(tmp_path / "telemetry.rank1.jsonl"), 1, 8,
                       torn_tail=True)
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import perf_analysis
    finally:
        sys.path.pop(0)
    rc = perf_analysis.stragglers(str(tmp_path), window=4)
    out = capsys.readouterr().out
    assert rc == 0
    assert "skipped torn JSONL line" in out
    assert "telemetry.rank1.jsonl" in out and "final line" in out
    assert "straggler: rank 1" in out


def test_hang_watch_survives_telemetry_rotation(tmp_path):
    """The supervisor's tail must reset a file offset when the active
    JSONL rotates (os.replace to .gN + fresh file at size 0): a stale
    large offset would both hide hang events and let the silence
    fallback kill a healthy cohort."""
    from paddle_tpu.distributed.launch import _HangWatch

    watch = _HangWatch(str(tmp_path), 4.0, poll_every_s=0.0)
    p = tmp_path / "telemetry.rank0.jsonl"
    filler = json.dumps({"kind": "event", "event": "collective",
                         "rank": 0, "step": 1, "ts": 1.0,
                         "key": "barrier#1"}, sort_keys=True)
    p.write_text((filler + "\n") * 50)
    assert watch.poll() is None  # offset advances past the filler
    # rotation: active file replaced by a FRESH, smaller one whose
    # only content is the hang event
    hang = json.dumps({"kind": "event", "event": "hang", "rank": 0,
                       "step": 2, "ts": 2.0, "stalled_s": 5.0,
                       "inflight_n": 1}, sort_keys=True)
    p.write_text(hang + "\n")
    det = watch.poll()
    assert det is not None and det["via"] == "hang-event", det
    assert det["ranks"] == [0]


# ---------------------------------------------------------------------------
# schema: hang / heartbeat event contracts (satellite)
# ---------------------------------------------------------------------------

def test_schema_locks_hang_and_heartbeat_events():
    schema = obs.load_schema()
    ok_hang = {"kind": "event", "event": "hang", "rank": 0, "step": 3,
               "ts": 1.0, "stalled_s": 2.5, "inflight_n": 1,
               "op": "barrier", "key": "barrier#3"}
    assert obs.validate_record(ok_hang, schema) == []
    bad = dict(ok_hang)
    bad.pop("stalled_s")
    assert any("stalled_s" in p for p in
               obs.validate_record(bad, schema))
    ok_beat = {"kind": "event", "event": "heartbeat", "rank": 0,
               "step": 3, "ts": 1.0, "up_s": 12.0, "inflight_n": 0}
    assert obs.validate_record(ok_beat, schema) == []
    assert any("up_s" in p for p in obs.validate_record(
        {"kind": "event", "event": "heartbeat", "rank": 0, "step": 0,
         "ts": 1.0}, schema))
    # wrong type on a typed watchdog field is caught
    assert any("stalled_s" in p for p in obs.validate_record(
        dict(ok_hang, stalled_s="2.5"), schema))


# ---------------------------------------------------------------------------
# acceptance: supervised 2-rank stall -> watchdog -> elastic recovery
# ---------------------------------------------------------------------------

def _launch_env():
    from childenv import cpu_child_env

    return cpu_child_env(drop=("FLAGS_tpu_hang_timeout_s",))


@pytest.mark.dist
def test_hang_timeout_without_telemetry_dir_warns(tmp_path):
    """--hang_timeout with no --log_dir / FLAGS_tpu_telemetry_dir has
    nowhere to read worker hang events from: the launch must say so
    instead of silently arming nothing supervisor-side."""
    script = tmp_path / "ok.py"
    script.write_text("print('fine')\n")
    env = _launch_env()
    env.pop("FLAGS_tpu_telemetry_dir", None)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6731", "--hang_timeout", "5",
         str(script)],
        env=env, cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "hang ESCALATION is off" in proc.stdout, proc.stdout


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.dist
def test_supervised_stall_is_diagnosed_and_elastically_recovered(
        tmp_path):
    """End-to-end forensics acceptance: rank 1 of a supervised 2-rank
    cohort stalls (alive, heartbeating) inside its 3rd barrier; every
    rank's watchdog dumps the in-flight table + thread stacks; the
    supervisor names rank 1 + the collective via the desync verdict,
    kills the cohort, drops rank 1 through --min_ranks, and the
    1-rank attempt completes rc=0. perf_analysis --hang-report over
    the collected bundle names the same rank and key."""
    runner = os.path.join(_DIR, "hang_watchdog_runner.py")
    log_dir = str(tmp_path / "logs")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6721,127.0.0.1:6722",
         "--log_dir", log_dir, "--max_restarts", "1",
         "--min_ranks", "1", "--hang_timeout", "4",
         runner, "5", "1", "3"],
        env=_launch_env(), cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    assert "alive but wedged" in proc.stdout, proc.stdout
    assert "hang verdict: stall" in proc.stdout, proc.stdout
    assert "elastic shrink 2 -> 1" in proc.stdout, proc.stdout

    # every rank left a flight dump carrying the in-flight table and
    # all-thread stacks, collected into postmortem/attempt0
    att0 = os.path.join(log_dir, "postmortem", "attempt0")
    docs = {}
    for rank in (0, 1):
        path = os.path.join(att0, "flightrec.rank%d.json" % rank)
        assert os.path.exists(path), os.listdir(att0)
        docs[rank] = json.load(open(path))
        assert docs[rank]["reason"] == "hang"
        assert any(k.startswith("MainThread")
                   for k in docs[rank]["stacks"])
    key = docs[0]["hang"]["key"]
    assert key.startswith("barrier#"), docs[0]["hang"]
    # rank 0 contributed and waited; rank 1 began but never arrived
    r0 = {e["key"]: e for e in docs[0]["inflight"]["inflight"]}
    r1 = {e["key"]: e for e in docs[1]["inflight"]["inflight"]}
    assert r0[key]["state"] == "arrived"
    assert r1[key]["state"] == "inflight"

    # the analyzer (the same code the supervisor ran) blames rank 1
    v = wd.analyze_hang(docs)
    assert v["verdict"] == "stall" and v["guilty_ranks"] == [1]
    assert v["collective"] == key

    # the supervisor stream: a hang event + the elastic_transition
    # carrying the verdict
    sup = os.path.join(log_dir, "telemetry",
                       "telemetry.supervisor.jsonl")
    recs = [json.loads(ln) for ln in open(sup) if ln.strip()]
    (hang_ev,) = [r for r in recs if r["event"] == "hang"]
    assert hang_ev["via"] == "hang-event"
    assert hang_ev["stalled_s"] >= 4.0
    (trans,) = [r for r in recs
                if r["event"] == "elastic_transition"]
    assert trans["hang"] is True
    assert trans["hang_verdict"] == "stall"
    assert trans["hang_guilty_ranks"] == [1]
    assert trans["hang_collective"] == key
    assert trans["old_world"] == 2 and trans["new_world"] == 1
    assert trans["failed_ranks"] == [1]

    # attempt 1 (world 1) finished the run
    log0 = open(os.path.join(log_dir, "workerlog.0")).read()
    assert "DONE rank=0 world=1 attempt=1" in log0, log0

    # one-command offline diagnosis over the collected bundle
    rep = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "perf_analysis.py"),
         "--hang-report", "--log-dir", log_dir],
        env=_launch_env(), cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=180)
    assert rep.returncode == 0, rep.stdout
    assert "rank 1: began but NEVER CONTRIBUTED" in rep.stdout
    assert key in rep.stdout
    assert "verdict: stall" in rep.stdout
