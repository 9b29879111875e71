"""Fault-tolerant distributed runtime: deterministic fault injection
(distributed/faults.py) exercising RPC reconnect + idempotent retry,
rank liveness fast-fail, store blob release, and the RpcServer shutdown
race — all on CPU, no accelerator involved."""
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.faults

from paddle_tpu.distributed import faults
from paddle_tpu.distributed.rpc import (RpcClient, RpcRemoteError,
                                        RpcServer)

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env(extra):
    from childenv import cpu_child_env

    return cpu_child_env(extra)


# -- injector unit behavior -------------------------------------------------

def test_parse_spec_roundtrip():
    injs = faults.parse_spec(
        "drop:side=client,point=recv,every=3;"
        "kill:at=40,exit_code=9;delay:every=2,delay_ms=1")
    assert [i.kind for i in injs] == ["drop", "kill", "delay"]
    assert injs[0].every == 3 and injs[0].side == "client"
    assert injs[1].at == 40 and injs[1].exit_code == 9
    assert injs[2].delay_ms == 1.0
    with pytest.raises(ValueError):
        faults.parse_spec("drop:every=1,at=2")  # both triggers
    with pytest.raises(ValueError):
        faults.parse_spec("explode:every=1")  # unknown kind


def test_injector_counts_only_matching_events():
    inj = faults.FaultInjector("drop", side="client", point="recv",
                               method="put", every=2)
    inj.fire("server", "recv", "put", None)   # wrong side: no count
    inj.fire("client", "send", "put", None)   # wrong point: no count
    inj.fire("client", "recv", "get", None)   # wrong method: no count
    inj.fire("client", "recv", "put", None)   # 1st match: no fire
    with pytest.raises(ConnectionError):
        inj.fire("client", "recv", "put", None)  # 2nd match: fires


def test_wire_format_roundtrips_large_batches():
    """u16 field count: a batched send_grads_batch for a model with
    hundreds of params per pserver must fit in one message (the u8
    count capped it at ~125 params and overflowed with a bare
    ValueError)."""
    from paddle_tpu.distributed.rpc import decode, encode

    fields = ["send_grads_batch", 7, 150]
    for i in range(150):
        fields += ["param_%d" % i, np.full((3,), i, np.float32)]
    body = encode(fields)[8:]  # strip the u64 length prefix
    out = decode(body)
    assert out[0] == "send_grads_batch" and out[2] == 150
    assert len(out) == len(fields)
    np.testing.assert_array_equal(out[-1], fields[-1])
    with pytest.raises(ValueError, match="max 65535"):
        encode(list(range(70000)))


# -- RPC reconnect + exactly-once retry -------------------------------------

def _counting_server():
    seen = []

    def handler(method, args):
        if method == "incr":
            seen.append(int(args[0]))
            return [len(seen)]
        if method == "boom":
            raise KeyError("table row missing")
        return list(args)

    srv = RpcServer("127.0.0.1", 0, handler)
    srv.start()
    return srv, seen


def test_client_reconnects_and_handler_runs_exactly_once():
    """Drop the connection on every 3rd response read: the request was
    already APPLIED server-side, so the blind-retry failure mode is a
    double-apply. The envelope dedup must keep the handler at exactly
    one invocation per call."""
    srv, seen = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        with faults.inject("drop", side="client", point="recv", every=3):
            for i in range(20):
                (n,) = cli.call("incr", i)
                assert n == i + 1  # replayed response, not re-applied
        assert seen == list(range(20))
    finally:
        cli.close()
        srv.shutdown()


def test_close_evicts_server_dedup_entry():
    """A clean client close must release the server-side dedup entry
    (it pins the client's last response blob otherwise)."""
    srv, _ = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        cli.call("incr", 0)
        assert cli._cid in srv._dedup
        cli.close()
        deadline = time.monotonic() + 5
        while cli._cid in srv._dedup and time.monotonic() < deadline:
            time.sleep(0.02)
        assert cli._cid not in srv._dedup
    finally:
        srv.shutdown()


def test_ack_last_releases_retained_blob_but_keeps_dedup():
    """Acked-release (ROADMAP carried-over item): after the client acks
    the applied seq, the server frees the retained response blob (a
    params-sized get_params_batch reply pinned per trainer between
    steps otherwise) while the seq marker stays for dedup — and later
    calls still dedup/replay correctly."""
    srv, seen = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        big = b"x" * (1 << 20)
        (echo,) = cli.call("echo", big)
        assert bytes(np.asarray(echo).tobytes()) == big

        def blob_bytes(resp):
            return sum(int(getattr(f, "nbytes", 0)) for f in resp)

        ent = srv._dedup[cli._cid]
        acked_seq = ent["seq"]
        assert blob_bytes(ent["resp"]) >= len(big), "blob retained pre-ack"
        cli.ack_last()
        ent = srv._dedup[cli._cid]
        assert ent["seq"] == acked_seq, "seq marker must survive the ack"
        assert blob_bytes(ent["resp"]) < len(big), "blob must be freed"
        # exactly-once semantics are untouched for later calls
        with faults.inject("drop", side="client", point="recv", every=2):
            for i in range(6):
                (n,) = cli.call("incr", i)
                assert n == i + 1
        assert seen == list(range(6))
    finally:
        cli.close()
        srv.shutdown()


def test_ack_of_stale_seq_is_a_noop():
    """An ack for anything but the newest completed seq (a late or
    confused client) must not disturb the dedup entry."""
    srv, _ = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        cli.call("echo", b"first")
        cli.call("echo", b"payload")  # newest completed seq is 2
        ent = srv._dedup[cli._cid]
        resp_before = ent["resp"]
        # hand-roll an ack for the STALE seq 1
        from paddle_tpu.distributed.rpc import (_ENVELOPE, read_msg,
                                                write_msg)

        with cli._lock:
            cli._seq += 1
            write_msg(cli._sock, [_ENVELOPE, cli._cid, cli._seq,
                                  "__rpc_ack__", 1])
            read_msg(cli._sock)
        assert srv._dedup[cli._cid]["resp"] is resp_before
    finally:
        cli.close()
        srv.shutdown()


def test_client_retries_send_side_drops_too():
    srv, seen = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        with faults.inject("drop", side="client", point="send", every=4):
            for i in range(12):
                cli.call("incr", i)
        assert seen == list(range(12))
    finally:
        cli.close()
        srv.shutdown()


def test_retry_budget_exhaustion_raises_connection_error(monkeypatch):
    monkeypatch.setenv("PADDLE_RPC_RETRIES", "2")
    monkeypatch.setenv("PADDLE_RPC_BACKOFF_S", "0.01")
    srv, _ = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        with faults.inject("drop", side="client", point="send", every=1):
            with pytest.raises(ConnectionError, match="after 2 retries"):
                cli.call("incr", 0)
    finally:
        cli.close()
        srv.shutdown()


def test_remote_errors_carry_type_and_traceback():
    srv, _ = _counting_server()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    try:
        with pytest.raises(RpcRemoteError) as ei:
            cli.call("boom")
        e = ei.value
        assert e.remote_type == "KeyError"
        assert "table row missing" in e.remote_msg
        assert "KeyError" in e.remote_traceback
        assert "remote traceback" in str(e)
        # the connection survives an application error (no retry storm)
        assert cli.call("echo", 7) == [7]
    finally:
        cli.close()
        srv.shutdown()


# -- RpcServer shutdown race (satellite regression) -------------------------

def test_server_shutdown_idempotent_and_safe_from_handler_thread():
    done = threading.Event()

    def handler(method, args):
        if method == "die":
            srv.shutdown()  # from THIS server's own handler thread
            done.set()
            return []
        return []

    srv = RpcServer("127.0.0.1", 0, handler)
    srv.start()
    cli = RpcClient("127.0.0.1:%d" % srv.port)
    cli.call("die")
    assert done.wait(timeout=10), "handler-thread shutdown deadlocked"
    # idempotent: repeated + concurrent shutdowns are no-ops
    srv.shutdown()
    srv.shutdown()
    cli.close()


def test_ps_sync_barrier_breaks_with_missing_ranks_and_recovers(
        monkeypatch):
    """A sync barrier stuck on a dead trainer must (a) time out naming
    the ranks that never arrived — heartbeat ages can't attribute it,
    every blocked waiter looks stale — and (b) reset so a later round
    with all trainers present still synchronizes."""
    monkeypatch.setenv("PADDLE_PS_BARRIER_TIMEOUT_S", "1")
    from paddle_tpu.distributed.ps import ParameterServer
    from paddle_tpu.fluid import framework as fw

    ps = ParameterServer(fw.Program(), None, trainers=2, mode="sync")
    try:
        with pytest.raises(RuntimeError, match=r"trainers \[1\] never "
                                               r"arrived"):
            ps.handle("send_barrier", [0])
        # recovery: both trainers arrive -> the reset barrier releases
        results = []
        ts = [threading.Thread(
            target=lambda t=t: results.append(
                ps.handle("send_barrier", [t]))) for t in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert results == [[], []]
    finally:
        ps.heartbeat.stop()


# -- host-collective liveness + blob release --------------------------------

def test_store_liveness_fast_fail_names_missing_ranks(monkeypatch):
    """A barrier blocked on a dead rank must fail in ~liveness_s with
    the missing rank ids + heartbeat age, not hang to the full
    PADDLE_HC_TIMEOUT_S."""
    monkeypatch.setenv("PADDLE_HC_HEARTBEAT_S", "0.2")
    monkeypatch.setenv("PADDLE_HC_LIVENESS_S", "1.0")
    # rank 1 never connects, so it is judged by the JOIN window (which
    # defaults to minutes to tolerate cold starts) — shrink it
    monkeypatch.setenv("PADDLE_HC_JOIN_S", "1.0")
    monkeypatch.setenv("PADDLE_HC_TIMEOUT_S", "120")
    from paddle_tpu.distributed.host_collectives import \
        HostCollectiveGroup

    g0 = HostCollectiveGroup(0, 2, "127.0.0.1:0")  # rank 1 never joins
    try:
        t0 = time.monotonic()
        with pytest.raises(RpcRemoteError) as ei:
            g0.barrier()
        dt = time.monotonic() - t0
        assert dt < 30, "fast-fail took %.0fs (liveness window 1s)" % dt
        assert "waiting on ranks {1}" in ei.value.remote_msg
        assert "last heartbeat" in ei.value.remote_msg
    finally:
        g0.shutdown()


def test_store_releases_blobs_after_each_collective(monkeypatch):
    """Seed leaked every contributed blob for the life of the run:
    _kv/_counts must drain once all ranks fetched (memory stays bounded
    across per-step barriers/allreduces)."""
    monkeypatch.setenv("PADDLE_HC_HEARTBEAT_S", "0.2")
    from paddle_tpu.distributed.host_collectives import \
        HostCollectiveGroup

    g0 = HostCollectiveGroup(0, 2, "127.0.0.1:0")
    ep = "127.0.0.1:%d" % g0._server.port
    g1 = HostCollectiveGroup(1, 2, ep)
    out = {}

    def run(g, r):
        for _ in range(5):
            g.barrier()
            out[(r, "sum")] = g.all_reduce(np.asarray([1.0 + r]))[0]
            out[(r, "b")] = int(g.broadcast(np.asarray([9 + r]),
                                            root=0)[0])

    ts = [threading.Thread(target=run, args=(g, r))
          for r, g in ((0, g0), (1, g1))]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        assert out[(0, "sum")] == out[(1, "sum")] == 3.0
        assert out[(0, "b")] == out[(1, "b")] == 9
        assert g0.store_stats() == (0, 0, 0), \
            "store still holds blobs: kv/counts/fetched=%s" \
            % (g0.store_stats(),)
    finally:
        g1.shutdown()
        g0.shutdown()


# -- end-to-end: collectives + PS train loop under injected drops -----------

@pytest.mark.dist
def test_two_rank_collectives_identical_under_injected_drops():
    """Acceptance: with fault injection dropping the store connection
    every N messages, a 2-rank host-collective run completes with
    results identical to the no-fault run."""
    script = textwrap.dedent("""
        import sys, numpy as np
        sys.path.insert(0, %r)
        from paddle_tpu.distributed.host_collectives import \\
            HostCollectiveGroup
        rank = int(sys.argv[1])
        g = HostCollectiveGroup(rank, 2, "127.0.0.1:" + sys.argv[2])
        for i in range(6):
            g.barrier()
            s = g.all_reduce(np.asarray([1.0 + rank, float(i)]))
            print("SUM", i, s.tolist(), flush=True)
        g.barrier()
        g.shutdown()
    """ % _REPO)

    def run(fault_spec):
        port = str(_free_port())
        extra = {"PADDLE_FAULTS": fault_spec} if fault_spec else {}
        procs = [subprocess.Popen(
            [sys.executable, "-c", script, str(r), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_env(extra)) for r in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            assert p.returncode == 0, out
            outs.append(sorted(ln for ln in out.splitlines()
                               if ln.startswith("SUM")))
        return outs

    clean = run(None)
    faulty = run("drop:side=client,point=recv,method=hc_gather,every=4")
    assert clean == faulty
    assert len(clean[0]) == 6


@pytest.mark.dist
def test_ps_sync_train_loop_identical_under_injected_drops():
    """Acceptance: a REAL sync PS train loop (fluid Executor +
    transpiled programs, dist_ps_runner) with the trainer connection
    dropped every N messages produces bit-identical losses to the
    no-fault run — retried grad pushes are never double-applied."""
    runner = os.path.join(_DIR, "dist_ps_runner.py")

    def run(fault_spec):
        eps = "127.0.0.1:%d" % _free_port()
        extra = {"PADDLE_FAULTS": fault_spec} if fault_spec else {}
        server = subprocess.Popen(
            [sys.executable, runner, "pserver", eps, eps, "1", "sync"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_env({}), cwd=_DIR)
        trainer = subprocess.Popen(
            [sys.executable, runner, "trainer", "0", eps, "1", "sync"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_env(extra), cwd=_DIR)
        try:
            tout, _ = trainer.communicate(timeout=240)
            assert trainer.returncode == 0, tout
            sout, _ = server.communicate(timeout=60)
            assert server.returncode == 0, sout
        finally:
            for p in (server, trainer):
                if p.poll() is None:
                    p.kill()
        return [ln for ln in tout.splitlines() if ln.startswith("LOSS")]

    clean = run(None)
    faulty = run("drop:side=client,point=recv,every=5")
    assert len(clean) == 5
    assert clean == faulty


# -- reconnect backoff jitter (elastic satellite) ---------------------------

def test_backoff_jitter_spreads_retry_sleeps(monkeypatch):
    """Pure exponential backoff synchronizes the cohort's retry clocks
    after a pserver restart (thundering herd); each sleep must jitter
    within [1-j, 1+j] of the capped exponential base, and j=0 must stay
    exactly deterministic."""
    monkeypatch.setenv("PADDLE_RPC_BACKOFF_S", "0.1")
    monkeypatch.setenv("PADDLE_RPC_BACKOFF_MAX_S", "0.8")
    monkeypatch.setenv("PADDLE_RPC_BACKOFF_JITTER", "0.5")
    srv, _ = _counting_server()
    try:
        cli = RpcClient("127.0.0.1:%d" % srv.port)
        base2 = 0.2   # 0.1 * 2^(2-1)
        draws = {cli._backoff_sleep_s(2) for _ in range(64)}
        assert all(0.1 - 1e-9 <= d <= 0.3 + 1e-9 for d in draws), draws
        assert len(draws) > 1, "jitter must actually vary the sleeps"
        assert any(abs(d - base2) > 0.01 for d in draws)
        # the exponential stays capped under jitter's upper bound
        assert all(d <= 0.8 * 1.5 + 1e-9
                   for d in (cli._backoff_sleep_s(30)
                             for _ in range(16)))
        cli.close()
        monkeypatch.setenv("PADDLE_RPC_BACKOFF_JITTER", "0")
        cli2 = RpcClient("127.0.0.1:%d" % srv.port)
        assert cli2._backoff_sleep_s(2) == base2
        assert cli2._backoff_sleep_s(30) == 0.8
        cli2.close()
    finally:
        srv.shutdown()


# -- preemption DURING a checkpoint save (elastic satellite) ----------------

def _run_ckpt_kill(mode, root):
    # cwd = the checkpoint parent: the fault-kill's flight dump lands
    # there instead of polluting the repo root
    proc = subprocess.run(
        [sys.executable, os.path.join(_DIR, "ckpt_kill_runner.py"),
         mode, root],
        env=_env({}), cwd=os.path.dirname(root), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=240)
    assert proc.returncode == 9, proc.stdout  # the injected kill's rc
    assert "SAVED0" in proc.stdout
    assert "UNREACHABLE" not in proc.stdout
    return proc.stdout


def test_fluid_restore_never_sees_half_written_step_dir(tmp_path):
    """PADDLE_FAULTS kill DURING the second fluid checkpoint save
    (payload written, publication pending): the .tmp dir is left on
    disk, and the newest-intact fallback restores checkpoint 0 without
    ever surfacing the half-written step."""
    root = str(tmp_path / "ck")
    _run_ckpt_kill("fluid", root)
    from paddle_tpu.fluid import checkpoint as ckpt

    leftovers = sorted(os.listdir(root))
    assert any(n.endswith(".tmp") for n in leftovers), leftovers
    assert ckpt.get_last_checkpoint_no(root) == 0
    latest = ckpt.latest_checkpoint_dir(root)
    assert latest and not latest.endswith(".tmp")
    assert ckpt.read_status(latest).step_no == 0


def test_sharded_restore_never_sees_half_written_step_dir(tmp_path):
    """Same for the orbax-backed manager: the kill fires after save()
    issued the async write (step dir uncommitted on disk);
    all_steps()/restore() must surface only step 0."""
    root = str(tmp_path / "sck")
    _run_ckpt_kill("sharded", root)
    leftovers = sorted(os.listdir(root))
    assert any("tmp" in n for n in leftovers), \
        "the kill must leave an uncommitted step: %s" % leftovers
    from paddle_tpu.distributed.sharded_checkpoint import \
        ShardedCheckpointManager

    mgr = ShardedCheckpointManager(root)
    try:
        assert mgr.all_steps() == [0]
        got = mgr.restore(template={
            "w": np.zeros((1 << 20,), np.float32),
            "step": np.zeros((1,), np.int64)})
        assert float(np.asarray(got["w"])[0]) == 1.0
        assert int(np.asarray(got["step"])[0]) == 0
    finally:
        mgr.close()


# -- pserver checkpoint/restore: exactly-once across a server death ---------

def test_pserver_checkpoint_restores_tables_and_dedup(tmp_path):
    """The server role's elastic story (ROADMAP carried-over item): a
    server that dies after applying-and-persisting a request comes back
    with its tables AND per-client applied-seq markers; the client's
    RETRY of that request is answered from the restored marker — never
    re-applied — while a genuinely new request executes normally."""
    from paddle_tpu.distributed.ps import ParameterServer
    from paddle_tpu.fluid import framework as fw

    ckpt_dir = str(tmp_path / "ps_ckpt")
    ps1 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    srv1 = RpcServer("127.0.0.1", 0, ps1.handle)
    srv1.start()
    cli = RpcClient("127.0.0.1:%d" % srv1.port)
    try:
        table0 = np.arange(12, dtype=np.float32).reshape(4, 3)
        cli.call("init_param", "w", table0)
        rows = np.asarray([1, 3], np.int64)
        vals = np.ones((2, 3), np.float32)
        cli.call("sparse_grad_sgd", "w", rows, vals, 0.5)
        applied = np.asarray(ps1.scope.find_var("w")).copy()
        assert not np.array_equal(applied, table0)
        retry_seq = cli._seq  # the request whose response could be lost
    finally:
        srv1.shutdown()
        ps1.heartbeat.stop()

    # the reborn server restores tables + dedup markers from disk
    ps2 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    dedup = ps2.restore_from_checkpoint()
    assert dedup and cli._cid in dedup
    np.testing.assert_array_equal(
        np.asarray(ps2.scope.find_var("w")), applied)
    srv2 = RpcServer("127.0.0.1", 0, ps2.handle)
    srv2.dedup_restore(dedup)
    srv2.start()
    try:
        from paddle_tpu.distributed.rpc import (_ENVELOPE, read_msg,
                                                write_msg)

        # the client never got its response: re-send the SAME envelope
        s = socket.create_connection(("127.0.0.1", srv2.port))
        try:
            write_msg(s, [_ENVELOPE, cli._cid, retry_seq,
                          "sparse_grad_sgd", "w", rows, vals, 0.5])
            resp = read_msg(s)
            assert resp and resp[0] == "ok", resp
            # the retry was answered from the marker, NOT re-applied
            np.testing.assert_array_equal(
                np.asarray(ps2.scope.find_var("w")), applied)
            # a NEW request still executes normally
            write_msg(s, [_ENVELOPE, cli._cid, retry_seq + 1,
                          "sparse_grad_sgd", "w", rows, vals, 0.5])
            resp2 = read_msg(s)
            assert resp2 and resp2[0] == "ok", resp2
            assert not np.array_equal(
                np.asarray(ps2.scope.find_var("w")), applied)
        finally:
            s.close()
    finally:
        srv2.shutdown()
        ps2.heartbeat.stop()
        cli.close()


def test_pserver_restored_complete_marker_still_stops_the_server(
        tmp_path):
    """A server killed between applying the LAST trainer's `complete`
    and answering it must not serve forever after restart: the
    restored marker carries the stop bit, so the trainer's retried
    `complete` is answered from dedup AND stops the reborn server —
    and a restore whose completed-set is already full releases
    wait_stopped immediately."""
    from paddle_tpu.distributed.ps import ParameterServer
    from paddle_tpu.fluid import framework as fw
    from paddle_tpu.distributed.rpc import (_ENVELOPE, read_msg,
                                            write_msg)

    ckpt_dir = str(tmp_path / "ps_ckpt")
    ps1 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    srv1 = RpcServer("127.0.0.1", 0, ps1.handle)
    srv1.start()
    cli = RpcClient("127.0.0.1:%d" % srv1.port)
    try:
        cli.call("complete", 0)  # applied + persisted (stop marker)
        last_seq = cli._seq
    finally:
        srv1.shutdown()
        ps1.heartbeat.stop()

    ps2 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    dedup = ps2.restore_from_checkpoint()
    try:
        assert ps2._completed == {0}
        assert dedup[cli._cid][2] is True, "stop bit must persist"
        srv2 = RpcServer("127.0.0.1", 0, ps2.handle)
        srv2.dedup_restore(dedup)
        srv2.start()
        # the retried complete replays from the marker AND stops the
        # reborn server (the hang the review caught)
        s = socket.create_connection(("127.0.0.1", srv2.port))
        try:
            write_msg(s, [_ENVELOPE, cli._cid, last_seq,
                          "complete", 0])
            assert read_msg(s)[0] == "ok"
        finally:
            s.close()
        srv2._stop_evt.wait(timeout=10)
        assert srv2._stop_evt.is_set()
        srv2.shutdown()
    finally:
        ps2.heartbeat.stop()
        cli.close()


def test_pserver_restore_falls_back_past_corrupt_snapshot(tmp_path):
    """Newest-intact semantics for the server snapshots too: a torn
    newest file (disk fault) falls back to the previous one."""
    from paddle_tpu.distributed.ps import ParameterServer
    from paddle_tpu.fluid import framework as fw

    ckpt_dir = str(tmp_path / "ps_ckpt")
    ps1 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    srv1 = RpcServer("127.0.0.1", 0, ps1.handle)
    srv1.start()
    cli = RpcClient("127.0.0.1:%d" % srv1.port)
    try:
        cli.call("init_param", "w", np.zeros((2, 2), np.float32))
        cli.call("sparse_grad_sgd", "w",
                 np.asarray([0], np.int64),
                 np.ones((1, 2), np.float32), 1.0)
        good = np.asarray(ps1.scope.find_var("w")).copy()
    finally:
        cli.close()
        srv1.shutdown()
        ps1.heartbeat.stop()
    snaps = sorted(os.listdir(ckpt_dir))
    assert len(snaps) == 2, snaps
    with open(os.path.join(ckpt_dir, snaps[-1]), "wb") as f:
        f.write(b"torn write")
    ps2 = ParameterServer(fw.Program(), None, trainers=1, mode="async",
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    try:
        assert ps2.restore_from_checkpoint() is not None
        # the corrupt newest snapshot fell back to snapshot 0 (the
        # state right after init_param: zeros)
        np.testing.assert_array_equal(
            np.asarray(ps2.scope.find_var("w")),
            np.zeros((2, 2), np.float32))
        assert not np.array_equal(
            np.asarray(ps2.scope.find_var("w")), good)
    finally:
        ps2.heartbeat.stop()


# -- acceptance: pserver killed mid-run, restarted by the supervisor --------

@pytest.mark.dist
@pytest.mark.slow
def test_ps_sync_pserver_killed_and_restarted_identical(tmp_path):
    """Acceptance (server-role elastic): a sync-PS cohort whose ONE
    pserver is PADDLE_FAULTS-killed mid-run and restarted by the
    launch_ps supervisor — restoring tables + dedup markers from its
    snapshots — completes with per-step losses IDENTICAL to the
    no-fault run (extends PR 1's exactly-once acceptance to the server
    role)."""
    script = tmp_path / "role.py"
    script.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "sys.path.insert(0, %r)\n"
        "import dist_ps_runner as R\n"
        "role = os.environ['TRAINING_ROLE']\n"
        "eps = os.environ['PADDLE_PSERVERS_IP_PORT_LIST']\n"
        "n = int(os.environ['PADDLE_TRAINERS_NUM'])\n"
        "if role == 'PSERVER':\n"
        "    if int(os.environ.get('PADDLE_RESTART_NUM', '0')) > 0:\n"
        "        os.environ.pop('PADDLE_FAULTS', None)\n"
        "    R.run_pserver(os.environ['PADDLE_CURRENT_ENDPOINT'],\n"
        "                  eps, n, 'sync')\n"
        "else:\n"
        "    os.environ.pop('PADDLE_FAULTS', None)\n"
        "    R.run_trainer(int(os.environ['PADDLE_TRAINER_ID']),\n"
        "                  eps, n, 'sync')\n"
        % (_DIR, _REPO))

    from paddle_tpu.distributed import launch_ps

    def run(tag, fault_spec, max_restarts):
        logs = str(tmp_path / ("logs_" + tag))
        server_ep = "127.0.0.1:%d" % _free_port()
        env_backup = dict(os.environ)
        clean = _env({})
        clean["PADDLE_RPC_RETRIES"] = "60"  # ride out the jax restart
        # the killed server's flight dump must land here, not in CWD
        clean["FLAGS_tpu_telemetry_dir"] = str(
            tmp_path / ("telemetry_" + tag))
        if fault_spec:
            clean["PADDLE_FAULTS"] = fault_spec
        argv = ["--servers", server_ep, "--worker_num", "2",
                "--log_dir", logs,
                "--ps_ckpt_dir", str(tmp_path / ("ps_state_" + tag)),
                str(script)]
        if max_restarts:
            argv = ["--max_restarts", str(max_restarts)] + argv
        try:
            os.environ.clear()
            os.environ.update(clean)
            rc = launch_ps.launch(argv)
        finally:
            os.environ.clear()
            os.environ.update(env_backup)
        assert rc == 0, open(
            os.path.join(logs, "workerlog.0.log")).read()
        out = []
        for i in range(2):
            with open(os.path.join(logs,
                                   "workerlog.%d.log" % i)) as f:
                out.append([ln for ln in f.read().splitlines()
                            if ln.startswith("LOSS")])
        return out, logs

    clean_losses, _ = run("clean", None, 0)
    # the kill lands mid-run on the server's Nth socket recv event
    faulty_losses, logs = run(
        "kill", "kill:side=server,point=recv,at=25", 2)
    with open(os.path.join(logs, "serverlog.0.log")) as f:
        slog = f.read()
    assert slog.count("SERVING") >= 2, \
        "server was not restarted by the supervisor:\n" + slog
    assert all(len(ls) == 5 for ls in clean_losses), clean_losses
    assert clean_losses == faulty_losses, (clean_losses, faulty_losses)
