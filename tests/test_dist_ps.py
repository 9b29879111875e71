"""Parameter-server mode end-to-end: REAL subprocesses on localhost
(reference pattern: test_dist_base.py:506 TestDistBase — 2 pservers +
2 trainers vs single-process, per-step loss comparison)."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.dist

_DIR = os.path.dirname(os.path.abspath(__file__))
_RUNNER = os.path.join(_DIR, "dist_ps_runner.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    from childenv import cpu_child_env

    return cpu_child_env()


def _spawn(args, extra_env=None):
    env = _env()
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen([sys.executable, _RUNNER] + args,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env=env, cwd=_DIR)


def _losses(out):
    return [float(line.split()[1]) for line in out.splitlines()
            if line.startswith("LOSS")]


@pytest.mark.parametrize("mode", [
    "sync", "async",
    # geo / half_async exercise alternate push schedules over the same
    # PS wire protocol; ~20s each, so they ride in the slow lane to
    # keep the default run inside the tier-1 budget (sync + async stay)
    pytest.param("geo", marks=pytest.mark.slow),
    pytest.param("half_async", marks=pytest.mark.slow),
])
def test_ps_2x2_localhost(mode):
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    ep_list = eps.split(",")
    n_trainers = 2

    single = _spawn(["single"])
    sout, _ = single.communicate(timeout=240)
    assert single.returncode == 0, sout
    base = _losses(sout)
    assert len(base) == 5

    servers = [_spawn(["pserver", ep, eps, str(n_trainers), mode])
               for ep in ep_list]
    trainers = [_spawn(["trainer", str(i), eps, str(n_trainers), mode])
                for i in range(n_trainers)]
    touts = []
    try:
        for t in trainers:
            out, _ = t.communicate(timeout=240)
            assert t.returncode == 0, out
            touts.append(out)
        for s in servers:
            out, _ = s.communicate(timeout=60)
            assert s.returncode == 0, out
    finally:
        for p in servers + trainers:
            if p.poll() is None:
                p.kill()

    all_ls = [_losses(out) for out in touts]
    for ls in all_ls:
        assert len(ls) >= 5, touts
        assert np.isfinite(ls).all()
        assert ls[-1] < ls[0], ls
    if mode == "sync":
        # sync PS == single-process SGD on the same global batch: the
        # pserver applies mean-of-half-batch grads == full-batch grad,
        # and each trainer's loss is the mean over its half, so the
        # AVERAGE of the two trainers' losses equals the single-process
        # full-batch loss at every step (fp tolerance only).
        avg = np.mean(all_ls, axis=0)
        np.testing.assert_allclose(avg, base, rtol=1e-4, atol=1e-4)


def test_ps_sync_prefetch_parity():
    """Async input pipeline in PS mode: trainers feeding prefetched
    on-device batches + LazyFetch results produce EXACTLY the same
    per-step losses as the plain synchronous trainers (the PS push
    path keeps its required per-step grad sync either way)."""
    n_trainers = 2

    def cohort(extra_env=None):
        eps = "127.0.0.1:%d" % _free_port()
        servers = [_spawn(["pserver", ep, eps, str(n_trainers), "sync"])
                   for ep in eps.split(",")]
        trainers = [
            _spawn(["trainer", str(i), eps, str(n_trainers), "sync"],
                   extra_env=extra_env)
            for i in range(n_trainers)]
        touts = []
        try:
            for t in trainers:
                out, _ = t.communicate(timeout=240)
                assert t.returncode == 0, out
                touts.append(out)
            for s in servers:
                out, _ = s.communicate(timeout=60)
                assert s.returncode == 0, out
        finally:
            for p in servers + trainers:
                if p.poll() is None:
                    p.kill()
        return [_losses(out) for out in touts]

    plain = cohort()
    prefetched = cohort({"PADDLE_PS_TEST_PREFETCH": "1"})
    assert plain == prefetched, (plain, prefetched)


def test_ps_distributed_lookup_table_sync():
    """distributed_lookup_table: sparse embedding hosted on pservers,
    row prefetch before each step, SelectedRows-style sparse grad push
    (reference: distributed_lookup_table_op.cc +
    parameter_prefetch.cc). Sync 2x2 == single-process."""
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    ep_list = eps.split(",")
    n_trainers = 2

    single = _spawn(["single_emb"])
    sout, _ = single.communicate(timeout=240)
    assert single.returncode == 0, sout
    base = _losses(sout)

    servers = [_spawn(["pserver_emb", ep, eps, str(n_trainers), "sync"])
               for ep in ep_list]
    trainers = [_spawn(["trainer_emb", str(i), eps, str(n_trainers),
                        "sync"]) for i in range(n_trainers)]
    touts = []
    try:
        for t in trainers:
            out, _ = t.communicate(timeout=240)
            assert t.returncode == 0, out
            touts.append(out)
        for s in servers:
            out, _ = s.communicate(timeout=60)
            assert s.returncode == 0, out
    finally:
        for p in servers + trainers:
            if p.poll() is None:
                p.kill()

    all_ls = [_losses(out) for out in touts]
    avg = np.mean(all_ls, axis=0)
    np.testing.assert_allclose(avg, base, rtol=1e-4, atol=1e-4)


def test_heartbeat_monitor_detects_lost_worker():
    """Reference: heart_beat_monitor.cc LostWorkerMonitor — a worker
    whose beats stop past the timeout is flagged."""
    from paddle_tpu.distributed.ps import HeartBeatMonitor

    lost = []
    m = HeartBeatMonitor(trainers=2, timeout_s=5.0,
                         on_lost=lost.append)
    t = [0.0]
    m._clock = lambda: t[0]
    m.beat(0)
    m.beat(1)
    t[0] = 3.0
    m.beat(1)  # worker 1 keeps beating
    assert m.lost_workers() == []
    t[0] = 7.0  # worker 0 silent for 7s > 5s; worker 1 only 4s
    assert m.lost_workers() == [0]
    assert lost == [0]
    m.beat(0)  # recovery clears the flag
    t[0] = 8.0
    assert m.lost_workers() == []


_FLEET_RUNNER = os.path.join(_DIR, "dist_fleet_ps_runner.py")


def _spawn_fleet(args):
    return subprocess.Popen([sys.executable, _FLEET_RUNNER] + args,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env=_env(), cwd=_DIR)


def test_fleet_a_sync_ps_2x2_localhost():
    """strategy.a_sync through the PUBLIC fleet API (role makers +
    init_server/run_server/init_worker) — reference: fleet 2.0
    parameter_server mode. 2 pservers + 2 trainers; every trainer's
    loss must decrease on the learnable batch."""
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    n_trainers = 2

    servers = [_spawn_fleet(["pserver", str(i), eps, str(n_trainers)])
               for i in range(2)]
    trainers = [_spawn_fleet(["trainer", str(i), eps, str(n_trainers)])
                for i in range(n_trainers)]
    touts = []
    try:
        for t in trainers:
            out, _ = t.communicate(timeout=240)
            assert t.returncode == 0, out
            touts.append(out)
        for s in servers:
            out, _ = s.communicate(timeout=60)
            assert s.returncode == 0, out
            assert "SERVED" in out
    finally:
        for p in servers + trainers:
            if p.poll() is None:
                p.kill()

    for out in touts:
        ls = _losses(out)
        assert len(ls) == 5, out
        assert ls[-1] < ls[0], (ls, out)


def test_fleet_ps_via_launch_ps(tmp_path):
    """The COMPLETE reference user workflow: one role-agnostic script
    (PaddleCloudRoleMaker from env) for 2 servers + 2 trainers, spawned
    by `paddle_tpu.distributed.launch_ps` — reference quickstart:
    launch_ps.py + fleet parameter_server mode."""
    from paddle_tpu.distributed import launch_ps

    script = os.path.join(_DIR, "fleet_ps_env_runner.py")
    logs = str(tmp_path / "logs")
    servers = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    env_backup = dict(os.environ)
    clean = _env()  # snapshot BEFORE clear: keep PATH/HOME/... intact
    try:
        # full swap: the spawned roles get exactly the child env
        os.environ.clear()
        os.environ.update(clean)
        rc = launch_ps.launch([
            "--servers", servers, "--worker_num", "2",
            "--log_dir", logs, script])
    finally:
        os.environ.clear()
        os.environ.update(env_backup)
    assert rc == 0
    for i in range(2):
        with open(os.path.join(logs, "workerlog.%d.log" % i)) as f:
            ls = _losses(f.read())
        assert len(ls) == 5
        assert ls[-1] < ls[0], ls
