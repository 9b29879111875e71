"""Multi-host e2e: paddle_tpu.distributed.launch spawns 2 localhost
"hosts" (one CPU device each) that form a global mesh via
jax.distributed; Fleet DP training matches single-process losses
(reference: test_dist_base.py:696 nccl2-mode cluster tests)."""
import pytest

pytestmark = pytest.mark.dist

import os
import socket
import subprocess
import sys

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_RUNNER = os.path.join(_DIR, "dist_fleet_runner.py")
_REPO = os.path.dirname(_DIR)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    from childenv import cpu_child_env

    return cpu_child_env()


def _losses(out):
    return [float(line.split()[1]) for line in out.splitlines()
            if line.startswith("LOSS")]


def test_launch_two_hosts_fleet_dp(tmp_path):
    single = subprocess.run(
        [sys.executable, _RUNNER, "single"], env=_env(), cwd=_DIR,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=240)
    assert single.returncode == 0, single.stdout
    base = _losses(single.stdout)
    assert len(base) == 5

    hosts = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    log_dir = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", hosts, "--log_dir", log_dir, _RUNNER],
        env=_env(), cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout

    per_host = []
    for tid in range(2):
        log = open(os.path.join(log_dir, "workerlog.%d" % tid)).read()
        ls = _losses(log)
        assert len(ls) == 5, log
        per_host.append(ls)
    # each host prints the mean over ITS batch shard; the average across
    # hosts equals the single-process full-batch loss at every step
    avg = np.mean(per_host, axis=0)
    np.testing.assert_allclose(avg, base, rtol=1e-4, atol=1e-4)
    assert avg[-1] < avg[0]
