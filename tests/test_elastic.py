"""DistributedStrategy.elastic — preemption checkpoint + auto-resume.

Reference: `framework/distributed_strategy.proto:301` reserves `elastic`
(unimplemented there). Here it wires `fluid/checkpoint.py` into every
step of the marked program: async numbered checkpoints every
`save_steps`, and transparent restore from the latest checkpoint before
the first step after a restart."""
import pytest

pytestmark = pytest.mark.dist

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import fleet
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import checkpoint as ckpt


def _build_and_minimize(seed, elastic, root):
    """One simulated process: fresh name counters (a restarted process
    rebuilds fc_0/fc_1..., matching the checkpointed names), build,
    optionally wrap with the elastic strategy, minimize."""
    from paddle_tpu.fluid import framework

    main, startup = fluid.Program(), fluid.Program()
    with framework.unique_name_guard(), \
            fluid.program_guard(main, startup):
        main.random_seed = startup.random_seed = seed
        x = fluid.data(name="x", shape=[-1, 16], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        h = fluid.layers.fc(input=x, size=24, act="tanh")
        pred = fluid.layers.fc(input=h, size=1, act=None)
        loss = fluid.layers.reduce_mean(fluid.layers.square(pred - y))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        st = fleet.DistributedStrategy()
        if elastic:
            st.elastic = True
            st.elastic_configs = {"checkpoint_dir": root,
                                  "save_steps": 2,
                                  "max_checkpoints": 2}
        fleet.init()
        opt = fleet.distributed_optimizer(opt, st)
        opt.minimize(loss)
    return main, startup, loss.name


def _data(steps, batch=8):
    rng = np.random.RandomState(3)
    xs = rng.randn(steps, batch, 16).astype(np.float32)
    w = rng.randn(16, 1).astype(np.float32)
    return xs, np.tanh(xs @ w)


def test_elastic_checkpoints_and_resumes(tmp_path):
    root = str(tmp_path / "elastic")
    xs, ys = _data(8)

    def make(elastic):
        return _build_and_minimize(seed=5, elastic=elastic, root=root)

    def run(main, startup, loss_name, scope, lo, hi):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        out = []
        for i in range(lo, hi):
            v, = exe.run(main, feed={"x": xs[i], "y": ys[i]},
                         fetch_list=[loss_name], scope=scope)
            out.append(float(np.asarray(v).reshape(-1)[0]))
        return out

    # uninterrupted reference trajectory (no elastic)
    m0, s0, ln0 = make(elastic=False)
    ref = run(m0, s0, ln0, Scope(), 0, 8)

    # run 1: elastic on, 4 steps -> checkpoints at steps 1 and 3
    m1, s1, ln1 = make(elastic=True)
    got1 = run(m1, s1, ln1, Scope(), 0, 4)
    cp = m1._elastic_cfg.get("_ckpt")
    assert cp is not None, "save_steps=2 over 4 steps must checkpoint"
    cp.close()  # flush the async writer before the simulated preemption
    status = ckpt.read_status(ckpt.latest_checkpoint_dir(root))
    assert status.step_no == 3

    # run 2: fresh program + scope (params re-initialized by startup),
    # elastic auto-resumes from step 3's checkpoint before step 4
    m2, s2, ln2 = make(elastic=True)
    got2 = run(m2, s2, ln2, Scope(), 4, 8)
    assert m2._elastic_cfg["_step"] >= 8 - 4

    np.testing.assert_allclose(got1, ref[:4], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got2, ref[4:], rtol=1e-4, atol=1e-5)


def test_elastic_off_leaves_program_unmarked(tmp_path):
    main, _, _ = _build_and_minimize(seed=9, elastic=False,
                                     root=str(tmp_path))
    assert getattr(main, "_elastic_cfg", None) is None


# -- supervised launch: fail-fast + restart-with-resume ---------------------

import os as _os
import subprocess as _sp
import sys as _sys

_DIR = _os.path.dirname(_os.path.abspath(__file__))
_REPO = _os.path.dirname(_DIR)


def _launch_env():
    from childenv import cpu_child_env

    return cpu_child_env()


def _lost_machines_src(log_dir, world, lost):
    """Source of the attempt-0 tail of a launch worker: ranks `lost`
    die for good, the others wait to be torn down. The supervisor reads
    "who died on their own" off ONE poll of its cohort and kills the
    survivors at once, so on a busy machine a rank that starts late is
    torn down before its first line, and two deaths a few milliseconds
    apart are two transitions. Hence: the first lost rank waits until
    every rank's log holds its attempt-0 line, then kills the other
    lost ranks (by the pids they left beside the logs) and exits within
    the same few microseconds."""
    return (
        "if attempt == 0:\n"
        "    import signal\n"
        "    lost, log_dir = %r, %r\n"
        "    pid = os.path.join(log_dir, 'lost.%%d.pid')\n"
        "    if tid in lost:\n"
        "        with open(pid %% tid + '.tmp', 'w') as f:\n"
        "            f.write(str(os.getpid()))\n"
        "        os.replace(pid %% tid + '.tmp', pid %% tid)\n"
        "    if tid == lost[0]:\n"
        "        def up(t):\n"
        "            log = os.path.join(log_dir, 'workerlog.%%d' %% t)\n"
        "            return os.path.exists(log) and \\\n"
        "                'ATTEMPT 0' in open(log).read()\n"
        "        deadline = time.time() + 60\n"
        "        while time.time() < deadline and not (\n"
        "                all(up(t) for t in range(%d))\n"
        "                and all(os.path.exists(pid %% t) for t in lost)):\n"
        "            time.sleep(0.01)\n"
        "        for t in lost[1:]:\n"
        "            os.kill(int(open(pid %% t).read()), signal.SIGKILL)\n"
        "        os._exit(7)  # the lost machine\n"
        "    time.sleep(30)\n" % (tuple(lost), log_dir, world))


def _loss_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("LOSS")]


def test_launch_fail_fast_exits_with_first_nonzero_rc(tmp_path):
    """First worker failure terminates the rest of the cohort and the
    launcher exits with THAT code — not the last seen, and not after the
    healthy worker's full (long) runtime."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "tid = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "if tid == 1:\n"
        "    sys.exit(7)\n"
        "time.sleep(120)\n")
    import time

    t0 = time.monotonic()
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6701,127.0.0.1:6702",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=90)
    dt = time.monotonic() - t0
    assert proc.returncode == 7, proc.stdout
    assert dt < 60, "fail-fast took %.0fs (healthy worker sleeps 120s)" \
        % dt
    assert "worker 1 exited with 7" in proc.stdout


def test_supervised_restart_resumes_from_elastic_checkpoint(tmp_path):
    """--max_restarts composes with the elastic checkpoint-resume path:
    attempt 0 is killed hard after step 4 (last published checkpoint:
    step 3), the restarted attempt resumes at step 4 and the combined
    trajectory matches an uninterrupted run."""
    runner = _os.path.join(_DIR, "elastic_launch_runner.py")
    ref_root = str(tmp_path / "ref_ckpt")
    ref = _sp.run([_sys.executable, runner, ref_root],
                  env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
                  stderr=_sp.STDOUT, text=True, timeout=240)
    assert ref.returncode == 0, ref.stdout
    ref_losses = _loss_lines(ref.stdout)
    assert len(ref_losses) == 8

    root = str(tmp_path / "crash_ckpt")
    log_dir = str(tmp_path / "logs")
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6703", "--log_dir", log_dir,
         "--max_restarts", "1", runner, root, "crash"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    assert "restart 1/1" in proc.stdout, proc.stdout

    log = open(_os.path.join(log_dir, "workerlog.0")).read()
    got = _loss_lines(log)
    # attempt 0 printed steps 0..4 then died; attempt 1 resumed from the
    # step-3 checkpoint and reran 4..7 (log is append mode)
    assert [ln.split()[1] for ln in got] == \
        ["0", "1", "2", "3", "4", "4", "5", "6", "7"], log
    # last occurrence per step: attempt 1's rerun of step 4 onwards
    resumed = {ln.split()[1]: float(ln.split()[2]) for ln in got}
    expected = {ln.split()[1]: float(ln.split()[2])
                for ln in ref_losses}
    for step in ("4", "5", "6", "7"):
        np.testing.assert_allclose(resumed[step], expected[step],
                                   rtol=1e-4, atol=1e-5)


# -- supervisor-collected flight-recorder postmortem ------------------------

def test_supervisor_collects_flight_dump_of_fault_killed_rank(tmp_path):
    """Acceptance (observability): a PADDLE_FAULTS kill on ONE rank of
    a supervised 2-worker cohort leaves a flight-recorder dump that the
    launch supervisor collects into <log_dir>/postmortem/attempt0/
    BEFORE the --max_restarts cohort restart; the dump parses, names
    the fatal fault event, and carries the rank's last step records
    intact. The restarted cohort completes clean (rc=0)."""
    import json as _json

    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "tid = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "attempt = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
        "if tid == 1 and attempt == 0:\n"
        "    # the designated victim: die at its 3rd collective send\n"
        "    os.environ['PADDLE_FAULTS'] = \\\n"
        "        'kill:side=client,point=send,method=hc_put_part,at=3'\n"
        "import numpy as np\n"
        "import paddle_tpu.fluid as fluid\n"
        "from paddle_tpu.fluid import framework\n"
        "from paddle_tpu.distributed.host_collectives import \\\n"
        "    group_from_env\n"
        "os.environ.setdefault('PADDLE_HC_LIVENESS_S', '4')\n"
        "os.environ.setdefault('PADDLE_HC_HEARTBEAT_S', '0.5')\n"
        "g = group_from_env()\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with framework.program_guard(main, startup):\n"
        "    x = fluid.data(name='x', shape=[-1, 8], dtype='float32')\n"
        "    loss = fluid.layers.reduce_mean(\n"
        "        fluid.layers.fc(input=x, size=4))\n"
        "    fluid.optimizer.SGD(0.1).minimize(loss)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(startup)\n"
        "feed = {'x': np.ones((2, 8), 'float32')}\n"
        "for i in range(6):\n"
        "    exe.run(main, feed=feed, fetch_list=[loss])\n"
        "    g.barrier()\n"
        "g.shutdown()\n"
        "sys.stdout.flush()\n"
        "os._exit(0)\n" % _REPO)
    log_dir = str(tmp_path / "logs")
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6711,127.0.0.1:6712",
         "--log_dir", log_dir, "--max_restarts", "1", str(script)],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout
    assert "restart 1/1" in proc.stdout, proc.stdout
    assert "collected" in proc.stdout and "flight-recorder" \
        in proc.stdout, proc.stdout

    # the victim's dump was secured under postmortem/attempt0 before
    # the restart (the restarted cohort overwrites the telemetry dir)
    dump_path = _os.path.join(log_dir, "postmortem", "attempt0",
                              "flightrec.rank1.json")
    assert _os.path.exists(dump_path), proc.stdout
    doc = _json.load(open(dump_path))
    assert doc["reason"] == "fault-kill"
    assert doc["fatal_event"]["event"] == "fault"
    assert doc["fatal_event"]["fault"] == "kill"
    assert doc["rank"] == 1
    # rank 1 died at its 3rd barrier: startup + 3 train steps recorded,
    # in order, with the step-phase split intact
    steps = [s["step"] for s in doc["steps"]]
    assert doc["n_steps"] >= 3 and steps == sorted(steps)
    assert all("total_ms" in s for s in doc["steps"])
    # the collective events before death rode along in the ring
    assert any(e.get("event") == "collective" for e in doc["events"])
    # the JSONL streams moved with the dumps, so attempt 1 started a
    # FRESH stream (no silent cross-attempt append with a reset step
    # counter) and attempt 0's records stay analyzable per-attempt
    att0 = _os.path.join(log_dir, "postmortem", "attempt0")
    assert _os.path.exists(_os.path.join(
        att0, "telemetry.rank1.jsonl")), _os.listdir(att0)
    tdir = _os.path.join(log_dir, "telemetry")
    assert _os.path.isdir(tdir)
    fresh = [f for f in _os.listdir(tdir) if f.endswith(".jsonl")]
    assert fresh, "restarted cohort must write its own stream"
    for f in fresh:
        recs = [_json.loads(ln) for ln in
                open(_os.path.join(tdir, f)) if ln.strip()]
        steps = [r["step"] for r in recs if r["kind"] == "step"]
        # a fresh stream restarts at step 1 — proof attempt 1 did not
        # append into attempt 0's file
        assert steps and steps[0] == 1, (f, steps[:3])
    # satellite: the run-wide postmortem index aggregates the dump
    index_path = _os.path.join(log_dir, "postmortem", "index.json")
    assert _os.path.exists(index_path), _os.listdir(
        _os.path.join(log_dir, "postmortem"))
    idx = _json.load(open(index_path))
    entries = [d for d in idx["dumps"]
               if d["attempt"] == 0 and d["rank"] == 1]
    assert entries and entries[0]["reason"] == "fault-kill"
    assert entries[0]["fatal_event"]["fault"] == "kill"
    assert entries[0]["n_steps"] >= 3


# -- elastic data re-sharding (reader.resharding) ---------------------------

def test_rank_slice_partitions_every_sample_exactly_once():
    from paddle_tpu.reader import resharding as rs

    for n in (0, 1, 5, 12, 24, 31):
        for world in (1, 2, 3, 4, 7):
            spans = [rs.rank_slice(n, r, world) for r in range(world)]
            # contiguous cover, no gap, no overlap, balanced
            assert spans[0][0] == 0 and spans[-1][1] == n
            for (a, b), (c, d) in zip(spans, spans[1:]):
                assert b == c
            sizes = [hi - lo for lo, hi in spans]
            assert max(sizes) - min(sizes) <= 1
    with pytest.raises(ValueError):
        rs.rank_slice(8, 2, 2)
    with pytest.raises(ValueError):
        rs.rank_slice(8, 0, 0)


def test_shard_batch_reshards_consistently_across_world_sizes():
    from paddle_tpu.reader import resharding as rs

    batch = {"x": np.arange(24).reshape(12, 2),
             "y": np.arange(12).reshape(12, 1)}
    for world in (1, 2, 3, 4):
        got = np.concatenate([rs.shard_batch(batch, r, world)["x"]
                              for r in range(world)])
        np.testing.assert_array_equal(got, batch["x"])
    tup = rs.shard_batch((batch["x"], batch["y"]), 1, 3)
    np.testing.assert_array_equal(tup[0], batch["x"][4:8])
    with pytest.raises(ValueError, match="disagree"):
        rs.shard_batch({"x": np.zeros((4, 1)), "y": np.zeros((5, 1))},
                       0, 2)


def test_resume_offset_and_skip_are_world_size_independent():
    from paddle_tpu.reader import resharding as rs

    # any world consumes global_batch samples per step: a checkpoint
    # taken at N resumes at the same sample cursor at N'
    assert rs.resume_sample_offset(5, 12) == 60
    assert rs.resume_sample_offset(-1, 12) == 0
    batches = [{"x": np.full((6, 1), i)} for i in range(5)]
    rest = list(rs.skip_steps(batches, 2))
    assert [int(b["x"][0, 0]) for b in rest] == [2, 3, 4]
    sharded = list(rs.shard_batches(rest, rank=1, world=2))
    assert all(b["x"].shape[0] == 3 for b in sharded)


# -- in-process elastic shrink: ZeRO-1 / AMP state re-shards at N' ----------
#
# The fast tier-1 elastic leg: a checkpoint written by an N-device
# sharded run restores into an N'-device program (N' != N), the
# executor re-pads/re-shards moments (and AMP masters) for the new
# mesh, and the post-restore trajectory is BIT-IDENTICAL to the
# replicated update restored from the same checkpoint — the invariant
# that makes an elastic world-size restart exact.

from paddle_tpu.utils.flags import get_flag, set_flags  # noqa: E402


@pytest.fixture
def _restore_shard_flags():
    old = {k: get_flag(k) for k in
           ("FLAGS_tpu_sharded_weight_update", "FLAGS_tpu_comm_bucket_mb")}
    yield
    set_flags(old)


def _shrink_batch():
    r = np.random.RandomState(0)
    # batch 24: divisible by every mesh size used below (4, 3, 2, 1)
    return (r.rand(24, 16).astype("float32"),
            r.randint(0, 4, (24, 1)).astype("int64"))


def _build_dp(ndev, zero1, amp=False, bucket_mb=0.0):
    """DP MLP (uneven fc size 31 -> flat-buffer padding differs between
    mesh sizes: 31 pads to 32 on 4/2 devs but 33 on 3) compiled for an
    ndev CPU sub-mesh."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.fluid import framework

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


def _run_dp(prog, startup, loss_name, steps, scope=None, restore=None):
    x, y = _shrink_batch()
    scope = scope or Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    if restore:
        status = ckpt.load_checkpoint(exe, restore, main_program=prog,
                                      scope=scope)
        assert status is not None
    losses = [float(np.asarray(exe.run(
        prog, feed={"img": x, "label": y}, fetch_list=[loss_name],
        scope=scope)[0]).mean()) for _ in range(steps)]
    return losses, exe, scope


@pytest.mark.parametrize("amp", [False, True], ids=["zero1", "amp_o2"])
def test_elastic_shrink_restores_bit_identical_at_new_world(
        tmp_path, _restore_shard_flags, amp):
    """Tier-1 elastic leg: train sharded on 4 devices, checkpoint
    (logical shapes), then continue at N' in {3, 2, 1}: the sharded
    continuation must be BIT-IDENTICAL to the replicated continuation
    restored from the same checkpoint — proving the ZeRO-1 moments
    (and at amp_o2 the fp32 masters) re-pad/re-shard exactly for the
    new mesh. N'=3 exercises genuinely different padding (31 -> 33).

    The amp leg runs the per-variable lowering (bucket cap 0): on the
    CPU backend the AMP x BUCKETED combination drifts one bf16 ulp off
    replicated at world sizes where /N rounds in bf16 (ndev=3) — a
    pre-existing instance of PR 4's optimization_barrier-does-not-pin-
    CPU-fusions caveat, invisible at the power-of-two worlds PR 6
    tested; recorded in ROADMAP."""
    bucket_mb = 0.0 if amp else 0.25
    root = str(tmp_path / "shrink")
    prog4, st4, ln = _build_dp(4, True, amp=amp, bucket_mb=bucket_mb)
    _, exe4, sc4 = _run_dp(prog4, st4, ln, steps=2)
    plan4 = prog4._shard_plan
    assert plan4 is not None and plan4.ndev == 4
    ckpt.save_checkpoint(exe4, root,
                         ckpt.TrainStatus(epoch_no=0, step_no=1),
                         main_program=prog4, scope=sc4)

    for ndev in (3, 2, 1):
        p_s, st_s, ln_s = _build_dp(ndev, True, amp=amp,
                                    bucket_mb=bucket_mb)
        sharded, _, _ = _run_dp(p_s, st_s, ln_s, steps=3, restore=root)
        p_r, st_r, ln_r = _build_dp(ndev, False, amp=amp)
        replicated, _, _ = _run_dp(p_r, st_r, ln_r, steps=3,
                                   restore=root)
        np.testing.assert_array_equal(
            np.asarray(sharded), np.asarray(replicated),
            err_msg="shrink 4->%d not bit-identical" % ndev)
        plan = getattr(p_s, "_shard_plan", None)
        if ndev > 1:
            # the plan (and its bucket layout) re-planned for N'
            assert plan is not None and plan.ndev == ndev
            if bucket_mb:
                assert plan.buckets, "bucket plan must re-plan for N'"
                assert all(e.padded % ndev == 0
                           for b in plan.buckets for e in b.entries)
            padded = sorted({info.padded
                             for info in plan.sharded_state.values()})
            assert all(p % ndev == 0 for p in padded), padded
            if ndev == 3:
                # 31-element tensors: padding genuinely changed vs N=4
                assert any(info.numel == 31 and info.padded == 33
                           for info in plan.sharded_state.values())


# -- elastic supervisor: shrink-to-survivors policy -------------------------

def test_launch_elastic_shrink_drops_dead_rank_and_reassigns(tmp_path):
    """--min_ranks: rank 1 of a 3-worker cohort dies for good; the
    restart relaunches the TWO survivors with contiguous ranks and a
    rebuilt endpoint list, and the supervisor publishes an
    elastic_transition event with the reassignment map + recovery wall
    time into its own telemetry stream."""
    import json as _json

    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "tid = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "attempt = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
        "print('WORLD', os.environ['PADDLE_TRAINERS_NUM'],\n"
        "      'RANK', tid, 'ATTEMPT', attempt,\n"
        "      'EPS', os.environ['PADDLE_TRAINER_ENDPOINTS'],\n"
        "      flush=True)\n"
        + _lost_machines_src(str(tmp_path / "logs"), 3, [1]))
    log_dir = str(tmp_path / "logs")
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6721,127.0.0.1:6722,127.0.0.1:6723",
         "--log_dir", log_dir, "--max_restarts", "1",
         "--min_ranks", "2", str(script)],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "elastic shrink 3 -> 2" in proc.stdout, proc.stdout
    assert "restart 1/1" in proc.stdout

    # attempt 1 ran at world 2 with contiguous ranks over the survivors
    log0 = open(_os.path.join(log_dir, "workerlog.0")).read()
    log1 = open(_os.path.join(log_dir, "workerlog.1")).read()
    assert "WORLD 3 RANK 0 ATTEMPT 0" in log0
    assert "WORLD 2 RANK 0 ATTEMPT 1" in log0
    assert "WORLD 2 RANK 1 ATTEMPT 1" in log1
    a1 = [ln for ln in log1.splitlines() if "ATTEMPT 1" in ln][0]
    eps = a1.split("EPS")[1].strip()
    assert eps == "127.0.0.1:6721,127.0.0.1:6723", a1  # 6722 dropped

    # the supervisor's own telemetry stream carries the seam event,
    # schema-valid against the locked telemetry contract
    sup = _os.path.join(log_dir, "telemetry",
                        "telemetry.supervisor.jsonl")
    assert _os.path.exists(sup), _os.listdir(log_dir)
    recs = [_json.loads(ln) for ln in open(sup) if ln.strip()]
    evs = [r for r in recs if r.get("event") == "elastic_transition"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["old_world"] == 3 and ev["new_world"] == 2
    assert ev["failed_ranks"] == [1]
    assert ev["reassignment"] == {"0": 0, "2": 1}
    assert ev["recovery_s"] >= 0
    from paddle_tpu.observability import schema as tschema

    assert tschema.validate_record(ev, tschema.load_schema()) == []


def test_launch_pod_aware_shrink_flat_fallback_and_rectangular(
        tmp_path):
    """Pod-aware elastic shrink (hybrid multi-pod topology): a 2x2
    cohort (--num_pods 2) losing ONE rank cannot stay rectangular
    (pods 1 vs 2) — the restart falls back to a FLAT 3-rank world,
    the elastic_transition event names the fallback
    (pod_topology=flat_fallback), and the shrunk workers see NO stale
    PADDLE_NUM_PODS/PADDLE_POD_ID. Losing one rank in EACH pod
    re-forms as a legal 1-per-pod 2-pod world. Never a wedged
    rendezvous either way."""
    import json as _json

    def run(kill_tids, ports):
        script = tmp_path / ("worker_%s.py" % "_".join(
            str(t) for t in kill_tids))
        log_dir = str(tmp_path / ("logs_%s" % "_".join(
            str(t) for t in kill_tids)))
        script.write_text(
            "import os, sys, time\n"
            "tid = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "attempt = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
            "print('WORLD', os.environ['PADDLE_TRAINERS_NUM'],\n"
            "      'RANK', tid, 'ATTEMPT', attempt,\n"
            "      'PODS', os.environ.get('PADDLE_NUM_PODS', '-'),\n"
            "      'POD', os.environ.get('PADDLE_POD_ID', '-'),\n"
            "      flush=True)\n"
            + _lost_machines_src(log_dir, len(ports), kill_tids))
        proc = _sp.run(
            [_sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--hosts", ",".join("127.0.0.1:%d" % p for p in ports),
             "--log_dir", log_dir, "--max_restarts", "1",
             "--min_ranks", "2", "--num_pods", "2", str(script)],
            env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
            stderr=_sp.STDOUT, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout
        sup = _os.path.join(log_dir, "telemetry",
                            "telemetry.supervisor.jsonl")
        recs = [_json.loads(ln) for ln in open(sup) if ln.strip()]
        evs = [r for r in recs
               if r.get("event") == "elastic_transition"]
        assert len(evs) == 1
        logs = {tid: open(_os.path.join(
            log_dir, "workerlog.%d" % tid)).read()
            for tid in range(len(ports))
            if _os.path.exists(_os.path.join(log_dir,
                                             "workerlog.%d" % tid))}
        return proc.stdout, evs[0], logs

    # attempt 0 runs 2 pods x 2 ranks (contiguous blocks)
    out, ev, logs = run([1], [6731, 6732, 6733, 6734])
    assert "WORLD 4 RANK 0 ATTEMPT 0 PODS 2 POD 0" in logs[0]
    assert "WORLD 4 RANK 3 ATTEMPT 0 PODS 2 POD 1" in logs[3]
    # lopsided survivors (1 vs 2): flat fallback keeping all three
    assert ev["old_world"] == 4 and ev["new_world"] == 3
    assert ev["pod_topology"] == "flat_fallback"
    assert ev["pods_old"] == 2 and ev["pods_new"] == 1
    assert ev["pod_survivor_counts"] == [1, 2]
    assert "pods 2 -> 1 (flat_fallback)" in out
    assert "WORLD 3 RANK 0 ATTEMPT 1 PODS - POD -" in logs[0]

    # one rank lost in EACH pod: re-forms rectangular at 1 rank/pod
    out, ev, logs = run([1, 2], [6741, 6742, 6743, 6744])
    assert ev["new_world"] == 2
    assert ev["pod_topology"] == "rectangular"
    assert ev["pods_old"] == ev["pods_new"] == 2
    assert ev["ranks_per_pod"] == 1
    assert "WORLD 2 RANK 0 ATTEMPT 1 PODS 2 POD 0" in logs[0]
    # the restarted cohort logs under its NEW contiguous rank ids
    assert "WORLD 2 RANK 1 ATTEMPT 1 PODS 2 POD 1" in logs[1]
    from paddle_tpu.observability import schema as tschema

    assert tschema.validate_record(ev, tschema.load_schema()) == []


def test_launch_elastic_gives_up_below_min_ranks(tmp_path):
    """Survivor count below --min_ranks must NOT relaunch a too-small
    cohort: the launcher exits with the failure rc."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "tid = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "if tid == 0:\n"
        "    time.sleep(30)\n"
        "sys.exit(9)\n")
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", "127.0.0.1:6725,127.0.0.1:6726",
         "--max_restarts", "3", "--min_ranks", "2", str(script)],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=90)
    assert proc.returncode == 9, proc.stdout
    assert "below --min_ranks 2; giving up" in proc.stdout
    # no relaunch happened after the give-up line
    assert "restart 1/3" not in proc.stdout


def test_write_postmortem_index_summarizes_all_attempts(tmp_path):
    """postmortem/index.json (carried-over ROADMAP item): every
    attempt's per-rank dumps summarized in one file — attempt, rank,
    reason, fatal event, last recorded step; unreadable dumps get an
    error entry instead of poisoning the index."""
    import json as _json

    from paddle_tpu.distributed import launch as launch_mod

    pm = tmp_path / "postmortem"
    (pm / "attempt0").mkdir(parents=True)
    (pm / "attempt1").mkdir()
    (pm / "attempt0" / "flightrec.rank1.json").write_text(_json.dumps({
        "reason": "fault-kill",
        "fatal_event": {"event": "fault", "fault": "kill"},
        "n_steps": 4,
        "steps": [{"step": 3}, {"step": 4}], "events": []}))
    (pm / "attempt1" / "flightrec.rank0.json").write_text(_json.dumps({
        "reason": "signal", "fatal_event": {"event": "signal"},
        "n_steps": 2, "steps": [{"step": 9}], "events": []}))
    (pm / "attempt1" / "flightrec.rank2.json").write_text("{torn")
    path = launch_mod._write_postmortem_index(str(pm))
    idx = _json.load(open(path))
    assert idx["attempts"] == 2
    assert len(idx["dumps"]) == 3
    # newest attempt first
    assert [d["attempt"] for d in idx["dumps"]] == [1, 1, 0]
    by = {(d["attempt"], d["rank"]): d for d in idx["dumps"]}
    assert by[(0, 1)]["reason"] == "fault-kill"
    assert by[(0, 1)]["last_step"] == 4
    assert by[(1, 0)]["fatal_event"]["event"] == "signal"
    assert "error" in by[(1, 2)]


# -- supervised elastic acceptance: 4 -> 3 kill/shrink ----------------------

@pytest.mark.slow
@pytest.mark.faults
def test_supervised_elastic_4_to_3_shrink_resumes_bit_identical(
        tmp_path):
    """Acceptance: a supervised 4-rank CPU run killed mid-run (rank 1
    via PADDLE_FAULTS) restarts as a 3-rank cohort (reassigned ranks,
    rebuilt rendezvous), resumes from the last intact checkpoint with
    re-sharded per-rank data, and its post-resume losses are
    BIT-IDENTICAL to an uninterrupted 3-rank run restored from the same
    checkpoint."""
    import json as _json
    import shutil as _shutil

    runner = _os.path.join(_DIR, "elastic_world_runner.py")
    root = str(tmp_path / "ckpt")
    log_dir = str(tmp_path / "logs")
    hosts = ",".join("127.0.0.1:%d" % p
                     for p in (6731, 6733, 6735, 6737))
    # rank 1 dies at its step-5 allreduce contribution (events: 1
    # startup agreement put + 2 per completed step): last published
    # checkpoint is step 3, so the 3-rank cohort resumes at step 4
    proc = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", hosts, "--log_dir", log_dir,
         "--max_restarts", "1", "--min_ranks", "3",
         runner, root, "8", "2", "1", "12"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    assert "elastic shrink 4 -> 3" in proc.stdout, proc.stdout

    log0 = open(_os.path.join(log_dir, "workerlog.0")).read()
    got = {}
    for ln in _loss_lines(log0):
        got[int(ln.split()[1])] = float(ln.split()[2])  # last wins
    assert sorted(got) == list(range(8)), log0
    resumes = [ln for ln in log0.splitlines()
               if ln.startswith("RESUME")]
    assert "RESUME 0 world=4 rank=0 attempt=0" in resumes[0]
    assert "RESUME 4 world=3 rank=0 attempt=1" in resumes[-1], resumes

    # uninterrupted 3-rank reference from the SAME checkpoint: copy
    # only the checkpoints the crashed attempt could have restored
    # (step_no <= 3 — the resumed attempt appended newer ones)
    ref_root = str(tmp_path / "ref_ckpt")
    _os.makedirs(ref_root)
    from paddle_tpu.fluid import checkpoint as _ck

    for name in _os.listdir(root):
        d = _os.path.join(root, name)
        if not _os.path.isdir(d):
            continue
        try:
            if _ck.read_status(d).step_no <= 3:
                _shutil.copytree(d, _os.path.join(ref_root, name))
        except OSError:
            continue
    ref_logs = str(tmp_path / "ref_logs")
    ref_hosts = ",".join("127.0.0.1:%d" % p for p in (6741, 6743, 6745))
    ref = _sp.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--hosts", ref_hosts, "--log_dir", ref_logs,
         runner, ref_root, "8", "2"],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=600)
    assert ref.returncode == 0, ref.stdout
    ref_log0 = open(_os.path.join(ref_logs, "workerlog.0")).read()
    assert "RESUME 4 world=3 rank=0 attempt=0" in ref_log0, ref_log0
    ref_losses = {int(ln.split()[1]): float(ln.split()[2])
                  for ln in _loss_lines(ref_log0)}
    assert sorted(ref_losses) == [4, 5, 6, 7], ref_log0
    for step in (4, 5, 6, 7):
        assert got[step] == ref_losses[step], (
            "step %d not bit-identical: elastic %.17g vs 3-rank ref "
            "%.17g" % (step, got[step], ref_losses[step]))

    # the seam is observable: transition event + recovery wall time
    sup = _os.path.join(log_dir, "telemetry",
                        "telemetry.supervisor.jsonl")
    evs = [_json.loads(ln) for ln in open(sup) if ln.strip()]
    evs = [r for r in evs if r.get("event") == "elastic_transition"]
    assert len(evs) == 1 and evs[0]["old_world"] == 4 \
        and evs[0]["new_world"] == 3 and evs[0]["recovery_s"] > 0
    # ... and tools/perf_analysis.py --elastic reports it
    pa = _sp.run(
        [_sys.executable, _os.path.join(_REPO, "tools",
                                        "perf_analysis.py"),
         "--elastic", "--log-dir", log_dir],
        env=_launch_env(), cwd=_REPO, stdout=_sp.PIPE,
        stderr=_sp.STDOUT, text=True, timeout=240)
    assert pa.returncode == 0, pa.stdout
    assert "world 4 -> 3" in pa.stdout, pa.stdout
