"""Cluster launcher (reference: `python/paddle/distributed/launch.py:193`,
env contract set at `distributed/utils.py:356-360`).

On GPU the launcher spawns one process per device. On TPU one process
drives all local chips (SPMD over the mesh), so the launcher spawns one
process per HOST, keeping the same PADDLE_* env contract:
  PADDLE_TRAINER_ID, PADDLE_CURRENT_ENDPOINT, PADDLE_TRAINERS_NUM,
  PADDLE_TRAINER_ENDPOINTS.
No worker is given a chip of its own: several endpoints on ONE host is
the CPU test shape of the host tier, never a way to split a TPU host's
chips (the workers would all open the same chips and all but one fail).

Supervision (pod-scale preemption is the common case, not the
exception):
  - FAIL FAST: the first worker that exits non-zero terminates the rest
    of the cohort — a half-dead cohort otherwise hangs in collectives
    until the full store timeout;
  - the launcher exits with the FIRST non-zero return code (lowest
    trainer id among the failures observed in a poll cycle),
    deterministically, not the last one seen;
  - `--max_restarts N` restarts the whole cohort up to N times after a
    failure; composed with the elastic checkpoint-resume path
    (fleet.DistributedStrategy.elastic), a preempted run resumes from
    the latest intact checkpoint. PADDLE_RESTART_NUM carries the attempt
    number into the workers. Log files reopen in append mode across
    restarts so no attempt's output is lost;
  - `--min_ranks M` makes those restarts ELASTIC: when a worker dies
    for good, the surviving cohort relaunches at the SMALLER world size
    N' (>= M) instead of requiring all N back — failed endpoints drop
    out, survivors get contiguous ranks 0..N'-1, and the rendezvous
    (host-collective store on endpoints[0] port+1, PS barriers, device
    mesh) rebuilds from the fresh PADDLE_* env. Restore then re-shards
    everything laid out P(dp) over N: checkpoints hold LOGICAL shapes
    (parallel/sharded_update.unshard_scope_value), so the resumed
    cohort's executor re-pads/re-shards ZeRO-1 moments, ZeRO-2 bucket
    plans and AMP fp32 masters for N' (bit-identical to a replicated
    update at any world size), and reader.resharding recomputes the
    per-rank sample assignment. Each transition lands an
    `elastic_transition` telemetry event (old/new world, reassignment
    map, recovery wall time) in <telemetry_dir>/telemetry.supervisor.jsonl.
    Elastic shrink needs the supervisor to own the whole cohort (the
    all-localhost multi-endpoint mode); per-host launchers fall back to
    fixed-world restarts. With `--num_pods K` (or PADDLE_NUM_PODS) the
    ranks partition into K contiguous pods (PADDLE_POD_ID exported;
    hybrid DCN+ICI meshes and the comm-lane telemetry read the
    topology) and the shrink is POD-AWARE: pods stay rectangular
    (every pod lost the same rank count) or the next cohort falls back
    to a flat single-pod world keeping every survivor — the
    elastic_transition event names which (`pod_topology`:
    "rectangular" | "flat_fallback") — never a lopsided topology that
    wedges the hybrid-mesh rendezvous;
  - SIGINT and SIGTERM both tear the cohort down (exit 128+signum);
  - supervised workers default PADDLE_CKPT_AGREE=1: multi-host
    checkpoint restore agrees cross-rank on the newest step EVERY rank
    can read (allreduce-min), so a restarted cohort never diverges on
    one rank's corrupt shard. Export PADDLE_CKPT_AGREE=0 to opt out.

Usage: python -m paddle_tpu.distributed.launch --hosts h1:port,h2:port
       [--max_restarts N] train.py [args...]
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


class ParallelEnvArgs:
    def __init__(self):
        self.cluster_node_ips = None
        self.node_ip = None
        self.use_paddlecloud = False
        self.started_port = None
        self.print_config = True
        self.selected_devices = None


def _parse_args(argv):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--hosts", type=str, default="127.0.0.1:6170",
                   help="comma-separated host:port endpoints (one per host)")
    p.add_argument("--host_id", type=int, default=None,
                   help="index of this host in --hosts (default: derive "
                        "from matching local address or 0)")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="restart the whole cohort up to N times after a "
                        "worker failure (composes with elastic "
                        "checkpoint-resume)")
    p.add_argument("--min_ranks", type=int, default=0,
                   help="elastic world-size policy: a restart may drop "
                        "dead workers and relaunch the survivors at any "
                        "world size >= M (0 = fixed world: all N must "
                        "come back)")
    p.add_argument("--hang_timeout", type=float, default=None,
                   help="runtime hang escalation: export "
                        "FLAGS_tpu_hang_timeout_s=S to the workers "
                        "(arming their in-process watchdogs) and watch "
                        "their telemetry streams for `hang` events / "
                        "heartbeat silence; an alive-but-wedged cohort "
                        "is dumped, killed and routed through the "
                        "--min_ranks elastic restart with the desync "
                        "verdict attached. Default: the "
                        "PADDLE_HANG_TIMEOUT_S env, else 0 (off)")
    p.add_argument("--num_pods", type=int, default=0,
                   help="multi-pod topology: partition the ranks into K "
                        "contiguous pods (PADDLE_NUM_PODS/PADDLE_POD_ID "
                        "exported to workers; hybrid DCN+ICI meshes and "
                        "the comm-lane telemetry read them). 0 = the "
                        "PADDLE_NUM_PODS env, else flat. Elastic "
                        "shrink keeps pods RECTANGULAR (equal-size) or "
                        "falls back to a flat world — never a wedged "
                        "rendezvous")
    p.add_argument("--mp_degree", type=int, default=0,
                   help="tensor (model) parallel degree: factor each "
                        "worker's intra-pod device tier into (replica, "
                        "model) — PADDLE_MP_DEGREE exported to workers; "
                        "hybrid (dcn, replica, model) meshes "
                        "(parallel/env.create_hybrid_mesh) and the "
                        "comm-lane telemetry read it. 0 = the "
                        "PADDLE_MP_DEGREE env, else 1 (no model axis). "
                        "Must divide each worker's local device count "
                        "or the worker falls back to a flat mesh with "
                        "a warning")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _launch_num_pods(args, world):
    """The effective pod count for a cohort of `world` ranks:
    --num_pods, else PADDLE_NUM_PODS, else 1 (flat). A count that does
    not divide the world cannot form rectangular pods — warn and run
    flat rather than hand the workers a lopsided topology."""
    npods = args.num_pods
    if not npods:
        try:
            npods = int(os.environ.get("PADDLE_NUM_PODS", "1") or 1)
        except ValueError:
            npods = 1
    if npods <= 1:
        return 1
    if world % npods:
        sys.stderr.write(
            "paddle_tpu.launch: %d rank(s) not divisible into %d "
            "pods; running a flat (single-pod) world\n"
            % (world, npods))
        return 1
    return npods


def _launch_mp_degree(args):
    """The effective model-parallel degree: --mp_degree, else
    PADDLE_MP_DEGREE, else 1 (no model axis). Divisibility against each
    worker's LOCAL device count is the worker's own check
    (parallel/env.create_hybrid_mesh warns and runs flat) — the
    launcher only resolves and exports the knob."""
    mp = getattr(args, "mp_degree", 0)
    if not mp:
        try:
            mp = int(os.environ.get("PADDLE_MP_DEGREE", "1") or 1)
        except ValueError:
            mp = 1
    return mp if mp > 1 else 1


def _pod_shrink(endpoints, failed_tids, npods):
    """Pod-aware elastic shrink decision. Returns (survivor_endpoints,
    new_npods, pod_event_fields): the surviving endpoints in rank
    order, the pod count of the NEXT cohort, and the fields the
    elastic_transition event carries. Pods stay RECTANGULAR — every
    pod the same size, the invariant a hybrid (dcn, ici) mesh needs —
    when each pod lost the same number of ranks; otherwise the next
    cohort falls back to a flat (npods=1) world with every survivor,
    and the event names the fallback. Never returns a lopsided
    topology (the wedged-rendezvous failure mode)."""
    failed = set(failed_tids)
    survivors = [ep for tid, ep in enumerate(endpoints)
                 if tid not in failed]
    if npods <= 1:
        return survivors, 1, {}
    per_pod = len(endpoints) // npods
    counts = [0] * npods
    for tid in range(len(endpoints)):
        if tid not in failed:
            counts[tid // per_pod] += 1
    rectangular = len(set(counts)) == 1 and counts[0] > 0
    if rectangular:
        return survivors, npods, {
            "pods_old": npods, "pods_new": npods,
            "pod_topology": "rectangular",
            "ranks_per_pod": counts[0]}
    return survivors, 1, {
        "pods_old": npods, "pods_new": 1,
        "pod_topology": "flat_fallback",
        "pod_survivor_counts": counts}


def _worker_env(endpoints, tid, restart_no, base_env=None,
                telemetry_dir=None, npods=1, hang_timeout_s=0.0,
                compile_cache_dir=None, mp_degree=1):
    """The PADDLE_* contract for one supervised worker. Cross-rank
    checkpoint-step agreement (PADDLE_CKPT_AGREE, see
    distributed/sharded_checkpoint.agree_newest_intact) is ON by
    default for supervised cohorts — a restarted cohort must not let
    one rank's corrupt newest shard silently diverge the replicas; the
    protocol is fault-injection tested and a no-op for single-worker
    cohorts (group_from_env returns None at world size 1). An explicit
    PADDLE_CKPT_AGREE=0 in the launcher's environment is respected.

    `telemetry_dir` (derived from --log_dir unless the launcher's own
    env already sets FLAGS_tpu_telemetry_dir) turns on each worker's
    observability sink + flight recorder, so a failed cohort leaves
    per-rank postmortems the supervisor can collect."""
    env = dict(os.environ if base_env is None else base_env)
    env.setdefault("PADDLE_CKPT_AGREE", "1")
    if telemetry_dir:
        env.setdefault("FLAGS_tpu_telemetry_dir", telemetry_dir)
    if compile_cache_dir:
        # persistent compilation cache shared across the cohort AND
        # across restarts/elastic transitions: a relaunched worker
        # deserializes its XLA executables instead of recompiling, so
        # recovery is coordination-bound, not compile-bound. The
        # supervisor always names one; other callers may leave it out
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir
    if hang_timeout_s and hang_timeout_s > 0:
        # one knob arms both tiers: the workers' in-process watchdogs
        # (stack + in-flight dumps, `hang`/`heartbeat` events) and the
        # supervisor's escalation watch. An explicit value in the
        # launcher's env wins.
        env.setdefault("FLAGS_tpu_hang_timeout_s",
                       repr(float(hang_timeout_s)))
    env.update({
        "PADDLE_TRAINER_ID": str(tid),
        "PADDLE_CURRENT_ENDPOINT": endpoints[tid],
        "PADDLE_TRAINERS_NUM": str(len(endpoints)),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_RESTART_NUM": str(restart_no),
    })
    if npods > 1:
        # multi-pod topology: contiguous rank blocks per pod. Workers
        # read these into hybrid (dcn, ici) meshes
        # (parallel/env.dcn_replicas) and the comm-lane telemetry
        env.update({
            "PADDLE_NUM_PODS": str(npods),
            "PADDLE_POD_ID": str(tid // (len(endpoints) // npods)),
        })
    else:
        # an elastic flat fallback must not leak the OLD topology into
        # the shrunk cohort through the inherited environment
        env.pop("PADDLE_NUM_PODS", None)
        env.pop("PADDLE_POD_ID", None)
    if mp_degree > 1:
        # model-parallel degree: each worker factors its intra-pod
        # device tier into (replica, model) —
        # parallel/env.create_hybrid_mesh and the comm-lane telemetry
        # read it (same contract as the pod vars above)
        env["PADDLE_MP_DEGREE"] = str(mp_degree)
    else:
        env.pop("PADDLE_MP_DEGREE", None)
    return env


def _telemetry_dir_for(args):
    """Where the workers' observability sink + flight dumps live: an
    explicit FLAGS_tpu_telemetry_dir in the launcher env wins;
    otherwise <log_dir>/telemetry; None without either (workers then
    run with telemetry off, dumps land in their CWD on a fault kill)."""
    explicit = os.environ.get("FLAGS_tpu_telemetry_dir")
    if explicit:
        return explicit
    if args.log_dir:
        return os.path.join(args.log_dir, "telemetry")
    return None


def _compile_cache_dir():
    """Where the workers' persistent compilation cache lives: the
    directory JAX_COMPILATION_CACHE_DIR names in the launcher's
    environment, passed through untouched; otherwise the fixed
    `<checkout>/.jax_cache` (the path is part of jax's cache key, so it
    is never derived from a log dir, a pid or the time). It survives
    restarts — that is its entire point — and is never collected into
    postmortem/."""
    from ..fluid import compile_cache

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        compile_cache.default_dir()


def _collect_flight_dumps(args, attempt):
    """Before a cohort restart (and after a final failure), move every
    per-rank flight-recorder dump AND telemetry JSONL stream into
    <log_dir>/postmortem/attempt<K>/ — the restart's fresh workers
    overwrite flightrec.rank<R>.json and would otherwise APPEND
    attempt K+1's step records (with a reset step counter) into
    attempt K's telemetry.rank<R>.jsonl, silently mixing two training
    attempts in one stream. The next attempt starts with a clean dir;
    run tools/perf_analysis.py --stragglers against the postmortem
    subdir to analyze a failed attempt."""
    import shutil

    tdir = _telemetry_dir_for(args)
    if not tdir or not os.path.isdir(tdir):
        return []
    dest_root = args.log_dir or tdir
    dest = os.path.join(dest_root, "postmortem", "attempt%d" % attempt)
    collected = []
    for fname in sorted(os.listdir(tdir)):
        is_dump = fname.startswith("flightrec.rank") and \
            fname.endswith(".json")
        is_jsonl = fname.startswith("telemetry.rank") and \
            fname.endswith(".jsonl")
        # preempt markers ride along: launch() has already read them
        # by the time dumps are collected, and a restarted attempt
        # must start marker-clean
        is_marker = fname.startswith("preempted.rank") and \
            fname.endswith(".json")
        if not (is_dump or is_jsonl or is_marker):
            continue
        os.makedirs(dest, exist_ok=True)
        try:
            shutil.move(os.path.join(tdir, fname),
                        os.path.join(dest, fname))
            if is_dump:
                collected.append(os.path.join(dest, fname))
        except OSError:
            pass
    if collected:
        sys.stderr.write(
            "paddle_tpu.launch: collected %d flight-recorder dump(s) "
            "into %s\n" % (len(collected), dest))
    _write_postmortem_index(os.path.join(dest_root, "postmortem"))
    return collected


def _preempt_marker_ranks(tdir):
    """Ranks with a preempt marker (preempted.rank<R>.json) in the
    telemetry dir: they left on a preemption notice — possibly with
    exit 0 — and must be treated as lost by the restart shrink."""
    if not tdir:
        return []
    from .preemption import read_preempt_markers

    return sorted({int(m["rank"]) for m in read_preempt_markers(tdir)})


def _write_postmortem_index(pm_root):
    """Refresh <log_dir>/postmortem/index.json: one entry per per-rank
    flight dump across EVERY attempt (attempt, rank, reason, fatal
    event, last recorded step), newest attempt first — so a
    multi-restart failure is triaged from one file instead of N x K
    dumps (ROADMAP carried-over observability item). Written atomically;
    unreadable dumps get an "error" entry rather than poisoning the
    index."""
    import json
    import re

    if not os.path.isdir(pm_root):
        return None
    att_re = re.compile(r"^attempt(\d+)$")
    dump_re = re.compile(r"^flightrec\.rank(\d+)\.json$")
    dumps = []
    for aname in sorted(os.listdir(pm_root)):
        m = att_re.match(aname)
        if not m:
            continue
        attempt = int(m.group(1))
        adir = os.path.join(pm_root, aname)
        for fname in sorted(os.listdir(adir)):
            dm = dump_re.match(fname)
            if not dm:
                continue
            entry = {"attempt": attempt, "rank": int(dm.group(1)),
                     "path": os.path.join(aname, fname)}
            try:
                with open(os.path.join(adir, fname)) as f:
                    doc = json.load(f)
                entry["reason"] = doc.get("reason")
                entry["fatal_event"] = doc.get("fatal_event")
                entry["n_steps"] = doc.get("n_steps")
                steps = doc.get("steps") or []
                entry["last_step"] = steps[-1].get("step") if steps \
                    else None
            except (OSError, ValueError) as e:
                entry["error"] = "%s: %s" % (type(e).__name__, e)
            dumps.append(entry)
    dumps.sort(key=lambda d: (-d["attempt"], d["rank"]))
    index = {"attempts": 1 + max((d["attempt"] for d in dumps),
                                 default=-1),
             "dumps": dumps}
    path = os.path.join(pm_root, "index.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(index, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def _supervisor_event(args, etype, **fields):
    """Append one telemetry event record to the supervisor's OWN stream
    (<telemetry_dir>/telemetry.supervisor.jsonl, same "event" schema as
    the workers' registry sink — tools/telemetry_schema.json). Written
    directly rather than through observability.registry: the supervisor
    must stay a subprocess babysitter and not import the jax stack. The
    stream is NOT collected into postmortem/ between attempts — it is
    the one place the whole run's elastic seams live."""
    import json

    tdir = _telemetry_dir_for(args)
    if not tdir:
        return None
    rec = {"kind": "event", "event": str(etype), "rank": -1, "step": 0,
           "ts": time.time()}
    rec.update(fields)
    try:
        os.makedirs(tdir, exist_ok=True)
        with open(os.path.join(tdir, "telemetry.supervisor.jsonl"),
                  "a") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    except OSError:
        return None
    return rec


class _TransitionWatch:
    """Defers one elastic_transition event until the respawned cohort's
    FIRST step records land in the workers' telemetry streams, so
    `recovery_s` splits into its two real components:

      coordination_s  failure detection -> shrunk cohort respawned
                      (the supervisor's own work: teardown, rank
                      reassignment, rendezvous env rebuild)
      compile_s       the new cohort's first-step compile (max over
                      ranks of the first step record's compile_ms) —
                      the part the persistent compilation cache
                      (JAX_COMPILATION_CACHE_DIR) collapses from
                      minutes to ~0

    recovery_s = coordination_s + compile_s. Workers that emit no
    telemetry (plain scripts) leave compile_s absent and recovery_s =
    coordination_s — exactly the event shape shipped before the split.
    The event is emitted ONCE: when every rank's first step arrived,
    or at flush() (cohort exit / next failure / supervisor teardown),
    whichever comes first."""

    def __init__(self, telemetry_dir, fields, world, emit,
                 poll_every_s=0.25):
        self.dir = telemetry_dir
        self.fields = dict(fields)
        self.world = int(world)
        self._emit = emit
        self._poll_every = float(poll_every_s)
        self._last_poll = 0.0
        self._offsets = {}
        self._first_compile_ms = {}  # rank -> first step's compile_ms
        self.done = False
        if not telemetry_dir:
            self.flush()

    def poll(self):
        if self.done:
            return
        now = time.monotonic()
        if now - self._last_poll < self._poll_every:
            return
        self._last_poll = now
        import json

        try:
            fnames = [f for f in sorted(os.listdir(self.dir))
                      if f.startswith("telemetry.rank")
                      and f.endswith(".jsonl")]
        except OSError:
            return
        for fname in fnames:
            path = os.path.join(self.dir, fname)
            off = self._offsets.get(fname, 0)
            try:
                size = os.path.getsize(path)
                if size <= off:
                    continue
                with open(path) as f:
                    f.seek(off)
                    chunk = f.read(size - off)
            except OSError:
                continue
            consumed = chunk.rfind("\n") + 1
            self._offsets[fname] = off + consumed
            for line in chunk[:consumed].splitlines():
                if '"kind": "step"' not in line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                rank = int(rec.get("rank", -1))
                if rank in self._first_compile_ms:
                    continue
                self._first_compile_ms[rank] = float(
                    rec.get("compile_ms", 0.0))
        if len(self._first_compile_ms) >= self.world:
            self.flush()

    def flush(self):
        """Emit with whatever arrived (all exit paths call this —
        the seam event must never be lost to a fast-exiting or
        telemetry-less cohort)."""
        if self.done:
            return
        self.done = True
        fields = dict(self.fields)
        coord = float(fields.get("coordination_s", 0.0))
        if self._first_compile_ms:
            fields["compile_s"] = round(
                max(self._first_compile_ms.values()) / 1e3, 4)
            fields["recovery_s"] = round(coord + fields["compile_s"], 4)
        else:
            fields["recovery_s"] = round(coord, 4)
        self._emit(fields)


class _HangWatch:
    """Supervisor-side hang detection over the workers' telemetry
    streams — plain file tailing, no jax imports, no RPC to the wedged
    cohort.

    Primary signal: a worker watchdog (FLAGS_tpu_hang_timeout_s, armed
    by --hang_timeout) publishes a `hang` event into its JSONL sink
    the moment a collective is stuck past the timeout; this watch
    tails `telemetry.rank*.jsonl` incrementally and fires on the first
    one. Fallback: every stream silent (no bytes appended — armed
    watchdogs heartbeat, so silence means the PROCESS is wedged before
    its watchdog could arm, or telemetry died with it) for
    4x the timeout after at least one record was seen."""

    STALE_FACTOR = 4.0

    def __init__(self, telemetry_dir, timeout_s, poll_every_s=0.5):
        self.dir = telemetry_dir
        self.timeout_s = float(timeout_s)
        self._poll_every = float(poll_every_s)
        self._last_poll = 0.0
        self._offsets = {}        # fname -> bytes already scanned
        self._last_growth = None  # monotonic ts of last appended byte
        self._seen_any = False
        self._hang_events = []    # parsed worker hang event records

    def _rank_files(self):
        try:
            return [f for f in sorted(os.listdir(self.dir))
                    if f.startswith("telemetry.rank")
                    and f.endswith(".jsonl")]
        except OSError:
            return []

    def poll(self):
        """None, or a detection dict {"via": "hang-event"|"silence",
        "ranks": [ranks that reported], "events": [...]}."""
        now = time.monotonic()
        if now - self._last_poll < self._poll_every:
            return None
        self._last_poll = now
        if self._last_growth is None:
            self._last_growth = now
        import json

        grew = False
        for fname in self._rank_files():
            path = os.path.join(self.dir, fname)
            off = self._offsets.get(fname, 0)
            try:
                size = os.path.getsize(path)
                if size < off:
                    # rotation: the active file was os.replace'd to a
                    # .gN generation and restarted at 0 — a stale
                    # offset would both hide new hang events and let
                    # the silence fallback kill a healthy cohort
                    off = self._offsets[fname] = 0
                if size <= off:
                    continue
                with open(path) as f:
                    f.seek(off)
                    chunk = f.read(size - off)
            except OSError:
                continue
            # only complete lines; a torn tail re-reads next poll
            consumed = chunk.rfind("\n") + 1
            self._offsets[fname] = off + consumed
            grew = grew or consumed > 0
            self._seen_any = self._seen_any or consumed > 0
            for line in chunk[:consumed].splitlines():
                if '"event": "hang"' not in line:
                    continue
                try:
                    self._hang_events.append(json.loads(line))
                except ValueError:
                    continue
        if grew:
            self._last_growth = now
        if self._hang_events:
            return {"via": "hang-event",
                    "ranks": sorted({int(e.get("rank", -1))
                                     for e in self._hang_events}),
                    "events": list(self._hang_events)}
        if self._seen_any and \
                now - self._last_growth > self.STALE_FACTOR \
                * self.timeout_s:
            return {"via": "silence", "ranks": [], "events": []}
        return None


def _hang_verdict(telemetry_dir):
    """Cross-rank desync verdict over the worker watchdogs' flight
    dumps (observability/watchdog.py's pure-JSON analyzer — the same
    code `perf_analysis --hang-report` runs, so supervisor and offline
    tooling can never disagree). Returns the verdict dict, or None
    when the dumps are unreadable/absent."""
    try:
        from ..observability.watchdog import (analyze_hang,
                                              load_hang_bundle)

        docs = load_hang_bundle(telemetry_dir)
        if not docs:
            return None
        return analyze_hang(docs)
    except Exception as e:  # noqa: BLE001 - escalation must proceed
        sys.stderr.write("paddle_tpu.launch: hang verdict failed: "
                         "%s\n" % e)
        return None


def _wait_for_hang_dumps(telemetry_dir, world, grace_s):
    """Give every rank's watchdog a beat to land its flight dump
    before the cohort is killed (they all fire within ~a tick of each
    other; the kill itself would suppress nothing — the dump is
    written first — but collecting a complete bundle beats a partial
    one)."""
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline:
        try:
            dumps = [f for f in os.listdir(telemetry_dir)
                     if f.startswith("flightrec.rank")
                     and f.endswith(".json")]
        except OSError:
            dumps = []
        if len(dumps) >= world:
            return True
        time.sleep(0.1)
    return False


def _hang_timeout_for(args):
    """--hang_timeout, else PADDLE_HANG_TIMEOUT_S, else 0 (off)."""
    if args.hang_timeout is not None:
        return max(0.0, float(args.hang_timeout))
    try:
        return max(0.0, float(
            os.environ.get("PADDLE_HANG_TIMEOUT_S", "0") or 0))
    except ValueError:
        return 0.0


def _spawn_cohort(args, endpoints, local_ids, restart_no, npods=1):
    procs, logs = [], []
    tdir = _telemetry_dir_for(args)
    if tdir:
        os.makedirs(tdir, exist_ok=True)
    ccdir = _compile_cache_dir()
    os.makedirs(ccdir, exist_ok=True)
    for tid in local_ids:
        env = _worker_env(endpoints, tid, restart_no,
                          telemetry_dir=tdir, npods=npods,
                          hang_timeout_s=_hang_timeout_for(args),
                          compile_cache_dir=ccdir,
                          mp_degree=_launch_mp_degree(args))
        cmd = [sys.executable, "-u", args.training_script] \
            + args.training_script_args
        out = None
        if args.log_dir:
            # append across restarts: attempt 0's tail is the evidence
            # for WHY the cohort restarted
            out = open(os.path.join(args.log_dir, "workerlog.%d" % tid),
                       "a" if restart_no else "w")
        logs.append(out)
        procs.append(subprocess.Popen(cmd, env=env, stdout=out,
                                      stderr=subprocess.STDOUT
                                      if out else None))
    return procs, logs


def _terminate_all(procs, grace_s=10.0):
    for p in procs:
        if p.poll() is None:
            try:
                p.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()


#: conventional exit code for a hang-escalated cohort kill (the shell
#: `timeout` convention; distinguishes "wedged, supervisor killed it"
#: from a worker's own failure in logs and restart accounting)
HANG_RC = 124


def _supervise(procs, local_ids, stop_sig, hang_watch=None,
               trans_watch=None):
    """Poll until all workers exit or one fails. Returns (rc,
    failed_tids, hang): rc is the first non-zero return code (lowest
    trainer id among the failures seen in the poll cycle that detected
    the fault), 0 on clean completion; failed_tids names the workers
    that died ON THEIR OWN in that cycle — the elastic policy treats
    them as lost machines (survivors are terminated by the fail-fast
    teardown and are NOT in the list). `hang` is None, or the
    _HangWatch detection dict for an alive-but-wedged cohort (rc is
    HANG_RC there; the guilty rank comes from the desync verdict over
    the collected dumps, not from this loop)."""
    while True:
        if trans_watch is not None and not trans_watch.done:
            # the pending elastic_transition is waiting for the new
            # cohort's first step records (its compile_s half)
            trans_watch.poll()
        if stop_sig["sig"] is not None:
            _terminate_all(procs)
            return 128 + stop_sig["sig"], [], None
        failed = [(tid, p.returncode) for tid, p in zip(local_ids, procs)
                  if p.poll() is not None and p.returncode != 0]
        if failed:
            # fail fast: a half-dead cohort hangs in collectives.
            # Popen reports a signal death as -N; exit statuses are
            # 0..255, so surface it as the conventional 128+N
            bad_tid, bad_rc = failed[0]
            if bad_rc < 0:
                bad_rc = 128 - bad_rc
            # DEGRADE_RC = a SURVIVOR whose live-resize seam failed,
            # loudly requesting the cohort-restart fallback — its
            # machine is healthy, so it must NOT be dropped by the
            # shrink (the preempt markers name who actually left)
            from .preemption import DEGRADE_RC

            lost = [tid for tid, rc_ in failed if rc_ != DEGRADE_RC]
            degraded = [tid for tid, rc_ in failed if rc_ == DEGRADE_RC]
            sys.stderr.write(
                "paddle_tpu.launch: worker %d exited with %d%s; "
                "terminating cohort\n"
                % (bad_tid, bad_rc,
                   " (live-resize degrade from worker(s) %s)"
                   % degraded if degraded else ""))
            _terminate_all(procs)
            return bad_rc, lost, None
        if all(p.poll() is not None for p in procs):
            return 0, [], None
        if hang_watch is not None:
            hang = hang_watch.poll()
            if hang is not None:
                sys.stderr.write(
                    "paddle_tpu.launch: cohort alive but wedged "
                    "(detected via %s%s); collecting dumps and "
                    "terminating\n"
                    % (hang["via"],
                       ", hang reported by rank(s) %s" % hang["ranks"]
                       if hang["ranks"] else ""))
                # let every rank's watchdog land its stack + in-flight
                # dump before the kill (they fire within ~a tick of
                # each other); SIGTERM dumps are once-suppressed after
                # a watchdog dump, so what's on disk IS the evidence
                _wait_for_hang_dumps(
                    hang_watch.dir, len(procs),
                    grace_s=min(10.0, max(
                        1.0, hang_watch.timeout_s)))
                # re-poll after the grace: the first detection froze
                # `ranks` at whichever rank's event landed first, and
                # the fallback blame must not punish ranks for losing
                # a reporting-order race
                hang_watch._last_poll = 0.0
                hang = hang_watch.poll() or hang
                _terminate_all(procs)
                return HANG_RC, [], hang
        time.sleep(0.1)


def _owns_whole_cohort(args, endpoints):
    """True when THIS launcher supervises every worker (the
    all-localhost multi-endpoint test/dev mode) — the precondition for
    elastic world-size shrink: a per-host launcher only sees its own
    workers and cannot reassign the global rank set."""
    return args.host_id is None and len(endpoints) > 1 and all(
        e.split(":")[0] in ("127.0.0.1", "localhost") for e in endpoints)


def launch(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    endpoints = args.hosts.split(",")
    host_id = args.host_id if args.host_id is not None else 0

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    stop_sig = {"sig": None}
    live_procs = []

    def _sig(signum, frame):
        stop_sig["sig"] = signum
        for p in live_procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    if args.min_ranks > 0 and not _owns_whole_cohort(args, endpoints):
        sys.stderr.write(
            "paddle_tpu.launch: --min_ranks needs the supervisor to own "
            "the whole cohort (all-localhost endpoints, no --host_id); "
            "falling back to fixed-world restarts\n")
    if _hang_timeout_for(args) > 0 and not _telemetry_dir_for(args):
        sys.stderr.write(
            "paddle_tpu.launch: --hang_timeout needs a telemetry dir "
            "(--log_dir or FLAGS_tpu_telemetry_dir) for supervisor-"
            "side detection; workers still arm their in-process "
            "watchdogs (dumps land in their CWD) but hang ESCALATION "
            "is off\n")

    max_r = max(args.max_restarts, 0)
    rc = 0
    pending_evt, t_fail = None, None
    npods = _launch_num_pods(args, len(endpoints))
    for attempt in range(max_r + 1):
        # On a single-host invocation with multiple endpoints we spawn
        # them all locally (test/dev mode, mirrors
        # multi-process-on-localhost testing — SURVEY.md §4.5). On real
        # clusters each host runs launch with its --host_id.
        # Recomputed per attempt: an elastic shrink changes the world.
        local_ids = list(range(len(endpoints))) \
            if _owns_whole_cohort(args, endpoints) else [host_id]
        procs, logs = _spawn_cohort(args, endpoints, local_ids, attempt,
                                    npods=npods)
        tdir = _telemetry_dir_for(args)
        trans_watch = None
        if pending_evt is not None:
            # coordination wall time = failure detection -> shrunk
            # cohort respawned. The event itself is DEFERRED until the
            # new cohort's first step records land, so it can report
            # compile_s (the recompile the persistent compilation
            # cache is supposed to collapse) separately — see
            # _TransitionWatch; a telemetry-less cohort emits
            # immediately with coordination time only
            pending_evt["coordination_s"] = round(
                time.monotonic() - t_fail, 4)
            trans_watch = _TransitionWatch(
                tdir, pending_evt, len(endpoints),
                emit=lambda fields: _supervisor_event(
                    args, "elastic_transition", **fields))
            pending_evt = None
        live_procs[:] = procs
        hang_timeout = _hang_timeout_for(args)
        hang_watch = (_HangWatch(tdir, hang_timeout)
                      if hang_timeout > 0 and tdir else None)
        try:
            rc, failed_tids, hang = _supervise(procs, local_ids,
                                               stop_sig, hang_watch,
                                               trans_watch)
        finally:
            if trans_watch is not None and not trans_watch.done:
                # cohort ended (clean exit, failure, or signal) before
                # every rank's first step arrived: tail once more, then
                # emit with what there is — the seam event must land
                # before the telemetry files move to postmortem/
                trans_watch._last_poll = 0.0
                trans_watch.poll()
                trans_watch.flush()
            for f in logs:
                if f:
                    f.close()
        if rc == 0 or stop_sig["sig"] is not None:
            break
        t_fail = time.monotonic()
        hang_fields = {}
        if hang is not None:
            # name the guilty rank BEFORE the dumps move: the desync
            # verdict over the per-rank in-flight tables (the same
            # analyzer perf_analysis --hang-report runs offline)
            verdict = _hang_verdict(tdir)
            guilty = list((verdict or {}).get("guilty_ranks") or [])
            if verdict is None and hang["ranks"]:
                # NO verdict at all (dumps missing/torn): fall back to
                # blaming the ranks that never published a hang event
                # — a fully wedged process (stuck before its watchdog
                # armed) can't report. A verdict that EXISTS but names
                # nobody ("indeterminate": every rank arrived, the
                # store/wire itself wedged) is respected: no machine
                # is dropped on a guess.
                reporters = set(hang["ranks"])
                guilty = [tid for tid in local_ids
                          if tid not in reporters]
            failed_tids = guilty
            hang_fields = {
                "hang": True,
                "hang_via": hang["via"],
                "hang_collective": (verdict or {}).get("collective"),
                "hang_op": (verdict or {}).get("op"),
                "hang_verdict": (verdict or {}).get("verdict"),
                "hang_guilty_ranks": guilty,
            }
            _supervisor_event(
                args, "hang",
                stalled_s=max([float(e.get("stalled_s", 0.0))
                               for e in hang["events"]] or [0.0]),
                inflight_n=max([int(e.get("inflight_n", 0))
                                for e in hang["events"]] or [0]),
                via=hang["via"], attempt=attempt,
                collective=hang_fields["hang_collective"] or "",
                verdict=hang_fields["hang_verdict"] or "",
                guilty_ranks=guilty)
            sys.stderr.write(
                "paddle_tpu.launch: hang verdict: %s (collective %s, "
                "guilty rank(s) %s)\n"
                % (hang_fields["hang_verdict"],
                   hang_fields["hang_collective"], guilty or "none"))
        # preempt markers: ranks that left via a preemption notice
        # (live seam, or the doomed half of a degraded one) exited 0 —
        # the restart shrink must drop them exactly like crashed ranks
        # (distributed/preemption.py writes the marker FIRST in the
        # seam, so it survives any later seam failure)
        preempt_ranks = _preempt_marker_ranks(tdir)
        if preempt_ranks:
            failed_tids = sorted(set(failed_tids) | set(preempt_ranks))
            sys.stderr.write(
                "paddle_tpu.launch: preempt marker(s) for rank(s) %s — "
                "included in the shrink\n" % preempt_ranks)
        # secure this attempt's per-rank flight-recorder dumps before
        # the restarted cohort overwrites them (and keep the final
        # failed attempt's evidence too when restarts are exhausted)
        _collect_flight_dumps(args, attempt)
        if attempt >= max_r:
            break
        if args.min_ranks > 0 and failed_tids \
                and _owns_whole_cohort(args, endpoints):
            survivors, new_npods, pod_fields = _pod_shrink(
                endpoints, failed_tids, npods)
            if len(survivors) < args.min_ranks:
                sys.stderr.write(
                    "paddle_tpu.launch: only %d endpoint(s) left after "
                    "dropping ranks %s — below --min_ranks %d; giving "
                    "up\n" % (len(survivors), sorted(failed_tids),
                              args.min_ranks))
                break
            if len(survivors) < len(endpoints):
                reassignment = {
                    old: new for new, old in enumerate(
                        tid for tid in range(len(endpoints))
                        if tid not in set(failed_tids))}
                from .preemption import DEGRADE_RC as _DEGRADE_RC

                degrade_fields = {}
                if preempt_ranks:
                    degrade_fields["preempted_ranks"] = preempt_ranks
                if rc == _DEGRADE_RC:
                    # the live seam failed mid-recovery and a survivor
                    # demanded this restart — record the degradation so
                    # perf_analysis --elastic shows live-vs-restart
                    # honestly
                    degrade_fields["degraded_from_live"] = True
                pending_evt = dict(
                    old_world=len(endpoints),
                    new_world=len(survivors),
                    mode="restart",
                    failed_ranks=sorted(failed_tids),
                    reassignment={str(o): n
                                  for o, n in reassignment.items()},
                    attempt=attempt + 1, **pod_fields,
                    **degrade_fields,
                    # a hang-escalated shrink carries its desync
                    # verdict: WHY this rank was dropped, stitched to
                    # the postmortem bundle the dumps moved into
                    **hang_fields)
                sys.stderr.write(
                    "paddle_tpu.launch: elastic shrink %d -> %d ranks "
                    "(dropped %s; reassignment %s%s)\n"
                    % (len(endpoints), len(survivors),
                       sorted(failed_tids),
                       {o: n for o, n in sorted(reassignment.items())},
                       ("; pods %d -> %d (%s)" % (
                           npods, new_npods,
                           pod_fields.get("pod_topology"))
                        if npods > 1 else "")))
                endpoints = survivors
                npods = new_npods
        sys.stderr.write(
            "paddle_tpu.launch: cohort failed (rc=%d); restart "
            "%d/%d\n" % (rc, attempt + 1, args.max_restarts))
    sys.exit(rc)


if __name__ == "__main__":
    launch()
