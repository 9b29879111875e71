"""Runtime hang watchdog: in-flight collective tracing, all-rank stack
forensics, and cross-rank desync diagnosis.

At pod scale a single stalled rank wedges the whole mesh: every other
rank blocks inside a collective with no error and no crash (Kumar et
al. 1909.09756; Wang et al. 2011.03641 — synchronous-collective stalls
are the dominant failure mode of scaled data parallelism). The flight
recorder (PR 7) fires only on exceptions/kills, and tpu-lint's
divergence checker (PR 5) proves schedules statically, before launch.
This module is the runtime twin, three pieces:

- **In-flight collective trace** (`InflightTrace`, always on — the
  NCCL-flight-recorder idiom adapted to the host-collective tier):
  every host collective and RPC barrier records enqueue → arrived →
  complete into a bounded ring keyed by the SAME schedule-key grammar
  the static checker uses (`analysis.collectives.runtime_schedule_key`),
  so the static and runtime checkers can never disagree on what "the
  same collective" means. The flight recorder dumps the table with
  every postmortem. Cost: a few dict ops per collective; it never
  touches the step path, the lowering, or the telemetry stream.

- **Watchdog thread** (`HangWatchdog`, armed by
  `FLAGS_tpu_hang_timeout_s`, default 0 = off): when a collective has
  been in flight past the timeout and neither a step epilogue nor a
  collective completion has advanced meanwhile, it dumps all-thread
  python stacks (`sys._current_frames`) plus the in-flight table
  through `flight.py`'s atomic path, publishes a `hang` event into the
  telemetry registry (the supervisor tails it), and optionally pulls a
  `capture.py` xplane trace of the wedged window
  (`FLAGS_tpu_hang_capture_s`). While armed it also heartbeats a
  `heartbeat` event so the supervisor can tell alive-but-wedged from
  dead. With the flag unset nothing starts: the step path, HLO and
  telemetry stream are byte-identical to a watchdog-less build
  (regression-tested).

- **Desync analyzer** (`analyze_hang` / `load_hang_bundle`, surfaced
  as `tools/perf_analysis.py --hang-report`): aligns the per-rank
  in-flight tables of a postmortem bundle by collective key and names
  the rank that never arrived — state `inflight` (began but never
  contributed), or no record at all (stalled before reaching it) — or
  the mismatched membership, as a structured verdict the launch
  supervisor attaches to the `elastic_transition` event.
"""
from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
import traceback
from collections import deque
from typing import Dict, List, Optional

__all__ = [
    "InflightTrace", "InflightToken", "HangWatchdog",
    "trace", "watchdog", "install", "maybe_install", "uninstall",
    "note_progress", "note_step_begin", "thread_stacks",
    "load_hang_bundle", "analyze_hang", "hang_report",
]


def _schedule_key(op, dtype=None, shape=None, world=None, ranks=None,
                  region=None):
    """The shared static/runtime collective identity (lazy import: the
    analyzer must stay importable on a process that never builds
    programs)."""
    from ..analysis.collectives import runtime_schedule_key

    return runtime_schedule_key(op, dtype=dtype, shape=shape,
                                world=world, ranks=ranks,
                                region=region or "")


class InflightToken:
    """Handle for one in-flight collective record; the issuing code
    marks lifecycle transitions through it. All methods are best-effort
    and never raise into the collective path."""

    __slots__ = ("_trace", "_entry")

    def __init__(self, trace, entry):
        self._trace = trace
        self._entry = entry

    def arrived(self) -> None:
        """This rank CONTRIBUTED its part (the put_part landed / the
        barrier RPC was sent); it is now waiting on its peers. The
        desync analyzer uses exactly this edge: a wedged rank still in
        state "inflight" never arrived — it is the guilty one."""
        self._trace._set_state(self._entry, "arrived")

    def done(self, ok: bool = True) -> None:
        self._trace._finish(self._entry, ok)


class InflightTrace:
    """Bounded per-rank ring of collective lifecycle records.

    Open entries (enqueued, not yet complete) live in an
    insertion-ordered dict; completed/failed entries retire into a
    bounded deque. `snapshot()` is JSON-encodable and is embedded in
    every flight-recorder dump."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from ..utils.flags import get_flag

            steps = int(
                get_flag("FLAGS_tpu_flight_recorder_steps", 64) or 64)
            capacity = max(32, 4 * steps)
        self.capacity = max(1, int(capacity))
        self._recent = deque(maxlen=self.capacity)
        self._open: Dict[int, dict] = {}
        self._seq = 0
        self._last_complete = time.monotonic()
        self._lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------
    def begin(self, op, key, tier="host", world=None, rank=None,
              dtype=None, shape=None, nbytes=None,
              ranks=None, region=None) -> InflightToken:
        """Record one collective enqueue; returns the token its caller
        marks `arrived()` / closes through. `key` is the cross-rank
        collective id ("barrier#12" — lockstep ranks agree on it).
        `region` tags the schedule key's region slot — a live mesh
        resize passes its elastic generation ("gen1") so pre- and
        post-seam collectives never alias in the desync analyzer."""
        entry = {
            "seq": 0,  # patched under the lock below
            "op": str(op),
            "key": str(key) if key is not None else None,
            "tier": str(tier),
            "world": None if world is None else int(world),
            "rank": None if rank is None else int(rank),
            "dtype": None if dtype is None else str(dtype),
            "shape": None if shape is None else [int(d) for d in shape],
            "bytes": None if nbytes is None else int(nbytes),
            # stored as the raw tuple; snapshot()/inflight() normalize
            # to the JSON list form on the rare dump path — the hot
            # per-collective path must not pay a serialization round
            # trip
            "schedule_key": _schedule_key(op, dtype=dtype, shape=shape,
                                          world=world, ranks=ranks,
                                          region=region),
            "state": "inflight",
            "ts_begin": time.time(),
        }
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._open[self._seq] = entry
        return InflightToken(self, entry)

    def _set_state(self, entry, state) -> None:
        with self._lock:
            if entry["state"] == "inflight":
                entry["state"] = state
                entry["ts_" + state] = time.time()

    def _finish(self, entry, ok) -> None:
        with self._lock:
            entry["state"] = "done" if ok else "failed"
            entry["ts_end"] = time.time()
            self._open.pop(entry["seq"], None)
            self._recent.append(entry)
            if ok:
                self._last_complete = time.monotonic()

    # -- views -------------------------------------------------------------
    @staticmethod
    def _jsonable(entry) -> dict:
        e = dict(entry)
        k = e.get("schedule_key")
        if isinstance(k, tuple):
            e["schedule_key"] = json.loads(json.dumps(k))
        return e

    def inflight(self) -> List[dict]:
        with self._lock:
            return [self._jsonable(e) for e in self._open.values()]

    def snapshot(self) -> dict:
        with self._lock:
            return {"inflight": [self._jsonable(e)
                                 for e in self._open.values()],
                    "recent": [self._jsonable(e)
                               for e in self._recent]}

    def oldest_inflight_age_s(self, now=None) -> Optional[float]:
        """Wall-clock age of the oldest open entry, None when nothing
        is in flight."""
        now = time.time() if now is None else now
        with self._lock:
            if not self._open:
                return None
            return max(0.0, now - min(e["ts_begin"]
                                      for e in self._open.values()))

    @property
    def last_complete_monotonic(self) -> float:
        with self._lock:
            return self._last_complete


# -- all-thread stack forensics ------------------------------------------

def thread_stacks(limit_frames: int = 40) -> Dict[str, str]:
    """{thread name: formatted python stack} for every live thread via
    sys._current_frames — the "where is everyone stuck" half of the
    hang dump. Never raises."""
    try:
        frames = sys._current_frames()
    except Exception:  # noqa: BLE001 - forensics are best-effort
        return {}
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in frames.items():
        label = "%s (tid=%s)" % (names.get(ident, "?"), ident)
        try:
            stack = "".join(traceback.format_stack(frame, limit_frames))
        except Exception:  # noqa: BLE001
            stack = "<unformattable>"
        out[label] = stack
    return out


# -- the watchdog thread --------------------------------------------------

class HangWatchdog:
    """Detects an alive-but-wedged rank: a collective in flight past
    `timeout_s` with neither a step epilogue nor a collective
    completion advancing meanwhile. On fire (once per hang): all-thread
    stacks + the in-flight table dump through the flight recorder's
    atomic path, a `hang` event lands in the telemetry registry, and
    (optionally) a capture.py xplane trace of the wedged window starts.
    While armed, a periodic `heartbeat` event proves liveness to the
    launch supervisor."""

    def __init__(self, timeout_s, trace=None, tick_s=None,
                 capture_s=None, heartbeat_s=None):
        self.timeout_s = float(timeout_s)
        self._trace = trace
        self.tick_s = float(tick_s) if tick_s is not None else \
            min(1.0, max(0.05, self.timeout_s / 4.0))
        if capture_s is None:
            from ..utils.flags import get_flag

            capture_s = float(
                get_flag("FLAGS_tpu_hang_capture_s", 0.0) or 0.0)
        self.capture_s = float(capture_s)
        # heartbeat cadence: fast enough that a supervisor watching at
        # the same timeout always sees one between ticks
        self.heartbeat_s = float(heartbeat_s) if heartbeat_s is not None \
            else min(30.0, max(0.25, self.timeout_s / 2.0))
        self._t0 = time.monotonic()
        self._last_step = time.monotonic()
        self._step_begin_ts: Optional[float] = None
        self._last_beat = 0.0
        self._fired = False
        self._fire_count = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- progress signals --------------------------------------------------
    def note_progress(self, kind: str = "step") -> None:
        self._last_step = time.monotonic()
        self._step_begin_ts = None
        self._fired = False  # progress resumed: re-arm for the next hang

    def note_step_begin(self) -> None:
        self._step_begin_ts = time.time()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "HangWatchdog":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="paddle_tpu-hang-watchdog")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    @property
    def fired(self) -> bool:
        return self._fired

    def trace(self) -> InflightTrace:
        return self._trace if self._trace is not None else trace()

    # -- the loop ----------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.wait(self.tick_s):
            try:
                self._tick()
            except Exception:  # noqa: BLE001 - the watchdog must never
                pass           # take down the process it watches

    def _tick(self, now=None) -> Optional[dict]:
        now = time.monotonic() if now is None else now
        self._maybe_heartbeat(now)
        tr = self.trace()
        # a completion or a step epilogue within the window means the
        # process is making progress (some OTHER collective advanced);
        # only fire when both signals are stale — the issue's contract.
        # Observed progress also RE-ARMS a fired watchdog: a transient
        # first hang (the store recovered, the collective completed)
        # must not leave it blind to a later real one mid-step
        quiet = now - max(tr.last_complete_monotonic, self._last_step)
        if quiet < self.timeout_s:
            self._fired = False
            return None
        if self._fired:
            return None
        age = tr.oldest_inflight_age_s()
        if age is None or age < self.timeout_s:
            return None
        return self._fire(age)

    def _maybe_heartbeat(self, now) -> None:
        if now - self._last_beat < self.heartbeat_s:
            return
        self._last_beat = now
        try:
            from .registry import registry

            tr = self.trace()
            age = tr.oldest_inflight_age_s()
            registry().event(
                "heartbeat",
                up_s=round(now - self._t0, 3),
                inflight_n=len(tr.inflight()),
                oldest_inflight_s=round(age, 3) if age else 0.0)
        except Exception:  # noqa: BLE001 - liveness only
            pass

    def _fire(self, age_s) -> dict:
        """One hang verdict from THIS rank's point of view: dump
        forensics, publish the event, optionally start a capture."""
        self._fired = True
        self._fire_count += 1
        tr = self.trace()
        entries = tr.inflight()
        oldest = min(entries, key=lambda e: e["ts_begin"]) if entries \
            else {}
        stacks = thread_stacks()
        hang_event = {
            "kind": "event", "event": "hang",
            "stalled_s": round(float(age_s), 3),
            "inflight_n": len(entries),
            "op": oldest.get("op") or "",
            "key": oldest.get("key") or "",
            "timeout_s": self.timeout_s,
            "in_step": self._step_begin_ts is not None,
        }
        try:
            from .registry import registry

            registry().event("hang", **{
                k: v for k, v in hang_event.items()
                if k not in ("kind", "event")})
        except Exception:  # noqa: BLE001 - forensics must still dump
            pass
        try:
            from . import flight

            # once=False: a transient first hang (the store recovered)
            # must not make a LATER real hang analyze a stale dump —
            # each fire rewrites the forensics atomically
            flight.recorder().dump(
                "hang", fatal_event=hang_event, once=False,
                extra={"stacks": stacks,
                       "inflight": tr.snapshot(),
                       "hang": hang_event})
        except Exception:  # noqa: BLE001
            pass
        if self.capture_s > 0:
            try:
                from .capture import controller

                controller().capture_for(self.capture_s)
            except Exception:  # noqa: BLE001 - capture is best-effort
                pass
        return hang_event


# -- process-global singletons -------------------------------------------

_lock = threading.Lock()
_trace: Optional[InflightTrace] = None
_watchdog: Optional[HangWatchdog] = None


def trace() -> InflightTrace:
    """THE process in-flight trace (always on; a ring append per
    collective)."""
    global _trace
    if _trace is None:
        with _lock:
            if _trace is None:
                _trace = InflightTrace()
    return _trace


def watchdog() -> Optional[HangWatchdog]:
    """The armed watchdog, or None when FLAGS_tpu_hang_timeout_s is
    unset (the zero-overhead default)."""
    return _watchdog


def install(timeout_s: Optional[float] = None) -> Optional[HangWatchdog]:
    """Arm (and start) the watchdog thread. `timeout_s` defaults to
    FLAGS_tpu_hang_timeout_s; <= 0 leaves the watchdog off and returns
    None. Idempotent: a second install returns the running instance."""
    global _watchdog
    if timeout_s is None:
        from ..utils.flags import get_flag

        try:
            timeout_s = float(
                get_flag("FLAGS_tpu_hang_timeout_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            timeout_s = 0.0
    if timeout_s <= 0:
        return None
    with _lock:
        if _watchdog is None:
            _watchdog = HangWatchdog(timeout_s).start()
        return _watchdog


def maybe_install() -> Optional[HangWatchdog]:
    """Flag-gated arming hook for the executor epilogue and group
    construction: a no-op dict read when the flag is unset."""
    if _watchdog is not None:
        return _watchdog
    return install()


def uninstall() -> None:
    """Stop and drop the watchdog (tests / teardown)."""
    global _watchdog
    with _lock:
        w = _watchdog
        _watchdog = None
    if w is not None:
        w.stop()


def note_progress(kind: str = "step") -> None:
    w = _watchdog
    if w is not None:
        w.note_progress(kind)


def note_step_begin() -> None:
    w = _watchdog
    if w is not None:
        w.note_step_begin()


def _reset_for_tests() -> None:
    global _trace, _watchdog
    uninstall()
    with _lock:
        _trace = None


# -- offline desync analysis ---------------------------------------------
#
# Input: the per-rank flight dumps of a postmortem bundle (a telemetry
# dir or <log_dir>/postmortem/attempt<K>). Pure-JSON — importable and
# runnable without jax, so the launch supervisor can attach the verdict
# before it restarts the cohort.

_DUMP_RE = re.compile(r"^flightrec\.rank(\d+)\.json$")


def load_hang_bundle(directory: str) -> Dict[int, dict]:
    """{rank: flight-dump doc} from every flightrec.rank<R>.json in
    `directory`. Unreadable dumps are skipped (a torn dump must not
    poison the verdict for the ranks that did dump)."""
    out: Dict[int, dict] = {}
    if not os.path.isdir(directory):
        return out
    for fname in sorted(os.listdir(directory)):
        m = _DUMP_RE.match(fname)
        if not m:
            continue
        try:
            with open(os.path.join(directory, fname)) as f:
                out[int(m.group(1))] = json.load(f)
        except (OSError, ValueError):
            continue
    return out


def _rank_entries(doc) -> List[dict]:
    inf = doc.get("inflight") or {}
    return list(inf.get("inflight") or []) + list(inf.get("recent")
                                                 or [])


def analyze_hang(docs_by_rank: Dict[int, dict]) -> dict:
    """Cross-rank desync verdict over per-rank in-flight tables.

    Aligns records by collective `key` (lockstep ranks agree on it —
    the same per-group tag#seq counter everywhere) and picks the hung
    collective: the open key blocking the most ranks (ties: the
    earliest seq). Per rank, the state of that key decides the blame:

    - "arrived"  — contributed, waiting on peers: a VICTIM;
    - "inflight" — began but never contributed: STALLED INSIDE the
      collective (the guilty rank);
    - no record  — never even reached the collective: stalled earlier
      (also guilty; its frontier shows where it stopped);
    - differing schedule_key across ranks — membership/schedule
      MISMATCH (the runtime twin of tpu-lint's divergence finding).

    Returns a structured verdict; "verdict" is one of "no-hang",
    "stall", "desync" (a rank never reached the collective),
    "membership-mismatch", or "indeterminate" (every rank arrived —
    the store/wire itself wedged)."""
    verdict = {
        "verdict": "no-hang", "ranks": sorted(docs_by_rank),
        "collective": None, "op": None, "schedule_key": None,
        "waiting_ranks": [], "stalled_ranks": [], "missing_ranks": [],
        "guilty_ranks": [], "per_rank": {},
    }
    if not docs_by_rank:
        return verdict
    # per rank: key -> entry (the newest record of that key wins: a
    # retried collective re-records)
    by_rank_keys: Dict[int, Dict[str, dict]] = {}
    open_keys: Dict[str, List[int]] = {}
    for rank, doc in docs_by_rank.items():
        keyed: Dict[str, dict] = {}
        for e in _rank_entries(doc):
            if not e.get("key"):
                continue
            # highest per-rank seq wins: RPC-tier keys are static per
            # endpoint ("send_barrier@host:port"), so an older retired
            # record must not mask the currently-open one
            cur = keyed.get(e["key"])
            if cur is None or e.get("seq", 0) >= cur.get("seq", 0):
                keyed[e["key"]] = e
        by_rank_keys[rank] = keyed
        for k, e in keyed.items():
            if e.get("state") in ("inflight", "arrived"):
                open_keys.setdefault(k, []).append(rank)

    def _key_order(k):
        # "barrier#12" -> (12, "barrier"): earliest cross-rank seq first
        tag, _, n = k.partition("#")
        try:
            return (int(n), tag)
        except ValueError:
            return (1 << 30, k)

    if not open_keys:
        return verdict
    hung = sorted(open_keys,
                  key=lambda k: (-len(open_keys[k]), _key_order(k)))[0]
    verdict["collective"] = hung
    waiting, stalled, missing = [], [], []
    skeys = {}
    for rank in sorted(docs_by_rank):
        e = by_rank_keys.get(rank, {}).get(hung)
        if e is None:
            missing.append(rank)
            # the laggard's frontier: its newest record shows how far
            # it got before it stopped
            frontier = max(
                _rank_entries(docs_by_rank[rank]),
                key=lambda r: r.get("seq", 0), default=None)
            verdict["per_rank"][rank] = {
                "state": "missing",
                "frontier_key": frontier.get("key") if frontier
                else None}
            continue
        verdict["op"] = verdict["op"] or e.get("op")
        skeys[rank] = json.dumps(e.get("schedule_key"), sort_keys=True)
        state = e.get("state")
        info = {"state": state, "frontier_key": hung}
        if e.get("ts_begin"):
            info["inflight_s"] = round(
                (docs_by_rank[rank].get("ts") or time.time())
                - e["ts_begin"], 3)
        verdict["per_rank"][rank] = info
        if state == "arrived":
            waiting.append(rank)
        elif state == "inflight":
            stalled.append(rank)
        else:  # done/failed: this rank already retired the collective
            info["state"] = state
    verdict["schedule_key"] = (
        json.loads(sorted(skeys.values())[0]) if skeys else None)
    verdict["waiting_ranks"] = waiting
    verdict["stalled_ranks"] = stalled
    verdict["missing_ranks"] = missing
    if skeys and len(set(skeys.values())) > 1:
        verdict["verdict"] = "membership-mismatch"
        verdict["mismatched_keys"] = {
            str(r): json.loads(s) for r, s in sorted(skeys.items())}
        verdict["guilty_ranks"] = sorted(
            set(stalled) | set(missing)) or sorted(docs_by_rank)
    elif stalled:
        verdict["verdict"] = "stall"
        verdict["guilty_ranks"] = sorted(set(stalled) | set(missing))
    elif missing:
        verdict["verdict"] = "desync"
        verdict["guilty_ranks"] = sorted(missing)
    elif waiting:
        verdict["verdict"] = "indeterminate"
    # attach the guilty ranks' main-thread stack tails when the dumps
    # carry them — "where exactly" without opening N files
    for rank in verdict["guilty_ranks"]:
        stacks = (docs_by_rank.get(rank) or {}).get("stacks") or {}
        main = next((v for k, v in stacks.items()
                     if k.startswith("MainThread")), None)
        if main:
            verdict["per_rank"].setdefault(rank, {})["stack_tail"] = \
                main[-1500:]
    return verdict


def hang_report(directory: str) -> dict:
    """One-call offline diagnosis: load the bundle, analyze, return
    {"verdict": ..., "lines": [human lines], "n_docs": dump count}
    (perf_analysis --hang-report prints the lines then the JSON)."""
    docs = load_hang_bundle(directory)
    v = analyze_hang(docs)
    lines = ["hang bundle %s: %d rank dump(s)"
             % (directory, len(docs))]
    if v["verdict"] == "no-hang":
        lines.append("no in-flight collective found — not a hang "
                     "postmortem (or the dumps predate the trace)")
        return {"verdict": v, "lines": lines, "n_docs": len(docs)}
    lines.append("hung collective: %s (%s), schedule key %s"
                 % (v["collective"], v["op"], v["schedule_key"]))
    if v["verdict"] == "membership-mismatch":
        lines.append("MEMBERSHIP MISMATCH: ranks disagree on the "
                     "collective's identity: %s"
                     % v.get("mismatched_keys"))
    for r in v["waiting_ranks"]:
        lines.append("  rank %d: arrived, waiting on peers (victim)"
                     % r)
    for r in v["stalled_ranks"]:
        lines.append("  rank %d: began but NEVER CONTRIBUTED — "
                     "stalled inside the collective (guilty)" % r)
    for r in v["missing_ranks"]:
        fk = (v["per_rank"].get(r) or {}).get("frontier_key")
        lines.append("  rank %d: never reached the collective "
                     "(last seen at %s) — guilty" % (r, fk))
    lines.append("verdict: %s; guilty rank(s): %s"
                 % (v["verdict"], v["guilty_ranks"] or "none"))
    return {"verdict": v, "lines": lines, "n_docs": len(docs)}
