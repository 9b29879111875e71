"""Profiler (reference: `python/paddle/fluid/profiler.py:39-255` over
`platform/profiler.cc` + CUPTI DeviceTracer).

TPU-native: the device tracer is jax.profiler (XPlane/perfetto, viewable in
TensorBoard or chrome://tracing); the `profiler(state, tracer_option,
profile_path)` context-manager API is preserved. `span` is the one way
the program marks its own time (profile host plane + phase counter +
legacy chrome buffer); RecordEvent is a span over the host-event table.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import defaultdict

from jax.profiler import StepTraceAnnotation as _StepTraceAnnotation
from jax.profiler import TraceAnnotation as _TraceAnnotation

# ONE lock for every counter table below: the counters are mutated from
# the main step loop AND background threads (the device prefetcher's
# producer, host-collective heartbeat/RPC handler threads, hapi's
# deferred-sync path) — the unlocked read-modify-write on the
# defaultdict's [count, total, max] lists lost updates under
# concurrency. Accumulation is a few arithmetic ops; one uncontended
# lock acquisition per event is noise next to a dispatched step.
_lock = threading.Lock()

_host_events = defaultdict(lambda: [0, 0.0, 0.0])  # name -> [count, total_s, max_s]

# chrome://tracing buffer: (name, start_us, dur_us, tid)
_trace_events = []
_trace_enabled = False

# -- step-phase counters (async pipeline observability) ---------------------
# Every Executor.run splits its wall time into these phases:
#   feed     — host-side feed prep + H2D issue (zero-ish when batches
#              arrive pre-transferred from reader/prefetcher.py)
#   dispatch — handing the jitted step to the runtime (async: returns
#              while the device still computes)
#   comm     — host blocked on cross-HOST collective coordination
#              (host_collectives barrier/allreduce/allgather: PS sync
#              barriers, checkpoint-step agreement, fleet metrics).
#              Device-tier ICI collective time is invisible to the host
#              (XLA overlaps it with compute) — for ICI evidence use
#              Executor.collective_report's per-collective byte census.
#   sync     — host blocked on device results (FLAGS_benchmark's
#              per-step block, return_numpy materialization, deferred
#              LazyFetch/hapi log-step syncs)
#   host     — everything else on the host between steps (cache lookup,
#              python overhead, PS bookkeeping)
# In a well-overlapped pipeline feed+sync+host ≈ 0 at steady state and
# dispatch-to-dispatch time ≈ device compute time.
STEP_PHASES = ("feed", "dispatch", "comm", "sync", "host")
#: breakdowns shown beside the phases and never added to `total_ms`:
#: the hybrid-mesh lanes of `comm` (host_collectives._comm_phase on a
#: PADDLE_NUM_PODS / PADDLE_MP_DEGREE launch) and the executor's two
#: named parts of `host` (`exe.bind`: cache lookup + state read from the
#: scope; `exe.writeback`: new state written back to it)
PHASE_BREAKDOWNS = ("comm_ici", "comm_dcn", "comm_mp", "bind", "writeback")
_step_phases = defaultdict(lambda: [0, 0.0, 0.0])  # -> [count, total_s, max_s]
# seconds per phase over the life of the process: what
# `step_phase_summary(reset=True)` clears above stays here, so a
# caller that resets per window can still ask what set-up compiled
_phase_lifetime = defaultdict(float)


def record_step_phase(name, dt, t0=None):
    """Accumulate `dt` seconds into step-phase counter `name` (and its
    lifetime total); with the segment's real start `t0`, also emits a
    chrome-trace event ("phase/<name>") while `profiler()` is on.
    Thread-safe: callers include the prefetcher's producer thread and
    RPC handler threads, concurrent with the main step loop."""
    with _lock:
        ev = _step_phases[name]
        ev[0] += 1
        ev[1] += dt
        ev[2] = max(ev[2], dt)
        _phase_lifetime[name] += dt
        if _trace_enabled and t0 is not None:
            _trace_events.append(("phase/" + name, t0 * 1e6, dt * 1e6,
                                  threading.get_ident() % 100000))


def move_step_phase(src, dst, dt):
    """Re-attribute `dt` seconds already counted under `src` to `dst`
    (the executor moves a first dispatch's measured backend compile
    into `compile`); `src` keeps its count, so `steps` is unchanged."""
    with _lock:
        _step_phases[src][1] -= dt
        _phase_lifetime[src] -= dt
        ev = _step_phases[dst]
        ev[0] += 1
        ev[1] += dt
        ev[2] = max(ev[2], dt)
        _phase_lifetime[dst] += dt


class span:
    """One named interval of the program's own time, in three places
    at once: the jax profile's host plane (a `TraceAnnotation(name,
    **args)`, on the device trace's clock), the step-phase counter of
    that name (`exe.feed` feeds `feed`: the part after the last dot)
    with its lifetime total, and the legacy chrome buffer while
    `profiler()` is on. `into` is a dict that also gets the seconds
    under the counter's name (the executor's per-step account).

    With no trace on, a span costs one TraceMe flag test, two clock
    reads and one locked add. A span whose body raised stays in the
    profile and out of the counters: a run that failed is not a step."""

    __slots__ = ("name", "_into", "_ann", "_t0")

    def __init__(self, name, into=None, **args):
        self.name = name
        self._into = into
        self._ann = _TraceAnnotation(name, **args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._record(dt)
        return False

    def _record(self, dt):
        phase = self.name.rpartition(".")[2]
        record_step_phase(phase, dt, self._t0)
        if self._into is not None:
            self._into[phase] = self._into.get(phase, 0.0) + dt


_step_nums = itertools.count()


def step_span(name):
    """A `StepTraceAnnotation` for one step of the program: the spans
    entered inside it nest under it in the profile's host plane, and
    its `step_num` (counted over the process) is the identifier they
    share. It feeds no counter."""
    return _StepTraceAnnotation(name, step_num=next(_step_nums))


def step_phase_total(name):
    """Accumulated seconds in one phase counter (0.0 when unseen) —
    the executor snapshots `comm` around each step so host time stays
    disjoint from collective time recorded by host_collectives."""
    with _lock:
        return _step_phases[name][1] if name in _step_phases else 0.0


def phase_lifetime_s(name):
    """Seconds counted under one phase since the process started;
    no reset clears it (`benchmark/readers/compile_total.py` reads
    `compile` after set-up and the window)."""
    with _lock:
        return _phase_lifetime.get(name, 0.0)


def reset_step_phases():
    with _lock:
        _step_phases.clear()


def step_phase_summary(reset=False):
    """Per-step timing breakdown: {"steps": N, "feed_ms": avg, ...,
    "total_ms": sum of avgs}. `steps` = number of dispatches; phase
    averages are totals over that denominator, so rarely-firing phases
    (a deferred sync every log_freq steps) amortize correctly."""
    with _lock:
        steps = _step_phases["dispatch"][0] if "dispatch" in _step_phases \
            else 0
        denom = max(steps, 1)
        out = {"steps": steps}
        total = 0.0
        for name in STEP_PHASES:
            avg_ms = _step_phases[name][1] * 1e3 / denom \
                if name in _step_phases else 0.0
            out[name + "_ms"] = round(avg_ms, 3)
            total += avg_ms
        out["total_ms"] = round(total, 3)
        if "compile" in _step_phases:
            # cache-miss compiles ride outside the steady-state total so
            # they never pollute host_ms, but the summary still shows them
            out["compile_ms"] = round(
                _step_phases["compile"][1] * 1e3 / denom, 3)
        for lane in PHASE_BREAKDOWNS:
            if lane in _step_phases:
                out[lane + "_ms"] = round(
                    _step_phases[lane][1] * 1e3 / denom, 3)
        if reset:
            _step_phases.clear()
    return out


def step_phase_line():
    """ONE human-readable summary line (bench.py prints it)."""
    s = step_phase_summary()
    return ("step phases: %d steps, feed %.2fms dispatch %.2fms "
            "comm %.2fms sync %.2fms host %.2fms "
            "(host total %.2fms/step)"
            % (s["steps"], s["feed_ms"], s["dispatch_ms"], s["comm_ms"],
               s["sync_ms"], s["host_ms"], s["total_ms"]))


def event_count(name):
    """Host-event fire count (RecordEvent name) — lets tests assert sync
    cadence (e.g. hapi's deferred-fetch 'hapi/loss_sync')."""
    with _lock:
        return _host_events[name][0] if name in _host_events else 0


_native_broken = False


def _native_trace():
    """The C++ event store (core/native/src/trace_events.cc) when the
    native runtime builds; None otherwise (pure-python buffer is the
    fallback). The .so builds lazily on first use, so the first call is
    probed and any failure permanently disables the native path."""
    global _native_broken
    if _native_broken:
        return None
    try:
        from ..core.native import NativeTrace

        NativeTrace.count()   # forces the lazy build; cheap afterwards
        return NativeTrace
    except Exception:
        _native_broken = True
        return None


class RecordEvent(span):
    """Host-side RAII event (reference: platform/profiler.h:126): a
    `span` that counts into the host-event table (`event_count`,
    `profiler_summary_rows`) in place of a step phase and, while
    `profiler()` is on, lands in the native event store."""

    __slots__ = ("_nid",)

    def __init__(self, name, event_type=None):
        super().__init__(name)
        self._nid = None

    def _record(self, dt):
        with _lock:
            ev = _host_events[self.name]
            ev[0] += 1
            ev[1] += dt
            ev[2] = max(ev[2], dt)
        if _trace_enabled:
            tid = threading.get_ident() % 100000
            nt = _native_trace()
            if nt is not None:
                if self._nid is None:
                    self._nid = nt.name_id(self.name)
                nt.record(self._nid, tid, int(self._t0 * 1e6),
                          int(dt * 1e6))
            else:
                with _lock:
                    _trace_events.append((self.name, self._t0 * 1e6,
                                          dt * 1e6, tid))


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    """Context manager (reference: profiler.py:255). Writes a jax trace to
    profile_path (a directory) viewable in TensorBoard."""
    started = False
    try:
        import jax.profiler

        os.makedirs(profile_path, exist_ok=True)
        jax.profiler.start_trace(profile_path)
        started = True
    except Exception:
        pass
    global _trace_enabled
    _trace_enabled = True
    nt = _native_trace()
    if nt is not None:
        nt.enable(True)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        _trace_enabled = False
        if started:
            import jax.profiler

            jax.profiler.stop_trace()
        export_chrome_tracing(os.path.join(profile_path,
                                           "paddle_tpu_trace.json"))
        if sorted_key:
            print_profiler_summary(wall)


def start_profiler(state="All", tracer_option="Default",
                   profile_path="/tmp/profile"):
    import jax.profiler

    os.makedirs(profile_path, exist_ok=True)
    jax.profiler.start_trace(profile_path)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    import jax.profiler

    jax.profiler.stop_trace()


def reset_profiler():
    with _lock:
        _host_events.clear()
        _step_phases.clear()
        del _trace_events[:]
    nt = _native_trace()
    if nt is not None:
        nt.reset()


def export_chrome_tracing(path):
    """chrome://tracing JSON export (reference: tools/timeline.py:32
    converting profiler.proto records; here the host RecordEvent buffer
    plus per-event complete ("ph":"X") entries)."""
    import json

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    nt = _native_trace()
    if nt is not None and nt.count() > 0:
        # the C++ writer streams the JSON (no python loop per event)
        if nt.export(path) == 0:
            return path
        raise OSError("chrome-trace export failed to open %r" % path)
    with _lock:
        trace_events = list(_trace_events)
    events = [{"name": name, "ph": "X", "pid": 0, "tid": tid,
               "ts": ts, "dur": dur, "cat": "host"}
              for name, ts, dur, tid in trace_events]
    data = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(data, f)
    return path


def profiler_summary_rows():
    """Per-event (name, calls, total_ms, avg_ms, max_ms) rows."""
    with _lock:
        host_events = {k: list(v) for k, v in _host_events.items()}
    rows = []
    for name, (cnt, total, mx) in sorted(host_events.items(),
                                         key=lambda kv: -kv[1][1]):
        rows.append((name, cnt, total * 1e3, total * 1e3 / max(cnt, 1),
                     mx * 1e3))
    return rows


def print_profiler_summary(wall=None):
    print("%-40s %10s %12s %12s %12s" % ("Event", "Calls", "Total(ms)",
                                         "Avg(ms)", "Max(ms)"))
    for name, cnt, total, avg, mx in profiler_summary_rows()[:50]:
        print("%-40s %10d %12.3f %12.3f %12.3f" % (name, cnt, total,
                                                   avg, mx))
    if wall is not None:
        print("wall: %.3f s" % wall)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    """nvprof shim — no-op on TPU; kept for script compatibility."""
    yield


def npu_profiler(*a, **k):
    return cuda_profiler()
