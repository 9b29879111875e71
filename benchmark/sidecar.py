"""The `*.trace.json.gz` sidecar that `jax.profiler.stop_trace` writes
beside the `.xplane.pb`, as the readers of the program's own names use
it. `trace.py` reads the `.xplane.pb` with `ProfileData`, which hands
out an event's name, start and duration but not the scope path (a stat
of the event's metadata) and keeps only the three `bench.*` host spans;
the sidecar carries every device event's `args.tf_op` and every host
`TraceAnnotation`, on one clock (`ts`, `dur` in microseconds).

The fold from events to regions is the program's
(`paddle_tpu.observability.attribution`): where the program has none,
as a parent commit may not, `fold` returns nothing and does not raise."""
from __future__ import annotations

import functools
import glob
import os
import re
import sys

DEVICE_PROCESS = re.compile(r"^/device:TPU:(\d+)$")
OPS_THREAD = "XLA Ops"
#: scope paths of the ops that draw random bits (dropout's masks)
RANDOM_BITS = ("jit(_bernoulli)", "jit(_uniform)")
TOP_OP_TYPES = 12


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def find(trace_dir):
    """The newest sidecar under a profile directory, or None."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    return found[-1] if found else None


def _key(path_or_dir):
    """(sidecar file, its mtime and size): what a parse is cached by;
    None where there is no sidecar."""
    path = path_or_dir if os.path.isfile(path_or_dir) else find(path_or_dir)
    if path is None:
        return None
    st = os.stat(path)
    return path, st.st_mtime_ns, st.st_size


def events_of(path_or_dir):
    """The chrome-trace events of a sidecar file (or of the newest one
    under a profile directory); [] where there is none. Parsed once a
    file."""
    key = _key(path_or_dir)
    return [] if key is None else _events(*key)


@functools.lru_cache(maxsize=2)
def _events(path, _mtime, _size):
    from paddle_tpu.observability import attribution

    return attribution.load_trace_events(path)


def threads_of(events):
    """({pid: process name}, {(pid, tid): thread name})"""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        name = (ev.get("args") or {}).get("name")
        if ev.get("name") == "process_name":
            procs[ev.get("pid")] = name
        elif ev.get("name") == "thread_name":
            threads[(ev.get("pid"), ev.get("tid"))] = name
    return procs, threads


def fold(path_or_dir):
    """The program's region fold of the profile, logged once a file:
    `attribution.time_attribution`'s dict, or None where the profile
    has no sidecar or the program's fold knows no regions."""
    key = _key(path_or_dir)
    if key is None:
        log("bench: the profile has no *.trace.json.gz: no region is read")
        return None
    return _fold(*key)


@functools.lru_cache(maxsize=2)
def _fold(path, mtime, size):
    from paddle_tpu.observability import attribution

    events = _events(path, mtime, size)
    t = attribution.time_attribution(events)
    if "by_region" not in t or not t.get("steps") or not t["total_us"]:
        log("bench: this program's time_attribution folds no regions "
            "(or the trace holds no execution of a step): no region is "
            "read")
        return None
    steps, total = t["steps"], t["total_us"]
    log("bench: device time by region, %d traced steps, %.3f ms a step "
        "(self times; %.2f %% under a pp[...] marker):"
        % (steps, total / steps / 1e3, 100.0 * t["matched_us"] / total))
    for region, us in t["by_region"].items():
        log("bench:   %-12s %10.3f ms/step %6.2f %%"
            % (region, us / steps / 1e3, 100.0 * us / total))
    log("bench: device time by fluid op type (ms/step): " + ", ".join(
        "%s %.3f" % (k, us / steps / 1e3)
        for k, us in list(t["by_op_type"].items())[:TOP_OP_TYPES]))
    rows = attribution.device_op_rows(events)["rows"]
    bits = sum(us for _name, path_, us in rows
               if any(r in path_ for r in RANDOM_BITS))
    log("bench: device time under %s: %.3f ms/step, %.2f %%"
        % (" / ".join(RANDOM_BITS), bits / t["devices"] / steps / 1e3,
           100.0 * bits / t["devices"] / total))
    return t
