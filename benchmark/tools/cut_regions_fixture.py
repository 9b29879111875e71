"""Cut a fixture for the tests of the region and span readers out of a
traced run's profile: ONE execution of the step's module (the second
the profiler saw, a steady one) with the `XLA Ops` events that start
inside it, its `XLA Modules` and `Steps` events, and the host's `exe.*`
and `bench.*` spans that touch it; of every event the name, `ts`, `dur`
and, of an operation, `args.tf_op` with the device's own picoseconds.

    python3 benchmark/tools/cut_regions_fixture.py <trace_dir> <out.json.gz>

`<trace_dir>` is `chiprun_out/bench_trace` after a `--trace 1` run."""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import sidecar  # noqa: E402

KEPT_ARGS = ("tf_op", "device_offset_ps", "device_duration_ps",
             "step_num", "fresh")


def cut(events, which=1):
    procs, threads = sidecar.threads_of(events)
    devices = {pid for pid, name in procs.items()
               if sidecar.DEVICE_PROCESS.match(str(name))}
    first = min(devices)
    by_module = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("pid") == first \
                and threads.get((first, ev.get("tid"))) == "XLA Modules":
            by_module.setdefault(ev["name"], []).append(ev)
    runs = sorted(max(by_module.values(),
                      key=lambda r: sum(e["dur"] for e in r)),
                  key=lambda e: e["ts"])
    run = runs[min(which, len(runs) - 1)]
    start, end = run["ts"], run["ts"] + run["dur"]
    out = [ev for ev in events if ev.get("ph") == "M"
           and ev.get("name") in ("process_name", "thread_name")
           and (ev.get("pid") == first or ev.get("pid") not in devices)]
    kept_threads = set()
    for ev in events:
        if ev.get("ph") != "X":
            continue
        pid, name = ev.get("pid"), str(ev.get("name", ""))
        thread = threads.get((pid, ev.get("tid")))
        if pid == first:
            inside = start <= ev["ts"] < end
            if thread in ("Steps", "XLA Modules"):
                # the chosen execution alone: the fold divides by them
                inside = inside and ev["ts"] + ev["dur"] <= end + 1e-3 \
                    and (thread == "Steps" or ev is run)
            elif thread != sidecar.OPS_THREAD:
                inside = False
        elif pid in devices:
            inside = False
        else:
            inside = name.startswith(("exe.", "bench.")) \
                and ev["ts"] < end and ev["ts"] + ev["dur"] > start
        if not inside:
            continue
        slim = {k: ev[k] for k in ("ph", "pid", "tid", "ts", "dur", "name")}
        args = {k: v for k, v in (ev.get("args") or {}).items()
                if k in KEPT_ARGS}
        if args:
            slim["args"] = args
        out.append(slim)
        kept_threads.add((pid, ev.get("tid")))
    return [ev for ev in out if ev["ph"] == "X"
            or ev["name"] == "process_name"
            or (ev["pid"], ev.get("tid")) in kept_threads]


def main(trace_dir, out_path):
    events = cut(sidecar.events_of(trace_dir))
    with gzip.open(out_path, "wt") as f:
        json.dump({"traceEvents": events}, f, separators=(",", ":"))
    print("%s: %d events, %d bytes" % (out_path, len(events),
                                       os.path.getsize(out_path)))


if __name__ == "__main__":
    main(*sys.argv[1:3])
