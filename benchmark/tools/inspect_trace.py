"""Look at one profiler trace by hand: every plane, its lines, how many
events each holds and the names that took most time. Optionally write
the part the reducer reads (`trace.read_xplane`) as a JSON fixture.

    python3 benchmark/tools/inspect_trace.py <trace_dir> [fixture.json]

A `--trace 1` run of `run.py` leaves its profile under
`chiprun_out/bench_trace` until the next one replaces it.
"""
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace  # noqa: E402


def main(trace_dir, fixture=None):
    from jax.profiler import ProfileData

    path = trace.find_xplane(trace_dir)
    print("file %s (%d bytes)" % (path, os.path.getsize(path)))
    for plane in ProfileData.from_file(path).planes:
        print("plane %r" % plane.name)
        for line in plane.lines:
            total = collections.Counter()
            n = 0
            for e in line.events:
                total[e.name] += e.duration_ns
                n += 1
            print("  line %r: %d events" % (line.name, n))
            for name, ns in total.most_common(12):
                print("    %10.3f ms  %s" % (ns / 1e6, name[:140]))
    if fixture:
        raw = trace.read_xplane(path)
        with open(fixture, "w") as f:
            json.dump({"devices": {str(k): v
                                   for k, v in raw["devices"].items()},
                       "host": raw["host"]}, f)
        print("fixture %s (%d bytes)" % (fixture, os.path.getsize(fixture)))


if __name__ == "__main__":
    main(*sys.argv[1:3])
