"""What lies under each part of a fluid op type in a traced run: the
fold's own table (`attribution.op_part_table`), then for one op type
after another, by (part, region), the operations that took most device
self time, each with the end of its scope path. A fusion's self time
goes to the one scope path the fusion carries: this is where to see
which fusion put a part's work under another part's name, or under none.

    python3 benchmark/tools/part_rows.py <trace_dir> [op_type ...] [--top N]

`<trace_dir>` is `chiprun_out/bench_trace` after a `--trace 1` run; with
no op type named, every op type whose code names a part."""
import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import sidecar  # noqa: E402


def rows_by_part(events, op_type):
    """{(part or "", region): {(operation, scope path's end): [ms a
    step and device, operations a step]}}"""
    from paddle_tpu.observability import attribution

    got = attribution.device_op_rows(events)
    per = 1e3 * max(got["steps"], 1) * max(got["devices"], 1)
    cells = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0.0, 0.0]))
    for name, path, us in got["rows"]:
        prov = attribution.provenance_of(path)
        if not prov or prov.get("op_type") != op_type:
            continue
        key = (attribution.part_of(path) or "",
               attribution.region_of(path, prov))
        # `%fusion.4366 = ...` -> `fusion`; the path after the marker
        operation = name.split(" = ")[0].lstrip("%").rstrip("0123456789.")
        end = path[path.rfind("pp["):].split("]", 1)[-1]
        row = cells[key][(operation, end[-100:])]
        row[0] += us / per
        row[1] += 1.0 / max(got["steps"], 1) / max(got["devices"], 1)
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("op_types", nargs="*")
    ap.add_argument("--top", type=int, default=6)
    args = ap.parse_args(argv)
    from paddle_tpu.observability import attribution

    events = sidecar.events_of(args.trace_dir)
    t = attribution.time_attribution(events)
    print("\n".join(attribution.op_part_table(t)))
    for op_type in args.op_types or [
            k for k, parts in t["by_op_part"].items() if set(parts) != {""}]:
        print("== %s" % op_type)
        cells = rows_by_part(events, op_type)
        for key in sorted(cells, key=lambda k: -sum(
                r[0] for r in cells[k].values())):
            rows = cells[key]
            print("  %s %s: %.3f ms a step in %d kinds of operation" % (
                "pt[%s]" % key[0] if key[0] else "(no part)", key[1],
                sum(r[0] for r in rows.values()), len(rows)))
            for (operation, end), (ms, count) in sorted(
                    rows.items(), key=lambda kv: -kv[1][0])[:args.top]:
                print("    %9.3f ms x%-5g %-36s ...%s"
                      % (ms, count, operation[:36], end))


if __name__ == "__main__":
    main()
