"""Named breakdown lanes of the executor's host time per step over the
window, summed, from `fluid.profiler.step_phase_summary()`: parts of a
phase (`bind` and `writeback` of `host`) that the summary shows beside
it and never adds to `total_ms`. Unlike `host_phases`, which takes the
phases every program has, this returns nothing where the program
publishes none of the lanes, as a program without those counters does."""


def read(ctx, lanes):
    p = ctx["phases"]
    if not p.get("steps") or not any(n + "_ms" in p for n in lanes):
        return None
    return sum(p.get(n + "_ms", 0.0) for n in lanes)
