"""Device time of one region of the step (forward, recompute, backward,
update: `paddle_tpu.observability.attribution.region_of`), in
milliseconds per step and device, from the program's fold of the traced
run's profile (`sidecar.fold` over `harness.TRACE_DIR`: self times of
the `XLA Ops` thread inside the traced executions of the step's
module). Returns nothing, and says so, where the profile has no
sidecar, the program's fold knows no regions, or under 80 % of the
device's self time carries a scope path: a fold that lost its names
must not report a small number."""
from benchmark import harness, sidecar

#: the least share of device self time that has to carry a scope path
MIN_COVERAGE = 0.8


def read(ctx, region, trace_dir=None):
    if not ctx["trace"]:
        return None
    t = sidecar.fold(trace_dir or harness.TRACE_DIR)
    if t is None:
        return None
    covered = 1.0 - t["unattributed_us"] / t["total_us"]
    if covered < MIN_COVERAGE:
        sidecar.log("bench: only %.1f %% of the device's self time carries "
                    "a scope path (under %.0f %%): region %r is not read"
                    % (100.0 * covered, 100.0 * MIN_COVERAGE, region))
        return None
    return t["by_region"][region] / t["steps"] / 1e3
