"""Device time in collective operations that the step does not hide, in
milliseconds a step: the self time of the all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all operations (their
`-start` and `-done` halves among them: a `-done` lasts as long as the
device waits for the exchange) on each device's `XLA Ops` line of the
traced run, over the traced steps, by the device that reads worst.
While one of these runs the line runs nothing else, so this is time the
step pays for the exchange. Returns nothing where the trace holds no
such operation, as a one-chip run's does not."""
import re

from benchmark import harness, trace

#: an operation's name as the trace gives it: `%all-reduce.3 = ...`
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")


def read(ctx, trace_dir=None):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    raw = trace.read_xplane(trace.find_xplane(trace_dir or harness.TRACE_DIR))
    worst = 0.0
    for events in raw["devices"].values():
        worst = max(worst, sum(
            ns for name, ns in trace.self_times(events).items()
            if COLLECTIVE.match(name)))
    if not worst:
        return None
    return worst / 1e6 / t["steps"]
