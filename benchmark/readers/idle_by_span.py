"""The device's idle gaps over the traced span that lie under the named
host spans (the executor's `exe.*` `TraceAnnotation`s), in percent of
the span: how much of the chip's idle time the executor's own host work
covers. Gaps are those between the merged `XLA Ops` intervals of the
first device, as `trace.reduce` takes them; the host spans are read
from the profile's sidecar, on the same clock. Logs every traced run
the gaps by host span name (`exe.*` and `bench.*`). Returns nothing
where the profile holds no span of those names, as a program without
them leaves it."""
from benchmark import harness, sidecar, trace


def overlap(gaps, spans):
    """Summed length of `gaps` covered by the union of `spans` (both
    [(start, end)])."""
    merged = trace.union(spans)
    total, i = 0.0, 0
    for s, e in sorted(gaps):
        while i < len(merged) and merged[i][1] <= s:
            i += 1
        j = i
        while j < len(merged) and merged[j][0] < e:
            total += min(e, merged[j][1]) - max(s, merged[j][0])
            j += 1
    return total


def gaps_and_spans(events):
    """(idle gaps of the first device [(start, end)], its traced span,
    {host span name: [(start, end)]} of the `exe.*` and `bench.*`
    spans), in the sidecar's microseconds."""
    procs, threads = sidecar.threads_of(events)
    ordinals = {pid: int(m.group(1)) for pid, m in (
        (pid, sidecar.DEVICE_PROCESS.match(str(name)))
        for pid, name in procs.items()) if m}
    if not ordinals:
        return [], 0.0, {}
    first = min(ordinals, key=ordinals.get)
    ops, host = [], {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        pid, name = ev.get("pid"), str(ev.get("name", ""))
        start, dur = float(ev.get("ts", 0.0)), float(ev.get("dur", 0.0) or 0)
        if pid == first:
            if threads.get((pid, ev.get("tid"))) == sidecar.OPS_THREAD:
                ops.append((start, start + dur))
        elif pid not in ordinals and name.startswith(("exe.", "bench.")):
            host.setdefault(name, []).append((start, start + dur))
    if not ops:
        return [], 0.0, host
    merged = trace.union(ops)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return gaps, merged[-1][1] - merged[0][0], host


def read(ctx, spans, trace_dir=None):
    if not ctx["trace"]:
        return None
    gaps, span, host = gaps_and_spans(
        sidecar.events_of(trace_dir or harness.TRACE_DIR))
    if not span:
        return None
    idle = sum(e - s for s, e in gaps)
    sidecar.log("bench: %d idle gaps, %.1f us of a %.1f us span; under "
                "host spans (a gap may lie under several): %s"
                % (len(gaps), idle, span, ", ".join(
                    "%s %.1f us" % (name, overlap(gaps, host[name]))
                    for name in sorted(host)) or "none in the profile"))
    named = [iv for name in spans for iv in host.get(name, ())]
    if not named:
        return None
    return 100.0 * overlap(gaps, named) / span
