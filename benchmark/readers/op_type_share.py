"""Device self time under one fluid op type (every region: forward,
recompute and backward), over the device's self time in the traced
executions of the step, in percent: the program's fold of the profile
(`sidecar.fold`'s `by_op_type`), so the same work is read whatever
implements it, a kernel or plain operations. Returns nothing where the
profile has no sidecar, the program's fold knows no regions, under 80 %
of the device's self time carries a scope path, or no operation ran
under that op type."""
from benchmark import harness, sidecar
from benchmark.readers.device_region import MIN_COVERAGE


def op_type_us(ctx, op_type, trace_dir=None):
    """(the fold, microseconds of one device under `op_type` over the
    fold's traced steps), or (None, 0.0)."""
    if not ctx["trace"]:
        return None, 0.0
    t = sidecar.fold(trace_dir or harness.TRACE_DIR)
    if t is None or 1.0 - t["unattributed_us"] / t["total_us"] < MIN_COVERAGE:
        return None, 0.0
    return t, float(t["by_op_type"].get(op_type, 0.0))


def read(ctx, op_type, trace_dir=None):
    t, us = op_type_us(ctx, op_type, trace_dir)
    if not us:
        return None
    return 100.0 * us / t["total_us"]
