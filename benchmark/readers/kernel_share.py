"""A kernel's device time over the device's busy time in the traced
span, in percent. Returns nothing where the trace holds no operation of
that name."""
from benchmark.readers.kernel_roofline import kernel_seconds


def read(ctx, match):
    seconds = kernel_seconds(ctx, match)
    if not seconds:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]
