"""A fluid op type's share of its roofline over the traced steps: the
least time the chip could take for what the op's calls need in a step
(the larger of operations over peak FLOP/s and bytes over peak bytes/s,
from `benchmark/kernels/<kernel>.py`) over the device self time under
that op type (`readers/op_type_share.py`: every region, whatever
implements the op). Returns nothing where that reader finds nothing."""
import importlib

from benchmark.readers.op_type_share import op_type_us


def read(ctx, op_type, kernel, trace_dir=None):
    t, us = op_type_us(ctx, op_type, trace_dir)
    if not us:
        return None
    need = importlib.import_module("benchmark.kernels." + kernel).needs(
        ctx["config"], ctx["traffic"])
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * t["steps"] / (us / 1e6)
