"""The whole step's share of the chips' peak, on the device's clock: the
operations a step needs (the family's count from the shapes, recompute
not counted) times the steps run under the profiler, over the traced
span (first operation's start to last operation's end, idle gaps and
all), over chips x the table's bf16 peak. The rate is taken on the
host's clock over the window; this is not, so a stall of the host
between steps lowers the rate and leaves this standing. Returns nothing
without a trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"] or not t["window_s"]:
        return None
    rate = ctx["flops_per_step"] * t["steps"] / t["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
