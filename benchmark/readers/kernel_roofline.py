"""A kernel's share of its roofline over the traced steps: the least
time the chip could take for what the kernel's calls need (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from
`benchmark/kernels/<kernel>.py`) over the kernel's summed device time.
Returns nothing where the trace holds no operation of that name."""
import importlib


def kernel_seconds(ctx, match):
    """Summed self time of the traced operations whose name holds every
    string of one of `match`'s entries (an entry is a list of strings; a
    name is an operation's whole text, operands and all, so a string
    that starts with `^` has to start the name)."""
    t = ctx["trace"]
    if not t:
        return 0.0

    def holds(name, part):
        return (name.startswith(part[1:]) if part.startswith("^")
                else part in name)

    return sum(sec for name, sec in t["op_seconds"].items()
               if any(all(holds(name, part) for part in entry)
                      for entry in match))


def read(ctx, kernel, match):
    seconds = kernel_seconds(ctx, match)
    if not seconds:
        return None
    need = importlib.import_module("benchmark.kernels." + kernel).needs(
        ctx["config"], ctx["traffic"])
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["trace"]["steps"] / seconds
