"""The executor's host work per step over the window, from
`fluid.profiler.step_phase_summary()`: the named phases summed. `sync`
is time blocked on the device, not host work, and is left out by the
metric's file."""


def read(ctx, phases):
    p = ctx["phases"]
    if not p.get("steps"):
        return None
    return sum(p[name + "_ms"] for name in phases)
