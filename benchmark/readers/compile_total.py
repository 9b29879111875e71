"""Seconds the executor has spent compiling since the process started:
the lifetime total of the `compile` phase counter
(`fluid.profiler.phase_lifetime_s`: tracing and lowering a program,
fingerprinting it for the persistent cache, and the backend compile
that the first dispatch pays where the cache misses), which no window's
reset clears. Read after set-up, the window and the traced steps, none
of which may compile past set-up, so it is set-up's share. Returns
nothing where the program keeps no such total."""


def read(ctx):
    from paddle_tpu.fluid import profiler

    total = getattr(profiler, "phase_lifetime_s", None)
    return None if total is None else total("compile")
