"""Device self time of one fluid op type inside itself, in milliseconds
per step and device: by the region of the step it ran in (forward,
recompute, backward, update: `attribution.region_of`), by the parts its
own code names (`attribution.part_scope`: a `pt[<part>]` scope under
the op's marker, read back by `attribution.part_of`), or by both, from
the program's fold of the traced run's profile (`sidecar.fold`'s
`by_op_type_region` and `by_op_part`; the op type found as
`readers/op_type_share.py` finds it). A fusion's self time goes to the
one scope path the fusion carries, so a part reads what XLA left under
its name. Returns nothing where the profile has no sidecar, the
program's fold lacks the two keys (a parent commit), under 80 % of the
device's self time carries a scope path, the op type ran nothing (in
the region and parts asked for), or parts are asked for and under 90 %
of the op type's time carries one: a fold that lost its names must not
report a small number. The program's own table (op type by region, a
line a part) is logged once a profile."""
from benchmark import sidecar
from benchmark.readers.op_type_share import op_type_us

#: the least share of an op type's self time that has to lie under a
#: part before any of its parts is read
MIN_PARTED = 0.9


#: the fold whose table was logged last (`sidecar.fold` hands out one
#: dict a sidecar file)
_logged = []


def _log_table(t):
    """The fold's own table on standard error, once a profile."""
    if _logged and _logged[0] is t:
        return
    _logged[:] = [t]
    from paddle_tpu.observability import attribution

    for line in attribution.op_part_table(t):
        sidecar.log("bench: " + line)


def _sum(row, region):
    return sum(row.values()) if region is None else row.get(region, 0.0)


def read(ctx, op_type, parts=None, region=None, trace_dir=None):
    t, whole = op_type_us(ctx, op_type, trace_dir)
    if not whole or "by_op_part" not in t or "by_op_type_region" not in t:
        return None
    _log_table(t)
    if parts is None:
        us = _sum(t["by_op_type_region"][op_type], region)
    else:
        by_part = t["by_op_part"][op_type]
        parted = 1.0 - sum(by_part.get("", {}).values()) / whole
        if parted < MIN_PARTED:
            sidecar.log("bench: only %.1f %% of %s's self time lies under a "
                        "pt[...] part (under %.0f %%): parts %r are not read"
                        % (100.0 * parted, op_type, 100.0 * MIN_PARTED,
                           list(parts)))
            return None
        us = sum(_sum(by_part.get(part, {}), region) for part in parts)
    return us / t["steps"] / 1e3 if us else None
