"""A counter of the program that the family's loop fetched with its loss
(a routed layer's load, a count of pairs) and kept, a value a step, in
its `FETCHED`: one statistic of it (`mean`, `max` or `last`) over every
step of the run so far. Returns nothing where the family keeps no such
list or no step filled it."""
import importlib


def read(ctx, gauge, stat="mean"):
    family = importlib.import_module(
        "benchmark.families." + ctx["config"]["family"])
    seen = getattr(family, "FETCHED", {}).get(gauge)
    if not seen:
        return None
    return float({"mean": sum(seen) / len(seen), "max": max(seen),
                  "last": seen[-1]}[stat])
