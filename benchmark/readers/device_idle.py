"""1 - (union of the device's operation intervals) / (traced span), in
percent, over the few steady steps the profiler saw."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
