"""The benchmark's core: finds a cell's files by name, builds the job
through the cell's family module, warms it up, measures a window,
reduces the trace, runs the plain reference and decides `correct`.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric is a file of its own (see README.md); this module knows
none of them by name."""
from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: one rule: the compile cache of every run lives in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def place_compile_cache():
    """`<checkout>/.jax_cache`, whatever the machine's environment names
    (PR 21's machine named a 192 MiB LRU outside the checkout that
    evicted entries between calls). Before jax is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


#: the last traced run's profile stays here until the next one replaces
#: it (`tools/inspect_trace.py` reads it); `chiprun_out/` is ignored
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "bench_trace")
#: the profiler is on for at least this many steps and this long
TRACE_STEPS = 4
TRACE_SECONDS = 1.0
WARM_STEPS = 2


# -- files -------------------------------------------------------------------

def load_json(*parts, base=BENCH_DIR):
    with open(os.path.join(base, *parts)) as f:
        return json.load(f)


def load_cell(name, base=BENCH_DIR):
    """A cell with its configuration and traffic mix, each from the file
    its name points at."""
    cell = load_json("workloads", name + ".json", base=base)
    config = load_json("configs", cell["config"] + ".json", base=base)
    traffic = load_json("traffic", cell["traffic"] + ".json", base=base)
    return cell, config, traffic


def load_family(config):
    return importlib.import_module("benchmark.families." + config["family"])


def cell_metrics(benchmark, cell_name, trace):
    """The entries of BENCHMARK.json this run reports: the cell's
    end-to-end metrics without a trace, its per-layer metrics with one."""
    entries = benchmark["per_layer" if trace else "end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def device_peaks(kind, base=BENCH_DIR):
    table = load_json("peaks.json", base=base)["peaks"]
    if kind not in table:
        raise KeyError("no published peaks for device kind %r (known: %s): "
                       "add a sourced row to benchmark/peaks.json"
                       % (kind, sorted(table)))
    return table[kind]


# -- weights -----------------------------------------------------------------

def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


_WEIGHT_FNS = {}


def make_weights(spec, seed):
    """All of a model's float32 weights in ONE jitted call from the seed.
    `spec` is [(name, shape, kind, scale)]: kind `normal` is a normal
    truncated at two sigma of standard deviation `scale`, `uniform` is
    uniform in +-scale, `ones` and `zeros` are constants."""
    import jax
    import jax.numpy as jnp

    spec = tuple((n, tuple(s), k, float(sc)) for n, s, k, sc in spec)
    if spec not in _WEIGHT_FNS:
        def make(key):
            out = {}
            for i, (name, shape, kind, scale) in enumerate(spec):
                k = jax.random.fold_in(key, i)
                if kind == "normal":
                    out[name] = scale * jax.random.truncated_normal(
                        k, -2.0, 2.0, shape, jnp.float32)
                elif kind == "uniform":
                    out[name] = jax.random.uniform(
                        k, shape, jnp.float32, -scale, scale)
                elif kind == "ones":
                    out[name] = jnp.ones(shape, jnp.float32)
                elif kind == "zeros":
                    out[name] = jnp.zeros(shape, jnp.float32)
                else:
                    raise ValueError("unknown weight kind %r" % (kind,))
            return out

        _WEIGHT_FNS[spec] = jax.jit(make)
    return _WEIGHT_FNS[spec](seed_key(seed))


# -- the comparison that decides `correct` -----------------------------------

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by round-off alone under Adam: left out of the change
DEAD_GRADIENT = 1e-3


def _gaps(got, want, floor, leaves):
    """Worst and median over `leaves` of |got - want| / max(want, floor),
    and the leaf that read worst."""
    gaps = {}
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], floor)
        gaps[k] = gap if math.isfinite(gap) else float("inf")
    where = max(gaps, key=gaps.get)
    return gaps[where], statistics.median(gaps.values()), where


def grad_errors(got, want):
    """{leaf: norm of (program's first gradient - reference's) over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger}. Unlike a gap of norms this sees rounding: errors that are
    random from element to element cancel in a norm and add up here, so
    operands in a lower precision read several times what bfloat16's do."""
    from benchmark.reference import common

    leaves = sorted(want["grad_norms"])
    floor = statistics.median(want["grad_norms"][k] for k in leaves)
    diff = common.diff_norms({k: got["grads"][k] for k in leaves},
                             {k: want["grads"][k] for k in leaves})
    out = {}
    for k in leaves:
        err = float(diff[k]) / max(want["grad_norms"][k], floor)
        out[k] = err if math.isfinite(err) else float("inf")
    return out


def compare(got, want):
    """The numbers compared, program (`got`) against reference (`want`):
    the first step's loss (before any update: the forward pass alone),
    the worst of all the checked steps' losses, the first gradient's norm
    and the parameters' change after the checked steps, each by the
    worst leaf and by the median leaf, and the first gradient's error
    (`grad_errors`) by both and by the leaf that agrees best (where the
    whole gradient is chaotic in rounding, the leaf nearest the loss
    still reads the forward pass's error). A gap is between the two
    NORMS of a leaf,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger. A cell's file names, with a limit each, the
    numbers it is held to. Returns ({name: value}, {name: leaf that
    read worst})."""
    n = min(len(got["losses"]), len(want["losses"]))
    loss_gaps = [abs(a - b) / abs(b) if math.isfinite(a) else float("inf")
                 for a, b in zip(got["losses"][:n], want["losses"][:n])]
    leaves = sorted(want["grad_norms"])
    missing = [k for k in leaves if k not in got["grad_norms"]
               or k not in got["change_norms"]]
    if missing:
        raise KeyError("the program reports no norm for %s" % missing)
    g_med = statistics.median(want["grad_norms"][k] for k in leaves)
    grad_gap, grad_mid, grad_leaf = _gaps(
        got["grad_norms"], want["grad_norms"], g_med, leaves)
    moved = [k for k in leaves
             if want["grad_norms"][k] >= DEAD_GRADIENT * g_med]
    c_med = statistics.median(want["change_norms"][k] for k in moved)
    change_gap, change_mid, change_leaf = _gaps(
        got["change_norms"], want["change_norms"], c_med, moved)
    errs = grad_errors(got, want)
    err_leaf = max(errs, key=errs.get)
    quartiles = statistics.quantiles(errs.values(), n=4)
    return ({"loss1_gap": loss_gaps[0], "loss_gap": max(loss_gaps),
             "grad_norm_gap": grad_gap, "grad_norm_gap_median": grad_mid,
             "grad_err": errs[err_leaf],
             "grad_err_median": statistics.median(errs.values()),
             "grad_err_min": min(errs.values()),
             "change_norm_gap": change_gap,
             "change_norm_gap_median": change_mid},
            {"grad_norm_gap": grad_leaf, "grad_err": err_leaf,
             "grad_err_min": min(errs, key=errs.get),
             "grad_err_quartiles": quartiles,
             "change_norm_gap": change_leaf, "leaves": len(leaves),
             "leaves_left_out_of_change": sorted(set(leaves) - set(moved))})


def verdict(numbers, limits):
    """{name: [value, limit]} of every compared number that has a limit,
    and whether all of them hold."""
    unknown = sorted(set(limits) - set(numbers))
    if unknown:
        raise KeyError("limits on %s, which `compare` does not read"
                       % unknown)
    compared = {k: [numbers[k], limits[k]] for k in numbers if k in limits}
    ok = bool(compared) and all(
        math.isfinite(v) and v <= lim for v, lim in compared.values())
    return compared, ok


# -- the measured window -----------------------------------------------------

def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_steps(job, feeds, stop, clock=time.perf_counter, annotate=_annotate):
    """Drive steps until `stop(steps_dispatched, seconds_since_start)`.
    Each step's loss is read one step late, as a loop that logs does: the
    host is at most one step ahead and the clock sees completions, not
    enqueues. Once `stop` says so, the step in flight completes and the
    clock stops at ITS completion. Returns the counts and both times."""
    t0 = clock()
    attempted = completed = failed = 0
    pending, t_done = None, t0

    def settle(handle):
        nonlocal completed, failed, t_done
        try:
            with annotate("bench.read_loss"):
                value = job.loss_value(handle)
        except Exception as e:  # noqa: BLE001 - a failed step is counted
            print("bench: step failed at its read: %r" % (e,),
                  file=sys.stderr)
            value = float("nan")
        t_done = clock()
        if math.isfinite(value):
            completed += 1
        else:
            failed += 1

    while not stop(attempted, clock() - t0):
        with annotate("bench.make_feed"):
            feed = feeds[attempted % len(feeds)]
        try:
            with annotate("bench.executor_run"):
                handle = job.step(feed)
        except Exception as e:  # noqa: BLE001
            print("bench: step failed at dispatch: %r" % (e,),
                  file=sys.stderr)
            handle = None
        attempted += 1
        if pending is not None:
            settle(pending)
        if handle is None:
            failed += 1
            if failed > 3:
                break
        pending = handle
    if pending is not None:
        settle(pending)
    return {"attempted": attempted, "completed": completed,
            "failed": failed, "elapsed_s": t_done - t0}


def memory_peak_bytes(job, devices):
    """The fullest chip's peak: the allocator's high-water mark, or what
    the compiler says the step that ran needs (arguments + outputs that
    are not aliased + temporaries), whichever is larger. On this runtime
    `peak_bytes_in_use` counts live arrays and misses a running step's
    temporaries (PR 21), so the compiler's account is the one that sees
    them."""
    seen = 0
    for d in devices:
        stats = d.memory_stats() or {}
        seen = max(seen, int(stats.get("peak_bytes_in_use", 0)))
    step = job.step_memory()
    need = (step["argument"] + step["output"] - step["alias"]
            + step["temp"] + step.get("generated_code", 0))
    return max(seen, need), {"allocator_peak": seen, "step": step}


# -- one run -----------------------------------------------------------------

def checked_steps(job, feeds, n):
    """Drive the job through its first `n` steps by the window's own call
    and read what the comparison needs: every loss, the first gradient
    leaf by leaf (from the optimizer's state after step 1, brought to the
    host so that the window's memory is the step's alone) with its norms,
    and the parameters' change after step `n`."""
    losses, grads = [], None
    for i in range(n):
        losses.append(job.loss_value(job.step(feeds[i % len(feeds)])))
        if i == 0:
            grads, grad_norms = job.first_gradient()
    return {"losses": losses, "grads": grads, "grad_norms": grad_norms,
            "change_norms": job.change_norms()}


def run_cell(name, seed, seconds, trace, devices, t_start, base=BENCH_DIR,
             benchmark=None, wrap_job=None,
             log=lambda *a: print(*a, file=sys.stderr, flush=True)):
    """One run of one cell; returns the result object of the last line.
    `wrap_job` lets a test break the timed path underneath."""
    import jax

    cell, config, traffic = load_cell(name, base=base)
    if benchmark is None:
        benchmark = load_json("BENCHMARK.json", base=ROOT)
    family = load_family(config)
    chips = int(cell["chips"])
    devices = list(devices)[:chips]

    marks = [("start", t_start), ("imports", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    job = family.build(config, traffic, cell, seed, devices)
    mark("build")
    if wrap_job is not None:
        job = wrap_job(job)
    feeds = family.make_ring(config, traffic, seed)
    mark("batches")
    n_check = int(cell["check_steps"])
    from paddle_tpu.fluid import profiler as prof

    got = checked_steps(job, feeds, n_check)
    mark("checked_steps")
    run_steps(job, feeds, lambda k, t: k >= WARM_STEPS)
    mark("warm_up")
    prof.step_phase_summary(reset=True)
    setup_s = time.perf_counter() - t_start
    log("bench: set-up %.2f s: %s" % (setup_s, ", ".join(
        "%s %.2f" % (b[0], b[1] - a[1]) for a, b in zip(marks, marks[1:]))))

    window = run_steps(job, feeds, lambda k, t: t >= seconds)
    phases = prof.step_phase_summary(reset=True)
    compiled_in_window = phases.get("compile_ms", 0.0) > 0.0
    log("bench: window %r phases %r" % (window, phases))

    ctx = {
        "cell": cell, "config": config, "traffic": traffic,
        "setup_s": setup_s, "window": window, "phases": phases,
        "units_per_step": family.units_per_step(config, traffic),
        "flops_per_step": family.flops_per_step(config, traffic),
        "peaks": device_peaks(devices[0].device_kind),
        "chips": chips, "trace": None,
    }
    breakdown = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if trace:
        from benchmark import trace as trace_mod

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        jax.profiler.start_trace(TRACE_DIR)
        try:
            traced = run_steps(
                job, feeds,
                lambda k, t: k >= TRACE_STEPS and t >= TRACE_SECONDS)
        finally:
            jax.profiler.stop_trace()
        reduced = trace_mod.reduce_file(trace_mod.find_xplane(TRACE_DIR),
                                        chips)
        reduced["steps"] = traced["completed"]
        ctx["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]

    peak, memory = memory_peak_bytes(job, devices)
    device["memory_peak_bytes"] = peak
    log("bench: memory %r" % (memory,))

    metrics = {}
    for entry in cell_metrics(benchmark, name, trace):
        spec = load_json("metrics", entry["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    # the reference runs last: the window is closed, the peak is read,
    # and the program's state is freed first
    job.free()
    del job
    gc.collect()
    t_ref = time.perf_counter()
    want = family.reference(config, traffic, cell, seed,
                            [feeds[i % len(feeds)] for i in range(n_check)])
    numbers, where = compare(got, want)
    compared, ok = verdict(numbers, cell["limits"])
    log("bench: reference %.2f s; losses program %r reference %r; %r"
        % (time.perf_counter() - t_ref, got["losses"], want["losses"],
           where))
    log("bench: read and not held: %r"
        % ({k: v for k, v in numbers.items() if k not in compared},))
    for k, (v, lim) in compared.items():
        log("compared %s = %.6g limit %.6g %s"
            % (k, v, lim, "ok" if v <= lim else "FAILS"))
    out = {"correct": ok, "attempted": window["attempted"],
           "failed": window["failed"] + (1 if compiled_in_window else 0),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared      # last, each number beside its limit
    return out
