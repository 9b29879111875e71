"""CPU tests of the benchmark's harness, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Tier-1 does not collect them. They drive the harness at the tiny presets
under `presets/` (same families, same references, same core); a CPU run
gives results and counts, never a time."""
import gzip
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from benchmark import harness, trace  # noqa: E402

PRESETS = os.path.join(HERE, "presets")
TINY = ["bert-tiny-s32", "resnet-tiny-b32"]


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_benchmark(cell):
    """BENCHMARK.json with every metric pointed at the tiny cell."""
    bm = benchmark_json()
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    return bm


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no row in the table of peaks (and gets none): the
    tiny runs borrow the v5e's, and report no device number anywhere."""
    real = harness.device_peaks
    monkeypatch.setattr(harness, "device_peaks",
                        lambda kind, base=harness.BENCH_DIR:
                        real("TPU v5 lite", base=base))


def run_tiny(cell, seed=3000000019, trace_run=False, wrap_job=None):
    import jax

    return harness.run_cell(
        cell, seed, 0.5, trace_run, jax.devices(), 0.0, base=PRESETS,
        benchmark=tiny_benchmark(cell), wrap_job=wrap_job)


# -- the files ---------------------------------------------------------------

def test_every_cell_metric_and_config_of_benchmark_json_is_a_file():
    bm = benchmark_json()
    assert bm["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
    for w in bm["workloads"]:
        cell, config, traffic = harness.load_cell(w["name"])
        assert cell["config"] == w["config"] in configs
        assert cell["traffic"] == w["traffic"] == traffic["name"]
        assert cell["chips"] == w["chips"]
        family = harness.load_family(config)
        assert family.flops_per_step(config, traffic) > 0
        assert set(cell["limits"]) <= {
            "loss1_gap", "loss_gap", "grad_norm_gap", "grad_norm_gap_median",
            "grad_err", "grad_err_median", "grad_err_min",
            "change_norm_gap", "change_norm_gap_median"}
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        spec = harness.load_json("metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    ends = {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in ends and m["workloads"]


def test_unknown_device_kind_is_an_error_not_a_default(monkeypatch):
    monkeypatch.undo()
    with pytest.raises(KeyError, match="no published peaks"):
        harness.device_peaks("cpu")
    assert harness.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12


# -- the window --------------------------------------------------------------

class FakeJob:
    """Steps that take `cost[i]` seconds of a clock the test owns; a loss
    is ready only once its step is done."""

    def __init__(self, clock, cost, bad=()):
        self.clock, self.cost, self.bad = clock, cost, set(bad)
        self.busy_until = 0.0
        self.n = 0

    def step(self, feed):
        start = max(self.clock.t, self.busy_until)
        self.busy_until = start + self.cost[self.n % len(self.cost)]
        self.n += 1
        self.clock.t += 0.001          # dispatch returns at once
        return (self.n - 1, self.busy_until)

    def loss_value(self, handle):
        i, done = handle
        self.clock.t = max(self.clock.t, done)
        return float("nan") if i in self.bad else 1.0


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _window(cost, seconds, bad=()):
    clock = Clock()
    job = FakeJob(clock, cost, bad)
    return harness.run_steps(job, [{}], lambda k, t: t >= seconds,
                             clock=clock, annotate=lambda name: _NoSpan())


def test_the_clock_stops_at_the_completion_of_the_last_step():
    w = _window([0.65], 2.0)
    # the step in flight at 2.0 s completes: the window is a whole number
    # of steps, not cut at the deadline
    assert w["completed"] == w["attempted"]
    assert w["elapsed_s"] == pytest.approx(w["completed"] * 0.65, abs=0.01)
    assert w["elapsed_s"] >= 2.0


def test_a_stalled_step_lowers_the_rate():
    steady = _window([0.1], 2.0)
    stalled = _window([0.1] * 5 + [1.0] + [0.1] * 100, 2.0)
    rate = lambda w: w["completed"] / w["elapsed_s"]  # noqa: E731
    assert rate(stalled) < 0.7 * rate(steady)


def test_a_step_whose_loss_is_not_finite_is_counted_as_failed():
    w = _window([0.1], 1.0, bad={3})
    assert w["failed"] == 1 and w["completed"] == w["attempted"] - 1


# -- the trace reducer -------------------------------------------------------

def recorded_trace():
    path = os.path.join(harness.BENCH_DIR, "fixtures",
                        "trace-bert-base-s128.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_reducer_on_the_recorded_trace():
    r = trace.reduce(recorded_trace(), 1)
    # one step of bert-base-s128 on the chip: 0.655 s, all of it busy
    assert r["window_s"] == pytest.approx(0.655, abs=0.005)
    assert 0.99 * r["window_s"] < r["busy_s"] <= r["window_s"]
    # a while loop holds its body: self times add up to the busy time,
    # plain durations would count the encoder twice
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"],
                                                          rel=1e-6)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == trace.TOP
    assert not ops[0][0].startswith("%while")
    assert all(len(name) <= trace.NAME_CHARS for name, _ in ops)
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_reducer_arithmetic_on_a_hand_made_trace():
    raw = {"devices": {0: [("loop", 0.0, 6e9), ("a", 0.0, 2e9),
                           ("b", 2e9, 4e9), ("c", 8e9, 2e9)]},
           "host": [("bench.read_loss", 5e9, 4e9)]}
    r = trace.reduce(raw, 1)
    assert r["busy_s"] == pytest.approx(8.0)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["op_seconds"] == {"loop": 0.0, "a": 2.0, "b": 4.0, "c": 2.0}
    assert r["breakdown"]["idle_gaps"] == [["bench.read_loss", 2.0]]
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []}, 1)


# -- operations per step -----------------------------------------------------

def test_bert_flops_against_a_hand_count():
    from benchmark.families import bert

    _, config, traffic = harness.load_cell("bert-base-s128")
    traffic = dict(traffic, batch=256)
    # by hand, in multiply-adds: 12 layers x 32,768 tokens x (768 x 2304
    # + 768 x 768 + 2 x 768 x 3072 + 2 x 128 x 768) + 256 x 19 masked x
    # (768 x 768 + 768 x 30522) + 256 x (768 x 768 + 2 x 768); x 6.
    # bench.py's 6N + 12LSH gives 26.72 TFLOP for the same step: it
    # counts the embedding tables and the whole LM head at every token.
    assert bert.flops_per_step(config, traffic) == pytest.approx(
        17.8649e12, rel=1e-4)
    assert bert.units_per_step(config, traffic) == 32768


def test_resnet_flops_against_a_hand_count():
    from benchmark.families import resnet
    from benchmark.reference import resnet as ref

    _, config, traffic = harness.load_cell("resnet50-b256")
    table = ref.conv_table(config)
    assert len(table) == 53
    # by hand (stage by stage, the stride on the 3x3): 4,089,184,256
    # multiply-adds an image, the dense layer's 2,048,000 among them
    assert resnet.flops_per_step(config, dict(traffic, batch=1)) == \
        6.0 * 4089184256
    assert len(ref.param_spec(config)) == 3 * 53 + 2


def long_context():
    """The long-context mix on BERT-base: no cell of BENCHMARK.json yet
    (PERF.md, Open questions), but its kernel readers are kept ready."""
    return (harness.load_json("configs", "bert-base.json"),
            harness.load_json("traffic", "b8-s4096.json"))


def test_flash_kernel_needs_against_a_hand_count():
    from benchmark.kernels import flash_attention

    config, traffic = long_context()
    need = flash_attention.needs(config, traffic)
    # by hand: 12 layers x 96 batch-heads x 4096^2 x 64 x (4 + 4 + 10),
    # and 12 x (96 x 4096 x 64 elements) x 2 bytes x (4 + 4 + 8) tensors
    assert need["flops"] == 12 * 96 * 4096 ** 2 * 64 * 18 == 22265110462464
    assert need["bytes"] == 12 * 96 * 4096 * 64 * 2 * 16
    assert need["calls_per_step"] == 48


def test_kernel_readers_match_names_and_return_nothing_for_no_kernel():
    from benchmark.readers import kernel_roofline, kernel_share

    config, traffic = long_context()
    ops = {"%pp_sdpa.1 = bf16[8] custom-call(...), tpu_custom_call": 2.0,
           "%fusion.2 = bf16[8] fusion(bf16[8] %pp_sdpa.1)": 1.5}
    ctx = {"trace": {"op_seconds": ops, "busy_s": 4.0, "steps": 2},
           "config": config, "traffic": traffic,
           "peaks": harness.device_peaks("TPU v5 lite")}
    sdpa = [["pp_sdpa", "tpu_custom_call"]]
    assert kernel_share.read(ctx, sdpa) == 50.0
    # 22.27 TFLOP a step at 197 TFLOP/s is 0.113 s; 2 steps in 2.0 s
    assert kernel_roofline.read(ctx, "flash_attention", sdpa) == \
        pytest.approx(11.30, abs=0.01)
    # `^` anchors at the operation's own name: the fusion that only
    # READS the kernel's result is not the kernel
    assert kernel_share.read(ctx, [["^%pp_sdpa"]]) == 50.0
    assert kernel_share.read(ctx, [["nothing-of-that-name"]]) is None
    assert kernel_roofline.read(ctx, "flash_attention", [["nope"]]) is None
    assert kernel_share.read(dict(ctx, trace=None), sdpa) is None


# -- one run at the tiny presets ---------------------------------------------

@pytest.mark.parametrize("cell", TINY)
def test_a_run_ends_in_the_contracts_result(cell):
    out = run_tiny(cell)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0 and m["unit"]
    assert out["device"]["platform"] == "cpu"
    for value, limit in out["compared"].values():
        assert value <= limit
    json.dumps(out)


@pytest.mark.parametrize("cell", TINY)
def test_a_step_that_leaves_the_state_unchanged_is_not_correct(cell):
    def wrap(job):
        real = job.step

        def step(feed):
            handle = real(feed)
            job.loss_value(handle)
            job._lay_weights()      # the update is thrown away
            return handle

        job.step = step
        return job

    out = run_tiny(cell, wrap_job=wrap)
    assert out["correct"] is False
    change = [v for k, (v, _) in out["compared"].items()
              if k.startswith("change_norm_gap")]
    # nothing moved: by the measure of norms the change reads 1
    assert change and change[0] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("cell", TINY)
def test_half_of_the_batch_left_out_is_not_correct(cell):
    def wrap(job):
        real = job.step

        def step(feed):
            half = len(next(iter(feed.values()))) // 2
            return real({k: v[:half] for k, v in feed.items()})

        job.step = step
        return job

    out = run_tiny(cell, wrap_job=wrap)
    assert out["correct"] is False


# -- the references and their control ----------------------------------------

@pytest.mark.parametrize("cell", TINY)
def test_a_lower_precision_in_the_programs_place_is_not_correct(cell):
    """The references against the library at the tiny preset, and the
    control: the bfloat16 program holds every limit of the tiny cell,
    the reference with float8 operands put in its place fails at least
    one, on each of three seeds."""
    import jax

    cell_f, config, traffic = harness.load_cell(cell, base=PRESETS)
    family = harness.load_family(config)
    n = int(cell_f["check_steps"])
    for seed in (41, 42, 2147483777):
        feeds = family.make_ring(config, traffic, seed)
        batches = [feeds[i % len(feeds)] for i in range(n)]
        want = family.reference(config, traffic, cell_f, seed, batches)
        job = family.build(config, traffic, cell_f, seed, jax.devices()[:1])
        try:
            got = harness.checked_steps(job, feeds, n)
        finally:
            job.free()
        compared, ok = harness.verdict(harness.compare(got, want)[0],
                                       cell_f["limits"])
        assert ok, (seed, compared)
        control = family.reference(config, traffic, cell_f, seed, batches,
                                   quant="float8_e4m3")
        compared, ok = harness.verdict(harness.compare(control, want)[0],
                                       cell_f["limits"])
        assert not ok, (seed, compared)
        print(cell, seed, "control", compared)


def test_adams_bias_corrected_a_step_ahead_is_not_correct():
    """The library's Adam as it stands (PERF.md, Open questions), planted
    in the reference put in the program's place: after three steps every
    leaf has moved (0.744 + 0.858 + 0.910) / 3 of Adam's or a little
    less, and the change's limit sees it."""
    cell_f, config, traffic = harness.load_cell("bert-tiny-s32",
                                                base=PRESETS)
    family = harness.load_family(config)
    n = int(cell_f["check_steps"])
    for seed in (41, 2147483777):
        feeds = family.make_ring(config, traffic, seed)
        batches = [feeds[i % len(feeds)] for i in range(n)]
        want = family.reference(config, traffic, cell_f, seed, batches)
        ahead = family.reference(config, traffic, cell_f, seed, batches,
                                 adam_ahead=1)
        numbers = harness.compare(ahead, want)[0]
        compared, ok = harness.verdict(numbers, cell_f["limits"])
        assert not ok, (seed, compared)
        assert 0.14 < numbers["change_norm_gap_median"] < 0.22, numbers
        assert numbers["grad_err"] == 0.0


def test_the_reference_takes_nothing_of_the_program():
    import re

    for name in ("bert", "resnet", "common"):
        with open(os.path.join(harness.BENCH_DIR, "reference",
                               name + ".py")) as f:
            assert not re.search(r"^\s*(import|from)\s+(paddle_tpu|bench\b|"
                                 r"chip_smoke|benchmark\.families)",
                                 f.read(), re.M)


# -- the data-parallel branch ------------------------------------------------

def test_data_parallel_branch_on_four_virtual_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices (XLA_FLAGS is set too late)")
    out = harness.run_cell(
        "bert-tiny-dp4-s32", 77, 0.5, False, jax.devices(), 0.0,
        base=PRESETS, benchmark=tiny_benchmark("bert-tiny-dp4-s32"))
    assert out["device"]["count"] == 4
    assert out["correct"] is True, out["compared"]
