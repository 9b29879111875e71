"""CPU tests of the `nemotron_h` family at the `nemotron-tiny` preset
(every kind of layer, the second half of 8 experts held), run by
hand with the other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Tier-1 does not collect them; a CPU run gives results and counts, never
a time."""
import importlib
import json
import math
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from benchmark import harness  # noqa: E402
from benchmark.tests.test_harness import (  # noqa: E402,F401
    PRESETS, cpu_peaks, run_tiny)

CELL = "nemotron-tiny-s24"
REAL = ("nemotron3-nano-30b-a3b-ep16", "b2-s8192")


def real_cell():
    return (harness.load_json("configs", REAL[0] + ".json"),
            harness.load_json("traffic", REAL[1] + ".json"))


def test_a_run_ends_in_the_contracts_result():
    out = run_tiny(CELL)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    json.dumps(out)


def test_the_counters_reach_the_gauge_reader():
    from benchmark.families import nemotron_h as family
    from benchmark.readers import program_gauge

    for seen in family.FETCHED.values():
        del seen[:]
    out = run_tiny(CELL)
    ctx = {"config": {"family": "nemotron_h"}}
    load = program_gauge.read(ctx, "moe.load_max_over_mean")
    pairs = program_gauge.read(ctx, "moe.held_pairs", stat="last")
    assert 1.0 <= load <= 4.0
    # two routed layers, 4 x 24 tokens, 3 experts a token, 4 of 8 held
    assert 0 < pairs <= 2 * 96 * 3
    # a value a step: the checked steps, the warm-up and the window
    assert len(family.FETCHED["moe.held_pairs"]) >= out["attempted"] + 3
    assert program_gauge.read(ctx, "no.such.gauge") is None


def test_a_family_that_fetches_no_counters_reads_nothing():
    from benchmark.readers import program_gauge

    assert program_gauge.read({"config": {"family": "bert"}},
                              "moe.load_max_over_mean") is None


def test_the_programs_share_is_the_planners_and_the_references_the_files():
    from benchmark.reference import nemotron_h as ref
    from paddle_tpu.parallel import planner

    for config in (real_cell()[0],
                   harness.load_cell(CELL, base=PRESETS)[1]):
        dep = config["deployment"]
        assert planner.experts_held(
            config["published"]["n_routed_experts"], dep["expert_parallel"],
            dep["expert_parallel_rank"]) == ref.held_range(config)


def test_half_of_the_batch_left_out_is_not_correct():
    def wrap(job):
        real = job.step

        def step(feed):
            half = len(next(iter(feed.values()))) // 2
            return real({k: v[:half] for k, v in feed.items()})

        job.step = step
        return job

    assert run_tiny(CELL, wrap_job=wrap)["correct"] is False


def test_a_step_that_leaves_the_state_unchanged_is_not_correct():
    def wrap(job):
        real = job.step

        def step(feed):
            handle = real(feed)
            job.loss_value(handle)
            job._lay_weights()      # the update is thrown away
            return handle

        job.step = step
        return job

    out = run_tiny(CELL, wrap_job=wrap)
    assert out["correct"] is False
    assert out["compared"]["change_norm_gap_median"][0] == pytest.approx(
        1.0, abs=1e-3)


def test_a_lower_precision_in_the_programs_place_is_not_correct():
    """The bfloat16 program holds the tiny cell's limits, the reference
    with float8 operands put in its place fails one, on three seeds."""
    import jax

    cell_f, config, traffic = harness.load_cell(CELL, base=PRESETS)
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
        numbers = harness.compare(got, want)[0]
        compared, ok = harness.verdict(numbers, cell_f["limits"])
        assert ok, (seed, numbers)
        control = family.reference(config, traffic, cell_f, seed, batches,
                                   quant="float8_e4m3")
        c_numbers = harness.compare(control, want)[0]
        compared, ok = harness.verdict(c_numbers, cell_f["limits"])
        assert not ok, (seed, c_numbers)
        print(CELL, seed, "program", numbers, "control", c_numbers)


def test_the_reference_takes_nothing_of_the_program():
    with open(os.path.join(harness.BENCH_DIR, "reference",
                           "nemotron_h.py")) as f:
        assert not re.search(r"^\s*(import|from)\s+(paddle_tpu|bench\b|"
                             r"chip_smoke|benchmark\.families)",
                             f.read(), re.M)


def test_the_cut_is_the_issues_count():
    """666.96 M parameters, 10.67 GB at 16 bytes each; the step's count
    by kind of layer."""
    import numpy as np

    config, traffic = real_cell()
    family = harness.load_family(config)
    n = sum(int(np.prod(s)) for _, s, _, _ in family.weight_spec(config))
    assert n == 666962944
    macs = family.macs_per_token(config, traffic)
    total = sum(macs.values())
    shares = {k: round(100 * v / total) for k, v in macs.items()}
    assert shares == {"M": 45, "*": 16, "E": 27, "head": 12}
    # a Mamba-2 layer by hand: the two projections, 4 taps, the scan
    by_hand = (2688 * 10304 + 4096 * 2688 + 6144 * 4
               + 8 * 128 * 128 / 2 + 64 * (128 * 64 / 2 + 2 * 128 * 64))
    assert macs["M"] == 4 * by_hand
    assert family.flops_per_step(config, traffic) == 6 * 16384 * total
    assert math.isclose(family.flops_per_step(config, traffic), 35.15e12,
                        rel_tol=1e-3)


def test_kernel_needs_against_a_hand_count():
    config, traffic = real_cell()
    ssd = importlib.import_module("benchmark.kernels.ssd_chunk_scan").needs(
        config, traffic)
    # 16,384 tokens, 4 layers, 2 calls; a token: 65,536 + 64 x 20,480
    assert ssd["calls_per_step"] == 8
    assert ssd["flops"] == 2 * 16384 * 8 * (65536 + 64 * 20480)
    assert ssd["bytes"] == 16384 * 8 * (16384 + 4096 + 512)
    from benchmark.families import nemotron_h as family

    moe_needs = importlib.import_module("benchmark.kernels.moe_experts").needs
    seen = family.FETCHED["moe.held_pairs"]
    del seen[:]
    moe = moe_needs(config, traffic)
    # no step run: 6,144 pairs a layer, ten products of 6,144 x 2,688 x
    # 1,856 in each of four layers
    assert moe["calls_per_step"] == 40
    assert moe["flops"] == 2 * 6144 * 2688 * 1856 * 40
    # steps run: the pairs they counted, the mean over the ring's last
    # turn (four batches)
    seen.extend([1.0, 20000.0, 30000.0, 20000.0, 30000.0])
    try:
        counted = moe_needs(config, traffic)
    finally:
        del seen[:]
    assert counted["flops"] == 2 * 25000 * 2688 * 1856 * 10
    assert counted["bytes"] == 10 * 2 * (
        4 * 8 * 2688 * 1856 + 25000 * (2688 + 1856))
    flash = importlib.import_module(
        "benchmark.kernels.flash_attention_gqa").needs(config, traffic)
    # one attention layer, 2 sequences, 32 query heads on 2 key/value
    # heads, half of 8,192^2 pairs, 128 wide, 2 x 2 + 5 products
    assert flash["calls_per_step"] == 4
    assert flash["flops"] == 2 * 32 * (8192 * 8192 // 2) * 128 * 2 * 9
    assert flash["bytes"] == 2 * 8192 * 128 * 2 * (2 * 68 + 136)
    # where every query head has its own key/value head and the whole
    # square counts, the encoder's count (`kernels/flash_attention.py`)
    bert = harness.load_json("configs", "bert-base.json")
    s4096 = harness.load_json("traffic", "b8-s4096.json")
    as_gqa = dict(bert, num_key_value_heads=12, head_dim=64,
                  hybrid_override_pattern="*" * 12)
    whole = importlib.import_module(
        "benchmark.kernels.flash_attention").needs(bert, s4096)
    half = importlib.import_module(
        "benchmark.kernels.flash_attention_gqa").needs(as_gqa, s4096)
    assert half["flops"] * 2 == whole["flops"]
    assert half["bytes"] == whole["bytes"]


def test_the_new_metrics_match_the_kernels_names():
    """What the compiled step calls the kernels (held by
    tests/test_tpu_compile.py) against the metrics' match lists."""
    from benchmark.readers import kernel_roofline

    ctx = {"trace": {"busy_s": 1.0, "steps": 1, "op_seconds": {
        '%ssd_chunk_scan_fwd.1 = bf16[2,64,8192,64] custom-call(%a), '
        'custom_call_target="tpu_custom_call"': 0.25,
        '%moe_experts_gmm.76 = bf16[24576,1856] custom-call(%a), '
        'custom_call_target="tpu_custom_call"': 0.125,
        '%moe_experts_tgmm.24 = bf16[8,2688,1856] custom-call(%a), '
        'custom_call_target="tpu_custom_call"': 0.125,
        "%fusion.1 = f32[2] fusion(%b)": 0.5}}}
    ctx["trace"]["op_seconds"][
        '%scaled_dot_product_attention_flash_fwd.2 = bf16[2,32,8192,128] '
        'custom-call(%q), custom_call_target="tpu_custom_call"'] = 0.0625
    for metric, seconds in (("ssd_scan_share_pct", 0.25),
                            ("moe_experts_share_pct", 0.25),
                            ("flash_attn_share_pct", 0.0625),
                            ("flash_attn_gqa_roofline_pct", 0.0625)):
        spec = harness.load_json("metrics", metric + ".json")
        assert kernel_roofline.kernel_seconds(
            ctx, spec["args"]["match"]) == seconds
