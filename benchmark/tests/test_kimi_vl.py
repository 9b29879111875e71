"""CPU tests of the `kimi_vl` family at the `kimi-vl-tiny` preset (a
dense layer and two routed layers, heads of 16 + 8 on values of 12, the
second half of 8 experts held), run by hand with the other harness
tests:

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
    PRESETS, benchmark_json, cpu_peaks, run_tiny)

CELL = "kimi-vl-tiny-s48"
REAL = ("kimi-vl-a3b-ep8", "b1-s16384")
NEW_CELL = "kimi-vl-a3b-ep8-longdoc"
NEW_METRICS = ("mla_attn_share_pct", "mla_attn_roofline_pct",
               "moe_experts_kimi_roofline_pct")


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
    from benchmark.families import kimi_vl as family
    from benchmark.readers import program_gauge

    for seen in family.FETCHED.values():
        del seen[:]
    out = run_tiny(CELL)
    ctx = {"config": {"family": "kimi_vl"}}
    load = program_gauge.read(ctx, "moe.load_max_over_mean")
    pairs = program_gauge.read(ctx, "moe.held_pairs", stat="last")
    assert 1.0 <= load <= 4.0
    # two routed layers, 2 x 48 tokens, 3 experts a token, 4 of 8 held
    assert 0 < pairs <= 2 * 96 * 3
    assert program_gauge.read(ctx, "moe.rows_made", stat="last") == 2 * 512
    # a value a step: the checked steps, the warm-up and the window
    assert len(family.FETCHED["moe.held_pairs"]) >= out["attempted"] + 3
    # and the needs file reads the rows the steps counted
    cell_f, config, traffic = harness.load_cell(CELL, base=PRESETS)
    needs = importlib.import_module("benchmark.kernels.moe_experts_kimi_vl")
    seen = family.FETCHED["moe.held_pairs"][-int(traffic["ring"]):]
    assert needs.rows_per_step(config, traffic) == sum(seen) / len(seen)


def test_the_programs_share_is_the_planners_and_the_references_the_files():
    from benchmark.reference import kimi_vl as ref
    from paddle_tpu.parallel import planner

    for config in (real_cell()[0],
                   harness.load_cell(CELL, base=PRESETS)[1]):
        dep = config["deployment"]
        assert planner.experts_held(
            config["published"]["n_routed_experts"], dep["expert_parallel"],
            dep["expert_parallel_rank"]) == ref.held_range(config)


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
    # 1 but for the middle pair of an even count of leaves: the median
    # floor lies between them, so the lower one reads just under 1
    assert out["compared"]["change_norm_gap_median"][0] == pytest.approx(
        1.0, abs=5e-3)


def test_a_lower_precision_in_the_programs_place_is_not_correct():
    """The bfloat16 program holds the tiny cell's limits, the reference
    with float8 operands put in its place fails one, on three seeds; so
    does half of one document's positions left out of the loss (the
    half-batch fault of a batch of one)."""
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
    one = dict(traffic, batch=1)
    feeds = family.make_ring(config, one, 43)[:1]
    want = family.reference(config, one, cell_f, 43, feeds)
    half = family.reference(config, one, cell_f, 43, feeds,
                            keep=slice(0, 0))
    assert not harness.verdict(harness.compare(half, want)[0],
                               cell_f["limits"])[1]


def test_the_reference_takes_nothing_of_the_program():
    with open(os.path.join(harness.BENCH_DIR, "reference",
                           "kimi_vl.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(import|from)\s+(paddle_tpu|bench\b|"
                         r"chip_smoke|benchmark\.families)", text, re.M)
    # the masked square over two products, no kernel
    assert "pallas" not in text and "k_rope[:, 0]" in text


def test_the_cut_is_the_issues_count():
    """668.9 M parameters, 10.70 GB at 16 bytes each; the step's count
    by part; every number of the catalog's config under its key, but
    the three reduced."""
    import numpy as np

    config, traffic = real_cell()
    family = harness.load_family(config)
    n = sum(int(np.prod(s)) for _, s, _, _ in family.weight_spec(config))
    assert n == 668890112
    assert round(n * 16 / 1e9, 2) == 10.70
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 8, 20480)
    assert config["published"] == dict(
        config["published"], num_hidden_layers=27, n_routed_experts=64,
        vocab_size=163840)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 16,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "kv_lora_rank": 512, "q_lora_rank": None,
            "intermediate_size": 11264, "moe_intermediate_size": 1408,
            "num_experts_per_tok": 6, "n_shared_experts": 2,
            "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
            "rope_theta": 800000, "rms_norm_eps": 1e-5, "n_group": 1,
            "topk_group": 1, "scoring_func": "sigmoid",
            "max_position_embeddings": 131072}.items():
        assert config[key] == value, key
    macs = family.macs_per_token(config, traffic)
    total = sum(macs.values())
    shares = {k: round(100 * v / total) for k, v in macs.items()}
    assert shares == {"attention": 59, "dense": 12, "routed": 21, "head": 7}
    # a mixer by hand: four projections and the causal half at 192 + 128
    assert macs["attention"] == 6 * (
        2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
        + 16 * 320 * 16384 / 2)
    assert macs["dense"] == 3 * 2048 * 11264
    # a routed layer: 6 x 8 / 64 of a token's pairs land here
    assert macs["routed"] == 5 * (2048 * 64 + 3 * 2048 * 2816
                                  + 0.75 * 3 * 2048 * 1408)
    assert family.flops_per_step(config, traffic) == 6 * 16384 * total
    assert math.isclose(family.flops_per_step(config, traffic), 55.54e12,
                        rel_tol=1e-3)


def test_kernel_needs_against_a_hand_count(monkeypatch):
    from benchmark.families import kimi_vl as family

    config, traffic = real_cell()
    peaks = harness.load_json("peaks.json")["peaks"]["TPU v5 lite"]
    mla = importlib.import_module(
        "benchmark.kernels.mla_attention").needs(config, traffic)
    # six layers, 16 heads, half of 16,384^2 pairs; forward 192 + 128
    # twice, backward three products at 192 and two at 128
    assert mla["calls_per_step"] == 24
    assert mla["flops"] == 6 * 16 * (16384 * 16384 // 2) * 2 * (
        2 * 320 + 3 * 192 + 2 * 128)
    # Q 16 x 192, K 16 x 128 and the rotary 64 ONCE, V and O 16 x 128
    assert mla["bytes"] == 6 * 4 * 2 * 16384 * (
        16 * 192 + 16 * 128 + 64 + 2 * 16 * 128)
    # bound by arithmetic
    assert (mla["flops"] / peaks["bf16_flops"]
            > mla["bytes"] / peaks["hbm_bytes_per_s"])
    gated = importlib.import_module("benchmark.kernels.moe_experts_kimi_vl")
    monkeypatch.setattr(family, "FETCHED", {"moe.held_pairs": []})
    # no step has run: what a uniform routing sends five layers' 8 held
    # of 64 experts, 6 a token
    assert gated.rows_per_step(config, traffic) == 5 * 16384 * 6 / 8
    need = gated.needs(config, traffic)
    rows, hf, matrix = 61440, 2048 * 1408, 5 * 8 * 2048 * 1408
    assert need["calls_per_step"] == 35
    assert need["flops"] == 2 * 11 * rows * hf
    wide = 2 * (2 * matrix + rows * (2048 + 2816))
    narrow = 2 * (matrix + rows * (1408 + 2048))
    sums = 2 * rows * (2048 + 2816 + 1408 + 2048) + 4 * 3 * matrix
    assert need["bytes"] == 3 * wide + 2 * narrow + sums
    # the same count as the file it restates, on a config with both
    # spellings of its keys
    both = dict(config, num_hidden_layers=5, num_experts=8,
                published={"num_experts": 64, "n_routed_experts": 64})
    from benchmark.families import qwen3_next

    monkeypatch.setattr(qwen3_next, "FETCHED", {"moe.held_pairs": []})
    assert importlib.import_module(
        "benchmark.kernels.moe_experts_gated").needs(both, traffic) == need
    monkeypatch.setattr(family, "FETCHED", {
        "moe.held_pairs": [1.0] + [30000.0, 50000.0] * 2})
    assert gated.rows_per_step(config, traffic) == 40000.0


def test_the_three_new_metrics_name_readers_that_exist():
    for name in NEW_METRICS:
        spec = harness.load_json("metrics", name + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert callable(reader.read)
        if "kernel" in spec["args"]:
            needs = importlib.import_module(
                "benchmark.kernels." + spec["args"]["kernel"]).needs(
                    *real_cell())
            assert needs["flops"] > 0 and needs["bytes"] > 0
    # and read nothing, without raising, where there is no trace
    ctx = {"trace": None, "config": real_cell()[0],
           "traffic": real_cell()[1]}
    for name in NEW_METRICS:
        spec = harness.load_json("metrics", name + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert reader.read(ctx, **spec["args"]) is None


def test_the_new_cell_is_appended_and_reports_what_the_issue_lists():
    bm = benchmark_json()
    assert bm["workloads"][-1]["name"] == NEW_CELL
    assert bm["workloads"][-1]["chips"] == 1
    assert bm["configs"][-1]["name"] == REAL[0]
    assert len(bm["workloads"]) == 7 and len(bm["configs"]) == 5
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1
    assert [m["name"] for m in bm["per_layer"]][-3:] == list(NEW_METRICS)
    lists = {m["name"]: m["workloads"]
             for m in bm["end_to_end"] + bm["per_layer"] if "workloads" in m}
    for name, cells in lists.items():
        if name.endswith(".tok") or name in (
                "setup_compile_s", "train_tokens_per_s",
                "flash_attn_share_pct", "moe_experts_share_pct",
                "moe_load_max_over_mean"):
            assert cells[-1] == NEW_CELL, name
        elif name in NEW_METRICS:
            assert cells == [NEW_CELL], name
        else:
            assert NEW_CELL not in cells, name
    cell = harness.load_json("workloads", NEW_CELL + ".json")
    assert cell["parallel"] == {} and cell["chips"] == 1
    assert cell["config"] == REAL[0] and cell["traffic"] == REAL[1]
