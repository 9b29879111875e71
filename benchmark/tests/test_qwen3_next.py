"""CPU tests of the `qwen3_next` family at the `qwen3-next-tiny` preset
(both kinds of layer, the second half of 8 experts held), run by hand
with the other harness tests:

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

CELL = "qwen3-next-tiny-s80"
REAL = ("qwen3-next-80b-a3b-ep16", "b1-s16384")
NEW_CELLS = ("qwen3-next-ep16-longdoc", "bert-base-dp4-s128")


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
    from benchmark.families import qwen3_next as family
    from benchmark.readers import program_gauge

    for seen in family.FETCHED.values():
        del seen[:]
    out = run_tiny(CELL)
    ctx = {"config": {"family": "qwen3_next"}}
    load = program_gauge.read(ctx, "moe.load_max_over_mean")
    pairs = program_gauge.read(ctx, "moe.held_pairs", stat="last")
    assert 1.0 <= load <= 4.0
    # four routed layers, 2 x 80 tokens, 3 experts a token, 4 of 8 held
    assert 0 < pairs <= 4 * 160 * 3
    assert program_gauge.read(ctx, "moe.rows_made", stat="last") == 4 * 512
    # a value a step: the checked steps, the warm-up and the window
    assert len(family.FETCHED["moe.held_pairs"]) >= out["attempted"] + 3


def test_the_programs_share_is_the_planners_and_the_references_the_files():
    from benchmark.reference import qwen3_next as ref
    from paddle_tpu.parallel import planner

    for config in (real_cell()[0],
                   harness.load_cell(CELL, base=PRESETS)[1]):
        dep = config["deployment"]
        assert planner.experts_held(
            config["published"]["num_experts"], dep["expert_parallel"],
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
                           "qwen3_next.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(import|from)\s+(paddle_tpu|bench\b|"
                         r"chip_smoke|benchmark\.families)", text, re.M)
    # the recurrence, not the chunked form
    assert "lax.scan(step" in text and "chunk" not in text.split('"""')[2]


def test_the_cut_is_the_issues_count():
    """625.7 M parameters, 10.01 GB at 16 bytes each; the step's count
    by part; every published number of the catalog's config under its
    key, but the three reduced."""
    import numpy as np

    config, traffic = real_cell()
    family = harness.load_family(config)
    n = sum(int(np.prod(s)) for _, s, _, _ in family.weight_spec(config))
    assert n == 625667136
    assert round(n * 16 / 1e9, 2) == 10.01
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    assert config["published"]["num_experts"] == 512
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key, value in {"hidden_size": 2048, "head_dim": 256,
                       "moe_intermediate_size": 512,
                       "shared_expert_intermediate_size": 512,
                       "linear_key_head_dim": 128,
                       "linear_value_head_dim": 128,
                       "linear_num_key_heads": 16,
                       "linear_num_value_heads": 32,
                       "num_experts_per_tok": 10,
                       "intermediate_size": 5120}.items():
        assert config[key] == value, key
    macs = family.macs_per_token(config, traffic)
    total = sum(macs.values())
    shares = {k: round(100 * v / total) for k, v in macs.items()}
    assert shares == {"delta": 40, "attention": 36, "routed": 9, "head": 15}
    # a Gated DeltaNet mixer by hand: three projections, 4 taps, the rule
    rule = 16 * 64 * 128 + 32 * (64 * 256 / 2 + 3 * 128 * 128 + 64 * 128 / 2)
    by_hand = 2048 * (12288 + 64) + 4096 * 2048 + 8192 * 4 + rule
    assert macs["delta"] == 3 * by_hand
    # the attention layer: the query twice as wide, the causal half
    assert macs["attention"] == 2048 * (2 * 16 + 2 * 2) * 256 \
        + 4096 * 2048 + 2 * 16 * 256 * 16384 / 2
    # a routed layer: 10 x 32 / 512 of a token's pairs land here
    assert macs["routed"] == 4 * (2048 * 512 + 3 * 2048 * 512 + 2048
                                  + 0.625 * 3 * 2048 * 512)
    assert family.flops_per_step(config, traffic) == 6 * 16384 * total
    assert math.isclose(family.flops_per_step(config, traffic), 26.09e12,
                        rel_tol=1e-3)


def test_kernel_needs_against_a_hand_count():
    config, traffic = real_cell()
    rule = importlib.import_module(
        "benchmark.kernels.gated_delta_rule").needs(config, traffic)
    # three layers, forward twice and backward once; a token's forward
    # 2,097,152 multiply-adds; the backward at twice the forward
    assert rule["calls_per_step"] == 9
    assert rule["flops"] == 3 * 16384 * 2 * 2097152 * 4
    token = 2 * 2048 * 2 + 4096 * 2 + 2 * 32 * 4    # q, k; v; g, beta
    out, states = 4096 * 2, 256 * 32 * 128 * 128 * 4
    assert rule["bytes"] == 3 * (
        2 * 16384 * (token + out) + states              # two forwards
        + 16384 * (token + out) + states + 16384 * token)   # the backward
    flash = importlib.import_module(
        "benchmark.kernels.flash_attention_d256").needs(config, traffic)
    # one attention layer, one sequence, 16 query heads on 2 key/value
    # heads, half of 16,384^2 pairs, 256 wide, 2 x 2 + 5 products
    assert flash["calls_per_step"] == 4
    assert flash["flops"] == 16 * (16384 * 16384 // 2) * 256 * 2 * 9
    assert flash["bytes"] == 16384 * 256 * 2 * (2 * 36 + 72)
    # the same count as the pattern-string file's, on a config with both
    # spellings
    both = dict(config, hybrid_override_pattern="DDD*")
    assert importlib.import_module(
        "benchmark.kernels.flash_attention_gqa").needs(both, traffic) == flash


def test_gated_experts_needs_against_a_hand_count(monkeypatch):
    from benchmark.families import qwen3_next as family

    config, traffic = real_cell()
    gated = importlib.import_module("benchmark.kernels.moe_experts_gated")
    monkeypatch.setattr(family, "FETCHED", {"moe.held_pairs": []})
    # no step has run: what a uniform routing sends four layers' 32 held
    # of 512 experts, 10 a token
    assert gated.rows_per_step(config, traffic) == 4 * 16384 * 10 / 16
    need = gated.needs(config, traffic)
    rows, hf, matrix = 40960, 2048 * 512, 4 * 32 * 2048 * 512
    # seven products a layer, 11 rows H F multiply-adds: up 2 and down 1
    # forward; up 2, through W_down 1, through WUp 2, gradients 1 + 2
    assert need["calls_per_step"] == 28
    assert need["flops"] == 2 * 11 * rows * hf
    wide = 2 * (2 * matrix + rows * (2048 + 1024))
    narrow = 2 * (matrix + rows * (512 + 2048))
    sums = 2 * rows * (2048 + 1024 + 512 + 2048) + 4 * 3 * matrix
    assert need["bytes"] == 3 * wide + 2 * narrow + sums
    # bound by memory at these rows: a held expert sees 320 tokens
    peaks = harness.load_json("peaks.json")["peaks"]["TPU v5 lite"]
    assert (need["bytes"] / peaks["hbm_bytes_per_s"]
            > need["flops"] / peaks["bf16_flops"])
    # the rows are the steps' own where steps have run: the ring's last
    # turn, whatever came before it
    monkeypatch.setattr(family, "FETCHED", {
        "moe.held_pairs": [1.0] + [30000.0, 50000.0] * 2})
    assert gated.rows_per_step(config, traffic) == 40000.0
    assert gated.needs(config, traffic)["flops"] == 2 * 11 * 40000.0 * hf


def _fold(by_op_type, total=1000.0, unattributed=50.0, steps=4):
    return {"by_op_type": by_op_type, "total_us": total, "steps": steps,
            "unattributed_us": unattributed}


def test_the_op_type_readers_read_the_programs_fold(monkeypatch):
    from benchmark import sidecar
    from benchmark.readers import op_type_roofline, op_type_share

    config, traffic = real_cell()
    ctx = {"trace": {"steps": 4}, "config": config, "traffic": traffic,
           "peaks": harness.load_json("peaks.json")["peaks"]["TPU v5 lite"]}
    fold = _fold({"gated_delta_rule": 250.0, "matmul": 500.0})
    monkeypatch.setattr(sidecar, "fold", lambda d: fold)
    assert op_type_share.read(ctx, "gated_delta_rule") == 25.0
    need = importlib.import_module(
        "benchmark.kernels.gated_delta_rule").needs(config, traffic)
    least = max(need["flops"] / ctx["peaks"]["bf16_flops"],
                need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    assert least == need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    assert op_type_roofline.read(
        ctx, "gated_delta_rule", "gated_delta_rule") == pytest.approx(
            100.0 * least * 4 / 250e-6)
    # nothing to read: None, never 0
    assert op_type_share.read(ctx, "ssd_chunk_scan") is None
    assert op_type_roofline.read(ctx, "ssd_chunk_scan",
                                 "gated_delta_rule") is None
    assert op_type_share.read(dict(ctx, trace=None),
                              "gated_delta_rule") is None
    monkeypatch.setattr(sidecar, "fold", lambda d: None)
    assert op_type_share.read(ctx, "gated_delta_rule") is None
    # a fold that lost its names reads nothing
    monkeypatch.setattr(sidecar, "fold", lambda d: _fold(
        {"gated_delta_rule": 250.0}, unattributed=300.0))
    assert op_type_share.read(ctx, "gated_delta_rule") is None


def test_collective_exposed_takes_the_worst_devices_collectives(
        monkeypatch):
    from benchmark import trace
    from benchmark.readers import collective_exposed

    ms = 1e6
    raw = {"host": [], "devices": {
        0: [("%fusion.1 = f32[8] fusion(%a)", 0.0, 10 * ms),
            ("%all-reduce.3 = f32[8] all-reduce(%b)", 10 * ms, 2 * ms),
            ("%all-gather-start.1 = bf16[8] all-gather-start(%c)",
             12 * ms, 1 * ms)],
        1: [("%reduce-scatter.2 = f32[2] reduce-scatter(%b)", 0.0, 6 * ms),
            ("%all-gather-done.1 = bf16[8] all-gather-done(%c)",
             6 * ms, 2 * ms),
            ("%fusion.all-reduce-like = f32[8] fusion(%a)", 8 * ms, 9 * ms)]}}
    monkeypatch.setattr(trace, "find_xplane", lambda d: "x")
    monkeypatch.setattr(trace, "read_xplane", lambda p: raw)
    assert collective_exposed.read({"trace": {"steps": 4}}) == 2.0
    assert collective_exposed.read({"trace": None}) is None
    raw["devices"] = {0: raw["devices"][0][:1]}
    assert collective_exposed.read({"trace": {"steps": 4}}) is None


def test_the_new_cells_are_appended_and_report_what_the_issue_lists():
    bm = benchmark_json()
    assert [w["name"] for w in bm["workloads"]][-2:] == list(NEW_CELLS)
    assert bm["configs"][-1]["name"] == REAL[0]
    assert [w["chips"] for w in bm["workloads"]][-2:] == [1, 4]
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1
    assert [m["name"] for m in bm["per_layer"]][-5:] == [
        "gdn_scan_share_pct", "gdn_scan_roofline_pct",
        "flash_attn_d256_roofline_pct", "collective_exposed_ms",
        "moe_experts_gated_roofline_pct"]
    lists = {m["name"]: m["workloads"]
             for m in bm["end_to_end"] + bm["per_layer"] if "workloads" in m}
    for name, cells in lists.items():
        if name.endswith(".tok") or name in ("setup_compile_s",
                                             "train_tokens_per_s"):
            assert cells[-2:] == list(NEW_CELLS), name
    for name in ("flash_attn_share_pct", "moe_experts_share_pct",
                 "moe_load_max_over_mean", "gdn_scan_share_pct",
                 "gdn_scan_roofline_pct", "flash_attn_d256_roofline_pct",
                 "moe_experts_gated_roofline_pct"):
        assert lists[name][-1] == NEW_CELLS[0], name
    assert lists["collective_exposed_ms"] == [NEW_CELLS[1]]
    for name in ("moe_experts_roofline_pct", "flash_attn_gqa_roofline_pct",
                 "ssd_scan_share_pct"):
        assert not set(NEW_CELLS) & set(lists[name]), name
    cell = harness.load_json("workloads", NEW_CELLS[1] + ".json")
    assert cell["parallel"] == {"dp": 4} and cell["chips"] == 4
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic["batch"] == 1024 and traffic["seq_len"] == 128
