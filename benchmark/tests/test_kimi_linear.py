"""CPU tests of the `kimi_linear` family at the `kimi-linear-tiny` preset
(the dense layer and one whole period of three KDA layers to one
latent-attention layer without position, KDA heads of 128, experts 8-15
of 32 held), run by hand with the other harness tests:

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

CELL = "kimi-linear-tiny-s80"
REAL = ("kimi-linear-48b-a3b-ep32", "b1-s8192")
NEW_CELL = "kimi-linear-ep32-longdoc"
NEW_METRICS = ("kda_scan_roofline_pct", "kda_decay_ms")


def real_cell():
    return (harness.load_json("configs", REAL[0] + ".json"),
            harness.load_json("traffic", REAL[1] + ".json"))


def planted(config, fault):
    """The configuration a planted fault's reference follows: `scalar`,
    each head's decay one scalar, the mean of g over its channels (the
    scalar-gated rule this configuration generalises); `rope`, the
    latent attention turned by the default rotary at `rope_theta` 1e4,
    which `mla_use_nope` leaves out."""
    config = json.loads(json.dumps(config))
    if fault == "scalar":
        config["kda_decay"] = "head_mean"
    elif fault == "rope":
        config["mla_use_nope"] = False
        config["rope_theta"] = 10000
    return config


def test_a_run_ends_in_the_contracts_result():
    out = run_tiny(CELL)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    json.dumps(out)


def test_the_counters_reach_the_gauge_reader():
    from benchmark.families import kimi_linear as family
    from benchmark.readers import program_gauge

    for seen in family.FETCHED.values():
        del seen[:]
    out = run_tiny(CELL)
    ctx = {"config": {"family": "kimi_linear"}}
    assert 1.0 <= program_gauge.read(ctx, "moe.load_max_over_mean") <= 4.0
    # four routed layers, 2 x 80 tokens, 3 experts a token, 8 of 32 held
    assert 0 < program_gauge.read(ctx, "moe.held_pairs",
                                  stat="last") <= 4 * 160 * 3
    assert len(family.FETCHED["moe.held_pairs"]) >= out["attempted"] + 3


def test_the_programs_share_is_the_planners_and_the_references_the_files():
    from benchmark.reference import kimi_linear as ref
    from paddle_tpu.parallel import planner

    for config in (real_cell()[0],
                   harness.load_cell(CELL, base=PRESETS)[1]):
        dep = config["deployment"]
        assert planner.experts_held(
            config["published"]["num_experts"], dep["expert_parallel"],
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
    assert out["compared"]["change_norm_gap_median"][0] == pytest.approx(
        1.0, abs=5e-3)


def test_the_planted_faults_are_not_correct():
    """The bfloat16 program holds the tiny cell's limits on two seeds;
    the reference with float8 operands put in its place fails them, and
    so do each head's decay made one scalar and half of the sequences
    left out of the loss. The latent attention turned by the default
    rotary moves its own layer's gradient well past float32's rounding,
    but over 80 positions and 8 rotary columns by less than the bfloat16
    program's own error (grad_err_median 0.007-0.015 against the sound
    0.030-0.037): the real cell's limits, from its own readings at
    16,384 positions, are the ones that must catch it."""
    import jax

    cell_f, config, traffic = harness.load_cell(CELL, base=PRESETS)
    family = harness.load_family(config)
    ref = family.ref
    n = int(cell_f["check_steps"])
    for seed in (41, 2147483777):
        feeds = family.make_ring(config, traffic, seed)
        batches = [feeds[i % len(feeds)] for i in range(n)]
        want = family.reference(config, traffic, cell_f, seed, batches)
        job = family.build(config, traffic, cell_f, seed, jax.devices()[:1])
        try:
            got = harness.checked_steps(job, feeds, n)
        finally:
            job.free()
        numbers = harness.compare(got, want)[0]
        assert harness.verdict(numbers, cell_f["limits"])[1], (seed, numbers)
        faults = {"control": family.reference(
            config, traffic, cell_f, seed, batches, quant="float8_e4m3"),
            "half": family.reference(config, traffic, cell_f, seed, batches,
                                     keep=slice(0, 1))}
        faults["scalar"] = ref.train(
            family.make_weights(config, seed), batches,
            planted(config, "scalar"), config["recipe"])
        rope = ref.train(family.make_weights(config, seed), batches,
                         planted(config, "rope"), config["recipe"])
        moved = harness.grad_errors(rope, want)
        assert min(moved[k] for k in moved if k.startswith("l3.")) > 1e-4
        for name, side in faults.items():
            c_numbers = harness.compare(side, want)[0]
            assert not harness.verdict(c_numbers, cell_f["limits"])[1], (
                seed, name, c_numbers)


def test_the_reference_takes_nothing_of_the_program():
    with open(os.path.join(harness.BENCH_DIR, "reference",
                           "kimi_linear.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(import|from)\s+(paddle_tpu|bench\b|"
                         r"chip_smoke|benchmark\.families|"
                         r".*hybrid_ops)", text, re.M)
    # the recurrence token by token, a factor a channel; no chunks
    assert "pallas" not in text and "_GDR_CHUNK" not in text
    assert "jnp.exp(g_t)[:, :, None] * state" in text


def test_the_cut_is_the_issues_count():
    """602.45 M parameters, 9.64 GB at 16 bytes each; every number of
    the catalog's config under its key but the reduced ones; the step's
    count by part."""
    import numpy as np

    config, traffic = real_cell()
    family = harness.load_family(config)
    n = sum(int(np.prod(s)) for _, s, _, _ in family.weight_spec(config))
    assert n == 602449792
    assert round(n * 16 / 1e9, 2) == 9.64
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * 32 == config["published"]["num_experts"]
    lin = config["linear_attn_config"]
    assert lin["kda_layers"][:4] == [1, 2, 3, 5]
    assert lin["full_attn_layers"][0] == 4
    for key, value in {
            "hidden_size": 2304, "num_attention_heads": 32,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "kv_lora_rank": 512,
            "intermediate_size": 9216, "moe_intermediate_size": 1024,
            "num_experts_per_token": 8, "num_shared_experts": 1,
            "routed_scaling_factor": 2.446, "mla_use_nope": True,
            "first_k_dense_replace": 1, "rms_norm_eps": 1e-5}.items():
        assert config[key] == value, key
    macs = family.macs_per_token(config, traffic)
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 3 * 4096 * 4 + 32 * (64 * 128 + 64 * 128 + 3 * 128 * 128
                                  + 64 * 64))
    assert macs["kda"] == 4 * kda
    assert macs["attention"] == (2304 * 32 * 192 + 2304 * 576
                                 + 512 * 32 * 256 + 32 * 128 * 2304
                                 + 32 * 320 * 4096)
    assert macs["dense"] == 3 * 2304 * 9216
    assert macs["routed"] == 4 * (2304 * 256 + 3 * 2304 * 1024
                                  + 8 * 8 / 256 * 3 * 2304 * 1024)
    assert math.isclose(family.flops_per_step(config, traffic), 19.004e12,
                        rel_tol=1e-3)


def test_kernel_needs_against_a_hand_count():
    """The per-channel rule at the published widths: 4 KDA layers of 32
    heads of 128 over 8,192 positions, forward twice and backward once;
    bound by memory."""
    config, traffic = real_cell()
    peaks = harness.load_json("peaks.json")["peaks"]["TPU v5 lite"]
    need = importlib.import_module("benchmark.kernels.kda_scan").needs(
        config, traffic)
    assert need["calls_per_step"] == 12
    per_token = 32 * (2 * 64 * 128 / 2 + 64 * 256 / 2 + 3 * 128 * 128
                      + 64 * 128 / 2)
    assert need["flops"] == 4 * 8192 * 2 * per_token * 4
    tokens, states = 8192, 128 * 32 * 128 * 128 * 4
    io = 2 * 4096 * 2 + 4096 * 2 + 4096 * 4 + 32 * 4      # q k, v, g, beta
    out = 4096 * 2
    assert need["bytes"] == 4 * (2 * tokens * (io + out) + states
                                 + tokens * (io + out) + states + tokens * io)
    assert round(need["flops"] / 1e12, 3) == 0.584
    assert round(need["bytes"] / 1e9, 2) == 8.34
    assert (need["bytes"] / peaks["hbm_bytes_per_s"]
            > need["flops"] / peaks["bf16_flops"])


def test_the_new_metrics_name_readers_that_exist():
    ctx = {"trace": None, "config": real_cell()[0],
           "traffic": real_cell()[1]}
    for name in NEW_METRICS:
        spec = harness.load_json("metrics", name + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        # read nothing, without raising, where there is no trace
        assert reader.read(ctx, **spec["args"]) is None


def test_the_new_cell_and_its_metrics_are_entries_that_report_it():
    """The cell in every list the issue names and in no other; the two
    new metrics its own, at the end of the per-layer list."""
    bm = benchmark_json()
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells[NEW_CELL]["chips"] == 1
    assert cells[NEW_CELL]["config"] == REAL[0]
    assert REAL[0] in {c["name"] for c in bm["configs"]}
    names = [m["name"] for m in bm["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 2] == list(NEW_METRICS)
    lists = {m["name"]: m["workloads"]
             for m in bm["end_to_end"] + bm["per_layer"] if "workloads" in m}
    for name, listed in lists.items():
        if name.endswith(".tok") or name in (
                "setup_compile_s", "train_tokens_per_s", "gdn_scan_share_pct",
                "gdn_inverse_ms", "gdn_local_ms", "gdn_walk_ms",
                "gdn_groups_ms", "flash_attn_share_pct", "mla_attn_share_pct",
                "attn_backward_ms", "moe_experts_share_pct",
                "moe_load_max_over_mean", "moe_experts_move_ms"):
            assert listed[-1] == NEW_CELL, name
        elif name in NEW_METRICS:
            assert listed == [NEW_CELL], name
        else:
            assert NEW_CELL not in listed, name
    cell = harness.load_json("workloads", NEW_CELL + ".json")
    assert cell["parallel"] == {} and cell["chips"] == 1
    assert cell["config"] == REAL[0] and cell["traffic"] == REAL[1]
