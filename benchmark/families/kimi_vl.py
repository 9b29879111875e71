"""Pretraining a latent-attention / routed-expert decoder (the language
model of Kimi-VL-A3B-Instruct) as a training user writes it against the
library's public API: `models.kimi_vl.kimi_vl_loss` (an unrolled stack
of a leading dense layer and routed layers, every attention mixer and
every feed-forward recomputed in the backward pass), Adam under bf16
AMP, `Executor.run(feed=..., fetch_list=...)` every step. The
configuration states the chip's share of the deployment (how many chips
share a layer's experts and which of them this one is:
`parallel.planner.experts_held` gives the program its share; the
reference reads the first expert held from the same file).

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`) and the batches; the plain reference is
given the same, and nothing the program made."""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.families import bert as bert_family
# one document a sequence, ids uniform over the vocabulary held, labels
# the ids shifted by one; a step's units are its tokens
from benchmark.families.nemotron_h import make_ring, units_per_step  # noqa: F401
from benchmark.reference import kimi_vl as ref

#: the counters a step's fetch carries beside the loss, in this order
GAUGES = ("moe.held_pairs", "moe.load_max_over_mean", "moe.rows_made")
#: what every step of this process fetched of them, a value a step
#: (`readers/program_gauge.py`, `kernels/moe_experts_kimi_vl.py`)
FETCHED = {name: [] for name in GAUGES}


def weight_spec(config):
    return ref.param_spec(config)


def macs_per_token(config, traffic):
    """Multiply-adds the forward pass needs for one token, by part
    ({"attention", "dense", "routed", "head"}), from the shapes: every
    matrix product at the rows it meets. Latent attention: the query's
    projection, the compression to the latent and the rotary key, the
    decompression to every head's key and value, the output projection
    and the causal half of Q K^T (heads of nope + rope) and of P V
    (heads of `v_head_dim`) at the sequence's length. A leading dense
    layer: its SwiGLU's three matrices. A routed layer: the router over
    all experts, the shared experts and the held experts at the pairs a
    uniform routing sends them (tokens x top-k x held / experts). The
    embedding lookup is no product."""
    h, nq = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr, dv = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]), int(config["v_head_dim"]))
    rank, f = int(config["kv_lora_rank"]), int(
        config["moe_intermediate_size"])
    fs = int(config["n_shared_experts"]) * f
    routed = int(config["published"]["n_routed_experts"])
    held, k = int(config["n_routed_experts"]), int(
        config["num_experts_per_tok"])
    s, n = int(traffic["seq_len"]), int(config["num_hidden_layers"])
    n_dense = min(n, int(config["first_k_dense_replace"]))
    return {
        "attention": n * (
            h * nq * (dn + dr) + h * (rank + dr) + rank * nq * (dn + dv)
            + nq * dv * h + nq * (dn + dr + dv) * s / 2),
        "dense": n_dense * 3 * h * int(config["intermediate_size"]),
        "routed": (n - n_dense) * (h * routed + 3 * h * fs
                                   + (k * held / routed) * 3 * h * f),
        "head": h * int(config["vocab_size"]),
    }


def flops_per_step(config, traffic):
    """Floating-point operations one training step NEEDS: forward and
    backward (two products for each forward one), recompute not
    counted, a multiply-add counted as two."""
    return 3.0 * 2.0 * units_per_step(config, traffic) * sum(
        macs_per_token(config, traffic).values())


class Job(bert_family.Job):
    """Adam's start and the first gradient's place are BERT's; the
    program and what a step fetches are this family's."""

    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import kimi_vl
        from paddle_tpu.parallel import planner

        config, recipe, dep = self.config, self.recipe, self.config[
            "deployment"]
        keys = ("vocab_size", "hidden_size", "num_hidden_layers",
                "first_k_dense_replace", "num_attention_heads",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "kv_lora_rank", "rope_theta", "intermediate_size",
                "moe_intermediate_size", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor",
                "norm_topk_prob", "rms_norm_eps", "initializer_range")
        cfg = kimi_vl.KimiVLConfig(
            n_routed_experts=config["published"]["n_routed_experts"],
            experts_held=planner.experts_held(
                config["published"]["n_routed_experts"],
                dep["expert_parallel"], dep["expert_parallel_rank"]),
            **{k: config[k] for k in keys})
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                blocks = []
                loss, counters, _ = kimi_vl.kimi_vl_loss(
                    cfg, int(self.traffic["seq_len"]),
                    checkpoints_out=blocks)
                # one fetch a step: the loss with the step's counters
                # behind it, read one step late like it
                fetched = fluid.layers.concat(
                    [fluid.layers.reshape(v, [1])
                     for v in [loss] + [counters[g] for g in GAUGES]])
                opt = fluid.optimizer.RecomputeOptimizer(
                    mixed_precision.decorate(
                        fluid.optimizer.AdamOptimizer(
                            learning_rate=float(recipe["learning_rate"]),
                            beta1=float(recipe["beta1"]),
                            beta2=float(recipe["beta2"]),
                            epsilon=float(recipe["epsilon"])),
                        use_dynamic_loss_scaling=False,
                        amp_dtype="bfloat16"))
                opt._set_checkpoints(blocks)
                opt.minimize(loss)
        return main_p, startup_p, fetched

    def weight_spec(self):
        return weight_spec(self.config)

    @staticmethod
    def loss_value(handle):
        loss, *counters = np.asarray(handle, dtype=np.float64).reshape(-1)
        for name, value in zip(GAUGES, counters):
            FETCHED[name].append(float(value))
        return float(loss)


build = Job


def reference(config, traffic, cell, seed, batches, quant=None, keep=None,
              adam_ahead=0):
    """The plain reference over the same weights and batches, with the
    same share of the experts. It takes the weights over: they are made
    anew for it. The half-batch fault (`keep`, a slice of sequences) of
    a batch of one document keeps the first half of its positions."""
    if keep is not None and int(traffic["batch"]) == 1:
        keep = int(traffic["seq_len"]) // 2
    return ref.train(harness.make_weights(weight_spec(config), seed),
                     batches, config, config["recipe"], quant=quant,
                     keep=keep, adam_ahead=adam_ahead)
