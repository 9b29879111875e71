"""Pretraining a gated-delta-rule / gated-attention / routed-expert
decoder as a training user writes it against the library's public API:
`models.qwen3_next.qwen3_next_loss` (an unrolled stack, every mixer and
every routed layer recomputed in the backward pass), Adam under bf16
AMP, `Executor.run(feed=..., fetch_list=...)` every step. The
configuration states the chip's share of the deployment (how many chips
share a layer's experts and which of them this one is:
`parallel.planner.experts_held` gives the program its share; the
reference reads the first expert held from the same file).

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`, the delta rule's `dt_bias` and `A_log`
spread onto their published ranges) and the batches; the plain
reference is given the same, and nothing the program made."""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.families import bert as bert_family
# one document a sequence, ids uniform over the vocabulary held, labels
# the ids shifted by one; a step's units are its tokens
from benchmark.families.nemotron_h import make_ring, units_per_step  # noqa: F401
from benchmark.kernels import gated_delta_rule as delta_rule_needs
from benchmark.reference import common
from benchmark.reference import qwen3_next as ref

#: the counters a step's fetch carries beside the loss, in this order
GAUGES = ("moe.held_pairs", "moe.load_max_over_mean", "moe.rows_made")
#: what every step of this process fetched of them, a value a step
#: (`readers/program_gauge.py`)
FETCHED = {name: [] for name in GAUGES}


def weight_spec(config):
    return ref.param_spec(config)


def make_weights(config, seed):
    return ref.spread_decay_init(
        harness.make_weights(weight_spec(config), seed))


def macs_per_token(config, traffic):
    """Multiply-adds the forward pass needs for one token, by part
    ({"delta", "attention", "routed", "head"}), from the shapes: every
    matrix product at the rows it meets. A Gated DeltaNet mixer: its
    three projections, the convolution's taps and the delta rule in its
    chunked form (`kernels/gated_delta_rule.py` says what of it).
    Gated attention: its four projections (the query's twice as wide:
    the gate) and the causal half of Q K^T and P V at the sequence's
    length. A routed layer: the router over all experts, the gated
    shared expert and its gate, and the held experts at the pairs a
    uniform routing sends them (tokens x top-k x held / experts). The
    embedding lookup is no product."""
    h = int(config["hidden_size"])
    hk, hv = (int(config["linear_num_key_heads"]),
              int(config["linear_num_value_heads"]))
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    key_dim, value_dim = hk * dk, hv * dv
    nq, nkv, d = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]), int(config["head_dim"]))
    f, fs = (int(config["moe_intermediate_size"]),
             int(config["shared_expert_intermediate_size"]))
    routed = int(config["published"]["num_experts"])
    held, k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    s, n = int(traffic["seq_len"]), int(config["num_hidden_layers"])
    n_attention = n // int(config["full_attention_interval"])
    return {
        "delta": (n - n_attention) * (
            h * (2 * key_dim + 2 * value_dim + 2 * hv) + value_dim * h
            + (2 * key_dim + value_dim) * int(
                config["linear_conv_kernel_dim"])
            + delta_rule_needs.forward_macs_per_token(config)),
        "attention": n_attention * (
            h * (2 * nq + 2 * nkv) * d + nq * d * h + 2 * nq * d * s / 2),
        "routed": n * (h * routed + 3 * h * fs + h
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
    program, the weights and what a step fetches are this family's."""

    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import qwen3_next
        from paddle_tpu.parallel import planner

        config, recipe, dep = self.config, self.recipe, self.config[
            "deployment"]
        keys = ("vocab_size", "hidden_size", "num_hidden_layers",
                "full_attention_interval", "num_attention_heads",
                "num_key_value_heads", "head_dim", "partial_rotary_factor",
                "rope_theta", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "norm_topk_prob",
                "rms_norm_eps", "initializer_range")
        cfg = qwen3_next.Qwen3NextConfig(
            num_experts=config["published"]["num_experts"],
            experts_held=planner.experts_held(
                config["published"]["num_experts"],
                dep["expert_parallel"], dep["expert_parallel_rank"]),
            **{k: config[k] for k in keys})
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                blocks = []
                loss, counters, _ = qwen3_next.qwen3_next_loss(
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

    def _lay_weights(self):
        for name, w in make_weights(self.config, self.seed).items():
            live = self.scope.find_var(name)
            if name in self.masters:
                self.scope.set_var(self.masters[name], w)
            self.scope.set_var(name, w.astype(live.dtype))

    def change_norms(self):
        now = self._shaped({leaf: self._state_name(leaf)
                            for leaf, _, _, _ in self.spec})
        return {k: float(v) for k, v in common.diff_norms(
            now, make_weights(self.config, self.seed)).items()}

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
    return ref.train(make_weights(config, seed), batches, config,
                     config["recipe"], quant=quant, keep=keep,
                     adam_ahead=adam_ahead)
