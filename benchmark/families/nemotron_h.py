"""Pretraining a hybrid state-space / routed-expert decoder as a
training user writes it against the library's public API:
`models.nemotron_h.nemotron_h_loss` (an unrolled stack of Mamba-2,
attention and routed-expert blocks, every block recomputed in the
backward pass), Adam under bf16 AMP, `Executor.run(feed=...,
fetch_list=...)` every step. The configuration states the chip's share
of the deployment (how many chips share a layer's experts and which of
them this one is: `parallel.planner.experts_held` gives the program its
share; the reference reads the first expert held from the same file).

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`, the scan's `dt_bias` and `A_log` spread
onto their published ranges) and the batches; the plain reference is
given the same, and nothing the program made."""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.families import bert as bert_family
from benchmark.reference import common
from benchmark.reference import nemotron_h as ref

#: the counters a step's fetch carries beside the loss, in this order
GAUGES = ("moe.held_pairs", "moe.load_max_over_mean")
#: what every step of this process fetched of them, a value a step
#: (`readers/program_gauge.py`, `kernels/moe_experts.py`)
FETCHED = {name: [] for name in GAUGES}


def weight_spec(config):
    return ref.param_spec(config)


def make_weights(config, seed):
    return ref.spread_ssm_init(
        harness.make_weights(weight_spec(config), seed))


def units_per_step(config, traffic):
    return int(traffic["batch"]) * int(traffic["seq_len"])


def macs_per_token(config, traffic):
    """Multiply-adds the forward pass needs for one token, by kind of
    layer ({"M", "*", "E", "head"}), from the shapes: every matrix
    product at the rows it meets. A Mamba-2 layer: its two projections,
    the convolution's taps and the scan in its chunked form (inside a
    chunk the causal half of C B^T, shared by a group's heads, and of
    its product with x; the chunk's state and what the state hands to
    each position). Attention: its four projections and the causal half
    of Q K^T and P V at the sequence's length. A routed layer: the
    router over all experts, the shared expert, and the held experts at
    the pairs a uniform routing sends them (tokens x top-k x held /
    experts). The embedding lookup is no product."""
    h = int(config["hidden_size"])
    heads, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    chunk, inner = int(config["chunk_size"]), heads * p
    conv_dim = inner + 2 * g * n
    nq, nkv, d = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]), int(config["head_dim"]))
    f, fs = (int(config["moe_intermediate_size"]),
             int(config["moe_shared_expert_intermediate_size"]))
    routed = int(config["published"]["n_routed_experts"])
    held, k = int(config["n_routed_experts"]), int(
        config["num_experts_per_tok"])
    s = int(traffic["seq_len"])
    scan = g * chunk * n / 2 + heads * (chunk * p / 2 + 2 * n * p)
    per_kind = {
        "M": h * (inner + conv_dim + heads) + inner * h
        + conv_dim * int(config["conv_kernel"]) + scan,
        "*": h * (nq + 2 * nkv) * d + nq * d * h + 2 * nq * d * s / 2,
        "E": h * routed + 2 * h * fs + (k * held / routed) * 2 * h * f,
    }
    out = {kind: per_kind[kind] * config["hybrid_override_pattern"].count(
        kind) for kind in per_kind}
    out["head"] = h * int(config["vocab_size"])
    return out


def flops_per_step(config, traffic):
    """Floating-point operations one training step NEEDS: forward and
    backward (two products for each forward one), recompute not
    counted, a multiply-add counted as two."""
    return 3.0 * 2.0 * units_per_step(config, traffic) * sum(
        macs_per_token(config, traffic).values())


def make_ring(config, traffic, seed):
    """`ring` host batches from the seed: one document a sequence, ids
    uniform over the vocabulary held, labels the ids shifted by one."""
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    ring = []
    for i in range(int(traffic["ring"])):
        r = np.random.default_rng([int(seed), i])
        ids = r.integers(0, int(config["vocab_size"]), (b, s + 1),
                         dtype=np.int64)
        ring.append({"ids": np.ascontiguousarray(ids[:, :-1]),
                     "labels": np.ascontiguousarray(ids[:, 1:])})
    return ring


class Job(bert_family.Job):
    """Adam's start and the first gradient's place are BERT's; the
    program, the weights and what a step fetches are this family's."""

    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import nemotron_h
        from paddle_tpu.parallel import planner

        config, recipe, dep = self.config, self.recipe, self.config[
            "deployment"]
        keys = ("vocab_size", "hidden_size", "hybrid_override_pattern",
                "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                "n_groups", "conv_kernel", "chunk_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "norm_topk_prob",
                "layer_norm_epsilon", "initializer_range")
        cfg = nemotron_h.NemotronHConfig(
            n_routed_experts=config["published"]["n_routed_experts"],
            experts_held=planner.experts_held(
                config["published"]["n_routed_experts"],
                dep["expert_parallel"], dep["expert_parallel_rank"]),
            **{k: config[k] for k in keys})
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                blocks = []
                loss, counters, _ = nemotron_h.nemotron_h_loss(
                    cfg, int(self.traffic["seq_len"]),
                    checkpoints_out=blocks)
                # one fetch a step: the loss with the step's counters
                # behind it, read one step late like it
                fetched = fluid.layers.concat(
                    [fluid.layers.reshape(v, [1])
                     for v in [loss] + [counters[g] for g in GAUGES
                                          if g in counters]])
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
    anew for it."""
    return ref.train(make_weights(config, seed), batches, config,
                     config["recipe"], quant=quant, keep=keep,
                     adam_ahead=adam_ahead)
