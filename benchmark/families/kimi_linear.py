"""Pretraining a delta-rule / latent-attention decoder with routed
experts (moonshotai's Kimi-Linear-48B-A3B) as a training user writes it
against the library's public API: `models.kimi_linear.kimi_linear_loss`
(an unrolled stack of a leading dense layer and routed layers, Kimi
Delta Attention and latent attention without position by
`linear_attn_config`, every mixer and every feed-forward recomputed in
the backward pass), Adam under bf16 AMP, `Executor.run(feed=...,
fetch_list=...)` every step. The configuration states the chip's share
of the deployment (`parallel.planner.experts_held` gives the program its
experts; the reference reads the first expert held from the same file).

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`, `A_log` spread onto the family's range)
and the batches; the plain reference is given the same, and nothing the
program made."""
from __future__ import annotations

from benchmark.families import bert as bert_family
# one document a sequence, ids uniform over the vocabulary held, labels
# the ids shifted by one; a step's units are its tokens
from benchmark.families.nemotron_h import make_ring, units_per_step  # noqa: F401
from benchmark import harness
from benchmark.kernels import kda_scan as kda_needs
from benchmark.reference import kimi_linear as ref

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
    ({"kda", "attention", "dense", "routed", "head"}), from the shapes:
    every matrix product at the rows it meets. A KDA mixer: its q, k, v
    and output projections, the two low-rank gates, the strength's
    projection, the convolutions' taps and the delta rule in its chunked
    form (`kernels/kda_scan.py` says what of it). Latent attention: the
    query's projection, the compression to the latent and the shared
    key part, the decompression to every head's key and value, the
    output projection and the causal half of Q K^T (heads of nope + rope)
    and of P V at the sequence's length. The dense layer: its SwiGLU's
    three matrices. A routed layer: the router over all experts, the
    shared expert and the held experts at the pairs a uniform routing
    sends them (tokens x top-k x held / experts). The embedding lookup
    is no product."""
    h, nq = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr, dv = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]), int(config["v_head_dim"]))
    rank, f = int(config["kv_lora_rank"]), int(config["moe_intermediate_size"])
    fs = int(config["num_shared_experts"]) * f
    routed = int(config["published"]["num_experts"])
    held, k = int(config["num_experts"]), int(config["num_experts_per_token"])
    lin = config["linear_attn_config"]
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    wide, taps = heads * d, int(lin["short_conv_kernel_size"])
    s, n = int(traffic["seq_len"]), int(config["num_hidden_layers"])
    kinds = [ref.layer_kind(config, i) for i in range(n)]
    n_dense = min(n, int(config["first_k_dense_replace"]))
    return {
        "kda": kinds.count("kda") * (
            4 * h * wide + 2 * (h * d + d * wide) + h * heads
            + 3 * wide * taps + kda_needs.forward_macs_per_token(config)),
        "attention": kinds.count("mla") * (
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
    program, the weights and what a step fetches are this family's."""

    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import kimi_linear
        from paddle_tpu.parallel import planner

        config, recipe, dep = self.config, self.recipe, self.config[
            "deployment"]
        keys = ("vocab_size", "hidden_size", "num_hidden_layers",
                "first_k_dense_replace", "num_attention_heads",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "kv_lora_rank", "mla_use_nope", "rope_theta",
                "intermediate_size", "moe_intermediate_size",
                "num_shared_experts", "num_experts_per_token",
                "routed_scaling_factor", "moe_renormalize", "rms_norm_eps",
                "linear_attn_config", "initializer_range")
        routed = config["published"]["num_experts"]
        cfg = kimi_linear.KimiLinearConfig(
            num_experts=routed,
            experts_held=planner.experts_held(
                routed, dep["expert_parallel"], dep["expert_parallel_rank"]),
            **{k: config[k] for k in keys})
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                blocks = []
                loss, counters, _ = kimi_linear.kimi_linear_loss(
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
        from benchmark.reference import common

        now = self._shaped({leaf: self._state_name(leaf)
                            for leaf, _, _, _ in self.spec})
        return {k: float(v) for k, v in common.diff_norms(
            now, make_weights(self.config, self.seed)).items()}

    @staticmethod
    def loss_value(handle):
        import numpy as np

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
