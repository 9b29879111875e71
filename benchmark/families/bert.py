"""BERT pretraining as a training user writes it against the library's
public API: `models.bert.bert_pretrain_loss` (scanned encoder, per-layer
recompute), Adam under bf16 AMP, `Executor.run(feed=..., fetch_list=...)`
every step. The recipe is copied from `bench.build_bert_train_program`
so that a later PR cannot change what is timed.

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`) and the batches; the plain reference is
given the same, and nothing the program made."""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.reference import bert as ref
from benchmark.families.fluid_job import FluidTrainJob

#: rows of a batch the float32 reference holds at a time (tokens, so that
#: a long-sequence cell takes fewer rows)
REFERENCE_BLOCK_TOKENS = 8192


def max_positions(config, traffic):
    return max(int(config["max_position_embeddings"]),
               int(traffic["seq_len"]))


def weight_spec(config, traffic):
    std = float(config["initializer_range"])
    return [(n, s, k, std) for n, s, k in
            ref.param_spec(config, max_positions(config, traffic))]


def max_pred(traffic):
    return max(1, int(traffic["seq_len"] * traffic["masked_share"]))


def units_per_step(config, traffic):
    return int(traffic["batch"]) * int(traffic["seq_len"])


def flops_per_step(config, traffic):
    """Floating-point operations one training step NEEDS (forward and
    backward, recompute not counted), from the shapes, a multiply-add
    counted as two: every matrix product of the forward pass at the rows
    it is applied to, times three (the backward pass is two products for
    each forward one). The encoder's matrices meet every token, the
    masked-LM head only the masked positions, the pooler and the
    next-sentence head one row a sequence; embedding lookups are not
    products. (`bench._bert_flops_per_token` counts 6 x ALL parameters
    per token, embedding tables and the head's [H, V] matrix included:
    26.72 TFLOP at b256 s128 where this count gives 17.87.)"""
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    h, f = int(config["hidden_size"]), int(config["intermediate_size"])
    n, v = int(config["num_hidden_layers"]), int(config["vocab_size"])
    layer = h * 3 * h + h * h + 2 * h * f        # per token
    attention = 2 * s * h                        # per token: QK^T and PV
    macs = b * s * n * (layer + attention)
    macs += b * max_pred(traffic) * (h * h + h * v)
    macs += b * (h * h + 2 * h)
    return 3.0 * 2.0 * macs


def make_ring(config, traffic, seed):
    """`ring` host batches from the seed, every row different."""
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    v, p = int(config["vocab_size"]), max_pred(traffic)
    ring = []
    for i in range(int(traffic["ring"])):
        r = np.random.default_rng([int(seed), i])
        split = r.integers(1, s, (b, 1))
        ring.append({
            "src_ids": r.integers(0, v, (b, s), dtype=np.int64),
            "pos_ids": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
            "sent_ids": (np.arange(s)[None, :] >= split).astype(np.int64),
            "input_mask": np.ones((b, s), np.float32),
            "mask_pos": np.argsort(r.random((b, s)), axis=1)[:, :p]
            .astype(np.int64),
            "mask_label": r.integers(0, v, (b, p), dtype=np.int64),
            "mask_weight": np.ones((b, p), np.float32),
            "nsp_label": r.integers(0, 2, (b, 1), dtype=np.int64),
        })
    return ring


#: a first step within this share of lr x sign(g) is Adam's. The probe
#: steps at a learning rate of its own, not the recipe's: the share does
#: not depend on it, and float32 rounding of a 1e-3 step on a weight of
#: order one is 6e-5 of it where a 2e-6 step's would be 3e-2
ADAM_PROBE_TOL = 0.02
ADAM_PROBE_LR = 1e-3


def beta_pow_names(program):
    return [op.input(slot)[0] for op in program.global_block().ops
            if op.type == "adam" for slot in ("Beta1Pow", "Beta2Pow")]


def adam_first_step(exe, recipe, start=None):
    """The library's first Adam step on one weight whose gradient is 1,
    over the learning rate: 1 for Adam as published (m / sqrt(v) is
    sign(g) on the first step). `start` lays the beta-power
    accumulators there first. A program of its own, through the public
    API, in a scope of its own."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid import framework

    main_p, startup_p = framework.Program(), framework.Program()
    with framework.program_guard(main_p, startup_p):
        with framework.unique_name_guard():
            x = fluid.layers.data("probe_x", shape=[1], dtype="float32")
            w = fluid.layers.create_parameter([1], "float32", name="probe_w")
            loss = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(x, w))
            fluid.optimizer.AdamOptimizer(
                learning_rate=ADAM_PROBE_LR,
                beta1=float(recipe["beta1"]), beta2=float(recipe["beta2"]),
                epsilon=float(recipe["epsilon"])).minimize(loss)
    scope = Scope()
    exe.run(startup_p, scope=scope)
    if start is not None:
        for name in beta_pow_names(main_p):
            scope.set_var(name, scope.find_var(name) * 0 + start)

    def weight():
        return float(np.asarray(scope.find_var("probe_w")).reshape(()))

    before = weight()
    exe.run(main_p, feed={"probe_x": np.ones((1, 1), np.float32)},
            fetch_list=[loss], scope=scope)
    return (before - weight()) / ADAM_PROBE_LR


class Job(FluidTrainJob):
    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import bert

        config, recipe = self.config, self.recipe
        cfg = bert.BertConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            intermediate_size=config["intermediate_size"],
            max_position_embeddings=max_positions(config, self.traffic),
            type_vocab_size=config["type_vocab_size"],
            hidden_dropout_prob=config["hidden_dropout_prob"],
            attention_probs_dropout_prob=config[
                "attention_probs_dropout_prob"],
            initializer_range=config["initializer_range"])
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                total, _, _, _ = bert.bert_pretrain_loss(
                    cfg, int(self.traffic["seq_len"]), is_test=False,
                    scan_layers=True, scan_remat=True)
                opt = mixed_precision.decorate(
                    fluid.optimizer.AdamOptimizer(
                        learning_rate=float(recipe["learning_rate"]),
                        beta1=float(recipe["beta1"]),
                        beta2=float(recipe["beta2"]),
                        epsilon=float(recipe["epsilon"])),
                    use_dynamic_loss_scaling=False,
                    amp_dtype="bfloat16")
                opt.minimize(total)
        return main_p, startup_p, total

    def weight_spec(self):
        return weight_spec(self.config, self.traffic)

    def lay_state(self, main_p):
        """Adam's beta-power accumulators, started where the library's
        first step is Adam's as Kingma & Ba state it. Today the library
        starts them at beta and its `adam` op multiplies by beta once
        more before it corrects the bias, so step t is corrected as step
        t + 1 (PERF.md, Open questions): a probe step reads 0.744 of
        Adam's, and the accumulators are started at 1 instead, which the
        probe then reads as 1. Once the library's own start reads 1,
        whichever way it was mended, nothing is laid here. The
        configuration's file states this under `recipe`."""
        recipe = self.recipe
        if abs(adam_first_step(self.exe, recipe) - 1.0) <= ADAM_PROBE_TOL:
            return
        if abs(adam_first_step(self.exe, recipe, start=1.0) - 1.0) \
                > ADAM_PROBE_TOL:
            raise AssertionError(
                "the library's Adam takes a first step that is not Adam's "
                "from its own start nor from beta-powers of 1: the "
                "benchmark cannot say what it would be timing")
        for name in beta_pow_names(main_p):
            self.scope.set_var(name, self.scope.find_var(name) * 0 + 1)

    def first_gradient_state(self, main_p):
        """Adam's first moment after one step is (1 - beta1) times the
        first gradient."""
        moment1 = {op.input("Param")[0]: op.input("Moment1")[0]
                   for op in main_p.global_block().ops if op.type == "adam"}
        return moment1, 1.0 / (1.0 - float(self.recipe["beta1"]))


build = Job


def reference(config, traffic, cell, seed, batches, quant=None, keep=None,
              adam_ahead=0):
    """The plain reference over the same weights and batches.
    `adam_ahead=1` plants the library's Adam as it stands: the bias
    corrected one step ahead."""
    weights = harness.make_weights(weight_spec(config, traffic), seed)
    block_rows = max(1, REFERENCE_BLOCK_TOKENS // int(traffic["seq_len"]))
    return ref.train(weights, batches, config, config["recipe"],
                     block_rows, quant=quant, keep=keep,
                     adam_ahead=adam_ahead)
