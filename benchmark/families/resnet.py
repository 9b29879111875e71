"""ResNet50 image classification as a training user writes it against
the library's public API: `models.resnet.resnet` (stages unrolled),
softmax cross-entropy, momentum under bf16 AMP, `Executor.run(feed=...,
fetch_list=...)` every step. The recipe is copied from
`bench.build_resnet_train_program` so that a later PR cannot change what
is timed.

The benchmark, not the program, makes the weights (one jitted call from
--seed, `harness.make_weights`) and the batches; the plain reference is
given the same, and nothing the program made."""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.families.fluid_job import FluidTrainJob
from benchmark.reference import resnet as ref


def units_per_step(config, traffic):
    return int(traffic["batch"])


def flops_per_step(config, traffic):
    """Floating-point operations one training step NEEDS (forward and
    backward), from the shapes, a multiply-add counted as two: every
    convolution's out_channels x in_channels x kernel^2 products at each
    of its output positions, and the dense layer's, times three (the
    backward pass is two products for each forward one). Batch norm,
    ReLU, pooling and the residual adds are memory passes and are not
    counted. (`bench._bench_resnet` writes 3 x 4.1e9 an image, which is
    the multiply-adds, half of this count.)"""
    macs = sum(cout * cin * k * k * side * side
               for _, cout, cin, k, _, side in ref.conv_table(config))
    macs += (int(config["stage_widths"][-1]) * int(config["expansion"])
             * int(config["num_classes"]))
    return 3.0 * 2.0 * macs * int(traffic["batch"])


def make_ring(config, traffic, seed):
    """`ring` host batches from the seed: float32 images of unit normal
    pixels, a label a row. Every row differs."""
    b, side = int(traffic["batch"]), int(config["image_size"])
    ring = []
    for i in range(int(traffic["ring"])):
        r = np.random.default_rng([int(seed), i])
        ring.append({
            "image": r.standard_normal((b, 3, side, side), dtype=np.float32),
            "label": r.integers(0, int(config["num_classes"]), (b, 1),
                                dtype=np.int64),
        })
    return ring


class Job(FluidTrainJob):
    ref = ref

    def build_program(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import resnet

        config, recipe = self.config, self.recipe
        side = int(config["image_size"])
        main_p, startup_p = self.fresh_programs()
        with framework.program_guard(main_p, startup_p):
            with framework.unique_name_guard():
                img = fluid.layers.data("image", shape=[3, side, side],
                                        dtype="float32")
                label = fluid.layers.data("label", shape=[1], dtype="int64")
                logits = resnet.resnet(
                    img, class_dim=int(config["num_classes"]),
                    depth=int(config["depth"]))
                loss = fluid.layers.mean(
                    fluid.layers.loss.softmax_with_cross_entropy(logits,
                                                                 label))
                opt = mixed_precision.decorate(
                    fluid.optimizer.MomentumOptimizer(
                        float(recipe["learning_rate"]),
                        momentum=float(recipe["momentum"])),
                    use_dynamic_loss_scaling=False,
                    amp_dtype="bfloat16")
                opt.minimize(loss)
        return main_p, startup_p, loss

    def weight_spec(self):
        return ref.param_spec(self.config)

    def first_gradient_state(self, main_p):
        """The velocity after one step from nought is the first
        gradient."""
        velocity = {op.input("Param")[0]: op.input("Velocity")[0]
                    for op in main_p.global_block().ops
                    if op.type == "momentum"}
        return velocity, 1.0


build = Job


def reference(config, traffic, cell, seed, batches, quant=None, keep=None):
    """The plain reference over the same weights and batches."""
    weights = harness.make_weights(ref.param_spec(config), seed)
    return ref.train(weights, batches, config, config["recipe"],
                     quant=quant, keep=keep)
