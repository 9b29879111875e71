"""What the grouped products of the held gated (SwiGLU) experts need in
one training step of the `kimi_vl` family (`moe_experts_gmm`,
`moe_experts_tgmm`: `paddle_tpu/ops/hybrid_ops.py` over the Pallas
grouped matrix product). The count is `kernels/moe_experts_gated.py`'s
(seven grouped products a layer at `WUp [E, H, 2F]`, 11 rows H F
multiply-adds, each matrix read once a product, the float32 gradients
written once: that file says why), under this configuration's keys: the
routed layers are those after the `first_k_dense_replace` leading ones,
`n_routed_experts` are held of `published.n_routed_experts`, and the
rows are the pairs this family's steps counted (`moe.held_pairs`,
fetched with the loss), the mean over the ring's last turn; where no
step has run, what a uniform routing sends."""
from benchmark.kernels.moe_experts_gated import PRODUCTS_HF


def routed_layers(config):
    n = int(config["num_hidden_layers"])
    return n - min(n, int(config["first_k_dense_replace"]))


def rows_per_step(config, traffic):
    """Pairs the held experts compute in a step, over all routed
    layers: counted by the steps run in this process, else expected."""
    from benchmark.families import kimi_vl as family

    seen = family.FETCHED["moe.held_pairs"][-int(traffic["ring"]):]
    if seen:
        return sum(seen) / len(seen)
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    return (routed_layers(config) * tokens
            * int(config["num_experts_per_tok"])
            * int(config["n_routed_experts"])
            / int(config["published"]["n_routed_experts"]))


def needs(config, traffic):
    h, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    layers, held = routed_layers(config), int(config["n_routed_experts"])
    rows = rows_per_step(config, traffic)
    matrix = layers * held * h * f            # elements of one [E, H, F]
    # up, up again and through WUp: the wide matrix, rows of H and of 2F;
    # down and through W_down: the narrow one, rows of F and of H
    read_once = 2 * (3 * (2 * matrix + rows * (h + 2 * f))
                     + 2 * (matrix + rows * (f + h)))         # bfloat16
    gradients = 2 * rows * ((h + 2 * f) + (f + h)) + 4 * 3 * matrix
    return {"flops": 2.0 * rows * h * f * PRODUCTS_HF,
            "bytes": float(read_once + gradients),
            "calls_per_step": layers * 7}
