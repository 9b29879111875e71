"""What the grouped products of held gated (SwiGLU) experts need in one
training step (`moe_experts_gmm`, `moe_experts_tgmm`:
`paddle_tpu/ops/hybrid_ops.py` over the Pallas grouped matrix product),
where an expert is `W_down (silu(W_gate x) * W_up x)` with the gate's
and the up projection's matrices side by side, `WUp [E, H, 2F]`:
floating-point operations (a multiply-add counted as two) and bytes to
and from device memory. `kernels/moe_experts.py` counts two-matrix
experts under the nemotron configuration's keys.

The rows are the pairs routed to the experts held, which the routing
says and not the shapes: the step counts them (the op's `HeldPairs`,
summed over the routed layers, fetched with the loss), the family keeps
what it fetched, and the rows here are the mean over the ring's last
turn, the steps a traced run traces; where no step has run, what a
uniform routing sends (tokens x top-k x held / experts a layer). Seven
grouped products a layer, all kernel work and all counted: the forward
pass makes the up product (rows x H x 2F) and the down product (rows x
F x H); the backward pass makes the up product again, takes the
cotangent through `W_down` transposed (rows x H x F) and through `WUp`
transposed (rows x 2F x H), and sums the two matrices' gradients over
the rows: 11 rows H F multiply-adds. The layer's recompute in the
backward pass makes no product (the op's gradient keeps its inputs, so
the output made again is dead), and none is counted. Bytes: a product
reads its experts' matrices once and its rows in and writes its rows
out at bfloat16; a gradient's product reads its two sets of rows and
writes the matrices' float32 sums once. An implementation that walks
the rows in several trips reads the matrices and the sums again a trip:
its cost, not counted. Gathering the rows, the activation, the pairs'
weights and the scatter are outside the kernels: not counted."""

#: multiply-adds a row in units of H F: up 2 + down 1 forward; up again
#: 2, through W_down 1, through WUp 2, the two gradients 1 + 2 backward
PRODUCTS_HF = 11


def rows_per_step(config, traffic):
    """Pairs the held experts compute in a step, over all routed
    layers: counted by the steps run in this process, else expected."""
    from benchmark.families import qwen3_next as family

    seen = family.FETCHED["moe.held_pairs"][-int(traffic["ring"]):]
    if seen:
        return sum(seen) / len(seen)
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    return (int(config["num_hidden_layers"]) * tokens
            * int(config["num_experts_per_tok"])
            * int(config["num_experts"])
            / int(config["published"]["num_experts"]))


def needs(config, traffic):
    h, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    layers, held = int(config["num_hidden_layers"]), int(config["num_experts"])
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
