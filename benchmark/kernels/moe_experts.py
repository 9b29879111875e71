"""What the grouped products of the held experts (`moe_experts_gmm`,
`moe_experts_tgmm`: `paddle_tpu/ops/hybrid_ops.py` over the Pallas
grouped matrix product) need in one training step: floating-point
operations (a multiply-add counted as two) and bytes to and from device
memory.

A routed layer's forward pass is two grouped products (up and down)
over the pairs routed to the experts held. How many those are is the
routing's to say, not the shapes': the step counts them (the op's
`HeldPairs`, summed over the routed layers, fetched with the loss) and
the family keeps what it fetched, so the rows here are the mean over
the ring's last turn, the steps a traced run traces. Where no step has
run, they are what a uniform routing sends (tokens x top-k x held /
experts a layer, the number the family's count of the step uses).
The forward runs three times a step (the forward pass, the block's
recompute in the backward pass, and the token block's own recompute
inside that), the backward pass is four products (two for the rows'
gradient, two for the weights'): ten products of rows x H x F a layer,
all kernel work and all counted. Bytes a product: the experts' matrix
once (an implementation that reads it once a token block repeats
itself, and that is not counted), the rows in and out. Rows past the
pairs there are cost nothing and are not counted."""

#: forward, the block's recompute, the token block's recompute
FORWARD_RUNS = 3


def rows_per_step(config, traffic):
    """Pairs the held experts compute in a step, over all routed
    layers: counted by the steps run in this process, else expected."""
    from benchmark.families import nemotron_h as family

    seen = family.FETCHED["moe.held_pairs"][-int(traffic["ring"]):]
    if seen:
        return sum(seen) / len(seen)
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    return (config["hybrid_override_pattern"].count("E") * tokens
            * int(config["num_experts_per_tok"])
            * int(config["n_routed_experts"])
            / int(config["published"]["n_routed_experts"]))


def needs(config, traffic):
    h, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    held = int(config["n_routed_experts"])
    layers = config["hybrid_override_pattern"].count("E")
    rows = rows_per_step(config, traffic)
    products = 2 * FORWARD_RUNS + 4              # a layer
    bytes_ = 2 * (layers * held * h * f + rows * (h + f))     # bfloat16
    return {"flops": 2.0 * rows * h * f * products,
            "bytes": float(bytes_ * products),
            "calls_per_step": layers * products}
