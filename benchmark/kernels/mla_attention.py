"""What the latent attention of one training step needs where it is
computed from decompressed keys and values (`scaled_dot_product_attention`
in `paddle_tpu/models/kimi_vl.py`: every layer, causal, 16 heads whose
queries and keys are `qk_nope_head_dim + qk_rope_head_dim` wide and
whose values are `v_head_dim` wide), from the shapes alone:
floating-point operations (a multiply-add counted as two) and bytes to
and from device memory. The metric that reads this is by op type, so
whatever implements the op is held to the same need.

A layer's step runs the forward pass twice (the layer is recomputed in
the backward pass) and the backward pass once; counted is the causal
half of the square (S^2 / 2 pairs of positions a head). A pair costs
two products forward (Q K^T at the key's width, P V at the value's) and
five backward (the scores again, dP = dO V^T and dV = P^T dO at the
value's width, dQ = dS K and dK = dS^T Q at the key's). Bytes: the
forward pass reads Q, K, V and writes O, the backward pass reads Q, K,
V, O, dO and writes dQ, dK, dV, each at bfloat16; K's rotary part is
ONE head that all the query heads read and is counted once a layer,
not once a head, with its gradient: an implementation that joins it to
every head's key in memory pays for that in its share. Left out: what
kernels compute above the diagonal in the blocks it crosses, softmax's
exponentials and the row statistics; the projections, the latent's
norm, the rotary embedding and the joins are other op types."""

#: the layer is recomputed in the backward pass: the forward pass runs
#: again
FORWARD_CALLS = 2


def needs(config, traffic):
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    nq = int(config["num_attention_heads"])
    dn, dr, dv = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]), int(config["v_head_dim"]))
    layers = int(config["num_hidden_layers"])
    pairs = b * nq * s * s / 2            # causal: half of the square
    dk = dn + dr
    forward, backward = 2 * (dk + dv), 2 * (3 * dk + 2 * dv)
    row = 2 * b * s                       # one column of a tensor: bf16
    # Q | K (each head's unrotated part, the one rotary head) | V | O
    tensors = row * (nq * dk + (nq * dn + dr) + nq * dv + nq * dv)
    return {"flops": float(layers * pairs * (FORWARD_CALLS * forward
                                             + backward)),
            # forward: Q K V in, O out; backward: those and dO in, dQ dK
            # dV out
            "bytes": float(layers * tensors * (FORWARD_CALLS + 2)),
            "calls_per_step": layers * (FORWARD_CALLS + 2)}
