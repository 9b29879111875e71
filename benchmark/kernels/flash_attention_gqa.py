"""What the flash-attention kernels of one training step need where the
attention is causal and several query heads share a key/value head (the
`*` layers of a hybrid decoder), from the shapes alone: floating-point
operations (a multiply-add counted as two) and bytes to and from device
memory. `kernels/flash_attention.py` counts an encoder (every layer an
attention layer, the whole square, one key/value head a query head).

A layer's step calls the forward kernel twice (the block is recomputed
in the backward pass: each call is kernel work and is counted) and the
backward kernels once. Counted is what the algorithm needs, not what an
implementation repeats: the causal half of the square (S^2 / 2 pairs of
positions a query head), in the forward pass two matrix products a pair
(Q K^T and P V), in the backward pass five (S again, dP, dV, dK, dQ),
though the library splits it into a dK/dV and a dQ kernel that each
form S and dP and visit blocks above the diagonal. Bytes: Q, O, dO and
dQ have a query head's count, K, V, dK and dV a key/value head's (the
kernels write dK and dV a query head and the sum over a group is made
outside: not counted); the forward pass reads Q, K, V and writes O, the
backward pass reads Q, K, V, O, dO and writes dQ, dK, dV. Softmax's
exponentials and the row statistics are not counted."""

#: the block is recomputed in the backward pass: the forward kernel
#: runs again
FORWARD_CALLS = 2


def needs(config, traffic):
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    nq, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    d = int(config["head_dim"])
    layers = config["hybrid_override_pattern"].count("*")
    pairs = b * nq * s * s / 2            # causal: half of the square
    flops = layers * pairs * d * 2 * (2 * FORWARD_CALLS + 5)
    tensor = b * s * d * 2                # one head's Q, K, V or O: bf16
    bytes_ = layers * tensor * (FORWARD_CALLS * (2 * nq + 2 * nkv)
                                + 4 * nq + 4 * nkv)
    return {"flops": float(flops), "bytes": float(bytes_),
            "calls_per_step": layers * (FORWARD_CALLS + 2)}
