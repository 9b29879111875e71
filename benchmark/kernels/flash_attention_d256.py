"""What the flash-attention kernels of one training step need in a
decoder whose every `full_attention_interval`-th layer is causal
grouped-query attention (16 query heads on 2 key/value heads of 256 in
the configuration that brought this file), from the shapes alone:
floating-point operations (a multiply-add counted as two) and bytes to
and from device memory. The count is `kernels/flash_attention_gqa.py`'s,
which finds its layers in a pattern string this configuration does not
have: a layer's step calls the forward kernel twice (the layer is
recomputed in the backward pass) and the backward kernels once; counted
is the causal half of the square (S^2 / 2 pairs of positions a query
head), two matrix products a pair forward and five backward. Bytes: Q,
O, dO and dQ have a query head's count, K, V, dK and dV a key/value
head's; the forward pass reads Q, K, V and writes O, the backward pass
reads Q, K, V, O, dO and writes dQ, dK, dV. Left out: what the kernels
compute above the diagonal in the blocks it crosses, dK and dV written
a query head and summed outside, softmax's exponentials and the row
statistics; the rotary embedding, the norms of q and k and the output
gate are other op types."""

#: the layer is recomputed in the backward pass: the forward kernel
#: runs again
FORWARD_CALLS = 2


def needs(config, traffic):
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    nq, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    d = int(config["head_dim"])
    layers = int(config["num_hidden_layers"]) // int(
        config["full_attention_interval"])
    pairs = b * nq * s * s / 2            # causal: half of the square
    tensor = b * s * d * 2                # one head's Q, K, V or O: bf16
    return {"flops": float(layers * pairs * d * 2 * (2 * FORWARD_CALLS + 5)),
            "bytes": float(layers * tensor * (
                FORWARD_CALLS * (2 * nq + 2 * nkv) + 4 * nq + 4 * nkv)),
            "calls_per_step": layers * (FORWARD_CALLS + 2)}
