"""What the flash-attention kernels of one training step need, from the
shapes alone: floating-point operations (a multiply-add counted as two)
and bytes to and from device memory.

A layer's step calls the forward kernel (twice where the layer is
recomputed in the backward pass: each call is kernel work and is
counted), and the backward kernels once. Counted is what the algorithm
needs, not what an implementation repeats: the forward pass two matrix
products per head (Q K^T and P V, 4 S^2 D operations), the backward pass
five (S again, dP, dV, dK, dQ: 10 S^2 D), though the library splits it
into a dK/dV and a dQ kernel that each form S and dP (14 S^2 D done).
So the share of the roofline cannot pass 100 % by counting. Bytes: the
forward pass reads Q, K, V and writes O; the backward pass reads Q, K,
V, O, dO and writes dQ, dK, dV; the row statistics are S/D of that and
left out. Softmax's exponentials are not counted."""


#: the encoder is a scan with per-layer recompute: the forward kernel
#: runs again in the backward pass
FORWARD_CALLS = 2


def needs(config, traffic):
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    h = int(config["num_attention_heads"])
    d = int(config["hidden_size"]) // h
    layers = int(config["num_hidden_layers"])
    tensor = b * h * s * d                  # elements of one of Q, K, V, O
    forward_calls = FORWARD_CALLS
    flops = layers * b * h * s * s * d * (4 * forward_calls + 10)
    bytes_ = layers * tensor * 2 * (4 * forward_calls + 8)   # bfloat16
    return {"flops": float(flops), "bytes": float(bytes_),
            "calls_per_step": layers * (forward_calls + 2)}
