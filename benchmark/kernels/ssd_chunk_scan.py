"""What the state-space scan's forward kernel (`ssd_chunk_scan_fwd`,
`paddle_tpu/ops/pallas/ssd_scan.py`) needs in one training step, from
the shapes alone: floating-point operations (a multiply-add counted as
two) and bytes to and from device memory.

A Mamba-2 layer's step calls the kernel twice: in the forward pass and
again where the block is recomputed in the backward pass (each call is
kernel work and is counted). The backward pass itself runs no kernel
yet (it is `jax.numpy`), so nothing of it is counted here. Counted is
what the chunked algorithm needs, not what an implementation repeats:
in a chunk of L positions the causal half of C B^T (L^2 N / 2
multiply-adds a group) and of its product with the inputs (L^2 P / 2 a
head), the chunk's state (L N P a head) and what the state at the
chunk's start hands to each position (L N P a head); the kernel forms
the whole L x L square and masks it. Bytes: it reads the scaled inputs
and B and C once and the decays' sums twice (a column and a row
layout, float32), and writes y. The exponentials are not counted."""

#: the block is recomputed in the backward pass: the kernel runs again
FORWARD_CALLS = 2


def needs(config, traffic):
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    heads, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    chunk = int(config["chunk_size"])
    layers = config["hybrid_override_pattern"].count("M")
    tokens = b * s
    macs = tokens * (g * chunk * n / 2 + heads * (chunk * p / 2 + 2 * n * p))
    bytes_ = tokens * (2 * heads * p * 2          # x in, y out: bfloat16
                       + 2 * g * n * 2            # B and C
                       + 2 * heads * 4)           # the sums, twice, float32
    calls = layers * FORWARD_CALLS
    return {"flops": 2.0 * macs * calls, "bytes": float(bytes_ * calls),
            "calls_per_step": calls}
