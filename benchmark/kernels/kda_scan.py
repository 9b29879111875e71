"""What the delta rule with a decay per key channel of one training step
needs (the op type `gated_delta_rule` in a Kimi Delta Attention mixer:
Kimi-Linear's KDA layers, whatever implements the op), from the shapes
alone: floating-point operations (a multiply-add counted as two) and
bytes to and from device memory.

A layer's step makes the forward pass twice (the mixer is recomputed in
the backward pass: each pass is the op's work and is counted) and the
backward pass once, at two products for each forward one. Counted is
what the chunked algorithm needs at `CHUNK` positions a chunk, with a
head's q, k and v at one head each (`num_heads` heads of `head_dim`):
a token's multiply-adds in the forward pass are the strictly lower half
of the decayed K K^T and the causal half of the decayed Q K^T (`CHUNK`
dk / 2 each: however the decay is anchored, a pair is one product of
dk), the unit triangular solve against its dk + dv right-hand sides by
substitution (`CHUNK` (dk + dv) / 2), what the state at the chunk's start
hands to the corrections and to the outputs (2 dk dv), the corrections'
part of the outputs (`CHUNK` dv / 2) and of the chunk's last state
(dk dv). The decay's exponentials and scalings are not products and are
not counted; an implementation that forms the diagonal blocks channel
by channel, inverts in matrix products or makes the chunk-local values
again in its backward pass does more: that is its cost, not the need.
Bytes: a forward pass reads q, k, v (two bytes an element), the
log-decay (float32 a channel: as many values as k) and the writing
strength (float32 a head) and writes the outputs; one of the two also
writes the state at every chunk's start (float32), which the backward
pass reads beside the inputs and the outputs' cotangent before it
writes the five inputs' gradients.

At the `kimi-linear-48b-a3b-ep32` configuration under `b1-s8192` (4
KDA layers of 32 heads of 128, 8,192 positions): 0.584 TFLOP and
8.34 GB a step, bound by memory (10.2 ms at the v5e's 819 GB/s, 3.0 ms
of arithmetic at 197 TFLOP/s); twice both at 16,384."""

#: positions a chunk that the count assumes (arXiv:2510.26692)
CHUNK = 64
#: the mixer is recomputed in the backward pass: the forward runs again
FORWARD_CALLS = 2


def layers(config):
    """KDA layers among those built, counted from 1 in the configuration's
    `linear_attn_config`."""
    kda = config["linear_attn_config"]["kda_layers"]
    return sum(1 for i in range(1, int(config["num_hidden_layers"]) + 1)
               if i in kda)


def forward_macs_per_token(config):
    lin = config["linear_attn_config"]
    h, d = int(lin["num_heads"]), int(lin["head_dim"])
    return h * (2 * CHUNK * d / 2 + CHUNK * 2 * d / 2 + 3 * d * d
                + CHUNK * d / 2)


def needs(config, traffic):
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    lin = config["linear_attn_config"]
    h, d = int(lin["num_heads"]), int(lin["head_dim"])
    n = layers(config)
    flops = n * tokens * 2.0 * forward_macs_per_token(config) * (
        FORWARD_CALLS + 2)
    qk, v, gates = 2 * h * d * 2, h * d * 2, h * d * 4 + h * 4
    states = -(-tokens // CHUNK) * h * d * d * 4
    forward = tokens * (qk + v + gates + v)
    backward = tokens * (qk + v + gates + v) + states \
        + tokens * (qk + v + gates)
    bytes_ = n * (FORWARD_CALLS * forward + states + backward)
    return {"flops": float(flops), "bytes": float(bytes_),
            "calls_per_step": n * (FORWARD_CALLS + 1)}
