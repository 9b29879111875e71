"""What the gated delta rule of one training step needs (the op type
`gated_delta_rule`: a Gated DeltaNet mixer's state update, whatever
implements it), from the shapes alone: floating-point operations (a
multiply-add counted as two) and bytes to and from device memory.

A layer's step makes the forward pass twice (the layer is recomputed in
the backward pass: each pass is the op's work and is counted) and the
backward pass once, at two products for each forward one. Counted is
what the chunked algorithm needs at `CHUNK` positions a chunk, not what
an implementation repeats. A token's multiply-adds in the forward pass:
for a key head the strictly lower half of K K^T and the causal half of
Q K^T (`CHUNK` dk / 2 each); for a value head the unit triangular solve
against its dk + dv right-hand sides (beta exp(g) K and beta V) by
substitution (`CHUNK` (dk + dv) / 2), what the state at the chunk's
start hands to the corrections and to the outputs (2 dk dv), the
corrections' part of the outputs (`CHUNK` dv / 2) and of the chunk's
last state (dk dv). An implementation that inverts the system in
matrix products, forms whole squares and masks them, or makes the
chunk-local values a third time in its backward pass does more: that
is its cost, not the need. Bytes: a forward pass reads q, k, v (the
inputs' two bytes an element) and the log-decay and writing strength
(float32 a value head) and writes the outputs; one of the two also
writes the state at every chunk's start (float32), which the backward
pass reads beside the inputs and the outputs' cotangent before it
writes the five inputs' gradients. Exponentials, the L2 norms, the
convolution and the gate around the op are other op types: not
counted."""

#: positions a chunk that the count assumes (arXiv:2412.06464, 3.3)
CHUNK = 64
#: the layer is recomputed in the backward pass: the forward runs again
FORWARD_CALLS = 2


def layers(config):
    n, every = (int(config["num_hidden_layers"]),
                int(config["full_attention_interval"]))
    return n - n // every


def forward_macs_per_token(config):
    hk, hv = (int(config["linear_num_key_heads"]),
              int(config["linear_num_value_heads"]))
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    return (hk * 2 * CHUNK * dk / 2
            + hv * (CHUNK * (dk + dv) / 2 + 3 * dk * dv + CHUNK * dv / 2))


def needs(config, traffic):
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    hk, hv = (int(config["linear_num_key_heads"]),
              int(config["linear_num_value_heads"]))
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    n = layers(config)
    flops = n * tokens * 2.0 * forward_macs_per_token(config) * (
        FORWARD_CALLS + 2)
    qk, v, gates = 2 * hk * dk * 2, hv * dv * 2, 2 * hv * 4
    states = -(-tokens // CHUNK) * hv * dk * dv * 4
    forward = tokens * (qk + v + gates + v)
    backward = tokens * (qk + v + gates + v) + states \
        + tokens * (qk + v + gates)
    bytes_ = n * (FORWARD_CALLS * forward + states + backward)
    return {"flops": float(flops), "bytes": float(bytes_),
            "calls_per_step": n * (FORWARD_CALLS + 1)}
