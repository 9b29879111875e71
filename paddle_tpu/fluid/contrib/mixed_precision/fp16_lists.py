"""AMP op lists (reference:
`python/paddle/fluid/contrib/mixed_precision/fp16_lists.py:28`).

On TPU the 16-bit type is bfloat16: same exponent range as fp32, so the
white list can be broader and dynamic loss scaling is unnecessary (it IS
wired — lowering._run_loss_scaled_post — for `amp_dtype="float16"`).
How the lists drive the trace-time cast policy, the fp32 master-weight
layout and its ZeRO sharding: `paddle_tpu/parallel/README.md`
("Mixed precision & ZeRO-2")."""
from __future__ import annotations

# MXU-bound ops: run in bf16
white_list = {
    "conv2d", "depthwise_conv2d", "conv2d_transpose", "matmul", "matmul_v2",
    "mul",
    # fp32-accumulating inside (preferred_element_type), so bf16 inputs
    # are safe despite the loss epilogue
    "fused_linear_softmax_xent",
}

# numerically sensitive: force fp32
black_list = {
    "softmax_with_cross_entropy", "cross_entropy", "exp", "log",
    "mean", "sum", "reduce_mean", "reduce_sum", "softmax",
    "sigmoid_cross_entropy_with_logits", "layer_norm", "batch_norm",
    # a routing decided in 16 bits flips at near-ties: scores in fp32
    "moe_router",
}

# neutral: follow inputs
gray_list = {
    "elementwise_add", "elementwise_mul", "elementwise_sub",
    "elementwise_div", "relu", "gelu", "tanh", "sigmoid", "dropout",
    "pool2d", "transpose2", "reshape2", "concat", "split", "slice",
    "scale",
}

# input slots whose PARAMETERS stay fp32 at level O2 (no 16-bit live
# copy, so no master either): the op reads them in fp32 whatever its
# other inputs are, and a 16-bit copy would round what the op is
# careful about (the router's matrix decides near-ties; the scans'
# A_log and dt_bias sit inside an exponential of an exponential, the
# state-space scan's D beside it)
fp32_param_slots = {
    "moe_router": ("W", "Bias"),
    "ssd_chunk_scan": ("ALog", "DtBias", "D"),
    "gated_delta_rule": ("ALog", "DtBias"),
}


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None,
                 custom_black_varnames=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        self.gray_list = set(gray_list)
        self.fp32_param_slots = dict(fp32_param_slots)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
            self.black_list -= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
            self.white_list -= set(custom_black_list)
        self.black_varnames = set(custom_black_varnames or [])
