"""Pallas TPU kernels for the hot ops: flash attention
(`flash_attention.py`), ragged paged attention, the forward pass of the
chunked state-space scan (`ssd_scan.py`) and the two walks over chunks,
forward and reverse, of the gated delta rule (`gated_delta_rule.py`).

These are the hand-scheduled kernels sitting below the XLA-lowered op
registry — the TPU-native counterpart of the reference's hand-written
CUDA in `paddle/fluid/operators/fused/` and `operators/math/`.
"""
from .flash_attention import flash_attention, reference_attention  # noqa: F401
from .ragged_paged_attention import (  # noqa: F401
    ragged_paged_attention, ragged_paged_attention_reference)
