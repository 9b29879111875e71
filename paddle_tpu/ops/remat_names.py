"""What a per-layer checkpoint keeps instead of making again.

`fluid/lowering._exec_scan` wraps a `remat` body, and the backward op's
lowering each segment of an unrolled stack under `RecomputeOptimizer`,
in ``jax.checkpoint(body, policy=save_only_these_names(*KEPT))``. The
ops below name the few values that cost less to hold across the
checkpoint than to rebuild in the backward pass:

- `DROPOUT_MASK`: the boolean keep mask of a dropout (one byte an
  element held; redrawing it costs a `random_bits` of four bytes an
  element, its layout and a compare);
- `NARROW_PRODUCT`: the output of ``X[.., K] x W[K, N]`` with N < K,
  at its compute dtype. Per byte held it avoids the most arithmetic of
  any value in a layer (BERT's FFN-out: K 3072, N 768);
- `FLASH_RESIDUAL`: what the flash kernels' forward call writes and
  their backward calls read (`ops/pallas/flash_attention.py`): the
  output at its own dtype and the per-row logsumexp, S floats a head.
  Both are written whether kept or not; held, they leave the forward
  kernel dead in the recompute, which then runs it once a layer and
  step and not twice (q, k and v are made again: projections, and the
  backward kernels' inputs either way). `flash_attention` asks `note`
  where it is called, carries the answer in its `custom_vjp`'s static
  argument and names the two values in the forward rule: that rule is
  traced when the checkpoint is differentiated, and what it reads
  there is in no cache key.

A name exists only while a checkpointed body is being traced: anywhere
else (no `remat`, dygraph, inference, serving) `keep` returns its
argument, `note` says no, and the traced program is what it was
without them.
"""
from __future__ import annotations

import contextlib
import contextvars

from jax.ad_checkpoint import checkpoint_name

DROPOUT_MASK = "dropout_keep_mask"
NARROW_PRODUCT = "narrow_matmul_product"
FLASH_RESIDUAL = "flash_attention_residual"
KEPT = (DROPOUT_MASK, NARROW_PRODUCT, FLASH_RESIDUAL)

#: the list that the checkpointed body being traced appends
#: (name, shape, dtype) to; None outside such a trace
_KEPT_BY_BODY = contextvars.ContextVar("remat_kept_by_body", default=None)


@contextlib.contextmanager
def collecting(kept):
    """Trace a scan body with `kept` (a list, or None for a body
    without `remat`: a plain scan nested in a checkpointed one names
    nothing, its values are not the outer policy's to save)."""
    tok = _KEPT_BY_BODY.set(kept)
    try:
        yield
    finally:
        _KEPT_BY_BODY.reset(tok)


def note(name, shape, dtype):
    """Whether a checkpointed body is being traced; if so its list
    takes a value of this shape and dtype under `name` (for one that
    is named later, where its maker's gradient rule is traced)."""
    kept = _KEPT_BY_BODY.get()
    if kept is None:
        return False
    kept.append((name, tuple(shape), dtype))
    return True


def keep(x, name):
    """Name `x` for the enclosing checkpoint's policy and note its
    shape and dtype; the identity outside one."""
    return checkpoint_name(x, name) if note(name, x.shape, x.dtype) else x


def keep_narrow_product(out, w):
    """`out` = X[.., K] x `w`: kept when `w` is a weight matrix [K, N]
    (not a batched operand) with N < K."""
    if w.ndim == 2 and w.shape[1] < w.shape[0]:
        return keep(out, NARROW_PRODUCT)
    return out
