"""What a scan's per-layer checkpoint keeps instead of making again.

`fluid/lowering._exec_scan` wraps a `remat` body in
``jax.checkpoint(body, policy=save_only_these_names(*KEPT))``. The ops
below name, with `keep`, the few values that cost less to hold across
the checkpoint than to rebuild in the backward pass:

- `DROPOUT_MASK`: the boolean keep mask of a dropout (one byte an
  element held; redrawing it costs a `random_bits` of four bytes an
  element, its layout and a compare);
- `NARROW_PRODUCT`: the output of ``X[.., K] x W[K, N]`` with N < K,
  at its compute dtype. Per byte held it avoids the most arithmetic of
  any value in a layer (BERT's FFN-out: K 3072, N 768).

A name exists only while a `remat` scan body is being traced: anywhere
else (no `remat`, dygraph, inference) `keep` returns its argument and
the traced program is what it was without it.
"""
from __future__ import annotations

import contextlib
import contextvars

from jax.ad_checkpoint import checkpoint_name

DROPOUT_MASK = "dropout_keep_mask"
NARROW_PRODUCT = "narrow_matmul_product"
KEPT = (DROPOUT_MASK, NARROW_PRODUCT)

#: the list that the remat scan body being traced appends
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


def keep(x, name):
    """Name `x` for the enclosing remat scan's policy and note its
    shape and dtype; the identity outside one."""
    kept = _KEPT_BY_BODY.get()
    if kept is None:
        return x
    kept.append((name, tuple(x.shape), x.dtype))
    return checkpoint_name(x, name)


def keep_narrow_product(out, w):
    """`out` = X[.., K] x `w`: kept when `w` is a weight matrix [K, N]
    (not a batched operand) with N < K."""
    if w.ndim == 2 and w.shape[1] < w.shape[0]:
        return keep(out, NARROW_PRODUCT)
    return out
