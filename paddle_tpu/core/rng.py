"""PRNG key construction for the whole framework.

The reference seeds a per-device curand generator state
(`paddle/fluid/operators/dropout_op.cu`, `uniform_random_op.cc`); the
TPU-native design threads counter-based stateless keys instead
(deterministic given program.random_seed + op index). This module picks
the key *implementation*: threefry2x32 is JAX's portable default but
generates bits with long serial VPU ops — on a BERT-base step the
dropout masks alone are ~1.2G draws in the forward pass while the MXU
idles (a scan's per-layer recompute keeps the masks and draws nothing
again: ops/remat_names.py). XLA's
RngBitGenerator ("rbg") uses the hardware RNG path on TPU. Controlled by
FLAGS_prng_impl ("auto" = rbg on TPU, threefry on CPU so seeded CPU
tests keep their exact streams).

`fold_in`/`split`/`bernoulli`/`uniform`/`normal` all accept the typed
keys `make_key` returns, so consumers are impl-agnostic.
"""
from __future__ import annotations

import jax

from ..utils.flags import get_flag


def resolved_impl() -> str:
    """The concrete key impl the current flag + backend resolve to."""
    impl = str(get_flag("FLAGS_prng_impl", "auto"))
    if impl == "auto":
        return "rbg" if jax.default_backend() == "tpu" else "threefry2x32"
    return impl


def make_key(seed):
    """A typed PRNG key for `seed` under the configured implementation.

    Works with a traced (dynamic) seed — used inside the jitted train
    step where the seed is a carried uint32 argument.
    """
    return jax.random.key(seed, impl=resolved_impl())
