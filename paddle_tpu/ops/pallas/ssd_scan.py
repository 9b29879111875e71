"""Forward pass of the chunked state-space scan (Mamba-2's SSD) as one
Pallas TPU kernel.

One grid step is one chunk of one B/C group of one sequence: the
`C B^T` product of the chunk is formed once and shared by the group's
heads; each head masks and decay-weights it, multiplies by its
`dt`-scaled inputs, adds what the state at the chunk's start hands to
every position, and moves its state [N, P] (float32, in VMEM across the
chunk axis, the innermost and sequential one) to the chunk's end. The
decays arrive as float32 cumulative sums made outside; the products run
at the inputs' dtype with float32 accumulation.

Backward: `ops/hybrid_ops.py` differentiates the same mathematics in
`jax.numpy`, group by group; this file has no backward kernel yet.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

_NEG_INF = -1e30


def _kernel(x_ref, b_ref, c_ref, col_ref, row_ref, y_ref, state, *,
            heads, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    cd = x_ref.dtype
    bm = b_ref[0]                                        # [L, N]
    cm = c_ref[0]
    cb = lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)   # [L, L]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    for h in range(heads):
        col = col_ref[0, 0, 0, h]                        # [L, 1] float32
        row = row_ref[0, 0, 0, h:h + 1, :]               # [1, L]
        last = col[chunk - 1:chunk, :]                   # [1, 1]
        xh = x_ref[0, h]                                 # [L, P]
        decay = jnp.exp(jnp.where(causal, col - row, _NEG_INF))
        y = jnp.dot((cb * decay).astype(cd), xh,
                    preferred_element_type=jnp.float32)
        y = y + jnp.exp(col) * jnp.dot(
            cm, state[h].astype(cd), preferred_element_type=jnp.float32)
        y_ref[0, h] = y.astype(y_ref.dtype)
        bw = (bm.astype(jnp.float32) * jnp.exp(last - col)).astype(cd)
        # [1, 1] -> [1, P] -> [N, P]: one broadcast an axis
        keep = jnp.exp(jnp.broadcast_to(last, (1, xh.shape[1])))
        state[h] = keep * state[h] + lax.dot_general(
            bw, xh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [N, P]


def ssd_chunk_scan_fwd(xdt, bm, cm, cs, chunk, interpret=None):
    """`xdt` [B, S, H, P]: the inputs times their step sizes; `bm`, `cm`
    [B, S, G, N]; `cs` [B, S, H] float32: the cumulative sum of
    `dt * A` inside each chunk. S a multiple of `chunk`. Returns
    y [B, S, H, P] at `xdt`'s dtype, without the skip term."""
    if interpret is None:
        interpret = _interpret_default()
    b, s, h, p = xdt.shape
    g, n = bm.shape[2:]
    hpg, nc = h // g, s // chunk
    x_t = jnp.transpose(xdt, (0, 2, 1, 3))               # [B, H, S, P]
    cs_g = jnp.transpose(cs.reshape(b, nc, chunk, g, hpg), (0, 3, 1, 4, 2))
    y = pl.pallas_call(
        lambda *refs: _kernel(*refs, heads=hpg, chunk=chunk),
        grid=(b, g, nc),
        in_specs=[
            pl.BlockSpec((1, hpg, chunk, p), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, 1, 1, hpg, chunk, 1),
                         lambda i, j, k: (i, j, k, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, hpg, chunk),
                         lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hpg, chunk, p),
                               lambda i, j, k: (i, j, k, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((hpg, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_scan_fwd",
    )(x_t, bm.reshape(b, s, g * n), cm.reshape(b, s, g * n),
      cs_g[..., None], cs_g)
    return jnp.transpose(y, (0, 2, 1, 3))
