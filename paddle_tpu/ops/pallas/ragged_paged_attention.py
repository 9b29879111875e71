"""Ragged paged attention for TPU serving (Pallas kernel + reference).

The serving runtime (paddle_tpu/serving) keeps the KV cache in
fixed-size HBM *pages* shared by every live request; each sequence owns
a block table naming its pages in order. One batch then mixes
sequences of wildly different lengths — long prefills next to
single-token decodes — and a dense [B, S, S] attention would burn both
HBM and MXU time on padding. This kernel is the TPU-native answer
(after "Ragged Paged Attention: A High-Performance and Flexible LLM
Inference Kernel for TPU", arXiv 2604.15464): ONE kernel walks each
sequence's block table with scalar prefetch, computes online-softmax
attention page by page in VMEM (the flash_attention.py recipe), and
masks by per-sequence query/context lengths — so a mixed
prefill+decode batch is a single fixed-shape dispatch regardless of
how ragged the real lengths are.

Semantics (shared by kernel and reference, golden-tested against the
dense `reference_attention`):

- ``q``             [S, Q, Hq, D] — Q is the padded per-sequence query
                    length (1 for pure decode buckets);
- ``k_pages``/``v_pages`` [P, page_size, Hkv, D] — the paged KV cache;
                    Hq must be a multiple of Hkv (GQA: query head h
                    reads kv head h // (Hq // Hkv));
- ``block_tables``  [S, pages_per_seq] int32 — page ids per sequence,
                    in order; entries past the live context must still
                    be valid page indices (pad with 0);
- ``context_lens``  [S] int32 — total tokens of the sequence ALREADY
                    WRITTEN to the cache, including this call's query
                    tokens (the serving step writes K/V first, then
                    attends);
- ``q_lens``        [S] int32 — valid query rows per sequence (None =
                    all Q rows valid). A row i < q_lens[s] has absolute
                    position ``context_lens[s] - q_lens[s] + i`` and
                    attends every cached position <= its own (causal).
                    Rows >= q_lens[s] (and whole sequences with
                    q_lens == 0 — inactive batch slots) return zeros.

On non-TPU backends the kernel runs under the Pallas interpreter, but
it is grid-sequential there — the serving engine's CPU tier-1 path
uses the jittable pure-JAX ``ragged_paged_attention_reference``
instead (``impl="auto"``), which implements the identical contract.
Inference-only by design: no VJP (the serving path never
differentiates through the cache).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import (_LANES, _NEG_INF, _compiler_params,
                              _interpret_default, _vmem, pltpu)

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference"]


def _check_args(q, k_pages, v_pages, block_tables, context_lens, q_lens,
                k_scale=None, v_scale=None):
    S, Q, Hq, D = q.shape
    P, page_size, Hkv, Dk = k_pages.shape
    if v_pages.shape != k_pages.shape:
        raise ValueError("k_pages %s != v_pages %s"
                         % (k_pages.shape, v_pages.shape))
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if k_scale is not None:
        if k_scale.shape != (P, page_size) or \
                v_scale.shape != (P, page_size):
            raise ValueError(
                "k_scale/v_scale must be [num_pages, page_size] = %s, "
                "got %s / %s" % ((P, page_size), k_scale.shape,
                                 v_scale.shape))
    if Dk != D:
        raise ValueError("head_dim mismatch: q %d vs pages %d" % (D, Dk))
    if Hq % Hkv != 0:
        raise ValueError("q heads %d not a multiple of kv heads %d"
                         % (Hq, Hkv))
    if block_tables.ndim != 2 or block_tables.shape[0] != S:
        raise ValueError("block_tables must be [S, pages_per_seq], got %s"
                         % (block_tables.shape,))
    if context_lens.shape != (S,):
        raise ValueError("context_lens must be [S], got %s"
                         % (context_lens.shape,))
    if q_lens is not None and q_lens.shape != (S,):
        raise ValueError("q_lens must be [S], got %s" % (q_lens.shape,))


# ---------------------------------------------------------------------------
# Pure-JAX reference (jittable; the serving engine's CPU path)
# ---------------------------------------------------------------------------

def ragged_paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     context_lens, q_lens=None, *,
                                     sm_scale=None, k_scale=None,
                                     v_scale=None):
    """Gather-then-mask reference with the exact kernel semantics.

    Fixed shapes throughout (the gather spans the FULL block table, not
    the batch's max context), so per-row results are independent of how
    the batch was packed — the property the serving engine's
    bit-identical continuous-batching contract rests on.

    `k_scale`/`v_scale` ([num_pages, page_size] fp32, both or neither)
    dequantize int8 pages in-flight: the gathered slot values are
    multiplied by their per-slot abs-max scale before the attention
    math, so quantized pages never materialize densely outside f32
    registers. With scales absent the computation is byte-identical to
    the pre-quantization reference."""
    q = jnp.asarray(q)
    k_pages = jnp.asarray(k_pages)
    v_pages = jnp.asarray(v_pages)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    context_lens = jnp.asarray(context_lens, jnp.int32)
    if q_lens is not None:
        q_lens = jnp.asarray(q_lens, jnp.int32)
    if k_scale is not None:
        k_scale = jnp.asarray(k_scale, jnp.float32)
    if v_scale is not None:
        v_scale = jnp.asarray(v_scale, jnp.float32)
    _check_args(q, k_pages, v_pages, block_tables, context_lens, q_lens,
                k_scale, v_scale)
    S, Q, Hq, D = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    npages = block_tables.shape[1]
    kvmax = npages * page_size
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if q_lens is None:
        q_lens = jnp.full((S,), Q, jnp.int32)

    # [S, kvmax, Hkv, D] — every sequence's pages, in table order
    k = k_pages[block_tables].reshape(S, kvmax, Hkv, D)
    v = v_pages[block_tables].reshape(S, kvmax, Hkv, D)
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(S, kvmax)[:, :, None, None]
        vs = v_scale[block_tables].reshape(S, kvmax)[:, :, None, None]
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs

    qf = q.astype(jnp.float32).reshape(S, Q, Hkv, G, D)
    s = jnp.einsum("sqhgd,skhd->shgqk", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale

    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, kvmax), 4)
    qrow = lax.broadcasted_iota(jnp.int32, (1, 1, 1, Q, 1), 3)
    qpos = (context_lens - q_lens)[:, None, None, None, None] + qrow
    valid = (kpos <= qpos) & (qrow < q_lens[:, None, None, None, None])
    s = jnp.where(valid, s, _NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid, p, 0.0)  # fully-masked rows: exp(0)=1 otherwise
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("shgqk,skhd->shgqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    o = o / jnp.where(l == 0.0, 1.0, l)
    # [S, Hkv, G, Q, D] -> [S, Q, Hq, D]
    return o.transpose(0, 3, 1, 2, 4).reshape(S, Q, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _rpa_kernel(tbl_ref, ctx_ref, qlen_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, page_size, q_rows,
                gq_rows, ks_ref=None, vs_ref=None):
    """Grid (S, Hkv, pages_per_seq); innermost page dim is sequential
    and carries the online-softmax (m, l, acc) state in VMEM scratch.
    The q block is the GQA-packed [G*Q, D] row block for (seq, kv
    head); row r maps to query group g = r // Q, row i = r % Q.
    `ks_ref`/`vs_ref` (quantized pool only) hold the page's per-slot
    fp32 scales as a lane-layout [1, page] row: a slot's scale factors
    out of both matmuls, so K's scales multiply the score columns and
    V's the prob columns — no [page, D] dequantized copy, and no
    lane-to-sublane move of the scale row."""
    s_idx = pl.program_id(0)
    j = pl.program_id(2)
    npages = pl.num_programs(2)
    ctx = ctx_ref[s_idx]
    qlen = qlen_ref[s_idx]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # dead page: nothing of this sequence's context lives at j
    @pl.when(j * page_size < ctx)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # [GQ, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [page, D]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s * sm_scale                             # [GQ, page]
        if ks_ref is not None:
            s = s * ks_ref[0]                        # [1, page] scales
        rows = lax.broadcasted_iota(jnp.int32, (gq_rows, page_size), 0)
        qi = rows - (rows // q_rows) * q_rows        # row i within Q
        kpos = j * page_size + lax.broadcasted_iota(
            jnp.int32, (gq_rows, page_size), 1)
        qpos = ctx - qlen + qi
        s = jnp.where((kpos <= qpos) & (qi < qlen), s, _NEG_INF)

        m_prev = m_scr[:]                            # [GQ, LANES]
        l_prev = l_scr[:]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        p = jnp.exp(s - m_next[:, :1])
        # a fully-masked row keeps m == -inf: exp(-inf - -inf) = nan —
        # zero it so l stays 0 and the final write outputs zeros
        p = jnp.where(m_next[:, :1] == _NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_next
        v = v_ref[0, 0].astype(jnp.float32)
        if vs_ref is not None:
            p = p * vs_ref[0]
        pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(j == npages - 1)
    def _final():
        l_row = jnp.max(l_scr[:], axis=-1, keepdims=True)
        l_safe = jnp.where(l_row == 0.0, 1.0, l_row)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _rpa_kernel_quant(tbl_ref, ctx_ref, qlen_ref, q_ref, k_ref, v_ref,
                      ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
                      **kw):
    """Operand-order adapter for the quantized pool: pallas passes the
    two scale blocks positionally after v; the body is `_rpa_kernel`."""
    _rpa_kernel(tbl_ref, ctx_ref, qlen_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, ks_ref=ks_ref, vs_ref=vs_ref,
                **kw)


def _rpa_call_impl(q_packed, k_heads, v_heads, block_tables,
                   context_lens, q_lens, *, sm_scale, q_rows, interpret,
                   k_scale=None, v_scale=None):
    """q_packed: [S, Hkv, G*Q, D]; k_heads/v_heads: [Hkv, P, page, D];
    k_scale/v_scale (optional): [P, page] fp32 per-slot dequant scales.
    Returns [S, Hkv, G*Q, D]."""
    S, Hkv, GQ, D = q_packed.shape
    _, P, page_size, _ = k_heads.shape
    npages = block_tables.shape[1]
    quant = k_scale is not None

    kernel = functools.partial(
        _rpa_kernel_quant if quant else _rpa_kernel,
        sm_scale=sm_scale, page_size=page_size, q_rows=q_rows,
        gq_rows=GQ)

    in_specs = [
        pl.BlockSpec((1, 1, GQ, D),
                     lambda s, h, j, tbl, ctx, ql: (s, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, D),
                     lambda s, h, j, tbl, ctx, ql:
                     (h, tbl[s, j], 0, 0)),
        pl.BlockSpec((1, 1, page_size, D),
                     lambda s, h, j, tbl, ctx, ql:
                     (h, tbl[s, j], 0, 0)),
    ]
    operands = [block_tables, context_lens, q_lens, q_packed, k_heads,
                v_heads]
    if quant:
        # [P, 1, page]: Mosaic wants a block's last two dims to be
        # multiples of (8, 128) or the whole array's — (1, page) is the
        # whole of the trailing [1, page]
        scale_spec = pl.BlockSpec(
            (1, 1, page_size),
            lambda s, h, j, tbl, ctx, ql: (tbl[s, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, Hkv, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, GQ, D), lambda s, h, j, tbl, ctx, ql: (s, h, 0, 0)),
        scratch_shapes=[
            _vmem((GQ, _LANES), jnp.float32),
            _vmem((GQ, _LANES), jnp.float32),
            _vmem((GQ, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, GQ, D), q_packed.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*operands)


def ragged_paged_attention(q, k_pages, v_pages, block_tables,
                           context_lens, q_lens=None, *, sm_scale=None,
                           impl="auto", interpret=None, k_scale=None,
                           v_scale=None):
    """Paged attention over mixed-length sequences through a block
    table (see module docstring for the argument contract).

    impl: "kernel" = the Pallas kernel (Mosaic on TPU, interpreter
    elsewhere), "reference" = the jittable pure-JAX gather reference,
    "auto" = kernel on TPU, reference on the CPU test backend — the
    interpreter is grid-sequential and only meant for kernel parity
    tests. `interpret` (None = interpreter off the TPU only) is for
    those tests too; on a tpu backend nothing turns it on by itself.

    k_scale/v_scale ([num_pages, page_size] fp32, both or neither):
    per-slot dequantization scales for int8 pages — kernel and
    reference multiply each slot's K/V by its scale in f32 before the
    attention math. Omitting them keeps the float paths byte-identical
    to the pre-quantization op."""
    q = jnp.asarray(q)
    k_pages = jnp.asarray(k_pages)
    v_pages = jnp.asarray(v_pages)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    context_lens = jnp.asarray(context_lens, jnp.int32)
    if q_lens is not None:
        q_lens = jnp.asarray(q_lens, jnp.int32)
    if k_scale is not None:
        k_scale = jnp.asarray(k_scale, jnp.float32)
    if v_scale is not None:
        v_scale = jnp.asarray(v_scale, jnp.float32)
    _check_args(q, k_pages, v_pages, block_tables, context_lens, q_lens,
                k_scale, v_scale)
    if impl not in ("auto", "kernel", "reference"):
        raise ValueError("impl must be auto|kernel|reference, got %r"
                         % (impl,))
    # on a tpu backend only a caller that names "reference" gets it
    if impl == "reference" or (impl == "auto" and _interpret_default()):
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, block_tables, context_lens, q_lens,
            sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale)

    S, Q, Hq, D = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if q_lens is None:
        q_lens = jnp.full((S,), Q, jnp.int32)
    if interpret is None:
        interpret = _interpret_default()

    # GQA packing: [S, Q, Hq, D] -> [S, Hkv, G*Q, D]; query head
    # h = kv*G + g shares kv head kv, so group-major rows r = g*Q + i
    q_packed = q.reshape(S, Q, Hkv, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(S, Hkv, G * Q, D)
    k_heads = k_pages.transpose(2, 0, 1, 3)   # [Hkv, P, page, D]
    v_heads = v_pages.transpose(2, 0, 1, 3)
    o = _rpa_call_impl(q_packed, k_heads, v_heads, block_tables,
                       context_lens, q_lens, sm_scale=float(sm_scale),
                       q_rows=Q, interpret=bool(interpret),
                       k_scale=k_scale, v_scale=v_scale)
    return o.reshape(S, Hkv, G, Q, D).transpose(0, 3, 1, 2, 4) \
        .reshape(S, Q, Hq, D)
