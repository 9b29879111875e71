"""Flash attention for TPU as a Pallas kernel (forward + backward).

Reference parity: the reference fuses inference attention by hand in CUDA
(`paddle/fluid/operators/math/bert_encoder_functor.cu`,
`operators/fused/multihead_matmul_op.cu`); training attention is unfused
matmul/softmax ops (`python/paddle/fluid/layers/nn.py` stacks). TPU-native
design: ONE blockwise online-softmax kernel (Dao et al. FlashAttention
recipe) that keeps the [S, S] score matrix out of HBM entirely — scores
live tile-by-tile in VMEM, the MXU does the two matmuls per tile, and the
running (m, l, acc) statistics are carried in VMEM scratch across the
sequential innermost grid dimension. Backward recomputes tiles the same
way (no O(S^2) residuals; only the per-row logsumexp is saved).

Layout: q, k, v are [B, H, S, D]; internally flattened to [B*H, S, D].
`key_bias` is an additive [B, S_k] bias on the keys (the BERT padding
mask); it is treated as non-differentiable (its cotangent is zero), which
matches how masks are used everywhere in the reference.

On non-TPU backends the same kernels run under the Pallas interpreter so
CPU CI exercises the identical code path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # VREG lane count: scratch stats are replicated across lanes


def _dropout_mask(seed, bh, row0, col0, block_q, block_k, p_drop):
    """Per-element keep/scale mask for attention-prob dropout, from a
    counter-based hash (murmur3 finalizer over the GLOBAL (row, col,
    batch*head, seed) coordinates). Deterministic per coordinate, so the
    backward kernels regenerate the identical mask regardless of grid
    iteration order, with no O(S^2) HBM mask buffer — the whole point of
    the flash recipe. Plain uint32 vector ops: lowers under Mosaic and
    the interpreter alike (pltpu.prng_* has no CPU interpret rule
    here)."""
    # every operand must be uint32 BEFORE arithmetic: row0/col0/bh are
    # traced int32 (program_id), and int32+uint32 promotion would make
    # the multiplies signed and the shifts arithmetic
    row0 = jnp.asarray(row0).astype(jnp.uint32)
    col0 = jnp.asarray(col0).astype(jnp.uint32)
    rows = row0 + lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0)
    cols = col0 + lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1)
    x = (rows * jnp.uint32(0x9E3779B1)) ^ (cols * jnp.uint32(0x85EBCA77))
    x = x ^ (jnp.asarray(bh).astype(jnp.uint32)
             * jnp.uint32(0xC2B2AE3D)) ^ seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(p_drop * 4294967296.0), 0xFFFFFFFF))
    return jnp.where(x >= thresh, 1.0 / (1.0 - p_drop),
                     0.0).astype(jnp.float32)


#: what a trace calls the three kernels (forward, dK/dV, dQ): the
#: benchmark's `flash_attn_*` metrics match the op type and
#: `tpu_custom_call` in an operation's text, so the names start with it
KERNEL_NAMES = ("scaled_dot_product_attention_flash_fwd",
                "scaled_dot_product_attention_flash_bwd_dkv",
                "scaled_dot_product_attention_flash_bwd_dq")


def _seed_spec():
    # scalar dropout seed rides in SMEM (full-array spec; one int32)
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _interpret_default() -> bool:
    """Mosaic compiles the kernels on a tpu backend, always; the Pallas
    interpreter is the CPU test mode and nothing selects it on a chip."""
    return jax.default_backend() != "tpu"


def _compiler_params():
    # Outer two grid dims are embarrassingly parallel; only the innermost
    # (the online-softmax / accumulation dim) is sequential.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal,
                block_q, block_k, p_drop):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A causal block is live unless every (row, col) pair has col > row.
    live = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)   # (1, bk) broadcast
        if causal:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)

        m_prev = m_scr[:]                       # [bq, LANES] lane-replicated
        l_prev = l_scr[:]
        m_curr = jnp.max(s, axis=-1, keepdims=True)      # [bq, 1]
        m_next = jnp.maximum(m_prev, m_curr)             # [bq, LANES]
        p = jnp.exp(s - m_next[:, :1])                   # [bq, bk]
        alpha = jnp.exp(m_prev - m_next)                 # [bq, LANES]
        # l accumulates the PRE-dropout sums: the softmax denominator is
        # over the full probs; dropout only zeroes/rescales the numerator
        # (out = dropout(softmax(s)) @ v)
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_next
        if p_drop > 0.0:
            p = p * _dropout_mask(seed_ref[0].astype(jnp.uint32), bh,
                                  iq * block_q, ik * block_k,
                                  block_q, block_k, p_drop)
        pv = lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(ik == nk - 1)
    def _final():
        # All lanes of m/l are equal; a lane-reduce reads them cheaply.
        l_row = jnp.max(l_scr[:], axis=-1, keepdims=True)   # [bq, 1]
        m_row = jnp.max(m_scr[:], axis=-1, keepdims=True)   # [bq, 1]
        l_safe = jnp.where(l_row == 0.0, 1.0, l_row)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_row + jnp.log(l_safe)                # [bq, 1]


def _kv_row(kv_rep):
    """Query-head program b -> its row of K and V: `kv_rep` query heads
    in a row share one key/value head (grouped-query attention), so K
    and V are read where they lie and never repeated in memory."""
    if kv_rep == 1:
        return lambda b: b
    return lambda b: b // kv_rep


def _fwd_call(q, k, v, key_bias, seed, sm_scale, causal, block_q,
              block_k, p_drop, interpret, kv_rep=1):
    BH, S, D = q.shape
    Sk = k.shape[1]
    nq, nk = S // block_q, Sk // block_k
    grid = (BH, nq, nk)
    kv = _kv_row(kv_rep)

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv(b), j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv(b), j, 0)),
    ]
    args = [q, k, v]
    has_bias = key_bias is not None
    has_drop = p_drop > 0.0
    if has_bias:
        # [BH, 1, Sk]: lane-layout so (1, bk) broadcasts over score rows
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)))
        args.append(key_bias)
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)

    def kernel(*refs):
        ins = refs[:len(args)]
        bias_ref = ins[3] if has_bias else None
        seed_ref = ins[3 + int(has_bias)] if has_drop else None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[len(args):]
        return _fwd_kernel(ins[0], ins[1], ins[2], bias_ref, seed_ref,
                           o_ref, lse_ref, m_scr, l_scr, acc_scr,
                           sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           p_drop=p_drop)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            # [BH, S, 1]: sublane-layout so lse reads back as (bq, 1)
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, _LANES), jnp.float32),
            _vmem((block_q, _LANES), jnp.float32),
            _vmem((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=KERNEL_NAMES[0],
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    bias_ref, seed_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, p_drop):
    bh = pl.program_id(0)
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)      # (1, bk)
        if causal:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])                      # [bq, bk]
        # with dropout: O = (P∘M) @ V, so dV = (P∘M)^T @ dO and
        # dP = (dO @ V^T)∘M; delta = rowsum(dO∘O) is unchanged because
        # rowsum((P∘M)∘dZ) = rowsum(dO∘O) still holds with Z = P∘M
        if p_drop > 0.0:
            mask = _dropout_mask(seed_ref[0].astype(jnp.uint32), bh,
                                 iq * block_q, ik * block_k,
                                 block_q, block_k, p_drop)
            z = p * mask
        else:
            z = p
        # dv += (p∘M)^T @ do
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            z, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T ; ds = p * (dp∘M - delta)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            dp = dp * mask
        ds = p * (dp - delta_ref[0]) * sm_scale
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   bias_ref, seed_ref, dq_ref, dq_scr, *,
                   sm_scale, causal, block_q, block_k, p_drop):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)      # (1, bk)
        if causal:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            dp = dp * _dropout_mask(
                seed_ref[0].astype(jnp.uint32), bh, iq * block_q,
                ik * block_k, block_q, block_k, p_drop)
        ds = p * (dp - delta_ref[0]) * sm_scale
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_call(q, k, v, key_bias, seed, o, lse, do, sm_scale, causal,
              block_q, block_k, p_drop, interpret, kv_rep=1):
    BH, S, D = q.shape
    Sk = k.shape[1]
    kv = _kv_row(kv_rep)
    nq, nk = S // block_q, Sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [BH, S, 1]

    has_bias = key_bias is not None
    has_drop = p_drop > 0.0

    def dkv_kernel(*refs):
        n_in = 6 + int(has_bias) + int(has_drop)
        ins = refs[:n_in]
        bias_ref = ins[6] if has_bias else None
        seed_ref = ins[6 + int(has_bias)] if has_drop else None
        dk_ref, dv_ref, dk_scr, dv_scr = refs[n_in:]
        _bwd_dkv_kernel(ins[0], ins[1], ins[2], ins[3], ins[4], ins[5],
                        bias_ref, seed_ref, dk_ref, dv_ref, dk_scr,
                        dv_scr, sm_scale=sm_scale, causal=causal,
                        block_q=block_q, block_k=block_k, p_drop=p_drop)

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # q
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),  # delta
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (kv(b), j, 0)),  # k
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (kv(b), j, 0)),  # v
    ]
    args = [q, do, lse, delta, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j)))
        args.append(key_bias)
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            _vmem((block_k, D), jnp.float32),
            _vmem((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=KERNEL_NAMES[1],
    )(*args)
    if kv_rep > 1:
        # a key/value head's gradient is the sum over its query heads
        dk, dv = (t.astype(jnp.float32).reshape(-1, kv_rep, Sk, D).sum(1)
                  .astype(t.dtype) for t in (dk, dv))

    def dq_kernel(*refs):
        n_in = 6 + int(has_bias) + int(has_drop)
        ins = refs[:n_in]
        bias_ref = ins[6] if has_bias else None
        seed_ref = ins[6 + int(has_bias)] if has_drop else None
        dq_ref, dq_scr = refs[n_in:]
        _bwd_dq_kernel(ins[0], ins[1], ins[2], ins[3], ins[4], ins[5],
                       bias_ref, seed_ref, dq_ref, dq_scr,
                       sm_scale=sm_scale, causal=causal,
                       block_q=block_q, block_k=block_k, p_drop=p_drop)

    in_specs_q = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),  # q
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # delta
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv(b), j, 0)),  # k
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv(b), j, 0)),  # v
    ]
    if has_bias:
        in_specs_q.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)))
    if has_drop:
        in_specs_q.append(_seed_spec())

    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=in_specs_q,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[_vmem((block_q, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=KERNEL_NAMES[2],
    )(*args)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry: padding wrapper + custom VJP
# ---------------------------------------------------------------------------

def _pad_to(x, axis, mult, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, key_bias, seed, sm_scale, causal, block_q,
                block_k, p_drop, kv_rep=1):
    """The int32 dropout `seed` is an ARGUMENT of the custom_vjp, with a
    None (zero) cotangent: a seed closed over instead is a tracer the
    vjp functions capture, and under `jax.checkpoint` inside a scan body
    (the long-context train step) that tracer escapes its trace."""
    o, _ = _fwd_call(q, k, v, key_bias, seed, sm_scale, causal,
                     block_q, block_k, p_drop, _interpret_default(),
                     kv_rep)
    return o


def _flash_core_fwd(q, k, v, key_bias, seed, sm_scale, causal, block_q,
                    block_k, p_drop, kv_rep=1):
    o, lse = _fwd_call(q, k, v, key_bias, seed, sm_scale, causal,
                       block_q, block_k, p_drop, _interpret_default(),
                       kv_rep)
    return o, (q, k, v, key_bias, seed, o, lse)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, p_drop, kv_rep,
                    res, do):
    q, k, v, key_bias, seed, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, key_bias, seed, o, lse, do,
                           sm_scale, causal, block_q, block_k,
                           p_drop, _interpret_default(), kv_rep)
    dbias = None if key_bias is None else jnp.zeros_like(key_bias)
    return dq, dk, dv, dbias, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, key_bias=None, causal=False, sm_scale=None,
                    block_q=128, block_k=128, dropout_p=0.0,
                    dropout_seed=None):
    """Blockwise (flash) attention.

    q: [B, H, Sq, D]; k, v: [B, Hkv, Sk, D] with H a multiple of Hkv
    (query head j reads key/value head j // (H / Hkv), in place);
    key_bias: optional [B, Sk] additive bias on keys (e.g. `(mask - 1) * 1e4` padding bias;
    non-differentiable). Returns [B, H, Sq, D] in q.dtype.

    dropout_p > 0 applies upscale-in-train dropout to the normalized
    attention probs INSIDE the kernel (mask regenerated from
    (dropout_seed, coordinates) in backward — no O(S^2) mask buffer),
    so dropout-active pretraining can run the flash path. dropout_seed:
    int32 scalar (traced is fine), required when dropout_p > 0.
    """
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError("%d query heads on %d key/value heads" % (H, Hkv))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError("dropout_p must be in [0, 1): %r" % dropout_p)
    seed = None
    if dropout_p > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed")
        seed = jnp.reshape(dropout_seed, (1,)).astype(jnp.int32)

    block_q = min(block_q, -(-Sq // 8) * 8)
    block_k = min(block_k, -(-Sk // 8) * 8)

    qf = _pad_to(q.reshape(B * H, Sq, D), 1, block_q)
    kf = _pad_to(k.reshape(B * Hkv, Sk, D), 1, block_k)
    vf = _pad_to(v.reshape(B * Hkv, Sk, D), 1, block_k)

    pad_k = (-Sk) % block_k
    bias = key_bias
    if pad_k and bias is None:
        bias = jnp.zeros((B, Sk), jnp.float32)
    if bias is not None:
        bias = _pad_to(bias.astype(jnp.float32), 1, block_k,
                       value=_NEG_INF)
        # one bias row per (b, h) program, lane-layout [BH, 1, Sk]
        bias = jnp.repeat(bias, H, axis=0)[:, None, :]

    o = _flash_core(qf, kf, vf, bias, seed, float(sm_scale),
                    bool(causal), int(block_q), int(block_k), dropout_p,
                    H // Hkv)
    return o[:, :Sq, :].reshape(B, H, Sq, D)


def reference_attention(q, k, v, key_bias=None, causal=False,
                        sm_scale=None):
    """Naive XLA attention with identical semantics (golden reference)."""
    D = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1)
                for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if key_bias is not None:
        s = s + key_bias[:, None, None, :].astype(jnp.float32)
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
