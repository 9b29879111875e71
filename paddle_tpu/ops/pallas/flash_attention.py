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
way (no O(S^2) residuals: besides its inputs the gradient holds the
output and the per-row logsumexp). Under a per-layer checkpoint
(`fluid/lowering.py`'s remat scan and recompute segments) those two
are what the checkpoint keeps (`ops/remat_names.FLASH_RESIDUAL`), the
logsumexp as S floats a head and not as the column the kernel writes,
whose every row pads to 128 lanes in HBM: the recompute then makes q,
k and v again and the forward kernel runs once a layer and step.

Layout: q, k, v are [B, H, S, D]; internally flattened to [B*H, S, D].
V's last axis may differ from Q's and K's (PR 33: latent attention
scores at 192 and mixes values of 128): every block, scratch and output
takes the width of what it holds and the VMEM figure counts both; at
equal widths the blocks are those of one width.
`key_bias` is an additive [B, S_k] bias on the keys (the BERT padding
mask); it is treated as non-differentiable (its cotangent is zero), which
matches how masks are used everywhere in the reference.

How the square is tiled (PR 28). Each kernel keeps one block of rows
RESIDENT in VMEM across the innermost grid dimension (a block of
queries in the forward and dQ kernels, a block of keys in the dK/dV
kernel) and STREAMS blocks of the other operand past it; inside a grid
step it walks the streamed block in sub-blocks, one tile of scores at a
time. `block_rule` picks the three sizes from the shapes and the dtype
and nothing else. The MXU is handed the tensors' own dtype (bfloat16
under AMP, float32 in the CPU tests, the same lines) and accumulates in
float32; everything the configurations call softmax (scores, running
max and sum, exp, the accumulators, lse, delta) is float32, and P and dS
are rounded once to the operands' dtype just before their products, as
the unfused path of the same op does. The dK/dV kernel forms the
TRANSPOSED tile (K Q^T), so that none of its four products needs a
transposed left operand. Causal: a grid step above the diagonal names
the block that is already in VMEM (so nothing is fetched) and runs no
sub-block; the iota/compare/select mask runs only on sub-blocks the
diagonal crosses. What this measured on a v5e is in PERF.md, section 6,
PR 28.

On non-TPU backends the same kernels run under the Pallas interpreter so
CPU CI exercises the identical code path.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import remat_names

_NEG_INF = -1e30
_LANES = 128  # VREG lane count: scratch stats are replicated across lanes
_SUBLANES = 8

_NT = (((1,), (1,)), ((), ()))   # A @ B^T
_NN = (((1,), (0,)), ((), ()))   # A @ B

#: what a trace calls the three kernels (forward, dK/dV, dQ): the
#: benchmark's `flash_attn_*` metrics match the op type and
#: `tpu_custom_call` in an operation's text, so the names start with it
KERNEL_NAMES = ("scaled_dot_product_attention_flash_fwd",
                "scaled_dot_product_attention_flash_bwd_dkv",
                "scaled_dot_product_attention_flash_bwd_dq")


# ---------------------------------------------------------------------------
# The block rule
# ---------------------------------------------------------------------------

# What the chip decided (a v5e; PR 28's sweep of the three kernels at the
# two cells' shapes, PERF.md section 6): tiles of 512 x 512 scores (256
# rows cost 1.5-1.9 x, 1,024 gain under 2 % and compile twice as long);
# the streamed operand in one block as long as the sequence while VMEM
# holds it (a forward call at 4,096 keys of 64, sub-blocks unrolled:
# 7.6 ms in blocks of 1,024, 6.5 in one), so that K and V are fetched
# once a head and a causal call has no dead grid step at all.
#: rows of the block a kernel keeps resident: the rows of a tile of scores
_RESIDENT_ROWS = 512
#: bytes of one streamed block of K (or V, Q, dO) as VMEM holds it (a
#: row of 64 takes a lane row of 128): 8,192 rows of bfloat16
_STREAMED_BYTES = 2 * 1024 * 1024
#: columns of a tile of scores: a streamed block is walked in
#: sub-blocks of at most this many rows
_SUB_ROWS = 512
#: sub-blocks unrolled into one straight line of code, so that one's
#: products overlap the last one's vector work (10-15 % of a kernel);
#: more are walked in a loop over groups of this many
_UNROLL = 8
#: what a kernel's blocks (double-buffered), scratch and tile
#: temporaries may take of VMEM, by `_vmem_bytes`; handed to Mosaic as
#: the kernel's limit, whose scoped default (16 MiB on a v5e, of 128 MiB)
#: is less
_VMEM_BUDGET = 32 * 1024 * 1024


class Blocks(NamedTuple):
    """Rows of each block. Forward and dQ: `block_q` resident, `block_k`
    streamed in sub-blocks of `sub_k`. dK/dV: `block_k_dkv` resident,
    `block_q_dkv` streamed in sub-blocks of `sub_q`. `vmem_bytes` is the
    larger of the two figures."""
    block_q: int
    block_k: int
    sub_k: int
    block_q_dkv: int
    block_k_dkv: int
    sub_q: int
    vmem_bytes: int


def _ceil_to(n, m):
    return -(-n // m) * m


def _padded(n):
    """The length a sequence of `n` is padded to: one block of a
    multiple of 8 rows while one resident block holds it, else a
    multiple of 128, which every larger block divides."""
    return _ceil_to(n, _SUBLANES) if n <= _RESIDENT_ROWS \
        else _ceil_to(n, _LANES)


def _largest_block(n, cap):
    """The largest multiple of 128 that divides the padded length `n`
    and is at most `cap`; all of `n` where that is no more."""
    if n <= cap:
        return n
    return max(m for m in range(_LANES, cap + 1, _LANES) if n % m == 0)


def _vmem_bytes(resident, streamed, sub, d, dv, itemsize, extra_tiles=0):
    """An upper bound, over the three kernels, on what one of them takes
    of VMEM: every block twice (the pipeline's two buffers), the float32
    scratch, and the tile temporaries (scores, probabilities, dP, dS and
    their casts; `extra_tiles` more for a causal or a dropout mask).
    Of a kernel's blocks half are as wide as a query or key (`d`) and
    half as wide as a value (`dv`); a row takes whole lane tiles."""
    dp = _ceil_to(d, _LANES) + _ceil_to(dv, _LANES)
    column = resident * _LANES * 4      # a [rows, 1] float32 block
    line = _SUBLANES * streamed * 4     # a [1, rows] float32 block
    blocks = (2 * resident * dp * itemsize      # two in, two out
              + streamed * dp * itemsize
              + 3 * column + 3 * line)
    scratch = resident * dp * 4 + 2 * column
    tiles = (6 + extra_tiles) * resident * sub * 4
    return 2 * blocks + scratch + tiles


def _tile(n_resident, n_streamed, d, dv, itemsize, extra_tiles):
    """(resident block, streamed block, sub-block, VMEM bytes) for
    padded lengths: the largest that divide them under the three caps
    (the streamed one by the wider of `d` and `dv`); while the VMEM
    figure is over the budget the streamed block is halved, then the
    tile."""
    res_cap, sub_cap = _RESIDENT_ROWS, _SUB_ROWS
    str_cap = max(_LANES, _STREAMED_BYTES
                  // (_ceil_to(max(d, dv), _LANES) * itemsize)
                  // _LANES * _LANES)
    while True:
        res = _largest_block(n_resident, res_cap)
        streamed = _largest_block(n_streamed, str_cap)
        sub = _largest_block(streamed, sub_cap)
        vmem = _vmem_bytes(res, streamed, sub, d, dv, itemsize, extra_tiles)
        if vmem <= _VMEM_BUDGET or max(res_cap, str_cap, sub_cap) <= _LANES:
            return res, streamed, sub, vmem
        if str_cap > sub_cap:
            str_cap = max(_LANES, min(str_cap, streamed) // 2
                          // _LANES * _LANES)
        elif sub_cap >= res_cap and sub_cap > _LANES:
            sub_cap = str_cap = sub_cap // 2
        else:
            res_cap = max(_LANES, res_cap // 2)


def block_rule(sq, sk, d, dtype, causal=False, dropout=False, dv=None):
    """The blocks the kernels step through for `sq` queries on `sk`
    keys of `d` (values of `dv`, `d` where not given) in `dtype`: a
    pure function of its arguments. Each block is a multiple of 8 that
    divides its padded length (`_padded`); `vmem_bytes` is under
    `_VMEM_BUDGET`."""
    itemsize = np.dtype(dtype).itemsize
    extra = int(bool(causal)) + int(bool(dropout))
    dv = d if dv is None else dv
    nq, nk = _padded(sq), _padded(sk)
    bq, bk, sub_k, vmem = _tile(nq, nk, d, dv, itemsize, extra)
    bk_dkv, bq_dkv, sub_q, vmem_dkv = _tile(nk, nq, d, dv, itemsize, extra)
    return Blocks(bq, bk, sub_k, bq_dkv, bk_dkv, sub_q,
                  max(vmem, vmem_dkv))


def _blocks_for(sq, sk, d, dv, dtype, causal, dropout, block_q, block_k):
    """(blocks, padded Sq, padded Sk) of a call: the rule's, but a
    length whose block the caller gave is stepped through in that block
    by all three kernels (in sub-blocks of at most `_SUB_ROWS`) and
    padded to a multiple of it."""
    rule = block_rule(sq, sk, d, dtype, causal, dropout, dv)
    if block_q is None and block_k is None:
        return rule, _padded(sq), _padded(sk)

    def side(n, given, resident, streamed, sub):
        if given is None:
            return _padded(n), resident, streamed, sub
        b = min(int(given), _ceil_to(n, _SUBLANES))
        return (_ceil_to(n, b), b, b,
                b if b % _LANES else _largest_block(b, _SUB_ROWS))

    nq, bq, bq_dkv, sub_q = side(sq, block_q, rule.block_q,
                                 rule.block_q_dkv, rule.sub_q)
    nk, bk_dkv, bk, sub_k = side(sk, block_k, rule.block_k_dkv,
                                 rule.block_k, rule.sub_k)
    itemsize, extra = np.dtype(dtype).itemsize, int(causal) + int(dropout)
    vmem = max(_vmem_bytes(bq, bk, sub_k, d, dv, itemsize, extra),
               _vmem_bytes(bk_dkv, bq_dkv, sub_q, d, dv, itemsize, extra))
    return Blocks(bq, bk, sub_k, bq_dkv, bk_dkv, sub_q, vmem), nq, nk


class _Spec(NamedTuple):
    """What a call is besides its arrays (the custom_vjp's one static
    argument)."""
    sm_scale: float
    causal: bool
    p_drop: float
    kv_rep: int      # query heads on one key/value head
    bias_rep: int    # programs (batch * head) on one row of the key bias
    blocks: Blocks
    #: the call is traced in a checkpointed body, whose policy keeps o
    #: and the row statistics (decided where `flash_attention` is
    #: called and carried here, so that it is part of every cache key)
    kept: bool = False


# ---------------------------------------------------------------------------
# Pieces the three kernels share
# ---------------------------------------------------------------------------

def _i32(c):
    return np.uint32(c).astype(np.int32)


def _fmix(x):
    """murmur3's finalizer on int32 lanes (shifts logical, products
    wrapping)."""
    x = x ^ lax.shift_right_logical(x, _i32(16))
    x = x * _i32(0x85EBCA6B)
    x = x ^ lax.shift_right_logical(x, _i32(13))
    x = x * _i32(0xC2B2AE35)
    return x ^ lax.shift_right_logical(x, _i32(16))


def _line(start, n, axis):
    """`start + arange(n)` as int32 along `axis` of a [n, 1] or [1, n]."""
    shape = (n, 1) if axis == 0 else (1, n)
    return start + lax.broadcasted_iota(jnp.int32, shape, axis)


def _row_hash(seed, bh, row0, n, axis):
    """A hash of (seed, batch * head, row) for `n` rows from `row0`."""
    return _fmix((_line(row0, n, axis) * _i32(0x9E3779B1))
                 ^ (bh * _i32(0xC2B2AE3D)) ^ seed)


def _col_hash(seed, col0, n, axis):
    """A hash of (seed, column) for `n` columns from `col0`."""
    return _fmix((_line(col0, n, axis) * _i32(0x85EBCA77))
                 ^ seed ^ _i32(0x27D4EB2F))


def _dropout_keep(row_hash, col_hash, p_drop):
    """The boolean keep mask of attention-prob dropout for a tile, from
    a counter-based hash of the GLOBAL (row, column, batch * head, seed)
    coordinates: deterministic per coordinate, so the backward kernels
    regenerate the identical mask whatever the blocks and the grid's
    order, with no O(S^2) mask buffer in HBM. The avalanche is paid on
    the row and the column terms ([rows, 1] and [1, cols], once a
    tile); an element costs one xor, one wrapping product (which the
    xor does not commute with) and the compare. Plain int32 vector ops:
    Mosaic and the interpreter lower them alike (pltpu.prng_* has no
    CPU interpret rule here). The kept values' 1/(1-p) is applied to the
    accumulators when they are written out."""
    # at or above it with probability 1 - p_drop
    threshold = np.int32(min(int(p_drop * 4294967296.0), 0xFFFFFFFF)
                         - 2 ** 31)
    return (row_hash ^ col_hash) * _i32(0x9E3779B1) >= threshold


def _visible(shape, row_axis, gap):
    """Causal: element (r, c) of a tile whose first column lies `gap`
    past its first row is seen when row >= column."""
    return (lax.broadcasted_iota(jnp.int32, shape, row_axis)
            - lax.broadcasted_iota(jnp.int32, shape, 1 - row_axis)) >= gap


def _scores(a, b, scale, bias, row_axis, gap):
    """A tile of scores, float32: `a @ b^T`, times the scale still owed,
    plus the key bias, and where the causal diagonal crosses the tile
    (`gap` not None, as `_visible` takes it) what is not seen at -1e30."""
    s = lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if bias is not None:
        s = s + bias
    if gap is not None:
        s = jnp.where(_visible(s.shape, row_axis, gap), s, _NEG_INF)
    return s


def _fold_scale(x, sm_scale):
    """`sm_scale` folded into a [rows, D] operand where that is exact in
    any dtype (a power of two: D = 64 gives 1/8); else left for the
    scores. Returns the operand and the factor still owed."""
    if math.frexp(sm_scale)[0] == 0.5:
        return x * jnp.asarray(sm_scale, x.dtype), 1.0
    return x, sm_scale


def _lanes(x, n):
    """A lane-replicated [rows, 128] statistic as [rows, n]."""
    if n % _LANES == 0:
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _sub_start(t, sub):
    return t * sub if isinstance(t, int) else pl.multiple_of(t * sub, sub)


def _visit(step, n_sub, seen=None, crossed=None):
    """Run `step(t, diagonal)` over the sub-blocks `t` of a streamed
    block: those in `seen` wholly under the causal diagonal, those in
    `crossed` with the mask; all `n_sub` where not causal. Bounds known
    at trace time are unrolled, `_UNROLL` sub-blocks to a straight line
    of code and a loop over such groups where there are more."""
    if seen is None:
        seen = (0, n_sub)
    for bounds, diagonal in ((seen, False), (crossed, True)):
        if bounds is None:
            continue
        lo, hi = bounds
        if not (isinstance(lo, int) and isinstance(hi, int)):
            lax.fori_loop(lo, hi, lambda t, c, d=diagonal: (step(t, d), c)[1],
                          None)
            continue
        groups = (hi - lo) // _UNROLL if hi - lo > _UNROLL else 0
        if groups:
            def group(g, c, d=diagonal, lo=lo):
                for u in range(_UNROLL):
                    step(lo + g * _UNROLL + u, d)
                return c
            lax.fori_loop(0, groups, group, None)
        for t in range(lo + groups * _UNROLL, hi):
            step(t, diagonal)


def _columns_seen(causal, row0, col0, block_q, sub_k, n_sub):
    """For a resident block of queries from `row0` and a streamed block
    of keys from `col0`: (`seen`, `crossed`, `live`) as `_visit` takes
    them. Sub-block t is all seen while its last column is at or under
    the block's first row, and none of it past the block's last row."""
    if not causal:
        return None, None, True
    n_seen = jnp.minimum(jnp.maximum(row0 - col0 + 1, 0) // sub_k, n_sub)
    n_live = jnp.minimum(
        jnp.maximum(row0 - col0 + block_q - 1 + sub_k, 0) // sub_k, n_sub)
    return (0, n_seen), (n_seen, n_live), n_live > 0


def _seed_spec():
    # scalar dropout seed rides in SMEM (full-array spec; one int32)
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _interpret_default() -> bool:
    """Mosaic compiles the kernels on a tpu backend, always; the Pallas
    interpreter is the CPU test mode and nothing selects it on a chip."""
    return jax.default_backend() != "tpu"


def _compiler_params(vmem_limit_bytes=None):
    # Outer two grid dims are embarrassingly parallel; only the innermost
    # (the online-softmax / accumulation dim) is sequential.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _kv_row(kv_rep):
    """Query-head program b -> its row of K and V: `kv_rep` query heads
    in a row share one key/value head (grouped-query attention), so K
    and V are read where they lie and never repeated in memory."""
    if kv_rep == 1:
        return lambda b: b
    return lambda b: b // kv_rep


def _last_live_k(i, block_q, block_k):
    """Causal: the last block of keys a block of queries sees."""
    return (i * block_q + block_q - 1) // block_k


def _k_block(spec):
    """Grid step (b, i, j) of the forward and dQ kernels -> its block of
    keys: causal, a step above the diagonal names the block already in
    VMEM, so nothing is fetched for it."""
    if not spec.causal:
        return lambda b, i, j: j
    block_q, block_k = spec.blocks.block_q, spec.blocks.block_k
    return lambda b, i, j: jnp.minimum(
        j, _last_live_k(i, block_q, block_k))


def _first_live_q(j, block_q, block_k):
    """Causal: the first block of queries that sees a block of keys."""
    return (j * block_k) // block_q


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, spec):
    block_q, dv = q_ref.shape[1], v_ref.shape[2]
    block_k, sub_k = k_ref.shape[1], spec.blocks.sub_k
    n_sub = block_k // sub_k
    bh, iq, ik = (pl.program_id(a) for a in range(3))
    row0, col0 = iq * block_q, ik * block_k

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seen, crossed, live = _columns_seen(spec.causal, row0, col0, block_q,
                                        sub_k, n_sub)

    @pl.when(live)
    def _body():
        q, scale = _fold_scale(q_ref[0], spec.sm_scale)
        if spec.p_drop > 0.0:
            seed = seed_ref[0]
            row_hash = _row_hash(seed, bh, row0, block_q, 0)

        def step(t, diagonal):
            c = _sub_start(t, sub_k)
            cols = pl.ds(c, sub_k)
            s = _scores(
                q, k_ref[0, cols, :], scale,
                None if bias_ref is None else bias_ref[0, :, cols],
                0, col0 + c - row0 if diagonal else None)
            m_prev, l_prev = m_scr[...], l_scr[...]     # lane-replicated
            m_next = jnp.maximum(m_prev,
                                 jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, sub_k))
            alpha = jnp.exp(m_prev - m_next)
            # l accumulates the PRE-dropout sums: the softmax denominator
            # is over the full probs; dropout only zeroes/rescales the
            # numerator (out = dropout(softmax(s)) @ v)
            l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[...] = m_next
            if spec.p_drop > 0.0:
                p = jnp.where(_dropout_keep(
                    row_hash, _col_hash(seed, col0 + c, sub_k, 1),
                    spec.p_drop), p, 0.0)
            v = v_ref[0, cols, :]
            acc_scr[...] = acc_scr[...] * _lanes(alpha, dv) + lax.dot_general(
                p.astype(v.dtype), v, _NN,
                preferred_element_type=jnp.float32)

        _visit(step, n_sub, seen, crossed)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _final():
        l_row = l_scr[...]
        l_safe = jnp.where(l_row == 0.0, 1.0, l_row)
        o_ref[0] = (acc_scr[...] * _lanes(
            (1.0 / (1.0 - spec.p_drop)) / l_safe, dv)).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l_safe[:, :1])   # [bq, 1]


def _with_optional(kernel, n_fixed, has_bias, has_drop, spec):
    """`kernel` as pallas_call calls it: after its `n_fixed` inputs come
    the key bias and the dropout seed where the call has them (the
    kernel gets None where not), then its outputs and scratch."""
    n_in = n_fixed + int(has_bias) + int(has_drop)

    def wrapped(*refs):
        bias_ref = refs[n_fixed] if has_bias else None
        seed_ref = refs[n_in - 1] if has_drop else None
        kernel(*refs[:n_fixed], bias_ref, seed_ref, *refs[n_in:], spec=spec)
    return wrapped


def _bias_spec(spec, block, index, column=False):
    """The key bias, one row a batch element: [B, 1, Sk] read as
    (1, block) lines, or [B, Sk, 1] as (block, 1) columns."""
    rep = spec.bias_rep
    if column:
        return pl.BlockSpec((1, block, 1),
                            lambda *g: (g[0] // rep, index(*g), 0))
    return pl.BlockSpec((1, 1, block),
                        lambda *g: (g[0] // rep, 0, index(*g)))


def _fwd_call(q, k, v, key_bias, seed, spec, interpret):
    BH, S, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    block_q, block_k = spec.blocks.block_q, spec.blocks.block_k
    grid = (BH, S // block_q, Sk // block_k)
    kv = _kv_row(spec.kv_rep)
    k_block = _k_block(spec)

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, i, j: (kv(b), k_block(b, i, j), 0)),
        pl.BlockSpec((1, block_k, Dv),
                     lambda b, i, j: (kv(b), k_block(b, i, j), 0)),
    ]
    args = [q, k, v]
    has_bias = key_bias is not None
    has_drop = spec.p_drop > 0.0
    if has_bias:
        in_specs.append(_bias_spec(spec, block_k, k_block))
        args.append(key_bias)
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)

    o, lse = pl.pallas_call(
        _with_optional(_fwd_kernel, 3, has_bias, has_drop, spec),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            # [BH, S, 1]: sublane-layout so lse reads back as (bq, 1)
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, _LANES), jnp.float32),
            _vmem((block_q, _LANES), jnp.float32),
            _vmem((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(_VMEM_BUDGET),
        name=KERNEL_NAMES[0],
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# With dropout: O = Z @ V for Z = P∘M / (1-p), so dV = Z^T @ dO and
# dS = P∘(dZ∘M / (1-p) - delta) for dZ = dO @ V^T, where
# delta = rowsum(dO∘O) is unchanged because rowsum(P∘dP) = rowsum(Z∘dZ).
# The kernels keep 1/(1-p) (and sm_scale) off the elements: they are
# handed delta * (1-p), accumulate P∘(dZ∘M - delta (1-p)) products, and
# scale the accumulators once as they write them out.

def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    bias_ref, seed_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, spec):
    """One block of keys resident, queries streamed; the tile is the
    transposed one, [keys, queries]."""
    block_k = k_ref.shape[1]
    block_q, sub_q = q_ref.shape[1], spec.blocks.sub_q
    n_sub = block_q // sub_q
    bh, ik, iq = (pl.program_id(a) for a in range(3))
    row0, col0 = iq * block_q, ik * block_k

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    seen = crossed = None
    live = True
    if spec.causal:
        # sub-block t of the queries: none sees the keys before its last
        # row reaches their first column; all do from the first whose
        # first row is at or past their last column
        t_live = jnp.minimum(jnp.maximum(col0 - row0, 0) // sub_q, n_sub)
        t_seen = jnp.minimum(
            jnp.maximum(col0 - row0 + block_k - 1 + sub_q - 1, 0) // sub_q,
            n_sub)
        seen, crossed, live = (t_seen, n_sub), (t_live, t_seen), \
            t_live < n_sub

    @pl.when(live)
    def _body():
        k, scale = _fold_scale(k_ref[0], spec.sm_scale)
        v = v_ref[0]
        if bias_ref is not None:
            bias = jnp.broadcast_to(bias_ref[0], (block_k, _LANES))
        if spec.p_drop > 0.0:
            seed = seed_ref[0]
            col_hash = _col_hash(seed, col0, block_k, 0)

        def step(t, diagonal):
            r = _sub_start(t, sub_q)
            rows = pl.ds(r, sub_q)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            s = _scores(
                k, q, scale,
                None if bias_ref is None else _lanes(bias, sub_q),
                1, col0 - row0 - r if diagonal else None)
            p = jnp.exp(s - lse_ref[0, :, rows])        # (1, sub_q)
            dp = lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
            z = p
            if spec.p_drop > 0.0:
                keep = _dropout_keep(
                    _row_hash(seed, bh, row0 + r, sub_q, 1), col_hash,
                    spec.p_drop)
                z, dp = jnp.where(keep, p, 0.0), jnp.where(keep, dp, 0.0)
            dv_scr[...] = dv_scr[...] + lax.dot_general(
                z.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, :, rows])
            dk_scr[...] = dk_scr[...] + lax.dot_general(
                ds.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)

        _visit(step, n_sub, seen, crossed)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _final():
        undrop = 1.0 / (1.0 - spec.p_drop)
        dk_ref[0] = (dk_scr[...] * (spec.sm_scale * undrop)
                     ).astype(dk_ref.dtype)
        dv = dv_scr[...]
        dv_ref[0] = (dv * undrop if spec.p_drop > 0.0 else dv
                     ).astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   bias_ref, seed_ref, dq_ref, dq_scr, *, spec):
    block_q = q_ref.shape[1]
    block_k, sub_k = k_ref.shape[1], spec.blocks.sub_k
    n_sub = block_k // sub_k
    bh, iq, ik = (pl.program_id(a) for a in range(3))
    row0, col0 = iq * block_q, ik * block_k

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    seen, crossed, live = _columns_seen(spec.causal, row0, col0, block_q,
                                        sub_k, n_sub)

    @pl.when(live)
    def _body():
        q, scale = _fold_scale(q_ref[0], spec.sm_scale)
        do = do_ref[0]
        lse = jnp.broadcast_to(lse_ref[0], (block_q, _LANES))
        delta = jnp.broadcast_to(delta_ref[0], (block_q, _LANES))
        if spec.p_drop > 0.0:
            seed = seed_ref[0]
            row_hash = _row_hash(seed, bh, row0, block_q, 0)

        def step(t, diagonal):
            c = _sub_start(t, sub_k)
            cols = pl.ds(c, sub_k)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            s = _scores(
                q, k, scale,
                None if bias_ref is None else bias_ref[0, :, cols],
                0, col0 + c - row0 if diagonal else None)
            p = jnp.exp(s - _lanes(lse, sub_k))
            dp = lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
            if spec.p_drop > 0.0:
                dp = jnp.where(_dropout_keep(
                    row_hash, _col_hash(seed, col0 + c, sub_k, 1),
                    spec.p_drop), dp, 0.0)
            ds = p * (dp - _lanes(delta, sub_k))
            dq_scr[...] = dq_scr[...] + lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32)

        _visit(step, n_sub, seen, crossed)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _final():
        dq_ref[0] = (dq_scr[...] * (spec.sm_scale / (1.0 - spec.p_drop))
                     ).astype(dq_ref.dtype)


def _bwd_call(q, k, v, key_bias, seed, o, lse, do, spec, interpret):
    BH, S, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    kv = _kv_row(spec.kv_rep)
    # [BH, S, 1]; with dropout the kernels want it less the 1/(1-p)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True) * (1.0 - spec.p_drop)

    has_bias = key_bias is not None
    has_drop = spec.p_drop > 0.0
    # dK/dV: keys resident, queries streamed; the row statistics as
    # lines ([BH, 1, S]) and the key bias as a column ([B, Sk, 1])
    block_q, block_k = spec.blocks.block_q_dkv, spec.blocks.block_k_dkv
    if spec.causal:
        def q_block(b, j, i):
            return jnp.maximum(i, _first_live_q(j, block_q, block_k))
    else:
        def q_block(b, j, i):
            return i

    def rows(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, j, i: (b, q_block(b, j, i), 0))

    def keys(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, j, i: (kv(b), j, 0))

    def out(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, j, 0))

    line = pl.BlockSpec((1, 1, block_q),
                        lambda b, j, i: (b, 0, q_block(b, j, i)))
    in_specs = [rows(D), rows(Dv), line, line, keys(D), keys(Dv)]
    args = [q, do, lse.reshape(BH, 1, S), delta.reshape(BH, 1, S), k, v]
    if has_bias:
        in_specs.append(_bias_spec(spec, block_k, lambda b, j, i: j,
                                   column=True))
        args.append(key_bias.reshape(-1, Sk, 1))
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    dk, dv = pl.pallas_call(
        _with_optional(_bwd_dkv_kernel, 6, has_bias, has_drop, spec),
        grid=(BH, Sk // block_k, S // block_q),
        in_specs=in_specs,
        out_specs=[out(D), out(Dv)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, Dv), v.dtype),
        ],
        scratch_shapes=[
            _vmem((block_k, D), jnp.float32),
            _vmem((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(_VMEM_BUDGET),
        name=KERNEL_NAMES[1],
    )(*args)
    if spec.kv_rep > 1:
        # a key/value head's gradient is the sum over its query heads
        dk, dv = (t.astype(jnp.float32)
                  .reshape(-1, spec.kv_rep, Sk, t.shape[2]).sum(1)
                  .astype(t.dtype) for t in (dk, dv))

    # dQ: queries resident, keys streamed, as in the forward kernel
    block_q, block_k = spec.blocks.block_q, spec.blocks.block_k
    k_block = _k_block(spec)

    def rows(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))

    def keys(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, i, j: (kv(b), k_block(b, i, j), 0))

    column = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    in_specs = [rows(D), rows(Dv), column, column, keys(D), keys(Dv)]
    args = [q, do, lse, delta, k, v]
    if has_bias:
        in_specs.append(_bias_spec(spec, block_k, k_block))
        args.append(key_bias)
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    dq = pl.pallas_call(
        _with_optional(_bwd_dq_kernel, 6, has_bias, has_drop, spec),
        grid=(BH, S // block_q, Sk // block_k),
        in_specs=in_specs,
        out_specs=rows(D),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[_vmem((block_q, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(_VMEM_BUDGET),
        name=KERNEL_NAMES[2],
    )(*args)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry: padding wrapper + custom VJP
# ---------------------------------------------------------------------------

def _pad_to(x, axis, length, value=0.0):
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_core(q, k, v, key_bias, seed, spec):
    """The int32 dropout `seed` is an ARGUMENT of the custom_vjp, with a
    None (zero) cotangent: a seed closed over instead is a tracer the
    vjp functions capture, and under `jax.checkpoint` inside a scan body
    (the long-context train step) that tracer escapes its trace."""
    o, _ = _fwd_call(q, k, v, key_bias, seed, spec, _interpret_default())
    return o


def _flash_core_fwd(q, k, v, key_bias, seed, spec):
    o, lse = _fwd_call(q, k, v, key_bias, seed, spec, _interpret_default())
    if spec.kept:
        # the backward rule reads these two and nothing else of the
        # call, so the checkpoint that keeps them recomputes no kernel;
        # the statistics as [BH, S]: the kernel's [BH, S, 1] column
        # takes 128 lanes a row in HBM
        o = checkpoint_name(o, remat_names.FLASH_RESIDUAL)
        lse = checkpoint_name(lse.reshape(lse.shape[:2]),
                              remat_names.FLASH_RESIDUAL)
    return o, (q, k, v, key_bias, seed, o, lse)


def _flash_core_bwd(spec, res, do):
    q, k, v, key_bias, seed, o, lse = res
    if spec.kept:
        lse = lse.reshape(*lse.shape, 1)
    dq, dk, dv = _bwd_call(q, k, v, key_bias, seed, o, lse, do, spec,
                           _interpret_default())
    dbias = None if key_bias is None else jnp.zeros_like(key_bias)
    return dq, dk, dv, dbias, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)

#: the signatures `_engaged` has said already
_said = set()


def _engaged(q, k, v, spec):
    """The engagement record: once per distinct signature, where a call
    is traced, the log says what the kernels were handed (a value's
    width beside a key's where they differ) and how they step through
    it."""
    signature = (q.shape, k.shape, v.shape, str(q.dtype), spec)
    if signature in _said:
        return
    _said.add(signature)
    b = spec.blocks
    nq, nk = q.shape[1] // b.block_q, k.shape[1] // b.block_k
    live = ""
    if spec.causal:
        seen = sum(min(_last_live_k(i, b.block_q, b.block_k) + 1, nk)
                   for i in range(nq))
        live = ", %.1f %% of them live (causal)" % (100.0 * seen / (nq * nk))
    keys = "k/v %s" % list(k.shape) if v.shape == k.shape else \
        "k %s, v %s" % (list(k.shape), list(v.shape))
    logging.getLogger(__name__).info(
        "flash attention: q %s on %s in %s, bias %s, dropout %g: "
        "blocks %d x %d in sub-blocks of %d (dK/dV: %d keys x %d in "
        "sub-blocks of %d), %d bytes of VMEM, %d grid steps a forward "
        "call%s", list(q.shape), keys, q.dtype,
        "yes" if spec.bias_rep else "no", spec.p_drop, b.block_q,
        b.block_k, b.sub_k, b.block_k_dkv, b.block_q_dkv, b.sub_q,
        b.vmem_bytes, q.shape[0] * nq * nk, live)


def flash_attention(q, k, v, key_bias=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, dropout_p=0.0,
                    dropout_seed=None):
    """Blockwise (flash) attention.

    q: [B, H, Sq, D]; k: [B, Hkv, Sk, D]; v: [B, Hkv, Sk, Dv] with H a
    multiple of Hkv (query head j reads key/value head
    j // (H / Hkv), in place). V has a head size of its own: Dv may
    differ from D (latent attention scores at 192 and mixes values of
    128), the blocks follow both, and neither is padded in HBM.
    key_bias: optional [B, Sk] additive bias on keys (e.g.
    `(mask - 1) * 1e4` padding bias; non-differentiable). `sm_scale`
    defaults to D ** -0.5, the query's. Returns [B, H, Sq, Dv] in
    q.dtype.

    block_q, block_k: left out, `block_rule` picks the blocks from the
    shapes and the dtype; given, all three kernels step through
    block_q x block_k.

    dropout_p > 0 applies upscale-in-train dropout to the normalized
    attention probs INSIDE the kernel (mask regenerated from
    (dropout_seed, coordinates) in backward — no O(S^2) mask buffer),
    so dropout-active pretraining can run the flash path. dropout_seed:
    int32 scalar (traced is fine), required when dropout_p > 0.
    """
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    if H % Hkv:
        raise ValueError("%d query heads on %d key/value heads" % (H, Hkv))
    if k.shape[3] != D or v.shape[:3] != k.shape[:3]:
        raise ValueError("q %s, k %s, v %s: keys are as wide as queries, "
                         "values lie on the keys' heads and positions"
                         % (q.shape, k.shape, v.shape))
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

    blocks, nq, nk = _blocks_for(Sq, Sk, D, Dv, q.dtype, causal,
                                 dropout_p > 0.0, block_q, block_k)
    qf = _pad_to(q.reshape(B * H, Sq, D), 1, nq)
    kf = _pad_to(k.reshape(B * Hkv, Sk, D), 1, nk)
    vf = _pad_to(v.reshape(B * Hkv, Sk, Dv), 1, nk)

    bias = key_bias
    if nk > Sk and bias is None:
        bias = jnp.zeros((B, Sk), jnp.float32)
    if bias is not None:
        # one line a batch element, [B, 1, Sk]: (1, bk) broadcasts over
        # the score rows of each of its H programs
        bias = _pad_to(bias.astype(jnp.float32), 1, nk,
                       value=_NEG_INF)[:, None, :]

    spec = _Spec(float(sm_scale), bool(causal), dropout_p, H // Hkv,
                 H if bias is not None else 0, blocks)
    _engaged(qf, kf, vf, spec)
    # in a checkpointed body: its record takes the two rows here, where
    # the body is traced (the forward rule is traced later)
    if remat_names.note(remat_names.FLASH_RESIDUAL, (B * H, nq, Dv),
                        q.dtype):
        remat_names.note(remat_names.FLASH_RESIDUAL, (B * H, nq),
                         jnp.dtype(jnp.float32))
        spec = spec._replace(kept=True)
    o = _flash_core(qf, kf, vf, bias, seed, spec)
    return o[:, :Sq, :].reshape(B, H, Sq, Dv)


def reference_attention(q, k, v, key_bias=None, causal=False,
                        sm_scale=None):
    """Naive XLA attention with identical semantics (golden reference);
    the output is as wide as V."""
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
