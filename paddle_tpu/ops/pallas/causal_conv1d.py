"""The causal depthwise convolution of a hybrid decoder's mixers
(`ops/hybrid_ops.causal_conv1d`) as a pair of Pallas TPU kernels, the
forward pass and its transpose, each reading its operands once.

X [B, S, C] is laid with its channels on the lanes. A grid step takes
one sequence's block of positions and block of channels (`conv_blocks`);
inside it a loop walks the channels `_LANES` at a time and, within
them, the positions `_TRIP` rows a trip, each trip's rows made float32
and cut into slabs of `_ROWS` (one float32 vreg's sublanes). The tap n
rows back is the slab rolled down its sublanes by n, its first n rows
taken from the slab before (rolled the same way and carried). The rows
before a block come from a halo block of `_HALO` rows, nought before the
sequence's start; the transpose reads the rows after a block too, a
second halo block of X and of the output's cotangent, and walks its
trips last to first.

The forward kernel sums the K taps in float32, in the order the
`jax.numpy` form sums them, adds the bias, applies `silu` where named
and writes Out at X's dtype. The reverse kernel makes the
pre-activation again from X (nothing is kept for it but the op's
inputs), its cotangent dpre = dOut silu'(pre), dX[t] = sum_k w_k
dpre[t + K-1-k] at X's dtype, and the filter's and the bias's
gradients summed in float32 over the positions and the sequences (the
grid's two inner axes are sequential for it) into one block a channel
block, written once. Positions past S (a last block that is partial)
are masked where they would reach a sum.

Both bodies loop and do not unroll (`tests/test_tpu_compile.py` holds
their size), and both are `jax.jit`s: a program lowers each signature
once (the forward twice where a recompute calls it too), whatever the
number of layers that call it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

_F32 = jnp.float32
#: rows of a float32 slab (a vreg's sublanes): a filter may have at
#: most this many taps after its first
_ROWS = 8
#: rows of a halo block: a 16-bit tile's sublanes
_HALO = 16
#: rows a trip of a kernel's inner loop takes (a multiple of `_HALO`),
#: and channels, forward and reverse (the reverse kernel's carries and
#: sums of 256 lanes stay in registers)
_TRIP = 32
_LANES = {"fwd": 512, "bwd": 256}
#: the most one grid step's blocks may take of VMEM, both buffers of
#: each (the scoped limit is 16 MiB)
_VMEM_BLOCK_BYTES = 8 << 20
#: the longest block of positions a grid step takes
_MOST_ROWS = 2048


def _lanes(bc, kind):
    """Channels a trip of a kernel's inner loop takes: `_LANES`, or
    the block's where that is fewer"""
    return min(_LANES[kind], bc)


def conv_blocks(s, c, itemsize, arrays):
    """(positions, channels) a grid step, for a sequence of `s`
    positions of `c` channels (a multiple of 128) at `itemsize` bytes,
    `arrays` blocks of that size a step (2 forward: X and Out; 3
    reverse: X, dOut and dX): channels the largest of 512, 256 and 128
    that divides c; positions a power of two of `_TRIP` or more whose
    blocks fit `_VMEM_BLOCK_BYTES` twice over, at most `_MOST_ROWS`,
    and no more than s rounded up to a whole trip."""
    bc = next(n for n in (512, 256, 128) if c % n == 0)
    bs = _TRIP
    while (bs * 2 <= _MOST_ROWS
           and 2 * arrays * 2 * bs * bc * itemsize <= _VMEM_BLOCK_BYTES):
        bs *= 2
    return min(bs, -(-s // _TRIP) * _TRIP), bc


def _down(slab, taps):
    """The slab rolled down its sublanes by 1 .. taps - 1 rows"""
    return [pltpu.roll(slab, n, 0) for n in range(1, taps)]


def _up(slab, taps):
    """The slab rolled up its sublanes by 1 .. taps - 1 rows"""
    return [pltpu.roll(slab, _ROWS - n, 0) for n in range(1, taps)]


def _back(slab, before, row):
    """([x[t], x[t-1], .., x[t-(K-1)]] for the slab's rows, the slab's
    own rolls down): rolled down, its first rows from the slab before it
    (`before`, that slab's rolls down)."""
    down = _down(slab, len(before) + 1)
    return [slab] + [jnp.where(row < n, b, d) for n, (b, d) in
                     enumerate(zip(before, down), 1)], down


def _ahead(slab, after, row):
    """([d[t], d[t+1], .., d[t+(K-1)]], the slab's own rolls up): rolled
    up, its last rows from the slab after it (`after`, its rolls up)."""
    up = _up(slab, len(after) + 1)
    return [slab] + [jnp.where(row < _ROWS - n, u, a) for n, (u, a) in
                     enumerate(zip(up, after), 1)], up


def _taps(shifted, w):
    """sum_k w_k shifted[K-1-k], float32, in the order the `jax.numpy`
    form sums it: [x[t], x[t-1], ..] gives the convolution, [d[t],
    d[t+1], ..] its transpose"""
    k, out = len(w), 0
    for i in range(k):
        out = out + shifted[k - 1 - i] * w[i]
    return out


def _by_lanes(w_ref, b_ref, lanes, body):
    """`body(cols, w, bias)` for the block's channels `lanes` at a
    time: w the K taps and bias their [_ROWS, lanes] float32 rows"""
    k, bc = w_ref.shape

    def trip(l, carry):
        cols = pl.ds(pl.multiple_of(l * lanes, lanes), lanes)
        w = [jnp.broadcast_to(w_ref[i:i + 1, cols].astype(_F32),
                              (_ROWS, lanes)) for i in range(k)]
        bias = None if b_ref is None else jnp.broadcast_to(
            b_ref[:, cols].astype(_F32), (_ROWS, lanes))
        body(cols, w, bias)
        return carry

    lax.fori_loop(0, bc // lanes, trip, 0)


def _rows(ref, start, n, cols):
    """n rows of a block from `start` (a multiple of `_HALO`), float32"""
    return ref[0, pl.ds(pl.multiple_of(start, _HALO), n), cols].astype(_F32)


def _fwd_kernel(x_ref, prev_ref, w_ref, *rest, silu, lanes):
    b_ref, o_ref = rest if len(rest) == 2 else (None,) + rest
    bs = x_ref.shape[1]
    k = w_ref.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (_ROWS, lanes), 0)
    first = pl.program_id(2) == 0

    def body(cols, w, bias):
        # the rows before the block: nought before the sequence's start
        head = jnp.where(first, 0.0, prev_ref[0, :, cols].astype(
            _F32)[_HALO - _ROWS:])

        def by_rows(i, before):
            x = _rows(x_ref, i * _TRIP, _TRIP, cols)
            out = []
            for h in range(0, _TRIP, _ROWS):
                back, before = _back(x[h:h + _ROWS], before, row)
                pre = _taps(back, w)
                if bias is not None:
                    pre = pre + bias
                out.append(jax.nn.silu(pre) if silu else pre)
            o_ref[0, pl.ds(pl.multiple_of(i * _TRIP, _TRIP), _TRIP),
                  cols] = jnp.concatenate(out).astype(o_ref.dtype)
            return before

        lax.fori_loop(0, bs // _TRIP, by_rows, _down(head, k))

    _by_lanes(w_ref, b_ref, lanes, body)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref,
                *rest, silu, s, lanes):
    b_ref, dx_ref, dw_ref, db_ref = rest if len(rest) == 4 else (
        None, rest[0], rest[1], None)
    bs = x_ref.shape[1]
    k, trips = w_ref.shape[0], bs // _TRIP
    j = pl.program_id(2)
    row = lax.broadcasted_iota(jnp.int32, (_ROWS, lanes), 0)
    # a last block that is partial: its rows past S are nought in X and
    # in the cotangent
    partial = s % bs != 0

    @pl.when((pl.program_id(1) == 0) & (j == 0))
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    def x_rows(start, n, cols):
        x = _rows(x_ref, start, n, cols)
        if partial:
            at = j * bs + start + lax.broadcasted_iota(jnp.int32, x.shape, 0)
            x = jnp.where(at < s, x, 0.0)
        return x

    def body(cols, w, bias):
        def d_pre(back, dy, at=None):
            """dOut silu'(pre) of a slab, nought from position S on where
            `at`, its first row's position, is given"""
            if silu:
                pre = _taps(back, w)
                if bias is not None:
                    pre = pre + bias
                sig = jax.nn.sigmoid(pre)
                dy = dy * sig + dy * pre * (sig * (1.0 - sig))
            return dy if at is None else jnp.where(at + row < s, dy, 0.0)

        # the cotangent of the first rows after the block, from the
        # block's last rows and the halo after it
        last = x_rows(bs - _HALO, _HALO, cols)[_HALO - _ROWS:]
        back, _ = _back(next_ref[0, :, cols].astype(_F32)[:_ROWS],
                        _down(last, k), row)
        after = d_pre(back, dy_next_ref[0, :, cols].astype(_F32)[:_ROWS],
                      (j + 1) * bs)
        head = jnp.where(j == 0, 0.0, prev_ref[0, :, cols].astype(
            _F32)[_HALO - _ROWS:])

        def by_rows(n, carry):
            later, sums = carry
            i = trips - 1 - n
            x = x_rows(i * _TRIP, _TRIP, cols)
            dy = _rows(dy_ref, i * _TRIP, _TRIP, cols)
            # the rows before the trip's first: the trip before's last
            tail = x_rows(jnp.maximum(i * _TRIP - _HALO, 0), _HALO, cols)
            down = _down(jnp.where(i == 0, head, tail[_HALO - _ROWS:]), k)
            # the slabs first to last for their cotangents and the sums,
            # then last to first for dX, which reads the slab after
            dps = []
            for h in range(0, _TRIP, _ROWS):
                back, down = _back(x[h:h + _ROWS], down, row)
                dp = d_pre(back, dy[h:h + _ROWS],
                           j * bs + i * _TRIP + h if partial else None)
                sums = [acc + back[k - 1 - t] * dp
                        for t, acc in enumerate(sums[:k])] + [sums[k] + dp]
                dps.append(dp)
            dx = []
            for dp in reversed(dps):
                ahead, later = _ahead(dp, later, row)
                dx.insert(0, _taps(ahead, w))
            dx_ref[0, pl.ds(pl.multiple_of(i * _TRIP, _TRIP), _TRIP),
                   cols] = jnp.concatenate(dx).astype(dx_ref.dtype)
            return later, sums

        zero = jnp.zeros(row.shape, _F32)
        _, sums = lax.fori_loop(0, trips, by_rows,
                                (_up(after, k), [zero] * (k + 1)))
        dw_ref[:, cols] += jnp.concatenate(
            [jnp.sum(t, axis=0, keepdims=True) for t in sums[:k]])
        if db_ref is not None:
            db_ref[:, cols] += jnp.sum(sums[k], axis=0, keepdims=True)

    _by_lanes(w_ref, b_ref, lanes, body)


def _specs(s, bs, bc, k, lead):
    """Block specs for index maps over (sequence, channel block,
    position block) named in `lead`'s order of the grid: the block, the
    halo of `_HALO` rows before it (clamped at the start) and after it
    (clamped at the end), the filter's [K, bc] and the bias's [1, bc]."""
    per, last = bs // _HALO, -(-s // _HALO) - 1

    def at(f):
        def index(*g):
            b, c, j = (g[lead.index(a)] for a in "bcj")
            return f(b, c, j)
        return index

    return (pl.BlockSpec((1, bs, bc), at(lambda b, c, j: (b, j, c))),
            pl.BlockSpec((1, _HALO, bc), at(
                lambda b, c, j: (b, jnp.maximum(j * per - 1, 0), c))),
            pl.BlockSpec((1, _HALO, bc), at(
                lambda b, c, j: (b, jnp.minimum((j + 1) * per, last), c))),
            pl.BlockSpec((k, bc), at(lambda b, c, j: (0, c))),
            pl.BlockSpec((1, bc), at(lambda b, c, j: (0, c))))


@functools.partial(jax.jit, static_argnames=("silu", "interpret"))
def _fwd(x, w, b, silu, interpret):
    n, s, c = x.shape
    k = w.shape[1]
    bs, bc = conv_blocks(s, c, x.dtype.itemsize, 2)
    block, before, _, filt, bias = _specs(s, bs, bc, k, "bcj")
    ins, specs = [x, x, w.T], [block, before, filt]
    if b is not None:
        ins.append(b.reshape(1, c))
        specs.append(bias)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, silu=silu, lanes=_lanes(bc, "fwd")),
        grid=(n, c // bc, pl.cdiv(s, bs)),
        in_specs=specs, out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret, name="causal_conv1d_fwd",
    )(*ins)


@functools.partial(jax.jit, static_argnames=("silu", "interpret"))
def _bwd(x, w, b, dy, silu, interpret):
    n, s, c = x.shape
    k = w.shape[1]
    bs, bc = conv_blocks(s, c, x.dtype.itemsize, 3)
    block, before, after, filt, bias = _specs(s, bs, bc, k, "cbj")
    ins = [x, x, x, dy, dy, w.T]
    specs = [block, before, after, block, after, filt]
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((k, c), _F32)]
    out_specs = [block, filt]
    if b is not None:
        ins.append(b.reshape(1, c))
        specs.append(bias)
        outs.append(jax.ShapeDtypeStruct((1, c), _F32))
        out_specs.append(bias)
    got = pl.pallas_call(
        functools.partial(_bwd_kernel, silu=silu, s=s,
                          lanes=_lanes(bc, "bwd")),
        grid=(c // bc, n, pl.cdiv(s, bs)),
        in_specs=specs, out_specs=out_specs, out_shape=outs,
        # the filter's and the bias's sums stay in place across the
        # sequences and the positions
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="causal_conv1d_bwd",
    )(*ins)
    d_b = None if b is None else got[2].reshape(c).astype(b.dtype)
    return got[0], got[1].T.astype(w.dtype), d_b


def causal_conv1d_fwd(x, w, b, silu):
    """Out = act(sum_k w[:, k] X[t - (K-1) + k] + b) for X [B, S, C],
    w [C, K] (K at most `_ROWS` + 1), b [C] or None, act `silu` or
    none; float32 sums, Out at X's dtype. C a multiple of 128."""
    return _fwd(x, w, b, silu=bool(silu), interpret=_interpret_default())


def causal_conv1d_bwd(x, w, b, dy, silu):
    """The transpose of `causal_conv1d_fwd` at dOut `dy`: (dX at X's
    dtype, dw at w's, db at b's or None), the pre-activation made again
    from X."""
    return _bwd(x, w, b, dy, silu=bool(silu),
                interpret=_interpret_default())
