"""The two walks over a sequence's chunks of the gated delta rule
(`ops/hybrid_ops.gated_delta_rule`) as Pallas TPU kernels: what is
sequential in the chunked algorithm, forward and reverse; the inverse
of each chunk's triangular system; and, for a decay per key channel, a
chunk's two decayed products and their transpose.

One grid step is one chunk of a block of value heads of one sequence,
the chunk axis innermost and sequential. Each head's state [dk, dv]
(in the reverse walk, the state's cotangent) is float32 in a VMEM
scratch across that axis, zeroed at the first chunk a walk meets; the
block's heads are walked in a Python loop inside the grid step, so
that their independent chains of products fill the MXU's latency.

The forward walk makes a chunk's local values itself, in VMEM, from
the chunked inputs and the inverse T of the chunk's triangular system
(`hybrid_ops._gdr_inverse`, made by `unit_lower_inverse` below and
rounded once to the operands' dtype as `hybrid_ops._gdr_local` rounds
it; the inverse is differentiated outside, by its own formula, where
the backward pass makes it again): the
products against q and k, the decay mask and T applied to
beta exp(gc) k and beta v never reach HBM. The reverse walk reads the
chunk-local values `_gdr_local` stacks for its transpose,
`[B, N, H, R, chunk, d]` with the last two dimensions whole. Products
run at the operands' dtype with float32 accumulation; what the two
compute is what `hybrid_ops._gdr_local` with `_gdr_walk_fwd`, and
`_gdr_walk_bwd`, compute, which remain what runs off the TPU and at
widths that are no whole lane tiles.

The inverse T = (I + a)^-1 of a strictly lower triangular a [64, 64]
float32 runs the two short series of `hybrid_ops._unit_lower_inverse`
in VMEM, every product float32 at `HIGHEST`: a grid step reads a block
of systems once and writes their T once, where the `jax.numpy` series
takes each of its ten [.., 64, 64] products through HBM (a 64-wide
float32 row padded to 128 lanes there). Two systems go side by side in
the 128 lanes, [a1 a2], and a product of the pair against one matrix of
each is [x1 x2] @ [[y1, 0], [0, y2]]: one MXU pass for two. The series
over the diagonal 16-blocks d takes the blocks alone, [B1 .. B8] in 16
rows against their block diagonal. The second series runs over
z = (a - d) d^-1 where the `jax.numpy` one runs over d^-1 (a - d), the
same matrix seen from d^-1's other side: T = d^-1 (I - z)(I + z^2),
and the last power and the running product share one right-hand side.

A decay per key channel makes a chunk's K K^T and Q K^T
sum_d x_id k_jd exp(gc_id - gc_jd) (`hybrid_ops._gdr_decayed`, the
same anchoring): below the diagonal 16-blocks each row block is
anchored at its first row, one product at the operands' dtype against
every earlier column; on them the exponentials are a key channel's
own, summed over the channels in float32 (a lane sum, on the XLU,
which bounds the forward kernel). `gated_delta_rule_decay` makes both
products of a block of heads of one chunk from q, k and gc read once;
`gated_delta_rule_decay_bwd` makes the exponentials again where the
`jax.numpy` products' transpose kept them in HBM. Both loop over the
grid step's heads, two a trip, with every row block of a trip's heads
in one array: each `pallas_call` site is traced and lowered on every
start of a process, compile cache or not, so a kernel's body is kept
a loop and not unrolled code (`tests/test_tpu_compile.py` holds its
size).
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
_NEG_INF = -1e30
#: the most one grid step's blocks may take of VMEM, both buffers of
#: each (the scoped limit is 16 MiB; the state and the compiler's own
#: temporaries need the rest)
_VMEM_BLOCK_BYTES = 8 << 20


def heads_a_step(h, r, chunk, dk, dv, itemsize):
    """Value heads walked in one grid step, for `h` key heads of `r`
    value heads each: whole key heads (a key head's q k^T is shared),
    the most, up to 8 value heads, that divide `h` and whose
    reverse-walk blocks (the larger set) fit `_VMEM_BLOCK_BYTES` twice
    over; one key head at the least."""
    head = (itemsize * chunk * (4 * dk + 2 * dv + chunk)  # w qg kd d_kd u do aqk
            + 4 * (dk * dv + chunk * dv + 2 * dv))        # start, d_u, gl, d_gl
    return r * next(n for n in range(max(1, min(h, 8 // r)), 0, -1)
                    if h % n == 0 and (2 * n * r * head <= _VMEM_BLOCK_BYTES
                                       or n == 1))


def _tn(a, b):
    """a^T b, float32: [i, m], [i, n] -> [m, n]"""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _nt(a, b):
    """a b^T, float32: [m, i], [n, i] -> [m, n]"""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _fwd_kernel(_, q_ref, k_ref, v_ref, t_ref, gc_ref, gct_ref, bt_ref, *rest,
                heads, r):
    if len(rest) == 2:                                   # no state is kept
        (out_ref, state), stack_ref = rest, None
    else:        # the stack comes in (never read) and a group's block goes out
        _, out_ref, stack_ref, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    cd, chunk = q_ref.dtype, q_ref.shape[-2]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    for h in range(heads):
        if h % r == 0:                                   # a new key head
            q, k = q_ref[0, 0, h // r], k_ref[0, 0, h // r]
            qk, qf, kf = _nt(q, k), q.astype(_F32), k.astype(_F32)
        gc = gct_ref[0, 0, 0, :, h:h + 1]                # [C, 1] float32
        beta = bt_ref[0, 0, 0, :, h:h + 1]
        grow, last = jnp.exp(gc), gc[chunk - 1:chunk, :]
        decay = jnp.exp(jnp.where(
            rows >= cols, gc - gc_ref[0, 0, 0, h:h + 1, :], _NEG_INF))
        t = t_ref[0, 0, h]                               # [C, C]
        w = jnp.dot(t, (kf * (beta * grow)).astype(cd),
                    preferred_element_type=_F32).astype(cd)
        u0 = jnp.dot(t, (v_ref[0, 0, h].astype(_F32) * beta).astype(cd),
                     preferred_element_type=_F32)
        before = state[h]                                # [dk, dv] float32
        if stack_ref is not None:
            stack_ref[0, 0, 0, h] = before
        low = before.astype(cd)
        u = (u0 - jnp.dot(w, low, preferred_element_type=_F32)).astype(cd)
        out = (jnp.dot((qf * grow).astype(cd), low,
                       preferred_element_type=_F32)
               + jnp.dot((qk * decay).astype(cd), u,
                         preferred_element_type=_F32))
        out_ref[0, 0, h] = out.astype(out_ref.dtype)
        # [1, 1] -> [1, dv] -> [dk, dv]: one broadcast an axis
        keep = jnp.exp(jnp.broadcast_to(last, (1, before.shape[1])))
        state[h] = keep * before + _tn(
            (kf * jnp.exp(last - gc)).astype(cd), u)


def _flip(v):
    """[1, d] <-> [d, 1]: the vector laid on the diagonal of a [d, d]
    and summed across the other axis (a row of lanes becomes a column
    of sublanes, and back)"""
    d = max(v.shape)
    eye = lax.broadcasted_iota(jnp.int32, (d, d), 0) == \
        lax.broadcasted_iota(jnp.int32, (d, d), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(v, (d, d)), 0.0),
                   axis=1 if v.shape[0] == 1 else 0, keepdims=True)


def _fwd_kernel_channels(_, q_ref, k_ref, v_ref, t_ref, gc_ref, bt_ref,
                         aqk_ref, *rest, heads, r):
    """`_fwd_kernel` of a decay per key channel: a head's log-decays
    [C, dk] as its block, the decayed q k^T handed in (made outside,
    anchored: `hybrid_ops._gdr_decayed`), and the state's rows each
    decayed by its own channel's factor."""
    if len(rest) == 2:
        (out_ref, state), stack_ref = rest, None
    else:
        _, out_ref, stack_ref, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    cd, chunk = q_ref.dtype, q_ref.shape[-2]
    for h in range(heads):
        if h % r == 0:
            qf = q_ref[0, 0, h // r].astype(_F32)
            kf = k_ref[0, 0, h // r].astype(_F32)
        gc = gc_ref[0, 0, h]                             # [C, dk] float32
        beta = bt_ref[0, 0, 0, :, h:h + 1]
        grow, last = jnp.exp(gc), gc[chunk - 1:chunk, :]
        t = t_ref[0, 0, h]
        w = jnp.dot(t, (kf * (beta * grow)).astype(cd),
                    preferred_element_type=_F32).astype(cd)
        u0 = jnp.dot(t, (v_ref[0, 0, h].astype(_F32) * beta).astype(cd),
                     preferred_element_type=_F32)
        before = state[h]
        if stack_ref is not None:
            stack_ref[0, 0, 0, h] = before
        low = before.astype(cd)
        u = (u0 - jnp.dot(w, low, preferred_element_type=_F32)).astype(cd)
        out = (jnp.dot((qf * grow).astype(cd), low,
                       preferred_element_type=_F32)
               + jnp.dot(aqk_ref[0, 0, h], u, preferred_element_type=_F32))
        out_ref[0, 0, h] = out.astype(out_ref.dtype)
        state[h] = _flip(jnp.exp(last)) * before + _tn(
            (kf * jnp.exp(last - gc)).astype(cd), u)


def _bwd_kernel(_, w_ref, aqk_ref, qg_ref, kd_ref, gl_ref, u_ref, start_ref,
                do_ref, du_ref, dkd_ref, dgl_ref, d_state, *, heads,
                channels=False):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        d_state[:] = jnp.zeros_like(d_state)

    cd = w_ref.dtype
    for h in range(heads):
        after = d_state[h]                               # [dk, dv] float32
        d_low = after.astype(cd)
        do = do_ref[0, 0, h]
        d_u = (_tn(aqk_ref[0, 0, h], do)
               + jnp.dot(kd_ref[0, 0, h], d_low, preferred_element_type=_F32))
        du_ref[0, 0, h] = d_u
        dkd_ref[0, 0, h] = _nt(u_ref[0, 0, h], d_low).astype(dkd_ref.dtype)
        if channels:
            # a factor a row: the sum over dv, a row's, as a row of dk
            dgl_ref[0, 0, 0, h:h + 1] = _flip(jnp.sum(
                after * start_ref[0, 0, 0, h], axis=1, keepdims=True))
            gl = _flip(gl_ref[0, 0, 0, h:h + 1])
        else:
            # the sum over dk here, the sum over dv outside
            dgl_ref[0, 0, 0, h:h + 1] = jnp.sum(
                after * start_ref[0, 0, 0, h], axis=0, keepdims=True)
            # read where the product needs it, as this kernel always has
            gl = gl_ref.at[0, 0, 0, h:h + 1]
        d_state[h] = (_tn(qg_ref[0, 0, h], do)
                      + gl[...] * after
                      - _tn(w_ref[0, 0, h], d_u.astype(cd)))


def _by_head(t):
    """[B, N, H, R, ...] -> [B, N, H * R, ...]"""
    return t.reshape(t.shape[:2] + (-1,) + t.shape[4:])


def _rows(t, heads):
    """A value a head and chunk (or a row a head), [B, N, H, R, ...],
    a grid step's heads together: [B, N, H * R / heads, heads, ...]"""
    return t.reshape(t.shape[:2] + (-1, heads) + t.shape[4:])


def _lanes(gl, heads, dv):
    """A head's decay over a chunk, [B, N, H, R] float32, as a row of
    `dv` lanes a head: [B, N, H * R / heads, heads, dv]"""
    gl = _rows(gl, heads)[..., None]
    return jnp.broadcast_to(gl, gl.shape[:-1] + (dv,))


def _call(kernel, name, hv, heads, group, ins, outs, dk, dv, reverse,
          interpret, into=None):
    """`ins`, `outs`: arrays and shapes [B, N, X, rows, cols] with X the
    `hv` value heads, their key heads or their blocks of `heads`, each
    blocked a chunk and a grid step's share of X; one more axis in
    front is the head groups of a stack of chunk states, of which the
    call meets group `group` alone. The walk meets chunk n - 1 - k at
    grid step k where `reverse`. `into`: the stack the last of `outs`
    is written into, the other groups' states left as they are."""
    b, n = ins[0].shape[:2]

    def spec(t):
        share = (1, 1, t.shape[-3] * heads // hv) + t.shape[-2:]
        if len(t.shape) == 5:
            return pl.BlockSpec(share, lambda i, j, k, g: (
                i, n - 1 - k if reverse else k, j, 0, 0))
        return pl.BlockSpec((1,) + share, lambda i, j, k, g: (
            g[0], i, n - 1 - k if reverse else k, j, 0, 0))

    in_specs = [spec(t) for t in ins]
    if into is not None:
        ins, in_specs = ins + [into], in_specs + [
            pl.BlockSpec(memory_space=pl.ANY)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hv // heads, n),
            in_specs=in_specs, out_specs=[spec(t) for t in outs],
            scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)]),
        out_shape=outs,
        # the group's index is input 0
        input_output_aliases={} if into is None else {
            len(ins): len(outs) - 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret_default() if interpret is None else interpret,
        name=name,
    )(jnp.asarray(group, jnp.int32).reshape(1), *ins)


def _stacked(stack):
    """[G, B, N, H, R, dk, dv] -> [G, B, N, H * R, dk, dv]"""
    return stack.reshape(stack.shape[:3] + (-1,) + stack.shape[5:])


def gated_delta_rule_fwd(q, k, v, t, gc, beta, into=None, interpret=None,
                         aqk=None):
    """The forward walk over chunks, each chunk's local values made on
    the way: W = T (beta exp(gc) k), U0 = T (beta v), then
    u = U0 - W S, out = q exp(gc) S + (q k^T . decay) u,
    S <- exp(gc_C) S + (k exp(gc_C - gc))^T u from S = 0. q, k
    [B, N, H, C, dk]; v [B, N, H, R, C, dv]; t [B, N, H, R, C, C] at
    q's dtype; gc, beta [B, N, H, R, C] float32. `into` (a stack
    [G, B, N, H, R, dk, dv] float32 of chunk states, a group's index):
    the state at every chunk's start is written into that group of the
    stack, in place. Returns (out [B, N, H, R, C, dv] at q's dtype, the
    stack, or None without `into`: the pass whose gradient nobody takes
    writes no state). A decay per key channel: gc [B, N, H, R, C, dk]
    with `aqk`, the decayed q k^T [B, N, H, R, C, C] at q's dtype, and
    S <- Diag(exp(gc_C)) S + (k exp(gc_C - gc))^T u."""
    lead, chunk, dk, dv = v.shape[:4], v.shape[4], q.shape[-1], v.shape[-1]
    hv, r = lead[2] * lead[3], lead[3]
    heads = heads_a_step(lead[2], r, chunk, dk, dv, q.dtype.itemsize)
    if aqk is None:
        # a head's log-decays as a row (for the mask's columns) and, with
        # its writing strengths, as a column of its block's lanes
        ins = [q, k, _by_head(v), _by_head(t), _rows(gc, heads)] + [
            jnp.swapaxes(_rows(x, heads), -1, -2) for x in (gc, beta)]
        kernel = _fwd_kernel
    else:
        ins = [q, k, _by_head(v), _by_head(t), _by_head(gc),
               jnp.swapaxes(_rows(beta, heads), -1, -2), _by_head(aqk)]
        kernel = _fwd_kernel_channels
    outs = [jax.ShapeDtypeStruct(ins[2].shape, q.dtype)]
    stack, group = (None, 0) if into is None else into
    if stack is not None:
        outs.append(jax.ShapeDtypeStruct(_stacked(stack).shape, _F32))
    got = _call(functools.partial(kernel, heads=heads, r=r),
                "gated_delta_rule_fwd", hv, heads, group, ins, outs, dk, dv,
                False, interpret, None if stack is None else _stacked(stack))
    out = got[0].reshape(lead + got[0].shape[3:])
    return out, None if stack is None else got[1].reshape(stack.shape)


def gated_delta_rule_bwd(w, aqk, qg, kd, gl, u, stack, group, d_out,
                         interpret=None):
    """The reverse walk, from the last chunk to the first, carrying the
    state's cotangent D from D = 0: d_u = aqk^T d_out + kd D,
    d_kd = u D^T, d_gl = sum(D . start), D <- qg^T d_out + gl D -
    w^T d_u. w, qg, kd [B, N, H, R, C, dk]; aqk [B, N, H, R, C, C]; gl
    [B, N, H, R] float32; u (the corrections) and d_out
    [B, N, H, R, C, dv]; the chunk-start states are group `group` of
    `stack` [G, B, N, H, R, dk, dv] float32, read where they lie.
    Returns (d_u float32, d_kd at w's dtype [B, N, H, R, C, dk], d_gl
    [B, N, H, R] float32). A gl of [B, N, H, R, dk] decays each row of
    the state by its own factor: D <- qg^T d_out + Diag(gl) D - w^T d_u
    and d_gl [B, N, H, R, dk] the sum over dv of D . start."""
    lead, dk, dv = w.shape[:4], w.shape[-1], u.shape[-1]
    hv = lead[2] * lead[3]
    heads = heads_a_step(lead[2], lead[3], w.shape[4], dk, dv,
                         w.dtype.itemsize)
    channels = gl.ndim == len(lead) + 1
    ins = [_by_head(t) for t in (w, aqk, qg, kd)] + [
        _rows(gl, heads) if channels else _lanes(gl, heads, dv), _by_head(u),
        _stacked(stack), _by_head(d_out)]
    outs = [jax.ShapeDtypeStruct(ins[5].shape, _F32),
            jax.ShapeDtypeStruct(ins[0].shape, w.dtype),
            jax.ShapeDtypeStruct(ins[4].shape, _F32)]
    d_u, d_kd, d_gl = _call(
        functools.partial(_bwd_kernel, heads=heads, channels=channels),
        "gated_delta_rule_bwd", hv, heads, group, ins, outs, dk, dv, True,
        interpret)
    return (d_u.reshape(lead + d_u.shape[3:]),
            d_kd.reshape(lead + d_kd.shape[3:]),
            d_gl.reshape(lead + (dk,)) if channels
            else jnp.sum(d_gl, axis=-1).reshape(lead))


#: pairs of systems a trip of the inverse kernel's loop takes at once,
#: independent chains of products that fill one another's latency
_PAIRS = 8


def systems_a_step(n, chunk):
    """Triangular systems [chunk, chunk] float32 a grid step of the
    inverse kernel takes: the most, in whole trips of `_PAIRS` pairs,
    whose blocks in and out (a row padded to whole lane tiles) fit
    `_VMEM_BLOCK_BYTES` twice over, and no more than `n` need."""
    trip = 2 * _PAIRS
    each = 2 * 2 * chunk * -(-chunk // 128) * 128 * 4
    return min(max(1, _VMEM_BLOCK_BYTES // (each * trip)), -(-n // trip)) * trip


def _inverse_kernel(a_ref, t_ref, *, block):
    c = a_ref.shape[-1]
    wide = 2 * c
    hi = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)

    def iota(shape, axis):
        return lax.broadcasted_iota(jnp.int32, shape, axis)

    col = iota((c, wide), 1) % c
    on_diag = iota((c, wide), 0) // block == col // block
    left = iota((c, wide), 1) < c
    short_block = iota((block, wide), 1) % c // block
    eye_short = jnp.where(iota((block, wide), 0) == iota((block, wide), 1)
                          % block, 1.0, 0.0).astype(_F32)
    on_wide_diag = iota((wide, wide), 0) // block == iota((wide, wide), 1) \
        // block

    def short(x):
        """a pair's diagonal blocks [c, 2c] as [B1 .. B8], [block, 2c]"""
        out = jnp.where(short_block == 0, x[:block], 0.0)
        for b in range(1, c // block):
            out = out + jnp.where(short_block == b,
                                  x[b * block:(b + 1) * block], 0.0)
        return out

    def blocks_diag(s):
        """[B1 .. B8] -> their block diagonal [2c, 2c]"""
        return jnp.where(on_wide_diag, jnp.concatenate([s] * (wide // block)),
                         0.0)

    def pair_diag(p):
        """[p1 p2] -> [[p1, 0], [0, p2]]"""
        return jnp.concatenate([jnp.where(left, p, 0.0),
                                jnp.where(left, 0.0, p)])

    def series(r, p, diag, factors):
        """r (I + p)(I + p^2)(I + p^4) ..., `factors` of them: each
        power and the running product times it share `diag(power)`."""
        rows = p.shape[0]
        for _ in range(factors - 1):
            both = hi(jnp.concatenate([p, r]), diag(p))
            p, r = both[:rows], r + both[rows:]
        return r + hi(r, diag(p))

    def body(trip, carry):
        for j in range(_PAIRS):
            at = (trip * _PAIRS + j) * 2
            pair = jnp.concatenate([a_ref[at], a_ref[at + 1]], 1)
            d = jnp.where(on_diag, pair, 0.0)
            x = short(d)
            # (I + d)^-1 = (I - x)(I + x^2)(I + x^4)(I + x^8), x^16 = 0
            u = series(eye_short - x, hi(x, blocks_diag(x)), blocks_diag,
                       (block - 1).bit_length() - 1)
            u_pair = jnp.where(on_diag, jnp.concatenate([u] * (c // block)),
                               0.0)
            # z = (a - d) u is strictly block lower, z^(c / block) = 0:
            # T = u (I - z)(I + z^2) ..., u z beside z^2
            z = hi(pair - d, blocks_diag(u))
            both = hi(jnp.concatenate([z, u_pair]), pair_diag(z))
            t = series(u_pair - both[c:], both[:c], pair_diag,
                       (c // block - 1).bit_length() - 1)
            t_ref[at], t_ref[at + 1] = t[:, :c], t[:, c:]
        return carry

    lax.fori_loop(0, a_ref.shape[0] // (2 * _PAIRS), body, 0)


def unit_lower_inverse(a, block, interpret=None):
    """(I + a)^-1 for strictly lower triangular a [..., 64, 64] float32,
    its diagonal `block`-blocks (16, the op's) inverted first, in one
    Pallas call; float32 [..., 64, 64]. An odd count of systems is made
    even by a zero system, which no pair then mixes with one of the
    others."""
    lead, c = a.shape[:-2], a.shape[-1]
    if a.shape[-2:] != (64, 64) or block != 16:
        raise ValueError("unit_lower_inverse: a %s, blocks of %d"
                         % (a.shape, block))
    a = a.reshape(-1, c, c)
    n = a.shape[0]
    if n % 2:
        a = jnp.pad(a, ((0, 1), (0, 0), (0, 0)))
    m = systems_a_step(a.shape[0], c)
    spec = pl.BlockSpec((m, c, c), lambda i: (i, 0, 0))
    t = pl.pallas_call(
        functools.partial(_inverse_kernel, block=block),
        grid=(pl.cdiv(a.shape[0], m),), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret_default() if interpret is None else interpret,
        name="gated_delta_rule_inverse",
    )(a)
    return t[:n].reshape(lead + (c, c))


#: rows of a diagonal block taken at a time: a vreg's sublanes
_ROWS = 8


def _decay_parts(q_ref, k_ref, gc_ref, h, hp, r, block):
    """What both decay kernels make of the `hp` value heads from `h`
    before their diagonal blocks, every `block` of a chunk's rows at
    once (nb of them, a head's after another's) and each anchored at its
    first row r: (q, k, gc [nb, block, dk] float32; grow = exp(gc_i -
    gc_r) [nb, block, dk]; fall = exp(gc_r - gc_j) for the columns j
    before the block, nought from it on, [nb, C, dk]; the left factors
    k grow and q grow, [nb, 2 block, dk], and the right one k fall,
    [nb, C, dk], at k's dtype). No exponent is positive. The heads share
    one key head, or have one each where `r` is 1."""
    c, dk = gc_ref.shape[-2:]
    nb, cd = hp * c // block, k_ref.dtype
    gc = gc_ref[0, 0, pl.ds(h, hp)]                      # [hp, C, dk]
    qf, kf = (jnp.broadcast_to(
        (x[0, 0, pl.ds(h, hp)] if r == 1 else x[0, 0, h // r]).astype(_F32),
        (hp, c, dk)) for x in (q_ref, k_ref))
    gb, qb, kb = (x.reshape(nb, block, dk) for x in (gc, qf, kf))
    anchor = gb[:, :1]                                   # [nb, 1, dk]
    grow = jnp.exp(gb - anchor)

    def by_block(x):
        """[hp, C, dk] -> [nb, C, dk], each head's once a block"""
        return jnp.broadcast_to(x[:, None], (hp, nb // hp, c, dk)).reshape(
            nb, c, dk)

    earlier = (lax.broadcasted_iota(jnp.int32, (nb, c, 1), 1) < block * (
        lax.broadcasted_iota(jnp.int32, (nb, c, 1), 0) % (c // block)))
    fall = jnp.exp(jnp.where(earlier, anchor - by_block(gc), _NEG_INF))
    return (qb, kb, gb, grow, fall,
            jnp.concatenate([kb * grow, qb * grow], 1).astype(cd),
            (by_block(kf) * fall).astype(cd))


def _diagonal(gb, kb, block):
    """The diagonal blocks' exponentials, every block at once, in parts
    of `_ROWS` rows from `top` (a vreg's sublanes) and the columns j up
    to the part's last row: (top, e = exp(gc_i - gc_j) [nb, j, _ROWS,
    dk], nought where i < j, and e k_j). No exponent is positive."""
    for top in range(0, block, _ROWS):
        n = top + _ROWS
        shape = (1, n, _ROWS, 1)
        seen = (lax.broadcasted_iota(jnp.int32, shape, 1)
                <= lax.broadcasted_iota(jnp.int32, shape, 2) + top)
        e = jnp.exp(jnp.where(
            seen, gb[:, top:n][:, None] - gb[:, :n][:, :, None], _NEG_INF))
        yield top, e, e * kb[:, :n][:, :, None]


def _heads_a_trip(heads, r):
    """Value heads a trip of a decay kernel's loop takes together: two
    where they pair off, their independent chains filling one another's
    latency, and share a key head or have one each"""
    return 2 if heads % 2 == 0 and (r == 1 or r % 2 == 0) else 1


def _decay_kernel(q_ref, k_ref, gc_ref, a_ref, aqk_ref, *, r, block):
    heads, c, dk = gc_ref.shape[2:]
    hp = _heads_a_trip(heads, r)
    nb = hp * c // block
    strict = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
              > lax.broadcasted_iota(jnp.int32, (c, c), 1))
    # a lane's column within its row's diagonal block (negative or past
    # the block elsewhere)
    shape = (nb, _ROWS, c)
    column = lax.broadcasted_iota(jnp.int32, shape, 2) - block * (
        lax.broadcasted_iota(jnp.int32, shape, 0) % (c // block))

    def laid(sums, lo, hi):
        """the sums of columns lo to hi [nb, j, _ROWS, 1] laid into
        their lanes by a tree of selects (a chain would wait on each
        lane sum in turn)"""
        if hi - lo == 1:
            return sums[:, lo]
        mid = (lo + hi) // 2
        return jnp.where(column < mid, laid(sums, lo, mid),
                         laid(sums, mid, hi))

    def trip(i, carry):
        h = i * hp
        qb, kb, gb, _, _, left, right = _decay_parts(q_ref, k_ref, gc_ref, h,
                                                     hp, r, block)
        # every block's rows of both products against its earlier columns
        below = jnp.einsum("bid,bjd->bij", left, right,
                           preferred_element_type=_F32)  # [nb, 2 block, C]
        # its diagonal block, `_ROWS` rows at a time, summed over the
        # channels in float32
        parts = ([], [])
        for top, _, ke in _diagonal(gb, kb, block):
            for x, part in zip((kb, qb), parts):
                part.append(jnp.where(
                    (column >= 0) & (column < top + _ROWS), laid(jnp.sum(
                        x[:, top:top + _ROWS][:, None] * ke, axis=-1,
                        keepdims=True), 0, top + _ROWS), 0.0))
        sk, sq = ((below[:, at:at + block] + jnp.concatenate(part, 1)
                   ).reshape(hp, c, c) for part, at in zip(parts, (0, block)))
        a_ref[0, 0, pl.ds(h, hp)] = jnp.where(strict, sk, 0.0)
        aqk_ref[0, 0, pl.ds(h, hp)] = sq.astype(aqk_ref.dtype)
        return carry

    lax.fori_loop(0, heads // hp, trip, 0)


def _decay_bwd_kernel(q_ref, k_ref, gc_ref, da_ref, daqk_ref, dq_ref, dk_ref,
                      dgc_ref, dq_acc, dk_acc, *, r, block):
    heads, c, dk = gc_ref.shape[2:]
    hp = _heads_a_trip(heads, r)
    nb, cd = hp * c // block, k_ref.dtype
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    on_block = rows // block == cols // block
    dq_acc[:] = jnp.zeros_like(dq_acc)
    dk_acc[:] = jnp.zeros_like(dk_acc)

    def blocks(d_p):
        """[hp, C, C] -> the transposed diagonal blocks [nb, j, i]"""
        d_p = jnp.where(on_block, jnp.swapaxes(d_p, 1, 2), 0.0)
        return sum(d_p[..., b * block:(b + 1) * block]
                   for b in range(c // block)).reshape(nb, block, block)

    def into(acc, h, x):
        """[hp, C, dk] of the value heads from `h` into their key heads'
        sums"""
        if r == 1:
            acc[pl.ds(h, hp)] += x
        else:
            acc[h // r] += jnp.sum(x, axis=0)

    def trip(i, carry):
        h = i * hp
        qb, kb, gb, grow, fall, left, right = _decay_parts(
            q_ref, k_ref, gc_ref, h, hp, r, block)
        d_a = jnp.where(rows > cols, da_ref[0, 0, pl.ds(h, hp)], 0.0)
        d_aqk = jnp.where(rows >= cols,
                          daqk_ref[0, 0, pl.ds(h, hp)].astype(_F32), 0.0)
        # below the diagonal blocks (the right factor is nought from a
        # block's first column on, so the cotangents need no mask there)
        d_below = jnp.concatenate([d_a.reshape(nb, block, c),
                                   d_aqk.reshape(nb, block, c)], 1).astype(cd)
        d_left = jnp.einsum("bij,bjd->bid", d_below, right,
                            preferred_element_type=_F32)
        d_right = jnp.einsum("bij,bid->bjd", d_below, left,
                             preferred_element_type=_F32)
        dk_row = [(d_left[:, t:t + _ROWS] * grow[:, t:t + _ROWS])
                  for t in range(0, block, _ROWS)]
        d_q = [(d_left[:, block + t:block + t + _ROWS] * grow[:, t:t + _ROWS])
               for t in range(0, block, _ROWS)]
        # the diagonal blocks, `_ROWS` rows at a time: a column's
        # cotangents across the channels, and the exponentials' share of
        # the column's k summed over its rows
        ck_all, cq_all = blocks(d_a), blocks(d_aqk)
        by_col = 0.0
        for top, e, ke in _diagonal(gb, kb, block):
            n, part = top // _ROWS, slice(top, top + _ROWS)
            ck, cq = (t[:, :top + _ROWS, part][..., None]
                      for t in (ck_all, cq_all))         # [nb, j, _ROWS, 1]
            dk_row[n] = dk_row[n] + jnp.sum(ck * ke, axis=1)
            d_q[n] = d_q[n] + jnp.sum(cq * ke, axis=1)
            col = jnp.sum((ck * kb[:, part][:, None]
                           + cq * qb[:, part][:, None]) * e, axis=2)
            if top + _ROWS < block:
                col = jnp.concatenate(
                    [col, jnp.zeros((nb, block - top - _ROWS, dk), _F32)], 1)
            by_col = by_col + col
        dk_col = (jnp.sum((d_right * fall).reshape(hp, -1, c, dk), axis=1)
                  + by_col.reshape(hp, c, dk))
        dk_row, d_q = (jnp.concatenate(x, 1).reshape(hp, c, dk)
                       for x in (dk_row, d_q))
        # d P / d gc_i = x_i d x_i as a row, -k_j d k_j as a column
        dgc_ref[0, 0, pl.ds(h, hp)] = (qb.reshape(hp, c, dk) * d_q
                                       + kb.reshape(hp, c, dk)
                                       * (dk_row - dk_col))
        into(dq_acc, h, d_q)
        into(dk_acc, h, dk_row + dk_col)
        return carry

    lax.fori_loop(0, heads // hp, trip, 0)
    dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)
    dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)


def _decay_call(kernel, name, block, q, gc, ins, outs, scratch, interpret):
    """One grid step a chunk and a block of value heads, as many as the
    walks take (`heads_a_step` with dv = dk: the reverse kernel's
    blocks a head, q, k, gc, the two [C, C] cotangents and three
    gradients, are about a reverse walk's): `ins` and `outs` [B, N, X,
    rows, cols] with X the value heads or their key heads; `scratch`
    float32 [C, dk] a key head, that many times."""
    b, n, h, c, dk = q.shape
    if c % block or block % _ROWS:
        raise ValueError("%s: chunks of %d in blocks of %d" % (name, c, block))
    hv, r = h * gc.shape[3], gc.shape[3]
    heads = heads_a_step(h, r, c, dk, dk, q.dtype.itemsize)

    def spec(t):
        return pl.BlockSpec((1, 1, t.shape[2] * heads // hv) + t.shape[3:],
                            lambda i, j, k: (i, j, k, 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, r=r, block=block),
        grid=(b, n, hv // heads),
        in_specs=[spec(t) for t in ins], out_specs=[spec(t) for t in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads // r, c, dk), _F32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_interpret_default() if interpret is None else interpret,
        name=name,
    )(*ins)


def gated_delta_rule_decay(q, k, gc, block, interpret=None):
    """The decayed products of a chunk of a decay per key channel,
    `hybrid_ops._gdr_decayed`'s anchored form in VMEM: A = sum_d
    k_id k_jd exp(gc_id - gc_jd) for j < i, float32, and Aqk the same
    of q against k for j <= i, at q's dtype; both nought above. q, k
    [B, N, H, C, dk]; gc [B, N, H, R, C, dk] float32. Returns A and
    Aqk [B, N, H, R, C, C]. A block of 16 rows anchored at its first
    row r: under its diagonal block one product at q's dtype of
    x exp(gc - gc_r) against k exp(gc_r - gc), on it the sum over
    channels in float32."""
    lead, c = gc.shape[:4], gc.shape[4]
    g = _by_head(gc)
    shape = g.shape[:3] + (c, c)
    a, aqk = _decay_call(
        _decay_kernel, "gated_delta_rule_decay", block, q, gc, [q, k, g],
        [jax.ShapeDtypeStruct(shape, _F32),
         jax.ShapeDtypeStruct(shape, q.dtype)], 0, interpret)
    return a.reshape(lead + (c, c)), aqk.reshape(lead + (c, c))


def gated_delta_rule_decay_bwd(q, k, gc, d_a, d_aqk, block, interpret=None):
    """The transpose of `gated_delta_rule_decay`: for P_ij = sum_d
    x_id k_jd exp(gc_id - gc_jd), d x_id = sum_j dP_ij k_jd exp(gc_id -
    gc_jd) and d k_jd = sum_i dP_ij x_id exp(gc_id - gc_jd), anchored
    as the forward kernel anchors them, and d gc = x d x - k d k summed
    over both products (in A, x is k). The cotangents are read where A
    and Aqk are not nought alone. Returns (d q, d k at q's dtype,
    summed over a key head's value heads; d gc float32)."""
    lead, c = gc.shape[:4], gc.shape[4]
    ins = [q, k, _by_head(gc), _by_head(d_a), _by_head(d_aqk)]
    d_q, d_k, d_gc = _decay_call(
        _decay_bwd_kernel, "gated_delta_rule_decay_bwd", block, q, gc, ins,
        [jax.ShapeDtypeStruct(q.shape, q.dtype)] * 2
        + [jax.ShapeDtypeStruct(ins[2].shape, _F32)], 2, interpret)
    return d_q, d_k, d_gc.reshape(gc.shape)
