"""The two walks over a sequence's chunks of the gated delta rule
(`ops/hybrid_ops.gated_delta_rule`) as Pallas TPU kernels: what is
sequential in the chunked algorithm, forward and reverse.

One grid step is one chunk of a block of value heads of one sequence,
the chunk axis innermost and sequential. Each head's state [dk, dv]
(in the reverse walk, the state's cotangent) is float32 in a VMEM
scratch across that axis, zeroed at the first chunk a walk meets; the
block's heads are walked in a Python loop inside the grid step, so
that their independent chains of products fill the MXU's latency.

The forward walk makes a chunk's local values itself, in VMEM, from
the chunked inputs and the inverse T of the chunk's triangular system
(`hybrid_ops._gdr_inverse`, float32 products outside, rounded once to
the operands' dtype as `hybrid_ops._gdr_local` rounds it; the inverse
is differentiated outside too, by its own formula, where the backward
pass makes it again): the
products against q and k, the decay mask and T applied to
beta exp(gc) k and beta v never reach HBM. The reverse walk reads the
chunk-local values `_gdr_local` stacks for its transpose,
`[B, N, H, R, chunk, d]` with the last two dimensions whole. Products
run at the operands' dtype with float32 accumulation; what the two
compute is what `hybrid_ops._gdr_local` with `_gdr_walk_fwd`, and
`_gdr_walk_bwd`, compute, which remain what runs off the TPU and at
widths that are no whole lane tiles.
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


def _bwd_kernel(_, w_ref, aqk_ref, qg_ref, kd_ref, gl_ref, u_ref, start_ref,
                do_ref, du_ref, dkd_ref, dgl_ref, d_state, *, heads):
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
        # the sum over dk here, the sum over dv outside
        dgl_ref[0, 0, 0, h:h + 1] = jnp.sum(
            after * start_ref[0, 0, 0, h], axis=0, keepdims=True)
        d_state[h] = (_tn(qg_ref[0, 0, h], do)
                      + gl_ref[0, 0, 0, h:h + 1] * after
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


def gated_delta_rule_fwd(q, k, v, t, gc, beta, into=None, interpret=None):
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
    writes no state)."""
    lead, chunk, dk, dv = v.shape[:4], v.shape[4], q.shape[-1], v.shape[-1]
    hv, r = lead[2] * lead[3], lead[3]
    heads = heads_a_step(lead[2], r, chunk, dk, dv, q.dtype.itemsize)
    # a head's log-decays as a row (for the mask's columns) and, with
    # its writing strengths, as a column of its block's lanes
    ins = [q, k, _by_head(v), _by_head(t), _rows(gc, heads)] + [
        jnp.swapaxes(_rows(x, heads), -1, -2) for x in (gc, beta)]
    outs = [jax.ShapeDtypeStruct(ins[2].shape, q.dtype)]
    stack, group = (None, 0) if into is None else into
    if stack is not None:
        outs.append(jax.ShapeDtypeStruct(_stacked(stack).shape, _F32))
    got = _call(functools.partial(_fwd_kernel, heads=heads, r=r),
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
    [B, N, H, R] float32)."""
    lead, dk, dv = w.shape[:4], w.shape[-1], u.shape[-1]
    hv = lead[2] * lead[3]
    heads = heads_a_step(lead[2], lead[3], w.shape[4], dk, dv,
                         w.dtype.itemsize)
    ins = [_by_head(t) for t in (w, aqk, qg, kd)] + [
        _lanes(gl, heads, dv), _by_head(u), _stacked(stack), _by_head(d_out)]
    outs = [jax.ShapeDtypeStruct(ins[5].shape, _F32),
            jax.ShapeDtypeStruct(ins[0].shape, w.dtype),
            jax.ShapeDtypeStruct(ins[4].shape, _F32)]
    d_u, d_kd, d_gl = _call(functools.partial(_bwd_kernel, heads=heads),
                            "gated_delta_rule_bwd", hv, heads, group, ins,
                            outs, dk, dv, True, interpret)
    return (d_u.reshape(lead + d_u.shape[3:]),
            d_kd.reshape(lead + d_kd.shape[3:]),
            jnp.sum(d_gl, axis=-1).reshape(lead))
