"""Ops of hybrid decoders whose layers are not all softmax attention
over a dense feed-forward: RMS norm (plain or zero-centred weight) and
L2 norm, the causal depthwise convolution, the chunked state-space scan
of a Mamba-2 mixer, the chunked gated delta rule of a Gated DeltaNet
mixer (a linear-attention state corrected by what it already holds), a
partial rotary embedding, the gated (SwiGLU) activation, and a
routed-expert layer in two ops (the router, sigmoid- or softmax-scored,
and the experts a chip holds as grouped matrix products, plain or
gated).

Precision is part of each op, not of an AMP list: the norms'
statistics, the scans' step sizes, decays, cumulative sums, triangular
solves and chunk states, the rotary angles and the router's scores are
float32 whatever the inputs' dtype; the matrix products run at the
inputs' dtype with float32 accumulation. `fp16_lists.fp32_param_slots`
keeps the parameters behind those float32 parts (the scans' `A_log`,
`dt_bias`, `D`; the router's matrix and bias) float32 under `decorate`.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax import lax

from .registry import get_op, register_op

_F32 = jnp.float32


def _part(name):
    """A part of the op being traced, named for the fold of device time
    (`observability/attribution.py`: `part_scope`, `part_of`): a scope
    at trace time, nothing at run time."""
    from ..observability import attribution

    return attribution.part_scope(name)


# ---------------------------------------------------------------------------
# RMS norm, causal depthwise convolution
# ---------------------------------------------------------------------------

@register_op("rms_norm")
def _rms_norm(ins, attrs):
    """Y = X * rsqrt(mean(X^2) + epsilon) * (scale_offset + Scale) over
    the last axis, or over each of `groups` equal parts of it;
    statistics in float32, Y at X's dtype. `scale_offset` 1 is the
    zero-centred weight `(1 + w)`, w starting at zero."""
    x = ins["X"][0]
    groups = int(attrs.get("groups", 1))
    eps = float(attrs.get("epsilon", 1e-5))
    xf = x.astype(_F32).reshape(x.shape[:-1] + (groups, -1))
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + eps)
    y = y.reshape(x.shape)
    if ins.get("Scale"):
        scale = ins["Scale"][0].astype(_F32)
        offset = float(attrs.get("scale_offset", 0.0))
        y = y * (scale + offset if offset else scale)
    return {"Y": y.astype(x.dtype)}


@register_op("l2_norm")
def _l2_norm(ins, attrs):
    """Y = X * rsqrt(sum(X^2) + epsilon) over the last axis, the sum in
    float32, Y at X's dtype."""
    x = ins["X"][0]
    xf = x.astype(_F32)
    y = xf * lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
                       + float(attrs.get("epsilon", 1e-6)))
    return {"Y": y.astype(x.dtype)}


@register_op("swiglu")
def _swiglu(ins, attrs):
    """Out = silu(G) * U for X = [G | U] halved along the last axis:
    the gated activation between a feed-forward's two products, the
    gate's and the up projection's matrices laid side by side."""
    gate, up = jnp.split(ins["X"][0], 2, axis=-1)
    return {"Out": jax.nn.silu(gate) * up}


def _yarn(inv, rd, theta, attrs):
    """YaRN's frequencies and scale (arXiv:2309.00071, as HF's
    `_compute_yarn_parameters` gives them) from the default ones `inv`
    of `rotary_dim` `rd`: column i's frequency moves from inv_i (fast
    turning, extrapolated) to inv_i / factor (interpolated) along a
    ramp clip((i - low) / (high - low), 0, 1) between the columns that
    turn `yarn_beta_fast` and `yarn_beta_slow` times over the original
    context, low floored and high ceiled, both within [0, rd - 1]."""
    factor = float(attrs["yarn_factor"])
    original = float(attrs["yarn_original_max_position"])

    def column(turns):
        return rd * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(column(float(attrs["yarn_beta_fast"]))), 0)
    high = min(math.ceil(column(float(attrs["yarn_beta_slow"]))), rd - 1)
    ramp = jnp.clip((jnp.arange(rd // 2, dtype=_F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp), \
        float(attrs["yarn_attention_factor"])


@register_op("rotary_embedding")
def _rotary_embedding(ins, attrs):
    """Rotary position embedding on the first `rotary_dim` of X
    [B, S, H, D]'s last axis, positions 0 .. S-1, base `theta` (both
    attributes required), the rotate-half convention: with x = [x1 | x2] the halves of that
    part, out = [x1 cos - x2 sin | x2 cos + x1 sin], the angle of
    column i at position t being t * theta^(-2 i / rotary_dim). Angles
    in float32, Out at X's dtype; the rest of the axis passes through.
    With the `yarn_*` attributes the frequencies are YaRN's (`_yarn`)
    and cos and sin are scaled by `yarn_attention_factor`: the turned
    columns come out scaled by it, the others pass unscaled."""
    x = ins["X"][0]
    s, d = x.shape[1], x.shape[-1]
    rd = int(attrs["rotary_dim"])
    if rd % 2 or not 0 < rd <= d:
        raise ValueError("rotary_embedding over %d of %d" % (rd, d))
    theta = float(attrs["theta"])
    inv = jnp.exp(-math.log(theta) * jnp.arange(0, rd, 2, dtype=_F32) / rd)
    scale = 1.0
    if "yarn_factor" in attrs:
        inv, scale = _yarn(inv, rd, theta, attrs)
    angle = jnp.arange(s, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = (x[..., :rd // 2].astype(_F32), x[..., rd // 2:rd].astype(_F32))
    turned = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return {"Out": jnp.concatenate([turned, x[..., rd:]], axis=-1)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_kernel(x, w, b, silu):
    """`causal_conv1d` by the Pallas pair of `pallas/causal_conv1d.py`:
    each pass reads X (and dOut) once, and the transpose makes the
    pre-activation again from X, so only the op's inputs are kept."""
    from .pallas.causal_conv1d import causal_conv1d_fwd

    return causal_conv1d_fwd(x, w, b, silu)


def _conv_kernel_fwd(x, w, b, silu):
    return _conv_kernel(x, w, b, silu), (x, w, b)


def _conv_kernel_bwd(silu, res, dy):
    from .pallas.causal_conv1d import causal_conv1d_bwd

    return causal_conv1d_bwd(*res, dy, silu)


_conv_kernel.defvjp(_conv_kernel_fwd, _conv_kernel_bwd)


def causal_conv1d(x, w, b=None, silu=False, kernel=None):
    """Depthwise causal convolution along a sequence: x [B, S, C], w
    [C, K]; Out[t] = sum_k w[:, k] x[t - (K-1) + k] + b, positions
    before the sequence's start reading zero, then `silu` where asked.
    Summed in float32, Out at x's dtype. `kernel` None takes the Pallas
    pair on a TPU where C is whole lane tiles (a multiple of 128) and
    the filter has at most 9 taps, and `jax.numpy` elsewhere."""
    k, (n, s, c) = w.shape[1], x.shape
    if kernel is None:
        kernel = jax.default_backend() == "tpu" and c % 128 == 0 and k <= 9
    by = "jax.numpy"
    if kernel:
        from .pallas.causal_conv1d import conv_blocks

        steps = []
        for arrays in (2, 3):
            bs, bc = conv_blocks(s, c, x.dtype.itemsize, arrays)
            steps.append("%d x %d a grid step, %d steps" % (
                bs, bc, n * -(-s // bs) * (c // bc)))
        by = "pallas (positions x channels: forward %s; reverse %s)" \
            % tuple(steps)
    # said where the op is traced: at the build's shape inference and
    # once a compile and layer
    logging.getLogger(__name__).info(
        "causal_conv1d x %s %s, %d taps%s%s: convolved by %s",
        tuple(x.shape), x.dtype.name, k, ", a bias" if b is not None else "",
        ", silu" if silu else "", by)
    if kernel:
        return _conv_kernel(x, w, b, bool(silu))
    xp = jnp.pad(x.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    out = sum(xp[:, i:i + s, :] * wf[:, i] for i in range(k))
    if b is not None:
        out = out + b.astype(_F32)
    if silu:
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


@register_op("causal_conv1d")
def _causal_conv1d(ins, attrs):
    """Depthwise causal convolution along a sequence (`causal_conv1d`
    above): X [B, S, C], Filter [C, K], Bias [C] where given;
    `activation` `silu` is applied where named."""
    return {"Out": causal_conv1d(
        ins["X"][0], ins["Filter"][0],
        ins["Bias"][0] if ins.get("Bias") else None,
        attrs.get("activation", "") == "silu")}


# ---------------------------------------------------------------------------
# The chunked state-space scan (Mamba-2, arXiv:2405.21060, section 6)
# ---------------------------------------------------------------------------

def _ssd_group(xdt, cs, bm, cm, chunk):
    """One B/C group in `jax.numpy`. xdt [B, S, Hg, P] (inputs times
    step sizes), bm/cm [B, S, N], cs [B, S, Hg] float32 (cumulative
    `dt * A` inside each chunk). Inside a chunk a masked,
    decay-weighted (C B^T) product; between chunks a scan over the
    chunk states [Hg, P, N], kept float32."""
    with _part("local"):
        b, s, hg, p = xdt.shape
        n = bm.shape[-1]
        nc, cd = s // chunk, xdt.dtype
        x5 = jnp.transpose(xdt.reshape(b, nc, chunk, hg, p), (0, 1, 3, 2, 4))
        b4, c4 = bm.reshape(b, nc, chunk, n), cm.reshape(b, nc, chunk, n)
        cs4 = jnp.transpose(cs.reshape(b, nc, chunk, hg), (0, 1, 3, 2))
        cb = jnp.einsum("bcln,bcsn->bcls", c4, b4,
                        preferred_element_type=_F32)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            causal, cs4[..., :, None] - cs4[..., None, :], -jnp.inf))
        y = jnp.einsum("bchls,bchsp->bchlp",
                       (cb[:, :, None] * decay).astype(cd), x5,
                       preferred_element_type=_F32)
        last = cs4[..., -1:]                             # [B, nc, Hg, 1]
        xw = (x5.astype(_F32) * jnp.exp(last - cs4)[..., None]).astype(cd)
        states = jnp.einsum("bcsn,bchsp->bchpn", b4, xw,
                            preferred_element_type=_F32)

        def step(prev, inp):
            st, dec = inp
            return prev * dec[..., None, None] + st, prev

        _, before = lax.scan(
            step, jnp.zeros((b, hg, p, n), _F32),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(last[..., 0]),
                                                      1, 0)))
        before = jnp.moveaxis(before, 0, 1)              # [B, nc, Hg, P, N]
        y = y + jnp.exp(cs4)[..., None] * jnp.einsum(
            "bcln,bchpn->bchlp", c4, before.astype(cd),
            preferred_element_type=_F32)
        return jnp.transpose(y, (0, 1, 3, 2, 4)).reshape(
            b, s, hg, p).astype(cd)


def _by_group(fn, bm, cm, *by_head):
    """`fn(*by_head_g, bm_g, cm_g)` for one group after another
    (`lax.map`: one group's [L, L] intermediates live at a time);
    `by_head` are [B, S, H, ...], `bm`/`cm` [B, S, G, N]. Every result
    comes back with the group axis first."""
    with _part("groups"):
        g = bm.shape[2]
        split = lambda t: jnp.moveaxis(  # noqa: E731
            t.reshape(t.shape[:2] + (g, -1) + t.shape[3:]), 2, 0)
        return lax.map(lambda a: fn(*a), tuple(map(split, by_head)) + (
            jnp.moveaxis(bm, 2, 0), jnp.moveaxis(cm, 2, 0)))


def _join_groups(t):
    """[G, B, S, Hg, ...] -> [B, S, G * Hg, ...]"""
    with _part("groups"):
        t = jnp.moveaxis(t, 0, 2)
        return t.reshape(t.shape[:2] + (-1,) + t.shape[4:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(xdt, bm, cm, cs, chunk, kernel):
    if kernel:
        from .pallas.ssd_scan import ssd_chunk_scan_fwd

        with _part("kernel"):
            return ssd_chunk_scan_fwd(xdt, bm, cm, cs, chunk)
    return _join_groups(_by_group(
        functools.partial(_ssd_group, chunk=chunk), bm, cm, xdt, cs))


def _ssd_fwd(xdt, bm, cm, cs, chunk, kernel):
    return _ssd(xdt, bm, cm, cs, chunk, kernel), (xdt, bm, cm, cs)


def _ssd_bwd(chunk, kernel, res, dy):
    """Group by group: the group's forward made again in `jax.numpy`
    and transposed, so that no [L, L] value outlives its group."""
    xdt, bm, cm, cs = res

    def one(x_g, cs_g, dy_g, b_g, c_g):
        return jax.vjp(functools.partial(_ssd_group, chunk=chunk),
                       x_g, cs_g, b_g, c_g)[1](dy_g)

    dx, dcs, db, dc = _by_group(one, bm, cm, xdt, cs, dy)
    with _part("groups"):
        return (_join_groups(dx), jnp.moveaxis(db, 0, 2),
                jnp.moveaxis(dc, 0, 2), _join_groups(dcs))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_chunk_scan(x, dt, dt_bias, a_log, bm, cm, d, chunk, kernel=None):
    """y_t = S_t C_t + D x_t with S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t B_t^T, a head at a time: x [B, S, H, P], dt [B, S, H]
    (before `softplus`), bm/cm [B, S, G, N] (head j reads group
    j // (H / G)), dt_bias, a_log, d [H]. The step sizes, the decays and
    their sums are float32. `kernel` None takes the Pallas forward on a
    TPU and `jax.numpy` elsewhere."""
    b, s, h, p = x.shape
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    with _part("groups"):
        step = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
        a = -jnp.exp(a_log.astype(_F32))
        xdt = (x.astype(_F32) * step[..., None]).astype(x.dtype)
        pad = -s % chunk
        if pad:
            # a padded position has a step of nought: it decays nothing
            # and adds nothing
            xdt, bm, cm, step = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (xdt, bm, cm, step))
        da = (step * a).reshape(b, -1, chunk, h)
        cs = jnp.cumsum(da, axis=2).reshape(b, -1, h)
    y = _ssd(xdt, bm, cm, cs, chunk, bool(kernel))[:, :s]
    with _part("groups"):
        return (y.astype(_F32)
                + d.astype(_F32)[:, None] * x.astype(_F32)).astype(x.dtype)


@register_op("ssd_chunk_scan")
def _ssd_chunk_scan(ins, attrs):
    """The chunked state-space scan of a Mamba-2 mixer
    (`ssd_chunk_scan` above): X [B, S, H, P], Dt [B, S, H], B and C
    [B, S, G, N], DtBias, ALog, D [H]; `chunk_size` positions a chunk
    (a sequence that is no whole number of chunks is padded inside)."""
    return {"Out": ssd_chunk_scan(
        ins["X"][0], ins["Dt"][0], ins["DtBias"][0], ins["ALog"][0],
        ins["B"][0], ins["C"][0], ins["D"][0],
        int(attrs.get("chunk_size", 128)))}


# ---------------------------------------------------------------------------
# The chunked gated delta rule (Gated DeltaNet: arXiv:2412.06464, section
# 3.3; the WY form of arXiv:2406.06484, section 3)
# ---------------------------------------------------------------------------

#: positions a chunk, and the diagonal blocks its triangular system is
#: inverted in
_GDR_CHUNK, _GDR_BLOCK = 64, 16
#: the most one head group's [chunk, chunk] float32 values may take
_GDR_TILE_BYTES = 32 << 20
_HIGHEST = lax.Precision.HIGHEST


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, kernel=False):
    """(I + a)^-1 for strictly lower triangular a [..., C, C], float32,
    in matrix products alone. With d the diagonal `_GDR_BLOCK`-blocks of
    a, d^16 = 0, so (I + d)^-1 = (I - d)(I + d^2)(I + d^4)(I + d^8);
    then I + a = (I + d)(I + m) with m = (I + d)^-1 (a - d) strictly
    block-lower, m^(C/16) = 0, and (I + m)^-1 the same product over m's
    powers. Two short series and not one of C terms: the powers of a
    whole chunk's matrix grow as binomials before they cancel.
    `kernel`: the same two series in one Pallas call
    (`pallas/gated_delta_rule.unit_lower_inverse`), its products kept in
    VMEM where these ten each go through HBM.

    The gradient is the inverse's own (`_unit_lower_inverse_bwd`), not
    the two series transposed: it needs T alone, in two products where
    the series' transpose runs twenty and keeps every power."""
    with _part("inverse"):
        if kernel:
            from .pallas.gated_delta_rule import unit_lower_inverse

            return unit_lower_inverse(a, _GDR_BLOCK)
        c = a.shape[-1]
        eye = jnp.eye(c, dtype=a.dtype)
        block = jnp.arange(c) // _GDR_BLOCK
        mm = functools.partial(jnp.matmul, precision=_HIGHEST)

        def series(x, nilpotent):
            inv, power = eye - x, x
            for _ in range(max(0, (nilpotent - 1).bit_length() - 1)):
                power = mm(power, power)
                inv = mm(inv, eye + power)
            return inv

        d = jnp.where(block[:, None] == block[None, :], a, 0.0)
        d_inv = series(d, _GDR_BLOCK)
        return mm(series(mm(d_inv, a - d), -(-c // _GDR_BLOCK)), d_inv)


def _unit_lower_inverse_fwd(a, kernel):
    t = _unit_lower_inverse(a, kernel)
    return t, t


def _unit_lower_inverse_bwd(kernel, t, d_t):
    """T = (I + a)^-1 has dT = -T da T, so d_a = -T^T d_T T^T: two
    float32 products. Kept to the strict lower triangle, where a lives:
    off it the truncated series is not the inverse, and its transpose
    and this formula differ."""
    with _part("inverse"):
        d_a = -jnp.einsum(
            "...ji,...jl->...il", t,
            jnp.einsum("...jk,...lk->...jl", d_t, t, precision=_HIGHEST),
            precision=_HIGHEST)
        return (jnp.tril(d_a, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _gdr_inverse(kk, gc, beta, cd, kernel=False):
    """(T = (I + tril(diag(beta) K K^T . decay, -1))^-1 at `cd`, decay)
    for kk = K K^T [B, N, H, 1, C, C] float32 and decay_ij =
    exp(gc_i - gc_j) on and under the diagonal, nought above it;
    `kernel`: T from the Pallas kernel."""
    with _part("inverse"):
        chunk = gc.shape[-1]
        rows, cols = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(
            rows >= cols, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        return _unit_lower_inverse(jnp.where(
            rows > cols, beta[..., None] * decay * kk, 0.0),
            kernel).astype(cd), decay


def _gdr_decayed(x, k, gc):
    """sum_d x_id k_jd exp(gc_id - gc_jd) for j <= i, nought above, of
    a decay per key channel: x, k [B, N, H, C, dk], gc [B, N, H, R, C,
    dk] float32 -> [B, N, H, R, C, C] float32. No exponent taken is
    positive, whatever the decays (x exp(gc) times k exp(-gc) overflows
    float32 once a channel's decay over the chunk passes 88). Each
    `_GDR_BLOCK` of rows is anchored at its first position r: below its
    diagonal block, (x_i exp(gc_i - gc_r)) . (k_j exp(gc_r - gc_j)), one
    product at x's dtype for the block's rows against every earlier
    column; on it, the sum over channels in float32. The block is the
    inverse's, 16: the diagonal blocks' work a chunk is C x 16 x dk
    exponentials, a quarter of a whole [C, C, dk]'s, and the products
    below them stay four [16, dk] x [dk, C] a chunk."""
    with _part("decay"):
        c, dk = gc.shape[-2:]
        nb, blk = c // _GDR_BLOCK, _GDR_BLOCK
        lead = gc.shape[:-2]
        xf, kf = (t.astype(_F32)[:, :, :, None] for t in (x, k))
        g = gc.reshape(lead + (nb, blk, dk))
        anchor = g[..., :1, :]
        left = (xf.reshape(xf.shape[:-2] + (nb, blk, dk))
                * jnp.exp(g - anchor)).astype(x.dtype)
        earlier = (jnp.arange(c)[None, :]
                   < (jnp.arange(nb) * blk)[:, None])[..., None]
        right = (kf[..., None, :, :] * jnp.exp(jnp.where(
            earlier, anchor - gc[..., None, :, :], -jnp.inf))).astype(x.dtype)
        below = jnp.einsum("...nid,...njd->...nij", left, right,
                           preferred_element_type=_F32)
        seen = (jnp.arange(blk)[:, None] >= jnp.arange(blk)[None, :])[
            ..., None]
        inside = jnp.sum(
            xf.reshape(xf.shape[:-2] + (nb, blk, 1, dk))
            * kf.reshape(kf.shape[:-2] + (nb, 1, blk, dk))
            * jnp.exp(jnp.where(seen, g[..., :, None, :] - g[..., None, :, :],
                                -jnp.inf)), axis=-1)
        on = jnp.eye(nb, dtype=_F32)[:, None, :, None]
        return below.reshape(lead + (c, c)) + (
            inside[..., :, :, None, :] * on).reshape(lead + (c, c))


@jax.custom_vjp
def _gdr_decayed_kernel(q, k, gc):
    """`_gdr_decayed`'s two products of a chunk in one Pallas call
    (`pallas/gated_delta_rule.gated_delta_rule_decay`): (K K^T decayed,
    strictly lower, float32; Q K^T decayed, causal, at q's dtype), the
    exponentials kept in VMEM. Its transpose is the reverse kernel's,
    which makes them again from q, k and gc."""
    from .pallas.gated_delta_rule import gated_delta_rule_decay

    with _part("decay"):
        return gated_delta_rule_decay(q, k, gc, _GDR_BLOCK)


def _gdr_decayed_kernel_fwd(q, k, gc):
    return _gdr_decayed_kernel(q, k, gc), (q, k, gc)


def _gdr_decayed_kernel_bwd(res, d):
    from .pallas.gated_delta_rule import gated_delta_rule_decay_bwd

    with _part("decay"):
        return gated_delta_rule_decay_bwd(*res, *d, _GDR_BLOCK)


_gdr_decayed_kernel.defvjp(_gdr_decayed_kernel_fwd, _gdr_decayed_kernel_bwd)


def _gdr_system_channels(q, k, gc, beta, kernel=False):
    """(T = (I + tril(diag(beta) A, -1))^-1, the causal Q K^T decayed)
    at q's dtype for A the decayed K K^T of a decay per key channel
    (`_gdr_decayed`); `kernel`: both products from the Pallas pair
    (`_gdr_decayed_kernel`) and T from the inverse kernel."""
    c = gc.shape[-2]
    if kernel:
        kk, aqk = _gdr_decayed_kernel(q, k, gc)
    with _part("inverse"):
        t = _unit_lower_inverse(jnp.where(
            jnp.arange(c)[:, None] > jnp.arange(c)[None, :],
            beta[..., None] * (kk if kernel else _gdr_decayed(k, k, gc)),
            0.0), kernel).astype(q.dtype)
    if kernel:
        return t, aqk
    with _part("decay"):
        return t, _gdr_decayed(q, k, gc).astype(q.dtype)


def _gdr_local_channels(q, k, v, gc, beta, kernel=False):
    """`_gdr_local` of a decay per key channel: gc [B, N, H, R, C, dk].
    The same six values, the decay's products anchored
    (`_gdr_decayed`), gl = exp(gc_C) [B, N, H, R, dk] a factor a row of
    the state."""
    with _part("local"):
        cd = q.dtype
        t, aqk = _gdr_system_channels(q, k, gc, beta, kernel)
        with _part("decay"):
            kf, grow = k.astype(_F32)[:, :, :, None], jnp.exp(gc)
            last = gc[..., -1:, :]
            kg = (kf * (beta[..., None] * grow)).astype(cd)
            qg = (q.astype(_F32)[:, :, :, None] * grow).astype(cd)
            kd = (kf * jnp.exp(last - gc)).astype(cd)
        solve = functools.partial(jnp.einsum, "bnhrij,bnhrjd->bnhrid", t,
                                  preferred_element_type=_F32)
        return (solve(kg).astype(cd),
                solve((v.astype(_F32) * beta[..., None]).astype(cd)),
                aqk, qg, kd, jnp.exp(last[..., 0, :]))


def _gdr_local(q, k, v, gc, beta, kernel=False):
    """What a chunk's positions need of one another, every chunk at
    once. q, k [B, N, H, C, dk]; v [B, N, H, R, C, dv] (R value heads a
    key head); gc (the log-decays summed from the chunk's start) and
    beta [B, N, H, R, C] float32. With T of `_gdr_inverse` (`kernel`:
    made by the Pallas kernel), returns
    W = T (beta exp(gc) K), U0 = T (beta V) (a chunk's corrections are
    U0 - W S for the state S at its start), the causal Q K^T . decay,
    Q exp(gc), K exp(gc_C - gc) and exp(gc_C). A gc of [B, N, H, R, C,
    dk] is a decay per key channel: `_gdr_local_channels`."""
    if gc.ndim == q.ndim + 1:
        return _gdr_local_channels(q, k, v, gc, beta, kernel)
    with _part("local"):
        cd = q.dtype
        kk, qk = (jnp.einsum("bnhid,bnhjd->bnhij", x, k,
                             preferred_element_type=_F32)[:, :, :, None]
                  for x in (k, q))
        t, decay = _gdr_inverse(kk, gc, beta, cd, kernel)
        kf, grow = k.astype(_F32)[:, :, :, None], jnp.exp(gc)[..., None]
        solve = functools.partial(jnp.einsum, "bnhrij,bnhrjd->bnhrid", t,
                                  preferred_element_type=_F32)
        w = solve((kf * (beta[..., None] * grow)).astype(cd)).astype(cd)
        u0 = solve((v.astype(_F32) * beta[..., None]).astype(cd))
        last = gc[..., -1:]
        return (w, u0, (qk * decay).astype(cd),
                (q.astype(_F32)[:, :, :, None] * grow).astype(cd),
                (kf * jnp.exp(last - gc)[..., None]).astype(cd),
                jnp.exp(last[..., 0]))


def _chunked(t, chunk, heads):
    """[B, S, heads * R, ...] -> [B, N, heads, R, chunk, ...]"""
    with _part("groups"):
        b, s = t.shape[:2]
        t = t.reshape((b, s // chunk, chunk, heads, -1) + t.shape[3:])
        return jnp.moveaxis(t, 2, 4)


def _unchunked(t):
    """[B, N, H, R, chunk, ...] -> [B, S, H * R, ...]"""
    with _part("groups"):
        t = jnp.moveaxis(t, 4, 2)
        return t.reshape(
            (t.shape[0], t.shape[1] * t.shape[2], -1) + t.shape[5:])


def _gdr_group_inputs(q, k, v, gc, beta):
    with _part("groups"):
        hk = q.shape[2]
        q, k = (_chunked(x, _GDR_CHUNK, hk)[:, :, :, 0] for x in (q, k))
        return (q, k) + tuple(_chunked(x, _GDR_CHUNK, hk)
                              for x in (v, gc, beta))


def _chunks_first(*ts):
    return tuple(jnp.moveaxis(t, 1, 0) for t in ts)


def _decays(gl, state):
    """The state [B, H, R, dk, dv] decayed by a chunk's gl: one factor a
    head [B, H, R], or one a row, a key channel, [B, H, R, dk]."""
    if gl.ndim == 4:
        return gl[..., None] * state
    return gl[..., None, None] * state


def _gdr_walk_fwd(w, u0, aqk, qg, kd, gl):
    """The chunks of `_gdr_local`'s values walked first to last by a
    `lax.scan`: (out [N, B, H, R, C, dv], the state at every chunk's
    start [N, B, H, R, dk, dv] float32), the chunk axis first as the
    scan stacks them."""
    with _part("walk"):
        cd = w.dtype

        def step(state, xs):
            w_c, u0_c, aqk_c, qg_c, kd_c, gl_c = xs
            low = state.astype(cd)
            u = (u0_c - jnp.einsum("bhrid,bhrde->bhrie", w_c, low,
                                   preferred_element_type=_F32)).astype(cd)
            out = (jnp.einsum("bhrid,bhrde->bhrie", qg_c, low,
                              preferred_element_type=_F32)
                   + jnp.einsum("bhrij,bhrje->bhrie", aqk_c, u,
                                preferred_element_type=_F32))
            after = _decays(gl_c, state) + jnp.einsum(
                "bhrid,bhrie->bhrde", kd_c, u, preferred_element_type=_F32)
            return after, (out.astype(cd), state)

        b, _, h, r, _, dk = qg.shape
        return lax.scan(
            step, jnp.zeros((b, h, r, dk, u0.shape[-1]), _F32),
            _chunks_first(w, u0, aqk, qg, kd, gl))[1]


def _gdr_walk_bwd(w, aqk, qg, kd, gl, u, starts, d_out):
    """The same chunks walked last to first, the state's cotangent
    carried: (d_u float32, d_kd, d_gl), a chunk each."""
    with _part("walk"):
        cd = w.dtype

        def step(d_state, xs):
            w_c, aqk_c, qg_c, kd_c, gl_c, u_c, start_c, do_c = xs
            d_low = d_state.astype(cd)
            d_u = (jnp.einsum("bhrij,bhrie->bhrje", aqk_c, do_c,
                              preferred_element_type=_F32)
                   + jnp.einsum("bhrjd,bhrde->bhrje", kd_c, d_low,
                                preferred_element_type=_F32))
            d_kd = jnp.einsum("bhrje,bhrde->bhrjd", u_c, d_low,
                              preferred_element_type=_F32)
            d_gl = jnp.sum(d_state * start_c,
                           axis=(-1,) if gl_c.ndim == 4 else (-2, -1))
            before = (jnp.einsum("bhrid,bhrie->bhrde", qg_c, do_c,
                                 preferred_element_type=_F32)
                      + _decays(gl_c, d_state)
                      - jnp.einsum("bhrid,bhrie->bhrde", w_c, d_u.astype(cd),
                                   preferred_element_type=_F32))
            return before, (d_u, d_kd.astype(cd), d_gl)

        _, walked = lax.scan(
            step, jnp.zeros(starts.shape[:1] + starts.shape[2:], _F32),
            _chunks_first(w, aqk, qg, kd, gl, u, starts, d_out), reverse=True)
        return tuple(jnp.moveaxis(t, 0, 1) for t in walked)


def _gdr_group_fwd(q, k, v, gc, beta):
    """One group of key heads: (out [B, S, Hv, dv], the state at every
    chunk's start [B, N, H, R, dk, dv] float32)."""
    out, starts = _gdr_walk_fwd(
        *_gdr_local(*_gdr_group_inputs(q, k, v, gc, beta)))
    with _part("groups"):
        return _unchunked(jnp.moveaxis(out, 0, 1)), jnp.moveaxis(starts, 0, 1)


def _gdr_group_fwd_kernel(into, q, k, v, gc, beta):
    """The same through the forward kernel, which is handed the
    triangular system's inverse (the inverse kernel's) and makes what
    else a chunk's positions need of one another where it walks.
    `into`: the stack of every group's chunk states and this group's
    place in it, or None where no state is kept. Returns (out, stack)."""
    from .pallas.gated_delta_rule import gated_delta_rule_fwd

    q, k, v, gc, beta = _gdr_group_inputs(q, k, v, gc, beta)
    if gc.ndim == q.ndim + 1:
        # a decay per key channel: the decayed Q K^T is handed in too
        with _part("local"):
            t, aqk = _gdr_system_channels(q, k, gc, beta, True)
        with _part("walk"):
            out, stack = gated_delta_rule_fwd(q, k, v, t, gc, beta, into,
                                              aqk=aqk)
        return _unchunked(out), stack
    with _part("local"):
        kk = jnp.einsum("bnhid,bnhjd->bnhij", k, k,
                        preferred_element_type=_F32)[:, :, :, None]
    with _part("walk"):
        out, stack = gated_delta_rule_fwd(
            q, k, v, _gdr_inverse(kk, gc, beta, q.dtype, True)[0], gc, beta,
            into)
    return _unchunked(out), stack


def _gdr_group_bwd(q, k, v, gc, beta, starts, d_out, group=None):
    """The group's chunk-local values made again and transposed: a
    second walk, from the last chunk to the first, carries the state's
    cotangent; what it needs of a chunk is linear in that state. The
    transpose of `_gdr_local` goes through the triangular system by the
    inverse's own gradient (`_unit_lower_inverse_bwd`): of the system
    it keeps T alone, across the walk, and runs two [C, C] products
    where the series were twenty. With
    `group`, `starts` is the stack of every group's chunk states, this
    group's at that place, and the kernels make T again and walk (the
    reverse one reads the states where they lie); without, the
    `jax.numpy` series and a `lax.scan` do."""
    cd = q.dtype
    ins = _gdr_group_inputs(q, k, v, gc, beta)
    (w, u0, aqk, qg, kd, gl), local_vjp = jax.vjp(
        functools.partial(_gdr_local, kernel=group is not None), *ins)
    d_out = _chunked(d_out, _GDR_CHUNK, q.shape[2])
    stack = starts
    with _part("groups"):
        if group is not None:
            starts = lax.dynamic_index_in_dim(stack, group, keepdims=False)
    with _part("local"):
        low = starts.astype(cd)
        u = (u0 - jnp.einsum("bnhrid,bnhrde->bnhrie", w, low,
                             preferred_element_type=_F32)).astype(cd)
    with _part("walk"):
        if group is not None:
            from .pallas.gated_delta_rule import gated_delta_rule_bwd

            # a loop stands between what is made before it and after it;
            # a kernel's call does not, and the compiler then schedules the
            # transposed products round it: 7 ms a step (9 and 0.2 GB while
            # the inverse's series were transposed too)
            w_, aqk_, qg_, kd_, gl_, u_, do_ = lax.optimization_barrier(
                (w, aqk, qg, kd, gl, u, d_out))
            d_u, d_kd, d_gl = lax.optimization_barrier(gated_delta_rule_bwd(
                w_, aqk_, qg_, kd_, gl_, u_, stack, group, do_))
        else:
            d_u, d_kd, d_gl = _gdr_walk_bwd(w, aqk, qg, kd, gl, u, starts,
                                            d_out)
    with _part("local"):
        d_uc = d_u.astype(cd)
        d_w = -jnp.einsum("bnhrie,bnhrde->bnhrid", d_uc, low,
                          preferred_element_type=_F32)
        d_aqk = jnp.einsum("bnhrie,bnhrje->bnhrij", d_out, u,
                           preferred_element_type=_F32)
        d_qg = jnp.einsum("bnhrie,bnhrde->bnhrid", d_out, low,
                          preferred_element_type=_F32)
        d_q, d_k, d_v, d_gc, d_beta = local_vjp(
            (d_w.astype(cd), d_u, d_aqk.astype(cd), d_qg.astype(cd), d_kd,
             d_gl))
    return (_unchunked(d_q[:, :, :, None]), _unchunked(d_k[:, :, :, None]),
            _unchunked(d_v), _unchunked(d_gc), _unchunked(d_beta))


def _gdr_groups(b, n_chunks, hk, r):
    """Groups of key heads the op walks one after another: the fewest
    that keep a group's [chunk, chunk] float32 values (one per value
    head and chunk) under `_GDR_TILE_BYTES`."""
    tile = b * n_chunks * r * _GDR_CHUNK * _GDR_CHUNK * 4
    return next(g for g in range(1, hk + 1)
                if hk % g == 0 and tile * (hk // g) <= _GDR_TILE_BYTES
                or g == hk)


def _head_groups(groups, *ts):
    """[B, S, H, ...] -> [groups, B, S, H / groups, ...] of each"""
    with _part("groups"):
        return tuple(jnp.moveaxis(t.reshape(
            t.shape[:2] + (groups, -1) + t.shape[3:]), 2, 0) for t in ts)


def _gdr_grouped(q, k, v, gc, beta):
    groups = _gdr_groups(q.shape[0], q.shape[1] // _GDR_CHUNK, q.shape[2],
                         v.shape[2] // q.shape[2])
    return _head_groups(groups, q, k, v, gc, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdr(q, k, v, gc, beta, kernel):
    if kernel:
        # a kernel's output cannot be dropped where nobody reads it
        with _part("groups"):
            return _join_groups(lax.map(
                lambda a: _gdr_group_fwd_kernel(None, *a)[0],
                _gdr_grouped(q, k, v, gc, beta)))
    return _gdr_fwd(q, k, v, gc, beta, kernel)[0]


def _gdr_fwd(q, k, v, gc, beta, kernel):
    grouped = _gdr_grouped(q, k, v, gc, beta)
    with _part("groups"):
        if kernel:
            # each group's walk writes its states into the one stack
            groups, b, s, hk, dk = grouped[0].shape
            hv, dv = grouped[2].shape[3:]
            starts, out = lax.scan(
                lambda stack, a: _gdr_group_fwd_kernel((stack, a[0]), *a[1:])[
                    ::-1],
                lax.empty((groups, b, s // _GDR_CHUNK, hk, hv // hk, dk, dv),
                          _F32),
                (jnp.arange(groups),) + grouped)
        else:
            out, starts = lax.map(lambda a: _gdr_group_fwd(*a), grouped)
    return _join_groups(out), (q, k, v, gc, beta, starts)


def _gdr_bwd(kernel, res, d_out):
    *ins, starts = res
    groups = starts.shape[0]
    *ins, d_out = _head_groups(groups, *ins, d_out)
    with _part("groups"):
        if kernel:
            grads = lax.map(
                lambda a: _gdr_group_bwd(*a[1:6], starts, a[6], group=a[0]),
                (jnp.arange(groups), *ins, d_out))
        else:
            grads = lax.map(lambda a: _gdr_group_bwd(*a),
                            (*ins, starts, d_out))
    return tuple(_join_groups(g) for g in grads)


_gdr.defvjp(_gdr_fwd, _gdr_bwd)


def gated_delta_rule(q, k, v, g, beta, kernel=None):
    """o_t = S_t^T q_t for the state S in R^{dk x dv} of each value
    head, S_0 = 0: S' = exp(g_t) S_{t-1}; u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T: the state decays, is read back at the new
    key, and takes the part of the value it did not already hold.
    q, k [B, S, Hk, dk]; v [B, S, Hv, dv], value head j reading key
    head j // (Hv / Hk); g (log-decay, <= 0) and beta [B, S, Hv]. A g
    of [B, S, Hv, dk] decays each key channel on its own (Kimi Delta
    Attention, arXiv:2510.26692): S' = Diag(exp(g_t)) S_{t-1}, the
    chunk-local products anchored so that no exponent is positive
    (`_gdr_decayed`).

    Computed `_GDR_CHUNK` positions a chunk (a sequence that is no
    whole number of chunks is padded with positions that decay nothing
    and write nothing): inside a chunk the corrections u solve a unit
    lower triangular system (`_gdr_local`), between chunks a walk
    carries the state. Log-decays, their sums, the system's inverse
    and the states are float32; the products run at the inputs' dtype
    with float32 accumulation. The backward pass is the op's own
    (`_gdr_group_bwd`, a second walk from the last chunk to the
    first): it keeps the inputs and the state at every chunk's start,
    nothing [S, S]-shaped and nothing a position. `kernel` None has
    the two walks made by the Pallas kernels of
    `pallas/gated_delta_rule.py` on a TPU where dk and dv are whole
    lane tiles (multiples of 128), and by `lax.scan` elsewhere. There
    the triangular system's inverse (float32 products at `HIGHEST`) is
    made by the Pallas kernel too, wherever the forward pass, its
    recompute or the backward pass needs it, and by the `jax.numpy`
    series elsewhere; its gradient is the formula d_a = -T^T d_T T^T,
    not the series transposed, either way. The backward pass's other
    chunk-local values are `jax.numpy`; the forward kernel makes the
    rest of a chunk's local values itself."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if hv % hk or k.shape != q.shape or g.shape not in ((b, s, hv),
                                                         (b, s, hv, dk)):
        raise ValueError("gated_delta_rule: q %s k %s v %s g %s beta %s"
                         % (q.shape, k.shape, v.shape, g.shape, beta.shape))
    pad = -s % _GDR_CHUNK
    n = (s + pad) // _GDR_CHUNK
    with _part("head"):
        g, beta = g.astype(_F32), beta.astype(_F32)
        if pad:
            q, k, v, g, beta = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (q, k, v, g, beta))
        gc = jnp.cumsum(g.reshape((b, n, _GDR_CHUNK) + g.shape[2:]),
                        axis=2).reshape((b, n * _GDR_CHUNK) + g.shape[2:])
    groups = _gdr_groups(b, n, hk, hv // hk)
    if kernel is None:
        kernel = (jax.default_backend() == "tpu" and dk % 128 == 0
                  and dv % 128 == 0)
    walked = inverted = decayed = "jax.numpy"
    if kernel:
        from .pallas.gated_delta_rule import heads_a_step, systems_a_step

        heads = heads_a_step(hk // groups, hv // hk, _GDR_CHUNK, dk, dv,
                             q.dtype.itemsize)
        walked = "pallas (%d value heads a grid step, %d grid steps a call)" \
            % (heads, b * (hv // groups // heads) * n)
        systems = b * n * hv // groups
        step = systems_a_step(systems, _GDR_CHUNK)
        inverted = "pallas (%d systems a grid step, %d grid steps a call)" \
            % (step, -(-systems // step))
        heads = heads_a_step(hk // groups, hv // hk, _GDR_CHUNK, dk, dk,
                             q.dtype.itemsize)
        decayed = "pallas (%d value heads a grid step, %d grid steps a " \
            "call)" % (heads, b * (hv // groups // heads) * n)
    # said where the op is traced: at the build's shape inference and
    # once a compile and layer
    logging.getLogger(__name__).info(
        "gated_delta_rule q, k %s v %s %s%s: %d positions a chunk, %d "
        "chunks, %d head groups, the chunks walked by %s, the inverse by "
        "%s%s; kept for the backward pass %d bytes (the inputs and %d "
        "states [%d, %d] float32)",
        tuple(q.shape), tuple(v.shape), q.dtype.name,
        ", a decay per key channel" if g.ndim == 4 else "", _GDR_CHUNK, n,
        groups,
        walked, inverted,
        ", the decayed products by " + decayed if g.ndim == 4 else "",
        sum(t.size * t.dtype.itemsize for t in (q, k, v, gc, beta))
        + b * n * hv * dk * dv * 4, b * n * hv, dk, dv)
    return _gdr(q, k, v, gc, beta, bool(kernel))[:, :s]


@register_op("gated_delta_rule")
def _gated_delta_rule(ins, attrs):
    """The gated delta rule of a Gated DeltaNet mixer
    (`gated_delta_rule` above): Q, K [B, S, Hk, dk], V [B, S, Hv, dv];
    A and B [B, S, Hv], the projections behind the decay and the
    writing strength; ALog, DtBias [Hv]. beta = sigmoid(B) and
    g = -exp(ALog) softplus(A + DtBias), both float32. A of
    [B, S, Hv, dk] with DtBias [Hv * dk] is a decay per key channel,
    ALog's a head's factor over its channels."""
    with _part("head"):
        if ins["A"][0].ndim == 4:
            a = ins["A"][0].astype(_F32)
            g = -jnp.exp(ins["ALog"][0].astype(_F32))[:, None] \
                * jax.nn.softplus(a + ins["DtBias"][0].astype(_F32).reshape(
                    a.shape[2:]))
        else:
            g = -jnp.exp(ins["ALog"][0].astype(_F32)) * jax.nn.softplus(
                ins["A"][0].astype(_F32) + ins["DtBias"][0].astype(_F32))
        beta = jax.nn.sigmoid(ins["B"][0].astype(_F32))
    return {"Out": gated_delta_rule(
        ins["Q"][0], ins["K"][0], ins["V"][0], g, beta)}


# ---------------------------------------------------------------------------
# Routed experts
# ---------------------------------------------------------------------------

@register_op("moe_router")
def _moe_router(ins, attrs):
    """Scores s = sigmoid(X W), or softmax(X W) where `score_function`
    says so, over ALL experts in float32; the `top_k` largest of
    s + Bias (Bias steers the choice only and gets no gradient);
    weights s_k / sum_k s_k where `norm_topk_prob`, times
    `routed_scaling_factor`. X [..., H] -> TopkIdx [T, k] int32,
    TopkWeight [T, k] float32, T the flattened leading axes."""
    x, w = ins["X"][0], ins["W"][0]
    k = int(attrs["top_k"])
    x2 = x.reshape(-1, x.shape[-1]).astype(_F32)
    score = {"sigmoid": jax.nn.sigmoid, "softmax": functools.partial(
        jax.nn.softmax, axis=-1)}[attrs.get("score_function", "sigmoid")]
    s = score(jnp.dot(x2, w.astype(_F32), precision=lax.Precision.HIGHEST))
    pick = s
    if ins.get("Bias"):
        pick = s + lax.stop_gradient(ins["Bias"][0].astype(_F32))
    _, idx = lax.top_k(lax.stop_gradient(pick), k)
    weight = jnp.take_along_axis(s, idx, axis=1)
    if attrs.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * float(attrs.get("routed_scaling_factor", 1.0))
    return {"TopkIdx": idx.astype(jnp.int32), "TopkWeight": weight}


def _tile(n, most=1024):
    """The largest multiple of 128 up to `most` that divides n, else
    the largest up to `most` (the kernel masks the remainder)."""
    for t in range(most, 0, -128):
        if n % t == 0:
            return t
    return min(most, -(-n // 128) * 128)


def _megablox():
    """The Pallas grouped products of `jax.experimental` without their
    `jit` wrappers: under a wrapper the compiled kernel is named after
    it (`gmm`), here after the scope it is called in, so a trace names
    the op type."""
    import importlib

    # the package re-exports a function under the module's own name
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    return backend.gmm.__wrapped__, backend.tgmm.__wrapped__


def _grouped_product(lhs, rhs, sizes, transpose_rhs=False):
    """Rows of `lhs` [R, K], sorted by group, times their group's
    matrix of `rhs` [G, K, N] ([G, N, K] where `transpose_rhs`); rows
    past sum(sizes) are not computed and hold anything. On a TPU the
    Pallas grouped product (work by the rows there are), elsewhere
    `lax.ragged_dot`."""
    if jax.default_backend() != "tpu":
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        return lax.ragged_dot(lhs, rhs, sizes).astype(lhs.dtype)
    gmm, _ = _megablox()
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    with jax.named_scope("moe_experts_gmm"):
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                   tiling=(min(512, lhs.shape[0]), _tile(k), _tile(n)),
                   transpose_rhs=transpose_rhs)


def _grouped_outer_product(acc, lhs, ct, sizes):
    """`acc` [G, K, N] float32 plus, a group, its rows of `lhs` [R, K]
    transposed times its rows of `ct` [R, N]: the gradient of a grouped
    product's matrices, summed in float32 where it lies."""
    if jax.default_backend() != "tpu":
        product = lambda w: lax.ragged_dot(  # noqa: E731
            lhs.astype(_F32), w, sizes, precision=lax.Precision.HIGHEST)
        return acc + jax.vjp(product, acc)[1](ct.astype(_F32))[0]
    _, tgmm = _megablox()
    k, n = lhs.shape[1], ct.shape[1]
    with jax.named_scope("moe_experts_tgmm"):
        return tgmm(lhs.swapaxes(0, 1), ct, sizes,
                    preferred_element_type=_F32,
                    # a float32 tile comes in and goes out: half the
                    # columns of the forward's fit the kernel's memory
                    tiling=(min(512, lhs.shape[0]), _tile(k),
                            _tile(n, 512)),
                    existing_out=acc)


def row_block(pairs, held, of):
    """Rows of the sorted pairs that one trip of the loop makes, for a
    layer of `pairs` (token, expert) pairs on a chip that holds `held`
    of `of` experts: what a uniform routing sends here and a third
    again, in whole 512-row tiles of the grouped product, so that a
    share near its mean goes through in one trip and a fuller one in
    as many as it needs (the nemotron cell's step at 8,192 rows a trip,
    which this gives there, 629.8 ms; at 2,048 639.7, at 4,096 645.0,
    at 1,024 647.6: PERF.md, PR 30)."""
    tiles = -(-pairs // 512)
    return 512 * min(tiles, -(-4 * pairs * held // (3 * 512 * of)))


def _window(i, order, sizes, block, k):
    """Row block i of the sorted pairs: (the pairs, their tokens, the
    share of each held expert's group that lies in the block, which
    rows lie before the last held pair)."""
    with _part("sort"):
        lo = i * block
        pairs = lax.dynamic_slice(order, (lo,), (block,))
        ends = jnp.cumsum(sizes)
        part = (jnp.clip(ends, lo, lo + block)
                - jnp.clip(ends - sizes, lo, lo + block))
        live = (lo + jnp.arange(block) < ends[-1])[:, None]
        return pairs, pairs // k, part, live


def _act(activation, x):
    return get_op(activation).compute({"X": [x]}, {})["Out"]


def _trips(sizes, block):
    with _part("sort"):
        return -(-jnp.sum(sizes) // block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed_rows(x, weight, w_up, w_down, order, sizes, block, activation):
    """The held experts' output [T, H], summed in float32: the sorted
    pairs `order` (held experts first, `sizes` pairs each) walked
    `block` rows at a time, as many blocks as hold a held pair."""
    k = weight.shape[1]
    scale = weight.reshape(-1).astype(_F32)

    def trip(i, out):
        pairs, tokens, part, live = _window(i, order, sizes, block, k)
        with _part("gather"):
            rows = jnp.take(x, tokens, axis=0)
        with _part("products"):
            mid = _act(activation, _grouped_product(rows, w_up, part))
            made = _grouped_product(mid, w_down, part).astype(_F32)
        with _part("scatter"):
            made = made * jnp.take(scale, pairs)[:, None]
            return out.at[tokens].add(jnp.where(live, made, 0.0))

    with _part("scatter"):
        return lax.fori_loop(0, _trips(sizes, block), trip,
                             jnp.zeros(x.shape, _F32)).astype(x.dtype)


def _routed_rows_fwd(x, weight, w_up, w_down, order, sizes, block,
                     activation):
    return (_routed_rows(x, weight, w_up, w_down, order, sizes, block,
                         activation),
            (x, weight, w_up, w_down, order, sizes))


def _routed_rows_bwd(block, activation, res, ct):
    """A second walk over the same row blocks: each block's rows and
    activations made again, then five products: the cotangent through
    the down matrices transposed (its row product with the activations
    is the routing weight's gradient, so the down product is not made
    again), the two matrices' gradients, summed in float32 over the
    blocks and rounded once, and the rows' through the up matrices."""
    x, weight, w_up, w_down, order, sizes = res
    k = weight.shape[1]
    scale = weight.reshape(-1).astype(_F32)

    def trip(i, carry):
        d_x, d_scale, d_up, d_down = carry
        pairs, tokens, part, live = _window(i, order, sizes, block, k)
        with _part("gather"):
            rows = jnp.take(x, tokens, axis=0)
        with _part("products"):
            mid, act_vjp = jax.vjp(functools.partial(_act, activation),
                                   _grouped_product(rows, w_up, part))
        with _part("gather"):
            ct_rows = jnp.take(ct, tokens, axis=0)
        with _part("products"):
            ct_mid = _grouped_product(ct_rows, w_down, part,
                                      True).astype(_F32)
        with _part("scatter"):
            d_scale = d_scale.at[pairs].add(jnp.sum(jnp.where(
                live, mid.astype(_F32) * ct_mid, 0.0), axis=1))
        with _part("gather"):
            by_pair = jnp.take(scale, pairs)[:, None]
        with _part("products"):
            d_down = _grouped_outer_product(
                d_down, mid, (ct_rows * by_pair).astype(x.dtype), part)
            ct_pre, = act_vjp((ct_mid * by_pair).astype(x.dtype))
            d_up = _grouped_outer_product(d_up, rows, ct_pre, part)
            d_rows = _grouped_product(ct_pre, w_up, part, True)
        with _part("scatter"):
            d_x = d_x.at[tokens].add(
                jnp.where(live, d_rows, 0).astype(_F32))
        return d_x, d_scale, d_up, d_down

    d_x, d_scale, d_up, d_down = lax.fori_loop(
        0, _trips(sizes, block), trip,
        (jnp.zeros(x.shape, _F32), jnp.zeros(scale.shape, _F32),
         jnp.zeros(w_up.shape, _F32), jnp.zeros(w_down.shape, _F32)))
    with _part("scatter"):
        return (d_x.astype(x.dtype),
                d_scale.reshape(weight.shape).astype(weight.dtype),
                d_up.astype(w_up.dtype), d_down.astype(w_down.dtype), None,
                None)


_routed_rows.defvjp(_routed_rows_fwd, _routed_rows_bwd)


def moe_experts(x, idx, weight, w_up, w_down, held_start=0,
                activation="relu2", num_experts=None):
    """The part of a routed layer's output that the experts
    [held_start, held_start + w_up.shape[0]) give: every pair routed to
    one of them is computed, none dropped. The (token, expert) pairs
    are sorted by expert, the pairs of experts held elsewhere last, and
    the held ones walked a row block (`row_block`, from the share of
    `num_experts` held) at a time, as many blocks as this step's
    routing fills (none where it sends nothing here, all T * k rows
    where it sends everything): a block's tokens gathered,
    the two products run as grouped products over the block's share of
    each expert's group, every row times its pair's float32 weight
    added to its token in float32. The backward pass walks the same
    blocks again (`_routed_rows_bwd`); only token-sized values are kept
    for it. Returns (out [T, H], pairs a held expert [E_held], rows
    made: blocks walked x rows a block)."""
    n_held = w_up.shape[0]
    of = num_experts or held_start + n_held
    block = row_block(idx.size, n_held, of)
    # said where the op is traced: at the build's shape inference and
    # once a compile and layer
    logging.getLogger(__name__).info(
        "moe_experts holds experts [%d, %d) of %d, top-%d: %d rows a "
        "trip, %d trips if every pair is held here", held_start,
        held_start + n_held, of, idx.shape[-1], block,
        -(-idx.size // block))
    with _part("sort"):
        local = idx.reshape(-1) - held_start             # [T * k]
        key = jnp.where((local >= 0) & (local < n_held), local, n_held)
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                        axis=0).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, -order.shape[0] % block))
    out = _routed_rows(x, weight, w_up, w_down, order, sizes, block,
                       activation)
    return out, sizes, _trips(sizes, block) * block


@register_op("moe_experts")
def _moe_experts(ins, attrs):
    """The held experts' part of a routed layer (`moe_experts` above).
    X [..., H]; TopkIdx, TopkWeight [T, k] from `moe_router`; WUp
    [E_held, H, F], WDown [E_held, F, H]; `held_start` the first held
    expert's number of `num_experts`, `activation` (a registered
    activation op) between the two products. Out like X; HeldPairs [1]
    (pairs computed here), LoadMaxOverMean [1] (the fullest held
    expert's pairs over the mean) and RowsMade [1] (rows of sorted
    pairs the op made: whole row blocks, so HeldPairs or more), float32
    counters of the step."""
    x, idx = ins["X"][0], ins["TopkIdx"][0]
    first, n_held = int(attrs.get("held_start", 0)), ins["WUp"][0].shape[0]
    of = int(attrs.get("num_experts", first + n_held))
    if first < 0 or first + n_held > of:
        raise ValueError("moe_experts holds experts [%d, %d) of %d"
                         % (first, first + n_held, of))
    out, sizes, made = moe_experts(
        x.reshape(-1, x.shape[-1]), idx, ins["TopkWeight"][0],
        ins["WUp"][0], ins["WDown"][0], first,
        attrs.get("activation", "relu2"), of)
    load = lax.stop_gradient(sizes).astype(_F32)
    return {"Out": out.reshape(x.shape),
            "HeldPairs": jnp.sum(load).reshape(1),
            "LoadMaxOverMean": (jnp.max(load) / jnp.maximum(
                jnp.mean(load), 1.0)).reshape(1),
            "RowsMade": made.astype(_F32).reshape(1)}
