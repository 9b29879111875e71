"""The delta-rule / latent-attention decoder (`models/kimi_linear.py`)
and the delta rule with a decay per key channel it brought, at a tiny
preset on the CPU with seeded random weights, against the benchmark's
plain reference (`benchmark/reference/kimi_linear.py`, which imports
nothing of the program: the delta rule token by token, attention as the
masked square, the experts as a loop)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import framework
from paddle_tpu.models import kimi_linear
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.registry import run_op
from benchmark.reference import kimi_linear as ref
from decoder_case import KIMI_LINEAR as CASE, stamps_change_no_number

_B, _S = CASE.b, CASE.s

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
         "mla_use_nope", "intermediate_size", "moe_intermediate_size",
         "routed_scaling_factor", "rms_norm_eps", "initializer_range",
         "linear_attn_config")


def _ref_cfg(cfg):
    d = {k: getattr(cfg, k) for k in _KEYS}
    d.update(num_experts=cfg.experts_held[1],
             num_shared_experts=cfg.n_shared_experts,
             num_experts_per_token=cfg.num_experts_per_tok,
             moe_renormalize=cfg.norm_topk_prob,
             published={"num_experts": cfg.n_routed_experts},
             deployment={"first_expert_held": cfg.experts_held[0]})
    return d


def _weights(cfg, seed, std=0.25):
    r = np.random.default_rng(seed)
    out = {}
    for name, shape, kind, scale in ref.param_spec(_ref_cfg(cfg)):
        if kind == "normal":
            # wider than the model's 0.02: every layer must matter
            out[name] = r.normal(0.0, std, shape)
        elif kind == "uniform":
            out[name] = r.uniform(-scale, scale, shape)
        else:
            out[name] = np.full(shape, 1.0 if kind == "ones" else 0.0) \
                + r.normal(0.0, 0.1, shape)
    return {k: np.asarray(v, np.float32)
            for k, v in ref.spread_decay_init(out).items()}


@pytest.mark.parametrize("amp,std,loss_tol,grad_tol", [
    (False, 0.25, 2e-5, 2e-3), (True, 0.05, 2e-2, 0.5)],
    ids=["float32", "bfloat16_amp"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        amp, std, loss_tol, grad_tol):
    """float32 program: tight, the chunked rule with its anchored
    per-channel products against the token-by-token recurrence, the
    unturned latent attention against the masked square, the sorted
    grouped products against the loop over experts. Under bfloat16 AMP
    the band is the kimi-vl and qwen3-next tests', for their reason: 8
    bits of mantissa through five layers, where one routing that flips at
    a near-tie moves a leaf's gradient by a tenth."""
    cfg = kimi_linear.KimiLinearConfig.tiny(experts_held=(2, 4))
    weights, batch = _weights(cfg, 11, std), CASE.batch(cfg, 12)
    loss, grads, _, _ = CASE.loss_and_grads(cfg, amp, weights, batch)
    want_loss, want = ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in weights.items()}, batch,
        _ref_cfg(cfg))
    assert abs(loss - float(want_loss)) <= loss_tol * abs(float(want_loss))
    assert set(grads) == set(want)
    worst = {}
    for k, g in want.items():
        g = np.asarray(g)
        norm = np.linalg.norm(g)
        assert norm > 0, k
        worst[k] = np.linalg.norm(grads[k] - g) / norm
    assert max(worst.values()) <= grad_tol, sorted(
        worst.items(), key=lambda kv: -kv[1])[:5]
    assert np.median(list(worst.values())) <= grad_tol / 4


def test_layer_kinds_follow_linear_attn_config():
    """Counted from 1: the published 27 are 20 delta-rule layers and 7
    latent-attention ones; the cut's five are KDA, KDA, KDA, MLA, KDA,
    the first with the dense feed-forward; the program has an op of each
    where its layer is; a layer listed nowhere is refused."""
    full = kimi_linear.KimiLinearConfig()
    assert full.layer_kinds.count("kda") == 20
    assert [i + 1 for i, k in enumerate(full.layer_kinds) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    cfg = kimi_linear.KimiLinearConfig.tiny()
    assert cfg.layer_kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert [cfg.is_dense(i) for i in range(5)] == [True] + [False] * 4
    main = CASE.build(cfg, False)[0]
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_delta_rule") == 4
    assert types.count("scaled_dot_product_attention") == 1
    assert types.count("rotary_embedding") == 0
    assert types.count("moe_router") == 4
    with pytest.raises(ValueError):
        kimi_linear.KimiLinearConfig.tiny(linear_attn_config={
            "full_attn_layers": [4], "kda_layers": [1, 2, 5],
            "head_dim": 128, "num_heads": 2, "short_conv_kernel_size": 4})


def _mixer_program(cfg, s, mixer):
    from paddle_tpu.fluid import layers

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            x = layers.data(name="x", shape=[s, cfg.hidden_size],
                            dtype="float32")
            out = mixer(x, cfg, "l3")
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    r = np.random.default_rng(13)
    p = {}
    for par in main.all_parameters():
        w = jnp.asarray(r.normal(0.0, 0.25, par.shape), jnp.float32)
        scope.set_var(par.name, w)
        p[par.name[len("l3."):]] = w
    return (lambda x: np.asarray(exe.run(main, feed={"x": x},
                                         fetch_list=[out], scope=scope)[0])), p


def test_nope_latent_attention_reads_no_position():
    """`mla_use_nope`: no rotary op, and the last query's output is the
    same when the tokens before it are shifted round by one position (a
    query sees its keys by content alone); turned at rope_theta, as
    kimi-vl's, it is not. The reference agrees with the program."""
    from paddle_tpu.models import kimi_vl

    cfg = kimi_linear.KimiLinearConfig.tiny()
    r = np.random.default_rng(5)
    x = r.normal(size=(1, 24, cfg.hidden_size)).astype(np.float32)
    shifted = np.concatenate([x[:, 22:23], x[:, :22], x[:, 23:]], axis=1)
    run, p = _mixer_program(cfg, 24, kimi_vl.latent_attention_mixer)
    a, b = run(x), run(shifted)
    assert float(np.max(np.abs(a[:, -1] - b[:, -1]))) <= 1e-5
    assert float(np.max(np.abs(a[:, -2] - b[:, -2]))) > 1e-3
    want = ref.latent_attention(jnp.asarray(x[0]), p, ref.static(
        _ref_cfg(cfg)))
    assert float(jnp.max(jnp.abs(a[0] - want))) <= 1e-4
    turned = kimi_linear.KimiLinearConfig.tiny(mla_use_nope=False)
    run, _ = _mixer_program(turned, 24, kimi_vl.latent_attention_mixer)
    a, b = run(x), run(shifted)
    assert float(np.max(np.abs(a[:, -1] - b[:, -1]))) > 1e-3


def test_the_kda_mixer_matches_the_reference():
    """The mixer alone, as a program of its own, at 100 positions: three
    convolutions, the decay a channel through two low-rank products,
    the gated norm a head."""
    cfg = kimi_linear.KimiLinearConfig.tiny()
    x = np.random.default_rng(3).normal(
        size=(1, 100, cfg.hidden_size)).astype(np.float32)
    run, p = _mixer_program(cfg, 100, kimi_linear.kda_mixer)
    got = run(x)
    want = ref._kda(jnp.asarray(x[0]), p, ref.static(_ref_cfg(cfg)), None)
    assert float(jnp.max(jnp.abs(got[0] - want))) <= 1e-4 * max(
        1.0, float(jnp.max(jnp.abs(want))))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts over 4 shares, top-8, one ungated shared expert: every
    share's routed part plus the shared expert counted once is what the
    uncut reference gives for the whole layer."""
    r = np.random.default_rng(4)

    def a(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    t, h, f = 48, 16, 12
    x = a(t, h)
    p = {"router": a(h, 32), "experts_gate_up": a(32, h, 2 * f, scale=0.3),
         "experts_down": a(32, f, h, scale=0.3),
         "shared_gate_up": a(h, 2 * f, scale=0.3),
         "shared_down": a(f, h, scale=0.3)}
    routing = run_op("moe_router", {"X": [x], "W": [p["router"]]},
                     {"top_k": 8, "routed_scaling_factor": 2.446})
    parts = []
    for first in range(0, 32, 8):
        out = run_op("moe_experts", {
            "X": [x], "TopkIdx": routing["TopkIdx"],
            "TopkWeight": routing["TopkWeight"],
            "WUp": [p["experts_gate_up"][first:first + 8]],
            "WDown": [p["experts_down"][first:first + 8]]},
            {"held_start": first, "num_experts": 32, "activation": "swiglu"})
        parts.append((out["Out"][0], float(out["HeldPairs"][0][0])))
    cfg = {"num_experts_per_tok": 8, "norm_topk_prob": True,
           "routed_scaling_factor": 2.446}
    shared_once = ref.mla._gated_mlp(x, p["shared_gate_up"],
                                     p["shared_down"], None)
    whole = ref.mla.routed_layer(x, p, cfg, None, (0, 32))
    assert float(jnp.max(jnp.abs(sum(q for q, _ in parts) + shared_once
                                 - whole))) <= 1e-4
    assert sum(n for _, n in parts) == t * 8       # every pair, once


# -- the delta rule with a decay per key channel ----------------------------

def _recurrence(q, k, v, g, beta):
    """The plain reference's token-by-token recurrence, a sequence at a
    time, every value head given its key head's q and k."""
    r = v.shape[2] // q.shape[2]
    return jax.vmap(ref.kda_rule)(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
        jnp.asarray(g), beta)


def _channel_args(seed, s, hk=2, r=2, dk=8, dv=16, low=-0.7, strong=0.0,
                  b=2):
    """Unit q and k, a log-decay uniform in [low, 0] a channel, and where
    `strong` a share of positions and channels planted at -30."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(b, s, hk, dk)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, hk * r, dv))
    g = rng.uniform(low, 0.0, size=(b, s, hk * r, dk))
    g = np.where(rng.uniform(size=g.shape) < strong, -30.0, g)
    bt = rng.uniform(0.1, 0.9, size=(b, s, hk * r))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, bt)]


@pytest.mark.parametrize("case", [
    dict(s=130), dict(s=200, r=1, dk=16, dv=8, strong=0.3),
    dict(s=96, low=-30.0, hk=1, r=4)],
    ids=["not_whole_chunks", "planted_minus_30",
         "every_decay_down_to_minus_30"])
@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_a_decay_per_channel_and_every_inputs_gradient_match_the_recurrence(
        case, kernel):
    """The chunks walked by `lax.scan` and by the two Pallas kernels
    (under the interpreter here), each against the recurrence, finite
    under decays of -30 a position and channel (e^-480 over a block of
    16: nothing may be clamped, and no exponent the rule takes is
    positive). The bands are the scalar rule's test's: float32 rounding
    through 64-position chunks, values to 2e-5 of the largest, gradients
    to 5e-5 of each input's largest."""
    rule = functools.partial(hybrid_ops.gated_delta_rule, kernel=kernel)
    args = _channel_args(5, **case)
    want = _recurrence(*args)
    got = rule(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(scale, 1.0)
    w = jnp.asarray(np.random.default_rng(9).normal(size=want.shape),
                    jnp.float32)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in (rule, _recurrence)]
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        top = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * max(top, 1.0), name


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_a_decay_equal_across_channels_is_the_scalar_rule(kernel):
    """The per-channel form handed one decay a head on every channel
    gives what the scalar form gives, values and gradients (the scalar
    one's gradient being the channels' sum)."""
    q, k, v, g, beta = _channel_args(7, 130)
    g = g[..., :1]
    rule = functools.partial(hybrid_ops.gated_delta_rule, kernel=kernel)
    wide = jnp.broadcast_to(g, g.shape[:-1] + (q.shape[-1],))
    assert float(jnp.max(jnp.abs(rule(q, k, v, wide, beta)
                                 - rule(q, k, v, g[..., 0], beta)))) <= 1e-5
    w = jnp.asarray(np.random.default_rng(2).normal(size=v.shape),
                    jnp.float32)
    d_wide = jax.grad(lambda g: jnp.sum(rule(q, k, v, g, beta) * w))(wide)
    d_one = jax.grad(lambda g: jnp.sum(rule(q, k, v, g, beta) * w))(
        g[..., 0])
    assert float(jnp.max(jnp.abs(jnp.sum(d_wide, -1) - d_one))) <= 1e-4 * max(
        1.0, float(jnp.max(jnp.abs(d_one))))


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_a_decay_per_channel_names_its_parts_where_a_trace_reads_them(
        kernel):
    """`pt[decay]` (the anchored products, q and k scaled by their
    decays) stands beside the scalar rule's five parts in an `op_name`
    of the recompute and of the backward pass (the forward kernel makes
    every other local value itself: no `pt[local]` is left beside it);
    the stamps change no number."""
    every = {"head", "decay", "inverse", "local", "walk", "groups"}
    stamps_change_no_number(
        "gated_delta_rule", functools.partial(
            hybrid_ops.gated_delta_rule, kernel=kernel),
        _channel_args(5, s=130),
        {"recompute": every - {"local"} if kernel else every,
         "backward": every})


def test_the_op_makes_a_channels_decay_from_its_projection_in_float32():
    """The registered op: A [B, S, H, dk] and DtBias [H * dk] give
    g = -exp(ALog_h) softplus(A + DtBias) a channel, float32, whatever
    the inputs' dtype; the rule then runs per channel."""
    q, k, v, _, beta = _channel_args(3, 70)
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.normal(size=q.shape[:2] + (4, 8)), jnp.bfloat16)
    a_log = jnp.asarray(np.log([1.0, 4.0, 9.0, 16.0]), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    b = jnp.asarray(np.log(beta / (1 - beta)), jnp.float32)
    got = run_op("gated_delta_rule", {
        "Q": [q], "K": [k], "V": [v], "A": [a], "B": [b], "ALog": [a_log],
        "DtBias": [dt_bias]}, {})["Out"][0]
    g = -np.exp(np.asarray(a_log))[:, None] * np.logaddexp(
        0.0, np.asarray(a, np.float32) + np.asarray(dt_bias).reshape(4, 8))
    want = _recurrence(q, k, v, jnp.asarray(g, jnp.float32),
                       jax.nn.sigmoid(b))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


# -- the decay's kernel pair -------------------------------------------------

def _decay_inputs(h, r, dk=16, n=2, seed=21):
    """q and k of `h` key heads, and a log-decay a channel for their `r`
    value heads each, summed over a chunk of 64 as the op sums it: A of
    1, 4, 9 and 16 (the head's factor, as `ALog` gives it) on a channel
    falling by up to 1.5 a position, channel 0 by 1.5 exactly. Unanchored,
    exp(gc) of a chunk's last position reaches e^-1536 and exp(-gc)
    e^1536: both far past float32."""
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(1, n, h, 64, dk)), jnp.float32)
            for _ in range(2))
    fall = rng.uniform(0.0, 1.5, size=(1, n, h, r, 64, dk))
    fall[..., 0] = 1.5
    a = np.array([1.0, 4.0, 9.0, 16.0])[np.arange(h * r) % 4].reshape(h, r)
    gc = np.cumsum(-a[:, :, None, None] * fall, axis=-2)
    return q, k, jnp.asarray(gc, jnp.float32)


def _anchored_products(q, k, gc):
    """What the kernel pair computes, from `_gdr_decayed`: (K K^T
    decayed, strictly lower; Q K^T decayed, causal)"""
    strict = np.tril(np.ones((64, 64), bool), -1)
    return (jnp.where(strict, hybrid_ops._gdr_decayed(k, k, gc), 0.0),
            hybrid_ops._gdr_decayed(q, k, gc))


_DECAY_CASES = {"two_heads_a_step": (4, 1, 2),
                "every_head_a_step": (4, 1, None),
                "two_value_heads_a_key_head": (2, 2, 2),
                "three_heads_one_a_trip": (3, 1, None)}


@pytest.mark.parametrize("case", list(_DECAY_CASES))
def test_the_decay_kernel_makes_both_anchored_products(monkeypatch, case):
    """`gated_delta_rule_decay` (under the interpreter) against
    `_gdr_decayed`, both products, at decays no unanchored form
    survives: every output finite and equal to float32 rounding. The
    grid steps take two value heads or every head of the call; their
    loop takes two at a time where they pair off."""
    from paddle_tpu.ops.pallas import gated_delta_rule as kernels

    h, r, heads = _DECAY_CASES[case]
    if heads:
        monkeypatch.setattr(kernels, "heads_a_step", lambda *a: heads)
    q, k, gc = _decay_inputs(h, r)
    got = kernels.gated_delta_rule_decay(q, k, gc, 16, interpret=True)
    for a, b in zip(got, _anchored_products(q, k, gc)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * max(
            1.0, float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("case", list(_DECAY_CASES))
def test_the_reverse_decay_kernel_is_the_products_transpose(monkeypatch,
                                                             case):
    """`gated_delta_rule_decay_bwd` against `jax.vjp` of `_gdr_decayed`'s
    two products: d q, d k (summed over a key head's value heads) and
    d gc = x d x - k d k, with cotangents on both products, on K K^T
    alone (x is k: k takes both terms) and on Q K^T alone. Cotangents
    where a product is nought by construction are not read."""
    from paddle_tpu.ops.pallas import gated_delta_rule as kernels

    h, r, heads = _DECAY_CASES[case]
    if heads:
        monkeypatch.setattr(kernels, "heads_a_step", lambda *a: heads)
    q, k, gc = _decay_inputs(h, r)
    rng = np.random.default_rng(5)
    d_a, d_aqk = (jnp.asarray(rng.normal(size=gc.shape[:-1] + (64,)),
                              jnp.float32) for _ in range(2))
    _, vjp = jax.vjp(_anchored_products, q, k, gc)
    zero = jnp.zeros_like(d_a)
    for cot in ((d_a, d_aqk), (d_a, zero), (zero, d_aqk)):
        got = kernels.gated_delta_rule_decay_bwd(q, k, gc, *cot, 16,
                                                 interpret=True)
        # what lies above either product's triangle changes nothing
        junk = [c + jnp.triu(jnp.full((64, 64), 7.0), 0 if i == 0 else 1)
                for i, c in enumerate(cot)]
        again = kernels.gated_delta_rule_decay_bwd(q, k, gc, *junk, 16,
                                                   interpret=True)
        for name, a, b, c in zip(("d_q", "d_k", "d_gc"), got, vjp(cot),
                                 again):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert bool(jnp.all(jnp.isfinite(a))), name
            assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * max(
                1.0, float(jnp.max(jnp.abs(b)))), name
            assert bool(jnp.all(a == c)), name


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_the_op_says_who_forms_the_decayed_products(kernel, caplog):
    """Where the op is traced its log line has a clause for the decay's
    two products of a decay per key channel (the kernel pair, its value
    heads a grid step and its grid steps a call, or `jax.numpy`), and
    none for a decay a head; the gradient keeps the inputs and the chunk
    states either way, nothing of the products."""
    import logging

    args = _channel_args(5, s=130)
    rule = functools.partial(hybrid_ops.gated_delta_rule, kernel=kernel)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        _, vjp = jax.vjp(rule, *args)
        rule(*args[:3], args[3][..., 0], args[4])
    said = [r.getMessage() for r in caplog.records]
    assert (", the decayed products by " + (
        "pallas (4 value heads a grid step, 6 grid steps a call)"
        if kernel else "jax.numpy") + "; kept ") in said[0]
    assert "decayed products" not in said[-1]
    shapes = sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(vjp)
                    if hasattr(x, "shape") and x.size > 16)
    assert shapes == sorted([(2, 192, 2, 8), (2, 192, 2, 8), (2, 192, 4, 16),
                             (2, 192, 4, 8), (2, 192, 4),
                             (1, 2, 3, 2, 2, 8, 16)])
