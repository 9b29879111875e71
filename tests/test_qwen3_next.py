"""The gated-delta-rule / gated-attention / routed-expert decoder
(`models/qwen3_next.py`) and the ops it brought, at a tiny preset on the
CPU with seeded random weights, against the benchmark's plain reference
(`benchmark/reference/qwen3_next.py`, which imports nothing of the
program: the delta rule token by token, attention as the masked
square, the experts as a loop)."""
import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import framework
from paddle_tpu.models import qwen3_next
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.pallas import gated_delta_rule as delta_kernels
from paddle_tpu.ops.registry import run_op
from benchmark.reference import qwen3_next as ref
from decoder_case import QWEN3_NEXT as CASE, stamps_change_no_number

_B, _S = CASE.b, CASE.s

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "full_attention_interval", "num_attention_heads",
         "num_key_value_heads", "head_dim", "partial_rotary_factor",
         "rope_theta", "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "num_experts_per_tok",
         "moe_intermediate_size", "shared_expert_intermediate_size",
         "norm_topk_prob", "rms_norm_eps", "initializer_range")


def _ref_cfg(cfg):
    d = {k: getattr(cfg, k) for k in _KEYS}
    d["num_experts"] = cfg.experts_held[1]
    d["published"] = {"num_experts": cfg.num_experts}
    d["deployment"] = {"first_expert_held": cfg.experts_held[0]}
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
            out[name] = np.full(shape, 1.0 if kind == "ones" else 0.0)
        if name.endswith("norm"):
            out[name] = out[name] + r.normal(0.0, 0.1, shape)
    return {k: np.asarray(v, np.float32)
            for k, v in ref.spread_decay_init(out).items()}


@pytest.mark.parametrize("amp,std,loss_tol,grad_tol", [
    (False, 0.25, 2e-5, 2e-3), (True, 0.05, 2e-2, 0.5)],
    ids=["float32", "bfloat16_amp"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        amp, std, loss_tol, grad_tol):
    """float32 program: tight, the chunked delta rule against the token
    by token recurrence, flash's stand-in against the masked square, the
    sorted grouped products against the loop over experts. Under
    bfloat16 AMP the band is what 8 bits of mantissa through four
    layers of two parts each leave at 160 tokens, where one routing
    that flips at a near-tie moves a leaf's gradient by a tenth (read on
    three seeds at weights of 0.05: the worst leaf 0.30, the median
    0.05-0.09; at 0.25 the worst leaf reads 0.56-1.04)."""
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=(2, 4))
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


# -- the gated delta rule ----------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """The plain reference's token-by-token recurrence, a sequence at a
    time, every value head given its key head's q and k."""
    r = v.shape[2] // q.shape[2]
    return jax.vmap(ref.delta_rule)(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta)


def _delta_args(seed, s, hk=2, r=2, dk=8, dv=16, decay=(0.5, 1.0),
                beta=(0.1, 0.9), b=2):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(b, s, hk, dk)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, hk * r, dv))
    g = np.log(rng.uniform(*decay, size=(b, s, hk * r)))
    bt = rng.uniform(*beta, size=(b, s, hk * r))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, bt)]


@pytest.mark.parametrize("case", [
    dict(s=64), dict(s=192), dict(s=130), dict(s=20, r=1),
    dict(s=256, dk=16, dv=8, decay=(0.999, 1.0), beta=(0.98, 1.0), b=1),
    dict(s=128, decay=(1e-3, 0.05), beta=(0.0, 0.02)),
    dict(s=128, decay=(0.9, 1.0), beta=(0.98, 1.0), r=4, hk=1)],
    ids=["one_chunk", "three_chunks", "not_whole_chunks", "under_a_chunk",
         "decay_and_beta_near_1", "decay_and_beta_near_0",
         "four_value_heads_a_key_head"])
@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_gated_delta_rule_and_every_inputs_gradient_match_the_recurrence(
        case, kernel):
    """The chunks walked by `lax.scan` and by the two Pallas kernels
    (under the interpreter here), each against the recurrence."""
    rule = functools.partial(hybrid_ops.gated_delta_rule, kernel=kernel)
    args = _delta_args(5, **case)
    want = _recurrence(*args)
    got = rule(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(scale, 1.0)
    w = jnp.asarray(np.random.default_rng(9).normal(size=want.shape),
                    jnp.float32)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in (rule, _recurrence)]
    for name, a, b in zip("q k v g beta".split(), *grads):
        top = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * max(top, 1.0), name


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_gated_delta_rule_names_its_parts_where_a_trace_reads_them(kernel):
    """`pt[inverse]` (inside `pt[local]`, and the inner part wins),
    `pt[local]`, `pt[walk]`, `pt[groups]` and `pt[head]` (g and beta
    made float32, the pad, the log-decays summed over each chunk) each
    stand in an `op_name` of the recompute and of the backward pass,
    whoever walks the chunks; the stamps change no number."""
    every = {"head", "inverse", "local", "walk", "groups"}
    stamps_change_no_number(
        "gated_delta_rule", functools.partial(
            hybrid_ops.gated_delta_rule, kernel=kernel),
        _delta_args(5, s=130), {"recompute": every, "backward": every})


def _chunked_inputs(seed, r, dtype, b=2, n=3, h=2, dk=8, dv=16):
    """Random inputs a chunk at a time, as `_gdr_group_inputs` lays
    them: q, k [B, N, H, C, dk] (unit rows); v [B, N, H, R, C, dv]; gc
    (decreasing inside a chunk) and beta [B, N, H, R, C] float32."""
    rng = np.random.default_rng(seed)
    c = hybrid_ops._GDR_CHUNK
    q, k = (rng.normal(size=(b, n, h, c, dk)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    gc = np.cumsum(np.log(rng.uniform(0.7, 1.0, (b, n, h, r, c))), axis=-1)
    return [jnp.asarray(t, kind) for t, kind in (
        (q, dtype), (k, dtype), (rng.normal(size=(b, n, h, r, c, dv)), dtype),
        (gc, jnp.float32), (rng.uniform(0.1, 0.9, (b, n, h, r, c)),
                            jnp.float32))]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("r", [2, 4])
def test_each_walk_kernel_alone_gives_what_its_scan_gives(r, dtype):
    """Forward: the kernel, handed the inverse alone, against
    `_gdr_local` and the scan. Reverse: on values no `_gdr_local` made.
    2 and 4 value heads a key head: 4 and 8 heads a sequence, walked in
    one grid step; the rule takes whole key heads and only blocks that
    divide."""
    tol = 1e-5 if dtype == jnp.float32 else 0.02
    close = lambda a, b: float(jnp.max(jnp.abs(  # noqa: E731
        a.astype(jnp.float32) - b.astype(jnp.float32)))) <= tol * max(
            1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
    q, k, v, gc, beta = ins = _chunked_inputs(3, r, dtype)
    local = hybrid_ops._gdr_local(*ins)
    want = [jnp.moveaxis(t, 0, 1) for t in hybrid_ops._gdr_walk_fwd(*local)]
    kk = jnp.einsum("bnhid,bnhjd->bnhij", k, k,
                    preferred_element_type=jnp.float32)[:, :, :, None]
    t = hybrid_ops._gdr_inverse(kk, gc, beta, dtype)[0]
    # the states go into group 1 of a stack of three, in place
    stack = jnp.full((3,) + want[1].shape, 7.0, jnp.float32)
    out, kept = delta_kernels.gated_delta_rule_fwd(q, k, v, t, gc, beta,
                                                   (stack, 1))
    assert out.dtype == dtype and kept.dtype == jnp.float32
    assert close(out, want[0]) and close(kept[1], want[1])
    assert float(jnp.min(kept[::2])) == float(jnp.max(kept[::2])) == 7.0
    alone, none = delta_kernels.gated_delta_rule_fwd(q, k, v, t, gc, beta)
    assert none is None and close(alone, want[0])
    rng = np.random.default_rng(4)
    w, _, aqk, qg, kd, gl = local
    u, d_out = (jnp.asarray(rng.normal(size=v.shape) / 4, dtype)
                for _ in range(2))
    want = hybrid_ops._gdr_walk_bwd(w, aqk, qg, kd, gl, u, kept[1], d_out)
    got = delta_kernels.gated_delta_rule_bwd(w, aqk, qg, kd, gl, u, kept, 1,
                                             d_out)
    assert [x.dtype for x in got] == [jnp.float32, dtype, jnp.float32]
    for a, b in zip(got, want):
        assert a.shape == b.shape and close(a, b)
    # a decay per key channel: the kernels take a head's log-decays as a
    # [C, dk] block and the decayed q k^T made outside, and decay the
    # state's rows each by its own channel's factor, a [dk] column
    gcc = jnp.asarray(np.cumsum(np.log(rng.uniform(
        0.7, 1.0, gc.shape + (q.shape[-1],))), axis=-2), jnp.float32)
    local = hybrid_ops._gdr_local(q, k, v, gcc, beta)
    want = [jnp.moveaxis(t, 0, 1) for t in hybrid_ops._gdr_walk_fwd(*local)]
    t, aqk = hybrid_ops._gdr_system_channels(q, k, gcc, beta)
    out, kept = delta_kernels.gated_delta_rule_fwd(q, k, v, t, gcc, beta,
                                                   (stack, 1), aqk=aqk)
    assert close(out, want[0]) and close(kept[1], want[1])
    w, _, aqk, qg, kd, gl = local
    assert gl.shape == gcc.shape[:-2] + gcc.shape[-1:]
    want = hybrid_ops._gdr_walk_bwd(w, aqk, qg, kd, gl, u, kept[1], d_out)
    got = delta_kernels.gated_delta_rule_bwd(w, aqk, qg, kd, gl, u, kept, 1,
                                             d_out)
    for a, b in zip(got, want):
        assert a.shape == b.shape and close(a, b)
    rule = delta_kernels.heads_a_step
    assert rule(2, r, 64, 8, 16, 4) == 2 * r
    # a head's blocks at 128 x 128 in bfloat16 take 0.22 MB: eight fit,
    # whole key heads of them; 6 key heads of 2 go in threes (4 would
    # not divide), 3 value heads a key head in two key heads, 16 in one
    # key head however many that is, and a wide head with its key head
    assert rule(4, 2, 64, 128, 128, 2) == 8
    assert rule(6, 2, 64, 128, 128, 2) == 6
    assert rule(4, 3, 64, 128, 128, 2) == 6
    assert rule(4, 16, 64, 128, 128, 2) == 16
    assert rule(4, 2, 64, 1024, 1024, 4) == 2


def test_gated_delta_rule_walks_head_groups_and_says_what_it_keeps(
        monkeypatch, caplog):
    """A tile budget that one key head's chunks fill: the op walks its
    key heads one after another and gives what one group gives, the
    kernels (each group's states written into, and read from, its place
    in the one stack) as the scans."""
    args = _delta_args(6, 130, hk=4, r=2)
    whole = hybrid_ops.gated_delta_rule(*args)
    g_whole = jax.grad(lambda *a: jnp.sum(jnp.square(
        hybrid_ops.gated_delta_rule(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    # 2 sequences x 3 chunks x 2 value heads x 64 x 64 float32
    monkeypatch.setattr(hybrid_ops, "_GDR_TILE_BYTES", 2 * 3 * 2 * 64 * 64 * 4)
    assert hybrid_ops._gdr_groups(2, 3, 4, 2) == 4
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        for kernel in (False, True):
            rule = functools.partial(hybrid_ops.gated_delta_rule,
                                     kernel=kernel)
            walked = rule(*args)
            g_walked = jax.grad(lambda *a: jnp.sum(jnp.square(rule(*a))),
                                argnums=(0, 1, 2, 3, 4))(*args)
            assert float(jnp.max(jnp.abs(walked - whole))) <= 1e-5
            for a, b in zip(g_walked, g_whole):
                assert float(jnp.max(jnp.abs(a - b))) <= 1e-4
    said = caplog.records[0].getMessage()
    # padded to 192: q, k 2 x 192 x 4 x 8, v 2 x 192 x 8 x 16, two
    # float32 [2, 192, 8] and 2 x 3 x 8 states of 8 x 16, all float32
    kept = 4 * (2 * 2 * 192 * 4 * 8 + 2 * 192 * 8 * 16 + 2 * 2 * 192 * 8
                + 2 * 3 * 8 * 8 * 16)
    assert said == (
        "gated_delta_rule q, k (2, 192, 4, 8) v (2, 192, 8, 16) float32: "
        "64 positions a chunk, 3 chunks, 4 head groups, the chunks walked "
        "by jax.numpy, the inverse by jax.numpy; kept for the backward "
        "pass %d bytes (the inputs and 48 states [8, 16] float32)" % kept)
    # a group call's 2 x 3 x 2 systems, in one block of 16
    assert (" 4 head groups, the chunks walked by pallas (2 value heads a "
            "grid step, 6 grid steps a call), the inverse by pallas (16 "
            "systems a grid step, 1 grid steps a call); kept ") in \
        caplog.records[-1].getMessage()


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_gated_delta_rule_keeps_token_sized_values_and_chunk_states(
        kernel, caplog):
    """The custom gradient's residuals: the five inputs and the state at
    every chunk's start; nothing [S, S]- or [S, dk, dv]-shaped, whoever
    walks the chunks, and the log line says who does."""
    args = _delta_args(7, 192)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        _, vjp = jax.vjp(functools.partial(
            hybrid_ops.gated_delta_rule, kernel=kernel), *args)
    assert ("1 head groups, the chunks walked by " + (
        "pallas (4 value heads a grid step, 6 grid steps a call), the "
        "inverse by pallas (32 systems a grid step, 1 grid steps a call);"
        if kernel else "jax.numpy, the inverse by jax.numpy;")) in \
        caplog.records[0].getMessage()
    shapes = sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(vjp)
                    if hasattr(x, "shape") and x.size > 16)
    s = 192
    assert shapes == sorted([(2, s, 2, 8), (2, s, 2, 8), (2, s, 4, 16),
                             (2, s, 4), (2, s, 4), (1, 2, 3, 2, 2, 8, 16)])


def test_unit_lower_inverse_of_a_chunk_of_equal_keys():
    """The hardest system a chunk can hold: every key the same, beta 1,
    no decay (all ones under the diagonal, whose powers are binomials)."""
    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    inv = hybrid_ops._unit_lower_inverse(a)
    eye = np.eye(64, dtype=np.float32)
    assert float(jnp.max(jnp.abs((eye + a) @ inv - eye))) <= 1e-4
    r = np.random.default_rng(0)
    a = jnp.tril(jnp.asarray(r.uniform(-1, 1, (3, 64, 64)), jnp.float32), -1)
    want = np.linalg.inv(np.asarray(eye + a, np.float64))
    got = np.asarray(hybrid_ops._unit_lower_inverse(a), np.float64)
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


def _systems(case):
    """Strictly lower triangular float32 systems [..., 64, 64]: the
    equal keys' one in front of random ones (a count of 70 leaves the
    second block of 64 six), an odd count, the op's leading shape."""
    r = np.random.default_rng(1)
    shape = {"equal_keys_and_random": (3,), "a_block_and_six": (70,),
             "odd": (17,), "the_ops_shape": (2, 3, 2, 2)}[case]
    a = np.tril(r.uniform(-1, 1, shape + (64, 64)), -1)
    a.reshape(-1, 64, 64)[0] = np.tril(np.ones((64, 64)), -1)
    return jnp.asarray(a, jnp.float32)


@pytest.mark.parametrize("case", ["equal_keys_and_random", "a_block_and_six",
                                  "odd", "the_ops_shape"])
def test_the_inverse_kernel_gives_the_series_inverse(case):
    """The Pallas inverse (under the interpreter here) against the
    inverse in float64, with the `jax.numpy` series' tolerance, and
    against the series itself to float32 rounding of its scale."""
    a = _systems(case)
    got = delta_kernels.unit_lower_inverse(a, hybrid_ops._GDR_BLOCK)
    assert got.shape == a.shape and got.dtype == jnp.float32
    eye = np.eye(64)
    want = np.linalg.inv(eye + np.asarray(a, np.float64))
    got = np.asarray(got, np.float64)
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))
    series = np.asarray(hybrid_ops._unit_lower_inverse(a), np.float64)
    assert np.max(np.abs(got - series)) <= 1e-6 * np.max(np.abs(series))
    # through the op's switch, the gradient the formula's either way
    d_t = jnp.asarray(np.random.default_rng(2).normal(size=a.shape),
                      jnp.float32)
    grads = [jax.vjp(lambda x, k=k: hybrid_ops._unit_lower_inverse(x, k),
                     a)[1](d_t)[0] for k in (True, False)]
    assert float(jnp.max(jnp.abs(grads[0] - grads[1]))) <= 1e-5 * float(
        jnp.max(jnp.abs(grads[1])))


def test_the_inverse_kernels_block_of_systems():
    """Whole loop trips of 8 pairs, up to 64 systems of [64, 64] (in
    and out, padded to 128 lanes and double buffered, 8 MiB), never
    more trips than the count needs."""
    rule = delta_kernels.systems_a_step
    assert rule(2048, 64) == 64 and rule(70, 64) == 64
    assert rule(18, 64) == 32 and rule(2, 64) == 16
    for shape, block in (((2, 32, 32), 16), ((2, 64, 64), 8)):
        with pytest.raises(ValueError):
            delta_kernels.unit_lower_inverse(jnp.zeros(shape), block)


@pytest.mark.parametrize("case", ["equal_keys", "random"])
def test_unit_lower_inverses_gradient_is_the_inverses_own(case):
    """d_a = -T^T d_T T^T on the strict lower triangle, where a lives,
    against autodiff through `jnp.linalg.inv`, on the two systems of
    the test above."""
    r = np.random.default_rng(0)
    a = jnp.tril(jnp.ones((64, 64), jnp.float32) if case == "equal_keys"
                 else jnp.asarray(r.uniform(-1, 1, (3, 64, 64)), jnp.float32),
                 -1)
    d_t = jnp.asarray(r.normal(size=a.shape), jnp.float32)
    eye = jnp.eye(64, dtype=jnp.float32)
    got, = jax.vjp(hybrid_ops._unit_lower_inverse, a)[1](d_t)
    want, = jax.vjp(lambda x: jnp.linalg.inv(eye + x), a)[1](d_t)
    want = np.tril(np.asarray(want), -1)
    assert np.max(np.abs(np.asarray(got) - want)) <= 1e-4 * np.max(
        np.abs(want))
    assert not np.any(np.triu(np.asarray(got)))


def _inside(jaxpr, count):
    """`count(eqn)` summed over the equations of a jaxpr and of every
    jaxpr inside it but a Pallas kernel's body."""
    n = 0
    for eqn in jaxpr.eqns:
        n += count(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _inside(sub, count)
    return n


def _square_float32_products(jaxpr):
    """The `dot_general`s outside the Pallas kernels whose operands are
    both [..., 64, 64] float32: the products of the `jax.numpy`
    triangular inverse and of its gradient, and no other of the op at
    head sizes that are not 64."""
    return _inside(jaxpr, lambda eqn: eqn.primitive.name == "dot_general"
                   and all(v.aval.shape[-2:] == (64, 64)
                           and v.aval.dtype == jnp.float32
                           for v in eqn.invars))


def _inverse_kernel_calls(jaxpr):
    return _inside(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
                   and eqn.params["name"] == "gated_delta_rule_inverse")


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernels"])
def test_the_backward_pass_transposes_the_inverse_in_two_products(kernel):
    """A group call of the forward pass runs the two series' ten
    products; the backward pass makes them again and adds the two of
    d_a = -T^T d_T T^T (twenty more when `jax.vjp` transposed the
    series). With the kernels, the inverse kernel's call stands where
    the ten stood, in both."""
    rule = functools.partial(hybrid_ops.gated_delta_rule, kernel=kernel)
    args = _delta_args(7, 192)
    out, vjp = jax.vjp(rule, *args)
    forward = jax.make_jaxpr(rule)(*args).jaxpr
    backward = jax.make_jaxpr(vjp)(jnp.ones_like(out)).jaxpr
    assert _square_float32_products(forward) == (0 if kernel else 10)
    assert _square_float32_products(backward) == (2 if kernel else 12)
    assert _inverse_kernel_calls(forward) == _inverse_kernel_calls(
        backward) == (1 if kernel else 0)


def test_the_delta_rule_op_makes_its_decay_and_strength_in_float32():
    q, k, v, _, _ = _delta_args(8, 64)
    r = np.random.default_rng(1)
    a, b = (jnp.asarray(r.normal(size=(2, 64, 4)), jnp.float32)
            for _ in range(2))
    a_log = jnp.asarray(np.log(r.uniform(1, 16, 4)), jnp.float32)
    dt_bias = jnp.asarray(r.uniform(-5, -2, 4), jnp.float32)
    ins = {"Q": [q], "K": [k], "V": [v], "A": [a], "B": [b],
           "ALog": [a_log], "DtBias": [dt_bias]}
    got = run_op("gated_delta_rule", ins, {})["Out"][0]
    want = _recurrence(q, k, v, -jnp.exp(a_log) * jax.nn.softplus(
        a + dt_bias), jax.nn.sigmoid(b))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5
    low = {s: [x.astype(jnp.bfloat16)] if s in "QKVAB" else [x]
           for s, (x,) in ins.items()}
    out = run_op("gated_delta_rule", low, {})["Out"][0]
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) <= 0.05
    jaxpr = str(jax.make_jaxpr(lambda d: run_op(
        "gated_delta_rule", d, {})["Out"][0])(low))
    assert "cumsum" in jaxpr and "bf16[2,1,64,4] = cumsum" not in jaxpr


# -- the small ops -----------------------------------------------------------

def test_rotary_embedding_at_a_quarter_of_the_head():
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(2, 12, 3, 16)), jnp.float32)
    got = run_op("rotary_embedding", {"X": [x]},
                 {"rotary_dim": 4, "theta": 1e7})["Out"][0]
    # by hand: pairs (0, 2) and (1, 3) turn, columns 4.. pass through
    want = np.array(x)
    for t in range(12):
        for i in range(2):
            angle = t * 1e7 ** (-2.0 * i / 4)
            c, s = math.cos(angle), math.sin(angle)
            a, b = np.array(x[:, t, :, i]), np.array(x[:, t, :, i + 2])
            want[:, t, :, i], want[:, t, :, i + 2] = a * c - b * s, \
                b * c + a * s
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    assert float(jnp.max(jnp.abs(got[:, 0] - x[:, 0]))) == 0.0
    # the reference's own, one sequence at a time
    for i in range(2):
        assert float(jnp.max(jnp.abs(
            ref.rotary(x[i], 4, 1e7) - got[i]))) <= 1e-5
    whole = run_op("rotary_embedding", {"X": [x]},
                   {"rotary_dim": 16, "theta": 10000.0})["Out"][0]
    assert float(jnp.max(jnp.abs(whole - ref.rotary(
        x[0], 16, 10000.0)[None])[0])) <= 1e-5
    low = run_op("rotary_embedding", {"X": [x.astype(jnp.bfloat16)]},
                 {"rotary_dim": 4, "theta": 1e7})["Out"][0]
    assert low.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="over 5 of 16"):
        run_op("rotary_embedding", {"X": [x]},
               {"rotary_dim": 5, "theta": 1e7})
    with pytest.raises(ValueError, match="over 0 of 16"):
        run_op("rotary_embedding", {"X": [x]},
               {"rotary_dim": 0, "theta": 1e7})


def test_l2_norm_swiglu_and_the_zero_centred_norm():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(4, 6, 8)), jnp.float32)
    y = run_op("l2_norm", {"X": [x]}, {"epsilon": 1e-6})["Y"][0]
    want = np.asarray(x) / np.sqrt(np.sum(np.square(np.asarray(x)), -1,
                                          keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-6
    out = run_op("swiglu", {"X": [x]}, {})["Out"][0]
    gate, up = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    assert out.shape == (4, 6, 4)
    assert float(jnp.max(jnp.abs(out - gate / (1 + np.exp(-gate)) * up))) \
        <= 1e-6
    w = jnp.asarray(r.normal(size=(8,)) * 0.1, jnp.float32)
    centred = run_op("rms_norm", {"X": [x], "Scale": [w]},
                     {"epsilon": 1e-6, "scale_offset": 1.0})["Y"][0]
    plain = run_op("rms_norm", {"X": [x], "Scale": [1.0 + w]},
                   {"epsilon": 1e-6})["Y"][0]
    assert float(jnp.max(jnp.abs(centred - plain))) <= 1e-6
    assert float(jnp.max(jnp.abs(centred - ref._norm(x, w, 1e-6)))) <= 1e-6
    # a weight of zero is the bare norm; without the attribute the op's
    # program is what it was
    bare = run_op("rms_norm", {"X": [x]}, {"epsilon": 1e-6})["Y"][0]
    zero = run_op("rms_norm", {"X": [x], "Scale": [0.0 * w]},
                  {"epsilon": 1e-6, "scale_offset": 1.0})["Y"][0]
    assert float(jnp.max(jnp.abs(zero - bare))) <= 1e-6
    adds = [str(jax.make_jaxpr(lambda a, b, at=at: run_op(
        "rms_norm", {"X": [a], "Scale": [b]}, at)["Y"][0])(x, w)).count(
            " = add ") for at in ({}, {"scale_offset": 1.0})]
    assert adds == [1, 2]       # the epsilon; and the offset


# -- the routed layer --------------------------------------------------------

def _moe_inputs(seed=4, t=48, h=16, f=12, experts=16):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(t, h)), jnp.float32),
            jnp.asarray(r.normal(size=(h, experts)), jnp.float32),
            jnp.asarray(r.normal(size=(experts, h, 2 * f)) * 0.3,
                        jnp.float32),
            jnp.asarray(r.normal(size=(experts, f, h)) * 0.3, jnp.float32))


_MOE_CFG = {"num_experts_per_tok": 3, "norm_topk_prob": True}


def _routed_share(x, w_r, w_gu, w_down, first, count):
    r = run_op("moe_router", {"X": [x], "W": [w_r]},
               {"top_k": 3, "score_function": "softmax"})
    out = run_op("moe_experts", {
        "X": [x], "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
        "WUp": [w_gu[first:first + count]],
        "WDown": [w_down[first:first + count]]},
        {"held_start": first, "num_experts": w_gu.shape[0],
         "activation": "swiglu"})
    return out["Out"][0], float(out["HeldPairs"][0][0])


def _shared(seed=6, h=16, fs=20):
    r = np.random.default_rng(seed)
    return {"shared_gate_up": jnp.asarray(r.normal(size=(h, 2 * fs)) * 0.3,
                                          jnp.float32),
            "shared_down": jnp.asarray(r.normal(size=(fs, h)) * 0.3,
                                       jnp.float32),
            "shared_gate": jnp.asarray(r.normal(size=(h, 1)), jnp.float32)}


def _ref_layer(x, w_r, w_gu, w_down, shared, held):
    p = dict(shared, router=w_r, experts_gate_up=w_gu[held[0]:sum(held)],
             experts_down=w_down[held[0]:sum(held)])
    return ref._routed_layer(x, p, _MOE_CFG, None, held)


def test_the_softmax_router_and_the_sigmoid_router_unchanged():
    x, w_r, _, _ = _moe_inputs()
    soft = run_op("moe_router", {"X": [x], "W": [w_r]},
                  {"top_k": 3, "score_function": "softmax"})
    idx, w = ref.routing(x, w_r, _MOE_CFG)
    assert np.array_equal(np.asarray(soft["TopkIdx"][0]), np.asarray(idx))
    assert float(jnp.max(jnp.abs(soft["TopkWeight"][0] - w))) <= 1e-6
    assert float(jnp.max(jnp.abs(jnp.sum(soft["TopkWeight"][0], -1) - 1))) \
        <= 1e-6
    p = np.asarray(jax.nn.softmax(x @ w_r, axis=-1))
    raw = run_op("moe_router", {"X": [x], "W": [w_r]},
                 {"top_k": 3, "score_function": "softmax",
                  "norm_topk_prob": False})["TopkWeight"][0]
    assert float(jnp.max(jnp.abs(raw - np.sort(p, -1)[:, ::-1][:, :3]))) \
        <= 1e-6
    # the default is the sigmoid score, and its program is the parent's:
    # no softmax, one logistic
    sig = run_op("moe_router", {"X": [x], "W": [w_r]},
                 {"top_k": 3, "routed_scaling_factor": 2.5})
    s = np.asarray(jax.nn.sigmoid(x @ w_r))
    top = np.sort(s, -1)[:, ::-1][:, :3]
    assert float(jnp.max(jnp.abs(
        sig["TopkWeight"][0] - 2.5 * top / top.sum(-1, keepdims=True)))) \
        <= 1e-5
    text = str(jax.make_jaxpr(lambda a, b: run_op(
        "moe_router", {"X": [a], "W": [b]}, {"top_k": 3})["TopkWeight"][0])(
            x, w_r))
    assert "logistic" in text and "reduce_max" not in text
    with pytest.raises(KeyError):
        run_op("moe_router", {"X": [x], "W": [w_r]},
               {"top_k": 3, "score_function": "tanh"})


@pytest.mark.parametrize("row_block", [None, 16],
                         ids=["one_trip", "several_trips"])
def test_gated_experts_give_the_loop_over_experts_and_its_gradients(
        monkeypatch, row_block):
    if row_block:
        monkeypatch.setattr(hybrid_ops, "row_block",
                            lambda pairs, held, of: row_block)
    x, w_r, w_gu, w_down = _moe_inputs()
    shared = _shared()

    def program(x, w_gu, w_down):
        return _routed_share(x, w_r, w_gu, w_down, 0, 16)[0]

    def loop(x, w_gu, w_down):
        return _ref_layer(x, w_r, w_gu, w_down, shared, (0, 16)) \
            - ref._gated_mlp(x, shared["shared_gate_up"],
                             shared["shared_down"], None) \
            * jax.nn.sigmoid(x @ shared["shared_gate"])

    got, want = program(x, w_gu, w_down), loop(x, w_gu, w_down)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4
    ct = jnp.asarray(np.random.default_rng(8).normal(size=got.shape),
                     jnp.float32)
    g_got, g_want = (jax.grad(lambda *a, f=f: jnp.sum(f(*a) * ct),
                              argnums=(0, 1, 2))(x, w_gu, w_down)
                     for f in (program, loop))
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: every share's routed part plus the
    shared expert (with its gate) counted once is what the uncut
    reference gives for the whole layer."""
    x, w_r, w_gu, w_down = _moe_inputs()
    shared = _shared()
    parts = [_routed_share(x, w_r, w_gu, w_down, 4 * rank, 4)
             for rank in range(4)]
    shared_once = ref._gated_mlp(
        x, shared["shared_gate_up"], shared["shared_down"], None) \
        * jax.nn.sigmoid(x @ shared["shared_gate"])
    whole = _ref_layer(x, w_r, w_gu, w_down, shared, (0, 16))
    total = sum(p[0] for p in parts) + shared_once
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4
    assert sum(p[1] for p in parts) == 48 * 3      # every pair, once
    # and one share is the reference's same share
    one = parts[2][0] + shared_once
    assert float(jnp.max(jnp.abs(
        one - _ref_layer(x, w_r, w_gu, w_down, shared, (8, 4))))) <= 1e-4


def test_the_planner_gives_a_chip_32_of_512():
    from paddle_tpu.parallel import planner

    assert planner.experts_held(512, 16) == (0, 32)
    assert planner.experts_held(512, 16, 15) == (480, 32)


# -- gated attention ---------------------------------------------------------

def test_gated_attention_mixer_matches_the_reference():
    """The mixer alone, as a program of its own: the query's gate, the
    zero-centred norms over the head, the partial rotary embedding, 4
    query heads on 2 key/value heads."""
    from paddle_tpu.fluid import layers

    cfg = qwen3_next.Qwen3NextConfig.tiny()
    r = np.random.default_rng(13)
    x = r.normal(size=(_B, 24, cfg.hidden_size)).astype(np.float32)
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            xin = layers.data(name="x", shape=[24, cfg.hidden_size],
                              dtype="float32")
            out = qwen3_next.gated_attention_mixer(xin, cfg, "l3")
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    p = {}
    for par in main.all_parameters():
        w = r.normal(0.0, 0.25, par.shape).astype(np.float32)
        scope.set_var(par.name, jnp.asarray(w))
        p[par.name[len("l3."):]] = jnp.asarray(w)
    got = np.asarray(exe.run(main, feed={"x": x}, fetch_list=[out],
                             scope=scope)[0])
    for i in range(_B):
        want = ref._gated_attention(jnp.asarray(x[i]), p, _ref_cfg(cfg), None)
        assert float(jnp.max(jnp.abs(got[i] - want))) <= 1e-4


# -- the program -------------------------------------------------------------

def test_the_router_and_the_decay_stay_float32_under_decorate():
    cfg = qwen3_next.Qwen3NextConfig.tiny()
    main = CASE.build(cfg, True)[0]
    masters = main._amp_master_of
    block = main.global_block()
    pinned = [n for n, _, _, _ in ref.param_spec(_ref_cfg(cfg))
              if n.rsplit(".", 1)[-1] in (
        "router", "A_log", "dt_bias")]
    assert len(pinned) == 4 + 3 + 3
    for name in pinned:
        assert name not in masters, name
        assert str(block._find_var_recursive(name).dtype) == "float32"
    for name in ("l0.in_proj_qkvz", "l1.experts_gate_up", "l3.q_proj",
                 "l3.q_norm", "l2.gate_norm", "embed"):
        assert name in masters
        assert str(block._find_var_recursive(name).dtype) == "bfloat16"


def test_the_unrolled_stack_is_recomputed_a_part_at_a_time(caplog):
    """Every mixer's and every routed layer's output is a checkpoint:
    the record names a segment a part and the head, each with the
    narrow products it keeps, and the delta rule and the routed layers
    say what they hold when they are traced."""
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=(4, 2))
    weights, batch = _weights(cfg, 1), CASE.batch(cfg, 2)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        loss, _, main, exe = CASE.loss_and_grads(cfg, False, weights,
                                                     batch)
    saved = exe.remat_saved(main)
    assert len(saved) == 2 * cfg.num_hidden_layers + 1
    # kept: the products narrower than what they contract. A delta-rule
    # mixer's `in_proj_ba` (32 -> 8; its output projection is 32 -> 32),
    # a routed layer's shared gate (32 -> 1), the attention's output
    # projection (64 -> 32; its K and V projections are 32 -> 32)
    kept = [len(saved[k]["kept"]) for k in sorted(
        saved, key=lambda k: int(k.rsplit("seg", 1)[1]))]
    assert kept == [1, 1, 1, 1, 1, 1, 1, 1, 0]
    said = {r.getMessage() for r in caplog.records}
    assert any(m.startswith("gated_delta_rule q, k (2, 128, 2, 8) v "
                            "(2, 128, 4, 8) float32: 64 positions a chunk, "
                            "2 chunks, 1 head groups") for m in said)
    assert any(m.startswith("moe_experts holds experts [4, 6) of 8, top-3")
               for m in said)
    # the same loss and gradients without recompute
    plain, g_plain, _, _ = CASE.loss_and_grads(cfg, False, weights,
                                                   batch, remat=False)
    assert abs(plain - loss) <= 1e-6


def test_the_counters_come_with_the_loss():
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=(0, 4))
    main, startup, loss, counters = CASE.build(cfg, True)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=CASE.batch(cfg, 3), scope=scope, fetch_list=[
        loss, counters["moe.held_pairs"],
        counters["moe.load_max_over_mean"], counters["moe.rows_made"]])
    pairs, load, made = (float(np.asarray(v).reshape(())) for v in got[1:])
    assert 0 < pairs <= 4 * _B * _S * 3       # four routed layers
    assert 1.0 <= load <= 4.0
    # 480 pairs a layer are fewer than a row block: one trip of 512 each
    assert made == 4 * 512 >= pairs


def test_layer_kinds_follow_the_interval():
    cfg = qwen3_next.Qwen3NextConfig(num_hidden_layers=8)
    assert [cfg.is_attention(i) for i in range(8)] == [
        False, False, False, True] * 2
    assert [ref.is_attention({"full_attention_interval": 4}, i)
            for i in range(4)] == [False, False, False, True]
    with pytest.raises(ValueError, match="3 value heads on 2 key heads"):
        qwen3_next.Qwen3NextConfig(linear_num_value_heads=3,
                                   linear_num_key_heads=2)
