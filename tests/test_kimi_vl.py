"""The latent-attention / routed-expert decoder (`models/kimi_vl.py`) at
a tiny preset on the CPU with seeded random weights, against the
benchmark's plain reference (`benchmark/reference/kimi_vl.py`, which
imports nothing of the program: attention as the masked square over a
head's own unrotated key and the one rotary key, the experts as a
loop)."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import framework
from paddle_tpu.models import kimi_vl
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.registry import run_op
from benchmark.reference import kimi_vl as ref
from decoder_case import KIMI_VL as CASE

_B, _S = CASE.b, CASE.s

_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
         "intermediate_size", "moe_intermediate_size", "n_shared_experts",
         "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
         "rms_norm_eps", "initializer_range")


def _ref_cfg(cfg):
    d = {k: getattr(cfg, k) for k in _KEYS}
    d["n_routed_experts"] = cfg.experts_held[1]
    d["published"] = {"n_routed_experts": cfg.n_routed_experts}
    d["deployment"] = {"first_expert_held": cfg.experts_held[0]}
    return d


def _weights(cfg, seed, std=0.25):
    r = np.random.default_rng(seed)
    out = {}
    for name, shape, kind, _ in ref.param_spec(_ref_cfg(cfg)):
        # wider than the model's 0.02: every layer must matter
        out[name] = r.normal(0.0, std, shape) if kind == "normal" \
            else 1.0 + r.normal(0.0, 0.1, shape)
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


@pytest.mark.parametrize("amp,std,loss_tol,grad_tol", [
    (False, 0.25, 2e-5, 2e-3), (True, 0.05, 2e-2, 0.5)],
    ids=["float32", "bfloat16_amp"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        amp, std, loss_tol, grad_tol):
    """float32 program: tight, the joined key of 24 against the two
    products of the reference, values of 12, flash's stand-in against
    the masked square, the sorted grouped products against the loop
    over experts. Under bfloat16 AMP the band is what 8 bits of
    mantissa through two layers of two parts each leave at 80 tokens,
    where one routing that flips at a near-tie moves a leaf's gradient
    by a tenth (the qwen3-next test's band, for its reason)."""
    cfg = kimi_vl.KimiVLConfig.tiny(experts_held=(2, 4))
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


# -- latent attention --------------------------------------------------------

def test_latent_attention_mixer_matches_the_reference():
    """The mixer alone, as a program of its own: queries and keys of
    16 + 8 on values of 12, the latent's own norm, the rotary embedding
    on the 8 alone, one rotary key head read by all 4 query heads."""
    from paddle_tpu.fluid import layers

    cfg = kimi_vl.KimiVLConfig.tiny()
    r = np.random.default_rng(13)
    x = r.normal(size=(_B, 24, cfg.hidden_size)).astype(np.float32)
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            xin = layers.data(name="x", shape=[24, cfg.hidden_size],
                              dtype="float32")
            out = kimi_vl.latent_attention_mixer(xin, cfg, "l1")
    assert tuple(out.shape)[1:] == (24, cfg.hidden_size)
    sdpa = [op for op in main.global_block().ops
            if op.type == "scaled_dot_product_attention"]
    assert len(sdpa) == 1
    block = main.global_block()
    q, k, v = (block._find_var_recursive(sdpa[0].input(s)[0])
               for s in "QKV")
    assert tuple(q.shape)[1:] == tuple(k.shape)[1:] == (4, 24, 24)
    assert tuple(v.shape)[1:] == (4, 24, 12)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    p = {}
    for par in main.all_parameters():
        w = r.normal(0.0, 0.25, par.shape).astype(np.float32)
        scope.set_var(par.name, jnp.asarray(w))
        p[par.name[len("l1."):]] = jnp.asarray(w)
    got = np.asarray(exe.run(main, feed={"x": x}, fetch_list=[out],
                             scope=scope)[0])
    for i in range(_B):
        want = ref.latent_attention(jnp.asarray(x[i]), p, _ref_cfg(cfg))
        assert float(jnp.max(jnp.abs(got[i] - want))) <= 1e-4


def test_the_rotary_key_is_one_head_and_turns_with_position():
    """The reference's score is q_nope . k_nope[h] + q_rope . k_r: with
    the unrotated parts at zero the scores of all heads read the one
    rotary key, and depend on the distance alone."""
    theta = 800000.0
    r = np.random.default_rng(5)
    q = jnp.asarray(np.tile(r.normal(size=(1, 1, 8)), (6, 4, 1)), jnp.float32)
    k = jnp.asarray(np.tile(r.normal(size=(1, 1, 8)), (6, 1, 1)), jnp.float32)
    s = jnp.einsum("qhd,kd->hqk", ref.rotary(q, theta),
                   ref.rotary(k, theta)[:, 0])
    for h in range(4):
        for d in range(1, 5):
            assert abs(float(s[h, d, 0] - s[0, d + 1, 1])) <= 1e-5
    got = run_op("rotary_embedding", {"X": [q[None]]},
                 {"rotary_dim": 8, "theta": theta})["Out"][0][0]
    assert float(jnp.max(jnp.abs(got - ref.rotary(q, theta)))) <= 1e-6


# -- the routed layer --------------------------------------------------------

_MOE_CFG = {"num_experts_per_tok": 3, "norm_topk_prob": True,
            "routed_scaling_factor": 2.446}


def _moe_inputs(seed=4, t=48, h=16, f=12, experts=64, fs=24):
    r = np.random.default_rng(seed)

    def a(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    return a(t, h), {"router": a(h, experts),
                     "experts_gate_up": a(experts, h, 2 * f, scale=0.3),
                     "experts_down": a(experts, f, h, scale=0.3),
                     "shared_gate_up": a(h, 2 * fs, scale=0.3),
                     "shared_down": a(fs, h, scale=0.3)}


def _routed_share(x, p, first, count, bias=None):
    ins = {"X": [x], "W": [p["router"]]}
    if bias is not None:
        ins["Bias"] = [bias]
    r = run_op("moe_router", ins,
               {"top_k": 3, "routed_scaling_factor": 2.446})
    out = run_op("moe_experts", {
        "X": [x], "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
        "WUp": [p["experts_gate_up"][first:first + count]],
        "WDown": [p["experts_down"][first:first + count]]},
        {"held_start": first, "num_experts": p["router"].shape[1],
         "activation": "swiglu"})
    return out["Out"][0], float(out["HeldPairs"][0][0])


def _ref_layer(x, p, held):
    cut = dict(p, experts_gate_up=p["experts_gate_up"][held[0]:sum(held)],
               experts_down=p["experts_down"][held[0]:sum(held)])
    return ref.routed_layer(x, cut, _MOE_CFG, None, held)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """64 experts over 8 shares: every share's routed part plus the two
    shared experts (one SwiGLU, ungated) counted once is what the uncut
    reference gives for the whole layer."""
    x, p = _moe_inputs()
    parts = [_routed_share(x, p, 8 * rank, 8) for rank in range(8)]
    shared_once = ref._gated_mlp(x, p["shared_gate_up"], p["shared_down"],
                                 None)
    whole = _ref_layer(x, p, (0, 64))
    total = sum(part[0] for part in parts) + shared_once
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4
    assert sum(part[1] for part in parts) == 48 * 3      # every pair, once
    # and one share is the reference's same share
    one = parts[5][0] + shared_once
    assert float(jnp.max(jnp.abs(one - _ref_layer(x, p, (40, 8))))) <= 1e-4


def test_the_planner_gives_a_chip_8_of_64():
    from paddle_tpu.parallel import planner

    assert planner.experts_held(64, 8) == (0, 8)
    assert planner.experts_held(64, 8, 7) == (56, 8)
    with pytest.raises(ValueError):
        planner.experts_held(64, 8, 8)


def test_the_router_against_a_hand_computation():
    """Sigmoid scores over all 64, the 3 largest of score + bias, the
    chosen scores (not score + bias) over their sum, times 2.446; a
    bias steers the choice alone and gets no gradient."""
    x, p = _moe_inputs()
    w_r = p["router"]
    s = np.asarray(jax.nn.sigmoid(x @ w_r), np.float64)
    bias = np.zeros(64, np.float32)
    bias[[3, 9]] = 5.0, -5.0
    for b in (None, jnp.asarray(bias)):
        ins = {"X": [x], "W": [w_r]}
        pick = s
        if b is not None:
            ins["Bias"], pick = [b], s + bias
        got = run_op("moe_router", ins,
                     {"top_k": 3, "routed_scaling_factor": 2.446})
        idx = np.argsort(-pick, axis=-1, kind="stable")[:, :3]
        assert np.array_equal(np.asarray(got["TopkIdx"][0]), idx)
        chosen = np.take_along_axis(s, idx, axis=1)
        want = 2.446 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        assert float(np.max(np.abs(np.asarray(got["TopkWeight"][0])
                                   - want))) <= 1e-5
        assert float(np.max(np.abs(
            np.asarray(got["TopkWeight"][0]).sum(-1) - 2.446))) <= 1e-5
    assert (idx == 3).any(axis=1).all() and not (idx == 9).any()
    # the reference's routing is the op's at a bias of zero
    r_idx, r_w = ref.routing(x, w_r, _MOE_CFG)
    op = run_op("moe_router", {"X": [x], "W": [w_r]},
                {"top_k": 3, "routed_scaling_factor": 2.446})
    assert np.array_equal(np.asarray(r_idx), np.asarray(op["TopkIdx"][0]))
    assert float(jnp.max(jnp.abs(r_w - op["TopkWeight"][0]))) <= 1e-6
    grad = jax.grad(lambda b: jnp.sum(run_op(
        "moe_router", {"X": [x], "W": [w_r], "Bias": [b]},
        {"top_k": 3})["TopkWeight"][0] ** 2))(jnp.asarray(bias))
    assert float(jnp.max(jnp.abs(grad))) == 0.0


# -- the program -------------------------------------------------------------

def test_the_router_and_its_bias_stay_float32_under_decorate():
    cfg = kimi_vl.KimiVLConfig.tiny()
    main = CASE.build(cfg, True)[0]
    masters = main._amp_master_of
    block = main.global_block()
    pinned = ["l1.router", "l1.router_bias"]
    for name in pinned:
        assert name not in masters, name
        assert str(block._find_var_recursive(name).dtype) == "float32"
    assert not block._find_var_recursive("l1.router_bias").trainable
    assert block._find_var_recursive("l0.router") is None    # dense
    for name in ("l0.gate_up", "l0.q_proj", "l1.kv_a_proj", "l1.kv_a_norm",
                 "l1.kv_b_proj", "l1.experts_gate_up", "l1.shared_down",
                 "embed"):
        assert name in masters
        assert str(block._find_var_recursive(name).dtype) == "bfloat16"


def test_the_unrolled_stack_of_two_layer_kinds_is_recomputed_a_part_at_a_time(
        caplog):
    """Every mixer's and every feed-forward's output is a checkpoint:
    the record names a segment a part and the head, and the two kinds
    of feed-forward keep different things (the dense layer's down
    product 48 -> 32 is narrow; a routed layer keeps none). The routed
    layers say what they hold when they are traced."""
    cfg = kimi_vl.KimiVLConfig.tiny(experts_held=(4, 2))
    weights, batch = _weights(cfg, 1), CASE.batch(cfg, 2)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        loss, _, main, exe = CASE.loss_and_grads(cfg, False, weights,
                                                     batch)
    saved = exe.remat_saved(main)
    assert len(saved) == 2 * cfg.num_hidden_layers + 1
    kept = [len(saved[k]["kept"]) for k in sorted(
        saved, key=lambda k: int(k.rsplit("seg", 1)[1]))]
    # a mixer keeps its two narrow products (32 -> 28 to the latent and
    # the rotary key, 48 -> 32 out; q_proj 32 -> 96 and kv_b_proj
    # 20 -> 112 widen), the dense layer its down product (48 -> 32), a
    # routed layer none (its shared experts' 32 -> 32 is no narrower)
    assert kept == [2, 1, 2, 0, 0]
    said = {r.getMessage() for r in caplog.records}
    assert any(m.startswith("moe_experts holds experts [4, 6) of 8, top-3")
               for m in said)
    # the same loss without recompute
    plain, _, _, _ = CASE.loss_and_grads(cfg, False, weights, batch,
                                             remat=False)
    assert abs(plain - loss) <= 1e-6


def test_the_counters_come_with_the_loss():
    cfg = kimi_vl.KimiVLConfig.tiny(experts_held=(0, 4))
    main, startup, loss, counters = CASE.build(cfg, True)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=CASE.batch(cfg, 3), scope=scope, fetch_list=[
        loss, counters["moe.held_pairs"],
        counters["moe.load_max_over_mean"], counters["moe.rows_made"]])
    pairs, load, made = (float(np.asarray(v).reshape(())) for v in got[1:])
    assert 0 < pairs <= _B * _S * 3           # one routed layer
    assert 1.0 <= load <= 4.0
    # 240 pairs are fewer than a row block: one trip of 512
    assert made == 512 >= pairs


def test_layer_kinds_follow_first_k_dense_replace():
    cfg = kimi_vl.KimiVLConfig(num_hidden_layers=4)
    assert [cfg.is_dense(i) for i in range(4)] == [True, False, False, False]
    assert [ref.is_dense({"first_k_dense_replace": 1}, i)
            for i in range(3)] == [True, False, False]
    names = {n for n, _, _, _ in ref.param_spec(_ref_cfg(
        kimi_vl.KimiVLConfig.tiny()))}
    assert "l0.gate_up" in names and "l0.router" not in names
    assert "l1.router" in names and "l1.gate_up" not in names


def test_a_mixers_segment_keeps_the_flash_kernels_output_and_statistics(
        monkeypatch):
    """The segment path of PR 36: with attention in the flash kernels
    (as on the chip at the cell's length) every mixer's segment keeps
    the kernels' output `[B heads, S, v_head_dim]` and S floats a head
    of row statistics beside its narrow products, and the whole step
    holds one forward kernel a layer where it held two."""
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES
    from paddle_tpu.utils import flags
    from decoder_case import flash_from_its_length, step_jaxpr, walk

    flash_from_its_length(monkeypatch)
    monkeypatch.setitem(flags._FLAGS, "FLAGS_flash_attention_min_seq", _S)
    cfg = kimi_vl.KimiVLConfig.tiny(experts_held=(0, 4))
    main, startup, loss, _ = CASE.build(cfg, True)
    jaxpr = step_jaxpr(main, startup, CASE.batch(cfg, 3), loss)
    calls = [e.params["name"] for _, e in walk(jaxpr)
             if e.primitive.name == "pallas_call"
             and e.params["name"] in KERNEL_NAMES]
    layers_n = cfg.num_hidden_layers
    assert {n: calls.count(n) for n in KERNEL_NAMES} == dict.fromkeys(
        KERNEL_NAMES, layers_n)

    saved = fluid.Executor(fluid.CPUPlace()).remat_saved(main)
    segs = [saved[k] for k in sorted(
        saved, key=lambda k: int(k.rsplit("seg", 1)[1]))]
    heads, dv = cfg.num_attention_heads, cfg.v_head_dim
    out, stats = _B * heads * _S * dv * 2, _B * heads * _S * 4
    for mixer in segs[0:2 * layers_n:2]:
        residual = [(r["shape"], r["dtype"], r["bytes"])
                    for r in mixer["kept"]
                    if r["name"] == "flash_attention_residual"]
        assert residual == [([_B * heads, _S, dv], "bfloat16", out),
                            ([_B * heads, _S], "float32", stats)]
        # besides the two narrow products it kept before
        assert len(mixer["kept"]) == 4 and mixer["n"] == 1
        assert mixer["bytes_per_layer"] == out + stats + sum(
            r["bytes"] for r in mixer["kept"]
            if r["name"] == "narrow_matmul_product")
    for other in segs[1:2 * layers_n:2] + segs[2 * layers_n:]:
        assert not any(r["name"] == "flash_attention_residual"
                       for r in other["kept"])
