"""The window/full-attention decoder with routed experts
(`models/laguna.py`) at a tiny preset on the CPU with seeded random
weights, against the benchmark's plain reference
(`benchmark/reference/laguna.py`, which imports nothing of the program:
attention as the masked square with the window an explicit mask, YaRN
from its formulas, the experts as a loop)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import framework, layers
from paddle_tpu.models import laguna
from paddle_tpu.ops.registry import run_op
from benchmark.reference import laguna as ref
from decoder_case import LAGUNA as CASE

_B, _S = CASE.b, CASE.s

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "num_experts_per_tok", "moe_intermediate_size",
         "shared_expert_intermediate_size", "norm_topk_prob",
         "mlp_only_layers", "sliding_window", "rope_parameters",
         "layer_types", "num_attention_heads_per_layer",
         "initializer_range")


def _ref_cfg(cfg):
    d = {k: getattr(cfg, k) for k in _KEYS}
    d["moe_routed_scaling_factor"] = cfg.routed_scaling_factor
    d["num_experts"] = cfg.experts_held[1]
    d["published"] = {"num_experts": cfg.num_experts}
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
    """float32 program: tight, a full layer with YaRN on half its head,
    a window of 8 under 40 positions, 6 and 4 query heads on 2, the
    per-head gates, the sorted grouped products against the loop over
    experts. Under bfloat16 AMP the band is the other routed decoders'
    (a routing that flips at a near-tie moves a leaf by a tenth)."""
    cfg = laguna.LagunaConfig.tiny(experts_held=(2, 4))
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


# -- attention ---------------------------------------------------------------

def _mixer_program(cfg, layer, s):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            x = layers.data(name="x", shape=[s, cfg.hidden_size],
                            dtype="float32")
            out = laguna.attention_mixer(x, cfg, "l%d" % layer, layer)
    return main, startup, out


def _run_mixer(cfg, layer, x, p):
    main, startup, out = _mixer_program(cfg, layer, x.shape[1])
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for name, w in p.items():
        scope.set_var("l%d.%s" % (layer, name), jnp.asarray(w))
    return np.asarray(exe.run(main, feed={"x": x}, fetch_list=[out],
                              scope=scope)[0])


def _mixer_weights(cfg, layer, r):
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads_per_layer[layer], \
        cfg.num_key_value_heads
    shapes = {"q_proj": (h, nq * d), "k_proj": (h, nkv * d),
              "v_proj": (h, nkv * d), "g_proj": (h, nq),
              "o_proj": (nq * d, h)}
    return {k: r.normal(0.0, 0.25, s).astype(np.float32)
            for k, s in shapes.items()}


@pytest.mark.parametrize("layer", [0, 1], ids=["full_yarn", "window"])
def test_the_attention_mixer_matches_the_reference(layer):
    """The mixer alone, as a program of its own: the layer's head count
    on 2 key/value heads, its rotary, its window, the gate of a head."""
    cfg = laguna.LagunaConfig.tiny()
    r = np.random.default_rng(13 + layer)
    x = r.normal(size=(_B, 24, cfg.hidden_size)).astype(np.float32)
    p = _mixer_weights(cfg, layer, r)
    main = _mixer_program(cfg, layer, 24)[0]
    sdpa = [op for op in main.global_block().ops
            if op.type == "scaled_dot_product_attention"]
    assert len(sdpa) == 1
    assert sdpa[0].attr("window") == (8 if layer == 1 else None)
    got = _run_mixer(cfg, layer, x, p)
    for i in range(_B):
        want = ref.attention(jnp.asarray(x[i]),
                             {k: jnp.asarray(v) for k, v in p.items()},
                             _ref_cfg(cfg), layer)
        assert float(jnp.max(jnp.abs(got[i] - want))) <= 1e-4


@pytest.mark.parametrize("layer", [0, 1], ids=["full_yarn", "window"])
def test_the_two_head_halves_add_up_to_the_uncut_attention_layer(layer):
    """Tensor parallelism over a pair: each half holds half of the query
    heads with their gates and `o_proj` rows and the key/value heads
    they read; the program's two halves' outputs add up to the uncut
    layer's (the reference's, at the published head counts)."""
    whole = laguna.LagunaConfig.tiny(num_attention_heads_per_layer=[8, 12, 8],
                                     num_key_value_heads=4)
    half = laguna.LagunaConfig.tiny(num_attention_heads_per_layer=[4, 6, 4],
                                    num_key_value_heads=2)
    r = np.random.default_rng(17 + layer)
    x = r.normal(size=(1, 32, whole.hidden_size)).astype(np.float32)
    p = _mixer_weights(whole, layer, r)
    d = whole.head_dim
    nq, nkv = half.num_attention_heads_per_layer[layer], 2
    total = 0.0
    for rank in (0, 1):
        q, kv = slice(rank * nq * d, (rank + 1) * nq * d), \
            slice(rank * nkv * d, (rank + 1) * nkv * d)
        share = {"q_proj": p["q_proj"][:, q], "k_proj": p["k_proj"][:, kv],
                 "v_proj": p["v_proj"][:, kv],
                 "g_proj": p["g_proj"][:, rank * nq:(rank + 1) * nq],
                 "o_proj": p["o_proj"][q]}
        total = total + _run_mixer(half, layer, x, share)
    want = ref.attention(jnp.asarray(x[0]),
                         {k: jnp.asarray(v) for k, v in p.items()},
                         _ref_cfg(whole), layer)
    assert float(jnp.max(jnp.abs(total[0] - want))) <= 1e-4


def test_yarn_angles_and_scale_against_the_formulas():
    """The full layers' rotary: YaRN on the first 64 of 128 columns,
    theta 5e5, factor 128 over 8,192 positions, beta 32 and 1: the ramp
    runs from column 9 to 18 (low = floor(64 ln(8192 / 64 pi) /
    2 ln 5e5), high = ceil(64 ln(8192 / 2 pi) / 2 ln 5e5)); cos and sin
    times 1.4852, the 64 others unscaled. The op and the reference
    against a hand computation."""
    rope = laguna.LagunaConfig().rope_parameters["full_attention"]
    theta, width = 500000.0, 64
    low = math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (9, 18)
    i = np.arange(32, dtype=np.float64)
    inv_e = theta ** (-2 * i / width)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = inv_e / 128 * ramp + inv_e * (1 - ramp)
    got_width, got_inv, scale = ref.frequencies(rope, 128)
    assert got_width == 64 and scale == pytest.approx(1.4852030263919618)
    np.testing.assert_allclose(np.asarray(got_inv), inv, rtol=2e-6)
    # one token of ones at positions 0 .. 4095: column c < 32 turns by
    # angle t inv_c, its pair (c + 32) likewise; the rest pass
    s = 4096
    x = np.zeros((1, s, 1, 128), np.float32)
    x[..., :32] = 1.0
    out = np.asarray(run_op("rotary_embedding", {"X": [jnp.asarray(x)]}, {
        "rotary_dim": 64, "theta": theta, "yarn_factor": 128.0,
        "yarn_original_max_position": 8192.0, "yarn_beta_fast": 32.0,
        "yarn_beta_slow": 1.0, "yarn_attention_factor": 1.4852030263919618,
    })["Out"][0])[0, :, 0]
    angle = np.arange(s)[:, None] * inv[None, :]
    np.testing.assert_allclose(out[:, :32], 1.4852030263919618
                               * np.cos(angle), atol=2e-3)
    np.testing.assert_allclose(out[:, 32:64], 1.4852030263919618
                               * np.sin(angle), atol=2e-3)
    assert not out[:, 64:].any()
    want = np.asarray(ref.rotary(jnp.asarray(x[0]), rope))
    np.testing.assert_allclose(out, want[:, 0], atol=2e-3)
    # the default rotary of a window layer: all 128 columns at 1e4
    plain = laguna.LagunaConfig().rope_parameters["sliding_attention"]
    assert ref.frequencies(plain, 128)[0] == 128
    assert ref.frequencies(plain, 128)[2] == 1.0


def test_the_rotary_layer_takes_yarn_and_refuses_what_it_does_not_build():
    main = framework.Program()
    with framework.program_guard(main, framework.Program()):
        x = layers.data(name="x", shape=[8, 2, 16], dtype="float32")
        layers.rotary_embedding(x, 16, 1e4)
        layers.rotary_embedding(x, 8, 1e4, scaling={
            "rope_type": "yarn", "factor": 4, "beta_fast": 32,
            "beta_slow": 1, "original_max_position_embeddings": 64,
            "attention_factor": 1.1})
        layers.rotary_embedding(x, 16, 1e4, scaling={"rope_type": "default"})
        with pytest.raises(ValueError, match="only yarn"):
            layers.rotary_embedding(x, 16, 1e4,
                                    scaling={"rope_type": "longrope"})
    ops = [op for op in main.global_block().ops
           if op.type == "rotary_embedding"]
    assert [sorted(op.attrs) for op in ops[::2]] == [
        ["rotary_dim", "theta"]] * 2
    assert ops[1].attr("yarn_factor") == 4.0
    assert ops[1].attr("yarn_attention_factor") == 1.1


# -- the routed layer --------------------------------------------------------

_MOE_CFG = {"num_experts_per_tok": 10, "norm_topk_prob": True,
            "moe_routed_scaling_factor": 2.5}


def _moe_inputs(seed=4, t=48, h=16, f=8, experts=256, fs=8):
    r = np.random.default_rng(seed)

    def a(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    return a(t, h), {"router": a(h, experts),
                     "experts_gate_up": a(experts, h, 2 * f, scale=0.3),
                     "experts_down": a(experts, f, h, scale=0.3),
                     "shared_gate_up": a(h, 2 * fs, scale=0.3),
                     "shared_down": a(fs, h, scale=0.3),
                     "shared_gate": a(h, 1)}


def _routed_share(x, p, first, count):
    r = run_op("moe_router", {"X": [x], "W": [p["router"]]},
               {"top_k": 10, "routed_scaling_factor": 2.5,
                "score_function": "softmax"})
    out = run_op("moe_experts", {
        "X": [x], "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
        "WUp": [p["experts_gate_up"][first:first + count]],
        "WDown": [p["experts_down"][first:first + count]]},
        {"held_start": first, "num_experts": p["router"].shape[1],
         "activation": "swiglu"})
    return out["Out"][0], float(out["HeldPairs"][0][0])


def test_the_32_shares_add_up_to_the_uncut_layer():
    """256 experts over 32 shares of 8: every share's routed part plus
    the gated shared expert counted once is what the uncut reference
    gives for the whole layer (softmax over 256, top-10, renormalised,
    times 2.5)."""
    x, p = _moe_inputs()
    parts = [_routed_share(x, p, 8 * rank, 8) for rank in range(32)]
    shared_once = ref._gated_mlp(x, p["shared_gate_up"], p["shared_down"],
                                 None) * jax.nn.sigmoid(x @ p["shared_gate"])
    whole = ref.routed_layer(x, p, _MOE_CFG, None, (0, 256))
    total = sum(part[0] for part in parts) + shared_once
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4
    assert sum(part[1] for part in parts) == 48 * 10      # every pair, once
    # and one share is the reference's same share
    cut = dict(p, experts_gate_up=p["experts_gate_up"][40:48],
               experts_down=p["experts_down"][40:48])
    one = parts[5][0] + shared_once
    assert float(jnp.max(jnp.abs(
        one - ref.routed_layer(x, cut, _MOE_CFG, None, (40, 8))))) <= 1e-4
    # the routed weights of a token sum to the scaling factor
    _, w = ref.routing(x, p["router"], _MOE_CFG)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-5)


def test_the_planner_gives_a_chip_8_of_256():
    from paddle_tpu.parallel import planner

    assert planner.experts_held(256, 32) == (0, 8)
    assert planner.experts_held(256, 32, 31) == (248, 8)


# -- the program -------------------------------------------------------------

def test_layer_kinds_heads_and_windows_follow_the_config():
    cfg = laguna.LagunaConfig()
    assert cfg.num_attention_heads_per_layer[:5] == [48, 72, 72, 72, 48]
    assert [cfg.window(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert [cfg.is_dense(i) for i in range(3)] == [True, False, False]
    assert cfg.routed_scaling_factor == 2.5
    with pytest.raises(ValueError, match="query heads"):
        laguna.LagunaConfig.tiny(num_attention_heads_per_layer=[4, 5, 4])
    names = {n for n, _, _, _ in ref.param_spec(_ref_cfg(
        laguna.LagunaConfig.tiny()))}
    assert "l0.gate_up" in names and "l0.router" not in names
    assert {"l1.router", "l1.shared_gate", "l1.g_proj"} <= names


def test_the_router_stays_float32_under_decorate():
    cfg = laguna.LagunaConfig.tiny()
    main = CASE.build(cfg, True)[0]
    masters = main._amp_master_of
    block = main.global_block()
    assert "l1.router" not in masters
    assert str(block._find_var_recursive("l1.router").dtype) == "float32"
    for name in ("l0.gate_up", "l0.q_proj", "l1.g_proj", "l2.o_proj",
                 "l1.experts_gate_up", "l1.shared_gate", "embed"):
        assert name in masters
        assert str(block._find_var_recursive(name).dtype) == "bfloat16"


def test_the_counters_come_with_the_loss_and_recompute_changes_nothing():
    cfg = laguna.LagunaConfig.tiny(experts_held=(0, 4))
    main, startup, loss, counters = CASE.build(cfg, True)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=CASE.batch(cfg, 3), scope=scope, fetch_list=[
        loss, counters["moe.held_pairs"],
        counters["moe.load_max_over_mean"], counters["moe.rows_made"]])
    pairs, load, made = (float(np.asarray(v).reshape(())) for v in got[1:])
    assert 0 < pairs <= 2 * _B * _S * 3           # two routed layers
    assert 1.0 <= load <= 4.0
    assert made == 2 * 512 >= pairs
    weights, batch = _weights(cfg, 1), CASE.batch(cfg, 2)
    with_remat, _, main, exe = CASE.loss_and_grads(cfg, False, weights,
                                                       batch)
    assert len(exe.remat_saved(main)) == 2 * cfg.num_hidden_layers + 1
    plain, _, _, _ = CASE.loss_and_grads(cfg, False, weights, batch,
                                             remat=False)
    assert abs(plain - with_remat) <= 1e-6


def test_the_window_layer_runs_the_window_kernels_once_a_layer(monkeypatch):
    """With attention in the flash kernels (as on the chip at the
    cell's length) the window layer's three calls are the windowed
    kernels and the full layers' the causal ones; each segment keeps
    its kernels' output and row statistics, so the step holds one
    forward kernel a layer."""
    from paddle_tpu.ops.pallas.flash_attention import (KERNEL_NAMES,
                                                       WINDOW_KERNEL_NAMES)
    from paddle_tpu.utils import flags
    from decoder_case import flash_from_its_length, step_jaxpr, walk

    flash_from_its_length(monkeypatch)
    monkeypatch.setitem(flags._FLAGS, "FLAGS_flash_attention_min_seq", _S)
    cfg = laguna.LagunaConfig.tiny(experts_held=(0, 4))
    main, startup, loss, _ = CASE.build(cfg, True)
    jaxpr = step_jaxpr(main, startup, CASE.batch(cfg, 3), loss)
    calls = [e.params["name"] for _, e in walk(jaxpr)
             if e.primitive.name == "pallas_call"
             and "scaled_dot_product_attention" in (e.params["name"] or "")]
    counted = {n: calls.count(n) for n in set(calls)}
    assert counted == dict(dict.fromkeys(KERNEL_NAMES, 2),
                           **dict.fromkeys(WINDOW_KERNEL_NAMES, 1))
    saved = fluid.Executor(fluid.CPUPlace()).remat_saved(main)
    segs = [saved[k] for k in sorted(
        saved, key=lambda k: int(k.rsplit("seg", 1)[1]))]
    for layer, mixer in enumerate(segs[0:2 * cfg.num_hidden_layers:2]):
        heads = cfg.num_attention_heads_per_layer[layer]
        residual = [(r["shape"], r["dtype"]) for r in mixer["kept"]
                    if r["name"] == "flash_attention_residual"]
        assert residual == [([_B * heads, _S, cfg.head_dim], "bfloat16"),
                            ([_B * heads, _S], "float32")]
