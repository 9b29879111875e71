"""The hybrid state-space / routed-expert decoder (`models/nemotron_h.py`)
and its ops, at a tiny preset on the CPU with seeded random weights,
against the benchmark's plain reference
(`benchmark/reference/nemotron_h.py`, which imports nothing of the
program)."""
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.models import nemotron_h
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.registry import run_op
from benchmark.reference import nemotron_h as ref
from decoder_case import NEMOTRON_H as CASE, lay, stamps_change_no_number

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_B, _S = CASE.b, CASE.s


def _ref_cfg(cfg):
    d = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "hybrid_override_pattern",
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
        "conv_kernel", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts_per_tok", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "routed_scaling_factor",
        "norm_topk_prob", "layer_norm_epsilon", "initializer_range")}
    d["n_routed_experts"] = cfg.experts_held[1]
    d["published"] = {"n_routed_experts": cfg.n_routed_experts}
    d["deployment"] = {"first_expert_held": cfg.experts_held[0]}
    return d


def _weights(cfg, seed):
    r = np.random.default_rng(seed)
    out = {}
    for name, shape, kind, scale in ref.param_spec(_ref_cfg(cfg)):
        if kind == "normal":
            # wider than the model's 0.02: every layer must matter
            out[name] = r.normal(0.0, 0.25, shape)
        elif kind == "uniform":
            out[name] = r.uniform(-scale, scale, shape)
        else:
            out[name] = np.full(shape, 1.0 if kind == "ones" else 0.0)
        if name.endswith("norm") or name.endswith(".D"):
            out[name] = out[name] + r.normal(0.0, 0.1, shape)
    return {k: np.asarray(v, np.float32)
            for k, v in ref.spread_ssm_init(out).items()}


@pytest.mark.parametrize("amp,loss_tol,grad_tol", [
    (False, 2e-5, 2e-3), (True, 2e-2, 0.2)],
    ids=["float32", "bfloat16_amp"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        amp, loss_tol, grad_tol):
    """float32 program: tight. Under bfloat16 AMP the band is what
    8 bits of mantissa through four layers leave: the loss to 2 %, the
    median leaf's gradient to 5 % of its norm and the worst to 20 %
    (read: 0.015 to 0.06 but for the last routed layer, whose router
    reads 0.13 and experts 0.11: a routing that flips at a near-tie
    between the bfloat16 and the float32 input moves a whole token)."""
    cfg = nemotron_h.NemotronHConfig.tiny(experts_held=(2, 4))
    weights, batch = _weights(cfg, 11), CASE.batch(cfg, 12)
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


def _recurrence(x, dt, dt_bias, a_log, bm, cm, d):
    """y_t = S_t C_t + D x_t, S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
    a position at a time."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    step = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    bh, ch = (jnp.repeat(t, h // g, axis=2) for t in (bm, cm))

    def one(state, inp):
        x_t, s_t, b_t, c_t = inp
        state = (jnp.exp(s_t * a)[..., None, None] * state
                 + (s_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = jax.lax.scan(one, jnp.zeros((b, h, p, n)), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, step, bh, ch)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def _scan_args(seq_len, seed=0, heads=4, p=8, g=2, n=16):
    r = np.random.default_rng(seed)
    shapes = ((2, seq_len, heads, p), (2, seq_len, heads), (heads,),
              (heads,), (2, seq_len, g, n), (2, seq_len, g, n), (heads,))
    return [jnp.asarray(r.normal(size=s), jnp.float32) for s in shapes]


@pytest.mark.parametrize("seq_len", [32, 40], ids=["whole_chunks",
                                                   "a_part_chunk"])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_ssd_chunk_scan_forward_and_backward_match_the_recurrence(
        seq_len, kernel):
    args = _scan_args(seq_len)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    chunked = lambda *a: hybrid_ops.ssd_chunk_scan(  # noqa: E731
        *a, chunk=16, kernel=kernel)
    want, got = _recurrence(*args), chunked(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    g_want = jax.grad(loss(_recurrence), argnums=tuple(range(7)))(*args)
    g_got = jax.grad(loss(chunked), argnums=tuple(range(7)))(*args)
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b))) + 1e-6


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_ssd_chunk_scan_names_its_parts_where_a_trace_reads_them(kernel):
    """`pt[kernel]` is the Pallas forward (made again in the recompute),
    `pt[local]` the `jax.numpy` group wherever it runs, the backward
    pass among it, `pt[groups]` the layouts round them."""
    made = "kernel" if kernel else "local"
    stamps_change_no_number(
        "ssd_chunk_scan", lambda *a: hybrid_ops.ssd_chunk_scan(
            *a, chunk=16, kernel=kernel), _scan_args(40),
        {"recompute": {made, "groups"}, "backward": {"local", "groups"}})


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_moe_experts_names_its_parts_where_a_trace_reads_them(gated):
    x, w_r, w_up, w_down = _moe_inputs()
    if gated:
        w_up = jnp.concatenate([w_up, w_up[:, :, ::-1]], axis=-1)
    score = jax.nn.sigmoid(x @ w_r)
    _, idx = jax.lax.top_k(score, 3)
    weight = jnp.take_along_axis(score, idx, axis=1)
    every = {"sort", "gather", "products", "scatter"}
    stamps_change_no_number(
        "moe_experts", lambda x, weight, w_up, w_down: hybrid_ops.moe_experts(
            x, idx, weight, w_up, w_down, held_start=4,
            activation="swiglu" if gated else "relu2", num_experts=16)[0],
        (x, weight, w_up[4:8], w_down[4:8]),
        {"recompute": every, "backward": every})


def _moe_inputs(tokens=48, hidden=16, experts=16, width=24, seed=5):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(tokens, hidden)), jnp.float32)
    w_r = jnp.asarray(r.normal(size=(hidden, experts)), jnp.float32)
    w_up = jnp.asarray(r.normal(size=(experts, hidden, width)) * 0.3,
                       jnp.float32)
    w_down = jnp.asarray(r.normal(size=(experts, width, hidden)) * 0.3,
                         jnp.float32)
    return x, w_r, w_up, w_down


def _routed_share(x, w_r, w_up, w_down, first, count, top_k=3):
    r = run_op("moe_router", {"X": [x], "W": [w_r]},
               {"top_k": top_k, "routed_scaling_factor": 2.5})
    o = run_op("moe_experts",
               {"X": [x], "TopkIdx": r["TopkIdx"],
                "TopkWeight": r["TopkWeight"],
                "WUp": [w_up[first:first + count]],
                "WDown": [w_down[first:first + count]]},
               {"held_start": first})
    return (o["Out"][0], float(o["HeldPairs"][0][0]),
            float(o["LoadMaxOverMean"][0][0]))


def _ref_layer(x, w_r, w_up, w_down, shared, held, top_k=3):
    cfg = {"num_experts_per_tok": top_k, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True}
    p = {"router": w_r, "experts_up": w_up[held[0]:held[0] + held[1]],
         "experts_down": w_down[held[0]:held[0] + held[1]],
         "shared_up": shared[0], "shared_down": shared[1]}
    return ref._experts(x, p, cfg, None, held)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Every share's routed part plus the shared expert counted once is
    what the uncut reference gives for the whole layer."""
    x, w_r, w_up, w_down = _moe_inputs()
    r = np.random.default_rng(6)
    shared = (jnp.asarray(r.normal(size=(16, 20)) * 0.3, jnp.float32),
              jnp.asarray(r.normal(size=(20, 16)) * 0.3, jnp.float32))
    parts = [_routed_share(x, w_r, w_up, w_down, e, 1) for e in range(16)]
    shared_once = ref._relu2_mlp(x, shared[0], shared[1], None)
    whole = _ref_layer(x, w_r, w_up, w_down, shared, (0, 16))
    total = sum(p[0] for p in parts) + shared_once
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4
    assert sum(p[1] for p in parts) == 48 * 3      # every pair, once
    # and a share of several experts is the reference's same share
    four = _routed_share(x, w_r, w_up, w_down, 4, 4)[0] + shared_once
    assert float(jnp.max(jnp.abs(
        four - _ref_layer(x, w_r, w_up, w_down, shared, (4, 4))))) <= 1e-4


@pytest.mark.parametrize("row_block", [512, 16],
                         ids=["one_block", "blocks_of_16"])
def test_no_token_is_dropped_under_a_routing_skewed_onto_one_expert(
        row_block, monkeypatch):
    """Every token sends a pair to expert 3 (its router column is
    large): the expert takes all 48, nothing is capped or dropped."""
    monkeypatch.setattr(hybrid_ops, "row_block", lambda *a: row_block)
    x, w_r, w_up, w_down = _moe_inputs()
    x = jnp.abs(x)
    w_r = w_r.at[:, 3].set(5.0)
    out, pairs, load = _routed_share(x, w_r, w_up, w_down, 0, 4)
    idx = run_op("moe_router", {"X": [x], "W": [w_r]},
                 {"top_k": 3})["TopkIdx"][0]
    assert bool(jnp.all(jnp.any(idx == 3, axis=1)))
    held = np.asarray((idx >= 0) & (idx < 4))
    assert pairs == held.sum() and held.sum() >= 48
    assert load >= 48 / (held.sum() / 4) - 1e-6
    zeros = (jnp.zeros((16, 1)), jnp.zeros((1, 16)))
    want = _ref_layer(x, w_r, w_up, w_down, zeros, (0, 4))
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-4 * float(
        jnp.max(jnp.abs(want)))


def _dense_masked_loop(x, idx, weight, w_up, w_down, first):
    """The routed part of the reference's `_experts`, the routing
    given: every held expert over every token, masked by the choice."""
    out = jnp.zeros_like(x)
    for e in range(w_up.shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * ref._relu2_mlp(x, w_up[e], w_down[e],
                                                  None)
    return out


def _routing_case(name):
    """(x, router, held experts' matrices, held) of 48 tokens routed
    top-3 over 16 experts."""
    x, w_r, w_up, w_down = _moe_inputs()
    x, w_r, (first, count) = {
        "uniform": (x, w_r, (4, 4)),
        "skewed_onto_one_held_expert": (
            jnp.abs(x), w_r.at[:, 6].set(5.0), (4, 4)),
        "every_pair_held_here": (x, w_r, (0, 16)),
        "no_pair_held_here": (
            jnp.abs(x), w_r.at[:, 12:].set(-5.0), (12, 4)),
    }[name]
    return (x, w_r, w_up[first:first + count],
            w_down[first:first + count], (first, count))


@pytest.mark.parametrize("row_block", [8, 16, None],
                         ids=["rows_of_8", "rows_of_16", "the_rule"])
@pytest.mark.parametrize("routing", [
    "uniform", "skewed_onto_one_held_expert", "every_pair_held_here",
    "no_pair_held_here"])
def test_the_row_blocks_a_routing_fills_give_the_dense_loops_layer(
        routing, row_block, monkeypatch):
    """The output and the gradients of the tokens, the routing weights
    and both stacks of matrices against the reference's dense masked
    loop, whatever the routing sends here: the worst case walks all
    T * k rows, none held walks nothing and gives zeros; `HeldPairs` is
    exact and `RowsMade` is whole row blocks."""
    if row_block:
        monkeypatch.setattr(hybrid_ops, "row_block", lambda *a: row_block)
    x, w_r, w_up, w_down, (first, count) = _routing_case(routing)
    r = run_op("moe_router", {"X": [x], "W": [w_r]},
               {"top_k": 3, "routed_scaling_factor": 2.5})
    idx, weight = r["TopkIdx"][0], r["TopkWeight"][0]
    # 144 pairs are fewer than the grouped product's tile of 512 rows
    block = hybrid_ops.row_block(idx.size, count, 16)
    assert block == (row_block or 512)

    per_expert = np.bincount(
        np.asarray(idx).reshape(-1), minlength=16)[first:first + count]
    held = int(per_expert.sum())
    trips = -(-held // block)
    if routing == "every_pair_held_here":
        assert held == 48 * 3 and trips == -(-48 * 3 // block)
    if routing == "no_pair_held_here":
        assert held == 0 and trips == 0
    if routing == "skewed_onto_one_held_expert":
        assert per_expert[2] == 48
    if routing == "uniform" and row_block == 8:
        # a group that lies in two row blocks, a last block partly live
        ends = np.cumsum(per_expert)
        assert any((e - 1) // block > (e - n) // block
                   for e, n in zip(ends, per_expert) if n)
        assert held % block

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    ours = lambda x, w, up, down: hybrid_ops.moe_experts(  # noqa: E731
        x, idx, w, up, down, first)[0]
    dense = lambda x, w, up, down: _dense_masked_loop(  # noqa: E731
        x, idx, w, up, down, first)
    args = (x, weight, w_up, w_down)
    want, got = dense(*args), ours(*args)
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale
    g_want = jax.grad(loss(dense), argnums=(0, 1, 2, 3))(*args)
    g_got = jax.grad(loss(ours), argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * max(
            float(jnp.max(jnp.abs(b))), 1.0)
    if not held:
        assert not float(jnp.max(jnp.abs(got)))
        assert not any(float(jnp.max(jnp.abs(g))) for g in g_got)

    o = run_op("moe_experts",
               {"X": [x], "TopkIdx": [idx], "TopkWeight": [weight],
                "WUp": [w_up], "WDown": [w_down]}, {"held_start": first})
    assert float(o["HeldPairs"][0][0]) == held
    assert float(o["RowsMade"][0][0]) == trips * block


def test_the_router_and_the_decay_stay_float32_under_decorate():
    cfg = nemotron_h.NemotronHConfig.tiny()
    weights, batch = _weights(cfg, 1), CASE.batch(cfg, 2)
    _, _, main, exe = CASE.loss_and_grads(cfg, True, weights, batch)
    masters = main._amp_master_of
    block = main.global_block()
    pinned = [n for n in weights if n.rsplit(".", 1)[-1] in (
        "router", "A_log", "dt_bias", "D")]
    assert len(pinned) == 2 + 3
    for name in pinned:
        assert name not in masters, name
        assert str(block._find_var_recursive(name).dtype) == "float32"
    for name in ("l0.in_proj", "l1.experts_up", "l2.q_proj", "embed"):
        assert name in masters
        assert str(block._find_var_recursive(name).dtype) == "bfloat16"
    # the ops themselves: float32 scores and steps from bfloat16 inputs
    x, w_r, _, _ = _moe_inputs()
    r = run_op("moe_router", {"X": [x.astype(jnp.bfloat16)], "W": [w_r]},
               {"top_k": 3})
    assert r["TopkWeight"][0].dtype == jnp.float32
    args = _scan_args(16)
    low = [a.astype(jnp.bfloat16) if i in (0, 1, 4, 5) else a
           for i, a in enumerate(args)]
    jaxpr = str(jax.make_jaxpr(lambda *a: hybrid_ops.ssd_chunk_scan(
        *a, chunk=8, kernel=False))(*low))
    assert "cumsum" in jaxpr and "bf16[2,2,8,4] = cumsum" not in jaxpr


def test_the_unrolled_stack_is_recomputed_a_block_at_a_time(caplog):
    """Every block's output is a checkpoint: the record names a segment
    a block and the head, each with the narrow products it keeps, and
    the routed layers say what they hold when they are traced."""
    cfg = nemotron_h.NemotronHConfig.tiny(experts_held=(4, 2))
    weights, batch = _weights(cfg, 1), CASE.batch(cfg, 2)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        loss, _, main, exe = CASE.loss_and_grads(cfg, False, weights,
                                                     batch)
    saved = exe.remat_saved(main)
    assert len(saved) == len(cfg.hybrid_override_pattern) + 1
    # kept, as a remat scan keeps BERT's FFN-out: the products narrower
    # than what they contract (a routed block's shared expert 40 -> 32,
    # the attention's K and V projections 32 -> 16)
    kept = [len(saved[k]["kept"]) for k in sorted(saved)]
    assert kept == [0, 1, 2, 1, 0]
    assert {r["name"] for v in saved.values() for r in v["kept"]} == {
        "narrow_matmul_product"}
    said = {r.getMessage() for r in caplog.records}
    # at the build's stand-in batch and at the fed one (144 pairs); the
    # Mamba blocks' convolutions say who convolves, off the TPU jax.numpy
    head = "moe_experts holds experts [4, 6) of 8, top-3: "
    conv = {m for m in said if m.startswith("causal_conv1d ")}
    assert conv and all(m.endswith(", a bias, silu: convolved by jax.numpy")
                        for m in conv)
    assert all(m.startswith(head) for m in said - conv)
    assert head + ("512 rows a trip, 1 trips if every pair is held "
                   "here") in said
    # the same loss without recompute
    main2, startup2, loss2, _ = CASE.build(cfg, False, remat=False)
    scope = Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(startup2, scope=scope)
    lay(main2, scope, weights)
    plain = exe2.run(main2, feed=batch, fetch_list=[loss2], scope=scope)[0]
    assert abs(float(np.asarray(plain).reshape(())) - loss) <= 1e-6


def test_the_counters_come_with_the_loss():
    cfg = nemotron_h.NemotronHConfig.tiny(experts_held=(0, 4))
    main, startup, loss, counters = CASE.build(cfg, True)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=CASE.batch(cfg, 3), scope=scope, fetch_list=[
        loss, counters["moe.held_pairs"],
        counters["moe.load_max_over_mean"], counters["moe.rows_made"]])
    pairs, load, made = (float(np.asarray(v).reshape(())) for v in got[1:])
    assert 0 < pairs <= 2 * _B * _S * 3       # two routed layers
    assert 1.0 <= load <= 4.0
    # 144 pairs a layer are fewer than a row block: one trip of 512 each
    assert made == 2 * 512 >= pairs


@pytest.mark.parametrize("experts, ranks", [(128, 16), (8, 4)])
def test_the_shares_of_a_group_cover_every_expert_once(experts, ranks):
    from paddle_tpu.parallel import planner

    shares = [planner.experts_held(experts, ranks, r) for r in range(ranks)]
    assert shares[0] == (0, experts // ranks)
    assert sorted(e for first, n in shares
                  for e in range(first, first + n)) == list(range(experts))
    with pytest.raises(ValueError):
        planner.experts_held(experts, ranks + 1)


def test_a_share_past_the_last_expert_is_refused():
    x, w_r, w_up, w_down = _moe_inputs()
    r = run_op("moe_router", {"X": [x], "W": [w_r]}, {"top_k": 3})
    with pytest.raises(ValueError, match=r"\[14, 18\) of 16"):
        run_op("moe_experts",
               {"X": [x], "TopkIdx": r["TopkIdx"],
                "TopkWeight": r["TopkWeight"], "WUp": [w_up[:4]],
                "WDown": [w_down[:4]]},
               {"held_start": 14, "num_experts": 16})


def test_grouped_query_flash_attention_matches_the_reference():
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(2, 4, 64, 16)), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(2, 2, 64, 16)), jnp.float32)
            for _ in range(2))

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a, causal=True))),
            argnums=(0, 1, 2))(q, k, v)

    got, g_got = both(lambda *a, causal: fa.flash_attention(
        *a, causal=causal, block_q=16, block_k=16))
    want, g_want = both(fa.reference_attention)
    assert abs(float(got - want)) <= 1e-4
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5


def test_the_flash_kernels_names_are_what_the_benchmark_matches():
    """`flash_attn_share_pct` and `flash_attn_roofline_pct` find the
    kernels by strings in an operation's text: each kernel's name holds
    every string of one entry, `tpu_custom_call` apart (the custom
    call's target, which the compiled text adds)."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert len(set(fa.KERNEL_NAMES)) == 3
    for metric in ("flash_attn_share_pct", "flash_attn_roofline_pct"):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               metric + ".json")) as f:
            match = json.load(f)["args"]["match"]
        for name in fa.KERNEL_NAMES:
            assert any(all(part in name for part in entry
                           if part != "tpu_custom_call")
                       for entry in match), (metric, name)
