"""layers.Scan — lax.scan-backed fixed-trip loop over stacked [n, ...]
parameters (the TPU-native deep-stack builder; no direct reference
counterpart: the reference's recurrent_op (operators/recurrent_op.cc)
steps a sub-block via scope mutation, here the loop is functional so
grads are ordinary jax.vjp through lax.scan). Covers: training through
the scan, remat, per-iteration dropout keys, and EXACT forward parity
of the scan BERT encoder against the unrolled one under shared
parameter values."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models import bert
from __graft_entry__ import _bert_feed


def _run(main, st, feed, fetch):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    return exe, lambda: np.asarray(
        exe.run(main, feed=feed, fetch_list=[fetch])[0])


def test_scan_trains_through_stacked_params():
    L, H = 3, 8
    main, st = framework.Program(), framework.Program()
    main.random_seed = st.random_seed = 5
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            x = fluid.layers.data("x", shape=[H], dtype="float32")
            w = fluid.layers.create_parameter(
                shape=[L, H, H], dtype="float32", name="stk.w",
                default_initializer=fluid.initializer.TruncatedNormal(
                    0.0, 0.2))
            h = fluid.layers.fc(x, size=H)
            scan = fluid.layers.Scan(n=L)
            with scan.block():
                wi = scan.slice_input(w)
                nh = fluid.layers.relu(fluid.layers.matmul(h, wi))
                fluid.layers.assign(nh, output=h)
            loss = fluid.layers.mean(h)
            fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    exe, step = _run(main, st, {"x": np.ones((2, H), np.float32)}, loss)
    w0 = np.asarray(global_scope().find_var("stk.w")).copy()
    ls = [float(step().ravel()[0]) for _ in range(4)]
    w1 = np.asarray(global_scope().find_var("stk.w"))
    assert np.isfinite(ls).all()
    assert ls[-1] != ls[0], "loss did not move"
    # grads reached EVERY slice of the stacked param
    per_layer_delta = np.abs(w1 - w0).reshape(L, -1).max(axis=1)
    assert (per_layer_delta > 0).all(), per_layer_delta


def test_scan_without_carry_rebind_raises():
    """A body that never rebinds a pre-existing var would discard every
    iteration's results — the lowering refuses it (mirrors the while
    cond-rebind check)."""
    H = 4
    main, st = framework.Program(), framework.Program()
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            x = fluid.layers.data("x", shape=[H], dtype="float32")
            w = fluid.layers.create_parameter(
                shape=[2, H, H], dtype="float32", name="nc.w")
            h = fluid.layers.fc(x, size=H)
            scan = fluid.layers.Scan(n=2)
            with scan.block():
                wi = scan.slice_input(w)
                fluid.layers.matmul(h, wi)  # result dropped: no assign
            loss = fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    with pytest.raises(Exception, match="never rebinds"):
        exe.run(main, feed={"x": np.ones((2, H), np.float32)},
                fetch_list=[loss])


def test_scan_slice_leading_dim_mismatch_raises():
    main, st = framework.Program(), framework.Program()
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            w = fluid.layers.create_parameter(
                shape=[4, 3], dtype="float32", name="w")
            scan = fluid.layers.Scan(n=3)
            with pytest.raises(ValueError, match="leading dim"):
                with scan.block():
                    scan.slice_input(w)
                    # unreachable; block exits via the raise
                    raise AssertionError


def _snapshot_params(prog):
    return {p.name: np.asarray(global_scope().find_var(p.name)).copy()
            for p in prog.all_parameters()}


def _stack_unrolled_into_scan(vals, cfg):
    """Assemble the scan path's stacked [L, ...] params from the
    unrolled per-layer values (q|k|v fused on the output axis)."""
    L = cfg.num_hidden_layers
    out = {}
    out["enc_qkv.w"] = np.stack([np.concatenate(
        [vals["layer_%d_attn_q.w" % i], vals["layer_%d_attn_k.w" % i],
         vals["layer_%d_attn_v.w" % i]], axis=1) for i in range(L)])
    out["enc_qkv.b"] = np.stack([np.concatenate(
        [vals["layer_%d_attn_q.b" % i], vals["layer_%d_attn_k.b" % i],
         vals["layer_%d_attn_v.b" % i]]) for i in range(L)])
    for scan_name, unroll_fmt in [
            ("enc_attn_out.w", "layer_%d_attn_out.w"),
            ("enc_attn_out.b", "layer_%d_attn_out.b"),
            ("enc_post_att_ln.scale", "layer_%d_post_att_ln.scale"),
            ("enc_post_att_ln.bias", "layer_%d_post_att_ln.bias"),
            ("enc_ffn0.w", "layer_%d_ffn0.w"),
            ("enc_ffn0.b", "layer_%d_ffn0.b"),
            ("enc_ffn1.w", "layer_%d_ffn1.w"),
            ("enc_ffn1.b", "layer_%d_ffn1.b"),
            ("enc_post_ffn_ln.scale", "layer_%d_post_ffn_ln.scale"),
            ("enc_post_ffn_ln.bias", "layer_%d_post_ffn_ln.bias")]:
        out[scan_name] = np.stack(
            [vals[unroll_fmt % i] for i in range(L)])
    return out


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.slow
def test_scan_bert_forward_parity_with_unrolled(remat):
    """Same parameter values => identical loss (is_test kills dropout).
    Also proves remat does not change the math."""
    cfg = bert.BertConfig.tiny()
    SEQ, B = 32, 2
    feed = _bert_feed(cfg, B, SEQ, max_pred=int(SEQ * 0.15))

    main_u, st_u = framework.Program(), framework.Program()
    main_u.random_seed = st_u.random_seed = 7
    with framework.program_guard(main_u, st_u):
        with framework.unique_name_guard():
            tot_u, _, _, _ = bert.bert_pretrain_loss(cfg, SEQ,
                                                     is_test=True)
    _, run_u = _run(main_u, st_u, feed, tot_u)
    loss_u = float(run_u().ravel()[0])
    vals = _snapshot_params(main_u)

    main_s, st_s = framework.Program(), framework.Program()
    main_s.random_seed = st_s.random_seed = 7
    with framework.program_guard(main_s, st_s):
        with framework.unique_name_guard():
            tot_s, _, _, _ = bert.bert_pretrain_loss(
                cfg, SEQ, is_test=True, scan_layers=True,
                scan_remat=remat)
    exe_s, run_s = _run(main_s, st_s, feed, tot_s)
    # overwrite shared params (embeddings/heads: same names) and
    # assemble the stacked encoder params from the unrolled values
    import jax.numpy as jnp

    stacked = _stack_unrolled_into_scan(vals, cfg)
    for name, v in {**vals, **stacked}.items():
        if global_scope().find_var(name) is not None \
                or name in stacked:
            global_scope().set_var(name, jnp.asarray(v))
    loss_s = float(run_s().ravel()[0])
    np.testing.assert_allclose(loss_s, loss_u, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_scan_bert_train_decreases_and_per_layer_dropout_differs():
    cfg = bert.BertConfig.tiny()
    SEQ, B = 32, 4
    main, st = framework.Program(), framework.Program()
    main.random_seed = st.random_seed = 9
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, SEQ, is_test=False, scan_layers=True,
                scan_remat=True)
            fluid.optimizer.AdamOptimizer(1e-3).minimize(total)
    feed = _bert_feed(cfg, B, SEQ, max_pred=int(SEQ * 0.15))
    _, step = _run(main, st, feed, total)
    ls = [float(step().ravel()[0]) for _ in range(6)]
    assert np.isfinite(ls).all()
    assert ls[-1] < ls[0], ls


# ---------------------------------------------------------------------------
# remat=True with a policy: the checkpoint keeps the dropout masks and
# the narrow matmul product (ops/remat_names.py), recomputes the rest
# ---------------------------------------------------------------------------

_B, _S = 3, 8  # with BertConfig.tiny: H 64, F 128, 4 heads, 2 layers


def _bare_checkpoint(monkeypatch):
    """The lowering as it was: jax.checkpoint(body, policy=None)."""
    import jax

    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


def _build_scan_bert(remat=True, amp=True, seed=9, seq=_S):
    from paddle_tpu.fluid.contrib import mixed_precision

    cfg = bert.BertConfig.tiny()
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seq)
    main, st = framework.Program(), framework.Program()
    main.random_seed = st.random_seed = seed
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, seq, is_test=False, scan_layers=True,
                scan_remat=remat)
            opt = fluid.optimizer.AdamOptimizer(1e-3)
            if amp:
                opt = mixed_precision.decorate(
                    opt, use_dynamic_loss_scaling=False)
            opt.minimize(total)
    return cfg, main, st, total, _bert_feed(cfg, _B, seq, max_pred=2)


def _step_jaxpr(main, st, feed, fetch):
    """The jaxpr of the whole train step, traced and not compiled."""
    import jax
    from paddle_tpu.fluid import lowering

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    block = main.global_block()
    feeds = exe._prepare_feed(block, feed)
    state_in, _ = lowering.analyze_block(block, list(feeds), [fetch.name])
    specs = {n: global_scope().find_var(n) for n in state_in}
    entry = lowering.compile_block(main, block, feeds, [fetch.name], specs)
    return entry.jitted.trace(
        {n: exe._aval_of(a) for n, a in feeds.items()},
        {n: exe._aval_of(specs[n]) for n in entry.state_mut_names},
        {n: exe._aval_of(specs[n]) for n in entry.state_ro_names},
        jax.ShapeDtypeStruct((), np.uint32)).jaxpr.jaxpr


def _walk(jaxpr, path=()):
    """(names of the enclosing equations, equation), depth first."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, path + (eqn.primitive.name,))


def _layer_scans(jaxpr, n_layers):
    """(forward, backward) scan equations of the encoder stack: the
    forward draws the masks, the backward holds the recompute."""
    fwd = bwd = None
    for path, eqn in _walk(jaxpr):
        if eqn.primitive.name != "scan" or path \
                or eqn.params["length"] != n_layers:
            continue
        inner = {e.primitive.name for _, e in _walk(eqn.params["jaxpr"].jaxpr)}
        if "random_bits" in inner and fwd is None:
            fwd = eqn
        elif "remat2" in inner or "checkpoint" in inner:
            bwd = eqn
    assert fwd is not None and bwd is not None
    return fwd, bwd


def _dots(scan_eqn):
    """(lhs shape, rhs shape, contracted rhs dim) of every dot_general
    under a scan equation."""
    out = []
    for _, e in _walk(scan_eqn.params["jaxpr"].jaxpr):
        if e.primitive.name == "dot_general":
            (_, rc), _ = e.params["dimension_numbers"]
            out.append((tuple(e.invars[0].aval.shape),
                        tuple(e.invars[1].aval.shape), tuple(rc)))
    return out


@pytest.mark.parametrize("policy,draws", [(True, 1), (False, 2)])
def test_remat_scan_draws_each_mask_once_with_the_policy(
        monkeypatch, policy, draws):
    """The differentiated step holds each mask's random_bits once where
    the bare checkpoint holds it twice (forward and recompute)."""
    if not policy:
        _bare_checkpoint(monkeypatch)
    cfg, main, st, total, feed = _build_scan_bert()
    jaxpr = _step_jaxpr(main, st, feed, total)
    heads = cfg.num_attention_heads
    per_shape = {(_B, heads, _S, _S): 0, (_B, _S, cfg.hidden_size): 0}
    for path, eqn in _walk(jaxpr):
        if eqn.primitive.name == "random_bits" and "scan" in path:
            per_shape[tuple(eqn.outvars[0].aval.shape)] += 1
    # one attention mask and two hidden masks a layer
    assert per_shape == {(_B, heads, _S, _S): draws,
                         (_B, _S, cfg.hidden_size): 2 * draws}


def test_remat_scan_recompute_drops_only_the_narrow_product():
    cfg, main, st, total, feed = _build_scan_bert()
    h, f = cfg.hidden_size, cfg.intermediate_size
    fwd, bwd = _layer_scans(_step_jaxpr(main, st, feed, total),
                            cfg.num_hidden_layers)
    ffn_out = ((_B, _S, f), (f, h), (0,))
    still = [((_B, _S, h), (h, 3 * h), (0,)),      # q/k/v
             ((_B, _S, h), (h, f), (0,)),          # FFN-in
             ((_B, _S, h), (h, h), (0,))]          # attention output
    assert ffn_out in _dots(fwd)
    bwd_dots = _dots(bwd)
    assert ffn_out not in bwd_dots
    for dot in still:
        assert dot in _dots(fwd) and dot in bwd_dots, dot


def test_remat_scan_stacks_carry_masks_and_narrow_product():
    """What the forward scan hands the backward: the carry at each
    layer's entry, three boolean masks and the bf16 FFN-out product —
    no other activation."""
    cfg, main, st, total, feed = _build_scan_bert()
    L, h = cfg.num_hidden_layers, cfg.hidden_size
    fwd, _ = _layer_scans(_step_jaxpr(main, st, feed, total), L)
    n_carry = fwd.params["num_carry"]
    stacked = sorted((str(v.aval.dtype), tuple(v.aval.shape))
                     for v in fwd.outvars[n_carry:])
    assert stacked == sorted([
        ("bool", (L, _B, cfg.num_attention_heads, _S, _S)),
        ("bool", (L, _B, _S, h)), ("bool", (L, _B, _S, h)),
        ("bfloat16", (L, _B, _S, h)),
        ("float32", (L, _B, _S, h))])


def test_remat_policy_is_bit_equal_to_bare_checkpoint(monkeypatch):
    """Same keys, same masks, same products: three Adam steps with the
    policy equal three under a bare jax.checkpoint to the last bit."""
    def three_steps():
        _, main, st, total, feed = _build_scan_bert()
        _, step = _run(main, st, feed, total)
        losses = [step() for _ in range(3)]
        return losses, _snapshot_params(main)

    losses_p, params_p = three_steps()
    with monkeypatch.context() as m:
        _bare_checkpoint(m)
        losses_b, params_b = three_steps()
    np.testing.assert_array_equal(losses_p, losses_b)
    assert params_p.keys() == params_b.keys()
    for name in params_p:
        np.testing.assert_array_equal(params_p[name], params_b[name],
                                      err_msg=name)


def _build_resnet50():
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import resnet

    main, st = framework.Program(), framework.Program()
    main.random_seed = st.random_seed = 3
    with framework.program_guard(main, st):
        with framework.unique_name_guard():
            img = fluid.layers.data("image", shape=[3, 32, 32],
                                    dtype="float32")
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    resnet.resnet(img, class_dim=1000, depth=50), label))
            mixed_precision.decorate(
                fluid.optimizer.MomentumOptimizer(0.02, 0.9),
                use_dynamic_loss_scaling=False).minimize(loss)
    feed = {"image": np.zeros((2, 3, 32, 32), np.float32),
            "label": np.zeros((2, 1), np.int64)}
    return main, st, feed, loss


def _build_scan_bert_no_remat():
    _, main, st, total, feed = _build_scan_bert(remat=False)
    return main, st, feed, total


@pytest.mark.parametrize("build", [_build_scan_bert_no_remat,
                                   _build_resnet50])
def test_programs_without_remat_lower_as_before(monkeypatch, build):
    """Outside a remat scan no value is named: the jaxpr is the one the
    ops gave before they could name anything (`keep` the identity)."""
    from paddle_tpu.ops import remat_names

    jaxpr = _step_jaxpr(*build())
    assert not any(e.primitive.name == "name" for _, e in _walk(jaxpr))
    monkeypatch.setattr(remat_names, "keep", lambda x, name: x)
    assert str(_step_jaxpr(*build())) == str(jaxpr)


def _build_scan_bert_remat():
    _, main, st, total, feed = _build_scan_bert(remat=True)
    return main, st, feed, total


def _build_nemotron_h():
    """The hybrid decoder's step: bf16 AMP, every block recomputed."""
    from paddle_tpu.models import nemotron_h
    from test_nemotron_h import _batch, _build

    cfg = nemotron_h.NemotronHConfig.tiny(experts_held=(0, 4))
    main, st, loss, _ = _build(cfg, True)
    return main, st, _batch(cfg, 3), loss


def _build_qwen3_next():
    """The gated-delta-rule / gated-attention decoder's step: bf16 AMP,
    every mixer and every routed layer recomputed."""
    from paddle_tpu.models import qwen3_next
    from test_qwen3_next import _batch, _build

    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=(0, 4))
    main, st, loss, _ = _build(cfg, True)
    return main, st, _batch(cfg, 3), loss


_FLASH_S = 128


def _build_scan_bert_flash():
    _, main, st, total, feed = _build_scan_bert(seq=_FLASH_S)
    return main, st, feed, total


def _flash_from_its_length(monkeypatch):
    """Attention of `_FLASH_S` keys goes to the flash kernels, as on
    the chip (tests/test_sdpa_dispatch.py does the same)."""
    import importlib

    import jax
    from paddle_tpu.utils import flags

    # the package's attribute of that name is the function
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(flags._FLAGS, "FLAGS_flash_attention_min_seq",
                        _FLASH_S)


#: sha256 of the step's jaxpr (addresses scrubbed) on this container's
#: jax. The first two as PR 26 lowered them; PR 27 (new ops, AMP's
#: fp32-pinned parameter slots, the segment policy, grouped-query
#: flash) left both as they were. The fourth as PR 28 lowered it; the
#: hybrid decoder's was retaken in PR 30 (`moe_experts` walks row
#: blocks in a loop, with a gradient of its own) and PR 31 (a score
#: function for the router, an offset for the norm's weight, both
#: attributes it does not set) left all four as they were and added
#: the fifth, its own decoder's (retaken in PR 34: the delta rule's
#: triangular inverse has a gradient of its own): with these every kind
#: of step the benchmark runs is held. The fourth was retaken in PR 36:
#: a flash call traced in a checkpointed body names its output and its
#: compact row statistics for the checkpoint to keep, so the scan stacks
#: the two and its recompute holds no forward kernel; the other four
#: (no flash kernel at these lengths) stood. A PR that means to change
#: one of these programs replaces the digest and says so
_STEP_DIGESTS = {
    "_build_scan_bert_remat": "f6d6b541724972c8",
    "_build_resnet50": "deed87d731a3ffb9",
    "_build_nemotron_h": "9df08f21cb176523",
    "_build_scan_bert_flash": "b097f3d589655a72",
    "_build_qwen3_next": "3c544571148cfcdf",
}


@pytest.mark.parametrize("build", [_build_scan_bert_remat,
                                   _build_resnet50, _build_nemotron_h,
                                   _build_scan_bert_flash,
                                   _build_qwen3_next])
def test_berts_and_resnets_steps_are_the_accepted_programs(
        monkeypatch, build):
    import hashlib
    import re

    import jax

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    if build is _build_scan_bert_flash:
        _flash_from_its_length(monkeypatch)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(_step_jaxpr(*build())))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _STEP_DIGESTS[build.__name__]


def test_remat_saved_record_lists_the_flash_residual(monkeypatch, caplog):
    """At a length the flash kernels take, the scan keeps their output
    and S floats a head of row statistics beside the two hidden masks
    and the narrow product (the attention mask is made in the kernel),
    stacks exactly those, and its recompute holds no forward kernel."""
    import logging

    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES

    _flash_from_its_length(monkeypatch)
    cfg, main, st, total, feed = _build_scan_bert(seq=_FLASH_S)
    L, h, heads = (cfg.num_hidden_layers, cfg.hidden_size,
                   cfg.num_attention_heads)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.fluid.lowering"):
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[total])
    (_, rec), = exe.remat_saved(main).items()
    out = _B * heads * _FLASH_S * (h // heads) * 2      # bfloat16
    stats = _B * heads * _FLASH_S * 4                   # float32, compact
    hidden_mask = _B * _FLASH_S * h
    product = _B * _FLASH_S * h * 2
    assert [(r["name"], r["shape"], r["dtype"], r["bytes"])
            for r in rec["kept"]] == [
        ("flash_attention_residual", [_B * heads, _FLASH_S, h // heads],
         "bfloat16", out),
        ("flash_attention_residual", [_B * heads, _FLASH_S], "float32",
         stats),
        ("dropout_keep_mask", [_B, _FLASH_S, h], "bool", hidden_mask),
        ("narrow_matmul_product", [_B, _FLASH_S, h], "bfloat16", product),
        ("dropout_keep_mask", [_B, _FLASH_S, h], "bool", hidden_mask)]
    assert rec["bytes_per_layer"] == out + stats + 2 * hidden_mask + product
    assert rec["bytes_over_scan"] == L * rec["bytes_per_layer"]
    said = [r for r in caplog.records if "keeps across" in r.getMessage()]
    assert len(said) == 1 and "flash_attention_residual float32[%d, %d]" % (
        _B * heads, _FLASH_S) in said[0].getMessage()

    fwd, bwd = _layer_scans(_step_jaxpr(main, st, feed, total), L)
    stacked = sorted((str(v.aval.dtype), tuple(v.aval.shape))
                     for v in fwd.outvars[fwd.params["num_carry"]:])
    assert stacked == sorted([
        ("bfloat16", (L, _B * heads, _FLASH_S, h // heads)),
        ("float32", (L, _B * heads, _FLASH_S)),
        # the layer's index: the recompute folds it into the key again
        # for the seed the backward kernels hash
        ("int32", (L,)),
        ("bool", (L, _B, _FLASH_S, h)), ("bool", (L, _B, _FLASH_S, h)),
        ("bfloat16", (L, _B, _FLASH_S, h)),
        ("float32", (L, _B, _FLASH_S, h))])

    def kernels(scan_eqn):
        return sorted(e.params["name"]
                      for _, e in _walk(scan_eqn.params["jaxpr"].jaxpr)
                      if e.primitive.name == "pallas_call")

    assert kernels(fwd) == [KERNEL_NAMES[0]]
    assert kernels(bwd) == sorted(KERNEL_NAMES[1:])


def test_remat_saved_record_matches_hand_arithmetic(caplog):
    import logging

    cfg, main, st, total, feed = _build_scan_bert()
    L, h, heads = (cfg.num_hidden_layers, cfg.hidden_size,
                   cfg.num_attention_heads)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    assert exe.remat_saved(main) == {}  # nothing traced yet
    with caplog.at_level(logging.INFO, logger="paddle_tpu.fluid.lowering"):
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[total])
    (marker, rec), = exe.remat_saved(main).items()
    assert marker.startswith("pp[b0;") and ";scan;" in marker
    attn_mask = _B * heads * _S * _S          # one byte an element
    hidden_mask = _B * _S * h
    product = _B * _S * h * 2                 # bfloat16
    assert rec["n"] == L
    assert [(r["name"], r["shape"], r["dtype"], r["bytes"])
            for r in rec["kept"]] == [
        ("dropout_keep_mask", [_B, heads, _S, _S], "bool", attn_mask),
        ("dropout_keep_mask", [_B, _S, h], "bool", hidden_mask),
        ("narrow_matmul_product", [_B, _S, h], "bfloat16", product),
        ("dropout_keep_mask", [_B, _S, h], "bool", hidden_mask)]
    assert rec["bytes_per_layer"] == attn_mask + 2 * hidden_mask + product
    assert rec["bytes_over_scan"] == L * rec["bytes_per_layer"]
    # logged once for the compiled entry, not once a step or a trace
    said = [r for r in caplog.records if "keeps across" in r.getMessage()]
    assert len(said) == 1 and str(rec["bytes_over_scan"]) in \
        said[0].getMessage()
    # a program whose scan has no remat keeps nothing and says nothing
    _, main_n, st_n, total_n, _ = _build_scan_bert(remat=False)
    exe.run(st_n)
    exe.run(main_n, feed=feed, fetch_list=[total_n])
    assert exe.remat_saved(main_n) == {}
