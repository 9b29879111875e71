"""Golden tests for the Pallas flash-attention kernel (interpret mode on
the CPU test mesh) against the naive XLA reference — forward and grads.

Mirrors the reference's OpTest check_output/check_grad discipline
(`python/paddle/fluid/tests/unittests/op_test.py:948,1236`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention, reference_attention


def _rand_qkv(rng, B, H, Sq, Sk, D, dtype="float32"):
    q = rng.standard_normal((B, H, Sq, D)).astype(dtype)
    k = rng.standard_normal((B, H, Sk, D)).astype(dtype)
    v = rng.standard_normal((B, H, Sk, D)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, 2, 2, 256, 256, 64)
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_key_bias_padding_mask():
    rng = np.random.default_rng(1)
    B, Sk = 2, 256
    q, k, v = _rand_qkv(rng, B, 2, 128, Sk, 64)
    mask = np.ones((B, Sk), np.float32)
    mask[0, 200:] = 0.0
    mask[1, 64:] = 0.0
    bias = jnp.asarray((mask - 1.0) * 1e4)
    out = flash_attention(q, k, v, key_bias=bias)
    ref = reference_attention(q, k, v, key_bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_unaligned_seq_lens_padded():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, 1, 2, 100, 100, 64)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, 1, 2, 128, 128, 64)
    w = jnp.asarray(rng.standard_normal((1, 2, 128, 64)).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=3e-4, rtol=3e-4,
                                   err_msg="d%s mismatch" % name)


def test_grads_with_bias_nondiff():
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, 1, 1, 128, 128, 64)
    mask = np.ones((1, 128), np.float32)
    mask[0, 96:] = 0.0
    bias = jnp.asarray((mask - 1.0) * 1e4)
    w = jnp.asarray(rng.standard_normal((1, 1, 128, 64)).astype("float32"))

    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, key_bias=bias) * w))(q)
    gr = jax.grad(lambda q: jnp.sum(
        reference_attention(q, k, v, key_bias=bias) * w))(q)
    np.testing.assert_allclose(g, gr, atol=3e-4, rtol=3e-4)


def test_bfloat16_close():
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, 1, 2, 128, 128, 64)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    np.testing.assert_allclose(out.astype(np.float32), ref,
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# In-kernel dropout (VERDICT r4 #3a): mask is a counter-based hash of
# GLOBAL (row, col, head, seed) coordinates — reproducible on the host,
# so fwd AND grads are checked EXACTLY against a reference computed with
# the identical mask.
# ---------------------------------------------------------------------------

def _fmix(x):
    """murmur3's finalizer on uint32."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _host_keep(seed, BH, S, Sk, p):
    """Numpy replica of the kernels' `_dropout_keep` over the full
    [BH, S, Sk] lattice (blocking-independent by construction): a hash
    a row and batch * head, a hash a column, one xor and one wrapping
    product an element, compared as int32."""
    r = np.arange(S, dtype=np.uint32)[None, :]
    c = np.arange(Sk, dtype=np.uint32)
    b = np.arange(BH, dtype=np.uint32)[:, None]
    with np.errstate(over="ignore"):
        rows = _fmix((r * np.uint32(0x9E3779B1))
                     ^ (b * np.uint32(0xC2B2AE3D)) ^ np.uint32(seed))
        cols = _fmix((c * np.uint32(0x85EBCA77)) ^ np.uint32(seed)
                     ^ np.uint32(0x27D4EB2F))
        x = (rows[:, :, None] ^ cols[None, None, :]) * np.uint32(0x9E3779B1)
    thresh = np.int32(min(int(p * 4294967296.0), 0xFFFFFFFF) - 2 ** 31)
    return x.view(np.int32) >= thresh


def _host_dropout_mask(seed, BH, S, Sk, p):
    return np.where(_host_keep(seed, BH, S, Sk, p), 1.0 / (1.0 - p),
                    0.0).astype(np.float32)


def _masked_reference(q, k, v, mask_bhsk, sm_scale=None):
    """dropout(softmax(s)) @ v with an explicit [B*H, Sq, Sk] mask."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    p = jax.nn.softmax(s, axis=-1)
    z = p * mask_bhsk.reshape(B, H, Sq, Sk)
    return jnp.einsum("bhqk,bhkd->bhqd", z,
                      v.astype(jnp.float32)).astype(q.dtype)


def test_dropout_forward_exact_vs_host_mask():
    rng = np.random.default_rng(7)
    B, H, S, D, p, seed = 2, 2, 256, 64, 0.3, 12345
    q, k, v = _rand_qkv(rng, B, H, S, S, D)
    out = flash_attention(q, k, v, dropout_p=p,
                          dropout_seed=jnp.int32(seed))
    mask = _host_dropout_mask(seed, B * H, S, S, p)
    ref = _masked_reference(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_dropout_blocking_independent_and_deterministic():
    rng = np.random.default_rng(8)
    q, k, v = _rand_qkv(rng, 1, 2, 256, 256, 64)
    seed = jnp.int32(99)
    a = flash_attention(q, k, v, dropout_p=0.2, dropout_seed=seed)
    b = flash_attention(q, k, v, dropout_p=0.2, dropout_seed=seed,
                        block_q=64, block_k=64)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    c = flash_attention(q, k, v, dropout_p=0.2,
                        dropout_seed=jnp.int32(100))
    assert not np.allclose(a, c)


def test_dropout_grads_exact_vs_host_mask():
    rng = np.random.default_rng(9)
    B, H, S, D, p, seed = 1, 2, 128, 64, 0.25, 4242
    q, k, v = _rand_qkv(rng, B, H, S, S, D)
    w = jnp.asarray(rng.standard_normal((B, H, S, D)).astype("float32"))
    mask = _host_dropout_mask(seed, B * H, S, S, p)

    g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, dropout_p=p, dropout_seed=jnp.int32(seed)) * w),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        _masked_reference(q, k, v, mask) * w),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=3e-4, rtol=3e-4,
                                   err_msg="d%s mismatch" % name)


def test_dropout_rate_and_keyed_bias_interaction():
    rng = np.random.default_rng(10)
    B, H, S, D, p = 1, 2, 256, 64, 0.4
    q, k, v = _rand_qkv(rng, B, H, S, S, D)
    mask = _host_dropout_mask(777, B * H, S, S, p)
    drop_frac = float((mask == 0.0).mean())
    assert abs(drop_frac - p) < 0.02  # hash uniformity sanity

    # padding bias composes with dropout (padded keys stay dead)
    pad = np.ones((B, S), np.float32)
    pad[0, 200:] = 0.0
    bias = jnp.asarray((pad - 1.0) * 1e4)
    out = flash_attention(q, k, v, key_bias=bias, dropout_p=p,
                          dropout_seed=jnp.int32(777))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    s = s + bias[:, None, None, :]
    z = jax.nn.softmax(s, axis=-1) * mask.reshape(B, H, S, S)
    ref = jnp.einsum("bhqk,bhkd->bhqd", z, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_dropout_zero_p_matches_plain():
    rng = np.random.default_rng(11)
    q, k, v = _rand_qkv(rng, 1, 1, 128, 128, 64)
    a = flash_attention(q, k, v)
    b = flash_attention(q, k, v, dropout_p=0.0)
    np.testing.assert_allclose(a, b)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, dropout_p=0.5)  # seed required


# -- decode shapes (serving): q_len=1 and ragged batches --------------------

def test_decode_q_len_1_matches_reference():
    """The serving decode shape: ONE query row against a long cached
    context (q block pads 1 -> 8 internally; the kernel must not read
    garbage from the padded rows)."""
    rng = np.random.default_rng(12)
    q, k, v = _rand_qkv(rng, 2, 2, 1, 256, 64)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    assert out.shape == (2, 2, 1, 64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_decode_ragged_batch_via_key_bias():
    """A ragged decode batch: every row q_len=1 but each sequence has
    a different live context length, expressed as the additive key
    padding bias (the pre-paging serving idiom) — rows must match the
    per-sequence dense truth, dead keys contribute nothing."""
    rng = np.random.default_rng(13)
    B, H, Sk, D = 3, 2, 192, 64
    q, k, v = _rand_qkv(rng, B, H, 1, Sk, D)
    lens = [192, 7, 64]
    mask = np.zeros((B, Sk), np.float32)
    for b, n in enumerate(lens):
        mask[b, :n] = 1.0
    bias = jnp.asarray((mask - 1.0) * 1e4)
    out = np.asarray(flash_attention(q, k, v, key_bias=bias))
    for b, n in enumerate(lens):
        ref = reference_attention(q[b:b + 1], k[b:b + 1, :, :n],
                                  v[b:b + 1, :, :n])
        np.testing.assert_allclose(out[b], np.asarray(ref)[0],
                                   atol=2e-5, rtol=2e-5)


def test_decode_q_len_1_unaligned_context():
    """q_len=1 with a context that is not a multiple of the k block
    (the auto-pad path must mask the padded tail keys)."""
    rng = np.random.default_rng(14)
    q, k, v = _rand_qkv(rng, 1, 2, 1, 100, 64)
    out = flash_attention(q, k, v, block_k=64)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# -- PR 28: operands in their own dtype, blocks from the shapes -------------

def _loss_and_grads(fn, q, k, v, w, **kw):
    def loss(q, k, v):
        o = fn(q, k, v, **kw)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    return (o,) + tuple(grads)


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_bfloat16_forward_and_grads_against_float32_reference(
        with_bias, causal, grouped):
    """bfloat16 operands go to the products as they are (float32
    accumulation and statistics; P and dS rounded once): forward and all
    three gradients against the reference on the float32 upcast of the
    same inputs."""
    rng = np.random.default_rng(20)
    B, H, S, D = 2, 4, 256, 64
    q, k, v = (t.astype(jnp.bfloat16)
               for t in _rand_qkv(rng, B, H, S, S, D))
    if grouped:
        k, v = k[:, :2], v[:, :2]
    w = jnp.asarray(rng.standard_normal((B, H, S, D)).astype("float32"))
    bias = None
    if with_bias:
        mask = np.ones((B, S), np.float32)
        mask[0, 200:] = 0.0
        bias = jnp.asarray((mask - 1.0) * 1e4)
    got = _loss_and_grads(flash_attention, q, k, v, w, key_bias=bias,
                          causal=causal)
    want = _loss_and_grads(
        reference_attention, *(t.astype(jnp.float32) for t in (q, k, v)),
        w, key_bias=bias, causal=causal)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g, np.float32), r,
                                   atol=3e-2, rtol=3e-2,
                                   err_msg="%s mismatch" % name)


@pytest.fixture(scope="module")
def causal_in_blocks_of_16():
    rng = np.random.default_rng(21)
    q, k, v = _rand_qkv(rng, 1, 2, 512, 512, 64)
    k, v = k[:, :1], v[:, :1]       # grouped: two query heads on one
    w = jnp.asarray(rng.standard_normal((1, 2, 512, 64)).astype("float32"))
    return (q, k, v, w), _loss_and_grads(
        flash_attention, q, k, v, w, causal=True, block_q=16, block_k=16)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128),
                                             (512, 256), (None, None)])
def test_causal_blocks_of_several_sub_blocks(monkeypatch, block_q, block_k,
                                             causal_in_blocks_of_16):
    """block_q != block_k and blocks of two sub-blocks (the cap on a
    sub-block steered down to 128 rows): steps above the diagonal name
    the resident block and run nothing, the mask runs only where the
    diagonal crosses, and all of it gives what blocks of 16 give."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_SUB_ROWS", 128)
    if block_q is None:     # the rule's own choice, with smaller caps
        monkeypatch.setattr(fa, "_RESIDENT_ROWS", 128)
        monkeypatch.setattr(fa, "_STREAMED_BYTES", 256 * 128 * 4)
        blocks = fa.block_rule(512, 512, 64, "float32", True)
        assert (blocks.block_q, blocks.block_k, blocks.sub_k) == \
            (128, 256, 128)
        assert (blocks.block_k_dkv, blocks.block_q_dkv, blocks.sub_q) == \
            (128, 256, 128)
    (q, k, v, w), want = causal_in_blocks_of_16
    got = _loss_and_grads(flash_attention, q, k, v, w, causal=True,
                          block_q=block_q, block_k=block_k)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=3e-5, rtol=3e-5,
                                   err_msg="%s mismatch" % name)


def test_many_sub_blocks_are_walked_in_groups(monkeypatch):
    """More sub-blocks than are unrolled into one line of code: a loop
    over groups and then the rest (5 = 2 groups of 2 and 1), forward and
    both backward kernels, with the key bias and the mask drawn."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_SUB_ROWS", 128)
    monkeypatch.setattr(fa, "_UNROLL", 2)
    rng = np.random.default_rng(23)
    q, k, v = _rand_qkv(rng, 1, 2, 640, 640, 64)
    w = jnp.asarray(rng.standard_normal((1, 2, 640, 64)).astype("float32"))
    pad = np.ones((1, 640), np.float32)
    pad[0, 600:] = 0.0
    kw = dict(key_bias=jnp.asarray((pad - 1.0) * 1e4), dropout_p=0.1,
              dropout_seed=jnp.int32(5))
    got = _loss_and_grads(flash_attention, q, k, v, w, block_q=640,
                          block_k=640, **kw)
    want = _loss_and_grads(flash_attention, q, k, v, w, block_q=128,
                           block_k=128, **kw)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=3e-5, rtol=3e-5,
                                   err_msg="%s mismatch" % name)


@pytest.fixture(scope="module")
def dropout_in_one_block():
    rng = np.random.default_rng(22)
    q, k, v = _rand_qkv(rng, 1, 2, 256, 256, 64)
    w = jnp.asarray(rng.standard_normal((1, 2, 256, 64)).astype("float32"))
    return (q, k, v, w), _loss_and_grads(
        flash_attention, q, k, v, w, dropout_p=0.2,
        dropout_seed=jnp.int32(99))


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 32),
                                             (32, 256)])
def test_dropout_mask_is_one_in_all_three_kernels_at_any_blocks(
        block_q, block_k, dropout_in_one_block):
    """The forward, the dK/dV (transposed tile) and the dQ kernels
    regenerate one mask from (seed, coordinates), whatever the blocks."""
    (q, k, v, w), want = dropout_in_one_block
    got = _loss_and_grads(flash_attention, q, k, v, w, dropout_p=0.2,
                          dropout_seed=jnp.int32(99), block_q=block_q,
                          block_k=block_k)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=3e-5, rtol=3e-5,
                                   err_msg="%s mismatch" % name)


def test_dropout_mask_is_even_along_rows_columns_and_heads():
    """One xor and one product an element over a hashed row term and a
    hashed column term: the rate holds on every row, column and head,
    and neighbours along either axis are kept independently."""
    p = 0.3
    keep = _host_keep(4242, 4, 512, 512, p)
    for axis in ((1, 2), (0, 2), (0, 1)):       # per head, row, column
        rate = 1.0 - keep.mean(axis=axis)
        assert np.abs(rate - p).max() < 0.06, (axis, rate.min(), rate.max())
    drop = (~keep).astype(np.float64) - p
    for a, b in ((drop[:, :-1], drop[:, 1:]),          # next row
                 (drop[:, :, :-1], drop[:, :, 1:]),    # next column
                 (drop[:-1], drop[1:])):               # next head
        assert abs((a * b).mean()) / (p * (1 - p)) < 0.01
    # four corners of a rectangle: what a bare xor of two terms would tie
    corners = (keep[:, :-1, :-1] ^ keep[:, 1:, :-1] ^ keep[:, :-1, 1:]
               ^ keep[:, 1:, 1:])
    q = 1.0 - p
    odd = 4 * q * p ** 3 + 4 * p * q ** 3       # independent corners
    assert abs(corners.mean() - odd) < 0.01


_CELLS = [
    # Sq, Sk, D, dtype, causal, dropout
    (4096, 4096, 64, "bfloat16", False, True),     # bert-base-s4096
    (8192, 8192, 128, "bfloat16", True, False),    # nemotron3-nano-...-s8192
    (100, 100, 64, "float32", False, False),
    (1, 256, 64, "float32", False, False),
    (4100, 4100, 64, "bfloat16", True, True),
    (600, 1000, 128, "float32", True, False),
    (2048, 2048, 256, "float32", False, True),
]


@pytest.mark.parametrize("sq,sk,d,dtype,causal,dropout", _CELLS)
def test_block_rule_is_a_pure_function_of_shapes_and_dtype(
        monkeypatch, sq, sk, d, dtype, causal, dropout):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    first = fa.block_rule(sq, sk, d, dtype, causal, dropout)
    nq, nk = fa._padded(sq), fa._padded(sk)
    assert nq >= sq and nk >= sk and nq - sq < 128 and nk - sk < 128
    for block, n in ((first.block_q, nq), (first.block_q_dkv, nq),
                     (first.block_k, nk), (first.block_k_dkv, nk),
                     (first.sub_k, first.block_k),
                     (first.sub_q, first.block_q_dkv)):
        assert block > 0 and block % 8 == 0 and n % block == 0
    assert 0 < first.vmem_bytes <= fa._VMEM_BUDGET
    # nothing but its arguments: no backend, device, flag or environment
    def refuse(*a, **kw):
        raise AssertionError("the block rule looked outside its arguments")

    import os

    from paddle_tpu.utils import flags
    for owner, name in ((jax, "default_backend"), (jax, "devices"),
                        (flags, "get_flags"), (flags, "get_flag"),
                        (os, "getenv")):
        monkeypatch.setattr(owner, name, refuse)
    assert fa.block_rule(sq, sk, d, dtype, causal, dropout) == first


def test_block_rule_on_the_two_cells():
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    s4096 = fa.block_rule(*_CELLS[0])
    nemotron = fa.block_rule(*_CELLS[1])
    # the grid of a forward call: 96 x 32 x 32 and 64 x 64 x 64 before
    assert 96 * (4096 // s4096.block_q) * (4096 // s4096.block_k) <= 6144
    assert 64 * (8192 // nemotron.block_q) * (8192 // nemotron.block_k) \
        <= 16384
    for blocks in (s4096, nemotron):
        assert min(blocks[:6]) >= 256 and blocks.sub_k % 128 == 0


# -- PR 33: V has a head size of its own -------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h,hkv,d,dv", [(16, 16, 192, 128), (4, 2, 48, 80)],
                         ids=["latent_192_on_128", "grouped_48_on_80"])
def test_values_of_their_own_width_forward_and_grads(h, hkv, d, dv, dtype):
    """Queries and keys of `d` on values of `dv`, causal: the output
    and dV take V's width, dQ and dK the key's, forward and all three
    gradients against the reference on the float32 upcast of the same
    inputs (latent attention's 16 heads of 128 + 64 on 128; a narrower
    key on a wider value with grouped heads)."""
    rng = np.random.default_rng(33)
    B, S = 1, 200
    q = jnp.asarray(rng.standard_normal((B, h, S, d)), dtype)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, d)), dtype)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, dv)), dtype)
    w = jnp.asarray(rng.standard_normal((B, h, S, dv)).astype("float32"))
    got = _loss_and_grads(flash_attention, q, k, v, w, causal=True)
    want = _loss_and_grads(
        reference_attention, *(t.astype(jnp.float32) for t in (q, k, v)),
        w, causal=True)
    assert [g.shape for g in got] == [(B, h, S, dv), q.shape, k.shape,
                                      v.shape]
    assert all(g.dtype == q.dtype for g in got)
    tol = 3e-2 if dtype == "bfloat16" else 5e-5
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g, np.float32), r, atol=tol,
                                   rtol=tol, err_msg="%s mismatch" % name)


def test_the_scale_is_the_querys_and_shapes_that_do_not_fit_raise():
    rng = np.random.default_rng(34)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 40)), jnp.float32)
    np.testing.assert_allclose(
        flash_attention(q, k, v),
        reference_attention(q, k, v, sm_scale=24 ** -0.5), atol=2e-5)
    with pytest.raises(ValueError, match="keys are as wide as queries"):
        flash_attention(q, v, v)
    with pytest.raises(ValueError, match="values lie on the keys'"):
        flash_attention(q, k, v[:, :, :32])


# what `block_rule` gave before values had a width of their own (the
# parent's, recorded): `_CELLS` and the qwen3-next cell's D = 256 at 16k
_RULE_BEFORE = [
    (512, 4096, 512, 4096, 512, 512, 15990784),
    (512, 8192, 512, 8192, 512, 512, 20971520),
    (104, 104, 104, 104, 104, 104, 1451008),
    (8, 256, 256, 8, 256, 8, 2426368),
    (384, 4224, 384, 4224, 384, 384, 12607488),
    (128, 1024, 512, 640, 512, 128, 7987200),
    (512, 2048, 512, 2048, 512, 512, 23461888),
    (512, 4096, 512, 4096, 512, 512, 21757952),
]


@pytest.mark.parametrize("cell,before", list(zip(
    _CELLS + [(16384, 16384, 256, "bfloat16", True, False)], _RULE_BEFORE)))
def test_equal_widths_are_stepped_through_as_before(cell, before):
    """A call whose values are as wide as its keys gets the blocks and
    the VMEM figure it got before, whether `dv` is left out or given."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert tuple(fa.block_rule(*cell)) == before
    assert tuple(fa.block_rule(*cell, dv=cell[2])) == before


def test_block_rule_on_the_latent_attention_cell():
    """16,384 positions, keys of 192 (a lane row and a half: 256 in
    VMEM) on values of 128: tiles of 512 x 512, 4,096 keys a streamed
    block by the wider of the two (D = 128 streams 8,192), and a
    figure under that of values widened to 192."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    args = (16384, 16384, 192, "bfloat16", True, False)
    latent = fa.block_rule(*args, dv=128)
    assert tuple(latent) == (512, 4096, 512, 4096, 512, 512, 18874368)
    assert fa.block_rule(16384, 16384, 128, "bfloat16", True,
                         False).block_k == 8192
    assert latent.vmem_bytes < fa.block_rule(*args).vmem_bytes \
        <= fa._VMEM_BUDGET


def test_sdpa_op_gives_values_their_width_on_every_path():
    """The op's three paths on the CPU (`reference_attention`; the
    unfused path under a mask; under dropout): the output takes V's
    last axis, the scale the query's."""
    from paddle_tpu.ops.registry import run_op

    rng = np.random.default_rng(35)
    q = jnp.asarray(rng.standard_normal((2, 4, 24, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 24, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 24, 12)), jnp.float32)
    want = reference_attention(q, k, v, causal=True)
    assert want.shape == (2, 4, 24, 12)
    ins = {"Q": [q], "K": [k], "V": [v]}
    attrs = {"causal": True, "sm_scale": -1.0, "is_test": True}
    def sdpa(ins, attrs):
        return run_op("scaled_dot_product_attention", ins, attrs)["Out"][0]

    np.testing.assert_allclose(sdpa(ins, attrs), want, atol=1e-6)
    mask = jnp.zeros((24, 24), jnp.float32)
    np.testing.assert_allclose(sdpa(dict(ins, Mask=[mask]), attrs), want,
                               atol=1e-5)
    dropped = sdpa(ins, dict(attrs, is_test=False, attn_dropout_prob=0.5,
                             _rng_key=jax.random.PRNGKey(0)))
    assert dropped.shape == want.shape


# -- PR 36: a checkpoint keeps the output and the row statistics -------------

def _kernel_calls(jaxpr):
    """{kernel's name: `pallas_call` equations of that name in `jaxpr`
    and every jaxpr below it}."""
    import collections

    from decoder_case import walk

    return dict(collections.Counter(
        eqn.params["name"] for _, eqn in walk(jaxpr)
        if eqn.primitive.name == "pallas_call"))


def _checkpointed_grads(kept, q, k, v, w, **kw):
    """(the jaxpr of value and gradients, their values) of one
    attention layer under the lowering's checkpoint, its body traced
    inside `collecting(kept)`: a list as in a `remat` scan or a
    recompute segment, None as anywhere else."""
    from paddle_tpu.ops import remat_names

    def loss(q, k, v):
        # a new function a trace, as the lowering's bodies are
        body = jax.checkpoint(
            lambda q, k, v: flash_attention(q * 1.5, k, v, **kw),
            policy=jax.checkpoint_policies.save_only_these_names(
                *remat_names.KEPT))
        with remat_names.collecting(kept):
            return jnp.sum(body(q, k, v).astype(jnp.float32) * w)

    f = jax.value_and_grad(loss, argnums=(0, 1, 2))
    return jax.make_jaxpr(f)(q, k, v), f(q, k, v)


@pytest.mark.parametrize("h,hkv,d,dv,dtype,kw", [
    (2, 2, 32, 32, "float32", dict(causal=True)),
    (4, 2, 32, 32, "bfloat16", dict(causal=True)),
    (2, 2, 48, 24, "float32", dict(causal=True)),
    (2, 2, 32, 32, "float32", dict(dropout_p=0.25,
                                   dropout_seed=jnp.int32(77))),
], ids=["equal_widths", "grouped_query", "values_of_their_own_width",
        "dropout_in_the_kernel"])
def test_a_checkpoint_keeps_the_output_and_the_row_statistics(
        h, hkv, d, dv, dtype, kw):
    """Traced in a checkpointed body the call's output and compact
    logsumexp are the checkpoint's to keep: the gradient's program
    holds the forward kernel once where the parent's (nothing named)
    holds it twice, the gradients are equal to the bit, and the body's
    list says what is kept."""
    import importlib

    from paddle_tpu.ops import remat_names
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    rng = np.random.default_rng(36)
    B, S = 2, 72
    q = jnp.asarray(rng.standard_normal((B, h, S, d)), dtype)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, d)), dtype)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, dv)), dtype)
    w = jnp.asarray(rng.standard_normal((B, h, S, dv)).astype("float32"))
    fwd, dkv, dq = fa.KERNEL_NAMES

    plain_jaxpr, (plain_loss, plain) = _checkpointed_grads(
        None, q, k, v, w, **kw)
    assert _kernel_calls(plain_jaxpr.jaxpr) == {fwd: 2, dkv: 1, dq: 1}
    kept = []
    kept_jaxpr, (kept_loss, got) = _checkpointed_grads(
        kept, q, k, v, w, **kw)
    assert _kernel_calls(kept_jaxpr.jaxpr) == {fwd: 1, dkv: 1, dq: 1}
    assert "name=%s" % remat_names.FLASH_RESIDUAL in str(kept_jaxpr)
    assert remat_names.FLASH_RESIDUAL not in str(plain_jaxpr)

    assert float(kept_loss) == float(plain_loss)
    for a, b, name in zip(got, plain, ("dq", "dk", "dv")):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), name
    # S = 72 is one block of 72 rows: the output at its own dtype and S
    # floats a head; the body was traced twice (the jaxpr, the values)
    rows = [(remat_names.FLASH_RESIDUAL, (B * h, S, dv), jnp.dtype(dtype)),
            (remat_names.FLASH_RESIDUAL, (B * h, S), jnp.dtype("float32"))]
    assert kept == rows + rows
    assert sum(int(np.prod(shape)) * np.dtype(dt).itemsize
               for _, shape, dt in rows) == \
        B * h * S * (dv * jnp.dtype(dtype).itemsize + 4)


def test_the_same_functions_traced_outside_and_inside_are_two_programs():
    """Whether the two values are named is decided where
    `flash_attention` is called and carried in the `custom_vjp`'s
    static argument: a forward rule that read the context itself would
    be in no cache key, and the same function objects traced first
    outside a checkpointed body would hand that program back inside
    one."""
    import importlib

    from paddle_tpu.ops import remat_names
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    rng = np.random.default_rng(37)
    q, k, v = _rand_qkv(rng, 1, 2, 64, 64, 16)
    policy = jax.checkpoint_policies.save_only_these_names(
        *remat_names.KEPT)

    def attend(q, k, v):      # one function object for every trace below
        return fa._flash_core(q, k, v, None, None, attend.spec)

    def program(kept):
        def loss(q, k, v):
            with remat_names.collecting(kept):
                attend.spec = base._replace(kept=remat_names.note(
                    remat_names.FLASH_RESIDUAL, (2, 64, 16), q.dtype))
                return jnp.sum(jax.checkpoint(
                    lambda *a: attend(*a), policy=policy)(q, k, v))
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q.reshape(2, 64, 16), k.reshape(2, 64, 16),
            v.reshape(2, 64, 16))

    base = fa._Spec(0.25, False, 0.0, 1, 0,
                    fa.block_rule(64, 64, 16, "float32"))
    assert base.kept is False
    outside, inside, outside_again = program(None), program([]), program(None)
    fwd = fa.KERNEL_NAMES[0]
    assert _kernel_calls(outside.jaxpr)[fwd] == 2
    assert _kernel_calls(inside.jaxpr)[fwd] == 1
    assert _kernel_calls(outside_again.jaxpr)[fwd] == 2
    assert str(outside) == str(outside_again) != str(inside)


# -- PR 37: a sliding window --------------------------------------------------

def _window_grads(fn, q, k, v, w, window, **kw):
    return _loss_and_grads(fn, q, k, v, w, causal=True, window=window, **kw)


@pytest.mark.parametrize("window,block_q,block_k,h,hkv,s", [
    (8, 16, 16, 2, 1, 64),        # under a block, two query heads on one
    (16, 16, 16, 2, 2, 64),       # a block's length
    (24, 16, 16, 2, 1, 64),       # over it: three key blocks a query block
    (5, 32, 16, 4, 2, 72),        # unequal blocks, padded length
    (20, 16, 32, 4, 2, 72),
    (40, 64, 64, 2, 1, 200),      # one block of keys sees two of queries
    (300, None, None, 2, 1, 1024),    # the rule's own blocks
    (512, None, None, 4, 2, 1280),
], ids=["w8_b16", "w16_b16", "w24_b16", "w5_b32x16_pad", "w20_b16x32_pad",
        "w40_b64", "w300_rule", "w512_rule_grouped"])
def test_window_forward_and_grads_match_the_reference(window, block_q,
                                                      block_k, h, hkv, s):
    """Causal within a window: the forward kernel and both backward
    kernels visit only the key (or query) blocks that meet the window,
    every step past the last live one runs nothing, and output and all
    three gradients are the masked reference's."""
    rng = np.random.default_rng(37)
    q = jnp.asarray(rng.standard_normal((1, h, s, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, hkv, s, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hkv, s, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, h, s, 16)).astype("float32"))
    got = _window_grads(flash_attention, q, k, v, w, window,
                        block_q=block_q, block_k=block_k)
    want = _window_grads(reference_attention, q, k, v, w, window)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5,
                                   err_msg="%s mismatch" % name)
    # a window as long as the sequence is plain causal attention
    if block_q is None:
        np.testing.assert_allclose(
            flash_attention(q, k, v, causal=True, window=s),
            reference_attention(q, k, v, causal=True), atol=2e-5)


def test_window_in_bfloat16_and_sub_blocks_of_a_longer_block(monkeypatch):
    """Blocks of several sub-blocks (the streamed block given longer
    than the window): every live sub-block takes the mask, those
    outside the window are skipped; bfloat16 operands against the
    float32 reference."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_SUB_ROWS", 128)
    rng = np.random.default_rng(38)
    q = jnp.asarray(rng.standard_normal((1, 4, 768, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 768, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 768, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((1, 4, 768, 32)).astype("float32"))
    got = _window_grads(flash_attention, q, k, v, w, 200, block_q=256,
                        block_k=384)
    want = _window_grads(reference_attention,
                         *(t.astype(jnp.float32) for t in (q, k, v)), w, 200)
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g, np.float32), r, atol=3e-2,
                                   rtol=3e-2, err_msg="%s mismatch" % name)


def _grids(q, k, v, **kw):
    """{kernel's name: its grid} of one forward and backward call."""
    from decoder_case import walk

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
            for _, e in walk(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("cell,before", list(zip(
    _CELLS + [(16384, 16384, 256, "bfloat16", True, False)], _RULE_BEFORE)))
def test_no_window_leaves_every_block_plan_and_grid_as_it_was(cell, before):
    """`window=None` (and 0) gives every shape the parent's blocks and
    VMEM figure, and the three kernels the parent's grids: the key (or
    query) axis over all blocks of the padded length."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    sq, sk, d, dtype, causal, dropout = cell
    assert tuple(fa.block_rule(*cell, window=None)) == before
    assert tuple(fa.block_rule(*cell, window=0)) == before
    assert fa._blocks_for(sq, sk, d, d, dtype, causal, dropout, None,
                          None, None)[0] == fa.block_rule(*cell)
    if sq * sk > 4096 * 4096 or dropout:
        return      # the grids below are traced at the smaller shapes
    q = jax.ShapeDtypeStruct((1, 2, sq, d), dtype)
    kv = jax.ShapeDtypeStruct((1, 2, sk, d), dtype)
    b = fa.block_rule(*cell)
    nq, nk = fa._padded(sq), fa._padded(sk)
    fwd, dkv, dq = fa.KERNEL_NAMES
    assert _grids(q, kv, kv, causal=causal) == {
        fwd: (2, nq // b.block_q, nk // b.block_k),
        dkv: (2, nk // b.block_k_dkv, nq // b.block_q_dkv),
        dq: (2, nq // b.block_q, nk // b.block_k)}


def test_the_window_plan_at_the_laguna_cell():
    """16,384 positions, heads of 128 in bfloat16, a window of 512:
    tiles of 512 x 512, the streamed block no longer than the resident
    one, and each kernel's innermost axis two blocks long (a block of
    queries meets the block before it and its own) where causal
    attention walks all 32; the kernels carry names of their own."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    b = fa.block_rule(16384, 16384, 128, "bfloat16", True, False,
                      window=512)
    assert b[:6] == (512, 512, 512, 512, 512, 512)
    assert b.vmem_bytes <= fa._VMEM_BUDGET
    spec = fa._Spec(128 ** -0.5, True, 0.0, 9, 0, b, window=512)
    assert fa._span(spec, 16384, 16384) == (2, 2)
    assert fa._span(spec._replace(window=0), 16384, 16384) == (32, 32)
    q = jax.ShapeDtypeStruct((1, 36, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    fwd, dkv, dq = fa.WINDOW_KERNEL_NAMES
    assert _grids(q, kv, kv, causal=True, window=512) == {
        fwd: (36, 32, 2), dkv: (36, 32, 2), dq: (36, 32, 2)}
    # a query block's first row sees 512 keys back from 513 on: the
    # block before the one before it
    assert fa._span(spec._replace(window=513), 16384, 16384) == (2, 2)
    assert fa._span(spec._replace(window=514), 16384, 16384) == (3, 3)
    with pytest.raises(ValueError, match="causal self-attention"):
        flash_attention(jnp.zeros((1, 1, 8, 8)), jnp.zeros((1, 1, 8, 8)),
                        jnp.zeros((1, 1, 8, 8)), window=4)


def test_sdpa_op_takes_the_window_on_every_path(monkeypatch):
    """The op's `window` on the flash path (interpreted), on
    `reference_attention` and on the unfused path with dropout off:
    the same masked attention; the program gets the attribute only
    where a window is given."""
    import importlib
    from paddle_tpu.fluid import framework, layers
    from paddle_tpu.ops.registry import run_op
    from paddle_tpu.utils import flags
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    rng = np.random.default_rng(39)
    q, k, v = _rand_qkv(rng, 1, 2, 48, 48, 16)
    want = reference_attention(q, k, v, causal=True, window=10)
    attrs = {"causal": True, "window": 10, "is_test": True}
    ins = {"Q": [q], "K": [k], "V": [v]}

    def sdpa(ins):
        return run_op("scaled_dot_product_attention", ins, attrs)["Out"][0]

    np.testing.assert_allclose(sdpa(ins), want, atol=2e-5)
    masked = sdpa(dict(ins, Mask=[jnp.zeros((48, 48))]))
    np.testing.assert_allclose(masked, want, atol=2e-5)
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(flags._FLAGS, "FLAGS_flash_attention_min_seq", 48)
    np.testing.assert_allclose(sdpa(ins), want, atol=2e-5)
    main = framework.Program()
    with framework.program_guard(main, framework.Program()):
        x = layers.data(name="x", shape=[2, 48, 16], dtype="float32")
        layers.scaled_dot_product_attention(x, x, x, causal=True)
        layers.scaled_dot_product_attention(x, x, x, causal=True, window=8)
        with pytest.raises(ValueError, match="needs causal"):
            layers.scaled_dot_product_attention(x, x, x, window=8)
    ops = [op for op in main.global_block().ops
           if op.type == "scaled_dot_product_attention"]
    assert not ops[0].has_attr("window")
    assert ops[1].attr("window") == 8
