"""The Pallas kernels of the main paths, compiled by the TPU's own
compiler (Mosaic through XLA:TPU) for a DESCRIBED v5e chip — nothing
executes, no chip is needed. Interpret-mode tests cannot see what this
sees: block shapes Mosaic refuses, VMEM overuse, layouts that do not
lower.

Only one process may load the TPU library, and it keeps it until it
exits: the topology is described inside a module-scoped fixture (never
at import, in a skipif or in parametrize), every compile happens in
this process, and all of these tests live in this ONE file.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports functions under the modules' own names
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
rpa = importlib.import_module(
    "paddle_tpu.ops.pallas.ragged_paged_attention")


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels' backend probe to its TPU side: the process
    runs on the CPU, the compile is for the chip."""
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(rpa, "_interpret_default", lambda: False)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# BERT long-context shapes: B2 H12 S4096 D64 in bf16
_QKV = ((2, 12, 4096, 64), jnp.bfloat16)


def test_flash_forward(one_chip, mosaic):
    _compile(lambda q, k, v: fa.flash_attention(q, k, v),
             one_chip, _QKV, _QKV, _QKV)


def test_flash_forward_backward_dropout_key_bias(one_chip, mosaic):
    def loss(q, k, v, bias, seed):
        o = fa.flash_attention(q, k, v, key_bias=bias, dropout_p=0.1,
                               dropout_seed=seed)
        return jnp.sum(o.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, _QKV, _QKV,
             _QKV, ((2, 4096), jnp.float32), ((1,), jnp.int32))


def test_flash_causal(one_chip, mosaic):
    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, _QKV, _QKV,
             _QKV)


def test_flash_at_16k_walks_its_sub_blocks_in_groups(one_chip, mosaic):
    """Past 4,096 keys the streamed block holds more sub-blocks than are
    unrolled into one line of code (8,192 rows of 64: two groups of
    eight): the loop over groups slices K, V, Q, dO and the key bias at
    offsets known only at run time."""
    blocks = fa.block_rule(16384, 16384, 64, jnp.bfloat16, False, True)
    assert blocks.block_k // blocks.sub_k > fa._UNROLL
    assert blocks.block_q_dkv // blocks.sub_q > fa._UNROLL

    def loss(q, k, v, bias, seed):
        o = fa.flash_attention(q, k, v, key_bias=bias, dropout_p=0.1,
                               dropout_seed=seed)
        return jnp.sum(o.astype(jnp.float32))

    qkv = ((1, 2, 16384, 64), jnp.bfloat16)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv,
             ((1, 16384), jnp.float32), ((1,), jnp.int32))


def _kernels_are_called(compiled, names):
    """What a trace will call each Mosaic kernel of the program (the
    custom calls' instruction names: the kernel's `name`, wrapped in
    the transformations it was traced under, as `jvp_<name>_`): every
    kernel carries one of `names`, and each of `names` is there."""
    called = {line.strip().split(" = ")[0]
              for line in compiled.as_text().splitlines()
              if "tpu_custom_call" in line}
    assert called and all(any(n in c for n in names) for c in called), called
    assert all(any(n in c for c in called) for n in names), called


def test_flash_grouped_query_at_8k_and_what_a_trace_calls_it(one_chip,
                                                             mosaic):
    """The hybrid decoder's attention layer: 32 query heads on 2
    key/value heads of 128 at 8,192, causal, K and V in place. The
    kernels' names are what the benchmark's `flash_attn_*` metrics
    match."""
    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    q = ((2, 32, 8192, 128), jnp.bfloat16)
    kv = ((2, 2, 8192, 128), jnp.bfloat16)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q,
                        kv, kv)
    _kernels_are_called(compiled, fa.KERNEL_NAMES)


def test_ssd_chunk_scan_at_the_published_widths(one_chip, monkeypatch):
    """Mamba-2's scan as Nemotron-3-Nano runs it: 64 heads of 64, 8
    groups of state 128, chunks of 128, two sequences of 8,192. The
    forward is the Pallas kernel, named for the benchmark's
    `ssd_scan_*` metrics; the backward is `jax.numpy`."""
    ssd = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    monkeypatch.setattr(ssd, "_interpret_default", lambda: False)

    def loss(x, dt, dt_bias, a_log, b, c, d):
        y = hybrid.ssd_chunk_scan(x, dt, dt_bias, a_log, b, c, d, 128,
                                  kernel=True)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    bf, f32 = jnp.bfloat16, jnp.float32
    compiled = _compile(
        jax.grad(loss, argnums=tuple(range(7))), one_chip,
        ((2, 8192, 64, 64), bf), ((2, 8192, 64), bf), ((64,), f32),
        ((64,), f32), ((2, 8192, 8, 128), bf), ((2, 8192, 8, 128), bf),
        ((64,), f32))
    _kernels_are_called(compiled, ["ssd_chunk_scan_fwd"])


def test_held_experts_grouped_products_at_the_published_widths(
        one_chip, monkeypatch):
    """8 held experts of 2688 x 1856 over 16,384 tokens routed top-6:
    the grouped products compile for the chip (1856 is no multiple of
    128: the kernel masks the remainder) inside the loops over row
    blocks, whose trip counts come from the routing, under the names
    the benchmark's `moe_experts_*` metrics match."""
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, idx, w, w_up, w_down):
        out = hybrid.moe_experts(x, idx, w, w_up, w_down)[0]
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    bf = jnp.bfloat16
    compiled = _compile(
        jax.grad(loss, argnums=(0, 2, 3, 4)), one_chip,
        ((16384, 2688), bf), ((16384, 6), jnp.int32),
        ((16384, 6), jnp.float32), ((8, 2688, 1856), bf),
        ((8, 1856, 2688), bf))
    _kernels_are_called(compiled, ["moe_experts_gmm", "moe_experts_tgmm"])
    # two products in the forward's loop, five in the backward's, each
    # under the part the op's code names round it
    in_loop = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line
               and "/while/body/pt[products]/moe_experts_" in line]
    assert len(in_loop) == 7
    assert sum("transpose(jvp())" in line for line in in_loop) == 5


def test_flash_at_a_head_of_256_and_what_a_trace_calls_it(one_chip, mosaic):
    """A gated attention layer's call: 16 query heads on 2 key/value
    heads of 256 at 16,384, causal. `block_rule` is a function of the
    head's width and had run at 64 and 128 only: at 256 the streamed
    block halves (4,096 keys) and the figure stays under the budget."""
    blocks = fa.block_rule(16384, 16384, 256, jnp.bfloat16, True, False)
    assert (blocks.block_q, blocks.block_k, blocks.sub_k) == (512, 4096, 512)
    assert blocks.vmem_bytes <= fa._VMEM_BUDGET

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, sm_scale=1 / 16.0)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    q = ((1, 16, 16384, 256), jnp.bfloat16)
    kv = ((1, 2, 16384, 256), jnp.bfloat16)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q,
                        kv, kv)
    _kernels_are_called(compiled, fa.KERNEL_NAMES)


def test_flash_at_keys_of_192_on_values_of_128(one_chip, mosaic):
    """A latent-attention layer's call (PR 33): 16 heads whose queries
    and keys are 128 + 64 wide on values of 128 at 16,384, causal. A
    key's row is a lane tile and a half; Mosaic takes the blocks as
    they are (no padding in HBM: the arguments are q, k and v at their
    own sizes), the forward and dQ kernels accumulate 128 and 192 wide,
    dK/dV both."""
    blocks = fa.block_rule(16384, 16384, 192, jnp.bfloat16, True, False,
                           dv=128)
    assert (blocks.block_q, blocks.block_k, blocks.sub_k) == (512, 4096, 512)
    assert blocks.vmem_bytes <= fa._VMEM_BUDGET

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    qk = ((1, 16, 16384, 192), jnp.bfloat16)
    v = ((1, 16, 16384, 128), jnp.bfloat16)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qk,
                        qk, v)
    _kernels_are_called(compiled, fa.KERNEL_NAMES)
    assert compiled.memory_analysis().argument_size_in_bytes == \
        2 * 16 * 16384 * (192 + 192 + 128)


def test_gated_experts_grouped_products_at_the_published_widths(
        one_chip, monkeypatch):
    """32 held experts of 2048 x (2 x 512) and 512 x 2048 over 16,384
    tokens routed top-10 of 512, `swiglu` between the two grouped
    products: the same loops over row blocks, the up product twice as
    wide as the down product's rows."""
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, idx, w, w_up, w_down):
        out = hybrid.moe_experts(x, idx, w, w_up, w_down,
                                 activation="swiglu", num_experts=512)[0]
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    bf = jnp.bfloat16
    compiled = _compile(
        jax.grad(loss, argnums=(0, 2, 3, 4)), one_chip,
        ((16384, 2048), bf), ((16384, 10), jnp.int32),
        ((16384, 10), jnp.float32), ((32, 2048, 1024), bf),
        ((32, 512, 2048), bf))
    _kernels_are_called(compiled, ["moe_experts_gmm", "moe_experts_tgmm"])
    assert hybrid.row_block(163840, 32, 512) == 13824


@pytest.mark.parametrize("walked_by", ["jax.numpy", "pallas"])
def test_gated_delta_rule_at_the_published_widths(one_chip, monkeypatch,
                                                  walked_by):
    """A Gated DeltaNet mixer's rule as the long-document cell runs it:
    16 key heads and 32 value heads of 128, one document of 16,384: 256
    chunks walked in 4 groups of key heads. Off the TPU the two walks
    over chunks are `lax.scan`s, loops of the compiled program inside
    the two loops over head groups; on it (heads of whole lane tiles)
    they are the two kernels of `pallas/gated_delta_rule.py`, compiled
    here by Mosaic, 8 value heads a grid step, and only the loops over
    head groups are left; there the triangular systems' inverse is that
    file's third kernel, 64 systems a grid step, in the forward pass
    and again in the backward pass. Either way what the custom gradient
    keeps (the five inputs and 8,192 chunk states) with the group's
    temporaries fits well inside what the step has left, and the
    inverse kernel needs no more of it than the `jax.numpy` series does
    (temporaries 2,368,144,384 bytes against 2,368,370,176)."""
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    kernels = importlib.import_module(
        "paddle_tpu.ops.pallas.gated_delta_rule")
    assert hybrid._gdr_groups(1, 256, 16, 2) == 4
    assert kernels.heads_a_step(4, 2, 64, 128, 128, 2) == 8
    assert kernels.systems_a_step(2048, 64) == 64
    if walked_by == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kernels, "_interpret_default", lambda: False)

    def loss(q, k, v, g, beta):
        out = hybrid.gated_delta_rule(q, k, v, -jax.nn.softplus(g),
                                      jax.nn.sigmoid(beta))
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    bf, f32 = jnp.bfloat16, jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((1, 16384, 16, 128), bf), ((1, 16384, 16, 128), bf),
        ((1, 16384, 32, 128), bf), ((1, 16384, 32), f32),
        ((1, 16384, 32), f32))]

    def compile_grad():
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()

    compiled = compile_grad()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if walked_by == "pallas":
        _kernels_are_called(compiled, ["gated_delta_rule_fwd",
                                       "gated_delta_rule_bwd",
                                       "gated_delta_rule_inverse"])
        assert text.count(" while(") == 2
        # the walks' kernels beside the `jax.numpy` series
        monkeypatch.setattr(kernels, "unit_lower_inverse",
                            lambda a, block: hybrid._unit_lower_inverse(a))
        series = compile_grad()
        assert "gated_delta_rule_inverse" not in series.as_text()
        assert temp <= series.memory_analysis().temp_size_in_bytes
    else:
        assert "tpu_custom_call" not in text and text.count(" while(") >= 4
    assert temp < 3 << 30


def _kda_rule_args(one_chip):
    """The kimi-linear cell's KDA rule: 32 heads of 128 (a key head a
    value head), g a key channel, one document of 8,192"""
    bf, f32 = jnp.bfloat16, jnp.float32
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((1, 8192, 32, 128), bf), ((1, 8192, 32, 128), bf),
        ((1, 8192, 32, 128), bf), ((1, 8192, 32, 128), f32),
        ((1, 8192, 32), f32))]


def _rule_grad():
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")

    def loss(q, k, v, g, beta):
        out = hybrid.gated_delta_rule(q, k, v, -jax.nn.softplus(g),
                                      jax.nn.sigmoid(beta))
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))


def _on_the_kernels(monkeypatch):
    kernels = importlib.import_module(
        "paddle_tpu.ops.pallas.gated_delta_rule")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret_default", lambda: False)
    return kernels


def _lowered_without_locations(fn, args):
    """`fn` lowered with no source locations in the text (a kernel's
    serialised body carries its call's, which move with any edit of
    the files it was called from)"""
    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return fn.lower(*args).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)


def _kernel_bodies(text):
    """[(kernel name, bytes of its serialised Mosaic body)] of each
    custom call in a lowered text"""
    return [(re.search(r'kernel_name = "([^"]+)"', line).group(1),
             len(re.search(r'body\\22: \\22([^\\]+)', line).group(1)))
            for line in text.splitlines() if "tpu_custom_call" in line]


@pytest.mark.parametrize("walked_by", ["jax.numpy", "pallas"])
def test_a_decay_per_channel_at_the_published_widths(one_chip, monkeypatch,
                                                     walked_by):
    """Kimi Delta Attention's rule as the kimi-linear cell runs it: 32
    heads of 128 (a key head a value head), g a key channel, one
    document of 8,192: 128 chunks walked in 2 groups of heads. On the
    TPU the walks are the two kernels with their per-channel blocks (a
    head's log-decays [64, 128] float32, the decayed q k^T handed in,
    the state's rows decayed by a [128] column), the inverse the third
    kernel, and the anchored products with the diagonal blocks'
    per-channel sums the decay's pair, `gated_delta_rule_decay` and its
    reverse kernel, in the forward pass and again in the backward
    pass's remade local values. The pair keeps the diagonal blocks'
    float32 exponentials and their cotangents out of HBM: the
    gradient's temporaries are 1,926,612,992 bytes with the kernels
    (2,538,583,552 while the products were the compiled program's),
    2,606,063,104 without."""
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    kernels = importlib.import_module(
        "paddle_tpu.ops.pallas.gated_delta_rule")
    assert hybrid._gdr_groups(1, 128, 32, 1) == 2
    assert kernels.heads_a_step(16, 1, 64, 128, 128, 2) == 8
    if walked_by == "pallas":
        _on_the_kernels(monkeypatch)
    compiled = _rule_grad().lower(*_kda_rule_args(one_chip)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if walked_by == "pallas":
        _kernels_are_called(compiled, [
            "gated_delta_rule_fwd", "gated_delta_rule_bwd",
            "gated_delta_rule_inverse", "gated_delta_rule_decay",
            "gated_delta_rule_decay_bwd"])
        assert temp < 2.0e9
    else:
        assert "tpu_custom_call" not in compiled.as_text()
        assert temp < 2.7e9


#: bytes of the decay kernels' serialised Mosaic bodies at the cell's
#: widths (their loops over heads and rows are loops, not unrolled
#: code: a body that unrolled them read 85,612 and 112,896)
_DECAY_BODY_BYTES = {"gated_delta_rule_decay": 12756,
                     "gated_delta_rule_decay_bwd": 10992}


def test_the_decay_kernels_lower_to_loops_and_not_unrolled_code(
        one_chip, monkeypatch):
    """Each site of a `pallas_call` is traced and lowered on every start
    of a process, the persistent compile cache's hits included: the
    kimi-linear cell's step holds the decay's pair at 16 sites. Lowered
    for the chip at the cell's widths, each of their bodies stays under
    twice the bytes of its looped form, so that an edit that unrolls
    a loop fails here and not in the cell's set-up time."""
    _on_the_kernels(monkeypatch)
    bodies = _kernel_bodies(_lowered_without_locations(
        _rule_grad(), _kda_rule_args(one_chip)))
    sizes = {name: [n for k, n in bodies if k == name]
             for name in _DECAY_BODY_BYTES}
    # the forward kernel in the forward pass and in the backward pass's
    # remade local values, the reverse one in the backward pass
    assert [len(sizes[k]) for k in _DECAY_BODY_BYTES] == [2, 1]
    for name, most in _DECAY_BODY_BYTES.items():
        assert max(sizes[name]) < 2 * most, (name, sizes[name])


def test_the_scalar_rule_lowers_to_the_programs_it_did(one_chip,
                                                       monkeypatch):
    """A decay a head (qwen3-next's rule at its widths: 16 key and 32
    value heads of 128, one document of 16,384) never reaches the
    decay's kernel pair: its gradient calls the walks and the inverse
    alone, and its lowered text, source locations aside, is the one
    the rule had before the pair existed."""
    import hashlib

    _on_the_kernels(monkeypatch)
    bf, f32 = jnp.bfloat16, jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((1, 16384, 16, 128), bf), ((1, 16384, 16, 128), bf),
        ((1, 16384, 32, 128), bf), ((1, 16384, 32), f32),
        ((1, 16384, 32), f32))]
    text = _lowered_without_locations(_rule_grad(), args)
    assert sorted(k for k, _ in _kernel_bodies(text)) == [
        "gated_delta_rule_bwd", "gated_delta_rule_fwd",
        "gated_delta_rule_inverse", "gated_delta_rule_inverse"]
    if jax.__version__ == "0.9.0":
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            "124f0b7c305da6d8"


#: bytes of the convolution's serialised Mosaic bodies at the cells'
#: widths: loops over lanes and rows, not unrolled code
_CONV_BODY_BYTES = {"causal_conv1d_fwd": 6392, "causal_conv1d_bwd": 12116}


@pytest.mark.parametrize("shape,bias", [
    ((2, 8192, 6144), True), ((1, 16384, 8192), False),
    ((1, 8192, 4096), False)], ids=["nemotron", "qwen3-next", "kimi-linear"])
def test_causal_conv1d_lowers_once_to_loops_at_the_cells_widths(
        one_chip, monkeypatch, shape, bias):
    """The causal convolution's pair at the three cells' widths (a Mamba
    layer's with a bias, the concatenated q|k|v of a Gated DeltaNet
    layer, one of a KDA layer's three), called three times with one
    signature as a KDA layer calls it: each kernel is lowered once in
    the program (`jax.jit` round each call), its body stays under twice
    the bytes of its looped form, both compile for the chip, and the
    gradient's temporaries are no more than the `jax.numpy` form's."""
    hybrid = importlib.import_module("paddle_tpu.ops.hybrid_ops")
    kernels = importlib.import_module("paddle_tpu.ops.pallas.causal_conv1d")
    monkeypatch.setattr(kernels, "_interpret_default", lambda: False)

    def grad(kernel):
        def loss(x, w, b):
            y = sum(hybrid.causal_conv1d(x * (i + 1), w, b, True, kernel)
                    for i in range(3))
            return jnp.sum(jnp.sin(y.astype(jnp.float32)))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2) if bias else (0, 1)))

    bf = jnp.bfloat16
    c = shape[-1]
    args = [jax.ShapeDtypeStruct(shape, bf, sharding=one_chip),
            jax.ShapeDtypeStruct((c, 4), bf, sharding=one_chip),
            jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
            if bias else None]
    bodies = _kernel_bodies(_lowered_without_locations(grad(True), args))
    assert sorted(k for k, _ in bodies) == sorted(_CONV_BODY_BYTES)
    for name, size in bodies:
        assert size < 2 * _CONV_BODY_BYTES[name], (name, size)
    compiled = grad(True).lower(*args).compile()
    _kernels_are_called(compiled, list(_CONV_BODY_BYTES))
    plain = grad(False).lower(*args).compile()
    assert "tpu_custom_call" not in plain.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        plain.memory_analysis().temp_size_in_bytes


def _rpa_shapes(seqs, q_rows, hq, hkv, d, pages, page, per_seq, dtype,
                scales=False):
    shapes = [((seqs, q_rows, hq, d),
               jnp.bfloat16 if dtype == jnp.int8 else dtype),
              ((pages, page, hkv, d), dtype),
              ((pages, page, hkv, d), dtype),
              ((seqs, per_seq), jnp.int32),
              ((seqs,), jnp.int32), ((seqs,), jnp.int32)]
    if scales:
        shapes += [((pages, page), jnp.float32)] * 2
    return shapes


def _rpa(q, kp, vp, tbl, ctx, ql, ks=None, vs=None):
    return rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, ql,
                                      impl="kernel", k_scale=ks,
                                      v_scale=vs)


# the serving engine's own shapes (TinyDecoderLM: 4 q heads, 2 kv heads,
# D16) for f32, and a real-width GQA shape for bf16 and int8 pages
@pytest.mark.parametrize("name,shapes", [
    ("decode_f32", _rpa_shapes(8, 1, 4, 2, 16, 256, 8, 16, jnp.float32)),
    ("prefill_f32", _rpa_shapes(4, 32, 4, 2, 16, 256, 16, 8,
                                jnp.float32)),
    ("decode_bf16_wide", _rpa_shapes(16, 1, 32, 8, 128, 1024, 16, 64,
                                     jnp.bfloat16)),
    ("decode_int8_wide", _rpa_shapes(16, 1, 32, 8, 128, 1024, 16, 64,
                                     jnp.int8, scales=True)),
    ("prefill_int8", _rpa_shapes(4, 32, 4, 2, 16, 256, 8, 16, jnp.int8,
                                 scales=True)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_ragged_paged_attention(one_chip, mosaic, name, shapes):
    _compile(_rpa, one_chip, *shapes)


def test_int8_scales_factor_out_of_the_matmuls(mosaic):
    """The kernel applies a slot's scale to score and prob COLUMNS
    instead of dequantizing [page, D] rows; same answer as the
    reference's dequantize-then-attend, here under the interpreter."""
    r = np.random.RandomState(0)
    S, Q, Hq, Hkv, D, P, page, per_seq = 3, 4, 4, 2, 16, 12, 8, 3
    q = jnp.asarray(r.randn(S, Q, Hq, D), jnp.float32)
    kp = jnp.asarray(r.randint(-127, 128, (P, page, Hkv, D)), jnp.int8)
    vp = jnp.asarray(r.randint(-127, 128, (P, page, Hkv, D)), jnp.int8)
    ks = jnp.asarray(r.rand(P, page) * 0.02 + 1e-3, jnp.float32)
    vs = jnp.asarray(r.rand(P, page) * 0.02 + 1e-3, jnp.float32)
    tbl = jnp.asarray(r.permutation(P)[:S * per_seq].reshape(S, per_seq),
                      jnp.int32)
    ctx = jnp.asarray([20, 9, 24], jnp.int32)
    ql = jnp.asarray([4, 1, 0], jnp.int32)
    got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, ql,
                                     impl="kernel", interpret=True,
                                     k_scale=ks, v_scale=vs)
    want = rpa.ragged_paged_attention_reference(
        q, kp, vp, tbl, ctx, ql, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
