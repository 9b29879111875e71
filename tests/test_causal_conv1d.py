"""The causal depthwise convolution of the hybrid decoders
(`ops/hybrid_ops.causal_conv1d`): its Pallas pair
(`ops/pallas/causal_conv1d.py`) under the interpreter against the
`jax.numpy` form, through `jax.vjp` of the op, with blocks of positions
that do not divide the sequence; the log line that says who convolves;
and the CPU path's program, which is the one it was before the pair."""
import functools
import hashlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.pallas import causal_conv1d as kernels
from paddle_tpu.ops.registry import get_op

_B, _S, _C, _K = 2, 200, 384, 4


def _args(seed, dtype=jnp.bfloat16, bias=True, s=_S, c=_C, k=_K):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(_B, s, c), dtype)
    w = jnp.asarray(r.uniform(-0.5, 0.5, (c, k)), dtype)
    b = jnp.asarray(r.randn(c) * 0.1, jnp.float32) if bias else None
    dy = jnp.asarray(r.randn(_B, s, c), dtype)
    return x, w, b, dy


def _close(got, want, name):
    assert got.shape == want.shape and got.dtype == want.dtype, name
    scale = max(1.0, float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
    # a float32 sum in another order, rounded to the output's dtype
    tol = 2.0 ** -8 if got.dtype == jnp.bfloat16 else 2e-6
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err <= tol * scale, (name, err, scale)


def _vjp(x, w, b, dy, silu, kernel):
    conv = functools.partial(hybrid_ops.causal_conv1d, silu=silu,
                             kernel=kernel)
    out, vjp = jax.vjp(conv, x, w, b)
    return (out,) + tuple(vjp(dy))


@pytest.mark.parametrize("most_rows", [64, None], ids=["blocks", "rule"])
@pytest.mark.parametrize("silu", [True, False], ids=["silu", "linear"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
def test_the_kernel_pair_is_the_formula_and_its_vjp(monkeypatch, bias, silu,
                                                    most_rows):
    """Out, dX, dFilter and dBias of the Pallas pair against the
    `jax.numpy` form's, two sequences of 200 positions, 384 channels
    (three blocks of 128): in blocks of 64 positions, two trips of the
    kernels' loops each (the last block partial, every block's halos
    before and after it read), or in the rule's one block of 224."""
    if most_rows:
        monkeypatch.setattr(kernels, "_MOST_ROWS", most_rows)
    assert kernels.conv_blocks(_S, _C, 2, 3) == (most_rows or 224, 128)
    x, w, b, dy = _args(1, bias=bias)
    got, want = (_vjp(x, w, b, dy, silu, kernel) for kernel in (True, False))
    for name, a, r in zip(("out", "d_x", "d_filter", "d_bias"), got, want):
        if r is None:
            assert a is None
        else:
            assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
            _close(a, r, name)


def test_the_kernel_pair_in_float32_with_two_taps_and_lanes_of_256():
    """Float32 operands, a filter of 2 taps, 512 channels (one block,
    which the reverse kernel walks 256 lanes at a time) and 70
    positions"""
    x, w, b, dy = _args(2, jnp.float32, s=70, c=512, k=2)
    assert kernels.conv_blocks(70, 512, 4, 3) == (96, 512)
    got, want = (_vjp(x, w, b, dy, True, kernel) for kernel in (True, False))
    for name, a, r in zip(("out", "d_x", "d_filter", "d_bias"), got, want):
        _close(a, r, name)


def test_the_rule_takes_the_cells_blocks():
    """The three cells' widths at bfloat16: 512 channels a block, 2,048
    positions a forward step and 1,024 a reverse one (three arrays)"""
    for s, c in ((8192, 6144), (16384, 8192), (8192, 4096)):
        assert kernels.conv_blocks(s, c, 2, 2) == (2048, 512)
        assert kernels.conv_blocks(s, c, 2, 3) == (1024, 512)


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
def test_the_op_says_who_convolves(monkeypatch, caplog, on_tpu):
    """Where the op is traced its log line gives the shape, the dtype,
    the bias and the activation, and who convolves: the Pallas pair with
    its blocks and grid steps where the backend is a TPU (the kernels
    run under the interpreter here), `jax.numpy` elsewhere. The op's
    results are the same either way."""
    x, w, b, dy = _args(3)
    ins = {"X": [x], "Filter": [w], "Bias": [b]}
    op = get_op("causal_conv1d")
    want = _vjp(x, w, b, dy, True, False)
    if on_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kernels, "_interpret_default", lambda: True)
    with caplog.at_level(logging.INFO, logger=hybrid_ops.__name__):
        out, vjp = jax.vjp(
            lambda x, w, b: op.compute(
                {"X": [x], "Filter": [w], "Bias": [b]},
                {"activation": "silu"})["Out"], *(ins[n][0] for n in ins))
    said = [r.getMessage() for r in caplog.records]
    assert said[0].startswith(
        "causal_conv1d x (2, 200, 384) bfloat16, 4 taps, a bias, silu: "
        "convolved by ")
    assert said[0].endswith(
        "pallas (positions x channels: forward 224 x 128 a grid step, 6 "
        "steps; reverse 224 x 128 a grid step, 6 steps)" if on_tpu
        else "jax.numpy")
    for name, a, r in zip(("out", "d_x", "d_filter", "d_bias"),
                          (out,) + tuple(vjp(dy)), want):
        _close(a, r, name)


#: sha256 of the op's jaxprs on the CPU, forward and vjp, at [2, 40,
#: 256] with 4 taps, before the kernel pair existed
_CPU_JAXPRS = {("", False): ("c6854aed2df98dc0", "20e65f18c22fb18f"),
               ("silu", False): ("21d042028fc13ed5", "955aab8d0d444e29"),
               ("", True): ("fe64c5e3d70e47d9", "447c72264781cca2"),
               ("silu", True): ("d477358c4597d5fb", "d207fa355e555667")}


@pytest.mark.parametrize("act,bias", list(_CPU_JAXPRS))
def test_the_cpu_path_is_the_program_it_was(act, bias):
    """Off a TPU the op traces to the jaxprs it traced to before the
    kernel pair, forward and gradient alike."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's jaxprs")
    op = get_op("causal_conv1d")

    def conv(x, w, *b):
        ins = {"X": [x], "Filter": [w]}
        if b:
            ins["Bias"] = [b[0]]
        return op.compute(ins, {"activation": act})["Out"]

    args = [jnp.ones((2, 40, 256), jnp.bfloat16),
            jnp.ones((256, 4), jnp.bfloat16)]
    if bias:
        args.append(jnp.ones((256,), jnp.float32))
    fwd = str(jax.make_jaxpr(conv)(*args))
    grad = str(jax.make_jaxpr(lambda *a: jax.vjp(conv, *a)[1](
        jnp.ones((2, 40, 256), jnp.bfloat16)))(*args))
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (fwd, grad)) == _CPU_JAXPRS[act, bias]
