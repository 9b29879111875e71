"""What the plain references share: the optimizers' arithmetic, per-leaf
norms, and the lower-precision stand-in used by the control.

Nothing here imports the program under test. Every array is an argument
of the jitted functions (a closed-over array is baked into the
executable: PR 21 paid 89.8 MB of compile cache for one)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def qdq_fp8(x):
    """Per-tensor scaled float8_e4m3 quantize-dequantize with a
    straight-through gradient: what a matmul operand loses in the
    precision below bfloat16."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def qdq_bf16(x):
    """Operands rounded to bfloat16's 8 exponent and 7 mantissa bits.
    `reduce_precision`, not a pair of casts: the TPU compiler may drop
    a float32 -> bfloat16 -> float32 pair as excess precision, and did
    (PR 24: the pair read 1e-7 where this reads 1e-3)."""
    q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x + jax.lax.stop_gradient(q - x)


QUANT = {None: None, "float32": None, "bfloat16": qdq_bf16,
         "float8_e4m3": qdq_fp8}


def operand(x, quant):
    return x if quant is None else quant(x)


@jax.jit
def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def scaled(tree, factor):
    return {k: v.astype(jnp.float32) * factor for k, v in tree.items()}


@jax.jit
def diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
        for k in a}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam_step(params, grads, m1, m2, t, *, lr, b1, b2, eps):
    """Adam as Kingma & Ba state it, bias correction folded into the
    step size; `t` counts from 1."""
    tf = t.astype(jnp.float32)
    alpha = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    out_p, out_m1, out_m2 = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        out_m1[k] = b1 * m1[k] + (1.0 - b1) * g
        out_m2[k] = b2 * m2[k] + (1.0 - b2) * jnp.square(g)
        out_p[k] = p - alpha * out_m1[k] / (jnp.sqrt(out_m2[k]) + eps)
    return out_p, out_m1, out_m2


@functools.partial(jax.jit, static_argnames=("lr", "mu"),
                   donate_argnums=(0, 2))
def momentum_step(params, grads, velocity, *, lr, mu):
    """Heavy-ball momentum: v = mu v + g, p = p - lr v."""
    out_p, out_v = {}, {}
    for k, p in params.items():
        out_v[k] = mu * velocity[k] + grads[k]
        out_p[k] = p - lr * out_v[k]
    return out_p, out_v


def zeros_like_tree(tree):
    return jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))(tree)
