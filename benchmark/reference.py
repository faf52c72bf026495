"""The parts of the plain reference that no model owns, in ``jax.numpy`` and
float32. It imports nothing of ``distkeras_tpu`` and knows no width of any
model: a model's own reference (its weights from the seed, its forward,
loss and gradients, ``train_readings``, ``token_gaps``) is its family's file
under ``families/``, which imports what is here.

Here: the precisions of a matrix product and their controls (``dot_highest``,
``make_dot_int``, ``dot_weights_int``, ``get_dot``), ``layer_norm`` and
``gelu``, Adam written out (``adam_step``, ``ADAM``), and the norms of a
tree's leaves that the training check compares (``leaf_norms``,
``leaf_norms_of_difference``, ``_tree_add``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}


# ----------------------------------------------------------- precisions
#
# ``dot`` is the one place the precision of a matrix product is decided.
# "highest" is the reference; the others are the contract's controls: the
# reference computed one step below what the configuration states.


def dot_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, levels: float):
    """Symmetric per-tensor rounding to ``levels`` steps each side, with a
    straight-through gradient (as quantized training does it)."""
    scale = jnp.max(jnp.abs(x)) / levels
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -levels, levels) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_dot_int(levels: float):
    """Products with both operands rounded to ``levels`` steps, and in the
    backward pass the incoming gradient too: int8 is 127, int4 is 7."""

    @jax.custom_vjp
    def dot(a, b):
        return dot_highest(_fake_quant(a, levels), _fake_quant(b, levels))

    def fwd(a, b):
        return dot(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        gq = _fake_quant(g, levels)
        aq, bq = _fake_quant(a, levels), _fake_quant(b, levels)
        ga = dot_highest(gq, jnp.swapaxes(bq, -1, -2))
        gb = dot_highest(jnp.swapaxes(aq, -1, -2), gq)
        if gb.ndim > b.ndim:  # a batch of rows against one matrix
            gb = gb.sum(axis=tuple(range(gb.ndim - b.ndim)))
        return ga.astype(a.dtype), gb.astype(b.dtype)

    dot.defvjp(fwd, bwd)
    return dot


def dot_weights_int(levels: float):
    """Weight-only rounding, per output column (what a w8/w4 server holds):
    the activations stay float32."""

    def dot(a, b):
        scale = jnp.max(jnp.abs(b), axis=0, keepdims=True) / levels
        scale = jnp.where(scale == 0, 1.0, scale)
        bq = jnp.clip(jnp.round(b / scale), -levels, levels) * scale
        return dot_highest(a, bq)

    return dot


def get_dot(precision: str):
    return {
        "highest": dot_highest,
        "int8": make_dot_int(127.0),
        "w_int4": dot_weights_int(7.0),
    }[precision]


# ---------------------------------------------------------------- layers


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["gamma"] + p["beta"]


def gelu(x, flavour: str):
    if flavour == "tanh":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x**3)))
    if flavour == "erf":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown gelu flavour {flavour!r}")


# ------------------------------------------------- Adam and leaf norms


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("lr",), donate_argnums=(0, 2, 3))
def adam_step(params, grads, mu, nu, count, *, lr: float):
    """optax.adam's update with its defaults, written out."""
    b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu, count


@jax.jit
def leaf_norms(tree):
    """The L2 norm of every leaf, as one vector in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_norms_of_difference(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
