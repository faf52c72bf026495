"""Pallas TPU kernels — the framework's hand-written native tier.

The reference has no native components of its own (SURVEY §3.4); its compute
runs in the Keras backend. Here the equivalent tier is XLA-compiled JAX plus
these Pallas kernels for ops worth owning:

- ``fused_sgd``: the optimizer update applied in ONE pass over each
  parameter buffer (p' = p - lr*u and m' = mu*m + g computed together in
  VMEM), instead of the separate update/apply traffic of the generic
  optax path (reference: the worker optimizer step inside
  distkeras/workers.py -> Worker.train's ``train_on_batch``).
- ``fused_adam``: the full Adam update (both moment EMAs, bias
  correction, rsqrt, and the parameter write) in one VMEM pass per
  buffer. The generic optax path streams p/g/m/v through HBM several
  times (update, then apply_updates); here each block is read once and
  written once. Bias-correction factors depend on the step count, so
  they enter the kernel as a (1, 2) SMEM scalar block instead of being
  baked in like lr/betas/eps.

Kernels compile with Mosaic on a TPU and run in the Pallas interpreter
anywhere else (``ops.kernel_mode.pallas_interpret``; tests run on the
8-device CPU mesh), chosen at trace time.

Layout: each parameter leaf is raveled and tiled to (rows, 128) f32 blocks
(lane width 128, sublane multiple 8 — see the Pallas TPU guide's tiling
table); leaves smaller than one tile use plain VPU-fused jnp math, where a
kernel launch would cost more than it saves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.kernel_mode import pallas_interpret

LANE = 128
BLOCK_ROWS = 512  # (512, 128) f32 = 256 KiB per buffer — comfortably in VMEM
_MIN_KERNEL_SIZE = 8 * LANE  # below one f32 tile, jnp is cheaper


def _block_rows_for(n: int) -> int:
    """Per-leaf block height: the sublane-aligned row count, capped at
    BLOCK_ROWS — a leaf slightly over one tile pads to its own size, not to
    a full 512-row block (64x waste for small leaves otherwise)."""
    rows = pl.cdiv(n, LANE)
    return min(int(np.ceil(rows / 8)) * 8, BLOCK_ROWS)


def _pad_to_blocks(flat, block_rows):
    """(n,) -> (rows, LANE) with rows a multiple of ``block_rows``."""
    n = flat.shape[0]
    rows = pl.cdiv(n, LANE)
    rows_padded = int(np.ceil(rows / block_rows)) * block_rows
    flat = jnp.pad(flat, (0, rows_padded * LANE - n))
    return flat.reshape(rows_padded, LANE)


def _unpad(mat, shape, dtype):
    n = int(np.prod(shape)) if shape else 1
    return mat.reshape(-1)[:n].reshape(shape).astype(dtype)


# ----------------------------------------------------------------- kernels


def _sgd_kernel(lr, p_ref, g_ref, out_ref):
    out_ref[:] = p_ref[:] - lr * g_ref[:]


def _sgd_momentum_kernel(lr, mu, nesterov, p_ref, g_ref, m_ref, op_ref, om_ref):
    m_new = mu * m_ref[:] + g_ref[:]
    update = g_ref[:] + mu * m_new if nesterov else m_new
    op_ref[:] = p_ref[:] - lr * update
    om_ref[:] = m_new


def _block_specs(num, block_rows):
    return [
        pl.BlockSpec((block_rows, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM)
        for _ in range(num)
    ]


def _leaf_sgd(p, g, lr, interpret):
    shape, dtype = p.shape, p.dtype
    if p.size < _MIN_KERNEL_SIZE:
        return (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(dtype)
    br = _block_rows_for(p.size)
    pm = _pad_to_blocks(p.ravel().astype(jnp.float32), br)
    gm = _pad_to_blocks(g.ravel().astype(jnp.float32), br)
    out = pl.pallas_call(
        functools.partial(_sgd_kernel, lr),
        out_shape=jax.ShapeDtypeStruct(pm.shape, jnp.float32),
        grid=(pm.shape[0] // br,),
        in_specs=_block_specs(2, br),
        out_specs=_block_specs(1, br)[0],
        interpret=interpret,
    )(pm, gm)
    return _unpad(out, shape, dtype)


def _leaf_sgd_momentum(p, g, m, lr, mu, nesterov, interpret):
    shape, dtype = p.shape, p.dtype
    if p.size < _MIN_KERNEL_SIZE:
        p32, g32, m32 = (x.astype(jnp.float32) for x in (p, g, m))
        m_new = mu * m32 + g32
        update = g32 + mu * m_new if nesterov else m_new
        return (p32 - lr * update).astype(dtype), m_new
    br = _block_rows_for(p.size)
    pm = _pad_to_blocks(p.ravel().astype(jnp.float32), br)
    gm = _pad_to_blocks(g.ravel().astype(jnp.float32), br)
    mm = _pad_to_blocks(m.ravel().astype(jnp.float32), br)
    op, om = pl.pallas_call(
        functools.partial(_sgd_momentum_kernel, lr, mu, nesterov),
        out_shape=(
            jax.ShapeDtypeStruct(pm.shape, jnp.float32),
            jax.ShapeDtypeStruct(pm.shape, jnp.float32),
        ),
        grid=(pm.shape[0] // br,),
        in_specs=_block_specs(3, br),
        out_specs=tuple(_block_specs(2, br)),
        interpret=interpret,
    )(pm, gm, mm)
    return _unpad(op, shape, dtype), _unpad(om, shape, jnp.float32)


def _adam_math(p32, g32, m32, v32, lr, b1, b2, eps, c1, c2):
    """The one copy of the Adam update; both the kernel and the small-leaf
    jnp path call it (c1/c2 are the bias-correction factors 1/(1-b^t))."""
    m_new = b1 * m32 + (1.0 - b1) * g32
    v_new = b2 * v32 + (1.0 - b2) * g32 * g32
    p_new = p32 - lr * (m_new * c1) / (jnp.sqrt(v_new * c2) + eps)
    return p_new, m_new, v_new


def _adam_kernel(lr, b1, b2, eps, c_ref, p_ref, g_ref, m_ref, v_ref,
                 op_ref, om_ref, ov_ref):
    op_ref[:], om_ref[:], ov_ref[:] = _adam_math(
        p_ref[:], g_ref[:], m_ref[:], v_ref[:],
        lr, b1, b2, eps, c_ref[0, 0], c_ref[0, 1],
    )


def _leaf_adam(p, g, m, v, scalars, lr, b1, b2, eps, interpret):
    shape, dtype = p.shape, p.dtype
    if p.size < _MIN_KERNEL_SIZE:
        p32, g32, m32, v32 = (x.astype(jnp.float32) for x in (p, g, m, v))
        c1, c2 = scalars[0, 0], scalars[0, 1]
        p_new, m_new, v_new = _adam_math(
            p32, g32, m32, v32, lr, b1, b2, eps, c1, c2
        )
        return p_new.astype(dtype), m_new, v_new
    br = _block_rows_for(p.size)
    pm = _pad_to_blocks(p.ravel().astype(jnp.float32), br)
    gm = _pad_to_blocks(g.ravel().astype(jnp.float32), br)
    mm = _pad_to_blocks(m.ravel().astype(jnp.float32), br)
    vm = _pad_to_blocks(v.ravel().astype(jnp.float32), br)
    scalar_spec = pl.BlockSpec(
        (1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM
    )
    op, om, ov = pl.pallas_call(
        functools.partial(_adam_kernel, lr, b1, b2, eps),
        out_shape=(
            jax.ShapeDtypeStruct(pm.shape, jnp.float32),
            jax.ShapeDtypeStruct(pm.shape, jnp.float32),
            jax.ShapeDtypeStruct(pm.shape, jnp.float32),
        ),
        grid=(pm.shape[0] // br,),
        in_specs=[scalar_spec] + _block_specs(4, br),
        out_specs=tuple(_block_specs(3, br)),
        interpret=interpret,
    )(scalars, pm, gm, mm, vm)
    return (
        _unpad(op, shape, dtype),
        _unpad(om, shape, jnp.float32),
        _unpad(ov, shape, jnp.float32),
    )


# ------------------------------------------------------------ optimizer API


class FusedSGD:
    """Fused-apply optimizer: one VMEM pass computes p' (and m') directly.

    Exposes the ``init``/``fused_apply`` protocol WorkerCore prefers over
    the two-step optax ``update``+``apply_updates`` when present.
    """

    def __init__(self, learning_rate=0.01, momentum=0.0, nesterov=False):
        if callable(learning_rate):
            raise TypeError(
                "pallas_sgd bakes the learning rate into the kernel and "
                "does not accept schedules; use optimizer 'sgd' with a "
                "schedule instead"
            )
        self.learning_rate = float(learning_rate)
        self.momentum = float(momentum)
        self.nesterov = bool(nesterov)

    def init(self, params):
        if self.momentum == 0.0:
            return ()
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

    def fused_apply(self, params, grads, state):
        interpret = pallas_interpret()
        if self.momentum == 0.0:
            new_params = jax.tree.map(
                lambda p, g: _leaf_sgd(p, g, self.learning_rate, interpret),
                params,
                grads,
            )
            return new_params, state
        out = jax.tree.map(
            lambda p, g, m: _leaf_sgd_momentum(
                p, g, m, self.learning_rate, self.momentum,
                self.nesterov, interpret,
            ),
            params,
            grads,
            state,
        )
        new_params = jax.tree.map(
            lambda pair: pair[0], out, is_leaf=lambda x: isinstance(x, tuple)
        )
        new_state = jax.tree.map(
            lambda pair: pair[1], out, is_leaf=lambda x: isinstance(x, tuple)
        )
        return new_params, new_state


class FusedAdam:
    """Fused-apply Adam: moments, bias correction, and the parameter write
    in one VMEM pass per buffer; numerically matches ``optax.adam``.

    State is ``(m_tree, v_tree, count)`` with ``count`` an int32 step
    counter (optax convention: first apply uses t = 1). Bias-correction
    factors 1/(1-b^t) are traced scalars, shipped to the kernel as a
    (1, 2) SMEM block.
    """

    def __init__(self, learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8):
        if callable(learning_rate):
            raise TypeError(
                "pallas_adam bakes the learning rate into the kernel and "
                "does not accept schedules; use optimizer 'adam' with a "
                "schedule instead"
            )
        self.learning_rate = float(learning_rate)
        self.b1 = float(b1)
        self.b2 = float(b2)
        self.eps = float(eps)

    def init(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return (
            jax.tree.map(zeros, params),
            jax.tree.map(zeros, params),
            jnp.zeros((), jnp.int32),
        )

    def fused_apply(self, params, grads, state):
        interpret = pallas_interpret()
        m_tree, v_tree, count = state
        t = (count + 1).astype(jnp.float32)
        c1 = 1.0 / (1.0 - self.b1**t)
        c2 = 1.0 / (1.0 - self.b2**t)
        scalars = jnp.stack([c1, c2]).reshape(1, 2)
        out = jax.tree.map(
            lambda p, g, m, v: _leaf_adam(
                p, g, m, v, scalars, self.learning_rate, self.b1,
                self.b2, self.eps, interpret,
            ),
            params,
            grads,
            m_tree,
            v_tree,
        )
        pick = lambda i: jax.tree.map(
            lambda trip: trip[i], out, is_leaf=lambda x: isinstance(x, tuple)
        )
        return pick(0), (pick(1), pick(2), count + 1)
