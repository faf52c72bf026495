"""Fused LayerNorm — one-pass Pallas TPU kernels, forward AND backward.

After FlashAttention (``ops/flash_attention.py``) the transformer's
remaining bandwidth-bound hot op is LayerNorm: the XLA path reads the
activation once for the mean, again for the variance, and a third time to
normalize, with the (B, T, D) tensor round-tripping HBM between passes.
These kernels compute mean/var/normalize/affine in ONE VMEM pass per row
block; the backward kernel recomputes the row statistics from x instead of
saving them, so nothing but (x, gamma) is carried between passes and the
1-D per-row stats never touch HBM at all.

No reference counterpart (the reference has no normalization layers beyond
BatchNorm and no attention workloads — SURVEY §3.3/§5.7); this is
performance tier for the rebuild's transformer family. Numerics match
``models.layers.LayerNorm.apply`` (f32 compute, biased variance, output
cast back to the input dtype).

Layout: x flattens to (rows, D) and tiles over row blocks; gamma/beta ride
along as a replicated (1, D) block. dgamma/dbeta come out of the backward
kernel as per-block partial sums, reduced in XLA. Requires D % 128 == 0
(lane width) — other widths take the plain jnp path, as do rows that
don't fill one sublane tile. Mosaic-compiled on a TPU and interpreted
anywhere else (``ops.kernel_mode.pallas_interpret``), chosen at trace
time like the other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distkeras_tpu.ops.kernel_mode import pallas_interpret

LANE = 128
DEFAULT_BLOCK_ROWS = 256
# x, dy, dx blocks live in VMEM together (f32); stay well under ~16 MB/core
_VMEM_ROW_BUDGET_BYTES = 4 * 1024 * 1024


def _reference_layer_norm(x, gamma, beta, epsilon):
    """The plain-XLA path — identical math to LayerNorm.apply."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + epsilon)
    return (y * gamma + beta).astype(x.dtype)


def _block_rows_for(n_rows: int, d: int) -> int:
    """Sublane-aligned row-block height under the VMEM budget."""
    budget = max(8, _VMEM_ROW_BUDGET_BYTES // (3 * d * 4))
    rows = min(DEFAULT_BLOCK_ROWS, budget, int(np.ceil(n_rows / 8)) * 8)
    return max(8, (rows // 8) * 8)


# ----------------------------------------------------------------- kernels


def _fwd_kernel(eps, x_ref, g_ref, b_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)  # (rows, D)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y_ref[:] = (y * g_ref[:] + b_ref[:]).astype(y_ref.dtype)


def _bwd_kernel(eps, x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    a = dy * g_ref[:]
    m1 = jnp.mean(a, axis=1, keepdims=True)
    m2 = jnp.mean(a * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rstd * (a - m1 - xhat * m2)).astype(dx_ref.dtype)
    # per-block partial sums; XLA reduces the block axis afterwards. The
    # refs are (1, 1, d) blocks — see _bwd's layout note on why the block
    # axis needs its own leading dim on real TPU.
    dg_ref[:] = jnp.sum(dy * xhat, axis=0, keepdims=True)[None]
    db_ref[:] = jnp.sum(dy, axis=0, keepdims=True)[None]


def _pad_rows(mat, block_rows):
    n = mat.shape[0]
    padded = int(np.ceil(n / block_rows)) * block_rows
    if padded != n:
        mat = jnp.pad(mat, ((0, padded - n), (0, 0)))
    return mat


def _row_specs(num, block_rows, d):
    return [
        pl.BlockSpec((block_rows, d), lambda i: (i, 0)) for _ in range(num)
    ]


def _vec_spec(d):
    return pl.BlockSpec((1, d), lambda i: (0, 0))


def _fwd(x2, gamma, beta, eps, block_rows, interpret):
    n, d = x2.shape
    xp = _pad_rows(x2, block_rows)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
        grid=(xp.shape[0] // block_rows,),
        in_specs=_row_specs(1, block_rows, d) + [_vec_spec(d), _vec_spec(d)],
        out_specs=_row_specs(1, block_rows, d)[0],
        interpret=interpret,
    )(xp, gamma.astype(jnp.float32)[None], beta.astype(jnp.float32)[None])
    return y[:n]


def _bwd(x2, gamma, dy2, eps, block_rows, interpret):
    n, d = x2.shape
    xp = _pad_rows(x2, block_rows)
    dyp = _pad_rows(dy2, block_rows)  # zero rows: zero dx and zero partials
    nblocks = xp.shape[0] // block_rows
    # dgamma/dbeta partials are (nblocks, 1, d) with (1, 1, d) blocks:
    # Mosaic requires a block's last two dims divisible by (8, 128) or
    # equal to the array's — a (1, d) block on a (nblocks, d) array has
    # block[-2] == 1 != nblocks and fails to lower on real TPU (the CPU
    # interpreter never checks). With the block axis leading, the last two
    # dims are (1, d) == the array's own (1, d).
    dx, dg_part, db_part = pl.pallas_call(
        functools.partial(_bwd_kernel, eps),
        out_shape=(
            jax.ShapeDtypeStruct(xp.shape, x2.dtype),
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.float32),
        ),
        grid=(nblocks,),
        in_specs=_row_specs(1, block_rows, d)
        + [_vec_spec(d)]
        + _row_specs(1, block_rows, d),
        out_specs=(
            _row_specs(1, block_rows, d)[0],
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ),
        interpret=interpret,
    )(xp, gamma.astype(jnp.float32)[None], dyp)
    return dx[:n], jnp.sum(dg_part, axis=(0, 1)), jnp.sum(db_part, axis=(0, 1))


# -------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(x2, gamma, beta, eps, block_rows, interpret):
    return _fwd(x2, gamma, beta, eps, block_rows, interpret)


def _fused_fwd(x2, gamma, beta, eps, block_rows, interpret):
    # beta rides the residuals only for its dtype: the cotangent must match
    # the primal's dtype even when gamma and beta dtypes differ
    return _fwd(x2, gamma, beta, eps, block_rows, interpret), (x2, gamma, beta)


def _fused_bwd(eps, block_rows, interpret, residuals, dy2):
    x2, gamma, beta = residuals
    dx, dg, db = _bwd(x2, gamma, dy2, eps, block_rows, interpret)
    return dx, dg.astype(gamma.dtype), db.astype(beta.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_layer_norm(x, gamma, beta, epsilon=1e-5):
    """LayerNorm over the trailing axis in one fused pass.

    ``x``: (..., D); ``gamma``/``beta``: (D,). Matches
    ``models.layers.LayerNorm.apply`` numerics (f32 compute, biased
    variance, result cast to x.dtype). Widths that don't tile the 128-wide
    lanes — or tiny inputs where a kernel launch costs more than it saves —
    take the identical-math XLA path instead.
    """
    d = x.shape[-1]
    n_rows = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    if d % LANE or x.ndim < 2 or n_rows < 8:
        return _reference_layer_norm(x, gamma, beta, epsilon)
    x2 = x.reshape(n_rows, d)
    block_rows = _block_rows_for(n_rows, d)
    out = _fused(
        x2, gamma, beta, float(epsilon), block_rows, pallas_interpret()
    )
    return out.reshape(x.shape)


def attach_fused_layernorm(model) -> int:
    """Point every LayerNorm at the fused kernel (single-chip fast path).
    Returns how many were attached. Process-local, like the attention
    hooks — not serialized."""
    from distkeras_tpu.models.layers import LayerNorm
    from distkeras_tpu.models.sequential import walk_layers

    n = 0
    for layer in walk_layers(model):
        if isinstance(layer, LayerNorm):
            layer.norm_fn = fused_layer_norm
            n += 1
    return n
