"""Multi-block long-context parity on the CPU mesh (VERDICT r4 task 7).

Every SP/attention parity test elsewhere runs at toy sequence lengths
(T=64, one kernel block, one ring hop ≈ short loops); the seq>=2048 regime
was only ever a queued TPU *performance* measurement. Correctness must not
wait on chip time: at T=2048 the flash kernel runs a genuine 4x4 block
grid (bq=bk=512), blockwise streams 4 K/V tiles, and the 8-device ring
makes 8 rotations over 256-token shards — the regimes where online-softmax
carry bugs, block-boundary masking bugs, and ring-accumulation bugs live.

All rows compare values AND gradients against the same dense reference.
Everything here is @slow: dense T=2048 materializes a 2048^2 score matrix
per head on one CPU core.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from distkeras_tpu.ops.flash_attention import effective_path, flash_attention
from distkeras_tpu.parallel.ring_attention import (
    blockwise_attention,
    dense_attention,
    ring_attention,
)
from distkeras_tpu.parallel.ulysses import ulysses_attention

B, T, H, D = 1, 2048, 2, 8

pytestmark = pytest.mark.slow


def qkv(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((B, T, H, D)).astype(np.float32))
        for _ in range(3)
    )


def seq_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def test_t2048_is_genuinely_multi_block():
    """Guard the regime claim: if kernel defaults ever change such that
    T=2048 stops exercising a multi-block grid, this file's parity rows
    silently degrade to the toy regime — fail loudly instead."""
    path, bq, bk = effective_path(T, D)
    assert path == "flash" and T // bq >= 4 and T // bk >= 4, (path, bq, bk)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_t2048_matches_dense(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_t2048_gradients_match_dense():
    q, k, v = qkv(seed=1)
    g_f = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_t2048_matches_dense(causal):
    q, k, v = qkv(seed=2)
    out = blockwise_attention(q, k, v, causal=causal, block_size=512)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_blockwise_t2048_gradients_match_dense():
    q, k, v = qkv(seed=3)
    g_b = jax.grad(
        lambda q, k, v: jnp.sum(
            blockwise_attention(q, k, v, causal=True, block_size=512) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_b, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_t2048_matches_dense(causal):
    """8 rotations x 256-token shards: the K/V blocks traverse the whole
    ring (toy-T tests rotate once or twice)."""
    q, k, v = qkv(seed=4)
    out = ring_attention(q, k, v, seq_mesh(), causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_ring_t2048_gradients_match_dense():
    q, k, v = qkv(seed=5)
    mesh = seq_mesh()
    g_r = jax.grad(
        lambda q, k, v: jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_r, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def qkv8(seed):
    """Ulysses shards HEADS over the axis: H must divide the 8-way mesh."""
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((B, T, 8, D)).astype(np.float32))
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_t2048_matches_dense(causal):
    q, k, v = qkv8(seed=6)
    out = ulysses_attention(q, k, v, seq_mesh(), causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_ulysses_t2048_gradients_match_dense():
    q, k, v = qkv8(seed=7)
    mesh = seq_mesh()
    g_u = jax.grad(
        lambda q, k, v: jnp.sum(
            ulysses_attention(q, k, v, mesh, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_u, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_t4096_matches_dense_values_and_grads():
    """seq-4096 = an 8x8 block grid (twice the depth of seq-2048's) —
    the correctness pin for a seq-4096 run on the chip, so the on-chip
    number never lands without an off-chip parity proof at the same
    sequence length."""
    T4 = 4096
    path, bq, bk = effective_path(T4, D)
    assert path == "flash" and T4 // bq == 8 and T4 // bk == 8, (path, bq, bk)
    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, T4, H, D)).astype(np.float32))
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    g_f = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
