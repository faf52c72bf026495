"""FlashAttention Pallas kernels (ops/flash_attention) vs the XLA dense
path — values AND gradients, causal and bidirectional (VERDICT r2 task 6:
the fused single-chip attention tier). CPU runs the kernels in interpreter
mode; the math is identical on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.flash_attention import (
    attach_flash_attention,
    flash_attention,
)
from distkeras_tpu.parallel.ring_attention import dense_attention


def qkv(b=2, t=128, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense_values(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense_gradients(causal):
    """The custom VJP (dq/dkv kernels, FlashAttention-2 split) must agree
    with XLA's autodiff through the dense path for all three inputs."""
    q, k, v = qkv(b=1, t=64, h=2, d=16)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=causal) ** 2)

    flash = lambda q, k, v, causal: flash_attention(  # noqa: E731
        q, k, v, causal=causal, block_q=32, block_k=32
    )
    gf = jax.grad(lambda *a: loss(flash, *a), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, rtol=1e-3
        )


def test_flash_uneven_seq_falls_back_to_dense():
    """T that does not tile must still compute correctly (dense fallback),
    never crash or pad silently."""
    q, k, v = qkv(t=96)  # 96 % 64 != 0
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_effective_path_clamps_blocks_before_dense():
    """T > 512 that does not tile the 512 default must shrink the block
    (halving, floor 128) instead of surrendering to the O(T^2) dense path
    (ADVICE r3 #1): 640 -> 128, 768 -> 256; truly non-tiling T stays
    dense; short T keeps its clamped-to-T block."""
    from distkeras_tpu.ops.flash_attention import effective_path

    assert effective_path(640, 64) == ("flash", 128, 128)
    assert effective_path(768, 64) == ("flash", 256, 256)
    assert effective_path(1152, 64) == ("flash", 128, 128)
    assert effective_path(96, 64, 64, 64) == ("dense", 64, 64)
    assert effective_path(64, 64) == ("flash", 64, 64)


def test_flash_clamped_block_matches_dense():
    """The clamped-block path (T=640 rerouted to bq=bk=128) computes the
    same values as dense attention."""
    q, k, v = qkv(t=640)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-5, rtol=1e-4
    )


@pytest.mark.slow
def test_flash_bf16_matches_dense_and_keeps_dtype():
    """bf16 is the TPU compute dtype (the chip runs flash under it):
    kernels accumulate f32 internally, outputs and grads come back bf16
    and finite, values track the dense path at bf16 tolerance."""
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(
            rng.standard_normal((2, 128, 2, 32)).astype(np.float32),
            dtype=jnp.bfloat16,
        )
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )
    g = jax.grad(
        lambda q: jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64
            ).astype(jnp.float32)
            ** 2
        )
    )(q)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_long_context_falls_back_to_blockwise(monkeypatch):
    """Sequences whose full K/V would overflow VMEM must route to the
    lax.scan blockwise path (same math, HBM-streamed), not crash in the
    Mosaic lowering."""
    import distkeras_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_VMEM_KV_BUDGET_BYTES", 1024)
    q, k, v = qkv(t=128)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_rejects_cross_attention():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="self-attention only"):
        flash_attention(q, k[:, :64], v)


def test_flash_block_larger_than_seq_clamps():
    """Default 128-blocks on a 64-token sequence must clamp, not fail."""
    q, k, v = qkv(t=64)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.slow
def test_attach_flash_trains_transformer():
    """The hook face: a transformer classifier trains end-to-end with the
    fused kernels in the training graph (fwd + custom VJP under jit/scan),
    matching the dense-trained weights."""
    from distkeras_tpu import SingleTrainer
    from distkeras_tpu.data import loaders
    from distkeras_tpu.data.transformers import OneHotTransformer
    from distkeras_tpu.models import zoo

    ds = loaders.synthetic_sequences(n=256, seq_len=64, vocab=16, seed=0)
    ds = OneHotTransformer(2, output_col="label_onehot").transform(ds)

    def make_model():
        return zoo.transformer_classifier(
            vocab_size=16, seq_len=64, d_model=32, num_heads=2, depth=1,
            seed=0,
        )

    kw = dict(
        loss="categorical_crossentropy",
        batch_size=32,
        num_epoch=1,
        label_col="label_onehot",
        seed=0,
    )
    m_dense = SingleTrainer(make_model(), "adam", **kw).train(ds)

    model = make_model()
    assert attach_flash_attention(model, block_q=32, block_k=32) == 1
    m_flash = SingleTrainer(model, "adam", **kw).train(ds)
    for a, b in zip(m_dense.get_weights(), m_flash.get_weights()):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_bwd_blocks_clamp_matches_measured_chip_budget():
    """Backward-only block clamping (ops/flash_attention._bwd_blocks):
    the dkv kernel scoped-VMEM-OOMed on chip at t=4096, bq=bk=512
    (16.64M > 16M, v5e 2026-08-01) while t=2048 measured healthy — the
    clamp must split exactly that pair of cases, and must never emit a
    block that stops tiling t."""
    from distkeras_tpu.ops.flash_attention import _bwd_blocks

    assert _bwd_blocks(4096, 64, 512, 512) == (256, 512)  # measured OOM
    assert _bwd_blocks(2048, 64, 512, 512) == (512, 512)  # measured OK
    # head_dim 256 (d2048/8 heads) also clamps — ran clean on chip at
    # 0.5224 MFU (frontier d2048 L2 row, 2026-08-01)
    assert _bwd_blocks(512, 256, 512, 512) == (256, 512)
    assert _bwd_blocks(256, 64, 256, 256) == (256, 256)   # short seq
    bq, bk = _bwd_blocks(65536, 64, 512, 512)             # floor
    assert bq >= 128 and bk >= 128
    assert 4096 % _bwd_blocks(4096, 64, 512, 512)[0] == 0


def test_effective_bwd_blocks_tracks_dispatch():
    """effective_bwd_blocks is the harness-facing view of the backward
    clamp: same function _bwd calls, so artifacts record what ran."""
    from distkeras_tpu.ops.flash_attention import effective_bwd_blocks

    assert effective_bwd_blocks(4096, 64) == (256, 512)
    assert effective_bwd_blocks(2048, 64) == (512, 512)
    # non-flash paths run no backward kernel
    assert effective_bwd_blocks(640, 64, 512, 512) == (
        effective_bwd_blocks(640, 64, 512, 512)
    )  # self-consistent
    assert effective_bwd_blocks(65536, 64) is None  # blockwise path
