"""FlashAttention — fused attention Pallas TPU kernels, forward AND backward.

The reference has no attention anywhere (SURVEY §3.3/§5.7), so this module
has no reference counterpart; it is the single-chip performance tier of the
rebuild's long-context stack (VERDICT r2 task 6: the expected MFU
bottleneck is unfused attention). ``dense_attention`` materializes the
(B, H, T, T) score matrix in HBM and round-trips it through the softmax;
these kernels stream K/V blocks through VMEM with the same online softmax
the ring uses (`parallel.ring_attention._block_attention`), so scores never
leave the chip's on-chip memory and the matmuls stay MXU-shaped:

- forward: one program per (batch, head, q-block); ``fori_loop`` over K/V
  blocks accumulating (acc, running max, normalizer); emits the output
  block plus the logsumexp row statistics the backward pass needs.
- backward (FlashAttention-2 split): a dq kernel over q-blocks and a dk/dv
  kernel over k-blocks, each recomputing p = exp(s - lse) blockwise from
  the saved (q, k, v, lse, delta) instead of reading a stored score matrix.

Layouts: public API is the framework's (B, T, H, D) attention layout
(``MultiHeadSelfAttention.attention_fn`` contract); kernels run (B, H, T, D).
Compute is f32 inside the kernels regardless of input dtype (bf16 in, bf16
out — the MXU accumulates f32 anyway). Mosaic-compiled on a TPU and
interpreted anywhere else (``ops.kernel_mode.pallas_interpret`` decides,
for all kernels alike); takes the XLA-fused dense path when the sequence
does not tile (T not divisible by the block size) — ``effective_path``
says which.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distkeras_tpu.ops.kernel_mode import pallas_interpret

# 512 measured on v5e (MFU_ATTRIB.jsonl, d512/L8/seq512 training step):
# bq=bk=128 -> 0.191, 256 -> 0.243, 512 -> 0.284 vs 0.255 XLA dense — the
# MXU wants 512-wide score matmuls; blocks clamp to T for shorter seqs
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# full K+V per (batch, head) program must fit comfortably in ~16 MB VMEM
_VMEM_KV_BUDGET_BYTES = 8 * 1024 * 1024


def _causal_mask(s, iq, bq, j, bk):
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(k_pos <= q_pos, s, -jnp.inf)


# ------------------------------------------------------------------ forward


def _fwd_kernel(causal, scale, bk, q_ref, k_ref, v_ref, o_ref, lse_ref):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
    bq, d = q.shape
    nk = k_ref.shape[2] // bk
    if causal:
        # blocks entirely above the diagonal are fully masked — skip them
        # (half the matmul work at seq >> block); partial blocks still
        # mask elementwise inside the body
        nk = jnp.minimum(nk, (iq * bq + bq + bk - 1) // bk)

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        if causal:
            s = _causal_mask(s, iq, bq, j, bk)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_blk)
        # fully-masked rows keep m == -inf; exp(-inf - -inf) is nan, so
        # guard the shift (same treatment as the ring's online softmax)
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - shift[:, None])
        corr = jnp.exp(jnp.where(jnp.isneginf(m), shift, m) - shift)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0,
        nk,
        body,
        (
            jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq,), -jnp.inf, jnp.float32),
            jnp.zeros((bq,), jnp.float32),
        ),
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(jnp.isneginf(m), -jnp.inf, m + jnp.log(l_safe))
    lse_ref[0, 0] = lse[:, None]


def _fwd(q, k, v, causal, bq, bk, interpret):
    """(B, H, T, D) -> (out, lse). lse is the scaled-score logsumexp.

    Row statistics (lse, and delta in the backward) travel as
    (B, H, T, 1): Mosaic requires a block's last two dims to be divisible
    by (8, 128) or equal to the array's — a (1, 1, bq) block on a
    (B, H, T) array has block[-2] == 1 != H and fails to lower on real
    TPU (the CPU interpreter never checks). With a trailing singleton the
    row block is (bq, 1): bq % 8 == 0 and 1 == array's last dim.
    """
    b, h, t, d = q.shape
    scale = 1.0 / (d**0.5)
    grid = (b, h, t // bq)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda i, j, iq: (i, j, iq, 0))
    kvspec = pl.BlockSpec((1, 1, t, d), lambda i, j, iq: (i, j, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal, scale, bk),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=(
            qspec,
            pl.BlockSpec((1, 1, bq, 1), lambda i, j, iq: (i, j, iq, 0)),
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ----------------------------------------------------------------- backward


def _dq_kernel(
    causal, scale, bk,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0]  # (bq, 1) block -> (bq,)
    delta = delta_ref[0, 0][:, 0]
    bq, d = q.shape
    nk = k_ref.shape[2] // bk
    if causal:
        nk = jnp.minimum(nk, (iq * bq + bq + bk - 1) // bk)
    shift = jnp.where(jnp.isneginf(lse), 0.0, lse)

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            s = _causal_mask(s, iq, bq, j, bk)
        p = jnp.exp(s - shift[:, None])  # masked s=-inf -> p=0
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(
    causal, scale, bq,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
):
    ik = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    bk, d = k.shape
    nq = q_ref.shape[2] // bq
    # causal: q blocks strictly before this k block's start are fully
    # masked — start the loop at the diagonal
    q_start = (ik * bk) // bq if causal else 0

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, 0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        do_blk = do_ref[0, 0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        lse_blk = lse_ref[0, 0, pl.ds(i * bq, bq), :][:, 0]
        delta_blk = delta_ref[0, 0, pl.ds(i * bq, bq), :][:, 0]
        shift = jnp.where(jnp.isneginf(lse_blk), 0.0, lse_blk)
        s = scale * jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        if causal:
            s = _causal_mask(s, i, bq, ik, bk)
        p = jnp.exp(s - shift[:, None])
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_blk[:, None]) * scale
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        q_start, nq, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
    )
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_blocks(t, d, bq, bk):
    """Clamp BACKWARD block sizes so each program's scoped VMEM fits.

    The backward kernels are hungrier than the forward: each program
    holds two full (t, d) streams (k+v for dq; q+do for dkv) plus ~4
    (bq, bk) f32 intermediates (s, p, dp, ds), and Mosaic double-buffers
    the streamed operands. On chip this bit at t=4096, d=64,
    bq=bk=512: "scoped allocation 16.64M > 16.00M limit" in the dkv
    kernel (v5e, 2026-08-01) — a failure the CPU interpreter can never
    see, since interpret mode doesn't model VMEM. The estimate below is
    deliberately coarse; its one calibration point is that it clamps
    the measured-failing (4096, 512, 512) case while leaving the
    measured-healthy (2048, 512, 512) one alone. Halving preserves
    divisibility for the power-of-two blocks ``effective_path`` picks;
    the guard skips candidates that stop tiling t (block == t short
    seqs never hit the budget anyway)."""

    def est(bq_, bk_):
        full = 2 * t * d * 4 * 2       # two full streams, double-buffered
        inter = 4 * bq_ * bk_ * 4      # s / p / dp / ds
        blocks = 6 * max(bq_, bk_) * d * 4  # block ins/outs + accumulators
        return full + inter + blocks

    while est(bq, bk) > _VMEM_KV_BUDGET_BYTES and max(bq, bk) > 128:
        big = "bq" if bq >= bk else "bk"
        cand = (bq if big == "bq" else bk) // 2
        if cand < 128 or t % cand != 0:
            break
        if big == "bq":
            bq = cand
        else:
            bk = cand
    return bq, bk


def _bwd(causal, bq, bk, interpret, residuals, dout):
    q, k, v, out, lse = residuals
    b, h, t, d = q.shape
    bq, bk = _bwd_blocks(t, d, bq, bk)
    scale = 1.0 / (d**0.5)
    # delta_i = sum_d do_i * o_i — rowwise, cheap in XLA, shared by both
    # backward kernels (the FlashAttention-2 trick that removes dp row sums);
    # keepdims: row stats travel as (B, H, T, 1), see _fwd's layout note
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32),
        axis=-1, keepdims=True,
    )

    qspec = pl.BlockSpec((1, 1, bq, d), lambda i, j, g: (i, j, g, 0))
    full = pl.BlockSpec((1, 1, t, d), lambda i, j, g: (i, j, 0, 0))
    rowq = pl.BlockSpec((1, 1, bq, 1), lambda i, j, g: (i, j, g, 0))
    rowf = pl.BlockSpec((1, 1, t, 1), lambda i, j, g: (i, j, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal, scale, bk),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        grid=(b, h, t // bq),
        in_specs=[qspec, full, full, qspec, rowq, rowq],
        out_specs=qspec,
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    kspec = pl.BlockSpec((1, 1, bk, d), lambda i, j, g: (i, j, g, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal, scale, bq),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), v.dtype),
        ),
        grid=(b, h, t // bk),
        in_specs=[full, kspec, kspec, full, rowf, rowf],
        out_specs=(kspec, kspec),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# -------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, bq, bk, interpret):
    out, _ = _fwd(q, k, v, causal, bq, bk, interpret)
    return out


def _flash_fwd(q, k, v, causal, bq, bk, interpret):
    out, lse = _fwd(q, k, v, causal, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, bq, bk, interpret, residuals, dout):
    return _bwd(causal, bq, bk, interpret, residuals, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def effective_path(t, head_dim, block_q=DEFAULT_BLOCK_Q,
                   block_k=DEFAULT_BLOCK_K):
    """(path, bq, bk) that ``flash_attention`` will actually run for
    sequence length ``t``: path is "flash", "blockwise" (K+V past the
    VMEM budget), or "dense" (T does not tile the clamped blocks); bq/bk
    are the clamped FORWARD block sizes. The single source of the
    dispatch decision — the dispatch below and the benchmark harnesses
    both read it, so an artifact can never claim a kernel that silently
    fell back. The backward re-clamps under its own VMEM model; read
    ``effective_bwd_blocks`` for what the bwd kernels actually run."""
    bq = min(block_q, t)
    bk = min(block_k, t)
    if 2 * t * head_dim * 4 > _VMEM_KV_BUDGET_BYTES:
        return "blockwise", bq, bk
    # T that does not tile the requested blocks first tries smaller blocks
    # (halving, floor 128 — the MXU tile) before surrendering to dense:
    # seq 640/768/1152 etc. should run the kernel at 128/256, not pay the
    # O(T^2) HBM score materialization (ADVICE r3 #1)
    bq = _largest_tiling_block(t, bq)
    bk = _largest_tiling_block(t, bk)
    if bq is None or bk is None:
        return "dense", min(block_q, t), min(block_k, t)
    return "flash", bq, bk


def effective_bwd_blocks(t, head_dim, block_q=DEFAULT_BLOCK_Q,
                         block_k=DEFAULT_BLOCK_K):
    """(bq, bk) the BACKWARD kernels will actually run for sequence
    length ``t`` on the flash path: ``effective_path``'s forward blocks
    re-clamped by the backward VMEM model (``_bwd_blocks`` — the same
    function ``_bwd`` itself calls, so harness artifacts and the
    dispatch agree by construction). None when the path isn't flash
    (no backward kernel runs)."""
    path, bq, bk = effective_path(t, head_dim, block_q, block_k)
    if path != "flash":
        return None
    return _bwd_blocks(t, head_dim, bq, bk)


def _largest_tiling_block(t, block):
    """Largest candidate in {block, block/2, ..., 128} ∪ {t} that divides
    ``t``, or None. Mosaic wants q-blocks a multiple of 8; halving from a
    power-of-two default keeps that invariant."""
    if t % block == 0:  # covers the clamped block == t short-seq case
        return block
    cand = block // 2
    while cand >= 128:
        if t % cand == 0:
            return cand
        cand //= 2
    return None


def flash_attention(
    q, k, v, causal=False,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
):
    """Fused attention in the framework layout: (batch, seq, heads, head_dim).

    Numerically matches ``parallel.ring_attention.dense_attention`` (same
    online-softmax math) for values and gradients; self-attention only.
    Sequences that do not tile (T % block != 0) first retry smaller blocks
    (halving, floor 128 — see ``effective_path``), and only fall back to
    the XLA dense path when no block tiles; never pads — correctness must
    not depend on the fast path.
    """
    from distkeras_tpu.parallel.ring_attention import (
        blockwise_attention,
        dense_attention,
    )

    if k.shape[1] != q.shape[1] or v.shape[1] != q.shape[1]:
        raise ValueError(
            "flash_attention is self-attention only: expected k/v seq "
            f"length {q.shape[1]} (q's), got k={k.shape[1]}, v={v.shape[1]}"
        )
    t, d = q.shape[1], q.shape[3]
    path, bq, bk = effective_path(t, d, block_q, block_k)
    # each program holds the full K+V (f32) in VMEM; past ~8 MB of the
    # ~16 MB/core the Mosaic lowering fails, so long contexts take the
    # lax.scan blockwise path (same online softmax, HBM-streamed); T that
    # does not tile the blocks takes the XLA dense path rather than padding
    if path == "blockwise":
        return blockwise_attention(q, k, v, causal=causal)
    if path == "dense":
        return dense_attention(q, k, v, causal=causal)
    # (B, T, H, D) -> (B, H, T, D) for the kernels, and back
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = _flash(qt, kt, vt, causal, bq, bk, pallas_interpret())
    return jnp.swapaxes(out, 1, 2)


def attach_flash_attention(model, block_q=DEFAULT_BLOCK_Q,
                           block_k=DEFAULT_BLOCK_K) -> int:
    """Point every MultiHeadSelfAttention at the fused kernel (single-chip
    fast path). Returns how many were attached. Process-local, like the
    ring/blockwise hooks — not serialized."""
    from distkeras_tpu.parallel.ring_attention import attach_attention_fn

    return attach_attention_fn(
        model, functools.partial(flash_attention, block_q=block_q,
                                 block_k=block_k)
    )
