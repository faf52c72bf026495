"""Operations and bytes from shapes, for utilization and roofline shares.

Counted from the algorithm, never from the compiler's cost analysis: a
product of (m, k) by (k, n) is 2 m k n operations, recomputed work is not
counted, and bytes are what the algorithm has to move once. Each function
returns a dict with ``flops`` and, where a roofline needs it, ``bytes``.
"""

from __future__ import annotations


def matmul_params(w: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    blocks' four attention matrices and two MLP matrices, and the output
    head. Embedding tables are lookups and do not count."""
    d, f = w["d"], w["inner"]
    return w["layers"] * (4 * d * d + 2 * d * f) + d * w["vocab"]


def train_flops_per_token(w: dict) -> dict:
    """Forward and backward of one token in a sequence of ``seq`` tokens:
    6 operations a matmul parameter (2 forward, 4 backward), and causal
    attention's two products (scores, values) over the half of the square
    that the mask keeps: forward 2 * 2 * d * (T / 2) a layer, three times
    that with the backward pass."""
    t, d = w["seq"], w["d"]
    dense = 6 * matmul_params(w)
    attention = w["layers"] * 3 * (2 * 2 * d * (t / 2))
    return {"flops": dense + attention, "dense": dense, "attention": attention}


def flash_attention_train(w: dict, batch: int) -> dict:
    """The flash kernels of one step over ``batch`` sequences, all layers:
    forward is two products over the causal half (scores, values); backward
    is the four the gradient needs (dp, dq, dk, dv). The scores that the
    FlashAttention-2 backward recomputes in each of its two kernels are
    recomputed work and are not counted. Bytes: q, k, v, o forward; those
    and do, dq, dk, dv backward, each (T, d) in bfloat16."""
    t, d, layers = w["seq"], w["d"], w["layers"]
    product = 2 * t * (t / 2) * d  # one (T, T/2 kept) x d product, all heads
    fwd = 2 * product
    bwd = 4 * product
    return {
        "flops_fwd": batch * layers * fwd, "flops_bwd": batch * layers * bwd,
        "flops": batch * layers * (fwd + bwd),
        "bytes_fwd": batch * layers * 4 * t * d * 2,
        "bytes_bwd": batch * layers * 8 * t * d * 2,
    }


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window): every matmul weight is read
    once and used for ``batch`` tokens; every cached key and value is read
    once. ``weight_bytes`` is bytes a matmul weight as served (1 for int8),
    ``kv_bytes`` bytes a cached value."""
    d, layers = w["d"], w["layers"]
    n = matmul_params(w)
    flops = 2 * n * batch + layers * 2 * 2 * d * cached * batch
    kv = layers * 2 * d * cached * batch * kv_bytes
    weights = n * weight_bytes
    return {"flops": flops, "bytes": weights + kv,
            "weight_bytes": weights, "kv_bytes": kv}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> dict:
    """The least time the chip could take (the larger of operations over
    the peak rate and bytes over the peak bandwidth) over the time taken,
    in percent, and which of the two bounds it."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes
    least = max(t_flops, t_bytes)
    return {
        "pct": 100.0 * least / seconds,
        "bound": "compute" if t_flops >= t_bytes else "memory",
        "least_s": least, "t_flops_s": t_flops, "t_bytes_s": t_bytes,
        "seconds": seconds,
    }
