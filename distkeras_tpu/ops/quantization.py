"""Weight-only int8 / int4 quantization for the serving path.

No reference counterpart (SURVEY §3.4: the reference ships no native/perf
tier at all); this is a TPU-first lever. Decode and batched inference are
memory-bound — the v5e HBM streams every weight matrix once per token —
so halving/quartering weight bytes moves tokens/sec directly, while the
MXU still computes in the activation dtype (the int8 weights upcast in
registers; XLA fuses the cast into the matmul's operand read).

Scheme: symmetric per-output-channel scales. An int8-quantized matrix is
the pytree `{"q": int8 (in, out), "s": f32 (out,)}` with
`w ≈ q * s[None, :]`. Because the scale is per OUTPUT column it commutes
through the matmul:

    x @ (q * s[None, :]) == (x @ q) * s[None, :]

so `qmatmul` never materializes the dequantized matrix — the int8 bytes
are what leaves HBM. Training on a quantized tree is unsupported (no
gradients through round()); quantize for serving, keep the f32 master
for training/checkpoints.

int4 (``bits=4``) halves the weight bytes again: values clip to [-7, 7]
and pack two-per-byte along the IN dimension (`Int4Weight`, a registered
pytree whose static aux carries the logical row count). This build's JAX
cannot materialize native ``jnp.int4`` arrays (convert_element_type on S4
recurses — re-checked 2026-08-01), so the packing is explicit int8 nibble
arithmetic; the unpack (two shifts + an interleave) fuses into the
matmul's operand read under XLA, and the packed bytes are what HBM
streams. Eighth-width weights cost accuracy headroom — the tests pin how
much on the zoo models; prefer int8 unless the bytes matter more.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: weight-matrix key names eligible for quantization when walking a
#: params tree: Dense kernels and the attention projections. Biases, LN
#: gains, embeddings, and conv kernels stay f32 (they are a rounding
#: error of the bytes; embeddings are gathers, not matmuls).
DEFAULT_QUANT_KEYS = ("kernel", "wq", "wk", "wv", "wo")


def quantize_int8(w):
    """f32 (in, out) -> {"q": int8, "s": f32 (out,)}, symmetric per-column."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"quantize_int8 expects a 2-D matrix; got {w.shape}")
    s = jnp.max(jnp.abs(w), axis=0) / 127.0
    s = jnp.where(s == 0, jnp.float32(1.0), s).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / s[None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


@jax.tree_util.register_pytree_node_class
class Int4Weight:
    """Packed int4 weight: ``q4`` int8 (ceil(in/2), out) holding two
    4-bit values per byte (row 2i in the low nibble, row 2i+1 in the
    high), ``s`` f32 (out,) per-column scales. ``rows`` (the logical in
    dimension) rides the pytree's STATIC aux data, so it stays a Python
    int under jit and can shape the unpack."""

    def __init__(self, q4, s, rows):
        self.q4, self.s, self.rows = q4, s, rows

    def tree_flatten(self):
        return (self.q4, self.s), self.rows

    @classmethod
    def tree_unflatten(cls, rows, children):
        return cls(*children, rows=rows)


def quantize_int4(w):
    """f32 (in, out) -> Int4Weight, symmetric per-column, range [-7, 7].
    Odd in dims pad one zero row before packing (sliced off at unpack)."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"quantize_int4 expects a 2-D matrix; got {w.shape}")
    rows, cols = w.shape
    s = jnp.max(jnp.abs(w), axis=0) / 7.0
    s = jnp.where(s == 0, jnp.float32(1.0), s).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / s[None, :]), -7, 7).astype(jnp.int8)
    if rows % 2:
        q = jnp.concatenate([q, jnp.zeros((1, cols), jnp.int8)], axis=0)
    packed = jnp.bitwise_or(
        jnp.left_shift(q[1::2], 4), jnp.bitwise_and(q[0::2], 0x0F)
    ).astype(jnp.int8)
    return Int4Weight(packed, s, rows)


def _unpack_int4(w):
    """Int4Weight -> int8 (rows, out). Low nibble sign-extends by the
    shift-up/arithmetic-shift-down trick; the high nibble's arithmetic
    right shift sign-extends directly."""
    p = w.q4
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    inter = jnp.stack([lo, hi], axis=1).reshape(-1, p.shape[1])
    return inter[: w.rows]


def is_quantized(w) -> bool:
    return isinstance(w, Int4Weight) or (
        isinstance(w, dict) and "q" in w and "s" in w
    )


def dequantize(w):
    """Quantized form -> f32 matrix (testing/debugging; serving never
    calls it)."""
    if isinstance(w, Int4Weight):
        return _unpack_int4(w).astype(jnp.float32) * w.s[None, :]
    return w["q"].astype(jnp.float32) * w["s"][None, :]


def qshape(w):
    """Logical shape of a weight that may or may not be quantized."""
    if isinstance(w, Int4Weight):
        return (w.rows, w.q4.shape[1])
    return w["q"].shape if is_quantized(w) else w.shape


def qmatmul(x, w):
    """x @ w for plain or quantized w, in x.dtype, without materializing
    the dequantized matrix (the per-out-column scale commutes)."""
    if isinstance(w, Int4Weight):
        return (x @ _unpack_int4(w).astype(x.dtype)) * w.s.astype(x.dtype)
    if is_quantized(w):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w.astype(x.dtype)


def quantize_params(params, keys=DEFAULT_QUANT_KEYS, bits=8):
    """Walk a params pytree; replace eligible 2-D float leaves (dict key in
    ``keys``) with their ``bits``-wide form (8 or 4). Already-quantized
    entries pass through unchanged — idempotent, and a tree quantized at
    one width is NOT re-quantized at another (round() already destroyed
    the master; re-quantize from the f32 original instead). Returns a new
    tree; the input is not mutated."""
    if bits == 16:
        return cast_params_bf16(params)
    if bits not in (8, 4):
        raise ValueError(f"bits must be 16, 8 or 4; got {bits}")
    quant = quantize_int8 if bits == 8 else quantize_int4
    if is_quantized(params):
        return params
    if isinstance(params, dict):
        out = {}
        for k, v in params.items():
            if (
                k in keys
                and hasattr(v, "ndim")
                and getattr(v, "ndim", 0) == 2
                and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
            ):
                out[k] = quant(v)
            else:
                out[k] = quantize_params(v, keys, bits)
        return out
    if isinstance(params, (list, tuple)):
        return type(params)(quantize_params(v, keys, bits) for v in params)
    return params


def cast_params_bf16(params):
    """The 16-bit serving tree (``bits=16``): every floating leaf of two or
    more dimensions cast to bfloat16, which takes in every matrix that
    ``bits=8`` would quantize, the embedding tables and expert weights
    stacked ``(E, in, out)``; vectors (biases, norm gains) keep their
    dtype, and a leaf that is quantized already passes through. Exact for
    weights that are bfloat16-representable. Returns a new tree."""
    if is_quantized(params):
        return params
    if isinstance(params, dict):
        return {k: cast_params_bf16(v) for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        return type(params)(cast_params_bf16(v) for v in params)
    if getattr(params, "ndim", 0) >= 2 and jnp.issubdtype(
            params.dtype, jnp.floating):
        return params.astype(jnp.bfloat16)
    return params


def is_serving_tree(params) -> bool:
    """A tree that went through ``quantize_model``: it holds a quantized
    matrix, or a bfloat16 one (``bits=16``). Serve-only."""
    return bool(count_quantized(params)) or any(
        getattr(leaf, "ndim", 0) >= 2 and leaf.dtype == jnp.bfloat16
        for leaf in jax.tree_util.tree_leaves(params)
    )


def count_quantized(params) -> int:
    """Number of quantized matrices in a tree (tests/reporting)."""
    if is_quantized(params):
        return 1
    if isinstance(params, dict):
        return sum(count_quantized(v) for v in params.values())
    if isinstance(params, (list, tuple)):
        return sum(count_quantized(v) for v in params)
    return 0


def quantize_model(model, keys=DEFAULT_QUANT_KEYS, bits=8):
    """Switch a built model's params to the int8/int4 serving tree IN
    PLACE and return the model (chainable). Serve-only: trainers reject
    quantized trees (no gradients through round()); quantize a copy —
    ``quantize_model(m.copy())`` — if the original must keep training.
    ``bits=16`` casts to bfloat16 instead (``cast_params_bf16``)."""
    if getattr(model, "params", None) is None:
        raise ValueError("quantize_model needs a BUILT model (params set)")
    model.params = quantize_params(model.params, keys, bits)
    return model
