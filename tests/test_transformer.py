"""Transformer model family: layers, serialization, convergence, and the
ring-attention attachment for sequence-parallel execution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from distkeras_tpu import SingleTrainer
from distkeras_tpu.data import loaders
from distkeras_tpu.data.transformers import OneHotTransformer
from distkeras_tpu.evaluators import AccuracyEvaluator
from distkeras_tpu.models import zoo
from distkeras_tpu.models.layers import (
    Embedding,
    GlobalAvgPool1D,
    LayerNorm,
    TransformerBlock,
)
from distkeras_tpu.models.sequential import Sequential
from distkeras_tpu.parallel.ring_attention import attach_ring_attention
from distkeras_tpu.predictors import ModelPredictor


def test_embedding_and_layernorm_shapes():
    model = Sequential([Embedding(vocab_size=16, dim=8), LayerNorm()])
    model.build((12,), seed=0)
    x = np.random.default_rng(0).integers(0, 16, (3, 12))
    y, _ = model.apply(model.params, model.state, jnp.asarray(x))
    assert y.shape == (3, 12, 8)
    # layernorm'd features: ~zero mean, ~unit variance per position
    np.testing.assert_allclose(np.asarray(y).mean(-1), 0.0, atol=1e-5)


def test_transformer_classifier_forward_and_roundtrip():
    model = zoo.transformer_classifier(
        vocab_size=32, seq_len=16, d_model=32, num_heads=2, depth=2,
        num_classes=3,
    )
    x = np.random.default_rng(0).integers(0, 32, (4, 16))
    y, _ = model.apply(model.params, model.state, jnp.asarray(x))
    assert y.shape == (4, 3)
    np.testing.assert_allclose(np.asarray(y).sum(-1), 1.0, atol=1e-5)

    clone = Sequential.from_config(model.get_config())
    clone.build((16,), seed=0)
    clone.set_weights(model.get_weights())
    y2, _ = clone.apply(clone.params, clone.state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-6)


def test_transformer_classifier_converges():
    ds = loaders.synthetic_sequences(n=2048, seq_len=32, vocab=16, seed=0)
    ds = OneHotTransformer(2, output_col="label_onehot").transform(ds)
    train, test = ds.split(0.85, seed=0)
    t = SingleTrainer(
        zoo.transformer_classifier(
            vocab_size=16, seq_len=32, d_model=32, num_heads=2, depth=1
        ),
        "adam",
        "categorical_crossentropy",
        batch_size=64,
        num_epoch=3,
        label_col="label_onehot",
    )
    trained = t.train(train, shuffle=True)
    acc = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(trained, batch_size=256).predict(test)
    )
    assert acc > 0.95, acc


@pytest.mark.slow
def test_attach_ring_attention_walks_blocks():
    model = zoo.transformer_classifier(
        vocab_size=16, seq_len=64, d_model=32, num_heads=2, depth=3
    )
    mesh = Mesh(np.array(jax.devices()), ("seq",))
    n = attach_ring_attention(model, mesh)
    assert n == 3  # one MHSA per block, found through sublayers()

    # forward with the sequence sharded 8 ways matches the dense forward
    x = np.random.default_rng(1).integers(0, 16, (2, 64))
    dense_model = zoo.transformer_classifier(
        vocab_size=16, seq_len=64, d_model=32, num_heads=2, depth=3
    )
    dense_model.set_weights(model.get_weights())
    y_ring, _ = model.apply(model.params, model.state, jnp.asarray(x))
    y_dense, _ = dense_model.apply(
        dense_model.params, dense_model.state, jnp.asarray(x)
    )
    np.testing.assert_allclose(
        np.asarray(y_ring), np.asarray(y_dense), atol=2e-5
    )


def test_synthetic_sequences_learnable_structure():
    ds = loaders.synthetic_sequences(n=100, seq_len=32, vocab=16, seed=1)
    x, y = ds["features"], ds["label"]
    assert x.shape == (100, 32) and x.min() >= 1 and x.max() < 16
    for i in range(10):
        marker = y[i] + 1
        assert (x[i] == marker).sum() >= 2  # the class marker is planted

# ------------------------------------------------ the block is written once


class _Seam:
    """Counts ``TransformerBlock.forward`` calls and the ``LayerNorm``
    applications inside and outside them while a program is traced."""

    def __init__(self, monkeypatch):
        self.forwards = self.inside = self.outside = self.depth = 0
        forward, norm = TransformerBlock.forward, LayerNorm.apply

        def counted_forward(blk, *a, **kw):
            self.forwards += 1
            self.depth += 1
            try:
                return forward(blk, *a, **kw)
            finally:
                self.depth -= 1

        def counted_norm(ln, *a, **kw):
            if self.depth:
                self.inside += 1
            else:
                self.outside += 1
            return norm(ln, *a, **kw)

        monkeypatch.setattr(TransformerBlock, "forward", counted_forward)
        monkeypatch.setattr(LayerNorm, "apply", counted_norm)

    def reset(self):
        self.forwards = self.inside = self.outside = 0


def _seam_lm(d_model=32, seq_len=32):
    return zoo.transformer_lm(vocab_size=61, seq_len=seq_len, d_model=d_model,
                              num_heads=2, depth=2, seed=0)


def _trace_apply(seam):
    lm = _seam_lm()
    x = jnp.zeros((2, 32), jnp.int32)
    seam.reset()
    jax.make_jaxpr(
        lambda p: lm.apply(p, lm.state, x, train=True,
                           rng=jax.random.PRNGKey(0))[0]
    )(lm.params)


def _solo(seam):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    lm = _seam_lm()
    gen = CachedSequenceGenerator(lm)
    bp = [(lm.params[str(bi)], None) for (_, bi, _, _) in gen._stages]
    caches = [(jnp.zeros((2, 32, 2, 16)), jnp.zeros((2, 32, 2, 16)))] * 2
    seam.reset()
    return gen, bp, caches


def _trace_solo_prefill(seam):
    gen, bp, caches = _solo(seam)
    jax.make_jaxpr(lambda x: gen._prefill(bp, caches, x)[0])(
        jnp.zeros((2, 5, 32)))


def _trace_solo_decode(seam):
    gen, bp, caches = _solo(seam)
    jax.make_jaxpr(
        lambda x: gen._stages_decode(bp, caches, x, 5, jnp.arange(32) <= 5)[0]
    )(jnp.zeros((2, 32)))


def _stepper(seam, kernel=False, spec=False, **kw):
    from distkeras_tpu.serving.engine import DecodeStepper, NgramDrafter

    lm = _seam_lm(256, 48) if kernel else _seam_lm()
    if spec:
        kw.update(speculative=NgramDrafter(), draft_k=3)
    st = DecodeStepper(lm, num_slots=2, **kw)
    if kw.get("paged"):
        want = "kernel" if kernel else "gather"
        assert st.attention.startswith(want), st.attention
    prompt = np.tile(np.array([3, 5, 7], np.int32), 3)  # 9 tokens
    return st, prompt


def _trace_step(seam, **kw):
    st, prompt = _stepper(seam, **kw)
    st.admit(0, prompt, max_new=4)
    seam.reset()
    st.step(np.array([True, False]))


def _trace_chunk(seam, **kw):
    st, prompt = _stepper(seam, **kw)
    assert st.begin_admit(0, prompt, max_new=4) == 8
    seam.reset()
    assert st.prefill_chunk(0, 4) == 4  # one call of the 4-token bucket


def _trace_verify(seam, **kw):
    st, prompt = _stepper(seam, spec=True, **kw)
    st.admit(0, prompt, max_new=6)
    seam.reset()
    _, _, used_verify = st.spec_step(np.array([True, False]), [(prompt, []), None])
    assert used_verify


PAGED = dict(paged=True, page_size=4)
PROGRAMS = {
    "apply": _trace_apply,
    "solo_prefill": _trace_solo_prefill,
    "solo_decode_step": _trace_solo_decode,
    "dense_step": _trace_step,
    "dense_chunk": _trace_chunk,
    "dense_verify": _trace_verify,
    "paged_step_gather": lambda s: _trace_step(s, **PAGED),
    "paged_step_kernel": lambda s: _trace_step(s, kernel=True, **PAGED),
    "paged_chunk": lambda s: _trace_chunk(s, **PAGED),
    "paged_verify": lambda s: _trace_verify(s, **PAGED),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_program_runs_the_one_block_body(program, monkeypatch):
    """Tracing a program family calls ``TransformerBlock.forward`` once a
    block, and no block norm is applied outside it: there is no second
    body. (What the arithmetic gives is pinned by the identity tests:
    slot to solo, paged to dense, kernel to gather, ``tp:2`` to solo.)"""
    seam = _Seam(monkeypatch)
    PROGRAMS[program](seam)
    assert seam.forwards == 2, "once a block"
    assert seam.inside == 4, "ln1 and ln2 of each block, inside forward"
    assert seam.outside <= 1, "the final norm alone is applied outside"


@pytest.mark.parametrize("bits", [None, 8, 16],
                         ids=["float32", "int8", "bfloat16"])
def test_forward_with_a_plain_causal_attend_is_apply(bits):
    """``forward`` under an ``attend`` written out here (causal softmax
    over the tokens' own keys, accumulated in float32) equals ``apply``
    to the bit. ``bfloat16``: the weights as ``quantize_model(bits=16)``
    leaves them (``"bo"`` and the norms stay float32) under bfloat16
    activations; the bias is cast to the product's dtype, so the
    attention branch stays bfloat16."""
    from distkeras_tpu.ops.quantization import quantize_model

    lm = _seam_lm()
    if bits:
        lm = quantize_model(lm, bits=bits)
    blk, p = lm.layers[1], lm.params["1"]
    dtype = jnp.bfloat16 if bits == 16 else jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32), dtype)

    def attend(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / q.shape[-1] ** 0.5)
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    want, _ = blk.apply(p, lm.state["1"], x)
    got = blk.forward(p, x, attend)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    if bits == 16:
        assert p["mhsa"]["bo"].dtype == jnp.float32
        o = blk.mhsa.forward(p["mhsa"], x, attend)
        assert o.dtype == jnp.bfloat16
