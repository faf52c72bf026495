"""Weight-only int8 serving tier (ops/quantization.py).

The reference has no serving/perf tier at all (SURVEY §3.4); this one is
TPU-first — decode is memory-bound, int8 weights quarter the HBM bytes
per token while the matmul still runs in the activation dtype. These
tests pin the numerics off-chip; the bytes-to-tokens/sec claim is the
chip's to measure (not measured on the chip by any cell of
`BENCHMARK.json` yet).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import zoo
from distkeras_tpu.ops.quantization import (
    Int4Weight,
    count_quantized,
    dequantize,
    is_quantized,
    qmatmul,
    qshape,
    quantize_int4,
    quantize_int8,
    quantize_model,
    quantize_params,
)
from distkeras_tpu.predictors import CachedSequenceGenerator, SequenceGenerator
from distkeras_tpu.utils.serialization import deserialize_model, serialize_model


def f32_and_quantized_lm(**kw):
    lm = zoo.transformer_lm(**kw)
    lm_q = quantize_model(lm.copy())
    return lm, lm_q


def test_roundtrip_error_within_half_scale():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    qw = quantize_int8(w)
    assert qw["q"].dtype == jnp.int8 and qw["s"].shape == (32,)
    err = np.abs(np.asarray(dequantize(qw)) - np.asarray(w))
    half_scale = np.asarray(qw["s"]) / 2 + 1e-7
    assert (err <= half_scale[None, :]).all()


def test_qmatmul_equals_dequantized_matmul():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
    qw = quantize_int8(w)
    np.testing.assert_allclose(
        np.asarray(qmatmul(x, qw)),
        np.asarray(x @ dequantize(qw)),
        atol=1e-4,
    )
    # plain weights pass through unchanged
    np.testing.assert_allclose(
        np.asarray(qmatmul(x, w)), np.asarray(x @ w), atol=0
    )


def test_quantize_params_walks_exactly_the_matmul_weights():
    lm = zoo.transformer_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    q = quantize_params(lm.params)
    # per block: wq wk wv wo + fc1/fc2 kernels = 6; plus the vocab head
    assert count_quantized(q) == 2 * 6 + 1
    # embeddings, LN gains, biases stay f32
    assert not is_quantized(q["0"]["tokens"])
    # idempotent
    assert count_quantized(quantize_params(q)) == count_quantized(q)
    # the source tree is not mutated
    assert count_quantized(lm.params) == 0


def test_classifier_argmax_survives_quantization():
    m = zoo.mnist_mlp(hidden=64, seed=0)
    rng = np.random.default_rng(2)
    X = rng.standard_normal((512, 784)).astype(np.float32)
    logits_f = m.predict(X)
    quantize_model(m)
    logits_q = m.predict(X)
    agree = (logits_f.argmax(1) == logits_q.argmax(1)).mean()
    assert agree >= 0.97, agree  # measured 0.994 on the pinned seed


def test_lm_logits_argmax_survives_quantization():
    """Teacher-forced per-position argmax on a RANDOM model — near-flat
    logits, the worst case for agreement; trained models have margins."""
    lm, lm_q = f32_and_quantized_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 97, (4, 48)))
    lf, _ = lm.apply(lm.params, lm.state, x, train=False)
    lq, _ = lm_q.apply(lm_q.params, lm_q.state, x, train=False)
    agree = (
        np.asarray(lf).argmax(-1) == np.asarray(lq).argmax(-1)
    ).mean()
    assert agree >= 0.9, agree  # measured 0.979 on the pinned seed


def test_cached_decode_runs_quantized_and_tracks_f32():
    lm, lm_q = f32_and_quantized_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, 97, (4, 8))
    out_f = CachedSequenceGenerator(lm).generate(prompts, 16)
    out_q = CachedSequenceGenerator(lm_q).generate(prompts, 16)
    # greedy divergence cascades after a first flipped token, so the bar
    # is deliberately loose; the logit-level bar above is the tight one
    agree = (out_f[:, 8:] == out_q[:, 8:]).mean()
    assert agree >= 0.5, agree  # measured 0.859 on the pinned seed
    # cached and uncached generators agree with each other when BOTH are
    # quantized (the decode path's qmatmul sites match layer.apply's)
    out_q_uncached = SequenceGenerator(lm_q).generate(prompts, 16)
    np.testing.assert_array_equal(out_q, out_q_uncached)


def test_trainers_reject_quantized_tree():
    from distkeras_tpu import SingleTrainer

    m = quantize_model(zoo.mnist_mlp(hidden=32, seed=0))
    with pytest.raises(ValueError, match="quantized"):
        SingleTrainer(m, "sgd", loss="categorical_crossentropy")


def test_serialize_rejects_quantized_tree():
    m = quantize_model(zoo.mnist_mlp(hidden=32, seed=0))
    with pytest.raises(ValueError, match="LOAD-TIME"):
        serialize_model(m)


def test_quantize_model_requires_built():
    from distkeras_tpu.models.sequential import Sequential
    from distkeras_tpu.models.layers import Dense

    with pytest.raises(ValueError, match="BUILT"):
        quantize_model(Sequential([Dense(4)]))


def test_bf16_kv_cache_decode():
    """Opt-in bf16 K/V caches (the other big HBM stream of the serving
    path): greedy output tracks f32 caches, the cache dtype is honored,
    and the full serving bundle (int8 weights + bf16 kv) decodes."""
    lm, lm_q = f32_and_quantized_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, 97, (4, 8))
    out_f = CachedSequenceGenerator(lm).generate(prompts, 16)
    out_bf = CachedSequenceGenerator(lm, kv_dtype=jnp.bfloat16).generate(
        prompts, 16
    )
    agree = (out_f[:, 8:] == out_bf[:, 8:]).mean()
    assert agree >= 0.9, agree  # measured 1.0 on the pinned seed
    out_bundle = CachedSequenceGenerator(
        lm_q, kv_dtype=jnp.bfloat16
    ).generate(prompts, 16)
    assert out_bundle.shape == out_f.shape
    agree_b = (out_f[:, 8:] == out_bundle[:, 8:]).mean()
    assert agree_b >= 0.5, agree_b  # int8-dominated; measured 0.859


@pytest.mark.parametrize("rows", [64, 63])
def test_int4_pack_roundtrip_is_exact_on_int4_values(rows):
    """Values already on the int4 grid survive pack -> unpack bit-exactly
    (the nibble arithmetic itself, incl. sign extension and the odd-row
    pad, loses nothing; only round() loses information)."""
    rng = np.random.default_rng(10)
    grid = rng.integers(-7, 8, (rows, 32)).astype(np.float32)
    qw = quantize_int4(jnp.asarray(grid))
    assert isinstance(qw, Int4Weight)
    assert qw.q4.shape == ((rows + 1) // 2, 32) and qw.q4.dtype == jnp.int8
    assert qshape(qw) == (rows, 32)
    scale = np.asarray(qw.s)  # max|col| / 7; grid values are multiples
    np.testing.assert_allclose(
        np.asarray(dequantize(qw)), grid, atol=1e-5
    )
    assert scale.shape == (32,)


def test_int4_roundtrip_error_within_half_scale():
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    qw = quantize_int4(w)
    err = np.abs(np.asarray(dequantize(qw)) - np.asarray(w))
    half_scale = np.asarray(qw.s) / 2 + 1e-7
    assert (err <= half_scale[None, :]).all()


@pytest.mark.parametrize("rows", [64, 63])
def test_int4_qmatmul_equals_dequantized_matmul(rows):
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.standard_normal((rows, 48)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8, rows)).astype(np.float32))
    qw = quantize_int4(w)
    np.testing.assert_allclose(
        np.asarray(qmatmul(x, qw)),
        np.asarray(x @ dequantize(qw)),
        atol=1e-4,
    )
    # and under jit, with Int4Weight riding the params pytree (rows is
    # static aux data, so the unpack shapes are concrete at trace time)
    import jax

    jitted = jax.jit(qmatmul)
    np.testing.assert_allclose(
        np.asarray(jitted(x, qw)), np.asarray(qmatmul(x, qw)), atol=1e-6
    )


def test_int4_tree_walk_and_rejections():
    lm = zoo.transformer_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    q = quantize_params(lm.params, bits=4)
    assert count_quantized(q) == 2 * 6 + 1
    assert is_quantized(q["2"]["mhsa"]["wq"])
    # a tree quantized at one width does not re-quantize at another
    assert count_quantized(quantize_params(q, bits=8)) == count_quantized(q)
    with pytest.raises(ValueError, match="bits"):
        quantize_params(lm.params, bits=2)
    from distkeras_tpu import SingleTrainer
    from distkeras_tpu.utils.serialization import serialize_model

    m4 = quantize_model(zoo.mnist_mlp(hidden=32, seed=0), bits=4)
    with pytest.raises(ValueError, match="quantized"):
        SingleTrainer(m4, "sgd", loss="categorical_crossentropy")
    with pytest.raises(ValueError, match="LOAD-TIME"):
        serialize_model(m4)


def test_int4_classifier_argmax_mostly_survives():
    """Eighth-width weights on a random-init MLP: the agreement bar is
    necessarily looser than int8's 0.97 (half the mantissa of nothing —
    these are near-flat logits); trained models hold much higher."""
    m = zoo.mnist_mlp(hidden=64, seed=0)
    rng = np.random.default_rng(13)
    X = rng.standard_normal((512, 784)).astype(np.float32)
    logits_f = m.predict(X)
    quantize_model(m, bits=4)
    logits_q = m.predict(X)
    agree = (logits_f.argmax(1) == logits_q.argmax(1)).mean()
    assert agree >= 0.8, agree  # measured 0.934 on the pinned seed


def test_int4_cached_decode_runs_and_matches_uncached():
    lm = zoo.transformer_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    lm4 = quantize_model(lm.copy(), bits=4)
    rng = np.random.default_rng(14)
    prompts = rng.integers(0, 97, (4, 8))
    out_c = CachedSequenceGenerator(lm4).generate(prompts, 16)
    out_u = SequenceGenerator(lm4).generate(prompts, 16)
    # both serving paths hit the same qmatmul sites: identical output
    np.testing.assert_array_equal(out_c, out_u)
    assert out_c.shape == (4, 24)


@pytest.mark.slow
def test_int4_real_digits_accuracy():
    """End-to-end on REAL data: int4 serves the trained digits classifier
    within two points of f32 (measured: f32 0.9481, int4 0.9407 on the
    pinned seed) — the honest cost of eighth-width weights."""
    from distkeras_tpu import AccuracyEvaluator, ModelPredictor, SingleTrainer
    from distkeras_tpu.data.loaders import digits
    from distkeras_tpu.data.transformers import (
        MinMaxTransformer,
        OneHotTransformer,
    )
    from distkeras_tpu.models.zoo import digits_mlp

    ds = digits()
    ds = MinMaxTransformer(0, 1, o_min=0, o_max=16).transform(ds)
    ds = OneHotTransformer(10, output_col="label_onehot").transform(ds)
    train, test = ds.split(0.85, seed=7)
    trained = SingleTrainer(
        digits_mlp(seed=0), "adam", loss="categorical_crossentropy",
        label_col="label_onehot", batch_size=32, num_epoch=6, seed=0,
    ).train(train)
    acc_f = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(trained, batch_size=256).predict(test)
    )
    acc_4 = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(
            quantize_model(trained.copy(), bits=4), batch_size=256
        ).predict(test)
    )
    assert acc_f > 0.9, acc_f
    assert acc_4 >= acc_f - 0.02, (acc_f, acc_4)


@pytest.mark.slow
def test_int8_real_digits_accuracy_over_mesh():
    """End-to-end on REAL data: train f32 on the in-repo digits, quantize
    a serving copy, predict through the data-parallel mesh predictor —
    the int8 tree replicates over the mesh like any pytree, and accuracy
    must not drop more than a point (measured: 0.9481 == 0.9481)."""
    from distkeras_tpu import AccuracyEvaluator, ModelPredictor, SingleTrainer
    from distkeras_tpu.data.loaders import digits
    from distkeras_tpu.data.transformers import (
        MinMaxTransformer,
        OneHotTransformer,
    )
    from distkeras_tpu.models.zoo import digits_mlp

    ds = digits()
    ds = MinMaxTransformer(0, 1, o_min=0, o_max=16).transform(ds)
    ds = OneHotTransformer(10, output_col="label_onehot").transform(ds)
    train, test = ds.split(0.85, seed=7)
    trained = SingleTrainer(
        digits_mlp(seed=0), "adam", loss="categorical_crossentropy",
        label_col="label_onehot", batch_size=32, num_epoch=6, seed=0,
    ).train(train)
    acc_f = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(trained, batch_size=256).predict(test)
    )
    acc_q = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(
            quantize_model(trained.copy()), batch_size=256,
            data_parallel=True,
        ).predict(test)
    )
    assert acc_f > 0.9, acc_f
    assert acc_q >= acc_f - 0.01, (acc_f, acc_q)


# ------------------------------------------------------------ serving bundles


@pytest.mark.parametrize("bits", [8, 4])
def test_serving_bundle_roundtrip_preserves_predictions(tmp_path, bits):
    """save/load of a quantized model is the DELIBERATE persistence path
    (serialize_model still rejects quantized trees): the loaded model
    predicts identically to the in-memory quantized one and decodes
    through the cached serving path."""
    from distkeras_tpu.utils.serialization import (
        load_serving_bundle,
        save_serving_bundle,
    )

    lm = zoo.transformer_lm(
        vocab_size=97, d_model=32, depth=2, seq_len=48, num_heads=4, seed=0
    )
    lm_q = quantize_model(lm.copy(), bits=bits)
    path = str(tmp_path / f"lm_int{bits}.dkt")
    save_serving_bundle(path, lm_q)
    served = load_serving_bundle(path)
    assert count_quantized(served.params) == count_quantized(lm_q.params)
    rng = np.random.default_rng(20)
    x = rng.integers(0, 97, (4, 48))
    np.testing.assert_allclose(
        np.asarray(served(x)), np.asarray(lm_q(x)), atol=1e-6
    )
    prompts = rng.integers(0, 97, (2, 8))
    np.testing.assert_array_equal(
        CachedSequenceGenerator(served).generate(prompts, 8),
        CachedSequenceGenerator(lm_q).generate(prompts, 8),
    )
    # int8 on-disk bytes beat the f32 master's — not by the full 4x on
    # THIS toy model, where the (deliberately unquantized) f32 embedding
    # tables are a big share of the bytes; measured 66,074 vs 140,801
    if bits == 8:
        master = serialize_model(lm)
        import os

        assert os.path.getsize(path) < 0.5 * len(master)


def test_serving_bundle_rejections(tmp_path):
    from distkeras_tpu.utils.serialization import (
        deserialize_serving_bundle,
        serialize_serving_bundle,
        unpack_frame,
        pack_frame,
    )

    m = zoo.mnist_mlp(hidden=32, seed=0)
    with pytest.raises(ValueError, match="not quantized"):
        serialize_serving_bundle(m)
    # an f32 model frame is not a serving bundle
    with pytest.raises(ValueError, match="not a serving bundle"):
        deserialize_serving_bundle(serialize_model(m))
    # the loaded bundle stays serve-only
    mq = quantize_model(m)
    blob = serialize_serving_bundle(mq)
    served = deserialize_serving_bundle(blob)
    with pytest.raises(ValueError, match="LOAD-TIME"):
        serialize_model(served)
    from distkeras_tpu import SingleTrainer

    with pytest.raises(ValueError, match="quantized"):
        SingleTrainer(served, "sgd", loss="categorical_crossentropy")
    # a spliced payload from a different architecture is caught by the
    # structural check, not served silently
    from distkeras_tpu.utils.serialization import serialize_params

    other = quantize_model(zoo.mnist_mlp(hidden=64, seed=0))
    header, _ = unpack_frame(blob)
    spliced = pack_frame(
        {k: header[k] for k in ("spec", "input_shape", "serving")},
        serialize_params(other.params),
    )
    with pytest.raises(ValueError, match="mismatch"):
        deserialize_serving_bundle(spliced)


def test_serving_bundle_rejects_tampered_internals():
    """Validation reaches INSIDE quantized leaves: a broadcastable (1,)
    scale or a truncated int4 pack must be rejected at load, not serve
    silently-wrong predictions / crash mid-inference."""
    from distkeras_tpu.utils.serialization import (
        deserialize_model,
        deserialize_serving_bundle,
        pack_frame,
        serialize_params,
        serialize_serving_bundle,
        unpack_frame,
    )

    def resave(model_q, mutate):
        blob = serialize_serving_bundle(model_q)
        header, _ = unpack_frame(blob)
        params = {k: v for k, v in model_q.params.items()}
        mutate(params)
        return pack_frame(header, serialize_params(params))

    m8 = quantize_model(zoo.mnist_mlp(hidden=32, seed=0))
    first = next(k for k in m8.params if "kernel" in m8.params[k])

    def shrink_scale(p):
        leaf = dict(p[first])
        leaf["kernel"] = {
            "q": leaf["kernel"]["q"],
            "s": np.ones(1, np.float32),
        }
        p[first] = leaf

    with pytest.raises(ValueError, match="int8 internals"):
        deserialize_serving_bundle(resave(m8, shrink_scale))

    m4 = quantize_model(zoo.mnist_mlp(hidden=32, seed=0), bits=4)

    def truncate_q4(p):
        from distkeras_tpu.ops.quantization import Int4Weight

        leaf = dict(p[first])
        w = leaf["kernel"]
        leaf["kernel"] = Int4Weight(np.asarray(w.q4)[:5], w.s, w.rows)
        p[first] = leaf

    with pytest.raises(ValueError, match="int4 internals"):
        deserialize_serving_bundle(resave(m4, truncate_q4))

    # ... and the f32 loader names the right loader for serving frames
    with pytest.raises(ValueError, match="SERVING bundle"):
        deserialize_model(serialize_serving_bundle(m8))


def test_serving_bundle_rejects_wrong_dtypes():
    """Dtype is part of the quantized contract: an int32 q4's nibble
    sign-extension returns the whole packed byte, so wrong-dtype leaves
    must fail at load, not decode to garbage."""
    from distkeras_tpu.ops.quantization import Int4Weight
    from distkeras_tpu.utils.serialization import (
        deserialize_serving_bundle,
        pack_frame,
        serialize_params,
        serialize_serving_bundle,
        unpack_frame,
    )

    def resave(model_q, mutate):
        blob = serialize_serving_bundle(model_q)
        header, _ = unpack_frame(blob)
        params = {k: v for k, v in model_q.params.items()}
        mutate(params)
        return pack_frame(header, serialize_params(params))

    m4 = quantize_model(zoo.mnist_mlp(hidden=32, seed=0), bits=4)
    first = next(k for k in m4.params if "kernel" in m4.params[k])

    def widen_q4(p):
        leaf = dict(p[first])
        w = leaf["kernel"]
        leaf["kernel"] = Int4Weight(
            np.asarray(w.q4).astype(np.int32), w.s, w.rows
        )
        p[first] = leaf

    with pytest.raises(ValueError, match="int4 internals"):
        deserialize_serving_bundle(resave(m4, widen_q4))

    m8 = quantize_model(zoo.mnist_mlp(hidden=32, seed=0))

    def float_q(p):
        leaf = dict(p[first])
        leaf["kernel"] = {
            "q": np.asarray(leaf["kernel"]["q"]).astype(np.float32),
            "s": leaf["kernel"]["s"],
        }
        p[first] = leaf

    with pytest.raises(ValueError, match="int8 internals"):
        deserialize_serving_bundle(resave(m8, float_q))

    # NON-quantized leaves pin their dtype too (ADVICE r5): a crafted
    # bundle substituting a float64 bias would otherwise load cleanly
    # on a shape-only check — load-bearing now that the serving engine
    # boots straight from bundles on disk
    def widen_bias(p):
        leaf = dict(p[first])
        leaf["bias"] = np.asarray(leaf["bias"], np.float64)
        p[first] = leaf

    with pytest.raises(ValueError, match="dtype mismatch"):
        deserialize_serving_bundle(resave(m8, widen_bias))
