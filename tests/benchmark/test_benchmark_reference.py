"""The GPT-2 family's plain reference (``benchmark/families/gpt2.py``)
against ``zoo.transformer_lm`` at a tiny size, and the controls: a lower precision in the program's place has to read worse
than the program does."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchmark_tiny_cells as tiny  # noqa: E402

from benchmark import reference  # noqa: E402
from benchmark.families import gpt2  # noqa: E402
from benchmark.drive_train import compare, worst_leaf_gap  # noqa: E402

W = gpt2.widths(tiny.CONFIG)


@pytest.fixture(scope="module")
def weights():
    return gpt2.make_weights(W, 2**31 + 9)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, W["vocab"], (3, W["seq"])).astype(np.int32)


def test_weights_are_a_function_of_the_seed_alone(weights):
    again = gpt2.make_weights(W, 2**31 + 9)
    other = gpt2.make_weights(W, 2**31 + 10)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), weights, again)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.array_equal(weights["0"]["tokens"], other["0"]["tokens"]))
    n = sum(x.size for x in jax.tree.leaves(weights))
    assert n == gpt2.param_count(W)["total"]


def test_forward_matches_the_program_s_model(weights, tokens):
    model = gpt2.build_program_model(W, weights, tiny.TRAIN)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.predict(tokens))
        got = np.stack([
            np.asarray(gpt2.logits(
                weights, gpt2.hidden(weights, jnp.asarray(row), W), W))
            for row in tokens])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_loss_and_gradients_match_the_program_s(weights, tokens):
    from distkeras_tpu.ops.losses import next_token_crossentropy

    model = gpt2.build_program_model(W, weights, tiny.TRAIN)

    def program_loss(params):
        y, _ = model.apply(params, model.state, jnp.asarray(tokens), train=True)
        return next_token_crossentropy(y, jnp.asarray(tokens))

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.value_and_grad(program_loss)(weights)
        got_loss, got_grads = gpt2.batch_grads(weights, tokens, W)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    gap = worst_leaf_gap(np.asarray(reference.leaf_norms(got_grads)),
                         np.asarray(reference.leaf_norms(want_grads)))
    assert gap < 1e-4


def test_adam_step_is_optax_s(weights, tokens):
    import optax

    with jax.default_matmul_precision("highest"):
        _, grads = gpt2.batch_grads(weights, tokens, W)
    opt = optax.adam(3e-3)
    updates, _ = opt.update(grads, opt.init(weights), weights)
    want = optax.apply_updates(weights, updates)
    zeros = jax.tree.map(jnp.zeros_like, weights)
    got, _, _, _ = reference.adam_step(
        jax.tree.map(jnp.array, weights), grads, zeros,
        jax.tree.map(jnp.zeros_like, weights), jnp.zeros(()), lr=3e-3)
    close = jax.tree.map(lambda a, b: bool(jnp.allclose(a, b, atol=1e-7)), got, want)
    assert all(jax.tree.leaves(close))


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_training_control_in_int8_comes_out_not_correct(seed):
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, W["vocab"], (2, W["seq"])).astype(np.int32)
               for _ in range(3)]
    with jax.default_matmul_precision("highest"):
        ref = gpt2.train_readings(W, seed, batches, 3e-3)
        low = gpt2.train_readings(W, seed, batches, 3e-3, "int8")
    ok, rows = compare(low, ref, tiny.TRAIN["check"]["limits"])
    assert not ok, rows
    same, _ = compare(ref, ref, tiny.TRAIN["check"]["limits"])
    assert same


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_serving_control_in_int4_reads_a_wider_gap_than_int8(seed):
    """At each position of the same prompt and tokens, the token that int4
    weights put first lies further below the reference's best than the one
    int8 weights put first."""
    weights = gpt2.make_weights(W, seed)
    seq = np.random.default_rng(seed).integers(0, W["vocab"], 60)
    with jax.default_matmul_precision("highest"):
        served, int4 = gpt2.token_gaps(weights, W, seq, 12, control="w_int4")
    assert served.shape == int4.shape == (48,)
    assert (served >= 0).all() and (int4 >= 0).all()
    assert int4.max() > tiny.CONFIG["serving"]["check"]["gap_limit"]
