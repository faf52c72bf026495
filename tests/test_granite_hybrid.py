"""Mamba-2 layers beside grouped-query layers (``models/mamba2.py``
``Mamba2Block``, ``zoo.granite_hybrid_lm``) against the benchmark's independent
plain reference (``benchmark/families/granite_hybrid.py``: the recurrence as a
scan over positions) at a tiny size, seeded: the full forward; chunked prefill
and paged decode through the state a slot and the attention layers' pages; the
carried state and convolution tail across chunk and block boundaries; what
padding, an idle slot and a reused slot do to a state; the typed refusals; the
counters and the spans."""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import spec  # noqa: E402
from benchmark.layer_metrics import _program_spans  # noqa: E402
from distkeras_tpu.models import mamba2, zoo  # noqa: E402
from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock  # noqa: E402
from distkeras_tpu.models.mamba2 import Mamba2Block  # noqa: E402
from distkeras_tpu.models.mla_moe import BlockUnsupportedError  # noqa: E402
from distkeras_tpu.ops.quantization import quantize_model  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.engine import DecodeStepper  # noqa: E402

# hidden 32; 8 Mamba heads of 8 with a state of 16, blocks of 8 positions; 4
# query heads over 2 K/V heads of 8; two periods of a 3:1 pattern
CONFIG = {
    "family": "granite_hybrid",
    "vocab_size": 211, "max_position_embeddings": 128,
    "num_hidden_layers": 8, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 64,
    "layer_types": ["mamba", "mamba", "mamba", "attention"] * 2,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.125,
    "logits_scaling": 8, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "num_local_experts": 0,
    "assumed": {"initializer_range": 0.02},
    "serving": {"weight_bits": 16, "weight_bytes": 2, "kv_dtype": "bfloat16",
                "kv_bytes": 2, "num_slots": 4, "page_size": 8,
                "num_pages": 80, "queue_capacity": 64,
                # bfloat16 operands and a bfloat16 K/V cache against the
                # float32 reference, logits of size 0.001: six sound runs of
                # this tiny cell read 0 to 3.2e-6 (a served token is the
                # reference's best, or was within the operands' rounding of
                # it); a state that padding advances reads 7e-4 to 8e-4, a
                # state that no admission resets 0 to 6.7e-5 by seed (what a
                # slot inherits decays, and shows only where it turns a pick)
                "check": {"gap_limit": 2e-5}},
}
# the same mechanisms with K/V heads of 64 that fill 128 lanes side by side,
# as the published model's do (8 of them; here 2): hidden 256, 4 query heads
# over 2 K/V heads of 64, 8 Mamba heads of 64, one Mamba and one attention layer
KERNEL_CONFIG = {
    **CONFIG, "hidden_size": 256, "num_hidden_layers": 2,
    "layer_types": ["mamba", "attention"], "mamba_d_head": 64,
    "shared_intermediate_size": 128, "attention_multiplier": 1 / 64}
STATE = (8, 8, 16)   # a Mamba layer's state a slot
TAIL = (3, 64 + 32)  # ... and its convolution's tail

# float32 weights and a float32 cache on both sides, every product at
# precision HIGHEST (the CPU's float32 either way): logits of size 0.005 (an
# embedding of 0.02 / 12 against a unit stream, over logits_scaling 8) read
# 1.6e-9 to 2.6e-9 apart between the chunk form with the one-token update and
# the scan over positions; a state rounded to bfloat16 moves them by 2e-7
# after 12 steps, 7e-7 after 60 and 2e-6 after 100
LOGIT_TOL = 2e-8


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("granite_hybrid", REPO)


@pytest.fixture(scope="module")
def tiny(fam):
    """(widths, the seeded bfloat16 weights, the same values as float32)."""
    w = fam.widths(CONFIG)
    weights = fam.make_weights(w, 7)
    return w, weights, jax.tree.map(lambda a: a.astype(jnp.float32), weights)


def _model(fam, w, weights, **control):
    return fam.build_program_model({**w, **control}, weights, {})


def _reference_logits(fam, w, weights, tokens):
    with jax.default_matmul_precision("highest"):
        h = fam.hidden(weights, jnp.asarray(tokens, jnp.int32), w)
        return np.asarray(fam.logits(weights, h, w))


def _stepper(model, **kw):
    kw = {"num_slots": 3, "paged": True, "page_size": 4, "num_pages": 80, **kw}
    return DecodeStepper(model, **kw)


def _admit(st, slot, prompt, chunk, max_new=16):
    """``prompt`` into ``slot`` in chunks of ``chunk``; how many it took."""
    left, chunks = st.begin_admit(slot, prompt, max_new=max_new), 0
    while left:
        left = st.prefill_chunk(slot, chunk)
        chunks += 1
    return chunks


def _states(st, slot):
    """The slot's state and tail of every Mamba layer, on the host."""
    return [tuple(np.asarray(a[slot]) for a in pool)
            for blk, pool in zip(st._gen._blocks, st._pools)
            if blk.kind == "ssm"]


class _Spy:
    """The final norm's output of every decode step, read off the step
    program itself; ``logits(slot)`` is that times the tied head."""

    def __init__(self, st, model):
        self.st, self.model, self.seen = st, model, []
        self.norm, self.real = st._gen._final_ln, st._gen._final_ln.apply

    def __enter__(self):
        def spy(params, state, x, **kw):
            y, s = self.real(params, state, x, **kw)
            jax.debug.callback(lambda a: self.seen.append(np.asarray(a)), y)
            return y, s

        self.norm.apply = spy
        return self

    def __exit__(self, *exc):
        jax.effects_barrier()
        del self.norm.apply

    def logits(self, slot):
        jax.effects_barrier()
        table = np.asarray(self.model.params["0"]["tokens"], np.float32)
        return np.stack([h[slot] for h in self.seen]) @ table.T / 8.0


def _decode(st, model, slot, n_new):
    active = np.zeros(st.num_slots, bool)
    active[slot] = True
    with _Spy(st, model) as spy:
        toks = [int(st.step(active)[slot]) for _ in range(n_new)]
        return toks, spy.logits(slot)


def test_the_zoo_model_s_apply_is_the_reference_s_forward(fam, tiny):
    """Logits of the whole model, float32 weights on both sides: the chunk
    form (blocks of 8, a sequence that is no whole number of them) against
    the reference's scan over positions; the blocks say what they cache."""
    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    blocks = model.layers[1:-2]
    assert [b.kind for b in blocks] == ["ssm", "ssm", "ssm", "gqa"] * 2
    for b in blocks:
        if b.kind == "ssm":
            assert type(b) is Mamba2Block and b.cached_rows == 0
            assert [s for s, _ in b.slot_state] == [STATE, TAIL]
            assert [str(d) for _, d in b.slot_state] == ["float32"] * 2
        else:
            assert type(b) is GroupedQueryMoEBlock and b.rope is None
            assert (b.kv_heads, b.head_dim, b.softmax_scale) == (2, 8, 0.125)
            assert b.residual_scale == 0.22 and b.gate is None
    assert model.layers[0].multiplier == 12.0
    assert model.params[str(len(model.layers) - 1)] == {}  # the tied head
    toks = np.random.default_rng(0).integers(0, w["vocab"], (2, 45))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    want = np.stack([_reference_logits(fam, w, weights, t) for t in toks])
    assert np.abs(want).max() > 0.003
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("left_out", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "mamba_d_conv"])
def test_each_multiplier_changes_the_logits_when_left_out(fam, tiny, left_out):
    """The comparison above sees each of Granite's four multipliers and the
    convolution: a program built without one is not the reference."""
    w, weights, f32 = tiny
    other = {"embedding_multiplier": {"embed_scale": 1.0},
             "residual_multiplier": {"residual_scale": 1.0},
             "attention_multiplier": {"attn_scale": 8 ** -0.5},
             "logits_scaling": {"logits_scaling": 1.0}}.get(left_out)
    toks = np.random.default_rng(0).integers(0, w["vocab"], (1, 40))
    if left_out == "attention_multiplier":
        # seeded scores are too small for their scale to show: both sides
        # get the attention layers' wq and wk sixteen times as large
        def louder(tree):
            tree = jax.tree.map(lambda a: a, tree)
            for i in (4, 8):
                for name in ("wq", "wk"):
                    tree[str(i)]["attn"][name] = tree[str(i)]["attn"][name] * 16
            return tree

        weights, f32 = louder(weights), louder(f32)
    if other is None:  # the convolution reduced to its newest tap
        f32 = jax.tree.map(lambda a: a, f32)
        for i in (1, 2, 3, 5, 6, 7):
            cw = f32[str(i)]["mixer"]["conv_w"]
            f32[str(i)]["mixer"]["conv_w"] = cw.at[:-1].set(0.0)
        other = {}
    model = _model(fam, w, f32, **other)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    want = _reference_logits(fam, w, weights, toks[0])[None]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_the_one_token_update_is_the_chunk_form_of_length_one():
    """``ssm_step`` and ``ssm_chunk`` over one position from the same state:
    two algorithms for one recurrence."""
    rng = np.random.default_rng(3)
    b, nh, hp, n = 2, 4, 8, 16
    state = jnp.asarray(rng.normal(size=(b, nh, hp, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, nh, hp)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
              for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, nh)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, nh), jnp.float32)
    d = jnp.ones((nh,), jnp.float32)
    y1, s1 = mamba2.ssm_step(state, x, bm, cm, dt, a, d)
    yc, sc = mamba2.ssm_chunk(
        state, x[:, None], bm[:, None], cm[:, None], dt[:, None], a, d, 8)
    np.testing.assert_allclose(np.asarray(yc[:, 0]), np.asarray(y1),
                               atol=2e-6, rtol=0)  # values of size 10
    np.testing.assert_allclose(np.asarray(sc), np.asarray(s1), atol=1e-6,
                               rtol=0)
    # a step of 0 leaves a state as it was, bit for bit
    _, same = mamba2.ssm_step(state, x, bm, cm, jnp.zeros_like(dt), a, d)
    assert np.array_equal(np.asarray(same), np.asarray(state))


@pytest.mark.parametrize("chunk", [5, 8, 13, 64], ids=lambda c: f"chunks-of-{c}")
def test_chunked_prefill_then_paged_decode_gives_the_reference_s_logits(
        fam, tiny, chunk):
    """Logits, not tokens: every decode step's logits against the
    reference's full forward over the prompt and the served tokens. A prompt
    of 53 positions in chunks of 5, 8, 13 and whole: pow2 buckets of 8, 8, 16
    and 64 behind 5, 8, 13 and 52 real tokens, so chunks end inside blocks
    of 8 and on them, with and without padding; the carried state and the
    convolution tail are the same after every way of cutting it."""
    w, weights, f32 = tiny
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        st = _stepper(model)
        assert st.layout == "ssm"
        assert st.attention.startswith("gather: heads of 8")
        chunks = _admit(st, 1, prompt, chunk)
        after_prefill = _states(st, 1)
        toks, got = _decode(st, model, 1, 12)
        whole = _stepper(model)
        _admit(whole, 2, prompt, 64)
    assert chunks == -(-52 // chunk)
    assert st._kv_alloc.pages_in_use == -(-(53 + 16) // 4)
    for (s_a, t_a), (s_b, t_b) in zip(after_prefill, _states(whole, 2)):
        np.testing.assert_allclose(s_a, s_b, atol=1e-6, rtol=0)
        np.testing.assert_allclose(t_a, t_b, atol=1e-6, rtol=0)
        assert np.abs(s_a).max() > 1e-3  # ... and it is a state
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))


def test_heads_of_64_side_by_side_ride_the_grouped_kernel(fam):
    """The attention layers of a model with K/V heads of 64 that fill the
    lanes in pairs (the published model's 8; here 2): the stepper says
    ``"kernel"``, compiles ONE step program, and the logits of chunked
    prefill then decode (the kernel interpreted, the layer's own softmax
    scale folded into the query) are the reference's."""
    w = fam.widths(KERNEL_CONFIG)
    weights = fam.make_weights(w, 7)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 37)
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        st = _stepper(model, page_size=8, num_pages=40)
        assert st.attention == "kernel" and st._step_table_buckets() == [16]
        _admit(st, 1, prompt, 16)
        toks, got = _decode(st, model, 1, 10)
    assert sorted(st._pstep_fns) == [(16, False)]
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    # logits of size 0.003; the kernel folds its softmax over blocks of pages
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)


def test_a_query_zero_outside_its_head_s_lanes_reads_its_own_head():
    """``paged_decode_attention`` for K/V heads of 64 in a flat pool against
    grouped-query attention over the gathered pages: float32 pool and
    bfloat16, a slot that is not decoding, a window's first position."""
    from distkeras_tpu.models.gqa_moe import attend_dense
    from distkeras_tpu.ops.paged_attention import (
        decode_attention_path, heads_side_by_side, paged_decode_attention)

    assert [heads_side_by_side(*a) for a in (
        (64, 8), (64, 1), (128, 8), (96, 8), (16, 8), (16, 2))] == [
        2, 0, 1, 0, 8, 0]
    said = decode_attention_path("gqa", 64, jnp.bfloat16, None, 16)
    assert said.startswith("gather: heads of 64")  # K/V heads not told
    assert decode_attention_path(
        "gqa", 64, jnp.bfloat16, None, 16, kv_heads=8) == "kernel"
    assert decode_attention_path(
        "gqa", 64, jnp.bfloat16, None, 16, kv_heads=1) == said
    rng = np.random.default_rng(0)
    b, nh, kvh, hd, ps, pages = 3, 8, 4, 64, 8, 40
    table = jnp.asarray(
        rng.permutation(pages - 1)[: b * 6].reshape(b, 6) + 1, jnp.int32)
    lengths = jnp.asarray([37, 0, 48], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, nh, hd)), jnp.float32)
    idx = (table[:, :, None] * ps + jnp.arange(ps)).reshape(b, -1)
    # (a bfloat16 pool: the query goes in as one bfloat16 term)
    for dtype, tol in ((jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)):
        ck, cv = (jnp.asarray(rng.normal(size=(pages * ps, kvh * hd)), dtype)
                  for _ in range(2))
        kg, vg = (c[idx].reshape(b, -1, kvh, hd) for c in (ck, cv))
        for first in (None, jnp.maximum(lengths - 20, 0)):
            got = paged_decode_attention(q, ck, cv, table, lengths, first,
                                         page_size=ps)
            see = jnp.arange(48)[None, :] < lengths[:, None]
            if first is not None:
                see = see & (jnp.arange(48)[None, :] >= first[:, None])
            want = attend_dense(q[:, None], kg, vg, see[:, None])[:, 0]
            want = jnp.where(lengths[:, None, None] > 0, want, 0.0)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=tol, rtol=0)


def test_a_state_held_in_bfloat16_is_another_result(fam, tiny, monkeypatch):
    """Why the state's precision is an attribute of the class and no option
    of the block, the zoo entry or a bundle: at the tiny size, float32
    operands, the comparison with the reference fails from a state that is
    rounded at every step, by more the more steps the state has been through
    (7e-7 after 60, where the float32 state reads 2e-9). No keyword reaches
    it, and a bundle does not carry it."""
    w, weights, f32 = tiny
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 20)
    with pytest.raises(TypeError, match="state_dtype"):
        zoo.granite_hybrid_lm(state_dtype="bfloat16")
    with pytest.raises(TypeError, match="state_dtype"):
        Mamba2Block(8, 8, 16, 64, state_dtype="bfloat16")
    monkeypatch.setattr(Mamba2Block, "state_dtype", "bfloat16")
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        assert "state_dtype" not in model.layers[1].get_config()
        st = _stepper(model)
        assert [str(a.dtype) for a in st._pools[0]] == ["bfloat16", "float32"]
        _admit(st, 1, prompt, 16, max_new=60)
        toks, got = _decode(st, model, 1, 60)
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    assert np.abs(got - ref).max() > 10 * LOGIT_TOL


def test_padding_and_an_idle_slot_leave_a_state_alone(fam, tiny):
    """A chunk's pow2 padding advances nothing (5 real tokens in a bucket of
    8 leave the state that 5 tokens give); a step in which a slot is not
    decoding leaves its state and tail bit for bit, while its neighbour's
    move; a slot that only prefills between a neighbour's steps is not
    touched by them."""
    w, _, f32 = tiny
    rng = np.random.default_rng(5)
    a, b = (rng.integers(0, w["vocab"], n) for n in (14, 30))
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        st = _stepper(model)
        _admit(st, 0, a, 5)   # 5, 5, 3 real tokens, each in a bucket of 8
        alone = _stepper(model)
        _admit(alone, 0, a, 64)
        for got, want in zip(_states(st, 0), _states(alone, 0)):
            np.testing.assert_allclose(got[0], want[0], atol=1e-6, rtol=0)
            np.testing.assert_allclose(got[1], want[1], atol=1e-6, rtol=0)
        before = _states(st, 0)
        st.begin_admit(1, b, max_new=8)
        st.prefill_chunk(1, 16)             # slot 1 mid-prefill ...
        mid = _states(st, 1)
        st.step(np.array([True, False, False]))  # ... while slot 0 steps
        for x, y in zip(mid, _states(st, 1)):
            assert all(np.array_equal(p, q) for p, q in zip(x, y))
        moved = _states(st, 0)
        assert all(not np.array_equal(x[0], y[0])
                   for x, y in zip(before, moved))
        st.step(np.array([False, False, False]))
        for x, y in zip(moved, _states(st, 0)):
            assert all(np.array_equal(p, q) for p, q in zip(x, y))


def test_a_chunk_is_never_built_under_the_floor(fam, tiny):
    """A short request near the end of its own pages: the chunk keeps the
    floor's bucket (8 here, 64 at the published sizes) and no smaller program
    is minted by live traffic; what the bucket holds beyond the slot's pages
    lands on the null page and advances no state."""
    w, weights, f32 = tiny
    prompt = np.random.default_rng(8).integers(0, w["vocab"], 7)
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        st = _stepper(model)
        assert (st.chunk_cap, st.chunk_floor) == (128, 8)
        # 7 + 1 positions: two pages of 4, so the second chunk (3 tokens at
        # position 3) has 5 rows of its own pages left and a bucket of 8
        assert _admit(st, 1, prompt, 3, max_new=1) == 2
        assert len(st._tables[1]) == 2
        toks, got = _decode(st, model, 1, 1)
    assert sorted(cb for cb, _ in st._pchunk_fns) == [8]
    ref = _reference_logits(fam, w, weights, prompt)[-1:]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("first_len", [1, 9], ids=["after-one-token", "after-9"])
def test_a_reused_slot_starts_from_zero(fam, tiny, first_len):
    """Two requests through one slot, one after the other, against each
    alone in a fresh stepper: the second's logits know nothing of the first
    (its first chunk, or for a prompt of one token its first step, starts
    the state from zeros by its ``where``); nothing is written by the host."""
    w, weights, f32 = tiny
    rng = np.random.default_rng(6)
    first = rng.integers(0, w["vocab"], 40)
    second = rng.integers(0, w["vocab"], first_len)
    with jax.default_matmul_precision("highest"):
        model = _model(fam, w, f32)
        st = _stepper(model)
        _admit(st, 1, first, 16)
        _decode(st, model, 1, 6)
        st.release(1)
        assert np.abs(_states(st, 1)[0][0]).max() > 1e-3  # still the first's
        _admit(st, 1, second, 16)
        toks, got = _decode(st, model, 1, 8)
    seq = np.concatenate([second, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(second) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert st.state_stats["resets"] == 2 and st._state_resets_new == 0


def test_the_stepper_sizes_its_pools_by_what_a_block_declares(fam, tiny):
    """Pools that differ by layer: a state and a tail a slot where the block
    says ``slot_state``, flat K/V pages where it caches rows; the page gate
    counts the attention layers alone, and ``stats`` says what a slot holds
    whatever its length."""
    w, _, f32 = tiny
    st = _stepper(_model(fam, w, f32), num_slots=2, num_pages=20)
    shapes = [[a.shape for a in pool] for pool in st._pools]
    ssm, kv = [(2, *STATE), (2, *TAIL)], [(80, 16)] * 2
    assert shapes == [ssm, ssm, ssm, kv] * 2
    # 2 K/V heads x 8 x (K and V) x 4 bytes a layer, two attention layers
    assert st.kv_bytes_per_token() == st.kv_bytes_per_token("full") == 256
    a_slot = 6 * 4 * (8 * 8 * 16 + 3 * 96)
    assert st.state_bytes_a_slot == a_slot
    stats = st.paged_stats()
    assert stats["layout"] == "ssm" and stats["bytes_per_token"] == 256
    assert stats["bytes_per_token_by_kind"] == {"full": 256}
    assert stats["state_layers"] == 6
    assert stats["state_bytes_a_slot"] == a_slot
    assert stats["state_bytes_total"] == 2 * a_slot
    assert stats["prefix_caches"].startswith("off: state layout")
    assert st.kv_bytes_total() == 2 * 2 * 80 * 16 * 4
    assert st.pages_for(10, 6) == 4 and st.can_fork is False


def test_what_a_state_makes_impossible_is_refused_typed(fam, tiny, tmp_path):
    """Each needs a snapshot or a rollback of the state that no program
    takes yet: typed, and saying so."""
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving.prefix_cache import PrefixStore

    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    says = "a block that holds a state a slot"
    for kw in ({"paged": False}, {"speculative": object()}, {"mesh": "tp:2"}):
        with pytest.raises(BlockUnsupportedError, match=says):
            DecodeStepper(model, num_slots=2, **{"paged": True, **kw})
    st = _stepper(model, prefix_cache=PrefixStore())
    assert st.prefix_cache is None and st.prefix_index is None
    _admit(st, 0, np.arange(9) % w["vocab"], 16)
    for call in (lambda: st.fork_slot(0, 1), lambda: st.swap_out(0),
                 lambda: st.swap_in(1, {})):
        with pytest.raises(BlockUnsupportedError, match=says):
            call()
    for kw in ({"role": "prefill"}, {"role": "decode"},
               {"prefix_cache": PrefixStore()}):
        with pytest.raises(BlockUnsupportedError, match=says):
            ServingEngine(model, num_slots=2, paged=True, page_size=4, **kw)
    with pytest.raises(BlockUnsupportedError, match="a state a sequence"):
        CachedSequenceGenerator(model).generate(
            np.arange(6)[None] % w["vocab"], steps=2)
    with pytest.raises(ValueError, match="no window and no indexer"):
        odd = zoo.granite_hybrid_lm()
        odd.layers[4].window = 8
        _stepper(odd)


class _FailsAtCollect:
    """A dispatched step's handle whose fetch fails: the device has run the
    step, and nothing of it reaches the host."""

    def __init__(self, handle):
        self._handle, self.active = handle, handle.active

    def ready(self):
        return self._handle.ready()

    def discard(self):
        self._handle.discard()

    def collect(self):
        self._handle.discard()  # in the air no more, as a failed collect's
        raise RuntimeError("injected: the tokens' fetch failed")


@pytest.mark.parametrize("fails_at", ["dispatch", "collect"])
def test_a_failed_step_leaves_no_state_ahead_of_its_tokens(fam, tiny, fails_at):
    """The scheduler two steps deep over the real stepper, a step failing
    once. At its DISPATCH (the seam fires before any device work) nothing
    has advanced: the probes blame the newest admission and the others
    decode on, every token the reference's. At its COLLECT the device has
    run it, and the step behind it: each has advanced the states it touched
    with no token delivered, so no probe can start from where the step did;
    every request of either mask fails typed (where pages would only be
    written again), none streams on from a state ahead of its tokens, and
    the slots serve the reference's tokens to who comes next."""
    from distkeras_tpu.faults import FaultPlan
    from distkeras_tpu.serving.scheduler import (
        ContinuousBatcher, InternalError, ServeRequest)

    w, weights, f32 = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, w["vocab"], n).astype(np.int32)
               for n in (21, 9, 30)]

    def drain(b, reqs):
        for _ in range(400):
            if all(r.done for r in reqs):
                return
            b.step()
        raise AssertionError("the scheduler made no progress")

    def sound(req, prompt):
        seq = np.asarray(req.result())
        assert len(seq) == len(prompt) + 12
        gaps, _ = fam.token_gaps(f32, w, seq, len(prompt))
        # float32 on both sides at HIGHEST: the served token is the
        # reference's best (a state one token ahead reads 1e-4 and more)
        assert gaps.max() <= 1e-7, gaps.max()

    with jax.default_matmul_precision("highest"):
        st = _stepper(_model(fam, w, f32))
        b = ContinuousBatcher(st, overlap=True, prefill_chunk=16,
                              quarantine_steps=2)
        first = [b.submit(ServeRequest(p, 12)) for p in prompts[:2]]
        for _ in range(8):  # both prefilled, a few tokens each, two in the air
            b.step()
        assert not any(r.done for r in first)
        if fails_at == "dispatch":
            with FaultPlan().arm("stepper.step", times=1):
                drain(b, first)
            with pytest.raises(InternalError, match="blamed"):
                first[1].result()  # the newest admission, by the masked retry
            sound(first[0], prompts[0])
            assert b.counters["blame_probes"] == 1
        else:
            real, calls = st.step_async, []

            def flaky(active):
                calls.append(1)
                handle = real(active)
                return _FailsAtCollect(handle) if len(calls) == 1 else handle

            st.step_async = flaky
            drain(b, first)
            for r in first:
                with pytest.raises(InternalError, match="blamed"):
                    r.result()
            assert b.counters["blame_probes"] == 0
            assert len(calls) == 2  # the failed step, and the one dropped
        assert b.counters["step_failures"] == 1
        later = [b.submit(ServeRequest(p, 12)) for p in prompts]
        drain(b, later)
        for r, p in zip(later, prompts):
            sound(r, p)
    assert not st._air and st._kv_alloc.pages_in_use == 0


def test_the_serving_engine_serves_the_reference_s_tokens(fam, tiny, tmp_path):
    """Through ``quantize_model(bits=16)``, a bundle and
    ``ServingEngine.from_bundle(paged=True)``: concurrent requests (more than
    slots, so that slots are reused), prefill in chunks beside decode,
    greedy; every served token's reference logit against the reference's
    best; the state's counters."""
    from distkeras_tpu.utils.serialization import save_serving_bundle

    w, weights, f32 = tiny
    model = quantize_model(_model(fam, w, weights), bits=16)
    mixer = model.params["1"]["mixer"]
    assert {k: str(v.dtype) for k, v in mixer.items() if k != "norm"} == {
        "w_in": "bfloat16", "conv_w": "bfloat16", "w_out": "bfloat16",
        "conv_b": "float32", "dt_bias": "float32", "a_log": "float32",
        "d_skip": "float32"}
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    eng = ServingEngine.from_bundle(
        path, num_slots=3, paged=True, page_size=8, num_pages=120,
        prefill_chunk=16)
    eng._stepper.warmup()
    eng._stepper.warm_prefill_buckets()
    eng.start()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, w["vocab"], n).astype(np.int32)
               for n in (5, 37, 60, 90, 1, 70, 12)]
    out = {}

    def go(i):
        out[i] = np.asarray(eng.generate(prompts[i], 16))

    threads = [threading.Thread(target=go, args=(i,)) for i in range(7)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    stats, health = eng.stats(), eng.health()
    eng.stop()
    assert health["status"] == "serving" and stats["restarts"] == 0
    paged, state = stats["paged"], stats["state"]
    assert paged["layout"] == "ssm" and paged["pages_in_use"] == 0
    assert state["layers"] == 6 and state["resets"] == 7
    assert state["state_bytes_a_slot"] == paged["state_bytes_a_slot"]
    # every delivered token but a request's first... is one slot's step:
    # a state is read and written once a decoding slot, layer and step
    a_step = 6 * 2 * 4 * 8 * 8 * 16
    assert state["state_bytes"] % a_step == 0
    assert state["state_bytes"] // a_step >= 7 * 16
    with jax.default_matmul_precision("highest"):
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            gaps, _ = fam.token_gaps(weights, w, seq, len(prompts[i]))
            # float32 state, bfloat16 operands, logits of size 0.001: a
            # served token is the reference's best (these seven read 0) or
            # within the operands' rounding of it
            assert gaps.max() <= 2e-5, (i, gaps.max())


def test_the_spans_carry_the_state_s_counters(tmp_path):
    """ONE K/V head of 64 (no pair fills the lanes): ``attention`` on every
    ``serving/step`` span reads ``gather: heads of 64 ...``, the word of
    ``stats()["paged"]["attention"]`` (the published model's 8 K/V heads of
    64 say ``kernel``: ``test_heads_of_64_side_by_side_...``; ROADMAP B10),
    ``state_bytes`` beside it is the decoding slots' states in and out, and
    every ``serving/collect`` span says how many slots an admission reset
    since the one before."""
    from test_serving_spans import _traced

    model = zoo.granite_hybrid_lm(
        vocab_size=61, seq_len=64, hidden_size=128, num_attention_heads=2,
        num_key_value_heads=1, mamba_n_heads=8, mamba_d_head=32,
        layer_types=("mamba", "attention"), attention_multiplier=1 / 64)
    engine = ServingEngine(model, num_slots=2, paged=True, page_size=4,
                           prefill_chunk=8)
    engine.start()
    try:
        engine.submit(np.arange(5, dtype=np.int32), 3).result(120)  # compiles
        before = engine.stats()["state"]
        _, plain = _traced(tmp_path, lambda: [
            list(engine.submit(np.arange(1, 12 + i, dtype=np.int32) % 61,
                               5).result(120)) for i in range(2)])
        after, paged = engine.stats()["state"], engine.stats()["paged"]
    finally:
        engine.stop()
    its = _program_spans.iterations(plain)
    (said,) = set(_program_spans.span_values(its, "serving/step", "attention"))
    assert paged["attention"].startswith(said)
    assert said.startswith("gather: heads of 64 are not a whole number of 128")
    a_step = 2 * 4 * 8 * 32 * 16  # one Mamba layer, one slot, in and out
    sent = _program_spans.span_values(its, "serving/step", "state_bytes")
    assert len(sent) == 10 and set(sent) == {a_step}
    rows = [a for n, _s, _d, _t, a in plain["spans"]
            if n == "serving/collect" and "state_resets" in a]
    assert len(rows) == after["steps"] - before["steps"] == 10
    assert sum(a["state_resets"] for a in rows) == 2
    assert after["resets"] - before["resets"] == 2
    assert after["state_bytes"] - before["state_bytes"] == 10 * a_step


def test_the_layers_are_found_by_name_when_a_process_loads_a_bundle_only():
    """``layer_from_config`` imports the module that registers the block."""
    import subprocess

    code = (
        "from distkeras_tpu.models.layers import layer_from_config\n"
        "b = layer_from_config({'layer': 'Mamba2Block', 'num_heads': 8,"
        " 'head_dim': 8, 'state_dim': 16, 'ffn_width': 64})\n"
        "h = layer_from_config({'layer': 'TiedHead', 'vocab_size': 61,"
        " 'logits_scaling': 8.0})\n"
        "print(b.kind, b.cached_rows, h.params_of)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.stdout.split() == ["ssm", "0", "0"], out.stderr[-2000:]
