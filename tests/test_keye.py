"""The grouped-query block whose keys a learned indexer selects
(``models/gqa_moe.py`` ``GroupedQueryMoEBlock`` with ``select``,
``zoo.keye_lm``) against the benchmark's independent plain reference
(``benchmark/families/keye_vl2.py``) at a tiny size, seeded: the full
forward, chunked prefill and paged decode through the selector's cache over a
request several ``topk`` long, each mechanism left out, the exact selection
and its tie rule, what the lowered step gathers, the experts' shares, the
served tokens over a bundle, and the block found by name."""

import os
import re
import subprocess
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
from benchmark.reference import dot_highest  # noqa: E402
from distkeras_tpu.models import gqa_moe  # noqa: E402
from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock  # noqa: E402
from distkeras_tpu.ops.quantization import quantize_model  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.engine import DecodeStepper  # noqa: E402

# hidden 32, 4 query heads over 2 K/V heads of 16, an indexer of 2 heads of 8
# that picks 8 of the cache, 16 experts top 3, no shared expert, 3 layers
CONFIG = {
    "family": "keye_vl2",
    "vocab_size": 211, "max_position_embeddings": 128, "num_hidden_layers": 3,
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 16, "num_experts": 16,
    "num_experts_per_tok": 3, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8},
    "assumed": {"initializer_range": 0.02},
}

# the same with selector keys of 64: on pages of 16 a page of them is a tile
# of 8 rows of two keys, which ``paged_index_scores`` reads in place
KERNEL_CONFIG = {
    **CONFIG,
    "sa_config": {**CONFIG["sa_config"], "indexer_head_dim": 64},
}
# and with K/V heads of 128: a page of keys is whole tiles too, and the
# grouped body attends the slot's pages under the selection's mask
STREAM_CONFIG = {**KERNEL_CONFIG, "head_dim": 128}
# how the step scores the selector keys and reads K and V -> (configuration,
# page size, what ``selector`` and ``attention`` say)
SELECTORS = {
    "gather": (CONFIG, 4, "gather", "gather: heads of 16"),
    "kernel": (KERNEL_CONFIG, 16, "kernel", "gather: heads of 16"),
    "streamed": (STREAM_CONFIG, 16, "kernel", "kernel"),
}

# float32 weights and a float32 cache on both sides, every product at
# precision HIGHEST (the CPU's float32 either way): logits of size 0.4 read
# 1e-7 to 4e-7 apart; a K/V cache rounded to float16 moves them by 1e-5 and
# more, a selector cache rounded until a pick changes by 0.05
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("keye_vl2", REPO)


@pytest.fixture(scope="module")
def tiny(fam):
    return _seeded(fam, CONFIG)


@pytest.fixture(scope="module")
def tiny_kernel(fam):
    return _seeded(fam, KERNEL_CONFIG)


@pytest.fixture(scope="module")
def tiny_stream(fam):
    return _seeded(fam, STREAM_CONFIG)


@pytest.fixture
def tinies(tiny, tiny_kernel, tiny_stream):
    return {"gather": tiny, "kernel": tiny_kernel, "streamed": tiny_stream}


def _seeded(fam, config):
    """(widths, the seeded bfloat16 weights, the same values as float32 with
    gains and shifts that are not 1 and 0, so that the norms are seen)."""
    w = fam.widths(config)
    weights = fam.make_weights(w, 7)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 64))

    def jitter(path, a):
        name = str(path[-1])
        if a.ndim == 1 and ("gamma" in name or "beta" in name):
            return (a.astype(jnp.float32) + 0.3 * jax.random.normal(
                next(keys), a.shape)).astype(jnp.bfloat16)
        return a

    weights = jax.tree_util.tree_map_with_path(jitter, weights)
    return w, weights, jax.tree.map(lambda a: a.astype(jnp.float32), weights)


def _model(fam, w, weights):
    return fam.build_program_model(w, weights, {})


def _reference_logits(fam, w, weights, tokens, **parts):
    with jax.default_matmul_precision("highest"):
        h = fam.hidden(weights, jnp.asarray(tokens, jnp.int32), w, **parts)
        return np.asarray(fam.logits(weights, h, w))


def test_the_zoo_model_s_apply_is_the_reference_s_forward(fam, tiny):
    """Logits of the whole model, float32 weights on both sides, over
    sequences 12 times ``topk`` long; the blocks say what they are."""
    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    blocks = model.layers[1:-2]
    assert all(type(b) is GroupedQueryMoEBlock and b.kind == "gqa"
               and b.kv_heads == 2 and b.head_dim == 16 and b.window is None
               and b.gate is None and b.shared_width == 0 and b.qk_norm
               and b.select == {"heads": 2, "head_dim": 8, "topk": 8}
               for b in blocks)
    assert "shared" not in model.params["1"]["ffn"]
    toks = np.random.default_rng(0).integers(0, w["vocab"], (2, 96))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    for row in range(2):
        ref = _reference_logits(fam, w, weights, toks[row])
        np.testing.assert_allclose(got[row], ref, atol=LOGIT_TOL, rtol=0)
    assert fam.param_count(w)["total"] == model.num_params()


@pytest.mark.parametrize("left_out", ["indexer", "qk_norm", "norm_topk"])
def test_each_mechanism_changes_the_logits_when_left_out(fam, tiny, left_out):
    """The indexer's selection, the norm a head on q and k and the weights'
    normalisation over the picks are in the program: a reference without one
    of them is hundreds of tolerances away from it."""
    w, weights, f32 = tiny
    toks = np.random.default_rng(3).integers(0, w["vocab"], (1, 64))
    model = _model(fam, w, f32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])[0]
    np.testing.assert_allclose(
        got, _reference_logits(fam, w, weights, toks[0]), atol=LOGIT_TOL)
    other, parts = dict(w), {}
    if left_out == "indexer":
        parts["select"] = False
    elif left_out == "qk_norm":
        parts["qk_norm"] = False
    else:
        other["norm_topk"] = False
    ref = _reference_logits(fam, other, weights, toks[0], **parts)
    assert np.abs(got - ref).max() > 100 * LOGIT_TOL


def test_a_tie_at_the_threshold_goes_to_the_lower_position():
    """Exactly ``min(visible, topk)`` keys a query, the largest scores, a
    tie at the threshold to the lower position; the mask (the chunk's form)
    and the rows (the step's form) are the same set, and both are the
    reference's own selection."""
    fam = spec.load_family("keye_vl2", REPO)
    s = jnp.asarray([[1., 3., 3., 2., 3., 0., -1., 3., -0., 3.],
                     [5., 5., 5., 5., 5., 5., 5., 5., 5., 5.],
                     [0., -0., 0., -0., 0., -0., 1., -0., 0., -0.]])
    see = jnp.ones(s.shape, bool).at[0, 1].set(False).at[1, 7:].set(False)
    for k in (1, 3, 4, 7, 12):
        mask = np.asarray(gqa_moe.select_mask(s, see, k))
        idx, valid = gqa_moe.select_rows(s, see, k)
        want = np.asarray(fam.selection(s, see, k))
        assert (mask == want).all(), k
        for row in range(3):
            visible = int(np.asarray(see)[row].sum())
            assert mask[row].sum() == min(visible, k)
            rows = np.asarray(idx)[row][np.asarray(valid)[row]]
            assert sorted(rows) == list(np.flatnonzero(mask[row])), (k, row)
    # position 1 is not visible: 2, 4, 7 are the lowest of the tied 3s
    assert list(np.flatnonzero(gqa_moe.select_mask(s, see, 3)[0])) == [2, 4, 7]
    assert list(np.flatnonzero(gqa_moe.select_mask(s, see, 3)[2])) == [0, 1, 6]


def test_selected_attention_is_dense_attention_under_the_selection_s_mask():
    """``attend_selected`` (a prefill chunk: the selection a tile of queries
    at a time, key blocks folded under it, at the first extent that holds
    the chunk) against ``attend_dense`` under ``select_mask``'s own mask,
    from every extent and with an extent that is no whole number of blocks."""
    rng = np.random.default_rng(0)
    n, t, k = 32, 88, 6
    q = jnp.asarray(rng.normal(size=(n, 4, 16)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(t, 2, 16)), jnp.float32)
    vals = jnp.asarray(rng.normal(size=(t, 2, 16)), jnp.float32)
    scores = jnp.asarray(rng.integers(-4, 5, size=(n, t)), jnp.float32)
    for start in (0, 9, 40, 56):
        qpos = jnp.asarray(start + np.arange(n))
        see = jnp.arange(t)[None, :] <= qpos[:, None]
        keep = gqa_moe.select_mask(scores, see, k)

        def chosen_of(lo, m, te):
            return gqa_moe.select_mask(
                jax.lax.dynamic_slice_in_dim(scores, lo, m, 0)[:, :te],
                jax.lax.dynamic_slice_in_dim(see, lo, m, 0)[:, :te], k)

        with jax.default_matmul_precision("highest"):
            want = gqa_moe.attend_dense(q[None], keys[None], vals[None],
                                        keep[None])[0]
            got = gqa_moe.attend_selected(
                q, lambda te: (keys[:te], vals[:te]), qpos, chosen_of, t,
                extents=(24, 48, 72), key_block=16, query_block=8)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def _stepper_logits(model, prompt, n_new, kv_dtype, chunk, round_selector=None,
                    page_size=4):
    """Prefill ``prompt`` in chunks of ``chunk`` and decode ``n_new`` tokens
    through the paged stepper; the logits of every decode step, read off the
    step program itself, and the stepper. ``round_selector``: a dtype the
    selector pools are rounded through once the prompt is prefilled."""
    st = DecodeStepper(model, num_slots=3, paged=True, page_size=page_size,
                       num_pages=240 // page_size, kv_dtype=kv_dtype)
    seen = []
    norm, real = st._gen._final_ln, st._gen._final_ln.apply

    def spy(params, state, x, **kw):
        y, s = real(params, state, x, **kw)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), y)
        return y, s

    norm.apply = spy
    try:
        slot = 1
        left = st.begin_admit(slot, prompt, max_new=n_new)
        chunks = 0
        while left:
            left = st.prefill_chunk(slot, chunk)
            chunks += 1
        if round_selector is not None:
            st._pools = [
                (k, v, i.astype(round_selector).astype(i.dtype))
                for k, v, i in st._pools]
        active = np.zeros(3, bool)
        active[slot] = True
        toks = [int(st.step(active)[slot]) for _ in range(n_new)]
        jax.effects_barrier()
    finally:
        del norm.apply
    head = np.asarray(model.params[str(len(model.layers) - 1)]["kernel"],
                      np.float32)
    return chunks, toks, np.stack([h[slot] for h in seen]) @ head, st


_CHUNKS = {"whole-pages": 16, "odd-chunks": 5, "one-chunk": 64}


@pytest.mark.parametrize("chunk,selector", [
    pytest.param(chunk, selector, id=f"{name}-{selector}")
    for selector in ("gather", "kernel") for name, chunk in _CHUNKS.items()
] + [pytest.param(16, "streamed", id="whole-pages-streamed")])
def test_chunked_prefill_then_paged_decode_gives_the_reference_s_logits(
        fam, tinies, chunk, selector):
    """Logits, not tokens: every decode step's logits against the
    reference's full forward over the prompt and the served tokens, for a
    request of 65 positions, eight times ``topk``: every chunk after the
    first and every step selects, in chunks that are and are not whole
    pages. ``gather``: through the selector pool's packed rows (a page a
    row: 4 keys of 8 values) gathered at the table's extent; ``kernel``:
    selector keys of 64 on pages of 16, a page a tile of 8 rows of two
    keys, scored where the pages lie by ``paged_index_scores``
    (interpreted); both gather the selected K and V rows by token (heads
    of 16). ``streamed``: K/V heads of 128 besides, so the step hands the
    selection on as a mask and ``paged_decode_attention`` attends the
    slot's own pages under it (interpreted): the same logits, from no
    ``top_k`` and no gathered row. The same comparison fails from a K/V
    cache rounded to float16 (for which there is no kernel: that stepper
    of the ``streamed`` case gathers), and from a selector cache rounded
    to 8 bits (float8, 3 bits of mantissa): float16 moves no score of these
    36 selections across its threshold (the comparison sees the selector's
    precision only through a changed pick, and then by 0.05: one key of 8
    is another)."""
    w, weights, f32 = tinies[selector]
    _, ps, says_selector, says_attention = SELECTORS[selector]
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    with jax.default_matmul_precision("highest"):
        chunks, toks, got, st = _stepper_logits(
            _model(fam, w, f32), prompt, 12, None, chunk, page_size=ps)
        _, toks16, got16, _ = _stepper_logits(
            _model(fam, w, f32), prompt, 12, None, chunk,
            round_selector=jnp.float8_e4m3fn, page_size=ps)
        _, tokskv, gotkv, stkv = _stepper_logits(
            _model(fam, w, f32), prompt, 12, jnp.float16, chunk, page_size=ps)
    assert chunks == -(-52 // chunk)
    assert st.selector.startswith(says_selector)
    assert st.attention.startswith(says_attention)
    assert stkv.attention.startswith("gather: ")
    assert st.paged_stats()["selector"] == st.selector
    assert st.paged_stats()["attention"] == st.attention
    assert st.layout == "gqa"
    assert st._index_page == {
        "gather": (32,), "kernel": (8, 128)}[says_selector]
    assert st._pools[0][2].shape == (240 // ps, *st._index_page)
    assert st._kv_alloc.pages_in_use == -(-65 // ps)
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))
    assert st.select_stats == {
        "steps": 12, "keys_cached": sum(range(53, 65)), "keys_selected": 96}
    for other_toks, other in ((toks16, got16), (tokskv, gotkv)):
        seq = np.concatenate([prompt, other_toks])
        ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
        assert np.abs(other - ref).max() > 4 * LOGIT_TOL


def test_the_stepper_holds_a_selector_pool_beside_the_keys_and_values(
        fam, tiny):
    """A third pool a layer under the one table: a selector key of 8 values
    a token, a page of 4 tokens a row, so a token costs its 32 bytes; the bytes a
    token costs by kind; one step program whatever the table's width; the
    chunk programs stop at the block's ``chunk_tokens``."""
    w, _, f32 = tiny
    st = DecodeStepper(_model(fam, w, f32), num_slots=2, paged=True,
                       page_size=4, num_pages=20)
    assert st.layout == "gqa" and st._ring == 0 and st.can_fork is False
    assert st._window_alloc is None and st.prefix_index is None
    assert [[a.shape for a in arrs] for arrs in st._pools] == [
        [(80, 32), (80, 32), (20, 32)]] * 3
    # 2 K/V heads x 16 x (K and V) x 4 bytes, and 8 x 4 bytes, a layer
    assert st.kv_bytes_per_token("full") == 3 * 256
    assert st.kv_bytes_per_token("index") == 3 * 32
    assert st.kv_bytes_per_token() == 3 * 288
    stats = st.paged_stats()
    assert stats["bytes_per_token"] == 3 * 288
    assert stats["bytes_per_token_by_kind"] == {"full": 768, "index": 96}
    assert stats["select"] == {"heads": 2, "head_dim": 8, "topk": 8}
    assert stats["prefix_caches"].startswith("off: selecting layout")
    assert st.kv_bytes_total() == 3 * 4 * (2 * 80 * 32 + 20 * 32)
    assert st._step_table_buckets() == [st._max_pages_bucket]
    assert st.chunk_cap == 128  # chunk_tokens 2,048 is past max_len here
    assert st.chunk_floor == 8  # shorter chunks are padded to a sixteenth
    blk = st._gen._blocks[0]
    blk.chunk_tokens = 16
    try:
        capped = DecodeStepper(_model(fam, w, f32), num_slots=2, paged=True,
                               page_size=4, num_pages=40)
    finally:
        del blk.chunk_tokens
    assert capped.chunk_cap == 128  # another model's blocks: their own say


@pytest.mark.parametrize("max_len", [64, 256])
def test_the_lowered_step_gathers_topk_rows_a_slot_whatever_the_table(
        fam, tiny, max_len):
    """In the step program's lowered text every gather of K/V rows (rows of
    Hkv x Dh = 32 values) yields ``topk`` = 8 rows a slot, at a table of 32
    pages and at one of 128; no operand of the K/V row width has the table's
    extent; the selector keys are gathered at the table's extent, a page a
    row."""
    w, _, f32 = tiny
    model = fam.build_program_model({**w, "seq": max_len}, f32, {})
    st = DecodeStepper(model, num_slots=3, paged=True, page_size=2,
                       num_pages=160)
    pbt = st._max_pages_bucket
    assert pbt == max_len // 2 and st._index_page == (16,)
    text = st._build_step_fn_paged(pbt).lower(
        st._params, st._ctx, st._pools, st._lens.copy(), np.zeros(3, bool),
        st._tables_array(pbt), *st._sampling_args()).as_text()
    gathers = re.findall(r'"?stablehlo\.gather"?.*-> tensor<([0-9x]+)xf32>',
                         text)
    kv_rows = [g for g in gathers if g.endswith("x32") and g.count("x") == 2]
    assert kv_rows and set(kv_rows) == {"3x8x32"}, kv_rows
    assert len(kv_rows) == 2 * 3  # K and V, a layer
    assert f"3x{max_len}x32x" not in text.replace("x32xf32", "x32x")
    assert f"3x{max_len // 2}x16" in gathers  # selector rows, a page each


def test_the_lowered_step_of_a_kernel_selector_gathers_no_selector_rows(
        fam, tiny_kernel):
    """Where the selector's path is ``"kernel"`` the step program holds the
    kernel's call a layer under ``attn/index`` and no gather of selector
    rows at the table's extent, as one row a page or as the pool holds a
    page; the K and V rows it gathers are still ``topk`` a slot; the page
    RMW of the token's own selector key is the one gather of the pool."""
    w, _, f32 = tiny_kernel
    model = fam.build_program_model({**w, "seq": 256}, f32, {})
    st = DecodeStepper(model, num_slots=3, paged=True, page_size=16,
                       num_pages=60)
    pbt = st._max_pages_bucket
    assert st.selector == "kernel" and pbt == 16
    assert st._pools[0][2].shape == (60, 8, 128)
    text = st._build_step_fn_paged(pbt).lower(
        st._params, st._ctx, st._pools, st._lens.copy(), np.zeros(3, bool),
        st._tables_array(pbt), *st._sampling_args()).as_text(debug_info=True)
    gathers = re.findall(r'"?stablehlo\.gather"?.*-> tensor<([0-9x]+)xf32>',
                         text)
    assert gathers.count("3x8x32") == 2 * 3  # K and V rows, a layer
    assert gathers.count("3x8x128") == 3  # the token's page, a layer
    for gone in ("3x16x1024", "3x16x8x128", "3x128x128"):
        assert gone not in gathers, gone
    assert text.count("paged_index_scores") >= 3
    assert re.search(r"attn/index[^\n]*paged_index_scores", text)


def test_the_lowered_step_of_a_streaming_stepper_sorts_and_gathers_no_rows(
        fam, tiny_stream):
    """Where ``attention`` is ``"kernel"`` too (K/V heads of 128 on pages of
    16) the step program holds the grouped kernel's call a layer under
    ``attn/sparse`` beside the selector's under ``attn/index``, and neither
    a ``lax.top_k`` of the scores (the sort of the gather body) nor a gather
    of K/V rows (rows of Hkv x Dh = 256 values), by token or at the table's
    extent: the selection travels as a mask. The page RMW of the token's own
    selector key is the one gather of a pool."""
    w, _, f32 = tiny_stream
    model = fam.build_program_model({**w, "seq": 256}, f32, {})
    st = DecodeStepper(model, num_slots=3, paged=True, page_size=16,
                       num_pages=60)
    pbt = st._max_pages_bucket
    assert st.selector == st.attention == "kernel" and pbt == 16
    assert st._step_table_buckets() == [pbt]
    assert [a.shape for a in st._pools[0]] == [
        (960, 256), (960, 256), (60, 8, 128)]
    text = st._build_step_fn_paged(pbt).lower(
        st._params, st._ctx, st._pools, st._lens.copy(), np.zeros(3, bool),
        st._tables_array(pbt), *st._sampling_args()).as_text(debug_info=True)
    # the router's top 3 of 16 is the one ``top_k`` left: none over the
    # table's 256 positions
    assert re.search(r"top_k[^\n]*tensor<3x16xf32>", text)
    assert not re.search(r"top_k[^\n]*tensor<3x256xf32>", text)
    gathers = re.findall(r'"?stablehlo\.gather"?.*-> tensor<([0-9x]+)xf32>',
                         text)
    assert not [g for g in gathers if g.endswith("x256")], gathers
    assert gathers.count("3x8x128") == 3  # the token's page, a layer
    assert text.count("paged_decode_attention") >= 3
    assert re.search(r"attn/sparse[^\n]*_paged_grouped_attention", text)
    assert re.search(r"attn/index[^\n]*paged_index_scores", text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_step_s_selection_is_select_mask_s_on_the_kernel_s_scores(dtype):
    """``select_rows`` over what ``paged_index_scores`` returns, NaN past a
    slot's length included, picks exactly the keys ``select_mask`` picks
    from the same scores: ``visible`` masks what the kernel leaves
    undefined before ``top_k`` sees it."""
    from distkeras_tpu.ops.paged_attention import paged_index_scores

    rng = np.random.default_rng(3)
    ps, di, nj, k = 16, 64, 2, 24
    lengths = np.array([5 * ps + 3, 20, 0, 7 * ps], np.int32)
    table = np.zeros((4, 8), np.int32)
    free = iter(rng.permutation(np.arange(1, 32)))
    for i, n in enumerate(-(-lengths // ps)):
        table[i, :n] = [next(free) for _ in range(n)]
    pool = rng.normal(size=(32, 8, 128)).astype(np.float32)
    pool[0] = np.nan  # the null page, which every unheld entry names
    scores = paged_index_scores(
        rng.normal(size=(4, nj, di)).astype(np.float32),
        rng.normal(size=(4, nj)).astype(np.float32),
        jnp.asarray(pool, dtype), table, lengths)
    visible = jnp.arange(8 * ps)[None, :] < lengths[:, None]
    idx, valid = gqa_moe.select_rows(scores, visible, k)
    mask = np.asarray(gqa_moe.select_mask(scores, visible, k))
    assert mask.sum(-1).tolist() == [k, 20, 0, k]
    for i in range(4):
        got = np.asarray(idx[i])[np.asarray(valid[i])]
        assert sorted(got) == list(np.nonzero(mask[i])[0]), i


def test_the_experts_shares_add_up_to_the_whole_layer(fam, tiny):
    """``experts_held`` = eight disjoint shares of the 16 routed experts:
    each share's whole layer output minus what every chip computes alike
    (the attention under its selection; there is no shared expert), summed
    over the shares and added to it, is the uncut reference's layer; and
    each share's expert layer is the reference's for the same experts."""
    w, weights, f32 = tiny
    p = f32["2"]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (40, 32))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(fam.layer(p, x, w, dot_highest)[0])
        alike = x + fam.attention(
            p["attn"], fam.rms_norm(x, p["ln1"]["gamma"], w["eps"]), w,
            dot_highest)
        u = fam.rms_norm(alike, p["ln2"]["gamma"], w["eps"])
    alike = np.asarray(alike)
    total = np.zeros_like(whole)
    for q in range(8):
        held = [2 * q, 2 * q + 1]
        blk = GroupedQueryMoEBlock(
            4, 2, 16, {"theta": 1e4}, gate=None, n_experts=16, top_k=3,
            expert_width=16, shared_width=0, qk_norm=True,
            select={"heads": 2, "head_dim": 8, "topk": 8}, experts_held=held)
        part = {**p, "ffn": {**p["ffn"], "experts": {
            k: v[np.asarray(held)] for k, v in p["ffn"]["experts"].items()}}}
        with jax.default_matmul_precision("highest"):
            y, _ = blk.apply(part, {}, x[None])
            mine, picks = blk.ffn(part["ffn"], u)
            ref = fam.expert_layer(p["ffn"], u, w, dot_highest, held=held,
                                   with_shared=False)[0]
        assert picks.sizes.shape == (2,)
        np.testing.assert_allclose(mine, ref, atol=2e-6, rtol=0)
        total += np.asarray(y)[0] - alike  # this share's routed part
    np.testing.assert_allclose(total + alike, whole, atol=5e-6, rtol=0)
    assert np.abs(total).max() > 1e-4


def test_a_block_without_a_shared_expert_builds_none_and_opens_no_scope():
    """``shared_width=0``: no ``shared`` leaves and no ``moe/shared`` scope
    in the lowered text; a block with one keeps both."""
    def lowered(shared):
        blk = GroupedQueryMoEBlock(
            4, 2, 16, {"theta": 1e4}, gate=None, n_experts=8, top_k=2,
            expert_width=16, shared_width=shared)
        p, _, _ = blk.init(jax.random.PRNGKey(0), (12, 32))
        text = jax.jit(lambda p, x: blk.apply(p, {}, x)[0]).lower(
            p, jnp.zeros((1, 12, 32))).as_text(debug_info=True)
        return p, text

    p, text = lowered(0)
    assert "shared" not in p["ffn"] and "moe/shared" not in text
    assert "moe/experts" in text and "attn/full" in text
    p, text = lowered(16)
    assert "shared" in p["ffn"] and "moe/shared" in text


def test_the_scopes_are_in_apply_chunk_and_step_alike(fam, tiny):
    """``attn/index`` and ``attn/sparse`` name the same parts in the three
    programs: the selection under the first, the gather of the selected rows
    under the second."""
    w, _, f32 = tiny
    model = _model(fam, w, f32)
    st = DecodeStepper(model, num_slots=2, paged=True, page_size=4,
                       num_pages=40)
    pbt = st._max_pages_bucket
    step = st._build_step_fn_paged(pbt).lower(
        st._params, st._ctx, st._pools, st._lens.copy(), np.zeros(2, bool),
        st._tables_array(pbt), *st._sampling_args()).as_text(debug_info=True)
    chunk = st._build_chunk_fn_paged(16, pbt).lower(
        st._params, st._pools, np.zeros((1, 16), np.int32),
        st._chunk_where(0, pbt, 0), np.int32(0)).as_text(debug_info=True)
    apply = jax.jit(lambda p, x: model.apply(p, model.state, x)[0]).lower(
        model.params, np.zeros((1, 16), np.int32)).as_text(debug_info=True)
    for text in (step, chunk, apply):
        assert "attn/index" in text and "attn/sparse" in text
        assert "moe/route" in text and "moe/experts" in text
        assert "moe/shared" not in text and "attn/full" not in text


@pytest.mark.parametrize("selector", ["gather", "kernel", "streamed"])
def test_the_serving_engine_serves_the_reference_s_tokens(
        fam, tinies, tmp_path, selector):
    """Through ``quantize_model(bits=16)``, a bundle and
    ``ServingEngine.from_bundle(paged=True)``: concurrent requests several
    ``topk`` long, prefill in chunks beside decode, greedy; every served
    token's reference logit against the reference's best; the selection's
    and the routing's counters. ``gather``: selector keys of 8 on pages of
    8, gathered; ``kernel``: keys of 64 on pages of 16, scored in place;
    ``streamed``: K/V heads of 128 besides, attended where the pages lie
    under the selection's mask (``attention`` says ``"kernel"``)."""
    from distkeras_tpu.utils.serialization import save_serving_bundle

    w, weights, f32 = tinies[selector]
    ps = {"gather": 8, "kernel": 16, "streamed": 16}[selector]
    model = quantize_model(_model(fam, w, weights), bits=16)
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, w["vocab"], n).astype(np.int32)
               for n in (5, 37, 60, 90, 12, 70)]

    def serve(attention=None):
        eng = ServingEngine.from_bundle(
            path, num_slots=4, paged=True, page_size=ps, num_pages=960 // ps,
            prefill_chunk=16)
        if attention:  # before any program is built: the step reads it then
            eng._stepper.attention = attention
        eng._stepper.warmup()
        eng._stepper.warm_prefill_buckets()
        eng.start()
        out = {}

        def go(i):
            out[i] = np.asarray(eng.generate(prompts[i], 16))

        threads = [threading.Thread(target=go, args=(i,)) for i in range(6)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        stats, health = eng.stats(), eng.health()
        eng.stop()
        return out, stats, health

    out, stats, health = serve()
    assert health["status"] == "serving" and stats["restarts"] == 0
    paged = stats["paged"]
    assert paged["layout"] == "gqa" and paged["pages_in_use"] == 0
    assert paged["selector"].startswith(SELECTORS[selector][2])
    assert paged["attention"].startswith(SELECTORS[selector][3])
    assert paged["bytes_per_token_by_kind"]["index"] == (
        3 * 4 * w["index_dim"])
    sel = stats["select"]
    assert sel["steps"] == stats["moe"]["steps"] > 0
    assert 8 * sel["steps"] <= sel["keys_selected"] < sel["keys_cached"]
    moe = stats["moe"]
    assert moe["experts_total"] == 16 and moe["zero_picks"] == 0
    assert moe["held_picks"] == moe["routed_tokens"] * 9
    if selector == "streamed":
        # heads of 128 round other picks than heads of 16 (one request
        # reads 0.11 from either body): held to the gather body's tokens,
        # the same stepper told to gather, and to its counters
        rows, gathered, _ = serve("gather: the test says so")
        assert gathered["paged"]["attention"].startswith("gather: the test")
        for count in ("keys_cached", "keys_selected"):  # steps: the mix's
            assert gathered["select"][count] == sel[count]
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            np.testing.assert_array_equal(seq, rows[i])
        return
    with jax.default_matmul_precision("highest"):
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            gaps, _ = fam.token_gaps(weights, w, seq, len(prompts[i]))
            # float32 cache, bfloat16 operands: a served token is the
            # reference's best, within the operands' rounding of it, or
            # (one request in five) a pick of 8 that the rounding swapped:
            # 0.003 to 0.02; a wrong selection reads 0.07 and more
            assert gaps.max() <= 0.04


def test_the_layer_is_found_by_name_when_a_process_loads_a_bundle_only():
    """``get_config`` / ``layer_from_config`` round-trip the new arguments
    in a process that has imported no model."""
    blk = GroupedQueryMoEBlock(
        4, 2, 16, {"theta": 1e7}, gate=None, n_experts=16, top_k=3,
        expert_width=16, shared_width=0, qk_norm=True,
        select={"heads": 2, "head_dim": 8, "topk": 8}, experts_held=[0, 1])
    code = (
        "from distkeras_tpu.models.layers import layer_from_config\n"
        f"b = layer_from_config({blk.get_config()!r})\n"
        "print(b.kind, b.qk_norm, b.shared_width, b.gate, b.select['topk'],"
        " b.get_config() == " f"{blk.get_config()!r})\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.stdout.split() == ["gqa", "True", "0", "None", "8", "True"], \
        out.stderr[-2000:]
    with pytest.raises(ValueError, match="no\\s+window"):
        GroupedQueryMoEBlock(4, 2, 16, {"theta": 1e4}, window=8, ffn_width=8,
                             select={"heads": 2, "head_dim": 8, "topk": 8})


def test_the_reference_s_first_training_loss_is_the_program_s(fam):
    """``train_readings`` follows the same forward: its first loss is the
    cross-entropy of the program's own ``apply`` on the same rows."""
    w = fam.widths(CONFIG)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       fam.make_weights(w, 7))
    batch = np.random.default_rng(4).integers(0, w["vocab"], (2, 24))
    with jax.default_matmul_precision("highest"):
        got = fam.train_readings(w, 7, [batch], lr=1e-3)
        model = _model(fam, w, f32)
        logits = model.apply(model.params, model.state, batch)[0]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -np.mean(np.take_along_axis(
        np.asarray(logp), batch[:, 1:, None], axis=-1))
    assert got["losses"][0] == pytest.approx(float(want), abs=1e-5)
    assert np.isfinite(got["change_norms"]).all()
