"""The paged decode-attention kernels against the gather bodies' arithmetic.

``ops.paged_attention.paged_decode_attention`` (run here in the Pallas
interpreter) must give what the engine's gather body gives: gather the
slot's pages at the table's extent, scores over every position, the
step's mask ``position < length``, softmax, weighted values — written
out below in NumPy float32. The kernel's products are exact in ``q`` and
the weights (three bfloat16 terms) and in the pool's values, so the two
differ by the order of float32 accumulation alone; the tolerance is that
bound and nothing wider: a ``q`` rounded to one bfloat16 term would miss
it by two orders of magnitude.

``paged_index_scores`` (the selector keys of a block that selects: a page's
keys side by side in whole tiles, no softmax) is held to
``GroupedQueryMoEBlock.index_scores`` over the gathered pages, the step's
gather body, at every position within a slot's length: to the order of the
float32 sums (both round the query to one bfloat16 term against a bfloat16
pool); what lies past a length is not compared, and no page past it is read.

``paged_latent_attention`` (the latent layout: one array of rows that
are keys and values, no head axis) is held the same way to the absorbed
attention over the gathered pages, ``models.mla_moe.attend_absorbed``'s
middle written out in NumPy float64: to the order of summation over a
float32 pool, and to the rounding of its two bfloat16 operands (the
query, the softmax weights) over a bfloat16 pool, which is what that
block's products take everywhere.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock
from distkeras_tpu.ops.paged_attention import (
    BLOCK_PAGES,
    INDEX_BLOCK_PAGES,
    LATENT_BLOCK_PAGES,
    decode_attention_path,
    index_page_shape,
    paged_decode_attention,
    paged_index_scores,
    paged_latent_attention,
)

PS, HD = 16, 128
EPS = float(np.finfo(np.float32).eps)


def _gather_body(q, ck, cv, table, lengths):
    """``DecodeStepper._kv_rows``'s gather (mask ``pos`` = length - 1),
    float32 throughout; a slot of length 0 reads zeros."""
    b, nh, hd = q.shape
    t = table.shape[1] * PS
    kg = np.asarray(ck, np.float32)[table].reshape(b, t, nh, hd)
    vg = np.asarray(cv, np.float32)[table].reshape(b, t, nh, hd)
    scores = np.einsum("bhd,bthd->bht", q, kg) / np.float32(np.sqrt(hd))
    mask = np.arange(t)[None, :] < lengths[:, None]
    scores = np.where(mask[:, None, :], scores, -np.inf)
    top = scores.max(-1, keepdims=True)
    w = np.exp(scores - np.where(np.isfinite(top), top, 0.0))
    norm = w.sum(-1, keepdims=True)
    w = w / np.where(norm == 0.0, 1.0, norm)
    return np.einsum("bht,bthd->bhd", w, vg).astype(np.float32)


def _pools(rng, num_pages, nh, dtype):
    shape = (num_pages, PS, nh, HD)
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), dtype))


def _check(q, ck, cv, table, lengths, block_pages):
    got = np.asarray(
        paged_decode_attention(q, ck, cv, table, lengths,
                               block_pages=block_pages)
    )
    want = _gather_body(q, ck, cv, table, lengths)
    # a float32 sum of n terms is off by at most n eps sum|terms|: the
    # longest sum here is the weighted values', weights summing to 1
    tol = int(lengths.max()) * EPS * float(np.abs(np.asarray(
        cv, np.float32)).max())
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    return got


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("nh", [2, 4])
def test_ragged_lengths_match_the_gather_body(nh, dtype):
    """Lengths 1, 15, 16, 17 (a page's edges), one slot at the table's
    full extent, and one slot that is not decoding: length 0 over a
    null table, which copies nothing and returns zeros."""
    rng = np.random.default_rng(nh)
    pbt, num_pages = 8, 40
    lengths = np.array([1, 15, 16, 17, pbt * PS, 0], np.int32)
    table = np.zeros((len(lengths), pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(-(-lengths // PS)):
        table[i, :n] = [next(free) for _ in range(n)]
    q = rng.normal(size=(len(lengths), nh, HD)).astype(np.float32)
    ck, cv = _pools(rng, num_pages, nh, dtype)
    got = _check(q, ck, cv, table, lengths, block_pages=4)
    assert not got[-1].any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_permuted_and_shared_pages(dtype):
    """Two slots whose tables share their leading pages (a prefix hit or
    a fork) in an order that is not the pool's, each with its own tail:
    a slot reads its own table, whatever its neighbour holds."""
    rng = np.random.default_rng(5)
    nh, pbt, num_pages = 2, 8, 24
    shared = [17, 3, 11]
    table = np.zeros((3, pbt), np.int32)
    table[0, :5] = shared + [9, 2]
    table[1, :4] = shared + [20]
    table[2, :2] = [2, 9]  # slot 0's tail, reversed
    lengths = np.array([5 * PS - 3, 3 * PS + 1, 2 * PS], np.int32)
    q = rng.normal(size=(3, nh, HD)).astype(np.float32)
    ck, cv = _pools(rng, num_pages, nh, dtype)
    _check(q, ck, cv, table, lengths, block_pages=2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("length", [4 * PS + 5, 6 * PS, 9 * PS + 16])
def test_length_ending_inside_a_block_of_pages(length, dtype):
    """Blocks of 4 pages: lengths of 5, 6 and 11 pages end one, two and
    three pages into a block; the block's other pages are not copied and
    what the buffer held before (the other slot's pages) is masked."""
    rng = np.random.default_rng(length)
    nh, pbt, num_pages = 4, 12, 32
    lengths = np.array([12 * PS, length], np.int32)
    table = np.zeros((2, pbt), np.int32)
    pages = rng.permutation(np.arange(1, num_pages))
    table[0] = pages[:pbt]
    n = -(-length // PS)
    table[1, :n] = pages[pbt:pbt + n]
    q = rng.normal(size=(2, nh, HD)).astype(np.float32)
    ck, cv = _pools(rng, num_pages, nh, dtype)
    _check(q, ck, cv, table, lengths, block_pages=4)


def test_a_table_narrower_than_a_block():
    """The default block (8 pages) over a table of 3: the block clamps
    to the table."""
    rng = np.random.default_rng(9)
    lengths = np.array([3 * PS - 1, 7], np.int32)
    table = np.array([[4, 2, 6], [1, 0, 0]], np.int32)
    q = rng.normal(size=(2, 2, HD)).astype(np.float32)
    ck, cv = _pools(rng, 8, 2, jnp.bfloat16)
    assert BLOCK_PAGES > table.shape[1]
    _check(q, ck, cv, table, lengths, block_pages=BLOCK_PAGES)


# ------------------------------------------------------- the latent layout

RANK, WIDTH, ROW = 128, 160, 256  # cn | k_pe | zeros to whole lanes
SCALE = 1.0 / np.sqrt(48.0)


def _latent_pool(rng, num_pages, dtype):
    rows = np.zeros((num_pages * PS, ROW), np.float32)
    rows[:, :WIDTH] = rng.normal(size=(num_pages * PS, WIDTH))
    return jnp.asarray(rows, dtype)


def _absorbed_over_gathered_pages(qc, pool, table, lengths):
    """``attend_absorbed``'s scores, softmax and weighted latents over a
    slot's gathered pages, float64; a slot of length 0 reads zeros."""
    pages = np.asarray(jnp.asarray(pool, jnp.float32), np.float64).reshape(
        -1, PS, ROW)
    out = np.zeros((*qc.shape[:2], RANK))
    for i, n in enumerate(lengths):
        if n:
            rows = pages[table[i]].reshape(-1, ROW)[:n]
            s = qc[i].astype(np.float64) @ rows[:, :WIDTH].T * SCALE
            w = np.exp(s - s.max(-1, keepdims=True))
            out[i] = (w / w.sum(-1, keepdims=True)) @ rows[:, :RANK]
    return out


def _latent_tables(rng, lengths, pbt, num_pages):
    table = np.zeros((len(lengths), pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(-(-lengths // PS)):
        table[i, :n] = [next(free) for _ in range(n)]
    return table


def _check_latent(qc, pool, table, lengths, block_pages):
    got = np.asarray(paged_latent_attention(
        qc, pool, table, lengths, PS, RANK, SCALE, block_pages=block_pages))
    values = float(np.nanmax(np.abs(np.asarray(pool, np.float32))))
    # float32 sums of exact products, as ``_check``
    tol = max(1, int(lengths.max())) * EPS * values
    if pool.dtype == jnp.bfloat16:
        # both operands bfloat16, as the latent block's products are
        # everywhere: the query rounded as the kernel rounds it, and
        # each softmax weight within half a bfloat16 step (2^-9) of its
        # float32 value, the weights summing to 1
        qc = np.asarray(jnp.asarray(qc, jnp.bfloat16), np.float32)
        tol += 2.0 ** -9 * values
    want = _absorbed_over_gathered_pages(qc, pool, table, lengths)
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    return got


# (lengths, the table's pages, pages a block; None: the body's default)
RAGGED = pytest.mark.parametrize("lengths,pbt,block_pages", [
    # a page's edges, a slot that is not decoding, the table's full extent
    ([1, 15, 16, 17, 0, 8 * PS], 8, 4),
    # a block's edges (4 pages), and ragged slots ending inside a block
    ([4 * PS - 1, 4 * PS, 4 * PS + 1, 9 * PS + 5, 6 * PS, 3], 12, 4),
    # a table far wider than the longest slot, not a whole number of blocks
    ([2 * PS + 3, 7, 0, PS], 37, 8),
    # a table narrower than the default block: the block clamps to it
    ([3 * PS - 1, 7], 3, None),
], ids=["page-edges", "block-edges", "wide-table", "narrow-table"])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@RAGGED
def test_latent_kernel_is_absorbed_attention_over_the_gathered_pages(
        lengths, pbt, block_pages, dtype):
    rng = np.random.default_rng(len(lengths) + pbt)
    lengths = np.array(lengths, np.int32)
    num_pages = 48
    table = _latent_tables(rng, lengths, pbt, num_pages)
    qc = rng.normal(size=(len(lengths), 4, WIDTH)).astype(np.float32)
    pool = _latent_pool(rng, num_pages, dtype)
    got = _check_latent(qc, pool, table, lengths,
                        block_pages or LATENT_BLOCK_PAGES)
    assert not got[lengths == 0].any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_kernel_never_reads_pages_past_a_slot_s_length(dtype):
    """Every page that no slot holds within its length is NaN, the null
    page and the pages that table entries past a slot's own name among
    them: none is copied, so none reaches the output."""
    rng = np.random.default_rng(11)
    lengths = np.array([5 * PS + 2, PS, 0, 2 * PS + 9], np.int32)
    num_pages, pbt = 32, 8
    table = _latent_tables(rng, lengths, pbt, num_pages)
    held = np.unique(np.concatenate(
        [table[i, :-(-n // PS)] for i, n in enumerate(lengths)]))
    rows = np.asarray(_latent_pool(rng, num_pages, jnp.float32)).reshape(
        num_pages, PS, ROW).copy()
    rows[np.setdiff1d(np.arange(num_pages), held)] = np.nan
    pool = jnp.asarray(rows.reshape(-1, ROW), dtype)
    # entries past a slot's own pages point at garbage, not at the null page
    for i, n in enumerate(-(-lengths // PS)):
        table[i, n:] = np.setdiff1d(np.arange(num_pages), held)[0]
    qc = rng.normal(size=(len(lengths), 2, WIDTH)).astype(np.float32)
    _check_latent(qc, pool, table, lengths, block_pages=4)


# ------------------------------------------- the selector keys of an indexer

NJ = 4  # the indexer's heads


def _index_pool(rng, num_pages, di, dtype, ps=PS):
    """The selector pool as the kernel holds it, ``(pages, rows, lanes)``;
    its row-major flattening a page is the gather body's one row a page."""
    flat = jnp.asarray(rng.normal(size=(num_pages, ps * di)), dtype)
    return flat.reshape(num_pages, *index_page_shape(ps, di))


def _check_index(qi, w, pool, table, lengths, block_pages, ps=PS):
    """The kernel against ``index_scores`` over the pages gathered at the
    table's extent, where a slot can see."""
    got = np.asarray(paged_index_scores(
        qi, w, pool, table, lengths, block_pages=block_pages))
    b, t = len(lengths), table.shape[1] * ps
    assert got.shape == (b, t) and got.dtype == np.float32
    rows = pool.reshape(pool.shape[0], -1)[table]  # (B, pages, ps x Di)
    want = np.asarray(GroupedQueryMoEBlock.index_scores(
        jnp.asarray(qi)[:, None], jnp.asarray(w)[:, None], rows, ps))[:, 0]
    seen = np.arange(t)[None, :] < lengths[:, None]
    assert np.isfinite(got[seen]).all()
    # float32 sums of exact products: Di terms a head, then J heads
    di = qi.shape[-1]
    values = float(np.nanmax(np.abs(np.asarray(pool, np.float32))))
    tol = (di + NJ) * EPS * values * float(
        (np.abs(w) * np.abs(qi).sum(-1)).sum(-1).max())
    np.testing.assert_allclose(got[seen], want[seen], rtol=0, atol=tol)
    return got


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@RAGGED
def test_index_kernel_is_index_scores_over_the_gathered_pages(
        lengths, pbt, block_pages, dtype):
    rng = np.random.default_rng(len(lengths) + pbt)
    lengths = np.array(lengths, np.int32)
    num_pages = 48
    table = _latent_tables(rng, lengths, pbt, num_pages)
    qi = rng.normal(size=(len(lengths), NJ, 64)).astype(np.float32)
    w = rng.normal(size=(len(lengths), NJ)).astype(np.float32)
    pool = _index_pool(rng, num_pages, 64, dtype)
    assert pool.shape == (48, 8, 128)  # two keys a row, a page a tile
    _check_index(qi, w, pool, table, lengths, block_pages)


@pytest.mark.parametrize("di,ps", [(128, 8), (32, 32), (256, 8)],
                         ids=["a-key-a-row", "four-keys-a-row",
                              "a-key-two-tiles-wide"])
def test_index_kernel_at_other_keys_a_row(di, ps):
    """A key of 128 is a row, keys of 32 lie four side by side, a key of
    256 is a row two lanes' tiles wide: the page is whole tiles each time."""
    rng = np.random.default_rng(di)
    lengths = np.array([3 * ps + 1, ps, 0, 5 * ps - 1], np.int32)
    table = np.zeros((4, 6), np.int32)
    free = iter(rng.permutation(np.arange(1, 24)))
    for i, n in enumerate(-(-lengths // ps)):
        table[i, :n] = [next(free) for _ in range(n)]
    assert decode_attention_path(
        "index", di, jnp.bfloat16, None, ps) == "kernel"
    pool = _index_pool(rng, 24, di, jnp.bfloat16, ps)
    assert pool.shape[1] % 8 == 0 and pool.shape[2] % 128 == 0
    qi = rng.normal(size=(4, NJ, di)).astype(np.float32)
    w = rng.normal(size=(4, NJ)).astype(np.float32)
    _check_index(qi, w, pool, table, lengths, block_pages=2, ps=ps)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_index_kernel_over_permuted_and_shared_pages(dtype):
    """Pages out of order in the pool, and two slots that read the same
    pages (a shared prompt) to different lengths."""
    rng = np.random.default_rng(23)
    lengths = np.array([6 * PS + 3, 4 * PS + 9, 2 * PS], np.int32)
    table = np.zeros((3, 8), np.int32)
    table[0, :7] = [17, 3, 29, 5, 11, 2, 23]
    table[1, :5] = [17, 3, 29, 5, 8]  # the first four shared with slot 0
    table[2, :2] = [31, 1]
    qi = rng.normal(size=(3, NJ, 64)).astype(np.float32)
    w = rng.normal(size=(3, NJ)).astype(np.float32)
    pool = _index_pool(rng, 32, 64, dtype)
    got = _check_index(qi, w, pool, table, lengths, block_pages=4)
    assert not np.array_equal(got[0, :4 * PS], got[1, :4 * PS])  # own query


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_index_kernel_never_reads_pages_past_a_slot_s_length(dtype):
    """As the latent body's: every page no slot holds within its length is
    NaN, the null page and what the table's later entries name among them;
    none is copied, so every score a slot can see is finite and right."""
    rng = np.random.default_rng(11)
    lengths = np.array([5 * PS + 2, PS, 0, 2 * PS + 9], np.int32)
    num_pages, pbt = 32, 8
    table = _latent_tables(rng, lengths, pbt, num_pages)
    held = np.unique(np.concatenate(
        [table[i, :-(-n // PS)] for i, n in enumerate(lengths)]))
    free = np.setdiff1d(np.arange(num_pages), held)
    rows = np.array(_index_pool(rng, num_pages, 64, jnp.float32))
    rows[free] = np.nan
    for i, n in enumerate(-(-lengths // PS)):
        table[i, n:] = free[0]
    qi = rng.normal(size=(len(lengths), NJ, 64)).astype(np.float32)
    w = rng.normal(size=(len(lengths), NJ)).astype(np.float32)
    _check_index(qi, w, jnp.asarray(rows, dtype), table, lengths,
                 block_pages=4)


def test_index_kernel_s_default_block_is_the_step_s():
    """The block the step runs with, over a table wider than it."""
    rng = np.random.default_rng(5)
    lengths = np.array([INDEX_BLOCK_PAGES * PS + 7, 3], np.int32)
    table = _latent_tables(rng, lengths, INDEX_BLOCK_PAGES + 5, 80)
    qi = rng.normal(size=(2, NJ, 64)).astype(np.float32)
    w = rng.normal(size=(2, NJ)).astype(np.float32)
    _check_index(qi, w, _index_pool(rng, 80, 64, jnp.bfloat16), table,
                 lengths, block_pages=None)


# ------------------------------------------- fewer K/V heads, and a window


def _grouped_oracle(q, ck, cv, table, lengths, first, ring, ps):
    """``models.layers.cache_attention`` a K/V head's group at a time: the
    positions ``first <= s < length`` of a slot gathered through its table
    (a ring: logical page ``p`` in column ``p % ring``), float32."""
    from distkeras_tpu.models.layers import cache_attention

    b, hq, hd = q.shape
    kvh = ck.shape[1] // hd
    out = np.zeros((b, hq, hd), np.float32)
    for i in range(b):
        pos = np.arange(first[i], lengths[i])
        if not len(pos):
            continue
        col = (pos // ps) % ring if ring else pos // ps
        rows = table[i, col] * ps + pos % ps
        k = np.asarray(ck, np.float32)[rows].reshape(1, -1, kvh, hd)
        v = np.asarray(cv, np.float32)[rows].reshape(1, -1, kvh, hd)
        g = hq // kvh
        for h in range(kvh):
            out[i, h * g:(h + 1) * g] = np.asarray(cache_attention(
                jnp.asarray(q[i:i + 1, h * g:(h + 1) * g]),
                jnp.repeat(k[:, :, h:h + 1], g, axis=2),
                jnp.repeat(v[:, :, h:h + 1], g, axis=2),
                jnp.ones((1, len(pos)), bool)))[0]
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("hq", [48, 72])
@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_grouped_queries_over_eight_kv_heads(hq, windowed, dtype):
    """The grouped body at the grouped cell's head counts (48 and 72 query
    heads over 8 K/V heads of 128), interpreted, against
    ``cache_attention`` a group at a time: without a first position over a
    growing table, and from ``max(0, length - window)`` over a ring of
    ``window / page + 1`` pages, at lengths below, at and beyond the window
    (a window of two pages: 32), a page's edges, and a slot of length 0.
    Against a float32 pool the products are float32; against a bfloat16 pool
    the query and the weights enter as one bfloat16 term, as everywhere in
    that block, and the tolerance is that rounding's."""
    rng = np.random.default_rng(hq)
    kvh, window = 8, 2 * PS
    ring = window // PS + 1 if windowed else 0
    lengths = np.array([0, 1, 15, 31, 32, 33, 48, 49, 130], np.int32)
    first = np.maximum(lengths - window, 0) if windowed else np.zeros_like(
        lengths)
    b, num_pages = len(lengths), 64
    pbt = ring or 12
    table = np.zeros((b, pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(np.minimum(-(-lengths // PS), pbt)):
        table[i, :n] = [next(free) for _ in range(n)]
    q = rng.normal(size=(b, hq, HD)).astype(np.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(num_pages * PS, kvh * HD)), dtype)
              for _ in range(2))
    got = np.asarray(paged_decode_attention(
        q, ck, cv, table, lengths, first if windowed else None,
        page_size=PS, ring=ring, block_pages=2))
    want = _grouped_oracle(q, ck, cv, table, lengths, first, ring, PS)
    big = float(np.abs(np.asarray(cv, np.float32)).max())
    tol = (130 * EPS * big if dtype == jnp.float32
           # one bfloat16 term: the scores move by 2^-9 |q . k| / sqrt(Dh)
           # (about 0.01 here) and the weights by 2^-9 of themselves
           else 0.03 * big)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    assert not got[0].any()


# which keys a slot's query reads, as the selecting step hands them on: a
# case -> (lengths, topk, scores a slot or None for seeded ones); blocks of
# two pages (32 positions), four K/V heads of 128 under 32 queries
_FAR, _NEAR = 1.0e3, -1.0e3


def _dense_under(q, ck, cv, table, chosen, kvh):
    """``attend_dense`` under ``chosen`` over every slot's pages gathered at
    the table's extent: what the selecting step's gather body computes."""
    from distkeras_tpu.models.gqa_moe import attend_dense

    b, t = chosen.shape
    hd = q.shape[-1]
    rows = (table[:, :, None] * PS + np.arange(PS)).reshape(b, t)
    return np.asarray(attend_dense(
        jnp.asarray(q)[:, None], ck[rows].reshape(b, t, kvh, hd),
        cv[rows].reshape(b, t, kvh, hd), chosen[:, None]))[:, 0]


def _selection_cases():
    ramp = np.arange(12 * PS, dtype=np.float32)
    return {
        # the k highest scores lie past the first block of 32 positions
        "first-block-empty": ([9 * PS + 3, 8 * PS], 24, [ramp, ramp]),
        # they lie in the first and the last block: nothing between
        "middle-block-empty": ([10 * PS, 7 * PS + 9], 20, [
            np.where((ramp < 10) | (ramp >= 9 * PS), _FAR - ramp, _NEAR),
            np.where((ramp < 10) | (ramp >= 6 * PS), _FAR - ramp, _NEAR)]),
        "a-slot-of-length-0": ([0, 6 * PS + 1, 0], 16, None),
        # fewer visible keys than k: every one of them is chosen
        "fewer-visible-than-topk": ([20, 3 * PS, 1], 64, None),
        "a-length-inside-a-page": ([4 * PS + 5, 2 * PS + 15, 7], 12, None),
        # eight keys AT the threshold and room for three: the lowest three
        "a-tie-at-the-threshold": ([8 * PS, 5 * PS + 2], 8, [
            np.where(ramp % 7 == 3, 2.0, np.where(ramp % 5 == 0, 1.0, 0.0)),
            np.where(ramp % 9 == 1, 2.0, 1.0)]),
    }


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(_selection_cases()))
def test_the_grouped_body_under_a_selection_is_dense_attention_under_its_mask(
        case, dtype):
    """``chosen``: the grouped body attends each slot's own pages under the
    selection's mask and gives what ``attend_dense`` gives under the same
    mask over the pages gathered at the table's extent: the exact selection
    of ``select_mask`` from scores that leave the first block of positions
    without a chosen key, a middle block without one, a slot of length 0
    (zeros), fewer visible keys than ``topk``, a length that ends inside a
    page, and a tie at the threshold. A running softmax that meets a block
    with nothing in it must not turn ``-inf - -inf`` into NaN."""
    from distkeras_tpu.models.gqa_moe import select_mask

    lengths, topk, scores = _selection_cases()[case]
    rng = np.random.default_rng(len(case))
    lengths = np.asarray(lengths, np.int32)
    b, hq, kvh, pbt, num_pages = len(lengths), 32, 4, 12, 48
    t = pbt * PS
    if scores is None:
        scores = rng.normal(size=(b, t)).astype(np.float32)
    scores = jnp.asarray(np.stack(scores)[:, :t], jnp.float32)
    visible = jnp.arange(t)[None, :] < lengths[:, None]
    chosen = select_mask(scores, visible, topk)
    counts = np.asarray(chosen).sum(-1)
    assert counts.tolist() == np.minimum(lengths, topk).tolist()
    if case.endswith("-block-empty"):
        empty = 0 if case.startswith("first") else 1
        assert not np.asarray(chosen)[:, empty * 2 * PS:(empty + 1) * 2 * PS
                                      ].any()
    table = np.zeros((b, pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(-(-lengths // PS)):
        table[i, :n] = [next(free) for _ in range(n)]
    q = rng.normal(size=(b, hq, HD)).astype(np.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(num_pages * PS, kvh * HD)), dtype)
              for _ in range(2))
    got = np.asarray(paged_decode_attention(
        q, ck, cv, table, lengths, page_size=PS, block_pages=2,
        chosen=chosen))
    want = _dense_under(q, ck, cv, table, chosen, kvh)
    big = float(np.abs(np.asarray(cv, np.float32)).max())
    # float32: the order of the sums; bfloat16: the one term the query and
    # the weights are rounded to on both sides, at another running maximum
    tol = 130 * EPS * big if dtype == jnp.float32 else 0.03 * big
    assert got.dtype == np.float32 and np.isfinite(got).all()
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=tol)
    assert not got[~live].any()
    if case == "fewer-visible-than-topk":
        # every visible key chosen: the selection masks nothing more
        np.testing.assert_array_equal(got, paged_decode_attention(
            q, ck, cv, table, lengths, page_size=PS, block_pages=2))


def test_a_selection_rides_with_heads_that_lie_side_by_side():
    """K/V heads of 64, two in a group of 128 lanes (``heads_side_by_side``):
    ``chosen`` goes through the wide query's call untouched and the lanes
    that come back are the head's own under the same mask."""
    from distkeras_tpu.models.gqa_moe import select_mask

    rng = np.random.default_rng(64)
    lengths = np.array([5 * PS + 2, 0, 3 * PS], np.int32)
    b, hq, kvh, hd, pbt, num_pages = 3, 8, 2, 64, 6, 24
    t = pbt * PS
    chosen = select_mask(
        jnp.asarray(rng.normal(size=(b, t)), jnp.float32),
        jnp.arange(t)[None, :] < lengths[:, None], 20)
    table = np.zeros((b, pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(-(-lengths // PS)):
        table[i, :n] = [next(free) for _ in range(n)]
    q = rng.normal(size=(b, hq, hd)).astype(np.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(num_pages * PS, kvh * hd)),
                          jnp.float32) for _ in range(2))
    got = np.asarray(paged_decode_attention(
        q, ck, cv, table, lengths, page_size=PS, block_pages=2,
        chosen=chosen))
    want = _dense_under(q, ck, cv, table, chosen, kvh)
    tol = 130 * EPS * float(np.abs(np.asarray(cv)).max())
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=0, atol=tol)
    assert np.isfinite(got).all() and not got[1].any()


def test_a_selection_is_one_more_operand_and_the_maskless_call_has_none():
    """Whether ``chosen`` is there is decided when the program is built: the
    maskless call's kernel takes the six operands it took (lengths, first
    positions and table prefetched, the queries, the two pools) and its body
    compares nothing with a selection; under ``chosen`` the slot's row
    stands before the pools. A selection goes with no first position and no
    ring (its positions are the table's own, from 0)."""
    import jax

    q = np.zeros((2, 16, HD), np.float32)
    ck = cv = jnp.zeros((8 * PS, 2 * HD), jnp.bfloat16)
    table, lengths = np.zeros((2, 4), np.int32), np.array([5, 40], np.int32)

    def call_of(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: paged_decode_attention(
            *a, page_size=PS, **kw))(q, ck, cv, table, lengths)
        (inner,) = [e.params["jaxpr"] for e in jaxpr.eqns
                    if "jaxpr" in e.params]  # the jitted wrapper
        (call,) = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
        return call

    plain = call_of()
    assert [v.aval.shape for v in plain.invars] == [
        (2,), (2,), (8,), (2, 16, HD), (8 * PS, 2 * HD), (8 * PS, 2 * HD)]
    body = str(plain.params["jaxpr"])
    masked = call_of(chosen=np.ones((2, 4 * PS), bool))
    assert [v.aval.shape for v in masked.invars] == [
        (2,), (2,), (8,), (2, 16, HD), (2, 1, 1, 4 * PS),
        (8 * PS, 2 * HD), (8 * PS, 2 * HD)]
    # the row's test and the guard of a block with nothing in it: one
    # comparison and one choice more than the maskless body, which has
    # neither
    for op, more in ((" ne ", 1), ("name=_where", 1), (" exp ", 0)):
        assert str(masked.params["jaxpr"]).count(op) == body.count(op) + more
    # a block under a selection keeps the grouped cell's bytes (16 pages of
    # 2 KB rows): 64 pages of these bfloat16 rows of 512 bytes, 32 of the
    # same rows in float32
    wide = np.zeros((2, 128), np.int32)
    for pool, blocks in ((ck, 2), (ck.astype(jnp.float32), 4)):
        call = jax.make_jaxpr(lambda *a: paged_decode_attention(
            *a, page_size=PS, chosen=np.ones((2, 128 * PS), bool)))(
                q, pool, pool, wide, lengths)
        assert f"i32[2,{blocks},1,{128 // blocks * PS}]" in str(call)
    for first, ring in ((None, 4), (lengths, 0)):
        with pytest.raises(ValueError, match="no first position and no ring"):
            paged_decode_attention(
                q, ck, cv, table, lengths, first, page_size=PS, ring=ring,
                chosen=np.ones((2, 4 * PS), bool))


def test_a_4d_pool_of_fewer_kv_heads_is_the_flat_pool():
    """``(pages, page, Hkv, Dh)`` as the docstring gives the pool: accepted,
    and the same numbers as its row-major flattening."""
    rng = np.random.default_rng(3)
    lengths = np.array([20, 0, 40], np.int32)
    table = np.array([[3, 5, 0], [0, 0, 0], [2, 7, 1]], np.int32)
    q = rng.normal(size=(3, 12, HD)).astype(np.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(8, PS, 2, HD)), jnp.float32)
              for _ in range(2))
    flat = [c.reshape(8 * PS, 2 * HD) for c in (ck, cv)]
    np.testing.assert_array_equal(
        paged_decode_attention(q, ck, cv, table, lengths),
        paged_decode_attention(q, *flat, table, lengths, page_size=PS))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_equal_heads_without_a_first_position_is_the_kernel_it_was(
        dtype, monkeypatch):
    """``Hq == Hkv``, a 4-D pool, no first position, no ring, at
    ``serve_backlog``'s shapes (32 slots, 16 heads of 128): the body PR 29
    wrote is the one that runs (the grouped one is not entered), and it
    matches the gather body to the tolerance this file has held since."""
    from distkeras_tpu.ops import paged_attention as pa

    def never(*a, **kw):
        raise AssertionError("the grouped body ran for equal heads")

    monkeypatch.setattr(pa, "_paged_grouped_attention", never)
    rng = np.random.default_rng(29)
    b, nh, pbt, num_pages = 32, 16, 8, 160
    lengths = rng.integers(1, pbt * PS + 1, b).astype(np.int32)
    lengths[5] = 0
    table = np.zeros((b, pbt), np.int32)
    free = iter(rng.permutation(np.arange(1, num_pages)))
    for i, n in enumerate(-(-lengths // PS)):
        table[i, :n] = [next(free) for _ in range(n)]
    q = rng.normal(size=(b, nh, HD)).astype(np.float32)
    ck, cv = _pools(rng, num_pages, nh, dtype)
    got = _check(q, ck, cv, table, lengths, block_pages=BLOCK_PAGES)
    assert not got[5].any()


@pytest.mark.parametrize(
    "layout,head_dim,kv_dtype,mesh,want",
    [
        ("kv", 128, jnp.bfloat16, None, "kernel"),
        ("kv", 256, jnp.float32, None, "kernel"),
        ("kv", 16, jnp.float32, None, "gather: heads of 16"),
        ("kv", 64, jnp.bfloat16, None, "gather: heads of 64"),
        ("kv", 128, jnp.float16, None, "gather: no kernel for a float16"),
        ("latent", None, jnp.bfloat16, None, "kernel"),
        ("kv", 128, jnp.bfloat16, object(), "gather: Mosaic kernels"),
        ("latent", None, jnp.float32, None, "kernel"),
        ("latent", None, jnp.float16, None, "gather: no kernel for a float16"),
        ("latent", None, jnp.float8_e4m3fn, None,
         "gather: no kernel for a float8_e4m3fn"),
        ("latent", None, jnp.bfloat16, object(), "gather: Mosaic kernels"),
        ("window", 128, jnp.bfloat16, None, "gather: the window page"),
        ("gqa", 128, jnp.bfloat16, None, "kernel"),
        ("gqa", 128, jnp.float32, None, "kernel"),
        ("gqa", 64, jnp.bfloat16, None, "gather: heads of 64"),
        ("gqa", 128, jnp.float16, None, "gather: no kernel for a float16"),
        ("gqa", 128, jnp.bfloat16, object(), "gather: Mosaic kernels"),
        ("index", 64, jnp.bfloat16, None, "kernel"),
        ("index", 128, jnp.float32, None, "kernel"),
        ("index", 32, jnp.bfloat16, None, "kernel"),
        ("index", 8, jnp.float32, None, "kernel"),  # by the key alone
        ("index", 48, jnp.bfloat16, None, "gather: selector keys of 48"),
        ("index", 192, jnp.bfloat16, None, "gather: selector keys of 192"),
        ("index", 64, jnp.float16, None, "gather: no kernel for a float16"),
        ("index", 64, jnp.bfloat16, object(), "gather: Mosaic kernels"),
    ],
)
def test_where_the_kernel_engages(layout, head_dim, kv_dtype, mesh, want):
    got = decode_attention_path(layout, head_dim, kv_dtype, mesh)
    assert got.startswith(want), got


@pytest.mark.parametrize("page_size,want", [
    (16, "kernel"), (8, "kernel"),
    (4, "gather: latent pages of 4 rows"),
    (12, "gather: latent pages of 12 rows"),
])
def test_a_latent_page_is_whole_tiles_of_the_pool(page_size, want):
    """A latent page is ``page_size`` rows of the flat pool and a copy
    covers whole tiles (Mosaic refuses less, ``test_chip_compile``); the
    ``"kv"`` layout's page is a leading index and takes any size."""
    got = decode_attention_path("latent", None, jnp.bfloat16, None, page_size)
    assert got.startswith(want), got
    grouped = decode_attention_path("gqa", 128, jnp.bfloat16, None, page_size)
    assert grouped.startswith(want.replace("latent", "grouped")), grouped
    assert decode_attention_path(
        "kv", 128, jnp.bfloat16, None, page_size) == "kernel"


@pytest.mark.parametrize("head_dim,page_size,want", [
    (64, 16, "kernel"),  # the selecting cell's: 8 rows of two keys
    (64, 32, "kernel"), (128, 8, "kernel"), (32, 32, "kernel"),
    (64, 8, "gather: selector pages of 8 keys of 64"),  # 4 rows
    (8, 4, "gather: selector pages of 4 keys of 8"),  # a quarter of a row
    (64, 24, "gather: selector pages of 24 keys of 64"),  # 12 rows
    (128, 4, "gather: selector pages of 4 keys of 128"),
])
def test_a_selector_page_is_whole_tiles_of_the_pool(head_dim, page_size, want):
    """A page of selector keys is ``page_size x Di`` values as rows of
    whole lanes, and a copy covers whole tiles of 8 rows (Mosaic refuses
    less, ``test_chip_compile``)."""
    got = decode_attention_path(
        "index", head_dim, jnp.bfloat16, None, page_size)
    assert got.startswith(want), got
    if got == "kernel":
        rows, lanes = index_page_shape(page_size, head_dim)
        assert rows % 8 == 0 and lanes % 128 == 0
        assert rows * lanes == page_size * head_dim


# ------------------------------------------------------ through the engine


def test_engine_with_128_wide_heads_decodes_through_the_kernel():
    """A small ``transformer_lm`` with heads of 128 on a paged engine:
    ``stats()["paged"]["attention"]`` says ``"kernel"``, one step
    program is compiled (at the widest table), and concurrent greedy
    requests decode the tokens of the solo ``CachedSequenceGenerator``,
    chunked prefill and mixed lengths included."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving import ServingEngine

    lm = zoo.transformer_lm(
        vocab_size=61, seq_len=48, d_model=256, num_heads=2, depth=2,
        seed=0,
    )
    ref = CachedSequenceGenerator(lm)
    eng = ServingEngine(lm, num_slots=3, paged=True, page_size=4,
                        prefill_chunk=4)
    eng.start()
    try:
        assert eng.stats()["paged"]["attention"] == "kernel"
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 61, n).astype(np.int32)
                   for n in (5, 1, 19, 30)]
        reqs = [eng.submit(p, 9) for p in prompts]
        for p, r in zip(prompts, reqs):
            want = ref.generate(p[None], steps=9)[0]
            np.testing.assert_array_equal(np.asarray(r.result()), want)
        paged = eng.stats()["paged"]
        assert paged["attention"] == "kernel"
        assert paged["compiled_step_buckets"] == [(16, False)]
    finally:
        eng.stop()


def test_engine_with_16_wide_heads_keeps_the_gather_body():
    """The suite's fixtures (heads of 16) stay on the gather body, with
    its pow2 table buckets, and say why."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.serving.engine import DecodeStepper

    lm = zoo.transformer_lm(
        vocab_size=61, seq_len=32, d_model=32, num_heads=2, depth=2,
        seed=0,
    )
    st = DecodeStepper(lm, num_slots=2, paged=True, page_size=4)
    why = st.paged_stats()["attention"]
    assert why.startswith("gather: heads of 16"), why
    assert st._step_table_buckets() == [1, 2, 4, 8]
    st.warmup()
    assert st.paged_stats()["compiled_step_buckets"] == [
        (1, False), (2, False), (4, False), (8, False)
    ]
