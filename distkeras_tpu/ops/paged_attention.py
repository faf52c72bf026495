"""Paged decode attention — one query a slot over the slot's own pages.

The serving engine's paged decode step (``serving/engine.py``
``_build_step_fn_paged``) holds every layer's cache in a pool of pages
and a page table ``(B, pages)`` a step. Its gather bodies read
``pool[table]`` at the extent of the longest table for every slot,
write that out, convert it and multiply it. The kernels here read each
slot's pages where they lie: the pool stays in HBM
(``memory_space=ANY``), the table and the lengths are scalar-
prefetched, and a program (one a slot) copies a block of pages at a
time into a double-buffered VMEM block, one asynchronous copy a page,
the next block in flight while the current one is multiplied and
folded into a running softmax. A slot's loop ends at ``ceil(length /
page_size)`` pages; a slot of length 0 copies nothing and returns
zeros. There is one body a page layout, and they share the copy
scaffolding's shape and ``_product``, nothing else:

**Layout ``"kv"``** (``paged_decode_attention``, ``_kernel``): pools
``(num_pages, page_size, H, Dh)`` of keys and of values.

*The products keep the data where the copy put it.* A page is
``(page_size, H, Dh)`` with every head in it, so a block flattens (for
free: ``H`` fills the sublane tile) to ``(tokens x H, Dh)`` rows. The
scores are ``q (H, Dh) @ rows^T -> (H, tokens x H)``: every head's
query against every head's keys, of which the entries with the row's
own head (column ``t * H + h`` in row ``h``) are kept and the rest
masked to ``-inf`` with the positions past the length. The masked
weights times the same flattening of the values' block is then exactly
``sum_t w[h, t] v[t, h]``. That is ``H`` times the multiplications the
attention needs, on a step bound by HBM (the issue's reckoning: 48e9
operations a step, 0.25 ms of the MXU at the benchmark's widths).

*Precision.* Keys and values enter the products in the pool's dtype
(bfloat16 or float32). ``q`` and the softmax weights are float32; each
is split exactly into three bfloat16 terms (8 + 8 + 8 bits of mantissa)
stacked along the rows of one product, so every product is bfloat16 x
pool dtype accumulated in float32 and their sum is the float32 operand's
product: finer than the one bfloat16 pass JAX's default precision gives
the gather body on a TPU, equal to the float32 arithmetic it has on the
CPU up to the order of summation. Scale, mask, running maximum,
normaliser and output are float32.

**Layout ``"latent"``** (``paged_latent_attention``, ``_latent_kernel``;
PR 31): ONE pool ``(num_pages x page_size, row)`` of latent rows ``[cn |
k_pe | zeros to whole lanes]``, a page its ``page_size`` consecutive
rows, no head axis. The absorbed attention of ``models.mla_moe``
(``attend_absorbed``) reads a row twice: whole as the key of every
head, and its first ``rank`` columns as the value of every head.

*No diagonal to keep.* A block is ``(tokens, row)`` as copied; the
scores are ``qc (H, row) @ block^T -> (H, tokens)``, every entry one the
attention needs, and the weights times the same block's leading columns
(a lane-aligned slice) are ``o_lat (H, rank)``. Each page is copied
once. What precedes (``q_nope Wuk^T``) and follows (``Wuv``, ``wo``)
stays in XLA. A page starts on a tile of the pool, so ``page_size`` is
a whole number of 8 rows (Mosaic refuses less).

*Precision.* The absorbed query and the softmax weights enter as ONE
bfloat16 term against a bfloat16 pool, accumulated in float32: what
``models.mla_moe._operands`` gives every product of that block, and
what the gather body gave these two; three terms cost 18% of the
kernel on the chip (PERF.md §6, PR 31). Against a float32 pool both
products are float32 at ``HIGHEST``. Scale, mask, running maximum,
normaliser and output are float32.

**Layout ``"gqa"``** (``paged_decode_attention`` with fewer K/V heads than
query heads, a first position, a ring, or a selection; ``_grouped_kernel``;
PR 35): pools
``(num_pages x page_size, Hkv x Dh)``, the row-major flattening of
``(num_pages, page_size, Hkv, Dh)``, held flat for the reason the latent
pool is (a 4-D bfloat16 pool of 8 heads tiles its two minor axes ``(8, 128)``
in pairs of rows, and no reshape of it is free). A page is ``page_size``
rows of the pool and a K/V head is a lane-aligned slice ``[h Dh, (h + 1) Dh)``
of a row, so a copied block of pages gives each K/V head's keys as ``(tokens,
Dh)`` with no relayout.

*Products grouped by K/V head.* A K/V head's ``G = Hq / Hkv`` queries (padded
to whole sublane tiles: 6 -> 8, 9 -> 16) against that head's keys: ``(G, Dh)
@ (tokens, Dh)^T``, every entry one the attention needs, and the weights times
the same head's slice of the values' block. PR 29's trick (every query against
every head's keys, the diagonal kept) would cost ``Hkv`` = 8 times that. *What
a block costs* at blocks of 16 pages of 16 tokens, 8 K/V heads of 128, a
bfloat16 pool: 1 MiB of keys and values (1.28 us at the v5e's 819 GB/s) and
``2 x 2 x Hq x Dh x 256`` operations: 6.3e6 at ``Hq`` 48, 9.4e6 at ``Hq`` 72
(0.03 and 0.05 us at the MXU's peak; but an operand of 8 or 16 rows fills a
sixteenth or an eighth of a pass, and each head's 128 x 128 tiles of keys and
of values are loaded for it: 32 tile loads a block, the same number a byte as
PR 29's body, which reads 72% of the bandwidth). A window layer's slot copies
the pages from ``first // page_size`` on and no others: at most ``window /
page_size + 1`` = 33, in three blocks of 11.

*A first position and a ring.* ``first`` ``(B,)`` is the first position a slot
attends (a window layer: ``max(0, length - window)``); the loop starts at its
page and masks the positions before it. With ``ring`` the table has ``ring``
columns and logical page ``p`` lies in column ``p % ring``: a window layer's
pages are overwritten in place once they are behind the window
(``serving/engine.py`` ``_build_grouped_pools``).

*Under a selection* (``chosen``, PR 42): a block whose queries read the keys
an indexer picks (``models.gqa_moe``, ``select``) hands its selection on as a
mask ``(B, T)`` over the slot's logical positions, and the same body attends
the slot's own pages under it: a program takes its slot's row as ``(blocks, 1,
block_pages x page_size)`` int32 in VMEM (192 KB at a table of 3,072 pages),
block ``i`` reads part ``i`` (whole lanes, no dynamic lane offset), and the
``where`` that masks by ``first`` and ``length`` masks by it too. No key is
approximated and none skipped: the softmax runs over exactly the chosen keys,
as ``attend_selected`` has it in the chunk. It reads EVERY cached page of the
slot where the gather body read ``topk`` rows, ten times the bytes at a tenth
of the keys chosen, and is the faster because pages stream where rows are
paid for one by one (XLA's gather: 14 ns a row of 1 KB, 73 GB/s). *What a
block costs* at the selecting cell's rows (4 K/V heads of 128: a row is 1 KB,
a page 16 KB a pool, half the grouped cell's): the same products a byte as
above, and twice the copies a byte, which is what paces it: 36,677 pages a
layer (587,000 cached keys on 32 slots, 1.2 GB) take **2.42 ms at blocks of
16 pages, 2.14 at 32, 2.03 at 64** where the memory alone would take 1.47
(my chip runs, PR 42, the cell's own step program: 58 ns a page of two
copies at 32, some 26 ns a copy as PR 40 found for copies under a test, and
0.2 us a block). Under a selection a block therefore keeps the grouped
cell's bytes and not its pages (32 pages of these rows; 64 pages cost every
process that builds the step program 4 s more of tracing, 384 copy sites,
for 5% of the kernel). Whole blocks copied without a test a page read 1.90
ms at 32 and 1.81 at 64 for 3 to 8 s more of tracing: left out. The gather
body it replaces took 1.84 ms of gathers and 1.33 of attention a layer,
after a sort of 1.37.

*A block with nothing in it.* Without a selection block 0 holds position
``first`` and every row's running maximum is finite from the start; under
one a block of 512 positions may hold no chosen key, the first block too,
``m_new`` stays ``-inf`` and ``exp(-inf - -inf)`` is NaN: the masked form
subtracts 0 where the maximum is not finite yet (``attend_selected``'s
``safe``). Whether ``chosen`` is there is decided when the program is
built; without it the call has the operands and the body it had.

*Precision.* As the latent body: the query and the softmax weights enter as
ONE bfloat16 term against a bfloat16 pool (what ``models.mla_moe._operands``
gives every product of that block), float32 at ``HIGHEST`` against a float32
pool; scale, mask, running maximum, normaliser and output are float32.

**Layout ``"index"``** (``paged_index_scores``, ``_index_kernel``; PR 40):
the selector keys of a block whose queries read the keys an indexer picks
(``models.gqa_moe``): ONE pool ``(num_pages, rows, 128)``, a page its
``page_size`` keys of ``Di`` values in order as ``rows`` rows of whole lanes,
``128 / Di`` keys side by side a row (``index_page_shape``: at 16 keys of 64
a page is one ``(8, 128)`` bfloat16 tile of 2 KB, the same bytes as the
gather body's one row of 1,024 values a page). No softmax and no values:
the body returns the scores themselves, ``(B, T)`` float32, and the exact
``top_k`` stays in XLA.

*What a block is.* ``block_pages`` pages as copied are ``(block_pages x
rows, 128)``: row ``r`` of page ``p`` holds keys ``r x 128 / Di ..`` of the
page. The query ``qi (J, Di)`` goes against each key of a row with zeros
beside it, as ``index_scores(..., packed)`` builds its parts: ``(128 / Di x
J, 128) @ block^T -> (128 / Di x J, block rows)``, ``relu``, times ``w``,
summed over the ``J`` heads of each part: ``(128 / Di, block rows)`` a
block, written to the block's own place of the output; the one
transposition from part-major to positions stays in XLA (6 MB a layer at
the selecting cell's shapes). *What it costs* there (32 slots, 16 heads of
64, blocks of 64 pages): 128 KB copied and ``2 x 32 x 128 x 512`` = 4.2e6
operations a block, twice the multiplications the scores need; 75e6 bytes
a layer at the cell's lengths are 0.09 ms of the v5e's bandwidth and the
products 0.02 ms of its MXU: the body is bound by the pace at which its
copies are issued, one of 2 KB a page, some 36,600 a layer: 17 ns a copy
where every copy site is unrolled, 38 ns at one copy an iteration of a
loop, 21 ns at ``_INDEX_UNROLL`` = 8 copies an iteration, which is how
they go (0.76 ms a layer; PERF.md §6, PR 40): wholly unrolled, the
body's 400 copy sites cost every process that builds the step program
seconds of tracing, 12 s of a warm set-up. A page is copied once;
what a slot's last, short block does not copy keeps what the buffer held,
which only reaches scores past the slot's length (a score is one row's
alone), and those are left undefined: the step masks by ``visible``
before ``top_k``. A slot of length 0 copies nothing.

*Precision.* As the gather body's ``index_scores``: the query enters as
ONE bfloat16 term against a bfloat16 pool, accumulated in float32
(``models.mla_moe._operands``); float32 at ``HIGHEST`` against a float32
pool; ``relu``, the weights ``w``, the sum over heads and the output are
float32. No score is approximated and no key skipped.

``decode_attention_path`` is the one place that says whether a kernel
serves a shape (as ``flash_attention.effective_path`` does for the
trainer's kernel); the engine reads it when it builds its step program.
Mosaic-compiled on a TPU, interpreted anywhere else
(``ops.kernel_mode.pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.kernel_mode import pallas_interpret

# pages a block: chosen on the v5e at the benchmark's shapes (16 tokens x
# 16 heads x 128 a page); the readings are in PERF.md §6 (PR 29)
BLOCK_PAGES = 8
# q and the softmax weights as this many bfloat16 terms (3 = exact)
_TERMS = 3
_LANES = 128
_SUBLANES = 8


def heads_side_by_side(head_dim, kv_heads) -> int:
    """How many K/V heads of ``head_dim`` lie side by side in one group of
    128 lanes of a flat pool's row, where whole groups hold all ``kv_heads``
    of them: 1 for heads that are a whole number of 128 lanes, 2 for 8 heads
    of 64, 0 where no grouping fits (heads of 96; one K/V head of 64).
    :func:`paged_decode_attention` then reads such a group as ONE head of 128
    lanes, each query zero outside its own head's lanes."""
    if not head_dim or not kv_heads:
        return 0
    if head_dim % _LANES == 0:
        return 1
    if _LANES % head_dim:
        return 0
    side = _LANES // head_dim
    return side if kv_heads % side == 0 else 0


def decode_attention_path(layout, head_dim, kv_dtype, mesh=None,
                          page_size=None, kv_heads=None):
    """``"kernel"`` where a kernel of this module serves the paged decode
    step (:func:`paged_decode_attention` for layout ``"kv"``,
    :func:`paged_latent_attention` for ``"latent"``, the grouped body of
    :func:`paged_decode_attention` for ``"gqa"``, and for ``"index"``,
    the selector keys of a block that selects, with ``head_dim`` the
    selector key's, :func:`paged_index_scores`), else ``"gather:
    <why>"`` — read from what the stepper can see of itself, never from
    a knob or a model's name. A model with layers that cache nothing a
    token (a state a slot: the stepper's ``"ssm"`` layout) asks as
    ``"gqa"``, with the K/V heads of its layers that DO cache rows: the
    layers that hold a state have no pages, and no say here. (A layer's own
    softmax scale is no ground for the gather: the kernels have ``1 /
    sqrt(head_dim)`` written in, and the stepper folds another scale into
    the query.)"""
    if mesh is not None:
        return "gather: Mosaic kernels are not partitioned over a mesh"
    if layout == "kv":
        if head_dim % _LANES:
            return (f"gather: heads of {head_dim} are not a whole number "
                    f"of {_LANES} lanes")
    elif layout == "latent":
        # a latent page is ``page_size`` rows of the flat pool, and a
        # copy starts and ends on a tile of the pool (Mosaic refuses it)
        if page_size is not None and page_size % _SUBLANES:
            return (f"gather: latent pages of {page_size} rows are not "
                    f"whole tiles of {_SUBLANES} rows")
    elif layout == "gqa":
        # a grouped page is ``page_size`` rows of ``Hkv x Dh`` values in
        # the flat pool: a head is a lane-aligned slice of a row, and a
        # copy starts and ends on a tile of the pool, as for the latent
        # (narrower heads that fill the lanes side by side, ``kv_heads``
        # given: the grouped body reads each group as one head)
        if head_dim % _LANES and not heads_side_by_side(head_dim, kv_heads):
            return (f"gather: heads of {head_dim} are not a whole number "
                    f"of {_LANES} lanes")
        if page_size is not None and page_size % _SUBLANES:
            return (f"gather: grouped pages of {page_size} rows are not "
                    f"whole tiles of {_SUBLANES} rows")
    elif layout == "index":
        # a page of selector keys is ``page_size x head_dim`` values held
        # as rows of whole lanes, keys side by side, and a copy starts and
        # ends on a tile of the pool, as for the latent
        lanes = _index_lanes(head_dim)
        if lanes is None:
            return (f"gather: selector keys of {head_dim} do not tile "
                    f"rows of {_LANES} lanes")
        if page_size is not None and page_size * head_dim % (
                _SUBLANES * lanes):
            return (f"gather: selector pages of {page_size} keys of "
                    f"{head_dim} are not whole tiles of {_SUBLANES} rows "
                    f"of {lanes}")
    else:
        return f"gather: the {layout} page layout has its own stage body"
    if jnp.dtype(kv_dtype) not in (jnp.dtype(jnp.bfloat16),
                                   jnp.dtype(jnp.float32)):
        return f"gather: no kernel for a {jnp.dtype(kv_dtype).name} pool"
    return "kernel"


def _product(x, pages, contract, n_terms=_TERMS):
    """float32 ``x (R, C)`` times a block ``pages`` in the pool's dtype,
    contracting ``x``'s columns with ``pages``' axis ``contract``,
    accumulated in float32. Against a bfloat16 pool ``x`` goes as
    ``n_terms`` bfloat16 terms stacked along the rows of ONE product
    (the block is the MXU's stationary operand either way) whose row
    groups are then summed: exact in ``x`` at three terms."""
    dims = (((1,), (contract,)), ((), ()))
    if pages.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            x, pages, dims, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    terms, rest = [], x
    for _ in range(n_terms):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    out = jax.lax.dot_general(
        jnp.concatenate(terms, axis=0), pages, dims,
        preferred_element_type=jnp.float32,
    )
    rows = x.shape[0]
    return sum(out[i * rows:(i + 1) * rows] for i in range(n_terms))


def _kernel(block_pages, pbt, lens_ref, table_ref, q_ref, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sems):
    b = pl.program_id(0)
    _, ps, nh, hd = k_hbm.shape
    scale = 1.0 / (hd ** 0.5)
    cols = block_pages * ps * nh  # a block's rows: (token, head) pairs
    length = lens_ref[b]
    npages = (length + ps - 1) // ps
    nblocks = (npages + block_pages - 1) // block_pages

    @pl.when(b == 0)
    def _():
        # a page that a short block does not copy keeps what the buffer
        # held; that is masked, but 0 x NaN is NaN in the values' product
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(i, slot, j):
        page = table_ref[b * pbt + i * block_pages + j]
        return (
            pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, j], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[slot, j], sems.at[1, slot]
            ),
        )

    def for_block(i, slot, act):
        """Start (or wait for) the copies of block ``i``'s own pages."""
        for j in range(block_pages):
            @pl.when(i * block_pages + j < npages)
            def _():
                for c in copies(i, slot, j):
                    act(c)

    @pl.when(nblocks > 0)
    def _():
        for_block(0, 0, lambda c: c.start())

    # which entries of a block's (H, tokens x H) scores are the row's own
    # head's, and which token each column is: the same for every block
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 0)
    own = col % nh == row
    tok = col // nh
    q = q_ref[0].astype(jnp.float32)  # (H, Dh)

    def block(i, carry):
        acc, m, l = carry
        slot = i % 2

        @pl.when(i + 1 < nblocks)
        def _():
            for_block(i + 1, 1 - slot, lambda c: c.start())

        for_block(i, slot, lambda c: c.wait())
        k = kbuf[slot].reshape(cols, hd)
        v = vbuf[slot].reshape(cols, hd)
        s = _product(q, k, 1) * scale  # (H, cols)
        keep = own & (tok < length - i * block_pages * ps)
        s = jnp.where(keep, s, -jnp.inf)
        # block 0 holds position 0, so every row's maximum is finite
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # 0 where masked
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = _product(p, v, 0)  # (H, Dh)
        return acc * corr + pv, m_new, l_new

    acc, _, l = jax.lax.fori_loop(
        0, nblocks, block,
        (
            jnp.zeros((nh, hd), jnp.float32),
            jnp.full((nh, 1), -jnp.inf, jnp.float32),
            jnp.zeros((nh, 1), jnp.float32),
        ),
    )
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def _paged_decode_attention(q, ck, cv, table, lengths, *, block_pages,
                            interpret):
    b, nh, hd = q.shape
    _, ps, _, _ = ck.shape
    pbt = table.shape[1]
    # the table's columns cover whole blocks; the pad is never read (a
    # block's pages past the slot's own are not copied)
    pad = -pbt % block_pages
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    kernel = functools.partial(_kernel, block_pages, pbt + pad)
    buf = (2, block_pages, ps, nh, hd)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, nh, hd), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, nh, hd), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(buf, ck.dtype),
                pltpu.VMEM(buf, cv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the zeroed buffers and the slots' turns are in order
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        lengths.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
        q, ck, cv,
    )


def paged_decode_attention(q, ck, cv, table, lengths, first=None, *,
                           page_size=None, ring=0, block_pages=None,
                           chosen=None):
    """Single-query attention of ``B`` slots over a paged pool.

    ``q``: ``(B, Hq, Dh)``; ``ck``, ``cv``: the pools of keys and of
    values, bfloat16 or float32, ``(num_pages, page_size, Hkv, Dh)`` with
    ``Hq`` a multiple of ``Hkv``, as the engine holds them: 4-D where
    ``Hkv == Hq`` (the GPT-2 block), and for fewer K/V heads than query
    heads as that array's row-major flattening ``(num_pages x page_size,
    Hkv x Dh)`` with ``page_size`` given (a 4-D pool of fewer K/V heads
    is accepted and flattened here, which on a TPU is a copy of the
    pool: for tests, not for a step); ``table``: ``(B, pages)`` int32, a
    slot's pages in logical order (entries past ``ceil(length /
    page_size)`` are never read), or with ``ring`` ``(B, ring)``: the
    slot's ring, logical page ``p`` in column ``p % ring``; ``lengths``:
    ``(B,)`` int32, the positions a slot attends (its own newest
    included), 0 for a slot that is not decoding; ``first``: ``(B,)``
    int32, the first position a slot attends (None: 0; a window layer:
    ``max(0, length - window)``): pages wholly before it are not copied;
    ``chosen``: ``(B, pages x page_size)`` bool (or anything whose nonzero
    means chosen), which of its positions a slot's query reads (None:
    all): the exact selection of a block that selects
    (``models.gqa_moe.select_mask``), with no first position and no ring.
    Returns ``(B, Hq, Dh)`` float32: ``softmax(q . k / sqrt(Dh)) . v``
    over positions ``first <= s < length`` (that are chosen) with query
    head ``j`` on K/V head ``j // (Hq / Hkv)``, zeros where the length is
    0 or nothing is chosen.

    With ``Hkv == Hq``, a 4-D pool, no first position, no ring and no
    selection this is ``_kernel``, the program it was before K/V heads
    could be fewer.
    Everything else is ``_grouped_kernel``. Heads narrower than the 128
    lanes that lie side by side in a flat row (``heads_side_by_side``: 8 K/V
    heads of 64 are 4 groups of 128) go through the same body with each
    group read as ONE head: a query is zero outside its own head's lanes,
    so its scores are its own head's, and of the group's weighted values
    its own head's lanes are kept. No key is moved and the kernel is what
    it was; it multiplies twice the values it needs, which a step that
    waits for memory does not feel."""
    if chosen is not None and (first is not None or ring):
        raise ValueError(
            "a selection is over the table's own positions from 0: it goes "
            "with no first position and no ring")
    if ck.ndim == 4 and ck.shape[2] == q.shape[1] and first is None \
            and not ring and chosen is None:
        return _paged_decode_attention(
            q, ck, cv, table, lengths,
            block_pages=min(int(block_pages or BLOCK_PAGES),
                            max(1, table.shape[1])),
            interpret=pallas_interpret(),
        )
    if ck.ndim == 4:
        page_size = ck.shape[1]
        ck, cv = (c.reshape(c.shape[0] * c.shape[1], -1) for c in (ck, cv))
    hd = q.shape[-1]
    side = heads_side_by_side(hd, ck.shape[1] // hd)
    if side > 1:
        return _narrow_heads(q, ck, cv, table, lengths, first, side,
                             page_size=page_size, ring=ring,
                             block_pages=block_pages, chosen=chosen)
    bp = int(block_pages or GROUPED_BLOCK_PAGES)
    if chosen is not None and not block_pages:
        # under a selection a block keeps the grouped cell's BYTES, so
        # narrower rows go more pages a block (the selecting cell's rows of
        # 1 KB: 32). Measured there alone (PERF.md §6, PR 42); the maskless
        # programs keep the block they were measured at
        bp *= max(1, _GROUPED_ROW_BYTES // (ck.shape[1] * ck.dtype.itemsize))
    if ring:
        # equal blocks that cover the ring: 33 pages go 11 at a time
        bp = -(-int(ring) // -(-int(ring) // bp))
    if first is None:
        first = jnp.zeros_like(lengths)
    return _paged_grouped_attention(
        q, ck, cv, table, lengths, first, chosen, page_size=int(page_size),
        ring=int(ring), block_pages=min(bp, max(1, table.shape[1])),
        interpret=pallas_interpret(),
    )


def _narrow_heads(q, ck, cv, table, lengths, first, side, **kw):
    """``paged_decode_attention`` for K/V heads of ``Dh = 128 / side`` lanes:
    query head ``j`` reads K/V head ``j // g``, which lies in lanes ``((j //
    g) % side) x Dh ..`` of group ``(j // g) // side``. The query goes in
    ``side x Dh`` wide, zero outside those lanes and times ``sqrt(side)``
    (the body divides by the square root of the width it sees); of the
    output's ``side x Dh`` values the same lanes come back."""
    b, nh, hd = q.shape
    g = nh // (ck.shape[1] // hd)
    part = (jnp.arange(nh) // g) % side  # (Hq,): which head of its group
    mine = part[:, None] == (jnp.arange(side * hd) // hd)[None, :]
    wide = jnp.where(mine[None], jnp.tile(q.astype(jnp.float32), (1, 1, side)),
                     0.0) * (side ** 0.5)
    out = paged_decode_attention(wide, ck, cv, table, lengths, first, **kw)
    return jnp.take_along_axis(
        out.reshape(b, nh, side, hd), part[None, :, None, None], axis=2
    )[:, :, 0]


# ------------------------------------------- fewer K/V heads, and a window

# pages a block of the grouped body (16 tokens x 8 heads x 128 a page at
# the grouped cell's widths: 256 tokens, 1 MiB of keys and values)
GROUPED_BLOCK_PAGES = 16
# a row of the pools that block was chosen at: 8 K/V heads of 128, bfloat16
_GROUPED_ROW_BYTES = 2048
# the query and the softmax weights as ONE bfloat16 term: what the
# grouped block's products take everywhere (``models.mla_moe._operands``)
_GROUPED_TERMS = 1


def _grouped_kernel(block_pages, pbt, ps, kvh, ring, masked, lens_ref,
                    first_ref, table_ref, q_ref, *refs):
    # under a selection the slot's row of ``chosen`` stands before the pools
    chosen_ref = refs[0] if masked else None
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sems = refs[1:] if masked else refs
    b = pl.program_id(0)
    rows, hd = q_ref.shape[1], q_ref.shape[2]  # Hkv x padded group, Dh
    gp = rows // kvh
    toks = block_pages * ps  # a block's rows: one a token, heads in lanes
    scale = 1.0 / (hd ** 0.5)
    length = lens_ref[b]
    first = jnp.minimum(first_ref[b], jnp.maximum(length - 1, 0))
    page0 = first // ps  # the first page with a position to attend
    npages = (length + ps - 1) // ps - page0
    nblocks = (npages + block_pages - 1) // block_pages

    @pl.when(b == 0)
    def _():
        # as in ``_kernel``: what a short block does not copy is masked,
        # and must be finite for the values' product
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(i, slot, j):
        page = page0 + i * block_pages + j  # logical
        if ring:
            page = page % ring
        at = pl.multiple_of(table_ref[b * pbt + page] * ps, ps)
        return (
            pltpu.make_async_copy(
                k_hbm.at[pl.ds(at, ps)], kbuf.at[slot, pl.ds(j * ps, ps)],
                sems.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[pl.ds(at, ps)], vbuf.at[slot, pl.ds(j * ps, ps)],
                sems.at[1, slot]),
        )

    def for_block(i, slot, act):
        """Start (or wait for) the copies of block ``i``'s own pages."""
        for j in range(block_pages):
            @pl.when(i * block_pages + j < npages)
            def _():
                for c in copies(i, slot, j):
                    act(c)

    @pl.when(nblocks > 0)
    def _():
        for_block(0, 0, lambda c: c.start())

    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, toks), 1)
    q = q_ref[0]  # (Hkv x gp, Dh) float32, a K/V head's group in a row

    def block(i, carry):
        acc, m, l = carry
        slot = i % 2

        @pl.when(i + 1 < nblocks)
        def _():
            for_block(i + 1, 1 - slot, lambda c: c.start())

        for_block(i, slot, lambda c: c.wait())
        k, v = kbuf[slot], vbuf[slot]  # (tokens, Hkv x Dh)
        # a K/V head's keys are a lane-aligned slice of the rows, and its
        # group of queries against them is every product the head needs
        s = jnp.concatenate([
            _product(q[h * gp:(h + 1) * gp], k[:, h * hd:(h + 1) * hd], 1,
                     _GROUPED_TERMS)
            for h in range(kvh)
        ], axis=0) * scale  # (rows, tokens)
        pos = (page0 + i * block_pages) * ps + tok
        keep = (pos >= first) & (pos < length)
        if masked:
            # the block's own part of the row: (1, tokens) over the rows
            keep = keep & (chosen_ref[0, i] != 0)
        s = jnp.where(keep, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        if masked:
            # a block may hold no chosen key, the first one too: a row with
            # no key yet keeps -inf, and exp(-inf - 0) = 0 where exp(-inf -
            # -inf) is NaN (``models.gqa_moe.attend_selected``'s ``safe``)
            safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        else:
            # block 0 holds position ``first``: every row's maximum is finite
            safe = m_new
        p = jnp.exp(s - safe)  # 0 where masked
        corr = jnp.exp(m - safe)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.concatenate([
            _product(p[h * gp:(h + 1) * gp], v[:, h * hd:(h + 1) * hd], 0,
                     _GROUPED_TERMS)
            for h in range(kvh)
        ], axis=0)  # (rows, Dh)
        return acc * corr + pv, m_new, l_new

    acc, _, l = jax.lax.fori_loop(
        0, nblocks, block,
        (
            jnp.zeros((rows, hd), jnp.float32),
            jnp.full((rows, 1), -jnp.inf, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
        ),
    )
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "ring", "block_pages", "interpret"),
)
def _paged_grouped_attention(q, ck, cv, table, lengths, first, chosen=None,
                             *, page_size, ring, block_pages, interpret):
    b, nh, hd = q.shape
    kvh = ck.shape[1] // hd
    g = nh // kvh
    # a K/V head's group of queries as whole sublane tiles: 6 -> 8, 9 -> 16
    gp = -(-g // _SUBLANES) * _SUBLANES
    qg = jnp.pad(q.astype(jnp.float32).reshape(b, kvh, g, hd),
                 ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    pbt = table.shape[1]
    pad = 0 if ring else -pbt % block_pages  # never read, as above
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    masked = chosen is not None
    kernel = functools.partial(
        _grouped_kernel, block_pages, pbt + pad, page_size, kvh, ring, masked)
    buf = (2, block_pages * page_size, kvh * hd)
    mask_spec, mask = [], []
    if masked:
        # a slot's row as ``(blocks, 1, a block's positions)``: a block
        # takes its part by its number, whole lanes
        toks = block_pages * page_size
        nb = (pbt + pad) // block_pages
        mask = [jnp.pad(
            chosen.astype(jnp.int32),
            ((0, 0), (0, nb * toks - pbt * page_size)),
        ).reshape(b, nb, 1, toks)]
        mask_spec = [pl.BlockSpec((1, nb, 1, toks),
                                  lambda i, *_: (i, 0, 0, 0))]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, kvh * gp, hd), lambda i, *_: (i, 0, 0)),
                *mask_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kvh * gp, hd),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(buf, ck.dtype),
                pltpu.VMEM(buf, cv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh * gp, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        lengths.astype(jnp.int32), first.astype(jnp.int32),
        table.astype(jnp.int32).reshape(-1),
        qg.reshape(b, kvh * gp, hd), *mask, ck, cv,
    )
    return out.reshape(b, kvh, gp, hd)[:, :, :g].reshape(b, nh, hd)


# ------------------------------------------------------- the latent layout

# pages a block of the latent body: chosen on the v5e at the latent
# cell's shapes (64 slots, 32 heads, pages of 16 rows x 640 bfloat16
# values, a table of 512); the readings are in PERF.md §6 (PR 31)
LATENT_BLOCK_PAGES = 32
# the absorbed query and the softmax weights as ONE bfloat16 term: what
# the latent block's products take everywhere (``models.mla_moe``)
_LATENT_TERMS = 1


def _latent_kernel(block_pages, pbt, ps, vcols, scale, lens_ref, table_ref,
                   q_ref, pool_hbm, o_ref, buf, sems):
    b = pl.program_id(0)
    nh = q_ref.shape[1]
    toks = block_pages * ps  # a block's rows: one a token, no head axis
    length = lens_ref[b]
    npages = (length + ps - 1) // ps
    nblocks = (npages + block_pages - 1) // block_pages

    @pl.when(b == 0)
    def _():
        # as in ``_kernel``: what a short block does not copy is masked,
        # and must be finite for the values' product
        buf[...] = jnp.zeros_like(buf)

    def copy(i, slot, j):
        page = table_ref[b * pbt + i * block_pages + j]
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(pl.multiple_of(page * ps, ps), ps)],
            buf.at[slot, pl.ds(j * ps, ps)],
            sems.at[slot],
        )

    def for_block(i, slot, act):
        """Start (or wait for) the copies of block ``i``'s own pages."""
        for j in range(block_pages):
            @pl.when(i * block_pages + j < npages)
            def _():
                act(copy(i, slot, j))

    @pl.when(nblocks > 0)
    def _():
        for_block(0, 0, lambda c: c.start())

    tok = jax.lax.broadcasted_iota(jnp.int32, (nh, toks), 1)
    q = q_ref[0]  # (H, row) float32: the absorbed query

    def block(i, carry):
        acc, m, l = carry
        slot = i % 2

        @pl.when(i + 1 < nblocks)
        def _():
            for_block(i + 1, 1 - slot, lambda c: c.start())

        for_block(i, slot, lambda c: c.wait())
        rows = buf[slot]  # (tokens, row): keys, and values in its head
        s = _product(q, rows, 1, _LATENT_TERMS) * scale  # (H, tokens)
        s = jnp.where(tok < length - i * toks, s, -jnp.inf)
        # block 0 holds position 0, so every row's maximum is finite
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # 0 where masked
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = _product(p, rows[:, :vcols], 0, _LATENT_TERMS)  # (H, vcols)
        return acc * corr + pv, m_new, l_new

    acc, _, l = jax.lax.fori_loop(
        0, nblocks, block,
        (
            jnp.zeros((nh, vcols), jnp.float32),
            jnp.full((nh, 1), -jnp.inf, jnp.float32),
            jnp.zeros((nh, 1), jnp.float32),
        ),
    )
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "rank", "scale", "block_pages",
                     "interpret"),
)
def _paged_latent_attention(qc, pool, table, lengths, *, page_size, rank,
                            scale, block_pages, interpret):
    b, nh, width = qc.shape
    row = pool.shape[1]
    pbt = table.shape[1]
    # the values are the rows' first ``rank`` columns, taken as whole
    # lanes (``row`` is whole lanes and no narrower); the query is padded
    # with zeros to the rows' width
    vcols = -(-rank // _LANES) * _LANES
    qc = jnp.pad(qc.astype(jnp.float32), ((0, 0), (0, 0), (0, row - width)))
    pad = -pbt % block_pages  # never read, as above
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    kernel = functools.partial(
        _latent_kernel, block_pages, pbt + pad, page_size, vcols, scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, nh, row), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, nh, vcols), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page_size, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, vcols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_latent_attention",
    )(
        lengths.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
        qc, pool,
    )
    return out[..., :rank]


def paged_latent_attention(qc, pool, table, lengths, page_size, rank, scale,
                           block_pages=LATENT_BLOCK_PAGES):
    """Single-query absorbed latent attention of ``B`` slots over a paged
    pool of latent rows.

    ``qc``: ``(B, H, W)`` float32, the absorbed query ``[q_nope Wuk^T |
    q_pe]``; ``pool``: ``(num_pages x page_size, row)`` bfloat16 or
    float32, page ``p`` the rows ``[p x page_size, (p + 1) x page_size)``,
    a row ``[cn (rank) | k_pe | zeros]`` with ``row >= W`` a whole number
    of lanes; ``table``, ``lengths``: as for
    :func:`paged_decode_attention`. Returns ``(B, H, rank)`` float32:
    ``softmax(qc . row[:W] x scale) . row[:rank]`` over positions ``<
    length``, zeros where the length is 0."""
    return _paged_latent_attention(
        qc, pool, table, lengths, page_size=int(page_size), rank=int(rank),
        scale=float(scale),
        block_pages=min(int(block_pages), max(1, table.shape[1])),
        interpret=pallas_interpret(),
    )


# ------------------------------------------- the selector keys of an indexer

# pages a block of the selector body: chosen on the v5e inside the selecting
# cell's step program (32 slots, pages of 16 keys of 64 bfloat16 values: 2 KB,
# a table of 3,072, 36,600 pages a layer): blocks of 32 / 64 / 128 pages read
# 0.71 / 0.62 / 0.59 ms a layer with the copies unrolled, and 128 pages
# double the buffers for 0.4% of the step (PERF.md §6, PR 40)
INDEX_BLOCK_PAGES = 64
# the query as ONE bfloat16 term against a bfloat16 pool: what
# ``GroupedQueryMoEBlock.index_scores`` gives it (``mla_moe._operands``)
_INDEX_TERMS = 1
# page copies an iteration of the loop that issues (or awaits) them
_INDEX_UNROLL = 8


def _index_lanes(head_dim):
    """The width of a row of selector keys in the kernel's pool: whole
    lanes that whole keys fill (two keys of 64 side by side in 128), None
    where keys of this size fill no such row."""
    if head_dim % _LANES == 0:
        return head_dim
    return _LANES if _LANES % head_dim == 0 else None


def index_page_shape(page_size, head_dim):
    """``(rows, lanes)`` of a page of selector keys as the kernel's pool
    holds it, where ``decode_attention_path("index", ...)`` says
    ``"kernel"``: the page's keys in order, ``lanes // head_dim`` side by
    side a row (its row-major flattening is the ``page_size x head_dim``
    values of the gather body's one row a page)."""
    lanes = _index_lanes(head_dim)
    return page_size * head_dim // lanes, lanes


def _index_kernel(block_pages, pbt, nj, lens_ref, table_ref, q_ref, w_ref,
                  pool_hbm, o_ref, buf, sems):
    b = pl.program_id(0)
    _, rpp, _ = pool_hbm.shape  # rows a page
    parts = q_ref.shape[1] // nj  # keys a row
    ps = rpp * parts
    length = lens_ref[b]
    npages = (length + ps - 1) // ps
    nblocks = (npages + block_pages - 1) // block_pages

    def for_block(i, slot, act):
        """Start (or wait for) the copies of block ``i``'s own pages:
        ``_INDEX_UNROLL`` at a time in a loop, then the rest one by one
        (wholly unrolled, some 400 copy sites cost every process that
        builds the step program seconds of tracing; one copy an
        iteration is half as fast: PERF.md §6, PR 40)."""
        def copy(j):
            page = table_ref[b * pbt + i * block_pages + j]
            act(pltpu.make_async_copy(
                pool_hbm.at[page],
                buf.at[slot, pl.ds(pl.multiple_of(j * rpp, rpp), rpp)],
                sems.at[slot],
            ))

        def group(g, carry):
            for u in range(_INDEX_UNROLL):
                copy(g * _INDEX_UNROLL + u)
            return carry

        def one(j, carry):
            copy(j)
            return carry

        n = jnp.minimum(block_pages, npages - i * block_pages)
        whole = n // _INDEX_UNROLL
        jax.lax.fori_loop(0, whole, group, 0)
        jax.lax.fori_loop(whole * _INDEX_UNROLL, n, one, 0)

    @pl.when(nblocks > 0)
    def _():
        for_block(0, 0, lambda c: c.start())

    q = q_ref[0]  # (parts x J, lanes) float32: head j of part r in r's lanes
    w = w_ref[0]  # (parts x J, 1) float32

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < nblocks)
        def _():
            for_block(i + 1, 1 - slot, lambda c: c.start())

        for_block(i, slot, lambda c: c.wait())
        # what a short block does not copy keeps what the buffer held: a
        # score is one row's alone, and those rows lie past the length
        dots = _product(q, buf[slot], 1, _INDEX_TERMS)  # (parts x J, rows)
        s = jnp.maximum(dots, 0.0) * w
        o_ref[0, i] = jnp.concatenate([
            jnp.sum(s[r * nj:(r + 1) * nj], axis=0, keepdims=True)
            for r in range(parts)
        ], axis=0)  # (parts, rows)
        return carry

    jax.lax.fori_loop(0, nblocks, block, 0)


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def _paged_index_scores(qi, w, pool, table, lengths, *, block_pages,
                        interpret):
    b, nj, di = qi.shape
    _, rpp, lanes = pool.shape
    parts = lanes // di
    pbt = table.shape[1]
    pad = -pbt % block_pages  # never read, as above
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    nb = (pbt + pad) // block_pages
    rows = block_pages * rpp
    # the query against each part of a row with zeros beside it, as
    # ``index_scores(..., packed)`` builds them
    qp = jnp.stack([
        jnp.pad(qi.astype(jnp.float32),
                ((0, 0), (0, 0), (r * di, (parts - 1 - r) * di)))
        for r in range(parts)], axis=1).reshape(b, parts * nj, lanes)
    wp = jnp.tile(w.astype(jnp.float32), (1, parts))[..., None]
    kernel = functools.partial(_index_kernel, block_pages, pbt + pad, nj)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, parts * nj, lanes), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, parts * nj, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, nb, parts, rows),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nb, parts, rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_index_scores",
    )(
        lengths.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
        qp, wp, pool,
    )
    # (B, block, part, row) -> position (block x rows + row) x parts + part
    return jnp.swapaxes(out, -1, -2).reshape(b, -1)[:, : pbt * rpp * parts]


def paged_index_scores(qi, w, pool, table, lengths, block_pages=None):
    """The indexer's scores of one query a slot over the slot's own
    selector pages where they lie.

    ``qi``: ``(B, J, Di)``, ``w``: ``(B, J)`` (``GroupedQueryMoEBlock.
    index_inputs``); ``pool``: ``(num_pages, rows, lanes)`` bfloat16 or
    float32, a page's ``page_size`` selector keys of ``Di`` values in order
    (:func:`index_page_shape`); ``table``, ``lengths``: as for
    :func:`paged_decode_attention`. Returns ``(B, pages x page_size)``
    float32: ``sum_j w[b, j] relu(qi[b, j] . key[b, s])`` at positions ``s <
    lengths[b]``, as ``index_scores`` gives it over the gathered pages;
    what lies past a slot's length is not defined (whatever the buffer
    held: the caller masks it)."""
    return _paged_index_scores(
        qi, w, pool, table, lengths,
        block_pages=min(int(block_pages or INDEX_BLOCK_PAGES),
                        max(1, table.shape[1])),
        interpret=pallas_interpret(),
    )
