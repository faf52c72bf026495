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
zeros. There is one body a page layout, and the two share the copy
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


def decode_attention_path(layout, head_dim, kv_dtype, mesh=None,
                          page_size=None):
    """``"kernel"`` where a kernel of this module serves the paged decode
    step (:func:`paged_decode_attention` for layout ``"kv"``,
    :func:`paged_latent_attention` for ``"latent"``), else ``"gather:
    <why>"`` — read from what the stepper can see of itself, never from
    a knob or a model's name."""
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


def paged_decode_attention(q, ck, cv, table, lengths,
                           block_pages=BLOCK_PAGES):
    """Single-query attention of ``B`` slots over a paged pool.

    ``q``: ``(B, H, Dh)``; ``ck``, ``cv``: the pools as the engine holds
    them, ``(num_pages, page_size, H, Dh)`` bfloat16 or float32;
    ``table``: ``(B, pages)`` int32, a slot's pages in logical order
    (entries past ``ceil(length / page_size)`` are never read);
    ``lengths``: ``(B,)`` int32, the positions a slot attends (its own
    newest included), 0 for a slot that is not decoding. Returns ``(B,
    H, Dh)`` float32: ``softmax(q . k / sqrt(Dh)) . v`` over positions
    ``< length``, zeros where the length is 0."""
    return _paged_decode_attention(
        q, ck, cv, table, lengths,
        block_pages=min(int(block_pages), max(1, table.shape[1])),
        interpret=pallas_interpret(),
    )


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
