"""Paged decode attention — one query a slot over the slot's own pages.

The serving engine's paged decode step (``serving/engine.py``
``_build_step_fn_paged``) holds every layer's keys and values in a pool
``(num_pages, page_size, H, Dh)`` and a page table ``(B, pages)`` a
step. Its gather body reads ``pool[table]`` at the extent of the longest
table for every slot, writes that out, converts it and multiplies it.
This kernel reads each slot's pages where they lie: the pools stay in
HBM (``memory_space=ANY``), the table and the lengths are scalar-
prefetched, and a program (one a slot) copies ``BLOCK_PAGES`` pages at
a time into a double-buffered VMEM block, one asynchronous copy a page,
the next block in flight while the current one is multiplied. A slot's
loop ends at ``ceil(length / page_size)`` pages; a slot of length 0
copies nothing and returns zeros.

**The products keep the data where the copy put it.** A page is
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

**Precision.** Keys and values enter the products in the pool's dtype
(bfloat16 or float32). ``q`` and the softmax weights are float32; each
is split exactly into three bfloat16 terms (8 + 8 + 8 bits of mantissa)
stacked along the rows of one product, so every product is bfloat16 x
pool dtype accumulated in float32 and their sum is the float32 operand's
product: finer than the one bfloat16 pass JAX's default precision gives
the gather body on a TPU, equal to the float32 arithmetic it has on the
CPU up to the order of summation. Scale, mask, running maximum,
normaliser and output are float32.

``decode_attention_path`` is the one place that says whether the kernel
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


def decode_attention_path(layout, head_dim, kv_dtype, mesh=None):
    """``"kernel"`` where :func:`paged_decode_attention` serves the paged
    decode step, else ``"gather: <why>"`` — read from what the stepper
    can see of itself, never from a knob or a model's name."""
    if mesh is not None:
        return "gather: Mosaic kernels are not partitioned over a mesh"
    if layout != "kv":
        return f"gather: the {layout} page layout has its own stage body"
    if head_dim % _LANES:
        return (f"gather: heads of {head_dim} are not a whole number of "
                f"{_LANES} lanes")
    if jnp.dtype(kv_dtype) not in (jnp.dtype(jnp.bfloat16),
                                   jnp.dtype(jnp.float32)):
        return f"gather: no kernel for a {jnp.dtype(kv_dtype).name} pool"
    return "kernel"


def _product(x, pages, contract):
    """float32 ``x (R, C)`` times a block ``pages`` in the pool's dtype,
    contracting ``x``'s columns with ``pages``' axis ``contract``,
    accumulated in float32. Against a bfloat16 pool ``x`` goes as
    ``_TERMS`` bfloat16 terms stacked along the rows of ONE product
    (the block is the MXU's stationary operand either way) whose row
    groups are then summed: exact in ``x`` at three terms."""
    dims = (((1,), (contract,)), ((), ()))
    if pages.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            x, pages, dims, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    terms, rest = [], x
    for _ in range(_TERMS):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    out = jax.lax.dot_general(
        jnp.concatenate(terms, axis=0), pages, dims,
        preferred_element_type=jnp.float32,
    )
    rows = x.shape[0]
    return sum(out[i * rows:(i + 1) * rows] for i in range(_TERMS))


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
