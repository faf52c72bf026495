"""Grouped matrix product — rows sorted by group, each group against its own
matrix of a stack.

``grouped_matmul(x, w, sizes)``: ``x`` ``(m, k)``, ``w`` ``(G, k, n)``,
``sizes`` ``(G,)`` int32; rows ``[sum(sizes[:g]), sum(sizes[:g + 1]))`` of
the result are those rows of ``x`` times ``w[g]``, float32, and the rows
past the last group are zero. It is what ``jax.lax.ragged_dot`` computes,
and it is where the expert layers of ``models/mla_moe.py`` and
``models/gqa_moe.py`` spend their time (``routed_experts``: three products
a layer, the tokens' picks sorted by expert).

**Why a kernel of its own** (PR 43; the readings are in ``PERF.md`` §6).
XLA lowers ``ragged_dot`` on a TPU to a kernel with weight blocks of 512 x
512 and row tiles of the largest power of two (up to 512) that divides
``m``. A decode step's product is bound by the reached experts' bytes, and
a block of 512 KB is a grid step of 0.64 us of copy behind a fixed cost of
about as much: it read 56% of the memory's rate. A prefill chunk's product
with a few dozen rows an expert multiplied a tile of 512 rows for each.
Here:

- **row tiles of 128 whatever ``m``** (``m`` is padded to whole tiles
  inside): an expert with a few rows multiplies 128 of them, never 256 or
  512;
- **weight blocks as large as fast memory takes**, from ``k``, ``n`` and
  the dtype alone (``weight_block``): all ``k`` rows of a group's matrix
  and as many whole groups of 128 of its ``n`` columns as ``BLOCK_BYTES``
  holds, the whole matrix where it fits. A block is one grid step, so the
  fixed cost of a step is paid a few times a group, and the copies set the
  pace. With all of ``k`` in a block a visit's product is final: no
  accumulator and no second pass over the output;
- **the visiting schedule is computed in the program** from ``sizes``
  (``_schedule``: which group and which row tile each grid step serves)
  and handed to the kernel by scalar prefetch. A group of no rows is not
  visited and its matrix is not read. Consecutive steps that serve one
  group read its block once, and consecutive steps on one row tile keep
  the tile's rows and its output in fast memory (Pallas copies a block
  only when its index changes), so a row tile that several groups share
  is written once, each group's rows selected into it under a mask. The
  rows of no group are the schedule's last group, whose visits write zeros
  and read no matrix.

The grid is ``(n / tn, visits)``, ``visits`` the static bound ``m / 128 +
G`` (every row tile once, and once more for every group that starts inside
one, the rows of no group among them); steps past the schedule's end keep
the last step's block indices and do nothing.

**Fast memory.** Two buffers of a weight block, of a row tile ``(128, k)``
and of an output tile ``(128, tn)``. The compiler's default scoped limit
(16 MiB of the v5e's 128) holds that for blocks up to some 6 MB (an
expert's 3 MB or 6 MB matrix whole); a larger block states its need in the
call's ``vmem_limit_bytes``. No other kernel of the repository sets it:
theirs are a few hundred KB. ``BLOCK_BYTES`` is 8 MB because the copies
are the faster the longer their contiguous runs (a block's ``tn`` columns
of one tile row lie together): on the chip a 6 MB matrix read whole took
583 us where two blocks of 3 MB took 708, and 25 MB matrices read in
blocks of 8 MB 538 us where blocks of 4 MB took 569 (``PERF.md`` §6).

**Precision.** The operands enter the product as they come (the callers
hand both in the weights' dtype: bfloat16 as served, float32 as
initialised) and accumulate in float32, in one pass over ``k``.

**Differentiation.** ``jax.custom_vjp`` whose backward is the plain
form's (``jax.lax.ragged_dot``'s own, transposed by JAX): ``apply`` under
``jax.grad`` works wherever the forward does.

Compiled on a TPU and interpreted elsewhere (``ops/kernel_mode.py``); a
Mosaic failure is raised to the caller. A Mosaic kernel is not partitioned
over a mesh (``ROADMAP.md`` B0): a program that shards these operands over
the chips of a TPU is refused by the compiler, as one that shards a paged
pool is; nothing in the repository does (the blocks with routed experts
refuse a ``tp`` mesh, and a chip's share of the experts is ``held``, not a
sharding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.kernel_mode import pallas_interpret

ROW_TILE = 128
_LANES = 128
# one weight block; two of them are in fast memory at a time
BLOCK_BYTES = 8 << 20
# the compiler's default scoped limit of fast memory on the v5e
_VMEM_DEFAULT = 16 << 20


def grouped_form(k, n) -> str:
    """``"kernel"`` where this module's kernel serves a grouped product of
    ``(m, k)`` rows against ``(G, k, n)`` matrices, else ``"ragged_dot"``
    — read off the widths, never from a knob or a model's name: a block is
    whole groups of 128 lanes both ways."""
    return "ragged_dot" if k % _LANES or n % _LANES else "kernel"


def weight_block(k, n, dtype) -> int:
    """``tn``: the columns of a weight block ``(k, tn)``. All of ``n`` where
    a matrix fits ``BLOCK_BYTES``, else the largest whole number of 128
    lanes that divides ``n`` and fits; 128 where nothing fits."""
    lanes = n // _LANES
    room = BLOCK_BYTES // (k * jnp.dtype(dtype).itemsize * _LANES)
    return _LANES * max(
        (d for d in range(1, lanes + 1) if lanes % d == 0 and d <= room),
        default=1)


def _schedule(sizes, tiles):
    """Which group and which row tile each of ``tiles + G`` grid steps
    serves, from the groups' ``sizes`` over ``tiles`` row tiles. The rows of
    no group are group ``G``. Returns ``(group, matrix, tile, offsets,
    visits)``: a step's group, the matrix its block is read from (its own;
    for group ``G`` the last one read, so no copy is made for it), its row
    tile, the groups' first rows ``(G + 2,)``, and how many steps the
    schedule has (the steps past it repeat the last)."""
    g = sizes.shape[0]
    rest = jnp.maximum(tiles * ROW_TILE - jnp.sum(sizes), 0)
    ext = jnp.concatenate([sizes, rest[None]])
    ends = jnp.cumsum(ext)
    first = (ends - ext) // ROW_TILE  # a group's first row tile
    count = jnp.where(ext > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    upto = jnp.cumsum(count)  # steps up to and with a group
    visits = upto[-1]
    step = jnp.minimum(jnp.arange(tiles + g, dtype=jnp.int32), visits - 1)
    # (every step against every group's last: one fused pass, no search loop)
    group = jnp.searchsorted(
        upto, step, side="right", method="compare_all").astype(jnp.int32)
    tile = first[group] + step - (upto[group] - count[group])
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(g, dtype=jnp.int32), 0))
    matrix = jnp.where(group == g, last, group)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, matrix, tile, offsets, visits[None]


def _kernel(g, group_ref, matrix_ref, tile_ref, offset_ref, visits_ref,
            x_ref, w_ref, o_ref):
    del matrix_ref  # the index maps' alone
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _visit():
        grp = group_ref[v]
        row = tile_ref[v] * ROW_TILE + jax.lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        own = (row >= offset_ref[grp]) & (row < offset_ref[grp + 1])

        # the other rows of the tile are another visit's: of the steps
        # before this one (kept), or of those behind it (overwritten)
        @pl.when(grp < g)
        def _product():
            y = jnp.dot(x_ref[...], w_ref[...],
                        preferred_element_type=jnp.float32)
            o_ref[...] = jnp.where(own, y, o_ref[...])

        @pl.when(grp == g)
        def _no_group():
            o_ref[...] = jnp.where(own, 0.0, o_ref[...])


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def _grouped_matmul(x, w, sizes, *, tn, interpret):
    m, k = x.shape
    g, _, n = w.shape
    tiles = -(-m // ROW_TILE)
    if tiles * ROW_TILE != m:
        x = jnp.pad(x, ((0, tiles * ROW_TILE - m), (0, 0)))
    # two buffers of each block, the product before its select, and room
    need = 2 * (k * tn * w.dtype.itemsize + ROW_TILE * k * x.dtype.itemsize
                + ROW_TILE * tn * 4) + ROW_TILE * tn * 4 + (2 << 20)
    out = pl.pallas_call(
        functools.partial(_kernel, g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, tiles + g),
            in_specs=[
                pl.BlockSpec((ROW_TILE, k),
                             lambda j, v, grp, mat, tile, *_: (tile[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, grp, mat, *_: (mat[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (ROW_TILE, tn),
                lambda j, v, grp, mat, tile, *_: (tile[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * ROW_TILE, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # a row tile's visits follow one another; column blocks are
            # independent
            dimension_semantics=("parallel", "arbitrary"),
            **({"vmem_limit_bytes": need} if need > _VMEM_DEFAULT else {}),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(w.size * w.dtype.itemsize
                            + x.size * x.dtype.itemsize + 4 * m * n)),
        interpret=interpret,
        name="grouped_matmul",
    )(*_schedule(sizes, tiles), x, w)
    return out[:m]


def plain_grouped_matmul(x, w, sizes):
    """The same product as XLA has it (``jax.lax.ragged_dot``, float32
    out): the form for widths the kernel does not take, and the kernel's
    backward."""
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """``x`` ``(m, k)`` rows sorted by group against the stacked ``w`` ``(G,
    k, n)``, ``sizes`` ``(G,)`` rows a group: ``(m, n)`` float32, the rows
    past the last group zero. ``k`` and ``n`` whole groups of 128 lanes
    (``grouped_form``); the module's docstring has the rest."""
    return _grouped_matmul(
        x, w, sizes.astype(jnp.int32),
        tn=weight_block(w.shape[1], w.shape[2], w.dtype),
        interpret=pallas_interpret())


def _forward(x, w, sizes):
    return grouped_matmul(x, w, sizes), (x, w, sizes)


def _backward(kept, ct):
    x, w, sizes = kept
    _, transposed = jax.vjp(lambda x, w: plain_grouped_matmul(x, w, sizes), x, w)
    return (*transposed(ct), None)


grouped_matmul.defvjp(_forward, _backward)
