"""``ops.grouped_matmul``: the experts' grouped product, in the Pallas
interpreter, against a product taken row by row in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import grouped_matmul as gm


def _row_by_row(x, w, sizes):
    """Each row times its own group's matrix; rows of no group zero."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    ends = np.cumsum(sizes)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for r in range(min(x.shape[0], int(ends[-1]))):
        out[r] = x[r] @ w[np.searchsorted(ends, r, side="right")]
    return out


def _operands(m, k, n, g, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(0.1 * rng.normal(size=(g, k, n)), dtype)
    return x, w


def _pass_spans(sizes, lo, cap):
    """A pass's group sizes as ``routed_experts`` hands them: each group's
    rows among the sorted rows ``lo .. lo + cap``."""
    ends = np.cumsum(sizes)
    return (np.clip(ends, lo, lo + cap)
            - np.clip(ends - sizes, lo, lo + cap)).astype(np.int32)


CASES = {
    # (m, k, n, sizes, dtype)
    "empty_groups": (256, 128, 256, [0, 100, 0, 0, 156, 0], jnp.float32),
    "no_rows_at_all": (128, 128, 128, [0, 0, 0, 0], jnp.float32),
    "one_group_holds_every_row": (384, 128, 128, [0, 384, 0, 0], jnp.float32),
    "a_group_straddles_two_tiles": (256, 256, 128, [100, 60, 96],
                                    jnp.float32),
    "a_group_spans_three_tiles": (384, 128, 128, [20, 300, 64], jnp.float32),
    "a_few_rows_an_expert": (384, 128, 256, [3, 0, 5, 2, 4, 0, 3, 1],
                             jnp.float32),
    "rows_not_whole_tiles": (300, 256, 128, [10, 0, 150, 100, 7],
                             jnp.float32),
    "fewer_rows_than_a_tile": (40, 128, 128, [7, 0, 20, 13], jnp.float32),
    "rows_past_the_last_group": (384, 128, 128, [30, 0, 41, 9], jnp.float32),
    "groups_end_on_tile_edges": (384, 128, 128, [128, 0, 128, 128],
                                 jnp.float32),
    "a_pass_from_its_first_row": (
        256, 128, 128, _pass_spans(np.array([100, 60, 200, 90]), 0, 256),
        jnp.float32),
    "a_later_pass_clipped": (
        256, 128, 128, _pass_spans(np.array([100, 60, 200, 90]), 256, 256),
        jnp.float32),
    "bfloat16_operands": (384, 256, 256, [50, 0, 200, 100, 0, 20],
                          jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_product_taken_row_by_row(case):
    m, k, n, sizes, dtype = CASES[case]
    sizes = np.asarray(sizes, np.int32)
    x, w = _operands(m, k, n, len(sizes), dtype)
    if dtype == jnp.bfloat16:
        # a product of two bfloat16 values is exact in float32: the CPU
        # multiplies the upcast operands (``models.mla_moe._operands``)
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes)))
    want = _row_by_row(x, w, sizes)
    assert got.shape == (m, n) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert not got[int(sizes.sum()):].any()  # zero, not merely small
    plain = np.asarray(gm.plain_grouped_matmul(x, w, jnp.asarray(sizes)))
    np.testing.assert_allclose(got, plain, atol=2e-5, rtol=0)


@pytest.mark.parametrize("tn", [128, 256])
def test_column_blocks_narrower_than_the_matrix(tn):
    """A matrix that does not fit one block is read in column blocks, each
    a sweep of the schedule of its own."""
    sizes = np.asarray([70, 0, 130, 56, 0, 100], np.int32)
    x, w = _operands(384, 128, 512, 6, jnp.float32, seed=1)
    got = gm._grouped_matmul(x, w, jnp.asarray(sizes), tn=tn, interpret=True)
    np.testing.assert_allclose(got, _row_by_row(x, w, sizes), atol=2e-5,
                               rtol=0)


def test_the_gradient_is_the_plain_forms():
    sizes = jnp.asarray([10, 100, 0, 90], jnp.int32)
    x, w = _operands(256, 128, 128, 4, jnp.float32, seed=2)

    def loss(mm):
        return lambda x, w: jnp.sum(jnp.sin(mm(x, w, sizes)))

    got = jax.jit(jax.grad(loss(gm.grouped_matmul), (0, 1)))(x, w)
    want = jax.jit(jax.grad(loss(gm.plain_grouped_matmul), (0, 1)))(x, w)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 1e-3
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    # the rows of no group and the empty group's matrix get no gradient
    assert not np.asarray(got[0])[200:].any()
    assert not np.asarray(got[1])[2].any()


def test_the_schedule_visits_each_reached_group_once_a_tile():
    """An empty group has no step, a group a step a row tile it touches,
    the rows of no group theirs, and the steps past the schedule repeat
    the last one's blocks (no copy, no product)."""
    sizes = jnp.asarray([100, 0, 60, 0, 96, 0], jnp.int32)  # 256 of 384
    group, matrix, tile, offsets, visits = (
        np.asarray(a) for a in gm._schedule(sizes, 3))
    assert int(visits[0]) == 5 and len(group) == 3 + 6
    assert group[:5].tolist() == [0, 2, 2, 4, 6]
    assert tile[:5].tolist() == [0, 0, 1, 1, 2]
    # the rows of no group read no matrix of their own
    assert matrix[:5].tolist() == [0, 2, 2, 4, 4]
    assert offsets.tolist() == [0, 100, 100, 160, 160, 256, 256, 384]
    assert (group[5:] == 6).all() and (tile[5:] == 2).all()
    assert (matrix[5:] == 4).all()


@pytest.mark.parametrize("k, n, dtype, tn", [
    (2048, 768, jnp.bfloat16, 768),     # an expert's whole matrix, 3 MB
    (768, 2048, jnp.bfloat16, 2048),
    (3072, 1024, jnp.bfloat16, 1024),   # 6 MB, whole
    (1024, 3072, jnp.bfloat16, 3072),
    (6144, 2048, jnp.bfloat16, 512),    # 25 MB: four blocks of 6 MB
    (2048, 6144, jnp.bfloat16, 2048),   # three of 8 MB
    (2048, 768, jnp.float32, 768),      # 6 MB in float32: still whole
    (4096, 2048, jnp.float32, 512),     # twice the bytes: half the columns
    (65536, 256, jnp.bfloat16, 128),    # nothing fits: the narrowest block
])
def test_a_weight_block_is_the_widest_that_fits(k, n, dtype, tn):
    assert gm.weight_block(k, n, dtype) == tn
    assert n % tn == 0 and tn % 128 == 0


def test_the_form_is_read_off_the_widths():
    assert gm.grouped_form(2048, 768) == "kernel"
    assert gm.grouped_form(128, 128) == "kernel"
    for k, n in [(16, 64), (2048, 64), (96, 128), (128, 200)]:
        assert gm.grouped_form(k, n) == "ragged_dot"
