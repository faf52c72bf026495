"""The main path's kernels, compiled for a described v5e chip (no chip needed).

The TPU's compiler is installed with JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Interpret mode
cannot see what Mosaic refuses — a slice off the tiling, too much VMEM — so
each kernel family of the trainer's path is lowered here with
``interpret=False`` at the widths ``chip_smoke.py`` runs, and the compiled
module must hold its ``tpu_custom_call``. Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module-scoped fixture, in the test's own
process, and nothing touches it at import: only one process may hold the
TPU library, and under pytest-xdist every worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# (batch, seq, heads, head_dim, dtype): chip_smoke's head dim 256 and the
# zoo's common head dim 64, both at seq 2048
FLASH_SHAPES = [
    pytest.param(4, 2048, 8, 256, jnp.bfloat16, id="hd256-bf16"),
    pytest.param(4, 2048, 8, 64, jnp.bfloat16, id="hd64-bf16"),
]


@pytest.mark.parametrize("b,t,h,d,dtype", FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, b, t, h, d, dtype):
    from distkeras_tpu.ops.flash_attention import _flash, effective_path

    path, bq, bk = effective_path(t, d)
    assert path == "flash"
    x = jax.ShapeDtypeStruct((b, h, t, d), dtype, sharding=one_chip)
    _compile(lambda q, k, v: _flash(q, k, v, True, bq, bk, False), x, x, x)


@pytest.mark.parametrize("b,t,h,d,dtype", FLASH_SHAPES)
def test_flash_backward_compiles_for_v5e(one_chip, b, t, h, d, dtype):
    from distkeras_tpu.ops.flash_attention import _flash, effective_path

    _, bq, bk = effective_path(t, d)
    x = jax.ShapeDtypeStruct((b, h, t, d), dtype, sharding=one_chip)

    def loss(q, k, v):
        out = _flash(q, k, v, True, bq, bk, False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    # forward, dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


def _ln_shapes(one_chip, rows=8192, d=2048, dtype=jnp.bfloat16):
    x = jax.ShapeDtypeStruct((rows, d), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    return x, g


def test_fused_layernorm_forward_compiles_for_v5e(one_chip):
    from distkeras_tpu.ops.fused_layernorm import _block_rows_for, _fused

    x, g = _ln_shapes(one_chip)
    rows = _block_rows_for(*x.shape)
    _compile(lambda x, g, b: _fused(x, g, b, 1e-5, rows, False), x, g, g)


def test_fused_layernorm_backward_compiles_for_v5e(one_chip):
    from distkeras_tpu.ops.fused_layernorm import _block_rows_for, _fused

    x, g = _ln_shapes(one_chip)
    rows = _block_rows_for(*x.shape)

    def loss(x, g, b):
        return jnp.sum(_fused(x, g, b, 1e-5, rows, False).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), x, g, g)


def test_adam_leaf_compiles_for_v5e(one_chip):
    from distkeras_tpu.ops.pallas_kernels import _leaf_adam

    p = jax.ShapeDtypeStruct((2048, 8192), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((1, 2), jnp.float32, sharding=one_chip)
    _compile(
        functools.partial(
            _leaf_adam, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, interpret=False
        ),
        p, p, p, p, c,
    )


def test_momentum_leaf_compiles_for_v5e(one_chip):
    from distkeras_tpu.ops.pallas_kernels import _leaf_sgd_momentum

    p = jax.ShapeDtypeStruct((2048, 8192), jnp.float32, sharding=one_chip)
    _compile(
        functools.partial(
            _leaf_sgd_momentum, lr=0.01, mu=0.9, nesterov=False,
            interpret=False,
        ),
        p, p, p,
    )


# the serving cell's shapes (cerebras-gpt-1.3b: 32 slots, 16 heads of 128,
# 1408 pages of 16 tokens, a table of 128 pages), and a float32 pool
PAGED_SHAPES = [
    pytest.param(32, 16, 128, 16, 1408, 128, jnp.bfloat16, id="gpt1.3b-bf16"),
    pytest.param(8, 8, 128, 16, 256, 32, jnp.float32, id="f32-pool"),
]


@pytest.mark.parametrize("b,nh,hd,ps,pages,pbt,dtype", PAGED_SHAPES)
def test_paged_decode_attention_compiles_for_v5e(
    one_chip, b, nh, hd, ps, pages, pbt, dtype
):
    """The decode step's page write, then the kernel over the written
    pool: the module holds the kernel and no copy of a pool (the pools
    stay where they lie, an operand of the custom call)."""
    from distkeras_tpu.ops.paged_attention import (
        BLOCK_PAGES,
        _paged_decode_attention,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, new, ck, cv, phys, off, table, lengths):
        ck = ck.at[phys, off].set(new.astype(ck.dtype))
        cv = cv.at[phys, off].set(new.astype(cv.dtype))
        o = _paged_decode_attention(
            q, ck, cv, table, lengths, block_pages=BLOCK_PAGES,
            interpret=False,
        )
        return o, ck, cv

    pool = s((pages, ps, nh, hd), dtype)
    row = s((b, nh, hd), jnp.float32)
    idx = s((b,), jnp.int32)
    text = jax.jit(step, donate_argnums=(2, 3)).lower(
        row, row, pool, pool, idx, idx, s((b, pbt), jnp.int32), idx
    ).compile().as_text()
    assert "tpu_custom_call" in text
    pool_shape = f"[{pages},{ps},{nh},{hd}]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and pool_shape in ln.split("=")[0]]
    assert not copies, copies


# the latent cell's shapes (kanana-2-30b-a3b-8l: 64 slots, 32 heads, rows of
# 512 + 64 values padded to 640, 8,960 pages of 16 tokens, a table of 512
# pages: 128 KB of scalar-prefetched table), the shortcut layer's cell
# (longcat-flash-chat-4l-ep32: 128 slots, 64 heads, 15,360 pages: 256 KB of
# table, 64 query rows a block), and a float32 pool
LATENT_SHAPES = [
    pytest.param(64, 32, 576, 512, 16, 8960, 512, jnp.bfloat16,
                 id="kanana-bf16"),
    pytest.param(128, 64, 576, 512, 16, 15360, 512, jnp.bfloat16,
                 id="longcat-bf16"),
    pytest.param(8, 4, 40, 32, 8, 64, 16, jnp.float32, id="f32-pool"),
]


def _latent_step(one_chip, b, nh, width, rank, ps, pages, pbt, dtype):
    from distkeras_tpu.ops.paged_attention import (
        LATENT_BLOCK_PAGES,
        _paged_latent_attention,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(qc, new, pool, at, table, lengths):
        pool = pool.at[at].set(new.astype(pool.dtype))
        with jax.named_scope("mla"):
            o = _paged_latent_attention(
                qc, pool, table, lengths, page_size=ps, rank=rank,
                scale=0.07, block_pages=LATENT_BLOCK_PAGES, interpret=False,
            )
        return o, pool

    row = -(-width // 128) * 128
    idx = s((b,), jnp.int32)
    return jax.jit(step, donate_argnums=(2,)).lower(
        s((b, nh, width), jnp.float32), s((b, row), jnp.float32),
        s((pages * ps, row), dtype), idx, s((b, pbt), jnp.int32), idx,
    )


@pytest.mark.parametrize("b,nh,width,rank,ps,pages,pbt,dtype", LATENT_SHAPES)
def test_paged_latent_attention_compiles_for_v5e(
    one_chip, b, nh, width, rank, ps, pages, pbt, dtype
):
    """The latent step's page write, then the kernel over the written
    pool: the module holds the kernel, no copy of the pool, and the
    custom call carries the ``mla`` scope that ``mla_decode_roofline``
    reads its operations by."""
    text = _latent_step(
        one_chip, b, nh, width, rank, ps, pages, pbt, dtype
    ).compile().as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "/mla/" in call.split('op_name="')[1].split('"')[0], call
    pool_shape = f"[{pages * ps},{-(-width // 128) * 128}]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and pool_shape in ln.split("=")[0]]
    assert not copies, copies


def test_latent_pages_of_four_rows_are_refused_by_mosaic(one_chip):
    """Why ``decode_attention_path`` keeps latent pages that are not
    whole tiles of the pool on the gather body."""
    from distkeras_tpu.ops.paged_attention import decode_attention_path

    assert decode_attention_path(
        "latent", None, jnp.bfloat16, None, 4).startswith("gather")
    with pytest.raises(Exception, match="aligned to tiling"):
        _latent_step(one_chip, 8, 4, 40, 32, 4, 64, 16,
                     jnp.bfloat16).compile()
