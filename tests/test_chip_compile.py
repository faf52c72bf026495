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

import contextlib
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


@contextlib.contextmanager
def _kernels_compiled():
    """Code that asks ``jax.default_backend()`` sees the CPU here and would
    interpret its kernels: the whole-program tests steer every kernel module
    to compile, as the chip does."""
    import distkeras_tpu.ops.grouped_matmul as gm
    import distkeras_tpu.ops.paged_attention as pa

    real = pa.pallas_interpret, gm.pallas_interpret
    pa.pallas_interpret = gm.pallas_interpret = lambda: False
    try:
        yield
    finally:
        pa.pallas_interpret, gm.pallas_interpret = real


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _grouped_calls(compiled):
    """The experts' grouped products of a compiled program: the kernel's
    custom calls (``ops/grouped_matmul.py``) with their scope paths."""
    calls = [ln.split('op_name="')[1].split('"')[0]
             for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and "grouped_matmul" in ln]
    assert all("/moe/experts/" in path for path in calls), calls
    return calls


# (rows, k, n, groups): the expert cells' grouped products, a decode step's
# and a prefill chunk's (kanana: every expert held; the others a share)
GROUPED_MATMUL_SHAPES = [
    pytest.param(384, 2048, 768, 128, id="kanana-step-up"),
    pytest.param(384, 768, 2048, 128, id="kanana-step-down"),
    pytest.param(6144, 2048, 768, 128, id="kanana-chunk-up"),
    pytest.param(6144, 768, 2048, 128, id="kanana-chunk-down"),
    pytest.param(384, 6144, 2048, 16, id="longcat-step-up"),
    pytest.param(384, 2048, 6144, 16, id="longcat-step-down"),
    pytest.param(960, 3072, 1024, 64, id="laguna-step-up"),
    pytest.param(960, 1024, 3072, 64, id="laguna-step-down"),
    pytest.param(200, 2048, 768, 16, id="rows-not-whole-tiles"),
]


@pytest.mark.parametrize("m,k,n,g", GROUPED_MATMUL_SHAPES)
def test_grouped_matmul_compiles_for_v5e(one_chip, m, k, n, g):
    """The experts' grouped product (``ops/grouped_matmul.py``) at the
    cells' widths, bfloat16 as served: Mosaic takes the blocks
    ``weight_block`` chooses (two of up to 8 MB in fast memory, the need
    stated in ``vmem_limit_bytes`` where it passes the default) and the
    scalar-prefetched schedule."""
    from distkeras_tpu.ops import grouped_matmul as gm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tn = gm.weight_block(k, n, jnp.bfloat16)
    assert k * tn * 2 <= gm.BLOCK_BYTES
    _compile(
        functools.partial(gm._grouped_matmul, tn=tn, interpret=False),
        sds((m, k), jnp.bfloat16), sds((g, k, n), jnp.bfloat16),
        sds((g,), jnp.int32))


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


# the grouped cell's shapes (laguna-s-2.1-5l-ep4: 96 slots, 8 K/V heads of
# 128, pages of 16 tokens): a full layer's 48 query heads over a table of
# 1,024 pages, a window layer's 72 over a ring of 33 from a first position;
# and the selecting cell's (keye-vl-2.0-30b-a3b-6l-ep8: 32 slots, 32 query
# heads over 4 K/V heads of 128, a table of 3,072 pages) under the
# selection's mask, a slot's row of 49,152 positions a program
GROUPED_SHAPES = [
    pytest.param(96, 48, 8, 43008, 1024, 0, False, id="laguna-full"),
    pytest.param(96, 72, 8, 96 * 33 + 1, 33, 33, False, id="laguna-window"),
    pytest.param(32, 32, 4, 47616, 3072, 0, True, id="keye-selected"),
]


@pytest.mark.parametrize("b,nh,kvh,pages,pbt,ring,masked", GROUPED_SHAPES)
def test_grouped_paged_attention_compiles_for_v5e(
    one_chip, b, nh, kvh, pages, pbt, ring, masked
):
    """The decode step's row write into the flat pool, then the grouped
    kernel over the written pool: Mosaic takes a K/V head's lane-aligned
    slice of a copied page and a group of 6 or 9 queries padded to whole
    tiles, and under a selection a block's own part of the slot's row of
    ``chosen`` by the block's number; the module holds the kernel and no
    copy of a pool."""
    from distkeras_tpu.ops.paged_attention import (
        GROUPED_BLOCK_PAGES,
        _paged_grouped_attention,
    )

    hd, ps = 128, 16
    bp = GROUPED_BLOCK_PAGES
    if ring:
        bp = -(-ring // -(-ring // bp))
    if masked:  # the grouped cell's bytes a block: 32 pages of 1 KB rows
        bp *= 2048 // (kvh * hd * 2)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, new, ck, cv, at, table, lengths, first, *chosen):
        ck = ck.at[at].set(new.astype(ck.dtype))
        cv = cv.at[at].set(new.astype(cv.dtype))
        o = _paged_grouped_attention(
            q, ck, cv, table, lengths, first, *chosen, page_size=ps,
            ring=ring, block_pages=bp, interpret=False,
        )
        return o, ck, cv

    pool = s((pages * ps, kvh * hd), jnp.bfloat16)
    idx = s((b,), jnp.int32)
    text = jax.jit(step, donate_argnums=(2, 3)).lower(
        s((b, nh, hd), jnp.float32), s((b, kvh * hd), jnp.float32), pool,
        pool, idx, s((b, pbt), jnp.int32), idx, idx,
        *([s((b, pbt * ps), jnp.bool_)] if masked else []),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    pool_shape = f"[{pages * ps},{kvh * hd}]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and pool_shape in ln.split("=")[0]]
    assert not copies, copies


def test_the_grouped_step_and_chunk_programs_compile_for_v5e(one_chip):
    """The stepper's own step program and a 2,048-token chunk program at
    the configuration's widths (hidden 3072, 48 / 72 query heads over 8
    K/V heads of 128, window 512, dense MLP 12288, experts of 1024, top 10
    of 256 router outputs, YaRN and plain rotary, 96 slots, pages of 16, a
    table of 1,024 pages and a ring of 33), with what is no width cut so
    that the CPU holds it: 2 experts held a layer, 512 rows of vocabulary,
    2,048 pages. Both hold the kernel or gather no more than they say, and
    neither copies a pool."""
    import numpy as np

    from distkeras_tpu.models import zoo
    from distkeras_tpu.serving.engine import DecodeStepper

    rope = {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
    }
    model = zoo.laguna_lm(
        vocab_size=512, seq_len=16384, hidden_size=3072,
        num_key_value_heads=8, head_dim=128, intermediate_size=12288,
        moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
        num_experts=256, num_experts_per_tok=10,
        num_attention_heads_per_layer=(48, 72, 72, 72, 48),
        sliding_window=512, rope_parameters=rope, experts_held=[0, 1])
    model.params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                model.params)
    st = DecodeStepper(model, num_slots=96, paged=True, page_size=16,
                       num_pages=2048, kv_dtype=jnp.bfloat16)
    assert st.attention == "kernel" and st._ring == 33
    pbt = st._max_pages_bucket
    assert pbt == 1024

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)

    with _kernels_compiled():
        step = st._build_step_fn_paged(pbt).lower(*shapes((
            st._params, st._ctx, st._pools, st._lens.copy(),
            np.zeros(96, bool), st._tables_array(pbt),
            *st._sampling_args()))).compile()
        chunk = st._build_chunk_fn_paged(2048, pbt).lower(*shapes((
            st._params, st._pools, np.zeros((1, 2048), np.int32),
            st._chunk_where(0, pbt, 0), np.int32(0)))).compile()
    text = step.as_text()
    assert text.count("tpu_custom_call") >= 5  # a kernel call a layer
    # four expert layers' three grouped products, in a layer's first pass
    # and in its loop's (2 experts of 256 held: the rows are compacted),
    # the kernel's, under the scope ``moe_decode_roofline`` reads, and no
    # ``ragged-dot``
    # (the chunk walks its three window layers in one loop's body)
    assert len(_grouped_calls(step)) == 4 * 3 * 2
    assert len(_grouped_calls(chunk)) == 3 * 3 * 2
    for compiled in (step, chunk):
        assert "ragged" not in compiled.as_text()
    for compiled in (step, chunk):
        for rows in (2048 * 16, (96 * 33 + 1) * 16):
            copies = [ln for ln in compiled.as_text().splitlines()
                      if " copy(" in ln and f"[{rows},1024]" in ln.split("=")[0]]
            assert not copies, copies[:3]
    # the chunk's transients beside 96 slots' pools fit the chip
    assert chunk.memory_analysis().temp_size_in_bytes < 2.0e9
    assert step.memory_analysis().temp_size_in_bytes < 1.5e9


def test_the_selecting_step_and_chunk_programs_compile_for_v5e(one_chip):
    """The stepper's own step program and a 2,048-token chunk program of a
    block whose keys an indexer selects, at the configuration's widths
    (keye-vl-2.0-30b-a3b-6l-ep8: hidden 2048, 32 query heads over 4 K/V
    heads of 128 with a norm a head, an indexer of 16 heads of 64 that picks
    2,048, experts of 768, top 8 of 128 router outputs, theta 1e7, 32 slots,
    pages of 16, a context row of 49,152 positions), with what is no width
    cut so that the CPU holds it: 2 layers, 2 experts held a layer, 512 rows
    of vocabulary, 4,096 pages. The step scores the selector keys where their
    pages lie (``paged_index_scores`` a layer, under ``attn/index``: no
    gather of the selector rows at the table's extent) and attends the K and
    V pages where they lie under the selection's mask
    (``paged_decode_attention`` a layer, under ``attn/sparse``: no sort of
    the scores, no gather of 2,048 rows a slot); a page of the selector pool
    is a tile of 8 rows of two keys; neither program copies or transposes a
    pool; the compiler's count of their transients fits beside the pools."""
    import numpy as np

    from distkeras_tpu.models import zoo
    from distkeras_tpu.serving.engine import DecodeStepper

    model = zoo.keye_lm(
        vocab_size=512, seq_len=49152, hidden_size=2048,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8,
        num_hidden_layers=2,
        sa_config={"indexer_num_heads": 16, "indexer_head_dim": 64,
                   "indexer_num_kv_heads": 1, "topk": 2048},
        rope_theta=1e7, experts_held=[0, 1])
    model.params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                model.params)
    st = DecodeStepper(model, num_slots=32, paged=True, page_size=16,
                       num_pages=4096, kv_dtype=jnp.bfloat16)
    assert st.attention == "kernel" == st.paged_stats()["attention"]
    assert st.selector == "kernel" and st.paged_stats()["selector"] == "kernel"
    assert st._index_page == (8, 128) and st.chunk_cap == 2048
    assert [a.shape for a in st._pools[0]] == [
        (65536, 512), (65536, 512), (4096, 8, 128)]
    # 4 x 128 keys and values and a selector key of 64, bfloat16, a layer
    assert st.kv_bytes_per_token() == 2 * (2048 + 128)
    pbt = st._max_pages_bucket
    assert pbt == 4096 and st._step_table_buckets() == [pbt]

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)

    with _kernels_compiled():
        step = st._build_step_fn_paged(pbt).lower(*shapes((
            st._params, st._ctx, st._pools, st._lens.copy(),
            np.zeros(32, bool), st._tables_array(pbt),
            *st._sampling_args()))).compile()
        chunk = st._build_chunk_fn_paged(2048, pbt).lower(*shapes((
            st._params, st._pools, np.zeros((1, 2048), np.int32),
            st._chunk_where(0, pbt, 0), np.int32(0)))).compile()
    text = step.as_text()
    # K and V: a kernel call a layer under the scope that
    # ``sparse_attn_decode_roofline`` reads; no rows gathered, the selected
    # by token or all at the table's extent, and no sort of the scores
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "paged_decode_attention" in ln]
    assert len(calls) == 2, calls
    for call in calls:
        assert "/attn/sparse/" in call.split('op_name="')[1].split('"')[0]
    assert "bf16[32,2048,512]" not in text
    # two expert layers' three grouped products each, the kernel's
    for compiled in (step, chunk):
        assert len(_grouped_calls(compiled)) == 6
        assert "ragged" not in compiled.as_text()
    assert "[32,49152,512]" not in text and "[32,65536,512]" not in text
    assert not [ln for ln in text.splitlines()
                if " sort(" in ln and "49152" in ln.split("=")[0]]
    # the selector keys: a kernel call a layer under the scope that
    # ``index_decode_roofline`` reads, and nothing of them at the table's
    # extent (the gather body's ``ci[table]`` and its packed products)
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "paged_index_scores" in ln]
    assert len(calls) == 2, calls
    for call in calls:
        assert "/attn/index/" in call.split('op_name="')[1].split('"')[0]
    for gone in ("[32,3072,1024]", "[32,3072,8,128]", "[32,24576,128]"):
        assert gone not in text, gone
    for compiled in (step, chunk):
        for shape in ("[65536,512]", "[4096,8,128]", "[4096,1024]"):
            moved = [ln for ln in compiled.as_text().splitlines()
                     if (" copy(" in ln or " transpose(" in ln)
                     and shape in ln.split("=")[0]]
            assert not moved, moved[:3]
    assert step.memory_analysis().temp_size_in_bytes < 1.0e9
    assert chunk.memory_analysis().temp_size_in_bytes < 1.0e9


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


# the selecting cell's shapes (keye-vl-2.0-30b-a3b-6l-ep8: 32 slots, an
# indexer of 16 heads of 64, 47,616 pages of 16 tokens, a table of 3,072
# pages: 393 KB of scalar-prefetched table; a page of selector keys one tile
# of 8 rows of two keys), and a float32 pool
INDEX_SHAPES = [
    pytest.param(32, 16, 64, (8, 128), 47616, 3072, jnp.bfloat16,
                 id="keye-bf16"),
    pytest.param(8, 4, 64, (8, 128), 64, 16, jnp.float32, id="f32-pool"),
]


def _index_step(one_chip, b, nj, di, page, pages, pbt, dtype):
    from distkeras_tpu.ops.paged_attention import (
        INDEX_BLOCK_PAGES,
        _paged_index_scores,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(qi, w, new, pool, at, table, lengths):
        # the token's page read, changed and written back, as the step does
        pool = pool.at[at].set((pool[at] + new.astype(pool.dtype)))
        with jax.named_scope("attn/index"):
            scores = _paged_index_scores(
                qi, w, pool, table, lengths,
                block_pages=min(INDEX_BLOCK_PAGES, pbt), interpret=False)
        return scores, pool

    idx = s((b,), jnp.int32)
    return jax.jit(step, donate_argnums=(3,)).lower(
        s((b, nj, di), jnp.float32), s((b, nj), jnp.float32),
        s((b, *page), jnp.float32), s((pages, *page), dtype), idx,
        s((b, pbt), jnp.int32), idx,
    )


@pytest.mark.parametrize("b,nj,di,page,pages,pbt,dtype", INDEX_SHAPES)
def test_paged_index_scores_compiles_for_v5e(
    one_chip, b, nj, di, page, pages, pbt, dtype
):
    """The selecting step's page write, then the kernel over the written
    selector pool: the module holds the kernel under the ``attn/index``
    scope that ``index_decode_roofline`` reads its operations by, scores
    of the table's whole extent come back, and the pool is neither copied
    nor transposed."""
    compiled = _index_step(
        one_chip, b, nj, di, page, pages, pbt, dtype).compile()
    text = compiled.as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "/attn/index/" in call.split('op_name="')[1].split('"')[0], call
    assert f"f32[{b},{pbt * 16}]" in text  # (B, T) scores
    pool_shape = f"[{pages},{page[0]},{page[1]}]"
    moved = [ln for ln in text.splitlines()
             if (" copy(" in ln or " transpose(" in ln)
             and pool_shape in ln.split("=")[0]]
    assert not moved, moved


@pytest.mark.parametrize("page,di,page_size", [
    pytest.param((4, 128), 64, 8, id="pages-of-four-rows"),
    pytest.param((1, 1024), 64, 16, id="a-page-a-row-as-the-gather-holds-it"),
])
def test_selector_pages_off_the_tiling_are_refused_by_mosaic(
        one_chip, page, di, page_size):
    """Why the selector pool is held ``(pages, 8, 128)`` where the kernel
    reads it, and why ``decode_attention_path`` keeps smaller pages on the
    gather body: Mosaic copies whole tiles, and neither half a tile nor the
    gather body's one row of 1,024 values a page is one."""
    from distkeras_tpu.ops.paged_attention import (
        decode_attention_path,
        index_page_shape,
    )

    assert index_page_shape(16, 64) == (8, 128)
    if page_size == 8:
        assert decode_attention_path(
            "index", di, jnp.bfloat16, None, page_size).startswith("gather")
    with pytest.raises(Exception, match="aligned to tiling"):
        _index_step(one_chip, 8, 4, di, page, 64, 16,
                    jnp.bfloat16).compile()


def test_the_state_step_and_chunk_programs_compile_for_v5e(one_chip):
    """The stepper's own step program and a 1,024-token chunk program of
    Mamba-2 layers beside a grouped-query layer at the configuration's widths
    (granite-4.0-h-micro: hidden 2048, 64 Mamba heads of 64 with a state of
    128, convolution 4, blocks of 256; 32 query heads over 8 K/V heads of 64,
    no rotation; gated MLPs of 8192; 64 slots, pages of 16, a context row of
    8,192 positions), with what is no width cut so that the CPU holds it: one
    period of 3 Mamba layers and an attention layer, 512 rows of vocabulary,
    1,024 pages. The 8 K/V heads of 64 lie side by side in pairs of 128
    lanes and ride the grouped kernel (``heads_side_by_side``), so there is
    one step program and no gathered copy of a slot's keys; the state is
    float32, ``(64, 64, 64, 128)`` a layer, and neither program copies it
    (donated, updated in place); the compiler's count of their transients
    fits beside the weights, the states and the pool."""
    import numpy as np

    from distkeras_tpu.models import zoo
    from distkeras_tpu.serving.engine import DecodeStepper

    model = zoo.granite_hybrid_lm(
        vocab_size=512, seq_len=8192, hidden_size=2048,
        num_attention_heads=32, num_key_value_heads=8,
        shared_intermediate_size=8192,
        layer_types=("mamba", "mamba", "mamba", "attention"),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=256, attention_multiplier=0.015625)
    model.params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, model.params)
    st = DecodeStepper(model, num_slots=64, paged=True, page_size=16,
                       num_pages=1024, kv_dtype=jnp.bfloat16)
    assert st.layout == "ssm" and st.attention == "kernel"
    assert st._step_table_buckets() == [512]
    assert [a.shape for a in st._pools[0]] == [(64, 64, 64, 128),
                                               (64, 3, 4352)]
    assert st.state_bytes_a_slot == 3 * (2097152 + 3 * 4352 * 4)
    pbt = st._max_pages_bucket
    assert pbt == 512

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)

    with _kernels_compiled():
        step = st._build_step_fn_paged(pbt).lower(*shapes((
            st._params, st._ctx, st._pools, st._lens.copy(),
            np.zeros(64, bool), st._tables_array(pbt),
            *st._sampling_args()))).compile()
        chunk = st._build_chunk_fn_paged(1024, pbt).lower(*shapes((
            st._params, st._pools, np.zeros((1, 1024), np.int32),
            st._chunk_where(0, pbt, 0), np.int32(0)))).compile()
    assert step.as_text().count("tpu_custom_call") >= 1  # the one attention
    for compiled in (step, chunk):
        for shape in ("[64,64,64,128]", f"[{1024 * 16},512]"):
            copies = [ln for ln in compiled.as_text().splitlines()
                      if " copy(" in ln and shape in ln.split("=")[0]]
            assert not copies, copies[:3]
    # 64 slots' step: nothing as large as one layer's states beside them
    assert step.memory_analysis().temp_size_in_bytes < 0.5e9
    assert chunk.memory_analysis().temp_size_in_bytes < 1.5e9
