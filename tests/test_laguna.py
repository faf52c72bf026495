"""The grouped-query block with window and full layers (``models/gqa_moe.py``
``GroupedQueryMoEBlock``, ``zoo.laguna_lm``) against the benchmark's
independent plain reference (``benchmark/families/laguna.py``) at a tiny size,
seeded: the full forward, chunked prefill and paged decode through the two
page budgets (gather step and interpreted kernel step) over a request several
windows long, the served tokens over a bundle, the experts' shares with the
shared expert counted once, the rotary frequencies, the router's
normalisation, the window pool's bound, and the counters."""

import os
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
from distkeras_tpu.models import gqa_moe, mla_moe  # noqa: E402
from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock  # noqa: E402
from distkeras_tpu.models.mla_moe import Picks  # noqa: E402
from distkeras_tpu.ops.quantization import quantize_model  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.engine import DecodeStepper  # noqa: E402

ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}
# hidden 32, 4 / 6 query heads over 2 K/V heads of 16, window 8, the dense
# layer and one period; 8 experts top 3 and a shared expert
CONFIG = {
    "family": "laguna",
    "vocab_size": 211, "max_position_embeddings": 128, "num_hidden_layers": 5,
    "hidden_size": 32, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "sliding_window": 8, "rope_parameters": ROPE,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5,
    "assumed": {"initializer_range": 0.02},
    "serving": {"weight_bits": 16, "weight_bytes": 2, "kv_dtype": "bfloat16",
                "kv_bytes": 2, "num_slots": 4, "page_size": 8,
                "num_pages": 80, "queue_capacity": 64,
                # bfloat16 operands and a bfloat16 cache against the float32
                # reference: the sound runs of this tiny cell read 0 to
                # 0.002; a ring that missed its prefill reads 0.018, a head
                # the reference never saw over 0.05
                "check": {"gap_limit": 0.006}},
}
# the same with heads of 128 (the kernel's lanes) and a window of two pages
KERNEL_CONFIG = {**CONFIG, "head_dim": 128, "sliding_window": 16}

# float32 weights and a float32 cache on both sides, every product at
# precision HIGHEST (the CPU's float32 either way): logits of size 0.4 read
# 1e-7 to 3e-7 apart; a cache rounded to float16 moves them by 1e-5 and more
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("laguna", REPO)


def _tiny(fam, config):
    w = fam.widths(config)
    weights = fam.make_weights(w, 7)
    return w, weights, jax.tree.map(lambda a: a.astype(jnp.float32), weights)


@pytest.fixture(scope="module")
def tiny(fam):
    """(widths, the seeded bfloat16 weights, the same values as float32)."""
    return _tiny(fam, CONFIG)


def _model(fam, w, weights):
    return fam.build_program_model(w, weights, {})


def _reference_logits(fam, w, weights, tokens):
    with jax.default_matmul_precision("highest"):
        h = fam.hidden(weights, jnp.asarray(tokens, jnp.int32), w)
        return np.asarray(fam.logits(weights, h, w))


def test_the_zoo_model_s_apply_is_the_reference_s_forward(fam, tiny):
    """Logits of the whole model, float32 weights on both sides; the blocks
    say their kind, K/V heads and window, and differ by layer."""
    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    blocks = model.layers[1:-2]
    assert all(type(b) is GroupedQueryMoEBlock and b.kind == "gqa"
               and b.kv_heads == 2 and b.head_dim == 16 for b in blocks)
    assert [b.num_heads for b in blocks] == [4, 6, 6, 6, 4]
    assert [b.window for b in blocks] == [None, 8, 8, 8, None]
    assert [b.n_experts for b in blocks] == [0, 8, 8, 8, 8]
    toks = np.random.default_rng(0).integers(0, w["vocab"], (2, 96))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    for row in range(2):
        ref = _reference_logits(fam, w, weights, toks[row])
        np.testing.assert_allclose(got[row], ref, atol=LOGIT_TOL, rtol=0)
    assert fam.param_count(w)["total"] == model.num_params()


@pytest.mark.parametrize("left_out", ["window", "gate", "yarn", "norm_topk"])
def test_each_mechanism_changes_the_logits_when_left_out(fam, tiny, left_out):
    """The window, the gate a head, the YaRN frequencies and the weights'
    normalisation are in the program: a reference without one of them is
    hundreds of tolerances away from it."""
    w, weights, f32 = tiny
    toks = np.random.default_rng(3).integers(0, w["vocab"], (1, 64))
    model = _model(fam, w, f32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])[0]
    np.testing.assert_allclose(
        got, _reference_logits(fam, w, weights, toks[0]), atol=LOGIT_TOL)
    other, other_weights = dict(w), weights
    if left_out == "window":
        other["window"] = 1 << 20
    elif left_out == "norm_topk":
        other["norm_topk"] = False
    elif left_out == "yarn":
        plain = {k: dict(v) for k, v in ROPE.items()}
        plain["full_attention"] = {**plain["sliding_attention"],
                                   "partial_rotary_factor": 0.5}
        other["rope"] = fam._frozen(plain)
    else:  # a gate of sigmoid(0) = 1/2 on every head
        other_weights = jax.tree.map(lambda a: a, weights)
        for i in range(1, 6):
            g = other_weights[str(i)]["attn"]["wgate"]
            other_weights[str(i)]["attn"]["wgate"] = jnp.zeros_like(g)
    ref = _reference_logits(fam, other, other_weights, toks[0])
    assert np.abs(got - ref).max() > 100 * LOGIT_TOL


def test_the_block_s_yarn_frequencies_are_the_reference_s(fam):
    """The program's blend (``gqa_moe.yarn_frequencies``) and the
    reference's own (``pair_frequencies``) at the published settings: 32
    pairs, the fastest kept, the slowest divided by 128, a ramp between."""
    pub = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5}
    ref, factor = fam.pair_frequencies(pub, 64)
    got = gqa_moe.yarn_frequencies(64, 5e5, 128, 8192, 32, 1)
    np.testing.assert_allclose(got, ref, rtol=1e-12)
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64)
    assert factor == pub["attention_factor"]
    assert got[0] == plain[0] and got[-1] == pytest.approx(plain[-1] / 128)
    mixed = (got < plain * (1 - 1e-9)) & (got > plain / 128 * (1 + 1e-9))
    assert 5 <= mixed.sum() <= 20 and (np.diff(got) < 0).all()


def test_route_normalises_softmax_scores_over_the_picks_when_asked():
    """``route``'s one new flag: softmax scores divided by the picks' sum.
    Without it softmax scores stay as they are and sigmoid scores are
    normalised, the two forms the latent blocks use; a router without a
    selection bias picks by score."""
    rng = jax.random.PRNGKey(0)
    p = {"wr": jax.random.normal(rng, (16, 12))}
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    chosen, w = mla_moe.route(p, x, 3, 2.5, softmax=True, normalise=True)
    s = jax.nn.softmax(jnp.dot(x, p["wr"], precision="highest"), axis=-1)
    top = np.argsort(-np.asarray(s), axis=-1)[:, :3]
    assert (np.sort(chosen, axis=-1) == np.sort(top, axis=-1)).all()
    np.testing.assert_allclose(w.sum(axis=-1), 2.5, rtol=1e-6)
    _, raw = mla_moe.route(p, x, 3, 2.5, softmax=True)
    np.testing.assert_allclose(
        raw, 2.5 * np.take_along_axis(np.asarray(s), np.asarray(chosen), -1),
        rtol=1e-6)
    biased = {**p, "bias": jnp.zeros((12,))}
    for kw in ({}, {"softmax": True}):
        a = mla_moe.route(p, x, 3, 2.5, **kw)
        b = mla_moe.route(biased, x, 3, 2.5, **kw)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    _, sig = mla_moe.route(p, x, 3, 1.0)
    np.testing.assert_allclose(sig.sum(axis=-1), 1.0, rtol=1e-6)


def _stepper_logits(model, prompt, n_new, kv_dtype, attention, chunk=16,
                    num_pages=60, page_size=4):
    """Prefill ``prompt`` in chunks of ``chunk`` and decode ``n_new`` tokens
    through the paged stepper; the logits of every decode step, read off the
    step program itself (the final norm's output as the program computed it,
    times the head), and the stepper."""
    st = DecodeStepper(model, num_slots=3, paged=True, page_size=page_size,
                       num_pages=num_pages, kv_dtype=kv_dtype)
    assert st.attention.startswith(attention), st.attention
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
        held = st.window_pages[0]
        active = np.zeros(3, bool)
        active[slot] = True
        toks = [int(st.step(active)[slot]) for _ in range(n_new)]
        jax.effects_barrier()
        assert st.window_pages[0] == held  # decoding takes no more
    finally:
        del norm.apply
    head = np.asarray(model.params[str(len(model.layers) - 1)]["kernel"],
                      np.float32)
    return chunks, toks, np.stack([h[slot] for h in seen]) @ head, st


@pytest.mark.parametrize("config, page_size, attention, chunk", [
    (CONFIG, 4, "gather", 16), (CONFIG, 4, "gather", 5),
    (KERNEL_CONFIG, 8, "kernel", 16), (KERNEL_CONFIG, 8, "kernel", 64)],
    ids=["gather", "gather-odd-chunks", "kernel", "kernel-one-chunk"])
def test_chunked_prefill_then_paged_decode_gives_the_reference_s_logits(
        fam, config, page_size, attention, chunk):
    """Logits, not tokens: every decode step's logits against the
    reference's full forward over the prompt and the served tokens, for a
    request of 65 positions: eight windows of 8 (four of 16), so that every
    window layer's ring of 3 pages is overwritten many times under it, by
    the gather step (heads of 16) and by ``paged_decode_attention``
    (interpreted; heads of 128), in chunks that are and are not whole pages.
    The same comparison fails from a cache rounded to float16."""
    w, weights, f32 = _tiny(fam, config)
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    kw = dict(num_pages=60, page_size=page_size, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        chunks, toks, got, st = _stepper_logits(
            _model(fam, w, f32), prompt, 12, None, attention, **kw)
        # (a float16 pool has no kernel: its step gathers)
        _, toks16, got16, _ = _stepper_logits(
            _model(fam, w, f32), prompt, 12, jnp.float16, "gather", **kw)
    assert chunks == -(-52 // chunk)
    assert st.layout == "gqa" and st._ring == 3
    # 65 positions: 17 (9) pages of the growing budget, 3 of the ring
    assert st._kv_alloc.pages_in_use == -(-65 // page_size)
    assert st.window_pages == (3, 9)
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))
    seq16 = np.concatenate([prompt, toks16])
    ref16 = _reference_logits(fam, w, weights, seq16)[len(prompt) - 1:-1]
    assert np.abs(got16 - ref16).max() > 4 * LOGIT_TOL


def test_the_stepper_sizes_its_pools_by_what_a_block_declares(fam, tiny):
    """A pool a layer kind: the full layers' of ``num_pages``, the window
    layers' of a ring of ``window / page + 1`` pages a slot; flat rows of
    K/V heads x head size; the bytes a token costs by kind."""
    w, _, f32 = tiny
    st = DecodeStepper(_model(fam, w, f32), num_slots=2, paged=True,
                       page_size=4, num_pages=20)
    assert st.layout == "gqa" and st._ring == 3 and st.can_fork is False
    shapes = [[a.shape for a in pair] for pair in st._pools]
    full, window = [(80, 32)] * 2, [((2 * 3 + 1) * 4, 32)] * 2
    assert shapes == [full, window, window, window, full]
    # 2 K/V heads x 16 x (K and V) x 4 bytes a layer
    assert st.kv_bytes_per_token("full") == 2 * 256
    assert st.kv_bytes_per_token("window") == 3 * 256
    assert st.kv_bytes_per_token() == 5 * 256
    stats = st.paged_stats()
    assert stats["bytes_per_token"] == 5 * 256
    assert stats["bytes_per_token_by_kind"] == {"full": 512, "window": 768}
    assert stats["window_positions_max"] == 12
    assert stats["window"]["total_pages"] == 6
    assert stats["prefix_caches"].startswith("off: grouped page layout")
    assert st.kv_bytes_total() == 4 * (2 * 2 * 80 * 32 + 3 * 2 * 28 * 32)


def test_the_serving_engine_serves_the_reference_s_tokens(fam, tiny, tmp_path):
    """Through ``quantize_model(bits=16)``, a bundle and
    ``ServingEngine.from_bundle(paged=True)``: concurrent requests, prefill
    in chunks beside decode, greedy; every served token's reference logit
    against the reference's best; the routing counters."""
    from distkeras_tpu.utils.serialization import save_serving_bundle

    w, weights, f32 = tiny
    model = quantize_model(_model(fam, w, weights), bits=16)
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    eng = ServingEngine.from_bundle(
        path, num_slots=4, paged=True, page_size=8, num_pages=120,
        prefill_chunk=16)
    eng._stepper.warmup()
    eng._stepper.warm_prefill_buckets()
    eng.start()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, w["vocab"], n).astype(np.int32)
               for n in (5, 37, 60, 90, 12, 70)]
    out = {}

    def go(i):
        out[i] = np.asarray(eng.generate(prompts[i], 16))

    threads = [threading.Thread(target=go, args=(i,)) for i in range(6)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    stats, health = eng.stats(), eng.health()
    eng.stop()
    assert health["status"] == "serving" and stats["restarts"] == 0
    paged = stats["paged"]
    assert paged["layout"] == "gqa" and paged["pages_in_use"] == 0
    assert paged["window"]["pages_in_use"] == 0
    assert paged["window_positions_max"] == 16
    moe = stats["moe"]
    assert moe["steps"] > 0 and moe["experts_total"] == 8
    assert 0 < moe["experts_hit_sum"] / moe["steps"] <= 8
    # every pick is a held routed expert's: tokens x 3 picks x 4 layers
    assert moe["zero_picks"] == 0
    assert moe["held_picks"] == moe["routed_tokens"] * 12
    with jax.default_matmul_precision("highest"):
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            gaps, _ = fam.token_gaps(weights, w, seq, len(prompts[i]))
            # float32 cache, bfloat16 operands: a served token is the
            # reference's best or within the operands' rounding of it
            assert gaps.max() <= 0.02


def _one_block(**kw):
    blk = GroupedQueryMoEBlock(
        6, 2, 16, {"theta": 1e4, "partial": 1.0}, window=8, n_experts=8,
        top_k=3, expert_width=16, shared_width=16, routed_scale=2.5, **kw)
    return blk


def test_the_experts_shares_add_up_to_the_whole_layer(fam, tiny):
    """``experts_held`` = four disjoint quarters of the routed experts: each
    share's whole layer output minus what every chip computes alike (the
    attention and the shared expert, counted once), summed over the shares
    and added to it, is the uncut reference's layer; and each share's expert
    layer is the reference's for the same experts."""
    w, weights, f32 = tiny
    p = f32["2"]  # a window layer with experts
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (40, 32))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(fam.layer(p, x, w, dot_highest, 1)[0])
        h = x + fam.attention(
            p["attn"], fam.rms_norm(x, p["ln1"]["gamma"], w["eps"]), w,
            dot_highest, 6, "sliding_attention")
        u = fam.rms_norm(h, p["ln2"]["gamma"], w["eps"])
        alike = np.asarray(h + fam.gated(p["ffn"]["shared"], u, dot_highest))
    total = np.zeros_like(whole)
    for q in range(4):
        held = [2 * q, 2 * q + 1]
        blk = _one_block(experts_held=held)
        part = {**p, "ffn": {**p["ffn"], "experts": {
            k: v[np.asarray(held)] for k, v in p["ffn"]["experts"].items()}}}
        with jax.default_matmul_precision("highest"):
            y, _ = blk.apply(part, {}, x[None])
            mine, picks = blk.ffn(part["ffn"], u)
            ref = fam.expert_layer(p["ffn"], u, w, dot_highest, held=held)[0]
        assert picks.sizes.shape == (2,)
        np.testing.assert_allclose(mine, ref, atol=2e-6, rtol=0)
        total += np.asarray(y)[0] - alike  # this share's routed part
    np.testing.assert_allclose(total + alike, whole, atol=5e-6, rtol=0)
    assert np.abs(total).max() > 1e-4


def test_no_token_is_dropped_and_a_masked_token_routes_nothing():
    blk = _one_block()
    p, _, _ = blk.init(jax.random.PRNGKey(3), (24, 32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    at = jnp.arange(24)
    mask = ((at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - 8))[None]
    y, picks = blk.forward(p, x, pos, mask)
    assert isinstance(picks, Picks) and int(picks.sizes.sum()) == 2 * 24 * 3
    assert int(picks.zero) == 0
    _, some = blk.forward(p, x, pos, mask,
                          token_mask=jnp.arange(24)[None] < 5)
    assert int(some.sizes.sum()) == 2 * 5 * 3
    # a long chunk's FFN goes a block of tokens at a time, to the same sums
    blk.token_block = 8
    try:
        y8, picks8 = blk.forward(p, x, pos, mask)
    finally:
        del blk.token_block
    np.testing.assert_allclose(y8, y, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(picks8.sizes, picks.sizes)


def test_blocked_attention_is_dense_attention_under_the_window_s_mask():
    """``attend_blocked`` (a prefill chunk: key blocks from the window's
    first key to the query's own position, queries a block at a time)
    against ``attend_dense`` under the same mask, keys that start before
    position 0 and an extent that is no whole number of blocks."""
    rng = np.random.default_rng(0)
    n, t, w, start = 48, 70, 9, 20
    q = jnp.asarray(rng.normal(size=(n, 6, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(t, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(t, 2, 16)), jnp.float32)
    qpos, kpos = start + np.arange(n), start - 12 + np.arange(t)
    for window in (w, None):
        see = (kpos[None] <= qpos[:, None]) & (kpos[None] >= 10)
        if window:
            see &= kpos[None] > qpos[:, None] - window
        with jax.default_matmul_precision("highest"):
            want = gqa_moe.attend_dense(q[None], k[None], v[None],
                                        jnp.asarray(see)[None])[0]
            # positions below 10 "do not exist": shift so that they are < 0
            got = gqa_moe.attend_blocked(
                q, k, v, jnp.asarray(qpos - 10), int(kpos[0]) - 10, window,
                key_block=16, query_block=16)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_the_layer_is_found_by_name_when_a_process_loads_a_bundle_only():
    """``layer_from_config`` imports the module that registers the block."""
    code = (
        "from distkeras_tpu.models.layers import layer_from_config\n"
        "b = layer_from_config({'layer': 'GroupedQueryMoEBlock',"
        " 'num_heads': 6, 'kv_heads': 2, 'head_dim': 16,"
        " 'rope': {'theta': 10000.0, 'partial': 1.0}, 'window': 8,"
        " 'ffn_width': 32})\n"
        "print(b.kind, b.kv_heads, b.window)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.stdout.split() == ["gqa", "2", "8"], out.stderr[-2000:]


def test_the_reference_s_first_training_loss_is_the_program_s(fam, tiny):
    """``train_readings`` follows the same forward: its first loss is the
    cross-entropy of the program's own ``apply`` on the same rows."""
    w, weights, f32 = tiny
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
