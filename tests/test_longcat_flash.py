"""The shortcut-connected expert layer (``models/mla_moe.py``
``ShortcutMoEBlock``, ``zoo.longcat_flash_lm``) against the benchmark's
independent plain reference (``benchmark/families/longcat_flash.py``) at a
tiny size, seeded: the full forward, chunked prefill and paged decode through
both attentions' pools, the served tokens over a bundle, the experts' shares
with the identity picks counted once, tokens that pick identity experts
only, no dropped token, the two attention factors and the unnormalised
weights, the counters, every refusal, and a tiny copy of the benchmark's cell
through its own driver."""

import os
import sys
import threading
import time

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
from distkeras_tpu.models import mla_moe  # noqa: E402
from distkeras_tpu.models.mla_moe import (  # noqa: E402
    BlockUnsupportedError, LatentMoEBlock, Picks, ShortcutMoEBlock)
from distkeras_tpu.ops.quantization import quantize_model  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.engine import DecodeStepper  # noqa: E402
from test_mla_moe import LOGIT_TOL, _stepper_logits  # noqa: E402

# hidden 64, 4 heads, a query of rank 48, 8 routed + 4 identity experts top-3
CONFIG = {
    "family": "longcat_flash",
    "vocab_size": 211, "max_position_embeddings": 128, "num_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": 48, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 3,
    "routed_scaling_factor": 6, "rope_theta": 10000000, "rms_norm_eps": 1e-5,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "assumed": {"initializer_range": 0.02, "router_bias_std": 0.002},
    "serving": {"weight_bits": 16, "weight_bytes": 2, "kv_dtype": "bfloat16",
                "kv_bytes": 2, "num_slots": 4, "page_size": 8,
                "num_pages": 80, "queue_capacity": 64,
                # bfloat16 operands and a bfloat16 cache against the float32
                # reference: the sound runs of this tiny cell read 0 to
                # 0.01; a head the reference never saw reads over 0.05
                "check": {"gap_limit": 0.03}},
}
SERVE = {
    "kind": "serve", "loop": "closed", "clients": 8, "shape_seed": 1,
    "pool": 32, "block": 8,
    "prompt_len": {"median": 20, "sigma": 0.6, "min": 2, "max": 90},
    "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
    "max_total": 128, "max_requests": 2000, "lead_s": 0.3,
    "stall_s": 5.0, "check": {"requests": 4},
    "trace": {"lead_s": 0.1, "seconds": 0.2},
}


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("longcat_flash", REPO)


@pytest.fixture(scope="module")
def tiny(fam):
    """(widths, the seeded bfloat16 weights, the same values as float32)."""
    w = fam.widths(CONFIG)
    weights = fam.make_weights(w, 7)
    return w, weights, jax.tree.map(lambda a: a.astype(jnp.float32), weights)


def _model(fam, w, weights):
    return fam.build_program_model(w, weights, {})


def _reference_logits(fam, w, weights, tokens):
    with jax.default_matmul_precision("highest"):
        h = fam.hidden(weights, jnp.asarray(tokens, jnp.int32), w)
        return np.asarray(fam.logits(weights, h, w))


def test_the_zoo_model_s_apply_is_the_reference_s_forward(fam, tiny):
    """Logits of the whole model, float32 weights on both sides; the model
    is made of ``ShortcutMoEBlock``s, which say their kind and are no
    ``LatentMoEBlock``."""
    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    blocks = model.layers[1:-2]
    assert all(type(b) is ShortcutMoEBlock and b.kind == "latent"
               and b.cached_rows == 2 and not isinstance(b, LatentMoEBlock)
               for b in blocks)
    toks = np.random.default_rng(0).integers(0, w["vocab"], (2, 96))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    for row in range(2):
        ref = _reference_logits(fam, w, weights, toks[row])
        np.testing.assert_allclose(got[row], ref, atol=LOGIT_TOL, rtol=0)
    assert fam.param_count(w)["total"] == model.num_params()


@pytest.mark.parametrize("left_out", ["q_scale", "kv_scale", "norm_topk"])
def test_each_assumed_factor_changes_the_logits_when_left_out(
        fam, tiny, left_out):
    """``sq``, ``skv`` and the unnormalised weights are in the program: a
    reference without one of them is hundreds of tolerances away."""
    w, weights, f32 = tiny
    assert w["q_scale"] == pytest.approx((64 / 48) ** 0.5)
    assert w["kv_scale"] == pytest.approx(2 ** 0.5) and not w["norm_topk"]
    toks = np.random.default_rng(3).integers(0, w["vocab"], (1, 64))
    model = _model(fam, w, f32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])[0]
    other = {**w, left_out: True if left_out == "norm_topk" else 1.0}
    ref = _reference_logits(fam, other, weights, toks[0])
    assert np.abs(got - ref).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(
        got, _reference_logits(fam, w, weights, toks[0]), atol=LOGIT_TOL)


@pytest.mark.parametrize("page_size, attention", [(4, "gather"), (8, "kernel")])
def test_chunked_prefill_then_paged_decode_gives_the_reference_s_logits(
        fam, tiny, page_size, attention):
    """Logits, not tokens: every decode step's logits against the
    reference's full forward over the prompt and the served tokens, through
    both attentions' pools, by the gather body (pages of 4 rows) and by
    ``paged_latent_attention`` (pages of 8); the same comparison fails from
    a cache rounded to float16."""
    w, weights, f32 = tiny
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    kw = dict(num_pages=60, page_size=page_size, attention=attention)
    with jax.default_matmul_precision("highest"):
        chunks, toks, got = _stepper_logits(
            _model(fam, w, f32), prompt, 12, None, **kw)
        # (a float16 pool has no kernel: its step gathers)
        _, toks16, got16 = _stepper_logits(
            _model(fam, w, f32), prompt, 12, jnp.float16,
            **{**kw, "attention": "gather"})
    assert chunks >= 3  # 52 positions, 16 a chunk
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))
    seq16 = np.concatenate([prompt, toks16])
    ref16 = _reference_logits(fam, w, weights, seq16)[len(prompt) - 1:-1]
    assert np.abs(got16 - ref16).max() > 4 * LOGIT_TOL


def test_the_stepper_sizes_its_pools_by_what_a_block_declares(fam, tiny):
    """Two pool arrays a block here, one for ``LatentMoEBlock``; the bytes
    a cached token takes count attentions, not blocks."""
    from distkeras_tpu.models import zoo

    w, _, f32 = tiny
    st = DecodeStepper(_model(fam, w, f32), num_slots=2, paged=True,
                       page_size=8, num_pages=20)
    assert st.layout == "latent"
    assert [len(rows) for rows in st._pools] == [2, 2]
    assert all(a.shape == (160, 128) for rows in st._pools for a in rows)
    # 2 layers x 2 attentions x (32 + 8 values, padded to 128) x 4 bytes
    assert st.kv_bytes_per_token() == 2 * 2 * 128 * 4
    assert st.paged_stats()["bytes_per_token"] == 2048
    assert st.kv_bytes_total() == 4 * 160 * 128 * 4
    one = DecodeStepper(zoo.mla_moe_lm(num_layers=2), num_slots=2, paged=True,
                        page_size=8, num_pages=20)
    assert [len(rows) for rows in one._pools] == [1, 1]
    assert one.kv_bytes_per_token() == 2 * 128 * 4


def test_the_serving_engine_serves_the_reference_s_tokens(fam, tiny, tmp_path):
    """Through ``quantize_model(bits=16)``, a bundle and
    ``ServingEngine.from_bundle(paged=True)``: concurrent requests, prefill
    in chunks beside decode, greedy; every served token's reference logit
    against the reference's best; the counters of the identity picks."""
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
    assert paged["layout"] == "latent" and paged["attention"] == "kernel"
    assert paged["bytes_per_token"] == 2 * 2 * 128 * 4
    assert paged["prefix_caches"].startswith("off")
    moe = stats["moe"]
    assert moe["steps"] > 0 and moe["experts_total"] == 8
    assert 0 < moe["experts_hit_sum"] / moe["steps"] <= 8
    # every pick is an identity expert's or a held routed expert's (all
    # eight are held): tokens x 3 picks x 2 layers
    assert moe["zero_picks"] > 0 and moe["held_picks"] > 0
    assert moe["zero_picks"] + moe["held_picks"] == moe["routed_tokens"] * 6
    with jax.default_matmul_precision("highest"):
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            gaps, _ = fam.token_gaps(weights, w, seq, len(prompts[i]))
            # float32 cache, bfloat16 operands: a served token is the
            # reference's best or within the operands' rounding of it
            assert gaps.max() <= 0.02


def _one_block(**kw):
    blk = ShortcutMoEBlock(4, 16, 8, 16, 32, 48, 128, 8, 4, 3, 32,
                           routed_scale=6.0, rope_theta=1e7, **kw)
    params, _, _ = blk.init(jax.random.PRNGKey(3), (24, 64))
    return blk, params


def test_absorbed_attention_is_expanded_attention():
    """One layer, the decode step's form against the prefill's, with the
    low-rank query and both factors."""
    blk, p = _one_block()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    mask = jnp.tril(jnp.ones((24, 24), bool))[None]
    with jax.default_matmul_precision("highest"):
        expanded, picks = blk.forward(p, x, pos, mask)
        absorbed, _ = blk.forward(p, x, pos, mask, absorbed=True)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-6, rtol=0)
    assert isinstance(picks, Picks) and picks.sizes.shape == (8,)
    assert int(picks.zero) + int(picks.sizes.sum()) == 2 * 24 * 3


def test_forward_hands_exchange_each_attention_s_rows_in_turn():
    """``exchange`` is called twice a layer, first attention first, with
    rows of the block's latent width."""
    blk, p = _one_block()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 64))
    pos, mask = jnp.arange(6)[None], jnp.tril(jnp.ones((6, 6), bool))[None]
    seen = []

    def exchange(new):
        seen.append(np.asarray(new))
        return new

    with jax.default_matmul_precision("highest"):
        y, _ = blk.forward(p, x, pos, mask, exchange)
        want, _ = blk.forward(p, x, pos, mask)
    assert [s.shape for s in seen] == [(1, 6, 40), (1, 6, 40)]
    assert np.abs(seen[0] - seen[1]).max() > 1e-3
    np.testing.assert_array_equal(y, want)


def _reference_layer(fam, w, p, x):
    with jax.default_matmul_precision("highest"):
        return np.asarray(fam.layer(p, x, w, dot_highest)[0])


def test_the_experts_shares_add_up_to_the_whole_layer(fam, tiny):
    """``experts_held`` = four disjoint quarters of the routed experts: each
    share's whole layer output minus what every chip computes alike (both
    attentions, both dense MLPs and the identity picks, counted once), summed
    over the shares and added to it, is the uncut reference's layer; and each
    share's expert layer is the reference's for the same experts."""
    w, weights, f32 = tiny
    p = f32["1"]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    whole = _reference_layer(fam, w, p, x)
    with jax.default_matmul_precision("highest"):
        h1 = fam.attention_part(p["0"], x, w, dot_highest)
        m, h2, _ = fam.expert_part(p, h1, w, dot_highest)
        h3 = fam.attention_part(p["1"], h2, w, dot_highest)
        u = fam.rms_norm(h1, p["0"]["ln2"]["gamma"], w["eps"])
        zero = fam.expert_layer(p["moe"], u, w, dot_highest,
                                held=np.zeros(0, np.int64))[0]
        alike = np.asarray(fam.mlp_part(p["1"], h3, zero, w, dot_highest))
    pos, mask = jnp.arange(40)[None], jnp.tril(jnp.ones((40, 40), bool))[None]
    total = np.zeros_like(whole)
    for q in range(4):
        held = [2 * q, 2 * q + 1]
        blk, _ = _one_block(experts_held=held)
        part = {**p, "moe": {**p["moe"], "experts": {
            k: v[np.asarray(held)] for k, v in p["moe"]["experts"].items()}}}
        with jax.default_matmul_precision("highest"):
            y, picks = blk.forward(part, x[None], pos, mask)
            mine, _ = blk.moe(part["moe"], u)
            ref = fam.expert_layer(p["moe"], u, w, dot_highest, held=held)[0]
        assert picks.sizes.shape == (2,)
        np.testing.assert_allclose(mine, ref, atol=2e-6, rtol=0)
        total += np.asarray(y)[0] - alike  # this share's routed part
    np.testing.assert_allclose(total + alike, whole, atol=5e-6, rtol=0)
    assert np.abs(total).max() > 1e-4 and np.abs(zero).max() > 1e-3


def _biased(p_moe, outputs):
    bias = np.zeros(12, np.float32)
    bias[list(outputs)] = 10.0
    return {**p_moe, "router": {**p_moe["router"], "bias": jnp.asarray(bias)}}


def test_tokens_that_pick_identity_experts_only_add_no_row(fam, tiny):
    """A selection bias that sends every pick of every token to the identity
    experts 8, 9 and 11: no row in the grouped products, ``weight x u`` in
    the output, which is the reference's; with a token mask the counters
    leave the switched-off tokens out."""
    w, weights, f32 = tiny
    p = _biased(f32["2"]["moe"], (8, 9, 11))
    blk, _ = _one_block()
    u = jax.random.normal(jax.random.PRNGKey(6), (96, 64))
    with jax.default_matmul_precision("highest"):
        y, picks = blk.moe(p, u)
        ref, _, zeros = fam.expert_layer(p, u, w, dot_highest)
        chosen, weight = mla_moe.route(p["router"], u, 3, 6.0, softmax=True)
        _, masked = blk.moe(p, u, jnp.arange(96) < 10)
    assert not np.asarray(picks.sizes).any() and int(picks.zero) == 96 * 3
    assert (np.asarray(zeros) == 3).all() and (np.asarray(chosen) >= 8).all()
    np.testing.assert_allclose(y, ref, atol=5e-6, rtol=0)
    np.testing.assert_allclose(
        y, np.asarray(weight).sum(-1, keepdims=True) * np.asarray(u), atol=5e-6)
    # not normalised: the weights are 6 x the softmax scores as they are
    assert np.asarray(weight).sum(-1).max() < 6.0
    assert int(masked.zero) == 30 and not np.asarray(masked.sizes).any()


def test_no_token_is_dropped_when_all_route_to_the_same_experts(fam, tiny):
    """A selection bias that sends every token to the routed experts 1, 3
    and 5: 96 tokens on each, none dropped (there is no capacity), no
    identity pick."""
    w, weights, f32 = tiny
    p = _biased(f32["2"]["moe"], (1, 3, 5))
    blk, _ = _one_block()
    u = jax.random.normal(jax.random.PRNGKey(6), (96, 64))
    with jax.default_matmul_precision("highest"):
        y, picks = blk.moe(p, u)
        ref = fam.expert_layer(p, u, w, dot_highest)[0]
    assert list(np.asarray(picks.sizes)) == [0, 96, 0, 96, 0, 96, 0, 0]
    assert int(picks.zero) == 0
    np.testing.assert_allclose(y, ref, atol=5e-6, rtol=0)


def test_a_long_chunk_s_expert_layer_goes_a_block_of_tokens_at_a_time(
        monkeypatch):
    """More tokens than ``token_block``: the same output and the same
    counters, a block's sort at a time (an 8,192-token chunk of the cell
    would hold 98,304 rows of 6,144 otherwise, twice 2.25e9 bytes)."""
    blk, p = _one_block()
    u = jax.random.normal(jax.random.PRNGKey(7), (96, 64))
    mask = jnp.arange(96) % 5 != 0
    with jax.default_matmul_precision("highest"):
        whole, picks = blk.moe(p["moe"], u, mask)
        monkeypatch.setattr(ShortcutMoEBlock, "token_block", 32)
        blocked, picks_b = blk.moe(p["moe"], u, mask)
        unmasked, picks_u = blk.moe(p["moe"], u)
    np.testing.assert_allclose(blocked[np.asarray(mask)],
                               whole[np.asarray(mask)], atol=1e-6, rtol=0)
    np.testing.assert_array_equal(picks_b.sizes, picks.sizes)
    assert int(picks_b.zero) == int(picks.zero)
    assert int(picks_u.zero) + int(picks_u.sizes.sum()) == 96 * 3


def test_the_step_s_counters_come_from_the_group_sizes_and_picks():
    """``routing_counts``: experts hit, the largest load, the identity picks
    and the held picks, over the layers; a layer without identity experts
    counts none."""
    picks = [Picks(jnp.asarray([2, 0, 5]), jnp.asarray(4)),
             Picks(jnp.asarray([0, 0, 1]), 0)]
    assert list(np.asarray(mla_moe.routing_counts(picks))) == [3, 5, 4, 8]
    # layers that compact their held rows send a fifth: those that overflowed
    picks = [Picks(p.sizes, p.zero, jnp.asarray(i)) for i, p in enumerate(picks)]
    assert list(np.asarray(mla_moe.routing_counts(picks))) == [3, 5, 4, 8, 1]

    class Span:
        def set_metadata(self, **kw):
            self.kw = kw

    from distkeras_tpu.models import zoo

    st = DecodeStepper(zoo.longcat_flash_lm(), num_slots=3, paged=True,
                       page_size=8, num_pages=20)
    span = Span()
    toks = st._note_routing(np.asarray([7, 8, 9, 6, 5, 4, 8]), 2, span)
    assert list(toks) == [7, 8, 9]
    # 2 tokens x 3 picks x 2 layers = 12 picks, 4 identity, 8 held
    assert span.kw == {
        "experts_hit": 3.0, "expert_load_max": 5, "experts_total": 8,
        "routed_tokens": 2, "zero_picks": 4, "held_picks": 8, "picks": 12,
        "overflow_passes": 0}
    assert {k: st.moe_stats[k] for k in (
        "steps", "routed_tokens", "zero_picks", "held_picks",
        "overflow_passes")} == {
        "steps": 1, "routed_tokens": 2, "zero_picks": 4, "held_picks": 8,
        "overflow_passes": 0}
    # a program whose layers compact their rows sends the fifth counter
    st._note_routing(np.asarray([7, 8, 9, 6, 5, 4, 8, 2]), 2, span)
    assert span.kw["overflow_passes"] == 2 and span.kw["held_picks"] == 8
    assert st.moe_stats["overflow_passes"] == 2 and st.moe_stats["steps"] == 2


def _decode_all_slots(model, steps=3, slots=256):
    """Every slot of a paged stepper prefilled with its own three tokens,
    then ``steps`` decode steps: the tokens and ``moe_stats``."""
    st = DecodeStepper(model, num_slots=slots, paged=True, page_size=8,
                       num_pages=slots + 8)
    rng = np.random.default_rng(5)
    for slot in range(slots):
        left = st.begin_admit(slot, rng.integers(0, 256, 3), max_new=steps)
        while left:
            left = st.prefill_chunk(slot, 8)
    active = np.ones(slots, bool)
    return np.stack([st.step(active) for _ in range(steps)]), st.moe_stats


@pytest.mark.parametrize("biased", [False, True], ids=["even", "skewed"])
def test_the_step_counts_the_layers_whose_held_rows_overflowed_a_pass(
        biased, monkeypatch):
    """A step of 256 slots x top 3 over three held experts of twelve router
    outputs compacts its 768 rows to a pass of 384. Under the seeded
    weights' even routing no layer-step needs a second pass; with a
    selection bias that sends every pick to the held experts every one
    does, and none is dropped: tokens and every other counter are the
    uncompacted body's."""
    from distkeras_tpu.models import zoo

    model = zoo.longcat_flash_lm(experts_held=[0, 1, 2])
    assert mla_moe.held_capacity(256 * 3, 3, 12) == 384
    if biased:
        model.params = jax.tree_util.tree_map_with_path(
            lambda path, a: a.at[:3].set(10.0) if "bias" in str(path) else a,
            model.params)
    toks, moe = _decode_all_slots(model)
    monkeypatch.setattr(mla_moe, "held_capacity", lambda *a: None)
    toks_all_rows, moe_all_rows = _decode_all_slots(model)
    np.testing.assert_array_equal(toks, toks_all_rows)
    assert moe_all_rows.pop("overflow_passes") == 0
    # 3 steps x 2 expert layers
    assert moe.pop("overflow_passes") == (6 if biased else 0)
    assert moe == moe_all_rows and moe["steps"] == 3
    if biased:
        assert moe["held_picks"] == 3 * 2 * 768 and moe["zero_picks"] == 0
    else:
        assert 0 < moe["held_picks"] < 3 * 2 * 384 and moe["zero_picks"] > 0


def test_the_16_bit_tree_and_its_bundle_keep_every_leaf_bit_for_bit(
        fam, tiny, tmp_path):
    """``quantize_model(bits=16)`` and a bundle's round trip, loaded by
    layer names alone."""
    from distkeras_tpu.utils.serialization import (
        load_serving_bundle, save_serving_bundle)

    w, weights, f32 = tiny
    model = quantize_model(_model(fam, w, weights), bits=16)
    for a, b in zip(jax.tree.leaves(model.params), jax.tree.leaves(weights)):
        assert a.dtype == jnp.bfloat16 and np.array_equal(
            np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    cast = quantize_model(_model(fam, w, f32), bits=16).params
    assert cast["1"]["moe"]["experts"]["wg"].dtype == jnp.bfloat16
    assert cast["1"]["0"]["attn"]["wqa"].dtype == jnp.bfloat16
    assert cast["1"]["1"]["attn"]["q_norm"]["gamma"].dtype == jnp.float32
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    back = load_serving_bundle(path)
    assert [type(l).__name__ for l in back.layers] == [
        "Embedding", "ShortcutMoEBlock", "ShortcutMoEBlock", "RMSNorm",
        "Dense"]
    assert back.layers[1].get_config() == model.layers[1].get_config()
    assert jax.tree.structure(back.params) == jax.tree.structure(weights)
    for a, b in zip(jax.tree.leaves(back.params), jax.tree.leaves(weights)):
        assert a.dtype == jnp.bfloat16 and np.array_equal(
            np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))


def test_a_process_that_only_loads_a_bundle_finds_the_block(tmp_path):
    """``layer_from_config`` imports the modules whose blocks register on
    import: a serving host that never built the model loads its bundle."""
    import subprocess

    code = (
        "from distkeras_tpu.models.layers import layer_from_config\n"
        "b = layer_from_config({'layer': 'ShortcutMoEBlock', 'num_heads': 2,"
        " 'qk_nope_dim': 8, 'qk_rope_dim': 4, 'v_dim': 8, 'kv_rank': 16,"
        " 'q_rank': 16, 'ffn_width': 32, 'n_experts': 4, 'n_zero': 2,"
        " 'top_k': 2, 'expert_width': 16})\n"
        "print(b.kind, b.cached_rows)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.stdout.split() == ["latent", "2"], out.stderr[-2000:]


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
    # (the selection bias picks and never weighs: no gradient reaches it)
    change = got["change_norms"]
    assert np.isfinite(change).all() and np.count_nonzero(change == 0) == 2


@pytest.mark.parametrize("feature", [
    "dense_bank", "speculative", "mesh", "int8", "fork", "swap_out", "swap_in",
    "role", "solo_generator"])
def test_what_the_engine_cannot_do_for_this_block_is_refused_typed(
        fam, tiny, feature, tp_mesh):
    """Each refusal is a ``BlockUnsupportedError``, at construction where a
    construction argument asks for the feature, and names the block by what
    it caches."""
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving.engine import NgramDrafter

    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    paged = dict(num_slots=2, paged=True, page_size=4, num_pages=40)
    with pytest.raises(BlockUnsupportedError, match="latent rows"):
        if feature == "dense_bank":
            ServingEngine(model, num_slots=2, paged=False)
        elif feature == "speculative":
            DecodeStepper(model, speculative=NgramDrafter(), **paged)
        elif feature == "mesh":
            ServingEngine(model, mesh=tp_mesh(2), **paged)
        elif feature == "int8":
            ServingEngine(quantize_model(model, bits=8), **paged)
        elif feature == "role":
            ServingEngine(model, role="prefill", **paged)
        elif feature == "solo_generator":
            CachedSequenceGenerator(model).generate(np.ones((1, 4), np.int32), 2)
        else:
            st = DecodeStepper(model, **paged)
            assert st.can_fork is False
            st.admit(0, np.arange(6), max_new=4)
            if feature == "fork":
                st.fork_slot(0, 1)
            elif feature == "swap_out":
                st.swap_out(0)
            else:
                st.swap_in(1, {"len": 3})


# ----------------------------------------- the benchmark's cell, tiny


def _tiny_cell(fam, tmp_path):
    return {"root": str(tmp_path), "config": CONFIG, "traffic": SERVE,
            "family": fam, "cell": {"chips": 1}}


def _drive(cell):
    import types

    from benchmark import drive_serve, harness

    args = types.SimpleNamespace(seed=2**31 + 321, seconds=0.6, trace=0)
    out = drive_serve.run(cell, args, time.perf_counter(),
                          harness.CompileWatch())
    assert out["compiled_in_window"] == 0
    return out


@pytest.mark.e2e
def test_a_tiny_copy_of_the_cell_is_correct_through_the_driver(fam, tmp_path):
    """``drive_serve.run`` as the benchmark runs it: the family's weights,
    ``quantize_model(bits=16)``, the bundle, the paged engine behind
    ``ServingServer``, the reference's check; ``release`` frees the pools
    under their name."""
    out = _drive(_tiny_cell(fam, tmp_path))
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]


@pytest.mark.e2e
def test_the_tiny_cell_from_altered_weights_is_not_correct(
        fam, tmp_path, monkeypatch):
    """The engine serves from a head the reference never saw."""
    real = fam.build_program_model

    def altered(w, weights, traffic):
        head = str(w["layers"] + 2)
        kernel = weights[head]["kernel"]
        noise = 0.05 * jax.random.normal(jax.random.PRNGKey(1), kernel.shape)
        weights = {**weights, head: {
            "kernel": (kernel.astype(jnp.float32) + noise).astype(kernel.dtype)}}
        return real(w, weights, traffic)

    monkeypatch.setattr(fam, "build_program_model", altered)
    out = _drive(_tiny_cell(fam, tmp_path))
    assert out["correct"] is False
    gap = {n: v for n, v, _ in out["compared"]}["widest_logit_gap"]
    assert gap > CONFIG["serving"]["check"]["gap_limit"]
