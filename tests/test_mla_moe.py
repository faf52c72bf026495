"""The latent-attention / routed-expert block (``models/mla_moe.py``,
``zoo.mla_moe_lm``) against the benchmark's independent plain reference
(``benchmark/families/deepseek_v3.py``) at a tiny size, seeded: the full
forward, chunked prefill and paged decode through the serving engine, the
two forms of the attention, the experts' shares, no dropped token, the
16-bit serving tree and its bundle, every refusal, and a tiny copy of the
benchmark's cell through its own driver."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402
from benchmark.reference import dot_highest  # noqa: E402
from distkeras_tpu.models import mla_moe  # noqa: E402
from distkeras_tpu.models.mla_moe import (  # noqa: E402
    BlockUnsupportedError, LatentMoEBlock)
from distkeras_tpu.ops.quantization import quantize_model  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.engine import DecodeStepper  # noqa: E402

# hidden 64, 4 heads, 8 experts top-2, 1 shared, 1 dense + 2 expert layers
CONFIG = {
    "family": "deepseek_v3",
    "vocab_size": 211, "max_position_embeddings": 128,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.448, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6,
    "assumed": {"initializer_range": 0.02, "router_bias_std": 0.01},
    "serving": {"weight_bits": 16, "weight_bytes": 2, "kv_dtype": "bfloat16",
                "kv_bytes": 2, "num_slots": 4, "page_size": 8,
                "num_pages": 80, "queue_capacity": 64,
                # bfloat16 operands and a bfloat16 cache against the
                # float32 reference: the sound runs of this tiny cell read
                # 0 to 0.004; a head the reference never saw reads 0.05
                "check": {"gap_limit": 0.02}},
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
# float32 products in another order than the reference's (absorbed against
# expanded attention, grouped against per-expert sums, chunks against one
# pass): logits of size 0.7 read 1.5e-7 to 2.4e-7 apart over three seeds; a
# cache rounded to float16 moves them by 1.6e-5, to bfloat16 by 1.4e-4
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("deepseek_v3", REPO)


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
    """(i) logits of the whole model, float32 weights on both sides."""
    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    toks = np.random.default_rng(0).integers(0, w["vocab"], (2, 96))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(model.params, model.state, toks)[0])
    for row in range(2):
        ref = _reference_logits(fam, w, weights, toks[row])
        np.testing.assert_allclose(got[row], ref, atol=LOGIT_TOL, rtol=0)
    assert fam.param_count(w)["total"] == model.num_params()


def _stepper_logits(model, prompt, n_new, kv_dtype, chunk=16, num_pages=80,
                    page_size=4, attention="gather"):
    """Prefill ``prompt`` in chunks of ``chunk`` and decode ``n_new`` tokens
    through the paged stepper; the logits of every decode step, read off the
    step program itself (the final norm's output as the program computed
    it, times the head). Pages of 4 rows keep the step on the gather body,
    pages of 8 take it through ``paged_latent_attention``."""
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
        active = np.zeros(3, bool)
        active[slot] = True
        toks = [int(st.step(active)[slot]) for _ in range(n_new)]
        jax.effects_barrier()
    finally:
        del norm.apply
    head = np.asarray(model.params[str(len(model.layers) - 1)]["kernel"],
                      np.float32)
    return chunks, toks, np.stack([h[slot] for h in seen]) @ head


@pytest.mark.parametrize("key_block", [512, 32], ids=["whole", "blocked"])
def test_chunked_prefill_then_paged_decode_gives_the_reference_s_logits(
        fam, tiny, key_block, monkeypatch):
    """(ii) logits, not tokens: every decode step's logits against the
    reference's full forward over the prompt and the served tokens; and the
    same comparison fails from a cache rounded to float16. ``blocked``: the
    chunk attends its 128 cache positions 32 at a time, as many blocks as
    the chunk's end needs (the form the cell's 8,192 positions take)."""
    monkeypatch.setattr(LatentMoEBlock, "key_block", key_block)
    w, weights, f32 = tiny
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    with jax.default_matmul_precision("highest"):
        chunks, toks, got = _stepper_logits(_model(fam, w, f32), prompt, 12, None)
        _, toks16, got16 = _stepper_logits(
            _model(fam, w, f32), prompt, 12, jnp.float16)
    assert chunks >= 3  # 52 positions, 16 a chunk
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))
    seq16 = np.concatenate([prompt, toks16])
    ref16 = _reference_logits(fam, w, weights, seq16)[len(prompt) - 1:-1]
    assert np.abs(got16 - ref16).max() > 4 * LOGIT_TOL


def test_the_kernel_s_decode_step_gives_the_reference_s_logits(fam, tiny):
    """(ii) with pages of 8 rows: the step attends each slot's pages in
    place (``ops.paged_attention.paged_latent_attention``, interpreted
    here), and its logits are the reference's to the same tolerance, two
    slots idle beside the one that decodes."""
    w, weights, f32 = tiny
    prompt = np.random.default_rng(1).integers(0, w["vocab"], 53)
    with jax.default_matmul_precision("highest"):
        _, toks, got = _stepper_logits(
            _model(fam, w, f32), prompt, 12, None, num_pages=60,
            page_size=8, attention="kernel")
    seq = np.concatenate([prompt, toks])
    ref = _reference_logits(fam, w, weights, seq)[len(prompt) - 1:-1]
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert toks == list(ref.argmax(axis=-1))


@pytest.mark.parametrize("hidden, expert_width, grouped", [
    (32, 16, "ragged_dot"), (128, 128, "kernel")])
def test_a_paged_engine_decodes_the_latent_block_through_the_kernel(
        hidden, expert_width, grouped):
    """A tiny ``zoo.mla_moe_lm`` on a paged engine with pages of 8 rows:
    ``stats()["paged"]["attention"]`` says ``"kernel"``, one step program
    is compiled (at the widest table), and concurrent greedy requests of
    unequal length, one slot idle beside them, decode the tokens of the
    un-paged forward (``model.apply`` over the growing sequence). With a
    hidden width and experts of whole lanes the step's and the chunk's
    grouped products are the kernel's too, and ``stats()["moe"]`` says
    so."""
    from distkeras_tpu.models import zoo

    lm = zoo.mla_moe_lm(
        vocab_size=61, seq_len=48, hidden_size=hidden, num_heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, intermediate_size=32,
        moe_intermediate_size=expert_width,
        n_routed_experts=4, num_experts_per_tok=2, num_layers=2, seed=0)
    eng = ServingEngine(lm, num_slots=3, paged=True, page_size=8,
                        prefill_chunk=8)
    eng.start()
    try:
        assert eng.stats()["paged"]["attention"] == "kernel"
        assert eng.stats()["moe"]["grouped"] == grouped
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 61, n).astype(np.int32) for n in (5, 19)]
        reqs = [eng.submit(p, 9) for p in prompts]
        got = [np.asarray(r.result()) for r in reqs]
        paged = eng.stats()["paged"]
    finally:
        eng.stop()
    assert paged["attention"] == "kernel"
    assert paged["compiled_step_buckets"] == [(8, False)]
    with jax.default_matmul_precision("highest"):
        for p, served in zip(prompts, got):
            seq = list(p)
            for _ in range(9):
                x = np.zeros((1, 48), np.int32)
                x[0, :len(seq)] = seq
                logits = lm.apply(lm.params, lm.state, x)[0]
                seq.append(int(np.asarray(logits)[0, len(seq) - 1].argmax()))
            np.testing.assert_array_equal(served, seq)


def test_the_serving_engine_serves_the_reference_s_tokens(fam, tiny, tmp_path):
    """(ii) through ``ServingEngine.from_bundle(paged=True)``: concurrent
    requests, prefill in chunks beside decode, greedy; every served token's
    reference logit against the reference's best."""
    from distkeras_tpu.utils.serialization import save_serving_bundle

    w, weights, f32 = tiny
    model = quantize_model(_model(fam, w, weights), bits=16)
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    eng = ServingEngine.from_bundle(
        path, num_slots=4, paged=True, page_size=4, num_pages=200,
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
    assert paged["layout"] == "latent"
    # 3 layers x (32 + 8 values, padded to the lane width) x 4 bytes
    assert paged["bytes_per_token"] == 3 * 128 * 4
    assert paged["prefix_caches"].startswith("off")
    moe = stats["moe"]
    # hidden 32 against experts of 16: not whole lanes, so the plain form
    assert moe["grouped"] == "ragged_dot"
    assert moe["steps"] > 0 and moe["experts_total"] == 8
    assert 0 < moe["experts_hit_sum"] / moe["steps"] <= 8
    assert moe["expert_load_max_sum"] >= moe["steps"]
    with jax.default_matmul_precision("highest"):
        for i, seq in out.items():
            assert len(seq) == len(prompts[i]) + 16
            gaps, _ = fam.token_gaps(weights, w, seq, len(prompts[i]))
            # float32 cache, bfloat16 operands: a served token is the
            # reference's best or within the operands' rounding of it
            assert gaps.max() <= 0.01


SWAPS = {"gap_limit": 0.1, "swap_share": 0.25, "swap_floor": 8,
         "swap_gap_limit": 2.0}


@pytest.mark.parametrize("n, gaps_at, want", [
    # 16 tokens: the 4 widest are held to 2.0 (scaled by 0.1 / 2.0), the
    # fifth to 0.1 as it is
    (16, {0: 1.5, 3: 1.0, 5: 0.9, 7: 0.8, 9: 0.04}, 0.075),
    (16, {0: 1.5, 3: 1.0, 5: 0.9, 7: 0.8, 9: 0.3}, 0.3),
    (16, {2: 3.0}, 0.15),
    # 4 tokens count as the floor's 8: 2 may be swaps
    (4, {0: 1.0, 1: 1.0, 2: 0.06}, 0.06),
    (4, {0: 1.0, 1: 1.0}, 0.05),
])
def test_a_request_s_widest_gaps_are_held_to_the_swap_s_limit(
        fam, n, gaps_at, want):
    """``judged``: what the serving check takes the widest of, when the
    configuration's ``serving.check`` states a share of swaps."""
    w = fam.widths({**CONFIG, "serving": {**CONFIG["serving"], "check": SWAPS}})
    gaps = np.zeros(n)
    for i, g in gaps_at.items():
        gaps[i] = g
    out = fam.judged(gaps, w)
    assert out.shape == gaps.shape and out.max() == pytest.approx(want)
    # a configuration that states none is judged by its gaps as they are
    plain = fam.widths(CONFIG)
    assert "swap_share" not in plain and "gap_limit" not in plain
    assert fam.judged(gaps, plain) is gaps


def test_the_reference_says_how_narrowly_it_routed(fam, tiny):
    """``route``'s margin is the distance between the last expert taken and
    the first left out; ``served_gaps`` gives each served position's
    narrowest over the expert layers, and ``token_gaps`` its gaps judged."""
    w, weights, _ = tiny
    p = weights["2"]["ffn"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(8), (40, w["d"]))
    with jax.default_matmul_precision("highest"):
        chosen, weight, margin = fam.route(p, x, w, dot_highest)
        s = jax.nn.sigmoid(x @ p["wr"].astype(jnp.float32)) \
            + p["bias"].astype(jnp.float32)
    ranked = np.sort(np.asarray(s), axis=-1)[:, ::-1]
    np.testing.assert_allclose(margin, ranked[:, 1] - ranked[:, 2], atol=1e-6)
    assert (np.sort(chosen, -1) == np.sort(np.argsort(-np.asarray(s), -1)[:, :2],
                                           -1)).all()
    np.testing.assert_allclose(np.asarray(weight).sum(-1), w["routed_scale"],
                               rtol=1e-5)
    seq = np.random.default_rng(5).integers(0, w["vocab"], 30)
    with jax.default_matmul_precision("highest"):
        gaps, none, narrowest = fam.served_gaps(weights, w, seq, 20)
        judged, _ = fam.token_gaps(weights, w, seq, 20)
    assert none is None and gaps.shape == narrowest.shape == (10,)
    assert (narrowest > 0).all() and np.isfinite(narrowest).all()
    np.testing.assert_array_equal(judged, gaps)  # CONFIG states no swaps


def test_the_rounded_control_hands_over_rounded_weights_and_dumps(fam, tiny):
    """``benchmark/controls_rounded.py``: the program's weights rounded a
    column (``levels`` None: as stated), the reference's untouched, and with
    ``dump`` every checked request's gaps and margins before judging."""
    from benchmark.controls_rounded import RoundedFamily, rounded

    w, weights, _ = tiny
    kernel = weights["1"]["attn"]["wq"]
    r = rounded({"k": jnp.array(kernel), "g": weights["1"]["ln1"]["gamma"]}, 7.0)
    assert r["k"].dtype == kernel.dtype and r["g"] is weights["1"]["ln1"]["gamma"]
    steps = np.asarray(r["k"].astype(jnp.float32)) / (
        np.abs(np.asarray(kernel.astype(jnp.float32))).max(0) / 7.0)
    assert np.abs(steps - np.round(steps)).max() < 0.05  # bfloat16's rounding
    seen = []

    class Spy:
        served_gaps, judged, token_gaps = (
            fam.served_gaps, fam.judged, fam.token_gaps)

        @staticmethod
        def build_program_model(w_, weights_, traffic):
            seen.append(weights_)

    sound = RoundedFamily(Spy, None, True)
    sound.build_program_model(w, weights, {})
    assert seen[-1] is weights
    RoundedFamily(Spy, 7.0, False).build_program_model(
        w, jax.tree.map(jnp.array, weights), {})
    assert not np.array_equal(seen[-1]["1"]["attn"]["wq"], kernel)
    seq = np.random.default_rng(5).integers(0, w["vocab"], 30)
    with jax.default_matmul_precision("highest"):
        got, _ = sound.token_gaps(weights, w, seq, 20)
        want, _ = fam.token_gaps(weights, w, seq, 20)
    np.testing.assert_array_equal(got, want)
    (row,) = sound.rows
    assert row["prompt_len"] == 20 and len(row["gaps"]) == len(row["margins"]) == 10


def _one_block(**kw):
    blk = LatentMoEBlock(4, 16, 8, 16, 32, n_experts=8, top_k=2, n_shared=1,
                         expert_width=32, routed_scale=2.448,
                         rope_theta=1e6, **kw)
    params, _, _ = blk.init(jax.random.PRNGKey(3), (24, 64))
    return blk, params


def test_absorbed_attention_is_expanded_attention():
    """(iii) one layer, the decode step's form against the prefill's."""
    blk, p = _one_block()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    mask = jnp.tril(jnp.ones((24, 24), bool))[None]
    with jax.default_matmul_precision("highest"):
        expanded, _ = blk.forward(p, x, pos, mask)
        absorbed, _ = blk.forward(p, x, pos, mask, absorbed=True)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-6, rtol=0)


def _reference_expert_layer(fam, w, p_ffn, x, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(fam.expert_layer(p_ffn, x, w, dot_highest, **kw)[0])


def test_the_experts_shares_add_up_to_the_whole_layer(fam, tiny):
    """(iv) ``experts_held`` = four disjoint quarters: the routed parts
    summed, the shared experts counted once, equal the uncut reference's
    whole layer."""
    w, weights, f32 = tiny
    p = f32["2"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    whole = _reference_expert_layer(fam, w, p, x)
    shared = np.asarray(mla_moe.gated_mlp(p["shared"], x))
    total = np.zeros_like(whole)
    for q in range(4):
        held = [2 * q, 2 * q + 1]
        blk, _ = _one_block(experts_held=held)
        part = {**p, "experts": {k: v[np.asarray(held)]
                                 for k, v in p["experts"].items()}}
        with jax.default_matmul_precision("highest"):
            y, picks = blk.ffn(part, x)
        assert picks.sizes.shape == (2,)
        total += np.asarray(y) - shared  # this share's routed part
        # and each share is the reference's for the same experts
        ref = _reference_expert_layer(fam, w, p, x, held=held,
                                      with_shared=False)
        np.testing.assert_allclose(np.asarray(y) - shared, ref, atol=2e-6)
    np.testing.assert_allclose(total + shared, whole, atol=5e-6, rtol=0)


def test_no_token_is_dropped_when_all_route_to_one_expert(fam, tiny):
    """(v) a selection bias that sends every token to experts 3 and 5: 96
    tokens on each, none dropped (there is no capacity)."""
    w, weights, f32 = tiny
    p = f32["3"]["ffn"]
    bias = np.zeros(8, np.float32)
    bias[[3, 5]] = 10.0
    p = {**p, "router": {**p["router"], "bias": jnp.asarray(bias)}}
    blk, _ = _one_block()
    x = jax.random.normal(jax.random.PRNGKey(6), (96, 64))
    with jax.default_matmul_precision("highest"):
        y, picks = blk.ffn(p, x)
    assert list(np.asarray(picks.sizes)) == [0, 0, 0, 96, 0, 96, 0, 0]
    np.testing.assert_allclose(
        y, _reference_expert_layer(fam, w, p, x), atol=5e-6, rtol=0)


def _plain_experts(p, x, chosen, weights, held, token_mask):
    """The held experts' routed sum, a token and a pick at a time, in
    float32: the reference the grouped passes are held to."""
    local = {e: i for i, e in enumerate(held)}
    y = np.zeros(x.shape, np.float32)
    sizes = np.zeros(len(held), np.int32)
    for t in range(x.shape[0]):
        if token_mask is not None and not token_mask[t]:
            continue
        for e, weight in zip(chosen[t], weights[t]):
            if int(e) not in local:
                continue
            i = local[int(e)]
            sizes[i] += 1
            gate = x[t] @ p["wg"][i]
            h = gate / (1.0 + np.exp(-gate)) * (x[t] @ p["wu"][i])
            y[t] += weight * (h @ p["wd"][i])
    return y, sizes


def _expert_inputs(n, held, outputs=48, top_k=4, d=16, width=8, biased=False):
    """Seeded stacked experts, hidden states and a router over ``outputs``;
    ``biased``: a selection bias that sends every pick to a held expert."""
    rng = np.random.default_rng(n + len(held) + (d != 16))

    def normal(*shape):
        return (0.1 * rng.normal(size=shape)).astype(np.float32)

    p = {"wg": normal(len(held), d, width), "wu": normal(len(held), d, width),
         "wd": normal(len(held), width, d)}
    router = {"wr": normal(d, outputs), "bias": np.zeros(outputs, np.float32)}
    if biased:
        router["bias"][np.asarray(held)] = 10.0
    x = rng.normal(size=(n, d)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        chosen, weights = mla_moe.route(router, jnp.asarray(x), top_k, 2.5)
    return p, x, np.asarray(chosen), np.asarray(weights)


def _routed(p, x, chosen, weights, held, outputs, token_mask):
    with jax.default_matmul_precision("highest"):
        return jax.jit(
            lambda *a: mla_moe.routed_experts(*a[:4], held, outputs, a[4])
        )(p, x, chosen, weights, token_mask)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("n", [16, 600], ids=["64rows", "2400rows"])
@pytest.mark.parametrize("held", [
    list(range(48)), list(range(3, 48, 4)), [17]],
    ids=["all_held", "1_in_4", "1_in_48"])
@pytest.mark.parametrize("d, width", [(16, 8), (128, 256)],
                         ids=["ragged_dot", "kernel"])
def test_routed_experts_is_the_plain_sum_over_held_picks(d, width, held, n,
                                                         masked):
    """(v-b) the grouped passes against a loop over tokens and picks, for
    every held share of the router's width, below and above the rows at
    which a share is compacted; the same bits from call to call. At widths
    of whole lanes the three products are the kernel's (interpreted here),
    the uncompacted body's and a compacted pass's alike."""
    p, x, chosen, weights = _expert_inputs(n, held, d=d, width=width)
    mask = (np.arange(n) % 5 != 0) if masked else None
    want, sizes = _plain_experts(p, x, chosen, weights, held, mask)
    y, picks = _routed(p, x, chosen, weights, held, 48, mask)
    again, _ = _routed(p, x, chosen, weights, held, 48, mask)
    # sums of 128 and 256 float32 terms round where sums of 16 and 8 do
    np.testing.assert_allclose(y, want, atol=1e-6 if d == 16 else 2e-5,
                               rtol=0)
    np.testing.assert_array_equal(picks.sizes, sizes)
    np.testing.assert_array_equal(y, again)
    compacted = mla_moe.held_capacity(n * 4, len(held), 48) is not None
    assert compacted == (n == 600 and len(held) < 48)
    # uniform picks: the held rows fit one pass
    assert picks.overflow is None if not compacted else int(picks.overflow) == 0
    assert sizes.sum() > 0 and np.abs(want).max() > 1e-4


@pytest.mark.parametrize("k, n, form", [
    (16, 8, "ragged_dot"), (8, 16, "ragged_dot"), (64, 64, "ragged_dot"),
    (128, 64, "ragged_dot"), (128, 256, "kernel"), (256, 128, "kernel"),
])
def test_the_grouped_product_takes_its_form_from_the_widths(k, n, form):
    """``_grouped_mm``'s rule: the kernel where ``k`` and ``n`` are whole
    groups of 128 lanes, ``ragged_dot`` otherwise; the program holds the
    one it says and not the other, and both are the same product."""
    assert mla_moe.grouped_form(k, n) == form
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.normal(size=(24, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, k, n)), jnp.float32)
    sizes = jnp.asarray([9, 0, 11], jnp.int32)
    text = str(jax.make_jaxpr(mla_moe._grouped_mm)(x, w, sizes))
    assert ("pallas_call" in text) == (form == "kernel")
    assert ("ragged_dot" in text) == (form == "ragged_dot")
    with jax.default_matmul_precision("highest"):
        got = mla_moe._grouped_mm(x, w, sizes)
        want = jax.lax.ragged_dot(x, w, sizes)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    assert not np.asarray(got)[20:].any()


@pytest.mark.parametrize("tokens, passes", [(400, 2), (600, 3)])
def test_held_rows_beyond_a_pass_take_further_passes_and_none_is_dropped(
        tokens, passes):
    """(v-c) a selection bias that sends every pick to the twelve held
    experts: ``tokens x 4`` held rows (a mask switches the other tokens
    off) against a pass of 896, so the loop behind the first pass runs
    once and twice; the same sum."""
    held = list(range(3, 48, 4))
    p, x, chosen, weights = _expert_inputs(600, held, biased=True)
    cap = mla_moe.held_capacity(600 * 4, 12, 48)
    assert np.isin(chosen, held).all() and cap == 896
    assert -(-tokens * 4 // cap) == passes
    mask = np.arange(600) < tokens
    want, sizes = _plain_experts(p, x, chosen, weights, held, mask)
    y, picks = _routed(p, x, chosen, weights, held, 48, mask)
    again, _ = _routed(p, x, chosen, weights, held, 48, mask)
    np.testing.assert_allclose(y, want, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(picks.sizes, sizes)
    np.testing.assert_array_equal(y, again)
    assert int(picks.overflow) == 1 and sizes.sum() == tokens * 4
    # a token mask that leaves one pass's worth of rows: no further pass
    mask = np.arange(600) < cap // 4
    y, picks = _routed(p, x, chosen, weights, held, 48, mask)
    want, _ = _plain_experts(p, x, chosen, weights, held, mask)
    np.testing.assert_allclose(y, want, atol=1e-6, rtol=0)
    assert int(picks.overflow) == 0 and int(picks.sizes.sum()) == cap


def test_a_layer_that_holds_every_expert_has_no_pass_loop():
    """(v-d) the capacity follows the held share: with every expert held,
    or too few rows to save any, the program is the uncompacted body (no
    loop, no row added to its token); a held share of many rows has a first
    pass and the loop's, each with its add."""
    # whole tiles of 128 rows, an odd number of them
    assert mla_moe.held_capacity(1536, 16, 768) == 384   # a step, 1 in 48
    assert mla_moe.held_capacity(12288, 16, 768) == 640  # a chunk's block
    assert mla_moe.held_capacity(10240, 64, 256) == 3456  # a block, 1 in 4
    assert mla_moe.held_capacity(960, 64, 256) is None    # its step: 640
    assert mla_moe.held_capacity(6144, 128, 128) is None  # every expert
    assert mla_moe.held_capacity(64, 1, 48) is None       # a handful of rows

    def program(held, n):
        p, x, chosen, weights = _expert_inputs(n, held)
        return str(jax.make_jaxpr(
            lambda *a: mla_moe.routed_experts(*a, held, 48)
        )(p, x, chosen, weights))

    # the group sizes' bincount is the one scatter of the uncompacted body
    for text in (program(list(range(48)), 600), program([17], 16)):
        assert "while" not in text and text.count("scatter-add") == 1
    shared = program(list(range(3, 48, 4)), 600)
    assert "while" in shared and shared.count("scatter-add") == 3


def test_the_16_bit_tree_and_its_bundle_keep_every_leaf_bit_for_bit(
        fam, tiny, tmp_path):
    """(vi) ``quantize_model(bits=16)`` and a bundle's round trip."""
    from distkeras_tpu.utils.serialization import (
        load_serving_bundle, save_serving_bundle, serialize_model)

    w, weights, f32 = tiny
    model = quantize_model(_model(fam, w, weights), bits=16)
    for a, b in zip(jax.tree.leaves(model.params), jax.tree.leaves(weights)):
        assert a.dtype == jnp.bfloat16 and np.array_equal(
            np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    # from float32: every matrix, the embedding and the stacked experts
    # become bfloat16 (exact: the values are bfloat16's), vectors stay
    cast = quantize_model(_model(fam, w, f32), bits=16).params
    assert cast["0"]["tokens"].dtype == jnp.bfloat16
    assert cast["2"]["ffn"]["experts"]["wg"].dtype == jnp.bfloat16
    assert cast["2"]["ln1"]["gamma"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(cast["2"]["ffn"]["experts"]["wd"], np.float32),
        np.asarray(f32["2"]["ffn"]["experts"]["wd"]))
    path = str(tmp_path / "tiny.dkt")
    save_serving_bundle(path, model)
    back = load_serving_bundle(path)
    assert [type(l).__name__ for l in back.layers] == [
        "Embedding", "LatentMoEBlock", "LatentMoEBlock", "LatentMoEBlock",
        "RMSNorm", "Dense"]
    assert back.layers[2].get_config() == model.layers[2].get_config()
    assert jax.tree.structure(back.params) == jax.tree.structure(weights)
    for a, b in zip(jax.tree.leaves(back.params), jax.tree.leaves(weights)):
        assert a.dtype == jnp.bfloat16 and np.array_equal(
            np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    with pytest.raises(ValueError, match="bits must be"):
        quantize_model(_model(fam, w, f32), bits=2)
    with pytest.raises(ValueError, match="not quantized"):
        save_serving_bundle(path, _model(fam, w, f32))
    assert serialize_model(_model(fam, w, f32))  # the float32 master still is


@pytest.mark.parametrize("feature", [
    "dense_bank", "speculative", "mesh", "int8", "fork", "swap_out", "swap_in",
    "role", "solo_generator"])
def test_what_the_engine_cannot_do_for_this_block_is_refused_typed(
        fam, tiny, feature, tp_mesh):
    """(vii) each refusal is a ``BlockUnsupportedError``, at construction
    where a construction argument asks for the feature."""
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving.engine import NgramDrafter

    w, weights, f32 = tiny
    model = _model(fam, w, f32)
    paged = dict(num_slots=2, paged=True, page_size=4, num_pages=40)
    with pytest.raises(BlockUnsupportedError):
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

    args = types.SimpleNamespace(seed=2**31 + 281, seconds=0.6, trace=0)
    out = drive_serve.run(cell, args, time.perf_counter(),
                          harness.CompileWatch())
    assert out["compiled_in_window"] == 0
    return out


@pytest.mark.e2e
def test_a_tiny_copy_of_the_cell_is_correct_through_the_driver(fam, tmp_path):
    """(viii) ``drive_serve.run`` as the benchmark runs it: the family's
    weights, ``quantize_model(bits=16)``, the bundle, the paged engine
    behind ``ServingServer``, the reference's check."""
    out = _drive(_tiny_cell(fam, tmp_path))
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["e2e"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert 0 < c["occupancy_sum_window"] <= c["slot_steps_window"]
    need = fam.decode_step(fam.widths(CONFIG), c["mean_batch"],
                           c["mean_cached"], weight_bytes=2, kv_bytes=2)
    parts = need["parts"]
    assert 0 < parts["moe"]["bytes"] + parts["mla"]["bytes"] < need["bytes"]
    assert 0 < parts["moe"]["flops"] + parts["mla"]["flops"] < need["flops"]


@pytest.mark.e2e
def test_the_tiny_cell_from_altered_weights_is_not_correct(
        fam, tmp_path, monkeypatch):
    """(viii) the engine serves from a head the reference never saw."""
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


def test_the_expert_layer_differentiates_through_the_kernel(monkeypatch):
    """``apply``'s use: ``routed_experts`` at widths of whole lanes under
    ``jax.grad`` (the kernel's ``custom_vjp``, group sizes traced inside the
    differentiated function) gives the gradients of the plain form, to the
    hidden states and to every expert's three matrices."""
    held = list(range(0, 48, 4))
    p, x, chosen, weights = (
        jax.tree.map(jnp.asarray, a)
        for a in _expert_inputs(40, held, d=128, width=128))

    def loss():  # a function a form: a traced one is not traced again
        def loss(p, x):
            y, _ = mla_moe.routed_experts(p, x, chosen, weights, held, 48)
            return jnp.sum(jnp.sin(y))
        return loss

    with jax.default_matmul_precision("highest"):
        assert "pallas_call" in str(jax.make_jaxpr(loss())(p, x))
        got = jax.jit(jax.grad(loss(), (0, 1)))(p, x)
        monkeypatch.setattr(mla_moe, "grouped_form", lambda k, n: "ragged_dot")
        assert "pallas_call" not in str(jax.make_jaxpr(loss())(p, x))
        want = jax.jit(jax.grad(loss(), (0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 1e-3
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)
