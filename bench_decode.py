"""Serving-path benchmark: autoregressive decode tokens/sec, KV-cache vs
full-recompute, on the MXU-shaped LM (d512 L8 seq512, bf16-era f32 params).

Decode is the memory-bound side of the framework (one attention row and
one MLP per token); this harness measures ``CachedSequenceGenerator``
(the O(T d) serving path) against ``SequenceGenerator`` (full recompute,
O(T^2 d)) on the same trained-shape model. The timing region ends with a
host fetch of the produced tokens (``bench.sync_fetch`` rationale: the
fetched tokens ARE the proof of execution).

Writes BENCH_DECODE.json and prints one JSON line:
    {"metric": "lm_decode_tokens_per_sec", "value": ..., "unit":
     "tokens/sec", "cached": ..., "uncached": ..., "speedup": ...}

Usage: python bench_decode.py [--cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from bench import setup_backend


def _measure_fork_parallel(platform, dev) -> dict:
    """Parallel sampling W ways from ONE prompt: the dense slot bank
    pays W full prefills and W full cache footprints; the paged bank
    admits once and CoW-FORKS the page table W-1 times (shared prefix
    pages, one partial-page copy per fork). Both sides then decode the
    same W streams through the same scheduler-free drive, so the ratio
    isolates what the fork machinery saves — the cheap-beam/parallel
    claim ROADMAP item 1 priced against the committed dense beam cost
    (BENCH_DECODE.json ``beam_search.cost_vs_f32_cached``)."""
    from distkeras_tpu.models.zoo import transformer_lm
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving.engine import DecodeStepper

    on_cpu = platform == "cpu"
    seq, d_model, depth, heads = (64, 128, 2, 4) if on_cpu else (512, 512, 8, 8)
    width = 4
    prompt_len = seq // 2  # a LONG shared prompt: what forking amortizes
    steps = seq // 4
    model = transformer_lm(
        vocab_size=8192, seq_len=seq, d_model=d_model, num_heads=heads,
        depth=depth, seed=0,
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 8192, prompt_len).astype(np.int32)
    temp = 0.8  # sampling: parallel streams must be able to diverge

    def drive(st, admit):
        admit(st)
        active = np.ones(width, bool)
        for _ in range(steps):
            st.step(active)

    def timed(mk, admit):
        st = mk()
        drive(st, admit)  # compile + warm
        for s in range(width):
            st.release(s)
        if getattr(st, "paged", False):
            # isolate the FORK: a device-prefix hit on the timed
            # re-admission would hand the paged side the prefill for
            # free through a different mechanism than the one priced;
            # ledgers reset so the committed row counts the timed forks
            if st.prefix_index is not None:
                st.prefix_index.clear()
            st._kv_alloc.reset_counters()
        t0 = time.perf_counter()
        drive(st, admit)
        dt = time.perf_counter() - t0
        return width * steps / dt

    def dense_admit(st):
        for s in range(width):
            st.admit(s, prompt)  # W full prefills

    def fork_admit(st):
        st.admit(0, prompt, max_new=steps + 1)
        for s in range(1, width):
            st.fork_slot(0, s, max_new=steps + 1)

    dense_tps = timed(
        lambda: DecodeStepper(model, num_slots=width, temperature=temp,
                              seed=0),
        dense_admit,
    )
    st_paged = []

    def mk_paged():
        st = DecodeStepper(model, num_slots=width, temperature=temp,
                           seed=0, paged=True, page_size=16)
        st_paged.append(st)
        return st

    fork_tps = timed(mk_paged, fork_admit)
    alloc = st_paged[-1]._kv_alloc
    # the greedy-identity pin is covered by tests; here pin the CLAIM'S
    # mechanics: the fork shared pages instead of recomputing them
    assert alloc.cow_copies >= 1 or prompt_len % 16 == 1
    # plain batched decode at the same width = the cost denominator the
    # committed beam row uses (what width-W decode costs with NO
    # shared-prompt machinery at all)
    plain = CachedSequenceGenerator(model, temperature=temp, seed=0)
    prompts_w = np.tile(prompt[None], (width, 1))
    plain.generate(prompts_w, steps=steps)
    t0 = time.perf_counter()
    plain.generate(prompts_w, steps=steps)
    plain_tps = width * steps / (time.perf_counter() - t0)
    return {
        "platform": platform,
        "device_kind": dev.device_kind,
        "width": width,
        "prompt_len": prompt_len,
        "decode_steps": steps,
        "temperature": temp,
        "plain_cached_w4_tokens_per_sec": round(plain_tps, 1),
        "dense_parallel_tokens_per_sec": round(dense_tps, 1),
        "paged_fork_tokens_per_sec": round(fork_tps, 1),
        "fork_vs_dense_parallel": round(fork_tps / dense_tps, 2),
        "cost_vs_plain_cached_w4": round(plain_tps / fork_tps, 2),
        "dense_parallel_cost_vs_plain_cached_w4": round(
            plain_tps / dense_tps, 2
        ),
        "cow_copies": int(alloc.cow_copies),
        "shared_pages_at_admit": int(alloc.shared_pages),
    }


#: stated next to every sharded row measured on the CPU mesh: the
#: "devices" are virtual slices of ONE host, so tp:N pays the real
#: partitioning + collective overhead while the N-memory-system
#: bandwidth win (the whole point on chip — PERF.md pins decode as
#: weight-read-bound) cannot appear. Ratios here gate collapse and
#: identity, not the on-chip speedup claim.
_SINGLE_HOST_CAVEAT = (
    "measured on one host with --xla_force_host_platform_device_count "
    "virtual devices: the tp:N sides pay partitioning/collective "
    "overhead but time-share one memory system, so ratios are a FLOOR "
    "on sharding cost, not a measure of the N-way HBM win"
)


def _measure_sharded(platform, dev, smoke=False) -> dict:
    """tp1 vs tp2 vs tp4 paged decode at EQUAL TOTAL KV BYTES: the
    same model, slot bank, page pool, and prompts, with only the mesh
    changing — the pool is head-sharded over the mesh, so total bytes
    are constant and only bytes-per-shard move. Every pass's outputs
    are asserted token-identical to the solo (tp1) pass before a
    number is recorded. The honest adversarial row runs a model small
    enough that per-step collective latency dominates any conceivable
    read win — committed as measured."""
    import jax

    from distkeras_tpu.models.zoo import transformer_lm
    from distkeras_tpu.parallel.mesh import serving_mesh
    from distkeras_tpu.serving.engine import DecodeStepper

    on_cpu = platform == "cpu"
    seq, d_model, depth = (64, 128, 2) if on_cpu else (512, 512, 8)
    heads = 4 if on_cpu else 8
    slots = 2 if smoke else 4
    steps = 8 if smoke else seq // 4
    prompt_len = seq // 4
    ways = [1, 2, 4]
    avail = len(jax.devices())
    ways = [w for w in ways if w <= avail]

    def run_grid(model, label):
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, model.params["0"]["tokens"].shape[0],
                         prompt_len).astype(np.int32)
            for _ in range(slots)
        ]

        def admit(st):
            for s, p in enumerate(prompts):
                st.admit(s, p, max_new=steps + 1)

        def decode(st):
            active = np.ones(slots, bool)
            outs = [[] for _ in range(slots)]
            for _ in range(steps):
                toks = st.step(active)
                for s in range(slots):
                    outs[s].append(int(toks[s]))
            return outs

        rows, ref, kv_bytes = {}, None, None
        for w in ways:
            mesh = None if w == 1 else serving_mesh(f"tp:{w}")
            st = DecodeStepper(
                model, num_slots=slots, paged=True, page_size=16,
                prefix_cache=None, mesh=mesh,
            )
            if kv_bytes is None:
                kv_bytes = st.kv_bytes_total()
            else:
                # the equal-byte-budget contract of this A/B
                assert st.kv_bytes_total() == kv_bytes, (
                    w, st.kv_bytes_total(), kv_bytes
                )
            admit(st)
            decode(st)  # compile + warm every program
            for s in range(slots):
                st.release(s)
            if st.prefix_index is not None:
                st.prefix_index.clear()
            # admission (prefill) runs OUTSIDE the timed window: the
            # row is labeled tokens/sec over decode_steps, so the
            # denominator must be decode time alone
            admit(st)
            t0 = time.perf_counter()
            outs = decode(st)
            dt = time.perf_counter() - t0
            if ref is None:
                ref = outs
            # identity asserted per pass, per slot, BEFORE recording
            assert outs == ref, f"{label} tp{w} diverged from tp1"
            rows[f"tp{w}"] = {
                "tokens_per_sec": round(slots * steps / dt, 1),
                "kv_shard_bytes": st.kv_shard_bytes(),
                "outputs_identical": True,
            }
        base = rows["tp1"]["tokens_per_sec"]
        for k, row in rows.items():
            row["ratio_vs_tp1"] = round(row["tokens_per_sec"] / base, 3)
        return rows, kv_bytes

    model = transformer_lm(
        vocab_size=512, seq_len=seq, d_model=d_model, num_heads=heads,
        depth=depth, seed=0,
    )
    rows, kv_bytes = run_grid(model, "main")
    # the adversarial row: a model so small the per-step collectives
    # cannot possibly amortize — tp4 SHOULD lose here, and the loss is
    # committed as measured (no cherry-picking the grid)
    small = transformer_lm(
        vocab_size=64, seq_len=32, d_model=32, num_heads=4, depth=1,
        seed=0,
    )
    adv = None
    if 4 in ways:

        def run_small():
            rng = np.random.default_rng(1)
            p = rng.integers(0, 64, 8).astype(np.int32)
            out = {}
            ref = None
            for w in (1, 4):
                mesh = None if w == 1 else serving_mesh("tp:4")
                st = DecodeStepper(
                    small, num_slots=2, paged=True, page_size=4,
                    prefix_cache=None, mesh=mesh,
                )
                st.admit(0, p, max_new=9)
                active = np.zeros(2, bool)
                active[0] = True
                toks = [int(st.step(active)[0]) for _ in range(8)]
                st.release(0)
                if st.prefix_index is not None:
                    st.prefix_index.clear()
                st.admit(0, p, max_new=9)
                t0 = time.perf_counter()
                toks = [int(st.step(active)[0]) for _ in range(8)]
                dt = time.perf_counter() - t0
                if ref is None:
                    ref = toks
                assert toks == ref, "adversarial tp4 diverged"
                out[f"tp{w}"] = round(8 / dt, 1)
            return out

        tps = run_small()
        adv = {
            "model": "transformer_lm d32 L1 seq32 (tiny: collectives "
                     "cannot amortize)",
            "tp1_tokens_per_sec": tps["tp1"],
            "tp4_tokens_per_sec": tps["tp4"],
            "ratio_vs_tp1": round(tps["tp4"] / tps["tp1"], 3),
            "outputs_identical": True,
        }
    return {
        "platform": platform,
        "device_kind": dev.device_kind,
        "devices_available": avail,
        "single_host_caveat": _SINGLE_HOST_CAVEAT,
        "model": f"transformer_lm d{d_model} L{depth} seq{seq} "
                 f"h{heads}",
        "num_slots": slots,
        "prompt_len": prompt_len,
        "decode_steps": steps,
        "kv_bytes_total": kv_bytes,
        "rows": rows,
        "adversarial_small_tp4": adv,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--fork-only", action="store_true",
                    help="measure ONLY the page-fork parallel-sampling "
                         "row and merge it into the existing "
                         "BENCH_DECODE.json (the committed on-chip "
                         "rows keep their measured numbers; this row "
                         "states its own platform)")
    ap.add_argument("--sharded-only", action="store_true",
                    help="measure ONLY the tensor-parallel decode grid "
                         "(tp1 vs tp2 vs tp4 at equal total KV bytes, "
                         "outputs identity-asserted per pass) and "
                         "merge it as the 'sharded' block of "
                         "BENCH_DECODE.json; creates the file when "
                         "absent (the check_bench temp-dir flow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sharded grid for the regression gate "
                         "(fewer slots/steps; ratios are noisy — the "
                         "committed artifact carries the claims)")
    args = ap.parse_args()

    # the sharded grid needs a multi-device topology: 8 virtual CPU
    # devices (the tests' mesh) when --cpu asks for the CPU
    platform = setup_backend(
        cpu=args.cpu, cpu_devices=8 if args.sharded_only else 1,
    )

    if args.sharded_only:
        import jax

        dev = jax.devices()[0]
        print(f"device: {dev.platform} ({dev.device_kind})", flush=True)
        record = {}
        if os.path.exists("BENCH_DECODE.json"):
            with open("BENCH_DECODE.json") as f:
                record = json.load(f)
        record["sharded"] = _measure_sharded(
            platform, dev, smoke=args.smoke
        )
        with open("BENCH_DECODE.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"sharded": record["sharded"]}))
        return

    if args.fork_only:
        import jax

        dev = jax.devices()[0]
        print(f"device: {dev.platform} ({dev.device_kind})", flush=True)
        with open("BENCH_DECODE.json") as f:
            record = json.load(f)
        record["page_fork_parallel"] = _measure_fork_parallel(
            platform, dev
        )
        with open("BENCH_DECODE.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps(
            {"page_fork_parallel": record["page_fork_parallel"]}
        ))
        return

    import jax

    from distkeras_tpu.models.zoo import transformer_lm
    from distkeras_tpu.predictors import (
        BeamSearchGenerator,
        CachedSequenceGenerator,
        SequenceGenerator,
    )
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(platform=platform)
    on_cpu = platform == "cpu"
    seq, d_model, depth, heads = (64, 128, 2, 4) if on_cpu else (512, 512, 8, 8)
    batch = 2 if on_cpu else 8
    prompt_len = seq // 8
    steps = seq - prompt_len  # fill the context
    uncached_steps = min(steps, 16 if on_cpu else 64)

    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})", flush=True)

    model = transformer_lm(
        vocab_size=8192, seq_len=seq, d_model=d_model, num_heads=heads,
        depth=depth, seed=0,
    )
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 8192, (batch, prompt_len)).astype(np.int32)

    def timed(gen, n_steps, batch_prompts=None):
        p = prompts if batch_prompts is None else batch_prompts
        gen.generate(p, steps=n_steps)  # compile + warm
        t0 = time.perf_counter()
        out = gen.generate(p, steps=n_steps)  # .generate host-fetches
        dt = time.perf_counter() - t0
        assert np.asarray(out).shape == (len(p), p.shape[1] + n_steps)
        return len(p) * n_steps / dt

    cached_tps = timed(CachedSequenceGenerator(model), steps)
    uncached_tps = timed(SequenceGenerator(model), uncached_steps)

    # weight-only int8 A/B on the SAME cached path: decode streams every
    # weight matrix from HBM once per token, so quartering the weight
    # bytes (ops/quantization.py) should move tokens/sec on chip; the
    # numerics are pinned off-chip by tests/test_quantization.py
    from distkeras_tpu.ops.quantization import count_quantized, quantize_model

    model_q = quantize_model(model.copy())
    int8_tps = timed(CachedSequenceGenerator(model_q), steps)
    # full serving bundle: int8 weights + bf16 K/V caches (halves the
    # other big per-token HBM stream; tests/test_quantization.py pins
    # the numerics of both pieces and the bundle)
    import jax.numpy as jnp

    bundle_tps = timed(
        CachedSequenceGenerator(model_q, kv_dtype=jnp.bfloat16), steps
    )
    # max-compression bundle: packed int4 weights (eighth-width, two
    # values per HBM byte) + bf16 K/V — the unpack is two shifts fused
    # into the matmul operand read, so this measures pure bytes-vs-
    # compute trade on chip
    model_q4 = quantize_model(model.copy(), bits=4)
    int4_tps = timed(
        CachedSequenceGenerator(model_q4, kv_dtype=jnp.bfloat16), steps
    )
    # beam search: W hypotheses ride the cache batch axis, plus a
    # per-token parent-beam cache gather — this row measures that
    # documented O(W) serving cost against the same f32 cached baseline
    beam_w = 4
    beam_tps = timed(BeamSearchGenerator(model, beam_width=beam_w), steps)

    # speculative decoding: needs models that AGREE, so train a
    # target/draft pair on the successor language (seconds at these
    # shapes), then race single-stream plain cached decode against
    # draft-and-verify — the one row here whose models are trained,
    # because acceptance (the whole mechanism) is a property of trained
    # agreement, not of random weights
    from distkeras_tpu import SingleTrainer
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.predictors import SpeculativeGenerator

    t_shape = (128, 2, 4) if on_cpu else (512, 8, 8)
    d_shape = (64, 1, 2) if on_cpu else (128, 2, 4)
    sv = 512  # successor vocab: small enough to train in seconds
    rng2 = np.random.default_rng(1)
    starts = rng2.integers(0, sv // 2, (512, 1))
    seqs = ((starts + np.arange(seq)) % sv).astype(np.int32)
    ds = Dataset({"features": seqs, "label": seqs})
    # 6 epochs: the 2-epoch pair only reached 1.27 accepted/round on
    # chip (2026-08-01) — acceptance is the mechanism, so train until
    # the pair actually agrees; still seconds at these shapes
    kw = dict(loss="next_token_crossentropy", num_epoch=6, batch_size=64,
              seed=0)

    def trained_lm(d, L, h):
        lm = transformer_lm(vocab_size=sv, seq_len=seq, d_model=d,
                            num_heads=h, depth=L, seed=0)
        return SingleTrainer(lm, "adam", **kw).train(ds)

    target_t = trained_lm(*t_shape)
    draft_t = trained_lm(*d_shape)
    spec_prompt = seqs[:1, :prompt_len]

    plain_1 = timed(
        CachedSequenceGenerator(target_t), steps, batch_prompts=spec_prompt
    )
    spec_gen = SpeculativeGenerator(target_t, draft_t, k=4)
    spec_1 = timed(spec_gen, steps, batch_prompts=spec_prompt)
    spec_rounds = int(spec_gen.last_rounds[0])

    record = {
        "metric": "lm_decode_tokens_per_sec",
        "value": round(cached_tps, 1),
        "unit": "tokens/sec",
        "platform": platform,
        "device_kind": dev.device_kind,
        "model": f"transformer_lm d{d_model} L{depth} seq{seq}",
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_steps": steps,
        "cached_tokens_per_sec": round(cached_tps, 1),
        # the uncached run covers only its first uncached_steps tokens
        # (contexts prompt_len..prompt_len+uncached_steps), the CHEAPEST
        # part of the O(T^2) recompute curve — so this ratio is a lower
        # bound on the full-decode advantage, and the field names say
        # which context range each side measured
        "uncached_tokens_per_sec_short_ctx": round(uncached_tps, 1),
        "uncached_ctx_range": [prompt_len, prompt_len + uncached_steps],
        "cached_ctx_range": [prompt_len, seq],
        "speedup_vs_uncached_short_ctx_lower_bound": round(
            cached_tps / uncached_tps, 2
        ),
        "int8_weight_only": {
            "tokens_per_sec": round(int8_tps, 1),
            "speedup_vs_f32_cached": round(int8_tps / cached_tps, 3),
            "quantized_matrices": count_quantized(model_q.params),
        },
        "int8_plus_bf16_kv": {
            "tokens_per_sec": round(bundle_tps, 1),
            "speedup_vs_f32_cached": round(bundle_tps / cached_tps, 3),
        },
        "int4_plus_bf16_kv": {
            "tokens_per_sec": round(int4_tps, 1),
            "speedup_vs_f32_cached": round(int4_tps / cached_tps, 3),
        },
        "beam_search": {
            "beam_width": beam_w,
            "tokens_per_sec": round(beam_tps, 1),
            "cost_vs_f32_cached": round(cached_tps / beam_tps, 2),
        },
        # single-stream (batch 1), TRAINED d{t} target + d{d} draft —
        # acceptance is trained agreement, so this is the one row whose
        # models are not random; speedup > 1 is the speculative claim
        "speculative_k4_trained_pair": {
            "target": f"d{t_shape[0]} L{t_shape[1]}",
            "draft": f"d{d_shape[0]} L{d_shape[1]}",
            "plain_cached_tokens_per_sec_b1": round(plain_1, 1),
            "speculative_tokens_per_sec_b1": round(spec_1, 1),
            "speedup": round(spec_1 / plain_1, 2),
            "verify_rounds": spec_rounds,
            "decode_steps": steps,
            "mean_accepted_per_round": round(steps / spec_rounds, 2),
        },
    }
    with open("BENCH_DECODE.json", "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
