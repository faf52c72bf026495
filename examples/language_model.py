"""Causal language-model training — the autoregressive long-context family.

No reference counterpart (the reference's workloads are MLP/CNN/tabular —
SURVEY §5.7); this example drives ``zoo.transformer_lm`` through the normal
trainer surface: next-token loss with shift-by-one targets, per-window
next-token accuracy, optional sequence parallelism (the causal ppermute
ring shards the token axis), and a greedy-decode demo at the end.

The toy corpus is a "successor language" (token t+1 = token t + 1 mod V)
so learning is verifiable at a glance: the decode must count upward.

Usage:
    python examples/language_model.py [--seq 128] [--cpu]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/language_model.py --cpu --seq-parallel 8
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=32)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--seq-parallel", type=int, default=0,
                    help="shard the token axis this many ways through the "
                         "causal ring (0 = single-device dense attention)")
    ap.add_argument("--remat", action="store_true",
                    help="per-block jax.checkpoint: activation memory O(1) "
                         "in depth at ~1/3 extra FLOPs")
    ap.add_argument("--text", metavar="PATH", nargs="?", const="", default=None,
                    help="train on a real text file, byte-level (default: "
                         "the repository's LICENSE) instead of the toy "
                         "successor corpus")
    ap.add_argument("--int8", action="store_true",
                    help="serve the decode demo from an int8 weight-only "
                    "copy (ops.quantization.quantize_model) — quarter the "
                    "HBM weight bytes per token on chip")
    ap.add_argument("--save-bundle", metavar="PATH", default=None,
                    help="with --int8: persist the quantized serving copy "
                    "as a serving bundle, reload it, and run the decode "
                    "demo from the RELOADED model (what a serving host "
                    "does at boot)")
    ap.add_argument("--speculative", action="store_true",
                    help="also train a small draft LM and run the decode "
                    "demo speculatively (draft-and-verify; output is "
                    "exactly the main model's greedy decode) — prints "
                    "the measured acceptance per verify round")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.save_bundle and not args.int8:
        # fail BEFORE training, not after a long run
        ap.error("--save-bundle stores a QUANTIZED serving copy; "
                 "pass --int8 too")
    if args.speculative and (args.text is not None or args.seq < 8):
        ap.error("--speculative runs on the toy successor corpus with "
                 "--seq >= 8 (the draft needs the same cheap task)")
    # draft shape, valid by construction (heads must divide d_model):
    draft_heads = 2
    draft_d = max(16, args.d_model // 4)
    draft_d += draft_d % draft_heads
    from distkeras_tpu.parallel.backend import setup_backend
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    # the chip, or an error; --cpu asks for the virtual CPU mesh
    enable_compile_cache(setup_backend(cpu=args.cpu, cpu_devices=8))

    from distkeras_tpu import SequenceParallelTrainer, SingleTrainer
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models import zoo

    if args.text is not None:
        from distkeras_tpu.data import loaders

        ds = loaders.text_corpus(args.text or None, seq_len=args.seq)
        if args.vocab != 32 or args.rows != 1024:
            print("note: --text is byte-level; --vocab is forced to 256 and "
                  "--rows to the corpus window count")
        args.vocab = 256
        print(f"byte-level corpus: {len(ds)} windows of {args.seq}")
    else:
        rng = np.random.default_rng(0)
        starts = rng.integers(0, args.vocab, args.rows)
        xs = ((starts[:, None] + np.arange(args.seq)[None, :]) % args.vocab
              ).astype(np.int32)
        ds = Dataset({"features": xs, "label": xs})

    model = zoo.transformer_lm(
        vocab_size=args.vocab, seq_len=args.seq, d_model=args.d_model,
        num_heads=args.heads, depth=args.depth, seed=0, remat=args.remat,
    )
    kw = dict(
        loss="next_token_crossentropy",
        learning_rate=2e-3,
        batch_size=args.batch,
        num_epoch=args.epochs,
        metrics=["next_token_accuracy"],
        seed=0,
    )
    if args.seq_parallel:
        trainer = SequenceParallelTrainer(
            model, "adam", num_workers=args.seq_parallel, **kw
        )
    else:
        trainer = SingleTrainer(model, "adam", **kw)

    t0 = time.time()
    trained = trainer.train(ds)
    dt = time.time() - t0
    hist = [h for h in trainer.get_history() if "next_token_accuracy" in h]
    print(f"trained {len(ds)} rows x {args.epochs} epochs in {dt:.1f}s; "
          f"next-token accuracy {float(hist[0]['next_token_accuracy']):.3f} "
          f"-> {float(hist[-1]['next_token_accuracy']):.3f}")

    from distkeras_tpu.predictors import CachedSequenceGenerator

    serve_model = trained
    if args.int8:
        from distkeras_tpu.ops.quantization import count_quantized, quantize_model

        serve_model = quantize_model(trained.copy())
        print(f"serving int8 weight-only "
              f"({count_quantized(serve_model.params)} quantized matrices)")
        if args.save_bundle:
            import os

            from distkeras_tpu.utils.serialization import (
                load_serving_bundle,
                save_serving_bundle,
            )

            save_serving_bundle(args.save_bundle, serve_model)
            serve_model = load_serving_bundle(args.save_bundle)
            print(f"serving bundle: {os.path.getsize(args.save_bundle)} "
                  f"bytes at {args.save_bundle}; decoding from the "
                  f"RELOADED copy")
    gen = CachedSequenceGenerator(serve_model)
    if args.text is not None:
        p_len = min(16, max(1, args.seq // 2))
        prompt = ds["features"][len(ds) // 2 : len(ds) // 2 + 1, :p_len]
        steps = max(1, min(48, args.seq - p_len))
        out = gen.generate(prompt, steps=steps)
        txt = bytes(out[0].tolist()).decode("latin-1")
        print(f"decode from {txt[:p_len]!r} -> {txt[p_len:]!r}")
    elif args.seq >= 8:
        # a RAGGED serving batch: three prompts of different lengths in
        # one compiled scan, each continued `steps` tokens (the model
        # learned "count upward", so every row must keep counting from
        # its own prompt end); prompt tokens wrap into the vocab
        steps = min(12, args.seq - 5)
        v = args.vocab
        prompts = [
            np.array([3 % v], np.int32),
            np.array([x % v for x in (10, 11, 12)], np.int32),
            np.arange(5, dtype=np.int32) % v,
        ]
        outs = gen.generate(prompts, steps=steps)
        for row in outs:
            print("greedy decode:", row.tolist())
    else:
        # tiny --seq: the single-prompt demo still fits
        steps = min(12, args.seq - 1)
        out = gen.generate(np.array([[3 % args.vocab]], np.int32),
                           steps=steps)
        print("greedy decode:", out[0].tolist())

    if args.speculative:
        # train a much smaller draft on the same corpus and decode
        # draft-and-verify: the output must equal the main model's
        # greedy decode token for token; acceptance per verify round is
        # the quantity speculative serving lives on
        from distkeras_tpu.predictors import SpeculativeGenerator

        draft = zoo.transformer_lm(
            vocab_size=args.vocab, seq_len=args.seq, d_model=draft_d,
            num_heads=draft_heads, depth=1, seed=1,
        )
        draft_t = SingleTrainer(draft, "adam", **kw).train(ds)
        spec = SpeculativeGenerator(trained, draft_t, k=4)
        sp_steps = min(12, args.seq - 5)
        prompt = np.array([[3 % args.vocab]], np.int32)
        out_s = spec.generate(prompt, steps=sp_steps)
        # re-derive the greedy reference directly in BOTH modes: the
        # ragged demo above may have served the quantized copy (--int8),
        # and reading its outs[0] would couple this branch to the demo
        # branch having run at all
        plain = CachedSequenceGenerator(trained).generate(
            prompt, steps=sp_steps
        )[0]
        match = "EXACT" if (out_s[0] == plain).all() else "MISMATCH"
        print(f"speculative decode ({match} vs greedy): "
              f"{out_s[0].tolist()}; "
              f"{sp_steps} tokens in {int(spec.last_rounds[0])} verify "
              f"rounds ({sp_steps / int(spec.last_rounds[0]):.2f} "
              f"accepted/round)")


if __name__ == "__main__":
    main()
