"""Long-context sequence classification — TRAINED with ring attention.

No reference counterpart (the reference's workloads are MLP/CNN/tabular —
SURVEY §5.7); this example shows the TPU rebuild's sequence-parallel path:
a transformer classifier trained end-to-end at a sequence length sharded
over a ``Mesh(("seq",))`` — K/V blocks rotate between devices via ppermute
with an online softmax, gradients flow back through the ring, and the
per-device attention footprint is O(T/N · T/N) instead of O(T · T).

Usage:
    python examples/long_context.py [--seq 2048] [--cpu]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context.py --seq 1024 --cpu   # 8-way sharded
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--sp-mode", choices=["ring", "ulysses"], default="ring",
                    help="how attention crosses the sequence shards: the "
                         "K/V ppermute ring, or Ulysses all-to-all head "
                         "sharding (heads divisible by the device count)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (virtual multi-device mesh "
                         "via XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    args = ap.parse_args()
    from distkeras_tpu.parallel.backend import setup_backend
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    # the chip, or an error; --cpu asks for the virtual CPU mesh
    enable_compile_cache(setup_backend(cpu=args.cpu, cpu_devices=8))

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from distkeras_tpu import SequenceParallelTrainer
    from distkeras_tpu.data import loaders
    from distkeras_tpu.data.transformers import OneHotTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.models import zoo
    from distkeras_tpu.parallel.ring_attention import attach_ring_attention
    from distkeras_tpu.predictors import ModelPredictor

    devices = jax.devices()
    n = len(devices)
    if args.seq % n:
        raise SystemExit(
            f"--seq {args.seq} must be divisible by the device count {n}"
        )
    if args.sp_mode == "ulysses" and args.heads % n:
        raise SystemExit(
            f"--sp-mode ulysses shards heads: --heads {args.heads} must be "
            f"divisible by the device count {n}"
        )
    print(f"devices: {n} x {devices[0].platform}; seq {args.seq} "
          f"-> {args.seq // n} tokens/device")

    # TRAIN at the full --seq length with the token axis sharded over the
    # mesh: every gradient step back-propagates through the ppermute ring
    # (per-device attention memory O((T/N)^2) instead of O(T^2))
    ds = loaders.synthetic_sequences(
        n=args.rows, seq_len=args.seq, vocab=args.vocab, seed=0
    )
    ds = OneHotTransformer(2, output_col="label_onehot").transform(ds)
    train, test = ds.split(0.85, seed=0)
    model = zoo.transformer_classifier(
        vocab_size=args.vocab, seq_len=args.seq, d_model=args.d_model,
        num_heads=args.heads, depth=args.depth,
    )
    trainer = SequenceParallelTrainer(
        model, "adam", "categorical_crossentropy",
        batch_size=args.batch, num_epoch=args.epochs,
        label_col="label_onehot", sp_mode=args.sp_mode,
    )
    t0 = time.perf_counter()
    trained = trainer.train(train, shuffle=True)
    train_s = time.perf_counter() - t0
    hist = trainer.get_history()
    # batches() drops the sub-batch remainder; count rows actually consumed
    rows_per_epoch = (len(train) // args.batch) * args.batch
    tokens_per_sec = rows_per_epoch * args.seq * args.epochs / train_s
    print(f"sequence-parallel training at {args.seq} tokens over "
          f"{trainer.num_workers} devices: {train_s:.1f}s "
          f"({tokens_per_sec:,.0f} tokens/s), "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")

    # evaluate long-context: re-attach sharded attention for inference
    # (training detaches its hook; the returned model is dense by default)
    mesh = Mesh(np.array(devices), ("seq",))
    if args.sp_mode == "ulysses":
        from distkeras_tpu.parallel.ulysses import attach_ulysses_attention

        attached = attach_ulysses_attention(trained, mesh)
    else:
        attached = attach_ring_attention(trained, mesh)
    acc = AccuracyEvaluator(label_col="label").evaluate(
        ModelPredictor(trained, batch_size=max(args.batch, 8)).predict(test)
    )
    print(f"long-context ({args.seq} tokens, {args.sp_mode} attention on "
          f"{attached} blocks) test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
