"""MNIST end-to-end — the canonical example (reference: examples/mnist.py).

Pipeline shape mirrors the reference exactly: load CSV -> transformers
(MinMax pixel scaling, one-hot labels, reshape for the CNN) -> trainer ->
predictor -> evaluator. BASELINE configs 1 (SingleTrainer, MLP) and
2 (DOWNPOUR, CNN, 8 workers).

Usage:
    python examples/mnist.py [single|downpour|sync] [--csv path/to/mnist.csv]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")

from distkeras_tpu import (
    DOWNPOUR,
    AccuracyEvaluator,
    LabelIndexTransformer,
    MinMaxTransformer,
    ModelPredictor,
    OneHotTransformer,
    SingleTrainer,
    SynchronousDistributedTrainer,
)
from distkeras_tpu.data.loaders import mnist
from distkeras_tpu.data.transformers import ReshapeTransformer
from distkeras_tpu.models.zoo import mnist_cnn, mnist_mlp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="single",
                    choices=["single", "downpour", "sync"])
    ap.add_argument("--csv", default=None, help="MNIST CSV (label + 784 pixels)")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--zero", action="store_true",
                    help="sync mode: ZeRO-1 — shard optimizer moments "
                    "over the data axis (~workers-fold less per-device "
                    "optimizer memory, same trajectory)")
    ap.add_argument("--n", type=int, default=16384, help="synthetic rows if no CSV")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (virtual multi-device mesh "
                         "via XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    args = ap.parse_args()
    from distkeras_tpu.parallel.backend import setup_backend
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    # the chip, or an error; --cpu asks for the virtual CPU mesh
    enable_compile_cache(
        setup_backend(cpu=args.cpu, cpu_devices=max(args.workers, 8))
    )

    # -- data pipeline (reference: examples/mnist.py transformer chain) ------
    raw = mnist(path=args.csv, n=args.n, flat=True)
    ds = MinMaxTransformer(n_min=0.0, n_max=1.0, o_min=0.0, o_max=255.0)(raw)
    ds = OneHotTransformer(10, input_col="label", output_col="label_onehot")(ds)
    train, test = ds.split(0.9, seed=7)

    if args.mode == "single":
        model = mnist_mlp(seed=0)
        trainer = SingleTrainer(
            model, worker_optimizer="adam", loss="categorical_crossentropy",
            label_col="label_onehot", batch_size=args.batch,
            num_epoch=args.epochs,
        )
    else:
        # CNN path: reshape flat pixels to (28, 28, 1)
        train = ReshapeTransformer("features", "features", (28, 28, 1))(train)
        test = ReshapeTransformer("features", "features", (28, 28, 1))(test)
        model = mnist_cnn(seed=0)
        cls = DOWNPOUR if args.mode == "downpour" else SynchronousDistributedTrainer
        # DOWNPOUR: N workers' window deltas sum at the PS -> local adam lr
        # scales by 1/N (found by calibration on this configuration); the sync
        # trainer means the global-batch loss, so full lr is right there
        lr = 1e-3 / args.workers if cls is DOWNPOUR else 1e-3
        extra = (
            {"shard_opt_state": True}
            if args.zero and cls is SynchronousDistributedTrainer
            else {}
        )
        trainer = cls(
            model, worker_optimizer="adam", learning_rate=lr,
            loss="categorical_crossentropy",
            label_col="label_onehot", batch_size=args.batch,
            num_epoch=args.epochs, num_workers=args.workers, **extra,
        )

    t0 = time.time()
    trained = trainer.train(train, shuffle=True)
    print(f"trained in {time.time() - t0:.1f}s "
          f"({len(train) * args.epochs / (time.time() - t0):.0f} samples/s)")

    # -- inference + evaluation (reference: ModelPredictor -> AccuracyEvaluator)
    pred = ModelPredictor(trained, features_col="features").predict(test)
    pred = LabelIndexTransformer(10)(pred)
    acc = AccuracyEvaluator(
        prediction_col="prediction_index", label_col="label"
    ).evaluate(pred)
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
