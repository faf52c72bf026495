"""REAL-data regression end-to-end — the regression face of the
reference's arbitrary-model support (reference: distkeras/trainers.py
trains whatever compiled Keras model the user hands it, regressors
included; SURVEY §3.1 Trainer contract).

Pipeline shape mirrors the classification examples: load the in-repo
442-row diabetes CSV (native C++ parser, float target) -> standardize
features AND target on train statistics only (leak-free) -> trainer
(``loss="mse"``) -> predictor -> R² evaluator. R² is scale-invariant, so
standardizing the target changes nothing about the reported number.

Usage:
    python examples/diabetes_regression.py [single|sync] [--cpu]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")

from distkeras_tpu import (
    ModelPredictor,
    RSquaredEvaluator,
    SingleTrainer,
    StandardScaleTransformer,
    SynchronousDistributedTrainer,
)
from distkeras_tpu.data.loaders import diabetes
from distkeras_tpu.models.zoo import tabular_regressor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="single",
                    choices=["single", "sync"])
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (virtual multi-device mesh)")
    args = ap.parse_args()
    from distkeras_tpu.parallel.backend import setup_backend
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    # the chip, or an error; --cpu asks for the virtual CPU mesh
    enable_compile_cache(
        setup_backend(cpu=args.cpu, cpu_devices=max(args.workers, 8))
    )

    train, test = diabetes().split(0.85, seed=7)
    print(f"real diabetes: {len(train)} train rows, {len(test)} test rows")
    feats = StandardScaleTransformer().fit(train)
    target = StandardScaleTransformer(input_col="label").fit(train)
    train, test = (target.transform(feats.transform(d))
                   for d in (train, test))

    if args.mode == "single":
        trainer = SingleTrainer(
            tabular_regressor(seed=0), "adam", "mse",
            learning_rate=1e-3, batch_size=args.batch,
            num_epoch=args.epochs, seed=0,
        )
    else:
        trainer = SynchronousDistributedTrainer(
            tabular_regressor(seed=0), "adam", "mse",
            learning_rate=1e-3,
            batch_size=max(args.batch // args.workers, 1),
            num_workers=args.workers, num_epoch=args.epochs, seed=0,
        )

    t0 = time.perf_counter()
    trained = trainer.train(train, shuffle=True)
    dt = time.perf_counter() - t0

    pred = ModelPredictor(trained, batch_size=256).predict(test)
    r2 = RSquaredEvaluator().evaluate(pred)
    print(f"{args.mode}: {dt:.1f}s, REAL holdout R^2 {r2:.4f} "
          "(predict-the-mean baseline scores 0.0)")


if __name__ == "__main__":
    main()
