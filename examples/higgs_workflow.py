"""ATLAS-Higgs tabular workflow — AEASGD (reference: examples/workflow.ipynb;
BASELINE config 3).

Pipeline: load CSV of physics features -> standard-scale -> one-hot ->
AEASGD trainer (elastic averaging) -> predictor -> evaluator.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")

from distkeras_tpu import (
    AEASGD,
    AccuracyEvaluator,
    LabelIndexTransformer,
    ModelPredictor,
    OneHotTransformer,
)
from distkeras_tpu.data.loaders import load_csv, synthetic_higgs
from distkeras_tpu.data.transformers import StandardScaleTransformer
from distkeras_tpu.models.zoo import higgs_mlp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", default=None, help="Higgs CSV (label + features)")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rho", type=float, default=5.0)
    ap.add_argument("--n", type=int, default=32768)
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

    raw = load_csv(args.csv) if args.csv else synthetic_higgs(n=args.n)
    num_features = raw["features"].shape[1]
    ds = StandardScaleTransformer()(raw)
    ds = OneHotTransformer(2, input_col="label", output_col="label_onehot")(ds)
    train, test = ds.split(0.85, seed=7)

    model = higgs_mlp(num_features=num_features, seed=0)
    trainer = AEASGD(
        model, worker_optimizer="sgd", loss="categorical_crossentropy",
        learning_rate=0.05, label_col="label_onehot", batch_size=args.batch,
        num_epoch=args.epochs, num_workers=args.workers, rho=args.rho,
        communication_window=8,
    )
    t0 = time.time()
    trained = trainer.train(train, shuffle=True)
    print(f"trained in {time.time() - t0:.1f}s; "
          f"PS updates: {trainer.parameter_server.num_updates}")

    pred = ModelPredictor(trained).predict(test)
    pred = LabelIndexTransformer(2)(pred)
    acc = AccuracyEvaluator(
        prediction_col="prediction_index", label_col="label"
    ).evaluate(pred)
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
