"""ResNet-18 / ImageNet-scale — DynSGD staleness-aware async SGD over a
file-sharded streaming dataset (BASELINE config 5; 32 workers at full
scale, reduced here to what the local device count supports).

With no ImageNet on disk, the script WRITES synthetic ImageNet-shaped data
to ``.npz`` shards chunk by chunk (uint8, never holding the full dataset in
one array) and trains from :class:`StreamingDataset`: one shard resident
per worker at a time, preprocessing applied per chunk via ``.map``, window
staging (stack + device_put) optionally prefetched on a background thread
(``prefetch=N``; off by default — the committed v5e A/Bs measured overlap
as a median loss, see PERF.md). This is the input-pipeline shape that
feeds real ImageNet: swap the synthetic writer for shards of decoded
images.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, ".")

import numpy as np

from distkeras_tpu import (
    AccuracyEvaluator,
    DynSGD,
    LabelIndexTransformer,
    ModelPredictor,
    OneHotTransformer,
)
from distkeras_tpu.data.loaders import synthetic_imagenet
from distkeras_tpu.data.streaming import ShardWriter, open_shards
from distkeras_tpu.models.zoo import resnet18

# one label->pattern mapping for every draw of the synthetic task: shards
# and the eval split must agree or the task is unlearnable (see
# loaders._spatial_prototype_classification)
PROTO_SEED = 7


def write_synthetic_shards(
    out_dir, n, num_classes, size, rows_per_shard, seed=PROTO_SEED
):
    """Generate shard files chunk by chunk — peak host memory is one chunk,
    so the on-disk dataset can exceed RAM. All shards land in ONE directory
    with one sidecar, so ``open_shards(out_dir)`` round-trips."""
    with ShardWriter(out_dir) as writer:
        written = 0
        chunk_i = 0
        while written < n:
            rows = min(rows_per_shard, n - written)
            # proto_seed pinned: every chunk (and the eval split) must
            # agree on the label->pattern mapping or the task is unlearnable
            chunk = synthetic_imagenet(
                n=rows, num_classes=num_classes, size=size,
                seed=seed + chunk_i, proto_seed=PROTO_SEED,
            )
            # uint8 on disk (as real image shards would be): 4x smaller files
            writer.add(
                {
                    "features": chunk["features"].astype(np.uint8),
                    "label": chunk["label"],
                }
            )
            written += rows
            chunk_i += 1
    return writer._paths


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--classes", type=int, default=100)
    ap.add_argument("--size", type=int, default=64, help="image side length")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--rows-per-shard", type=int, default=256)
    ap.add_argument("--shard-dir", default=None,
                    help="existing shard tree (skips synthetic generation)")
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

    def preprocess(chunk):
        x = chunk["features"].astype(np.float32) / 255.0
        onehot = np.eye(args.classes, dtype=np.float32)[chunk["label"]]
        return {"features": x, "label": chunk["label"], "label_onehot": onehot}

    if args.shard_dir:
        root = args.shard_dir
    else:
        root = tempfile.mkdtemp(prefix="dkt_imagenet_")
        t0 = time.time()
        shard_paths = write_synthetic_shards(
            root, args.n, args.classes, args.size, args.rows_per_shard
        )
        print(f"wrote {len(shard_paths)} shards under {root} "
              f"in {time.time() - t0:.1f}s (reuse with --shard-dir {root})")
    train = open_shards(root).map(preprocess)

    # held-out eval set stays in-memory (it is small)
    from distkeras_tpu.data.dataset import Dataset

    test_raw = synthetic_imagenet(
        n=max(args.n // 10, args.batch), num_classes=args.classes,
        size=args.size, seed=99, proto_seed=PROTO_SEED,
    )
    test = Dataset(
        {
            "features": np.asarray(test_raw["features"], np.float32) / 255.0,
            "label": test_raw["label"],
        }
    )
    test = OneHotTransformer(
        args.classes, input_col="label", output_col="label_onehot"
    )(test)

    model = resnet18(
        num_classes=args.classes, input_shape=(args.size, args.size, 3),
        seed=0, bn_momentum=0.9,  # short demo runs: eval stats must track
    )
    # adam lr 1e-3 (found by calibration on this configuration): a from-scratch
    # ResNet under DynSGD stays at a constant prediction with plain sgd;
    # the 1/(staleness+1) delta scaling already provides the per-worker
    # division
    trainer = DynSGD(
        model, worker_optimizer="adam", loss="categorical_crossentropy",
        learning_rate=1e-3, label_col="label_onehot", batch_size=args.batch,
        num_epoch=args.epochs, num_workers=args.workers,
        communication_window=4, compute_dtype="bfloat16",
    )
    t0 = time.time()
    trained = trainer.train(train, shuffle=True)
    print(f"trained in {time.time() - t0:.1f}s; "
          f"PS updates: {trainer.parameter_server.num_updates}")

    pred = ModelPredictor(trained, batch_size=256).predict(test)
    pred = LabelIndexTransformer(args.classes)(pred)
    acc = AccuracyEvaluator(
        prediction_col="prediction_index", label_col="label"
    ).evaluate(pred)
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
