"""Measure the REFERENCE'S compute pattern on this host — the missing
measured denominator (VERDICT r3 weak #5: ``vs_baseline`` divided by an
analytic 2,000 samples/sec constant; nothing measured stood behind it).

dist-keras's worker inner loop (reference: distkeras/workers.py ->
Worker.train) is: iterate DataFrame rows in Python inside a Spark
executor, accumulate ``batch_size`` rows, call Keras ``train_on_batch``
on the stacked minibatch, repeat. TensorFlow/Keras are installed in this
sandbox, so that exact pattern is measurable here — same host, same
Python, same per-row iterator overhead the reference pays — against the
SAME CNN architecture (zoo.mnist_cnn: 32/32-pool-64/64-pool convs +
dense 256 + dropout + softmax 10) at the reference's batch size 32.

For the same-host ratio, the companion measurement is our framework's
fair CPU run (``tools/fair_cpu_bench.py``) and, for the chip claim, the
committed TPU record of 2026-07-31 (``BENCH_TPU.json``).

Writes REFERENCE_PATTERN.json and prints one JSON line:
    {"metric": "reference_pattern_train_samples_per_sec", "value": N,
     "unit": "samples/sec", "framework": "tf-keras train_on_batch", ...}

Methodology notes:
- rows stream from a Python generator (row-at-a-time, like
  ``mapPartitions`` hands the worker an iterator of Rows) and are stacked
  with np.stack per batch — the reference's per-batch staging cost.
- warmup batches are excluded (TF's first batches trace/compile).
- single process, CPU — the reference's executors were CPU processes;
  its published deployments scaled by adding executors, so samples/sec
  PER EXECUTOR is the comparable unit (BASELINE.md).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

BATCH = 32  # the reference examples' train batch (SURVEY §3.2)
WARMUP_BATCHES = 10
TIMED_BATCHES = 100


def build_keras_mnist_cnn():
    import keras
    from keras import layers

    model = keras.Sequential(
        [
            keras.Input((28, 28, 1)),
            layers.Conv2D(32, 3, activation="relu", padding="same"),
            layers.Conv2D(32, 3, activation="relu", padding="same"),
            layers.MaxPooling2D(2),
            layers.Conv2D(64, 3, activation="relu", padding="same"),
            layers.Conv2D(64, 3, activation="relu", padding="same"),
            layers.MaxPooling2D(2),
            layers.Flatten(),
            layers.Dense(256, activation="relu"),
            layers.Dropout(0.5),
            layers.Dense(10, activation="softmax"),
        ]
    )
    model.compile(optimizer="sgd", loss="categorical_crossentropy")
    return model


def row_iterator(n, seed=0):
    """Row-at-a-time generator: the shape of the iterator Spark's
    mapPartitions hands the reference worker."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        x = rng.random((28, 28, 1)).astype(np.float32)
        y = np.zeros(10, np.float32)
        y[rng.integers(0, 10)] = 1.0
        yield x, y


def main() -> None:
    import keras

    model = build_keras_mnist_cnn()
    total_rows = (WARMUP_BATCHES + TIMED_BATCHES) * BATCH
    rows = row_iterator(total_rows)

    def next_batch():
        xs, ys = [], []
        for _ in range(BATCH):
            x, y = next(rows)
            xs.append(x)
            ys.append(y)
        return np.stack(xs), np.stack(ys)

    for _ in range(WARMUP_BATCHES):
        model.train_on_batch(*next_batch())

    t0 = time.perf_counter()
    loss = 0.0
    for _ in range(TIMED_BATCHES):
        loss = model.train_on_batch(*next_batch())
    dt = time.perf_counter() - t0

    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "REFERENCE_PATTERN.json",
    )
    record = {
        "metric": "reference_pattern_train_samples_per_sec",
        "value": round(TIMED_BATCHES * BATCH / dt, 1),
        "unit": "samples/sec",
        "framework": f"tf-keras {keras.__version__} train_on_batch "
        "over a Python row iterator",
        "model": "mnist_cnn (32/32-pool-64/64-pool + dense256)",
        "batch": BATCH,
        "timed_batches": TIMED_BATCHES,
        "final_loss": round(float(np.asarray(loss).ravel()[0]), 4),
        "host": os.uname().nodename,
    }
    # anchored to the repo root (where bench.py reads it), never the CWD
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
