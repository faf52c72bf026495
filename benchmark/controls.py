"""The controls of ``correct``: the lower precision in the program's place,
at the cell's own size, on the chip. Each has to come out NOT correct.

    python3 benchmark/controls.py --workload <cell> --seeds 11,12,13 [--seconds 12]

Training: the reference computed with int8 products (both operands and the
incoming gradient rounded to 127 steps) stands in for the program, which
states bfloat16; its readings are compared with the float32 reference's as
a run's are. Serving: the program's own int4 path (``quantize_model(bits=4)``)
is switched on for a short window at the cell's own load, and the run's own
check reads the served tokens. The benchmark's own runs never run this; a
small-size copy is a test under ``tests/benchmark``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, loadgen, spec  # noqa: E402
from benchmark.harness import log  # noqa: E402


def train_control(cell: dict, seed: int, precision: str = "int8") -> dict:
    import jax
    import numpy as np

    from benchmark import drive_train

    family = cell["family"]
    w = family.widths(cell["config"])
    traffic = cell["traffic"]
    rows = int(traffic["batch_per_chip"]) * int(cell["cell"]["chips"])
    rng = np.random.default_rng(seed)
    batches = [loadgen.training_batch(traffic, rng, rows, w["seq"], w["vocab"])
               for _ in range(int(traffic["check"]["steps"]))]
    lr, k = float(traffic["learning_rate"]), int(traffic["window"])
    with jax.default_matmul_precision("highest"):
        ref = family.train_readings(w, seed, batches, lr, moment_after=k)
        low = family.train_readings(w, seed, batches, lr, precision, k)
    ok, rows_ = drive_train.compare(low, ref, traffic["check"]["limits"])
    return {"seed": seed, "correct": ok,
            "compared": {name: [value, limit] for name, value, limit in rows_}}


def serve_control(cell: dict, seed: int, seconds: float, bits: int = 4) -> dict:
    import types

    from benchmark import drive_serve

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    watch = harness.CompileWatch()
    out = drive_serve.run(cell, args, time.perf_counter(), watch,
                          overrides={"weight_bits": bits})
    return {"seed": seed, "correct": out["correct"],
            "compared": {name: [value, limit]
                         for name, value, limit in out["compared"]},
            "attempted": out["attempted"], "failed": out["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, ROOT)
    train = cell["traffic"]["kind"] == "train"
    # a training control runs the reference alone, on one chip of any host
    device = harness.require_tpu(1 if train else int(cell["cell"]["chips"]),
                                 exact=not train)
    harness.enable_cache(device["platform"])
    failed_to_fail = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        if train:
            row = train_control(cell, seed)
        else:
            row = serve_control(cell, seed, args.seconds)
        log(f"control {args.workload}: {json.dumps(row)}")
        failed_to_fail += bool(row["correct"])
    log(f"controls that came out correct (should be 0): {failed_to_fail}")
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
