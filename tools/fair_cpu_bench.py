"""The framework's FAIR same-host CPU number (VERDICT r4 weak #3 / task 3).

An early CPU-scaled run of `bench.py` showed 6.5 samples/sec while the
reference's own pattern (tf-keras ``train_on_batch``, measured by
tools/reference_pattern_bench.py) does ~794 samples/sec on the same host.
That 6.5 was never a fair CPU measurement: it ran the NORTH-STAR shape
(batch 128) on an 8-virtual-device mesh time-slicing ONE physical core.

This harness measures the number that IS comparable to the reference
pattern: ONE CPU device (no virtual mesh), XLA:CPU free to use its host
threads, the SAME CNN (zoo.mnist_cnn, full width), the SAME batch size 32,
f32 (CPU has no fast bf16), through the framework's standard device-
resident training path (``WorkerCore.indexed_window`` — the same code path
bench.py times on chip). Steady state: the first, compile-bearing window
is excluded, like every other harness here.

Writes FAIR_CPU.json at the repo root and prints one JSON line.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

# runnable as `python tools/fair_cpu_bench.py`: the repo root (bench.py,
# distkeras_tpu) is this file's parent's parent, not the script dir
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = 32  # the reference pattern's batch (tools/reference_pattern_bench.py)
WINDOW = 8  # steps fused per XLA call; 256 samples/window
WARMUP_WINDOWS = 2
TIMED_WINDOWS = 12


def main() -> None:
    from distkeras_tpu.parallel.mesh import force_cpu_mesh

    force_cpu_mesh(1)  # ONE device: the fair unit is this host, undivided

    import jax

    from distkeras_tpu.models.zoo import mnist_cnn
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.workers import WorkerCore
    from bench import _flops_per_call, measured_reference_pattern, sync_fetch

    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr)

    model = mnist_cnn(seed=0)
    core = WorkerCore(
        model,
        get_optimizer("sgd", 0.01),
        "categorical_crossentropy",
        compute_dtype=None,  # f32: XLA:CPU emulates bf16 slowly
    )

    n_data = BATCH * 64
    rng = np.random.default_rng(0)
    data_x = jax.device_put(rng.random((n_data, 28, 28, 1), np.float32))
    data_y = jax.device_put(
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, n_data)]
    )

    def fresh_idx():
        return rng.integers(0, n_data, (WINDOW, BATCH)).astype(np.int32)

    params, state = model.params, model.state
    opt_state = core.init_opt_state(params)
    key = jax.random.PRNGKey(0)

    flops_per_window = _flops_per_call(
        core.indexed_window.lower(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        ).compile()
    )

    for _ in range(WARMUP_WINDOWS):
        params, state, opt_state, key, mets = core.indexed_window(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        )
    sync_fetch(mets["loss"])

    t0 = time.perf_counter()
    for _ in range(TIMED_WINDOWS):
        params, state, opt_state, key, mets = core.indexed_window(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        )
    final_loss = sync_fetch(mets["loss"])
    dt = time.perf_counter() - t0

    sps = TIMED_WINDOWS * WINDOW * BATCH / dt
    record = {
        "metric": "fair_cpu_train_samples_per_sec",
        "value": round(sps, 1),
        "unit": "samples/sec",
        "platform": "cpu",
        "device_kind": dev.device_kind,
        "devices": 1,
        "batch": BATCH,
        "compute_dtype": "float32",
        "host_cores": os.cpu_count(),
        "final_loss": (
            round(final_loss, 4) if math.isfinite(final_loss)
            else repr(final_loss)
        ),
        "model_flops_per_sec_tf": (
            round(flops_per_window * TIMED_WINDOWS / dt / 1e12, 4)
            if flops_per_window is not None
            else None
        ),
    }
    ref = measured_reference_pattern()
    if ref is not None:
        record["measured_reference_pattern"] = ref
        record["vs_measured_reference_same_host"] = round(
            sps / ref["value"], 2
        )
    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "FAIR_CPU.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
