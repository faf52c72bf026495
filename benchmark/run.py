"""One cell, once, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``configs/``, the model family that file names under ``families/``, its
traffic mix under ``traffic/`` and its per-layer metrics under
``layer_metrics/``, all by name; the traffic file's ``kind`` picks the
driver. Fails, and prints no result, without a TPU. The last line of
standard output is the result; everything else goes on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, readers, spec, trace_reduce  # noqa: E402
from benchmark.harness import log  # noqa: E402

DRIVERS = {"train": "benchmark.drive_train", "serve": "benchmark.drive_serve"}


def per_layer_metrics(cell: dict, out: dict, device: dict, trace: dict) -> tuple:
    ctx = {
        "samples": out["samples"],
        "counters": out["counters"], "trace": trace, "e2e": out["e2e"],
        "family": cell["family"], "widths": cell["family"].widths(cell["config"]),
        "config": cell["config"], "traffic": cell["traffic"],
        "peaks": spec.load_peaks(device["kind"]), "chips": cell["cell"]["chips"],
        "operands": {},
    }
    metrics = {}
    for entry in cell["per_layer"]:
        m = {"name": entry["name"],
             **spec.load_layer_metric(entry["name"], cell["root"], cell["bench"])}
        value = readers.read(m, ctx)
        if value is None:
            log(f"per-layer: {entry['name']} found nothing to read")
            continue
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics, ctx["operands"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, ROOT)
    device = harness.require_tpu(int(cell["cell"]["chips"]))
    cache = harness.enable_cache(device["platform"])
    watch = harness.CompileWatch()
    log(f"cell {args.workload}: config {cell['cell']['config']}, traffic "
        f"{cell['cell']['traffic']}, seed {args.seed}, seconds {args.seconds}, "
        f"trace {args.trace}; device {json.dumps(device)}; compile cache {cache}")

    kind = cell["traffic"]["kind"]
    if kind not in DRIVERS:
        raise spec.SpecError(f"traffic kind {kind!r}: no driver ({sorted(DRIVERS)})")
    out = importlib.import_module(DRIVERS[kind]).run(cell, args, T_START, watch)

    if out["compiled_in_window"]:
        print(f"benchmark: {out['compiled_in_window']} program(s) compiled "
              f"inside the measured window; warm them in set-up",
              file=sys.stderr, flush=True)
        return 5
    out["e2e"]["setup_s"] = out["setup_s"]
    log(f"compile: {json.dumps(watch.snapshot())}")
    device["memory_peak_bytes"] = out["peak_bytes"]

    breakdown = None
    if args.trace:
        path = out["profile"].path()
        if path is None:
            print("benchmark: the profiler wrote no trace", file=sys.stderr)
            return 6
        table = spec.load_trace_table(ROOT, cell["bench"])
        trace = trace_reduce.reduce_trace(trace_reduce.load_xplane(path), table)
        if not trace.get("devices") or not trace["busy_s"] > 0:
            print("benchmark: no operation ran on the device in the traced "
                  "window", file=sys.stderr)
            return 6
        log(f"trace: {os.path.getsize(path)} bytes; window {trace['window_s']:.4f} s, "
            f"busy {trace['busy_s']:.4f} s; programs {json.dumps(trace['programs'])}; "
            f"kernels {json.dumps(trace['kernels'])}; collective "
            f"{trace['collective_s']:.5f} s, exposed {trace['collective_exposed_s']:.5f} s")
        if trace["unknown_programs"]:
            log(f"trace: programs that no family matches: "
                f"{json.dumps(trace_reduce.top(trace['unknown_programs']))}")
        metrics, operands = per_layer_metrics(cell, out, device, trace)
        harness.check_shares(metrics, operands)
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": trace["idle_gaps"]}
    else:
        metrics = {}
        for entry in cell["end_to_end"]:
            if entry["name"] not in out["e2e"]:
                print(f"benchmark: the run has no {entry['name']}",
                      file=sys.stderr)
                return 7
            metrics[entry["name"]] = {"value": out["e2e"][entry["name"]],
                                      "unit": entry["unit"]}
    log(f"end to end: {json.dumps(out['e2e'])}")
    print(harness.result_line(
        correct=out["correct"], attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, breakdown=breakdown,
        compared=out["compared"]), flush=True)
    # what decided ``correct``, as the last lines of standard error too
    for name, value, limit in out["compared"]:
        print(f"check: {name} = {value:.6g} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
