"""Attribute the fused-path MFU delta one piece at a time (on-chip sweep).

The capture sweep's flash number changes three things at once (flash
attention + fused LayerNorm + pallas_adam), so a regression in any one of
them hides inside the bundle. This tool measures each attachment in
isolation against the dense/adam baseline, plus flash block-size variants,
and appends one JSON line per configuration to MFU_ATTRIB.jsonl.

Run from the repo root, on the chip (it fails without one):
    python tools/mfu_attrib.py [--quick]
(--quick drops the block-size variants.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import setup_backend  # noqa: E402
from bench_mfu import measure  # noqa: E402


def mode_configs(quick=False, long=False, scale=False, best=False,
                 retire=False, frontier=False):
    """The (label, measure-kwargs) list for each sweep mode — a plain
    function so tests can pin every mode's kwargs against ``measure``'s
    real signature without a TPU."""
    configs = [
        ("baseline dense+adam", {}),
        ("pallas_adam only", {"opt_name": "pallas_adam"}),
        ("fused_ln only", {"fused_ln": True}),
        # blocks pinned explicitly so a label always means one config,
        # independent of DEFAULT_BLOCK_Q/K retuning (512 since d7707a8)
        ("flash only bq512 bk512", {"attention": "flash", "fused_ln": False,
                                    "opt_name": "adam",
                                    "block_q": 512, "block_k": 512}),
        ("flash bundle", {"attention": "flash", "fused_ln": True,
                          "opt_name": "pallas_adam"}),
    ]
    if not quick:
        configs += [
            (f"flash only bq{bq} bk{bk}",
             {"attention": "flash", "fused_ln": False, "opt_name": "adam",
              "block_q": bq, "block_k": bk})
            for bq, bk in [(128, 128), (256, 256)]
        ]
    if long:
        shape = {"seq": 2048, "depth": 4, "batch": 8}
        configs = [
            ("dense seq2048", dict(shape)),
            ("flash seq2048", {"attention": "flash", **shape}),
        ]
    elif scale:
        wide = {"d_model": 1024, "depth": 4}
        configs = [
            ("dense d1024 L4", dict(wide)),
            ("flash d1024 L4", {"attention": "flash", **wide}),
            ("flash batch128", {"attention": "flash", "batch": 128}),
        ]
    elif best:
        bundle = {"attention": "flash", "opt_name": "pallas_adam"}
        configs = [
            ("best bundle d1024", {"d_model": 1024, "depth": 4, **bundle}),
            ("best bundle d1024 batch128",
             {"d_model": 1024, "depth": 4, "batch": 128, **bundle}),
            # seq-4096: dense materializes (B,H,4096,4096) scores in HBM;
            # flash streams 8 K/V blocks through VMEM per program
            ("dense seq4096", {"seq": 4096, "depth": 4, "batch": 4}),
            ("flash seq4096",
             {"attention": "flash", "seq": 4096, "depth": 4, "batch": 4}),
        ]
    elif retire:
        wide = {"d_model": 1024, "depth": 4}
        configs = [
            ("retire baseline d1024", dict(wide)),
            ("retire fused_ln d1024", {"fused_ln": True, **wide}),
            ("retire pallas_adam d1024", {"opt_name": "pallas_adam", **wide}),
        ]
    elif frontier:
        # Past the adjudicated best bundle (d1024 batch128 -> 0.525 MFU,
        # 2026-08-01): does MFU keep climbing with wider matmuls (d2048,
        # head_dim 256), more tokens per program (seq 1024 at d1024), or
        # a still-bigger batch? Exploratory rows — whatever wins becomes
        # the next --best once it has a second confirming window.
        bundle = {"attention": "flash", "opt_name": "pallas_adam"}
        configs = [
            ("frontier d2048 L2", {"d_model": 2048, "depth": 2,
                                   "batch": 32, **bundle}),
            ("frontier d1024 seq1024", {"d_model": 1024, "depth": 4,
                                        "seq": 1024, "batch": 32, **bundle}),
            # batch-256 WITHOUT remat is a known wall — f32 jvp temps OOM
            # HBM (16.2G vs 15.75G; two committed error rows,
            # 2026-08-01) — so the sweep no longer re-pays that compile:
            # only the remat variant runs. Per-block jax.checkpoint
            # trades a forward recompute for O(1)-in-depth activation
            # memory; measured 0.4248 MFU — the shape fits, ~10 points
            # below batch-128, adjudicating remat as the capability
            # lever rather than the throughput config.
            ("frontier d1024 batch256 remat",
             {"d_model": 1024, "depth": 4, "batch": 256, "remat": True,
              **bundle}),
        ]
    return configs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="default sweep only: drop the block-size variants "
                    "(no effect with --long/--scale/--best/--retire/"
                    "--frontier)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument(
        "--long", action="store_true",
        help="long-sequence A/B instead: seq 2048, depth 4, batch 8 — "
        "where dense attention's (B,H,T,T) HBM scores stop being free",
    )
    mode.add_argument(
        "--scale", action="store_true",
        help="MXU scaling rows instead: d_model 1024 and batch 128 — "
        "how MFU moves when the matmuls widen / batch fills the array",
    )
    mode.add_argument(
        "--best", action="store_true",
        help="the ADJUDICATED winning-bundle rows (r5 on-chip: flash "
        "wins everywhere, pallas_adam wins at d1024, fused_ln retired): "
        "flash+pallas_adam at d1024 batch 64/128, and a seq-4096 A/B "
        "(8 K/V blocks/program — twice the multi-block depth of "
        "--long); for EXPLORATORY rows past this bundle see --frontier",
    )
    mode.add_argument(
        "--frontier", action="store_true",
        help="exploratory ceiling rows past the adjudicated best bundle: "
        "d2048 (head_dim 256), seq-1024 at d1024, and batch-256 with "
        "per-block remat (without remat batch-256 OOMs HBM — committed "
        "error rows) — hunting the next --best config",
    )
    mode.add_argument(
        "--retire", action="store_true",
        help="retire-or-win rows for the losing kernels (VERDICT r3 task "
        "7): fused_layernorm and pallas_adam re-measured at d_model 1024 "
        "(wider rows = more memory-bound LN; 4x the optimizer tree) "
        "against the same-shape baseline — a positive row keeps the "
        "kernel, a negative one retires it in PERF.md",
    )
    args = ap.parse_args()

    platform = setup_backend()  # the chip, or an error
    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(platform=platform)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})", flush=True)

    configs = mode_configs(quick=args.quick, long=args.long,
                           scale=args.scale, best=args.best,
                           retire=args.retire, frontier=args.frontier)
    mode_name = next(
        (m for m in ("long", "scale", "best", "retire", "frontier")
         if getattr(args, m)),
        "quick" if args.quick else "default",
    )

    # Every sweep stamps its start and end on stdout, so the rows it
    # appends to MFU_ATTRIB.jsonl can be tied to a dated invocation.
    def stamp(line):
        print(time.strftime("%Y-%m-%dT%H:%M:%SZ ", time.gmtime()) + line,
              flush=True)

    stamp(
        f"mfu_attrib --{mode_name} start device={dev.device_kind} "
        f"pid={os.getpid()} rows={[label for label, _ in configs]}"
    )
    with open("MFU_ATTRIB.jsonl", "a") as f:
        for label, kw in configs:
            try:
                rec = measure(platform, **kw)
            except Exception as e:  # one row's failure: keep the rest
                rec = {"label": label, "error": f"{type(e).__name__}: {e}"}
            else:
                rec["label"] = label
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            stamp(
                f"mfu_attrib --{mode_name} row {label!r}: "
                + (f"value={rec.get('value')}" if "error" not in rec
                   else "ERROR " + rec["error"].split(chr(10))[0][:120])
            )


if __name__ == "__main__":
    main()
