"""MXU utilization benchmark: transformer-LM training step, bf16, resident data.

The north-star MNIST CNN (bench.py) is host-history-faithful but tiny — its
FLOPs can't fill a systolic array, so its MFU says nothing about the
framework's ceiling. This harness measures the framework on an MXU-shaped
workload: a transformer classifier (d_model 512, depth 8, seq 512) trained
through the same ``WorkerCore.indexed_window`` device-resident path, bf16
compute, window-scanned. MFU and tflops_per_sec come from the ANALYTIC
model-flops count (24*T*d^2 + 4*T^2*d per layer forward, x3 for the train
step) — the conventional definition, and the only one comparable across
attention paths, since XLA's cost model cannot see inside Pallas custom
calls; the cost-model number is reported alongside as
``xla_cost_tflops_per_sec`` for the dense-path cross-check. Peak is the
device generation's published bf16 number (bench.py's table).

``measure()`` is the reusable harness (``tools/mfu_attrib.py`` sweeps it to
attribute the fused-path pieces one at a time); ``main()`` is the capture
entry that writes BENCH_MFU.json and prints one JSON line:
    {"metric": "transformer_train_mfu", "value": ..., "unit": "fraction",
     "attention": "flash"|"dense", "samples_per_sec": ...,
     "tflops_per_sec": ..., "xla_cost_tflops_per_sec": ..., ...}

Usage: python bench_mfu.py [--cpu] [--attention auto|flash|dense]
(CPU fallback scales shapes down and reports tflops with mfu=null — no
published CPU peak; auto runs flash only on TPU.)
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from bench import _flops_per_call, _peak_flops, setup_backend, sync_fetch


def measure(
    platform,
    attention="dense",
    fused_ln=None,
    opt_name=None,
    block_q=None,
    block_k=None,
    seq=None,
    d_model=None,
    depth=None,
    batch=None,
    remat=False,
):
    """One MFU measurement on the current backend; returns the record dict.

    ``fused_ln``/``opt_name`` default to the measured-best configuration
    (MFU_ATTRIB.jsonl on v5e: XLA's fused LayerNorm and optax adam beat
    the hand kernels at this size — only the attention kernel pays, once
    its blocks are MXU-sized). Pass them explicitly to measure the other
    pieces. Shape overrides exist for scaling studies; the defaults are
    the round-comparable config.
    """
    import jax

    from distkeras_tpu.models.zoo import transformer_classifier
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.workers import WorkerCore

    on_cpu = platform == "cpu"
    dseq, dd, ddepth, heads = (64, 128, 2, 4) if on_cpu else (512, 512, 8, 8)
    seq = dseq if seq is None else seq
    d_model = dd if d_model is None else d_model
    depth = ddepth if depth is None else depth
    batch = (8 if on_cpu else 64) if batch is None else batch
    window = 2 if on_cpu else 8
    vocab, n_classes = 8192, 16
    warmup, timed = (1, 2) if on_cpu else (2, 6)

    dev = jax.devices()[0]

    model = transformer_classifier(
        vocab_size=vocab,
        seq_len=seq,
        d_model=d_model,
        num_heads=heads,
        depth=depth,
        num_classes=n_classes,
        seed=0,
        # jax.checkpoint per block: activation temps stay O(1) in depth
        # at the cost of a forward recompute in the backward — the lever
        # for batch/seq sizes whose f32 jvp temps outgrow HBM (the
        # batch-256 OOM row, 2026-08-01)
        remat=remat,
    )
    if fused_ln is None:
        fused_ln = False
    if opt_name is None:
        opt_name = "adam"
    attached_ln = 0
    if attention == "flash":
        from distkeras_tpu.ops.flash_attention import (
            DEFAULT_BLOCK_K,
            DEFAULT_BLOCK_Q,
            attach_flash_attention,
        )

        # None -> the module's tuned defaults (512 as of MFU_ATTRIB.jsonl);
        # a measure() default here would silently shadow future retuning
        block_q = DEFAULT_BLOCK_Q if block_q is None else block_q
        block_k = DEFAULT_BLOCK_K if block_k is None else block_k
        attach_flash_attention(model, block_q=block_q, block_k=block_k)
    if fused_ln:
        from distkeras_tpu.ops.fused_layernorm import attach_fused_layernorm

        attached_ln = attach_fused_layernorm(model)

    def make_core(name):
        return WorkerCore(
            model,
            get_optimizer(name, 1e-3),
            "categorical_crossentropy",
            compute_dtype="bfloat16",
        )

    core = make_core(opt_name)

    n_data = batch * 8
    rng = np.random.default_rng(0)
    data_x = jax.device_put(rng.integers(0, vocab, (n_data, seq)).astype(np.int32))
    data_y = jax.device_put(
        np.eye(n_classes, dtype=np.float32)[rng.integers(0, n_classes, n_data)]
    )

    def fresh_idx():
        return rng.integers(0, n_data, (window, batch)).astype(np.int32)

    params = model.params
    state = model.state
    opt_state = core.init_opt_state(params)
    key = jax.random.PRNGKey(0)

    try:
        compiled = core.indexed_window.lower(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        ).compile()
    except Exception as e:
        if opt_name == "adam":
            raise
        # a fused-optimizer lowering failure must not cost the window the
        # attention A/B — fall back to the generic adam and keep measuring
        print(f"{opt_name} failed to compile ({type(e).__name__}); "
              "falling back to adam", flush=True)
        opt_name = "adam"
        core = make_core(opt_name)
        opt_state = core.init_opt_state(params)
        compiled = core.indexed_window.lower(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        ).compile()
    xla_flops_per_window = _flops_per_call(compiled)
    # MFU uses the ANALYTIC model-flops count (the conventional definition,
    # and the only one that stays comparable across attention paths: XLA's
    # cost model cannot see inside Pallas custom calls, so the flash path
    # would otherwise report an understated MFU). Per layer forward:
    # qkv+proj 8*T*d^2 + MLP 16*T*d^2 + attention 4*T^2*d; training step
    # ~3x forward (backward ~2x).
    per_layer_fwd = 24 * seq * d_model**2 + 4 * seq**2 * d_model
    analytic_flops_per_window = 3 * depth * per_layer_fwd * batch * window

    for _ in range(warmup):
        params, state, opt_state, key, _m = core.indexed_window(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        )
    # host-fetch barrier: see bench.sync_fetch
    sync_fetch(_m["loss"])

    t0 = time.perf_counter()
    for _ in range(timed):
        params, state, opt_state, key, _m = core.indexed_window(
            params, state, opt_state, key, data_x, data_y, fresh_idx()
        )
    final_loss = sync_fetch(_m["loss"])
    dt = time.perf_counter() - t0

    sps = timed * window * batch / dt
    fps = analytic_flops_per_window * timed / dt
    record = {
        "metric": "transformer_train_mfu",
        "value": None,
        "unit": "fraction",
        "platform": platform,
        "device_kind": dev.device_kind,
        "model": f"transformer d{d_model} L{depth} seq{seq} bf16",
        "attention": attention,
        "optimizer": opt_name,
        "fused_layernorm_layers": attached_ln,
        "batch": batch,
        # finite => real compute happened; non-finite goes out as a string
        # so the artifact stays strictly-valid JSON
        "final_loss": (
            round(final_loss, 4) if math.isfinite(final_loss)
            else repr(final_loss)
        ),
        "samples_per_sec": round(sps, 1),
        "tflops_per_sec": round(fps / 1e12, 2),
        "xla_cost_tflops_per_sec": (
            round(xla_flops_per_window * timed / dt / 1e12, 2)
            if xla_flops_per_window is not None
            else None
        ),
    }
    if remat:
        record["remat"] = True  # absent field == no checkpointing
    if attention == "flash":
        from distkeras_tpu.ops.flash_attention import (
            effective_bwd_blocks,
            effective_path,
        )

        # always recorded: an artifact must say which kernel config it
        # measured (blocks clamp to seq for short T), and which path the
        # dispatch ACTUALLY ran — flash silently falls back to blockwise
        # (VMEM budget) or dense (non-tiling T) at some shapes, and an
        # A/B row must not attribute a fallback's numbers to the kernel
        record["block_q"], record["block_k"] = block_q, block_k
        # the dispatch may shrink blocks to tile T (ADVICE r3 #1): record
        # the blocks that actually RAN, not just the requested ones
        eff_path, eff_bq, eff_bk = effective_path(
            seq, d_model // heads, block_q, block_k
        )
        record["effective_attention"] = eff_path
        record["effective_block_q"] = eff_bq
        record["effective_block_k"] = eff_bk
        # the backward re-clamps blocks under its own VMEM model (the
        # seq-4096 dkv kernel OOMed at the forward's 512s, v5e
        # 2026-08-01); record what the bwd actually runs so the artifact
        # keeps the single-source-of-dispatch promise for BOTH passes
        bwd = effective_bwd_blocks(seq, d_model // heads, block_q, block_k)
        if bwd is not None:
            record["effective_bwd_block_q"], record["effective_bwd_block_k"] = bwd
    peak = _peak_flops(dev)
    if peak is not None:
        record["value"] = round(fps / peak, 4)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument(
        "--attention",
        choices=["auto", "flash", "dense", "best"],
        default="auto",
        help="flash = fused Pallas kernels (ops/flash_attention); dense = "
        "XLA dense attention (the baseline the kernel is judged against). "
        "auto picks flash on TPU and dense elsewhere — off-TPU the Pallas "
        "interpreter would measure interpreter overhead, not the framework. "
        "best measures BOTH and records the winner as the headline "
        "artifact (VERDICT r3 weak #1: the committed BENCH_MFU.json must "
        "never document the losing bundle while the README cites the win)",
    )
    args = ap.parse_args()

    platform = setup_backend(cpu=args.cpu)

    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(platform=platform)
    if args.attention == "auto":
        args.attention = "dense" if platform == "cpu" else "flash"
    if args.attention == "best" and platform == "cpu":
        args.attention = "dense"  # flash off-TPU measures the interpreter

    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})", flush=True)
    def write_artifact(rec):
        with open("BENCH_MFU.json", "w") as f:
            json.dump(rec, f, indent=2)

    if args.attention == "best":
        # winner by MFU (falls back to tflops when no published peak)
        def score(r):
            # .get: never KeyError mid-sweep on a record shape drift — an
            # unknown TPU generation must still finish the A/B (ADVICE r4 #1)
            v = r.get("value")
            return v if v is not None else r["tflops_per_sec"]

        record = None
        for attn in ("dense", "flash"):
            rec = measure(platform, attention=attn)
            print(json.dumps(rec), flush=True)
            if record is None or score(rec) > score(record):
                loser, record = record, rec
            else:
                loser = rec
            if loser is not None:
                # the A/B loser rides along: the artifact documents the margin
                record["ab_loser"] = {
                    k: loser.get(k) for k in
                    ("attention", "value", "tflops_per_sec", "samples_per_sec")
                }
            # artifact written after EVERY measure (a failure mid-sweep
            # must not cost the finished dense row its place on disk)
            write_artifact(record)
    else:
        record = measure(platform, attention=args.attention)
        write_artifact(record)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
