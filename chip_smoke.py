"""Chip smoke: the trainer and the server, once, on the chip, at full width.

    python chip_smoke.py            # one chip: trainer phase, server phase
    python chip_smoke.py --chips 4  # four chips: the two cross-chip paths

One process, no child. The device is asked for first; anything but a TPU
ends the run non-zero before a model is built. No phase's failure is
caught: an exception ends the run. ``ok`` is the conjunction of the checks
the phases return. The last line of stdout is the contract's JSON object;
sizes, memory, compile seconds, cache and timings go on earlier lines.

The model is the repo's ``zoo.transformer_lm`` at the widest shape its
block has run: d_model 2048, 8 heads (head dim 256), seq 2048, vocab
32768. Depth and batch are chosen from the device's memory and printed.
The phases are plain functions of their sizes, so
``tests/test_chip_smoke.py`` calls them tiny on the CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

WIDTH = dict(vocab_size=32768, seq_len=2048, d_model=2048, num_heads=8)
# bf16 rounding of a mean loss near ln(vocab) ~ 10: the sync trainer's
# all-reduce changes the order of the gradient sum, nothing else
LOSS_TOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ device


def require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        raise SystemExit(2)
    return device


def memory_stat(key: str) -> list[int]:
    """One ``memory_stats()`` entry for every device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def bytes_in_use() -> list[int]:
    return memory_stat("bytes_in_use")


def peak_bytes() -> list[int]:
    return memory_stat("peak_bytes_in_use")


def live_bytes() -> list[int]:
    """Bytes of live JAX arrays on each device, from their shardings: what
    the program holds there, on any backend (the CPU reports no
    memory_stats)."""
    import gc

    import jax

    gc.collect()
    out = dict.fromkeys(jax.devices(), 0)
    for arr in jax.live_arrays():
        shard = arr.sharding.shard_shape(arr.shape)
        nbytes = int(np.prod(shard)) * arr.dtype.itemsize
        for dev in arr.sharding.device_set:
            out[dev] += nbytes
    return list(out.values())


def _gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


class CompileWatch:
    """Counts what JAX's own monitoring reports while the phases run:
    backend compiles and their seconds, persistent-cache hits and misses."""

    def __init__(self):
        from jax._src import monitoring

        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.compile_seconds += secs

    def _on_event(self, event, **_kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 2),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


# ------------------------------------------------------------------- sizes


def param_count(width: dict, depth: int) -> int:
    v, t, d = width["vocab_size"], width["seq_len"], width["d_model"]
    block = 12 * d * d + 13 * d  # qkv+proj, 4x MLP, biases, two LayerNorms
    return v * d + t * d + depth * block + 2 * d + d * v + v


def choose_sizes(bytes_limit: int, width: dict = WIDTH, kernels: bool = True,
                 min_batch: int = 1) -> dict:
    """Depth and batches from the device's memory. While it trains the
    device holds 20 bytes a parameter: the model's own f32 weights, the
    trainer's copy of them, two adam moments and the gradients. Depth is
    the largest (at least 4) that keeps 16 of those under 45% of the
    device and lets ``min_batch`` sequences fit; batch is the largest
    power of two (at most 8) whose activations fit what is left of 85%.
    The per-sequence constants are what the TPU compiler's memory
    analysis of the scanned window gave at these widths (PERF.md, PR 22):
    6 bytes a logit, 8 saved (T, d) bf16 activations a block and, without
    the flash kernel (``kernels=False``), 3 bytes a score. The server
    gets one slot for each full-length f32 KV sequence that fits a
    quarter of the device, from 2 to 8."""
    v, t, d = width["vocab_size"], width["seq_len"], width["d_model"]
    scores = 0 if kernels else width["num_heads"] * t * t * 3

    def fit(depth: int) -> int:
        per_seq = t * v * 6 + depth * (8 * t * d * 2 + scores)
        state = 20 * param_count(width, depth)
        return int((0.85 * bytes_limit - state) // per_seq)

    depth = 4
    while (16 * param_count(width, depth + 1) <= 0.45 * bytes_limit
           and fit(depth + 1) >= min_batch):
        depth += 1
    batch = min(8, 1 << (max(1, fit(depth)).bit_length() - 1))
    kv_per_slot = depth * 2 * t * d * 4  # f32 K and V, every block
    slots = int(max(2, min(8, (0.25 * bytes_limit) // kv_per_slot)))
    return {
        **width, "depth": depth, "batch": batch, "window": 4,
        "windows": 3, "slots": slots, "new_tokens": 16,
        "prompt_lens": [5, 37, 200, 700, 1500],
    }


def build_lm(sizes: dict, seed: int):
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(
        vocab_size=sizes["vocab_size"], seq_len=sizes["seq_len"],
        d_model=sizes["d_model"], num_heads=sizes["num_heads"],
        depth=sizes["depth"], seed=seed,
    )


ALPHABET = 256  # tokens the training data uses, of the model's whole vocab


def successor_data(sizes: dict, n: int, seed: int):
    """Token t+1 = token t + 1 (mod ALPHABET), from seeded starts: the toy
    successor language of ``examples/serve_lm.py``, over a small alphabet
    inside the model's full vocabulary so that a dozen steps can learn it
    (which tokens occur at all, then which follows which)."""
    from distkeras_tpu.data.dataset import Dataset

    rng = np.random.default_rng(seed)
    period = min(ALPHABET, sizes["vocab_size"])
    starts = rng.integers(0, period, n)
    xs = ((starts[:, None] + np.arange(sizes["seq_len"])[None, :])
          % period).astype(np.int32)
    return Dataset({"features": xs, "label": xs})


def attach_kernels(model, sizes: dict) -> dict:
    """Attach the flash and fused-LayerNorm kernels; report what runs."""
    from distkeras_tpu.ops.flash_attention import (
        attach_flash_attention,
        effective_path,
    )
    from distkeras_tpu.ops.fused_layernorm import attach_fused_layernorm
    from distkeras_tpu.ops.kernel_mode import pallas_interpret

    head_dim = sizes["d_model"] // sizes["num_heads"]
    return {
        "flash_attached": attach_flash_attention(model),
        "fused_ln_attached": attach_fused_layernorm(model),
        "effective_path": effective_path(sizes["seq_len"], head_dim)[0],
        "head_dim": head_dim,
        "interpret": pallas_interpret(),
    }


def _losses(trainer) -> list[float]:
    return [r["loss"] for r in trainer.get_history()]


# ------------------------------------------------------------------ phases


def trainer_phase(sizes: dict, seed: int = 0):
    """``SingleTrainer`` with adam, bf16 compute and both kernels for
    ``windows`` windows of ``window`` steps. Returns (trained model,
    report); ``report["ok"]``: every loss finite, the last window's mean
    below the first's, and attention on the flash path."""
    from distkeras_tpu import SingleTrainer

    model = build_lm(sizes, seed)
    kernels = attach_kernels(model, sizes)
    steps = sizes["windows"] * sizes["window"]
    data = successor_data(sizes, steps * sizes["batch"], seed)
    trainer = SingleTrainer(
        model, "adam", loss="next_token_crossentropy", learning_rate=3e-4,
        metrics=(), batch_size=sizes["batch"], num_epoch=1,
        window=sizes["window"], seed=seed, compute_dtype="bfloat16",
    )
    t0 = time.perf_counter()
    trained = trainer.train(data)
    seconds = time.perf_counter() - t0
    losses = _losses(trainer)
    # a step's loss is one small batch's: the start and the end are the
    # means of the first and the last window
    w = sizes["window"]
    first, last = float(np.mean(losses[:w])), float(np.mean(losses[-w:]))
    report = {
        **kernels,
        "steps": len(losses),
        "losses": [round(x, 4) for x in losses],
        "loss_first_window": first,
        "loss_last_window": last,
        "seconds": round(seconds, 2),
        "window_seconds": [round(dt, 3) for _, dt in
                           trainer.history.get_timings()],
    }
    report["ok"] = bool(
        len(losses) == steps
        and np.all(np.isfinite(losses))
        and last < first
        and kernels["effective_path"] == "flash"
        and kernels["flash_attached"] == sizes["depth"]
    )
    return trained, report


def _prompts(sizes: dict, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed + 1)
    return [
        rng.integers(0, sizes["vocab_size"], n).astype(np.int32)
        for n in sizes["prompt_lens"]
    ]


def export_bundle(model, path: str) -> int:
    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.utils.serialization import save_serving_bundle

    # the attention and LayerNorm hooks are process-local and not
    # serialized: the bundle holds the plain architecture and int8 weights
    save_serving_bundle(path, quantize_model(model.copy()))
    return os.path.getsize(path)


def _reference(bundle: str, prompts, steps: int) -> list[np.ndarray]:
    """Solo decode of the same bundle: the repo's identity reference."""
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.utils.serialization import load_serving_bundle

    ref = CachedSequenceGenerator(load_serving_bundle(bundle))
    rows = ref.generate(list(prompts), steps=steps)
    return [np.asarray(r)[: p.size + steps] for r, p in zip(rows, prompts)]


def _engine(bundle: str, sizes: dict, **kw):
    """A paged engine booted from the bundle on disk. The watchdog waits
    out a cold full-width compile (tens of seconds each on the chip)."""
    from distkeras_tpu.serving import ServingEngine

    return ServingEngine.from_bundle(
        bundle, num_slots=sizes["slots"], paged=True, queue_capacity=32,
        watchdog_interval=300.0, **kw,
    )


def server_phase(model, sizes: dict, seed: int = 0):
    """Export ``model`` as a serving bundle, boot a paged
    ``ServingEngine`` from it behind a ``ServingServer``, and drive it
    with a ``ServingClient`` over TCP: one ``generate`` per prompt length
    and one ``generate_stream``. ``report["ok"]``: every reply
    token-identical to ``CachedSequenceGenerator`` on the same bundle,
    health clean before and a drained stop after."""
    from distkeras_tpu.serving import ServingClient, ServingServer

    prompts = _prompts(sizes, seed)
    steps = sizes["new_tokens"]
    with tempfile.TemporaryDirectory() as tmp:
        bundle = os.path.join(tmp, "lm_int8.dkt")
        bundle_bytes = export_bundle(model, bundle)
        engine = _engine(bundle, sizes)
        server = ServingServer(engine).start()
        t0 = time.perf_counter()
        with ServingClient(server.host, server.port) as cli:
            replies = [cli.generate(p, steps) for p in prompts]
            stream = cli.generate_stream(prompts[1], steps)
            chunks = [np.asarray(c) for c in stream]
            streamed = np.asarray(stream.sequence)
            health = cli.health()
            stats = cli.stats()
        seconds = time.perf_counter() - t0
        mem = bytes_in_use()
        server.shutdown(drain=True)
        stopped = engine.health()["status"]
        del engine, server
        want = _reference(bundle, prompts, steps)
    identical = [bool(np.array_equal(r, w)) for r, w in zip(replies, want)]
    stream_ok = bool(
        np.array_equal(streamed, want[1])
        and np.array_equal(np.concatenate(chunks), want[1][prompts[1].size:])
    )
    report = {
        "bundle_bytes": bundle_bytes,
        "requests": len(replies) + 1,
        "identical": identical,
        "stream_identical": stream_ok,
        "stream_chunks": len(chunks),
        "health": {k: health.get(k) for k in (
            "status", "restarts", "watchdog_trips", "quarantined_slots",
            "kv_page_util")},
        "completed": stats["completed"],
        "stopped_status": stopped,
        "bytes_in_use_serving": mem,
        "seconds": round(seconds, 2),
    }
    report["ok"] = bool(
        all(identical) and stream_ok
        and all(r.size == p.size + steps for r, p in zip(replies, prompts))
        and health["status"] == "serving" and health["restarts"] == 0
        and health["watchdog_trips"] == 0
        and health["quarantined_slots"] == 0
        and stats["completed"] == len(replies) + 1
        and stopped == "draining"
    )
    return report


def sync_trainer_phase(sizes: dict, num_workers: int, seed: int = 0):
    """``SynchronousDistributedTrainer`` over ``num_workers`` devices
    against ``SingleTrainer`` on the same seed and global batch: one
    window, per-step loss within ``LOSS_TOL``. ``spread``: every worker's
    device peaked above the replicated training state (None where the
    backend reports no memory)."""
    from distkeras_tpu import SingleTrainer, SynchronousDistributedTrainer

    per_worker = max(1, sizes["batch"] // num_workers)
    global_batch = per_worker * num_workers
    data = successor_data(sizes, sizes["window"] * global_batch, seed)
    common = dict(
        loss="next_token_crossentropy", learning_rate=3e-4, metrics=(),
        num_epoch=1, window=sizes["window"], seed=seed,
        compute_dtype="bfloat16",
    )

    def run(make):
        # no Pallas hooks on either side: the sync trainer's step is one
        # GSPMD program, and Mosaic kernels cannot be partitioned
        # automatically (the TPU compiler refuses them outside shard_map)
        trainer = make(build_lm(sizes, seed))
        t0 = time.perf_counter()
        trainer.train(data)
        return _losses(trainer), round(time.perf_counter() - t0, 2)

    sync_l, sync_s = run(
        lambda m: SynchronousDistributedTrainer(
            m, "adam", batch_size=per_worker, num_workers=num_workers,
            **common))
    peaks = peak_bytes()  # before the single run piles onto device 0
    single_l, single_s = run(
        lambda m: SingleTrainer(m, "adam", batch_size=global_batch,
                                **common))
    diffs = [abs(a - b) for a, b in zip(sync_l, single_l)]
    # replicated f32 weights and two adam moments, on every worker's device
    state = 12 * param_count(sizes, sizes["depth"])
    spread = (
        all(p >= state for p in peaks[:num_workers]) if any(peaks) else None
    )
    report = {
        "attention": "dense (XLA)", "global_batch": global_batch,
        "loss_sync": sync_l, "loss_single": single_l,
        "max_abs_diff": max(diffs), "tolerance": LOSS_TOL,
        "peak_bytes_after_sync": peaks, "replicated_state_bytes": state,
        "spread": spread,
        "seconds_sync": sync_s, "seconds_single": single_s,
    }
    report["ok"] = bool(
        len(sync_l) == len(single_l) == sizes["window"]
        and np.all(np.isfinite(sync_l)) and max(diffs) <= LOSS_TOL
        and spread is not False
    )
    return report


def tp_server_phase(sizes: dict, tp: int, seed: int = 0):
    """``ServingEngine(mesh="tp:N")`` against the unsharded engine on
    device 0, both from one bundle: token-identical greedy replies.
    ``spread``: the mesh's devices hold live arrays in equal measure
    (within a tenth), and the first holds less than the unsharded engine
    put there (embeddings and LayerNorms replicate; matmul weights and
    the KV pool shard)."""
    prompts = _prompts(sizes, seed)
    steps = sizes["new_tokens"]
    with tempfile.TemporaryDirectory() as tmp:
        bundle = os.path.join(tmp, "lm_int8.dkt")
        export_bundle(build_lm(sizes, seed), bundle)

        def serve(**kw):
            before = live_bytes()
            engine = _engine(bundle, sizes, **kw).start()
            out = [np.asarray(engine.generate(p, steps)) for p in prompts]
            held = {
                # what this engine added, not what the process held before
                "live_bytes": [a - b for a, b in zip(live_bytes(), before)],
                "bytes_in_use": bytes_in_use(),
            }
            health = engine.health()
            engine.stop(drain=True)
            return out, held, health

        sharded, held_tp, health = serve(mesh=f"tp:{tp}")
        solo, held_solo, _ = serve()
    identical = [bool(np.array_equal(a, b)) for a, b in zip(sharded, solo)]
    live_tp, live_solo = held_tp["live_bytes"], held_solo["live_bytes"]
    spread = bool(
        min(live_tp[:tp]) > 0
        and max(live_tp[:tp]) <= 1.1 * min(live_tp[:tp])
        and live_tp[0] < live_solo[0]
    )
    report = {
        "identical": identical,
        "mesh": health.get("mesh"),
        "kv_shard_bytes": health.get("kv_shard_bytes"),
        "tp": held_tp, "solo": held_solo, "spread": spread,
    }
    report["ok"] = bool(all(identical) and spread)
    return report


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    t_start = time.perf_counter()
    device = require_tpu()  # before anything is built
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{device['count']} devices", file=sys.stderr)
        return 2
    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(platform=device["platform"])
    watch = CompileWatch()
    limit = int(jax.devices()[0].memory_stats()["bytes_limit"])
    sizes = (
        choose_sizes(limit) if args.chips == 1
        else choose_sizes(limit, kernels=False, min_batch=args.chips)
    )
    log(f"device: {json.dumps(device)} bytes_limit={_gib(limit)}")
    log(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    log(f"sizes: {json.dumps(sizes)} params="
        f"{param_count(WIDTH, sizes['depth']) / 1e6:.0f}M")

    checks = {}
    if args.chips == 1:
        trained, rep = trainer_phase(sizes, args.seed)
        log(f"trainer: {json.dumps(rep)}")
        log(f"trainer: bytes_in_use={bytes_in_use()} {watch.snapshot()}")
        checks["trainer"] = rep["ok"]
        rep = server_phase(trained, sizes, args.seed)
        del trained
        log(f"server: {json.dumps(rep)}")
        checks["server"] = rep["ok"]
    else:
        rep = sync_trainer_phase(sizes, args.chips, args.seed)
        log(f"sync_trainer: {json.dumps(rep)}")
        checks["sync_trainer"] = rep["ok"] and rep["spread"] is True
        rep = tp_server_phase(sizes, args.chips, args.seed)
        log(f"tp_server: {json.dumps(rep)}")
        checks["tp_server"] = rep["ok"]

    peak = peak_bytes()
    log(f"peak_bytes_in_use: {peak} ({', '.join(_gib(p) for p in peak)})")
    log(f"compile: {json.dumps(watch.snapshot())} cache_dir={cache_dir}")
    log(f"checks: {json.dumps(checks)} "
        f"wall_seconds={time.perf_counter() - t_start:.1f}")
    ok = bool(checks) and all(checks.values())
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
