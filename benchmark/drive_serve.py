"""The serving driver: the program's int8 bundle behind ``ServingServer``,
driven over TCP by ``ServingClient.generate_stream`` from client threads
of this one process (the client module imports JAX, so it cannot live in a
child; PERF.md). Closed loop: every client sends its next request when the
last reply ends. An open-loop mix (arrivals due at fixed times, time to
first token) has no driver yet; it lands with the cell that runs it on the
chip (PERF.md, open questions).
"""

from __future__ import annotations

import gc
import os
import tempfile
import threading
import time

import numpy as np

from benchmark import loadgen
from benchmark.harness import (
    Profile, annotate, log, memory_stat, peak_bytes, percentile)


class Record:
    """What the client saw of one request, on the client's clock."""

    __slots__ = ("request", "sent", "times", "sizes", "done", "error",
                 "sequence")

    def __init__(self, request: dict):
        self.request = request
        self.sent = None
        self.times, self.sizes = [], []
        self.done, self.error = None, None
        self.sequence = None


def boot_engine(family, w: dict, traffic: dict, serving: dict, seed: int,
                tmp: str):
    """Weights from the seed on the device, in the program's own model as
    the cell's family builds it, quantized by the program, saved and loaded
    as a bundle (what a serving host does), then the paged engine with the
    configuration file's slots and pages."""
    import jax

    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.utils.serialization import save_serving_bundle

    model = family.build_program_model(
        w, family.make_weights(w, seed), traffic)
    quantize_model(model, bits=int(serving["weight_bits"]))
    path = os.path.join(tmp, "bundle.dkt")
    save_serving_bundle(path, model)
    log(f"set-up: bundle {os.path.getsize(path)} bytes")
    del model
    gc.collect()
    kv_dtype = {"float32": None, "bfloat16": jax.numpy.bfloat16}[serving["kv_dtype"]]
    return ServingEngine.from_bundle(
        path, num_slots=int(serving["num_slots"]), paged=True,
        page_size=int(serving["page_size"]), num_pages=int(serving["num_pages"]),
        kv_dtype=kv_dtype, queue_capacity=int(serving["queue_capacity"]),
        watchdog_interval=300.0)


def release(engine) -> None:
    """Drop the stopped engine's device arrays (weights, KV pools) so that
    the reference's float32 weights fit: whatever else still points at the
    engine, its memory is free."""
    stepper = engine._stepper
    for name in ("_pools", "_caches", "_params", "_ctx"):
        if hasattr(stepper, name):
            setattr(stepper, name, None)
    engine.model.params = None


def stream_one(client, rec: Record) -> None:
    """One request over the wire; every chunk stamped as it arrives."""
    req = rec.request
    try:
        with annotate("bench/client_stream"):
            stream = client.generate_stream(req["prompt"], req["max_new_tokens"])
            rec.sent = time.perf_counter()
            for chunk in stream:
                rec.times.append(time.perf_counter())
                rec.sizes.append(len(chunk))
        rec.sequence = np.asarray(stream.sequence)
        rec.done = time.perf_counter()
    except Exception as e:  # a failed request is counted, never raised
        rec.error = repr(e)


def warm_drive(server, traffic: dict, w: dict) -> None:
    """Two requests over the wire before load starts, the mix's shortest
    prompt and one of several prefill chunks: they run the small programs
    that only a live request reaches (the context-row write, the stream)."""
    from distkeras_tpu.serving import ServingClient

    lens = [traffic["prompt_len"]["min"],
            min(traffic["prompt_len"]["max"], traffic["max_total"] - 4)]
    with ServingClient(server.host, server.port, retry=False) as client:
        for n in lens:
            rec = Record({"prompt": np.arange(n, dtype=np.int32) % w["vocab"],
                          "max_new_tokens": 4})
            stream_one(client, rec)
            if rec.error:
                raise RuntimeError(f"warm request failed: {rec.error}")


class Load:
    """Client threads over a list of requests, closed loop: every thread
    sends its next request when the last reply ends, until ``stop``."""

    def __init__(self, host, port, requests, traffic):
        from distkeras_tpu.serving import ServingClient

        self.requests = requests
        self.records: list[Record] = []
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._next = 0
        self.clients = [ServingClient(host, port, retry=False, timeout=600.0)
                        for _ in range(int(traffic["clients"]))]
        self.threads = [threading.Thread(target=self._client_loop, args=(c,),
                                         daemon=True) for c in self.clients]

    def start(self) -> float:
        self.t0 = time.perf_counter()
        for t in self.threads:
            t.start()
        return self.t0

    def _take(self) -> Record | None:
        with self._lock:
            if self._next >= len(self.requests):
                return None
            rec = Record(self.requests[self._next])
            self._next += 1
            self.records.append(rec)
            return rec

    def _client_loop(self, client) -> None:
        while not self.stop.is_set():
            rec = self._take()
            if rec is None:
                return
            stream_one(client, rec)

    def finish(self) -> None:
        self.stop.set()
        for c in self.clients:
            try:
                c.close()
            except OSError:
                pass
        for t in self.threads:
            t.join(timeout=30.0)
            if t.is_alive():
                raise RuntimeError("a load thread did not end")


def window_numbers(records, t_open, t_close) -> dict:
    """The end-to-end numbers of one window, from the clients' clocks."""
    tokens, gaps, chunk_sizes, cached = 0, [], [], []
    for r in records:
        prompt_len, before = len(r.request["prompt"]), 0
        for i, (t, n) in enumerate(zip(r.times, r.sizes)):
            if t_open <= t < t_close:
                tokens += n
                chunk_sizes.append(n)
                cached.append(prompt_len + before)
                if i > 0:
                    gaps.append(t - r.times[i - 1])
            before += n
    # every request that was being served inside the window (one still
    # waiting for a slot, as half the clients do by construction, has not
    # been attempted yet)
    mine = [r for r in records if (r.times or r.error) and r.sent < t_close
            and (r.done is None or r.done >= t_open)]
    return {"tokens": tokens, "gaps": gaps, "chunk_sizes": chunk_sizes,
            "cached": cached, "mine": mine}


def check_sample(records, t_close, seed: int, n: int) -> list:
    """Requests the window finished: the longest, and others drawn from
    the seed."""
    done = [r for r in records if r.done is not None and r.done < t_close
            and r.sequence is not None]
    if not done:
        return []
    done.sort(key=lambda r: -len(r.sequence))
    rng = np.random.default_rng(seed)
    rest = list(rng.permutation(len(done) - 1) + 1)[: n - 1]
    return [done[0]] + [done[i] for i in rest]


def run(cell: dict, args, t_start: float, watch, overrides: dict | None = None) -> dict:
    import jax

    from distkeras_tpu.serving import ServingServer

    config, traffic = cell["config"], cell["traffic"]
    serving = {**config["serving"], **(overrides or {})}
    family = cell["family"]
    w = family.widths(config)
    if traffic["loop"] != "closed":
        raise ValueError(f"loop {traffic['loop']!r}: only closed-loop mixes "
                         f"have a driver yet (PERF.md, open questions)")
    lead = float(traffic["lead_s"])

    with tempfile.TemporaryDirectory() as tmp:
        engine = boot_engine(family, w, traffic, serving, args.seed, tmp)
    engine._stepper.warmup()
    engine._stepper.warm_prefill_buckets()
    engine.compile_ledger.mark_warmed()
    server = ServingServer(engine, backlog=256).start()
    warm_drive(server, traffic, w)
    log(f"set-up: engine warm; compile {watch.snapshot()}")

    requests = loadgen.make_requests(
        traffic, args.seed, w["vocab"], int(traffic["max_requests"]))
    load = Load(server.host, server.port, requests, traffic)
    profile = Profile(bool(args.trace), traffic["trace"]["lead_s"],
                      traffic["trace"]["seconds"], cell["root"])

    # load runs for ``lead_s`` before the window, so that it opens in
    # steady state; those seconds also run the small programs that only
    # live traffic reaches, and count as set-up
    t_load = load.start()
    time.sleep(max(0.0, t_load + lead - time.perf_counter()))
    stats_open = engine.stats()
    compiles_before, compile_s = watch.compiles, watch.compile_seconds
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    with annotate("bench/window"):
        while True:
            now = time.perf_counter()
            if now - t_open >= args.seconds:
                break
            profile.poll(now - t_open)
            time.sleep(min(0.02, max(0.0, t_open + args.seconds - now)))
    t_close = time.perf_counter()
    profile.stop()
    stats_close = engine.stats()
    compiled_in_window = watch.compiles - compiles_before
    ledger = stats_close["compiles"]
    storms = ledger["storms"] - stats_open["compiles"]["storms"]
    minted = [r for r in ledger["recent"] if r["trigger"] != "warmup"]
    if minted:
        log(f"compile ledger: programs minted by live traffic: {minted}")

    # the clients stop with the window; a request has failed if it raised
    # an error, or had begun to stream and then got no token for ``stall_s``
    load.stop.set()
    peak = peak_bytes()
    numbers = window_numbers(load.records, t_open, t_close)
    mine = numbers["mine"]
    stall = float(traffic["stall_s"])
    failed = [r for r in mine if r.error is not None or (
        r.done is None and t_close - r.times[-1] > stall)]
    for r in failed[:5]:
        log(f"failed request: error={r.error} chunks={len(r.times)}")
    sample = check_sample(load.records, t_close, args.seed,
                          int(traffic["check"]["requests"]))
    server.shutdown(drain=False)
    load.finish()
    health = engine.health()
    log(f"engine: status {health['status']} restarts {health['restarts']} "
        f"watchdog_trips {health['watchdog_trips']} storms {storms}")
    release(engine)
    del engine, server, load
    gc.collect()
    log(f"engine released: bytes in use {memory_stat('bytes_in_use')}")

    window_s = t_close - t_open
    gaps = numbers["gaps"]
    e2e = {"serve_tokens_per_s": numbers["tokens"] / window_s}
    log(f"window: {window_s:.4f} s, {numbers['tokens']} tokens to clients, "
        f"{len(mine)} requests attempted, {len(failed)} failed; "
        f"tokens per chunk mean {np.mean(numbers['chunk_sizes'] or [0]):.3f}")
    if gaps:
        log(f"itl: n={len(gaps)} median_ms={1000 * percentile(gaps, 50):.3f} "
            f"p95_ms={1000 * percentile(gaps, 95):.3f}")

    steps = stats_close["steps"] - stats_open["steps"]
    occupancy = stats_close["occupancy_sum"] - stats_open["occupancy_sum"]
    counters = {
        "occupancy_sum_window": occupancy,
        "slot_steps_window": steps * int(serving["num_slots"]),
        "mean_batch": occupancy / steps if steps else None,
        "mean_cached": float(np.mean(numbers["cached"])) if numbers["cached"] else None,
        "scheduler_steps": steps, "compile_seconds_setup": compile_s,
    }

    # the reference, once the engine is gone: one full forward over each
    # sampled prompt with its served tokens
    t_ref = time.perf_counter()
    widest, n_tokens = 0.0, 0
    if sample:
        with jax.default_matmul_precision("highest"):
            weights = family.make_weights(w, args.seed)
            for r in sample:
                g, _ = family.token_gaps(
                    weights, w, r.sequence, len(r.request["prompt"]))
                widest, n_tokens = max(widest, float(g.max())), n_tokens + len(g)
            del weights
    limit = float(serving["check"]["gap_limit"])
    log(f"check: widest_logit_gap = {widest:.6g} (limit {limit}) over "
        f"{n_tokens} served tokens of {len(sample)} requests, longest "
        f"{len(sample[0].sequence) if sample else 0}; reference took "
        f"{time.perf_counter() - t_ref:.2f} s")
    lengths_ok = all(
        len(r.sequence) == len(r.request["prompt"]) + r.request["max_new_tokens"]
        for r in sample)
    correct = bool(sample) and widest <= limit and lengths_ok and \
        health["restarts"] == 0 and health["watchdog_trips"] == 0

    return {
        "correct": correct, "attempted": len(mine), "failed": len(failed),
        "setup_s": setup_s, "compiled_in_window": compiled_in_window + storms,
        "peak_bytes": peak, "profile": profile, "e2e": e2e,
        "counters": counters, "samples": {"itl_gaps_s": gaps},
        "compared": [("widest_logit_gap", widest, limit)],
    }
