"""What every run shares: the device or an error, compile counting, the
compile cache, memory readings, the profiler window and the result line.
``require_tpu``, ``CompileWatch`` and the memory readers are copies of
``chip_smoke.py``'s (PR 22), kept here so that the program's may change
without moving the yardstick."""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time


def log(msg: str) -> None:
    print(msg, flush=True)


class NoChip(SystemExit):
    """JAX found no TPU, or not as many chips as the cell asks for."""


def require_tpu(chips: int, exact: bool = True) -> dict:
    """The device as JAX reports it. Anything but a TPU with exactly the
    cell's number of chips ends the run non-zero before a model is built
    (``exact=False``: at least that many, for what runs the reference
    alone)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < chips or (
            exact and device["count"] != chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{device}", file=sys.stderr, flush=True)
        raise NoChip(2)
    return device


def memory_stat(key: str) -> list[int]:
    import jax

    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest chip."""
    return max(memory_stat("peak_bytes_in_use"))


class CompileWatch:
    """Counts what JAX's own monitoring reports: backend compiles and their
    seconds, persistent-cache hits and misses."""

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
        return {"compiles": self.compiles,
                "compile_seconds": self.compile_seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def enable_cache(platform: str) -> str | None:
    """The program's own placement (``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``: a fixed path inside the checkout), and
    every program cached however short its compile, so that the second run
    of a cell in a checkout compiles nothing."""
    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache(platform=platform)
    if path is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Profile:
    """A few seconds of the profiler inside the measured window, started
    and stopped by the driver's loop at the offsets the traffic file gives.
    Only host annotations and device events: no Python tracer."""

    def __init__(self, enabled: bool, lead_s: float, seconds: float, root: str):
        self.enabled = enabled
        self.lead_s, self.seconds = lead_s, seconds
        self.dir = os.path.join(root, ".bench_trace")
        self.state = "idle" if enabled else "done"
        self.t_start = None

    def poll(self, since_open: float) -> None:
        """Called from the driver's loop with the seconds since the window
        opened; starts and stops the trace when its offsets pass."""
        import jax

        if self.state == "idle" and since_open >= self.lead_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" and (
                time.perf_counter() - self.t_start >= self.seconds):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"

    def path(self) -> str | None:
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if found else None


def annotate(name: str):
    """A span of the benchmark's own in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def check_shares(metrics: dict, operands: dict) -> None:
    """A share of a peak or of a roofline above 105% is a fault of the
    count, not a result: print what it was made from and end the run."""
    for name, m in metrics.items():
        if m["unit"] == "%" and ("roofline" in name or "mfu" in name) \
                and m["value"] > 105.0:
            print(f"benchmark: {name} reads {m['value']}% of a peak: the "
                  f"operations or bytes are counted too high, or the time "
                  f"leaves out part of the work. operands: "
                  f"{json.dumps(operands.get(name), default=float)}",
                  file=sys.stderr, flush=True)
            raise SystemExit(4)


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None = None,
                compared: list | None = None) -> str:
    """``compared``: the (name, value, limit) rows that decided ``correct``,
    under a key of their own that comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if compared:
        out["check"] = {name: {"value": float(value), "limit": float(limit)}
                        for name, value, limit in compared}
    return json.dumps(out)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))

