"""Runtime ledger of XLA program mints — the compile black box.

XLA compiles are the serving tier's least visible stall class: a mint
on the serving path blocks the scheduler thread for tens to hundreds
of milliseconds (PERF.md r14 committed a 0.17x bench ratio to mid-pass
compiles before the keying was fixed structurally; r16 found a ~240 ms
compile stall inside an interactive p99), yet until this module the
only trace was the watchdog's grace extension. The ledger instruments
the one chokepoint every serving program passes through
(``DecodeStepper._jit``) so EVERY mint records:

- ``key`` — the program family and bucket (``"admit[16]"``,
  ``"paged_step[4,masked]"``), stamped at the ``_jit`` call site;
- ``seconds`` — the wall time the calling thread lost to the mint
  (trace + compile + first dispatch: the stall a request actually
  experienced, not the compiler's self-reported time);
- ``trigger`` — ``"warmup"`` (inside ``DecodeStepper.warmup()``, the
  off-path place compiles belong) or ``"serving"`` (the live path);
- ``inflight`` — how many requests were queued/active at mint time
  (the blast radius);
- ``rewarm`` — True when this (key, shape-signature) was already
  minted by an earlier stepper generation: a supervisor restart
  recompiling a known-hot program is expected, not a storm.

Detection rides jax's backend-compile monitoring event (fired
synchronously, on the calling thread, once per REAL compile — an
executable-cache-size heuristic was observed to lag the compile by
several calls and then blame an innocent later one), so a silent
RETRACE of an existing program — the layout-drift class
``out_shardings`` pinning exists to prevent — is caught exactly like
a fresh bucket; when the monitoring API is absent the wrapper falls
back to first-call-per-program detection.

**Compile-storm detection**: once :meth:`CompileLedger.mark_warmed`
has been called (a harness's explicit "the warm set is complete"
boundary, after ``warmup()`` + the ``warm_*_buckets`` warms its
traffic needs), any serving-path mint of a program
signature never seen before is a STORM — it records an
``xla.compile.storm`` flight-recorder event and ticks the
``serving_compile_storms`` gauge. Both soaks assert zero storms, and
``tests/test_serving_mixes.py`` holds the invariant that a pass over a
warmed engine contains no mints at all — the twice-repeated bench
post-mortem turned into a standing gate.
"""

from __future__ import annotations

import threading
import time
from collections import deque


class CompileLedger:
    """Engine-owned mint ledger, shared across supervisor-rebuilt
    stepper generations (restart recompiles are attributed, and the
    counters never reset mid-window underneath ``MetricsHistory``).

    ``registry``: registers ``<prefix>_compiles`` /
    ``<prefix>_compile_seconds`` counters and the
    ``<prefix>_compile_storms`` / ``<prefix>_compile_warmed`` gauges.
    ``recorder``: every mint lands as an ``xla.compile`` event (storms
    additionally as ``xla.compile.storm``). ``inflight_fn``: cheap
    callable for the requests-in-flight stamp (the engine wires the
    scheduler's occupancy)."""

    def __init__(self, registry=None, recorder=None,
                 prefix: str = "serving", capacity: int = 256,
                 inflight_fn=None):
        self._records: deque = deque(maxlen=int(capacity))
        self._seen: set = set()
        self._lock = threading.Lock()
        self.recorder = recorder
        self.inflight_fn = inflight_fn
        self.warmed = False
        self.total = 0
        self.warmup_mints = 0
        self.serving_mints = 0
        self.rewarms = 0
        self.storms = 0
        self.seconds = 0.0
        self._compiles_counter = None
        self._seconds_counter = None
        if registry is not None:
            # counters (not gauges): mints only accumulate, and the
            # history layer computes windowed compile RATES from them
            self._compiles_counter = registry.counter(
                f"{prefix}_compiles",
                help="XLA programs minted (compiled) at runtime",
            )
            self._seconds_counter = registry.counter(
                f"{prefix}_compile_seconds",
                help="wall seconds serving threads lost to XLA mints",
            )
            registry.gauge(
                f"{prefix}_compile_storms",
                fn=lambda: self.storms,
                help="post-warmup serving-path mints of never-seen "
                     "programs",
            )
            registry.gauge(
                f"{prefix}_compile_warmed",
                fn=lambda: self.warmed,
                help="1 once warmup completed (storm detection armed)",
            )

    # -- warmup boundary ----------------------------------------------------

    def mark_warmed(self) -> None:
        """Arm storm detection: everything compiled so far was warmup
        or acknowledged cold-start; from here, a serving-path mint of
        a new program signature is a storm. A HARNESS-level
        declaration, made after the full warm set its traffic needs
        (live warm drives + the stepper's ``warm_*_buckets`` warms) —
        ``DecodeStepper.warmup()`` deliberately does not call it,
        because it covers only the step/verify families."""
        self.warmed = True

    # -- recording (called from the _jit wrapper) ---------------------------

    def record_mint(self, key: str, seconds: float, signature=(),
                    warming: bool = False, generation=None) -> dict:
        """One program mint. ``signature`` is the hashable shape/dtype
        tuple of the call's arguments — (key, signature) identity is
        what distinguishes a supervisor restart recompiling a known
        program (``rewarm``) from a genuinely new program appearing
        mid-serving (a storm candidate)."""
        sig = (str(key), signature)
        inflight = None
        fn = self.inflight_fn
        if fn is not None:
            try:
                inflight = fn()
            except Exception:  # noqa: BLE001 — observability boundary
                inflight = None
        with self._lock:
            rewarm = sig in self._seen
            self._seen.add(sig)
            trigger = "warmup" if warming else "serving"
            storm = self.warmed and not warming and not rewarm
            rec = {
                "t": time.time(),
                "key": str(key),
                "seconds": round(float(seconds), 4),
                "trigger": trigger,
                "inflight": inflight,
                "rewarm": rewarm,
                "storm": storm,
            }
            if generation is not None:
                rec["generation"] = generation
            self._records.append(rec)
            self.total += 1
            self.seconds += float(seconds)
            if warming:
                self.warmup_mints += 1
            else:
                self.serving_mints += 1
                if rewarm:
                    self.rewarms += 1
            if storm:
                self.storms += 1
        if self._compiles_counter is not None:
            self._compiles_counter.inc()
            self._seconds_counter.inc(float(seconds))
        if self.recorder is not None:
            self.recorder.record("xla.compile", **{
                k: rec[k] for k in
                ("key", "seconds", "trigger", "inflight", "rewarm")
            })
            if storm:
                # the page-now event: a compile landed on the serving
                # path AFTER warmup claimed coverage — either warmup
                # has a hole or a compile key regressed to something
                # traffic-shape-dependent
                self.recorder.record(
                    "xla.compile.storm", key=rec["key"],
                    seconds=rec["seconds"], inflight=inflight,
                )
        return rec

    # -- reading ------------------------------------------------------------

    def tail(self, n: int) -> list:
        """The most recent ``n`` mint records (newest last)."""
        if n <= 0:
            return []
        with self._lock:
            return list(self._records)[-n:]

    def mints(self) -> list:
        with self._lock:
            return list(self._records)

    def snapshot(self) -> dict:
        """The JSON-able ledger summary ``stats()`` and the soak
        summaries carry."""
        with self._lock:
            return {
                "total": self.total,
                "warmup": self.warmup_mints,
                "serving": self.serving_mints,
                "rewarms": self.rewarms,
                "storms": self.storms,
                "seconds": round(self.seconds, 4),
                "warmed": self.warmed,
                "recent": [
                    {k: r[k] for k in
                     ("key", "seconds", "trigger", "inflight",
                      "rewarm", "storm")}
                    for r in list(self._records)[-8:]
                ],
            }
