"""Overlap ledger — the zero-bubble decode instrumentation.

The serving loop alternates host scheduling work (admission, chunked
prefill, QoS decisions, page bookkeeping, stream pushes) with the
compiled device step. Sequentially those phases add; with async
dispatch they overlap, and the *bubble* — iteration wall-clock the
device spent idle waiting on the host — is the number the overlap
refactor exists to shrink. This ledger makes it a first-class,
time-series-visible metric instead of a one-off bench printout:

- ``serving_step_bubble_seconds`` (histogram): per scheduler
  iteration, ``iteration_wall - device_wall`` clipped at zero, where
  iteration wall is collect-to-collect and device wall is
  dispatch-to-ready for that iteration's step.
- ``serving_overlap_efficiency`` (gauge): cumulative
  ``device_seconds / iteration_seconds`` — the fraction of decode
  wall-clock the device was actually computing (1.0 = zero bubble).
  ``1 - efficiency`` is the bubble fraction ``dkt_top`` renders.

The batcher stamps three instants per step through this ledger:
``note_dispatch()`` when the compiled call has RETURNED (the device
starts once the host has handed the program over; the call itself is
host time, and a stamp taken before it counted 8.8 ms of an idle chip
as device time in every ``serve_backlog`` iteration),
``note_ready()`` when device completion is first *observed* (an
opportunistic poll between host phases, or implicitly at collect),
and ``note_collect()`` when the tokens are materialized. Steps close
in dispatch order and two can be open at once: the overlapped loop
dispatches step n+1 before it collects step n. The device is serial,
so a step's device wall starts at the later of its own dispatch stamp
and the previous step's ready stamp, and ends at its ready stamp; the
iteration wall is collect-to-collect. Device wall is measured, not
inferred: if readiness was never observed before the blocking
collect, the device ran right up to the collect and the bubble for
that interval is honestly zero. The clock is injectable so the
arithmetic is unit-testable without sleeping.

Both loop modes feed the same ledger. The sequential control (and a
stepper without an async face, whose device call runs inside the
dispatch) has one blocking call that is dispatch and wait at once, so
it stamps dispatch BEFORE that call: its device wall holds the call's
host part, which is the bubble the overlapped loop hides and the trace
(``device_idle_pct``) prices exactly.
"""

from __future__ import annotations

import collections
import time


class OverlapLedger:
    """Per-iteration dispatch/ready/collect bookkeeping over a
    ``MetricsRegistry``. Single-writer (the scheduler thread); the
    gauge callback tolerates a torn read like every other scrape."""

    def __init__(self, registry, clock=time.monotonic):
        self._clock = clock
        # 1 µs .. ~67 s: decode bubbles on a warm CPU engine are
        # tens of microseconds; a compile stall is tens of seconds
        self.bubble = registry.histogram(
            "serving_step_bubble_seconds",
            help="per-iteration host bubble: iteration wall minus "
                 "device wall",
            start=1e-6, factor=2.0, num_buckets=26,
        )
        registry.gauge(
            "serving_overlap_efficiency",
            help="cumulative device_wall / iteration_wall (1.0 = "
                 "zero bubble)",
            fn=lambda: self.efficiency,
        )
        self.iterations = 0
        self.device_seconds = 0.0
        self.iteration_seconds = 0.0
        # [dispatched_at, ready_at] of every dispatched, uncollected
        # step, oldest first
        self._open: collections.deque[list] = collections.deque()
        self._last_ready = None  # when the device ended the last step
        self._last_collect = None

    # -- the three stamps (scheduler thread only) ---------------------------

    def note_dispatch(self) -> None:
        """A compiled step was just handed to the device (stamp this
        when the call returns): one more open step."""
        self._open.append([self._clock(), None])

    def note_ready(self) -> None:
        """Device completion of the OLDEST open step observed (first
        observation wins — later polls and the implicit collect stamp
        never move it back)."""
        try:
            oldest = self._open[0]
        except IndexError:  # nothing open (or stop() just discarded it)
            return
        if oldest[1] is None:
            oldest[1] = self._clock()

    def note_collect(self) -> None:
        """Tokens materialized: close the oldest open step's entry.
        No-op when nothing was dispatched (idle scheduler passes)."""
        now = self._clock()
        try:
            dispatched, ready = self._open.popleft()
        except IndexError:
            return
        if ready is None:
            ready = now
        # the device is serial: a step dispatched behind another one
        # starts when that one ends, not when the host let go of it
        start = (
            dispatched if self._last_ready is None
            else max(dispatched, self._last_ready)
        )
        device = max(0.0, min(ready, now) - start)
        # iteration wall: collect-to-collect once steady, else
        # dispatch-to-collect (the first iteration has no predecessor)
        base = (
            self._last_collect if self._last_collect is not None
            else dispatched
        )
        iter_wall = max(0.0, now - base)
        device = min(device, iter_wall)
        self.bubble.observe(iter_wall - device)
        self.iterations += 1
        self.device_seconds += device
        self.iteration_seconds += iter_wall
        self._last_ready = ready
        self._last_collect = now

    def discard(self) -> None:
        """Drop every open entry without closing it (the steps were
        abandoned — scheduler stop with a handle still in the air, or
        the step dispatched behind one whose collect raised)."""
        self._open.clear()

    # -- read side ----------------------------------------------------------

    @property
    def efficiency(self):
        """Cumulative device/iteration wall fraction; None before the
        first completed iteration (a gauge gap, not a fake 0 or 1)."""
        if self.iteration_seconds <= 0.0:
            return None
        return min(1.0, self.device_seconds / self.iteration_seconds)

    @property
    def bubble_fraction(self):
        """``1 - efficiency``; None before the first iteration."""
        eff = self.efficiency
        return None if eff is None else 1.0 - eff

    def snapshot(self) -> dict:
        """JSON-able summary for ``health``/bench blocks."""
        eff = self.efficiency
        return {
            "iterations": self.iterations,
            "device_seconds": round(self.device_seconds, 6),
            "iteration_seconds": round(self.iteration_seconds, 6),
            "efficiency": None if eff is None else round(eff, 4),
            "bubble_fraction": (
                None if eff is None else round(1.0 - eff, 4)
            ),
        }
