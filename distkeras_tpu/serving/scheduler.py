"""Request scheduling for the online serving runtime — pure host logic.

Two schedulers, one per inference shape:

- ``ContinuousBatcher``: iteration-level (Orca-style) batching for the
  autoregressive decode path. A fixed bank of ``num_slots`` sequence
  slots advances ONE token per scheduler step; finished sequences are
  evicted and queued requests admitted between steps, so the compiled
  decode step always sees the same static (num_slots, seq_len) shape
  while the logical batch composition churns freely. This is the
  serving counterpart of the generators' "one compiled program" rule:
  the program is compiled once, occupancy is a runtime mask.
- ``WindowedBatcher``: size/timeout-windowed batching for
  ``ModelPredictor``-style batch scoring — requests accumulate until
  the window fills or the wait budget expires, then run as one padded
  forward.

Neither class imports JAX or touches sockets: the device face is an
injected "stepper" object (``engine.DecodeStepper`` in production, a
pure-Python fake in the unit tests) with::

    num_slots : int        # slot-bank width (static batch shape)
    max_len   : int        # sequence capacity per slot
    begin_admit(slot, prompt) -> int   # start admission; returns the
                           # prefill positions remaining (0 = decodable)
    prefill_chunk(slot, budget) -> int # prefill <= budget more prompt
                           # positions; returns positions remaining
    release(slot)          # slot freed (bookkeeping hook)
    step(active) -> (num_slots,) int array, the token appended per slot

A stepper MAY additionally expose ``step_async(active)`` returning a
handle with ``ready() -> bool`` and ``collect() -> tokens`` (and,
optionally, ``discard()`` for a handle that is dropped un-collected):
with ``overlap=True`` the batcher then keeps the device busy through
its own host work — the zero-bubble loop. One call admits and prefills
under step N-1, DISPATCHES step N behind it (``step_async`` with a step
still in the air: the stepper builds N's arguments as they will be once
N-1 is collected), and only then collects and emits N-1, so the jitted
call of N runs while the device steps and not after it. Handles are
collected in dispatch order; at most two are open, and only inside a
call. Where step N needs N-1's tokens on the host (a grammar mask, a
drafter's sequences), where nothing may be in the air (a preemption),
or after a failure, the call collects first and dispatches after, one
step deep (``_lookahead_refusal``). Steppers without the async face
still work under ``overlap=True`` (the device call runs synchronously
at dispatch; the loop shape and outputs are unchanged), and
``overlap=False`` keeps the strict one-call-emits sequential control.
Both modes stamp the same ``OverlapLedger``
(``serving_step_bubble_seconds`` / ``serving_overlap_efficiency``),
so the bubble is one instrument read either way.

Speculative steppers additionally expose ``speculative`` (truthy),
``wants_sequences`` (the batcher then passes each active slot's host
sequence so far), and ``spec_step(active, seqs) -> (toks, counts,
used_verify)`` where ``toks`` is (num_slots, w) and row i's first
``counts[i]`` entries are the tokens slot i emits this iteration —
slots advance a VARIABLE 1..w tokens per step, so EOS / max-tokens /
deadline checks run per emitted token, in emission order.

Backpressure is explicit: a full queue rejects at ``submit`` with
``OverloadedError`` (the server turns that into an ``overloaded`` wire
reply) instead of queueing unboundedly. Per-request deadlines are
checked at admission and after every step; drain mode stops admission
of NEW requests while in-flight ones run to completion.

Failures are CONTAINED, not fatal: a device-step exception triggers
blame assignment (masked retry of the newest admission, bisection if
needed — ``ContinuousBatcher._step_with_blame``) so only the culpable
request fails (typed ``InternalError``) and its slot is quarantined,
while every surviving stream advances exactly one token per iteration;
a prefill crash fails just its own (attributable) request. See
docs/ARCHITECTURE.md "Failure modes & recovery".
"""

from __future__ import annotations

import collections
import contextlib
import logging
import queue as _queue
import threading
import time

import numpy as np

logger = logging.getLogger(__name__)


_NO_EVICT = object()  # "no eviction pending" sentinel (step loop)


class _NoSpan:
    """The span this module opens when nobody handed it a profiler's:
    the face of ``jax.profiler.TraceAnnotation`` (a context manager
    that takes arguments at its open and, through ``set_metadata``, at
    its close) and nothing behind it."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_NO_SPAN = _NoSpan()


def _no_span(name, **args):
    return _NO_SPAN


# a gap this long between two working iterations, with work held at the
# first one's close, is a stall (``ContinuousBatcher._note_gap``): the
# longest sound iteration on record is 0.17 s, the holes that one
# serving run in a dozen shows are 2-4 s
STALL_GAP_S = 0.5

# why a call of the overlapped loop did not dispatch ahead of the step
# in the air (``ContinuousBatcher._lookahead_refusal``); each has a
# ``drained_<reason>`` counter
_DRAIN_REASONS = (
    "sync_stepper", "wants_sequences", "grammar", "preempt", "failed_step",
)


def _still_held(active, reqs, slots) -> np.ndarray:
    """The slots of a dispatched step's mask that still hold the
    request they held at its dispatch (``reqs``)."""
    return np.array(
        [a and r is s for a, r, s in zip(active, reqs, slots)], bool
    )


class _Inflight:
    """One dispatched-but-uncollected device step, scheduler-side: the
    active mask / sequences it was issued against, the request each
    slot held at that moment (a slot of the mask may be evicted, and
    even given to a new tenant, before this step is collected: the
    step's result counts only where the slot still holds that
    request), the wall/mint stamps its collect needs for attribution,
    and exactly one of — an engine ``step_async`` handle (async
    dispatch), a held synchronous result tuple (steppers without an
    async face: speculative drafters materialize host-side mid-call,
    unit-test fakes), or a stashed dispatch exception (a failure at
    dispatch surfaces at the COLLECT of this step's own iteration,
    where the blame machinery runs)."""

    __slots__ = (
        "active", "seqs", "reqs", "t0", "mints0", "handle", "result",
        "exc",
    )

    def __init__(self, active, seqs, reqs, t0, mints0):
        self.active = active
        self.seqs = seqs
        self.reqs = reqs
        self.t0 = t0
        self.mints0 = mints0
        self.handle = None
        self.result = None
        self.exc = None

    def ready(self) -> bool:
        if self.handle is not None:
            return self.handle.ready()
        return True  # held result / stashed exception: nothing to wait on

    def held(self, slots) -> np.ndarray:
        return _still_held(self.active, self.reqs, slots)

    def drop(self) -> None:
        """Abandon the step un-collected, and say so to a handle that
        wants to know (the stepper stops counting it as in the air)."""
        discard = getattr(self.handle, "discard", None)
        if discard is not None:
            discard()


class ServingError(RuntimeError):
    """Base class for request-level serving failures; ``code`` is the
    stable wire-level error string the server replies with."""

    code = "error"


class OverloadedError(ServingError):
    """Admission queue full — retry later (explicit backpressure)."""

    code = "overloaded"


class PoolExhaustedError(OverloadedError):
    """The paged KV cache's page pool cannot cover an allocation —
    capacity pressure, not a fault, so it IS ``overloaded`` on the wire
    (retriable; ``retry_after_ms`` rides the typed error so embedded
    callers get the same backoff hint the server stamps on replies).
    Raised by ``serving.paging.PageAllocator.alloc`` and surfaced by
    the scheduler when an admission's page reservation cannot be met."""

    def __init__(self, msg, retry_after_ms: float = 50.0):
        super().__init__(msg)
        self.retry_after_ms = float(retry_after_ms)
        # what networking.RetryPolicy reads (seconds, Retry-After style)
        self.retry_after = self.retry_after_ms / 1e3


class ShedError(OverloadedError):
    """Refused at the door by the adaptive overload gate
    (``resilience.AdmissionController``) — plain ``overloaded`` on the
    wire, but the ``retry_after_ms`` hint is HONEST: the gate's recent
    observed queue sojourn, not a server-wide constant, so shed
    clients back off by how congested the queue actually is."""

    def __init__(self, msg, retry_after_ms: float = 50.0):
        super().__init__(msg)
        self.retry_after_ms = float(retry_after_ms)
        self.retry_after = self.retry_after_ms / 1e3


class QuotaExhaustedError(OverloadedError):
    """A tenant's admission quota (router-side token bucket) cannot
    cover this request — per-tenant backpressure, shed AT THE DOOR so
    one tenant's burst never holds pages or queue slots another tenant
    needs. Retriable; ``retry_after_ms`` is the honest refill time."""

    code = "quota_exhausted"

    def __init__(self, msg, retry_after_ms: float = 50.0):
        super().__init__(msg)
        self.retry_after_ms = float(retry_after_ms)
        self.retry_after = self.retry_after_ms / 1e3


class WrongRoleError(ServingError):
    """The verb is not served by this engine's disaggregation role —
    a prefill worker refuses plain ``generate``/``resume``, a decode
    worker refuses the ``prefill`` face. A routing error (the fleet
    router dispatches by role), not backpressure: never retried."""

    code = "wrong_role"


class PeerError(ServingError):
    """A worker-to-worker KV fabric operation failed — a peer prefix
    fetch, a direct prefill→decode push, or the serving half of a
    sibling's ``kv.fetch``. Typed so every peer path stays fail-soft:
    the requester degrades to local recompute (token-identical to the
    never-fetched run), the router falls back to its relay hop — a
    peer failure is never a client-visible error by itself."""

    code = "kv_peer"


class StaleEpochError(PeerError):
    """A peer frame or fetch named a KV epoch this engine no longer
    serves — the sibling routed on a digest advertised before this
    engine restarted or rolled over. Refused typed (never served: a
    restarted engine may hold different weights, and KV pages computed
    under them would silently break the recompute-identity pin); the
    requester falls back to local recompute and picks up the new epoch
    on its next digest poll."""

    code = "stale_epoch"


class DeadlineExceededError(ServingError):
    """The request's deadline expired before it finished decoding."""

    code = "deadline_exceeded"


class EngineStoppedError(ServingError):
    """The engine is draining or stopped; no new admissions."""

    code = "stopping"


class InternalError(ServingError):
    """The engine failed this request for an internal reason — a device
    step blamed on it, a prefill crash, or a scheduler restart that
    aborted it mid-flight. Typed so clients are never left to a timeout
    or a bare connection error when the engine is the thing at fault."""

    code = "internal"


class ServeRequest:
    """One generate request riding the continuous batcher.

    ``deadline`` is an absolute ``time.monotonic()`` instant (None =
    no deadline). ``result(timeout)`` blocks until the request finishes
    and returns the full sequence (prompt + generated tokens, cut after
    the first generated ``eos_id`` inclusive, matching the generators'
    return convention) or raises the recorded ``ServingError``.

    ``trace``: an optional ``obs.tracing.TraceContext``. When set, the
    batcher additionally records a per-request EVENT ledger (one entry
    per prefill chunk, one per blame assignment) that
    ``obs.tracing.request_spans`` turns into the server-side phase
    timeline; untraced requests skip the ledger entirely (the
    timestamps below are always stamped — they feed ``latency()``).

    ``sampling``: an optional ``sampling.SamplingParams``. ``n > 1``
    makes this a COMPLETION GROUP: the request holds n slots (one
    prefill + n-1 CoW forks), ``completions`` collects each stream's
    tokens, and ``result()`` returns a LIST of n sequences. The group
    finishes when every completion finishes; any typed failure fails
    the whole group (all complete, or all typed — never a partial
    reply).
    """

    _ids = iter(range(1, 1 << 62))
    _ids_lock = threading.Lock()

    def __init__(self, prompt, max_new_tokens, eos_id=None, deadline=None,
                 trace=None, sampling=None, tenant=None, priority=0,
                 stream=False, prefill_only=False):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}"
            )
        with self._ids_lock:
            self.id = next(self._ids)
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = None if eos_id is None else int(eos_id)
        self.deadline = None if deadline is None else float(deadline)
        # multi-tenant QoS identity: the tenant name scopes WFQ shares,
        # quotas, and metric labels; the priority class orders
        # admission and licenses preemption (higher = more urgent)
        self.tenant = "default" if tenant is None else str(tenant)
        self.priority = int(priority)
        self.preemptions = 0  # times this request was swapped out
        # when swapped out: the stepper's host-side swap state (KV rows
        # in the PrefixStore serialization format + ctx/sampler state);
        # rides the REQUEST so a stop/deadline/restart that fails a
        # swapped request drops the host state with it — nothing leaks
        self._swap = None
        self.sampling = sampling  # SamplingParams | None (= greedy)
        self.n = 1 if sampling is None else int(sampling.n)
        # streaming delivery: the scheduler hands each iteration's
        # emitted tokens over, bounded by construction (at most
        # max_new_tokens entries + one sentinel) — token delivery never
        # runs under the scheduler lock or blocks on a slow client
        # socket. ``stream=True``: a FIFO of the request's own, drained
        # by ``next_chunk`` (in-process consumers). A SINK in its place
        # (the server's: ``push(req, tokens | None)`` and ``wake``)
        # takes the hand-overs of all its requests in one queue, and
        # the batcher wakes it once an iteration (``_emitting``)
        self.stream = bool(stream)
        self._sink = stream if hasattr(stream, "push") else None
        self._chunks = (
            _queue.SimpleQueue()
            if self.stream and self._sink is None else None
        )
        # first CHUNK FLUSHED to the wire (streaming path) — stamped by
        # the server thread after the send completes; the honest TTFT
        # (``latency()`` prefers it over the scheduler-side append)
        self.first_sent = None
        # disaggregated prefill: the request completes the moment its
        # prefill finishes, with the slot's swap-format state on
        # ``export`` instead of decoded tokens (the prefill worker's
        # half of the prefill/decode role split)
        self.prefill_only = bool(prefill_only)
        self.export = None
        self.created = time.monotonic()
        self.started = None  # admission instant (queue wait ends)
        self.prefill_finished = None  # slot became decodable
        self.first_token = None  # first generated token appended (TTFT)
        self.finished = None
        # per-completion token lists; ``tokens`` IS completions[0] (the
        # n=1 fast path every existing call site reads)
        self.completions: list[list[int]] = [[] for _ in range(self.n)]
        self.tokens: list[int] = self.completions[0]
        self.error: ServingError | None = None
        self.trace = trace  # TraceContext | None (None = no ledger)
        self.events: list[dict] = []  # trace ledger (traced reqs only)
        self.prefill_chunks = 0  # stepper.prefill_chunk calls, this req
        self.iterations = 0  # scheduler iterations this slot advanced
        self.page_waited = False  # admission held it for KV pages
        self._done = threading.Event()

    # -- lifecycle (called by the batcher, under its lock) ------------------

    def _finish(self, error: ServingError | None = None):
        self.error = error
        self.finished = time.monotonic()
        self._swap = None  # host KV rows released with the request
        self._done.set()
        # terminal sentinel AFTER the result is readable: the draining
        # thread sees every chunk, then None, then reads
        # ``error``/``result()`` without racing the finish
        if self._sink is not None:
            self._sink.push(self, None)
        elif self._chunks is not None:
            self._chunks.put(None)

    def _push_chunk(self, toks) -> None:
        """One scheduler iteration's emitted tokens for the draining
        (server) thread. Called by the batcher BEFORE any eviction this
        iteration triggers, so the sentinel can never overtake data."""
        if self._sink is not None:
            self._sink.push(self, list(toks))
        elif self._chunks is not None:
            self._chunks.put(list(toks))

    def next_chunk(self, timeout=None):
        """Blocking read of the stream FIFO: a list of newly emitted
        tokens, or None when the request finished (read ``error`` /
        ``result()`` after the sentinel). Raises ``TimeoutError`` when
        nothing arrived in ``timeout`` seconds — the draining thread's
        guard against a wedged scheduler (the engine watchdog fails the
        request typed long before a sane timeout elapses)."""
        try:
            return self._chunks.get(timeout=timeout)
        except _queue.Empty:
            raise TimeoutError(
                f"request {self.id}: no stream progress in {timeout}s"
            ) from None

    def _expired(self, now) -> bool:
        return self.deadline is not None and now >= self.deadline

    # -- caller face --------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout=None):
        """The full sequence (prompt + generated, cut after the first
        generated eos) — or, for a completion group (``n > 1``), the
        LIST of n such sequences in completion order."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} still running")
        if self.error is not None:
            raise self.error
        if self.n == 1:
            return self._seq(self.tokens)
        return [self._seq(c) for c in self.completions]

    def _seq(self, toks) -> np.ndarray:
        seq = np.concatenate([self.prompt, np.asarray(toks, np.int32)])
        if self.eos_id is not None and self.eos_id in toks:
            cut = self.prompt.size + list(toks).index(self.eos_id) + 1
            seq = seq[:cut]
        return seq

    def latency(self) -> dict:
        """Per-request timing breakdown (seconds) for the metrics sink:
        queue wait (submit -> admission), prefill (admission -> slot
        decodable), decode (decodable -> done), plus ``ttft`` and
        ``total``. Phases a failed request never reached stay None.

        TTFT accounting: on the STREAMING path ``ttft`` measures to
        the first token's DELIVERY (the server thread's stamp after
        the first chunk frame flushed to the socket) — the number a
        client actually experiences. The non-streaming path keeps the
        scheduler-side first-append stamp, which UNDERCOUNTS by
        however long the finished response then waits behind decode
        and the reply serialization; PERF.md r18 states the measured
        before/after of that correction."""

        def span(a, b):
            return None if a is None or b is None else b - a

        first = (
            self.first_sent
            if self.first_sent is not None
            else self.first_token
        )
        return {
            "queue_wait": span(self.created, self.started),
            "prefill": span(self.started, self.prefill_finished),
            "decode": span(self.prefill_finished, self.finished),
            "ttft": span(self.created, first),
            "total": span(self.created, self.finished),
        }


class ContinuousBatcher:
    """Slot-bank continuous batching: admission, eviction, and completion
    bookkeeping around an injected device stepper. Thread-safe submit;
    ``step()`` must be driven by exactly one loop (the engine thread).

    Slots have an explicit lifecycle: ``queued -> prefilling ->
    decoding -> evicted``. Admission is INCREMENTAL (Sarathi-style
    chunked prefill): ``begin_admit`` starts a slot in the prefilling
    state, and each scheduler iteration spends at most
    ``prefill_chunk`` prompt tokens (shared across prefilling slots,
    oldest admission first) via ``stepper.prefill_chunk`` before the
    decode step runs — so one long prompt delays every decoding slot's
    next token by one bounded chunk, not its whole prefill. Slots mid-
    prefill are excluded from the step's active mask. ``prefill_chunk=
    None`` removes the budget (full prefill at admission — the PR 1
    scheduler's behavior, kept as the benchmark baseline).
    """

    def __init__(self, stepper, queue_capacity=64, prefill_chunk=None,
                 quarantine_steps=64, registry=None, recorder=None,
                 qos=None, overlap=False, shed_gate=None, span=None):
        """``quarantine_steps``: scheduler iterations a slot sits out
        after a device step is blamed on its request (its cache rows are
        suspect, and a systematically poisonous traffic shape should not
        re-enter the bank instantly); the slot recycles into the free
        pool automatically once the probation expires.

        ``registry``: an ``obs.MetricsRegistry`` to register the
        scheduler's counters and occupancy gauges in (the engine passes
        its own, so the ``metrics`` verb scrapes them); None builds a
        private one. ``counters`` stays dict-shaped (a
        ``CounterGroup``) so every existing call site and reset loop
        keeps working while the values become typed metrics.

        ``recorder``: an ``obs.FlightRecorder`` (the engine passes its
        own) — the batcher then records iteration summaries, blame and
        quarantine decisions, and prefill failures ALWAYS-ON (one
        bounded-deque append per working iteration; idle iterations
        record nothing). None disables recording.

        ``qos``: an optional ``qos.QosPolicy``. None (the default)
        keeps the single-FIFO scheduler exactly as it was. A policy
        replaces the queue with priority classes + per-tenant weighted
        fair queuing, and (``preempt=True``) lets a higher-priority
        arrival that cannot be admitted DISPLACE the lowest-priority
        decodable slot: the victim's KV swaps out to host through the
        stepper (``swap_out``), its pages free, and it re-queues at
        the front of its class with the swap state riding the request;
        resume is ``swap_in`` (restore + re-reserve), token-identical
        across the boundary. ``max_preemptions`` bounds displacement
        per request so nothing livelocks.

        ``overlap``: True runs the ZERO-BUBBLE loop, as deep as is
        legal and at most two steps — each ``step()`` call first does
        the host scheduling work (admission, chunked prefill, exports,
        forks, deadline sweeps) while the PREVIOUS iteration's device
        step runs, then dispatches the NEXT step behind it, then
        collects the previous step's tokens (emission/eviction — the
        only host sync point) and returns with one step in the air.
        Where the next step cannot be built before the previous one's
        tokens are on the host (``_lookahead_refusal``) the call
        collects first and dispatches after. Token order per request
        is UNCHANGED; a step that fails surfaces at the collect of its
        own iteration with the same blame/quarantine semantics. False
        (the default here; the ``ServingEngine`` defaults to True) is
        the strictly sequential dispatch-and-wait loop — the
        bit-identical control side of the bench A/B, and what
        raw-batcher unit tests drive so one ``step()`` call emits its
        own tokens. Steppers without a ``step_async`` face (fakes,
        speculative draft/verify — the drafter materializes host
        state mid-call) run their device call synchronously at
        dispatch; the loop structure and failure surfacing stay
        identical.

        ``shed_gate``: an optional
        ``resilience.AdmissionController``. None (the default) keeps
        the door exactly as it was — admit until ``queue_capacity``,
        then typed ``overloaded``. A gate is consulted BEFORE the
        capacity check on every ``submit``: it may shed the request
        (typed ``overloaded`` with an honest sojourn-derived
        ``retry_after_ms``) or clamp its ``max_new_tokens`` (brownout
        rung 2 — deterministic decode makes the clamped reply an
        exact prefix of the full one), and the admission phase feeds
        it each admitted request's queue sojourn so the CoDel side
        has a signal.

        ``span``: ``span(name, **args)`` opens a span on the profiler's
        timeline (the engine passes ``jax.profiler.TraceAnnotation``
        behind a function; this module imports no JAX). Every phase of
        an iteration runs under one, named ``serving/<phase>`` inside
        ``serving/iter``; a count known only when a span closes is set
        there with ``set_metadata``. None opens nothing."""
        from distkeras_tpu.serving.qos import _QosQueues

        self.stepper = stepper
        self.queue_capacity = int(queue_capacity)
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        self.qos = qos
        self.shed_gate = shed_gate
        self._preemptible = qos is not None and qos.preempt and hasattr(
            stepper, "swap_out"
        )
        self.prefill_chunk = (
            None if prefill_chunk is None else int(prefill_chunk)
        )
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 or None; got {prefill_chunk}"
            )
        self.quarantine_steps = int(quarantine_steps)
        if self.quarantine_steps < 1:
            raise ValueError("quarantine_steps must be >= 1")
        # the request queue: a plain FIFO deque, or (under a QoS
        # policy) priority-classed per-tenant WFQ queues speaking the
        # same deque face — head-of-line discipline is unchanged, only
        # WHICH request is at the head becomes policy
        self._queue = (
            collections.deque() if qos is None else _QosQueues(qos)
        )
        self._slots: list[ServeRequest | None] = [None] * stepper.num_slots
        # completion-group bookkeeping: which completion index each
        # slot serves (0 for singles and group primaries) and which
        # reserved slots still await their post-prefill CoW fork
        self._slot_comp = [0] * stepper.num_slots
        self._awaiting_fork: dict[int, int] = {}  # slot -> completion
        # slot -> prefill positions remaining; membership IS the
        # "prefilling" state. FIFO order = admission order (fairness:
        # the oldest admission reaches its first token first).
        self._prefill_left: dict[int, int] = {}
        self._prefill_fifo: collections.deque[int] = collections.deque()
        # blame bookkeeping: per-slot admission sequence (most-recently-
        # admitted is the prime suspect of a step failure) and the
        # quarantine ledger (slot -> scheduler iteration it recycles at)
        self._admit_seq = 0
        self._admit_order = [0] * stepper.num_slots
        self._quarantined: dict[int, int] = {}
        self._sched_iters = 0  # step() calls (not device steps)
        # zero-bubble decode: the dispatched-but-uncollected step
        # BETWEEN calls (at most one; inside a call the loop holds a
        # second while it collects the first). Only the scheduler
        # thread touches it outside stop().
        self.overlap = bool(overlap)
        self._inflight: _Inflight | None = None
        # the last collect raised: the next call runs one step deep
        self._step_failed = False
        self._lock = threading.Lock()
        # the stream senders to wake where the emission under way
        # closes (``_emitting``); None outside one. Under the lock.
        self._stream_wakes: set | None = None
        self._work = threading.Event()  # signals the engine loop
        self._draining = False
        self._stopped = False
        self.recorder = recorder
        self._span = _no_span if span is None else span
        # this iteration's counts, made once when admission ends: the
        # ``serving/iter`` span and the recorder's iteration line both
        # read them from here
        self._iter_counts: dict = {}
        # how the loop spent its time between iterations (``loop_stats``);
        # the scheduler thread alone writes it, readers take torn reads
        self._loop = {
            "waits": 0, "wait_s": 0.0, "idle_passes": 0, "iterations": 0,
            "stalls": 0, "longest_gap_s": 0.0, "longest_iter_s": 0.0,
            "longest_iter_cpu_s": 0.0,
        }
        # the last working iteration's close, while it left work held:
        # (monotonic s, the thread's CPU ns, waits, idle_passes); else None
        self._held_since: tuple | None = None
        from distkeras_tpu.obs import MetricsRegistry, OverlapLedger

        self.registry = registry if registry is not None else MetricsRegistry()
        # the bubble instrument (serving_step_bubble_seconds /
        # serving_overlap_efficiency) — stamped by BOTH loop modes, so
        # the overlapped-vs-sequential A/B reads the same meter
        self.overlap_ledger = OverlapLedger(self.registry)
        # the old hand-rolled counter dict, now a CounterGroup over
        # typed registry counters (``serving_scheduler_<key>``): every
        # ``counters["key"] += 1`` call site, test, and bench counter
        # reset keeps working unchanged, and the values become
        # scrapeable through the ``metrics`` verb. ``fresh=True``: a
        # supervisor-rebuilt batcher starts at zero like the dict did.
        self.counters = self.registry.group(
            "serving_scheduler",
            (
                "submitted",
                "rejected_overloaded",
                # adaptive load shedding (0 without a shed gate).
                # Pairing invariant: every shed is a typed
                # ``overloaded`` reply carrying ``retry_after_ms``
                "shed_overloaded",  # refused at the door by the gate
                "shed_clamped",  # admitted with max_new_tokens clamped
                "completed",
                "deadline_exceeded",
                "steps",
                "occupancy_sum",  # sum over steps of active slots
                "tokens_generated",
                "prefill_chunks",  # stepper.prefill_chunk calls
                "prefill_tokens",  # prompt positions prefilled
                # fault / recovery counters (the self-healing paths)
                "step_failures",  # device step raised
                "blame_probes",  # extra step calls assigning blame
                "internal_errors",  # requests failed InternalError
                "prefill_failures",  # begin_admit/prefill_chunk raised
                "pool_exhausted",  # admissions failed typed overloaded
                # (paged KV: page reservation could not be met)
                "page_waits",  # iterations whose admission held the
                # head-of-line request because the pool was short
                "page_wait_requests",  # requests so held (each once)
                "quarantines",  # slots sent to probation
                # speculative decode (0 on non-speculative steppers)
                "spec_windows",  # slot-windows processed via verify
                "spec_tokens",  # tokens emitted from verify windows
                "spec_draft_accepted",  # emitted tokens DRAFT sourced
                # multi-tenant QoS / preemption (0 without a policy).
                # Pairing invariant at quiescence: preemptions ==
                # resumes + swap_in_failures + swapped_failed — every
                # swap-out ends in a resume or a TYPED failure, never
                # a stranded request
                "preemptions",  # successful swap-outs (victims)
                "resumes",  # swapped requests restored + decoding
                "preempt_aborted",  # swap-out failed; victim untouched
                "swap_in_failures",  # restore failed; request typed
                "swapped_failed",  # failed (stop/deadline) while out
                "swapped_tokens",  # context tokens serialized to host
                # disaggregated prefill/decode (0 on unified engines)
                "exports",  # prefill-only slots serialized + completed
                "export_failures",  # swap-out at export raised; typed
                "streamed_chunks",  # per-iteration token chunks pushed
                # the overlapped loop's depth (0 under overlap=False)
                "ahead_steps",  # steps dispatched with a step in the air
                # slot-steps whose slot no longer held the request it
                # held at dispatch when the step was collected (an EOS,
                # deadline or blame eviction one step earlier)
                "discarded_slot_steps",
                # calls that had a step in the air and did not
                # dispatch ahead of it, by reason
                *(f"drained_{r}" for r in _DRAIN_REASONS),
            ),
        )
        # occupancy gauges, computed at scrape time from state the
        # batcher already keeps (unlocked reads: scrapes tolerate a
        # torn read, the serving path must not pay a lock for them)
        self.registry.gauge(
            "serving_scheduler_queue_depth", fn=lambda: len(self._queue)
        )
        self.registry.gauge(
            "serving_scheduler_active_slots",
            fn=lambda: sum(s is not None for s in self._slots),
        )
        self.registry.gauge(
            "serving_scheduler_prefilling_slots",
            fn=lambda: len(self._prefill_left),
        )
        self.registry.gauge(
            "serving_scheduler_quarantined_slots",
            fn=lambda: len(self._quarantined),
        )
        self.registry.gauge(
            "serving_scheduler_num_slots", fn=lambda: len(self._slots)
        )
        # per-slot acceptance ledger (lifetime): windows seen / tokens
        # emitted per slot index — stats() reports the per-slot rates
        self._spec_windows = np.zeros(stepper.num_slots, np.int64)
        self._spec_emitted = np.zeros(stepper.num_slots, np.int64)
        # sampling observability (engine-registry names, per the
        # subsystem contract): requests that asked for anything beyond
        # plain greedy, and slots created by completion-group forks
        self.sampled_requests = self.registry.counter(
            "serving_sampled_requests", fresh=True
        )
        self.forked_slots = self.registry.counter(
            "serving_forked_slots", fresh=True
        )
        # per-tenant labeled counters (created lazily per tenant seen):
        # serving_preemptions{tenant=}, serving_swapped_tokens{tenant=}
        # — QoS violations must be ATTRIBUTABLE, not just counted.
        # Cardinality-bounded: tenant is a client-chosen wire string,
        # so past MAX_TENANT_LABELS distinct names the tail folds into
        # one label instead of growing the registry forever
        self._tenant_counters: dict[tuple, object] = {}
        self._tenant_label_seen: set[str] = set()

    def _tenant_counter(self, name: str, tenant: str):
        from distkeras_tpu.serving.qos import fold_tenant

        tenant = fold_tenant(self._tenant_label_seen, tenant)
        key = (name, tenant)
        c = self._tenant_counters.get(key)
        if c is None:
            c = self.registry.counter(name, labels={"tenant": tenant})
            self._tenant_counters[key] = c
        return c

    # -- submission ---------------------------------------------------------

    def submit(self, req: ServeRequest) -> ServeRequest:
        """Enqueue a request or fail fast: ``EngineStoppedError`` while
        draining/stopped, ``OverloadedError`` on a full queue (the
        bounded queue IS the backpressure contract), ``ValueError`` when
        the request cannot ever fit the slot capacity."""
        if req.prompt.size + req.max_new_tokens > self.stepper.max_len:
            raise ValueError(
                f"prompt ({req.prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the serving capacity "
                f"({self.stepper.max_len})"
            )
        if req.n > 1:
            if not getattr(self.stepper, "can_fork", False):
                raise ValueError(
                    f"n={req.n} parallel completions need CoW slot "
                    "forking — serve with paged=True"
                )
            if req.n > len(self._slots):
                raise ValueError(
                    f"n={req.n} completions exceed the "
                    f"{len(self._slots)}-slot bank"
                )
            if req.stream or req.prefill_only:
                # a completion group has no single token order to
                # stream, and a prefill-only export is one slot's
                # state — both are caller errors, not backpressure
                raise ValueError(
                    f"n={req.n} completion groups cannot be streamed "
                    "or prefill-exported"
                )
        if req.prefill_only and req.stream:
            raise ValueError(
                "prefill_only requests produce no tokens to stream"
            )
        if req.prefill_only and not hasattr(self.stepper, "swap_out"):
            raise ValueError(
                "prefill export needs a stepper with swap_out support"
            )
        if getattr(self.stepper, "paged", False):
            need = self._pages_for_request(req)
            if need > self.stepper.total_pages:
                # can NEVER fit the pool — a caller error like the
                # max_len check above, not transient backpressure
                raise ValueError(
                    f"request needs {need} KV pages but the pool holds "
                    f"{self.stepper.total_pages}"
                )
        if self.shed_gate is not None:
            # the overload-defense door, OUTSIDE the batcher lock (the
            # gate has its own leaf lock; its burn_fn walks the
            # metrics registry): shed/refuse surface as typed
            # ``overloaded`` with the gate's honest sojourn-derived
            # retry hint, clamp trims the ask before it queues
            action, hint_ms, clamp = self.shed_gate.admit(
                getattr(req, "priority", 0), req.max_new_tokens
            )
            t = self.shed_gate.poll_transition()
            if t is not None and self.recorder is not None:
                self.recorder.record(
                    "scheduler.shed_rung", old=t[0], new=t[1],
                    **self.shed_gate.state(),
                )
            if action != "admit":
                self.counters["shed_overloaded"] += 1
                raise ShedError(
                    "admission shed by overload gate "
                    f"(rung {self.shed_gate.state()['rung']})",
                    retry_after_ms=hint_ms,
                )
            if clamp is not None and clamp < req.max_new_tokens:
                req.max_new_tokens = clamp
                self.counters["shed_clamped"] += 1
        with self._lock:
            if self._draining or self._stopped:
                raise EngineStoppedError("engine is draining; not accepting")
            if len(self._queue) >= self.queue_capacity:
                self.counters["rejected_overloaded"] += 1
                raise OverloadedError(
                    f"admission queue full ({self.queue_capacity})"
                )
            self._queue.append(req)
            self.counters["submitted"] += 1
            if req.sampling is not None and not req.sampling.is_default:
                self.sampled_requests.inc()
        self._work.set()
        return req

    def _pages_for_request(self, req) -> int:
        """Pages a whole request reserves end to end: the primary's
        admission plus the fresh pages of its n-1 forks (history pages
        are CoW-shared) — what group admission gates on."""
        need = self.stepper.pages_for(req.prompt.size, req.max_new_tokens)
        if req.n > 1:
            fork_for = getattr(self.stepper, "fork_pages_for", None)
            per_fork = (
                fork_for(req.prompt.size, req.max_new_tokens)
                if fork_for is not None
                else need
            )
            need += (req.n - 1) * per_fork
        return need

    # -- compile attribution (the ledger's trace face) ----------------------

    def _led_total(self) -> int:
        """The stepper's compile-ledger mint count (0 when no ledger —
        fake steppers, draft banks): read before a device call so a
        mint landing inside it can be attributed to the traced
        request(s) it stalled."""
        led = getattr(self.stepper, "ledger", None)
        return 0 if led is None else led.total

    def _note_mints(self, req, n0, t0, t1) -> None:
        """Attribute compile-ledger mints that landed during a device
        call to a TRACED request's event ledger — ``request_spans``
        renders the entry as an ``xla.compile`` span in the
        client-assembled timeline, making the stall visible exactly
        where the request experienced it. Untraced requests cost one
        int compare."""
        if req is None or req.trace is None:
            return
        led = getattr(self.stepper, "ledger", None)
        if led is None:
            return
        n = led.total - n0
        if n <= 0:
            return
        recs = led.tail(n)
        req.events.append({
            "name": "xla.compile",
            "t0": t0, "t1": t1,
            "mints": n,
            "keys": [r["key"] for r in recs],
            "seconds": round(sum(r["seconds"] for r in recs), 4),
            "trigger": recs[-1]["trigger"] if recs else None,
        })

    # -- scheduler iteration ------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration: recycle expired quarantines, admit
        queued requests into free slots (prefilling state), spend the
        prefill chunk budget on slots mid-prefill (oldest first),
        advance every DECODING slot one token (with blame assignment on
        a step failure — see ``_step_with_blame``), evict finished
        sequences. Returns True when any slot made progress (the engine
        loop idles when False).

        Two loop shapes, one contract: sequential mode runs host-work
        -> dispatch+wait -> emit in one pass; overlapped mode
        (``overlap=True``) runs host-work (the PREVIOUS step still on
        the device) -> dispatch the next step behind it -> collect+emit
        the previous step, and returns without waiting on the one it
        dispatched. Emitted token order per request is identical —
        only where the wall-clock goes differs."""
        run = self._step_overlapped if self.overlap else self._step_sequential
        loop = self._loop
        if self.idle:
            loop["idle_passes"] += 1
            return run()  # a pass over an idle bank: no span for it
        t0, cpu0 = time.monotonic(), time.thread_time_ns()
        if self._held_since is not None:
            self._note_gap(t0, cpu0)
        with self._span("serving/iter") as it:
            progressed = run()
            it.set_metadata(**self._iter_counts)
        t1, cpu1 = time.monotonic(), time.thread_time_ns()
        loop["iterations"] += 1
        if t1 - t0 > loop["longest_iter_s"]:
            loop["longest_iter_s"] = t1 - t0
            loop["longest_iter_cpu_s"] = (cpu1 - cpu0) / 1e9
        if not progressed:
            loop["idle_passes"] += 1
        # unlocked reads: only this thread fills or frees a slot or the
        # air, and a request queued a moment later shows at the next close
        held = (
            self._inflight is not None or len(self._queue) > 0
            or any(s is not None for s in self._slots)
        )
        self._held_since = (
            (t1, cpu1, loop["waits"], loop["idle_passes"])
            if held else None
        )
        return progressed

    def _note_gap(self, now: float, cpu_now: int) -> None:
        """The gap between the last working iteration, which left work
        held, and the one that opens ``now`` (``cpu_now``: this thread's
        CPU clock then); over ``STALL_GAP_S`` it is
        a stall, with what tells its three causes apart: ``waits`` > 0,
        the loop believed it had nothing to do; ``waits`` 0 and
        ``cpu_s`` near 0, this thread was not run; ``cpu_s`` near
        ``gap_s``, it was busy in something no span names."""
        t_close, cpu_close, waits, idle_passes = self._held_since
        loop = self._loop
        gap = now - t_close
        if gap > loop["longest_gap_s"]:
            loop["longest_gap_s"] = gap
        if gap <= STALL_GAP_S:
            return
        loop["stalls"] += 1
        stall = {
            "gap_s": round(gap, 4),
            "cpu_s": round((cpu_now - cpu_close) / 1e9, 4),
            "waits": loop["waits"] - waits,
            "idle_passes": loop["idle_passes"] - idle_passes,
            "queue_depth": len(self._queue),
            "held": sum(s is not None for s in self._slots),
            "in_air": self._inflight is not None,
        }
        if self.recorder is not None:
            self.recorder.record("scheduler.stall", **stall)
        logger.warning("scheduler stall: %s", stall)

    def _step_sequential(self) -> bool:
        """The strictly sequential iteration (the pre-overlap loop,
        kept verbatim as the bit-identical control side of the
        overlap bench A/B): every phase waits for the previous one,
        so the device idles through all the host work and vice
        versa — the bubble the ledger measures."""
        progressed, _ = self._admit_phase(preempt_now=True)
        active, seqs = self._mask_phase()
        if not active.any():
            return progressed
        step_t0 = time.monotonic()
        mints0 = self._led_total()
        self.overlap_ledger.note_dispatch()
        toks, counts, blamed, used_verify = self._step_with_blame(
            active, seqs
        )
        self.overlap_ledger.note_collect()
        return self._finish_step(
            active, step_t0, mints0, toks, counts, blamed, used_verify
        )

    def _step_overlapped(self) -> bool:
        """The zero-bubble iteration. With step N-1 in the air, call N:

            admit / prefill chunk   (N-1 on the device; the chunk's
                                     call chains behind it)
            look-ahead mask         (today's mask, less the slots whose
                                     budget N-1 exhausts)
            dispatch step N         (lengths and sample positions as
                                     they WILL be once N-1 is
                                     collected; chains behind N-1 and
                                     the chunk)
            collect step N-1        (usually no wait left)
            emit step N-1
            return                  (one step in the air between
                                     calls; two only inside a call)

        so the jitted call of N — as long as the device step itself
        where the step is fast — runs while the device steps.

        Why step N need not wait for N-1's tokens: everything its call
        passes from the host is known beforehand. The last token of
        every slot lives in the stepper's context ON THE DEVICE (N-1
        writes it there, N reads it there); lengths and sample
        positions advance by exactly one for every slot of N-1's mask;
        the page table is complete from admission; sampling params are
        per request. A slot whose budget N-1 exhausts is known
        (``len(comp) + 1 >= max_new_tokens``) and left out of N's mask.
        Only a finish by EOS, deadline or blame is not known: it costs
        one DISCARDED slot-step (``_Inflight.held``, ``_emit``).

        Why this is loop structure, not semantics:

        - Admission / chunked-prefill / export device calls CHAIN
          behind the in-flight step through its un-materialized
          arrays and touch only slots the in-flight mask excludes —
          per-slot device state is disjoint, so the collected tokens
          are unaffected.
        - Slots freed by this call's collect admit on the NEXT call
          (one device-step later than the sequential loop under slot
          contention); each request's own token stream is unchanged.
        - QoS preemption needs NOTHING in the air — swapping a slot
          out from under an in-flight step would fetch post-step KV
          against pre-step host token lists — so a call that may
          preempt drains first (``_lookahead_refusal``), picks its
          victim after collect, and goes on.
        - A step that raises (at dispatch or inside the device call)
          surfaces at the COLLECT of its own iteration, where the
          blame probes run synchronously against unadvanced state —
          identical containment to the sequential loop. With two in
          the air the later step is dropped un-collected first
          (``_collect_with_blame``): nothing of either has advanced.
        """
        prev = self._inflight
        after_failure, self._step_failed = self._step_failed, False
        if prev is not None and prev.ready():
            # opportunistic poll: the device finished while the host
            # was away — stamp it so the ledger's device wall is
            # measured, not inferred from the blocking collect
            self.overlap_ledger.note_ready()
        progressed, blocked = self._admit_phase(preempt_now=False)
        if prev is not None:
            why_not = self._lookahead_refusal(prev, blocked, after_failure)
            ahead = None
            if why_not is None:
                ahead = self._dispatch_phase(ahead_of=prev)
            else:
                with self._lock:
                    self.counters[f"drained_{why_not}"] += 1
            self._inflight = ahead
            if prev.ready():
                self.overlap_ledger.note_ready()
            failed = self._collect_phase(prev, ahead)
            if why_not is None and not failed:
                return True  # step N, if any slot takes it, is in the air
        if blocked is not None and self._preempt_phase(blocked):
            progressed = True
        self._inflight = self._dispatch_phase()
        return progressed or prev is not None or self._inflight is not None

    def _lookahead_refusal(self, prev: _Inflight, blocked,
                           after_failure: bool) -> str | None:
        """Why this call must collect ``prev`` before it dispatches the
        next step (a ``_DRAIN_REASONS`` name), or None where the next
        step can be dispatched behind it. Decided per call from what
        the loop can observe: one algorithm whose depth depends on its
        input."""
        st = self.stepper
        if not self._steps_async():
            return "sync_stepper"  # its device call is synchronous
        if getattr(st, "wants_sequences", False):
            return "wants_sequences"  # ... with prev's tokens in them
        if prev.exc is not None or after_failure:
            # prev failed at dispatch, or the last call's collect
            # raised: the state a step ahead would assume is not the
            # one to come
            return "failed_step"
        constrained = getattr(st, "constrained_slots", ())
        with self._lock:
            if any(
                self._slots[i] is not None and i not in self._prefill_left
                for i in constrained
            ):
                return "grammar"  # the token mask is built from the token
            if (
                blocked is not None and self._preemptible
                and self._pick_victim_locked(blocked) is not None
            ):
                return "preempt"  # swap-out needs nothing in the air
        return None

    def _steps_async(self) -> bool:
        """Whether the stepper's device call can be issued and left in
        the air: it has the ``step_async`` face and is not speculative
        (the draft->verify path materializes host state mid-call)."""
        st = self.stepper
        return not getattr(st, "speculative", False) and hasattr(
            st, "step_async"
        )

    def _dispatch_phase(self, ahead_of: _Inflight | None = None):
        """Mask + dispatch: the next step, issued and not waited for;
        None when no slot takes it. ``ahead_of``: the step still in
        the air that this one is dispatched behind."""
        active, seqs = self._mask_phase(ahead_of)
        if not active.any():
            return None
        if ahead_of is not None:
            with self._lock:
                self.counters["ahead_steps"] += 1
            self._iter_counts["ahead"] = 1
        return self._dispatch(
            active, seqs, time.monotonic(), self._led_total()
        )

    def _collect_phase(self, inf: _Inflight, later=None) -> bool:
        """Collect + emit ``inf``, the oldest step in the air; True
        when its collect raised (``later``, the step dispatched behind
        it, is then dropped)."""
        toks, counts, blamed, used_verify, failed = (
            self._collect_with_blame(inf, later)
        )
        self.overlap_ledger.note_collect()
        if failed:
            self.overlap_ledger.discard()  # the dropped step's stamp
        self._finish_step(
            inf.active, inf.t0, inf.mints0, toks, counts, blamed,
            used_verify, reqs=inf.reqs,
        )
        return failed

    def _dispatch(self, active, seqs, t0, mints0) -> _Inflight:
        """Issue the device step for ``active`` without waiting on it.
        Async when the stepper exposes ``step_async`` and is not
        speculative (the draft->verify path materializes host state
        mid-call); otherwise the device call runs synchronously HERE
        and its result — or exception — rides the handle to this
        iteration's collect, so loop structure and failure surfacing
        are stepper-independent. The ledger's dispatch stamp is taken
        when an async call RETURNS (the device starts once it has the
        program; the call is the host's time), and before a
        synchronous one (which is dispatch and wait at once)."""
        with self._lock:
            reqs = list(self._slots)
        inf = _Inflight(active, seqs, reqs, t0, mints0)
        st = self.stepper
        is_async = self._steps_async()
        if not is_async:
            self.overlap_ledger.note_dispatch()
        try:
            if is_async:
                inf.handle = st.step_async(active)
            else:
                inf.result = self._device_step(active, seqs)
        except Exception as e:  # noqa: BLE001 — device crash boundary
            inf.exc = e
        if is_async:
            self.overlap_ledger.note_dispatch()
        return inf

    def _collect_with_blame(self, inf: _Inflight, later=None):
        """The overlapped loop's sync point: materialize the in-flight
        step's tokens (or re-raise its deferred failure) and assign
        blame exactly like ``_step_with_blame`` — a failed call
        advanced nothing, so the synchronous probes retry from the
        same state the failed dispatch saw. ``later``: the step
        dispatched behind ``inf`` and still in the air; when ``inf``
        fails it is dropped un-collected BEFORE the probes (it assumed
        an advance that did not happen, and nothing of it has reached
        the host), and the probes run against the slots of ``inf``'s
        mask that still hold their request. Where the stepper holds a
        state a slot (``state_a_slot``) a dispatched step HAS advanced
        what it touched: there are no probes, and every slot of either
        mask is blamed. Returns ``(toks, counts,
        blamed, used_verify, failed)`` in the variable-advance shape."""
        active = inf.active
        try:
            if inf.exc is not None:
                raise inf.exc
            if inf.handle is not None:
                # collect() already materialized host-side — take the
                # array as-is into the (B, 1) shape the emit path wants
                toks = inf.handle.collect()
                return (
                    toks.reshape(-1, 1),
                    np.where(active, 1, 0).astype(np.int64),
                    [],
                    np.zeros(len(active), bool),
                    False,
                )
            toks, counts, used = inf.result
            return toks, counts, [], used, False
        except Exception:  # noqa: BLE001 — device crash boundary
            with self._lock:
                self.counters["step_failures"] += 1
                held = inf.held(self._slots)
        self._step_failed = True
        if later is not None:
            later.drop()
            self._inflight = None
        if inf.handle is not None and getattr(
                self.stepper, "state_a_slot", False):
            # a state a slot: the step ran on the device before its
            # collect raised (and ``later`` behind it), so every slot of
            # either mask holds a state ahead of the tokens it delivered,
            # where pages would only be written again. No probe starts
            # from the state the failed step saw: all of them fail,
            # typed, as a blamed slot does
            if later is not None:
                with self._lock:
                    held = held | later.held(self._slots)
            return (None, None, [int(i) for i in np.flatnonzero(held)],
                    np.zeros(len(active), bool), True)
        return (*self._assign_blame(held, inf.seqs), True)

    def _preempt_phase(self, blocked) -> bool:
        """The overlapped loop's deferred preemption: decided AFTER
        collect (nothing in flight), re-validated against post-collect
        state — an eviction that just freed the capacity the blocked
        request needs makes displacement unnecessary (admission places
        it next call), where the sequential loop would have preempted
        on its earlier, pre-step view."""
        if not self._preemptible:
            return False
        with self._span("serving/preempt"):
            with self._lock:
                free = sum(
                    s is None and i not in self._quarantined
                    for i, s in enumerate(self._slots)
                )
                fits = free >= blocked.n and (
                    not getattr(self.stepper, "paged", False)
                    or self._pages_for_request(blocked)
                    <= self.stepper.available_pages
                )
                preempt = (
                    None if fits else self._pick_victim_locked(blocked)
                )
            if preempt is None:
                return False
            return self._preempt(*preempt)

    def _admit_phase(self, preempt_now: bool):
        """Host scheduling work at the top of an iteration: quarantine
        recycle, admission of queued requests into free slots (page-
        gated when paged), swap-in resumes, the chunked-prefill
        budget, prefill-only exports, completion-group forks. Returns
        ``(progressed, blocked)`` — ``blocked`` is the head-of-line
        request admission could not place (the preemption candidate).
        ``preempt_now``: the sequential loop preempts here; the
        overlapped loop defers to ``_preempt_phase`` after collect."""
        with self._span("serving/admit") as sp:
            progressed, blocked, admitted = self._admit(preempt_now)
            sp.set_metadata(admitted=admitted)
        return progressed, blocked

    def _admit(self, preempt_now: bool):
        now = time.monotonic()
        admitted = []
        paged = getattr(self.stepper, "paged", False)
        page_budget = self.stepper.available_pages if paged else None
        blocked = None  # head-of-line candidate admission could not place
        page_wait = False  # ... and it was pages that it lacked
        preempt = None
        with self._lock:
            self._sched_iters += 1
            for s, until in list(self._quarantined.items()):
                if self._sched_iters >= until:
                    del self._quarantined[s]  # probation served
            free = [
                i for i, slot in enumerate(self._slots)
                if slot is None and i not in self._quarantined
            ]
            taken = 0
            while True:
                req = self._pop_live(now)
                if req is None:
                    break
                if req.n > len(free) - taken:
                    # a completion group needs its n slots TOGETHER
                    # (forks happen the moment prefill finishes, before
                    # the primary emits — that is what keeps completion
                    # j identical to an independent derived-seed
                    # admission); head-of-line FIFO waits for evictions
                    self._queue.appendleft(req)
                    blocked = req
                    break
                if paged:
                    # admission reserves pages: gate on the pool, not
                    # just a free slot, so occupancy is bounded by KV
                    # bytes actually needed. The head-of-line request
                    # WAITS for eviction to free pages (FIFO fairness);
                    # begin_admit's typed PoolExhaustedError is the
                    # backstop for races and shared-page estimates.
                    need = self._pages_for_request(req)
                    if need > page_budget:
                        self._queue.appendleft(req)
                        blocked = req
                        page_wait = True
                        self.counters["page_waits"] += 1
                        if not req.page_waited:
                            req.page_waited = True
                            self.counters["page_wait_requests"] += 1
                        break
                    page_budget -= need
                group = free[taken:taken + req.n]
                taken += req.n
                if req.started is None:  # a resume keeps its stamps
                    req.started = now
                    if self.shed_gate is not None:
                        # queue sojourn (submit -> admission): the
                        # CoDel signal the gate sheds on
                        self.shed_gate.note_delay(now - req.created)
                self._admit_seq += 1
                for j, s in enumerate(group):
                    self._slots[s] = req
                    self._slot_comp[s] = j
                    self._admit_order[s] = self._admit_seq
                    if j > 0:
                        self._awaiting_fork[s] = j
                admitted.append((group[0], req))
            if (
                blocked is not None and self._preemptible
                and preempt_now
            ):
                # a higher-priority arrival blocked on capacity may
                # displace the lowest-priority decodable slot — picked
                # under the lock, swapped outside it (device fetch)
                preempt = self._pick_victim_locked(blocked)
        preempted = False
        if preempt is not None:
            preempted = self._preempt(*preempt)
        # device work outside the lock: submit() must never block on a
        # compile or a step (backpressure replies stay fast under load)
        began = []
        for i, req in admitted:
            if req._swap is not None:
                # a preempted request resuming: restore + re-reserve;
                # the slot is decodable immediately (its prefill ran
                # before the preemption)
                self._resume(i, req)
                continue
            try:
                kw = {"max_new": req.max_new_tokens} if paged else {}
                if req.sampling is not None:
                    kw["sampling"] = req.sampling
                    kw["eos_id"] = req.eos_id
                n0, ta = self._led_total(), time.monotonic()
                began.append(
                    (i, req, self.stepper.begin_admit(i, req.prompt, **kw))
                )
                self._note_mints(req, n0, ta, time.monotonic())
            except Exception as e:  # noqa: BLE001 — admission boundary
                # a prefill crash is attributable by construction (one
                # slot, one request): fail IT typed, keep everything else
                self._fail_admission(i, req, e)
        now = time.monotonic()
        with self._lock:
            for i, req, left in began:
                if self._slots[i] is not req:
                    continue  # stopped underneath us
                if left > 0:
                    self._prefill_left[i] = left
                    self._prefill_fifo.append(i)
                else:
                    req.prefill_finished = now
        progressed = self._spend_prefill_budget() or preempted
        progressed = self._export_prefilled() or progressed
        progressed = self._fork_completions() or progressed
        counts = {
            "iter": self._sched_iters, "active": 0,
            "prefilling": len(self._prefill_left),
            "queue_depth": len(self._queue),
            # the overlapped loop's: 1 if this call dispatched its step
            # behind one in the air; slot-steps its collect discarded
            "ahead": 0, "discarded": 0,
        }
        if paged:
            total = self.stepper.total_pages
            counts["pages_in_use"] = total - self.stepper.free_pages
            counts["pages_total"] = total
            counts["page_waits"] = int(page_wait)
            # a second budget, where window layers have one: the pool of
            # the slots' rings (``pages_*`` stay the budget that grows)
            window = getattr(self.stepper, "window_pages", None)
            if window is not None:
                counts["window_pages_in_use"] = window[0]
                counts["window_pages_total"] = window[1]
        self._iter_counts = counts
        return progressed, blocked, len(admitted)

    def _mask_phase(self, ahead_of: _Inflight | None = None):
        """Deadline-sweep slots that produce no tokens (mid-prefill,
        awaiting-fork) and compute the decode active mask + optional
        per-slot host sequences. Runs immediately before dispatch in
        both loop modes. ``ahead_of``: a step still in the air — the
        mask is then the one its collect WILL leave: a slot whose
        budget that step exhausts (it emits one token a slot) sits
        this one out, as it would once evicted."""
        with self._span("serving/mask"):
            active, seqs = self._mask(ahead_of)
            self._iter_counts["active"] = int(active.sum())
        return active, seqs

    def _mask(self, ahead_of=None):
        now = time.monotonic()
        with self._lock:
            # deadline sweep for slots still mid-prefill AND groups
            # still waiting on their forks (both produce no tokens, so
            # the post-step check never sees them; a fork stalled on
            # pool pressure must time out typed, never wait forever)
            for i, req in enumerate(self._slots):
                if req is None or (
                    i not in self._prefill_left
                    and i not in self._awaiting_fork
                ):
                    continue
                if req._expired(now):
                    self._evict(
                        i,
                        req,
                        DeadlineExceededError(
                            "deadline passed during prefill"
                        ),
                    )
            # slots awaiting their fork — and the primaries they fork
            # FROM — sit this step out: the primary must not emit a
            # token its siblings' forks would then silently inherit
            fork_held = set(self._awaiting_fork)
            for s in self._awaiting_fork:
                req = self._slots[s]
                if req is None:
                    continue
                for i, r in enumerate(self._slots):
                    if r is req and self._slot_comp[i] == 0:
                        fork_held.add(i)
            active = np.array(
                [
                    s is not None and i not in self._prefill_left
                    and i not in fork_held
                    for i, s in enumerate(self._slots)
                ],
                bool,
            )
            if ahead_of is not None:
                for i in np.flatnonzero(
                    active & ahead_of.held(self._slots)
                ):
                    req = self._slots[i]
                    done = len(req.completions[self._slot_comp[i]]) + 1
                    if done >= req.max_new_tokens:
                        active[i] = False
            seqs = None
            if active.any() and getattr(
                self.stepper, "wants_sequences", False
            ):
                # host-side truth per slot: (prompt, emitted-so-far),
                # handed over ZERO-COPY — only this thread mutates the
                # token lists and only after the device call, so the
                # drafter may materialize just the slots it actually
                # searches (throttled slots cost nothing per iteration)
                seqs = [
                    (req.prompt, req.completions[self._slot_comp[i]])
                    if req is not None and active[i]
                    else None
                    for i, req in enumerate(self._slots)
                ]
        return active, seqs

    def _finish_step(self, active, step_t0, mints0, toks, counts,
                     blamed, used_verify, reqs=None) -> bool:
        """Emission/eviction for one collected device step (the former
        tail of the monolithic ``step``): decode-phase mint
        attribution, blame eviction + quarantine, per-token budget /
        EOS / deadline checks in emission order, stream pushes (before
        any eviction they trigger), WFQ charging, speculative
        acceptance counters, and the recorder's iteration line."""
        with self._span("serving/emit") as sp:
            emitted = self._emit(
                active, step_t0, mints0, toks, counts, blamed,
                used_verify, reqs,
            )
            sp.set_metadata(emitted=emitted)
        return True

    def _emit(self, active, step_t0, mints0, toks, counts, blamed,
              used_verify, reqs=None) -> int:
        """``_finish_step``'s body; returns the tokens emitted.
        ``reqs``: the request each slot held when the step was
        dispatched (the overlapped loop's; None = as they are now)."""
        now = time.monotonic()
        if self._led_total() > mints0:
            # a mint landed inside the decode phase: every traced
            # active request was stalled by it — the span lands on
            # each of their timelines (the blast radius, attributed)
            noted = set()
            for i, r in enumerate(self._slots):
                if r is None or not active[i] or id(r) in noted:
                    continue
                noted.add(id(r))
                self._note_mints(r, mints0, step_t0, now)
        emitted_total = 0
        # the queue and the pool as this iteration's admission left
        # them, on the tape beside what the step emitted
        c = self._iter_counts
        pool = {
            "queue_depth": c.get("queue_depth"),
            "pages_in_use": c.get("pages_in_use"),
            "page_waits": c.get("page_waits"),
        }
        with self._emitting():
            if reqs is not None:
                # THE GUARD of the two-deep loop: a slot of this step's
                # mask may have been evicted at the collect of the step
                # before it (EOS, deadline, blame), after this step was
                # dispatched, and even given to a new tenant since. The
                # step's token for it is discarded, here and in the
                # stepper's collect (lengths and sample positions stay
                # the new tenant's). What the discarded slot-step wrote
                # on the device is harmless by device order: its page
                # write lands inside the old tenant's reservation (a
                # slot that ends by EOS or deadline is short of
                # max_new), pages freed at the eviction are written
                # again only by programs dispatched AFTER this step
                # (and no position is read before its tenant has
                # written it), and the context row likewise.
                held = _still_held(active, reqs, self._slots)
                discarded = int(active.sum()) - int(held.sum())
                self.counters["discarded_slot_steps"] += discarded
                self._iter_counts["discarded"] = discarded
                active = held
            n_active = int(active.sum())
            self.counters["steps"] += 1
            self.counters["occupancy_sum"] += n_active
            for i in blamed:
                req = self._slots[i]
                if req is None:
                    continue  # stopped underneath the blame probes
                if self.recorder is not None:
                    # the black-box line a post-mortem reads: WHICH
                    # slot/request the failed step was pinned on
                    self.recorder.record(
                        "scheduler.blame", slot=i, request_id=req.id,
                        iter=self._sched_iters,
                        probes=self.counters["blame_probes"],
                    )
                if req.trace is not None:
                    # the blame window (failed step + probes) on the
                    # culprit's own ledger — request_spans turns it
                    # into a scheduler.blame span
                    req.events.append({
                        "name": "scheduler.blame",
                        "t0": step_t0, "t1": now, "slot": i,
                    })
                self._quarantine_locked(i)
                self._evict(
                    i,
                    req,
                    InternalError(
                        f"device step failed and was blamed on this "
                        f"request (slot {i}); slot quarantined for "
                        f"{self.quarantine_steps} iterations"
                    ),
                )
            if toks is None:
                if self.recorder is not None:
                    self.recorder.record(
                        "scheduler.iteration", iter=self._sched_iters,
                        active=n_active, emitted=0, blamed=blamed,
                        **pool,
                    )
                return 0  # every active slot was blamed this round
            blamed_set = set(blamed)
            for i, req in enumerate(self._slots):
                if req is None or not active[i] or i in blamed_set:
                    continue
                # variable advance: a slot emits 1..w tokens per
                # iteration (speculative windows), so every budget /
                # EOS / deadline check runs PER EMITTED TOKEN, in
                # emission order — a window's tail past the first
                # finish/expiry condition is never emitted
                req.iterations += 1
                comp = req.completions[self._slot_comp[i]]
                emitted = 0
                new_toks = []
                pending_evict = _NO_EVICT  # deferred past the chunk push
                for tok in np.atleast_1d(toks[i])[: int(counts[i])]:
                    tok = int(tok)
                    comp.append(tok)
                    new_toks.append(tok)
                    emitted += 1
                    if req.first_token is None:
                        req.first_token = now
                    self.counters["tokens_generated"] += 1
                    finished = (
                        len(comp) >= req.max_new_tokens
                        or (req.eos_id is not None and tok == req.eos_id)
                    )
                    if finished:
                        pending_evict = None
                        break
                    if req._expired(now):
                        pending_evict = DeadlineExceededError(
                            f"deadline passed after "
                            f"{len(req.tokens)} tokens"
                        )
                        break
                if req.stream and new_toks:
                    # the streaming push happens BEFORE any eviction
                    # this iteration triggers: _finish's terminal
                    # sentinel must never overtake the final tokens
                    self.counters["streamed_chunks"] += 1
                    req._push_chunk(new_toks)
                    if req._sink is not None:
                        self._stream_wakes.add(req._sink.wake)
                if pending_evict is not _NO_EVICT:
                    self._evict(i, req, pending_evict)
                emitted_total += emitted
                if self.qos is not None and emitted:
                    # WFQ service accounting: decode tokens actually
                    # generated, normalized by the tenant's weight
                    self._queue.charge(req.tenant, emitted)
                if used_verify[i]:
                    self.counters["spec_windows"] += 1
                    self.counters["spec_tokens"] += emitted
                    # the window's last token is the target's
                    # correction; everything before it came from the
                    # draft — attribution for the acceptance counters
                    self.counters["spec_draft_accepted"] += max(
                        0, min(emitted, int(counts[i]) - 1)
                    )
                    self._spec_windows[i] += 1
                    self._spec_emitted[i] += emitted
        if self.recorder is not None:
            # one black-box line per WORKING iteration (idle loops
            # record nothing): what the slot bank did this tick
            self.recorder.record(
                "scheduler.iteration", iter=self._sched_iters,
                active=n_active, emitted=emitted_total,
                spec=bool(used_verify.any()),
                blamed=blamed if blamed else None, **pool,
            )
        return emitted_total

    # -- disaggregated prefill export ---------------------------------------

    def _export_prefilled(self) -> bool:
        """Complete every ``prefill_only`` request whose prefill just
        finished: fetch the slot's state through ``stepper.swap_out``
        (the SAME host format QoS preemption rides — the disagg
        transfer hop serializes exactly this dict), park it on
        ``req.export``, and free the slot. Runs BEFORE the decode
        active mask is computed, so a prefill-only slot never takes a
        decode step — the whole point of the prefill role.

        Failure semantics mirror ``_preempt``'s: the device fetch runs
        outside the lock; a failed swap-out fails ONLY this request,
        typed (a ``ServingError`` passes through as itself, anything
        else becomes ``internal``), and the recorder names the
        exception class."""
        import copy

        with self._lock:
            ready = [
                (i, req)
                for i, req in enumerate(self._slots)
                if req is not None and req.prefill_only
                and i not in self._prefill_left
            ]
        progressed = False
        for i, req in ready:
            try:
                state = self.stepper.swap_out(i)  # device fetch
            except Exception as e:  # noqa: BLE001 — export boundary
                err = (
                    copy.copy(e)
                    if isinstance(e, ServingError)
                    else InternalError(
                        f"prefill export failed for this request: {e!r}"
                    )
                )
                with self._lock:
                    self.counters["export_failures"] += 1
                    self._record_swap_error("export", i, req, e)
                    if self._slots[i] is req:
                        self._evict(i, req, err)
                progressed = True
                continue
            with self._lock:
                if self._slots[i] is not req:
                    continue  # stopped underneath the fetch
                req.export = state
                self.counters["exports"] += 1
                self._evict(i, req, None)
            progressed = True
        return progressed

    # -- preemption by KV swap (multi-tenant QoS) ---------------------------

    def _record_swap_error(self, op, slot, req, exc):
        """The swap paths' sibling of the engine's
        ``_record_prefix_error``: every swallowed swap/restore failure
        leaves its EXCEPTION CLASS on the tape — a swap path failing
        every call must not look identical to a quiet one from the
        counters alone. Caller holds the lock."""
        if self.recorder is not None:
            self.recorder.record(
                "qos.swap_error", op=op, slot=slot,
                request_id=req.id, tenant=req.tenant,
                error=type(exc).__name__, detail=repr(exc)[:200],
            )

    def _pick_victim_locked(self, blocked):
        """The slot a blocked higher-priority arrival may displace:
        DECODING (not mid-prefill, not part of a completion group),
        strictly lower priority than ``blocked``, preemption budget
        not exhausted (``qos.max_preemptions`` — the livelock bound:
        a request displaced that many times becomes immune), and
        short enough that its context row round-trips the swap.
        Among candidates: lowest priority first, then fewest emitted
        tokens (cheapest swap, least work parked). Caller holds the
        lock. Returns ``(slot, request)`` or None."""
        best = None
        max_len = self.stepper.max_len
        for i, req in enumerate(self._slots):
            if req is None or i in self._prefill_left:
                continue
            if req.n > 1 or i in self._awaiting_fork:
                continue  # completion groups are never preempted
            if req.priority >= blocked.priority:
                continue
            if req.preemptions >= self.qos.max_preemptions:
                continue  # immune: nothing livelocks
            if req.prompt.size + len(req.tokens) >= max_len:
                continue  # context cannot round-trip the prompt row
            key = (req.priority, len(req.tokens), i)
            if best is None or key < best[0]:
                best = (key, i, req)
        if best is None:
            return None
        return best[1], best[2]

    def _preempt(self, slot, vreq) -> bool:
        """Swap the victim out (device->host fetch OUTSIDE the lock,
        like every other device call), free its slot and pages, and
        re-queue it at the FRONT of its class with the swap state
        riding the request. A failed swap-out ABORTS the preemption —
        the ``kv.swap`` seam fires before any state changes, so the
        victim keeps decoding untouched — and the recorder names the
        exception class (a silently failing swap path must not look
        like a quiet one)."""
        try:
            state = self.stepper.swap_out(slot)
        except Exception as e:  # noqa: BLE001 — preemption is optional
            with self._lock:
                self.counters["preempt_aborted"] += 1
                self._record_swap_error("swap_out", slot, vreq, e)
            return False
        with self._lock:
            if self._slots[slot] is not vreq:
                return False  # stopped/evicted underneath the fetch
            vreq._swap = state
            vreq.preemptions += 1
            self.counters["preemptions"] += 1
            self.counters["swapped_tokens"] += int(state["len"])
            self._tenant_counter(
                "serving_preemptions", vreq.tenant
            ).inc()
            self._tenant_counter(
                "serving_swapped_tokens", vreq.tenant
            ).inc(int(state["len"]))
            self._slots[slot] = None
            self.stepper.release(slot)  # pages freed; host state rides req
            self._queue.appendleft(vreq)
            if self.recorder is not None:
                self.recorder.record(
                    "qos.preempt", slot=slot, request_id=vreq.id,
                    tenant=vreq.tenant, priority=vreq.priority,
                    tokens=int(state["len"]),
                    preemptions=vreq.preemptions,
                )
        self._work.set()
        return True

    def _resume(self, i, req):
        """Swap a preempted request back in: re-reserve + restore
        (``stepper.swap_in``); the slot is decodable immediately.
        Failure semantics: a failed swap-in fails ONLY this request,
        typed — a ``ServingError`` (notably ``PoolExhaustedError``)
        passes through as itself so pool pressure stays retriable
        ``overloaded``, anything else becomes ``internal`` — and the
        recorder names the exception class. The scheduler never
        wedges on a failed restore."""
        import copy

        mints0, t0 = self._led_total(), time.monotonic()
        try:
            self.stepper.swap_in(
                i, req._swap,
                max_new=req.max_new_tokens - len(req.tokens),
            )
            # the r16 stall class: a swap-restore bucket compiling on
            # the resume path — if it happens to a traced request, the
            # timeline says so
            self._note_mints(req, mints0, t0, time.monotonic())
        except Exception as e:  # noqa: BLE001 — admission boundary
            err = (
                copy.copy(e)
                if isinstance(e, ServingError)
                else InternalError(
                    f"swap-in failed for this request: {e!r}"
                )
            )
            with self._lock:
                self.counters["swap_in_failures"] += 1
                self._record_swap_error("swap_in", i, req, e)
                if self._slots[i] is req:
                    self._evict(i, req, err)
            return
        with self._lock:
            if self._slots[i] is not req:
                return  # stopped underneath us
            req._swap = None
            if req.prefill_finished is None:
                # a WIRE-resumed request (disagg transfer) was
                # prefilled on another engine: its decode phase starts
                # here, so the local timeline needs the boundary stamp
                req.prefill_finished = time.monotonic()
            self.counters["resumes"] += 1
            if self.recorder is not None:
                self.recorder.record(
                    "qos.resume", slot=i, request_id=req.id,
                    tenant=req.tenant, priority=req.priority,
                    tokens=len(req.tokens),
                )

    # -- blame assignment ----------------------------------------------------

    def _device_step(self, active, seqs):
        """One device advance, normalized to the variable-advance
        shape: ``(toks (B, w), counts (B,), used_verify (B,))``. Plain
        steppers advance every active slot exactly one token (w = 1);
        speculative steppers route through ``spec_step`` (draft ->
        verify -> 1..k+1 tokens per slot). ``used_verify`` is per-slot
        so the acceptance ledger never counts a plain-step-fallback
        advance as a verify window."""
        st = self.stepper
        if getattr(st, "speculative", False):
            toks, counts, used = st.spec_step(active, seqs)
            return (
                np.asarray(toks),
                np.asarray(counts),
                np.asarray(active, bool) & bool(used),
            )
        toks = st.step(active)
        if not isinstance(toks, np.ndarray):
            # real steppers collect() host-side already; only fakes
            # handing back lists/device arrays need the copy
            toks = np.asarray(toks)
        return (
            toks.reshape(-1, 1),
            np.where(active, 1, 0).astype(np.int64),
            np.zeros(len(active), bool),
        )

    def _step_with_blame(self, active, seqs=None):
        """Advance the active slots one window, surviving a poison
        request: when the device step (plain decode OR speculative
        verify — both crash boundaries look identical from here) raises,
        retry with the most-recently-admitted active slot masked out
        (the prime suspect — established streams were stepping fine
        before it arrived); if the retry fails too, bisect the active
        set until the minimal culpable slots are isolated. Every
        non-blamed slot advances EXACTLY one window (failed calls
        advance nothing — the injection seams fire before device work,
        a real XLA failure aborts the whole program, and speculative
        retries re-verify the SAME cached draft proposals), so
        surviving streams stay token-identical to their solo decode.
        Returns ``(toks, counts, blamed, used_verify)``; ``toks`` is
        None when nothing advanced. An engine-level failure (every
        probe failing) blames all active slots — the supervisor's
        restart budget is the backstop for a stepper that is truly
        dead, not poisoned."""
        try:
            toks, counts, used = self._device_step(active, seqs)
            return toks, counts, [], used
        except Exception:  # noqa: BLE001 — device crash boundary
            with self._lock:
                self.counters["step_failures"] += 1
        return self._assign_blame(active, seqs)

    def _assign_blame(self, active, seqs):
        """The probe cascade after a failed device step (shared by the
        sequential ``_step_with_blame`` and the overlapped
        ``_collect_with_blame`` — by the time either gets here the
        failed call has advanced nothing, so the probes are ordinary
        synchronous steps): newest-admission masked retry, then
        bisection. Same return shape as ``_step_with_blame``."""
        idxs = [int(i) for i in np.flatnonzero(active)]
        if len(idxs) <= 1:
            # alone in the batch = culpable by elimination (none: every
            # slot of the failed step's mask has left since)
            return None, None, idxs, np.zeros(len(active), bool)
        with self._lock:
            suspect = max(idxs, key=lambda i: self._admit_order[i])
        retry = active.copy()
        retry[suspect] = False
        try:
            with self._lock:
                self.counters["blame_probes"] += 1
            toks, counts, used = self._device_step(retry, seqs)
            return toks, counts, [suspect], used
        except Exception:  # noqa: BLE001
            pass
        # the newest admission alone is not the story: bisect the whole
        # active set (nothing has advanced yet — all probes so far failed)
        got: dict[int, tuple[np.ndarray, int, bool]] = {}
        blamed: list[int] = []

        def probe(group):
            mask = np.zeros_like(active)
            mask[group] = True
            try:
                with self._lock:
                    self.counters["blame_probes"] += 1
                t, cnt, u = self._device_step(mask, seqs)
            except Exception:  # noqa: BLE001
                if len(group) == 1:
                    blamed.append(group[0])
                    return
                half = len(group) // 2
                probe(group[:half])
                probe(group[half:])
                return
            for i in group:
                got[i] = (np.atleast_1d(t[i]), int(cnt[i]), bool(u[i]))

        probe(idxs)
        if not got:
            return None, None, blamed, np.zeros(len(active), bool)
        w = max(row.shape[0] for row, _, _ in got.values())
        toks = np.zeros((len(active), w), dtype=np.int64)
        counts = np.zeros(len(active), dtype=np.int64)
        used = np.zeros(len(active), bool)
        for i, (row, cnt, u) in got.items():
            toks[i, : row.shape[0]] = row
            counts[i] = cnt
            used[i] = u
        return toks, counts, blamed, used

    def _quarantine_locked(self, i):
        """Send slot ``i`` to probation. Caller holds the lock."""
        self.counters["quarantines"] += 1
        self._quarantined[i] = self._sched_iters + self.quarantine_steps
        if self.recorder is not None:
            self.recorder.record(
                "scheduler.quarantine", slot=i,
                until_iter=self._quarantined[i],
            )

    def _fail_admission(self, i, req, exc):
        """A begin_admit/prefill_chunk crash: fail the (attributable)
        request typed and free the slot. A ``ServingError`` (notably
        ``PoolExhaustedError`` — typed retriable ``overloaded`` with a
        ``retry_after_ms`` hint) passes through AS ITSELF: capacity
        pressure must reach the client as backpressure, not be
        laundered into ``internal``."""
        import copy

        err = (
            # a fresh copy per request: an injected seam re-raises ONE
            # instance, and tracebacks must not be shared across
            # requests (same discipline as stop()'s per-request fail())
            copy.copy(exc)
            if isinstance(exc, ServingError)
            else InternalError(
                f"prefill failed for this request: {exc!r}"
            )
        )
        with self._lock:
            self.counters["prefill_failures"] += 1
            if self.recorder is not None:
                self.recorder.record(
                    "scheduler.prefill_failure", slot=i,
                    request_id=req.id, error=repr(exc)[:200],
                )
            if self._slots[i] is req:
                self._evict(i, req, err)

    def _spend_prefill_budget(self) -> bool:
        """Advance mid-prefill slots, oldest admission first, spending
        at most ``prefill_chunk`` prompt tokens this iteration (no cap
        when None). Returns True when any prefill progressed. Device
        calls run outside the lock; only this (engine) thread mutates
        the prefill state, so the unlocked reads between chunks are
        safe — the lock guards concurrent ``stats()``/``stop()``."""
        budget = self.prefill_chunk
        spent = 0
        progressed = False
        while True:
            with self._lock:
                if not self._prefill_fifo or (
                    budget is not None and spent >= budget
                ):
                    return progressed
                i = self._prefill_fifo[0]
                req = self._slots[i]
                left = self._prefill_left[i]
                give = (
                    left if budget is None else min(left, budget - spent)
                )
            mints0 = self._led_total()
            chunk_t0 = time.monotonic()
            try:
                new_left = self.stepper.prefill_chunk(i, give)  # device work
            except Exception as e:  # noqa: BLE001 — admission boundary
                self._fail_admission(i, req, e)
                progressed = True  # the queue can move into this slot now
                continue
            now = time.monotonic()
            self._note_mints(req, mints0, chunk_t0, now)
            with self._lock:
                if self._slots[i] is not req:
                    continue  # stopped/evicted underneath us
                consumed = left - new_left
                req.prefill_chunks += 1
                if req.trace is not None:
                    req.events.append({
                        "name": "serving.prefill_chunk",
                        "t0": chunk_t0, "t1": now,
                        "tokens": int(consumed), "slot": i,
                    })
                if consumed <= 0 and new_left > 0:
                    # a stepper that consumes nothing would spin this
                    # loop forever — fail loudly (the engine loop's
                    # crash boundary completes every pending request)
                    raise RuntimeError(
                        f"stepper made no prefill progress on slot {i}"
                    )
                spent += consumed
                progressed = progressed or consumed > 0
                self.counters["prefill_chunks"] += 1
                self.counters["prefill_tokens"] += consumed
                self._prefill_left[i] = new_left
                if new_left == 0:
                    self._drop_prefill(i)
                    req.prefill_finished = now

    def _drop_prefill(self, i):
        """Leave the prefilling state. Caller holds the lock."""
        self._prefill_left.pop(i, None)
        try:
            self._prefill_fifo.remove(i)
        except ValueError:
            pass

    def _fork_completions(self) -> bool:
        """CoW-fork a completion group's reserved slots the moment its
        primary finishes prefill — BEFORE the primary emits a single
        token, so every completion's stream starts at emitted position
        0 under its own derived seed (completion j is token-identical
        to an independent admission with ``seed_for_completion(seed,
        j)``). Device work outside the lock.

        Failure semantics: POOL EXHAUSTION at fork time is capacity
        pressure, not a fault — admission's page gating is advisory
        (the fork's pages are not physically reserved through a
        multi-iteration prefill), so a raced-away pool makes the group
        WAIT (primary stays held, the fork retries next iteration as
        evictions free pages — the same head-of-line discipline as
        page-gated admission; the deadline sweep bounds the wait).
        Any OTHER fork failure fails the WHOLE group typed."""
        if not self._awaiting_fork:
            return False
        with self._lock:
            ready = []
            for s, j in list(self._awaiting_fork.items()):
                req = self._slots[s]
                if req is None:
                    self._awaiting_fork.pop(s)
                    continue
                primary = next(
                    (i for i, r in enumerate(self._slots)
                     if r is req and self._slot_comp[i] == 0),
                    None,
                )
                if primary is None:
                    # the primary died (its failure already completed
                    # the group) — clean the orphaned reservation
                    self._awaiting_fork.pop(s)
                    self._slots[s] = None
                    self.stepper.release(s)
                    continue
                if primary not in self._prefill_left:
                    ready.append((primary, s, j, req))
        progressed = False
        for primary, s, j, req in ready:
            with self._lock:
                if (
                    self._slots[s] is not req
                    or self._slots[primary] is not req
                ):
                    # a sibling's failure already evicted this group —
                    # never fork from a released primary (and never
                    # record a second, mistyped failure for it)
                    continue
            mints0, t0 = self._led_total(), time.monotonic()
            try:
                self.stepper.fork_slot(
                    primary, s, max_new=req.max_new_tokens, completion=j
                )
                self._note_mints(req, mints0, t0, time.monotonic())
            except OverloadedError:
                # pool pressure: leave the reservation in place and
                # retry next iteration (evictions free pages); the
                # whole group keeps waiting un-started
                continue
            except Exception as e:  # noqa: BLE001 — admission boundary
                self._fail_admission(s, req, e)
                continue
            progressed = True
            with self._lock:
                if self._slots[s] is req:
                    self._awaiting_fork.pop(s, None)
                    self.forked_slots.inc()
        return progressed

    def _pop_live(self, now) -> ServeRequest | None:
        """Next queued request whose deadline has not already expired;
        expired ones complete immediately with DeadlineExceededError.
        Caller holds the lock."""
        while self._queue:
            req = self._queue.popleft()
            if req._expired(now):
                self.counters["deadline_exceeded"] += 1
                if req._swap is not None:
                    # preemption pairing: a swapped request dying typed
                    # in the queue is its swap-out's terminal partner
                    self.counters["swapped_failed"] += 1
                self._finish_request(
                    req, DeadlineExceededError("deadline expired in queue")
                )
                continue
            return req
        return None

    def _evict(self, slot_idx, req, error):
        """Free a slot and complete its request (or, for a completion
        group, one completion of it). Caller holds the lock.

        Group semantics ("all complete or all typed"): a clean finish
        of one completion releases only its slot — the request finishes
        when its LAST completion does; any typed error releases every
        sibling slot immediately and fails the whole request with it.
        """
        self._slots[slot_idx] = None
        self._drop_prefill(slot_idx)
        self._awaiting_fork.pop(slot_idx, None)
        self.stepper.release(slot_idx)
        if error is not None:
            for i, r in enumerate(self._slots):
                if r is req:  # group siblings die with the request
                    self._slots[i] = None
                    self._drop_prefill(i)
                    self._awaiting_fork.pop(i, None)
                    self.stepper.release(i)
            if isinstance(error, InternalError):
                self.counters["internal_errors"] += 1
            elif isinstance(error, OverloadedError):
                self.counters["pool_exhausted"] += 1
            else:
                self.counters["deadline_exceeded"] += 1
            self._finish_request(req, error)
            return
        if any(r is req for r in self._slots):
            return  # sibling completions still decoding / forking
        self.counters["completed"] += 1
        self._finish_request(req, None)

    def _finish_request(self, req, error):
        """Complete ``req`` (caller holds the lock) and wake the sender
        of its stream for the sentinel: with the iteration's chunks
        where ``_emit`` closes, at once anywhere else (a deadline, a
        stop, a restart, the watchdog)."""
        req._finish(error)
        if req._sink is not None:
            if self._stream_wakes is None:
                req._sink.wake()
            else:
                self._stream_wakes.add(req._sink.wake)

    @contextlib.contextmanager
    def _emitting(self):
        """The lock across one emission, and behind it ONE wake of each
        stream sender the emission handed anything to: a server's
        streams share a sender, so an iteration wakes one thread where
        a FIFO a request woke one a streaming slot."""
        wakes = set()
        with self._lock:
            self._stream_wakes = wakes
            try:
                yield
            finally:
                self._stream_wakes = None
        for wake in wakes:
            wake()

    # -- drain / shutdown ---------------------------------------------------

    def drain(self):
        """Stop admitting NEW requests; queued and in-flight ones keep
        running (the engine loop calls ``step`` until ``idle``)."""
        with self._lock:
            self._draining = True
        self._work.set()

    def stop(self, error: ServingError | None = None):
        """Hard stop: fail everything still queued or in flight.
        ``error``: the typed failure handed to each pending request —
        default ``EngineStoppedError`` (a deliberate shutdown); the
        engine supervisor passes ``InternalError`` so requests aborted
        by a scheduler crash/restart are distinguishable from a drain."""
        proto = error if error is not None else EngineStoppedError(
            "engine stopped"
        )

        def fail():  # per-request instance: tracebacks must not be shared
            return type(proto)(*proto.args)

        with self._lock:
            self._draining = self._stopped = True
            # an in-flight step's results die with the requests: the
            # handle is dropped UNCOLLECTED (every slot is released
            # below, re-admission re-initializes per-slot state, and a
            # supervisor restart rebuilds the stepper outright)
            inflight, self._inflight = self._inflight, None
            if inflight is not None:
                inflight.drop()
            self.overlap_ledger.discard()
            while self._queue:
                req = self._queue.popleft()
                if req._swap is not None:
                    # a restart/stop racing a swapped-out request: the
                    # typed failure below drops its host swap state
                    # with it (pairing: preemptions == resumes +
                    # swap_in_failures + swapped_failed)
                    self.counters["swapped_failed"] += 1
                self._finish_request(req, fail())
            self._prefill_left.clear()
            self._prefill_fifo.clear()
            self._awaiting_fork.clear()
            failed = set()  # a completion group holds several slots
            for i, req in enumerate(self._slots):
                if req is not None:
                    self._slots[i] = None
                    self.stepper.release(i)
                    if id(req) not in failed:
                        failed.add(id(req))
                        self._finish_request(req, fail())
        self._work.set()

    # -- introspection ------------------------------------------------------

    @property
    def idle(self) -> bool:
        with self._lock:
            return (
                self._inflight is None
                and not self._queue
                and all(s is None for s in self._slots)
            )

    def inflight_snapshot(self) -> list[dict]:
        """The in-flight request table for a post-mortem bundle: every
        queued and slotted request with its trace id (when traced) —
        the "who was in the air when it went down" page. JSON-able and
        cheap (one pass under the lock)."""

        def row(req, state, slot=None):
            return {
                "request_id": req.id,
                "state": state,
                "slot": slot,
                "tenant": req.tenant,
                "priority": req.priority,
                "preemptions": req.preemptions,
                "prompt_len": int(req.prompt.size),
                "max_new_tokens": req.max_new_tokens,
                "tokens_emitted": sum(len(c) for c in req.completions),
                "trace_id": (
                    None if req.trace is None else req.trace.trace_id
                ),
            }

        with self._lock:
            out = [
                row(r, "swapped" if r._swap is not None else "queued")
                for r in self._queue
            ]
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                state = (
                    "prefilling" if i in self._prefill_left else "decoding"
                )
                out.append(row(req, state, slot=i))
            return out

    def load(self) -> dict:
        """Cheap occupancy snapshot for the health surface (polled by
        load balancers / the fleet router every few hundred ms — must
        not build the full ``stats()`` dict): queued + active work and
        the capacity bounds a router needs to account in-flight load."""
        with self._lock:
            return {
                "queue_depth": len(self._queue),
                "queue_capacity": self.queue_capacity,
                "active_slots": sum(s is not None for s in self._slots),
                "prefilling_slots": len(self._prefill_left),
                "num_slots": len(self._slots),
                # decode geometry ("tp:N" / None): rides health so the
                # fleet router and autoscaler see per-replica meshes
                "mesh": getattr(self.stepper, "mesh_spec", None),
            }

    def stats(self) -> dict:
        with self._lock:
            active = sum(s is not None for s in self._slots)
            out = dict(self.counters)
            out["sampled_requests"] = self.sampled_requests.value
            out["forked_slots"] = self.forked_slots.value
            out["queue_depth"] = len(self._queue)
            out["active_slots"] = active
            out["prefilling_slots"] = len(self._prefill_left)
            out["quarantined_slots"] = len(self._quarantined)
            out["num_slots"] = len(self._slots)
            out["mesh"] = getattr(self.stepper, "mesh_spec", None)
            out["prefill_chunk"] = self.prefill_chunk
            out["draining"] = self._draining
        steps = out["steps"]
        out["mean_batch_occupancy"] = (
            out["occupancy_sum"] / steps if steps else 0.0
        )
        if self.qos is not None:
            out["qos"] = {
                "enabled": True,
                "preempt": self.qos.preempt,
                "max_preemptions": self.qos.max_preemptions,
                "tenant_service": self._queue.service_snapshot(),
            }
        else:
            out["qos"] = {"enabled": False}
        out["overlap"] = self.overlap_stats()
        out["loop"] = self.loop_stats()
        st = self.stepper
        if getattr(st, "speculative", False):
            drafted = int(getattr(st, "spec_drafted_tokens", 0))
            accepted = out["spec_draft_accepted"]
            windows = out["spec_windows"]
            out["speculative"] = {
                "enabled": True,
                "draft_source": st.drafter.name,
                "draft_k": st.draft_k,
                "verify_steps": int(st.spec_verify_steps),
                "fallback_steps": int(st.spec_fallback_steps),
                "draft_failures": int(
                    getattr(st, "spec_draft_failures", 0)
                ),
                "windows": windows,
                "drafted_tokens": drafted,
                "accepted_draft_tokens": accepted,
                "rejected_draft_tokens": max(0, drafted - accepted),
                "emitted_tokens": out["spec_tokens"],
                "mean_tokens_per_window": (
                    round(out["spec_tokens"] / windows, 3)
                    if windows else 0.0
                ),
                "per_slot_acceptance": [
                    round(float(e) / w, 3) if w else None
                    for e, w in zip(self._spec_emitted, self._spec_windows)
                ],
            }
        else:
            out["speculative"] = {"enabled": False}
        return out

    def overlap_stats(self) -> dict:
        """The ``overlap`` block of ``stats()`` and ``health()``: the
        bubble ledger, and how deep the overlapped loop ran —
        ``ahead_steps`` of ``steps`` were dispatched with a step in
        the air, ``drained`` counts the calls that collected first by
        reason (``_lookahead_refusal``), ``discarded_slot_steps`` the
        slot-steps thrown away because the slot's tenant left between
        dispatch and collect."""
        c = self.counters
        return {
            "enabled": self.overlap,
            **self.overlap_ledger.snapshot(),
            "steps": c["steps"],
            "ahead_steps": c["ahead_steps"],
            "drained": {
                r: c[f"drained_{r}"] for r in _DRAIN_REASONS
                if c[f"drained_{r}"]
            },
            "discarded_slot_steps": c["discarded_slot_steps"],
        }

    def loop_stats(self) -> dict:
        """The ``loop`` block of ``stats()`` and ``health()``: how the
        scheduler thread spent the time between its working iterations,
        kept without a profiler. ``waits`` / ``wait_s`` are its parks in
        ``wait_for_work``, ``idle_passes`` the ``step()`` calls over an
        idle bank or that made no progress, ``iterations`` the working
        ones; ``longest_iter_s`` the longest of those and
        ``longest_iter_cpu_s`` this thread's CPU time inside it (near 0:
        it stood still in there; near ``longest_iter_s``: it was busy),
        ``longest_gap_s`` the longest gap between two of them while work
        was held at the first one's close, ``stalls`` the gaps over
        ``STALL_GAP_S`` (each a ``scheduler.stall`` recorder line and a
        WARNING: ``_note_gap``)."""
        out = dict(self._loop)
        for key in ("wait_s", "longest_gap_s", "longest_iter_s",
                    "longest_iter_cpu_s"):
            out[key] = round(out[key], 4)
        return out

    def wait_for_work(self, timeout=0.05):
        """Engine-loop helper: park until a submit/drain signal."""
        t0 = time.monotonic()
        with self._span("serving/wait") as sp:
            woken = self._work.wait(timeout)
            self._work.clear()
            sp.set_metadata(
                woken=int(woken), queue_depth=len(self._queue),
                held=sum(s is not None for s in self._slots),
            )
        self._loop["waits"] += 1
        self._loop["wait_s"] += time.monotonic() - t0


class _Ticket:
    """Completion handle for one windowed-batch item."""

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._error = None

    def _finish(self, result=None, error=None):
        self._result, self._error = result, error
        self._done.set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("predict batch still running")
        if self._error is not None:
            raise self._error
        return self._result


class WindowedBatcher:
    """Size/timeout-windowed batcher for batch scoring: items accumulate
    until ``max_batch`` rows are waiting or ``max_wait`` elapsed since
    the first, then ``run_batch`` scores them as one array and each
    ticket receives its row span. The ``ModelPredictor`` face of the
    server — decode gets iteration-level batching, scoring gets windows.
    """

    def __init__(self, run_batch, max_batch=64, max_wait=0.005,
                 queue_capacity=256):
        self.run_batch = run_batch
        self.max_batch = int(max_batch)
        self.max_wait = float(max_wait)
        self.queue_capacity = int(queue_capacity)
        self._items: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = False
        self._thread = None

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="windowed-batcher", daemon=True
            )
            self._thread.start()
        return self

    def submit(self, x) -> _Ticket:
        x = np.asarray(x)
        if x.ndim < 1:
            raise ValueError("predict input must be at least 1-D (rows)")
        if len(x) > self.queue_capacity:
            # a request that can NEVER fit is a caller error, not
            # transient backpressure — OverloadedError would send the
            # client into a retry loop that cannot succeed
            raise ValueError(
                f"predict request of {len(x)} rows exceeds the queue "
                f"capacity ({self.queue_capacity})"
            )
        ticket = _Ticket()
        with self._lock:
            if self._stop:
                raise EngineStoppedError("predict batcher stopped")
            depth = sum(len(item) for item, _ in self._items)
            if depth + len(x) > self.queue_capacity:
                raise OverloadedError(
                    f"predict queue full ({self.queue_capacity} rows)"
                )
            self._items.append((x, ticket))
        self._work.set()
        return ticket

    def _loop(self):
        while True:
            self._work.wait(0.05)
            self._work.clear()
            batch = self._collect()
            if batch is None:
                if self._stop and not self._items:
                    return
                continue
            xs, tickets = batch
            try:
                ys = self.run_batch(np.concatenate(xs, axis=0))
            except Exception as e:  # noqa: BLE001 — per-window boundary
                for _, t in zip(xs, tickets):
                    t._finish(error=e)
                continue
            off = 0
            for x, t in zip(xs, tickets):
                t._finish(result=np.asarray(ys[off : off + len(x)]))
                off += len(x)

    def _collect(self):
        """Wait out the window from the first queued item, then take up
        to ``max_batch`` rows (whole items only; one oversized item runs
        alone rather than splitting a request across windows)."""
        with self._lock:
            if not self._items:
                return None
        deadline = time.monotonic() + self.max_wait
        while time.monotonic() < deadline:
            with self._lock:
                if (
                    sum(len(i) for i, _ in self._items) >= self.max_batch
                    or self._stop
                ):
                    break
            time.sleep(self.max_wait / 10)
        xs, tickets, rows = [], [], 0
        with self._lock:
            while self._items:
                x, t = self._items[0]
                if xs and rows + len(x) > self.max_batch:
                    break
                self._items.popleft()
                xs.append(x)
                tickets.append(t)
                rows += len(x)
        return (xs, tickets) if xs else None

    def close(self):
        with self._lock:
            self._stop = True
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
