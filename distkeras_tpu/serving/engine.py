"""Online inference engine: the device face of the serving runtime.

``DecodeStepper`` turns ``CachedSequenceGenerator``'s one-shot compiled
decode into an ITERATION-LEVEL program: a fixed (num_slots, seq_len)
slot bank where every call to ``step`` advances each active slot by one
token against persistent per-stage K/V caches, and admission prefills a
single slot's prompt without disturbing its neighbours. Admission is
INCREMENTAL: ``begin_admit`` writes the prompt row (and restores any
``prefix_cache`` hit's K/V), then ``prefill_chunk`` advances the
remaining prefix a bounded chunk at a time, so the scheduler can
interleave prefill with decode steps (Sarathi-style chunked prefill)
instead of stalling every active slot behind one long prompt. The
batch shape is static — XLA compiles the step once per sampling config
and the prefill once per prompt-length bucket plus once per
chunk-length bucket (powers of two, like the ragged generator's
bucketed scan keys) — so continuous batching churns the logical batch
composition at zero recompiles.

A block's arithmetic is written once, in ``models/``
(``TransformerBlock.forward``, ``LatentMoEBlock.forward``); a program
here hands it a cache callable (``attend`` / ``exchange``) and owns
nothing else of the block: where the new rows are written (per-row
positions, pages, frozen slots), what is gathered or read in place, the
mask. One callable a program form (per-slot positions: step and
verify; one slot's chunk) and cache (dense bank, ``"kv"`` pages, latent
pages), one walk a form for all of them. Family parsing, param-group
unpacking, the stage with MoE no-drop routing (``_stage``) and the
prompt prefill are the generator's.

``ServingEngine`` wraps the stepper in a ``ContinuousBatcher`` driven
by a dedicated scheduler thread, adds a ``WindowedBatcher`` over
``ModelPredictor`` for batch scoring, and wires per-request latency /
queue-depth / batch-occupancy metrics into
``utils.profiling.MetricsLogger`` with ``annotate()`` trace spans
around the device phases.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import time

import numpy as np

from distkeras_tpu import faults
from distkeras_tpu.networking import RetryPolicy
from distkeras_tpu.serving.scheduler import (
    ContinuousBatcher,
    EngineStoppedError,
    InternalError,
    PeerError,
    ServeRequest,
    ServingError,
    StaleEpochError,
    WindowedBatcher,
    WrongRoleError,
)
from distkeras_tpu.utils.profiling import annotate, span as _span

logger = logging.getLogger(__name__)


def _host_bytes(tree) -> int:
    """Bytes of the leaves of ``tree`` that live on the host (NumPy
    arrays and scalars, not ``jax.Array``s): what a jitted call that
    takes ``tree`` uploads before it can run."""
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(tree)
        if not isinstance(leaf, jax.Array)
    )


def _bucket_pow2(n: int, cap: int) -> int:
    """Round ``n`` up to a power of two, clamped to ``cap`` (compiled-
    program keys must not grow per distinct prompt length). n <= 0
    stays 0: a one-token prompt has nothing to prefill."""
    if n <= 0:
        return 0
    return min(1 << (n - 1).bit_length(), cap)


def _lead(a, n: int):
    """``a`` with ``n`` trailing axes of one (a per-slot vector against
    per-slot rows of ``n`` more axes)."""
    return a[(slice(None),) + (None,) * n]


def _write_rows(cache, at, new, keep):
    """``cache[at] = new`` where ``keep``; a row that is not decoding
    gets back what it held, so one scatter serves every occupancy."""
    import jax.numpy as jnp

    return cache.at[at].set(
        jnp.where(keep, new.astype(cache.dtype), cache[at])
    )


class _MintScope(threading.local):
    """Thread-local attribution slot for the compile listener: the
    ``_MintTimer`` currently executing on this thread, if any."""

    def __init__(self):
        self.key = None
        self.compiles = 0


_MINT_SCOPE = _MintScope()
_MINT_LISTENER_ON = False
_MINT_LISTENER_LOCK = threading.Lock()


def _on_backend_compile(event, secs, **_kw):
    """jax monitoring listener: one firing per REAL backend compile,
    synchronous inside the triggering call — the ground truth the
    mint detector keys on (an executable-cache-size heuristic was
    observed to lag the compile by several calls and then attribute
    the mint to an innocent later call)."""
    if _MINT_SCOPE.key is not None and event.endswith(
        "backend_compile_duration"
    ):
        _MINT_SCOPE.compiles += 1


def _ensure_mint_listener() -> None:
    """Register the process-wide compile listener once."""
    global _MINT_LISTENER_ON
    if _MINT_LISTENER_ON:
        return
    with _MINT_LISTENER_LOCK:
        if _MINT_LISTENER_ON:
            return
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(
            _on_backend_compile
        )
        _MINT_LISTENER_ON = True


class _MintTimer:
    """Transparent wrapper around one jitted program that detects XLA
    mints at call time: jax's monitoring hook fires (synchronously,
    on the calling thread) once per real backend compile, so a call
    during which it fired records the wall time the calling thread
    just lost on the stepper's ``obs.CompileLedger``. Off the mint
    path this costs two thread-local attribute writes per call."""

    __slots__ = ("fn", "key", "stepper")

    def __init__(self, fn, key, stepper):
        self.fn = fn
        self.key = str(key)
        self.stepper = stepper
        _ensure_mint_listener()

    def __call__(self, *args):
        scope = _MINT_SCOPE
        prev_key, prev_n = scope.key, scope.compiles
        scope.key, scope.compiles = self.key, 0
        t0 = time.perf_counter()
        try:
            out = self.fn(*args)
            if scope.compiles:
                self.stepper._record_mint(
                    self.key, time.perf_counter() - t0, args
                )
        finally:
            scope.key, scope.compiles = prev_key, prev_n
        return out


class NgramDrafter:
    """Model-free draft source: prompt-lookup (n-gram) drafting.

    Proposes the ``k`` tokens that followed the most recent earlier
    occurrence of the sequence's current suffix (longest match first,
    ``ngram_max`` down to ``ngram_min`` tokens) — the prompt-lookup
    decoding idea: templated serving traffic (few-shot headers, code
    edits, extraction over a quoted document) repeats spans of its own
    context, and copying the continuation of the last such span is
    free. No model, no device state, no training: proposals are a pure
    host-side function of each slot's sequence so far, which is why
    this drafter works the moment speculation is switched on. When no
    suffix recurs it proposes nothing and the engine falls back to the
    plain decode step for that iteration — incompressible traffic pays
    only the (counted) fallback, never a wasted verify.

    Incompressible traffic still produces ACCIDENTAL suffix matches
    (random contexts repeat bigrams by chance), and one junk proposal
    drags every active slot through a k+1-position verify to accept a
    single token — so the drafter self-throttles on FEEDBACK: a slot
    whose proposals were fully rejected ``cold_after`` windows in a row
    stops proposing for ``retry_every`` windows, then probes again.
    Repetitive traffic never builds a rejection streak, so the win is
    untouched; adversarial traffic degrades to near-plain-decode cost
    instead of paying the verify tax forever.
    """

    name = "ngram"
    wants_sequences = True  # the batcher passes prompt+emitted per slot

    def __init__(self, ngram_max=3, ngram_min=2, k=None,
                 cold_after=3, retry_every=16):
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max; got "
                f"{ngram_min}, {ngram_max}"
            )
        self.cold_after = int(cold_after)
        self.retry_every = int(retry_every)
        self._streak = None  # per-slot consecutive all-rejected windows
        self._pause = None  # per-slot windows left to sit out
        self._proposed = None  # slots that proposed in the live round
        del k  # accepted for symmetry; the stepper passes k per call

    def bind(self, stepper):
        b = stepper.num_slots
        self._streak = np.zeros(b, np.int64)
        self._pause = np.zeros(b, np.int64)
        self._proposed = np.zeros(b, bool)

    def warmup(self):
        pass

    def admit(self, slot, prompt):
        self._streak[slot] = 0
        self._pause[slot] = 0

    def release(self, slot):
        self._streak[slot] = 0
        self._pause[slot] = 0

    def invalidate(self, mask):
        pass

    def sync(self, active, toks, counts, lens0):
        """Acceptance feedback: ``counts[i] - 1`` of slot i's proposals
        were accepted this window. All-rejected windows build the
        throttle streak; any acceptance resets it."""
        del toks, lens0
        judged = np.asarray(active, bool) & self._proposed
        rejected = judged & (np.asarray(counts) <= 1)
        self._streak[judged & ~rejected] = 0
        self._streak[rejected] += 1
        cold = self._streak >= self.cold_after
        self._pause[cold] = self.retry_every
        self._streak[cold] = 0

    def propose(self, active, k, seqs):
        """(B, k) int32 proposals + (B,) proposal counts. Slots whose
        suffix has no earlier occurrence (or whose sequence is absent),
        and slots sitting out a rejection-streak pause, get count 0."""
        b = active.shape[0]
        dtoks = np.zeros((b, k), np.int32)
        dcnt = np.zeros((b,), np.int32)
        self._proposed[:] = False
        if seqs is None:
            return dtoks, dcnt
        from numpy.lib.stride_tricks import sliding_window_view

        for i in np.flatnonzero(active):
            if self._pause[i] > 0:
                self._pause[i] -= 1
                continue
            s = seqs[i]
            if s is None:
                continue
            if isinstance(s, tuple):  # zero-copy (prompt, emitted)
                prompt, toks = s
                s = (
                    np.concatenate(
                        [prompt, np.asarray(toks, prompt.dtype)]
                    )
                    if len(toks)
                    else np.asarray(prompt)
                )
            if s.size < self.ngram_min + 1:
                continue
            ln = s.size
            for n in range(min(self.ngram_max, ln - 1),
                           self.ngram_min - 1, -1):
                pat = s[ln - n:]
                # windows ending before the suffix itself; the LAST
                # earlier occurrence wins (most recent context)
                hits = np.flatnonzero(
                    (sliding_window_view(s, n)[: ln - n] == pat).all(1)
                )
                if hits.size:
                    j = int(hits[-1])
                    cont = s[j + n : j + n + k]
                    dtoks[i, : cont.size] = cont
                    dcnt[i] = cont.size
                    self._proposed[i] = True
                    break
        return dtoks, dcnt


class ModelDrafter:
    """Draft source backed by a small draft LM: the serving-tier lift
    of ``SpeculativeGenerator``'s draft path. The draft model runs its
    OWN quiet slot bank (a nested plain ``DecodeStepper``, same slots,
    scratch-padded so over-draft writes land past the real positions),
    admitted/released in lockstep with the target's slots. Each round
    proposes ``k`` greedy draft tokens via k+1 draft steps — the extra
    step writes the draft's K/V for the last proposed position, the
    same gapless-cache fix ``SpeculativeGenerator.draft_chunk``
    carries — and after the target's verify the draft's context row
    and length are rolled back to the ACCEPTED sequence (the agreeing
    prefix is already in place; the target's correction token is
    written over the rejected proposal). A draft-side crash never
    fails a request: the slot is marked invalid and simply stops
    proposing (one token per iteration, plain-greedy pace) until its
    next admission.

    Known tradeoff, stated: the draft's prompt prefill runs UNCHUNKED
    on the scheduler thread the iteration its slot turns decodable —
    a deliberate exception to the PR 2 chunk budget, acceptable only
    because a draft worth serving is many times smaller than the
    target (its whole prefill costs on the order of one target chunk);
    lockstep-chunking the draft admission is the lift if a heavy draft
    ever makes this stall visible."""

    name = "draft_lm"
    wants_sequences = False

    def __init__(self, model):
        self.model = model
        self._st = None
        self._valid = None

    def bind(self, stepper):
        """(Re)build the nested draft slot bank against ``stepper``'s
        geometry — called from ``DecodeStepper.__init__``, including
        the supervisor's post-crash rebuilds."""
        tgt = stepper
        if self.model.input_shape[0] != tgt.max_len:
            raise ValueError(
                "draft and target must be built to the same sequence "
                f"length; got {self.model.input_shape[0]} vs "
                f"{tgt.max_len}"
            )
        self._st = DecodeStepper(
            self.model, num_slots=tgt.num_slots, temperature=0.0,
            kv_dtype=tgt._gen.kv_dtype,
            scratch=_bucket_pow2(tgt.draft_k, tgt.max_len) + 2,
            _quiet=True,
        )
        if self._st._gen._emb.vocab_size != tgt._gen._emb.vocab_size:
            raise ValueError(
                "draft and target must share a vocabulary; got "
                f"{self._st._gen._emb.vocab_size} vs "
                f"{tgt._gen._emb.vocab_size}"
            )
        self._st.on_compile = lambda: (
            tgt.on_compile() if tgt.on_compile is not None else None
        )
        self._valid = np.zeros(tgt.num_slots, bool)

    def warmup(self):
        self._st.warmup()

    def admit(self, slot, prompt):
        self._st.admit(slot, prompt)
        self._valid[slot] = True

    def release(self, slot):
        self._valid[slot] = False
        self._st.release(slot)

    def invalidate(self, mask):
        """A draft-side failure: stop proposing for these slots (the
        engine keeps decoding them one token per iteration)."""
        self._valid[np.asarray(mask, bool)] = False

    def propose(self, active, k, seqs):
        del seqs
        act = np.asarray(active, bool) & self._valid
        b = act.shape[0]
        dtoks = np.zeros((b, k), np.int32)
        if not act.any():
            return dtoks, np.zeros((b,), np.int32)
        toks = [self._st.step(act) for _ in range(k + 1)]
        for j in range(k):  # the k+1-th step's proposal is discarded
            dtoks[act, j] = np.asarray(toks[j])[act]
        return dtoks, np.where(act, k, 0).astype(np.int32)

    def sync(self, active, toks, counts, lens0):
        """Roll the draft bank back to the verified truth: write the
        accepted tokens over the draft's proposals (only the target's
        correction actually differs) and reset the draft lengths to
        the target's."""
        act = np.asarray(active, bool) & self._valid
        if not act.any():
            return
        self._st.write_segment(act, toks, counts, lens0)
        self._st._lens[act] = lens0[act] + counts[act]


class _InflightStep:
    """One dispatched-but-uncollected decode step (the zero-bubble
    handle): holds the stepper, the active mask the step was issued
    with, each slot's tenancy count at that moment, and the
    UN-MATERIALIZED device token array. ``ready()`` is a non-blocking
    poll; ``collect()`` is the single host sync point — it fetches the
    tokens AND applies the host bookkeeping a successful step implies
    (length/RNG-position advance, grammar cursors), so nothing advances
    until the step is known good. A handle is open (in the stepper's
    ``_air``, in dispatch order) from its dispatch to its ``collect()``
    or ``discard()``: a step dispatched meanwhile counts the advance
    this one still owes (``DecodeStepper._owed``). Single-consumer,
    collect-once, in dispatch order (the scheduler thread)."""

    __slots__ = ("_stepper", "active", "_toks", "_tenancy")

    def __init__(self, stepper, active, toks):
        self._stepper = stepper
        self.active = active
        self._toks = toks
        self._tenancy = stepper._tenancy.copy()
        stepper._air.append(self)

    def ready(self) -> bool:
        """True when the device result is available (collect would not
        block). Best-effort: backends/arrays without a readiness probe
        report True — the overlap ledger then measures the blocking
        collect honestly instead of guessing."""
        if self._toks is None:
            return True
        is_ready = getattr(self._toks, "is_ready", None)
        if is_ready is None:
            return True  # already host-side (numpy fallback paths)
        try:
            return bool(is_ready())
        except Exception:  # noqa: BLE001 — a poll must never crash
            return True

    def owed(self) -> np.ndarray:
        """The slots whose bookkeeping this step advances at its
        collect: those of its mask whose tenant has not left since the
        dispatch. A slot released meanwhile (evicted at the collect of
        the step before this one, and perhaps admitted anew since)
        keeps the state its release and its new admission gave it: this
        step's token for it is discarded, as the scheduler discards it
        (``ContinuousBatcher._emit``)."""
        return self.active & (self._tenancy == self._stepper._tenancy)

    def discard(self) -> None:
        """The step is dropped un-collected: the stepper stops counting
        it as in the air, and nothing of it advances. (A scheduler
        thread that ``stop()`` from another thread overtook inside a
        call may still collect it: every slot was released meanwhile,
        so it owes nothing then either.)"""
        try:
            self._stepper._air.remove(self)
        except ValueError:  # collected, or discarded before
            pass

    def collect(self) -> np.ndarray:
        """Materialize the step's tokens (THE host sync point) and
        advance the host bookkeeping. Raises whatever the device call
        deferred; in that case nothing has advanced — the same "a
        failed call advanced nothing" contract the blame probes rely
        on."""
        if self._toks is None:
            raise RuntimeError("decode step already collected")
        st, active = self._stepper, self.active
        with _span("serving/collect") as sp:
            try:
                # the one device->host fetch
                toks = np.asarray(self._toks)
            finally:
                self._toks = None
                self.discard()  # collected or failed: in the air no more
            if st._moe_layers:
                # the expert layers' routing counters ride the tokens'
                # fetch (no second device sync)
                toks = st._note_routing(toks, int(active.sum()), sp)
            if st._select:
                st._note_selection(active, sp)
            if st._state_layers:
                st._note_state(active, sp)
            owed = self.owed()
            st._lens[owed] = np.minimum(st._lens[owed] + 1, st._lens_cap)
            # the RNG counter mirrors the length discipline exactly: a
            # failed call advanced nothing, a successful one advanced
            # each slot it still owes once — replay through blame
            # probes is this line
            st._spos[owed] += 1
            if st._grammar:
                st._advance_grammar(
                    toks.reshape(-1, 1), np.where(owed, 1, 0)
                )
        return toks


class DecodeStepper:
    """Slot-bank decode over a causal-LM-family model.

    State per slot: one row of the (B, T) token buffer and one row of
    each stage's (B, T, H, Dh) K/V caches, plus a host-side length.
    Admission prefills K/V for positions ``0..len-2`` (the step that
    follows consumes the last prompt token, exactly like
    ``CachedSequenceGenerator``'s scan start) — either in one call
    (``admit``) or incrementally (``begin_admit`` + ``prefill_chunk``,
    optionally skipping a ``prefix_cache`` hit's positions entirely).
    ``step(active)`` embeds each slot's last token at its OWN position,
    attends one row against the caches, and appends the sampled/greedy
    token — inactive slots freeze (masked writes). Greedy slot output
    is the cached generator's greedy decode, token for token,
    regardless of what the neighbouring slots are doing, and regardless
    of whether its prefix came from the cache, chunked prefill, or
    both — THE correctness bar of this subsystem.
    """

    def __init__(self, model, num_slots=8, temperature=0.0, seed=0,
                 top_k=None, top_p=None, kv_dtype=None,
                 prefix_cache=None, speculative=None, draft_k=4,
                 spec_mode="rejection", scratch=None, paged=False,
                 page_size=16, num_pages=None, recorder=None,
                 mesh=None, compile_ledger=None, _quiet=False):
        """``prefix_cache``: an optional ``prefix_cache.PrefixStore``.
        When set, ``begin_admit`` restores the longest cached prefix's
        K/V rows into the slot before any prefill compute, and every
        finished prefill publishes its missing pow2 ladder rungs (an
        exact-length repeat therefore re-prefills the sub-rung tail —
        the stated reuse ceiling, not full-hit-on-repeat).

        ``paged``: replace the per-slot contiguous K/V caches with a
        BLOCK-PAGED pool — per stage, a fixed ``(num_pages, page_size,
        H, Dh)`` device pool plus host-managed per-slot page tables
        (``paging.PageAllocator`` owns the free list / refcounts).
        Admission RESERVES exactly the pages the request can touch
        (``prompt + max_new`` positions, not the worst-case sequence),
        so slot occupancy is length-independent: the pool, not the slot
        count x max_len product, is the capacity. The step / chunked-
        prefill / speculative-verify programs gather each slot's pages
        into its logical K/V row (program keys add the pow2-bucketed
        max-pages-per-slot, so compiles stay O(log T) per family), and
        greedy output remains pinned token-identical to the dense bank
        and to solo decode. Full prompt-prefix pages are shared
        copy-on-write across slots through a device-resident
        ``DevicePrefixIndex`` (refcounted page-table entries, zero
        bytes moved on a hit) in front of the host ``PrefixStore``
        ladder; ``fork_slot`` forks a live slot's table the same way
        (beam / parallel sampling pay only divergent pages). Pool
        exhaustion raises the typed, retriable ``PoolExhaustedError``
        (``overloaded`` on the wire) before any slot state mutates.

        ``page_size``: tokens per page. ``num_pages``: pool size; None
        sizes the pool to the dense bank's byte budget
        (``num_slots * ceil(seq_len / page_size)`` pages) so paged-by-
        default never regresses capacity. ``recorder``: an optional
        ``obs.FlightRecorder`` — page grants/frees, CoW forks, pool
        exhaustion, and prefix-cache errors land on the tape.

        ``speculative``: an optional draft source (``NgramDrafter`` /
        ``ModelDrafter``). When set, the scheduler drives ``spec_step``
        instead of ``step``: the drafter proposes up to ``draft_k``
        tokens per active slot and a once-compiled VERIFY program
        scores all k+1 candidate positions against the live K/V caches
        in one call. Greedy slots accept the longest argmax-agreeing
        prefix plus the target's correction (output = the target's
        greedy decode, exactly); under ``spec_mode="rejection"`` (the
        default) SAMPLED slots accept each drafted token with its
        target probability and draw corrections from the residual —
        distribution-preserving and same-seed replay-deterministic.
        ``spec_mode="strict"`` is the legacy greedy-agreement-only
        mode: any sampling config (engine-wide or per-request) is
        refused with the historical ValueError.

        ``scratch``: extra (masked) positions padded onto the cache /
        context time axis so speculative over-draft and verify writes
        land past the real sequence instead of clamping onto it
        (default: sized from ``draft_k`` when speculative, else 0).
        ``_quiet``: skip the fault seams — the draft model's nested
        stepper must not trip seams armed for live target traffic.

        ``mesh``: tensor-parallel serving mesh — ``"tp:N"``, an int, or
        a ``jax.sharding.Mesh`` carrying a ``"model"`` axis (resolved
        through ``parallel.mesh.serving_mesh``). The stepper then
        places its OWN copy of the weights with the Megatron-paired
        decode specs (``parallel.tensor_parallel.shard_decode_params``:
        attention QKV/O head-sharded, MLP column/row, MoE expert stacks
        expert-sharded over the same axis, embeddings/LN/head
        replicated) and shards every K/V pool / cache bank HEAD-wise
        over the same axis, so the weight-read-bound step streams 1/N
        of the bytes per shard. All host bookkeeping — page tables,
        ``PageAllocator`` refcounts, prefix-index entries, sampler
        state — is mesh-oblivious: a page id names a (page_size, H,
        Dh) extent whose bytes happen to live split across shards.
        The compiled programs are the SAME bodies as solo; XLA's
        partitioner inserts the collectives (one psum per attention/
        MLP pair). ``mesh=None`` (the default) leaves every code path
        byte-for-byte as before; the stepper's copy of the weights is
        then ``model.params`` with its host leaves placed on the
        default device, once. Requires ``num_heads %% N == 0`` —
        validated loudly here, at bundle load. The nested draft
        stepper (``ModelDrafter``) always runs solo: a draft worth
        serving fits one device, and its proposals are verified by the
        sharded target anyway."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.predictors import CachedSequenceGenerator

        # reuse the generator's model-family validation, stage parsing,
        # sampling config, and MoE no-drop routing wholesale
        self._gen = CachedSequenceGenerator(
            model, temperature=temperature, seed=seed, top_k=top_k,
            top_p=top_p, kv_dtype=kv_dtype,
        )
        self.model = model
        # the block kind IS the page layout: "kv" (keys and values of
        # every head, one budget), "latent" (a latent row an attention),
        # "gqa" (grouped keys and values, a budget a layer kind), "ssm"
        # (layers that hold a state a slot and nothing a token, beside
        # grouped layers under the page table). It picks
        # what the step / chunk programs are built from (``_LAYOUTS``) and
        # what is refused and why (``_PAGED_ONLY``) — decided here, when
        # programs are built, never inside a traced function, and never
        # by a block's class
        self.layout = self._gen.block_kind
        self._face = self._LAYOUTS[self.layout]
        # the indexer of a block that selects the keys it reads (None:
        # every cached key is read): ``{"heads", "head_dim", "topk"}``
        self._select = getattr(self._gen._blocks[0], "select", None)
        self._paged_only = self._PAGED_ONLY.get(
            "gqa/select" if self._select else self.layout)
        self.prefix_caches_off = None
        if self._paged_only:
            from distkeras_tpu.ops.quantization import count_quantized

            self._refuse_unsupported(
                "the dense slot bank (paged=False)" if not paged else None,
                "speculative decoding" if speculative else None,
                "a tensor-parallel serving mesh" if mesh is not None
                else None,
                "int8 / int4 weights (quantize_model(bits=8|4))"
                if count_quantized(model.params) else None,
            )
            # a host PrefixStore row is (p, H, Dh) keys and values of one
            # head count, and the device index shares a prompt's early
            # pages, which a latent pool never had exercised and a window
            # layer's ring has overwritten: both are switched off for
            # these layouts, and stats() says so
            prefix_cache = None
            self.prefix_caches_off = self._paged_only["prefix_caches"]
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1; got {num_slots}")
        self.max_len = int(model.input_shape[0])
        # the most tokens one prefill-chunk program takes: a block whose
        # chunk costs tokens x cached positions says so (``chunk_tokens``),
        # and so does a block that holds a state a slot (its chunk is a
        # loop over blocks of positions: a longer one gains nothing, and
        # every bucket is a whole-depth program to compile)
        selecting = [b for b in self._gen._blocks
                     if getattr(b, "select", None)
                     or getattr(b, "slot_state", None)]
        self.chunk_cap = min(
            [self.max_len] + [int(b.chunk_tokens) for b in selecting])
        # ... and the fewest a chunk program is built for, a sixteenth of
        # that (shorter chunks are padded to it: such a block's chunk
        # program is a switch of extents a layer, and a dozen of them cost
        # minutes of set-up; five buckets, 128 to 2,048, cost half)
        self.chunk_floor = max(1, self.chunk_cap // 16) if selecting else 1
        self.seed = int(seed)
        self.drafter = speculative if speculative else None
        self.draft_k = int(draft_k)
        if self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1; got {draft_k}")
        self._kb = _bucket_pow2(self.draft_k, self.max_len)
        self.spec_mode = spec_mode
        if self.drafter is not None:
            # one shared validation (sampling.check_spec_sampling):
            # strict mode raises the legacy greedy-only ValueError,
            # rejection mode (default) serves sampled slots too
            from distkeras_tpu.serving.sampling import check_spec_sampling

            self.spec_mode = check_spec_sampling(
                spec_mode, temperature, top_k, top_p
            )
        if scratch is None:
            scratch = self._kb + 1 if self.drafter is not None else 0
        self._tp = self.max_len + int(scratch)  # padded time axis
        # parked/over-draft lens cap: plain steppers keep the PR 1 cap
        # (max_len); scratch-padded ones may walk into the pad
        self._lens_cap = self.max_len + max(0, int(scratch) - 1)
        self._quiet = bool(_quiet)
        # the compile ledger (``obs.CompileLedger``): engine-owned and
        # passed through the stepper config so it SURVIVES supervisor
        # restarts — a restart's recompiles are attributed (rewarm),
        # never counted from zero. The nested draft stepper gets none
        # (its programs belong to the drafter, not the serving path).
        self.ledger = None if _quiet else compile_ledger
        self._warming = False  # True inside warmup(): mints off-path
        # what a block caches is what it says: the heads of a cached row
        # (K/V heads) and their size, and the window layers' window;
        # everything a layout decides is picked from ``_LAYOUTS``, once
        nh, hd, self._window = getattr(self, self._face["shape"])()
        self._ring = 0  # pages of a slot's ring in a window layer
        b, t = self.num_slots, self._tp
        # -- serving mesh (tensor-parallel decode) ------------------------
        # Resolved FIRST (before any device allocation): a bad mesh must
        # fail the boot, not the first step. The two shardings every
        # program output is pinned to: K/V head-sharded, everything else
        # replicated.
        self.mesh = None
        self._kv_sh = None
        self._repl_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from distkeras_tpu.parallel.mesh import serving_mesh
            from distkeras_tpu.parallel.tensor_parallel import (
                shard_decode_params,
            )

            self.mesh = serving_mesh(mesh)
            tp_ways = int(self.mesh.shape["model"])
            if nh % tp_ways:
                # the cache is sharded by K/V head, so K/V heads are what
                # the model axis must divide (for this block they are the
                # query heads too)
                raise ValueError(
                    f"cannot shard {nh} K/V heads over mesh "
                    f"'tp:{tp_ways}': the model axis must divide the "
                    f"K/V heads (num_heads where every query head has "
                    f"its own) — pick a mesh that divides them or serve "
                    f"this bundle solo"
                )
            self._kv_sh = NamedSharding(
                self.mesh, PartitionSpec(None, None, "model")
            )
            self._repl_sh = NamedSharding(self.mesh, PartitionSpec())
            # the stepper's OWN placed copy: the trainable master tree
            # (and the predict path reading it) stays untouched
            self._params = shard_decode_params(model.params, self.mesh)
            self._ctx = jax.device_put(
                jnp.zeros((b, t), jnp.int32), self._repl_sh
            )
        else:
            # the stepper's OWN resident copy, placed once: a host leaf
            # (a bundle's NumPy tree) goes to the device here, so no
            # program call uploads it again; a leaf that is a
            # ``jax.Array`` already is bound as it is, no second copy.
            # ``model.params`` stays as handed in
            self._params = jax.device_put(model.params)
            self._ctx = jnp.zeros((b, t), jnp.int32)
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.recorder = recorder
        if self.paged:
            from distkeras_tpu.ops.paged_attention import (
                decode_attention_path,
            )
            from distkeras_tpu.serving.paging import PageAllocator
            from distkeras_tpu.serving.prefix_cache import (
                DevicePrefixIndex,
            )

            if self.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1; got {page_size}"
                )
            pages_per_slot = -(-t // self.page_size)
            if num_pages is None:
                # dense-equivalent byte budget (+ the null sentinel)
                num_pages = b * pages_per_slot + 1
            self._kv_alloc = PageAllocator(
                int(num_pages), self.page_size, recorder=recorder,
            )
            # page-table bucket ceiling: the pow2 bucket that covers a
            # full-capacity slot (every runtime bucket is <= this)
            self._max_pages_bucket = max(
                1, 1 << (pages_per_slot - 1).bit_length()
            )
            # how the decode step attends, by what this stepper is:
            # "kernel" (each slot's own pages, in place) or "gather:
            # <why>" (the gathered extent of the longest table)
            # (the layers of an "ssm" model that cache rows are grouped;
            # the grouped body is told the K/V heads, since narrow ones
            # that fill the lanes side by side ride it)
            grouped = self.layout in ("gqa", "ssm")
            if grouped and hd is None:
                self.attention = "none: no layer caches keys and values"
            else:
                self.attention = decode_attention_path(
                    "gqa" if grouped else self.layout, hd,
                    self._gen.kv_dtype, self.mesh, self.page_size,
                    **({"kv_heads": nh} if grouped else {}),
                )
            # a block that selects asks as any grouped stepper does: where
            # the answer is "kernel" the grouped body attends the slot's own
            # pages under the selection's mask (the selected rows lie on
            # nearly as many pages as there are rows, and whole pages stream
            # at ten times the rate rows are gathered at); "gather: <why>"
            # gathers the selected rows by token (``_select_rows``).
            # how the decode step scores such a block's selector keys:
            # "kernel" (each slot's own selector pages, in place) or
            # "gather: <why>"; None for a block that selects nothing
            self.selector = None
            if self._select:
                self.selector = decode_attention_path(
                    "index", self._select["head_dim"], self._gen.kv_dtype,
                    self.mesh, self.page_size,
                )
            # a step program that costs the same at every table width is
            # compiled once, at the widest
            self._one_step_extent = (
                self.attention == "kernel" or bool(self._select))
            self._caches = None
            self._window_alloc = None
            self._pools = getattr(self, self._face["pools"])(
                int(num_pages), nh, hd
            )
            self._tables: list[list[int]] = [[] for _ in range(b)]
            self.prefix_index = (
                None if self._paged_only
                else DevicePrefixIndex(self._kv_alloc)
            )
            # paged program caches (separate families from the dense
            # ones: their keys carry the page-table bucket; the masked
            # flag selects the grammar-constrained variant)
            self._pstep_fns = {}  # (table-bucket, masked) -> step
            self._pchunk_fns = {}  # (chunk-bucket, table-bucket) -> fn
            self._pverify_fns = {}  # (candidates, table-bucket, masked)
            self._pcopy_fns = {}  # (prefix-bucket, table-bucket) -> fn
            self._page_copy_fn = None  # one-page CoW device copy
            self._row_copy_fn = None  # ctx-row copy (fork)
        else:
            self._kv_alloc = None
            self.prefix_index = None
            self._caches = [
                (
                    self._place_kv(
                        jnp.zeros((b, t, nh, hd), self._gen.kv_dtype)
                    ),
                    self._place_kv(
                        jnp.zeros((b, t, nh, hd), self._gen.kv_dtype)
                    ),
                )
                for _ in self._gen._stages
            ]
        self._lens = np.ones((b,), np.int32)  # host mirror; >=1 always
        # expert layers' routing counters: the step program of a model
        # with routed experts returns them behind its tokens
        self._moe_layers = sum(
            1 for blk in self._gen._blocks if getattr(blk, "n_experts", 0)
        )
        # how the expert layers' grouped products multiply, by their widths
        # (``models.mla_moe._grouped_mm`` asks the same): "kernel"
        # (``ops/grouped_matmul.py``) or "ragged_dot"
        self.grouped = None
        if self._moe_layers:
            from distkeras_tpu.ops.grouped_matmul import grouped_form

            self.grouped = grouped_form(
                self._gen._emb.dim, self._gen._blocks[-1].expert_width)
        self.moe_stats = {
            "steps": 0, "experts_hit_sum": 0.0, "expert_load_max_sum": 0,
            "experts_total": (
                len(self._gen._blocks[-1].held) if self._moe_layers else 0
            ),
            # of the active slots' tokens x top_k x expert layers picks:
            # those of an identity expert and of a held routed expert
            "routed_tokens": 0, "zero_picks": 0, "held_picks": 0,
            # layer-steps whose held rows took more than one pass
            "overflow_passes": 0,
        }
        # a selecting block: over the decode steps, the cached positions
        # the active slots held and those their queries read
        self.select_stats = {"steps": 0, "keys_cached": 0,
                             "keys_selected": 0}
        # layers that hold a state a slot (``slot_state``): how many, what
        # one slot holds over all of them, and of that the states alone,
        # which a decode step reads and writes for every decoding slot
        held = [b.slot_state for b in self._gen._blocks
                if getattr(b, "slot_state", None)]
        self._state_layers = len(held)
        self.state_bytes_a_slot = sum(
            int(np.prod(shape)) * np.dtype(dt).itemsize
            for arrays in held for shape, dt in arrays)
        self._state_bytes_a_step_slot = 2 * sum(
            int(np.prod(arrays[0][0])) * np.dtype(arrays[0][1]).itemsize
            for arrays in held)
        # over the decode steps: the bytes of state they read and wrote;
        # and the slots whose state an admission reset, in all and since
        # the last collect
        self.state_stats = {"steps": 0, "state_bytes": 0, "resets": 0}
        self._state_resets_new = 0
        self.host_arg_bytes_step = 0  # of the last decode-step call
        self._step_fns = {}  # masked flag -> compiled decode step
        self._admit_fns = {}  # prefill-length bucket -> compiled admit
        self._chunk_fns = {}  # chunk-length bucket -> compiled chunk
        self._copy_fn = None  # prefix restore (specializes per pb shape)
        self._row_fn = None  # compiled ctx-row write (one program)
        self._verify_fns = {}  # (candidates, masked) -> compiled verify
        self._seg_fn = None  # compiled accepted-segment ctx write
        # -- per-slot sampler state (the tentpole) --------------------
        # Every step/verify program takes these as DATA (never baked
        # into the compile key): per-slot temperature / top-k / top-p /
        # seed plus the EMITTED-POSITION counter the RNG keys on.
        # Greedy slots (temps == 0, the default) take exact argmax, so
        # an all-greedy bank reproduces the pre-sampling programs'
        # output token for token. ``default_sampling`` carries the
        # engine-wide construction knobs for admissions that bring no
        # per-request params (back-compat: engine-wide temperature
        # still samples, now replay-deterministically).
        from distkeras_tpu.serving.sampling import (
            SamplingParams,
            TokenMaskCompiler,
        )

        self.default_sampling = SamplingParams(
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
        )
        self._temps = np.zeros((b,), np.float32)
        self._topk = np.zeros((b,), np.int32)  # 0 = disabled
        self._topp = np.ones((b,), np.float32)  # 1.0 = disabled
        self._seeds = np.zeros((b,), np.int32)
        self._spos = np.zeros((b,), np.int32)  # emitted-token counter
        # zero-bubble decode: the dispatched, uncollected steps in
        # dispatch order (``_InflightStep``), and how often each slot's
        # tenant has left (``release``): a step in the air advances a
        # slot at its collect only if the count is what it was
        self._air: list[_InflightStep] = []
        self._tenancy = np.zeros((b,), np.int64)
        self._slot_params = [None] * b  # SamplingParams per slot
        self._grammar = {}  # slot -> incremental grammar mask state
        self._mask_compiler = TokenMaskCompiler(
            self._gen._emb.vocab_size
        )
        self.constrained_masks = 0  # masks applied (device-side rows)
        self.mask_exhaustions = 0  # all-candidates-zeroed fallbacks
        for i in range(b):
            self._reset_slot_sampling(i)
        self._nh, self._hd = nh, hd
        self.prefix_cache = prefix_cache
        # speculation bookkeeping: prompts kept for draft admission,
        # which slots have a draft admitted, the proposal cache that
        # keeps blame-probe retries from re-advancing the draft bank,
        # and the drafted/verify counters stats() attributes per source
        self._spec_prompts: dict[int, np.ndarray] = {}
        self._spec_admitted: set[int] = set()
        self._spec_pending = None  # (lens snapshot, dtoks, dcnt)
        self.spec_verify_steps = 0
        self.spec_fallback_steps = 0
        self.spec_drafted_tokens = 0
        # drafter exceptions swallowed by spec_step (admission or
        # proposal): the request survives at plain-decode pace, and this
        # count is the only trace of it — health() and stats() show it
        self.spec_draft_failures = 0
        # prefix-store failures are degraded to misses, never surfaced
        # to the request (the cache is an optimization, not a dependency)
        self.prefix_fetch_failures = 0
        # called right before each NEW program build: the engine's
        # watchdog extends its wedge grace through it, so a live-path
        # XLA compile (a fresh prompt-length bucket, minutes into
        # serving) is never mistaken for a wedged scheduler
        self.on_compile = None
        # in-progress admissions: slot -> pending prompt / next prefill
        # position (host bookkeeping for the chunked lifecycle)
        self._pending: dict[int, np.ndarray] = {}
        self._prefill_pos: dict[int, int] = {}
        if self.drafter is not None:
            self.drafter.bind(self)

    @property
    def speculative(self) -> bool:
        return self.drafter is not None

    @property
    def _params(self):
        """The parameter tree, every program's first argument."""
        return self._params_tree

    @_params.setter
    def _params(self, tree):
        """Bind ``tree`` (``None`` drops the stepper's copy) and sum
        what of it is on the host, once a binding: the
        ``host_arg_bytes`` of every program call's span starts from it.
        The constructor binds device arrays on both branches, so the
        sum is 0; anything else means a caller re-bound host arrays,
        which every call then uploads: a fault to look for."""
        self._params_tree = tree
        self._params_host_bytes = _host_bytes(tree)

    def _host_arg_bytes(self, host) -> int:
        """What a program call uploads before it can run: ``host``, the
        arguments built in NumPy for this call, and the tree's share on
        the host, which is 0 unless host arrays were re-bound to
        ``_params`` after the constructor placed them (a fault)."""
        import jax

        return self._params_host_bytes + sum(
            a.nbytes for a in jax.tree_util.tree_leaves(host)
        )

    def paged_stats(self) -> dict:
        """Pool / allocator / device-prefix-index observability for the
        engine's ``stats()`` (empty when dense)."""
        if not self.paged:
            return {"enabled": False}
        out = {"enabled": True, "layout": self.layout,
               "attention": self.attention,
               "bytes_per_token": self.kv_bytes_per_token()}
        if self.prefix_caches_off:
            out["prefix_caches"] = "off: " + self.prefix_caches_off
        out.update(self._kv_alloc.stats())
        if self._window_alloc is not None:
            # the second budget: what a token costs by layer kind, and
            # what a slot's window layers hold at most, whatever its length
            out["bytes_per_token_by_kind"] = {
                kind: self.kv_bytes_per_token(kind)
                for kind in ("full", "window")
            }
            out["window_positions_max"] = self._ring * self.page_size
            out["window_pages_a_slot"] = self._ring
            out["window"] = self._window_alloc.stats()
        if self._select:
            # the second kind of cached row under the one table
            out["bytes_per_token_by_kind"] = {
                kind: self.kv_bytes_per_token(kind)
                for kind in ("full", "index")
            }
            out["select"] = dict(self._select)
            out["selector"] = self.selector
        if self._state_layers:
            # what a slot holds whatever its length, beside what a token
            # costs in the layers that cache rows
            out["bytes_per_token_by_kind"] = {
                "full": self.kv_bytes_per_token("full")}
            out["state_layers"] = self._state_layers
            out["state_bytes_a_slot"] = self.state_bytes_a_slot
            out["state_bytes_total"] = (
                self.state_bytes_a_slot * self.num_slots)
        # mesh geometry: the pool's TOTAL bytes are mesh-invariant;
        # what changes with tp:N is how many land per shard
        out["mesh"] = self.mesh_spec
        out["kv_bytes_total"] = self.kv_bytes_total()
        out["kv_shard_bytes"] = self.kv_shard_bytes()
        out["device_prefix"] = (
            self.prefix_index.stats()
            if self.prefix_index is not None
            else {"entries": 0}
        )
        out["compiled_step_buckets"] = sorted(self._pstep_fns)
        out["compiled_chunk_buckets"] = sorted(self._pchunk_fns)
        # what the last decode-step call handed over from the host
        out["host_arg_bytes_step"] = self.host_arg_bytes_step
        return out

    @property
    def wants_sequences(self) -> bool:
        """True when the draft source needs each slot's host-side
        sequence so far (prompt + emitted) — the batcher builds them."""
        return self.drafter is not None and self.drafter.wants_sequences

    @property
    def state_a_slot(self) -> bool:
        """True where some block holds a state a slot: a step that ran on
        the device has advanced the state of every slot of its mask,
        whether or not its tokens reach the host, so the scheduler cannot
        probe again from where a step whose collect raised began."""
        return self._state_layers > 0

    def _fire(self, site, **ctx):
        """Fault seam, silenced for nested (draft) steppers: seams
        armed against live target traffic must not trip on the draft
        bank's internal steps."""
        if not self._quiet:
            faults.fire(site, **ctx)

    def _compiling(self):
        """About to build (and on first call, compile) a new program —
        let the watchdog know so the compile is not read as a wedge."""
        hook = self.on_compile
        if hook is not None:
            hook()

    # -- serving mesh -------------------------------------------------------

    def _place_kv(self, arr):
        """Pin one K/V pool/cache array to the head shard (identity
        when solo)."""
        if self.mesh is None:
            return arr
        import jax

        return jax.device_put(arr, self._kv_sh)

    def _jit(self, fn, donate=(), out="kv", key=None):
        """``jax.jit`` with mesh-pinned OUTPUT shardings. Solo this is
        plain jit; under a mesh every program's K/V outputs are pinned
        back to the head shard and ctx/token outputs to replicated, so
        the layout never drifts across the donation chain — a program
        whose reshape/scatter left the compiler free to re-lay-out a
        pool would silently retrace every subsequent program (a fresh
        input sharding is a fresh compile key).

        THE compile chokepoint: every serving program is created here,
        so when a ``compile_ledger`` is attached the jitted callable
        is wrapped in a mint detector — a call during which jax's
        backend-compile monitoring event fired (a genuinely new
        program OR a silent retrace of an old one) records (``key``,
        wall seconds, warmup|serving trigger, in-flight requests) on
        the ledger. Off the mint path the wrapper costs two
        thread-local writes per call. ``key``: the ledger's program
        name, stamped at the call site with its bucket (e.g.
        ``"admit[16]"``); defaults to the function's name."""
        import jax

        if self.mesh is None:
            jitted = jax.jit(fn, donate_argnums=donate)
        else:
            kv, rp = self._kv_sh, self._repl_sh
            outs = {
                "kv": kv,  # a caches/pools pytree alone
                "ctx": rp,  # the context rows alone
                "step": (rp, kv, rp),  # (ctx, caches/pools, tokens)
                "verify": (rp, kv, rp, rp),  # (ctx, kv, tokens, counts)
            }[out]
            jitted = jax.jit(fn, donate_argnums=donate,
                             out_shardings=outs)
        if self.ledger is None:
            return jitted
        return _MintTimer(
            jitted, key or getattr(fn, "__name__", "program"), self
        )

    def _record_mint(self, key, seconds, args):
        """One detected program mint (called by ``_MintTimer``): build
        the hashable shape/dtype signature (metadata only — donated
        buffers keep their avals readable) and hand it to the ledger.
        Never raises: the mint already happened, the serving path must
        not fail over its bookkeeping."""
        led = self.ledger
        if led is None:
            return
        try:
            import jax

            sig = tuple(
                (
                    tuple(getattr(leaf, "shape", ()) or ()),
                    str(getattr(leaf, "dtype", type(leaf).__name__)),
                )
                for leaf in jax.tree_util.tree_leaves(args)
            )
        except Exception:  # noqa: BLE001 — observability boundary
            sig = ()
        try:
            led.record_mint(
                key, seconds, signature=sig, warming=self._warming
            )
        except Exception:  # noqa: BLE001 — observability boundary
            pass

    @property
    def mesh_spec(self):
        """``"tp:N"`` under a serving mesh, None solo — the geometry
        string ``health``/``stats``/the fleet router surface."""
        if self.mesh is None:
            return None
        return f"tp:{int(self.mesh.shape['model'])}"

    @property
    def mesh_devices(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.size)

    def kv_bytes_total(self) -> int:
        """Total K/V bytes across all stages and shards (pool or dense
        bank) — constant across mesh sizes at a fixed config, which is
        what makes tp1/tp2/tp4 bench rows an equal-byte comparison."""
        import jax

        arrs = self._row_pools() if self.paged else self._caches
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(arrs)
        )

    def _row_pools(self) -> list:
        """The pools of the layers that cache rows a token: not a layer's
        state a slot, which no token count sizes."""
        return [arrs for blk, arrs in zip(self._gen._blocks, self._pools)
                if not getattr(blk, "slot_state", None)]

    def kv_bytes_per_token(self, kind=None) -> int:
        """Bytes one cached token takes over all layers: keys and values
        of every K/V head, or one latent row an attention, and a
        selecting block's selector key, read off the pools' own shapes.
        ``kind`` (``"full"`` | ``"window"`` | ``"index"``): over the
        layers of that kind alone, where layers differ (a window layer
        holds a token only while it is inside the window), or over the
        selector keys alone."""
        if not self.paged:
            return (np.dtype(self._gen.kv_dtype).itemsize * 2 * self._nh
                    * self._hd * len(self._gen._stages))
        total = 0
        for blk, arrs in zip(self._gen._blocks, self._pools):
            if getattr(blk, "slot_state", None):
                continue  # a state a slot: nothing a token
            mine = "window" if getattr(blk, "window", None) is not None \
                else "full"
            for j, a in enumerate(arrs):
                index = j == 2 and bool(getattr(blk, "select", None))
                if kind not in (None, "index" if index else mine):
                    continue
                if index:  # a page's keys under one leading index
                    values = int(np.prod(a.shape[1:])) // self.page_size
                else:  # a row a token
                    values = int(np.prod(
                        a.shape[1:] if a.ndim == 2 else a.shape[2:]))
                total += values * a.dtype.itemsize
        return total

    # -- the blocks that only the paged engine serves --------------------------

    # by block kind: why, and why the prefix caches are off for it
    _PAGED_ONLY = {
        "latent": {
            "what": "a block that caches latent rows",
            "why": "its cache is a latent row a token and attention, not "
                   "(H, Dh) keys and values",
            "prefix_caches": (
                "latent page layout: the host PrefixStore and the "
                "DevicePrefixIndex hold (p, H, Dh) K/V rows only"),
        },
        "gqa/select": {
            "what": "a grouped-query block whose keys an indexer selects",
            "why": "its cache holds a selector key a token and layer "
                   "beside the keys and values, under one page table, and "
                   "a query reads the rows its indexer picks, which only "
                   "the paged step and chunk programs of one chip do",
            "prefix_caches": (
                "selecting layout: the host PrefixStore and the "
                "DevicePrefixIndex hold (p, H, Dh) K/V rows of one head "
                "count and no selector keys"),
        },
        "ssm": {
            "what": "a block that holds a state a slot",
            "why": "its memory is a fixed block a slot that every step "
                   "rewrites, not rows a token: what shares, exports, "
                   "swaps or rolls back a sequence needs a snapshot of "
                   "that state, which no program takes yet",
            "prefix_caches": (
                "state layout: a shared prefix's pages say nothing of "
                "the state after it, and no snapshot of a state is "
                "kept at page boundaries"),
        },
        "gqa": {
            "what": "a grouped-query block with window layers",
            "why": "its cache is a page budget a layer kind, a window "
                   "layer's pages a ring a slot that is overwritten "
                   "behind the window, and its K/V heads are fewer than "
                   "its query heads, which differ by layer",
            "prefix_caches": (
                "grouped page layout: a window layer's ring has "
                "overwritten a prompt's early pages, and the host "
                "PrefixStore holds (p, H, Dh) rows of one head count"),
        },
    }

    def _refuse_unsupported(self, *features):
        """Typed refusal of what a block that only the paged engine
        serves cannot run yet (each named in PERF.md, "cannot run
        yet"); nothing for the GPT-2 block."""
        from distkeras_tpu.models.mla_moe import BlockUnsupportedError

        why = self._paged_only
        for what in features:
            if what and why:
                raise BlockUnsupportedError(
                    f"{what} cannot serve {why['what']} yet: "
                    f"{why['why']}"
                )

    # what each page layout (= block kind) brings: the shape of a cached
    # row, its pools, and the two cache callables of ``_cache_callable``
    _LAYOUTS = {
        "kv": {"shape": "_kv_cache_shape", "pools": "_build_kv_pools",
               "rows": "_kv_rows", "chunk": "_kv_chunk"},
        "latent": {"shape": "_latent_cache_shape",
                   "pools": "_build_latent_pools",
                   "rows": "_latent_rows", "chunk": "_latent_chunk"},
        "gqa": {"shape": "_grouped_cache_shape",
                "pools": "_build_grouped_pools",
                "rows": "_gqa_rows", "chunk": "_gqa_chunk"},
        # pools that DIFFER by layer: a state and a convolution tail a
        # slot where the block says ``slot_state``, grouped K/V pages
        # under the one table where it caches rows
        "ssm": {"shape": "_grouped_cache_shape",
                "pools": "_build_hybrid_pools",
                "rows": "_hybrid_rows", "chunk": "_hybrid_chunk"},
    }

    def _kv_cache_shape(self):
        from distkeras_tpu.ops.quantization import qshape

        nh = self._gen._blocks[0].mhsa.num_heads
        wq = self.model.params[str(self._gen._stages[0][1])]["mhsa"]["wq"]
        return nh, qshape(wq)[1] // nh, None

    def _latent_cache_shape(self):
        # no (H, Dh) rows: a latent row a token and attention
        return self._gen._blocks[0].num_heads, None, None

    def _build_kv_pools(self, num_pages, nh, hd):
        import jax.numpy as jnp

        return [
            tuple(
                self._place_kv(jnp.zeros(
                    (num_pages, self.page_size, nh, hd), self._gen.kv_dtype,
                ))
                for _ in range(2)
            )
            for _ in self._gen._stages
        ]

    def _build_latent_pools(self, num_pages, nh, hd):
        """A latent page: (page_size, kv_rank + rope) an attention (a
        block says how many it has: ``cached_rows``), ONE array and not
        a K and a V. The (num_pages, page_size, W) pool is held as its
        row-major flattening (num_pages x page_size, W), page p = rows
        [p * ps, (p + 1) * ps): the TPU's default layout of the 3-D
        array puts the page axis minor, and every program then copies
        the whole pool to rows and back, a layer (seen in the compiled
        step, PERF.md PR 28). For the same reason a row is padded with
        zeros to a multiple of 128 values, the TPU's lane width: 576 ->
        640."""
        import jax.numpy as jnp

        return [
            tuple(
                jnp.zeros(
                    (num_pages * self.page_size, self._latent_row(blk)),
                    self._gen.kv_dtype,
                )
                for _ in range(blk.cached_rows)
            )
            for blk in self._gen._blocks
        ]

    def _grouped_cache_shape(self):
        """``(K/V heads, head size, window)`` that the grouped blocks
        say: the first two alike in every layer that caches rows (a
        slot's table serves every layer of a kind), one window size among
        the window layers (None: no window layer). A layer that caches
        nothing a token (a state a slot: ``slot_state``) has no say here
        and no pool of rows; a model of such layers alone gives ``(None,
        None, None)``."""
        blocks = [b for b in self._gen._blocks
                  if not getattr(b, "slot_state", None)]
        if not blocks:
            return None, None, None
        shapes = {(b.kv_heads, b.head_dim) for b in blocks}
        windows = {b.window for b in blocks} - {None}
        selects = {repr(b.select) for b in blocks}
        if len(shapes) != 1 or len(windows) > 1 or len(selects) != 1:
            raise ValueError(
                f"a paged pool has one (K/V heads, head size), one "
                f"window size and one indexer over the layers of a model "
                f"that cache rows a token (a layer that holds a state a "
                f"slot instead is not asked); got "
                f"{sorted(shapes)}, windows {sorted(windows)} and "
                f"indexers {sorted(selects)}"
            )
        return (*shapes.pop(), windows.pop() if windows else None)

    def _build_grouped_pools(self, num_pages, kvh, hd):
        """The grouped layout's pools, a budget a layer kind. A full
        layer's pages come from ``num_pages`` (``_kv_alloc``: the budget
        that grows with a request). A window layer reads the last
        ``window`` positions and nothing else, so a slot holds a RING of
        ``ceil(window / page_size) + 1`` pages there (the window, plus
        the page of the write frontier), logical page ``p`` in column
        ``p % ring`` of the slot's window table, overwritten in place
        once it lies behind the window; the window pool is sized here to
        ``num_slots`` rings, so whenever a slot is free its ring is too.
        Every pool is ``(pages x page_size, Hkv x Dh)``, the row-major
        flattening of ``(pages, page_size, Hkv, Dh)``: a head is a
        lane-aligned slice of a row, which is how the kernel takes a K/V
        head's keys out of a copied page (``ops/paged_attention.py``).

        A block that selects has a THIRD pool, its selector keys: one
        key of ``select["head_dim"]`` values a token, in the pages of the
        same table and budget as the keys and values (reserved, released
        and counted with them). A page's keys lie side by side in the
        pool's leading index (``_index_page``): ``(pages, page_size x
        Di)``, or where the step's kernel reads them in place ``(pages,
        page_size x Di / 128, 128)``, the same values in the same order
        as whole tiles: at 64 values a key a token costs its 128 bytes,
        and not a 128-lane row of 256, a page is ONE index to gather, and
        one tile-aligned copy of the kernel's."""
        import jax.numpy as jnp

        from distkeras_tpu.serving.paging import PageAllocator

        ps = self.page_size
        window_pages = 0
        if self._window is not None:
            self._ring = -(-self._window // ps) + 1
            window_pages = self.num_slots * self._ring + 1
            self._window_alloc = PageAllocator(
                window_pages, ps, recorder=self.recorder)
            self._window_tables = [[] for _ in range(self.num_slots)]
        return [
            tuple(
                jnp.zeros(
                    ((num_pages if blk.window is None else window_pages)
                     * ps, kvh * hd),
                    self._gen.kv_dtype,
                )
                for _ in range(2)
            ) + ((
                jnp.zeros((num_pages, *self._index_page),
                          self._gen.kv_dtype),
            ) if blk.select else ())
            for blk in self._gen._blocks
        ]

    def _build_hybrid_pools(self, num_pages, kvh, hd):
        """Pools that differ by layer. A layer that says ``slot_state``
        (a state and a convolution tail) gets those arrays with the slot
        as their leading index: ``(num_slots, H, P, N)`` and ``(num_slots,
        K - 1, C)``, float32 both (``slot_state`` gives the dtypes). They are
        indexed by SLOT and not through the table: every sequence has
        exactly one of each, whatever its length, every step rewrites it
        whole, and nothing of it is ever shared, so a page table would
        name one fixed page a slot. A slot takes them with the slot and
        gives them back with it; no allocator. A layer that caches rows
        gets the grouped layout's flat K/V pools, ``(pages x page_size,
        Hkv x Dh)``, from the one budget ``num_pages``."""
        import jax.numpy as jnp

        if self._window is not None or self._select:
            raise ValueError(
                "beside layers that hold a state a slot, the layers that "
                "cache rows have no window and no indexer yet")
        return [
            tuple(jnp.zeros((self.num_slots, *shape), dt)
                  for shape, dt in blk.slot_state)
            if getattr(blk, "slot_state", None) else
            tuple(jnp.zeros((num_pages * self.page_size, kvh * hd),
                            self._gen.kv_dtype) for _ in range(2))
            for blk in self._gen._blocks
        ]

    @property
    def _index_page(self) -> tuple:
        """A page of the selector pool as it is held, the ``page_size x
        Di`` values of its keys in order: one row where the step gathers
        them (a gather out of the device's memory costs by the row, 10 to
        23 ns each up to 2 KB, PERF.md, PR 39: a page a row is an eighth
        of the rows of two keys a row), rows of 128 lanes that are whole
        tiles where ``paged_index_scores`` copies a slot's own pages and
        gathers nothing (``self.selector == "kernel"``, PR 40). The chunk
        gathers one slot's pages by the leading index either way."""
        from distkeras_tpu.ops.paged_attention import index_page_shape

        di = self._select["head_dim"]
        if self.selector == "kernel":
            return index_page_shape(self.page_size, di)
        return (self.page_size * di,)

    # -- the latent-attention block ------------------------------------------

    @staticmethod
    def _latent_row(blk) -> int:
        """Values a pool row holds: the block's latent width rounded up
        to the TPU's lane width."""
        return -(-blk.latent_width // 128) * 128

    @staticmethod
    def _pad_row(new, pool):
        """Latent rows ``(n, latent_width)`` as the pool holds them."""
        import jax.numpy as jnp

        pad = pool.shape[-1] - new.shape[-1]
        return jnp.pad(new.astype(pool.dtype), ((0, 0), (0, pad)))

    def _note_routing(self, fetched, n_active, span):
        """Split the step's fetch into its tokens and the expert layers'
        counters (``models.mla_moe.routing_counts``); sum them for
        ``stats()["moe"]`` and put them on the ``serving/collect`` span.
        ``experts_hit`` is the distinct held routed experts that some
        active slot's token reached, a mean over the expert layers;
        ``expert_load_max`` the largest token count on one expert;
        ``zero_picks`` and ``held_picks`` how many of the active slots'
        ``picks`` (tokens x top_k x expert layers) took an identity
        expert and a held routed expert; ``overflow_passes`` how many
        expert layers held more rows than one pass of
        ``routed_experts`` takes (a fifth counter, which a program whose
        layers hold every expert does not send: 0)."""
        toks, counts = fetched[:self.num_slots], fetched[self.num_slots:]
        hit_sum, load_max, zero, held, *over = (int(c) for c in counts)
        over = sum(over)
        hit = hit_sum / self._moe_layers
        m = self.moe_stats
        self.moe_stats = {
            **m, "steps": m["steps"] + 1,
            "experts_hit_sum": m["experts_hit_sum"] + hit,
            "expert_load_max_sum": m["expert_load_max_sum"] + load_max,
            "routed_tokens": m["routed_tokens"] + n_active,
            "zero_picks": m["zero_picks"] + zero,
            "held_picks": m["held_picks"] + held,
            "overflow_passes": m["overflow_passes"] + over,
        }
        span.set_metadata(
            experts_hit=hit, expert_load_max=load_max,
            experts_total=m["experts_total"], routed_tokens=n_active,
            zero_picks=zero, held_picks=held, overflow_passes=over,
            picks=n_active * self._moe_layers
            * self._gen._blocks[-1].top_k,
        )
        return toks

    def _note_selection(self, active, span):
        """A selecting block's counters of one decode step, from the
        host's own lengths (no fetch): ``keys_cached``, the cached
        positions the active slots' queries could see, and
        ``keys_selected``, those they read (``min(cached, topk)`` each);
        on the ``serving/collect`` span and summed for
        ``stats()["select"]``."""
        cached = self._lens[active].astype(np.int64)
        seen = int(cached.sum())
        read = int(np.minimum(cached, self._select["topk"]).sum())
        m = self.select_stats
        self.select_stats = {
            "steps": m["steps"] + 1, "keys_cached": m["keys_cached"] + seen,
            "keys_selected": m["keys_selected"] + read,
        }
        span.set_metadata(keys_cached=seen, keys_selected=read)

    def _state_bytes(self, active) -> int:
        """The bytes of state one decode step reads and writes: every
        decoding slot's state, in and out, over the layers that hold one;
        from the host's own count, no fetch."""
        return int(np.count_nonzero(active)) * self._state_bytes_a_step_slot

    def _note_state(self, active, span):
        """The state layers' counters of one collected step: the bytes of
        state it moved (``_state_bytes``) summed for ``stats()["state"]``,
        and on the ``serving/collect`` span the slots whose state an
        admission reset since the last collect."""
        m, new = self.state_stats, self._state_resets_new
        self._state_resets_new = 0
        self.state_stats = {
            "steps": m["steps"] + 1,
            "state_bytes": m["state_bytes"] + self._state_bytes(active),
            "resets": m["resets"] + new,
        }
        span.set_metadata(state_resets=new)

    def kv_shard_bytes(self) -> int:
        """K/V bytes RESIDENT PER SHARD — the number a capacity planner
        compares against one device's HBM."""
        return self.kv_bytes_total() // self.mesh_devices

    # -- per-slot sampler state ---------------------------------------------

    def _reset_slot_sampling(self, slot):
        """Park a slot on the engine-wide default params (greedy unless
        the engine was constructed with a temperature)."""
        self.set_sampling(slot, None)

    def set_sampling(self, slot, params, completion=0, eos_id=None):
        """Bind ``params`` (None = the engine default) to ``slot``:
        the vectorized per-slot arrays the step/verify programs read,
        the emitted-position RNG counter (reset to 0 — admission IS
        the replay boundary), and a fresh grammar mask state when the
        params carry one. ``completion`` derives the slot's seed
        (``sampling.seed_for_completion``) so n-parallel completions
        diverge while completion 0 stays the solo reference."""
        from distkeras_tpu.serving.sampling import seed_for_completion

        p = params if params is not None else self.default_sampling
        self._slot_params[slot] = p
        self._temps[slot] = p.temperature
        self._topk[slot] = 0 if p.top_k is None else p.top_k
        self._topp[slot] = 1.0 if p.top_p is None else p.top_p
        self._seeds[slot] = seed_for_completion(p.seed, completion)
        self._spos[slot] = 0
        self._grammar.pop(slot, None)
        if p.grammar is not None:
            self._grammar[slot] = self._mask_compiler.compile(
                p.grammar, eos_id=eos_id
            )

    def _build_tmask(self, active):
        """The (B, V) additive grammar mask for this step — None when
        no ACTIVE slot is constrained (the unmasked program then runs:
        greedy/sampled traffic never pays for grammar support). A mask
        that zeroes out every candidate falls back to forced-EOS
        (request eos when known, else unconstrained) — recorded on the
        flight tape, never a hang."""
        if not self._grammar:
            return None
        rows = [i for i in self._grammar if active[i]]
        if not rows:
            return None
        v = self._gen._emb.vocab_size
        tm = np.zeros((self.num_slots, v), np.float32)
        for i in rows:
            st = self._grammar[i]
            allow = np.asarray(st.mask(), bool)
            if not allow.any():
                self.mask_exhaustions += 1
                if self.recorder is not None:
                    self.recorder.record(
                        "sampling.mask_exhausted", slot=i,
                        pos=int(self._spos[i]),
                    )
                eos = st.eos_id
                allow = np.zeros(v, bool)
                if eos is not None and 0 <= int(eos) < v:
                    allow[int(eos)] = True  # forced-EOS fallback
                else:
                    allow[:] = True  # no eos known: unconstrain
            tm[i] = np.where(allow, 0.0, -np.inf)
            self.constrained_masks += 1
        return tm

    def _advance_grammar(self, toks, counts):
        """Consume the emitted tokens into each constrained slot's mask
        state (``toks`` (B, w) with ``counts[i]`` real entries)."""
        for i, st in self._grammar.items():
            for j in range(int(counts[i])):
                st.advance(int(toks[i, j]))

    def _sampling_args(self, owed=0):
        """The per-slot sampler arrays every step/verify call passes
        (fresh copies: the device call must see this iteration's
        snapshot even if host bookkeeping advances meanwhile).
        ``owed``: what the steps still in the air will add to the
        sample positions (``_owed``)."""
        return (
            self._temps.copy(), self._topk.copy(), self._topp.copy(),
            self._seeds.copy(), self._spos + owed,
        )

    def _owed(self):
        """Per slot, the advance that the steps in the air still owe
        (each adds one to length and sample position at its collect):
        a step dispatched behind them passes lengths and positions as
        they WILL be. 0 (the scalar) with nothing in the air."""
        if not self._air:
            return 0
        return sum(h.owed().astype(np.int32) for h in self._air)

    @property
    def constrained_slots(self):
        """The slots whose next token mask is built from the token
        before it: the scheduler collects a step before it dispatches
        the next while one of them decodes."""
        return self._grammar.keys()

    @property
    def can_fork(self) -> bool:
        """Whether n-parallel completions can be scheduled here
        (``fork_slot`` needs the paged CoW machinery, and its page copy
        knows (p, H, Dh) pages only)."""
        return self.paged and not self._paged_only

    def fork_pages_for(self, prompt_len: int, max_new: int) -> int:
        """FRESH pages one fork of a just-prefilled slot allocates
        (full history pages below the frontier are shared) — what the
        scheduler adds per extra completion when gating a group
        admission on the pool."""
        need = self.pages_for(prompt_len, max_new)
        frontier = (max(1, int(prompt_len)) - 1) // self.page_size
        return max(0, need - frontier)

    # -- param plumbing -----------------------------------------------------

    def _unpack(self, params):
        """Per-stage (block, MoE) param groups + embed/ln/head groups,
        keyed by layer index exactly as ``_decode_prologue`` does."""
        n_layers = len(self.model.layers)
        bp = [
            (params[str(bi)], None if mi is None else params[str(mi)])
            for (_, bi, _, mi) in self._gen._stages
        ]
        # a head that is another layer's table (a tied head: the
        # embedding's) says whose parameters it reads: ``params_of``
        head_of = getattr(self._gen._head, "params_of", n_layers - 1)
        return (
            bp,
            params["0"],
            params[str(n_layers - 2)],
            params[str(head_of)],
        )

    def _embed(self, p_emb, tok, pos):
        """Embed (B,) tokens at per-slot (B,) positions (clamped to the
        table like the generator's embed closure)."""
        import jax.numpy as jnp

        x = p_emb["tokens"][tok]
        scale = getattr(self._gen._emb, "multiplier", 1.0)
        if scale != 1.0:
            x = x * scale
        if "positions" in p_emb:
            n_pos = p_emb["positions"].shape[0]
            x = x + p_emb["positions"][jnp.minimum(pos, n_pos - 1)]
        return x

    # -- admission ----------------------------------------------------------

    def admit(self, slot: int, prompt, max_new=None, sampling=None,
              eos_id=None) -> None:
        """One-shot admission: ``begin_admit`` plus prefill drained to
        completion in a single call (the unlimited-budget degenerate of
        the chunked lifecycle — what the PR 1 scheduler always did)."""
        left = self.begin_admit(
            slot, prompt, max_new=max_new, sampling=sampling,
            eos_id=eos_id,
        )
        while left > 0:
            left = self.prefill_chunk(slot, left)

    def pages_for(self, prompt_len: int, max_new: int) -> int:
        """Pages a request needs end to end: its prompt plus decode
        budget (plus the speculative scratch window), page-rounded —
        what admission reserves and what the scheduler gates on."""
        need = int(prompt_len) + int(max_new)
        if self.drafter is not None:
            need += self._kb + 1  # verify writes walk into scratch
        need = min(need, self._tp)
        return max(1, -(-need // self.page_size))

    @property
    def free_pages(self) -> int:
        return self._kv_alloc.free_pages if self.paged else 1 << 30

    @property
    def available_pages(self) -> int:
        """What admission can actually obtain: the free list PLUS
        pages the device prefix index holds alone (reclaimed under
        pressure — cached prefixes never starve live traffic)."""
        if not self.paged:
            return 1 << 30
        n = self._kv_alloc.free_pages
        if self.prefix_index is not None:
            n += self.prefix_index.reclaimable()
        return n

    @property
    def total_pages(self) -> int:
        return self._kv_alloc.total_pages if self.paged else 1 << 30

    def _alloc_pages(self, n: int, reason: str) -> list[int]:
        """Allocate with pool-pressure reclaim: shed LRU device-prefix
        entries before refusing — exhaustion means LIVE demand exceeds
        the pool, not that the cache filled it."""
        deficit = n - self._kv_alloc.free_pages
        if deficit > 0 and self.prefix_index is not None:
            self.prefix_index.reclaim(deficit)
        return self._kv_alloc.alloc(n, reason=reason)

    def _record_prefix_error(self, op: str, exc: BaseException, slot):
        """The prefix cache is best-effort, but a degraded lookup or
        insert must leave its EXCEPTION CLASS on the tape — a store
        that is silently failing every call looks identical to a cold
        one from the counters alone."""
        self.prefix_fetch_failures += 1
        if self.recorder is not None:
            self.recorder.record(
                "prefix_cache.error", op=op,
                error=type(exc).__name__, detail=repr(exc)[:200],
                slot=slot,
            )

    def begin_admit(self, slot: int, prompt, max_new=None,
                    sampling=None, eos_id=None) -> int:
        """Start admitting ``prompt`` into ``slot``: write its context
        row, restore the longest ``prefix_cache`` hit's K/V rows, and
        return the number of prefill positions STILL to compute (0 =
        ready to decode). ``prefill_chunk`` advances the remainder —
        the scheduler spreads it over iterations so a long prompt never
        stalls the decoding slots beyond its per-iteration budget.

        ``sampling``: this request's ``SamplingParams`` (None = the
        engine default). Admission resets the slot's emitted-position
        RNG counter, which is what makes any re-admission of the same
        (prompt, params) — retry after restart, quarantine
        re-verification, another replica — replay token-identically.
        ``eos_id`` feeds the grammar mask state's forced-EOS fallback.

        Paged mode additionally RESERVES the slot's page table first
        (``max_new`` bounds the reservation; None reserves to capacity)
        — sharing any device-resident prefix hit's full pages, falling
        back to the host ladder — and raises the typed, retriable
        ``PoolExhaustedError`` BEFORE any slot state mutates when the
        pool cannot cover it. That nothing-mutated guarantee holds for
        a RELEASED slot (the scheduler path, which always releases
        before reuse); re-admitting over a still-held slot first frees
        its previous table (a test-drive convenience, not a resumable
        path)."""
        self._fire("stepper.prefill", slot=slot)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = prompt.size
        if not 1 <= plen <= self.max_len:
            raise ValueError(
                f"prompt length {plen} outside [1, {self.max_len}]"
            )
        target = plen - 1  # prefill covers positions 0..plen-2
        start = 0
        host_hit = None
        if self.paged:
            start, host_hit = self._reserve_pages(
                slot, prompt, plen, max_new
            )
        elif self.prefix_cache is not None and target >= 1:
            try:
                host_hit = self.prefix_cache.lookup(prompt[:target])
            except Exception as e:  # noqa: BLE001 — cache is best-effort
                self._record_prefix_error("lookup", e, slot)
                host_hit = None  # a broken cache degrades to a miss
        # sampling binds AFTER the page reservation: a PoolExhausted
        # admission must leave the slot (sampler state included)
        # exactly as it was
        self.set_sampling(slot, sampling, eos_id=eos_id)
        row = np.zeros((1, self.max_len), np.int32)
        row[0, :plen] = prompt
        if self._row_fn is None:
            import jax

            self._compiling()
            self._row_fn = self._jit(
                lambda ctx, r, s: jax.lax.dynamic_update_slice(
                    ctx, r, (s, 0)
                ),
                donate=(0,), out="ctx", key="ctx_row",
            )
        self._ctx = self._row_fn(self._ctx, row, np.int32(slot))
        if host_hit is not None:
            start, kv = host_hit
            self._restore_prefix(slot, kv)
        self._pending[slot] = prompt
        self._prefill_pos[slot] = start
        if self._state_layers:
            # the slot's state starts from zero: the program that runs its
            # position 0 (the first chunk, or the step of a prompt of one
            # token) does it, by its ``where``; no host write
            self._state_resets_new += 1
        if self.drafter is not None:
            # kept for draft admission once the slot turns decodable;
            # the proposal cache is stale the moment slot composition
            # changes (a parked slot's length can collide with its
            # next occupant's)
            self._spec_prompts[slot] = prompt
            self._spec_pending = None
        self._lens[slot] = plen
        if start >= target:
            self._finish_admit(slot)
            return 0
        return target - start

    def _reserve_pages(self, slot, prompt, plen, max_new):
        """Paged admission's first act: decide the prefix-reuse source
        (device index vs host ladder — the LONGER coverage wins), build
        the slot's page table (shared full pages + fresh private
        pages), and reserve everything the request can ever write.
        Exhaustion raises ``PoolExhaustedError`` with every reference
        taken here released — nothing to roll back, no slot state has
        been touched yet. Returns ``(prefill_start, host_hit_or_None)``
        (a host hit is restored by the caller AFTER the table exists)."""
        target = plen - 1
        mnew = (self.max_len - plen) if max_new is None else int(max_new)
        need = self.pages_for(plen, max(1, mnew))
        if self._tables[slot]:
            # direct re-admission without release() (test drives);
            # the scheduler always releases first
            self._free_slot_pages(slot)
        start = 0
        shared: list[int] = []
        if self.prefix_index is not None and target >= self.page_size:
            hit = self.prefix_index.lookup(prompt[:target])
            if hit is not None:
                start, shared = hit  # pages already retained for us
        host_hit = None
        if self.prefix_cache is not None and target >= 1:
            try:
                host_hit = self.prefix_cache.lookup(prompt[:target])
            except Exception as e:  # noqa: BLE001 — cache is best-effort
                self._record_prefix_error("lookup", e, slot)
                host_hit = None
        if host_hit is not None and host_hit[0] <= start:
            host_hit = None  # device coverage already >= the rung
        if host_hit is not None and shared:
            # the host ladder reaches further than the device index —
            # a restore WRITES positions [0, p), so shared (immutable)
            # pages cannot back them; go all-private
            self._kv_alloc.free(shared, reason="admit_host_override")
            start, shared = 0, []
        try:
            fresh = self._alloc_pages(need - len(shared), "admit")
        except Exception:
            if shared:
                self._kv_alloc.free(shared, reason="admit_abort")
            raise
        if self._window_alloc is not None:
            # the second budget: a ring that does not grow with the
            # request. All or nothing over both: a ring that cannot be
            # had gives the growing pages back
            try:
                self._window_tables[slot] = self._window_alloc.alloc(
                    min(self._ring, need), reason="admit"
                )
            except Exception:
                self._kv_alloc.free(shared + fresh, reason="admit_abort")
                raise
        self._tables[slot] = shared + fresh
        return start, host_hit

    def _free_slot_pages(self, slot):
        pages = self._tables[slot]
        self._tables[slot] = []
        if pages:
            self._kv_alloc.free(pages, reason="release")
        if self._window_alloc is not None:
            pages = self._window_tables[slot]
            self._window_tables[slot] = []
            if pages:
                self._window_alloc.free(pages, reason="release")

    @property
    def window_pages(self):
        """``(in use, total)`` of the window layers' pool; None where no
        layer has a window."""
        if not self.paged or self._window_alloc is None:
            return None
        a = self._window_alloc
        return a.pages_in_use, a.total_pages

    def fork_slot(self, src: int, dst: int, max_new=None,
                  completion=1) -> None:
        """Copy-on-write fork: ``dst`` becomes a divergent continuation
        of ``src`` — n-parallel sampling and beam candidates pay only
        their divergent pages instead of a full-cache copy. Full pages
        strictly below the write frontier (position ``len-1``, where
        the next step's K/V lands) are SHARED into ``dst``'s table
        (refcount++, zero bytes); the partial frontier page, if any, is
        device-copied (the one CoW copy divergence costs); the rest of
        ``dst``'s budget is fresh private pages. The context row and
        host length are copied, so both slots decode from the identical
        sequence state — and a greedy fork is pinned token-identical to
        its source's solo decode. ``src`` must be a DECODING slot (not
        mid-prefill); ``dst`` must be free. Raises ``PoolExhaustedError``
        (nothing mutated) when the pool cannot cover the fork.

        ``completion``: the fork's completion index within its request
        — ``dst`` copies ``src``'s sampling params and emitted-position
        counter but samples under ``seed_for_completion(seed,
        completion)``, so its stream is exactly what an independent
        admission with that derived seed would produce (grammar mask
        state is CLONED: each completion walks the grammar alone)."""
        self._refuse_unsupported("fork / beam (copy-on-write page forks)")
        if not self.paged:
            raise ValueError("fork_slot requires paged=True")
        if src in self._pending or not self._tables[src]:
            raise ValueError(
                f"slot {src} is not a decodable admitted slot"
            )
        if self._tables[dst]:
            raise ValueError(f"slot {dst} already holds pages")
        ln = int(self._lens[src])
        ps = self.page_size
        mnew = (self.max_len - ln) if max_new is None else int(max_new)
        need = self.pages_for(ln, max(1, mnew))
        frontier = (ln - 1) // ps  # page the next K/V write lands in
        shared = list(self._tables[src][:frontier])
        self._kv_alloc.share(shared)
        try:
            fresh = self._alloc_pages(max(0, need - frontier), "fork")
        except Exception:
            if shared:
                self._kv_alloc.free(shared, reason="fork_abort")
            raise
        table = shared + fresh
        if (ln - 1) % ps != 0 and frontier < len(self._tables[src]):
            # the frontier page holds positions frontier*ps .. len-2 of
            # the shared history: copy it so src and dst can diverge
            src_pg = self._tables[src][frontier]
            if self._page_copy_fn is None:
                import jax

                self._compiling()
                self._page_copy_fn = self._jit(
                    lambda pools, s, d: [
                        (ck.at[d].set(ck[s]), cv.at[d].set(cv[s]))
                        for ck, cv in pools
                    ],
                    donate=(0,), out="kv", key="page_cow",
                )
            with annotate("serving/page_cow"):
                self._pools = self._page_copy_fn(
                    self._pools, np.int32(src_pg),
                    np.int32(table[frontier]),
                )
            self._kv_alloc.note_cow(src_pg, table[frontier])
        self._tables[dst] = table
        if self._row_copy_fn is None:
            import jax

            self._compiling()
            self._row_copy_fn = self._jit(
                lambda ctx, s, d: ctx.at[d].set(ctx[s]),
                donate=(0,), out="ctx", key="ctx_row_copy",
            )
        self._ctx = self._row_copy_fn(
            self._ctx, np.int32(src), np.int32(dst)
        )
        self._lens[dst] = ln
        # divergence is the SEED: dst copies src's sampler state and
        # position counter, keyed to its own completion stream
        from distkeras_tpu.serving.sampling import seed_for_completion

        src_p = self._slot_params[src] or self.default_sampling
        self._slot_params[dst] = src_p
        self._temps[dst] = self._temps[src]
        self._topk[dst] = self._topk[src]
        self._topp[dst] = self._topp[src]
        self._seeds[dst] = seed_for_completion(src_p.seed, completion)
        self._spos[dst] = self._spos[src]
        if src in self._grammar:
            self._grammar[dst] = self._grammar[src].clone()
        else:
            self._grammar.pop(dst, None)
        if self.drafter is not None:
            sp = self._spec_prompts.get(src)
            if sp is not None:
                self._spec_prompts[dst] = sp
            # the draft bank holds no K/V for the tokens src decoded
            # before the fork, so a lazily-admitted draft for dst would
            # propose from garbage positions (junk that verify rejects
            # — correct output, pure overhead). Mark dst admitted and
            # INVALID: model drafters skip it (plain-decode pace until
            # its next real admission); host-sequence drafters (ngram)
            # ignore invalidate and keep proposing from the true tokens.
            self._spec_admitted.add(dst)
            self.drafter.invalidate(np.arange(self.num_slots) == dst)
            self._spec_pending = None

    # -- preemption swap (multi-tenant QoS) ---------------------------------

    def swap_out(self, slot: int) -> dict:
        """Serialize a DECODABLE slot's live state to host memory —
        the preemption path's first half. Fetches the slot's written
        K/V cache positions (``0 .. len-2``) per stage in the SAME
        host row format the ``PrefixStore`` ladder serializes
        (per-stage ``(p, H, Dh)`` numpy in ``kv_dtype`` — bit-exact,
        so restore reproduces the device state and the resumed stream
        stays token-identical to an uninterrupted decode), plus the
        context row, host length, and the sampler/grammar state the
        position-keyed RNG needs to continue mid-stream.

        READ-ONLY: no slot state mutates here — the caller (the
        scheduler) releases the slot (freeing its pages) only after a
        successful swap-out, so a failure at the ``kv.swap`` seam
        leaves the victim decoding untouched. The returned dict rides
        the preempted request; dropping it (typed failure, stop) is
        the only cleanup."""
        self._refuse_unsupported("swap-out / preemption / K/V export")
        self._fire("kv.swap", slot=slot, direction="out")
        if slot in self._pending:
            raise ValueError(
                f"slot {slot} is mid-prefill; only decodable slots "
                "can be swapped out"
            )
        ln = int(self._lens[slot])
        if ln > self.max_len:
            raise ValueError(
                f"slot {slot} context ({ln}) has walked past the "
                f"prompt row ({self.max_len}); not swappable"
            )
        p = ln - 1  # written cache positions
        nh, hd = self._nh, self._hd
        if p < 1:
            kv = [
                (
                    np.zeros((0, nh, hd), np.dtype(self._gen.kv_dtype)),
                    np.zeros((0, nh, hd), np.dtype(self._gen.kv_dtype)),
                )
                for _ in self._gen._stages
            ]
        elif self.paged:
            npg = -(-p // self.page_size)
            pages = np.asarray(self._tables[slot][:npg], np.int32)
            kv = [
                (
                    np.asarray(ck[pages]).reshape(-1, nh, hd)[:p].copy(),
                    np.asarray(cv[pages]).reshape(-1, nh, hd)[:p].copy(),
                )
                for ck, cv in self._pools
            ]
        else:
            kv = [
                (
                    np.asarray(ck[slot, :p]).copy(),
                    np.asarray(cv[slot, :p]).copy(),
                )
                for ck, cv in self._caches
            ]
        return {
            "len": ln,
            "ctx": np.asarray(self._ctx[slot, :ln]).copy(),
            "kv": kv,
            "spos": int(self._spos[slot]),
            "seed": int(self._seeds[slot]),
            "params": self._slot_params[slot],
            "grammar": self._grammar.get(slot),
            "spec_prompt": self._spec_prompts.get(slot),
        }

    def swap_in(self, slot: int, state: dict, max_new=None) -> None:
        """Restore a swapped-out request into a FREE slot — resume is
        re-reserve + restore. Paged mode first reserves the full page
        budget (``len + remaining`` positions — the same total the
        original admission reserved; all PRIVATE pages, since the
        restore writes every position); exhaustion raises the typed
        retriable ``PoolExhaustedError`` BEFORE any slot state
        mutates. Then the context row and the host K/V rows are
        written back through the same bucketed restore programs a
        prefix-cache hit uses, and the host length + sampler counter
        resume exactly where the swap-out left them — the next step
        computes precisely what an uninterrupted decode would have
        (garbage at positions >= len-1 is overwritten by that step's
        own K/V write before anything attends it, the standing
        restore argument)."""
        self._refuse_unsupported("swap-in / resume of exported K/V")
        self._fire("kv.swap", slot=slot, direction="in")
        ln = int(state["len"])
        remaining = (
            (self.max_len - ln) if max_new is None else int(max_new)
        )
        if self.paged:
            if self._tables[slot]:
                self._free_slot_pages(slot)
            need = self.pages_for(ln, max(1, remaining))
            self._tables[slot] = self._alloc_pages(need, "swap_in")
        row = np.zeros((1, self.max_len), np.int32)
        row[0, :ln] = state["ctx"]
        if self._row_fn is None:
            import jax

            self._compiling()
            self._row_fn = self._jit(
                lambda ctx, r, s: jax.lax.dynamic_update_slice(
                    ctx, r, (s, 0)
                ),
                donate=(0,), out="ctx", key="ctx_row",
            )
        self._ctx = self._row_fn(self._ctx, row, np.int32(slot))
        if state["kv"][0][0].shape[0] >= 1:
            self._restore_prefix(slot, state["kv"])
        self._lens[slot] = ln
        # sampler state resumes mid-stream: the position-keyed RNG
        # continues from the exact emitted-token counter, so a sampled
        # stream's post-resume draws equal the uninterrupted ones
        p = state["params"] if state["params"] is not None else (
            self.default_sampling
        )
        self._slot_params[slot] = p
        self._temps[slot] = p.temperature
        self._topk[slot] = 0 if p.top_k is None else p.top_k
        self._topp[slot] = 1.0 if p.top_p is None else p.top_p
        self._seeds[slot] = state["seed"]
        self._spos[slot] = state["spos"]
        if state["grammar"] is not None:
            self._grammar[slot] = state["grammar"]
        else:
            self._grammar.pop(slot, None)
        self._pending.pop(slot, None)
        self._prefill_pos.pop(slot, None)
        if self.drafter is not None:
            # like fork_slot: the draft bank holds no K/V for this
            # stream, so mark the slot admitted but INVALID — model
            # drafters stop proposing (plain-decode pace), host-
            # sequence drafters (ngram) keep working from true tokens
            if state["spec_prompt"] is not None:
                self._spec_prompts[slot] = state["spec_prompt"]
            self._spec_admitted.add(slot)
            self.drafter.invalidate(np.arange(self.num_slots) == slot)
            self._spec_pending = None

    def prefill_chunk(self, slot: int, budget: int) -> int:
        """Prefill up to ``budget`` more positions of ``slot``'s pending
        prompt; returns positions remaining (0 = ready to decode). A
        chunk covering the WHOLE prefix from position 0 takes the
        original bucketed full-prefill program; a mid-prompt chunk runs
        the generators' ``_stage_chunk`` body against the slot's
        existing cache rows. Chunk lengths bucket to powers of two —
        garbage K/V computed past the chunk's real tokens sits at
        positions >= the prefill frontier and is overwritten (by the
        next chunk or the decode steps) before any query attends it."""
        self._fire("stepper.prefill", slot=slot)
        prompt = self._pending.get(slot)
        if prompt is None:
            # admission cancelled underneath us (release() raced this
            # call from stop/evict) — report done, never crash the
            # engine loop over a benign shutdown race
            return 0
        target = prompt.size - 1
        pos = self._prefill_pos[slot]
        n = min(int(budget), target - pos)
        if n > 0:
            if self.paged:
                # one program family: every chunk (including a whole
                # prefix from 0) runs the paged gather/scatter chunk
                n = self._prefill_mid(slot, prompt, pos, n)
            elif pos == 0 and n == target:
                self._prefill_full(slot, prompt)
            else:
                n = self._prefill_mid(slot, prompt, pos, n)
            pos += n
            self._prefill_pos[slot] = pos
        if pos >= target:
            self._finish_admit(slot)
            return 0
        return target - pos

    def _prefill_full(self, slot, prompt):
        """Whole-prefix prefill in one program (bucketed pow2 key): a
        serving mix of naturally varying prompt lengths costs O(log T)
        compiles, not O(T)."""
        plen = prompt.size
        row = np.zeros((1, self.max_len), np.int32)
        row[0, :plen] = prompt
        pb = _bucket_pow2(plen - 1, self.max_len - 1)
        fn = self._admit_fns.get(pb)
        if fn is None:
            self._compiling()
            fn = self._build_admit_fn(pb)
            # copy-on-write: stats() iterates this dict from other
            # threads, so never mutate a published mapping in place
            self._admit_fns = {**self._admit_fns, pb: fn}
        with annotate("serving/prefill"):
            self._caches = fn(
                self._params, self._caches, row, np.int32(slot),
            )

    def _prefill_mid(self, slot, prompt, pos, n) -> int:
        """One mid-prompt chunk: positions ``pos..pos+n-1`` against the
        slot's live cache rows; returns the positions actually consumed.
        Chunk-program keys stay powers of two ALWAYS: when the bucket
        would run past the cache's time axis (a clamped
        ``dynamic_update_slice`` would silently shift onto real rows),
        the chunk SHRINKS to the largest pow2 that fits rather than
        compiling an arbitrary-length tail program — near-capacity
        traffic must not break the O(log T) compile discipline."""
        n = min(n, self.chunk_cap)
        cb = max(_bucket_pow2(n, self.max_len), self.chunk_floor)
        room = (
            len(self._tables[slot]) * self.page_size - pos
            if self.paged
            else self._tp - pos
        )
        if self._state_layers:
            # a chunk of a state layout is never built under its floor (a
            # bucket below it is one no warm-up compiled: a whole-depth
            # program minted by live traffic): what a bucket holds beyond
            # the slot's own pages are positions that do not exist, whose
            # rows land on the null page and which advance no state; the
            # table's own extent is what a bucket may not pass
            room = self._max_pages_bucket * self.page_size - pos
        if cb > room:
            cb = 1 << (room.bit_length() - 1)  # largest pow2 <= room
            n = min(n, cb)
        toks = np.zeros((1, cb), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        if self.paged:
            # chunk programs run at the FIXED full-capacity extent: the
            # cost is amortized per prompt token (and equals the dense
            # chunk's extent), while a per-table-bucket key would
            # multiply program shapes by arrival interleaving — a
            # mid-pass XLA compile costs more than the gather it saves.
            # The DYNAMIC extent lives in the per-token step program.
            pbt = self._max_pages_bucket
            key = (cb, pbt)
            fn = self._pchunk_fns.get(key)
            if fn is None:
                self._compiling()
                fn = self._build_chunk_fn_paged(cb, pbt)
                self._pchunk_fns = {**self._pchunk_fns, key: fn}
            host = (toks, self._chunk_where(slot, pbt, n), np.int32(pos))
            with _span(
                "serving/prefill_chunk",
                host_arg_bytes=self._host_arg_bytes(host), tokens=n,
                **({"grouped": self.grouped} if self.grouped else {}),
            ):
                self._pools = fn(self._params, self._pools, *host)
            return n
        fn = self._chunk_fns.get(cb)
        if fn is None:
            self._compiling()
            fn = self._build_chunk_fn(cb)
            self._chunk_fns = {**self._chunk_fns, cb: fn}
        host = (toks, np.int32(slot), np.int32(pos))
        with _span(
            "serving/prefill_chunk",
            host_arg_bytes=self._host_arg_bytes(host),
        ):
            self._caches = fn(self._params, self._caches, *host)
        return n

    def _table_bucket(self) -> int:
        """Pow2 bucket covering every OCCUPIED slot's table — the step
        / verify program key. Occupied (not active) so blame-probe
        masks never change the program mid-blame."""
        if self._one_step_extent:
            # the kernel reads a slot's own pages whatever the table's
            # width, and a selecting step the rows it picks: one step
            # program, at the widest table
            return self._max_pages_bucket
        m = max((len(t) for t in self._tables), default=0)
        return _bucket_pow2(max(1, m), self._max_pages_bucket)

    def _step_table_buckets(self) -> list[int]:
        """Every bucket ``_table_bucket`` can return: what the warm
        methods compile the step program at."""
        top = self._max_pages_bucket
        if self._one_step_extent:
            return [top]
        return [1 << i for i in range(top.bit_length())]

    @staticmethod
    def _padded(pages, width) -> np.ndarray:
        """``pages`` as a table row of ``width``, the null page behind."""
        row = np.zeros((width,), np.int32)
        row[: len(pages)] = pages
        return row

    def _table_row(self, slot, pbt) -> np.ndarray:
        return self._padded(self._tables[slot], pbt)

    def _chunk_where(self, slot, pbt, n):
        """The chunk program's ``where``: the slot's page-table row; with
        a window budget ``(row, ring row, n)``, a table a layer kind and
        the chunk's count of real tokens (a ring takes no write that is
        not a real token's: behind it lies what the window still reads)."""
        row = self._table_row(slot, pbt)
        if self._state_layers:
            # a state is indexed by slot, and not advanced by padding
            return row, np.int32(slot), np.int32(n)
        if self._window_alloc is None:
            return row
        ring = self._padded(self._window_tables[slot], self._ring)
        return row, ring, np.int32(n)

    def _tables_array(self, pbt):
        """The (B, pbt) page-table argument of the step / verify
        programs; rows pad with the null sentinel page 0 (masked). With
        a window budget ``(tables, rings (B, ring))``: a table a layer
        kind."""
        def filled(tables, width):
            arr = np.zeros((self.num_slots, width), np.int32)
            for i, pages in enumerate(tables):
                arr[i, : len(pages)] = pages
            return arr

        arr = filled(self._tables, pbt)
        if self._window_alloc is None:
            return arr
        return arr, filled(self._window_tables, self._ring)

    def _finish_admit(self, slot):
        """Admission complete: drop the pending state and publish the
        finished prefix's missing pow2 ladder rungs to the store. The
        device->host K/V fetch happens ONLY when a rung is actually
        missing (and only up to the longest missing rung), so steady-
        state traffic over warmed prefixes costs zero transfers."""
        prompt = self._pending.pop(slot, None)
        self._prefill_pos.pop(slot, None)
        if prompt is None:
            return  # release() raced the final chunk; nothing to publish
        target = prompt.size - 1
        if self.paged and self.prefix_index is not None and target >= 1:
            # device-resident sharing: register the prompt's FULL pages
            # strictly below the write frontier (the slot only writes
            # at/past position ``target``, so these pages are immutable
            # from here on). Zero transfers — the index just retains
            # the page ids.
            m = target // self.page_size
            if m >= 1:
                self.prefix_index.insert(
                    prompt[:target], self._tables[slot][:m]
                )
        store = self.prefix_cache
        if store is None or target < 1:
            return
        try:
            missing = store.missing_rungs(prompt[:target])
            if not missing:
                return
            pmax = max(missing)
            with annotate("serving/prefix_insert"):
                if self.paged:
                    npg = -(-pmax // self.page_size)
                    pages = np.asarray(
                        self._tables[slot][:npg], np.int32
                    )
                    kv = [
                        (
                            np.asarray(ck[pages]).reshape(
                                -1, self._nh, self._hd
                            )[:pmax],
                            np.asarray(cv[pages]).reshape(
                                -1, self._nh, self._hd
                            )[:pmax],
                        )
                        for ck, cv in self._pools
                    ]
                else:
                    kv = [
                        (
                            np.asarray(ck[slot, :pmax]),
                            np.asarray(cv[slot, :pmax]),
                        )
                        for ck, cv in self._caches
                    ]
                store.insert_prefixes(prompt[:target], kv)
        except Exception as e:  # noqa: BLE001 — cache is best-effort
            # a store failure must never fail the (already fully
            # prefilled) request; it just forgoes the reuse
            self._record_prefix_error("insert", e, slot)

    def _restore_prefix(self, slot, kv):
        """Copy a cache hit's host K/V rows into the slot (bucketed
        program key; bucket padding past the real prefix is garbage at
        positions >= the frontier, overwritten before it is attended)."""
        p = kv[0][0].shape[0]
        pb = min(_bucket_pow2(p, self.max_len), self.max_len)
        nh, hd = self._nh, self._hd
        ks = np.zeros((len(kv), pb, nh, hd), np.dtype(self._gen.kv_dtype))
        vs = np.zeros_like(ks)
        for si, (k, v) in enumerate(kv):
            ks[si, :p] = k
            vs[si, :p] = v
        if self.paged:
            pbt = self._max_pages_bucket  # fixed extent, like the chunks
            key = (pb, pbt)
            fn = self._pcopy_fns.get(key)
            if fn is None:
                self._compiling()
                fn = self._build_copy_fn_paged(pb, pbt)
                self._pcopy_fns = {**self._pcopy_fns, key: fn}
            with annotate("serving/prefix_copy"):
                self._pools = fn(
                    self._pools, ks, vs, self._table_row(slot, pbt)
                )
            return
        if self._copy_fn is None:
            self._compiling()
            self._copy_fn = self._build_copy_fn()
        with annotate("serving/prefix_copy"):
            self._caches = self._copy_fn(
                self._caches, ks, vs, np.int32(slot)
            )

    def release(self, slot: int) -> None:
        self._tenancy[slot] += 1  # steps in the air owe it nothing now
        self._lens[slot] = 1  # keep pos = lens-1 in range while parked
        self._pending.pop(slot, None)  # eviction mid-prefill
        self._prefill_pos.pop(slot, None)
        self._reset_slot_sampling(slot)  # parked slots sample nothing
        if self.paged:
            # a quarantined / evicted slot must give its pages back the
            # moment it leaves the bank (shared prefix pages survive
            # via the index's and other holders' refs)
            self._free_slot_pages(slot)
        self._spec_prompts.pop(slot, None)
        if slot in self._spec_admitted:
            self._spec_admitted.discard(slot)
            self._spec_pending = None
            self.drafter.release(slot)

    def warmup(self) -> None:
        """Compile the decode step off the serving path. The supervisor
        warms a REBUILT stepper before swapping it in, so the first
        live iteration after a restart does not spend the watchdog
        budget inside XLA (a ~1 s compile is indistinguishable from a
        wedge by heartbeat age alone). An all-inactive step call: every
        write is masked, so the slot bank is numerically untouched; the
        step-index argument is traced data, so the program is the same
        one live traffic uses. Deliberately does NOT route through
        ``step()`` — warmup must not trip armed ``stepper.step`` fault
        seams meant for live traffic.

        Compile-ledger semantics: everything minted inside this call
        records ``trigger="warmup"``. It deliberately does NOT call
        ``ledger.mark_warmed()`` — this method covers only the
        step/verify families (prefill buckets, restores, and grammar
        variants compile elsewhere), so declaring warmup COMPLETE is
        the harness's call, made explicitly after whatever warm set
        its traffic needs (``warm_prefill_buckets`` /
        ``warm_restore_buckets`` / ``warm_constrained_buckets``).
        From that mark on, a serving-path mint of a program signature
        no generation has ever compiled is a compile STORM
        (``xla.compile.storm`` on the tape + the
        ``serving_compile_storms`` gauge)."""
        with self._warm():
            self._warmup()

    @contextlib.contextmanager
    def _warm(self):
        """A warm method's body: whatever it mints records
        ``trigger="warmup"`` on the compile ledger, and one span names
        it on the profiler's timeline."""
        self._warming = True
        try:
            with annotate("serving/warmup"):
                yield
        finally:
            self._warming = False

    def _warmup(self) -> None:
        active = np.zeros(self.num_slots, bool)
        sargs = self._sampling_args()  # parked slots = greedy defaults
        if self.paged:
            # warm EVERY table bucket of the step program (the one
            # paged family with a dynamic extent): with the gather body
            # the bucket tracks the longest occupied table at runtime,
            # and a mid-serving bucket change must find its program
            # compiled — a live-path step compile is exactly the stall
            # paging must not reintroduce. O(log pages) programs, off
            # the serving path; ONE where the kernel attends (the
            # extent costs it nothing, so the table is always the
            # widest). Only the UNMASKED variants warm here: grammar
            # traffic is the rare case and its first mask may compile
            # on-path (graced via on_compile, like a fresh prefill
            # bucket).
            for pbt in self._step_table_buckets():
                fn = self._pstep_fns.get((pbt, False))
                if fn is None:
                    fn = self._build_step_fn_paged(pbt)
                    self._pstep_fns = {
                        **self._pstep_fns, (pbt, False): fn
                    }
                table = self._tables_array(pbt)  # an idle bank: zeros
                self._ctx, self._pools, _ = fn(
                    self._params, self._ctx, self._pools,
                    self._lens.copy(), active, table, *sargs,
                )
            if self.drafter is not None:
                key = (self._kb + 1, self._max_pages_bucket, False)
                vfn = self._pverify_fns.get(key)
                if vfn is None:
                    vfn = self._build_verify_fn_paged(*key)
                    self._pverify_fns = {**self._pverify_fns, key: vfn}
                self._ctx, self._pools, _, _ = vfn(
                    self._params, self._ctx, self._pools,
                    self._lens.copy(), active,
                    np.zeros((self.num_slots, self._kb), np.int32),
                    np.zeros((self.num_slots,), np.int32), table,
                    *sargs,
                )
                self.drafter.warmup()
            return
        fn = self._step_fns.get(False)
        if fn is None:
            fn = self._build_step_fn()
            self._step_fns = {**self._step_fns, False: fn}
        self._ctx, self._caches, _ = fn(
            self._params, self._ctx, self._caches,
            self._lens.copy(), active, None, *sargs,
        )
        if self.drafter is not None:
            # compile the verify (all writes masked: numerically a
            # no-op) and let the drafter warm its own programs, so a
            # supervisor restart never compiles on the serving path
            c = self._kb + 1
            fn = self._verify_fns.get((c, False))
            if fn is None:
                fn = self._build_verify_fn(c)
                self._verify_fns = {**self._verify_fns, (c, False): fn}
            self._ctx, self._caches, _, _ = fn(
                self._params, self._ctx, self._caches,
                self._lens.copy(), active,
                np.zeros((self.num_slots, self._kb), np.int32),
                np.zeros((self.num_slots,), np.int32), None, *sargs,
            )
            self.drafter.warmup()

    def warm_prefill_buckets(self) -> None:
        """Compile every pow2 admit / chunk-prefill bucket OFF the
        serving path. A serial warm drive CANNOT cover these: which
        chunk bucket a prefill hits depends on how the scheduler's
        per-iteration budget splits across concurrently-admitted
        prompts (a 3-deep prefill queue hands the second slot
        whatever budget the first left), so the bucket set is
        traffic-shape-dependent even for a fixed prompt mix — exactly
        the mid-serving mint class the compile ledger flags. O(log T)
        programs per family; mints record ``trigger="warmup"``. Only
        safe on an IDLE bank (the dense paths write masked-garbage
        rows through slot 0, overwritten before anything attends
        them — the standing restore argument)."""
        with self._warm():
            top = self.chunk_cap if self.paged else self.max_len
            cb = self.chunk_floor if self.paged else 1
            while True:
                cbb = min(cb, top)
                toks = np.zeros((1, cbb), np.int32)
                if self.paged:
                    # paged admission runs ONE program family (every
                    # chunk, whole-prefix included, is the paged
                    # gather/scatter chunk at fixed extent). Slot 0's
                    # table must be empty (the writes scatter into the
                    # null sentinel page): a non-idle bank SKIPS the
                    # bucket entirely — caching the built-but-never-
                    # executed fn would mark the family compiled, so
                    # the first live chunk would pay the mint without
                    # the _compiling() watchdog grace
                    if self._tables[0]:
                        if cb >= top:
                            break
                        cb <<= 1
                        continue
                    pbt = self._max_pages_bucket
                    key = (cbb, pbt)
                    fn = self._pchunk_fns.get(key)
                    if fn is None:
                        fn = self._build_chunk_fn_paged(cbb, pbt)
                        self._pchunk_fns = {
                            **self._pchunk_fns, key: fn
                        }
                    # empty table row -> null sentinel page
                    self._pools = fn(
                        self._params, self._pools, toks,
                        self._chunk_where(0, pbt, 0), np.int32(0),
                    )
                else:
                    fn = self._chunk_fns.get(cbb)
                    if fn is None:
                        fn = self._build_chunk_fn(cbb)
                        self._chunk_fns = {**self._chunk_fns, cbb: fn}
                    self._caches = fn(
                        self._params, self._caches, toks,
                        np.int32(0), np.int32(0),
                    )
                if cb >= top:
                    break
                cb <<= 1
            if not self.paged:
                # the dense whole-prefix (admit) family: pow2 buckets
                # clamped to max_len - 1 (the near-capacity bucket a
                # non-pow2 capacity keys on)
                pb, buckets = 1, set()
                while True:
                    buckets.add(min(pb, self.max_len - 1))
                    if pb >= self.max_len - 1:
                        break
                    pb <<= 1
                row = np.zeros((1, self.max_len), np.int32)
                for pb in sorted(b for b in buckets if b >= 1):
                    fn = self._admit_fns.get(pb)
                    if fn is None:
                        fn = self._build_admit_fn(pb)
                        self._admit_fns = {**self._admit_fns, pb: fn}
                    self._caches = fn(
                        self._params, self._caches, row,
                        np.int32(0),
                    )

    def warm_constrained_buckets(self) -> None:
        """Compile the grammar-MASKED step/verify variants off the
        serving path. ``warmup()`` deliberately skips these
        (unconstrained traffic must never pay for the grammar
        variants), which means a constrained mix under CHURNING
        occupancy mints them live: the paged STEP key tracks the
        longest OCCUPIED table, so which masked-step bucket an
        iteration needs is traffic-shape-dependent — exactly the
        mid-serving mint class the compile ledger flags. Verify
        windows always run at the fixed ``_max_pages_bucket`` extent,
        so only that bucket's masked/unmasked variants are warmed.
        Harnesses serving grammar/speculative traffic call this
        before ``mark_warmed()``; O(log pages) masked-step programs
        plus two verify variants. All writes masked (inactive bank):
        the slot bank is numerically untouched."""
        with self._warm():
            active = np.zeros(self.num_slots, bool)
            sargs = self._sampling_args()
            vocab = self._gen._emb.vocab_size
            tmask = np.zeros((self.num_slots, vocab), np.float32)
            cand = np.zeros((self.num_slots, self._kb), np.int32)
            cnt = np.zeros((self.num_slots,), np.int32)
            if not self.paged:
                fn = self._step_fns.get(True)
                if fn is None:
                    fn = self._build_step_fn(True)
                    self._step_fns = {**self._step_fns, True: fn}
                self._ctx, self._caches, _ = fn(
                    self._params, self._ctx, self._caches,
                    self._lens.copy(), active, None, *sargs, tmask,
                )
                if self.drafter is not None:
                    key = (self._kb + 1, True)
                    vfn = self._verify_fns.get(key)
                    if vfn is None:
                        vfn = self._build_verify_fn(*key)
                        self._verify_fns = {
                            **self._verify_fns, key: vfn
                        }
                    self._ctx, self._caches, _, _ = vfn(
                        self._params, self._ctx, self._caches,
                        self._lens.copy(), active, cand, cnt,
                        None, *sargs, tmask,
                    )
                return
            # the masked STEP tracks the longest OCCUPIED table, so
            # it needs every bucket the step can key on; verify
            # windows always run at the fixed _max_pages_bucket extent
            # (the live call site pins it), so warming verify at the
            # sub-max buckets would mint programs no iteration can
            # ever key on
            for pbt in self._step_table_buckets():
                table = self._tables_array(pbt)  # all slots inactive
                key = (pbt, True)
                fn = self._pstep_fns.get(key)
                if fn is None:
                    fn = self._build_step_fn_paged(pbt, True)
                    self._pstep_fns = {**self._pstep_fns, key: fn}
                self._ctx, self._pools, _ = fn(
                    self._params, self._ctx, self._pools,
                    self._lens.copy(), active, table, *sargs,
                    tmask,
                )
            if self.drafter is not None:
                pbt = self._max_pages_bucket
                table = np.zeros((self.num_slots, pbt), np.int32)
                # warmup() covers the unmasked max-bucket verify; the
                # MASKED variant is this method's contribution (warm
                # both anyway — harnesses may call this without
                # warmup(), and a warm re-mint costs nothing)
                for vmasked in (False, True):
                    vkey = (self._kb + 1, pbt, vmasked)
                    vfn = self._pverify_fns.get(vkey)
                    if vfn is None:
                        vfn = self._build_verify_fn_paged(*vkey)
                        self._pverify_fns = {
                            **self._pverify_fns, vkey: vfn
                        }
                    extra = (tmask,) if vmasked else ()
                    self._ctx, self._pools, _, _ = vfn(
                        self._params, self._ctx, self._pools,
                        self._lens.copy(), active, cand, cnt,
                        table, *sargs, *extra,
                    )

    def warm_restore_buckets(self) -> None:
        """Compile every pow2 swap-restore bucket OFF the serving
        path: which bucket a QoS resume (or a prefix-cache hit / a
        disagg ``resume``) needs depends on the victim's length at
        preempt time — timing-dependent, so without this warm a mint
        lands inside some interactive request's p99 (the exact ~240 ms
        stall PERF.md r16 measured before the QoS bench warmed these
        off-path; factored here from that bench so the soaks and any
        harness share one warm). Buckets: every power of two up to
        ``max_len`` plus the max_len-CLAMPED value a near-capacity
        restore keys on. Only safe on an IDLE bank — the dense path
        writes (masked-garbage) rows through slot 0. Mints record
        ``trigger="warmup"``."""
        with self._warm():
            dt = np.dtype(self._gen.kv_dtype)
            nh, hd = self._nh, self._hd
            pb, buckets = 1, set()
            while True:
                buckets.add(min(pb, self.max_len))
                if pb >= self.max_len:
                    break
                pb <<= 1
            for p in sorted(buckets):
                kv = [
                    (np.zeros((p, nh, hd), dt), np.zeros((p, nh, hd), dt))
                    for _ in self._gen._stages
                ]
                if self.paged and not self._tables[0]:
                    # an empty table row scatters into the null
                    # sentinel page — garbage there is unreachable by
                    # construction, so this is safe even mid-serving
                    self._restore_prefix(0, kv)
                elif not self.paged:
                    self._restore_prefix(0, kv)
            # the ctx-row write both swap_in and begin_admit share.
            # Only when not yet compiled (the write exists solely to
            # mint the program), and never over an occupied slot 0 —
            # zeroing a live request's context row would corrupt its
            # remaining decode, the exact class the paged restores
            # above guard against. Dense occupancy: ``release`` parks a
            # slot at lens == 1 (never 0 — pos = lens-1 must stay in
            # range), so lens > 1 means a live occupant and ``_pending``
            # covers the mid-prefill window; a ``> 0`` test here would
            # be unsatisfiable and silently skip the warm, handing the
            # mint to the first live admission as a compile storm
            occupied = (
                bool(self._tables[0]) if self.paged
                else (int(self._lens[0]) > 1 or 0 in self._pending)
            )
            if self._row_fn is None and not occupied:
                import jax

                self._compiling()
                self._row_fn = self._jit(
                    lambda ctx, r, s: jax.lax.dynamic_update_slice(
                        ctx, r, (s, 0)
                    ),
                    donate=(0,), out="ctx", key="ctx_row",
                )
                row = np.zeros((1, self.max_len), np.int32)
                self._ctx = self._row_fn(self._ctx, row, np.int32(0))

    def _build_admit_fn(self, pb: int):
        """Compiled whole-prefix prefill for bucket ``pb``: positions
        0..pb-1 via the generator's shared ``_prefill`` body. The
        slot's context row is NOT written here — ``begin_admit`` owns
        that (one shared program), so this program only reads ``row``
        for the prompt embeddings."""
        import jax
        import jax.numpy as jnp

        gen = self._gen

        def admit(params, caches, row, slot):
            bp, p_emb, _, _ = self._unpack(params)
            if pb >= 1:
                x = p_emb["tokens"][row[:, :pb]]
                if "positions" in p_emb:
                    x = x + p_emb["positions"][:pb]
                nh, hd = caches[0][0].shape[2], caches[0][0].shape[3]
                small = [
                    (
                        jnp.zeros((1, pb, nh, hd), gen.kv_dtype),
                        jnp.zeros((1, pb, nh, hd), gen.kv_dtype),
                    )
                    for _ in gen._stages
                ]
                _, small = gen._prefill(bp, small, x)
                caches = [
                    (
                        jax.lax.dynamic_update_slice(
                            ck, sk, (slot, 0, 0, 0)
                        ),
                        jax.lax.dynamic_update_slice(
                            cv, sv, (slot, 0, 0, 0)
                        ),
                    )
                    for (ck, cv), (sk, sv) in zip(caches, small)
                ]
            return caches

        return self._jit(admit, donate=(1,), out="kv",
                         key=f"admit[{pb}]")

    def _build_copy_fn(self):
        """Compiled prefix-cache restore: write the stacked per-stage
        host K/V rows ``(n_stages, pb, H, Dh)`` into one slot's cache
        rows (program key = the pb bucket, via the argument shape)."""
        import jax

        def copy(caches, ks, vs, slot):
            out = []
            for si, (ck, cv) in enumerate(caches):
                out.append(
                    (
                        jax.lax.dynamic_update_slice(
                            ck, ks[si][None].astype(ck.dtype),
                            (slot, 0, 0, 0),
                        ),
                        jax.lax.dynamic_update_slice(
                            cv, vs[si][None].astype(cv.dtype),
                            (slot, 0, 0, 0),
                        ),
                    )
                )
            return out

        return self._jit(copy, donate=(0,), out="kv",
                         key="restore")

    def _build_copy_fn_paged(self, pbk: int, pbt: int):
        """Compiled paged prefix restore: scatter the stacked per-stage
        host K/V rows ``(n_stages, pbk, H, Dh)`` to the physical flat
        positions the slot's leading logical positions map to. Bucket
        padding past the real prefix lands at later reserved positions
        (clamped to the table), overwritten before anything attends it."""
        import jax.numpy as jnp

        nh, hd = self._nh, self._hd

        def copy(pools, ks, vs, trow):
            fpos = self._flat_positions(trow, jnp.arange(pbk), pbt)
            out = []
            for si, (ck, cv) in enumerate(pools):
                out.append(
                    (
                        ck.reshape(-1, nh, hd)
                        .at[fpos].set(ks[si].astype(ck.dtype))
                        .reshape(ck.shape),
                        cv.reshape(-1, nh, hd)
                        .at[fpos].set(vs[si].astype(cv.dtype))
                        .reshape(cv.shape),
                    )
                )
            return out

        return self._jit(copy, donate=(0,), out="kv",
                         key=f"paged_restore[{pbk},{pbt}]")

    # -- the programs -------------------------------------------------------
    #
    # A program form is one stage walk, whatever holds the cache
    # (``_step_program``, ``_verify_program``, ``_chunk_program``), and a
    # block's arithmetic is its ``forward`` in ``models/``. The dense
    # bank, the ``"kv"`` page pool and the latent page pool each bring a
    # CACHE CALLABLE a form and nothing else, picked by
    # ``_cache_callable(form, pbt)`` when a program is built. A trace
    # calls it once with what the stages share (step and verify:
    # ``(table, rows, pos, active)``, the table None for the bank; chunk:
    # ``(where, start, pos)``) and gets ``stage(blk, moe, p, pm, x,
    # cache) -> (x, cache, group sizes or None)``, which owns where new
    # rows go and what is attended:
    #
    # - the bank: each slot's ``(T, H, Dh)`` row, written at the row's
    #   own positions and attended whole;
    # - ``"kv"`` pages: a ``(num_pages, page_size, H, Dh)`` pool per
    #   stage; a write scatters to the (page, offset) of its logical
    #   position. Where ``self.attention == "kernel"`` (unsharded, heads
    #   a whole number of 128 lanes, a bfloat16 or float32 pool:
    #   ``ops.paged_attention.decode_attention_path``) the decode step
    #   then reads each slot's own pages where they lie: nothing
    #   gathered, converted or padded, a slot not decoding costs
    #   nothing, nor does the table's width, so ONE step program, at
    #   ``_max_pages_bucket``. Everything else GATHERS ``pool[table]``
    #   -> (B, T' = bucket * page_size, H, Dh): the step under a ``tp``
    #   mesh or with heads of 16 or 64 (keyed by the pow2-bucketed page
    #   count: its extent tracks the longest OCCUPIED table at O(log T)
    #   compiles), and chunk, verify and restore at the full extent;
    # - latent pages: per stage, a ``(num_pages x page_size, row)`` pool
    #   for each latent attention the block has (its ``cached_rows``: one
    #   for ``LatentMoEBlock``, two for ``ShortcutMoEBlock``), ONE array
    #   that is keys and values, written by the block's ``forward``
    #   through ``exchange``, an attention a call. The decode step under
    #   ``"kernel"`` (unsharded, pages that are whole tiles of 8 rows, a
    #   bfloat16 or float32 pool) hands ``forward`` a callable that
    #   attends each slot's own pages where they lie
    #   (``ops.paged_attention.paged_latent_attention``): ONE step
    #   program here too. The step otherwise, and the chunk always,
    #   gather the table's pages into rows.
    #
    # Masks, the softmax (``models.layers.cache_attention``; the kernel
    # folds the same softmax over blocks of pages, in float32) and the
    # sampling tail are shared: paged greedy output stays token-identical
    # to the bank's and to solo decode. ``stats()["paged"]["attention"]``
    # and the ``serving/step`` span say how a paged step attends.

    def _cache_callable(self, form: str, pbt):
        """What the bank or the page layout decides for a program of
        ``form`` (``"step"``, ``"verify"``, ``"chunk"``) at table bucket
        ``pbt`` (None: the bank), picked once when its builder runs:
        the form's cache callable and how the head multiplies."""
        def head(p_head, x):
            return self._gen._head.apply(p_head, {}, x)[0]

        chunk = form == "chunk"
        if not self.paged:
            return self._bank_chunk if chunk else self._bank_rows, head
        cache = getattr(self, self._face["chunk" if chunk else "rows"])
        if self._paged_only:
            from distkeras_tpu.models.mla_moe import matmul

            tied = getattr(self._gen._head, "logits", None)
            return (
                functools.partial(cache, pbt),
                tied or (
                    lambda p_head, x: matmul(x, p_head["kernel"])),  # bf16
            )
        if chunk:
            return functools.partial(cache, pbt), head
        # the kernel attends one token a slot: the step, not the verify
        in_place = form == "step" and self.attention == "kernel"
        return functools.partial(cache, pbt, in_place), head

    def _step_program(self, pbt, masked, key):
        """The decode step, dense (``pbt`` None) or paged at table
        bucket ``pbt``: every active slot's last token at its own
        position through the stages, then the sampling tail. Sampling
        params are DATA (per-slot arrays), never part of the compile
        key: one program serves greedy and sampled slots mixed, and an
        all-greedy batch takes the argmax fast path (``lax.cond`` on
        ``any(temps > 0)``), bit-identical to the pre-sampling program.
        ``masked`` selects the grammar variant (an extra (B, V) additive
        mask argument); unconstrained traffic never compiles or pays it.
        Inactive / short rows pad their tables with the null sentinel
        page (writes masked to read-back, reads masked by the position
        mask; the kernel never reads the pad), so one program serves
        every occupancy."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.models.mla_moe import routing_counts
        from distkeras_tpu.serving import sampling as _sp

        gen = self._gen
        b, tp = self.num_slots, self._tp
        cache_of, head = self._cache_callable("step", pbt)

        def step(params, ctx, caches, lens, active, table,
                 temps, topk, topp, seeds, spos, *mask):
            bp, p_emb, p_ln, p_head = self._unpack(params)
            pos = jnp.clip(lens - 1, 0, tp - 1)  # (B,) per-slot position
            rows = jnp.arange(b)
            tok = jnp.take_along_axis(ctx, pos[:, None], axis=1)[:, 0]
            x = self._embed(p_emb, tok, pos)
            stage = cache_of(table, rows, pos, active)
            new_caches, routed = [], []
            for (blk, _, moe, _), (p, pm), cache in zip(
                gen._stages, bp, caches
            ):
                x, cache, sizes = stage(blk, moe, p, pm, x, cache)
                new_caches.append(cache)
                if sizes is not None:
                    routed.append(sizes)
            x, _ = gen._final_ln.apply(p_ln, {}, x)
            logit = head(p_head, x)  # (B, V)
            if masked:
                logit = logit + mask[0]  # grammar mask (0 / -inf rows)
            nxt = jax.lax.cond(
                jnp.any(temps > 0.0),
                lambda: _sp.sample_tokens(
                    logit, temps, topk, topp, seeds, spos
                ),
                lambda: jnp.argmax(logit, axis=-1).astype(jnp.int32),
            ).astype(ctx.dtype)
            wpos = jnp.clip(pos + 1, 0, tp - 1)
            cur = ctx[rows, wpos]
            write = active & (pos + 1 <= tp - 1)
            ctx = ctx.at[rows, wpos].set(jnp.where(write, nxt, cur))
            if routed:
                # the routing counters ride the tokens' fetch
                nxt = jnp.concatenate(
                    [nxt, routing_counts(routed).astype(nxt.dtype)]
                )
            return ctx, new_caches, nxt

        return self._jit(step, donate=(1, 2), out="step", key=key)

    def _verify_program(self, c: int, pbt, masked, key):
        """The speculative verify for ``c`` candidates per slot (the
        slot's last real token plus ``c-1`` draft proposals; ``c`` is
        the pow2 ``draft_k`` bucket + 1, the chunk-program discipline),
        dense or paged at table bucket ``pbt``. One call scores every
        candidate position of every active slot against the live cache
        (the step's cache callable at (B, C) positions, gathered where
        the step reads in place), computes the
        accepted window (greedy rows by longest argmax agreement,
        sampled rows by rejection sampling,
        ``sampling.spec_window_tokens``) and writes the accepted tokens
        into the context rows; the scheduler reads back only (tokens,
        counts). K/V and context writes past the real sequence land in
        the scratch pad (``_tp``; paged: the slot's reserved scratch
        pages, ``pages_for`` includes the verify window); inactive
        slots are frozen throughout. ``masked`` adds the grammar mask
        argument, applied to candidate 0 only: constrained slots never
        draft (``spec_step`` zeroes their proposals), so candidate 0 is
        the single token they emit per window."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.serving import sampling as _sp

        gen = self._gen
        b, ml = self.num_slots, self.max_len
        cache_of, head = self._cache_callable("verify", pbt)

        def verify(params, ctx, caches, lens, active, dtoks, dcnt, table,
                   temps, topk, topp, seeds, spos, *mask):
            bp, p_emb, p_ln, p_head = self._unpack(params)
            pos = jnp.clip(lens - 1, 0, ml - 1)  # (B,)
            rows = jnp.arange(b)
            tok0 = ctx[rows, pos]
            chunk = jnp.concatenate([tok0[:, None], dtoks], axis=1)
            cpos = pos[:, None] + jnp.arange(c)[None, :]  # (B, C) < tp
            x = self._embed(p_emb, chunk, cpos)  # (B, C, d)
            stage = cache_of(table, rows, cpos, active)
            new_caches = []
            for (blk, _, moe, _), (p, pm), cache in zip(
                gen._stages, bp, caches
            ):
                x, cache, _ = stage(blk, moe, p, pm, x, cache)
                new_caches.append(cache)
            x, _ = gen._final_ln.apply(p_ln, {}, x)
            logit = head(p_head, x)  # (B, C, V)
            if masked:
                logit = logit.at[:, 0].add(mask[0])
            out, n_new = jax.lax.cond(
                jnp.any(temps > 0.0),
                lambda: _sp.spec_window_tokens(
                    logit, dtoks, dcnt, temps, topk, topp, seeds, spos
                ),
                lambda: _sp.greedy_window_tokens(logit, dtoks, dcnt),
            )
            out = out.astype(ctx.dtype)
            wpos = cpos + 1  # <= ml-1 + c < tp: scratch absorbs overrun
            keep = active[:, None] & (
                jnp.arange(c)[None, :] < n_new[:, None]
            )
            rows2 = rows[:, None]
            cur = ctx[rows2, wpos]
            ctx = ctx.at[rows2, wpos].set(jnp.where(keep, out, cur))
            return ctx, new_caches, out, n_new

        return self._jit(verify, donate=(1, 2), out="verify", key=key)

    def _chunk_program(self, cb: int, pbt, key):
        """The mid-prompt prefill chunk for chunk bucket ``cb``, dense
        or paged at table bucket ``pbt``: the chunk's tokens at
        positions ``start..start+cb-1`` through every stage against ONE
        slot's cache (``where``: its slot, or its page-table row), so
        neighbours are untouched. ``start`` is traced: one program per
        bucket serves every position and every slot."""
        import jax.numpy as jnp

        gen = self._gen
        cache_of, _ = self._cache_callable("chunk", pbt)

        def chunk(params, caches, toks, where, start):
            bp, p_emb, _, _ = self._unpack(params)
            pos = start + jnp.arange(cb)  # (cb,) absolute positions
            x = self._embed(p_emb, toks, pos)  # (1, cb, d)
            stage = cache_of(where, start, pos)
            out = []
            for (blk, _, moe, _), (p, pm), cache in zip(
                gen._stages, bp, caches
            ):
                x, cache, _ = stage(blk, moe, p, pm, x, cache)
                out.append(cache)
            return out

        return self._jit(chunk, donate=(1,), out="kv", key=key)

    # the builders' names and ``_jit`` keys, as the call sites, the
    # compile ledger and ``compiled_step_buckets`` know them

    def _build_step_fn(self, masked=False):
        return self._step_program(
            None, masked, f"step[{'masked' if masked else 'plain'}]"
        )

    def _build_step_fn_paged(self, pbt: int, masked=False):
        return self._step_program(
            pbt, masked,
            f"paged_step[{pbt}{',masked' if masked else ''}]",
        )

    def _build_verify_fn(self, c: int, masked=False):
        return self._verify_program(
            c, None, masked, f"verify[{c}{',masked' if masked else ''}]"
        )

    def _build_verify_fn_paged(self, c: int, pbt: int, masked=False):
        return self._verify_program(
            c, pbt, masked,
            f"paged_verify[{c},{pbt}{',masked' if masked else ''}]",
        )

    def _build_chunk_fn(self, cb: int):
        return self._chunk_program(cb, None, f"chunk[{cb}]")

    def _build_chunk_fn_paged(self, cb: int, pbt: int):
        return self._chunk_program(cb, pbt, f"paged_chunk[{cb},{pbt}]")

    # -- the cache callables ------------------------------------------------

    def _bank_rows(self, table, rows, pos, active):
        """The dense bank (``table`` is None) at per-slot positions
        ``pos``: (B,) for the step, (B, C) for the verify. K/V written
        at each row's own positions, frozen where a slot is inactive; a
        row attends itself up to its own position."""
        import jax.numpy as jnp

        from distkeras_tpu.models.layers import cache_attention

        gen, t = self._gen, self._tp

        def stage(blk, moe, p, pm, x, cache):
            kv = []

            def attend(q, k_new, v_new):
                at = (_lead(rows, pos.ndim - 1), pos)
                keep = _lead(active, pos.ndim + 1)
                kv.extend(
                    _write_rows(c, at, new, keep)
                    for c, new in zip(cache, (k_new, v_new))
                )
                return cache_attention(
                    q, *kv, jnp.arange(t) <= pos[..., None]
                )

            x = gen._stage(blk, moe, p, pm, x, attend)
            return x, tuple(kv), None

        return stage

    def _bank_chunk(self, slot, start, pos):
        """The dense bank, one slot's chunk: the slot's row sliced out,
        the generators' ``_stage_chunk`` against it (K/V write at
        ``start``, (C, T) query mask), the row written back."""
        import jax
        import jax.numpy as jnp

        gen, t = self._gen, self._tp
        row = (1, t, self._nh, self._hd)  # one slot's cache row
        qmask = jnp.arange(t)[None, :] <= pos[:, None]  # (cb, T)

        def stage(blk, moe, p, pm, x, cache):
            x, *new = gen._stage_chunk(
                blk, moe, p, pm, x,
                *(jax.lax.dynamic_slice(c, (slot, 0, 0, 0), row)
                  for c in cache),
                start, qmask,
            )
            return x, tuple(
                jax.lax.dynamic_update_slice(c, r, (slot, 0, 0, 0))
                for c, r in zip(cache, new)
            ), None

        return stage

    def _page_of(self, table, rows, pos, pbt: int):
        """(page, offset) of each slot's logical positions ``pos``."""
        import jax.numpy as jnp

        ps = self.page_size
        page = table[
            _lead(rows, pos.ndim - 1), jnp.clip(pos // ps, 0, pbt - 1)
        ]
        return page, pos % ps

    def _flat_positions(self, trow, pos, pbt: int):
        """Rows of the flat pool that one slot's logical positions
        ``pos`` map to through its page-table row ``trow``."""
        import jax.numpy as jnp

        ps = self.page_size
        return trow[jnp.clip(pos // ps, 0, pbt - 1)] * ps + pos % ps

    def _kv_rows(self, pbt: int, in_place: bool, table, rows, pos, active):
        """``"kv"`` pages at per-slot positions ``pos`` ((B,) step, (B,
        C) verify): the masked page write, then ``in_place`` (the step
        where ``self.attention == "kernel"``) ``paged_decode_attention``
        over the slot's own pages in the written pool (positions <= pos;
        nothing for a slot that is not decoding, whose row is never
        used), the pages gathered at the bucket's extent under the
        position mask otherwise."""
        import jax.numpy as jnp

        from distkeras_tpu.models.layers import cache_attention
        from distkeras_tpu.ops.paged_attention import (
            paged_decode_attention,
        )

        gen = self._gen
        b, t = self.num_slots, pbt * self.page_size
        at = self._page_of(table, rows, pos, pbt)

        def stage(blk, moe, p, pm, x, pool):
            kv = []

            def attend(q, k_new, v_new):
                keep = _lead(active, pos.ndim + 1)
                kv.extend(
                    _write_rows(c, at, new, keep)
                    for c, new in zip(pool, (k_new, v_new))
                )
                if in_place:
                    return paged_decode_attention(
                        q, *kv, table, jnp.where(active, pos + 1, 0)
                    )
                kg, vg = (
                    c[table].reshape(b, t, *q.shape[-2:]) for c in kv
                )
                return cache_attention(
                    q, kg, vg, jnp.arange(t) <= pos[..., None]
                )

            x = gen._stage(blk, moe, p, pm, x, attend)
            return x, tuple(kv), None

        return stage

    def _kv_chunk(self, pbt: int, trow, start, pos):
        """``"kv"`` pages, one slot's chunk: its pages gathered into its
        logical row, the generators' ``_stage_chunk`` against the row
        (as the bank's chunk), the chunk's updated positions scattered
        back to their physical pages."""
        import jax
        import jax.numpy as jnp

        gen = self._gen
        ps, nh, hd = self.page_size, self._nh, self._hd
        cb, t = pos.shape[0], pbt * ps
        qmask = jnp.arange(t)[None, :] <= pos[:, None]  # (cb, T')
        fpos = self._flat_positions(trow, pos, pbt)  # (cb,)

        def stage(blk, moe, p, pm, x, pool):
            x, *rows = gen._stage_chunk(
                blk, moe, p, pm, x,
                *(c[trow].reshape(t, nh, hd)[None] for c in pool),
                start, qmask,
            )
            new = [
                jax.lax.dynamic_slice(
                    r, (0, start, 0, 0), (1, cb, nh, hd)
                )[0]
                for r in rows
            ]
            return x, tuple(
                c.reshape(-1, nh, hd)
                .at[fpos].set(u.astype(c.dtype))
                .reshape(c.shape)
                for c, u in zip(pool, new)
            ), None

        return stage

    def _latent_rows(self, pbt: int, table, rows, pos, active):
        """Latent pages, one token a slot (the absorbed form of a latent
        block's ``forward``): ``exchange`` owns the masked page write of
        the attention it is called for (the block's pools in its order)
        and hands back how the written pool is attended. Where
        ``self.attention == "kernel"`` that is
        ``paged_latent_attention`` over each slot's own pages where
        they lie (positions <= pos; nothing for a slot that is not
        decoding); otherwise every slot's pages gathered at the
        bucket's extent, in the pool's dtype, under the position mask
        (float32 is what accumulates either way)."""
        import jax.numpy as jnp

        from distkeras_tpu.ops.paged_attention import (
            paged_latent_attention,
        )

        b, ps = self.num_slots, self.page_size
        t = pbt * ps
        in_place = self.attention == "kernel"
        phys, off = self._page_of(table, rows, pos, pbt)
        if in_place:
            lengths, t_mask = jnp.where(active, pos + 1, 0), None
        else:
            t_mask = (jnp.arange(t)[None, :] <= pos[:, None])[:, None]

        def stage(blk, moe, p, pm, x, pools):
            written = []  # an attention's pool, in the block's order

            def exchange(new):  # (B, 1, latent_width) float32
                pool = pools[len(written)]
                at = phys * ps + off  # rows of the flat pool
                row = jnp.where(
                    active[:, None], self._pad_row(new[:, 0], pool),
                    pool[at],
                )
                mine = pool.at[at].set(row)
                written.append(mine)
                if in_place:
                    return lambda qc, scale: paged_latent_attention(
                        qc[:, 0], mine, table, lengths, ps,
                        blk.kv_rank, scale,
                    )[:, None]
                pages = mine.reshape(-1, ps, pool.shape[-1])
                return pages[table].reshape(b, t, -1)[
                    ..., :new.shape[-1]]

            x, picks = blk.forward(
                p, x[:, None], pos[:, None], t_mask, exchange,
                absorbed=True, token_mask=active[:, None],
            )
            return x[:, 0], tuple(written), picks

        return stage

    def _latent_chunk(self, pbt: int, trow, start, pos):
        """Latent pages, one slot's chunk (the expanded form of a latent
        block's ``forward``): ``exchange`` scatters the chunk's rows to
        their physical pages in the pool of the attention it is called
        for and returns the slot's gathered logical row with the chunk's
        own rows written into it."""
        import jax
        import jax.numpy as jnp

        ps = self.page_size
        cb, t = pos.shape[0], pbt * ps
        qmask = (jnp.arange(t)[None, :] <= pos[:, None])[None]
        fpos = self._flat_positions(trow, pos, pbt)  # (cb,)

        def stage(blk, moe, p, pm, x, pools):
            written = []  # an attention's pool, in the block's order

            def exchange(new):
                pool = pools[len(written)]
                rows = self._pad_row(new[0], pool)  # (cb, row width)
                written.append(pool.at[fpos].set(rows))
                row = pool.reshape(-1, ps, pool.shape[-1])[trow]
                return jax.lax.dynamic_update_slice(
                    row.reshape(t, -1), rows, (start, 0)
                )[None, :, :new.shape[-1]]

            x, picks = blk.forward(
                p, x, pos[None], qmask, exchange,
                n_keys=jnp.minimum(start + cb, t),
            )
            return x, tuple(written), picks

        return stage

    def _gqa_rows(self, pbt: int, tables, rows, pos, active):
        """Grouped pages, one token a slot: ``attend`` owns the masked
        write of the token's key and value into the layer's own kind of
        page (a full layer: column ``pos // page_size`` of the slot's
        table; a window layer: column ``(pos // page_size) % ring`` of
        its ring) and how the written pools are attended. Where
        ``self.attention == "kernel"`` that is ``paged_decode_attention``
        over the slot's own pages where they lie, from the window's first
        position in a window layer; otherwise the positions a slot may
        see gathered (a full layer: the table's extent; a window layer:
        the ring's) under the position mask."""
        import jax.numpy as jnp

        from distkeras_tpu.models.gqa_moe import attend_dense
        from distkeras_tpu.ops.paged_attention import (
            paged_decode_attention,
        )

        b, ps, ring = self.num_slots, self.page_size, self._ring
        kvh, hd = self._nh, self._hd
        in_place = self.attention == "kernel"
        full, rings = tables if ring else (tables, None)
        lengths = jnp.where(active, pos + 1, 0)

        if self._select:
            return self._select_rows(full, rows, pos, active)

        def stage(blk, moe, p, pm, x, pool):
            w = blk.window
            table = full if w is None else rings
            col = (jnp.clip(pos // ps, 0, pbt - 1) if w is None
                   else (pos // ps) % ring)
            at = table[rows, col] * ps + pos % ps  # rows of the flat pool
            kv = []

            def attend(q, k_new, v_new):
                # a slot that is not decoding writes nothing: its row's
                # index is out of range, and dropped
                kv.extend(
                    c.at[jnp.where(active, at, c.shape[0])].set(
                        new.reshape(b, kvh * hd).astype(c.dtype),
                        mode="drop",
                    )
                    for c, new in zip(pool, (k_new, v_new))
                )
                scale = getattr(blk, "softmax_scale", None)
                if in_place:
                    if scale is not None:
                        # the kernel's scale is 1 / sqrt(Dh): the layer's
                        # own goes into the query
                        q = q * (scale * np.sqrt(hd))
                    return paged_decode_attention(
                        q, *kv, table, lengths,
                        None if w is None else jnp.maximum(pos + 1 - w, 0),
                        page_size=ps, ring=0 if w is None else ring,
                    )
                if w is None:
                    kpos = jnp.arange(pbt * ps)[None, :]  # (1, T')
                    idx = (table[:, :, None] * ps
                           + jnp.arange(ps)).reshape(b, -1)
                    see = kpos <= pos[:, None]
                else:
                    n = ring * ps  # what a ring can hold
                    kpos = pos[:, None] - (n - 1) + jnp.arange(n)[None, :]
                    idx = (table[rows[:, None], (kpos // ps) % ring] * ps
                           + kpos % ps)
                    see = (kpos >= 0) & (kpos > pos[:, None] - w)
                kg, vg = (c[idx].reshape(b, -1, kvh, hd) for c in kv)
                return attend_dense(
                    q[:, None], kg, vg, see[:, None], scale)[:, 0]

            x, picks = blk.forward(
                p, x, pos, None, attend, token_mask=active
            )
            return x, tuple(kv), picks

        return stage

    def _select_rows(self, table, rows, pos, active):
        """Grouped pages of a block that selects, one token a slot. Under
        ``attn/index``: the token's selector key written into its part of
        its page of the selector pool, then the scores of every cached
        position: where ``self.selector == "kernel"`` by
        ``paged_index_scores`` over each slot's OWN selector pages where
        they lie, to its own length (no gathered copy; PR 40), otherwise
        over every slot's selector rows gathered at the table's extent (a
        page a row, as they lie); then the exact selection of ``topk`` of
        what a slot can see. Under ``attn/sparse``: the token's key and
        value written, then one of two bodies, by ``self.attention``:

        ``"kernel"`` (PR 42): the selection as a mask (``select_mask``: 32
        counting passes, no sort) handed to ``paged_decode_attention`` as
        ``chosen``: the grouped body streams each slot's own K and V pages
        where they lie and attends under the mask. It reads every cached
        page of the slot, ten times the bytes of the selected rows at
        ``topk`` a tenth of the cache, at the rate whole pages stream at.

        ``"gather: <why>"`` (a mesh, a pool the kernels do not read, pages
        that are not whole tiles, narrow heads): the selection as
        positions (``select_rows``: ``lax.top_k``), their physical rows
        through the table (``table[slot, s // page] x page + s % page``),
        keys and values of THOSE rows and no others gathered by token,
        grouped-query attention over them: what this body reads of K and V
        does not grow with the cached length beyond ``topk`` rows a slot
        and layer, and it pays by the row.

        The softmax runs over the same keys either way."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.models.gqa_moe import (
            attend_dense, select_mask, select_rows)
        from distkeras_tpu.ops.paged_attention import (
            paged_decode_attention, paged_index_scores)

        b, ps = self.num_slots, self.page_size
        kvh, hd = self._nh, self._hd
        topk, di = self._select["topk"], self._select["head_dim"]
        in_place = self.selector == "kernel"
        streamed = self.attention == "kernel"
        lengths = jnp.where(active, pos + 1, 0)
        # no position lies past the context row: its pages, not the bucket
        table = table[:, : -(-self.max_len // ps)]
        extent = table.shape[1] * ps
        page = table[rows, jnp.clip(pos // ps, 0, table.shape[1] - 1)]
        at = page * ps + pos % ps  # rows of the flat K/V pools
        part = (jnp.arange(ps * di) // di)[None, :] == (pos % ps)[:, None]
        visible = jnp.arange(extent)[None, :] <= pos[:, None]

        def stage(blk, moe, p, pm, x, pool):
            written = []

            def attend(q, k_new, v_new, index):
                qi, ki_new, wi = index  # (B, J, Di), (B, Di), (B, J)
                ck, cv, ci = pool
                with jax.named_scope("attn/index"):
                    # a slot that is not decoding writes nothing: its
                    # row's index is out of range, and dropped
                    mine = jnp.where(
                        part, jnp.tile(ki_new.astype(ci.dtype), (1, ps)),
                        ci[page].reshape(b, -1))
                    ci = ci.at[jnp.where(active, page, ci.shape[0])].set(
                        mine.reshape(b, *ci.shape[1:]), mode="drop")
                    if in_place:
                        scores = paged_index_scores(
                            qi, wi, ci, table, lengths)
                    else:
                        scores = blk.index_scores(
                            qi[:, None], wi[:, None], ci[table], ps)[:, 0]
                    if streamed:
                        chosen = select_mask(scores, visible, topk)
                    else:
                        idx, valid = select_rows(scores, visible, topk)
                with jax.named_scope("attn/sparse"):
                    kv = [
                        c.at[jnp.where(active, at, c.shape[0])].set(
                            new.reshape(b, kvh * hd).astype(c.dtype),
                            mode="drop")
                        for c, new in ((ck, k_new), (cv, v_new))
                    ]
                    written.extend((*kv, ci))
                    if streamed:
                        return paged_decode_attention(
                            q, *kv, table, lengths, page_size=ps,
                            chosen=chosen)
                    phys = table[rows[:, None], idx // ps] * ps + idx % ps
                    kg, vg = (c[phys].reshape(b, -1, kvh, hd) for c in kv)
                    return attend_dense(
                        q[:, None], kg, vg, valid[:, None])[:, 0]

            x, picks = blk.forward(
                p, x, pos, None, attend, token_mask=active
            )
            return x, tuple(written), picks

        return stage

    def _select_chunk(self, trow, pos):
        """Grouped pages of a block that selects, one slot's chunk. Under
        ``attn/index``: the slot's selector rows gathered into its logical
        row of keys, the chunk's own written into it (and the rows back to
        their pages) FIRST, then the scores a tile of queries at a time and
        the exact selection a query. Under ``attn/sparse``: the chunk's keys
        and values scattered to their pages, the slot's pages gathered,
        and the visible key blocks folded under the selection's mask
        (``attend_selected``), at the first of the block's extents that
        holds the chunk's last position."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.models.gqa_moe import (
            attend_selected, select_mask)

        ps = self.page_size
        kvh, hd = self._nh, self._hd
        topk, di = self._select["topk"], self._select["head_dim"]
        cb = pos.shape[0]
        trow = trow[: -(-self.max_len // ps)]
        extent = trow.shape[0] * ps
        fpos = trow[jnp.clip(pos // ps, 0, trow.shape[0] - 1)] * ps \
            + pos % ps  # (cb,)

        def stage(blk, moe, p, pm, x, pool):
            written = []
            extents = [m * topk for m in blk.select_extents
                       if m * topk < extent]

            def attend(q, k_new, v_new, index):
                qi, ki_new, wi = index  # (1, cb, J, Di), (1, cb, Di), ..
                ck, cv, ci = pool
                with jax.named_scope("attn/index"):
                    keys = ci[trow].reshape(extent, di).at[pos].set(
                        ki_new[0].astype(ci.dtype), mode="drop")
                    ci = ci.at[trow].set(keys.reshape(-1, *ci.shape[1:]))

                def chosen_of(lo, m, te):
                    with jax.named_scope("attn/index"):
                        scores = blk.index_scores(
                            jax.lax.dynamic_slice_in_dim(qi[0], lo, m, 0),
                            jax.lax.dynamic_slice_in_dim(wi[0], lo, m, 0),
                            keys[:te])
                        at = jax.lax.dynamic_slice_in_dim(pos, lo, m, 0)
                        return select_mask(
                            scores, jnp.arange(te)[None, :] <= at[:, None],
                            topk)

                with jax.named_scope("attn/sparse"):
                    kv = [
                        c.at[fpos].set(
                            new[0].reshape(cb, kvh * hd).astype(c.dtype))
                        for c, new in ((ck, k_new), (cv, v_new))
                    ]
                    written.extend((*kv, ci))

                    def keys_of(te):
                        at = (trow[: -(-te // ps), None] * ps
                              + jnp.arange(ps)).reshape(-1)[:te]
                        return tuple(c[at].reshape(te, kvh, hd) for c in kv)

                    return attend_selected(
                        q[0], keys_of, pos, chosen_of, extent, extents,
                        blk.key_block)[None]

            x, picks = blk.forward(p, x, pos[None], None, attend)
            return x, tuple(written), picks

        return stage

    def _gqa_chunk(self, pbt: int, where, start, pos):
        """Grouped pages, one slot's chunk (``attend_blocked``: the keys
        a query can see and no others). A full layer: the chunk's keys
        and values scattered to their pages, the slot's pages gathered
        into its row and attended up to the chunk's end. A window layer
        attends ``window + chunk`` keys: the ``window`` positions before
        the chunk out of the ring, then the chunk's own, which never
        pass through the ring; the ring then takes the chunk's last real
        positions (at most what it holds, so no two land on one row)."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.models.gqa_moe import attend_blocked

        ps, ring = self.page_size, self._ring
        kvh, hd = self._nh, self._hd
        cb = pos.shape[0]
        trow, rrow, n = where if ring else (where, None, None)
        if self._select:
            return self._select_chunk(trow, pos)
        fpos = self._flat_positions(trow, pos, pbt)  # (cb,)
        ridx = (trow[:, None] * ps + jnp.arange(ps)).reshape(-1)

        def stage(blk, moe, p, pm, x, pool):
            w = blk.window
            scale = getattr(blk, "softmax_scale", None)
            kv = []

            def rows_of(new, c):  # (1, cb, Hkv, Dh) as the pool holds it
                return new[0].reshape(cb, kvh * hd).astype(c.dtype)

            def attend(q, k_new, v_new):
                if w is None:
                    kv.extend(c.at[fpos].set(rows_of(new, c))
                              for c, new in zip(pool, (k_new, v_new)))
                    kg, vg = (c[ridx].reshape(-1, kvh, hd) for c in kv)
                    return attend_blocked(
                        q[0], kg, vg, pos, 0, None, blk.key_block,
                        scale=scale)[None]
                # the window before the chunk, out of the ring as it is
                prev = start - w + jnp.arange(w)  # negative: no such key
                pidx = rrow[(prev // ps) % ring] * ps + prev % ps
                # the ring takes the last m positions of the real chunk
                m = min(cb, ring * ps)
                off = jnp.clip(n - m, 0, cb - m)
                last = start + off + jnp.arange(m)
                widx = jnp.where(
                    last < start + n,
                    rrow[(last // ps) % ring] * ps + last % ps,
                    pool[0].shape[0],  # out of range: dropped
                )
                keys = []
                for c, new in zip(pool, (k_new, v_new)):
                    mine = rows_of(new, c)
                    keys.append(jnp.concatenate(
                        [c[pidx], mine]).reshape(-1, kvh, hd))
                    kv.append(c.at[widx].set(
                        jax.lax.dynamic_slice_in_dim(mine, off, m, 0),
                        mode="drop"))
                return attend_blocked(
                    q[0], *keys, pos, start - w, w, blk.key_block,
                    scale=scale)[None]

            x, picks = blk.forward(p, x, pos[None], None, attend)
            return x, tuple(kv), picks

        return stage

    def _hybrid_rows(self, pbt: int, tables, rows, pos, active):
        """Pools that differ by layer, one token a slot. A layer that
        caches rows is the grouped layout's (``_gqa_rows``). A layer that
        holds a state a slot (``slot_state``) reads and writes its arrays
        in place, by slot: a slot at position 0 starts from zeros (its
        admission's reset: there is nothing before position 0), and a
        slot that is not decoding keeps its state and its tail as they
        were (K/V gets away with a write out of range; a state has to be
        told)."""
        grouped = self._gqa_rows(pbt, tables, rows, pos, active) \
            if self._nh is not None else None
        fresh = pos == 0

        def stage(blk, moe, p, pm, x, pool):
            if not getattr(blk, "slot_state", None):
                return grouped(blk, moe, p, pm, x, pool)
            carry = self._zero_where(fresh, pool)
            x, carry = blk.forward(p, x, carry, keep=active, step=True)
            return x, carry, None

        return stage

    @staticmethod
    def _zero_where(fresh, arrays):
        """``arrays`` (a slot, or slots, leading) with zeros where ``fresh``
        (a flag, or one a slot): a sequence's state before its position 0.
        The one place a state is reset: in the program that runs position
        0, by a select, never by a host write."""
        import jax.numpy as jnp

        fresh = jnp.asarray(fresh)
        return tuple(
            jnp.where(fresh.reshape(fresh.shape + (1,) * (a.ndim - fresh.ndim)),
                      jnp.zeros((), a.dtype), a) for a in arrays)

    def _hybrid_chunk(self, pbt: int, where, start, pos):
        """Pools that differ by layer, one slot's chunk: ``where`` is
        ``(table row, slot, n)``. A layer that caches rows is the grouped
        layout's (``_gqa_chunk``). A layer that holds a state a slot takes
        the slot's state and tail (zeros where the chunk starts at
        position 0), carries them over the chunk's ``n`` real tokens and
        no further (the pow2 padding behind them advances nothing), and
        writes them back to the slot: the next chunk, and then the step,
        go on from there."""
        import jax

        trow, slot, n = where
        grouped = self._gqa_chunk(pbt, trow, start, pos) \
            if self._nh is not None else None

        def stage(blk, moe, p, pm, x, pool):
            if not getattr(blk, "slot_state", None):
                return grouped(blk, moe, p, pm, x, pool)
            carry = self._zero_where(start == 0, tuple(
                jax.lax.dynamic_index_in_dim(a, slot, 0) for a in pool))
            x, carry = blk.forward(p, x, carry, n_valid=n)
            return x, tuple(
                jax.lax.dynamic_update_index_in_dim(a, new[0], slot, 0)
                for a, new in zip(pool, carry)), None

        return stage

    # -- the decode step ----------------------------------------------------

    def step(self, active) -> np.ndarray:
        """Advance every active slot one token; returns the (B,) tokens
        appended this step (entries for inactive slots are meaningless).
        One compiled call plus one small host fetch per step — the
        iteration-level scheduling loop the batcher drives. Dispatch +
        immediate collect of :meth:`step_async`, so the sequential
        control path and the overlapped loop run the SAME program with
        the same host bookkeeping, in the same order."""
        return self.step_async(active).collect()

    def step_async(self, active) -> "_InflightStep":
        """Dispatch one decode step WITHOUT materializing its result:
        the jitted call returns device futures, ``self._ctx`` and the
        KV state take them immediately (later admissions/prefills chain
        on the step through the donation arguments — no explicit sync
        needed), and the un-fetched token array rides the returned
        :class:`_InflightStep`. The host bookkeeping a successful step
        implies (``_lens``/``_spos`` advance, grammar cursors) is
        DEFERRED to ``collect()`` so a failed call still advances
        nothing — the blame-retry discipline is unchanged, it just
        surfaces at the collect of the step's own iteration.

        May be called with a step still in the air (the overlapped
        loop dispatches step n+1 before it collects step n): the
        lengths and sample positions passed are then the host's plus
        what the open steps owe (``_owed``) — the values the host will
        hold once they are collected, in dispatch order. Everything
        else the call passes is known before n's tokens are: each
        slot's last token is in ``self._ctx`` on the device, the page
        table is complete from admission, the sampler's parameters are
        the request's. Host state still advances only at ``collect()``.
        No slot with a grammar may be in ``active`` then
        (``constrained_slots``): its mask needs the token."""
        active = np.asarray(active, bool)
        # the injection seam fires BEFORE any device work or host
        # bookkeeping: a failed step leaves the slot bank exactly as it
        # was, which is what makes the batcher's blame retries sound
        self._fire("stepper.step", active=active)
        with _span("serving/step_args"):
            tmask = self._build_tmask(active)  # None unless constrained
            masked = tmask is not None
            if self.paged:
                pbt = self._table_bucket()
                key = (pbt, masked)
                fn = self._pstep_fns.get(key)
                if fn is None:
                    self._compiling()
                    fn = self._build_step_fn_paged(pbt, masked)
                    self._pstep_fns = {**self._pstep_fns, key: fn}
                table = self._tables_array(pbt)
            else:
                fn = self._step_fns.get(masked)
                if fn is None:
                    self._compiling()
                    fn = self._build_step_fn(masked)
                    self._step_fns = {**self._step_fns, masked: fn}
                table = None  # the bank has none
            # every argument of the step that is built on the host
            owed = self._owed()
            host = (
                np.minimum(self._lens + owed, self._lens_cap), active,
                table, *self._sampling_args(owed),
                *((tmask,) if masked else ()),
            )
            self.host_arg_bytes_step = self._host_arg_bytes(host)
        with _span(
            "serving/step", host_arg_bytes=self.host_arg_bytes_step
        ) as span:
            if self.paged:
                span.set_metadata(attention=self.attention)
                if self.selector:
                    span.set_metadata(selector=self.selector)
                if self.grouped:
                    span.set_metadata(grouped=self.grouped)
                if self._state_layers:
                    span.set_metadata(state_bytes=self._state_bytes(active))
                self._ctx, self._pools, toks = fn(
                    self._params, self._ctx, self._pools, *host
                )
            else:
                self._ctx, self._caches, toks = fn(
                    self._params, self._ctx, self._caches, *host
                )
        return _InflightStep(self, active, toks)

    # -- speculative decode (draft -> verify -> rollback) -------------------

    def spec_step(self, active, seqs=None):
        """One speculative scheduler advance: draft up to ``draft_k``
        tokens per active slot, verify all k+1 candidate positions
        against the live caches in ONE compiled call, accept the
        longest greedy-agreeing prefix plus the target's correction.
        Returns ``(toks, counts, used_verify)``: ``toks`` is (B, k+1)
        with row i's first ``counts[i]`` entries the tokens emitted
        for slot i this iteration (1..k+1 per slot — variable
        advance). Rollback past rejected positions is the host length:
        rejected K/V sits at positions >= the new frontier and is
        rewritten by the next window before anything attends it.

        When no slot has a proposal this iteration the engine falls
        back to the plain decode step (counted) — the verify's k
        wasted positions are not worth running to accept one token.
        A drafter failure (admission or proposal) never fails the
        request: the slots are invalidated and decode continues at
        plain-greedy pace.

        Blame-probe safe: proposals are cached against a length
        snapshot, so a crashed verify retried on a masked subset
        re-verifies the SAME drafts instead of re-advancing the draft
        bank."""
        active = np.asarray(active, bool)
        k = self._kb
        drafter = self.drafter
        # draft admission for slots that just turned decodable
        for i in np.flatnonzero(active):
            i = int(i)
            if i not in self._spec_admitted:
                self._spec_admitted.add(i)
                prompt = self._spec_prompts.get(i)
                try:
                    drafter.admit(i, prompt)
                except Exception:  # noqa: BLE001 — draft is best-effort
                    self.spec_draft_failures += 1
                    drafter.invalidate(
                        np.arange(self.num_slots) == i
                    )
        pend = self._spec_pending
        if pend is not None and np.array_equal(
            pend[0][active], self._lens[active]
        ):
            _, dtoks, dcnt = pend  # blame-probe retry: same drafts
        else:
            try:
                dtoks, dcnt = drafter.propose(active, self.draft_k, seqs)
            except Exception:  # noqa: BLE001 — draft is best-effort
                self.spec_draft_failures += 1
                drafter.invalidate(active)
                dtoks = np.zeros((self.num_slots, self.draft_k), np.int32)
                dcnt = np.zeros((self.num_slots,), np.int32)
            if dtoks.shape[1] < k:
                # pad proposals to the pow2 program bucket; padded
                # positions are masked out of acceptance by dcnt
                dtoks = np.concatenate(
                    [
                        dtoks,
                        np.zeros(
                            (self.num_slots, k - dtoks.shape[1]), np.int32
                        ),
                    ],
                    axis=1,
                )
            if self._grammar:
                # grammar-constrained slots never ride a draft window:
                # the host cannot know a future position's mask before
                # the tokens leading to it exist. They advance one
                # masked token per iteration (candidate 0 of the
                # verify, or the plain step on fallback) — zeroed HERE,
                # before the proposal cache, so blame-probe replay sees
                # the same zeroed drafts
                for i in self._grammar:
                    dtoks[i] = 0
                    dcnt[i] = 0
            self._spec_pending = (self._lens.copy(), dtoks, dcnt)
        if int(dcnt[active].sum()) == 0:
            self.spec_fallback_steps += 1
            toks = self.step(active)
            return (
                np.asarray(toks).reshape(-1, 1),
                np.where(active, 1, 0).astype(np.int64),
                False,
            )
        # the verify seam fires with drafts already proposed and
        # BEFORE any device work: a crashed verify leaves the target
        # bank untouched (blame retries re-use the cached proposals)
        self._fire("stepper.verify", active=active)
        c = k + 1
        lens0 = self._lens.copy()
        tmask = self._build_tmask(active)
        vmasked = tmask is not None
        sargs = self._sampling_args()
        extra = (tmask,) if vmasked else ()
        if self.paged:
            # verify windows amortize over k+1 candidate tokens, so
            # they too run at the fixed extent (one program per c)
            pbt = self._max_pages_bucket
            key = (c, pbt, vmasked)
            fn = self._pverify_fns.get(key)
            if fn is None:
                self._compiling()
                fn = self._build_verify_fn_paged(c, pbt, vmasked)
                self._pverify_fns = {**self._pverify_fns, key: fn}
            with annotate("serving/verify"):
                self._ctx, self._pools, t_out, n_new = fn(
                    self._params, self._ctx, self._pools, lens0,
                    active, dtoks.astype(np.int32),
                    dcnt.astype(np.int32), self._tables_array(pbt),
                    *sargs, *extra,
                )
        else:
            key = (c, vmasked)
            fn = self._verify_fns.get(key)
            if fn is None:
                self._compiling()
                fn = self._build_verify_fn(c, vmasked)
                self._verify_fns = {**self._verify_fns, key: fn}
            with annotate("serving/verify"):
                self._ctx, self._caches, t_out, n_new = fn(
                    self._params, self._ctx, self._caches, lens0,
                    active, dtoks.astype(np.int32),
                    dcnt.astype(np.int32), None, *sargs, *extra,
                )
        t_out = np.asarray(t_out)
        counts = np.where(active, np.asarray(n_new), 0).astype(np.int64)
        self._lens[active] = np.minimum(
            self._lens[active] + counts[active], self._lens_cap
        )
        self._spos[active] += counts[active].astype(np.int32)
        if self._grammar:
            self._advance_grammar(t_out, counts)
        self.spec_verify_steps += 1
        self.spec_drafted_tokens += int(dcnt[active].sum())
        drafter.sync(active, t_out, counts, lens0)
        return t_out, counts, True

    def write_segment(self, active, toks, counts, lens0) -> None:
        """Write each active row's first ``counts[i]`` tokens at
        positions ``lens0[i] .. lens0[i]+counts[i]-1`` of its context
        row — how a draft bank's proposals are rolled back to the
        verified truth after a window."""
        if self._seg_fn is None:
            import jax
            import jax.numpy as jnp

            self._compiling()

            def seg(ctx, toks, lens0, counts, active):
                b, cw = toks.shape
                rows = jnp.arange(b)[:, None]
                wpos = lens0[:, None] + jnp.arange(cw)[None, :]
                keep = active[:, None] & (
                    jnp.arange(cw)[None, :] < counts[:, None]
                )
                cur = ctx[rows, wpos]
                return ctx.at[rows, wpos].set(
                    jnp.where(keep, toks.astype(ctx.dtype), cur)
                )

            self._seg_fn = self._jit(seg, donate=(0,), out="ctx",
                                     key="accept_segment")
        self._ctx = self._seg_fn(
            self._ctx, np.asarray(toks, np.int32),
            lens0.astype(np.int32), counts.astype(np.int32),
            np.asarray(active, bool),
        )

class ServingEngine:
    """The in-process serving runtime: continuous-batching decode plus
    windowed batch scoring over one model, driven by a dedicated
    scheduler thread. ``server.ServingServer`` fronts it with TCP; it
    is equally usable embedded (the benchmark drives it directly).

    ``generate`` is synchronous (submit + wait); ``submit`` returns the
    ``ServeRequest`` handle for callers managing their own concurrency.
    ``stop(drain=True)`` refuses new work and completes everything
    already admitted or queued before returning — the graceful-shutdown
    contract the server's ``stop`` verb exposes.
    """

    def __init__(self, model, num_slots=8, queue_capacity=64,
                 temperature=0.0, seed=0, top_k=None, top_p=None,
                 kv_dtype=None, predict_batch=64, predict_window=0.005,
                 prefill_chunk="auto", prefix_cache=True,
                 prefix_cache_bytes=64 << 20, quarantine_steps=64,
                 watchdog_interval=10.0, watchdog_grace=None,
                 max_restarts=3, restart_backoff=0.05,
                 metrics_path=None, speculative=None, draft_bundle=None,
                 draft_k=4, ngram_max=3, spec_mode="rejection",
                 flight_recorder=True,
                 recorder_capacity=2048, postmortem_dir=None,
                 slos=None, slo_interval=5.0, paged=False,
                 page_size=16, num_pages=None, qos=None, mesh=None,
                 role="unified", history=True, history_interval=1.0,
                 history_capacity=600, trace_ring=8192, overlap=True,
                 shed=False):
        """``prefill_chunk``: per-scheduler-iteration prefill token
        budget — "auto" picks ``max(16, seq_len // 8)``, an int sets it
        directly, None disables chunking (full synchronous prefill at
        admission, the PR 1 behavior). ``prefix_cache``: True builds a
        byte-bounded ``PrefixStore`` (``prefix_cache_bytes``), a
        ``PrefixStore`` instance is used as-is (shareable across
        engines), falsy disables prefix reuse.

        ``speculative``: enables draft-and-verify decode in the slot
        bank — ``"ngram"`` for the model-free prompt-lookup drafter
        (works with no second model; ``ngram_max`` caps the suffix
        match length), ``"draft"`` for a draft-LM drafter fed by
        ``draft_bundle`` (a serving-bundle path or a model instance),
        ``True`` picks ``"draft"`` when a bundle is given else
        ``"ngram"``, or pass a drafter instance directly. ``draft_k``
        is the proposals-per-window budget; each scheduler iteration
        then emits 1..draft_k+1 tokens per slot, greedy output still
        pinned token-identical to solo greedy decode. Under
        ``spec_mode="rejection"`` (the default) SAMPLED requests ride
        the same verify machinery via rejection sampling
        (distribution-preserving, same-seed replay-exact);
        ``spec_mode="strict"`` is the legacy greedy-only mode
        (temperature=0, no top_k/top_p — anything else refused with
        the historical ValueError).

        Self-healing knobs: ``quarantine_steps`` (scheduler iterations
        a blamed slot sits out — see ``ContinuousBatcher``),
        ``watchdog_interval`` (seconds without a scheduler heartbeat
        before the supervisor declares the thread dead/wedged, fails
        in-flight requests typed, and restarts it with a rebuilt
        stepper; keep it comfortably above the slowest legitimate
        device phase — a first-step XLA compile counts),
        ``watchdog_grace`` (seconds after each scheduler (re)launch
        during which WEDGE detection stays disarmed — fresh prefill
        buckets still compile on the live path even though restarts
        pre-warm the decode step; default ``max(2, watchdog_interval)``;
        dead-thread detection is never graced), ``max_restarts``
        (lifetime restart budget; exhausted
        = the engine stays ``degraded`` and refuses generate with
        ``InternalError``), ``restart_backoff`` (base of the
        exponential full-jitter delay between restarts — the same
        ``networking.RetryPolicy`` schedule clients use).

        Black-box knobs: ``flight_recorder`` (True keeps an always-on
        ``obs.FlightRecorder`` ring of ``recorder_capacity`` events —
        scheduler iterations, blame/quarantine, watchdog trips, armed
        fault-seam firings; False disables it, the bench's A/B
        control), ``postmortem_dir`` (where terminal events — watchdog
        trips, permanent degradation — dump their post-mortem bundle;
        None keeps the latest bundle in memory only, still served by
        the ``postmortem`` verb), ``slos`` (a list of ``obs.SloSpec``
        — see ``obs.default_serving_slos``; verdicts ride ``health()``
        as ``slo``/``slo_violations``, re-evaluated at most every
        ``slo_interval`` seconds; breaches count in
        ``serving_slo_breaches`` and land in the recorder).

        Time-series knobs: ``history`` (True — the default — keeps an
        ``obs.MetricsHistory`` ring of periodic registry snapshots,
        snapped from the supervisor thread's poll loop at
        ``history_interval`` seconds, ``history_capacity`` snapshots
        deep: ten minutes at the defaults, exactly the slow burn
        window; False is the bench's A/B control). The ring answers
        the ``timeseries`` DKT1 verb (windowed rates / quantiles /
        trends) and — when ``slos`` are configured — multi-window
        BURN-RATE verdicts riding ``health`` as ``burn`` next to the
        point-in-time ``slo`` block. ``trace_ring``: the span ring's
        capacity (``obs.TraceCollector``); the first dropped span
        lands a ``trace.drops`` event on the flight recorder, so span
        loss under load is on the incident tape, not only a gauge.

        QoS knob: ``qos`` — an optional ``qos.QosPolicy``. None keeps
        the single-FIFO scheduler. A policy turns the queue into
        priority classes + per-tenant weighted fair queuing, and
        (``preempt=True``) lets a higher-priority arrival displace
        the lowest-priority decodable slot by serializing its KV out
        to host (``swap_out``) and freeing its pages; resume is
        restore + re-reserve, token-identical across the boundary.
        Requests carry ``tenant``/``priority`` via ``submit``.

        Capacity knobs: ``paged=True`` swaps the stepper's per-slot
        contiguous K/V caches for the block-paged pool (``page_size``
        tokens per page; ``num_pages`` — None sizes the pool to the
        dense bank's byte budget). Admission reserves exactly each
        request's pages, device-resident prefix pages are shared
        copy-on-write across slots, and pool exhaustion surfaces as
        the typed retriable ``overloaded`` (with ``retry_after_ms``)
        instead of a hung or failed request. See ``DecodeStepper``.

        Scale-up knob: ``mesh`` — tensor-parallel decode over a
        ``NamedSharding`` mesh (``"tp:N"``, an int, or a live
        ``jax.sharding.Mesh``; see ``DecodeStepper``). Weights split
        N ways (models larger than one chip serve at all; the
        weight-read-bound step gets N memory systems), the paged K/V
        pools shard head-wise over the same axis, and EVERY admission
        path — chunked prefill, prefix hits, CoW forks, speculative
        verify, QoS swap — stays pinned token-identical to solo
        decode. Supervisor restarts rebuild the sharded stepper from
        the same config. Mesh geometry rides ``health()`` (``mesh``,
        ``kv_shard_bytes``) and the ``serving_mesh_devices`` /
        ``serving_kv_shard_bytes`` gauges, so the fleet router and
        the autoscaler can see per-replica geometry.

        Loop-structure knob: ``overlap`` (True — the default) runs the
        scheduler's ZERO-BUBBLE loop, as deep as is legal and at most
        two steps: with step N-1 on the device a scheduler call does
        its host work (admission, chunked prefill, deadline sweeps),
        dispatches step N behind it (``DecodeStepper.step_async`` with
        a step in the air), and only then collects and emits N-1 — the
        step's host call runs while the device steps. Where N needs
        N-1's tokens on the host (a grammar, a drafter), before a
        preemption and after a failure, the call collects first
        (``ContinuousBatcher._lookahead_refusal``). Emitted token
        ORDER is unchanged — the overlap moves wall-clock, not
        semantics — and a step that fails surfaces at the collect of
        its own iteration with blame/quarantine behavior identical to
        the sequential loop. ``overlap=False`` is the bit-identical
        sequential control (the bench A/B's baseline side). The bubble
        is measured either way: ``serving_step_bubble_seconds`` /
        ``serving_overlap_efficiency`` in the registry and an
        ``overlap`` block on ``health()`` and ``stats()``, which also
        says how deep the loop ran (``ahead_steps`` of ``steps``,
        ``drained`` by reason, ``discarded_slot_steps``); ``stop()``
        logs the block at INFO.

        ``shed``: adaptive load shedding at the admission door. False
        (the default) keeps the door exactly as it was. True builds a
        ``resilience.AdmissionController`` with defaults, a dict
        passes constructor kwargs, an instance is used as-is; the
        gate's brownout ladder is driven by THIS engine's burn-rate
        verdicts (``burn_verdict``), its CoDel side by admitted
        queue sojourns, and its refusals are typed ``overloaded``
        with honest sojourn-derived ``retry_after_ms``. State rides
        ``health()["shed"]``; the gate object survives supervisor
        restarts (its congestion history is evidence, not state to
        reset)."""
        from distkeras_tpu.obs import MetricsRegistry

        self.model = model
        # disaggregated serving role: "unified" (the default — both
        # prefill and decode, every path byte-for-byte as before),
        # "prefill" (admission + chunked prefill only; finished slots
        # are EXPORTED in the kv_transfer wire format instead of
        # decoded — plain generate is refused typed ``wrong_role``),
        # or "decode" (decodes transferred slots via ``resume``; the
        # ``prefill`` face is refused — plain generate stays allowed,
        # a decode worker CAN serve from scratch and warmups use it).
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified', 'prefill', or 'decode'; "
                f"got {role!r}"
            )
        self.role = str(role)
        self._stepper = None
        self._decode_err = None
        self.prefix_store = None
        # the engine-owned metrics registry: scheduler counters, prefix-
        # cache counters, engine gauges, and request-latency histograms
        # all register here; the server's ``metrics`` verb ships
        # ``metrics_snapshot()``. Component-owned (not module-global)
        # so in-process fleets keep per-replica books.
        self.registry = MetricsRegistry()
        # engine-owned span ring for the same reason: the server
        # records this engine's request spans here, and draining to
        # THIS engine's MetricsLogger can never steal a sibling
        # engine's pending spans in an in-process fleet
        from distkeras_tpu.obs import FlightRecorder, TraceCollector

        # span ring capacity is a knob; the FIRST dropped span lands a
        # ``trace.drops`` recorder event (the 0 -> nonzero transition)
        # so silent span loss under load is on the incident tape
        self.trace_collector = TraceCollector(
            capacity=trace_ring, on_drop=self._on_trace_drop
        )
        # span-ring drops, scrapeable (today they are counted but only
        # visible in the JSONL drain): lifetime total, so a drain's
        # read-and-reset of ``dropped`` never zeroes the gauge
        self.registry.gauge(
            "serving_trace_collector_dropped",
            fn=lambda: self.trace_collector.dropped_total,
        )
        # the black box: always-on ring of component events; every
        # self-healing decision and armed seam firing lands here, and
        # terminal events dump it as a post-mortem bundle
        self.recorder = (
            FlightRecorder(capacity=recorder_capacity)
            if flight_recorder
            else None
        )
        if self.recorder is not None:
            self.recorder.register_gauges(self.registry, "serving")
        # the XLA compile ledger: engine-owned (it must survive
        # supervisor restarts — a rebuilt stepper's recompiles are
        # attributed as rewarms, and the counters never reset under
        # the history ring), handed to every stepper generation via
        # the config. Counts serving_compiles / _compile_seconds and
        # detects post-warmup compile STORMS (gauge + recorder event).
        from distkeras_tpu.obs import CompileLedger, MetricsHistory

        self.compile_ledger = CompileLedger(
            registry=self.registry, recorder=self.recorder,
            prefix="serving", inflight_fn=self._inflight_estimate,
        )
        # the performance time-series ring: periodic registry
        # snapshots (the supervisor thread's poll loop is the cadence
        # — no new thread) answering windowed queries and burn-rate
        # SLO verdicts; ``history=False`` is the bench's A/B control
        self.history = (
            MetricsHistory(
                self.metrics_snapshot, interval=history_interval,
                capacity=history_capacity,
            )
            if history
            else None
        )
        self.postmortem_dir = postmortem_dir
        self.last_postmortem = None
        self.last_postmortem_path = None
        store = None
        if prefix_cache:
            from distkeras_tpu.serving.prefix_cache import PrefixStore

            store = (
                prefix_cache
                if isinstance(prefix_cache, PrefixStore)
                else PrefixStore(
                    max_bytes=prefix_cache_bytes, registry=self.registry
                )
            )
        # resolve the serving mesh LOUDLY at bundle load: an
        # unparseable spec or a mesh wider than the device pool must
        # fail the boot health-check, not the first step
        self._mesh = None
        if mesh is not None:
            from distkeras_tpu.parallel.mesh import serving_mesh

            self._mesh = serving_mesh(mesh)
        drafter = self._resolve_drafter(
            speculative, draft_bundle, ngram_max
        )
        self.spec_mode = spec_mode
        if drafter is not None:
            # a config error, not a model limitation: validate here
            # (the ONE shared helper — the stepper re-checks through
            # the same code) rather than letting a stepper ValueError
            # silently demote the engine to predict-only
            from distkeras_tpu.serving.sampling import check_spec_sampling

            self.spec_mode = check_spec_sampling(
                spec_mode, temperature, top_k, top_p
            )
        # everything a supervisor restart needs to rebuild the device
        # face from scratch (fresh slot bank, fresh caches, recompiled
        # programs; the host-side prefix store SURVIVES restarts, and
        # the drafter re-binds to each rebuilt stepper)
        self._stepper_cfg = dict(
            num_slots=num_slots, temperature=temperature, seed=seed,
            top_k=top_k, top_p=top_p, kv_dtype=kv_dtype,
            prefix_cache=store, speculative=drafter, draft_k=draft_k,
            spec_mode=self.spec_mode, paged=paged, page_size=page_size,
            num_pages=num_pages, recorder=self.recorder,
            mesh=self._mesh, compile_ledger=self.compile_ledger,
        )
        try:
            self._stepper = DecodeStepper(model, **self._stepper_cfg)
            self._stepper.on_compile = self._extend_grace
            if self._stepper.prefix_caches_off:
                from distkeras_tpu.serving.prefix_cache import PrefixStore

                self._stepper._refuse_unsupported(
                    f"role {role!r} (K/V export and resume)"
                    if role != "unified" else None,
                    "a shared PrefixStore (prefix caches over its pages)"
                    if isinstance(prefix_cache, PrefixStore) else None,
                )
                # the stepper switched the prefix caches off for this
                # page layout; stats()["paged"]["prefix_caches"] says so
                store = None
            self.prefix_store = store
            if store is not None:
                # fabric staleness at a glance: seconds since the
                # store's content (and so its advertised digest) last
                # moved — the dkt_top fabric column's "age"
                self.registry.gauge(
                    "serving_kv_fabric_digest_age_seconds",
                    fn=store.digest_age,
                )
        except ValueError as e:
            if self._mesh is not None:
                # a mesh was requested explicitly for sharded decode:
                # demoting to predict-only would hide a config error
                # (e.g. heads not divisible by tp) — fail the boot
                raise
            # non-LM models still serve the predict verb; generate
            # replies with this error instead of refusing to boot
            self._decode_err = e
        if self._stepper is not None and prefill_chunk == "auto":
            prefill_chunk = max(16, min(self._stepper.max_len // 8,
                                        self._stepper.chunk_cap))
        from distkeras_tpu.serving.resilience import as_shed_gate

        # the overload gate rides _batcher_cfg so a supervisor-rebuilt
        # batcher keeps the SAME gate (sojourn history and brownout
        # state are evidence about the host, not about one batcher)
        self.shed_gate = as_shed_gate(shed, burn_fn=self.burn_verdict)
        if self.shed_gate is not None:
            # brownout rung as a gauge (0=ok..3=refuse) so dkt_top and
            # the history rings can see shedding without a stats RPC;
            # registered only when shedding is enabled so default
            # metric sets stay byte-identical
            self.registry.gauge(
                "serving_shed_rung",
                fn=lambda: self.shed_gate.state()["rung"],
            )
        self._batcher_cfg = dict(
            queue_capacity=queue_capacity, prefill_chunk=prefill_chunk,
            quarantine_steps=quarantine_steps, registry=self.registry,
            recorder=self.recorder, qos=qos, overlap=overlap,
            shed_gate=self.shed_gate,
        )
        self.qos = qos
        self.batcher = (
            None
            if self._stepper is None
            else ContinuousBatcher(
                self._stepper, span=_span, **self._batcher_cfg
            )
        )
        from distkeras_tpu.data.dataset import Dataset
        from distkeras_tpu.predictors import ModelPredictor

        self._Dataset = Dataset
        self._predictor = ModelPredictor(
            model, batch_size=int(predict_batch)
        )
        self._predict_batcher = WindowedBatcher(
            self._run_predict_batch, max_batch=int(predict_batch),
            max_wait=float(predict_window),
        )
        self.metrics = None
        if metrics_path is not None:
            from distkeras_tpu.utils.profiling import MetricsLogger

            self.metrics = MetricsLogger(metrics_path)
        self._thread = None
        self._stop_evt = threading.Event()
        self._started = False
        # supervisor state: the scheduler loop stamps _heartbeat every
        # iteration; the supervisor thread watches it and the thread's
        # liveness, failing in-flight work typed and restarting the
        # loop (rebuilt stepper) under the bounded restart budget
        self.watchdog_interval = float(watchdog_interval)
        self.watchdog_grace = (
            max(2.0, self.watchdog_interval)
            if watchdog_grace is None
            else float(watchdog_grace)
        )
        self._grace_until = 0.0
        self.max_restarts = int(max_restarts)
        self._restart_delays = RetryPolicy(
            max_attempts=self.max_restarts + 1,
            base_delay=float(restart_backoff), max_delay=2.0, seed=seed,
        )
        self._supervisor = None
        self._crash_evt = threading.Event()  # crash boundary -> supervisor
        self._heartbeat = time.monotonic()
        self._restarts = 0
        self._watchdog_trips = 0
        self._failed = False  # permanently degraded (see _failed_reason)
        self._failed_reason = None
        self._last_crash = None
        # the sender of the server in front of this engine (a
        # ``ServingServer`` puts its own here): its counters are
        # ``stats()["streams"]``
        self.stream_sender = None
        # engine-level gauges (scrape-time callbacks over state the
        # engine already keeps) and per-phase request-latency
        # histograms (log-bucketed: 0.1 ms .. ~52 s in 20 buckets),
        # observed at request completion in ``wait``
        reg = self.registry
        reg.gauge("serving_engine_restarts", fn=lambda: self._restarts)
        reg.gauge(
            "serving_engine_watchdog_trips",
            fn=lambda: self._watchdog_trips,
        )
        reg.gauge("serving_engine_degraded", fn=lambda: self._failed)
        reg.gauge(
            "serving_engine_heartbeat_age_seconds",
            fn=lambda: (
                time.monotonic() - self._heartbeat
                if self._started and self.batcher is not None
                else None
            ),
        )
        reg.gauge(
            "serving_engine_prefix_fetch_failures",
            fn=lambda: (
                0 if self._stepper is None
                else self._stepper.prefix_fetch_failures
            ),
        )
        # sampling & structured-decoding observability: device-side
        # grammar masks applied and all-candidates-zeroed forced-EOS
        # fallbacks (both live on the stepper, like the prefix ledger;
        # sampled-request and forked-slot counters live on the batcher)
        reg.gauge(
            "serving_constrained_masks",
            fn=lambda: (
                0 if self._stepper is None
                else self._stepper.constrained_masks
            ),
        )
        reg.gauge(
            "serving_mask_exhaustions",
            fn=lambda: (
                0 if self._stepper is None
                else self._stepper.mask_exhaustions
            ),
        )
        # mesh geometry gauges: devices this replica's decode spans
        # (1 = solo) and the K/V bytes resident per shard — what a
        # capacity planner compares against one device's HBM, and the
        # ``mesh`` column ``dkt_top`` renders per replica
        reg.gauge(
            "serving_mesh_devices",
            fn=lambda: (
                None if self._stepper is None
                else self._stepper.mesh_devices
            ),
        )
        reg.gauge(
            "serving_kv_shard_bytes",
            fn=lambda: (
                None if self._stepper is None
                else self._stepper.kv_shard_bytes()
            ),
        )
        # disaggregated-serving observability: the role as a stable id
        # (0 unified / 1 prefill / 2 decode — ``dkt_top`` renders the
        # name), the transfer ledger (sends/recvs/errors + bytes both
        # directions), and the in-flight transfer queue depth (prefill
        # requests admitted but not yet exported+encoded, resumes not
        # yet admitted) — the "is the transfer path backing up" gauge
        reg.gauge(
            "serving_engine_role_id",
            fn=lambda: {"unified": 0, "prefill": 1, "decode": 2}[
                self.role
            ],
        )
        self._transfer_pending = 0
        reg.gauge(
            "serving_transfer_pending",
            fn=lambda: self._transfer_pending,
        )
        self.transfer_sends = reg.counter(
            "serving_transfer_sends", fresh=True
        )
        self.transfer_recvs = reg.counter(
            "serving_transfer_recvs", fresh=True
        )
        self.transfer_errors = reg.counter(
            "serving_transfer_errors", fresh=True
        )
        self.transfer_bytes_out = reg.counter(
            "serving_transfer_bytes_out", fresh=True
        )
        self.transfer_bytes_in = reg.counter(
            "serving_transfer_bytes_in", fresh=True
        )
        # the fleet KV fabric's identity + transport: ``kv_epoch`` is
        # a RANDOM 32-bit stamp minted at construction and re-minted
        # on every supervisor restart — random, not a counter, so a
        # restarted process (or a rolled-over replacement on the same
        # endpoint) can never collide with its predecessor's epoch
        # and serve pages a sibling routed to under the old digest.
        # ``peer_fabric`` is the pooled worker-to-worker client spine
        # (kv.fetch pulls, direct disagg pushes); cheap until used —
        # no sockets are opened at construction.
        self.kv_epoch = int.from_bytes(os.urandom(4), "big")
        from distkeras_tpu.serving.kv_transfer import PeerFabric

        self.peer_fabric = PeerFabric(registry=self.registry)
        if paged:
            # page-pool occupancy gauges, read from whichever stepper
            # generation is live (supervisor restarts rebuild the pool)
            def _alloc():
                st = self._stepper
                return None if st is None else st._kv_alloc

            reg.gauge(
                "serving_kv_pages_total",
                fn=lambda: (
                    None if _alloc() is None else _alloc().total_pages
                ),
            )
            reg.gauge(
                "serving_kv_pages_in_use",
                fn=lambda: (
                    None if _alloc() is None else _alloc().pages_in_use
                ),
            )
            reg.gauge(
                "serving_kv_pages_shared",
                fn=lambda: (
                    None if _alloc() is None else _alloc().shared_pages
                ),
            )
            reg.gauge(
                "serving_kv_cow_copies",
                fn=lambda: (
                    None if _alloc() is None else _alloc().cow_copies
                ),
            )
            reg.gauge(
                "serving_kv_page_util",
                fn=lambda: (
                    None if _alloc() is None
                    else round(_alloc().utilization(), 4)
                ),
            )
        self._lat_hists = {
            phase: reg.histogram(f"serving_request_{phase}_seconds")
            for phase in ("queue_wait", "prefill", "decode", "ttft",
                          "total")
        }
        # per-tenant latency histograms (tenant-labeled twins of the
        # above, created lazily per tenant seen in ``wait``) — what
        # per-tenant SLO specs grade, so a QoS violation names WHO.
        # Cardinality-bounded (qos.MAX_TENANT_LABELS): tenant is a
        # client-chosen wire string, and the tail folds rather than
        # growing two histograms per unique name forever
        self._tenant_lat_hists: dict[tuple, object] = {}
        self._tenant_hist_seen: set[str] = set()
        # SLO watchdog: declarative specs graded from THIS registry,
        # cadence-guarded (health polls between evaluations read the
        # cached verdict); breaches count + land in the recorder
        self.slo = None
        if slos:
            from distkeras_tpu.obs import SloEvaluator

            self.slo = SloEvaluator(
                slos, self.metrics_snapshot, interval=slo_interval,
                registry=reg, recorder=self.recorder, prefix="serving",
            )

    def _inflight_estimate(self):
        """Cheap requests-in-flight read for the compile ledger's
        per-mint stamp (queued + slotted; unlocked reads, like the
        occupancy gauges — a torn read is fine for a blast-radius
        number)."""
        batcher = self.batcher
        if batcher is None:
            return None
        try:
            return len(batcher._queue) + sum(
                s is not None for s in batcher._slots
            )
        except Exception:  # noqa: BLE001 — observability boundary
            return None

    def _on_trace_drop(self):
        """First-ever span drop (TraceCollector ``on_drop``): one
        ``trace.drops`` event so the loss is on the incident tape."""
        if self.recorder is not None:
            self.recorder.record(
                "trace.drops",
                capacity=self.trace_collector.capacity,
            )

    @staticmethod
    def _resolve_drafter(speculative, draft_bundle, ngram_max):
        """Map the engine-level speculation knobs onto a draft source
        (None = speculation off)."""
        if not speculative:
            if draft_bundle is not None:
                raise ValueError(
                    "draft_bundle is only meaningful with speculative "
                    "decoding enabled; pass speculative='draft'"
                )
            return None
        if hasattr(speculative, "propose") and hasattr(
            speculative, "bind"
        ):
            # any drafter-protocol object, not just the built-ins —
            # the stepper duck-types the whole protocol
            return speculative
        if speculative is True:
            speculative = "draft" if draft_bundle is not None else "ngram"
        if speculative == "ngram":
            return NgramDrafter(ngram_max=ngram_max)
        if speculative == "draft":
            if draft_bundle is None:
                raise ValueError(
                    "speculative='draft' needs draft_bundle= (a serving-"
                    "bundle path or a model instance)"
                )
            if isinstance(draft_bundle, str):
                from distkeras_tpu.utils.serialization import (
                    load_serving_bundle,
                )

                draft_bundle = load_serving_bundle(draft_bundle)
            return ModelDrafter(draft_bundle)
        raise ValueError(
            f"speculative must be falsy, True, 'ngram', 'draft', or a "
            f"drafter instance; got {speculative!r}"
        )

    @classmethod
    def from_bundle(cls, path: str, **kwargs) -> "ServingEngine":
        """Boot from a quantized serving bundle on disk — what a serving
        host does at startup (``utils.serialization.load_serving_bundle``
        validates structure, shapes, AND dtypes before any weight is
        trusted)."""
        from distkeras_tpu.utils.serialization import load_serving_bundle

        return cls(load_serving_bundle(path), **kwargs)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ServingEngine":
        if self._started:
            return self
        self._started = True
        if self.recorder is not None:
            # every ARMED fault-seam firing becomes a ring event, so a
            # bundle names the injection that preceded the failure
            faults.add_observer(self.recorder.fault_observer)
        self._predict_batcher.start()
        if self.batcher is not None:
            self._launch_scheduler(self.batcher)
            self._supervisor = threading.Thread(
                target=self._supervise, name="serving-supervisor",
                daemon=True,
            )
            self._supervisor.start()
        return self

    def _extend_grace(self):
        """A device program is about to compile (stepper ``on_compile``
        hook, also stamped at each scheduler launch): push the wedge
        detector's grace window out so the compile — however far into
        the serving lifetime it happens (a fresh prompt-length bucket,
        minutes in) — is never read as a wedged scheduler. Dead-thread
        detection is unaffected."""
        self._grace_until = max(
            self._grace_until, time.monotonic() + self.watchdog_grace
        )

    def _launch_scheduler(self, batcher):
        self._heartbeat = time.monotonic()
        self._grace_until = self._heartbeat + self.watchdog_grace
        self._thread = threading.Thread(
            target=self._loop, args=(batcher,), name="serving-engine",
            daemon=True,
        )
        self._thread.start()

    def _loop(self, batcher):
        """The scheduler thread: admit/step/evict until stopped; in
        drain mode, exit only once everything in flight completed. A
        crash that escapes the batcher's own blame machinery fails
        every pending request TYPED (``InternalError``, not a silent
        hang) and hands off to the supervisor, which restarts the loop
        with a rebuilt stepper. ``batcher`` is bound at thread start: a
        superseded (restart-replaced) loop notices and exits instead of
        driving the new generation's state."""
        try:
            while True:
                if self.batcher is not batcher:
                    return  # superseded by a supervisor restart
                self._heartbeat = time.monotonic()
                faults.fire("scheduler.loop", busy=not batcher.idle)
                progressed = batcher.step()
                if self._stop_evt.is_set() and batcher.idle:
                    return
                if not progressed:
                    if self._stop_evt.is_set():
                        return
                    batcher.wait_for_work()
        except Exception as e:  # noqa: BLE001 — scheduler crash boundary
            self._last_crash = repr(e)
            batcher.stop(error=InternalError(
                f"scheduler crashed; request aborted: {e!r}"
            ))
            if self.metrics is not None:
                self.metrics.log(
                    event="serving_engine_crash", error=repr(e)
                )
            self._crash_evt.set()  # wake the supervisor immediately

    # -- supervisor ---------------------------------------------------------

    def _supervise(self):
        """Watchdog: a dead scheduler thread (crash boundary fired) or
        a wedged one (no heartbeat for ``watchdog_interval`` — stuck in
        a device call or a pathological sleep) trips a restart. The
        wedged thread cannot be killed; it is ABANDONED — its batcher
        is stopped (in-flight requests fail typed) and replaced, and
        the zombie exits on its own next iteration via the superseded
        check."""
        poll = max(0.01, min(0.05, self.watchdog_interval / 4))
        while not self._stop_evt.is_set():
            self._crash_evt.wait(timeout=poll)
            self._crash_evt.clear()
            if self._stop_evt.is_set():
                return
            if self.history is not None:
                # the time-series cadence rides this existing poll
                # loop (cadence-guarded: one float compare per tick)
                self.history.maybe_snap()
            th = self._thread
            if th is None or self._failed:
                continue
            now = time.monotonic()
            dead = not th.is_alive()
            wedged = (
                now - self._heartbeat > self.watchdog_interval
                and now > self._grace_until  # compiles are not wedges
            )
            if not dead and not wedged:
                continue
            self._watchdog_trips += 1
            if self.recorder is not None:
                self.recorder.record(
                    "engine.watchdog_trip", dead=dead, wedged=wedged,
                    restarts=self._restarts,
                    heartbeat_age=round(now - self._heartbeat, 3),
                    last_crash=self._last_crash,
                )
            if self.metrics is not None:
                self.metrics.log(
                    event="serving_watchdog_trip",
                    dead=dead, wedged=wedged, restarts=self._restarts,
                )
            # dump BEFORE the restart tears the old batcher down: the
            # bundle's in-flight table is the state at trip time
            self._safe_dump(
                "watchdog_trip",
                {"dead": dead, "wedged": wedged,
                 "last_crash": self._last_crash},
            )
            self._restart(dead)

    def _restart(self, dead):
        """Fail everything the old scheduler generation held (typed —
        clients must never block on a dead loop), then rebuild the
        stepper and relaunch under the restart budget with exponential
        full-jitter backoff (the shared ``RetryPolicy`` schedule)."""
        old = self.batcher
        old.stop(error=InternalError(
            "scheduler " + ("crashed" if dead else "wedged")
            + "; in-flight request aborted by the supervisor"
        ))
        if self._restarts >= self.max_restarts:
            self._failed = True
            self._failed_reason = (
                f"scheduler restart budget exhausted "
                f"({self._restarts}/{self.max_restarts})"
            )
            if self.recorder is not None:
                self.recorder.record(
                    "engine.degraded", reason=self._failed_reason,
                )
            if self.metrics is not None:
                self.metrics.log(
                    event="serving_restart_budget_exhausted",
                    restarts=self._restarts,
                )
            self._safe_dump(
                "degraded", {"reason": self._failed_reason},
            )
            return
        if self._stop_evt.wait(self._restart_delays.delay(self._restarts)):
            return  # shutdown arrived during the backoff
        try:
            # the dead generation's copy of the weights goes before the
            # new stepper places its own from ``model.params``, so that
            # a restart never holds the served tree twice (the old
            # stepper itself outlives this call: its programs keep it
            # in a reference cycle)
            self._stepper._params = None
            stepper = DecodeStepper(self.model, **self._stepper_cfg)
            stepper.on_compile = self._extend_grace
            # compile the decode step HERE, on the supervisor thread,
            # so the first live iteration is serving, not compiling
            stepper.warmup()
        except Exception as e:  # noqa: BLE001 — rebuild is last-resort
            self._failed = True
            self._failed_reason = f"stepper rebuild failed: {e!r}"
            self._last_crash = repr(e)
            if self.recorder is not None:
                self.recorder.record(
                    "engine.degraded", reason=self._failed_reason,
                )
            self._safe_dump(
                "degraded", {"reason": self._failed_reason},
            )
            return
        self._restarts += 1
        self._stepper = stepper
        # new scheduler generation = new KV epoch: siblings holding
        # the old digest get typed ``stale_epoch`` refusals (and fall
        # back to recompute) until their next health poll re-learns
        # this replica — a restarted engine can never serve pages
        # against a promise its predecessor made
        self.kv_epoch = int.from_bytes(os.urandom(4), "big")
        batcher = ContinuousBatcher(
            stepper, span=_span, **self._batcher_cfg
        )
        self.batcher = batcher
        self._launch_scheduler(batcher)
        if self.recorder is not None:
            self.recorder.record(
                "engine.restarted", restarts=self._restarts
            )
        if self.metrics is not None:
            self.metrics.log(
                event="serving_engine_restarted", restarts=self._restarts
            )

    def stop(self, drain=True):
        """Shutdown. ``drain=True``: stop admissions, finish queued and
        in-flight requests, then stop; ``drain=False``: fail them."""
        self._stop_evt.set()
        self._crash_evt.set()  # wake the supervisor so it can exit
        if self._supervisor is not None:
            self._supervisor.join(timeout=10)
            self._supervisor = None
        batcher = self.batcher
        if batcher is not None:
            if drain:
                batcher.drain()
            else:
                batcher.stop()
            batcher._work.set()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        if batcher is not None and (not drain or not batcher.idle):
            # fail anything the loop left behind (hard stop, or a drain
            # whose scheduler thread was already dead)
            batcher.stop()
        if batcher is not None:
            logger.info(
                "serving engine stopped: overlap %s; loop %s; streams %s",
                batcher.overlap_stats(), batcher.loop_stats(),
                None if self.stream_sender is None
                else self.stream_sender.stats(),
            )
        self._predict_batcher.close()
        self.peer_fabric.close()  # pooled peer sockets do not leak
        if self.recorder is not None:
            faults.remove_observer(self.recorder.fault_observer)
        self.drain_traces()  # the tail of the span ring is not lost

    # -- generate -----------------------------------------------------------

    def submit(self, prompt, max_new_tokens, eos_id=None,
               deadline=None, trace=None, sampling=None, tenant=None,
               priority=0, stream=False, kv_peers=None,
               _prefill_only=False) -> ServeRequest:
        """``trace``: an optional ``obs.TraceContext`` — the scheduler
        then keeps the per-request event ledger ``obs.request_spans``
        turns into the server-side phase timeline. None (the default)
        costs nothing.

        ``sampling``: per-request ``SamplingParams`` (or its wire
        dict). None = the engine-wide defaults (greedy unless the
        engine was built with a temperature). ``n > 1`` schedules n
        parallel completions via CoW ``fork_slot`` (paged engines);
        a grammar constrains decoding with device-side token masks.

        ``tenant``/``priority``: the request's QoS identity (default
        tenant "default", priority 0). Without a ``qos`` policy they
        only label metrics; with one they pick the WFQ share and the
        priority class (higher = more urgent, may preempt).

        ``stream``: True = the scheduler pushes each iteration's
        emitted tokens into the request's chunk FIFO
        (``req.next_chunk``) as they are generated, for an in-process
        consumer. The server's streaming ``generate`` passes a sink in
        its place (``push(req, tokens | None)`` and ``wake``): every
        stream of the server hands over to one sender thread, woken
        once an iteration (``ServeRequest``, ``server.StreamSender``).

        ``kv_peers``: the fleet router's page-affinity hint — a list
        of ``{"endpoint": [host, port], "epoch": E, "len": n}`` dicts
        naming siblings whose advertised prefix digest covered this
        prompt. Before admission, any peer promising MORE coverage
        than the local prefix cache is dialed over the peer fabric
        (``kv.fetch``) and the validated pages inserted locally, so
        admission's normal prefix-restore path hits. Strictly
        best-effort and fail-soft: every failure — dead peer, stale
        epoch, breaker open, corrupt frame — leaves the local cache
        untouched and admission recomputes, token-identical to the
        never-fetched run."""
        from distkeras_tpu.serving.sampling import (
            SamplingParams,
            check_spec_sampling,
        )

        if self.role == "prefill" and not _prefill_only:
            raise WrongRoleError(
                "this engine serves role 'prefill': plain generate is "
                "not served here — route prompts through the prefill "
                "verb (the fleet router does this by role)"
            )
        batcher = self.batcher  # one read: restarts swap the attribute
        if batcher is None:
            raise EngineStoppedError(
                f"model does not support generate: {self._decode_err}"
            )
        if not self._started:
            raise EngineStoppedError("engine not started")
        if self._failed:
            raise InternalError(
                f"engine is degraded: {self._failed_reason} "
                f"(last crash: {self._last_crash})"
            )
        sampling = SamplingParams.from_wire(sampling)
        if sampling is not None and self._stepper is not None and (
            self._stepper.speculative
        ):
            # the strict (legacy greedy-agreement) mode refuses sampled
            # requests through the SAME shared validation the
            # constructors use — rejection mode accepts them
            check_spec_sampling(
                self.spec_mode, sampling.temperature, sampling.top_k,
                sampling.top_p,
            )
        if kv_peers:
            # BEFORE the request enters the batcher: the scheduler
            # thread's begin_admit reads the prefix store after this
            # thread's insert, so a successful fetch is visible to
            # exactly this admission
            self._peer_prefetch(prompt, kv_peers)
        req = ServeRequest(
            prompt, max_new_tokens, eos_id=eos_id, deadline=deadline,
            trace=trace, sampling=sampling, tenant=tenant,
            priority=priority, stream=stream,
            prefill_only=_prefill_only,
        )
        return self._admit(req)

    def _admit(self, req: ServeRequest) -> ServeRequest:
        """The one admission path ``submit`` and ``resume`` share:
        batcher submit with the restart-race translation, plus the
        submit-time metrics line."""
        batcher = self.batcher
        try:
            try:
                return batcher.submit(req)
            except EngineStoppedError:
                if self._stop_evt.is_set():
                    raise  # a real shutdown: "stopping" is the truth
                # the batcher we read was stopped by a supervisor
                # restart mid-call — a transient internal condition,
                # not a drain; tell the client the engine's story
                raise InternalError(
                    "scheduler restarting after a failure; retry shortly"
                ) from None
        finally:
            if self.metrics is not None:
                st = batcher.stats()
                self.metrics.log(
                    event="serving_submit", request_id=req.id,
                    prompt_len=int(req.prompt.size),
                    max_new_tokens=req.max_new_tokens,
                    queue_depth=st["queue_depth"],
                    active_slots=st["active_slots"],
                )

    def generate(self, prompt, max_new_tokens, eos_id=None,
                 deadline=None, timeout=None, trace=None,
                 sampling=None, tenant=None, priority=0) -> np.ndarray:
        """Returns the full sequence (prompt + generated, eos-trimmed);
        with ``sampling.n > 1``, a LIST of n such sequences."""
        req = self.submit(
            prompt, max_new_tokens, eos_id=eos_id, deadline=deadline,
            trace=trace, sampling=sampling, tenant=tenant,
            priority=priority,
        )
        return self.wait(req, timeout)

    def wait(self, req: ServeRequest, timeout=None) -> np.ndarray:
        """Block on a submitted request and run the completion
        bookkeeping — latency-histogram observations, the JSONL
        ``serving_complete`` record, and (for traced requests) draining
        finished spans to the metrics sink. The server's ``generate``
        verb uses ``submit`` + ``wait`` so it can hold the request
        handle for the trace timeline; ``generate`` above is the
        embedded one-call face over the same path."""
        try:
            return req.result(timeout)
        finally:
            lat = req.latency()
            for phase, hist in self._lat_hists.items():
                if lat[phase] is not None:
                    hist.observe(lat[phase])
            tenant = getattr(req, "tenant", "default")
            if tenant != "default":
                from distkeras_tpu.serving.qos import fold_tenant

                # tenant-labeled twins of the ttft/total histograms —
                # the series per-tenant SLO specs grade
                tenant = fold_tenant(self._tenant_hist_seen, tenant)
                for phase in ("ttft", "total"):
                    if lat[phase] is None:
                        continue
                    key = (tenant, phase)
                    h = self._tenant_lat_hists.get(key)
                    if h is None:
                        h = self.registry.histogram(
                            f"serving_request_{phase}_seconds",
                            labels={"tenant": tenant},
                        )
                        self._tenant_lat_hists[key] = h
                    h.observe(lat[phase])
            if self.metrics is not None:
                self.metrics.log(
                    event="serving_complete", request_id=req.id,
                    tokens=len(req.tokens),
                    error=None if req.error is None else req.error.code,
                    **{k: v for k, v in lat.items() if v is not None},
                )
                if req.trace is not None:
                    self.drain_traces()

    # -- disaggregated prefill/decode ---------------------------------------

    def _record_transfer(self, event, **fields):
        if self.recorder is not None:
            self.recorder.record(event, **fields)

    def prefill(self, prompt, max_new_tokens, eos_id=None,
                deadline=None, sampling=None, tenant=None, priority=0,
                timeout=None):
        """The prefill worker's half of the role split: admit +
        chunked-prefill ``prompt``, then serialize the finished slot
        (KV rows in the PR 12 swap format + ctx/sampler state) into
        one ``kv_transfer`` wire frame and free the slot — the decode
        half is ``resume`` on another engine. Returns ``(blob, meta)``
        where ``meta`` is the JSON-able transfer summary the wire
        reply header carries.

        Failure contract: the ``kv.transfer`` fault seam fires
        (direction "send") before the state is encoded; any failure —
        seam, export, codec — fails ONLY this request, typed (a
        ``ServingError`` passes through, anything else becomes
        ``internal``), counts in ``serving_transfer_errors``, and
        lands on the flight tape as ``kv.transfer.error`` naming the
        exception class."""
        from distkeras_tpu.serving import kv_transfer

        if self.role == "decode":
            raise WrongRoleError(
                "this engine serves role 'decode': it resumes "
                "transferred slots, it does not prefill for export"
            )
        from distkeras_tpu.serving.sampling import SamplingParams

        sampling = SamplingParams.from_wire(sampling)
        req = self.submit(
            prompt, max_new_tokens, eos_id=eos_id, deadline=deadline,
            sampling=sampling, tenant=tenant, priority=priority,
            _prefill_only=True,
        )
        self._transfer_pending += 1
        try:
            faults.fire("kv.transfer", direction="send",
                        request_id=req.id)
            self.wait(req, timeout)  # raises the typed failure, if any
            blob = kv_transfer.encode_state(
                req.export, prompt_len=int(req.prompt.size),
                sampling=sampling, eos_id=req.eos_id,
            )
        except Exception as e:  # noqa: BLE001 — transfer boundary
            self.transfer_errors.inc()
            self._record_transfer(
                "kv.transfer.error", op="send", request_id=req.id,
                error=type(e).__name__, detail=repr(e)[:200],
            )
            if isinstance(e, ServingError):
                raise
            raise InternalError(
                f"kv transfer send failed: {e!r}"
            ) from e
        finally:
            self._transfer_pending -= 1
            req.export = None  # host KV rows released with the frame
        self.transfer_sends.inc()
        self.transfer_bytes_out.inc(len(blob))
        meta = {
            "len": int(req.prompt.size),
            "prompt_len": int(req.prompt.size),
            "bytes": len(blob),
            "version": kv_transfer.VERSION,
        }
        self._record_transfer(
            "kv.transfer.send", request_id=req.id, bytes=len(blob),
            tokens=int(req.prompt.size),
        )
        return blob, meta

    def resume(self, state, max_new_tokens, eos_id=None, deadline=None,
               trace=None, tenant=None, priority=0,
               stream=False) -> ServeRequest:
        """The decode worker's half: admit a TRANSFERRED slot — a
        ``kv_transfer`` wire frame (bytes) or an already-decoded state
        dict — and decode it to completion. Returns the ``ServeRequest``
        handle (``wait`` for the sequence; ``stream`` as
        ``submit``'s). The resumed stream is pinned
        token-identical to an uninterrupted decode of the same
        (prompt, params) on one engine — the PR 12 swap identity,
        now crossing a process boundary.

        The ``kv.transfer`` seam fires (direction "recv") before the
        frame is decoded; a corrupt/truncated frame raises the typed
        ``KvTransferError`` (never a hang, nothing admitted), and
        every failure lands on the tape naming its class."""
        from distkeras_tpu.serving import kv_transfer

        if self.role == "prefill":
            raise WrongRoleError(
                "this engine serves role 'prefill': transferred slots "
                "resume on a decode worker"
            )
        if self.batcher is None:
            # same typed refusal submit() gives this state — a
            # predict-only engine must not launder it into internal
            raise EngineStoppedError(
                f"model does not support generate: {self._decode_err}"
            )
        try:
            faults.fire("kv.transfer", direction="recv")
            nbytes = None
            if isinstance(state, (bytes, bytearray, memoryview)):
                nbytes = len(state)
                state = kv_transfer.decode_state(bytes(state))
            sampling = state.get("sampling")
            plen = int(state["prompt_len"])
            ln = int(state["len"])
            ctx = np.asarray(state["ctx"], np.int32)
            emitted = [int(t) for t in ctx[plen:ln]]
            grammar = None
            if sampling is not None and sampling.grammar is not None:
                # grammar state is a pure function of (spec, eos,
                # consumed tokens): recompile and replay — no
                # executable state ever rides the frame
                grammar = self._stepper._mask_compiler.compile(
                    sampling.grammar, eos_id=state.get("eos_id")
                )
                for t in emitted:
                    grammar.advance(t)
            req = ServeRequest(
                ctx[:plen], max_new_tokens,
                eos_id=(
                    state.get("eos_id") if eos_id is None else eos_id
                ),
                deadline=deadline, trace=trace, sampling=sampling,
                tenant=tenant, priority=priority, stream=stream,
            )
            req.tokens.extend(emitted)
            # the stepper-format swap dict _resume hands to swap_in —
            # exactly what a QoS preemption parks on the request
            req._swap = {
                "len": ln,
                "ctx": ctx[:ln],
                "kv": state["kv"],
                "spos": int(state["spos"]),
                "seed": int(state["seed"]),
                "params": sampling,
                "grammar": grammar,
                "spec_prompt": state.get("spec_prompt"),
            }
            self._admit(req)
        except Exception as e:  # noqa: BLE001 — transfer boundary
            self.transfer_errors.inc()
            self._record_transfer(
                "kv.transfer.error", op="recv",
                error=type(e).__name__, detail=repr(e)[:200],
            )
            if isinstance(e, ServingError):
                raise
            raise InternalError(
                f"kv transfer receive failed: {e!r}"
            ) from e
        self.transfer_recvs.inc()
        if nbytes is not None:
            self.transfer_bytes_in.inc(nbytes)
        self._record_transfer(
            "kv.transfer.recv", request_id=req.id,
            bytes=nbytes, tokens=ln,
        )
        return req

    # -- fleet KV fabric ----------------------------------------------------

    def _peer_prefetch(self, prompt, kv_peers) -> None:
        """Best-effort peer prefix fetch ahead of one admission: walk
        the router's ``kv_peers`` hints and, for any sibling promising
        more coverage than the local host cache holds, pull its pages
        over the peer fabric and insert them locally (pow2 ladder,
        direct — no two-touch gate: the pages were already proven hot
        on the sibling). Admission's normal prefix-restore path then
        hits exactly as if local traffic had cached them, which is
        why identity is free: a fetch is strictly additive to the
        cache, so success and every failure mode alike decode
        token-identically to the never-fetched run. NEVER raises —
        every failure is counted, recorded, and degraded to
        recompute."""
        store = self.prefix_store
        fab = self.peer_fabric
        if store is None or fab is None:
            return
        tokens = np.asarray(prompt, np.int32).reshape(-1)
        have = store.coverage(tokens)
        for peer in kv_peers:
            try:
                ep = peer.get("endpoint")
                want = int(peer.get("len") or 0)
                epoch = peer.get("epoch")
            except AttributeError:
                continue  # malformed hint: never worth a request
            if ep is None or want <= have:
                continue  # local cache already covers this promise
            try:
                state = fab.fetch(ep, tokens[:want], epoch=epoch)
            except Exception as e:  # noqa: BLE001 — fail-soft boundary
                fab.counters["fetch_degraded"] += 1
                self._record_transfer(
                    "kv.peer.degraded", op="fetch", endpoint=list(ep),
                    error=type(e).__name__, detail=str(e)[:200],
                )
                continue
            if state is None:
                # clean typed miss: the digest aged out on the sibling
                fab.counters["fetch_degraded"] += 1
                self._record_transfer(
                    "kv.peer.degraded", op="fetch", endpoint=list(ep),
                    error="miss", detail="peer no longer holds pages",
                )
                continue
            p = int(state["len"])
            if p > have:
                store.insert_prefixes(tokens[:p], state["kv"])
                have = max(have, store.coverage(tokens))
                self._record_transfer(
                    "kv.peer.fetch", endpoint=list(ep), tokens=p,
                )
            if have >= want:
                return  # the longest promise is covered; stop dialing

    def serve_prefix(self, tokens, epoch=None):
        """The serving half of the fabric's ``kv.fetch`` verb: the
        longest locally-cached prefix of ``tokens`` as a DKTX frame.

        Serves from the HOST prefix store only, by design: the paged
        device pools belong to the scheduler thread (donated buffers
        are invalidated mid-step, so a connection-thread read would
        race the device), while the host ladder is lock-guarded,
        survives restarts, and already mirrors everything the device
        index holds at pow2 granularity — so a fetch hit costs the
        sibling one locked read, never a device sync.

        The epoch gate runs first: a request stamped with an epoch
        this engine no longer serves is refused typed
        (``stale_epoch``) — the sibling routed on a digest advertised
        before a restart/rollover, and pages served across that
        boundary could have been computed under different weights.
        Returns ``(blob, reply_header)``; a miss is ``(None, header)``
        with ``hit: false`` — typed, so the requester degrades to
        recompute silently."""
        from distkeras_tpu.serving import kv_transfer

        fab = self.peer_fabric
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        faults.fire(
            "kv.peer", direction="serve", tokens=int(tokens.size)
        )
        if epoch is not None and int(epoch) != int(self.kv_epoch):
            fab.counters["stale_refusals"] += 1
            self._record_transfer(
                "kv.peer.stale", asked=int(epoch),
                current=int(self.kv_epoch),
            )
            raise StaleEpochError(
                f"kv epoch {int(epoch)} is not current (this engine "
                f"serves epoch {self.kv_epoch}): the digest you "
                f"routed on predates a restart or rollover"
            )
        store = self.prefix_store
        if store is None:
            raise PeerError("this replica serves no prefix cache")
        hit = store.peek(tokens)
        if hit is None:
            fab.counters["fetch_miss"] += 1
            return None, {"ok": True, "hit": False}
        p, kv = hit
        blob = kv_transfer.encode_prefix(
            tokens[:p], kv, epoch=self.kv_epoch
        )
        fab.counters["fetch_served"] += 1
        fab.counters["bytes_out"] += len(blob)
        self._record_transfer(
            "kv.peer.serve", tokens=int(p), bytes=len(blob)
        )
        return blob, {
            "ok": True, "hit": True, "len": int(p),
            "epoch": int(self.kv_epoch),
        }

    def fabric_snapshot(self) -> dict:
        """The fleet-fabric ledger (rides ``stats`` and the dkt_top
        fabric columns): peer transfer counters, breaker states, the
        retry-budget ledger, this engine's KV epoch, and the prefix
        digest siblings route on."""
        out = self.peer_fabric.snapshot()
        out["epoch"] = int(self.kv_epoch)
        if self.prefix_store is not None:
            out["digest"] = self.prefix_store.digest()
        return out

    def drain_traces(self) -> int:
        """Flush this engine's trace collector into its
        ``MetricsLogger`` (one ``trace_span`` JSONL line per span);
        no-op without a ``metrics_path``. Returns spans written."""
        if self.metrics is None:
            return 0
        return self.trace_collector.drain_to(self.metrics)

    # -- predict ------------------------------------------------------------

    def _run_predict_batch(self, x):
        with annotate("serving/predict_batch"):
            ds = self._Dataset({"features": x})
            return self._predictor.predict(ds)["prediction"]

    def predict(self, x, timeout=None) -> np.ndarray:
        """Batch-scoring face: rows accumulate into the current window
        and run as one padded ``ModelPredictor`` forward."""
        if not self._started:
            raise EngineStoppedError("engine not started")
        return self._predict_batcher.submit(x).result(timeout)

    # -- observability ------------------------------------------------------

    def metrics_snapshot(self) -> list:
        """JSON-able samples of every registered metric — the payload
        of the server's ``metrics`` verb. A shared ``PrefixStore``
        instance passed in from outside keeps its own registry; its
        samples are merged here so the verb still sees the cache."""
        samples = self.registry.snapshot()
        store = self.prefix_store
        if store is not None and store.registry is not self.registry:
            samples = samples + store.registry.snapshot()
        return samples

    def timeseries(self, window=None, names=None, points=30) -> dict:
        """The ``timeseries`` DKT1 verb's payload: windowed rate /
        quantile / trend digests of every registered series (see
        ``obs.MetricsHistory.digest``) plus — when SLOs are
        configured — the multi-window burn-rate verdict. ``window``
        defaults to the fast burn window (60 s). Raises ``ValueError``
        when the engine was built with ``history=False`` (the wire
        maps it to ``bad_request``)."""
        from distkeras_tpu.obs import FAST_WINDOW

        if self.history is None:
            raise ValueError(
                "metrics history disabled (ServingEngine(history="
                "False)); the timeseries verb has nothing to serve"
            )
        self.history.maybe_snap()  # predict-only engines have no
        # supervisor thread; a query is its own cadence
        out = self.history.digest(
            window=FAST_WINDOW if window is None else float(window),
            names=names, points=int(points),
        )
        out["ok"] = True
        out["burn"] = self.burn_verdict()
        return out

    def burn_verdict(self) -> dict | None:
        """Multi-window burn-rate verdict over the configured SLO
        specs (None without both ``slos`` and ``history``): fast 1m /
        slow 10m, verdicts ``ok`` / ``spiking`` (fast window only —
        happening now) / ``burning`` (slow only — budget eroding) /
        ``breach`` (both — sustained AND current)."""
        if self.history is None or self.slo is None:
            return None
        self.history.maybe_snap()
        return self.history.burn(self.slo.specs)

    def _safe_dump(self, reason, detail):
        """Supervisor-path dump: a post-mortem failure (snapshot race,
        disk) must never break the self-healing it documents."""
        try:
            self.dump_postmortem(reason, detail=detail)
        except Exception as e:  # noqa: BLE001 — observability boundary
            if self.metrics is not None:
                self.metrics.log(
                    event="postmortem_dump_failed", reason=reason,
                    error=repr(e),
                )

    def dump_postmortem(self, reason: str, detail=None):
        """Dump this engine's post-mortem bundle (the shared
        ``obs.dump_postmortem`` schema): flight-recorder ring, metrics
        snapshot, the batcher's in-flight request table with trace
        ids (plus any spans the collector still holds for them), the
        serving config, armed fault-seam state, and a FORCED SLO
        verdict as of the dump. Kept on ``last_postmortem`` for the
        ``postmortem`` verb; written to ``postmortem_dir`` when one is
        configured. Returns ``(bundle, path)``."""
        from distkeras_tpu.obs import dump_postmortem as _dump

        batcher = self.batcher
        in_flight = (
            [] if batcher is None else batcher.inflight_snapshot()
        )
        trace_spans = []
        for row in in_flight:
            if row["trace_id"] is not None:
                trace_spans.extend(
                    self.trace_collector.spans_for(row["trace_id"])
                )
        cfg = dict(self._batcher_cfg)
        cfg.pop("registry", None)
        cfg.pop("recorder", None)
        gate = cfg.pop("shed_gate", None)
        if gate is not None:
            cfg["shed"] = gate.state()
        cfg.update(
            model=type(self.model).__name__,
            num_slots=(
                None if self._stepper is None
                else self._stepper.num_slots
            ),
            speculative=(
                self._stepper is not None
                and bool(self._stepper.speculative)
            ),
            watchdog_interval=self.watchdog_interval,
            watchdog_grace=self.watchdog_grace,
            max_restarts=self.max_restarts,
        )
        bundle, path = _dump(
            self.postmortem_dir, "serving_engine", reason,
            recorder=self.recorder, metrics=self.metrics_snapshot(),
            in_flight=in_flight, config=cfg, trace_spans=trace_spans,
            slo=None if self.slo is None else self.slo.evaluate(),
            detail=detail,
        )
        self.last_postmortem = bundle
        self.last_postmortem_path = path
        if self.metrics is not None:
            self.metrics.log(
                event="postmortem_dumped", reason=reason, path=path,
            )
        return bundle, path

    def postmortem(self):
        """Latest bundle for the ``postmortem`` DKT1 verb: the
        in-memory last dump, falling back to the newest file in
        ``postmortem_dir`` (a restarted process still serves the bundle
        its predecessor wrote). ``(bundle_or_None, path_or_None)``."""
        if self.last_postmortem is not None:
            return self.last_postmortem, self.last_postmortem_path
        if self.postmortem_dir is not None:
            from distkeras_tpu.obs import latest_postmortem

            return latest_postmortem(self.postmortem_dir)
        return None, None

    def transfer_snapshot(self) -> dict:
        """The kv-transfer ledger (rides ``health``/``stats``):
        frames sent/received/errored, bytes both directions, and the
        in-flight transfer queue depth."""
        return {
            "pending": self._transfer_pending,
            "sends": self.transfer_sends.value,
            "recvs": self.transfer_recvs.value,
            "errors": self.transfer_errors.value,
            "bytes_out": self.transfer_bytes_out.value,
            "bytes_in": self.transfer_bytes_in.value,
        }

    def health(self) -> dict:
        """Liveness summary, cheap enough for a load balancer to poll:
        ``status`` is ``serving`` (scheduler heartbeating), ``degraded``
        (scheduler dead/restarting, or the restart budget is exhausted),
        or ``draining`` (shutdown in progress); plus the heartbeat age,
        the quarantined-slot count, the restart ledger, and — when
        SLOs are configured — the cadence-guarded SLO verdict
        (``slo``: ok|warn|breach, ``slo_violations`` naming the
        violating series)."""
        batcher = self.batcher
        if self._stop_evt.is_set():
            status = "draining"
        elif batcher is None:
            status = "serving"  # predict-only engines have no scheduler
        else:
            th = self._thread
            now = time.monotonic()
            healthy = (
                self._started
                and not self._failed
                and th is not None
                and th.is_alive()
                and (
                    now - self._heartbeat <= self.watchdog_interval
                    # a stale heartbeat inside the compile/launch grace
                    # is the supervisor's definition of fine — health
                    # must not pull a node the watchdog would not trip
                    or now <= self._grace_until
                )
            )
            status = "serving" if healthy else "degraded"
        out = {
            "status": status,
            # the disaggregation role rides health so the fleet
            # router's books (and its role-aware dispatch) learn each
            # replica's role from the same poll that gates rotation
            "role": self.role,
            "restarts": self._restarts,
            "max_restarts": self.max_restarts,
            "restart_budget_exhausted": self._failed,
            "watchdog_trips": self._watchdog_trips,
            "quarantined_slots": (
                0 if batcher is None else len(batcher._quarantined)
            ),
            "transfer": self.transfer_snapshot(),
            # the fleet KV fabric's routing surface: this engine's KV
            # epoch plus the compact prefix digest (gen-memoized — an
            # unchanged cache costs one int compare per poll). The
            # router's page-aware routing and peer-fetch hints are
            # computed entirely from this block.
            "kv_fabric": {
                "epoch": int(self.kv_epoch),
                "digest": (
                    None
                    if self.prefix_store is None
                    else self.prefix_store.digest()
                ),
                # the peer-transfer ledger summary (plain int reads) —
                # republished by the router's replica books so the
                # dkt_top fabric columns need no metrics scrape
                "peer": {
                    k: self.peer_fabric.counters[k]
                    for k in (
                        "fetches", "fetch_ok", "fetch_degraded",
                        "fetch_served", "fetch_miss", "pushes",
                        "push_ok", "push_degraded", "stale_refusals",
                        "bytes_in", "bytes_out",
                    )
                },
            },
        }
        if batcher is not None:
            # load surface for routers/load-balancers: occupancy plus
            # the capacity bounds (slots + queue) a fleet router uses
            # to account per-replica in-flight work and shed overload
            out.update(batcher.load())
        if batcher is not None and getattr(
            self._stepper, "speculative", False
        ):
            # the load-balancer-facing acceptance aggregate: mean
            # tokens emitted per verify window (1.0 = drafts never
            # agree, draft_k+1 = ceiling); None until the first window
            w = batcher.counters.get("spec_windows", 0)
            out["speculative_tokens_per_window"] = (
                round(batcher.counters["spec_tokens"] / w, 2)
                if w else None
            )
            # drafter exceptions spec_step swallowed (the fall-back to
            # the plain step is otherwise invisible: tokens still match)
            out["speculative_draft_failures"] = int(
                self._stepper.spec_draft_failures
            )
        if batcher is not None and getattr(self._stepper, "paged", False):
            # pool pressure for routers/load balancers: the fraction of
            # KV pages in use — the paged tier's real capacity signal
            # (slot occupancy alone no longer bounds admissions)
            out["kv_page_util"] = round(
                self._stepper._kv_alloc.utilization(), 4
            )
        if batcher is not None and self._stepper is not None:
            # per-replica geometry for the router/autoscaler: how many
            # devices this replica's decode spans and the K/V bytes
            # each shard holds (mesh also rides ``batcher.load()``)
            out["kv_shard_bytes"] = self._stepper.kv_shard_bytes()
        if batcher is not None and self.history is not None:
            # the autoscaler's windowed signals, computed replica-side
            # over the engine's own history ring and republished by
            # the router's books: how often admission hit an exhausted
            # page pool in the last minute, and which way the queue
            # is trending (req/s of depth growth — the leading
            # indicator a point-in-time depth sample misses)
            self.history.maybe_snap()
            out["pool_exhausted_rate"] = self.history.rate(
                "serving_scheduler_pool_exhausted", window=60.0
            )
            out["queue_depth_trend"] = self.history.trend(
                "serving_scheduler_queue_depth", window=60.0
            )
        if batcher is not None:
            # the zero-bubble ledger: how much of decode wall-clock the
            # device actually computed (overlap mode or the sequential
            # control — the instrument reads the same either way)
            out["overlap"] = batcher.overlap_stats()
            # how the scheduler thread spent the time between its
            # iterations, and the stalls it met (``loop_stats``)
            out["loop"] = batcher.loop_stats()
        if self.stream_sender is not None:
            # the server's one sender thread: how often the scheduler
            # woke it and how many frames a wake wrote
            out["streams"] = self.stream_sender.stats()
        if self.shed_gate is not None:
            # overload-gate state for routers and dkt_top: the current
            # brownout rung, whether the CoDel side is shedding, and
            # the sojourn EWMA behind the honest retry_after hints
            out["shed"] = self.shed_gate.state()
        out["heartbeat_age"] = (
            None
            if batcher is None or not self._started
            else time.monotonic() - self._heartbeat
        )
        if self.slo is not None:
            verdict = self.slo.maybe_evaluate()
            out["slo"] = verdict["slo"]
            out["slo_violations"] = verdict["violations"]
            if self.history is not None:
                # the burn-rate sibling of the point-in-time verdict:
                # "spiking now" vs "slowly burning" vs sustained
                # breach, from the same spec list over the history
                # ring (fast 1m / slow 10m)
                b = self.burn_verdict()
                out["burn"] = b["burn"]
                out["burn_violations"] = b["violations"]
        if self._last_crash is not None:
            out["last_crash"] = self._last_crash
        return out

    def stats(self) -> dict:
        out = {
            "model": type(self.model).__name__,
            "num_params": int(self.model.num_params()),
            "generate_enabled": self.batcher is not None,
        }
        if self.batcher is not None:
            out.update(self.batcher.stats())
            out["compiled_prefill_buckets"] = sorted(
                self._stepper._admit_fns
            )
            out["compiled_chunk_buckets"] = sorted(
                self._stepper._chunk_fns
            )
            out["prefix_fetch_failures"] = (
                self._stepper.prefix_fetch_failures
            )
            out["paged"] = self._stepper.paged_stats()
            if self._stepper._moe_layers:
                # the expert layers' routing, summed over decode steps
                out["moe"] = {"grouped": self._stepper.grouped,
                              **self._stepper.moe_stats}
            if self._stepper._select:
                out["select"] = dict(self._stepper.select_stats)
            if self._stepper._state_layers:
                # the layers that hold a state a slot: the bytes of state
                # the decode steps moved, the slots reset by an admission
                out["state"] = {
                    **self._stepper.state_stats,
                    "layers": self._stepper._state_layers,
                    "state_bytes_a_slot": self._stepper.state_bytes_a_slot,
                }
        if self.stream_sender is not None:
            out["streams"] = self.stream_sender.stats()
        out["restarts"] = self._restarts
        out["watchdog_trips"] = self._watchdog_trips
        out["status"] = self.health()["status"]
        out["role"] = self.role
        out["transfer"] = self.transfer_snapshot()
        out["kv_fabric"] = self.fabric_snapshot()
        # the XLA compile ledger: every runtime mint with its trigger
        # (warmup vs serving), wall seconds, and the storm count — the
        # soaks assert storms == 0 from exactly this block
        out["compiles"] = self.compile_ledger.snapshot()
        out["prefix_cache"] = (
            self.prefix_store.stats()
            if self.prefix_store is not None
            else {"enabled": False}
        )
        return out
