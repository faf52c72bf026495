"""Online serving subsystem (distkeras_tpu/serving/).

Three tiers, matching the subsystem's layering:

- scheduler unit tests: pure host logic against a fake stepper — no
  sockets, no JAX compiles — pinning admission order, slot eviction
  and reuse, bounded-queue backpressure, deadlines, drain semantics;
- stepper tests: the compiled slot-bank decode must equal
  ``CachedSequenceGenerator``'s greedy decode token for token, for
  every slot, regardless of batch composition churn;
- end-to-end: engine + TCP server + client over localhost — generate
  and predict round trips, ``overloaded`` replies under saturation,
  deadline failures, and graceful drain completing in-flight work.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from distkeras_tpu.serving.scheduler import (
    ContinuousBatcher,
    DeadlineExceededError,
    EngineStoppedError,
    OverloadedError,
    ServeRequest,
    WindowedBatcher,
)

# ------------------------------------------------------------ fake stepper


class FakeStepper:
    """Pure-Python stand-in for the device face: slot ``i`` emits
    ``base + i*100 + n`` for its n-th token, so every scheduling
    decision is visible in the token stream. Prefill is the chunked
    lifecycle contract: ``begin_admit`` reports ``len(prompt) - 1``
    positions to prefill, ``prefill_chunk`` consumes up to the budget;
    every chunk call is recorded so tests can pin the budget."""

    def __init__(self, num_slots=2, max_len=32, base=1000):
        self.num_slots = num_slots
        self.max_len = max_len
        self.base = base
        self.admitted = []  # (slot, prompt list) in admission order
        self.released = []
        self.chunks = []  # (slot, tokens consumed) per prefill_chunk
        self._n = np.zeros(num_slots, int)
        self._left = np.zeros(num_slots, int)

    def begin_admit(self, slot, prompt):
        self.admitted.append((slot, list(np.asarray(prompt))))
        self._n[slot] = 0
        self._left[slot] = max(0, len(np.asarray(prompt)) - 1)
        return int(self._left[slot])

    def prefill_chunk(self, slot, budget):
        n = min(int(budget), int(self._left[slot]))
        self.chunks.append((slot, n))
        self._left[slot] -= n
        return int(self._left[slot])

    def admit(self, slot, prompt):
        left = self.begin_admit(slot, prompt)
        while left:
            left = self.prefill_chunk(slot, left)

    def release(self, slot):
        self.released.append(slot)

    def step(self, active):
        toks = np.full(self.num_slots, -1)
        for i in np.flatnonzero(active):
            self._n[i] += 1
            toks[i] = self.base + i * 100 + self._n[i]
        return toks


class FakeSpecStepper(FakeStepper):
    """Variable-advance fake: every verify call emits a WINDOW of
    ``window`` tokens per active slot (the speculative contract),
    token values following the same slot/sequence scheme as
    ``FakeStepper`` so emission order stays visible."""

    speculative = True
    wants_sequences = False
    draft_k = 3

    def __init__(self, num_slots=2, max_len=32, base=1000, window=3):
        super().__init__(num_slots, max_len, base)
        self.window = window
        self.spec_verify_steps = 0
        self.spec_fallback_steps = 0
        self.spec_drafted_tokens = 0
        self.drafter = type("D", (), {"name": "fake"})()

    def spec_step(self, active, seqs=None):
        active = np.asarray(active, bool)
        w = self.window
        toks = np.zeros((self.num_slots, w), int)
        for i in np.flatnonzero(active):
            for c in range(w):
                self._n[i] += 1
                toks[i, c] = self.base + i * 100 + self._n[i]
        self.spec_verify_steps += 1
        self.spec_drafted_tokens += (w - 1) * int(active.sum())
        return toks, np.where(active, w, 0), True


def _req(plen=3, max_new=4, **kw):
    return ServeRequest(np.arange(1, plen + 1), max_new, **kw)


# ------------------------------------------------------- scheduler units


def test_admission_fifo_and_slot_fill():
    st = FakeStepper(num_slots=2)
    b = ContinuousBatcher(st, queue_capacity=8)
    reqs = [b.submit(_req(max_new=2)) for _ in range(3)]
    b.step()
    # first two requests took the two slots, in submission order
    assert [s for s, _ in st.admitted] == [0, 1]
    assert st.admitted[0][1] == list(reqs[0].prompt)
    assert st.admitted[1][1] == list(reqs[1].prompt)
    b.step()
    assert reqs[0].done and reqs[1].done and not reqs[2].done
    assert reqs[0].result().tolist() == [1, 2, 3, 1001, 1002]
    assert reqs[1].result().tolist() == [1, 2, 3, 1101, 1102]
    # the freed slots pick up the queued request
    b.step()
    b.step()
    assert reqs[2].result().tolist() == [1, 2, 3, 1001, 1002]
    assert st.released == [0, 1, 0]
    s = b.stats()
    assert s["completed"] == 3 and s["queue_depth"] == 0
    assert s["mean_batch_occupancy"] == pytest.approx(6 / 4)


def test_eos_evicts_early():
    class EosStepper(FakeStepper):
        def step(self, active):
            toks = super().step(active)
            return np.where(toks >= 0, [7, 9], toks)  # slot0 -> 7 always

    st = EosStepper(num_slots=2)
    b = ContinuousBatcher(st)
    r0 = b.submit(_req(max_new=10, eos_id=7))
    r1 = b.submit(_req(max_new=3, eos_id=99))
    b.step()
    assert r0.done and not r1.done  # slot0 hit eos on its first token
    assert r0.result().tolist() == [1, 2, 3, 7]
    b.step()
    b.step()
    assert r1.result().tolist() == [1, 2, 3, 9, 9, 9]  # max_new wins


def test_overloaded_rejects_at_bounded_queue():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st, queue_capacity=2)
    b.submit(_req())
    b.submit(_req())
    with pytest.raises(OverloadedError):
        b.submit(_req())
    assert b.stats()["rejected_overloaded"] == 1
    # capacity violations are a ValueError, not backpressure
    with pytest.raises(ValueError, match="exceeds the serving capacity"):
        b.submit(_req(plen=30, max_new=30))


def test_deadline_expired_in_queue():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st)
    dead = b.submit(_req(deadline=time.monotonic() - 0.001))
    live = b.submit(_req(max_new=1))
    b.step()
    assert dead.done
    with pytest.raises(DeadlineExceededError):
        dead.result()
    assert live.result().tolist() == [1, 2, 3, 1001]
    assert st.admitted[0][1] == list(live.prompt)  # dead never admitted


def test_deadline_expires_mid_decode():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st)
    r = b.submit(_req(max_new=20, deadline=time.monotonic() + 0.05))
    b.step()
    assert not r.done  # produced a token within budget
    time.sleep(0.08)
    b.step()
    assert r.done
    with pytest.raises(DeadlineExceededError):
        r.result()
    assert len(r.tokens) == 2  # partial progress recorded
    assert st.released == [0]  # slot freed for the next request


def test_drain_finishes_in_flight_and_refuses_new():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st)
    r0 = b.submit(_req(max_new=3))
    r1 = b.submit(_req(max_new=2))  # still queued when drain starts
    b.step()
    b.drain()
    with pytest.raises(EngineStoppedError):
        b.submit(_req())
    while not b.idle:
        assert b.step() or not b.idle
    assert r0.result().tolist() == [1, 2, 3, 1001, 1002, 1003]
    assert r1.result().tolist() == [1, 2, 3, 1001, 1002]


def test_hard_stop_fails_everything():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st)
    r0 = b.submit(_req(max_new=5))
    r1 = b.submit(_req(max_new=5))
    b.step()
    b.stop()
    for r in (r0, r1):
        with pytest.raises(EngineStoppedError):
            r.result()
    assert b.idle and st.released == [0]


def test_windowed_batcher_never_fit_is_value_error():
    """A predict request larger than the queue can EVER hold is a
    caller error, not transient backpressure — OverloadedError would
    send a well-behaved client into an unwinnable retry loop."""
    wb = WindowedBatcher(lambda x: x, max_batch=4, queue_capacity=8)
    with pytest.raises(ValueError, match="exceeds the queue capacity"):
        wb.submit(np.zeros((9, 2)))


def test_windowed_batcher_coalesces_one_window():
    calls = []

    def run_batch(x):
        calls.append(len(x))
        return x * 2

    wb = WindowedBatcher(run_batch, max_batch=16, max_wait=0.1).start()
    try:
        tickets = [wb.submit(np.full((2, 3), i)) for i in range(3)]
        outs = [t.result(timeout=5) for t in tickets]
        assert calls == [6]  # one window scored all three items
        for i, y in enumerate(outs):
            np.testing.assert_array_equal(y, np.full((2, 3), i * 2))
    finally:
        wb.close()


def test_chunk_budget_bounds_decode_stall():
    """Fairness: admitting a max-length prompt mid-stream must not
    stall an already-decoding slot beyond the configured chunk budget —
    the decoding slot gets its token EVERY iteration while the long
    prompt prefills, and no single chunk exceeds the budget."""
    st = FakeStepper(num_slots=2, max_len=128)
    b = ContinuousBatcher(st, queue_capacity=8, prefill_chunk=4)
    r0 = b.submit(_req(plen=2, max_new=40))
    b.step()
    assert len(r0.tokens) == 1  # r0 decoding
    long = b.submit(
        ServeRequest(np.arange(1, 98, dtype=np.int32), 8)
    )  # 96 prefill positions -> 24 budget-4 chunks
    before = len(st.chunks)
    iters = 0
    while long.first_token is None:
        got = len(r0.tokens)
        assert b.step()
        iters += 1
        # the decoding slot advanced THIS iteration too (no starvation)
        assert len(r0.tokens) == got + 1
    # prefill spread over ceil(96/4) = 24 iterations, one chunk each,
    # every chunk within budget
    new_chunks = st.chunks[before:]
    assert [n for _, n in new_chunks] == [4] * 24
    assert iters == 24  # first token the same iteration prefill ended
    assert b.counters["prefill_tokens"] >= 96
    # the long request still decodes to completion afterwards
    while not long.done:
        b.step()
    assert len(long.tokens) == 8
    lat = long.latency()
    assert lat["prefill"] > 0 and lat["ttft"] >= lat["prefill"]


def test_unbounded_prefill_is_one_chunk():
    """prefill_chunk=None (the PR 1 baseline) admits in one synchronous
    chunk — the stall the budget exists to remove."""
    st = FakeStepper(num_slots=1, max_len=128)
    b = ContinuousBatcher(st, prefill_chunk=None)
    b.submit(ServeRequest(np.arange(1, 98, dtype=np.int32), 2))
    b.step()
    assert st.chunks == [(0, 96)]


def test_latency_splits_queue_prefill_decode():
    st = FakeStepper(num_slots=1, max_len=64)
    b = ContinuousBatcher(st, prefill_chunk=2)
    r0 = b.submit(_req(plen=6, max_new=2))  # 5 positions -> 3 chunks
    r1 = b.submit(_req(plen=2, max_new=1))  # queued behind r0
    steps = 0
    while not (r0.done and r1.done):
        b.step()
        steps += 1
        assert steps < 50
    for r in (r0, r1):
        lat = r.latency()
        assert lat["queue_wait"] >= 0
        assert lat["prefill"] >= 0
        assert lat["decode"] >= 0
        assert lat["ttft"] >= lat["queue_wait"] + lat["prefill"]
        assert lat["total"] >= lat["ttft"]
    # r1 waited in the queue while r0 held the only slot
    assert r1.latency()["queue_wait"] >= r0.latency()["prefill"]


# ------------------------------------------- speculative scheduler units


def test_spec_variable_advance_and_budget_cap_per_token():
    """A slot may emit 1..k+1 tokens per iteration; the max-tokens
    budget is checked PER EMITTED TOKEN, so a window overrunning the
    budget emits exactly up to it and frees the slot the same
    iteration."""
    st = FakeSpecStepper(num_slots=1, window=3)
    b = ContinuousBatcher(st)
    r = b.submit(_req(max_new=5))
    b.step()
    assert len(r.tokens) == 3 and not r.done
    b.step()  # window of 3, budget leaves room for 2
    assert r.done and len(r.tokens) == 5
    assert r.result().tolist() == [1, 2, 3, 1001, 1002, 1003, 1004, 1005]
    assert st.released == [0]
    s = b.stats()
    assert s["spec_windows"] == 2 and s["spec_tokens"] == 5
    # draft attribution: every non-final window token is draft-sourced
    assert s["spec_draft_accepted"] == 2 + 2
    assert s["speculative"]["enabled"]
    assert s["speculative"]["draft_source"] == "fake"
    assert s["speculative"]["mean_tokens_per_window"] == 2.5
    assert s["speculative"]["per_slot_acceptance"][0] == 2.5


def test_spec_eos_mid_window_frees_slot_same_iteration():
    """EOS landing mid-window: the tokens after it are NEVER emitted,
    the request completes trimmed, and the slot is free for the next
    queued request the same iteration it accepted its EOS."""
    st = FakeSpecStepper(num_slots=1, window=4)
    b = ContinuousBatcher(st)
    r0 = b.submit(_req(max_new=10, eos_id=1002))  # 2nd token of window 1
    r1 = b.submit(_req(max_new=2, eos_id=None))
    b.step()
    assert r0.done and len(r0.tokens) == 2  # window tail dropped
    assert r0.result().tolist() == [1, 2, 3, 1001, 1002]
    assert st.released == [0]
    b.step()  # freed slot picked r1 up
    assert r1.done and len(r1.tokens) == 2
    assert b.stats()["spec_tokens"] == 4


def test_spec_deadline_mid_window_stops_emission():
    """A deadline that expired while the window was computing must not
    keep emitting: at most the in-flight token lands (the plain-step
    semantics), the rest of the window is dropped, and the request
    fails typed with its slot freed the same iteration."""
    st = FakeSpecStepper(num_slots=1, window=4)
    b = ContinuousBatcher(st)
    r = b.submit(_req(max_new=20, deadline=time.monotonic() + 0.05))
    b.step()
    assert len(r.tokens) == 4 and not r.done  # within budget
    time.sleep(0.08)  # the deadline expires while "computing"
    b.step()
    assert r.done
    with pytest.raises(DeadlineExceededError):
        r.result()
    # exactly ONE in-flight token landed (the plain-step semantics);
    # the window's post-deadline tail was dropped
    assert len(r.tokens) == 5
    assert st.released == [0]


# ------------------------------------------------------------ prefix store


def _kv(p, stages=2, nh=2, hd=4, fill=1.0):
    return [
        (
            np.full((p, nh, hd), fill, np.float32),
            np.full((p, nh, hd), -fill, np.float32),
        )
        for _ in range(stages)
    ]


def test_prefix_store_hit_miss_and_longest_prefix():
    from distkeras_tpu.serving import PrefixStore

    ps = PrefixStore(max_bytes=1 << 20)
    toks = np.arange(100, 112, dtype=np.int32)
    assert ps.lookup(toks) is None  # miss on empty
    ps.insert(toks[:4], _kv(4, fill=4.0))
    ps.insert(toks[:8], _kv(8, fill=8.0))
    p, kv = ps.lookup(toks)  # longest stored prefix wins
    assert p == 8 and kv[0][0][0, 0, 0] == 8.0
    p, _ = ps.lookup(toks[:6])  # len-8 entry too long for a 6-token key
    assert p == 4
    assert ps.lookup(np.arange(50, 62, dtype=np.int32)) is None
    st = ps.stats()
    assert st["hits"] == 2 and st["misses"] == 2
    assert st["hit_tokens"] == 12 and st["entries"] == 2
    assert 0 < st["hit_rate"] < 1


def test_prefix_store_lru_eviction_and_byte_bound():
    from distkeras_tpu.serving import PrefixStore

    entry_bytes = sum(k.nbytes + v.nbytes for k, v in _kv(4))
    ps = PrefixStore(max_bytes=int(entry_bytes * 2.5))  # fits 2 entries
    a = np.arange(0, 4, dtype=np.int32)
    b = np.arange(10, 14, dtype=np.int32)
    c = np.arange(20, 24, dtype=np.int32)
    ps.insert(a, _kv(4))
    ps.insert(b, _kv(4))
    assert ps.lookup(a) is not None  # refresh a: b is now LRU
    ps.insert(c, _kv(4))  # over budget -> evicts b
    assert ps.stats()["evictions"] == 1
    assert ps.lookup(b) is None
    assert ps.lookup(a) is not None and ps.lookup(c) is not None
    assert ps.stats()["bytes"] <= ps.max_bytes
    # an entry that can never fit is refused, not a store flush
    assert not ps.insert(np.arange(64, dtype=np.int32), _kv(64))
    assert ps.stats()["oversize_rejected"] == 1
    assert ps.stats()["entries"] == 2


def test_prefix_store_two_touch_admission():
    """missing_rungs implements two-touch admission: a rung's first
    miss only marks the ghost list (one-shot prompts never earn a
    device fetch); the second miss asks for the insert."""
    from distkeras_tpu.serving import PrefixStore

    ps = PrefixStore(max_bytes=1 << 20)
    toks = np.arange(300, 320, dtype=np.int32)  # rungs 8, 16
    assert ps.missing_rungs(toks) == []  # first touch: ghost only
    assert ps.missing_rungs(toks) == [8, 16]  # second touch: fetch
    ps.insert_prefixes(toks, _kv(toks.size))
    assert ps.missing_rungs(toks) == []  # stored now
    # the ghost list is bounded: flooding it evicts the oldest marks
    ps2 = PrefixStore(max_bytes=1 << 20, seen_capacity=4)
    a = np.arange(0, 8, dtype=np.int32)
    assert ps2.missing_rungs(a) == []
    for i in range(1, 4):  # 3 floods x 2 rungs = 6 marks > capacity 4
        ps2.missing_rungs(np.arange(i * 50, i * 50 + 16, dtype=np.int32))
    assert ps2.missing_rungs(a) == []  # a's mark was evicted: re-ghosted


def test_prefix_store_pow2_ladder_shares_headers():
    """insert_prefixes stores the pow2 truncations, so two prompts that
    share only a HEADER (not the full prefix) still find each other."""
    from distkeras_tpu.serving import PrefixStore

    ps = PrefixStore(max_bytes=1 << 20)
    header = np.arange(200, 216, dtype=np.int32)  # 16 tokens
    a = np.concatenate([header, [7, 8, 9]]).astype(np.int32)
    ps.insert_prefixes(a, _kv(a.size))
    # a different suffix on the same header hits the len-16 ladder rung
    b = np.concatenate([header, [1, 2, 3, 4]]).astype(np.int32)
    p, _ = ps.lookup(b)
    assert p == 16
    # inserting the same prompt again adds nothing (exact keys exist)
    assert ps.insert_prefixes(a, _kv(a.size)) == 0


# --------------------------------------------------- stepper vs generator


@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(
        vocab_size=61, seq_len=32, d_model=32, num_heads=2, depth=2,
        seed=0,
    )


@pytest.fixture(scope="module")
def lm_ref(lm):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    return CachedSequenceGenerator(lm)


def test_stepper_matches_cached_generator_with_churn(lm, lm_ref):
    """Slots admitted at different times, with different prompt lengths,
    evicted and reused — every slot's greedy stream must equal its solo
    ``CachedSequenceGenerator`` decode (composition independence is THE
    correctness property of continuous batching)."""
    from distkeras_tpu.serving.engine import DecodeStepper

    st = DecodeStepper(lm, num_slots=3)
    rng = np.random.default_rng(0)
    p = [rng.integers(0, 61, n).astype(np.int32) for n in (5, 1, 9, 3)]
    steps = [8, 8, 6, 5]
    ref = [lm_ref.generate(pi[None], steps=s)[0] for pi, s in zip(p, steps)]

    serving = {}  # slot -> request index
    outs = [[] for _ in p]
    admit_at = {2: 1, 4: 2}  # step index -> request index (staggered)
    st.admit(0, p[0])
    serving[0] = 0
    next_req = 3
    for i in range(40):
        ri = admit_at.get(i)
        if ri is not None:
            st.admit(ri, p[ri])  # slots 1 and 2, first occupants
            serving[ri] = ri
        if not serving:
            break
        active = np.zeros(3, bool)
        active[list(serving)] = True
        toks = st.step(active)
        for slot, ri in list(serving.items()):
            outs[ri].append(int(toks[slot]))
            if len(outs[ri]) == steps[ri]:
                del serving[slot]
                st.release(slot)
                if next_req < len(p):  # reuse the freed slot
                    st.admit(slot, p[next_req])
                    serving[slot] = next_req
                    next_req += 1
    for ri in range(len(p)):
        assert outs[ri] == ref[ri][len(p[ri]):].tolist(), f"request {ri}"


def test_stepper_prefill_buckets_are_logarithmic(lm):
    from distkeras_tpu.serving.engine import DecodeStepper

    st = DecodeStepper(lm, num_slots=2)
    rng = np.random.default_rng(1)
    for plen in (1, 2, 3, 4, 5, 6, 7, 9, 12, 17):
        st.admit(0, rng.integers(0, 61, plen).astype(np.int32))
    # 10 distinct prompt lengths compile only the pow2 buckets (a
    # one-token prompt has nothing to prefill — no bucket-0 program,
    # its context-row write is the shared _row_fn)
    assert sorted(st._admit_fns) == [1, 2, 4, 8, 16]


def _decode_slot(st, slot, steps):
    """Drive ``steps`` decode steps with only ``slot`` active."""
    out = []
    for _ in range(steps):
        active = np.zeros(st.num_slots, bool)
        active[slot] = True
        out.append(int(st.step(active)[slot]))
    return out


def test_stepper_chunked_prefill_matches_solo_decode(lm, lm_ref):
    """A prompt prefilled in small budget-bounded chunks must decode
    token-for-token equal to the solo cached generator (which prefills
    in one pass) — chunked prefill is a schedule change, not a model
    change."""
    from distkeras_tpu.serving.engine import DecodeStepper

    st = DecodeStepper(lm, num_slots=2)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 61, 23).astype(np.int32)
    ref = lm_ref.generate(prompt[None], steps=7)[0]
    left = st.begin_admit(0, prompt)
    assert left == 22
    sizes = []
    while left:
        before = left
        left = st.prefill_chunk(0, 5)
        sizes.append(before - left)
    assert sizes == [5, 5, 5, 5, 2]  # budget respected, chunked to done
    assert sorted(st._chunk_fns) == [2, 8]  # pow2 buckets (5 -> 8)
    assert _decode_slot(st, 0, 7) == ref[23:].tolist()


def test_stepper_chunk_buckets_stay_pow2_at_capacity(lm, lm_ref):
    """A prompt prefilling up against the cache's time axis must shrink
    its tail chunk to a pow2 that fits — never compile an arbitrary-
    length program (the O(log T) compile discipline) and never let a
    clamped dynamic_update_slice shift writes onto real rows."""
    from distkeras_tpu.serving.engine import DecodeStepper

    st = DecodeStepper(lm, num_slots=1)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 61, 31).astype(np.int32)  # target 30 of 32
    ref = lm_ref.generate(prompt[None], steps=1)[0]
    left = st.begin_admit(0, prompt)
    while left:
        left = st.prefill_chunk(0, 5)  # pos 25: bucket 8 > room 7
    assert all(b & (b - 1) == 0 for b in st._chunk_fns), st._chunk_fns
    assert _decode_slot(st, 0, 1) == ref[31:].tolist()


def test_stepper_release_mid_prefill_is_benign(lm, lm_ref):
    """release() racing an in-flight chunked admission (engine stop /
    deadline evict) must cancel quietly — the next prefill_chunk
    reports done instead of crashing the engine loop — and the slot
    stays fully reusable."""
    from distkeras_tpu.serving.engine import DecodeStepper

    st = DecodeStepper(lm, num_slots=2)
    rng = np.random.default_rng(12)
    left = st.begin_admit(0, rng.integers(0, 61, 20).astype(np.int32))
    left = st.prefill_chunk(0, 4)
    assert left > 0
    st.release(0)
    assert st.prefill_chunk(0, 4) == 0  # cancelled, not a KeyError
    prompt = rng.integers(0, 61, 5).astype(np.int32)
    ref = lm_ref.generate(prompt[None], steps=4)[0]
    st.admit(0, prompt)
    assert _decode_slot(st, 0, 4) == ref[5:].tolist()


def test_stepper_prefix_cache_hit_matches_solo_decode(lm, lm_ref):
    """Cache-hit, chunked, and combined admission paths all pin to the
    solo cached decode; the store's counters see the traffic."""
    from distkeras_tpu.serving import PrefixStore
    from distkeras_tpu.serving.engine import DecodeStepper

    store = PrefixStore(max_bytes=8 << 20)
    st = DecodeStepper(lm, num_slots=2, prefix_cache=store)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 61, 17).astype(np.int32)
    ref = lm_ref.generate(prompt[None], steps=6)[0]

    st.admit(0, prompt)  # first miss: ghost-marked only (two-touch)
    assert store.stats()["misses"] == 1 and store.stats()["entries"] == 0
    assert _decode_slot(st, 0, 6) == ref[17:].tolist()
    st.release(0)

    st.admit(1, prompt)  # second miss: ladder fetched and inserted
    assert store.stats()["misses"] == 2 and store.stats()["entries"] >= 1
    assert _decode_slot(st, 1, 6) == ref[17:].tolist()
    st.release(1)

    # exact repeat: full hit (16 = plen-1 prefix stored), zero prefill
    left = st.begin_admit(1, prompt)
    assert left == 0
    assert store.stats()["hits"] == 1
    assert store.stats()["hit_tokens"] == 16
    assert _decode_slot(st, 1, 6) == ref[17:].tolist()
    st.release(1)

    # combined: shared header + fresh suffix -> hit covers the pow2
    # rung, chunked prefill computes only the remainder
    ext = np.concatenate(
        [prompt, rng.integers(0, 61, 9).astype(np.int32)]
    )
    ref_ext = lm_ref.generate(ext[None], steps=6)[0]
    left = st.begin_admit(0, ext)
    assert 0 < left < ext.size - 1  # partial hit: suffix only
    while left:
        left = st.prefill_chunk(0, 4)
    assert _decode_slot(st, 0, 6) == ref_ext[26:].tolist()


def test_stepper_spec_ngram_matches_solo_decode_all_paths(lm, lm_ref):
    """Speculative decode with the model-free prompt-lookup drafter
    must stay token-identical to solo greedy decode across EVERY
    admission path — full, chunked, and prefix-cache hit — for both
    repetitive prompts (where proposals actually fire) and random ones
    (rejection-heavy)."""
    from distkeras_tpu.serving import NgramDrafter, PrefixStore
    from distkeras_tpu.serving.engine import DecodeStepper

    store = PrefixStore(max_bytes=8 << 20)
    st = DecodeStepper(
        lm, num_slots=2, prefix_cache=store,
        speculative=NgramDrafter(), draft_k=4,
    )
    rng = np.random.default_rng(23)
    rep = np.array([5, 9, 5, 9, 5, 9, 5, 9, 5], np.int32)
    rnd = rng.integers(0, 61, 13).astype(np.int32)

    def spec_decode(slot, prompt, steps):
        out = []
        while len(out) < steps:
            active = np.zeros(st.num_slots, bool)
            active[slot] = True
            seqs = [None] * st.num_slots
            seqs[slot] = np.concatenate(
                [prompt, np.asarray(out, np.int32)]
            )
            toks, counts, _ = st.spec_step(active, seqs)
            out.extend(
                int(t) for t in np.atleast_1d(toks[slot])[: counts[slot]]
            )
        return out[:steps]

    # full admission (repetitive AND random), slots side by side
    for slot, prompt in ((0, rep), (1, rnd)):
        st.admit(slot, prompt)
    for slot, prompt in ((0, rep), (1, rnd)):
        ref = lm_ref.generate(prompt[None], steps=7)[0]
        assert spec_decode(slot, prompt, 7) == ref[prompt.size:].tolist()
        st.release(slot)
    assert st.spec_verify_steps > 0  # the repetitive prompt proposed
    # chunked admission
    left = st.begin_admit(0, rep)
    while left:
        left = st.prefill_chunk(0, 3)
    ref = lm_ref.generate(rep[None], steps=6)[0]
    assert spec_decode(0, rep, 6) == ref[rep.size:].tolist()
    st.release(0)
    # prefix-cache hit admission (two-touch: second admit stores)
    st.admit(1, rnd)
    st.release(1)
    st.admit(1, rnd)
    st.release(1)
    left = st.begin_admit(1, rnd)
    # 12 prefill positions: the len-8 ladder rung restores, the
    # sub-rung tail chunks — the combined admission path
    assert 0 < left < rnd.size - 1 and store.stats()["hits"] >= 1
    while left:
        left = st.prefill_chunk(1, 3)
    ref = lm_ref.generate(rnd[None], steps=6)[0]
    assert spec_decode(1, rnd, 6) == ref[rnd.size:].tolist()


def test_stepper_spec_self_draft_is_the_ceiling(lm, lm_ref):
    """A draft that always agrees (the target itself) accepts k+1
    tokens every window — the serving-tier sibling of the solo
    generator's ceiling pin — while output stays exactly greedy."""
    from distkeras_tpu.serving.engine import DecodeStepper, ModelDrafter

    st = DecodeStepper(
        lm, num_slots=2, speculative=ModelDrafter(lm), draft_k=3,
    )
    rng = np.random.default_rng(24)
    prompt = rng.integers(0, 61, 6).astype(np.int32)
    ref = lm_ref.generate(prompt[None], steps=12)[0]
    st.admit(0, prompt)
    out = []
    active = np.array([True, False])
    while len(out) < 12:
        toks, counts, used = st.spec_step(active)
        assert used and counts[0] == 4  # every window fully accepted
        out.extend(int(t) for t in toks[0][: counts[0]])
    assert out[:12] == ref[6:].tolist()
    assert st.spec_verify_steps == 3 and st.spec_fallback_steps == 0


@pytest.mark.chaos
def test_spec_verify_crash_blamed_like_decode_step(lm, lm_ref):
    """The stepper.verify seam: a crashing verify must ride the SAME
    blame machinery as a crashing decode step — the newest admission
    fails typed and is quarantined, the survivor keeps its window-
    exact stream (cached proposals re-verified, never re-drafted)."""
    from distkeras_tpu import faults
    from distkeras_tpu.serving import InternalError
    from distkeras_tpu.serving.engine import DecodeStepper, ModelDrafter

    st = DecodeStepper(
        lm, num_slots=2, speculative=ModelDrafter(lm), draft_k=3,
    )
    b = ContinuousBatcher(st, quarantine_steps=3)
    rng = np.random.default_rng(25)
    p0 = rng.integers(0, 61, 5).astype(np.int32)
    p1 = rng.integers(0, 61, 8).astype(np.int32)
    ref0 = lm_ref.generate(p0[None], steps=8)[0]
    r0 = b.submit(ServeRequest(p0, 8))
    b.step()  # r0 decoding alone, one clean window
    r1 = b.submit(ServeRequest(p1, 8))
    with faults.FaultPlan(seed=0).arm("stepper.verify", times=1):
        while not (r0.done and r1.done):
            assert b.step() or not b.idle
    with pytest.raises(InternalError, match="blamed"):
        r1.result()  # newest admission took the blame
    np.testing.assert_array_equal(r0.result(), ref0)  # survivor exact
    s = b.stats()
    assert s["step_failures"] == 1 and s["quarantines"] == 1
    assert s["blame_probes"] >= 1


def test_engine_speculative_wiring_and_validation(lm, lm_ref):
    """Engine-level knobs: speculative='ngram' serves token-identical
    output with the stats/health surfaces filled in; misconfigs raise
    at construction instead of demoting the engine to predict-only."""
    from distkeras_tpu.serving import ServingEngine

    eng = ServingEngine(
        lm, num_slots=2, speculative="ngram", draft_k=4
    ).start()
    try:
        prompt = np.array([4, 11, 4, 11, 4, 11, 4], np.int32)
        ref = lm_ref.generate(prompt[None], steps=8)[0]
        np.testing.assert_array_equal(eng.generate(prompt, 8), ref)
        st = eng.stats()
        spec = st["speculative"]
        assert spec["enabled"] and spec["draft_source"] == "ngram"
        assert spec["draft_k"] == 4
        assert spec["windows"] + spec["fallback_steps"] > 0
        assert "per_slot_acceptance" in spec
        assert "speculative_tokens_per_window" in eng.health()
    finally:
        eng.stop()
    # sampled speculative serving is now legal under the default
    # rejection mode; the legacy greedy-agreement refusal survives as
    # the EXPLICIT strict mode (one shared validation helper)
    ServingEngine(lm, speculative="ngram", temperature=0.7)
    with pytest.raises(ValueError, match="GREEDY"):
        ServingEngine(lm, speculative="ngram", temperature=0.7,
                      spec_mode="strict")
    with pytest.raises(ValueError, match="draft_bundle"):
        ServingEngine(lm, speculative="draft")
    with pytest.raises(ValueError, match="draft_bundle"):
        ServingEngine(lm, draft_bundle="/nope.dkt")  # without speculative
    # the drafter protocol is duck-typed: a custom drafter instance is
    # accepted as-is, not just the built-ins
    from distkeras_tpu.serving import NgramDrafter

    class CustomDrafter(NgramDrafter):
        name = "custom"

    eng = ServingEngine(lm, num_slots=1, speculative=CustomDrafter())
    try:
        assert eng.stats()["speculative"]["draft_source"] == "custom"
    finally:
        eng.stop()


def test_engine_defaults_expose_prefix_and_chunk_knobs(lm):
    """Engine-level wiring: prefix cache on by default, auto chunk
    budget resolved from seq_len, both visible in stats()."""
    from distkeras_tpu.serving import PrefixStore, ServingEngine

    eng = ServingEngine(lm, num_slots=2)
    try:
        st = eng.stats()
        assert st["prefill_chunk"] == 16  # max(16, 32 // 8)
        assert st["prefix_cache"]["enabled"]
        assert st["prefix_cache"]["entries"] == 0
        assert isinstance(eng.prefix_store, PrefixStore)
    finally:
        eng.stop()
    eng = ServingEngine(lm, num_slots=2, prefix_cache=False,
                        prefill_chunk=None)
    try:
        st = eng.stats()
        assert st["prefill_chunk"] is None
        assert st["prefix_cache"] == {"enabled": False}
    finally:
        eng.stop()


# ------------------------------------------------------------- end to end


@pytest.fixture()
def served(lm):
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=4, queue_capacity=16)
    srv = ServingServer(eng).start()
    yield srv
    srv.shutdown()


def _client(srv):
    from distkeras_tpu.serving import ServingClient

    return ServingClient("127.0.0.1", srv.port)


def test_server_generate_predict_stats_roundtrip(lm, lm_ref, served):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 61, n).astype(np.int32)
               for n in (1, 4, 6, 2, 7)]
    refs = [lm_ref.generate(pi[None], steps=6)[0] for pi in prompts]
    results = [None] * len(prompts)

    def worker(i):
        with _client(served) as c:
            results[i] = c.generate(prompts[i], 6)

    ths = [threading.Thread(target=worker, args=(i,))
           for i in range(len(prompts))]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=120)
    for i, (got, want) in enumerate(zip(results, refs)):
        np.testing.assert_array_equal(got, want, err_msg=f"request {i}")

    with _client(served) as c:
        assert c.health()["status"] == "serving"
        x = np.stack([np.resize(p, 32) for p in prompts]).astype(np.int32)
        np.testing.assert_allclose(
            c.predict(x), lm.predict(x), atol=1e-5
        )
        st = c.stats()
        assert st["completed"] == len(prompts)
        assert st["generate_enabled"] and st["num_slots"] == 4
        assert st["mean_batch_occupancy"] >= 1.0


def test_client_stamps_served_by_and_connected_endpoint(lm_ref, served):
    """Placement observability satellite: every reply is stamped with
    the ``(host, port)`` that answered it, mirrored on
    ``last_served_by``, and ``connected_endpoint`` names the live
    socket's peer — the surfaces fleet tests assert prefix-affinity
    placement on instead of reaching into router internals."""
    prompt = np.arange(1, 5, dtype=np.int32)
    ref = lm_ref.generate(prompt[None], steps=4)[0]
    with _client(served) as c:
        assert c.last_served_by is None  # nothing answered yet
        assert c.connected_endpoint == ("127.0.0.1", served.port)
        np.testing.assert_array_equal(c.generate(prompt, 4), ref)
        assert c.last_served_by == ("127.0.0.1", served.port)
        # health replies carry the stamp too, and the server's own
        # canonical endpoint rides the health body
        h = c.health()
        assert tuple(h["served_by"]) == ("127.0.0.1", served.port)
        assert h["endpoint"] == [served.host, served.port]
    # closed client: between connections, no endpoint to report
    assert c.connected_endpoint is None


def test_shutdown_drain_races_stop_verb_while_prefilling(lm, lm_ref):
    """Shutdown-race satellite (the fleet rollover's load-bearing
    path): the ``stop`` verb's side-thread shutdown racing the owner's
    direct ``shutdown()`` while a long admission is still CHUNK-
    PREFILLING and more work sits queued behind it — everything
    already admitted or queued must complete token-identical, both
    shutdown paths must return, nothing may hang."""
    from distkeras_tpu.serving import ServingEngine, ServingServer

    # 1 slot + tiny chunk budget: the long prompt prefills over many
    # scheduler iterations while the second request waits in queue
    eng = ServingEngine(
        lm, num_slots=1, queue_capacity=4, prefill_chunk=4,
        prefix_cache=False,
    )
    srv = ServingServer(eng).start()
    rng = np.random.default_rng(7)
    long_p = rng.integers(0, 61, 24).astype(np.int32)
    short_p = rng.integers(0, 61, 3).astype(np.int32)
    eng.generate(short_p, 1)  # warm the compile so the race window
    # below is about PREFILL, not a first-call XLA build
    refs = [
        lm_ref.generate(long_p[None], steps=6)[0],
        lm_ref.generate(short_p[None], steps=6)[0],
    ]
    results = [None, None]

    def worker(i, p):
        with _client(srv) as c:
            results[i] = c.generate(p, 6)

    ths = [
        threading.Thread(target=worker, args=(0, long_p)),
        threading.Thread(target=worker, args=(1, short_p)),
    ]
    ths[0].start()
    # wait until the long admission is mid-prefill (slot active,
    # decode not yet started), then queue the second request behind it
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["prefilling_slots"] >= 1 or st["active_slots"] >= 1:
            break
        time.sleep(0.002)
    ths[1].start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["active_slots"] + st["queue_depth"] >= 2:
            break
        time.sleep(0.002)
    with _client(srv) as c:
        assert c.stop()["stopping"]  # side-thread drain begins
    srv.shutdown()  # races it; must WAIT, not tear down under it
    for t in ths:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in ths)
    for i, (got, want) in enumerate(zip(results, refs)):
        np.testing.assert_array_equal(
            got, want, err_msg=f"request {i} dropped by the race"
        )
    with pytest.raises(EngineStoppedError):
        eng.generate(short_p, 2)


def test_server_generate_eos_trims(lm, lm_ref, served):
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 61, 4).astype(np.int32)
    ref = lm_ref.generate(prompt[None], steps=8, eos_id=40)[0]
    with _client(served) as c:
        got = c.generate(prompt, 8, eos_id=40)
    np.testing.assert_array_equal(got, ref)


def test_server_replies_overloaded_under_saturation(lm, lm_ref):
    """Acceptance: with one slot and a one-deep queue, a burst of
    concurrent requests gets explicit ``overloaded`` replies for the
    overflow while the admitted ones complete correctly. Clients run
    with ``retry=False`` — this test observes the RAW backpressure
    contract (the default RetryPolicy would absorb the rejections;
    that behavior is pinned in test_faults.py)."""
    from distkeras_tpu.serving import ServingClient, ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=1, queue_capacity=1)
    srv = ServingServer(eng).start()
    try:
        prompt = np.arange(1, 4, dtype=np.int32)
        ref = lm_ref.generate(prompt[None], steps=12)[0]
        n = 6
        barrier = threading.Barrier(n)
        outcomes = [None] * n

        def worker(i):
            with ServingClient("127.0.0.1", srv.port, retry=False) as c:
                barrier.wait()
                try:
                    outcomes[i] = c.generate(prompt, 12)
                except OverloadedError:
                    outcomes[i] = "overloaded"

        ths = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        rejected = [o for o in outcomes if isinstance(o, str)]
        completed = [o for o in outcomes if isinstance(o, np.ndarray)]
        assert rejected, "queue saturation produced no overloaded reply"
        assert completed, "no request completed under saturation"
        for got in completed:
            np.testing.assert_array_equal(got, ref)
        assert eng.stats()["rejected_overloaded"] == len(rejected)
    finally:
        srv.shutdown()


def test_server_refuses_oversized_frames(lm):
    """The serving port takes bytes from untrusted peers: a declared
    frame length past the cap is refused BEFORE buffering, with a typed
    reply, and the connection closes (the stream is unrecoverable)."""
    import socket
    import struct

    from distkeras_tpu.serving import ServingEngine, ServingServer
    from distkeras_tpu.utils.serialization import unpack_frame

    eng = ServingEngine(lm, num_slots=1)
    srv = ServingServer(eng, max_frame_bytes=1 << 16).start()
    try:
        with socket.create_connection(("127.0.0.1", srv.port)) as s:
            s.sendall(struct.pack(">Q", 1 << 40) + b"xx")
            ln = struct.unpack(">Q", s.recv(8))[0]
            body = b""
            while len(body) < ln:
                chunk = s.recv(ln - len(body))
                assert chunk
                body += chunk
            header, _ = unpack_frame(body)
            assert header["error"] == "frame_too_large"
            # server closed the stream: clean EOF, or RST when our
            # unread junk bytes were still in its receive buffer
            try:
                assert s.recv(1) == b""
            except ConnectionResetError:
                pass
    finally:
        srv.shutdown()


def test_shutdown_not_stalled_by_idle_connection(lm):
    """An idle persistent connection (blocked in its next recv) must not
    stall shutdown for the full join timeout or leak its thread — the
    server force-closes lingering sockets after the drain grace."""
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=1)
    srv = ServingServer(eng).start()
    idle = _client(srv)  # holds a connection, sends nothing
    try:
        t0 = time.monotonic()
        srv.shutdown()
        assert time.monotonic() - t0 < 15
        assert not any(t.is_alive() for t in srv._conn_threads)
    finally:
        idle.close()


def test_server_deadline_exceeded(served):
    with _client(served) as c:
        with pytest.raises(DeadlineExceededError):
            c.generate(np.arange(1, 4, dtype=np.int32), 8, deadline_ms=0)


def test_graceful_shutdown_completes_in_flight(lm, lm_ref):
    """Acceptance: the ``stop`` verb drains — requests admitted or
    queued before the stop complete with correct results; requests
    after it are refused."""
    from distkeras_tpu.serving import ServingEngine, ServingError, ServingServer

    eng = ServingEngine(lm, num_slots=2, queue_capacity=16)
    srv = ServingServer(eng).start()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 61, n).astype(np.int32) for n in (2, 5, 3)]
    refs = [lm_ref.generate(pi[None], steps=10)[0] for pi in prompts]
    results = [None] * len(prompts)

    def worker(i):
        with _client(srv) as c:
            results[i] = c.generate(prompts[i], 10)

    ths = [threading.Thread(target=worker, args=(i,))
           for i in range(len(prompts))]
    for t in ths:
        t.start()
    # wait until the burst is actually in flight server-side
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["active_slots"] + st["queue_depth"] >= len(prompts):
            break
        time.sleep(0.005)
    with _client(srv) as c:
        assert c.stop()["stopping"]
    for t in ths:
        t.join(timeout=120)
    for i, (got, want) in enumerate(zip(results, refs)):
        np.testing.assert_array_equal(got, want, err_msg=f"request {i}")
    # the drained engine refuses new work
    with pytest.raises(ServingError):
        eng.generate(prompts[0], 4)
    srv.shutdown()


def test_stop_verb_races_direct_shutdown(lm, lm_ref):
    """Shutdown-race satellite: the ``stop`` verb's side-thread
    ``shutdown()`` racing the owner's direct ``shutdown()`` call, with
    a generate still in flight — the in-flight request must complete
    (drain semantics), both shutdown paths must return, and neither may
    return while the other is still tearing down (the second caller
    WAITS instead of racing)."""
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=2, queue_capacity=8)
    srv = ServingServer(eng).start()
    prompt = np.arange(1, 5, dtype=np.int32)
    ref = lm_ref.generate(prompt[None], steps=10)[0]
    result = [None]

    def worker():
        with _client(srv) as c:
            result[0] = c.generate(prompt, 10)

    th = threading.Thread(target=worker)
    th.start()
    # wait until the request is actually in flight server-side
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["active_slots"] + st["queue_depth"] >= 1:
            break
        time.sleep(0.005)
    with _client(srv) as c:
        assert c.stop()["stopping"]  # side-thread shutdown begins
    srv.shutdown()  # races the side thread; must WAIT for completion
    # by the time the direct call returned, teardown is really done:
    # engine refuses work and no connection threads are left
    with pytest.raises(EngineStoppedError):
        eng.generate(prompt, 2)
    assert not any(t.is_alive() for t in srv._conn_threads)
    th.join(timeout=60)
    assert not th.is_alive()
    np.testing.assert_array_equal(result[0], ref)  # drained, not failed


def test_double_shutdown_is_idempotent(lm):
    """Shutdown-race satellite: ``shutdown()`` twice (and once more via
    the context manager's ``__exit__``) is safe, and the repeat returns
    only after the first teardown completed — no exceptions, no
    half-dead server state, engine ``stop`` also re-entrant."""
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=1)
    with ServingServer(eng) as srv:
        srv.shutdown()
        t0 = time.monotonic()
        srv.shutdown()  # second call: waits/returns, never raises
        assert time.monotonic() - t0 < 5
        assert srv._shutdown_done.is_set()
    # the with-exit above was shutdown call #3; engine stop is also
    # re-entrant on an already-stopped engine
    eng.stop()


def test_engine_from_bundle_and_non_lm_predict_only(tmp_path):
    """Booting from a quantized serving bundle serves the quantized
    numbers; a non-LM model still serves predict but names the decode
    problem on generate."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving import ServingEngine, ServingError
    from distkeras_tpu.utils.serialization import save_serving_bundle

    lm_q = quantize_model(
        zoo.transformer_lm(
            vocab_size=61, seq_len=32, d_model=32, num_heads=2,
            depth=2, seed=0,
        )
    )
    path = str(tmp_path / "lm.dkt")
    save_serving_bundle(path, lm_q)
    metrics = str(tmp_path / "serving_metrics.jsonl")
    eng = ServingEngine.from_bundle(
        path, num_slots=2, metrics_path=metrics
    ).start()
    try:
        prompt = np.arange(1, 6, dtype=np.int32)
        ref = CachedSequenceGenerator(lm_q).generate(prompt[None], 6)[0]
        np.testing.assert_array_equal(eng.generate(prompt, 6), ref)
    finally:
        eng.stop()
    from distkeras_tpu.utils.profiling import read_metrics

    events = [m["event"] for m in read_metrics(metrics)]
    assert "serving_submit" in events and "serving_complete" in events
    done = next(m for m in read_metrics(metrics)
                if m["event"] == "serving_complete")
    assert done["tokens"] == 6 and done["error"] is None
    assert done["total"] >= done["queue_wait"] >= 0

    mlp = zoo.mnist_mlp(hidden=16, seed=0)
    eng = ServingEngine(mlp).start()
    try:
        x = np.random.default_rng(0).standard_normal((3, 784)).astype(
            np.float32
        )
        np.testing.assert_allclose(
            eng.predict(x), mlp.predict(x), atol=1e-6
        )
        with pytest.raises(ServingError, match="does not support generate"):
            eng.generate(np.arange(3), 4)
    finally:
        eng.stop()


# ------------------------------- two steps deep on the real DecodeStepper


def _lookahead_model(layout):
    from distkeras_tpu.models import zoo

    if layout == "latent":
        lm = zoo.mla_moe_lm(
            vocab_size=61, seq_len=48, hidden_size=32, num_heads=2,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            kv_lora_rank=16, intermediate_size=32,
            moe_intermediate_size=16, n_routed_experts=4,
            num_experts_per_tok=2, num_layers=2, seed=0)
        return lm, dict(paged=True, page_size=8, prefill_chunk=8)
    lm = zoo.transformer_lm(
        vocab_size=61, seq_len=48, d_model=32, num_heads=2, depth=2,
        seed=0)
    if layout == "kv":
        return lm, dict(paged=True, page_size=4, prefill_chunk=4)
    return lm, dict(prefill_chunk=4)  # the dense slot bank


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("layout", ["kv", "latent", "bank"])
def test_replies_under_lookahead_equal_the_sequential_engines(
    layout, sampled
):
    """The overlapped engine dispatches step n+1 before it collects
    step n; its replies are token for token ``overlap=False``'s, over
    budget and EOS finishes and slots that change tenant under a
    discarded step, and it compiles the programs the sequential engine
    compiles: the same keys at the same argument signatures."""
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.serving.sampling import SamplingParams

    lm, kw = _lookahead_model(layout)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 61, n).astype(np.int32)
               for n in (5, 2, 13, 7, 3)]
    budgets = (9, 4, 6, 11, 8)

    def serve(eng, eos=None):
        reqs = [
            eng.submit(
                p, n, eos_id=eos,
                sampling=SamplingParams(temperature=0.9, top_k=12, seed=7 + i)
                if sampled else None,
            )
            for i, (p, n) in enumerate(zip(prompts, budgets))
        ]
        return [np.asarray(r.result(timeout=120)).tolist() for r in reqs]

    def run(overlap, eos=None):
        eng = ServingEngine(lm, num_slots=2, overlap=overlap, **kw)
        eng.start()
        try:
            plain = serve(eng)
            if eos is None:  # the third token the first request is given
                eos = plain[0][len(prompts[0]) + 2]
            ended = serve(eng, eos)
            st = eng._stepper
            return plain, ended, eos, eng.stats(), dict(
                programs=sorted(eng.compile_ledger._seen, key=repr),
                step_keys=sorted(
                    st._pstep_fns if kw.get("paged") else st._step_fns),
                lens=st._lens.tolist(), spos=st._spos.tolist(),
                in_the_air=len(st._air),
            )
        finally:
            eng.stop()

    want_plain, want_ended, eos, seq_stats, seq = run(False)
    plain, ended, _, stats, ov = run(True, eos)
    assert plain == want_plain and ended == want_ended
    # it did end by EOS, short of its budget
    assert want_ended[0][-1] == eos and len(want_ended[0]) < len(plain[0])
    assert ov == seq  # no program the sequential engine has not; all parked
    look = stats["overlap"]
    assert look["ahead_steps"] > 0.5 * look["steps"] and not look["drained"]
    assert look["discarded_slot_steps"] >= 1
    seq_look = seq_stats["overlap"]
    assert seq_look["ahead_steps"] == seq_look["discarded_slot_steps"] == 0
    if kw.get("paged"):
        assert (stats["paged"]["compiled_step_buckets"]
                == seq_stats["paged"]["compiled_step_buckets"])


def test_a_slot_with_a_grammar_keeps_the_real_engine_one_step_deep():
    """The stepper's ``constrained_slots`` is what the loop observes:
    while a constrained slot decodes every call collects first (its
    token mask is built from the token), and replies are the
    sequential engine's."""
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.serving.sampling import SamplingParams

    lm, kw = _lookahead_model("kv")
    allow = SamplingParams(
        grammar={"kind": "allow", "tokens": [3, 5, 8, 13]})
    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(7, 10, dtype=np.int32)]

    def run(overlap):
        eng = ServingEngine(lm, num_slots=2, overlap=overlap, **kw)
        eng.start()
        try:
            reqs = [eng.submit(prompts[0], 6, sampling=allow),
                    eng.submit(prompts[1], 12)]
            out = [np.asarray(r.result(timeout=120)).tolist() for r in reqs]
            return out, eng.stats()["overlap"]
        finally:
            eng.stop()

    want, _ = run(False)
    got, look = run(True)
    assert got == want
    assert set(got[0][5:]) <= {3, 5, 8, 13}
    assert look["drained"].get("grammar", 0) >= 3
    # once the constrained request has left, the loop looks ahead again
    assert look["ahead_steps"] >= 1


# ------------------------- a page budget a layer kind (the grouped block)


def _laguna(**kw):
    from distkeras_tpu.models import zoo

    return zoo.laguna_lm(vocab_size=61, seq_len=256, hidden_size=32, **kw)


def _grouped_stepper(num_slots=3, num_pages=60, **kw):
    from distkeras_tpu.serving.engine import DecodeStepper

    return DecodeStepper(_laguna(), num_slots=num_slots, paged=True,
                         page_size=4, num_pages=num_pages, **kw)


def test_a_request_holds_two_budgets_and_release_frees_both():
    """Admission reserves the growing budget (``pages_for(prompt +
    max_new)``, the full layers') and a ring of the window pool (the window
    of 8 over pages of 4: 3 pages) that does not grow with the request: 20
    times the window holds no more window pages than 2 times; release frees
    both."""
    st = _grouped_stepper()
    assert st.window_pages == (0, 9) and st._ring == 3
    st.begin_admit(0, np.arange(1, 13) % 61, max_new=4)     # 2 x the window
    assert st._kv_alloc.pages_in_use == 4 and st.window_pages[0] == 3
    st.begin_admit(1, np.arange(1, 121) % 61, max_new=40)   # 20 x
    assert st._kv_alloc.pages_in_use == 4 + 40 and st.window_pages[0] == 6
    st.begin_admit(2, np.arange(1, 4), max_new=2)           # under a window
    assert st.window_pages[0] == 6 + 2  # pages_for(5) = 2 < the ring
    stats = st.paged_stats()
    assert stats["window"]["pages_in_use"] == 8
    assert stats["window_positions_max"] == 12
    for slot in range(3):
        st.release(slot)
    assert st._kv_alloc.pages_in_use == 0 and st.window_pages == (0, 9)
    assert st._tables == [[], [], []] and st._window_tables == [[], [], []]


@pytest.mark.parametrize("short", ["growing", "window"])
def test_exhaustion_of_either_budget_is_typed_and_holds_nothing(short):
    """All or nothing over both: where either pool cannot cover the
    request, ``PoolExhaustedError`` is raised with no page of the other
    held and the slot as it was."""
    from distkeras_tpu.serving.scheduler import PoolExhaustedError

    st = _grouped_stepper(num_pages=12 if short == "growing" else 60)
    if short == "window":  # someone else holds all but two of the rings' pages
        held = st._window_alloc.alloc(7)
    with pytest.raises(PoolExhaustedError):
        st.begin_admit(0, np.arange(1, 41) % 61, max_new=20)  # 15 + 3 pages
    assert st._kv_alloc.pages_in_use == 0
    assert st.window_pages[0] == (7 if short == "window" else 0)
    assert st._tables[0] == [] and st._window_tables[0] == []
    assert 0 not in st._pending
    if short == "window":
        st._window_alloc.free(held)
    st.begin_admit(0, np.arange(1, 9), max_new=4)  # and the slot still admits
    assert st.window_pages[0] == 3


def test_the_scheduler_s_iteration_carries_the_window_pool():
    """``serving/iter``'s counters: ``pages_*`` stay the growing budget,
    ``window_pages_*`` join them where a layer has a window."""
    st = _grouped_stepper()
    b = ContinuousBatcher(st, queue_capacity=8, prefill_chunk=16)
    req = b.submit(ServeRequest(np.arange(1, 30) % 61, 3))
    for _ in range(6):
        b.step()
        if req.done:
            break
        counts = dict(b._iter_counts)
    assert counts["pages_total"] == 59 and counts["pages_in_use"] == 8
    assert counts["window_pages_total"] == 9
    assert counts["window_pages_in_use"] == 3
    assert req.done and st.window_pages[0] == 0


def _keye(**kw):
    from distkeras_tpu.models import zoo

    return zoo.keye_lm(vocab_size=61, seq_len=256, hidden_size=32, **kw)


@pytest.mark.parametrize("block", ["window", "select"])
@pytest.mark.parametrize("feature", [
    "dense_bank", "speculative", "mesh", "int8", "prefix_store", "fork",
    "swap_out", "swap_in", "role", "solo_generator"])
def test_what_the_engine_cannot_do_for_the_grouped_block_is_refused_typed(
        feature, block, tp_mesh):
    """Each thing the grouped-query block with window layers, or the one
    whose keys an indexer selects, cannot do yet is a
    ``BlockUnsupportedError`` that names it and the block, at construction
    where a construction argument asks for it."""
    from distkeras_tpu.models.mla_moe import BlockUnsupportedError
    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.serving.engine import DecodeStepper, NgramDrafter
    from distkeras_tpu.serving.prefix_cache import PrefixStore

    names = {
        "dense_bank": "dense slot bank", "speculative": "speculative",
        "mesh": "tensor-parallel", "int8": "int8 / int4",
        "prefix_store": "PrefixStore", "fork": "fork / beam",
        "swap_out": "swap-out", "swap_in": "swap-in", "role": "role",
        "solo_generator": "solo cached generators",
    }
    model = _laguna() if block == "window" else _keye()
    what = {"window": "window layers", "select": "an indexer selects"}[block]
    paged = dict(num_slots=2, paged=True, page_size=4, num_pages=40)
    with pytest.raises(BlockUnsupportedError, match=names[feature]) as err:
        if feature == "dense_bank":
            ServingEngine(model, num_slots=2, paged=False)
        elif feature == "speculative":
            DecodeStepper(model, speculative=NgramDrafter(), **paged)
        elif feature == "mesh":
            ServingEngine(model, mesh=tp_mesh(2), **paged)
        elif feature == "int8":
            ServingEngine(quantize_model(model, bits=8), **paged)
        elif feature == "prefix_store":
            ServingEngine(model, prefix_cache=PrefixStore(max_bytes=1 << 20),
                          **paged)
        elif feature == "role":
            ServingEngine(model, role="prefill", **paged)
        elif feature == "solo_generator":
            CachedSequenceGenerator(model).generate(np.ones((1, 4), np.int32), 2)
        else:
            st = DecodeStepper(model, **paged)
            assert st.can_fork is False and st.prefix_index is None
            st.admit(0, np.arange(6), max_new=4)
            if feature == "fork":
                st.fork_slot(0, 1)
            elif feature == "swap_out":
                st.swap_out(0)
            else:
                st.swap_in(1, {"len": 3})
    if feature != "solo_generator":
        assert what in str(err.value)


def _selecting_stepper(num_slots=3, num_pages=60, **kw):
    from distkeras_tpu.serving.engine import DecodeStepper

    return DecodeStepper(_keye(), num_slots=num_slots, paged=True,
                         page_size=4, num_pages=num_pages, **kw)


def test_selector_pages_are_reserved_and_released_with_their_k_v_pages():
    """One table and one budget: a page of the table is a page of the
    selector pool too (a third pool a layer of ``num_pages`` pages, four
    8-value keys a row), so what admission reserves and release frees is
    counted once, by the one allocator; exhaustion is typed and holds
    nothing; the bytes a token costs are split by kind."""
    from distkeras_tpu.serving.scheduler import PoolExhaustedError

    st = _selecting_stepper()
    assert st._window_alloc is None and st.window_pages is None
    assert [a.shape for a in st._pools[0]] == [(240, 32), (240, 32), (60, 32)]
    st.begin_admit(0, np.arange(1, 41) % 61, max_new=20)   # 15 pages
    st.begin_admit(1, np.arange(1, 121) % 61, max_new=40)  # 40 pages
    assert st._kv_alloc.pages_in_use == 55
    with pytest.raises(PoolExhaustedError):
        st.begin_admit(2, np.arange(1, 30) % 61, max_new=4)  # 9 > 4 left
    assert st._kv_alloc.pages_in_use == 55 and st._tables[2] == []
    assert 2 not in st._pending
    stats = st.paged_stats()
    assert stats["pages_in_use"] == 55 and "window" not in stats
    assert stats["bytes_per_token_by_kind"] == {
        "full": 2 * (2 * 2 * 16 * 4), "index": 2 * 8 * 4}
    assert stats["bytes_per_token"] == st.kv_bytes_per_token() == 576
    assert stats["attention"].startswith("gather: heads of 16")
    for slot in range(2):
        st.release(slot)
    assert st._kv_alloc.pages_in_use == 0 and st._tables == [[], [], []]
    st.begin_admit(2, np.arange(1, 30) % 61, max_new=4)  # and it admits now
    assert st._kv_alloc.pages_in_use == 9


def test_the_selection_s_counters_are_on_the_collect_span_and_in_stats():
    """``keys_cached`` (the cached positions the active slots' queries could
    see) and ``keys_selected`` (``min(cached, topk)`` a slot) of every decode
    step, from the host's own lengths: on the ``serving/collect`` span and
    summed in ``select_stats``; the iteration's page counters as they are."""
    from distkeras_tpu.serving import engine as engine_mod

    st = _selecting_stepper()
    seen = []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            seen.append((self.name, kw))

    real = engine_mod._span
    engine_mod._span = lambda name, **kw: Span(name)
    try:
        st.admit(0, np.arange(1, 21) % 61, max_new=8)  # 20 positions: > topk
        st.admit(1, np.arange(1, 5) % 61, max_new=8)   # 4: under topk = 8
        for _ in range(3):
            st.step(np.array([True, True, False]))
    finally:
        engine_mod._span = real
    rows = [kw for name, kw in seen
            if name == "serving/collect" and "keys_cached" in kw]
    assert [r["keys_cached"] for r in rows] == [24, 26, 28]
    assert [r["keys_selected"] for r in rows] == [12, 13, 14]
    assert all("experts_hit" in kw for name, kw in seen
               if name == "serving/collect" and "keys_cached" not in kw)
    assert st.select_stats == {"steps": 3, "keys_cached": 78,
                               "keys_selected": 39}
    b = ContinuousBatcher(_selecting_stepper(), queue_capacity=8,
                          prefill_chunk=16)
    req = b.submit(ServeRequest(np.arange(1, 30) % 61, 3))
    for _ in range(8):
        b.step()
        if req.done:
            break
        counts = dict(b._iter_counts)
    assert counts["pages_total"] == 59 and counts["pages_in_use"] == 8
    assert "window_pages_total" not in counts


def test_the_default_prefix_cache_is_switched_off_and_says_so():
    from distkeras_tpu.serving import ServingEngine

    eng = ServingEngine(_laguna(), num_slots=2, paged=True, page_size=4,
                        num_pages=40)  # prefix_cache=True is the default
    assert eng.prefix_store is None
    assert eng.stats()["paged"]["prefix_caches"].startswith(
        "off: grouped page layout")
