"""Zero-bubble decode: the overlap ledger and the overlapped loop.

Three tiers, no device work anywhere:

- ledger arithmetic under a fake clock: the bubble histogram and the
  efficiency gauge are pure functions of the dispatch/ready/collect
  stamps, pinned to hand-computed values;
- loop structure against fake steppers: tokens dispatched by
  iteration N emit at iteration N+1's collect, final outputs are
  identical to the sequential loop, and the trailing flush/idle/stop
  semantics hold with a step still in the air;
- failure containment: a step that raises — at dispatch or deferred
  into the handle's collect — surfaces on the collect of its OWN
  iteration with the sequential loop's blame/quarantine semantics.
"""

from __future__ import annotations

import numpy as np
import pytest

from distkeras_tpu.obs import MetricsRegistry, OverlapLedger
from distkeras_tpu.serving.scheduler import (
    ContinuousBatcher,
    InternalError,
)

from test_serving import FakeStepper, _req


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _ledger():
    reg = MetricsRegistry()
    clock = FakeClock()
    return OverlapLedger(reg, clock=clock), reg, clock


# ------------------------------------------------------- ledger arithmetic


def test_ledger_bubble_and_efficiency_arithmetic():
    led, reg, clock = _ledger()
    assert led.efficiency is None and led.bubble_fraction is None

    # iteration 1: dispatch @0, ready observed @3, collect @5 —
    # device wall 3, iteration wall 5 (no predecessor), bubble 2
    led.note_dispatch()
    clock.t = 3.0
    led.note_ready()
    clock.t = 5.0
    led.note_collect()
    assert led.iterations == 1
    assert led.device_seconds == pytest.approx(3.0)
    assert led.iteration_seconds == pytest.approx(5.0)

    # iteration 2: dispatch @6, never polled ready, collect @9 —
    # device ran up to the collect (device wall 3), iteration wall is
    # collect-to-collect (9 - 5 = 4), bubble 1
    clock.t = 6.0
    led.note_dispatch()
    clock.t = 9.0
    led.note_collect()
    assert led.iterations == 2
    assert led.device_seconds == pytest.approx(6.0)
    assert led.iteration_seconds == pytest.approx(9.0)
    assert led.efficiency == pytest.approx(6.0 / 9.0)
    assert led.bubble_fraction == pytest.approx(1.0 - 6.0 / 9.0)

    hist = next(
        s for s in reg.snapshot()
        if s["name"] == "serving_step_bubble_seconds"
    )
    assert hist["count"] == 2
    assert hist["sum"] == pytest.approx(3.0)  # bubbles 2 + 1

    snap = led.snapshot()
    assert snap["iterations"] == 2
    assert snap["efficiency"] == pytest.approx(2 / 3, abs=1e-4)
    assert snap["bubble_fraction"] == pytest.approx(1 / 3, abs=1e-4)


def test_ledger_gauge_rides_registry_and_gaps_before_first_iteration():
    led, reg, clock = _ledger()
    gauge = next(
        s for s in reg.snapshot()
        if s["name"] == "serving_overlap_efficiency"
    )
    assert gauge["value"] is None  # a gap, not a fake 0 or 1
    led.note_dispatch()
    clock.t = 2.0
    led.note_ready()
    led.note_collect()
    gauge = next(
        s for s in reg.snapshot()
        if s["name"] == "serving_overlap_efficiency"
    )
    assert gauge["value"] == pytest.approx(1.0)  # zero bubble


def test_ledger_first_ready_observation_wins():
    led, _, clock = _ledger()
    led.note_dispatch()
    clock.t = 1.0
    led.note_ready()
    clock.t = 4.0
    led.note_ready()  # later poll must not move the stamp
    clock.t = 4.0
    led.note_collect()
    assert led.device_seconds == pytest.approx(1.0)


def test_ledger_collect_without_dispatch_and_discard_are_noops():
    led, _, clock = _ledger()
    led.note_ready()
    led.note_collect()  # idle scheduler pass
    assert led.iterations == 0
    led.note_dispatch()
    clock.t = 7.0
    led.discard()  # abandoned step (stop with a handle in the air)
    led.note_collect()
    assert led.iterations == 0 and led.efficiency is None


# --------------------------------------------------- overlapped loop shape


class AsyncFakeStepper(FakeStepper):
    """FakeStepper with the ``step_async`` face: the token math runs
    eagerly (host fake), but the result rides a handle that reports
    not-ready for ``delay_polls`` ready() calls and only hands the
    tokens out at collect() — the un-materialized device array shape
    of the real stepper."""

    def __init__(self, *a, delay_polls=1, **kw):
        super().__init__(*a, **kw)
        self.delay_polls = delay_polls
        self.collected = 0

    def step_async(self, active):
        toks = super().step(active)
        stepper = self

        class Handle:
            def __init__(self):
                self.polls = 0

            def ready(self):
                self.polls += 1
                return self.polls > stepper.delay_polls

            def collect(self):
                stepper.collected += 1
                return toks

        return Handle()


def _drain(b, n=50):
    for _ in range(n):
        if b.idle:
            return
        b.step()
    raise AssertionError("batcher did not drain")


def test_overlap_tokens_emit_on_the_next_call_and_match_sequential():
    seq_st = FakeStepper(num_slots=2)
    seq_b = ContinuousBatcher(seq_st)
    seq_reqs = [seq_b.submit(_req(max_new=3)) for _ in range(3)]
    while not seq_b.idle:
        seq_b.step()

    st = AsyncFakeStepper(num_slots=2)
    b = ContinuousBatcher(st, overlap=True)
    assert b.overlap
    reqs = [b.submit(_req(max_new=3)) for _ in range(3)]
    b.step()  # admit + dispatch — tokens still in the air
    assert not any(r.done for r in reqs)
    assert not b.idle  # an in-flight step is live work
    _drain(b)
    assert st.collected > 0  # the async face actually carried them
    for r, sr in zip(reqs, seq_reqs):
        assert r.result().tolist() == sr.result().tolist()
    assert b.counters["tokens_generated"] == 9
    # the ledger closed one entry per collected step
    assert b.overlap_ledger.iterations >= 3
    assert b.stats()["overlap"]["enabled"] is True


def test_overlap_without_step_async_falls_back_and_matches():
    # FakeStepper has no step_async: the device call runs
    # synchronously at dispatch, but the loop shape (emit on the NEXT
    # call) and the final outputs are unchanged
    seq_b = ContinuousBatcher(FakeStepper(num_slots=2))
    seq_reqs = [seq_b.submit(_req(max_new=4)) for _ in range(2)]
    while not seq_b.idle:
        seq_b.step()

    b = ContinuousBatcher(FakeStepper(num_slots=2), overlap=True)
    reqs = [b.submit(_req(max_new=4)) for _ in range(2)]
    b.step()
    assert not any(r.done for r in reqs)
    _drain(b)
    for r, sr in zip(reqs, seq_reqs):
        assert r.result().tolist() == sr.result().tolist()


def test_overlap_streamed_chunk_order_matches_sequential():
    def run(overlap):
        b = ContinuousBatcher(AsyncFakeStepper(num_slots=2),
                              overlap=overlap)
        r = b.submit(_req(max_new=5, stream=True))
        while not b.idle:
            b.step()
        chunks = []
        while True:  # FIFO retains everything; drain to the sentinel
            c = r.next_chunk(timeout=0.1)
            if c is None:
                break
            chunks.append(list(c))
        return chunks, r.result().tolist()

    # stream chunk flattening must equal the final tokens, both modes
    seq_chunks, seq_final = run(False)
    ov_chunks, ov_final = run(True)
    assert ov_final == seq_final
    assert [t for c in ov_chunks for t in c] == [
        t for c in seq_chunks for t in c
    ]


def test_overlap_stop_with_step_in_the_air():
    b = ContinuousBatcher(AsyncFakeStepper(num_slots=1), overlap=True)
    r = b.submit(_req(max_new=5))
    b.step()  # dispatched, uncollected
    assert not b.idle
    b.stop()
    assert b.idle  # the handle was dropped with the requests
    assert r.done
    with pytest.raises(Exception):
        r.result()


# ----------------------------------------------------- failure containment


def test_dispatch_raise_surfaces_at_its_own_collect():
    class BoomStepper(FakeStepper):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.booms = 0

        def step(self, active):
            self.booms += 1
            raise RuntimeError("injected step crash")

    st = BoomStepper(num_slots=1)
    b = ContinuousBatcher(st, overlap=True, quarantine_steps=2)
    r = b.submit(_req(max_new=4))
    b.step()  # dispatch: the failure is stashed on the handle
    assert not r.done  # not surfaced early
    assert b.counters["step_failures"] == 0
    b.step()  # collect of its own iteration: blame by elimination
    assert r.done
    with pytest.raises(InternalError, match="blamed"):
        r.result()
    assert b.counters["step_failures"] == 1
    assert b.counters["quarantines"] == 1


def test_deferred_collect_raise_surfaces_at_its_own_collect():
    class DeferredBoomStepper(AsyncFakeStepper):
        def step_async(self, active):
            class Handle:
                @staticmethod
                def ready():
                    return True

                @staticmethod
                def collect():
                    raise RuntimeError("deferred device failure")

            return Handle()

    b = ContinuousBatcher(DeferredBoomStepper(num_slots=1),
                          overlap=True, quarantine_steps=2)
    r = b.submit(_req(max_new=4))
    b.step()
    assert not r.done
    b.step()
    assert r.done
    with pytest.raises(InternalError, match="blamed"):
        r.result()
    assert b.counters["step_failures"] == 1


def test_overlap_blame_isolates_poison_slot_among_survivors():
    class PoisonStepper(AsyncFakeStepper):
        """Any batch containing the poison slot fails; probes that
        mask it out succeed — the bisection must isolate it."""

        poison = 1

        def step(self, active):
            if np.asarray(active, bool)[self.poison]:
                raise RuntimeError("poison slot in batch")
            return super().step(active)

        def step_async(self, active):
            # fail at the HANDLE, after a successful dispatch
            toks_or_exc = None
            try:
                toks_or_exc = self.step(active)
            except RuntimeError as e:
                toks_or_exc = e

            class Handle:
                @staticmethod
                def ready():
                    return True

                @staticmethod
                def collect():
                    if isinstance(toks_or_exc, Exception):
                        raise toks_or_exc
                    return toks_or_exc

            return Handle()

    st = PoisonStepper(num_slots=2)
    b = ContinuousBatcher(st, overlap=True, quarantine_steps=100)
    good = b.submit(_req(max_new=2))
    bad = b.submit(_req(plen=4, max_new=2))  # admitted second -> slot 1
    _drain(b)
    with pytest.raises(InternalError, match="blamed"):
        bad.result()
    # the survivor decoded to completion, token-identical to solo
    assert good.result().tolist() == [1, 2, 3, 1001, 1002]
    assert b.counters["step_failures"] >= 1
    assert b.counters["blame_probes"] >= 1


def test_sequential_mode_is_unchanged_one_call_emits():
    st = FakeStepper(num_slots=1)
    b = ContinuousBatcher(st)  # overlap defaults False on the raw batcher
    assert not b.overlap
    r = b.submit(_req(max_new=1))
    b.step()
    assert r.done  # same-call emission, the pre-overlap contract
    assert r.result().tolist() == [1, 2, 3, 1001]
    # the sequential control stamps the same ledger
    assert b.overlap_ledger.iterations == 1


# ------------------------------------------------- two steps in the air


class PositionStepper:
    """A fake with the real stepper's discipline, so that a wrong
    look-ahead shows in the tokens: slot ``i`` at host length ``L``
    emits ``base + 100 * i + L`` (a pure function of the position, as
    a greedy decode's token is of its context), host lengths and sample
    positions advance only at ``collect()``, in dispatch order, a step
    dispatched with others in the air passes lengths as they WILL be,
    and a released slot owes the steps in the air nothing. Every
    dispatch / collect / discard is logged with its step number."""

    def __init__(self, num_slots=2, max_len=64, base=1000, poison=None):
        self.num_slots, self.max_len, self.base = num_slots, max_len, base
        self.poison = poison  # a step with this slot fails at collect
        self.lens = np.ones(num_slots, int)
        self.spos = np.zeros(num_slots, int)
        self.tenancy = np.zeros(num_slots, int)
        self.air = []
        self.log = []  # (event, step number)
        self.seen = []  # (lens, spos) each dispatch passed, by step
        self.admitted = []
        self.constrained_slots = set()

    def begin_admit(self, slot, prompt, **kw):
        self.admitted.append(slot)
        self.lens[slot] = len(np.asarray(prompt))
        self.spos[slot] = 0
        return 0

    def release(self, slot):
        self.tenancy[slot] += 1
        self.lens[slot] = 1
        self.spos[slot] = 0

    def step(self, active):
        return self.step_async(active).collect()

    def step_async(self, active):
        st = self
        active = np.asarray(active, bool)
        owed = sum((h.owed().astype(int) for h in self.air),
                   np.zeros(self.num_slots, int))
        lens = self.lens + owed
        number = len(self.seen)
        self.seen.append((lens.copy(), self.spos + owed))
        toks = np.where(
            active, self.base + 100 * np.arange(self.num_slots) + lens, -1
        )
        boom = self.poison is not None and active[self.poison]

        class Handle:
            tenancy = self.tenancy.copy()

            def ready(self):
                return True

            def owed(self):
                return active & (self.tenancy == st.tenancy)

            def discard(self):
                st.log.append(("discard", number))
                st.air.remove(self)

            def collect(self):
                st.log.append(("collect", number))
                st.air.remove(self)
                if boom:
                    raise RuntimeError("poison slot in batch")
                owed = self.owed()
                st.lens[owed] += 1
                st.spos[owed] += 1
                return toks

        self.log.append(("dispatch", number))
        handle = Handle()
        self.air.append(handle)
        return handle


def _run(b, reqs=(), n=200):
    """Drive the batcher until it is idle; each request's chunks."""
    for _ in range(n):
        if b.idle:
            break
        b.step()
    assert b.idle
    out = []
    for r in reqs:
        chunks = []
        while r.stream:
            c = r.next_chunk(timeout=0.1)
            if c is None:
                break
            chunks.append(list(c))
        out.append(chunks)
    return out


def test_lookahead_dispatches_step_k_before_it_collects_step_k_minus_1():
    st = PositionStepper(num_slots=2)
    b = ContinuousBatcher(st, overlap=True)
    reqs = [b.submit(_req(max_new=4)) for _ in range(2)]
    _run(b)
    assert st.log == [
        ("dispatch", 0),
        ("dispatch", 1), ("collect", 0),
        ("dispatch", 2), ("collect", 1),
        # step 2's token leaves one of the budget: step 3 is the last,
        # and the look-ahead mask behind it is empty
        ("dispatch", 3), ("collect", 2),
        ("collect", 3),
    ]
    # lengths and sample positions as they WILL be: one more a step
    # although the host's advanced one collect later
    assert [tuple(l) for l, _ in st.seen] == [(3, 3), (4, 4), (5, 5), (6, 6)]
    assert [tuple(p) for _, p in st.seen] == [(0, 0), (1, 1), (2, 2), (3, 3)]
    for i, r in enumerate(reqs):
        assert r.result().tolist() == [1, 2, 3] + [
            1000 + 100 * i + n for n in (3, 4, 5, 6)
        ]
    ov = b.stats()["overlap"]
    assert ov["steps"] == 4 and ov["ahead_steps"] == 3
    assert ov["drained"] == {} and ov["discarded_slot_steps"] == 0
    assert not st.air


def _preemptible():
    from distkeras_tpu.serving.qos import QosPolicy

    class Swappable(PositionStepper):
        def swap_out(self, slot):
            return {"len": int(self.lens[slot]), "spos": int(self.spos[slot])}

        def swap_in(self, slot, state, max_new=None):
            self.lens[slot], self.spos[slot] = state["len"], state["spos"]

    st = Swappable(num_slots=1)
    b = ContinuousBatcher(
        st, overlap=True, qos=QosPolicy(preempt=True, max_preemptions=1),
    )
    lo = b.submit(_req(max_new=6, tenant="a", priority=0))
    b.step()
    b.step()
    hi = _req(max_new=2, tenant="b", priority=2)
    return st, b, [lo], hi


def _constrained_by_stepper():
    st = PositionStepper(num_slots=1)
    st.constrained_slots = {0}
    return st, ContinuousBatcher(st, overlap=True), [], _req(max_new=4)


def _wants_sequences():
    st = PositionStepper(num_slots=1)
    st.wants_sequences = True
    return st, ContinuousBatcher(st, overlap=True), [], _req(max_new=4)


@pytest.mark.parametrize("reason,build", [
    ("wants_sequences", _wants_sequences),
    ("grammar", _constrained_by_stepper),
    ("preempt", _preemptible),
])
def test_each_fallback_reason_runs_one_step_deep_and_is_counted(
    reason, build
):
    st, b, before, req = build()
    b.submit(req)
    _run(b)
    assert req.done and all(r.done for r in before)
    assert b.stats()["overlap"]["drained"].get(reason, 0) >= 1
    if reason == "preempt":
        # only the call that preempts drains; the step after the
        # swap-out is dispatched with nothing in the air
        assert b.counters["preemptions"] == b.counters["resumes"] == 1
        first = st.log.index(("collect", 1))
        assert st.log[first + 1] == ("dispatch", 2)
    else:
        # every call collects before it dispatches
        events = [e for e, _ in st.log]
        assert events == ["dispatch", "collect"] * (len(events) // 2)
        assert b.stats()["overlap"]["ahead_steps"] == 0
    # a stream is its positions in order, whatever the depth
    assert req.result().tolist()[3:] == [
        1000 + n for n in range(3, 3 + req.max_new_tokens)
    ]
    for r in before:
        assert r.result().tolist()[3:] == [1000 + n for n in range(3, 9)]
    assert not st.air


@pytest.mark.parametrize("stepper", [FakeStepper, "speculative"])
def test_sync_steppers_never_look_ahead(stepper):
    from test_serving import FakeSpecStepper

    st = (
        FakeSpecStepper(num_slots=1) if stepper == "speculative"
        else stepper(num_slots=1)
    )
    b = ContinuousBatcher(st, overlap=True)
    r = b.submit(_req(max_new=6))
    _run(b)
    assert r.done
    ov = b.stats()["overlap"]
    assert ov["ahead_steps"] == 0
    # every call that found a step in the air collected it first
    assert ov["drained"] == {"sync_stepper": ov["steps"]}


def _finish_kinds(overlap):
    """Four streams on three slots: one ends by its budget, one by EOS
    (and a queued request takes its slot while the discarded step is
    in the air), one by its deadline; the late one by its budget."""
    st = PositionStepper(num_slots=3)
    b = ContinuousBatcher(st, overlap=overlap)
    budget = b.submit(_req(max_new=5, stream=True))
    eos = b.submit(_req(max_new=9, eos_id=1100 + 5, stream=True))
    dead = b.submit(_req(max_new=9, stream=True))
    late = b.submit(_req(plen=5, max_new=3, stream=True))
    for _ in range(50):
        if len(dead.tokens) >= 4:
            break
        b.step()
    dead.deadline = 0.0  # expires at its next emission
    reqs = [budget, eos, dead, late]
    return st, b, reqs, _run(b, reqs)


def test_streams_equal_the_sequential_controls_over_every_finish():
    _, seq_b, seq_reqs, seq_chunks = _finish_kinds(False)
    st, b, reqs, chunks = _finish_kinds(True)
    assert chunks == seq_chunks  # chunk for chunk
    for r, sr in zip(reqs, seq_reqs):
        assert r.tokens == sr.tokens  # token for token
        assert type(r.error) is type(sr.error)
    budget, eos, dead, late = reqs
    assert budget.tokens == [1003, 1004, 1005, 1006, 1007]
    assert eos.tokens == [1103, 1104, 1105] and eos.error is None
    assert dead.tokens == [1203, 1204, 1205, 1206, 1207]
    assert late.tokens == [1105, 1106, 1107]  # slot 1 again, from ITS length
    ov, seq_ov = b.stats()["overlap"], seq_b.stats()["overlap"]
    # the EOS and the deadline each cost one slot-step; the budget none
    assert ov["discarded_slot_steps"] == 2
    assert seq_ov["discarded_slot_steps"] == seq_ov["ahead_steps"] == 0
    assert ov["ahead_steps"] >= ov["steps"] - 2 and not ov["drained"]
    assert b.counters["tokens_generated"] == 16
    assert b.counters["occupancy_sum"] == seq_b.counters["occupancy_sum"]


def test_new_tenant_admitted_under_the_old_tenants_discarded_step():
    st = PositionStepper(num_slots=1)
    b = ContinuousBatcher(st, overlap=True)
    old = b.submit(_req(max_new=9, eos_id=1004))
    new = b.submit(_req(plen=6, max_new=3))
    _run(b)
    assert old.result().tolist() == [1, 2, 3, 1003, 1004]
    # step 2 held the old tenant at length 5 and was in the air when
    # the new tenant took the slot: dispatched behind it, step 3 passed
    # the NEW tenant's own length and sample position, and step 2's
    # collect left them alone
    assert st.log[:7] == [
        ("dispatch", 0), ("dispatch", 1), ("collect", 0),
        ("dispatch", 2), ("collect", 1), ("dispatch", 3), ("collect", 2),
    ]
    assert st.seen[2][0][0] == 5 and st.seen[3] == ([6], [0])
    assert new.result().tolist()[6:] == [1006, 1007, 1008]
    assert b.stats()["overlap"]["discarded_slot_steps"] == 1


def test_stop_with_a_lookahead_engaged_drops_the_handle():
    st = PositionStepper(num_slots=1)
    b = ContinuousBatcher(st, overlap=True)
    r = b.submit(_req(max_new=9))
    b.step()
    b.step()  # step 1 dispatched behind step 0, step 0 collected
    assert st.log[-2:] == [("dispatch", 1), ("collect", 0)]
    b.stop()  # the request is cancelled with step 1 in the air
    assert st.log[-1] == ("discard", 1) and not st.air and b.idle
    assert r.done and r.tokens == [1003]
    with pytest.raises(Exception):
        r.result()


def test_collect_raises_with_two_in_the_air():
    st = PositionStepper(num_slots=3, poison=2)
    b = ContinuousBatcher(st, overlap=True, quarantine_steps=100)
    good = [b.submit(_req(max_new=6)) for _ in range(2)]  # slots 0, 1
    b.step()  # step 0 in the air
    b.step()
    bad = b.submit(_req(plen=4, max_new=4))  # -> slot 2
    b.step()  # admits the poison; step 2 holds it, step 1 collected
    before = st.lens.copy(), st.spos.copy()
    n_log = len(st.log)
    b.step()  # step 3 behind step 2; step 2's collect raises
    events = st.log[n_log:]
    # the later step is dropped un-collected BEFORE the probes run
    assert events[:3] == [("dispatch", 3), ("collect", 2), ("discard", 3)]
    # the probes passed the lengths and sample positions as they were
    # when step 2 was dispatched: nothing of either step had advanced
    probe = events[3][1]
    assert st.seen[probe][0].tolist() == before[0].tolist()
    assert st.seen[probe][1].tolist() == before[1].tolist()
    with pytest.raises(InternalError, match="blamed"):
        bad.result()
    ov = b.stats()["overlap"]
    assert b.counters["step_failures"] == 1
    assert b.counters["blame_probes"] >= 1
    # the survivors advanced exactly one position in the failed call
    assert st.lens.tolist() == [before[0][0] + 1, before[0][1] + 1, 1]
    # the call after the failure runs one step deep, then it looks
    # ahead again
    n_log = len(st.log)
    b.step()
    assert [e for e, _ in st.log[n_log:]] == ["collect", "dispatch"]
    assert b.stats()["overlap"]["drained"] == {"failed_step": 1}
    _run(b)
    for slot, r in enumerate(good):
        assert r.result().tolist()[3:] == [
            1000 + 100 * slot + n for n in (3, 4, 5, 6, 7, 8)
        ]
    assert b.stats()["overlap"]["ahead_steps"] > ov["ahead_steps"]
    assert not st.air


def test_dispatch_raise_with_a_step_in_the_air_rides_its_own_record():
    class DispatchBoom(PositionStepper):
        boom_at = 2

        def step_async(self, active):
            if len(self.seen) == self.boom_at and self.air:
                self.boom_at = None
                raise RuntimeError("injected dispatch crash")
            return super().step_async(active)

    st = DispatchBoom(num_slots=2)
    b = ContinuousBatcher(st, overlap=True, quarantine_steps=100)
    a = b.submit(_req(max_new=5))
    c = b.submit(_req(max_new=5))
    b.step()
    b.step()
    b.step()  # step 2's dispatch raises behind step 1; step 1 collected
    assert b.counters["step_failures"] == 0 and len(a.tokens) == 2
    b.step()  # its own collect: the probes run one step deep
    assert b.counters["step_failures"] == 1
    assert b.stats()["overlap"]["drained"] == {"failed_step": 1}
    _run(b)
    # the newest admission is the prime suspect of a failure that names
    # no slot; the other stream is whole
    assert a.result().tolist()[3:] == [1003, 1004, 1005, 1006, 1007]
    with pytest.raises(InternalError, match="blamed"):
        c.result()


def test_ledger_with_two_open_stamps():
    led, reg, clock = _ledger()
    # step 0: handed over @1 (its call began @0: host time, not the
    # device's), ready @4
    clock.t = 1.0
    led.note_dispatch()
    # step 1's call runs while step 0 does: handed over @3
    clock.t = 3.0
    led.note_dispatch()
    clock.t = 4.0
    led.note_ready()  # the OLDEST open step's
    clock.t = 4.5
    led.note_collect()  # closes step 0: device 4 - 1, wall 4.5 - 1
    assert led.iterations == 1
    assert led.device_seconds == pytest.approx(3.0)
    assert led.iteration_seconds == pytest.approx(3.5)
    # step 1 started when the device ended step 0 (@4), not at its
    # stamp (@3); never polled, collected @7: device 7 - 4, wall
    # collect-to-collect 7 - 4.5
    clock.t = 7.0
    led.note_collect()
    assert led.iterations == 2
    assert led.device_seconds == pytest.approx(3.0 + 2.5)  # clipped to wall
    assert led.iteration_seconds == pytest.approx(3.5 + 2.5)
    # two open, the older one's collect raises: close it, drop the other
    clock.t = 8.0
    led.note_dispatch()
    clock.t = 8.5
    led.note_dispatch()
    clock.t = 9.0
    led.note_collect()
    led.discard()
    assert led.iterations == 3
    led.note_collect()  # nothing open: no-op
    assert led.iterations == 3
    assert led.device_seconds == pytest.approx(5.5 + 1.0)
    assert led.iteration_seconds == pytest.approx(6.0 + 2.0)
