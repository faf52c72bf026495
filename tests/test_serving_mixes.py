"""Whole request mixes through one engine, each request held to its solo
tokens.

The tests of ``test_serving.py``, ``test_paged_serving.py``,
``test_sampling.py``, ``test_qos.py`` and ``test_overlap.py`` pin one
admission path at a time with a prompt made for it. These drive the seeded
mixes of ``serving_mixes.py`` through a started engine at a tiny size (one
layer, 16 wide, 32 positions, two slots), where chunked prefill, the prefix
store, the page pool, sampling, pre-emption and the overlapped loop meet in
one run: every request still comes back as its solo decode, the stores and
pools account for what they did, and nothing is compiled once the engine has
been warmed. No case reads a clock or compares speeds.
"""

from __future__ import annotations

import numpy as np
import pytest

import serving_mixes as mixes
from serving_mixes import (
    CHUNK, PAGE, POOL_PAGES, SLOTS, assert_all_equal, drive, drive_trace,
    engine, requests_of, solo_refs,
)

PAGED = dict(paged=True, page_size=PAGE, num_pages=POOL_PAGES)


@pytest.fixture(scope="module")
def lm():
    return mixes.tiny_lm()


@pytest.fixture(scope="module")
def ref_gen(lm):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    return CachedSequenceGenerator(lm)


@pytest.fixture(scope="module")
def three_mixes(ref_gen):
    """``{name: (requests, header requests, the requests' references)}``."""
    return {name: (reqs, prime, solo_refs(ref_gen, reqs))
            for name, (reqs, prime) in mixes.the_three_mixes().items()}


@pytest.fixture(scope="module")
def production(three_mixes):
    reqs, _, refs = three_mixes["production_mix"]
    return reqs, refs


# ------------------------------------- chunked prefill, stores and pools


@pytest.mark.parametrize("bank", ["dense", "paged"])
@pytest.mark.parametrize(
    "mix", ["production_mix", "mixed_long", "prefix_heavy"])
def test_a_mix_comes_back_as_solo_tokens_with_and_without_the_store(
        lm, three_mixes, mix, bank):
    """Chunked prefill under staggered arrivals, on the dense bank and on a
    paged pool of the same bytes with twice the slots. Without a prefix
    store the engine reports none; with one, the headers (seen twice, as
    two-touch admission asks) are served from it where the mix shares them.
    The pool never refuses, shares the header's pages, and holds nothing
    once the requests are done and its index is cleared."""
    reqs, prime, refs = three_mixes[mix]
    kw = dict(PAGED, slots=2 * SLOTS) if bank == "paged" else {}
    for store in (False, True):
        eng = engine(lm, prefix_cache=store, **kw)
        try:
            for _ in range(2):
                drive(eng, prime)
            assert_all_equal(drive(eng, reqs), refs, f"{mix}/{bank}/{store}")
            stats = eng.stats()
            assert stats["internal_errors"] == 0
            assert stats["prefill_chunk"] == CHUNK
            if not store:
                assert stats["prefix_cache"] == {"enabled": False}
            elif mix == "prefix_heavy":
                assert stats["prefix_cache"]["hits"] > 0
            elif mix == "mixed_long":
                assert stats["prefix_cache"]["hits"] == 0
            if bank == "paged":
                pool = stats["paged"]
                assert pool["total_pages"] == POOL_PAGES - 1
                assert pool["exhaustions"] == 0
                if mix == "prefix_heavy":
                    assert pool["device_prefix"]["hits"] > 0
                st = eng._stepper
                st.prefix_index.clear()
                assert st._kv_alloc.pages_in_use == 0
                assert st._kv_alloc.free_pages == POOL_PAGES - 1
        finally:
            eng.stop()


def test_the_pool_admits_more_slots_than_the_bank_holds_at_equal_bytes(lm):
    """A dense bank charges every slot the whole sequence; the pool of the
    same bytes (and its sentinel page) charges a short request one page, so
    it holds twice the slots at once."""
    from distkeras_tpu.serving.engine import DecodeStepper

    dense = DecodeStepper(lm, num_slots=SLOTS)
    pool = DecodeStepper(lm, num_slots=2 * SLOTS, prefix_cache=None, **PAGED)
    assert (pool.kv_bytes_total() * (POOL_PAGES - 1)
            == dense.kv_bytes_total() * POOL_PAGES)
    rng = np.random.default_rng(3)
    for slot in range(pool.num_slots):
        pool.admit(slot, rng.integers(0, mixes.VOCAB, 4).astype(np.int32),
                   max_new=4)
    assert pool.num_slots > dense.num_slots
    assert pool._kv_alloc.pages_in_use == pool.num_slots
    pool.step(np.ones(pool.num_slots, bool))


# --------------------------------------- recorder, history, the ledger


def test_the_flight_recorder_changes_no_token_and_tapes_the_scheduler(
        lm, production):
    reqs, refs = production
    kinds = set()
    for recorder in (False, True):
        eng = engine(lm, prefix_cache=True, flight_recorder=recorder)
        try:
            assert_all_equal(drive(eng, reqs), refs, f"recorder={recorder}")
            if recorder:
                assert eng.recorder.events_recorded > 0
                kinds = {e["kind"] for e in eng.recorder.snapshot()}
        finally:
            eng.stop()
    assert "scheduler.iteration" in kinds


def test_the_metrics_history_changes_no_token_and_answers_over_the_run(
        lm, production):
    """With the time-series ring on (at a cadence short enough for this
    run) the tokens are those without it, and the ring then answers: two
    snapshots or more, the completed counter moving, the burn verdict ok."""
    from distkeras_tpu.obs import default_serving_slos

    reqs, refs = production
    off = engine(lm, prefix_cache=True, history=False)
    on = engine(
        lm, prefix_cache=True, history=True, history_interval=0.05,
        slos=default_serving_slos(
            latency_p99_s=600.0, error_rate=0.5, min_count=1))
    try:
        for eng in (off, on):
            for _ in range(2):
                assert_all_equal(drive(eng, reqs), refs, "history")
        ts = on.timeseries(window=60.0)
    finally:
        off.stop()
        on.stop()
    assert ts["snapshots"] >= 2
    assert len(ts["series"]) > 10
    completed = [r for r in ts["series"]
                 if r["name"] == "serving_scheduler_completed"]
    assert completed and (completed[0]["rate"] or 0) > 0
    assert ts["burn"]["burn"] == "ok"


def test_a_warmed_engine_builds_no_program_inside_a_pass(lm, production):
    reqs, refs = production
    eng = engine(lm, prefix_cache=True)
    try:
        for _ in range(2):
            drive(eng, reqs)
        mixes.warm(eng)
        built = eng.compile_ledger.total
        for _ in range(2):
            assert_all_equal(drive(eng, reqs), refs, "after warm-up")
        assert eng.compile_ledger.total == built
        assert eng.compile_ledger.storms == 0
        assert eng.stats()["compiles"]["storms"] == 0
    finally:
        eng.stop()


# -------------------------------------------------------------- sampling


def _sampled(n):
    from distkeras_tpu.serving import SamplingParams

    return [SamplingParams(temperature=0.7, top_p=0.9, seed=1000 + i)
            for i in range(n)]


def test_greedy_requests_keep_their_solo_tokens_beside_sampled_passes(
        lm, production):
    """One engine serves the mix sampled, then greedy, then sampled again:
    the greedy pass is the solo reference whatever ran before it."""
    reqs, refs = production
    eng = engine(lm, prefix_cache=True)
    try:
        drive(eng, reqs, sampling=_sampled(len(reqs)))
        assert_all_equal(drive(eng, reqs), refs, "greedy after sampled")
        drive(eng, reqs, sampling=_sampled(len(reqs)))
        assert_all_equal(drive(eng, reqs), refs, "greedy, again")
    finally:
        eng.stop()


def test_a_sampled_mix_repeats_itself_under_its_seeds(lm, production):
    """Position-keyed draws: the same seeds give the same tokens on a
    second pass, on another engine, and whatever the neighbours are."""
    reqs, refs = production
    sampling = _sampled(len(reqs))
    passes = []
    for _ in range(2):
        eng = engine(lm, prefix_cache=True)
        try:
            passes.append(drive(eng, reqs, sampling=sampling))
            passes.append(drive(eng, reqs, sampling=sampling))
        finally:
            eng.stop()
    for again in passes[1:]:
        assert_all_equal(again, passes[0], "sampled replay")
    for (p, _), out in zip(reqs, passes[0]):
        assert np.array_equal(out[: p.size], p)
    assert any(not np.array_equal(a, r) for a, r in zip(passes[0], refs))


def test_four_completions_by_fork_are_four_derived_seed_admissions(
        lm, production):
    """``n=4`` prefills once and forks the slot three times; the four
    completions are those of four separate admissions under the seeds
    ``seed_for_completion`` derives."""
    from distkeras_tpu.serving import SamplingParams
    from distkeras_tpu.serving.sampling import seed_for_completion

    n = 4
    base = production[0][: max(2, len(production[0]) // 3)]
    forks = [SamplingParams(temperature=0.8, seed=500 + i, n=n)
             for i in range(len(base))]
    singles = [(p, s) for p, s in base for _ in range(n)]
    seeds = [
        SamplingParams(temperature=0.8, seed=seed_for_completion(500 + i, j))
        for i in range(len(base)) for j in range(n)]
    outs = {}
    for side, reqs, sampling in (("fork", base, forks),
                                 ("single", singles, seeds)):
        eng = engine(lm, slots=max(SLOTS, n), paged=True)
        try:
            outs[side] = drive(eng, reqs, sampling=sampling)
            if side == "fork":
                assert eng.batcher.forked_slots.value >= (n - 1) * len(base)
                assert eng.stats()["forked_slots"] >= (n - 1) * len(base)
        finally:
            eng.stop()
    for i in range(len(base)):
        assert len(outs["fork"][i]) == n
        for j in range(n):
            assert np.array_equal(
                outs["fork"][i][j], outs["single"][i * n + j]), (i, j)


# ------------------------------------------------------------------- QoS


_QOS_TRACES = {
    "two_tenant_burst": lambda: mixes.two_tenant_burst(4 * mixes.REQUESTS, 0),
    "swap_thrash": lambda: mixes.swap_thrash(3 * mixes.REQUESTS, 1),
}


@pytest.mark.parametrize("side", ["fifo", "qos"])
@pytest.mark.parametrize("scenario", sorted(_QOS_TRACES))
def test_two_tenants_on_one_pool_each_get_their_solo_tokens(
        lm, ref_gen, scenario, side):
    """First come first served, and priorities with pre-emption by page
    swap, on the same pool: every request its solo tokens (on the QoS side
    across being swapped out and in), every pre-emption paired with a
    resumption or a counted failure, and the trace's summary names its
    tenants."""
    from distkeras_tpu.serving import QosPolicy

    trace = _QOS_TRACES[scenario]()
    tenants = set(mixes.loadgen.summarize(trace)["tenants"])
    assert tenants == ({"batch", "interactive"}
                       if scenario == "two_tenant_burst" else {"lo", "hi"})
    refs = solo_refs(ref_gen, requests_of(trace))
    policy = QosPolicy(preempt=True, max_preemptions=2)
    eng = engine(lm, slots=2 * SLOTS, **PAGED,
                 qos=policy if side == "qos" else None)
    try:
        # when a request is swapped out hangs on how the threads fall: a
        # pass or two see it, and a few more are allowed
        for passes in range(1, 7):
            assert_all_equal(drive_trace(eng, trace), refs,
                             f"{scenario}/{side}")
            stats = eng.stats()
            if passes >= 2 and (side == "fifo" or stats["preemptions"]):
                break
    finally:
        eng.stop()
    assert stats["internal_errors"] == 0
    assert stats["completed"] == passes * len(trace)
    if side == "qos":
        assert stats["preemptions"] >= 1
        assert stats["preemptions"] == (
            stats["resumes"] + stats["swap_in_failures"]
            + stats["swapped_failed"])
        assert stats["swap_in_failures"] == stats["swapped_failed"] == 0
    else:
        assert stats.get("preemptions", 0) == 0


# ------------------------------------------------- the overlapped loop


def _decode_heavy(ref_gen):
    trace = mixes.loadgen.make_trace(
        process="poisson", rate=max(50.0, 12000.0 / mixes.SEQ),
        n=3 * mixes.REQUESTS, vocab=mixes.VOCAB, seed=11,
        tenants=mixes.loadgen.decode_heavy_tenants(mixes.SEQ))
    assert any(ev.get("stream") for ev in trace)
    return (dict(), lambda eng: drive_trace(eng, trace, stream=True),
            solo_refs(ref_gen, requests_of(trace)))


def _short_uniform(ref_gen):
    reqs = mixes.short_uniform(np.random.default_rng(170))
    return dict(), lambda eng: drive(eng, reqs), solo_refs(ref_gen, reqs)


def _sampled_long(ref_gen):
    reqs = mixes.mixed_long(np.random.default_rng(171))
    from distkeras_tpu.serving import SamplingParams

    sampling = [SamplingParams(temperature=0.7, top_p=0.9, seed=2000 + i)
                for i in range(len(reqs))]
    return dict(), lambda eng: drive(eng, reqs, sampling=sampling), None


def _preempt(ref_gen):
    from distkeras_tpu.serving import QosPolicy

    trace = mixes.two_tenant_burst(3 * mixes.REQUESTS, 13)
    kw = dict(PAGED, slots=2 * SLOTS,
              qos=QosPolicy(preempt=True, max_preemptions=2))
    return (kw, lambda eng: drive_trace(eng, trace),
            solo_refs(ref_gen, requests_of(trace)))


@pytest.mark.parametrize(
    "row", [_decode_heavy, _short_uniform, _sampled_long, _preempt],
    ids=["decode_heavy", "short_uniform", "sampled", "preempt"])
def test_the_overlapped_loop_gives_the_sequential_loop_s_tokens(
        lm, ref_gen, row):
    """The loop that dispatches a step before it collects the last, and the
    sequential one: the same tokens as each other, as the solo reference
    where the row is greedy, and as themselves on a second pass (the
    sampled row's seeded replay; the pre-empting row across the swap).
    Streamed chunks come in order, and a warmed engine builds nothing."""
    kw, run, refs = row(ref_gen)
    outs = {}
    for overlap in (False, True):
        eng = engine(lm, overlap=overlap, **kw)
        try:
            run(eng)
            run(eng)
            mixes.warm(eng, restore="qos" in kw)
            built = eng.compile_ledger.total
            first, second = run(eng), run(eng)
            assert eng.compile_ledger.total == built, overlap
            assert eng.compile_ledger.storms == 0
            stats = eng.stats()
        finally:
            eng.stop()
        assert_all_equal(second, first, f"replay, overlap={overlap}")
        if refs is not None:
            assert_all_equal(first, refs, f"overlap={overlap}")
        if "qos" in kw:
            assert stats["preemptions"] >= 1
            assert stats["preemptions"] == (
                stats["resumes"] + stats["swap_in_failures"]
                + stats["swapped_failed"])
        outs[overlap] = first
    assert_all_equal(outs[True], outs[False], "overlapped against sequential")
