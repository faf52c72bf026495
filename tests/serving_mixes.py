"""The tiny model, the seeded request mixes and the solo references that the
serving-mix tests share (``test_serving_mixes.py`` and its siblings).

Every mix is a list of ``(prompt, steps)`` drawn from one seeded generator, a
dozen lines each. A request's reference is its solo greedy decode by
``CachedSequenceGenerator``: whatever the engine does around a request
(chunks its prefill, serves its prefix from a store, pages its cache, swaps
it out and in, hands it to another replica), its tokens are those. Nothing
here reads a clock: ``drive`` and ``drive_trace`` pace their submissions by
sleeping the mix's own gaps, and no case compares two speeds.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import loadgen  # noqa: E402  (tools/loadgen.py, the seeded trace generator)

SEQ, D_MODEL, DEPTH, HEADS, VOCAB = 32, 16, 1, 2, 61
SLOTS, REQUESTS, CHUNK, PAGE = 2, 6, 8, 16
#: pages of a paged pool that holds what a dense bank of ``SLOTS`` slots
#: holds, and the sentinel page beside them
POOL_PAGES = SLOTS * -(-SEQ // PAGE) + 1
TIMEOUT = 120.0


def tiny_lm():
    from distkeras_tpu.models.zoo import transformer_lm

    return transformer_lm(
        vocab_size=VOCAB, seq_len=SEQ, d_model=D_MODEL, num_heads=HEADS,
        depth=DEPTH, seed=0,
    )


# ------------------------------------------------------------- the mixes


def _steps(rng, prompt_len):
    steps = int(rng.integers(max(2, SEQ // 8), max(3, SEQ // 4)))
    return max(1, min(steps, SEQ - prompt_len))


def mixed_long(rng, n=REQUESTS):
    """Prompts of 1 to 3/4 of the sequence: no two share a prefix."""
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(1, max(2, 3 * SEQ // 4)))
        steps = _steps(rng, plen)
        reqs.append((rng.integers(0, VOCAB, plen).astype(np.int32), steps))
    return reqs


def prefix_heavy(rng, header, n=REQUESTS):
    """Every prompt is the shared ``header`` and 1 to 4 fresh tokens."""
    reqs = []
    for _ in range(n):
        sfx = rng.integers(0, VOCAB, int(rng.integers(1, 5)))
        prompt = np.concatenate([header, sfx]).astype(np.int32)
        reqs.append((prompt, _steps(rng, prompt.size)))
    return reqs


def production_mix(rng, headers, n=REQUESTS):
    """Two of three requests extend one of the shared headers, the third
    is novel and long."""
    reqs = []
    for i in range(n):
        if i % 3 < 2:
            sfx = rng.integers(
                0, VOCAB, int(rng.integers(1, max(2, SEQ // 8))))
            prompt = np.concatenate(
                [headers[i % len(headers)], sfx]).astype(np.int32)
        else:
            plen = int(rng.integers(1, max(2, 3 * SEQ // 4)))
            prompt = rng.integers(0, VOCAB, plen).astype(np.int32)
        reqs.append((prompt, _steps(rng, prompt.size)))
    return reqs


def short_uniform(rng, n=REQUESTS):
    plen = steps = max(2, SEQ // 8)
    return [(rng.integers(0, VOCAB, plen).astype(np.int32), steps)
            for _ in range(n)]


def zero_reuse(rng, n=REQUESTS):
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(8, max(9, SEQ // 2)))
        reqs.append(
            (rng.integers(0, VOCAB, plen).astype(np.int32), _steps(rng, plen)))
    return reqs


def the_three_mixes(seed=0):
    """``{name: (requests, requests that put the headers in a store)}``."""
    rng = np.random.default_rng(seed)
    header = rng.integers(0, VOCAB, SEQ // 2).astype(np.int32)
    headers = [header, rng.integers(0, VOCAB, SEQ // 4).astype(np.int32)]
    return {
        "production_mix": (
            production_mix(rng, headers),
            [prefix_heavy(rng, h, 1)[0] for h in headers]),
        "mixed_long": (mixed_long(rng), []),
        "prefix_heavy": (
            prefix_heavy(rng, header), prefix_heavy(rng, header, 1)),
    }


_BATCH = {"name": "batch", "weight": 0.8, "priority": 0,
          "prompt_len": (SEQ // 3, SEQ // 2 + 1),
          "steps": (max(2, SEQ // 6), max(3, SEQ // 3))}
_INTERACTIVE = {"name": "interactive", "weight": 0.2, "priority": 2,
                "prompt_len": (4, max(5, SEQ // 8)),
                "steps": (max(2, SEQ // 16), max(3, SEQ // 8))}
_BURST_RATE = max(60.0, 16000.0 / SEQ)


def two_tenant_burst(n, seed):
    """A low-priority tenant's bursts fill the pool while a high-priority
    tenant trickles in."""
    return loadgen.make_trace(
        process="bursty", rate=_BURST_RATE, n=n, vocab=VOCAB, seed=seed,
        tenants=[_BATCH, _INTERACTIVE], burst_factor=8.0, period=1.0,
        duty=0.4)


def swap_thrash(n, seed):
    """Both classes at a uniform high load: as much swapping as can be."""
    return loadgen.make_trace(
        process="poisson", rate=2 * _BURST_RATE, n=n, vocab=VOCAB, seed=seed,
        tenants=[{**_BATCH, "name": "lo", "weight": 0.5},
                 {**_BATCH, "name": "hi", "weight": 0.5, "priority": 2}])


def requests_of(trace):
    return [(ev["prompt"], ev["steps"]) for ev in trace]


# ------------------------------------------------- references and drives


def solo_refs(ref_gen, reqs):
    """One ragged call of the solo generator: a greedy ragged row equals
    its solo decode, so the run of the longest budget cut to each request's
    own is each request's reference."""
    ragged = ref_gen.generate(
        [p for p, _ in reqs], steps=max(s for _, s in reqs))
    return [np.asarray(row)[: p.size + s]
            for row, (p, s) in zip(list(ragged), reqs)]


def assert_all_equal(outs, refs, what):
    assert len(outs) == len(refs), what
    for i, (a, r) in enumerate(zip(outs, refs)):
        assert np.array_equal(a, r), (
            f"{what}: request {i} is not its reference")


def engine(model, *, slots=SLOTS, prefix_cache=False, **kw):
    from distkeras_tpu.serving import ServingEngine

    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("queue_capacity", 256)
    return ServingEngine(
        model, num_slots=slots, prefix_cache=prefix_cache, **kw).start()


def drive(eng, reqs, sampling=None):
    """Submit ``reqs`` a millisecond apart, so that a prompt lands while
    others decode, and wait for all of them."""
    handles = []
    for i, (p, s) in enumerate(reqs):
        kw = {} if sampling is None else {"sampling": sampling[i]}
        handles.append(eng.submit(p, s, **kw))
        time.sleep(0.001)
    return [h.result(TIMEOUT) for h in handles]


def drive_trace(eng, trace, stream=False):
    """Submit a ``loadgen`` trace with its tenants and priorities, paced by
    the trace's own gaps. With ``stream``, the events that ask for it are
    streamed, and their chunks must flatten to exactly the decoded tail."""
    handles, last = [], 0.0
    for ev in trace:
        time.sleep(min(max(ev["t"] - last, 0.0), 0.05))
        last = ev["t"]
        handles.append(eng.submit(
            ev["prompt"], ev["steps"], tenant=ev["tenant"],
            priority=ev["priority"],
            stream=bool(stream and ev.get("stream"))))
    results = [h.result(TIMEOUT) for h in handles]
    for h, ev, res in zip(handles, trace, results):
        if not (stream and ev.get("stream")):
            continue
        toks = []
        while (chunk := h.next_chunk(timeout=5.0)) is not None:
            toks.extend(int(x) for x in chunk)
        assert toks == [int(x) for x in res[len(ev["prompt"]):]], (
            "the streamed chunks are not the decoded tail in order")
    return results


def warm(eng, *, restore=False):
    """Every prefill bucket compiled, and the ledger told so: a program
    built from here on is a storm."""
    eng._stepper.warm_prefill_buckets()
    if restore:
        eng._stepper.warm_restore_buckets()
    eng.compile_ledger.mark_warmed()


def in_threads(work, n, wave=None):
    """``work(i)`` for every i in ``n``, one thread each, ``wave`` at a time;
    what a thread raises is raised here."""
    errors = []

    def run(i):
        try:
            work(i)
        except Exception as e:  # noqa: BLE001 - raised below
            errors.append((i, repr(e)))

    wave = wave or max(n, 1)
    for base in range(0, n, wave):
        ths = [threading.Thread(target=run, args=(i,))
               for i in range(base, min(base + wave, n))]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=TIMEOUT)
    assert not errors, errors[:3]


def generate_all(endpoint, reqs, wave=None, trace=False):
    """Over TCP, one connection a request, as real traffic has: the replies
    and the last request's timeline."""
    from distkeras_tpu.serving import ServingClient

    outs, traces = [None] * len(reqs), [None] * len(reqs)

    def one(i):
        with ServingClient(*endpoint, timeout=TIMEOUT) as c:
            outs[i] = c.generate(*reqs[i], trace=trace)
            traces[i] = c.last_trace

    in_threads(one, len(reqs), wave)
    return outs, traces[-1] if traces else None
