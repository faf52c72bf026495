"""Online-serving benchmark: chunked prefill + prefix cache vs PR 1.

Two serving optimizations ride the continuous batcher, and each gets an
honest A/B over IDENTICAL request streams through identical scheduler/
stepper/dispatch code:

- **Chunked prefill** (Sarathi-style): the PR 1 scheduler ran a new
  prompt's FULL prefill synchronously inside the scheduler iteration,
  so one long prompt stalled every decoding slot; the chunked scheduler
  spends at most ``prefill_chunk`` prompt tokens per iteration between
  decode steps. Measured by time-to-first-token and p99 end-to-end
  latency under mixed long-prompt traffic.
- **Shared-prefix KV reuse**: identical prompt prefixes (system
  prompts, few-shot headers) recompute K/V per request on PR 1; the
  prefix store serves them from cache (two-touch admission: one-shot
  novel prompts never earn a device fetch). Honesty protocol: warmup
  runs the timed set (so every compiled bucket is warm on both sides),
  then before EVERY timed pass the store is CLEARED and re-seeded with
  header-only requests — timed-run hits come from the shared header,
  the claimed effect, never from replaying warmed full prompts.

Measurement discipline for the 1-core sandbox: baseline and optimized
timed passes are INTERLEAVED (minutes-scale machine-speed drift hits
both sides equally), repeated ``--repeats`` times, and aggregated as
median-of-repeats percentiles with the across-repeat p99 spread kept
in the artifact.

- **Speculative decoding** (prompt-lookup drafter): its own A/B on a
  successor-trained LM — both sides the full chunked+cached engine,
  the optimized side adding ``speculative="ngram"``. Repetitive
  (self-similar) traffic is the claimed win; an incompressible row
  (random prompts, budgets too short to wrap into self-repetition)
  measures what the drafter + verify machinery costs when it cannot
  propose — stated, not hidden.

Correctness rides along: every request's greedy output is asserted
identical between the two configs, across repeats, AND to its solo
``CachedSequenceGenerator`` decode (cache-hit, chunked, and combined
admission paths all pinned; the speculative sides too). The PR 1
continuous-vs-serial ratio is kept for continuity.

Writes BENCH_SERVING.json and prints one JSON line.

Usage: python bench_serving.py [--cpu] [--smoke] [--slots 8]
                               [--requests 24] [--chunk N]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from bench import setup_backend


def _make_mixed_long(n, seq, vocab, rng):
    """Mixed LONG-prompt traffic: prompts 1..3*seq/4 tokens (the PR 1
    mix capped at seq/4 — too short to ever show prefill stalls),
    decode budgets seq/8..seq/4."""
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(1, max(2, 3 * seq // 4)))
        steps = int(rng.integers(max(2, seq // 8), max(3, seq // 4)))
        steps = max(1, min(steps, seq - plen))
        prompt = rng.integers(0, vocab, plen).astype(np.int32)
        reqs.append((prompt, steps))
    return reqs


def _make_prefix_heavy(n, seq, vocab, rng, header):
    """Prefix-heavy traffic: every prompt = the shared ``header`` plus
    a fresh 1..4-token suffix (the system-prompt / few-shot shape the
    prefix store exists for); decode budgets seq/8..seq/4."""
    reqs = []
    for _ in range(n):
        sfx = rng.integers(0, vocab, int(rng.integers(1, 5)))
        prompt = np.concatenate([header, sfx]).astype(np.int32)
        steps = int(rng.integers(max(2, seq // 8), max(3, seq // 4)))
        steps = max(1, min(steps, seq - prompt.size))
        reqs.append((prompt, steps))
    return reqs


def _make_spec_repetitive(n, seq, vocab, rng):
    """REPETITIVE/templated traffic for the speculative A/B: counting
    runs LONGER than the vocabulary, so the sequence literally repeats
    spans of itself (mod-V wrap) — the traffic shape prompt-lookup
    drafting exists for (few-shot templates, code edits, extraction
    over quoted context). On the successor-trained model the greedy
    continuation keeps counting, so the drafter's copied spans are
    RIGHT and acceptance runs near the ceiling."""
    reqs = []
    plen = min(vocab + 8, max(2, seq // 3))
    for _ in range(n):
        start = int(rng.integers(0, vocab))
        prompt = ((start + np.arange(plen)) % vocab).astype(np.int32)
        steps = int(rng.integers(seq // 8, seq // 4))
        steps = max(1, min(steps, seq - plen))
        reqs.append((prompt, steps))
    return reqs


def _make_spec_incompressible(n, seq, vocab, rng):
    """INCOMPRESSIBLE traffic: random prompts whose suffixes (almost)
    never recur, and decode budgets short enough that the generated
    tail cannot wrap into self-repetition — the drafter proposes
    nothing, and this row measures what speculation COSTS when it
    cannot win (the honesty row of the A/B)."""
    reqs = []
    plen = min(vocab + 8, max(2, seq // 3))
    for _ in range(n):
        prompt = rng.integers(0, vocab, plen).astype(np.int32)
        steps = int(rng.integers(max(2, vocab // 4),
                                 max(3, 3 * vocab // 4)))
        steps = max(1, min(steps, seq - plen))
        reqs.append((prompt, steps))
    return reqs


def _make_long_tail(n, seq, vocab, rng):
    """Long-tail mixed-length traffic — the paged A/B's adjudicating
    workload: most requests are SHORT (the mass of real mixed traffic),
    a tail is long. A dense (num_slots, seq_len) bank charges every
    one of them worst-case sequence memory; the paged pool charges
    what each actually needs, so the same KV byte budget sustains more
    concurrent slots."""
    reqs = []
    for _ in range(n):
        r = rng.random()
        if r < 0.70:  # short mass
            plen = int(rng.integers(1, max(2, seq // 8)))
        elif r < 0.95:  # medium
            plen = int(rng.integers(seq // 8, max(seq // 8 + 1, seq // 3)))
        else:  # the long tail
            plen = int(rng.integers(seq // 2, max(seq // 2 + 1, 3 * seq // 4)))
        steps = int(rng.integers(max(2, seq // 16), max(3, seq // 8)))
        steps = max(1, min(steps, seq - plen))
        reqs.append((rng.integers(0, vocab, plen).astype(np.int32), steps))
    return reqs


def _make_short_uniform(n, seq, vocab, rng):
    """Uniform SHORT prompts and budgets. The expected adversarial
    row going in (no length diversity for reservation to exploit) —
    measured, it is where the paged step's DYNAMIC attention extent
    pays instead: every table is short, so the bucketed gather attends
    a fraction of the dense bank's fixed worst-case extent. Committed
    as measured either way."""
    plen = max(2, seq // 8)
    steps = max(2, seq // 8)
    return [
        (rng.integers(0, vocab, plen).astype(np.int32), steps)
        for _ in range(n)
    ]


def _make_long_uniform(n, seq, vocab, rng):
    """The paged A/B's ADVERSARIAL row: every request near the
    sequence capacity. Reservations are worst-case for everyone (the
    equal-byte pool admits no more concurrency than the dense bank),
    the attention extent is full on both sides, and paging's
    gather/scatter plus allocator bookkeeping have NO occupancy win to
    pay for them — the honest cost row."""
    plen = 5 * seq // 8
    steps = max(2, seq // 8)
    return [
        (rng.integers(0, vocab, plen).astype(np.int32), steps)
        for _ in range(n)
    ]


def _make_production_mix(n, seq, vocab, rng, headers):
    """The adjudicating workload: 2/3 of requests extend one of the
    shared headers with a fresh mixed-length suffix (real serving
    traffic shares system prompts), 1/3 are entirely novel long-ish
    prompts (they pay the store's insert cost and never hit)."""
    reqs = []
    for i in range(n):
        if i % 3 < 2:
            h = headers[i % len(headers)]
            sfx = rng.integers(
                0, vocab, int(rng.integers(1, max(2, seq // 8)))
            )
            prompt = np.concatenate([h, sfx]).astype(np.int32)
        else:
            plen = int(rng.integers(1, max(2, 3 * seq // 4)))
            prompt = rng.integers(0, vocab, plen).astype(np.int32)
        steps = int(rng.integers(max(2, seq // 8), max(3, seq // 4)))
        steps = max(1, min(steps, seq - prompt.size))
        reqs.append((prompt, steps))
    return reqs


def _solo_refs(ref_gen, reqs):
    """Solo references via ONE ragged-generator call (per-request
    rectangular calls would compile a scan per distinct prompt
    length): each greedy ragged row is pinned equal to its solo
    decode, so trimming the shared-steps run to each request's budget
    IS the solo reference."""
    smax = max(s for _, s in reqs)
    ragged = ref_gen.generate([p for p, _ in reqs], steps=smax)
    return [
        np.asarray(row)[: p.size + s]
        for row, (p, s) in zip(list(ragged), reqs)
    ]


def _drive(engine, reqs, timeout=600.0, arrivals=None, sampling=None):
    """Submit ``reqs`` on the ``arrivals`` schedule (absolute offsets in
    seconds from the drive start; None = all at once), wait for all;
    returns (wall_seconds, tokens, results, latencies). Staggered
    arrivals are the traffic shape chunked prefill exists for — a long
    prompt landing WHILE other slots decode; an all-at-once burst has
    no in-flight decodes to protect. ``sampling``: optional per-request
    ``SamplingParams`` list (the sampled-side A/B driver); the token
    count scales by each request's ``n`` completions."""
    t0 = time.perf_counter()
    handles = []
    for i, (p, s) in enumerate(reqs):
        if arrivals is not None:
            wait = t0 + arrivals[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        kw = {} if sampling is None else {"sampling": sampling[i]}
        handles.append(engine.submit(p, s, **kw))
    results = [h.result(timeout) for h in handles]
    dt = time.perf_counter() - t0
    toks = sum(
        s * (1 if sampling is None else sampling[i].n)
        for i, (_, s) in enumerate(reqs)
    )
    return dt, toks, results, [h.latency() for h in handles]


def _pct(per_repeat):
    """Robust latency aggregate over repeats: per-repeat percentiles,
    MEDIAN across repeats (one OS-scheduling hiccup must not own the
    reported tail), with the honest across-repeat p99 spread kept."""
    reps = [np.asarray(r, float) for r in per_repeat]
    p50s = [float(np.percentile(r, 50)) for r in reps]
    p99s = [float(np.percentile(r, 99)) for r in reps]
    return {
        "mean": round(float(np.mean([r.mean() for r in reps])), 2),
        "p50": round(float(np.median(p50s)), 2),
        "p99": round(float(np.median(p99s)), 2),
        "p99_spread": [round(min(p99s), 2), round(max(p99s), 2)],
    }


def _engine(model, reqs, *, slots, prefill_chunk, prefix_cache,
            speculative=None, draft_k=4, flight_recorder=True,
            paged=False, page_size=16, num_pages=None, qos=None,
            history=True, history_interval=1.0, slos=None,
            overlap=True):
    from distkeras_tpu.serving import ServingEngine

    return ServingEngine(
        model, num_slots=slots, queue_capacity=2 * len(reqs) + 8,
        prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
        speculative=speculative, draft_k=draft_k,
        flight_recorder=flight_recorder,
        paged=paged, page_size=page_size, num_pages=num_pages,
        qos=qos, history=history, history_interval=history_interval,
        slos=slos, overlap=overlap,
    ).start()


def _reset(eng, prime):
    """Identical start state for every timed pass: prefix store CLEARED
    (timed-run hits must come from genuinely shared structure, never
    from replaying warmed or previous-pass prompts) and re-seeded with
    the ``prime`` requests (e.g. one request carrying the workload's
    shared header — driven twice, because two-touch admission only
    stores a prefix on its second miss); scheduler counters zeroed."""
    st0 = eng._stepper
    if getattr(st0, "paged", False):
        # the device-resident index is reuse state like the host store:
        # cleared before every timed pass so hits come from the pass's
        # own shared structure (the prime re-seeds it below); pool and
        # index LEDGERS reset so the committed snapshot covers the
        # timed passes, not the warm drives
        if st0.prefix_index is not None:
            st0.prefix_index.clear()
            st0.prefix_index.reset_counters()
        st0._kv_alloc.reset_counters()
    if eng.prefix_store is not None:
        eng.prefix_store.clear()
        if prime:
            _drive(eng, prime)
            _drive(eng, prime)
        eng.prefix_store.reset_counters()
    elif prime and getattr(st0, "paged", False):
        _drive(eng, prime)
        _drive(eng, prime)
    for k in eng.batcher.counters:
        eng.batcher.counters[k] = 0
    st = eng._stepper
    if getattr(st, "speculative", False):
        # per-pass speculative counters, so summed snapshots cover
        # exactly the timed window like every other field
        st.spec_verify_steps = 0
        st.spec_fallback_steps = 0
        st.spec_drafted_tokens = 0
        eng.batcher._spec_windows[:] = 0
        eng.batcher._spec_emitted[:] = 0


def _timed_pass(eng, reqs, arrivals, results):
    d, t, res, lat = _drive(eng, reqs, arrivals=arrivals)
    if results and results[-1] is not None:
        for a, b in zip(results[-1], res):  # greedy must not drift
            assert np.array_equal(a, b), "repeat output drift"
    results.append(res)
    return d, t, lat, eng.stats()  # per-pass counter snapshot


def _side(runs, prefix_cache):
    """Aggregate one engine config's repeats. Counters are reset before
    every timed pass and snapshotted after it, then SUMMED here, so
    every field in the record covers the same all-repeats window as
    wall_seconds and per_request (no last-pass-only numbers next to
    pooled aggregates)."""
    per_request = [
        {
            "ttft_ms": round(lat["ttft"] * 1e3, 2),
            "total_ms": round(lat["total"] * 1e3, 2),
            "queue_ms": round(lat["queue_wait"] * 1e3, 2),
            "prefill_ms": round(lat["prefill"] * 1e3, 2),
            "decode_ms": round(lat["decode"] * 1e3, 2),
        }
        for _, _, lats, _ in runs
        for lat in lats
    ]
    tps = [t / d for d, t, _, _ in runs]
    snaps = [s for _, _, _, s in runs]
    stats = dict(snaps[-1])
    for key in ("steps", "occupancy_sum", "prefill_chunks",
                "prefill_tokens", "tokens_generated", "completed"):
        stats[key] = sum(s[key] for s in snaps)
    stats["mean_batch_occupancy"] = (
        stats["occupancy_sum"] / stats["steps"] if stats["steps"] else 0.0
    )
    if prefix_cache:
        pc = dict(snaps[-1]["prefix_cache"])  # entries/bytes: last pass
        for key in ("hits", "misses", "hit_tokens", "inserts",
                    "evictions"):
            pc[key] = sum(s["prefix_cache"][key] for s in snaps)
        stats["prefix_cache"] = pc
    side = {
        "prefill_chunk": stats["prefill_chunk"],
        "prefix_cache_enabled": prefix_cache,
        "tokens_per_sec": round(float(np.median(tps)), 1),
        "tokens_per_sec_spread": [
            round(min(tps), 1), round(max(tps), 1)
        ],
        "wall_seconds": round(sum(d for d, _, _, _ in runs), 3),
        "ttft_ms": _pct(
            [[lat["ttft"] * 1e3 for lat in lats]
             for _, _, lats, _ in runs]
        ),
        "latency_ms": _pct(
            [[lat["total"] * 1e3 for lat in lats]
             for _, _, lats, _ in runs]
        ),
        "scheduler_steps": stats["steps"],
        "mean_batch_occupancy": round(stats["mean_batch_occupancy"], 2),
        "prefill_chunks": stats["prefill_chunks"],
        "per_request": per_request,
    }
    if prefix_cache:
        side["prefix_cache"] = {
            k: stats["prefix_cache"][k]
            for k in ("hits", "misses", "hit_tokens", "entries",
                      "evictions", "bytes")
        }
    return side


def _measure_ab(model, reqs, *, slots, chunk, prime=None, arrivals=None,
                repeats=1):
    """The A/B proper: baseline (PR 1 config) and chunked+cached engines
    measured with INTERLEAVED timed passes — baseline, optimized,
    baseline, optimized, ... — so the sandbox's minutes-scale speed
    drift hits both sides equally instead of whichever side ran last
    (alternate the measurements, never run one side after the other).
    Two warm passes per engine on the
    SAME arrival schedule as the timed runs first: warm pass one
    compiles the miss-path programs while populating the store, pass
    two the hit-path restore/suffix-chunk programs; matching the
    schedule matches the budget-split chunk shapes, so no timed pass
    ever pays a one-off compile."""
    base = _engine(model, reqs, slots=slots, prefill_chunk=None,
                   prefix_cache=False)
    opt = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                  prefix_cache=True)
    try:
        for eng in (base, opt):
            _drive(eng, reqs, arrivals=arrivals)
            _drive(eng, reqs, arrivals=arrivals)
        base_runs, opt_runs = [], []
        base_out, opt_out = [], []
        for _ in range(repeats):
            _reset(base, None)
            base_runs.append(_timed_pass(base, reqs, arrivals, base_out))
            _reset(opt, prime)
            opt_runs.append(_timed_pass(opt, reqs, arrivals, opt_out))
    finally:
        base.stop()
        opt.stop()
    return (
        _side(base_runs, False),
        _side(opt_runs, True),
        base_out[-1],
        opt_out[-1],
    )


def _spec_summary(runs):
    """Pool the speculative counters over a side's timed passes (they
    are zeroed by ``_reset`` before each one)."""
    snaps = [s["speculative"] for _, _, _, s in runs]
    tot = {
        k: sum(s[k] for s in snaps)
        for k in ("windows", "verify_steps", "fallback_steps",
                  "drafted_tokens", "accepted_draft_tokens",
                  "rejected_draft_tokens", "emitted_tokens")
    }
    tot["mean_tokens_per_window"] = (
        round(tot["emitted_tokens"] / tot["windows"], 3)
        if tot["windows"] else 0.0
    )
    return tot


def _measure_spec_ab(model, reqs, refs, *, slots, chunk, arrivals,
                     repeats, draft_k):
    """Speculative A/B: the SAME chunked+cached engine config with and
    without ``speculative="ngram"`` over identical request streams —
    interleaved timed passes per the PERF.md protocol, outputs on both
    sides asserted token-identical to the solo references."""
    base = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                   prefix_cache=True)
    opt = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                  prefix_cache=True, speculative="ngram",
                  draft_k=draft_k)
    try:
        for eng in (base, opt):  # warm both sides' programs
            _drive(eng, reqs, arrivals=arrivals)
            _drive(eng, reqs, arrivals=arrivals)
        base_runs, opt_runs = [], []
        base_out, opt_out = [], []
        for _ in range(repeats):
            _reset(base, None)
            base_runs.append(_timed_pass(base, reqs, arrivals, base_out))
            _reset(opt, None)
            opt_runs.append(_timed_pass(opt, reqs, arrivals, opt_out))
    finally:
        base.stop()
        opt.stop()
    for i, (a, b, r) in enumerate(zip(base_out[-1], opt_out[-1], refs)):
        assert np.array_equal(a, r), f"spec req {i}: baseline != solo"
        assert np.array_equal(b, r), f"spec req {i}: speculative != solo"
    b_side = _side(base_runs, True)
    o_side = _side(opt_runs, True)
    return {
        "num_requests": len(reqs),
        "prompt_lens": [int(p.size) for p, _ in reqs],
        "decode_steps": [int(s) for _, s in reqs],
        "baseline": b_side,
        "speculative": o_side,
        "acceptance": _spec_summary(opt_runs),
        "tokens_per_sec_ratio": _ratio(
            o_side["tokens_per_sec"], b_side["tokens_per_sec"]
        ),
        "latency_p99_speedup": _ratio(
            b_side["latency_ms"]["p99"], o_side["latency_ms"]["p99"]
        ),
        "outputs_identical": True,
    }


def _drive_tcp(port, reqs, arrivals, trace=False, timeout=600.0):
    """Fire ``reqs`` at a live server over TCP on the arrival schedule
    (one client connection per request, concurrent — the fleet bench's
    driving discipline), optionally with per-request tracing. Returns
    (wall_seconds, tokens, results, last_trace_of_final_request)."""
    import threading

    from distkeras_tpu.serving import ServingClient

    n = len(reqs)
    results = [None] * n
    traces = [None] * n
    errors = []
    t0 = time.perf_counter()

    def worker(i):
        prompt, steps = reqs[i]
        wait = t0 + arrivals[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            with ServingClient("127.0.0.1", port, timeout=timeout) as c:
                results[i] = c.generate(prompt, steps, trace=trace)
                traces[i] = c.last_trace
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append((i, repr(e)))

    ths = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=timeout)
    assert not errors, f"tracing bench requests failed: {errors[:3]}"
    wall = time.perf_counter() - t0
    return wall, sum(s for _, s in reqs), results, traces[-1]


def _measure_tracing(model, reqs, refs, *, slots, chunk, arrivals,
                     repeats):
    """Tracing-overhead A/B over REAL TCP: the same engine + server
    serving identical request streams, one side untraced (the default
    path every production request rides), one side with per-request
    ``trace=True`` (span records + per-request event ledger + timeline
    on the reply). Interleaved timed passes per the PERF.md protocol;
    outputs on both sides asserted token-identical to the solo refs.
    Also captures the well-formedness artifacts the CI harness pins:
    a complete sample timeline, the ``metrics`` verb snapshot, and a
    parse of the Prometheus dump."""
    from distkeras_tpu.obs import parse_prometheus, timeline_complete
    from distkeras_tpu.serving import ServingClient, ServingServer

    eng = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                  prefix_cache=True)
    srv = ServingServer(eng).start()
    untraced, traced = [], []
    sample_trace = None
    try:
        _drive_tcp(srv.port, reqs, arrivals)  # warm every bucket
        _drive_tcp(srv.port, reqs, arrivals, trace=True)
        for _ in range(repeats):
            wall, toks, outs, _ = _drive_tcp(srv.port, reqs, arrivals)
            untraced.append(toks / wall)
            for a, r in zip(outs, refs):
                assert np.array_equal(a, r), "untraced != solo"
            wall, toks, outs, tl = _drive_tcp(
                srv.port, reqs, arrivals, trace=True
            )
            traced.append(toks / wall)
            sample_trace = tl
            for a, r in zip(outs, refs):
                assert np.array_equal(a, r), "traced != solo"
        with ServingClient("127.0.0.1", srv.port) as c:
            samples = c.metrics()
            prom_series = parse_prometheus(c.metrics(prometheus=True))
    finally:
        srv.shutdown()
    assert sample_trace is not None and timeline_complete(
        sample_trace["spans"]
    ), sample_trace
    overhead = {
        "num_requests": len(reqs),
        "repeats": repeats,
        "untraced_tokens_per_sec": round(float(np.median(untraced)), 1),
        "untraced_spread": [round(min(untraced), 1),
                            round(max(untraced), 1)],
        "traced_tokens_per_sec": round(float(np.median(traced)), 1),
        "traced_spread": [round(min(traced), 1), round(max(traced), 1)],
        # >= 0.97 = the per-request tracing machinery costs < 3%;
        # untraced requests ride the SAME instrumented binary with no
        # trace context, so tracing-off overhead is bounded above by
        # whatever this ratio shows tracing-ON costs
        "traced_vs_untraced": _ratio(
            float(np.median(traced)), float(np.median(untraced))
        ),
        "outputs_identical": True,
    }
    observability = {
        "sample_trace_spans": [s["name"] for s in sample_trace["spans"]],
        "sample_trace_complete": True,
        "metrics_samples": len(samples),
        "metrics_sample_names": sorted(
            {s["name"] for s in samples}
        )[:8],
        "prometheus_series": len(prom_series),
        "prometheus_parses": True,
    }
    return overhead, observability


def _measure_recorder(model, reqs, refs, *, slots, chunk, arrivals,
                      repeats):
    """Flight-recorder overhead A/B: the same chunked+cached engine
    config with the always-on black box ON (the default — one bounded
    ring append per working scheduler iteration plus blame/quarantine
    events) vs OFF (``flight_recorder=False``, the control). Direct
    engine drive (no TCP) on purpose: the recorder's cost sits on the
    scheduler thread, and the wire would only dilute it. Interleaved
    timed passes per the PERF.md protocol; outputs on both sides
    asserted token-identical to the solo references. The < 2% budget
    lives in ``test_bench_harness.py`` against the committed row."""
    off = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                  prefix_cache=True, flight_recorder=False)
    on = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                 prefix_cache=True, flight_recorder=True)
    off_tps, on_tps = [], []
    off_out, on_out = [], []
    try:
        for eng in (off, on):  # warm both sides' programs
            _drive(eng, reqs, arrivals=arrivals)
            _drive(eng, reqs, arrivals=arrivals)
        for _ in range(repeats):
            _reset(off, None)
            d, t, res, _ = _drive(off, reqs, arrivals=arrivals)
            off_tps.append(t / d)
            off_out = res
            _reset(on, None)
            d, t, res, _ = _drive(on, reqs, arrivals=arrivals)
            on_tps.append(t / d)
            on_out = res
        events_recorded = on.recorder.events_recorded
        overwrites = on.recorder.overwrites
        kinds = {e["kind"] for e in on.recorder.snapshot()}
    finally:
        off.stop()
        on.stop()
    for i, (a, b, r) in enumerate(zip(off_out, on_out, refs)):
        assert np.array_equal(a, r), f"recorder req {i}: off != solo"
        assert np.array_equal(b, r), f"recorder req {i}: on != solo"
    assert "scheduler.iteration" in kinds, kinds
    return {
        "num_requests": len(reqs),
        "repeats": repeats,
        "recorder_off_tokens_per_sec": round(
            float(np.median(off_tps)), 1
        ),
        "off_spread": [round(min(off_tps), 1), round(max(off_tps), 1)],
        "recorder_on_tokens_per_sec": round(
            float(np.median(on_tps)), 1
        ),
        "on_spread": [round(min(on_tps), 1), round(max(on_tps), 1)],
        # >= 0.98 = the always-on black box costs < 2% tokens/sec
        # (the stated budget; the committed-artifact test pins it)
        "recorder_vs_off": _ratio(
            float(np.median(on_tps)), float(np.median(off_tps))
        ),
        "events_recorded": int(events_recorded),
        "ring_overwrites": int(overwrites),
        "outputs_identical": True,
    }


def _measure_obs(model, reqs, refs, *, slots, chunk, arrivals,
                 repeats):
    """Metrics-history overhead A/B: the chunked+cached engine with
    the time-series ring ON (the default — one registry walk per
    ``history_interval`` on the supervisor thread, never the
    scheduler's) vs OFF (``history=False``, the control). Direct
    engine drive, interleaved timed passes, outputs pinned to the
    solo references — the same protocol as the PR 8 recorder row, and
    the same < 2% budget (``check_bench --kind obs`` pins the
    committed ratio).

    This block also carries the COMPILE invariant the r14/r16 bench
    post-mortems bought: both engines are ledger-warmed after the
    warm drives (``mark_warmed``), every timed pass asserts ZERO
    mints landed inside it (``timed_pass_compiles``), and the ON side
    proves the ``timeseries`` digest + burn verdict actually computed
    over the measured traffic."""
    from distkeras_tpu.obs import default_serving_slos

    off = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                  prefix_cache=True, history=False)
    # a tight history cadence so even the smoke's short timed passes
    # land multiple snapshots in the ring; SLOs configured so the
    # burn verdict grades real series (loose bounds: the A/B measures
    # cost, not violations)
    on = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                 prefix_cache=True, history=True,
                 history_interval=0.05,
                 slos=default_serving_slos(latency_p99_s=600.0,
                                           error_rate=0.5,
                                           min_count=1))
    off_tps, on_tps = [], []
    off_out, on_out = [], []
    timed_mints = 0
    try:
        for eng in (off, on):  # warm both sides' programs
            _drive(eng, reqs, arrivals=arrivals)
            _drive(eng, reqs, arrivals=arrivals)
            # the warm drives cannot cover every CHUNK bucket (which
            # bucket a prefill hits depends on how the budget splits
            # across concurrently-admitted prompts — timing, not
            # traffic shape), so compile the full pow2 families
            # off-path before arming: from here, a timed-pass mint is
            # a storm AND a broken bench invariant
            eng._stepper.warm_prefill_buckets()
            eng.compile_ledger.mark_warmed()
        for _ in range(repeats):
            _reset(off, None)
            m0 = off.compile_ledger.total
            d, t, res, _ = _drive(off, reqs, arrivals=arrivals)
            timed_mints += off.compile_ledger.total - m0
            off_tps.append(t / d)
            off_out = res
            _reset(on, None)
            m0 = on.compile_ledger.total
            d, t, res, _ = _drive(on, reqs, arrivals=arrivals)
            timed_mints += on.compile_ledger.total - m0
            on_tps.append(t / d)
            on_out = res
        assert timed_mints == 0, (
            f"{timed_mints} XLA mints landed inside timed passes — "
            f"the committed numbers would include compile stalls "
            f"(ledger: {on.compile_ledger.snapshot()} / "
            f"{off.compile_ledger.snapshot()})"
        )
        # the ON side's history actually answers over the measured
        # traffic: windowed digest + burn verdict computed post-pass
        ts = on.timeseries(window=60.0)
        burn = ts["burn"]
        completed = [
            r for r in ts["series"]
            if r["name"] == "serving_scheduler_completed"
        ]
        ts_ok = (
            ts["snapshots"] >= 2
            and len(ts["series"]) > 10
            and bool(completed)
            and (completed[0]["rate"] or 0) > 0
            and burn is not None
        )
        storms = (
            on.compile_ledger.storms + off.compile_ledger.storms
        )
    finally:
        off.stop()
        on.stop()
    for i, (a, b, r) in enumerate(zip(off_out, on_out, refs)):
        assert np.array_equal(a, r), f"obs req {i}: history-off != solo"
        assert np.array_equal(b, r), f"obs req {i}: history-on != solo"
    assert ts_ok, ts
    return {
        "num_requests": len(reqs),
        "repeats": repeats,
        "history_off_tokens_per_sec": round(
            float(np.median(off_tps)), 1
        ),
        "off_spread": [round(min(off_tps), 1), round(max(off_tps), 1)],
        "history_on_tokens_per_sec": round(
            float(np.median(on_tps)), 1
        ),
        "on_spread": [round(min(on_tps), 1), round(max(on_tps), 1)],
        # >= 0.98 = the history ring costs < 2% tokens/sec (the
        # stated budget; check_bench --kind obs pins the committed
        # row)
        "history_vs_off": _ratio(
            float(np.median(on_tps)), float(np.median(off_tps))
        ),
        # the standing no-compiles-in-timed-passes gate (r14/r16)
        "timed_pass_compiles": int(timed_mints),
        "compile_storms": int(storms),
        "timeseries": {
            "snapshots": int(ts["snapshots"]),
            "series_rows": len(ts["series"]),
            "completed_rate_positive": True,
            "burn_verdict": burn["burn"],
        },
        "outputs_identical": True,
    }


def _measure_paged_ab(model, reqs, refs, *, slots, chunk, arrivals,
                      repeats, page_size=16, prime=None,
                      slot_multiple=2):
    """Paged-vs-dense A/B at an EQUAL KV byte budget: the dense side
    serves ``slots`` slots each pinned to worst-case sequence memory;
    the paged side spends the SAME pool bytes (``slots * ceil(seq /
    page_size)`` pages) across ``slot_multiple x slots`` logical slots,
    each reserving only what its request needs — the occupancy unlock
    under mixed-length traffic, plus device-resident block-granular
    prefix sharing. Interleaved timed passes per the PERF.md protocol;
    outputs on BOTH sides asserted token-identical to the solo refs on
    every pass (the paged admission paths ride the same pin)."""
    seq = model.input_shape[0]
    pool_pages = slots * (-(-seq // page_size)) + 1  # + null sentinel
    dense = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                    prefix_cache=True)
    paged = _engine(model, reqs, slots=slot_multiple * slots,
                    prefill_chunk=chunk, prefix_cache=True,
                    paged=True, page_size=page_size,
                    num_pages=pool_pages)
    try:
        for eng in (dense, paged):  # warm every program family
            _drive(eng, reqs, arrivals=arrivals)
            _drive(eng, reqs, arrivals=arrivals)
        dense_runs, paged_runs = [], []
        dense_out, paged_out = [], []
        for _ in range(repeats):
            _reset(dense, prime)
            dense_runs.append(
                _timed_pass(dense, reqs, arrivals, dense_out)
            )
            _reset(paged, prime)
            paged_runs.append(
                _timed_pass(paged, reqs, arrivals, paged_out)
            )
        paged_stats = paged.stats()["paged"]
    finally:
        dense.stop()
        paged.stop()
    for i, (a, b, r) in enumerate(zip(dense_out[-1], paged_out[-1],
                                      refs)):
        assert np.array_equal(a, r), f"paged A/B req {i}: dense != solo"
        assert np.array_equal(b, r), f"paged A/B req {i}: paged != solo"
    d_side = _side(dense_runs, True)
    p_side = _side(paged_runs, True)
    p_side["paged"] = {
        k: paged_stats[k]
        for k in ("page_size", "total_pages", "shared_pages",
                  "cow_copies", "exhaustions")
    }
    p_side["paged"]["device_prefix"] = {
        k: paged_stats["device_prefix"][k]
        for k in ("hits", "misses", "hit_pages", "reclaims")
    }
    return {
        "num_requests": len(reqs),
        "prompt_lens": [int(p.size) for p, _ in reqs],
        "decode_steps": [int(s) for _, s in reqs],
        "dense_slots": slots,
        "paged_slots": slot_multiple * slots,
        "kv_pool_pages": pool_pages - 1,
        "dense": d_side,
        "paged": p_side,
        "tokens_per_sec_ratio": _ratio(
            p_side["tokens_per_sec"], d_side["tokens_per_sec"]
        ),
        "latency_p99_speedup": _ratio(
            d_side["latency_ms"]["p99"], p_side["latency_ms"]["p99"]
        ),
        "occupancy_ratio": _ratio(
            p_side["mean_batch_occupancy"],
            max(d_side["mean_batch_occupancy"], 1e-9),
        ),
        "outputs_identical": True,
    }


def _measure_paged_block(model, ref_gen, *, seq, vocab, slots, chunk,
                         requests, gap_ms, repeats, rng, header,
                         high_load_factor=3.0):
    """The full paged-vs-dense block: long-tail mixed lengths at HIGH
    load (arrivals ``high_load_factor`` x faster than the standard
    tiers — occupancy only pays when demand exceeds the dense slot
    count), prefix-heavy reuse (must not regress), and the
    short-uniform adversarial row."""
    paged_workloads = {
        "long_tail_mixed": (
            _make_long_tail(int(requests * 2), seq, vocab, rng),
            None,
        ),
        "prefix_heavy": (
            _make_prefix_heavy(requests, seq, vocab, rng, header),
            _make_prefix_heavy(1, seq, vocab, rng, header),
        ),
        "short_uniform": (
            _make_short_uniform(requests, seq, vocab, rng),
            None,
        ),
        "long_uniform": (
            _make_long_uniform(requests, seq, vocab, rng),
            None,
        ),
    }
    block = {
        "page_size": 16,
        "high_load_arrival_gap_ms": round(gap_ms / high_load_factor, 3),
        "workloads": {},
    }
    for name, (timed, prime) in paged_workloads.items():
        refs = _solo_refs(ref_gen, timed)
        gap = gap_ms / (high_load_factor if name == "long_tail_mixed"
                        else 1.0)
        arrivals = np.cumsum(rng.exponential(gap / 1e3, len(timed)))
        wl = _measure_paged_ab(
            model, timed, refs, slots=slots, chunk=chunk,
            arrivals=arrivals, repeats=repeats, prime=prime,
        )
        block["workloads"][name] = wl
        print(json.dumps({f"paged_{name}": {
            "tokens_per_sec_ratio": wl["tokens_per_sec_ratio"],
            "occupancy_ratio": wl["occupancy_ratio"],
            "latency_p99_speedup": wl["latency_p99_speedup"],
        }}), flush=True)
    return block


def _measure_sampling_block(model, reqs, refs, *, slots, chunk,
                            arrivals, repeats, rng):
    """The sampling block: (a) sampled-vs-greedy — the SAME
    chunked+cached engine config serving the identical request stream
    greedy vs per-request temperature/top-p sampled, interleaved timed
    passes per the PERF.md protocol; the greedy side is identity-
    asserted against the solo refs, the sampled side REPLAY-asserted
    across repeats (position-keyed RNG: same seed, same tokens — the
    repeat-drift assert IS the claim). (b) n=4-via-fork — one n=4
    completion-group request (CoW ``fork_slot`` after one shared
    prefill) vs FOUR independent admissions with the derived
    per-completion seeds, on identical paged engines; the two sides
    produce token-identical completions BY CONSTRUCTION (asserted),
    so the ratio prices exactly the shared prefill + shared pages."""
    from distkeras_tpu.serving import SamplingParams
    from distkeras_tpu.serving.sampling import seed_for_completion

    # -- (a) sampled vs greedy ---------------------------------------------
    greedy = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                     prefix_cache=True)
    sampled = _engine(model, reqs, slots=slots, prefill_chunk=chunk,
                      prefix_cache=True)
    sparams = [
        SamplingParams(temperature=0.7, top_p=0.9, seed=1000 + i)
        for i in range(len(reqs))
    ]
    g_tps, s_tps = [], []
    g_out, s_out = [], []
    try:
        for eng in (greedy, sampled):  # warm the greedy programs
            _drive(eng, reqs, arrivals=arrivals)
        _drive(sampled, reqs, arrivals=arrivals, sampling=sparams)
        for _ in range(repeats):
            _reset(greedy, None)
            d, t, res, _ = _drive(greedy, reqs, arrivals=arrivals)
            g_tps.append(t / d)
            g_out = res
            _reset(sampled, None)
            d, t, res, _ = _drive(
                sampled, reqs, arrivals=arrivals, sampling=sparams
            )
            s_tps.append(t / d)
            if s_out:
                for i, (a, b) in enumerate(zip(s_out, res)):
                    assert np.array_equal(a, b), (
                        f"sampled req {i}: replay drift across repeats"
                    )
            s_out = res
    finally:
        greedy.stop()
        sampled.stop()
    for i, (a, r) in enumerate(zip(g_out, refs)):
        assert np.array_equal(a, r), f"sampling A/B req {i}: greedy != solo"
    row_ab = {
        "num_requests": len(reqs),
        "temperature": 0.7,
        "top_p": 0.9,
        "greedy_tokens_per_sec": round(float(np.median(g_tps)), 1),
        "greedy_spread": [round(min(g_tps), 1), round(max(g_tps), 1)],
        "sampled_tokens_per_sec": round(float(np.median(s_tps)), 1),
        "sampled_spread": [round(min(s_tps), 1), round(max(s_tps), 1)],
        # the overhead row: per-token sort + counter-keyed draw vs
        # plain argmax, everything else identical
        "tokens_per_sec_ratio": _ratio(
            float(np.median(s_tps)), float(np.median(g_tps))
        ),
        "outputs_identical": True,
        "replay_identical": True,
    }

    # -- (b) n=4 via fork vs 4 independent admissions ----------------------
    n = 4
    base = reqs[: max(2, len(reqs) // 3)]
    fork_params = [
        SamplingParams(temperature=0.8, seed=500 + i, n=n)
        for i in range(len(base))
    ]
    ind_reqs, ind_params = [], []
    for i, (p, s) in enumerate(base):
        for j in range(n):
            ind_reqs.append((p, s))
            ind_params.append(SamplingParams(
                temperature=0.8,
                seed=seed_for_completion(500 + i, j),
            ))
    fork_arr = np.cumsum(rng.exponential(0.002, len(base)))
    ind_arr = np.repeat(fork_arr, n)  # the same instants, 4 users each
    fork_eng = _engine(model, ind_reqs, slots=max(slots, n),
                       prefill_chunk=chunk, prefix_cache=False,
                       paged=True)
    ind_eng = _engine(model, ind_reqs, slots=max(slots, n),
                      prefill_chunk=chunk, prefix_cache=False,
                      paged=True)
    f_tps, i_tps = [], []
    f_out, i_out = [], []
    try:
        _drive(fork_eng, base, arrivals=fork_arr, sampling=fork_params)
        _drive(ind_eng, ind_reqs, arrivals=ind_arr, sampling=ind_params)
        for _ in range(repeats):
            _reset(fork_eng, None)
            d, t, res, _ = _drive(
                fork_eng, base, arrivals=fork_arr, sampling=fork_params
            )
            f_tps.append(t / d)
            f_out = res
            _reset(ind_eng, None)
            d, t, res, _ = _drive(
                ind_eng, ind_reqs, arrivals=ind_arr,
                sampling=ind_params,
            )
            i_tps.append(t / d)
            i_out = res
        fork_stats = fork_eng.stats()
        forked_total = int(fork_eng.batcher.forked_slots.value)
    finally:
        fork_eng.stop()
        ind_eng.stop()
    for i in range(len(base)):
        for j in range(n):
            assert np.array_equal(f_out[i][j], i_out[i * n + j]), (
                f"fork req {i} completion {j} != independent admission"
            )
    return {
        "sampled_vs_greedy": row_ab,
        "n4_fork": {
            "n": n,
            "num_requests": len(base),
            "fork_tokens_per_sec": round(float(np.median(f_tps)), 1),
            "fork_spread": [round(min(f_tps), 1),
                            round(max(f_tps), 1)],
            "independent_tokens_per_sec": round(
                float(np.median(i_tps)), 1
            ),
            "independent_spread": [round(min(i_tps), 1),
                                   round(max(i_tps), 1)],
            # > 1 = one prefill + CoW page sharing beat n admissions
            "fork_vs_independent": _ratio(
                float(np.median(f_tps)), float(np.median(i_tps))
            ),
            "completions_identical": True,
            "cow_copies": fork_stats["paged"]["cow_copies"],
            "forked_slots": forked_total,
        },
    }


def _drive_trace(engine, trace, timeout=600.0, stream=False):
    """Submit a ``tools/loadgen.py`` trace on its arrival schedule —
    tenant and priority ride each submit — and wait for all. Returns
    ``(wall_seconds, decode_tokens, results, latencies)``; latencies
    are per-event dicts with the event's tenant attached. With
    ``stream=True``, events carrying a truthy ``stream`` flag submit
    as streaming requests and their retained chunk FIFOs are drained
    post-completion and asserted to flatten to EXACTLY the decode
    tail — the chunk-order identity pin, per drive (opt-in so the
    QoS block's timings stay untouched)."""
    t0 = time.perf_counter()
    handles = []
    for ev in trace:
        wait = t0 + ev["t"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        handles.append(engine.submit(
            ev["prompt"], ev["steps"], tenant=ev["tenant"],
            priority=ev["priority"],
            stream=bool(stream and ev.get("stream")),
        ))
    results = [h.result(timeout) for h in handles]
    dt = time.perf_counter() - t0
    if stream:
        for h, ev, res in zip(handles, trace, results):
            if not ev.get("stream"):
                continue
            toks = []
            while True:  # FIFO retains everything; drain to sentinel
                c = h.next_chunk(timeout=5.0)
                if c is None:
                    break
                toks.extend(int(x) for x in c)
            tail = [int(x) for x in res[len(ev["prompt"]):]]
            assert toks == tail, (
                f"streamed chunks flatten to {toks[:8]}..., decode "
                f"tail is {tail[:8]}... — chunk order broke")
    toks = sum(ev["steps"] for ev in trace)
    lats = [
        {**h.latency(), "tenant": ev["tenant"]}
        for h, ev in zip(handles, trace)
    ]
    return dt, toks, results, lats


def _tenant_pct(runs, tenant):
    """Per-tenant total-latency percentiles (ms) pooled per repeat —
    the ``_pct`` discipline scoped to one tenant's events."""
    return _pct([
        [lat["total"] * 1e3 for lat in lats if lat["tenant"] == tenant]
        for _, _, lats, _ in runs
    ])


def _measure_qos_scenario(model, trace, refs, *, slots, chunk,
                          page_size, num_pages, repeats, qos_policy):
    """One QoS A/B scenario: a FIFO engine vs a QoS-scheduled engine
    (same slots, same page pool — EQUAL HARDWARE) serving the SAME
    loadgen trace, interleaved timed passes per the PERF.md protocol.
    Every request is greedy and asserted token-identical to its solo
    reference on BOTH sides EVERY pass — on the QoS side that pin
    crosses the preempt/resume boundary, so the swap path's identity
    claim is re-proven per bench pass, not just in tier-1."""
    fifo = _engine(model, trace, slots=slots, prefill_chunk=chunk,
                   prefix_cache=False, paged=True,
                   page_size=page_size, num_pages=num_pages)
    qos = _engine(model, trace, slots=slots, prefill_chunk=chunk,
                  prefix_cache=False, paged=True,
                  page_size=page_size, num_pages=num_pages,
                  qos=qos_policy)
    fifo_runs, qos_runs = [], []
    preemptions = {"preemptions": 0, "resumes": 0, "preempt_aborted": 0,
                   "swap_in_failures": 0, "swapped_failed": 0,
                   "swapped_tokens": 0}

    def warm_restore_buckets(eng):
        """Compile every pow2 swap-restore bucket OFF the timed path:
        which bucket a resume needs depends on the victim's length at
        preempt time (timing-dependent), and a mid-pass XLA compile
        would land inside some interactive request's p99."""
        st = eng._stepper
        pbt = st._max_pages_bucket
        nh, hd = st._nh, st._hd
        dt = np.dtype(st._gen.kv_dtype)
        # every bucket _restore_prefix can key on: pow2s plus the
        # max_len-CLAMPED value (the bucket a near-capacity victim
        # restores at when max_len is not itself a power of two)
        pb, buckets = 1, set()
        while True:
            buckets.add(min(pb, st.max_len))
            if pb >= st.max_len:
                break
            pb <<= 1
        for pb in sorted(buckets):
            key = (pb, pbt)
            if key not in st._pcopy_fns:
                st._pcopy_fns = {
                    **st._pcopy_fns,
                    key: st._build_copy_fn_paged(pb, pbt),
                }
            ks = np.zeros((len(st._gen._stages), pb, nh, hd), dt)
            # an all-zero table row scatters into the null sentinel
            # page (garbage there is unreachable by construction)
            st._pools = st._pcopy_fns[key](
                st._pools, ks, ks.copy(), np.zeros((pbt,), np.int32)
            )

    try:
        for eng in (fifo, qos):  # warm every program family
            _drive_trace(eng, trace)
            _drive_trace(eng, trace)
            warm_restore_buckets(eng)
        for _ in range(repeats):
            _reset(fifo, None)
            d, t, res, lats = _drive_trace(fifo, trace)
            for i, (a, r) in enumerate(zip(res, refs)):
                assert np.array_equal(a, r), f"qos A/B {i}: fifo != solo"
            fifo_runs.append((d, t, lats, fifo.stats()))
            _reset(qos, None)
            d, t, res, lats = _drive_trace(qos, trace)
            for i, (a, r) in enumerate(zip(res, refs)):
                # the preempt/resume identity pin, per bench pass
                assert np.array_equal(a, r), f"qos A/B {i}: qos != solo"
            snap = qos.stats()
            for k in preemptions:
                preemptions[k] += snap[k]
            qos_runs.append((d, t, lats, snap))
    finally:
        fifo.stop()
        qos.stop()
    tenants = sorted({ev["tenant"] for ev in trace})
    f_tps = [t / d for d, t, _, _ in fifo_runs]
    q_tps = [t / d for d, t, _, _ in qos_runs]
    out = {
        "num_requests": len(trace),
        "tenants": {
            t: {
                "requests": sum(ev["tenant"] == t for ev in trace),
                "priority": next(
                    ev["priority"] for ev in trace if ev["tenant"] == t
                ),
                "fifo_latency_ms": _tenant_pct(fifo_runs, t),
                "qos_latency_ms": _tenant_pct(qos_runs, t),
            }
            for t in tenants
        },
        "fifo_tokens_per_sec": round(float(np.median(f_tps)), 1),
        "qos_tokens_per_sec": round(float(np.median(q_tps)), 1),
        "tokens_per_sec_ratio": _ratio(
            float(np.median(q_tps)), float(np.median(f_tps))
        ),
        "qos_counters": preemptions,
        "outputs_identical": True,
    }
    for t in tenants:
        row = out["tenants"][t]
        row["p99_speedup"] = _ratio(
            row["fifo_latency_ms"]["p99"], row["qos_latency_ms"]["p99"]
        )
    return out


def _measure_qos_block(model, ref_gen, *, seq, vocab, slots, chunk,
                       requests, repeats, seed=0):
    """The multi-tenant QoS block: FIFO vs QoS at equal hardware over
    loadgen traces. ``two_tenant_burst`` is the claimed win — a
    low-priority batch tenant's bursts saturate the page pool while a
    high-priority interactive tenant trickles in; priority admission
    + preemption-by-page-swap must hold the interactive tenant's p99
    down (committed floor in check_bench). ``swap_thrash`` is the
    honest adversarial row: UNIFORM high load from both classes keeps
    preempting/resuming the low class (maximum swap churn, no idle
    capacity for the win to come from) — the throughput cost is
    committed as measured."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    try:
        import loadgen
    finally:
        _sys.path.pop(0)
    from distkeras_tpu.serving import QosPolicy

    page_size = 16
    paged_slots = 2 * slots
    num_pages = slots * (-(-seq // page_size)) + 1  # dense-equal budget
    policy = QosPolicy(preempt=True, max_preemptions=2)
    batch = {
        "name": "batch", "weight": 0.8, "priority": 0,
        "prompt_len": (seq // 3, seq // 2 + 1),
        "steps": (max(2, seq // 6), max(3, seq // 3)),
    }
    interactive = {
        "name": "interactive", "weight": 0.2, "priority": 2,
        "prompt_len": (4, max(5, seq // 8)),
        "steps": (max(2, seq // 16), max(3, seq // 8)),
    }
    # the burst arrives well past the pool's service rate: overload is
    # the regime QoS exists for (an idle fleet needs no scheduler) —
    # FIFO must build a genuinely deep queue for the interactive
    # tenant to be stuck behind
    burst_rate = max(60.0, 16000.0 / seq)
    scenarios = {
        "two_tenant_burst": loadgen.make_trace(
            process="bursty", rate=burst_rate, n=4 * requests,
            tenants=[batch, interactive], vocab=vocab, seed=seed,
            burst_factor=8.0, period=1.0, duty=0.4,
        ),
        "swap_thrash": loadgen.make_trace(
            process="poisson", rate=2 * burst_rate, n=3 * requests,
            tenants=[
                {**batch, "name": "lo", "weight": 0.5},
                {**batch, "name": "hi", "weight": 0.5, "priority": 2},
            ],
            vocab=vocab, seed=seed + 1,
        ),
    }
    block = {
        "paged_slots": paged_slots,
        "kv_pool_pages": num_pages - 1,
        "qos_policy": policy.describe(),
        "scenarios": {},
    }
    for name, trace in scenarios.items():
        refs = _solo_refs(
            ref_gen, [(ev["prompt"], ev["steps"]) for ev in trace]
        )
        sc = _measure_qos_scenario(
            model, trace, refs, slots=paged_slots, chunk=chunk,
            page_size=page_size, num_pages=num_pages,
            repeats=repeats, qos_policy=policy,
        )
        sc["trace"] = {
            "process": "bursty" if name == "two_tenant_burst"
            else "poisson",
            # the spec rate the trace was actually generated at (the
            # thrash row runs 2x the burst rate)
            "rate": burst_rate if name == "two_tenant_burst"
            else 2 * burst_rate,
            "summary": loadgen.summarize(trace),
        }
        if name == "two_tenant_burst":
            sc["hi_p99_speedup"] = sc["tenants"]["interactive"][
                "p99_speedup"]
            sc["lo_p99_cost"] = _ratio(
                sc["tenants"]["batch"]["qos_latency_ms"]["p99"],
                sc["tenants"]["batch"]["fifo_latency_ms"]["p99"],
            )
        block["scenarios"][name] = sc
        print(json.dumps({f"qos_{name}": {
            "tokens_per_sec_ratio": sc["tokens_per_sec_ratio"],
            "preemptions": sc["qos_counters"]["preemptions"],
            **({"hi_p99_speedup": sc["hi_p99_speedup"]}
               if name == "two_tenant_burst" else {}),
        }}), flush=True)
    return block


def _overlap_row(make_engine, drive, *, repeats, n, refs=None,
                 pair_identity=False, extra_warm=None,
                 record_preemptions=False):
    """One overlapped-vs-sequential A/B row: the SAME engine config
    built twice (``make_engine(overlap)``), INTERLEAVED timed passes
    per the PERF.md protocol, outputs pinned every pass — to the solo
    ``refs`` when greedy, or overlapped==sequential + replay-stable
    across passes (``pair_identity``, the sampled row where no greedy
    solo reference exists). Both loop modes stamp the same
    ``OverlapLedger``, so the bubble fraction on each side is read
    from ONE instrument: per-pass device/iteration-second deltas
    summed over the timed window (warm drives excluded by
    construction). Ledger-warmed after the warm drives; a mint inside
    any timed pass is an assertion failure, not a footnote."""
    sq = make_engine(False)
    ov = make_engine(True)
    sides = {"sq": sq, "ov": ov}
    tps = {"sq": [], "ov": []}
    dev = {"sq": 0.0, "ov": 0.0}
    itw = {"sq": 0.0, "ov": 0.0}
    preempts = {"sq": 0, "ov": 0}
    last = {"sq": None, "ov": None}
    timed_mints = 0
    try:
        for eng in (sq, ov):  # warm every program family per side
            drive(eng)
            drive(eng)
            eng._stepper.warm_prefill_buckets()
            if extra_warm is not None:
                extra_warm(eng)
            eng.compile_ledger.mark_warmed()
        for _ in range(repeats):
            for name in ("sq", "ov"):
                eng = sides[name]
                _reset(eng, None)
                led = eng.batcher.overlap_ledger
                m0 = eng.compile_ledger.total
                dev0, it0 = led.device_seconds, led.iteration_seconds
                d, t, res = drive(eng)
                timed_mints += eng.compile_ledger.total - m0
                dev[name] += led.device_seconds - dev0
                itw[name] += led.iteration_seconds - it0
                preempts[name] += eng.stats().get("preemptions", 0)
                tps[name].append(t / d)
                if refs is not None:
                    for i, (a, r) in enumerate(zip(res, refs)):
                        assert np.array_equal(a, r), (
                            f"overlap A/B [{name}] req {i}: != solo")
                if last[name] is not None:
                    for a, b in zip(last[name], res):
                        assert np.array_equal(a, b), (
                            f"overlap A/B [{name}]: repeat drift")
                last[name] = res
            if pair_identity:
                for i, (a, b) in enumerate(zip(last["sq"], last["ov"])):
                    assert np.array_equal(a, b), (
                        f"overlap A/B req {i}: overlapped != sequential")
        assert timed_mints == 0, (
            f"{timed_mints} XLA mints landed inside timed passes "
            f"(ledger: {ov.compile_ledger.snapshot()} / "
            f"{sq.compile_ledger.snapshot()})"
        )
        storms = sq.compile_ledger.storms + ov.compile_ledger.storms
    finally:
        sq.stop()
        ov.stop()
    bf = {
        name: (1.0 - dev[name] / itw[name]) if itw[name] > 0 else None
        for name in ("sq", "ov")
    }
    row = {
        "num_requests": n,
        "sequential_tokens_per_sec": round(
            float(np.median(tps["sq"])), 1),
        "sequential_spread": [
            round(min(tps["sq"]), 1), round(max(tps["sq"]), 1)],
        "overlapped_tokens_per_sec": round(
            float(np.median(tps["ov"])), 1),
        "overlapped_spread": [
            round(min(tps["ov"]), 1), round(max(tps["ov"]), 1)],
        "tokens_per_sec_ratio": _ratio(
            float(np.median(tps["ov"])), float(np.median(tps["sq"]))),
        "sequential_bubble_fraction": round(bf["sq"], 4),
        "overlapped_bubble_fraction": round(bf["ov"], 4),
        "bubble_reduction": round(bf["sq"] - bf["ov"], 4),
        "timed_pass_compiles": int(timed_mints),
        "compile_storms": int(storms),
        "outputs_identical": True,
    }
    if record_preemptions:
        row["preemptions"] = {
            "sequential": preempts["sq"], "overlapped": preempts["ov"]
        }
    return row


def _measure_overlap_block(model, ref_gen, *, seq, vocab, slots, chunk,
                           requests, repeats, rng):
    """Zero-bubble decode: the overlapped scheduler loop (host
    admission/emission for iteration N+1 under iteration N's device
    step) vs the sequential control, same engine config otherwise.
    Four traffic shapes:

    - ``decode_heavy`` is the claimed win — long decode runs and a
      streamed tenant, the regime where per-iteration host work is a
      fixed tax the overlap can hide;
    - ``short_uniform`` is the honest adversarial row: short uniform
      bursts are host-work-LIGHT (admission once, then tight decode),
      so there is little bubble to reclaim — committed as measured;
    - ``sampled`` re-proves identity where no greedy solo reference
      exists: overlapped == sequential per pass AND seeded replay
      stable across passes;
    - ``preempt`` pins the deferred-preemption path: a paged + QoS
      engine under a two-tenant burst, identity asserted ACROSS the
      preempt/resume boundary on both sides, per-side preemption
      counts committed (the committed overlapped side must actually
      have preempted — check_bench gates it).

    Every pass is identity-asserted, zero compiles inside timed
    windows, and the bubble reduction on decode_heavy carries a
    committed floor in ``check_bench --kind overlap``."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    try:
        import loadgen
    finally:
        _sys.path.pop(0)
    from distkeras_tpu.serving import QosPolicy, SamplingParams

    block = {"rows": {}}

    # -- decode_heavy: the claimed win, streamed tenant riding along --
    trace = loadgen.make_trace(
        process="poisson", rate=max(50.0, 12000.0 / seq),
        n=3 * requests, tenants=loadgen.decode_heavy_tenants(seq),
        vocab=vocab, seed=11,
    )
    assert any(ev.get("stream") for ev in trace), (
        "decode_heavy trace drew no streamed events — pick a seed "
        "that exercises the stream-push ordering")
    refs = _solo_refs(
        ref_gen, [(ev["prompt"], ev["steps"]) for ev in trace]
    )
    row = _overlap_row(
        lambda overlap: _engine(
            model, trace, slots=slots, prefill_chunk=chunk,
            prefix_cache=False, overlap=overlap),
        lambda eng: _drive_trace(eng, trace, stream=True)[:3],
        repeats=repeats, n=len(trace), refs=refs,
    )
    row["streamed_requests"] = sum(
        bool(ev.get("stream")) for ev in trace
    )
    row["trace"] = {
        "process": "poisson",
        "rate": max(50.0, 12000.0 / seq),
        "summary": loadgen.summarize(trace),
    }
    block["rows"]["decode_heavy"] = row

    # -- short_uniform: host-work-light, the adversarial row ----------
    reqs = _make_short_uniform(requests, seq, vocab, rng)
    block["rows"]["short_uniform"] = _overlap_row(
        lambda overlap: _engine(
            model, reqs, slots=slots, prefill_chunk=chunk,
            prefix_cache=False, overlap=overlap),
        lambda eng: _drive(eng, reqs)[:3],
        repeats=repeats, n=len(reqs),
        refs=_solo_refs(ref_gen, reqs),
    )

    # -- sampled: identity without a greedy solo reference ------------
    sreqs = _make_mixed_long(requests, seq, vocab, rng)
    sampling = [
        SamplingParams(temperature=0.7, top_p=0.9, seed=2000 + i)
        for i in range(len(sreqs))
    ]
    block["rows"]["sampled"] = _overlap_row(
        lambda overlap: _engine(
            model, sreqs, slots=slots, prefill_chunk=chunk,
            prefix_cache=False, overlap=overlap),
        lambda eng: _drive(eng, sreqs, sampling=sampling)[:3],
        repeats=repeats, n=len(sreqs), pair_identity=True,
    )

    # -- preempt: deferred preemption under a paged + QoS burst -------
    page_size = 16
    paged_slots = 2 * slots
    num_pages = slots * (-(-seq // page_size)) + 1  # dense-equal pool
    policy = QosPolicy(preempt=True, max_preemptions=2)
    batch = {
        "name": "batch", "weight": 0.8, "priority": 0,
        "prompt_len": (seq // 3, seq // 2 + 1),
        "steps": (max(2, seq // 6), max(3, seq // 3)),
    }
    interactive = {
        "name": "interactive", "weight": 0.2, "priority": 2,
        "prompt_len": (4, max(5, seq // 8)),
        "steps": (max(2, seq // 16), max(3, seq // 8)),
    }
    burst_rate = max(60.0, 16000.0 / seq)
    ptrace = loadgen.make_trace(
        process="bursty", rate=burst_rate, n=3 * requests,
        tenants=[batch, interactive], vocab=vocab, seed=13,
        burst_factor=8.0, period=1.0, duty=0.4,
    )
    block["rows"]["preempt"] = _overlap_row(
        lambda overlap: _engine(
            model, ptrace, slots=paged_slots, prefill_chunk=chunk,
            prefix_cache=False, paged=True, page_size=page_size,
            num_pages=num_pages, qos=policy, overlap=overlap),
        lambda eng: _drive_trace(eng, ptrace)[:3],
        repeats=repeats, n=len(ptrace),
        refs=_solo_refs(
            ref_gen, [(ev["prompt"], ev["steps"]) for ev in ptrace]
        ),
        extra_warm=lambda eng: eng._stepper.warm_restore_buckets(),
        record_preemptions=True,
    )

    for name, row in block["rows"].items():
        print(json.dumps({f"overlap_{name}": {
            "tokens_per_sec_ratio": row["tokens_per_sec_ratio"],
            "bubble_reduction": row["bubble_reduction"],
        }}), flush=True)
    block["timed_pass_compiles"] = sum(
        r["timed_pass_compiles"] for r in block["rows"].values()
    )
    block["compile_storms"] = sum(
        r["compile_storms"] for r in block["rows"].values()
    )
    block["outputs_identical"] = True
    return block


def _boot_disagg_fleet(model, *, slots, chunk, roles):
    """One bench fleet: len(roles) engines (each ``slots`` slots, same
    chunk budget — EQUAL HARDWARE across sides) behind a role-aware
    router, health-gated into rotation before any traffic."""
    from distkeras_tpu.serving import (
        FleetRouter,
        ServingEngine,
        ServingServer,
    )

    engines, servers = [], []
    for role in roles:
        eng = ServingEngine(
            model, num_slots=slots, queue_capacity=256,
            prefill_chunk=chunk, prefix_cache=False, role=role,
        )
        servers.append(ServingServer(eng).start())
        engines.append(eng)
    router = FleetRouter(
        endpoints=[(s.host, s.port) for s in servers],
        health_interval=0.1,
    ).start()
    for s in servers:
        assert router.wait_in_rotation((s.host, s.port), timeout=60.0)
    return engines, servers, router


def _drive_disagg_tcp(port, trace, timeout=600.0):
    """Fire a loadgen trace at a router over TCP on its arrival
    schedule — STREAMED events via ``generate_stream`` (real
    first-byte TTFT + inter-chunk gaps), the rest via plain
    ``generate``. Returns ``(wall, decode_tokens, results, ttfts,
    gaps)`` where ttfts/gaps cover the streamed events only (the
    honest delivery-time measurements)."""
    import threading

    from distkeras_tpu.serving import ServingClient

    n = len(trace)
    results = [None] * n
    ttfts = [None] * n
    gaps: list[list] = [[] for _ in range(n)]
    errors = []
    t0 = time.perf_counter()

    def worker(i):
        ev = trace[i]
        wait = t0 + ev["t"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            with ServingClient("127.0.0.1", port,
                               timeout=timeout) as c:
                if ev.get("stream"):
                    st = c.generate_stream(ev["prompt"], ev["steps"])
                    for _ in st:
                        pass
                    results[i] = st.sequence
                    ttfts[i] = st.ttft_s
                    gaps[i] = list(st.inter_token_s)
                else:
                    results[i] = c.generate(ev["prompt"], ev["steps"])
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append((i, repr(e)))

    ths = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=timeout)
    assert not errors, f"disagg bench requests failed: {errors[:3]}"
    wall = time.perf_counter() - t0
    return (
        wall, sum(ev["steps"] for ev in trace), results,
        [t for t in ttfts if t is not None],
        [g for gs in gaps for g in gs],
    )


def _measure_disagg_scenario(model, trace, refs, *, slots, chunk,
                             repeats):
    """One disagg A/B scenario at equal hardware: 1 prefill + 1 decode
    worker vs 2 unified replicas, both behind a role-aware router,
    serving the SAME trace over real TCP with interleaved timed
    passes. Every request's output (streamed or not) is asserted
    token-identical to its solo reference EVERY pass on BOTH sides —
    on the disagg side that pin crosses the wire transfer."""
    _, d_servers, d_router = _boot_disagg_fleet(
        model, slots=slots, chunk=chunk, roles=("prefill", "decode"),
    )
    _, u_servers, u_router = _boot_disagg_fleet(
        model, slots=slots, chunk=chunk, roles=("unified", "unified"),
    )
    d_runs, u_runs = [], []
    try:
        for port in (d_router.port, u_router.port):  # warm both sides
            _drive_disagg_tcp(port, trace)
            _drive_disagg_tcp(port, trace)
        for rt in (d_router, u_router):
            for k in rt.counters:
                rt.counters[k] = 0
        for _ in range(repeats):
            for port, runs in ((d_router.port, d_runs),
                               (u_router.port, u_runs)):
                wall, toks, res, ttfts, gaps = _drive_disagg_tcp(
                    port, trace
                )
                for i, (a, r) in enumerate(zip(res, refs)):
                    assert np.array_equal(a, r), (
                        f"disagg A/B req {i}: output != solo "
                        f"(port {port})"
                    )
                runs.append((wall, toks, ttfts, gaps))
        d_stats = d_router.stats()
        transfer = {
            k: d_stats[k]
            for k in ("disagg_routed", "transfer_sends", "transfer_ok",
                      "transfer_typed", "transfer_retries",
                      "peer_sends", "peer_ok", "peer_typed",
                      "peer_degraded")
        }
    finally:
        for rt in (d_router, u_router):
            rt.shutdown()
        for s in d_servers + u_servers:
            s.shutdown()

    def side(runs):
        tps = [t / w for w, t, _, _ in runs]
        return {
            "tokens_per_sec": round(float(np.median(tps)), 1),
            "tokens_per_sec_spread": [
                round(min(tps), 1), round(max(tps), 1)
            ],
            "wall_seconds": round(sum(w for w, _, _, _ in runs), 3),
            # first DELIVERED chunk frame, client wall clock — the
            # streaming TTFT the whole PR exists to make honest
            "ttft_ms": _pct(
                [[t * 1e3 for t in ttfts] for _, _, ttfts, _ in runs]
            ),
            # inter-chunk delivery gaps: the tail a decoding client
            # feels when a long prompt lands next door
            "inter_token_ms": _pct(
                [[g * 1e3 for g in gaps] for _, _, _, gaps in runs]
            ),
        }

    d_side, u_side = side(d_runs), side(u_runs)
    return {
        "num_requests": len(trace),
        "streamed_requests": sum(
            1 for ev in trace if ev.get("stream")
        ),
        "disagg": d_side,
        "unified": u_side,
        # > 1 = the role split isolates decoding clients from
        # long-prompt arrivals (the DistServe claim, measured at the
        # client); honest either way on the adversarial row
        "inter_token_p99_ratio": _ratio(
            u_side["inter_token_ms"]["p99"],
            d_side["inter_token_ms"]["p99"],
        ),
        "ttft_p99_ratio": _ratio(
            u_side["ttft_ms"]["p99"], d_side["ttft_ms"]["p99"]
        ),
        "tokens_per_sec_ratio": _ratio(
            d_side["tokens_per_sec"], u_side["tokens_per_sec"]
        ),
        "transfer": transfer,
        # both ledgers: every relay hop resolved (ok/typed) AND every
        # direct-push pairing settled exactly once (ok/typed/degraded
        # — a degraded pairing fell back to the relay, never stranded)
        "transfer_balanced": (
            transfer["transfer_sends"]
            == transfer["transfer_ok"] + transfer["transfer_typed"]
            and transfer["peer_sends"]
            == transfer["peer_ok"] + transfer["peer_typed"]
            + transfer["peer_degraded"]
        ),
        "outputs_identical": True,
    }


def _measure_disagg_block(model, ref_gen, *, seq, vocab, slots, chunk,
                          requests, repeats, seed=0):
    """The disaggregated prefill/decode block: 1 prefill + 1 decode
    worker vs 2 unified replicas at EQUAL hardware over the standard
    loadgen harness. ``interactive`` (the claimed win) is the
    ``interactive`` preset — streamed short chat turns mixed with
    prefill-heavy long documents, where the role split keeps decode
    iterations free of prefill chunks. ``short_uniform_overhead`` is
    the honest adversarial row: uniformly SHORT streamed prompts,
    where prefill is one cheap chunk and the transfer hop (serialize
    + two wire crossings + restore) is PURE overhead — committed as
    measured."""
    import os
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    try:
        import loadgen
    finally:
        _sys.path.pop(0)

    repeats = max(1, min(int(repeats), 3))
    rate = max(40.0, 10000.0 / seq)
    scenarios = {
        "interactive": loadgen.make_trace(
            process="poisson", rate=rate, n=3 * requests,
            tenants=loadgen.interactive_tenants(seq), vocab=vocab,
            seed=seed,
        ),
        "short_uniform_overhead": loadgen.make_trace(
            process="poisson", rate=rate, n=2 * requests,
            tenants=[{
                "name": "chat", "weight": 1.0, "priority": 0,
                "stream": 1.0,
                "prompt_len": (4, max(6, seq // 10)),
                "steps": (max(4, seq // 16), max(6, seq // 6)),
            }],
            vocab=vocab, seed=seed + 1,
        ),
    }
    block = {
        "hardware": {
            "workers_per_side": 2,
            "slots_per_worker": slots,
            "prefill_chunk": chunk,
        },
        "streaming_ttft": (
            "ttft_ms measures to the FIRST DELIVERED chunk frame at "
            "the client (generate_stream), not a reconstructed "
            "server-side timestamp"
        ),
        "scenarios": {},
    }
    for name, trace in scenarios.items():
        # cap every request inside the bank capacity
        for ev in trace:
            ev["steps"] = max(
                1, min(int(ev["steps"]), seq - int(ev["prompt"].size))
            )
        refs = _solo_refs(
            ref_gen, [(ev["prompt"], ev["steps"]) for ev in trace]
        )
        sc = _measure_disagg_scenario(
            model, trace, refs, slots=slots, chunk=chunk,
            repeats=repeats,
        )
        sc["trace"] = {
            "preset": (
                "interactive" if name == "interactive"
                else "short_uniform"
            ),
            "rate": rate,
            "summary": loadgen.summarize(trace),
        }
        block["scenarios"][name] = sc
        print(json.dumps({f"disagg_{name}": {
            k: sc[k]
            for k in ("inter_token_p99_ratio", "ttft_p99_ratio",
                      "tokens_per_sec_ratio")
        }}), flush=True)
    return block


def _drive_waves(port, reqs, *, wave=4, timeout=600.0):
    """Fire ``reqs`` at a live server/router over TCP in concurrent
    waves of ``wave`` clients (waves keep a least-loaded router
    honestly choosing under load without melting the 1-core bench
    box). Every request must succeed; returns
    ``(wall, results, latencies)`` with per-request client wall
    latencies in seconds."""
    import threading

    from distkeras_tpu.serving import ServingClient

    results = [None] * len(reqs)
    lats = [None] * len(reqs)
    errors = []
    t0 = time.perf_counter()

    def worker(i):
        prompt, steps = reqs[i]
        try:
            ta = time.perf_counter()
            with ServingClient("127.0.0.1", port, timeout=timeout) as c:
                results[i] = c.generate(prompt, steps)
            lats[i] = time.perf_counter() - ta
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append((i, repr(e)))

    for base in range(0, len(reqs), wave):
        ths = [
            threading.Thread(target=worker, args=(i,))
            for i in range(base, min(base + wave, len(reqs)))
        ]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=timeout)
    assert not errors, f"resilience bench requests failed: {errors[:3]}"
    return time.perf_counter() - t0, results, lats


def _drive_storm(port, hi_reqs, storm_reqs, *, budget, timeout=600.0):
    """One storm pass: every ``storm_reqs`` launched AT ONCE as a
    priority-0 no-retry burst (tenant ``storm``, all clients sharing
    ``budget`` so the pass's attempt accounting is one ledger) while
    the priority-2 interactive requests ride through concurrently.
    Returns ``(wall, hi_results, hi_lats, storm_results, outcomes)``;
    ``outcomes`` classifies every storm reply — ``ok`` /
    ``typed_overloaded`` (checked to carry an honest ``retry_after``
    hint; a refusal without one counts ``hint_missing``) /
    ``typed_other`` / ``untyped`` — so a silent hang or a raw socket
    error is a counted finding, not a lost thread."""
    import threading

    from distkeras_tpu.serving import ServingClient, ServingError

    hi_res = [None] * len(hi_reqs)
    hi_lat = [None] * len(hi_reqs)
    st_res = [None] * len(storm_reqs)
    outcomes = {"ok": 0, "typed_overloaded": 0, "typed_other": 0,
                "untyped": 0, "hint_missing": 0}
    olock = threading.Lock()
    errors = []

    def hi(i):
        prompt, steps = hi_reqs[i]
        try:
            ta = time.perf_counter()
            with ServingClient("127.0.0.1", port, timeout=timeout) as c:
                hi_res[i] = c.generate(
                    prompt, steps, tenant="interactive", priority=2
                )
            hi_lat[i] = time.perf_counter() - ta
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append((i, repr(e)))

    def storm(i):
        prompt, steps = storm_reqs[i]
        try:
            with ServingClient("127.0.0.1", port, timeout=timeout,
                               retry=False, retry_budget=budget) as c:
                st_res[i] = c.generate(
                    prompt, steps, tenant="storm", priority=0
                )
            with olock:
                outcomes["ok"] += 1
        except ServingError as e:
            with olock:
                if getattr(e, "code", None) == "overloaded":
                    outcomes["typed_overloaded"] += 1
                    if getattr(e, "retry_after", None) is None:
                        outcomes["hint_missing"] += 1
                else:
                    outcomes["typed_other"] += 1
        except Exception:  # noqa: BLE001 — untyped = a counted finding
            with olock:
                outcomes["untyped"] += 1

    ths = [
        threading.Thread(target=storm, args=(i,))
        for i in range(len(storm_reqs))
    ] + [
        threading.Thread(target=hi, args=(i,))
        for i in range(len(hi_reqs))
    ]
    t0 = time.perf_counter()
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=timeout)
    assert not errors, f"hi-priority requests failed: {errors[:3]}"
    return time.perf_counter() - t0, hi_res, hi_lat, st_res, outcomes


def _measure_storm_row(model, ref_gen, *, seq, vocab, slots, chunk,
                       requests, repeats, rng):
    """Adaptive load shedding under a 5x storm: shedding-off vs
    shedding-on, SAME engine config otherwise, over real TCP. Each
    timed pass fires a 5x burst of priority-0 storm requests while
    priority-2 interactive requests ride through; goodput is the
    interactive tokens delivered per wall second. On the shedding
    side the operator seam DECLARES the brownout for the storm window
    (``burn_fn`` -> "burning": rung 1 sheds priority<=0 at the door
    and NEVER clamps, so replies stay token-identical) — the rung-1
    machinery exercised is the real one end to end (typed
    ``overloaded`` over the wire with honest sojourn-derived
    ``retry_after_ms`` hints), made deterministic where organic CoDel
    latching at bench scale is seed-dependent; the sojourn gate still
    rides on top. Pairing is exact by construction and GATED: gate
    sheds == typed overloaded refusals received, every refusal
    hinted, zero untyped errors on either side."""
    from distkeras_tpu.serving import ServingEngine, ServingServer
    from distkeras_tpu.serving.resilience import RetryBudget

    hi_reqs = [
        (rng.integers(0, vocab, max(2, seq // 8)).astype(np.int32),
         max(2, seq // 8))
        for _ in range(requests)
    ]
    storm_reqs = [
        (rng.integers(0, vocab, max(2, seq // 8)).astype(np.int32),
         max(2, seq // 16))
        for _ in range(5 * requests)
    ]
    hi_refs = _solo_refs(ref_gen, hi_reqs)
    storm_refs = _solo_refs(ref_gen, storm_reqs)
    # capacity covers the whole burst on BOTH sides: the off side must
    # queue (not capacity-refuse) so the only typed refusals anywhere
    # come from the shed gate — the exact-pairing precondition
    cap = 2 * (len(hi_reqs) + len(storm_reqs)) + 8

    def boot(shed):
        eng = ServingEngine(
            model, num_slots=slots, queue_capacity=cap,
            prefill_chunk=chunk, prefix_cache=False,
            shed=dict(burn_interval=0.05) if shed else False,
        ).start()
        return eng, ServingServer(eng).start()

    eng_on, srv_on = boot(True)
    eng_off, srv_off = boot(False)
    sides = {"shed_off": (eng_off, srv_off),
             "shed_on": (eng_on, srv_on)}
    budget = RetryBudget(ratio=0.5, burst=max(10.0, len(storm_reqs)))
    goodput = {name: [] for name in sides}
    hi_lats = {name: [] for name in sides}
    tally = {
        name: {"ok": 0, "typed_overloaded": 0, "typed_other": 0,
               "untyped": 0, "hint_missing": 0}
        for name in sides
    }
    hi_tokens = sum(s for _, s in hi_reqs)
    timed_mints = 0
    gate = eng_on.shed_gate
    steady_burn = gate.burn_fn
    try:
        for eng, srv in sides.values():  # warm every bucket, both sides
            for _ in range(2):
                _drive_waves(srv.port, hi_reqs + storm_reqs,
                             wave=2 * slots)
            eng.compile_ledger.mark_warmed()
        # snapshot AFTER warm: the warm waves queue deep enough to
        # latch the sojourn gate organically, and the warm clients'
        # default retry policy absorbs those sheds silently — they are
        # not part of the timed-window pairing ledger
        sheds0 = gate.state()["sheds"]
        for _ in range(repeats):
            for name in ("shed_off", "shed_on"):
                eng, srv = sides[name]
                if name == "shed_on":
                    gate.burn_fn = lambda: "burning"
                    deadline = time.monotonic() + 10.0
                    while gate.rung() < 1:  # brownout engaged
                        assert time.monotonic() < deadline
                        time.sleep(0.01)
                m0 = eng.compile_ledger.total
                wall, hi_res, hi_lat, st_res, outc = _drive_storm(
                    srv.port, hi_reqs, storm_reqs, budget=budget
                )
                if name == "shed_on":
                    gate.burn_fn = steady_burn
                timed_mints += eng.compile_ledger.total - m0
                for i, (a, r) in enumerate(zip(hi_res, hi_refs)):
                    assert np.array_equal(a, r), (
                        f"storm A/B [{name}] hi req {i}: != solo")
                for i, (a, r) in enumerate(zip(st_res, storm_refs)):
                    if a is not None:  # refused requests have no reply
                        assert np.array_equal(a, r), (
                            f"storm A/B [{name}] storm req {i}: != solo")
                goodput[name].append(hi_tokens / wall)
                hi_lats[name].append([t * 1e3 for t in hi_lat])
                for k, v in outc.items():
                    tally[name][k] += v
        storms = sum(
            e.compile_ledger.storms for e, _ in sides.values()
        )
    finally:
        gate.burn_fn = steady_burn
        for eng, srv in sides.values():
            srv.shutdown()
            eng.stop()
    # the declared brownout must RELEASE: rung back to 0 once the
    # operator seam reads "ok" again (burn_interval-paced refresh)
    deadline = time.monotonic() + 10.0
    while gate.rung() != 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    sheds = gate.state()["sheds"] - sheds0
    for name in sides:
        assert tally[name]["untyped"] == 0, (name, tally[name])
        assert tally[name]["typed_other"] == 0, (name, tally[name])
        assert tally[name]["hint_missing"] == 0, (name, tally[name])
    p_off, p_on = _pct(hi_lats["shed_off"]), _pct(hi_lats["shed_on"])
    return {
        "num_hi_requests": len(hi_reqs),
        "num_storm_requests": len(storm_reqs),
        "storm_multiplier": 5,
        "hi_tokens_per_pass": hi_tokens,
        "shed_off": {
            "goodput_tokens_per_sec": round(
                float(np.median(goodput["shed_off"])), 1),
            "hi_latency_ms": p_off,
            "storm_outcomes": tally["shed_off"],
        },
        "shed_on": {
            "goodput_tokens_per_sec": round(
                float(np.median(goodput["shed_on"])), 1),
            "hi_latency_ms": p_on,
            "storm_outcomes": tally["shed_on"],
        },
        "goodput_ratio": _ratio(
            float(np.median(goodput["shed_on"])),
            float(np.median(goodput["shed_off"])),
        ),
        "hi_p99_improvement": _ratio(p_off["p99"], p_on["p99"]),
        "shed_pairing": {
            "gate_sheds": int(sheds),
            "typed_overloaded": tally["shed_on"]["typed_overloaded"],
            "exact": int(sheds)
            == tally["shed_on"]["typed_overloaded"],
        },
        "hints_honest": True,
        "retry_budget": budget.snapshot(),
        # the LIVE rung, not state()["rung"]: that one is the
        # last-admission snapshot and goes stale once traffic stops
        "shed_rung_released": gate.rung() == 0,
        "brownout": (
            "declared via the operator burn seam for each storm "
            "window (rung 1: shed priority<=0, never clamp — "
            "identity-safe); the CoDel sojourn gate rides on top "
            "organically"
        ),
        "timed_pass_compiles": int(timed_mints),
        "compile_storms": int(storms),
        "outputs_identical": True,
    }


def _measure_gray_row(model, ref_gen, *, seq, vocab, slots, chunk,
                      requests, repeats, rng):
    """Gray failure vs circuit breakers: a 2-replica fleet whose first
    replica is slowed 250 ms per data-path request via the
    ``net.delay`` seam — health polls stay GREEN the whole time
    (asserted every pass on both routers: ejection never fires, the
    failure shape binary health cannot see) — routed through a
    breaker-armed router vs a plain one, SHARED replicas, interleaved
    timed passes. The breaker is tripped OFF the timed path and its
    ``open_secs`` outlives the whole measured window, so no half-open
    probe's stall pollutes a committed p99 (``probes_in_timed_window``
    is committed and gated at 0). Every reply on both sides is
    token-identical to its solo reference — a gray replica delays,
    it must never corrupt."""
    from distkeras_tpu import faults
    from distkeras_tpu.serving import (
        FleetRouter,
        ServingEngine,
        ServingServer,
    )

    reqs = _make_short_uniform(requests, seq, vocab, rng)
    refs = _solo_refs(ref_gen, reqs)
    engines, servers = [], []
    routers = {}
    plan = faults.FaultPlan()
    lats = {"breaker_off": [], "breaker_on": []}
    timed_mints = 0
    probes_in_window = 0
    try:
        for _ in range(2):
            eng = ServingEngine(
                model, num_slots=slots,
                queue_capacity=4 * len(reqs) + 8,
                prefill_chunk=chunk, prefix_cache=False,
            ).start()
            servers.append(ServingServer(eng).start())
            engines.append(eng)
        slow_port = int(servers[0].port)
        slow_ep = (servers[0].host, slow_port)
        for srv in servers:  # warm each replica directly, seam disarmed
            for _ in range(2):
                _drive_waves(srv.port, reqs, wave=2 * slots)
        for eng in engines:
            eng.compile_ledger.mark_warmed()
        routers["breaker_on"] = FleetRouter(
            endpoints=[(s.host, s.port) for s in servers],
            health_interval=0.1, affinity=False,
            # open_secs outlives every timed pass: once open the
            # breaker STAYS open through the measured window
            breaker=dict(open_secs=120.0, outlier_trips=2,
                         outlier_factor=3.0, min_latency=0.02),
        ).start()
        routers["breaker_off"] = FleetRouter(
            endpoints=[(s.host, s.port) for s in servers],
            health_interval=0.1, affinity=False,
        ).start()
        for rt in routers.values():
            for s in servers:
                assert rt.wait_in_rotation(
                    (s.host, s.port), timeout=60.0
                )
        plan.arm(
            "net.delay", action="delay", delay=0.25, times=None,
            when=lambda ctx: ctx.get("port") == slow_port,
        ).activate()

        def slow_state(rt):
            for r in rt.replicas():
                if tuple(r["endpoint"]) == slow_ep:
                    return r
            raise AssertionError("slow replica left the books")

        # trip the breaker OFF the timed path: concurrent bursts give
        # both replicas windowed latency until the outlier sweep opens
        rt_on = routers["breaker_on"]
        deadline = time.monotonic() + 120.0
        while slow_state(rt_on)["breaker"]["state"] != "open":
            assert time.monotonic() < deadline, "breaker never opened"
            _drive_waves(rt_on.port, reqs[: 2 * slots], wave=2 * slots)
        for _ in range(repeats):
            for name in ("breaker_off", "breaker_on"):
                rt = routers[name]
                m0 = sum(e.compile_ledger.total for e in engines)
                p0 = rt.counters.get("breaker_probes", 0)
                _, res, lat = _drive_waves(rt.port, reqs, wave=4)
                timed_mints += (
                    sum(e.compile_ledger.total for e in engines) - m0
                )
                if name == "breaker_on":
                    probes_in_window += (
                        rt.counters.get("breaker_probes", 0) - p0
                    )
                    assert (
                        slow_state(rt)["breaker"]["state"] == "open"
                    )
                # the gray property: health stays green on BOTH
                # routers the whole time — ejection never fires
                st = slow_state(rt)
                assert st["state"] == "active", st
                for i, (a, r) in enumerate(zip(res, refs)):
                    assert np.array_equal(a, r), (
                        f"gray A/B [{name}] req {i}: != solo")
                lats[name].append([t * 1e3 for t in lat])
        on_counters = {
            k: int(routers["breaker_on"].counters[k])
            for k in ("breaker_opens", "breaker_half_opens",
                      "breaker_closes", "breaker_probes",
                      "breaker_bypass_forwards")
        }
        storms = sum(e.compile_ledger.storms for e in engines)
    finally:
        plan.deactivate()
        for rt in routers.values():
            rt.shutdown()
        for s in servers:
            s.shutdown()
        for e in engines:
            e.stop()
    assert on_counters["breaker_bypass_forwards"] == 0
    p_off, p_on = _pct(lats["breaker_off"]), _pct(lats["breaker_on"])
    return {
        "num_requests": len(reqs),
        "injected_delay_ms": 250.0,
        "breaker_off": {"latency_ms": p_off},
        "breaker_on": {"latency_ms": p_on, "counters": on_counters},
        "routed_p99_ratio": _ratio(p_off["p99"], p_on["p99"]),
        "slow_replica_health_green": True,
        "probes_in_timed_window": int(probes_in_window),
        "timed_pass_compiles": int(timed_mints),
        "compile_storms": int(storms),
        "outputs_identical": True,
    }


def _measure_hedge_row(model, ref_gen, *, seq, vocab, slots, chunk,
                       requests, repeats, rng):
    """Hedged requests vs the stalled-primary tail: the same 2-replica
    fleet (first replica stalled 300 ms per request via ``net.delay``,
    breakers OFF — hedging is the defense under test), routed through
    a hedging router (``hedge_after=50 ms``) vs a plain one, SHARED
    replicas, serial requests so each one honestly faces the
    least-loaded choice. Winners are token-identical to the solo
    references every pass (the hedging identity rule: greedy decode
    makes the hedge a replay, so whichever reply wins IS the answer),
    and the hedge ledger must balance at scrape:
    launched == wins + losers, no lost hedge threads."""
    from distkeras_tpu import faults
    from distkeras_tpu.serving import (
        FleetRouter,
        ServingClient,
        ServingEngine,
        ServingServer,
    )

    reqs = _make_short_uniform(requests, seq, vocab, rng)
    refs = _solo_refs(ref_gen, reqs)
    engines, servers = [], []
    routers = {}
    plan = faults.FaultPlan()
    lats = {"hedge_off": [], "hedge_on": []}
    timed_mints = 0
    try:
        for _ in range(2):
            eng = ServingEngine(
                model, num_slots=slots,
                queue_capacity=4 * len(reqs) + 8,
                prefill_chunk=chunk, prefix_cache=False,
            ).start()
            servers.append(ServingServer(eng).start())
            engines.append(eng)
        slow_port = int(servers[0].port)
        for srv in servers:  # warm each replica directly, seam disarmed
            for _ in range(2):
                _drive_waves(srv.port, reqs, wave=2 * slots)
        for eng in engines:
            eng.compile_ledger.mark_warmed()
        routers["hedge_on"] = FleetRouter(
            endpoints=[(s.host, s.port) for s in servers],
            health_interval=0.1, affinity=False, hedge_after=0.05,
        ).start()
        routers["hedge_off"] = FleetRouter(
            endpoints=[(s.host, s.port) for s in servers],
            health_interval=0.1, affinity=False,
        ).start()
        for rt in routers.values():
            for s in servers:
                assert rt.wait_in_rotation(
                    (s.host, s.port), timeout=60.0
                )
        plan.arm(
            "net.delay", action="delay", delay=0.3, times=None,
            when=lambda ctx: ctx.get("port") == slow_port,
        ).activate()
        for _ in range(repeats):
            for name in ("hedge_off", "hedge_on"):
                rt = routers[name]
                m0 = sum(e.compile_ledger.total for e in engines)
                lat = []
                with ServingClient(
                    "127.0.0.1", rt.port, timeout=600.0
                ) as c:
                    for i, (p, s) in enumerate(reqs):
                        ta = time.perf_counter()
                        out = c.generate(p, s)
                        lat.append((time.perf_counter() - ta) * 1e3)
                        assert np.array_equal(out, refs[i]), (
                            f"hedge A/B [{name}] req {i}: != solo")
                timed_mints += (
                    sum(e.compile_ledger.total for e in engines) - m0
                )
                lats[name].append(lat)
        hedge_counters = {
            k: int(routers["hedge_on"].counters[k])
            for k in ("hedges_launched", "hedge_wins", "hedge_losers")
        }
        storms = sum(e.compile_ledger.storms for e in engines)
    finally:
        plan.deactivate()
        for rt in routers.values():
            rt.shutdown()
        for s in servers:
            s.shutdown()
        for e in engines:
            e.stop()
    assert hedge_counters["hedges_launched"] >= 1, hedge_counters
    assert hedge_counters["hedges_launched"] == (
        hedge_counters["hedge_wins"] + hedge_counters["hedge_losers"]
    ), hedge_counters
    p_off, p_on = _pct(lats["hedge_off"]), _pct(lats["hedge_on"])
    return {
        "num_requests": len(reqs),
        "injected_delay_ms": 300.0,
        "hedge_after_ms": 50.0,
        "hedge_off": {"latency_ms": p_off},
        "hedge_on": {"latency_ms": p_on, "counters": hedge_counters},
        "p99_ratio": _ratio(p_off["p99"], p_on["p99"]),
        "hedges_balanced": True,
        "timed_pass_compiles": int(timed_mints),
        "compile_storms": int(storms),
        "outputs_identical": True,
    }


def _measure_resilience_block(model, ref_gen, *, seq, vocab, slots,
                              chunk, requests, repeats, rng):
    """Overload defense & gray-failure resilience: three A/B rows.

    - ``storm``: adaptive load shedding under a 5x priority-0 storm —
      shedding-on goodput (interactive tokens delivered per second)
      vs shedding-off, exact shed/refusal pairing, honest retry
      hints, zero untyped errors (committed goodput floor in
      ``check_bench --kind resilience``);
    - ``gray``: a health-green replica stalling every data-path
      request — breaker-armed routing vs plain, routed p99 recovery
      with zero probes inside timed windows (committed recovery
      floor);
    - ``hedge``: a stalled primary vs tail-latency hedging — the
      hedge ledger balanced, winners token-identical (committed as
      measured plus the ledger invariants).

    Every pass identity-asserted, zero compiles inside timed windows
    across all three rows."""
    repeats = max(1, min(int(repeats), 3))
    block = {"rows": {}}
    block["rows"]["storm"] = _measure_storm_row(
        model, ref_gen, seq=seq, vocab=vocab, slots=slots, chunk=chunk,
        requests=requests, repeats=repeats, rng=rng,
    )
    print(json.dumps({"resilience_storm": {
        "goodput_ratio": block["rows"]["storm"]["goodput_ratio"],
        "hi_p99_improvement": block["rows"]["storm"][
            "hi_p99_improvement"],
    }}), flush=True)
    block["rows"]["gray"] = _measure_gray_row(
        model, ref_gen, seq=seq, vocab=vocab, slots=slots, chunk=chunk,
        requests=requests, repeats=repeats, rng=rng,
    )
    print(json.dumps({"resilience_gray": {
        "routed_p99_ratio": block["rows"]["gray"]["routed_p99_ratio"],
    }}), flush=True)
    block["rows"]["hedge"] = _measure_hedge_row(
        model, ref_gen, seq=seq, vocab=vocab, slots=slots, chunk=chunk,
        requests=requests, repeats=repeats, rng=rng,
    )
    print(json.dumps({"resilience_hedge": {
        "p99_ratio": block["rows"]["hedge"]["p99_ratio"],
        "hedges_launched": block["rows"]["hedge"]["hedge_on"][
            "counters"]["hedges_launched"],
    }}), flush=True)
    block["timed_pass_compiles"] = sum(
        r["timed_pass_compiles"] for r in block["rows"].values()
    )
    block["compile_storms"] = sum(
        r["compile_storms"] for r in block["rows"].values()
    )
    block["outputs_identical"] = True
    return block


def _measure_serial(model, reqs, *, arrivals=None, repeats=1):
    """1 slot + PR 1 config = serve-one-at-a-time through identical
    code (the PR 1 continuity ratio)."""
    eng = _engine(model, reqs, slots=1, prefill_chunk=None,
                  prefix_cache=False)
    try:
        _drive(eng, reqs, arrivals=arrivals)
        runs, outs = [], []
        for _ in range(repeats):
            _reset(eng, None)
            runs.append(_timed_pass(eng, reqs, arrivals, outs))
    finally:
        eng.stop()
    return _side(runs, False)


def _ratio(a, b):
    return round(a / max(b, 1e-9), 2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the CI harness test")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--chunk", type=int, default=None,
                    help="prefill token budget per scheduler iteration "
                         "(default seq/4)")
    ap.add_argument("--gap-ms", type=float, default=None,
                    help="mean request inter-arrival gap (exponential; "
                         "default per tier)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed passes per side, per-request samples "
                         "pooled (1-core scheduling noise); --smoke "
                         "forces 1")
    ap.add_argument("--tracing-only", action="store_true",
                    help="run ONLY the tracing-overhead A/B and merge "
                         "the row into the existing BENCH_SERVING.json "
                         "(the committed artifact keeps its measured "
                         "workload numbers)")
    ap.add_argument("--recorder-only", action="store_true",
                    help="run ONLY the flight-recorder overhead A/B "
                         "and merge the row into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--obs-only", action="store_true",
                    help="run ONLY the metrics-history overhead A/B "
                         "(history-on vs history-off, plus the "
                         "zero-compiles-in-timed-passes invariant and "
                         "the timeseries/burn digest proof) and merge "
                         "the block into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--paged-only", action="store_true",
                    help="run ONLY the paged-vs-dense KV-cache A/B "
                         "and merge the block into the existing "
                         "BENCH_SERVING.json")
    ap.add_argument("--sampling-only", action="store_true",
                    help="run ONLY the sampling block (sampled-vs-"
                         "greedy overhead A/B + n=4-via-fork vs 4 "
                         "independent admissions) and merge it into "
                         "the existing BENCH_SERVING.json")
    ap.add_argument("--qos-only", action="store_true",
                    help="run ONLY the multi-tenant QoS block (FIFO "
                         "vs QoS under a two-tenant burst + the "
                         "swap-thrash adversarial row) and merge it "
                         "into the existing BENCH_SERVING.json")
    ap.add_argument("--overlap-only", action="store_true",
                    help="run ONLY the zero-bubble decode block "
                         "(overlapped vs sequential scheduler loop "
                         "across decode-heavy / short-uniform / "
                         "sampled / preempt traffic, every pass "
                         "identity-asserted) and merge it into the "
                         "existing BENCH_SERVING.json")
    ap.add_argument("--resilience-only", action="store_true",
                    help="run ONLY the overload-defense block (storm "
                         "shedding goodput A/B, gray-failure breaker "
                         "A/B, hedged-request tail A/B) and merge it "
                         "into the existing BENCH_SERVING.json")
    ap.add_argument("--disagg-only", action="store_true",
                    help="run ONLY the disaggregated prefill/decode "
                         "block (1 prefill + 1 decode worker vs 2 "
                         "unified replicas on the interactive trace "
                         "+ the short-uniform adversarial row) and "
                         "merge it into the existing "
                         "BENCH_SERVING.json")
    args = ap.parse_args()

    platform = setup_backend(cpu=args.cpu or args.smoke)

    import jax

    from distkeras_tpu.models.zoo import transformer_lm
    from distkeras_tpu.predictors import CachedSequenceGenerator
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(platform=platform)
    # CPU tier shrinks vocab/width until the per-step cost is dispatch-
    # bound rather than FLOP-bound — the regime a real chip's decode
    # step lives in (memory-bound: a batch-8 step costs ~a batch-1
    # step), so the CPU deltas measure SCHEDULING, not a 1-core MXU
    # stand-in grinding the matmul FLOPs
    # the CPU tier needs seq long enough that a full prefill costs
    # MULTIPLE decode-step times — that cost is the stall chunked
    # prefill exists to bound; at short seq a prefill is one cheap
    # dispatch and the A/B would measure pure chunking overhead
    if args.smoke:
        seq, d_model, depth, heads, vocab = 32, 16, 1, 2, 61
        args.slots = min(args.slots, 2)
        args.requests = min(args.requests, 6)
        args.repeats = 1
        gap_ms = 1.0
    elif platform == "cpu":
        seq, d_model, depth, heads, vocab = 256, 64, 2, 4, 512
        gap_ms = 3.0
    else:
        seq, d_model, depth, heads, vocab = 512, 512, 8, 8, 8192
        gap_ms = 2.0
    if args.gap_ms is not None:
        gap_ms = args.gap_ms
    chunk = args.chunk if args.chunk is not None else max(8, seq // 4)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})", flush=True)

    model = transformer_lm(
        vocab_size=vocab, seq_len=seq, d_model=d_model, num_heads=heads,
        depth=depth, seed=0,
    )
    ref_gen = CachedSequenceGenerator(model)
    rng = np.random.default_rng(0)
    header = rng.integers(0, vocab, seq // 2).astype(np.int32)
    headers = [header, rng.integers(0, vocab, seq // 4).astype(np.int32)]
    workloads = {
        # (timed requests, prefix-store priming requests).
        # production_mix is the adjudicating A/B; mixed_long isolates
        # chunking + the store's cold-insert overhead (no request ever
        # hits — the honesty row); prefix_heavy is the reuse ceiling.
        # Priming seeds ONLY the shared headers (fresh suffixes), so
        # timed hits come from shared structure, never replayed prompts.
        "production_mix": (
            _make_production_mix(args.requests, seq, vocab, rng, headers),
            [_make_prefix_heavy(1, seq, vocab, rng, h)[0]
             for h in headers],
        ),
        "mixed_long": (
            _make_mixed_long(args.requests, seq, vocab, rng),
            None,
        ),
        "prefix_heavy": (
            _make_prefix_heavy(args.requests, seq, vocab, rng, header),
            _make_prefix_heavy(1, seq, vocab, rng, header),
        ),
    }

    if args.paged_only:
        # merge-mode sibling of --tracing-only / --recorder-only:
        # measure just the paged-vs-dense block into the committed
        # record, leaving the other workload numbers as measured
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        record["paged"] = _measure_paged_block(
            model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
            chunk=chunk, requests=args.requests, gap_ms=gap_ms,
            repeats=args.repeats, rng=rng, header=header,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"paged": {
            n: w["tokens_per_sec_ratio"]
            for n, w in record["paged"]["workloads"].items()
        }}))
        return

    if args.overlap_only:
        # merge-mode sibling of --qos-only: measure just the
        # zero-bubble decode block into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        record["overlap"] = _measure_overlap_block(
            model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
            chunk=chunk, requests=args.requests, repeats=args.repeats,
            rng=np.random.default_rng(170),
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"overlap": {
            n: {
                "tokens_per_sec_ratio": r["tokens_per_sec_ratio"],
                "bubble_reduction": r["bubble_reduction"],
            }
            for n, r in record["overlap"]["rows"].items()
        }}))
        return

    if args.disagg_only:
        # merge-mode sibling of --qos-only: measure just the disagg
        # block into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        record["disagg"] = _measure_disagg_block(
            model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
            chunk=chunk, requests=args.requests, repeats=args.repeats,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"disagg": {
            n: {
                "inter_token_p99_ratio": sc["inter_token_p99_ratio"],
                "tokens_per_sec_ratio": sc["tokens_per_sec_ratio"],
            }
            for n, sc in record["disagg"]["scenarios"].items()
        }}))
        return

    if args.resilience_only:
        # merge-mode sibling of --disagg-only: measure just the
        # overload-defense block into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        record["resilience"] = _measure_resilience_block(
            model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
            chunk=chunk, requests=args.requests, repeats=args.repeats,
            rng=np.random.default_rng(180),
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        rows = record["resilience"]["rows"]
        print(json.dumps({"resilience": {
            "storm_goodput_ratio": rows["storm"]["goodput_ratio"],
            "gray_routed_p99_ratio": rows["gray"]["routed_p99_ratio"],
            "hedge_p99_ratio": rows["hedge"]["p99_ratio"],
        }}))
        return

    if args.qos_only:
        # merge-mode sibling of --paged-only: measure just the QoS
        # block into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        record["qos"] = _measure_qos_block(
            model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
            chunk=chunk, requests=args.requests, repeats=args.repeats,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"qos": {
            "hi_p99_speedup": record["qos"]["scenarios"][
                "two_tenant_burst"]["hi_p99_speedup"],
            "swap_thrash_ratio": record["qos"]["scenarios"][
                "swap_thrash"]["tokens_per_sec_ratio"],
        }}))
        return

    if args.sampling_only:
        # merge-mode sibling of --paged-only: measure just the
        # sampling block into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        timed, _ = workloads["production_mix"]
        refs = _solo_refs(ref_gen, timed)
        arrivals = np.cumsum(rng.exponential(gap_ms / 1e3, len(timed)))
        record["sampling"] = _measure_sampling_block(
            model, timed, refs, slots=args.slots, chunk=chunk,
            arrivals=arrivals, repeats=args.repeats, rng=rng,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"sampling": {
            "sampled_vs_greedy": record["sampling"][
                "sampled_vs_greedy"]["tokens_per_sec_ratio"],
            "n4_fork_vs_independent": record["sampling"]["n4_fork"][
                "fork_vs_independent"],
        }}))
        return

    if args.obs_only:
        # merge-mode sibling of --recorder-only: measure just the
        # metrics-history A/B into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        timed, _ = workloads["production_mix"]
        refs = _solo_refs(ref_gen, timed)
        arrivals = np.cumsum(rng.exponential(gap_ms / 1e3, len(timed)))
        record["obs"] = _measure_obs(
            model, timed, refs, slots=args.slots, chunk=chunk,
            arrivals=arrivals, repeats=args.repeats,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"obs": {
            "history_vs_off": record["obs"]["history_vs_off"],
            "timed_pass_compiles": record["obs"][
                "timed_pass_compiles"],
        }}))
        return

    if args.recorder_only:
        # merge-mode sibling of --tracing-only: measure just the
        # recorder A/B into the committed record
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        timed, _ = workloads["production_mix"]
        refs = _solo_refs(ref_gen, timed)
        arrivals = np.cumsum(rng.exponential(gap_ms / 1e3, len(timed)))
        record["recorder_overhead"] = _measure_recorder(
            model, timed, refs, slots=args.slots, chunk=chunk,
            arrivals=arrivals, repeats=args.repeats,
        )
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps(
            {"recorder_overhead": record["recorder_overhead"]}
        ))
        return

    if args.tracing_only:
        # merge-mode: measure just the tracing A/B (+ the artifact
        # well-formedness block) into the committed record, leaving
        # the committed workload numbers as measured
        with open("BENCH_SERVING.json") as f:
            record = json.load(f)
        timed, _ = workloads["production_mix"]
        refs = _solo_refs(ref_gen, timed)
        arrivals = np.cumsum(rng.exponential(gap_ms / 1e3, len(timed)))
        overhead, obsv = _measure_tracing(
            model, timed, refs, slots=args.slots, chunk=chunk,
            arrivals=arrivals, repeats=args.repeats,
        )
        record["tracing_overhead"] = overhead
        record["observability"] = obsv
        with open("BENCH_SERVING.json", "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"tracing_overhead": overhead}))
        return

    record = {
        "metric": "serving_tokens_per_sec",
        "unit": "tokens/sec",
        "platform": platform,
        "device_kind": dev.device_kind,
        "model": f"transformer_lm d{d_model} L{depth} seq{seq}",
        "slots": args.slots,
        "prefill_chunk": chunk,
        "workloads": {},
    }
    record["arrival_gap_ms"] = gap_ms
    record["repeats_per_side"] = args.repeats
    arrival_sched = {}
    refs_by_wl = {}
    for name, (timed, prime) in workloads.items():
        refs = refs_by_wl[name] = _solo_refs(ref_gen, timed)
        # one deterministic Poisson-ish arrival schedule per workload,
        # identical for every side of the A/B
        arrivals = arrival_sched[name] = np.cumsum(
            rng.exponential(gap_ms / 1e3, len(timed))
        )
        base, opt, base_out, opt_out = _measure_ab(
            model, timed, slots=args.slots, chunk=chunk, prime=prime,
            arrivals=arrivals, repeats=args.repeats,
        )
        for i, (a, b, r) in enumerate(zip(base_out, opt_out, refs)):
            assert np.array_equal(a, r), f"{name} req {i}: baseline != solo"
            assert np.array_equal(b, r), f"{name} req {i}: chunked+cached != solo"
        record["workloads"][name] = {
            "num_requests": len(timed),
            "prompt_lens": [int(p.size) for p, _ in timed],
            "decode_steps": [int(s) for _, s in timed],
            "baseline": base,
            "chunked_cached": opt,
            "ttft_p99_speedup": _ratio(
                base["ttft_ms"]["p99"], opt["ttft_ms"]["p99"]
            ),
            "ttft_p50_speedup": _ratio(
                base["ttft_ms"]["p50"], opt["ttft_ms"]["p50"]
            ),
            "latency_p99_speedup": _ratio(
                base["latency_ms"]["p99"], opt["latency_ms"]["p99"]
            ),
            "tokens_per_sec_ratio": _ratio(
                opt["tokens_per_sec"], base["tokens_per_sec"]
            ),
            "outputs_identical": True,
        }
        print(json.dumps({name: {
            k: record["workloads"][name][k]
            for k in ("ttft_p99_speedup", "latency_p99_speedup",
                      "tokens_per_sec_ratio")
        }}), flush=True)

    # PR 1 continuity: continuous batching vs serve-one-at-a-time
    # (1 slot degenerates to serial through identical code)
    timed, _ = workloads["mixed_long"]
    serial = _measure_serial(
        model, timed, arrivals=arrival_sched["mixed_long"],
        repeats=args.repeats,
    )
    cont = record["workloads"]["mixed_long"]["baseline"]
    record["continuous_vs_serial"] = {
        "continuous_tokens_per_sec": cont["tokens_per_sec"],
        "serial_tokens_per_sec": serial["tokens_per_sec"],
        "speedup": _ratio(
            cont["tokens_per_sec"], serial["tokens_per_sec"]
        ),
    }
    record["value"] = record["workloads"]["production_mix"][
        "chunked_cached"
    ]["tokens_per_sec"]

    # -- tracing overhead A/B (traced vs untraced, over real TCP) -----------
    timed, _ = workloads["production_mix"]
    overhead, obsv = _measure_tracing(
        model, timed, refs_by_wl["production_mix"],
        slots=args.slots, chunk=chunk,
        arrivals=arrival_sched["production_mix"], repeats=args.repeats,
    )
    record["tracing_overhead"] = overhead
    record["observability"] = obsv
    print(json.dumps({"tracing_overhead": {
        "traced_vs_untraced": overhead["traced_vs_untraced"],
    }}), flush=True)

    # -- flight-recorder overhead A/B (always-on black box vs off) ----------
    timed, _ = workloads["production_mix"]
    record["recorder_overhead"] = _measure_recorder(
        model, timed, refs_by_wl["production_mix"],
        slots=args.slots, chunk=chunk,
        arrivals=arrival_sched["production_mix"], repeats=args.repeats,
    )
    print(json.dumps({"recorder_overhead": {
        "recorder_vs_off": record["recorder_overhead"][
            "recorder_vs_off"
        ],
    }}), flush=True)

    # -- metrics-history overhead A/B (time-series ring on vs off) ----------
    timed, _ = workloads["production_mix"]
    record["obs"] = _measure_obs(
        model, timed, refs_by_wl["production_mix"],
        slots=args.slots, chunk=chunk,
        arrivals=arrival_sched["production_mix"], repeats=args.repeats,
    )
    print(json.dumps({"obs": {
        "history_vs_off": record["obs"]["history_vs_off"],
        "timed_pass_compiles": record["obs"]["timed_pass_compiles"],
    }}), flush=True)

    # -- zero-bubble decode A/B (overlapped vs sequential loop) -------------
    # dedicated rng: the downstream blocks (paged, sampling, qos, ...)
    # replay the SAME shared-stream draws their committed numbers were
    # measured with — consuming from ``rng`` here would silently deal
    # every later workload a different hand; the fixed seed also makes
    # the overlap workloads identical between --overlap-only and the
    # full run
    record["overlap"] = _measure_overlap_block(
        model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
        chunk=chunk, requests=args.requests, repeats=args.repeats,
        rng=np.random.default_rng(170),
    )

    # -- paged-vs-dense KV cache A/B (equal byte budget) --------------------
    record["paged"] = _measure_paged_block(
        model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
        chunk=chunk, requests=args.requests, gap_ms=gap_ms,
        repeats=args.repeats, rng=rng, header=header,
    )

    # -- sampling block (sampled-vs-greedy overhead + n=4 via fork) ---------
    timed, _ = workloads["production_mix"]
    record["sampling"] = _measure_sampling_block(
        model, timed, refs_by_wl["production_mix"],
        slots=args.slots, chunk=chunk,
        arrivals=arrival_sched["production_mix"], repeats=args.repeats,
        rng=rng,
    )
    print(json.dumps({"sampling": {
        "sampled_vs_greedy": record["sampling"]["sampled_vs_greedy"][
            "tokens_per_sec_ratio"],
        "n4_fork_vs_independent": record["sampling"]["n4_fork"][
            "fork_vs_independent"],
    }}), flush=True)

    # -- multi-tenant QoS A/B (FIFO vs priorities + preemption) -------------
    record["qos"] = _measure_qos_block(
        model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
        chunk=chunk, requests=args.requests, repeats=args.repeats,
    )

    # -- disaggregated prefill/decode A/B (role split vs unified) -----------
    record["disagg"] = _measure_disagg_block(
        model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
        chunk=chunk, requests=args.requests, repeats=args.repeats,
    )

    # -- overload defense & gray-failure resilience A/B ---------------------
    # dedicated rng (the overlap-block precedent): the resilience rows
    # draw the same hand in --resilience-only and the full run
    record["resilience"] = _measure_resilience_block(
        model, ref_gen, seq=seq, vocab=vocab, slots=args.slots,
        chunk=chunk, requests=args.requests, repeats=args.repeats,
        rng=np.random.default_rng(180),
    )

    # -- speculative decoding A/B (prompt-lookup drafter) -------------------
    # Speculation pays off only when the model's continuation repeats
    # structure the drafter can find, so this A/B runs on a successor-
    # trained LM whose vocabulary is SMALLER than its prompts (counting
    # wraps => the sequence repeats itself): spec_repetitive is the
    # claimed win, spec_incompressible (random prompts, short budgets)
    # states what the drafter + verify machinery costs when it cannot
    # propose. Both sides are the full chunked+cached engine; only
    # speculative="ngram" differs.
    draft_k = 4
    if args.smoke:
        spec_model, spec_vocab, spec_seq = model, vocab, seq
    else:
        from distkeras_tpu import SingleTrainer
        from distkeras_tpu.data.dataset import Dataset

        spec_vocab, spec_seq = 32, min(128, seq)
        spec_model = transformer_lm(
            vocab_size=spec_vocab, seq_len=spec_seq, d_model=d_model,
            num_heads=heads, depth=depth, seed=0,
        )
        srng = np.random.default_rng(1)
        starts = srng.integers(0, spec_vocab, 512)
        xs = (
            (starts[:, None] + np.arange(spec_seq)[None, :]) % spec_vocab
        ).astype(np.int32)
        spec_model = SingleTrainer(
            spec_model, "adam", loss="next_token_crossentropy",
            learning_rate=2e-3, batch_size=32, num_epoch=3, seed=0,
        ).train(Dataset({"features": xs, "label": xs}))
    spec_gen = CachedSequenceGenerator(spec_model)
    record["speculative"] = {
        "drafter": "ngram",
        "draft_k": draft_k,
        "model": (
            f"transformer_lm d{d_model} L{depth} seq{spec_seq} "
            f"v{spec_vocab}" + ("" if args.smoke else " (trained)")
        ),
        "workloads": {},
    }
    spec_workloads = {
        "spec_repetitive": _make_spec_repetitive(
            args.requests, spec_seq, spec_vocab, rng
        ),
        "spec_incompressible": _make_spec_incompressible(
            args.requests, spec_seq, spec_vocab, rng
        ),
    }
    for name, timed in spec_workloads.items():
        refs = _solo_refs(spec_gen, timed)
        arrivals = np.cumsum(rng.exponential(gap_ms / 1e3, len(timed)))
        wl = _measure_spec_ab(
            spec_model, timed, refs, slots=args.slots, chunk=chunk,
            arrivals=arrivals, repeats=args.repeats, draft_k=draft_k,
        )
        record["speculative"]["workloads"][name] = wl
        print(json.dumps({name: {
            "tokens_per_sec_ratio": wl["tokens_per_sec_ratio"],
            "latency_p99_speedup": wl["latency_p99_speedup"],
            "tokens_per_window": wl["acceptance"][
                "mean_tokens_per_window"
            ],
        }}), flush=True)

    with open("BENCH_SERVING.json", "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps({
        "metric": record["metric"], "value": record["value"],
        "continuous_vs_serial": record["continuous_vs_serial"]["speedup"],
        "speculative_repetitive_ratio": record["speculative"][
            "workloads"]["spec_repetitive"]["tokens_per_sec_ratio"],
    }))


if __name__ == "__main__":
    main()
