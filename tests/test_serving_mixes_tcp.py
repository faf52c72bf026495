"""The request mixes of ``serving_mixes.py`` over real TCP: a server in front
of one engine, a router in front of a prefill and a decode worker, and two
routers in front of a replica made slow.

What crosses the wire is held to what the engine alone gives: every reply
is its request's solo decode, traced or not, streamed or not, handed from a
prefill worker to a decode worker or not, shed around, routed off a slow
replica or hedged past it. Beside the tokens, each ledger that the path
keeps must balance. No case reads a clock or compares speeds; a wait is a
bounded number of short sleeps.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import serving_mixes as mixes
from serving_mixes import (
    SEQ, SLOTS, TIMEOUT, VOCAB, assert_all_equal, generate_all, in_threads,
    loadgen, requests_of, solo_refs,
)


@pytest.fixture(scope="module")
def lm():
    return mixes.tiny_lm()


@pytest.fixture(scope="module")
def ref_gen(lm):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    return CachedSequenceGenerator(lm)


def _client(port, **kw):
    from distkeras_tpu.serving import ServingClient

    return ServingClient("127.0.0.1", port, timeout=TIMEOUT, **kw)


def _generate_all(port, reqs, **kw):
    return generate_all(("127.0.0.1", port), reqs, **kw)


def _until(cond, what, tries=2000, nap=0.01):
    for _ in range(tries):
        if cond():
            return
        time.sleep(nap)
    raise AssertionError(f"{what}: not after {tries} naps")


def _servers(lm, n, **kw):
    from distkeras_tpu.serving import ServingServer

    engines = [mixes.engine(lm, **kw) for _ in range(n)]
    return engines, [ServingServer(e).start() for e in engines]


def _router(servers, **kw):
    from distkeras_tpu.serving import FleetRouter

    router = FleetRouter(
        endpoints=[(s.host, s.port) for s in servers],
        health_interval=0.1, **kw).start()
    for s in servers:
        assert router.wait_in_rotation((s.host, s.port), timeout=60.0)
    return router


def _shut(routers=(), servers=(), engines=()):
    for r in routers:
        r.shutdown()
    for s in servers:
        s.shutdown()
    for e in engines:
        e.stop()


# ------------------------------------------------- tracing and metrics


@pytest.fixture(scope="module")
def served(lm, ref_gen):
    reqs = mixes.the_three_mixes()["production_mix"][0]
    engines, servers = _servers(lm, 1, prefix_cache=True)
    yield servers[0].port, reqs, solo_refs(ref_gen, reqs)
    _shut(servers=servers, engines=engines)


def test_traced_and_untraced_requests_get_the_same_tokens(served):
    """With per-request tracing the reply carries a timeline: complete, one
    terminal span, client, server, queue and decode among its spans. The
    tokens are those of the untraced request and of the solo decode."""
    from distkeras_tpu.obs import timeline_complete

    port, reqs, refs = served
    plain, no_timeline = _generate_all(port, reqs)
    traced, timeline = _generate_all(port, reqs, trace=True)
    assert_all_equal(plain, refs, "untraced")
    assert_all_equal(traced, refs, "traced")
    assert no_timeline is None
    assert timeline_complete(timeline["spans"]), timeline
    assert {"client.request", "server.generate", "serving.queue",
            "serving.decode"} <= {s["name"] for s in timeline["spans"]}


def test_the_metrics_verb_and_its_prometheus_dump_agree(served):
    from distkeras_tpu.obs import parse_prometheus

    port, reqs, _ = served
    _generate_all(port, reqs)
    with _client(port) as c:
        samples = c.metrics()
        series = parse_prometheus(c.metrics(prometheus=True))
    assert len(samples) > 10
    # a histogram is one sample and a series a bucket
    assert len(series) > len(samples)


# --------------------------------- prefill and decode on two workers


def _interactive(n):
    return loadgen.make_trace(
        process="poisson", rate=max(40.0, 10000.0 / SEQ), n=n, vocab=VOCAB,
        seed=0, tenants=loadgen.interactive_tenants(SEQ))


def _short_chat(n):
    return loadgen.make_trace(
        process="poisson", rate=max(40.0, 10000.0 / SEQ), n=n, vocab=VOCAB,
        seed=1, tenants=[{
            "name": "chat", "weight": 1.0, "priority": 0, "stream": 1.0,
            "prompt_len": (4, max(6, SEQ // 10)),
            "steps": (max(4, SEQ // 16), max(6, SEQ // 6))}])


_DISAGG_TRACES = {
    "interactive": lambda: _interactive(3 * mixes.REQUESTS),
    "short_uniform_overhead": lambda: _short_chat(2 * mixes.REQUESTS),
}


@pytest.mark.parametrize("scenario", sorted(_DISAGG_TRACES))
def test_a_request_prefilled_on_one_worker_decodes_its_solo_tokens_on_another(
        lm, ref_gen, scenario):
    """A prefill worker and a decode worker behind a role-aware router:
    the K/V crosses the wire between them, streamed requests are delivered
    chunk by chunk, and every reply is the solo decode. The router's
    ledger balances: every transfer it sent came back ok or typed, every
    direct push ok, typed or degraded to the relay."""
    trace = _DISAGG_TRACES[scenario]()
    for ev in trace:
        ev["steps"] = max(1, min(int(ev["steps"]), SEQ - ev["prompt"].size))
    refs = solo_refs(ref_gen, requests_of(trace))
    streamed = [bool(ev.get("stream")) for ev in trace]
    assert any(streamed)
    engines, servers, router = [], [], None
    try:
        for role in ("prefill", "decode"):
            e, s = _servers(lm, 1, role=role)
            engines += e
            servers += s
        router = _router(servers)
        outs, chunks = [None] * len(trace), [None] * len(trace)

        def one(i):
            ev = trace[i]
            with _client(router.port) as c:
                if ev.get("stream"):
                    st = c.generate_stream(ev["prompt"], ev["steps"])
                    chunks[i] = [int(t) for chunk in st for t in chunk]
                    outs[i] = st.sequence
                else:
                    outs[i] = c.generate(ev["prompt"], ev["steps"])

        for _ in range(2):
            in_threads(one, len(trace))
            assert_all_equal(outs, refs, scenario)
            for ev, out, got in zip(trace, outs, chunks):
                if ev.get("stream"):
                    assert got == [int(t) for t in out[ev["prompt"].size:]]
        # the ledger balances at quiescence: a stream's last frame reaches
        # its client before the router's thread has counted the hop
        def balanced():
            s = router.stats()
            return (s["transfer_sends"]
                    == s["transfer_ok"] + s["transfer_typed"]
                    and s["peer_sends"] == s["peer_ok"] + s["peer_typed"]
                    + s["peer_degraded"])

        _until(balanced, "every transfer and every push resolved", tries=500)
        stats = router.stats()
    finally:
        _shut([router] if router else [], servers, engines)
    assert stats["disagg_routed"] > 0 and stats["transfer_sends"] > 0


# -------------------------------------------- overload and gray failure


def test_a_storm_is_shed_by_typed_refusals_and_the_rest_get_their_tokens(
        lm, ref_gen):
    """Five low-priority requests for each interactive one, all at once and
    without retries, against an engine that sheds and one that does not.
    The shedding side is told of the brownout through the operator's seam
    (rung 1 sheds priority 0 at the door and clamps nothing). On both
    sides no reply is untyped and every reply that came is the solo decode;
    on the shedding side every refusal is ``overloaded`` with a hint, as
    many as the gate counts, and the rung is let go afterwards."""
    from distkeras_tpu.serving import ServingError, ServingServer
    from distkeras_tpu.serving.resilience import RetryBudget

    rng = np.random.default_rng(180)
    plen = max(2, SEQ // 8)
    hi = [(rng.integers(0, VOCAB, plen).astype(np.int32), plen)
          for _ in range(mixes.REQUESTS)]
    storm = [(rng.integers(0, VOCAB, plen).astype(np.int32), max(2, SEQ // 16))
             for _ in range(5 * mixes.REQUESTS)]
    reqs = storm + hi
    refs = solo_refs(ref_gen, reqs)
    budget = RetryBudget(ratio=0.5, burst=float(len(storm)))
    for shed in (False, True):
        eng = mixes.engine(
            lm, queue_capacity=2 * len(reqs) + 8,
            shed=dict(burn_interval=0.05) if shed else False)
        srv = ServingServer(eng).start()
        gate = eng.shed_gate
        outs = [None] * len(reqs)
        tally = {"ok": 0, "overloaded": 0, "no_hint": 0, "other": 0}
        lock = threading.Lock()

        def one(i, srv=srv, outs=outs, tally=tally):
            stormy = i < len(storm)
            kw = (dict(retry=False, retry_budget=budget) if stormy else {})
            try:
                with _client(srv.port, **kw) as c:
                    outs[i] = c.generate(
                        *reqs[i], tenant="storm" if stormy else "interactive",
                        priority=0 if stormy else 2)
                key = "ok"
            except ServingError as e:
                assert stormy, f"an interactive request was refused: {e!r}"
                key = "overloaded" if e.code == "overloaded" else "other"
                if key == "overloaded" and e.retry_after is None:
                    key = "no_hint"
            with lock:
                tally[key] += 1

        try:
            if shed:
                steady, gate.burn_fn = gate.burn_fn, lambda: "burning"
                _until(lambda: gate.rung() >= 1, "the brownout's rung")
            in_threads(one, len(reqs))
            if shed:
                gate.burn_fn = steady
                _until(lambda: gate.rung() == 0, "the rung's release")
                sheds = gate.state()["sheds"]
        finally:
            srv.shutdown()
            eng.stop()
        assert tally["other"] == tally["no_hint"] == 0, (shed, tally)
        assert tally["ok"] + tally["overloaded"] == len(reqs)
        for out, ref in zip(outs, refs):
            assert out is None or np.array_equal(out, ref)
        assert all(out is not None for out in outs[len(storm):])
        if shed:
            assert tally["overloaded"] == sheds >= 1, (tally, sheds)
        else:
            assert gate is None and tally["overloaded"] == 0, tally
    assert budget.snapshot()["attempts"] >= len(storm)


@pytest.fixture
def one_slow_replica(lm, ref_gen):
    """Two replicas, warmed by the mix itself, the first of which then
    takes a quarter of a second longer on every request: its health stays
    green, which is what makes the failure gray."""
    from distkeras_tpu import faults

    reqs = mixes.short_uniform(np.random.default_rng(181))
    engines, servers = _servers(lm, 2, queue_capacity=4 * len(reqs) + 8)
    for srv in servers:
        _generate_all(srv.port, reqs, wave=2 * SLOTS)
    for eng in engines:
        eng.compile_ledger.mark_warmed()
    slow_port = int(servers[0].port)
    plan = faults.FaultPlan()
    plan.arm("net.delay", action="delay", delay=0.25, times=None,
             when=lambda ctx: ctx.get("port") == slow_port)
    routers = []

    def slow_row(router):
        return next(r for r in router.replicas()
                    if tuple(r["endpoint"]) == (servers[0].host, slow_port))

    try:
        yield reqs, solo_refs(ref_gen, reqs), servers, plan, routers, slow_row
        assert sum(e.compile_ledger.storms for e in engines) == 0
    finally:
        plan.deactivate()
        _shut(routers, servers, engines)


def test_a_breaker_routes_off_a_slow_replica_that_health_calls_green(
        one_slow_replica):
    """Through a router with breakers and a plain one, over the same two
    replicas: every reply is the solo decode on both (a gray replica delays,
    it never corrupts), both routers keep the slow replica in rotation, and
    once the breaker is open it stays open through the pass with no probe
    and no forward around it."""
    reqs, refs, servers, plan, routers, slow_row = one_slow_replica
    routers += [
        _router(servers, affinity=False, breaker=dict(
            open_secs=120.0, outlier_trips=2, outlier_factor=3.0,
            min_latency=0.02)),
        _router(servers, affinity=False),
    ]
    armed, plain = routers
    plan.activate()
    for _ in range(200):
        if slow_row(armed)["breaker"]["state"] == "open":
            break
        _generate_all(armed.port, reqs[: 2 * SLOTS])
    assert slow_row(armed)["breaker"]["state"] == "open"
    probes = armed.counters.get("breaker_probes", 0)
    for router in (plain, armed):
        outs, _ = _generate_all(router.port, reqs, wave=4)
        assert_all_equal(outs, refs, "behind a slow replica")
        assert slow_row(router)["state"] == "active"
    assert slow_row(armed)["breaker"]["state"] == "open"
    assert armed.counters.get("breaker_probes", 0) == probes
    assert armed.counters["breaker_opens"] >= 1
    assert armed.counters["breaker_bypass_forwards"] == 0


def test_a_hedge_past_a_slow_replica_wins_or_loses_and_is_counted_once(
        one_slow_replica):
    """Requests one after another through a router that hedges after 50 ms
    and a plain one: whichever reply wins is the solo decode (greedy, so a
    hedge is a replay), hedges were launched, and each ended as a win or as
    a loser."""
    reqs, refs, servers, plan, routers, _ = one_slow_replica
    routers += [_router(servers, affinity=False, hedge_after=0.05),
                _router(servers, affinity=False)]
    hedging, plain = routers
    plan.activate()
    for router in (plain, hedging):
        with _client(router.port) as c:
            outs = [c.generate(p, s) for p, s in reqs]
        assert_all_equal(outs, refs, "past a slow replica")
    counters = hedging.counters
    assert counters["hedges_launched"] >= 1
    _until(lambda: counters["hedges_launched"]
           == counters["hedge_wins"] + counters["hedge_losers"],
           "every hedge resolved", tries=200)
    assert plain.counters.get("hedges_launched", 0) == 0
