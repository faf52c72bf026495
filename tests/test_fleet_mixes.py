"""Two replicas behind ``FleetRouter`` and one engine alone give the same
tokens.

``test_fleet.py`` pins the router on fakes and one hand-made batch on real
engines. These send the seeded mixes of ``serving_mixes.py`` through a
``FleetController`` of two real replicas, routed by prefix affinity and at
random, and through a single server: every reply is the request's solo
decode, so placement decides where a prefix is kept and never what comes
back. The routers' counters say how each one placed. No case reads a clock.
"""

from __future__ import annotations

import numpy as np
import pytest

import serving_mixes as mixes
from serving_mixes import (
    CHUNK, SEQ, SLOTS, TIMEOUT, VOCAB, assert_all_equal, generate_all,
    solo_refs,
)


def _workloads():
    rng = np.random.default_rng(0)
    headers = [rng.integers(0, VOCAB, SEQ // 2).astype(np.int32)
               for _ in range(2)]
    shared = [mixes.prefix_heavy(rng, headers[i % 2], 1)[0]
              for i in range(mixes.REQUESTS)]
    return {"prefix_heavy": (shared, [(h, 1) for h in headers]),
            "zero_reuse": (mixes.zero_reuse(rng), [])}


@pytest.fixture(scope="module")
def lm():
    return mixes.tiny_lm()


@pytest.fixture(scope="module")
def ref_gen(lm):
    from distkeras_tpu.predictors import CachedSequenceGenerator

    return CachedSequenceGenerator(lm)


@pytest.mark.parametrize("workload", ["prefix_heavy", "zero_reuse"])
def test_a_fleet_and_a_single_engine_give_the_same_tokens(
        lm, ref_gen, workload):
    """One server, a fleet routing by prefix affinity and a fleet routing to
    the least loaded: the headers go through the wire twice first (two-touch
    admission), then the mix. All three give the solo tokens. The affinity
    router placed by hash or spilled, the other never by hash, neither
    failed over; where the mix shares headers the affinity fleet's stores
    hit, where it shares nothing no store does."""
    from distkeras_tpu.serving import FleetController, ServingServer

    reqs, prime = _workloads()[workload]
    refs = solo_refs(ref_gen, reqs)
    kw = dict(num_slots=SLOTS, queue_capacity=2 * len(reqs) + 8,
              prefill_chunk=CHUNK, prefix_cache=True)
    single = mixes.engine(lm, prefix_cache=True)
    server = ServingServer(single).start()
    fleets = {}
    try:
        for name, affinity in (("affinity", True), ("random", False)):
            fleets[name] = FleetController(
                lm, replicas=2, router_kw=dict(
                    health_interval=0.2, affinity=affinity,
                    request_timeout=TIMEOUT), **kw).start()
        sides = {"single": ("127.0.0.1", server.port),
                 **{n: ctl.endpoint for n, ctl in fleets.items()}}
        for name, endpoint in sides.items():
            for _ in range(2):
                generate_all(endpoint, prime)
            assert_all_equal(
                generate_all(endpoint, reqs)[0], refs, f"{workload}/{name}")
        routed = {n: ctl.router.stats() for n, ctl in fleets.items()}
        hits = {n: sum(r.engine.prefix_store.stats()["hits"]
                       for r in ctl.replicas) for n, ctl in fleets.items()}
        hits["single"] = single.prefix_store.stats()["hits"]
    finally:
        server.shutdown()
        single.stop()
        for ctl in fleets.values():
            ctl.stop()
    for name, stats in routed.items():
        assert stats["forwards"] >= len(reqs), name
        assert stats["failovers"] == stats["fleet_overloaded"] == 0, name
    assert routed["random"]["affinity_routed"] == 0
    assert (routed["affinity"]["affinity_routed"]
            + routed["affinity"]["spilled"]) > 0
    if workload == "prefix_heavy":
        assert hits["affinity"] > 0 and hits["single"] > 0
    else:
        assert hits == {"single": 0, "affinity": 0, "random": 0}
