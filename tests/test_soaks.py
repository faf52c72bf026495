"""The three soak smokes: ``tools/soak_fleet.py`` (fleet and fabric) and
``tools/soak_training.py`` at their ``smoke=True`` scale, each held to its
own acceptance bar. The chaos harnesses themselves are pinned on the CPU,
so a drift surfaces as a red test and not as a dead soak run.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

pytestmark = pytest.mark.e2e


@pytest.mark.chaos
def test_soak_fleet_smoke():
    """``tools/soak_fleet.py --smoke`` runs end to end at tier-1 scale
    and meets its own acceptance bar: a REAL subprocess replica
    kill -9'd mid-stream under armed ``router.*``/``net.*``/
    ``stepper.step`` seams, zero hung clients, zero untyped errors,
    zero corrupt outputs, exact attempt accounting, the autoscaler
    reaping AND replacing the victim in one tick, and a
    checkpoint-triggered rollover of the full fleet. Mirrors the
    ``soak_serving``/
    ``soak_training`` treatment: the chaos harness itself is pinned on
    CPU so a drift surfaces as a red test, not a dead soak run."""
    import soak_fleet  # REPO/tools is on sys.path (module top)

    summary = soak_fleet.run_soak(seed=0, smoke=True)
    assert summary["hung"] == 0
    assert summary["untyped_errors"] == 0, summary["untyped_samples"]
    assert summary["corrupt_outputs"] == 0
    assert summary["accounting_exact"]
    # every attempt — completed, typed, or failed-over through the
    # kill -9 — assembled exactly one complete trace: "0 hung /
    # 0 untyped" is now instrumentation-verified, not just client-side
    assert summary["trace_attempts"] > 0
    assert summary["trace_incomplete"] == 0, (
        summary["trace_incomplete_samples"]
    )
    assert summary["control_errors"] == []
    assert summary["kill"]["in_flight_at_kill"]
    # the elastic control loop: the kill -9'd victim was reaped AND
    # replaced by the autoscaler's below_min row (same tick), so the
    # fleet is back at strength before the rollover
    assert summary["autoscale"]["reaps"] >= 1
    assert summary["autoscale"]["scale_ups"] >= 1
    assert summary["autoscale"]["errors"] == 0
    assert summary["autoscale"]["fleet_size_after_replace"] == 2
    # checkpoint-cadence publish -> continuous deploy: the PS commit
    # stream published ONE bundle (byte-identical to the boot bundle —
    # zero deltas) and the deployer rolled the FULL 2-replica fleet
    assert summary["deploy"]["published"] == 1
    assert summary["deploy"]["publish_errors"] == 0
    assert summary["deploy"]["bundle_identical_to_boot"] is True
    assert len(summary["rollover"]["replaced"]) == 2
    # replicas pre-warm + mark_warmed before READY: a compile storm
    # anywhere in the soak (including the autoscaler's replacement
    # joining under traffic) fails the bar
    assert summary["compile_storms"] == 0
    assert summary["completed"] > 0
    # the overload-defense ledgers: one replica is GRAY (net.delay
    # stalls, health green) and the router runs breakers + budget +
    # hedging — every launched hedge resolved win XOR loss, at least
    # one launched (the gray stalls and the kill window both exceed
    # the hedge delay), and no open-breaker replica ever received a
    # non-probe forward
    res = summary["resilience"]
    assert res["hedges"]["launched"] >= 1
    assert res["hedges"]["launched"] == (
        res["hedges"]["wins"] + res["hedges"]["losers"]
    )
    assert res["breakers"]["bypass_forwards"] == 0
    assert res["retry_budget"]["exhausted"] >= (
        res["retry_budget_exhausted"]
    )
    assert summary["ok"]


@pytest.mark.chaos
def test_soak_fabric_smoke():
    """``tools/soak_fleet.py --fabric --smoke`` runs end to end at
    tier-1 scale and meets its own acceptance bar: the prefix-digest
    holder kill -9'd with ``kv.fetch`` transfers in flight, then a
    reserved decode worker kill -9'd with direct pushes in flight —
    zero hung clients, zero untyped errors, zero divergent outputs in
    EITHER fabric direction, a healthy validated transfer proven
    before each kill, a corpse-naming hint degrading to token-
    identical recompute after it, and the router's pairing ledger
    balanced exactly (``peer_sends == peer_ok + peer_typed +
    peer_degraded``). Same treatment as the other soak smokes: the
    chaos harness itself is pinned on CPU so a drift surfaces as a
    red test, not a dead soak run."""
    import soak_fleet  # REPO/tools is on sys.path (module top)

    summary = soak_fleet.run_fabric_soak(seed=0, smoke=True)
    for phase in ("fetch", "push"):
        ph = summary[phase]
        assert ph["hung"] == 0, phase
        assert ph["untyped"] == 0, (phase, ph["untyped_samples"])
        assert ph["divergent"] == 0, phase
        assert ph["completed"] > 0, phase
        assert ph["control_errors"] == [], phase
    # healthy fetch before the kill, degrade-to-recompute after it —
    # with the probe's output token-identical to solo decode
    assert summary["fetch"]["peer"]["fetch_ok"] >= 1
    assert summary["fetch"]["peer"]["fetch_degraded"] >= 1
    assert summary["fetch"]["probe_identical"] is True
    # healthy direct push before the kill, relay fallback after it,
    # and every pairing resolved exactly once
    assert summary["push"]["router"]["peer_ok"] >= 1
    assert summary["push"]["router"]["peer_degraded"] >= 1
    assert summary["push"]["pairing_balanced"]
    assert summary["ok"]


@pytest.mark.chaos
def test_soak_training_smoke():
    """``tools/soak_training.py --smoke`` runs end to end at tier-1 scale
    and meets its own acceptance bar: zero hung workers, a real primary
    kill with standby promotion in BOTH phases, and exactly-once commit
    application across the failover (the ledger phase's bit-exact center,
    the training phase's run-vs-run commit-ledger match). Mirrors the
    ``soak_serving.py`` treatment: the chaos harness itself is pinned on
    CPU so a drift surfaces as a red test, not a dead soak run."""
    import soak_training  # REPO/tools is on sys.path (module top)

    summary = soak_training.run_soak(seed=0, smoke=True)
    ledger = summary["phases"]["ledger"]
    assert ledger["hung"] == 0
    assert ledger["errors"] == []
    assert ledger["promoted"] and ledger["promote_reason"] == "primary-lost"
    assert ledger["exactly_once"]
    assert ledger["applied_updates"] == ledger["expected_updates"]
    training = summary["phases"]["training"]
    assert training["faulted"]["hung"] is False
    assert training["faulted"]["error"] is None
    assert len(training["faulted"]["promotions"]) == 1
    assert training["faulted"]["failovers"] >= 1
    assert training["ledger_match"]
    assert summary["ok"]
