"""Measurement-harness pins (no TPU needed).

Chip time is budgeted, so a kwarg drifting out of `bench_mfu.measure`'s
signature or a render regression must be caught HERE, on CPU, not
discovered as a dead chip call (the r4 `--attention best` KeyError,
ADVICE r4 #1, is the cautionary tale).
"""

import inspect
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import benchmarks  # noqa: E402
import bench_fleet  # noqa: E402
import bench_mfu  # noqa: E402
import bench_serving  # noqa: E402
import check_bench  # noqa: E402
import mfu_attrib  # noqa: E402


MODES = {
    "default": {},
    "quick": {"quick": True},
    "long": {"long": True},
    "scale": {"scale": True},
    "best": {"best": True},
    "retire": {"retire": True},
    "frontier": {"frontier": True},
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_mode_configs_match_measure_signature(mode):
    accepted = set(inspect.signature(bench_mfu.measure).parameters) - {
        "platform"
    }
    configs = mfu_attrib.mode_configs(**MODES[mode])
    assert configs, mode
    labels = [label for label, _ in configs]
    assert len(labels) == len(set(labels)), f"duplicate labels in {mode}"
    for label, kw in configs:
        extra = set(kw) - accepted
        assert not extra, f"{mode}/{label}: measure() has no kwargs {extra}"


def test_best_mode_is_an_ab():
    """--best must keep a dense comparator next to the flash seq-4096 row —
    a lone flash number cannot claim a win."""
    labels = {label for label, _ in mfu_attrib.mode_configs(best=True)}
    assert "dense seq4096" in labels and "flash seq4096" in labels


@pytest.mark.e2e
def test_bench_serving_smoke_mode_end_to_end(tmp_path, monkeypatch):
    """``bench_serving.py --smoke`` runs tiny shapes end to end and the
    artifact carries the full A/B schema — per-request TTFT, latency
    percentiles, prefix-cache counters, and the output-identity flag.
    Before this pin the serving benchmark was the one harness entry
    with NO CPU exercise: a kwarg drift or schema regression would
    surface as a broken adjudication run, not a red test."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        sys, "argv", ["bench_serving.py", "--smoke", "--gap-ms", "0.5"]
    )
    bench_serving.main()
    rec = json.loads((tmp_path / "BENCH_SERVING.json").read_text())
    assert rec["metric"] == "serving_tokens_per_sec"
    assert rec["value"] > 0
    assert rec["continuous_vs_serial"]["speedup"] > 0
    assert set(rec["workloads"]) == {
        "production_mix", "mixed_long", "prefix_heavy"
    }
    for name, wl in rec["workloads"].items():
        assert wl["outputs_identical"] is True, name
        for key in ("ttft_p99_speedup", "ttft_p50_speedup",
                    "latency_p99_speedup", "tokens_per_sec_ratio"):
            assert wl[key] > 0, (name, key)
        for side in ("baseline", "chunked_cached"):
            s = wl[side]
            assert s["tokens_per_sec"] > 0, (name, side)
            for pct in ("mean", "p50", "p99"):
                assert s["ttft_ms"][pct] >= 0
                assert s["latency_ms"][pct] >= s["ttft_ms"][pct] * 0.99
            assert len(s["per_request"]) == wl["num_requests"]
            for pr in s["per_request"]:
                assert {"ttft_ms", "total_ms", "queue_ms",
                        "prefill_ms", "decode_ms"} <= set(pr)
        # the cached side reports its store; the baseline must not
        # pretend to have one
        assert "prefix_cache" in wl["chunked_cached"]
        assert "prefix_cache" not in wl["baseline"]
    # the prefix-heavy workload actually HITS (the priming contract)
    assert rec["workloads"]["prefix_heavy"]["chunked_cached"][
        "prefix_cache"]["hits"] > 0
    # tracing-overhead row + observability artifacts: the traced-vs-
    # untraced A/B ran over real TCP with identical outputs, the
    # sample timeline is complete (>= the acceptance span set), the
    # metrics snapshot is non-trivial, and the Prometheus dump parsed
    # (RATIO magnitudes are only meaningful in the full run — the
    # committed artifact carries the < 3% claim)
    tr = rec["tracing_overhead"]
    assert tr["untraced_tokens_per_sec"] > 0
    assert tr["traced_tokens_per_sec"] > 0
    assert tr["traced_vs_untraced"] > 0
    assert tr["outputs_identical"] is True
    obs = rec["observability"]
    assert obs["sample_trace_complete"] is True
    assert {"client.request", "server.generate", "serving.queue",
            "serving.decode"} <= set(obs["sample_trace_spans"])
    assert obs["metrics_samples"] > 10
    assert obs["prometheus_parses"] is True
    assert obs["prometheus_series"] > obs["metrics_samples"]
    # flight-recorder overhead row: the always-on black box vs off,
    # identical outputs, the ring actually taped scheduler events
    ro = rec["recorder_overhead"]
    assert ro["recorder_off_tokens_per_sec"] > 0
    assert ro["recorder_on_tokens_per_sec"] > 0
    assert ro["recorder_vs_off"] > 0
    assert ro["outputs_identical"] is True
    assert ro["events_recorded"] > 0
    # paged-vs-dense block (the --paged-only merge-mode artifact,
    # produced inline by the full run): all three workloads, both
    # sides, the pool ledger, and the identity flag — RATIO magnitudes
    # are only meaningful in the full run; the committed artifact
    # carries the >= 1.2x long-tail claim
    pg = rec["paged"]
    assert set(pg["workloads"]) == {
        "long_tail_mixed", "prefix_heavy", "short_uniform",
        "long_uniform",
    }
    for name, wl in pg["workloads"].items():
        assert wl["outputs_identical"] is True, name
        assert wl["tokens_per_sec_ratio"] > 0, name
        assert wl["paged_slots"] > wl["dense_slots"], name
        for side in ("dense", "paged"):
            assert wl[side]["tokens_per_sec"] > 0, (name, side)
        pp = wl["paged"]["paged"]
        assert pp["total_pages"] > 0, name
        assert pp["exhaustions"] == 0, name  # gating, not refusal
    # the paged prefix-heavy row actually SHARED device pages
    assert pg["workloads"]["prefix_heavy"]["paged"]["paged"][
        "device_prefix"]["hits"] > 0
    # sampling block: sampled-vs-greedy (greedy side solo-identical,
    # sampled side replay-identical across repeats) + n=4-via-fork
    # (completions token-identical to 4 independent derived-seed
    # admissions, forks actually happened) — RATIO magnitudes are only
    # meaningful in the full run; the committed artifact carries the
    # overhead and fork-economics claims
    sb = rec["sampling"]
    ab = sb["sampled_vs_greedy"]
    assert ab["outputs_identical"] is True
    assert ab["replay_identical"] is True
    assert ab["greedy_tokens_per_sec"] > 0
    assert ab["sampled_tokens_per_sec"] > 0
    assert ab["tokens_per_sec_ratio"] > 0
    nf = sb["n4_fork"]
    assert nf["n"] == 4
    assert nf["completions_identical"] is True
    assert nf["forked_slots"] >= 3 * nf["num_requests"]
    assert nf["fork_vs_independent"] > 0
    # multi-tenant QoS block: FIFO vs QoS at equal hardware over
    # loadgen traces — every request on BOTH sides token-identical to
    # its solo reference (on the QoS side that pin crosses the
    # preempt/resume boundary), preemption/resume pairing holds, and
    # the trace summary names the tenants. RATIO magnitudes are only
    # meaningful in the full run (a 2-slot smoke bank does not
    # saturate); the committed artifact carries the >= 1.3x claim.
    qb = rec["qos"]
    assert set(qb["scenarios"]) == {"two_tenant_burst", "swap_thrash"}
    for name, sc in qb["scenarios"].items():
        assert sc["outputs_identical"] is True, name
        assert sc["tokens_per_sec_ratio"] > 0, name
        qc = sc["qos_counters"]
        assert qc["preemptions"] == (
            qc["resumes"] + qc["swap_in_failures"]
            + qc["swapped_failed"]
        ), (name, qc)
        assert set(sc["trace"]["summary"]["tenants"]) == (
            {"batch", "interactive"} if name == "two_tenant_burst"
            else {"lo", "hi"}
        ), name
    assert qb["scenarios"]["two_tenant_burst"]["hi_p99_speedup"] > 0
    # disaggregated prefill/decode block: both scenarios ran the
    # two-hop path over real TCP with outputs identity-asserted across
    # the transfer, streamed requests measured TTFT at first DELIVERED
    # chunk, and the router's transfer ledger balanced (RATIO
    # magnitudes are only meaningful in the full run — the committed
    # artifact carries the inter-token isolation claim)
    dg = rec["disagg"]
    assert set(dg["scenarios"]) == {
        "interactive", "short_uniform_overhead"
    }
    for name, sc in dg["scenarios"].items():
        assert sc["outputs_identical"] is True, name
        assert sc["transfer_balanced"] is True, (name, sc["transfer"])
        assert sc["streamed_requests"] > 0, name
        assert sc["transfer"]["transfer_sends"] > 0, name
        for side in ("disagg", "unified"):
            assert sc[side]["tokens_per_sec"] > 0, (name, side)
            assert sc[side]["ttft_ms"]["p99"] > 0, (name, side)
            assert sc[side]["inter_token_ms"]["p99"] >= 0, (name, side)
    # observability (metrics-history) block: history-on vs off with
    # identical outputs, the timeseries digest + burn verdict computed
    # over the measured traffic, and — the r14/r16 standing gate —
    # ZERO XLA mints inside timed passes (RATIO magnitudes are only
    # meaningful in the full run; the committed artifact carries the
    # < 2% budget under check_bench --kind obs)
    ob = rec["obs"]
    assert ob["history_off_tokens_per_sec"] > 0
    assert ob["history_on_tokens_per_sec"] > 0
    assert ob["history_vs_off"] > 0
    assert ob["outputs_identical"] is True
    assert ob["timed_pass_compiles"] == 0
    assert ob["compile_storms"] == 0
    assert ob["timeseries"]["snapshots"] >= 2
    assert ob["timeseries"]["series_rows"] > 10
    assert ob["timeseries"]["burn_verdict"] == "ok"
    # zero-bubble decode block: overlapped vs sequential loop across
    # all four traffic shapes, every pass identity-asserted (sampled
    # = overlapped==sequential + seeded replay; preempt crosses the
    # preempt/resume boundary), both sides' bubble fractions read
    # from the one OverlapLedger, streamed chunk order pinned, and
    # zero compiles inside timed windows (RATIO/bubble magnitudes are
    # only meaningful in the full run — the committed artifact
    # carries the bubble-reduction floor under check_bench --kind
    # overlap)
    ovb = rec["overlap"]
    assert set(ovb["rows"]) == {
        "decode_heavy", "short_uniform", "sampled", "preempt"
    }
    for name, row in ovb["rows"].items():
        assert row["outputs_identical"] is True, name
        assert row["tokens_per_sec_ratio"] > 0, name
        assert row["timed_pass_compiles"] == 0, name
        assert row["compile_storms"] == 0, name
        for side in ("sequential", "overlapped"):
            assert row[f"{side}_tokens_per_sec"] > 0, (name, side)
            assert 0.0 <= row[f"{side}_bubble_fraction"] <= 1.0, (
                name, side)
    assert ovb["rows"]["decode_heavy"]["streamed_requests"] > 0
    assert ovb["rows"]["preempt"]["preemptions"].keys() == {
        "sequential", "overlapped"
    }
    assert ovb["timed_pass_compiles"] == 0
    assert ovb["compile_storms"] == 0
    # overload-defense block: storm shedding, gray-failure breaker,
    # and hedged-request A/Bs, every survivor identity-asserted, all
    # three pairing ledgers balanced (gate sheds == typed refusals,
    # hedges launched == wins + losers, zero breaker bypasses), the
    # slow replica health-GREEN on both routers, and zero compiles
    # inside timed windows (RATIO magnitudes are only meaningful in
    # the full run — the committed artifact carries the goodput and
    # p99-recovery floors under check_bench --kind resilience)
    rs = rec["resilience"]
    assert set(rs["rows"]) == {"storm", "gray", "hedge"}
    for name, row in rs["rows"].items():
        assert row["outputs_identical"] is True, name
        assert row["timed_pass_compiles"] == 0, name
        assert row["compile_storms"] == 0, name
    st = rs["rows"]["storm"]
    assert st["goodput_ratio"] > 0
    assert st["shed_pairing"]["exact"] is True, st["shed_pairing"]
    assert st["hints_honest"] is True
    assert st["shed_rung_released"] is True
    for side in ("shed_off", "shed_on"):
        oc = st[side]["storm_outcomes"]
        assert oc["untyped"] == 0, (side, oc)
        assert oc["typed_other"] == 0, (side, oc)
    assert st["retry_budget"]["attempts"] >= st["num_storm_requests"]
    gr = rs["rows"]["gray"]
    assert gr["routed_p99_ratio"] > 0
    assert gr["slow_replica_health_green"] is True
    assert gr["probes_in_timed_window"] == 0
    gc = gr["breaker_on"]["counters"]
    assert gc["breaker_opens"] >= 1
    assert gc["breaker_bypass_forwards"] == 0
    hd = rs["rows"]["hedge"]
    assert hd["p99_ratio"] > 0
    assert hd["hedges_balanced"] is True
    hc = hd["hedge_on"]["counters"]
    assert hc["hedges_launched"] >= 1
    assert hc["hedges_launched"] == (
        hc["hedge_wins"] + hc["hedge_losers"]
    ), hc
    assert rs["timed_pass_compiles"] == 0
    assert rs["compile_storms"] == 0
    # the regression gate: the fresh smoke ratios must land within the
    # stated band of the COMMITTED artifact (a perf collapse fails
    # tier-1 here instead of silently rotting the committed numbers)
    committed = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    violations = check_bench.compare_serving(rec, committed)
    assert violations == [], violations
    violations = check_bench.compare_disagg(rec, committed)
    assert violations == [], violations
    violations = check_bench.compare_obs(rec, committed)
    assert violations == [], violations
    violations = check_bench.compare_overlap(rec, committed)
    assert violations == [], violations
    violations = check_bench.compare_resilience(rec, committed)
    assert violations == [], violations
    # speculative A/B schema: both traffic shapes, both sides, the
    # acceptance ledger, and the identity flag (win/cost RATIOS are
    # only meaningful in the full trained-model run, not at smoke
    # scale — the committed artifact carries those)
    spec = rec["speculative"]
    assert spec["drafter"] == "ngram" and spec["draft_k"] >= 1
    assert set(spec["workloads"]) == {
        "spec_repetitive", "spec_incompressible"
    }
    for name, wl in spec["workloads"].items():
        assert wl["outputs_identical"] is True, name
        assert wl["tokens_per_sec_ratio"] > 0, name
        for side in ("baseline", "speculative"):
            assert wl[side]["tokens_per_sec"] > 0, (name, side)
        acc = wl["acceptance"]
        assert acc["windows"] + acc["fallback_steps"] > 0, name
        assert acc["mean_tokens_per_window"] >= 0, name
        assert (
            acc["drafted_tokens"]
            >= acc["accepted_draft_tokens"]
        ), name


@pytest.mark.e2e
def test_bench_decode_sharded_smoke_end_to_end(tmp_path, monkeypatch):
    """``bench_decode.py --sharded-only --smoke`` runs the tp1/tp2/tp4
    grid end to end on the 8-virtual-device CPU mesh and the artifact
    carries the committed schema: per-row tokens/sec + ratio, the
    per-pass identity flag, the equal-total-KV-bytes contract, the
    single-host caveat, and the mandatory adversarial small-model tp4
    row — then the fresh block must clear the ``check_bench`` decode
    gate against the committed artifact (ratio bands + floors), so a
    sharding collapse fails tier-1 instead of rotting the numbers."""
    import bench_decode

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        sys, "argv",
        ["bench_decode.py", "--sharded-only", "--smoke", "--cpu"],
    )
    bench_decode.main()
    rec = json.loads((tmp_path / "BENCH_DECODE.json").read_text())
    sh = rec["sharded"]
    assert sh["devices_available"] >= 4
    assert "single_host_caveat" in sh
    assert set(sh["rows"]) == {"tp1", "tp2", "tp4"}
    for name, row in sh["rows"].items():
        assert row["outputs_identical"] is True, name
        assert row["tokens_per_sec"] > 0, name
        assert row["ratio_vs_tp1"] > 0, name
        ways = int(name[2:])
        assert row["kv_shard_bytes"] * ways == sh["kv_bytes_total"], name
    adv = sh["adversarial_small_tp4"]
    assert adv["outputs_identical"] is True
    assert adv["ratio_vs_tp1"] > 0
    committed = json.loads(
        open(os.path.join(REPO, "BENCH_DECODE.json")).read()
    )
    violations = check_bench.compare_decode(rec, committed)
    assert violations == [], violations


def test_committed_bench_decode_sharded_block():
    """The COMMITTED sharded block carries THIS PR's claims honestly:
    every tp:N row token-identical to solo, equal total KV bytes
    across geometries, the single-host caveat stated, the ratios above
    their collapse floors, and the adversarial small-model tp4 row —
    where per-step collectives dominate and sharding LOSES — committed
    as measured."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_DECODE.json")).read()
    )
    # self-comparison exercises every invariant and the floors (the
    # floor values live in check_bench.COMMITTED_FLOORS — the one
    # source of truth; asserting literals here would silently drift)
    assert check_bench.compare_decode(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["decode"]) == {
        "sharded.rows.tp2.ratio_vs_tp1",
        "sharded.rows.tp4.ratio_vs_tp1",
        "sharded.adversarial_small_tp4.ratio_vs_tp1",
    }
    sh = rec["sharded"]
    adv = sh["adversarial_small_tp4"]
    assert adv["ratio_vs_tp1"] < 1.0  # it IS the honesty row on CPU
    # gate plumbing: a flipped identity flag or a dropped row is a
    # violation, not a silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["sharded"]["rows"]["tp2"]["outputs_identical"] = False
    assert any(
        "tp2" in v for v in check_bench.compare_decode(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["sharded"]["adversarial_small_tp4"]
    assert any(
        "adversarial" in v for v in check_bench.compare_decode(bad, rec)
    )


def _check_fleet_record(rec):
    """The BENCH_FLEET.json contract both the smoke artifact and the
    committed artifact must meet: three sides per workload (single /
    fleet_affinity / fleet_random), throughput + latency percentiles,
    prefix-cache ledgers with hit rates, router counters on the fleet
    sides, the single-core honesty caveat, and the identity flag."""
    assert rec["metric"] == "fleet_tokens_per_sec"
    assert rec["value"] > 0
    assert rec["replicas"] == 2
    assert "time-share" in rec["single_core_caveat"]
    assert set(rec["workloads"]) == {"prefix_heavy", "zero_reuse"}
    for name, wl in rec["workloads"].items():
        assert wl["outputs_identical"] is True, name
        assert wl["fleet_vs_single"] > 0, name
        for rate_key in ("affinity_hit_rate", "random_hit_rate"):
            assert 0.0 <= wl[rate_key] <= 1.0, (name, rate_key)
        for side in ("single", "fleet_affinity", "fleet_random"):
            s = wl[side]
            assert s["tokens_per_sec"] > 0, (name, side)
            for pct in ("mean", "p50", "p99"):
                assert s["latency_ms"][pct] > 0, (name, side, pct)
            pc = s["prefix_cache"]
            assert pc["hits"] + pc["misses"] >= 0, (name, side)
            assert 0.0 <= pc["hit_rate"] <= 1.0, (name, side)
            if side == "single":
                assert "router" not in s, name  # no router to report
                assert len(pc["entries_per_replica"]) == 1, name
            else:
                r = s["router"]
                # every timed request was forwarded, none dropped to
                # the fleet-level failure counters on a quiet bench
                assert r["forwards"] >= wl["num_requests"], (name, side)
                assert r["failovers"] == 0, (name, side)
                assert len(pc["entries_per_replica"]) == 2, name
        # the A/B is honest: the random side routed none by affinity,
        # the affinity side routed generates by hash (spill allowed)
        aff = wl["fleet_affinity"]["router"]
        rnd = wl["fleet_random"]["router"]
        assert rnd["affinity_routed"] == 0, name
        assert aff["affinity_routed"] + aff["spilled"] > 0, name
    # zero-reuse is the adversarial row: nothing to hit on either side
    zr = rec["workloads"]["zero_reuse"]
    assert zr["affinity_hit_rate"] == 0.0
    assert zr["random_hit_rate"] == 0.0


@pytest.mark.e2e
def test_bench_fleet_smoke_mode_end_to_end(tmp_path, monkeypatch):
    """``bench_fleet.py --smoke`` boots the full three-sided harness —
    one single server plus TWO 2-replica fleets over real TCP — on tiny
    shapes and writes an artifact carrying the committed schema. Same
    rationale as the serving pin: a kwarg drift or schema regression
    must surface as a red CPU test, not a broken adjudication run."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "argv", ["bench_fleet.py", "--smoke"])
    bench_fleet.main()
    rec = json.loads((tmp_path / "BENCH_FLEET.json").read_text())
    _check_fleet_record(rec)
    # the priming contract at any scale: the affinity side of the
    # prefix-heavy workload concentrates each header's KV and HITS
    assert rec["workloads"]["prefix_heavy"]["fleet_affinity"][
        "prefix_cache"]["hits"] > 0
    # observability artifacts: a traced generate THROUGH THE ROUTER
    # assembled a complete timeline with the router's routing span,
    # and the metrics verb aggregated per-replica-labeled samples
    obs = rec["observability"]
    assert obs["sample_trace_complete"] is True
    assert "router.route" in obs["sample_trace_spans"]
    assert len(obs["sample_trace_spans"]) >= 5
    assert "router" in obs["replica_labels"]
    assert len(obs["replica_labels"]) == 3  # router + 2 replicas
    assert obs["prometheus_parses"] is True
    # the fleet side of the regression gate (ratio bands + invariants
    # against the committed artifact)
    committed = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    violations = check_bench.compare_fleet(rec, committed)
    assert violations == [], violations


def test_committed_bench_serving_tracing_row():
    """The COMMITTED tracing-overhead row (the number PERF.md quotes)
    carries the claim: full per-request tracing costs < 3% tokens/sec
    on the interleaved TCP A/B, with outputs token-identical — and the
    committed observability block is well-formed. Regenerating the
    artifact with a worse number must fail here, not slip through."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    tr = rec["tracing_overhead"]
    assert tr["outputs_identical"] is True
    assert tr["traced_vs_untraced"] >= 0.97, tr
    obs = rec["observability"]
    assert obs["sample_trace_complete"] is True
    assert obs["prometheus_parses"] is True
    assert {"client.request", "server.generate",
            "serving.decode"} <= set(obs["sample_trace_spans"])
    # the committed flight-recorder row carries PR 8's claim: the
    # always-on black box costs < 2% tokens/sec, outputs identical
    ro = rec["recorder_overhead"]
    assert ro["outputs_identical"] is True
    assert ro["recorder_vs_off"] >= 0.98, ro
    assert ro["events_recorded"] > 0


def test_committed_bench_serving_paged_block():
    """The COMMITTED paged-vs-dense block carries THIS PR's capacity
    claim: at an EQUAL KV byte budget, the paged cache sustains
    >= 1.2x tokens/sec on high-load long-tail traffic (more concurrent
    slots in the same bytes), prefix-heavy does not regress, every
    admission path stayed token-identical, and the adversarial
    short-uniform row is COMMITTED (stated, whatever it cost) — plus
    the bench_decode page-fork row materially under the committed
    dense beam cost."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    pg = rec["paged"]
    for name, wl in pg["workloads"].items():
        assert wl["outputs_identical"] is True, name
    lt = pg["workloads"]["long_tail_mixed"]
    assert lt["tokens_per_sec_ratio"] >= 1.2, lt["tokens_per_sec_ratio"]
    assert lt["occupancy_ratio"] > 1.0  # the mechanism, not just the win
    assert pg["workloads"]["prefix_heavy"]["tokens_per_sec_ratio"] >= 0.95
    # the adversarial rows exist and are real measurements (committed
    # as measured, win or cost — no floor on honesty rows)
    assert pg["workloads"]["short_uniform"]["tokens_per_sec_ratio"] > 0
    assert pg["workloads"]["long_uniform"]["tokens_per_sec_ratio"] > 0
    # bench_decode: page-table forking prices beam/parallel sampling
    # materially under the committed dense beam gather cost
    dec = json.loads(
        open(os.path.join(REPO, "BENCH_DECODE.json")).read()
    )
    fork = dec["page_fork_parallel"]
    beam_cost = dec["beam_search"]["cost_vs_f32_cached"]
    assert fork["cost_vs_plain_cached_w4"] < beam_cost / 2, (
        fork, beam_cost
    )
    assert fork["fork_vs_dense_parallel"] >= 1.0, fork
    assert fork["cow_copies"] >= 1


def test_committed_bench_serving_sampling_block():
    """The COMMITTED sampling block carries THIS PR's claims: the
    temp+top-p sampled stream clears the stated CPU-tier floor vs the
    identical greedy stream (greedy side solo-identical, sampled side
    replay-exact; the cost is the XLA:CPU sort inside the nucleus
    transform — PERF.md r15 states the split), and n=4 completions
    via one prefill + CoW page forks at least match 4 independent
    admissions while producing token-identical completions (the fork
    prices only shared work — the samples themselves cannot move)."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    sb = rec["sampling"]
    ab = sb["sampled_vs_greedy"]
    assert ab["outputs_identical"] is True
    assert ab["replay_identical"] is True
    assert ab["tokens_per_sec_ratio"] >= 0.5, ab
    nf = sb["n4_fork"]
    assert nf["completions_identical"] is True
    assert nf["fork_vs_independent"] >= 1.0, nf
    assert nf["forked_slots"] >= 3 * nf["num_requests"]


def test_committed_bench_serving_qos_block():
    """The COMMITTED QoS block carries THIS PR's robustness claim:
    under a low-priority burst at equal hardware, priority admission
    + preemption-by-page-swap holds the high-priority tenant's p99
    >= 1.3x better than FIFO's, with every request token-identical to
    solo decode across the preempt/resume boundary and every swap-out
    paired with a resume (quiet bench: no typed failures). The
    swap-thrash adversarial row — uniform high load, both classes
    churning the swap path — is COMMITTED as measured (stated,
    whatever it cost), with real preemption traffic behind it."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    qb = rec["qos"]
    burst = qb["scenarios"]["two_tenant_burst"]
    assert burst["outputs_identical"] is True
    assert burst["hi_p99_speedup"] >= 1.3, burst["hi_p99_speedup"]
    qc = burst["qos_counters"]
    assert qc["preemptions"] >= 1
    assert qc["preemptions"] == qc["resumes"], qc
    # the win is attributable: the committed per-tenant percentiles
    # show WHO got faster and who paid
    assert burst["tenants"]["interactive"]["priority"] > (
        burst["tenants"]["batch"]["priority"]
    )
    thrash = qb["scenarios"]["swap_thrash"]
    assert thrash["outputs_identical"] is True
    assert thrash["tokens_per_sec_ratio"] > 0  # no floor on honesty rows
    assert thrash["qos_counters"]["preemptions"] >= 1  # it DID thrash


def test_committed_bench_serving_disagg_block():
    """The COMMITTED disagg block carries THIS PR's claims honestly:
    under the interactive trace's long-prompt arrivals the role split
    holds inter-token p99 at least the floored factor better than two
    unified replicas at equal hardware (decode iterations never share
    a device with prefill chunks), with every output token-identical
    across the wire transfer, TTFT measured at first DELIVERED chunk,
    the transfer ledger balanced, and the short-uniform adversarial
    row — where the transfer hop is pure overhead — committed as
    measured."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    # self-comparison exercises every invariant + the committed floors
    # (floor values live in check_bench.COMMITTED_FLOORS — the one
    # source of truth)
    assert check_bench.compare_disagg(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["disagg"]) == {
        "disagg.scenarios.interactive.inter_token_p99_ratio",
    }
    dg = rec["disagg"]
    inter = dg["scenarios"]["interactive"]
    assert inter["transfer"]["transfer_sends"] >= 1
    assert inter["streamed_requests"] > 0
    # the honest adversarial row exists and is a real measurement
    adv = dg["scenarios"]["short_uniform_overhead"]
    assert adv["tokens_per_sec_ratio"] > 0
    # gate plumbing: a flipped identity flag or broken pairing is a
    # violation, not a silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["disagg"]["scenarios"]["interactive"][
        "outputs_identical"] = False
    assert any(
        "interactive" in v for v in check_bench.compare_disagg(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["disagg"]["scenarios"]["interactive"][
        "transfer_balanced"] = False
    assert any(
        "pairing" in v for v in check_bench.compare_disagg(bad, rec)
    )


def test_committed_bench_serving_obs_block():
    """The COMMITTED obs block carries THIS PR's claims honestly: the
    metrics-history ring (periodic registry snapshots answering
    windowed rates/quantiles/trends and burn-rate verdicts) costs
    within the floored < 2% budget with outputs token-identical on
    both sides, the timeseries digest + burn verdict actually
    computed over the measured traffic, and the standing compile
    invariant holds — the committed timed passes contain ZERO XLA
    mints (the r14 "0.17x from mid-pass compiles" / r16 "~240 ms
    stall inside interactive p99" post-mortems as a permanent
    gate)."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    # self-comparison exercises every invariant + the committed floor
    # (floor values live in check_bench.COMMITTED_FLOORS — the one
    # source of truth)
    assert check_bench.compare_obs(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["obs"]) == {
        "obs.history_vs_off",
    }
    ob = rec["obs"]
    assert ob["timed_pass_compiles"] == 0
    assert ob["compile_storms"] == 0
    assert ob["timeseries"]["burn_verdict"] == "ok"
    # gate plumbing: a nonzero compile count or a flipped identity
    # flag is a violation, not a silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["obs"]["timed_pass_compiles"] = 3
    assert any(
        "mints landed inside" in v
        for v in check_bench.compare_obs(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["obs"]["outputs_identical"] = False
    assert any(
        "outputs not identical" in v
        for v in check_bench.compare_obs(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["obs"]
    assert any(
        "missing obs block" in v
        for v in check_bench.compare_obs(bad, rec)
    )


def test_committed_bench_serving_overlap_block():
    """The COMMITTED overlap block carries THIS PR's claims honestly:
    the overlapped loop's bubble reduction on the decode-heavy trace
    clears its committed floor, the host-work-light short_uniform
    honesty row is present as measured (no floor — there is little
    bubble to reclaim there), every row is identity-asserted with
    zero compiles inside timed windows, the decode_heavy trace
    exercised streamed delivery, and the committed preempt row
    actually preempted on the overlapped side (the deferred-
    preemption path demonstrably ran)."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    # self-comparison exercises every invariant and the floors (the
    # floor values live in check_bench.COMMITTED_FLOORS — the one
    # source of truth; asserting literals here would silently drift)
    assert check_bench.compare_overlap(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["overlap"]) == {
        "overlap.rows.decode_heavy.bubble_reduction",
        "overlap.rows.preempt.preemptions.overlapped",
    }
    ovb = rec["overlap"]
    assert ovb["timed_pass_compiles"] == 0
    assert ovb["compile_storms"] == 0
    # the claimed win actually reduced the bubble; the honesty row is
    # committed as measured, whatever it measured
    dh = ovb["rows"]["decode_heavy"]
    assert dh["bubble_reduction"] > 0
    assert dh["streamed_requests"] > 0
    assert "short_uniform" in ovb["rows"]
    assert ovb["rows"]["preempt"]["preemptions"]["overlapped"] >= 1
    # gate plumbing: a flipped identity flag, a dropped honesty row,
    # or a nonzero timed-pass compile count is a violation, not a
    # silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["overlap"]["rows"]["sampled"]["outputs_identical"] = False
    assert any(
        "sampled" in v and "identical" in v
        for v in check_bench.compare_overlap(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["overlap"]["rows"]["short_uniform"]
    assert any(
        "short_uniform" in v
        for v in check_bench.compare_overlap(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["overlap"]["rows"]["decode_heavy"]["timed_pass_compiles"] = 2
    assert any(
        "mints landed inside" in v
        for v in check_bench.compare_overlap(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["overlap"]
    assert any(
        "missing overlap block" in v
        for v in check_bench.compare_overlap(bad, rec)
    )


def test_committed_bench_serving_resilience_block():
    """The COMMITTED resilience block carries THIS PR's claims
    honestly: shedding-on goodput clears its >= 1.5x floor under the
    5x storm with the shed/refusal pairing exact and every refusal
    hinted, breaker-on routed p99 clears the >= 2x recovery floor
    (i.e. <= 0.5x breaker-off) with the slow replica health-GREEN on
    both sides and zero bypass forwards, the hedge ledger balances
    with at least one hedge launched, and zero XLA mints landed
    inside any timed window. Self-comparison exercises every
    invariant plus the committed floors — regenerating the artifact
    with a broken defense must fail here, not slip through."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_SERVING.json")).read()
    )
    assert check_bench.compare_resilience(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["resilience"]) == {
        "resilience.rows.storm.goodput_ratio",
        "resilience.rows.gray.routed_p99_ratio",
        "resilience.rows.hedge.hedge_on.counters.hedges_launched",
    }
    rs = rec["resilience"]
    assert rs["timed_pass_compiles"] == 0
    assert rs["compile_storms"] == 0
    st = rs["rows"]["storm"]
    assert st["storm_multiplier"] == 5
    assert st["shed_pairing"]["gate_sheds"] == (
        st["shed_pairing"]["typed_overloaded"]
    )
    gr = rs["rows"]["gray"]
    assert gr["breaker_on"]["counters"]["breaker_opens"] >= 1
    assert gr["probes_in_timed_window"] == 0
    hd = rs["rows"]["hedge"]
    hc = hd["hedge_on"]["counters"]
    assert hc["hedge_wins"] + hc["hedge_losers"] == (
        hc["hedges_launched"]
    )
    # gate plumbing: a broken pairing ledger, a health-red replica, an
    # unbalanced hedge ledger, or a timed-pass mint is a violation,
    # not a silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["resilience"]["rows"]["storm"]["shed_pairing"]["exact"] = False
    assert any(
        "pairing" in v
        for v in check_bench.compare_resilience(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["resilience"]["rows"]["gray"][
        "slow_replica_health_green"] = False
    assert any(
        "health-green" in v
        for v in check_bench.compare_resilience(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["resilience"]["rows"]["hedge"]["hedge_on"]["counters"][
        "hedge_losers"] += 1
    assert any(
        "unbalanced" in v
        for v in check_bench.compare_resilience(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["resilience"]["rows"]["gray"]["timed_pass_compiles"] = 3
    assert any(
        "mints landed inside" in v
        for v in check_bench.compare_resilience(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["resilience"]
    assert any(
        "missing resilience block" in v
        for v in check_bench.compare_resilience(bad, rec)
    )


def test_committed_bench_fleet_artifact_schema():
    """The COMMITTED BENCH_FLEET.json (the number PERF.md quotes) still
    matches the schema this harness produces, and carries the claimed
    effect: prefix-affinity routing beats random routing on hit rate
    for the prefix-heavy workload."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    _check_fleet_record(rec)
    ph = rec["workloads"]["prefix_heavy"]
    assert ph["affinity_hit_rate"] > ph["random_hit_rate"]


def test_committed_bench_fleet_autoscale_block():
    """The COMMITTED autoscale block carries the elastic-fleet claims
    honestly: the fleet grew past one replica INSIDE the measured ramp
    (provisioning curve from 1 to scaled_to), every join under live
    traffic compile-stormed ZERO times (the pre-warm-before-rotation
    contract), outputs stayed token-identical to solo decode, and both
    p99-under-ramp numbers sit under the collapse ceiling.
    Self-comparison exercises every invariant plus the committed
    floors — regenerating the artifact without the scale event must
    fail here, not slip through."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    assert check_bench.compare_autoscale(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["autoscale"]) == {
        "autoscale.autoscaled.scaled_to",
        "autoscale.autoscaled.scale_ups",
    }
    au = rec["autoscale"]["autoscaled"]
    assert au["join_compile_storms"] == 0
    assert au["scaled_to"] >= 2
    curve = au["replicas_over_time"]
    assert curve[0][1] == 1 and max(n for _, n in curve) == au["scaled_to"]
    assert rec["autoscale"]["trace"]["process"] == "ramp"
    # gate plumbing: a storm on join or a never-scaled fleet is a
    # violation, not a silent pass
    import copy

    bad = copy.deepcopy(rec)
    bad["autoscale"]["autoscaled"]["join_compile_storms"] = 1
    assert any(
        "compile storms" in v
        for v in check_bench.compare_autoscale(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["autoscale"]["autoscaled"]["scaled_to"] = 1
    assert any(
        "never scaled" in v
        for v in check_bench.compare_autoscale(bad, rec)
    )


@pytest.mark.slow
def test_bench_fleet_autoscale_smoke_end_to_end(tmp_path, monkeypatch):
    """``bench_fleet.py --smoke --autoscale-only`` (the ``--kind
    autoscale`` gate's fresh side) runs the interleaved ramp A/B —
    static-1 vs autoscaled, identity-pinned — end to end on CPU and
    the fresh artifact passes the autoscale gate against the committed
    one: the fleet scales mid-ramp, the join is storm-free, and the
    p99 ratio lands inside the band."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        sys, "argv", ["bench_fleet.py", "--smoke", "--autoscale-only"]
    )
    bench_fleet.main()
    rec = json.loads((tmp_path / "BENCH_FLEET.json").read_text())
    committed = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    violations = check_bench.compare_autoscale(rec, committed)
    assert violations == [], violations


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


def test_committed_bench_fleet_fabric_block():
    """The COMMITTED fabric block carries the fleet-KV-fabric claims
    honestly: the fetch side actually restored prefix pages over the
    wire (fetch_ok >= 1, zero degrades), the churned side degraded
    EVERY dial to recompute with zero successes (the fail-soft
    contract, measured), the wire ledger pairs byte-for-byte, and
    outputs stayed token-identical to solo decode on all three sides.
    Self-comparison exercises every invariant plus the committed
    floors — regenerating the artifact with a broken fabric must fail
    here, not slip through."""
    rec = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    assert check_bench.compare_fabric(rec, rec) == []
    assert set(check_bench.COMMITTED_FLOORS["fabric"]) == {
        "fabric.fetch.peer.fetch_ok",
        "fabric.churn_vs_recompute",
    }
    fb = rec["fabric"]
    assert fb["outputs_identical"] is True
    assert fb["fetch"]["peer"]["fetch_ok"] >= 1
    assert fb["fetch"]["peer"]["fetch_degraded"] == 0
    assert fb["churn"]["peer"]["fetch_ok"] == 0
    assert fb["churn"]["peer"]["fetch_degraded"] >= 1
    assert (
        fb["fetch"]["peer"]["bytes_in"]
        == fb["fetch"]["serve"]["bytes_out"]
        > 0
    )
    assert fb["wire_bytes_per_restored_token"] > 0
    # gate plumbing: a fabric that silently stopped fetching, or one
    # whose degrade path broke identity, is a violation — not a pass
    import copy

    bad = copy.deepcopy(rec)
    bad["fabric"]["fetch"]["peer"]["fetch_ok"] = 0
    bad["fabric"]["fetch"]["peer"]["fetches"] = 0
    assert any(
        "no peer fetch ever succeeded" in v
        for v in check_bench.compare_fabric(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["fabric"]["outputs_identical"] = False
    assert any(
        "outputs not identical" in v
        for v in check_bench.compare_fabric(bad, rec)
    )
    bad = copy.deepcopy(rec)
    bad["fabric"]["fetch"]["peer"]["bytes_in"] += 1
    assert any(
        "wire bytes unpaired" in v
        for v in check_bench.compare_fabric(bad, rec)
    )
    bad = copy.deepcopy(rec)
    del bad["fabric"]
    assert any(
        "missing fabric block" in v
        for v in check_bench.compare_fabric(bad, rec)
    )


@pytest.mark.slow
def test_bench_fleet_fabric_smoke_end_to_end(tmp_path, monkeypatch):
    """``bench_fleet.py --smoke --fabric-only`` (the ``--kind fabric``
    gate's fresh side) runs the three-sided A/B — recompute vs warm
    peer fetch vs churned-store degrade, identity-pinned — end to end
    on CPU and the fresh artifact passes the fabric gate against the
    committed one: pages actually crossed the wire, every churned dial
    degraded to recompute, and the ratios land inside the band."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        sys, "argv", ["bench_fleet.py", "--smoke", "--fabric-only"]
    )
    bench_fleet.main()
    rec = json.loads((tmp_path / "BENCH_FLEET.json").read_text())
    committed = json.loads(
        open(os.path.join(REPO, "BENCH_FLEET.json")).read()
    )
    violations = check_bench.compare_fabric(rec, committed)
    assert violations == [], violations


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


def test_north_star_cite_reads_artifact(tmp_path):
    rec = {"value": 123456.7, "unit": "samples/sec/chip", "batch": 2048}
    (tmp_path / "BENCH_TPU.json").write_text(json.dumps(rec))
    cite = benchmarks._north_star_cite(str(tmp_path))
    assert "123,457" in cite and "samples/sec/chip" in cite


def test_north_star_cite_survives_missing_artifact(tmp_path):
    cite = benchmarks._north_star_cite(str(tmp_path))
    assert "BENCH_TPU.json" in cite  # still cites the artifact by name
    (tmp_path / "BENCH_TPU.json").write_text("not json {")
    assert "BENCH_TPU.json" in benchmarks._north_star_cite(str(tmp_path))


def test_render_md_smoke(tmp_path):
    """render_md over a minimal two-section run list: both platform tables,
    the fallback `*` marker, and the cross-platform caveat all present."""
    runs = [
        {
            "platform": "tpu",
            "device_kind": "TPU v5 lite",
            "scale": "smoke",
            "results": [
                {
                    "config": 1,
                    "name": "SingleTrainer / MNIST MLP",
                    "samples_per_sec_per_chip": 3638.6,
                    "target_accuracy": 0.78,
                    "epochs_to_target": 6,
                    "final_accuracy": 0.80,
                    "seconds_total": 9.7,
                },
            ],
        },
        {
            "platform": "cpu",
            "device_kind": "cpu",
            "scale": "smoke",
            "results": [
                {
                    "config": 7,
                    "name": "AEASGD / REAL breast-cancer",
                    "samples_per_sec_per_chip": 15438.8,
                    "compile_in_window": True,
                    "target_accuracy": 0.87,
                    "epochs_to_target": 1,
                    "final_accuracy": 0.88,
                    "seconds_total": 6.3,
                },
            ],
        },
    ]
    benchmarks.render_md(runs, str(tmp_path))
    text = (tmp_path / "BENCHMARKS.md").read_text()
    assert "## Platform `tpu`" in text and "## Platform `cpu`" in text
    assert "CAVEAT" in text  # smoke-scale rows measure dispatch, not the chip
    assert "3638.6" in text
