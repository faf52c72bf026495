"""Elastic-fleet control-loop units: policy, autoscaler, publisher,
deployer — all under fake clocks and fake actuators (zero sleeps, zero
engines).

The :class:`AutoscalePolicy` is PURE by design exactly so these tests
can drive hysteresis, cooldowns, and clamps deterministically; the
:class:`Autoscaler` tests pin the tick ORDER (reap before decide —
the kill-9-then-replace-same-tick regression) with a duck-typed
controller; the publisher/deployer tests cover the checkpoint-cadence
→ bundle → rollover chain down to the atomic rename. The loadgen ramp
preset, the dkt_top fleet column, and the policy run over the seeded
ramp ride along — every satellite of the elastic-fleet PR has its pin
here.
"""

import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import dkt_top  # noqa: E402
import loadgen  # noqa: E402

from distkeras_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from distkeras_tpu.obs.recorder import FlightRecorder  # noqa: E402
from distkeras_tpu.obs.timeseries import (  # noqa: E402
    BURN_BREACH,
    BURN_BURNING,
    BURN_OK,
)
from distkeras_tpu.serving.autoscale import (  # noqa: E402
    HOLD,
    SCALE_DOWN,
    SCALE_UP,
    AutoscalePolicy,
    Autoscaler,
    BundlePublisher,
    ContinuousDeployer,
    ReplicaSignals,
    signals_from_router,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def sig(ep=0, state="active", util=0.0, **kw):
    """A replica signal whose utilization is exactly ``util`` (queue
    fill drives it; slots and pool left neutral)."""
    return ReplicaSignals(
        endpoint=("127.0.0.1", 9000 + ep), state=state,
        queue_depth=int(round(util * 100)), queue_capacity=100, **kw
    )


def policy(clock, **kw):
    base = dict(
        min_replicas=1, max_replicas=4,
        up_threshold=0.75, down_threshold=0.25,
        up_ticks=2, down_ticks=2,
        up_cooldown=10.0, down_cooldown=30.0,
        clock=clock,
    )
    base.update(kw)
    return AutoscalePolicy(**base)


# ---------------------------------------------------------- the policy


class TestAutoscalePolicy:
    def test_breach_scales_up_immediately_no_streak(self):
        clk = FakeClock()
        p = policy(clk)
        d = p.decide([sig(0, util=0.1, burn=BURN_BREACH)])
        assert (d.action, d.reason) == (SCALE_UP, "slo_breach")

    def test_up_cooldown_gates_even_a_breach(self):
        clk = FakeClock()
        p = policy(clk, up_cooldown=10.0)
        assert p.decide([sig(0, burn=BURN_BREACH)]).action == SCALE_UP
        clk.advance(5.0)
        d = p.decide([sig(0, burn=BURN_BREACH), sig(1, burn=BURN_BREACH)])
        assert (d.action, d.reason) == (HOLD, "up_cooldown")
        clk.advance(5.0)
        d = p.decide([sig(0, burn=BURN_BREACH), sig(1, burn=BURN_BREACH)])
        assert d.action == SCALE_UP

    def test_pressure_needs_consecutive_ticks(self):
        clk = FakeClock()
        p = policy(clk, up_ticks=3)
        for _ in range(2):
            assert p.decide([sig(0, util=0.9)]).action == HOLD
            clk.advance(1.0)
        d = p.decide([sig(0, util=0.9)])
        assert (d.action, d.reason) == (SCALE_UP, "pressure:utilization")

    def test_hysteresis_band_arms_neither_direction(self):
        # load parked between the thresholds: every tick holds and
        # neither streak ever arms — the no-flap property
        clk = FakeClock()
        p = policy(clk, up_ticks=1, down_ticks=1, down_cooldown=0.0)
        for _ in range(20):
            d = p.decide([sig(0, util=0.5), sig(1, util=0.5)])
            assert (d.action, d.reason) == (HOLD, "steady")
            clk.advance(5.0)

    def test_oscillation_across_one_boundary_cannot_flap(self):
        # alternating above-up / in-band resets the up streak each
        # in-band tick, so up_ticks=2 never fires; the down side needs
        # BELOW down_threshold, which never happens
        clk = FakeClock()
        p = policy(clk, up_ticks=2, down_ticks=2)
        for i in range(10):
            d = p.decide([sig(0, util=0.9 if i % 2 == 0 else 0.5)])
            assert d.action == HOLD
            clk.advance(1.0)

    def test_below_min_bypasses_hysteresis_and_cooldowns(self):
        clk = FakeClock()
        p = policy(clk, min_replicas=2, up_cooldown=1e9)
        assert p.decide([sig(0, burn=BURN_BREACH)]).action == SCALE_UP
        # a second below-min tick scales again despite the huge
        # cooldown: replacing dead capacity is not growth
        d = p.decide([sig(0)])
        assert (d.action, d.reason) == (SCALE_UP, "below_min")

    def test_above_max_clamps_down_one_per_tick(self):
        clk = FakeClock()
        p = policy(clk, max_replicas=2)
        d = p.decide([sig(0, util=0.3), sig(1, util=0.1), sig(2, util=0.9)])
        assert (d.action, d.reason) == (SCALE_DOWN, "above_max")
        assert d.target == ("127.0.0.1", 9001)  # the least loaded

    def test_at_max_holds_under_breach(self):
        clk = FakeClock()
        p = policy(clk, max_replicas=2)
        d = p.decide([sig(0, burn=BURN_BREACH), sig(1, burn=BURN_BREACH)])
        assert (d.action, d.reason) == (HOLD, "at_max")

    def test_min_equals_max_policy_never_grows_past_bound(self):
        clk = FakeClock()
        p = policy(clk, min_replicas=2, max_replicas=2, up_ticks=1)
        assert p.decide([sig(0)]).reason == "below_min"
        d = p.decide([sig(0, util=0.99), sig(1, util=0.99)])
        assert (d.action, d.reason) == (HOLD, "at_max")

    def test_scale_down_prefers_least_loaded(self):
        clk = FakeClock()
        p = policy(clk, down_ticks=1, down_cooldown=0.0)
        fleet = [sig(0, util=0.2), sig(1, util=0.0), sig(2, util=0.1)]
        d = p.decide(fleet)
        assert (d.action, d.reason) == (SCALE_DOWN, "idle")
        assert d.target == ("127.0.0.1", 9001)

    def test_down_cooldown_measured_from_last_scale_up(self):
        # never shrink right after growing: the capacity just bought
        # must get its chance to absorb the load
        clk = FakeClock()
        p = policy(clk, up_ticks=1, down_ticks=1, down_cooldown=30.0,
                   up_cooldown=0.0)
        assert p.decide([sig(0, util=0.9)]).action == SCALE_UP
        clk.advance(10.0)
        d = p.decide([sig(0, util=0.0), sig(1, util=0.0)])
        assert (d.action, d.reason) == (HOLD, "down_cooldown")
        clk.advance(30.0)
        assert p.decide([sig(0, util=0.0), sig(1, util=0.0)]).action \
            == SCALE_DOWN

    def test_rising_queue_trend_blocks_scale_down(self):
        clk = FakeClock()
        p = policy(clk, down_ticks=1, down_cooldown=0.0)
        d = p.decide([
            sig(0, util=0.0, queue_depth_trend=2.5),
            sig(1, util=0.0),
        ])
        assert d.action == HOLD

    def test_pool_exhaustion_is_pressure(self):
        clk = FakeClock()
        p = policy(clk, up_ticks=1)
        d = p.decide([sig(0, util=0.0, pool_exhausted_rate=0.5)])
        assert (d.action, d.reason) == (SCALE_UP, "pressure:pool_exhausted")

    def test_non_ok_burn_is_pressure(self):
        clk = FakeClock()
        p = policy(clk, up_ticks=1)
        d = p.decide([sig(0, util=0.0, burn=BURN_BURNING)])
        assert (d.action, d.reason) == (SCALE_UP, "pressure:burn_burning")

    def test_draining_replicas_do_not_count(self):
        clk = FakeClock()
        p = policy(clk, min_replicas=2)
        d = p.decide([sig(0), sig(1, state="draining")])
        assert (d.action, d.reason) == (SCALE_UP, "below_min")

    def test_at_min_idle_holds(self):
        clk = FakeClock()
        p = policy(clk, down_ticks=1, down_cooldown=0.0)
        d = p.decide([sig(0, util=0.0)])
        assert (d.action, d.reason) == (HOLD, "at_min")

    def test_constructor_validates_bounds_and_gap(self):
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            AutoscalePolicy(up_threshold=0.3, down_threshold=0.5)

    def test_utilization_is_worst_resource(self):
        s = ReplicaSignals(
            endpoint=("h", 1), in_flight=1, capacity=4,
            queue_depth=1, queue_capacity=100, kv_page_util=0.9,
        )
        assert s.utilization() == 0.9

    def test_signals_from_router_maps_books(self):
        class R:
            def replicas(self):
                return [{
                    "endpoint": ["127.0.0.1", 9100], "state": "active",
                    "in_flight": 2, "capacity": 4, "queue_depth": 3,
                    "queue_capacity": 8, "kv_page_util": 0.5,
                    "pool_exhausted_rate": 0.0,
                    "queue_depth_trend": 1.5, "burn": BURN_OK,
                }]

        (s,) = signals_from_router(R())
        assert s.endpoint == ("127.0.0.1", 9100)
        assert s.utilization() == 0.5 and s.queue_depth_trend == 1.5


# ------------------------------------------------------- the autoscaler


class FakeReplica:
    def __init__(self, endpoint):
        self.endpoint = endpoint


class FakeRouter:
    def __init__(self, controller):
        self.registry = MetricsRegistry()
        self.recorder = FlightRecorder()
        self._ctl = controller

    def replicas(self):
        return [
            {"endpoint": list(r.endpoint), "state": "active",
             "queue_depth": 0, "queue_capacity": 100}
            for r in self._ctl.replicas
        ]


class FakeController:
    """Duck-typed FleetController: books the autoscaler reads, call
    order it must respect, failure modes it must absorb."""

    def __init__(self, n=2, dead=()):
        self.replicas = [
            FakeReplica(("127.0.0.1", 9200 + i)) for i in range(n)
        ]
        self._dead = set(dead)
        self.calls = []
        self.router = FakeRouter(self)
        self.fail_scale_up = False
        self._next = 9200 + n

    def reap_dead(self):
        self.calls.append("reap_dead")
        reaped = [r for r in self.replicas if r.endpoint[1] in self._dead]
        self.replicas = [
            r for r in self.replicas if r.endpoint[1] not in self._dead
        ]
        self._dead.clear()
        return reaped

    def scale_up(self, n=1):
        self.calls.append("scale_up")
        if self.fail_scale_up:
            raise RuntimeError("boot failed")
        added = [FakeReplica(("127.0.0.1", self._next))]
        self._next += 1
        self.replicas.extend(added)
        return added

    def scale_down(self, endpoint=None):
        self.calls.append(("scale_down", endpoint))
        self.replicas = [
            r for r in self.replicas if r.endpoint != tuple(endpoint)
        ]


class TestAutoscaler:
    def test_reap_and_replace_in_the_same_tick(self):
        """The kill -9 regression: a dead replica must be reaped AND
        its replacement booted inside ONE tick — reap_dead runs before
        the decision, so the policy sees the shrunken fleet and its
        below_min row fires immediately."""
        clk = FakeClock()
        ctl = FakeController(n=2, dead={9201})
        sc = Autoscaler(
            ctl, policy(clk, min_replicas=2, max_replicas=2),
            interval=1.0, clock=clk,
        )
        d = sc.tick()
        assert (d.action, d.reason) == (SCALE_UP, "below_min")
        assert ctl.calls == ["reap_dead", "scale_up"]
        assert len(ctl.replicas) == 2
        assert sc._counters["reaps"] == 1
        assert sc._counters["scale_ups"] == 1
        kinds = [e["kind"] for e in ctl.router.recorder.snapshot()]
        assert kinds.index("autoscale.reap") \
            < kinds.index("autoscale.scale_up")

    def test_deploys_run_on_hold_ticks_only(self):
        clk = FakeClock()
        pending = [{"version": 1, "path": "/x",
                    "ledger": {"replaced": [1, 2]}}]

        class D:
            calls = 0

            def maybe_deploy(self):
                D.calls += 1
                return pending.pop() if pending else None

        ctl = FakeController(n=1)
        sc = Autoscaler(
            ctl, policy(clk, min_replicas=2), interval=1.0,
            deployer=D(), clock=clk,
        )
        assert sc.tick().action == SCALE_UP  # below_min: no deploy
        assert D.calls == 0 and sc.last_deploy is None
        assert sc.tick().action == HOLD
        assert D.calls == 1 and sc.last_deploy["version"] == 1
        assert sc._counters["deploys"] == 1
        kinds = [e["kind"] for e in ctl.router.recorder.snapshot()]
        assert "autoscale.deploy" in kinds

    def test_actuation_failure_counted_never_raised(self):
        clk = FakeClock()
        ctl = FakeController(n=1)
        ctl.fail_scale_up = True
        sc = Autoscaler(
            ctl, policy(clk, min_replicas=2), interval=1.0, clock=clk,
        )
        d = sc.tick()  # must not raise
        assert d.action == SCALE_UP
        assert sc._counters["errors"] == 1
        assert sc._counters["scale_ups"] == 0
        assert any(
            e["kind"] == "autoscale.error"
            for e in ctl.router.recorder.snapshot()
        )

    def test_maybe_tick_is_cadence_guarded(self):
        clk = FakeClock()
        ctl = FakeController(n=1)
        sc = Autoscaler(ctl, policy(clk), interval=10.0, clock=clk)
        assert sc.maybe_tick() is not None
        clk.advance(5.0)
        assert sc.maybe_tick() is None
        clk.advance(5.0)
        assert sc.maybe_tick() is not None
        assert sc.ticks == 2

    def test_tick_before_controller_start_raises(self):
        class Stopped:
            router = None

        with pytest.raises(RuntimeError):
            Autoscaler(Stopped(), policy(FakeClock())).tick()


# --------------------------------------- publisher / deployer (the CD leg)


class FakePS:
    def __init__(self):
        self.listener = None
        self.every = None

    def add_snapshot_listener(self, cb, every=1):
        self.listener, self.every = cb, every

    def remove_snapshot_listener(self, cb):
        if self.listener == cb:  # bound methods compare by ==, not is
            self.listener = None


class TestBundlePublisher:
    def test_atomic_rename_and_monotonic_versions(self, tmp_path):
        ps = FakePS()

        def build(center, meta, path):
            with open(path, "w") as f:
                f.write(f"v{meta['n']}")

        pub = BundlePublisher(ps, build, str(tmp_path), every=2)
        assert ps.every == 2 and pub.latest() is None
        ps.listener(2, {"w": 1}, {"n": 2}, {})
        ps.listener(4, {"w": 2}, {"n": 4}, {})
        latest = pub.latest()
        assert latest["version"] == 4
        assert latest["path"].endswith("bundle_v00000004.dkt")
        assert pub.published == 2 and pub.publish_errors == 0
        names = sorted(os.listdir(tmp_path))
        assert names == ["bundle_v00000002.dkt", "bundle_v00000004.dkt"]
        assert not any(n.endswith(".tmp") for n in names)
        pub.close()
        assert ps.listener is None

    def test_failing_build_counted_and_leaves_no_partial(self, tmp_path):
        ps = FakePS()

        def build(center, meta, path):
            with open(path, "w") as f:
                f.write("partial")
            raise RuntimeError("quantize blew up")

        pub = BundlePublisher(ps, build, str(tmp_path))
        ps.listener(1, {}, {}, {})
        assert pub.publish_errors == 1 and pub.published == 0
        assert pub.latest() is None
        assert os.listdir(tmp_path) == []

    def test_rides_real_ps_commit_cadence(self, tmp_path):
        from distkeras_tpu.parameter_servers import DeltaParameterServer

        params = {"w": np.zeros((3,), np.float32)}
        ps = DeltaParameterServer(params)
        seen = []

        def build(center, meta, path):
            seen.append(float(np.asarray(center["w"]).sum()))
            with open(path, "wb") as f:
                f.write(b"x")

        pub = BundlePublisher(ps, build, str(tmp_path), every=2)
        delta = {"w": np.ones((3,), np.float32)}
        for _ in range(4):
            ps.commit(delta)
        assert pub.published == 2
        assert pub.latest()["version"] == 4
        # the snapshot is the center AT that commit, not a later one
        assert seen == [6.0, 12.0]
        pub.close()


class FakePublisher:
    def __init__(self, latest=None):
        self._latest = latest

    def latest(self):
        return None if self._latest is None else dict(self._latest)

    def publish(self, version):
        self._latest = {"version": version, "path": f"/b/v{version}"}


class TestContinuousDeployer:
    def test_deploys_only_new_versions(self):
        rolls = []

        class Ctl:
            def rollover(self, bundle=None, timeout=None):
                rolls.append(bundle)
                return {"replaced": [("h", 1), ("h", 2)]}

        pub = FakePublisher()
        dep = ContinuousDeployer(Ctl(), pub, timeout=5.0)
        assert dep.maybe_deploy() is None  # nothing published yet
        pub.publish(1)
        out = dep.maybe_deploy()
        assert out["version"] == 1 and len(out["ledger"]["replaced"]) == 2
        assert dep.maybe_deploy() is None  # already current
        assert rolls == ["/b/v1"] and dep.deploys == 1

    def test_attach_time_version_is_the_baseline(self):
        class Ctl:
            def rollover(self, **kw):
                raise AssertionError("must not roll the boot bundle")

        pub = FakePublisher({"version": 5, "path": "/b/v5"})
        dep = ContinuousDeployer(Ctl(), pub)
        assert dep.maybe_deploy() is None  # fleet booted from v5


# ------------------------------------------------- the satellite pins


class TestLoadgenRamp:
    def test_ramp_deterministic_ascending_and_climbing(self):
        kw = dict(n=200, seed=7, period=5.0, floor_frac=0.1)
        a = loadgen.arrivals("ramp", 50.0, **kw)
        b = loadgen.arrivals("ramp", 50.0, **kw)
        assert np.array_equal(a, b)
        assert len(a) == 200 and np.all(np.diff(a) >= 0)
        assert not np.array_equal(
            a, loadgen.arrivals("ramp", 50.0, **{**kw, "seed": 8})
        )
        # the climb: early gaps dwarf late gaps (trickle -> peak)
        gaps = np.diff(a)
        assert gaps[:20].mean() > 3 * gaps[-20:].mean()

    def test_ramp_steps_quantize_the_climb(self):
        a = loadgen.arrivals(
            "ramp", 40.0, n=120, seed=1, period=4.0, ramp_steps=4,
        )
        assert len(a) == 120 and np.all(np.diff(a) >= 0)

    def test_summarize_phase_rates_document_the_climb(self):
        trace = loadgen.make_trace(
            process="ramp", rate=40.0, n=240, seed=3, period=6.0,
            floor_frac=0.1, tenants=loadgen.interactive_tenants(32),
        )
        s = loadgen.summarize(trace, phases=3)
        rows = s["phase_rates"]
        assert len(rows) == 3
        assert sum(r["events"] for r in rows) == len(trace)
        assert rows[-1]["rate"] > rows[0]["rate"]
        # phases=0 keeps the base schema unchanged
        assert "phase_rates" not in loadgen.summarize(trace)


class TestDktTopFleetColumn:
    SAMPLES = [
        {"name": "fleet_replicas", "kind": "gauge", "value": 2,
         "labels": {"replica": "router"}},
        {"name": "fleet_autoscale_scale_ups", "kind": "counter",
         "value": 3, "labels": {"replica": "router"}},
        {"name": "fleet_autoscale_scale_downs", "kind": "counter",
         "value": 1, "labels": {"replica": "router"}},
    ]

    def test_header_carries_replicas_and_scale_markers(self):
        out = dkt_top.format_table(self.SAMPLES)
        header = out.splitlines()[0]
        assert "replicas=2" in header and "↑3↓1" in header

    def test_fleet_replicas_sparkline_rides_the_series(self):
        series = {
            ("router", "fleet_replicas", ()): {
                "points": [1, 1, None, 2, 2], "rate": None, "trend": 0.1,
            },
        }
        header = dkt_top.format_table(
            self.SAMPLES, series=series
        ).splitlines()[0]
        assert "replicas=2" in header
        # the provisioning curve: low block, gap, high block
        assert "▁▁ ██" in header

    def test_no_markers_when_fleet_never_scaled(self):
        samples = [dict(self.SAMPLES[0])]
        header = dkt_top.format_table(samples).splitlines()[0]
        assert "replicas=2" in header and "↑" not in header


class TestPolicyOnTheRamp:
    """The pure policy over a seeded ramp of arrivals, under an injected
    clock and with no fleet: a replica is a queue served at a fixed rate,
    a tick reads how busy the replicas were and how full their queues are,
    and what the policy decides is applied at once. The ramp climbs past
    what one replica serves, so the policy must add a second, and once the
    ramp is over it must come back."""

    PEAK, N, PERIOD, SEED = 50.0, 450, 6.0, 7
    SERVICE_RATE, SLOTS, QUEUE, TICK = 30.0, 100, 100, 0.1

    def ramp(self, seed=SEED):
        return loadgen.arrivals(
            "ramp", self.PEAK, n=self.N, seed=seed, period=self.PERIOD,
            floor_frac=0.2)

    def run(self, arrivals, max_replicas=2, ticks_after=100):
        """``[(time, replicas)]`` at every change, and the decisions."""
        clk = FakeClock()
        p = policy(clk, max_replicas=max_replicas, down_ticks=5,
                   up_cooldown=1.0, down_cooldown=3.0)
        replicas, backlog, seen = 1, 0.0, 0
        curve, decisions = [(0.0, 1)], []
        end = float(arrivals[-1]) + ticks_after * self.TICK
        while clk.t < end:
            clk.advance(self.TICK)
            arrived = int(np.searchsorted(arrivals, clk.t)) - seen
            seen += arrived
            can = replicas * self.SERVICE_RATE * self.TICK
            done = min(backlog + arrived, can)
            backlog += arrived - done
            d = p.decide([
                ReplicaSignals(
                    endpoint=("127.0.0.1", 9000 + i),
                    in_flight=int(round(self.SLOTS * done / can)),
                    capacity=self.SLOTS,
                    queue_depth=min(
                        self.QUEUE, int(round(backlog / replicas))),
                    queue_capacity=self.QUEUE)
                for i in range(replicas)])
            decisions.append((d.action, d.reason))
            replicas += {SCALE_UP: 1, SCALE_DOWN: -1, HOLD: 0}[d.action]
            if replicas != curve[-1][1]:
                curve.append((round(clk.t, 2), replicas))
        return curve, decisions

    def test_the_policy_scales_up_on_the_ramp(self):
        arrivals = self.ramp()
        curve, decisions = self.run(arrivals)
        # the provisioning curve starts at one replica and climbs while
        # the ramp is still arriving, on utilization and nothing else
        assert curve[0] == (0.0, 1)
        assert curve[1][1] == 2 and curve[1][0] < float(arrivals[-1])
        assert max(n for _, n in curve) == 2
        assert (SCALE_UP, "pressure:utilization") in decisions
        # a fleet held to one replica never grows, whatever the ramp
        static, held = self.run(arrivals, max_replicas=1)
        assert static == [(0.0, 1)]
        assert (HOLD, "at_max") in held
        assert not any(a == SCALE_UP for a, _ in held)

    def test_the_policy_comes_back_to_one_replica_after_the_ramp(self):
        arrivals = self.ramp()
        curve, decisions = self.run(arrivals)
        assert curve[-1][1] == 1 and curve[-1][0] > float(arrivals[-1])
        assert decisions.count((SCALE_DOWN, "idle")) == 1
        # one up and one down: the cooldowns and the streaks keep it
        # from flapping on the way
        assert [n for _, n in curve] == [1, 2, 1]
        assert decisions[-1] == (HOLD, "at_min")

    def test_the_same_seed_gives_the_same_ramp_and_the_same_decisions(self):
        a, b = self.ramp(), self.ramp()
        assert np.array_equal(a, b) and len(a) == self.N
        assert np.all(np.diff(a) >= 0)
        assert self.run(a) == self.run(b)
        other = self.ramp(seed=8)
        assert not np.array_equal(a, other)
        # another seed is another ramp, and still one that scales up
        assert max(n for _, n in self.run(other)[0]) == 2


class TestAutoscalerThreadLifecycle:
    def test_start_shutdown_idempotent_and_ticks(self):
        clk = FakeClock()
        ctl = FakeController(n=1)
        sc = Autoscaler(ctl, policy(clk), interval=0.01)
        done = threading.Event()
        orig = sc.tick

        def tick():
            try:
                return orig()
            finally:
                done.set()

        sc.tick = tick
        with sc:
            assert sc.start() is sc  # second start: no second thread
            assert done.wait(5.0)
        assert sc._thread is None
        sc.shutdown()  # idempotent
