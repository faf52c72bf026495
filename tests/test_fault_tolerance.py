"""Fault tolerance: exactly-once commits, worker retry, failure detection.

The reference has none of this (SURVEY §5.3): fault tolerance is delegated
to Spark task retry, and a retried partition's commits are silently
double-absorbed by the PS. The rebuild's contract: commit-sequence dedup
makes retries exactly-once, crashed worker threads are restarted, and a
heartbeat monitor flags silent workers.
"""

import threading
import time

import numpy as np
import pytest

from distkeras_tpu import DOWNPOUR
from distkeras_tpu.data import loaders
from distkeras_tpu.data.transformers import MinMaxTransformer, OneHotTransformer
from distkeras_tpu.models import zoo
from distkeras_tpu.networking import connect
from distkeras_tpu.parameter_servers import (
    DeltaParameterServer,
    DynSGDParameterServer,
    RemoteParameterServerClient,
    SocketParameterServer,
)
from distkeras_tpu.utils.profiling import read_metrics
from distkeras_tpu.workers import DOWNPOURWorker


def make_data(n=512, seed=0):
    ds = loaders.synthetic_mnist(n=n, seed=seed)
    ds = MinMaxTransformer(0, 1, o_min=0, o_max=255).transform(ds)
    ds = OneHotTransformer(10, output_col="label_onehot").transform(ds)
    return ds


PARAMS = {"w": np.zeros(3, np.float32)}
DELTA = {"w": np.ones(3, np.float32)}


# ------------------------------------------------------ exactly-once commits


def test_commit_dedup_exactly_once():
    ps = DeltaParameterServer(PARAMS)
    ps.commit(DELTA, commit_id=(0, 0))
    ps.commit(DELTA, commit_id=(0, 0))  # replay of the same commit
    ps.commit(DELTA, commit_id=(0, 1))
    ps.commit(DELTA, commit_id=(0, 0))  # late replay after progress
    assert ps.num_updates == 2
    assert ps.num_duplicates == 2
    np.testing.assert_allclose(ps.get_params()["w"], 2 * np.ones(3))


def test_commit_dedup_is_per_worker():
    ps = DeltaParameterServer(PARAMS)
    ps.commit(DELTA, commit_id=(0, 0))
    ps.commit(DELTA, commit_id=(1, 0))  # same seq, different worker: applies
    assert ps.num_updates == 2
    assert ps.num_duplicates == 0


def test_commit_without_id_never_deduped():
    ps = DeltaParameterServer(PARAMS)
    ps.commit(DELTA)
    ps.commit(DELTA)
    assert ps.num_updates == 2


def test_dynsgd_dedup_does_not_advance_version():
    ps = DynSGDParameterServer(PARAMS)
    _, tag = ps.pull()
    ps.commit(DELTA, tag, commit_id=(0, 0))
    v = ps._meta["version"]
    ps.commit(DELTA, tag, commit_id=(0, 0))  # duplicate
    assert ps._meta["version"] == v


# --------------------------------------------------------- failure detection


def test_suspected_failures_by_heartbeat():
    ps = DeltaParameterServer(PARAMS)
    ps.pull(worker_id=0)
    ps.pull(worker_id=1)
    time.sleep(0.05)
    ps.pull(worker_id=1)  # worker 1 stays live
    assert ps.suspected_failures(timeout=0.04) == [0]
    assert ps.suspected_failures(timeout=10.0) == []


# ------------------------------------------------------- worker crash + retry


class FlakyDOWNPOURWorker(DOWNPOURWorker):
    """Crashes once, at its fail_at-th commit, then behaves."""

    fail_at = 2

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._crashed_once = False

    def finish_window(self):
        if self._seq == self.fail_at and not self._crashed_once:
            self._crashed_once = True
            self._pending = None
            raise RuntimeError("injected worker crash")
        super().finish_window()


class FlakyDOWNPOUR(DOWNPOUR):
    worker_cls = FlakyDOWNPOURWorker


def test_worker_crash_is_retried_and_replay_is_deduped(tmp_path):
    ds = make_data(n=512)
    metrics = str(tmp_path / "ft.jsonl")
    t = FlakyDOWNPOUR(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=1,
        metrics_path=metrics,
    )
    t.train(ds)

    # both workers crashed once each (same class), were retried, finished
    assert len(t.failures) == 2
    assert {f["worker_id"] for f in t.failures} == {0, 1}
    events = [r for r in read_metrics(metrics) if r["event"] == "worker_failure"]
    assert len(events) == 2

    # each partition: 256 rows -> 8 batches -> 4 windows; the retry replays
    # the 2 pre-crash commits, which the PS must drop, not double-apply
    ps = t.parameter_server
    assert ps.num_updates == 8, (ps.num_updates, ps.num_duplicates)
    assert ps.num_duplicates == 4  # 2 replayed commits per worker


def test_worker_exhausted_retries_gives_up_others_continue():
    ds = make_data(n=512)

    class AlwaysCrash(DOWNPOURWorker):
        def finish_window(self):
            if self.worker_id == 0:
                raise RuntimeError("hard failure")
            super().finish_window()

    class Crashy(DOWNPOUR):
        worker_cls = AlwaysCrash

    t = Crashy(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=2,
    )
    t.train(ds)  # must not raise or hang
    assert len(t.failures) == 3  # initial + 2 retries, worker 0 only
    assert all(f["worker_id"] == 0 for f in t.failures)
    assert t.parameter_server.num_updates == 4  # worker 1's 4 windows landed


def test_a_logger_that_raises_in_the_crash_boundary_loses_one_thread_only(
        monkeypatch):
    """A metrics logger that raises while a failure is being recorded takes
    the exception out of ``attempt_partition`` itself. The worker's thread
    then ends on that exception and on no other (the flag its adoption loop
    reads is set before the ``try``), and the other worker trains on."""
    ds = make_data(n=512)

    class AlwaysCrash(DOWNPOURWorker):
        def finish_window(self):
            if self.worker_id == 0:
                raise RuntimeError("hard failure")
            super().finish_window()

    class Crashy(DOWNPOUR):
        worker_cls = AlwaysCrash

    class RaisingLogger:
        def log(self, **record):
            if record.get("event") == "worker_failure":
                raise OSError("disk full")

    ended = []
    monkeypatch.setattr(
        threading, "excepthook", lambda args: ended.append(args.exc_type))
    t = Crashy(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=2,
        elastic=True,
    )
    t.metrics_logger = RaisingLogger()
    t.train(ds)  # must not raise or hang
    assert ended == [OSError]
    assert [f["worker_id"] for f in t.failures] == [0]
    assert t.parameter_server.num_updates == 4  # worker 1's 4 windows landed


@pytest.mark.slow
def test_heartbeat_monitor_flags_silent_worker(tmp_path):
    ds = make_data(n=512)

    class Stall(DOWNPOURWorker):
        def finish_window(self):
            super().finish_window()
            if self.worker_id == 0:
                time.sleep(0.8)  # goes silent mid-training

    class Stally(DOWNPOUR):
        worker_cls = Stall

    t = Stally(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        heartbeat_timeout=0.3,
        metrics_path=str(tmp_path / "hb.jsonl"),
    )
    t.train(ds)
    assert any(s["worker_id"] == 0 for s in t.suspicions), t.suspicions


# ----------------------------------------------------- socket fault injection


def test_socket_server_survives_client_disconnects():
    ps = DeltaParameterServer(PARAMS)
    srv = SocketParameterServer(ps, host="127.0.0.1")
    srv.start()
    try:
        # half a commit, then vanish
        sock = connect("127.0.0.1", srv.port)
        sock.sendall(b"c")
        sock.close()
        # garbage action byte
        sock = connect("127.0.0.1", srv.port)
        sock.sendall(b"z")
        sock.close()
        time.sleep(0.1)

        # server still serves a well-behaved client, with dedup intact
        client = RemoteParameterServerClient("127.0.0.1", srv.port)
        center, _ = client.pull()
        np.testing.assert_allclose(center["w"], np.zeros(3))
        client.commit(DELTA, commit_id=(7, 0))
        client.commit(DELTA, commit_id=(7, 0))
        client.close()
        assert ps.num_updates == 1
        assert ps.num_duplicates == 1
    finally:
        srv.stop()


def test_socket_pull_registers_heartbeat():
    """A remote worker that pulls and dies before committing must still be
    visible to the failure detector."""
    ps = DeltaParameterServer(PARAMS)
    srv = SocketParameterServer(ps, host="127.0.0.1")
    srv.start()
    try:
        client = RemoteParameterServerClient("127.0.0.1", srv.port)
        client.pull(worker_id=5)
        client.close()
        time.sleep(0.05)
        assert ps.suspected_failures(timeout=0.01) == [5]
    finally:
        srv.stop()


def test_snapshot_failure_does_not_crash_committing_worker():
    ps = DeltaParameterServer(PARAMS)
    ps.snapshot_every = 1

    def exploding_snapshot(n, center, meta, worker_snaps):
        raise OSError("disk full")

    ps.on_snapshot = exploding_snapshot
    ps.commit(DELTA, commit_id=(0, 0))  # must not raise
    assert ps.num_updates == 1


# ------------------------------------------------- elastic partition adoption


class OutageDOWNPOURWorker(DOWNPOURWorker):
    """Models a time-correlated outage: worker 0 crashes at its 2nd
    commit on each of its first ``heal_after`` train() attempts, then
    behaves — an outage that outlives the owner thread's retry budget
    but not the epoch (the case elastic adoption exists for)."""

    heal_after = 2

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._attempts = 0

    def train(self, *args, **kwargs):
        self._attempts += 1
        return super().train(*args, **kwargs)

    def finish_window(self):
        if (
            self.worker_id == 0
            and self._attempts <= self.heal_after
            and self._seq == 2
        ):
            self._pending = None
            raise RuntimeError("injected outage")
        super().finish_window()


class OutageDOWNPOUR(DOWNPOUR):
    worker_cls = OutageDOWNPOURWorker


def test_elastic_adoption_trains_full_dataset(tmp_path):
    """Worker 0's outage outlives its retry budget (1 retry, heals on
    attempt 3): without elastic its partition's tail is lost; with it, a
    survivor adopts the dead worker's OBJECT and the full dataset
    trains, with PS dedup keeping the replayed commits exactly-once."""
    ds = make_data(n=512)
    metrics = str(tmp_path / "elastic.jsonl")
    t = OutageDOWNPOUR(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=1,
        elastic=True,
        metrics_path=metrics,
    )
    t.train(ds)

    # owner thread: initial + 1 retry, both crashed
    owner_failures = [f for f in t.failures if "adopted_by" not in f]
    assert len(owner_failures) == 2
    assert all(f["worker_id"] == 0 for f in owner_failures)
    # adoption succeeded — by the surviving worker when worker 0 gave up
    # first, by the post-join main-thread drain when the survivor
    # finished before the orphan appeared (both orders are correct;
    # which one runs depends on thread scheduling)
    assert len(t.adoptions) == 1
    adoption = t.adoptions[0]
    assert adoption["worker_id"] == 0 and adoption["ok"] is True
    assert adoption["adopted_by"] in (1, "main")
    events = {r["event"] for r in read_metrics(metrics)}
    assert {"partition_orphaned", "partition_adopted"} <= events
    # full dataset trained: each partition is 256 rows -> 4 windows.
    # worker 0 committed seqs 0,1 before each crash; the retry and the
    # adoption each replay them (2 x 2 deduped) before landing 2,3.
    ps = t.parameter_server
    assert ps.num_updates == 8, (ps.num_updates, ps.num_duplicates)
    assert ps.num_duplicates == 4


def test_elastic_abandons_unhealable_partition():
    """A worker whose failure is NOT time-correlated (crashes forever)
    fails its adopter too: the partition is recorded abandoned, train()
    terminates, and the orphan is not re-queued."""
    ds = make_data(n=512)

    class AlwaysCrash(DOWNPOURWorker):
        def finish_window(self):
            if self.worker_id == 0:
                raise RuntimeError("hard failure")
            super().finish_window()

    class Crashy(DOWNPOUR):
        worker_cls = AlwaysCrash

    t = Crashy(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=1,
        elastic=True,
    )
    t.train(ds)  # must not raise or hang
    assert len(t.adoptions) == 1
    assert t.adoptions[0]["ok"] is False
    # owner attempts (2) + adoption attempts (2), all worker 0
    assert len(t.failures) == 4
    assert all(f["worker_id"] == 0 for f in t.failures)
    assert t.parameter_server.num_updates == 4  # worker 1's windows only


def test_elastic_adoption_survives_reset_failure():
    """reset_for_retry itself can raise mid-outage (remote_ps reconnect)
    — it runs inside the crash boundary, so a failing reset becomes a
    recorded failure + abandoned partition, never a lost orphan or an
    exception escaping the post-join drain."""
    ds = make_data(n=512)

    class BrokenReset(DOWNPOURWorker):
        def finish_window(self):
            if self.worker_id == 0:
                raise RuntimeError("hard failure")
            super().finish_window()

        def reset_for_retry(self):
            if self.worker_id == 0:
                raise ConnectionRefusedError("PS unreachable")
            super().reset_for_retry()

    class Broken(DOWNPOUR):
        worker_cls = BrokenReset

    t = Broken(
        zoo.mnist_mlp(hidden=16),
        "sgd",
        "categorical_crossentropy",
        learning_rate=0.05,
        batch_size=32,
        num_workers=2,
        communication_window=2,
        num_epoch=1,
        mode="threads",
        label_col="label_onehot",
        worker_retries=0,
        elastic=True,
    )
    t.train(ds)  # must not raise
    assert len(t.adoptions) == 1 and t.adoptions[0]["ok"] is False
    errors = [f["error"] for f in t.failures]
    assert len(errors) == 2  # owner crash, then the adoption's reset
    assert "ConnectionRefusedError" in errors[1]
    assert t.parameter_server.num_updates == 4  # worker 1's windows only
