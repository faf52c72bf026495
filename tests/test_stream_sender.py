"""The server's one stream sender (PR 38): every streamed request's chunk
frames leave through one thread that the scheduler wakes once an iteration.

What is pinned here: the wire is the parent's byte for byte, a reader that
stops reading delays its own stream alone, a stream that dies takes no other
along, the typed endings still reach the client, shutdown leaves no thread
behind, and ``stats()["streams"]`` counts one wake an emitting iteration.
Every test runs under a time limit of its own (``within``): a lost wake would
otherwise hang a connection's thread for its 600 s guard.
"""

from __future__ import annotations

import functools
import socket
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.faults import FaultPlan
from distkeras_tpu.networking import RetryPolicy, recv_data, send_data
from distkeras_tpu.serving.scheduler import ContinuousBatcher, ServeRequest
from distkeras_tpu.utils.serialization import (
    deserialize_params,
    pack_frame,
    serialize_params,
    unpack_frame,
)
from test_serving import FakeStepper


def within(seconds):
    """Run the test's body in a thread and fail it if it is still running
    after ``seconds``: the file's stand-in for a time-limit plugin."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kw):
            box = {}

            def body():
                try:
                    fn(*args, **kw)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box["exc"] = e

            th = threading.Thread(target=body, daemon=True)
            th.start()
            th.join(seconds)
            assert not th.is_alive(), f"still running after {seconds} s"
            if "exc" in box:
                raise box["exc"]

        return run

    return wrap


def _join(threads, timeout=120):
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    assert not any(t.is_alive() for t in threads)


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {msg}"
        time.sleep(0.005)


# ------------------------------------------------------- the hand-over alone


class Sink:
    """What a request is submitted with in a FIFO's place; ``log`` holds
    the pushes and the wakes in the order they came."""

    def __init__(self):
        self.log = []

    def push(self, req, tokens):
        self.log.append((req.id, tokens))

    def wake(self):
        self.log.append("wake")

    @property
    def wakes(self):
        return self.log.count("wake")


@within(60)
def test_an_emitting_iteration_wakes_the_sink_once_and_the_sentinel_is_last():
    sink = Sink()
    b = ContinuousBatcher(FakeStepper(num_slots=4))
    reqs = [ServeRequest(np.arange(3 + i), 3 + i, stream=sink)
            for i in range(4)]
    for r in reqs:
        b.submit(r)
    steps0 = b.counters["steps"]
    for _ in range(40):
        if all(r.done for r in reqs):
            break
        b.step()
    assert all(r.done and r.error is None for r in reqs)
    assert all(r._chunks is None and r.stream for r in reqs)
    # one wake an iteration that pushed anything, however many slots pushed
    assert sink.wakes == b.counters["steps"] - steps0
    assert sink.log[-1] == "wake"
    for r in reqs:
        at = [i for i, e in enumerate(sink.log) if e != "wake" and e[0] == r.id]
        mine = [sink.log[i][1] for i in at]
        assert mine[-1] is None and all(mine[:-1])
        assert [t for toks in mine[:-1] for t in toks] == r.tokens
        # the sentinel is handed over behind the last chunk, in the same
        # iteration: no wake lies between the two
        assert "wake" not in sink.log[at[-2]:at[-1]]
    assert b.counters["streamed_chunks"] == sum(
        e != "wake" and e[1] is not None for e in sink.log)


@within(60)
@pytest.mark.parametrize("how", ["stop", "deadline_in_queue"])
def test_a_finish_outside_an_emission_wakes_the_sink_at_once(how):
    sink = Sink()
    b = ContinuousBatcher(FakeStepper(num_slots=1))
    first = b.submit(ServeRequest(np.arange(3), 8, stream=sink))
    b.step()
    b.step()
    assert first.tokens and not first.done
    if how == "stop":
        ended = first
        b.stop()
    else:
        ended = b.submit(ServeRequest(np.arange(3), 4, stream=sink,
                                      deadline=time.monotonic() - 1.0))
        for _ in range(20):  # admission pops the queue: it has expired
            if ended.done:
                break
            b.step()
        assert ended.error.code == "deadline_exceeded"
    assert ended.done
    at = sink.log.index((ended.id, None))
    assert sink.log[at + 1] == "wake"


@within(60)
def test_stream_true_keeps_the_fifo_of_the_request_s_own():
    b = ContinuousBatcher(FakeStepper(num_slots=2))
    req = b.submit(ServeRequest(np.arange(3), 4, stream=True))
    while not req.done:
        b.step()
    got = []
    while (c := req.next_chunk(timeout=1.0)) is not None:
        got.extend(c)
    assert got == req.tokens and req._sink is None


# ------------------------------------------------- the write that keeps the lock


@within(60)
@pytest.mark.parametrize("how", ["whole", "cut_short", "full", "peer_gone",
                                 "closed"])
def test_send_nowait_is_send_with_msg_dontwait(how):
    """``networking.send_nowait`` takes what ``sock.send(data,
    MSG_DONTWAIT)`` takes and raises what it raises, on a socket whose own
    mode (blocking, for its connection thread's ``recv``) it leaves alone."""
    from distkeras_tpu.networking import send_nowait

    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
        if how == "whole":
            assert send_nowait(a, bytearray(b"frame")) == 5
            assert b.recv(16) == b"frame" and a.getblocking()
            return
        if how == "closed":
            a.close()
            with pytest.raises(OSError):
                send_nowait(a, b"x")
            return
        if how == "peer_gone":
            b.close()
            with pytest.raises(ConnectionError):
                for _ in range(4):
                    send_nowait(a, b"x")
            return
        big, took = b"x" * (1 << 20), []
        with pytest.raises(BlockingIOError):
            for _ in range(64):
                took.append(send_nowait(a, big))
        assert took and all(0 < n <= len(big) for n in took)
        if how == "cut_short":
            assert took[-1] < len(big)  # the buffer took a part, and said so
        else:
            b.setblocking(False)
            got = 0
            while got < sum(took):  # nothing was lost on the way
                got += len(b.recv(1 << 20))
            assert send_nowait(a, b"more") == 4
    finally:
        a.close()
        b.close()


@within(60)
def test_send_nowait_keeps_the_interpreter_lock():
    """A pass of small frames is ONE turn at the lock: threads that wait
    for it run no bytecode while 200 frames are written (under
    ``sock.send``, which hands it over at every frame, four contenders
    tick some 16 times here), where libc's ``send`` can be called."""
    from distkeras_tpu import networking

    if networking._SEND is None:
        pytest.skip("no libc send to call under the lock here")
    a, b = socket.socketpair()
    ticks, stop = [0], threading.Event()

    def contender():
        while not stop.is_set():
            ticks[0] += 1
            time.sleep(0)  # gives the lock up, then waits for it again

    old = sys.getswitchinterval()
    sys.setswitchinterval(30.0)  # no forced hand-over inside the test
    threads = [threading.Thread(target=contender, daemon=True)
               for _ in range(4)]
    try:
        for th in threads:
            th.start()
        _wait(lambda: ticks[0] > 40, msg="the contenders to run")
        frame = b"DKT1" + b"x" * 54
        before = ticks[0]
        for _ in range(200):
            networking.send_nowait(a, frame)
        during = ticks[0] - before
    finally:
        stop.set()
        sys.setswitchinterval(old)
        _join(threads, 10)
    assert during == 0
    assert len(b.recv(1 << 20)) == 200 * len(frame)
    a.close()
    b.close()


# ---------------------------------------------------------- over the wire


@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(vocab_size=61, seq_len=256, d_model=32,
                              num_heads=2, depth=2, seed=0)


@pytest.fixture()
def served(lm):
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=8, queue_capacity=64,
                        prefix_cache=False)
    srv = ServingServer(eng).start()
    eng.submit(np.arange(1, 5, dtype=np.int32), 3).result(120)  # compiles
    yield srv
    srv.shutdown()


def _prompt(i):
    return ((np.arange(3 + i % 5, dtype=np.int32) * (7 + i)) % 59) + 1


def _reference(srv, prompt, steps):
    """The non-streamed ``generate`` of the same prompt, same server."""
    from distkeras_tpu.serving import ServingClient

    with ServingClient("127.0.0.1", srv.port) as c:
        return np.asarray(c.generate(prompt, steps))


class RawStream:
    """A streaming ``generate`` spoken by hand, so that the frames' bytes
    can be held against ``pack_frame``'s."""

    def __init__(self, port, prompt, steps, rcvbuf=None, **header):
        self.prompt, self.steps = prompt, steps
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf is not None:  # before the connect: it bounds the window
            self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        self.sock.settimeout(120)
        self.sock.connect(("127.0.0.1", port))
        self.header = {"verb": "generate", "stream": True,
                       "max_new_tokens": int(steps), **header}
        self.raw, self.tokens, self.end, self.sequence = [], [], None, None

    def send(self):
        send_data(self.sock, pack_frame(
            self.header, serialize_params(np.asarray(self.prompt, np.int32))))
        return self

    def read_frame(self):
        """One frame; True while the stream goes on."""
        raw = recv_data(self.sock)
        h, body = unpack_frame(raw)
        if h.get("stream") == "chunk":
            self.raw.append((raw, h))
            self.tokens.extend(h["tokens"])
            return True
        self.end = h
        if h.get("ok"):
            self.sequence = np.asarray(deserialize_params(body))
        return False

    def read_all(self):
        while self.read_frame():
            pass
        return self

    def close(self):
        self.sock.close()


def _check_whole(st, ref):
    """The chunks concatenate to the reference, the ``end`` frame came
    behind the last of them, and each frame is ``pack_frame`` of its
    header."""
    assert st.end == {"ok": True, "stream": "end", "tokens": st.steps}
    np.testing.assert_array_equal(st.sequence, ref)
    assert st.tokens == [int(t) for t in ref[len(st.prompt):]]
    for raw, h in st.raw:
        assert list(h) == ["ok", "stream", "tokens"]
        assert raw == pack_frame(
            {"ok": True, "stream": "chunk", "tokens": h["tokens"]})


@within(300)
@pytest.mark.parametrize("n,switch", [(8, None), (24, 1e-5)],
                         ids=["8_streams", "24_streams_fast_switching"])
def test_concurrent_streams_are_whole_and_byte_exact(served, n, switch):
    """Streams of different lengths at once (24 over 8 slots with the
    interpreter switching threads every 10 us: more workers than cores,
    as the queue the scheduler and the sender share must bear)."""
    jobs = [(_prompt(i), 3 + (5 * i) % 17) for i in range(n)]
    refs = [_reference(served, p, s) for p, s in jobs]
    streams = [RawStream(served.port, p, s) for p, s in jobs]
    chunks0 = served.engine.stats()["streamed_chunks"]
    frames0 = served.engine.stats()["streams"]["frames_sent"]
    old = sys.getswitchinterval()
    if switch is not None:
        sys.setswitchinterval(switch)
    try:
        threads = [threading.Thread(target=lambda st=st: st.send().read_all())
                   for st in streams]
        for t in threads:
            t.start()
        _join(threads)
    finally:
        sys.setswitchinterval(old)
    for st, ref in zip(streams, refs):
        _check_whole(st, ref)
        st.close()
    stats = served.engine.stats()
    assert stats["streamed_chunks"] - chunks0 == sum(s for _p, s in jobs)
    assert (stats["streams"]["frames_sent"] - frames0
            == stats["streamed_chunks"] - chunks0)
    assert stats["streams"]["dead_streams"] == 0


@within(300)
def test_a_reader_that_stops_reading_delays_its_own_stream_alone(served):
    """One client behind a small receive buffer asks for 200 tokens and
    reads nothing; seven others stream meanwhile and finish. The stalled
    stream's frames wait in its outbox and arrive whole and in order once
    it reads."""
    slow_prompt = _prompt(99)
    slow_ref = _reference(served, slow_prompt, 200)
    jobs = [(_prompt(i), 20 + i) for i in range(7)]
    refs = [_reference(served, p, s) for p, s in jobs]
    slow = RawStream(served.port, slow_prompt, 200, rcvbuf=1)
    def server_end():
        # by its peer: a reference client's connection may still be closing
        mine = slow.sock.getsockname()
        for c in list(served._conns):
            try:
                if c.getpeername() == mine:
                    return c
            except OSError:
                pass  # closed underneath
        return None

    _wait(lambda: server_end() is not None, msg="the slow connection")
    # the server's end of it: a small send buffer
    server_end().setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
    slow.send()
    streams = [RawStream(served.port, p, s) for p, s in jobs]
    threads = [threading.Thread(target=lambda st=st: st.send().read_all())
               for st in streams]
    for t in threads:
        t.start()
    _join(threads)
    for st, ref in zip(streams, refs):
        _check_whole(st, ref)
        st.close()
    sender = served.engine.stats
    _wait(lambda: sender()["streams"]["would_block"] > 0,
          msg="a write cut short")
    # its request decodes to the end whether or not anybody reads
    _wait(lambda: sender()["completed"] >= 7 + 8 + 1 + 1,
          msg="the stalled request's decode")
    stats = sender()["streams"]
    assert stats["outbox_peak"] > 1 and stats["dead_streams"] == 0
    assert not slow.raw  # it has read nothing so far
    slow.read_all()
    _check_whole(slow, slow_ref)
    slow.close()
    assert sender()["streams"]["frames_sent"] == sender()["streamed_chunks"]


@within(300)
def test_a_client_that_goes_away_kills_its_stream_and_no_other(served):
    jobs = [(_prompt(i), 40) for i in range(4)]
    refs = [_reference(served, p, s) for p, s in jobs]
    streams = [RawStream(served.port, p, s) for p, s in jobs]
    gone, rest = streams[0], streams[1:]
    threads = [threading.Thread(target=lambda st=st: st.send().read_all())
               for st in rest]
    for t in threads:
        t.start()
    gone.send()
    gone.read_frame()
    gone.read_frame()
    gone.close()  # unread frames behind it: the server's writes now fail
    _join(threads)
    for st, ref in zip(rest, refs[1:]):
        _check_whole(st, ref)
        st.close()
    stats = served.engine.stats
    _wait(lambda: stats()["streams"]["dead_streams"] == 1, msg="the death")
    assert served._sender._thread.is_alive()
    # the decode of the dead stream completes idle, and the next stream is
    # served as any other
    again = RawStream(served.port, *jobs[0]).send().read_all()
    _check_whole(again, refs[0])
    again.close()
    assert stats()["streams"]["dead_streams"] == 1


@within(300)
def test_a_dropped_chunk_frame_takes_that_connection_alone(served):
    """``server.reply`` -> ``"drop"`` on a chunk frame: the stream vanishes
    mid-stream, its retrying client resends and skips what it had, and the
    caller sees the same tokens; the streams beside it never notice."""
    from distkeras_tpu.serving import ServingClient

    jobs = [(_prompt(i), 12) for i in range(3)]
    refs = [_reference(served, p, s) for p, s in jobs]
    got = [None] * 3
    handles = [None] * 3
    clients = [ServingClient("127.0.0.1", served.port,
                             retry=RetryPolicy(base_delay=0.01, seed=i))
               for i in range(3)]

    def drive(i):
        st = clients[i].generate_stream(*jobs[i])
        handles[i] = st
        got[i] = ([t for chunk in st for t in chunk], st.sequence)

    plan = FaultPlan().arm("server.reply", action="drop", times=1, after=7)
    threads = [threading.Thread(target=drive, args=(i,)) for i in range(3)]
    with plan:
        for t in threads:
            t.start()
        _join(threads)
    for c in clients:
        c.close()
    assert plan.fired("server.reply") == 1
    for (toks, seq), (p, _s), ref in zip(got, jobs, refs):
        np.testing.assert_array_equal(seq, ref)
        assert toks == [int(t) for t in ref[len(p):]]
    assert sorted(st._sends for st in handles) == [1, 1, 2]
    assert served.engine.stats()["streams"]["dead_streams"] == 1


@within(300)
@pytest.mark.parametrize("drain", [True, False], ids=["drain", "hard"])
def test_shutdown_with_streams_open_leaves_no_thread(lm, drain):
    from distkeras_tpu.serving import ServingEngine, ServingServer

    ours = ("serving-stream-sender", "serving-conn")
    before = {t for t in threading.enumerate() if t.name in ours}
    eng = ServingEngine(lm, num_slots=4, queue_capacity=16,
                        prefix_cache=False)
    srv = ServingServer(eng).start()
    jobs = [(_prompt(i), 60) for i in range(3)]
    streams = [RawStream(srv.port, p, s).send() for p, s in jobs]
    for st in streams:
        assert st.read_frame()  # every stream is open and decoding
    sender = srv._sender._thread
    assert sender.is_alive() and sender.name == "serving-stream-sender"
    threads = [threading.Thread(target=st.read_all) for st in streams]
    for t in threads:
        t.start()
    srv.shutdown(drain=drain)
    _join(threads)
    for st in streams:
        if drain:
            assert st.end["stream"] == "end" and len(st.tokens) == 60
        else:
            assert st.end["ok"] is False and st.end["error"] == "stopping"
        st.close()
    assert not sender.is_alive()
    _wait(lambda: not ({t for t in threading.enumerate()
                        if t.name in ours and t.is_alive()} - before),
          timeout=10, msg="the server's threads to end")


@within(300)
def test_the_streams_block_counts_one_wake_an_emitting_iteration(served):
    eng = served.engine
    s0 = eng.stats()
    jobs = [(_prompt(i), 24) for i in range(8)]
    streams = [RawStream(served.port, p, s) for p, s in jobs]
    barrier = threading.Barrier(8)

    def drive(st):
        barrier.wait(30)
        st.send().read_all()

    threads = [threading.Thread(target=drive, args=(st,)) for st in streams]
    for t in threads:
        t.start()
    _join(threads)
    for st in streams:
        assert len(st.tokens) == 24
        st.close()
    s1 = eng.stats()
    d = {k: s1["streams"][k] - s0["streams"][k]
         for k in ("sender_wakes", "frames_sent")}
    # every request streams and ends by its budget, so each decode step
    # emitted and no step's tokens were thrown away
    assert s1["discarded_slot_steps"] == s0["discarded_slot_steps"]
    assert d["sender_wakes"] == s1["steps"] - s0["steps"]
    assert d["frames_sent"] == s1["streamed_chunks"] - s0["streamed_chunks"]
    assert d["frames_sent"] == 8 * 24
    assert d["frames_sent"] / d["sender_wakes"] > 4
    block = eng.health()["streams"]
    assert set(block) == {"sender_wakes", "frames_sent", "frames_per_wake",
                          "coalesced_frames", "would_block", "outbox_peak",
                          "dead_streams"}
    assert block["would_block"] == 0 and block["dead_streams"] == 0
    # no server thread but the sender wakes a token: the connections'
    # threads slept from their submit to their stream's end
    assert sum(t.name == "serving-stream-sender" and t.is_alive()
               for t in threading.enumerate()) >= 1


@within(300)
def test_a_deadline_outside_an_emission_ends_the_stream_typed(lm):
    """The one slot decodes a long request; a streamed one with a short
    deadline expires in the queue, where no emission is open: its sentinel
    must still wake the sender, and the client gets the typed frame."""
    from distkeras_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(lm, num_slots=1, queue_capacity=8, prefix_cache=False)
    srv = ServingServer(eng).start()
    try:
        eng.submit(np.arange(1, 5, dtype=np.int32), 3).result(120)
        long = RawStream(srv.port, _prompt(1), 150).send()
        assert long.read_frame()
        t0 = time.monotonic()
        late = RawStream(srv.port, _prompt(2), 8, deadline_ms=30.0).send()
        late.read_all()
        assert late.end["ok"] is False
        assert late.end["error"] == "deadline_exceeded" and not late.raw
        assert time.monotonic() - t0 < 30
        long.read_all()
        assert len(long.tokens) == 150 and long.end["stream"] == "end"
        long.close()
        late.close()
        assert eng.stats()["streams"]["dead_streams"] == 0
    finally:
        srv.shutdown()
