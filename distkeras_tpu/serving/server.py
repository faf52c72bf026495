"""TCP front of the serving engine — the online face of ``networking``.

Same wire primitives as the cross-host parameter-server path
(``networking.send_data``/``recv_data``: 8-byte length prefix, Nagle
off) carrying ``serialization.pack_frame`` frames (JSON header + npz
payload, no pickle on the wire — the serving port accepts bytes from
untrusted clients, so the codec choice is load-bearing here, not just
hygiene). One frame per request, one per reply; each connection gets a
thread, so slow clients never block the scheduler.

Verbs (header ``{"verb": ...}``):

- ``generate``: payload = 1-D int prompt; header carries
  ``max_new_tokens``, optional ``eos_id``, optional ``deadline_ms``
  (budget relative to arrival), optional ``sampling`` (a
  ``sampling.SamplingParams`` wire dict: temperature / top_k / top_p /
  seed / n / grammar; absent = greedy). Reply payload = the full
  sequence (prompt + generated, eos-trimmed) — or, for ``n > 1``
  parallel completions, the list of n sequences with ``n`` on the
  reply header. Failures reply
  ``{"ok": false, "error": code}`` with code ``overloaded`` (bounded
  admission queue full — explicit backpressure), ``deadline_exceeded``,
  or ``stopping`` (drain in progress).
- ``generate`` with ``stream: true``: the one verb that replies with
  MULTIPLE frames on the connection — zero or more
  ``{"stream": "chunk", "tokens": [...]}`` frames pushed as the
  scheduler emits them (one per scheduler iteration that advanced the
  slot), then a terminal ``{"stream": "end"}`` frame carrying the full
  sequence payload (or a typed error frame). TTFT becomes a real
  first-byte measurement: ``ServeRequest.first_sent`` is stamped when
  the first chunk frame flushes. After the terminal frame the
  connection returns to request/reply discipline. The chunk frames
  of EVERY stream are written by the server's one sender thread
  (``StreamSender``), which the scheduler wakes once an iteration;
  the connection's thread sleeps from ``submit`` to the sentinel and
  writes the terminal frame itself.
- ``prefill`` (disaggregated serving): same request shape as
  ``generate``; the engine runs admission + chunked prefill only and
  replies with the finished slot's state as a ``kv_transfer`` wire
  frame (reply payload) plus a ``transfer`` summary header — the
  prefill worker's half of the prefill/decode role split.
- ``kv.transfer``: payload = a ``kv_transfer`` frame from ``prefill``;
  the engine resumes the slot and decodes to completion (streamable
  with ``stream: true``). A corrupt/truncated frame replies typed
  ``kv_transfer``, never hangs.
- ``predict``: payload = (N, ...) feature rows; reply payload = the
  model's outputs (windowed-batched server-side).
- ``health`` / ``stats``: JSON-only replies. ``health`` carries engine
  liveness (``serving | degraded | draining``, heartbeat age,
  quarantined slots, the supervisor's restart ledger) plus
  ``max_frame_bytes`` so clients can self-limit. ``stats`` carries the
  scheduler counters (incl. prefill chunk/token counts, slot lifecycle
  occupancy, and the fault/recovery counters), the prefix-cache
  hit/miss/eviction state, the compiled prefill/chunk buckets, and the
  live connection count. ``overloaded`` error replies carry a
  ``retry_after_ms`` backoff hint.
- ``metrics``: the typed-registry snapshot (``obs.metrics``) —
  scheduler/engine/prefix-cache counters, gauges, and latency
  histograms as JSON samples; ``format: "prometheus"`` returns the
  text exposition dump instead (``tools/dkt_top.py`` polls this verb).
- ``timeseries``: windowed digests over the engine's metrics-history
  ring (``obs.MetricsHistory``) — per-series reset-aware rates,
  windowed histogram quantiles, EWMA/trend, sparkline-ready resampled
  points — plus the multi-window burn-rate SLO verdict when SLOs are
  configured. Header knobs: ``window`` (seconds, default 60),
  ``names`` (series filter), ``points`` (sparkline resolution).
- ``postmortem``: the engine's latest crash bundle (watchdog trip or
  permanent degradation — ``obs.dump_postmortem`` schema), or None;
  ``tools/dkt_postmortem.py`` renders it into an incident timeline.
- ``stop``: begins graceful shutdown — in-flight and queued requests
  complete, new ones are refused, then the listener closes.

Tracing (``obs.tracing``): a request header may carry an optional
``trace`` field (``TraceContext.to_wire``). ``generate`` then records
a ``server.generate`` span plus the scheduler's per-request phase
timeline (queue wait, prefill chunks, decode, blame), returned on the
reply when the client asked (``return`` flag). Typed ERROR replies
are stamped with the trace id (and the timeline, for a traced
generate) so client-side failures join server-side spans.
"""

from __future__ import annotations

import collections
import logging
import selectors
import socket
import threading
import time

import numpy as np

from distkeras_tpu import faults
from distkeras_tpu.networking import (
    recv_data,
    send_data,
    send_nowait,
    wire_bytes,
)
from distkeras_tpu.obs import stamp_error_trace as _stamp_trace
from distkeras_tpu.serving.scheduler import EngineStoppedError, ServingError
from distkeras_tpu.utils.profiling import annotate
from distkeras_tpu.utils.profiling import span as timeline_span
from distkeras_tpu.utils.serialization import (
    deserialize_params,
    pack_frame,
    serialize_params,
    unpack_frame,
)

_PROTOCOL = 1

logger = logging.getLogger(__name__)

_KILL = object()  # handed over in a chunk's place: the stream is given up


class _Stream:
    """One streamed request's connection as the sender holds it, and the
    sink its request is submitted with in a chunk FIFO's place: the
    scheduler calls ``push`` under its lock for every chunk and for the
    sentinel, and ``wake`` once where its emission closes."""

    __slots__ = ("sender", "wake", "sock", "buf", "marks", "sent",
                 "ending", "blocked", "dead", "done")

    def __init__(self, sender, sock):
        self.sender = sender
        self.wake = sender.wake  # equal for every stream of a sender
        self.sock = sock
        self.buf = bytearray()  # the outbox: bytes the socket has not taken
        # the outbox's frames, none wholly out yet: (its end among the
        # stream's bytes, request, tokens, hand-over instant)
        self.marks = collections.deque()
        self.sent = 0  # bytes the socket took, over the stream's life
        self.ending = False  # the sentinel's turn has come
        self.blocked = False  # waiting for the socket to take more
        self.dead = False
        # set with the sentinel's turn come and the outbox empty, or dead
        self.done = threading.Event()

    def push(self, req, tokens):
        # the hand-over's instant only where a trace will show it
        t0 = time.monotonic() if tokens and req.trace is not None else None
        self.sender._handed.append((self, req, tokens, t0))


class StreamSender:
    """The one thread that writes every stream's chunk frames.

    The scheduler appends ``(stream, request, tokens | None)`` to one
    queue in the order things happen (``_Stream.push``, under the lock
    its emission holds, so a sentinel never overtakes data) and wakes
    this thread ONCE an iteration. A pass takes everything handed
    over, packs each chunk's frame as the connection threads did
    (``pack_frame`` behind ``send_data``'s length prefix: the wire's
    bytes are unchanged) and writes each connection's frames in one
    ``send`` that never blocks (``MSG_DONTWAIT``; the socket's mode is
    left alone for its connection thread's ``recv``) and keeps the
    interpreter lock (``networking.send_nowait``): a pass is one turn
    at the lock, not one a frame, among the client threads its frames
    wake.

    Back-pressure: what a full socket buffer refuses stays in that
    stream's outbox (at most ``max_new_tokens`` frames, as its FIFO
    held), and the thread waits for that socket to turn writable
    beside its own wake. A slow or stalled reader delays only its own
    stream. A stream dies on a write error (the client went away; its
    decode completes idle) or an injected ``server.reply`` drop: its
    connection's thread, woken through ``done``, closes the socket.

    No system call on the scheduler's side while nothing is blocked:
    the wake is an event then, and a byte on a socket pair only while
    this thread sits in ``select``."""

    def __init__(self):
        self._handed = collections.deque()
        self._wake = threading.Event()
        self._lock = threading.Lock()  # start / stop, the live set, wakes
        self._thread = None
        self._stopping = False
        self._selecting = False
        self._streams: set = set()  # opened, not yet done
        self._blocked = 0  # of them, waiting for writability
        self._selector = self._wake_r = self._wake_w = None
        self._wakes = 0
        self._counts = dict.fromkeys(
            ("frames_sent", "coalesced_frames", "would_block",
             "outbox_peak", "dead_streams"), 0)

    # -- the scheduler's and the connection threads' side -------------------

    def open(self, sock) -> _Stream:
        """The sink to submit a streamed request with; starts the
        thread with the first."""
        with self._lock:
            if self._stopping:
                raise EngineStoppedError("server stopping")
            if self._thread is None:
                self._selector = selectors.DefaultSelector()
                self._wake_r, self._wake_w = socket.socketpair()
                self._wake_r.setblocking(False)
                self._selector.register(self._wake_r, selectors.EVENT_READ)
                self._thread = threading.Thread(
                    target=self._run, name="serving-stream-sender",
                    daemon=True,
                )
                self._thread.start()
            stream = _Stream(self, sock)
            self._streams.add(stream)
        return stream

    def discard(self, stream):
        """Forget a stream whose request was never admitted."""
        with self._lock:
            self._streams.discard(stream)

    def kill(self, stream, timeout=5.0):
        """Give ``stream`` up from outside (its thread's guard ran
        out): the sender lets go of the socket, then sets ``done``."""
        self._handed.append((stream, None, _KILL, None))
        self.wake()
        stream.done.wait(timeout)

    def wake(self):
        with self._lock:
            self._wakes += 1
        self._wake.set()
        if self._selecting:
            try:
                self._wake_w.send(b"\0")
            except OSError:
                pass  # stopped underneath: nothing left to wake

    def stop(self):
        """End the thread: what is still open dies (``done`` set), so
        no connection thread is left waiting on a stream."""
        with self._lock:
            self._stopping = True
            thread = self._thread
        if thread is None:
            return
        self.wake()
        thread.join(timeout=10)
        if not thread.is_alive():
            self._selector.close()
            self._wake_r.close()
            self._wake_w.close()

    def stats(self) -> dict:
        """``sender_wakes``: wakes the scheduler sent (one an emitting
        iteration, one a finish outside an emission); ``frames_sent``:
        chunk frames wholly written; ``frames_per_wake``: their ratio;
        ``coalesced_frames``: frames queued behind bytes still waiting,
        so sharing a write; ``would_block``: writes a full socket
        buffer cut short; ``outbox_peak``: most frames one stream had
        waiting; ``dead_streams``: streams ended by a write error, an
        injected drop or the server's stop."""
        out = dict(self._counts)
        out["sender_wakes"] = self._wakes
        out["frames_per_wake"] = out["frames_sent"] / max(self._wakes, 1)
        return out

    # -- the thread ---------------------------------------------------------

    def _run(self):
        try:
            while not self._stopping:
                self._flush(self._wait())
            self._flush(())  # what the engine's stop has just handed over
        except Exception:  # noqa: BLE001 — the thread's boundary
            logger.exception("stream sender died; its streams die with it")
        finally:
            with self._lock:
                self._stopping = True
                left = list(self._streams)
            for stream in left:
                self._die(stream)

    def _wait(self) -> list:
        """Sleep until woken or, with bytes refused, until a socket
        takes more; returns the streams whose socket does."""
        if not self._blocked:
            self._wake.wait()
            self._wake.clear()
            return []
        # ``wake`` reads ``_selecting`` after it has set the event, this
        # reads the event after it has set ``_selecting``: one of the
        # two sees the other, so no wake is slept through
        self._selecting = True
        events = () if self._wake.is_set() else self._selector.select()
        self._selecting = False
        self._wake.clear()
        ready = []
        for key, _ in events:
            if key.data is not None:
                ready.append(key.data)
                continue
            try:
                self._wake_r.recv(4096)
            except BlockingIOError:
                pass
        return ready

    def _flush(self, ready):
        """One pass: every hand-over so far, a connection at a time,
        then the sockets that turned writable."""
        by_stream = {}
        while True:
            try:
                item = self._handed.popleft()
            except IndexError:
                break
            by_stream.setdefault(item[0], []).append(item)
        if not by_stream and not ready:
            return
        with timeline_span("serving/stream_flush") as sp:
            sent0 = self._counts["frames_sent"]
            for stream, items in by_stream.items():
                self._guarded(stream, self._take, items)
            for stream in ready:
                if stream not in by_stream:
                    self._guarded(stream, self._write)
            sp.set_metadata(frames=self._counts["frames_sent"] - sent0,
                            streams=len(by_stream))

    def _guarded(self, stream, step, *args):
        """``step`` on a live stream; a write error is the stream's
        death (client went away; decode completes idle), and so is
        anything else, which must not take the other streams along."""
        if stream.dead:
            return
        try:
            step(stream, *args)
            if stream.ending and not stream.buf:
                self._close(stream)
        except (ConnectionError, OSError):
            self._die(stream)
        except Exception:  # noqa: BLE001 — one stream's boundary
            logger.exception("stream sender: a stream's pass failed")
            self._die(stream)

    def _take(self, stream, items):
        """``items``: this pass's hand-overs of one stream, in order.
        Each chunk becomes a frame in the outbox, under its own
        ``serving/stream_send`` span; the last one's span holds the
        write of them all."""
        chunks = [it for it in items if it[2] is not None]
        for k, (_st, req, tokens, t0) in enumerate(chunks):
            if tokens is _KILL:
                raise ConnectionError("stream given up by its thread")
            with annotate(
                "serving/stream_send", req=req.id, tokens=len(tokens)
            ):
                frame = pack_frame(
                    {"ok": True, "stream": "chunk", "tokens": tokens}
                )
                act = faults.fire("server.reply", nbytes=len(frame))
                if act == "drop":
                    # injected: vanish mid-stream, behind what was sent
                    self._write(stream)
                    raise ConnectionError("injected server.reply drop")
                if stream.buf:
                    self._counts["coalesced_frames"] += 1
                stream.buf += wire_bytes(stream.sock, frame)
                stream.marks.append(
                    (stream.sent + len(stream.buf), req, len(tokens), t0)
                )
                if len(stream.marks) > self._counts["outbox_peak"]:
                    self._counts["outbox_peak"] = len(stream.marks)
                if k == len(chunks) - 1:
                    self._write(stream)
        if len(chunks) < len(items):
            stream.ending = True  # the sentinel: nothing follows it

    def _write(self, stream):
        """Hand the socket the outbox without blocking; stamp the
        frames that left whole; wait for writability on what stays."""
        if stream.buf:
            try:
                n = send_nowait(stream.sock, stream.buf)
            except (BlockingIOError, InterruptedError):
                n = 0
            if n:
                stream.sent += n
                del stream.buf[:n]
                now = time.monotonic()
                while stream.marks and stream.marks[0][0] <= stream.sent:
                    _end, req, ntok, t0 = stream.marks.popleft()
                    self._counts["frames_sent"] += 1
                    if req.first_sent is None:
                        req.first_sent = now  # DELIVERY-time TTFT stamp
                    if t0 is not None:
                        # per-chunk trace span (rides the request
                        # ledger; the timeline's serving.stream_chunk
                        # children): hand-over to the frame's last byte
                        req.events.append({
                            "name": "serving.stream_chunk", "t0": t0,
                            "t1": now, "tokens": ntok,
                        })
            if stream.buf:
                self._counts["would_block"] += 1
        if bool(stream.buf) != stream.blocked:
            if stream.buf:
                self._selector.register(
                    stream.sock, selectors.EVENT_WRITE, stream
                )
            else:
                self._selector.unregister(stream.sock)
            stream.blocked = not stream.blocked
            self._blocked += 1 if stream.blocked else -1

    def _close(self, stream):
        with self._lock:
            self._streams.discard(stream)
        stream.done.set()

    def _die(self, stream):
        if stream.done.is_set():
            return
        stream.dead = True
        stream.buf.clear()
        stream.marks.clear()
        if stream.blocked:
            stream.blocked = False
            self._blocked -= 1
            try:
                self._selector.unregister(stream.sock)
            except (KeyError, ValueError, OSError):
                pass  # the seam's reset closed the socket itself
        self._counts["dead_streams"] += 1
        self._close(stream)


class ServingServer:
    """Serve one ``ServingEngine`` over TCP. ``port=0`` binds an
    ephemeral port (read it back from ``.port``)."""

    def __init__(self, engine, host="127.0.0.1", port=0, backlog=64,
                 max_frame_bytes=64 << 20, retry_after_ms=50.0):
        """``max_frame_bytes``: per-request frame cap enforced before
        buffering (the port accepts untrusted bytes; an unchecked
        length prefix is a one-client memory DoS). 64 MiB comfortably
        covers prompts and predict feature batches. It also rides the
        ``health`` reply so well-behaved clients can self-limit before
        sending. ``retry_after_ms``: the Retry-After-style hint stamped
        on ``overloaded`` replies — clients with a ``RetryPolicy`` back
        off by it instead of guessing."""
        self.engine = engine
        self.max_frame_bytes = int(max_frame_bytes)
        self.retry_after_ms = float(retry_after_ms)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, int(port)))
        self._sock.listen(int(backlog))
        self.host, self.port = self._sock.getsockname()[:2]
        self._accept_thread = None
        self._conn_threads: list[threading.Thread] = []
        self._conns: set[socket.socket] = set()
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self._shutdown_done = threading.Event()
        # every streamed request's chunk frames leave through this one
        # thread (started with the first stream); the engine reports its
        # counters as ``stats()["streams"]``
        self._sender = engine.stream_sender = StreamSender()
        reg = getattr(engine, "registry", None)
        if reg is not None:  # server-level gauge rides the engine book
            reg.gauge(
                "serving_server_open_connections",
                fn=lambda: len(self._conns),
            )

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ServingServer":
        self.engine.start()
        if self._accept_thread is None:
            self._accept_thread = threading.Thread(
                target=self._accept_loop, name="serving-accept",
                daemon=True,
            )
            self._accept_thread.start()
        return self

    def shutdown(self, drain=True):
        """Close the listener and stop the engine. ``drain=True`` lets
        queued and in-flight requests finish first (their connection
        threads stay alive until the replies are flushed).

        Idempotent AND awaitable: the ``stop`` verb runs shutdown on a
        side thread, so a second caller (the owner's ``shutdown()``, a
        ``with`` block's ``__exit__``) must not return while the first
        is still draining — it waits for completion instead of racing
        the teardown."""
        with self._lock:
            first = not self._stopping.is_set()
            self._stopping.set()
        if not first:
            self._shutdown_done.wait(timeout=90)
            return
        try:
            # shutdown BEFORE close: a bare close does not wake a
            # thread blocked in accept(), which would leak it and
            # stall the accept-thread join below for its full timeout
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
            self.engine.stop(drain=drain)
            with self._lock:
                threads = list(self._conn_threads)
            # short grace for threads flushing their last reply, then
            # force-close the sockets of the rest — an idle persistent
            # connection sits in recv_data forever and would otherwise
            # stall shutdown and leak its thread
            deadline = time.monotonic() + 5
            for th in threads:
                th.join(timeout=max(0.0, deadline - time.monotonic()))
            # a stream still open now (a reader that stopped reading)
            # dies here, which wakes its connection's thread
            self._sender.stop()
            with self._lock:
                lingering = list(self._conns)
            for conn in lingering:
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    conn.close()
                except OSError:
                    pass
            for th in threads:
                th.join(timeout=5)
            if self._accept_thread is not None:
                self._accept_thread.join(timeout=5)
        finally:
            self._shutdown_done.set()  # waiters must never hang on a crash

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()

    # -- connection handling ------------------------------------------------

    def _accept_loop(self):
        while not self._stopping.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            th = threading.Thread(
                target=self._serve_conn, args=(conn,),
                name="serving-conn", daemon=True,
            )
            with self._lock:
                self._conn_threads = [
                    t for t in self._conn_threads if t.is_alive()
                ]
                self._conn_threads.append(th)
                self._conns.add(conn)
                # started under the lock: shutdown() joins what it finds
                # in the list, and a thread cannot be joined before it
                # has been started
                th.start()

    def _serve_conn(self, conn: socket.socket):
        try:
            self._serve_frames(conn)
        finally:
            with self._lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_frames(self, conn: socket.socket):
        while True:
            try:
                frame = recv_data(conn, max_len=self.max_frame_bytes)
            except ValueError:
                # oversized declared frame: the stream position is
                # unrecoverable (bytes keep coming) — reply (marked
                # ``fatal`` so the client knows the close that follows
                # was deliberate and why) and close
                try:
                    send_data(conn, pack_frame(
                        {"ok": False, "error": "frame_too_large",
                         "fatal": True,
                         "max_frame_bytes": self.max_frame_bytes,
                         "detail": f"limit {self.max_frame_bytes} bytes"}
                    ))
                except (ConnectionError, OSError):
                    pass
                return
            except (ConnectionError, OSError):
                return
            header = {}
            try:
                header, payload = unpack_frame(frame)
                if header.get("stream") and header.get("verb") in (
                    "generate", "kv.transfer"
                ):
                    # the streaming path sends its own frames (chunks
                    # + terminal); everything else stays one-reply
                    if not self._serve_stream(conn, header, payload):
                        return
                    if self._stopping.is_set():
                        return
                    continue
                reply = self._dispatch(header, payload)
            except ServingError as e:
                h = {"ok": False, "error": e.code, "detail": str(e)}
                # Retry-After semantics: tell the client how long to
                # back off instead of letting the fleet guess — and
                # prefer the error's OWN hint (quota waits, shed-gate
                # sojourn estimates) over the server-wide constant
                if getattr(e, "retry_after", None) is not None:
                    h["retry_after_ms"] = e.retry_after * 1e3
                elif e.code == "overloaded":
                    h["retry_after_ms"] = self.retry_after_ms
                _stamp_trace(h, header, e)
                reply = pack_frame(h)
            except Exception as e:  # noqa: BLE001 — wire boundary
                h = {"ok": False, "error": "bad_request",
                     "detail": repr(e)}
                _stamp_trace(h, header, e)
                reply = pack_frame(h)
            act = faults.fire("server.reply", nbytes=len(reply))
            if act == "drop":
                return  # injected: vanish without replying (conn closes)
            try:
                send_data(conn, reply)
            except (ConnectionError, OSError):
                return
            if self._stopping.is_set():
                return

    # -- verbs --------------------------------------------------------------

    def _dispatch(self, header: dict, payload: bytes) -> bytes:
        verb = header.get("verb")
        faults.fire("server.dispatch", verb=verb)
        if verb in ("generate", "predict", "prefill", "kv.transfer",
                    "kv.fetch"):
            # the gray-failure seam: a delay armed here (filtered by
            # port) slows this replica's DATA path while its health
            # polls stay green — the failure shape circuit breakers
            # exist to catch
            faults.fire("net.delay", verb=verb, port=int(self.port))
        if verb == "generate":
            return self._generate(header, payload)
        if verb == "prefill":
            return self._prefill(header, payload)
        if verb == "kv.transfer":
            return self._transfer(header, payload)
        if verb == "kv.fetch":
            return self._kv_fetch(header, payload)
        if verb == "predict":
            return self._predict(payload)
        if verb == "metrics":
            # the typed-registry snapshot (scheduler/engine/prefix-
            # cache counters, gauges, latency histograms); format=
            # "prometheus" ships the text exposition dump instead
            samples = self.engine.metrics_snapshot()
            if header.get("format") == "prometheus":
                from distkeras_tpu.obs import render_prometheus

                return pack_frame(
                    {"ok": True, "format": "prometheus",
                     "text": render_prometheus(samples)}
                )
            return pack_frame({"ok": True, "metrics": samples})
        if verb == "timeseries":
            # windowed rate/quantile/trend digests over the engine's
            # metrics-history ring + the burn-rate SLO verdict; header
            # knobs: window (seconds), names (series filter), points
            # (sparkline resolution). history=False engines refuse
            # with bad_request (a ValueError at this boundary).
            return pack_frame(self.engine.timeseries(
                window=header.get("window"),
                names=header.get("names"),
                points=int(header.get("points") or 30),
            ))
        if verb == "postmortem":
            # the latest crash bundle (watchdog trip / degradation),
            # retrievable remotely so soak triage never needs shell
            # access to the serving host; None when nothing has died
            bundle, path = self.engine.postmortem()
            return pack_frame(
                {"ok": True, "postmortem": bundle, "path": path}
            )
        if verb == "health":
            # engine liveness (serving|degraded|draining, heartbeat age,
            # quarantine + restart ledger) plus the server's own limits,
            # so clients can self-limit frame sizes before sending
            h = {
                "ok": True,
                "protocol": _PROTOCOL,
                "max_frame_bytes": self.max_frame_bytes,
                # the server's canonical bound address: a fleet router
                # keys its rotation on this, and a health reply that
                # names its endpoint is self-describing in logs
                "endpoint": [self.host, int(self.port)],
            }
            h.update(self.engine.health())
            if self._stopping.is_set():
                h["status"] = "draining"
            return pack_frame(h)
        if verb == "stats":
            stats = self.engine.stats()
            # server-level observability rides the same verb: scheduler
            # counters, slot lifecycle (prefilling vs decoding), prefix-
            # cache hit/miss/eviction state, and live connection count
            with self._lock:
                stats["open_connections"] = len(self._conns)
            return pack_frame({"ok": True, "stats": stats})
        if verb == "stop":
            # reply first, then drain on a side thread so the client
            # gets its ack before the listener goes away
            threading.Thread(
                target=self.shutdown, kwargs={"drain": True}, daemon=True
            ).start()
            return pack_frame({"ok": True, "stopping": True})
        raise ValueError(f"unknown verb {verb!r}")

    def _generate(self, header: dict, payload: bytes) -> bytes:
        from distkeras_tpu.obs import TraceContext, request_spans, start_span
        from distkeras_tpu.serving.sampling import SamplingParams

        prompt = np.asarray(deserialize_params(payload))
        deadline = None
        if header.get("deadline_ms") is not None:
            deadline = time.monotonic() + float(header["deadline_ms"]) / 1e3
        # per-request sampling params ride an optional header field
        # (absent = the greedy no-params path, one dict lookup); a
        # malformed spec is a submit-boundary ValueError -> bad_request
        sampling = SamplingParams.from_wire(header.get("sampling"))
        # opt-in tracing: absent field = one dict lookup and nothing
        # else; present = a server.generate span plus the scheduler's
        # per-request phase timeline, returned on the reply when the
        # client asked for it (``return`` in the wire field)
        ctx = TraceContext.from_wire(header.get("trace"))
        span = None
        col = None
        if ctx is not None:
            from distkeras_tpu.obs import COLLECTOR

            # this engine's own span ring (drained to ITS MetricsLogger)
            col = getattr(self.engine, "trace_collector", None) or COLLECTOR
            attrs = {}
            if sampling is not None:
                # sampler params on the span: a sampled request's trace
                # names what it asked for (replayable from the trace)
                attrs["sampling"] = sampling.to_wire()
            span = start_span(
                "server.generate", ctx, collector=col,
                prompt_len=int(prompt.size),
                max_new_tokens=int(header["max_new_tokens"]),
                **attrs,
            )
        req = None

        def assemble_trace(status):
            """End the server span with ``status`` and build the reply's
            ``trace`` dict (timeline included when the client asked):
            the one assembly every exit path — ok, typed, untyped —
            shares, so they cannot drift apart."""
            spans = (
                []
                if req is None
                else request_spans(req, ctx, collector=col)
            )
            spans.append(span.end(status=status))
            tr = {"id": ctx.trace_id}
            if ctx.want_timeline:
                tr["timeline"] = spans
            return tr

        try:
            req = self.engine.submit(
                prompt,
                int(header["max_new_tokens"]),
                eos_id=header.get("eos_id"),
                deadline=deadline,
                trace=ctx,
                sampling=sampling,
                # QoS identity rides two optional header fields (absent
                # = default tenant, priority 0 — the pre-QoS wire)
                tenant=header.get("tenant"),
                priority=int(header.get("priority") or 0),
                # the router's page-affinity hint: siblings whose
                # digest covered this prompt (fail-soft peer fetch)
                kv_peers=header.get("kv_peers"),
            )
            seq = self.engine.wait(req)
        except ServingError as e:
            if ctx is not None:
                e.trace = assemble_trace(e.code)
            raise
        except Exception as e:  # noqa: BLE001 — the wire boundary
            # replies generic bad_request for non-typed failures; the
            # span must still end (and hit the collector/JSONL sink)
            # or exactly the untyped failure class vanishes from traces
            if ctx is not None:
                tr = assemble_trace("bad_request")
                try:
                    e.trace = tr
                except AttributeError:
                    pass  # exotic exception refusing attributes
            raise
        if isinstance(seq, list):
            # n-parallel completions: the payload is the LIST of
            # sequences (the pytree codec carries ragged lengths)
            reply = {
                "ok": True,
                "n": len(seq),
                "tokens": int(sum(s.size - prompt.size for s in seq)),
            }
            if ctx is not None:
                reply["trace"] = assemble_trace("ok")
            return pack_frame(
                reply, serialize_params([np.asarray(s) for s in seq])
            )
        reply = {"ok": True, "tokens": int(seq.size - prompt.size)}
        if ctx is not None:
            reply["trace"] = assemble_trace("ok")
        return pack_frame(reply, serialize_params(np.asarray(seq)))

    @staticmethod
    def _deadline_of(header: dict):
        if header.get("deadline_ms") is None:
            return None
        return time.monotonic() + float(header["deadline_ms"]) / 1e3

    def _prefill(self, header: dict, payload: bytes) -> bytes:
        """Disaggregated prefill: admission + chunked prefill, then
        the finished slot's state as a ``kv_transfer`` frame (the
        reply payload). Typed failures ride the normal error path —
        ``wrong_role`` on a decode engine, ``overloaded`` under
        pressure, ``kv_transfer`` if encoding failed.

        With a ``push_to`` header ([host, port] — the router's chosen
        decode worker), the frame is PUSHED point-to-point over this
        engine's peer fabric instead of relayed through the router:
        the decode's final reply comes back here and is relayed to
        the router with ``pushed: true``. Fail-soft: any push failure
        — wire death, breaker open, a typed decode refusal — returns
        the frame to the router (``pushed: false`` + the blob as
        payload), whose relay loop finishes the hop the pre-fabric
        way; the prefill work is never wasted."""
        t0 = time.monotonic()
        prompt = np.asarray(deserialize_params(payload))
        blob, meta = self.engine.prefill(
            prompt, int(header["max_new_tokens"]),
            eos_id=header.get("eos_id"),
            deadline=self._deadline_of(header),
            sampling=header.get("sampling"),
            tenant=header.get("tenant"),
            priority=int(header.get("priority") or 0),
        )
        push_to = header.get("push_to")
        if push_to:
            return self._push(header, blob, meta, push_to, t0)
        return pack_frame({"ok": True, "transfer": meta}, blob)

    def _push(self, header: dict, blob: bytes, meta: dict, push_to,
              t0: float) -> bytes:
        """The direct-push leg of the disagg hop (see ``_prefill``)."""

        def degrade(code, detail):
            return pack_frame(
                {"ok": True, "pushed": False, "transfer": meta,
                 "push_error": code, "push_detail": str(detail)[:200]},
                blob,
            )

        theader = {
            "verb": "kv.transfer",
            "max_new_tokens": int(header["max_new_tokens"]),
        }
        for k in ("eos_id", "tenant", "priority", "request_id"):
            if header.get(k) is not None:
                theader[k] = header[k]
        if header.get("deadline_ms") is not None:
            # the request's budget was set at router arrival; the
            # decode hop gets what prefill left of it — a budget
            # already spent degrades (the router owns the deadline
            # verdict, and the frame must not decode past it)
            left = float(header["deadline_ms"]) - (
                (time.monotonic() - t0) * 1e3
            )
            if left <= 0:
                return degrade("deadline_exceeded",
                               "deadline spent during prefill")
            theader["deadline_ms"] = left
        try:
            reply, body = self.engine.peer_fabric.push(
                tuple(push_to), theader, blob
            )
        except Exception as e:  # noqa: BLE001 — fail-soft boundary
            return degrade(getattr(e, "code", "kv_peer"), e)
        if not reply.get("ok"):
            # a typed decode refusal (overloaded, kv_transfer, ...):
            # hand the frame back — the router's relay loop owns
            # sibling retries and must keep its PR 14 semantics
            return degrade(reply.get("error", "kv_peer"),
                           reply.get("detail", ""))
        out = dict(reply)
        out["pushed"] = True
        out["transfer"] = meta
        return pack_frame(out, body or b"")

    def _kv_fetch(self, header: dict, payload: bytes) -> bytes:
        """Fleet KV fabric: serve the longest locally-cached prefix
        of the requested tokens as a DKTX frame (see
        ``ServingEngine.serve_prefix``). Typed failures — stale
        epoch, no cache — ride the normal error path; a plain miss
        is an ``ok`` reply with ``hit: false``."""
        tokens = np.asarray(deserialize_params(payload))
        blob, reply = self.engine.serve_prefix(
            tokens, epoch=header.get("epoch")
        )
        if blob is None:
            return pack_frame(reply)
        return pack_frame(reply, blob)

    def _transfer(self, header: dict, payload: bytes) -> bytes:
        """Disaggregated decode (non-streaming): resume a transferred
        slot and decode it to completion. The reply mirrors
        ``generate``'s (full sequence payload), so the router can
        relay either interchangeably."""
        req = self.engine.resume(
            payload, int(header["max_new_tokens"]),
            eos_id=header.get("eos_id"),
            deadline=self._deadline_of(header),
            tenant=header.get("tenant"),
            priority=int(header.get("priority") or 0),
        )
        seq = self.engine.wait(req)
        return pack_frame(
            {"ok": True,
             "tokens": int(np.asarray(seq).size - req.prompt.size)},
            serialize_params(np.asarray(seq)),
        )

    def _serve_stream(self, conn: socket.socket, header: dict,
                      payload: bytes) -> bool:
        """Streaming ``generate`` / ``kv.transfer``: submit with the
        sender's sink, which writes one ``stream: "chunk"`` frame per
        scheduler iteration that advanced the slot while this thread
        sleeps; then the terminal ``stream: "end"`` frame with the
        full sequence payload (identity stays assertable downstream)
        or a typed error frame, from this thread. Returns False when
        the connection is no longer usable (died mid-stream /
        injected drop). The first chunk's flush stamps
        ``req.first_sent`` — the delivery-time TTFT ``latency()``
        reports."""
        from distkeras_tpu.obs import TraceContext, request_spans, start_span

        verb = header.get("verb")
        faults.fire("server.dispatch", verb=verb)
        faults.fire("net.delay", verb=verb, port=int(self.port))
        ctx = TraceContext.from_wire(header.get("trace"))
        span = col = None
        if ctx is not None:
            from distkeras_tpu.obs import COLLECTOR

            col = getattr(self.engine, "trace_collector", None) or COLLECTOR
            span = start_span(
                "server.generate", ctx, collector=col, stream=True,
                max_new_tokens=int(header["max_new_tokens"]),
            )
        req = None

        def send_error(e, code=None):
            h = {"ok": False, "error": code or getattr(e, "code", "bad_request"),
                 "detail": repr(e) if code == "bad_request" else str(e)}
            if getattr(e, "retry_after", None) is not None:
                h["retry_after_ms"] = e.retry_after * 1e3
            elif h["error"] == "overloaded":
                h["retry_after_ms"] = self.retry_after_ms
            if span is not None:
                spans = (
                    [] if req is None
                    else request_spans(req, ctx, collector=col)
                )
                spans.append(span.end(status=h["error"]))
                h["trace"] = {"id": ctx.trace_id}
                if ctx.want_timeline:
                    h["trace"]["timeline"] = spans
            else:
                _stamp_trace(h, header, e)
            try:
                send_data(conn, pack_frame(h))
                return True
            except (ConnectionError, OSError):
                return False

        stream = None
        try:
            stream = self._sender.open(conn)
            if verb == "generate":
                from distkeras_tpu.serving.sampling import SamplingParams

                prompt = np.asarray(deserialize_params(payload))
                req = self.engine.submit(
                    prompt, int(header["max_new_tokens"]),
                    eos_id=header.get("eos_id"),
                    deadline=self._deadline_of(header),
                    trace=ctx,
                    sampling=SamplingParams.from_wire(
                        header.get("sampling")
                    ),
                    tenant=header.get("tenant"),
                    priority=int(header.get("priority") or 0),
                    stream=stream,
                    kv_peers=header.get("kv_peers"),
                )
            else:
                req = self.engine.resume(
                    payload, int(header["max_new_tokens"]),
                    eos_id=header.get("eos_id"),
                    deadline=self._deadline_of(header),
                    trace=ctx,
                    tenant=header.get("tenant"),
                    priority=int(header.get("priority") or 0),
                    stream=stream,
                )
        except Exception as e:  # noqa: BLE001 — wire boundary
            if stream is not None:
                self._sender.discard(stream)  # nothing was admitted
            if isinstance(e, ServingError):
                return send_error(e)
            return send_error(e, code="bad_request")
        # generous bound: the engine watchdog fails a wedged scheduler's
        # requests typed long before this fires — the timeout is the
        # belt to that suspender
        if not stream.done.wait(timeout=600.0):
            self._sender.kill(stream)
            send_error(
                TimeoutError(
                    f"request {req.id}: no stream end in 600.0s"
                ),
                code="internal",
            )
            return False
        if stream.dead:
            return False  # client went away (or injected drop)
        try:
            seq = self.engine.wait(req)  # completion bookkeeping
        except ServingError as e:
            return send_error(e)
        reply = {"ok": True, "stream": "end", "tokens": len(req.tokens)}
        if span is not None:
            spans = request_spans(req, ctx, collector=col)
            spans.append(span.end(status="ok"))
            reply["trace"] = {"id": ctx.trace_id}
            if ctx.want_timeline:
                reply["trace"]["timeline"] = spans
        frame = pack_frame(reply, serialize_params(np.asarray(seq)))
        act = faults.fire("server.reply", nbytes=len(frame))
        if act == "drop":
            return False
        try:
            send_data(conn, frame)
        except (ConnectionError, OSError):
            return False
        return True

    def _predict(self, payload: bytes) -> bytes:
        x = np.asarray(deserialize_params(payload))
        y = self.engine.predict(x)
        return pack_frame({"ok": True}, serialize_params(np.asarray(y)))


def serve(engine, host="127.0.0.1", port=0) -> ServingServer:
    """Convenience: construct + start in one call."""
    return ServingServer(engine, host=host, port=port).start()
