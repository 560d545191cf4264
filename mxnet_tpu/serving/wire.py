"""Binary dispatch wire: the persistent router↔engine transport.

Two things live here:

1. **The typed, NON-EXECUTABLE frame codec.** Born in ``kvstore.py``
   for the dist_async parameter-server channel (its first cut spoke
   pickled frames — i.e. any peer that could reach the port could run
   arbitrary code), it is now the repo's ONE wire encoding, shared by
   the dist_async RPCs and the serving dispatch protocol below:
   a tagged tree of plain data (None/bool/int/float/str/bytes/dict/
   tuple) plus ndarrays as a struct header (dtype, shape) + raw buffer
   bytes. Decoding can only ever build data, never import or call
   anything; every malformed-frame failure surfaces as ``ValueError``
   so servers have ONE refusal path, and frame/ndarray sizes are
   capped (no 'length bomb' allocations).

2. **The dispatch protocol** replacing the router's JSON-over-HTTP
   long-poll (`_RemoteSeat` used to pay a fresh TCP connection, a
   dedicated waiter thread, and a full ``tokens.tolist()`` → JSON →
   ``np.asarray`` round-trip per in-flight request):

   - :class:`WireListener` — the engine side, started from
     ``ServingEngine.expose()`` alongside the HTTP server
     (``MXNET_TPU_WIRE*`` knobs). One reader thread per accepted
     connection feeds the existing submit path; results ride back
     through a per-connection writer thread, so a slow peer can never
     stall the engine worker.
   - :class:`WireClient` — the router side: a small pool of
     PERSISTENT multiplexed connections (``MXNET_TPU_WIRE_CONNS``).
     A single reader thread per connection demuxes RESULT/ERROR
     frames by correlation id — zero threads spawned per request.

   Frames are codec-encoded tuples, length-prefixed on the stream::

       ("HELLO",  {client/engine identity, "version": 1})
       ("SUBMIT", corr_id, {"tokens": int32 ndarray, "token_types",
                            "deadline_ms", "trace_id", "span_id",
                            tenancy: "model_id", "tenant",
                            "tenant_class",
                            decode: "max_new_tokens", "eos_id",
                            "stream", "temperature", "top_k", "top_p",
                            "seed"})
       ("RESULT", corr_id, {"result": ndarray, "cost", "breakdown",
                            "engine_ms", "trace_id"})
       ("ERROR",  corr_id, {"error_type", "error"})
       ("PING", n) / ("PONG", n)

   The decode sampling fields ride the SUBMIT frame itself (validated
   at engine admission — an out-of-range value comes back as an ERROR
   frame with ``error_type: InvalidSamplingError``, never a NaN from
   the compiled step), so a router re-dispatching the request after a
   seat failure replays the SAME seed: the replacement seat resamples
   the identical token sequence and the part-index dedupe works on
   sampled streams exactly as on greedy ones.

   Raw typed ndarray payloads — no ``tolist()`` — are the point: the
   dominant per-request overhead at high QPS was serialization.
   ``trace_id``/``span_id`` ride the SUBMIT frame so engine-side span
   trees parent under the router's ``router/request`` root exactly as
   they did over HTTP (the same crossing the dist_async wire uses).

Hostile-frame discipline (mirrors the dist_async server): an
undecodable or oversized frame refuses THE CONNECTION (the stream has
lost framing), an unknown frame type or garbage correlation id errors
THE FRAME (framing is intact), and neither ever kills the process.
"""
from __future__ import annotations

import itertools
import socket
import struct
import threading
import time
from collections import deque

import numpy as np

from .. import envvars
from ..base import MXNetError
from ..retrying import Reconnector
from ..telemetry import events as _events
from . import metrics as _metrics

__all__ = ["wire_encode", "wire_decode", "send_frame", "recv_frame",
           "WireError", "FrameTooLargeError", "WireListener",
           "WireClient", "PROTOCOL_VERSION"]

PROTOCOL_VERSION = 1

FRAME_HELLO = "HELLO"
FRAME_SUBMIT = "SUBMIT"
FRAME_RESULT = "RESULT"
FRAME_ERROR = "ERROR"
FRAME_PING = "PING"
FRAME_PONG = "PONG"


class WireError(MXNetError):
    """A dispatch-wire transport failure (connection down, handshake
    mismatch, in-flight request orphaned). The router maps it onto
    :class:`~.router.RemoteEngineError` — i.e. failover-eligible."""


class FrameTooLargeError(MXNetError, ValueError):
    """A length prefix (or ndarray header) promises more bytes than the
    channel's cap — refused BEFORE allocation. Subclasses ValueError
    (the codec's single refusal type) and MXNetError (what kvstore's
    dist_async channel historically raised here)."""


# -- typed frame codec ------------------------------------------------------
#   N none | T true | F false | i int64 | f float64
#   s utf-8 str | b bytes        (u32 length prefix)
#   a ndarray: u8 dtype-str-len + dtype.str + u8 ndim + u64*ndim + raw
#   l tuple:  u32 count + items
#   d dict:   u32 count + key/value item pairs
_WIRE_MAX_DEPTH = 16
MAX_FRAME_DEFAULT = 1 << 33        # 8 GiB: dist_async pushes big grads


def _enc(obj, out, depth=0):
    if depth > _WIRE_MAX_DEPTH:
        raise ValueError("wire object nests too deep")
    if obj is None:
        out.append(b"N")
    elif obj is True:
        out.append(b"T")
    elif obj is False:
        out.append(b"F")
    elif isinstance(obj, (int, np.integer)):
        out.append(b"i" + struct.pack("<q", int(obj)))
    elif isinstance(obj, (float, np.floating)):
        out.append(b"f" + struct.pack("<d", float(obj)))
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        out.append(b"s" + struct.pack("<I", len(raw)) + raw)
    elif isinstance(obj, (bytes, bytearray)):
        out.append(b"b" + struct.pack("<I", len(obj)) + bytes(obj))
    elif isinstance(obj, np.ndarray):
        if obj.dtype.hasobject:
            raise ValueError("object arrays are not wire-encodable")
        dt = obj.dtype.str.encode("ascii")
        out.append(b"a" + struct.pack("<B", len(dt)) + dt
                   + struct.pack("<B", obj.ndim)
                   + struct.pack(f"<{obj.ndim}Q", *obj.shape))
        out.append(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, (list, tuple)):
        out.append(b"l" + struct.pack("<I", len(obj)))
        for item in obj:
            _enc(item, out, depth + 1)
    elif isinstance(obj, dict):
        out.append(b"d" + struct.pack("<I", len(obj)))
        for k, v in obj.items():
            _enc(k, out, depth + 1)
            _enc(v, out, depth + 1)
    else:
        raise ValueError(
            f"type {type(obj).__name__} is not wire-encodable (only "
            "plain data rides the wire)")
    return out


def _dec(buf, pos, depth=0):
    if depth > _WIRE_MAX_DEPTH:
        raise ValueError("wire object nests too deep")
    tag = buf[pos:pos + 1]
    pos += 1
    if tag == b"N":
        return None, pos
    if tag == b"T":
        return True, pos
    if tag == b"F":
        return False, pos
    if tag == b"i":
        return struct.unpack_from("<q", buf, pos)[0], pos + 8
    if tag == b"f":
        return struct.unpack_from("<d", buf, pos)[0], pos + 8
    if tag in (b"s", b"b"):
        (n,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        raw = bytes(buf[pos:pos + n])
        if len(raw) != n:
            raise ValueError("truncated wire frame")
        return (raw.decode("utf-8") if tag == b"s" else raw), pos + n
    if tag == b"a":
        (dl,) = struct.unpack_from("<B", buf, pos)
        pos += 1
        dt = np.dtype(bytes(buf[pos:pos + dl]).decode("ascii"))
        pos += dl
        if dt.hasobject:
            raise ValueError("object arrays are not wire-decodable")
        (ndim,) = struct.unpack_from("<B", buf, pos)
        pos += 1
        shape = struct.unpack_from(f"<{ndim}Q", buf, pos)
        pos += 8 * ndim
        count = int(np.prod(shape, dtype=np.int64)) if ndim else 1
        nbytes = count * dt.itemsize
        if nbytes > MAX_FRAME_DEFAULT or pos + nbytes > len(buf):
            raise ValueError("truncated/oversized ndarray frame")
        arr = np.frombuffer(buf, dt, count=count, offset=pos).reshape(shape)
        return arr.copy(), pos + nbytes   # copy: own the memory
    if tag == b"l":
        (n,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        items = []
        for _ in range(n):
            item, pos = _dec(buf, pos, depth + 1)
            items.append(item)
        return tuple(items), pos
    if tag == b"d":
        (n,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        out = {}
        for _ in range(n):
            k, pos = _dec(buf, pos, depth + 1)
            v, pos = _dec(buf, pos, depth + 1)
            out[k] = v
        return out, pos
    raise ValueError(f"unknown wire tag {bytes(tag)!r} — refusing frame")


def wire_encode(obj) -> bytes:
    return b"".join(_enc(obj, []))


def wire_decode(data) -> object:
    try:
        obj, pos = _dec(memoryview(data), 0)
    except ValueError:
        raise
    except (struct.error, TypeError, UnicodeDecodeError, IndexError,
            OverflowError, MemoryError) as e:
        # every malformed-frame failure surfaces as ValueError so the
        # server's bad-frame handling has ONE refusal path
        raise ValueError(f"malformed wire frame: {e!r}") from e
    if pos != len(data):
        raise ValueError("trailing bytes in wire frame")
    return obj


def send_frame(sock, obj, max_frame=None):
    """Encode + length-prefix + send; returns the frame's byte size so
    callers can account wire traffic without re-encoding."""
    data = wire_encode(obj)
    cap = max_frame if max_frame is not None else MAX_FRAME_DEFAULT
    if len(data) > cap:
        raise FrameTooLargeError(
            f"wire frame of {len(data)} bytes exceeds the cap ({cap})")
    sock.sendall(struct.pack("<Q", len(data)) + data)
    return len(data)


def recv_frame(sock, max_frame=None):
    """(decoded object, frame bytes) — None on a cleanly closed peer.
    A length prefix past ``max_frame`` raises BEFORE allocating."""
    hdr = b""
    while len(hdr) < 8:
        chunk = sock.recv(8 - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (n,) = struct.unpack("<Q", hdr)
    cap = max_frame if max_frame is not None else MAX_FRAME_DEFAULT
    if n > cap:
        raise FrameTooLargeError(
            f"wire frame of {n} bytes exceeds the cap ({cap})")
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            return None
        buf += chunk
    return wire_decode(bytes(buf)), n


def _max_frame_bytes():
    return int(envvars.get("MXNET_TPU_WIRE_MAX_FRAME_MB")) << 20


# -- shared plumbing --------------------------------------------------------
class _FrameWriter:
    """The WRITE half of one wire socket: frames queue here and a
    dedicated writer thread encodes + sends them. Completion callbacks
    (which run on the engine's worker thread) and the router's
    dispatcher therefore NEVER block on a slow peer's socket — the one
    thread that may is this writer, whose stall harms only its own
    connection."""

    def __init__(self, sock, name, max_frame, on_sent=None):
        self._sock = sock
        self._max_frame = max_frame
        self._on_sent = on_sent       # (frame_tag, nbytes) accounting
        self._dq = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def send(self, frame):
        """Queue one frame; False when the writer is already closed
        (the caller's peer is gone — nothing to do with the frame)."""
        with self._cv:
            if self._closed:
                return False
            self._dq.append(frame)
            self._cv.notify()
        return True

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def _run(self):
        while True:
            with self._cv:
                while not self._dq and not self._closed:
                    self._cv.wait(0.5)
                if not self._dq:
                    return              # closed and drained
                frame = self._dq.popleft()
            try:
                n = send_frame(self._sock, frame,
                               max_frame=self._max_frame)
            except (OSError, ValueError) as e:
                # peer gone or frame unencodable: this connection is
                # done; the owner notices via its reader (EOF) — leave
                # a trace rather than dying silently (thread-hygiene)
                _events.emit("wire_writer_error", error=repr(e))
                self.close()
                return
            if self._on_sent is not None:
                tag = frame[0] if isinstance(frame, tuple) and frame \
                    else "?"
                self._on_sent(tag, n)


def _hard_close(sock):
    """shutdown(SHUT_RDWR) + close. A bare ``close()`` on a socket
    whose OWN reader thread is blocked in ``recv`` does not release
    the kernel socket on Linux (the in-flight syscall holds the file
    reference) — no FIN is sent, the PEER never sees EOF, and a
    killed connection looks alive from the other side forever.
    ``shutdown`` tears the TCP stream down immediately and wakes the
    blocked reader regardless."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


def _safe_callback(cb, *args):
    """Invoke a completion callback; a broken observer must not kill
    the wire thread that delivered its result (same contract as
    InferenceFuture callbacks)."""
    try:
        cb(*args)
    except Exception as e:
        _events.emit("wire_callback_error", error=repr(e))


# -- engine side ------------------------------------------------------------
class WireListener:
    """Binary dispatch listener for one :class:`~.engine.ServingEngine`
    — or, with ``handler=``, for any frame-served peer surface (the
    router's active/active HA journal channel reuses exactly this
    listener with a synchronous handler instead of an engine).

    Started by ``ServingEngine.expose()`` next to the HTTP exposition
    server (``MXNET_TPU_WIRE=0`` opts out); the port is advertised in
    ``/healthz`` as ``wire_port`` so a fronting router can upgrade its
    dispatch transport without configuration. The submit path is the
    ENGINE's — admission errors ride back as ERROR frames carrying the
    serving error's class name, results as RESULT frames with the
    raw typed ndarray (no ``tolist()``) plus the request's amortized
    cost bill and the engine-observed wall (``engine_ms``, the router's
    dispatch-overhead baseline).

    ``handler(payload_dict) -> body_dict`` (when given) serves each
    SUBMIT frame synchronously on the connection's reader thread —
    right for instant bookkeeping ops (the HA journal), wrong for
    model forwards (which keep the engine's async future path). A
    raising handler errors THE FRAME with the exception's class name,
    never the connection.
    """

    def __init__(self, engine=None, host="127.0.0.1", port=None,
                 max_frame=None, owner_id=None, handler=None,
                 side="engine"):
        if engine is None and handler is None:
            raise ValueError("WireListener needs an engine or a handler")
        self._engine = engine
        self._handler = handler
        self._owner_id = str(owner_id) if owner_id is not None \
            else (engine.engine_id if engine is not None else "?")
        self._side = str(side)
        self._max_frame = (int(max_frame) if max_frame is not None
                           else _max_frame_bytes())
        eid = self._owner_id
        frames = _metrics.wire_frames_counter()
        self._f_in = {}
        self._f_out = {}
        self._frames = frames
        byt = _metrics.wire_bytes_counter()
        self._b_in = byt.labels(side=self._side, transport="wire",
                                direction="in")
        self._b_out = byt.labels(side=self._side, transport="wire",
                                 direction="out")
        self._conns_g = _metrics.wire_connections_gauge() \
            .labels(side=self._side)
        self._refusals = _metrics.wire_refusals_counter() \
            .labels(side=self._side)
        self._closed = False
        self._lock = threading.Lock()
        self._open = set()            # live connection sockets
        # chaos receive hook (serving.chaos): None when chaos is off —
        # nothing is patched, the per-frame cost is one attribute read
        self.chaos_rx = None
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        want = int(port if port is not None
                   else envvars.get("MXNET_TPU_WIRE_PORT"))
        try:
            srv.bind((host, want))
        except OSError:
            if not want:
                raise
            # the configured port is taken (two engines in one
            # process): an ephemeral port beats no wire at all — the
            # router discovers whatever /healthz advertises
            _events.emit("wire_port_fallback", engine_id=eid, port=want)
            srv.bind((host, 0))
        srv.listen(16)
        self._srv = srv
        threading.Thread(target=self._accept_loop,
                         name=f"mxnet_tpu_wire_accept_{eid}",
                         daemon=True).start()
        _events.emit("wire_listen", engine_id=eid, host=host,
                     port=self.port)

    @property
    def port(self):
        return self._srv.getsockname()[1]

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            conns = list(self._open)
        try:
            self._srv.close()
        except OSError:
            pass
        for conn in conns:
            _hard_close(conn)         # unblocks readers, FINs peers

    def kill_connections(self):
        """Abruptly close every ACCEPTED connection (the listener keeps
        listening — peers reconnect). The chaos harness's
        ``kill_wire`` fault; also a handy drill primitive. Returns the
        number of connections killed."""
        with self._lock:
            conns = list(self._open)
        for conn in conns:
            # shutdown, not just close: this conn's own reader thread
            # is blocked in recv, and without SHUT_RDWR no FIN ever
            # reaches the peer — the "killed" connection would look
            # alive from the router side indefinitely
            _hard_close(conn)
        return len(conns)

    def _count_in(self, tag, n):
        child = self._f_in.get(tag)
        if child is None:
            child = self._f_in[tag] = self._frames.labels(
                side="engine", direction="in", frame=str(tag))
        child.inc()
        self._b_in.inc(n)

    def _count_out(self, tag, n):
        child = self._f_out.get(tag)
        if child is None:
            child = self._f_out[tag] = self._frames.labels(
                side="engine", direction="out", frame=str(tag))
        child.inc()
        self._b_out.inc(n)

    def _accept_loop(self):
        while True:
            try:
                conn, peer = self._srv.accept()
            except OSError:
                return
            with self._lock:
                if self._closed:
                    try:
                        conn.close()
                    except OSError:
                        pass
                    return
                self._open.add(conn)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(
                target=self._serve, args=(conn, peer),
                name=f"mxnet_tpu_wire_serve_fd{conn.fileno()}",
                daemon=True).start()

    def _serve(self, conn, peer):
        eid = self._owner_id
        self._conns_g.inc()
        writer = _FrameWriter(
            conn, f"mxnet_tpu_wire_write_fd{conn.fileno()}",
            self._max_frame, on_sent=self._count_out)
        try:
            while True:
                got = recv_frame(conn, max_frame=self._max_frame)
                if got is None:
                    return
                frame, nbytes = got
                if not isinstance(frame, tuple) or not frame:
                    raise ValueError(
                        "dispatch frame must be a tagged tuple, got "
                        f"{type(frame).__name__}")
                tag = frame[0]
                self._count_in(tag if isinstance(tag, str) else "?",
                               nbytes)
                rx = self.chaos_rx
                if rx is not None and not rx(tag):
                    continue        # chaos dropped the inbound frame
                if tag == FRAME_PING:
                    writer.send((FRAME_PONG,) + tuple(frame[1:2]))
                elif tag == FRAME_HELLO:
                    writer.send((FRAME_HELLO,
                                 {"engine_id": eid,
                                  "version": PROTOCOL_VERSION,
                                  "max_frame": self._max_frame}))
                elif tag == FRAME_SUBMIT:
                    self._handle_submit(frame, writer)
                else:
                    # unknown frame TYPE with intact framing: error the
                    # frame, keep the connection (a newer peer may mix
                    # frame kinds this engine predates)
                    corr = frame[1] if len(frame) > 1 \
                        and isinstance(frame[1], int) else None
                    self._error_frame(writer, corr,
                                      f"unknown frame type {tag!r}")
        except (ValueError, MXNetError) as e:
            # undecodable / oversized / mistyped frame: the STREAM has
            # lost framing — drop this client, keep serving the rest
            self._refusals.inc()
            _events.emit("wire_frame_refused", engine_id=eid,
                         peer=str(peer), error=str(e))
            return
        except (ConnectionError, EOFError, OSError):
            return
        finally:
            writer.close()
            with self._lock:
                self._open.discard(conn)
            try:
                conn.close()
            except OSError:
                pass
            self._conns_g.dec()

    def _error_frame(self, writer, corr, message, error_type="WireError"):
        self._refusals.inc()
        writer.send((FRAME_ERROR, corr,
                     {"error_type": error_type, "error": message,
                      "engine_id": self._owner_id}))

    def _handle_submit(self, frame, writer):
        corr = frame[1] if len(frame) > 1 else None
        payload = frame[2] if len(frame) > 2 else None
        if not isinstance(corr, int):
            # garbage correlation id: the peer could never match a
            # reply to its request — error the frame, never the process
            self._error_frame(writer, None,
                              f"bad correlation id {corr!r}")
            return
        if not isinstance(payload, dict):
            self._error_frame(writer, corr,
                              "SUBMIT payload must be a dict")
            return
        if self._handler is not None:
            # synchronous peer-surface op (e.g. the router HA journal):
            # instant bookkeeping, answered inline on the reader
            # thread; a raising handler errors THE FRAME with the
            # exception's class name, keeping the connection
            try:
                body = self._handler(payload)
            except Exception as e:
                writer.send((FRAME_ERROR, corr,
                             {"error_type": type(e).__name__,
                              "error": str(e),
                              "engine_id": self._owner_id}))
                return
            writer.send((FRAME_RESULT, corr,
                         dict(body or {}, engine_id=self._owner_id)))
            return
        t0 = time.perf_counter()
        submit_payload = getattr(self._engine, "submit_payload", None)
        try:
            if submit_payload is not None:
                # decode engines take the whole payload (generation
                # params + the stream flag ride the same dict)
                fut, streamed = submit_payload(payload)
            else:
                fut = self._engine.submit(
                    payload.get("tokens"), payload.get("token_types"),
                    deadline_ms=payload.get("deadline_ms"),
                    trace_id=payload.get("trace_id"),
                    parent_span_id=payload.get("span_id"),
                    model_id=payload.get("model_id"),
                    tenant=payload.get("tenant"),
                    tenant_class=payload.get("tenant_class"))
                streamed = False
        except Exception as e:
            # admission failure (queue full, too long, stopped,
            # malformed tokens): the class name rides back so the
            # router re-raises the same serving error class
            writer.send((FRAME_ERROR, corr,
                         {"error_type": type(e).__name__,
                          "error": str(e),
                          "engine_id": self._engine.engine_id}))
            return

        if streamed:
            # one partial RESULT frame per generated token, demuxed by
            # the SAME correlation id ("seq" orders, "final": False
            # marks the partial; the frame stays MINIMAL — the
            # correlation id already names the request, trace id and
            # cost ride the final body). A peer that never asked for
            # streaming gets exactly one RESULT with no "final" key —
            # the pre-streaming protocol, so old peers keep working.
            def _part(_f, part):
                writer.send((FRAME_RESULT, corr,
                             {"seq": int(part.get("index", 0)),
                              "token": part.get("token"),
                              "final": False}))

            fut.add_part_callback(_part)

        def _done(f):
            engine_ms = round((time.perf_counter() - t0) * 1e3, 3)
            exc = f.exception(timeout=0)
            if exc is not None:
                writer.send((FRAME_ERROR, corr,
                             {"error_type": type(exc).__name__,
                              "error": str(exc),
                              "engine_ms": engine_ms,
                              "engine_id": self._engine.engine_id}))
                return
            body = {"result": np.asarray(f.result(timeout=0)),
                    "cost": f.cost,
                    # the engine-measured critical path rides the
                    # final RESULT frame verbatim, like cost: router
                    # and loadgen must see the same numbers
                    "breakdown": getattr(f, "breakdown", None),
                    "trace_id": f.trace_id,
                    "engine_ms": engine_ms,
                    "engine_id": self._engine.engine_id}
            if streamed:
                # the final frame carries the AUTHORITATIVE full
                # sequence: a client that lost partials (killed
                # connection) misses nothing, one that has them can
                # verify seq count
                body["final"] = True
                body["seq"] = len(f.parts())
            writer.send((FRAME_RESULT, corr, body))

        fut.add_done_callback(_done)


# -- router side ------------------------------------------------------------
class _WireConn:
    """One persistent connection: socket + writer thread + reader
    thread + the in-flight correlation table the reader demuxes."""

    __slots__ = ("sock", "writer", "reader", "pending", "plock",
                 "alive", "pongs")

    def __init__(self, sock):
        self.sock = sock
        self.writer = None
        self.reader = None
        # corr_id -> (on_done, deadline, on_part, timeout_s); a
        # streamed partial refreshes the deadline (token progress IS
        # liveness)
        self.pending = {}
        self.plock = threading.Lock()
        self.alive = True
        self.pongs = {}               # ping nonce -> Event


class WireClient:
    """Router-side half: a pool of persistent multiplexed connections
    to one engine's dispatch listener.

    ``dispatch`` registers the request under a fresh correlation id
    and queues a SUBMIT frame — no blocking I/O, no thread creation on
    the dispatch path. Each connection's single reader thread demuxes
    RESULT/ERROR frames back to the registered callbacks; a connection
    dying fails ITS in-flight requests with :class:`WireError` (the
    router's failover requeues them — nothing is lost). ``ensure()``
    performs the blocking connect/handshake work and belongs on the
    router's poll thread, never the dispatcher.
    """

    def __init__(self, host, port, client_id, expect_engine_id=None,
                 conns=None, timeout_s=None, max_frame=None):
        self._host = str(host)
        self._port = int(port)
        self._client_id = str(client_id)
        self._expect = (str(expect_engine_id)
                        if expect_engine_id is not None else None)
        self._n = max(1, int(conns if conns is not None
                             else envvars.get("MXNET_TPU_WIRE_CONNS")))
        self._timeout = float(timeout_s if timeout_s is not None
                              else envvars.get("MXNET_TPU_WIRE_TIMEOUT_S"))
        self._max_frame = (int(max_frame) if max_frame is not None
                           else _max_frame_bytes())
        self._slots = [None] * self._n
        self._lock = threading.Lock()
        self._rr = itertools.count()
        self._corr = itertools.count(1)
        self._ping_seq = itertools.count(1)
        self._closed = False
        self._connect_failed = False  # edge-triggered event spam guard
        # repo-wide reconnect policy (mxnet_tpu.retrying): consecutive
        # failed connects back off 0.2 s doubling to a 5 s cap, so a
        # dead peer costs one connect per backoff window, not one per
        # poll tick; any success resets the ladder
        self._recon = Reconnector()
        frames = _metrics.wire_frames_counter()
        self._frames = frames
        self._f_in = {}
        self._f_out = {}
        byt = _metrics.wire_bytes_counter()
        self._b_in = byt.labels(side="router", transport="wire",
                                direction="in")
        self._b_out = byt.labels(side="router", transport="wire",
                                 direction="out")
        self._conns_g = _metrics.wire_connections_gauge() \
            .labels(side="router")

    @property
    def port(self):
        return self._port

    def _count_in(self, tag, n):
        child = self._f_in.get(tag)
        if child is None:
            child = self._f_in[tag] = self._frames.labels(
                side="router", direction="in", frame=str(tag))
        child.inc()
        self._b_in.inc(n)

    def _count_out(self, tag, n):
        child = self._f_out.get(tag)
        if child is None:
            child = self._f_out[tag] = self._frames.labels(
                side="router", direction="out", frame=str(tag))
        child.inc()
        self._b_out.inc(n)

    # -- connection management (poll thread) -------------------------------
    def ensure(self):
        """(Re)connect any dead slot. Blocking (connect + handshake) —
        call from the health-poll thread. Returns the live count.
        Consecutive failed connects are backoff-gated by the shared
        :class:`~mxnet_tpu.retrying.Reconnector` policy — a dead peer
        is not re-dialed on every poll tick."""
        live = 0
        for i in range(self._n):
            with self._lock:
                if self._closed:
                    return live
                conn = self._slots[i]
            if conn is not None and conn.alive:
                live += 1
                continue
            if not self._recon.ready():
                return live     # backing off a recent failed connect
            try:
                fresh = self._connect()
            except (OSError, MXNetError, ValueError) as e:
                self._recon.failed()
                if not self._connect_failed:
                    self._connect_failed = True
                    _events.emit("wire_connect_error",
                                 host=self._host, port=self._port,
                                 engine_id=self._expect, error=repr(e))
                return live
            self._connect_failed = False
            self._recon.succeeded()
            stale = None
            with self._lock:
                if self._closed:
                    stale = fresh
                else:
                    stale, self._slots[i] = self._slots[i], fresh
                    live += 1
            if stale is fresh:
                self._teardown(fresh)
                return live
            if stale is not None:
                self._teardown(stale)
        return live

    def _connect(self):
        sock = socket.create_connection((self._host, self._port),
                                        timeout=self._timeout)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # handshake runs SYNCHRONOUSLY (still on the poll thread)
            # before the reader spins up: a port serving some other
            # protocol — or a replacement engine under a recycled
            # port — must be rejected before any SUBMIT rides it
            send_frame(sock, (FRAME_HELLO,
                              {"client_id": self._client_id,
                               "version": PROTOCOL_VERSION}),
                       max_frame=self._max_frame)
            sock.settimeout(self._timeout)
            got = recv_frame(sock, max_frame=self._max_frame)
            if got is None:
                raise WireError("peer closed during wire handshake")
            frame, _n = got
            if not (isinstance(frame, tuple) and frame
                    and frame[0] == FRAME_HELLO):
                raise WireError(f"bad wire handshake reply: {frame!r}")
            info = frame[1] if len(frame) > 1 \
                and isinstance(frame[1], dict) else {}
            eid = info.get("engine_id")
            if (self._expect is not None and eid is not None
                    and str(eid) != self._expect):
                raise WireError(
                    f"wire port answered as engine {eid!r}, expected "
                    f"{self._expect!r} (stale port?)")
            sock.settimeout(None)
        except BaseException:
            try:
                sock.close()
            except OSError:
                pass
            raise
        conn = _WireConn(sock)
        fd = sock.fileno()
        conn.writer = _FrameWriter(
            sock, f"mxnet_tpu_wire_write_fd{fd}", self._max_frame,
            on_sent=self._count_out)
        conn.reader = threading.Thread(
            target=self._read_loop, args=(conn,),
            name=f"mxnet_tpu_wire_read_fd{fd}", daemon=True)
        conn.reader.start()
        self._conns_g.inc()
        return conn

    def has_live(self):
        with self._lock:
            return any(c is not None and c.alive for c in self._slots)

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            conns = [c for c in self._slots if c is not None]
            self._slots = [None] * self._n
        for conn in conns:
            self._teardown(conn)

    def kill_connections(self):
        """Abruptly tear down every live connection WITHOUT closing
        the client (``ensure`` reconnects on the next tick) — the
        chaos harness's router-side ``kill_wire`` fault. In-flight
        requests fail with :class:`WireError`, i.e. the router's
        failover requeues them. Returns the number killed."""
        with self._lock:
            conns = [c for c in self._slots if c is not None]
            self._slots = [None] * self._n
        for conn in conns:
            self._teardown(conn)
        return len(conns)

    def _teardown(self, conn, error=None):
        with conn.plock:
            was_alive, conn.alive = conn.alive, False
            orphans = list(conn.pending.items())
            conn.pending.clear()
            pongs = list(conn.pongs.values())
            conn.pongs.clear()
        if not was_alive and not orphans:
            return
        conn.writer.close()
        _hard_close(conn.sock)        # FIN + wake the blocked reader
        if was_alive:
            self._conns_g.dec()
        for evt in pongs:
            evt.set()
        exc = WireError(
            f"wire connection to {self._host}:{self._port} lost"
            + (f": {error!r}" if error is not None else "")
            + (f" ({len(orphans)} in flight)" if orphans else ""))
        for _corr, entry in orphans:
            _safe_callback(entry[0], exc, None)

    # -- dispatch (router dispatcher thread) --------------------------------
    def dispatch(self, payload, on_done, timeout_s, on_part=None):
        """Queue one SUBMIT on a live connection. ``on_done(exc, body)``
        fires exactly once: with the RESULT/ERROR frame body (exc None)
        on the connection's reader thread, or with a :class:`WireError`
        when the connection dies or the reply outlives ``timeout_s``.
        ``on_part(body)`` (optional) fires once per streamed partial
        RESULT frame (``final: False``) BEFORE the final delivery;
        each partial refreshes the reply deadline — a long generation
        making token progress is alive, only a silent one times out.
        Raises :class:`WireError` when no live connection exists — the
        caller falls back (HTTP) or fails over."""
        deadline = time.monotonic() + float(timeout_s) + self._timeout
        for _ in range(self._n):
            i = next(self._rr) % self._n
            with self._lock:
                conn = self._slots[i]
            if conn is None or not conn.alive:
                continue
            corr = next(self._corr)
            with conn.plock:
                if not conn.alive:
                    continue
                conn.pending[corr] = (on_done, deadline, on_part,
                                      float(timeout_s))
            if not conn.writer.send((FRAME_SUBMIT, corr, payload)):
                with conn.plock:
                    delivered = conn.pending.pop(corr, None) is None
                if delivered:
                    # a teardown raced in between registering the
                    # pending entry and the failed send: it already
                    # fired on_done(WireError) — trying another
                    # connection here would deliver twice
                    return corr
                continue
            return corr
        raise WireError(
            f"no live wire connection to {self._host}:{self._port}")

    def ping(self, timeout_s=None):
        """Round-trip a PING on one live connection; True on PONG."""
        nonce = next(self._ping_seq)
        evt = threading.Event()
        for _ in range(self._n):
            i = next(self._rr) % self._n
            with self._lock:
                conn = self._slots[i]
            if conn is None or not conn.alive:
                continue
            with conn.plock:
                if not conn.alive:
                    continue
                conn.pongs[nonce] = evt
            if not conn.writer.send((FRAME_PING, nonce)):
                with conn.plock:
                    conn.pongs.pop(nonce, None)
                continue
            ok = evt.wait(timeout_s if timeout_s is not None
                          else self._timeout)
            with conn.plock:
                conn.pongs.pop(nonce, None)
            return ok and conn.alive
        return False

    def sweep(self):
        """Fail in-flight requests whose reply outlived the dispatch
        timeout (poll-thread housekeeping — the reader can't notice a
        reply that never comes). They fail with WireError, i.e. the
        router's failover requeues them."""
        now = time.monotonic()
        for conn in list(self._slots):
            if conn is None:
                continue
            expired = []
            with conn.plock:
                for corr, entry in list(conn.pending.items()):
                    if now > entry[1]:
                        expired.append((corr, entry[0]))
                        del conn.pending[corr]
            for corr, on_done in expired:
                _safe_callback(on_done, WireError(
                    f"wire dispatch {corr} to {self._host}:"
                    f"{self._port} timed out"), None)

    # -- reader (one thread per connection) ---------------------------------
    def _read_loop(self, conn):
        err = None
        try:
            while True:
                got = recv_frame(conn.sock, max_frame=self._max_frame)
                if got is None:
                    break
                frame, nbytes = got
                tag = frame[0] if isinstance(frame, tuple) and frame \
                    else None
                self._count_in(tag if isinstance(tag, str) else "?",
                               nbytes)
                if tag in (FRAME_RESULT, FRAME_ERROR) \
                        and len(frame) >= 3:
                    corr = frame[1]
                    body = frame[2] if isinstance(frame[2], dict) \
                        else {"error_type": "WireError",
                              "error": "malformed reply body"}
                    if tag == FRAME_RESULT \
                            and body.get("final") is False:
                        # streamed partial: deliver to the part hook,
                        # KEEP the pending entry, refresh its deadline
                        # (token progress is liveness). A peer
                        # streaming at a non-streaming entry (no
                        # on_part) is ignored — the final RESULT still
                        # resolves it.
                        on_part = None
                        with conn.plock:
                            entry = (conn.pending.get(corr)
                                     if isinstance(corr, int) else None)
                            if entry is not None \
                                    and entry[2] is not None:
                                on_done, _dl, on_part, t_s = entry
                                conn.pending[corr] = (
                                    on_done,
                                    time.monotonic() + t_s
                                    + self._timeout, on_part, t_s)
                        if on_part is not None:
                            _safe_callback(on_part, body)
                        continue
                    with conn.plock:
                        entry = (conn.pending.pop(corr, None)
                                 if isinstance(corr, int) else None)
                    if entry is None:
                        # garbage/duplicate correlation id from the
                        # peer: nothing to deliver to — count it, keep
                        # the connection (framing is intact)
                        _events.emit("wire_unknown_correlation",
                                     host=self._host, port=self._port,
                                     corr=repr(corr))
                        continue
                    _safe_callback(entry[0], None, body)
                elif tag == FRAME_PONG and len(frame) >= 2:
                    with conn.plock:
                        evt = conn.pongs.pop(frame[1], None)
                    if evt is not None:
                        evt.set()
                else:
                    _events.emit("wire_unknown_frame",
                                 host=self._host, port=self._port,
                                 frame=repr(tag))
        except (ConnectionError, EOFError, OSError, ValueError,
                MXNetError) as e:
            err = e
        finally:
            self._teardown(conn, error=err)
