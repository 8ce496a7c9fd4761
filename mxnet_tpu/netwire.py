"""netwire: zero-copy socket transport for the fleet and the input plane.

The reference framework ran every cross-host byte through ps-lite
(``ps::Postoffice``, PAPER.md layers 0/7): one length-prefixed binary
transport under both the parameter plane and the data plane. This
module is that role rebuilt for the reproduction: a single framing
layer under :class:`mxnet_tpu.fleet.SocketReplica` (inference fleets
across hosts) and :mod:`mxnet_tpu.netfeed` (decode hosts streaming
ready batches to training hosts), replacing the same-host-only pickled
``multiprocessing.Pipe`` and ``shared_memory`` ring primitives.

Frame layout (all integers network byte order)::

    offset 0   magic      2s   b"MW"
    offset 2   version    u8   WIRE_VERSION of the sender
    offset 3   flags      u8   reserved (0)
    offset 4   header_len u16  total fixed-header bytes, >= 18
    offset 6   meta_len   u32  JSON metadata length
    offset 10  body_len   u64  concatenated array payload length
    offset 18  ..header_len    appended header fields (skew tail)
    [meta_len bytes]           UTF-8 JSON: op, mid, array descriptors,
                               dtrace context, request envelope
    [body_len bytes]           raw array payloads, back to back

Version skew rides the PR 15 appended-field idiom at both levels: a
newer sender may append trailing fixed-header bytes (``header_len``
tells an old reader how much to skip) and new JSON keys (an old reader
indexes only what it knows); an old sender's shorter frames parse
unchanged. Both directions are pinned by test.

**No pickle on the hot path.** Arrays cross as raw bytes described by
``{"d": dtype.str, "s": shape}`` descriptors in the metadata; the
sender hands ``sendmsg`` one ``memoryview`` per array (zero copies —
scatter/gather out of the numpy buffers) and the receiver rebuilds
views over a single recv buffer with ``np.frombuffer``. Object dtypes
are refused at encode time: anything that would need pickle does not
belong on this wire. Both length fields are checked against
``MXNET_TPU_WIRE_MAX_FRAME_MB`` *before* allocation (a hostile or
corrupt prefix must not OOM the reader), and every short read raises a
named :class:`WireError` saying what was being read and how many bytes
were missing — the ``_read_exact`` hardening idiom from the checkpoint
loader (:func:`mxnet_tpu.ndarray.load_from_stream`).

:class:`WireClient` keeps ``MXNET_TPU_WIRE_POOL`` persistent
connections per peer and multiplexes requests by message id, so one
slow response never head-of-line-blocks the pool. Per-attempt
deadlines come from the caller (the router's remaining-budget envelope,
PR 14) and are enforced on the waiter. TCP backpressure is surfaced
rather than hidden: a send that blocks longer than
``MXNET_TPU_WIRE_BACKPRESSURE_MS`` counts ``wire.backpressure_stalls``
and lands in the ``wire.backpressure_stall_ms`` histogram, and
``wire.pending`` gauges in-flight depth — inflated rtt under
backpressure is exactly what feeds the router's p95 hedge trigger and
breaker failure accounting, so a congested peer sheds load the same
way a slow one does.

:class:`WireServer` is the PR 7 lifecycle discipline applied to a
listener: a 0.2 s-poll accept loop, per-connection reader threads on a
0.5 s idle poll (so ``close()`` joins everything with bounded
timeouts), replies sent on the receiving connection under a per-socket
send lock.

The network fault plane (:mod:`mxnet_tpu.faults`: ``net_drop``,
``net_partition``, ``net_reorder``, ``net_slow``) injects *inside*
``WireConn.send_frame`` — below every consumer — so the chaos tests
prove requests survive loss, resets, and reordering with the same
seeded, counted machinery as the process-fault drills.

Telemetry (all under ``wire.``): ``bytes_tx``/``bytes_rx``,
``frames_tx``/``frames_rx``, ``rtt_ms``, ``reconnects``,
``backpressure_stalls``/``backpressure_stall_ms``, ``pending``.
``trace_report --view wire`` renders a per-peer rollup of
``fleet.SocketReplica.wire_stats()`` from a record handed to it; no
tool writes such a record since PR 28.
"""
from __future__ import annotations

import json
import logging
import socket
import struct
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import dtrace as _dtrace
from . import env as _env
from . import faults as _faults
from . import telemetry as _tel
from .base import MXNetError

__all__ = ["WIRE_VERSION", "WireError", "WireTimeout", "WirePeerLost",
           "Frame", "encode_frame", "decode_frame", "read_frame",
           "WireConn", "WireServer", "WireClient"]

_log = logging.getLogger(__name__)

WIRE_VERSION = 1

_MAGIC = b"MW"
#: magic(2s) version(B) flags(B) header_len(H) meta_len(I) body_len(Q)
_PREFIX = struct.Struct("!2sBBHIQ")


class WireError(MXNetError):
    """Framing/transport failure: bad magic, truncated read, refused
    length, or a broken socket mid-frame."""


class WireTimeout(WireError):
    """A waiter's per-attempt deadline expired before the reply."""


class WirePeerLost(WireError):
    """The connection died with the request in flight (reset,
    partition, or peer crash) — the caller cannot know whether the
    peer served it."""


class Frame:
    """One decoded frame: ``op``/``mid`` routing fields, the metadata
    dict (request envelope, array descriptors already consumed), the
    decoded numpy arrays (views over the recv buffer), and the dtrace
    context the sender attached (or None)."""

    __slots__ = ("op", "mid", "meta", "arrays", "tctx")

    def __init__(self, op: str, mid: str, meta: dict,
                 arrays: List[np.ndarray], tctx: Optional[dict]):
        self.op = op
        self.mid = mid
        self.meta = meta
        self.arrays = arrays
        self.tctx = tctx

    def __repr__(self):
        return ("Frame(op=%r, mid=%r, arrays=%d, meta_keys=%s)"
                % (self.op, self.mid, len(self.arrays),
                   sorted(self.meta)))


def _max_frame_bytes() -> int:
    return int(_env.get("MXNET_TPU_WIRE_MAX_FRAME_MB")) << 20


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def encode_frame(op: str, mid: str, arrays: Sequence = (),
                 meta: Optional[dict] = None,
                 trace_ctx: Optional[dict] = None,
                 _header_tail: bytes = b"") -> List[memoryview]:
    """Encode one frame as a buffer list ready for ``sendmsg``: element
    0 is the header+metadata bytes, each following element is one
    array's raw buffer (a zero-copy ``memoryview`` of the numpy data).

    ``_header_tail`` is the skew test hook: bytes appended to the fixed
    header, exactly what a future WIRE_VERSION would do. Readers of
    this version skip them via ``header_len``.
    """
    descs = []
    bufs: List[memoryview] = [memoryview(b"")]   # slot 0 patched below
    body_len = 0
    for a in arrays:
        arr = np.asarray(a)
        if not arr.flags.c_contiguous:
            # 0-d arrays are always contiguous, so this never promotes
            # a scalar to 1-d the way unconditional ascontiguousarray
            # would — shapes round-trip bit-identically
            arr = np.ascontiguousarray(arr)
        if arr.dtype.hasobject:
            raise WireError(
                "refusing to encode dtype %s for op %r: object arrays "
                "would need pickle, which never rides this wire"
                % (arr.dtype, op))
        descs.append({"d": arr.dtype.str, "s": list(arr.shape)})
        mv = memoryview(arr).cast("B") if arr.nbytes else memoryview(b"")
        bufs.append(mv)
        body_len += arr.nbytes
    obj = {"op": str(op), "mid": str(mid), "arrays": descs}
    if meta:
        obj["m"] = meta
    if trace_ctx is not None:
        obj["tctx"] = trace_ctx
    meta_bytes = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    cap = _max_frame_bytes()
    if body_len > cap or len(meta_bytes) > cap:
        raise WireError(
            "frame for op %r exceeds MXNET_TPU_WIRE_MAX_FRAME_MB: "
            "body=%d meta=%d cap=%d bytes" % (op, body_len,
                                              len(meta_bytes), cap))
    header = _PREFIX.pack(_MAGIC, WIRE_VERSION, 0,
                          _PREFIX.size + len(_header_tail),
                          len(meta_bytes), body_len) + _header_tail
    bufs[0] = memoryview(header + meta_bytes)
    return bufs


def read_frame(read_exact: Callable[[int, str], memoryview],
               what: str = "<wire>") -> Frame:
    """Decode one frame from a ``read_exact(n, what) -> buffer``
    callable (socket- or bytes-backed). Raises :class:`WireError` on
    bad magic, refused lengths, truncation, or descriptor/body length
    mismatch. Trailing fixed-header bytes from a newer peer are read
    and ignored; unknown metadata keys are ignored by construction.
    """
    head = bytes(read_exact(_PREFIX.size, what + " frame header"))
    magic, version, _flags, header_len, meta_len, body_len = \
        _PREFIX.unpack(head)
    if magic != _MAGIC:
        raise WireError("bad frame magic %r from %s (expected %r) — "
                        "peer is not speaking the netwire protocol"
                        % (magic, what, _MAGIC))
    if header_len < _PREFIX.size:
        raise WireError("frame header_len %d from %s is shorter than "
                        "the fixed prefix (%d)"
                        % (header_len, what, _PREFIX.size))
    if header_len > _PREFIX.size:
        # appended-field skew: a newer sender's extra header bytes —
        # read and drop, exactly like old routers ignoring envelope
        # tail fields
        read_exact(header_len - _PREFIX.size, what + " header tail")
    cap = _max_frame_bytes()
    for field, n in (("meta", meta_len), ("body", body_len)):
        if n > cap:
            raise WireError(
                "refusing frame from %s: %s length field %d exceeds "
                "MXNET_TPU_WIRE_MAX_FRAME_MB cap of %d bytes (v%d "
                "frame; corrupt or hostile prefix?)"
                % (what, field, n, cap, version))
    try:
        obj = json.loads(bytes(read_exact(meta_len, what + " metadata"))
                         .decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as e:
        raise WireError("frame metadata from %s is not valid JSON: %s"
                        % (what, e))
    body = read_exact(body_len, what + " payload")
    mv = memoryview(body).cast("B") if body_len else memoryview(b"")
    arrays, off = [], 0
    for d in obj.get("arrays", ()):
        dt = np.dtype(d["d"])
        shape = tuple(int(x) for x in d["s"])
        nb = int(dt.itemsize * int(np.prod(shape, dtype=np.int64)))
        if off + nb > body_len:
            raise WireError(
                "frame from %s: array descriptors claim %d+ bytes but "
                "the body holds %d" % (what, off + nb, body_len))
        arrays.append(np.frombuffer(mv[off:off + nb], dtype=dt)
                      .reshape(shape))
        off += nb
    if off != body_len:
        raise WireError("frame from %s: body has %d bytes but the "
                        "descriptors consumed %d" % (what, body_len, off))
    return Frame(obj.get("op", ""), obj.get("mid", ""),
                 obj.get("m") or {}, arrays, obj.get("tctx"))


def decode_frame(data) -> Frame:
    """Decode a frame from a contiguous buffer (tests, property
    checks). The same path sockets use, minus the I/O."""
    mv = memoryview(data)
    pos = [0]

    def read_exact(n: int, what: str) -> memoryview:
        if pos[0] + n > len(mv):
            raise WireError(
                "truncated %s: wanted %d bytes, only %d available"
                % (what, n, len(mv) - pos[0]))
        out = mv[pos[0]:pos[0] + n]
        pos[0] += n
        return out

    return read_frame(read_exact)


def _sock_read_exact(sock: socket.socket, n: int, what: str,
                     first_poll: bool = False) -> memoryview:
    """recv_into a preallocated buffer until ``n`` bytes arrived.
    EOF or a mid-frame stall raises a named :class:`WireError`;
    ``first_poll`` lets an idle-poll timeout on the FIRST byte
    propagate as ``socket.timeout`` (the reader loop's stop-check
    tick) while any later timeout means a peer parked mid-frame."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        try:
            k = sock.recv_into(view[got:], min(n - got, 1 << 20))
        except socket.timeout:
            if first_poll and got == 0:
                raise
            raise WireError(
                "wire read of %s stalled mid-frame with %d of %d bytes "
                "(peer wedged or framing mismatch)" % (what, got, n))
        except OSError as e:
            # includes EBADF from a concurrent close() — the reader
            # loop treats any WireError as "connection gone"
            raise WireError("wire read of %s failed after %d of %d "
                            "bytes: %s" % (what, got, n, e))
        if k == 0:
            raise WireError("truncated %s: peer closed after %d of %d "
                            "bytes" % (what, got, n))
        got += k
    return view


# ---------------------------------------------------------------------------
# one connection
# ---------------------------------------------------------------------------

class WireConn:
    """One framed socket: locked scatter/gather sends (with the fault
    hooks and backpressure accounting), unlocked single-reader
    receives, and per-connection byte/frame counters."""

    def __init__(self, sock: socket.socket, peer: str = "?"):
        self.peer = peer
        self._sock = sock
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        from .analysis import sanitizers as _san
        self._slock = _san.maybe_instrument(threading.Lock(),
                                            "wire-send-%s" % peer)
        self._held: Optional[List[memoryview]] = None   # net_reorder
        self._closed = False
        self.bytes_tx = 0
        self.bytes_rx = 0
        self.frames_tx = 0
        self.frames_rx = 0
        self.stalls = 0

    # -- send ---------------------------------------------------------------
    def send_frame(self, bufs: List[memoryview]) -> int:
        """Write one encoded frame (fault plane applied); returns bytes
        written (0 when the frame was dropped/held by a fault). Raises
        :class:`WireError` on a broken socket."""
        if _faults.fires("net_slow"):
            time.sleep(_faults.slow_ms() / 1e3)
        if _faults.fires("net_partition"):
            _log.warning("net_partition injected: hard-closing %s",
                         self.peer)
            self.close()
            raise WirePeerLost("connection to %s lost (injected "
                               "partition)" % self.peer)
        if _faults.fires("net_drop"):
            return 0
        queue = [bufs]
        with self._slock:
            if _faults.fires("net_reorder") and self._held is None:
                # hold this frame back; it rides behind the NEXT one
                self._held = bufs
                return 0
            if self._held is not None:
                queue.append(self._held)   # swapped order on the wire
                self._held = None
            sent = 0
            t0 = time.perf_counter()
            try:
                for frame_bufs in queue:
                    sent += self._write(frame_bufs)
                    self.frames_tx += 1
            except OSError as e:
                self._closed = True
                raise WireError("send to %s failed: %s" % (self.peer, e))
            self.bytes_tx += sent
        stall_ms = (time.perf_counter() - t0) * 1e3
        if stall_ms >= float(_env.get("MXNET_TPU_WIRE_BACKPRESSURE_MS")):
            self.stalls += 1
            _tel.inc("wire.backpressure_stalls")
            _tel.observe("wire.backpressure_stall_ms", stall_ms)
        _tel.inc("wire.frames_tx")
        _tel.inc("wire.bytes_tx", sent)
        return sent

    def _write(self, bufs: List[memoryview]) -> int:
        total = sum(len(b) for b in bufs)
        sent = self._sock.sendmsg(bufs)
        if sent < total:
            # a short scatter/gather write: flatten the remainder and
            # drain it with plain send() (bounded by SO_SNDTIMEO-free
            # blocking writes; the stall shows up in backpressure)
            rest = b"".join(bytes(b) for b in bufs)[sent:]
            while rest:
                k = self._sock.send(rest)
                rest = rest[k:]
            sent = total
        return sent

    # -- receive ------------------------------------------------------------
    def recv_frame(self, idle_ok: bool = False) -> Optional[Frame]:
        """Read one frame; ``idle_ok`` turns an idle-poll timeout
        before any byte arrived into ``None`` (the reader loop's
        stop-check tick)."""
        try:
            frame = read_frame(
                lambda n, what, _first=[True]: self._read(n, what, _first),
                what="peer %s" % self.peer)
        except socket.timeout:
            if idle_ok:
                return None
            raise WireError("idle read from %s timed out" % self.peer)
        self.frames_rx += 1
        _tel.inc("wire.frames_rx")
        return frame

    def _read(self, n: int, what: str, first: List[bool]) -> memoryview:
        out = _sock_read_exact(self._sock, n, what,
                               first_poll=first[0])
        first[0] = False
        self.bytes_rx += n
        _tel.inc("wire.bytes_rx", n)
        return out

    def close(self):
        self._closed = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass

    @property
    def closed(self) -> bool:
        return self._closed


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class WireServer:
    """Threaded frame server: ``handler(frame, respond)`` runs on the
    per-connection reader thread; ``respond(op, arrays=(), meta=None)``
    replies on the same connection with the request's mid (so a pooled
    client demultiplexes it back to the right waiter). Lifecycle is the
    ps.py discipline: polled accept loop, polled per-conn readers,
    bounded joins in ``close()``."""

    def __init__(self, handler: Callable, host: str = "127.0.0.1",
                 port: int = 0, name: str = "wire"):
        self._handler = handler
        self._name = name
        self._stop = threading.Event()
        self._closed = False
        self._conn_threads: List[threading.Thread] = []
        self._conns: List[WireConn] = []
        from .analysis import sanitizers as _san
        self._lock = _san.maybe_instrument(threading.Lock(),
                                           "wire-server-%s" % name)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, int(port)))
        self._sock.listen(64)
        self.host, self.port = self._sock.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name="mxtpu-wire-accept-%s" % name, daemon=True)
        self._accept_thread.start()

    def _accept_loop(self):
        try:
            self._sock.settimeout(0.2)
        except OSError:
            return   # close() won the race to the listening socket
        while not self._stop.is_set():
            try:
                raw, addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            # 0.5s idle poll: a parked reader wakes to check _stop, so
            # close() can join it with a bounded timeout
            raw.settimeout(0.5)
            conn = WireConn(raw, peer="%s:%d" % addr[:2])
            th = threading.Thread(
                target=self._serve, args=(conn,),
                name="mxtpu-wire-conn-%s" % self._name, daemon=True)
            with self._lock:
                self._conn_threads = [t for t in self._conn_threads
                                      if t.is_alive()] + [th]
                self._conns = [c for c in self._conns
                               if not c.closed] + [conn]
            th.start()

    def _serve(self, conn: WireConn):
        try:
            while not self._stop.is_set():
                try:
                    frame = conn.recv_frame(idle_ok=True)
                except WireError:
                    return    # peer hung up / garbage framing: drop conn
                if frame is None:
                    continue   # idle poll tick: re-check _stop

                def respond(op: str, arrays: Sequence = (),
                            meta: Optional[dict] = None,
                            _mid=frame.mid):
                    conn.send_frame(encode_frame(op, _mid, arrays, meta))

                try:
                    self._handler(frame, respond)
                except WireError:
                    return    # reply path broke: drop the connection
                except Exception as e:   # noqa: BLE001 (report, don't die)
                    try:
                        respond("err", meta={
                            "error": "%s: %s" % (type(e).__name__, e)})
                    except WireError:
                        return
        finally:
            conn.close()

    def close(self):
        """Signal stop, close the listener, join accept + conn threads
        with bounded timeouts (they poll ``_stop``). Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=5.0)
        with self._lock:
            threads = list(self._conn_threads)
            conns = list(self._conns)
            self._conn_threads = []
            self._conns = []
        for c in conns:
            c.close()
        stragglers = 0
        for th in threads:
            th.join(timeout=2.0)
            stragglers += th.is_alive()
        if stragglers or self._accept_thread.is_alive():
            _log.warning("WireServer(%s).close: %d thread(s) alive after "
                         "bounded join; leaking daemon thread(s) rather "
                         "than hanging teardown", self._name, stragglers)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# pooled client
# ---------------------------------------------------------------------------

class _Waiter:
    """Reply waiter for one mid (the fleet ``_PendingWaiter`` shape,
    with wire-taxonomy errors)."""

    __slots__ = ("_done", "_frame", "_error", "t0", "_on_cancel", "conn")

    def __init__(self):
        self.conn = None    # the connection the request rides
        self._done = threading.Event()
        self._frame: Optional[Frame] = None
        self._error: Optional[BaseException] = None
        self.t0 = time.perf_counter()
        self._on_cancel: Optional[Callable[[], None]] = None

    def resolve(self, frame: Frame):
        self._frame = frame
        self._done.set()

    def fail(self, err: BaseException):
        self._error = err
        self._done.set()

    def wait(self, timeout_s: float) -> Frame:
        if not self._done.wait(timeout_s):
            raise WireTimeout("wire reply still pending after %.3fs"
                              % timeout_s)
        if self._error is not None:
            raise self._error
        return self._frame

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self):
        """Forget the pending mid (a timed-out attempt the router
        abandoned, or a fault-dropped frame whose reply will never
        come) so the pending table cannot grow under chaos."""
        cb, self._on_cancel = self._on_cancel, None
        if cb is not None:
            cb()


class _PooledConn:
    """One pool slot: a lazily-(re)connected WireConn plus its reader
    thread and pending-mid table."""

    def __init__(self, client: "WireClient", idx: int):
        self._client = client
        self._idx = idx
        from .analysis import sanitizers as _san
        self._lock = _san.maybe_instrument(
            threading.Lock(), "wire-client-%s-%d" % (client.peer, idx))
        self._conn: Optional[WireConn] = None
        self._reader: Optional[threading.Thread] = None
        self._pending: Dict[str, _Waiter] = {}
        self._ever_connected = False

    def _ensure_conn(self) -> WireConn:
        # caller holds self._lock
        if self._conn is not None and not self._conn.closed:
            return self._conn
        timeout_s = float(
            _env.get("MXNET_TPU_WIRE_CONNECT_TIMEOUT_MS")) / 1e3
        try:
            raw = socket.create_connection(
                (self._client.host, self._client.port), timeout=timeout_s)
        except OSError as e:
            raise WirePeerLost("cannot connect to %s:%d (%s)"
                               % (self._client.host, self._client.port, e))
        raw.settimeout(0.5)
        self._conn = WireConn(raw, peer="%s:%d" % (self._client.host,
                                                   self._client.port))
        if self._ever_connected:
            self._client._note_reconnect()
        self._ever_connected = True
        self._reader = threading.Thread(
            target=self._read_loop, args=(self._conn,),
            name="mxtpu-wire-reader-%s-%d" % (self._client.peer,
                                              self._idx),
            daemon=True)
        self._reader.start()
        return self._conn

    def _forget(self, mid: str):
        with self._lock:
            self._pending.pop(mid, None)

    def request(self, bufs: List[memoryview], mid: str) -> _Waiter:
        w = _Waiter()
        w._on_cancel = lambda: self._forget(mid)
        with self._lock:
            conn = w.conn = self._ensure_conn()
            self._pending[mid] = w
        try:
            conn.send_frame(bufs)
        except WireError as e:
            with self._lock:
                self._pending.pop(mid, None)
            self._fail_pending(conn)
            raise WirePeerLost(str(e))
        return w

    def _read_loop(self, conn: WireConn):
        client = self._client
        while not client._stop.is_set() and not conn.closed:
            try:
                frame = conn.recv_frame(idle_ok=True)
            except WireError:
                break
            if frame is None:
                continue
            # a traced reply carries the peer's harvested spans: merge
            # BEFORE resolving the waiter (the root may finish right
            # after), same ordering as the fleet pipe reader
            payload = frame.meta.get("dtrace")
            if payload:
                trc = _dtrace._TRACER
                if trc is not None:
                    trc.absorb(payload)
            with self._lock:
                w = self._pending.pop(frame.mid, None)
            if w is not None:
                _tel.observe("wire.rtt_ms",
                             (time.perf_counter() - w.t0) * 1e3)
                client._note_rtt((time.perf_counter() - w.t0) * 1e3)
                w.resolve(frame)
        self._fail_pending(conn)

    def _fail_pending(self, conn: WireConn):
        """``conn`` is lost: fail the requests that ride it, and only
        those. Its reader notices the loss up to a receive timeout after
        the sender did, and by then the slot may have redialled and hold
        a newer connection's waiters."""
        conn.close()
        with self._lock:
            if self._conn is conn:
                self._conn = None
            lost = [m for m, w in self._pending.items() if w.conn is conn]
            pending = [self._pending.pop(m) for m in lost]
        for w in pending:
            w.fail(WirePeerLost("connection to %s lost mid-request"
                                % self._client.peer))

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def counters(self) -> Tuple[int, int, int, int, int]:
        with self._lock:
            c = self._conn
            if c is None:
                return (0, 0, 0, 0, 0)
            return (c.frames_tx, c.frames_rx, c.bytes_tx, c.bytes_rx,
                    c.stalls)

    def close(self):
        with self._lock:
            conn, self._conn = self._conn, None
            reader = self._reader
        if conn is not None:
            conn.close()
        if reader is not None:
            reader.join(timeout=2.0)
        with self._lock:
            pending = list(self._pending.values())
            self._pending.clear()
        for w in pending:
            w.fail(WirePeerLost("client for %s closed"
                                % self._client.peer))


class WireClient:
    """Pooled, reconnecting, mid-multiplexed client for one peer.

    ``request(op, arrays, meta, timeout_s)`` round-robins over
    ``MXNET_TPU_WIRE_POOL`` persistent connections and returns a waiter
    whose ``wait`` enforces the caller's per-attempt deadline. A dead
    connection fails its in-flight waiters with :class:`WirePeerLost`
    and reconnects on the next request (counted in
    ``wire.reconnects``); the retry decision belongs to the caller
    (the router already owns retry/hedge budgets).
    """

    def __init__(self, host: str, port: int, peer: Optional[str] = None,
                 pool: Optional[int] = None):
        self.host = host
        self.port = int(port)
        self.peer = peer or "%s:%d" % (host, port)
        n = int(_env.get("MXNET_TPU_WIRE_POOL") if pool is None else pool)
        self._stop = threading.Event()
        from .analysis import sanitizers as _san
        self._stats_lock = _san.maybe_instrument(
            threading.Lock(), "wire-stats-%s" % self.peer)
        self._rr = 0
        self._reconnects = 0
        self._rtts: List[float] = []
        self._conns = [_PooledConn(self, i) for i in range(max(1, n))]
        self._closed = False

    # -- bookkeeping --------------------------------------------------------
    def _note_reconnect(self):
        with self._stats_lock:
            self._reconnects += 1
        _tel.inc("wire.reconnects")

    def _note_rtt(self, ms: float):
        with self._stats_lock:
            self._rtts.append(ms)
            if len(self._rtts) > 4096:
                del self._rtts[:2048]

    # -- request path -------------------------------------------------------
    def request(self, op: str, arrays: Sequence = (),
                meta: Optional[dict] = None,
                trace_ctx: Optional[dict] = None) -> _Waiter:
        """Send one request; returns the waiter. Tries every pool slot
        once before giving up with :class:`WirePeerLost`."""
        if self._closed:
            raise WireError("WireClient for %s is closed" % self.peer)
        mid = uuid.uuid4().hex
        bufs = encode_frame(op, mid, arrays, meta, trace_ctx)
        last: Optional[BaseException] = None
        for _ in range(len(self._conns)):
            with self._stats_lock:
                slot = self._conns[self._rr % len(self._conns)]
                self._rr += 1
            try:
                w = slot.request(bufs, mid)
            except WirePeerLost as e:
                last = e
                continue
            _tel.set_gauge("wire.pending", self.pending_count())
            return w
        raise WirePeerLost("no usable connection to %s: %s"
                           % (self.peer, last))

    def call(self, op: str, arrays: Sequence = (),
             meta: Optional[dict] = None, timeout_s: float = 5.0,
             trace_ctx: Optional[dict] = None) -> Frame:
        """Synchronous convenience: request + wait. The reply frame's
        ``op`` is the peer's verdict ("ok"/"err"/...); callers own the
        taxonomy."""
        return self.request(op, arrays, meta, trace_ctx).wait(timeout_s)

    def pending_count(self) -> int:
        return sum(c.pending_count() for c in self._conns)

    def alive(self) -> bool:
        return not self._closed

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        """Per-peer rollup (``trace_report --view wire`` renders it):
        frames, bytes, rtt mean/p99, reconnects, backpressure stalls."""
        ftx = frx = btx = brx = stalls = 0
        for c in self._conns:
            a, b, c_, d, e = c.counters()
            ftx += a
            frx += b
            btx += c_
            brx += d
            stalls += e
        with self._stats_lock:
            rtts = sorted(self._rtts)
            reconnects = self._reconnects
        out = {"peer": self.peer, "pool": len(self._conns),
               "frames_tx": ftx, "frames_rx": frx,
               "bytes_tx": btx, "bytes_rx": brx,
               "reconnects": reconnects,
               "backpressure_stalls": stalls,
               "pending": self.pending_count()}
        if rtts:
            out["rtt_ms"] = {
                "count": len(rtts),
                "mean": round(sum(rtts) / len(rtts), 3),
                "p50": round(rtts[len(rtts) // 2], 3),
                "p99": round(rtts[min(len(rtts) - 1,
                                      int(0.99 * len(rtts)))], 3)}
        return out

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for c in self._conns:
            c.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
