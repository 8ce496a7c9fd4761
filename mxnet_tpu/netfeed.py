"""netfeed: the disaggregated input pipeline — decode hosts streaming
ready device-feed batches to training hosts over :mod:`netwire`.

The same-host input plane (:mod:`mxnet_tpu.io_pipeline`) moves decoded
batches through a ``shared_memory`` ring; this module is its cross-host
sibling, the reference's data-plane role for ps-lite: a decode fleet
runs :class:`NetFeedServer` around any ``DataIter`` (typically the
PR 5 device-feed iterator: raw uint8 frames + deferred augmentation
params), and the training host runs :class:`NetFeedIter`, which speaks
the frame protocol and plugs into :class:`~mxnet_tpu.io_pipeline.
FeedScheduler` unchanged — ``io.feed_stall_ms`` stays the one signal
for "the chip starved", now measuring the network feed.

Batches cross bit-identically: every numpy payload (data, labels,
index, the ``tops``/``lefts``/``mirror`` augmentation arrays) rides as
a raw described buffer, scalar augmentation params (``mean``/``scale``/
``layout``/``crop``) ride in frame metadata, and the property test
pins equality against the in-process path array for array.

Flow control is credit-based pipelining: the client keeps
``MXNET_TPU_NETFEED_DEPTH`` ``next`` requests outstanding on ONE
connection (the server answers in arrival order, so the decode host is
always D batches ahead), and every reply carries a sequence number so
an injected ``net_reorder`` cannot shuffle epochs — the client
reassembles by seq, never by arrival. End of epoch is an explicit
``eof`` reply (never a dropped connection), ``reset`` restarts the
underlying iterator, and a decode host that stops answering fails the
epoch with a named :class:`~mxnet_tpu.netwire.WireTimeout` after
``MXNET_TPU_NETFEED_TIMEOUT_S`` instead of wedging the training loop.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import env as _env
from . import netwire as _netwire
from . import telemetry as _tel
from .base import MXNetError
from .io import DataBatch, DataDesc, DataIter

__all__ = ["NetFeedServer", "NetFeedIter", "serve_subprocess",
           "demo_feed_factory"]

_log = logging.getLogger(__name__)

#: augmentation-dict keys that are numpy arrays on the wire; everything
#: else in ``batch.aug`` must be a JSON-representable scalar/list
_AUG_ORDER = ("tops", "lefts", "mirror")


def _np(x) -> np.ndarray:
    asnumpy = getattr(x, "asnumpy", None)
    return asnumpy() if callable(asnumpy) else np.asarray(x)


def _descs_out(descs) -> List[list]:
    return [[d.name, list(d.shape), np.dtype(d.dtype).str,
             getattr(d, "layout", "NCHW")] for d in descs]


def _descs_in(raw) -> List[DataDesc]:
    return [DataDesc(name, tuple(shape), dtype=np.dtype(dt),
                     layout=layout)
            for name, shape, dt, layout in raw]


class NetFeedServer:
    """Serve one ``DataIter``'s batches as netwire frames (the decode
    host role). Ops: ``meta`` (iterator descriptors), ``next`` (one
    batch or an ``eof`` marker, stamped with an epoch sequence
    number), ``reset``, ``stop``. The base iterator is driven under a
    lock — one decode stream per server; parallelism lives inside the
    base iterator (e.g. the decode-pool pipeline), not in racing
    ``next`` calls."""

    def __init__(self, base: DataIter, host: str = "127.0.0.1",
                 port: int = 0):
        self.base = base
        from .analysis import sanitizers as _san
        self._lock = _san.maybe_instrument(threading.Lock(),
                                           "netfeed-iter")
        self._seq = 0
        self.stopped = threading.Event()
        self._wire = _netwire.WireServer(self._handle, host, port,
                                         name="netfeed")
        self.host, self.port = self._wire.host, self._wire.port

    # -- batch codec --------------------------------------------------------
    @staticmethod
    def encode_batch(batch: DataBatch, seq: int) -> Tuple[dict, list]:
        """Split one batch into (frame metadata, wire arrays): data +
        label + optional index + augmentation arrays as raw buffers,
        scalar aug params in metadata."""
        data = [_np(d) for d in (batch.data or [])]
        label = [_np(x) for x in (batch.label or [])]
        arrays = data + label
        meta: Dict[str, object] = {"seq": int(seq),
                                   "pad": int(batch.pad or 0),
                                   "nd": len(data), "nl": len(label)}
        if batch.index is not None:
            arrays.append(np.asarray(batch.index))
            meta["has_index"] = True
        aug = getattr(batch, "aug", None)
        if aug is not None:
            scalars, akeys = {}, []
            for k in _AUG_ORDER:
                if k in aug:
                    akeys.append(k)
                    arrays.append(np.asarray(aug[k]))
            for k, v in aug.items():
                if k in _AUG_ORDER:
                    continue
                if isinstance(v, np.ndarray):
                    akeys.append(k)
                    arrays.append(v)
                elif isinstance(v, tuple):
                    scalars[k] = list(v)
                elif isinstance(v, (np.floating, np.integer)):
                    scalars[k] = v.item()
                else:
                    scalars[k] = v
            meta["aug_arrays"] = akeys
            meta["aug_meta"] = scalars
        return meta, arrays

    @staticmethod
    def decode_batch(frame: "_netwire.Frame") -> DataBatch:
        """Inverse of :meth:`encode_batch`; array payloads stay numpy
        (the consumer — FeedScheduler staging or the fit loop — owns
        device placement)."""
        from . import ndarray as nd

        meta = frame.meta
        arrays = list(frame.arrays)
        noff = int(meta.get("nd", 0))
        loff = noff + int(meta.get("nl", 0))
        data = [nd.array(a) for a in arrays[:noff]]
        label = [nd.array(a) for a in arrays[noff:loff]]
        pos = loff
        index = None
        if meta.get("has_index"):
            index = np.asarray(arrays[pos])
            pos += 1
        batch = DataBatch(data, label, pad=int(meta.get("pad", 0)),
                          index=index)
        akeys = meta.get("aug_arrays")
        if akeys is not None or meta.get("aug_meta"):
            aug: Dict[str, object] = {}
            for k in (akeys or ()):
                aug[k] = np.asarray(arrays[pos])
                pos += 1
            for k, v in (meta.get("aug_meta") or {}).items():
                # crop crossed as a JSON list; the device-feed
                # consumers unpack it positionally so a tuple restores
                # the in-process shape exactly
                aug[k] = tuple(v) if isinstance(v, list) else v
            batch.aug = aug
        return batch

    # -- frame protocol -----------------------------------------------------
    def _handle(self, frame, respond):
        op = frame.op
        if op == "next":
            with self._lock:
                seq = self._seq
                self._seq += 1
                try:
                    batch = self.base.next()
                except StopIteration:
                    batch = None
            if batch is None:
                respond("batch", (), {"seq": seq, "eof": True})
                return
            # encode outside the lock: the batch is this request's own,
            # and host-syncing device arrays must not serialize the
            # next decode
            meta, arrays = self.encode_batch(batch, seq)
            _tel.inc("io.netfeed.batches_served")
            respond("batch", arrays, meta)
        elif op == "meta":
            with self._lock:
                respond("ok", (), {
                    "provide_data": _descs_out(self.base.provide_data),
                    "provide_label": _descs_out(self.base.provide_label),
                    "batch_size": int(getattr(self.base, "batch_size",
                                              0))})
        elif op == "reset":
            with self._lock:
                self.base.reset()
                self._seq = 0
            respond("ok")
        elif op == "stop":
            respond("ok")
            self.stopped.set()
        else:
            respond("err", (), {"error": "unknown netfeed op %r" % (op,)})

    def close(self):
        self._wire.close()
        close = getattr(self.base, "close", None)
        if callable(close):
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NetFeedIter(DataIter):
    """The training-host end: a ``DataIter`` over a remote
    :class:`NetFeedServer`. Keeps ``MXNET_TPU_NETFEED_DEPTH`` batch
    requests in flight on one connection and reassembles replies by
    sequence number, so the decode host's read-ahead hides the wire
    rtt; wrap it in :class:`~mxnet_tpu.io_pipeline.FeedScheduler` and
    ``io.feed_stall_ms`` proves whether the chip ever waited. The time
    ``next()`` itself blocks on the wire lands in
    ``io.netfeed_wait_ms`` — stalls the FeedScheduler's own depth then
    absorbs."""

    def __init__(self, host: str, port: int, depth: Optional[int] = None,
                 timeout_s: Optional[float] = None):
        super().__init__()
        self._client = _netwire.WireClient(host, int(port),
                                           peer="netfeed", pool=1)
        self._depth = max(1, int(_env.get("MXNET_TPU_NETFEED_DEPTH")
                                 if depth is None else depth))
        self._timeout_s = float(_env.get("MXNET_TPU_NETFEED_TIMEOUT_S")
                                if timeout_s is None else timeout_s)
        self._out: deque = deque()          # issued, unresolved waiters
        self._buf: Dict[int, object] = {}   # seq -> reply frame
        self._expected = 0
        self._done = False
        self._closed = False
        frame = self._client.call("meta", timeout_s=self._timeout_s)
        if frame.op != "ok":
            raise MXNetError("netfeed meta failed: %s"
                             % frame.meta.get("error"))
        self._provide_data = _descs_in(frame.meta["provide_data"])
        self._provide_label = _descs_in(frame.meta["provide_label"])
        self.batch_size = int(frame.meta.get("batch_size", 0))

    @property
    def provide_data(self):
        return self._provide_data

    @property
    def provide_label(self):
        return self._provide_label

    # -- pipeline pump ------------------------------------------------------
    def _pump(self):
        while len(self._out) < self._depth:
            self._out.append(self._client.request("next"))

    def _collect(self, deadline: float):
        """Resolve the oldest outstanding waiter into the seq buffer."""
        if not self._out:
            raise MXNetError("netfeed protocol error: expected seq %d "
                             "but nothing is outstanding" % self._expected)
        w = self._out.popleft()
        try:
            frame = w.wait(max(0.0, deadline - time.perf_counter()))
        except _netwire.WireTimeout:
            w.cancel()
            raise _netwire.WireTimeout(
                "netfeed batch %d not served within %.1fs (decode host "
                "wedged or MXNET_TPU_NETFEED_TIMEOUT_S too tight)"
                % (self._expected, self._timeout_s))
        seq = int(frame.meta.get("seq", -1))
        self._buf[seq] = frame

    def next(self) -> DataBatch:
        if self._done:
            raise StopIteration
        self._pump()
        t0 = time.perf_counter() if _tel.enabled() else 0.0
        deadline = time.perf_counter() + self._timeout_s
        while self._expected not in self._buf:
            self._collect(deadline)
        if _tel.enabled():
            _tel.observe("io.netfeed_wait_ms",
                         (time.perf_counter() - t0) * 1e3)
        frame = self._buf.pop(self._expected)
        self._expected += 1
        if frame.meta.get("eof"):
            self._done = True
            self._drain()
            raise StopIteration
        self._pump()
        _tel.inc("io.netfeed.batches")
        return NetFeedServer.decode_batch(frame)

    def _drain(self):
        """Resolve every outstanding request (post-eof they are all
        cheap ``eof`` replies) so reset() starts from a quiet wire."""
        deadline = time.perf_counter() + self._timeout_s
        while self._out:
            try:
                self._collect(deadline)
            except (MXNetError, _netwire.WireError):
                break
        self._buf.clear()

    def reset(self):
        self._drain()
        frame = self._client.call("reset", timeout_s=self._timeout_s)
        if frame.op != "ok":
            raise MXNetError("netfeed reset failed: %s"
                             % frame.meta.get("error"))
        self._expected = 0
        self._done = False

    def iter_next(self) -> bool:
        try:
            self._current = self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._current.data

    def getlabel(self):
        return self._current.label

    def getpad(self):
        return self._current.pad

    def getindex(self):
        return self._current.index

    def close(self, stop_server: bool = False):
        if self._closed:
            return
        self._closed = True
        self._drain()
        if stop_server:
            try:
                self._client.call("stop", timeout_s=5.0)
            except _netwire.WireError:
                pass
        self._client.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# two-process plumbing
# ---------------------------------------------------------------------------

def _netfeed_main(port_conn, factory_ref: str):
    """Decode-host entry point (spawn target): build the base iterator
    from a ``"module:attr"`` factory ref, serve it, report the bound
    port, run until a ``stop`` frame."""
    from .fleet import _resolve_factory

    server = NetFeedServer(_resolve_factory(factory_ref)())
    try:
        port_conn.send(server.port)
        port_conn.close()
        while not server.stopped.wait(0.5):
            pass
    finally:
        server.close()


def serve_subprocess(factory_ref: str, start_method: str = "spawn",
                     timeout_s: float = 60.0):
    """Spawn a decode host serving ``factory_ref``'s iterator over
    loopback; returns ``(process, host, port)``. The caller stops it
    with ``NetFeedIter.close(stop_server=True)`` (or kills the
    process)."""
    import multiprocessing

    from .fleet import _resolve_factory

    _resolve_factory(factory_ref)   # fail fast in the parent
    ctx = multiprocessing.get_context(start_method or "spawn")
    port_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=_netfeed_main,
                       args=(child_conn, factory_ref),
                       name="mxtpu-netfeed", daemon=True)
    proc.start()
    child_conn.close()
    if not port_conn.poll(timeout_s):
        port_conn.close()
        proc.join(1.0)
        raise MXNetError("netfeed decode host never reported a port")
    try:
        port = int(port_conn.recv())
    except (EOFError, OSError):
        port_conn.close()
        raise MXNetError("netfeed decode host died before reporting "
                         "a port")
    port_conn.close()
    return proc, "127.0.0.1", port


# ---------------------------------------------------------------------------
# deterministic demo feed (tests / bench)
# ---------------------------------------------------------------------------

class _DemoFeed(DataIter):
    """A seeded synthetic device-feed iterator: uint8 NHWC frames plus
    the PR 5 deferred-augmentation ``batch.aug`` contract, bit-exactly
    reproducible — run it locally and through the wire and the batches
    must match byte for byte."""

    def __init__(self, batches: int = 12, batch_size: int = 8,
                 hw: int = 16, seed: int = 7):
        super().__init__()
        self.batch_size = int(batch_size)
        self._n = int(batches)
        self._hw = int(hw)
        self._seed = int(seed)
        self._i = 0

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size, self._hw, self._hw, 3),
                         dtype=np.uint8, layout="NHWC")]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,),
                         dtype=np.float32, layout="N")]

    def next(self) -> DataBatch:
        from . import ndarray as nd

        if self._i >= self._n:
            raise StopIteration
        rng = np.random.RandomState(self._seed * 1000003 + self._i)
        b, s = self.batch_size, self._hw
        crop = s - 2
        data = rng.randint(0, 256, (b, s, s, 3)).astype(np.uint8)
        labels = rng.randint(0, 10, (b,)).astype(np.float32)
        batch = DataBatch([nd.array(data)], [nd.array(labels)], pad=0,
                          index=np.arange(self._i * b, (self._i + 1) * b))
        batch.aug = {"tops": rng.randint(0, 3, (b,)).astype(np.int32),
                     "lefts": rng.randint(0, 3, (b,)).astype(np.int32),
                     "mirror": rng.rand(b) < 0.5,
                     "mean": 127.5, "scale": 1.0 / 128.0,
                     "layout": "NHWC", "crop": (crop, crop)}
        self._i += 1
        return batch

    def reset(self):
        self._i = 0


def demo_feed_factory() -> DataIter:
    """Spawn-resolvable factory (``"mxnet_tpu.netfeed:demo_feed_factory"``)
    for the netfeed tests."""
    return _DemoFeed()
