"""Parallel input pipeline: multi-process decode into a shared-memory
batch ring, plus async double-buffered device staging.

The reference scaled JPEG decode with an OMP pool inside
``iter_image_recordio.cc`` and overlapped host prep with device compute
via ``iter_prefetcher.h``. The Python port's thread pool is GIL-bound for
the numpy-heavy augmentation path, so this module sidesteps the GIL with
real processes while keeping the bytes moving through shared memory:

* :class:`ShmRecordStore` — the (possibly shuffled) raw record bytes laid
  out once in a ``multiprocessing.shared_memory`` segment; workers slice
  records out of it without re-reading or re-pickling the dataset.
* :class:`ShmBatchRing` — a preallocated ring of batch-sized slots
  (float32 images + labels). Workers decode **in place** into a slot, so
  a finished batch is assembled in shared memory without ever being
  pickled through a queue; the consumer does one memcpy out of the slot
  and frees it.
* :class:`ProcessDecodePipeline` — owns the workers, the task/result
  queues and the slot accounting. Augmentation stays keyed by
  ``(epoch, record index)`` (see ``io.RecordDecoder``), so results are
  bit-identical to the single-thread path for any worker count.
* :class:`DeviceStagingIter` — wraps any ``DataIter`` and keeps one batch
  staged ahead: while the (async-dispatched) device step for batch N
  executes, the host decodes batch N+1 and issues its ``device_put``, so
  H2D transfer overlaps compute instead of serializing with it.

Failure contract: a dead worker must never hang the training loop. Every
blocking wait carries a timeout; liveness of the worker set is checked on
each timeout and a crash surfaces as :class:`PipelineError`, which
``ImageRecordIter`` catches to fall back to in-process decode with a
warning (``io.pipeline.worker_crashes`` counts the events).

Everything here is opt-in: ``preprocess_mode="process"`` or
``MXNET_TPU_DECODE_PROCS=N`` on :class:`~mxnet_tpu.io.ImageRecordIter`,
``MXNET_TPU_DEVICE_STAGING=1`` for the fit-loop staging wrapper. See
docs/performance.md ("Input pipeline tuning").
"""
from __future__ import annotations

import logging
import multiprocessing
import os
import queue as _queue
import struct
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import telemetry as _tel
from . import env as _env
from .base import MXNetError
from .io import DataBatch, DataDesc, DataIter, RecordDecoder

__all__ = ["ShmRecordStore", "ShmBatchRing", "ProcessDecodePipeline",
           "DeviceStagingIter", "FeedScheduler", "RequestStager",
           "PipelineError"]


class PipelineError(MXNetError):
    """A decode worker died or the ring stalled past its deadline; the
    caller should fall back to in-process decode."""


# ---------------------------------------------------------------------------
# shared-memory layouts
# ---------------------------------------------------------------------------

class ShmRecordStore:
    """Raw record bytes in one shared-memory segment.

    Layout: ``<Q n><Q offsets[n+1]><blob>``. The offsets preserve the
    parent's record ORDER (including any shuffle), so worker decode
    indices mean the same record everywhere.
    """

    def __init__(self, shm, owner: bool):
        self._shm = shm
        self._owner = owner
        self.n = struct.unpack_from("<Q", shm.buf, 0)[0]
        self._offsets = np.frombuffer(shm.buf, dtype=np.uint64, count=self.n + 1,
                                      offset=8)
        self._base = 8 + (self.n + 1) * 8

    @classmethod
    def create(cls, records: Sequence[bytes]) -> "ShmRecordStore":
        from multiprocessing import shared_memory

        n = len(records)
        blob = sum(len(r) for r in records)
        shm = shared_memory.SharedMemory(create=True,
                                         size=max(1, 8 + (n + 1) * 8 + blob))
        struct.pack_into("<Q", shm.buf, 0, n)
        offsets = np.ndarray((n + 1,), dtype=np.uint64, buffer=shm.buf, offset=8)
        base = 8 + (n + 1) * 8
        pos = 0
        for i, rec in enumerate(records):
            offsets[i] = pos
            shm.buf[base + pos:base + pos + len(rec)] = rec
            pos += len(rec)
        offsets[n] = pos
        return cls(shm, owner=True)

    @classmethod
    def attach(cls, name: str) -> "ShmRecordStore":
        from multiprocessing import shared_memory

        return cls(shared_memory.SharedMemory(name=name), owner=False)

    @property
    def name(self) -> str:
        return self._shm.name

    def __len__(self) -> int:
        return self.n

    def get(self, i: int) -> bytes:
        lo, hi = int(self._offsets[i]), int(self._offsets[i + 1])
        return bytes(self._shm.buf[self._base + lo:self._base + hi])

    def close(self):
        # drop numpy views into the buffer before closing the mapping
        self._offsets = None
        try:
            self._shm.close()
            if self._owner:
                self._shm.unlink()
        except Exception:
            pass


class ShmBatchRing:
    """Preallocated ring of batch slots in shared memory.

    Each slot holds ``(batch, *data_shape)`` float32 images plus a
    ``(batch, label_width)`` float32 label block. Workers write decoded
    images straight into a slot view — the batch is assembled in place,
    never pickled."""

    def __init__(self, num_slots: int, batch_size: int, data_shape,
                 label_width: int = 1, name: Optional[str] = None):
        from multiprocessing import shared_memory

        self.num_slots = int(num_slots)
        self.batch_size = int(batch_size)
        self.data_shape = tuple(data_shape)
        self.label_width = int(label_width)
        img_elems = self.batch_size * int(np.prod(self.data_shape))
        self._img_bytes = img_elems * 4
        self._lbl_bytes = self.batch_size * self.label_width * 4
        self.slot_bytes = self._img_bytes + self._lbl_bytes
        if name is None:
            self._shm = shared_memory.SharedMemory(
                create=True, size=max(1, self.num_slots * self.slot_bytes))
            self._owner = True
        else:
            self._shm = shared_memory.SharedMemory(name=name)
            self._owner = False

    def meta(self) -> dict:
        """Picklable description a worker uses to re-attach."""
        return {"name": self._shm.name, "num_slots": self.num_slots,
                "batch_size": self.batch_size, "data_shape": self.data_shape,
                "label_width": self.label_width}

    @classmethod
    def attach(cls, meta: dict) -> "ShmBatchRing":
        return cls(meta["num_slots"], meta["batch_size"], meta["data_shape"],
                   meta["label_width"], name=meta["name"])

    def img_view(self, slot: int) -> np.ndarray:
        return np.ndarray((self.batch_size,) + self.data_shape,
                          dtype=np.float32, buffer=self._shm.buf,
                          offset=slot * self.slot_bytes)

    def label_view(self, slot: int) -> np.ndarray:
        return np.ndarray((self.batch_size, self.label_width),
                          dtype=np.float32, buffer=self._shm.buf,
                          offset=slot * self.slot_bytes + self._img_bytes)

    def close(self):
        try:
            self._shm.close()
            if self._owner:
                self._shm.unlink()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# worker process
# ---------------------------------------------------------------------------

def _decode_worker_main(worker_id: int, decoder_cfg: dict, batch_size: int,
                        label_width: int, store_name: str, ring_meta: dict,
                        task_q, result_q):
    """Decode loop of one worker process.

    Runs with only host-side deps (numpy/PIL/recordio); it never touches
    a jax device, so spawning workers beside a live TPU client is safe.
    Tasks are ``(cursor, epoch, slot)``; the worker decodes the whole
    batch into ring slot ``slot`` and reports ``(cursor, epoch, slot,
    err, decode_seconds)``. Exits on the ``None`` sentinel or when the
    parent disappears."""
    store = ring = None
    try:
        store = ShmRecordStore.attach(store_name)
        ring = ShmBatchRing.attach(ring_meta)
        decoder = RecordDecoder(**decoder_cfg)
        parent = multiprocessing.parent_process()
        while True:
            try:
                task = task_q.get(timeout=1.0)
            except _queue.Empty:
                if parent is not None and not parent.is_alive():
                    return
                continue
            if task is None:
                return
            cursor, epoch, slot = task
            t0 = time.perf_counter()
            err = None
            try:
                imgs = ring.img_view(slot)
                labels = ring.label_view(slot)
                for j in range(batch_size):
                    idx = cursor + j
                    rec = store.get(idx % store.n)
                    img, lab = decoder.decode(rec,
                                              decoder.derive_rng(epoch, idx))
                    imgs[j] = img
                    if label_width == 1:
                        labels[j, 0] = float(lab.ravel()[0])
                    else:
                        labels[j, :] = lab.ravel()[:label_width]
                decoder.normalize_inplace(imgs)
                del imgs, labels  # release buffer views before any close
            except BaseException as e:  # report, don't die: parent decides
                err = "%s: %s" % (type(e).__name__, e)
            result_q.put((cursor, epoch, slot, err, time.perf_counter() - t0))
    except (KeyboardInterrupt, BrokenPipeError):
        pass
    finally:
        if store is not None:
            store.close()
        if ring is not None:
            ring.close()


# ---------------------------------------------------------------------------
# parent-side pipeline
# ---------------------------------------------------------------------------

class ProcessDecodePipeline:
    """Owns decode workers + the shared-memory ring; serves batches by
    cursor with read-ahead scheduling.

    The parent assigns ring slots and enqueues ``(cursor, epoch, slot)``
    tasks; completions arrive out of order and are parked in ``_ready``
    until the consumer asks for that cursor. Results from a superseded
    epoch (after ``reset``) are dropped and their slot reclaimed, so a
    mid-epoch reset cannot poison the next epoch or leak slots."""

    def __init__(self, records: Sequence[bytes], decoder_cfg: dict,
                 batch_size: int, label_width: int = 1, num_workers: int = 2,
                 num_slots: Optional[int] = None,
                 start_method: Optional[str] = None,
                 timeout: Optional[float] = None):
        self.batch_size = int(batch_size)
        self.num_workers = max(1, int(num_workers))
        method = start_method or _env.get("MXNET_TPU_DECODE_START")
        ctx = multiprocessing.get_context(method)
        slots = num_slots or _env.get("MXNET_TPU_DECODE_RING") \
            or max(2, 2 * self.num_workers)
        self.timeout = timeout if timeout is not None \
            else _env.get("MXNET_TPU_DECODE_TIMEOUT")
        self._store = ShmRecordStore.create(records)
        self._ring = ShmBatchRing(slots, batch_size,
                                  decoder_cfg["data_shape"], label_width)
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self._free: List[int] = list(range(slots))
        self._pending: Dict[Tuple[int, int], int] = {}
        self._ready: Dict[Tuple[int, int], int] = {}
        self._closed = False
        self._procs = []
        try:
            for i in range(self.num_workers):
                p = ctx.Process(
                    target=_decode_worker_main,
                    args=(i, decoder_cfg, batch_size, label_width,
                          self._store.name, self._ring.meta(),
                          self._task_q, self._result_q),
                    daemon=True, name="mxtpu-decode-%d" % i)
                p.start()
                self._procs.append(p)
        except BaseException:
            self.shutdown()
            raise
        # belt and braces: shm segments must not outlive a GC'd pipeline
        self._finalizer = weakref.finalize(
            self, ProcessDecodePipeline._cleanup,
            self._procs, self._task_q, self._store, self._ring)

    @property
    def num_slots(self) -> int:
        return self._ring.num_slots

    def workers_alive(self) -> bool:
        return all(p.is_alive() for p in self._procs)

    # -- scheduling --------------------------------------------------------
    def schedule(self, cursor: int, epoch: int) -> bool:
        """Enqueue decode of the batch at ``cursor`` if a slot is free."""
        key = (cursor, epoch)
        if key in self._pending or key in self._ready or not self._free:
            return key in self._pending or key in self._ready
        slot = self._free.pop()
        self._pending[key] = slot
        self._task_q.put((cursor, epoch, slot))
        return True

    def prefetch(self, cursor: int, epoch: int, limit: int):
        """Read-ahead: schedule successor batches while slots are free."""
        for k in range(1, self.num_slots):
            nxt = cursor + k * self.batch_size
            if nxt >= limit or not self._free:
                break
            self.schedule(nxt, epoch)

    def _drain_one(self, timeout: float, epoch: int) -> bool:
        """Pull one completion off the result queue; returns False on
        timeout. Raises on worker death or a reported decode error."""
        try:
            cursor, ep, slot, err, dur = self._result_q.get(timeout=timeout)
        except _queue.Empty:
            if not self.workers_alive():
                raise PipelineError(
                    "decode worker died (exitcodes %s)"
                    % [p.exitcode for p in self._procs])
            return False
        self._pending.pop((cursor, ep), None)
        if err is not None:
            self._free.append(slot)
            raise MXNetError("decode worker failed on batch at cursor %d: %s"
                             % (cursor, err))
        if ep != epoch:
            # superseded epoch (reset() mid-flight): drop, reclaim slot
            self._free.append(slot)
        else:
            self._ready[(cursor, ep)] = slot
            _tel.observe("io.pipeline.decode_ms", dur * 1e3)
        return True

    def get_batch(self, cursor: int, epoch: int,
                  limit: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking fetch of the decoded batch at ``cursor``; copies it
        out of the ring (one memcpy) and frees the slot. Schedules the
        cursor itself plus read-ahead for its successors."""
        key = (cursor, epoch)
        self.schedule(cursor, epoch)
        if limit is not None:
            self.prefetch(cursor, epoch, limit)
        if _tel.enabled():
            # heartbeat: a silently dead worker shows up on the next
            # scrape as workers_alive < configured count, long before
            # the stall timeout fires the in-process fallback
            _tel.set_gauge("io.pipeline.workers_alive",
                           float(sum(p.is_alive() for p in self._procs)))
        stalled = key not in self._ready
        t0 = time.perf_counter()
        while key not in self._ready:
            if time.perf_counter() - t0 > self.timeout:
                raise PipelineError(
                    "decode pipeline stalled %.0fs waiting for cursor %d"
                    % (self.timeout, cursor))
            self._drain_one(0.2, epoch)
            # a stale-epoch drain may have freed the slot the key needs
            self.schedule(cursor, epoch)
        if stalled:
            _tel.inc("io.pipeline.stalls")
            _tel.observe("io.pipeline.stall_ms",
                         (time.perf_counter() - t0) * 1e3)
        slot = self._ready.pop(key)
        imgs = np.array(self._ring.img_view(slot))
        labels = np.array(self._ring.label_view(slot))
        self._free.append(slot)
        _tel.set_gauge("io.pipeline.ring_occupancy",
                       self.num_slots - len(self._free))
        if limit is not None:
            self.prefetch(cursor, epoch, limit)
        return imgs, labels

    def flush(self):
        """Forget parked results (reset path). Pending tasks stay owned
        by their slots; their completions are reclaimed as stale on the
        next drains, so no slot is ever double-assigned."""
        for key, slot in list(self._ready.items()):
            self._free.append(slot)
        self._ready.clear()

    # -- teardown ----------------------------------------------------------
    @staticmethod
    def _cleanup(procs, task_q, store, ring):
        for p in procs:
            if p.is_alive():
                try:
                    task_q.put_nowait(None)
                except Exception:
                    pass
        for p in procs:
            p.join(timeout=1.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=1.0)
        try:
            task_q.close()
            task_q.cancel_join_thread()
        except Exception:
            pass
        store.close()
        ring.close()

    def shutdown(self):
        """Stop workers (sentinel, then terminate), release shared
        memory. Never blocks more than ~2s per worker, never raises."""
        if self._closed:
            return
        self._closed = True
        if hasattr(self, "_finalizer"):
            self._finalizer.detach()
        ProcessDecodePipeline._cleanup(self._procs, self._task_q,
                                       self._store, self._ring)
        try:
            self._result_q.close()
            self._result_q.cancel_join_thread()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()


# ---------------------------------------------------------------------------
# device staging
# ---------------------------------------------------------------------------

class DeviceStagingIter(DataIter):
    """Double-buffered device staging around any ``DataIter``.

    ``next()`` returns the batch staged on the previous call and
    immediately pulls + stages the following one. Because the training
    step is dispatched asynchronously by XLA, the host work for batch
    N+1 (decode + ``device_put`` issue) runs while the device executes
    step N — H2D transfer overlaps compute instead of serializing with
    it (reference ``iter_prefetcher.h``). The two live batches are the
    double buffer; arrays are freshly created per batch, so executors
    that donate input buffers can consume them safely.

    Telemetry: ``io.staging.h2d_ms`` (stage issue latency) and
    ``io.staging.batches``; per-array H2D bytes land on the NDArray
    counters (``ndarray.h2d_bytes``).

    Enable in the fit loop with ``MXNET_TPU_DEVICE_STAGING=1`` or wrap an
    iterator explicitly."""

    def __init__(self, base: DataIter, ctx=None, group=None):
        super().__init__()
        self.base = base
        self._ctx = ctx
        # executor group (or anything with `_mesh` + `_place`): batches
        # staged here land batch-sharded along the group's `dp` mesh
        # axis, so the fused sharded step's own `_place` is a no-copy
        # re-handle instead of a late cross-device reshard
        self._group = group
        self.batch_size = getattr(base, "batch_size", 0)
        self._staged: Optional[DataBatch] = None
        self._exhausted = False

    @property
    def provide_data(self):
        return self.base.provide_data

    @property
    def provide_label(self):
        return self.base.provide_label

    def reset(self):
        self.base.reset()
        self._staged = None
        self._exhausted = False

    # -- checkpoint support (checkpoint.py): the wrapper has no stream
    # state of its own beyond the staged read-ahead, which a seek must
    # discard — the base iterator will re-produce it from the restored
    # logical position
    def get_checkpoint_state(self):
        get = getattr(self.base, "get_checkpoint_state", None)
        return get() if callable(get) else None

    def set_checkpoint_state(self, state):
        self._staged = None
        self._exhausted = False
        st = getattr(self.base, "set_checkpoint_state", None)
        if callable(st):
            st(state)

    def _to_device(self, x, batch_axis=0):
        from .ndarray import NDArray, array

        grp = self._group
        if grp is not None and getattr(grp, "_mesh", None) is not None:
            return grp._place(x, batch_axis)
        if isinstance(x, NDArray):
            if self._ctx is not None and x.context != self._ctx:
                return x.as_in_context(self._ctx)
            return x
        return array(x, ctx=self._ctx)

    @staticmethod
    def _batch_axis(descs, i):
        try:
            return DataDesc.get_batch_axis(descs[i].layout)
        except (AttributeError, IndexError, TypeError):
            return 0

    def _stage(self, batch: DataBatch) -> DataBatch:
        t0 = time.perf_counter() if _tel.enabled() else 0.0
        d_descs = batch.provide_data or self.provide_data or []
        l_descs = batch.provide_label or self.provide_label or []
        data = [self._to_device(d, self._batch_axis(d_descs, i))
                for i, d in enumerate(batch.data)]
        label = [self._to_device(l, self._batch_axis(l_descs, i))
                 for i, l in enumerate(batch.label)]
        if _tel.enabled():
            _tel.observe("io.staging.h2d_ms",
                         (time.perf_counter() - t0) * 1e3)
            _tel.inc("io.staging.batches")
        staged = DataBatch(data, label, batch.pad, batch.index,
                           provide_data=batch.provide_data,
                           provide_label=batch.provide_label)
        # device-feed batches carry their deferred augmentation params;
        # dropping them here would feed raw stored frames to the model
        aug = getattr(batch, "aug", None)
        if aug is not None:
            staged.aug = aug
        return staged

    def next(self) -> DataBatch:
        if self._staged is None:
            if self._exhausted:
                raise StopIteration
            # first batch of the epoch: stage synchronously
            self._staged = self._stage(self.base.next())
        current = self._staged
        self._staged = None
        try:
            self._staged = self._stage(self.base.next())
        except StopIteration:
            self._exhausted = True
        return current

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

    def close(self):
        close = getattr(self.base, "close", None)
        if callable(close):
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def maybe_wrap_device_staging(data_iter: DataIter, group=None) -> DataIter:
    """Fit-loop hook: wrap ``data_iter`` in :class:`DeviceStagingIter`
    when ``MXNET_TPU_DEVICE_STAGING=1`` (idempotent). A
    :class:`FeedScheduler` already stages on its worker thread, so it is
    never double-wrapped. ``group`` (the bound executor group) makes the
    staging mesh-aware: batches land dp-sharded."""
    if not _env.get("MXNET_TPU_DEVICE_STAGING"):
        return data_iter
    if isinstance(data_iter, (DeviceStagingIter, FeedScheduler)):
        return data_iter
    logging.getLogger(__name__).info(
        "device staging enabled: wrapping %s in DeviceStagingIter",
        type(data_iter).__name__)
    return DeviceStagingIter(data_iter, group=group)


# ---------------------------------------------------------------------------
# feed scheduler
# ---------------------------------------------------------------------------

class FeedScheduler(DataIter):
    """Keeps up to ``depth`` staged batches in flight ahead of the
    training loop.

    A generalization of :class:`DeviceStagingIter`'s double buffer: a
    worker thread pulls batches from the base iterator, stages them to
    device (``device_put`` issue — H2D overlaps compute, device-feed
    ``batch.aug`` params preserved), and parks them in a bounded queue.
    ``next()`` pops, and the time the fit loop spends BLOCKED on an
    empty queue is recorded as the ``io.feed_stall_ms`` histogram — the
    signal StepTrace's dominant-cause labeling uses to call a step
    input-starved rather than compute-bound. ``io.feed.in_flight``
    gauges queue occupancy; ``io.feed.batches`` counts deliveries.

    Enable in the fit loop with ``MXNET_TPU_FEED_DEPTH=N`` (N >= 1) or
    wrap an iterator explicitly. Depth buys tolerance to host-side
    jitter (a slow memmap gather, a GC pause) at N batches of extra
    host+device memory; 2-4 covers most of it."""

    _END = object()

    def __init__(self, base: DataIter, depth: int = 2, ctx=None,
                 group=None):
        super().__init__()
        self.base = base
        self.depth = max(1, int(depth))
        self._ctx = ctx
        self._group = group   # see DeviceStagingIter: mesh-sharded staging
        self.batch_size = getattr(base, "batch_size", 0)
        self._q = _queue.Queue(maxsize=self.depth)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._exhausted = False
        self._closed = False

    @property
    def provide_data(self):
        return self.base.provide_data

    @property
    def provide_label(self):
        return self.base.provide_label

    # staging reuses the DeviceStagingIter conversion/telemetry path
    _to_device = DeviceStagingIter._to_device
    _batch_axis = staticmethod(DeviceStagingIter._batch_axis)
    _stage = DeviceStagingIter._stage

    def _worker(self):
        try:
            while not self._stop.is_set():
                try:
                    batch = self.base.next()
                except StopIteration:
                    self._put(self._END)
                    return
                self._put(self._stage(batch))
        except BaseException as e:   # surfaced on the consumer's next()
            self._err = e
            self._put(self._END)

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except _queue.Full:
                continue

    def _ensure_thread(self):
        if self._thread is None:
            self._stop.clear()
            self._err = None
            self._thread = threading.Thread(
                target=self._worker, name="mxtpu-feed-scheduler",
                daemon=True)
            self._thread.start()

    def next(self) -> DataBatch:
        if self._exhausted:
            raise StopIteration
        self._ensure_thread()
        t0 = time.perf_counter() if _tel.enabled() else 0.0
        item = self._q.get()
        if _tel.enabled():
            _tel.observe("io.feed_stall_ms",
                         (time.perf_counter() - t0) * 1e3)
            _tel.set_gauge("io.feed.in_flight", self._q.qsize())
        if item is self._END:
            self._exhausted = True
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        _tel.inc("io.feed.batches")
        return item

    def _drain(self):
        # stop first: a worker blocked on a full queue polls the event
        # inside _put and exits; only then is the queue safe to drain
        # (no late put can land a stale batch in the next epoch)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                break

    def reset(self):
        self._drain()
        self.base.reset()
        self._err = None
        self._exhausted = False
        self._closed = False
        # thread restarts lazily on the first next() of the new epoch

    # -- checkpoint support (checkpoint.py): stop the worker and drop
    # its in-flight read-ahead before seeking the base — staged batches
    # belong to the pre-seek position and must not leak into the
    # resumed stream
    def get_checkpoint_state(self):
        get = getattr(self.base, "get_checkpoint_state", None)
        return get() if callable(get) else None

    def set_checkpoint_state(self, state):
        self._drain()
        self._err = None
        self._exhausted = False
        st = getattr(self.base, "set_checkpoint_state", None)
        if callable(st):
            st(state)

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

    def close(self):
        if self._closed:    # idempotent: __exit__ + explicit close
            return
        self._closed = True
        self._drain()
        close = getattr(self.base, "close", None)
        if callable(close):
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def maybe_wrap_feed_scheduler(data_iter: DataIter, group=None) -> DataIter:
    """Fit-loop hook: wrap ``data_iter`` in :class:`FeedScheduler` when
    ``MXNET_TPU_FEED_DEPTH`` >= 1 (idempotent; subsumes device
    staging). ``group`` makes the worker's staging mesh-aware (see
    :func:`maybe_wrap_device_staging`)."""
    depth = _env.get("MXNET_TPU_FEED_DEPTH")
    if depth <= 0:
        return data_iter
    if isinstance(data_iter, FeedScheduler):
        return data_iter
    if isinstance(data_iter, DeviceStagingIter):
        data_iter = data_iter.base   # scheduler stages; unwrap the buffer
    logging.getLogger(__name__).info(
        "feed scheduler enabled: %d staged batches in flight ahead of "
        "%s", depth, type(data_iter).__name__)
    return FeedScheduler(data_iter, depth=depth, group=group)


# ---------------------------------------------------------------------------
# serving-tier request staging
# ---------------------------------------------------------------------------

class RequestStager:
    """Staged H2D for serving request batches (``mxnet_tpu.serving``).

    One scheduled batch = the queued request payloads concatenated
    along the batch axis and padded up to the scheduled bucket size
    (zero rows, sliced off again after the dispatch), then device-
    placed through the caller's mesh-aware ``place`` function (the
    ``FusedInfer.place_batch`` NamedSharding path: batch sharded along
    the mesh's data axes — ``dp``, never ``tp`` — params already
    resident, replicated or tensor-sharded). Padding to a ladder rung is
    what keeps every dispatch one of at most ``len(buckets)`` stable
    shapes — mixed request rates never retrace.

    A single payload that already fills its bucket (the interactive
    lane's common case once the adaptive scheduler ships full rungs)
    skips the concat+pad entirely (``serve.stage_fastpath``).

    Telemetry: ``serve.h2d_bytes`` and ``serve.pad_rows`` so the
    scheduler's mean-occupancy number stays honest about
    pad waste (the wall-time split lives in the scheduler's
    per-request ``serve.h2d_ms``).
    """

    def __init__(self, place=None):
        self._place = place
        # facts about the most recent stage() call, read by the
        # scheduler's span emitter to tag the traced h2d interval
        # (fastpath taken? bytes shipped?) without re-deriving them
        self.last_fastpath = False
        self.last_bytes = 0
        # pad rows are always zeros of a ladder shape: cache one
        # template per (rows, tail-shape, dtype) instead of allocating
        # a fresh zero block on every under-full dispatch — under a
        # fleet every replica batcher pays this on the hot path
        self._pad_cache: dict = {}

    def rebind_place(self, place) -> None:
        """Re-point staging at a new mesh-aware placement fn (a server
        re-bound across mesh factorings rebuilds its FusedInfer; the
        stager must place onto the NEW mesh's batch sharding, not keep
        shipping rows to the old device set). The pad cache survives —
        pad blocks are host arrays, placement-independent."""
        self._place = place

    def _pad_rows(self, pad: int, shape: tuple, dtype) -> np.ndarray:
        key = (pad, shape, np.dtype(dtype).str)
        block = self._pad_cache.get(key)
        if block is None:
            block = np.zeros((pad,) + shape, dtype)
            if len(self._pad_cache) >= 64:   # ladder shapes are few;
                self._pad_cache.clear()      # runaway keys mean abuse
            self._pad_cache[key] = block
        return block

    def stage(self, rows: Sequence[Sequence[np.ndarray]], bucket: int):
        """``rows`` is one payload tuple per queued request (arrays of
        shape ``(k, ...)``, normally k=1), all with the same arity.
        Returns ``(placed_arrays, pad)`` where ``pad`` is the number of
        zero rows added to reach ``bucket``."""
        n = sum(int(r[0].shape[0]) for r in rows)
        if n > bucket:
            raise MXNetError("request batch of %d rows scheduled into a "
                             "bucket of %d" % (n, bucket))
        pad = bucket - n
        self.last_fastpath = len(rows) == 1 and pad == 0
        if self.last_fastpath:
            # interactive fast path: one payload already filling its
            # bucket — no concat, no pad, straight to placement
            batch = [np.asarray(a) for a in rows[0]]  # graft: host-sync
            _tel.inc("serve.stage_fastpath")
        else:
            cols = list(zip(*rows))
            batch = [np.concatenate([np.asarray(a) for a in c],  # graft: host-sync
                                    axis=0)
                     for c in cols]
            if pad:
                batch = [np.concatenate(
                    [b, self._pad_rows(pad, b.shape[1:], b.dtype)],
                    axis=0)
                    for b in batch]
        placed = self._place(batch) if self._place is not None else batch
        self.last_bytes = sum(int(b.nbytes) for b in batch)
        _tel.inc("serve.h2d_bytes", self.last_bytes)
        if pad:
            _tel.inc("serve.pad_rows", pad)
        return placed, pad
