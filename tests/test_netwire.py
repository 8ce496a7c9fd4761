"""Zero-copy socket transport: frame codec properties (round-trip,
zero-length arrays, >cap refusal before allocation, truncation at every
cut point, version skew in BOTH directions), the pooled client against
a live loopback server (echo, reconnect, mid multiplexing), the four
net_* faults injected inside the framing layer, and the disaggregated
netfeed input plane (bit-identical batches across processes, seq
reassembly under net_reorder, FeedScheduler integration)."""
import struct
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import faults, netfeed, netwire, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.netwire import (WireClient, WireError, WirePeerLost,
                               WireServer, WireTimeout, decode_frame,
                               encode_frame)


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.reset()
    telemetry.disable()


@pytest.fixture
def no_faults():
    yield
    faults.configure(None)


def _wire_bytes(*args, **kwargs) -> bytes:
    return b"".join(bytes(b) for b in encode_frame(*args, **kwargs))


def _echo_server():
    """A server that doubles float arrays and echoes metadata."""
    def handler(frame, respond):
        if frame.op == "boom":
            raise RuntimeError("handler exploded")
        respond("ok", [np.asarray(a) * 2 for a in frame.arrays],
                {"echo": frame.meta})
    return WireServer(handler, name="echo-test")


# ---------------------------------------------------------------------------
# codec properties
# ---------------------------------------------------------------------------

def test_frame_round_trip_is_bit_identical():
    rng = np.random.RandomState(0)
    arrays = [rng.randn(4, 3).astype(np.float32),
              rng.randint(0, 255, (2, 2, 2)).astype(np.uint8),
              np.float64(3.5),                      # 0-d scalar
              np.zeros((0, 7), dtype=np.int64),     # zero-length
              np.array([], dtype=np.float16),
              rng.randn(5).astype(">f8")]           # big-endian dtype
    meta = {"k": [1, 2], "s": "x"}
    f = decode_frame(_wire_bytes("infer", "m-1", arrays, meta,
                                 trace_ctx={"trace": "t1"}))
    assert f.op == "infer" and f.mid == "m-1"
    assert f.meta == meta
    assert f.tctx == {"trace": "t1"}
    assert len(f.arrays) == len(arrays)
    for orig, got in zip(arrays, f.arrays):
        orig = np.asarray(orig)
        assert got.dtype == orig.dtype
        assert got.shape == orig.shape
        assert np.array_equal(got, orig)
        assert got.tobytes() == orig.tobytes()      # bit-identical


def test_empty_frame_round_trips():
    f = decode_frame(_wire_bytes("ping", "m-0"))
    assert f.op == "ping" and f.arrays == [] and f.meta == {}
    assert f.tctx is None


def test_non_contiguous_arrays_round_trip():
    base = np.arange(24, dtype=np.float32).reshape(4, 6)
    views = [base[:, ::2], base.T, np.asfortranarray(base)]
    f = decode_frame(_wire_bytes("x", "m", views))
    for orig, got in zip(views, f.arrays):
        assert got.shape == orig.shape
        assert np.array_equal(got, orig)


def test_object_dtype_is_refused_no_pickle_on_the_wire():
    with pytest.raises(WireError, match="pickle"):
        encode_frame("x", "m", [np.array([object()])])


def test_oversize_length_field_refused_before_allocation(monkeypatch):
    """A corrupt/hostile prefix claiming a multi-GiB body must be
    refused from the 18-byte header alone — no allocation, and the
    error names the cap knob."""
    prefix = netwire._PREFIX
    cap = netwire._max_frame_bytes()
    assert cap == 4 << 30     # the default cap is 4 GiB
    for body_len in (cap + 1, 5 << 30, (1 << 64) - 1):
        head = prefix.pack(netwire._MAGIC, netwire.WIRE_VERSION, 0,
                           prefix.size, 0, body_len)
        with pytest.raises(WireError,
                           match="MXNET_TPU_WIRE_MAX_FRAME_MB"):
            decode_frame(head)
    # the metadata length field (u32) can only exceed a lowered cap
    monkeypatch.setenv("MXNET_TPU_WIRE_MAX_FRAME_MB", "1")
    head = prefix.pack(netwire._MAGIC, netwire.WIRE_VERSION, 0,
                       prefix.size, 2 << 20, 0)
    with pytest.raises(WireError, match="MXNET_TPU_WIRE_MAX_FRAME_MB"):
        decode_frame(head)


def test_oversize_payload_refused_at_encode(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WIRE_MAX_FRAME_MB", "1")
    with pytest.raises(WireError, match="MXNET_TPU_WIRE_MAX_FRAME_MB"):
        encode_frame("x", "m", [np.zeros(2 << 20, dtype=np.uint8)])


def test_truncated_frames_raise_named_errors():
    whole = _wire_bytes("infer", "m-1", [np.arange(8, dtype=np.int32)],
                        {"a": 1})
    prefix = netwire._PREFIX
    # cut mid-header, mid-metadata, and mid-payload: every cut point
    # raises a WireError (an MXNetError) naming what was truncated
    for cut in (0, 3, prefix.size - 1, prefix.size + 2, len(whole) - 5):
        with pytest.raises(MXNetError, match="truncated"):
            decode_frame(whole[:cut])
    # and the named part tells you WHICH read starved
    with pytest.raises(WireError, match="header"):
        decode_frame(whole[:4])
    with pytest.raises(WireError, match="payload"):
        decode_frame(whole[:len(whole) - 1])


def test_bad_magic_rejected():
    bad = b"XX" + _wire_bytes("x", "m")[2:]
    with pytest.raises(WireError, match="magic"):
        decode_frame(bad)


def test_header_len_shorter_than_prefix_rejected():
    prefix = netwire._PREFIX
    head = prefix.pack(netwire._MAGIC, netwire.WIRE_VERSION, 0,
                       prefix.size - 4, 0, 0)
    with pytest.raises(WireError, match="header_len"):
        decode_frame(head)


def test_descriptor_body_mismatch_rejected():
    whole = bytearray(_wire_bytes("x", "m", [np.zeros(4, np.float64)]))
    # lie about the body length: descriptors now claim more than it holds
    prefix = netwire._PREFIX
    magic, ver, flags, hlen, mlen, blen = prefix.unpack(
        bytes(whole[:prefix.size]))
    whole[:prefix.size] = prefix.pack(magic, ver, flags, hlen, mlen,
                                      blen - 8)
    with pytest.raises(WireError, match="descriptors"):
        decode_frame(bytes(whole[:-8]))


# ---------------------------------------------------------------------------
# version skew: both directions, pinned
# ---------------------------------------------------------------------------

def test_skew_newer_sender_to_old_reader():
    """A future sender appends header bytes (header_len grows) and new
    metadata keys; THIS version's reader skips the tail via header_len
    and ignores the unknown keys — the PR 15 appended-field idiom on
    the wire."""
    arrays = [np.arange(6, dtype=np.float32).reshape(2, 3)]
    raw = _wire_bytes("infer", "m-9", arrays, {"known": 1},
                      _header_tail=b"\xde\xad\xbe\xef\x00\x01")
    # splice an unknown top-level metadata key in, like a new field
    prefix = netwire._PREFIX
    f = decode_frame(raw)
    assert f.meta == {"known": 1}
    assert np.array_equal(f.arrays[0], arrays[0])
    # longer tail than any plausible extension still decodes
    f2 = decode_frame(_wire_bytes("x", "m", arrays,
                                  _header_tail=b"\x00" * 512))
    assert np.array_equal(f2.arrays[0], arrays[0])
    assert prefix.unpack(raw[:prefix.size])[3] == prefix.size + 6


def test_skew_old_sender_to_new_reader():
    """An older sender omits fields newer readers know about (tctx,
    m): the reader fills safe defaults instead of crashing — JSON
    metadata makes absent keys indistinguishable from default."""
    import json
    prefix = netwire._PREFIX
    meta_bytes = json.dumps({"op": "infer", "mid": "m-old",
                             "arrays": []}).encode()
    raw = prefix.pack(netwire._MAGIC, netwire.WIRE_VERSION, 0,
                      prefix.size, len(meta_bytes), 0) + meta_bytes
    f = decode_frame(raw)
    assert f.op == "infer" and f.mid == "m-old"
    assert f.meta == {} and f.tctx is None and f.arrays == []


# ---------------------------------------------------------------------------
# live loopback: pooled client vs threaded server
# ---------------------------------------------------------------------------

def test_client_server_echo_and_stats(tel):
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=2)
    try:
        for i in range(10):
            x = np.full((4, 4), i, dtype=np.float32)
            f = client.call("infer", [x], {"i": i}, timeout_s=10.0)
            assert f.op == "ok"
            assert np.array_equal(f.arrays[0], x * 2)
            assert f.meta["echo"] == {"i": i}
        st = client.stats()
        assert st["peer"] == "echo" and st["pool"] == 2
        assert st["frames_tx"] == 10 and st["frames_rx"] == 10
        assert st["bytes_tx"] > 10 * 64 and st["bytes_rx"] > 10 * 64
        assert st["reconnects"] == 0 and st["pending"] == 0
        assert st["rtt_ms"]["count"] == 10
        assert st["rtt_ms"]["p99"] >= st["rtt_ms"]["p50"] >= 0.0
        assert tel.peek("wire.frames_tx") >= 10
    finally:
        client.close()
        srv.close()


def test_server_handler_exception_becomes_err_reply():
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        f = client.call("boom", timeout_s=10.0)
        assert f.op == "err"
        assert "exploded" in f.meta["error"]
        # the connection survives a handler error
        f2 = client.call("infer", [np.ones(2, np.float32)],
                         timeout_s=10.0)
        assert f2.op == "ok"
    finally:
        client.close()
        srv.close()


def test_concurrent_requests_multiplex_by_mid():
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=2)
    errs, lock = [], threading.Lock()

    def worker(i):
        try:
            x = np.full((8,), i, dtype=np.float64)
            f = client.call("infer", [x], {"i": i}, timeout_s=30.0)
            assert np.array_equal(f.arrays[0], x * 2), i
            assert f.meta["echo"]["i"] == i
        except Exception as e:   # noqa: BLE001 (collected+asserted)
            with lock:
                errs.append(e)

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errs, errs[:3]
        assert client.pending_count() == 0
    finally:
        client.close()
        srv.close()


# ---------------------------------------------------------------------------
# the network fault plane, injected inside the framing layer
# ---------------------------------------------------------------------------

def test_net_partition_fails_fast_then_reconnects(tel, no_faults):
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        assert client.call("infer", timeout_s=10.0).op == "ok"
        faults.configure("net_partition")
        with pytest.raises(WirePeerLost):
            client.request("infer")
        faults.configure(None)
        # the pooled conn redials on the next request
        assert client.call("infer", timeout_s=10.0).op == "ok"
        assert client.stats()["reconnects"] >= 1
    finally:
        faults.configure(None)
        client.close()
        srv.close()


def test_a_lost_connections_late_reader_spares_the_redialled_ones_requests(
        no_faults):
    """A connection's reader notices its loss up to a receive timeout
    after the sender did: by then the slot has redialled and holds the
    next request, which is not the old connection's to fail (on a loaded
    worker the partition test above lost its second request this way)."""
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        assert client.call("infer", timeout_s=10.0).op == "ok"
        slot = client._conns[0]
        old = slot._conn
        faults.configure("net_partition")
        with pytest.raises(WirePeerLost):
            client.request("infer")
        faults.configure("net_drop")       # the next request stays pending
        w = client.request("infer")
        faults.configure(None)
        assert slot._conn is not old and w.conn is slot._conn
        slot._fail_pending(old)             # the old reader, arriving late
        assert not w.done() and client.pending_count() == 1
        slot._fail_pending(slot._conn)      # its own connection's loss
        with pytest.raises(WirePeerLost):
            w.wait(1.0)
    finally:
        faults.configure(None)
        client.close()
        srv.close()


def test_net_drop_times_out_without_leaking_pending(no_faults):
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        faults.configure("net_drop")
        w = client.request("infer", [np.ones(4, np.float32)])
        with pytest.raises(WireTimeout):
            w.wait(0.3)
        w.cancel()   # the router's timeout path: forget the mid
        assert client.pending_count() == 0
        faults.configure(None)
        assert client.call("infer", timeout_s=10.0).op == "ok"
    finally:
        faults.configure(None)
        client.close()
        srv.close()


def test_net_reorder_swaps_frames_mids_still_match(no_faults):
    """With reorder armed the FIRST frame is held and rides behind the
    second — replies come back swapped, and mid multiplexing still
    resolves each waiter with its own answer."""
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        faults.configure("net_reorder", seed=1)
        a = np.full((4,), 1.0, dtype=np.float64)
        b = np.full((4,), 2.0, dtype=np.float64)
        wa = client.request("infer", [a], {"tag": "a"})
        wb = client.request("infer", [b], {"tag": "b"})
        fa, fb = wa.wait(10.0), wb.wait(10.0)
        assert np.array_equal(fa.arrays[0], a * 2)
        assert np.array_equal(fb.arrays[0], b * 2)
        assert fa.meta["echo"]["tag"] == "a"
        assert fb.meta["echo"]["tag"] == "b"
        plan = faults._PLAN
        assert plan.injected.get("net_reorder", 0) >= 1
    finally:
        faults.configure(None)
        client.close()
        srv.close()


def test_net_slow_injects_wire_latency(no_faults):
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    try:
        t0 = time.perf_counter()
        client.call("infer", timeout_s=10.0)
        base = time.perf_counter() - t0
        faults.configure("net_slow", slow_ms=60.0)
        t0 = time.perf_counter()
        client.call("infer", timeout_s=10.0)
        slowed = time.perf_counter() - t0
        assert slowed >= 0.05 and slowed > base
    finally:
        faults.configure(None)
        client.close()
        srv.close()


def test_server_close_is_idempotent_and_joins_threads():
    srv = _echo_server()
    client = WireClient(srv.host, srv.port, peer="echo", pool=1)
    client.call("infer", timeout_s=10.0)
    client.close()
    srv.close()
    srv.close()   # idempotent
    # pending requests against a closed server fail, not hang
    client2 = WireClient(srv.host, srv.port, peer="gone", pool=1)
    with pytest.raises(WireError):
        client2.call("infer", timeout_s=2.0)
    client2.close()


# ---------------------------------------------------------------------------
# netfeed: the disaggregated input plane
# ---------------------------------------------------------------------------

def _collect_epoch(it):
    out = []
    while True:
        try:
            out.append(it.next())
        except StopIteration:
            return out


def _assert_batches_bit_identical(ref, got):
    assert len(got) == len(ref)
    for r, g in zip(ref, got):
        for rd, gd in zip(r.data, g.data):
            rn, gn = rd.asnumpy(), gd.asnumpy()
            assert gn.dtype == rn.dtype
            assert rn.tobytes() == gn.tobytes()
        for rl, gl in zip(r.label, g.label):
            assert np.array_equal(rl.asnumpy(), gl.asnumpy())
        assert np.array_equal(r.index, g.index)
        assert r.pad == g.pad
        for k in ("tops", "lefts", "mirror"):
            assert np.array_equal(r.aug[k], g.aug[k]), k
        for k in ("mean", "scale", "layout", "crop"):
            assert r.aug[k] == g.aug[k], k
        assert isinstance(g.aug["crop"], tuple)


def test_netfeed_batches_cross_bit_identical_in_process():
    ref = _collect_epoch(netfeed.demo_feed_factory())
    srv = netfeed.NetFeedServer(netfeed.demo_feed_factory())
    it = netfeed.NetFeedIter(srv.host, srv.port)
    try:
        assert it.batch_size == 8
        d = it.provide_data[0]
        assert d.name == "data" and np.dtype(d.dtype) == np.uint8
        assert d.layout == "NHWC"
        _assert_batches_bit_identical(ref, _collect_epoch(it))
        # reset restarts the epoch deterministically
        it.reset()
        _assert_batches_bit_identical(ref, _collect_epoch(it))
    finally:
        it.close()
        srv.close()


def test_netfeed_seq_reassembly_survives_net_reorder(no_faults):
    """Depth-pipelined batch replies arrive out of order under an
    armed net_reorder; the client reassembles by sequence number, so
    the epoch order is exactly the in-process order."""
    ref = _collect_epoch(netfeed.demo_feed_factory())
    srv = netfeed.NetFeedServer(netfeed.demo_feed_factory())
    it = netfeed.NetFeedIter(srv.host, srv.port, depth=3)
    try:
        faults.configure("net_reorder:0.5", seed=5)
        got = _collect_epoch(it)
        faults.configure(None)
        _assert_batches_bit_identical(ref, got)
    finally:
        faults.configure(None)
        it.close()
        srv.close()


@pytest.mark.slow
def test_netfeed_two_process_epoch_bit_identical(tel):
    """The acceptance run: a real spawned decode host streams an epoch
    over loopback; batches match the in-process iterator byte for
    byte, and wrapped in FeedScheduler the feed-stall p99 stays near
    zero (the chip never starves)."""
    from mxnet_tpu.io_pipeline import FeedScheduler

    ref = _collect_epoch(netfeed.demo_feed_factory())
    proc, host, port = netfeed.serve_subprocess(
        "mxnet_tpu.netfeed:demo_feed_factory")
    it = netfeed.NetFeedIter(host, port)
    try:
        sched = FeedScheduler(it, depth=2)
        got = [sched.next()]    # warmup: first device_put compiles
        telemetry.reset()       # measure steady-state stalls only
        telemetry.enable()
        for batch in sched:
            got.append(batch)
            time.sleep(0.005)   # a "training step": read-ahead covers it
        _assert_batches_bit_identical(ref, got)
        sched.close()
        snap = telemetry.snapshot()
        stall = snap["io"]["feed_stall_ms"]
        assert stall["count"] >= len(got) - 2
        # the wire feed kept the queue full: stalls are queue-pop noise
        assert stall["p99"] < 250.0
    finally:
        it.close(stop_server=True)
        proc.join(10)
        if proc.is_alive():
            proc.kill()
            proc.join(5)
        assert not proc.is_alive()


def test_netfeed_timeout_names_the_decode_host(no_faults):
    """A wedged decode host fails the epoch with a named WireTimeout
    instead of hanging the training loop."""
    hang = threading.Event()

    class _WedgedIter(netfeed._DemoFeed):
        def next(self):
            hang.wait(30.0)
            raise StopIteration

    srv = netfeed.NetFeedServer(_WedgedIter())
    it = netfeed.NetFeedIter(srv.host, srv.port, timeout_s=0.5)
    try:
        with pytest.raises(WireTimeout, match="decode host"):
            it.next()
    finally:
        hang.set()
        it.close()
        srv.close()
