"""dist_async parameter-server tier (reference
kvstore_dist_server.h:199-207): per-push server-side updates with NO
cross-worker aggregation — workers run at their own pace on
possibly-stale weights. Round-2 left this tier synchronous (documented
divergence); round 3 implements the reference architecture for real
over a host-side TCP server (mxnet_tpu/parallel/ps.py).

Launched through tools/launch.py like every dist tier; needs no
jax.distributed (the async control plane is sockets), so it runs
anywhere.
"""
import pytest

from dist_util import REPO, fill, launch

ASYNC_SCRIPT = r"""
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx

kv = mx.kv.create("dist_async")
rank, nw = kv.rank, kv.num_workers
assert nw == 2, nw
assert kv.type == "dist_async"

# ---- semantics: no-optimizer push ASSIGNS (reference DataHandle
# without updater); last writer wins, both writes are valid outcomes
kv.init(0, mx.nd.zeros((3,)))
kv.push(0, mx.nd.array(np.full((3,), float(rank + 1), np.float32)))
kv.barrier()
out = mx.nd.zeros((3,))
kv.pull(0, out)
v = out.asnumpy()[0]
assert v in (1.0, 2.0), v

# ---- server-side optimizer: per-push SGD update, pulls see progress
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
kv.barrier()
kv.init(1, mx.nd.zeros((2,)))
for step in range(5):
    kv.push(1, mx.nd.array(np.ones((2,), np.float32)))
w = mx.nd.zeros((2,))
kv.barrier()
kv.pull(1, w)
# 10 pushes total (5 per worker) of grad=1 with lr 0.5: w = -0.5 * 10
np.testing.assert_allclose(w.asnumpy(), np.full((2,), -5.0), atol=1e-5)

# ---- end-to-end: Module trains with update_on_kvstore through the
# async server (push grad -> server SGD -> pull weights)
rng = np.random.RandomState(0)
n = 256
y = rng.randint(0, 2, n).astype(np.float32)
X = (rng.randn(n, 8).astype(np.float32) * 0.5 + y[:, None])
Xs, ys = X[rank::nw], y[rank::nw]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
net = mx.sym.Activation(data=net, act_type="relu")
net = mx.sym.FullyConnected(data=net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(data=net, name="softmax")

it = mx.io.NDArrayIter(Xs, ys, batch_size=16, shuffle=False)
mod = mx.mod.Module(net, context=mx.cpu())
# async staleness slows the early epochs (workers descend on
# possibly-stale weights — the reference async mode's known trade);
# 30 epochs converges fully where sync needs ~8
mod.fit(it, num_epoch=30, kvstore=kv,
        optimizer="sgd", optimizer_params={"learning_rate": 0.1})
it.reset()
acc = next(iter(dict(mod.score(it, "acc")).values()))
print("ASYNC rank=%d acc=%.3f" % (rank, acc))
assert acc > 0.9, acc
kv.barrier()
if rank == 0:
    kv.close()
print("ASYNC_OK rank=%d" % rank)
"""


def test_dist_async_two_workers(tmp_path):
    # run the whole tier AUTHENTICATED: the secret propagates through
    # launch.py's local env path and every PS frame carries an HMAC
    # tag (round-4 hardening exercised end to end, not just in-process)
    out = launch(tmp_path, fill(ASYNC_SCRIPT, tmp_path), port=23475,
                 timeout=420,
                 extra_env={"MXTPU_PS_SECRET": "gate-token"})
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-2500:])
    assert out.stdout.count("ASYNC_OK") == 2, out.stdout[-1500:]


def test_set_optimizer_repeat_keeps_state(tmp_path):
    """A late worker's set_optimizer must NOT wipe server-side momentum
    accumulated by earlier pushes (advisor r3 medium finding; the
    reference only sends the command from rank 0). First writer wins."""
    import pickle

    import numpy as np

    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import ps

    server = ps.ParameterServer("127.0.0.1", 23711, num_workers=1)
    try:
        c = ps.PSClient("127.0.0.1", 23711)
        blob = pickle.dumps(opt_mod.SGD(learning_rate=0.1, momentum=0.9))
        c.call("set_optimizer", blob)
        c.call("init", 0, 0, np.zeros(2, np.float32))
        c.call("push", 0, np.ones(2, np.float32))
        # repeat from a "late worker": must be a no-op on server state
        c.call("set_optimizer", blob)
        c.call("push", 0, np.ones(2, np.float32))
        got = c.call("pull", 0)
        # momentum SGD, mom=0.9 lr=0.1 grad=1: u1=-0.1, u2=0.9*u1-0.1
        want = np.full(2, -0.1 + (0.9 * -0.1 - 0.1), np.float32)
        np.testing.assert_allclose(got, want, atol=1e-6)
        c.close()
    finally:
        server.close()


def test_ps_hmac_framing(monkeypatch):
    """MXTPU_PS_SECRET adds an HMAC tag per frame; a peer with the
    wrong secret cannot get a frame past the unpickler."""
    import numpy as np

    from mxnet_tpu.parallel import ps

    monkeypatch.setenv("MXTPU_PS_SECRET", "cluster-token")
    # the secret resolves once per process; reset the cache so this
    # test's env takes effect (and is restored for later tests)
    monkeypatch.setattr(ps, "_SECRET_CACHE", False)
    server = ps.ParameterServer("127.0.0.1", 23712, num_workers=1)
    try:
        c = ps.PSClient("127.0.0.1", 23712)
        c.call("init", 0, 0, np.arange(3, dtype=np.float32))
        np.testing.assert_allclose(c.call("pull", 0), [0.0, 1.0, 2.0])
        c.close()

        # wrong secret: hand-craft a frame tagged with the wrong key
        # (raw socket — the in-process server reads the env too, so a
        # monkeypatched client would just agree with it). The server
        # must close the connection at the HMAC check, before
        # pickle.loads, never sending an "ok".
        import hashlib
        import hmac as hmac_mod
        import pickle as pkl
        import socket
        import struct

        payload = pkl.dumps(("pull", 0))
        bad_tag = hmac_mod.new(b"wrong-token", payload,
                               hashlib.sha256).digest()
        raw = socket.create_connection(("127.0.0.1", 23712), timeout=10)
        raw.sendall(struct.pack("!Q", len(payload)) + bad_tag + payload)
        assert raw.recv(1) == b"", "server answered a mistagged frame"
        raw.close()

        # server is still healthy for authenticated peers
        c2 = ps.PSClient("127.0.0.1", 23712)
        np.testing.assert_allclose(c2.call("pull", 0), [0.0, 1.0, 2.0])
        c2.close()
    finally:
        server.close()


def test_async_dead_node_detection():
    """Failure-detection parity for the async tier (reference
    KVStore::get_num_dead_node, kvstore_dist.h:149-158): a rank that
    joined the group and then lost its connection is reported dead."""
    import os
    import subprocess
    import sys

    script = r"""
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu.parallel import ps

os.environ["MXTPU_COORDINATOR"] = "127.0.0.1:23476"
os.environ["MXTPU_NUM_WORKERS"] = "2"
os.environ["MXTPU_WORKER_RANK"] = "0"
kv = mx.kv.create("dist_async")            # rank 0: hosts server + hello
assert kv.num_dead_node() == 0

host, port = ps.ps_address()
peer = ps.PSClient(host, port)             # rank 1 joins...
peer.call("hello", 1)
assert kv.num_dead_node() == 0
peer.close()                               # ...and dies
import time
deadline = time.time() + 10
while kv.num_dead_node() != 1 and time.time() < deadline:
    time.sleep(0.1)
assert kv.num_dead_node() == 1, kv.num_dead_node()

# graceful leave is NOT a death: a polite rank 2 joins and says bye
peer2 = ps.PSClient(host, port)
peer2.call("hello", 2)
peer2.call("bye", 2)
peer2.close()
time.sleep(0.3)
assert kv.num_dead_node() == 1, kv.num_dead_node()
kv.close()
print("DEAD_NODE_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", fill(script, "")],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr[-1500:])
    assert "DEAD_NODE_OK" in r.stdout


def test_server_refuses_unauthenticated_start(monkeypatch):
    """Default-on frame auth (round-4 verdict #7): with no secret staged
    the server must refuse to start (unauthenticated pickle frames are
    RCE for anyone who can reach the port); MXTPU_PS_INSECURE=1 is the
    explicit opt-out."""
    import pytest

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import ps

    monkeypatch.delenv("MXTPU_PS_SECRET", raising=False)
    monkeypatch.delenv("MXTPU_PS_SECRET_FILE", raising=False)
    monkeypatch.delenv("MXTPU_PS_INSECURE", raising=False)
    monkeypatch.setattr(ps, "_SECRET_CACHE", False)
    with pytest.raises(MXNetError, match="refuses to start"):
        ps.ParameterServer("127.0.0.1", 23713, num_workers=1)

    monkeypatch.setenv("MXTPU_PS_INSECURE", "1")
    monkeypatch.setattr(ps, "_SECRET_CACHE", False)
    server = ps.ParameterServer("127.0.0.1", 23713, num_workers=1)
    server.close()


def test_launch_generates_job_secret(monkeypatch):
    """tools/launch.py stages a generated secret when the operator set
    none, so every launched job runs authenticated by default."""
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "launch_mod", _os.path.join(_os.path.dirname(__file__), "..",
                                    "tools", "launch.py"))
    launch_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch_mod)

    monkeypatch.delenv("MXTPU_PS_SECRET", raising=False)
    monkeypatch.delenv("MXTPU_PS_INSECURE", raising=False)
    s = launch_mod.job_secret()
    assert s and len(s) >= 32
    # operator-provided secret wins
    monkeypatch.setenv("MXTPU_PS_SECRET", "operator-token")
    assert launch_mod.job_secret() == "operator-token"
    # explicit opt-out: no generated secret
    monkeypatch.setenv("MXTPU_PS_INSECURE", "1")
    monkeypatch.delenv("MXTPU_PS_SECRET", raising=False)
    assert launch_mod.job_secret() is None
