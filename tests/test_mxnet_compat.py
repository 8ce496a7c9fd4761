"""`import mxnet` compatibility alias: reference-style scripts run
against this framework unchanged (module names, `from mxnet.x import y`
forms, the FeedForward workflow, checkpointing by prefix)."""
import numpy as np

import mxnet as mx
from mxnet.symbol import Variable
from mxnet import io as mio
from mxnet import ndarray as mnd


def _build():
    net = Variable("data")
    net = mx.symbol.FullyConnected(data=net, name="fc1", num_hidden=16)
    net = mx.symbol.Activation(data=net, name="relu1", act_type="relu")
    net = mx.symbol.FullyConnected(data=net, name="fc2", num_hidden=2)
    return mx.symbol.SoftmaxOutput(data=net, name="softmax")


def test_alias_modules_are_mxnet_tpu():
    import mxnet_tpu

    assert mx.nd is mxnet_tpu.ndarray
    assert mx.sym is mxnet_tpu.symbol
    assert mx.mod is mxnet_tpu.module
    assert mnd is mxnet_tpu.ndarray
    assert mx.kv.create("local").type == "local"
    # reference gpu contexts resolve to the accelerator context
    assert mx.gpu(0) == mx.tpu(0)


def test_every_reference_module_name_imports():
    """Every python/mxnet/*.py module name from the reference resolves
    under the alias package (round 4 closed misc/kvstore_server/libinfo/
    _ndarray_internal/_symbol_internal/symbol_doc/torch)."""
    import importlib

    reference_modules = [
        "attribute", "base", "callback", "context", "executor",
        "executor_manager", "initializer", "io", "kvstore",
        "kvstore_server", "libinfo", "lr_scheduler", "metric", "misc",
        "model", "module", "monitor", "name", "ndarray", "operator",
        "optimizer", "random", "recordio", "rtc", "symbol",
        "symbol_doc", "test_utils", "torch", "visualization",
        "_ndarray_internal", "_symbol_internal",
    ]
    for name in reference_modules:
        mod = importlib.import_module("mxnet." + name)
        assert mod is getattr(mx, name), name
    # the misc module is the schedulers' historical home
    assert mx.misc.FactorScheduler is mx.lr_scheduler.FactorScheduler
    # libinfo finds the built native libraries (both ship in-tree, so
    # an empty list means discovery broke, not "nothing built")
    paths = mx.libinfo.find_lib_path()
    assert paths and all(p.endswith(".so") for p in paths), paths


def test_kvstore_server_role_hosts_ps(tmp_path):
    """A DMLC_ROLE=server process must host a live parameter server
    (the reference launch contract: trackers spawn server processes
    that sit in KVStoreServer.run())."""
    import os
    import subprocess
    import sys

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.parallel import ps

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_ROLE"] = "server"
    env["MXTPU_COORDINATOR"] = "127.0.0.1:23721"
    env["MXTPU_NUM_WORKERS"] = "1"
    # log to files, not pipes: an undrained pipe can deadlock the child
    # and would swallow startup diagnostics on failure
    out_path = tmp_path / "server.log"
    with open(out_path, "w") as log:
        server = subprocess.Popen(
            [sys.executable, "-c",
             "import jax; jax.config.update('jax_platforms', 'cpu');"
             "import mxnet.kvstore_server"],  # module import runs the role
            env=env, stdout=log, stderr=subprocess.STDOUT)
    try:
        client = ps.PSClient("127.0.0.1", 23722, timeout_s=60)
        import numpy as np

        client.call("init", 0, 7, np.arange(3, dtype=np.float32))
        got = client.call("pull", 7)
        np.testing.assert_allclose(got, [0.0, 1.0, 2.0])
        client.close()

        # a WORKER kvstore must coexist with the external server: rank
        # 0 detects the bound address, runs as a pure client against
        # the SAME store, and its close() stops the external server
        # (the full reference tracker contract, not just raw sockets)
        import mxnet_tpu as mxt

        os.environ["MXTPU_COORDINATOR"] = "127.0.0.1:23721"
        os.environ["MXTPU_NUM_WORKERS"] = "1"
        os.environ["MXTPU_WORKER_RANK"] = "0"
        try:
            kv = mxt.kv.create("dist_async")
            assert kv._server is None         # deferred to external
            pulled = mxt.nd.zeros((3,))
            kv.pull(7, pulled)
            np.testing.assert_allclose(pulled.asnumpy(), [0.0, 1.0, 2.0])
            kv.close()                        # must stop the external PS
        finally:
            for k in ("MXTPU_COORDINATOR", "MXTPU_NUM_WORKERS",
                      "MXTPU_WORKER_RANK"):
                os.environ.pop(k, None)
        assert server.wait(timeout=30) == 0, out_path.read_text()[-1500:]
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()


def test_reference_style_training_script(tmp_path):
    """The reference's python-howto flavor: build with mx.symbol.*,
    group outputs, train with FeedForward, checkpoint, reload."""
    out = _build()
    fc1 = out.get_internals()["fc1_output"]
    group = mx.symbol.Group([fc1, out])
    assert group.list_outputs() == ["fc1_output", "softmax_output"]

    rng = np.random.RandomState(0)
    y = rng.randint(0, 2, 128).astype(np.float32)
    X = (rng.randn(128, 6) + y[:, None] * 1.5).astype(np.float32)
    model = mx.model.FeedForward.create(
        out, X=mio.NDArrayIter(X, y, batch_size=32, shuffle=True),
        num_epoch=20, learning_rate=0.3)
    acc = (model.predict(mio.NDArrayIter(X, batch_size=32))
           .argmax(axis=1) == y).mean()
    assert acc > 0.9, acc

    prefix = str(tmp_path / "compat")
    model.save(prefix, 20)
    again = mx.model.FeedForward.load(prefix, 20)
    np.testing.assert_array_equal(
        again.predict(mio.NDArrayIter(X, batch_size=32)),
        model.predict(mio.NDArrayIter(X, batch_size=32)))
