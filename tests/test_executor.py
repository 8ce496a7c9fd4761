"""Executor tests (reference tests/python/unittest/test_executor.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym


def _net():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data=data, num_hidden=3, name="fc")
    return sym.SoftmaxOutput(data=fc, name="sm")


def test_simple_bind_and_forward():
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 6))
    assert set(ex.arg_dict) == {"data", "fc_weight", "fc_bias", "sm_label"}
    ex.arg_dict["data"][:] = np.random.randn(4, 6)
    ex.arg_dict["fc_weight"][:] = np.random.randn(3, 6)
    outs = ex.forward(is_train=False)
    assert outs[0].shape == (4, 3)
    np.testing.assert_allclose(outs[0].asnumpy().sum(axis=1), np.ones(4),
                               rtol=1e-5)


def test_grad_req_add():
    a = sym.Variable("a")
    out = a * 3.0
    arr = mx.nd.array(np.ones((2, 2), dtype=np.float32))
    grad = mx.nd.zeros((2, 2))
    ex = out.bind(mx.cpu(), {"a": arr}, args_grad={"a": grad}, grad_req="add")
    for i in range(3):
        ex.forward(is_train=True)
        ex.backward()
    np.testing.assert_allclose(grad.asnumpy(), np.full((2, 2), 9.0))


def test_grad_req_null():
    a = sym.Variable("a")
    b = sym.Variable("b")
    out = a * b
    ones = np.ones((2, 2), dtype=np.float32)
    ga = mx.nd.zeros((2, 2))
    ex = out.bind(mx.cpu(), {"a": mx.nd.array(ones), "b": mx.nd.array(2 * ones)},
                  args_grad={"a": ga},
                  grad_req={"a": "write", "b": "null"})
    ex.forward(is_train=True)
    ex.backward()
    np.testing.assert_allclose(ga.asnumpy(), 2 * ones)
    assert ex.grad_dict.get("b") is None


def test_backward_head_grads():
    a = sym.Variable("a")
    out = a * a
    x = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    ga = mx.nd.zeros((3,))
    ex = out.bind(mx.cpu(), {"a": mx.nd.array(x)}, args_grad={"a": ga})
    ex.forward(is_train=True)
    head = mx.nd.array(np.array([1.0, 0.5, 2.0], dtype=np.float32))
    ex.backward([head])
    np.testing.assert_allclose(ga.asnumpy(), 2 * x * head.asnumpy(),
                               rtol=1e-6)


def test_executor_reshape():
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 6))
    w = np.random.randn(3, 6).astype(np.float32)
    ex.arg_dict["fc_weight"][:] = w
    ex2 = ex.reshape(data=(8, 6))
    assert ex2.arg_dict["data"].shape == (8, 6)
    # params shared
    np.testing.assert_allclose(ex2.arg_dict["fc_weight"].asnumpy(), w)
    assert ex2.arg_dict["fc_weight"] is ex.arg_dict["fc_weight"]
    ex2.arg_dict["data"][:] = np.random.randn(8, 6)
    outs = ex2.forward(is_train=False)
    assert outs[0].shape == (8, 3)


def test_monitor_callback():
    """Monitor emission happens when the computation actually runs: the
    train forward is lazy, so internals arrive with backward() (fused —
    one forward per monitored batch) or with the lazy .outputs fetch."""
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.arg_dict["data"][:] = np.random.randn(2, 4)
    seen = []
    ex.set_monitor_callback(lambda name, arr: seen.append(name))
    ex.forward(is_train=True)
    ex.backward()
    assert any("fc_output" in n for n in seen)
    assert any("sm_output" in n for n in seen)
    # gradients still computed alongside the monitored internals
    assert ex.grad_dict["fc_weight"].asnumpy().shape == (3, 4)

    # forward-only train step: internals arrive with the outputs fetch
    seen.clear()
    ex.forward(is_train=True)
    assert not seen
    _ = ex.outputs
    assert any("fc_output" in n for n in seen)


def test_monitor_with_integer_internals():
    """Integer-dtype internals (Cast) need float0 cotangents in the
    monitored fused fwd+bwd — a plain zeros_like would make jax.vjp
    reject the graph."""
    data = mx.sym.Variable("data")
    casted = mx.sym.Cast(data, dtype="int32", name="c")
    back = mx.sym.Cast(casted, dtype="float32", name="b")
    fc = mx.sym.FullyConnected(back, num_hidden=2, name="fc")
    out = mx.sym.SoftmaxOutput(fc, name="softmax")
    ex = out.simple_bind(mx.cpu(), data=(2, 3))
    ex.arg_dict["data"][:] = np.random.rand(2, 3) * 5
    seen = []
    ex.set_monitor_callback(lambda n, a: seen.append(n))
    ex.forward(is_train=True)
    ex.backward()
    assert any("c_output" in n for n in seen)


def test_copy_params_from():
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    w = np.random.randn(3, 4).astype(np.float32)
    ex.copy_params_from({"fc_weight": mx.nd.array(w)},
                        allow_extra_params=True)
    np.testing.assert_allclose(ex.arg_dict["fc_weight"].asnumpy(), w)


def test_outputs_lazy_train():
    """Train-mode forward defers compute to backward (one fused XLA call)."""
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.arg_dict["data"][:] = np.random.randn(2, 4)
    ex.forward(is_train=True)
    ex.backward()
    out = ex.outputs[0].asnumpy()
    assert out.shape == (2, 3)


def test_segmented_remat_matches_plain():
    """MXNET_BACKWARD_DO_MIRROR routes through segmented remat
    (make_graph_eval(remat=True)): outputs, aux updates and gradients
    must match the plain path exactly; the emitted backward must carry
    optimization barriers and recompute (more matmuls)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import make_graph_eval

    net = mx.sym.Variable("data")
    for i in range(9):
        net = mx.sym.FullyConnected(net, num_hidden=16, name="rfc%d" % i)
        net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.BatchNorm(net, name="rbn")   # aux crosses segments
    net = mx.sym.FullyConnected(net, num_hidden=2, name="rcls")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    plain, n_aux = make_graph_eval(net)
    remat, n_aux2 = make_graph_eval(net, remat=True)
    assert n_aux == n_aux2

    arg_shapes, _, aux_shapes = net.infer_shape(data=(4, 16))
    rng = np.random.RandomState(0)
    args = [rng.randn(*s).astype(np.float32) * 0.3 for s in arg_shapes]
    lbl = net.list_arguments().index("softmax_label")
    args[lbl] = rng.randint(0, 2, (4,)).astype(np.float32)
    aux = [np.ones(s, np.float32) if "var" in n else np.zeros(s, np.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)]
    key = jax.random.PRNGKey(0)

    o1, a1 = plain(args, aux, key, True)
    o2, a2 = remat(args, aux, key, True)
    for x, y in zip(o1 + a1, o2 + a2):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)

    def loss(fn):
        def f(a):
            outs, aux_o = fn(a, aux, key, True)
            return (sum(jnp.sum(o) for o in outs)
                    + sum(jnp.sum(x) for x in aux_o))
        return f

    g1 = jax.grad(loss(plain))(args)
    g2 = jax.grad(loss(remat))(args)
    for x, y in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)

    txt = jax.jit(jax.grad(loss(remat))).lower(args).as_text()
    assert txt.count("optimization_barrier") > 0
    plain_txt = jax.jit(jax.grad(loss(plain))).lower(args).as_text()
    assert txt.count("stablehlo.dot") > plain_txt.count("stablehlo.dot")


def _remat_chain(head=3):
    """Nine products of growing input width behind one another, a
    BatchNorm, and a head of ``head`` classes: 21 op nodes, so four
    segments, the last one BatchNorm, head and loss."""
    net = mx.sym.Variable("data")
    for i in range(9):
        net = mx.sym.FullyConnected(net, num_hidden=16 + 8 * (i % 3),
                                    name="rfc%d" % i)
        net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.BatchNorm(net, name="rbn")
    net = mx.sym.FullyConnected(net, num_hidden=head, name="rcls")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _remat_case(net, budget, batch=4):
    """``(outputs, aux, gradients, lowered text, telemetry)`` of the
    training pass of ``net`` under ``make_graph_eval(remat=budget is not
    False, remat_budget=budget)``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.executor import make_graph_eval

    fn, _ = make_graph_eval(net) if budget is False else \
        make_graph_eval(net, remat=True, remat_budget=budget)
    arg_shapes, _, aux_shapes = net.infer_shape(data=(batch, 16))
    rng = np.random.RandomState(0)
    args = [rng.randn(*s).astype(np.float32) * 0.3 for s in arg_shapes]
    lbl = net.list_arguments().index("softmax_label")
    args[lbl] = rng.randint(0, 2, (batch,)).astype(np.float32)
    aux = [np.ones(s, np.float32) if "var" in n else np.zeros(s, np.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)]
    key = jax.random.PRNGKey(0)

    def loss(a):
        outs, aux_o = fn(a, aux, key, True)
        return (sum(jnp.sum(o) for o in outs)
                + sum(jnp.sum(x) for x in aux_o))

    outs, aux_o = fn(args, aux, key, True)
    grads = jax.grad(loss)(args)
    telemetry.reset()
    telemetry.enable()
    try:
        text = jax.jit(jax.grad(loss)).lower(args).as_text()
        seen = {k: telemetry.peek(k) for k in (
            "remat.segments", "remat.segments_recomputed",
            "remat.kept_results")}
        seen.update({k: telemetry.peek(k, "gauge") for k in (
            "remat.kept_bytes", "remat.budget_bytes")})
    finally:
        telemetry.disable()
    return outs, aux_o, grads, text, seen


def _dots(text, dim=None):
    """Products in a lowered program's text; with ``dim`` those that read
    or write a tensor with a dimension of that length."""
    import re

    lines = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
    if dim is None:
        return len(lines)
    return sum(bool(re.search(r"[<x]%dx" % dim, ln)) for ln in lines)


def test_last_segment_is_not_recomputed():
    """The last segment's backward starts where its forward ends, so it
    runs under no ``jax.checkpoint``: its product appears three times in
    the step (forward, two gradients), not four; every other product is
    still run again."""
    net = _remat_chain(head=3)
    *_, plain, _ = _remat_case(net, False)
    *_, remat, seen = _remat_case(net, 0)
    assert _dots(plain) == 30 and _dots(plain, 3) == 3
    assert _dots(remat, 3) == 3
    assert _dots(remat) == 9 * 4 + 3
    assert seen["remat.segments"] == 4
    assert seen["remat.segments_recomputed"] == 3
    assert seen["remat.kept_results"] == 0 and seen["remat.kept_bytes"] == 0


@pytest.mark.parametrize("budget", [0, 768, 1408, 1 << 30])
def test_recomputation_keeps_by_work_a_byte_inside_the_budget(budget):
    """Products are kept by descending operations a byte (here: by the
    width they read) while they fit the stated budget; what is kept is
    not run again; a budget of 0 keeps nothing, a large one every product
    outside the last segment; and whatever is kept, outputs, auxiliary
    states and gradients are the plain path's."""
    net = _remat_chain()
    o1, a1, g1, _, _ = _remat_case(net, False)
    o2, a2, g2, text, seen = _remat_case(net, budget)
    for x, y in zip(o1 + a1, o2 + a2):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)
    for x, y in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)
    # product i reads 16 (i = 0), else the width of product i - 1; each
    # holds batch x its own width x 4 bytes
    widths = [16 + 8 * (i % 3) for i in range(9)]
    reads = [16] + widths[:-1]
    left, kept = budget, 0
    for i in sorted(range(9), key=lambda i: -reads[i]):
        if 4 * widths[i] * 4 <= left:
            left -= 4 * widths[i] * 4
            kept += 1
    assert seen["remat.kept_results"] == kept
    assert seen["remat.kept_bytes"] == budget - left <= budget
    assert seen["remat.budget_bytes"] == budget
    assert kept == {0: 0, 1 << 30: 9}.get(budget, kept)
    assert _dots(text) == (9 - kept) * 4 + kept * 3 + 3


def test_recomputation_budget_is_what_the_device_reports_free(monkeypatch):
    """Unstated, the budget is what the device reports free when the step
    is traced less a reserve reckoned from the shapes, never under 0; the
    CPU backend reports nothing and the budget is 0."""
    from mxnet_tpu import executor

    net = _remat_chain()
    assert executor._device_free_bytes() is None
    *_, seen = _remat_case(net, None)
    assert seen["remat.budget_bytes"] == 0 == seen["remat.kept_results"]
    budgets = []
    for free in (1 << 30, (1 << 30) + 4096, 64):
        monkeypatch.setattr(executor, "_device_free_bytes", lambda: free)
        budgets.append(_remat_case(net, None)[-1]["remat.budget_bytes"])
    # the reserve: arguments twice, boundaries, the largest segment twice
    arg_shapes, _, _ = net.infer_shape(data=(4, 16))
    args = 4 * sum(int(np.prod(s)) for s in arg_shapes)
    assert (1 << 30) - budgets[0] > 2 * args
    assert budgets[1] - budgets[0] == 4096 and budgets[2] == 0


def test_monitor_installed_between_forward_and_backward():
    """Per-batch monitor semantics: whether to monitor is decided at
    emission time (backward / lazy outputs), so a callback installed
    after forward(is_train=True) still observes that batch."""
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.arg_dict["data"][:] = np.random.randn(2, 4)
    ex.forward(is_train=True)
    seen = []
    ex.set_monitor_callback(lambda name, arr: seen.append(name))
    ex.backward()
    assert any("fc_output" in n for n in seen)


def test_symbol_grad_with_integer_head():
    """Symbol.grad over a base symbol whose outputs include a
    non-differentiable (integer) head: float0 cotangents keep jax.vjp
    happy (ADVICE r2); the float head still produces real gradients."""
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    fc = mx.sym.FullyConnected(data=data, weight=w, no_bias=True,
                               num_hidden=3, name="fc")
    ints = mx.sym.Cast(fc, dtype="int32", name="ci")
    grp = mx.sym.Group([fc, ints])
    gsym = grp.grad(["w"])
    ex = gsym.simple_bind(mx.cpu(), data=(2, 4), w=(3, 4),
                          grad_req="null")
    x = np.random.rand(2, 4).astype(np.float32)
    ex.arg_dict["data"][:] = x
    ex.arg_dict["w"][:] = np.random.rand(3, 4).astype(np.float32)
    out = ex.forward()[0].asnumpy()
    # d(sum(fc))/dw = column sums of x broadcast over hidden rows;
    # the integer head contributes nothing
    expect = np.tile(x.sum(axis=0), (3, 1))
    np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_monitor_fires_once_when_outputs_read_before_backward():
    """Reading .outputs between forward(is_train=True) and backward()
    must not double-emit the batch's monitor callbacks (once-per-batch
    contract of set_monitor_callback)."""
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    ex.arg_dict["data"][:] = np.random.randn(2, 4)
    seen = []
    ex.set_monitor_callback(lambda name, arr: seen.append(name))
    ex.forward(is_train=True)
    _ = ex.outputs            # lazy fetch emits this batch's internals
    n_after_outputs = len(seen)
    assert n_after_outputs > 0
    ex.backward()
    assert len(seen) == n_after_outputs, "backward re-emitted the batch"
