"""Sibling convolutions lowered as one (executor._plan_conv_groups).

The merged lowering against its unmerged twin, which is the
``want_internals=True`` path of the same ``eval_graph`` (every node on
its own, as the monitor wants them): same outputs, same gradient for
every parameter, same new moving statistics, to the tolerance of
summation order at float32 / ``highest`` (tests/conftest.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu.executor import make_graph_eval
from mxnet_tpu.models.inception_bn import _inception_a, _inception_b
from mxnet_tpu.models.resnet import _bottleneck

TOL = dict(rtol=2e-5, atol=2e-6)


def _close(got, want, name="", loose=1.0):
    """Equal to summation order: a few float32 ulps of the tensor's
    largest entry, which is what a reordered sum can move any entry by."""
    want = np.asarray(want)
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    np.testing.assert_allclose(got, want, err_msg=name, rtol=2e-5 * loose,
                               atol=(2e-6 + 1e-5 * scale) * loose)


def _block(kind):
    data = mx.sym.Variable("data")
    if kind == "inception_a":
        return _inception_a(data, 6, 4, 6, 4, 8, "avg", 4, "3a"), (4, 10, 9, 9)
    if kind == "inception_b":
        return _inception_b(data, 6, 8, 4, 6, "3c"), (4, 10, 9, 9)
    assert kind == "resnet_stage0_unit0"
    return _bottleneck(data, 16, (1, 1), False, "stage0_unit0"), (4, 16, 7, 7)


def _inputs(sym, data_shape, seed=0):
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape)
    rng = np.random.RandomState(seed)
    args = [jnp.asarray(rng.uniform(-1, 1, s).astype(np.float32))
            for s in arg_shapes]
    aux = []
    for name, s in zip(sym.list_auxiliary_states(), aux_shapes):
        lo = 0.5 if name.endswith("var") else -0.5
        aux.append(jnp.asarray(rng.uniform(lo, lo + 1, s).astype(np.float32)))
    return args, aux


def _both_ways(sym, data_shape, is_train=True, **graph_kw):
    """(outputs, aux, grads) of the merged lowering and of the plain one."""
    eval_graph, _ = make_graph_eval(sym, **graph_kw)
    args, aux = _inputs(sym, data_shape)

    def run(plain):
        def f(args):
            res = eval_graph(args, aux, None, is_train,
                             want_internals=plain)
            return res[0], res[1]

        (outs, aux_out), vjp = jax.vjp(f, args)
        rng = np.random.RandomState(7)
        cts = [jnp.asarray(rng.uniform(-1, 1, o.shape).astype(np.float32))
               for o in outs]
        grads, = vjp((cts, [jnp.zeros_like(a) for a in aux_out]))
        return outs, aux_out, grads

    return jax.jit(run, static_argnums=0)(False), \
        jax.jit(run, static_argnums=0)(True)


def _assert_same(merged, plain, names):
    for what, a, b in zip(("outputs", "aux", "grads"), merged, plain):
        assert len(a) == len(b)
        for i, (x, y) in enumerate(zip(a, b)):
            _close(x, y, "%s[%s]" % (what, names[i] if what == "grads"
                                     else i))


def _counters(sym, data_shape, graph_kw=None, **more_shapes):
    """(groups, member convolutions) lowered as one in one traced
    program of ``sym``."""
    telemetry.reset()
    telemetry.enable()
    try:
        eval_graph, _ = make_graph_eval(sym, **(graph_kw or {}))
        arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape,
                                                    **more_shapes)
        jax.eval_shape(
            lambda a, x: eval_graph(a, x, None, True),
            [jax.ShapeDtypeStruct(s, jnp.float32) for s in arg_shapes],
            [jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_shapes])
        return (telemetry.peek("lower.conv_groups_merged") or 0,
                telemetry.peek("lower.convs_merged") or 0)
    finally:
        telemetry.disable()
        telemetry.reset()


def _conv_eqns(jaxpr):
    """Every conv_general_dilated equation, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_conv_eqns(sub))
    return found


def _forward_jaxpr(sym, data_shape, **graph_kw):
    eval_graph, _ = make_graph_eval(sym, **graph_kw)
    args, aux = _inputs(sym, data_shape)
    return jax.make_jaxpr(
        lambda a, x: eval_graph(a, x, None, True))(args, aux).jaxpr


@pytest.mark.parametrize("kind, groups, members", [
    ("inception_a", 1, 3), ("inception_b", 1, 2),
    ("resnet_stage0_unit0", 1, 2)])
@pytest.mark.parametrize("is_train", [True, False], ids=["train", "eval"])
def test_block_merged_equals_plain(kind, groups, members, is_train):
    sym, shape = _block(kind)
    assert _counters(sym, shape) == (groups, members)
    merged, plain = _both_ways(sym, shape, is_train=is_train)
    _assert_same(merged, plain, sym.list_arguments())
    if is_train:    # the moving statistics did move
        _, aux = _inputs(sym, shape)
        assert all(float(jnp.max(jnp.abs(a - b))) > 0
                   for a, b in zip(merged[1], aux))


@pytest.mark.parametrize("kind", ["inception_a", "resnet_stage0_unit0"])
def test_remat_merged_equals_plain(kind):
    sym, shape = _block(kind)
    assert _counters(sym, shape, dict(remat=True))[0] <= 1
    merged, plain = _both_ways(sym, shape, remat=True)
    _assert_same(merged, plain, sym.list_arguments())


def test_remat_plans_inside_a_segment():
    """Members that fall into different remat segments stay apart; what
    is merged is merged inside one segment. (Outputs and statistics
    only: through this many BatchNorms the recomputed backward pass of
    ANY remat program, merged or not, sits a few percent from the plain
    one's; the blocks above hold the gradients.)"""
    net = models.get_inception_bn_28_small(num_classes=4)
    whole = _counters(net, (2, 3, 28, 28))
    cut = _counters(net, (2, 3, 28, 28), dict(remat=True))
    assert whole == (10, 28)
    assert 0 < cut[0] <= 10 and 2 * cut[0] <= cut[1] <= 28
    merged, plain = _both_ways(net, (2, 3, 28, 28), remat=True)
    _assert_same(merged[:2], plain[:2], net.list_arguments())


def test_use_global_stats_merged_equals_plain():
    data = mx.sym.Variable("data")
    outs = []
    for name, width in (("a", 3), ("b", 5)):
        conv = mx.sym.Convolution(data=data, num_filter=width, kernel=(1, 1),
                                  name="conv_" + name)
        bn = mx.sym.BatchNorm(data=conv, use_global_stats=True,
                              fix_gamma=False, name="bn_" + name)
        outs.append(mx.sym.Activation(data=bn, act_type="tanh",
                                      name="tanh_" + name))
    sym = mx.sym.Concat(*outs, num_args=2)
    assert _counters(sym, (2, 8, 5, 5)) == (1, 2)
    merged, plain = _both_ways(sym, (2, 8, 5, 5))
    _assert_same(merged, plain, sym.list_arguments())
    _, aux = _inputs(sym, (2, 8, 5, 5))
    for a, b in zip(merged[1], aux):    # global statistics stay put
        np.testing.assert_array_equal(a, b)


def _pair(a=None, b=None, bn=(None, None), act=("relu", "relu"),
          groups=(None, None)):
    """Two convolutions on one tensor, each conv -> BatchNorm ->
    Activation, with per-member overrides."""
    data = mx.sym.Variable("data")
    outs = []
    for i, over in enumerate((a or {}, b or {})):
        kw = dict(num_filter=4, kernel=(1, 1), no_bias=True)
        kw.update(over)
        name = "ab"[i]
        with mx.AttrScope(**({"ctx_group": groups[i]} if groups[i] else {})):
            conv = mx.sym.Convolution(data=data, name="conv_" + name, **kw)
            norm = mx.sym.BatchNorm(data=conv, name="bn_" + name,
                                    **(bn[i] or {}))
            outs.append(mx.sym.Activation(data=norm, act_type=act[i],
                                          name="act_" + name))
    return mx.sym.Group(outs)


def _by_group(n):
    devs = jax.devices()
    return {"dev1": devs[0], "dev2": devs[-1]}.get(n.attrs.get("ctx_group"))


@pytest.mark.parametrize("case, kw, graph_kw", [
    ("stride", dict(b=dict(stride=(2, 2))), {}),
    ("kernel", dict(b=dict(kernel=(3, 3), pad=(1, 1))), {}),
    ("pad", dict(b=dict(pad=(1, 1))), {}),
    ("dilate", dict(a=dict(kernel=(3, 3), pad=(1, 1)),
                    b=dict(kernel=(3, 3), pad=(2, 2), dilate=(2, 2))), {}),
    ("num_group", dict(a=dict(num_group=2), b=dict(num_group=2)), {}),
    ("bias", dict(b=dict(no_bias=False)), {}),
    ("layout", dict(b=dict(layout="NHWC")), {}),
    ("node_device", dict(groups=("dev1", "dev2")),
     dict(node_device=_by_group)),
])
def test_not_merged(case, kw, graph_kw):
    sym = _pair(**kw)
    if case == "layout":    # one member reads NCHW data as NHWC: nothing
        from mxnet_tpu import executor      # to run, the plan is empty
        nodes = [n for n in sym._topo() if not n.is_variable]
        assert executor._plan_conv_groups(nodes, [], None, {}) == {}
        return
    assert _counters(sym, (2, 8, 6, 6), graph_kw) == (0, 0)
    assert len(_conv_eqns(_forward_jaxpr(sym, (2, 8, 6, 6), **graph_kw))) == 2


@pytest.mark.parametrize("case, kw, depth", [
    ("alike", {}, 3),
    ("default_geometry_spelled_out",
     dict(a=dict(stride=(1, 1), pad=(0, 0), dilate=(1, 1), layout="NCHW")), 3),
    ("bias_on_both", dict(a=dict(no_bias=False), b=dict(no_bias=False)), 3),
    ("other_act", dict(act=("relu", "tanh")), 2),
    ("other_eps", dict(bn=(dict(eps=1e-3), dict(eps=2e-5))), 1),
    ("other_momentum", dict(bn=(dict(momentum=0.9), dict(momentum=0.5))), 1),
    ("one_device", dict(groups=("dev1", "dev1")), 3),
])
def test_merge_ends_at_the_last_common_link(case, kw, depth):
    """One convolution either way; how far down the chains the merge
    reaches shows in how many BatchNorm statistics are taken (one
    reduction pair a merged BatchNorm, one a member otherwise)."""
    sym = _pair(**kw)
    graph_kw = dict(node_device=_by_group) if case == "one_device" else {}
    assert _counters(sym, (2, 8, 6, 6), graph_kw) == (1, 2)
    jaxpr = _forward_jaxpr(sym, (2, 8, 6, 6), **graph_kw)
    assert len(_conv_eqns(jaxpr)) == 1
    names = [str(e.source_info.name_stack) for e in jaxpr.eqns]
    assert any("Convolution:conv_a+conv_b" in n for n in names)
    assert any("BatchNorm:bn_a+bn_b" in n for n in names) == (depth >= 2)
    assert any("Activation:act_a+act_b" in n for n in names) == (depth >= 3)
    merged, plain = _both_ways(sym, (2, 8, 6, 6), **graph_kw)
    _assert_same(merged, plain, sym.list_arguments())


def test_second_reader_of_a_member_ends_the_merge_there():
    """A member whose convolution is read twice keeps its own tensor:
    the convolutions merge, the BatchNorms do not."""
    data = mx.sym.Variable("data")
    ca = mx.sym.Convolution(data=data, num_filter=4, kernel=(1, 1),
                            no_bias=True, name="conv_a")
    cb = mx.sym.Convolution(data=data, num_filter=4, kernel=(1, 1),
                            no_bias=True, name="conv_b")
    na = mx.sym.BatchNorm(data=ca, name="bn_a")
    nb = mx.sym.BatchNorm(data=cb, name="bn_b")
    sym = mx.sym.Group([na + ca, nb])
    assert _counters(sym, (2, 8, 6, 6)) == (1, 2)
    jaxpr = _forward_jaxpr(sym, (2, 8, 6, 6))
    assert len(_conv_eqns(jaxpr)) == 1
    assert not any("bn_a+bn_b" in str(e.source_info.name_stack)
                   for e in jaxpr.eqns)
    merged, plain = _both_ways(sym, (2, 8, 6, 6))
    _assert_same(merged, plain, sym.list_arguments())


def test_a_member_that_is_a_graph_output_keeps_its_tensor():
    data = mx.sym.Variable("data")
    ca = mx.sym.Convolution(data=data, num_filter=3, kernel=(1, 1),
                            name="conv_a")
    cb = mx.sym.Convolution(data=data, num_filter=5, kernel=(1, 1),
                            name="conv_b")
    sym = mx.sym.Group([ca, mx.sym.BatchNorm(data=ca, name="bn_a"),
                        mx.sym.BatchNorm(data=cb, name="bn_b")])
    assert _counters(sym, (2, 8, 6, 6)) == (1, 2)
    merged, plain = _both_ways(sym, (2, 8, 6, 6))
    assert merged[0][0].shape == (2, 3, 6, 6)
    _assert_same(merged, plain, sym.list_arguments())


_LSTM = dict(data=(4, 5), softmax_label=(4, 5),
             **{"l%d_init_%s" % (i, s): (4, 16) for i in (0, 1) for s in "hc"})


@pytest.mark.parametrize("build, shapes, groups, members", [
    (lambda: models.get_inception_bn(num_classes=1000),
     dict(data=(2, 3, 224, 224)), 10, 28),
    # one pair of siblings (stage0_unit0: b1 and the unstrided shortcut),
    # 64 channels in and 64 + 256 out: does not pay, stays apart
    (lambda: models.get_resnet50(num_classes=1000),
     dict(data=(2, 3, 224, 224)), 0, 0),
    (lambda: models.get_lenet(num_classes=10), dict(data=(2, 1, 28, 28)),
     0, 0),
    (lambda: models.lstm_unroll(2, 5, 50, 16, 16, 50), _LSTM, 0, 0),
], ids=["inception_bn", "resnet50", "lenet", "lstm"])
def test_counters_of_the_model_zoo(build, shapes, groups, members):
    net = build()
    shapes = dict(shapes)
    assert _counters(net, shapes.pop("data"), **shapes) == (groups, members)


@pytest.mark.parametrize("name, build, shape", [
    ("lenet", lambda: models.get_lenet(num_classes=10), (2, 1, 28, 28)),
    ("resnet", lambda: models.get_resnet([1, 1], [8, 32, 64], num_classes=4,
                                         small_input=True), (2, 3, 8, 8)),
])
def test_graph_without_paying_siblings_lowers_as_before(name, build, shape):
    """A convolution a node, no merged scope, and the very jaxpr of the
    plain path's forward pass."""
    net = build()
    n_convs = sum(1 for n in net._topo()
                  if not n.is_variable and n.op.op_name == "Convolution")
    jaxpr = _forward_jaxpr(net, shape)
    assert len(_conv_eqns(jaxpr)) == n_convs
    assert not any("+" in str(e.source_info.name_stack) for e in jaxpr.eqns)
    eval_graph, _ = make_graph_eval(net)
    args, aux = _inputs(net, shape)
    plain = jax.make_jaxpr(lambda a, x: eval_graph(
        a, x, None, True, want_internals=True)[:2])(args, aux).jaxpr
    assert [str(e.primitive) for e in jaxpr.eqns] == \
        [str(e.primitive) for e in plain.eqns]


@pytest.mark.parametrize("c_in, widths, stride, merged", [
    (64, (64, 256), (1, 1), False),   # ResNet-50 stage0_unit0: 4*64 < 3*320
    (192, (64, 64, 64), (1, 1), True),    # Inception-BN 3a
    (608, (128, 192), (1, 1), True),      # Inception-BN 4e
    (24, (16, 16), (1, 1), True),         # 4*24 = 3*32: the last that pays
    (23, (16, 16), (1, 1), False),
    (8, (16, 16), (2, 2), True),      # strided: the outputs are a quarter
])
def test_merged_only_where_the_bytes_say_it_pays(c_in, widths, stride, merged):
    data = mx.sym.Variable("data")
    sym = mx.sym.Group([
        mx.sym.Convolution(data=data, num_filter=w, kernel=(1, 1),
                           stride=stride, no_bias=True, name="conv_%d" % i)
        for i, w in enumerate(widths)])
    shape = (2, c_in, 8, 8)
    want = (1, len(widths)) if merged else (0, 0)
    assert _counters(sym, shape) == want
    assert len(_conv_eqns(_forward_jaxpr(sym, shape))) == \
        (1 if merged else len(widths))
    got, plain = _both_ways(sym, shape)
    _assert_same(got, plain, sym.list_arguments())


def test_scope_names_all_members():
    sym, shape = _block("inception_a")
    names = {str(e.source_info.name_stack)
             for e in _forward_jaxpr(sym, shape).eqns}
    for op, stem in (("Convolution", "conv"), ("BatchNorm", "bn"),
                     ("Activation", "relu")):
        want = "%s:%s" % (op, "+".join(
            "%s_3a_%s" % (stem, m) for m in ("1x1", "3x3r", "d3x3r")))
        assert any(want in n for n in names), want


def _toy_net():
    data = mx.sym.Variable("data")
    body = _inception_a(data, 6, 4, 6, 4, 8, "avg", 4, "3a")
    body = _inception_b(body, 6, 8, 4, 6, "3c")
    pool = mx.sym.Pooling(data=body, kernel=(1, 1), global_pool=True,
                          pool_type="avg")
    fc = mx.sym.FullyConnected(data=mx.sym.Flatten(data=pool), num_hidden=5,
                               name="fc1")
    return mx.sym.SoftmaxOutput(data=fc, name="softmax")


def _fit(monkeypatch, fused, merge):
    from mxnet_tpu import executor

    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1" if fused else "0")
    if not merge:
        monkeypatch.setattr(executor, "_plan_conv_groups",
                            lambda *a, **k: {})
    net = _toy_net()
    rng = np.random.RandomState(3)
    x = rng.uniform(-1, 1, (16, 8, 9, 9)).astype(np.float32)
    y = rng.randint(0, 5, (16,)).astype(np.float32)
    arg_shapes, _, aux_shapes = net.infer_shape(data=(8, 8, 9, 9))
    arg_params = {n: mx.nd.array(rng.uniform(-.5, .5, s).astype(np.float32))
                  for n, s in zip(net.list_arguments(), arg_shapes)
                  if n not in ("data", "softmax_label")}
    aux_params = {n: mx.nd.array(np.full(s, 1.0 if n.endswith("var") else 0.0,
                                         np.float32))
                  for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=8), num_epoch=2,
            optimizer="sgd",
            optimizer_params=dict(learning_rate=0.05, momentum=0.9),
            initializer=None, arg_params=arg_params, aux_params=aux_params)
    return net, mod


@pytest.mark.parametrize("fused", [False, True], ids=["classic", "fused"])
def test_fit_merged_equals_unmerged(monkeypatch, fused):
    """Four optimizer steps through Module.fit, classic loop and fused
    step: the trained parameters and moving statistics agree with the
    planner switched off underneath."""
    _, merged = _fit(monkeypatch, fused, merge=True)
    _, plain = _fit(monkeypatch, fused, merge=False)
    for got, want in zip(merged.get_params(), plain.get_params()):
        assert sorted(got) == sorted(want)
        for name in want:
            np.testing.assert_allclose(got[name].asnumpy(),
                                       want[name].asnumpy(),
                                       err_msg=name, rtol=1e-4, atol=1e-5)


def test_parameter_names_shapes_and_checkpoint_round_trip(monkeypatch,
                                                          tmp_path):
    net, mod = _fit(monkeypatch, fused=True, merge=True)
    args, auxs = mod.get_params()
    shapes = {n: a.shape for n, a in args.items()}
    # every member keeps its own OIHW weight and BatchNorm leaves
    assert shapes["conv_3a_1x1_weight"] == (6, 8, 1, 1)
    assert shapes["conv_3a_3x3r_weight"] == (4, 8, 1, 1)
    assert shapes["conv_3a_d3x3r_weight"] == (4, 8, 1, 1)
    assert shapes["bn_3a_3x3r_gamma"] == (4,)
    assert auxs["bn_3a_d3x3r_moving_var"].shape == (4,)
    assert sorted(args) == sorted(
        n for n in net.list_arguments() if n not in ("data", "softmax_label"))
    assert sorted(auxs) == sorted(net.list_auxiliary_states())
    prefix = str(tmp_path / "toy")
    mod.save_checkpoint(prefix, 2)
    sym2, args2, auxs2 = mx.model.load_checkpoint(prefix, 2)
    assert sym2.list_arguments() == net.list_arguments()
    for saved, live in ((args2, args), (auxs2, auxs)):
        assert sorted(saved) == sorted(live)
        for name in live:
            np.testing.assert_array_equal(saved[name].asnumpy(),
                                          live[name].asnumpy())
    x = np.random.RandomState(5).uniform(-1, 1, (8, 8, 9, 9)) \
        .astype(np.float32)
    again = mx.mod.Module(sym2, context=mx.cpu())
    again.bind(data_shapes=[("data", x.shape)], for_training=False)
    again.set_params(args2, auxs2)
    it = mx.io.NDArrayIter(x, np.zeros(8, np.float32), batch_size=8)
    np.testing.assert_allclose(again.predict(it).asnumpy(),
                               mod.predict(it).asnumpy(), **TOL)


@pytest.mark.multichip
def test_sibling_weights_sharded_differently_give_the_same_numbers():
    """A tensor-parallel plan may shard one sibling's weight on its
    output channels and another's on its input channels: the
    concatenation inside the program is GSPMD's to partition, and the
    numbers are those of one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sym, shape = _block("inception_a")
    eval_graph, _ = make_graph_eval(sym)
    args, aux = _inputs(sym, shape)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    specs = {"data": P("dp"), "conv_3a_1x1_weight": P("tp"),
             "conv_3a_3x3r_weight": P(None, "tp"),
             "bn_3a_1x1_gamma": P("tp")}
    placed = [jax.device_put(a, NamedSharding(mesh, specs.get(n, P())))
              for n, a in zip(sym.list_arguments(), args)]

    def loss(args, plain):
        outs = eval_graph(args, aux, None, True, want_internals=plain)[0]
        return jnp.sum(jnp.square(outs[0]))

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    one_l, one_g = grad(args, True)
    many_l, many_g = grad(placed, False)
    _close(many_l, one_l, "loss")
    for name, a, b in zip(sym.list_arguments(), many_g, one_g):
        _close(a, b, name, loose=4.0)     # the batch's sums are partial sums
