"""The plain reference against the program on seeded weights at toy
width (forward loss, gradients, three SGD steps), and the controls: the
reference with 8-bit matmul inputs must fail a limit that the stated
precision passes."""
import jax
import numpy as np
import pytest

import toy
from benchmark.reference import check, train

RECIPE = {"learning_rate": 0.004, "momentum": 0.9, "wd": 1e-4}


def _program_readings(config, seed, batches):
    """Three steps of the program's own executor and optimizer at full
    precision, outside fit: (losses, first gradients, final params)."""
    import mxnet_tpu as mx
    from benchmark.drivers import fit

    net = fit._factory(config["model"]["factory"])(**config["model"]["args"])
    shape = (config["batch"],) + tuple(config["input_chw"])
    ref = config["reference"]
    shapes = train.param_shapes(ref["net"], ref["args"], shape)
    params0 = train.init_params(shapes, seed)
    mod = mx.mod.Module(net, context=[mx.cpu(0)])
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", shape[:1])])
    mod.init_params(
        initializer=None,
        arg_params={k: mx.nd.array(np.asarray(v))
                    for k, v in params0.items()},
        aux_params={k: mx.nd.array(np.full(s, f, np.float32))
                    for k, (s, f) in train.aux_shapes(shapes).items()})
    mod.init_optimizer(optimizer="sgd", optimizer_params=dict(RECIPE))
    losses, grads = [], None
    for x, y in batches:
        mod.forward_backward(mx.io.DataBatch([mx.nd.array(x)],
                                             [mx.nd.array(y)]))
        prob = mod.get_outputs()[0].asnumpy()
        losses.append(float(-np.log(
            prob[np.arange(len(y)), y.astype(int)]).mean()))
        if grads is None:
            ex = mod._exec_group.executor
            grads = {n: ex.grad_dict[n].asnumpy() / len(y)
                     for n in mod._param_names}
        mod.update()
    final, _ = mod.get_params()
    return params0, losses, grads, {k: v.asnumpy() for k, v in final.items()}


# (later losses, worst-leaf gaps). ResNet: float32 on both sides in
# different summation orders, and 1e-4 / 1e-3 leave room for nothing
# else (bf16 moves these by 1e-2). Inception-BN: the program's BatchNorm
# takes the variance in one pass, E[x^2] - E[x]^2, which in float32 loses
# digits where a channel's mean dwarfs its spread (the 1x1 projections
# behind the 3x3 average pools); the reference's two-pass form does not.
# With the reference switched to the one-pass form the two agree to 1e-6
# (PR 23), so the 2e-2 here is that formula's error, not a layer's; over
# three steps at batch 8 it compounds, hence the loose last tolerance.
@pytest.mark.parametrize("kind,loss_tol,leaf_tol,delta_tol", [
    ("resnet", 1e-4, 1e-3, 1e-3), ("inception_bn", 5e-2, 2e-2, 0.3)])
def test_reference_matches_the_program_in_float32(kind, loss_tol, leaf_tol,
                                                  delta_tol, monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    config = toy.cell(kind)["config"]
    shape = (8,) + tuple(config["input_chw"])
    config["batch"] = 8
    rng = np.random.default_rng(3)
    batches = [(rng.standard_normal(shape, dtype=np.float32),
                rng.integers(0, 10, 8).astype(np.float32))
               for _ in range(3)]
    params0, losses, grads, final = _program_readings(config, 5, batches)
    ref = config["reference"]
    want = train.follow(ref["net"], ref["args"], RECIPE, params0, batches)
    want_l, want_g, want_d = (want["losses"], want["grad_norms"],
                              want["delta_norms"])
    np.testing.assert_allclose(losses[0], want_l[0], rtol=1e-4)
    np.testing.assert_allclose(losses, want_l, rtol=loss_tol)
    got_g = {k: float(np.linalg.norm(v)) for k, v in grads.items()}
    got_d = {k: float(np.linalg.norm(final[k] - np.asarray(params0[k])))
             for k in final}
    assert max(check.leaf_gaps(got_g, want_g).values()) < leaf_tol
    assert max(check.leaf_gaps(got_d, want_d).values()) < delta_tol


@pytest.mark.parametrize("kind,batch", [("resnet", 64), ("inception_bn", 8)])
def test_controls_fail_where_the_stated_precision_passes(kind, batch):
    """The controls at a size a test run can hold (the chip readings at
    the cells' own size are in PERF.md): a pipeline that rounds its
    tensors to bfloat16 at other places than the reference's own carries
    about the floor's noise again and passes; 8-bit matmul inputs on top
    of bfloat16 carry several times more and fail."""
    cell = toy.cell(kind)
    config, ref = cell["config"], cell["config"]["reference"]
    shape = (batch,) + tuple(config["input_chw"])
    params0 = train.init_params(
        train.param_shapes(ref["net"], ref["args"], shape), 11)
    x = jax.numpy.asarray(np.random.default_rng(4).standard_normal(
        shape, dtype=np.float32))
    logp = {p: train.forward_logprob(ref["net"], ref["args"], params0, x, p)
            for p in (None, "bfloat16", "bf16_accumulate", "int8_matmul",
                      "fp8_matmul")}
    excess = {p: check.excess_noise(logp[p], logp["bfloat16"], logp[None])[0]
              for p in logp if p}
    print(kind, excess)
    limit = cell["limits"]["step1_excess_noise"]
    assert excess["bfloat16"] == 0.0
    assert excess["int8_matmul"] > 2 * limit
    assert excess["fp8_matmul"] > 2 * limit
    assert excess["bf16_accumulate"] > -1.0   # it runs; PERF.md: not caught


def test_a_net_is_a_file_of_its_own(tmp_path, monkeypatch):
    """A configuration names its reference net by file: a new net is a
    new file under ``reference/`` and no edit to one that is there."""
    from benchmark import reference

    (tmp_path / "two_layer.py").write_text(
        "def net(ops, x, width=4):\n"
        "    x = ops.conv(x, 'c1', width, 3, 1, 1)\n"
        "    x = ops.bn(x, 'b1', 1e-5, relu=True)\n"
        "    return ops.fc(ops.global_avg(x), 'fc1', 3)\n")
    monkeypatch.setattr(reference, "__path__",
                        list(reference.__path__) + [str(tmp_path)])
    shapes = train.param_shapes("two_layer", {"width": 6}, (2, 3, 8, 8))
    assert shapes == {"c1_weight": (6, 3, 3, 3), "b1_gamma": (6,),
                      "b1_beta": (6,), "fc1_weight": (3, 6),
                      "fc1_bias": (3,)}
    logp = train.forward_logprob("two_layer", {"width": 6},
                                 train.init_params(shapes, 1),
                                 jax.numpy.ones((2, 3, 8, 8)))
    assert logp.shape == (2, 3)


def test_leaf_gaps_measure_small_leaves_against_the_median():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 3e-9}       # c is all but zero
    gaps = check.leaf_gaps(got, want)
    assert max(gaps, key=gaps.get) == "a" and gaps["a"] == pytest.approx(0.1)
    assert check.dead_leaves(got, want) == []
    assert check.dead_leaves(dict(got, b=0.1), want) == ["b"]


def test_seed_key_takes_seeds_past_31_bits():
    a = train.seed_key(2 ** 31 + 5)
    b = train.seed_key(5)
    assert not np.array_equal(jax.random.key_data(a) if hasattr(
        jax.random, "key_data") else a, jax.random.key_data(b))
