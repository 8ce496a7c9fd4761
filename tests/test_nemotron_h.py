"""The language-model operators (RMSNorm, squared ReLU, CausalConv1D,
SSMScan, CausalAttention, RoutedExperts) against plain ``jax.numpy``, and
``models.get_nemotron_h`` through ``Module.fit`` on the fused step against
the benchmark's float32 reference. Toy widths, seeded."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_nemotron_h
from mxnet_tpu.ops import moe as moe_ops

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import nemotron_h as ref  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)

TOY = dict(pattern="MEMEMEM*E", hidden=32, vocab=96, experts_total=16,
           experts_held=4, first_expert=4, seq_len=24, mamba_heads=4,
           mamba_head_dim=8, ssm_groups=2, ssm_state=8, chunk=16,
           attn_heads=4, kv_heads=2, head_dim=8, top_k=3, expert_hidden=16,
           shared_hidden=24)


def aux_states(net, shapes, given=None):
    """The auxiliary states of ``net`` in their op's shapes and dtypes:
    zeros, or ``given`` by the end of a state's name."""
    out = []
    for name, shape, dtype in zip(net.list_auxiliary_states(),
                                  net.infer_shape(**shapes)[2],
                                  net.infer_type()[2]):
        value = next((v for k, v in (given or {}).items()
                      if name.endswith(k)), np.zeros(shape))
        out.append(mx.nd.array(np.asarray(value), dtype=dtype))
    return out


def run_op(net, inputs, head, aux=None):
    """Outputs and input gradients of a one-output symbol, through bind /
    forward / backward."""
    args = {k: mx.nd.array(v, dtype=v.dtype) for k, v in inputs.items()}
    grads = {k: mx.nd.zeros(v.shape) for k, v in inputs.items()
             if np.issubdtype(v.dtype, np.floating)}
    ex = net.bind(mx.cpu(), args, args_grad=grads,
                  aux_states=aux_states(
                      net, {k: v.shape for k, v in inputs.items()}, aux))
    ex.forward(is_train=True)
    ex.backward([mx.nd.array(head)])
    return ex.outputs[0].asnumpy(), {k: g.asnumpy() for k, g in grads.items()}


def close(a, b, tol=2e-5):
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) <= tol * scale, \
        (float(np.abs(np.asarray(a) - np.asarray(b)).max()), scale)


def against(fn, net, inputs, seed=0, tol=2e-5, aux=None):
    """``net`` (a Symbol over ``inputs``) against the plain ``fn(**inputs)``:
    the output, and every float input's gradient under a random head."""
    want = fn(**{k: jnp.asarray(v) for k, v in inputs.items()})
    head = np.random.default_rng(seed).standard_normal(want.shape).astype(
        np.float32)
    floats = [k for k, v in inputs.items()
              if np.issubdtype(v.dtype, np.floating)]
    want_g = jax.grad(lambda fl: jnp.sum(fn(**{
        **{k: jnp.asarray(v) for k, v in inputs.items()}, **fl}) * head))(
            {k: jnp.asarray(inputs[k]) for k in floats})
    got, got_g = run_op(net, inputs, head, aux)
    close(got, want, tol)
    for k in floats:
        close(got_g[k], want_g[k], tol)


def rng_inputs(seed, **shapes):
    rng = np.random.default_rng(seed)
    return {k: rng.standard_normal(s).astype(np.float32)
            for k, s in shapes.items()}


# ---------------------------------------------------------------------------
# normalisation, activation, convolution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("groups,gated", [(1, False), (4, True)])
def test_rmsnorm_against_plain(groups, gated):
    shapes = dict(data=(6, 16), gamma=(16,))
    if gated:
        shapes["gate"] = (6, 16)
    inputs = rng_inputs(1, **shapes)

    def plain(data, gamma, gate=None):
        y = data * jax.nn.silu(gate) if gate is not None else data
        yg = y.reshape(6, groups, 16 // groups)
        yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + 1e-5)
        return yg.reshape(6, 16) * gamma

    v = {k: sym.Variable(k) for k in inputs}
    against(plain, sym.RMSNorm(eps=1e-5, num_groups=groups, gated=gated, **v),
            inputs)


def test_rmsnorm_shape_and_type_inference():
    net = sym.RMSNorm(data=sym.Variable("data"), num_groups=2, name="n")
    arg, out, _ = net.infer_shape(data=(5, 8))
    assert arg == [(5, 8), (8,)] and out == [(5, 8)]
    with pytest.raises(mx.MXNetError):
        sym.RMSNorm(data=sym.Variable("data"), num_groups=3).infer_shape(
            data=(5, 8))
    types, _, _ = net.infer_type(data=np.float32)
    assert all(t == np.float32 for t in types)


@pytest.mark.parametrize("act,plain", [
    ("relu2", lambda x: jnp.square(jnp.maximum(x, 0))),
    ("silu", jax.nn.silu)])
def test_activation_types(act, plain):
    inputs = rng_inputs(2, data=(5, 7))
    against(lambda data: plain(data),
            sym.Activation(data=sym.Variable("data"), act_type=act), inputs)


def test_causal_conv1d_against_plain():
    t, c, k = 10, 6, 4
    inputs = rng_inputs(3, data=(2 * t, c), weight=(c, k), bias=(c,))

    def plain(data, weight, bias):
        x = data.reshape(2, t, c)
        rows = []
        for pos in range(t):
            acc = bias
            for i in range(k):
                src = pos - (k - 1) + i
                if src >= 0:
                    acc = acc + weight[:, i] * x[:, src]
            rows.append(acc)
        return jnp.stack(rows, axis=1).reshape(2 * t, c)

    v = {n: sym.Variable(n) for n in inputs}
    against(plain, sym.CausalConv1D(kernel=k, seq_len=t, **v), inputs)
    with pytest.raises(mx.MXNetError):
        sym.CausalConv1D(data=sym.Variable("data"), kernel=k,
                         seq_len=7).infer_shape(data=(20, c))


# ---------------------------------------------------------------------------
# the chunked scan against the step-by-step recurrence
# ---------------------------------------------------------------------------
def plain_scan(data, dt, A_log, D, dt_bias, t, h, p, g, n):
    """The recurrence, a position at a time."""
    b = data.shape[0] // t
    di, gn = h * p, g * n
    x = data[:, :di].reshape(b, t, h, p)
    bm = jnp.repeat(data[:, di:di + gn].reshape(b, t, g, n), h // g, axis=2)
    cm = jnp.repeat(data[:, di + gn:].reshape(b, t, g, n), h // g, axis=2)
    step = jax.nn.softplus(dt.reshape(b, t, h) + dt_bias)
    a = -jnp.exp(A_log)

    def one(s, v):
        x_i, step_i, b_i, c_i = v
        s = jnp.exp(step_i * a)[..., None, None] * s \
            + (step_i[..., None] * x_i)[..., None] * b_i[:, :, None]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_i) + D[:, None] * x_i

    _, ys = jax.lax.scan(one, jnp.zeros((b, h, p, n)),
                         tuple(v.swapaxes(0, 1) for v in (x, step, bm, cm)))
    return ys.swapaxes(0, 1).reshape(b * t, di)


def scan_net(t, h, p, g, n, chunk):
    names = ("data", "dt", "A_log", "D", "dt_bias")
    return sym.SSMScan(num_heads=h, head_dim=p, num_groups=g, state_size=n,
                       chunk=chunk, seq_len=t,
                       **{k: sym.Variable(k) for k in names})


def scan_inputs(seed, rows, h, p, g, n, dt_shift=-2.0, a_shift=0.0):
    inputs = rng_inputs(seed, data=(rows, h * p + 2 * g * n), dt=(rows, h),
                        A_log=(h,), D=(h,), dt_bias=(h,))
    inputs["dt_bias"] += dt_shift     # -2: steps of about 0.1
    inputs["A_log"] += a_shift
    return inputs


def scan_counters():
    return tuple(telemetry.peek("lower.scan_kernel." + k) or 0
                 for k in ("pallas_chunked", "xla_chunked"))


# whole tiles: the Pallas chunk kernel (the interpreter here); anything
# else: the same written passes in jax.numpy
TOY_SCAN = dict(h=4, p=3, g=2, n=5, chunk=8)
TILE_SCAN = dict(h=8, p=64, g=2, n=128, chunk=128)


@pytest.mark.parametrize("shape,t,body", [
    (TOY_SCAN, 8, "xla"), (TOY_SCAN, 12, "xla"), (TOY_SCAN, 24, "xla"),
    (TILE_SCAN, 384, "pallas"), (TILE_SCAN, 512, "pallas"),
    (TILE_SCAN, 200, "pallas"), (dict(TILE_SCAN, p=128, h=4), 256, "pallas"),
    (dict(TILE_SCAN, n=64), 128, "xla"), (dict(TILE_SCAN, chunk=64), 128,
                                          "xla"),
], ids=["1chunk", "1.5chunks", "3chunks", "kernel-3chunks", "kernel-4chunks",
        "kernel-padded-tail", "kernel-head-of-128", "state-of-64-is-xla",
        "chunk-of-64-is-xla"])
def test_ssm_scan_against_recurrence(shape, t, body):
    """Both bodies, two sequences: the output and the gradients of all
    five arguments against ``jax.grad`` of the step-by-step recurrence in
    float32, and the counter that says which body the shapes chose."""
    h, p, g, n = (shape[k] for k in "hpgn")
    # at a chunk of 128 the steps are a model's (about 0.05, A in -2..0):
    # the cumulative decay inside a chunk is a float32 sum, so steps that
    # drive it to the hundreds cost the CHUNKED form digits the
    # position-by-position recurrence keeps
    inputs = scan_inputs(4, 2 * t, h, p, g, n) if shape["chunk"] < 64 \
        else scan_inputs(4, 2 * t, h, p, g, n, dt_shift=-3.0, a_shift=-1.0)
    net = scan_net(t, h, p, g, n, shape["chunk"])
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_scan(t=t, h=h, p=p, g=g, n=n, **kw), net,
                inputs, tol=5e-5)
        assert scan_counters() == ((1, 0) if body == "pallas" else (0, 1))
    finally:
        telemetry.disable()


def chunked_scan_rounding_state(inputs, t, h, p, g, n, chunk, state_dtype):
    """The recurrence in float32, a chunk at a time, the state carried to
    the next chunk rounded to ``state_dtype``: what a kernel whose scratch
    is bfloat16 would compute, nothing else rounded."""
    step = jax.nn.softplus(inputs["dt"].reshape(t, h) + inputs["dt_bias"])
    decay = jnp.exp(step * -jnp.exp(inputs["A_log"]))
    di, gn = h * p, g * n
    x = inputs["data"][:, :di].reshape(t, h, p)
    bm = jnp.repeat(inputs["data"][:, di:di + gn].reshape(t, g, n), h // g, 1)
    cm = jnp.repeat(inputs["data"][:, di + gn:].reshape(t, g, n), h // g, 1)

    def one(s, v):
        d, dx, b, c = v
        s = d[:, None, None] * s + dx[..., None] * b[:, None]
        return s, jnp.einsum("hpn,hn->hp", s, c)

    s, ys = jnp.zeros((h, p, n)), []
    for i in range(0, t, chunk):
        sl = slice(i, i + chunk)
        s, y = jax.lax.scan(one, s, (decay[sl], step[sl, :, None] * x[sl],
                                     bm[sl], cm[sl]))
        s = s.astype(state_dtype).astype(jnp.float32)
        ys.append(y)
    return (jnp.concatenate(ys) + inputs["D"][:, None] * x).reshape(t, di)


@pytest.mark.parametrize("shape,t", [(dict(TOY_SCAN, p=8, n=8), 8 * 48),
                                     (dict(TILE_SCAN, h=2, g=1), 128 * 32)],
                         ids=["xla", "pallas"])
def test_ssm_scan_carries_a_float32_state_under_bfloat16(shape, t):
    """bfloat16 inputs, many chunks, next to no decay, a first chunk that
    leaves a state a thousand times what each later chunk adds: rounded to
    bfloat16 at a chunk's end, the state swallows every later addition
    (each under half a unit in its last place) and drifts from the
    float32 recurrence by a part in a thousand a chunk. The tolerance sits
    between that and the rounding of the matrix products' inputs. The
    mirror holds the gradient's carried state: a last chunk whose head
    gradient is a thousand times the others'."""
    from mxnet_tpu.executor import make_graph_eval

    h, p, g, n, chunk = (shape[k] for k in ("h", "p", "g", "n", "chunk"))
    inputs = scan_inputs(6, t, h, p, g, n, dt_shift=-4.0, a_shift=-12.0)
    inputs["data"] = np.abs(inputs["data"])     # one sign: the state grows
    inputs["data"][:chunk, :h * p] *= 1000.0
    inputs["D"] *= 0.0
    inputs = {k: np.asarray(jnp.asarray(v, jnp.bfloat16 if k in (
        "data", "dt") else jnp.float32)) for k, v in inputs.items()}
    as_f32 = {k: jnp.asarray(v, jnp.float32) for k, v in inputs.items()}
    want = chunked_scan_rounding_state(as_f32, t, h, p, g, n, chunk,
                                       jnp.float32)
    rounded = chunked_scan_rounding_state(as_f32, t, h, p, g, n, chunk,
                                          jnp.bfloat16)
    net = scan_net(t, h, p, g, n, chunk)
    eval_graph, _ = make_graph_eval(net)
    names = net.list_arguments()

    def gap(a, b):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    got, vjp = jax.vjp(lambda data: eval_graph(
        [data if k == "data" else jnp.asarray(inputs[k]) for k in names], [],
        None, True)[0][0], jnp.asarray(inputs["data"]))
    assert got.dtype == jnp.bfloat16
    assert gap(got, want) < 4e-3 < 8e-3 < gap(rounded, want), \
        (gap(got, want), gap(rounded, want))
    head = np.ones(want.shape, np.float32)
    head[-chunk:] *= 1000.0
    want_dx = jax.grad(lambda d: jnp.sum(head * chunked_scan_rounding_state(
        {**as_f32, "data": d}, t, h, p, g, n, chunk, jnp.float32)))(
            as_f32["data"])[:-chunk, :h * p]
    dx = vjp(jnp.asarray(head, got.dtype))[0][:-chunk, :h * p]
    assert gap(dx, want_dx) < 4e-3, gap(dx, want_dx)


@pytest.mark.parametrize("shape,t", [(dict(TOY_SCAN, p=8, n=8), 8 * 64),
                                     (dict(TILE_SCAN, h=2, g=1), 128 * 32)],
                         ids=["xla", "pallas"])
def test_ssm_scan_step_gradients_hold_along_a_long_sequence(shape, t):
    """bfloat16, many chunks: the gradients of ``A_log`` and ``dt_bias``
    sum the decay's gradient over every position. Written as ``<dy_t, y_t>
    - <x_t, dx_t>`` summed from the sequence's end, that gradient is a
    difference of two differently rounded products and drifts (it read
    2-3 times the true value here, and 82% off on the chip at 8,192
    positions); as one matrix by rows and columns inside each chunk it
    stays with float32 autodiff of the recurrence."""
    from mxnet_tpu.executor import make_graph_eval

    h, p, g, n, chunk = (shape[k] for k in ("h", "p", "g", "n", "chunk"))
    inputs = scan_inputs(8, t, h, p, g, n, dt_shift=-3.0, a_shift=-1.0)
    inputs["data"][:, h * p:] *= 0.3
    inputs = {k: jnp.asarray(v, jnp.bfloat16 if k in ("data", "dt")
                             else jnp.float32) for k, v in inputs.items()}
    head = jnp.asarray(np.random.default_rng(2).standard_normal(
        (t, h * p)), jnp.bfloat16)
    names = scan_net(t, h, p, g, n, chunk).list_arguments()
    eval_graph, _ = make_graph_eval(scan_net(t, h, p, g, n, chunk))
    got = jax.grad(lambda fl: jnp.sum(eval_graph(
        [fl[k] for k in names], [], None, True)[0][0].astype(jnp.float32)
        * head.astype(jnp.float32)))(inputs)
    want = jax.jit(jax.grad(lambda fl: jnp.sum(plain_scan(
        t=t, h=h, p=p, g=g, n=n, **fl) * head.astype(jnp.float32))))(
            {k: v.astype(jnp.float32) for k, v in inputs.items()})
    for k in ("A_log", "dt_bias", "D"):
        close(got[k], want[k], tol=2e-2)


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sub_jaxprs(sub)


def test_ssm_scan_scratch_and_carries_are_float32():
    """What crosses chunks, by dtype, in both bodies' traced passes: the
    kernels' VMEM scratch, the ``lax.scan`` carries of the XLA body and
    the chunk-start states kept for the backward pass."""
    from mxnet_tpu.ops import pallas_kernels, seq

    dims, chunk, t = (4, 64, 2, 128), 128, 256
    h, p, g, n = dims
    bf16 = jnp.bfloat16
    args = (jnp.ones((1, t, h * p + 2 * g * n), bf16), jnp.ones((1, t, h)),
            -jnp.ones((h,)), jnp.ones((h,)))
    for kernel in (True, False):
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(seq.ssd_scan(
            *a, dims, chunk, kernel).astype(jnp.float32)),
            argnums=range(4)))(*args)
        scratch, carries = [], []
        for sub in _sub_jaxprs(jaxpr.jaxpr):
            for eqn in sub.eqns:
                if eqn.primitive.name == "pallas_call":
                    scratch += [a.dtype for a in
                                eqn.params["grid_mapping"].scratch_avals]
                if eqn.primitive.name == "scan" and eqn.params["num_carry"]:
                    nc, k = eqn.params["num_consts"], eqn.params["num_carry"]
                    carries += [v.aval.dtype for v in eqn.invars[nc:nc + k]
                                if v.aval.ndim == 4]
        held = scratch if kernel else carries
        # the interpreter's and Mosaic's branch each hold both kernels
        assert len(held) == (4 if kernel else 2), (kernel, held)
        assert all(d == jnp.float32 for d in held), (kernel, held)
    _, starts = pallas_kernels.ssd_chunk_forward(*args, dims=dims,
                                                 chunk=chunk,
                                                 with_states=True)
    assert starts.dtype == jnp.float32
    x, b_mat = jnp.ones((t, h, p), bf16), jnp.ones((t, g, n), bf16)
    assert seq.ssd_chunked(x, args[1][0], args[2], args[3], b_mat, b_mat,
                           chunk)[1].dtype == jnp.float32


@pytest.mark.parametrize("build,shapes", [
    (lambda: mx.models.get_lenet(num_classes=10), dict(data=(2, 1, 28, 28))),
    (lambda: mx.models.get_resnet([1, 1], [8, 32, 64], num_classes=4,
                                  small_input=True), dict(data=(2, 3, 8, 8))),
    (lambda: mx.models.lstm_unroll(2, 5, 50, 16, 16, 50),
     dict(data=(4, 5), softmax_label=(4, 5),
          **{"l%d_init_%s" % (i, s): (4, 16) for i in (0, 1) for s in "hc"})),
], ids=["lenet", "resnet", "lstm"])
def test_nets_without_a_scan_count_no_scan_kernel(build, shapes):
    from mxnet_tpu.executor import make_graph_eval

    net = build()
    telemetry.reset()
    telemetry.enable()
    try:
        eval_graph, _ = make_graph_eval(net)
        arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
        jax.eval_shape(
            lambda a, x: eval_graph(a, x, None, True),
            [jax.ShapeDtypeStruct(s, jnp.float32) for s in arg_shapes],
            [jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_shapes])
        assert scan_counters() == (0, 0)
    finally:
        telemetry.disable()


def test_ssm_scan_shapes():
    net = sym.SSMScan(data=sym.Variable("data"), dt=sym.Variable("dt"),
                      num_heads=4, head_dim=3, num_groups=2, state_size=5,
                      seq_len=6, name="s")
    arg, out, _ = net.infer_shape(data=(12, 32))
    assert arg == [(12, 32), (12, 4), (4,), (4,), (4,)] and out == [(12, 12)]
    assert net.list_arguments() == ["data", "dt", "s_A_log", "s_D",
                                    "s_dt_bias"]
    with pytest.raises(mx.MXNetError):
        net.infer_shape(data=(12, 30))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def plain_attention(query, key, value, t, hq, hkv, d, rotary):
    b = query.shape[0] // t
    q = query.reshape(b, t, hq, d)
    k = jnp.repeat(key.reshape(b, t, hkv, d), hq // hkv, axis=2)
    v = jnp.repeat(value.reshape(b, t, hkv, d), hq // hkv, axis=2)
    if rotary:
        q, k = ref._rope(q, 10000.0), ref._rope(k, 10000.0)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return out.reshape(b * t, hq * d)


@pytest.mark.parametrize("rotary", [True, False])
def test_attention_grouped_heads_against_masked_softmax(rotary, monkeypatch):
    from mxnet_tpu.ops import attention

    monkeypatch.setattr(attention, "BLOCK_Q", 8)    # three blocks, one short
    t, hq, hkv, d = 20, 4, 2, 8
    inputs = rng_inputs(5, query=(2 * t, hq * d), key=(2 * t, hkv * d),
                        value=(2 * t, hkv * d))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.CausalAttention(num_heads=hq, num_kv_heads=hkv, head_dim=d,
                              seq_len=t, rotary=rotary, **v)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_attention(t=t, hq=hq, hkv=hkv, d=d,
                                             rotary=rotary, **kw), net,
                inputs, tol=5e-5)
        assert telemetry.peek("lower.attention_kernel.xla_blockwise") >= 1
    finally:
        telemetry.disable()


def test_attention_ungrouped_heads_whole_blocks():
    """As many key/value heads as query heads, a sequence of whole query
    blocks and a head narrower than the lanes splash wants: the blockwise
    lowering serves it."""
    t, h, d = 128, 2, 8
    inputs = rng_inputs(6, query=(t, h * d), key=(t, h * d),
                        value=(t, h * d))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.CausalAttention(num_heads=h, num_kv_heads=h, head_dim=d,
                              seq_len=t, **v)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_attention(t=t, hq=h, hkv=h, d=d,
                                             rotary=True, **kw), net, inputs,
                tol=5e-5)
        assert telemetry.peek("lower.attention_kernel.xla_blockwise") >= 1
        assert not telemetry.peek("lower.attention_kernel.pallas_splash")
    finally:
        telemetry.disable()


def test_attention_shape_inference():
    net = sym.CausalAttention(query=sym.Variable("q"), key=sym.Variable("k"),
                              value=sym.Variable("v"), num_heads=4,
                              num_kv_heads=2, head_dim=8, seq_len=5)
    arg, out, _ = net.infer_shape(q=(10, 32))
    assert arg == [(10, 32), (10, 16), (10, 16)] and out == [(10, 32)]
    with pytest.raises(mx.MXNetError):
        sym.CausalAttention(query=sym.Variable("q"), key=sym.Variable("k"),
                            value=sym.Variable("v"), num_heads=4,
                            num_kv_heads=3, head_dim=8,
                            seq_len=5).infer_shape(q=(10, 32))


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
def plain_experts(data, router_weight, up_weight, down_weight, select_bias,
                  first, top_k, scale):
    scores = jax.nn.sigmoid(data @ router_weight)
    _, eid = jax.lax.top_k(scores + select_bias, top_k)
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    wts = chosen / chosen.sum(1, keepdims=True) * scale
    out = jnp.zeros_like(data)
    for j in range(up_weight.shape[0]):
        gate = jnp.sum(jnp.where(eid == first + j, wts, 0.0), axis=1)
        out = out + gate[:, None] * (jnp.square(jnp.maximum(
            data @ up_weight[j], 0.0)) @ down_weight[j])
    return out


def skewed_expert_inputs(seed, rows=64, h=12, e=8, held=3, f=10, hot=2):
    """A router that sends well over half the rows to expert ``hot``: the
    op's inputs and its selection bias (a state)."""
    inputs = rng_inputs(seed, data=(rows, h), router_weight=(h, e),
                        up_weight=(held, h, f), down_weight=(held, f, h))
    bias = np.zeros(e, np.float32)
    bias[hot] = 5.0
    return inputs, bias


def test_routed_experts_against_loop_under_a_skewed_router():
    first, top_k, e, held = 1, 2, 8, 3
    inputs, bias = skewed_expert_inputs(7)
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.RoutedExperts(num_experts=e, num_held=held, first_held=first,
                            top_k=top_k, scale=2.5, num_hidden=10, **v)
    assert [n.split("_", 1)[1] for n in net.list_auxiliary_states()] == [
        "expert_rows", "select_bias"]
    against(lambda **kw: plain_experts(select_bias=jnp.asarray(bias),
                                       first=first, top_k=top_k, scale=2.5,
                                       **kw), net, inputs, tol=5e-5,
            aux={"select_bias": bias})
    # every row went to the hot expert: no capacity would hold them
    eid, wts = moe_ops.route(jnp.asarray(inputs["data"]),
                             jnp.asarray(inputs["router_weight"]),
                             jnp.asarray(bias), top_k, 2.5)
    assert int((np.asarray(eid) == 2).any(axis=1).sum()) > 32
    rows, weights, slot, _, block_expert, nblocks, dropped = moe_ops.plan(
        eid, wts, first, held, 8)
    assert int(dropped) == 0
    here = (np.asarray(eid) >= first) & (np.asarray(eid) < first + held)
    assert int((np.asarray(weights) != 0).sum()) == int(here.sum())
    assert int(nblocks) * 8 >= int(here.sum())
    # every pair that lands here has a slot of its own, the others none
    slot = np.asarray(slot)
    assert (slot[here] < len(np.asarray(rows))).all()
    assert len(set(slot[here].tolist())) == int(here.sum())
    assert (slot[~here] == len(np.asarray(rows))).all()
    assert (np.asarray(rows)[slot[here]] == np.nonzero(here)[0]).all()


def test_routed_experts_counts_rows_on_the_device():
    inputs, bias = skewed_expert_inputs(8)
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.RoutedExperts(num_experts=8, num_held=3, first_held=1,
                            top_k=2, scale=1.0, num_hidden=10, name="x", **v)
    shapes = {k: a.shape for k, a in inputs.items()}
    assert net.infer_shape(**shapes)[2] == [(9,), (8,)]
    assert net.infer_type()[2] == [np.dtype(np.int32), np.dtype(np.float32)]
    ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in inputs.items()},
                  aux_states=aux_states(net, shapes, {"select_bias": bias}))
    before = ex.aux_arrays[0].asnumpy()
    for _ in range(2):
        ex.forward(is_train=True)
        ex.backward([mx.nd.ones((64, 12))])
    after = ex.aux_arrays[0].asnumpy()
    assert after.dtype == np.int32
    # no bias_update_rate: the selection bias stays as it was handed over
    assert (ex.aux_arrays[1].asnumpy() == bias).all()
    op = net._outputs[0][0].op
    counters, gauges = op.aux_counters(before, after)
    assert counters["moe.rows_total"] == 2 * 64 * 2
    assert counters["moe.dropped_rows"] == 0
    assert 0 < counters["moe.rows_here"] <= counters["moe.rows_total"]
    assert gauges["moe.expert_load_max_over_mean"] > 1.5
    # the device's int32 wraps; the increment does not
    c2, _ = op.aux_counters(np.full(9, 2 ** 31 - 3, np.int32),
                            np.full(9, -(2 ** 31) + 4, np.int32))
    assert c2["moe.dropped_rows"] == 7


def test_a_training_step_moves_the_selection_bias_against_the_load():
    """``bias_update_rate``: after each training step every expert's bias
    has moved by the rate, down where it drew more rows than the mean and
    up where fewer, by the counts of the step that read it; a forward pass
    outside training moves nothing; the plain reference's ``balance_step``
    says the same."""
    inputs, bias = skewed_expert_inputs(8)
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.RoutedExperts(num_experts=8, num_held=3, first_held=1,
                            top_k=2, scale=1.0, num_hidden=10,
                            bias_update_rate=0.25, name="x", **v)
    shapes = {k: a.shape for k, a in inputs.items()}
    ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in inputs.items()},
                  aux_states=aux_states(net, shapes, {"select_bias": bias}))
    ex.forward(is_train=False)
    assert (ex.aux_arrays[1].asnumpy() == bias).all()
    want = jnp.asarray(bias)
    for _ in range(3):
        rows_before = ex.aux_arrays[0].asnumpy()
        ex.forward(is_train=True)
        ex.backward([mx.nd.ones((64, 12))])
        load = (ex.aux_arrays[0].asnumpy() - rows_before)[:8]
        assert load.sum() == 64 * 2
        want = ref.balance_step(want, jnp.asarray(load, jnp.float32), 0.25)
        np.testing.assert_allclose(ex.aux_arrays[1].asnumpy(), want)
        moved = ex.aux_arrays[1].asnumpy() - bias
    # the hot expert drew every row at first: its bias has only fallen
    assert moved[2] == pytest.approx(-0.75)
    assert ex.aux_arrays[1].dtype == np.float32


def test_balanced_start_evens_a_skewed_router():
    """``reference.balanced_bias``: the family's rule run on one batch's
    scores until it settles loads every expert within a few rows of the
    mean, from a router that sent most rows to a few."""
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((512, 16)) + 2.0 * rng.standard_normal(16)
    scores = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
    rates = jnp.asarray(np.geomspace(0.1, 0.001, 200), jnp.float32)
    zero = jnp.zeros(16, jnp.float32)
    before = ref.loads(jax.lax.top_k(scores, 3)[1], 16)
    bias = ref.balanced_bias(scores, zero, 3, rates)
    after = ref.loads(jax.lax.top_k(scores + bias, 3)[1], 16)
    mean = 512 * 3 / 16
    assert float(before.max()) > 2.5 * mean
    assert float(after.sum()) == 512 * 3
    assert float(jnp.abs(after - mean).max()) <= 0.1 * mean


def test_expert_shares_add_up_to_the_uncut_layer():
    """Over all 16 shares of an expert layer, the routed parts plus the
    shared expert counted once equal the uncut reference layer."""
    args = dict(TOY, pattern="E", experts_held=16, first_expert=0)
    shapes = ref.param_shapes(args)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        (48, TOY["hidden"])).astype(np.float32))
    c = ref.config(args)
    whole, _, _ = ref._experts(params, "layer0_", x, c, *ref._ROUND[None],
                               None)
    inputs = {k[len("layer0_experts_"):]: np.asarray(params[k])
              for k in shapes if "experts_" in k and not k.endswith(ref.STATE)}
    total = np.asarray(ref.shared_part(params, "layer0_", x,
                                       *ref._ROUND[None]))
    v = {k: sym.Variable(k) for k in ["data"] + list(inputs)}
    for share in range(16):
        net = sym.RoutedExperts(num_experts=16, num_held=1, first_held=share,
                                top_k=3, scale=2.5, num_hidden=16, **v)
        mine = dict(inputs, data=np.asarray(x))
        mine["up_weight"] = inputs["up_weight"][share:share + 1]
        mine["down_weight"] = inputs["down_weight"][share:share + 1]
        ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in mine.items()},
                      aux_states=aux_states(
                          net, {k: a.shape for k, a in mine.items()}))
        total = total + ex.forward(is_train=False)[0].asnumpy()
    close(total, np.asarray(whole), 5e-5)


# ---------------------------------------------------------------------------
# token ids under mixed precision
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("id_dtype", [np.int32, np.float32])
def test_token_ids_reach_embedding_intact_under_bfloat16(id_dtype):
    vocab, dim = 16384, 4
    net = sym.Embedding(data=sym.Variable("data"), input_dim=vocab,
                        output_dim=dim, name="embed")
    table = np.arange(vocab, dtype=np.float32)[:, None] \
        * np.ones((1, dim), np.float32)
    ids = np.array([[300, 16383, 255, 257]], id_dtype)
    ex = net.bind(mx.cpu(), {"data": mx.nd.array(ids, dtype=id_dtype),
                             "embed_weight": mx.nd.array(table)},
                  compute_dtype="bfloat16")
    out = ex.forward(is_train=False)[0].asnumpy()
    # the table's rows are rounded to bfloat16, the ids are not: 300 and
    # 16383 pick their own rows (as bfloat16 they would read 300 -> 300,
    # 16383 -> 16384: out of range)
    want = np.asarray(jnp.asarray(table[[300, 16383, 255, 257]],
                                  jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(out[0], want)


# ---------------------------------------------------------------------------
# the model through Module.fit against the benchmark's reference
# ---------------------------------------------------------------------------
class Ring:
    """The DataIter protocol ``fit`` uses, over fixed int32 batches."""

    def __init__(self, batches):
        self.batches = batches
        self.batch_size = batches[0][0].shape[0]
        self.provide_data = [mx.io.DataDesc("data", batches[0][0].shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label",
                                             batches[0][1].shape)]
        self.k = 0

    def reset(self):
        self.k = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        if self.k >= len(self.batches):
            raise StopIteration
        ids, lab = self.batches[self.k]
        self.k += 1
        return mx.io.DataBatch([mx.nd.array(ids, dtype=np.int32)],
                               [mx.nd.array(lab, dtype=np.int32)], pad=0)


def toy_batches(n, batch=2, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, TOY["vocab"], (batch, TOY["seq_len"] + 1))
        out.append((ids[:, :-1].astype(np.int32),
                    ids[:, 1:].astype(np.int32)))
    return out


RECIPE = {"learning_rate": 0.01, "wd": 0.01, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


def fit_toy(monkeypatch, batches, compute_dtype=None, mirror=True,
            aux_given=True, toy=TOY):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        toy, jax.random.PRNGKey(5)).items()}
    mod = mx.mod.Module(get_nemotron_h(**toy), context=mx.cpu(0))
    # the selection biases are states: the reference's one dictionary of
    # everything from the seed is handed over in two
    states = {k: mx.nd.array(v) for k, v in params0.items()
              if k.endswith(ref.STATE)}
    states.update({n: mx.nd.zeros((toy["experts_total"] + 1,),
                                  dtype=np.int32)
                   for n in mod.symbol.list_auxiliary_states()
                   if n.endswith("expert_rows")})
    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()
                            if not k.endswith(ref.STATE)},
                aux_params=states if aux_given else None,
                num_epoch=1)
        counters = {k: telemetry.peek(k) for k in (
            "step.dispatches", "step.fused_steps", "step.fused_fallback",
            "moe.rows_here", "moe.rows_total", "moe.dropped_rows",
            "lower.scan_kernel.xla_chunked",
            "lower.attention_kernel.xla_blockwise",
            "lower.experts_kernel.xla_loop",
            "lower.experts_kernel.pallas_grouped")}
        counters["load"] = telemetry.peek("moe.expert_load_max_over_mean",
                                          "gauge")
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters


@pytest.mark.parametrize("bias_update_rate", [0.0, 0.05])
def test_model_fits_on_the_fused_step_like_the_reference(monkeypatch,
                                                         bias_update_rate):
    """Three Adam steps through ``Module.fit`` against the benchmark's
    reference, the selection biases left alone and moved against the loads
    after every step (then step 2 routes by what step 1 counted)."""
    batches = toy_batches(3)
    toy = dict(TOY, bias_update_rate=bias_update_rate)
    mod, params0, counters = fit_toy(monkeypatch, batches, toy=toy)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    tokens = 2 * TOY["seq_len"]
    assert counters["moe.rows_total"] == 3 * 4 * tokens * TOY["top_k"]
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["load"] >= 1.0
    assert counters["lower.scan_kernel.xla_chunked"] >= 4
    # toy widths are no whole tiles: the loop of XLA products, once a layer
    assert counters["lower.experts_kernel.xla_loop"] == 4
    assert not counters["lower.experts_kernel.pallas_grouped"]
    want = ref.follow(toy, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))
    got = {k: v.asnumpy() for part in mod.get_params()
           for k, v in part.items()}
    delta = ref.leaf_norms({k: jnp.asarray(got[k] - params0[k])
                            for k in params0})
    for k, bias in want["states"].items():
        # float32 on both sides: the same rows counted, the same biases
        np.testing.assert_allclose(got[k], bias, atol=1e-7)
        assert (np.abs(bias).max() > 0) == bool(bias_update_rate)
    worst = max(abs(float(delta[k]) - want["delta_norms"][k])
                / max(want["delta_norms"][k], 1e-3) for k in delta)
    assert worst < 2e-3, worst
    # every held expert's slice trained
    for k, n in want["delta_norms"].items():
        if "[" in k:
            assert float(delta[k]) > 0.5 * n > 0


WHOLE_TILES = dict(TOY, pattern="MEE", hidden=128, expert_hidden=136,
                   seq_len=64)


def test_whole_tile_experts_take_the_grouped_kernel_and_match_the_loop(
        monkeypatch):
    """A row of whole lanes, an inner width of whole sublanes, blocks of
    16 rows: each expert layer takes the Pallas kernels (interpreted here),
    counted once a layer, and two Adam steps read the loop's losses, first
    moments and parameters, the same rows counted."""
    from mxnet_tpu.ops import moe

    ids = np.random.default_rng(13).integers(0, TOY["vocab"], (2, 2, 65))
    batches = [(i[:, :-1].astype(np.int32), i[:, 1:].astype(np.int32))
               for i in ids]
    runs = {}
    for body in ("pallas_grouped", "xla_loop"):
        if body == "xla_loop":
            monkeypatch.setattr(moe, "grouped_experts_applicable",
                                lambda *a: False)
        mod, _, counters = fit_toy(monkeypatch, batches, toy=WHOLE_TILES)
        other = "xla_loop" if body == "pallas_grouped" else "pallas_grouped"
        assert counters["lower.experts_kernel." + body] == 2
        assert not counters["lower.experts_kernel." + other]
        assert counters["moe.dropped_rows"] == 0
        assert counters["step.dispatches"] == 2
        assert not counters["step.fused_fallback"]
        params = {k: v.asnumpy() for part in mod.get_params()
                  for k, v in part.items()}
        moments = {name: mod._updater.states[i][0].asnumpy()
                   for i, name in enumerate(mod._param_names)}
        runs[body] = (counters["moe.rows_here"], params, moments)
    (rows_k, params_k, moments_k), (rows_l, params_l, moments_l) = \
        runs["pallas_grouped"], runs["xla_loop"]
    assert rows_k == rows_l > 0
    # Adam's step divides by the gradient's size: a gradient near zero
    # turns a rounding into a step, so the parameters agree less closely
    for k in params_l:
        close(params_k[k], params_l[k], 2e-4)
    for k in moments_l:
        close(moments_k[k], moments_l[k], 2e-5)
    assert any("experts_up_weight" in k and np.abs(v).max() > 0
               for k, v in moments_k.items())


@pytest.mark.parametrize("aux_given", [True, False])
def test_one_program_serves_every_step(monkeypatch, aux_given):
    """The experts' row counts stay int32 from bind on, whether ``fit``
    is handed auxiliary states or makes them: a float32 copy set over
    them made step 2 another program than step 1."""
    mod, _, counters = fit_toy(monkeypatch, toy_batches(3),
                               aux_given=aux_given)
    assert counters["jit_entries"] == 1
    assert counters["moe.dropped_rows"] == 0 and counters["moe.rows_here"] > 0
    for name, a in mod._exec_group.executor.aux_dict.items():
        assert a.dtype == (np.int32 if name.endswith("expert_rows")
                           else np.float32)


def test_model_loss_follows_the_reference(monkeypatch):
    """Step by step: the metric's mean cross-entropy after each step."""
    batches = toy_batches(3, seed=12)
    losses = []

    class Watch(Ring):
        def next(self):
            if 0 < self.k < len(self.batches):
                losses.append(self.metric.get()[1] * self.k)
            return super().next()

    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        TOY, jax.random.PRNGKey(6)).items()}
    mod = mx.mod.Module(get_nemotron_h(**TOY), context=mx.cpu(0))
    it = Watch(batches)
    it.metric = mx.metric.create("ce")
    mod.fit(it, eval_metric=it.metric, optimizer="adam",
            optimizer_params=dict(RECIPE), initializer=None,
            arg_params={k: mx.nd.array(v) for k, v in params0.items()
                        if not k.endswith(ref.STATE)},
            num_epoch=1, allow_missing=True)
    sums = losses + [it.metric.get()[1] * 3]
    per_step = np.diff([0.0] + sums)
    want = ref.follow(TOY, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(8).reshape(2, 4))
    np.testing.assert_allclose(per_step, want["losses"], rtol=2e-4)


def test_model_trains_in_bfloat16_with_int32_ids(monkeypatch):
    batches = toy_batches(6, seed=13)
    mod, params0, counters = fit_toy(monkeypatch, batches,
                                     compute_dtype="bfloat16")
    assert counters["step.dispatches"] == 6
    assert not counters["step.fused_fallback"]
    assert counters["moe.dropped_rows"] == 0
    ex = mod._exec_group.executor
    assert ex.arg_dict["data"].dtype == np.int32
    got, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in got.values())
    # the router and the scan's parameters stayed float32 inside the step
    assert got["layer1_experts_router_weight"].dtype == np.float32


def test_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src.replace(
        "mxnet_tpu/optimizer.py", "").replace("``mxnet_tpu``", "") \
        or "import mxnet_tpu" not in src
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src


def test_attention_takes_the_splash_kernel_at_whole_lane_heads():
    """A head of 128 and whole blocks: JAX's multi-query Pallas kernel,
    one call a key/value head (the interpreter here), forward and
    gradients against the masked softmax."""
    t, hq, hkv, d = 256, 4, 2, 128
    inputs = rng_inputs(14, query=(t, hq * d), key=(t, hkv * d),
                        value=(t, hkv * d))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.CausalAttention(num_heads=hq, num_kv_heads=hkv, head_dim=d,
                              seq_len=t, **v)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_attention(t=t, hq=hq, hkv=hkv, d=d,
                                             rotary=True, **kw), net, inputs,
                tol=2e-4)
        assert telemetry.peek("lower.attention_kernel.pallas_splash") >= 1
        assert not telemetry.peek("lower.attention_kernel.xla_blockwise")
    finally:
        telemetry.disable()


def test_model_with_whole_lane_heads_fits_under_recomputation(monkeypatch):
    """A head of 128: the splash kernel inside the fused step, traced twice
    (forward and the recomputed segment) from one cached kernel object."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    net = get_nemotron_h(pattern="M*E", hidden=64, vocab=256,
                         experts_total=8, experts_held=4, seq_len=128,
                         mamba_heads=4, mamba_head_dim=8, ssm_groups=2,
                         ssm_state=8, chunk=32, attn_heads=2, kv_heads=1,
                         head_dim=128, top_k=2, expert_hidden=16,
                         shared_hidden=32)
    ids = np.random.default_rng(3).integers(0, 256, (4, 128)).astype(np.int32)
    batches = [(ids[i:i + 1], np.roll(ids[i:i + 1], -1, 1)) for i in range(4)]
    telemetry.reset()
    telemetry.enable()
    try:
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params={"learning_rate": 1e-3, "rescale_grad": 1.0},
                initializer=mx.init.Xavier(), num_epoch=1)
        assert telemetry.peek("lower.attention_kernel.pallas_splash") >= 1
        assert telemetry.peek("step.dispatches") == 4
        assert not telemetry.peek("step.fused_fallback")
    finally:
        telemetry.disable()
    got, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in got.values())


# ---------------------------------------------------------------------------
# how often the scan runs in a training step
# ---------------------------------------------------------------------------
KERNEL_TOY = dict(pattern="MEM", hidden=64, vocab=256, experts_total=8,
                  experts_held=4, seq_len=256, mamba_heads=4,
                  mamba_head_dim=64, ssm_groups=2, ssm_state=128, chunk=128,
                  attn_heads=2, kv_heads=1, head_dim=8, top_k=2,
                  expert_hidden=16, shared_hidden=32)


def traced_fused_step(monkeypatch, toy):
    """The fused train step of ``toy`` as ``fit`` builds it under
    ``MXNET_BACKWARD_DO_MIRROR``, traced (``jax.stages.Traced``)."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    traced = []
    real_jit = jax.jit

    def spy(fn, **kw):
        jfn = real_jit(fn, **kw)
        if getattr(fn, "__name__", "") != "step":
            return jfn

        class Spy:
            def __call__(self, *args):
                traced.append(jfn.trace(*args))
                return jfn(*args)

            def _cache_size(self):
                return jfn._cache_size()
        return Spy()

    monkeypatch.setattr(jax, "jit", spy)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, toy["vocab"], (1, toy["seq_len"] + 1))
    mod = mx.mod.Module(get_nemotron_h(**toy), context=mx.cpu(0))
    mod.fit(Ring([(ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32))]),
            eval_metric="ce", optimizer="adam",
            optimizer_params={"learning_rate": 1e-3, "rescale_grad": 1.0},
            initializer=mx.init.Xavier(), num_epoch=1)
    monkeypatch.undo()
    assert len(traced) == 1
    return traced[0]


@pytest.mark.parametrize("body", ["pallas", "xla"])
def test_the_scan_runs_forward_twice_and_backward_once_a_step(monkeypatch,
                                                              body):
    """Under segment recomputation each scan node runs its forward body
    twice in the step (the forward pass, and the recomputed one that keeps
    the chunk-start states) and its written backward once: no third
    forward, as autodiff under an inner ``jax.checkpoint`` ran. Counted
    as calls of the kernels' (jitted, so shared by the layers) callers in
    the step lowered for the TPU, and for the XLA body
    as the ``lax.scan``s that carry one float32 state ``[G, H/G, P, N]``
    across the chunks (forward) or its gradient (reversed)."""
    import re

    toy = KERNEL_TOY if body == "pallas" else dict(KERNEL_TOY, ssm_state=16)
    nodes = toy["pattern"].count("M")
    traced = traced_fused_step(monkeypatch, toy)
    if body == "pallas":
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert 'kernel_name = "ssd_chunk_forward"' in text
        ran = [len(re.findall(r"call @_ssd_chunk_%s(_\d+)?\(" % k, text))
               for k in ("forward", "backward")]
    else:
        state = (toy["ssm_groups"], toy["mamba_heads"] // toy["ssm_groups"],
                 toy["mamba_head_dim"], toy["ssm_state"])
        ran = [0, 0]
        for sub in _sub_jaxprs(traced.jaxpr.jaxpr):
            for eqn in sub.eqns:
                if eqn.primitive.name == "scan" and \
                        eqn.params["num_carry"] == 1:
                    carry = eqn.invars[eqn.params["num_consts"]].aval
                    if carry.shape == state and carry.dtype == jnp.float32:
                        ran[bool(eqn.params["reverse"])] += 1
    assert ran == [2 * nodes, nodes]


# ---------------------------------------------------------------------------
# what the recomputation keeps
# ---------------------------------------------------------------------------
def _count(jaxpr, pick):
    return sum(bool(pick(eqn)) for sub in _sub_jaxprs(jaxpr)
               for eqn in sub.eqns)


def test_attention_kernel_and_routing_run_once_a_step(monkeypatch):
    """Under segment recomputation the attention kernel's output and
    log-sum-exp and the experts' routing are kept: the forward kernel is
    called as often as the one backward kernel (once a step:
    ``attend_splash`` names both in its own forward rule, where the plan
    sees them), none of JAX's three splash kernels is called, and
    ``top_k`` and the layout's ``sort`` appear once a ``RoutedExperts``
    node (a second ``sort`` is the backward
    pass's: it brings the combine weights' gradient from the slots back to
    the rows, ``moe.pairs_from_slots``). Counted in the traced step, and as
    calls of the kernel's jitted caller (forward, backward) in the step
    lowered for the TPU."""
    import re

    toy = dict(KERNEL_TOY, pattern="M*E", seq_len=128, mamba_head_dim=8,
               ssm_state=8, chunk=32, head_dim=128)
    traced = traced_fused_step(monkeypatch, toy)
    jaxpr = traced.jaxpr.jaxpr

    def kernels(name):
        return _count(jaxpr, lambda e: e.primitive.name == "pallas_call"
                      and name in str(e.params.get("name")))

    # one call a branch of ``platform_dependent`` (interpreter, Mosaic)
    assert kernels("causal_attention_forward") \
        == kernels("causal_attention_backward") == 2
    assert kernels("splash_mqa") == 0
    assert _count(jaxpr, lambda e: e.primitive.name == "top_k") == 1
    assert _count(jaxpr, lambda e: e.primitive.name == "sort") == 2
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "splash" not in text
    assert len(re.findall(r"call @_attention_forward(_\d+)?\(", text)) == 1
    for which in ("forward", "backward"):
        assert text.count(
            'kernel_name = "causal_attention_%s"' % which) == 1


@functools.lru_cache(None)
def _model_pass(budget, seed=7):
    """Outputs, auxiliary states and the float arguments' gradients of one
    training pass of the toy model under ``make_graph_eval``: plain
    (``budget`` False) or recomputed with ``remat_budget=budget``; and the
    ``remat.*`` telemetry of that trace."""
    from mxnet_tpu.executor import make_graph_eval, zero_cotangent

    net = get_nemotron_h(**TOY)
    shape = (2, TOY["seq_len"])
    fn, _ = make_graph_eval(net) if budget is False else \
        make_graph_eval(net, remat=True, remat_budget=budget)
    arg_shapes, _, aux_shapes = net.infer_shape(data=shape,
                                                softmax_label=shape)
    rng = np.random.default_rng(seed)
    args = []
    for name, s in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            args.append(rng.integers(0, TOY["vocab"], s).astype(np.int32))
        elif name.endswith(("_A_log", "_dt_bias", "_D")):
            args.append(rng.uniform(0.1, 0.5, s).astype(np.float32))
        else:
            args.append((rng.standard_normal(s) / np.sqrt(s[-1])).astype(
                np.float32))
    args = [jnp.asarray(a) for a in args]
    aux = [jnp.zeros(s, t) for s, t in zip(aux_shapes, net.infer_type()[2])]
    floats = [i for i, a in enumerate(args) if a.dtype == np.float32]
    key = jax.random.PRNGKey(0)

    def f(fl):
        full = list(args)
        for i, v in zip(floats, fl):
            full[i] = v
        return fn(full, aux, key, True)

    telemetry.reset()
    telemetry.enable()
    try:
        (outs, aux_out), vjp = jax.vjp(f, [args[i] for i in floats])
        grads, = vjp(([jnp.ones_like(o) for o in outs],
                      [zero_cotangent(a) for a in aux_out]))
        seen = {k: telemetry.peek(k) for k in (
            "remat.segments", "remat.segments_recomputed",
            "remat.kept_results")}
        seen.update({k: telemetry.peek(k, "gauge") for k in (
            "remat.kept_bytes", "remat.budget_bytes")})
    finally:
        telemetry.disable()
    return outs + aux_out + grads, seen


@pytest.mark.parametrize("budget", [0, 40000, 1 << 40])
def test_recomputation_plan_keeps_by_budget_and_matches_plain(budget):
    """The toy model's 85 nodes make nine segments, eight recomputed.
    With a stated budget of 0 only what the ops keep always engages (the
    result and the routing of each of four expert layers, the attention
    kernel's result: 9); with a large one every product outside the last
    segment as well (20); in between as many as fit, and the bytes kept
    never pass the budget plus the always-kept. Whatever is kept, the
    values are the plain path's."""
    plain, _ = _model_pass(False)
    floor = _model_pass(0)[1]
    got, seen = _model_pass(budget)
    for x, y in zip(plain, got):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)
    assert seen["remat.segments"] == 9
    assert seen["remat.segments_recomputed"] == 8
    assert floor["remat.kept_results"] == 9
    assert seen["remat.budget_bytes"] == budget
    assert seen["remat.kept_bytes"] <= budget + floor["remat.kept_bytes"]
    assert seen["remat.kept_results"] == {0: 9, 1 << 40: 29}.get(
        budget, seen["remat.kept_results"])
    assert 9 <= seen["remat.kept_results"] <= 29
    if budget == 40000:
        assert 9 < seen["remat.kept_results"] < 29


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy("nemotron3_nano_l9_e8of128_bf16.json",
                              get_nemotron_h(**TOY), *toy_batches(1)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 16,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 4})
