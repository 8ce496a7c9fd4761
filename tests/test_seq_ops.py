"""Sequence op + fused RNN tests (reference test_operator.py sequence
tests; RNN validated against a manual numpy recurrence the way the
reference validated cuDNN RNN against CPU)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.ops.seq import rnn_param_size


def _bind_forward(s, args_np, is_train=False):
    args = {k: mx.nd.array(v) for k, v in args_np.items()}
    ex = s.bind(mx.cpu(), args, grad_req="null")
    return ex, ex.forward(is_train=is_train)


def test_sequence_last():
    data = sym.Variable("data")
    s = sym.SequenceLast(data=data, use_sequence_length=True,
                         name="seqlast")
    x = np.arange(24).reshape(4, 3, 2).astype(np.float32)
    lengths = np.array([2, 4, 1], dtype=np.float32)
    _, outs = _bind_forward(s, {"data": x, "seqlast_sequence_length": lengths})
    expected = np.stack([x[1, 0], x[3, 1], x[0, 2]])
    np.testing.assert_allclose(outs[0].asnumpy(), expected)


def test_sequence_mask():
    data = sym.Variable("data")
    s = sym.SequenceMask(data=data, use_sequence_length=True, value=-1.0,
                         name="seqmask")
    x = np.ones((3, 2, 2), dtype=np.float32)
    lengths = np.array([1, 3], dtype=np.float32)
    _, outs = _bind_forward(s, {"data": x, "seqmask_sequence_length": lengths})
    out = outs[0].asnumpy()
    np.testing.assert_allclose(out[0, 0], 1)
    np.testing.assert_allclose(out[1, 0], -1)
    np.testing.assert_allclose(out[2, 1], 1)


def test_sequence_reverse():
    data = sym.Variable("data")
    s = sym.SequenceReverse(data=data, use_sequence_length=True,
                            name="seqrev")
    x = np.arange(12).reshape(3, 2, 2).astype(np.float32)
    lengths = np.array([2, 3], dtype=np.float32)
    _, outs = _bind_forward(s, {"data": x, "seqrev_sequence_length": lengths})
    out = outs[0].asnumpy()
    np.testing.assert_allclose(out[0, 0], x[1, 0])
    np.testing.assert_allclose(out[1, 0], x[0, 0])
    np.testing.assert_allclose(out[2, 0], x[2, 0])
    np.testing.assert_allclose(out[0, 1], x[2, 1])


def _np_lstm(x, params, h0, c0, hidden):
    """Manual LSTM recurrence matching the documented flat layout."""
    t_len, n, input_size = x.shape
    off = 0

    def take(shape):
        nonlocal off
        size = int(np.prod(shape))
        out = params[off:off + size].reshape(shape)
        off += size
        return out

    wx = take((4 * hidden, input_size))
    wh = take((4 * hidden, hidden))
    bx = take((4 * hidden,))
    bh = take((4 * hidden,))
    h, c = h0.copy(), c0.copy()
    outs = []

    def sigmoid(v):
        return 1 / (1 + np.exp(-v))

    for t in range(t_len):
        gates = x[t].dot(wx.T) + bx + h.dot(wh.T) + bh
        i, f, g, o = np.split(gates, 4, axis=-1)
        i, f, o = sigmoid(i), sigmoid(f), sigmoid(o)
        g = np.tanh(g)
        c = f * c + i * g
        h = o * np.tanh(c)
        outs.append(h)
    return np.stack(outs), h, c


def test_rnn_lstm_matches_manual():
    t_len, n, input_size, hidden = 5, 2, 3, 4
    psize = rnn_param_size(1, input_size, hidden, False, "lstm")
    rng = np.random.RandomState(0)
    x = rng.randn(t_len, n, input_size).astype(np.float32)
    params = (rng.randn(psize) * 0.1).astype(np.float32)
    h0 = np.zeros((1, n, hidden), dtype=np.float32)
    c0 = np.zeros((1, n, hidden), dtype=np.float32)

    data = sym.Variable("data")
    rnn = sym.RNN(data=data, state_size=hidden, num_layers=1, mode="lstm",
                  state_outputs=True, name="rnn")
    _, outs = _bind_forward(rnn, {
        "data": x, "rnn_parameters": params, "rnn_state": h0,
        "rnn_state_cell": c0})
    expected_out, expected_h, expected_c = _np_lstm(x, params, h0[0], c0[0],
                                                    hidden)
    np.testing.assert_allclose(outs[0].asnumpy(), expected_out, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(outs[1].asnumpy()[0], expected_h, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(outs[2].asnumpy()[0], expected_c, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("mode", ["rnn_relu", "rnn_tanh", "gru", "lstm"])
@pytest.mark.parametrize("bidirectional", [False, True])
def test_rnn_modes_shapes(mode, bidirectional):
    t_len, n, input_size, hidden, layers = 4, 3, 5, 6, 2
    dirs = 2 if bidirectional else 1
    psize = rnn_param_size(layers, input_size, hidden, bidirectional, mode)
    rng = np.random.RandomState(1)
    args = {
        "data": rng.randn(t_len, n, input_size).astype(np.float32),
        "r_parameters": (rng.randn(psize) * 0.1).astype(np.float32),
        "r_state": np.zeros((layers * dirs, n, hidden), dtype=np.float32),
    }
    if mode == "lstm":
        args["r_state_cell"] = np.zeros((layers * dirs, n, hidden),
                                        dtype=np.float32)
    data = sym.Variable("data")
    rnn = sym.RNN(data=data, state_size=hidden, num_layers=layers, mode=mode,
                  bidirectional=bidirectional, name="r")
    s_args, s_outs, _ = rnn.infer_shape(data=(t_len, n, input_size))
    assert s_outs[0] == (t_len, n, hidden * dirs)
    _, outs = _bind_forward(rnn, args)
    assert outs[0].shape == (t_len, n, hidden * dirs)


def test_rnn_gradient():
    from mxnet_tpu.test_utils import check_numeric_gradient

    t_len, n, input_size, hidden = 3, 2, 2, 3
    psize = rnn_param_size(1, input_size, hidden, False, "lstm")
    rng = np.random.RandomState(0)
    data = sym.Variable("data")
    rnn = sym.RNN(data=data, state_size=hidden, num_layers=1, mode="lstm",
                  name="r")
    check_numeric_gradient(rnn, {
        "data": rng.randn(t_len, n, input_size).astype(np.float32),
        "r_parameters": (rng.randn(psize) * 0.2).astype(np.float32),
        "r_state": np.zeros((1, n, hidden), dtype=np.float32),
        "r_state_cell": np.zeros((1, n, hidden), dtype=np.float32)},
        check_eps=0.08, numeric_eps=1e-2)


# ---------------------------------------------------------------------------
# GatedDeltaRule: the chunked WY form against the position-by-position
# recurrence of the benchmark's reference
# ---------------------------------------------------------------------------
import os  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import olmo_hybrid as olmo_ref  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from test_nemotron_h import against, close  # noqa: E402


def delta_net(t, h, dk, dv, chunk, neg_eigval):
    v = {n: sym.Variable(n) for n in ("query", "key", "value", "a", "b",
                                      "A_log", "dt_bias")}
    return sym.GatedDeltaRule(num_heads=h, key_dim=dk, value_dim=dv,
                              chunk=chunk, seq_len=t, neg_eigval=neg_eigval,
                              name="delta", **v)


def delta_inputs(seed, rows, h, dk, dv, a_shift=0.0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {"query": normal(rows, h * dk), "key": normal(rows, h * dk),
            "value": normal(rows, h * dv), "a": normal(rows, h) + a_shift,
            "b": normal(rows, h),
            "A_log": np.log(rng.uniform(1.0, 16.0, h)).astype(np.float32),
            "dt_bias": (normal(h) - 3.0)}


def plain_delta(query, key, value, a, b, A_log, dt_bias, t, h, neg_eigval):
    """The operator's definition: normalise, gate, then the reference's
    recurrence one position at a time."""
    n = query.shape[0] // t

    def unit(x):
        x = x.reshape(n, t, h, -1)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k = unit(query), unit(key)
    beta = jax.nn.sigmoid(b).reshape(n, t, h) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(A_log) * jax.nn.softplus(a + dt_bias)).reshape(
        n, t, h)
    o = olmo_ref.delta_rule(q * q.shape[-1] ** -0.5, k,
                            value.reshape(n, t, h, -1), alpha, beta, 16)
    return o.reshape(n * t, -1)


def delta_counters():
    return tuple(telemetry.peek("lower.delta_rule_kernel." + k) or 0
                 for k in ("pallas_chunked", "xla_chunked"))


# widths of whole sublanes: the Pallas chunk kernels (the interpreter
# here); anything else: the XLA body under autodiff
TOY_DELTA = dict(h=3, dk=6, dv=10)
SUBLANE_DELTA = dict(h=3, dk=8, dv=16)


@pytest.mark.parametrize("body", ["xla", "pallas"])
@pytest.mark.parametrize("t,chunk,neg_eigval", [
    (16, 16, True), (40, 16, True), (40, 16, False), (96, 32, True)],
    ids=["1chunk", "2.5chunks", "2.5chunks-beta-under-1", "3chunks"])
def test_gated_delta_rule_against_recurrence(t, chunk, neg_eigval, body):
    """Two sequences, one of them not whole chunks: the output and the
    gradients of all seven arguments against ``jax.grad`` of the
    recurrence, with ``b`` doubled and not, for both bodies; the traced
    node counts the lowering its shapes chose, once."""
    shape = SUBLANE_DELTA if body == "pallas" else TOY_DELTA
    h, dk, dv = shape["h"], shape["dk"], shape["dv"]
    inputs = delta_inputs(1, 2 * t, h, dk, dv)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_delta(t=t, h=h, neg_eigval=neg_eigval,
                                         **kw),
                delta_net(t, h, dk, dv, chunk, neg_eigval), inputs, seed=3,
                tol=5e-5)
        assert delta_counters() == ((1, 0) if body == "pallas" else (0, 1))
    finally:
        telemetry.disable()
    # the doubling is inside the operator: the two forms differ
    as_jnp = {k: jnp.asarray(v) for k, v in inputs.items()}
    doubled, plain = (plain_delta(t=t, h=h, neg_eigval=flag, **as_jnp)
                      for flag in (True, False))
    assert float(jnp.abs(doubled - plain).max()) > 1e-2


@pytest.mark.parametrize("dk,dv,body", [(6, 10, "xla"), (8, 16, "pallas")])
def test_gated_delta_rule_gradients_hold_along_a_long_sequence(dk, dv, body):
    """2,048 positions of bfloat16 inputs at the chunk the model uses,
    decays near 1 so that the state lives through the whole sequence: the
    decay's parameters sum their gradient over every position (the
    gradient PR 27 found 82% off on the chip when it was formed as a
    difference of two bfloat16 products), and every argument's gradient
    stays with float32 autodiff of the recurrence. Both bodies, the
    kernels' at the smallest widths their rule admits."""
    from mxnet_tpu.executor import make_graph_eval

    t, h, chunk = 2048, 2, 64
    f32 = delta_inputs(5, t, h, dk, dv, a_shift=-2.0)
    inputs = {k: jnp.asarray(v, jnp.float32 if k in ("A_log", "dt_bias")
                             else jnp.bfloat16) for k, v in f32.items()}
    head = jnp.asarray(np.random.default_rng(2).standard_normal(
        (t, h * dv)), jnp.bfloat16).astype(jnp.float32)
    net = delta_net(t, h, dk, dv, chunk, True)
    names = net.list_arguments()
    eval_graph, _ = make_graph_eval(net)

    def run(fl):
        return eval_graph([fl[k] for k in names], [], None, True)[0][0]

    telemetry.reset()
    telemetry.enable()
    try:
        got_o = run(inputs)
        assert delta_counters() == ((1, 0) if body == "pallas" else (0, 1))
    finally:
        telemetry.disable()
    assert got_o.dtype == jnp.bfloat16
    got = jax.jit(jax.grad(lambda fl: jnp.sum(
        run(fl).astype(jnp.float32) * head)))(inputs)
    as_f32 = {k: v.astype(jnp.float32) for k, v in inputs.items()}

    def plain(fl):
        return plain_delta(t=t, h=h, neg_eigval=True, **fl)

    want = jax.jit(jax.grad(lambda fl: jnp.sum(plain(fl) * head)))(as_f32)
    # the output is rounded to bfloat16 once; gradients with respect to
    # bfloat16 inputs are rounded to them
    close(got_o.astype(jnp.float32), plain(as_f32), 1e-2)
    for k in ("A_log", "dt_bias"):
        close(got[k], want[k], 2e-3)
    for k in ("query", "key", "value", "a", "b"):
        close(got[k].astype(jnp.float32), want[k], 2e-2)


@pytest.mark.parametrize("dims,chunk,dtype,takes", [
    ((15, 96, 192), 64, "float32", True),       # the cell's own shapes
    ((2, 8, 16), 64, "float32", True),
    ((15, 96, 192), 64, "bfloat16", False),     # another result
    ((15, 96, 192), 64, "float16", False),
    ((15, 96, 192), 60, "float32", False),      # not whole sublane tiles
    ((15, 96, 192), 256, "float32", False),     # wider than the lanes
    ((4, 128, 256), 128, "float32", True),      # the widest
    ((4, 136, 256), 64, "float32", False),      # keys past one lane tile
    ((4, 128, 264), 64, "float32", False),      # values past two
    ((3, 6, 10), 16, "float32", False)],        # toy widths
    ids=["cell", "smallest", "bfloat16", "float16", "chunk60", "chunk256",
         "widest", "keys136", "values264", "toy"])
def test_delta_chunk_applicable(dims, chunk, dtype, takes):
    from mxnet_tpu.ops import pallas_kernels

    assert pallas_kernels.delta_chunk_applicable(
        dims, chunk, jnp.dtype(dtype)) is takes


def delta_scan_args(seed, b, t, h, dk, dv):
    """What ``GatedDeltaRule.apply`` hands the scan: unit keys, scaled unit
    queries, log-decays <= 0, beta in (0, 2); float32."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return (unit(normal(b, t, h, dk)) * dk ** -0.5, unit(normal(b, t, h, dk)),
            normal(b, t, h, dv), -jax.nn.softplus(normal(b, t, h) - 2.0),
            2.0 * jax.nn.sigmoid(normal(b, t, h)))


@pytest.mark.parametrize("dims,chunk,dtype,takes", [
    ((32, 128, 128), 64, "float32", True),      # the cell's own shapes
    ((4, 128, 256), 32, "float32", True),
    ((2, 128, 8), 16, "float32", True),         # values as the scalar rule
    ((32, 128, 128), 64, "bfloat16", False),    # another result
    ((4, 8, 8), 32, "float32", False),          # the toys: no whole lane tile
    ((15, 96, 192), 64, "float32", False),      # the scalar kernels' cell
    ((32, 128, 128), 24, "float32", False),     # no whole sub-chunks
    ((32, 128, 128), 40, "float32", False),
    ((32, 128, 128), 128, "float32", False)],   # past four sub-chunks
    ids=["cell", "values256", "values8", "bfloat16", "keys8", "keys96",
         "chunk24", "chunk40", "chunk128"])
def test_delta_channel_applicable(dims, chunk, dtype, takes):
    """The rule beside ``delta_chunk_applicable`` for a decay a key
    channel: keys of whole lane tiles, chunks of whole 16-position
    sub-chunks; everything it refuses keeps the XLA body."""
    from mxnet_tpu.ops import pallas_kernels

    assert pallas_kernels.delta_channel_applicable(
        dims, chunk, jnp.dtype(dtype)) is takes
    if takes:
        assert pallas_kernels.delta_chunk_applicable(
            dims, chunk, jnp.dtype(dtype))


def test_delta_heads_a_step_count_the_channel_decays_tiles():
    """A head with a decay a key channel holds the gate's block and ``exp(c
    - r)`` beside what the scalar rule counts: four heads a grid step at
    the Ling cell's shape, two at values of 256; the Olmo cell's five
    stand. The sub-chunk is one number in the kernels and in the XLA
    body."""
    from mxnet_tpu.ops import pallas_kernels as pk, seq

    assert pk._delta_heads(32, 128, 128, 64) == 4
    assert pk._delta_heads(32, 128, 128, 64, channel=True) == 4
    assert pk._delta_heads(4, 128, 256, 64, channel=True) == 2
    assert pk._delta_heads(15, 96, 192, 64) == 5
    assert seq.SUB_CHUNK == pk.DELTA_SUB_CHUNK == 16


@pytest.mark.parametrize("dims,key_heads,chunk,t,channel,takes", [
    ((32, 128, 128), 32, 64, 8192, True, True),     # the Ling cell
    ((32, 128, 128), 16, 64, 8192, False, True),    # the Qwen3-Next cell
    ((15, 96, 192), 15, 64, 8192, False, False),    # the Olmo cell: part lanes
    ((4, 128, 256), 4, 32, 64, True, True),         # values of two lane tiles
    ((2, 128, 8), 2, 16, 32, True, False),          # values of part of one
    ((32, 128, 128), 32, 64, 8192 + 32, True, False),  # a part chunk
    ((32, 128, 128), 32, 24, 8184, False, False),  # bfloat16 tiles: 16 rows
    ((8, 128, 128), 1, 64, 128, False, False)],     # 4 heads a step, 8 a key
    ids=["ling", "qwen3_next", "olmo", "values256", "values8", "part_chunk",
         "chunk24", "steps_split_a_key_head"])
def test_delta_rows_applicable(dims, key_heads, chunk, t, channel, takes):
    """Where the chunk kernels read the op's rows as the projections leave
    them: heads of whole lane tiles, sequences of whole chunks of whole
    bfloat16 sublane tiles, a step's value heads covering whole key heads.
    What it refuses keeps the head-major entry."""
    from mxnet_tpu.ops import pallas_kernels as pk

    assert pk.delta_rows_applicable(dims, key_heads, chunk, t,
                                    channel) is takes


ROWS_CASES = {
    # value heads, key heads, a decay a key channel, gate_floor, neg_eigval
    "two_value_heads_a_key_head": (2, 1, False, 0.0, False),
    "channel_bounded_gate": (2, 2, True, -5.0, True),
    "channel_softplus_gate": (2, 2, True, 0.0, False),
}


def rows_case(case, dtype, dk=128, dv=128, t=32, chunk=16, n=2):
    """The op at 128 keys and values a head over two sequences of two
    chunks, its inputs in ``dtype`` (``A_log`` and ``dt_bias`` float32, as
    the executor hands them), a cotangent in ``dtype``, and the same
    function through ``gated_delta_chunked`` / ``_channel`` and autodiff."""
    from mxnet_tpu.ops import seq
    from mxnet_tpu.ops.registry import OpContext, create_operator

    h, hk, channel, floor, neg = ROWS_CASES[case]
    rng = np.random.default_rng(4)
    rows, wide = n * t, h * dk if channel else h

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    inputs = [jnp.asarray(x, dtype) for x in (
        normal(rows, hk * dk), normal(rows, hk * dk), normal(rows, h * dv),
        normal(rows, wide), normal(rows, h))] + [
        jnp.asarray(np.log(rng.uniform(1.0, 4.0, h)), jnp.float32),
        jnp.asarray(normal(wide) - 2.0)]
    head = jnp.asarray(normal(rows, h * dv), dtype).astype(jnp.float32)
    op = create_operator("GatedDeltaRule", num_heads=h, num_key_heads=hk,
                         key_dim=dk, value_dim=dv, chunk=chunk, seq_len=t,
                         gate_floor=floor, neg_eigval=neg)

    def through_op(inputs):
        o = op.apply(OpContext(True), list(inputs), [])[0][0]
        return jnp.sum(o.astype(jnp.float32) * head), o

    def through_bodies(inputs):
        q, k, v, a, b, a_log, dt_bias = (x.astype(jnp.float32)
                                         for x in inputs)

        def unit(x):
            x = x.reshape(n, t, hk, dk)
            return jnp.repeat(x * jax.lax.rsqrt(
                jnp.sum(x * x, -1, keepdims=True) + op.NORM_EPS), h // hk, 2)

        rate = jnp.repeat(jnp.exp(a_log), wide // h)
        g = floor * jax.nn.sigmoid(rate * (a + dt_bias)) if floor \
            else -rate * jax.nn.softplus(a + dt_bias)
        g = g.reshape((n, t, h, dk) if channel else (n, t, h))
        beta = jax.nn.sigmoid(b).reshape(n, t, h) * (2.0 if neg else 1.0)
        body = seq.gated_delta_chunked_channel if channel \
            else seq.gated_delta_chunked
        q, k, v = unit(q) * dk ** -0.5, unit(k), v.reshape(n, t, h, dv)
        o = jnp.stack([body(q[i], k[i], v[i], g[i], beta[i], chunk)[0]
                       for i in range(n)]).reshape(rows, h * dv)
        # the op's one rounding of the result
        o = o.astype(dtype)
        return jnp.sum(o.astype(jnp.float32) * head), o

    return inputs, through_op, through_bodies


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_row_major_entry_against_the_chunked_bodies(case, dtype):
    """The chunk kernels reading the op's rows where they lie (interpreted
    here): the casts, ``q / |q| / sqrt(K)`` and ``k / |k|`` a KEY head, a
    channel's gate in both forms and the value heads' shared key head all
    happen in VMEM. Against the same function through the XLA bodies and
    autodiff: the output and all seven gradients, from float32 inputs
    (nothing is rounded: the float32 tolerance) and from bfloat16 ones (the
    result and each wide gradient rounded once, to bfloat16, at the store;
    ``A_log``'s and ``dt_bias``'s gradients are float32 sums over every row
    and hold far inside a bfloat16 step). Counted
    ``lower.delta_rule_layout.rows``."""
    inputs, through_op, through_bodies = rows_case(case, jnp.dtype(dtype))
    telemetry.reset()
    telemetry.enable()
    try:
        (_, got_o), got = jax.value_and_grad(through_op, has_aux=True)(inputs)
        assert telemetry.peek("lower.delta_rule_layout.rows") == 1
        assert not telemetry.peek("lower.delta_rule_layout.heads")
        assert delta_counters() == (1, 0)
    finally:
        telemetry.disable()
    (_, want_o), want = jax.value_and_grad(through_bodies, has_aux=True)(
        inputs)
    assert got_o.dtype == want_o.dtype == jnp.dtype(dtype)
    rounded = dtype == "bfloat16"
    close(got_o.astype(jnp.float32), want_o.astype(jnp.float32),
          8e-3 if rounded else 1e-5)
    for name, g, w in zip(("query", "key", "value", "a", "b", "A_log",
                           "dt_bias"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        whole = name in ("A_log", "dt_bias")
        close(g.astype(jnp.float32), w.astype(jnp.float32),
              (1e-3 if whole else 8e-3) if rounded else 2e-5)


def test_heads_of_part_lane_tiles_keep_the_head_major_entry():
    """15 heads of 96 keys and 192 values (the Olmo cell's) are no whole
    lane tiles: the node counts ``lower.delta_rule_layout.heads`` and
    traces the program it did before the row-major entry came, float32
    ``[B, H, T, K]`` operands through transposes (its text is held to the
    parent's by hash in ``tests/test_qwen3_next.py``, ``olmo.delta``)."""
    from test_nemotron_h import _sub_jaxprs

    from mxnet_tpu.ops.registry import OpContext, create_operator

    t, h, dk, dv = 128, 15, 96, 192
    op = create_operator("GatedDeltaRule", num_heads=h, key_dim=dk,
                         value_dim=dv, chunk=64, seq_len=t, neg_eigval=True)
    shapes = op.infer_shape([(t, h * dk)] + [None] * 6)[0]
    args = [jax.ShapeDtypeStruct(s, jnp.float32 if i > 4 else jnp.bfloat16)
            for i, s in enumerate(shapes)]
    telemetry.reset()
    telemetry.enable()
    try:
        jaxpr = jax.make_jaxpr(
            lambda *a: op.apply(OpContext(True), list(a), [])[0][0])(*args)
        assert telemetry.peek("lower.delta_rule_layout.heads") == 1
        assert not telemetry.peek("lower.delta_rule_layout.rows")
        assert delta_counters() == (1, 0)
    finally:
        telemetry.disable()
    calls = [eqn for sub in _sub_jaxprs(jaxpr.jaxpr) for eqn in sub.eqns
             if eqn.primitive.name == "pallas_call"]
    assert calls and all(
        v.aval.dtype == jnp.float32 for eqn in calls for v in eqn.invars)
    assert {tuple(v.aval.shape) for v in calls[0].invars[:3]} == {
        (1, h, t, dk), (1, h, t, dv)}
    assert "transpose" in {eqn.primitive.name
                           for sub in _sub_jaxprs(jaxpr.jaxpr)
                           for eqn in sub.eqns}


@pytest.mark.parametrize("decay", ["head", "channel", "rows_head",
                                   "rows_channel"])
def test_gated_delta_scan_traces_two_kernels_and_no_scan(decay):
    """``jax.grad`` through the kernel body holds exactly the forward and
    the backward ``pallas_call`` (each twice: the interpreter's and
    Mosaic's branch), every VMEM scratch float32, and neither a ``scan``
    nor a ``triangular_solve``; the XLA body holds both and no kernel. With
    one decay a head and with one a key channel (``g [B, T, H, K]`` at 128
    keys a head). ``rows_*``: the same through the row-major entry
    (``seq.gated_delta_rows`` from bfloat16 rows, two value heads a key
    head; a channel's gate formed in the kernel), where besides nothing a
    row wide is left beside the kernels: no ``transpose`` of more than the
    per-head scalars, no ``broadcast_in_dim`` of a key head, no float32
    array of a position's keys or values."""
    from test_nemotron_h import _sub_jaxprs

    from mxnet_tpu.ops import pallas_kernels as pk, seq

    def traced(fn, args):
        """The value and every gradient of ``fn``: ``jax.vjp`` under a
        cotangent of the result's own type, so that no cast of the result
        stands beside the kernels."""
        def both(do, *a):
            o, back = jax.vjp(fn, *a)
            return o, back(do)

        jaxpr = jax.make_jaxpr(both)(jax.eval_shape(fn, *args), *args)
        names, scratch, prims, outside = [], [], set(), []
        for sub in _sub_jaxprs(jaxpr.jaxpr):
            for eqn in sub.eqns:
                prims.add(eqn.primitive.name)
                if eqn.primitive.name == "pallas_call":
                    names.append(eqn.params["name"])
                    scratch += [a.dtype for a in
                                eqn.params["grid_mapping"].scratch_avals]
        for eqn in jaxpr.jaxpr.eqns:
            # the kernels' callers are jitted: look inside them, not inside
            # the kernels
            inner = eqn.params["jaxpr"].jaxpr.eqns \
                if eqn.primitive.name in ("pjit", "jit") else [eqn]
            outside += [e for e in inner if e.primitive.name != "pallas_call"]
        return names, scratch, prims, outside

    def kernels_alone(names, scratch, prims):
        assert sorted(names) == ["delta_chunk_backward"] * 2 \
            + ["delta_chunk_forward"] * 2, names
        assert len(scratch) == 4 and all(
            d == jnp.float32 for d in scratch), scratch
        assert not prims & {"scan", "triangular_solve", "while",
                            "checkpoint", "remat"}, prims

    if decay.startswith("rows"):
        channel = decay == "rows_channel"
        t, h, hk, dk, dv = 128, 4, 4 if channel else 2, 128, 128
        rng = np.random.default_rng(0)

        def normal(*shape, dtype=jnp.bfloat16):
            return jnp.asarray(rng.standard_normal(shape), dtype)

        f32 = jnp.float32
        args = (normal(t, hk * dk), normal(t, hk * dk), normal(t, h * dv)) + (
            (normal(t, h * dk), jax.nn.sigmoid(normal(1, t, h, dtype=f32)),
             jnp.ones((1, h * dk), f32), -jnp.ones((1, h * dk), f32))
            if channel else
            (-jax.nn.softplus(normal(1, t, h, dtype=f32)),
             jax.nn.sigmoid(normal(1, t, h, dtype=f32)), None, None))
        spec = pk.DeltaRows(t, h, hk, dk, dv, 64, -5.0 if channel else 0.0,
                            1e-6)
        args = tuple(a for a in args if a is not None)
        names, scratch, prims, outside = traced(
            lambda *a: seq.gated_delta_rows(
                *a, *(None,) * (7 - len(a)), spec), args)
        kernels_alone(names, scratch, prims)
        for eqn in outside:
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(v.aval, "shape", ())
                wide = int(np.prod(shape)) >= t * hk * min(dk, dv)
                assert not (wide and v.aval.dtype == f32 and len(shape) != 5
                            ), eqn      # 5: the chunk-start states
                assert not (wide and eqn.primitive.name in (
                    "transpose", "broadcast_in_dim")), eqn
        return
    if decay == "head":
        args = delta_scan_args(0, 1, 128, 2, 8, 16)
    else:
        q, k, v, g, beta = delta_scan_args(0, 1, 128, 2, 128, 128)
        args = (q, k, v, g[..., None] * jnp.linspace(0.1, 1.0, 128), beta)
    for kernel in (True, False):
        names, scratch, prims, _ = traced(
            lambda *a: seq.gated_delta_scan(*a, 64, kernel), args)
        if kernel:
            kernels_alone(names, scratch, prims)
        else:
            assert not names and {"scan", "triangular_solve"} <= prims


def test_delta_chunk_forward_returns_the_chunk_start_states():
    """The residual the backward kernel reads: float32 ``[B, T/chunk, H,
    K, V]``, equal to the XLA body's carried states, beside an equal
    output; without them the kernel returns ``None``."""
    from mxnet_tpu.ops import pallas_kernels, seq

    args = delta_scan_args(1, 2, 192, 3, 8, 16)
    o, starts = pallas_kernels.delta_chunk_forward(*args, chunk=64,
                                                   with_states=True)
    assert starts.dtype == jnp.float32 and starts.shape == (2, 3, 3, 8, 16)
    assert not np.asarray(starts[:, 0]).any()
    for i in range(2):
        want_o, want = seq.gated_delta_chunked(*(a[i] for a in args),
                                               chunk=64)
        close(starts[i], want, 5e-6)
        close(o[i], want_o, 5e-6)
    assert pallas_kernels.delta_chunk_forward(
        *args, chunk=64, with_states=False)[1] is None


def test_gated_delta_rule_shapes():
    net = delta_net(8, 3, 4, 6, 4, True)
    args, outs, _ = net.infer_shape(query=(16, 12))
    assert dict(zip(net.list_arguments(), args)) == {
        "query": (16, 12), "key": (16, 12), "value": (16, 18), "a": (16, 3),
        "b": (16, 3), "A_log": (3,), "dt_bias": (3,)}
    assert outs == [(16, 18)]
    with pytest.raises(mx.MXNetError, match="whole sequences"):
        delta_net(7, 3, 4, 6, 4, True).infer_shape(query=(16, 12))
    with pytest.raises(mx.MXNetError, match="heads"):
        net.infer_shape(query=(16, 10))
