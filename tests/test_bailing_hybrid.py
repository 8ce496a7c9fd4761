"""``models.get_bailing_hybrid`` (Ling 3.0: the delta rule with a decay a key
channel five layers in six, latent attention whose values are narrower than
its keys under a head-wise gate, routed experts chosen inside a few groups
beside a shared one) through ``Module.fit`` on the fused step against the
benchmark's float32 reference; the operators' new forms
(``GatedDeltaRule`` with ``a`` a channel wide and ``gate_floor``,
``CausalAttention(value_dim=...)`` at 192-wide keys, ``RoutedExperts(
n_group=..., topk_group=...)``) against plain ``jax.numpy``; and the share
by experts of ``model-configs`` section 4 under the group limit. Toy
widths, seeded."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_bailing_hybrid
from mxnet_tpu.ops import attention, moe, seq

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import bailing_hybrid as ref  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)
from test_glm4_moe_lite import _attention_over  # noqa: E402
from test_nemotron_h import (Ring, against, aux_states, close,  # noqa: E402
                             rng_inputs, run_op)
from test_olmo_hybrid import toy_batches  # noqa: E402

TOY = dict(layer_types=["kda", "kda", "latent_attention", "kda"],
           dense_layers=1, hidden=32, vocab=96, heads=4, kda_key_dim=8,
           kda_value_dim=8, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
           dense_hidden=48, experts_total=32, experts_held=8, first_expert=8,
           top_k=4, n_group=4, topk_group=2, expert_hidden=16, seq_len=64,
           chunk=32, bias_update_rate=0.01)
RECIPE = {"learning_rate": 0.001, "wd": 0.01, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the delta rule with a decay a key channel
# ---------------------------------------------------------------------------
def recurrence(q, k, v, g, beta):
    """``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t`` a position at a time: ``q``, ``k``, ``g [B, T, H,
    K]``, ``v [B, T, H, V]``, ``beta [B, T, H]``."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s0 = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0)
                                        for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_inputs(seed, batch=2, t=192, h=3, dk=16, dv=24):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return tuple(jnp.asarray(x, jnp.float32) for x in (
        unit(rng.normal(size=(batch, t, h, dk))) * dk ** -0.5,
        unit(rng.normal(size=(batch, t, h, dk))),
        rng.normal(size=(batch, t, h, dv)),
        rng.uniform(size=(batch, t, h))))


@pytest.mark.parametrize("decay,tol", [
    ("spread", 1e-5), ("at_the_bound", 1e-5), ("near_zero", 1e-5)])
def test_channel_body_is_the_recurrence(decay, tol):
    """The chunked per-channel body (chunks of 64 in sub-chunks of 16)
    against the position-wise recurrence over two sequences of a batch:
    log-decays spread over (-5, 0), EVERY channel at the bound -5 for the
    whole sequence (``exp(-c)`` over a chunk would be ``exp(320)``), and
    every channel near 0; the output and the gradients of q, k, v, finite
    everywhere. (The gradient of ``g`` at the bound is what the cumulative
    sums' cancellation leaves of it: compared where it is not tiny.)"""
    q, k, v, beta = delta_inputs(3)
    rng = np.random.default_rng(4)
    g = jnp.asarray({"spread": -5.0 * rng.uniform(size=q.shape),
                     "at_the_bound": np.full(q.shape, -4.9999),
                     "near_zero": -1e-4 * rng.uniform(size=q.shape)}[decay],
                    jnp.float32)

    def body(q, k, v, g):
        return seq.gated_delta_scan(q, k, v, g, beta, 64, False)

    got, want = body(q, k, v, g), recurrence(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, want, tol)
    head = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
    got_g = jax.grad(lambda *a: jnp.sum(body(*a) * head),
                     argnums=(0, 1, 2, 3))(q, k, v, g)
    want_g = jax.grad(lambda *a: jnp.sum(recurrence(*a, beta) * head),
                      argnums=(0, 1, 2, 3))(q, k, v, g)
    for a, b in zip(got_g[:3], want_g[:3]):
        assert bool(jnp.all(jnp.isfinite(a)))
        close(a, b, 10 * tol)
    assert bool(jnp.all(jnp.isfinite(got_g[3])))
    if decay != "at_the_bound":
        close(got_g[3], want_g[3], 10 * tol)


def test_heads_in_groups_compute_what_all_heads_do(monkeypatch):
    """Where all heads' float32 intermediates together would pass
    ``seq._CHANNEL_RUN_BYTES`` the body runs a group of heads at a time
    under ``jax.checkpoint``: the same output and gradients (the heads are
    independent), at toy size with the bound set to a byte."""
    q, k, v, beta = delta_inputs(5, t=128, h=4)
    g = jnp.asarray(-3.0 * np.random.default_rng(6).uniform(size=q.shape),
                    jnp.float32)

    def loss(*a):
        return jnp.sum(jnp.square(seq.gated_delta_scan(*a, beta, 64, False)))

    want = seq.gated_delta_scan(q, k, v, g, beta, 64, False)
    want_g = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, g)
    monkeypatch.setattr(seq, "_CHANNEL_RUN_BYTES", 1)
    text = str(jax.make_jaxpr(lambda *a: seq.gated_delta_scan(
        *a, beta, 64, False))(q, k, v, g))
    assert "checkpoint" in text or "remat" in text
    close(seq.gated_delta_scan(q, k, v, g, beta, 64, False), want, 1e-6)
    for a, b in zip(jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, g), want_g):
        close(a, b, 1e-5)


def channel_gates(decay, shape, seed):
    """Log-decays ``[B, T, H, K]``: every channel at the bound -5, every
    channel near 0, or both kinds and everything between inside one head
    (a channel keeps its kind along the sequence, its size moves)."""
    rng = np.random.default_rng(seed)
    if decay == "at_the_bound":
        return jnp.full(shape, -5.0, jnp.float32)
    if decay == "near_zero":
        return jnp.asarray(-1e-3 * rng.uniform(size=shape), jnp.float32)
    kind = rng.integers(0, 3, size=(1, 1) + shape[2:])
    g = np.where(kind == 0, -5.0 + 1e-3 * rng.uniform(size=shape),
                 np.where(kind == 1, -1e-3 * rng.uniform(size=shape),
                          -5.0 * rng.uniform(size=shape)))
    return jnp.asarray(g, jnp.float32)


@pytest.mark.parametrize("decay,h,chunks,dv", [
    ("at_the_bound", 2, 2, 128), ("near_zero", 3, 3, 128),
    ("mixed_in_a_head", 4, 4, 128), ("mixed_in_a_head", 2, 2, 256),
    ("mixed_in_a_head", 2, 3, 64)],
    ids=["at_the_bound", "near_zero", "mixed_in_a_head", "values_256",
         "values_64"])
def test_channel_kernels_against_the_xla_body(decay, h, chunks, dv):
    """The Pallas chunk kernels with a decay a key channel (interpreted
    here) at 128 keys a head, chunks of 64, against
    ``seq.gated_delta_chunked_channel`` and its autodiff: the output, the
    chunk-start states, and dq, dk, dv, dg A CHANNEL and dbeta. (At the
    bound dg is what the cumulative sums' cancellation leaves: 1e-3 where
    dq is 13, so it is held at 1e-4 of its own largest.)"""
    from mxnet_tpu.ops import pallas_kernels

    t, dk = 64 * chunks, 128
    q, k, v, beta = delta_inputs(7, batch=2, t=t, h=h, dk=dk, dv=dv)
    beta = 2.0 * beta
    g = channel_gates(decay, q.shape, 8)
    assert pallas_kernels.delta_channel_applicable((h, dk, dv), 64, q.dtype)
    o, starts = pallas_kernels.delta_chunk_forward(q, k, v, g, beta, chunk=64,
                                                   with_states=True)
    assert starts.dtype == jnp.float32
    assert starts.shape == (2, chunks, h, dk, dv)
    assert not np.asarray(starts[:, 0]).any()
    for i in range(2):
        want_o, want = seq.gated_delta_chunked_channel(
            q[i], k[i], v[i], g[i], beta[i], 64)
        close(o[i], want_o, 2e-5)
        close(starts[i], want, 2e-5)
    assert pallas_kernels.delta_chunk_forward(
        q, k, v, g, beta, chunk=64, with_states=False)[1] is None
    head = jnp.asarray(np.random.default_rng(9).normal(size=o.shape),
                       jnp.float32)

    def grads(kernel):
        return jax.grad(lambda *a: jnp.sum(seq.gated_delta_scan(
            *a, 64, kernel) * head), argnums=range(5))(q, k, v, g, beta)

    for name, got, want in zip(("dq", "dk", "dv", "dg", "dbeta"),
                               grads(True), grads(False)):
        assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got)))
        close(got, want, 1e-4 if (name, decay) == ("dg", "at_the_bound")
              else 3e-5)


def _delta_op(inputs, head, **op):
    net = sym.GatedDeltaRule(**op, **{k: sym.Variable(k) for k in inputs})
    telemetry.reset()
    telemetry.enable()
    try:
        return run_op(net, inputs, head), {
            n: telemetry.peek("lower." + n) for n in (
                "delta_rule_gate.head", "delta_rule_gate.channel",
                "delta_rule_kernel.pallas_chunked",
                "delta_rule_kernel.xla_chunked")}
    finally:
        telemetry.disable()


@pytest.mark.parametrize("dk,dv", [(6, 10), (8, 16)],
                         ids=["xla_body", "pallas_kernel"])
def test_equal_channels_and_the_softplus_form_are_the_per_head_op(dk, dv):
    """With ``a`` and ``dt_bias`` the same in every channel of a head and
    ``gate_floor`` 0, the per-channel op computes today's ``GatedDeltaRule``:
    against the per-head op on its XLA body and on its Pallas kernels
    (interpreted), the output and every shared input's gradient within a
    float32 tolerance (1e-5 of the largest value: the two bodies order
    their sums differently); ``a``'s gradient a head is the channels'
    sum."""
    t, h = 64, 2
    inputs = rng_inputs(5, query=(2 * t, h * dk), key=(2 * t, h * dk),
                        value=(2 * t, h * dv), a=(2 * t, h), b=(2 * t, h),
                        A_log=(h,), dt_bias=(h,))
    head = rng_inputs(6, o=(2 * t, h * dv))["o"]
    op = dict(num_heads=h, key_dim=dk, value_dim=dv, chunk=32, seq_len=t,
              neg_eigval=True)
    (want, want_g), counted = _delta_op(inputs, head, **op)
    assert counted["delta_rule_gate.head"] == 1
    assert not counted["delta_rule_gate.channel"]
    wide = dict(inputs, a=np.repeat(inputs["a"], dk, axis=1),
                dt_bias=np.repeat(inputs["dt_bias"], dk))
    (got, got_g), counted = _delta_op(wide, head, **op)
    assert counted["delta_rule_gate.channel"] == 1
    assert counted["delta_rule_kernel.xla_chunked"] == 1
    assert not counted["delta_rule_kernel.pallas_chunked"]
    close(got, want, 1e-5)
    for name in ("query", "key", "value", "b", "A_log"):
        close(got_g[name], want_g[name], 5e-5)
    close(got_g["a"].reshape(2 * t, h, dk).sum(-1), want_g["a"], 5e-5)
    close(got_g["dt_bias"].reshape(h, dk).sum(-1), want_g["dt_bias"], 5e-5)


@pytest.mark.parametrize("shape", ["head", "channel", "channel_kernel"])
def test_bounded_gate_against_plain(shape):
    """``gate_floor`` -5: ``g = -5 sigmoid(exp(A_log) (a + dt_bias))``, with
    either shape of ``a``, against the recurrence written out; a decay a
    channel at toy widths on the XLA body, at 128 keys a head and chunks of
    64 (a sequence of two and a half) on the Pallas chunk kernels, each
    counted once."""
    t, h, dk, dv, chunk = (160, 2, 128, 128, 64) if shape == "channel_kernel" \
        else (64, 2, 8, 8, 32)
    wide = h if shape == "head" else h * dk
    inputs = rng_inputs(7, query=(t, h * dk), key=(t, h * dk),
                        value=(t, h * dv), a=(t, wide), b=(t, h),
                        A_log=(h,), dt_bias=(wide,))
    inputs["a"] = 3.0 * inputs["a"]

    def plain(query, key, value, a, b, A_log, dt_bias):
        def unit(x):
            x = x.reshape(1, t, h, -1)
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        rate = jnp.repeat(jnp.exp(A_log), wide // h)
        g = -5.0 * jax.nn.sigmoid(rate * (a + dt_bias))
        g = jnp.broadcast_to(g.reshape(1, t, h, -1), (1, t, h, dk))
        o = recurrence(unit(query) * dk ** -0.5, unit(key),
                       value.reshape(1, t, h, dv), g,
                       jax.nn.sigmoid(b).reshape(1, t, h))
        return o.reshape(t, h * dv)

    net = sym.GatedDeltaRule(num_heads=h, key_dim=dk, value_dim=dv,
                             chunk=chunk, seq_len=t, gate_floor=-5.0,
                             **{k: sym.Variable(k) for k in inputs})
    telemetry.reset()
    telemetry.enable()
    try:
        against(plain, net, inputs, tol=5e-5)
        counted = [telemetry.peek("lower.delta_rule_kernel." + k) or 0
                   for k in ("pallas_chunked", "xla_chunked")]
    finally:
        telemetry.disable()
    # 8 keys a head are whole sublanes: the scalar kernels' shape, and no
    # whole lane tile for a decay a channel
    assert counted == {"head": [1, 0], "channel": [0, 1],
                       "channel_kernel": [1, 0]}[shape]


def test_bad_gates_are_refused():
    v = {k: sym.Variable(k) for k in ("query", "key", "value", "a", "b")}
    shapes = dict(query=(32, 16), key=(32, 16), value=(32, 16), b=(32, 2))
    op = dict(num_heads=2, key_dim=8, value_dim=8, seq_len=32)
    with pytest.raises(mx.base.MXNetError, match="neither"):
        sym.GatedDeltaRule(**op, **v).infer_shape(a=(32, 4), **shapes)
    with pytest.raises(mx.base.MXNetError, match="above 0"):
        sym.GatedDeltaRule(gate_floor=1.0, **op, **v).infer_shape(
            a=(32, 2), **shapes)
    # a decay a channel wants chunks of whole sub-chunks
    inputs = rng_inputs(1, a=(32, 16), A_log=(2,), dt_bias=(16,), **shapes)
    with pytest.raises(mx.base.MXNetError, match="sub-chunks"):
        run_op(sym.GatedDeltaRule(chunk=24, **op,
                                  **{k: sym.Variable(k) for k in inputs}),
               inputs, np.zeros((32, 16), np.float32))


# ---------------------------------------------------------------------------
# attention whose values are narrower than its keys
# ---------------------------------------------------------------------------
def plain_attention(query, key, value, heads, head_dim, value_dim,
                    rotary_dim, theta):
    """Dense causal softmax a head over keys of ``head_dim`` and values of
    ``value_dim``, the rotation of the last ``rotary_dim`` columns written
    out (half-split pairs)."""
    t = query.shape[0]
    q, k = (x.reshape(t, heads, head_dim) for x in (query, key))
    v = value.reshape(t, heads, value_dim)
    half, keep = rotary_dim // 2, head_dim - rotary_dim
    ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None, :]
                for f in (np.cos, np.sin))

    def turn(x):
        a, b = x[..., keep:keep + half], x[..., keep + half:]
        return jnp.concatenate([x[..., :keep], a * cos - b * sin,
                                b * cos + a * sin], axis=-1)

    s = jnp.einsum("qhd,khd->hqk", turn(q), turn(k)) / np.sqrt(head_dim)
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                      v).reshape(t, heads * value_dim)


def test_values_narrower_than_keys_against_plain():
    """``value_dim``: keys of 12 (8 + 4 rotary), values and the result of
    8, on the XLA path toy widths take."""
    t, h = 48, 3
    inputs = rng_inputs(2, query=(t, h * 12), key=(t, h * 12),
                        value=(t, h * 8))
    net = sym.CausalAttention(num_heads=h, num_kv_heads=h, head_dim=12,
                              value_dim=8, seq_len=t, rotary_dim=4,
                              rope_theta=1e4,
                              **{k: sym.Variable(k) for k in inputs})
    assert net.infer_shape(**{k: v.shape for k, v in inputs.items()})[1] \
        == [(t, h * 8)]
    against(lambda query, key, value: plain_attention(
        query, key, value, h, 12, 8, 4, 1e4), net, inputs, tol=5e-5)


def test_keys_of_192_and_values_of_128_take_the_splash_kernel(monkeypatch):
    """The Ling latent layer's head, 128 + 64 rotary key columns beside 128
    value columns, at 512 positions: through the relayout passes and the
    splash kernel (keys widened to 256 with zero columns; all interpreted
    here) against ``attend_blockwise`` on the XLA path: the output and the
    three input gradients; no ``[T, T]`` tensor in either."""
    t, h = 512, 2
    inputs = rng_inputs(7, query=(t, h * 192), key=(t, h * 192),
                        value=(t, h * 128))
    head = rng_inputs(8, h=(t, h * 128))["h"]
    op = dict(num_heads=h, num_kv_heads=h, head_dim=192, value_dim=128,
              seq_len=t, rotary_dim=64, rope_theta=6e6)
    (want, want_g), counted = _attention_over(monkeypatch, False, inputs,
                                              head, **op)
    assert counted["attention_kernel.xla_blockwise"] >= 1
    (got, got_g), counted = _attention_over(monkeypatch, True, inputs, head,
                                            **op)
    assert got.shape == (t, h * 128)
    close(got, want, 2e-4)
    for name in inputs:
        close(got_g[name], want_g[name], 2e-4)
    assert counted["attention_kernel.pallas_splash"] >= 1
    assert not counted["attention_kernel.xla_blockwise"]
    # and the XLA path is the plain softmax
    close(want, plain_attention(*(jnp.asarray(inputs[k]) for k in (
        "query", "key", "value")), h, 192, 128, 64, 6e6), 2e-4)


@pytest.mark.parametrize("head_dim,value_dim,splash", [
    (192, 128, True), (192, 0, False), (256, 128, True), (128, 64, False),
    (64, 0, True), (128, 0, True)])
def test_which_heads_the_splash_path_takes(head_dim, value_dim, splash):
    op = attention.CausalAttention(num_heads=4, num_kv_heads=4,
                                   head_dim=head_dim, value_dim=value_dim,
                                   seq_len=1024)
    assert op._splash_applies() is splash


# ---------------------------------------------------------------------------
# experts chosen inside a few groups
# ---------------------------------------------------------------------------
def written_out_choice(choice, n_group, topk_group, top_k):
    """The group limit a row at a time in numpy: the groups' scores (sum of
    the two largest), the kept groups, the largest inside them."""
    out = []
    for row in np.asarray(choice):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(row, -np.inf).reshape(n_group, -1)
        masked[kept] = groups[kept]
        out.append(np.argsort(-masked.reshape(-1), kind="stable")[:top_k])
    return np.asarray(out)


def test_group_limited_choice_against_a_written_out_mask():
    """``route(n_group=4, topk_group=2)`` over 32 experts, 4 a row: the ids
    against the mask written out a row at a time, and against the
    reference's comparisons; a row whose largest scores lie in a DROPPED
    group (one huge expert in a group of small ones: the group's two
    largest sum lower than two middling ones elsewhere) is among them; the
    weights are the chosen unbiased scores over their sum."""
    rng = np.random.default_rng(3)
    s, e, h = 96, 32, 16
    x = rng.standard_normal((s, h)).astype(np.float32)
    router = rng.standard_normal((h, e)).astype(np.float32)
    bias = (0.1 * rng.standard_normal(e)).astype(np.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)))
    eid, wts = moe.route(jnp.asarray(x), jnp.asarray(router),
                         jnp.asarray(bias), 4, 2.5, n_group=4, topk_group=2)
    want = written_out_choice(scores + bias, 4, 2, 4)
    assert np.array_equal(np.sort(np.asarray(eid), 1), np.sort(want, 1))
    # some row's best expert overall is NOT chosen: its group was dropped
    best = np.argmax(scores + bias, axis=1)
    lost = [r for r in range(s) if best[r] not in np.asarray(eid)[r]]
    assert lost, "no row lost its best expert to the group limit"
    unlimited, _ = moe.route(jnp.asarray(x), jnp.asarray(router),
                             jnp.asarray(bias), 4, 2.5)
    assert all(best[r] in np.asarray(unlimited)[r] for r in range(s))
    # every row's experts lie in at most two groups
    assert max(len(set(row // 8)) for row in np.asarray(eid)) <= 2
    chosen = np.take_along_axis(scores, np.asarray(eid), 1)
    close(wts, 2.5 * chosen / chosen.sum(1, keepdims=True), 1e-6)
    # the reference's mask, written as comparisons
    c = ref.config(dict(TOY))
    assert np.array_equal(
        np.sort(np.asarray(ref.choose(jnp.asarray(scores + bias), c)), 1),
        np.sort(want, 1))


def test_bad_groups_are_refused():
    op = dict(num_experts=32, num_held=8, top_k=4, num_hidden=8, gated=True)
    for bad in (dict(n_group=5), dict(n_group=4, topk_group=5),
                dict(n_group=16, topk_group=1), dict(n_group=32)):
        with pytest.raises(mx.base.MXNetError, match="groups"):
            moe.RoutedExperts(**op, **bad).infer_shape([(16, 8)] + [None] * 4)
    moe.RoutedExperts(n_group=4, topk_group=2, **op).infer_shape(
        [(16, 8)] + [None] * 4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4 under the group limit: at 32 experts in 4
    groups, 8 held a share, the four shares' routed parts as the program
    computes them plus the shared expert counted once equal the uncut
    reference's expert layer."""
    args = dict(TOY, layer_types=["kda"], dense_layers=0, experts_held=32,
                first_expert=0)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(rng_inputs(9, x=(48, TOY["hidden"]))["x"])
    pre = "layer0_"
    whole, load, _ = ref.experts(params, pre, x, args)
    assert float(load.sum()) == 48 * TOY["top_k"]
    st, mm = ref._ROUND[None]
    total = np.asarray(ref.gated(params, pre + "ffn_shared_", x, st, mm))
    inputs = {n: np.asarray(params[pre + "ffn_experts_%s_weight" % n])
              for n in ("router", "gate", "up", "down")}
    names = ["data"] + [n + "_weight" for n in inputs]
    v = {k: sym.Variable(k) for k in names}
    c = ref.config(args)
    for first in range(0, 32, 8):
        net = sym.RoutedExperts(
            num_experts=32, num_held=8, first_held=first, top_k=TOY["top_k"],
            scale=c["routed_scale"], gated=True, n_group=4, topk_group=2,
            num_hidden=TOY["expert_hidden"], **v)
        mine = {"data": np.asarray(x), "router_weight": inputs["router"]}
        mine.update({n + "_weight": inputs[n][first:first + 8]
                     for n in ("gate", "up", "down")})
        ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in mine.items()},
                      aux_states=aux_states(
                          net, {k: a.shape for k, a in mine.items()}))
        part = ex.forward(is_train=False)[0].asnumpy()
        # the reference given the same share computes the same part
        theirs = ref.routed_part(
            x, ref.route(params, pre, x, c),
            tuple(jnp.asarray(mine[n + "_weight"])
                  for n in ("gate", "up", "down")), first, st, mm)
        close(part, np.asarray(theirs), 5e-5)
        total = total + part
    close(total, np.asarray(whole), 5e-5)


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.delta_rule_gate.channel", "lower.delta_rule_gate.head",
            "lower.delta_rule_kernel.xla_chunked",
            "lower.delta_rule_kernel.pallas_chunked",
            "lower.attention_kernel.xla_blockwise",
            "lower.attention_kernel.pallas_splash",
            "lower.experts_body.swiglu", "lower.experts_kernel.xla_loop",
            "moe.rows_total", "moe.rows_here", "moe.dropped_rows",
            "remat.segments", "remat.segments_recomputed",
            "remat.kept_results")


def fit_toy(monkeypatch, batches, compute_dtype=None, toy=TOY, seed=5):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        toy, jax.random.PRNGKey(seed)).items()}
    net = get_bailing_hybrid(**toy)
    args_of = set(net.list_arguments())
    mod = mx.mod.Module(net, context=mx.cpu(0))
    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k in args_of},
                aux_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k not in args_of},
                num_epoch=1)
        counters = {k: telemetry.peek(k) for k in COUNTERS}
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters


def test_model_fits_on_the_fused_step_like_the_reference(monkeypatch):
    """Three Adam steps through ``Module.fit`` under recomputation against
    the benchmark's reference: the first gradient (Adam's first moment) and
    the three-step change by leaf, the selection biases among the leaves;
    one dispatch a step, one program; the lowerings and the experts' rows
    as telemetry reads them. Tolerances: float32 on both sides, the
    program's chunked delta rule against the reference's recurrence and its
    grouped experts against a masked loop: the median leaf agrees to 2e-4,
    the worst (a router or a decay gate's few hundred numbers, whose
    gradients are sums that cancel) to 1e-2."""
    batches = toy_batches(3, toy=TOY)
    mod, params0, counters = fit_toy(monkeypatch, batches)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    assert counters["lower.delta_rule_gate.channel"] == 3
    assert not counters["lower.delta_rule_gate.head"]
    assert counters["lower.delta_rule_kernel.xla_chunked"] == 3
    assert not counters["lower.delta_rule_kernel.pallas_chunked"]
    assert counters["lower.attention_kernel.xla_blockwise"] == 1
    assert counters["lower.experts_body.swiglu"] == 3
    assert counters["lower.experts_kernel.xla_loop"] == 3
    # (row, expert) pairs: 3 expert layers x 3 steps x 128 rows x top-4
    assert counters["moe.rows_total"] == 3 * 3 * 128 * 4
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    args, aux = mod.get_params()
    states = {k for k in params0 if k.endswith(ref.STATE)}
    assert len(states) == 3 and set(args) == set(params0) - states
    assert states <= set(aux)
    want = ref.follow(TOY, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))
    got = {k: v.asnumpy() for k, v in {**args, **aux}.items()
           if k in params0}
    delta = ref.leaf_norms({k: jnp.asarray(got[k] - params0[k])
                            for k in params0})
    assert set(delta) == set(want["delta_norms"])
    assert sum("experts_up_weight[" in k for k in delta) \
        == 3 * TOY["experts_held"]
    gaps = sorted(abs(float(delta[k]) - want["delta_norms"][k])
                  / max(want["delta_norms"][k], 1e-3) for k in delta)
    assert gaps[len(gaps) // 2] < 2e-4 and gaps[-1] < 1e-2, gaps[-3:]
    assert all(n > 0 for n in want["delta_norms"].values())
    # the first gradient, from Adam's first moment after ONE step from a
    # zero state: m1 = (1 - b1) (g + wd w0)
    mod, _, _ = fit_toy(monkeypatch, batches[:1])
    grads = {}
    for i, name in enumerate(mod._param_names):
        m1 = mod._updater.states[i][0].asnumpy()
        grads[name] = jnp.asarray(m1 / (1.0 - RECIPE["beta1"])
                                  - RECIPE["wd"] * params0[name])
    norms = ref.leaf_norms(grads)
    assert set(norms) == set(want["grad_norms"])
    for name, norm in norms.items():
        assert abs(float(norm) - want["grad_norms"][name]) \
            <= 2e-3 * max(want["grad_norms"][name], 1e-3), name


def test_model_loss_and_logprob_follow_the_reference(monkeypatch):
    """The forward pass alone: the program's loss of the first batch (the
    metric's cross-entropy) against the reference's, float32."""
    batches = toy_batches(1, toy=TOY)
    mod, params0, _ = fit_toy(monkeypatch, batches)
    ids, labels = (jnp.asarray(x) for x in batches[0])
    with jax.default_matmul_precision("highest"):
        want, _ = ref.loss_and_logprob(
            {k: jnp.asarray(v) for k, v in params0.items()}, ids, labels,
            TOY, jnp.arange(4))
    # the metric saw one batch, before its update
    it = Ring(batches)
    metric = mx.metric.create("ce")
    net = get_bailing_hybrid(**TOY)
    fresh = mx.mod.Module(net, context=mx.cpu(0))
    fresh.bind(it.provide_data, it.provide_label, for_training=False)
    args_of = set(net.list_arguments())
    fresh.set_params({k: mx.nd.array(v) for k, v in params0.items()
                      if k in args_of},
                     {k: mx.nd.array(v) for k, v in params0.items()
                      if k not in args_of}, allow_missing=True)
    fresh.forward(it.next(), is_train=False)
    fresh.update_metric(metric, [mx.nd.array(batches[0][1])])
    assert metric.get()[1] == pytest.approx(float(want), rel=2e-5)


def test_a_swiglu_limit_is_refused_not_guessed():
    limits = [[0, 0, 0, 4], [0, 0, 0, 0]]
    with pytest.raises(ValueError, match="clamp's form"):
        get_bailing_hybrid(**dict(TOY, swiglu_limits=limits))
    with pytest.raises(ValueError, match="clamp's form"):
        ref.config(dict(TOY, swiglu_limits=limits))
    with pytest.raises(ValueError, match="entries"):
        get_bailing_hybrid(**dict(TOY, swiglu_limits=[[0], [0]]))
    get_bailing_hybrid(**dict(TOY, swiglu_limits=[[0] * 4, [0] * 4]))
    with pytest.raises(ValueError, match="not 'kda'"):
        get_bailing_hybrid(**dict(TOY, layer_types=["kda", "mamba"]))


def test_published_defaults_are_the_catalogs():
    """The factory's and the reference's defaults are the published sizes:
    42 layers, latent attention at published 5, 11, ..., 41, 35 KDA; the
    parameter count of the cut the configuration states."""
    import json

    assert ref.LAYER_TYPES.count("latent_attention") == 7
    assert [i for i, k in enumerate(ref.LAYER_TYPES)
            if k == "latent_attention"] == list(range(5, 42, 6))
    from mxnet_tpu.models import bailing_hybrid as model

    assert model.LAYER_TYPES == ref.LAYER_TYPES
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "ling3_flash_l6_e8of512_bf16.json")) as f:
        config = json.load(f)
    assert config["model"]["args"] == config["reference"]["args"]
    args = config["reference"]["args"]
    assert args["layer_types"] == list(ref.LAYER_TYPES[1:7])
    n = sum(int(np.prod(s))
            for s in ref.param_shapes(args, states=False).values())
    assert round(n / 1e6, 1) == 767.0
    cost = ref.step_cost(args, 1)
    assert set(cost["parts"]) == {
        "linattn_proj_conv", "linattn_scan", "attention_proj",
        "attention_kernel", "dense_ffn", "moe_grouped_matmul", "moe_rest",
        "lm_head_loss", "embed"}
    # attention at 192 key and 128 value columns, the causal half, 3 passes
    assert cost["parts"]["attention_kernel"][0] \
        == 3 * 8192 * 8192 * 32 * (192 + 128)
    # the recurrence's useful work: 7 K V a position and head
    assert cost["parts"]["linattn_scan"][0] \
        == 3 * 5 * 7 * 8192 * 32 * 128 * 128


def test_reference_imports_nothing_of_the_program():
    import inspect

    src = inspect.getsource(ref)
    assert "mxnet_tpu" not in src.replace("mxnet_tpu/optimizer.py", "")


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy("ling3_flash_l6_e8of512_bf16.json",
                              get_bailing_hybrid(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # ``GatedDeltaRule`` computes in float32 whatever the compute
        # dtype (a bfloat16 operand is another result: ``ops/seq.py``)
        "seq": 94,
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 12,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 4})
