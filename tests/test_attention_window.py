"""``CausalAttention``'s band and scaled rotary frequencies (``window``,
``rope_factor`` and its kin): the op against softmax under a written-out
``[T, T]`` mask on both lowerings (the splash kernels interpreted, the XLA
blockwise body), the one-kernel backward pass under the band against JAX's
two kernels, the list of block pairs it walks, YaRN's tables against a
float64 transcription of the published formulas, and under the arguments'
defaults the six older language cells' ``CausalAttention`` against the
parent's traced programs. Small shapes, seeded."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.ops import attention
from mxnet_tpu.ops import pallas_kernels as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import laguna as ref  # noqa: E402
from op_program_text import program_hashes  # noqa: E402
from test_attention_backward import gap, gradients, operands  # noqa: E402
from test_nemotron_h import against, rng_inputs, run_op  # noqa: E402


# ---------------------------------------------------------------------------
# the window: a position reads the last ``window`` keys, its own among them
# ---------------------------------------------------------------------------
def plain_attention(query, key, value, t, hq, hkv, d, window=0, turn=None):
    """The op's statement with the ``[T, T]`` mask written out; ``turn``
    rotates ``[B, T, H, D]`` queries and keys."""
    q, k, v = (x.reshape(-1, t, h, d)
               for x, h in ((query, hq), (key, hkv), (value, hkv)))
    if turn is not None:
        q, k = turn(q), turn(k)
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    p = jax.nn.softmax(jnp.where(jnp.asarray(mask), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST).reshape(
                          -1, hq * d)


def attention_net(inputs, t, hq, hkv, d, **op):
    return sym.CausalAttention(
        num_heads=hq, num_kv_heads=hkv, head_dim=d, seq_len=t,
        **{"rotary": False, **op}, **{k: sym.Variable(k) for k in inputs})


def small_blocks(monkeypatch):
    """Blocks of 128 for both passes of the splash path, so that four blocks
    are 512 positions on the interpreter."""
    monkeypatch.setattr(attention, "SPLASH_BLOCK", 128)
    monkeypatch.setattr(pk, "ATTENTION_BACKWARD_BLOCK", 128)


# (lowering, positions, head width, block): the splash kernels interpreted,
# and the XLA body at a head no kernel takes
LOWERINGS = {"splash": ("pallas_splash", 512, 128, 128),
             "xla": ("xla_blockwise", 1536, 8, 512)}


@pytest.mark.parametrize("lowering,blocks,group", [
    ("splash", 0.5, 6), ("splash", 1, 8), ("splash", 1.5, 6),
    ("splash", 2.25, 8), ("xla", 0.5, 8), ("xla", 1, 6), ("xla", 1.5, 8)],
    ids=lambda v: {0.5: "half_a_block", 1: "a_block",
                   1.5: "a_block_and_a_half",
                   2.25: "two_blocks_and_a_quarter"}.get(v, str(v)))
def test_window_against_the_written_out_mask(monkeypatch, lowering, blocks,
                                             group):
    """``CausalAttention(window=w)``: the output and the gradient of every
    input against softmax under the written-out band, for a window inside
    one block (the diagonal block carries both edges), of exactly a block,
    of a block and a half (both pairs behind the diagonal cross the edge)
    and of two and a quarter (an interior pair between the edge's and the
    diagonal's: the kernels' case; the XLA body masks every block it reads
    alike), groups of 6 and of 8 query heads a key/value head. Float32
    on both sides at ``highest``: sums in another order."""
    small_blocks(monkeypatch)
    counter, t, d, block = LOWERINGS[lowering]
    window = int(blocks * block)
    inputs = rng_inputs(int(8 * blocks) + group, query=(t, group * d),
                        key=(t, d), value=(t, d))
    net = attention_net(inputs, t, group, 1, d, window=window)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_attention(t=t, hq=group, hkv=1, d=d,
                                             window=window, **kw),
                net, inputs, tol=2e-5)
        counted = {k: telemetry.peek("lower." + k) or 0 for k in (
            "attention_kernel." + counter, "attention_mask.window",
            "attention_mask.causal", "attention_window.block_pairs",
            "attention_window.block_pairs_causal",
            "attention_backward.fused")}
    finally:
        telemetry.disable()
    assert counted["attention_kernel." + counter] >= 1
    assert counted["attention_mask.window"] >= 1
    assert not counted["attention_mask.causal"]
    if lowering == "splash":
        assert counted["attention_backward.fused"] >= 1
        pairs = len(pk.attention_block_pairs(t // block, window, block)[0])
        traced = counted["attention_window.block_pairs"] // pairs
        assert counted["attention_window.block_pairs"] == traced * pairs
        assert counted["attention_window.block_pairs_causal"] == traced * 10


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_a_window_of_the_whole_sequence_is_the_causal_program(monkeypatch,
                                                              lowering):
    """``window >= seq_len`` reads every earlier key: the op takes the
    causal program, and its numbers are ``window=0``'s to the bit."""
    small_blocks(monkeypatch)
    counter, t, d, _ = LOWERINGS[lowering]
    t = min(t, 512)
    inputs = rng_inputs(3, query=(t, 6 * d), key=(t, d), value=(t, d))
    head = rng_inputs(4, y=(t, 6 * d))["y"]
    telemetry.reset()
    telemetry.enable()
    try:
        want, want_g = run_op(attention_net(inputs, t, 6, 1, d), inputs, head)
        for window in (t, t + 7):
            got, got_g = run_op(attention_net(inputs, t, 6, 1, d,
                                              window=window), inputs, head)
            assert np.array_equal(got, want)
            for name in inputs:
                assert np.array_equal(got_g[name], want_g[name]), name
        assert telemetry.peek("lower.attention_mask.causal") >= 3
        assert not telemetry.peek("lower.attention_mask.window")
        assert not telemetry.peek("lower.attention_window.block_pairs")
    finally:
        telemetry.disable()
    # and one key fewer is another function
    got, _ = run_op(attention_net(inputs, t, 6, 1, d, window=t - 1), inputs,
                    head)
    assert np.abs(got - want).max() > 1e-4


@pytest.mark.parametrize("window,dtype", [
    (64, "float32"), (192, "float32"), (300, "float32"), (192, "bfloat16")])
def test_one_kernel_backward_under_the_band_agrees_with_the_split_kernels(
        monkeypatch, window, dtype):
    """``pallas_kernels.attention_backward(window=...)`` against JAX's ``dq``
    and ``dkv`` kernels under the same ``LocalMask``, over four blocks of
    128: the band's pairs alone are grid steps, ``dq`` starts at a query
    block's first pair, and every key block's ``dk`` and ``dv`` come out.
    Tolerances as ``tests/test_attention_backward.py``'s causal case."""
    small_blocks(monkeypatch)
    q, k, v, do = operands(1, 2, 3, 512, 128, 128, dtype, seed=window)
    fused = gradients(lambda *a: attention.attend_splash(*a, window=window),
                      q, k, v, do)
    split = gradients(lambda *a: attention.attend_splash(
        *a, fused=False, window=window), q, k, v, do)
    near = 2e-6 if dtype == "float32" else 8e-3
    for got, same in zip(fused, split):
        assert got.dtype == same.dtype == jnp.dtype(dtype)
        assert gap(got, same) <= near
    # the causal pass is another result: the band was read
    causal = gradients(attention.attend_splash, q, k, v, do)
    assert gap(fused[0], causal[0]) > 1e-2


@pytest.mark.parametrize("blocks,window,pairs", [
    (16, 512, 31), (16, 513, 31), (16, 514, 45), (16, 1, 16), (16, 1025, 16 + 15 + 14),
    (16, 0, 136), (4, 192, 4 + 3 + 2)])
def test_the_bands_pairs_of_blocks(blocks, window, pairs):
    """The list the backward kernel walks: ``qi - ceil((w - 1) / 512) <= ki
    <= qi``, a query block's pairs together and the diagonal's last; at the
    cell's 16 blocks and a window of 512 it is 31 of the causal 136."""
    block = 512 if blocks == 16 else 128
    qs, ks = pk.attention_block_pairs(blocks, window, block)
    assert len(qs) == len(ks) == pairs
    behind = -(-(window - 1) // block) if window else blocks
    want = [(q, k) for q in range(blocks)
            for k in range(max(0, q - behind), q + 1)]
    assert list(zip(qs.tolist(), ks.tolist())) == want
    # every pair holds a score and no pair outside the list does
    if window:
        i, j = np.arange(blocks * block)[:, None], \
            np.arange(blocks * block)[None, :]
        band = ((j <= i) & (j > i - window)).reshape(
            blocks, block, blocks, block).any(axis=(1, 3))
        assert {(q, k) for q, k in zip(*np.nonzero(band))} == set(want)


def test_bad_windows_and_scalings_are_refused():
    v = {k: sym.Variable(k) for k in ("query", "key", "value")}
    op = dict(num_heads=4, num_kv_heads=2, head_dim=8, seq_len=32)
    with pytest.raises(mx.base.MXNetError, match="window"):
        sym.CausalAttention(window=-1, **op, **v).infer_shape(query=(32, 32))
    with pytest.raises(mx.base.MXNetError, match="rope_factor"):
        sym.CausalAttention(rope_factor=0.5, **op, **v).infer_shape(
            query=(32, 32))
    with pytest.raises(mx.base.MXNetError, match="original positions"):
        sym.CausalAttention(rope_factor=4.0, **op, **v).infer_shape(
            query=(32, 32))
    sym.CausalAttention(window=8, rope_factor=4.0, rope_original_positions=16,
                        **op, **v).infer_shape(query=(32, 32))


# ---------------------------------------------------------------------------
# rotary frequencies scaled by length
# ---------------------------------------------------------------------------
def yarn_float64(theta, r, factor, original, beta_fast, beta_slow):
    """HF's ``_compute_yarn_parameters`` transcribed, a column at a time."""
    def c(n):
        return r * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    lo = max(math.floor(c(beta_fast)), 0)
    hi = min(math.ceil(c(beta_slow)), r - 1)
    out = []
    for i in range(r // 2):
        f = theta ** (-2.0 * i / r)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / factor * ramp)
    return np.array(out), lo, hi


def test_yarn_tables_against_the_formulas_in_float64():
    """The published full layers' frequencies (64 columns at theta 5e5,
    factor 64 over 4,096 positions, beta 64 / 1): the ramp runs from column
    5 to 16; the op's tables are cos and sin of position times frequency
    times the attention factor, rounded once to float32; the reference's
    frequencies are the same numbers."""
    want, lo, hi = yarn_float64(5e5, 64, 64.0, 4096, 64.0, 1.0)
    assert (lo, hi) == (5, 16)
    factor = 1.4158883083359672
    assert factor == pytest.approx(0.1 * math.log(64) + 1, rel=1e-15)
    scaling = (64.0, 4096, 64.0, 1.0, factor)
    got, said = attention.rope_frequencies(5e5, 32, scaling)
    assert said == factor
    np.testing.assert_allclose(got, want, rtol=1e-14)
    np.testing.assert_allclose(ref.rotary_frequencies(
        5e5, 64, 64.0, 4096, 64.0, 1.0), want, rtol=1e-14)
    assert np.array_equal(got[:6], 5e5 ** (-np.arange(6) / 32.0))
    np.testing.assert_allclose(got[16:], 5e5 ** (-np.arange(16, 32) / 32.0)
                               / 64.0, rtol=1e-14)
    # attention_factor 0 is 0.1 ln(factor) + 1
    assert attention.rope_frequencies(
        5e5, 32, (64.0, 4096, 64.0, 1.0, 0.0))[1] == pytest.approx(factor)
    t = 300
    c, s = attention.rope_tables(t, 5e5, 32, 128, scaling)
    ang = np.arange(t, dtype=np.float64)[:, None] * want[None]
    assert c.shape == s.shape == (t, 128) and c.dtype == np.float32
    assert np.array_equal(c[:, :64], np.ones((t, 64), np.float32))
    assert not s[:, :64].any()
    cos, sin = ((f(ang) * factor).astype(np.float32) for f in (np.cos,
                                                               np.sin))
    np.testing.assert_allclose(c[:, 64:], np.concatenate([cos, cos], 1),
                               rtol=0, atol=2e-7)
    np.testing.assert_allclose(s[:, 64:], np.concatenate([-sin, sin], 1),
                               rtol=0, atol=2e-7)


def test_a_factor_of_one_gives_the_plain_tables_exactly():
    plain = attention.rope_tables(256, 1e4, 32, 128)
    for scaling in ((1.0, 0, 32.0, 1.0, 0.0), (1.0, 4096, 64.0, 1.0, 1.0)):
        for a, b in zip(attention.rope_tables(256, 1e4, 32, 128, scaling),
                        plain):
            assert np.array_equal(a, b)
    for a, b in zip(attention.relayout_tables(256, 1e4, 32, 64),
                    attention.relayout_tables(256, 1e4, 32, 64,
                                              (1.0, 0, 32.0, 1.0, 0.0))):
        assert np.array_equal(a, b)
    # and the op with the defaults spelt out is the op without them
    inputs = rng_inputs(5, query=(64, 32), key=(64, 16), value=(64, 16))
    head = rng_inputs(6, y=(64, 32))["y"]
    want, want_g = run_op(attention_net(inputs, 64, 4, 2, 8, rotary=True),
                          inputs, head)
    got, got_g = run_op(attention_net(
        inputs, 64, 4, 2, 8, rotary=True, rope_factor=1.0,
        rope_original_positions=4096, rope_beta_fast=64.0), inputs, head)
    assert np.array_equal(got, want)
    assert all(np.array_equal(got_g[k], want_g[k]) for k in inputs)


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_yarn_through_the_op_on_both_lowerings(monkeypatch, lowering):
    """Scaled rotary over the LAST half of the head's columns, groups of 6,
    under a window: through the relayout pass's tables on the splash path
    and through ``rope`` on the XLA one, against the reference's float64
    tables and the written-out mask."""
    small_blocks(monkeypatch)
    _, t, d, _ = LOWERINGS[lowering]
    t, r = min(t, 512), d // 2
    scaling = dict(rope_theta=5e5, rope_factor=8.0,
                   rope_original_positions=64, rope_beta_fast=16.0,
                   rope_beta_slow=1.0, rope_attention_factor=1.2)
    freq = ref.rotary_frequencies(5e5, r, 8.0, 64, 16.0, 1.0)
    assert not np.array_equal(freq, ref.rotary_frequencies(5e5, r))

    def turn(x):
        return jnp.concatenate([x[..., :d - r],
                                ref._rotary(x[..., d - r:], freq, 1.2)], -1)

    inputs = rng_inputs(7, query=(t, 6 * d), key=(t, d), value=(t, d))
    net = attention_net(inputs, t, 6, 1, d, rotary=True, rotary_dim=r,
                        window=160, **scaling)
    # float32 tables of float64 angles on both sides; the pass's own
    # arithmetic is x * C + partner * S
    against(lambda **kw: plain_attention(t=t, hq=6, hkv=1, d=d, window=160,
                                         turn=turn, **kw),
            net, inputs, tol=5e-5)


# sha256 of ``CausalAttention``'s traced program (value and gradients) at the
# six older language cells' shapes, as ``python tests/op_program_text.py``
# printed them on PR 48's tree: this repo's forward kernel, the relayout pass
# and the one-kernel backward pass in the text. (PR 48 put the forward kernel
# where JAX's splash kernel stood, which changed the text by design: that,
# and nothing else in these programs, moved the hashes PR 47 recorded.) A PR
# that changes what these nodes compute on purpose reads the new ones off
# this test's failure.
PROGRAMS = {
    "glm.attention": "1148df97bad7ff7d6fcefb091e832d8e7d5e1c5c87b7c6d456307b4a7eccc1ab",
    "lfm2.attention": "971a8014ee8c32432cb2f139c91e741f4d60040f7baef34c0f5b80cf5c836113",
    "ling.attention": "6ec278448f7fc611876e65036fad105388a0f792d64f61fbf0c57eaaca757825",
    "nemotron.attention": "2afd3bfcbb4ffddc5baf05bce42e9f5e2400a47a96cacf174656586933cfc442",
    "olmo.attention": "38348ca02a8a7067bf5d5bf1de4578a3b7a598a88b59ded2c806c7068c3dd840",
    "qwen3_next.attention": "5ba9657279055f9e444fad55f4c518525ffed26cdd6c3fefba726af28ea3abd4",
}


@pytest.fixture(scope="module")
def traced_programs():
    return program_hashes(sorted(PROGRAMS))


@pytest.mark.parametrize("node", sorted(PROGRAMS))
def test_the_older_cells_attention_traces_the_recorded_program(
        traced_programs, node):
    """Under ``window=0`` and ``rope_factor=1`` the six older cells'
    ``CausalAttention``, at their published shapes and 8,192 positions (the
    forward kernel, the relayout pass and the one-kernel backward pass in
    the text), trace the value and the gradients on record, to the
    character."""
    assert traced_programs[node] == PROGRAMS[node]
