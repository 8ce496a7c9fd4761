"""The fused step is the same program however it is reached.

A Pallas kernel reaches XLA as an opaque payload that carries its
operations' locations. With JAX's defaults those hold the file paths and
the names of the ten innermost Python frames, which reach past the jit
boundary into whoever called it: ``xprof._InstrumentedJit._compile`` with
telemetry on, ``FusedTrainStep._do`` with it off. JAX cannot strip what
sits inside a payload from the module it hashes for the persistent cache,
so the traced run of every cell whose step holds a kernel asked for a key
the untraced runs never wrote (PR 49). ``ops/pallas_kernels.py`` serializes
this repo's kernels without their locations (``_strip_locations``); here a
toy step whose attention takes the Pallas lowering is LOWERED for a TPU
(nothing compiled, nothing run) the way each path reaches it, and
``xprof.program_identity`` must not tell the lowerings apart.
"""
import base64
import json
import os
import re

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry, xprof
from mxnet_tpu.ops import pallas_kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, HEADS, DIM = 512, 2, 128      # whole tiles: the Pallas lowering


def _net():
    data = mx.sym.Variable("data")
    q, k, v = (mx.sym.FullyConnected(data, num_hidden=HEADS * DIM, name=n)
               for n in "qkv")
    net = mx.sym.CausalAttention(query=q, key=k, value=v, num_heads=HEADS,
                                 num_kv_heads=HEADS, head_dim=DIM,
                                 seq_len=T, name="attn")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="out")
    return mx.sym.SoftmaxOutput(net, name="softmax")


class _Lowered(Exception):
    """Ends the fit where the step would have been compiled."""


def _fit():
    x = np.random.RandomState(0).rand(T, 32).astype(np.float32)
    y = (np.arange(T) % 16).astype(np.float32)
    with pytest.raises(_Lowered):
        mx.mod.Module(_net()).fit(
            mx.io.NDArrayIter(x, y, batch_size=T), num_epoch=1,
            optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.1})


def _two_frames_deeper():
    def inner():
        _fit()
    inner()


def _lower_step(monkeypatch, on, fit=_fit, debug_info=False):
    """The text the fused step lowers to for a TPU, reached as ``fit``
    reaches it with telemetry ``on`` or off: through xprof's wrapper
    (``lower``) or the plain jit's call; ``debug_info``: with its
    locations."""
    texts = []
    real_jit = jax.jit

    def spy(fn, **kw):
        jfn = real_jit(fn, **kw)
        if getattr(fn, "__name__", "") != "step":
            return jfn

        class Spy:
            def lower(self, *args):
                texts.append(jfn.trace(*args).lower(
                    lowering_platforms=("tpu",)).as_text(
                        debug_info=debug_info))
                raise _Lowered()

            __call__ = lower

        return Spy()

    with monkeypatch.context() as m:
        m.setattr(jax, "jit", spy)
        m.setattr(xprof, "_override", None)   # telemetry's switch decides
        m.setenv("MXNET_TPU_FUSED_STEP", "1")
        telemetry.reset()
        (telemetry.enable if on else telemetry.disable)()
        try:
            fit()
        finally:
            telemetry.reset()
            telemetry.disable()
    (text,) = texts
    return text


def _payloads(text):
    """Each kernel's serialized Mosaic module, as bytes."""
    found = []
    for config in xprof._PAYLOAD_RE.findall(text.encode()):
        config = json.loads(config.decode().replace("\\22", '"')
                            .replace("\\\\", "\\"))
        found.append(base64.b64decode(config["custom_call_config"]["body"]))
    return found


@pytest.fixture(scope="module")
def lowerings():
    with pytest.MonkeyPatch.context() as m:
        return {"off": _lower_step(m, on=False),
                "on": _lower_step(m, on=True),
                "off, deeper": _lower_step(m, on=False,
                                           fit=_two_frames_deeper)}


def test_one_identity_with_telemetry_on_and_off_and_from_any_depth(
        lowerings):
    identities = {how: xprof.program_identity(text)
                  for how, text in lowerings.items()}
    module_sha, kernels = identities["off"]
    names = [name for name, _ in kernels]
    # the relayout passes and this repo's two attention kernels
    assert len(kernels) == 10 and {"causal_attention_forward",
                                   "causal_attention_backward"} <= set(names)
    for how, identity in identities.items():
        assert identity == (module_sha, kernels), how
        assert xprof.diff_builds(
            dict(zip(("module_sha", "kernels"), identities["off"])),
            dict(zip(("module_sha", "kernels"), identity))) is None
    assert len(set(lowerings.values())) == 1      # to the byte


def test_no_payload_holds_a_frame_or_a_path(lowerings):
    frames = re.compile(rb"xprof|fused_step|executor|_compile|__call__"
                        rb"|\.py|" + re.escape(REPO.encode()))
    for how, text in lowerings.items():
        payloads = _payloads(text)
        assert len(payloads) == 10
        for i, payload in enumerate(payloads):
            assert not frames.search(payload), (how, i,
                                                frames.search(payload))
    assert REPO not in lowerings["on"]


def test_nothing_but_the_kernels_loses_its_locations(monkeypatch):
    """What the device trace's readers go by (``tf_op``: the phase and the
    graph node an op was traced under) is in the locations still, and so
    are the frames of everything outside a payload (JAX leaves those out
    of the cache's key itself): no option of JAX's was touched."""
    text = _lower_step(monkeypatch, on=False, debug_info=True)
    for scope in ("fwd/", "bwd/", "update/", "CausalAttention:attn",
                  "FullyConnected:out"):
        assert scope in text, scope
    assert "fused_step.py" in text and REPO in text
    assert jax.config.jax_traceback_in_locations_limit == 10
    assert not any(b".py" in payload for payload in _payloads(text))


def _serializer():
    from jax._src import tpu_custom_call

    return tpu_custom_call


def test_diff_builds_finds_the_unstripped_kernels_by_itself(monkeypatch):
    """With JAX's serializer as it comes (the parent's program) the two
    paths' records differ, and ``diff_builds`` says where: in the payloads
    of the kernels traced inside the backward pass, whose frames run out
    past the jit into ``xprof.py`` on one path and ``fused_step.py`` on
    the other."""
    pallas_kernels._strip_locations()
    monkeypatch.setattr(
        _serializer(), "_lower_mosaic_module_to_asm",
        _serializer()._lower_mosaic_module_to_asm.__wrapped__)
    off, on = (xprof.program_identity(_lower_step(monkeypatch, on=how))
               for how in (False, True))
    told = xprof.diff_builds(dict(module_sha=off[0], kernels=off[1]),
                             dict(module_sha=on[0], kernels=on[1]))
    assert told is not None and "module text" not in told
    assert "kernel causal_attention_backward (#5): payload" in told
    assert "causal_attention_forward" not in told   # its frames end inside


def test_a_kernel_without_a_name_keeps_its_locations(monkeypatch):
    """Only what ``pallas_call`` was given a name for is stripped: a
    kernel of the user's own (``Rtc``, or ``pl.pallas_call`` itself) goes
    to XLA as JAX writes it."""
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jax.ShapeDtypeStruct((8, 128), np.float32)

    def lowered(call):
        return jax.jit(call).trace(x).lower(
            lowering_platforms=("tpu",)).as_text()

    ours, = _payloads(lowered(lambda a: pallas_kernels.pallas_call(
        double, a, out_shape=x, name="double_rows")))
    users, = _payloads(lowered(lambda a: pl.pallas_call(
        double, out_shape=x, name="double")(a)))
    unnamed, = _payloads(lowered(lambda a: pallas_kernels.pallas_call(
        double, a, out_shape=x)))
    assert b".py" not in ours
    assert b"test_build_identity.py" in users
    assert b"test_build_identity.py" in unnamed


def test_jax_s_own_variable_keeps_the_frames(monkeypatch):
    """A kernel's author asks JAX for frames in JAX's own way, and the
    serializer is then left alone."""
    def jax_s(module, **kw):
        raise AssertionError("not called here")

    monkeypatch.setattr(_serializer(), "_lower_mosaic_module_to_asm", jax_s)
    monkeypatch.setenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "10")
    pallas_kernels._strip_locations.__wrapped__()
    assert _serializer()._lower_mosaic_module_to_asm is jax_s
    monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT")
    pallas_kernels._strip_locations.__wrapped__()
    assert _serializer()._lower_mosaic_module_to_asm.__wrapped__ is jax_s
