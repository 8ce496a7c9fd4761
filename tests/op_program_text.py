"""The traced programs (``jax.make_jaxpr`` of the value and the gradients) of
``GatedDeltaRule``, ``RoutedExperts`` and ``CausalAttention`` at the shapes of the benchmark's
older language cells, each node as its model's factory builds it (no
argument this repo added after them is given), as sha256 of the text with
the addresses in it taken out. ``tests/test_qwen3_next.py`` holds them
against the hashes this file gave on the parent of the PR that added
``num_key_heads``, ``score_func`` and ``aux_loss_coef``: under those
arguments' defaults the older cells' operators trace the program they did,
to the character; ``tests/test_attention_window.py`` holds the attentions
against what this file gave on the tree of the PR that last changed their
text on purpose (48: the forward pass a kernel of this repo's). Run it
against another tree to read that tree's::

    PYTHONPATH=<tree> python tests/op_program_text.py
"""
import hashlib
import json
import re

import jax
import jax.numpy as jnp

# the cells' own nodes: operator, its arguments, its inputs' shapes
# (``benchmark/configs/*.json``: one packed sequence of 8,192 positions)
ROWS = 8192
NODES = {
    "olmo.delta": ("GatedDeltaRule", dict(
        num_heads=15, key_dim=96, value_dim=192, chunk=64, seq_len=ROWS,
        neg_eigval=True), None),
    "ling.delta": ("GatedDeltaRule", dict(
        num_heads=32, key_dim=128, value_dim=128, chunk=64, seq_len=ROWS,
        gate_floor=-5.0), 32 * 128),
    "nemotron.experts": ("RoutedExperts", dict(
        num_experts=128, num_held=8, first_held=0, top_k=6, scale=2.5,
        num_hidden=1856, bias_update_rate=0.001), 2688),
    "glm.experts": ("RoutedExperts", dict(
        num_experts=64, num_held=8, first_held=0, top_k=4, scale=1.8,
        num_hidden=1536, gated=True, bias_update_rate=0.001), 2048),
    "lfm2.experts": ("RoutedExperts", dict(
        num_experts=64, num_held=8, first_held=0, top_k=4, scale=1.0,
        num_hidden=1792, gated=True, norm_eps=1e-6,
        bias_update_rate=0.001), 2048),
    "ling.experts": ("RoutedExperts", dict(
        num_experts=512, num_held=8, first_held=0, top_k=8, scale=2.5,
        num_hidden=768, gated=True, n_group=8, topk_group=4,
        bias_update_rate=0.01), 2560),
    # every older language cell's ``CausalAttention`` (the hint: the query's
    # width), held by ``tests/test_laguna.py`` against the parent of the PR
    # that added ``window`` and the scaled rotary frequencies
    "nemotron.attention": ("CausalAttention", dict(
        num_heads=32, num_kv_heads=2, head_dim=128, seq_len=ROWS,
        rope_theta=10000.0), 32 * 128),
    "olmo.attention": ("CausalAttention", dict(
        num_heads=15, num_kv_heads=15, head_dim=128, seq_len=ROWS,
        rotary=False), 15 * 128),
    "glm.attention": ("CausalAttention", dict(
        num_heads=20, num_kv_heads=20, head_dim=256, seq_len=ROWS,
        rope_theta=1000000.0, rotary_dim=64), 20 * 256),
    "lfm2.attention": ("CausalAttention", dict(
        num_heads=32, num_kv_heads=8, head_dim=64, seq_len=ROWS,
        rope_theta=1000000.0), 32 * 64),
    "ling.attention": ("CausalAttention", dict(
        num_heads=32, num_kv_heads=32, head_dim=192, seq_len=ROWS,
        rope_theta=6000000.0, rotary_dim=64, value_dim=128), 32 * 192),
    "qwen3_next.attention": ("CausalAttention", dict(
        num_heads=16, num_kv_heads=2, head_dim=256, seq_len=ROWS,
        rope_theta=10000000.0, rotary_dim=64), 16 * 256),
}


def _inputs(op, hint):
    """Abstract inputs and auxiliary states of ``op`` from its own shape
    inference: bfloat16 where the executor casts, the rest as declared."""
    if type(op).op_name == "GatedDeltaRule":
        h = op.num_heads
        known = [(ROWS, h * op.key_dim), None, None,
                 (ROWS, hint) if hint else None, None, None, None]
    else:
        known = [(ROWS, hint)] + [None] * (len(op.list_arguments()) - 1)
    shapes, _, aux = op.infer_shape(known)
    whole = set(getattr(op, "full_precision_args", ()))
    args = [jax.ShapeDtypeStruct(s, jnp.float32 if n in whole
                                 else jnp.bfloat16)
            for n, s in zip(op.list_arguments(), shapes)]
    _, _, aux_types = op.infer_type([a.dtype for a in args])
    return args, [jax.ShapeDtypeStruct(s, t) for s, t in zip(aux, aux_types)]


def program_text(name):
    from mxnet_tpu.ops.registry import OpContext, create_operator

    kind, params, hint = NODES[name]
    op = create_operator(kind, **params)
    args, aux = _inputs(op, hint)

    def value(args, aux):
        outs, states = op.apply(OpContext(True), list(args), list(aux))
        return outs[0].astype(jnp.float32).sum(), states

    # the matmul precision is in the text: the one tests/conftest.py sets
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(
            lambda a, x: jax.value_and_grad(value, has_aux=True)(a, x))(
                args, aux))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


def program_hashes(names=None):
    return {name: hashlib.sha256(program_text(name).encode()).hexdigest()
            for name in sorted(names or NODES)}


if __name__ == "__main__":
    print(json.dumps(program_hashes(), indent=1))
