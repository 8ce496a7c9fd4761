"""Laguna: a language model whose blocks are softmax attention and a
feed-forward behind pre-RMSNorms, the attention SLIDING-WINDOW three layers
in four and full in the fourth, with MORE query heads on the windowed layers
than on the full ones over the same key/value heads, a sigmoid gate a query
head on the attention's result, rotary scaled by length (YaRN) over half of
the full layers' head and plain over all of the windowed layers', a dense
SwiGLU in layer 0 and 256 small sigmoid-routed experts, 8 a token, beside one
shared expert in every other layer (poolside/Laguna-XS.2, ``model_type:
laguna``; the defaults below are that model's published sizes). ``h = x +
Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; no bias anywhere.

**Attention** at layer ``l`` (``ops/attention.py`` ``CausalAttention``):
``heads_per_layer[l]`` query heads of ``head_dim`` over ``kv_heads``
key/value heads (48 and 64 over 8: groups of 6 and of 8). A
``"full_attention"`` layer reads every earlier key, with rotary over the
LAST ``full_rotary_dim`` columns of the head at ``full_rope_theta``, the
frequencies YaRN's (``yarn_factor`` over ``yarn_original_positions``,
``yarn_beta_fast`` / ``yarn_beta_slow``) and cos and sin times
``yarn_attention_factor``; a ``"sliding_attention"`` layer reads the last
``window`` keys, its own among them, with plain rotary over the whole head at
``window_rope_theta``. ``g = sigmoid(W_g u)``, one number a query head
(``attn_gate="head"``), multiplies that head's result before ``W_o``;
``"elementwise"`` is a gate a column (Qwen3-Next's form, a ``W_g`` ``head_dim``
times as tall), ``"none"`` no gate.

**Feed-forward** by ``mlp_layer_types``: ``"dense"`` is ``W_down (silu(W_gate
x) * W_up x)`` at ``dense_hidden``; ``"sparse"`` is ``ops/moe.py``
``RoutedExperts``: ``experts_held`` of ``experts_total`` from
``first_expert`` on, one chip's share of an expert-parallel layout; the
router scores all of them (``score_func``: ``"sigmoid"``), the ``top_k``
largest, weights the chosen scores over their sum times ``routed_scale``, no
auxiliary loss; with ``bias_update_rate`` > 0 the choice is by the scores plus
the op's selection bias, a state a training step moves against each expert's
load (the DeepSeek line's balancing; the published config, an inference one,
names none: 0, the default, leaves the state at 0). Beside the op one ungated
shared expert at ``shared_hidden`` as plain nodes.

Layout as ``nemotron_h.py``: activations ``[batch * seq_len, hidden]``,
``data`` int32 ids ``[batch, seq_len]``.
"""
from .. import symbol as sym

__all__ = ["get_laguna"]

LAYER_TYPES = tuple("full_attention" if i % 4 == 0 else "sliding_attention"
                    for i in range(40))
MLP_LAYER_TYPES = ("dense",) + ("sparse",) * 39
_HEADS = {"full_attention": 48, "sliding_attention": 64}
HEADS_PER_LAYER = tuple(_HEADS[kind] for kind in LAYER_TYPES)


def _fc(x, width, name):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name)


def _gated(x, width, hidden, name):
    """``W_down (silu(W_gate x) * W_up x)`` as plain nodes."""
    gate = sym.Activation(data=_fc(x, width, name + "_gate"),
                          act_type="silu", name=name + "_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, width, name + "_up"),
                        name=name + "_mul"), hidden, name + "_down")


def _attention(x, name, heads, kv_heads, head_dim, seq_len, hidden, attn_gate,
               **rotary_and_mask):
    a = sym.CausalAttention(
        query=_fc(x, heads * head_dim, name + "_q"),
        key=_fc(x, kv_heads * head_dim, name + "_k"),
        value=_fc(x, kv_heads * head_dim, name + "_v"),
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        seq_len=seq_len, rotary=True, name=name + "_attn",
        **rotary_and_mask)
    if attn_gate == "head":
        # one number a (row, head) over the head's columns
        gate = sym.Activation(data=_fc(x, heads, name + "_g"),
                              act_type="sigmoid", name=name + "_g_act")
        a = sym.Reshape(
            data=sym.broadcast_mul(
                lhs=sym.Reshape(data=a, shape=(-1, heads, head_dim),
                                name=name + "_attn_heads"),
                rhs=sym.Reshape(data=gate, shape=(-1, heads, 1),
                                name=name + "_g_heads"),
                name=name + "_gated"),
            shape=(-1, heads * head_dim), name=name + "_gated_rows")
    elif attn_gate == "elementwise":
        a = sym._Mul(lhs=a, rhs=sym.Activation(
            data=_fc(x, heads * head_dim, name + "_g"), act_type="sigmoid",
            name=name + "_g_act"), name=name + "_gated")
    elif attn_gate != "none":
        raise ValueError("get_laguna: attn_gate %r is not 'head', "
                         "'elementwise' or 'none'" % (attn_gate,))
    return _fc(a, hidden, name + "_o")


def get_laguna(layer_types=LAYER_TYPES, mlp_layer_types=None,
               heads_per_layer=None, hidden=2048, vocab=100352, kv_heads=8,
               head_dim=128, window=512, full_rotary_dim=64,
               full_rope_theta=500000.0, yarn_factor=64.0,
               yarn_original_positions=4096, yarn_beta_fast=64.0,
               yarn_beta_slow=1.0, yarn_attention_factor=1.4158883083359672,
               window_rope_theta=10000.0, attn_gate="head", dense_hidden=8192,
               experts_total=256, experts_held=256, first_expert=0, top_k=8,
               routed_scale=2.5, score_func="sigmoid", expert_hidden=512,
               shared_hidden=512, eps=1e-6, seq_len=8192,
               bias_update_rate=0.0):
    """Next-token language model: Embedding, the blocks of ``layer_types``
    (``"full_attention"`` / ``"sliding_attention"``) with the feed-forwards
    of ``mlp_layer_types`` (``"dense"`` / ``"sparse"``; ``None``: dense in
    layer 0, experts behind it) and ``heads_per_layer`` query heads (``None``:
    48 on full layers, 64 on windowed ones), a final RMSNorm, an untied head
    over ``vocab`` and ``SoftmaxOutput`` (its gradient the mean over
    tokens). Layer i's parameters are named ``layer<i>_*``: the mixer's
    ``_q``, ``_k``, ``_v``, ``_g`` (the gate), ``_o`` behind ``_mixer_norm``;
    the feed-forward's ``_ffn_gate``, ``_ffn_up``, ``_ffn_down`` (dense) or
    ``_ffn_experts_*`` and ``_ffn_shared_*`` behind ``_ffn_norm``."""
    layer_types = tuple(layer_types)
    if mlp_layer_types is None:
        mlp_layer_types = MLP_LAYER_TYPES[:len(layer_types)]
    if heads_per_layer is None:
        heads_per_layer = tuple(_HEADS.get(kind, 0) for kind in layer_types)
    if not len(layer_types) == len(mlp_layer_types) == len(heads_per_layer):
        raise ValueError("get_laguna: %d layer types, %d feed-forward types, "
                         "%d head counts" % (len(layer_types),
                                             len(mlp_layer_types),
                                             len(heads_per_layer)))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, (kind, ffn, heads) in enumerate(zip(layer_types, mlp_layer_types,
                                               heads_per_layer)):
        name = "layer%d" % i
        if kind == "full_attention":
            how = dict(rope_theta=full_rope_theta,
                       rotary_dim=full_rotary_dim,
                       rope_factor=yarn_factor,
                       rope_original_positions=yarn_original_positions,
                       rope_beta_fast=yarn_beta_fast,
                       rope_beta_slow=yarn_beta_slow,
                       rope_attention_factor=yarn_attention_factor)
        elif kind == "sliding_attention":
            how = dict(rope_theta=window_rope_theta, window=window)
        else:
            raise ValueError("get_laguna: layer %d is %r, not "
                             "'full_attention' or 'sliding_attention'"
                             % (i, kind))
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_mixer_norm")
        x = sym._Plus(lhs=x, rhs=_attention(
            n, name, heads, kv_heads, head_dim, seq_len, hidden, attn_gate,
            **how), name=name + "_mixer_add")
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_ffn_norm")
        if ffn == "dense":
            out = _gated(n, dense_hidden, hidden, name + "_ffn")
        elif ffn == "sparse":
            out = sym.RoutedExperts(
                data=n, num_experts=experts_total, num_held=experts_held,
                first_held=first_expert, top_k=top_k, scale=routed_scale,
                num_hidden=expert_hidden, gated=True, score_func=score_func,
                bias_update_rate=bias_update_rate,
                name=name + "_ffn_experts")
            if shared_hidden:
                out = sym._Plus(
                    lhs=out, rhs=_gated(n, shared_hidden, hidden,
                                        name + "_ffn_shared"),
                    name=name + "_ffn_sum")
        else:
            raise ValueError("get_laguna: layer %d's feed-forward is %r, not "
                             "'dense' or 'sparse'" % (i, ffn))
        x = sym._Plus(lhs=x, rhs=out, name=name + "_ffn_add")
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = _fc(x, vocab, "lm_head")
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
