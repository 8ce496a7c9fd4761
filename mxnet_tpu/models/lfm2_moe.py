"""LFM2-MoE: a language model whose every block is a mixer and a feed-forward
behind pre-RMSNorms, the mixer chosen by ``layer_types`` (three gated short
convolutions to one grouped-head attention), the first ``dense_layers``
feed-forwards dense and gated, the rest routed gated experts with NO shared
one, the head tied to the embedding (LiquidAI/LFM2-24B-A2B, ``model_type:
lfm2_moe``; the defaults below are that model's published sizes). ``h = x +
Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; no bias anywhere.

**The gated short convolution** (``"conv"``): ``[B; C; z] = W_in u`` (three
equal chunks in that order), ``Op = W_out (C * conv(B * z))``, ``conv`` a
depthwise causal convolution of kernel ``conv_kernel`` over the ``hidden``
channels, no bias, no activation (``ops/seq.py`` ``GatedShortConv`` between
the two ``FullyConnected`` nodes). Its state, like the scans', runs across
the documents of a packed sequence.

**Attention** (``"full_attention"``): ``heads`` query heads over ``kv_heads``
key/value heads at ``head_dim`` (the published 64: half a lane tile,
``ops/attention.py``), each head's query and key through an RMSNorm over its
own columns under ONE gamma of a head's width for all query heads and one
for all key heads, rotary positions over the whole head.

Layout as ``nemotron_h.py``: activations ``[batch * seq_len, hidden]``,
``data`` int32 ids ``[batch, seq_len]``. An expert layer holds
``experts_held`` of ``experts_total`` routed experts from ``first_expert``
on: one chip's share of an expert-parallel layout (``ops/moe.py``), and
with no shared expert that share's part is the layer's whole result here;
the mixers and the dense layer are whole.
"""
from .. import symbol as sym

__all__ = ["get_lfm2_moe"]

LAYER_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                  "conv") * 9 + ("full_attention", "conv")


def _fc(x, width, name, **kw):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name, **kw)


def _short_conv(x, name, seq_len, hidden, kernel):
    y = sym.GatedShortConv(data=_fc(x, 3 * hidden, name + "_conv_in"),
                           kernel=kernel, seq_len=seq_len,
                           name=name + "_conv")
    return _fc(y, hidden, name + "_conv_out")


def _attention(x, name, seq_len, heads, kv_heads, head_dim, rope_theta,
               hidden, eps):
    def normed(part, count):
        # a head's norm: every head on its own columns, ONE gamma of a
        # head's width
        return sym.RMSNorm(data=_fc(x, count * head_dim, name + "_" + part),
                           num_groups=count, shared_gamma=True, eps=eps,
                           name="%s_%snorm" % (name, part))

    a = sym.CausalAttention(
        query=normed("q", heads), key=normed("k", kv_heads),
        value=_fc(x, kv_heads * head_dim, name + "_v"), num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, seq_len=seq_len,
        rotary=True, rope_theta=rope_theta, name=name + "_attn")
    return _fc(a, hidden, name + "_o")


def _gated(x, width, hidden, name):
    """``W_down (silu(W_gate x) * W_up x)`` as plain nodes."""
    gate = sym.Activation(data=_fc(x, width, name + "_gate"),
                          act_type="silu", name=name + "_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, width, name + "_up"),
                        name=name + "_mul"), hidden, name + "_down")


def get_lfm2_moe(layer_types=LAYER_TYPES, dense_layers=2, hidden=2048,
                 vocab=65536, heads=32, kv_heads=8, head_dim=64,
                 conv_kernel=3, dense_hidden=11776, experts_total=64,
                 experts_held=64, first_expert=0, top_k=4, routed_scale=1.0,
                 expert_hidden=1536, rope_theta=1000000.0, eps=1e-5,
                 seq_len=8192, bias_update_rate=0.0, tie_head=True):
    """Next-token language model: Embedding, the blocks of ``layer_types``,
    a final RMSNorm, the head over ``vocab`` and ``SoftmaxOutput`` (its
    gradient the mean over tokens). With ``tie_head`` the head reads the
    embedding's own parameter ``embed_weight`` (``[vocab, hidden]`` in both
    uses; its gradient is the sum of both), else ``lm_head_weight``. Layer
    i's parameters are named ``layer<i>_*``: a conv mixer's ``_conv_in``,
    ``_conv``, ``_conv_out``, an attention's ``_q``, ``_qnorm``, ``_k``,
    ``_knorm``, ``_v``, ``_o``, each behind ``_operator_norm``; the
    feed-forward's ``_ffn_*`` behind ``_ffn_norm`` (an expert layer's
    ``_ffn_experts_*``). ``bias_update_rate``: what a training step moves
    the experts' selection biases by against their loads (``ops/moe.py``)."""
    if not 0 <= dense_layers <= len(layer_types):
        raise ValueError("get_lfm2_moe: %d dense layers of %d"
                         % (dense_layers, len(layer_types)))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    table = sym.Variable("embed_weight")
    x = sym.Embedding(data=data, weight=table, input_dim=vocab,
                      output_dim=hidden, name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, kind in enumerate(layer_types):
        name = "layer%d" % i
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_operator_norm")
        if kind == "conv":
            out = _short_conv(n, name, seq_len, hidden, conv_kernel)
        elif kind == "full_attention":
            out = _attention(n, name, seq_len, heads, kv_heads, head_dim,
                             rope_theta, hidden, eps)
        else:
            raise ValueError("get_lfm2_moe: layer %d is %r, not 'conv' or "
                             "'full_attention'" % (i, kind))
        x = sym._Plus(lhs=x, rhs=out, name=name + "_operator_add")
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_ffn_norm")
        if i < dense_layers:
            out = _gated(n, dense_hidden, hidden, name + "_ffn")
        else:
            # no shared expert: the held experts' part is the layer's result
            out = sym.RoutedExperts(
                data=n, num_experts=experts_total, num_held=experts_held,
                first_held=first_expert, top_k=top_k, scale=routed_scale,
                num_hidden=expert_hidden, gated=True, norm_eps=1e-6,
                bias_update_rate=bias_update_rate,
                name=name + "_ffn_experts")
        x = sym._Plus(lhs=x, rhs=out, name=name + "_ffn_add")
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = _fc(x, vocab, "lm_head", **({"weight": table} if tie_head
                                         else {}))
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
