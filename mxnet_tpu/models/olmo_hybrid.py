"""Olmo-Hybrid: a language model whose blocks are a mixer and a dense
gated feed-forward, each followed (not preceded) by an RMSNorm before its
residual add, the mixer chosen by ``layer_types``: ``linear_attention``
the gated delta rule (``ops/seq.py`` ``GatedDeltaRule``), ``full_attention``
causal softmax attention over normalised queries and keys, no rotary
(allenai/Olmo-Hybrid-7B, ``model_type: olmo_hybrid``; the defaults below
are that model's published sizes, three linear layers to one full).

``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(W_down (silu(W_gate h) *
W_up h))``; no bias anywhere. Layout as ``nemotron_h.py``: activations are
``[batch * seq_len, hidden]``, ``data`` int32 ids ``[batch, seq_len]``.

A mixer holds ``heads_held`` of the model's ``heads`` from ``first_head``
on, one chip's share of a layout that divides each layer's heads over
chips: its projections are built at the held heads' width, so nothing here
stands in for the other chips, and what their heads would add to the
output projection's sum is left out. The full-attention layer's query/key
norm takes its mean square over the columns held. The feed-forward, the
norms and the head over the vocabulary are whole.
"""
from .. import symbol as sym

__all__ = ["get_olmo_hybrid"]

LAYER_TYPES = ("linear_attention", "linear_attention", "linear_attention",
               "full_attention")


def _fc(x, width, name):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name)


def _linear_attention(x, name, seq_len, heads, key_dim, value_dim, kernel,
                      chunk, neg_eigval, hidden, eps):
    def conv(width, part):
        y = _fc(x, width, "%s_%s" % (name, part))
        y = sym.CausalConv1D(data=y, kernel=kernel, seq_len=seq_len,
                             no_bias=True, name="%s_%sconv" % (name, part))
        return sym.Activation(data=y, act_type="silu",
                              name="%s_%sconv_act" % (name, part))

    o = sym.GatedDeltaRule(
        query=conv(heads * key_dim, "q"), key=conv(heads * key_dim, "k"),
        value=conv(heads * value_dim, "v"), a=_fc(x, heads, name + "_a"),
        b=_fc(x, heads, name + "_b"), num_heads=heads, key_dim=key_dim,
        value_dim=value_dim, chunk=chunk, seq_len=seq_len,
        neg_eigval=neg_eigval, name=name + "_delta")
    # a head's norm under ONE gamma of a head's width, the gate after
    o = sym.RMSNorm(data=o, gate=_fc(x, heads * value_dim, name + "_g"),
                    gated=True, gate_after=True, num_groups=heads,
                    shared_gamma=True, eps=eps, name=name + "_gnorm")
    return _fc(o, hidden, name + "_o")


def _full_attention(x, name, seq_len, heads, head_dim, hidden, eps):
    width = heads * head_dim
    q = sym.RMSNorm(data=_fc(x, width, name + "_q"), eps=eps,
                    name=name + "_qnorm")
    k = sym.RMSNorm(data=_fc(x, width, name + "_k"), eps=eps,
                    name=name + "_knorm")
    a = sym.CausalAttention(query=q, key=k, value=_fc(x, width, name + "_v"),
                            num_heads=heads, num_kv_heads=heads,
                            head_dim=head_dim, seq_len=seq_len, rotary=False,
                            name=name + "_attn")
    return _fc(a, hidden, name + "_o")


def _feed_forward(x, name, ffn_hidden, hidden):
    gate = sym.Activation(data=_fc(x, ffn_hidden, name + "_ffn_gate"),
                          act_type="silu", name=name + "_ffn_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, ffn_hidden, name + "_ffn_up"),
                        name=name + "_ffn_mul"),
               hidden, name + "_ffn_down")


def get_olmo_hybrid(layer_types=LAYER_TYPES * 8, hidden=3840, vocab=100352,
                    heads=30, heads_held=30, first_head=0, head_dim=128,
                    linear_key_dim=96, linear_value_dim=192, conv_kernel=4,
                    ffn_hidden=11008, eps=1e-6, seq_len=8192, chunk=64,
                    neg_eigval=True):
    """Next-token language model: Embedding, the blocks of ``layer_types``,
    a final RMSNorm, an untied head over ``vocab`` and ``SoftmaxOutput``
    (its gradient the mean over tokens). Layer i's parameters are named
    ``layer<i>_*``. ``first_head`` names which of the ``heads`` the
    ``heads_held`` are: the symbol is the same for every share, the
    weights differ."""
    if not (0 <= first_head and 0 < heads_held
            and first_head + heads_held <= heads):
        raise ValueError("get_olmo_hybrid: heads %d..%d of %d"
                         % (first_head, first_head + heads_held, heads))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, kind in enumerate(layer_types):
        name = "layer%d" % i
        if kind == "linear_attention":
            out = _linear_attention(x, name, seq_len, heads_held,
                                    linear_key_dim, linear_value_dim,
                                    conv_kernel, chunk, neg_eigval, hidden,
                                    eps)
        elif kind == "full_attention":
            out = _full_attention(x, name, seq_len, heads_held, head_dim,
                                  hidden, eps)
        else:
            raise ValueError("get_olmo_hybrid: layer %d is %r, not "
                             "'linear_attention' or 'full_attention'"
                             % (i, kind))
        out = sym.RMSNorm(data=out, eps=eps, name=name + "_mixer_norm")
        x = sym._Plus(lhs=x, rhs=out, name=name + "_mixer_add")
        out = sym.RMSNorm(data=_feed_forward(x, name, ffn_hidden, hidden),
                          eps=eps, name=name + "_ffn_norm")
        x = sym._Plus(lhs=x, rhs=out, name=name + "_ffn_add")
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = _fc(x, vocab, "lm_head")
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
