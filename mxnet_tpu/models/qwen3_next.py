"""Qwen3-Next: a language model whose blocks are a mixer and a layer of
routed experts behind pre-RMSNorms, the mixer chosen by ``layer_types``
(three layers of the gated delta rule to one of gated softmax attention),
EVERY feed-forward 512 small routed experts chosen 10 a token by a softmax
router beside one gated shared expert (Qwen/Qwen3-Next-80B-A3B-Instruct,
``model_type: qwen3_next``; the defaults below are that model's published
sizes). ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; no bias
anywhere. The family's RMSNorm is zero-centred (``x / rms(x) * (1 + w)``):
with ``gamma = 1 + w`` it is ``RMSNorm`` as it stands.

**``linear_attention``** (Gated DeltaNet, arXiv:2412.06464; ``ops/seq.py``
``GatedDeltaRule`` with ``num_key_heads``): ``key_heads`` query/key heads of
``linear_key_dim`` under ``value_heads`` value heads of ``linear_value_dim``,
value head ``j`` reading key head ``j // (value_heads / key_heads)`` with its
own decay and step gate. ``q~, k~, v = silu(conv(W u))`` (depthwise causal
convolutions of ``conv_kernel``); the op normalises ``q~`` and ``k~`` a key
head; ``beta = sigmoid(W_b u)``, ``g = -exp(A_log) softplus(W_a u +
dt_bias)`` a value head; ``Mixer = W_o (RMSNorm(o) * gamma * silu(W_z u))``,
the norm a value head under ONE gamma of its width, before the gate. The
published fused ``in_proj_qkvz`` / ``in_proj_ba`` are their rows as separate
``FullyConnected`` nodes (``_q``, ``_k``, ``_v``, ``_g``; ``_b``, ``_a``).

**``full_attention``** (gated attention, Qiu et al., arXiv:2505.06708):
``[q; gate]_h = (W_q u)_h`` (``2 head_dim`` a head, ONE projection), ``k``,
``v`` ``kv_heads`` heads; RMSNorm over each head's ``head_dim`` on q and k
(one gamma each); rotary over ``rotary_dim`` columns of the head
(``CausalAttention(rotary_dim=...)``: the last ones); causal softmax,
``heads / kv_heads`` query heads a key/value head; ``a * sigmoid(gate)``
elementwise; ``W_o``.

**Experts** (``ops/moe.py`` ``RoutedExperts(score_func="softmax")``):
``experts_held`` of ``experts_total`` from ``first_expert`` on, one chip's
share of an expert-parallel layout; the router scores all of them,
``softmax`` over the whole width, weights the chosen probabilities over
their sum, no scale, no selection bias; ``aux_loss_coef`` weighs the
load-balancing loss whose gradient the op's backward pass adds to the
router's. Beside the op, plain nodes: ``sigmoid(w_sg . u) *
SharedExpert(u)``, the gate one ``hidden -> 1`` projection.

Layout as ``nemotron_h.py``: activations ``[batch * seq_len, hidden]``,
``data`` int32 ids ``[batch, seq_len]``.
"""
from .. import symbol as sym

__all__ = ["get_qwen3_next"]

LAYER_TYPES = tuple("full_attention" if (i + 1) % 4 == 0
                    else "linear_attention" for i in range(48))


def _fc(x, width, name):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name)


def _linear_attention(x, name, seq_len, key_heads, value_heads, key_dim,
                      value_dim, kernel, chunk, hidden, eps):
    def conv(width, part):
        y = _fc(x, width, "%s_%s" % (name, part))
        y = sym.CausalConv1D(data=y, kernel=kernel, seq_len=seq_len,
                             no_bias=True, name="%s_%sconv" % (name, part))
        return sym.Activation(data=y, act_type="silu",
                              name="%s_%sconv_act" % (name, part))

    o = sym.GatedDeltaRule(
        query=conv(key_heads * key_dim, "q"),
        key=conv(key_heads * key_dim, "k"),
        value=conv(value_heads * value_dim, "v"),
        a=_fc(x, value_heads, name + "_a"),
        b=_fc(x, value_heads, name + "_b"), num_heads=value_heads,
        num_key_heads=key_heads, key_dim=key_dim, value_dim=value_dim,
        chunk=chunk, seq_len=seq_len, name=name + "_delta")
    # a value head's norm under ONE gamma of its width, the gate after
    o = sym.RMSNorm(data=o, gate=_fc(x, value_heads * value_dim, name + "_g"),
                    gated=True, gate_after=True, num_groups=value_heads,
                    shared_gamma=True, eps=eps, name=name + "_gnorm")
    return _fc(o, hidden, name + "_o")


def _full_attention(x, name, seq_len, heads, kv_heads, head_dim, rotary_dim,
                    rope_theta, hidden, eps):
    # [q; gate] a head from ONE projection
    qg = sym.Reshape(data=_fc(x, heads * 2 * head_dim, name + "_q"),
                     shape=(-1, heads, 2 * head_dim), name=name + "_q_heads")
    q = sym.Reshape(
        data=sym.slice_axis(data=qg, axis=2, begin=0, end=head_dim,
                            name=name + "_q_query"),
        shape=(-1, heads * head_dim), name=name + "_q_rows")
    gate = sym.Reshape(
        data=sym.slice_axis(data=qg, axis=2, begin=head_dim,
                            end=2 * head_dim, name=name + "_q_gate"),
        shape=(-1, heads * head_dim), name=name + "_q_gate_rows")
    q = sym.RMSNorm(data=q, num_groups=heads, shared_gamma=True, eps=eps,
                    name=name + "_qnorm")
    k = sym.RMSNorm(data=_fc(x, kv_heads * head_dim, name + "_k"),
                    num_groups=kv_heads, shared_gamma=True, eps=eps,
                    name=name + "_knorm")
    a = sym.CausalAttention(
        query=q, key=k, value=_fc(x, kv_heads * head_dim, name + "_v"),
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        seq_len=seq_len, rotary=True, rope_theta=rope_theta,
        rotary_dim=rotary_dim, name=name + "_attn")
    a = sym._Mul(lhs=a, rhs=sym.Activation(data=gate, act_type="sigmoid",
                                           name=name + "_q_gate_act"),
                 name=name + "_gated")
    return _fc(a, hidden, name + "_o")


def _gated(x, width, hidden, name):
    """``W_down (silu(W_gate x) * W_up x)`` as plain nodes."""
    gate = sym.Activation(data=_fc(x, width, name + "_gate"),
                          act_type="silu", name=name + "_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, width, name + "_up"),
                        name=name + "_mul"), hidden, name + "_down")


def get_qwen3_next(layer_types=LAYER_TYPES, hidden=2048, vocab=151936,
                   heads=16, kv_heads=2, head_dim=256, rotary_dim=64,
                   rope_theta=10000000.0, linear_key_heads=16,
                   linear_value_heads=32, linear_key_dim=128,
                   linear_value_dim=128, conv_kernel=4, experts_total=512,
                   experts_held=512, first_expert=0, top_k=10,
                   expert_hidden=512, shared_hidden=512, aux_loss_coef=0.001,
                   eps=1e-6, seq_len=8192, chunk=64):
    """Next-token language model: Embedding, the blocks of ``layer_types``
    (``"linear_attention"`` / ``"full_attention"``), a final RMSNorm, an
    untied head over ``vocab`` and ``SoftmaxOutput`` (its gradient the mean
    over tokens). Layer i's parameters are named ``layer<i>_*``: a
    delta-rule mixer's ``_q``, ``_k``, ``_v`` with ``_qconv``, ``_kconv``,
    ``_vconv``, ``_a``, ``_b``, ``_delta_A_log``, ``_delta_dt_bias``, ``_g``,
    ``_gnorm``, ``_o``; an attention mixer's ``_q`` (query and gate),
    ``_qnorm``, ``_k``, ``_knorm``, ``_v``, ``_o``; each behind
    ``_mixer_norm``; the expert layer's ``_ffn_experts_*``, ``_ffn_shared_*``
    and ``_ffn_sgate`` (the shared expert's scalar gate) behind
    ``_ffn_norm``. ``aux_loss_coef``: the weight of each layer's
    load-balancing loss in the step's gradient
    (``ops/moe.py``; 0: none, and the experts' traced program is the
    sigmoid-routed models' but for the scores)."""
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, kind in enumerate(layer_types):
        name = "layer%d" % i
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_mixer_norm")
        if kind == "linear_attention":
            out = _linear_attention(n, name, seq_len, linear_key_heads,
                                    linear_value_heads, linear_key_dim,
                                    linear_value_dim, conv_kernel, chunk,
                                    hidden, eps)
        elif kind == "full_attention":
            out = _full_attention(n, name, seq_len, heads, kv_heads,
                                  head_dim, rotary_dim, rope_theta, hidden,
                                  eps)
        else:
            raise ValueError("get_qwen3_next: layer %d is %r, not "
                             "'linear_attention' or 'full_attention'"
                             % (i, kind))
        x = sym._Plus(lhs=x, rhs=out, name=name + "_mixer_add")
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_ffn_norm")
        out = sym.RoutedExperts(
            data=n, num_experts=experts_total, num_held=experts_held,
            first_held=first_expert, top_k=top_k, num_hidden=expert_hidden,
            gated=True, score_func="softmax", aux_loss_coef=aux_loss_coef,
            name=name + "_ffn_experts")
        if shared_hidden:
            shared_gate = sym.Activation(
                data=_fc(n, 1, name + "_ffn_sgate"), act_type="sigmoid",
                name=name + "_ffn_sgate_act")
            out = sym._Plus(
                lhs=out, rhs=sym.broadcast_mul(
                    lhs=_gated(n, shared_hidden, hidden,
                               name + "_ffn_shared"),
                    rhs=shared_gate, name=name + "_ffn_shared_gated"),
                name=name + "_ffn_sum")
        x = sym._Plus(lhs=x, rhs=out, name=name + "_ffn_add")
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = _fc(x, vocab, "lm_head")
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
