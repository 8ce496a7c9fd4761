"""Bailing hybrid (Ling 3.0): a language model whose blocks are a mixer and
a feed-forward behind pre-RMSNorms, the mixer chosen by ``layer_types``
(five layers of Kimi Delta Attention to one of latent attention), the first
``dense_layers`` feed-forwards dense and gated, the rest routed gated
experts chosen inside a few GROUPS beside a shared one
(inclusionAI/Ling-3.0-flash, ``model_type: bailing_hybrid``; the defaults
below are that model's published sizes). ``h = x + Mixer(RMSNorm(x))``, ``y
= h + FFN(RMSNorm(h))``; no bias anywhere.

**``kda``** (Kimi Linear, arXiv:2510.26692): the gated delta rule with one
decay a KEY CHANNEL (``ops/seq.py`` ``GatedDeltaRule`` with ``a`` ``[rows,
H*K]``). ``q~, k~, v = silu(conv(W u))`` (depthwise causal convolutions of
``conv_kernel``), the op normalises ``q~`` and ``k~`` a head; the decay gate
``a = W_a u`` is ONE full-rank projection a channel wide, bounded by
``gate_floor`` (``g = gate_floor * sigmoid(exp(A_log) (a + dt_bias))``), the
step gate ``beta = sigmoid(W_b u)`` a head; ``Mixer = W_o (RMSNorm(o) *
sigmoid(W_g u))``: the norm in ``kda_norm_groups`` groups over a position's
``H*V`` columns under a gamma of that width, the gate elementwise AFTER the
norm.

**``latent_attention``** (DeepSeek-V2, arXiv:2405.04434, section 2.1) with
no query latent and values NARROWER than keys: ``[q_n; q_r]_h = (W_q u)_h``
(``nope_dim + rope_dim``); ``[c_kv; k_r] = W_kva u`` (``kv_rank +
rope_dim``), ``c = RMSNorm(c_kv)``, ``[k_n; val]_h = (W_kvb c)_h``
(``nope_dim + v_dim``); a head's key ``[k_n,h; k_r]``, ONE rotary key for
all heads; ``CausalAttention(head_dim=nope_dim + rope_dim, value_dim=v_dim,
rotary_dim=rope_dim)``; then ONE sigmoid gate a head on the result, ``a_h *
sigmoid((W_gate u)_h)`` (Qiu et al., arXiv:2505.06708), and ``W_o``.

**Experts** (``ops/moe.py`` ``RoutedExperts``): ``experts_held`` of
``experts_total`` from ``first_expert`` on, one chip's share of an
expert-parallel layout; the router scores all of them and the choice is
limited to ``topk_group`` of ``n_group`` groups of consecutive experts. The
published ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
(``swiglu_limits``) name a clamp whose FORM the config does not state: a
non-zero limit on a layer built here raises, none is guessed.

Layout as ``nemotron_h.py``: activations ``[batch * seq_len, hidden]``,
``data`` int32 ids ``[batch, seq_len]``.
"""
from .. import symbol as sym

__all__ = ["get_bailing_hybrid"]

LAYER_TYPES = tuple("latent_attention" if (i + 1) % 6 == 0 else "kda"
                    for i in range(42))


def _fc(x, width, name):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name)


def _kda(x, name, seq_len, heads, key_dim, value_dim, kernel, chunk,
         gate_floor, norm_groups, hidden, eps):
    def conv(width, part):
        y = _fc(x, width, "%s_%s" % (name, part))
        y = sym.CausalConv1D(data=y, kernel=kernel, seq_len=seq_len,
                             no_bias=True, name="%s_%sconv" % (name, part))
        return sym.Activation(data=y, act_type="silu",
                              name="%s_%sconv_act" % (name, part))

    o = sym.GatedDeltaRule(
        query=conv(heads * key_dim, "q"), key=conv(heads * key_dim, "k"),
        value=conv(heads * value_dim, "v"),
        a=_fc(x, heads * key_dim, name + "_a"), b=_fc(x, heads, name + "_b"),
        num_heads=heads, key_dim=key_dim, value_dim=value_dim, chunk=chunk,
        seq_len=seq_len, gate_floor=gate_floor, name=name + "_delta")
    o = sym.RMSNorm(data=o, num_groups=norm_groups, eps=eps,
                    name=name + "_gnorm")
    gate = sym.Activation(data=_fc(x, heads * value_dim, name + "_g"),
                          act_type="sigmoid", name=name + "_g_act")
    return _fc(sym._Mul(lhs=o, rhs=gate, name=name + "_gated"), hidden,
               name + "_o")


def _latent_attention(x, name, seq_len, heads, kv_rank, nope, rope, v_dim,
                      rope_theta, hidden, eps):
    q = _fc(x, heads * (nope + rope), name + "_q")
    kv_a = _fc(x, kv_rank + rope, name + "_kv_a")
    c = sym.RMSNorm(data=sym.slice_axis(data=kv_a, axis=1, begin=0,
                                        end=kv_rank, name=name + "_kv_c"),
                    eps=eps, name=name + "_kv_norm")
    k_r = sym.slice_axis(data=kv_a, axis=1, begin=kv_rank,
                         end=kv_rank + rope, name=name + "_kv_rope")
    kv = sym.Reshape(data=_fc(c, heads * (nope + v_dim), name + "_kv_b"),
                     shape=(-1, heads, nope + v_dim),
                     name=name + "_kv_heads")
    k_n = sym.slice_axis(data=kv, axis=2, begin=0, end=nope,
                         name=name + "_kv_nope")
    val = sym.slice_axis(data=kv, axis=2, begin=nope, end=nope + v_dim,
                         name=name + "_kv_value")
    # the one rotary key, beside every head's own content part
    k_r = sym.broadcast_axis(
        data=sym.Reshape(data=k_r, shape=(-1, 1, rope),
                         name=name + "_kv_rope_head"),
        axis=(1,), size=(heads,), name=name + "_kv_rope_heads")
    key = sym.Concat(k_n, k_r, dim=2, name=name + "_kv_key")
    a = sym.CausalAttention(
        query=q,
        key=sym.Reshape(data=key, shape=(-1, heads * (nope + rope)),
                        name=name + "_kv_key_rows"),
        value=sym.Reshape(data=val, shape=(-1, heads * v_dim),
                          name=name + "_kv_value_rows"),
        num_heads=heads, num_kv_heads=heads, head_dim=nope + rope,
        value_dim=v_dim, seq_len=seq_len, rotary=True,
        rope_theta=rope_theta, rotary_dim=rope, name=name + "_attn")
    # one sigmoid gate a head on the heads' results
    gate = sym.Activation(data=_fc(x, heads, name + "_gate"),
                          act_type="sigmoid", name=name + "_gate_act")
    a = sym.broadcast_mul(
        lhs=sym.Reshape(data=a, shape=(-1, heads, v_dim),
                        name=name + "_attn_heads"),
        rhs=sym.Reshape(data=gate, shape=(-1, heads, 1),
                        name=name + "_gate_heads"), name=name + "_gated")
    return _fc(sym.Reshape(data=a, shape=(-1, heads * v_dim),
                           name=name + "_gated_rows"), hidden, name + "_o")


def _gated(x, width, hidden, name):
    """``W_down (silu(W_gate x) * W_up x)`` as plain nodes."""
    gate = sym.Activation(data=_fc(x, width, name + "_gate"),
                          act_type="silu", name=name + "_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, width, name + "_up"),
                        name=name + "_mul"), hidden, name + "_down")


def get_bailing_hybrid(layer_types=LAYER_TYPES, dense_layers=2, hidden=2560,
                       vocab=157184, heads=32, kda_key_dim=128,
                       kda_value_dim=128, conv_kernel=4, gate_floor=-5.0,
                       kda_norm_groups=1, kv_rank=512, nope_dim=128,
                       rope_dim=64, v_dim=128, rope_theta=6000000.0,
                       dense_hidden=6144, experts_total=512, experts_held=512,
                       first_expert=0, top_k=8, n_group=8, topk_group=4,
                       routed_scale=2.5, expert_hidden=768, shared_experts=1,
                       swiglu_limits=None, eps=1e-6, seq_len=8192, chunk=64,
                       bias_update_rate=0.0):
    """Next-token language model: Embedding, the blocks of ``layer_types``
    (``"kda"`` / ``"latent_attention"``), a final RMSNorm, an untied head
    over ``vocab`` and ``SoftmaxOutput`` (its gradient the mean over
    tokens). Layer i's parameters are named ``layer<i>_*``: a KDA mixer's
    ``_q``, ``_k``, ``_v`` with ``_qconv``, ``_kconv``, ``_vconv``, ``_a``,
    ``_b``, ``_delta_A_log``, ``_delta_dt_bias``, ``_gnorm``, ``_g``, ``_o``;
    a latent mixer's ``_q``, ``_kv_a``, ``_kv_norm``, ``_kv_b``, ``_gate``,
    ``_o``; each behind ``_mixer_norm``; the feed-forward's ``_ffn_*``
    behind ``_ffn_norm`` (an expert layer's ``_ffn_experts_*`` and
    ``_ffn_shared_*``). ``swiglu_limits``: ``(routed, shared)``, each a
    limit a layer of ``layer_types`` (the published lists' entries for the
    layers built); ``None`` is no limit anywhere. ``bias_update_rate``: what
    a training step moves the experts' selection biases by against their
    loads (``ops/moe.py``)."""
    layers = len(layer_types)
    if not 0 <= dense_layers <= layers:
        raise ValueError("get_bailing_hybrid: %d dense layers of %d"
                         % (dense_layers, layers))
    for which, limits in zip(("expert", "share_expert"),
                             swiglu_limits or ()):
        if len(limits) != layers:
            raise ValueError("get_bailing_hybrid: %d %s_swiglu_limit_list "
                             "entries for %d layers"
                             % (len(limits), which, layers))
        bad = next((j for j, limit in enumerate(limits) if limit), None)
        if bad is not None:
            raise ValueError(
                "get_bailing_hybrid: %s_swiglu_limit_list is %s on layer %d: "
                "the config names a limit and not the clamp's form, and "
                "none is guessed here" % (which, limits[bad], bad))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, kind in enumerate(layer_types):
        name = "layer%d" % i
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_mixer_norm")
        if kind == "kda":
            out = _kda(n, name, seq_len, heads, kda_key_dim, kda_value_dim,
                       conv_kernel, chunk, gate_floor, kda_norm_groups,
                       hidden, eps)
        elif kind == "latent_attention":
            out = _latent_attention(n, name, seq_len, heads, kv_rank,
                                    nope_dim, rope_dim, v_dim, rope_theta,
                                    hidden, eps)
        else:
            raise ValueError("get_bailing_hybrid: layer %d is %r, not 'kda' "
                             "or 'latent_attention'" % (i, kind))
        x = sym._Plus(lhs=x, rhs=out, name=name + "_mixer_add")
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_ffn_norm")
        if i < dense_layers:
            out = _gated(n, dense_hidden, hidden, name + "_ffn")
        else:
            out = sym.RoutedExperts(
                data=n, num_experts=experts_total, num_held=experts_held,
                first_held=first_expert, top_k=top_k, scale=routed_scale,
                num_hidden=expert_hidden, gated=True, n_group=n_group,
                topk_group=topk_group, bias_update_rate=bias_update_rate,
                name=name + "_ffn_experts")
            if shared_experts:
                out = sym._Plus(
                    lhs=out, rhs=_gated(n, shared_experts * expert_hidden,
                                        hidden, name + "_ffn_shared"),
                    name=name + "_ffn_sum")
        x = sym._Plus(lhs=x, rhs=out, name=name + "_ffn_add")
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = _fc(x, vocab, "lm_head")
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
