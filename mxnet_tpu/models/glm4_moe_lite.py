"""GLM-4 MoE Lite: a language model whose every block is latent attention
and a feed-forward behind pre-RMSNorms, the first ``dense_layers``
feed-forwards dense and gated, the rest routed gated experts beside shared
ones (zai-org/GLM-4.7-Flash, ``model_type: glm4_moe_lite``; the defaults
below are that model's published sizes). ``h = x + Attn(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; no bias anywhere.

**Latent attention** (DeepSeek-V2, arXiv:2405.04434, section 2.1): queries
and keys/values come through low-rank chains with an RMSNorm on each
latent. ``c_q = RMSNorm(W_qa u)`` (``q_rank``), each head's query ``[q_n;
q_r] = (W_qb c_q)_h`` (``nope_dim + rope_dim``); ``[c_kv; k_r] = W_kva u``
(``kv_rank + rope_dim``), ``c = RMSNorm(c_kv)``, each head's ``[k_n; val]
= (W_kvb c)_h`` (``nope_dim + v_dim``). A head's key is ``[k_n,h; k_r]``:
its own content part beside ONE rotary key that all heads share; rotary
positions turn the last ``rope_dim`` columns of queries and keys
(``CausalAttention(rotary_dim=...)``), the softmax scale is
``(nope_dim + rope_dim)^-1/2``. The op is handed the key already broadcast
to the heads (``num_kv_heads = heads``); it takes ONE head width, so
``v_dim`` must equal ``nope_dim + rope_dim`` (the published 192 + 64 =
256).

Layout as ``nemotron_h.py``: activations ``[batch * seq_len, hidden]``,
``data`` int32 ids ``[batch, seq_len]``. An expert layer holds
``experts_held`` of ``experts_total`` routed experts from ``first_expert``
on: one chip's share of an expert-parallel layout (``ops/moe.py``); the
shared expert, attention and the dense layer are whole.
"""
from .. import symbol as sym

__all__ = ["get_glm4_moe_lite"]


def _fc(x, width, name):
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name)


def _latent_attention(x, name, seq_len, heads, q_rank, kv_rank, nope, rope,
                      v_dim, rope_theta, hidden, eps):
    c_q = sym.RMSNorm(data=_fc(x, q_rank, name + "_q_a"), eps=eps,
                      name=name + "_q_a_norm")
    q = _fc(c_q, heads * (nope + rope), name + "_q_b")
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
        seq_len=seq_len, rotary=True, rope_theta=rope_theta,
        rotary_dim=rope, name=name + "_attn")
    return _fc(a, hidden, name + "_o")


def _gated(x, width, hidden, name):
    """``W_down (silu(W_gate x) * W_up x)`` as plain nodes."""
    gate = sym.Activation(data=_fc(x, width, name + "_gate"),
                          act_type="silu", name=name + "_act")
    return _fc(sym._Mul(lhs=gate, rhs=_fc(x, width, name + "_up"),
                        name=name + "_mul"), hidden, name + "_down")


def get_glm4_moe_lite(layers=47, dense_layers=1, hidden=2048, vocab=154880,
                      heads=20, q_rank=768, kv_rank=512, nope_dim=192,
                      rope_dim=64, v_dim=256, rope_theta=1000000.0,
                      dense_hidden=10240, experts_total=64, experts_held=64,
                      first_expert=0, top_k=4, routed_scale=1.8,
                      expert_hidden=1536, shared_experts=1, eps=1e-5,
                      seq_len=8192, bias_update_rate=0.0):
    """Next-token language model: Embedding, ``layers`` blocks, a final
    RMSNorm, an untied head over ``vocab`` and ``SoftmaxOutput`` (its
    gradient the mean over tokens). Layer i's parameters are named
    ``layer<i>_*``: the attention's chain ``_q_a``, ``_q_a_norm``,
    ``_q_b``, ``_kv_a``, ``_kv_norm``, ``_kv_b``, ``_o`` behind
    ``_attn_norm``, the feed-forward's ``_ffn_*`` behind ``_ffn_norm``
    (an expert layer's ``_ffn_experts_*`` and ``_ffn_shared_*``).
    ``bias_update_rate``: what a training step moves the experts'
    selection biases by against their loads (``ops/moe.py``)."""
    if v_dim != nope_dim + rope_dim:
        raise ValueError("get_glm4_moe_lite: values of %d beside keys of "
                         "%d + %d: CausalAttention takes one head width"
                         % (v_dim, nope_dim, rope_dim))
    if not 0 <= dense_layers <= layers:
        raise ValueError("get_glm4_moe_lite: %d dense layers of %d"
                         % (dense_layers, layers))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i in range(layers):
        name = "layer%d" % i
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_attn_norm")
        out = _latent_attention(n, name, seq_len, heads, q_rank, kv_rank,
                                nope_dim, rope_dim, v_dim, rope_theta, hidden,
                                eps)
        x = sym._Plus(lhs=x, rhs=out, name=name + "_attn_add")
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_ffn_norm")
        if i < dense_layers:
            out = _gated(n, dense_hidden, hidden, name + "_ffn")
        else:
            out = sym.RoutedExperts(
                data=n, num_experts=experts_total, num_held=experts_held,
                first_held=first_expert, top_k=top_k, scale=routed_scale,
                num_hidden=expert_hidden, gated=True,
                bias_update_rate=bias_update_rate,
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
