"""Nemotron-H: a hybrid language model whose layers are each ONE mixer
behind a pre-RMSNorm and a residual add, chosen by a pattern string:
``M`` a Mamba-2 state-space mixer, ``*`` causal grouped-head attention,
``E`` routed experts beside a shared expert (NVIDIA-Nemotron-3-Nano-30B-
A3B, ``model_type: nemotron_h``; the defaults below are that model's
published sizes). Beyond the reference's zoo: its only language model is
the 2016 LSTM (``lstm.py``).

Activations are ``[batch * seq_len, hidden]`` throughout, the rows whole
sequences laid end to end (``ops/seq.py``). ``data`` is ``[batch,
seq_len]`` token ids, int32 (under ``MXNET_COMPUTE_DTYPE=bfloat16`` the
executor leaves ids uncast), ``softmax_label`` the next token at each
position. An expert layer holds ``experts_held`` of ``experts_total``
routed experts from ``first_expert`` on: one chip's share of an
expert-parallel layout (``ops/moe.py``).
"""
from .. import symbol as sym

__all__ = ["get_nemotron_h"]


def _mamba(x, name, seq_len, heads, head_dim, groups, state, kernel, chunk,
           hidden, eps):
    d_inner, gn = heads * head_dim, groups * state
    zxd = sym.FullyConnected(data=x, num_hidden=2 * d_inner + 2 * gn + heads,
                             no_bias=True, name=name + "_in_proj")
    z = sym.slice_axis(data=zxd, axis=1, begin=0, end=d_inner)
    xbc = sym.slice_axis(data=zxd, axis=1, begin=d_inner,
                         end=2 * d_inner + 2 * gn)
    dt = sym.slice_axis(data=zxd, axis=1, begin=2 * d_inner + 2 * gn,
                        end=2 * d_inner + 2 * gn + heads)
    xbc = sym.CausalConv1D(data=xbc, kernel=kernel, seq_len=seq_len,
                           name=name + "_conv")
    xbc = sym.Activation(data=xbc, act_type="silu", name=name + "_conv_act")
    y = sym.SSMScan(data=xbc, dt=dt, num_heads=heads, head_dim=head_dim,
                    num_groups=groups, state_size=state, chunk=chunk,
                    seq_len=seq_len, name=name + "_scan")
    y = sym.RMSNorm(data=y, gate=z, gated=True, num_groups=groups, eps=eps,
                    name=name + "_gnorm")
    return sym.FullyConnected(data=y, num_hidden=hidden, no_bias=True,
                              name=name + "_out_proj")


def _attention(x, name, seq_len, heads, kv_heads, head_dim, rotary,
               rope_theta, hidden):
    q = sym.FullyConnected(data=x, num_hidden=heads * head_dim, no_bias=True,
                           name=name + "_q")
    k = sym.FullyConnected(data=x, num_hidden=kv_heads * head_dim,
                           no_bias=True, name=name + "_k")
    v = sym.FullyConnected(data=x, num_hidden=kv_heads * head_dim,
                           no_bias=True, name=name + "_v")
    a = sym.CausalAttention(query=q, key=k, value=v, num_heads=heads,
                            num_kv_heads=kv_heads, head_dim=head_dim,
                            seq_len=seq_len, rotary=rotary,
                            rope_theta=rope_theta, name=name + "_attn")
    return sym.FullyConnected(data=a, num_hidden=hidden, no_bias=True,
                              name=name + "_o")


def _experts(x, name, total, held, first, top_k, scale, expert_hidden,
             shared_hidden, hidden, bias_update_rate):
    routed = sym.RoutedExperts(data=x, num_experts=total, num_held=held,
                               first_held=first, top_k=top_k, scale=scale,
                               num_hidden=expert_hidden,
                               bias_update_rate=bias_update_rate,
                               name=name + "_experts")
    up = sym.FullyConnected(data=x, num_hidden=shared_hidden, no_bias=True,
                            name=name + "_shared_up")
    act = sym.Activation(data=up, act_type="relu2", name=name + "_shared_act")
    shared = sym.FullyConnected(data=act, num_hidden=hidden, no_bias=True,
                                name=name + "_shared_down")
    return routed + shared


def get_nemotron_h(pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                           "EMEMEMEME",
                   hidden=2688, vocab=131072, experts_total=128,
                   experts_held=128, first_expert=0, seq_len=8192,
                   mamba_heads=64, mamba_head_dim=64, ssm_groups=8,
                   ssm_state=128, conv_kernel=4, chunk=128, attn_heads=32,
                   kv_heads=2, head_dim=128, rotary=True, rope_theta=10000.0,
                   top_k=6, routed_scale=2.5, expert_hidden=1856,
                   shared_hidden=3712, eps=1e-5, bias_update_rate=0.0):
    """Next-token language model: Embedding, the layers of ``pattern``,
    a final RMSNorm, an untied head over ``vocab`` and ``SoftmaxOutput``
    (its gradient the mean over tokens). Layer i's parameters are named
    ``layer<i>_*``. ``bias_update_rate``: what a training step moves the
    experts' selection biases by against their loads (``ops/moe.py``; the
    family trains with 1e-3, 0 leaves them as loaded)."""
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                      name="embed")
    x = sym.Reshape(data=x, shape=(-1, hidden))
    for i, kind in enumerate(pattern):
        name = "layer%d" % i
        n = sym.RMSNorm(data=x, eps=eps, name=name + "_norm")
        if kind == "M":
            out = _mamba(n, name, seq_len, mamba_heads, mamba_head_dim,
                         ssm_groups, ssm_state, conv_kernel, chunk, hidden,
                         eps)
        elif kind == "*":
            out = _attention(n, name, seq_len, attn_heads, kv_heads,
                             head_dim, rotary, rope_theta, hidden)
        elif kind == "E":
            out = _experts(n, name, experts_total, experts_held,
                           first_expert, top_k, routed_scale, expert_hidden,
                           shared_hidden, hidden, bias_update_rate)
        else:
            raise ValueError("get_nemotron_h: layer %d is %r, not one of "
                             "'M', '*', 'E'" % (i, kind))
        x = x + out
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = sym.FullyConnected(data=x, num_hidden=vocab, no_bias=True,
                                name="lm_head")
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")
