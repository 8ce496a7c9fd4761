"""The plain reference of ``lfm2_moe`` language models: forward pass, loss,
gradients and Adam, in ``jax.numpy`` and float32 (``follow`` and
``forward_logprob`` set ``jax.default_matmul_precision("highest")``), no
kernels, no chunks, no layout of rows by expert, nothing of the program.
Also this architecture's count of a step's operations and bytes
(``step_cost``), its parts of the step by scope (``part_of``) and the
lowering counters a traced run prints (``LOWERINGS``), kept with the
benchmark: everything model-shaped that ``drivers/fit_lm_ref.py`` asks for.

The architecture (LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type:
lfm2_moe``): ``hidden`` d = 2,048, vocabulary 65,536, the head tied to the
embedding, 40 blocks, ``norm_eps`` 1e-5, no bias anywhere.

* Block (pre-norm): ``h = x + Op(RMSNorm_d(x))``, ``y = h +
  FFN(RMSNorm_d(h))``; after the last block ``RMSNorm_d``, the head (the
  embedding's own matrix, ``[vocab, d]``), next-token cross-entropy, mean
  over tokens.
* ``Op``, ``layer_types[i] == "conv"``, on ``u = RMSNorm_d(x)``: ``[B; C;
  z] = W_in u`` (d -> 3d, three equal chunks in that order), ``v = B * z``,
  ``c_t = sum_{k=0..K-1} w[:, k] * v_{t-(K-1)+k}`` (depthwise over the d
  channels, causal, zeros before the sequence's start, ``conv_L_cache`` K =
  3, no bias, NO activation), ``Op = W_out (C * c)``.
* ``Op``, ``"full_attention"``: ``q = W_q u`` (H = 32 heads of 64), ``k =
  W_k u``, ``val = W_v u`` (8 heads of 64); each head's q and k through
  ``RMSNorm_64`` over its own columns, ONE gamma for all query heads and one
  for all key heads; ``rope`` over the whole head at ``rope_theta`` 1e6 in
  the half-split convention; ``o_h = softmax_causal(q_h k_g(h)^T / sqrt(64))
  val_g(h)``, four query heads a key/value head (``g(h) = h // 4``); ``Op =
  W_o [o_h]_h``.
* Dense feed-forward (the first ``num_dense_layers`` blocks): ``W_2
  (silu(W_1 h') * W_3 h')`` at ``intermediate_size`` 11,776.
* Expert feed-forward (the rest), ``h' = RMSNorm_d(h)``: ``s = sigmoid(W_r
  h')`` over 64 experts in float32; the 4 largest of ``s + b``; ``w_e = s_e /
  (sum of the chosen s + 1e-6)``, times ``routed_scaling_factor`` 1; ``FFN =
  sum_e w_e E_e(h')``, every ``E`` the gated form at
  ``moe_intermediate_size`` 1,536; NO shared expert. Only ``experts_held``
  experts from ``first_expert`` are here: rows routed elsewhere add nothing
  (``model-configs`` section 4). ``b``, the selection bias
  (``use_expert_bias``), is a STATE and no weight: no gradient reaches it,
  and after every step ``b_e += bias_update_rate * sign(mean load -
  load_e)`` (``balance_step``, the balancing without an auxiliary loss that
  the Nemotron and GLM cells run).

Departures from the published description, each for memory or for the cut
and none in the mathematics: the convolution is K shifted products;
attention's softmax goes in blocks of queries, each against all keys under
the mask; the dense feed-forward, the head and the loss go in blocks of
rows; experts are a loop over the held experts with a mask, every expert
computing every row; each block is recomputed in the backward pass; the
convolution's state runs across the documents of a packed sequence (the
traffic has no separators).

``init_params`` with ``init.balance`` also starts the selection biases
where the balancing rule settles (``balanced_start``), as the GLM cell's
reference does.

``precision`` (``loss_and_logprob``): ``None`` float32; ``"bfloat16"`` the
stated precision's floor (every tensor an operator of the program reads or
writes rounded to bfloat16, arithmetic inside float32: the gated
convolution is ONE operator, its gates and taps float32 between the two
projections' roundings); the controls, each the bfloat16 pipeline with ONE
thing wrong: ``"int8_matmul"``, ``"fp8_matmul"`` (matmul inputs at 8 bits),
``"conv_ungated"`` (``v = z``: the gate ``B`` left out), ``"no_qk_norm"``
(the heads' RMSNorms left out), ``"weights_unnormalised"`` (the chosen
scores not divided by their sum).
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, train
# what the sibling references define and this one computes alike: the
# rounding the compiler may not drop, the rotation, RMSNorm, the family's
# balancing rule, Adam as the program states it, the gated feed-forward and
# the loop over held experts, the traffic's law, the leaves of a comparison
from .glm4_moe_lite import (balance_rates, gated, leaf_norms, leaves,
                            routed_part, zipf_ids)
from .nemotron_h import (_bf16, _rope, _stretch, balance_step,
                         balanced_bias, loads)
from .olmo_hybrid import _rmsnorm, make_adam

LAYER_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                  "conv") * 9 + ("full_attention", "conv")
DEFAULTS = dict(
    layer_types=LAYER_TYPES, dense_layers=2, hidden=2048, vocab=65536,
    heads=32, kv_heads=8, head_dim=64, conv_kernel=3, dense_hidden=11776,
    experts_total=64, experts_held=64, first_expert=0, top_k=4,
    routed_scale=1.0, expert_hidden=1536, rope_theta=1000000.0, eps=1e-5,
    seq_len=8192, bias_update_rate=0.0, tie_head=True)
STATE = "experts_select_bias"   # the leaves that are states, by suffix
NORM_EPS = 1e-6                 # beside the chosen scores' sum
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.shortconv_body.xla_fused",
             "lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise",
             "lower.experts_body.swiglu",
             "lower.experts_kernel.pallas_grouped",
             "lower.experts_kernel.xla_loop")
ATTN_BLOCK = 256
ROW_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)


# name -> (stored tensors rounded by, matmul inputs rounded by)
_ROUND = {None: (arrays._same, arrays._same),
          "int8_matmul": (_bf16, arrays._int8),
          "fp8_matmul": (_bf16, arrays._fp8)}
_ROUND.update({name: (_bf16, arrays._same) for name in (
    "bfloat16", "conv_ungated", "no_qk_norm", "weights_unnormalised")})


def _theirs(precision):
    """``precision`` as the sibling's ``gated`` / ``routed_part`` know it:
    this file's own controls are the bfloat16 pipeline to them."""
    return precision if precision in (None, "int8_matmul", "fp8_matmul") \
        else "bfloat16"


def config(args):
    cfg = dict(DEFAULTS)
    unknown = set(args) - set(cfg)
    if unknown:
        raise ValueError("lfm2_moe: unknown arguments %s" % sorted(unknown))
    cfg.update(args)
    cfg["layer_types"] = tuple(cfg["layer_types"])
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in ("conv", "full_attention"):
            raise ValueError("lfm2_moe: layer %d is %r" % (i, kind))
    return cfg


def _tag(args):
    return sorted((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in args.items())


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args, states=True):
    """The program's parameter names -> shapes, in the program's order;
    with ``states`` the selection biases too, each after its router."""
    c = config(args)
    d, hd = c["hidden"], c["head_dim"]
    out = {"embed_weight": (c["vocab"], d)}
    for i, kind in enumerate(c["layer_types"]):
        p = "layer%d_" % i
        out[p + "operator_norm_gamma"] = (d,)
        if kind == "conv":
            out[p + "conv_in_weight"] = (3 * d, d)
            out[p + "conv_weight"] = (d, c["conv_kernel"])
            out[p + "conv_out_weight"] = (d, d)
        else:
            out[p + "q_weight"] = (c["heads"] * hd, d)
            out[p + "qnorm_gamma"] = (hd,)
            out[p + "k_weight"] = (c["kv_heads"] * hd, d)
            out[p + "knorm_gamma"] = (hd,)
            out[p + "v_weight"] = (c["kv_heads"] * hd, d)
            out[p + "o_weight"] = (d, c["heads"] * hd)
        out[p + "ffn_norm_gamma"] = (d,)
        if i < c["dense_layers"]:
            f = c["dense_hidden"]
            out[p + "ffn_gate_weight"] = (f, d)
            out[p + "ffn_up_weight"] = (f, d)
            out[p + "ffn_down_weight"] = (d, f)
        else:
            held, f = c["experts_held"], c["expert_hidden"]
            out[p + "ffn_experts_router_weight"] = (d, c["experts_total"])
            if states:
                out[p + "ffn_" + STATE] = (c["experts_total"],)
            out[p + "ffn_experts_gate_weight"] = (held, d, f)
            out[p + "ffn_experts_up_weight"] = (held, d, f)
            out[p + "ffn_experts_down_weight"] = (held, f, d)
    out["final_norm_gamma"] = (d,)
    if not c["tie_head"]:
        out["lm_head_weight"] = (c["vocab"], d)
    return out


def _fan_in(name, shape, tied):
    if name == "embed_weight":
        # tied, the table is drawn as the head it also is: at std 1 the
        # logits of unit-rms rows would have std sqrt(hidden) = 45
        return shape[-1] if tied else 1
    if "_ffn_experts_" in name:     # stacked [held, in, out]; router [in, E]
        return shape[-2]
    return shape[-1]                # the convolution's [channels, K]: K taps


def init_params(args, seed_key, init=None):
    """Every parameter and state from the key, float32, on the device.
    Matrices: normal, std 1/sqrt(fan-in) (the convolution's taps by their
    K; the tied embedding as the head, ``_fan_in``; an untied one std 1)
    from ONE generator run ``DRAWS`` times over slices of one buffer
    (``glm4_moe_lite.init_params``); norm weights 1; the selection biases 0,
    or with ``init["balance"]`` (``{"from", "to", "steps", "hold",
    "zipf_exponent"}``) where the family's balancing rule settles on one
    batch drawn from the same key (``balanced_start``). (Anything that is
    no dictionary, which is what ``tools/sweep_lr.py`` hands over, is taken
    as no ``init``.)"""
    if not isinstance(init, dict):
        init = {}
    c = config(args)
    shapes = param_shapes(args)
    sizes = {n: int(np.prod(s)) for n, s in shapes.items()
             if n.endswith("_weight")}
    total = sum(sizes.values())

    def make(key):
        per = -(-total // (DRAWS * 1024)) * 1024
        flat = jax.lax.fori_loop(
            0, DRAWS, lambda i, buf: jax.lax.dynamic_update_slice(
                buf, jax.random.normal(jax.random.fold_in(key, i), (per,),
                                       jnp.float32), (i * per,)),
            jnp.zeros((DRAWS * per,), jnp.float32))
        out, at = {}, 0
        for name, shape in shapes.items():
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(STATE):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = flat[at:at + sizes[name]].reshape(shape) \
                    / math.sqrt(_fan_in(name, shape, c["tie_head"]))
                at += sizes[name]
        return out

    params = jax.jit(make)(seed_key)
    balance = init.get("balance")
    if balance and any(k.endswith(STATE) for k in params):
        ids = zipf_ids(jax.random.fold_in(seed_key, 999), c["vocab"],
                       c["seq_len"], balance.get("zipf_exponent", 1.0))
        bias, load = balanced_start(args, params, ids,
                                    balance_rates(balance))
        params.update({k: jnp.asarray(v) for k, v in bias.items()})
        print("balanced start: rows of the drawn batch by expert, largest / "
              "mean by layer: %s" % "  ".join(
                  "%d / %.0f" % (v.max(), v.mean())
                  for _, v in sorted(load.items())), flush=True)
    return params


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def _shifted(v, by):
    """``v [B, T, C]`` moved ``by`` positions later along T, zeros before
    the sequence's start."""
    if not by:
        return v
    return jnp.concatenate([jnp.zeros_like(v[:, :by]), v[:, :-by]], axis=1)


def gated_conv(proj, w, seq_len, ungated=False):
    """``C * conv(B * z)`` of ``proj [rows, 3C] = [B; C; z]`` and the taps
    ``w [C, K]``: ``[rows, C]``; with ``ungated`` ``v = z``."""
    ch, taps = w.shape
    b, gate, z = (proj[:, i * ch:(i + 1) * ch].reshape(-1, seq_len, ch)
                  for i in range(3))
    v = z if ungated else b * z
    conv = sum(_shifted(v, taps - 1 - k) * w[:, k] for k in range(taps))
    return (gate * conv).reshape(-1, ch)


def short_conv(p, pre, u, args, precision=None):
    """``Op(u)`` of a conv block, ``[rows, hidden]``, ``u`` the block's
    input after its norm."""
    c = config(args)
    st, mm = _ROUND[precision]
    um = mm(u)
    proj = st(um @ mm(st(p[pre + "conv_in_weight"])).T)
    y = st(gated_conv(proj, st(p[pre + "conv_weight"]), c["seq_len"],
                      precision == "conv_ungated"))
    return st(mm(y) @ mm(st(p[pre + "conv_out_weight"])).T)


def attention(p, pre, u, args, precision=None):
    """``Op(u)`` of an attention block, ``[rows, hidden]``."""
    c = config(args)
    st, mm = _ROUND[precision]
    t, hq, hkv, hd = c["seq_len"], c["heads"], c["kv_heads"], c["head_dim"]
    bsz, group = u.shape[0] // t, hq // hkv
    um = mm(u)

    def proj(part, heads):
        return st(um @ mm(st(p[pre + part + "_weight"])).T).reshape(
            bsz, t, heads, hd)

    def normed(x, part):
        if precision == "no_qk_norm":
            return x
        return st(_rmsnorm(x, st(p[pre + part + "norm_gamma"]), c["eps"]))

    q, k, val = proj("q", hq), proj("k", hkv), mm(proj("v", hkv))
    q = mm(st(_rope(normed(q, "q"), c["rope_theta"])))
    k = mm(st(_rope(normed(k, "k"), c["rope_theta"])))
    blk = _stretch(t, ATTN_BLOCK)

    @jax.checkpoint
    def block(qi, start, k, val):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k) / math.sqrt(hd)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(blk))[:, None]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", mm(prob), val)

    # one block of queries after another (a lax.map, so that the compiler
    # cannot hold several blocks' scores at once); query head h reads
    # key/value head h // group
    qb = jnp.moveaxis(q.reshape(bsz, t // blk, blk, hkv, group, hd), 1, 0)
    out = jax.lax.map(lambda x: block(x[0], x[1], k, val),
                      (qb, jnp.arange(0, t, blk)))
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz * t, hq * hd))
    return st(mm(out) @ mm(st(p[pre + "o_weight"])).T)


def route(p, pre, u, c, precision=None, rates=None):
    """Expert ids ``[S, k]``, combine weights ``[S, k]`` (float32; the
    router reads the layer's input unrounded by ``mm``) and the selection
    bias they were chosen with: the layer's own, or with ``rates`` the one
    ``balanced_bias`` settles at from it."""
    scores = jax.nn.sigmoid(u @ p[pre + "ffn_experts_router_weight"])
    bias = p[pre + "ffn_" + STATE]
    if rates is not None:
        bias = balanced_bias(scores, bias, c["top_k"], rates)
    _, eid = jax.lax.top_k(scores + bias, c["top_k"])
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    if precision != "weights_unnormalised":
        chosen = chosen / (chosen.sum(axis=1, keepdims=True) + NORM_EPS)
    return eid, chosen * c["routed_scale"], bias


def experts(p, pre, u, args, precision=None, rates=None):
    """``FFN(u)`` of an expert block, ``[rows, hidden]`` (the held experts'
    part: there is no shared expert), the rows each expert drew ``[E]`` and
    the selection bias they were chosen with."""
    c = config(args)
    routed = route(p, pre, u, c, precision, rates)
    out = routed_part(
        u, routed, tuple(p[pre + "ffn_experts_%s_weight" % n]
                         for n in ("gate", "up", "down")),
        c["first_expert"], _theirs(precision))
    return out, loads(routed[0], c["experts_total"]), routed[2]


def hidden_states(params, ids, args, precision=None, remat=True, rates=None):
    """Token ids ``[B, T]`` -> what the head reads, ``[B*T, hidden]`` (the
    blocks and the final norm), and by expert layer's state name the rows
    each expert drew ``[E]`` and the selection bias it chose with (with
    ``rates``: the balanced one, ``route``)."""
    c = config(args)
    st, mm = _ROUND[precision]
    x = st(params["embed_weight"])[ids.reshape(-1)]

    def block(i, kind, pre, p, x):
        u = st(_rmsnorm(x, st(p[pre + "operator_norm_gamma"]), c["eps"]))
        op = short_conv if kind == "conv" else attention
        x = st(x + op(p, pre, u, args, precision))
        u = st(_rmsnorm(x, st(p[pre + "ffn_norm_gamma"]), c["eps"]))
        if i < c["dense_layers"]:
            return st(x + gated(p, pre + "ffn_", u, st, mm)), None
        out, *routed = experts(p, pre, u, args, precision, rates)
        return st(x + out), routed

    load, bias = {}, {}
    for i, kind in enumerate(c["layer_types"]):
        pre = "layer%d_" % i
        fn = functools.partial(block, i, kind, pre)
        own = {k: v for k, v in params.items() if k.startswith(pre)}
        x, routed = (jax.checkpoint(fn) if remat else fn)(own, x)
        if routed:
            load[pre + "ffn_" + STATE], bias[pre + "ffn_" + STATE] = routed
    return st(_rmsnorm(x, st(params["final_norm_gamma"]), c["eps"])), \
        load, bias


def loss_logprob_loads(params, ids, labels, args, rows, precision=None,
                       remat=True):
    """Mean next-token cross-entropy over all positions; the
    log-probabilities ``[len(rows), vocab]`` at the flat positions ``rows``
    and the expert layers' loads: ``loss, (log-probabilities, loads)``. The
    head (the embedding's own matrix where ``tie_head``) and the loss go in
    blocks of rows, so that the ``[B*T, vocab]`` float32 logits never exist
    whole."""
    st, mm = _ROUND[precision]
    x, load, _ = hidden_states(params, ids, args, precision, remat)
    head = "embed_weight" if config(args)["tie_head"] else "lm_head_weight"
    w = mm(st(params[head]))

    def logprob(x):
        return jax.nn.log_softmax(st(mm(x) @ w.T), axis=-1)

    @jax.checkpoint
    def picked(xl):
        return jnp.sum(jnp.take_along_axis(logprob(xl[0]), xl[1][:, None],
                                           axis=1))

    blk = _stretch(x.shape[0], ROW_BLOCK)
    total = jnp.sum(jax.lax.map(picked, (x.reshape(-1, blk, x.shape[1]),
                                         labels.reshape(-1, blk))))
    return -total / x.shape[0], (logprob(x[rows]), load)


def loss_and_logprob(params, ids, labels, args, rows, precision=None,
                     remat=True):
    loss, (logp, _) = loss_logprob_loads(params, ids, labels, args, rows,
                                         precision, remat)
    return loss, logp


def balanced_start(args, params, ids, rates):
    """The selection biases a model in training would hold: one float32
    forward pass over ``ids [B, T]`` in which each expert layer, when the
    pass reaches it, runs ``balance_step`` on its own scores at ``rates``
    one after another and goes on with the bias that gives
    (``balanced_bias``), so that the next layer balances on what it will
    really read. Returns ``{state name: bias}``, float32 on the host, and by
    state name the loads they give on ``ids``."""
    @jax.jit
    def run(params, ids, rates):
        _, load, bias = hidden_states(params, ids, args, remat=False,
                                      rates=rates)
        return bias, load

    with jax.default_matmul_precision("highest"):
        bias, load = run(params, ids, jnp.asarray(rates, jnp.float32))
    return ({k: np.asarray(v, np.float32) for k, v in bias.items()},
            {k: np.asarray(v) for k, v in load.items()})


# ---------------------------------------------------------------------------
# training: Adam as mxnet_tpu/optimizer.py states it
# ---------------------------------------------------------------------------
def grad_groups(args, n):
    """The parameter names in the program's order, cut into ``n`` runs of
    about equal size."""
    sizes = {k: int(np.prod(s))
             for k, s in param_shapes(args, states=False).items()}
    share, groups, run = sum(sizes.values()) / n, [[]], 0
    for name, size in sizes.items():
        if run >= share * len(groups) and len(groups) < n:
            groups.append([])
        groups[-1].append(name)
        run += size
    return groups


def make_grad(args, names):
    """jitted (params, ids, labels, rows) -> (gradients of ``names``, loss,
    log-probabilities at ``rows``, loads by state name): the mean loss over
    the batch's tokens differentiated with respect to the leaves ``names``
    alone (the tied matrix's gradient is the sum over its two uses: it is
    ONE leaf here). ``rows [B, n]`` are positions within each sequence. The
    batch goes one sequence at a time, gradients and loads added up (the
    loss is a mean over tokens, no layer looks across sequences)."""
    def run(params, ids, labels, rows):
        rest = {k: v for k, v in params.items() if k not in names}

        def loss(sub, i, l, r):
            return loss_logprob_loads({**rest, **sub}, i, l, args, r)

        grad = jax.value_and_grad(loss, has_aux=True)
        sub = {k: params[k] for k in names}
        if ids.shape[0] == 1:    # no second copy of the gradients to add to
            (value, (logp, load)), g = grad(sub, ids, labels, rows[0])
            return g, value, logp, load

        def one(acc, seq):
            (value, (logp, load)), g = grad(sub, seq[0][None], seq[1][None],
                                            seq[2])
            return jax.tree_util.tree_map(jnp.add, acc, g), (value, logp,
                                                              load)

        g, (values, logp, load) = jax.lax.scan(
            one, jax.tree_util.tree_map(jnp.zeros_like, sub),
            (ids, labels, rows))
        return (jax.tree_util.tree_map(lambda x: x / ids.shape[0], g),
                jnp.mean(values), logp.reshape((-1,) + logp.shape[2:]),
                {k: v.sum(axis=0) for k, v in load.items()})

    return jax.jit(run)


def follow(args, recipe, params_host, batches, rows):
    """Follow ``len(batches)`` steps from the host copy of the seeded
    weights and states. Returns what ``check.compare`` reads: losses, the
    first gradient's norm and the change over all the steps by leaf (the
    selection biases among the leaves of the change: ``balance_step`` moves
    them after each step, by the loads of the step that read them, and no
    Adam), and the first step's log-probabilities at ``rows`` (``[B, n]``
    positions within each sequence; the result is ``[B * n, vocab]``).

    A step's gradient is taken in ``GRAD_PASSES`` passes, each with respect
    to a run of the leaves, and folded into Adam's moments before the next
    pass (``glm4_moe_lite.follow``). The weights move once every pass has
    been, from the moments alone."""
    rows = jnp.asarray(rows, jnp.int32)
    rate = config(args)["bias_update_rate"]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v) for k, v in params_host.items()}
        state = {k: p.pop(k) for k in list(p) if k.endswith(STATE)}
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        moments, apply = make_adam(recipe)
        grads = [(names, train.compiled_once(
            make_grad(args, names),
            ({**p, **state},) + tuple(batches[0]) + (rows,),
            ("lfm2_moe.grad", _tag(args), names)))
            for names in grad_groups(args, GRAD_PASSES)]
        losses, grad_norms, logp = [], {}, None
        for t, (ids, labels) in enumerate(batches, 1):
            for names, grad in grads:
                g, loss, lp, load = grad({**p, **state}, ids, labels, rows)
                if t == 1:
                    grad_norms.update({k: float(n)
                                       for k, n in leaf_norms(g).items()})
                    logp = np.asarray(lp, np.float64)
                new_m, new_v = moments(
                    {k: m[k] for k in names}, {k: v[k] for k in names}, g,
                    {k: p[k] for k in names})
                m.update(new_m)
                v.update(new_v)
                del g, new_m, new_v
            losses.append(float(loss))
            p = apply(p, m, v, jnp.float32(t))
            state = {k: balance_step(b, load[k], rate)
                     for k, b in state.items()}
        del m, v
        p.update(state)
        delta = {}
        for k in list(p):
            d = leaf_norms({k: p.pop(k) - jnp.asarray(params_host[k])})
            delta.update({name: float(n) for name, n in d.items()})
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "logprob": logp}


def forward_logprob(args, params_host, ids, labels, rows, precision):
    """Log-probabilities at ``rows`` (``[B, n]``) of one forward pass at
    ``precision``, one sequence at a time: ``[B * n, vocab]``."""
    rows = jnp.asarray(rows, jnp.int32)

    @jax.jit
    def run(params, ids, labels, rows):
        logp = jax.lax.map(
            lambda s: loss_and_logprob(params, s[0][None], s[1][None], args,
                                       s[2], precision, remat=False)[1],
            (ids, labels, rows))
        return logp.reshape((-1,) + logp.shape[2:])

    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(v) for k, v in params_host.items()}
        run = train.compiled_once(
            run, (params, ids, labels, rows),
            ("lfm2_moe.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``), by the node's layer and name: a block's
    ``_ffn_*`` nodes (its norm and add among them) are its feed-forward,
    dense or of experts; the rest is its mixer: a conv block's
    ``shortconv`` (norm, ``W_in``, the gated convolution, ``W_out``, the
    add), an attention block's kernel and what is around it. The parts the
    language-model readers of the benchmark know keep their names."""
    c = config(args)
    kinds, dense = c["layer_types"], c["dense_layers"]
    layer = re.compile(r"layer(\d+)_(ffn_)?")

    def part(phase, op, node):
        if phase == "update":
            return "optimizer"
        if phase == "metric":
            return "lm_head_loss"
        m = layer.match(node)
        if m and int(m.group(1)) < len(kinds):
            i = int(m.group(1))
            if not m.group(2):
                if kinds[i] == "conv":
                    return "shortconv"
                return "attention_kernel" if op == "CausalAttention" \
                    else "attention_proj"
            if i < dense:
                return "dense_ffn"
            return "moe_grouped_matmul" if op == "RoutedExperts" \
                else "moe_rest"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def layer_cost(kind, args, tokens, itemsize=2):
    """Forward operations of one block's part ``kind`` (``"conv"``,
    ``"full_attention"``, ``"dense"``, ``"experts"``) over ``tokens``
    positions, and the bytes it cannot avoid: ``{part: (flops, bytes)}``. A
    matmul of ``[m, k] x [k, n]`` is ``2 m k n``; attention counts the
    causal half AT THE MODEL'S OWN 64 COLUMNS A HEAD, whatever a kernel
    pads or packs. Bytes: each matrix read once in the compute dtype, each
    boundary activation read and written once. The routed experts are
    counted by the EVEN share of the pairs (``tokens x top_k x held /
    total`` rows through three matrices): ``fit_lm_ref`` hands ``step_cost``
    no routed rows, so this yardstick does not move with the routing."""
    c = config(args)
    d, hq, hkv, hd = c["hidden"], c["heads"], c["kv_heads"], c["head_dim"]
    act = tokens * d * itemsize
    if kind == "conv":
        taps = c["conv_kernel"]
        weights = d * 3 * d + d * taps + d * d
        # two products and the taps' multiply-adds, 2 a weight and token,
        # and the two gates; u read, the in-projection's 3d and the op's d
        # written and read again, the output written
        return {"shortconv": (
            2 * tokens * weights + 2 * tokens * d,
            weights * itemsize + 2 * act + 2 * tokens * 4 * d * itemsize)}
    if kind == "full_attention":
        t = c["seq_len"]
        weights = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
        # q and k before and after their norms, val and the kernel's result
        between = 2 * hq * hd + 2 * hkv * hd + hkv * hd + hq * hd
        return {"attention_proj": (
            2 * tokens * weights,
            weights * itemsize + 2 * act + 2 * tokens * between * itemsize),
            # scores and the weighted sum, each 2 T^2 a column and query
            # head, the causal half
            "attention_kernel": (
                (tokens // t) * t * t * hq * 2 * hd,
                tokens * (2 * hq + 2 * hkv) * hd * itemsize)}
    if kind == "dense":
        f = c["dense_hidden"]
        return {"dense_ffn": (3 * 2 * tokens * d * f,
                              3 * d * f * itemsize + 2 * act)}
    if kind == "experts":
        f, e, held = c["expert_hidden"], c["experts_total"], \
            c["experts_held"]
        rows = tokens * c["top_k"] * held // e
        return {"moe_grouped_matmul": (
            3 * 2 * rows * d * f,
            3 * held * d * f * itemsize + 2 * rows * d * itemsize),
            "moe_rest": (2 * tokens * d * e, d * e * 4 + 2 * act)}
    raise ValueError(kind)


def step_cost(args, batch, itemsize=2):
    """A training step's useful operations and least bytes: ``{"flops",
    "bytes", "recompute_flops", "parts": {part: (flops, bytes)}}``. Every
    part three times over (forward, and the two products of the backward
    pass); the forward pass that recomputation repeats is stated separately
    and is NOT among the useful operations. Bytes: the forward pass's three
    times over, plus each parameter's float32 master, gradient and two Adam
    moments read and the master and moments written (16 + 12 bytes) and its
    compute-dtype copy written (the tied matrix once: it is one
    parameter)."""
    c = config(args)
    tokens = batch * c["seq_len"]
    parts = {}
    for i, kind in enumerate(c["layer_types"]):
        for part in (kind, "dense" if i < c["dense_layers"] else "experts"):
            for name, cost in layer_cost(part, args, tokens,
                                         itemsize).items():
                have = parts.get(name, (0, 0))
                parts[name] = (have[0] + cost[0], have[1] + cost[1])
    d, v = c["hidden"], c["vocab"]
    parts["lm_head_loss"] = (2 * tokens * d * v,
                             d * v * itemsize + tokens * d * itemsize
                             + 2 * tokens * v * itemsize)
    parts["embed"] = (0, 2 * tokens * d * itemsize)
    fwd = sum(f for f, _ in parts.values())
    n_params = sum(int(np.prod(s))
                   for s in param_shapes(args, states=False).values())
    state_bytes = n_params * (16 + 12 + itemsize)
    parts = {k: (3 * f, 3 * b) for k, (f, b) in parts.items()}
    return {"flops": 3 * fwd, "recompute_flops": fwd,
            "bytes": sum(b for _, b in parts.values()) + state_bytes,
            "state_bytes": state_bytes, "params": n_params, "parts": parts}
