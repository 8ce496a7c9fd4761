"""The plain reference of ``laguna`` language models (Laguna-XS.2): forward
pass, loss, gradients and Adam, in ``jax.numpy`` and float32 (``follow`` and
``forward_logprob`` set ``jax.default_matmul_precision("highest")``), the
attention masks written out ``[T, T]`` (a block of queries at a time), the
rotary tables from float64 frequencies, no kernels, no band of blocks, no
layout of rows by expert, nothing of the program. Also this architecture's
count of a step's operations and bytes (``step_cost``), its parts of the step
by scope (``part_of``) and the lowering counters a traced run prints
(``LOWERINGS``), kept with the benchmark: everything model-shaped that
``drivers/fit_lm_ref.py`` asks for.

The architecture (poolside/Laguna-XS.2 ``config.json``, ``model_type:
laguna``): ``hidden`` d = 2,048, vocabulary 100,352, untied head, 40 blocks,
``rms_norm_eps`` 1e-6, no bias anywhere (``attention_bias`` false). A reading
that no key pins down is marked (+) and listed under ``assumed`` in the
configuration's file.

* Block (pre-norm+): ``h = x + Attn(RMSNorm_d(x))``, ``y = h +
  FFN(RMSNorm_d(h))``; after the last block ``RMSNorm_d``, the head,
  next-token cross-entropy, mean over tokens.
* Attention at layer ``l``, on ``u = RMSNorm_d(x)``: ``H_l`` =
  ``num_attention_heads_per_layer[l]`` query heads (48 on ``full_attention``
  layers, 64 on ``sliding_attention`` ones) of D = 128 over 8 key/value
  heads, query head ``h`` reading key/value head ``h // (H_l / 8)``; no norm
  of q or k a head (+: the config names none). Rotary, the half-split
  convention, on the head's LAST ``r`` columns (+: the published code turns
  the first; a fixed permutation of ``W_q``'s and ``W_k``'s columns):

  - ``sliding_attention``: ``r`` = 128 (``partial_rotary_factor`` 1), the
    plain ``f_i = theta^(-2i/r)``, theta 1e4; softmax over the keys ``i -
    512 < j <= i``: 512 keys, the position's own among them (+: as HF's
    sliding overlay counts a window);
  - ``full_attention``: ``r`` = 64 (0.5), theta 5e5, YaRN as HF's
    ``_compute_yarn_parameters`` writes it: ``c(n) = r ln(4096 / (2 pi n)) /
    (2 ln theta)``, ``lo = floor(c(beta_fast = 64))``, ``hi = ceil(c(beta_slow
    = 1))`` within ``[0, r - 1]``, ``ramp_i = clip((i - lo) / (hi - lo), 0,
    1)``, the frequency used ``f_i (1 - ramp_i) + (f_i / 64) ramp_i``, cos and
    sin times ``attention_factor`` 1.4158883083359672 (= 0.1 ln 64 + 1);
    softmax over ``j <= i``.

  Scores ``q_h . k / sqrt(128)``. ``g = sigmoid(W_g u)`` in ``R^{H_l}`` (+:
  ``gating: true`` read as ONE gate a query head; the parameter count pins
  it), head ``h``'s result times ``g_h``, then ``W_o``. Attention runs across
  the packed sequence's document boundaries (+), as in every cell.
* Feed-forward: layer 0 (``mlp_layer_types[0] = dense``) ``W_down
  (silu(W_gate h') * W_up h')`` at 8,192. Every other layer: ``s =
  sigmoid(W_r h')`` over 256 experts in float32 (+); the 8 largest; ``w_e =
  2.5 s_e / (sum of the chosen s + 1e-20)`` on the experts' OUTPUTS; ``FFN =
  sum_e w_e E_e(h') + E_shared(h')``, every ``E`` the gated form at 512, the
  shared one ungated by any router. The 8 are the largest of ``s + b``,
  ``b`` [256] float32 the experts' selection bias (+: the config names no
  balancing and a training run has one; this is the DeepSeek line's, whose
  router law the family follows): a STATE and no weight, no gradient reaches
  it, and after every step ``b_e += bias_update_rate * sign(mean load -
  load_e)``, the loads counted over the step's rows and over all 256
  experts; the combine weights are the chosen experts' unbiased scores.
  With ``bias_update_rate`` 0 there is no such state and the choice is by
  ``s`` alone. No auxiliary loss. Only ``experts_held`` experts from
  ``first_expert`` are here: rows routed elsewhere add nothing
  (``model-configs`` section 4).

Departures from the published description, each for memory or for the cut
and none in the mathematics: attention's softmax goes in blocks of queries,
each against ALL keys under its rows of the written-out mask; the dense
feed-forward, the shared expert, the head and the loss go in blocks of rows;
experts are a ``lax.scan`` over the held experts with a mask, every expert
computing every row; each block is recomputed in the backward pass.

``init_params``: as the siblings (normal, std 1/sqrt(fan-in)); the
selection biases 0, or with ``init.balance`` where the balancing rule itself
settles on one sequence drawn by the traffic's law (``balanced_start``, as
``glm4_moe_lite``'s): a router drawn from a seed loads a few experts several
times the mean, a model in training does not, and the step's own rule keeps
them even from there.

``precision`` (``loss_and_logprob``): ``None`` float32; ``"bfloat16"`` the
stated precision's floor (every tensor an operator of the program reads or
writes rounded to bfloat16, arithmetic inside float32); the controls, each
the bfloat16 pipeline with ONE thing wrong: ``"int8_matmul"``,
``"fp8_matmul"`` (matmul inputs at 8 bits), ``"no_window"`` (the windowed
layers causal), ``"window_511"`` (a key fewer), ``"no_attn_gate"``,
``"plain_rotary"`` (the full layers' frequencies unscaled), ``"attention_
factor_1"``, ``"softmax_router"``, ``"scale_1"`` (no routed scaling factor),
``"window_heads_48"`` (a windowed layer's first 48 query heads alone).
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, train
# what the sibling references define and this one computes alike: the
# rounding the compiler may not drop, RMSNorm, the gated feed-forward in
# blocks of rows, the held experts' part, a matrix's fan-in, Adam as the
# program states it, the leaves of a tree, the traffic's ids
from .glm4_moe_lite import balance_rates, gated, leaf_norms, zipf_ids
from .nemotron_h import (_bf16, _stretch, balance_step, balanced_bias,
                         loads)
from .olmo_hybrid import _rmsnorm, make_adam
from .qwen3_next import _fan_in, _tag, routed_part

LAYER_TYPES = tuple("full_attention" if i % 4 == 0 else "sliding_attention"
                    for i in range(40))
DEFAULTS = dict(
    layer_types=LAYER_TYPES, mlp_layer_types=None, heads_per_layer=None,
    hidden=2048, vocab=100352, kv_heads=8, head_dim=128, window=512,
    full_rotary_dim=64, full_rope_theta=500000.0, yarn_factor=64.0,
    yarn_original_positions=4096, yarn_beta_fast=64.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.4158883083359672, window_rope_theta=10000.0,
    attn_gate="head", dense_hidden=8192, experts_total=256, experts_held=256,
    first_expert=0, top_k=8, routed_scale=2.5, score_func="sigmoid",
    expert_hidden=512, shared_hidden=512, eps=1e-6, seq_len=8192,
    bias_update_rate=0.0)
STATE = "experts_select_bias"   # the leaves that are states, by suffix
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.attention_mask.causal",
             "lower.attention_mask.window",
             "lower.attention_window.block_pairs",
             "lower.attention_window.block_pairs_causal",
             "lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise",
             "lower.attention_backward.fused",
             "lower.attention_backward.split",
             "lower.experts_score.sigmoid",
             "lower.experts_score.softmax",
             "lower.experts_body.swiglu",
             "lower.experts_kernel.pallas_grouped",
             "lower.experts_kernel.xla_loop")
ATTN_BLOCK = 256
ROW_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)

CONTROLS = ("int8_matmul", "fp8_matmul", "no_window", "window_511",
            "no_attn_gate", "plain_rotary", "attention_factor_1",
            "softmax_router", "scale_1", "window_heads_48")
# name -> (stored tensors rounded by, matmul inputs rounded by)
_ROUND = {None: (arrays._same, arrays._same),
          "int8_matmul": (_bf16, arrays._int8),
          "fp8_matmul": (_bf16, arrays._fp8)}
_ROUND.update({name: (_bf16, arrays._same) for name in ("bfloat16",)
               + CONTROLS[2:]})


def config(args):
    cfg = dict(DEFAULTS)
    unknown = set(args) - set(cfg)
    if unknown:
        raise ValueError("laguna: unknown arguments %s" % sorted(unknown))
    cfg.update(args)
    kinds = cfg["layer_types"] = tuple(cfg["layer_types"])
    cfg["mlp_layer_types"] = tuple(
        cfg["mlp_layer_types"] or ["dense"] + ["sparse"] * (len(kinds) - 1))
    cfg["heads_per_layer"] = tuple(
        cfg["heads_per_layer"] or [48 if k == "full_attention" else 64
                                   for k in kinds])
    for i, (kind, ffn) in enumerate(zip(kinds, cfg["mlp_layer_types"])):
        if kind not in ("full_attention", "sliding_attention") \
                or ffn not in ("dense", "sparse"):
            raise ValueError("laguna: layer %d is %r with a %r feed-forward"
                             % (i, kind, ffn))
    if cfg["attn_gate"] not in ("head", "elementwise", "none"):
        raise ValueError("laguna: attn_gate %r" % (cfg["attn_gate"],))
    return cfg


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args, states=True):
    """The program's parameter names -> shapes, in the program's order; with
    ``states`` and a ``bias_update_rate`` the selection biases too, each
    after its router."""
    c = config(args)
    d, hd, hkv = c["hidden"], c["head_dim"], c["kv_heads"]
    gate_rows = {"head": 1, "elementwise": hd, "none": 0}[c["attn_gate"]]
    out = {"embed_weight": (c["vocab"], d)}
    for i, (ffn, h) in enumerate(zip(c["mlp_layer_types"],
                                     c["heads_per_layer"])):
        p = "layer%d_" % i
        out[p + "mixer_norm_gamma"] = (d,)
        out[p + "q_weight"] = (h * hd, d)
        out[p + "k_weight"] = (hkv * hd, d)
        out[p + "v_weight"] = (hkv * hd, d)
        if gate_rows:
            out[p + "g_weight"] = (h * gate_rows, d)
        out[p + "o_weight"] = (d, h * hd)
        out[p + "ffn_norm_gamma"] = (d,)
        if ffn == "dense":
            f = c["dense_hidden"]
            out[p + "ffn_gate_weight"] = (f, d)
            out[p + "ffn_up_weight"] = (f, d)
            out[p + "ffn_down_weight"] = (d, f)
            continue
        held, f, fs = c["experts_held"], c["expert_hidden"], c["shared_hidden"]
        out[p + "ffn_experts_router_weight"] = (d, c["experts_total"])
        if states and c["bias_update_rate"]:
            out[p + "ffn_" + STATE] = (c["experts_total"],)
        out[p + "ffn_experts_gate_weight"] = (held, d, f)
        out[p + "ffn_experts_up_weight"] = (held, d, f)
        out[p + "ffn_experts_down_weight"] = (held, f, d)
        if fs:
            out[p + "ffn_shared_gate_weight"] = (fs, d)
            out[p + "ffn_shared_up_weight"] = (fs, d)
            out[p + "ffn_shared_down_weight"] = (d, fs)
    out["final_norm_gamma"] = (d,)
    out["lm_head_weight"] = (c["vocab"], d)
    return out


def init_params(args, seed_key, init=None):
    """Every parameter and state from the key, float32, on the device.
    Matrices: normal, std 1/sqrt(fan-in) (the embedding std 1) from ONE
    generator run ``DRAWS`` times over slices of one buffer
    (``olmo_hybrid.init_params``, PR 30); norm weights 1; the selection
    biases 0, or with ``init["balance"]`` (``{"from", "to", "steps", "hold",
    "zipf_exponent"}``) where the balancing rule settles on one sequence
    drawn from the same key by the traffic's law (``balanced_start``), "as
    from a checkpoint". (Anything that is no dictionary, which is what
    ``tools/sweep_lr.py`` hands over, is taken as no ``init``.)"""
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
                    / math.sqrt(_fan_in(name, shape))
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
def rotary_frequencies(theta, r, factor=1.0, original=0, beta_fast=32.0,
                       beta_slow=1.0):
    """The ``r / 2`` frequencies of ``r`` turned columns, float64: the plain
    ``theta^(-2i/r)``, or with ``factor`` > 1 YaRN's blend of them and of
    ``f_i / factor`` (the module's docstring)."""
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)
    if factor == 1:
        return f

    def column(turns):
        return r * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(column(beta_fast)), 0)
    hi = min(math.ceil(column(beta_slow)), r - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def _rotary(x, freq, factor):
    """``x [B, T, H, r]`` turned by position (half-split: column ``i`` with
    column ``i + r/2``), cos and sin times ``factor``; the tables float64 on
    the host, float32 where they are used."""
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * freq[None]
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[None, :, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, pre, kind, h, u, args, precision=None):
    """``Attn(u)`` of one block, ``[rows, hidden]``: ``u`` the block's input
    after its norm, ``kind`` the layer's mask and rotary, ``h`` its query
    heads."""
    c = config(args)
    st, mm = _ROUND[precision]
    t, hkv, d = c["seq_len"], c["kv_heads"], c["head_dim"]
    bsz = u.shape[0] // t
    um = mm(u)

    def proj(part):
        return st(um @ mm(st(p[pre + part + "_weight"])).T)

    q = proj("q").reshape(bsz, t, h, d)
    k = proj("k").reshape(bsz, t, hkv, d)
    v = proj("v").reshape(bsz, t, hkv, d)
    gate = proj("g") if c["attn_gate"] != "none" else None
    window = 0
    if kind == "sliding_attention":
        r, factor = d, 1.0
        freq = rotary_frequencies(c["window_rope_theta"], r)
        if precision != "no_window":
            window = c["window"] - (precision == "window_511")
        if precision == "window_heads_48":
            # the full layers' count on every layer: the first of the heads
            h = min(c["heads_per_layer"])
            q = q[:, :, :h]
    else:
        r = c["full_rotary_dim"]
        factor = 1.0 if precision == "attention_factor_1" \
            else c["yarn_attention_factor"]
        freq = rotary_frequencies(c["full_rope_theta"], r) \
            if precision == "plain_rotary" else rotary_frequencies(
                c["full_rope_theta"], r, c["yarn_factor"],
                c["yarn_original_positions"], c["yarn_beta_fast"],
                c["yarn_beta_slow"])
    # the last ``r`` columns turn, as the program's do
    q, k = (jnp.concatenate([x[..., :d - r], _rotary(x[..., d - r:], freq,
                                                     factor)], axis=-1)
            for x in (q, k))
    k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
    k, v, q = mm(st(k)), mm(v), mm(st(q))       # plain: keys repeated
    blk = _stretch(t, ATTN_BLOCK)

    @jax.checkpoint
    def block(qi, start, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        # the block's rows of the [T, T] mask, written out: key j, query i
        i, j = (start + jnp.arange(blk))[:, None], jnp.arange(t)[None, :]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", mm(prob), v)

    # one block of queries after another (a lax.map, so that the compiler
    # cannot hold several blocks' scores at once)
    qb = jnp.moveaxis(q.reshape(bsz, t // blk, blk, h, d), 1, 0)
    out = jax.lax.map(lambda x: block(x[0], x[1], k, v),
                      (qb, jnp.arange(0, t, blk)))
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz, t, h, d))
    w_o = p[pre + "o_weight"]
    if gate is not None and precision != "no_attn_gate":
        g = st(jax.nn.sigmoid(gate))
        g = g.reshape(bsz, t, -1, 1 if c["attn_gate"] == "head" else d)
        out = st(out * g[:, :, :h])
    if h * d != w_o.shape[1]:       # the control: the heads that are there
        w_o = w_o[:, :h * d]
    return st(mm(out.reshape(bsz * t, h * d)) @ mm(st(w_o)).T)


def scores(logits, precision=None, score_func="sigmoid"):
    """The router's scores of ``logits [S, E]``."""
    if (score_func == "softmax") != (precision == "softmax_router"):
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def route(p, pre, u, c, precision=None, rates=None):
    """What the layer's router gives ``u [S, d]`` (float32; it reads the
    layer's input unrounded by ``mm``): ``{"eid" [S, k]: the largest of the
    scores plus the selection bias, "wts" [S, k]: the chosen experts' own
    scores over their sum times the scale, "load" [E]: the rows each expert
    drew, "bias" [E]: the one they were chosen with (the layer's own; with
    ``rates`` the one ``balanced_bias`` settles at from it; None where the
    model has no such state)}``."""
    s = scores(u @ p[pre + "ffn_experts_router_weight"], precision,
               c["score_func"])
    bias = p.get(pre + "ffn_" + STATE)
    if bias is not None and rates is not None:
        bias = balanced_bias(s, bias, c["top_k"], rates)
    eid = jax.lax.top_k(s if bias is None else s + bias, c["top_k"])[1]
    chosen = jnp.take_along_axis(s, eid, axis=1)
    wts = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)
    if precision != "scale_1":
        wts = wts * c["routed_scale"]
    return {"eid": eid, "wts": wts, "bias": bias,
            "load": loads(eid, c["experts_total"])}


def shared_part(p, pre, u, st, mm):
    """The shared expert: what every chip that shares the layer computes
    alike."""
    return gated(p, pre + "ffn_shared_", u, st, mm)


def experts(p, pre, u, args, precision=None, rates=None):
    """``FFN(u)`` of an expert block, ``[rows, hidden]``, and what ``route``
    gave."""
    c = config(args)
    st, mm = _ROUND[precision]
    routed = route(p, pre, u, c, precision, rates)
    out = routed_part(
        u, routed, tuple(p[pre + "ffn_experts_%s_weight" % n]
                         for n in ("gate", "up", "down")),
        c["first_expert"], st, mm)
    if c["shared_hidden"]:
        out = st(out + shared_part(p, pre, u, st, mm))
    return out, routed


def hidden_states(params, ids, args, precision=None, remat=True, rates=None):
    """Token ids ``[B, T]`` -> what the head reads, ``[B*T, hidden]`` (the
    blocks and the final norm), and by expert layer's prefix what ``route``
    says of its routing: the rows each expert drew (``load``) and the
    selection bias they were chosen with (``bias``)."""
    c = config(args)
    st, mm = _ROUND[precision]
    x = st(params["embed_weight"])[ids.reshape(-1)]

    def block(kind, ffn, h, pre, p, x):
        u = st(_rmsnorm(x, st(p[pre + "mixer_norm_gamma"]), c["eps"]))
        x = st(x + attention(p, pre, kind, h, u, args, precision))
        u = st(_rmsnorm(x, st(p[pre + "ffn_norm_gamma"]), c["eps"]))
        if ffn == "dense":
            return st(x + gated(p, pre + "ffn_", u, st, mm)), {}
        out, routed = experts(p, pre, u, args, precision, rates)
        return st(x + out), {k: routed[k] for k in ("load", "bias")}

    routed = {}
    for i, (kind, ffn, h) in enumerate(zip(
            c["layer_types"], c["mlp_layer_types"], c["heads_per_layer"])):
        pre = "layer%d_" % i
        fn = functools.partial(block, kind, ffn, h, pre)
        own = {k: v for k, v in params.items() if k.startswith(pre)}
        x, said = (jax.checkpoint(fn) if remat else fn)(own, x)
        if said:
            routed[pre] = said
    return st(_rmsnorm(x, st(params["final_norm_gamma"]), c["eps"])), routed


def loss_logprob_loads(params, ids, labels, args, rows, precision=None,
                       remat=True):
    """``(mean next-token cross-entropy over all positions of ids [B, T],
    (log-probabilities [len(rows), vocab] at the flat positions rows, the
    expert layers' loads by state name))``. The head and the loss go in
    blocks of rows, so that the ``[B*T, vocab]`` float32 logits never exist
    whole."""
    st, mm = _ROUND[precision]
    x, routed = hidden_states(params, ids, args, precision, remat)
    load = {pre + "ffn_" + STATE: r["load"] for pre, r in routed.items()}
    w = mm(st(params["lm_head_weight"]))

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
        routed = hidden_states(params, ids, args, remat=False,
                               rates=rates)[1]
        return tuple({pre + "ffn_" + STATE: r[what]
                      for pre, r in routed.items()}
                     for what in ("bias", "load"))

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
    alone. ``rows [B, n]`` are positions within each sequence. The batch
    goes one sequence at a time, gradients and loads added up (the loss is a
    mean over tokens, no layer looks across sequences)."""
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
    pass (``olmo_hybrid.follow``, PR 30). The weights move once every pass
    has been, from the moments alone."""
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
            ("laguna.grad", _tag(args), names)))
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
            ("laguna.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``), by the node's layer and name: a block's
    ``_ffn_*`` nodes (its norm and add among them) are its feed-forward,
    dense or of experts (the op apart from the shared expert beside it); the
    rest its attention: the op of a WINDOWED layer ``attention_window_
    kernel``, the op of a full layer ``attention_kernel``, and both kinds'
    projections, gate, norm and add ``attention_proj``. The parts the
    language-model readers of the benchmark know keep their names."""
    c = config(args)
    kinds, ffns = c["layer_types"], c["mlp_layer_types"]
    layer = re.compile(r"layer(\d+)_(ffn_)?")

    def part(phase, op, node):
        if phase == "update":
            return "optimizer"
        if phase == "metric":
            return "lm_head_loss"
        m = layer.match(node)
        if m and int(m.group(1)) < len(kinds):
            i = int(m.group(1))
            if m.group(2):
                if ffns[i] == "dense":
                    return "dense_ffn"
                return "moe_grouped_matmul" if op == "RoutedExperts" \
                    else "moe_rest"
            if op != "CausalAttention":
                return "attention_proj"
            return "attention_window_kernel" \
                if kinds[i] == "sliding_attention" else "attention_kernel"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def useful_pairs(t, window=0):
    """The (query, key) pairs of one sequence of ``t`` positions that hold a
    score: ``sum_i min(i + 1, window)``, every earlier key without one."""
    w = min(window, t) if window else t
    return w * (w + 1) // 2 + (t - w) * w


def layer_cost(kind, args, tokens, itemsize=2, heads=None):
    """Forward operations of one block's part ``kind`` (``"full_attention"``,
    ``"sliding_attention"``, ``"dense"``, ``"experts"``) over ``tokens``
    positions, and the bytes it cannot avoid: ``{part: (flops, bytes)}``. A
    matmul of ``[m, k] x [k, n]`` is ``2 m k n``.

    An attention kernel is counted by its USEFUL pairs, whatever blocks a
    kernel runs: ``useful_pairs`` a sequence (the causal half, or under the
    window ``sum_i min(i + 1, w)``) x ``heads`` query heads x ``2 x 2 x
    head_dim`` (the score and the weighted sum, a multiply and an add a
    column); its bytes q and the result a query head, k and v a key/value
    head, each once. The routed experts are counted by the EVEN share of the
    pairs (``tokens x top_k x held / total`` rows through three matrices):
    ``fit_lm_ref`` hands ``step_cost`` no routed rows, so this yardstick does
    not move with the routing. Other bytes: each matrix read once in the
    compute dtype, each boundary activation read and written once."""
    c = config(args)
    d, t = c["hidden"], c["seq_len"]
    act = tokens * d * itemsize
    if kind in ("full_attention", "sliding_attention"):
        h, hkv, hd = heads, c["kv_heads"], c["head_dim"]
        gate = {"head": 1, "elementwise": hd, "none": 0}[c["attn_gate"]] * h
        weights = d * (h * hd + 2 * hkv * hd + gate) + h * hd * d
        # u read; q, k, v, the gate, the kernel's result and the gated
        # result written and read again; the output written
        between = h * hd + 2 * hkv * hd + gate + 2 * h * hd
        window = c["window"] if kind == "sliding_attention" else 0
        part = "attention_window_kernel" if window else "attention_kernel"
        return {"attention_proj": (
            2 * tokens * weights,
            weights * itemsize + 2 * act + 2 * tokens * between * itemsize),
            part: ((tokens // t) * useful_pairs(t, window) * h * 2 * 2 * hd,
                   tokens * (2 * h * hd + 2 * hkv * hd) * itemsize)}
    if kind == "dense":
        f = c["dense_hidden"]
        return {"dense_ffn": (3 * 2 * tokens * d * f,
                              3 * d * f * itemsize + 2 * act)}
    if kind == "experts":
        f, e, held, fs = c["expert_hidden"], c["experts_total"], \
            c["experts_held"], c["shared_hidden"]
        rows = tokens * c["top_k"] * held // e
        return {"moe_grouped_matmul": (
            3 * 2 * rows * d * f,
            3 * held * d * f * itemsize + 2 * rows * d * itemsize),
            "moe_rest": (2 * tokens * d * e + 3 * 2 * tokens * d * fs,
                         3 * d * fs * itemsize + d * e * 4 + 2 * act)}
    raise ValueError(kind)


def step_cost(args, batch, itemsize=2):
    """A training step's useful operations and least bytes: ``{"flops",
    "bytes", "recompute_flops", "parts": {part: (flops, bytes)}}``. Every
    part three times over (forward, and the two products of the backward
    pass); the forward pass that recomputation repeats is stated separately
    and is NOT among the useful operations. Bytes: the forward pass's three
    times over, plus each parameter's float32 master, gradient and two Adam
    moments read and the master and moments written (16 + 12 bytes) and its
    compute-dtype copy written."""
    c = config(args)
    tokens = batch * c["seq_len"]
    parts = {}
    for mix, ffn, h in zip(c["layer_types"], c["mlp_layer_types"],
                           c["heads_per_layer"]):
        for kind in (mix, "dense" if ffn == "dense" else "experts"):
            for name, cost in layer_cost(kind, args, tokens, itemsize,
                                         h).items():
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
