"""The plain reference of ``bailing_hybrid`` language models (Ling 3.0):
forward pass, loss, gradients and Adam, in ``jax.numpy`` and float32
(``follow`` and ``forward_logprob`` set
``jax.default_matmul_precision("highest")``), no kernels, no chunks, no WY
form, no sub-chunks, no layout of rows by expert, nothing of the program.
Also this architecture's count of a step's operations and bytes
(``step_cost``), its parts of the step by scope (``part_of``) and the
lowering counters a traced run prints (``LOWERINGS``), kept with the
benchmark: everything model-shaped that ``drivers/fit_lm_ref.py`` asks for.

The architecture (inclusionAI/Ling-3.0-flash ``config.json``, ``model_type:
bailing_hybrid``): ``hidden`` d = 2,560, vocabulary 157,184, untied head,
42 blocks, ``rms_norm_eps`` 1e-6, no bias anywhere. What no paper pins down
is a READING of a key, marked (+) and listed under ``assumed`` in the
configuration's file.

* Block (pre-norm+): ``h = x + Mixer(RMSNorm_d(x))``, ``y = h +
  FFN(RMSNorm_d(h))``; after the last block ``RMSNorm_d``, the head,
  next-token cross-entropy, mean over tokens.
* ``kda`` (every layer whose published index i has ``(i + 1) % 6 != 0``+):
  Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) over H = 32 heads, K
  = V = 128. With ``u`` the normed input: ``q~, k~, v = silu(conv4(W u))``
  (depthwise causal convolution of 4, zeros before the sequence's start;
  SiLU+ after each, none on ``v`` beyond it); per head ``q = q~ / |q~|_2 /
  sqrt(K)``, ``k = k~ / |k~|_2`` (the root over ``|x|^2 + 1e-6``); ``a = W_a
  u`` in R^{H x K} (ONE full-rank matrix+); ``g = kda_lower_bound *
  sigmoid(exp(A_log_h) (a + dt_bias))``+, every channel's log-decay in (-5,
  0); ``beta = sigmoid(W_b u)`` in R^H; per head ``S_t = (I - beta_t k_t
  k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``,
  ``S_0 = 0``; ``Mixer = W_o (RMSNorm(o) * sigmoid(W_g u))``, the norm ONE
  group over a position's H x V columns under a gamma of that width+, the
  gate elementwise after it.
* ``latent_attention`` (the other layers): ``[q_n; q_r]_h = (W_q u)_h`` at
  n + r = 128 + 64 (no query latent); ``[c_kv; k_r] = W_kva u`` in
  R^{512+64}; ``c = RMSNorm_512(c_kv)``; ``[k_n; val]_h = (W_kvb c)_h`` at n
  + v = 128 + 128; ``q_h = [q_n,h; rope_t(q_r,h)]``, ``k_h = [k_n,h;
  rope_t(k_r)]``, the SAME rotated ``k_r`` in every head, ``rope_theta``
  6e6, the half-split convention; ``o_h = softmax_causal(q_h k_h^T / sqrt(n
  + r)) val_h``, keys of 192 and values of 128 as they are; ``o_h <- o_h *
  sigmoid((W_gate u)_h)``, one scalar a head+; ``W_o`` over H x v.
* Dense feed-forward (the first ``first_k_dense_replace`` blocks):
  ``W_down (silu(W_gate h') * W_up h')`` at 6,144.
* Expert feed-forward (the rest): ``s = sigmoid(W_r h')`` over 512 experts
  in float32; ``s' = s + b``; the 8 groups of 64 consecutive experts are
  scored by the sum of their 2 largest ``s'``, the 4 best groups kept, the
  8 largest ``s'`` taken inside them (written as MASKS here: ranks by
  comparison, no top-k of groups); ``w_e = 2.5 s_e / (sum of the chosen s +
  1e-20)``; ``FFN = sum_e w_e E_e(h') + E_shared(h')``, every ``E`` the
  gated form at 768. Only ``experts_held`` experts from ``first_expert``
  are here: rows routed elsewhere add nothing. ``b`` is a STATE no gradient
  reaches; after every step ``b_e += bias_update_rate * sign(mean load -
  load_e)`` (``balance_step``).
* ``swiglu_limits`` (the published ``expert_swiglu_limit_list`` /
  ``share_expert_swiglu_limit_list``): a non-zero limit raises, here as in
  the program; the clamp's form is not stated by the config.
* Multi-token prediction is left out (``mtp_loss_scaling_factor`` 0: the
  module adds nothing to the loss).

Departures from the published description, each for memory or for the cut
and none in the mathematics: the recurrence is a ``lax.scan`` over
positions cut into checkpointed stretches of ``chunk`` positions (a plain
scan would keep 8,192 states for the backward pass); attention's softmax
goes in blocks of queries, each against all keys under the mask; the dense
feed-forward, the head and the loss go in blocks of rows; experts are a
loop over the held experts with a mask, every expert computing every row;
each block is recomputed in the backward pass.

``init_params``: as the siblings (normal, std 1/sqrt(fan-in)), the decay
gate's parameters drawn so that the gates neither close nor vanish at the
start (its docstring), and with ``init.balance`` the selection biases
start where the balancing rule settles.

``precision`` (``loss_and_logprob``): ``None`` float32; ``"bfloat16"`` the
stated precision's floor (every tensor an operator of the program reads or
writes rounded to bfloat16, arithmetic inside float32; the delta rule is
ONE operator, its inside float32); the controls, each the bfloat16 pipeline
with ONE thing wrong: ``"int8_matmul"``, ``"fp8_matmul"`` (matmul inputs at
8 bits), ``"decay_per_head"`` (a head's 128 log-decays replaced by their
mean), ``"gate_unbounded"`` (``g = -exp(A_log) softplus(a + dt_bias)``),
``"no_group_limit"`` (the 8 largest ``s'`` of all 512), ``"weights_
unnormalised"`` (the chosen scores not divided by their sum),
``"state_bf16"`` (the carried state rounded to bfloat16 every position).
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
# balancing rule, Adam as the program states it, the leaves of a tree
from .glm4_moe_lite import balance_rates, leaf_norms, zipf_ids
from .nemotron_h import _bf16, _rope, _stretch, balance_step, loads
from .olmo_hybrid import _rmsnorm, make_adam

LAYER_TYPES = tuple("latent_attention" if (i + 1) % 6 == 0 else "kda"
                    for i in range(42))
DEFAULTS = dict(
    layer_types=LAYER_TYPES, dense_layers=2, hidden=2560, vocab=157184,
    heads=32, kda_key_dim=128, kda_value_dim=128, conv_kernel=4,
    gate_floor=-5.0, kda_norm_groups=1, kv_rank=512, nope_dim=128,
    rope_dim=64, v_dim=128, rope_theta=6000000.0, dense_hidden=6144,
    experts_total=512, experts_held=512, first_expert=0, top_k=8, n_group=8,
    topk_group=4, routed_scale=2.5, expert_hidden=768, shared_experts=1,
    swiglu_limits=None, eps=1e-6, seq_len=8192, chunk=64,
    bias_update_rate=0.0)
STATE = "experts_select_bias"   # the leaves that are states, by suffix
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.delta_rule_gate.channel",
             "lower.delta_rule_gate.head",
             "lower.delta_rule_kernel.pallas_chunked",
             "lower.delta_rule_kernel.xla_chunked",
             "lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise",
             "lower.experts_body.swiglu",
             "lower.experts_kernel.pallas_grouped",
             "lower.experts_kernel.xla_loop")
NORM_EPS = 1e-6         # under the root of |q|^2, |k|^2
ATTN_BLOCK = 256
ROW_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)

CONTROLS = ("int8_matmul", "fp8_matmul", "decay_per_head", "gate_unbounded",
            "no_group_limit", "weights_unnormalised", "state_bf16")
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
        raise ValueError("bailing_hybrid: unknown arguments %s"
                         % sorted(unknown))
    cfg.update(args)
    cfg["layer_types"] = tuple(cfg["layer_types"])
    for which, limits in zip(("expert", "share_expert"),
                             cfg["swiglu_limits"] or ()):
        if any(limits):
            raise ValueError(
                "bailing_hybrid: %s_swiglu_limit_list %s: the config names a "
                "limit and not the clamp's form, and none is guessed here"
                % (which, list(limits)))
    return cfg


def _tag(args):
    """``args`` as something ``repr`` orders the same in every process."""
    def plain(v):
        return tuple(plain(x) for x in v) if isinstance(v, (list, tuple)) \
            else v

    return sorted((k, plain(v)) for k, v in args.items())


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args, states=True):
    """The program's parameter names -> shapes, in the program's order;
    with ``states`` the selection biases too, each after its router."""
    c = config(args)
    d, h = c["hidden"], c["heads"]
    hk, hv = h * c["kda_key_dim"], h * c["kda_value_dim"]
    n, r, v = c["nope_dim"], c["rope_dim"], c["v_dim"]
    out = {"embed_weight": (c["vocab"], d)}
    for i, kind in enumerate(c["layer_types"]):
        p = "layer%d_" % i
        out[p + "mixer_norm_gamma"] = (d,)
        if kind == "kda":
            for part, w in (("q", hk), ("k", hk), ("v", hv)):
                out[p + part + "_weight"] = (w, d)
                out[p + part + "conv_weight"] = (w, c["conv_kernel"])
            out[p + "a_weight"] = (hk, d)
            out[p + "b_weight"] = (h, d)
            out[p + "delta_A_log"] = (h,)
            out[p + "delta_dt_bias"] = (hk,)
            out[p + "gnorm_gamma"] = (hv,)
            out[p + "g_weight"] = (hv, d)
            out[p + "o_weight"] = (d, hv)
        elif kind == "latent_attention":
            out[p + "q_weight"] = (h * (n + r), d)
            out[p + "kv_a_weight"] = (c["kv_rank"] + r, d)
            out[p + "kv_norm_gamma"] = (c["kv_rank"],)
            out[p + "kv_b_weight"] = (h * (n + v), c["kv_rank"])
            out[p + "gate_weight"] = (h, d)
            out[p + "o_weight"] = (d, h * v)
        else:
            raise ValueError("layer %d is %r" % (i, kind))
        out[p + "ffn_norm_gamma"] = (d,)
        if i < c["dense_layers"]:
            ffn = [("ffn_", c["dense_hidden"])]
        else:
            held, f = c["experts_held"], c["expert_hidden"]
            out[p + "ffn_experts_router_weight"] = (d, c["experts_total"])
            if states:
                out[p + "ffn_" + STATE] = (c["experts_total"],)
            out[p + "ffn_experts_gate_weight"] = (held, d, f)
            out[p + "ffn_experts_up_weight"] = (held, d, f)
            out[p + "ffn_experts_down_weight"] = (held, f, d)
            ffn = [("ffn_shared_", c["shared_experts"] * f)] \
                if c["shared_experts"] else []
        for pre, width in ffn:
            out[p + pre + "gate_weight"] = (width, d)
            out[p + pre + "up_weight"] = (width, d)
            out[p + pre + "down_weight"] = (d, width)
    out["final_norm_gamma"] = (d,)
    out["lm_head_weight"] = (c["vocab"], d)
    return out


def _fan_in(name, shape):
    if name == "embed_weight":
        return 1
    if "_ffn_experts_" in name:     # stacked [held, in, out]; router [in, E]
        return shape[-2]
    return shape[-1]


def init_params(args, seed_key, init=None):
    """Every parameter and state from the key, float32, on the device.
    Matrices: normal, std 1/sqrt(fan-in) (the embedding std 1; a
    convolution's fan-in is its kernel) from ONE generator run ``DRAWS``
    times over slices of one buffer, the convolutions' narrow weights from a
    draw of their own (``olmo_hybrid.init_params``, PR 30: both for what
    the chip's tiling does to the other ways); norm weights 1.

    **The decay gate**, ``g = floor * sigmoid(A (a + dt_bias))``, ``a = W_a
    u``: ``init["gate_rate"] = [lo, hi]``: ``A = exp(A_log)`` uniform a
    head; ``init["decay"] = [lo, hi]``: the log-decay ``-g`` a position that
    a channel starts from at ``a = 0``, log-uniform a channel (``dt_bias =
    logit(-g / |floor|) / A``); ``init["decay_gate_scale"]``: ``W_a`` at
    that share of its fan-in scale. The defaults, [1, 4], [0.001, 1.0] and
    0.1, start every channel's decay ``exp(g)`` between 0.37 and 0.999, as
    the family draws a state-space layer's (``dt`` in [0.001, 0.1] x ``A``
    in [1, 16]), and keep it within a factor ``exp(+-A |a|)``, ``A |a|``
    of order 0.1 to 1, of where it was drawn: no gate closes (at full
    scale ``W_a u`` has the residual stream's rms, 1 to 2.6, times ``A`` up
    to 4: one position in a few saturates the sigmoid, ``exp(g)`` = 0.007,
    the state is wiped and the gated norm multiplies that position's
    gradient: the Olmo configuration's lesson, PR 30) and none vanishes (the
    family's own draw for the softplus form, ``dt_bias`` -2.3 to -6.9 under
    ``A`` up to 16, puts the bounded gate's sigmoid at ``exp(-110)``: no
    decay at all in most channels, a delta rule without its gate).

    The selection biases 0, or with ``init["balance"]`` where the family's
    balancing rule settles on one batch drawn from the same key
    (``balanced_start``). (Anything that is no dictionary, which is what
    ``tools/sweep_lr.py`` hands over, is taken as no ``init``.)"""
    if not isinstance(init, dict):
        init = {}
    c = config(args)
    shapes = param_shapes(args)
    a_lo, a_hi = init.get("gate_rate", (1.0, 4.0))
    g_lo, g_hi = init.get("decay", (0.001, 1.0))
    gate = init.get("decay_gate_scale", 0.1)
    floor = abs(c["gate_floor"]) or 1.0

    def is_narrow(name):        # a convolution's few taps a channel
        return name.endswith("conv_weight")

    sizes = {n: int(np.prod(s)) for n, s in shapes.items()
             if n.endswith("_weight")}
    wide = sum(v for n, v in sizes.items() if not is_narrow(n))
    heads = sum(s[0] for n, s in shapes.items() if n.endswith("_A_log"))
    chans = sum(s[0] for n, s in shapes.items() if n.endswith("_dt_bias"))

    def make(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        per = -(-wide // (DRAWS * 1024)) * 1024
        flat = {False: jax.lax.fori_loop(
            0, DRAWS, lambda i, buf: jax.lax.dynamic_update_slice(
                buf, jax.random.normal(jax.random.fold_in(k1, i), (per,),
                                       jnp.float32), (i * per,)),
            jnp.zeros((DRAWS * per,), jnp.float32)),
                True: jax.random.normal(
                    k3, (max(sum(sizes.values()) - wide, 1),), jnp.float32)}
        rate = a_lo + (a_hi - a_lo) * jax.random.uniform(
            k2, (max(heads, 1),), jnp.float32)
        unit = jax.random.uniform(k4, (max(chans, 1),), jnp.float32)
        out, at, head_at, chan_at, last_rate = {}, {False: 0, True: 0}, 0, \
            0, None
        for name, shape in shapes.items():
            if name.endswith("_A_log"):
                last_rate = rate[head_at:head_at + shape[0]]
                head_at += shape[0]
                out[name] = jnp.log(last_rate)
            elif name.endswith("_dt_bias"):
                g0 = jnp.exp(math.log(g_lo) + unit[chan_at:chan_at + shape[0]]
                             * (math.log(g_hi) - math.log(g_lo)))
                chan_at += shape[0]
                share = jnp.clip(g0 / floor, 1e-6, 1.0 - 1e-6)
                out[name] = (jnp.log(share) - jnp.log1p(-share)) \
                    / jnp.repeat(last_rate, shape[0] // last_rate.shape[0])
            elif name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(STATE):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                narrow = is_narrow(name)
                draw = flat[narrow][at[narrow]:at[narrow] + sizes[name]]
                out[name] = draw.reshape(shape) \
                    * ((gate if name.endswith("_a_weight") else 1.0)
                       / math.sqrt(_fan_in(name, shape)))
                at[narrow] += sizes[name]
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
def delta_rule(q, k, v, g, b, stretch, round_state=False):
    """The delta rule with a decay a key channel, one position at a time.
    ``q``, ``k [B, T, H, K]``, ``v [B, T, H, V]``, ``g [B, T, H, K]`` (the
    log-decays, <= 0), ``b [B, T, H]`` -> ``o [B, T, H, V]``: ``S_t = (I -
    b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T
    q_t``, ``S_0 = 0``."""
    bsz, t, h, dk = k.shape
    length = _stretch(t, stretch)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp                       # [B, H, ...]
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        if round_state:
            s = _bf16(s)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    @jax.checkpoint
    def some(s, inp):
        return jax.lax.scan(step, s, inp)

    def cut(x):     # [B, T, ...] -> [T/length, length, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // length, length) + x.shape[1:])

    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(some, s0, tuple(cut(x) for x in (q, k, v, g, b)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _kda(p, pre, u, c, st, mm, precision):
    t, h = c["seq_len"], c["heads"]
    dk, dv, kern = c["kda_key_dim"], c["kda_value_dim"], c["conv_kernel"]
    bsz = u.shape[0] // t
    um = mm(u)

    def proj(part):
        return st(um @ mm(st(p[pre + part + "_weight"])).T)

    def conv(part, width):
        x = jnp.pad(proj(part).reshape(bsz, t, -1),
                    ((0, 0), (kern - 1, 0), (0, 0)))
        w = st(p[pre + part + "conv_weight"])
        y = st(sum(x[:, i:i + t] * w[:, i] for i in range(kern)))
        return st(jax.nn.silu(y)).reshape(bsz, t, h, width)

    q, k, v = conv("q", dk), conv("k", dk), conv("v", dv)
    a = proj("a").reshape(bsz, t, h, dk)
    b = jax.nn.sigmoid(proj("b").reshape(bsz, t, h))
    # one operator of the program from here to ``o``: float32 inside
    q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + NORM_EPS) for x in (q, k))
    q = q * dk ** -0.5
    rate = jnp.exp(p[pre + "delta_A_log"])[:, None]
    z = a + p[pre + "delta_dt_bias"].reshape(h, dk)
    if c["gate_floor"] and precision != "gate_unbounded":
        g = c["gate_floor"] * jax.nn.sigmoid(rate * z)
    else:
        g = -rate * jax.nn.softplus(z)
    if precision == "decay_per_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    o = st(delta_rule(q, k, v, g, b, c["chunk"], precision == "state_bf16"))
    o = o.reshape(bsz * t, h * dv)
    groups = c["kda_norm_groups"]
    o = _rmsnorm(o.reshape(bsz * t, groups, -1),
                 st(p[pre + "gnorm_gamma"]).reshape(groups, -1), c["eps"])
    o = st(st(o.reshape(bsz * t, h * dv)) * st(jax.nn.sigmoid(proj("g"))))
    return st(mm(o) @ mm(st(p[pre + "o_weight"])).T)


def _latent_attention(p, pre, u, c, st, mm):
    t, h, n, r, v = (c["seq_len"], c["heads"], c["nope_dim"], c["rope_dim"],
                     c["v_dim"])
    bsz = u.shape[0] // t

    def proj(x, part):
        return st(mm(x) @ mm(st(p[pre + part + "_weight"])).T)

    q = proj(u, "q").reshape(bsz, t, h, n + r)
    kv_a = proj(u, "kv_a")
    k_r = kv_a[:, c["kv_rank"]:].reshape(bsz, t, 1, r)
    latent = st(_rmsnorm(kv_a[:, :c["kv_rank"]],
                         st(p[pre + "kv_norm_gamma"]), c["eps"]))
    kv = proj(latent, "kv_b").reshape(bsz, t, h, n + v)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_r, (bsz, t, h, r))],
                        axis=-1)
    val = kv[..., n:]
    q, k = (jnp.concatenate([x[..., :n], _rope(x[..., n:], c["rope_theta"])],
                            axis=-1) for x in (q, k))
    q, k, val = mm(st(q)), mm(st(k)), mm(val)
    blk = _stretch(t, ATTN_BLOCK)

    @jax.checkpoint
    def block(qi, start, k, val):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(n + r)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(blk))[:, None]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", mm(prob), val)

    # one block of queries after another (a lax.map, so that the compiler
    # cannot hold several blocks' scores at once)
    qb = jnp.moveaxis(q.reshape(bsz, t // blk, blk, h, n + r), 1, 0)
    out = jax.lax.map(lambda x: block(x[0], x[1], k, val),
                      (qb, jnp.arange(0, t, blk)))
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz * t, h, v))
    gate = st(jax.nn.sigmoid(proj(u, "gate")))              # [rows, H]
    out = st(out * gate[:, :, None]).reshape(bsz * t, h * v)
    return proj(out, "o")


def mixer(params, pre, kind, u, args, precision=None):
    """``Mixer(u)`` of one block, ``[rows, hidden]``, ``u`` the block's
    input after its norm."""
    c = config(args)
    st, mm = _ROUND[precision]
    if kind == "kda":
        return _kda(params, pre, u, c, st, mm, precision)
    return _latent_attention(params, pre, u, c, st, mm)


def gated(p, pre, x, st, mm):
    """``W_down (silu(W_gate x) * W_up x)`` of the matrices ``pre + gate /
    up / down``, ``[width, hidden]`` as ``FullyConnected`` holds them, in
    blocks of rows."""
    w_gate, w_up, w_down = (mm(st(p[pre + "%s_weight" % n]))
                            for n in ("gate", "up", "down"))

    @jax.checkpoint
    def rows(x):
        xm = mm(x)
        gate = st(jax.nn.silu(st(xm @ w_gate.T)))
        return st(mm(st(gate * st(xm @ w_up.T))) @ w_down.T)

    blk = _stretch(x.shape[0], ROW_BLOCK)
    return jax.lax.map(rows, x.reshape(-1, blk, x.shape[1])).reshape(x.shape)


def group_mask(choice, n_group, topk_group):
    """``[S, E]`` booleans: the experts of the ``topk_group`` groups (of
    ``n_group`` runs of consecutive experts) whose two largest ``choice``
    sum highest; a tie goes to the lower group. Written as comparisons: a
    group is kept if fewer than ``topk_group`` groups rank before it."""
    s, e = choice.shape
    groups = choice.reshape(s, n_group, e // n_group)
    first = jnp.max(groups, axis=-1, keepdims=True)
    # the largest taken out once (its first occurrence), the next largest
    at = jnp.argmax(groups, axis=-1)[..., None]
    rest = jnp.where(jnp.arange(e // n_group) == at, -jnp.inf, groups)
    score = first[..., 0] + jnp.max(rest, axis=-1)          # [S, n_group]
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (jnp.arange(n_group)[None, :] < jnp.arange(n_group)[:, None]))
    kept = jnp.sum(before, axis=-1) < topk_group            # [S, n_group]
    return jnp.repeat(kept, e // n_group, axis=1)


def balanced_bias(scores, bias, c, rates):
    """``balance_step`` over and over on ONE batch's scores ``[S, E]``, at
    ``rates`` one after another, the choice under the group limit: where
    the rule settles."""
    def body(b, rate):
        return balance_step(b, loads(choose(scores + b, c),
                                     scores.shape[1]), rate), None

    return jax.lax.scan(body, bias, rates)[0]


def choose(choice, c, precision=None):
    """The ``top_k`` experts of ``choice [S, E]`` inside the kept groups."""
    if c["n_group"] > 1 and precision != "no_group_limit":
        choice = jnp.where(group_mask(choice, c["n_group"], c["topk_group"]),
                           choice, -jnp.inf)
    return jax.lax.top_k(choice, c["top_k"])[1]


def route(p, pre, u, c, precision=None, rates=None):
    """Expert ids ``[S, k]``, combine weights ``[S, k]`` (float32; the
    router reads the layer's input unrounded by ``mm``) and the selection
    bias they were chosen with: the layer's own, or with ``rates`` the one
    ``balanced_bias`` settles at from it."""
    scores = jax.nn.sigmoid(u @ p[pre + "ffn_experts_router_weight"])
    bias = p[pre + "ffn_" + STATE]
    if rates is not None:
        bias = balanced_bias(scores, bias, c, rates)
    eid = choose(scores + bias, c, precision)
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    if precision != "weights_unnormalised":
        chosen = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)
    return eid, chosen * c["routed_scale"], bias


def routed_part(u, routed, weights, first, st, mm):
    """What experts ``first .. first + held`` add to ``FFN(u)``: a loop over
    them with a mask. ``weights`` are the ``[held, ...]`` stacks (gate, up,
    down) whose entry j is expert ``first + j``; ``routed`` what ``route``
    gave."""
    eid, wts = routed[:2]
    um = mm(u)
    total = jnp.zeros_like(u)

    @jax.checkpoint
    def expert(um, gate, up, down, w):
        a = st(st(jax.nn.silu(st(um @ mm(st(gate))))) * st(um @ mm(st(up))))
        return st(mm(a) @ mm(st(down))) * w[:, None]

    for j in range(weights[0].shape[0]):
        w = jnp.sum(jnp.where(eid == first + j, wts, 0.0), axis=1)    # [S]
        total = total + expert(um, *(x[j] for x in weights), w)
    return st(total)


def experts(p, pre, u, args, precision=None, rates=None):
    """``FFN(u)`` of an expert block, ``[rows, hidden]``, the rows each
    expert drew ``[E]`` and the selection bias they were chosen with."""
    c = config(args)
    st, mm = _ROUND[precision]
    routed = route(p, pre, u, c, precision, rates)
    out = routed_part(
        u, routed, tuple(p[pre + "ffn_experts_%s_weight" % n]
                         for n in ("gate", "up", "down")),
        c["first_expert"], st, mm)
    if c["shared_experts"]:
        out = st(out + gated(p, pre + "ffn_shared_", u, st, mm))
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
        u = st(_rmsnorm(x, st(p[pre + "mixer_norm_gamma"]), c["eps"]))
        x = st(x + mixer(p, pre, kind, u, args, precision))
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
    head and the loss go in blocks of rows, so that the ``[B*T, vocab]``
    float32 logits never exist whole."""
    st, mm = _ROUND[precision]
    x, load, _ = hidden_states(params, ids, args, precision, remat)
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
    pass, so that the float32 weights, both moments and a pass's gradients
    fit the chip beside one 8k sequence's float32 activations
    (``olmo_hybrid.follow``, PR 30). The weights move once every pass has
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
            ("bailing_hybrid.grad", _tag(args), names)))
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
            ("bailing_hybrid.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``), by the node's layer and name: a block's
    ``_ffn_*`` nodes (its norm and add among them) are its feed-forward,
    dense or of experts; the rest its mixer, by the layer's kind: the delta
    rule's op apart from its projections, convolutions, gates and gated
    norm, the attention op apart from the latent chain and the head-wise
    gate. The parts the language-model readers of the benchmark know keep
    their names."""
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
            if m.group(2):
                if i < dense:
                    return "dense_ffn"
                return "moe_grouped_matmul" if op == "RoutedExperts" \
                    else "moe_rest"
            if kinds[i] == "kda":
                return "linattn_scan" if op == "GatedDeltaRule" \
                    else "linattn_proj_conv"
            return "attention_kernel" if op == "CausalAttention" \
                else "attention_proj"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def layer_cost(kind, args, tokens, itemsize=2):
    """Forward operations of one block's part ``kind`` (``"kda"``,
    ``"latent_attention"``, ``"dense"``, ``"experts"``) over ``tokens``
    positions, and the bytes it cannot avoid: ``{part: (flops, bytes)}``. A
    matmul of ``[m, k] x [k, n]`` is ``2 m k n``.

    ``linattn_scan`` is the RECURRENCE's useful work, whatever chunking,
    sub-chunking or padding a kernel does: a position and head decays the
    state (K V multiplies), reads it with the key (2 K V), forms the
    rank-one update (2 K V) and reads it with the query (2 K V): ``7 K V``
    (the scalar-decay siblings' chunked count comes to about as much, as
    ``olmo_hybrid.layer_cost`` notes); its bytes are q, k, v, the
    per-channel gate and beta read, o written, and one float32 state a
    ``chunk`` positions and head written and read (what any backward pass
    must keep). ``attention_kernel`` is the causal half of the scores over
    ``nope_dim + rope_dim`` = 192 key columns and of the weighted sum over
    ``v_dim`` = 128 value columns a head, whatever is padded. The routed
    experts are counted by the EVEN share of the pairs (``tokens x top_k x
    held / total`` rows through three matrices): ``fit_lm_ref`` hands
    ``step_cost`` no routed rows, so this yardstick does not move with the
    routing. Bytes: each matrix read once in the compute dtype, each
    boundary activation read and written once."""
    c = config(args)
    d, h, t = c["hidden"], c["heads"], c["seq_len"]
    act = tokens * d * itemsize
    if kind == "kda":
        k, v, kern = c["kda_key_dim"], c["kda_value_dim"], c["conv_kernel"]
        wide = h * (3 * k + 2 * v + 1)          # q k a, v g, b
        proj = 2 * tokens * d * wide + 2 * tokens * h * v * d \
            + 2 * tokens * h * (2 * k + v) * kern
        proj_b = (d * wide + h * v * d) * itemsize + 2 * act \
            + 2 * tokens * wide * itemsize + 2 * tokens * h * v * itemsize
        chunks = tokens // c["chunk"]
        return {"linattn_proj_conv": (proj, proj_b),
                "linattn_scan": (
                    7 * tokens * h * k * v,
                    tokens * h * (3 * k + 2 * v + 1) * itemsize
                    + 2 * chunks * h * k * v * 4)}
    if kind == "latent_attention":
        n, r, v, kr = c["nope_dim"], c["rope_dim"], c["v_dim"], c["kv_rank"]
        weights = d * h * (n + r) + d * (kr + r) + kr * h * (n + v) \
            + d * h + h * v * d
        # u read; the latent, q, k (each head's content part and the one
        # rotary key), val, the head gates and the kernel's result written
        # and read again; the output written
        between = (kr + r) + h * (n + r) + (h * n + r) + 2 * h * v + h
        return {"attention_proj": (
            2 * tokens * weights,
            weights * itemsize + 2 * act + 2 * tokens * between * itemsize),
            "attention_kernel": (
                (tokens // t) * t * t * h * (n + r + v),
                tokens * h * (2 * (n + r) + 2 * v) * itemsize)}
    if kind == "dense":
        f = c["dense_hidden"]
        return {"dense_ffn": (3 * 2 * tokens * d * f,
                              3 * d * f * itemsize + 2 * act)}
    if kind == "experts":
        f, e, held = c["expert_hidden"], c["experts_total"], \
            c["experts_held"]
        fs = c["shared_experts"] * f
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
    for i, mix in enumerate(c["layer_types"]):
        for kind in (mix, "dense" if i < c["dense_layers"] else "experts"):
            for name, cost in layer_cost(kind, args, tokens,
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
