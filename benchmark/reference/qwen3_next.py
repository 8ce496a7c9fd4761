"""The plain reference of ``qwen3_next`` language models (Qwen3-Next):
forward pass, loss with the routers' auxiliary load-balancing term,
gradients and Adam, in ``jax.numpy`` and float32 (``follow`` and
``forward_logprob`` set ``jax.default_matmul_precision("highest")``), no
kernels, no chunks, no WY form, no layout of rows by expert, nothing of the
program. Also this architecture's count of a step's operations and bytes
(``step_cost``), its parts of the step by scope (``part_of``) and the
lowering counters a traced run prints (``LOWERINGS``), kept with the
benchmark: everything model-shaped that ``drivers/fit_lm_ref.py`` asks for.

The architecture (Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``,
``model_type: qwen3_next``, as the family's published modelling code reads
its keys): ``hidden`` d = 2,048, vocabulary 151,936, untied head, 48 blocks,
``rms_norm_eps`` 1e-6, no bias anywhere. A reading that no key pins down is
marked (+) and listed under ``assumed`` in the configuration's file.

* Block (pre-norm): ``h = x + Mixer(RMSNorm_d(x))``, ``y = h +
  MoE(RMSNorm_d(h))``; after the last block ``RMSNorm_d``, the head,
  next-token cross-entropy, mean over tokens. The family's RMSNorm is
  zero-centred, ``x / rms(x) * (1 + w)``: ``gamma = 1 + w`` here (+: the
  same function; it differs only under weight decay, which the recipe does
  not use).
* ``linear_attention`` (every layer whose published index i has ``(i + 1) %
  4 != 0``): the gated delta rule (Yang et al., arXiv:2412.06464) over Hk =
  16 query/key heads of K = 128 under Hv = 32 value heads of V = 128. With
  ``u`` the normed input: ``q~, k~, v = silu(conv4(W u))`` (depthwise causal
  convolution of 4 taps, zeros before the sequence's start, no bias); per
  KEY head ``q = q~ / |q~|_2 / sqrt(K)``, ``k = k~ / |k~|_2`` (the root over
  ``|x|^2 + 1e-6``); value head ``j`` reads key head ``j // 2``; per VALUE
  head ``beta = sigmoid(W_b u)``, ``g = -exp(A_log) softplus(W_a u +
  dt_bias)``, ``S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T
  k_t)^T``, ``o_t = S_t^T q_t``, ``S_0 = 0``; ``Mixer = W_o [RMSNorm_V(o_j)
  * gamma * silu((W_z u)_j)]_j``, ONE gamma of 128 shared by the heads, the
  norm before the gate. (The published ``in_proj_qkvz`` and ``in_proj_ba``
  hold the rows of ``W_q, W_k, W_v, W_z`` and ``W_b, W_a`` a key head's
  group at a time: the same function up to a fixed order of rows.)
* ``full_attention`` (the other layers): ``[q; gate]_h = (W_q u)_h`` at 256
  + 256 a head, 16 heads; ``k``, ``v`` 2 heads of 256; ``q_h <-
  RMSNorm_256(q_h) * gamma_q``, ``k_h <- RMSNorm_256(k_h) * gamma_k``;
  rotary over 64 of the 256 columns (``partial_rotary_factor`` 0.25,
  ``rope_theta`` 1e7, the half-split convention; the LAST 64 here as
  ``ops/attention.rope`` turns them, the first in the published code+: the
  same function up to a fixed permutation of ``W_q``'s, ``W_k``'s and the
  two gammas' columns); ``a_h = softmax_causal(q_h k_{h // 8}^T /
  sqrt(256)) v_{h // 8}``; ``a <- a * sigmoid(gate)`` elementwise; ``W_o``.
* Experts (every layer): ``p = softmax(W_r h')`` over 512 experts in
  float32; the 10 largest; ``w_e = p_e / (sum of the chosen p)``
  (``norm_topk_prob``), no scale, no selection bias; ``MoE = sum_e w_e
  E_e(h') + sigmoid(w_sg . h') E_shared(h')``, every ``E`` the gated form
  ``W_down (silu(W_gate x) * W_up x)`` at 512. Only ``experts_held``
  experts from ``first_expert`` are here: rows routed elsewhere add nothing.
* The loss: ``L = CE + c sum_layers L_aux``, ``L_aux = E sum_e f_e P_e``
  over the STEP's rows (``f_e`` the rows that chose ``e`` over the rows, no
  gradient through it; ``P_e`` the mean of ``p_e`` over the rows; E = 512,
  over all experts held or not; a layer at a time+, where the published
  helper pools the layers' rows first), ``c`` = ``aux_loss_coef`` 0.001+.
  What is REPORTED as the loss is the cross-entropy alone, as the program's
  metric is. With several sequences a step ``f`` is the step's: ``follow``
  takes the loads of all the step's rows in a pass of their own first, and
  given ``f`` the term is linear in ``p`` and adds up over the sequences.
* Multi-token prediction is left out (+).

Departures from the published description, each for memory or for the cut
and none in the mathematics: the recurrence is a ``lax.scan`` over
positions cut into checkpointed stretches of ``chunk`` positions; attention's
softmax goes in blocks of queries, each against all keys under the mask;
the shared expert, the head and the loss go in blocks of rows; experts are
a ``lax.scan`` over the held experts with a mask, every expert computing
every row; each block is recomputed in the backward pass.

``init_params``: as the siblings (normal, std 1/sqrt(fan-in)), the decay
drawn as ``olmo_hybrid`` draws it, and with ``init.balance`` the ROUTERS
start where a descent on ``L_aux`` alone leaves them on one sequence drawn
by the traffic's law (``balanced_start``): this family has no selection
bias to pre-balance.

``precision`` (``loss_terms``): ``None`` float32; ``"bfloat16"`` the stated
precision's floor (every tensor an operator of the program reads or writes
rounded to bfloat16, arithmetic inside float32; the delta rule is ONE
operator, its inside float32); the controls, each the bfloat16 pipeline with
ONE thing wrong: ``"int8_matmul"``, ``"fp8_matmul"`` (matmul inputs at 8
bits), ``"state_bf16"`` (the carried state rounded to bfloat16 every
position), ``"sigmoid_router"`` (``p = sigmoid(W_r h')``), ``"key_heads_
ungrouped"`` (each of the 32 value heads its own 64-wide slice of the
2,048-wide q and k: the projection cut wrongly), ``"no_attn_gate"``,
``"no_shared_gate"`` (the gates left out), ``"no_aux_loss"`` (``c`` = 0: no
forward pass reads ``c``, so this control reads the floor by construction;
PERF.md section 2).
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, train
# what the sibling references define and this one computes alike: the
# rounding the compiler may not drop, the rotation, RMSNorm, the
# position-wise recurrence (written for a decay a key channel: one decay a
# head is one channel, broadcast), the gated feed-forward in blocks of rows,
# Adam as the program states it, the leaves of a tree, the traffic's ids
from .bailing_hybrid import delta_rule, gated
from .glm4_moe_lite import balance_rates, leaf_norms, zipf_ids
from .nemotron_h import _bf16, _rope, _stretch, loads
from .olmo_hybrid import _rmsnorm, make_adam

LAYER_TYPES = tuple("full_attention" if (i + 1) % 4 == 0
                    else "linear_attention" for i in range(48))
DEFAULTS = dict(
    layer_types=LAYER_TYPES, hidden=2048, vocab=151936, heads=16, kv_heads=2,
    head_dim=256, rotary_dim=64, rope_theta=10000000.0, linear_key_heads=16,
    linear_value_heads=32, linear_key_dim=128, linear_value_dim=128,
    conv_kernel=4, experts_total=512, experts_held=512, first_expert=0,
    top_k=10, expert_hidden=512, shared_hidden=512, aux_loss_coef=0.001,
    eps=1e-6, seq_len=8192, chunk=64)
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.delta_rule_heads.grouped",
             "lower.delta_rule_heads.equal",
             "lower.delta_rule_gate.head",
             "lower.delta_rule_kernel.pallas_chunked",
             "lower.delta_rule_kernel.xla_chunked",
             "lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise",
             "lower.experts_score.softmax",
             "lower.experts_score.sigmoid",
             "lower.experts_body.swiglu",
             "lower.experts_kernel.pallas_grouped",
             "lower.experts_kernel.xla_loop")
NORM_EPS = 1e-6         # under the root of |q|^2, |k|^2
ATTN_BLOCK = 256
ROW_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)

CONTROLS = ("int8_matmul", "fp8_matmul", "state_bf16", "sigmoid_router",
            "key_heads_ungrouped", "no_attn_gate", "no_shared_gate",
            "no_aux_loss")
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
        raise ValueError("qwen3_next: unknown arguments %s" % sorted(unknown))
    cfg.update(args)
    cfg["layer_types"] = tuple(cfg["layer_types"])
    return cfg


def _tag(args):
    """``args`` as something ``repr`` orders the same in every process."""
    return sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                  for k, v in args.items())


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args):
    """The program's parameter names -> shapes, in the program's order."""
    c = config(args)
    d, hd = c["hidden"], c["head_dim"]
    hk = c["linear_key_heads"] * c["linear_key_dim"]
    hv = c["linear_value_heads"] * c["linear_value_dim"]
    out = {"embed_weight": (c["vocab"], d)}
    for i, kind in enumerate(c["layer_types"]):
        p = "layer%d_" % i
        out[p + "mixer_norm_gamma"] = (d,)
        if kind == "linear_attention":
            for part, w in (("q", hk), ("k", hk), ("v", hv)):
                out[p + part + "_weight"] = (w, d)
                out[p + part + "conv_weight"] = (w, c["conv_kernel"])
            out[p + "a_weight"] = (c["linear_value_heads"], d)
            out[p + "b_weight"] = (c["linear_value_heads"], d)
            out[p + "delta_A_log"] = (c["linear_value_heads"],)
            out[p + "delta_dt_bias"] = (c["linear_value_heads"],)
            out[p + "g_weight"] = (hv, d)
            out[p + "gnorm_gamma"] = (c["linear_value_dim"],)
            out[p + "o_weight"] = (d, hv)
        elif kind == "full_attention":
            out[p + "q_weight"] = (c["heads"] * 2 * hd, d)
            out[p + "qnorm_gamma"] = (hd,)
            out[p + "k_weight"] = (c["kv_heads"] * hd, d)
            out[p + "knorm_gamma"] = (hd,)
            out[p + "v_weight"] = (c["kv_heads"] * hd, d)
            out[p + "o_weight"] = (d, c["heads"] * hd)
        else:
            raise ValueError("layer %d is %r" % (i, kind))
        out[p + "ffn_norm_gamma"] = (d,)
        held, f = c["experts_held"], c["expert_hidden"]
        out[p + "ffn_experts_router_weight"] = (d, c["experts_total"])
        out[p + "ffn_experts_gate_weight"] = (held, d, f)
        out[p + "ffn_experts_up_weight"] = (held, d, f)
        out[p + "ffn_experts_down_weight"] = (held, f, d)
        if c["shared_hidden"]:
            out[p + "ffn_sgate_weight"] = (1, d)
            out[p + "ffn_shared_gate_weight"] = (c["shared_hidden"], d)
            out[p + "ffn_shared_up_weight"] = (c["shared_hidden"], d)
            out[p + "ffn_shared_down_weight"] = (d, c["shared_hidden"])
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
    """Every parameter from the key, float32, on the device. Matrices:
    normal, std 1/sqrt(fan-in) (the embedding std 1; a convolution's fan-in
    is its kernel) from ONE generator run ``DRAWS`` times over slices of one
    buffer, the convolutions' narrow weights from a draw of their own
    (``olmo_hybrid.init_params``, PR 30: both for what the chip's tiling
    does to the other ways); norm weights 1 (the zero-centred ``w`` = 0).
    The delta rule's decay a VALUE head as ``olmo_hybrid`` draws it, for its
    reasons: ``init["decay"]``: ``A`` uniform (``A_log`` its log),
    ``init["time_step"] = [min, max, floor]``: ``dt`` log-uniform
    (``dt_bias`` its inverse softplus), ``init["decay_gate_scale"]``:
    ``W_a`` at that share of its fan-in scale, so that no position closes a
    gate.

    With ``init["balance"]`` (``{"from", "to", "steps", "hold",
    "zipf_exponent"}``) the ROUTERS start where a descent on the auxiliary
    loss alone leaves them on one sequence drawn from the same key by the
    traffic's law (``balanced_start``), "as from a checkpoint": this family
    balances by that loss and has no selection bias to set. (Anything that
    is no dictionary, which is what ``tools/sweep_lr.py`` hands over, is
    taken as no ``init``.)"""
    if not isinstance(init, dict):
        init = {}
    c = config(args)
    shapes = param_shapes(args)
    tmin, tmax, tfloor = init.get("time_step", (0.001, 0.1, 1e-4))
    lo, hi = init.get("decay", (1.0, 16.0))
    gate = init.get("decay_gate_scale", 0.1)

    def is_narrow(name):        # a convolution's few taps a channel
        return name.endswith("conv_weight")

    sizes = {n: int(np.prod(s)) for n, s in shapes.items()
             if n.endswith("_weight")}
    wide = sum(v for n, v in sizes.items() if not is_narrow(n))
    heads = sum(s[0] for n, s in shapes.items() if n.endswith("_A_log"))

    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        per = -(-wide // (DRAWS * 1024)) * 1024
        flat = {False: jax.lax.fori_loop(
            0, DRAWS, lambda i, buf: jax.lax.dynamic_update_slice(
                buf, jax.random.normal(jax.random.fold_in(k1, i), (per,),
                                       jnp.float32), (i * per,)),
            jnp.zeros((DRAWS * per,), jnp.float32)),
                True: jax.random.normal(
                    k3, (max(sum(sizes.values()) - wide, 1),), jnp.float32)}
        unit = jax.random.uniform(k2, (2, max(heads, 1)), jnp.float32)
        out, at, head_at = {}, {False: 0, True: 0}, [0, 0]

        def take_unit(row, n):
            got = unit[row, head_at[row]:head_at[row] + n]
            head_at[row] += n
            return got

        for name, shape in shapes.items():
            if name.endswith("_A_log"):
                out[name] = jnp.log(lo + (hi - lo) * take_unit(0, shape[0]))
            elif name.endswith("_dt_bias"):
                dt = jnp.exp(math.log(tmin) + take_unit(1, shape[0])
                             * (math.log(tmax) - math.log(tmin)))
                dt = jnp.maximum(dt, tfloor)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
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
    if balance:
        ids = zipf_ids(jax.random.fold_in(seed_key, 999), c["vocab"],
                       c["seq_len"], balance.get("zipf_exponent", 1.0))
        routers, before, load = balanced_start(args, params, ids,
                                               balance_rates(balance))
        params.update({k: jnp.asarray(v) for k, v in routers.items()})
        print("balanced start: rows of the drawn batch by expert, largest / "
              "mean by layer: %s (the routers as drawn: %s)" % (
                  "  ".join("%d / %.0f" % (v.max(), v.mean())
                            for _, v in sorted(load.items())),
                  "  ".join("%d" % v.max()
                            for _, v in sorted(before.items()))), flush=True)
    return params


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def _linear_attention(p, pre, u, c, st, mm, precision):
    t, hk, hv = c["seq_len"], c["linear_key_heads"], c["linear_value_heads"]
    dk, dv, kern = c["linear_key_dim"], c["linear_value_dim"], \
        c["conv_kernel"]
    bsz = u.shape[0] // t
    um = mm(u)

    def proj(part):
        return st(um @ mm(st(p[pre + part + "_weight"])).T)

    def conv(part):
        x = jnp.pad(proj(part).reshape(bsz, t, -1),
                    ((0, 0), (kern - 1, 0), (0, 0)))
        w = st(p[pre + part + "conv_weight"])
        y = st(sum(x[:, i:i + t] * w[:, i] for i in range(kern)))
        return st(jax.nn.silu(y))

    q, k, v = conv("q"), conv("k"), conv("v").reshape(bsz, t, hv, dv)
    a, b = (proj(part).reshape(bsz, t, hv) for part in "ab")
    # one operator of the program from here to ``o``: float32 inside
    if precision == "key_heads_ungrouped":
        # the projection cut into one slice a VALUE head: another model
        hk, dk = hv, hk * dk // hv
    q, k = (x.reshape(bsz, t, hk, dk) for x in (q, k))
    q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + NORM_EPS) for x in (q, k))
    q = q * dk ** -0.5
    # value head j reads key head j // (Hv / Hk)
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    g = -jnp.exp(p[pre + "delta_A_log"]) \
        * jax.nn.softplus(a + p[pre + "delta_dt_bias"])
    # S_t = e^{g_t} S_{t-1} + b_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T, o_t =
    # S_t^T q_t, a position at a time
    o = st(delta_rule(q, k, v, g[..., None], jax.nn.sigmoid(b), c["chunk"],
                      precision == "state_bf16"))
    gate = jax.nn.silu(proj("g").reshape(bsz, t, hv, dv))
    o = st(_rmsnorm(o, st(p[pre + "gnorm_gamma"]), c["eps"]) * gate)
    return st(mm(o.reshape(bsz * t, hv * dv))
              @ mm(st(p[pre + "o_weight"])).T)


def _full_attention(p, pre, u, c, st, mm, precision):
    t, h, hkv, d, r = (c["seq_len"], c["heads"], c["kv_heads"],
                       c["head_dim"], c["rotary_dim"])
    bsz = u.shape[0] // t
    um = mm(u)

    def proj(part):
        return st(um @ mm(st(p[pre + part + "_weight"])).T)

    qg = proj("q").reshape(bsz, t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    q = st(_rmsnorm(q, st(p[pre + "qnorm_gamma"]), c["eps"]))
    k = st(_rmsnorm(proj("k").reshape(bsz, t, hkv, d),
                    st(p[pre + "knorm_gamma"]), c["eps"]))
    v = proj("v").reshape(bsz, t, hkv, d)
    # the last ``rotary_dim`` columns turn, as the program's do
    q, k = (jnp.concatenate([x[..., :d - r],
                             _rope(x[..., d - r:], c["rope_theta"])], axis=-1)
            for x in (q, k))
    k = mm(st(jnp.repeat(k, h // hkv, axis=2)))   # plain: keys repeated
    v = mm(jnp.repeat(v, h // hkv, axis=2))
    q = mm(st(q))
    blk = _stretch(t, ATTN_BLOCK)

    @jax.checkpoint
    def block(qi, start, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(blk))[:, None]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", mm(prob), v)

    # one block of queries after another (a lax.map, so that the compiler
    # cannot hold several blocks' scores at once)
    qb = jnp.moveaxis(q.reshape(bsz, t // blk, blk, h, d), 1, 0)
    out = jax.lax.map(lambda x: block(x[0], x[1], k, v),
                      (qb, jnp.arange(0, t, blk)))
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz, t, h, d))
    if precision != "no_attn_gate":
        out = st(out * st(jax.nn.sigmoid(gate)))
    return st(mm(out.reshape(bsz * t, h * d))
              @ mm(st(p[pre + "o_weight"])).T)


def mixer(params, pre, kind, u, args, precision=None):
    """``Mixer(u)`` of one block, ``[rows, hidden]``, ``u`` the block's
    input after its norm."""
    c = config(args)
    st, mm = _ROUND[precision]
    if kind == "linear_attention":
        return _linear_attention(params, pre, u, c, st, mm, precision)
    return _full_attention(params, pre, u, c, st, mm, precision)


def scores(logits, precision=None):
    """The router's scores of ``logits [S, E]``: softmax over the experts."""
    if precision == "sigmoid_router":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def aux_loss(p, load):
    """``E sum_e f_e P_e`` of one layer: ``p [S, E]`` the router's scores,
    ``load [E]`` the rows each expert drew of the ``S`` (``f = load / S``,
    taken as given: no gradient), ``P`` the mean of ``p`` over the rows."""
    s, e = p.shape
    return e * jnp.sum(jax.lax.stop_gradient(load) / s * jnp.mean(p, axis=0))


def balanced_router(u, w, c, rates):
    """Where a descent on ``L_aux`` alone leaves the router ``w [d, E]`` on
    ONE batch's inputs ``u [S, d]``: at each of ``rates`` a step against the
    loss's gradient (``f`` from the router's own choice at that step),
    scaled so that its rms is ``rate`` times the scale routers are drawn
    at; of the routers the descent passes through, the one whose loss read
    lowest (the choice of experts is discontinuous and the walk is not
    monotone: its LAST step left one seed in six with an expert at three
    times the mean load, PERF.md section 6, PR 44)."""
    scale = 1.0 / math.sqrt(w.shape[0])

    def loss(w):
        p = scores(u @ w)
        return aux_loss(p, loads(jax.lax.top_k(p, c["top_k"])[1],
                                 c["experts_total"]))

    def body(carry, rate):
        w, best, least = carry
        value, g = jax.value_and_grad(loss)(w)
        best = jnp.where(value < least, w, best)
        step = rate * scale * g * jax.lax.rsqrt(jnp.mean(g * g) + 1e-30)
        return (w - step, best, jnp.minimum(value, least)), None

    (last, best, least), _ = jax.lax.scan(
        body, (w, w, jnp.float32(jnp.inf)), rates)
    return jnp.where(loss(last) < least, last, best)


def route(p, pre, u, c, precision=None, rates=None):
    """What the layer's router gives ``u [S, d]`` (float32; it reads the
    layer's input unrounded by ``mm``): ``{"eid" [S, k], "wts" [S, k]: the
    chosen probabilities over their sum, "scores" [S, E], "router": the one
    they came from (the layer's own, or with ``rates`` the one
    ``balanced_router`` leaves), "own_load" [E]: the rows each expert draws
    under the layer's OWN router}``."""
    def choose(w):
        prob = scores(u @ w, precision)
        return prob, jax.lax.top_k(prob, c["top_k"])[1]

    w = p[pre + "ffn_experts_router_weight"]
    own = None
    if rates is not None:
        own = loads(choose(w)[1], c["experts_total"])
        w = balanced_router(u, w, c, rates)
    prob, eid = choose(w)
    chosen = jnp.take_along_axis(prob, eid, axis=1)
    load = loads(eid, c["experts_total"])
    return {"eid": eid, "wts": chosen / chosen.sum(axis=1, keepdims=True),
            "scores": prob, "router": w, "load": load,
            "own_load": load if own is None else own}


def routed_part(u, routed, weights, first, st, mm):
    """What experts ``first .. first + held`` add to ``MoE(u)``: one held
    expert after another, each computing EVERY row under a mask. ``weights``
    are the ``[held, ...]`` stacks (gate, up, down) whose entry j is expert
    ``first + j``; ``routed`` what ``route`` gave. (A ``lax.scan`` over the
    stacks, so that the compiler holds one expert's ``[rows, hidden]``
    float32 result at a time: unrolled, the 32 held experts' were scheduled
    side by side and a gradient pass of the cell's size did not leave Adam's
    moments their room on the chip, PERF.md section 6, PR 44.)"""
    eid, wts = routed["eid"], routed["wts"]
    um = mm(u)

    @jax.checkpoint
    def expert(um, gate, up, down, w):
        a = st(st(jax.nn.silu(st(um @ mm(st(gate))))) * st(um @ mm(st(up))))
        return st(mm(a) @ mm(st(down))) * w[:, None]

    def one(total, x):
        gate, up, down, j = x
        w = jnp.sum(jnp.where(eid == first + j, wts, 0.0), axis=1)    # [S]
        return total + expert(um, gate, up, down, w), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        tuple(weights) + (jnp.arange(weights[0].shape[0]),))
    return st(total)


def shared_part(p, pre, u, st, mm, precision=None):
    """``sigmoid(w_sg . u) * SharedExpert(u)``: what every chip that shares
    the layer computes alike."""
    out = gated(p, pre + "ffn_shared_", u, st, mm)
    if precision == "no_shared_gate":
        return out
    gate = st(jax.nn.sigmoid(st(mm(u) @ mm(st(p[pre + "ffn_sgate_weight"])).T)))
    return st(out * gate)


def experts(p, pre, u, args, precision=None, rates=None):
    """``MoE(u)`` of one block, ``[rows, hidden]``, and of what ``route``
    gave what a caller reads: ``{"load" [E]: the rows each expert drew,
    "mean_score" [E]: the router's scores' mean over the rows, "router",
    "own_load"}``."""
    c = config(args)
    st, mm = _ROUND[precision]
    routed = route(p, pre, u, c, precision, rates)
    out = routed_part(
        u, routed, tuple(p[pre + "ffn_experts_%s_weight" % n]
                         for n in ("gate", "up", "down")),
        c["first_expert"], st, mm)
    if c["shared_hidden"]:
        out = st(out + shared_part(p, pre, u, st, mm, precision))
    return out, {"load": routed["load"],
                 "mean_score": jnp.mean(routed["scores"], axis=0),
                 "router": routed["router"], "own_load": routed["own_load"]}


def hidden_states(params, ids, args, precision=None, remat=True, rates=None):
    """Token ids ``[B, T]`` -> what the head reads, ``[B*T, hidden]`` (the
    blocks and the final norm), and by layer prefix what ``experts`` says
    of its routing."""
    c = config(args)
    st, mm = _ROUND[precision]
    x = st(params["embed_weight"])[ids.reshape(-1)]

    def block(kind, pre, p, x):
        u = st(_rmsnorm(x, st(p[pre + "mixer_norm_gamma"]), c["eps"]))
        x = st(x + mixer(p, pre, kind, u, args, precision))
        u = st(_rmsnorm(x, st(p[pre + "ffn_norm_gamma"]), c["eps"]))
        out, routed = experts(p, pre, u, args, precision, rates)
        return st(x + out), routed

    routed = {}
    for i, kind in enumerate(c["layer_types"]):
        pre = "layer%d_" % i
        fn = functools.partial(block, kind, pre)
        own = {k: v for k, v in params.items() if k.startswith(pre)}
        x, routed[pre] = (jax.checkpoint(fn) if remat else fn)(own, x)
    return st(_rmsnorm(x, st(params["final_norm_gamma"]), c["eps"])), routed


def loss_terms(params, ids, labels, args, rows, precision=None, remat=True,
               f=None):
    """``(cross-entropy, sum of the layers' L_aux, log-probabilities
    [len(rows), vocab] at the flat positions rows, loads by layer)`` of
    ``ids [B, T]``: the mean next-token cross-entropy over all positions and
    ``sum_layers E sum_e f_e P_e`` with ``P`` the mean score over THESE rows
    and ``f`` by layer the share of rows that chose each expert: given
    (``{prefix: [E]}``, the step's), or these rows' own. The head and the
    loss go in blocks of rows, so that the ``[B*T, vocab]`` float32 logits
    never exist whole."""
    c = config(args)
    st, mm = _ROUND[precision]
    x, routed = hidden_states(params, ids, args, precision, remat)
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
    load = {pre: r["load"] for pre, r in routed.items()}
    aux = sum(c["experts_total"] * jnp.sum(
        (jax.lax.stop_gradient(load[pre]) / x.shape[0] if f is None
         else f[pre]) * r["mean_score"]) for pre, r in routed.items())
    return -total / x.shape[0], aux, logprob(x[rows]), load


def loss_and_logprob(params, ids, labels, args, rows, precision=None,
                     remat=True):
    """The reported loss (the cross-entropy alone) and the
    log-probabilities at ``rows``."""
    ce, _, logp, _ = loss_terms(params, ids, labels, args, rows, precision,
                                remat)
    return ce, logp


def balanced_start(args, params, ids, rates):
    """The routers a model in training would hold: one float32 forward pass
    over ``ids [B, T]`` in which each expert layer, when the pass reaches
    it, runs ``balanced_router`` on its own inputs and goes on with the
    router that leaves, so that the next layer balances on what it will
    really read. Returns ``{router's name: weight}``, float32 on the host,
    and by that name the loads on ``ids`` each layer's router as drawn would
    give on the same inputs, and the loads the routers returned give."""
    @jax.jit
    def run(params, ids, rates):
        routed = hidden_states(params, ids, args, remat=False,
                               rates=rates)[1]
        return tuple({pre: r[what] for pre, r in routed.items()}
                     for what in ("router", "own_load", "load"))

    name = "%sffn_experts_router_weight"
    with jax.default_matmul_precision("highest"):
        out = run(params, ids, jnp.asarray(rates, jnp.float32))
    return tuple({name % pre: np.asarray(v) for pre, v in part.items()}
                 for part in out)


# ---------------------------------------------------------------------------
# training: Adam as mxnet_tpu/optimizer.py states it
# ---------------------------------------------------------------------------
def grad_groups(args, n):
    """The parameter names in the program's order, cut into ``n`` runs of
    about equal size."""
    sizes = {k: int(np.prod(s)) for k, s in param_shapes(args).items()}
    share, groups, run = sum(sizes.values()) / n, [[]], 0
    for name, size in sizes.items():
        if run >= share * len(groups) and len(groups) < n:
            groups.append([])
        groups[-1].append(name)
        run += size
    return groups


def make_loads(args):
    """jitted (params, ids) -> by layer the share of the step's rows that
    chose each expert, ``f [E]``: a forward pass a sequence, the loads added
    up."""
    def run(params, ids):
        load = jax.lax.map(
            lambda s: {pre: r["load"] for pre, r in hidden_states(
                params, s[None], args, remat=False)[1].items()}, ids)
        return {pre: v.sum(axis=0) / ids.size for pre, v in load.items()}

    return jax.jit(run)


def make_grad(args, names):
    """jitted (params, ids, labels, rows, f) -> (gradients of ``names``, the
    cross-entropy, log-probabilities at ``rows``): ``CE + c sum_layers
    L_aux`` differentiated with respect to the leaves ``names`` alone,
    ``f`` by layer the step's shares (``make_loads``; ``None`` with ONE
    sequence a step, whose own they are). ``rows [B, n]`` are positions
    within each sequence. The batch goes one sequence at a time, gradients
    added up: the cross-entropy is a mean over tokens, and given ``f``
    ``L_aux`` is a mean over rows too."""
    coef = config(args)["aux_loss_coef"]

    def run(params, ids, labels, rows, f):
        rest = {k: v for k, v in params.items() if k not in names}

        def loss(sub, i, l, r):
            ce, aux, logp, _ = loss_terms({**rest, **sub}, i, l, args, r, f=f)
            return ce + coef * aux, (ce, logp)

        grad = jax.value_and_grad(loss, has_aux=True)
        sub = {k: params[k] for k in names}
        if ids.shape[0] == 1:    # no second copy of the gradients to add to
            (_, (value, logp)), g = grad(sub, ids, labels, rows[0])
            return g, value, logp

        def one(acc, seq):
            (_, (value, logp)), g = grad(sub, seq[0][None], seq[1][None],
                                         seq[2])
            return jax.tree_util.tree_map(jnp.add, acc, g), (value, logp)

        g, (values, logp) = jax.lax.scan(
            one, jax.tree_util.tree_map(jnp.zeros_like, sub),
            (ids, labels, rows))
        return (jax.tree_util.tree_map(lambda x: x / ids.shape[0], g),
                jnp.mean(values), logp.reshape((-1,) + logp.shape[2:]))

    return jax.jit(run)


def follow(args, recipe, params_host, batches, rows):
    """Follow ``len(batches)`` steps from the host copy of the seeded
    weights. Returns what ``check.compare`` reads: losses (the
    cross-entropy), the first gradient's norm (of ``CE + c sum L_aux``) and
    the parameters' change over all the steps by leaf, and the first step's
    log-probabilities at ``rows`` (``[B, n]`` positions within each
    sequence; the result is ``[B * n, vocab]``).

    A step's gradient is taken in ``GRAD_PASSES`` passes, each with respect
    to a run of the leaves, and folded into Adam's moments before the next
    pass (``olmo_hybrid.follow``, PR 30); with several sequences a step the
    routers' loads over ALL the step's rows are taken first, in a forward
    pass of their own (``make_loads``). The weights move once every pass has
    been, from the moments alone."""
    rows = jnp.asarray(rows, jnp.int32)
    several = batches[0][0].shape[0] > 1
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v) for k, v in params_host.items()}
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        moments, apply = make_adam(recipe)
        step_loads = train.compiled_once(
            make_loads(args), (p, batches[0][0]),
            ("qwen3_next.loads", _tag(args))) if several else None
        f0 = step_loads(p, batches[0][0]) if several else None
        grads = [(names, train.compiled_once(
            make_grad(args, names), (p,) + tuple(batches[0]) + (rows, f0),
            ("qwen3_next.grad", _tag(args), names)))
            for names in grad_groups(args, GRAD_PASSES)]
        losses, grad_norms, logp = [], {}, None
        for t, (ids, labels) in enumerate(batches, 1):
            f = step_loads(p, ids) if several else None
            for names, grad in grads:
                g, loss, lp = grad(p, ids, labels, rows, f)
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
        del m, v
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
            ("qwen3_next.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``), by the node's layer and name: a block's
    ``_ffn_*`` nodes (its norm and add among them) are its expert layer,
    the op apart from the gated shared expert beside it; the rest its
    mixer, by the layer's kind: the delta rule's op apart from its
    projections, convolutions and gated norm, the attention op apart from
    the query-and-gate projection, the head norms, the gate's product and
    the output projection. The parts the language-model readers of the
    benchmark know keep their names."""
    kinds = config(args)["layer_types"]
    layer = re.compile(r"layer(\d+)_(ffn_)?")

    def part(phase, op, node):
        if phase == "update":
            return "optimizer"
        if phase == "metric":
            return "lm_head_loss"
        m = layer.match(node)
        if m and int(m.group(1)) < len(kinds):
            if m.group(2):
                return "moe_grouped_matmul" if op == "RoutedExperts" \
                    else "moe_rest"
            if kinds[int(m.group(1))] == "linear_attention":
                return "linattn_scan" if op == "GatedDeltaRule" \
                    else "linattn_proj_conv"
            return "attention_kernel" if op == "CausalAttention" \
                else "attention_proj"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def layer_cost(kind, args, tokens, itemsize=2):
    """Forward operations of one block's part ``kind``
    (``"linear_attention"``, ``"full_attention"``, ``"experts"``) over
    ``tokens`` positions, and the bytes it cannot avoid: ``{part: (flops,
    bytes)}``. A matmul of ``[m, k] x [k, n]`` is ``2 m k n``.

    ``linattn_scan`` is the RECURRENCE's useful work, whatever chunking,
    padding or repeating a kernel does: a position and VALUE head decays
    the state (K V multiplies), reads it with the key (2 K V), forms the
    rank-one update (2 K V) and reads it with the query (2 K V): ``7 K V``;
    its bytes are q and k read once a KEY head, v, the gates and beta read
    and o written a value head, and one float32 state a ``chunk`` positions
    and value head written and read (what any backward pass must keep).
    ``attention_kernel`` is the causal half of the scores and of the
    weighted sum over ``head_dim`` columns a query head, q and the result a
    query head, k and v a key/value head. The routed experts are counted by
    the EVEN share of the pairs (``tokens x top_k x held / total`` rows
    through three matrices): ``fit_lm_ref`` hands ``step_cost`` no routed
    rows, so this yardstick does not move with the routing. Bytes: each
    matrix read once in the compute dtype, each boundary activation read
    and written once."""
    c = config(args)
    d, t = c["hidden"], c["seq_len"]
    act = tokens * d * itemsize
    if kind == "linear_attention":
        hk, hv = c["linear_key_heads"], c["linear_value_heads"]
        k, v, kern = c["linear_key_dim"], c["linear_value_dim"], \
            c["conv_kernel"]
        wide = 2 * hk * k + 2 * hv * v + 2 * hv         # q k, v z, b a
        proj = 2 * tokens * d * wide + 2 * tokens * hv * v * d \
            + 2 * tokens * (2 * hk * k + hv * v) * kern
        proj_b = (d * wide + hv * v * d) * itemsize + 2 * act \
            + 2 * tokens * wide * itemsize + 2 * tokens * hv * v * itemsize
        chunks = tokens // c["chunk"]
        return {"linattn_proj_conv": (proj, proj_b),
                "linattn_scan": (
                    7 * tokens * hv * k * v,
                    tokens * (2 * hk * k + 2 * hv * v + 2 * hv) * itemsize
                    + 2 * chunks * hv * k * v * 4)}
    if kind == "full_attention":
        h, hkv, hd = c["heads"], c["kv_heads"], c["head_dim"]
        weights = d * h * 2 * hd + 2 * d * hkv * hd + h * hd * d
        # u read; q with its gate, the normed q, k and its normed copy, v,
        # the kernel's result and the gated result written and read again;
        # the output written
        between = 2 * h * hd + h * hd + 2 * hkv * hd + hkv * hd + 2 * h * hd
        return {"attention_proj": (
            2 * tokens * weights,
            weights * itemsize + 2 * act + 2 * tokens * between * itemsize),
            "attention_kernel": (
                (tokens // t) * t * t * h * 2 * hd,
                tokens * (2 * h * hd + 2 * hkv * hd) * itemsize)}
    if kind == "experts":
        f, e, held, fs = c["expert_hidden"], c["experts_total"], \
            c["experts_held"], c["shared_hidden"]
        rows = tokens * c["top_k"] * held // e
        return {"moe_grouped_matmul": (
            3 * 2 * rows * d * f,
            3 * held * d * f * itemsize + 2 * rows * d * itemsize),
            "moe_rest": (2 * tokens * d * e + 3 * 2 * tokens * d * fs
                         + (2 * tokens * d if fs else 0),
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
    for mix in c["layer_types"]:
        for kind in (mix, "experts"):
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
    n_params = sum(int(np.prod(s)) for s in param_shapes(args).values())
    state_bytes = n_params * (16 + 12 + itemsize)
    parts = {k: (3 * f, 3 * b) for k, (f, b) in parts.items()}
    return {"flops": 3 * fwd, "recompute_flops": fwd,
            "bytes": sum(b for _, b in parts.values()) + state_bytes,
            "state_bytes": state_bytes, "params": n_params, "parts": parts}
