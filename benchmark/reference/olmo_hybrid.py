"""The plain reference of ``olmo_hybrid`` language models: forward pass, loss,
gradients and Adam, in ``jax.numpy`` and float32 (``follow`` and
``forward_logprob`` set ``jax.default_matmul_precision("highest")``), no
kernels, no chunks, no WY form, nothing of the program. Also this
architecture's count of a step's operations and bytes (``step_cost``), its
parts of the step by scope (``part_of``) and the lowering counters a traced
run prints (``LOWERINGS``), kept with the benchmark: everything model-shaped
that ``drivers/fit_lm_ref.py`` asks for.

The architecture (allenai/Olmo-Hybrid-7B ``config.json``, ``model_type:
olmo_hybrid``): ``hidden`` d = 3,840, vocabulary 100,352, untied head, 32
blocks, ``layer_types`` (``linear_attention`` x3, ``full_attention``) x8,
``rms_norm_eps`` 1e-6, no bias anywhere. What no key of the config names is
ASSUMED, marked (+) here and listed in the configuration file.

* Block (+: the OLMo 2 / OLMo 3 family's reordered norm): ``h = x +
  RMSNorm_d(Mixer(x))``, ``y = h + RMSNorm_d(W_down (silu(W_gate h) * W_up
  h))``, the feed-forward 11,008 wide (``hidden_act: silu``).
* ``linear_attention``: the gated delta rule (Yang et al.,
  arXiv:2412.06464) over H = 30 heads, keys ``linear_key_head_dim`` K = 96,
  values ``linear_value_head_dim`` V = 192. ``q~, k~ = silu(conv4(W_q x)),
  silu(conv4(W_k x))`` in ``[T, H, K]``, ``v = silu(conv4(W_v x))`` in ``[T,
  H, V]`` (depthwise causal convolution, ``linear_conv_kernel_dim`` 4, no
  bias+); ``q = q~ / |q~|_2 / sqrt(K)``, ``k = k~ / |k~|_2`` per head+ (the
  root taken over ``|x|^2 + 1e-6``); ``b_t = sigmoid(W_b x_t)``, doubled
  under ``linear_allow_neg_eigval``; ``a_t = exp(-exp(A_log) softplus(W_a
  x_t + dt_bias))``, ``A_log``, ``dt_bias`` float32 per head+. Per head
  ``S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T`` (``S_0 = 0``,
  S in R^{K x V}), ``o_t = S_t^T q_t``; then ``Mixer(x) = W_o [
  RMSNorm_V(o_t,h) * gamma * silu((W_g x_t)_h) ]_h``, ONE ``gamma`` in R^V
  shared by the heads+, the norm BEFORE the gate+.
* ``full_attention``: 30 query and 30 key/value heads of 128, ``q <-
  RMSNorm(W_q x) * gamma_q``, ``k <- RMSNorm(W_k x) * gamma_k`` over all the
  columns held+, no rotary (+: ``rope_parameters.rope_theta`` is null),
  causal softmax at scale 128^-1/2, ``W_o``.
* final RMSNorm, head, next-token cross-entropy (mean over tokens).

**The share by heads** (``heads_held`` of ``heads`` from ``first_head``):
every mixer projection is given at the held heads' width and ``W_o`` sums
over the held heads only; what the absent heads would add is left out, as
in the program. The query/key norm's mean square is over the columns held
(the exchange would add the other chips' sums of squares): ``qk_ms``, an
argument of this file alone, hands ``mixer`` the mean squares of ALL the
columns instead, and with it the shares add up to the uncut layer exactly
(``tests/test_olmo_hybrid.py``).

Departures from the published description, each for memory and none in the
mathematics: the recurrence is a ``lax.scan`` over positions cut into
checkpointed stretches of ``chunk`` positions (a plain scan would keep
8,192 states for the backward pass); attention's softmax goes in blocks of
queries, each against all keys under the mask; the feed-forward goes in
blocks of rows; each block is recomputed in the backward pass.

``precision`` (``loss_and_logprob``): ``None`` float32; ``"bfloat16"`` the stated
precision's floor (every tensor an operator of the program reads or writes
rounded to bfloat16, arithmetic inside float32; the delta rule is ONE
operator, its inside float32); the controls, each the bfloat16 pipeline
with one thing wrong: ``"int8_matmul"``, ``"fp8_matmul"`` (matmul inputs at
8 bits), ``"bf16_state"`` (the carried state rounded to bfloat16 every
``chunk`` positions), ``"b_undoubled"`` (``b`` in (0, 1) where the config
doubles it), ``"no_l2norm"`` (queries and keys not normalised).
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, train

LAYER_TYPES = ("linear_attention", "linear_attention", "linear_attention",
               "full_attention")
DEFAULTS = dict(
    layer_types=LAYER_TYPES * 8, hidden=3840, vocab=100352, heads=30,
    heads_held=30, first_head=0, head_dim=128, linear_key_dim=96,
    linear_value_dim=192, conv_kernel=4, ffn_hidden=11008, eps=1e-6,
    seq_len=8192, chunk=64, neg_eigval=True)
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.delta_rule_kernel.xla_chunked",
             "lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise")
NORM_EPS = 1e-6         # under the root of |q|^2, |k|^2
ATTN_BLOCK = 256
FFN_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)


def _bf16(x):
    """Round to bfloat16 as an operation the compiler may not drop
    (``nemotron_h._bf16``; PERF.md, PR 26)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# name -> (stored tensors rounded by, matmul inputs rounded by)
_ROUND = {None: (arrays._same, arrays._same),
          "bfloat16": (_bf16, arrays._same),
          "int8_matmul": (_bf16, arrays._int8),
          "fp8_matmul": (_bf16, arrays._fp8),
          "bf16_state": (_bf16, arrays._same),
          "b_undoubled": (_bf16, arrays._same),
          "no_l2norm": (_bf16, arrays._same)}


def config(args):
    cfg = dict(DEFAULTS)
    unknown = set(args) - set(cfg)
    if unknown:
        raise ValueError("olmo_hybrid: unknown arguments %s" % sorted(unknown))
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
    d, held, f = c["hidden"], c["heads_held"], c["ffn_hidden"]
    hk, hv = held * c["linear_key_dim"], held * c["linear_value_dim"]
    width = held * c["head_dim"]
    out = {"embed_weight": (c["vocab"], d)}
    for i, kind in enumerate(c["layer_types"]):
        p = "layer%d_" % i
        if kind == "linear_attention":
            for part, w in (("q", hk), ("k", hk), ("v", hv)):
                out[p + part + "_weight"] = (w, d)
                out[p + part + "conv_weight"] = (w, c["conv_kernel"])
            out[p + "a_weight"] = (held, d)
            out[p + "b_weight"] = (held, d)
            out[p + "delta_A_log"] = (held,)
            out[p + "delta_dt_bias"] = (held,)
            out[p + "gnorm_gamma"] = (c["linear_value_dim"],)
            out[p + "g_weight"] = (hv, d)
            out[p + "o_weight"] = (d, hv)
        elif kind == "full_attention":
            out[p + "q_weight"] = (width, d)
            out[p + "qnorm_gamma"] = (width,)
            out[p + "k_weight"] = (width, d)
            out[p + "knorm_gamma"] = (width,)
            out[p + "v_weight"] = (width, d)
            out[p + "o_weight"] = (d, width)
        else:
            raise ValueError("layer %d is %r" % (i, kind))
        out[p + "mixer_norm_gamma"] = (d,)
        out[p + "ffn_gate_weight"] = (f, d)
        out[p + "ffn_up_weight"] = (f, d)
        out[p + "ffn_down_weight"] = (d, f)
        out[p + "ffn_norm_gamma"] = (d,)
    out["final_norm_gamma"] = (d,)
    out["lm_head_weight"] = (c["vocab"], d)
    return out


def init_params(args, seed_key, init=None):
    """Every parameter from the key in ONE jitted call on the device,
    float32. ``init`` is the configuration's ``init``: ``{"time_step":
    [min, max, floor], "decay": [low, high], "decay_gate_scale": s}`` (or
    its ``time_step`` alone, which is what ``tools/sweep_lr.py`` hands
    over). Matrices: normal, std 1/sqrt(fan-in) (the embedding std 1; a
    convolution's fan-in is its kernel), so that every operator's output is
    of order one; norm weights 1; the delta rule's decay as the family
    draws a state-space layer's: ``A`` uniform in ``decay`` (``A_log`` its
    log), ``dt`` log-uniform in [min, max] floored at floor (``dt_bias``
    its inverse softplus), and the decay gate's projection ``W_a`` at
    ``decay_gate_scale`` of its fan-in scale, so that the step stays near
    the ``dt`` drawn. (At full scale ``W_a x`` has the residual stream's
    rms, 1 to 2.6, against a ``dt_bias`` of -2.3 to -6.9: a position in a
    few hundred then closes the gate, ``a`` < 0.05, the state is wiped,
    ``o`` is what two small terms leave of each other, and the head's
    RMSNorm multiplies that position's gradient a thousandfold: one
    position of 512 carried the whole first gradient and moved it
    fourfold under a rounding to bfloat16, in this reference alone; PR 30.
    No model in training closes its gates so: the family draws ``dt`` in
    [0.001, 0.1] for ``a`` in [0.2, 0.999].)"""
    if not isinstance(init, dict):
        init = {"time_step": init} if init else {}
    shapes = param_shapes(args)
    tmin, tmax, tfloor = init.get("time_step", (0.001, 0.1, 1e-4))
    lo, hi = init.get("decay", (1.0, 16.0))
    gate = init.get("decay_gate_scale", 0.1)

    def is_matrix(name):
        return name.endswith("_weight")

    def is_narrow(name):        # a convolution's few taps a channel
        return name.endswith("conv_weight")

    sizes = {n: int(np.prod(s)) for n, s in shapes.items() if is_matrix(n)}
    wide = sum(v for n, v in sizes.items() if not is_narrow(n))
    heads = sum(s[0] for n, s in shapes.items() if n.endswith("_A_log"))

    def make(key):
        # ONE generator for all the matrices and one uniform draw for the
        # decays, cut up: a draw a leaf makes the compiler build a
        # generator a leaf (train.init_params, PR 23). The generator runs
        # ``DRAWS`` times, each filling a slice of whole tiles of one
        # buffer in place: as one draw of 766 M numbers its temporaries
        # were 13.0 of the chip's 16.9 GB, the largest thing a run put on
        # the chip (3.5 so; the compiler's counts for a described v5e, and
        # 13.026 GB reserved on the chip; PR 30). The convolutions' narrow
        # weights have a draw of their own: the compiler turns their
        # slices of the long buffer into an ``[n, 4]`` view of all of it,
        # which the chip's tiling pads 32-fold (98 GB: refused)
        k1, k2, k3 = jax.random.split(key, 3)
        per = -(-wide // (DRAWS * 1024)) * 1024
        flat = {False: jax.lax.fori_loop(
            0, DRAWS, lambda i, buf: jax.lax.dynamic_update_slice(
                buf, jax.random.normal(jax.random.fold_in(k1, i), (per,),
                                       jnp.float32), (i * per,)),
            jnp.zeros((DRAWS * per,), jnp.float32)),
                True: jax.random.normal(k3, (sum(sizes.values()) - wide,),
                                        jnp.float32)}
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
                fan = 1 if name == "embed_weight" else shape[1]
                narrow = is_narrow(name)
                draw = flat[narrow][at[narrow]:at[narrow] + sizes[name]]
                out[name] = draw.reshape(shape) \
                    * ((gate if name.endswith("_a_weight") else 1.0)
                       / math.sqrt(fan))
                at[narrow] += sizes[name]
        return out

    return jax.jit(make)(seed_key)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def _rmsnorm(x, gamma, eps, mean_square=None):
    if mean_square is None:
        mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * gamma


def _stretch(t, want):
    """The largest divisor of ``t`` that is at most ``want``."""
    return max(d for d in range(1, want + 1) if t % d == 0)


def delta_rule(q, k, v, a, b, stretch, round_state=False):
    """The gated delta rule, one position at a time. ``q``, ``k [B, T, H,
    K]``, ``v [B, T, H, V]``, ``a``, ``b [B, T, H]`` (the decay in (0, 1)
    and the step in (0, 2)) -> ``o [B, T, H, V]``:
    ``S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T``, ``o_t =
    S_t^T q_t``, ``S_0 = 0``."""
    bsz, t, h, dk = k.shape
    length = _stretch(t, stretch)

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp                       # [B, H, ...]
        s = a_t[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    @jax.checkpoint
    def some(s, inp):
        s, o = jax.lax.scan(step, s, inp)
        if round_state:
            s = _bf16(s)
        return s, o

    def cut(x):     # [B, T, ...] -> [T/length, length, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // length, length) + x.shape[1:])

    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(some, s0, tuple(cut(x) for x in (q, k, v, a, b)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _linear_attention(p, pre, u, c, st, mm, precision):
    t, h = c["seq_len"], c["heads_held"]
    dk, dv, kern = c["linear_key_dim"], c["linear_value_dim"], \
        c["conv_kernel"]
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
    a, b = (proj(part).reshape(bsz, t, h) for part in "ab")
    # one operator of the program from here to ``o``: float32 inside
    if precision != "no_l2norm":
        q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                  + NORM_EPS) for x in (q, k))
    q = q * dk ** -0.5
    b = jax.nn.sigmoid(b)
    if c["neg_eigval"] and precision != "b_undoubled":
        b = 2.0 * b
    a = jnp.exp(-jnp.exp(p[pre + "delta_A_log"])
                * jax.nn.softplus(a + p[pre + "delta_dt_bias"]))
    o = st(delta_rule(q, k, v, a, b, c["chunk"], precision == "bf16_state"))
    gate = jax.nn.silu(proj("g").reshape(bsz, t, h, dv))
    o = st(_rmsnorm(o, st(p[pre + "gnorm_gamma"]), c["eps"]) * gate)
    return st(mm(o.reshape(bsz * t, h * dv))
              @ mm(st(p[pre + "o_weight"])).T)


def qk_mean_squares(p, pre, u):
    """The mean squares of ALL the columns of ``W_q u`` and ``W_k u``, each
    ``[rows, 1]``: what the uncut layer normalises by, and what the
    exchange between the chips that share a layer would give each."""
    return tuple(jnp.mean(jnp.square(u @ p[pre + n + "_weight"].T), axis=-1,
                          keepdims=True) for n in "qk")


def _full_attention(p, pre, u, c, st, mm, qk_ms=None):
    t, h, d = c["seq_len"], c["heads_held"], c["head_dim"]
    bsz = u.shape[0] // t
    um = mm(u)

    def proj(part):
        return st(um @ mm(st(p[pre + part + "_weight"])).T)

    ms_q, ms_k = qk_ms or (None, None)
    q = st(_rmsnorm(proj("q"), st(p[pre + "qnorm_gamma"]), c["eps"], ms_q))
    k = st(_rmsnorm(proj("k"), st(p[pre + "knorm_gamma"]), c["eps"], ms_k))
    q, k, v = (mm(x.reshape(bsz, t, h, d)) for x in (q, k, proj("v")))
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
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz * t, h * d))
    return st(mm(out) @ mm(st(p[pre + "o_weight"])).T)


def _feed_forward(p, pre, x, st, mm):
    w_gate, w_up, w_down = (mm(st(p[pre + "ffn_%s_weight" % n]))
                            for n in ("gate", "up", "down"))

    @jax.checkpoint
    def rows(x):
        xm = mm(x)
        gate = st(jax.nn.silu(st(xm @ w_gate.T)))
        return st(mm(st(gate * st(xm @ w_up.T))) @ w_down.T)

    blk = _stretch(x.shape[0], FFN_BLOCK)
    return jax.lax.map(rows, x.reshape(-1, blk, x.shape[1])).reshape(x.shape)


def mixer(params, pre, kind, x, args, precision=None, qk_ms=None):
    """``Mixer(x)`` of one block, ``[rows, hidden]``, before the block's
    norm: what the shares by heads of one layer add up in."""
    c = config(args)
    st, mm = _ROUND[precision]
    if kind == "linear_attention":
        return _linear_attention(params, pre, x, c, st, mm, precision)
    return _full_attention(params, pre, x, c, st, mm, qk_ms)


def hidden_states(params, ids, args, precision=None, remat=True):
    """Token ids ``[B, T]`` -> what the head reads, ``[B*T, hidden]``: the
    blocks and the final norm."""
    c = config(args)
    st, mm = _ROUND[precision]
    x = st(params["embed_weight"])[ids.reshape(-1)]

    def block(kind, pre, p, x):
        out = mixer(p, pre, kind, x, args, precision)
        x = st(x + st(_rmsnorm(out, st(p[pre + "mixer_norm_gamma"]),
                               c["eps"])))
        out = _feed_forward(p, pre, x, st, mm)
        return st(x + st(_rmsnorm(out, st(p[pre + "ffn_norm_gamma"]),
                                  c["eps"])))

    for i, kind in enumerate(c["layer_types"]):
        pre = "layer%d_" % i
        fn = functools.partial(block, kind, pre)
        own = {k: v for k, v in params.items() if k.startswith(pre)}
        x = (jax.checkpoint(fn) if remat else fn)(own, x)
    return st(_rmsnorm(x, st(params["final_norm_gamma"]), c["eps"]))


def loss_and_logprob(params, ids, labels, args, rows, precision=None,
                     remat=True):
    """Mean next-token cross-entropy over all positions, and the
    log-probabilities ``[len(rows), vocab]`` at the flat positions
    ``rows``. The head and the loss go in blocks of rows, so that the
    ``[B*T, vocab]`` float32 logits never exist whole."""
    st, mm = _ROUND[precision]
    x = hidden_states(params, ids, args, precision, remat)
    w = mm(st(params["lm_head_weight"]))

    def logprob(x):
        return jax.nn.log_softmax(st(mm(x) @ w.T), axis=-1)

    @jax.checkpoint
    def picked(xl):
        return jnp.sum(jnp.take_along_axis(logprob(xl[0]), xl[1][:, None],
                                           axis=1))

    blk = _stretch(x.shape[0], FFN_BLOCK)
    total = jnp.sum(jax.lax.map(picked, (x.reshape(-1, blk, x.shape[1]),
                                         labels.reshape(-1, blk))))
    return -total / x.shape[0], logprob(x[rows])


# ---------------------------------------------------------------------------
# training: Adam as mxnet_tpu/optimizer.py states it
# ---------------------------------------------------------------------------
def leaves(tree):
    """name -> array: every parameter is a leaf of its own."""
    return dict(tree)


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in leaves(tree).items()}


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


def make_grad(args, names):
    """jitted (params, ids, labels, rows) -> (gradients of ``names``, loss,
    log-probabilities at ``rows``): the mean loss over the batch's tokens
    differentiated with respect to the leaves ``names`` alone. ``rows [B,
    n]`` are positions within each sequence. The batch goes one sequence
    at a time, gradients added up (the loss is a mean over tokens, no
    layer looks across sequences)."""
    def run(params, ids, labels, rows):
        rest = {k: v for k, v in params.items() if k not in names}

        def loss(sub, i, l, r):
            return loss_and_logprob({**rest, **sub}, i, l, args, r)

        grad = jax.value_and_grad(loss, has_aux=True)
        sub = {k: params[k] for k in names}
        if ids.shape[0] == 1:    # no second copy of the gradients to add to
            (value, logp), g = grad(sub, ids, labels, rows[0])
            return g, value, logp

        def one(acc, seq):
            (value, logp), g = grad(sub, seq[0][None], seq[1][None], seq[2])
            return jax.tree_util.tree_map(jnp.add, acc, g), (value, logp)

        g, (values, logp) = jax.lax.scan(
            one, jax.tree_util.tree_map(jnp.zeros_like, sub),
            (ids, labels, rows))
        return (jax.tree_util.tree_map(lambda x: x / ids.shape[0], g),
                jnp.mean(values), logp.reshape((-1,) + logp.shape[2:]))

    return jax.jit(run)


def make_adam(recipe):
    """Adam as ``mxnet_tpu/optimizer.py`` states it, in two jitted halves:
    ``moments(m, v, grads, w)`` -> ``(m, v)`` (donated): ``g = rescale_grad
    * grad + wd * w; m = b1 m + (1-b1) g; v = b2 v + (1-b2) g^2``; and
    ``apply(w, m, v, t)`` -> ``w`` (donated): ``w -= lr sqrt(1-b2^t) /
    (1-b1^t) m / (sqrt(v) + eps)``, which needs no gradient any more."""
    lr, wd = recipe["learning_rate"], recipe.get("wd", 0.0)
    b1, b2 = recipe.get("beta1", 0.9), recipe.get("beta2", 0.999)
    eps, rescale = recipe.get("epsilon", 1e-8), recipe.get("rescale_grad",
                                                           1.0)

    def moments(m, v, grads, w):
        g = {k: rescale * grads[k] + wd * w[k] for k in grads}
        return ({k: b1 * m[k] + (1.0 - b1) * g[k] for k in g},
                {k: b2 * v[k] + (1.0 - b2) * g[k] * g[k] for k in g})

    def apply(w, m, v, t):
        step_lr = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        return {k: w[k] - step_lr * m[k] / (jnp.sqrt(v[k]) + eps) for k in w}

    return (jax.jit(moments, donate_argnums=(0, 1)),
            jax.jit(apply, donate_argnums=(0,)))


def follow(args, recipe, params_host, batches, rows):
    """Follow ``len(batches)`` steps from the host copy of the seeded
    weights. Returns what ``check.compare`` reads: losses, the first
    gradient's norm and the parameters' change over all the steps by leaf,
    and the first step's log-probabilities at ``rows`` (``[B, n]``
    positions within each sequence; the result is ``[B * n, vocab]``).

    A step's gradient is taken in ``GRAD_PASSES`` passes, each with respect to
    a run of the leaves, and folded into Adam's moments before the next
    pass: float32 weights, two moments and ALL the gradients at once, with
    one 8k sequence's float32 activations, are 17.0 of the v5e's 16.9 GB
    (the compiler's count, PR 30); half the gradients at a time fit. The
    weights move once every pass has been, from the moments alone."""
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v) for k, v in params_host.items()}
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        moments, apply = make_adam(recipe)
        grads = [(names, train.compiled_once(
            make_grad(args, names), (p,) + tuple(batches[0]) + (rows,),
            ("olmo_hybrid.grad", _tag(args), names)))
            for names in grad_groups(args, GRAD_PASSES)]
        losses, grad_norms, logp = [], {}, None
        for t, (ids, labels) in enumerate(batches, 1):
            for names, grad in grads:
                g, loss, lp = grad(p, ids, labels, rows)
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
            ("olmo_hybrid.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``): the block's kind by the node's layer, the
    feed-forward with its norm and add apart from the mixer with its own.
    The parts the language-model readers of the benchmark know keep their
    names (``attention_proj``, ``attention_kernel``, ``lm_head_loss``,
    ``optimizer``)."""
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
                return "dense_ffn"
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
    """Forward operations of one block over ``tokens`` positions, by part,
    and the bytes it cannot avoid: ``{part: (flops, bytes)}``. A matmul of
    ``[m, k] x [k, n]`` is ``2 m k n``; attention counts the causal half;
    the delta rule counts what its chunked form needs a chunk of L
    positions and head (the triangular halves of ``K K^T``, ``Q K^T`` and
    of the product with ``U``; the forward substitution over both
    right-hand sides; the chunk's two maps of the state, the state's
    product with them, and the three products against the state): ``L^2
    (3K + 2V) + 2 L K^2 + 6 L K V + 2 K^2 V``; the position-by-position
    recurrence needs about as many (7 K V a position) and 8,192 steps.
    Bytes: each matrix read once in the compute dtype, each boundary
    activation read and written once."""
    c = config(args)
    d, held, t = c["hidden"], c["heads_held"], c["seq_len"]
    act = tokens * d * itemsize
    f = c["ffn_hidden"]
    out = {"dense_ffn": (3 * 2 * tokens * d * f,
                         3 * d * f * itemsize + 2 * act)}
    if kind == "linear_attention":
        k, v, L = c["linear_key_dim"], c["linear_value_dim"], c["chunk"]
        wide = held * (2 * k + 2 * v + 2)            # q k v g a b
        proj = 2 * tokens * d * wide + 2 * tokens * held * v * d \
            + 2 * tokens * held * (2 * k + v) * c["conv_kernel"]
        proj_b = (d * wide + held * v * d) * itemsize + 2 * act \
            + 2 * tokens * wide * itemsize + 2 * tokens * held * v * itemsize
        chunks = tokens // L
        scan = chunks * held * (L * L * (3 * k + 2 * v) + 2 * L * k * k
                                + 6 * L * k * v + 2 * k * k * v)
        # q, k, v, a, b read, o written; a [K, V] float32 state a chunk and
        # head written and read
        scan_b = tokens * held * (2 * k + 2 * v + 2) * itemsize \
            + 2 * chunks * held * k * v * 4
        out.update(linattn_proj_conv=(proj, proj_b),
                   linattn_scan=(scan, scan_b))
    elif kind == "full_attention":
        w = held * c["head_dim"]
        out.update(
            attention_proj=(2 * tokens * d * 3 * w + 2 * tokens * w * d,
                            4 * d * w * itemsize + 2 * act),
            # scores and the weighted sum, each 2 T^2 D a head, the half
            attention_kernel=((tokens // t) * 2 * (2 * t * t * c["head_dim"]
                                                   * held) // 2,
                              tokens * 4 * w * itemsize))
    else:
        raise ValueError(kind)
    return out


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
    for kind in c["layer_types"]:
        for name, cost in layer_cost(kind, args, tokens, itemsize).items():
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
