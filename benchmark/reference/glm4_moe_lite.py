"""The plain reference of ``glm4_moe_lite`` language models: forward pass,
loss, gradients and Adam, in ``jax.numpy`` and float32 (``follow`` and
``forward_logprob`` set ``jax.default_matmul_precision("highest")``), no
kernels, no chunks, no layout of rows by expert, nothing of the program.
Also this architecture's count of a step's operations and bytes
(``step_cost``), its parts of the step by scope (``part_of``) and the
lowering counters a traced run prints (``LOWERINGS``), kept with the
benchmark: everything model-shaped that ``drivers/fit_lm_ref.py`` asks for.

The architecture (zai-org/GLM-4.7-Flash ``config.json``, ``model_type:
glm4_moe_lite``): ``hidden`` d = 2,048, vocabulary 154,880, untied head, 47
blocks, ``rms_norm_eps`` 1e-5, no bias anywhere.

* Block (pre-norm): ``h = x + Attn(RMSNorm_d(x))``, ``y = h +
  FFN(RMSNorm_d(h))``; after the last block ``RMSNorm_d``, the head,
  next-token cross-entropy, mean over tokens.
* Latent attention on ``u = RMSNorm_d(x)``, H = 20 heads, n =
  ``qk_nope_head_dim`` 192, r = ``qk_rope_head_dim`` 64, v = ``v_head_dim``
  256: ``c_q = RMSNorm_768(W_qa u)`` (``q_lora_rank``); ``[q_n; q_r]_h =
  (W_qb c_q)_h``; ``[c_kv; k_r] = W_kva u`` in R^{512+64}
  (``kv_lora_rank``); ``c = RMSNorm_512(c_kv)``; ``[k_n; val]_h = (W_kvb
  c)_h``; ``q_h = [q_n,h; rope_t(q_r,h)]``, ``k_h = [k_n,h; rope_t(k_r)]``,
  the SAME rotated ``k_r`` in every head, ``rope`` over those r columns at
  ``rope_theta`` 1e6 in the half-split convention; ``o_h =
  softmax_causal(q_h k_h^T / sqrt(n + r)) val_h``; ``Attn = W_o [o_h]_h``.
* Dense feed-forward (the first ``first_k_dense_replace`` = 1 blocks):
  ``W_down (silu(W_gate h') * W_up h')`` at ``intermediate_size`` 10,240.
* Expert feed-forward (the rest), ``h' = RMSNorm_d(h)``: ``s = sigmoid(W_r
  h')`` over 64 experts in float32; the 4 largest of ``s + b``; ``w_e = 1.8
  s_e / (sum of the chosen s + 1e-20)``; ``FFN = sum_e w_e E_e(h') +
  E_shared(h')``, every ``E`` the gated form at ``moe_intermediate_size``
  1,536. Only ``experts_held`` experts from ``first_expert`` are here: rows
  routed elsewhere add nothing (``model-configs`` section 4). ``b``, the
  selection bias, is a STATE and no weight: no gradient reaches it, and
  after every step ``b_e += bias_update_rate * sign(mean load - load_e)``
  (``balance_step``, the family's balancing without an auxiliary loss;
  ``topk_method: noaux_tc``).
* The multi-token-prediction module (``num_nextn_predict_layers`` 1) is
  left out: the published modelling code does not run it either.

Departures from the published description, each for memory or for the cut
and none in the mathematics: attention's softmax goes in blocks of queries,
each against all keys under the mask; the dense feed-forward, the head and
the loss go in blocks of rows; experts are a loop over the held experts
with a mask, every expert computing every row; each block is recomputed in
the backward pass.

``init_params`` with ``init.balance`` also starts the selection biases
where the balancing rule settles (``balanced_start``): a router drawn from
a seed loads a few experts several times the mean, a model in training
does not.

``precision`` (``loss_and_logprob``): ``None`` float32; ``"bfloat16"`` the
stated precision's floor (every tensor an operator of the program reads or
writes rounded to bfloat16, arithmetic inside float32); the controls, each
the bfloat16 pipeline with ONE thing wrong: ``"int8_matmul"``,
``"fp8_matmul"`` (matmul inputs at 8 bits), ``"rope_whole_head"`` (rotary
over all n + r columns of queries and keys), ``"no_latent_norm"`` (the two
latents' RMSNorms left out), ``"experts_ungated"`` (a routed expert ``W_down
W_up x``), ``"weights_unnormalised"`` (the chosen scores not divided by
their sum).
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
# balancing rule, Adam as the program states it
from .nemotron_h import (_bf16, _rope, _stretch, balance_step,
                         balanced_bias, loads)
from .olmo_hybrid import _rmsnorm, make_adam

DEFAULTS = dict(
    layers=47, dense_layers=1, hidden=2048, vocab=154880, heads=20,
    q_rank=768, kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256,
    rope_theta=1000000.0, dense_hidden=10240, experts_total=64,
    experts_held=64, first_expert=0, top_k=4, routed_scale=1.8,
    expert_hidden=1536, shared_experts=1, eps=1e-5, seq_len=8192,
    bias_update_rate=0.0)
STATE = "experts_select_bias"   # the leaves that are states, by suffix
# the lowering counters of the program a traced run prints
LOWERINGS = ("lower.attention_kernel.pallas_splash",
             "lower.attention_kernel.xla_blockwise",
             "lower.experts_body.swiglu")
ATTN_BLOCK = 256
ROW_BLOCK = 2048
GRAD_PASSES = 2         # a step's gradient is taken in this many (``follow``)
DRAWS = 16              # the matrices are drawn in this many (``init_params``)


# name -> (stored tensors rounded by, matmul inputs rounded by)
_ROUND = {None: (arrays._same, arrays._same),
          "int8_matmul": (_bf16, arrays._int8),
          "fp8_matmul": (_bf16, arrays._fp8)}
_ROUND.update({name: (_bf16, arrays._same) for name in (
    "bfloat16", "rope_whole_head", "no_latent_norm", "experts_ungated",
    "weights_unnormalised")})


def config(args):
    cfg = dict(DEFAULTS)
    unknown = set(args) - set(cfg)
    if unknown:
        raise ValueError("glm4_moe_lite: unknown arguments %s"
                         % sorted(unknown))
    cfg.update(args)
    return cfg


def _tag(args):
    return sorted(args.items())


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args, states=True):
    """The program's parameter names -> shapes, in the program's order;
    with ``states`` the selection biases too, each after its router."""
    c = config(args)
    d, h, n, r, v = (c["hidden"], c["heads"], c["nope_dim"], c["rope_dim"],
                     c["v_dim"])
    out = {"embed_weight": (c["vocab"], d)}
    for i in range(c["layers"]):
        p = "layer%d_" % i
        out[p + "attn_norm_gamma"] = (d,)
        out[p + "q_a_weight"] = (c["q_rank"], d)
        out[p + "q_a_norm_gamma"] = (c["q_rank"],)
        out[p + "q_b_weight"] = (h * (n + r), c["q_rank"])
        out[p + "kv_a_weight"] = (c["kv_rank"] + r, d)
        out[p + "kv_norm_gamma"] = (c["kv_rank"],)
        out[p + "kv_b_weight"] = (h * (n + v), c["kv_rank"])
        out[p + "o_weight"] = (d, h * v)
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


def balance_rates(spec):
    """``{"from", "to", "steps", "hold"}`` -> the rates ``balanced_start``
    runs at: ``steps`` falling geometrically, then ``hold`` at the last."""
    return np.concatenate([
        np.geomspace(spec["from"], spec["to"], spec["steps"]),
        np.full(spec["hold"], spec["to"])]).astype(np.float32)


def init_params(args, seed_key, init=None):
    """Every parameter and state from the key, float32, on the device.
    Matrices: normal, std 1/sqrt(fan-in) (the embedding std 1) from ONE
    generator run ``DRAWS`` times over slices of one buffer (as one draw of
    700 M numbers its temporaries are most of the chip: ``olmo_hybrid.
    init_params``, PR 30); norm weights 1; the selection biases 0, or with
    ``init["balance"]`` (``{"from", "to", "steps", "hold",
    "zipf_exponent"}``) where the family's balancing rule settles on one
    batch drawn from the same key (``balanced_start``), so that every
    seed's experts are loaded evenly from step 1, as a model in training
    holds them; the step's own rule (``bias_update_rate``) keeps them so.
    (Anything that is no dictionary, which is what ``tools/sweep_lr.py``
    hands over, is taken as no ``init``.)"""
    if not isinstance(init, dict):
        init = {}
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
        c = config(args)
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


def zipf_ids(key, vocab, seq_len, exponent):
    """One sequence of ``seq_len`` ids, Zipf over 1 .. vocab-1 (rank r is id
    r): the law the ``resident_tokens`` traffic draws its ids from."""
    weights = np.arange(1, vocab, dtype=np.float64) ** -float(exponent)
    cdf = jnp.asarray(np.cumsum(weights) / weights.sum(), jnp.float32)
    u = jax.random.uniform(key, (1, seq_len), jnp.float32)
    return jnp.minimum(1 + jnp.searchsorted(cdf, u).astype(jnp.int32),
                       vocab - 1)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def attention(p, pre, u, args, precision=None):
    """``Attn(u)`` of one block, ``[rows, hidden]``, ``u`` the block's input
    after its norm."""
    c = config(args)
    st, mm = _ROUND[precision]
    t, h, n, r, v = (c["seq_len"], c["heads"], c["nope_dim"], c["rope_dim"],
                     c["v_dim"])
    bsz = u.shape[0] // t

    def proj(x, part):
        return st(mm(x) @ mm(st(p[pre + part + "_weight"])).T)

    def latent(x, part):
        if precision == "no_latent_norm":
            return x
        return st(_rmsnorm(x, st(p[pre + part + "_norm_gamma"]), c["eps"]))

    q = proj(latent(proj(u, "q_a"), "q_a"), "q_b").reshape(bsz, t, h, n + r)
    kv_a = proj(u, "kv_a")
    k_r = kv_a[:, c["kv_rank"]:].reshape(bsz, t, 1, r)
    kv = proj(latent(kv_a[:, :c["kv_rank"]], "kv"), "kv_b").reshape(
        bsz, t, h, n + v)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_r, (bsz, t, h, r))],
                        axis=-1)
    val = kv[..., n:]
    if precision == "rope_whole_head":
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    else:
        q, k = (jnp.concatenate([x[..., :n], _rope(x[..., n:],
                                                   c["rope_theta"])], axis=-1)
                for x in (q, k))
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
    out = st(jnp.moveaxis(out, 0, 1).reshape(bsz * t, h * v))
    return proj(out, "o")


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
        chosen = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)
    return eid, chosen * c["routed_scale"], bias


def routed_part(u, routed, weights, first, precision=None):
    """What experts ``first .. first + held`` add to ``FFN(u)``: a loop over
    them with a mask. ``weights`` are the ``[held, ...]`` stacks (gate, up,
    down) whose entry j is expert ``first + j``; ``routed`` what ``route``
    gave."""
    st, mm = _ROUND[precision]
    eid, wts = routed[:2]
    um = mm(u)
    total = jnp.zeros_like(u)

    @jax.checkpoint
    def expert(um, gate, up, down, w):
        a = st(um @ mm(st(up)))
        if precision != "experts_ungated":
            a = st(st(jax.nn.silu(st(um @ mm(st(gate))))) * a)
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
        c["first_expert"], precision)
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

    def block(i, pre, p, x):
        u = st(_rmsnorm(x, st(p[pre + "attn_norm_gamma"]), c["eps"]))
        x = st(x + attention(p, pre, u, args, precision))
        u = st(_rmsnorm(x, st(p[pre + "ffn_norm_gamma"]), c["eps"]))
        if i < c["dense_layers"]:
            return st(x + gated(p, pre + "ffn_", u, st, mm)), None
        out, *routed = experts(p, pre, u, args, precision, rates)
        return st(x + out), routed

    load, bias = {}, {}
    for i in range(c["layers"]):
        pre = "layer%d_" % i
        fn = functools.partial(block, i, pre)
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
def leaves(tree):
    """name -> array with every expert's slice of the stacked up and down
    weights a leaf of its own (``name[j]``), as ``fit_lm._leaf_norms`` cuts
    the program's (it knows those two suffixes; the stacked gate is one
    leaf on both sides)."""
    out = {}
    for k, v in tree.items():
        if k.endswith(("experts_up_weight", "experts_down_weight")):
            out.update({"%s[%d]" % (k, j): v[j] for j in range(v.shape[0])})
        else:
            out[k] = v
    return out


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in leaves(tree).items()}


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
            ("glm4_moe_lite.grad", _tag(args), names)))
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
            ("glm4_moe_lite.forward", _tag(args), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# the step's parts by scope, and their operations and bytes
# ---------------------------------------------------------------------------
def part_of(args):
    """Which part of the step a scope's (phase, op, node) belongs to
    (``trace/scopes.by_part``), by the node's layer and name: a block's
    ``_ffn_*`` nodes (its norm and add among them) are its feed-forward,
    dense or of experts, the rest its attention. The parts the
    language-model readers of the benchmark know keep their names."""
    dense = config(args)["dense_layers"]
    layer = re.compile(r"layer(\d+)_(ffn_)?")

    def part(phase, op, node):
        if phase == "update":
            return "optimizer"
        if phase == "metric":
            return "lm_head_loss"
        m = layer.match(node)
        if m:
            if not m.group(2):
                return "attention_kernel" if op == "CausalAttention" \
                    else "attention_proj"
            if int(m.group(1)) < dense:
                return "dense_ffn"
            return "moe_grouped_matmul" if op == "RoutedExperts" \
                else "moe_rest"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def layer_cost(kind, args, tokens, itemsize=2):
    """Forward operations of one block's part ``kind`` (``"attention"``,
    ``"dense"``, ``"experts"``) over ``tokens`` positions, and the bytes it
    cannot avoid: ``{part: (flops, bytes)}``. A matmul of ``[m, k] x [k,
    n]`` is ``2 m k n``; attention counts the causal half. Bytes: each
    matrix read once in the compute dtype, each boundary activation read and
    written once. The routed experts are counted by the EVEN share of the
    pairs (``tokens x top_k x held / total`` rows through three matrices):
    ``fit_lm_ref`` hands ``step_cost`` no routed rows, so this yardstick
    does not move with the routing."""
    c = config(args)
    d, h, n, r, v = (c["hidden"], c["heads"], c["nope_dim"], c["rope_dim"],
                     c["v_dim"])
    act = tokens * d * itemsize
    if kind == "attention":
        qr, kr, t = c["q_rank"], c["kv_rank"], c["seq_len"]
        weights = d * qr + qr * h * (n + r) + d * (kr + r) \
            + kr * h * (n + v) + h * v * d
        # u read; the two latents, q, k (each head's content part and the
        # one rotary key), val and the kernel's result written and read
        # again; the output written
        between = qr + (kr + r) + h * (n + r) + (h * n + r) + 2 * h * v
        return {"attention_proj": (
            2 * tokens * weights,
            weights * itemsize + 2 * act + 2 * tokens * between * itemsize),
            # scores over n + r columns and the weighted sum over v, each 2
            # T^2 a column and head, the causal half
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
    for i in range(c["layers"]):
        for kind in ("attention",
                     "dense" if i < c["dense_layers"] else "experts"):
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
