"""The plain reference of ``nemotron_h`` language models: forward pass, loss,
gradients and Adam, in ``jax.numpy`` and float32 (the caller sets
``jax.default_matmul_precision("highest")``), no kernels, no chunks, nothing
of the program. Also this architecture's count of a step's operations and
bytes (``step_cost``), kept with the benchmark.

How a language-model configuration names its files (beside ``README.md``,
which describes the image configurations): ``driver: fit_lm``; ``model``:
the program's factory and its arguments; ``reference``: ``{"net":
"nemotron_h", "args": {...}}`` with the SAME arguments, read here as ``cfg``;
``tokens``: ``{"batch", "seq_len"}``; traffic ``resident_tokens_*`` (generator
``resident_tokens``); limits under the cell's name as for every cell.

The architecture (NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type: nemotron_h``): ``hidden`` 2688, vocabulary 131,072, untied
head, 52 layers, each ONE mixer behind a pre-RMSNorm (eps 1e-5) and a
residual add, by ``hybrid_override_pattern``:

* ``M``, Mamba-2 mixer. ``d_inner = mamba_heads x mamba_head_dim = 64 x 64
  = 4096``; ``ssm_groups`` 8, ``ssm_state`` N = 128, ``conv_kernel`` 4,
  ``chunk`` 128. ``[z | xBC | dt] = W_in u`` with widths 4096 | 4096 +
  2x8x128 = 6144 | 64 (no bias). ``xBC <- silu(causal depthwise
  conv1d_k4(xBC) + b_conv)``; split into ``x`` (64 heads x 64), ``B``, ``C``
  (8 groups x 128; head h reads group h // 8). ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` per head. Per head ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t B_t^T`` (S in R^{64x128}), ``y_t = S_t C_t + D x_t``.
  Then ``y <- RMSNorm_grouped(y * silu(z))`` (8 groups of 512, a learned
  weight), ``out = W_out y``.
* ``*``, attention: 32 query heads, 2 key/value heads, head 128, causal,
  no bias; rotary over the whole head (``rope_theta`` 10000,
  ``partial_rotary_factor`` 1; ASSUMED to apply: the configuration file says
  so under ``assumed``).
* ``E``, experts: ``s = sigmoid(W_r n)`` over 128 experts in float32; the 6
  largest of ``s + b``; weights ``s_e / sum of the chosen s`` x 2.5; expert
  ``W_down relu(W_up n)^2`` at width 1856; one shared expert of that form at
  width 3712, always added. Only ``experts_held`` experts from
  ``first_expert`` are here: rows routed elsewhere add nothing
  (``model-configs`` section 4). ``b``, the selection bias, is a STATE and
  no weight: no gradient reaches it, and the family balances its experts
  without an auxiliary loss by moving it after every step against each
  expert's load, ``b_e += bias_update_rate * sign(mean load - load_e)``
  (loads over the step's rows and over all experts; ``balance_step``). A
  model in training therefore routes evenly, and a router drawn from a seed
  does not (a few experts draw several times the mean): ``balanced_start``
  runs that same rule on the first batch, layer by layer, until the loads
  are even, and training starts from there, as from a checkpoint.
* final RMSNorm, head, next-token cross-entropy (mean over tokens).

Departures from the published description, each for memory or for the cut
and none in the mathematics: the recurrence is a ``lax.scan`` over time cut
into checkpointed stretches (a plain scan would keep 8,192 states for the
backward pass); attention's softmax is computed in blocks of queries, each
against all keys under the mask; experts are a loop over the held experts
with a mask, every expert computing every row; each layer is recomputed in
the backward pass.

``precision`` (``forward``): ``None`` float32; ``"bfloat16"`` the stated
precision's floor (every tensor a layer reads or writes rounded to bfloat16,
arithmetic inside float32, as ``arrays.py``); the controls, each the
bfloat16 pipeline with one thing lowered: ``"int8_matmul"``,
``"fp8_matmul"`` (matmul inputs at 8 bits), ``"bf16_state"`` (the scan's
carried state rounded to bfloat16 at every chunk's end), ``"capacity_1.25"``
(an expert drops the rows past 1.25x the mean load).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, train

DEFAULTS = dict(
    pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    hidden=2688, vocab=131072, experts_total=128, experts_held=128,
    first_expert=0, seq_len=8192, mamba_heads=64, mamba_head_dim=64,
    ssm_groups=8, ssm_state=128, conv_kernel=4, chunk=128, attn_heads=32,
    kv_heads=2, head_dim=128, rotary=True, rope_theta=10000.0, top_k=6,
    routed_scale=2.5, expert_hidden=1856, shared_hidden=3712, eps=1e-5,
    bias_update_rate=0.0)
STATE = "experts_select_bias"   # the leaves that are states, by suffix

def _bf16(x):
    """Round to bfloat16's 8 bits of exponent and 7 of mantissa, as an
    operation the compiler may not drop (a convert there and back it may,
    and did: the ``bf16_state`` control read exactly the floor on the v5e
    until the rounding was written this way; PERF.md, PR 26)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# name -> (stored tensors rounded by, matmul inputs rounded by)
_ROUND = {None: (arrays._same, arrays._same),
          "bfloat16": (_bf16, arrays._same),
          "int8_matmul": (_bf16, arrays._int8),
          "fp8_matmul": (_bf16, arrays._fp8),
          "bf16_state": (_bf16, arrays._same),
          "capacity_1.25": (_bf16, arrays._same)}
ATTN_BLOCK = 256


def config(args):
    cfg = dict(DEFAULTS)
    unknown = set(args) - set(cfg)
    if unknown:
        raise ValueError("nemotron_h: unknown arguments %s" % sorted(unknown))
    cfg.update(args)
    return cfg


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(args):
    """The program's parameter names -> shapes, in the program's order."""
    c = config(args)
    h = c["hidden"]
    di = c["mamba_heads"] * c["mamba_head_dim"]
    gn = c["ssm_groups"] * c["ssm_state"]
    heads = (c["mamba_heads"],)
    out = {"embed_weight": (c["vocab"], h)}
    for i, kind in enumerate(c["pattern"]):
        p = "layer%d_" % i
        out[p + "norm_gamma"] = (h,)
        if kind == "M":
            out[p + "in_proj_weight"] = (2 * di + 2 * gn + heads[0], h)
            out[p + "conv_weight"] = (di + 2 * gn, c["conv_kernel"])
            out[p + "conv_bias"] = (di + 2 * gn,)
            out[p + "scan_A_log"] = heads
            out[p + "scan_D"] = heads
            out[p + "scan_dt_bias"] = heads
            out[p + "gnorm_gamma"] = (di,)
            out[p + "out_proj_weight"] = (h, di)
        elif kind == "*":
            q = c["attn_heads"] * c["head_dim"]
            kv = c["kv_heads"] * c["head_dim"]
            out[p + "q_weight"] = (q, h)
            out[p + "k_weight"] = (kv, h)
            out[p + "v_weight"] = (kv, h)
            out[p + "o_weight"] = (h, q)
        elif kind == "E":
            held, f = c["experts_held"], c["expert_hidden"]
            out[p + "experts_router_weight"] = (h, c["experts_total"])
            out[p + "experts_select_bias"] = (c["experts_total"],)
            out[p + "experts_up_weight"] = (held, h, f)
            out[p + "experts_down_weight"] = (held, f, h)
            out[p + "shared_up_weight"] = (c["shared_hidden"], h)
            out[p + "shared_down_weight"] = (h, c["shared_hidden"])
        else:
            raise ValueError("layer %d is %r" % (i, kind))
    out["final_norm_gamma"] = (h,)
    out["lm_head_weight"] = (c["vocab"], h)
    return out


def _fan_in(name, shape):
    if name.endswith("experts_up_weight") or name.endswith(
            "experts_down_weight") or name.endswith("router_weight"):
        return shape[-2]
    return shape[-1]


def init_params(args, seed_key, time_step=(0.001, 0.1, 1e-4)):
    """Every parameter from the key in ONE jitted call on the device,
    float32. Matrices: normal, std 1/sqrt(fan-in) (the embedding std 1), so
    that every layer's output and the router's scores are of order one, the
    range training sees; norm weights 1, biases 0; the scan's parameters as
    the family initialises them: ``A`` uniform in [1, 16] (``A_log`` its
    log), ``dt`` log-uniform in [time_step_min, time_step_max] floored at
    time_step_floor (``dt_bias`` its inverse softplus), ``D`` 1."""
    shapes = param_shapes(args)
    tmin, tmax, tfloor = time_step

    def is_matrix(name):
        return not name.endswith(("_A_log", "_dt_bias", "_gamma", "_D",
                                  "_bias"))

    sizes = {n: int(np.prod(s)) for n, s in shapes.items() if is_matrix(n)}
    heads = sum(s[0] for n, s in shapes.items() if n.endswith("_A_log"))

    def make(key):
        # ONE normal draw for all the matrices and one uniform draw for the
        # scans' parameters, cut up: a draw a leaf makes the compiler build
        # a generator a leaf (train.init_params, PR 23)
        k1, k2 = jax.random.split(key)
        flat = jax.random.normal(k1, (sum(sizes.values()),), jnp.float32)
        unit = jax.random.uniform(k2, (2, max(heads, 1)), jnp.float32)
        out, at, head_at = {}, 0, [0, 0]

        def take_unit(row, n):
            got = unit[row, head_at[row]:head_at[row] + n]
            head_at[row] += n
            return got

        for name, shape in shapes.items():
            if name.endswith("_A_log"):
                out[name] = jnp.log(1.0 + 15.0 * take_unit(0, shape[0]))
            elif name.endswith("_dt_bias"):
                dt = jnp.exp(math.log(tmin) + take_unit(1, shape[0])
                             * (math.log(tmax) - math.log(tmin)))
                dt = jnp.maximum(dt, tfloor)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name.endswith("_gamma") or name.endswith("_D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("_bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan = 1 if name == "embed_weight" else (
                    shape[1] if name.endswith("conv_weight")
                    else _fan_in(name, shape))
                out[name] = flat[at:at + sizes[name]].reshape(shape) \
                    / math.sqrt(fan)
                at += sizes[name]
        return out

    return jax.jit(make)(seed_key)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def _rmsnorm(x, gamma, eps, groups=1):
    xg = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(x.shape) * gamma


def _stretch(t, want):
    """The largest divisor of ``t`` that is at most ``want``."""
    return max(d for d in range(1, want + 1) if t % d == 0)


def _scan(x, dt, a_head, b_mat, c_mat, chunk, round_state):
    """The recurrence, one position at a time. ``x [B, T, H, P]``, ``dt [B,
    T, H]``, ``b_mat``/``c_mat [B, T, G, N]`` -> ``y [B, T, H, P]``."""
    b, t, h, p = x.shape
    g = b_mat.shape[2]
    rep = h // g
    length = chunk if t % chunk == 0 else _stretch(t, chunk)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp                       # [B, ...]
        b_h = jnp.repeat(b_t, rep, axis=1)              # [B, H, N]
        c_h = jnp.repeat(c_t, rep, axis=1)
        s = jnp.exp(dt_t * a_head)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_h)

    @jax.checkpoint
    def stretch(s, inp):
        s, y = jax.lax.scan(step, s, inp)
        if round_state:
            s = _bf16(s)
        return s, y

    def cut(v):     # [B, T, ...] -> [T/length, length, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // length, length) + v.shape[1:])

    s0 = jnp.zeros((b, h, p, b_mat.shape[3]), jnp.float32)
    _, y = jax.lax.scan(stretch, s0, (cut(x), cut(dt), cut(b_mat),
                                      cut(c_mat)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(p, pre, u, c, st, mm, precision):
    bsz = u.shape[0] // c["seq_len"]
    t = c["seq_len"]
    h, pd, g, n = (c["mamba_heads"], c["mamba_head_dim"], c["ssm_groups"],
                   c["ssm_state"])
    di, gn, k = h * pd, g * n, c["conv_kernel"]
    zxd = st(mm(u) @ mm(st(p[pre + "in_proj_weight"])).T)
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * gn], \
        zxd[:, 2 * di + 2 * gn:]
    xs = jnp.pad(xbc.reshape(bsz, t, -1), ((0, 0), (k - 1, 0), (0, 0)))
    w = st(p[pre + "conv_weight"])
    conv = sum(xs[:, i:i + t] * w[:, i] for i in range(k)) \
        + st(p[pre + "conv_bias"])
    xbc = st(jax.nn.silu(conv))
    x = xbc[..., :di].reshape(bsz, t, h, pd)
    b_mat = xbc[..., di:di + gn].reshape(bsz, t, g, n)
    c_mat = xbc[..., di + gn:].reshape(bsz, t, g, n)
    dt = jax.nn.softplus(dt.reshape(bsz, t, h) + p[pre + "scan_dt_bias"])
    a_head = -jnp.exp(p[pre + "scan_A_log"])
    y = _scan(x, dt, a_head, b_mat, c_mat, c["chunk"],
              precision == "bf16_state")
    y = st(y + p[pre + "scan_D"][:, None] * x).reshape(bsz * t, di)
    y = st(_rmsnorm(y * jax.nn.silu(z), st(p[pre + "gnorm_gamma"]),
                    c["eps"], groups=g))
    return st(mm(y) @ mm(st(p[pre + "out_proj_weight"])).T)


def _rope(x, theta):
    """``x [B, T, H, D]``; the half-split convention of the published
    modelling code (``rotate_half``)."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, pre, u, c, st, mm):
    t = c["seq_len"]
    bsz = u.shape[0] // t
    hq, hkv, d = c["attn_heads"], c["kv_heads"], c["head_dim"]
    um = mm(u)

    def proj(name, heads):
        return st(um @ mm(st(p[pre + name])).T).reshape(bsz, t, heads, d)

    q, k, v = proj("q_weight", hq), proj("k_weight", hkv), \
        proj("v_weight", hkv)
    if c["rotary"]:
        q, k = st(_rope(q, c["rope_theta"])), st(_rope(k, c["rope_theta"]))
    k = mm(jnp.repeat(k, hq // hkv, axis=2))      # plain: keys repeated
    v = mm(jnp.repeat(v, hq // hkv, axis=2))
    q = mm(q)

    blk = _stretch(t, ATTN_BLOCK)

    @jax.checkpoint
    def block(qi, start, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(blk))[:, None]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", mm(prob), v)

    # one block of queries after another (a lax.map, so that the compiler
    # cannot hold several blocks' scores at once)
    qb = jnp.moveaxis(q.reshape(bsz, t // blk, blk, hq, d), 1, 0)
    out = jax.lax.map(lambda x: block(x[0], x[1], k, v),
                      (qb, jnp.arange(0, t, blk)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, hq, d)
    out = st(out).reshape(bsz * t, hq * d)
    return st(mm(out) @ mm(st(p[pre + "o_weight"])).T)


def loads(eid, experts):
    """Rows each of ``experts`` experts drew, ``[E]`` float32."""
    return jnp.zeros((experts,), jnp.float32).at[eid.reshape(-1)].add(1.0)


def balance_step(bias, load, rate):
    """One step of the family's balancing: each expert's selection bias
    moves by ``rate`` against its load."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def balanced_bias(scores, bias, top_k, rates):
    """``balance_step`` over and over on ONE batch's scores ``[S, E]``, at
    ``rates`` one after another: where the rule settles."""
    def body(b, rate):
        _, eid = jax.lax.top_k(scores + b, top_k)
        return balance_step(b, loads(eid, scores.shape[1]), rate), None

    return jax.lax.scan(body, bias, rates)[0]


def route(p, pre, u, c, rates=None):
    """Expert ids ``[S, k]``, combine weights ``[S, k]`` (float32; the
    router reads the layer's input unrounded by ``mm``) and the selection
    bias they were chosen with: the layer's own, or with ``rates`` the one
    ``balanced_bias`` settles at from it."""
    scores = jax.nn.sigmoid(u @ p[pre + "experts_router_weight"])
    bias = p[pre + STATE]
    if rates is not None:
        bias = balanced_bias(scores, bias, c["top_k"], rates)
    _, eid = jax.lax.top_k(scores + bias, c["top_k"])
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    wts = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20) \
        * c["routed_scale"]
    return eid, wts, bias


def routed_part(p, pre, u, c, st, mm, first, held, weights,
                capacity=None, routed=None):
    """What experts ``first .. first + held`` add: a loop over them with a
    mask. ``weights`` are ``[held', ...]`` stacks whose entry j is expert
    ``first + j``; ``routed`` what ``route`` gave, if it has been asked."""
    eid, wts, _ = routed or route(p, pre, u, c)
    w_up, w_down = weights
    um = mm(u)
    total = jnp.zeros_like(u)
    for j in range(held):
        hit = eid == first + j                              # [S, k]
        if capacity is not None:
            # the rows past the capacity, in row order, are dropped
            taken = jnp.cumsum(hit.any(axis=1))
            hit = hit & (taken <= capacity)[:, None]
        gate = jnp.sum(jnp.where(hit, wts, 0.0), axis=1)    # [S]

        @jax.checkpoint
        def expert(um, up, down, gate):
            a = st(jnp.square(jnp.maximum(st(um @ mm(st(up))), 0.0)))
            return st(mm(a) @ mm(st(down))) * gate[:, None]

        total = total + expert(um, w_up[j], w_down[j], gate)
    return st(total)


def shared_part(p, pre, u, st, mm):
    a = st(jnp.square(jnp.maximum(
        st(mm(u) @ mm(st(p[pre + "shared_up_weight"])).T), 0.0)))
    return st(mm(a) @ mm(st(p[pre + "shared_down_weight"])).T)


def _experts(p, pre, u, c, st, mm, precision, rates=None):
    """The layer's result, the rows each expert drew ``[E]`` and the
    selection bias they were chosen with."""
    capacity = None
    if precision == "capacity_1.25":
        capacity = math.ceil(1.25 * u.shape[0] * c["top_k"]
                             / c["experts_total"])
    chosen = route(p, pre, u, c, rates)
    routed = routed_part(
        p, pre, u, c, st, mm, c["first_expert"], c["experts_held"],
        (p[pre + "experts_up_weight"], p[pre + "experts_down_weight"]),
        capacity, chosen)
    return (st(routed + shared_part(p, pre, u, st, mm)),
            loads(chosen[0], c["experts_total"]), chosen[2])


def forward_routed(params, ids, args, precision=None, remat=True,
                   rates=None):
    """Token ids ``[B, T]`` -> logits ``[B*T, vocab]``, and by expert
    layer's state name the rows each expert drew ``[E]`` and the selection
    bias it chose with (with ``rates``: the balanced one, ``route``)."""
    c = config(args)
    st, mm = _ROUND[precision]
    x = st(params["embed_weight"])[ids.reshape(-1)]

    def layer(kind, pre, params, x):
        n = st(_rmsnorm(x, st(params[pre + "norm_gamma"]), c["eps"]))
        routed = None
        if kind == "M":
            out = _mamba(params, pre, n, c, st, mm, precision)
        elif kind == "*":
            out = _attention(params, pre, n, c, st, mm)
        else:
            out, *routed = _experts(params, pre, n, c, st, mm, precision,
                                    rates)
        return st(x + out), routed

    load, bias = {}, {}
    for i, kind in enumerate(c["pattern"]):
        pre = "layer%d_" % i
        fn = functools.partial(layer, kind, pre)
        own = {k: v for k, v in params.items() if k.startswith(pre)}
        x, routed = (jax.checkpoint(fn) if remat else fn)(own, x)
        if routed:
            load[pre + STATE], bias[pre + STATE] = routed
    x = st(_rmsnorm(x, st(params["final_norm_gamma"]), c["eps"]))
    return st(mm(x) @ mm(st(params["lm_head_weight"])).T), load, bias


def forward(params, ids, args, precision=None, remat=True):
    """Token ids ``[B, T]`` -> logits ``[B*T, vocab]``."""
    return forward_routed(params, ids, args, precision, remat)[0]


def loss_and_logprob(params, ids, labels, args, rows, precision=None,
                     remat=True):
    """Mean next-token cross-entropy over all positions, and the
    log-probabilities ``[len(rows), vocab]`` at the flat positions
    ``rows``."""
    loss, (logp, _) = loss_logprob_loads(params, ids, labels, args, rows,
                                         precision, remat)
    return loss, logp


def loss_logprob_loads(params, ids, labels, args, rows, precision=None,
                       remat=True):
    """``loss_and_logprob`` and the expert layers' loads (``forward_routed``):
    ``loss, (log-probabilities, loads)``."""
    logits, load, _ = forward_routed(params, ids, args, precision, remat)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)
    return -jnp.mean(picked), (logp[rows], load)


# ---------------------------------------------------------------------------
# training: Adam as mxnet_tpu/optimizer.py states it
# ---------------------------------------------------------------------------
def leaves(tree):
    """name -> array with every expert's slice of a stacked expert weight
    a leaf of its own (``name[j]``), so that an expert that never trains
    shows."""
    out = {}
    for k, v in tree.items():
        if k.endswith("experts_up_weight") or k.endswith(
                "experts_down_weight"):
            for j in range(v.shape[0]):
                out["%s[%d]" % (k, j)] = v[j]
        else:
            out[k] = v
    return out


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in leaves(tree).items()}


def make_step(args, recipe):
    """jitted (params, m, v, ids, labels, rows, t) -> (params, m, v, loss,
    gradient norms by leaf, log-probabilities at ``rows``); the first three
    are donated. ``rows [B, n]`` are positions within each sequence. The
    batch goes one sequence at a time, gradients added up (the loss is a
    mean over tokens, no layer looks across sequences): float32 activations
    of one 8k sequence are what fits beside float32 weights, moments and
    gradients. ``g = rescale_grad * grad + wd * w; m = b1 m + (1-b1) g; v =
    b2 v + (1-b2) g^2; w -= lr sqrt(1-b2^t)/(1-b1^t) m / (sqrt(v) + eps)``
    (``mxnet_tpu/optimizer.py``, Adam). The leaves that are states
    (``STATE``) get no Adam: ``balance_step`` moves them, by the loads of
    the step that read them, summed over the batch's sequences."""
    rate = config(args)["bias_update_rate"]
    lr, wd = recipe["learning_rate"], recipe.get("wd", 0.0)
    b1, b2 = recipe.get("beta1", 0.9), recipe.get("beta2", 0.999)
    eps, rescale = recipe.get("epsilon", 1e-8), recipe.get("rescale_grad",
                                                           1.0)

    def step(params, m, v, ids, labels, rows, t):
        n_seq = ids.shape[0]

        def one(acc, seq):
            i, l, r = seq
            (loss, (logp, load)), g = jax.value_and_grad(
                loss_logprob_loads, has_aux=True)(params, i[None], l[None],
                                                  args, r)
            return jax.tree_util.tree_map(jnp.add, acc, g), (loss, logp,
                                                              load)

        if n_seq == 1:       # no second copy of the gradients to add to
            (loss, (logp, load)), grads = jax.value_and_grad(
                loss_logprob_loads, has_aux=True)(params, ids, labels, args,
                                                  rows[0])
            losses, logp = loss[None], logp[None]
        else:
            grads, (losses, logp, load) = jax.lax.scan(
                one, jax.tree_util.tree_map(jnp.zeros_like, params),
                (ids, labels, rows))
            grads = jax.tree_util.tree_map(lambda g: g / n_seq, grads)
            load = {k: v.sum(axis=0) for k, v in load.items()}
        norms = leaf_norms({k: g for k, g in grads.items()
                            if not k.endswith(STATE)})
        step_lr = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        new_p, new_m, new_v = {}, {}, {}
        for k, w in params.items():
            if k.endswith(STATE):
                new_p[k], new_m[k], new_v[k] = \
                    balance_step(w, load[k], rate), m[k], v[k]
                continue
            g = rescale * grads[k] + wd * w
            new_m[k] = b1 * m[k] + (1.0 - b1) * g
            new_v[k] = b2 * v[k] + (1.0 - b2) * g * g
            new_p[k] = w - step_lr * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
        return (new_p, new_m, new_v, jnp.mean(losses), norms,
                logp.reshape((-1,) + logp.shape[2:]))

    return jax.jit(step, donate_argnums=(0, 1, 2))


def follow(args, recipe, params_host, batches, rows):
    """Follow ``len(batches)`` steps from the host copy of the seeded
    weights. Returns what ``check.compare`` reads: losses, the first
    gradient's norm and the parameters' change over all the steps by leaf,
    the first step's log-probabilities at ``rows`` (``[B, n]`` positions
    within each sequence; the result is ``[B * n, vocab]``), and the states
    (``STATE``) as the last step left them."""
    step = make_step(args, recipe)
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v) for k, v in params_host.items()}
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        step = train.compiled_once(
            step, (p, m, v) + tuple(batches[0]) + (rows, jnp.float32(1)),
            ("nemotron_h.step", sorted(args.items()),
             sorted(recipe.items())))
        losses, grad_norms, logp = [], None, None
        for t, (ids, labels) in enumerate(batches, 1):
            p, m, v, loss, norms, lp = step(p, m, v, ids, labels, rows,
                                            jnp.float32(t))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(n) for k, n in norms.items()}
                logp = np.asarray(lp, np.float64)
        del m, v
        states = {k: np.asarray(a) for k, a in p.items() if k.endswith(STATE)}
        delta = {}
        for k in list(p):
            d = leaf_norms({k: p.pop(k) - jnp.asarray(params_host[k])})
            delta.update({name: float(n) for name, n in d.items()})
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "logprob": logp, "states": states}


def balanced_start(args, params, ids, precision, rates):
    """The selection biases a model in training would hold: one forward
    pass over ``ids [B, T]`` at ``precision`` in which each expert layer,
    when the pass reaches it, runs ``balance_step`` on its own scores at
    ``rates`` one after another and goes on with the bias that gives
    (``balanced_bias``), so that the next layer balances on what it will
    really read. Returns ``{state name: bias}``, float32 on the host, and
    by state name the loads they give on ``ids``."""
    @jax.jit
    def run(params, ids, rates):
        _, load, bias = forward_routed(params, ids, args, precision,
                                       remat=False, rates=rates)
        return bias, load

    with jax.default_matmul_precision("highest"):
        bias, load = run({k: jnp.asarray(v) for k, v in params.items()},
                         ids, jnp.asarray(rates, jnp.float32))
    return ({k: np.asarray(v, np.float32) for k, v in bias.items()},
            {k: np.asarray(v) for k, v in load.items()})


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
            ("nemotron_h.forward", sorted(args.items()), precision))
        return np.asarray(run(params, ids, labels, rows), np.float64)


# ---------------------------------------------------------------------------
# a step's operations and bytes
# ---------------------------------------------------------------------------
def layer_cost(kind, args, tokens, itemsize=2, rows_here=None):
    """Forward operations of one layer over ``tokens`` positions, by part,
    and the bytes it cannot avoid: ``{part: (flops, bytes)}``. A matmul of
    ``[m, k] x [k, n]`` is ``2 m k n``; attention counts the causal half;
    the scan counts its intra-chunk quadratic form and its inter-chunk
    state products, as the chunked algorithm needs them (the sequential
    reference needs fewer operations and far more steps). Bytes: each
    matrix read once in the compute dtype, each boundary activation read
    and written once. ``rows_here``: (row, expert) pairs really routed to
    the held experts (default: the uniform share)."""
    c = config(args)
    h, t = c["hidden"], c["seq_len"]
    act = tokens * h * itemsize
    if kind == "M":
        heads, pd, g, n, L = (c["mamba_heads"], c["mamba_head_dim"],
                              c["ssm_groups"], c["ssm_state"], c["chunk"])
        di, gn = heads * pd, g * n
        win = 2 * di + 2 * gn + heads
        proj = 2 * tokens * h * win + 2 * tokens * di * h
        proj += 2 * tokens * (di + 2 * gn) * c["conv_kernel"]
        proj_b = (h * win + di * h) * itemsize + 2 * act \
            + 2 * tokens * win * itemsize + 2 * tokens * di * itemsize
        # per chunk: C B^T [L, L, N] a group; (M x) [L, L, P] a head; the
        # chunk's state [L, P, N] a head; C S [L, P, N] a head
        scan = 2 * tokens * L * n * g + 2 * tokens * L * pd * heads \
            + 2 * 2 * tokens * pd * n * heads
        # x, B, C, dt read, y written; a [P, N] float32 state a chunk and
        # head written and read
        scan_b = tokens * (2 * di + 2 * gn) * itemsize + tokens * heads * 4 \
            + 2 * (tokens // L) * heads * pd * n * 4
        return {"ssm_proj_conv": (proj, proj_b), "ssm_scan": (scan, scan_b)}
    if kind == "*":
        hq, hkv, d = c["attn_heads"], c["kv_heads"], c["head_dim"]
        q, kv = hq * d, hkv * d
        proj = 2 * tokens * h * (q + 2 * kv) + 2 * tokens * q * h
        proj_b = (h * (q + 2 * kv) + q * h) * itemsize + 2 * act
        # scores and the weighted sum, each 2 T^2 D a head, the causal half
        attn = (tokens // t) * 2 * (2 * t * t * d * hq) // 2
        attn_b = tokens * (2 * q + 2 * kv) * itemsize
        return {"attention_proj": (proj, proj_b),
                "attention_kernel": (attn, attn_b)}
    if kind == "E":
        f, fs, e = c["expert_hidden"], c["shared_hidden"], c["experts_total"]
        if rows_here is None:
            rows_here = tokens * c["top_k"] * c["experts_held"] // e
        router = 2 * tokens * h * e
        grouped = 2 * 2 * rows_here * h * f
        shared = 2 * 2 * tokens * h * fs
        grouped_b = 2 * c["experts_held"] * h * f * itemsize \
            + 2 * rows_here * h * itemsize
        return {"moe_grouped_matmul": (grouped, grouped_b),
                "moe_rest": (router + shared,
                             2 * h * fs * itemsize + h * e * 4 + 2 * act)}
    raise ValueError(kind)


def step_cost(args, batch, itemsize=2, rows_here=None):
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

    def add(name, cost):
        f, b = parts.get(name, (0, 0))
        parts[name] = (f + cost[0], b + cost[1])

    n_e = max(1, c["pattern"].count("E"))
    for kind in c["pattern"]:
        per = None if rows_here is None else rows_here // n_e
        for name, cost in layer_cost(kind, args, tokens, itemsize,
                                     per).items():
            add(name, cost)
    h, v = c["hidden"], c["vocab"]
    add("lm_head_loss", (2 * tokens * h * v,
                         h * v * itemsize + tokens * h * itemsize
                         + 2 * tokens * v * itemsize))
    add("embed", (0, 2 * tokens * h * itemsize))
    fwd = sum(f for f, _ in parts.values())
    n_params = sum(int(np.prod(s)) for s in param_shapes(args).values())
    state_bytes = n_params * (16 + 12 + itemsize)
    parts = {k: (3 * f, 3 * b) for k, (f, b) in parts.items()}
    return {"flops": 3 * fwd, "recompute_flops": fwd,
            "bytes": sum(b for _, b in parts.values()) + state_bytes,
            "state_bytes": state_bytes, "params": n_params, "parts": parts}
