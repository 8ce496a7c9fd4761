"""The reference's training: seeded weights, the loss, and three steps of
SGD with momentum, in float32 at ``highest`` matmul precision, and single
forward passes at the other precisions of ``arrays.PRECISIONS``. It takes
the seed, the batches and the recipe; nothing the program has made.
"""
import functools
import glob
import hashlib
import importlib
import os
import pickle
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import arrays, walk


def seed_key(seed):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def load_net(name):
    """The layer table a configuration names: ``net`` of the file
    ``reference/<name>.py``. A new net is a new file; none is listed
    anywhere."""
    return importlib.import_module(__package__ + "." + name).net


def param_shapes(net, net_args, input_shape):
    return walk.walk(load_net(net), input_shape, **net_args).params


def init_params(shapes, seed, sharding=None):
    """Every parameter from the seed in ONE jitted call on the device, in
    float32 (the type the master copy is kept in): Xavier (uniform,
    magnitude 3, averaged fans, the reference framework's default) for
    weights, gamma 1, beta and biases 0."""
    names = list(shapes)
    sizes = {n: int(np.prod(shapes[n])) for n in names
             if n.endswith("_weight")}

    def make(key):
        # ONE draw for all the weights, cut up: a draw per leaf made the
        # compiler build hundreds of generators, tens of seconds of
        # set-up in every run (PR 23)
        flat = jax.random.uniform(key, (sum(sizes.values()),), jnp.float32,
                                  -1.0, 1.0)
        out, at = {}, 0
        for name in names:
            shape = shapes[name]
            if name.endswith("_weight"):
                hw = int(np.prod(shape[2:]))
                scale = np.sqrt(3.0 / ((shape[1] + shape[0]) * hw / 2.0))
                out[name] = flat[at:at + sizes[name]].reshape(shape) * scale
                at += sizes[name]
            elif name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def aux_shapes(shapes):
    """BatchNorm's moving statistics, which the program keeps beside the
    parameters: name -> (shape, initial value)."""
    out = {}
    for name, shape in shapes.items():
        if name.endswith("_gamma"):
            base = name[:-len("gamma")]
            out[base + "moving_mean"] = (shape, 0.0)
            out[base + "moving_var"] = (shape, 1.0)
    return out


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def make_step(net, net_args, recipe, precision=None):
    """jitted (params, moms, x, y) -> (params, moms, mean loss, per-leaf
    norms of the gradient the optimizer gets: the batch mean's gradient,
    before weight decay is added; every row's log-probabilities)."""
    net_fn = load_net(net)
    lr, mom, wd = recipe["learning_rate"], recipe["momentum"], recipe["wd"]

    def loss_fn(params, x, y):
        logits = net_fn(arrays.ArrayOps(params, precision), x, **net_args)
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        return loss, logp

    @jax.jit
    def step(params, moms, x, y):
        (loss, rows), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, y)
        norms = _leaf_norms(grads)
        new_p, new_m = {}, {}
        for k, w in params.items():
            m = mom * moms[k] - lr * (grads[k] + wd * w)
            new_m[k] = m
            new_p[k] = w + m
        return new_p, new_m, loss, norms, rows

    return step


def forward_logprob(net, net_args, params, x, precision=None):
    """The log-probabilities of one forward pass at ``params`` (training
    mode: the batch's statistics) at one of ``arrays.PRECISIONS``, rows x
    classes, on the host. What the stated precision's noise floor and the
    controls are read from: a forward pass needs no backward program."""
    net_fn = load_net(net)

    @jax.jit
    def forward(params, x):
        logits = net_fn(arrays.ArrayOps(params, precision, remat=False), x,
                        **net_args)
        return jax.nn.log_softmax(logits, axis=-1)

    with jax.default_matmul_precision("highest"):
        forward = compiled_once(forward, (params, x),
                                ("forward", net, net_args, precision))
        return np.asarray(forward(params, x), np.float64)


def compiled_once(step, args, tag):
    """``step`` compiled for ``args``, kept from one process to the next.

    JAX's own persistent cache did not serve the ResNet-50 reference (each
    run built it again, ~175 s on the v5e's host; PR 23), so the compiled
    program is kept here under a key of our own: what is computed (``tag``
    and this package's source), for which shapes and placement, by which
    JAX on which chip. The file lies beside JAX's cache. Any failure to
    keep or read it falls back to compiling, which is only slower."""
    from jax.experimental import serialize_executable as se

    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not root or jax.default_backend() == "cpu":
        return step
    here = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256(repr((tag, jax.__version__,
                             jax.devices()[0].device_kind)).encode())
    for leaf in jax.tree_util.tree_leaves(args):
        h.update(repr((leaf.shape, str(leaf.dtype),
                       str(getattr(leaf, "sharding", None)))).encode())
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        with open(path, "rb") as f:
            h.update(f.read())
    path = os.path.join(root, "benchmark_reference",
                        "step-%s.pkl" % h.hexdigest()[:32])
    try:
        if os.path.exists(path):
            with open(path, "rb") as f:      # written below, by us
                return se.deserialize_and_load(*pickle.load(f))
        compiled = step.lower(*args).compile()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp%d" % os.getpid(), "wb") as f:
            pickle.dump(se.serialize(compiled), f)
        os.replace(f.name, path)
        return compiled
    except Exception as e:    # keep going: the cache is a convenience
        warnings.warn("reference program not kept (%s: %s)"
                      % (type(e).__name__, e))
        return step


def follow(net, net_args, recipe, params, batches, precision=None):
    """Follow the first ``len(batches)`` steps from ``params``. Returns
    {"losses", "grad_norms": the first gradient's norm by leaf,
    "delta_norms": the norm of the parameters' change over all the steps
    by leaf, "logprob": the first step's log-probabilities, rows x
    classes, at the seeded weights}, on the host."""
    step = make_step(net, net_args, recipe, precision)
    with jax.default_matmul_precision("highest"):
        p = params
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        # labels as int32 where the rows are (a host iterator's labels
        # arrive on the CPU backend)
        batches = [(jnp.asarray(x), y) for x, y in batches]
        batches = [(x, jax.device_put(jnp.asarray(y, jnp.int32), x.sharding))
                   for x, y in batches]
        step = compiled_once(step, (p, m) + batches[0],
                             (net, net_args, recipe, precision))
        losses, grad_norms, row_logprob = [], None, None
        for x, y in batches:
            p, m, loss, norms, rows = step(p, m, x, y)
            losses.append(loss)
            if grad_norms is None:
                grad_norms, row_logprob = norms, rows
        delta = _leaf_norms({k: p[k] - params[k] for k in params})
    fetch = functools.partial(jax.tree_util.tree_map, float)
    return {"losses": fetch(losses), "grad_norms": fetch(grad_norms),
            "delta_norms": fetch(delta),
            "logprob": np.asarray(row_logprob, np.float64)}
