"""Graph executor.

TPU-native re-design of the reference's GraphExecutor
(``src/symbol/graph_executor.h:23-279``): binding a Symbol yields an
executor whose forward and forward+backward paths are each ONE jitted XLA
computation over the whole graph. This is the reference's bulk-execution
design (``InitOpSegs``, ``graph_executor.cc:842-892``) taken to its
conclusion: instead of pushing per-node engine ops, XLA fuses, schedules and
plans memory for the entire graph (subsuming the reference's
GraphStorageAllocator, ``src/symbol/graph_memory_allocator.h``).

Autodiff: the reference builds an explicit backward graph
(``StaticGraph::MakeBackwardPass``, ``static_graph.cc:395``); here the
backward computation is ``jax.vjp`` through the same graph-eval function,
with op-custom gradients (SoftmaxOutput etc.) supplied via
``jax.custom_vjp`` in each op's ``apply``.

Training-step laziness: ``forward(is_train=True)`` records inputs;
``backward()`` then runs a single fused fwd+bwd XLA computation that also
materializes the outputs — so a fit() iteration costs exactly one device
dispatch. Auxiliary states (BatchNorm moving stats) commit on ``backward()``
(divergence from the reference: a train-mode forward with no backward does
not update moving stats).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import telemetry as _tel
from .base import MXNetError, getenv
from .context import Context
from .engine import get_engine
from .ndarray import NDArray
from .ops.registry import OpContext
from . import random as _random

__all__ = ["Executor", "make_graph_eval", "zero_cotangent"]


def zero_cotangent(x):
    """A vjp cotangent of zeros for ``x``: float0 for non-differentiable
    (integer/bool) primal outputs — a plain zeros_like would make
    ``jax.vjp`` reject graphs with integer internals (Cast). Shared by
    the executor's fused fwd+bwd and the whole-batch fused train step
    (:mod:`mxnet_tpu.fused_step`)."""
    import jax
    import jax.numpy as jnp

    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


class _ConvGroup:
    """Sibling convolutions lowered as one (:func:`_plan_conv_groups`).
    ``links[d]`` holds every member's node at link ``d`` of its chain, as
    far as the merge reaches: the convolutions, then their BatchNorms,
    then their Activations."""

    __slots__ = ("links", "conv_op", "offsets", "channel_axis")

    def __init__(self, links, channel_axis):
        from .ops.nn import Convolution

        self.links = links
        self.channel_axis = channel_axis
        self.offsets = [0]
        for conv in links[0]:
            self.offsets.append(self.offsets[-1] + conv.op.num_filter)
        self.conv_op = Convolution(**dict(links[0][0].op.params,
                                          num_filter=self.offsets[-1]))

    def pays(self, data_shape):
        """Whether the one wide convolution moves fewer bytes than its
        members apart, reckoned from the shapes. Saved for certain: the
        input is read by the forward pass and by the weight gradient, and
        its gradient is written and read again for the sum, once instead
        of once a member — 4 (k-1) passes over the input. At risk: up to
        3 passes over the outputs, where the slices, their gradients'
        concatenation and a BatchNorm pass come off the fusions they rode.
        (On the v5e, PR 25: Inception-BN's ten groups, at least as wide in
        as out, won 2.8 ms of a 50.6 ms step; ResNet-50's ``stage0_unit0``,
        64 channels in and 64 + 256 out at 56x56, lost 1.9 of 100.2 ms.)"""
        _, (out_shape,), _ = self.conv_op.infer_shape([tuple(data_shape)])
        return 4 * (len(self.links[0]) - 1) * math.prod(data_shape) \
            >= 3 * math.prod(out_shape)


def _plan_conv_groups(op_nodes, out_index, node_device, seg_of):
    """Find the sibling convolutions of a graph: two or more
    ``Convolution`` nodes that read the same tensor with the same
    geometry are one convolution of the summed output width — the same
    multiply-adds, but the input is read once a pass instead of once a
    member, and one input gradient is written instead of one a member to
    be summed. XLA has no pass that joins them, so the lowering does.

    The merge follows the members down their chains while they stay
    alike: where every member's output feeds only a ``BatchNorm`` (equal
    parameters, on the convolution's channel axis) those run as one over
    the concatenated channels — per-channel statistics do not mix
    channels — and where each of those feeds only an ``Activation`` of one
    ``act_type``, that does too. Where the tails differ the merge ends at
    the last common link. Decided from the graph alone: members share
    device (``node_device``) and remat segment (``seg_of``), and every
    other input of the merged nodes (weight, bias, gamma, beta) is there
    before the first member's place in the order.

    Returns ``{uid: _ConvGroup}`` for every node a group evaluates.
    Whether a group is worth lowering as one is for the shapes to say,
    when the program is traced (:meth:`_ConvGroup.pays`)."""
    from .ops.nn import Activation, BatchNorm, Convolution

    pos = {n.uid: i for i, n in enumerate(op_nodes)}
    readers = {}
    for n in op_nodes:
        for slot, (src, i) in enumerate(n.inputs):
            readers.setdefault((src.uid, i), []).append((n, slot))
    for key in out_index:           # a graph output is read too
        readers.setdefault(key, []).append((None, 0))

    def place(n):
        return (node_device(n) if node_device is not None else None,
                seg_of.get(n.uid, 0))

    def sole_reader(n):
        """The node that alone reads ``n``'s output, as its data."""
        users = readers.get((n.uid, 0), [])
        if len(users) == 1 and users[0][1] == 0:
            return users[0][0]
        return None

    def ready(link, first):
        """Are the parameters of ``link`` there where ``first`` runs?"""
        return all(pos.get(src.uid, -1) < pos[first.uid]
                   for n in link for src, _ in n.inputs[1:])

    siblings = {}
    for n in op_nodes:
        if type(n.op) is Convolution and n.op.num_group == 1:
            key = (n.inputs[0][0].uid, n.inputs[0][1], n.op._norm_params(),
                   n.op._is_nhwc(), n.op.no_bias, place(n))
            siblings.setdefault(key, []).append(n)

    group_of = {}
    for convs in siblings.values():
        first = convs[0]
        if len(convs) < 2 or not ready(convs, first):
            continue
        ndim = len(first.op.kernel) + 2
        caxis = ndim - 1 if first.op._is_nhwc() else 1
        links = [convs]
        for cls, alike in (
                (BatchNorm, lambda op: (op.eps, op.momentum, op.fix_gamma,
                                        op.use_global_stats)
                 if op.axis % ndim == caxis else None),
                (Activation, lambda op: op.act_type)):
            nxt = [sole_reader(n) for n in links[-1]]
            if any(n is None or type(n.op) is not cls for n in nxt):
                break
            kinds = {alike(n.op) for n in nxt}
            if len(kinds) != 1 or None in kinds or not ready(nxt, first) \
                    or {place(n) for n in nxt} != {place(first)}:
                break
            links.append(nxt)
        group = _ConvGroup(links, caxis)
        group_of.update((n.uid, group) for link in links for n in link)
    return group_of


def _device_free_bytes():
    """Bytes the local devices report free now (the least over them), or
    None where one reports nothing: the CPU backend."""
    import jax

    free = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "bytes_limit" not in stats or "bytes_in_use" not in stats:
            return None
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return min(free)


def _plan_kept(plans, arg_index, arg_list, budget):
    """What the segmented recomputation keeps of the program being
    traced: ``{uid: {result: name}}``, the ``checkpoint_name`` under which
    each chosen node's ``apply`` marks the value (``OpContext.keep``).

    Ops say what may be kept (:meth:`Operator.remat_results`: a result,
    the bytes it holds, the operations it costs to compute again), from
    the shapes and dtypes this walk infers as binding does. A result
    stated without a cost is kept always. The others are kept by
    descending operations a byte while they fit ``budget`` bytes. The
    last segment is not recomputed, so nothing of it is a candidate.

    ``budget`` ``None`` takes it from what can be observed: the bytes the
    device reports free now, less what the step itself will want there,
    reckoned from the shapes with room to spare: its arguments twice
    (their copies in the compute dtype, and their gradients), every value
    that crosses a segment boundary, and the largest segment's values
    twice (recomputed, and their gradients). Nothing where the device
    reports nothing. (On the v5e, PR 29, a language model of 667 M
    parameters at 8,192 tokens: 8.90 GB free, 5.59 reckoned where the
    compiler's own count came to 5.46, so 3.31 GB of budget; every
    candidate's 1.39 GB kept, and the step read 247 ms for 269.)

    Counted once a traced program: ``remat.segments``,
    ``remat.segments_recomputed``, ``remat.kept_results``, gauges
    ``remat.kept_bytes`` and ``remat.budget_bytes``."""
    from .ops.registry import Operator

    def nbytes(shape, dtype):
        return math.prod(shape) * np.dtype(dtype).itemsize

    always, ranked, held, crossing = [], [], [0], 0
    if any(type(n.op).remat_results is not Operator.remat_results
           for seg, _, _ in plans[:-1] for n in seg):
        shapes = {uid: [tuple(arg_list[i].shape)]
                  for uid, i in arg_index.items()}
        types = {uid: [arg_list[i].dtype] for uid, i in arg_index.items()}
        for seg, _, out_keys in plans:
            for n in seg:
                in_shapes = [shapes[src.uid][i] for src, i in n.inputs]
                in_types = [types[src.uid][i] for src, i in n.inputs]
                shapes[n.uid] = n.op.infer_shape(in_shapes)[1]
                types[n.uid] = n.op.infer_type(in_types)[1]
                if seg is not plans[-1][0]:
                    for result, size, ops in n.op.remat_results(
                            in_shapes, in_types):
                        (always if ops is None else ranked).append(
                            (n, result, size, ops))
            held.append(sum(nbytes(sh, t) for n in seg for sh, t in
                            zip(shapes[n.uid], types[n.uid])))
            crossing += sum(nbytes(shapes[uid][i], types[uid][i])
                            for uid, i in out_keys)
    if budget is None:
        free = _device_free_bytes()
        reserve = 2 * sum(nbytes(a.shape, a.dtype) for a in arg_list) \
            + crossing + 2 * max(held)
        budget = 0 if free is None else max(0, free - reserve)
    kept, kept_bytes, left = {}, 0, budget
    ranked.sort(key=lambda c: -c[3] / max(c[2], 1))
    for n, result, size, ops in always + ranked:
        if ops is not None:
            if size > left:
                continue
            left -= size
        kept.setdefault(n.uid, {})[result] = "%s:%s" % (n.name, result)
        kept_bytes += size
    _tel.inc("remat.segments", len(plans))
    _tel.inc("remat.segments_recomputed", len(plans) - 1)
    _tel.inc("remat.kept_results", sum(len(r) for r in kept.values()))
    _tel.set_gauge("remat.kept_bytes", kept_bytes)
    _tel.set_gauge("remat.budget_bytes", budget)
    return kept


def _weight_grad_flops(symbol, arg_shapes):
    """``{argument: operations}`` of the products that form each
    argument's gradient, as the nodes that read it state them
    (:meth:`Operator.weight_grad_flops`) from the shapes this walk infers
    as binding does; summed where several nodes read one argument. An
    argument that any reader says nothing about is left out."""
    shapes, flops, silent = {}, {}, set()
    for n in symbol._topo():
        if n.is_variable:
            shapes[n.uid] = [tuple(arg_shapes[n.name])]
            continue
        in_shapes = [shapes[src.uid][i] for src, i in n.inputs]
        shapes[n.uid] = n.op.infer_shape(in_shapes)[1]
        stated = n.op.weight_grad_flops(in_shapes)
        for slot, (src, _) in enumerate(n.inputs):
            if not src.is_variable:
                continue
            if slot in stated:
                flops[src.name] = flops.get(src.name, 0) + stated[slot]
            else:
                silent.add(src.name)
    return {name: f for name, f in flops.items() if name not in silent}


def make_graph_eval(symbol, node_device=None, remat=False,
                    remat_budget=None):
    """Build the pure graph-eval function for a symbol.

    Returns ``(eval_graph, n_aux)`` where
    ``eval_graph(arg_list, aux_list, key, is_train, want_internals=False)``
    evaluates the whole DAG over jnp arrays. Shared by :class:`Executor`
    and the sharded training-step builders in :mod:`mxnet_tpu.parallel`.

    ``node_device(node) -> jax.Device | None`` implements model-parallel
    placement (the reference's ``ctx_group``/``AssignContext`` +
    ``_CrossDeviceCopy`` insertion, ``graph_executor.cc:391-508``): inputs
    of a placed node are ``device_put`` to its device inside the single
    jitted program, so XLA emits the cross-device transfers — and their
    reverse transfers in the backward pass — in one compiled computation.

    ``remat=True`` is the memonger design behind the reference's
    ``MXNET_BACKWARD_DO_MIRROR`` (``static_graph.cc:395-439``): the topo
    order is split into ~sqrt(N) segments and each but the last evaluates
    under ``jax.checkpoint``, so the backward pass stores only segment
    BOUNDARY activations and recomputes inside each segment — sublinear
    activation memory for chain-like graphs. (Wrapping the whole
    function in one checkpoint would save nothing: the recompute would
    materialize every activation again at once.) The last segment's
    backward starts where its forward ends, so it is evaluated plainly.
    What else stays is decided by what it costs to compute again against
    what it costs to hold, from the shapes, when a program is traced
    (:func:`_plan_kept`, :meth:`Operator.remat_results`);
    ``remat_budget`` states the bytes the ranked results may take in
    place of what the device reports free. Internals-mode calls fall back
    to the unsegmented path (monitoring wants every tensor live anyway).

    Sibling convolutions — Inception's parallel 1x1 branches, a ResNet
    unit's first 1x1 beside its unstrided shortcut — are lowered as one
    convolution of the summed width where that moves fewer bytes
    (:func:`_plan_conv_groups` finds them here, from the graph;
    :meth:`_ConvGroup.pays` decides from the shapes when a program is
    traced; telemetry ``lower.conv_groups_merged`` /
    ``lower.convs_merged`` count groups and members per traced program).
    Parameter names, shapes and checkpoints are untouched.
    ``want_internals=True`` lowers every node on its own, as before.
    """
    import jax

    nodes = symbol._topo()
    arg_index = {}
    i = 0
    for n in nodes:
        if n.is_variable:
            arg_index[n.uid] = i
            i += 1
    aux_slots = {}
    slot = 0
    for n in nodes:
        if not n.is_variable:
            k = len(n.op.list_auxiliary_states())
            if k:
                aux_slots[n.uid] = list(range(slot, slot + k))
                slot += k
    n_aux = slot
    out_index = [(n.uid, i) for n, i in symbol._outputs]
    op_nodes = [n for n in nodes if not n.is_variable]
    segments = []
    if remat:
        # segmented remat (memonger / sqrt schedule)
        n_seg = max(2, int(math.isqrt(len(op_nodes))))
        seg_size = max(1, (len(op_nodes) + n_seg - 1) // n_seg)
        segments = [op_nodes[i:i + seg_size]
                    for i in range(0, len(op_nodes), seg_size)]
    seg_of = {n.uid: si for si, seg in enumerate(segments) for n in seg}
    group_of = _plan_conv_groups(op_nodes, out_index, node_device, seg_of)

    def _eval_group(g, env, aux_out, is_train):
        """One convolution (+ BatchNorm + Activation, as far as the plan
        merged them) for all members of ``g``, sliced per member into env;
        False, and nothing done, where the shapes say it does not pay.
        The parameters stay the members' own: they are concatenated here,
        inside the traced program, and each gets its gradient through the
        concatenation's transpose."""
        import jax.numpy as jnp

        convs = g.links[0]
        src, i = convs[0].inputs[0]
        if not g.pays(env[src.uid][i].shape):
            return False
        _tel.inc("lower.conv_groups_merged")
        _tel.inc("lower.convs_merged", len(convs))
        dev = node_device(convs[0]) if node_device is not None else None

        def put(x):
            return x if dev is None else jax.device_put(x, dev)

        def gather(link, slot):
            return jnp.concatenate(
                [put(env[n.inputs[slot][0].uid][n.inputs[slot][1]])
                 for n in link])

        def scope(link):
            return jax.named_scope("%s:%s" % (
                link[0].op.op_name, "+".join(n.name for n in link)))

        def cut(y, axis):
            return [jax.lax.slice_in_dim(y, lo, hi, axis=axis)
                    for lo, hi in zip(g.offsets, g.offsets[1:])]

        ins = [put(env[src.uid][i])] + [
            gather(convs, slot) for slot in range(1, len(convs[0].inputs))]
        octx = OpContext(is_train, None)    # none of the three ops draws
        with scope(convs):
            (y,), _ = g.conv_op.apply(octx, ins, [])
        if len(g.links) > 1:
            bns = g.links[1]
            slots = [aux_slots[n.uid] for n in bns]
            aux_in = [jnp.concatenate([aux_out[s[k]] for s in slots])
                      for k in range(len(slots[0]))]
            with scope(bns):
                (y,), new_aux = bns[0].op.apply(
                    octx, [y, gather(bns, 1), gather(bns, 2)], aux_in)
            for k, a in enumerate(new_aux):
                for s, piece in zip(slots, cut(a, 0)):
                    aux_out[s[k]] = piece
        if len(g.links) > 2:
            acts = g.links[2]
            with scope(acts):
                (y,), _ = acts[0].op.apply(octx, [y], [])
        for n, piece in zip(g.links[-1], cut(y, g.channel_axis)):
            env[n.uid] = [piece]
        return True

    def _eval_nodes(node_list, env, aux_out, key, is_train,
                    internals=None, kept=None):
        """Evaluate op nodes into env (uid -> outputs list) in place.
        With ``internals`` every node is lowered on its own (the monitor
        wants each node's tensor); without, sibling convolutions are
        lowered as one where that pays. ``kept``: what the recomputation
        plan keeps of each node (:func:`_plan_kept`)."""
        merged = set()
        for n in node_list:
            g = group_of.get(n.uid) if internals is None else None
            if g is not None:
                if n is g.links[0][0] and _eval_group(g, env, aux_out,
                                                      is_train):
                    merged.add(id(g))
                if id(g) in merged:
                    continue
            ins = [env[src.uid][i] for src, i in n.inputs]
            if node_device is not None:
                dev = node_device(n)
                if dev is not None:
                    ins = [jax.device_put(x, dev) for x in ins]
            slots = aux_slots.get(n.uid, [])
            aux_in = [aux_out[s] for s in slots]
            rng = jax.random.fold_in(key, n.uid) if key is not None else None
            octx = OpContext(is_train, rng, kept and kept.get(n.uid))
            # the node's name on every op it lowers to (metadata only;
            # an operator made outside the registry has its class's)
            op_name = getattr(n.op, "op_name", type(n.op).__name__)
            with jax.named_scope("%s:%s" % (op_name, n.name)):
                outs, new_aux = n.op.apply(octx, ins, aux_in)
            for s, a in zip(slots, new_aux):
                aux_out[s] = a
            env[n.uid] = list(outs)
            if internals is not None:
                for oi, o in enumerate(outs):
                    oname = "%s_%s" % (n.name, n.op.list_outputs()[oi])
                    internals[oname] = o

    def eval_graph(arg_list, aux_list, key, is_train, want_internals=False):
        env = {}
        aux_out = list(aux_list)
        internals = {} if want_internals else None
        for n in nodes:
            if n.is_variable:
                env[n.uid] = [arg_list[arg_index[n.uid]]]
        _eval_nodes(op_nodes, env, aux_out, key, is_train, internals)
        outputs = [env[uid][i] for uid, i in out_index]
        if want_internals:
            return outputs, aux_out, internals
        return outputs, aux_out

    if not remat:
        return eval_graph, n_aux

    # static plan: which (uid, out_idx) values cross each segment
    # boundary. A segment must emit the values it produces that a later
    # segment or the graph outputs consume. Variables are never segment
    # outputs — they sit in the caller's store for the duration.
    consumed_later = [set() for _ in segments]
    for si, seg in enumerate(segments):
        for n in seg:
            for src, i in n.inputs:
                src_seg = seg_of.get(src.uid, -1)  # -1: a variable
                if 0 <= src_seg < si:
                    consumed_later[src_seg].add((src.uid, i))
    for uid, i in out_index:
        src_seg = seg_of.get(uid, -1)
        if src_seg >= 0:
            consumed_later[src_seg].add((uid, i))

    plans = []
    for si, seg in enumerate(segments):
        in_keys = sorted(
            {(src.uid, i) for n in seg for src, i in n.inputs
             if seg_of.get(src.uid, -1) != si},
            key=lambda k: (k[0], k[1]))
        out_keys = sorted(consumed_later[si], key=lambda k: (k[0], k[1]))
        plans.append((seg, in_keys, out_keys))

    def eval_graph_remat(arg_list, aux_list, key, is_train,
                         want_internals=False):
        if want_internals:
            return eval_graph(arg_list, aux_list, key, is_train,
                              want_internals=True)
        store = {}
        for n in nodes:
            if n.is_variable:
                store[(n.uid, 0)] = arg_list[arg_index[n.uid]]
        aux_state = list(aux_list)
        # what is dear to recompute and cheap to hold stays, by the
        # shapes of this trace; all else inside a segment is recomputed.
        # The last segment's backward starts where its forward ends:
        # recomputing it would free nothing at the step's peak.
        kept = _plan_kept(plans, arg_index, arg_list, remat_budget)
        checkpoint = functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.save_only_these_names(
                *(name for r in kept.values() for name in r.values())))
        for seg, in_keys, out_keys in plans:
            def seg_fn(in_vals, aux_vals, _seg=seg, _in=in_keys,
                       _out=out_keys):
                # boundary values keyed as {uid: {out_idx: val}} — both
                # dict and the list envs produced by _eval_nodes support
                # the env[uid][i] indexing the node loop uses
                env = {}
                for (uid, i), v in zip(_in, in_vals):
                    env.setdefault(uid, {})[i] = v
                aux_out = list(aux_vals)
                _eval_nodes(_seg, env, aux_out, key, is_train, kept=kept)
                return [env[uid][i] for uid, i in _out], aux_out

            in_vals = [store[k] for k in in_keys]
            if seg is not segments[-1]:
                seg_fn = checkpoint(seg_fn)
            out_vals, aux_state = seg_fn(in_vals, aux_state)
            store.update(zip(out_keys, out_vals))
        outputs = [store[(uid, i)] for uid, i in out_index]
        return outputs, aux_state

    return eval_graph_remat, n_aux


_UNSET = object()  # distinguishes "not passed" from explicit None


class Executor:
    def __init__(self, symbol, ctx: Context, args, args_grad=None,
                 grad_req: Union[str, Dict[str, str], List[str]] = "write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 compute_dtype=_UNSET, label_names=None):
        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = group2ctx or {}
        # mixed precision: compute in this dtype (e.g. "bfloat16") with
        # full-precision params/grads outside the jitted graph. Default
        # comes from MXNET_COMPUTE_DTYPE so existing scripts opt in via
        # env; pass compute_dtype=None to force full precision for this
        # executor even when the env var is set.
        if compute_dtype is _UNSET:
            compute_dtype = getenv("MXNET_COMPUTE_DTYPE", None)
        self._compute_dtype = compute_dtype
        # args that must never be cast under mixed precision; when the
        # binder doesn't say (plain symbol.bind), fall back to the
        # "*label" naming convention
        self._label_names = (set(label_names) if label_names is not None
                             else {n for n in symbol.list_arguments()
                                   if n.endswith("label")})
        self.arg_names = symbol.list_arguments()
        if len(set(self.arg_names)) != len(self.arg_names):
            # two distinct Variable nodes sharing a name: name-keyed
            # binding would silently drop one (reference GraphExecutor
            # rejects this with "Find duplicate argument name")
            dups = sorted({n for n in self.arg_names
                           if self.arg_names.count(n) > 1})
            raise MXNetError(
                "duplicate argument name(s) %s: reuse one Variable "
                "instance instead of creating it twice" % dups)
        self.output_names = symbol.list_outputs()
        self.aux_names = symbol.list_auxiliary_states()

        self.arg_arrays = self._to_list(args, self.arg_names, "args")
        self.arg_dict = dict(zip(self.arg_names, self.arg_arrays))
        if args_grad is None:
            self.grad_arrays = [None] * len(self.arg_names)
        else:
            self.grad_arrays = self._to_list(args_grad, self.arg_names,
                                             "args_grad", allow_missing=True)
        self.grad_dict = {n: g for n, g in zip(self.arg_names, self.grad_arrays)
                          if g is not None}

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        for n in self.arg_names:
            if self.grad_dict.get(n) is None:
                self._grad_req[n] = "null"

        aux_states = aux_states or []
        self.aux_arrays = self._to_list(aux_states, self.aux_names, "aux_states")
        self.aux_dict = dict(zip(self.aux_names, self.aux_arrays))

        self._outputs: Optional[List[NDArray]] = None
        self._train_pending = False
        self._monitor_callback = None
        self._step = 0
        self._base_key = None

        self._build()

    @staticmethod
    def _to_list(arrays, names, what, allow_missing=False):
        if arrays is None:
            arrays = {}
        if isinstance(arrays, dict):
            out = [arrays.get(n) for n in names]
            if not allow_missing and any(a is None for a in out):
                missing = [n for n, a in zip(names, out) if a is None]
                raise MXNetError("%s: missing arrays for %s" % (what, missing))
            return out
        arrays = list(arrays)
        if len(arrays) != len(names):
            raise MXNetError("%s: expected %d arrays, got %d"
                             % (what, len(names), len(arrays)))
        return arrays

    # ------------------------------------------------------------------
    # graph -> pure function
    # ------------------------------------------------------------------
    def _build(self):
        import jax

        _tel.inc("executor.bind")
        node_device = None
        if self._group2ctx:
            group2dev = {g: c.jax_device() for g, c in self._group2ctx.items()}

            def node_device(n):  # noqa: F811
                group = n.attrs.get("ctx_group")
                return group2dev.get(group)

        # MXNET_BACKWARD_DO_MIRROR (reference static_graph.cc:395-439
        # memonger mirroring): segmented remat — see make_graph_eval
        do_mirror = getenv("MXNET_BACKWARD_DO_MIRROR", False)
        eval_graph, self._n_aux = make_graph_eval(self._symbol, node_device,
                                                  remat=do_mirror)
        self._eval_graph = eval_graph

        grad_idx = [i for i, n in enumerate(self.arg_names)
                    if self._grad_req.get(n, "null") != "null"]
        self._grad_idx = grad_idx

        cdtype = None
        if self._compute_dtype is not None:
            import jax.numpy as jnp
            if isinstance(self._compute_dtype, str):
                cdtype = getattr(jnp, self._compute_dtype, None)
                if cdtype is None or not isinstance(cdtype, type):
                    raise MXNetError(
                        "invalid compute dtype %r (MXNET_COMPUTE_DTYPE / "
                        "compute_dtype); expected a jax dtype name like "
                        "'bfloat16' or 'float16'" % (self._compute_dtype,))
            else:
                cdtype = self._compute_dtype
        # label args keep full precision (bf16 cannot represent class ids
        # >= 256 exactly), and so do the variables an op declares it must
        # read uncast (token ids into Embedding, a router's float32
        # weights); everything else float casts to compute dtype
        uncast = set(self._label_names)
        for n in self._symbol._topo():
            keep = () if n.is_variable \
                else getattr(n.op, "full_precision_args", ())
            if keep:
                for slot, (src, _) in zip(n.op.list_arguments(), n.inputs):
                    if slot in keep and src.is_variable:
                        uncast.add(src.name)
        cast_arg = [cdtype is not None and n not in uncast
                    for n in self.arg_names]

        def cast_in(args):
            if cdtype is None:
                return args
            import jax.numpy as jnp
            return [a.astype(cdtype)
                    if c and jnp.issubdtype(a.dtype, jnp.floating) else a
                    for a, c in zip(args, cast_arg)]

        def cast_out(outs):
            if cdtype is None:
                return outs
            import jax.numpy as jnp
            return [o.astype(jnp.float32)
                    if jnp.issubdtype(o.dtype, jnp.floating) else o
                    for o in outs]

        def run_graph(args, aux, key, is_train, **kw):
            res = eval_graph(cast_in(args), aux, key, is_train, **kw)
            if kw.get("want_internals"):
                outs, aux_out, internals = res
                return cast_out(outs), aux_out, internals
            outs, aux_out = res
            return cast_out(outs), aux_out

        # the mixed-precision-aware pure graph function, exposed so the
        # fused train step (fused_step.py) can trace fwd+bwd+update as
        # ONE jitted computation with the exact same numerics
        self._run_graph = run_graph

        @jax.jit
        def fwd_infer(args, aux, key):
            outs, _ = run_graph(args, aux, key, False)
            return outs

        @jax.jit
        def fwd_train(args, aux, key):
            return run_graph(args, aux, key, True)

        # Donate the aux buffers (BN running stats) into the fused train
        # step: backward() always replaces them with aux_out, so XLA can
        # write the new stats into the old HBM buffers. Args (params) are
        # NOT donated — they outlive the step (the optimizer update, which
        # donates them itself, runs outside this computation) — and neither
        # are head_grads (self._head_ones is cached across steps). Donation
        # follows the same engine-safety rule as the optimizer kernels,
        # re-checked at every call: set_engine() may switch to a threaded
        # engine after bind, and a donation decision frozen at bind time
        # would keep deleting buffers a queued reader still sees.
        from .optimizer import _donation_ok

        fwd_bwd_cache = {}

        def get_fwd_bwd(want_internals):
            k = (want_internals, _donation_ok())
            if k not in fwd_bwd_cache:
                # a build here means XLA traces + compiles a fresh fused
                # step — the recompile events the telemetry tier exists
                # to make visible (a flapping donation decision or
                # monitor flag shows up as a climbing jit_build count)
                _tel.inc("executor.jit_build")
                fwd_bwd_cache[k] = make_fwd_bwd(*k)
            return fwd_bwd_cache[k]

        def make_fwd_bwd(want_internals, donate):
            # one builder for the plain and the monitored training step:
            # with want_internals the SAME fused fwd+bwd also emits every
            # internal output, so a monitored batch costs one forward
            # (the naive monitor-forward-then-train scheme doubled it)
            def step(args, aux, key, head_grads):
                garr = [args[i] for i in grad_idx]

                def f(garr):
                    full = list(args)
                    for pos, i in enumerate(grad_idx):
                        full[i] = garr[pos]
                    # casts live inside the vjp'd fn: gradients come back
                    # in the arrays' own (full) precision automatically
                    return run_graph(full, aux, key, True,
                                     want_internals=want_internals)

                res, vjp = jax.vjp(f, garr)
                # zero cotangents for everything but the heads
                cts = (head_grads,) + tuple(
                    jax.tree_util.tree_map(zero_cotangent, r)
                    for r in res[1:])
                grads, = vjp(cts)
                return res + (grads,)

            # compile registry site (xprof off -> plain jax.jit; the
            # wrapper keeps .lower() for the HLO regression gates)
            from . import xprof as _xprof

            return _xprof.jit(
                step, site="executor.fwd_bwd",
                arg_names=(tuple(self.arg_names), tuple(self.aux_names),
                           "rng_key", "head_grads"),
                donate_argnums=(1,) if donate else ())

        def fwd_bwd(args, aux, key, head_grads):
            outs, aux_out, grads = get_fwd_bwd(False)(args, aux, key,
                                                      head_grads)
            return outs, grads, aux_out

        def fwd_bwd_monitor(args, aux, key, head_grads):
            outs, aux_out, internals, grads = get_fwd_bwd(True)(
                args, aux, key, head_grads)
            return outs, grads, aux_out, internals

        @jax.jit
        def fwd_monitor(args, aux, key):
            return run_graph(args, aux, key, True, want_internals=True)

        self._fwd_infer = fwd_infer
        self._fwd_train = fwd_train
        self._fwd_bwd = fwd_bwd
        # raw jitted step factory, exposed for the HLO regression gates
        # (tests/test_hlo_gates.py asserts aux donation aliasing on
        # _get_fwd_bwd(False) under the default engine)
        self._get_fwd_bwd = get_fwd_bwd
        self._fwd_monitor = fwd_monitor
        self._fwd_bwd_monitor = fwd_bwd_monitor

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _key(self):
        import jax

        if self._base_key is None:
            self._base_key = _random.next_key()
        self._step += 1
        return jax.random.fold_in(self._base_key, self._step)

    def _arg_data(self):
        return [a._data for a in self.arg_arrays]

    def _aux_data(self):
        return [a._data for a in self.aux_arrays]

    def forward(self, is_train: bool = False, **kwargs):
        """Run forward (reference ``GraphExecutor::Forward``,
        ``graph_executor.cc:990``). kwargs update named input arrays."""
        for name, arr in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError("forward: unknown argument '%s'" % name)
            self.arg_dict[name][:] = arr
        _tel.inc("executor.forward")
        self._last_key = self._key()
        if is_train:
            # lazy: the fused fwd+bwd in backward() materializes outputs;
            # accessing .outputs before backward triggers a fwd-only run.
            # Returns None here — materializing now would double the forward
            # work of every fit() iteration.
            self._train_pending = True
            self._outputs = None
            # monitoring is deferred into the fused fwd+bwd (or the lazy
            # outputs fetch) so the forward runs exactly once per batch;
            # whether to monitor is decided there, so a callback installed
            # between forward and backward still sees this batch. The
            # emitted flag keeps it once per batch even when .outputs is
            # read before backward().
            self._monitor_emitted = False
            return None
        self._train_pending = False
        outs = self._fwd_infer(self._arg_data(), self._aux_data(),
                               self._last_key)
        self._set_outputs(outs)
        return self.outputs

    def backward(self, out_grads=None):
        """Fused forward+backward in one XLA computation (reference
        ``GraphExecutor::Backward``, ``graph_executor.cc:1003``)."""
        import jax.numpy as jnp

        if not self._train_pending:
            raise MXNetError("backward called without forward(is_train=True)")
        _tel.inc("executor.backward")
        # the fused fwd+bwd below is one XLA computation launch; the
        # optimizer update and any metric fold launch separately on this
        # (unfused) path — step.dispatches makes the per-batch dispatch
        # count measurable against MXNET_TPU_FUSED_STEP=1
        _tel.inc("step.dispatches")
        if out_grads is None:
            import jax

            sig = tuple((a.shape, str(a.dtype)) for a in self.arg_arrays)
            if getattr(self, "_head_sig", None) != sig:
                # exact output shapes AND dtypes from abstract evaluation —
                # jax.vjp requires cotangents to match primal dtypes, so
                # fp16/bf16 graphs need fp16/bf16 head grads
                outs_spec, _ = jax.eval_shape(
                    self._fwd_train, self._arg_data(), self._aux_data(),
                    self._last_key)
                self._head_ones = [jnp.ones(s.shape, dtype=s.dtype)
                                   for s in outs_spec]
                self._head_sig = sig
            heads = self._head_ones
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = [g._data for g in out_grads]
        if self._monitor_callback is not None \
                and not getattr(self, "_monitor_emitted", False):
            outs, grads, aux_out, internals = self._fwd_bwd_monitor(
                self._arg_data(), self._aux_data(), self._last_key, heads)
            self._emit_monitor(internals)
        else:
            outs, grads, aux_out = self._fwd_bwd(
                self._arg_data(), self._aux_data(), self._last_key, heads)
        self._set_outputs(outs)
        self._train_pending = False
        for pos, i in enumerate(self._grad_idx):
            name = self.arg_names[i]
            garr = self.grad_arrays[i]
            g = grads[pos]
            req = self._grad_req[name]

            def _assign(garr=garr, g=g, req=req):
                import jax.dtypes

                if getattr(g, "dtype", None) == jax.dtypes.float0:
                    # integer-dtype arg: jax emits a float0 zero-tangent
                    g = jnp.zeros(g.shape, garr.dtype)
                garr._data = (garr._data + g.astype(garr.dtype)
                              if req == "add" else g.astype(garr.dtype))
            get_engine().push(_assign, mutable_vars=[garr._var])
        for arr, new in zip(self.aux_arrays, aux_out):
            def _assign_aux(arr=arr, new=new):
                arr._data = new

            get_engine().push(_assign_aux, mutable_vars=[arr._var])

    @property
    def outputs(self) -> List[NDArray]:
        if self._outputs is None:
            if self._train_pending:
                if self._monitor_callback is not None \
                        and not getattr(self, "_monitor_emitted", False):
                    outs, _, internals = self._fwd_monitor(
                        self._arg_data(), self._aux_data(), self._last_key)
                    self._emit_monitor(internals)
                else:
                    outs, _ = self._fwd_train(
                        self._arg_data(), self._aux_data(), self._last_key)
                self._set_outputs(outs)
            else:
                raise MXNetError("no forward has been run")
        return self._outputs

    def _set_outputs(self, outs):
        self._outputs = [NDArray(o, ctx=self._ctx) for o in outs]

    # ------------------------------------------------------------------
    # monitor (reference MXExecutorSetMonitorCallback ->
    # GraphExecutor::RunOps monitor hook, graph_executor.cc:937-951)
    # ------------------------------------------------------------------
    def set_monitor_callback(self, callback: Callable[[str, NDArray], None]):
        """Install a per-internal-output callback. Semantics are
        per-BATCH, not per-forward: emission happens inside the fused
        fwd+bwd (or the lazy outputs fetch), so each training batch
        fires the callbacks exactly once, and a callback installed
        between forward and backward still observes that batch."""
        self._monitor_callback = callback

    def _emit_monitor(self, internals):
        self._monitor_emitted = True
        for name, value in internals.items():
            self._monitor_callback(name, NDArray(value, ctx=self._ctx))

    # ------------------------------------------------------------------
    # utilities
    # ------------------------------------------------------------------
    def copy_params_from(self, arg_params: Dict[str, NDArray],
                         aux_params: Optional[Dict[str, NDArray]] = None,
                         allow_extra_params: bool = False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("unknown param '%s'" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name][:] = arr
                elif not allow_extra_params:
                    raise MXNetError("unknown aux '%s'" % name)

    def reshape(self, partial_shaping: bool = False, allow_up_sizing: bool = False,
                fresh_args=(), **kwargs) -> "Executor":
        """Rebind to new input shapes, sharing parameter arrays whose shape
        is unchanged (reference ``executor.py:270``). Names in
        ``fresh_args`` always get new storage even at the same shape, so
        writes through the new executor can't alias the old one's inputs."""
        from . import ndarray as nd

        fresh = set(fresh_args)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = []
        new_grads: Dict[str, NDArray] = {}
        for name, shape, arr, grad in zip(self.arg_names, arg_shapes,
                                          self.arg_arrays, self.grad_arrays):
            if shape == arr.shape and name not in fresh:
                new_args.append(arr)
                if grad is not None:
                    new_grads[name] = grad
            else:
                new_args.append(nd.zeros(shape, ctx=self._ctx, dtype=arr.dtype))
                if grad is not None:
                    new_grads[name] = nd.zeros(shape, ctx=self._ctx)
        new_aux = []
        for shape, arr in zip(aux_shapes, self.aux_arrays):
            new_aux.append(arr if shape == arr.shape
                           else nd.zeros(shape, ctx=self._ctx, dtype=arr.dtype))
        return Executor(self._symbol, self._ctx, new_args,
                        new_grads or None, self._grad_req, new_aux,
                        group2ctx=self._group2ctx,
                        compute_dtype=self._compute_dtype,
                        label_names=self._label_names)

    def debug_str(self) -> str:
        """Allocation/graph plan dump (reference GraphExecutor::Print)."""
        lines = ["Symbol outputs: %s" % self.output_names]
        for n in self._symbol._topo():
            kind = "var" if n.is_variable else n.op.op_name
            lines.append("  %-30s %s <- %s" % (
                n.name, kind, [src.name for src, _ in n.inputs]))
        return "\n".join(lines)
