"""Routed-expert feed-forward: one chip's share of a sparse expert layer.

Beyond the reference (2016 MXNet predates sparse experts). The op is told
which experts it holds (``first_held .. first_held + num_held`` of
``num_experts``), routes over ALL of them at the published router width,
and computes the part of the layer's result that its own experts give;
rows routed to an absent expert add nothing here (in an expert-parallel
layout the chip that holds that expert adds them; on one chip the layer
runs without its exchange). What every chip computes alike, a shared
expert, is not this op's: it is plain ``FullyConnected`` nodes beside it,
and a family without one (``models/lfm2_moe.py``) takes the op's result as
the layer's whole result.

**No capacity, no dropped row.** The (row, expert) pairs that land here
are laid out by expert in blocks of ``block`` rows, each block one
expert's (:func:`plan`); a group's last block is padded, with weight 0.
``top_k`` picks distinct experts, so a held expert draws a row at most
once: which rows it draws is a dense compare over ``[held, k, rows]``, one
``lax.sort`` a held expert's column brings them to the front in row order
with their weights riding, and the columns are written end to end, each at
its expert's block-aligned start. The weights' gradient comes back from
the slots to the pairs by one more sort (:func:`pairs_from_slots`), the
loads and the chosen scores by a compare against every expert: nothing in
either pass is a scalar scatter, gather or search whose length is the
pairs or the slots (on the chip those cost 7-30 ns an element, a sort of
the same length under 1 ns a key; PERF.md section 5). The layout's
length is static, ``rows x min(top_k, num_held)`` plus one block an
expert, but the products run in a loop over the blocks really filled (a
dynamic trip count), each block's results scattered back weighted onto
its rows, so work follows the rows really routed: :func:`grouped_experts`,
forward and backward loops written out because a loop of unknown length
has no reverse-mode autodiff. An imbalanced router makes one expert's
group long, never short of a row (an expert may draw every row: its column
is a row long).

Router scores, selection and combine weights are float32 at ``highest``
matmul precision whatever the compute dtype: the choice of experts is a
discontinuous function of the scores. ``score_func`` names the scores:
``"sigmoid"`` (the default: one sigmoid an expert, the DeepSeek line's) or
``"softmax"`` over ALL the experts' logits (Qwen3-Next's 512; the chosen
probabilities over their sum are then its ``norm_topk_prob`` weights). The
combine weights are the chosen scores over their sum plus ``norm_eps`` (the
families differ: ``1e-20``, the default, in the DeepSeek line that the
Nemotron and GLM models follow, ``1e-6`` in ``lfm2_moe``; under a softmax
the sum is at least ``top_k / num_experts`` and ``1e-20`` is below float32's
last bit of it), times ``scale``. The expert products take inputs in the
compute dtype and accumulate in float32.

**The auxiliary load-balancing loss** (``aux_loss_coef`` c > 0; the
families that route by softmax balance by it and have no selection bias):
the step's loss is ``CE + c sum_layers L_aux``, ``L_aux = E sum_e f_e P_e``
(:func:`aux_loss`: ``f_e`` the share of the rows that chose expert ``e``,
no gradient through it, ``P_e`` the mean score; over ALL ``E`` experts, held
or not, and over the rows this node sees: in an expert-parallel layout the
loads and the means would be summed over the chips first, and on one chip's
share they are its own rows', as the selection bias's loads are). It enters
through the backward pass alone (:func:`aux_loss_gradient`, an identity on
the scores whose cotangent gains ``c E load_e / S^2`` in every row), the way
the families' training code adds it: no second head, no output, nothing
fetched, the metric stays the cross-entropy; with c = 0 (the default) the
node traces the program it always did.

**Group-limited choice** (``n_group`` > 1; DeepSeek-V3, arXiv:2412.19437,
section 2.1.2; the Ling model's 512 experts in 8 groups): the experts are
``n_group`` runs of consecutive ids; a group's score is the sum of its TWO
largest ``scores + select_bias``, the ``topk_group`` best groups are kept
and the ``top_k`` experts are the largest inside them, so a token's experts
lie on at most ``topk_group`` groups' chips. The mask is float32 beside
the scores and touches the choice alone: the combine weights are the
chosen experts' unbiased scores as ever. ``n_group = topk_group = 1`` (the
default) is no limit and the program the other models trace. What the op
does NOT cover: an ``ep`` mesh axis with its exchange (ROADMAP Reach A2).

**Two expert bodies**, by ``gated``: ``W_down relu(W_up x)^2`` (the
default; :func:`grouped_experts`) and the gated ``W_down (silu(W_gate x) *
W_up x)`` with a third stacked weight (:func:`grouped_experts_gated`), each
with both passes written out. Routing, layout, balancing and the counters
are one; which body a traced node took is counted
(``lower.experts_body.relu2`` / ``lower.experts_body.swiglu``), beside how
its products lower (``lower.experts_kernel.*``), the layout's form
(``lower.experts_plan.column_sort``, the one there is) and the scores
(``lower.experts_score.sigmoid`` / ``.softmax``).

**The selection bias is a state, not a weight.** ``select_bias`` (float32,
``num_experts``) is added to the scores for the choice only and no gradient
reaches it; the family balances its experts by moving it after every
training step against each expert's load: ``b_e += bias_update_rate *
sign(mean load - load_e)``, the loads counted over the step's rows and over
ALL experts, held or not (the router is whole on every chip; in an
expert-parallel layout the counts would be summed over the chips first, and
on one chip's share they are its own rows'). So it is an auxiliary state
like BatchNorm's moving statistics: it moves only where ``is_train``, by the
step that read it, and with ``bias_update_rate`` 0 (the default) it never
moves.

The auxiliary state ``expert_rows`` (int32, ``num_experts + 1``) adds up,
on the device, the rows routed to each expert (all of them, held or not)
and in its last slot the rows that landed here but were not computed (0
by construction; counted so that a change that drops shows). Nothing is
fetched per step: :meth:`RoutedExperts.aux_counters` turns two readings
into telemetry when the module asks at a fence
(``Module.publish_aux_counters``).
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from .pallas_kernels import grouped_experts_applicable
from .registry import Operator, Param, REQUIRED, register_op

BLOCK_ROWS = 512


def block_rows(rows, top_k=0, num_experts=1):
    """Rows a block of the layout holds, for ``rows`` tokens: an eighth of
    them, up to ``BLOCK_ROWS`` or, where that is more, an evenly loaded
    expert's share (``rows x top_k / num_experts``) and a quarter, in whole
    128s. A balanced layer then runs ONE block an expert: at a cap of just
    the even share (512 of 512, latent-attention cell, PR 32) every expert
    that draws a row over the mean pads a second block."""
    even = -(-5 * rows * top_k // (4 * num_experts * 128)) * 128
    return min(max(BLOCK_ROWS, even), max(8, rows // 8))


def layout_length(rows, top_k, num_held, block):
    """Slots of the layout: every pair that can land here, and a last
    block an expert that padding may fill."""
    return -(-(rows * min(top_k, num_held) + num_held * (block - 1))
             // block) * block


def route(x, router, select_bias, top_k, scale, keep=lambda v: v,
          norm_eps=1e-20, n_group=1, topk_group=1, score_func="sigmoid",
          aux_loss_coef=0.0):
    """``x [S, h]`` -> (expert ids ``[S, k]`` int32, combine weights
    ``[S, k]`` float32): scores ``sigmoid(x W_r)`` or, ``score_func``
    ``"softmax"``, ``softmax(x W_r)`` over all the experts; the ``top_k``
    largest of ``scores + select_bias``, with ``n_group`` > 1 inside the
    ``topk_group`` groups whose two largest sum highest; weights the chosen
    scores over their sum plus ``norm_eps``, times ``scale``. ``keep``
    marks the ids where they are made: the weights' gradient reads them,
    and must read the marked ones for a recomputation to skip the top-k.
    ``aux_loss_coef`` > 0 adds the load-balancing loss's gradient to the
    scores' (:func:`aux_loss_gradient`)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if score_func == "softmax" \
        else jax.nn.sigmoid(logits)
    choice = scores + select_bias.astype(f32)
    if n_group > 1:
        groups = choice.reshape(choice.shape[0], n_group, -1)
        _, best = jax.lax.top_k(jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1),
                                topk_group)
        kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=f32), axis=1)
        choice = jnp.where(kept[:, :, None] > 0, groups,
                           -jnp.inf).reshape(choice.shape)
    _, eid = jax.lax.top_k(choice, top_k)
    eid = keep(eid.astype(jnp.int32))
    if aux_loss_coef:
        scores = aux_loss_gradient(
            scores, expert_load(eid, scores.shape[1]), aux_loss_coef)
    # the chosen scores by a compare against every expert, not a gather a
    # pair (nor, backward, a scatter-add): one value and zeros, the sum exact
    expert = jnp.arange(scores.shape[1], dtype=jnp.int32)
    chosen = jnp.sum(jnp.where(eid[:, :, None] == expert, scores[:, None, :],
                               0.0), axis=2)
    wts = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + norm_eps) \
        * scale
    return eid, wts


def aux_loss(scores, load):
    """The router's load-balancing loss of one layer (Fedus et al., Switch
    Transformers, arXiv:2101.03961, equation 4, as the published modelling
    code of the softmax-routed families writes it): ``E sum_e f_e P_e``,
    ``f_e`` the rows that chose expert ``e`` over the rows (``load [E] /
    S``: they sum to ``top_k``), ``P_e`` the mean of ``scores [S, E]``
    over the rows; over ALL experts, held or not. 1 x ``top_k`` where both
    are even."""
    import jax.numpy as jnp

    s, e = scores.shape
    return e * jnp.sum(load.astype(jnp.float32) / s
                       * jnp.mean(scores, axis=0))


def aux_loss_gradient(scores, load, coef):
    """``scores``, unchanged, whose gradient gains ``coef`` times
    :func:`aux_loss`'s: the loss ``L = CE + coef sum_layers L_aux`` reaches
    the router through the backward pass alone, as the families' training
    code adds it (no second head, nothing fetched, the metric stays the
    cross-entropy). ``f`` carries no gradient, so ``dL_aux / dscores[r, e]
    = E load_e / S^2``: one row for all rows. The step's head gives the
    gradient of the MEAN loss over tokens (``SoftmaxOutput(normalization=
    "valid")``), which is the scale the sum is written in."""
    import jax
    import jax.numpy as jnp

    s, e = scores.shape

    @jax.custom_vjp
    def f(scores, load):
        return scores

    def f_fwd(scores, load):
        return scores, load

    def f_bwd(load, d):
        row = (coef * e / (s * s)) * load.astype(jnp.float32)
        return d + row[None].astype(d.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(scores, load)


def balance_step(bias, load, rate):
    """The family's balancing without an auxiliary loss: each expert's
    selection bias moves by ``rate`` against its load, ``load [E]`` the rows
    each expert drew."""
    import jax.numpy as jnp

    load = load.astype(jnp.float32)
    return bias.astype(jnp.float32) \
        + rate * jnp.sign(jnp.mean(load) - load)


def expert_load(eid, num_experts):
    """Rows each of ``num_experts`` experts drew, ``[E]`` int32: a compare
    of every pair against every expert and a sum, no scatter-add."""
    import jax.numpy as jnp

    expert = jnp.arange(num_experts, dtype=jnp.int32)
    return jnp.sum((eid.reshape(1, -1) == expert[:, None])
                   .astype(jnp.int32), axis=1)


def plan(eid, wts, first_held, num_held, block):
    """Lay the pairs that land on the held experts out in blocks.

    Returns ``(rows [L], weights [L], slot [S, k], order [held, S],
    block_expert [L / block], nblocks, dropped)``: slot i of the layout
    computes row ``rows[i]`` through the expert of its block, and that row
    adds the result times ``weights[i]`` (``wts`` by slot, no gradient
    through them); padding slots read row 0 with weight 0. ``slot[r, j]``
    is where pair (r, j) sits in the layout, ``L`` for a pair that does not
    land here. ``order[e]`` is the rows sorted for
    held expert ``e``: those it drew first, in row order (its slots in the
    layout's order), then the others; :func:`pairs_from_slots` carries the
    weights' gradient back along it. ``nblocks`` (traced) is how many blocks
    are filled, ``dropped`` the pairs that landed here and got no slot.

    Dense compares over ``[held, k, S]`` and one sort a held expert's
    column: no scatter, gather or search whose length is the pairs or the
    slots (a scalar one costs the chip 7-30 ns an element, PERF.md
    section 5)."""
    import jax
    import jax.numpy as jnp

    s, k = eid.shape
    length = layout_length(s, k, num_held, block)
    # [held, k, S], the rows innermost: pair (r, j) chose held expert e;
    # at most one j a row and expert, so the masked sums hold one value
    held = first_held + jnp.arange(num_held, dtype=jnp.int32)
    match = eid.T[None] == held[:, None, None]
    hit = jnp.any(match, axis=1)
    w = jnp.sum(jnp.where(match, jax.lax.stop_gradient(wts).T[None], 0.0),
                axis=1)
    hits = hit.astype(jnp.int32)
    counts = jnp.sum(hits, axis=1)
    padded = (counts + block - 1) // block * block
    pend = jnp.cumsum(padded)
    pstart = pend - padded
    row = jnp.arange(s, dtype=jnp.int32)
    # an expert's rows to the front of its column, their weights beside
    key, w = jax.lax.sort((jnp.where(hit, row, row + s), w), dimension=1,
                          num_keys=1)
    order = jnp.where(key < s, key, key - s)
    drew = row < counts[:, None]
    # columns into the layout at their experts' starts, in order: what a
    # column holds past its expert's rows reads as padding, and the next
    # expert's column overwrites it (row + 1, so that 0 is an empty slot)
    rows = jnp.zeros((length + s,), jnp.int32)
    weights = jnp.zeros((length + s,), w.dtype)
    for e in range(num_held):
        rows = jax.lax.dynamic_update_slice(
            rows, jnp.where(drew[e], order[e] + 1, 0), (pstart[e],))
        weights = jax.lax.dynamic_update_slice(
            weights, jnp.where(drew[e], w[e], 0.0), (pstart[e],))
    rows, weights = rows[:length], weights[:length]
    # a pair's slot: its row's place among its expert's rows
    at = pstart[:, None] + jnp.cumsum(hits, axis=1) - 1
    slot = jnp.where(jnp.any(match, axis=0),
                     jnp.sum(jnp.where(match, at[:, None], 0), axis=0), length)
    first = jnp.arange(length // block, dtype=jnp.int32) * block
    block_expert = jnp.minimum(
        jnp.sum((pend[None] <= first[:, None]).astype(jnp.int32), axis=1),
        num_held - 1)
    dropped = jnp.sum(counts) - jnp.sum((rows > 0).astype(jnp.int32))
    return (jnp.maximum(rows - 1, 0), weights, slot.T.astype(jnp.int32),
            order, block_expert, pend[-1] // block, dropped)


def pairs_from_slots(dwt, slot, order, block_expert, nblocks):
    """``dwt [L]`` by slot of :func:`plan`'s layout -> ``[S, k]`` by pair,
    0 for a pair that does not land here: each held expert's run of slots
    cut out where it starts, carried from the expert's order back to the
    rows' by one sort, and spread onto the pairs by a compare (what
    ``jnp.take(dwt, slot)`` gives, without a gather the pairs long)."""
    import jax
    import jax.numpy as jnp

    held, s = order.shape
    nbmax = block_expert.shape[0]
    block = dwt.shape[0] // nbmax
    expert = jnp.arange(held, dtype=jnp.int32)
    filled = jnp.arange(nbmax, dtype=jnp.int32) < nblocks
    blocks = jnp.sum(((block_expert[None] == expert[:, None])
                      & filled[None]).astype(jnp.int32), axis=1)
    pend = jnp.cumsum(blocks) * block
    pstart = pend - blocks * block
    run = jnp.concatenate([dwt, jnp.zeros((s,), dwt.dtype)])
    by_place = jnp.stack([jax.lax.dynamic_slice(run, (pstart[e],), (s,))
                          for e in range(held)])
    _, by_row = jax.lax.sort((order, by_place), dimension=1, num_keys=1)
    # a pair's expert by where its slot lies; ``held`` for none
    of = jnp.sum((slot.T[None] >= pend[:, None, None]).astype(jnp.int32),
                 axis=0)
    return jnp.sum(jnp.where(of[None] == expert[:, None, None],
                             by_row[:, None], 0.0), axis=0).T


def _take_block(b, block, rows, weights, block_expert):
    """Block ``b`` of the layout: its expert, its rows, their weights."""
    import jax

    r = jax.lax.dynamic_slice(rows, (b * block,), (block,))
    w = jax.lax.dynamic_slice(weights, (b * block,), (block,))
    return block_expert[b], r, w


def _expert_block(xb, w_up_e, cd):
    import jax.numpy as jnp

    h1 = jnp.dot(xb, w_up_e, preferred_element_type=jnp.float32)
    r = jnp.maximum(h1, 0.0)
    return r, (r * r).astype(cd)


def grouped_experts(x, w_up, w_down, wts, rows, weights, slot, order,
                    block_expert, nblocks):
    """``y[r] = sum_j wts[r, j] * W_down[e] relu(W_up[e] x[r])^2`` over the
    pairs (r, j) that land here: ``x [S, h]``, ``w_up [held, h, f]``,
    ``w_down [held, f, h]``, ``wts [S, k]``, the layout of :func:`plan`
    (``weights`` is ``wts`` by slot). One block a loop step, ``nblocks``
    steps, each adding its rows' weighted results onto the float32 result
    (a padding slot adds zero to row 0). Differentiable in ``x``, the
    expert weights and ``wts`` (whose gradient comes back by ``slot`` and
    ``order``: :func:`pairs_from_slots`)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    cd = x.dtype
    block = rows.shape[0] // block_expert.shape[0]

    def forward(x, w_up, w_down, wts, rows, weights, slot, order,
                block_expert, nblocks):
        def body(b, out):
            e, r, w = _take_block(b, block, rows, weights, block_expert)
            _, a = _expert_block(x[r], w_up[e], cd)
            o = jnp.dot(a, w_down[e], preferred_element_type=f32)
            return out.at[r].add(o * w[:, None])

        return jax.lax.fori_loop(0, nblocks, body,
                                 jnp.zeros(x.shape, f32)).astype(cd)

    # the layout rides as explicit (integer, gradient-free) arguments: a
    # custom_vjp may not close over traced values
    f = jax.custom_vjp(forward)

    def f_fwd(*args):
        return forward(*args), args

    def f_bwd(res, dy):
        (x, w_up, w_down, wts, rows, weights, slot, order, block_expert,
         nblocks) = res

        def body(b, carry):
            dx, dwu, dwd, dwt = carry
            e, r, w = _take_block(b, block, rows, weights, block_expert)
            xb, dyb = x[r], dy[r]
            relu, a = _expert_block(xb, w_up[e], cd)
            # d(result)/d(a), before the slot's weight
            da = jnp.dot(dyb, w_down[e].T, preferred_element_type=f32)
            dwt = jax.lax.dynamic_update_slice(
                dwt, jnp.sum(a.astype(f32) * da, axis=1), (b * block,))
            dyw = (dyb.astype(f32) * w[:, None]).astype(cd)
            dwd = dwd.at[e].add(jnp.dot(a.T, dyw, preferred_element_type=f32))
            dh = (da * w[:, None] * 2.0 * relu).astype(cd)
            dwu = dwu.at[e].add(jnp.dot(xb.T, dh, preferred_element_type=f32))
            dxb = jnp.dot(dh, w_up[e].T, preferred_element_type=f32)
            return dx.at[r].add(dxb), dwu, dwd, dwt

        dx, dwu, dwd, dwt = jax.lax.fori_loop(
            0, nblocks, body,
            (jnp.zeros(x.shape, f32),
             jnp.zeros(w_up.shape, f32), jnp.zeros(w_down.shape, f32),
             jnp.zeros(weights.shape, f32)))
        dwts = pairs_from_slots(dwt, slot, order, block_expert, nblocks)
        return (dx.astype(cd), dwu.astype(w_up.dtype),
                dwd.astype(w_down.dtype), dwts) + (None,) * 6

    f.defvjp(f_fwd, f_bwd)
    return f(x, w_up, w_down, wts, rows, weights, slot, order, block_expert,
             nblocks)


def _swiglu_block(xb, w_gate_e, w_up_e, cd):
    import jax
    import jax.numpy as jnp

    g = jnp.dot(xb, w_gate_e, preferred_element_type=jnp.float32)
    u = jnp.dot(xb, w_up_e, preferred_element_type=jnp.float32)
    return g, u, (jax.nn.silu(g) * u).astype(cd)


def grouped_experts_gated(x, w_gate, w_up, w_down, wts, rows, weights, slot,
                          order, block_expert, nblocks):
    """:func:`grouped_experts` with the gated body: ``y[r] = sum_j wts[r, j]
    * W_down[e] (silu(W_gate[e] x[r]) * W_up[e] x[r])``, ``w_gate`` stacked
    like ``w_up``. Differentiable in ``x``, the three expert weights and
    ``wts``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    cd = x.dtype
    block = rows.shape[0] // block_expert.shape[0]

    def forward(x, w_gate, w_up, w_down, wts, rows, weights, slot, order,
                block_expert, nblocks):
        def body(b, out):
            e, r, w = _take_block(b, block, rows, weights, block_expert)
            _, _, a = _swiglu_block(x[r], w_gate[e], w_up[e], cd)
            o = jnp.dot(a, w_down[e], preferred_element_type=f32)
            return out.at[r].add(o * w[:, None])

        return jax.lax.fori_loop(0, nblocks, body,
                                 jnp.zeros(x.shape, f32)).astype(cd)

    f = jax.custom_vjp(forward)

    def f_fwd(*args):
        return forward(*args), args

    def f_bwd(res, dy):
        (x, w_gate, w_up, w_down, wts, rows, weights, slot, order,
         block_expert, nblocks) = res

        def body(b, carry):
            dx, dwg, dwu, dwd, dwt = carry
            e, r, w = _take_block(b, block, rows, weights, block_expert)
            xb, dyb = x[r], dy[r]
            g, u, a = _swiglu_block(xb, w_gate[e], w_up[e], cd)
            # d(result)/d(a), before the slot's weight
            da = jnp.dot(dyb, w_down[e].T, preferred_element_type=f32)
            dwt = jax.lax.dynamic_update_slice(
                dwt, jnp.sum(a.astype(f32) * da, axis=1), (b * block,))
            dyw = (dyb.astype(f32) * w[:, None]).astype(cd)
            dwd = dwd.at[e].add(jnp.dot(a.T, dyw, preferred_element_type=f32))
            daw = da * w[:, None]
            sig = jax.nn.sigmoid(g)
            # silu(g) = g sig(g); its slope sig (1 + g (1 - sig))
            dg = (daw * u * sig * (1.0 + g * (1.0 - sig))).astype(cd)
            du = (daw * g * sig).astype(cd)
            dwg = dwg.at[e].add(jnp.dot(xb.T, dg, preferred_element_type=f32))
            dwu = dwu.at[e].add(jnp.dot(xb.T, du, preferred_element_type=f32))
            dxb = jnp.dot(dg, w_gate[e].T, preferred_element_type=f32) \
                + jnp.dot(du, w_up[e].T, preferred_element_type=f32)
            return dx.at[r].add(dxb), dwg, dwu, dwd, dwt

        dx, dwg, dwu, dwd, dwt = jax.lax.fori_loop(
            0, nblocks, body,
            (jnp.zeros(x.shape, f32), jnp.zeros(w_gate.shape, f32),
             jnp.zeros(w_up.shape, f32), jnp.zeros(w_down.shape, f32),
             jnp.zeros(weights.shape, f32)))
        dwts = pairs_from_slots(dwt, slot, order, block_expert, nblocks)
        return (dx.astype(cd), dwg.astype(w_gate.dtype),
                dwu.astype(w_up.dtype), dwd.astype(w_down.dtype), dwts) \
            + (None,) * 6

    f.defvjp(f_fwd, f_bwd)
    return f(x, w_gate, w_up, w_down, wts, rows, weights, slot, order,
             block_expert, nblocks)


def grouped_experts_kernel(x, w_experts, wts, rows, weights, slot, order,
                           block_expert, nblocks, gated):
    """:func:`grouped_experts` (``w_experts = (w_up, w_down)``) or
    :func:`grouped_experts_gated` (``(w_gate, w_up, w_down)``) with each
    pass three Pallas kernels over the filled blocks (rows gathered,
    products, rows added back: ``pallas_kernels.grouped_experts_forward`` /
    ``_backward``) where the loops run a block a step: the same products at
    the same precisions, the weight gradients' float32 sums in VMEM an
    expert at a time."""
    import jax
    import jax.numpy as jnp

    from . import pallas_kernels as pk

    def forward(x, w_experts, wts, rows, weights, slot, order, block_expert,
                nblocks):
        return pk.grouped_experts_forward(
            x, w_experts, rows, weights, block_expert,
            jnp.reshape(nblocks, (1,)), gated=gated)

    f = jax.custom_vjp(forward)

    def f_fwd(*args):
        return forward(*args), args

    def f_bwd(res, dy):
        (x, w_experts, wts, rows, weights, slot, order, block_expert,
         nblocks) = res
        dx, dws, dwt = pk.grouped_experts_backward(
            x, w_experts, rows, weights, block_expert,
            jnp.reshape(nblocks, (1,)), dy, gated=gated)
        dwts = pairs_from_slots(dwt, slot, order, block_expert, nblocks)
        return (dx, dws, dwts) + (None,) * 6

    f.defvjp(f_fwd, f_bwd)
    return f(x, tuple(w_experts), wts, rows, weights, slot, order,
             block_expert, nblocks)


@register_op("RoutedExperts")
class RoutedExperts(Operator):
    """The held experts' part of a routed-expert layer (see the module's
    docstring). Experts are ``W_down relu(W_up x)^2``, or with ``gated``
    ``W_down (silu(W_gate x) * W_up x)``; no bias."""

    name_hint = "routedexperts"
    PARAMS = {
        "num_experts": Param(int, REQUIRED, "experts the router scores"),
        "num_held": Param(int, REQUIRED, "experts whose weights are here"),
        "first_held": Param(int, 0, "index of the first held expert"),
        "top_k": Param(int, REQUIRED),
        "scale": Param(float, 1.0, "routed scaling factor"),
        "num_hidden": Param(int, REQUIRED, "an expert's inner width"),
        "bias_update_rate": Param(float, 0.0, "what a training step moves "
                                  "each expert's selection bias by, "
                                  "against its load"),
        "gated": Param(bool, False, "experts W_down (silu(W_gate x) * W_up "
                       "x), a third stacked weight gate_weight"),
        "norm_eps": Param(float, 1e-20, "added to the chosen scores' sum "
                          "before the combine weights are divided by it"),
        "n_group": Param(int, 1, "groups of consecutive experts the choice "
                         "is limited by; 1: no limit"),
        "topk_group": Param(int, 1, "groups a row's experts may lie in"),
        "score_func": Param(str, "sigmoid", "the router's scores: sigmoid "
                            "an expert, or softmax over all of them"),
        "aux_loss_coef": Param(float, 0.0, "weight of the load-balancing "
                               "loss whose gradient the backward pass adds "
                               "to the router's"),
    }
    # arguments that reach the op in their own dtype under mixed precision
    full_precision_args = ("router_weight",)

    def list_arguments(self):
        gate = ["gate_weight"] if self.gated else []
        return ["data", "router_weight"] + gate + ["up_weight", "down_weight"]

    def list_auxiliary_states(self):
        return ["expert_rows", "select_bias"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("RoutedExperts: data shape unknown")
        e, held = self.num_experts, self.num_held
        if not (0 <= self.first_held and self.first_held + held <= e
                and 0 < held and self.top_k <= e):
            raise MXNetError("RoutedExperts: experts %d..%d of %d, top_k %d"
                             % (self.first_held, self.first_held + held, e,
                                self.top_k))
        if self.score_func not in ("sigmoid", "softmax"):
            raise MXNetError("RoutedExperts: score_func %r is neither "
                             "'sigmoid' nor 'softmax'" % self.score_func)
        if self.aux_loss_coef < 0:
            raise MXNetError("RoutedExperts: aux_loss_coef %g is below 0"
                             % self.aux_loss_coef)
        g, kept = self.n_group, self.topk_group
        if g < 1 or e % g or not 1 <= kept <= g or (
                g > 1 and (e // g < 2 or kept * (e // g) < self.top_k)):
            raise MXNetError("RoutedExperts: top_k %d inside %d of %d groups "
                             "of %d experts" % (self.top_k, kept, g, e))
        h, f = data[1], self.num_hidden
        up = [(held, h, f)] * (2 if self.gated else 1)
        return ([data, (h, e)] + up + [(held, f, h)], [data],
                [(e + 1,), (e,)])

    def infer_type(self, in_types, out_types=None):
        import numpy as np

        ins, outs, _ = super().infer_type(in_types, out_types)
        return ins, outs, [np.dtype(np.int32), np.dtype(np.float32)]

    def remat_results(self, in_shapes, in_types):
        """Kept always under recomputation: the result (one activation),
        so that the recomputed forward skips the loop, and what ``route``
        and ``plan`` return (integers and scalars a row), so that top-k,
        the sort and the layout run once a step. Both are needed again
        only as the backward pass's residuals."""
        import numpy as np

        rows, h = in_shapes[0]
        block = block_rows(rows, self.top_k, self.num_experts)
        length = layout_length(rows, self.top_k, self.num_held, block)
        # ids, weights and slots a pair; a row an expert and row; row and
        # weight a slot; an expert a block; the count of blocks
        routing = 4 * (3 * rows * self.top_k + self.num_held * rows
                       + 2 * length + length // block + 1)
        return [("output", rows * h * np.dtype(in_types[0]).itemsize, None),
                ("routing", routing, None)]

    def apply(self, ctx, inputs, aux):
        import jax.numpy as jnp

        from .. import telemetry as _tel

        x, router, *w_experts = inputs
        counted, bias = aux
        e = self.num_experts
        keep = functools.partial(ctx.keep, result="routing")
        eid, wts = route(x, router, bias, self.top_k, self.scale, keep,
                         self.norm_eps, self.n_group, self.topk_group,
                         self.score_func, self.aux_loss_coef)
        block = block_rows(x.shape[0], self.top_k, self.num_experts)
        *layout, dropped = plan(eid, wts, self.first_held, self.num_held,
                                block)
        wts, rows, weights, slot, order, block_expert, nblocks = keep(
            (wts, *layout))
        _tel.inc("lower.experts_plan.column_sort")
        _tel.inc("lower.experts_score.%s" % self.score_func)
        _tel.inc("lower.experts_body.%s"
                 % ("swiglu" if self.gated else "relu2"))
        layout = (wts, rows, weights, slot, order, block_expert, nblocks)
        # one Pallas kernel a pass where the shapes are whole tiles that
        # fit VMEM, else a loop of XLA products, a block a step
        if grouped_experts_applicable(x.shape[1], self.num_hidden, block,
                                      x.dtype, self.gated, x.shape[0]):
            _tel.inc("lower.experts_kernel.pallas_grouped")
            y = grouped_experts_kernel(x, w_experts, *layout, self.gated)
        else:
            _tel.inc("lower.experts_kernel.xla_loop")
            body = grouped_experts_gated if self.gated else grouped_experts
            y = body(x, *w_experts, *layout)
        y = ctx.keep(y, "output")
        load = expert_load(eid, e)
        if ctx.is_train and self.bias_update_rate:
            bias = balance_step(bias, load, self.bias_update_rate)
        seen = jnp.concatenate([load, dropped[None].astype(jnp.int32)])
        return [y], [counted.astype(jnp.int32) + seen, bias]

    def aux_counters(self, before, after):
        """Telemetry from two host readings of ``expert_rows``:
        ``{counter: increment}``, ``{gauge: value}``."""
        import numpy as np

        d = (np.asarray(after, np.int64) - np.asarray(before, np.int64)) \
            % (1 << 32)                      # int32 on the device wraps
        lo, e = self.first_held, self.num_experts
        held = d[lo:lo + self.num_held]
        counters = {"moe.rows_total": int(d[:e].sum()),
                    "moe.rows_here": int(held.sum()),
                    "moe.dropped_rows": int(d[e])}
        gauges = {}
        if held.sum():
            gauges["moe.expert_load_max_over_mean"] = \
                float(held.max() / held.mean())
        return counters, gauges
