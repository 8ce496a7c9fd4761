"""``RoutedExperts``' grouped products as Pallas kernels (a pass gathers
the blocks' rows, runs the products, adds the rows back:
``pallas_kernels.grouped_experts_forward`` / ``_backward`` under
``moe.grouped_experts_kernel``) against the XLA loop, both bodies, in the
interpreter at whole-tile toy shapes: the layouts that matter (an expert of
several blocks, an expert with no row, a row two held experts share, a block
that is mostly padding, the static bound of blocks, held experts that do not
start at 0), a tile of ``f`` that hangs over the edge, and what the float32
accumulators are for. Seeded; no time comes from here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_kernels as pk

H, F, BLOCK = 128, 256, 16


def pairs_of(name, rows):
    """``(expert ids [rows, k], experts in all, first held, held)`` of a
    hand-made routing."""
    r = np.arange(rows)
    if name == "three_blocks_and_an_empty_expert":
        # expert 1 draws 40 rows (three blocks of 16), expert 2 none; the
        # second choice goes to an expert that is not held (5)
        first = np.where(r < 40, 1, np.where(r < 52, 0, 3))
        return np.stack([first, np.full(rows, 5)], 1), 8, 0, 4
    if name == "every_row_shared_to_the_bound":
        # both held experts take every row: each row sits in two blocks,
        # and with rows = 1 mod block each expert's last block holds ONE
        # row and every block of the static bound is filled
        return np.stack([np.zeros(rows, int), np.ones(rows, int)], 1), 2, 0, 2
    if name == "held_from_the_third":
        # experts 2..5 are held; rows go to (r mod 8, r mod 8 + 1): some
        # land on two adjacent held experts, some on one, some on none
        return np.stack([r % 8, (r % 8 + 1) % 8], 1), 8, 2, 4
    if name == "nothing_lands_here":
        return np.stack([np.full(rows, 6), np.full(rows, 7)], 1), 8, 0, 4
    if name == "one_expert_draws_every_row":
        # no capacity would hold them: expert 2 (the second held) takes
        # every row, the other choices spread over all eight
        second = np.where(r % 8 == 2, 7, r % 8)
        return np.stack([second, np.full(rows, 2)], 1), 8, 1, 3
    if name == "ids_of_no_expert":
        # what a capacity laid under the op hands down (10 ** 6): such a
        # pair lands nowhere, beside pairs that do and a row with none left
        ids = np.stack([r % 4, 3 - r % 4 + 4 * (r % 2)], 1)
        ids[r % 3 == 0, 0] = 10 ** 6
        ids[r % 5 == 0, 1] = 10 ** 6
        return ids, 8, 0, 4
    raise KeyError(name)


LAYOUTS = {"three_blocks_and_an_empty_expert": 64,
           "every_row_shared_to_the_bound": 33,
           "held_from_the_third": 48,
           "nothing_lands_here": 24,
           "one_expert_draws_every_row": 40,
           "ids_of_no_expert": 45}


def problem(name, gated, dtype, seed=0, h=H, f=F, block=BLOCK):
    rows = LAYOUTS[name]
    eid, experts, first, held = pairs_of(name, rows)
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(key[0], (rows, h), jnp.float32).astype(dtype)
    wts = jax.random.uniform(key[1], eid.shape, jnp.float32, 0.2, 1.0)
    ws = [(jax.random.normal(key[2 + i], shape, jnp.float32)
           / np.sqrt(shape[1])).astype(dtype)
          for i, shape in enumerate([(held, h, f)] * (2 if gated else 1)
                                    + [(held, f, h)])]
    *layout, dropped = moe.plan(jnp.asarray(eid, jnp.int32), wts, first,
                                held, block)
    assert int(dropped) == 0
    head = jnp.cos(jnp.arange(rows * h, dtype=jnp.float32)
                   .reshape(rows, h) * 0.37)
    return x, tuple(ws), wts, layout, head, (eid, first, held)


def both_bodies(x, ws, wts, layout, head, gated):
    """``{body: (y, (dx, dws, dwts))}`` of the loop and the kernel."""
    rows, weights, slot, order, block_expert, nblocks = layout

    def run(body):
        def loss(x, ws, wts):
            lay = (wts, rows, jax.lax.stop_gradient(weights), slot, order,
                   block_expert, nblocks)
            if body == "loop":
                fn = moe.grouped_experts_gated if gated \
                    else moe.grouped_experts
                y = fn(x, *ws, *lay)
            else:
                y = moe.grouped_experts_kernel(x, ws, *lay, gated)
            return jnp.sum(y.astype(jnp.float32) * head), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(x, ws, wts)
        return y, grads

    return {body: run(body) for body in ("loop", "kernel")}


def gap(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_kernel_matches_the_loop(name, gated, dtype):
    """Forward and every gradient (``x``, each stacked weight, ``wts``):
    float32 to the order of the sums, bfloat16 to the rounding of one
    output (each side rounds a float32 sum it formed in its own order)."""
    x, ws, wts, layout, head, (eid, first, held) = problem(
        name, gated, jnp.dtype(dtype))
    nblocks, bound = int(layout[-1]), layout[-2].shape[0]
    if name == "every_row_shared_to_the_bound":
        assert nblocks == bound == 6
        # an expert's last block is one row and fifteen padding slots
        assert int((np.asarray(layout[1]).reshape(bound, BLOCK)[2] > 0)
                   .sum()) == 1
    elif name == "nothing_lands_here":
        assert nblocks == 0
    else:
        assert 0 < nblocks < bound
    if name == "three_blocks_and_an_empty_expert":
        assert list(np.asarray(layout[-2])[:nblocks]) == [0, 1, 1, 1, 3]
    got = both_bodies(x, ws, wts, layout, head, gated)
    (y0, (dx0, dws0, dwt0)), (y1, (dx1, dws1, dwt1)) = \
        got["loop"], got["kernel"]
    tol = 2e-6 if dtype == "float32" else 2.0 ** -7
    assert y1.dtype == y0.dtype and dx1.dtype == dx0.dtype
    assert gap(y1, y0) <= tol and gap(dx1, dx0) <= tol
    assert gap(dwt1, dwt0) <= (tol if dtype == "float32" else 1e-5)
    assert dwt1.dtype == jnp.float32
    for a, b in zip(dws1, dws0):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert gap(a, b) <= tol
        # an expert that draws no row: zeros, exactly
        for e in range(held):
            if not (eid == first + e).any():
                assert not np.asarray(a[e], np.float32).any()
                assert not np.asarray(b[e], np.float32).any()
    if name == "three_blocks_and_an_empty_expert":
        assert not np.asarray(dws1[0][2], np.float32).any()
        assert np.asarray(dws1[0][1], np.float32).any()


def stated_layout(eid, wts, first, held, block):
    """:func:`moe.plan`'s layout stated a pair at a time: ``(rows, weights,
    slot, the filled blocks' experts)``."""
    s, k = eid.shape
    length = moe.layout_length(s, k, held, block)
    rows, weights = np.zeros(length, np.int32), np.zeros(length, np.float32)
    slot, experts, at = np.full((s, k), length, np.int32), [], 0
    for e in range(held):
        r, j = np.nonzero(eid == first + e)         # in row order
        rows[at:at + len(r)], weights[at:at + len(r)] = r, wts[r, j]
        slot[r, j] = at + np.arange(len(r))
        experts += [e] * -(-len(r) // block)
        at = len(experts) * block
    return rows, weights, slot, experts


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_layout_is_the_stated_one(name):
    """Every landing pair one slot, each block one expert's, padding of
    weight 0, no pair dropped whatever the imbalance, ``nblocks`` the
    blocks filled; ``order`` an expert's rows first; and a value a slot
    comes back to the pairs as ``jnp.take`` brings it, whatever lies in the
    slots no pair has (the kernels define nothing past the filled blocks)."""
    rows_in = LAYOUTS[name]
    eid, _, first, held = pairs_of(name, rows_in)
    wts = np.asarray(jax.random.uniform(jax.random.PRNGKey(5), eid.shape,
                                        jnp.float32, 0.2, 1.0))
    rows, weights, slot, order, block_expert, nblocks, dropped = (
        np.asarray(a) for a in jax.jit(
            moe.plan, static_argnums=(2, 3, 4))(
                jnp.asarray(eid, jnp.int32), wts, first, held, BLOCK))
    want_rows, want_weights, want_slot, experts = stated_layout(
        eid, wts, first, held, BLOCK)
    length = len(want_rows)
    assert int(dropped) == 0 and int(nblocks) == len(experts)
    assert rows.dtype == slot.dtype == block_expert.dtype == np.int32
    assert weights.dtype == np.float32
    assert (rows == want_rows).all() and (weights == want_weights).all()
    assert (slot == want_slot).all()
    assert len(block_expert) == length // BLOCK
    assert list(block_expert[:len(experts)]) == experts
    assert ((0 <= block_expert) & (block_expert < held)).all()
    here = (eid >= first) & (eid < first + held)
    assert int((weights != 0).sum()) == int(here.sum())
    for e in range(held):
        drew = np.nonzero((eid == first + e).any(axis=1))[0]
        assert sorted(order[e]) == list(range(rows_in))
        assert list(order[e][:len(drew)]) == list(drew)
    dwt = np.full(length, np.nan, np.float32)
    dwt[slot[here]] = np.arange(1, here.sum() + 1)
    back = np.asarray(jax.jit(moe.pairs_from_slots)(
        dwt, slot, order, block_expert, nblocks))
    assert back.dtype == np.float32
    assert (back == np.asarray(jnp.take(jnp.asarray(dwt), slot, mode="fill",
                                        fill_value=0))).all()
    if name == "one_expert_draws_every_row":
        assert (eid == 2).any(axis=1).all() and experts.count(1) == 3
    if name == "ids_of_no_expert":
        assert (eid == 10 ** 6).any() and (~here).all(axis=1).any()
        assert 0 < here.sum() < (eid != 10 ** 6).sum()


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_results_are_those_of_a_gather_by_slot(monkeypatch, name, gated):
    """``y``, ``dx``, the weights' gradients and ``dwts`` of both bodies
    with the weights' gradient brought back by ``jnp.take(dwt, slot)``, as
    it was before the sorts: the same values, to the bit."""
    x, ws, wts, layout, head, _ = problem(name, gated, jnp.float32)
    got = both_bodies(x, ws, wts, layout, head, gated)
    monkeypatch.setattr(
        moe, "pairs_from_slots", lambda dwt, slot, *_: jnp.take(
            dwt, slot, mode="fill", fill_value=0))
    want = both_bodies(x, ws, wts, layout, head, gated)
    for body in ("loop", "kernel"):
        a, b = jax.tree_util.tree_leaves(got[body]), \
            jax.tree_util.tree_leaves(want[body])
        assert len(a) == len(b) == 3 + len(ws)
        for u, v in zip(a, b):
            assert (np.asarray(u) == np.asarray(v)).all()


def test_the_routing_lowers_without_a_scalar_scatter_or_gather():
    """At the Ling cell's shapes (8,192 rows, 8 of 512, 8 held): the
    layout, the load count and the weights' gradient's way back to the
    pairs hold no ``scatter``, no ``gather`` and no ``while`` (a scalar one
    costs the chip 7-30 ns an element; the sorts ride the vector unit).
    Lowered, not compiled or run."""
    s, k, e, held = 8192, 8, 512, 8
    block = moe.block_rows(s, k, e)
    length = moe.layout_length(s, k, held, block)
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    texts = {
        "plan": jax.jit(lambda eid, wts: moe.plan(
            eid, wts, 0, held, block)).lower(sd((s, k), i32),
                                             sd((s, k), f32)),
        "load": jax.jit(lambda eid: moe.expert_load(eid, e)).lower(
            sd((s, k), i32)),
        "back": jax.jit(moe.pairs_from_slots).lower(
            sd((length,), f32), sd((s, k), i32), sd((held, s), i32),
            sd((length // block,), i32), sd((), i32))}
    for what, lowered in texts.items():
        text = lowered.as_text()
        assert "stablehlo.sort" in text or what == "load"
        for op in ("scatter", "gather", "while"):
            assert "stablehlo.%s" % op not in text \
                and "\"%s\"" % op not in text, (what, op)


def test_a_traced_node_counts_its_layout_once():
    from mxnet_tpu import symbol as sym
    from mxnet_tpu import telemetry
    import mxnet_tpu as mx

    net = sym.RoutedExperts(num_experts=4, num_held=2, top_k=2,
                            num_hidden=F, data=sym.Variable("data"), name="x")
    telemetry.reset()
    telemetry.enable()
    try:
        net.simple_bind(mx.cpu(), data=(64, H)).forward(is_train=False)
        assert telemetry.peek("lower.experts_plan.column_sort") == 1
        assert telemetry.peek("lower.experts_kernel.pallas_grouped") == 1
    finally:
        telemetry.disable()


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_a_tile_of_f_that_hangs_over_the_edge(monkeypatch, gated):
    """``f`` = 192 in tiles of 128: the last tile's upper half lies past
    the weights (the interpreter fills it with NaN), and is zeroed before
    any product reads it; the tiles' partial sums add up to the loop's."""
    monkeypatch.setattr(pk, "_experts_tiles", lambda *a: (128, 128, 0))
    pk._experts_jitted.cache_clear()
    try:
        x, ws, wts, layout, head, _ = problem(
            "three_blocks_and_an_empty_expert", gated, jnp.float32, f=192)
        got = both_bodies(x, ws, wts, layout, head, gated)
    finally:
        pk._experts_jitted.cache_clear()
    (y0, (dx0, dws0, dwt0)), (y1, (dx1, dws1, dwt1)) = \
        got["loop"], got["kernel"]
    assert gap(y1, y0) <= 2e-6 and gap(dx1, dx0) <= 2e-6
    assert gap(dwt1, dwt0) <= 2e-6
    for a, b in zip(dws1, dws0):
        assert np.isfinite(np.asarray(a)).all() and gap(a, b) <= 2e-6


def test_weight_gradients_are_summed_in_float32_across_an_experts_blocks():
    """One expert of six blocks in bfloat16, every addend positive: the
    kernel's weight gradients are within ONE bfloat16 rounding of the sum
    formed in float64 from the same rounded factors. The same sum carried
    in bfloat16 from block to block is not (this test's own stand-in for a
    bfloat16 accumulator), so the bound tells them apart."""
    rows, block, h, f = 96, 16, 128, 128
    bf16, f64 = jnp.bfloat16, np.float64
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.uniform(key[0], (rows, h), jnp.float32, 0.5, 1.5) \
        .astype(bf16)
    w_up = (jax.random.uniform(key[1], (1, h, f), jnp.float32, 0.5, 1.5)
            / h).astype(bf16)
    w_down = (jax.random.uniform(key[2], (1, f, h), jnp.float32, 0.5, 1.5)
              / f).astype(bf16)
    dy = jax.random.uniform(key[3], (rows, h), jnp.float32, 0.5, 1.5) \
        .astype(bf16)
    eid = jnp.zeros((rows, 1), jnp.int32)
    wts = jnp.ones((rows, 1), jnp.float32)
    rows_, weights, slot, order_, block_expert, nblocks, _ = moe.plan(
        eid, wts, 0, 1, block)
    assert int(nblocks) == 6
    _, vjp = jax.vjp(lambda up, down: moe.grouped_experts_kernel(
        x, (up, down), wts, rows_, weights, slot, order_, block_expert,
        nblocks, False), w_up, w_down)
    dwu, dwd = (np.asarray(g[0], f64) for g in vjp(dy))

    def rounded(v):
        return np.asarray(jnp.asarray(v, jnp.float32).astype(bf16), f64)

    # the block's factors as the kernel rounds them, the sums in float64
    xs, dys = np.asarray(x, f64), np.asarray(dy, f64)
    up, down = np.asarray(w_up[0], f64), np.asarray(w_down[0], f64)
    relu = np.maximum(xs @ up, 0.0)
    a = rounded(relu * relu)
    dh = rounded((dys @ down.T) * 2.0 * relu)
    order = np.asarray(rows_).reshape(-1, block)[:6]
    for got, left, right in ((dwu, xs, dh), (dwd, a, dys)):
        parts = [left[r].T @ right[r] for r in order]
        want = rounded(sum(parts))
        carried = 0.0
        for part in parts:
            carried = rounded(carried + part)
        one_rounding = 2.0 ** -8 * np.abs(want)
        assert (np.abs(got - want) <= one_rounding).all()
        assert (np.abs(carried - want) > one_rounding).any()


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_every_product_and_every_sum_is_float32(gated):
    """In the traced kernels under bfloat16: every ``dot_general`` takes
    bfloat16 operands and asks for a float32 result; what is summed across
    blocks (the weight gradients' accumulators, the scatter's rows) is a
    float32 VMEM scratch; the results by slot are float32; the gather moves
    32-bit words and nothing else."""
    x, ws, wts, layout, head, _ = problem(
        "three_blocks_and_an_empty_expert", gated, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, ws, wts: jnp.sum(
        moe.grouped_experts_kernel(x, ws, wts, *layout, gated)
        .astype(jnp.float32) * head), argnums=(0, 1, 2)))(x, ws, wts)
    calls = [eqn for sub in _sub_jaxprs(jaxpr.jaxpr) for eqn in sub.eqns
             if eqn.primitive.name == "pallas_call"]
    # a pass is gather(s), products, scatter; the interpreter's and Mosaic's
    # branch each hold both passes
    names = [call.params["name"] for call in calls]
    assert sorted(names) == sorted(
        2 * (["grouped_experts_gather"] * 3 + ["grouped_experts_scatter"] * 2
             + ["grouped_experts_forward", "grouped_experts_backward"]))
    nw = 3 if gated else 2
    for call in calls:
        name = call.params["name"][len("grouped_experts_"):]
        scratch = [a for a in call.params["grid_mapping"].scratch_avals
                   if "sem" not in str(a.dtype)]
        dots = [eqn for sub in _sub_jaxprs(call.params["jaxpr"])
                for eqn in sub.eqns if eqn.primitive.name == "dot_general"]
        assert len(dots) == {"gather": 0, "scatter": 0,
                             "forward": nw, "backward": 3 * nw - 1}[name]
        for eqn in dots:
            assert eqn.params["preferred_element_type"] == jnp.float32
            assert all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)
        outs = [a.dtype for a in call.params["out_avals"]]
        if name == "gather":
            assert outs == [jnp.uint32] and scratch[0].dtype == jnp.uint32
        elif name == "scatter":
            assert [a.dtype for a in scratch] == [jnp.float32]
            assert outs == [jnp.bfloat16]
        elif name == "forward":
            assert outs == [jnp.float32]
        else:
            # the unpacked rows of a block, then an accumulator a weight
            assert [a.dtype for a in scratch] == \
                [jnp.bfloat16] * 2 + [jnp.float32] * nw
            assert [a.shape[1:] for a in scratch[2:]] == \
                [w.shape[1:] for w in ws]
            assert outs == [jnp.float32] * 2 + [jnp.bfloat16] * nw


def test_packed_rows_unpack_exactly():
    """A 16-bit row rides two columns a word, and comes back bit for bit,
    at a width of an odd count of lane tiles too (the high halves of the
    last tile are padding)."""
    for h in (128, 256, 384, 2688):
        x = jax.random.normal(jax.random.PRNGKey(h), (24, h), jnp.float32) \
            .astype(jnp.bfloat16)
        packed = pk._pack_rows(x)
        assert packed.dtype == jnp.uint32
        assert packed.shape == (24, pk._packed_width(h, 2))
        back = pk._unpack_rows(packed, h, jnp.bfloat16)
        assert back.dtype == jnp.bfloat16 and back.shape == x.shape
        assert (np.asarray(back, np.float32)
                == np.asarray(x, np.float32)).all()
    x32 = jnp.ones((8, 128), jnp.float32)
    assert pk._pack_rows(x32) is x32
    assert pk._unpack_rows(x32, 128, jnp.float32) is x32


@pytest.mark.parametrize("h,f,block,dtype,gated,tokens,takes", [
    (2688, 1856, 512, "bfloat16", False, 8192, True),   # the Nemotron cell
    (2048, 1536, 640, "bfloat16", True, 8192, True),    # the GLM cell
    (128, 256, 16, "bfloat16", True, 64, True),
    (128, 256, 8, "float32", False, 64, True),
    (128, 256, 8, "bfloat16", False, 64, False),    # half a bfloat16 tile
    (32, 24, 8, "float32", False, 64, False),       # toy widths
    (128, 24, 8, "float32", True, 64, True),        # f the array's full width
    (128, 20, 8, "float32", True, 64, False),       # ... of whole sublanes
    (96, 128, 8, "float32", False, 64, False),      # a row is whole lanes
    (128, 128, 16, "float16", False, 64, False),
    (2048, 1536, 640, "bfloat16", True, 1 << 16, True),     # rows in tiles
    (128, 128, 16, "bfloat16", False, 1 << 20, False),  # no tile of the rows
    (8192, 8192, 1024, "bfloat16", True, 8192, False)],     # nor of f fits
    ids=lambda v: str(v))
def test_which_shapes_take_the_kernel(h, f, block, dtype, gated, tokens,
                                      takes):
    assert pk.grouped_experts_applicable(h, f, block, jnp.dtype(dtype),
                                         gated, tokens) is takes
    if takes:
        fwd, bwd, need = pk._experts_tiles(h, f, block, gated,
                                           jnp.dtype(dtype).itemsize)
        assert need <= pk._EXPERTS_VMEM and bwd <= fwd <= f
        assert all(t == f or t % 128 == 0 for t in (fwd, bwd))
        size = jnp.dtype(dtype).itemsize
        for width, each in ((pk._packed_width(h, size), 4),
                            (h, 4 + 2 * size)):
            tile = pk._row_tile(width, tokens, each)
            assert width % tile == 0 and tile % 128 == 0
            assert tokens * tile * each <= pk._EXPERTS_VMEM
