"""Pallas TPU kernels: what the train path's operators lower to where
their shapes are whole tiles.

:func:`pallas_call` is the one way in. It decides interpreter or Mosaic
per LOWERING: a computation lowered for the CPU gets the Pallas
interpreter (so every kernel is testable without hardware), one lowered
for a TPU gets the compiled kernel, and nothing in the process can flip
that. ``rtc`` compiles a user's kernel source through it.

Five families of kernels follow, each written from a trace of the
benchmark cell it aimed at and each with a line in the ledger there
(``docs/pallas.md``): the state-space scan's chunk kernels (``SSMScan``),
the gated delta rule's with a decay a head or a key channel
(``GatedDeltaRule``), the routed experts' grouped products
(``RoutedExperts``), the pass that takes the attention kernels their
operands and both passes of that attention, one kernel each
(``CausalAttention``). Each has both passes
written out (the operator joins them under one ``jax.custom_vjp``) and an
``*_applicable`` rule over shapes; the operator chooses the kernel or its
``jax.numpy`` body when the node is traced and counts the choice
(``lower.*``).
"""
from __future__ import annotations

import collections
import functools
import os
import types

import numpy as np

from .. import env as _env

__all__ = ["pallas_available", "pallas_call", "ssd_chunk_applicable",
           "ssd_chunk_forward", "ssd_chunk_backward",
           "delta_chunk_applicable", "delta_channel_applicable",
           "delta_chunk_forward", "delta_chunk_backward",
           "DeltaRows", "delta_rows_applicable", "delta_rows_forward",
           "delta_rows_backward",
           "grouped_experts_applicable", "grouped_experts_forward",
           "grouped_experts_backward", "attention_relayout",
           "attention_applicable", "attention_backward",
           "attention_forward"]

# lanes of a tile
TILE_N = 128


@functools.lru_cache(None)
def pallas_available() -> bool:
    if _env.get("MXNET_TPU_NO_PALLAS"):
        return False
    try:
        import jax
        from jax.experimental import pallas as pl  # noqa: F401

        return True
    except Exception:  # pragma: no cover
        return False


# the kernels this module's ``pallas_call`` has traced, by the name their
# Mosaic module goes under: they are serialized without their locations
_NO_LOCATIONS = set()


@functools.lru_cache(None)
def _strip_locations():
    """Once a process, when the first kernel is traced: the kernels NAMED
    in ``_NO_LOCATIONS`` reach XLA without their operations' locations. A
    Pallas kernel is an opaque payload to XLA (the serialized Mosaic
    module), and JAX writes into it the file paths and names of the ten
    innermost Python frames of every operation, which reach past the jit
    into WHOEVER CALLED IT: the same step then asks the persistent cache
    for another key from ``xprof``'s wrapper than from a plain ``jax.jit``
    call, from another caller, and from another checkout directory (PR 49:
    every traced run of a language cell built its step again, 40-70 s).
    JAX captures the frames at each operation's trace and serializes the
    module itself, with no option and no hook of the kernel's author's, so
    the one seam is its serializer: ``strip-debuginfo`` runs over the
    module first. Nothing else JAX lowers changes, and no option of JAX's
    is set. With ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT`` in the environment
    (a kernel's author asking JAX for frames: a Mosaic error names them)
    nothing is stripped (``docs/pallas.md``)."""
    if "JAX_TRACEBACK_IN_LOCATIONS_LIMIT" in os.environ:
        return
    try:
        from jax._src import tpu_custom_call
        from jax._src.lib.mlir import passmanager

        serialize = tpu_custom_call._lower_mosaic_module_to_asm
    except (ImportError, AttributeError):
        # a JAX that serializes elsewhere: the kernels run as they are,
        # and ``tests/test_build_identity.py`` says what was lost
        return

    @functools.wraps(serialize)
    def without_locations(module, **kw):
        # the module was made for this one serialization: in place
        attrs = module.operation.attributes
        if "sym_name" in attrs and attrs["sym_name"].value in _NO_LOCATIONS:
            with module.context:
                passmanager.PassManager.parse(
                    "builtin.module(strip-debuginfo)").run(module.operation)
        return serialize(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = without_locations


def pallas_call(kernel, *operands, **kw):
    """``pl.pallas_call(kernel, **kw)(*operands)`` with interpret mode
    chosen by the platform the enclosing computation is LOWERED for
    (``jax.lax.platform_dependent``): the interpreter on ``cpu``, the
    Mosaic-compiled kernel everywhere else. A process-wide "what is the
    default backend" answer is wrong as soon as one process holds two
    backends, which every process on a TPU host does. A kernel given a
    ``name`` lowers to the same bytes whoever calls it
    (:func:`_strip_locations`)."""
    import jax
    from jax.experimental import pallas as pl

    if kw.get("name"):
        _NO_LOCATIONS.add(kw["name"])
        _strip_locations()

    def lowered(interpret):
        return lambda *ops: pl.pallas_call(kernel, interpret=interpret,
                                           **kw)(*ops)

    return jax.lax.platform_dependent(*operands, cpu=lowered(True),
                                      default=lowered(False))


# ---------------------------------------------------------------------------
# Chunked selective state-space scan (``ops/seq.py`` ``SSMScan``)
# ---------------------------------------------------------------------------
#
# One grid step is one chunk of one group of one sequence, the chunk axis
# innermost and sequential. It sees the chunk's ``B``, ``C`` ``[L, N]`` and
# the group's ``x`` ``[L, heads * P]``, forms ``C B^T`` once, and for each
# head builds the masked decay matrix in VMEM. What crosses chunks (the
# state, or in the backward kernel its gradient) is a float32 VMEM scratch
# ``[N, heads * P]``: a head's state transposed, the heads side by side
# along the lanes. Heads narrower than 128 lanes are handled in units of
# whole 128-lane tiles: a product with a unit's ``x`` costs the MXU what a
# product with one head's would, and a lane select keeps each head's half.
#
# Precision (both kernels): decays, cumulative sums, the carried state,
# its gradient and every accumulator are float32; matrix products take
# their inputs in the compute dtype (``x.dtype``) and accumulate in
# float32; outputs are rounded to the compute dtype once.

_SSD_MASKED = -1e30     # exp() of it is 0: a decay above the diagonal


def _ssd_units(heads, p):
    """Heads a 128-lane unit holds, its width in lanes, units a group."""
    side = max(1, 128 // p)
    return side, side * p, heads // side


def ssd_chunk_applicable(dims, chunk, dtype) -> bool:
    """Whether the chunk kernels take ``dims = (H, P, G, N)``: whole
    tiles, that is a chunk and a state of whole 128-lane rows, heads that
    fill 128 lanes alone or side by side, ``B`` and ``C`` starting at a
    whole state's width inside ``x|B|C``, and a compute dtype the MXU
    takes."""
    h, p, g, n = dims
    side, width, _ = _ssd_units(h // g, p)
    return (chunk % 128 == 0 and n % 128 == 0 and (h * p) % n == 0
            and width % 128 == 0 and (h // g) % side == 0
            and str(dtype) in ("bfloat16", "float32") and pallas_available())


def _ssd_small(dt, a_head, g, chunk):
    """The per-position scalars a chunk needs, by (sequence, group, chunk)
    with the positions along the lanes ``[.., heads, L]``: the step and
    the cumulative decay inside the chunk. (A kernel turns them, ``[L,
    heads]``, where it wants a position a sublane; kept that way in HBM
    they would be padded sixteenfold.)

    The cumulative sum is a float32 sum made on the MXU: the float32
    addends cut exactly into three bfloat16 pieces, each times a triangle
    of ones (exact products, float32 accumulation); it reads as close to a
    float64 sum as ``jnp.cumsum`` does, and XLA's ``reduce-window`` took
    0.19 ms a pass over these 2 MB. The pieces are cut with
    ``lax.reduce_precision``: a convert to bfloat16 and back is an
    identity to XLA on the TPU, and the sum was then a bfloat16 one."""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    b, t, h = dt.shape
    hg = h // g
    dts = dt.reshape(b, t // chunk, chunk, g, hg).transpose(0, 3, 1, 4, 2)
    upto = jnp.triu(jnp.ones((chunk, chunk), bf16))         # [s, t]: s <= t
    cum, rest = 0.0, dts * a_head.reshape(1, g, 1, hg, 1)
    for _ in range(3):
        piece = jax.lax.reduce_precision(rest, exponent_bits=8,
                                         mantissa_bits=7)
        cum = cum + jnp.einsum("bgchs,st->bgcht", piece.astype(bf16), upto,
                               preferred_element_type=f32)
        rest = rest - piece
    return dts, cum


def _ssd_chunk_tools(hg, p, chunk):
    """What the two kernels' bodies share: the three products, a unit's
    lane masks, the causal mask and a head's decay matrix."""
    import jax
    import jax.numpy as jnp

    side, width, units = _ssd_units(hg, p)
    f32 = jnp.float32

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=f32)

    nn = lambda a, b: dot(a, b, ((1,), (0,)))      # noqa: E731
    nt = lambda a, b: dot(a, b, ((1,), (1,)))      # noqa: E731
    tn = lambda a, b: dot(a, b, ((0,), (0,)))      # noqa: E731

    def lanes():
        return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def spread(cols, unit):
        """``cols [rows, heads]`` -> ``[rows, width]``: each head of the
        unit's value across that head's lanes."""
        h0 = unit * side
        out = cols[:, h0:h0 + 1]
        for k in range(1, side):
            out = jnp.where(lanes() >= k * p, cols[:, h0 + k:h0 + k + 1], out)
        return out

    def head_lanes(k):
        lane = lanes()
        return (lane >= k * p) & (lane < (k + 1) * p)

    def causal(turned=False):
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        return row <= col if turned else row >= col

    def decay(cum, cumr, h, mask, turned=False):
        """Head h's ``exp(cum_t - cum_s)`` where ``mask`` (s <= t), else 0:
        t down and s across, or ``turned``, s down and t across."""
        seg = cum[:, h:h + 1] - cumr[h:h + 1, :]
        return jnp.exp(jnp.where(mask, -seg if turned else seg,
                                 _SSD_MASKED))

    return (side, width, units, nn, nt, tn, spread, head_lanes, causal,
            decay)


def _ssd_specs(dims, chunk, order):
    """Block specs by (sequence, group, chunk); ``order`` maps the grid's
    chunk index to the chunk (the backward kernel runs them reversed).
    ``wide`` is a group's heads in ``x|B|C`` or in an array ``[B, T, H *
    P]``; ``b_in`` / ``c_in`` are the group's ``B`` and ``C`` inside
    ``x|B|C``, ``state`` the group's in an array ``[B, T, G * N]``,
    ``small`` the group's per-position scalars (:func:`_ssd_small`)."""
    from jax.experimental import pallas as pl

    h, p, g, n = dims
    hg = h // g

    def columns(width, first):
        return pl.BlockSpec((None, chunk, width),
                            lambda bi, gi, ci: (bi, order(ci), first + gi))

    small = pl.BlockSpec((None, None, None, hg, chunk),
                         lambda bi, gi, ci: (bi, gi, order(ci), 0, 0))
    skip = pl.BlockSpec((None, 1, hg * p), lambda bi, gi, ci: (gi, 0, 0))
    starts = pl.BlockSpec((None, None, None, n, hg * p),
                          lambda bi, gi, ci: (bi, order(ci), gi, 0, 0))
    return (columns(hg * p, 0), columns(n, h * p // n),
            columns(n, (h * p + g * n) // n), columns(n, 0), small, skip,
            starts)


def _ssd_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _ssd_chunk_forward(xbc, dt, a_head, d_skip, *, dims, chunk, with_states):
    """``y = scan(x, dt, a_head, B, C) + D x`` for sequences of whole
    chunks, one kernel call (see the section's comment): ``xbc [B, T, H*P
    + 2*G*N]`` holds ``x | B | C`` (``dims = (H, P, G, N)``) and is read
    where it lies, through three block specs. Returns ``y [B, T, H*P]`` in
    ``xbc.dtype`` and, ``with_states``, the float32 state at each chunk's
    start as the backward kernel reads it, ``[B, T/chunk, G, N, (H/G) *
    P]`` (else ``None``). Scratch: the carried state, float32 ``[N, (H/G)
    * P]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (b, t, _), (h, p, g, n) = xbc.shape, dims
    hg, nc, cd = h // g, t // chunk, xbc.dtype
    side, width, units, nn, nt, tn, spread, head_lanes, causal, decay = \
        _ssd_chunk_tools(hg, p, chunk)

    def kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, *rest):
        st_ref = rest[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            st_ref[...] = jnp.zeros_like(st_ref)

        if with_states:
            rest[0][...] = st_ref[...]
        bm, cm = b_ref[...], c_ref[...]
        cb = nt(cm, bm)                                    # [t, s]
        from_start = nn(cm, st_ref[...].astype(cd))        # [L, hg * p]
        cumr = cum_ref[...]
        dts, cum = dt_ref[...].T, cumr.T
        last = cum[chunk - 1:chunk, :]
        ecum, elast = jnp.exp(cum), jnp.exp(last)
        to_end = jnp.exp(last - cum) * dts
        lower = causal()
        # unrolled: as a ``lax.fori_loop`` over the units both kernels ran
        # at half the speed
        for unit in range(units):
            sl = slice(unit * width, (unit + 1) * width)
            xf = x_ref[:, sl].astype(f32)
            xdt = (xf * spread(dts, unit)).astype(cd)
            y = None
            for k in range(side):
                m = (cb * decay(cum, cumr, unit * side + k, lower)).astype(cd)
                part = nn(m, xdt)
                y = part if y is None else jnp.where(head_lanes(k), part, y)
            y = y + spread(ecum, unit) * from_start[:, sl] \
                + d_ref[:, sl] * xf
            y_ref[:, sl] = y.astype(y_ref.dtype)
            xw = (xf * spread(to_end, unit)).astype(cd)
            st_ref[:, sl] = spread(elast, unit) * st_ref[:, sl] + tn(bm, xw)

    dts, cum = _ssd_small(dt, a_head, g, chunk)
    wide, b_in, c_in, _, row, skip, starts = _ssd_specs(
        dims, chunk, lambda ci: ci)
    out_shape = [jax.ShapeDtypeStruct((b, t, h * p), cd)]
    out_specs = [wide]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, g, n, hg * p), f32))
        out_specs.append(starts)
    out = pallas_call(
        kernel, xbc, xbc, xbc, dts, cum,
        jnp.repeat(d_skip, p).reshape(g, 1, hg * p),
        grid=(b, g, nc),
        in_specs=[wide, b_in, c_in, row, row, skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, hg * p), f32)],
        compiler_params=_ssd_params(), name="ssd_chunk_forward")
    return out[0], out[1] if with_states else None


def _ssd_chunk_backward(xbc, dt, a_head, d_skip, starts, dy, *, dims, chunk):
    """The mirror of :func:`_ssd_chunk_forward` over the chunks reversed:
    each chunk's matrices are formed again in VMEM from the inputs and the
    chunk-start state, and the state's gradient rides a float32 scratch
    ``[N, (H/G) * P]``. Returns what ``seq.ssd_chunked_grad`` returns
    (see there for the decay's gradient): ``dx [B, T, H*P]``, ``dB``, ``dC
    [B, T, G*N]`` in ``xbc.dtype`` and, in float32 ``[B, T, H]``, the
    gradient of each position's cumulative decay and of its step where
    the step scales ``x``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (b, t, _), (h, p, g, n) = xbc.shape, dims
    hg, nc, cd = h // g, t // chunk, xbc.dtype
    side, width, units, nn, nt, tn, spread, head_lanes, causal, decay = \
        _ssd_chunk_tools(hg, p, chunk)

    def kernel(x_ref, dy_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, st_ref,
               dx_ref, db_ref, dc_ref, dcum_ref, dstep_ref, ds_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)

        bm, cm = b_ref[...], c_ref[...]
        cb = nt(cm, bm)                                    # [t, s]
        st0, ds_end = st_ref[...].astype(cd), ds_ref[...].astype(cd)
        from_start = nn(cm, st0)                           # [L, hg * p]
        from_end = nn(bm, ds_end)
        cumr = cum_ref[...]
        dts, cum = dt_ref[...].T, cumr.T
        last = cum[chunk - 1:chunk, :]
        ecum, elast = jnp.exp(cum), jnp.exp(last)
        wexp = jnp.exp(last - cum)
        head_col = jax.lax.broadcasted_iota(jnp.int32, (1, hg), 1)
        head_row = jax.lax.broadcasted_iota(jnp.int32, (hg, 1), 0)
        at_last = jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0) == chunk - 1
        lower, upper = causal(), causal(True)
        cb_t = nt(bm, cm)                                  # [s, t]
        dcb = jnp.zeros((chunk, chunk), f32)
        dc = jnp.zeros((chunk, n), f32)
        db = jnp.zeros((chunk, n), f32)
        at_t = jnp.zeros((chunk, hg), f32)     # d cum, by the row it is in
        at_s = jnp.zeros((hg, chunk), f32)     # ... by the column, negative
        dstep = jnp.zeros((chunk, hg), f32)

        def by_head(v, k):
            return jnp.sum(jnp.where(head_lanes(k), v, 0.0), axis=1,
                           keepdims=True)

        for unit in range(units):
            sl = slice(unit * width, (unit + 1) * width)
            xu, gu = x_ref[:, sl], dy_ref[:, sl]
            xf, gf = xu.astype(f32), gu.astype(f32)
            dte, ee, we = (spread(v, unit) for v in (dts, ecum, wexp))
            xdt = (xf * dte).astype(cd)
            dxs = None
            for k in range(side):
                hd = unit * side + k
                dk = decay(cum, cumr, hd, lower)
                mf = cb * dk
                mine = head_lanes(k)
                dm = nt(gu if side == 1 else
                        jnp.where(mine, gu, jnp.zeros_like(gu)), xdt)
                dcb = dcb + dm * dk
                # the decay's gradient: one matrix, by rows and by columns
                e = dm * mf
                at_t = jnp.where(head_col == hd,
                                 jnp.sum(e, axis=1, keepdims=True), at_t)
                at_s = jnp.where(head_row == hd,
                                 jnp.sum(e, axis=0, keepdims=True), at_s)
                # m transposed, formed where it is used: measured faster
                # than turning m (0.23 ms a layer's backward pass)
                dxk = nn((cb_t * decay(cum, cumr, hd, upper, True)
                          ).astype(cd), gu)
                dxs = dxk if dxs is None else jnp.where(mine, dxk, dxs)
            end_u = from_end[:, sl]
            dxs = dxs + we * end_u
            dx_ref[:, sl] = (dte * dxs + d_ref[:, sl] * gf).astype(
                dx_ref.dtype)
            got = gf * ee * from_start[:, sl]      # <dy, y from the start>
            gave = xf * (we * dte) * end_u         # <x to the end, dS_end>
            xdx = xf * dxs
            # what the decay carries over the whole chunk: the state,
            # <dS_end, S_start>, and each position's own part of dS_end
            over = jnp.sum(ds_ref[:, sl] * st_ref[:, sl], axis=0,
                           keepdims=True)
            all_gave = jnp.sum(gave, axis=0, keepdims=True)
            for k in range(side):
                hd = unit * side + k
                mid = by_head(got - gave, k)
                ends = by_head(all_gave, k) \
                    + elast[:, hd:hd + 1] * by_head(over, k)
                at_t = at_t + jnp.where(
                    head_col == hd, jnp.where(at_last, mid + ends, mid), 0.0)
                dstep = jnp.where(head_col == hd, by_head(xdx, k), dstep)
            dyw = (gf * ee).astype(cd)
            xw = (xf * (we * dte)).astype(cd)
            dc = dc + nt(dyw, st0[:, sl])
            db = db + nt(xw, ds_end[:, sl])
            ds_ref[:, sl] = spread(elast, unit) * ds_ref[:, sl] + tn(cm, dyw)
        dcb = dcb.astype(cd)
        dc_ref[...] = (dc + nn(dcb, bm)).astype(dc_ref.dtype)
        db_ref[...] = (db + tn(dcb, cm)).astype(db_ref.dtype)
        dcum_ref[...] = at_t.T - at_s
        dstep_ref[...] = dstep.T

    dts, cum = _ssd_small(dt, a_head, g, chunk)
    wide, b_in, c_in, state, row, skip, at_start = _ssd_specs(
        dims, chunk, lambda ci: nc - 1 - ci)
    small = jax.ShapeDtypeStruct((b, g, nc, hg, chunk), f32)
    dx, db, dc, dcum, dstep = pallas_call(
        kernel, xbc, dy, xbc, xbc, dts, cum,
        jnp.repeat(d_skip, p).reshape(g, 1, hg * p), starts,
        grid=(b, g, nc),
        in_specs=[wide, wide, b_in, c_in, row, row, skip, at_start],
        out_specs=[wide, state, state, row, row],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), cd),
                   jax.ShapeDtypeStruct((b, t, g * n), cd),
                   jax.ShapeDtypeStruct((b, t, g * n), cd), small, small],
        scratch_shapes=[pltpu.VMEM((n, hg * p), f32)],
        compiler_params=_ssd_params(), name="ssd_chunk_backward")

    def by_position(v):
        return v.transpose(0, 2, 4, 1, 3).reshape(b, t, h)

    return dx, db, dc, by_position(dcum), by_position(dstep)


@functools.lru_cache(None)
def _ssd_jitted():
    """The two kernels' callers as ``jax.jit`` functions, made once: the
    layers of a model share shapes, so a step traces and lowers each
    kernel (an unrolled body, and the interpreter's twin beside it) once
    and calls it a layer, where bare calls traced it a layer and pass: 9
    s of an 8,192-token model's set-up on the chip's host."""
    import jax

    return (jax.jit(_ssd_chunk_forward,
                    static_argnames=("dims", "chunk", "with_states")),
            jax.jit(_ssd_chunk_backward, static_argnames=("dims", "chunk")))


def ssd_chunk_forward(*args, **static):
    """:func:`_ssd_chunk_forward` through its shared ``jax.jit``."""
    return _ssd_jitted()[0](*args, **static)


def ssd_chunk_backward(*args, **static):
    """:func:`_ssd_chunk_backward` through its shared ``jax.jit``."""
    return _ssd_jitted()[1](*args, **static)


# ---------------------------------------------------------------------------
# Chunked gated delta rule (``ops/seq.py`` ``GatedDeltaRule``)
# ---------------------------------------------------------------------------
#
# One grid step is one chunk of a few heads of one sequence, the chunk axis
# innermost and sequential; see ``seq.gated_delta_chunked`` for the chunk's
# WY form. The kernels have TWO ENTRIES over one chunk body
# (``_delta_chunk_tools``: ``forward_steps`` / ``backward_steps`` over
# ``parts`` / ``channel_parts`` and ``head_scalar`` / ``head_channel``);
# only where a head's rows come from and go to differs:
#
# - ROW-MAJOR (``_delta_rows_forward`` / ``_backward``; ``seq.
#   gated_delta_rows``; ``delta_rows_applicable``: heads of whole lane
#   tiles, 128 keys and 128 or 256 values, sequences of whole chunks): the
#   op's own arrays ``[rows, Hk * K]`` / ``[rows, H * V]`` IN THE COMPUTE
#   DTYPE, as the projections leave them, in blocks of ``(chunk, a step's
#   heads x a head's lanes)``; a head is a static lane-aligned slice of
#   the block. In VMEM, before the body: the cast to float32, ``q / |q| /
#   sqrt(K)`` and ``k / |k|`` a KEY head (value head ``i`` reads key head
#   ``i // ratio`` of the step's block, so shared key heads are never
#   repeated in HBM), a channel's gate from ``a``, ``exp(A_log)`` and
#   ``dt_bias`` in both forms of ``gate_floor``. After the backward body: a
#   key head's ``dq``, ``dk`` summed over its value heads in float32, the
#   normalisation's backward pass, the gate's, the stores. No float32 or
#   head-major copy of a wide array exists on either side of the call.
# - HEAD-MAJOR (``_delta_chunk_forward`` / ``_backward``; ``seq.
#   gated_delta_scan``): float32 ``[B, H, T, K]`` arrays that the op casts,
#   normalises, gates, repeats and transposes in XLA (a block's last
#   dimension is the array's own, so a head's width need not be whole
#   128-lane tiles: the Olmo cell's 96 and 192 are not).
#
# In both, the per-position scalars (the step gate and a head's decay, 1 MB
# arrays) go with the positions along the lanes (``small``), formed in XLA.
# Inside a step, all in VMEM and per head: the cumulative
# log-decay and the masked decay matrix, ``K K^T`` and ``Q K^T``, ``A``, the
# chunk's explicit ``T = (I + A)^-1``, ``u = T b (v - G K S)``, the output
# ``G Q S + ((Q K^T) * decay) u`` and the state's update. Because the state
# is at hand the system has ONE right-hand side. ``T`` comes from forward
# substitution by columns (row j is final after j steps, and is taken off
# the rows below it scaled by ``A``'s column j: L - 1 multiply-adds on the
# VPU over the sublane tiles that still change), never from a power series;
# the backward kernel reuses it transposed: with ``u = T R``, ``dR = T^T
# du`` and ``dA = -tril(dR u^T, -1)``. What crosses chunks (the state, or in
# the backward kernel its gradient) is a float32 VMEM scratch ``[heads, K,
# V]``. Products that share their right operand are taken as one, their
# left operands stacked by rows (``[Q; K] K^T``, ``[Q; K] S``, ``[dR; dO]
# u^T``, ...): the MXU's weights are loaded once for 128 rows, not twice
# for 64 (a twentieth of the backward kernel's time, none of the forward's:
# at six passes a product the kernels are bound by the rows they stream).
#
# The differentiable function over them is ``seq.gated_delta_rows``
# (row-major; residuals: the op's inputs as they came, which the segment
# holds or recomputes anyway, and the float32 chunk-start states ``[B,
# T/chunk, H, K, V]`` the forward kernel writes when asked) or ``seq.
# gated_delta_scan`` (head-major; residuals: the five float32 head-major
# inputs and the same states); the backward kernel forms everything else
# again. Under segment recomputation the op so runs forward (no states),
# forward (with states), backward.
#
# Precision (both kernels): everything is float32, as in the XLA body. Every
# ``dot`` takes float32 operands at ``Precision.HIGHEST`` (Mosaic's
# ``contract_precision<fp32>``: six bfloat16 passes, never one) and
# accumulates in float32; the cumulative sums, the decays, the
# substitution, the carried state, its gradient and every accumulator are
# float32. The decay's gradient is ONE float32 matrix ``E = dP * P + dA *
# A`` inside the chunk, summed along its rows (at t) and its columns (at i,
# negative). ROUNDINGS: head-major none (the caller rounds the output to
# the compute dtype once, outside, and autodiff rounds the wide gradients
# where it transposes the casts); row-major the same ones, at the stores:
# ``o``, ``dquery``, ``dkey``, ``dvalue`` and a channel's ``da`` leave in the
# compute dtype, each rounded once from its float32 value after the
# normalisation's and the gate's backward passes; the casts from the
# compute dtype are exact. ``A_log``'s and ``dt_bias``'s gradients are
# float32 sums over every row: a channel's accumulate in float32 in VMEM
# along the chunk axis (an output block resident across it) and are summed
# over the sequences in XLA, never rounded; a head's are autodiff's over
# the float32 ``dg [B, T, H]``, as ever.
#
# One decay a KEY CHANNEL (``g [B, T, H, K]``; ``seq.
# gated_delta_chunked_channel`` has the algebra) runs through the same two
# kernels, the branch taken in Python when the call is traced
# (``channel_parts`` / ``head_channel``; the scalar bodies are untouched by
# it). What differs: the gate rides the keys' layout and its gradient
# leaves in it; the cumulative sum ``c [L, K]`` is a product with a
# triangle of ones (exact: the six passes hold a float32 times 1); the
# decay does not factor out of ``K K^T``, so ``A`` and ``P`` are formed a
# block row a 16-position sub-chunk, ``([Q_s; K_s] * exp(c - r_s)) (K *
# exp(r_s - c))^T`` over the columns of sub-chunks ``<= s`` (the rest
# scaled by 0), every exponent referred to the sub-chunk's MIDDLE so that
# both factors stay within ``exp(+-40)`` at a log-decay of -5 (referred to
# its start the six-pass product's low-order parts go subnormal and Mosaic
# flushes them); ``exp(c)``, ``exp(c_L - c)`` are ``[L, K]`` and scale q
# and k, ``exp(c_L)`` ``[K, 1]`` scales the state's key axis, all with
# exponents <= 0. The decay's gradient has no one-matrix form a channel;
# ``c`` enters only as a factor ``exp(+-c)`` of a row of q or k, and for an
# operand ``X * exp(c)``, ``dc = X * dX``: ``dc = q * dq + k * (dk_row -
# dk_col)``, k's gradient split by the side k stood on, plus ``c_L``'s
# terms at the last position, then the reversed cumulative sum as a
# product with the same triangle. The scores' gradients go back the way
# the scores came: a block row ``[dP_s; dA_s b] (K * exp(r_s - c))`` for q
# and k by rows, its transpose against ``[Q_s; K_s] * exp(c - r_s)`` for k
# by columns.

# heads a grid step takes at most (three or five measure alike, one is a
# tenth slower), and the float32 words it may hold by the count below: five
# heads at 64 x (96 + 192) fit the 16 MB of scoped VMEM, fifteen do not
_DELTA_HEADS = 5
_DELTA_STEP_WORDS = 420_000
# positions a sub-chunk of the chunk with a decay a key channel
# (``seq.gated_delta_chunked_channel``, whose ``SUB_CHUNK`` this is)
DELTA_SUB_CHUNK = 16


def _delta_heads(heads, dk, dv, chunk, channel=False):
    """Heads a grid step takes: the most that divide ``heads`` evenly and
    fit VMEM together, a head counted as its rows of q/k and v, four of the
    chunk's square matrices and its state, each padded to whole lanes; with
    a decay a key ``channel`` also the gate's block and ``exp(c - r)`` (the
    scaled copies of k a sub-chunk come and go one at a time): four heads a
    step at 64 x 128 x 128, which measured 4-8% under two and 12-18% under
    one a pass of a layer alone (PR 41)."""
    def lanes(width):
        return -(-width // 128) * 128

    words = chunk * (lanes(dk) + lanes(dv) + 4 * lanes(chunk)) \
        + dk * lanes(dv)
    if channel:
        words += 2 * chunk * lanes(dk)
    most = min(_DELTA_HEADS, max(1, _DELTA_STEP_WORDS // words))
    return max(d for d in range(1, most + 1) if heads % d == 0)


def delta_chunk_applicable(dims, chunk, dtype) -> bool:
    """Whether the chunk kernels take ``dims = (H, K, V)``: float32
    operands (the op computes in float32 whatever the compute dtype; a
    bfloat16 operand is another result), a chunk of whole sublane tiles
    that fits the lanes, and widths of whole sublanes up to one lane tile
    of keys and two of values, so that a step's heads fit VMEM (a head's
    width need NOT be whole lanes: a block's last dimension is the
    array's)."""
    _, dk, dv = dims
    return (str(dtype) == "float32" and chunk % 8 == 0 and 8 <= chunk <= 128
            and dk % 8 == 0 and dv % 8 == 0 and dk <= 128 and dv <= 256
            and pallas_available())


def delta_channel_applicable(dims, chunk, dtype) -> bool:
    """Whether the chunk kernels take ``dims = (H, K, V)`` with one decay a
    KEY CHANNEL (``g [B, T, H, K]``): what :func:`delta_chunk_applicable`
    admits, of that a key width of whole lane tiles (128: the gate and the
    scaled keys are ``[rows, K]`` tiles with K along the lanes; what the
    published models of this family have) and a chunk of whole
    ``DELTA_SUB_CHUNK``-position sub-chunks (two sublane tiles each) up to
    64 positions, four sub-chunks."""
    return (delta_chunk_applicable(dims, chunk, dtype) and dims[1] % 128 == 0
            and chunk % DELTA_SUB_CHUNK == 0 and chunk <= 64)


_DeltaChunk = collections.namedtuple(
    "_DeltaChunk", "b_col e_col w_col e_last decay p kkd a t_inv qs ks z u")
# the same with a decay a key channel: ``e_col``, ``w_col [L, K]`` and
# ``e_last [K, 1]`` (beside it as the row ``e_last_row [1, K]``), the block
# rows' factors ``e_row [L, K]`` and ``col_exp`` (a sub-chunk's ``[L, K]``
# each) where ``decay [L, L]`` was, ``qe``, ``ke`` = q, k ``* e_col`` and
# ``qs``, ``ks`` their products with the state
_DeltaChannelChunk = collections.namedtuple(
    "_DeltaChannelChunk", "b_col e_col w_col e_last e_last_row e_row col_exp "
    "p kkd a t_inv qe ke qs ks z u")


def _delta_chunk_tools(chunk):
    """What the two kernels' bodies share: the products, the masks, a row
    turned into a column, and the chunk's matrices from its inputs and
    start states."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=f32)

    nn = lambda a, b: dot(a, b, ((1,), (0,)))      # noqa: E731
    nt = lambda a, b: dot(a, b, ((1,), (1,)))      # noqa: E731
    tn = lambda a, b: dot(a, b, ((0,), (0,)))      # noqa: E731

    def both(product, x, y, shared):
        """``product(x, shared)``, ``product(y, shared)`` as one product of
        ``[x; y]`` (both ``[chunk, .]``)."""
        out = product(jnp.concatenate([x, y], axis=0), shared)
        return out[:chunk], out[chunk:]

    def tn_sum(x0, y0, x1, y1):
        """``x0^T y0 + x1^T y1`` as one product over ``2 chunk`` rows."""
        return tn(jnp.concatenate([x0, x1], axis=0),
                  jnp.concatenate([y0, y1], axis=0))

    def masks():
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        return row >= col, row > col, row == col

    def column(r, eye):
        """``[1, L]`` -> ``[L, 1]``, exactly."""
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    def row(c, eye):
        """``[L, 1]`` -> ``[1, L]``, exactly."""
        return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)

    def unit_lower_inverses(mats, eye):
        """``(I + a)^-1`` for each strictly lower triangular ``a [L, L]`` of
        ``mats`` by forward substitution: step j takes row j (final by
        then) off the rows below it, by sublane tiles of 8 rows; a tile
        whose rows are all above j + 1 has nothing left to change and is
        skipped. The systems go in lockstep: one's chain of L - 1
        dependent steps fills the others' waits (a head at a time, each
        chain between its own products, cost a forward kernel 0.8 ms of
        3.0 at the cell's shapes)."""
        ident = jnp.where(eye, 1.0, 0.0).astype(f32)
        xs = [[ident[r:r + 8] for r in range(0, chunk, 8)] for _ in mats]
        for j in range(chunk - 1):
            for a, tiles in zip(mats, xs):
                done = tiles[j // 8][j % 8:j % 8 + 1, :]
                for i in range((j + 1) // 8, chunk // 8):
                    tiles[i] = tiles[i] - a[8 * i:8 * i + 8, j:j + 1] * done
        return [jnp.concatenate(tiles, axis=0) for tiles in xs]

    def parts(heads, masks3):
        """A chunk's matrices for each head of ``heads`` (tuples ``q, k, v,
        g_row, b_row, s``, ``s`` the start state): what the forward kernel
        computes and the backward kernel forms again."""
        lower, strict, eye = masks3
        first = []
        for q, k, _, g_row, b_row, _ in heads:
            # the cumulative log-decay c as a column; exp(c_t - c_i), t >= i
            c_col = jnp.sum(jnp.where(lower, g_row, 0.0), axis=1,
                            keepdims=True)
            decay = jnp.exp(jnp.where(lower, c_col - row(c_col, eye),
                                      _SSD_MASKED))
            b_col = column(b_row, eye)
            last = c_col[chunk - 1:chunk, :]
            e_col, w_col, e_last = (jnp.exp(c_col), jnp.exp(last - c_col),
                                    jnp.exp(last))
            qk, kk = both(nt, q, k, k)
            p = qk * decay                                 # [t, i], t >= i
            kkd = jnp.where(strict, kk * decay, 0.0)
            first.append((b_col, e_col, w_col, e_last, decay, p, kkd,
                          b_col * kkd))
        inverses = unit_lower_inverses([f[-1] for f in first], eye)
        out = []
        for (q, k, v, _, _, s), f, t_inv in zip(heads, first, inverses):
            b_col, e_col = f[0], f[1]
            qs, ks = both(nn, q, k, s)
            z = v - e_col * ks
            out.append(_DeltaChunk(*f, t_inv, qs, ks, z,
                                   nn(t_inv, b_col * z)))
        return out

    sub = DELTA_SUB_CHUNK
    ns = chunk // sub

    def by_sub(x, s):
        """Sub-chunk ``s``'s rows of ``x [L, .]``."""
        return x[s * sub:(s + 1) * sub]

    def square_eye(n):
        return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def triangle(lower):
        """Ones where ``lower``: ``triangle @ x`` sums x's rows from the
        chunk's start, ``triangle^T @ x`` to its end; exactly (the six
        passes hold a float32 times 1), in float32."""
        return jnp.where(lower, 1.0, 0.0).astype(f32)

    def channel_parts(heads, masks3):
        """:func:`parts` with a decay a key channel: ``g [L, K]`` where
        ``g_row`` was. ``A`` and ``P`` are formed a block row a sub-chunk,
        ``([Q_s; K_s] * exp(c - r_s)) (K * exp(r_s - c))^T`` over the
        columns of sub-chunks ``<= s`` (the others' factor is 0), ``r_s``
        the decays summed to the sub-chunk's MIDDLE: both factors within
        ``exp(+-40)`` at a log-decay of -5, as
        ``seq.gated_delta_chunked_channel`` has it and for its reason."""
        lower, strict, eye = masks3
        dk = heads[0][0].shape[-1]
        upto, eye_k = triangle(lower), square_eye(dk)
        at = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
        first = []
        for q, k, _, g, b_row, _ in heads:
            # c [L, K]: the log-decays summed from the chunk's start
            c = nn(upto, g)
            mids = [c[s * sub + sub // 2 - 1:s * sub + sub // 2]
                    for s in range(ns)]
            e_row = jnp.exp(c - jnp.concatenate(
                [jnp.broadcast_to(m, (sub, dk)) for m in mids], axis=0))
            col_exp = [jnp.exp(jnp.where(at < (s + 1) * sub, m - c,
                                         _SSD_MASKED))
                       for s, m in enumerate(mids)]
            qr, kr = q * e_row, k * e_row
            blocks = [nt(jnp.concatenate([by_sub(qr, s), by_sub(kr, s)],
                                         axis=0), k * e)
                      for s, e in enumerate(col_exp)]
            p = jnp.where(lower, jnp.concatenate(
                [blk[:sub] for blk in blocks], axis=0), 0.0)
            kkd = jnp.where(strict, jnp.concatenate(
                [blk[sub:] for blk in blocks], axis=0), 0.0)
            b_col = column(b_row, eye)
            last = c[chunk - 1:chunk, :]
            e_last_row = jnp.exp(last)
            first.append((b_col, jnp.exp(c), jnp.exp(last - c),
                          column(e_last_row, eye_k), e_last_row, e_row,
                          col_exp, p, kkd, b_col * kkd))
        inverses = unit_lower_inverses([f[-1] for f in first], eye)
        out = []
        for (q, k, v, _, _, s), f, t_inv in zip(heads, first, inverses):
            b_col, e_col = f[0], f[1]
            qe, ke = q * e_col, k * e_col
            qs, ks = both(nn, qe, ke, s)
            z = v - ks
            out.append(_DeltaChannelChunk(*f, t_inv, qe, ke, qs, ks, z,
                                          nn(t_inv, b_col * z)))
        return out

    def forward_steps(hb, head_ops, channel, st_ref, keep_start, put_o):
        """A grid step of the forward kernel, unrolled over its ``hb`` heads:
        ``head_ops(i)`` gives head ``i``'s ``q, k, v, g, b_row, s`` (float32,
        q and k normalised), ``put_o(i, o)`` takes its output and
        ``keep_start(i, s)``, where given, its chunk-start state; the carried
        state ``st_ref [hb, K, V]`` moves on. Where a head's rows come from
        and go to is the caller's: both entries run this."""
        heads = [head_ops(i) for i in range(hb)]
        part = channel_parts if channel else parts
        for i, (ops, c) in enumerate(zip(heads, part(heads, masks()))):
            kh, s = ops[1], ops[5]
            if keep_start is not None:
                keep_start(i, s)
            # a channel's exp(c) scales q before its product with the state
            from_start = c.qs if channel else c.e_col * c.qs
            put_o(i, from_start + nn(c.p, c.u))
            st_ref[i] = c.e_last * s + tn(kh * c.w_col, c.u)

    def backward_steps(hb, dk, head_ops, channel, ds_ref, io):
        """A grid step of the backward kernel, unrolled over its ``hb``
        heads, the mirror of :func:`forward_steps`: ``io.dy(i)`` is head
        ``i``'s output cotangent ``[L, V]`` and ``io.dq`` / ``dk`` / ``dv``
        ``(i, x)`` take the gradients of its (normalised) q, k and v,
        ``io.db`` / ``io.dg`` those of its step gate and log-decay (rows
        ``[1, L]``; a decay a key channel ``[L, K]``), all float32; the
        state's gradient rides ``ds_ref [hb, K, V]``."""
        masks3 = lower, strict, eye = masks()
        at_last = jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0) == chunk - 1

        def rows(x):
            return jnp.sum(x, axis=1, keepdims=True)

        if channel:
            upto, eye_k = triangle(lower), square_eye(dk)

        def head_scalar(i, ops, c):
            qh, kh, s, ds, dy = ops[0], ops[1], ops[5], ds_ref[i], io.dy(i)
            (b_col, e_col, w_col, e_last, decay, p, kkd, a, t_inv, qs, ks, z,
             u) = c
            # o = G Q S + P u, S_end = g_L S + (w k)^T u
            du = tn(p, dy) + nn(kh * w_col, ds)
            dr = tn(t_inv, du)                  # u = T R: dR = T^T du
            da, dp = both(nt, dr, dy, u)        # dA = -dR u^T, below
            da = -da
            dz = b_col * dr                     # R = b (v - G K S)
            dks = -e_col * dz
            dyw = e_col * dy
            uds = nt(u, ds)                     # d (w k)
            g_kk = jnp.where(strict, da * b_col * decay, 0.0)
            g_qk = dp * decay
            at_q, at_k = both(nn, g_qk, g_kk, kh)
            of_q, of_k = both(nt, dyw, dks, s)
            io.dq(i, at_q + of_q)
            io.dk(i, at_k + tn_sum(g_kk, kh, g_qk, qh) + of_k + w_col * uds)
            io.dv(i, dz)
            io.db(i, row(rows(dr * z) + rows(da * kkd), eye))
            # the decay's gradient: ONE matrix by rows and by columns, then
            # what the chunk's own scalings carry
            e = dp * p + da * a
            took = rows(dy * qs) * e_col + rows(dks * ks)   # d G, times G
            gave = rows(uds * kh) * w_col                   # d w, times w
            ends = jnp.sum(gave, axis=0, keepdims=True) \
                + e_last * jnp.sum(rows(ds * s), axis=0, keepdims=True)
            dc = rows(e) - column(jnp.sum(e, axis=0, keepdims=True), eye) \
                + took - gave + jnp.where(at_last, ends, 0.0)
            # g_j is in c_t for every t >= j of the chunk
            io.dg(i, jnp.sum(jnp.where(lower, dc, 0.0), axis=0,
                             keepdims=True))
            ds_ref[i] = e_last * ds + tn_sum(kh, dks, qh, dyw)

        def head_channel(i, ops, c):
            """A decay a key channel. ``c [L, K]`` enters only as a factor
            ``exp(+-c)`` of a row of q or k, and for an operand ``X *
            exp(c)``, ``dc = X * dX``: the channel's gradient is ``q * dq``
            and ``k * dk``, the latter signed by the side k stood on (times
            ``exp(c)``: a block row of ``A`` or ``P``, ``(K * exp(c)) S``;
            times ``exp(-c)``: the columns, ``K * exp(c_L - c)``), plus at
            the last position what ``c_L`` carries."""
            qh, kh, s, ds, dy = ops[0], ops[1], ops[5], ds_ref[i], io.dy(i)
            (b_col, e_col, w_col, e_last, e_last_row, e_row, col_exp, p, kkd,
             _, t_inv, qe, ke, _, _, z, u) = c
            # o = (Q * E) S + P u, S_end = Diag(e_L) S + (w k)^T u
            du = tn(p, dy) + nn(kh * w_col, ds)
            dr = tn(t_inv, du)                  # u = T R: dR = T^T du
            da, dp = both(nt, dr, dy, u)        # dA = -dR u^T, below
            da = -da
            dz = b_col * dr                     # R = b (v - (K * E) S)
            g_kk = jnp.where(strict, da * b_col, 0.0)
            g_qk = jnp.where(lower, dp, 0.0)
            # the scores a block row: d [Q_s; K_s] by rows, d K by columns
            qr, kr = qh * e_row, kh * e_row
            at_q, at_k, at_col = [], [], None
            for j, e in enumerate(col_exp):
                top = jnp.concatenate([by_sub(g_qk, j), by_sub(g_kk, j)],
                                      axis=0)
                at_rows = nn(top, kh * e)
                at_q.append(at_rows[:sub])
                at_k.append(at_rows[sub:])
                mine = e * tn(top, jnp.concatenate(
                    [by_sub(qr, j), by_sub(kr, j)], axis=0))
                at_col = mine if at_col is None else at_col + mine
            of_q, of_k = both(nt, dy, -dz, s)   # d (Q * E), d (K * E)
            gave = w_col * nt(u, ds)            # d (w k), times w
            dq = e_row * jnp.concatenate(at_q, axis=0) + e_col * of_q
            dk_row = e_row * jnp.concatenate(at_k, axis=0) + e_col * of_k
            dk_col = at_col + gave
            io.dq(i, dq)
            io.dk(i, dk_row + dk_col)
            io.dv(i, dz)
            io.db(i, row(rows(dr * z) + rows(da * kkd), eye))
            # c_L: in every w_t = exp(c_L - c_t), and in Diag(exp(c_L)) S
            ends = jnp.sum(kh * gave, axis=0, keepdims=True) \
                + e_last_row * row(rows(ds * s), eye_k)
            dc = qh * dq + kh * (dk_row - dk_col) \
                + jnp.where(at_last, ends, 0.0)
            # g_j is in c_t for every t >= j of the chunk
            io.dg(i, tn(upto, dc))
            ds_ref[i] = e_last * ds + tn_sum(ke, -dz, qe, dy)

        heads = [head_ops(i) for i in range(hb)]
        part, one = (channel_parts, head_channel) if channel \
            else (parts, head_scalar)
        for i, (ops, c) in enumerate(zip(heads, part(heads, masks3))):
            one(i, ops, c)

    return types.SimpleNamespace(forward_steps=forward_steps,
                                 backward_steps=backward_steps)


def _scalars_row(ref):
    """``put(i, x)`` that stores head ``i``'s row ``x [1, L]`` of a block of
    per-position scalars ``[hb, L]``."""
    return lambda i, x: ref.__setitem__((slice(i, i + 1), slice(None)), x)


def _delta_scalars(b, t, h, hb, chunk, dk, dv):
    """What both entries share of their layouts: the per-position scalars
    ``[B, T, H]`` sent as ``[B, H / hb, T / chunk, hb, chunk]`` (``small``;
    positions along the lanes) and brought back (``by_position``), and the
    block specs of those and of the chunk-start states given the chunk
    order."""
    from jax.experimental import pallas as pl

    nc = t // chunk

    def small(x):
        return x.reshape(b, nc, chunk, h // hb, hb).transpose(0, 3, 1, 4, 2)

    def by_position(x):
        return x.transpose(0, 2, 4, 1, 3).reshape(b, t, h)

    def specs(order):
        return (pl.BlockSpec((None, None, None, hb, chunk),
                             lambda bi, hi, ci: (bi, hi, order(ci), 0, 0)),
                pl.BlockSpec((None, None, hb, dk, dv),
                             lambda bi, hi, ci: (bi, order(ci), hi, 0, 0)))

    return small, by_position, specs


def _delta_layout(q, v, chunk, channel):
    """The HEAD-MAJOR entry's view of the op's arrays: heads before
    positions, the scalars ``[B, H / hb, T / chunk, hb, chunk]``; and the
    block specs by (sequence, head group, chunk) given the chunk order. A
    decay a key ``channel`` goes as the keys do. What keeps this entry:
    heads that are not whole lane tiles (96 x 192) and sequences of part
    chunks. Whole lane tiles take :func:`_delta_rows_layout`, which reads
    the rows where they lie. (Two attempts at that, and why they differ.
    PR 41 kept the op's XLA prologue and changed only these block specs to
    ``[B, T, H * K]``: the kernels ran as fast and the transposes left, but
    the prologue had already made float32 ``[B, T, H, K]`` arrays, tiles of 8
    heads x 128 lanes, and XLA relaid them as ``[B, T, H * K]`` in passes of
    its own: ``step_device_ms`` 498 against 483. PR 45 deleted the
    prologue: the kernels take the op's INPUTS, 2-D in the compute dtype,
    so no 4-D float32 array exists for XLA to relay: the Ling cell's
    ``step_device_ms`` 418.1 against 453.7 head-major, the scope's XLA
    share 0.8 ms a step against 40.1, ``copy`` 5.9 against 28.6.)"""
    from jax.experimental import pallas as pl

    b, t, h, dk = q.shape
    dv = v.shape[-1]
    hb, nc = _delta_heads(h, dk, dv, chunk, channel), t // chunk
    small, by_position, small_specs = _delta_scalars(b, t, h, hb, chunk, dk,
                                                     dv)

    def wide(x):
        return x.transpose(0, 2, 1, 3)

    def specs(order):
        def block(width):
            return pl.BlockSpec((None, hb, chunk, width),
                                lambda bi, hi, ci: (bi, hi, order(ci), 0))

        return (block(dk), block(dv)) + small_specs(order)

    return (b, t, h, dk, dv, hb, nc), wide, small, by_position, specs


def _delta_chunk_forward(q, k, v, g, beta, *, chunk, with_states):
    """The gated delta rule over sequences of whole chunks, one kernel call
    (see the section's comment). ``q``, ``k [B, T, H, K]``, ``v [B, T, H,
    V]``, ``beta [B, T, H]`` and ``g [B, T, H]``, one decay a head, or ``[B,
    T, H, K]``, one a key channel; all float32. Returns ``o [B, T, H, V]``
    and, ``with_states``, the float32 state at each chunk's start ``[B,
    T/chunk, H, K, V]`` (else ``None``). Scratch: the carried state, float32
    ``[heads a step, K, V]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    channel = g.ndim == 4
    (b, t, h, dk, dv, hb, nc), wide, small, _, specs = _delta_layout(
        q, v, chunk, channel)
    tools = _delta_chunk_tools(chunk)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest):
        st_ref = rest[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            st_ref[...] = jnp.zeros_like(st_ref)

        def head_ops(i):
            return (q_ref[i], k_ref[i], v_ref[i],
                    g_ref[i] if channel else g_ref[i:i + 1, :],
                    b_ref[i:i + 1, :], st_ref[i])

        tools.forward_steps(
            hb, head_ops, channel, st_ref,
            rest[0].__setitem__ if with_states else None, o_ref.__setitem__)

    keys, values, scalars, states = specs(lambda ci: ci)
    out_shape = [jax.ShapeDtypeStruct((b, h, t, dv), f32)]
    out_specs = [values]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, h, dk, dv), f32))
        out_specs.append(states)
    out = pallas_call(
        kernel, wide(q), wide(k), wide(v),
        wide(g) if channel else small(g), small(beta),
        grid=(b, h // hb, nc),
        in_specs=[keys, keys, values, keys if channel else scalars, scalars],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_ssd_params(), name="delta_chunk_forward")
    return out[0].transpose(0, 2, 1, 3), out[1] if with_states else None


def _delta_chunk_backward(q, k, v, g, beta, starts, do, *, chunk):
    """The mirror of :func:`_delta_chunk_forward` over the chunks reversed:
    each chunk's matrices are formed again in VMEM from the inputs and the
    chunk-start state, and the state's gradient rides a float32 scratch
    ``[heads a step, K, V]``. ``do [B, T, H, V]``; returns ``dq``, ``dk``,
    ``dv``, ``dg`` in the inputs' shapes and ``dbeta [B, T, H]``, all
    float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    channel = g.ndim == 4
    (b, t, h, dk, dv, hb, nc), wide, small, by_position, specs = \
        _delta_layout(q, v, chunk, channel)
    tools = _delta_chunk_tools(chunk)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)

        def head_ops(i):
            return (q_ref[i], k_ref[i], v_ref[i],
                    g_ref[i] if channel else g_ref[i:i + 1, :],
                    b_ref[i:i + 1, :], st_ref[i])

        tools.backward_steps(hb, dk, head_ops, channel, ds_ref,
                             types.SimpleNamespace(
            dy=do_ref.__getitem__, dq=dq_ref.__setitem__,
            dk=dk_ref.__setitem__, dv=dv_ref.__setitem__,
            db=_scalars_row(db_ref),
            dg=dg_ref.__setitem__ if channel else _scalars_row(dg_ref)))

    keys, values, scalars, states = specs(lambda ci: nc - 1 - ci)
    small_out = jax.ShapeDtypeStruct((b, h // hb, nc, hb, chunk), f32)
    keys_out = jax.ShapeDtypeStruct((b, h, t, dk), f32)
    dq, dk_, dv_, dg, db = pallas_call(
        kernel, wide(q), wide(k), wide(v),
        wide(g) if channel else small(g), small(beta), starts, wide(do),
        grid=(b, h // hb, nc),
        in_specs=[keys, keys, values, keys if channel else scalars, scalars,
                  states, values],
        out_specs=[keys, keys, values, keys if channel else scalars, scalars],
        out_shape=[keys_out, keys_out,
                   jax.ShapeDtypeStruct((b, h, t, dv), f32),
                   keys_out if channel else small_out, small_out],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_ssd_params(), name="delta_chunk_backward")
    return (wide(dq), wide(dk_), wide(dv_),
            wide(dg) if channel else by_position(dg), by_position(db))


# what the row-major entry needs beside its arrays: the op's own shape
# (``heads`` value heads reading ``key_heads`` query/key heads), the gate's
# form where the gate is formed in the kernel (a decay a key channel) and the
# normalisation's epsilon; hashable, so one ``jax.jit`` entry a shape
DeltaRows = collections.namedtuple(
    "DeltaRows", "seq_len heads key_heads key_dim value_dim chunk gate_floor "
    "eps")


def delta_rows_applicable(dims, key_heads, chunk, seq_len, channel) -> bool:
    """Whether the chunk kernels, where they take ``dims = (H, K, V)`` at all
    (:func:`delta_chunk_applicable` / :func:`delta_channel_applicable`), can
    read and write the op's wide arrays ROW-MAJOR where the projections
    leave them (:func:`_delta_rows_forward`): a head is whole lane tiles of
    keys and of values (a static lane-aligned slice of a block), the
    sequences are whole chunks of whole bfloat16 sublane tiles (16 rows),
    and a step's value heads cover whole key heads. 64 x 128 x 128 at 32 / 32
    and at 32 / 16 heads are such shapes; 96 x 192 is not and keeps the
    head-major entry."""
    h, dk, dv = dims
    return (dk % TILE_N == 0 and dv % TILE_N == 0 and chunk % 16 == 0
            and seq_len % chunk == 0 and h % key_heads == 0
            and _delta_heads(h, dk, dv, chunk, channel)
            % (h // key_heads) == 0)


def _delta_rows_layout(query, value, gate, spec):
    """The row-major entry's view: nothing of the wide arrays moves. The
    blocks are ``(chunk, a step's heads x a head's lanes)`` of ``[rows, Hk *
    K]`` / ``[rows, H * V]`` by (sequence, head group, chunk); the per-head
    scalars go as :func:`_delta_layout` sends them (``small``: 1 MB arrays),
    the gate's two parameter rows ``[1, H * K]`` and the sums of their
    gradients ``[B, 1, H * K]`` a step's lanes at a time, the latter
    resident across the chunk axis."""
    from jax.experimental import pallas as pl

    t, h, dk, dv, chunk = (spec.seq_len, spec.heads, spec.key_dim,
                           spec.value_dim, spec.chunk)
    b, nc, ratio = query.shape[0] // t, t // chunk, h // spec.key_heads
    channel = gate.ndim == 2
    hb = _delta_heads(h, dk, dv, chunk, channel)
    small, by_position, small_specs = _delta_scalars(b, t, h, hb, chunk, dk,
                                                     dv)

    def specs(order):
        def rows(width):
            return pl.BlockSpec(
                (chunk, width), lambda bi, hi, ci: (bi * nc + order(ci), hi))

        scalars, states = small_specs(order)
        return types.SimpleNamespace(
            keys=rows(hb // ratio * dk), values=rows(hb * dv),
            gate=rows(hb * dk) if channel else scalars, scalars=scalars,
            states=states,
            gate_row=pl.BlockSpec((1, hb * dk), lambda bi, hi, ci: (0, hi)),
            gate_sum=pl.BlockSpec((None, 1, hb * dk),
                                  lambda bi, hi, ci: (bi, 0, hi)))

    return (b, nc, hb, ratio, channel), small, by_position, specs


def _delta_rows_tools(spec):
    """What happens in VMEM between the op's rows and the chunk's matrices
    (float32 throughout): the normalisation of a key head's q and k and a
    channel's gate, and their backward passes, as ``GatedDeltaRule`` states
    them and autodiff differentiates them."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    floor, q_scale = spec.gate_floor, spec.key_dim ** -0.5

    def lanes(i, width):
        return slice(None), slice(i * width, (i + 1) * width)

    def unit(ref, j):
        """Key head ``j``'s rows, float32, and ``1 / |x|`` a row."""
        x = ref[lanes(j, spec.key_dim)].astype(f32)
        return x, jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + spec.eps)

    def unit_back(x, r, dy):
        """``d x`` of ``y = x r``, ``r = rsqrt(sum(x^2) + eps)``."""
        return r * dy - x * (r * r * r
                             * jnp.sum(x * dy, axis=-1, keepdims=True))

    def softplus(y):
        return jnp.maximum(y, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(y)))

    def gate(a, scale, bias):
        """A channel's log-decay ``[L, K]`` from ``a`` as it comes and the
        rows ``scale = exp(A_log)``, ``bias = dt_bias``: both forms of
        ``gate_floor``. Returns it with what its backward pass reads."""
        y = a.astype(f32) + bias
        if floor:
            sig = jax.nn.sigmoid(scale * y)
            return floor * sig, (y, sig)
        sp = softplus(y)
        return -scale * sp, (y, sp)

    def gate_back(dg, scale, kept):
        """``d a [L, K]`` and the sums over the chunk's rows ``d scale``,
        ``d bias [1, K]``."""
        y, other = kept
        if floor:
            dz = dg * (floor * other * (1.0 - other))   # other: the sigmoid
            da, dscale = dz * scale, dz * y
        else:
            da, dscale = -scale * dg * jax.nn.sigmoid(y), -dg * other
        return (da, jnp.sum(dscale, axis=0, keepdims=True),
                jnp.sum(da, axis=0, keepdims=True))

    def reader(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, gate_rows):
        """``head_ops(i)`` of a step over its blocks (what the shared steps
        ask: ``q, k, v, g, b_row, s`` of value head ``i``, float32, q and k
        normalised), with what the backward pass reads again: ``key_heads[j]
        = (x, 1 / |x|)`` of key head ``j``'s q and of its k, formed once a
        key head, and a channel's ``gates[i]``. ``gate_rows``: the refs of
        ``scale`` and ``bias``, a decay a key channel; else ``()`` and
        ``g_ref`` holds the heads' log-decays."""
        ratio = spec.heads // spec.key_heads
        key_heads, normed, gates = {}, {}, {}

        def head_ops(i):
            j = i // ratio
            if j not in key_heads:
                key_heads[j] = (q, qr), (k, kr) = [
                    unit(ref, j) for ref in (q_ref, k_ref)]
                normed[j] = q * qr * q_scale, k * kr
            if gate_rows:
                at = lanes(i, spec.key_dim)
                g, gates[i] = gate(g_ref[at], *(r[at] for r in gate_rows))
            else:
                g = g_ref[i:i + 1, :]
            return (*normed[j], v_ref[lanes(i, spec.value_dim)].astype(f32),
                    g, b_ref[i:i + 1, :], st_ref[i])

        return head_ops, key_heads, gates

    return types.SimpleNamespace(lanes=lanes, unit_back=unit_back,
                                 gate_back=gate_back, q_scale=q_scale,
                                 reader=reader)


def _delta_rows_forward(query, key, value, gate, beta, scale=None, bias=None,
                        *, spec, with_states):
    """:func:`_delta_chunk_forward` over the op's arrays AS THEY LIE, for
    shapes :func:`delta_rows_applicable` admits: ``query``, ``key [rows, Hk *
    K]``, ``value [rows, H * V]`` in the compute dtype, before their
    normalisation; ``beta [B, T, H]`` float32; ``gate`` either the log-decay
    a head ``[B, T, H]`` float32 or, a decay a key channel, the op's ``a
    [rows, H * K]`` in the compute dtype with the float32 rows ``scale =
    exp(A_log)`` (a head's value on each of its channels) and ``bias =
    dt_bias``, ``[1, H * K]``. In VMEM, a step: the casts to float32, ``q / |q|
    / sqrt(K)`` and ``k / |k|`` a KEY head (value head ``i`` reads key head
    ``i // ratio`` of the step's block: no repeated copy exists), the
    channel's gate, then the chunk body both entries share. Returns ``o
    [rows, H * V]`` ROUNDED TO THE COMPUTE DTYPE at the store (its one
    rounding) and, ``with_states``, the float32 chunk-start states ``[B,
    T/chunk, H, K, V]``. Scratch: the carried state, float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (b, nc, hb, ratio, channel), small, _, specs = _delta_rows_layout(
        query, value, gate, spec)
    h, dk, dv = spec.heads, spec.key_dim, spec.value_dim
    tools, rows = _delta_chunk_tools(spec.chunk), _delta_rows_tools(spec)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *rest):
        gate_rows, (o_ref, *rest) = rest[:2 * channel], rest[2 * channel:]
        st_ref = rest[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            st_ref[...] = jnp.zeros_like(st_ref)

        head_ops, _, _ = rows.reader(q_ref, k_ref, v_ref, g_ref, b_ref,
                                     st_ref, gate_rows)

        def put_o(i, o):
            o_ref[rows.lanes(i, dv)] = o.astype(o_ref.dtype)

        tools.forward_steps(
            hb, head_ops, channel, st_ref,
            rest[0].__setitem__ if with_states else None, put_o)

    at = specs(lambda ci: ci)
    out_shape = [jax.ShapeDtypeStruct(value.shape, value.dtype)]
    out_specs = [at.values]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, h, dk, dv), f32))
        out_specs.append(at.states)
    out = pallas_call(
        kernel, query, key, value, gate if channel else small(gate),
        small(beta), *((scale, bias) if channel else ()),
        grid=(b, h // hb, nc),
        in_specs=[at.keys, at.keys, at.values, at.gate, at.scalars]
        + [at.gate_row] * (2 * channel),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_ssd_params(), name="delta_chunk_forward")
    return out[0], out[1] if with_states else None


def _delta_rows_backward(query, key, value, gate, beta, scale, bias, starts,
                         do, *, spec):
    """The mirror of :func:`_delta_rows_forward` over the chunks reversed,
    as :func:`_delta_chunk_backward` mirrors its forward: the rows are read
    as they lie and normalised and gated again in VMEM, the shared body
    gives the gradients of the normalised q and k a VALUE head, a key head's
    are their float32 sum, and the normalisation's backward pass (``dx = r dy
    - x r^3 sum(x dy)``) and the gate's run before the stores. Returns
    ``dquery``, ``dkey``, ``dvalue`` (and a decay a key channel ``da``) in the
    inputs' shapes ROUNDED TO THE COMPUTE DTYPE at the store, where the
    cast's own backward pass rounded them; ``dbeta`` and a head's ``dg [B,
    T, H]`` float32; and a channel's ``dscale``, ``dbias [1, H * K]``:
    float32 sums over every row, accumulated in VMEM along the chunk axis
    in float32 and summed over the sequences outside, never rounded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (b, nc, hb, ratio, channel), small, by_position, specs = \
        _delta_rows_layout(query, value, gate, spec)
    h, dk, dv = spec.heads, spec.key_dim, spec.value_dim
    tools, rows = _delta_chunk_tools(spec.chunk), _delta_rows_tools(spec)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *rest):
        gate_rows, rest = rest[:2 * channel], rest[2 * channel:]
        st_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref = rest[:7]
        gate_sums, ds_ref = rest[7:-1], rest[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)
            for ref in gate_sums:
                ref[...] = jnp.zeros_like(ref)

        head_ops, key_heads, gates = rows.reader(
            q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, gate_rows)

        def key_gradient(ref, which, scale):
            """The sum over a key head's value heads, through the
            normalisation, to the store."""
            sums = {}

            def put(i, x):
                j = i // ratio
                sums[j] = x if i % ratio == 0 else sums[j] + x
                if i % ratio == ratio - 1:
                    x0, r = key_heads[j][which]
                    ref[rows.lanes(j, dk)] = rows.unit_back(
                        x0, r, sums.pop(j) * scale).astype(ref.dtype)

            return put

        def put_dv(i, x):
            dv_ref[rows.lanes(i, dv)] = x.astype(dv_ref.dtype)

        def put_da(i, dg):
            at = rows.lanes(i, dk)
            da, dscale, dbias = rows.gate_back(dg, gate_rows[0][at],
                                               gates.pop(i))
            dg_ref[at] = da.astype(dg_ref.dtype)
            for ref, x in zip(gate_sums, (dscale, dbias)):
                ref[at] += x

        tools.backward_steps(
            hb, dk, head_ops, channel, ds_ref, types.SimpleNamespace(
                dy=lambda i: do_ref[rows.lanes(i, dv)].astype(f32),
                dq=key_gradient(dq_ref, 0, rows.q_scale),
                dk=key_gradient(dk_ref, 1, 1.0), dv=put_dv,
                db=_scalars_row(db_ref),
                dg=put_da if channel else _scalars_row(dg_ref)))

    at = specs(lambda ci: nc - 1 - ci)
    small_out = jax.ShapeDtypeStruct((b, h // hb, nc, hb, spec.chunk), f32)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)     # noqa: E731
    gate_sum = jax.ShapeDtypeStruct((b, 1, h * dk), f32)
    dq, dk_, dv_, dg, db, *sums = pallas_call(
        kernel, query, key, value, gate if channel else small(gate),
        small(beta), *((scale, bias) if channel else ()), starts, do,
        grid=(b, h // hb, nc),
        in_specs=[at.keys, at.keys, at.values, at.gate, at.scalars]
        + [at.gate_row] * (2 * channel) + [at.states, at.values],
        out_specs=[at.keys, at.keys, at.values, at.gate, at.scalars]
        + [at.gate_sum] * (2 * channel),
        out_shape=[like(query), like(key), like(value),
                   like(gate) if channel else small_out, small_out]
        + [gate_sum] * (2 * channel),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_ssd_params(), name="delta_chunk_backward")
    dscale, dbias = (jnp.sum(x, axis=0) for x in sums) if channel \
        else (None, None)
    return (dq, dk_, dv_, dg if channel else by_position(dg),
            by_position(db), dscale, dbias)


@functools.lru_cache(None)
def _delta_jitted():
    """The kernels' callers (head-major forward and backward, row-major
    forward and backward) as ``jax.jit`` functions, made once, as
    :func:`_ssd_jitted` and for its reason: a model's layers share shapes,
    so a step traces and lowers each unrolled body once."""
    import jax

    return (jax.jit(_delta_chunk_forward,
                    static_argnames=("chunk", "with_states")),
            jax.jit(_delta_chunk_backward, static_argnames=("chunk",)),
            jax.jit(_delta_rows_forward,
                    static_argnames=("spec", "with_states")),
            jax.jit(_delta_rows_backward, static_argnames=("spec",)))


def delta_chunk_forward(*args, **static):
    """:func:`_delta_chunk_forward` through its shared ``jax.jit``."""
    return _delta_jitted()[0](*args, **static)


def delta_chunk_backward(*args, **static):
    """:func:`_delta_chunk_backward` through its shared ``jax.jit``."""
    return _delta_jitted()[1](*args, **static)


def delta_rows_forward(*args, **static):
    """:func:`_delta_rows_forward` through its shared ``jax.jit``."""
    return _delta_jitted()[2](*args, **static)


def delta_rows_backward(*args, **static):
    """:func:`_delta_rows_backward` through its shared ``jax.jit``."""
    return _delta_jitted()[3](*args, **static)


# ---------------------------------------------------------------------------
# Grouped expert products (``ops/moe.py`` ``RoutedExperts``)
# ---------------------------------------------------------------------------
#
# A pass over the blocks of ``moe.plan``'s layout is three kernel calls, none
# of them a loop of XLA ops: the block's rows are GATHERED into layout order,
# the PRODUCTS run over contiguous blocks, and the weighted results are
# SCATTER-ADDED back onto their rows. A block is ``block`` slots of ONE
# expert, the layout is sorted by expert, and only the first ``nblocks``
# (traced) of the static ``L / block`` blocks are filled. Every grid has the
# blocks as a sequential axis; a step at or past ``nblocks`` does nothing,
# and its index maps are clamped to the last step that did, so it fetches
# nothing new. ``block_expert``, ``nblocks`` and, in the row kernels, the
# slots' rows are scalar-prefetched (SMEM).
#
# Rows in and out (``_gather_rows`` / ``_scatter_rows``). One DMA a row is
# bound by the descriptors (60 ns a row on a v5e: two thirds of a forward
# block's time, my chip runs, PR 33), so the rows move on the vector unit
# instead: the gather holds the whole ``[S, w]`` table in VMEM (copied in
# once a call) and copies a slot's row from it by the prefetched row; the
# scatter holds the float32 ``[S, w]`` result in VMEM, adds each slot's row
# onto it, and rounds it out once, at the end. Both take a tile of the width
# a time where the whole does not fit. A dynamic row of a VMEM array is a
# 32-bit affair (a bfloat16 row shares its sublane with its neighbour), so a
# 16-bit ``x`` is handed over PACKED, two columns a word (``_pack_rows``),
# and the product kernels unpack a block exactly (``_unpack_rows``). Within
# a block a row occurs once; across blocks the grid is sequential and the
# result never leaves VMEM between them, so a row two experts share is added
# twice in order. A padding slot (row 0, weight 0) gathers row 0 and adds a
# zero onto it.
#
# The products (``_experts_products_forward`` / ``_backward``): grid
# (blocks, tiles of ``f``), the tiles innermost. An expert's weight tiles
# come by index map (``block_expert[b]``), so with one tile consecutive
# blocks of an expert fetch them once, and the transposed uses are
# ``dot_general`` dimension numbers. The backward kernel keeps one float32
# accumulator a weight in VMEM scratch, all of ``[h, f]``: it is overwritten
# by a block whose expert differs from the block before and added to
# otherwise, and every step rounds its tile into the output block, which the
# pipeline writes back as its index moves on: the last block of an expert
# writes last, and what it writes is the whole sum. An expert that draws no
# row is never visited by a block: after the blocks the grid has a step an
# expert and tile, which writes zeros for those experts and stays where it
# was for the others. No ``[held, h, f]`` float32 array exists in HBM, and
# none is filled with zeros first.
#
# A last tile of ``f`` that hangs over the edge (``f`` is the array's full
# width where it is not whole lanes: 1856) holds no defined values there, so
# the weight tiles are zeroed past ``f`` before any product reads them.
#
# Precision: every product takes operands in the compute dtype and
# accumulates float32; the activation and its slopes are float32 before the
# one rounding; accumulators, the results by slot, the summed rows and
# ``dwt`` are float32.

_EXPERTS_VMEM = 112 << 20       # asked of the compiler; a v5e has 128 MiB
_ROW_UNROLL = 8                 # rows a step of the row kernels' loops


def _lanes(width):
    return -(-width // 128) * 128


def _filled(b, n_ref):
    """Block ``b``, or the last filled one for a step past them."""
    import jax.numpy as jnp

    return jnp.maximum(jnp.minimum(b, n_ref[0] - 1), 0)


def _packed_width(h, itemsize):
    """32-bit words of a row of ``h`` values as the row kernels move it."""
    return h if itemsize == 4 else -(-h // 256) * 128


def _pack_rows(x):
    """``x [S, h]`` as 32-bit rows: itself where it is 32-bit, else word
    ``j`` of a row holds columns ``j`` (low half) and ``j + w`` (high half,
    zero past ``h``), ``w`` the packed width."""
    import jax
    import jax.numpy as jnp

    if x.dtype.itemsize == 4:
        return x
    h = x.shape[1]
    w = _packed_width(h, 2)

    def bits(v):
        return jax.lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.uint32)

    high = jnp.pad(x[:, w:], ((0, 0), (0, 2 * w - h)))
    return bits(x[:, :w]) | (bits(high) << 16)


def _unpack_rows(u, h, cd):
    """The rows :func:`_pack_rows` packed, ``[n, h]`` in ``cd``, exactly."""
    import jax
    import jax.numpy as jnp

    if u.dtype == cd:
        return u
    f32 = jnp.float32
    low = jax.lax.bitcast_convert_type(u << 16, f32).astype(cd)
    high = jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000),
                                        f32).astype(cd)
    return jnp.concatenate([low, high], axis=1)[:, :h]


def _row_tile(width, rows, bytes_each):
    """The widest tile of whole lanes that divides ``width`` and whose
    ``rows`` rows at ``bytes_each`` a value fit the row kernels' share of
    VMEM; ``None`` where one lane tile does not."""
    lanes = width // 128
    for count in range(1, lanes + 1):
        if lanes % count == 0 and \
                rows * (width // count) * bytes_each <= _EXPERTS_VMEM * 2 // 3:
            return width // count
    return None


def _experts_tiles(h, f, block, gated, itemsize):
    """``(forward tile of f, backward tile of f, VMEM bytes reckoned)`` of
    the product kernels: the widest tiles of whole lanes (or all of ``f``)
    whose steps fit ``_EXPERTS_VMEM``, the larger need of the two; ``None``
    where no tile does. Counted a step: the weight tiles twice (the
    pipeline's two buffers), in the backward kernel also their output
    blocks twice and the float32 accumulators of all of ``f``; the block's
    rows in (packed, twice) and out (float32, twice) and unpacked; the
    block's float32 intermediates a tile wide, which the compiler spills to
    VMEM, and the products' float32 results."""
    nw = 3 if gated else 2
    hl = _lanes(h)
    packed = 4 * block * _lanes(_packed_width(h, itemsize))
    rows32 = 4 * block * hl

    def need(ft, backward):
        tile = hl * _lanes(ft)
        mid = 4 * block * _lanes(ft)
        if backward:
            whole = tile * -(-f // ft)
            return (nw * (4 * whole + 4 * tile * itemsize) + 4 * packed
                    + 3 * rows32 + 2 * block * hl * itemsize + rows32
                    + (10 if gated else 8) * mid + 2 * 4 * tile)
        return (2 * nw * tile * itemsize + 2 * packed + 3 * rows32
                + block * hl * itemsize + rows32 + (6 if gated else 4) * mid)

    def widest(backward):
        # all of f, then each count of tiles at its narrowest whole lanes
        tiles = [f] + [t for t in range(_lanes(f) - 128, 0, -128)
                       if -(-f // t) < -(-f // max(t - 128, 1)) or t == 128]
        for ft in tiles:
            if need(ft, backward) <= _EXPERTS_VMEM - (6 << 20):
                return ft
        return None

    fwd, bwd = widest(False), widest(True)
    if fwd is None or bwd is None:
        return None
    return fwd, bwd, max(need(fwd, False), need(bwd, True))


def grouped_experts_applicable(h, f, block, dtype, gated, tokens) -> bool:
    """Whether the grouped kernels take experts ``h -> f -> h`` over blocks
    of ``block`` slots of ``tokens`` rows: a compute dtype the MXU takes, a
    block of whole sublane tiles of it, ``h`` of whole lanes (a row's
    width), ``f`` of whole lanes or, as the array's full width, whole
    sublanes, a tiling of ``f`` whose step fits the VMEM a v5e may be asked
    for, and a tile of the rows' width that the row kernels can hold."""
    dtype = np.dtype(dtype) if str(dtype) != "bfloat16" else None
    itemsize = 2 if dtype is None else dtype.itemsize
    if dtype is not None and dtype != np.dtype("float32"):
        return False
    sublanes = 32 // itemsize
    if block % sublanes or block % _ROW_UNROLL or h % 128 or f % sublanes \
            or not pallas_available():
        return False
    if _row_tile(_packed_width(h, itemsize), tokens, 4) is None \
            or _row_tile(h, tokens, 4 + 2 * itemsize) is None:
        return False
    return _experts_tiles(h, f, block, gated, itemsize) is not None


def _experts_params():
    """Two sequential grid axes, and the VMEM the rules above reckon by."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_EXPERTS_VMEM)


def _gather_rows(table, rows, nblocks, block):
    """``table [S, w]`` (32-bit) -> ``[L, w]``: slot ``i`` of a filled block
    holds ``table[rows[i]]``; the other blocks hold nothing defined."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, w = table.shape
    nbmax = rows.shape[0] // block
    wt = _row_tile(w, s, 4)

    def kernel(rows_ref, n_ref, table_hbm, o_ref, held, sem):
        t, b = pl.program_id(0), pl.program_id(1)

        @pl.when((b == 0) & (n_ref[0] > 0))
        def _():
            at = table_hbm if wt == w else table_hbm.at[
                :, pl.ds(pl.multiple_of(t * wt, 128), wt)]
            copy = pltpu.make_async_copy(at, held, sem)
            copy.start()
            copy.wait()

        @pl.when(b < n_ref[0])
        def _():
            def body(k, carry):
                for u in range(_ROW_UNROLL):
                    i = k * _ROW_UNROLL + u
                    r = rows_ref[b * block + i]
                    o_ref[pl.ds(i, 1), :] = held[pl.ds(r, 1), :]
                return carry

            jax.lax.fori_loop(0, block // _ROW_UNROLL, body, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(w // wt, nbmax),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, wt),
                               lambda t, b, r, n: (_filled(b, n), t)),
        scratch_shapes=[pltpu.VMEM((s, wt), table.dtype),
                        pltpu.SemaphoreType.DMA(())])
    return pallas_call(
        kernel, rows, nblocks, table, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], w), table.dtype),
        compiler_params=_experts_params(),
        name="grouped_experts_gather")


def _scatter_rows(vals, rows, nblocks, block, s, dtype):
    """``vals [L, h]`` float32 -> ``[S, h]`` in ``dtype``: zeros and, for
    every slot ``i`` of a filled block, ``vals[i]`` added onto row
    ``rows[i]``; the sums float32, rounded once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h = vals.shape[1]
    nbmax = rows.shape[0] // block
    ht = _row_tile(h, s, 4 + 2 * np.dtype(dtype).itemsize)

    def kernel(rows_ref, n_ref, v_ref, o_ref, acc):
        b = pl.program_id(1)

        @pl.when(b == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(b < n_ref[0])
        def _():
            def body(k, carry):
                for u in range(_ROW_UNROLL):
                    i = k * _ROW_UNROLL + u
                    r = rows_ref[b * block + i]
                    acc[pl.ds(r, 1), :] += v_ref[pl.ds(i, 1), :]
                return carry

            jax.lax.fori_loop(0, block // _ROW_UNROLL, body, 0)

        @pl.when(b == nbmax - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(h // ht, nbmax),
        in_specs=[pl.BlockSpec((block, ht),
                               lambda t, b, r, n: (_filled(b, n), t))],
        out_specs=pl.BlockSpec((s, ht), lambda t, b, r, n: (0, t)),
        scratch_shapes=[pltpu.VMEM((s, ht), jnp.float32)])
    return pallas_call(
        kernel, rows, nblocks, vals, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h), dtype),
        compiler_params=_experts_params(),
        name="grouped_experts_scatter")


def _experts_tools(ft, f, up_t):
    """What the two product kernels' bodies share: the products, a skipped
    step's tile, a hanging tile's mask, a block's inner products (``up_t``:
    the gate and up weights ride ``[f, h]``, see :func:`_experts_layout`)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    nf = -(-f // ft)

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=f32)

    nn = lambda a, b: dot(a, b, ((1,), (0,)))      # noqa: E731
    nt = lambda a, b: dot(a, b, ((1,), (1,)))      # noqa: E731
    tn = lambda a, b: dot(a, b, ((0,), (0,)))      # noqa: E731

    def tile_of(b, j, n_ref):
        # a skipped step keeps the last tile fetched
        return jnp.where(b < n_ref[0], j, nf - 1)

    def in_f(tile, j, axis):
        """The weight tile with what hangs over ``f`` zeroed."""
        if f % ft == 0:
            return tile
        at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, axis)
        return jnp.where(at < f - j * ft, tile, jnp.zeros_like(tile))

    into_f = nt if up_t else nn

    def forward_block(xb, ups, gated):
        """The float32 inner products of a block and its activation: ``(g,
        u, sig, a)`` gated, ``(None, relu, None, a)`` else; ``a`` float32."""
        if gated:
            g, u = into_f(xb, ups[0]), into_f(xb, ups[1])
            sig = jax.nn.sigmoid(g)
            return g, u, sig, g * sig * u
        relu = jnp.maximum(into_f(xb, ups[0]), 0.0)
        return None, relu, None, relu * relu

    def weight_specs(h, nw, at):
        """A block spec a weight; ``at(*grid and prefetched)`` gives the
        expert and the tile of ``f``."""
        from jax.experimental import pallas as pl

        up = pl.BlockSpec((None, h, ft), lambda *a: (at(*a)[0], 0, at(*a)[1]))
        down = pl.BlockSpec((None, ft, h),
                            lambda *a: (at(*a)[0], at(*a)[1], 0))
        return [down if up_t else up] * (nw - 1) + [down]

    return nn, nt, tn, tile_of, in_f, forward_block, weight_specs


def _experts_products_forward(xg, ws, weights, block_expert, nblocks, *,
                              gated, up_t):
    """The experts' weighted results by slot: ``xg [L, w]`` the blocks'
    rows as :func:`_gather_rows` leaves them, ``ws`` the stacked weights
    (``gate`` first where ``gated``, ``up [held, h, f]``, ``down [held, f,
    h]``), ``weights [L]`` by slot, ``block_expert [L / block]``,
    ``nblocks [1]``. Returns float32 ``[L, h]``, defined in the filled
    blocks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    cd = ws[0].dtype
    _, f, h = ws[-1].shape
    length, w = xg.shape
    nbmax = block_expert.shape[0]
    block = length // nbmax
    nw = len(ws)
    ft = _experts_tiles(h, f, block, gated, cd.itemsize)[0]
    nf = -(-f // ft)
    nn, _, _, tile_of, in_f, forward_block, weight_specs = _experts_tools(
        ft, f, up_t)

    def kernel(be_ref, n_ref, x_ref, w_ref, *rest):
        w_refs, o_ref, xb_ref = rest[:nw], rest[nw], rest[nw + 1]
        b, j = pl.program_id(0), pl.program_id(1)

        @pl.when(b < n_ref[0])
        def _():
            @pl.when(j == 0)
            def _():
                xb_ref[...] = _unpack_rows(x_ref[...], h, cd)

            ups = [in_f(r[...], j, 0 if up_t else 1) for r in w_refs[:-1]]
            *_, a = forward_block(xb_ref[...], ups, gated)
            o = nn(a.astype(cd), in_f(w_refs[-1][...], j, 0))
            if nf == 1:
                o_ref[...] = o * w_ref[...]
            else:
                @pl.when(j == 0)
                def _():
                    o_ref[...] = o

                @pl.when(j > 0)
                def _():
                    o_ref[...] += o

                @pl.when(j == nf - 1)
                def _():
                    o_ref[...] *= w_ref[...]

    def at_block(b, j, be, n):
        return _filled(b, n), 0

    def tile(b, j, be, n):
        return be[_filled(b, n)], tile_of(b, j, n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nbmax, nf),
        in_specs=[pl.BlockSpec((block, w), at_block),
                  pl.BlockSpec((block, 1), at_block)]
        + weight_specs(h, nw, tile),
        out_specs=pl.BlockSpec((block, h), at_block),
        scratch_shapes=[pltpu.VMEM((block, h), cd)])
    return pallas_call(
        kernel, block_expert, nblocks, xg, weights.reshape(-1, 1), *ws,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((length, h), f32),
        compiler_params=_experts_params(),
        name="grouped_experts_forward")


def _experts_products_backward(xg, dyg, ws, weights, block_expert, nblocks,
                               *, gated, up_t):
    """The mirror of :func:`_experts_products_forward`: ``dyg [L, w]`` the
    blocks' rows of the result's gradient -> ``(dx by slot [L, h] float32,
    the weights' gradients in the weights' shapes and dtypes, dwt [L]
    float32 by slot)``, each block's inner products formed again."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    cd = ws[0].dtype
    held, f, h = ws[-1].shape
    length, w = xg.shape
    nbmax = block_expert.shape[0]
    block = length // nbmax
    nw = len(ws)
    ft = _experts_tiles(h, f, block, gated, cd.itemsize)[1]
    nf = -(-f // ft)
    nn, nt, tn, tile_of, in_f, forward_block, weight_specs = _experts_tools(
        ft, f, up_t)
    # the gradient of a gate or up weight, and the rows' through it
    into_w = (lambda xb, d: tn(d, xb)) if up_t else tn
    back = nn if up_t else nt

    def kernel(be_ref, n_ref, idle_ref, x_ref, dy_ref, w_ref, *rest):
        w_refs = rest[:nw]
        dx_ref, dwt_ref = rest[nw], rest[nw + 1]
        dw_refs = rest[nw + 2:2 * nw + 2]
        xb_ref, dyb_ref = rest[2 * nw + 2:2 * nw + 4]
        accs = rest[2 * nw + 4:]
        b, j = pl.program_id(0), pl.program_id(1)
        n = n_ref[0]

        # after the blocks, a step an expert and tile: an expert that drew
        # no row was never visited, and gets its zeros here
        @pl.when((b >= nbmax)
                 & (idle_ref[jnp.maximum(b - nbmax, 0)] == b - nbmax))
        def _():
            for ref in dw_refs:
                ref[...] = jnp.zeros_like(ref)

        @pl.when(b < n)
        def _():
            e = be_ref[b]
            first = (b == 0) | (be_ref[jnp.maximum(b - 1, 0)] != e)

            @pl.when(j == 0)
            def _():
                xb_ref[...] = _unpack_rows(x_ref[...], h, cd)
                dyb_ref[...] = _unpack_rows(dy_ref[...], h, cd)

            def accumulate(acc, ref, product):
                @pl.when(first)
                def _():
                    acc[j] = product

                @pl.when(jnp.logical_not(first))
                def _():
                    acc[j] += product

                ref[...] = acc[j].astype(ref.dtype)

            def add_over_tiles(ref, part):
                @pl.when(j == 0)
                def _():
                    ref[...] = part

                @pl.when(j > 0)
                def _():
                    ref[...] += part

            wgt = w_ref[...]
            xb, dyb = xb_ref[...], dyb_ref[...]
            ups = [in_f(r[...], j, 0 if up_t else 1) for r in w_refs[:-1]]
            down = in_f(w_refs[-1][...], j, 0)
            g, u, sig, a = forward_block(xb, ups, gated)
            a = a.astype(cd)
            # d(result)/d(a), before the slot's weight
            da = nt(dyb, down)
            add_over_tiles(dwt_ref, jnp.sum(a.astype(f32) * da, axis=1,
                                            keepdims=True))
            accumulate(accs[-1], dw_refs[-1],
                       tn(a, (dyb.astype(f32) * wgt).astype(cd)))
            daw = da * wgt
            if gated:
                # silu(g) = g sig(g); its slope sig (1 + g (1 - sig))
                dg = (daw * u * sig * (1.0 + g * (1.0 - sig))).astype(cd)
                du = (daw * g * sig).astype(cd)
                accumulate(accs[0], dw_refs[0], into_w(xb, dg))
                accumulate(accs[1], dw_refs[1], into_w(xb, du))
                dxb = back(dg, ups[0]) + back(du, ups[1])
            else:
                dh = (daw * 2.0 * u).astype(cd)
                accumulate(accs[0], dw_refs[0], into_w(xb, dh))
                dxb = back(dh, ups[0])
            add_over_tiles(dx_ref, dxb)

    def at_block(b, j, be, n, idle):
        return _filled(b, n), 0

    def tile(b, j, be, n, idle):
        return be[_filled(b, n)], tile_of(b, j, n)

    def grad_tile(b, j, be, n, idle):
        # the blocks' steps follow the weights' tiles; a step of the sweep
        # after them moves on only to an expert that drew no row
        e = jnp.clip(b - nbmax, 0, held - 1)
        to = jnp.where(idle[e] >= 0, idle[e], be[_filled(b, n)])
        return (jnp.where(b < nbmax, be[_filled(b, n)], to),
                jnp.where((b < nbmax) | (idle[e] != e), tile_of(b, j, n), j))

    # the last expert at or before each that drew no row, -1 before the first
    drew = jnp.zeros((held,), jnp.int32).at[block_expert].add(
        (jnp.arange(nbmax) < nblocks[0]).astype(jnp.int32))
    idle = jax.lax.cummax(jnp.where(drew == 0, jnp.arange(held), -1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(nbmax + held, nf),
        in_specs=[pl.BlockSpec((block, w), at_block)] * 2
        + [pl.BlockSpec((block, 1), at_block)] + weight_specs(h, nw, tile),
        out_specs=[pl.BlockSpec((block, h), at_block),
                   pl.BlockSpec((block, 1), at_block)]
        + weight_specs(h, nw, grad_tile),
        scratch_shapes=[pltpu.VMEM((block, h), cd)] * 2
        + [pltpu.VMEM((nf, ft, h) if up_t else (nf, h, ft), f32)] * (nw - 1)
        + [pltpu.VMEM((nf, ft, h), f32)])
    dx, dwt, *dws = pallas_call(
        kernel, block_expert, nblocks, idle.astype(jnp.int32), xg, dyg,
        weights.reshape(-1, 1), *ws, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((length, h), f32),
                   jax.ShapeDtypeStruct((length, 1), f32)]
        + [jax.ShapeDtypeStruct(w_.shape, w_.dtype) for w_ in ws],
        compiler_params=_experts_params(),
        name="grouped_experts_backward")
    return dx, tuple(dws), dwt[:, 0]


def _experts_layout(ws):
    """``(the weights as the product kernels take them, up_t)``: where ``f``
    is not whole lanes (1856) XLA lays a ``[held, h, f]`` array out with
    ``h`` innermost, and a kernel that asked for it row-major would have it
    copied in, and its gradient copied back, every pass (80 MB each way in
    the Nemotron cell; 12 ms a step, my chip runs, PR 33). So there the gate
    and up weights ride TRANSPOSED, ``[held, f, h]``, which is that layout
    under another name, and the kernels' products take them so."""
    import jax.numpy as jnp

    up_t = ws[-1].shape[1] % 128 != 0
    if up_t:
        ws = tuple(jnp.swapaxes(w, 1, 2) for w in ws[:-1]) + (ws[-1],)
    return tuple(ws), up_t


def _experts_forward(x, ws, rows, weights, block_expert, nblocks, *, gated):
    """``y [S, h]`` in ``x``'s dtype: the grouped experts' weighted results
    summed onto their rows (``moe.grouped_experts`` states the sum; the
    layout is ``moe.plan``'s, ``nblocks [1]``)."""
    block = rows.shape[0] // block_expert.shape[0]
    ws, up_t = _experts_layout(ws)
    xg = _gather_rows(_pack_rows(x), rows, nblocks, block)
    og = _experts_products_forward(xg, ws, weights, block_expert, nblocks,
                                   gated=gated, up_t=up_t)
    return _scatter_rows(og, rows, nblocks, block, x.shape[0], x.dtype)


def _experts_backward(x, ws, rows, weights, block_expert, nblocks, dy, *,
                      gated):
    """``dy [S, h]`` -> ``(dx [S, h]`` in ``x``'s dtype, the weights'
    gradients, ``dwt [L]`` float32 by slot)``."""
    import jax.numpy as jnp

    block = rows.shape[0] // block_expert.shape[0]
    ws, up_t = _experts_layout(ws)
    xg = _gather_rows(_pack_rows(x), rows, nblocks, block)
    dyg = _gather_rows(_pack_rows(dy.astype(x.dtype)), rows, nblocks, block)
    dxg, dws, dwt = _experts_products_backward(
        xg, dyg, ws, weights, block_expert, nblocks, gated=gated, up_t=up_t)
    if up_t:
        dws = tuple(jnp.swapaxes(d, 1, 2) for d in dws[:-1]) + (dws[-1],)
    dx = _scatter_rows(dxg, rows, nblocks, block, x.shape[0], x.dtype)
    return dx, dws, dwt


@functools.lru_cache(None)
def _experts_jitted():
    """The two passes' callers as ``jax.jit`` functions, made once, as
    :func:`_ssd_jitted` and for its reason."""
    import jax

    return (jax.jit(_experts_forward, static_argnames=("gated",)),
            jax.jit(_experts_backward, static_argnames=("gated",)))


def grouped_experts_forward(*args, **static):
    """:func:`_experts_forward` through its shared ``jax.jit``."""
    return _experts_jitted()[0](*args, **static)


def grouped_experts_backward(*args, **static):
    """:func:`_experts_backward` through its shared ``jax.jit``."""
    return _experts_jitted()[1](*args, **static)


# ---------------------------------------------------------------------------
# Attention's operands between the graph's layout and the kernel's
# (``ops/attention.py`` ``CausalAttention``, the ``pallas_splash`` path)
# ---------------------------------------------------------------------------
#
# The graph holds ``[B*T, H*D]`` rows, the splash kernels take ``[B, H, T,
# D]``. One pass an operand and direction: a grid step reads a ``[rows, D]``
# tile of one head where it lies and writes it where it belongs (the
# relayout is the two index maps, nothing is transposed in VMEM), and on the
# way rotary positions turn the LAST columns of the head and ``scale``
# multiplies all of it: float32 from the operand's dtype, ONE rounding to
# it. ``y = x * C + partner(x) * S`` over the whole lanes that hold the
# turned columns (``tables = (C, S) [T, w]``: 1 and 0 on lanes that pass
# through, which are also copied unchanged; the sign of the rotation is in
# ``S``; a lane's partner is half the turned width away: two rotations of
# the lanes and a select). The pass back is its transpose: ``scale`` first,
# then the rotation by the negative angle (``x * C - partner(x) * S``). No
# accumulator; heads are the innermost grid axis, so a tile of the tables
# is fetched once a block of positions.

# rows a grid step: a 168 MB pass took 0.37 ms at 512, 0.32 at 1,024 and
# 0.31 at 2,048 (my chip runs, PR 37): the steps' own time, 0.35 us each
_RELAYOUT_ROWS = 1024


def _turned(x, tables, half, scale, back, dtype, period=0):
    """A ``[rows, D]`` tile turned and scaled (the section's comment); with
    ``period`` a tile of heads ``period`` columns wide side by side, each
    turned on its own."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    x = x.astype(jnp.float32)
    if back and scale != 1.0:
        x = x * scale
    if half:
        c, s = tables
        w = c.shape[-1]
        start = x.shape[-1] - w
        r = x[:, start:]
        lane = lax.broadcasted_iota(jnp.int32, r.shape, 1)
        if period:
            lane = lane % period
        own = period or w
        ps = jnp.where(lane < own - half, pltpu.roll(r, w - half, 1),
                       pltpu.roll(r, half, 1)) * s
        y = jnp.where(lane < own - 2 * half, r,
                      r * c - ps if back else r * c + ps)
        x = jnp.concatenate([x[:, :start], y], axis=1) if start else y
    if not back and scale != 1.0:
        x = x * scale
    return x.astype(dtype)


def attention_relayout(x, tables=(), *, batch, heads, half=0, scale=1.0,
                       back=False):
    """``x [B*T, H*D]`` -> ``[B, H, T, D]``, the last ``2 * half`` columns
    of every head turned by ``tables`` and all of it times ``scale``; with
    ``back`` its transpose, ``[B, H, T, D]`` -> ``[B*T, H*D]``: the way of
    the kernel's result and of every cotangent (the section's comment).

    A head narrower than the 128 lanes (64 columns: ``128 % D == 0``) has
    no tile of its own among the rows, so a grid step moves the ``128 / D``
    heads that share one: the ``[rows, 128]`` tile is turned as a whole
    (``tables`` tiled to 128 columns, a lane's partner half the turned
    width away WITHIN its head) and cut into the heads' ``[rows, D]``
    tiles, or put together from them on the way back."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if back:
        t, d = x.shape[2:]
    else:
        t, d = x.shape[0] // batch, x.shape[1] // heads
    bt = next((n for n in range(min(_RELAYOUT_ROWS, t), 0, -128)
               if t % n == 0), t)
    nt = t // bt
    pack = TILE_N // d if d < TILE_N else 1     # heads a 128-lane tile

    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        cut = pack > 1 and not back     # the turned tile goes out by head
        if pack > 1 and back:
            tile = jnp.concatenate([x_ref[j].astype(jnp.float32)
                                    for j in range(pack)], axis=1)
        else:
            tile = x_ref[...]
        tile = _turned(tile, [r[...] for r in refs[:-1]], half, scale, back,
                       jnp.float32 if cut else o_ref.dtype,
                       d if pack > 1 else 0)
        if cut:
            for j in range(pack):
                o_ref[j] = tile[:, j * d:(j + 1) * d].astype(o_ref.dtype)
        else:
            o_ref[...] = tile

    rows = pl.BlockSpec((bt, pack * d), lambda b, i, h: (b * nt + i, h))
    by_head = pl.BlockSpec((None, pack if pack > 1 else None, bt, d),
                           lambda b, i, h: (b, h, i, 0))
    table = [pl.BlockSpec((bt, c.shape[1]), lambda b, i, h: (i, 0))
             for c in tables]
    shape = (batch * t, heads * d) if back else (batch, heads, t, d)
    return pallas_call(
        kernel, x, *tables, grid=(batch, nt, heads // pack),
        in_specs=[by_head if back else rows] + table,
        out_specs=rows if back else by_head,
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="attention_rows_from_heads" if back
        else "attention_heads_from_rows")


# ---------------------------------------------------------------------------
# Causal attention's backward pass as one kernel
# (``ops/attention.py`` ``attend_splash``, the ``pallas_splash`` path)
# ---------------------------------------------------------------------------
#
# The forward kernel (below) leaves the output and the float32 log-sum-exp
# a (head, position). JAX's backward pass is two kernels,
# ``dq`` (three products) and ``dkv`` (four), and each forms the scores, the
# ``exp`` and ``do v^T`` for itself. Here one grid step is one (query block,
# key block) pair of the causal half, of one query head of one key/value
# head of one sequence, and forms them ONCE for all three gradients, five
# products where seven ran:
#
#   s^T = k q^T                      [bkv, bq]   (keys by rows: ``lse`` and
#   p^T = exp(s^T - lse)                          ``di`` are rows ``[1, bq]``
#   dv += p^T do                                  and no score is transposed
#   dp^T = v do^T                                 for ``dv`` and ``dk``)
#   ds^T = p^T (dp^T - di)
#   dk += ds^T q
#   dq += ds k                       (the one transposed operand a pair)
#
# Query blocks are the outer loop of a head and key blocks the inner: only
# the pairs at or under the diagonal are grid steps, from a table that is
# prefetched into SMEM (a sequence of 16 x 16 blocks has 136), and only the
# blocks ON the diagonal pay for a mask. ``dq`` of the current query block
# is a float32 scratch across its key blocks; ``dk`` and ``dv`` of the WHOLE
# key/value head are float32 scratch ``[T, D]`` / ``[T, Dv]`` across the
# head's steps, the group's query heads adding into the same two (16 MiB at
# 8,192 positions of 256 + 256 columns: above Mosaic's default, hence
# ``vmem_limit_bytes``; a v5e core has 128 MiB).
#
# Precision: scores, ``exp``, ``dp``, ``ds`` and all three accumulators are
# float32; the five products take operands in the compute dtype, ``p`` and
# ``ds`` rounded to it where JAX's two kernels round them; ``dq``, ``dk``,
# ``dv`` are rounded to the compute dtype once, when complete. No ``[T, T]``
# array, no float32 copy of an operand and no partial sum reaches HBM.

ATTENTION_BACKWARD_BLOCK = 512
_ATTENTION_VMEM = 100 << 20     # asked of the compiler; a v5e has 128 MiB
_ATTENTION_MASKED = -0.7 * float(np.finfo(np.float32).max)   # as splash's


def _attention_backward_vmem(t, d, dv, itemsize, block):
    """Bytes of VMEM the kernel holds at once: the head's two float32
    accumulators, their results in the compute dtype (an output block is
    double-buffered), the pair's operands, and its float32 scores."""
    wide = _lanes(d) + _lanes(dv)
    return (t * wide * (4 + 2 * itemsize)
            + block * (2 * _lanes(d) + _lanes(dv)) * (4 * itemsize + 4)
            + 8 * block * block * 4)


def attention_applicable(t, d, dv, dtype) -> bool:
    """Whether this repo's two attention kernels (the forward pass below,
    the one-kernel backward pass) take key/value heads of ``t`` positions of
    ``d`` key and ``dv`` value columns, whatever the size of a head's group
    of query heads: ONE rule, since ``attend_splash`` joins the two under
    one ``custom_vjp`` and the backward kernel reads the forward kernel's
    rows of log-sum-exp. The splash path's own shapes (heads of whole lanes
    or of 64 columns, whole blocks of whole lane tiles), a compute dtype the
    MXU takes, and what each kernel holds resident (the backward pass a
    head's float32 accumulators, the forward pass its keys and values: the
    smaller of the two while the two blocks are one size) within the VMEM a
    v5e may be asked for, 8 MiB left to Mosaic's own."""
    name = "bfloat16" if str(dtype) == "bfloat16" else np.dtype(dtype).name
    if name not in ("bfloat16", "float32") or not pallas_available():
        return False
    if any(w % 128 and w != 64 for w in (d, dv)):
        return False
    itemsize = 2 if name == "bfloat16" else 4
    backward, forward = (min(block, t) for block in (
        ATTENTION_BACKWARD_BLOCK, ATTENTION_FORWARD_BLOCK))
    if any(t % block or block % 128 for block in (backward, forward)):
        return False
    return max(_attention_backward_vmem(t, d, dv, itemsize, backward),
               _attention_forward_vmem(1, t, d, dv, itemsize, forward)
               ) <= _ATTENTION_VMEM - (8 << 20)


def _blocks_behind(window, block):
    """Key blocks before a query block's own that a window of ``window``
    keys reaches: ``ceil((window - 1) / block)``."""
    return -(-(window - 1) // block)


def _band(window, block):
    """``(behind, edge_from)``: the key blocks a query block reads before its
    own, and the nearest of them that the band's lower edge crosses
    (``behind + 1``: none); 0, 0 without a window."""
    if not window:
        return 0, 0
    return (_blocks_behind(window, block),
            max(1, -(-(window - block + 1) // block)))


@functools.lru_cache(None)
def attention_block_pairs(blocks, window=0, block=ATTENTION_BACKWARD_BLOCK):
    """The (query block, key block) pairs of ``blocks`` x ``blocks`` that
    hold a score: those at or under the diagonal, or with ``window`` (a
    position reads the last ``window`` keys, its own among them) the band's,
    ``qi - ceil((window - 1) / block) <= ki <= qi``. A query block's pairs
    together and in key order, the one ON the diagonal last: ``(q, k)
    [pairs]`` int32."""
    qs, ks = np.tril_indices(blocks)
    if window:
        band = qs - ks <= _blocks_behind(window, block)
        qs, ks = qs[band], ks[band]
    return qs.astype(np.int32), ks.astype(np.int32)


def attention_backward(q, k, v, do, lse, di, window=0):
    """``dq, dk, dv`` of causal attention ``o = softmax(q k^T) v`` from the
    output's cotangent: ``q [B, Hkv, G, T, D]`` (already scaled), ``k [B,
    Hkv, T, D]``, ``v [B, Hkv, T, Dv]``, ``do [B, Hkv, G, T, Dv]``, the
    forward kernel's float32 log-sum-exp ``lse`` and ``di = sum(o * do)``
    ``[B, Hkv, G, T]`` (the section's comment). ``window`` (below ``T``): a
    position reads the last ``window`` keys, and the grid is the band's
    pairs of blocks."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, group, t, d = q.shape
    dv_ = v.shape[-1]
    cd = q.dtype
    block = min(ATTENTION_BACKWARD_BLOCK, t)
    pairs = attention_block_pairs(t // block, window, block)
    npairs = len(pairs[0])
    behind, edge_from = _band(window, block)
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))       # x y^T

    def kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        g, step = pl.program_id(2), pl.program_id(3)
        qi, ki = qi_ref[step], ki_ref[step]
        rows = pl.ds(pl.multiple_of(ki * block, block), block)

        @pl.when((g == 0) & (step == 0))
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        # a query block's first pair
        @pl.when(ki == jnp.maximum(qi - behind, 0) if window else ki == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        def pair(diagonal, edge=False):
            qb, kb, vb, dob = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
            st = lax.dot_general(kb, qb, nt, preferred_element_type=f32)
            if diagonal:    # the same block of positions both ways
                st = jnp.where(
                    lax.broadcasted_iota(jnp.int32, st.shape, 0)
                    <= lax.broadcasted_iota(jnp.int32, st.shape, 1),
                    st, _ATTENTION_MASKED)
            if edge:        # the band's lower edge: key > query - window
                st = jnp.where(
                    lax.broadcasted_iota(jnp.int32, st.shape, 1)
                    - lax.broadcasted_iota(jnp.int32, st.shape, 0)
                    < window - (qi - ki) * block, st, _ATTENTION_MASKED)
            pt = jnp.exp(st - lse_ref[...])
            dv_acc[rows, :] += jnp.dot(pt.astype(cd), dob,
                                       preferred_element_type=f32)
            dpt = lax.dot_general(vb, dob, nt, preferred_element_type=f32)
            dst = (dpt - di_ref[...]) * pt
            dk_acc[rows, :] += jnp.dot(dst.astype(cd), qb,
                                       preferred_element_type=f32)
            # transposed in float32, then rounded: the form JAX's own fused
            # ``dkv`` kernel runs on the chip
            dq_acc[...] += jnp.dot(dst.T.astype(cd), kb,
                                   preferred_element_type=f32)

        if not window:
            @pl.when(ki < qi)
            def _():
                pair(False)
        else:
            # only the pairs the edge crosses pay for its mask
            if edge_from > 1:
                @pl.when((ki < qi) & (qi - ki < edge_from))
                def _():
                    pair(False)

            if edge_from <= behind:
                @pl.when(qi - ki >= edge_from)
                def _():
                    pair(False, edge=True)

        @pl.when(ki == qi)
        def _():
            pair(True, edge=0 < window < block)
            dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

        @pl.when((g == group - 1) & (step == npairs - 1))
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    def by_query(width):
        return pl.BlockSpec((None, None, None, block, width),
                            lambda b, h, g, p, qi, ki: (b, h, g, qi[p], 0))

    def by_key(width):
        return pl.BlockSpec((None, None, block, width),
                            lambda b, h, g, p, qi, ki: (b, h, ki[p], 0))

    def whole(width):
        return pl.BlockSpec((None, None, t, width),
                            lambda b, h, g, p, qi, ki: (b, h, 0, 0))

    # one number a position: a row ``[1, block]`` of ``[.., 1, T]``
    row = pl.BlockSpec((None, None, None, 1, block),
                       lambda b, h, g, p, qi, ki: (b, h, g, 0, qi[p]))
    return pallas_call(
        kernel, *pairs, q, k, v, do, lse[:, :, :, None], di[:, :, :, None],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, group, npairs),
            in_specs=[by_query(d), by_key(d), by_key(dv_), by_query(dv_),
                      row, row],
            out_specs=[by_query(d), whole(d), whole(dv_)],
            scratch_shapes=[pltpu.VMEM((block, d), f32),
                            pltpu.VMEM((t, d), f32),
                            pltpu.VMEM((t, dv_), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, cd),
                   jax.ShapeDtypeStruct(k.shape, cd),
                   jax.ShapeDtypeStruct(v.shape, cd)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_ATTENTION_VMEM),
        name="window_attention_backward" if window
        else "causal_attention_backward")


# ---------------------------------------------------------------------------
# Causal attention's forward pass as one kernel
# (``ops/attention.py`` ``attend_splash``, the ``pallas_splash`` path)
# ---------------------------------------------------------------------------
#
# The online softmax over the (query block, key block) pairs the backward
# kernel's table lists, but not as a grid over that table: a grid step
# is one QUERY block of one key/value head of one sequence, for ``heads``
# query heads of its group; the head's keys and values are whole in VMEM
# (fetched once a head: their block does not move with the query block) and
# the key blocks at or under the diagonal, or inside the band, are a loop IN
# the step: the blocks the band's lower edge crosses (their distance from
# the diagonal is static, one ``pl.when`` a distance), the unmasked ones
# between (a ``fori_loop`` over a dynamic range), the one ON the diagonal.
# The step opens by clearing its statistics and closes by writing ``out``
# and the log-sum-exp. As in the backward kernel the scores are formed by key
# rows:
#
#   s^T = k q^T                       [bkv, bq]
#   m' = max(m, max over keys s^T)    [1, bq]   (a position's running max,
#   p^T = exp(s^T - m')                          sum and log-sum-exp are ONE
#   l = exp(m - m') l + sum over keys p^T        number each: rows, where
#   o^T = exp(m - m') o^T + v^T p^T   [Dv, bq]   splash keeps 128 lanes, and
#                                                the reductions run down
#                                                sublanes, not along lanes)
#
# ``v^T`` is the pair's value block transposed once in VMEM for the step's
# heads; ``o^T`` is transposed back once a query block, when ``out = (o^T /
# l)^T`` is written. One head's pair is a CHAIN: product, maximum, ``exp``,
# product, each waiting on the one before, so the step runs its heads'
# chains unrolled side by side for the scheduler to interleave (one head's
# MXU work under another's vector work). Under a window a chain is a column
# PART of the query block (a position's statistics do not meet another's),
# and a part of a masked pair reads only the key rows its queries can see,
# in whole tiles of 128 rows: above the diagonal and below the band's edge
# nothing is computed; the ``iota`` mask cuts the rest.
#
# Precision: scores, maxima, ``exp``, sums, the rescale and the ``[Dv, bq]``
# accumulator are float32; the two products take operands in the compute
# dtype (``v`` as it lies, ``p`` rounded ONCE) and accumulate in float32;
# ``out`` is rounded once, ``lse = m + log(l)`` leaves as float32 rows ``[..,
# 1, T]``, the form the backward kernel reads.

ATTENTION_FORWARD_BLOCK = 512


def _attention_forward_vmem(heads, t, d, dv, itemsize, block):
    """Bytes of VMEM a step of ``heads`` query heads holds at once: the
    head's keys and values, the heads' query and output blocks (each
    double-buffered), their float32 accumulators, and the chains' float32
    scores."""
    wide = _lanes(d) + _lanes(dv)
    return (2 * itemsize * wide * (t + heads * block)
            + 4 * heads * block * _lanes(dv) + 6 * block * block * 4)


def _attention_forward_heads(group, t, d, dv, itemsize, block):
    """Query heads of a group a grid step runs side by side: the most that
    divide the group, up to eight, within the VMEM asked for."""
    return max(n for n in range(1, min(group, 8) + 1) if group % n == 0 and (
        n == 1 or _attention_forward_vmem(n, t, d, dv, itemsize, block)
        <= _ATTENTION_VMEM - (8 << 20)))


def _attention_forward(q, k, v, *, window=0):
    """``out [B, Hkv, G, T, Dv]`` and the float32 log-sum-exp ``[B, Hkv, G,
    T]`` of causal attention ``softmax(q k^T) v``: ``q [B, Hkv, G, T, D]``
    (already scaled), ``k [B, Hkv, T, D]``, ``v [B, Hkv, T, Dv]`` (the
    section's comment). ``window`` (below ``T``): a position reads the last
    ``window`` keys, and a query block's loop is the band's key blocks."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, group, t, d = q.shape
    dv_ = v.shape[-1]
    cd = q.dtype
    block = min(ATTENTION_FORWARD_BLOCK, t)
    behind, edge_from = _band(window, block)
    gs = _attention_forward_heads(group, t, d, dv_, cd.itemsize, block)
    # column parts of a query block: under a window most pairs are masked
    # ones, and a part skips the key rows none of its queries sees (heads of
    # whole lane tiles: at 64 columns two parts lost, docs/pallas.md)
    parts = 2 if window and min(d, dv_, block // 2) >= TILE_N else 1
    sub = block // parts
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))       # x y^T

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_acc, l_acc, o_acc):
        qi = pl.program_id(3)
        m_acc[...] = jnp.full_like(m_acc, _ATTENTION_MASKED)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

        def pair(ki, behind_by=None):
            """Key block ``ki`` against the step's chains; ``behind_by``:
            its distance from the diagonal where it is masked (0: ON it),
            static."""
            rows = pl.ds(pl.multiple_of(ki * block, block), block)
            kb, vt = k_ref[rows, :], v_ref[rows, :].T
            diagonal = behind_by == 0
            # a query at column c of the block sees the key rows above
            # ``c - reach``: the band's lower edge, where it crosses the pair
            reach = window - behind_by * block \
                if window and behind_by is not None else None
            if reach is not None and reach >= block:
                reach = None
            for g in range(gs):
                for part in range(parts):
                    cols = slice(part * sub, (part + 1) * sub)
                    # the key rows this part's queries can see, in whole
                    # lane tiles of ``v^T``
                    last = (part + 1) * sub if diagonal else block
                    first = 0 if reach is None else max(
                        0, (part * sub - reach + 1) // TILE_N * TILE_N)
                    if first >= last:
                        continue
                    s = lax.dot_general(kb[first:last], q_ref[g, cols, :],
                                        nt, preferred_element_type=f32)
                    key = lax.broadcasted_iota(jnp.int32, s.shape, 0) + first
                    query = lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                        + part * sub
                    if diagonal:
                        s = jnp.where(key <= query, s, _ATTENTION_MASKED)
                    if reach is not None:
                        s = jnp.where(query - key < reach, s,
                                      _ATTENTION_MASKED)
                    m_prev = m_acc[g, :, cols]
                    m_next = jnp.maximum(m_prev,
                                         s.max(axis=0, keepdims=True))
                    alpha = jnp.exp(m_prev - m_next)
                    p = jnp.exp(s - m_next)
                    l_acc[g, :, cols] = alpha * l_acc[g, :, cols] \
                        + p.sum(axis=0, keepdims=True)
                    m_acc[g, :, cols] = m_next
                    o_acc[g, :, cols] = alpha * o_acc[g, :, cols] + jnp.dot(
                        vt[:, first:last], p.astype(cd),
                        preferred_element_type=f32)

        # in key order: the pairs the band's edge crosses (their distance is
        # one of at most two), the unmasked ones between, the diagonal's
        for behind_by in range(behind, edge_from - 1, -1) if window else ():
            @pl.when(qi >= behind_by)
            def _(behind_by=behind_by):
                pair(qi - behind_by, behind_by)

        lax.fori_loop(jnp.maximum(qi - edge_from + 1, 0) if window else 0,
                      qi, lambda ki, _: pair(ki), None)
        pair(qi, 0)
        for g in range(gs):
            l = l_acc[g]
            o_ref[g] = (o_acc[g] / l).T.astype(o_ref.dtype)
            lse_ref[g] = m_acc[g] + jnp.log(l)

    def by_query(width):
        return pl.BlockSpec((None, None, gs, block, width),
                            lambda b, h, g, i: (b, h, g, i, 0))

    def whole(width):
        return pl.BlockSpec((None, None, t, width),
                            lambda b, h, g, i: (b, h, 0, 0))

    # one number a position: a row ``[1, block]`` of ``[.., 1, T]``
    row = pl.BlockSpec((None, None, gs, 1, block),
                       lambda b, h, g, i: (b, h, g, 0, i))
    out, lse = pallas_call(
        kernel, q, k, v, grid=(b, hkv, group // gs, t // block),
        in_specs=[by_query(d), whole(d), whole(dv_)],
        out_specs=[by_query(dv_), row],
        scratch_shapes=[pltpu.VMEM((gs, 1, block), f32),
                        pltpu.VMEM((gs, 1, block), f32),
                        pltpu.VMEM((gs, dv_, block), f32)],
        out_shape=[jax.ShapeDtypeStruct((b, hkv, group, t, dv_), cd),
                   jax.ShapeDtypeStruct((b, hkv, group, 1, t), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_ATTENTION_VMEM),
        name="window_attention_forward" if window
        else "causal_attention_forward")
    return out, lse[:, :, :, 0]


@functools.lru_cache(None)
def _attention_jitted():
    """The forward kernel's caller as a ``jax.jit`` function, made once, as
    :func:`_ssd_jitted` and for its reason: a model's attention layers share
    shapes, so a step traces and lowers the kernel once a shape."""
    import jax

    return jax.jit(_attention_forward, static_argnames=("window",))


def attention_forward(q, k, v, window=0):
    """:func:`_attention_forward` through its shared ``jax.jit``."""
    return _attention_jitted()(q, k, v, window=window)
