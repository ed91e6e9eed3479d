"""Grouped matrix products as Pallas kernels: ``moe_gmm`` and ``moe_tgmm``.

The expert layer of a sparse model (``parallel/moe.py: moe_share_ffn``)
multiplies rows sorted by expert with that expert's matrix:
``out[r] = lhs[r] . rhs[group of r]``. ``lax.ragged_dot`` is that product,
and on the chip XLA lowers it to a Mosaic kernel of its own with tiles of
512 x 256 x 128 whatever the shape: at [65536, 2304] x [16, 2304, 896]
that is 9,009 grid steps of 0.17 us of MXU work each, the left operand
read seven times, 32-47 TFLOP/s of 197 (ledger, PR 34). The tiles are
XLA's, by no parameter ``lax.ragged_dot`` takes, so the product lives
here, with tiles sized to the shape by :func:`_plan`:

* ``moe_gmm``: ``[m, k] x [g, k, n] -> [m, n]``, and with the right
  operand read transposed ``[m, n] x [g, k, n]^T -> [m, k]`` (the input's
  cotangent: through the index map and the product's dimensions, the
  weights are never transposed in memory); its store can multiply each
  row by a float32 scale (the routing weight of the down product);
* ``moe_gmm_pair``: the same body over two pairs of operands,
  ``a1 . b1^T + a2 . b2^T`` summed in float32 and written once (the
  input's cotangent through gate and up, whose weights are never
  concatenated in memory);
* ``moe_tgmm``: ``lhs^T [k, m] x [m, n]`` per group ``-> [g, k, n]`` (the
  weights' gradient), accumulated in VMEM over the row tiles of one group:
  in the result's own block where the result is float32, else in a
  float32 scratch block that is rounded into it at the group's last visit.

All walk a list of VISITS, (row tile, group) pairs that come in by scalar
prefetch beside the groups' offsets: a row tile that a group boundary cuts
is visited once per group under a row mask, an empty group once with
nothing in the mask (so ``moe_tgmm`` writes its block as zeros). The list
has the static worst-case length, ``m // tm + g - 1``: the same grid
whatever the routing. The WORK follows the routing: the groups may end
before the last row (the landed count), the real visits end at the tile
that holds the last grouped row, and the surplus visits repeat it, so they
fetch and write nothing, and do nothing. No kernel reads a row past the
groups' end: the products do not store it (and never write the tiles
after the one the end cuts), ``moe_tgmm`` zeroes such rows of its
operands before it contracts over them, and ``moe_combine`` stops at a
count. Operands go to the MXU
in the type they arrive in and accumulation is float32. A result is
written ONCE, in the type its consumer reads: the forward products
float32; the hidden's cotangent and the weights' gradients in the
operands' type (float32 accumulation, one rounding at the store: the bits
``astype`` on a float32 result gives); the input's cotangent through gate
and up in the type the input arrived in (float32 rows: ``moe_combine``
back to the tokens reads float32), so that no XLA pass over ``[rows, d]``
exists only to rescale, round, widen or add what a kernel has just
written.

:func:`grouped_matmul` and :func:`grouped_pair` are the entry points, each
a ``jax.custom_vjp`` whose residuals are its operands; :func:`combine` and
:func:`take_rows` sum rows back to their tokens by ``moe_combine``. Kernels
off (the CPU default), a width that is no multiple of 128 or tiles that do
not fit: ``lax.ragged_dot`` / the scatter-add, counted in ``FALLBACKS``
under ``moe_gmm`` / ``moe_combine``; ``MXNET_PALLAS`` governs them all.
"""
from __future__ import annotations

import functools

from .. import telemetry as _tel
from . import pallas_kernels as _pk

__all__ = ["grouped_matmul", "grouped_pair", "combine", "take_rows",
           "GMM_CALLS"]

#: (kernel, operand type, result type, whether the store scales, tiles) ->
#: call sites that took the kernel, filled while tracing (FLASH_CALLS')
GMM_CALLS = {}

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # [m, k] x [m, n] -> [k, n]


def _pairs(kernel):
    """How many (lhs, rhs) pairs ``kernel`` multiplies into one result."""
    return 2 if kernel == "moe_gmm_pair" else 1


def _took_kernel(kernel, dtype, out_dtype, scaled, tiles):
    import jax.numpy as jnp

    key = (kernel, jnp.dtype(dtype).name, jnp.dtype(out_dtype).name,
           scaled, tiles)
    GMM_CALLS[key] = GMM_CALLS.get(key, 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.kernel_total.%s.%s.%s%s" % (
            key[:3] + (".scaled" if scaled else "",))).inc()


def _vmem(kernel, tm, tk, tn, itemsize, k_steps=1, out_itemsize=4,
          scaled=False):
    """Bytes of scoped VMEM a kernel asks for at tiles ``(tm, tk, tn)``:
    every operand and result block twice over for the pipeline, then the
    body's own. ``moe_gmm`` (``moe_gmm_pair``: two of each operand block):
    the left operand's tile once more, as the compiler lays it out for the
    MXU; where k is stepped through, the float32 accumulator and the
    product before it is added; where a pair is summed at one step, the
    first float32 product; else the product goes to the result's block as
    it is made, a few hundred bytes a row in flight (more where it is
    rounded on the way); the rows' scale comes as a [1, tm] float32 block
    and is turned into a column 128 rows at a time.
    ``moe_tgmm``: the left operand's tile transposed, the narrower
    operand's masked copy with its float32 form, and for a result narrower
    than float32 the float32 scratch block it is rounded from (with the
    narrower result's blocks, what the float32 result's two take). Fitted
    to what the chip's compiler asks for at the three benchmark cells'
    shapes, read stage by stage under a rising limit: 0 - 2 % over it at
    sixty settings, never under (AOT, PR 37; test_chip_compile.py holds
    the shapes)."""
    if kernel == "moe_tgmm":
        scratch = tk * tn * 4 if out_itemsize < 4 else 0
        return (2 * tm * (tk + tn) * itemsize + 2 * tk * tn * out_itemsize
                + scratch + tm * tk * itemsize
                + tm * min(tk, tn) * (4 + itemsize))
    pairs = _pairs(kernel)
    blocks = (2 * pairs * (tm * tk + tk * tn) * itemsize
              + 2 * tm * tn * out_itemsize
              + (tm * 576 + 128 * 1024 if scaled else 0))
    own = tm * tk * itemsize + (256 * tm if pairs > 1 else 0)
    if k_steps > 1:
        own += 8 * tm * tn
    elif pairs > 1:
        own += 4 * tm * tn
    else:
        own += tm * (1408 if out_itemsize < 4 else 768)
    return blocks + own


def _divisors(width):
    """The multiples of 128 that divide ``width``, largest first."""
    return [d for d in range(width, 0, -128) if width % d == 0]


def _plan(m, k, n, g, itemsize, kernel="moe_gmm", out_itemsize=4,
          scaled=False):
    """``((tm, tk, tn), refusal)``: the tiles a product of ``m`` rows,
    contraction ``k`` and result width ``n`` over ``g`` groups runs at, and
    why it would NOT take the kernel (a ``FALLBACKS`` reason) or None when
    it will. ``out_itemsize``: the item size of the result's type;
    ``scaled``: the store multiplies by a row scale. The only place that
    knows shapes: of the tiles that fit ``_VMEM_LIMIT``, those that move
    the fewest bytes between HBM and VMEM, the larger tiles on a tie. A
    ``moe_gmm`` reads the left operand once per column tile, and the right
    operand's [k, n] at every visit, or once a group where ``tk`` is all
    of k (the block then stays while the visits are one group's); a
    ``moe_gmm_pair`` does so for both of its pairs (``k`` is ONE pair's
    contraction); a ``moe_tgmm`` reads each operand once per tile of the
    other's width. Ms a call on one v5e at the Mellum2
    cell's [65536, 2304] x [16, 2304, 896] in bfloat16 (tools/gmm_probe.py;
    my chip runs, PR 35; ``lax.ragged_dot`` 6.08, megablox 1.96 at its
    best tiling that fits): 256 x 2304 x 896 1.57 (the pick: 603 MB moved,
    172 TFLOP/s), 512 x 1152 x 896 2.04 (1,127 MB), 512 x 768 x 896 2.16,
    512 x 384 x 896 2.38, 256 x 1152 x 896 2.40, 256 x 2304 x 128 3.49;
    the weights' gradient 512 x 1152 x 896 1.79 (the pick), 256 x 1152 x
    896 1.84, 512 x 768 x 896 1.85, 1024 x 768 x 896 1.91. By what the
    store does (``--results float32,bfloat16 --scale --pair``; my chip
    runs, PR 37; float32 result / bfloat16 result at the pick): the
    product 1.58 / 1.56; with the rows' scale in the store 1.61 / 1.59; the
    right operand read transposed 1.61 (256 x 896 x 2304) / 1.61 (512 x 896
    x 2304, which fits with the narrower result); the weights' gradient
    1.79 / 1.74 (the scratch block costs nothing, the narrower write-back
    saves); the pair [65536, 896] x [16, 2304, 896]^T twice -> [65536,
    2304] at 256 x 896 x 1152 (two right-hand blocks of [2304, 896] do not
    fit beside a row tile: the column tile is halved and both left operands
    are read twice) 3.17 / 3.14, 171 TFLOP/s, where two single calls take
    3.21 and leave an add. At the GLM cell's [32768, 2048] x [8, 2048, 1536]
    (128-row tiles): 1.21 / 1.21, scaled 1.24 / 1.22, the weights' gradient
    1.42 / 1.35, the pair 2.43 / 2.42 at 128 x 1536 x 1024; at the Kimi
    cell's [16384, 2304] x [8, 2304, 1024]: 0.50 / 0.50, scaled 0.51 / 0.50,
    the weights' gradient 0.60 / 0.56, the pair 0.98 / 0.97."""
    if not _pk.enabled():
        return None, "disabled"
    if m % 128 or k % 128 or n % 128:
        return None, "untileable"
    pairs = _pairs(kernel)
    best = None
    for tm in (t for t in (512, 256, 128) if m % t == 0):
        visits = m // tm + g - 1
        for tn in _divisors(n):
            for tk in _divisors(k):
                if _vmem(kernel, tm, tk, tn, itemsize, k // tk,
                         out_itemsize, scaled) > _pk._VMEM_LIMIT:
                    continue
                if kernel == "moe_tgmm":
                    moved = m * (k * (n // tn) + n * (k // tk))
                else:
                    moved = pairs * (
                        m * k * (n // tn)
                        + (g if tk == k else visits) * k * n)
                if best is None or moved < best[0]:
                    best = (moved, (tm, tk, tn))
    if best is None:
        return None, "vmem"
    return best[1], None


def _visits(group_sizes, m, tm):
    """The kernels' scalar operands: the groups' offsets [g + 1], then the
    group and the row tile of each of the ``m // tm + g - 1`` visits, and
    how many of them are real [1]. A group is visited once per row tile it
    has rows in, an empty one once (at the tile its neighbours meet in, or
    at the tile of the last grouped row where that is earlier); tiles never
    go back, and no real visit goes past the tile that holds the groups'
    last row. The surplus visits repeat the last real one."""
    import jax.numpy as jnp

    i32 = jnp.int32
    g = group_sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(i32)), m)
    starts = jnp.concatenate([jnp.zeros(1, i32), ends[:-1]])
    first = jnp.minimum(starts // tm, jnp.maximum(ends[-1] - 1, 0) // tm)
    last = jnp.where(ends > starts, (ends - 1) // tm, first)
    upto = jnp.cumsum(last - first + 1)  # visits of the groups up to here
    at = jnp.minimum(jnp.arange(m // tm + g - 1, dtype=i32), upto[-1] - 1)
    group = jnp.sum(at[:, None] >= upto[None, :], axis=1).astype(i32)
    tile = last[group] - (upto[group] - 1 - at)
    offsets = jnp.concatenate([jnp.zeros(1, i32), ends])
    return offsets, group, tile, upto[-1:]


def _rows_of_visit(offsets, groups, tiles, v, shape, tm):
    """Which rows of visit ``v``'s tile belong to its group: a mask of
    ``shape`` = [tm, width]."""
    import jax.numpy as jnp
    from jax import lax

    group = groups[v]
    row = tiles[v] * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[group]) & (row < offsets[group + 1])


#: rows a step of ``_clear_past``'s loop sets: a bfloat16 sublane tile
_CLEAR = 16


def _clear_past(ref, tile, offsets):
    """Rows of ``ref``'s [tm, width] block of row tile ``tile`` at and past
    the groups' end set to zero where the block lies, ``_CLEAR`` rows a
    step from the step that holds the end; nothing where the tile ends
    before it. A few small steps at the tile the end cuts, where a mask of
    the whole block would ask ``moe_tgmm``'s body for a block more of
    VMEM."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    tm, width = ref.shape
    start, end = tile * tm, offsets[offsets.shape[0] - 1]

    def clear(i, carry):
        at = pl.multiple_of(i * _CLEAR, _CLEAR)
        row = start + at + lax.broadcasted_iota(jnp.int32, (_CLEAR, 1), 0)
        ref[pl.ds(at, _CLEAR), :] = jnp.where(
            row < end, ref[pl.ds(at, _CLEAR), :], 0)
        return carry

    lax.fori_loop(jnp.clip(end - start, 0, tm) // _CLEAR, tm // _CLEAR,
                  clear, 0)


def _column(row_ref):
    """A ``[1, tm]`` float32 block as a ``[tm, 1]`` column, 128 rows at a
    time: 128 lanes of the row spread down a square, masked to its
    diagonal and summed along the lanes (one term a row that is not zero:
    exact). The rows' scale comes in lane-dense so; as ``[m, 1]`` it would
    lie a lane tile wide in HBM, 128 times its size."""
    import jax.numpy as jnp
    from jax import lax

    diagonal = (lax.broadcasted_iota(jnp.int32, (128, 128), 0)
                == lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(diagonal, row_ref[:, at:at + 128], 0.0), axis=1,
                 keepdims=True) for at in range(0, row_ref.shape[1], 128)],
        axis=0)


def _gmm_kernel(offsets, groups, tiles, real, *refs, tm, k_steps, dims,
                pairs, scaled):
    """One visit's [tm, tn] block of ``moe_gmm`` (``pairs`` of operands:
    their products summed), ``tk`` of the contraction a grid step; the rows
    of the visit's group are stored at the last, under their scale where
    there is one, in the result's type. A surplus visit does nothing."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    operands, rest = refs[:2 * pairs], refs[2 * pairs:]
    scale_ref = rest[0] if scaled else None
    out_ref, *acc_ref = rest[scaled:]
    v, at = pl.program_id(1), pl.program_id(2)

    def store(total):
        if scaled:
            total = total * _column(scale_ref)
        mine = _rows_of_visit(offsets, groups, tiles, v, out_ref.shape, tm)
        out_ref[...] = jnp.where(mine, total.astype(out_ref.dtype),
                                 out_ref[...])

    @pl.when(v < real[0])
    def _visit():
        part = _pk._dot(operands[0][...], operands[1][...], dims)
        for a_ref, b_ref in zip(operands[2::2], operands[3::2]):
            part += _pk._dot(a_ref[...], b_ref[...], dims)
        if k_steps == 1:
            store(part)
            return
        acc, = acc_ref

        @pl.when(at == 0)
        def _first():
            acc[...] = part

        @pl.when(at > 0)
        def _add():
            acc[...] += part

        @pl.when(at == k_steps - 1)
        def _last():
            store(acc[...])


def _tgmm_kernel(offsets, groups, tiles, real, lhs_ref, rhs_ref, out_ref,
                 *acc_ref, tm, visits):
    """One visit's part of a group's [tk, tn] block of ``moe_tgmm``: zeros
    at the group's first visit, then the product of the visit's rows, the
    narrower operand masked to them, the wider one's rows past the groups'
    end set to zero where it lies (a zero in the narrower one does not hold
    a row that is not finite out of the sum). A float32 result is summed
    where it lies; a narrower one in the float32 scratch block, rounded
    into the result at the group's last real visit. A surplus visit does
    nothing."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    acc = acc_ref[0] if acc_ref else out_ref

    @pl.when(v < real[0])
    def _visit():
        @pl.when((v == 0) | (groups[jnp.maximum(v - 1, 0)] != groups[v]))
        def _first():
            acc[...] = jnp.zeros_like(acc)

        narrow, wide = ((lhs_ref, rhs_ref) if lhs_ref.shape[1]
                        <= rhs_ref.shape[1] else (rhs_ref, lhs_ref))
        _clear_past(wide, tiles[v], offsets)
        a, b = lhs_ref[...], rhs_ref[...]
        mine = _rows_of_visit(offsets, groups, tiles, v, narrow.shape, tm)
        if narrow is lhs_ref:
            a = jnp.where(mine, a, 0)
        else:
            b = jnp.where(mine, b, 0)
        acc[...] += _pk._dot(a, b, _TN)
        if acc_ref:
            @pl.when((v == real[0] - 1)
                     | (groups[jnp.minimum(v + 1, visits - 1)] != groups[v]))
            def _last():
                out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _call(kernel, dtype, out_dtype, m, k, n, g, plan, transposed, scaled,
          interpret):
    """One of the kernels at one setting, jitted over (group sizes,
    operands...) with its list of visits. Cached, so that a model's layers
    share it (``pallas_kernels._flash_call``): the list's dozen small
    operations were a second of tracing a step with 40 call sites
    otherwise. ``m, k, n``: rows, contraction and result width of a
    ``moe_gmm`` (``transposed``: the right operand is [g, n, k];
    ``moe_gmm_pair``: lhs, rhs, lhs, rhs, each pair contracting ``k``;
    ``scaled``: a last operand [1, m] float32); of a ``moe_tgmm`` the rows
    and the widths of its two operands. ``out_dtype``: the result's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tk, tn = plan
    visits = m // tm + g - 1
    out_dtype = jnp.dtype(out_dtype)
    if kernel == "moe_tgmm":
        body = functools.partial(_tgmm_kernel, tm=tm, visits=visits)
        grid = (n // tn, k // tk, visits)
        in_specs = [
            pl.BlockSpec((tm, tk), lambda j, i, v, o, gr, t, r: (t[v], i)),
            pl.BlockSpec((tm, tn), lambda j, i, v, o, gr, t, r: (t[v], j))]
        out_specs = pl.BlockSpec(
            (None, tk, tn), lambda j, i, v, o, gr, t, r: (gr[v], i, j))
        out_shape = jax.ShapeDtypeStruct((g, k, n), out_dtype)
        scratch = [pltpu.VMEM((tk, tn), jnp.float32)] * (
            out_dtype.itemsize < 4)
    else:
        k_steps = k // tk
        pairs = _pairs(kernel)
        body = functools.partial(
            _gmm_kernel, tm=tm, k_steps=k_steps, pairs=pairs, scaled=scaled,
            dims=_NT if transposed else _NN)
        grid = (n // tn, visits, k_steps)
        if transposed:
            rhs = pl.BlockSpec(
                (None, tn, tk), lambda j, v, i, o, gr, t, r: (gr[v], j, i))
        else:
            rhs = pl.BlockSpec(
                (None, tk, tn), lambda j, v, i, o, gr, t, r: (gr[v], i, j))
        in_specs = [
            pl.BlockSpec((tm, tk), lambda j, v, i, o, gr, t, r: (t[v], i)),
            rhs] * pairs
        if scaled:
            in_specs.append(pl.BlockSpec(
                (1, tm), lambda j, v, i, o, gr, t, r: (0, t[v])))
        out_specs = pl.BlockSpec(
            (tm, tn), lambda j, v, i, o, gr, t, r: (t[v], j))
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
        scratch = [pltpu.VMEM((tm, tn), jnp.float32)] * (k_steps > 1)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))}
    call = pl.pallas_call(
        body, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        interpret=interpret, name=kernel, **params)
    return jax.jit(lambda group_sizes, *operands: call(
        *_visits(group_sizes, m, tm), *operands))


def _product(kernel, operands, group_sizes, plan, out_dtype,
             transposed=False, scale=None):
    """``kernel`` over ``operands`` (lhs, rhs; a pair: lhs, rhs, lhs, rhs)
    at ``plan``, its result in ``out_dtype``, its rows times ``scale`` [m]
    where there is one; counted."""
    import jax.numpy as jnp

    lhs, rhs = operands[:2]
    m, k = lhs.shape
    g = group_sizes.shape[0]
    if kernel == "moe_tgmm":
        n = rhs.shape[1]
    else:
        n = rhs.shape[1] if transposed else rhs.shape[2]
    scaled = scale is not None
    if scaled:
        operands += (scale.astype(jnp.float32)[None, :],)
    out_dtype = jnp.dtype(out_dtype)
    _took_kernel(kernel, lhs.dtype, out_dtype, scaled, plan)
    return _call(kernel, lhs.dtype.name, out_dtype.name, m, k, n, g, plan,
                 transposed, scaled, _pk._interpret())(group_sizes, *operands)


def _every_row(group_sizes, m):
    """``group_sizes`` with the rows past their end put in the last group:
    what ``lax.ragged_dot`` needs to write every row of ``m``."""
    import jax.numpy as jnp

    return group_sizes.at[-1].add(
        jnp.maximum(m - jnp.sum(group_sizes), 0).astype(group_sizes.dtype))


def _weights_gradient(lhs, g_out, group_sizes, plan):
    """``lhs^T . g_out`` per group, in the operands' type."""
    return _product("moe_tgmm", (lhs, g_out), group_sizes, plan, lhs.dtype)


def grouped_matmul(lhs, rhs, group_sizes, row_scale=None):
    """``lhs [m, k]`` times ``rhs [g, k, n]`` by groups of rows ->
    ``[m, n]`` float32: the first ``group_sizes[0]`` rows meet ``rhs[0]``,
    the next ``group_sizes[1]`` rows ``rhs[1]``, and so on, up to the
    groups' end, ``sum(group_sizes) <= m`` (``moe_share_ffn``'s landed
    count). Rows past the groups' end are UNSPECIFIED, in the value and in
    ``lhs``'s and ``row_scale``'s cotangents. The kernels do not store
    them (the tile the end cuts keeps what its block held there, nothing is
    written after it) and read none of them, so nothing in ``lhs``'s or the cotangent's rows
    past the end reaches ``rhs``'s gradient, not even a NaN; the fallback
    (``lax.ragged_dot``, which on the chip leaves a row outside every group
    unwritten) computes them in the last group, where they count. The
    operands go to the MXU in their common type,
    accumulation is float32. ``row_scale [m]`` (float32): each row of the
    float32 total is multiplied by its scale before it is written, the
    bits of ``grouped_matmul(lhs, rhs, group_sizes) * row_scale[:, None]``
    without that pass over ``[m, n]``.

    The product and its gradients run as the ``moe_gmm`` / ``moe_tgmm``
    kernels (:func:`_plan` sizes their tiles from the shapes), under one
    ``jax.custom_vjp`` that keeps its operands and nothing the kernels
    make: the cotangents of ``lhs`` and ``rhs`` are written in the
    operands' type by the kernels (``g * row_scale`` rounded to it first,
    where there is a scale); the scale's is ``sum_c g[r, c] * (lhs .
    rhs)[r, c]`` over the unscaled product, rebuilt by the forward kernel.
    Routed to ``lax.ragged_dot`` (the scale a plain multiply), and counted
    in ``pallas_kernels.FALLBACKS`` under ``moe_gmm``, when the kernels are
    disabled, a dimension is no multiple of 128, or no tiles fit the
    scoped VMEM. Every call site that takes a kernel is counted in
    ``GMM_CALLS`` with its types and tiles."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = jnp.result_type(lhs, rhs)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    (m, k), (g, _, n) = lhs.shape, rhs.shape
    size = dtype.itemsize
    plans = (_plan(m, k, n, g, size, scaled=row_scale is not None),
             _plan(m, n, k, g, size, out_itemsize=size),
             _plan(m, k, n, g, size, "moe_tgmm", out_itemsize=size))
    refusal = next((why for _, why in plans if why is not None), None)
    if refusal is not None:
        _pk._fallback("moe_gmm", refusal, (m, k, n, g))
        out = lax.ragged_dot(lhs, rhs, _every_row(group_sizes, m),
                             preferred_element_type=jnp.float32)
        return out if row_scale is None else out * row_scale[:, None]
    forward, to_lhs, to_rhs = (plan for plan, _ in plans)

    def run(lhs, rhs, group_sizes, row_scale):
        return _product("moe_gmm", (lhs, rhs), group_sizes, forward,
                        jnp.float32, scale=row_scale)

    def fwd(*operands):
        return run(*operands), operands

    def bwd(kept, g_out):
        lhs, rhs, group_sizes, row_scale = kept
        g_scale = None
        if row_scale is not None:
            unscaled = run(lhs, rhs, group_sizes, None)
            g_scale = jnp.sum(g_out * unscaled, axis=1).astype(
                row_scale.dtype)
            g_out = g_out * row_scale[:, None]
        g_out = g_out.astype(dtype)
        g_lhs = _product("moe_gmm", (g_out, rhs), group_sizes, to_lhs, dtype,
                         transposed=True)
        return (g_lhs, _weights_gradient(lhs, g_out, group_sizes, to_rhs),
                None, g_scale)

    product = jax.custom_vjp(run)
    product.defvjp(fwd, bwd)
    return product(lhs, rhs, group_sizes, row_scale)


def grouped_pair(lhs, rhs_a, rhs_b, group_sizes):
    """``(grouped_matmul(lhs, rhs_a), grouped_matmul(lhs, rhs_b))``, both
    float32, under ONE ``jax.custom_vjp``: the two forward products as they
    are, and ``lhs``'s cotangent ``g_a . rhs_a^T + g_b . rhs_b^T`` as one
    ``moe_gmm_pair`` call that sums both products in float32 and writes
    the sum once, where two calls write two ``[m, k]`` results that are
    each rounded and then added. The operands' type is the weights';
    ``lhs`` may arrive wider (the gathered rows of a float32 activation):
    it is rounded to the weights' type here, kept so, and its cotangent
    is written in the type it ARRIVED in, which is the type the cotangent's
    consumer reads (the scatter-add back to the tokens takes float32: a
    bfloat16 result would be one more pass that widens it). The groups may
    end before the last row, as :func:`grouped_matmul`'s: rows past their
    end are unspecified in both values and in ``lhs``'s cotangent, and
    nothing of them reaches a weights' gradient. Where the pair's blocks
    fit no plan (or the kernels take nothing), two :func:`grouped_matmul`
    calls."""
    import jax
    import jax.numpy as jnp

    dtype, arrived = jnp.result_type(rhs_a, rhs_b), lhs.dtype
    rhs_a, rhs_b = rhs_a.astype(dtype), rhs_b.astype(dtype)
    (m, k), (g, _, n) = lhs.shape, rhs_a.shape
    size = dtype.itemsize
    plans = (_plan(m, k, n, g, size),
             _plan(m, n, k, g, size, "moe_gmm_pair",
                   out_itemsize=arrived.itemsize),
             _plan(m, k, n, g, size, "moe_tgmm", out_itemsize=size))
    if any(why for _, why in plans):
        lhs = lhs.astype(dtype)
        return (grouped_matmul(lhs, rhs_a, group_sizes),
                grouped_matmul(lhs, rhs_b, group_sizes))
    forward, to_lhs, to_rhs = (plan for plan, _ in plans)

    def run(lhs, rhs_a, rhs_b, group_sizes):
        return tuple(_product("moe_gmm", (lhs.astype(dtype), rhs),
                              group_sizes, forward, jnp.float32)
                     for rhs in (rhs_a, rhs_b))

    def fwd(lhs, *rest):
        lhs = lhs.astype(dtype)
        return run(lhs, *rest), (lhs,) + rest

    def bwd(kept, g_out):
        lhs, rhs_a, rhs_b, group_sizes = kept
        g_a, g_b = (g.astype(dtype) for g in g_out)
        g_lhs = _product("moe_gmm_pair", (g_a, rhs_a, g_b, rhs_b),
                         group_sizes, to_lhs, arrived, transposed=True)
        return (g_lhs, _weights_gradient(lhs, g_a, group_sizes, to_rhs),
                _weights_gradient(lhs, g_b, group_sizes, to_rhs), None)

    pair = jax.custom_vjp(run)
    pair.defvjp(fwd, bwd)
    return pair(lhs, rhs_a, rhs_b, group_sizes)


#: the most scoped VMEM a ``moe_combine`` call may name: three quarters of
#: the 128 MiB a v5e core has. The kernel holds a slab of the whole [n, d]
#: sum while it runs, and the whole width fits at the three hybrid cells'
#: shapes (81 MiB at [8192, 2304]), where a slab of a third takes twice the
#: time (``_combine_plan``)
_COMBINE_CAP = 96 * 1024 * 1024
#: bucket rows a step of the kernel's row loop adds, unrolled
_UNROLL = 8


def _combine_vmem(n, tb, dc):
    """Bytes of scoped VMEM ``moe_combine`` asks for at ``tb`` bucket rows
    a block and ``dc`` columns a slab: the ``[n, dc]`` float32 sum of the
    slab and the rows' ``[tb, dc]`` block twice over for the pipeline."""
    return 4 * dc * (n + 2 * tb)


def _combine_plan(n, d, rows, dtype):
    """``((tb, dc), vmem_limit, refusal)`` of ``moe_combine`` summing
    ``rows`` bucket rows of width ``d`` back to ``n`` tokens: blocks of
    ``tb`` rows and the widest slab of ``dc`` columns whose sum fits
    ``_COMBINE_CAP``, under a limit the call names where it passes Mosaic's
    default ``_VMEM_LIMIT`` (``_combine_vmem`` is Mosaic's own total to the
    MiB). Every slab walks every row, and the walk is the scalar unit's
    work a row (its token's address), so a slab of a third takes twice as
    long as the whole width. Ms a call on one v5e (``tools/gmm_probe.py
    --combine``; my chip runs, PR 39; evenly routed / every token on the
    first experts): at the Mellum2 cell's 65,536 rows of 2304 summed to
    8,192 tokens, 512 x 2304 1.00 / 0.98 (676 GB/s), 256 x 2304 1.03 /
    1.01, 512 x 1152 1.55 / 1.54, 512 x 768 2.07 / 1.84, and XLA's
    scatter-add 6.44; at the GLM cell's 32,768 of 2048: 512 x 2048 0.54 /
    0.52, 512 x 1024 0.78 / 0.72, the scatter-add 2.66; at the Kimi cell's
    16,384 of 2304: 512 x 2304 0.37 / 0.37, 512 x 768 0.65 / 0.58, the
    scatter-add 2.06. Or why the sum goes to XLA (a ``FALLBACKS``
    reason)."""
    import jax.numpy as jnp

    if not _pk.enabled():
        return None, None, "disabled"
    tb = next((t for t in (512, 256, 128, 64, 32, 16, 8) if rows % t == 0),
              None)
    if jnp.dtype(dtype) != jnp.float32 or d % 128 or n % 8 or tb is None:
        return None, None, "untileable"
    mib = 1024 * 1024
    for dc in _divisors(d):
        need = _combine_vmem(n, tb, dc)
        if need <= _pk._VMEM_LIMIT:
            return (tb, dc), None, None
        if need <= _COMBINE_CAP:
            return (tb, dc), -(-need // mib) * mib, None
    return None, None, "vmem"


def _combine_kernel(tok, count, rows_ref, out_hbm, acc, done, *, tb, dc):
    """One block of ``tb`` bucket rows of ``moe_combine`` in one column
    slab: each of its rows below ``count`` added to its token's row of the
    slab's float32 sum, which stays in VMEM while the blocks pass (zeros at
    the first, written to the result's columns by one DMA at the last). A
    block past the count adds nothing; its index map holds the last block
    that has a row below the count, so nothing is fetched for it."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slab, block = pl.program_id(0), pl.program_id(1)
    n = acc.shape[0]
    chunk = next(c for c in (512, 256, 128, 64, 32, 16, 8) if n % c == 0)

    @pl.when(block == 0)
    def _zeros():
        def clear(c, carry):
            acc[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :] = (
                jnp.zeros((chunk, dc), jnp.float32))
            return carry

        lax.fori_loop(0, n // chunk, clear, 0)

    def add(r, carry):
        t = tok[block * tb + r]
        acc[pl.ds(t, 1), :] += rows_ref[pl.ds(r, 1), :]
        return carry

    def add_unrolled(i, carry):
        for u in range(_UNROLL):
            add(i * _UNROLL + u, carry)
        return carry

    here = jnp.clip(count[0] - block * tb, 0, tb)
    whole = here // _UNROLL
    lax.fori_loop(0, whole, add_unrolled, 0)
    lax.fori_loop(whole * _UNROLL, here, add, 0)

    @pl.when(block == pl.num_programs(1) - 1)
    def _write():
        copy = pltpu.make_async_copy(
            acc, out_hbm.at[:, pl.ds(pl.multiple_of(slab * dc, 128), dc)],
            done)
        copy.start()
        copy.wait()


@functools.lru_cache(maxsize=None)
def _combine_call(n, d, rows, plan, limit, interpret):
    """``moe_combine`` at one setting, jitted over (rows [rows, d] float32,
    their tokens [rows] int32, how many of the rows to sum [1] int32)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tb, dc = plan
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=limit)}
    call = pl.pallas_call(
        functools.partial(_combine_kernel, tb=tb, dc=dc),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(d // dc, rows // tb),
            # a block past the count holds the block of row count - 1
            in_specs=[pl.BlockSpec((tb, dc), lambda s, b, tok, count: (
                jnp.minimum(b, jnp.maximum(count[0] - 1, 0) // tb), s))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, dc), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        interpret=interpret, name="moe_combine", **params)
    return jax.jit(lambda rows_, tok, count: call(tok, count, rows_))


def _summed(rows, tok, n, count, plan, limit):
    """The first ``count`` of ``rows`` summed back to their ``n`` tokens:
    ``moe_combine`` at ``plan``, counted, or with no plan the scatter-add,
    the rows past the count sent out of range and dropped (none of them is
    read)."""
    import jax.numpy as jnp

    R = rows.shape[0]
    if plan is None:
        tok = jnp.where(jnp.arange(R) < count, tok, n)
        return jnp.zeros((n, rows.shape[1]), rows.dtype).at[tok].add(
            rows, mode="drop")
    _took_kernel("moe_combine", rows.dtype, jnp.float32, False, plan)
    return _combine_call(n, rows.shape[1], R, plan, limit, _pk._interpret())(
        rows, tok, jnp.full((1,), count, jnp.int32))


def _combine_how(n, d, rows, dtype):
    """``_combine_plan``'s plan and limit, having counted a refusal in
    ``FALLBACKS`` (the plan is then None)."""
    plan, limit, refusal = _combine_plan(n, d, rows, dtype)
    if refusal is not None:
        _pk._fallback("moe_combine", refusal, (n, d, rows))
    return plan, limit


def combine(rows, tok, n, count):
    """The first ``count`` (an int32 scalar) of ``rows [R, d]`` summed back
    to the ``n`` tokens ``tok [R]`` names -> ``[n, d]``: the value of
    ``zeros.at[tok[:count]].add(rows[:count])``, each of those rows read
    once and every token's row written once. The rows past the count are
    not read and may hold anything, NaN included (``moe_share_ffn``'s
    landed count: the rows past it are what the grouped products leave
    unspecified). The ``moe_combine``
    kernel (:func:`_combine_plan` sizes it from ``(n, d, R)``); its
    transpose, under a ``jax.custom_vjp``, is the gather ``g[tok]`` (in the
    rows past the count too, whose cotangent is then unspecified). Kernels
    off, a type other than float32 or a width that is no multiple of 128:
    the scatter-add itself, counted in ``pallas_kernels.FALLBACKS`` under
    ``moe_combine``."""
    import jax

    plan, limit = _combine_how(n, rows.shape[1], rows.shape[0], rows.dtype)
    if plan is None:
        return _summed(rows, tok, n, count, None, None)

    def summed(rows, tok, count):
        return _summed(rows, tok, n, count, plan, limit)

    run = jax.custom_vjp(summed)
    run.defvjp(lambda rows, tok, count: (summed(rows, tok, count), tok),
               lambda tok, g: (g[tok], None, None))
    return run(rows, tok, count)


def take_rows(x, tok, count):
    """``x[tok]`` (``x [n, d]``, ``tok [R]``) whose transpose, the rows'
    cotangent summed back to the tokens, is the ``moe_combine`` kernel in
    place of XLA's scatter-add (:func:`combine`'s transpose pair): it sums
    the first ``count`` rows' cotangents alone and reads none of the others
    (kernel or scatter-add); the gather itself is of every row."""
    import jax

    n, d = x.shape
    plan, limit = _combine_how(n, d, tok.shape[0], x.dtype)

    @jax.custom_vjp
    def run(x, tok, count):
        return x[tok]

    run.defvjp(lambda x, tok, count: (x[tok], (tok, count)),
               lambda kept, g: (_summed(g, kept[0], n, kept[1], plan, limit),
                                None, None))
    return run(x, tok, count)
