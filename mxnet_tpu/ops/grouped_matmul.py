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
  weights are never transposed in memory);
* ``moe_tgmm``: ``lhs^T [k, m] x [m, n]`` per group ``-> [g, k, n]`` (the
  weights' gradient), accumulated in the result's own block in VMEM over
  the row tiles of one group.

Both walk a list of VISITS, (row tile, group) pairs that come in by scalar
prefetch beside the groups' offsets: a row tile that a group boundary cuts
is visited once per group under a row mask, an empty group once with
nothing in the mask (so ``moe_tgmm`` writes its block as zeros). The list
has the static worst-case length, ``m // tm + g - 1``, the surplus visits
masked whole: the same grid whatever the routing. Operands go to the MXU
in the type they arrive in, accumulation and results are float32.

:func:`grouped_matmul` is the entry point, a ``jax.custom_vjp`` whose
residuals are its operands. Kernels off (the CPU default), a width that is
no multiple of 128 or tiles that do not fit: ``lax.ragged_dot``, counted in
``pallas_kernels.FALLBACKS`` under ``moe_gmm``. ``MXNET_PALLAS`` and
interpret mode govern these kernels as they govern the others.
"""
from __future__ import annotations

import functools

from .. import telemetry as _tel
from . import pallas_kernels as _pk

__all__ = ["grouped_matmul", "GMM_CALLS"]

#: (kernel, operand type, (tm, tk, tn)) -> number of call sites that took
#: the kernel, filled while tracing like ``pallas_kernels.FLASH_CALLS``
GMM_CALLS = {}

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # [m, k] x [m, n] -> [k, n]


def _took_kernel(kernel, dtype, tiles):
    import jax.numpy as jnp

    key = (kernel, jnp.dtype(dtype).name, tiles)
    GMM_CALLS[key] = GMM_CALLS.get(key, 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.kernel_total.%s.%s" % key[:2]).inc()


def _vmem(kernel, tm, tk, tn, itemsize, k_steps=1):
    """Bytes of scoped VMEM a kernel asks for at tiles ``(tm, tk, tn)``:
    every operand and result block twice over for the pipeline, then the
    body's own. ``moe_gmm``: where k is stepped through, the float32
    accumulator and the product before it is added; at one step the
    product goes to the result's block as it is made, half of it in
    flight. ``moe_tgmm``: the left operand's tile transposed, and the
    narrower operand's masked copy with its float32 form. Fitted to what
    the chip's compiler accepts and refuses at both benchmark cells'
    shapes, never under it (AOT and my chip runs, PR 35;
    test_chip_compile.py holds the shapes)."""
    if kernel == "moe_tgmm":
        return (2 * tm * (tk + tn) * itemsize + 2 * tk * tn * 4
                + tm * tk * itemsize + tm * min(tk, tn) * (4 + itemsize))
    blocks = 2 * (tm * tk + tk * tn) * itemsize + 2 * tm * tn * 4
    return blocks + tm * tn * (8 if k_steps > 1 else 2)


def _divisors(width):
    """The multiples of 128 that divide ``width``, largest first."""
    return [d for d in range(width, 0, -128) if width % d == 0]


def _plan(m, k, n, g, itemsize, kernel="moe_gmm"):
    """``((tm, tk, tn), refusal)``: the tiles a product of ``m`` rows,
    contraction ``k`` and result width ``n`` over ``g`` groups runs at, and
    why it would NOT take the kernel (a ``FALLBACKS`` reason) or None when
    it will. The only place that knows shapes: of the tiles that fit
    ``_VMEM_LIMIT``, those that move the fewest bytes between HBM and
    VMEM, the larger tiles on a tie. A ``moe_gmm`` reads the left operand
    once per column tile, and the right operand's [k, n] at every visit,
    or once a group where ``tk`` is all of k (the block then stays while
    the visits are one group's); a ``moe_tgmm`` reads each operand once
    per tile of the other's width. Ms a call on one v5e at the Mellum2
    cell's [65536, 2304] x [16, 2304, 896] in bfloat16 (tools/gmm_probe.py;
    my chip runs, PR 35; ``lax.ragged_dot`` 6.08, megablox 1.96 at its
    best tiling that fits): 256 x 2304 x 896 1.57 (the pick: 603 MB moved,
    172 TFLOP/s), 512 x 1152 x 896 2.04 (1,127 MB), 512 x 768 x 896 2.16,
    512 x 384 x 896 2.38, 256 x 1152 x 896 2.40, 256 x 2304 x 128 3.49;
    the weights' gradient 512 x 1152 x 896 1.79 (the pick), 256 x 1152 x
    896 1.84, 512 x 768 x 896 1.85, 1024 x 768 x 896 1.91."""
    if not _pk.enabled():
        return None, "disabled"
    if m % 128 or k % 128 or n % 128:
        return None, "untileable"
    best = None
    for tm in (t for t in (512, 256, 128) if m % t == 0):
        visits = m // tm + g - 1
        for tn in _divisors(n):
            for tk in _divisors(k):
                if _vmem(kernel, tm, tk, tn, itemsize,
                         k // tk) > _pk._VMEM_LIMIT:
                    continue
                if kernel == "moe_tgmm":
                    moved = m * (k * (n // tn) + n * (k // tk))
                else:
                    moved = (m * k * (n // tn)
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
    has rows in, an empty one once (at the tile its neighbours meet in);
    tiles never go back. The surplus visits repeat the last real one and
    are masked whole by their number."""
    import jax.numpy as jnp

    i32 = jnp.int32
    g = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(i32)), m)
    starts = jnp.concatenate([jnp.zeros(1, i32), ends[:-1]])
    first = jnp.minimum(starts // tm, tiles - 1)
    last = jnp.where(ends > starts, (ends - 1) // tm, first)
    upto = jnp.cumsum(last - first + 1)  # visits of the groups up to here
    at = jnp.minimum(jnp.arange(tiles + g - 1, dtype=i32), upto[-1] - 1)
    group = jnp.sum(at[:, None] >= upto[None, :], axis=1).astype(i32)
    tile = last[group] - (upto[group] - 1 - at)
    offsets = jnp.concatenate([jnp.zeros(1, i32), ends])
    return offsets, group, tile, upto[-1:]


def _rows_of_visit(offsets, groups, tiles, real, v, shape, tm):
    """Which rows of visit ``v``'s tile belong to its group: a mask of
    ``shape`` = [tm, width]; nothing of a surplus visit."""
    import jax.numpy as jnp
    from jax import lax

    group = groups[v]
    row = tiles[v] * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    lo = offsets[group]
    hi = jnp.where(v < real[0], offsets[group + 1], lo)
    return (row >= lo) & (row < hi)


def _gmm_kernel(offsets, groups, tiles, real, lhs_ref, rhs_ref, out_ref,
                *acc_ref, tm, k_steps, dims):
    """One visit's [tm, tn] block of ``moe_gmm``, ``tk`` of the contraction
    a grid step; the rows of the visit's group are stored at the last."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v, at = pl.program_id(1), pl.program_id(2)
    part = _pk._dot(lhs_ref[...], rhs_ref[...], dims)

    def store(total):
        mine = _rows_of_visit(offsets, groups, tiles, real, v,
                              out_ref.shape, tm)
        out_ref[...] = jnp.where(mine, total, out_ref[...])

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


def _tgmm_kernel(offsets, groups, tiles, real, lhs_ref, rhs_ref, out_ref, *,
                 tm):
    """One visit's part of a group's [tk, tn] block of ``moe_tgmm``: zeros
    at the group's first visit, then the product of the visit's rows, the
    narrower operand masked to them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(2)

    @pl.when((v == 0) | (groups[jnp.maximum(v - 1, 0)] != groups[v]))
    def _first():
        out_ref[...] = jnp.zeros_like(out_ref)

    a, b = lhs_ref[...], rhs_ref[...]
    if a.shape[1] <= b.shape[1]:
        a = jnp.where(_rows_of_visit(offsets, groups, tiles, real, v,
                                     a.shape, tm), a, 0)
    else:
        b = jnp.where(_rows_of_visit(offsets, groups, tiles, real, v,
                                     b.shape, tm), b, 0)
    out_ref[...] += _pk._dot(a, b, _TN)


@functools.lru_cache(maxsize=None)
def _call(kernel, dtype, m, k, n, g, plan, transposed, interpret):
    """One of the two kernels at one setting, jitted over (group sizes,
    lhs, rhs) with its list of visits. Cached, so that a model's layers
    share it (``pallas_kernels._flash_call``): the list's dozen small
    operations were a second of tracing a step with 40 call sites
    otherwise. ``m, k, n``: rows, contraction and result width of a
    ``moe_gmm`` (``transposed``: the right operand is [g, n, k]); of a
    ``moe_tgmm`` the rows and the widths of its two operands."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tk, tn = plan
    visits = m // tm + g - 1
    if kernel == "moe_tgmm":
        body = functools.partial(_tgmm_kernel, tm=tm)
        grid = (n // tn, k // tk, visits)
        in_specs = [
            pl.BlockSpec((tm, tk), lambda j, i, v, o, gr, t, r: (t[v], i)),
            pl.BlockSpec((tm, tn), lambda j, i, v, o, gr, t, r: (t[v], j))]
        out_specs = pl.BlockSpec(
            (None, tk, tn), lambda j, i, v, o, gr, t, r: (gr[v], i, j))
        out_shape = jax.ShapeDtypeStruct((g, k, n), jnp.float32)
        scratch = []
    else:
        k_steps = k // tk
        body = functools.partial(_gmm_kernel, tm=tm, k_steps=k_steps,
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
            rhs]
        out_specs = pl.BlockSpec(
            (tm, tn), lambda j, v, i, o, gr, t, r: (t[v], j))
        out_shape = jax.ShapeDtypeStruct((m, n), jnp.float32)
        scratch = [pltpu.VMEM((tm, tn), jnp.float32)] * (k_steps > 1)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))}
    call = pl.pallas_call(
        body, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        interpret=interpret, name=kernel, **params)
    return jax.jit(lambda group_sizes, lhs, rhs: call(
        *_visits(group_sizes, m, tm), lhs, rhs))


def _product(kernel, lhs, rhs, group_sizes, plan, transposed=False):
    """``kernel`` over ``lhs`` and ``rhs`` at ``plan``, counted."""
    m, k = lhs.shape
    g = group_sizes.shape[0]
    if kernel == "moe_tgmm":
        n = rhs.shape[1]
    else:
        n = rhs.shape[1] if transposed else rhs.shape[2]
    _took_kernel(kernel, lhs.dtype, plan)
    return _call(kernel, lhs.dtype.name, m, k, n, g, plan, transposed,
                 _pk._interpret())(group_sizes, lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` times ``rhs [g, k, n]`` by groups of rows ->
    ``[m, n]`` float32: the first ``group_sizes[0]`` rows meet ``rhs[0]``,
    the next ``group_sizes[1]`` rows ``rhs[1]``, and so on.
    ``sum(group_sizes) == m`` is the caller's to keep (``moe_share_ffn``
    does: its empty rows ride in the last group); rows past the last group
    are never written. The operands go to the MXU in their common type,
    accumulation is float32.

    The product and both gradients run as the ``moe_gmm`` / ``moe_tgmm``
    kernels (:func:`_plan` sizes their tiles from the shapes), under one
    ``jax.custom_vjp`` that keeps its operands and nothing the kernels
    make. Routed to ``lax.ragged_dot``, and counted in
    ``pallas_kernels.FALLBACKS`` under ``moe_gmm``, when the kernels are
    disabled, a dimension is no multiple of 128, or no tiles fit the
    scoped VMEM. Every call site that takes a kernel is counted in
    ``GMM_CALLS`` with its tiles."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = jnp.result_type(lhs, rhs)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    (m, k), (g, _, n) = lhs.shape, rhs.shape
    size = dtype.itemsize
    plans = (_plan(m, k, n, g, size), _plan(m, n, k, g, size),
             _plan(m, k, n, g, size, "moe_tgmm"))
    refusal = next((why for _, why in plans if why is not None), None)
    if refusal is not None:
        _pk._fallback("moe_gmm", refusal, (m, k, n, g))
        return lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
    forward, to_lhs, to_rhs = (plan for plan, _ in plans)

    @jax.custom_vjp
    def product(lhs, rhs, group_sizes):
        return _product("moe_gmm", lhs, rhs, group_sizes, forward)

    def fwd(lhs, rhs, group_sizes):
        return (_product("moe_gmm", lhs, rhs, group_sizes, forward),
                (lhs, rhs, group_sizes))

    def bwd(kept, g_out):
        lhs, rhs, group_sizes = kept
        g_out = g_out.astype(dtype)
        g_lhs = _product("moe_gmm", g_out, rhs, group_sizes, to_lhs,
                         transposed=True)
        g_rhs = _product("moe_tgmm", lhs, g_out, group_sizes, to_rhs)
        return g_lhs.astype(dtype), g_rhs.astype(dtype), None

    product.defvjp(fwd, bwd)
    return product(lhs, rhs, group_sizes)
