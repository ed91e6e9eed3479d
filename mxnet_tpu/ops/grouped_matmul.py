"""The expert layer's passes over its sorted bucket as Pallas kernels:
the grouped products ``moe_gmm`` and ``moe_tgmm``, the gather ``moe_take``
and the sum back to the tokens ``moe_combine``.

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
  row by a float32 scale (the routing weight of the down product); what a
  call does around its products (``GMM_CALLS``' fifth field): its left
  operand formed as ``silu(gate) * up`` from two float32 blocks as they
  are loaded (``SWIGLU``), the rebuilt down product's store writing the
  routing weight's cotangent in place of the product
  (``SCALE_COTANGENT``), the hidden cotangent's store writing gate's and
  up's through the SiLU (``SWIGLU_COTANGENT``);
* ``moe_gmm_pair``: the same body over two pairs of operands,
  ``a1 . b1^T + a2 . b2^T`` summed in float32 and written once (the
  input's cotangent through gate and up, whose weights are never
  concatenated in memory);
* ``moe_tgmm``: ``lhs^T [k, m] x [m, n]`` per group ``-> [g, k, n]`` (the
  weights' gradient), accumulated in VMEM over the row tiles of one group:
  in the result's own block where the result is float32, else in a
  float32 scratch block that is rounded into it at the group's last visit
  (``SWIGLU`` too: the down weights' gradient);
* ``moe_take``: ``x[tok]``, the bucket's rows gathered from the tokens'
  (forward, and the output's cotangent for the backward), the tokens' rows
  held in VMEM, the rows past a count never written;
* ``moe_combine``: the bucket's rows summed back to the tokens, the sum
  held in VMEM, the rows past a count never read.

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
operands before it contracts over them, and ``moe_take`` and
``moe_combine`` stop at a count. Operands go to the MXU in the right
operand's type and accumulation is float32. A result is
written ONCE, in the type its consumer reads: the forward products
float32; the hidden's cotangent and the weights' gradients in the
operands' type (float32 accumulation, one rounding at the store: the bits
``astype`` on a float32 result gives); the input's cotangent through gate
and up in the type the input arrived in (float32 rows: ``moe_combine``
back to the tokens reads float32), so that no XLA pass over ``[rows, d]``
exists only to rescale, round, widen or add what a kernel has just
written.

:func:`expert_mlp` is the layer's entry point: the gather, gate and up,
the SiLU and the down product under ONE ``jax.custom_vjp``, so that no
pass over the bucket is XLA's and none goes past the landed rows;
:func:`grouped_matmul` is one product alone, a ``jax.custom_vjp`` whose
residuals are its operands; :func:`combine` is the ``moe_combine`` sum,
whose transpose is the ``moe_take`` gather. Kernels off (the CPU default),
a width that is no multiple of 128 or tiles that do not fit:
``lax.ragged_dot``, XLA's gather, SiLU and scatter-add, each refusal
counted once in ``FALLBACKS`` under ``moe_gmm`` / ``moe_take`` /
``moe_combine``; ``MXNET_PALLAS`` governs them all.
"""
from __future__ import annotations

import functools

from .. import telemetry as _tel
from . import pallas_kernels as _pk

__all__ = ["grouped_matmul", "expert_mlp", "combine", "GMM_CALLS"]

#: (kernel, operand type, result type, whether the store scales, what the
#: call does around its products or None, tiles) -> call sites that took
#: the kernel, filled while tracing (FLASH_CALLS')
GMM_CALLS = {}

#: what a call does around its products (``GMM_CALLS``' fifth field, the
#: names joined by "+"). ``SWIGLU``: the left operand is ``silu(gate) *
#: up``, formed in the operands' type from a float32 block of each as it
#: is loaded, so that an expert's hidden is never stored;
#: ``SCALE_COTANGENT`` (the down product rebuilt in its backward pass):
#: the product is not stored, the store writes the cotangent times the
#: rows' scale in the operands' type and each row's ``sum(g * product)``,
#: the routing weight's cotangent; ``SWIGLU_COTANGENT`` (the hidden's
#: cotangent): the store writes gate's and up's cotangents through
#: ``silu(gate) * up``, from their float32 blocks, in place of the product
SWIGLU = "swiglu"
SCALE_COTANGENT = "scale_cotangent"
SWIGLU_COTANGENT = "swiglu_cotangent"

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # [m, k] x [m, n] -> [k, n]


def _pairs(kernel):
    """How many (lhs, rhs) pairs ``kernel`` multiplies into one result."""
    return 2 if kernel == "moe_gmm_pair" else 1


def _took_kernel(kernel, dtype, out_dtype, scaled, tiles, fused=None):
    import jax.numpy as jnp

    key = (kernel, jnp.dtype(dtype).name, jnp.dtype(out_dtype).name,
           scaled, fused, tiles)
    GMM_CALLS[key] = GMM_CALLS.get(key, 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.kernel_total.%s.%s.%s%s%s" % (
            key[:3] + (".scaled" if scaled else "",
                       "." + fused.replace("+", ".") if fused else ""))).inc()


def _vmem(kernel, tm, tk, tn, itemsize, k_steps=1, out_itemsize=4,
          scaled=False, epilogue=None, swiglu=False):
    """Bytes of scoped VMEM a kernel asks for at tiles ``(tm, tk, tn)``:
    every operand and result block twice over for the pipeline, then the
    body's own. ``moe_gmm`` (``moe_gmm_pair``: two of each operand block):
    the left operand's tile once more, as the compiler lays it out for the
    MXU; where k is stepped through, the float32 accumulator and the
    product before it is added; where a pair is summed at one step, the
    first float32 product; else the product goes to the result's block as
    it is made, a few hundred bytes a row in flight (more where it is
    rounded on the way); the rows' scale comes as a [1, tm] float32 block
    and is turned into a column 128 rows at a time. A ``swiglu`` left
    operand is two float32 blocks, gate and up, twice over, and the SiLU
    in the operands' type through ``_WIDE`` bytes an element of the tile
    in flight. The
    ``SCALE_COTANGENT`` epilogue: the float32 cotangent's [tm, tn] block
    twice over and the row sums' [1, tm] block (eight sublanes) twice over;
    its products in flight fit in what the plain store is counted
    (Mosaic's totals for a v5e at the four MoE cells' shapes lie 5.3 - 7.4
    bytes a block element over the scaled store's, against the 8 counted).
    The ``SWIGLU_COTANGENT`` epilogue: gate's and up's float32 blocks
    twice over, the second result's block twice over, and ``_SWIGLU_OWN``
    bytes a block element of the SiLU's gradient in flight.
    ``moe_tgmm``: the left operand's tile transposed, the narrower
    operand's masked copy with its float32 form, and for a result narrower
    than float32 the float32 scratch block it is rounded from (with the
    narrower result's blocks, what the float32 result's two take); a
    ``swiglu`` left operand as in ``moe_gmm``.
    Fitted to what the chip's compiler asks for at the benchmark cells'
    shapes, read stage by stage under a rising limit: 0 - 2 % over it at
    sixty settings, never under (AOT, PR 37; test_chip_compile.py holds
    the shapes); the ``SWIGLU`` forms and ``SWIGLU_COTANGENT`` 10 - 30 %
    over it at the four MoE cells' plans and the next larger tiles, never
    under (compiled for a v5e)."""
    left = 8 if swiglu else itemsize
    wide = _WIDE * tm * tk if swiglu else 0
    if kernel == "moe_tgmm":
        scratch = tk * tn * 4 if out_itemsize < 4 else 0
        return (2 * tm * (tk * left + tn * itemsize)
                + 2 * tk * tn * out_itemsize + scratch + tm * tk * itemsize
                + tm * min(tk, tn) * (4 + itemsize) + wide)
    pairs = _pairs(kernel)
    blocks = (2 * pairs * (tm * tk * left + tk * tn * itemsize)
              + 2 * tm * tn * out_itemsize
              + (tm * 576 + 128 * 1024 if scaled else 0))
    own = tm * tk * itemsize + (256 * tm if pairs > 1 else 0) + wide
    if epilogue == SCALE_COTANGENT:
        blocks += 8 * tm * tn + 64 * tm
    elif epilogue == SWIGLU_COTANGENT:
        blocks += 16 * tm * tn + 2 * tm * tn * out_itemsize
        own += _SWIGLU_OWN * tm * tn
    if k_steps > 1:
        own += 8 * tm * tn
    elif pairs > 1:
        own += 4 * tm * tn
    else:
        own += tm * (1408 if out_itemsize < 4 else 768)
    return blocks + own


#: bytes of VMEM an element of a ``SWIGLU`` tile asks for in flight, and
#: an element of the ``SWIGLU_COTANGENT`` store
_WIDE, _SWIGLU_OWN = 8, 16


def _divisors(width):
    """The multiples of 128 that divide ``width``, largest first."""
    return [d for d in range(width, 0, -128) if width % d == 0]


def _plan(m, k, n, g, itemsize, kernel="moe_gmm", **how):
    """``((tm, tk, tn), refusal)``: the tiles a product of ``m`` rows,
    contraction ``k`` and result width ``n`` over ``g`` groups runs at, and
    why it would NOT take the kernel (a ``FALLBACKS`` reason) or None when
    it will. ``how``: what :func:`_vmem` counts beside the tiles (the
    result's item size, a row scale, what the call does around its
    products). The only place that
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
                         **how) > _pk._VMEM_LIMIT:
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


def _rows_of_visit(offsets, groups, tiles, v, shape, tm, axis=0):
    """Which rows of visit ``v``'s tile belong to its group: a mask of
    ``shape`` = [tm, width], or [1, tm] with the rows along ``axis`` 1."""
    import jax.numpy as jnp
    from jax import lax

    group = groups[v]
    row = tiles[v] * tm + lax.broadcasted_iota(jnp.int32, shape, axis)
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


def _row(column):
    """A ``[tm, 1]`` float32 column as a ``[1, tm]`` row, 128 rows at a
    time: :func:`_column` the other way round, the column spread across a
    square, masked to its diagonal and summed down the sublanes (exact)."""
    import jax.numpy as jnp
    from jax import lax

    diagonal = (lax.broadcasted_iota(jnp.int32, (128, 128), 0)
                == lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(diagonal, column[at:at + 128, :], 0.0), axis=0,
                 keepdims=True) for at in range(0, column.shape[0], 128)],
        axis=1)


def _swiglu(gate_ref, up_ref, dtype):
    """``silu(gate) * up`` of two float32 blocks, in ``dtype``."""
    import jax

    gate = gate_ref[...]
    return (gate * jax.nn.sigmoid(gate) * up_ref[...]).astype(dtype)


def _gmm_kernel(offsets, groups, tiles, real, *refs, tm, k_steps, dims,
                pairs, scaled, swiglu, epilogue, fed):
    """One visit's [tm, tn] block of ``moe_gmm`` (``pairs`` of operands:
    their products summed), ``tk`` of the contraction a grid step, each
    left operand in the right one's type ``fed`` (``swiglu``: formed from
    a gate and an up block); the rows of the visit's group are stored at
    the last, under their scale where there is one, in the result's type.
    The ``SCALE_COTANGENT`` epilogue stores no product: the float32
    cotangent block ``g`` times the rows' scale, in the result's type, and
    each row's ``sum(g * total)`` over the tile's columns to a [1, tm]
    float32 block of partial sums. The ``SWIGLU_COTANGENT`` epilogue reads
    the product as the hidden's cotangent and stores gate's and up's
    through ``silu(gate) * up``, from their float32 blocks. A surplus visit
    does nothing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    per = 3 if swiglu else 2
    operands, rest = refs[:per * pairs], refs[per * pairs:]
    taken = {None: 0, SCALE_COTANGENT: 1, SWIGLU_COTANGENT: 2}[epilogue]
    beside, rest = rest[:taken], rest[taken:]
    scale_ref = rest[0] if scaled else None
    rest = rest[scaled:]
    outs, acc_ref = rest[:1 + bool(epilogue)], rest[1 + bool(epilogue):]
    v, at = pl.program_id(1), pl.program_id(2)

    def store(total):
        mine = _rows_of_visit(offsets, groups, tiles, v, outs[0].shape, tm)

        def put(ref, value):
            ref[...] = jnp.where(mine, value.astype(ref.dtype), ref[...])

        if epilogue == SCALE_COTANGENT:
            g, sums = beside[0][...], outs[1]
            put(outs[0], g * _column(scale_ref))
            row = _rows_of_visit(offsets, groups, tiles, v, sums.shape, tm,
                                 axis=1)
            sums[...] = jnp.where(
                row, _row(jnp.sum(g * total, axis=1, keepdims=True)),
                sums[...])
        elif epilogue == SWIGLU_COTANGENT:
            gate, up = beside[0][...], beside[1][...]
            sig = jax.nn.sigmoid(gate)
            put(outs[0], total * up * (sig * (1.0 + gate * (1.0 - sig))))
            put(outs[1], total * (gate * sig))
        else:
            put(outs[0], total * _column(scale_ref) if scaled else total)

    def product(refs_):
        left = (_swiglu(*refs_[:2], fed) if swiglu
                else refs_[0][...].astype(fed))
        return _pk._dot(left, refs_[-1][...], dims)

    @pl.when(v < real[0])
    def _visit():
        part = product(operands[:per])
        for first in range(per, per * pairs, per):
            part += product(operands[first:first + per])
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


def _tgmm_kernel(offsets, groups, tiles, real, *refs, tm, visits, swiglu,
                 fed):
    """One visit's part of a group's [tk, tn] block of ``moe_tgmm``: zeros
    at the group's first visit, then the product of the visit's rows in
    ``fed`` (``swiglu``: the left operand formed from a gate and an up
    block), the narrower operand masked to them, the wider one's rows past
    the groups' end set to zero where it lies (a zero in the narrower one
    does not hold a row that is not finite out of the sum). A float32
    result is summed where it lies; a narrower one in the float32 scratch
    block, rounded into the result at the group's last real visit. A
    surplus visit does nothing."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    lhs_refs, (rhs_ref, out_ref, *acc_ref) = (refs[:1 + swiglu],
                                              refs[1 + swiglu:])
    v = pl.program_id(2)
    acc = acc_ref[0] if acc_ref else out_ref

    @pl.when(v < real[0])
    def _visit():
        @pl.when((v == 0) | (groups[jnp.maximum(v - 1, 0)] != groups[v]))
        def _first():
            acc[...] = jnp.zeros_like(acc)

        lhs_narrow = lhs_refs[0].shape[1] <= rhs_ref.shape[1]
        for wide in ((rhs_ref,) if lhs_narrow else lhs_refs):
            _clear_past(wide, tiles[v], offsets)
        a = (_swiglu(*lhs_refs, fed) if swiglu
             else lhs_refs[0][...].astype(fed))
        b = rhs_ref[...].astype(fed)
        mine = _rows_of_visit(offsets, groups, tiles, v,
                              (a if lhs_narrow else b).shape, tm)
        if lhs_narrow:
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
          interpret, epilogue=None, swiglu=False):
    """One of the kernels at one setting, jitted over (group sizes,
    operands...) with its list of visits. Cached, so that a model's layers
    share it (``pallas_kernels._flash_call``): the list's dozen small
    operations were a second of tracing a step with 40 call sites
    otherwise. ``dtype``: the type the products are fed. ``m, k, n``: rows,
    contraction and result width of a ``moe_gmm`` (``transposed``: the
    right operand is [g, n, k]; ``moe_gmm_pair``: lhs, rhs, lhs, rhs, each
    pair contracting ``k``; ``swiglu``: the left operand is a float32 gate
    and up, [m, k] each; then with the ``SCALE_COTANGENT`` epilogue a
    float32 cotangent [m, n], with ``SWIGLU_COTANGENT`` a float32 gate and
    up [m, n]; ``scaled``: a last operand [1, m] float32); of a
    ``moe_tgmm`` the rows and the widths of its two operands (``swiglu``:
    the left one a gate and an up). ``out_dtype``: the result's; with
    ``SCALE_COTANGENT`` a second result, the rows' partial sums [n // tn,
    1, m] float32, with ``SWIGLU_COTANGENT`` two results [m, n], gate's and
    up's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tk, tn = plan
    visits = m // tm + g - 1
    out_dtype = jnp.dtype(out_dtype)
    lefts = 1 + swiglu
    if kernel == "moe_tgmm":
        body = functools.partial(_tgmm_kernel, tm=tm, visits=visits,
                                 swiglu=swiglu, fed=jnp.dtype(dtype))
        grid = (n // tn, k // tk, visits)
        in_specs = [
            pl.BlockSpec((tm, tk), lambda j, i, v, o, gr, t, r: (t[v], i))
        ] * lefts + [
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
            swiglu=swiglu, epilogue=epilogue, fed=jnp.dtype(dtype),
            dims=_NT if transposed else _NN)
        grid = (n // tn, visits, k_steps)
        if transposed:
            rhs = pl.BlockSpec(
                (None, tn, tk), lambda j, v, i, o, gr, t, r: (gr[v], j, i))
        else:
            rhs = pl.BlockSpec(
                (None, tk, tn), lambda j, v, i, o, gr, t, r: (gr[v], i, j))
        in_specs = ([
            pl.BlockSpec((tm, tk), lambda j, v, i, o, gr, t, r: (t[v], i))
        ] * lefts + [rhs]) * pairs
        block = pl.BlockSpec((tm, tn), lambda j, v, i, o, gr, t, r: (t[v], j))
        in_specs += [block] * {None: 0, SCALE_COTANGENT: 1,
                               SWIGLU_COTANGENT: 2}[epilogue]
        if scaled:
            in_specs.append(pl.BlockSpec(
                (1, tm), lambda j, v, i, o, gr, t, r: (0, t[v])))
        out_specs = block
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
        if epilogue == SCALE_COTANGENT:
            out_specs = [block, pl.BlockSpec(
                (None, 1, tm), lambda j, v, i, o, gr, t, r: (j, 0, t[v]))]
            out_shape = [out_shape, jax.ShapeDtypeStruct(
                (n // tn, 1, m), jnp.float32)]
        elif epilogue == SWIGLU_COTANGENT:
            out_specs, out_shape = [block] * 2, [out_shape] * 2
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
             transposed=False, scale=None, swiglu=False, epilogue=None,
             beside=()):
    """``kernel`` over ``operands`` (lhs, rhs; a pair: lhs, rhs, lhs, rhs;
    ``swiglu``: gate, up, rhs) at ``plan``, fed the right operand's type
    (``moe_tgmm``: the narrower operand's), its result in ``out_dtype``,
    its rows times ``scale`` [m] where there is one; counted. ``epilogue``
    in place of the plain store, reading ``beside``: ``SCALE_COTANGENT``
    the float32 cotangent [m, n] of the scaled product -> ``(cotangent *
    scale`` in ``out_dtype``, the scale's cotangent [m] float32);
    ``SWIGLU_COTANGENT`` the float32 gate and up [m, n] -> their
    cotangents ``(g_gate, g_up)`` in ``out_dtype``."""
    import jax.numpy as jnp

    lhs, rhs = operands[0], operands[1 + swiglu]
    m, k = lhs.shape
    g = group_sizes.shape[0]
    if kernel == "moe_tgmm":
        n = rhs.shape[1]
        fed = min(lhs.dtype, rhs.dtype, key=lambda t: t.itemsize)
    else:
        n = rhs.shape[1] if transposed else rhs.shape[2]
        fed = rhs.dtype
    operands += tuple(x.astype(jnp.float32) for x in beside)
    scaled = scale is not None
    if scaled:
        operands += (scale.astype(jnp.float32)[None, :],)
    out_dtype = jnp.dtype(out_dtype)
    fused = "+".join(name for name in (SWIGLU if swiglu else None, epilogue)
                     if name) or None
    _took_kernel(kernel, fed, out_dtype, scaled, plan, fused)
    out = _call(kernel, fed.name, out_dtype.name, m, k, n, g, plan,
                transposed, scaled, _pk._interpret(), epilogue, swiglu)(
                    group_sizes, *operands)
    if epilogue == SCALE_COTANGENT:
        scaled_cotangent, partial = out
        return scaled_cotangent, jnp.sum(partial, axis=(0, 1))
    return tuple(out) if epilogue else out


def _landed_rows(group_sizes, m):
    """A ``[m, 1]`` mask of the rows inside the groups: off the kernels,
    the rows past their end (which a kernel upstream may have left
    unwritten) are read as zeros and written as zeros, so nothing in them
    reaches a value or a gradient."""
    import jax.numpy as jnp

    return (jnp.arange(m) < jnp.sum(group_sizes))[:, None]


def _weights_gradient(lhs, g_out, group_sizes, plan):
    """``lhs^T . g_out`` per group, in the operands' type."""
    return _product("moe_tgmm", (lhs, g_out), group_sizes, plan, lhs.dtype)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` times ``rhs [g, k, n]`` by groups of rows ->
    ``[m, n]`` float32: the first ``group_sizes[0]`` rows meet ``rhs[0]``,
    the next ``group_sizes[1]`` rows ``rhs[1]``, and so on, up to the
    groups' end, ``sum(group_sizes) <= m``. Rows past the groups' end are
    UNSPECIFIED, in the value and in ``lhs``'s cotangent. The kernels do
    not store them (the tile the end cuts keeps what its block held there,
    nothing is written after it) and read none of them, so nothing in
    ``lhs``'s or the cotangent's rows past the end reaches ``rhs``'s
    gradient, not even a NaN; the fallback (``lax.ragged_dot``, which on
    the chip leaves a row outside every group unwritten) reads them as
    zeros and writes zeros there. The operands go to the MXU in their
    common type, accumulation is float32.

    The product and its gradients run as the ``moe_gmm`` / ``moe_tgmm``
    kernels (:func:`_plan` sizes their tiles from the shapes), under one
    ``jax.custom_vjp`` that keeps its operands and nothing the kernels
    make: the cotangents of ``lhs`` and ``rhs`` are written in the
    operands' type by the kernels. Routed to ``lax.ragged_dot``, and
    counted in ``pallas_kernels.FALLBACKS`` under ``moe_gmm``, when the
    kernels are disabled, a dimension is no multiple of 128, or no tiles
    fit the scoped VMEM. Every call site that takes a kernel is counted in
    ``GMM_CALLS`` with its types and tiles."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.result_type(lhs, rhs)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    (m, k), (g, _, n) = lhs.shape, rhs.shape
    size = dtype.itemsize
    plans = (_plan(m, k, n, g, size),
             _plan(m, n, k, g, size, out_itemsize=size),
             _plan(m, k, n, g, size, "moe_tgmm", out_itemsize=size))
    refusal = next((why for _, why in plans if why is not None), None)
    if refusal is not None:
        _pk._fallback("moe_gmm", refusal, (m, k, n, g))
        return _ragged_dot(lhs, rhs, group_sizes)
    forward, to_lhs, to_rhs = (plan for plan, _ in plans)

    def run(lhs, rhs, group_sizes):
        return _product("moe_gmm", (lhs, rhs), group_sizes, forward,
                        jnp.float32)

    def fwd(*operands):
        return run(*operands), operands

    def bwd(kept, g_out):
        lhs, rhs, group_sizes = kept
        g_out = g_out.astype(dtype)
        g_lhs = _product("moe_gmm", (g_out, rhs), group_sizes, to_lhs, dtype,
                         transposed=True)
        return g_lhs, _weights_gradient(lhs, g_out, group_sizes, to_rhs), None

    product = jax.custom_vjp(run)
    product.defvjp(fwd, bwd)
    return product(lhs, rhs, group_sizes)


def _ragged_dot(lhs, rhs, group_sizes):
    """``lax.ragged_dot`` into float32, the rows past the groups' end read
    and written as zeros (``_landed_rows``): the products' fallback."""
    import jax.numpy as jnp
    from jax import lax

    live = _landed_rows(group_sizes, lhs.shape[0])
    return jnp.where(live, lax.ragged_dot(
        jnp.where(live, lhs, 0), rhs, group_sizes,
        preferred_element_type=jnp.float32), 0.0)


def expert_mlp(x, tok, count, w_gate, w_up, w_down, group_sizes,
               row_scale, keep=None):
    """The held experts' gated MLPs over the sorted bucket, up to the sum
    back to the tokens -> ``[R, d]`` float32: the value of ``rows =
    x[tok]``, ``gate, up = rows . w_gate, rows . w_up`` and ``(silu(gate)
    * up) . w_down`` times ``row_scale``, each product by groups of rows
    (:func:`grouped_matmul`'s), under ONE ``jax.custom_vjp``, so that no
    pass over the bucket's rows is XLA's and none goes past the landed
    rows: the ``moe_take`` kernel gathers the first ``count`` rows in the
    weights' type; the down product forms its operand ``silu(gate) * up``
    from the float32 gate and up as it loads them (``SWIGLU``), so the
    hidden is never stored, and so does its rebuilt twin for the routing
    weight's cotangent (``SCALE_COTANGENT``) and the down weights'
    gradient; the hidden's cotangent is never stored either: its product's
    store writes gate's and up's cotangents through the SiLU, in the
    weights' type (``SWIGLU_COTANGENT``), which ``moe_gmm_pair`` and
    ``moe_tgmm`` read; ``x``'s cotangent is summed back to its rows by
    ``moe_combine``. ``keep(gate, up)`` names the two float32 values a
    checkpoint policy may keep (``ops/remat.py: offer``), inside the
    forward rule, where the backward pass reads them. The rows past the
    count are unspecified in the value, and nothing of them reaches a
    cotangent. The weights' type is the operands'; accumulation is
    float32. Where a plan refuses (or the kernels take nothing): that
    formula in XLA (the gather of every row, ``lax.ragged_dot`` with the
    rows past the count read and written as zeros, the SiLU), the refusal
    counted once in ``FALLBACKS`` under ``moe_gmm``."""
    import jax
    import jax.numpy as jnp

    keep = keep or (lambda *values: values)
    dtype = jnp.result_type(w_gate, w_up, w_down)
    w_gate, w_up, w_down = (w.astype(dtype) for w in (w_gate, w_up, w_down))
    (n, d), R, (g, _, f) = x.shape, tok.shape[0], w_gate.shape
    size, f32 = dtype.itemsize, jnp.float32
    plans = (_plan(R, d, f, g, size),
             _plan(R, f, d, g, size, scaled=True, swiglu=True),
             _plan(R, f, d, g, size, out_itemsize=size, scaled=True,
                   swiglu=True, epilogue=SCALE_COTANGENT),
             _plan(R, d, f, g, size, out_itemsize=size,
                   epilogue=SWIGLU_COTANGENT),
             _plan(R, f, d, g, size, "moe_tgmm", out_itemsize=size,
                   swiglu=True),
             _plan(R, f, d, g, size, "moe_gmm_pair",
                   out_itemsize=x.dtype.itemsize),
             _plan(R, d, f, g, size, "moe_tgmm", out_itemsize=size))
    refusal = next((why for _, why in plans if why is not None), None)
    if refusal is not None:
        _pk._fallback("moe_gmm", refusal, (R, d, f, g))
        rows = x[tok].astype(dtype)
        gate, up = keep(*(_ragged_dot(rows, w, group_sizes)
                          for w in (w_gate, w_up)))
        hidden = (jax.nn.silu(gate) * up).astype(dtype)
        return _ragged_dot(hidden, w_down, group_sizes) * row_scale[:, None]
    (up_plan, down, rebuilt, to_hidden, to_down, to_rows,
     to_gate_up) = (plan for plan, _ in plans)
    take = _take_how(n, d, R, x.dtype, dtype)
    back = _combine_how(n, d, R, x.dtype)

    def hidden(x, tok, count, w_gate, w_up, group_sizes):
        rows = _take(x, tok, count, dtype, *take)
        return (rows,) + tuple(
            _product("moe_gmm", (rows, w), group_sizes, up_plan, f32)
            for w in (w_gate, w_up))

    def down_product(gate, up, w_down, group_sizes, row_scale):
        return _product("moe_gmm", (gate, up, w_down), group_sizes, down,
                        f32, scale=row_scale, swiglu=True)

    def run(x, tok, count, w_gate, w_up, w_down, group_sizes, row_scale):
        _, gate, up = hidden(x, tok, count, w_gate, w_up, group_sizes)
        return down_product(gate, up, w_down, group_sizes, row_scale)

    def fwd(x, tok, count, w_gate, w_up, w_down, group_sizes, row_scale):
        rows, gate, up = hidden(x, tok, count, w_gate, w_up, group_sizes)
        out = down_product(gate, up, w_down, group_sizes, row_scale)
        # named where the forward reads them no more: ``jax.checkpoint``
        # copies a kept value that the forward goes on to read through a
        # ``reduce_precision`` of its own, a pass over the bucket
        return out, (rows, *keep(gate, up), tok, count, w_gate, w_up, w_down,
                     group_sizes, row_scale)

    def bwd(kept, g_out):
        (rows, gate, up, tok, count, w_gate, w_up, w_down, group_sizes,
         row_scale) = kept
        g_down, g_scale = _product(
            "moe_gmm", (gate, up, w_down), group_sizes, rebuilt, dtype,
            scale=row_scale, swiglu=True, epilogue=SCALE_COTANGENT,
            beside=(g_out,))
        g_gate, g_up = _product(
            "moe_gmm", (g_down, w_down), group_sizes, to_hidden, dtype,
            transposed=True, epilogue=SWIGLU_COTANGENT, beside=(gate, up))
        g_rows = _product("moe_gmm_pair", (g_gate, w_gate, g_up, w_up),
                          group_sizes, to_rows, x.dtype, transposed=True)
        return (_summed(g_rows, tok, n, count, *back).astype(x.dtype), None,
                None, _weights_gradient(rows, g_gate, group_sizes, to_gate_up),
                _weights_gradient(rows, g_up, group_sizes, to_gate_up),
                _product("moe_tgmm", (gate, up, g_down), group_sizes, to_down,
                         dtype, swiglu=True),
                None, g_scale.astype(row_scale.dtype))

    mlp = jax.custom_vjp(run)
    mlp.defvjp(fwd, bwd)
    return mlp(x, tok, count, w_gate, w_up, w_down, group_sizes, row_scale)


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


def _take_vmem(n, tb, dc, out_itemsize):
    """Bytes of scoped VMEM ``moe_take`` asks for: the ``[n, dc]`` float32
    slab of the source, the result's ``[tb, dc]`` block twice over, and
    where the result is narrower a float32 block the rows are gathered
    into before they are rounded."""
    return dc * (4 * n + 2 * tb * out_itemsize
                 + (4 * tb if out_itemsize < 4 else 0))


def _slab_plan(n, d, rows, dtype, vmem, least=8):
    """``((tb, dc), vmem_limit, refusal)`` of a kernel that holds a float32
    ``[n, dc]`` slab of ``n`` tokens' rows in VMEM while it walks ``rows``
    bucket rows in blocks of ``tb`` (at least ``least``): the widest slab
    whose ``vmem(tb, dc)`` fits ``_COMBINE_CAP``, under a limit the call
    names where it passes Mosaic's default ``_VMEM_LIMIT``. Or why the
    rows go to XLA (a ``FALLBACKS`` reason)."""
    import jax.numpy as jnp

    if not _pk.enabled():
        return None, None, "disabled"
    tb = next((t for t in (512, 256, 128, 64, 32, 16, 8)
               if t >= least and rows % t == 0), None)
    if jnp.dtype(dtype) != jnp.float32 or d % 128 or n % 8 or tb is None:
        return None, None, "untileable"
    mib = 1024 * 1024
    for dc in _divisors(d):
        need = vmem(tb, dc)
        if need <= _pk._VMEM_LIMIT:
            return (tb, dc), None, None
        if need <= _COMBINE_CAP:
            return (tb, dc), -(-need // mib) * mib, None
    return None, None, "vmem"


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
    return _slab_plan(n, d, rows, dtype,
                      lambda tb, dc: _combine_vmem(n, tb, dc))


def _take_plan(n, d, rows, dtype, out_dtype):
    """``((tb, dc), vmem_limit, refusal)`` of ``moe_take`` gathering
    ``rows`` bucket rows of width ``d`` from ``n`` float32 token rows into
    ``out_dtype``, as :func:`_combine_plan` plans the sum back (blocks of
    16 rows at the least, a bfloat16 sublane tile)."""
    import jax.numpy as jnp

    size = jnp.dtype(out_dtype).itemsize
    return _slab_plan(n, d, rows, dtype,
                      lambda tb, dc: _take_vmem(n, tb, dc, size), least=16)


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


def _take_kernel(tok, count, x_hbm, out_ref, held, done, *stage, tb, dc):
    """One block of ``tb`` bucket rows of ``moe_take`` in one column slab:
    each of its rows below ``count`` copied from its token's row of the
    slab, which one DMA brings into VMEM at the slab's first block, then
    rounded into the result's block where that is narrower (through the
    float32 ``stage``). A block past the count copies nothing; its index
    map holds the last block that has a row below the count, so nothing
    is written back for it."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slab, block = pl.program_id(0), pl.program_id(1)

    @pl.when(block == 0)
    def _load():
        copy = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(pl.multiple_of(slab * dc, 128), dc)], held,
            done)
        copy.start()
        copy.wait()

    rows_ref = stage[0] if stage else out_ref

    def take(r, carry):
        rows_ref[pl.ds(r, 1), :] = held[pl.ds(tok[block * tb + r], 1), :]
        return carry

    def take_unrolled(i, carry):
        for u in range(_UNROLL):
            take(i * _UNROLL + u, carry)
        return carry

    here = jnp.clip(count[0] - block * tb, 0, tb)
    whole = here // _UNROLL
    lax.fori_loop(0, whole, take_unrolled, 0)
    lax.fori_loop(whole * _UNROLL, here, take, 0)
    if stage:
        def narrow(c, carry):
            at = pl.multiple_of(c * 16, 16)
            out_ref[pl.ds(at, 16), :] = stage[0][pl.ds(at, 16), :].astype(
                out_ref.dtype)
            return carry

        lax.fori_loop(0, (here + 15) // 16, narrow, 0)


@functools.lru_cache(maxsize=None)
def _take_call(n, d, rows, out_dtype, plan, limit, interpret):
    """``moe_take`` at one setting, jitted over (x [n, d] float32, the rows'
    tokens [rows] int32, how many rows to gather [1] int32) -> [rows, d]
    ``out_dtype``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tb, dc = plan
    out_dtype = jnp.dtype(out_dtype)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=limit)}
    call = pl.pallas_call(
        functools.partial(_take_kernel, tb=tb, dc=dc),
        out_shape=jax.ShapeDtypeStruct((rows, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(d // dc, rows // tb),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            # a block past the count holds the block of row count - 1
            out_specs=pl.BlockSpec((tb, dc), lambda s, b, tok, count: (
                jnp.minimum(b, jnp.maximum(count[0] - 1, 0) // tb), s)),
            scratch_shapes=[pltpu.VMEM((n, dc), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]
            + [pltpu.VMEM((tb, dc), jnp.float32)] * (out_dtype.itemsize < 4)),
        interpret=interpret, name="moe_take", **params)
    return jax.jit(lambda x, tok, count: call(tok, count, x))


def _take(x, tok, count, dtype, plan, limit):
    """``x[tok]`` in ``dtype``, the rows from ``count`` on UNSPECIFIED:
    ``moe_take`` at ``plan``, counted, or with no plan XLA's gather (of
    every row)."""
    import jax.numpy as jnp

    if plan is None:
        return x[tok].astype(dtype)
    dtype = jnp.dtype(dtype)
    _took_kernel("moe_take", x.dtype, dtype, False, plan)
    return _take_call(x.shape[0], x.shape[1], tok.shape[0], dtype.name, plan,
                      limit, _pk._interpret())(
        x, tok, jnp.full((1,), count, jnp.int32))


def _take_how(n, d, rows, dtype, out_dtype):
    """``_take_plan``'s plan and limit, having counted a refusal in
    ``FALLBACKS`` (the plan is then None)."""
    plan, limit, refusal = _take_plan(n, d, rows, dtype, out_dtype)
    if refusal is not None:
        _pk._fallback("moe_take", refusal, (n, d, rows))
    return plan, limit


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
    transpose, under a ``jax.custom_vjp``, is the gather ``g[tok]`` by the
    ``moe_take`` kernel, which writes the first ``count`` rows alone (the
    cotangent of the rows past the count is unspecified). Kernels off, a
    type other than float32 or a width that is no multiple of 128: the
    scatter-add itself, counted in ``pallas_kernels.FALLBACKS`` under
    ``moe_combine``."""
    import jax

    R, d = rows.shape
    plan, limit = _combine_how(n, d, R, rows.dtype)
    if plan is None:
        return _summed(rows, tok, n, count, None, None)
    take = _take_how(n, d, R, rows.dtype, rows.dtype)

    def summed(rows, tok, count):
        return _summed(rows, tok, n, count, plan, limit)

    run = jax.custom_vjp(summed)
    run.defvjp(lambda rows, tok, count: (summed(rows, tok, count),
                                         (tok, count)),
               lambda kept, g: (_take(g, *kept, g.dtype, *take), None, None))
    return run(rows, tok, count)
