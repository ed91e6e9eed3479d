"""Hand-written Pallas TPU kernels for the hot ops.

This is the TPU-native analog of the reference's cuDNN fast paths: the
reference swaps in ``cudnn_*-inl.h`` implementations at op-creation time
when USE_CUDNN is set (ref: src/operator/convolution.cc op-creation switch,
SURVEY §2.5); we swap in Pallas kernels when running on a TPU backend.
XLA already fuses elementwise chains into matmuls/convs (that is mshadow's
expression-template job, SURVEY §2.13), so kernels here are reserved for
patterns XLA does not schedule optimally by itself:

- ``flash_attention``: blockwise softmax(QK^T)V with running log-sum-exp
  accumulation in VMEM — avoids materialising the [T, T] score matrix in
  HBM. Used by the transformer flagship model and available to user code.
- ``fused_softmax``: one-pass row softmax (max/exp/sum/div in VMEM) used by
  SoftmaxOutput's forward on large vocabularies.

Enable/disable with MXNET_PALLAS=1/0; by default kernels are active only
when the default device is a TPU. Off-TPU (tests) the kernels run in
Pallas interpret mode so CPU CI exercises the same code path; on a TPU
``interpret`` is never set.
A shape a kernel cannot take is routed to the plain XLA implementation
by an explicit rule, and every such routing is counted in ``FALLBACKS``
(mxtel ``pallas.fallback_total.<kernel>.<reason>``) and logged once per
distinct shape — same contract as the reference falling back to the
non-cuDNN path, minus the silence.
"""
from __future__ import annotations

import functools
import logging
import os

from .. import telemetry as _tel

__all__ = ["enabled", "flash_attention", "flash_kernel_usable",
           "fused_softmax", "FALLBACKS"]

log = logging.getLogger("mxnet_tpu.pallas")

#: Mosaic's default scoped-VMEM limit on v5e ("limit 16.00M" in the
#: compiler's RESOURCE_EXHAUSTED message). Every operand block of a
#: pallas_call is double-buffered against it by the pipeline.
_VMEM_LIMIT = 16 * 1024 * 1024
#: the most scoped VMEM a flash call may ask Mosaic for in place of that
#: default (``vmem_limit_bytes``), when its operands fit at no block under
#: it: half of the 128 MiB of VMEM a v5e TensorCore has (the chip's
#: published figure; the other half stays with whatever XLA keeps there
#: around the kernel). Whole-length operands past it go to XLA as before.
_VMEM_CAP = 64 * 1024 * 1024
#: what such a call asks for over ``_flash_vmem``'s footprint: the
#: compiler's own scratch beside what the model counts
_VMEM_MARGIN = 1.25

#: (kernel, reason) -> number of call sites routed to XLA instead of the
#: kernel. Decisions are made while tracing, so this counts traces, not
#: executions. Plain ints so a run without telemetry can assert on them
#: (chip_smoke.py does).
FALLBACKS = {}
_logged = set()


def _fallback(kernel, reason, shape):
    """Count one routing of ``kernel`` to its XLA implementation."""
    FALLBACKS[(kernel, reason)] = FALLBACKS.get((kernel, reason), 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.fallback_total.%s.%s" % (kernel, reason)).inc()
    if (kernel, reason, shape) not in _logged:
        _logged.add((kernel, reason, shape))
        # with the kernels off (the CPU default) every call lands here
        level = logging.DEBUG if reason == "disabled" else logging.WARNING
        log.log(level, "%s%s -> XLA (%s)", kernel, shape, reason)


def _on_tpu():
    """True when computation actually lands on TPU: honours the pinned
    default device (``jax.default_backend()`` alone is the wrong signal)."""
    from ..context import default_jax_device

    return default_jax_device().platform == "tpu"


def enabled():
    v = os.environ.get("MXNET_PALLAS", "").strip().lower()
    if v in ("0", "false", "off"):
        return False
    if v in ("1", "true", "on"):
        return True
    return _on_tpu()


def _interpret():
    """Interpret mode off-TPU so the kernels are testable on CPU."""
    return not _on_tpu()


def _env_int(name, default):
    """Int env knob; malformed/empty values fall back to the default
    (a bad export of a probe knob must not take the kernels down)."""
    try:
        v = os.environ.get(name, "")
        return int(v) if v.strip() else default
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def _attention_reference(q, k, v, causal, scale, window=None):
    """Plain XLA attention, also the backward path for the Pallas forward.
    Fewer k/v heads than q heads are repeated by group; ``window``: a
    causal query sees its last ``window`` keys, itself included."""
    import jax.numpy as jnp

    group = q.shape[1] // k.shape[1] if q.ndim == 4 else 1
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        iq = jnp.arange(tq)[:, None]
        ik = jnp.arange(tk)[None, :]
        seen = ik <= iq
        if window is not None:
            seen = seen & (ik > iq - window)
        scores = jnp.where(seen, scores, -1e30)
    import jax

    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


#: (kernel, operand type of its products, (tiles visited, tiles the mask
#: is applied on, tiles of the square) per head, in tiles of step x step[,
#: the scoped-VMEM limit the call names, where it names one]) -> number of
#: call sites that took the kernel. Filled while tracing,
#: like ``FALLBACKS``: it says which mechanism a compiled step holds
#: (chip_smoke.py asserts on it), not how often it ran.
FLASH_CALLS = {}


def _operand_dtype(dtype):
    """What the kernels' products are fed: bfloat16 inputs go to the MXU
    as they are stored, anything else as float32. Accumulation and the
    softmax's statistics are float32 either way."""
    import jax.numpy as jnp

    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _fold_scale(dtype, scale):
    """Whether ``scale`` is multiplied into the q (or k) block before the
    product, instead of into every score after it: in float32 always, in
    bfloat16 only when that is exact (a power of two; 0.125 at d=64)."""
    import math

    import jax.numpy as jnp

    return (_operand_dtype(dtype) == jnp.float32
            or math.frexp(scale)[0] == 0.5)


def _least(a, b):
    import jax.numpy as jnp

    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _most(a, b):
    import jax.numpy as jnp

    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _q_side_steps(row0, block, step, window):
    """``(start, edge_end, end)``: the k/v steps wholly before the block of
    q positions ``[row0, row0 + block)`` that a windowed forward or dq
    kernel visits. ``[start, edge_end)`` hold a key that some query of the
    block no longer sees (``k <= q - window``: masked), ``[edge_end, end)``
    are seen whole. Steps before ``start`` hold no visible key. Python ints
    (``_tile_counts``) or traced scalars (the kernels) alike."""
    end = row0 // step
    start = _most(row0 - window + 1, 0) // step
    edge_end = _most(row0 + block + step - 1 - window, 0) // step
    return start, _least(end, _most(start, edge_end)), end


def _k_side_steps(col0, block, step, window, n_steps):
    """``(first, clean_end, end)``: the q steps wholly after the block of k
    positions ``[col0, col0 + block)`` that a windowed dkv kernel visits:
    ``[first, clean_end)`` see the whole block, ``[clean_end, end)`` hold a
    query that no longer sees its first keys (masked). Steps from ``end``
    on see none of it."""
    first = (col0 + block) // step
    end = _least(n_steps, (col0 + block + window - 2) // step + 1)
    return first, _least(end, _most(first, (col0 + window) // step)), end


def _tile_counts(t_own, t_other, step, causal, window=None, block=None,
                 k_side=False):
    """(visited, masked, square) tiles of one head, in tiles of ``step``
    x ``step``: what a kernel's loops below cover of the score square. A
    causal kernel visits the tiles on and under the diagonal and applies
    the mask on the diagonal's own tiles only. With a ``window`` the count
    follows the windowed kernels' loops, a block of ``block`` positions at
    a time (``k_side``: the dkv kernel's, which owns k positions): the
    steps wholly outside the window are not visited, and the mask is also
    applied on the steps its edge crosses, over the block's whole width."""
    n_own, n_other = t_own // step, t_other // step
    if not causal:
        return n_own * n_other, 0, n_own * n_other
    if window is None:
        return n_own * (n_own + 1) // 2, n_own, n_own * n_other
    wide = block // step
    visited = masked = 0
    for at in range(0, t_own, block):
        if k_side:
            first, clean_end, end = _k_side_steps(at, block, step, window,
                                                  n_other)
            edge = end - clean_end
        else:
            first, edge_end, end = _q_side_steps(at, block, step, window)
            edge = edge_end - first
        visited += (end - first) * wide
        masked += edge * wide
        for i in range(1, wide + 1):  # the block's own steps, i tiles each
            visited += i
            masked += i if window <= i * step - 1 else 1
    return visited, masked, n_own * n_other


def _took_kernel(kernel, dtype, tiles, vmem_limit=None):
    """Count one call site that took ``kernel``; a call that asks for its
    own scoped-VMEM limit carries it as a fourth part of the key."""
    import jax.numpy as jnp

    operand = jnp.dtype(_operand_dtype(dtype)).name
    key = (kernel, operand, tiles)
    if vmem_limit is not None:
        key += (vmem_limit,)
    FLASH_CALLS[key] = FLASH_CALLS.get(key, 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.kernel_total.%s.%s" % (kernel, operand)).inc()


# The kernels build their score tiles transposed, st[k, q]: k
# positions down the sublanes, q positions along the lanes. What is kept
# per q position (running max and sum, lse, dcap) is then a lane-dense
# row that broadcasts down the sublanes as it is stored, and the
# accumulators are [d, positions] with every lane in use. And it decides
# which products fill the MXU: contraction over d (q.k^T, dO.v^T) uses
# d of its 128 rows whatever the layout, but a product whose OUTPUT is d
# wide (p.v, ds.k, p^T.dO, ds^T.q) streams d rows through full 128 x 128
# tiles of p or ds when written [d, n] = x^T[d, m] . p[m, n], where
# [n, d] = p[n, m] . x[m, d] would fill d of 128 columns.
_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # [m, d] x [m, n] -> [d, n]
_TT = (((0,), (1,)), ((), ()))  # [m, d] x [n, m] -> [d, n]


def _dot(a, b, dims):
    """A product on the MXU: operands as they come, float32 out."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _under_diagonal(square):
    """``square`` [n, n] of transposed scores whose first row and first
    column are the same position: keep k <= q."""
    import jax.numpy as jnp
    from jax import lax

    k = lax.broadcasted_iota(jnp.int32, square.shape, 0)
    q = lax.broadcasted_iota(jnp.int32, square.shape, 1)
    return jnp.where(k <= q, square, -1e30)


def _in_window(st, off, window, diagonal=False):
    """``st`` [k, q] of transposed scores whose first row's position is
    ``off`` (a scalar, traced or not) past its first column's: keep
    ``k > q - window``, and ``k <= q`` too where the tile holds the
    diagonal."""
    import jax.numpy as jnp
    from jax import lax

    ahead = (lax.broadcasted_iota(jnp.int32, st.shape, 0)
             - lax.broadcasted_iota(jnp.int32, st.shape, 1) + off)  # k - q
    keep = ahead > -window
    if diagonal:
        keep = keep & (ahead <= 0)
    return jnp.where(keep, st, -1e30)


def _edge_loops(tile, carry, step, lo, mid, hi, edge_first):
    """``tile`` over the steps ``[lo, mid)`` and then ``[mid, hi)`` of a
    windowed kernel; the part the window's edge crosses (``edge=True``:
    masked) comes first on the q side and last on the k side."""
    from jax import lax

    carry = lax.fori_loop(
        lo, mid, lambda i, c: tile(i * step, c, edge=edge_first), carry)
    return lax.fori_loop(
        mid, hi, lambda i, c: tile(i * step, c, edge=not edge_first), carry)


def _put_lanes(old, new, lo, hi):
    """``old`` with its lanes ``[lo, hi)`` replaced by ``new``."""
    import jax.numpy as jnp

    parts = [old[:, :lo], new, old[:, hi:]]
    parts = [x for x in parts if x.shape[1]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block, step, n_steps, window=None):
    """One block of ``block`` q positions against k/v, ``step`` positions
    at a time. Causal: the steps wholly before the block in a loop, then
    the block's own ``block // step`` steps unrolled, each against the q
    lanes from its diagonal on (lanes before it see nothing of it) and
    masked on its diagonal tile alone. With a ``window`` the loop starts at
    the first step that holds a visible key and masks the steps the
    window's edge crosses (``_q_side_steps``); a row that sees nothing of
    such a step gathers ones under the starting maximum, which the first
    key it does see (it always sees itself) scales to nothing."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    op = _operand_dtype(q_ref.dtype)
    fold = _fold_scale(q_ref.dtype, scale)
    row0 = pl.program_id(1) * block
    q = q_ref[0].astype(op)  # [block, d]
    if fold:
        q = q * scale

    def tile(k0, carry, lo=0, diagonal=False, edge=False):
        # the running max and sum ride 8 sublanes deep, [8, block] with
        # every row the same: lanes of a one-row value cannot be sliced
        acc, l, m = carry  # [dv, block], [8, block], [8, block]
        kblk = k_ref[0, pl.ds(k0, step), :].astype(op)
        vblk = v_ref[0, pl.ds(k0, step), :].astype(op)
        st = _dot(kblk, q[lo:], _NT)
        if not fold:
            st = st * scale
        if edge:
            st = _in_window(st, 0 if diagonal else k0 - row0, window,
                            diagonal)
        elif diagonal:
            st = _put_lanes(st, _under_diagonal(st[:, :step]), 0, step)
        m_old, l_old = m[:, lo:], l[:, lo:]
        m_new = jnp.maximum(m_old, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new[0:1])
        alpha = jnp.exp(m_old - m_new)
        l_new = l_old * alpha + jnp.sum(pt, axis=0, keepdims=True)
        pv = _dot(vblk, pt.astype(op), _TN)
        acc_new = acc[:, lo:] * alpha[0:1] + pv
        return (_put_lanes(acc, acc_new, lo, block),
                _put_lanes(l, l_new, lo, block),
                _put_lanes(m, m_new, lo, block))

    carry = (jnp.zeros((v_ref.shape[-1], block), jnp.float32),
             jnp.zeros((8, block), jnp.float32),
             jnp.full((8, block), -1e30, jnp.float32))
    if window is None:
        carry = lax.fori_loop(0, row0 // step if causal else n_steps,
                              lambda i, c: tile(i * step, c), carry)
    else:
        carry = _edge_loops(tile, carry, step, *_q_side_steps(
            row0, block, step, window), edge_first=True)
    if causal:
        for lo in range(0, block, step):
            carry = tile(row0 + lo, carry, lo, diagonal=True,
                         edge=window is not None and window < block - lo)
    acc, l, m = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[0:1]).T.astype(o_ref.dtype)
    # log-sum-exp per row: the backward reconstructs p = exp(s - lse).
    # Stored 8 rows deep like the running statistics: Mosaic requires the
    # last-two block dims be (8k, 128k) or full, so a (1, block) row
    # block would not lower.
    lse_ref[0] = m + jnp.log(l)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                         dq_ref, *, scale, causal, block, step, n_steps,
                         window=None):
    """dQ for one block of q positions: stream k/v as the forward does
    (under a ``window`` too), rebuild p from the saved lse, accumulate
    k^T.ds (flash-attention backward, q side)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    op = _operand_dtype(q_ref.dtype)
    fold = _fold_scale(q_ref.dtype, scale)
    row0 = pl.program_id(1) * block
    q = q_ref[0].astype(op)      # [block, d]
    if fold:
        q = q * scale
    do = do_ref[0].astype(op)    # [block, dv]

    def tile(k0, acc, lo=0, diagonal=False, edge=False):
        kblk = k_ref[0, pl.ds(k0, step), :].astype(op)
        vblk = v_ref[0, pl.ds(k0, step), :].astype(op)
        st = _dot(kblk, q[lo:], _NT)
        if not fold:
            st = st * scale
        if edge:
            st = _in_window(st, 0 if diagonal else k0 - row0, window,
                            diagonal)
        elif diagonal:
            st = _put_lanes(st, _under_diagonal(st[:, :step]), 0, step)
        # lse, and dcap = rowsum(dO * O): one of their 8 equal rows
        pt = jnp.exp(st - lse_ref[0, 0:1, lo:])
        dpt = _dot(vblk, do[lo:], _NT)
        dst = (pt * (dpt - dcap_ref[0, 0:1, lo:])).astype(op)
        new = acc[:, lo:] + _dot(kblk, dst, _TN)
        return _put_lanes(acc, new, lo, block)

    acc = jnp.zeros((q.shape[1], block), jnp.float32)  # [d, block]
    if window is None:
        acc = lax.fori_loop(0, row0 // step if causal else n_steps,
                            lambda i, c: tile(i * step, c), acc)
    else:
        acc = _edge_loops(tile, acc, step, *_q_side_steps(
            row0, block, step, window), edge_first=True)
    if causal:
        for lo in range(0, block, step):
            acc = tile(row0 + lo, acc, lo, diagonal=True,
                       edge=window is not None and window < block - lo)
    dq_ref[0] = (acc * scale).T.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                          *refs, scale, causal, block, step, n_steps,
                          window=None):
    """dK/dV for one block of ``block`` k positions: stream q/dO ``step``
    positions at a time, accumulate dO^T.p and q^T.ds (flash-attention
    backward, k side). Causal: the block's own ``block // step`` steps
    unrolled, each against the k rows up to its diagonal (rows after it
    are seen by nothing of it) and masked on its diagonal tile alone,
    then the steps wholly after the block in a loop; with a ``window``
    that loop ends at the last step that holds a query which sees the
    block, and the steps the window's trailing edge crosses are masked
    (``_k_side_steps``). One q head's part: under grouped queries the
    results are float32 and the group is summed outside.

    ``refs`` is ``(dk_ref, dv_ref)``, or ``(dq_ref, dk_ref, dv_ref,
    dq_acc)`` for ``flash_bwd_dqkv``, the whole backward in one kernel:
    each visited tile also adds ds^T.k into its q rows of ``dq_acc``, a
    float32 [tq, d] scratch that lives across the head's k blocks (the
    grid runs them in order), zeroed at the first and written to the
    head's resident dQ block at the last. dQ is one q head's own: under
    grouped queries nothing is summed. It is [tq, d] and not [d, tq] as
    the other accumulators: k^T.ds, which reads the k block transposed
    where the scores read it as it is, stops the TPU compiler with an
    internal error at d = 128 and 1024-row blocks (AOT)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    dq_ref, dk_ref, dv_ref, dq_acc = (
        refs if len(refs) == 4 else (None,) + refs + (None,))
    op = _operand_dtype(q_ref.dtype)
    fold = _fold_scale(q_ref.dtype, scale)
    col0 = pl.program_id(1) * block
    kblk = k_ref[0].astype(op)   # [block, d]
    if fold:
        kblk = kblk * scale
    vblk = v_ref[0].astype(op)   # [block, dv]
    if dq_ref is not None:
        @pl.when(pl.program_id(1) == 0)
        def _():
            dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def tile(q0, carry, hi=block, diagonal=False, edge=False):
        dk, dv = carry  # [d, block], [dv, block]
        lanes = pl.ds(q0, step)
        q = q_ref[0, lanes, :].astype(op)
        do = do_ref[0, lanes, :].astype(op)
        lse = lse_ref[0, 0:1, lanes]     # [1, step]
        dcap = dcap_ref[0, 0:1, lanes]
        st = _dot(kblk[:hi], q, _NT)
        if not fold:
            st = st * scale
        if edge:
            st = _in_window(st, step - hi if diagonal else col0 - q0,
                            window, diagonal)
        elif diagonal:
            below = _under_diagonal(st[hi - step:])
            st = (jnp.concatenate([st[:hi - step], below], axis=0)
                  if hi > step else below)
        pt = jnp.exp(st - lse)
        dv_new = dv[:, :hi] + _dot(do, pt.astype(op), _TT)
        dpt = _dot(vblk[:hi], do, _NT)
        dst = (pt * (dpt - dcap)).astype(op)
        dk_new = dk[:, :hi] + _dot(q, dst, _TT)
        if dq_ref is not None:
            dq_acc[lanes, :] += _dot(dst, kblk[:hi], _TN)
        return _put_lanes(dk, dk_new, 0, hi), _put_lanes(dv, dv_new, 0, hi)

    carry = (jnp.zeros(kblk.shape[::-1], jnp.float32),
             jnp.zeros(vblk.shape[::-1], jnp.float32))
    if causal:
        for hi in range(step, block + step, step):
            carry = tile(col0 + hi - step, carry, hi, diagonal=True,
                         edge=window is not None and window < hi)
    if window is None:
        carry = lax.fori_loop((col0 + block) // step if causal else 0,
                              n_steps, lambda j, c: tile(j * step, c), carry)
    else:
        carry = _edge_loops(tile, carry, step, *_k_side_steps(
            col0, block, step, window, n_steps), edge_first=False)
    dk, dv = carry
    # dk = scale * ds^T.q whether the scale went into k or into the scores
    dk_ref[0] = (dk * scale).T.astype(dk_ref.dtype)
    dv_ref[0] = dv.T.astype(dv_ref.dtype)
    if dq_ref is not None:
        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _():
            # dq = scale * ds.k, the scale already in kblk where it was
            # folded; out a step of q positions at a time, so that no
            # second [tq, d] float32 value is held beside the accumulator
            def put(j, carry):
                rows = pl.ds(pl.multiple_of(j * step, step), step)
                part = dq_acc[rows, :]
                if not fold:
                    part = part * scale
                dq_ref[0, rows, :] = part.astype(dq_ref.dtype)
                return carry

            lax.fori_loop(0, n_steps, put, 0)


@functools.lru_cache(maxsize=None)
def _flash_call(name, dtype, bh, tq, tk, d, dv, causal, scale, block, step,
                interpret, group=1, window=None, vmem_limit=None):
    """One of the four kernels at one setting, as a jitted pallas_call.
    Cached, so that a model's layers share it: its body is then traced
    and lowered once a program and not once a call site (24 layers are
    72 sites; a warm gpt2-medium set-up spent 1.2 s more on them than
    the parent's before this; my chip runs, PR 28).

    ``group`` q heads read one k/v head (``bh`` counts q heads; k and v
    come with ``bh // group``): the index maps send program ``i`` to k/v
    head ``i // group``, nothing is copied. The dkv and dqkv kernels then
    give one q head's dk and dv, in float32, for the caller to sum over
    the group. dqkv's dQ block is the head's whole [tq, d], resident
    across the k blocks of the grid's second axis, which Mosaic runs in
    order (its default, "arbitrary"), as the accumulator needs. With a
    ``window`` the call is named ``flash_win_*``, so that a trace tells
    the windowed layers' device time from the others'. ``vmem_limit``
    (``_flash_plan`` names it where no block fits under the default) is
    handed to Mosaic as the call's scoped-VMEM limit; None passes no
    compiler parameter at all."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # a group of one maps as before it could be grouped: no operation
    kv_head = (lambda i: i) if group == 1 else (lambda i: i // group)

    def whole(rows, width):
        return pl.BlockSpec((1, rows, width), lambda i, j: (i, 0, 0))

    def whole_kv(rows, width):
        return pl.BlockSpec((1, rows, width),
                            lambda i, j: (kv_head(i), 0, 0))

    def blocked(width):
        return pl.BlockSpec((1, block, width), lambda i, j: (i, j, 0))

    def blocked_kv(width):
        return pl.BlockSpec((1, block, width),
                            lambda i, j: (kv_head(i), j, 0))

    stats = pl.BlockSpec((1, 8, block), lambda i, j: (i, 0, j))
    k_side = name in ("flash_bwd_dkv", "flash_bwd_dqkv")
    own, other = (tk, tq) if k_side else (tq, tk)
    part = dtype if group == 1 else jnp.float32  # one q head's dk and dv
    k_side_in = [whole(tq, d), blocked_kv(d), blocked_kv(dv), whole(tq, dv),
                 whole(8, tq), whole(8, tq)]
    dkv_out = (jax.ShapeDtypeStruct((bh, tk, d), part),
               jax.ShapeDtypeStruct((bh, tk, dv), part))
    body, in_specs, out_specs, out_shape = {
        "flash_fwd": (
            _flash_fwd_kernel,
            [blocked(d), whole_kv(tk, d), whole_kv(tk, dv)],
            (blocked(dv), stats),
            (jax.ShapeDtypeStruct((bh, tq, dv), dtype),
             jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32))),
        "flash_bwd_dq": (
            _flash_bwd_dq_kernel,
            [blocked(d), whole_kv(tk, d), whole_kv(tk, dv), blocked(dv),
             stats, stats],
            blocked(d),
            jax.ShapeDtypeStruct((bh, tq, d), dtype)),
        "flash_bwd_dkv": (
            _flash_bwd_dkv_kernel, k_side_in, (blocked(d), blocked(dv)),
            dkv_out),
        "flash_bwd_dqkv": (
            _flash_bwd_dkv_kernel, k_side_in,
            (whole(tq, d), blocked(d), blocked(dv)),
            (jax.ShapeDtypeStruct((bh, tq, d), dtype),) + dkv_out),
    }[name]
    scratch = ([pltpu.VMEM((tq, d), jnp.float32)]
               if name == "flash_bwd_dqkv" else [])
    params = {} if vmem_limit is None or interpret else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)}
    return jax.jit(pl.pallas_call(
        functools.partial(body, scale=scale, causal=causal, block=block,
                          step=step, n_steps=other // step, window=window),
        out_shape=out_shape, grid=(bh, own // block), in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch, interpret=interpret,
        name=_kernel_name(name, window), **params))


def _kernel_name(name, window):
    return name if window is None else name.replace("flash_", "flash_win_")


def _flash_attention_pallas(q, k, v, causal, scale, block, step, window=None,
                            vmem_limit=None):
    """Forward kernel; returns (o, lse) with lse saved for the backward."""
    b, h, tq, d = q.shape
    hkv, tk, dv = k.shape[1], k.shape[2], v.shape[-1]
    bh = b * h
    _took_kernel(_kernel_name("flash_fwd", window), q.dtype,
                 _tile_counts(tq, tk, step, causal, window, block),
                 vmem_limit)
    out, lse = _flash_call("flash_fwd", q.dtype.name, bh, tq, tk, d, dv,
                           causal, scale, block, step, _interpret(),
                           h // hkv, window, vmem_limit)(
        q.reshape(bh, tq, d), k.reshape(b * hkv, tk, d),
        v.reshape(b * hkv, tk, dv))
    return out.reshape(b, h, tq, dv), lse  # lse: (b*h, 8, tq)


def _flash_attention_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                block, step, window=None, vmem_limit=None,
                                fused=True):
    """Blockwise backward: no kernel materialises the [T, T] score matrix
    in HBM — the cliff the dense-vjp fallback hits at long T. ``fused``:
    one ``flash_bwd_dqkv`` call, which builds each tile's S and dP once
    for all three gradients (5 products a tile); otherwise the
    ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair, which builds them in each
    (7), where the plan keeps it (``_flash_plan``). Under grouped
    queries dk and dv come from the kernel a q head at a time in float32
    and are summed over each group here, before their one rounding to
    the operands' type."""
    import jax.numpy as jnp

    b, h, tq, d = q.shape
    hkv, tk, dv = k.shape[1], k.shape[2], v.shape[-1]
    bh, group = b * h, h // hkv
    operands = (q.reshape(bh, tq, d), k.reshape(b * hkv, tk, d),
                v.reshape(b * hkv, tk, dv), g.reshape(bh, tq, dv), lse,
                # D_i = rowsum(dO * O): one fused elementwise+reduce pass
                # in XLA, broadcast to lse's 8-row stats layout
                jnp.broadcast_to(
                    jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1).reshape(bh, 1, tq), (bh, 8, tq)))
    setting = (q.dtype.name, bh, tq, tk, d, dv, causal, scale)
    # the k side owns blocks of k and steps through q: the same two
    # sizes, fitted to the other length where the two differ
    k_block, k_step, _ = _select_blocks(tk, tq, block, step)
    k_tiles = _tile_counts(tk, tq, k_step, causal, window, k_block,
                           k_side=True)
    if fused:
        _took_kernel(_kernel_name("flash_bwd_dqkv", window), q.dtype,
                     k_tiles, vmem_limit)
        dq, dk, dv_ = _flash_call(
            "flash_bwd_dqkv", *setting, k_block, k_step, _interpret(), group,
            window, vmem_limit)(*operands)
    else:
        _took_kernel(_kernel_name("flash_bwd_dq", window), q.dtype,
                     _tile_counts(tq, tk, step, causal, window, block),
                     vmem_limit)
        dq = _flash_call("flash_bwd_dq", *setting, block, step,
                         _interpret(), group, window, vmem_limit)(*operands)
        _took_kernel(_kernel_name("flash_bwd_dkv", window), q.dtype,
                     k_tiles, vmem_limit)
        dk, dv_ = _flash_call("flash_bwd_dkv", *setting, k_block, k_step,
                              _interpret(), group, window,
                              vmem_limit)(*operands)
    if group > 1:
        dk, dv_ = (x.reshape(b, hkv, group, tk, -1).sum(axis=2).astype(
            q.dtype) for x in (dk, dv_))
    return (dq.reshape(b, h, tq, d), dk.reshape(b, hkv, tk, d),
            dv_.reshape(b, hkv, tk, dv))


def _select_blocks(tq, tk, block_q=None, block_k=None):
    """Resolve flash block sizes for a (tq, tk) problem.

    Returns ``(block_q, block_k, ok)``; ``ok=False`` means no legal tiling
    exists and the caller must use the dense path. A program of the
    forward and dq kernels owns ``block_q`` q positions and steps through
    k/v ``block_k`` positions at a time; the dkv kernel is their mirror
    image with the same two numbers (it owns ``block_q`` k positions and
    steps through q), so it asks with the lengths swapped.

    - Defaults 1024 and 512 (``_flash_plan`` halves the first while the
      operands overflow VMEM). Device time of the three kernels, forward
      and ``jax.grad``, causal, bf16, on one v5e (tools/flash_probe.py; my
      chip runs, PR 28), ms a call at [8,16,1024,64] / [1,16,8192,64]:
      1024x512 1.13 / 6.46, 512x512 1.40 / 7.19, 1024x256 1.32 / 8.44,
      512x256 1.83 / 10.7, 256x256 2.62 / 15.5 (2048x512: Mosaic refuses,
      VMEM). The q positions ride the lanes of the score tile and the
      four MXUs split a product by its 128-lane column tiles, so narrow
      blocks starve them; a program costs several hundred cycles besides.
      At 256-wide keys AND values, [1,20,8192,256], where no block fits
      the default scoped VMEM and ``_flash_plan`` names the calls' limit
      instead (my chip runs, PR 36): 1024x512 19.37 (fwd 4.69, dq 6.27,
      dkv 8.41), 512x512 20.07, 256x256 28.01: the defaults stand. At
      [1,32,8192,192] x 192 the same way 1024x512 reads 27.67.
      Earlier rounds' claims for the float32 body on another chip
      (docs/perf_analysis.md r4/r5: "block_k 128 -> 512 +19% tokens/s at
      T=1024, +54% at T=8192; block_q 1024 +5 MFU points at T=8192") were
      not re-measured and do not describe this body.
    - ``block_k`` is capped at 512: a [1024, 1024] float32 score tile is
      4 MiB, and the kernels hold two or three.
    - ``block_k`` divides ``block_q``: a causal kernel unrolls its own
      block's ``block_q // block_k`` diagonal steps.
    - Env knobs MXNET_FLASH_BLOCK_Q/K override for A/B probes; malformed
      values fall back silently.
    - Blocks shrink to a divisor of T so lengths tileable at a smaller
      block stay on the kernel.
    - Mosaic legality (enforced uniformly so CPU interpret mode takes the
      same path a TPU compile would): both blocks ride the lane (last)
      dimension of score tiles, of the (1, 8, block) lse/dcap stats blocks
      and of the dkv kernel's ``pl.ds(j * block_k, block_k)`` lane slices,
      whose start index is a dynamic loop variable — Mosaic must prove it
      a multiple of 128, which only holds when the block itself is.
      Probed on chip (r5): even a FULL-dim off-128 block fails with
      "cannot statically prove that index in dimension 2 is a multiple
      of 128", so the rule is strict 128-multiples for both blocks and
      off-128 lengths (including any T < 128) take the dense path.
    """
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 512
    block_q = _env_int("MXNET_FLASH_BLOCK_Q", block_q)
    block_k = _env_int("MXNET_FLASH_BLOCK_K", block_k)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk, 512)
    # sub-128 blocks are never lane-legal, so a smaller request (arg or
    # env probe) rounds up rather than silently dropping a tileable
    # shape to the dense path; T < 128 itself stays dense (min keeps the
    # block at T, which the legality check below rejects)
    if block_q < 128:
        block_q = min(128, tq)
    if block_k < 128:
        block_k = min(128, tk)
    # shrink to the largest 128-multiple that divides T, so lengths
    # tileable at a smaller block stay on the kernel; scanning every
    # multiple (not just halvings) keeps e.g. tq=8320 on block_q=640
    # instead of collapsing to 128. Also re-scan when the requested block
    # is not itself a 128-multiple (e.g. an env probe of 192): a legal
    # divisor beats the dense fallback. The scan leaves the block
    # unchanged when no 128-multiple divides T — the legality check
    # below then routes the shape to the dense path.
    if tq % block_q or block_q % 128:
        for m in range(block_q // 128, 0, -1):
            if tq % (m * 128) == 0:
                block_q = m * 128
                break
    if tk % block_k or block_k % 128 or block_q % block_k:
        for m in range(block_k // 128, 0, -1):
            if tk % (m * 128) == 0 and block_q % (m * 128) == 0:
                block_k = m * 128
                break
    aligned = block_q % 128 == 0 and block_k % 128 == 0
    ok = (aligned and tq % block_q == 0 and tk % block_k == 0
          and block_q % block_k == 0)
    return block_q, block_k, ok


def _flash_vmem(tq, tk, d, dv, block_q, block_k, itemsize, group=1,
                fused=True):
    """Bytes of scoped VMEM the hungriest kernel of a call asks for: of
    ``flash_fwd`` and ``flash_bwd_dqkv`` (``fused``), or of ``flash_fwd``
    and the ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair. Every operand block
    is double-buffered by the pipeline, each a whole number of 128-lane
    tiles wide in VMEM whatever d is (192 takes 256): the full-length
    operands (K and V in fwd/dq, Q and dO in dkv), the streamed in/out
    blocks, and the lse/dcap stats rows (full length in dkv). On top sit
    the body's float32 [block_k, block_q] score tiles (2 in fwd, 2.75 in
    dq, 1.5 in dkv), the [d, block_q] accumulators, and in dkv the block's
    own k and v. dqkv is dkv with dQ on top: its float32 [tq, d]
    accumulator, the head's whole dQ block (double-buffered, as every
    result), and the fifth product's temporaries (0.75 of a score tile,
    3.5 [block_k, d] and half a [block_q, d] in float32), fitted so that
    it is under none of Mosaic's totals at T=1k..16k, d=64..256, bf16 and
    f32, blocks 128..1024, grouped or not, under a window or not, and over
    them by 0-0.7 MiB at the cells' shapes and near 16 MiB (the limit at
    which each compiles; AOT). The pair's terms are fitted to the
    sizes the chip's compiler reports when it refuses, at T=1k..32k,
    d=64..256, bf16 and f32, blocks 256..1024 (and d=192, dv=128 at
    T=8192; AOT): it accepts nothing of those that Mosaic refuses
    (test_chip_compile.py holds the shapes). Under grouped queries
    (``group`` > 1) dkv's two result blocks are float32. A window changes
    nothing: the kernels still hold the whole of the other side's
    operands. Past the default limit, where ``_flash_plan`` names the
    calls' own, it is what they ask for: at T=8192, d = dv = 256, bf16 the
    pair reads 27.0 / 22.0 / 19.1 / 18.0 MiB at 1024x512 / 512x512 /
    256x256 / 128x128 where Mosaic reports 21.00 / 19.00 / 18.00 / 17.50M
    for dkv, the hungriest (f32 at d = dv = 128 the same four; AOT):
    3-29 % over, so the limit named is never under what the compiler
    takes."""
    wide, wide_v = (-(-n // 128) * 128 for n in (d, dv))  # whole lane tiles
    io = (wide + wide_v) * itemsize
    tile = block_k * block_q * 4
    fwd = 2 * (tk + block_q) * io + 2 * 8 * block_q * 4 \
        + 2 * tile + 4 * dv * block_q
    dkv = 2 * (tq + 2 * block_q) * io + 2 * 2 * 8 * tq * 4 \
        + 1.5 * tile + 4 * (d + dv) * block_q + block_q * io
    if group > 1:
        dkv += 2 * block_q * (wide + wide_v) * (4 - itemsize)
    if fused:
        # dQ's float32 [tq, d] accumulator and its double-buffered block,
        # and what Mosaic takes beside dkv's for the fifth product
        return max(fwd, dkv + 4 * tq * wide + 2 * tq * wide * itemsize
                   + 0.75 * tile + 4 * (3.5 * block_k + 0.5 * block_q) * wide)
    dq = 2 * (tk + block_q) * io + 2 * block_q * wide * itemsize \
        + 2 * 2 * 8 * block_q * 4 + 2.75 * tile + 4 * d * block_q
    return max(fwd, dq, dkv)


def _flash_plan(tq, tk, d, dv, block_q=None, block_k=None, itemsize=4,
                group=1):
    """``(block_q, block_k, refusal, vmem_limit, fused)``: the blocks
    ``flash_attention`` runs these operands at, why it would NOT take the
    Pallas kernels (a ``FALLBACKS`` reason) or None when it will, the
    scoped-VMEM limit the calls must ask Mosaic for, or None where its
    default does, and whether the backward is the one ``flash_bwd_dqkv``
    kernel (``_flash_vmem``'s ``fused``): every gate the kernels apply —
    enablement, block-tiling legality and the scoped-VMEM footprint. A
    ``block_q`` the caller did not name is halved while the footprint
    overflows, so that long or wide operands stay on the kernels at a
    smaller block.

    Where the operands fit under the default limit at NO block (the
    whole-length ones alone are past it: 256-wide keys and values at T =
    8192 in bfloat16 are 16.8 MB double-buffered) a smaller block buys
    nothing: the blocks stay as ``_select_blocks`` gives them and the
    calls name their own limit, the footprint with ``_VMEM_MARGIN`` over
    it in whole MiB, refused (``"vmem"``) past ``_VMEM_CAP``.

    A plan is made for each backward, the one ``flash_bwd_dqkv`` kernel
    and the ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair, and the one kernel
    is taken unless only the pair fits (T = 16384 at 256-wide heads) or
    the pair fits at larger blocks: the one kernel's dQ accumulator and
    block are whole-length, so a smaller block frees less for it (f32 at
    T = 4096 and 128-wide heads fits it at 128 x 128, the pair at 512 x
    512), and 256 x 256 blocks cost over twice 1024 x 512
    (``_select_blocks``). Such a call keeps the blocks and the backward
    it had before the one kernel."""
    named = block_q is not None
    block_q, block_k, tiles = _select_blocks(tq, tk, block_q, block_k)
    if not enabled():
        return block_q, block_k, "disabled", None, True
    if not tiles:
        return block_q, block_k, "untileable", None, True

    def plan(fused):
        need = functools.partial(_flash_vmem, tq, tk, d, dv,
                                 itemsize=itemsize, group=group, fused=fused)
        if need(128, 128) > _VMEM_LIMIT:
            mib = 1024 * 1024
            limit = -int(-_VMEM_MARGIN * need(block_q, block_k) // mib) * mib
            return (block_q, block_k, None, limit, fused) \
                if limit <= _VMEM_CAP else None
        bq, bk = block_q, block_k
        while need(bq, bk) > _VMEM_LIMIT:
            smaller = _select_blocks(tq, tk, bq // 2, bk)
            if named or not smaller[2] or smaller[0] >= bq:
                return None
            bq, bk, _ = smaller
        return bq, bk, None, None, fused

    one, pair = plan(True), plan(False)
    if one is not None and (pair is None or one[:2] >= pair[:2]):
        return one
    return pair or (block_q, block_k, "vmem", None, True)


def flash_kernel_usable(tq, tk, d, dv, block_q=None, block_k=None,
                        itemsize=4, group=1):
    """True iff ``flash_attention`` will take the PALLAS KERNEL path for
    ``[.., tq, d] x [.., tk, d] -> [.., tk, dv]`` operands of
    ``itemsize`` bytes per element. Public so composers (e.g. the
    Ulysses sequence-parallel local attention) can choose between the
    kernel and their OWN memory-bounded fallback instead of ever
    hitting flash_attention's dense O(T^2) fallback."""
    return _flash_plan(tq, tk, d, dv, block_q, block_k, itemsize,
                       group)[2] is None


def flash_attention(q, k, v, causal=True, scale=None, window=None,
                    block_q=None, block_k=None):
    """Blockwise-softmax attention. q: [batch, heads, time, d_head]; k, v:
    the same, or with fewer heads that divide q's (grouped queries: q
    head ``h`` reads k/v head ``h // group``, through the kernels' index
    maps, nothing is repeated in memory). ``window``: a causal query sees
    its last ``window`` keys, itself included (``q - window < k <= q``);
    the kernels then visit only the tiles that hold a visible key and are
    named ``flash_win_fwd`` / ``flash_win_bwd_dqkv`` (``flash_win_bwd_dq``
    / ``flash_win_bwd_dkv`` where the backward is the pair). A window that
    reaches every key is no window.

    Forward AND backward run as Pallas kernels: the forward saves the
    per-row log-sum-exp, and the backward reconstructs attention weights
    blockwise from it (standard flash-attention backward), so the [T, T]
    score matrix never exists in HBM in either direction. The backward is
    ONE kernel, ``flash_bwd_dqkv``, that builds each tile's scores and
    dP once for dQ, dK and dV; where its dQ accumulator leaves it no
    room beside the whole q side, or less than the pair ``flash_bwd_dq``
    + ``flash_bwd_dkv`` has, the pair (``_flash_plan``). The kernels run
    whenever the shapes tile.

    The products take their operands in the inputs' type: bfloat16 q, k,
    v (and the p and ds tiles rebuilt from them) go to the MXU as
    bfloat16, float32 inputs as float32; either way every product
    accumulates in float32 and the running max and sum, exp, lse and
    dcap are float32. The scale goes into the q (or k) block where that
    is exact (float32, or a power of two as 0.125 at d=64), otherwise
    into the scores. On one v5e the three kernels at the gpt2-medium
    step's shape, [8, 16, 1024, 64] bfloat16 causal, take 1.13 ms of
    device time a layer where the float32-operand body of PR 27 took
    1.87 (tools/flash_probe.py; my chip runs, PR 28); what the change
    was made of is in ``_NT``'s comment and PERF.md section 6.

    Routed to plain XLA, and counted in ``FALLBACKS``, when the kernels
    are disabled, the lengths do not tile, the operands overflow
    ``_VMEM_CAP`` of scoped VMEM (``_flash_plan`` names which), or causal
    attention is asked over tq != tk. Operands that fit under Mosaic's
    default limit at no block (256-wide keys and values at T=8192) run at
    ``_select_blocks``' blocks under a limit the calls name themselves.
    Every call site that takes the kernels is counted in ``FLASH_CALLS``,
    with that limit in its key where one is set.

    Block sizing: ``_select_blocks`` (1024 q positions a program, k/v
    512 at a time, with this PR's readings); MXNET_FLASH_BLOCK_Q/K
    override for probes.
    """
    import jax
    import jax.numpy as jnp
    from . import remat

    scale = 1.0 / float(q.shape[-1]) ** 0.5 if scale is None else scale
    # one type for all three, so that every product has two operands of it
    dtype = jnp.result_type(q, k, v)
    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    tq, tk = q.shape[2], k.shape[2]
    group = 1
    if q.ndim == 4:
        if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
            raise ValueError("%d q heads over %d k and %d v heads"
                             % (q.shape[1], k.shape[1], v.shape[1]))
        group = q.shape[1] // k.shape[1]
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window of %r, causal %r" % (window, causal))
        window = None if window >= tk else int(window)
    block_q, block_k, refusal, vmem_limit, fused = _flash_plan(
        tq, tk, q.shape[-1], v.shape[-1], block_q, block_k,
        q.dtype.itemsize, group)
    if q.ndim != 4:
        refusal = "ndim"
    elif causal and tq != tk and refusal is None:
        # the causal kernels unroll each block's own diagonal steps
        refusal = "causal_rectangle"
    if refusal is not None:
        _fallback("flash_attention", refusal, tuple(q.shape))
        return _attention_reference(q, k, v, causal, scale, window)

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = _flash_attention_pallas(q, k, v, causal, scale,
                                       block_q, block_k, window, vmem_limit)
        return o

    def fwd(q, k, v):
        o, lse = _flash_attention_pallas(q, k, v, causal, scale, block_q,
                                         block_k, window, vmem_limit)
        o, lse = remat.offer("flash", o, lse)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        return _flash_attention_bwd_pallas(*res, g, causal, scale, block_q,
                                           block_k, window, vmem_limit,
                                           fused)

    attn.defvjp(fwd, bwd)
    return attn(q, k, v)


# ---------------------------------------------------------------------------
# fused row softmax
# ---------------------------------------------------------------------------


def _softmax_kernel(x_ref, o_ref):
    import jax.numpy as jnp

    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def fused_softmax(x):
    """One-pass softmax over the last axis of a 2-D array.

    Pallas analog of the reference's cuDNN softmax fast path
    (ref: src/operator/cudnn_softmax_activation-inl.h). Rows are tiled
    across the grid in blocks of a multiple of 8 (the sublane tile; the
    last block may be ragged — rows are independent, so what the padding
    holds never reaches a stored row) or in one full-height block; each
    block is reduced entirely in VMEM. Routed to jax.nn.softmax, and
    counted in ``FALLBACKS``, when disabled or when 8 rows overflow VMEM.
    """
    import jax

    if not enabled():
        _fallback("fused_softmax", "disabled", tuple(x.shape))
        return jax.nn.softmax(x, axis=-1)
    if x.ndim != 2:
        _fallback("fused_softmax", "ndim", tuple(x.shape))
        return jax.nn.softmax(x, axis=-1)
    n, c = x.shape
    # per element of a block: input and output double-buffered by the
    # pipeline, plus the kernel's f32 working copies (x, exp)
    per_row = c * (4 * x.dtype.itemsize + 8)
    block_rows = min(256, (3 * _VMEM_LIMIT // 4) // per_row // 8 * 8)
    if block_rows == 0:
        _fallback("fused_softmax", "vmem", tuple(x.shape))
        return jax.nn.softmax(x, axis=-1)
    if block_rows >= n:
        block_rows = n  # one block of the array's full height

    from jax.experimental import pallas as pl

    interpret = _interpret()
    kernel = pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(pl.cdiv(n, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        interpret=interpret,
        name="fused_softmax",
    )
    if interpret:
        return kernel(x)
    # chosen where the program is lowered: on a TPU machine an executor
    # bound to the host (ctx=mx.cpu(0)) shares this trace, and Mosaic
    # kernels lower for the TPU only
    return jax.lax.platform_dependent(
        x, tpu=kernel, default=lambda x: jax.nn.softmax(x, axis=-1))
