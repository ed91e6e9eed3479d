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


def _attention_reference(q, k, v, causal, scale):
    """Plain XLA attention, also the backward path for the Pallas forward."""
    import jax.numpy as jnp

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        iq = jnp.arange(tq)[:, None]
        ik = jnp.arange(tk)[None, :]
        scores = jnp.where(ik <= iq, scores, -1e30)
    import jax

    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_q, block_k, n_k):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, d]
    bq, d = q.shape

    def body(i, carry):
        acc, l, m = carry
        kblk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            qpos = iq * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = i * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[:, None] + pv
        return acc_new, l_new, m_new

    acc0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    m0 = jnp.full((bq,), -1e30, jnp.float32)
    if causal:
        # only k blocks whose start can be <= the last q position of this block
        upper = lax.div((iq + 1) * block_q - 1, block_k) + 1
        upper = jnp.minimum(upper, n_k)
    else:
        upper = n_k
    acc, l, m = lax.fori_loop(0, upper, body, (acc0, l0, m0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    # log-sum-exp per row: the backward reconstructs p = exp(s - lse).
    # Stored 8-row broadcast: Mosaic requires the last-two block dims be
    # (8k, 128k) or full, so a (1, block_q) row block would not lower —
    # stats ride as (bh, 8, tq) with every sublane row identical.
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l))[None, :], (8, bq))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                         dq_ref, *, scale, causal, block_q, block_k, n_k):
    """dQ for one q block: stream K/V blocks, rebuild p from the saved
    lse, accumulate ds·K (flash-attention backward, q side)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale   # [bq, d]
    do = do_ref[0].astype(jnp.float32)         # [bq, dv]
    lse = lse_ref[0, 0]                        # [bq] (8-row broadcast)
    dcap = dcap_ref[0, 0]                      # [bq] = rowsum(dO * O)
    bq = q.shape[0]

    def body(i, acc):
        kblk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            qpos = iq * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = i * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        p = jnp.exp(s - lse[:, None])
        dp = lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None])
        return acc + lax.dot_general(ds, kblk, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    if causal:
        upper = jnp.minimum(lax.div((iq + 1) * block_q - 1, block_k) + 1, n_k)
    else:
        upper = n_k
    acc0 = jnp.zeros(q.shape, jnp.float32)
    acc = lax.fori_loop(0, upper, body, acc0)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                          dk_ref, dv_ref, *, scale, causal, block_q,
                          block_k, n_q):
    """dK/dV for one k block: stream Q/dO blocks, accumulate p^T·dO and
    ds^T·q (flash-attention backward, k side)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    kblk = k_ref[0].astype(jnp.float32)   # [bk, d]
    vblk = v_ref[0].astype(jnp.float32)   # [bk, dv]
    bk = kblk.shape[0]

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(j * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(j * block_q, block_q)]
        dcap = dcap_ref[0, 0, pl.ds(j * block_q, block_q)]
        s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            kpos = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_new = dv + lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None])
        dk_new = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk_new, dv_new

    if causal:
        # q blocks at or after this k block's first position
        lower = lax.div(ik * block_k, block_q)
    else:
        lower = 0
    dk0 = jnp.zeros(kblk.shape, jnp.float32)
    dv0 = jnp.zeros(vblk.shape, jnp.float32)
    dk, dv = lax.fori_loop(lower, n_q, body, (dk0, dv0))
    # q was pre-scaled, so ds^T·q already carries one factor of scale;
    # dk = scale * ds^T·q_unscaled == ds^T·(q*scale)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_attention_pallas(q, k, v, causal, scale, block_q, block_k):
    """Forward kernel; returns (o, lse) with lse saved for the backward."""
    import jax
    from jax.experimental import pallas as pl

    b, h, tq, d = q.shape
    tk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(bh, tk, d)
    v3 = v.reshape(bh, tk, v.shape[-1])
    n_q = tq // block_q
    n_k = tk // block_k

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    import jax.numpy as jnp

    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, tq, v.shape[-1]), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32),
        ),
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, v3.shape[-1]), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, v3.shape[-1]), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
        ),
        interpret=_interpret(),
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, tq, v.shape[-1]), lse  # lse: (b*h, 8, tq)


def _flash_attention_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                block_q, block_k):
    """Blockwise backward: neither pass materialises the [T, T] score
    matrix in HBM — the cliff the dense-vjp fallback hits at long T."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, tq, d = q.shape
    tk = k.shape[2]
    dv_dim = v.shape[-1]
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(bh, tk, d)
    v3 = v.reshape(bh, tk, dv_dim)
    do3 = g.reshape(bh, tq, dv_dim)
    lse3 = lse  # (bh, 8, tq), 8-row broadcast (see _flash_fwd_kernel)
    # D_i = rowsum(dO * O): one fused elementwise+reduce pass in XLA,
    # broadcast to the same 8-row stats layout
    dcap = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1).reshape(bh, 1, tq), (bh, 8, tq))
    n_q = tq // block_q
    n_k = tk // block_k

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, dv_dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, dv_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse3, dcap)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, dv_dim), v.dtype),
        ),
        grid=(bh, n_k),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tq, dv_dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 8, tq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 8, tq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv_dim), lambda i, j: (i, j, 0)),
        ),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse3, dcap)

    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, dv_dim))


def _select_blocks(tq, tk, block_q=None, block_k=None):
    """Resolve flash block sizes for a (tq, tk) problem.

    Returns ``(block_q, block_k, ok)``; ``ok=False`` means no legal tiling
    exists and the caller must use the dense path.

    - ``block_q=None`` picks the shape-keyed default: 1024 for T>=8192,
      512 below (measured in docs/perf_analysis.md — K/V HBM traffic per
      q row scales with 1/block_q, so long context wants larger q blocks;
      1024 buys ~+5 MFU points at T=8192 with no effect at 1k-4k).
    - ``block_k=None`` defaults to 512 (capped there): wider K tiles
      halve/quarter the inner-loop iterations and widen the MXU dots —
      128 -> 512 measured +19% tokens/s at T=1024 and +54% at T=8192
      (docs/perf_analysis.md r5). 1024 FAILS to compile (VMEM), so the
      cap is hard and env probes clamp to it.
    - Env knobs MXNET_FLASH_BLOCK_Q/K override for A/B probes; malformed
      values fall back silently.
    - Blocks shrink to a divisor of T so lengths tileable at a smaller
      block stay on the kernel.
    - Mosaic legality (enforced uniformly so CPU interpret mode takes the
      same path a TPU compile would): block_q rides the lane (last)
      dimension of the (1, 8, block_q) lse/dcap stats blocks AND the
      backward kernels' ``pl.ds(j * block_q, block_q)`` lane slices,
      whose start index is a dynamic loop variable — Mosaic must prove
      it a multiple of 128, which only holds when block_q itself is.
      Probed on chip (r5): even a FULL-dim off-128 block fails with
      "cannot statically prove that index in dimension 2 is a multiple
      of 128", so the rule is strict 128-multiples for both blocks and
      off-128 lengths (including any T < 128) take the dense path.
    """
    if block_q is None:
        block_q = 1024 if tq >= 8192 else 512
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
    if tk % block_k or block_k % 128:
        for m in range(block_k // 128, 0, -1):
            if tk % (m * 128) == 0:
                block_k = m * 128
                break
    aligned = block_q % 128 == 0 and block_k % 128 == 0
    ok = aligned and tq % block_q == 0 and tk % block_k == 0
    return block_q, block_k, ok


def _flash_refusal(tq, tk, d, dv, block_q=None, block_k=None, itemsize=4):
    """Why ``flash_attention`` would NOT take the Pallas kernel for these
    operands (a ``FALLBACKS`` reason), or None when it will: every gate
    the kernel applies — enablement, block-tiling legality, the
    ``MXNET_FLASH_MIN_T`` crossover, and the scoped-VMEM footprint."""
    if not enabled():
        return "disabled"
    block_q, block_k, tiles = _select_blocks(tq, tk, block_q, block_k)
    if not tiles:
        return "untileable"
    # the crossover is a hardware-perf decision; interpret mode
    # (CPU tests) always takes the kernel path for coverage
    if tk < _env_int("MXNET_FLASH_MIN_T", 0) and not _interpret():
        return "below_min_t"
    # Scoped VMEM of the hungriest of the three kernels. Every operand
    # block is double-buffered by the pipeline: the full-length operands
    # (K and V in fwd/dq, Q and dO in dkv), the streamed in/out blocks,
    # and the lse/dcap stats rows (full length in dkv); on top sit the
    # body's f32 temporaries, two [block_q, block_k] score tiles and the
    # accumulators. Fitted to what the chip's compiler reports and
    # checked against it at T=128..32k, d=64..256, bf16 and f32
    # (tests/unittest/test_chip_compile.py holds the bench shapes).
    io = d + dv
    scores = 2 * block_q * block_k * 4
    fwd = (2 * tk * io + 2 * block_q * io) * itemsize \
        + 2 * 8 * block_q * 4 + scores + block_q * dv * 4
    dq = (2 * tk * io + 2 * block_q * (io + d)) * itemsize \
        + 2 * 2 * 8 * block_q * 4 + scores + block_q * d * 4
    dkv = (2 * tq * io + 2 * block_k * 2 * io) * itemsize \
        + 2 * 2 * 8 * tq * 4 + scores + block_k * io * 4
    if max(fwd, dq, dkv) > _VMEM_LIMIT - 512 * 1024:
        return "vmem"
    return None


def flash_kernel_usable(tq, tk, d, dv, block_q=None, block_k=None,
                        itemsize=4):
    """True iff ``flash_attention`` will take the PALLAS KERNEL path for
    ``[.., tq, d] x [.., tk, d] -> [.., tk, dv]`` operands of
    ``itemsize`` bytes per element. Public so composers (e.g. the
    Ulysses sequence-parallel local attention) can choose between the
    kernel and their OWN memory-bounded fallback instead of ever
    hitting flash_attention's dense O(T^2) fallback."""
    return _flash_refusal(tq, tk, d, dv, block_q, block_k, itemsize) is None


def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=None, block_k=None):
    """Blockwise-softmax attention. q,k,v: [batch, heads, time, d_head].

    Forward AND backward run as Pallas kernels: the forward saves the
    per-row log-sum-exp, and the backward reconstructs attention weights
    blockwise from it (standard flash-attention backward), so the [T, T]
    score matrix never exists in HBM in either direction. Measured on
    the real chip (docs/perf_analysis.md, round 4): with the kernel
    backward, flash beats the dense XLA path at EVERY training length —
    1.06x tokens/s at T=1024 rising to 19x at T=8192, where dense
    spills to 2% MFU and flash holds 39% — so the kernel is the default
    whenever shapes tile. MXNET_FLASH_MIN_T (default 0) can re-impose a
    crossover; MXNET_FLASH_DENSE_BWD=1 forces the dense recompute
    backward for A/B probes.

    Routed to plain XLA, and counted in ``FALLBACKS``, when the kernels
    are disabled, the lengths do not tile, or the operands overflow the
    scoped VMEM (``_flash_refusal`` names which).

    Block sizing (measured, docs/perf_analysis.md rounds 4-5): every
    q-block grid cell DMAs the FULL K/V into VMEM, so K/V HBM traffic
    scales with tq/block_q — block_q 128 -> 512 took T=8192 training
    from 41% to 59% MFU and T=1024 from 55% to 61% (r4 figures, under
    the OLD 18Td accounting — r5 switched the bench to the standard
    12Td convention, so don't compare them to current MFU numbers;
    tokens/s comparisons are convention-free); 512 -> 1024 buys a
    further ~12% tokens/s at T=8192. block_k widens the inner-loop MXU
    dots and cuts loop iterations: 128 -> 512 measured +19% tokens/s at
    T=1024 and +54% at T=8192 (1024 fails to compile — VMEM — so 512
    is a hard cap). Defaults are therefore shape-keyed in
    ``_select_blocks`` (block_q: 1024 for T>=8192, 512 below, clamped
    to tq; block_k: 512); MXNET_FLASH_BLOCK_Q/K override for probes.
    """
    import jax

    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    tq, tk = q.shape[2], k.shape[2]
    block_q, block_k, _tiles = _select_blocks(tq, tk, block_q, block_k)
    refusal = "ndim" if q.ndim != 4 else _flash_refusal(
        tq, tk, q.shape[-1], v.shape[-1], block_q, block_k,
        q.dtype.itemsize)
    if refusal is not None:
        _fallback("flash_attention", refusal, tuple(q.shape))
        return _attention_reference(q, k, v, causal, scale)

    dense_bwd = os.environ.get("MXNET_FLASH_DENSE_BWD", "") == "1"

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = _flash_attention_pallas(q, k, v, causal, scale,
                                       block_q, block_k)
        return o

    def fwd(q, k, v):
        o, lse = _flash_attention_pallas(q, k, v, causal, scale,
                                         block_q, block_k)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        if dense_bwd:  # A/B probe path: recompute attention densely
            _, pullback = jax.vjp(
                lambda q, k, v: _attention_reference(q, k, v, causal, scale),
                q, k, v)
            return pullback(g)
        return _flash_attention_bwd_pallas(q, k, v, o, lse, g, causal,
                                           scale, block_q, block_k)

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
