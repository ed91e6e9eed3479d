"""Chunked gated delta rule with a per-channel decay (KDA), forward and
backward.

The recurrence, per head, with the state ``S`` [K, V] starting at 0:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

is computed ``CHUNK`` = 64 positions at a time (Kimi Linear,
arXiv:2510.26692, section 3). With ``G`` the cumulative log-decay inside a
chunk and ``S`` the state at its start, the chunk's pseudo-values and
outputs are

    U = T (V - (K * e^G) S),    T = (I + diag(beta) tril(A, -1))^-1 diag(beta)
    O = (Q * e^G) S + tril(Aqk) U
    S' = Diag(e^{G_last}) S + (K * e^{G_last - G})^T U

where ``A[t, i] = sum_c k_tc k_ic e^{G_tc - G_ic}`` and ``Aqk`` the same
with q on the left. Two stages, each a pair of Pallas kernels under one
``custom_vjp`` with a plain-XLA form beside it:

* **in the chunk** (every chunk by itself): ``A``, ``Aqk``, the inverse in
  float32, ``W = T (K * e^G)`` and ``U0 = T V``. No ``e^{-G}`` is ever
  formed: a chunk is four blocks of 16 rows; a pair of positions in
  different blocks is taken against the row block's own starting gate
  (both factors <= 1), a pair inside one block by its own exponent ``G_t -
  G_i <= 0``. The kernels ``kda_chunk_fwd`` and ``kda_chunk_bwd``: a
  program takes one head's next eight chunks, reading q, k, v and g where
  they lie (time-leading, a head's [rows, D] block of the [B, T, H * D]
  view) and keeping a chunk's tiles in VMEM: ``G`` as a float32 product
  with a triangle of ones, the pairs in different blocks as MXU products,
  the pairs inside a block one column position at a time on the VPU (k.k
  and q.k off one set of exponentials, which are never a tensor in
  memory), the inverse by blocks that double (:func:`_chunk_inverse`). The
  forward keeps the inverse ([C, C] float32 a chunk) for the backward,
  which rebuilds the gates and the pairs, takes the inverse's derivative
  as ``-X^T dX X^T`` and writes the five gradients time-leading.
  :func:`_prepare` is the same stage in plain XLA, every chunk at once,
  heads leading (its inverse: the 16-row diagonal blocks by forward
  substitution, the four joined by the finite series of a nilpotent
  matrix), differentiated by jax.
* **across chunks** (the state's pass): ``U = U0 - W S``, ``O = Qg S +
  Aqk U``, ``S' = decay * S + Kd^T U``. This is the sequential part. The
  kernels ``kda_state_fwd`` and ``kda_state_bwd``: a program walks a
  head's chunks in order with the state [V, K] in VMEM, float32; the
  backward walks them in reverse with the state's gradient there and
  rebuilds ``U`` from the chunk-start states the forward kept.
  :func:`_state_pass_xla` is the same pass as a ``lax.scan`` over chunks,
  differentiated by jax.

One plan (:func:`_plan`) decides for both stages from what it can observe:
the kernels are enabled (``pallas_kernels.enabled()``: a TPU, or
``MXNET_PALLAS=1`` for interpret mode), keys and values are equally wide,
a head fills whole 128-lane tiles, the chunks divide into grid steps and
the in-chunk backward's blocks leave VMEM room (a 128-wide head does).
Anything else runs the XLA forms, the only path on the CPU by default,
and is counted in ``pallas_kernels.FALLBACKS`` under ``kda_chunk`` and
``kda`` with the reason; a call site that took a kernel is counted in
``KDA_CALLS`` (mxtel ``pallas.kernel_total.<kernel>.<dtype>``).

The kernels are named for the stage they hold, because a device trace
names kernels: ``kda_chunk_*`` and ``kda_state_*`` are read apart there.
:func:`chunk_stage` is the in-chunk stage by itself, so that it can be
timed alone (the benchmark's ``kda_chunk_share``).

Products take their operands in ``dtype`` (bfloat16 in the benchmark's
configuration) and accumulate in float32; log-decays, their cumulative
sums, the pairs inside a block, the state and the inverse are float32
whatever ``dtype`` is, in the kernels as in XLA.
"""
from __future__ import annotations

import functools
import itertools
import math

from .. import telemetry as _tel
from . import pallas_kernels as _pk

__all__ = ["kda_attention", "chunk_stage", "KDA_CALLS", "CHUNK"]

CHUNK = 64
#: rows of a diagonal block of a chunk (see the module's text)
SUB = 16
#: chunks a program of the kernels walks per grid step
CHUNKS_PER_STEP = 8

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # [c, m] x [c, n] -> [m, n]

#: (kernel, operand type) -> number of call sites that took the kernel,
#: filled while tracing like ``pallas_kernels.FLASH_CALLS``
KDA_CALLS = {}


def _took_kernel(kernel, dtype):
    import jax.numpy as jnp

    key = (kernel, jnp.dtype(dtype).name)
    KDA_CALLS[key] = KDA_CALLS.get(key, 0) + 1
    if _tel.ENABLED:
        _tel.counter("pallas.kernel_total.%s.%s" % key).inc()


# -- in the chunk ------------------------------------------------------------------


def _block_diagonal(x, blocks):
    """[..., R, S, S'] blocks -> [..., R*S, R*S'] with them on the diagonal."""
    import jax.numpy as jnp

    eye = jnp.eye(blocks, dtype=x.dtype)
    full = x[..., :, :, None, :] * eye[:, None, :, None]
    size = blocks * x.shape[-1]
    return full.reshape(x.shape[:-3] + (size, size))


def _inverse(m):
    """Inverse of unit lower-triangular ``m`` [..., C, C], float32: the
    ``SUB``-row diagonal blocks by forward substitution, row by row; the
    blocks joined through ``(I + P)^-1 = (I - P)(I + P^2)...`` for the
    block-strictly-lower, hence nilpotent, ``P = D^-1 (m - D)``."""
    import jax.numpy as jnp
    from jax import lax

    C = m.shape[-1]
    R = C // SUB
    hi = lax.Precision.HIGHEST
    m6 = m.reshape(m.shape[:-2] + (R, SUB, R, SUB))
    diag = jnp.stack([m6[..., r, :, r, :] for r in range(R)], axis=-3)
    eye = jnp.eye(SUB, dtype=m.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (SUB,))]
    for t in range(1, SUB):
        done = jnp.stack(rows, axis=-2)
        rows.append(eye[t] - jnp.einsum(
            "...j,...jc->...c", diag[..., t, :t], done, precision=hi))
    dinv = _block_diagonal(jnp.stack(rows, axis=-2), R)
    block = jnp.arange(C) // SUB
    off = jnp.where(block[:, None] != block[None, :], m, 0.0)
    p = jnp.matmul(dinv, off, precision=hi)
    ident = jnp.eye(C, dtype=m.dtype)
    inv, power, order = ident - p, p, 2
    while order < R:
        power = jnp.matmul(power, power, precision=hi)
        inv = jnp.matmul(inv, ident + power, precision=hi)
        order *= 2
    return jnp.matmul(inv, dinv, precision=hi)


def _unit_lower_inverse(m):
    """:func:`_inverse` with the inverse's own derivative,
    ``dM = -X^T dX X^T``: two products, no pass back through the rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.custom_vjp
    def inverse(m):
        return _inverse(m)

    def fwd(m):
        x = _inverse(m)
        return x, x

    def bwd(x, dx):
        xt = jnp.swapaxes(x, -1, -2)
        hi = lax.Precision.HIGHEST
        return (-jnp.matmul(jnp.matmul(xt, dx, precision=hi), xt,
                            precision=hi),)

    inverse.defvjp(fwd, bwd)
    return inverse(m)


def _prepare(q, k, v, g, beta, dtype):
    """What the state's pass takes, for every chunk at once, heads
    leading. q, k, v, g [B, H, T, D] and beta [B, H, T], float32 -> ``w, u0,
    qg, kd`` [B, H, T, D] and ``aqk`` [B, H, T, C] in ``dtype``, ``decay``
    [B, H, N, D] float32."""
    import jax
    import jax.numpy as jnp

    B, H, T, D = q.shape
    C, S = CHUNK, SUB
    N, R = T // C, C // S
    f32 = jnp.float32

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    q5, k5, v5 = (x.astype(f32).reshape(B, H, N, C, D) for x in (q, k, v))
    G = jnp.cumsum(g.astype(f32).reshape(B, H, N, C, D), axis=3)
    G6 = G.reshape(B, H, N, R, S, D)
    ends = G6[..., -1, :]  # [B, H, N, R, D]
    start = jnp.concatenate(
        [jnp.zeros_like(ends[:, :, :, :1]), ends[:, :, :, :-1]], axis=3)
    # pairs in different blocks: both sides against the row block's start
    own = jnp.exp(G6 - start[..., None, :])
    k6, q6 = k5.reshape(G6.shape), q5.reshape(G6.shape)
    col = k5[:, :, :, None] * jnp.exp(jnp.minimum(
        start[..., None, :] - G[:, :, :, None], 0.0))  # [B, H, N, R, C, D]
    block = jnp.arange(C) // S
    before = block[:, None] > block[None, :]

    def across(rows):
        a = mm("bhnrsd,bhnrid->bhnrsi", rows * own, col)
        return jnp.where(before, a.reshape(B, H, N, C, C), 0.0)

    # pairs inside one block: each by its own exponent, k.k and q.k off one
    # set of exponentials. Recomputed in the backward pass: the [.., S, S,
    # D] exponentials are then an operand of reductions in both passes and
    # never a tensor in memory (2.1 GB a layer at T = 8192, 32 heads of 128)
    @jax.checkpoint
    def within(k6, q6, G6):
        pair = k6[..., None, :, :] * jnp.exp(jnp.minimum(
            G6[..., :, None, :] - G6[..., None, :, :], 0.0))
        return (_block_diagonal(
            jnp.sum(k6[..., :, None, :] * pair, axis=-1), R),
            _block_diagonal(jnp.sum(q6[..., :, None, :] * pair, axis=-1), R))

    pos = jnp.arange(C)
    kk_in, qk_in = within(k6, q6, G6)
    a_kk = across(k6) + jnp.where(pos[:, None] > pos[None, :], kk_in, 0.0)
    aqk = across(q6) + jnp.where(pos[:, None] >= pos[None, :], qk_in, 0.0)

    b4 = beta.astype(f32).reshape(B, H, N, C)
    solve = _unit_lower_inverse(
        jnp.eye(C, dtype=f32) + b4[..., :, None] * a_kk) * b4[..., None, :]
    decayed = jnp.exp(G)
    w = mm("bhnti,bhnid->bhntd", solve, k5 * decayed)
    u0 = mm("bhnti,bhnid->bhntd", solve, v5)
    last = G[:, :, :, -1]  # [B, H, N, D]
    kd = k5 * jnp.exp(last[:, :, :, None] - G)

    def flat(x):
        return x.astype(dtype).reshape(B, H, T, x.shape[-1])

    return (flat(w), flat(u0), flat(q5 * decayed), flat(kd), flat(aqk),
            jnp.exp(last))


# -- in the chunk: the kernels -----------------------------------------------------


def _dot32(a, b, dims):
    """A true float32 product on the MXU (``Precision.HIGHEST``)."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _chunk_masks():
    """The masks of a chunk, built once a program, [C, C] but for
    ``within`` [R, S, C]: rows are the later position ``t``, lanes the
    earlier ``i``."""
    import jax.numpy as jnp
    from jax import lax

    C = CHUNK
    shift = SUB.bit_length() - 1
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    levels, size = [], 1
    while size < C:  # the lower-left quarter of every diagonal 2*size block
        low = size.bit_length() - 1
        levels.append(((t >> (low + 1)) == (i >> (low + 1)))
                      & (((t >> low) & 1) == 1) & (((i >> low) & 1) == 0))
        size *= 2
    block = (C // SUB, SUB, C)  # a lane's place in its row's own block
    within = lax.broadcasted_iota(jnp.int32, block, 2) - SUB * (
        lax.broadcasted_iota(jnp.int32, block, 0))
    return {"eye": t == i, "lower": t > i, "lower_eq": t >= i,
            "before": (t >> shift) > (i >> shift), "within": within,
            "cumulate": (t >= i).astype(jnp.float32), "levels": levels}


def _chunk_inverse(m, masks):
    """Inverse of unit lower-triangular ``m`` [C, C], float32, by blocks
    that double: with the diagonal blocks of one size inverted in ``x``,
    ``[[A, 0], [L, B]]^-1 = [[A^-1, 0], [-B^-1 L A^-1, B^-1]]`` gives the
    next size, for every pair of blocks at once, as two whole-chunk
    products. It is forward substitution by blocks: nothing grows that
    the inverse itself does not hold (:func:`_inverse`'s result to
    rounding). A generator (:func:`_each_chunk`): every product waits for
    the one before, so it pauses after each."""
    import jax.numpy as jnp

    levels = masks["levels"]
    x = masks["eye"].astype(m.dtype) - jnp.where(levels[0], m, 0.0)
    for level in levels[1:]:
        half = _dot32(x, jnp.where(level, m, 0.0), _NN)
        yield
        x = x - _dot32(half, x, _NN)
        yield
    return x


def _chunk_gates(g, masks):
    """``G`` [C, D] the cumulative log-decay of a chunk, the gate at which
    each row's own block starts, broadcast to the block's rows, and the
    starts themselves, one [1, D] row a block after the first."""
    import jax.numpy as jnp

    G = _dot32(masks["cumulate"], g, _NN)
    starts = [G[r * SUB - 1:r * SUB] for r in range(1, CHUNK // SUB)]
    rows = [jnp.zeros((SUB, G.shape[1]), G.dtype)] + [
        jnp.broadcast_to(s, (SUB, G.shape[1])) for s in starts]
    return G, jnp.concatenate(rows, axis=0), starts


def _chunk_across(q, k, G, own, starts, masks, op):
    """The pairs of positions in different blocks of one chunk: k.k and
    q.k [C, C] float32 (zero elsewhere), as :func:`_prepare`'s ``across``
    has them, and what their backward takes again: the row factors ``k *
    own`` and ``q * own`` in ``op``, and the decay of the column factor
    (float32, one [C, D] a row block after the first)."""
    import jax.numpy as jnp

    C, S = CHUNK, SUB
    f32 = jnp.float32
    ko, qo = (k * own).astype(op), (q * own).astype(op)
    since, kx, qx = [], [jnp.zeros((S, C), f32)], [jnp.zeros((S, C), f32)]
    for r, start in enumerate(starts, 1):
        since.append(jnp.exp(jnp.minimum(start - G, 0.0)))
        block = slice(r * S, (r + 1) * S)
        both = _pk._dot(jnp.concatenate([ko[block], qo[block]], axis=0),
                        (k * since[-1]).astype(op), _NT)
        kx.append(both[:S])
        qx.append(both[S:])
    before = masks["before"]
    return (jnp.where(before, jnp.concatenate(kx, axis=0), 0.0),
            jnp.where(before, jnp.concatenate(qx, axis=0), 0.0), ko, qo, since)


def _chunk_within(q, k, G, masks):
    """The pairs inside one block, each by its own exponent: k.k (strictly
    below the diagonal) and q.k (the diagonal too) [C, C] float32, zero
    elsewhere. One column position of all four blocks at a time ([R, S,
    D] views): its exponentials serve both and are gone."""
    import jax.numpy as jnp

    C, S = CHUNK, SUB
    R, D = C // S, k.shape[1]
    k3, q3, G3 = (x.reshape(R, S, D) for x in (k, q, G))
    within = masks["within"]
    kk = qk = jnp.zeros((R, S, C), jnp.float32)
    for i in range(S):
        pair = k3[:, i:i + 1] * jnp.exp(jnp.minimum(G3 - G3[:, i:i + 1], 0.0))
        kk = jnp.where(within == i, jnp.sum(k3 * pair, axis=2, keepdims=True),
                       kk)
        qk = jnp.where(within == i, jnp.sum(q3 * pair, axis=2, keepdims=True),
                       qk)
    return (jnp.where(masks["lower"], kk.reshape(C, C), 0.0),
            jnp.where(masks["lower_eq"], qk.reshape(C, C), 0.0))


def _as_column(row, masks):
    """[1, C] along the lanes -> [C, 1] down the sublanes."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(masks["eye"], row, 0.0), axis=1, keepdims=True)


def _each_chunk(per_step, together, chunk):
    """Run the generator ``chunk(c)`` for a program's ``per_step`` chunks,
    up to ``together`` of them side by side: each runs to its next
    ``yield`` in turn. Chunks are independent, and where one waits for a
    chain of float32 products (the cumulative sum, the inverse) the
    scheduler finds the others' instructions next to it; one after the
    other it does not look that far. What it buys and costs (my chip
    runs, PR 31, at [1, 8192, 32, 128]): ms a call alone / by twos / fours
    / eights, the forward 8.96 / 5.46 / 4.89 / 4.67, the backward 7.29 /
    6.13 / 5.69 / 5.56; every copy's operations are traced again in every
    process, about 1 ms each on the chip's host, 290 a chunk forward and
    520 backward, and set-up pays them: hence four and two."""
    from jax import lax

    together = math.gcd(together, per_step)

    def step(i, carry):
        chunks = [chunk(i * together + c) for c in range(together)]
        for _ in itertools.zip_longest(*chunks):
            pass
        return carry

    lax.fori_loop(0, per_step // together, step, 0)


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u0_ref,
                      qg_ref, kd_ref, a_ref, dec_ref, x_ref, *, per_step):
    """One head's next ``per_step`` chunks, each by itself: what
    :func:`_prepare` computes, a chunk's tiles never leaving VMEM, and the
    chunk's inverse [C, C] float32 for the backward pass."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    C = CHUNK
    op = w_ref.dtype
    dot = _pk._dot
    masks = _chunk_masks()

    def chunk(c):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        q, k, v, g = (ref[0, rows, :] for ref in (q_ref, k_ref, v_ref, g_ref))
        beta = b_ref[0, 0, pl.ds(c, 1), :]  # [1, C]
        G, begun, starts = _chunk_gates(g, masks)
        yield
        kx, qx = _chunk_across(q, k, G, jnp.exp(G - begun), starts, masks,
                               op)[:2]
        kin, qin = _chunk_within(q, k, G, masks)
        a_ref[0, 0, rows, :] = (qx + qin).astype(op)
        x = yield from _chunk_inverse(jnp.where(
            masks["eye"], 1.0, _as_column(beta, masks) * (kx + kin)), masks)
        x_ref[0, 0, rows, :] = x
        solve = (x * beta).astype(op)
        decayed = jnp.exp(G)
        last = G[C - 1:C]
        w_ref[0, 0, rows, :] = dot(solve, (k * decayed).astype(op),
                                   _NN).astype(op)
        u0_ref[0, 0, rows, :] = dot(solve, v.astype(op), _NN).astype(op)
        qg_ref[0, 0, rows, :] = (q * decayed).astype(op)
        kd_ref[0, 0, rows, :] = (k * jnp.exp(last - G)).astype(op)
        dec_ref[0, 0, pl.ds(c, 1), :] = jnp.exp(last)

    _each_chunk(per_step, 4, chunk)


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, x_ref, dw_ref,
                      du0_ref, dqg_ref, dkd_ref, da_ref, ddec_ref, dq_ref,
                      dk_ref, dv_ref, dg_ref, db_ref, *, per_step):
    """The same chunks' gradients. A chunk's gates and its pairs in
    different blocks are rebuilt in VMEM as the forward had them, and its
    inverse is the forward's own (ten float32 products to rebuild, 16 KB
    to read). The exponentials of the pairs inside a block are taken
    again, one column position at a time, and serve every gradient that
    passes through them at once; k.k itself is not rebuilt there: what it
    gives to beta's gradient is read off the row side's sums."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    C, S, D = CHUNK, SUB, q_ref.shape[-1]
    R = C // S
    op = dw_ref.dtype
    f32 = jnp.float32
    dot = _pk._dot
    masks = _chunk_masks()
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    stack = functools.partial(jnp.concatenate, axis=0)
    blocks = [slice(r * S, (r + 1) * S) for r in range(R)]
    zero = jnp.zeros((S, D), f32)

    def rows_sum(x):
        return jnp.sum(x, axis=0, keepdims=True)

    def chunk(c):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        q, k, v, g = (ref[0, rows, :] for ref in (q_ref, k_ref, v_ref, g_ref))
        dw, du0 = dw_ref[0, 0, rows, :], du0_ref[0, 0, rows, :]
        dqg, dkd = (ref[0, 0, rows, :].astype(f32)
                    for ref in (dqg_ref, dkd_ref))
        daqk = da_ref[0, 0, rows, :].astype(f32)
        beta = b_ref[0, 0, pl.ds(c, 1), :]  # [1, C]
        down = _as_column(beta, masks)
        G, begun, starts = _chunk_gates(g, masks)
        yield
        own = jnp.exp(G - begun)
        kx, _, ko, qo, since = _chunk_across(q, k, G, own, starts, masks, op)
        x = x_ref[0, 0, rows, :]
        solve = (x * beta).astype(op)
        decayed = jnp.exp(G)
        last = G[C - 1:C]
        tail = jnp.exp(last - G)
        kd = k * tail

        # w = solve (k e^G), u0 = solve v, qg, kd, decay
        dsolve = dot(dw, (k * decayed).astype(op), _NT) + dot(
            du0, v.astype(op), _NT)
        dke = dot(solve, dw, _TN)
        dv_ref[0, rows, :] = dot(solve, du0, _TN)
        dk = dke * decayed + dkd * tail
        dq = dqg * decayed
        dG = (dke * k + dqg * q) * decayed - dkd * kd
        dlast = rows_sum(dkd * kd) + ddec_ref[0, 0, pl.ds(c, 1), :] * jnp.exp(
            last)

        # solve = inverse(I + beta a_kk) beta, the inverse's own derivative;
        # dm is the gradient of beta_t a_kk[t, i]: a_kk's is beta_t dm, and
        # beta_t's is the sum over i of dm a_kk, taken below pair by pair
        half = _dot32(x, dsolve * beta, _TN)
        yield
        dm = jnp.where(masks["lower"], -_dot32(half, x, _NT), 0.0)
        yield
        dbeta = jnp.sum(dm * kx, axis=1, keepdims=True)  # [C, 1]

        # pairs in different blocks
        dk_rows, dq_rows, dG_rows = [zero], [zero], [zero]
        dkx = jnp.where(masks["before"], dm * down, 0.0).astype(op)
        dqx = jnp.where(masks["before"], daqk, 0.0).astype(op)
        for r, block in enumerate(blocks[1:], 1):
            both = stack([dkx[block], dqx[block]])
            col = k * since[r - 1]
            dboth = dot(both, col.astype(op), _NN)
            dcol = dot(both, stack([ko[block], qo[block]]), _TN)
            dk = dk + dcol * since[r - 1]
            through = dcol * col
            dstart = rows_sum(through)
            dG = dG - through
            dko, dqo = dboth[:S] * own[block], dboth[S:] * own[block]
            dk_rows.append(dko), dq_rows.append(dqo)
            through = dko * k[block] + dqo * q[block]
            dG_rows.append(through)
            dG = dG + jnp.where(row == r * S - 1,
                                dstart - rows_sum(through), 0.0)

        # pairs inside a block, one column position of all four blocks at
        # a time ([R, S, D] views): its exponentials serve the row side (dm
        # and daqk times the pair), beta_t's sum and the column side at once
        def inside(x):  # [C, C] -> its diagonal blocks [R, S, S]
            return jnp.stack([x[block, block] for block in blocks])

        dkk, dqk = inside(dm), inside(jnp.where(masks["lower_eq"], daqk, 0.0))
        k3, q3, G3 = (x.reshape(R, S, D) for x in (k, q, G))
        scaled = down.reshape(R, S, 1) * k3
        at = lax.broadcasted_iota(jnp.int32, (R, S, 1), 1)
        dkb = dqb = dGb = dki = jnp.zeros((R, S, D), f32)
        for i in range(S):
            ck, cq = dkk[:, :, i:i + 1], dqk[:, :, i:i + 1]
            e = jnp.exp(jnp.minimum(G3 - G3[:, i:i + 1], 0.0))
            pair = k3[:, i:i + 1] * e
            dkb = dkb + ck * pair
            dqb = dqb + cq * pair
            z = (ck * scaled + cq * q3) * e
            dGb = dGb + z * k3[:, i:i + 1]
            dki = jnp.where(at == i, jnp.sum(z, axis=1, keepdims=True), dki)
        dbeta = dbeta + jnp.sum(k3 * dkb, axis=2, keepdims=True).reshape(C, 1)
        db_ref[0, 0, pl.ds(c, 1), :] = rows_sum(dsolve * x) + rows_sum(
            jnp.where(masks["eye"], dbeta, 0.0))
        dq_ref[0, rows, :] = dq + stack(dq_rows) + dqb.reshape(C, D)
        dk_ref[0, rows, :] = dk + stack(dk_rows) + (
            down.reshape(R, S, 1) * dkb + dki).reshape(C, D)
        dG = dG + stack(dG_rows) + (dGb - k3 * dki).reshape(C, D) + jnp.where(
            row == C - 1, dlast, 0.0)
        yield
        dg_ref[0, rows, :] = _dot32(masks["cumulate"], dG, _TN)

    _each_chunk(per_step, 2, chunk)


@functools.lru_cache(maxsize=None)
def _chunk_call(name, dtype, B, T, H, D, per_step, interpret):
    """One of the in-chunk stage's two kernels at one setting, as a jitted
    pallas_call (as :func:`_kda_call`). q, k, v, g and their gradients are
    time-leading, seen as [B, T, H * D]: a head's rows are a (rows, D)
    block where they lie. beta and its gradient are [B, H, N, C]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C = CHUNK
    N = T // C
    rows = per_step * C
    lying = pl.BlockSpec((1, rows, D), lambda b, h, j: (b, j, h))
    wide = pl.BlockSpec((1, 1, rows, D), lambda b, h, j: (b, h, j, 0))
    pairs = pl.BlockSpec((1, 1, rows, C), lambda b, h, j: (b, h, j, 0))
    decay = pl.BlockSpec((1, 1, per_step, D), lambda b, h, j: (b, h, j, 0))
    betas = pl.BlockSpec((1, 1, per_step, C), lambda b, h, j: (b, h, j, 0))
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    if name == "kda_chunk_bwd":
        body = _chunk_bwd_kernel
        in_specs = [lying] * 4 + [betas, pairs] + [wide] * 4 + [pairs, decay]
        out_specs = (lying,) * 4 + (betas,)
        out_shape = tuple(shape((B, T, H * D), f32) for _ in range(4)) + (
            shape((B, H, N, C), f32),)
    else:
        body = _chunk_fwd_kernel
        in_specs = [lying] * 4 + [betas]
        out_specs = (wide,) * 4 + (pairs, decay, pairs)
        out_shape = tuple(shape((B, H, T, D), dtype) for _ in range(4)) + (
            shape((B, H, T, C), dtype), shape((B, H, N, D), f32),
            shape((B, H, T, C), f32))
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))}
    return jax.jit(pl.pallas_call(
        functools.partial(body, per_step=per_step), out_shape=out_shape,
        grid=(B, H, N // per_step), in_specs=in_specs, out_specs=out_specs,
        interpret=interpret, name=name, **params))


def _chunk_stage_pallas(per_step, dtype):
    """The in-chunk stage on the kernels, under one ``custom_vjp``:
    arguments time-leading as :func:`chunk_stage` takes them."""
    import jax
    import jax.numpy as jnp
    from . import remat
    f32 = jnp.float32

    def call(name, q, k, v, g, beta, *cotangents):
        B, T, H, D = q.shape
        _took_kernel(name, dtype)
        lying = [x.astype(f32).reshape(B, T, H * D) for x in (q, k, v, g)]
        by_chunk = beta.astype(f32).transpose(0, 2, 1).reshape(
            B, H, T // CHUNK, CHUNK)
        return _chunk_call(name, dtype.name, B, T, H, D, per_step,
                           _pk._interpret())(*lying, by_chunk, *cotangents)

    @jax.custom_vjp
    def run(q, k, v, g, beta):
        return call("kda_chunk_fwd", q, k, v, g, beta)[:-1]

    def fwd(*args):
        *prepared, inverse = call("kda_chunk_fwd", *args)
        *prepared, inverse = remat.offer("kda_chunk", *prepared, inverse)
        return tuple(prepared), (args, inverse)

    def bwd(res, cotangents):
        args, inverse = res
        *wide, dbeta = call("kda_chunk_bwd", *args, inverse, *cotangents)
        grads = [dx.reshape(args[0].shape) for dx in wide] + [
            dbeta.reshape(*dbeta.shape[:2], -1).transpose(0, 2, 1)]
        return tuple(dx.astype(x.dtype) for dx, x in zip(grads, args))

    run.defvjp(fwd, bwd)
    return run


# -- across chunks: plain XLA ------------------------------------------------------


def _state_pass_xla(w, u0, qg, kd, aqk, decay):
    """The state's pass as a ``lax.scan`` over chunks; jax differentiates
    it. Shapes as :func:`_prepare` gives them; o [B, H, T, D] float32."""
    import jax.numpy as jnp
    from jax import lax

    B, H, T, D = w.shape
    N = decay.shape[2]
    f32 = jnp.float32

    def by_chunk(x):  # [B, H, T, X] -> [N, B, H, C, X]
        return jnp.moveaxis(x.reshape(B, H, N, T // N, x.shape[-1]), 2, 0)

    def step(S, xs):  # S [B, H, K, V] float32
        w_, u0_, qg_, kd_, a_, dec = xs
        Sd = S.astype(w_.dtype)
        u = u0_.astype(f32) - jnp.einsum("bhck,bhkv->bhcv", w_, Sd,
                                         preferred_element_type=f32)
        ud = u.astype(w_.dtype)
        o = jnp.einsum("bhck,bhkv->bhcv", qg_, Sd,
                       preferred_element_type=f32) + jnp.einsum(
            "bhci,bhiv->bhcv", a_, ud, preferred_element_type=f32)
        S = S * dec[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", kd_, ud, preferred_element_type=f32)
        return S, o

    xs = tuple(by_chunk(x) for x in (w, u0, qg, kd, aqk)) + (
        jnp.moveaxis(decay, 2, 0),)
    _, o = lax.scan(step, jnp.zeros((B, H, D, D), f32), xs)
    return jnp.moveaxis(o, 0, 2).reshape(B, H, T, D)


# -- across chunks: the kernels ----------------------------------------------------

def _state_fwd_kernel(w_ref, u0_ref, qg_ref, kd_ref, a_ref, dec_ref, o_ref,
                    st_ref, state, *, chunk, per_step):
    """One head's next ``per_step`` chunks, in order. ``state`` [V, K]
    float32 (the state transposed: the decay is then a row along the
    lanes) lives in VMEM across the grid's last axis."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    op = w_ref.dtype
    dot = _pk._dot

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for c in range(per_step):
        rows = slice(c * chunk, (c + 1) * chunk)
        st = state[...]
        st_ref[0, 0, c] = st
        sd = st.astype(op)
        u = u0_ref[0, 0, rows, :].astype(jnp.float32) - dot(
            w_ref[0, 0, rows, :], sd, _NT)
        ud = u.astype(op)
        o_ref[0, 0, rows, :] = dot(qg_ref[0, 0, rows, :], sd, _NT) + dot(
            a_ref[0, 0, rows, :], ud, _NN)
        state[...] = st * dec_ref[0, 0, c:c + 1, :] + dot(
            ud, kd_ref[0, 0, rows, :], _TN)


def _state_bwd_kernel(w_ref, u0_ref, qg_ref, kd_ref, a_ref, dec_ref, st_ref,
                    do_ref, dw_ref, du0_ref, dqg_ref, dkd_ref, da_ref,
                    ddec_ref, dstate, *, chunk, per_step):
    """The same chunks in reverse. ``dstate`` [V, K] float32 is the
    gradient of the state that leaves a chunk; ``U`` is rebuilt from the
    chunk-start state the forward kept."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    op = w_ref.dtype
    f32 = jnp.float32
    dot = _pk._dot

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for c in reversed(range(per_step)):
        rows = slice(c * chunk, (c + 1) * chunk)
        w, qg, kd = w_ref[0, 0, rows, :], qg_ref[0, 0, rows, :], kd_ref[0, 0, rows, :]
        a = a_ref[0, 0, rows, :]
        dec = dec_ref[0, 0, c:c + 1, :]
        st = st_ref[0, 0, c]
        sd = st.astype(op)
        ds = dstate[...]
        dsd = ds.astype(op)
        do = do_ref[0, 0, rows, :].astype(op)
        u = (u0_ref[0, 0, rows, :].astype(f32) - dot(w, sd, _NT)).astype(op)
        du = dot(a, do, _TN) + dot(kd, dsd, _NT)
        dud = du.astype(op)
        du0_ref[0, 0, rows, :] = du.astype(du0_ref.dtype)
        dw_ref[0, 0, rows, :] = (-dot(dud, sd, _NN)).astype(dw_ref.dtype)
        dqg_ref[0, 0, rows, :] = dot(do, sd, _NN).astype(dqg_ref.dtype)
        dkd_ref[0, 0, rows, :] = dot(u, dsd, _NN).astype(dkd_ref.dtype)
        da_ref[0, 0, rows, :] = dot(do, u, _NT).astype(da_ref.dtype)
        ddec_ref[0, 0, c:c + 1, :] = jnp.sum(ds * st, axis=0, keepdims=True)
        dstate[...] = ds * dec + dot(do, qg, _TN) - dot(dud, w, _TN)


@functools.lru_cache(maxsize=None)
def _kda_call(name, dtype, B, T, H, D, per_step, interpret):
    """One of the two kernels at one setting, as a jitted pallas_call that
    a model's layers share (``pallas_kernels._flash_call`` says why)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C = CHUNK
    N = T // C
    steps = N // per_step
    rows = per_step * C
    back = name == "kda_state_bwd"

    def at(j):  # the backward walks the chunks in reverse
        return steps - 1 - j if back else j

    wide = pl.BlockSpec((1, 1, rows, D), lambda b, h, j: (b, h, at(j), 0))
    pairs = pl.BlockSpec((1, 1, rows, C), lambda b, h, j: (b, h, at(j), 0))
    decay = pl.BlockSpec((1, 1, per_step, D),
                         lambda b, h, j: (b, h, at(j), 0))
    states = pl.BlockSpec((1, 1, per_step, D, D),
                          lambda b, h, j: (b, h, at(j), 0, 0))
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    if back:
        body = _state_bwd_kernel
        in_specs = [wide, wide, wide, wide, pairs, decay, states, wide]
        out_specs = (wide, wide, wide, wide, pairs, decay)
        out_shape = tuple(shape((B, H, T, D), dtype) for _ in range(4)) + (
            shape((B, H, T, C), dtype), shape((B, H, N, D), f32))
    else:
        body = _state_fwd_kernel
        in_specs = [wide, wide, wide, wide, pairs, decay]
        out_specs = (wide, states)
        out_shape = (shape((B, H, T, D), f32),
                     shape((B, H, N, D, D), f32))
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}
    return jax.jit(pl.pallas_call(
        functools.partial(body, chunk=C, per_step=per_step),
        out_shape=out_shape, grid=(B, H, steps), in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=[pltpu.VMEM((D, D), f32)],
        interpret=interpret, name=name, **params))


def _state_pass_pallas(per_step):
    """The state's pass on the kernels, under one ``custom_vjp``."""
    import jax

    def call(name, w, *rest):
        B, H, T, D = w.shape
        _took_kernel(name, w.dtype)
        return _kda_call(name, w.dtype.name, B, T, H, D, per_step,
                         _pk._interpret())(w, *rest)

    @jax.custom_vjp
    def run(w, u0, qg, kd, aqk, decay):
        return call("kda_state_fwd", w, u0, qg, kd, aqk, decay)[0]

    def fwd(w, u0, qg, kd, aqk, decay):
        o, states = call("kda_state_fwd", w, u0, qg, kd, aqk, decay)
        return o, (w, u0, qg, kd, aqk, decay, states)

    def bwd(res, do):
        return call("kda_state_bwd", *res, do)

    run.defvjp(fwd, bwd)
    return run


def _plan(B, T, H, D, Dv):
    """``(chunks per grid step, refusal)`` for both stages: why the
    kernels would NOT take these shapes (a ``FALLBACKS`` reason), or None
    when they will."""
    if not _pk.enabled():
        return 0, "disabled"
    N = T // CHUNK
    per_step = min(CHUNKS_PER_STEP, N)
    # a head fills whole 128-lane tiles, and the decay's block rides
    # sublanes by chunk (8 of them, or all)
    if D != Dv or D % 128 or N % per_step:
        return 0, "untileable"
    # the in-chunk backward's twelve [rows, D] operand blocks, counted as
    # float32 and twice over for the pipeline, leave its own temporaries
    # half of the scoped VMEM (6.3 MB at D = 128; at 256 Mosaic runs out:
    # AOT, PR 31)
    if 2 * 12 * per_step * CHUNK * D * 4 > _pk._VMEM_LIMIT // 2:
        return 0, "vmem"
    return per_step, None


def chunk_stage(q, k, v, g, beta, dtype=None):
    """The in-chunk stage by itself, as :func:`kda_attention` runs it:
    arguments as there, time leading -> what the state's pass takes
    (:func:`_prepare`), heads leading. The ``kda_chunk_fwd`` /
    ``kda_chunk_bwd`` kernels where :func:`_plan` admits the shapes,
    otherwise XLA, counted in ``pallas_kernels.FALLBACKS`` under
    ``kda_chunk``."""
    import jax
    import jax.numpy as jnp

    B, T, H, D = q.shape
    if T % CHUNK:
        raise ValueError("kda_attention: T=%d is not whole chunks of %d"
                         % (T, CHUNK))
    dtype = jnp.dtype(q.dtype if dtype is None else dtype)
    per_step, refusal = _plan(B, T, H, D, v.shape[-1])
    with jax.named_scope("kda.chunk"):
        if refusal is None:
            return _chunk_stage_pallas(per_step, dtype)(q, k, v, g, beta)
        _pk._fallback("kda_chunk", refusal, tuple(q.shape))
        # heads lead from here on: a chunk of one head is a [64, D] tile
        return _prepare(*(x.transpose(0, 2, 1, 3) for x in (q, k, v, g)),
                        beta.transpose(0, 2, 1), dtype)


def kda_attention(q, k, v, g, beta, dtype=None):
    """The gated delta rule over whole sequences. q, k [B, T, H, D] (q
    already scaled), v [B, T, H, D], g [B, T, H, D] the log-decay per
    channel (<= 0), beta [B, T, H] -> o [B, T, H, D] float32. ``dtype``:
    what the products take their operands in (default: q's type).

    T is a whole number of chunks of 64 (anything else raises: pad the
    sequence). Both stages run as kernels (``kda_chunk_fwd`` /
    ``kda_chunk_bwd``, ``kda_state_fwd`` / ``kda_state_bwd``) where they
    are enabled and the head is 128-lane wide, otherwise in XLA, counted
    in ``pallas_kernels.FALLBACKS`` under ``kda_chunk`` and ``kda``."""
    import jax

    B, T, H, D = q.shape
    prepared = chunk_stage(q, k, v, g, beta, dtype)
    per_step, refusal = _plan(B, T, H, D, v.shape[-1])
    with jax.named_scope("kda.state"):
        if refusal is not None:
            _pk._fallback("kda", refusal, tuple(q.shape))
            o = _state_pass_xla(*prepared)
        else:
            o = _state_pass_pallas(per_step)(*prepared)
    return o.transpose(0, 2, 1, 3)
