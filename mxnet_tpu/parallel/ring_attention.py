"""Ring attention: sequence/context parallelism over a mesh axis.

Not present in the 2016 reference (SURVEY §5.7 explicitly lists it as the
TPU-era extension to build): attention over sequences sharded across
devices, rotating K/V blocks around the ring with `lax.ppermute` while
accumulating softmax numerator/denominator in log-sum-exp form (flash/
blockwise accumulation), so each chip only ever holds its sequence shard.
Used inside shard_map with a mesh axis named e.g. 'seq'.
"""
from __future__ import annotations

import functools


def _block_attn(q, k, v, mask, scale):
    """One blockwise attention contribution with running-max bookkeeping.
    q: [B,H,Tq,D], k/v: [B,H,Tk,D]; mask: [Tq,Tk] boolean (True = keep)."""
    import jax.numpy as jnp

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    neg = jnp.asarray(-1e30, scores.dtype)
    scores = jnp.where(mask[None, None], scores, neg)
    m = jnp.max(scores, axis=-1)  # [B,H,Tq]
    p = jnp.exp(scores - m[..., None])
    # fully-masked rows: exp(neg - neg)=1 would pollute; zero them
    row_any = jnp.any(mask, axis=-1)  # [Tq]
    p = p * row_any[None, None, :, None].astype(p.dtype)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    m = jnp.where(row_any[None, None], m, neg)
    return o, l, m


def _merge_block(o_acc, l_acc, m_acc, o, l, m):
    """Merge one block's (o, l, m) into running accumulators with
    log-sum-exp rescaling (the flash-attention combine step). Shared by
    ring_attention and ulysses."""
    import jax.numpy as jnp

    new_m = jnp.maximum(m_acc, m)
    alpha = jnp.exp(m_acc - new_m)
    beta = jnp.exp(m - new_m)
    return (o_acc * alpha[..., None] + o * beta[..., None],
            l_acc * alpha + l * beta,
            new_m)


def ring_attention(q, k, v, axis_name, causal=True, scale=None, q_offset=0):
    """Attention with K/V ring-rotated across `axis_name`.

    Shapes (inside shard_map, per-shard): q,k,v [batch, heads, t_local, d].
    Global sequence = ring_size * t_local, laid out contiguously by rank.
    Returns [batch, heads, t_local, d].

    ``q_offset`` places the global query block at that absolute position
    within the key sequence: query i (global) sits at key position
    ``q_offset + i`` for causal masking. This is the chunked-prefill
    geometry (serving/model.py cp_prefill_kv): queries are the last
    ``ring * t_local_q`` tokens of a longer key sequence, so a serving
    prefill chunk attends to the whole accumulated prefix without
    re-running it. ``q_offset=0`` is the training case (q and k cover
    the same sequence).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    from .mesh import axis_size

    ring = axis_size(axis_name)
    my_rank = lax.axis_index(axis_name)
    tq = q.shape[2]
    tk = k.shape[2]

    # accumulators in f32 for stability on bf16 inputs
    acc_dtype = jnp.float32
    o_acc = jnp.zeros(q.shape[:3] + (v.shape[-1],), acc_dtype)
    l_acc = jnp.zeros(q.shape[:3], acc_dtype)
    m_acc = jnp.full(q.shape[:3], -1e30, acc_dtype)
    # mark accumulators as device-varying along the ring axis so the scan
    # carry type matches under shard_map's varying-axis checking
    from .mesh import mark_varying

    o_acc, l_acc, m_acc = mark_varying((o_acc, l_acc, m_acc), axis_name)

    def body(step, carry):
        o_acc, l_acc, m_acc, k_cur, v_cur = carry
        kv_rank = (my_rank - step) % ring
        if causal:
            # absolute positions: q at q_offset + my_rank*tq + iq ;
            # k at kv_rank*tk + ik
            iq = jnp.arange(tq)[:, None] + my_rank * tq + q_offset
            ik = jnp.arange(tk)[None, :] + kv_rank * tk
            mask = ik <= iq
        else:
            mask = jnp.ones((tq, tk), bool)
        o, l, m = _block_attn(q, k_cur, v_cur, mask, scale)
        o_acc2, l_acc2, new_m = _merge_block(
            o_acc, l_acc, m_acc,
            o.astype(acc_dtype), l.astype(acc_dtype), m.astype(acc_dtype))
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o_acc2, l_acc2, new_m, k_next, v_next)

    o_acc, l_acc, m_acc, _, _ = lax.fori_loop(
        0, ring, body, (o_acc, l_acc, m_acc, k, v)
    )
    out = o_acc / jnp.maximum(l_acc, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(mesh, seq_axis="seq", causal=True, q_offset=0):
    """Wrap ring_attention in shard_map over `seq_axis` of `mesh`.
    Takes/returns global arrays [B, H, T, D] with T sharded on seq_axis.
    Q and K/V lengths may differ; ``q_offset`` is the queries' absolute
    start position in the key sequence (chunked-prefill reuse)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, seq_axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    def f(q, k, v):
        return ring_attention(q, k, v, seq_axis, causal=causal,
                              q_offset=q_offset)

    return f
