"""Ulysses (all-to-all) sequence parallelism.

The second canonical context-parallel scheme alongside ring attention
(SURVEY §5.7; neither exists in the 2016 reference — both are the TPU-era
long-context extensions this framework treats as first-class). Where ring
attention rotates K/V shards around the mesh axis, Ulysses re-shards with
two all-to-alls: inputs arrive sequence-sharded, an all-to-all trades the
sequence axis for the head axis so each device holds the FULL sequence
for heads/N attention heads, blockwise (flash-style) attention runs
locally, and a second all-to-all restores sequence sharding.

Cost model vs ring: both move O(seq·d) activation bytes per device, but
Ulysses does it in TWO dense all-to-all collectives (one latency hop
each on a torus) while ring takes N ppermute hops overlapped with
compute. Ulysses wins when heads >= axis size and the interconnect has
strong all-to-all bandwidth; ring wins when heads < axis size or K/V
transfer must hide entirely behind compute.

Used inside shard_map with a mesh axis named e.g. 'seq'; head count must
be divisible by the axis size.
"""
from __future__ import annotations

import functools


def ulysses_attention(q, k, v, axis_name, causal=True, scale=None,
                      q_offset=0):
    """All-to-all sequence-parallel attention.

    Per-shard shapes (inside shard_map): q,k,v [batch, heads, t_local, d]
    with the global sequence laid out contiguously by rank along
    `axis_name`. Returns [batch, heads, t_local, d].

    Q and K/V lengths may differ; ``q_offset`` is the queries' absolute
    start position in the key sequence for causal masking — the
    chunked-prefill geometry (serving/model.py cp_prefill_kv), same
    contract as ring_attention. A nonzero offset (or rectangular q/k)
    takes the blockwise fallback; the square Pallas-kernel path is the
    training case.
    """
    import jax.numpy as jnp
    from jax import lax

    from .mesh import axis_size

    n = axis_size(axis_name)
    b, h, t_local, d = q.shape
    tk_local = k.shape[2]
    if h % n != 0:
        raise ValueError(
            "ulysses: heads (%d) must divide by mesh axis size (%d)" % (h, n))
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def seq_to_heads(x):
        # [B, H, Tl, D] -> heads split across devices, full sequence local:
        # all_to_all splits the head axis and concatenates the seq axis
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    from .ring_attention import _block_attn, _merge_block

    ql, kl, vl = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # ql: [B, H/n, T_global, D] — exactly the flash kernel's shape, and
    # unlike ring there is no cross-step LSE combine, so the local
    # attention can ride the tuned Pallas kernels (fwd AND custom-vjp
    # backward) whenever the local problem tiles and K/V fit the
    # kernel's per-cell VMEM budget. Gating on the same conditions
    # flash_attention checks guarantees the kernel path — never its
    # dense O(T^2) fallback, which would lose this loop's
    # O(T_global*chunk) memory bound.
    from ..ops import pallas_kernels as pk

    t_global = ql.shape[2]
    tk_global = kl.shape[2]
    if (q_offset == 0 and t_global == tk_global
            and pk.flash_kernel_usable(t_global, tk_global, d,
                                       vl.shape[-1],
                                       itemsize=ql.dtype.itemsize)):
        out = pk.flash_attention(ql, kl, vl, causal=causal, scale=scale)
        return heads_to_seq(out.astype(q.dtype))
    # fallback: blockwise over key chunks with the shared flash-style
    # LSE accumulation — peak memory O(T_global*chunk) scores per
    # head-chunk, not O(T_global^2)
    chunk = tk_local
    acc = jnp.float32
    iq = jnp.arange(t_global)[:, None] + q_offset

    def body(c, carry):
        o_acc, l_acc, m_acc = carry
        kc = lax.dynamic_slice_in_dim(kl, c * chunk, chunk, axis=2)
        vc = lax.dynamic_slice_in_dim(vl, c * chunk, chunk, axis=2)
        if causal:
            ik = c * chunk + jnp.arange(chunk)[None, :]
            mask = ik <= iq
        else:
            mask = jnp.ones((t_global, chunk), bool)
        o, l, m = _block_attn(ql, kc, vc, mask, scale)
        return _merge_block(o_acc, l_acc, m_acc,
                            o.astype(acc), l.astype(acc), m.astype(acc))

    init = (jnp.zeros(ql.shape[:3] + (vl.shape[-1],), acc),
            jnp.zeros(ql.shape[:3], acc),
            jnp.full(ql.shape[:3], -1e30, acc))
    from .mesh import mark_varying

    # block results are device-varying (post-all_to_all operands);
    # mark the initial carry to match (same as ring's accumulators)
    init = mark_varying(init, axis_name)
    o_acc, l_acc, m_acc = lax.fori_loop(0, tk_global // chunk, body, init)
    out = o_acc / jnp.maximum(l_acc, 1e-30)[..., None]
    return heads_to_seq(out.astype(q.dtype))


def make_ulysses_attention(mesh, seq_axis="seq", causal=True, q_offset=0):
    """Wrap ulysses_attention in shard_map over `seq_axis` of `mesh` —
    same factory contract as make_ring_attention: takes/returns global
    arrays [batch, heads, seq, d] sharded on the sequence axis, with
    ``q_offset`` placing the query block inside the key sequence."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(None, None, seq_axis, None)
    fn = functools.partial(
        ulysses_attention, axis_name=seq_axis, causal=causal,
        q_offset=q_offset)
    # replication checking off: the Pallas flash kernel's out_shapes
    # carry no varying-axes annotation, which the checker rejects inside
    # shard_map; correctness is pinned by the dense parity + ring
    # cross-check tests instead
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)

    def apply(q, k, v):
        shard = NamedSharding(mesh, spec)
        q = jax.device_put(q, shard)
        k = jax.device_put(k, shard)
        v = jax.device_put(v, shard)
        return mapped(q, k, v)

    return apply
