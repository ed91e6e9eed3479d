"""Residuals worth keeping: names for a ``jax.checkpoint`` policy.

A block that is checkpointed whole runs its forward twice, and a kernel
under a ``custom_vjp`` with it: the backward pass re-runs the kernel's
forward rule only to get its residuals back. Where a value is dear to
rebuild and small next to what rebuilding it costs, the code that makes it
calls :func:`offer` on it, which tags it with
``jax.ad_checkpoint.checkpoint_name``. A caller that checkpoints says which
names it keeps: ``jax.checkpoint(f, policy=jax.checkpoint_policies.
save_only_these_names(...))`` (``models/hybrid_lm.forward`` does). Without
such a policy a name is an identity that lowering drops: the dense LM's
step, which checkpoints nothing, is the same program with the names as
without.

Offered today: ``flash`` (``pallas_kernels.flash_attention``'s forward
rule: o and the row log-sum-exp), ``kda_chunk`` (``kda``'s in-chunk
forward rule: the kernel's seven results), and from
``parallel.moe.moe_share_ffn``'s sorted path ``moe_sort`` (which
assignments fill the bucket, the argsort's result) and ``moe_hidden`` (the
gate and up products over the bucket, float32; the rows a batch left empty
hold the finite products of the tokens that ride there under a zero
weight, not zeros: nothing downstream of the down product reads them).

A float that is kept AND used further on in the forward pass gets a
``reduce_precision`` from ``jax.checkpoint`` (against XLA carrying a
fusion's excess precision into the forward pass alone). After a kernel,
whose result is in memory as it is, that is one more pass over the value:
16 copies of [1, 32, 8192, 128] bfloat16 are 3.3 ms of the benchmark's
hybrid step (my chip runs, PR 33); an unsigned view and back to dodge it
leaves as many ``bitcast-convert`` operations in the compiled step (AOT).
"""
from __future__ import annotations

__all__ = ["offer", "OFFERED"]

#: name -> bytes offered under it so far, summed over every trace like
#: ``pallas_kernels.FLASH_CALLS``: what a caller's trace added is what its
#: policy keeps of that name (``hybrid_lm.forward`` reads it so)
OFFERED = {}


def offer(name, *values):
    """``values`` tagged ``name`` for a checkpoint policy to keep, as a
    tuple. A kernel's forward rule is traced when it is differentiated, so
    what it offers is counted once a call site and only where a backward
    pass will want it."""
    from jax.ad_checkpoint import checkpoint_name

    OFFERED[name] = OFFERED.get(name, 0) + sum(
        v.size * v.dtype.itemsize for v in values)
    return tuple(checkpoint_name(v, name) for v in values)
