"""Decoder-only transformer LM — the mesh-scale flagship.

Not in the 2016 reference (its sequence model is the unrolled LSTM); this
is the long-context/distributed-first model family the north-star demands:
tensor-parallel attention/MLP (Megatron-style column→row sharding expressed
as PartitionSpecs, XLA inserts the all-reduces), data-parallel batch, and
ring-attention sequence parallelism (parallel/ring_attention.py) for
sequences longer than one chip's HBM.

Pure-function style: params are a pytree dict; forward is jit/vjp-friendly.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as _np


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 512
    num_heads: int = 8
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    use_ring_attention: bool = False
    seq_axis: str = "seq"  # mesh axis for sequence parallelism
    tensor_axis: str = "model"  # mesh axis for tensor parallelism
    data_axis: str = "data"  # mesh axis the batch is sharded on

    @property
    def head_dim(self):
        return self.d_model // self.num_heads


def init_params(cfg: TransformerConfig, key):
    """Initialize a params pytree."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.num_layers + 2)

    def dense(k, shape, scale=None):
        if scale is None:
            scale = 1.0 / _np.sqrt(shape[0])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params = {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
        "pos_embed": dense(keys[1], (cfg.max_seq_len, cfg.d_model), scale=0.02),
        "layers": [],
        "ln_f": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                 "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[2 + i], 6)
        params["layers"].append({
            "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                    "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
            "wqkv": dense(k[0], (cfg.d_model, 3 * cfg.d_model)),
            "wo": dense(k[1], (cfg.d_model, cfg.d_model)),
            "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                    "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
            "w1": dense(k[2], (cfg.d_model, cfg.d_ff)),
            "w2": dense(k[3], (cfg.d_ff, cfg.d_model)),
        })
    return params


def param_partition_specs(cfg: TransformerConfig):
    """Megatron-style tensor-parallel PartitionSpecs: qkv/w1 column-sharded,
    wo/w2 row-sharded on the tensor axis; embeddings sharded on vocab."""
    from jax.sharding import PartitionSpec as P

    t = cfg.tensor_axis
    layer = {
        "ln1": {"scale": P(), "bias": P()},
        "wqkv": P(None, t),
        "wo": P(t, None),
        "ln2": {"scale": P(), "bias": P()},
        "w1": P(None, t),
        "w2": P(t, None),
    }
    return {
        "embed": P(t, None),
        "pos_embed": P(),
        "layers": [dict(layer) for _ in range(cfg.num_layers)],
        "ln_f": {"scale": P(), "bias": P()},
    }


def _layer_norm(x, p, eps=1e-5):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
        return out.astype(x.dtype)


def _attention(q, k, v, causal=True):
    # Pallas flash kernel on TPU; flash_attention falls back to the plain
    # XLA path internally when disabled or untileable.
    from ..ops.pallas_kernels import flash_attention

    return flash_attention(q, k, v, causal=causal)


def _sharded_attention(mesh, cfg):
    """``_attention`` run per shard of ``mesh``. XLA cannot partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"),
    and attention is independent across batch rows and heads, so each
    device runs the kernel on the rows of its data shard and the heads
    of its tensor shard; an axis that does not divide its dimension is
    left replicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def attn(q, k, v):
        def axis(name, dim):
            if name in mesh.axis_names and dim % mesh.shape[name] == 0:
                return name
            return None

        spec = P(axis(cfg.data_axis, q.shape[0]),
                 axis(cfg.tensor_axis, q.shape[1]), None, None)
        # check_vma off: the kernel's out_shapes carry no varying-axes
        # annotation (same as parallel/ulysses.py)
        return shard_map(
            functools.partial(_attention, causal=True), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return attn


def forward(params, tokens, cfg: TransformerConfig, mesh=None):
    """tokens [B, T] int32 -> logits [B, T, vocab]. Under a mesh, pass
    it: attention then runs per shard (``_sharded_attention``), or as
    ring attention with ``cfg.use_ring_attention``.

    Every equation lies under a ``jax.named_scope`` that names the layer's
    part (``embed``, ``norm``, ``attn`` with ``attn.qkv`` / ``attn.out``
    around the projections, ``mlp``, ``head``): what
    ``mx.profiler.scope_map`` reads off the compiled step."""
    import jax
    import jax.numpy as jnp

    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + params["pos_embed"][:T][None].astype(x.dtype)

    if cfg.use_ring_attention and mesh is not None:
        from ..parallel.ring_attention import make_ring_attention

        attn_fn = make_ring_attention(mesh, seq_axis=cfg.seq_axis, causal=True)
    elif mesh is not None:
        attn_fn = _sharded_attention(mesh, cfg)
    else:
        attn_fn = functools.partial(_attention, causal=True)

    H, D = cfg.num_heads, cfg.head_dim
    for lp in params["layers"]:
        h = _layer_norm(x, lp["ln1"])
        with jax.named_scope("attn"):
            with jax.named_scope("attn.qkv"):
                qkv = jnp.einsum("btd,de->bte", h, lp["wqkv"])
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(t):
                return t.reshape(B, T, H, D).transpose(0, 2, 1, 3)

            o = attn_fn(heads(q), heads(k), heads(v))
            o = o.transpose(0, 2, 1, 3).reshape(B, T, H * D)
            with jax.named_scope("attn.out"):
                x = x + jnp.einsum("btd,de->bte", o, lp["wo"])
        h = _layer_norm(x, lp["ln2"])
        with jax.named_scope("mlp"):
            ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lp["w1"]))
            x = x + jnp.einsum("btf,fd->btd", ff, lp["w2"])
    x = _layer_norm(x, params["ln_f"])
    with jax.named_scope("head"):
        return jnp.einsum("btd,vd->btv", x, params["embed"])


def loss_fn(cfg: TransformerConfig, mesh=None):
    """Next-token cross-entropy loss closure for parallel.make_train_step.
    batch = dict(tokens=[B,T] int32). ``mesh``: the mesh the step runs
    on, if any (see ``forward``)."""
    import jax
    import jax.numpy as jnp

    def f(params, batch, rng):
        del rng
        tokens = batch["tokens"]
        with jax.named_scope("embed"):  # the batch's slicing
            inputs = tokens[:, :-1]
        logits = forward(params, inputs, cfg, mesh=mesh)
        with jax.named_scope("loss"):
            targets = tokens[:, 1:]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            return jnp.mean(nll)

    return f
