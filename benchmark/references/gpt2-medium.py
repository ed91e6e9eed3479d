"""Plain reference for the ``gpt2-medium`` configuration.

GPT-2 as published (Radford et al. 2019; the Hugging Face ``config.json``
named in ``configs/gpt2-medium.json``): learned positions, pre-LayerNorm
blocks, full multi-head causal attention, tanh-GELU MLP, tied output head.
Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, no batching
tricks. It imports nothing of ``mxnet_tpu`` and takes nothing the program has
made: the weights are drawn here from the seed, and the drivers hand the same
draw to the program.

Departures, which follow the program and are stated in the configuration's
file: no bias on the four projections of a block; the vocabulary is padded
to a multiple of 128 rows.

What the configuration states about precision is followed as storage only:
parameters whose stored type is bfloat16 are rounded to bfloat16 after each
optimizer update (a deployment that keeps bf16 weights loses an update under
half a unit in the last place, whatever computes it); every sum, product
and moment is float32.

``quant`` names the precision of the matmul operands: ``None`` is the
reference; ``"fp8"`` is the control, the nearest precision below the bfloat16
the configuration states (operands scaled by tensor into float8_e4m3's range
and rounded to its 3 mantissa bits; straight-through gradient).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import flops
import lowprec
import traffic

LN_EPS = 1e-5
ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)
#: rows of the batch per block of the reference's loss and gradient
ROWS_PER_BLOCK = 2


def sizes(config):
    """The sizes this file computes with, from the configuration's file."""
    pad = int(config["assumed"]["vocab_padded_to_multiple_of"])
    vocab = int(config["vocab_size"])
    return dict(
        d=int(config["n_embd"]), L=int(config["n_layer"]),
        H=int(config["n_head"]), ff=int(config["n_inner"]),
        P=int(config["n_positions"]), vocab=vocab,
        V=-(-vocab // pad) * pad)


def train_flops(config, mix):
    """Model FLOPs of one training step of this configuration under the
    mix ``mix`` (forward and backward, causal attention counted once,
    nothing recomputed): what ``mfu.train`` divides by time and peak."""
    sz = sizes(config)
    batch, T = int(mix["batch"]), int(mix["seq_len"])
    return batch * T * flops.lm_train_flops_per_token(
        sz["d"], sz["L"], sz["ff"], sz["V"], T)


def attention_work(config, mix):
    """(FLOPs, least bytes) of one training step's attention, all layers,
    forward and backward, as an algorithm: what ``flash_roofline`` holds
    the attention kernels' device time against."""
    sz = sizes(config)
    work, nbytes = flops.causal_attention_work(
        int(mix["batch"]), sz["H"], int(mix["seq_len"]), sz["d"] // sz["H"])
    return sz["L"] * work, sz["L"] * nbytes


def _stacked(sz, key, dtype):
    """The weights, layers stacked on a leading axis. GPT-2's initial
    scale 0.02 for the embeddings; 1/sqrt(fan-in) for the projections, as
    the program's own initializer draws them."""
    d, L, ff = sz["d"], sz["L"], sz["ff"]
    k = jax.random.split(key, 6)

    def dense(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    ones, zeros = jnp.ones((L, d), jnp.float32), jnp.zeros((L, d), jnp.float32)
    return {
        "embed": dense(k[0], (sz["V"], d), 0.02),
        "pos_embed": dense(k[1], (sz["P"], d), 0.02),
        "layers": {
            "ln1": {"scale": ones, "bias": zeros},
            "wqkv": dense(k[2], (L, d, 3 * d), d ** -0.5),
            "wo": dense(k[3], (L, d, d), d ** -0.5),
            "ln2": {"scale": ones, "bias": zeros},
            "w1": dense(k[4], (L, d, ff), d ** -0.5),
            "w2": dense(k[5], (L, ff, d), ff ** -0.5),
        },
        "ln_f": {"scale": jnp.ones((d,), jnp.float32),
                 "bias": jnp.zeros((d,), jnp.float32)},
    }


def unstack(stacked, L):
    """The same weights as the program holds them: a list of layers."""
    out = dict(stacked)
    out["layers"] = [jax.tree.map(lambda a: a[i], stacked["layers"])
                     for i in range(L)]
    return out


def make_params(config, seed, dtype="bfloat16"):
    """One jitted call: the model's weights from the seed, on the device,
    in the type they are served and trained in, as a list of layers."""
    sz = sizes(config)

    @jax.jit
    def make(key):
        return unstack(_stacked(sz, key, jnp.dtype(dtype)), sz["L"])

    return make(traffic.key_of(seed))


def leaf_names(config):
    """Names of the leaves in the order of :func:`leaf_norms`."""
    L = sizes(config)["L"]
    per_layer = ("ln1.bias", "ln1.scale", "ln2.bias", "ln2.scale",
                 "w1", "w2", "wo", "wqkv")
    names = ["embed"]
    names += ["layers.%d.%s" % (i, n) for i in range(L) for n in per_layer]
    return names + ["ln_f.bias", "ln_f.scale", "pos_embed"]


def leaf_norms(tree):
    """Euclidean norm of every leaf of a list-of-layers tree, float32,
    in ``jax.tree`` order (the order of :func:`leaf_names`)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def _stacked_norms(tree, L):
    return leaf_norms(unstack(tree, L))


# -- the model -----------------------------------------------------------------


def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, H, q):
    B, T, d = x.shape
    D = d // H
    h = _layer_norm(x, lp["ln1"])
    qkv = q(h) @ q(lp["wqkv"])
    qh, kh, vh = (t.reshape(B, T, H, D).transpose(0, 2, 1, 3)
                  for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh)) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", q(probs), q(vh))
    o = o.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + q(o) @ q(lp["wo"])
    h = _layer_norm(x, lp["ln2"])
    return x + q(_gelu_new(q(h) @ q(lp["w1"]))) @ q(lp["w2"])


def logits_fn(stacked, tokens, H, quant=None):
    """tokens [B, T] -> logits [B, T, V], float32."""
    q = lowprec.quantiser(quant)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), stacked)
    T = tokens.shape[1]
    x = f32["embed"][tokens] + f32["pos_embed"][:T][None]

    @jax.checkpoint
    def body(x, lp):
        return _block(x, lp, H, q), None

    x, _ = lax.scan(body, x, f32["layers"])
    x = _layer_norm(x, f32["ln_f"])
    return q(x) @ q(f32["embed"]).T


def _block_loss_sum(stacked, tokens, H, quant):
    logits = logits_fn(stacked, tokens[:, :-1], H, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(nll)


def loss_and_grad(stacked, tokens, H, quant=None, rows=ROWS_PER_BLOCK):
    """Mean next-token cross-entropy over all rows of ``tokens`` [B, T+1]
    and its float32 gradient, summed over blocks of ``rows`` rows."""
    B, T1 = tokens.shape
    rows = min(rows, B)
    if B % rows:
        raise ValueError("batch %d is not whole blocks of %d rows" % (B, rows))
    blocks = tokens.reshape(B // rows, rows, T1)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), stacked)

    def one(acc, blk):
        loss, grad = jax.value_and_grad(_block_loss_sum)(f32, blk, H, quant)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grad)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, f32))
    (loss, grad), _ = lax.scan(one, zero, blocks)
    n = B * (T1 - 1)
    return loss / n, jax.tree.map(lambda g: g / n, grad)


@functools.lru_cache(maxsize=None)
def _train_program(L, H, lr, steps, quant, keep_rows):
    """Three Adam steps from the seed's weights, as one jitted program."""

    def run(stacked0, batches):
        mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), stacked0)
        nu = mu
        stacked, losses, first_grad = stacked0, [], None
        for t in range(1, steps + 1):
            tokens = batches[t - 1]
            if keep_rows is not None:  # the planted fault: rows left out
                tokens = tokens[:keep_rows]
            loss, grad = loss_and_grad(stacked, tokens, H, quant)
            if first_grad is None:
                first_grad = _stacked_norms(grad, L)
            losses.append(loss)
            mu = jax.tree.map(
                lambda m, g: ADAM["b1"] * m + (1 - ADAM["b1"]) * g, mu, grad)
            nu = jax.tree.map(
                lambda v, g: ADAM["b2"] * v + (1 - ADAM["b2"]) * g * g,
                nu, grad)
            c1, c2 = 1 - ADAM["b1"] ** t, 1 - ADAM["b2"] ** t
            stacked = jax.tree.map(
                lambda p, m, v: lowprec.store(p.astype(jnp.float32) - lr * (
                    m / c1) / (jnp.sqrt(v / c2) + ADAM["eps"]), p.dtype),
                stacked, mu, nu)
        change = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            stacked, stacked0)
        return dict(loss=jnp.stack(losses), grad_norm=first_grad,
                    change_norm=_stacked_norms(change, L))

    return jax.jit(run)


def train_readings(config, seed, batches, lr, quant=None, keep_rows=None):
    """The numbers a training cell compares, from the reference: the loss
    of each of the first steps, the norm of the first gradient and of the
    parameters' change after them, leaf by leaf. ``batches`` [steps, B,
    T+1] int32. ``keep_rows``: the half-batch fault, planted here."""
    sz = sizes(config)
    batches = jnp.asarray(batches)
    with jax.default_matmul_precision("highest"):
        stacked0 = jax.jit(
            lambda k: _stacked(sz, k, jnp.dtype(config["dtype"])))(
                traffic.key_of(seed))
        out = _train_program(sz["L"], sz["H"], float(lr),
                             int(batches.shape[0]), quant, keep_rows)(
                                 stacked0, batches)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}
