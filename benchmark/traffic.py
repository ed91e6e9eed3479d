"""The one general generator of traffic: a mix is a data file of parameters
(``traffic/<mix>.json``), and this reads it. The same seed gives the same
inputs; every seed gives the same set of sizes in another order, so a seed
changes the values and the order and never the amount of work.
"""
from __future__ import annotations

import numpy as np


def rng_of(seed, stream=0):
    """A generator for one stream of a seed (any whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def key_of(seed):
    """A jax PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def token_batches(mix, vocab, seed, count):
    """``count`` batches [count, batch, seq_len + 1] of int32 ids below
    ``vocab``: rows that all differ."""
    rng = rng_of(seed, 1)
    return rng.integers(0, vocab, (count, int(mix["batch"]),
                                   int(mix["seq_len"]) + 1), dtype=np.int32)


def image_batches(mix, seed):
    """A pool of image batches made ON the device in one jitted call:
    images [pool, batch, 3, image, image] float32 in [0, 1) and labels
    [pool, batch] float32 class ids below ``classes``."""
    import jax
    import jax.numpy as jnp

    n, b, s = int(mix["pool_batches"]), int(mix["batch"]), int(mix["image"])

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(jax.random.fold_in(key, 2))
        images = jax.random.uniform(k1, (n, b, 3, s, s), jnp.float32)
        labels = jax.random.randint(k2, (n, b), 0, int(mix["classes"]))
        return images, labels.astype(jnp.float32)

    return make(key_of(seed))
