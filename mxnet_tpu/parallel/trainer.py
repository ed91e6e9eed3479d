"""Sharded training steps: the performance path of the framework.

Where the reference's hot loop is Engine pushes of per-op kernels plus
KVStore reduce (SURVEY §3.1), the TPU-native hot loop is ONE jit-compiled
program per step: forward + backward + optimizer update, with buffer
donation for in-place weight updates and shardings that put gradients on
ICI all-reduces. Both LM cells of the benchmark run it, and the
Module/KVStore facade ultimately delegates to it on a mesh.

Sharding model: params/opt_state are committed to the mesh with
jax.device_put before training (ShardedTrainer does this); jit then infers
all program shardings from the committed inputs, and the mean-over-batch
loss makes XLA insert the gradient all-reduce (the KVStore 'device'
all-reduce of SURVEY §2.7, now riding ICI).
"""
from __future__ import annotations

import itertools

from .. import profiler as _profiler
from .. import telemetry as _tel
from ..base import MXNetError


def data_parallel_spec(mesh, batch_axis="data"):
    """(replicated, batch-sharded) NamedShardings for pure data parallelism."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P()), NamedSharding(mesh, P(batch_axis))


def _put_batch(batch, batch_spec):
    """Commit a host batch to the mesh. batch_spec: one sharding applied to
    every leaf, or a pytree of shardings matching the batch."""
    import jax

    if batch_spec is None:
        return batch
    if isinstance(batch_spec, dict) or isinstance(batch_spec, (list, tuple)):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s), batch, batch_spec,
            is_leaf=lambda x: hasattr(x, "shape") or hasattr(x, "__array__"),
        )
    return jax.tree.map(lambda x: jax.device_put(x, batch_spec), batch)


def make_train_step(loss_fn, optimizer=None, mesh=None, param_spec=None,
                    batch_spec=None, donate=True, has_aux=False):
    """Build a jitted fused train step (fwd+bwd+update in one XLA program).

    loss_fn(params, batch, rng) -> loss (or (loss, aux) when has_aux).
    optimizer: optax GradientTransformation (default optax.sgd(0.01)).
    With a mesh, the host batch is committed per batch_spec (default:
    sharded on dim 0 over the first mesh axis) and params should be
    committed by the caller (ShardedTrainer handles it); jit infers the
    rest. donate=True donates params+opt_state for in-place HBM updates.

    Returns (step_fn, init_state): step_fn(params, opt_state, batch, rng)
    -> (params, opt_state, loss[, aux]).
    """
    import jax
    import optax

    if optimizer is None:
        optimizer = optax.sgd(0.01)

    def step(params, opt_state, batch, rng):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, rng
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if has_aux:
            return params, opt_state, loss, aux
        return params, opt_state, loss

    from ..analysis import compile_verify as _cv

    # fixed-shape sharded step: one compile (MXNET_JIT_VERIFY names the
    # offending arg if a varying value sneaks into the trace)
    jitted = _cv.wrap(
        "trainer.sharded_step",
        jax.jit(step, donate_argnums=(0, 1) if donate else ()),
        budget=1, group="train.sharded_step")

    if mesh is not None and batch_spec is None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch_spec = NamedSharding(mesh, P(mesh.axis_names[0]))

    steps = itertools.count()

    def step_fn(params, opt_state, batch, rng):
        batch = _put_batch(batch, batch_spec)
        if not _tel.ENABLED:
            return jitted(params, opt_state, batch, rng)
        # telemetry on: the step under a ``train.step`` span that a
        # running capture sees with the step's number, and the program
        # handed to a capture through mx.profiler for its scope map
        _profiler.note_program(_cv.unwrap(jitted), params, opt_state, batch,
                               rng)
        with _tel.span("train.step", step=next(steps)):
            return jitted(params, opt_state, batch, rng)

    # the jitted program itself, for callers that lower it ahead of time
    # to read its HLO or its memory analysis (chip_smoke.py)
    step_fn.jitted = jitted

    def init_state(params):
        state = optimizer.init(params)
        if mesh is None:
            return state
        # optax makes its step counters on the default device,
        # uncommitted, while the step returns them replicated over the
        # mesh; jit keys its cache on input shardings, so without this
        # the second call would compile the whole step again
        from jax.sharding import NamedSharding, PartitionSpec as P

        replicated = NamedSharding(mesh, P())
        return jax.tree.map(
            lambda x: x if x.committed else jax.device_put(x, replicated),
            state)

    return step_fn, init_state


class ShardedTrainer:
    """Stateful convenience wrapper: commits params to the mesh, builds the
    fused step, tracks opt_state/rng.

    Example:
        trainer = ShardedTrainer(loss_fn, params, optax.adam(1e-3), mesh=mesh)
        for batch in data:
            loss = trainer.step(batch)
    """

    def __init__(self, loss_fn, params, optimizer=None, mesh=None,
                 param_spec=None, batch_spec=None, donate=True, seed=0, has_aux=False):
        import jax

        self.mesh = mesh
        self.has_aux = has_aux
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if param_spec is None:
                param_spec = NamedSharding(mesh, P())  # replicated
            if isinstance(param_spec, dict):
                params = jax.tree.map(
                    lambda x, s: jax.device_put(x, s), params, param_spec,
                    is_leaf=lambda x: hasattr(x, "shape"),
                )
            else:
                params = jax.device_put(params, param_spec)
        self.params = params
        self._step_fn, init_state = make_train_step(
            loss_fn, optimizer=optimizer, mesh=mesh, param_spec=param_spec,
            batch_spec=batch_spec, donate=donate, has_aux=has_aux,
        )
        self.opt_state = init_state(params)
        self._rng = jax.random.PRNGKey(seed)

    def step(self, batch):
        import jax

        self._rng, sub = jax.random.split(self._rng)
        out = self._step_fn(self.params, self.opt_state, batch, sub)
        if self.has_aux:
            self.params, self.opt_state, loss, aux = out
            return loss, aux
        self.params, self.opt_state, loss = out
        return loss
