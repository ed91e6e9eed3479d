"""Pipeline parallelism: GPipe-style stage execution over a mesh axis.

Not in the 2016 reference (its model parallelism is ctx_group graph
partitioning with the engine overlapping stages implicitly — SURVEY
§2.7); this is the explicit TPU-era formulation: each device along the
'pipe' mesh axis owns one stage's weights, microbatches stream through
with `lax.ppermute` carrying activations to the next stage each tick,
and the schedule runs S + M - 1 ticks (the GPipe bubble). Differentiable
end-to-end: jax.grad through ppermute gives the reverse schedule for
free.

Constraints (the classic SPMD-pipeline ones): every stage must map
activations of one shape to the same shape, and stage weights must share
a common pytree structure (stacked on a leading stage axis).
"""
from __future__ import annotations



def pipeline_apply(stage_fn, stage_params, x, axis_name, n_microbatches):
    """Run a pipeline inside shard_map.

    stage_fn(params_slice, act) -> act; stage_params are THIS device's
    stage weights; x: [n_microbatches, mb, ...] microbatched input
    (identical on every device; stage 0 consumes it). Returns the
    pipeline output [n_microbatches, mb, ...] (valid on the LAST stage;
    other devices hold don't-care values)."""
    import jax.numpy as jnp
    from jax import lax

    from .mesh import axis_size

    stages = axis_size(axis_name)
    stage_id = lax.axis_index(axis_name)
    if x.shape[0] != n_microbatches:
        raise ValueError(
            "pipeline input has %d microbatches, schedule expects %d"
            % (x.shape[0], n_microbatches))
    mb_shape = x.shape[1:]
    total_ticks = stages + n_microbatches - 1
    perm = [(i, (i + 1) % stages) for i in range(stages)]

    state = jnp.zeros(mb_shape, x.dtype)      # activation held by stage
    outs = jnp.zeros((n_microbatches,) + mb_shape, x.dtype)
    # the carry becomes device-varying along the pipe axis after the
    # first ppermute; mark the initials so the loop carry types match
    # (same discipline as ring_attention's accumulators)
    from .mesh import mark_varying

    state, outs = mark_varying((state, outs), axis_name)

    def tick(t, carry):
        state, outs = carry
        # stage 0 ingests microbatch t (when in range), others take the
        # activation permuted from the previous stage
        feed = lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_microbatches - 1), keepdims=False)
        inp = jnp.where(stage_id == 0, feed, state)
        act = stage_fn(stage_params, inp)
        # last stage records its result for microbatch t - (stages - 1)
        out_slot = t - (stages - 1)
        valid = (out_slot >= 0) & (out_slot < n_microbatches)
        slot = jnp.clip(out_slot, 0, n_microbatches - 1)
        cur = lax.dynamic_index_in_dim(outs, slot, keepdims=False)
        upd = jnp.where(valid & (stage_id == stages - 1), act, cur)
        outs = lax.dynamic_update_index_in_dim(outs, upd, slot, axis=0)
        state = lax.ppermute(act, axis_name, perm)
        return state, outs

    _, outs = lax.fori_loop(0, total_ticks, tick, (state, outs))
    return outs


def make_pipeline(mesh, stage_fn, pipe_axis="pipe", n_microbatches=4):
    """shard_map wrapper: stacked stage params [S, ...] sharded on the
    pipe axis; input [n_microbatches, mb, ...] replicated; output taken
    from the last stage (psum-masked so every host sees it)."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    stages = mesh.shape[pipe_axis]

    def inner(stacked_params, x):
        from jax import lax

        # each device's shard is [1, ...]: its own stage's weights
        my_params = jax.tree.map(lambda p: p[0], stacked_params)
        outs = pipeline_apply(
            stage_fn, my_params, x, pipe_axis, n_microbatches)
        # broadcast the last stage's result to every device
        mask = (lax.axis_index(pipe_axis) == stages - 1).astype(outs.dtype)
        return lax.psum(outs * mask, pipe_axis)

    mapped = shard_map(
        inner, mesh=mesh,
        in_specs=(P(pipe_axis), P()), out_specs=P())

    def apply(stacked_params, x):
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != stages:
                raise ValueError(
                    "stacked stage params have leading dim %d but the "
                    "'%s' mesh axis has %d stages — each device must hold "
                    "exactly one stage" % (leaf.shape[0], pipe_axis, stages))
        stacked_params = jax.tree.map(
            lambda p: jax.device_put(
                p, NamedSharding(mesh, P(pipe_axis))), stacked_params)
        x = jax.device_put(x, NamedSharding(mesh, P()))
        return mapped(stacked_params, x)

    return apply
