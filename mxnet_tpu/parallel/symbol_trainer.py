"""Fused train step compiled from a Symbol graph.

This is the TPU-native answer to the reference's bulk-exec + kvstore loop
(SURVEY §2.6 InitOpSegs, §3.1): the WHOLE training step — forward, backward
(jax.vjp with loss-head cotangents, same semantics as Executor.backward),
optimizer update (optax) — is one XLA program with donated param/opt/aux
buffers, so weights update in-place in HBM and every elementwise op fuses
into the surrounding matmuls/convs.

Mixed precision: master params stay f32; tensors with ndim>=2 are cast to
``compute_dtype`` (bf16 on TPU → MXU) inside the step; FC accumulates f32
via preferred_element_type, convs ride XLA:TPU's f32 MXU accumulators
(see ops/nn.py dtype note).

Used by bench.py; Module users get the same semantics through the
Executor's fused fwd+bwd path.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError


def make_symbol_train_step(symbol, input_shapes, optimizer=None,
                           compute_dtype=None, ctx=None, mesh=None,
                           batch_axis="data", donate=True, seed=0):
    """Compile symbol into a fused train step.

    input_shapes: dict of data/label name -> shape (the non-parameter args).
    Returns (step, state) where state = dict(params, opt_state, aux) of
    jax arrays and step(state, batch_dict, rng) -> (state, outputs_list).
    ctx: where the state lives and the step runs; defaults to the current
    context like the rest of the API (``with mx.tpu(0): ...``), never to
    "the chip if one is found".
    With a mesh, batch leaves are committed sharded on `batch_axis` and
    params replicated (pure data parallelism; XLA emits the ICI psum).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ..context import current_context

    if optimizer is None:
        optimizer = optax.sgd(0.05, momentum=0.9)
    if ctx is None:
        ctx = current_context()

    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**input_shapes)
    param_names = [n for n in arg_names if n not in input_shapes]

    if any((not n.is_variable) and n.op.is_host_op for n in symbol.nodes):
        # host ops would have to trace as pure_callback inside this jit —
        # the compiled-program host-callback path the hybrid executor
        # exists to avoid (see executor.py); Module/FeedForward handle
        # these graphs through the hybrid engine instead
        raise MXNetError("make_symbol_train_step does not support host "
                         "ops (Custom/NumpyOp/torch bridge)")
    # persistent jit cache: the fused train step (and bench.py's scanned
    # loop over it) caches across processes once a cache is placed
    # (JAX_COMPILATION_CACHE_DIR); the bind below also applies the
    # MXNET_COMPILE_OPT graph rewrites to the traced program
    # (docs/how_to/compilation.md)
    from .. import compile as _compile

    _compile.ensure_jit_cache()
    # one throwaway bind to reuse the Executor's traced program & plan;
    # release its device arrays — `run` is a bound method and would
    # otherwise pin a second full parameter set in HBM
    exe = symbol.simple_bind(ctx, grad_req="null", **input_shapes)
    run = exe._run
    no_head_grad = exe._head_no_grad
    exe._release_device_arrays()
    if not all(no_head_grad):
        raise MXNetError("make_symbol_train_step requires loss-op heads")

    rng0 = _np.random.RandomState(seed)
    params = {}
    for n, s in zip(arg_names, arg_shapes):
        if n in input_shapes:
            continue
        fan_in = float(_np.prod(s[1:])) if len(s) > 1 else float(s[0])
        scale = _np.sqrt(2.0 / max(fan_in, 1.0))
        if n.endswith("bias") or n.endswith("beta"):
            params[n] = jnp.zeros(s, jnp.float32)
        elif n.endswith("gamma"):
            params[n] = jnp.ones(s, jnp.float32)
        else:
            params[n] = jnp.asarray(rng0.normal(0, scale, s), jnp.float32)
    aux = [
        jnp.zeros(s, jnp.float32) if "mean" in n else jnp.ones(s, jnp.float32)
        for n, s in zip(aux_names, aux_shapes)
    ]

    cdt = jnp.dtype(compute_dtype) if compute_dtype else None

    def _cast(p):
        if cdt is None:
            return p
        return {
            k: (v.astype(cdt) if v.ndim >= 2 else v) for k, v in p.items()
        }

    def step_impl(params, opt_state, aux, batch, rng):
        def f(p):
            pc = _cast(p)
            vals = [
                (batch[n] if n in batch else pc[n]) for n in arg_names
            ]
            outs, new_aux = run(vals, aux, rng, is_train=True)
            # only inexact heads get cotangents (integer heads, e.g. a
            # BlockGrad'd id tensor, have none); moving stats are state,
            # not differentiable outputs — both ride through has_aux so
            # the vjp never builds a backward graph for them
            flt = [o for o in outs if jnp.issubdtype(o.dtype, jnp.inexact)]
            return flt, (outs, new_aux)

        flt, vjp_fn, (outs, new_aux) = jax.vjp(f, params, has_aux=True)
        head_grads = [jnp.ones(o.shape, o.dtype) for o in flt]
        (grads,) = vjp_fn(head_grads)
        grads = {k: v.astype(jnp.float32) for k, v in grads.items()}
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, new_aux, outs

    from ..analysis import compile_verify as _cv

    # fixed-shape bind: the per-batch step and the scanned loop each
    # compile exactly once (budget 1 — any second compile means a
    # caller leaked a varying value into the traced signature)
    jitted = _cv.wrap(
        "symbol_trainer.step",
        jax.jit(step_impl, donate_argnums=(0, 1, 2) if donate else ()),
        budget=1, group="train.symbol_step")

    batch_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch_sharding = NamedSharding(mesh, P(batch_axis))
        rep = NamedSharding(mesh, P())
        params = jax.device_put(params, rep)
        aux = [jax.device_put(a, rep) for a in aux]
    else:
        dev = ctx.jax_device
        params = jax.device_put(params, dev)
        aux = [jax.device_put(a, dev) for a in aux]

    opt_state = optimizer.init(params)
    state = {"params": params, "opt_state": opt_state, "aux": aux}

    def step(state, batch, rng):
        batch = {
            k: jax.device_put(
                jnp.asarray(v), batch_sharding if batch_sharding else ctx.jax_device
            )
            for k, v in batch.items()
        }
        p, o, a, outs = jitted(state["params"], state["opt_state"], state["aux"], batch, rng)
        return {"params": p, "opt_state": o, "aux": a}, outs

    def loop_impl(params, opt_state, aux, batches, rngs):
        def body(carry, xs):
            params, opt_state, aux = carry
            batch, rng = xs
            params, opt_state, aux, outs = step_impl(
                params, opt_state, aux, batch, rng)
            return (params, opt_state, aux), tuple(outs)

        (params, opt_state, aux), stacked = jax.lax.scan(
            body, (params, opt_state, aux), (batches, rngs))
        return params, opt_state, aux, stacked

    # the scanned loop legitimately re-traces per distinct chunk length
    # (a tail chunk is a different K) — budget a small bucket set
    jitted_loop = _cv.wrap(
        "symbol_trainer.loop",
        jax.jit(loop_impl, donate_argnums=(0, 1, 2) if donate else ()),
        budget=4, group="train.symbol_step")

    def loop(state, batches, rng):
        """Run K train steps in ONE dispatch (jitted lax.scan).

        Each jitted call pays a host-side dispatch cost regardless of
        compute, and a per-batch step() train loop pays it every batch;
        scanning K steps pays it once.

        batches: dict name -> stacked array with leading axis K (one
        slice per step). rng: a single PRNGKey, split into K per-step
        keys. Returns (state, outs) where outs is a tuple with one entry
        per symbol head, each stacked over the K steps (leading axis K).
        """
        K = next(iter(batches.values())).shape[0]
        if batch_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # leading axis is the step index; the per-step batch axis
            # (now axis 1) carries the data-parallel sharding
            tgt = NamedSharding(mesh, P(None, batch_axis))
        else:
            tgt = ctx.jax_device
        batches = {k: jax.device_put(jnp.asarray(v), tgt)
                   for k, v in batches.items()}
        rngs = jax.random.split(rng, K)
        p, o, a, outs = jitted_loop(
            state["params"], state["opt_state"], state["aux"], batches, rngs)
        return {"params": p, "opt_state": o, "aux": a}, outs

    step.loop = loop
    return step, state
