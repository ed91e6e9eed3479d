"""Scanned fast path for the public ``fit()`` training loops.

The reference's throughput numbers are ``fit()`` numbers (ref:
python/mxnet/model.py:117 _train_multi_device) — its engine pipelines the
per-batch pushes so the Python loop never blocks. Here every jitted
dispatch pays a host-side cost, and a loop that fences every batch
(metric updates do) pays it in full, so a per-batch loop is
structurally slower than one compiled train step a dispatch
(``parallel/trainer.py``). This module closes that gap for the public API:
K training steps run as ONE dispatched ``lax.scan`` program — forward,
backward, and the REAL ``mxnet_tpu.optimizer.Optimizer.update`` traced
into the program — so ``FeedForward.fit``/``Module.fit`` get the same
throughput as the internal trainer while preserving the reference
semantics (per-index lr/wd multipliers, gradient clipping, rescale,
schedulers, Adam step counts).

How the Python Optimizer is traced (not reimplemented): inside the scan
body each parameter/gradient/state leaf is wrapped in an NDArray facade
around the tracer and ``optimizer.update(index, w, g, state)`` runs with
two instance patches active:

- ``_get_lr`` returns a traced per-step base lr (host-precomputed from
  the real scheduler for each of the K steps) times the static
  lr_mult/idx2name lookup — schedulers stay host logic (see run_chunk
  for the one-update boundary nuance the per-batch loop itself has).
- ``_index_update_count`` reads as a traced step number (Adam's bias
  correction switches to jnp.sqrt on traced t, optimizer.py) and
  ``_update_count`` is a no-op during tracing; real counts advance on
  the host after each chunk.

Optimizers whose update is stateful on the host beyond counts (SGLD's
host-side PRNG draw) are not scan-safe and must use the per-batch path —
``supports_optimizer`` is the gate.
"""
from __future__ import annotations

import numpy as _np

from .. import profiler as _profiler
from .. import telemetry as _tel
from ..base import MXNetError

# exactly these classes (not subclasses: a subclass may override update
# with host logic the trace would freeze)
_SCANNABLE_OPTIMIZERS = ("SGD", "ccSGD", "NAG", "Adam", "AdaGrad",
                         "RMSProp", "AdaDelta", "Test")


def _resident_on(a, dev):
    """True iff ``a`` is a jax.Array wholly resident on ``dev``.

    Probes ``a.devices()`` (the stable jax.Array API — a set of devices)
    rather than ``a.device``, whose property-vs-method status has moved
    across jax versions; numpy arrays and anything else without
    ``devices()`` report False (host path)."""
    devices = getattr(a, "devices", None)
    if devices is None:
        return False
    try:
        return set(devices()) == {dev}
    except TypeError:  # .devices is data, not callable, on exotic types
        return False


def supports_optimizer(optimizer):
    from .. import optimizer as opt

    cls = type(optimizer)
    return any(
        cls is opt.Optimizer.opt_registry.get(n.lower()) for n in _SCANNABLE_OPTIMIZERS
    )


class _TracedCounts(dict):
    """Every index reads as the traced step count while update() traces."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __getitem__(self, key):
        return self._t

    def __contains__(self, key):
        return True


def _static_lr_mult(optimizer, index):
    if index in optimizer.lr_mult:
        return optimizer.lr_mult[index]
    if index in optimizer.idx2name:
        return optimizer.lr_mult.get(optimizer.idx2name[index], 1.0)
    return 1.0


class FitTrainer:
    """Compiled K-step trainer driving a Symbol's fused fwd+bwd program
    and the user's real Optimizer object. Create via ``make_fit_trainer``."""

    def __init__(self, symbol, ctx, input_shapes, optimizer, arg_params,
                 aux_params, param_names, compute_dtype=None):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self.optimizer = optimizer
        self.param_names = list(param_names)
        self.input_names = list(input_shapes)
        self.ctx = ctx
        self._cdt = jnp.dtype(compute_dtype) if compute_dtype else None

        if any((not n.is_variable) and n.op.is_host_op for n in symbol.nodes):
            # host ops run eagerly via the Executor's hybrid mode; inside
            # a lax.scan they would have to become pure_callback nodes —
            # the compiled-program host-callback path the hybrid engine
            # exists to avoid. Per-batch loop handles these graphs.
            raise MXNetError("scanned fit does not support host ops "
                             "(Custom/NumpyOp/torch bridge)")
        # persistent jit cache (docs/how_to/compilation.md): the K-step
        # scanned program this trainer builds is the single most
        # expensive compile in the framework — with
        # JAX_COMPILATION_CACHE_DIR set the next process loads it from
        # disk instead of rebuilding (the bind below also applies the
        # MXNET_COMPILE_OPT graph rewrites to the traced program)
        from .. import compile as _compile

        _compile.ensure_jit_cache()
        exe = symbol.simple_bind(ctx, grad_req="null", **input_shapes)
        if not all(exe._head_no_grad):
            raise MXNetError("scanned fit requires loss-op heads")
        self._run = exe._run
        # _run is a bound method and pins the executor; release its
        # freshly allocated device arg/grad/aux arrays (the trainer keeps
        # its own copies — without this the parameters sit in HBM twice)
        exe._release_device_arrays()
        self._arg_names = symbol.list_arguments()

        dev = ctx.jax_device
        self.params = {
            n: jax.device_put(jnp.asarray(arg_params[n].asnumpy(), jnp.float32), dev)
            for n in self.param_names
        }
        self.aux = [
            jax.device_put(jnp.asarray(a.asnumpy(), jnp.float32), dev)
            for a in (aux_params[n] for n in symbol.list_auxiliary_states())
        ]
        # real optimizer states (host-created NDArrays) -> jax leaf lists
        self._state_tree = []
        self.opt_states = []
        for i, n in enumerate(self.param_names):
            st = optimizer.create_state(i, arg_params[n])
            leaves, treedef = jax.tree_util.tree_flatten(
                st, is_leaf=lambda x: x is None)
            self._state_tree.append(treedef)
            self.opt_states.append([
                None if l is None else jax.device_put(
                    jnp.asarray(l.asnumpy(), jnp.float32), dev)
                for l in leaves
            ])
        self._jit_cache = {}
        # seed the per-step dropout keys from the package random chain so
        # mx.random.seed governs the scanned path exactly like the
        # per-batch path (both draw from the same stateful chain)
        from .. import random as _mxrandom

        self._key = _mxrandom.next_key()
        # guardian sentinel (docs/how_to/guardrails.md): when on, every
        # scanned step computes finiteness + grad norm and applies the
        # whole update (params, opt states, aux) through jnp.where — a
        # poisoned step is suppressed INSIDE the fused program, and the
        # per-step verdicts stack into the chunk's outputs (they ride
        # the existing per-chunk D2H with the metrics; zero extra host
        # syncs). Off (the default), none of the sentinel ops are even
        # traced. The grad.nan/loss.spike chaos points stage
        # one host-drawn multiplier per step (lax.scan bodies trace
        # once, so the per-step fire pattern must enter as data).
        from ..resilience import faults as _flt
        from ..resilience import guardian as _grd

        # mxprof (telemetry/prof.py): the scanned K-step loop is the
        # training hot program — keep what's needed to attribute it
        # (analytic DAG cost + the staged shapes that key the record)
        self._symbol = symbol
        self._input_shapes = dict(input_shapes)
        self._prof_analytic = None
        self._prof_keys = {}
        self.last_program_key = None

        self._aux_names = symbol.list_auxiliary_states()
        self._guard_on = _grd.enabled()
        self._guard_max_norm = (
            _grd._env_float("MXNET_GUARDIAN_GRADNORM_MAX", 0.0)
            if self._guard_on else 0.0)
        self._inject = _flt.armed("grad.nan") or _flt.armed("loss.spike")
        self._last_flags = None

    # -- tracing helpers -------------------------------------------------------
    def _traced_update(self, params, opt_states, grads, lr_t, t_t):
        """Run the REAL optimizer.update once per parameter with traced
        values, returning new (params, opt_states)."""
        import types

        from ..ndarray import NDArray

        opt = self.optimizer
        orig_get_lr = opt._get_lr
        orig_update_count = opt._update_count
        orig_counts = opt._index_update_count

        def patched_get_lr(self_o, index):
            return lr_t * _static_lr_mult(self_o, index)

        try:
            opt._get_lr = types.MethodType(patched_get_lr, opt)
            opt._update_count = types.MethodType(lambda s, i: None, opt)
            opt._index_update_count = _TracedCounts(t_t)
            new_params, new_states = {}, []
            for i, n in enumerate(self.param_names):
                w = NDArray(params[n], self.ctx)
                g = NDArray(grads[n], self.ctx)
                leaves = [
                    None if l is None else NDArray(l, self.ctx)
                    for l in opt_states[i]
                ]
                st = self._jax.tree_util.tree_unflatten(
                    self._state_tree[i], leaves)
                opt.update(i, w, g, st)
                new_params[n] = w._data
                new_states.append([
                    None if l is None else l._data for l in leaves
                ])
            return new_params, new_states
        finally:
            opt._get_lr = orig_get_lr
            opt._update_count = orig_update_count
            opt._index_update_count = orig_counts

    def _make_loop(self, K):
        import jax
        import jax.numpy as jnp

        cdt = self._cdt

        def cast_param(v):
            return v.astype(cdt) if (cdt is not None and v.ndim >= 2) else v

        def cast_data(v):
            return (
                v.astype(cdt)
                if (cdt is not None and v.ndim >= 2 and
                    jnp.issubdtype(v.dtype, jnp.floating))
                else v
            )

        guard_on = self._guard_on
        max_norm = self._guard_max_norm
        inject = self._inject

        # every equation under a named scope (mx.profiler.scope_map reads
        # them off the compiled loop): the Symbol's nodes under their
        # operators' types (executor.py), the rest here; the scan's own
        # slicing of the staged batches has none
        def step(params, opt_states, aux, batch, lr_t, t_t, rng, mult):
            def f(p):
                with jax.named_scope("cast"):
                    vals = [
                        (cast_data(batch[n]) if n in batch
                         else cast_param(p[n]))
                        for n in self._arg_names
                    ]
                outs, new_aux = self._run(vals, aux, rng, is_train=True)
                # inexact heads only get cotangents; aux is state, not a
                # differentiable output
                flt = [o for o in outs
                       if jnp.issubdtype(o.dtype, jnp.inexact)]
                return flt, (outs, new_aux)

            flt, vjp_fn, (outs, new_aux) = jax.vjp(f, params, has_aux=True)
            with jax.named_scope("loss"):
                head_grads = [jnp.ones(o.shape, o.dtype) for o in flt]
            (grads,) = vjp_fn(head_grads)
            with jax.named_scope("optimizer"):
                grads = {k: v.astype(jnp.float32) for k, v in grads.items()}
            flags = None
            with jax.named_scope("guardian"):
                if inject:  # chaos multiplier (1.0: this step drew no fault)
                    grads = {k: v * mult for k, v in grads.items()}
                if guard_on:
                    gsq = sum(jnp.sum(jnp.square(g)) for g in grads.values())
                    ok = jnp.array(True)
                    for g in grads.values():
                        ok = ok & jnp.all(jnp.isfinite(g))
                    if max_norm > 0.0:
                        ok = ok & (gsq <= jnp.float32(max_norm) ** 2)
            with jax.named_scope("optimizer"):
                new_params, new_states = self._traced_update(
                    params, opt_states, grads, lr_t, t_t)
            if guard_on:
                def sel(new, old):
                    return jnp.where(ok, new, old)

                with jax.named_scope("guardian"):
                    new_params = {k: sel(v, params[k])
                                  for k, v in new_params.items()}
                    new_states = [
                        [None if l is None else sel(l, o)
                         for l, o in zip(ns, os_)]
                        for ns, os_ in zip(new_states, opt_states)
                    ]
                    new_aux = [sel(a, b) for a, b in zip(new_aux, aux)]
                    flags = (ok, jnp.sqrt(gsq))
            return new_params, new_states, new_aux, outs, flags

        def loop(params, opt_states, aux, batches, lrs, ts, rngs, mults):
            def body(carry, xs):
                params, opt_states, aux = carry
                batch, lr_t, t_t, rng, mult = xs
                params, opt_states, aux, outs, flags = step(
                    params, opt_states, aux, batch, lr_t, t_t, rng, mult)
                return (params, opt_states, aux), (tuple(outs), flags)

            (params, opt_states, aux), (stacked, flags) = jax.lax.scan(
                body, (params, opt_states, aux),
                (batches, lrs, ts, rngs, mults))
            return params, opt_states, aux, stacked, flags

        # donation updates params, optimizer state and aux in place
        return jax.jit(loop, donate_argnums=(0, 1, 2))

    # -- public API ------------------------------------------------------------
    def stage_chunk(self, batch_list):
        """Stack K batches (dict name -> numpy or NDArray) into device
        arrays with leading axis K; returns an opaque staged chunk.

        Arrays already resident on the target device stack ON device
        (jnp.stack — an HBM copy, no host round trip): a prefetching
        pipeline or device-cached dataset feeds the scan at HBM speed.
        Host arrays stack on host and ship once per chunk; with a bf16
        compute dtype the image tensor is cast before transfer, halving
        H2D bytes. Iterator contract: yielded DataBatch
        arrays must not be mutated afterwards (the reference's async
        engine imposes the same rule)."""
        import jax

        from ..ndarray import NDArray

        K = len(batch_list)
        dev = self.ctx.jax_device
        jnp = self._jnp
        bf16 = (self._cdt is not None and str(self._cdt) == "bfloat16")
        staged = {}
        for n in self.input_names:
            vals = [b[n] for b in batch_list]
            datas = [v._data if isinstance(v, NDArray) else v for v in vals]
            on_dev = all(_resident_on(a, dev) for a in datas)
            if on_dev:
                v = jnp.stack(datas)
                if bf16 and v.ndim >= 3 and v.dtype == jnp.float32:
                    v = v.astype(jnp.bfloat16)
                staged[n] = v
                continue
            v = _np.stack([_np.asarray(a) for a in datas])
            if bf16 and v.ndim >= 3 and v.dtype == _np.float32:
                v = v.astype(self._jnp.bfloat16)
            staged[n] = jax.device_put(v, dev)
        return K, staged

    def run_chunk(self, staged):
        """Run K fused train steps on a staged chunk. Returns the list of
        head outputs, each stacked with leading axis K (device arrays)."""
        import jax

        K, batches = staged
        opt = self.optimizer
        base = opt.num_update
        # lr for step k = scheduler(base+k+1), the count every parameter
        # AFTER the first sees in the per-batch loop (the reference calls
        # _get_lr before _update_count, so within one batch the first
        # parameter reads the pre-increment count and the rest read the
        # post-increment count — at a scheduler boundary the two differ
        # by one update for that first parameter; we pick the dominant
        # post-increment value uniformly)
        lrs = _np.asarray(
            [
                (opt.lr_scheduler(base + k + 1)
                 if opt.lr_scheduler is not None else opt.lr)
                for k in range(K)
            ], _np.float32)
        ts = _np.arange(base + 1, base + K + 1, dtype=_np.int32)
        self._key, sub = jax.random.split(self._key)
        rngs = jax.random.split(sub, K)
        if self._inject:
            # one host fire decision per step, staged into the program
            from ..resilience import guardian as _grd

            mults = _np.asarray(
                [_grd.grad_fault_multiplier() for _ in range(K)],
                _np.float32)
        else:
            mults = _np.ones((K,), _np.float32)

        if K not in self._jit_cache:
            from ..analysis import compile_verify as _cv

            # one compile per chunk length K (the memo key IS the
            # bucket) — MXNET_JIT_VERIFY names any arg that breaks it
            self._jit_cache[K] = _cv.wrap(
                "fit_trainer.loop|K=%d" % K, self._make_loop(K),
                budget=1, group="train.fit_loop")
            if _tel.ENABLED:
                # the scanned loop is a jit build like any executor
                # program — the compile layer's cache-hit counters say
                # whether it loaded from disk or compiled cold
                _tel.counter("executor.jit_builds_total").inc()
            from ..telemetry import prof as _prof

            if _prof.ENABLED:
                # mxprof: AOT-compile the loop through attribute_jit so
                # the cost/memory record IS this program's one compile
                # (docs/how_to/profiling.md); falls back to the plain
                # jitted fn on any analysis failure
                if self._prof_analytic is None:
                    try:
                        self._prof_analytic = _prof.graph_cost(
                            self._symbol, self._input_shapes)
                    except Exception:
                        self._prof_analytic = {}
                sig = ",".join(
                    "%s=%s" % (n, "x".join(str(d) for d in batches[n].shape))
                    for n in sorted(batches))
                pkey = "fit_trainer|K=%d|%s" % (K, sig)
                # graph identity for the attribution memo: the traced
                # program depends on the symbol, the optimizer's traced
                # update (class + static scalar config), and the
                # compute dtype — not just the staged shapes
                opt = self.optimizer
                # graph identity must cover EVERYTHING _make_loop traces
                # as a constant: the symbol, the optimizer's static
                # scalar config, the compute dtype, AND the guardian /
                # fault-injection switches — an unguarded trainer's
                # cached program handed to a guarded one would silently
                # disable the sentinel
                ghash = _prof.graph_hash("%s|%s|%s|%s|g=%d,%s,%d" % (
                    _prof.symbol_fingerprint(self._symbol),
                    type(opt).__name__,
                    sorted((k, v) for k, v in vars(opt).items()
                           if isinstance(v, (int, float, str, bool))),
                    self._cdt, self._guard_on, self._guard_max_norm,
                    self._inject))
                from ..analysis import compile_verify as _cv

                # attribution replaces the program with its AOT compile
                # — rebind through the verifier boundary so compile
                # counting survives the swap
                _prev = self._jit_cache[K]
                self._jit_cache[K] = _cv.rebind(_prev, _prof.attribute_jit(
                    pkey, _cv.unwrap(_prev),
                    (self.params, self.opt_states, self.aux, batches, lrs,
                     ts, rngs, mults),
                    site="fit_trainer.scan",
                    analytic=self._prof_analytic or None,
                    meta={"K": K, "steps_per_call": K},
                    graph_key=ghash))
                self._prof_keys[K] = _prof.program_key_for(
                    pkey, graph_key=ghash)
        self.last_program_key = self._prof_keys.get(K)
        if _tel.ENABLED:
            # a capture through mx.profiler gets the loop's scope map: the
            # executable itself where mxprof's attribution holds it, else
            # the jitted loop and the shapes it runs on, lowered at stop
            from ..analysis import compile_verify as _cv

            _profiler.note_program(
                _cv.unwrap(self._jit_cache[K]), self.params, self.opt_states,
                self.aux, batches, lrs, ts, rngs, mults)
        (self.params, self.opt_states, self.aux, stacked,
         self._last_flags) = self._jit_cache[K](
            self.params, self.opt_states, self.aux, batches, lrs, ts, rngs,
            mults)

        # host-side optimizer bookkeeping advances by K applied steps
        for i in range(len(self.param_names)):
            opt._index_update_count[i] = (
                opt._index_update_count.get(i, opt.begin_num_update) + K)
        opt.num_update = max(opt.num_update, base + K)
        return list(stacked)

    def take_step_flags(self):
        """The newest chunk's per-step guardian verdicts —
        ``(ok[K], grad_norm[K])`` device arrays — or None when the
        trainer runs unguarded. Consumed once (cleared on read) so a
        drain can never double-account a chunk."""
        flags, self._last_flags = self._last_flags, None
        return flags

    # -- guardian snapshot/rollback -------------------------------------------
    def snapshot_state(self):
        """Full host copy of the trainer state (params, optimizer
        states, aux, host-side step bookkeeping) — the guardian's
        in-memory last-good ring payload."""
        opt = self.optimizer
        return {
            "params": {n: _np.asarray(v) for n, v in self.params.items()},
            "aux": [_np.asarray(a) for a in self.aux],
            "opt_states": [
                [None if l is None else _np.asarray(l) for l in st]
                for st in self.opt_states
            ],
            "num_update": opt.num_update,
            "counts": dict(opt._index_update_count),
        }

    def restore_state(self, snap):
        """Adopt a :meth:`snapshot_state` dump (guardian rollback)."""
        import jax

        jnp = self._jnp
        dev = self.ctx.jax_device
        self.params = {n: jax.device_put(jnp.asarray(v), dev)
                       for n, v in snap["params"].items()}
        self.aux = [jax.device_put(jnp.asarray(a), dev)
                    for a in snap["aux"]]
        self.opt_states = [
            [None if l is None else jax.device_put(jnp.asarray(l), dev)
             for l in st]
            for st in snap["opt_states"]
        ]
        opt = self.optimizer
        opt.num_update = snap["num_update"]
        opt._index_update_count = dict(snap["counts"])

    def load_params(self, arg_params, aux_params):
        """Adopt checkpoint params/aux (the guardian's DISK rollback
        fallback). Names missing from the checkpoint (a prefix reused
        across model variants, allow_missing saves) keep their current
        device values — a recoverable rollback must not become a
        KeyError crash. A .params checkpoint carries no optimizer
        state, so momenta/variances restart from fresh zeros — the same
        contract as resuming a run from a checkpoint without its
        .states file."""
        import jax

        from ..ndarray import NDArray

        jnp = self._jnp
        dev = self.ctx.jax_device
        self.params = {
            n: (jax.device_put(
                jnp.asarray(arg_params[n].asnumpy(), jnp.float32), dev)
                if n in arg_params else self.params[n])
            for n in self.param_names
        }
        self.aux = [
            (jax.device_put(
                jnp.asarray(aux_params[n].asnumpy(), jnp.float32), dev)
             if n in aux_params else a)
            for n, a in zip(self._aux_names, self.aux)
        ]
        self.opt_states = []
        for i, n in enumerate(self.param_names):
            # create_state wants an NDArray-shaped weight; the restored
            # device value covers names the checkpoint did not
            w = arg_params.get(n)
            if w is None:
                w = NDArray(self.params[n], self.ctx)
            st = self.optimizer.create_state(i, w)
            leaves, _treedef = jax.tree_util.tree_flatten(
                st, is_leaf=lambda x: x is None)
            self.opt_states.append([
                None if l is None else jax.device_put(
                    jnp.asarray(l.asnumpy(), jnp.float32), dev)
                for l in leaves
            ])

    def write_back(self, arg_params, aux_params, aux_names):
        """Copy the device state into the user-visible NDArray dicts
        (epoch boundaries, checkpoints, final params)."""
        for n in self.param_names:
            arg_params[n][:] = _np.asarray(self.params[n])
        for n, a in zip(aux_names, self.aux):
            aux_params[n][:] = _np.asarray(a)


def make_fit_trainer(symbol, ctx, input_shapes, optimizer, arg_params,
                     aux_params, param_names, compute_dtype=None):
    return FitTrainer(symbol, ctx, input_shapes, optimizer, arg_params,
                      aux_params, param_names, compute_dtype=compute_dtype)
