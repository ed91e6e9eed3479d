"""Executor: bound, compiled computation graph.

TPU-native redesign of GraphExecutor (ref: src/symbol/graph_executor.cc
1,164 LoC, include/mxnet/symbolic.h:283-391, python/mxnet/executor.py:359).

Mapping of the reference bind pipeline (SURVEY §3.2) onto XLA:
- InitGraph + MakeBackwardPass (static_graph.cc:395)  → jax.vjp
- AssignContext / _CrossDeviceCopy (graph_executor.cc:391-490) → per-node
  jax.device_put placement driven by ctx_group attrs + group2ctx
- InitDataEntryMemory / GraphStorageAllocator (static planning) → XLA
  buffer assignment inside jax.jit
- InitCachedOps / InitOpSegs bulk execution (graph_executor.cc:842) → the
  whole graph is ONE compiled XLA program (the ultimate bulk segment)
- Monitor hook (graph_executor.cc:938) → eager per-node replay when a
  monitor is installed (the reference likewise disables bulk exec then)

Training-step economics: the reference runs forward then backward as two
engine pushes over shared buffers. Here ``forward(is_train=True)`` runs a
single fused fwd+bwd XLA program (outputs + gradients), caching gradients
keyed on argument version counters; ``backward()`` then just writes them
into ``grad_arrays`` honoring grad_req write/add/null — one compiled
program per batch, matching the reference's cost model.

grad_req semantics (write/add/null) follow OpReqType kWriteTo/kAddTo/kNullOp
(ref: include/mxnet/operator.h:43-56).
"""
from __future__ import annotations

import functools
import time as _time

import numpy as _np

from . import compile as _compile
from . import telemetry as _tel
from .analysis import compile_verify as _cv
from .telemetry import prof as _prof
from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray, zeros
from . import random as _random

__all__ = ["Executor"]


def _as_req_list(grad_req, arg_names):
    if isinstance(grad_req, str):
        return [grad_req] * len(arg_names)
    if isinstance(grad_req, (list, tuple)):
        return list(grad_req)
    if isinstance(grad_req, dict):
        return [grad_req.get(n, "null") for n in arg_names]
    raise MXNetError("invalid grad_req %r" % (grad_req,))


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 _compile_opts=None):
        import jax

        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = dict(group2ctx or {})
        self._monitor_callback = None

        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        # -- normalize args ---------------------------------------------------
        if isinstance(args, dict):
            missing = [n for n in self._arg_names if n not in args]
            if missing:
                raise MXNetError("bind: missing arguments %s" % missing)
            self.arg_arrays = [args[n] for n in self._arg_names]
        else:
            if len(args) != len(self._arg_names):
                raise MXNetError(
                    "bind: expected %d args, got %d" % (len(self._arg_names), len(args))
                )
            self.arg_arrays = list(args)

        if args_grad is None:
            self.grad_arrays = [None] * len(self._arg_names)
        elif isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in self._arg_names]
        else:
            self.grad_arrays = list(args_grad)
            while len(self.grad_arrays) < len(self._arg_names):
                self.grad_arrays.append(None)

        self._reqs = _as_req_list(grad_req, self._arg_names)
        for i, (g, r) in enumerate(zip(self.grad_arrays, self._reqs)):
            if g is None and r != "null":
                self._reqs[i] = "null"

        # -- aux states -------------------------------------------------------
        if aux_states is None:
            if self._aux_names:
                # derive aux shapes from the bound argument shapes
                shape_kwargs = {
                    n: a.shape for n, a in zip(self._arg_names, self.arg_arrays)
                }
                _, _, aux_shapes = symbol.infer_shape(**shape_kwargs)
                if aux_shapes is None or any(s is None for s in aux_shapes):
                    raise MXNetError("bind: aux_states required (shapes underdetermined)")
                self.aux_arrays = [zeros(s, self._ctx) for s in aux_shapes]
            else:
                self.aux_arrays = []
        elif isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in self._aux_names]
        else:
            self.aux_arrays = list(aux_states)

        # -- plan -------------------------------------------------------------
        # argument mapping keys off the ORIGINAL symbol's variable nodes;
        # the compile passes preserve variable objects by identity, so
        # the same map serves the rewritten graph (folded-away variables
        # simply stop being looked up)
        self._var_argidx = {}
        ai = 0
        for n in symbol.nodes:
            if n.is_variable:
                self._var_argidx[id(n)] = ai
                ai += 1
        self._multi_device = bool(self._group2ctx)
        # compile layer (docs/how_to/compilation.md): rewrite the graph
        # before lowering — off by default, skipped under the eager
        # multi-device pipeline (ctx_group placement is per ORIGINAL
        # node). A pass failure falls back to the unrewritten graph (a
        # slower bind must never become a crashed one); only the
        # explicit MXNET_COMPILE_VERIFY gate is allowed to propagate.
        self._exec_symbol = symbol
        if _compile.ENABLED and not self._multi_device:
            try:
                self._exec_symbol = _compile.optimize(
                    symbol,
                    input_shapes={
                        n: a.shape
                        for n, a in zip(self._arg_names, self.arg_arrays)},
                    input_types={
                        n: a.dtype
                        for n, a in zip(self._arg_names, self.arg_arrays)},
                    **dict(_compile_opts or {}))
            except _compile.CompileVerifyError:
                raise
            except Exception as e:
                import logging

                logging.getLogger("mxnet_tpu.compile").warning(
                    "graph rewrite failed (%s: %s); binding the "
                    "unrewritten graph", type(e).__name__, e)
                self._exec_symbol = symbol
        self._nodes = self._exec_symbol.nodes
        self._nid = {id(n): i for i, n in enumerate(self._nodes)}
        self._node_aux = {}
        pos = 0
        for n in self._nodes:
            if n.is_variable:
                continue
            na = len(n.op.list_auxiliary_states(n.params))
            if na:
                self._node_aux[id(n)] = (pos, pos + na)
                pos += na
        self._heads = [(self._nid[id(nd)], i)
                       for nd, i in self._exec_symbol._outputs]
        # loss-head semantics come from the USER's graph (rewrites never
        # wrap loss heads, and a boundary transpose head is never a loss)
        self._head_no_grad = [
            (not nd.is_variable) and nd.op.head_no_grad(nd.params)
            for nd, _ in symbol._outputs
        ]
        self._grad_idx = [i for i, r in enumerate(self._reqs) if r != "null"]

        # node devices for model parallelism (ctx_group; SURVEY §2.7)
        self._node_device = {}
        if self._multi_device:
            for n in self._nodes:
                grp = n.attrs.get("ctx_group")
                c = self._group2ctx.get(grp, self._ctx) if grp else self._ctx
                self._node_device[id(n)] = c.jax_device

        # gradient-checkpoint (memonger "mirror") planning: maximal runs of
        # consecutive mirrored nodes are rematerialized in backward via
        # jax.checkpoint (ref: static_graph.cc:404-422 force_mirroring attr,
        # MXNET_BACKWARD_DO_MIRROR env; demo example/memcost/)
        self._plan = self._build_mirror_plan()

        # hybrid (host-segmented) execution: graphs containing host ops
        # (Custom/NumpyOp/torch bridge) run as jitted segments with the
        # host ops executed EAGERLY between them — the reference's engine
        # model (custom ops are host functions between device kernels,
        # ref custom-inl.h) and the structural fix for the jax CPU
        # host-callback deadlock: no pure_callback ever enters a
        # compiled program on this path.
        self._host_serials = {
            i for i, n in enumerate(self._nodes)
            if not n.is_variable and n.op.is_host_op
        }
        self._hybrid = bool(self._host_serials) and not self._multi_device
        if self._hybrid:
            self._hyb_plan = self._build_hybrid_plan()
            self._seg_jit = {}      # (plan_idx, is_train) -> jitted fwd
            self._seg_bwd_jit = {}  # plan_idx -> jitted bwd
            self._hyb_saved = None
            # host-op instances live exactly as long as their executor
            # (the reference creates the operator once per binding,
            # custom-inl.h); a module-level cache would leak operators
            # across rebinds
            self._host_op_cache = {}

        # persistent jit cache (JAX_COMPILATION_CACHE_DIR): compiled
        # programs from this bind land on disk and the next process
        # loads them instead of rebuilding — no-op when unconfigured
        _compile.ensure_jit_cache()

        # jitted entry points (skip jit under multi-device eager pipeline)
        if self._multi_device:
            self._fwd_infer = functools.partial(self._run, is_train=False)
            self._fwd_train = functools.partial(self._run, is_train=True)
            self._fwd_bwd = self._fwd_bwd_impl
        elif self._hybrid:
            self._fwd_infer = functools.partial(
                self._hybrid_run, is_train=False)
            self._fwd_train = functools.partial(
                self._hybrid_run, is_train=True)
            self._fwd_bwd = None  # hybrid backward walks saved segments
        else:
            # budget 2: the rng arg dispatches as None (deterministic)
            # or a PRNG key array — two legal traces per entry point
            self._fwd_infer = _cv.wrap(
                "executor.fwd_infer",
                jax.jit(functools.partial(self._run, is_train=False)),
                budget=2, group="executor.bind")
            self._fwd_train = _cv.wrap(
                "executor.fwd_train",
                jax.jit(functools.partial(self._run, is_train=True)),
                budget=2, group="executor.bind")
            self._fwd_bwd = _cv.wrap(
                "executor.fwd_bwd", jax.jit(self._fwd_bwd_impl),
                budget=2, group="executor.bind")
            if _tel.ENABLED:
                # each bind builds fresh programs — under bucketing /
                # reshape this is the recompile stream worth watching
                _tel.counter("executor.jit_builds_total").inc(3)

        self._outputs_nd = None
        self._grad_cache = None  # (arg_versions, grads)
        # mxprof: entry points attributed (AOT cost/memory analysis)
        # lazily at first dispatch, when the concrete args exist
        self._prof_done = set()
        self._prof_analytic_memo = None
        self._prof_ghash = None

    # -- hybrid (host-segmented) engine ----------------------------------------
    def _graph_meta(self):
        head_keys = {(id(self._nodes[i]), j) for i, j in self._heads}
        consumers = {}
        for serial, n in enumerate(self._nodes):
            if n.is_variable:
                continue
            for s, i in n.inputs:
                consumers.setdefault((id(s), i), set()).add(serial)
        return head_keys, consumers

    def _segment_item(self, chunk, head_keys, consumers):
        """Describe a jit segment: external inputs, live outputs, aux
        window, rng-needing serials (same bookkeeping as the mirror
        plan's emit)."""
        seg_set = set(chunk)
        produced = []
        for s in chunk:
            n = self._nodes[s]
            for i in range(len(n.op.list_outputs(n.params))):
                produced.append((id(n), i))
        produced_set = set(produced)
        ext, seen = [], set()
        for s in chunk:
            for src, i in self._nodes[s].inputs:
                k = (id(src), i)
                if k not in produced_set and k not in seen:
                    seen.add(k)
                    ext.append(k)
        outs = [
            k for k in produced
            if k in head_keys or (consumers.get(k, set()) - seg_set)
        ]
        aux_slices = [
            self._node_aux[id(self._nodes[s])]
            for s in chunk if id(self._nodes[s]) in self._node_aux
        ]
        aux_ids = [j for lo, hi in aux_slices for j in range(lo, hi)]
        rng_serials = [s for s in chunk if self._nodes[s].op.need_rng]
        return ("seg", tuple(chunk), tuple(ext), tuple(outs),
                tuple(aux_ids), tuple(rng_serials))

    def _build_hybrid_plan(self):
        """Topo plan of ("var", serial) | ("host", serial, in_keys) |
        segment items. Host ops split the graph into maximal jittable
        segments; variables are env loads emitted in place."""
        head_keys, consumers = self._graph_meta()
        plan, run = [], []

        def flush():
            if run:
                plan.append(self._segment_item(tuple(run), head_keys,
                                               consumers))
                run.clear()

        for serial, n in enumerate(self._nodes):
            if n.is_variable:
                plan.append(("var", serial))
            elif serial in self._host_serials:
                flush()
                in_keys = tuple((id(s), i) for s, i in n.inputs)
                plan.append(("host", serial, in_keys))
            else:
                run.append(serial)
        flush()
        return plan

    def _seg_fn(self, item, is_train):
        """The pure function for one segment (ext, aux, rngs) ->
        (outs, new_aux)."""
        _, serials, ext_keys, out_keys, aux_ids, rng_serials = item

        def seg_fn(ext_vals, aux_in, rngs_in):
            local = dict(zip(ext_keys, ext_vals))
            laux = dict(zip(aux_ids, aux_in))
            rmap = dict(zip(rng_serials, rngs_in))
            for s in serials:
                self._apply_node(s, local, laux, rmap.get(s), is_train)
            return ([local[k] for k in out_keys],
                    [laux[j] for j in aux_ids])

        return seg_fn

    def _hybrid_run(self, arg_vals, aux_vals, rng, is_train, save=False):
        import jax

        dev = self._ctx.jax_device
        env = {}
        new_aux = list(aux_vals)
        saved = [] if save else None
        # any forward invalidates previously saved backward state: a
        # backward() after an inference forward must fail loudly, not
        # silently replay an older train batch's residuals (the jit
        # engine recomputes from current args; same observable contract)
        self._hyb_saved = None
        for idx, item in enumerate(self._hyb_plan):
            kind = item[0]
            if kind == "var":
                n = self._nodes[item[1]]
                env[(id(n), 0)] = arg_vals[self._var_argidx[id(n)]]
            elif kind == "host":
                _, serial, in_keys = item
                n = self._nodes[serial]
                ins_np = [_np.asarray(env[k]) for k in in_keys]  # D2H sync
                outs_np, bctx = n.op.host_apply(
                    n.params, ins_np, is_train, cache=self._host_op_cache)
                out_avals = []
                for i, o in enumerate(outs_np):
                    v = jax.device_put(_np.asarray(o), dev)
                    env[(id(n), i)] = v
                    out_avals.append((v.shape, v.dtype))
                if save:
                    saved.append(("host", idx, bctx, out_avals))
            else:
                _, serials, ext_keys, out_keys, aux_ids, rng_serials = item
                key = (idx, is_train)
                if key not in self._seg_jit:
                    self._seg_jit[key] = _cv.wrap(
                        "executor.seg|%s" % (key,),
                        jax.jit(self._seg_fn(item, is_train)),
                        budget=2, group="executor.seg")
                    if _tel.ENABLED:
                        _tel.counter("executor.jit_builds_total").inc()
                ext_vals = [env[k] for k in ext_keys]
                aux_in = [new_aux[j] for j in aux_ids]
                rngs = ([jax.random.fold_in(rng, s) for s in rng_serials]
                        if rng is not None else [])
                outs, aux_out = self._seg_jit[key](ext_vals, aux_in, rngs)
                env.update(zip(out_keys, outs))
                for j, v in zip(aux_ids, aux_out):
                    new_aux[j] = v
                if save:
                    saved.append(("seg", idx, ext_vals, aux_in, rngs,
                                  [(o.shape, o.dtype) for o in outs]))
        if save:
            self._hyb_saved = saved
        outputs = [env[(id(self._nodes[i]), j)] for i, j in self._heads]
        return outputs, new_aux

    def _seg_bwd(self, idx):
        """Jitted segment backward: re-runs the segment forward under
        jax.vjp with the saved inputs (rematerialization — the memory
        schedule mirror nodes buy on the jit path comes free here) and
        pulls cotangents back to the segment's external inputs. aux
        updates are state, not differentiable outputs."""
        if idx in self._seg_bwd_jit:
            return self._seg_bwd_jit[idx]
        import jax

        item = self._hyb_plan[idx]
        seg_fn = self._seg_fn(item, True)
        import jax.numpy as jnp

        def bwd(ext_vals, aux_in, rngs, out_cts):
            # out_cts covers only the inexact (differentiable) outputs;
            # integer outputs are filtered out of the vjp so no float0
            # cotangents cross the jit boundary (dtype mask is static
            # at trace time)
            def f(ev):
                outs, _ = seg_fn(ev, aux_in, rngs)
                return [o for o in outs
                        if jnp.issubdtype(o.dtype, jnp.inexact)]

            _, vjp_fn = jax.vjp(f, ext_vals)
            (ext_cts,) = vjp_fn(out_cts)
            return ext_cts

        self._seg_bwd_jit[idx] = _cv.wrap(
            "executor.seg_bwd|%d" % idx, jax.jit(bwd),
            budget=2, group="executor.seg")
        if _tel.ENABLED:
            _tel.counter("executor.jit_builds_total").inc()
        return self._seg_bwd_jit[idx]

    def _hybrid_backward(self, head_grads):
        """Reverse-mode over the hybrid plan: cotangents flow backward
        through jitted segment vjps and eager host-op backwards, then
        accumulate into grad_arrays per grad_req."""
        import jax
        import jax.numpy as jnp

        if self._hyb_saved is None:
            raise MXNetError("backward before forward(is_train=True)")
        dev = self._ctx.jax_device
        float0 = jax.dtypes.float0
        cot = {}
        for (nidx, oidx), hg in zip(self._heads, head_grads):
            if hg is None:  # integer-dtype head: no cotangent exists
                continue
            k = (id(self._nodes[nidx]), oidx)
            cot[k] = cot.get(k, 0) + hg

        def _accum(key, g):
            if g is None or getattr(g, "dtype", None) == float0:
                return
            cot[key] = cot.get(key, 0) + g

        for entry in reversed(self._hyb_saved):
            if entry[0] == "host":
                _, idx, bctx, out_avals = entry
                item = self._hyb_plan[idx]
                _, serial, in_keys = item
                n = self._nodes[serial]
                # no cotangent reached any output -> skip the eager host
                # backward, UNLESS this is a loss-semantics op
                # (head_no_grad): those produce real input grads while
                # IGNORING out_grads, so absence of cotangents does not
                # mean zero gradients for them
                if (not n.op.head_no_grad(n.params)
                        and all(cot.get((id(n), i)) is None
                                for i in range(len(out_avals)))):
                    continue
                ogs = []
                for i, (shape, dtype) in enumerate(out_avals):
                    c = cot.get((id(n), i))
                    ogs.append(_np.zeros(shape, dtype) if c is None
                               else _np.asarray(c))
                in_grads = n.op.host_grad(n.params, bctx, ogs)
                for k, g in zip(in_keys, in_grads):
                    _accum(k, jax.device_put(_np.asarray(g), dev))
            else:
                _, idx, ext_vals, aux_in, rngs, out_avals = entry
                item = self._hyb_plan[idx]
                out_keys = item[3]
                # only inexact outputs participate in the vjp (same
                # static mask as _seg_bwd's filtered forward)
                pairs = [
                    (cot.get(k), av) for k, av in zip(out_keys, out_avals)
                    if jnp.issubdtype(jnp.dtype(av[1]), jnp.inexact)
                ]
                # all-zero cotangents still cost a backward pass; skip
                # segments nothing flowed into (e.g. past a BlockGrad)
                if all(c is None or getattr(c, "dtype", None) == float0
                       for c, _ in pairs):
                    continue
                out_cts = [
                    jnp.zeros(av[0], jnp.dtype(av[1])) if c is None
                    else (c.astype(av[1])
                          if getattr(c, "dtype", None) != jnp.dtype(av[1])
                          else c)
                    for c, av in pairs
                ]
                ext_cts = self._seg_bwd(idx)(ext_vals, aux_in, rngs, out_cts)
                for k, g in zip(item[2], ext_cts):
                    _accum(k, g)

        argidx_key = getattr(self, "_argidx_key", None)
        if argidx_key is None:
            argidx_key = self._argidx_key = {
                self._var_argidx[id(n)]: (id(n), 0)
                for n in self._nodes if n.is_variable
            }
        grads = []
        for i in self._grad_idx:
            g = cot.get(argidx_key.get(i))
            if g is None or getattr(g, "dtype", None) == float0:
                g = jnp.zeros(self.arg_arrays[i].shape,
                              self.arg_arrays[i]._data.dtype)
            grads.append(g)
        self._apply_grads(grads)
        # release the saved activations/residuals: a full per-batch
        # activation set must not stay pinned between optimizer steps
        self._hyb_saved = None

    # -- mirror (gradient checkpointing) planning ------------------------------
    def _build_mirror_plan(self):
        """Group consecutive mirrored nodes into remat segments.

        Returns a list of plan items: ``("node", serial)`` or
        ``("seg", serials, ext_keys, out_keys)`` where keys are
        ``(node_id, out_idx)`` env entries. Mirroring comes from the
        ``force_mirroring`` node attr, with MXNET_BACKWARD_DO_MIRROR as the
        global default (ref: static_graph.cc:404-422)."""
        import math

        from .base import env_bool, env_int

        mirror_all = env_bool("MXNET_BACKWARD_DO_MIRROR", False)
        # selective recompute: regex over op names — remat only matching
        # nodes (e.g. "BatchNorm|Activation" recomputes the cheap
        # elementwise ops in backward, trading VPU time for the HBM
        # re-reads that bound convnets, WITHOUT recomputing the convs
        # the way MXNET_BACKWARD_DO_MIRROR=1 does). Extends the ref's
        # per-node force_mirroring attr to a pattern
        # (ref: static_graph.cc:404-422).
        import os as _os
        import re as _re

        pattern = _os.environ.get("MXNET_BACKWARD_MIRROR_PATTERN", "")
        pat = _re.compile(pattern) if pattern else None
        # segment length: remat in chunks so backward peak holds one
        # chunk's activations, not the whole graph's (ref mirror_step,
        # static_graph.cc:404-422). 0 = sqrt(run length), the classic
        # O(sqrt(N)) memory schedule.
        mirror_step = env_int("MXNET_BACKWARD_MIRROR_STEP", 0)

        def mirrored(n):
            if n.is_variable:
                return False
            a = n.attrs.get("force_mirroring")
            if a is not None:
                return str(a).lower() in ("true", "1")
            if pat is not None and pat.search(n.op.name):
                return True
            return mirror_all

        # multi-device eager pipeline doesn't jit; keep per-node plan
        if self._multi_device or not any(mirrored(n) for n in self._nodes):
            return [("node", i) for i in range(len(self._nodes))]

        head_keys, consumers = self._graph_meta()

        plan, run = [], []

        def emit(chunk):
            plan.append(self._segment_item(tuple(chunk), head_keys,
                                           consumers))

        def flush():
            if not run:
                return
            step = mirror_step or max(1, int(math.sqrt(len(run))))
            for lo in range(0, len(run), step):
                emit(run[lo:lo + step])
            run.clear()

        for serial, n in enumerate(self._nodes):
            if mirrored(n):
                run.append(serial)
            elif n.is_variable:
                # variables are plain env loads — emit them ahead of the
                # open segment instead of splitting it (weight variables
                # interleave with ops in topo order; splitting would
                # reduce every segment to a single op)
                plan.append(("node", serial))
            else:
                flush()
                plan.append(("node", serial))
        flush()
        return plan

    def _apply_node(self, serial, env, aux_store, node_rng, is_train):
        """Evaluate one node into env/aux_store. aux_store is indexed by
        global aux position (list in the main loop, dict inside remat
        segments). node_rng is the already-folded per-node key or None."""
        import jax

        n = self._nodes[serial]
        ins = [env[(id(s), i)] for s, i in n.inputs]
        if self._multi_device:
            dev = self._node_device[id(n)]
            ins = [jax.device_put(x, dev) for x in ins]
        sl = self._node_aux.get(id(n))
        aux_in = [aux_store[j] for j in range(sl[0], sl[1])] if sl else []
        # the node's lowering under its operator's type and its own name
        # (``sym.BatchNorm/bn0``): what mx.profiler.scope_map reads off the
        # compiled program, forward and backward
        with jax.named_scope("sym.%s" % n.op.name), jax.named_scope(n.name):
            outs, n_aux = n.op.apply(n.params, ins, aux_in, is_train,
                                     node_rng)
        for i, o in enumerate(outs):
            env[(id(n), i)] = o
        if sl:
            for j, v in zip(range(sl[0], sl[1]), n_aux):
                aux_store[j] = v

    # -- the traced program ----------------------------------------------------
    def _run(self, arg_vals, aux_vals, rng, is_train):
        import jax

        env = {}
        new_aux = list(aux_vals)
        for item in self._plan:
            if item[0] == "node":
                serial = item[1]
                n = self._nodes[serial]
                if n.is_variable:
                    v = arg_vals[self._var_argidx[id(n)]]
                    if self._multi_device:
                        v = jax.device_put(v, self._node_device[id(n)])
                    env[(id(n), 0)] = v
                    continue
                node_rng = (
                    jax.random.fold_in(rng, serial)
                    if (n.op.need_rng and rng is not None)
                    else None
                )
                self._apply_node(serial, env, new_aux, node_rng, is_train)
                continue

            # remat segment: recompute these nodes' activations in
            # backward (same segment closure as the hybrid engine)
            _, serials, ext_keys, out_keys, aux_ids, rng_serials = item
            seg_fn = self._seg_fn(item, is_train)
            fn = jax.checkpoint(seg_fn) if is_train else seg_fn
            ext_vals = [env[k] for k in ext_keys]
            aux_in = [new_aux[j] for j in aux_ids]
            rngs = ([jax.random.fold_in(rng, s) for s in rng_serials]
                    if rng is not None else [])
            outs, aux_out = fn(ext_vals, aux_in, rngs)
            env.update(zip(out_keys, outs))
            for j, v in zip(aux_ids, aux_out):
                new_aux[j] = v
        outputs = [env[(id(self._nodes[i]), j)] for i, j in self._heads]
        return outputs, new_aux

    def _fwd_bwd_impl(self, arg_vals, aux_vals, rng, head_grads):
        """head_grads: cotangents for the INEXACT-dtype heads only, in
        head order — integer heads (e.g. a BlockGrad'd id tensor riding
        along for metrics) are excluded from the vjp entirely, since
        jax.vjp demands float0 cotangents for them. aux states travel
        through has_aux (state, not differentiable outputs)."""
        import jax
        import jax.numpy as jnp

        gidx = self._grad_idx

        def f(ga):
            vals = list(arg_vals)
            for i, g in zip(gidx, ga):
                vals[i] = g
            outs, new_aux = self._run(vals, aux_vals, rng, is_train=True)
            flt = [o for o in outs if jnp.issubdtype(o.dtype, jnp.inexact)]
            return flt, (outs, new_aux)

        ga0 = [arg_vals[i] for i in gidx]
        _, vjp_fn, (outs, new_aux) = jax.vjp(f, ga0, has_aux=True)
        (grads,) = vjp_fn(list(head_grads))
        return outs, new_aux, grads

    # -- mxprof attribution ----------------------------------------------------
    def _prof_analytic(self):
        """Analytic DAG cost for this bind (memoized; jax-free walk)."""
        if self._prof_analytic_memo is None:
            try:
                self._prof_analytic_memo = _prof.graph_cost(
                    self._symbol,
                    {n: a.shape for n, a in zip(self._arg_names,
                                                self.arg_arrays)},
                    {n: a.dtype for n, a in zip(self._arg_names,
                                                self.arg_arrays)})
            except Exception:
                self._prof_analytic_memo = {}
        return self._prof_analytic_memo or None

    def _prof_attribute(self, tag, fn, args):
        """Swap a jitted entry point for its AOT-compiled, cost-
        attributed form on first dispatch (MXNET_PROF=1 only; the
        jitted paths are fixed-shape per bind so the compiled callable
        is a drop-in). Returns the callable to dispatch."""
        if tag in self._prof_done or self._hybrid or self._multi_device \
                or self.arg_arrays is None:
            return fn
        self._prof_done.add(tag)
        sig = ",".join(
            "%s=%s:%s" % (n, "x".join(str(d) for d in a.shape), a.dtype)
            for n, a in zip(self._arg_names, self.arg_arrays))
        if self._prof_ghash is None:
            # graph identity: attribute_jit's memo must never hand one
            # bind's compiled program to a DIFFERENT program whose arg
            # shapes happen to coincide — the symbol fingerprint covers
            # op params (relu-vs-tanh), grad_req covers which args the
            # vjp differentiates (frozen-param binds are different
            # fwd_bwd programs at identical shapes)
            try:
                self._prof_ghash = "%s|req=%s" % (
                    _prof.symbol_fingerprint(self._exec_symbol),
                    ",".join(self._reqs))
            except Exception:
                self._prof_ghash = "%x" % id(self._exec_symbol)
        # rebind through the verifier boundary (if one wraps this entry
        # point) so compile counting survives the AOT swap
        out = _cv.rebind(fn, _prof.attribute_jit(
            "executor|%s|%s" % (tag, sig), _cv.unwrap(fn), args,
            site="executor.%s" % tag, analytic=self._prof_analytic(),
            meta={"outputs": self._output_names},
            graph_key=self._prof_ghash))
        setattr(self, "_" + tag, out)  # tag IS the entry-point attr name
        return out

    # -- helpers ---------------------------------------------------------------
    def _release_device_arrays(self):
        """Free this executor's device arg/grad/aux arrays while keeping
        the traced program (`_run`) usable as a pure function. A trainer
        that only borrows `_run` (fit_trainer) calls this so the bound
        method doesn't pin a second parameter set in HBM.
        The executor is unusable for forward/backward afterwards."""
        self.arg_arrays = self.grad_arrays = self.aux_arrays = None
        self._outputs_nd = None

    def _arg_vals(self):
        return [a._data for a in self.arg_arrays]

    def _aux_vals(self):
        return [a._data for a in self.aux_arrays]

    def _default_head_grads(self):
        """Default cotangents per head: ones for loss ops, zeros
        otherwise, None for integer-dtype heads (no cotangent exists —
        the vjp paths exclude them)."""
        import jax.numpy as jnp

        if self._outputs_nd is None or len(self._outputs_nd) != len(self._heads):
            raise MXNetError("backward before forward")
        hg = []
        for out_nd, no_grad in zip(self._outputs_nd, self._head_no_grad):
            d = out_nd._data.dtype
            if not jnp.issubdtype(d, jnp.inexact):
                hg.append(None)
                continue
            fill = 1.0 if no_grad else 0.0
            hg.append(jnp.full(out_nd.shape, fill, dtype=d))
        return hg

    def _versions(self):
        return tuple(a.version for a in self.arg_arrays) + tuple(
            a.version for a in self.aux_arrays
        )

    def _write_outputs(self, outs):
        if self._outputs_nd is None:
            self._outputs_nd = [NDArray(o, self._ctx) for o in outs]
        else:
            for nd, o in zip(self._outputs_nd, outs):
                nd._set_data(o)

    def _write_aux(self, new_aux):
        for nd, v in zip(self.aux_arrays, new_aux):
            nd._set_data(v)

    def _monitor_replay(self, is_train):
        """Eager per-node replay invoking the monitor callback per output
        (ref: graph_executor.cc:938-955 + monitor install disabling bulk)."""
        import jax

        env = {}
        aux_vals = self._aux_vals()
        arg_vals = self._arg_vals()
        rng = _random.next_key()
        for serial, n in enumerate(self._nodes):
            if n.is_variable:
                env[(id(n), 0)] = arg_vals[self._var_argidx[id(n)]]
                continue
            ins = [env[(id(s), i)] for s, i in n.inputs]
            aux_slice = self._node_aux.get(id(n))
            aux_in = aux_vals[aux_slice[0]:aux_slice[1]] if aux_slice else []
            node_rng = jax.random.fold_in(rng, serial) if n.op.need_rng else None
            outs, _ = n.op.apply(n.params, ins, aux_in, is_train, node_rng)
            onames = n.op.list_outputs(n.params)
            for i, o in enumerate(outs):
                env[(id(n), i)] = o
                self._monitor_callback(
                    "%s_%s" % (n.name, onames[i]), NDArray(o, self._ctx)
                )

    # -- public API ------------------------------------------------------------
    @property
    def outputs(self):
        """ref: python/mxnet/executor.py outputs property."""
        if self._outputs_nd is None:
            self.forward(is_train=False)
        return self._outputs_nd

    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def forward(self, is_train=False, **kwargs):
        """ref: python/mxnet/executor.py:118 / GraphExecutor::Forward.
        mxtel: per-call walltime lands in ``executor.forward_secs``
        (all binds aggregate into one process histogram)."""
        if not _tel.ENABLED:
            return self._forward_impl(is_train, **kwargs)
        t0 = _time.monotonic()
        try:
            return self._forward_impl(is_train, **kwargs)
        finally:
            _tel.histogram("executor.forward_secs").observe(
                _time.monotonic() - t0)

    def _forward_impl(self, is_train=False, **kwargs):
        if kwargs:
            arg_dict = self.arg_dict
            for k, v in kwargs.items():
                if k not in arg_dict:
                    raise MXNetError("forward: unknown argument %s" % k)
                if isinstance(v, NDArray):
                    v.copyto(arg_dict[k])
                else:
                    arg_dict[k][:] = v
        if self._monitor_callback is not None:
            self._monitor_replay(is_train)

        rng = _random.next_key() if is_train else None
        if self._hybrid:
            outs, new_aux = self._hybrid_run(
                self._arg_vals(), self._aux_vals(), rng, is_train,
                save=is_train and bool(self._grad_idx))
            self._write_outputs(outs)
            if is_train:
                self._write_aux(new_aux)
            self._grad_cache = None
            return self.outputs
        if is_train and self._grad_idx and all(self._head_no_grad):
            # fused fwd+bwd program; gradients cached for backward().
            # Only worth it when EVERY head is a loss op: with any
            # non-loss head, backward() REQUIRES out_grads and re-runs
            # the vjp with real cotangents, so a fused pass here would
            # compute a full backward only to discard it.
            self._outputs_shape_probe()
            hg = [g for g in self._default_head_grads() if g is not None]
            if _prof.ENABLED:
                self._prof_attribute(
                    "fwd_bwd", self._fwd_bwd,
                    (self._arg_vals(), self._aux_vals(), rng, hg))
            outs, new_aux, grads = self._fwd_bwd(
                self._arg_vals(), self._aux_vals(), rng, hg
            )
            self._write_outputs(outs)
            self._write_aux(new_aux)
            self._grad_cache = (self._versions(), grads)
        else:
            if _prof.ENABLED:
                if is_train:
                    self._prof_attribute(
                        "fwd_train", self._fwd_train,
                        (self._arg_vals(), self._aux_vals(), rng))
                else:
                    self._prof_attribute(
                        "fwd_infer", self._fwd_infer,
                        (self._arg_vals(), self._aux_vals(), None))
            outs, new_aux = (
                self._fwd_train(self._arg_vals(), self._aux_vals(), rng)
                if is_train
                else self._fwd_infer(self._arg_vals(), self._aux_vals(), None)
            )
            self._write_outputs(outs)
            if is_train:
                self._write_aux(new_aux)
            self._grad_cache = None
        return self.outputs

    def _outputs_shape_probe(self):
        """Populate output shapes once (needed for default head grads)."""
        if self._outputs_nd is None:
            outs, _ = self._fwd_infer(self._arg_vals(), self._aux_vals(), None)
            self._write_outputs(outs)

    def backward(self, out_grads=None):
        """ref: python/mxnet/executor.py:148 / GraphExecutor::Backward.
        With no out_grads, heads must be loss ops (no_head_grad) — the
        reference asserts the same (graph_executor.cc head_grad handling).
        mxtel: per-call walltime lands in ``executor.backward_secs``."""
        if not _tel.ENABLED:
            return self._backward_impl(out_grads)
        t0 = _time.monotonic()
        try:
            return self._backward_impl(out_grads)
        finally:
            _tel.histogram("executor.backward_secs").observe(
                _time.monotonic() - t0)

    def _backward_impl(self, out_grads=None):
        import jax.numpy as jnp

        if not self._grad_idx:
            return
        if out_grads is None:
            if not all(self._head_no_grad):
                raise MXNetError(
                    "backward() without out_grads requires loss-op heads; "
                    "pass out_grads for outputs %s"
                    % [n for n, ng in zip(self._output_names, self._head_no_grad) if not ng]
                )
            if self._grad_cache is not None and self._grad_cache[0] == self._versions():
                grads = self._grad_cache[1]
                self._apply_grads(grads)
                return
            if self._hybrid:
                self._hybrid_backward(self._default_head_grads())
                return
            hg = self._default_head_grads()
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            if isinstance(out_grads, dict):
                out_grads = [out_grads[n] for n in self._output_names]
            hg = [
                (g._data if isinstance(g, NDArray) else jnp.asarray(g))
                for g in out_grads
            ]
            # cotangents for integer-dtype heads do not exist; drop any
            # the caller supplied (mirrors _default_head_grads). Output
            # dtypes come from a shape probe ONLY when no forward ran
            # yet (the probe is itself a forward: in hybrid mode it
            # invalidates saved backward state) — without the mask an
            # integer head would feed the vjp one cotangent too many
            if self._outputs_nd is None:
                self._outputs_shape_probe()
            hg = [
                None if not jnp.issubdtype(o._data.dtype, jnp.inexact)
                else g
                for g, o in zip(hg, self._outputs_nd)
            ]
        if self._hybrid:
            self._hybrid_backward(hg)
            return
        rng = _random.next_key()
        outs, new_aux, grads = self._fwd_bwd(
            self._arg_vals(), self._aux_vals(), rng,
            [g for g in hg if g is not None]
        )
        self._write_outputs(outs)
        self._apply_grads(grads)

    def _apply_grads(self, grads):
        for slot, i in enumerate(self._grad_idx):
            g = grads[slot]
            tgt = self.grad_arrays[i]
            req = self._reqs[i]
            if req == "write":
                tgt._set_data(g.astype(tgt._data.dtype))
            elif req == "add":
                tgt._set_data(tgt._data + g.astype(tgt._data.dtype))

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        """ref: python/mxnet/executor.py:211."""
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: %s not an argument" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    arr.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError("copy_params_from: %s not an aux state" % name)

    def set_monitor_callback(self, callback):
        """ref: python/mxnet/executor.py:86 / MXExecutorSetMonitorCallback."""
        self._monitor_callback = callback

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new shapes sharing parameter arrays — the analog of
        bucketing's shared-memory rebind (ref: graph_executor.h:50 shared_exec)."""
        new_shapes = {}
        arg_shapes, _, _ = self._symbol.infer_shape_partial(**kwargs)
        arg_dict = self.arg_dict
        new_args = {}
        for name, s in zip(self._arg_names, arg_shapes):
            cur = arg_dict[name]
            if s is not None and tuple(s) != cur.shape:
                new_args[name] = zeros(s, cur.context, cur.dtype)
            else:
                new_args[name] = cur
        grads = {
            n: (g if g is not None else None)
            for n, g in zip(self._arg_names, self.grad_arrays)
        }
        new_grads = {}
        for n, g in grads.items():
            if g is None:
                continue
            tgt_shape = new_args[n].shape
            new_grads[n] = g if g.shape == tgt_shape else zeros(tgt_shape, g.context, g.dtype)
        return Executor(
            self._symbol, self._ctx, new_args,
            args_grad=new_grads or None,
            grad_req={n: r for n, r in zip(self._arg_names, self._reqs)},
            aux_states=self.aux_arrays, group2ctx=self._group2ctx,
        )

    def debug_str(self):
        return self._symbol.debug_str()

    # -- simple_bind -----------------------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                     group2ctx=None, shared_exec=None, **kwargs):
        """ref: python/mxnet/symbol.py:635 simple_bind — allocate all
        argument/grad/aux arrays from inferred shapes."""
        import numpy as np

        ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("simple_bind: cannot infer shapes from %s" % kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_types, _, aux_types = symbol.infer_type(
            **{k: v for k, v in (type_dict or {}).items()}
        )
        # Bucketing memory share (the GraphStoragePool role of
        # graph_memory_allocator.h:40-122 / graph_executor.h:274): a bucket
        # bound with shared_exec reuses the shared executor's argument,
        # GRADIENT and aux buffers whenever name+shape+dtype line up — for
        # an RNN bucket family that is every parameter, so per-bucket
        # NDArray memory is O(data shapes), not O(params x buckets).
        # Shapes that differ between buckets (data/label/states) get fresh
        # arrays; their old per-bucket intermediates live INSIDE each jit
        # program where XLA's arena (not Python) owns reuse, so the
        # reference's size-range matching has no analog to do here.
        shared_args = shared_exec.arg_dict if shared_exec is not None else {}
        shared_grads = shared_exec.grad_dict if shared_exec is not None else {}
        shared_aux = shared_exec.aux_dict if shared_exec is not None else {}
        shared_reqs = (dict(zip(shared_exec._arg_names, shared_exec._reqs))
                       if shared_exec is not None else {})
        args = {}
        for name, shape, t in zip(arg_names, arg_shapes, arg_types):
            cand = shared_args.get(name)
            if cand is not None and cand.shape == tuple(shape) and cand.dtype == t:
                args[name] = cand
            else:
                args[name] = zeros(shape, ctx, dtype=t)
        reqs = _as_req_list(grad_req, arg_names)
        args_grad = {}
        for name, shape, t, r in zip(arg_names, arg_shapes, arg_types, reqs):
            if r == "null":
                continue
            cand = shared_grads.get(name)
            # "add" keeps private buffers ON BOTH SIDES: a shared
            # accumulator would mix gradient sums across buckets between
            # updates, and a "write" bucket aliasing an "add" accumulator
            # would clobber partially accumulated state
            if (r == "write" and shared_reqs.get(name) == "write"
                    and cand is not None
                    and cand.shape == tuple(shape) and cand.dtype == t):
                args_grad[name] = cand
            else:
                args_grad[name] = zeros(shape, ctx, dtype=t)
        aux_states = []
        for i, (name, shape, t) in enumerate(zip(aux_names, aux_shapes, aux_types)):
            cand = shared_aux.get(name)
            if cand is not None and cand.shape == tuple(shape) and cand.dtype == t:
                # shared aux keeps moving stats consistent across buckets,
                # like the reference's shared data_entry for aux
                aux_states.append(cand)
                continue
            # default aux init: variance-like states to 1 (ref: initializer.py
            # _init_one for moving_var), others 0
            if "var" in name:
                from .ndarray import ones as _ones

                aux_states.append(_ones(shape, ctx, dtype=t))
            else:
                aux_states.append(zeros(shape, ctx, dtype=t))
        return Executor(
            symbol, ctx, args, args_grad=args_grad or None, grad_req=grad_req,
            aux_states=aux_states, group2ctx=group2ctx, shared_exec=shared_exec,
        )
