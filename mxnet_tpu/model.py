"""FeedForward estimator + checkpointing
(ref: python/mxnet/model.py:1-924). The _train_multi_device loop
(model.py:117) is preserved: slice batch per device, fwd/bwd per executor,
sync gradients through KVStore (update_on_kvstore) or local updater, update
metric host-side. Checkpoints are `prefix-symbol.json` +
`prefix-%04d.params` with arg:/aux: name prefixes, as in the reference
(save_checkpoint model.py:311)."""
from __future__ import annotations

import logging
import os
import re
import threading
import time
from collections import namedtuple

import numpy as _np

from . import telemetry as _tel
from .telemetry import prof as _prof
from .base import MXNetError
from .resilience import faults as _faults
from .resilience import guardian as _guardian
from .context import Context, cpu, current_context
from .ndarray import NDArray, zeros, load as nd_load, save as nd_save
from . import io
from . import metric as metric_mod
from . import optimizer as opt
from .executor_manager import DataParallelExecutorManager, _check_arguments
from .initializer import Uniform
from . import ndarray as nd
from .symbol import Symbol, load as sym_load

BASE_ESTIMATOR = object

BatchEndParam = namedtuple(
    "BatchEndParams", ["epoch", "nbatch", "eval_metric", "locals"]
)


def _create_kvstore(kvstore, num_device, arg_params):
    """ref: python/mxnet/model.py:39."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            from . import kvstore as kvs

            kv = kvs.create(kvstore)
            if kvstore == "local":
                max_size = max(_np.prod(param.shape) for param in arg_params.values())
                if max_size < 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        kv = kvstore
    if kv is None:
        update_on_kvstore = False
    return (kv, update_on_kvstore)


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names, update_on_kvstore):
    """ref: model.py:87."""
    for idx, param_on_devs in enumerate(param_arrays):
        kvstore.init(idx, arg_params[param_names[idx]])
        if update_on_kvstore:
            kvstore.pull(idx, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore):
    """ref: model.py:97."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list[0] is None:
            continue
        # grad.nan/loss.spike chaos points (no-op unless armed): only
        # for stores with no in-process updater (the elastic path,
        # where the update runs server-side and the poison must ride
        # the aggregation round into the server guard) — a store with a
        # local updater injects inside get_updater already, and firing
        # here too would double-draw the seeded pattern per step
        if getattr(kvstore, "_updater", None) is None:
            grad_list = [g if g is None else _guardian.corrupt_grad(g)
                         for g in grad_list]
        kvstore.push(index, grad_list, priority=-index)
        kvstore.pull(index, arg_list, priority=-index)


def _update_params(param_arrays, grad_arrays, updater, num_device, kvstore=None):
    """ref: model.py:107."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list[0] is None:
            continue
        if kvstore:
            kvstore.push(index, grad_list, priority=-index)
            kvstore.pull(index, grad_list, priority=-index)
        for k, p in enumerate(zip(arg_list, grad_list)):
            w, g = p
            updater(index * num_device + k, g, w)


def _desc_name(d):
    """provide_data/provide_label entries are (name, shape) tuples or
    DataDesc namedtuples."""
    return d.name if isinstance(d, io.DataDesc) else d[0]


def _desc_shape(d):
    return tuple(d.shape if isinstance(d, io.DataDesc) else d[1])


def _scan_k():
    """Steps fused per dispatch in the scanned fit path; 0 disables."""
    import os

    if os.environ.get("MXNET_SCAN_TRAIN", "1") in ("0", "false", "off"):
        return 0
    return int(os.environ.get("MXNET_TRAIN_SCAN_K", "8"))


def _buffer_batch(data_batch, input_names):
    """Snapshot one DataBatch for deferred staging (shared by the two
    scanned loops): stage_chunk and _scan_drain read these values up to
    K batches after the iterator has advanced, so nothing the iterator
    can mutate may be held by reference. NDArray entries are unwrapped
    to their backing ``jax.Array`` — the array itself is immutable, but
    the NDArray facade is not (``__setitem__`` rebinds ``_data``), so a
    DataIter recycling its NDArray batch objects would otherwise alias
    every buffered dict to the newest batch. Raw numpy arrays are
    copied for the same reason (iterators that reuse their numpy
    buffers are common in the reference ecosystem)."""
    arrs = [a._data if isinstance(a, NDArray)
            else (_np.array(a) if isinstance(a, _np.ndarray) else a)
            for a in list(data_batch.data) + list(data_batch.label)]
    return dict(zip(input_names, arrs))


def _scan_flush(trainer, buf, epoch, nbatch0, guardian=None):
    """Dispatch one K-batch chunk; returns the pending record drained
    after the NEXT chunk is in flight (shared by FeedForward's
    _train_scanned and Module._try_scanned_fit). mxtel: the "fit.chunk"
    span covers staging + dispatch (the async device work completes
    later — the drain's metric fence is its clock) and carries the
    update count the chunk starts at as its step number. The trainer's
    guardian verdicts for the chunk ride the pending record.

    Guardian snapshots are captured HERE, before the dispatch mutates
    the trainer state: at flush time the state is the previous chunk's
    result, which the drain interleaved with this flush verifies — the
    payload is committed to the last-good ring only after that
    verification passes (commit_snapshot). Snapshotting at drain time
    instead would capture state the in-flight chunk has already
    advanced (and possibly poisoned) past the verified steps."""
    with _tel.span("fit.chunk", step=trainer.optimizer.num_update):
        snap = None
        if guardian is not None and guardian.snapshot_due():
            snap = trainer.snapshot_state()
        if _prof.ENABLED:
            # mxprof step decomposition: staging is the host/input
            # phase, run_chunk the dispatch phase; the drain that runs
            # alongside the NEXT flush measures device + D2H. A chunk
            # whose dispatch performed the attribution compile is NOT
            # recorded — seconds of XLA build inside the window would
            # drown the steady-state phase shares.
            n_attr = _prof.attribution_count()
            t0 = time.monotonic()
            staged = trainer.stage_chunk(buf)
            t1 = time.monotonic()
            outs = trainer.run_chunk(staged)
            t2 = time.monotonic()
            prof_ctx = (trainer.last_program_key, t1 - t0, t2 - t1) \
                if _prof.attribution_count() == n_attr else None
        else:
            staged = trainer.stage_chunk(buf)
            outs = trainer.run_chunk(staged)
            prof_ctx = None
        return (outs, trainer.take_step_flags(), snap, buf, epoch, nbatch0,
                prof_ctx)


def _scan_drain(pending, eval_metric, label_names, batch_end_callback,
                nbatch_base, guardian=None):
    """Metric updates + per-batch callbacks for a completed chunk.
    nbatch_base: FeedForward numbers batches from 1, Module from 0.
    Returns the guardian's chunk verdict ("ok"/"skip"/"rollback"; "ok"
    when unguarded) — the caller owns acting on a rollback.

    D2H minimisation: Accuracy only needs the argmax class id per
    sample — reduce [K,N,C] probabilities to [K,N] ids ON DEVICE before
    pulling to host (a [K,N,C] float pull per chunk is the largest
    transfer of the loop). Accuracy already accepts 1-D predicted
    labels."""
    if pending is None:
        return "ok"
    with _tel.span("fit.metric"):
        return _scan_drain_chunk(pending, eval_metric, label_names,
                                 batch_end_callback, nbatch_base, guardian)


def _scan_drain_chunk(pending, eval_metric, label_names, batch_end_callback,
                      nbatch_base, guardian):
    """:func:`_scan_drain`'s work, under its ``fit.metric`` span: the pull
    of the chunk's outputs (the fence), the metric's updates and, each
    under a ``fit.callbacks`` span of its own, the per-batch callbacks."""
    outs, flags, snap, bufs, epoch, nbatch0, prof_ctx = pending
    if guardian is not None:
        # the snapshot captured at this chunk's flush is the PREVIOUS
        # chunk's result, verified by the drain that ran alongside that
        # flush — commit it before accounting this chunk's flags
        guardian.commit_snapshot(snap)
    if prof_ctx is not None:
        # device phase: how long the drain truly blocks on the chunk's
        # compute (block-until-ready delta — zero when the device
        # already finished while the host staged the next chunk)
        td = time.monotonic()
        for o in outs:
            bur = getattr(o, "block_until_ready", None)
            if bur is not None:
                bur()
        t_device = time.monotonic() - td
        td = time.monotonic()
    if (type(eval_metric) is metric_mod.Accuracy and len(outs) == 1
            and getattr(outs[0], "ndim", 0) == 3):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("metric"):
            host_outs = [_np.asarray(jnp.argmax(outs[0], axis=-1))]
    else:
        host_outs = [_np.asarray(o) for o in outs]  # one D2H per head
    from .analysis import compile_verify as _cv

    _cv.note_d2h(sum(int(h.nbytes) for h in host_outs),
                 "mxnet_tpu/model.py::_scan_drain")
    if prof_ctx is not None:
        key, t_host, t_dispatch = prof_ctx
        samples = None
        if host_outs and getattr(host_outs[0], "ndim", 0) >= 2:
            samples = int(host_outs[0].shape[0] * host_outs[0].shape[1])
        _prof.note_step(
            "train.scanned",
            {"host": t_host, "dispatch": t_dispatch, "device": t_device,
             "d2h": time.monotonic() - td},
            key=key, batches=len(bufs), samples=samples)
    losses = [] if guardian is not None else None
    for k, b in enumerate(bufs):
        labels = [NDArray(_np.asarray(
            b[n].asnumpy() if isinstance(b[n], NDArray) else b[n]),
            cpu(0)) for n in label_names]
        preds = [NDArray(h[k], cpu(0)) for h in host_outs]
        eval_metric.update(labels, preds)
        if losses is not None:
            losses.append(guardian.metric_step_loss())
        if batch_end_callback is not None:
            with _tel.span("fit.callbacks"):
                _multiple_callbacks(batch_end_callback, BatchEndParam(
                    epoch=epoch, nbatch=nbatch0 + k + nbatch_base,
                    eval_metric=eval_metric, locals=locals()))
    if guardian is not None:
        return guardian.drain_chunk(flags, losses)
    return "ok"


def _fed(data_iter):
    """``data_iter`` for the scanned loops: itself with telemetry off,
    else its batches each drawn under a ``fit.feed`` span (an idle chip
    while the iterator works is then named in a capture)."""
    if not _tel.ENABLED:
        return data_iter

    def spanned():
        batches = iter(data_iter)
        while True:
            with _tel.span("fit.feed"):
                batch = next(batches, None)
            if batch is None:
                return
            yield batch

    return spanned()


def _train_scanned(trainer, symbol, ctx0, param_names, aux_names, arg_params,
                   aux_params, begin_epoch, end_epoch, epoch_size, optimizer,
                   train_data, eval_data, eval_metric, epoch_end_callback,
                   batch_end_callback, logger, eval_batch_end_callback, K,
                   guardian=None):
    """K-step-scanned single-device training loop: same observable
    semantics as _train_multi_device's per-batch loop (metrics, per-batch
    callbacks, epoch checkpointing), but the step itself is a compiled
    K-step lax.scan through parallel/fit_trainer.py — one dispatch per K
    batches, so the per-dispatch host cost and the metric fence amortize.
    Per-batch callbacks fire after their chunk completes (they lag the
    device by up to K batches, exactly like the reference's async engine
    lag between push and metric sync; ref model.py:244)."""
    input_names = trainer.input_names

    eval_exe = None

    def _flush(buf, epoch, nbatch0):
        return _scan_flush(trainer, buf, epoch, nbatch0, guardian=guardian)

    def _drain(pending, eval_metric):
        action = _scan_drain(pending, eval_metric, label_names,
                             batch_end_callback, nbatch_base=1,
                             guardian=guardian)
        if guardian is not None and action == "rollback":
            guardian.rollback(trainer.restore_state,
                              disk_restore_fn=trainer.load_params,
                              data_iter=train_data)

    label_names = [_desc_name(d) for d in train_data.provide_label]

    def _scanned_one_epoch(epoch):
        tic = time.time()
        eval_metric.reset()
        nbatch = 0
        pending = None
        buf = []
        while True:
            do_reset = True
            for data_batch in _fed(train_data):
                buf.append(_buffer_batch(data_batch, input_names))
                nbatch += 1
                if len(buf) == K:
                    new_pending = _flush(buf, epoch, nbatch - K)
                    _drain(pending, eval_metric)
                    pending = new_pending
                    buf = []
                if epoch_size is not None and nbatch >= epoch_size:
                    do_reset = False
                    break
            if do_reset:
                logger.info("Epoch[%d] Resetting Data Iterator", epoch)
                train_data.reset()
            if epoch_size is None or nbatch >= epoch_size:
                break
        if buf:  # epoch tail: smaller scan, compiled once per tail size
            new_pending = _flush(buf, epoch, nbatch - len(buf))
            _drain(pending, eval_metric)
            pending = new_pending
            buf = []
        _drain(pending, eval_metric)
        if guardian is not None:
            guardian.end_epoch()  # no chunk in flight across the boundary
        toc = time.time()
        logger.info("Epoch[%d] Time cost=%.3f", epoch, toc - tic)

    train_data.reset()
    for epoch in range(begin_epoch, end_epoch):
        with _tel.span("epoch"):
            _scanned_one_epoch(epoch)

        trainer.write_back(arg_params, aux_params, aux_names)
        _multiple_callbacks(epoch_end_callback, epoch, symbol, arg_params,
                            aux_params)

        if eval_data:
            if eval_exe is None:
                eval_shapes = {
                    _desc_name(d): _desc_shape(d)
                    for d in list(eval_data.provide_data)
                    + list(eval_data.provide_label)
                }
                eval_exe = symbol.simple_bind(ctx0, grad_req="null",
                                              **eval_shapes)
            eval_exe.copy_params_from(arg_params, aux_params)
            eval_metric.reset()
            eval_data.reset()
            eval_label_names = [_desc_name(d)
                                for d in eval_data.provide_label]
            eval_data_names = [_desc_name(d)
                               for d in eval_data.provide_data]
            for i, eval_batch in enumerate(eval_data):
                for n, a in zip(eval_data_names, eval_batch.data):
                    a.copyto(eval_exe.arg_dict[n])
                # labels too: loss-style heads (MakeLoss/criterions) read
                # them; leaving bind-time zeros would silently score the
                # loss against zeros
                for n, a in zip(eval_label_names, eval_batch.label):
                    if n in eval_exe.arg_dict:
                        a.copyto(eval_exe.arg_dict[n])
                eval_exe.forward(is_train=False)
                eval_metric.update(eval_batch.label, eval_exe.outputs)
                if eval_batch_end_callback is not None:
                    _multiple_callbacks(eval_batch_end_callback, BatchEndParam(
                        epoch=epoch, nbatch=i, eval_metric=eval_metric,
                        locals=locals()))
            for name, value in eval_metric.get_name_value():
                logger.info("Epoch[%d] Validation-%s=%f", epoch, name, value)
            eval_data.reset()

    from . import engine as _engine

    if _engine.Engine._instance is not None:
        _engine.Engine._instance.wait_for_all()


def _train_multi_device(symbol, ctx, arg_names, param_names, aux_names, arg_params,
                        aux_params, begin_epoch, end_epoch, epoch_size, optimizer,
                        kvstore, update_on_kvstore, train_data, eval_data=None,
                        eval_metric=None, epoch_end_callback=None,
                        batch_end_callback=None, logger=None, work_load_list=None,
                        monitor=None, eval_batch_end_callback=None,
                        sym_gen=None, compute_dtype=None):
    """Core DP training loop (ref: python/mxnet/model.py:117-310)."""
    if logger is None:
        logger = logging
    # training-run guardian (MXNET_GUARDIAN=1; docs/how_to/guardrails.md):
    # None when off — every hook below reduces to a None check
    guard = _guardian.TrainingGuardian.create(
        kvstore=kvstore, epoch_end_callback=epoch_end_callback, logger=logger)
    if guard is not None and eval_metric is not None:
        guard.attach_metric(eval_metric)  # loss-like metrics only
    if guard is not None:
        # exact-resume bridge: a data-service iterator marks its
        # frontier at every guardian snapshot, so rollback replays the
        # exact records instead of MXNET_GUARDIAN_FF_BATCHES skipping
        guard.attach_data_iter(train_data)
    K = _scan_k()
    _scan_attempted = False
    if (K > 1 and len(ctx) == 1 and kvstore is None and not update_on_kvstore
            and monitor is None and sym_gen is None
            and work_load_list is None):
        from .parallel.fit_trainer import make_fit_trainer, supports_optimizer

        if supports_optimizer(optimizer):
            input_shapes = {
                _desc_name(d): _desc_shape(d)
                for d in (list(train_data.provide_data)
                          + list(train_data.provide_label))
            }
            # only CONSTRUCTION falls back (host ops / non-loss heads);
            # once training starts, errors must surface — a silent
            # restart on the per-batch path would retrain from epoch 0
            # with already-mutated params and a shifted lr schedule
            trainer = None
            try:
                trainer = make_fit_trainer(
                    symbol, ctx[0], input_shapes, optimizer, arg_params,
                    aux_params, param_names, compute_dtype=compute_dtype)
            except MXNetError as e:
                logger.debug("scanned fit unavailable (%s); using the "
                             "per-batch loop", e)
            except Exception as e:  # device_put/tracing/optimizer-state
                # failures during CONSTRUCTION must not abort fit() — the
                # per-batch loop may still train fine
                logger.warning("scanned fit construction failed (%s: %s); "
                               "using the per-batch loop",
                               type(e).__name__, e)
            if trainer is not None:
                return _train_scanned(
                    trainer, symbol, ctx[0], param_names, aux_names,
                    arg_params, aux_params, begin_epoch, end_epoch,
                    epoch_size, optimizer, train_data, eval_data,
                    eval_metric, epoch_end_callback, batch_end_callback,
                    logger, eval_batch_end_callback, K, guardian=guard)
            _scan_attempted = True
    if compute_dtype is not None:
        # mixed precision rides the scanned trainer; the per-batch loop
        # trains in the arrays' dtype (f32) — a silent precision change
        # must not look like it took effect
        logger.warning(
            "compute_dtype=%s requested but the scanned fit fast path is "
            "unavailable (%s); training proceeds in the parameter dtype",
            compute_dtype,
            "construction failed" if _scan_attempted else "eligibility")
    executor_manager = DataParallelExecutorManager(
        symbol=symbol, sym_gen=sym_gen, ctx=ctx, train_data=train_data,
        param_names=param_names, arg_names=arg_names, aux_names=aux_names,
        work_load_list=work_load_list, logger=logger,
    )
    if monitor:
        executor_manager.install_monitor(monitor)
    executor_manager.set_params(arg_params, aux_params)

    if not update_on_kvstore:
        updater = opt.get_updater(optimizer)
    if kvstore:
        _initialize_kvstore(
            kvstore=kvstore, param_arrays=executor_manager.param_arrays,
            arg_params=arg_params, param_names=executor_manager.param_names,
            update_on_kvstore=update_on_kvstore,
        )
    if update_on_kvstore:
        kvstore.set_optimizer(optimizer)

    # the updater whose device sentinel the guardian reads per step:
    # the local closure, or the one kvstore.set_optimizer installed
    guard_updater = None
    if guard is not None:
        guard_updater = getattr(kvstore, "_updater", None) \
            if update_on_kvstore else updater

    def _guard_snapshot():
        executor_manager.copy_to(arg_params, aux_params)
        return ({k: v.asnumpy().copy() for k, v in arg_params.items()},
                {k: v.asnumpy().copy() for k, v in aux_params.items()},
                _guardian.snapshot_updater_states(guard_updater))

    def _guard_restore(payload):
        args, auxs, opt_states = payload
        for k, v in args.items():
            arg_params[k][:] = v
        for k, v in auxs.items():
            aux_params[k][:] = v
        executor_manager.set_params(arg_params, aux_params)
        _guardian.restore_updater_states(guard_updater, opt_states)

    def _guard_disk_restore(args, auxs):
        for k, v in args.items():
            if k in arg_params:
                arg_params[k][:] = v.asnumpy()
        for k, v in auxs.items():
            if k in aux_params:
                aux_params[k][:] = v.asnumpy()
        executor_manager.set_params(arg_params, aux_params)
        # no optimizer state in a .params checkpoint: drop the momenta
        _guardian.zero_updater_states(guard_updater)

    def _train_one_batch(data_batch, epoch, nbatch, eval_metric):
        """One optimizer step (mxtel: wrapped in a "batch" span nested
        under the epoch span; step walltime and samples/sec feed the
        train.* metrics)."""
        import jax

        with _tel.span("batch"):
            step_tic = time.monotonic() if _tel.ENABLED else 0.0
            # mxprof (MXNET_PROF=1): fenced sub-phase stamps — host
            # input prep, fwd/bwd dispatch, optimizer update, metric
            # D2H — emitted as one step_breakdown record per batch
            prof_t = {"update": 0.0, "d2h": 0.0} if _prof.ENABLED else None
            n_attr = _prof.attribution_count() if prof_t is not None else 0

            def _timed(fn, slot):
                if prof_t is None:
                    return fn()
                t = time.monotonic()
                try:
                    return fn()
                finally:
                    prof_t[slot] += time.monotonic() - t

            t0 = time.monotonic() if prof_t is not None else 0.0
            executor_manager.load_data_batch(data_batch)
            if monitor is not None:
                monitor.tic()
            t1 = time.monotonic() if prof_t is not None else 0.0
            executor_manager.forward(is_train=True)
            executor_manager.backward()
            if prof_t is not None:
                t2 = time.monotonic()
                prof_t["host"] = t1 - t0
                prof_t["dispatch"] = t2 - t1
                # device phase: forward/backward are ASYNC dispatches on
                # accelerator backends — without a fence here the device
                # seconds would land in d2h/update and a compute-bound
                # run would misread as host-bound. Blocking on the
                # gradient leaves (the last values the step produces) is
                # the cost of the fenced decomposition, paid only under
                # MXNET_PROF=1.
                for glist in executor_manager.grad_arrays:
                    for g in (glist or []):
                        if g is None:
                            continue
                        bur = getattr(g._data, "block_until_ready", None)
                        if bur is not None:
                            bur()
                prof_t["device"] = time.monotonic() - t2

            # the update's and the metric's eager operations under the
            # scopes the scanned loop gives them (mx.profiler.scope_map)
            def _do_update():
                with jax.named_scope("optimizer"):
                    if update_on_kvstore:
                        _update_params_on_kvstore(
                            executor_manager.param_arrays,
                            executor_manager.grad_arrays, kvstore)
                    else:
                        _update_params(
                            executor_manager.param_arrays,
                            executor_manager.grad_arrays,
                            updater=updater, num_device=len(ctx),
                            kvstore=kvstore)

            def _do_metric():
                with jax.named_scope("metric"):
                    executor_manager.update_metric(
                        eval_metric, data_batch.label)

            if guard is None:
                _timed(_do_update, "update")
                if monitor is not None:
                    monitor.toc_print()
                _timed(_do_metric, "d2h")
            else:
                # metric BEFORE the guarded update: outputs don't
                # depend on it, and the guardian's loss feed reads this
                # batch's metric delta for the z-score channel
                _timed(_do_metric, "d2h")
                action = _timed(lambda: guard.guard_batch(
                    _do_update,
                    grad_arrays_fn=lambda: [
                        g[0] for g in executor_manager.grad_arrays
                        if g and g[0] is not None],
                    updater=guard_updater), "update")
                if action == "rollback":
                    guard.rollback(_guard_restore,
                                   disk_restore_fn=_guard_disk_restore,
                                   data_iter=train_data)
                else:
                    guard.maybe_snapshot(_guard_snapshot)
                if monitor is not None:
                    monitor.toc_print()
            if prof_t is not None and _prof.attribution_count() == n_attr:
                # a batch whose dispatch performed the attribution
                # compile is not recorded (see _scan_flush)
                _prof.note_step("train.batch", prof_t, batches=1,
                                samples=train_data.batch_size)
            if _tel.ENABLED:
                dt = time.monotonic() - step_tic
                _tel.histogram("train.step_secs").observe(dt)
                if dt > 0:
                    _tel.gauge("train.samples_per_sec").set(
                        train_data.batch_size / dt)
            if batch_end_callback is not None:
                # locals() here is the helper's scope; merge the outer
                # training-loop objects callbacks historically read via
                # param.locals (executor_manager and friends are closure
                # free vars, so they already appear)
                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                    locals=dict(locals(), symbol=symbol,
                                arg_params=arg_params,
                                aux_params=aux_params))
                _multiple_callbacks(batch_end_callback, batch_end_params)

    def _train_one_epoch(epoch):
        tic = time.time()
        eval_metric.reset()
        nbatch = 0
        while True:
            do_reset = True
            for data_batch in train_data:
                nbatch += 1
                _train_one_batch(data_batch, epoch, nbatch, eval_metric)
                if epoch_size is not None and nbatch >= epoch_size:
                    do_reset = False
                    break
            if do_reset:
                logger.info("Epoch[%d] Resetting Data Iterator", epoch)
                train_data.reset()
            if epoch_size is None or nbatch >= epoch_size:
                break
        toc = time.time()
        logger.info("Epoch[%d] Time cost=%.3f", epoch, toc - tic)

        if epoch_end_callback or epoch + 1 == end_epoch:
            executor_manager.copy_to(arg_params, aux_params)
        _multiple_callbacks(epoch_end_callback, epoch, symbol, arg_params, aux_params)

        if eval_data:
            eval_metric.reset()
            eval_data.reset()
            for i, eval_batch in enumerate(eval_data):
                executor_manager.load_data_batch(eval_batch)
                executor_manager.forward(is_train=False)
                executor_manager.update_metric(eval_metric, eval_batch.label)
                if eval_batch_end_callback is not None:
                    batch_end_params = BatchEndParam(
                        epoch=epoch, nbatch=i, eval_metric=eval_metric, locals=locals()
                    )
                    _multiple_callbacks(eval_batch_end_callback, batch_end_params)
            name_value = eval_metric.get_name_value()
            for name, value in name_value:
                logger.info("Epoch[%d] Validation-%s=%f", epoch, name, value)
            eval_data.reset()

    train_data.reset()
    for epoch in range(begin_epoch, end_epoch):
        with _tel.span("epoch"):
            _train_one_epoch(epoch)

    # fence host tasks (async epoch checkpoints): a failed write must
    # surface here, at the training call site, not be swallowed
    from . import engine as _engine

    if _engine.Engine._instance is not None:
        _engine.Engine._instance.wait_for_all()


def _multiple_callbacks(callbacks, *args, **kwargs):
    if isinstance(callbacks, list):
        for cb in callbacks:
            cb(*args, **kwargs)
        return
    if callbacks:
        callbacks(*args, **kwargs)


_ckpt_vars = {}  # prefix -> engine write-var serializing its checkpoints
_ckpt_vars_lock = threading.Lock()  # guards check-then-insert on _ckpt_vars


def fence_checkpoint(prefix):
    """Block until all queued async checkpoint writes of `prefix` have
    landed (no-op when none are pending or the engine is non-native)."""
    with _ckpt_vars_lock:
        var = _ckpt_vars.get(prefix)
    if var is not None:
        from . import engine as _engine

        _engine.Engine.get().wait_for_var(var)


def _write_params_atomic(param_name, save_dict):
    """Crash-safe params write: tmp file → fsync → atomic rename →
    best-effort directory fsync. At every instant `param_name` is either
    absent, the previous complete file, or the new complete file — a
    crash (or an injected ``ckpt.write`` fault) can strand a ``.tmp-*``
    leftover but can never tear the ``.params`` file in place. Stream
    URIs (s3:// etc.) have no rename; they keep the plain write."""
    if "://" in param_name:
        nd_save(param_name, save_dict)
        return
    tmp = "%s.tmp-%d" % (param_name, os.getpid())
    nd_save(tmp, save_dict)
    # the injected crash window: tmp written, final name untouched —
    # recovery must see the previous epoch, never a torn file
    _faults.point("ckpt.write")
    with open(tmp, "rb") as f:
        os.fsync(f.fileno())
    os.replace(tmp, param_name)
    dirfd = None
    try:  # durability of the rename itself
        dirfd = os.open(os.path.dirname(os.path.abspath(param_name)),
                        os.O_RDONLY)
        os.fsync(dirfd)
    except OSError:
        pass
    finally:
        if dirfd is not None:
            os.close(dirfd)


_CKPT_RE = re.compile(r"-(\d{4,})\.params")


def _checkpoint_epochs(prefix):
    """Epochs with an existing `prefix-NNNN.params`, newest first.
    The suffix is FULL-matched so a sibling run's longer prefix
    ('model-ft-0006.params' while scanning 'model') can neither inject
    phantom epochs nor get its files pruned by this run."""
    d = os.path.dirname(os.path.abspath(prefix)) or "."
    base = os.path.basename(prefix)
    epochs = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for fn in names:
        if not fn.startswith(base + "-"):
            continue
        m = _CKPT_RE.fullmatch(fn[len(base):])
        if m is not None:
            epochs.append(int(m.group(1)))
    return sorted(set(epochs), reverse=True)


def _prune_checkpoints(prefix, keep_n):
    """Rolling retention: keep the newest `keep_n` epochs of `prefix`,
    delete the rest — including stranded tmp siblings from crashed
    writes and the epoch's optimizer `.states` sidecar (an orphaned
    states file has no matching params to resume with). Best-effort —
    retention must never fail a training step."""
    import glob as _glob

    for epoch in _checkpoint_epochs(prefix)[keep_n:]:
        path = "%s-%04d.params" % (prefix, epoch)
        try:
            os.remove(path)
            logging.info('Pruned old checkpoint "%s"', path)
        except OSError:
            pass
        stale = _glob.glob(_glob.escape(path) + ".tmp-*")
        stale.append("%s-%04d.states" % (prefix, epoch))
        for s in stale:
            try:
                os.remove(s)
            except OSError:
                pass


def _params_file_ok(path):
    """Structurally validate a .params file WITHOUT materializing its
    tensors: header, names, and every tensor record must land exactly
    on EOF. The resume scan runs this over possibly-multi-GB files; a
    full nd_load here would double resume I/O (the winner is loaded
    once, by load_checkpoint)."""
    import struct as _struct

    from .base import _DTYPE_MX_TO_NP
    from .ndarray import _ND_MAGIC

    try:
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            head = f.read(24)
            if len(head) < 24:
                return False
            magic, _res, count = _struct.unpack("<QQQ", head)
            if magic != _ND_MAGIC:
                return False
            raw = f.read(8)
            if len(raw) < 8:
                return False
            (n_names,) = _struct.unpack("<Q", raw)
            for _ in range(n_names):
                raw = f.read(8)
                if len(raw) < 8:
                    return False
                f.seek(_struct.unpack("<Q", raw)[0], 1)
            for _ in range(count):
                raw = f.read(4)
                if len(raw) < 4:
                    return False
                (ndim,) = _struct.unpack("<I", raw)
                dims_raw = f.read(4 * ndim)
                if len(dims_raw) < 4 * ndim:
                    return False
                dims = _struct.unpack("<%dI" % ndim, dims_raw) if ndim else ()
                raw = f.read(4)
                if len(raw) < 4:
                    return False
                (code,) = _struct.unpack("<I", raw)
                if code not in _DTYPE_MX_TO_NP:
                    return False
                n = 1
                for d in dims:
                    n *= d
                f.seek(n * _np.dtype(_DTYPE_MX_TO_NP[code]).itemsize, 1)
            # seeks past EOF don't error; the final position check is
            # what catches truncation (and trailing garbage)
            return f.tell() == size
    except (OSError, ValueError):
        return False


def find_latest_checkpoint(prefix):
    """Newest epoch whose ``prefix-NNNN.params`` loads cleanly, or None.

    Corrupt or partial files (a torn write from a pre-atomic-rename
    build, a truncated copy) are skipped with a warning and the scan
    falls back to the next older epoch — the resume path after a
    preemption must land on the newest VALID state, not die on the
    newest file."""
    fence_checkpoint(prefix)
    for epoch in _checkpoint_epochs(prefix):
        path = "%s-%04d.params" % (prefix, epoch)
        if not _params_file_ok(path):
            logging.warning(
                'Skipping corrupt/partial checkpoint "%s"', path)
            continue
        return epoch
    return None


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params,
                    sync=False, keep_n=None):
    """ref: python/mxnet/model.py:311.

    Async by default: the file write is pushed to the dependency engine
    with a per-prefix write variable (successive checkpoints of one
    prefix serialize; different prefixes overlap) so the training loop
    keeps stepping while the params hit disk — the TPU-era async
    checkpoint pattern, fenced by ``nd.waitall()``. ``sync=True`` (or a
    NaiveEngine / non-native build) writes inline.

    The params file lands via tmp + fsync + atomic rename (crash-safe;
    see docs/how_to/fault_tolerance.md). ``keep_n`` enables rolling
    retention: after a successful write, only the newest ``keep_n``
    epochs of this prefix are kept on disk."""
    if symbol is not None:
        symbol.save("%s-symbol.json" % prefix)
    # snapshot device buffers now: later mutations must not leak into
    # the checkpoint being written
    save_dict = {("arg:%s" % k): v.asnumpy() for k, v in arg_params.items()}
    save_dict.update(
        {("aux:%s" % k): v.asnumpy() for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)

    def _write():
        _write_params_atomic(param_name, save_dict)
        logging.info('Saved checkpoint to "%s"', param_name)
        if keep_n is not None and keep_n >= 1:
            _prune_checkpoints(prefix, int(keep_n))

    from . import engine as _engine

    eng = _engine.Engine.get()
    if sync or not eng.is_native:
        _write()
        return
    with _ckpt_vars_lock:
        var = _ckpt_vars.get(prefix)
        if var is None:
            var = _ckpt_vars[prefix] = eng.new_variable()
    eng.push(_write, mutable_vars=[var])


def load_checkpoint(prefix, epoch):
    """ref: python/mxnet/model.py:341. Fences any in-flight async
    checkpoint of this prefix before reading."""
    fence_checkpoint(prefix)
    symbol = sym_load("%s-symbol.json" % prefix)
    save_dict = nd_load("%s-%04d.params" % (prefix, epoch))
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        if tp == "aux":
            aux_params[name] = v
    return (symbol, arg_params, aux_params)


class FeedForward(BASE_ESTIMATOR):
    """Estimator API (ref: python/mxnet/model.py:378)."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=Uniform(0.01), numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, compute_dtype=None, **kwargs):
        if isinstance(symbol, Symbol):
            self.symbol = symbol
            self.sym_gen = None
        else:
            assert callable(symbol)
            self.symbol = None
            self.sym_gen = symbol
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.argument_checked = False
        if self.sym_gen is None:
            self._check_arguments()
        if ctx is None:
            ctx = [current_context()]
        elif isinstance(ctx, Context):
            ctx = [ctx]
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.kwargs = kwargs.copy()
        self.optimizer = optimizer
        self.initializer = initializer
        self.numpy_batch_size = numpy_batch_size
        self._pred_exec = None
        self.begin_epoch = begin_epoch
        # TPU extension: mixed-precision training through the scanned fit
        # path (f32 master weights, `compute_dtype` activations/matmuls).
        # None = f32, or set MXNET_COMPUTE_DTYPE=bfloat16 process-wide.
        import os

        self.compute_dtype = (
            compute_dtype if compute_dtype is not None
            else os.environ.get("MXNET_COMPUTE_DTYPE") or None)

    def _check_arguments(self):
        if self.argument_checked:
            return
        assert self.symbol is not None
        self.argument_checked = True
        _check_arguments(self.symbol)
        if self.allow_extra_params:
            if self.arg_params:
                arg_names = set(self.symbol.list_arguments())
                self.arg_params = {
                    k: v for k, v in self.arg_params.items() if k in arg_names
                }
            if self.aux_params:
                aux_names = set(self.symbol.list_auxiliary_states())
                self.aux_params = {
                    k: v for k, v in self.aux_params.items() if k in aux_names
                }

    @staticmethod
    def _is_data_arg(name):
        return name.endswith("data") or name.endswith("label")

    def _init_params(self, inputs, overwrite=False):
        """ref: model.py:470."""
        inputs = [
            x if isinstance(x, io.DataDesc) else io.DataDesc(*x) for x in inputs
        ]
        input_shapes = {item.name: item.shape for item in inputs}
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        assert arg_shapes is not None
        arg_names = self.symbol.list_arguments()
        input_names = input_shapes.keys()
        param_names = [key for key in arg_names if key not in input_names]
        aux_names = self.symbol.list_auxiliary_states()

        param_name_attrs = [
            x for x in zip(arg_names, arg_shapes) if x[0] in param_names
        ]
        arg_params = {k: zeros(s) for k, s in param_name_attrs}
        aux_name_attrs = list(zip(aux_names, aux_shapes))
        aux_params = {k: zeros(s) for k, s in aux_name_attrs}

        for k, v in arg_params.items():
            if self.arg_params and k in self.arg_params and (not overwrite):
                arg_params[k][:] = self.arg_params[k][:]
            else:
                self.initializer(k, v)
        for k, v in aux_params.items():
            if self.aux_params and k in self.aux_params and (not overwrite):
                aux_params[k][:] = self.aux_params[k][:]
            else:
                self.initializer(k, v)

        self.arg_params = arg_params
        self.aux_params = aux_params
        return (arg_names, list(param_names), aux_names)

    def __getstate__(self):
        this = self.__dict__.copy()
        this["_pred_exec"] = None
        return this

    def __setstate__(self, state):
        self.__dict__.update(state)

    def _init_predictor(self, input_shapes, type_dict=None):
        """ref: model.py:522."""
        if self._pred_exec is not None:
            arg_shapes, _, _ = self.symbol.infer_shape(**dict(input_shapes))
            assert arg_shapes is not None, "Incomplete input shapes"
            pred_shapes = [x.shape for x in self._pred_exec.arg_arrays]
            if arg_shapes == pred_shapes:
                return
        pred_exec = self.symbol.simple_bind(
            self.ctx[0], grad_req="null", type_dict=type_dict, **dict(input_shapes)
        )
        pred_exec.copy_params_from(self.arg_params, self.aux_params)
        _check_arguments(self.symbol)
        self._pred_exec = pred_exec

    def _init_iter(self, X, y, is_train):
        """ref: model.py:544."""
        if isinstance(X, (_np.ndarray, NDArray)):
            if y is None:
                if is_train:
                    raise ValueError("y must be specified when X is numpy.ndarray")
                y = _np.zeros(X.shape[0])
            if not isinstance(y, (_np.ndarray, NDArray)):
                raise TypeError("y must be ndarray when X is numpy.ndarray")
            X = X.asnumpy() if isinstance(X, NDArray) else X
            y = y.asnumpy() if isinstance(y, NDArray) else y
            if X.shape[0] != y.shape[0]:
                raise ValueError("The numbers of data points and labels not equal")
            if y.ndim == 2 and y.shape[1] == 1:
                y = y.flatten()
            if y.ndim != 1:
                raise ValueError("Label must be 1D or 2D (with 2nd dimension being 1)")
            if is_train:
                return io.NDArrayIter(
                    X, y, int(min(X.shape[0] // 2, self.numpy_batch_size)),
                    shuffle=is_train, last_batch_handle="roll_over",
                )
            return io.NDArrayIter(
                X, y, int(min(X.shape[0], self.numpy_batch_size)), shuffle=False
            )
        if not isinstance(X, io.DataIter):
            raise TypeError("X must be DataIter, NDArray or numpy.ndarray")
        return X

    def _init_eval_iter(self, eval_data):
        """ref: model.py:577."""
        if eval_data is None:
            return eval_data
        if isinstance(eval_data, (tuple, list)) and len(eval_data) == 2:
            if eval_data[0] is not None:
                if eval_data[1] is None and isinstance(eval_data[0], io.DataIter):
                    return eval_data[0]
                input_data = (
                    _np.array(eval_data[0]) if isinstance(eval_data[0], list) else eval_data[0]
                )
                input_label = (
                    _np.array(eval_data[1]) if isinstance(eval_data[1], list) else eval_data[1]
                )
                return self._init_iter(input_data, input_label, is_train=True)
            raise ValueError("Eval data is NONE")
        if not isinstance(eval_data, io.DataIter):
            raise TypeError("Eval data must be DataIter, or NDArray/numpy.ndarray pair")
        return eval_data

    def predict(self, X, num_batch=None, return_data=False, reset=True):
        """ref: model.py:602."""
        X = self._init_iter(X, None, is_train=False)
        if reset:
            X.reset()
        data_shapes = X.provide_data
        data_names = [x[0] for x in data_shapes]
        type_dict = dict((key, value.dtype) for (key, value) in self.arg_params.items())
        for x in X.provide_data:
            if isinstance(x, io.DataDesc):
                type_dict[x.name] = x.dtype
            else:
                type_dict[x[0]] = _np.float32
        self._init_predictor(data_shapes, type_dict)
        batch_size = X.batch_size
        data_arrays = [self._pred_exec.arg_dict[name] for name in data_names]
        output_list = [[] for _ in range(len(self._pred_exec.outputs))]
        if return_data:
            data_list = [[] for _ in X.provide_data]
            label_list = [[] for _ in X.provide_label]
        i = 0
        for batch in X:
            _load_data(batch, data_arrays)
            self._pred_exec.forward(is_train=False)
            padded = batch.pad
            real_size = batch_size - padded
            for o_list, o_nd in zip(output_list, self._pred_exec.outputs):
                o_list.append(o_nd[0:real_size].asnumpy())
            if return_data:
                for j, x in enumerate(batch.data):
                    data_list[j].append(x[0:real_size].asnumpy())
                for j, x in enumerate(batch.label):
                    label_list[j].append(x[0:real_size].asnumpy())
            i += 1
            if num_batch is not None and i == num_batch:
                break
        outputs = [_np.concatenate(x) for x in output_list]
        if len(outputs) == 1:
            outputs = outputs[0]
        if return_data:
            data = [_np.concatenate(x) for x in data_list]
            label = [_np.concatenate(x) for x in label_list]
            if len(data) == 1:
                data = data[0]
            if len(label) == 1:
                label = label[0]
            return outputs, data, label
        return outputs

    def score(self, X, eval_metric="acc", num_batch=None, batch_end_callback=None,
              reset=True):
        """ref: model.py:677."""
        X = self._init_iter(X, None, is_train=False)
        if reset:
            X.reset()
        data_shapes = X.provide_data
        data_names = [x[0] for x in data_shapes]
        type_dict = dict((key, value.dtype) for (key, value) in self.arg_params.items())
        for x in X.provide_data:
            if isinstance(x, io.DataDesc):
                type_dict[x.name] = x.dtype
            else:
                type_dict[x[0]] = _np.float32
        self._init_predictor(data_shapes, type_dict)
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        data_arrays = [self._pred_exec.arg_dict[name] for name in data_names]
        for i, batch in enumerate(X):
            if num_batch is not None and i == num_batch:
                break
            _load_data(batch, data_arrays)
            self._pred_exec.forward(is_train=False)
            eval_metric.update(batch.label, self._pred_exec.outputs)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(
                    epoch=0, nbatch=i, eval_metric=eval_metric, locals=locals()
                )
                _multiple_callbacks(batch_end_callback, batch_end_params)
        return eval_metric.get()[1]

    def _resume_from_checkpoint(self, resume, epoch_end_callback, logger):
        """Preemption-safe restart: locate the newest VALID checkpoint
        and continue from it. ``resume`` is the checkpoint prefix, or
        True to discover the prefix from a ``do_checkpoint`` epoch-end
        callback (which stamps ``.prefix`` on its closure). A fresh run
        (no checkpoint yet) starts from scratch — resume is idempotent
        under kill/rerun loops."""
        prefix = resume if isinstance(resume, str) else None
        if prefix is None:
            cbs = epoch_end_callback if isinstance(epoch_end_callback, list) \
                else [epoch_end_callback]
            for cb in cbs:
                p = getattr(cb, "prefix", None)
                if isinstance(p, str):
                    prefix = p
                    break
        if prefix is None:
            raise MXNetError(
                "fit(resume=True) needs a checkpoint prefix: pass "
                "resume='<prefix>' or a callback.do_checkpoint(prefix) "
                "epoch_end_callback")
        epoch = find_latest_checkpoint(prefix)
        if epoch is None:
            logger.info("resume: no valid checkpoint under prefix %r; "
                        "starting fresh", prefix)
            return
        _sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.begin_epoch = epoch
        logger.info("resume: restarting from checkpoint %r epoch %d",
                    prefix, epoch)

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, monitor=None,
            eval_batch_end_callback=None, resume=False):
        """ref: python/mxnet/model.py:708. TPU extension: ``resume`` —
        True (or a checkpoint prefix string) reloads the newest valid
        checkpoint and continues from its epoch, skipping corrupt or
        partial files, so a preempted run restarts with one flag (see
        docs/how_to/fault_tolerance.md)."""
        if logger is None:
            logger = logging
        if resume:
            self._resume_from_checkpoint(resume, epoch_end_callback, logger)
        data = self._init_iter(X, y, is_train=True)
        eval_data = self._init_eval_iter(eval_data)

        if self.sym_gen:
            self.symbol = self.sym_gen(data.default_bucket_key)
            self._check_arguments()
        self.kwargs["sym"] = self.symbol

        arg_names, param_names, aux_names = self._init_params(
            data.provide_data + data.provide_label
        )
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        # create kvstore
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self.ctx), self.arg_params
        )
        param_idx2name = {}
        if update_on_kvstore:
            param_idx2name.update(enumerate(param_names))
        else:
            for i, n in enumerate(param_names):
                for k in range(len(self.ctx)):
                    param_idx2name[i * len(self.ctx) + k] = n
        self.kwargs["param_idx2name"] = param_idx2name

        # init optimizer
        if isinstance(self.optimizer, str):
            batch_size = data.batch_size
            if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
                batch_size *= kvstore.num_workers
            optimizer = opt.create(
                self.optimizer, rescale_grad=(1.0 / batch_size), **self.kwargs
            )
        elif isinstance(self.optimizer, opt.Optimizer):
            optimizer = self.optimizer

        _train_multi_device(
            self.symbol, self.ctx, arg_names, param_names, aux_names,
            self.arg_params, self.aux_params,
            begin_epoch=self.begin_epoch, end_epoch=self.num_epoch,
            epoch_size=self.epoch_size, optimizer=optimizer,
            train_data=data, eval_data=eval_data, eval_metric=eval_metric,
            epoch_end_callback=epoch_end_callback,
            batch_end_callback=batch_end_callback,
            kvstore=kvstore, update_on_kvstore=update_on_kvstore,
            logger=logger, work_load_list=work_load_list, monitor=monitor,
            eval_batch_end_callback=eval_batch_end_callback,
            sym_gen=self.sym_gen, compute_dtype=self.compute_dtype,
        )

    def save(self, prefix, epoch=None):
        """ref: model.py:809."""
        if epoch is None:
            epoch = self.num_epoch
        assert epoch is not None
        # explicit save → durable on return (async path is the epoch-end
        # do_checkpoint callback)
        save_checkpoint(prefix, epoch, self.symbol, self.arg_params,
                        self.aux_params, sync=True)

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        """ref: model.py:829."""
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(
            symbol, ctx=ctx, arg_params=arg_params, aux_params=aux_params,
            begin_epoch=epoch, **kwargs
        )

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None, epoch_size=None,
               optimizer="sgd", initializer=Uniform(0.01), eval_data=None,
               eval_metric="acc", epoch_end_callback=None, batch_end_callback=None,
               kvstore="local", logger=None, work_load_list=None,
               eval_batch_end_callback=None, **kwargs):
        """ref: model.py:862."""
        model = FeedForward(
            symbol, ctx=ctx, num_epoch=num_epoch, epoch_size=epoch_size,
            optimizer=optimizer, initializer=initializer, **kwargs
        )
        model.fit(
            X, y, eval_data=eval_data, eval_metric=eval_metric,
            epoch_end_callback=epoch_end_callback,
            batch_end_callback=batch_end_callback,
            kvstore=kvstore, logger=logger, work_load_list=work_load_list,
            eval_batch_end_callback=eval_batch_end_callback,
        )
        return model


def _load_data(batch, targets):
    for d_src, d_target in zip(batch.data, targets):
        d_src.copyto(d_target)
