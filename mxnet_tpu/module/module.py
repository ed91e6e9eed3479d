"""Module: symbol + contexts + optimizer state
(ref: python/mxnet/module/module.py:1-622 and executor_group.py:68-551).

Data parallelism follows SURVEY §2.7 row 1: batch sliced per context,
one executor per device, gradient reduce + weight update via KVStore or a
local updater. On a TPU mesh the preferred path is mxnet_tpu.parallel's
pjit trainer; Module keeps reference-API parity and works over plural
Contexts (e.g. 8 virtual CPU devices in tests).
"""
from __future__ import annotations

import logging

import numpy as _np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..initializer import Uniform
from ..ndarray import NDArray, zeros
from .. import optimizer as opt
from ..executor_manager import _split_input_slice, _check_arguments
from ..model import _create_kvstore, _initialize_kvstore, _update_params, \
    _update_params_on_kvstore
from .base_module import BaseModule


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None):
        super().__init__(logger=logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._execs = []
        self._data_shapes = None
        self._label_shapes = None
        self._slices = None

    @staticmethod
    def load(prefix, epoch=None, load_optimizer_states=False, **kwargs):
        """ref: module.py:86. TPU extension: ``epoch=None`` resumes from
        the newest VALID checkpoint of the prefix (corrupt/partial
        epochs skipped — see model.find_latest_checkpoint and
        docs/how_to/fault_tolerance.md)."""
        from ..model import find_latest_checkpoint, load_checkpoint

        if epoch is None:
            epoch = find_latest_checkpoint(prefix)
            if epoch is None:
                from ..base import MXNetError

                raise MXNetError(
                    "Module.load(%r, epoch=None): no valid checkpoint found"
                    % (prefix,))
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        keep_n=None):
        """ref: module.py:119. The params file lands crash-safely (tmp +
        fsync + atomic rename); ``keep_n`` keeps only the newest N
        epochs on disk (rolling retention)."""
        from ..model import save_checkpoint as _save_ckpt

        self._sync_params_from_devices()
        _save_ckpt(prefix, epoch, self.symbol, *self.get_params()[:1],
                   self.get_params()[1], sync=True, keep_n=keep_n)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)

    # -- properties ------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [
            (name, tuple(o.shape))
            for name, o in zip(self._output_names, self._execs[0].outputs)
        ]

    def get_params(self):
        """ref: module.py:175."""
        live = getattr(self, "_scan_live", None)
        if live is not None:
            # scanned fit in progress: the freshest weights live in the
            # trainer's device state, not the executor — sync so a
            # mid-epoch checkpoint callback never reads stale params
            trainer, ap, xp = live
            trainer.write_back(ap, xp, self._aux_names)
            return (ap, xp)
        assert self.binded or self._arg_params is not None
        if self.binded and self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    # -- bind ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """ref: module.py:235."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        if not for_training:
            assert not inputs_need_grad

        from ..io import DataDesc

        data_shapes = [
            x if isinstance(x, DataDesc) else DataDesc(*x) for x in data_shapes
        ]
        label_shapes = [
            x if isinstance(x, DataDesc) else DataDesc(*x) for x in (label_shapes or [])
        ]
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes

        batch_size = data_shapes[0].shape[0]
        self._slices = _split_input_slice(batch_size, self._work_load_list)

        self._grad_req = grad_req
        shared_execs = (
            shared_module._execs if shared_module is not None else [None] * len(self._context)
        )
        self._execs = []
        for i, ctx in enumerate(self._context):
            dev_batch = self._slices[i].stop - self._slices[i].start
            shapes = {}
            for d in data_shapes + label_shapes:
                shapes[d.name] = (dev_batch,) + tuple(d.shape[1:])
            reqs = {}
            for name in self._symbol.list_arguments():
                if name in self._param_names:
                    reqs[name] = grad_req if for_training else "null"
                elif inputs_need_grad and name in self._data_names:
                    reqs[name] = grad_req
                else:
                    reqs[name] = "null"
            exec_ = self._symbol.simple_bind(
                ctx, grad_req=reqs, shared_exec=shared_execs[i], **shapes
            )
            self._execs.append(exec_)

        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())

    def _reset_bind(self):
        self.binded = False
        self._execs = []

    # -- params ----------------------------------------------------------------
    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        """ref: module.py:155."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None:
            initializer = Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: zeros(self._execs[0].arg_dict[name].shape,
                            dtype=self._execs[0].arg_dict[name].dtype)
                for name in self._param_names
            }
        if self._aux_params is None:
            self._aux_params = {
                name: zeros(arr.shape, dtype=arr.dtype)
                for name, arr in zip(self._aux_names, self._execs[0].aux_arrays)
            }

        for name, arr in self._arg_params.items():
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name].asnumpy() if isinstance(arg_params[name], NDArray) else arg_params[name]
            elif not allow_missing or initializer is not None:
                if initializer is not None:
                    initializer(name, arr)
        for name, arr in self._aux_params.items():
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name].asnumpy() if isinstance(aux_params[name], NDArray) else aux_params[name]
            elif initializer is not None:
                initializer(name, arr)

        self.params_initialized = True
        self._params_dirty = False
        for exec_ in self._execs:
            exec_.copy_params_from(self._arg_params, self._aux_params)

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        """ref: module.py:422."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._data_shapes[0].shape[0]
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._param_names))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {i * len(self._context) + k: n for i, n in enumerate(self._param_names)}
                    )
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(
                optimizer, sym=self.symbol, param_idx2name=idx2name, **optimizer_params
            )
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._param_arrays(),
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _param_arrays(self):
        arg_names = self._symbol.list_arguments()
        idx = [arg_names.index(n) for n in self._param_names]
        return [[e.arg_arrays[i] for e in self._execs] for i in idx]

    def _grad_arrays(self):
        arg_names = self._symbol.list_arguments()
        idx = [arg_names.index(n) for n in self._param_names]
        return [[e.grad_arrays[i] for e in self._execs] for i in idx]

    # -- compute ---------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        """ref: module.py:459."""
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._load_batch(data_batch)
        for exec_ in self._execs:
            exec_.forward(is_train=is_train)

    def _load_batch(self, data_batch):
        for name_list, arrays in (
            (self._data_names, data_batch.data),
            (self._label_names, data_batch.label or []),
        ):
            for name, src in zip(name_list, arrays):
                for exec_, sl in zip(self._execs, self._slices):
                    src[sl].copyto(exec_.arg_dict[name])

    def backward(self, out_grads=None):
        """ref: module.py:468."""
        assert self.binded and self.params_initialized
        for exec_ in self._execs:
            exec_.backward(out_grads=out_grads)

    def update(self):
        """ref: module.py:480."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._param_arrays(), self._grad_arrays(), self._kvstore
            )
        else:
            _update_params(
                self._param_arrays(), self._grad_arrays(), updater=self._updater,
                num_device=len(self._context), kvstore=self._kvstore,
            )

    def get_outputs(self, merge_multi_context=True):
        """ref: module.py:500."""
        assert self.binded and self.params_initialized
        outputs = [exec_.outputs for exec_ in self._execs]
        if merge_multi_context:
            from ..ndarray import concatenate

            if len(outputs) == 1:
                return list(outputs[0])
            return [
                concatenate([outputs[d][i].as_in_context(cpu()) for d in range(len(outputs))])
                for i in range(len(outputs[0]))
            ]
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        """ref: module.py:518."""
        assert self.binded and self.params_initialized and self.inputs_need_grad
        arg_names = self._symbol.list_arguments()
        idx = [arg_names.index(n) for n in self._data_names]
        grads = [[e.grad_arrays[i] for i in idx] for e in self._execs]
        if merge_multi_context:
            from ..ndarray import concatenate

            if len(grads) == 1:
                return list(grads[0])
            return [
                concatenate([grads[d][i].as_in_context(cpu()) for d in range(len(grads))])
                for i in range(len(grads[0]))
            ]
        return grads

    def update_metric(self, eval_metric, labels):
        """ref: module.py:537."""
        for exec_, sl in zip(self._execs, self._slices):
            labels_slice = [label[sl] for label in labels]
            eval_metric.update(labels_slice, exec_.outputs)

    def _sync_params_from_devices(self):
        """Average per-device copies back into _arg_params
        (ref: module.py:546 _sync_params_from_devices)."""
        for name in self._param_names:
            blocks = [e.arg_dict[name] for e in self._execs]
            w = blocks[0].copy()
            for b in blocks[1:]:
                w += b.as_in_context(w.context)
            w /= len(blocks)
            w.copyto(self._arg_params[name])
        for name in self._aux_names:
            blocks = [e.aux_dict[name] for e in self._execs]
            w = blocks[0].copy()
            for b in blocks[1:]:
                w += b.as_in_context(w.context)
            w /= len(blocks)
            w.copyto(self._aux_params[name])
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """ref: module.py:569."""
        assert self.optimizer_initialized
        import pickle

        with open(fname, "wb") as fout:
            fout.write(pickle.dumps(self._optimizer))

    def load_optimizer_states(self, fname):
        """ref: module.py:581."""
        assert self.optimizer_initialized
        import pickle

        with open(fname, "rb") as f:
            self._optimizer = pickle.loads(f.read())
        self._updater = opt.get_updater(self._optimizer)

    def install_monitor(self, mon):
        """ref: module.py:594."""
        assert self.binded
        for exec_ in self._execs:
            mon.install(exec_)

    # -- scanned fast path (parallel/fit_trainer.py) ---------------------------
    def _try_scanned_fit(self, train_data, eval_data, eval_metric,
                         validation_metric, epoch_end_callback,
                         batch_end_callback, eval_end_callback,
                         eval_batch_end_callback, begin_epoch, num_epoch,
                         monitor, guardian=None):
        """Run fit() as K-step compiled scans when eligible (the same
        fast path FeedForward uses, model._train_scanned): single
        device, local updates (no kvstore), scannable optimizer, no
        monitor. Observable semantics preserved: per-batch metrics and
        callbacks (Module numbers batches from 0), per-epoch Train-*
        logging, epoch_end callbacks with synced params, eval via
        score(). Returns False to fall back."""
        import os as _os
        import time as _time

        from ..base import MXNetError
        from ..model import (_buffer_batch, _desc_name, _desc_shape, _fed,
                             _multiple_callbacks, _scan_drain, _scan_flush,
                             _scan_k)
        from ..parallel.fit_trainer import make_fit_trainer, supports_optimizer

        K = _scan_k()
        # the scanned trainer has grad_req='write' semantics for every
        # param — a module bound with 'add'/'null' (frozen or accumulated
        # params) must keep the per-batch loop
        if (K <= 1 or len(self._context) != 1 or monitor is not None
                or self._kvstore is not None or self._update_on_kvstore
                or not train_data.provide_label
                or getattr(self, "_grad_req", "write") != "write"
                or not supports_optimizer(self._optimizer)):
            return False
        input_shapes = {
            _desc_name(d): _desc_shape(d)
            for d in (list(train_data.provide_data)
                      + list(train_data.provide_label))
        }
        arg_params, aux_params = self.get_params()
        try:
            trainer = make_fit_trainer(
                self._symbol, self._context[0], input_shapes,
                self._optimizer, arg_params, aux_params, self._param_names,
                compute_dtype=_os.environ.get("MXNET_COMPUTE_DTYPE") or None)
        except MXNetError as e:
            self.logger.debug("scanned fit unavailable (%s); per-batch "
                              "loop", e)
            return False
        except Exception as e:  # construction-only failures fall back
            self.logger.warning("scanned fit construction failed (%s: %s); "
                                "per-batch loop", type(e).__name__, e)
            return False
        input_names = trainer.input_names
        label_names = [_desc_name(d) for d in train_data.provide_label]

        def _drain(pending):
            action = _scan_drain(pending, eval_metric, label_names,
                                 batch_end_callback, nbatch_base=0,
                                 guardian=guardian)
            if guardian is not None and action == "rollback":
                guardian.rollback(trainer.restore_state,
                                  disk_restore_fn=trainer.load_params,
                                  data_iter=train_data)

        # while the scanned loop is live, get_params() syncs from the
        # trainer (a batch_end_callback that checkpoints mid-epoch must
        # not read epoch-start weights)
        self._scan_live = (trainer, arg_params, aux_params)
        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = _time.time()
                eval_metric.reset()
                pending = None
                buf = []
                nbatch = 0
                for data_batch in _fed(train_data):
                    buf.append(_buffer_batch(data_batch, input_names))
                    nbatch += 1
                    if len(buf) == K:
                        new_pending = _scan_flush(trainer, buf, epoch,
                                                  nbatch - K,
                                                  guardian=guardian)
                        _drain(pending)
                        pending = new_pending
                        buf = []
                if buf:
                    new_pending = _scan_flush(trainer, buf, epoch,
                                              nbatch - len(buf),
                                              guardian=guardian)
                    _drain(pending)
                    pending = new_pending
                    buf = []
                _drain(pending)
                if guardian is not None:
                    # no chunk in flight across the epoch boundary
                    guardian.end_epoch()
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                     val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                 _time.time() - tic)
                trainer.write_back(arg_params, aux_params, self._aux_names)
                self.set_params(arg_params, aux_params)
                if epoch_end_callback is not None:
                    _multiple_callbacks(epoch_end_callback, epoch,
                                        self.symbol, arg_params, aux_params)
                if eval_data:
                    res = self.score(eval_data, validation_metric,
                                     score_end_callback=eval_end_callback,
                                     batch_end_callback=eval_batch_end_callback,
                                     epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
        finally:
            self._scan_live = None
        return True
