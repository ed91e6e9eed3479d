"""Scanned fit() fast path (parallel/fit_trainer.py): must preserve the
per-batch loop's semantics — same convergence, same metric/callback
counts, real Optimizer state advancement — while running K steps per
dispatch."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _fit(scan, optimizer="sgd", opt_kwargs=None, seed=7, num_epoch=2,
         lr_scheduler=None, batch_cb=None):
    os.environ["MXNET_SCAN_TRAIN"] = "1" if scan else "0"
    try:
        np.random.seed(seed)
        mx.random.seed(seed)  # initializers draw from the mx.random chain
        train = mx.io.MNISTIter(batch_size=32, num_synthetic=512, seed=1)
        val = mx.io.MNISTIter(batch_size=32, num_synthetic=256, seed=2,
                              shuffle=False)
        kw = dict(opt_kwargs or {})
        if lr_scheduler is not None:
            kw["lr_scheduler"] = lr_scheduler
        model = mx.FeedForward(
            mx.models.get_mlp(), ctx=mx.cpu(0), num_epoch=num_epoch,
            optimizer=optimizer, initializer=mx.initializer.Xavier(), **kw)
        model.fit(X=train, eval_data=val, batch_end_callback=batch_cb)
        return model
    finally:
        os.environ.pop("MXNET_SCAN_TRAIN", None)


def test_scanned_matches_perbatch_sgd():
    m1 = _fit(scan=True, opt_kwargs={"learning_rate": 0.1, "momentum": 0.9})
    m2 = _fit(scan=False, opt_kwargs={"learning_rate": 0.1, "momentum": 0.9})
    a1 = m1.score(mx.io.MNISTIter(batch_size=32, num_synthetic=256, seed=2,
                                  shuffle=False))
    a2 = m2.score(mx.io.MNISTIter(batch_size=32, num_synthetic=256, seed=2,
                                  shuffle=False))
    assert a1 > 0.9 and a2 > 0.9
    # same seeds, same arithmetic -> near-identical weights (fp drift only)
    for k in m1.arg_params:
        np.testing.assert_allclose(
            m1.arg_params[k].asnumpy(), m2.arg_params[k].asnumpy(),
            rtol=2e-2, atol=2e-3, err_msg=k)


def test_scanned_adam_with_scheduler_converges():
    sched = mx.lr_scheduler.FactorScheduler(step=10, factor=0.9)
    m = _fit(scan=True, optimizer="adam",
             opt_kwargs={"learning_rate": 0.002}, lr_scheduler=sched)
    acc = m.score(mx.io.MNISTIter(batch_size=32, num_synthetic=256, seed=2,
                                  shuffle=False))
    assert acc > 0.9


def test_scanned_callback_counts_and_tail_chunks():
    """Per-batch callbacks must fire once per batch even when the epoch
    length is not a multiple of K (tail chunk takes a smaller scan)."""
    os.environ["MXNET_TRAIN_SCAN_K"] = "5"  # 512/32 = 16 batches: 5,5,5,1
    seen = []
    try:
        _fit(scan=True, opt_kwargs={"learning_rate": 0.1},
             num_epoch=1, batch_cb=lambda p: seen.append(p.nbatch))
    finally:
        os.environ.pop("MXNET_TRAIN_SCAN_K", None)
    assert seen == list(range(1, 17))


def test_scanned_optimizer_counts_advance():
    """lr schedulers key off num_update; the host-side counts must
    advance by exactly the number of applied batches."""
    os.environ["MXNET_SCAN_TRAIN"] = "1"
    try:
        np.random.seed(0)
        train = mx.io.MNISTIter(batch_size=32, num_synthetic=320, seed=1)
        opt = mx.optimizer.create("sgd", learning_rate=0.05,
                                  rescale_grad=1.0 / 32)
        model = mx.FeedForward(mx.models.get_mlp(), ctx=mx.cpu(0),
                               num_epoch=2, optimizer=opt,
                               initializer=mx.initializer.Xavier())
        model.fit(X=train)
        assert opt.num_update == 2 * (320 // 32)
    finally:
        os.environ.pop("MXNET_SCAN_TRAIN", None)


def test_resident_on_probe():
    """stage_chunk's device-residency probe must use jax.Array.devices()
    (stable API), not .device (property vs method across jax versions);
    numpy reports False (advisor r3)."""
    import jax

    from mxnet_tpu.parallel.fit_trainer import _resident_on

    dev = jax.devices("cpu")[0]
    arr = jax.device_put(np.ones((4,), np.float32), dev)
    assert _resident_on(arr, dev)
    assert not _resident_on(np.ones((4,), np.float32), dev)
    assert not _resident_on(arr, jax.devices("cpu")[1])


def test_stage_chunk_on_device_branch(monkeypatch):
    """Device-resident inputs must stack ON device — no device_put host
    round trip (the cost the fast path exists to avoid)."""
    import jax

    from mxnet_tpu.parallel import fit_trainer
    from mxnet_tpu.parallel.fit_trainer import make_fit_trainer

    np.random.seed(0)
    mx.random.seed(0)
    shapes = {"data": (8, 784), "softmax_label": (8,)}
    sym = mx.models.get_mlp()
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = mx.initializer.Xavier()
    arg_params = {}
    for name, s in zip(sym.list_arguments(), arg_shapes):
        if name in shapes:
            continue
        arr = mx.nd.zeros(s, mx.cpu(0))
        init(name, arr)
        arg_params[name] = arr
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    trainer = make_fit_trainer(sym, mx.cpu(0), shapes, opt, arg_params, {},
                               list(arg_params))
    dev = mx.cpu(0).jax_device
    batches = [
        {"data": jax.device_put(
             np.random.rand(8, 784).astype(np.float32), dev),
         "softmax_label": jax.device_put(
             np.random.randint(0, 10, (8,)).astype(np.float32), dev)}
        for _ in range(2)
    ]
    calls = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda *a, **k: (
        calls.append(a), real_put(*a, **k))[1])
    K, staged = trainer.stage_chunk(batches)
    assert K == 2 and not calls, "on-device stack path was not taken"
    outs = trainer.run_chunk((K, staged))
    assert outs[0].shape[0] == 2


def test_module_scan_gate_rejects_nonwrite_grad_req(monkeypatch):
    """A module bound with grad_req='add' must NOT take the scanned
    trainer (which has unconditional write semantics) — advisor r3."""
    from mxnet_tpu.parallel import fit_trainer

    def boom(*a, **k):
        raise AssertionError("scanned trainer constructed despite "
                             "grad_req != 'write'")

    monkeypatch.setattr(fit_trainer, "make_fit_trainer", boom)
    os.environ["MXNET_SCAN_TRAIN"] = "1"
    try:
        np.random.seed(1)
        mx.random.seed(1)
        train = mx.io.MNISTIter(batch_size=32, num_synthetic=64, seed=1)
        mod = mx.module.Module(mx.models.get_mlp(), context=mx.cpu(0))
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label, grad_req="add")
        mod.fit(train, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                initializer=mx.initializer.Xavier())
    finally:
        os.environ.pop("MXNET_SCAN_TRAIN", None)


def test_fit_survives_trainer_construction_crash(monkeypatch):
    """Non-MXNetError failures during scanned-trainer CONSTRUCTION must
    fall back to the per-batch loop, not abort fit() (advisor r3)."""
    from mxnet_tpu import model as model_mod
    from mxnet_tpu.parallel import fit_trainer

    def boom(*a, **k):
        raise TypeError("synthetic construction failure")

    monkeypatch.setattr(fit_trainer, "make_fit_trainer", boom)
    m = _fit(scan=True, opt_kwargs={"learning_rate": 0.1})
    acc = m.score(mx.io.MNISTIter(batch_size=32, num_synthetic=256, seed=2,
                                  shuffle=False))
    assert acc > 0.9


def test_buffer_batch_survives_iterator_buffer_reuse():
    """Batch contents must be snapshotted at buffering time — a DataIter
    that recycles its batch buffers (numpy in place, or NDArray
    ``__setitem__`` rebinding ``_data``) cannot corrupt staged chunks or
    deferred metric updates (advisor r3 + review). NDArrays unwrap to
    their immutable jax backing; numpy is copied."""
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.model import _buffer_batch

    data_nd = mx.nd.zeros((4, 2), mx.cpu(0))
    label_np = np.ones((4,), np.float32)
    batch = DataBatch(data=[data_nd], label=[label_np])
    buf = _buffer_batch(batch, ["data", "softmax_label"])
    assert buf["softmax_label"] is not label_np
    label_np[:] = 99.0  # iterator recycles its numpy buffer
    np.testing.assert_array_equal(buf["softmax_label"], np.ones((4,)))
    data_nd[:] = 7.0  # iterator recycles its NDArray batch object
    np.testing.assert_array_equal(np.asarray(buf["data"]), np.zeros((4, 2)))


def test_module_scanned_get_params_fresh_mid_epoch():
    """A batch_end_callback that checkpoints mid-epoch must see the
    trainer's CURRENT weights, not epoch-start values (advisor r3)."""
    os.environ["MXNET_SCAN_TRAIN"] = "1"
    os.environ["MXNET_TRAIN_SCAN_K"] = "4"
    try:
        np.random.seed(3)
        mx.random.seed(3)
        train = mx.io.MNISTIter(batch_size=32, num_synthetic=512, seed=1)
        mod = mx.module.Module(mx.models.get_mlp(), context=mx.cpu(0))
        snaps = []

        def cb(param):
            if param.nbatch == 7:  # mid-epoch (16 batches/epoch)
                ap, _ = mod.get_params()
                snaps.append(ap["fc1_weight"].asnumpy().copy())

        mod.fit(train, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb)
        assert len(snaps) == 2
        # epoch-1's mid-epoch snapshot must differ from epoch-0's (the
        # stale-params bug returned identical epoch-start values only
        # when nothing had trained yet; here both are mid-training and
        # must reflect progress)
        assert not np.allclose(snaps[0], snaps[1])
        final, _ = mod.get_params()
        assert not np.allclose(snaps[1], final["fc1_weight"].asnumpy())
    finally:
        os.environ.pop("MXNET_SCAN_TRAIN", None)
        os.environ.pop("MXNET_TRAIN_SCAN_K", None)
