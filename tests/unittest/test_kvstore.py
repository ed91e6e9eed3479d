"""KVStore semantics tests (modeled on reference test_kvstore.py:125 —
"push ones from N fake devices, expect N")."""
import numpy as np
import pytest

import mxnet_tpu as mx

SHAPE = (4, 4)
KEYS = [5, 7, 11]


def init_kv(kv_type="local"):
    kv = mx.kvstore.create(kv_type)
    kv.init(3, mx.nd.zeros(SHAPE))
    kv.init(KEYS, [mx.nd.zeros(SHAPE)] * len(KEYS))
    return kv


def check_diff_to_scalar(A, x):
    assert (np.abs(A.asnumpy() - x) < 1e-5).all(), (A.asnumpy(), x)


def test_single_kv_pair():
    kv = init_kv()
    kv.push(3, mx.nd.ones(SHAPE))
    val = mx.nd.empty(SHAPE)
    kv.pull(3, out=val)
    check_diff_to_scalar(val, 1)


def test_aggregator_multi_devs():
    kv = init_kv()
    num_devs = 4
    devs = [mx.cpu(i) for i in range(num_devs)]
    vals = [mx.nd.ones(SHAPE, d) for d in devs]
    kv.push(3, vals)
    outs = [mx.nd.empty(SHAPE, d) for d in devs]
    kv.pull(3, out=outs)
    for out in outs:
        check_diff_to_scalar(out, num_devs)


def test_list_kv_pair():
    kv = init_kv()
    num_devs = 3
    devs = [mx.cpu(i) for i in range(num_devs)]
    vals = [[mx.nd.ones(SHAPE, d) * 2.0 for d in devs] for _ in KEYS]
    kv.push(KEYS, vals)
    outs = [[mx.nd.empty(SHAPE, d) for d in devs] for _ in KEYS]
    kv.pull(KEYS, out=outs)
    for out in outs:
        for o in out:
            check_diff_to_scalar(o, num_devs * 2.0)


def test_updater():
    kv = init_kv()

    def updater(key, recv, local):
        local += recv

    kv._set_updater(updater)
    num_devs = 4
    devs = [mx.cpu(i) for i in range(num_devs)]
    vals = [mx.nd.ones(SHAPE, d) for d in devs]
    kv.push(3, vals)
    kv.push(3, vals)
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    check_diff_to_scalar(out, num_devs * 2)


def test_optimizer_on_kvstore():
    kv = mx.kvstore.create("local")
    kv.init(0, mx.nd.zeros(SHAPE))
    # Test optimizer: weight += grad * rescale (ref: optimizer.py Test +
    # tests/nightly/dist_sync_kvstore.py arithmetic)
    opt = mx.optimizer.create("test", rescale_grad=0.5)
    kv.set_optimizer(opt)
    kv.push(0, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(0, out=out)
    check_diff_to_scalar(out, 0.5)
    kv.push(0, mx.nd.ones(SHAPE))
    kv.pull(0, out=out)
    check_diff_to_scalar(out, 1.0)


@pytest.mark.parametrize("kv_type", ["local", "device"])
def test_update_on_kvstore_with_no_context_on_the_host_device(kv_type):
    """Data parallelism over accelerator contexts: the store is
    initialised from host-resident parameters while every gradient lives
    on a device, so none of the pushing contexts is the store's own.
    The stored weight must follow the merged gradient (ref
    kvstore_local.h Push) — with cpu(0) among the contexts, as in every
    other test here, the mismatch never shows; on four real chips it
    stopped Module.fit at the first update (PR 22)."""
    kv = mx.kvstore.create(kv_type)
    kv.init(3, mx.nd.ones(SHAPE))  # on cpu(0), where arg_params live
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    devs = [mx.cpu(1), mx.cpu(2)]
    grads = [mx.nd.ones(SHAPE, d) for d in devs]
    outs = [mx.nd.zeros(SHAPE, d) for d in devs]
    kv.push(3, grads)
    kv.pull(3, out=outs)
    for o in outs:
        check_diff_to_scalar(o, 1 - 0.1 * 2)
    kv.push(3, grads)  # momentum state was made beside the moved weight
    kv.pull(3, out=outs)
    for o in outs:
        np.testing.assert_allclose(o.asnumpy(), 0.8 - 0.38, rtol=1e-6)


def test_dist_sync_arithmetic_single_process():
    """The dist_sync acceptance arithmetic (ref:
    tests/nightly/dist_sync_kvstore.py:30-40) degenerated to 1 worker:
    value after n pushes of ones with Test optimizer lr=rate."""
    rate = 2.0
    kv = mx.kvstore.create("dist_sync")
    kv.init(9, mx.nd.ones(SHAPE))
    kv.set_optimizer(mx.optimizer.create("test", rescale_grad=rate))
    nrepeat = 3
    for _ in range(nrepeat):
        kv.push(9, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(9, out=out)
    nworker = kv.num_workers
    expected = (nworker + 1) * nworker * rate / 2 * nrepeat / nworker + 1
    check_diff_to_scalar(out, expected)


def test_get_type_and_rank():
    kv = mx.kvstore.create("local")
    assert kv.type == "local"
    assert kv.rank == 0
    assert kv.num_workers == 1


def test_kvstore_server_facade():
    """ref: python/mxnet/kvstore_server.py — command protocol works
    in-process; a legacy DMLC_ROLE=server launch fails loudly."""
    import pickle

    from mxnet_tpu.kvstore_server import KVStoreServer

    kv = mx.kvstore.create("local")
    server = KVStoreServer(kv)
    opt = mx.optimizer.create("sgd", learning_rate=0.5)
    server._controller(0, pickle.dumps(opt))
    assert kv._updater is not None
    server.run()  # no server loop; must return immediately
    with pytest.raises(mx.MXNetError):
        server._controller(42, b"")


def test_kvstore_server_role_rejected(monkeypatch):
    from mxnet_tpu.kvstore_server import _init_kvstore_server_module

    monkeypatch.setenv("DMLC_ROLE", "server")
    with pytest.raises(mx.MXNetError, match="worker"):
        _init_kvstore_server_module()
    monkeypatch.setenv("DMLC_ROLE", "worker")
    _init_kvstore_server_module()  # no-op
