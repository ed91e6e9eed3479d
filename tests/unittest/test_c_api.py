"""Drive the flat C API through ctypes, as an external binding would.

Parity target: the reference's C API surface (include/mxnet/c_api.h,
include/mxnet/c_predict_api.h) exercised the way
tests/python/predict/mxnet_predict_example.py and the MATLAB binding use
it. The library embeds CPython; loading it inside this Python process
shares the interpreter (Py_IsInitialized short-circuits init), which is
exactly the in-process path the reference's own Python binding takes.
"""
import ctypes
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _native

c_uint_p = ctypes.POINTER(ctypes.c_uint)


@pytest.fixture(scope="module")
def lib():
    lib = _native.load("c_api")
    if lib is None:
        pytest.skip("c_api native build unavailable")
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


def check(lib, rc):
    assert rc == 0, lib.MXGetLastError().decode()


def test_version_and_seed(lib):
    v = ctypes.c_int()
    check(lib, lib.MXGetVersion(ctypes.byref(v)))
    assert v.value >= 10000
    check(lib, lib.MXRandomSeed(0))


def test_ndarray_roundtrip(lib):
    shape = (ctypes.c_uint * 2)(3, 4)
    h = ctypes.c_void_p()
    check(lib, lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h)))
    data = np.arange(12, dtype=np.float32)
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, data.ctypes.data_as(ctypes.c_void_p), 12))
    check(lib, lib.MXNDArrayWaitToRead(h))
    # shape readback
    ndim = ctypes.c_uint()
    pdata = c_uint_p()
    check(lib, lib.MXNDArrayGetShape(h, ctypes.byref(ndim),
                                     ctypes.byref(pdata)))
    assert [pdata[i] for i in range(ndim.value)] == [3, 4]
    # copy back
    out = np.zeros(12, dtype=np.float32)
    check(lib, lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), 12))
    np.testing.assert_array_equal(out, data)
    # context
    dt, di = ctypes.c_int(), ctypes.c_int()
    check(lib, lib.MXNDArrayGetContext(h, ctypes.byref(dt), ctypes.byref(di)))
    assert dt.value == 1 and di.value == 0
    check(lib, lib.MXNDArrayFree(h))


def test_func_invoke_and_op_list(lib):
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXListAllOpNames(ctypes.byref(n),
                                    ctypes.byref(arr)))
    names = [arr[i].decode() for i in range(n.value)]
    assert "dot" in names and "sqrt" in names
    # c = dot(a, b) through the generic invoke
    def make(shape, val):
        s = (ctypes.c_uint * len(shape))(*shape)
        h = ctypes.c_void_p()
        check(lib, lib.MXNDArrayCreate(s, len(shape), 1, 0, 0,
                                       ctypes.byref(h)))
        d = np.full(shape, val, dtype=np.float32)
        check(lib, lib.MXNDArraySyncCopyFromCPU(
            h, d.ctypes.data_as(ctypes.c_void_p), d.size))
        return h

    a, b = make((2, 3), 2.0), make((3, 4), 3.0)
    nout = ctypes.c_uint(1)
    out = (ctypes.c_void_p * 1)()
    ins = (ctypes.c_void_p * 2)(a, b)
    check(lib, lib.MXFuncInvokeByName(
        b"dot", ins, 2, 0, None, None, ctypes.byref(nout), out))
    assert nout.value == 1
    res = np.zeros(8, dtype=np.float32)
    check(lib, lib.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(out[0]), res.ctypes.data_as(ctypes.c_void_p), 8))
    np.testing.assert_allclose(res, 18.0)
    for h in (a, b, ctypes.c_void_p(out[0])):
        lib.MXNDArrayFree(h)


def test_func_invoke_capacity_protocol(lib):
    """When output capacity is too small the call fails AND reports the
    required count in *num_outputs so callers retry (header contract;
    the R/JVM bindings rely on this for >8-output ops)."""
    shape = (ctypes.c_uint * 2)(4, 16)
    h = ctypes.c_void_p()
    check(lib, lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h)))
    d = np.zeros((4, 16), np.float32)
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, d.ctypes.data_as(ctypes.c_void_p), d.size))
    keys = (ctypes.c_char_p * 2)(b"num_outputs", b"axis")
    vals = (ctypes.c_char_p * 2)(b"16", b"1")
    ins = (ctypes.c_void_p * 1)(h)
    nout = ctypes.c_uint(2)  # deliberately too small
    small = (ctypes.c_void_p * 2)()
    rc = lib.MXFuncInvokeByName(b"SliceChannel", ins, 1, 2, keys, vals,
                                ctypes.byref(nout), small)
    assert rc != 0 and nout.value == 16
    big = (ctypes.c_void_p * 16)()
    check(lib, lib.MXFuncInvokeByName(b"SliceChannel", ins, 1, 2, keys,
                                      vals, ctypes.byref(nout), big))
    assert nout.value == 16
    lib.MXNDArrayFree(h)
    for i in range(16):
        lib.MXNDArrayFree(ctypes.c_void_p(big[i]))


def test_func_invoke_capacity_retry_single_execution(lib):
    """The capacity-failure retry returns the FIRST invocation's parked
    outputs — the op executes exactly once (advisor r4: a re-execution
    would advance stateful/random ops twice). Proven by mutating the
    input between the failed call and the retry: the retried outputs
    still hold pre-mutation values, while a fresh call afterwards (cache
    consumed) sees the mutation."""
    shape = (ctypes.c_uint * 2)(2, 4)
    h = ctypes.c_void_p()
    check(lib, lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h)))
    d = np.arange(8, dtype=np.float32).reshape(2, 4)
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, d.ctypes.data_as(ctypes.c_void_p), d.size))
    keys = (ctypes.c_char_p * 2)(b"num_outputs", b"axis")
    vals = (ctypes.c_char_p * 2)(b"4", b"1")
    ins = (ctypes.c_void_p * 1)(h)
    nout = ctypes.c_uint(1)  # deliberately too small
    small = (ctypes.c_void_p * 1)()
    rc = lib.MXFuncInvokeByName(b"SliceChannel", ins, 1, 2, keys, vals,
                                ctypes.byref(nout), small)
    assert rc != 0 and nout.value == 4

    def first_col(handle):
        res = np.zeros(2, dtype=np.float32)
        check(lib, lib.MXNDArraySyncCopyToCPU(
            ctypes.c_void_p(handle), res.ctypes.data_as(ctypes.c_void_p), 2))
        return res

    d2 = d + 100.0
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, d2.ctypes.data_as(ctypes.c_void_p), d2.size))
    big = (ctypes.c_void_p * 4)()
    check(lib, lib.MXFuncInvokeByName(b"SliceChannel", ins, 1, 2, keys,
                                      vals, ctypes.byref(nout), big))
    assert nout.value == 4
    np.testing.assert_allclose(first_col(big[0]), d[:, 0])  # pre-mutation
    big2 = (ctypes.c_void_p * 4)()
    check(lib, lib.MXFuncInvokeByName(b"SliceChannel", ins, 1, 2, keys,
                                      vals, ctypes.byref(nout), big2))
    np.testing.assert_allclose(first_col(big2[0]), d2[:, 0])  # re-executed
    lib.MXNDArrayFree(h)
    for i in range(4):
        lib.MXNDArrayFree(ctypes.c_void_p(big[i]))
        lib.MXNDArrayFree(ctypes.c_void_p(big2[i]))


def test_error_reporting(lib):
    h = ctypes.c_void_p()
    nout = ctypes.c_uint(1)
    out = (ctypes.c_void_p * 1)()
    rc = lib.MXFuncInvokeByName(
        b"definitely_not_an_op", None, 0, 0, None, None,
        ctypes.byref(nout), out)
    assert rc != 0
    assert b"definitely_not_an_op" in lib.MXGetLastError()


def test_symbol_json_and_lists(lib):
    sym = mx.models.get_lenet()
    js = sym.tojson().encode()
    h = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateFromJSON(js, ctypes.byref(h)))
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXSymbolListArguments(h, ctypes.byref(n),
                                         ctypes.byref(arr)))
    args = [arr[i].decode() for i in range(n.value)]
    assert args == sym.list_arguments()
    out_json = ctypes.c_char_p()
    check(lib, lib.MXSymbolSaveToJSON(h, ctypes.byref(out_json)))
    assert mx.symbol.load_json(out_json.value.decode()).list_arguments() == args
    check(lib, lib.MXSymbolFree(h))


def test_symbol_compose_and_infer_shape(lib):
    # data -> FullyConnected(num_hidden=8), built entirely through the C ABI
    data = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    atom = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"8")
    check(lib, lib.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 1, keys, vals, ctypes.byref(atom)))
    fc = ctypes.c_void_p()
    args = (ctypes.c_void_p * 1)(data)
    check(lib, lib.MXSymbolCompose(atom, b"fc1", 1, None, args,
                                   ctypes.byref(fc)))
    # infer shape with CSR args
    akeys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    sdata = (ctypes.c_uint * 2)(5, 10)
    in_sz = ctypes.c_uint()
    out_sz = ctypes.c_uint()
    aux_sz = ctypes.c_uint()
    in_nd = c_uint_p()
    out_nd = c_uint_p()
    aux_nd = c_uint_p()
    in_d = ctypes.POINTER(c_uint_p)()
    out_d = ctypes.POINTER(c_uint_p)()
    aux_d = ctypes.POINTER(c_uint_p)()
    complete = ctypes.c_int()
    check(lib, lib.MXSymbolInferShape(
        fc, 1, akeys, indptr, sdata,
        ctypes.byref(in_sz), ctypes.byref(in_nd), ctypes.byref(in_d),
        ctypes.byref(out_sz), ctypes.byref(out_nd), ctypes.byref(out_d),
        ctypes.byref(aux_sz), ctypes.byref(aux_nd), ctypes.byref(aux_d),
        ctypes.byref(complete)))
    assert complete.value == 1
    assert out_sz.value == 1
    out_shape = [out_d[0][i] for i in range(out_nd[0])]
    assert out_shape == [5, 8]
    for h in (data, atom, fc):
        lib.MXSymbolFree(h)


def test_predict_api_end_to_end(lib, tmp_path):
    # train nothing: save random params for lenet, predict through C ABI
    sym = mx.models.get_lenet()
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(2, 1, 28, 28), softmax_label=(2,))
    rng = np.random.RandomState(0)
    params = {}
    for name, s in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params["arg:" + name] = mx.nd.array(
            rng.normal(0, 0.1, s).astype(np.float32))
    for name, s in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + name] = mx.nd.array(np.zeros(s, np.float32))
    pfile = str(tmp_path / "p.params")
    mx.nd.save(pfile, params)
    param_bytes = open(pfile, "rb").read()

    h = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 4)
    sdata = (ctypes.c_uint * 4)(2, 1, 28, 28)
    check(lib, lib.MXPredCreate(
        sym.tojson().encode(), param_bytes, len(param_bytes), 1, 0,
        1, keys, indptr, sdata, ctypes.byref(h)))
    x = rng.rand(2, 1, 28, 28).astype(np.float32)
    check(lib, lib.MXPredSetInput(
        h, b"data", x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x.size))
    check(lib, lib.MXPredForward(h))
    sd = c_uint_p()
    snd = ctypes.c_uint()
    check(lib, lib.MXPredGetOutputShape(h, 0, ctypes.byref(sd),
                                        ctypes.byref(snd)))
    oshape = [sd[i] for i in range(snd.value)]
    assert oshape == [2, 10]
    out = np.zeros(20, dtype=np.float32)
    check(lib, lib.MXPredGetOutput(
        h, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 20))
    out = out.reshape(2, 10)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)  # softmax

    # MXPredReshape returns an independent predictor; original keeps bs=2
    h2 = ctypes.c_void_p()
    indptr2 = (ctypes.c_uint * 2)(0, 4)
    sdata2 = (ctypes.c_uint * 4)(1, 1, 28, 28)
    check(lib, lib.MXPredReshape(1, keys, indptr2, sdata2, h,
                                 ctypes.byref(h2)))
    sd2 = c_uint_p()
    snd2 = ctypes.c_uint()
    check(lib, lib.MXPredGetOutputShape(h2, 0, ctypes.byref(sd2),
                                        ctypes.byref(snd2)))
    assert [sd2[i] for i in range(snd2.value)] == [1, 10]
    check(lib, lib.MXPredGetOutputShape(h, 0, ctypes.byref(sd2),
                                        ctypes.byref(snd2)))
    assert [sd2[i] for i in range(snd2.value)] == [2, 10]
    check(lib, lib.MXPredFree(h2))
    check(lib, lib.MXPredFree(h))


def test_atomic_symbol_reused(lib):
    """One atomic handle composed twice yields two distinct symbols
    (the reference C API permits handle reuse)."""
    atom = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"4")
    check(lib, lib.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 1, keys, vals, ctypes.byref(atom)))
    outs = []
    for nm in (b"fca", b"fcb"):
        d = ctypes.c_void_p()
        check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(d)))
        fc = ctypes.c_void_p()
        args = (ctypes.c_void_p * 1)(d)
        check(lib, lib.MXSymbolCompose(atom, nm, 1, None, args,
                                       ctypes.byref(fc)))
        n = ctypes.c_uint()
        arr = ctypes.POINTER(ctypes.c_char_p)()
        check(lib, lib.MXSymbolListOutputs(fc, ctypes.byref(n),
                                           ctypes.byref(arr)))
        outs.append([arr[i].decode() for i in range(n.value)])
        lib.MXSymbolFree(d)
        lib.MXSymbolFree(fc)
    lib.MXSymbolFree(atom)
    assert outs[0] == ["fca_output"] and outs[1] == ["fcb_output"]


# ---- round-2 surface: full C ABI (ref c_api.h:528-1418) ---------------------

def _mk_strarr(strs):
    arr = (ctypes.c_char_p * len(strs))(*[s.encode() for s in strs])
    return arr


def _atomic(lib, op, **params):
    keys = _mk_strarr(list(params.keys()))
    vals = _mk_strarr([str(v) for v in params.values()])
    h = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateAtomicSymbol(
        op.encode(), len(params), keys, vals, ctypes.byref(h)))
    return h


def _compose(lib, atom, name, **inputs):
    keys = _mk_strarr(list(inputs.keys()))
    args = (ctypes.c_void_p * len(inputs))(*[v for v in inputs.values()])
    out = ctypes.c_void_p()
    check(lib, lib.MXSymbolCompose(
        atom, name.encode(), len(inputs), keys, args, ctypes.byref(out)))
    return out


def _variable(lib, name):
    h = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateVariable(name.encode(), ctypes.byref(h)))
    return h


def _nd_from_np(lib, arr):
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    shape = (ctypes.c_uint * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    check(lib, lib.MXNDArrayCreate(shape, arr.ndim, 1, 0, 0, ctypes.byref(h)))
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, arr.ctypes.data_as(ctypes.c_void_p), arr.size))
    return h


def _nd_to_np(lib, h, shape):
    out = np.zeros(shape, dtype=np.float32)
    check(lib, lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), out.size))
    return out


def test_c_api_symbol_attr_and_info(lib):
    v = _variable(lib, "x")
    check(lib, lib.MXSymbolSetAttr(v, b"ctx_group", b"dev1"))
    out = ctypes.c_char_p()
    ok = ctypes.c_int()
    check(lib, lib.MXSymbolGetAttr(v, b"ctx_group", ctypes.byref(out),
                                   ctypes.byref(ok)))
    assert ok.value == 1 and out.value == b"dev1"
    # name readback
    check(lib, lib.MXSymbolGetName(v, ctypes.byref(out), ctypes.byref(ok)))
    assert ok.value == 1 and out.value == b"x"
    # copy is independent
    cp = ctypes.c_void_p()
    check(lib, lib.MXSymbolCopy(v, ctypes.byref(cp)))
    check(lib, lib.MXSymbolSetAttr(cp, b"ctx_group", b"dev2"))
    check(lib, lib.MXSymbolGetAttr(v, b"ctx_group", ctypes.byref(out),
                                   ctypes.byref(ok)))
    assert out.value == b"dev1"
    # creators list + info
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(arr)))
    names = {arr[i] for i in range(n.value)}
    assert b"Convolution" in names and b"FullyConnected" in names
    name = ctypes.c_char_p(); desc = ctypes.c_char_p()
    nargs = ctypes.c_uint()
    an = ctypes.POINTER(ctypes.c_char_p)()
    at = ctypes.POINTER(ctypes.c_char_p)()
    ad = ctypes.POINTER(ctypes.c_char_p)()
    kv = ctypes.c_char_p(); rt = ctypes.c_char_p()
    check(lib, lib.MXSymbolGetAtomicSymbolInfo(
        b"Convolution", ctypes.byref(name), ctypes.byref(desc),
        ctypes.byref(nargs), ctypes.byref(an), ctypes.byref(at),
        ctypes.byref(ad), ctypes.byref(kv), ctypes.byref(rt)))
    assert name.value == b"Convolution"
    params = {an[i] for i in range(nargs.value)}
    assert b"kernel" in params and b"num_filter" in params
    lib.MXSymbolFree(v)
    lib.MXSymbolFree(cp)


def test_c_api_symbol_infer_type(lib):
    data = _variable(lib, "data")
    fc = _compose(lib, _atomic(lib, "FullyConnected", num_hidden=4),
                  "fc", data=data)
    keys = _mk_strarr(["data"])
    codes = (ctypes.c_int * 1)(0)  # f32
    sizes = [ctypes.c_uint() for _ in range(3)]
    datas = [ctypes.POINTER(ctypes.c_int)() for _ in range(3)]
    complete = ctypes.c_int()
    check(lib, lib.MXSymbolInferType(
        fc, 1, keys, codes,
        ctypes.byref(sizes[0]), ctypes.byref(datas[0]),
        ctypes.byref(sizes[1]), ctypes.byref(datas[1]),
        ctypes.byref(sizes[2]), ctypes.byref(datas[2]),
        ctypes.byref(complete)))
    assert complete.value == 1
    assert [datas[0][i] for i in range(sizes[0].value)] == [0, 0, 0]
    assert datas[1][0] == 0


def test_c_api_recordio_roundtrip(lib, tmp_path):
    uri = str(tmp_path / "t.rec").encode()
    h = ctypes.c_void_p()
    check(lib, lib.MXRecordIOWriterCreate(uri, ctypes.byref(h)))
    recs = [b"hello", b"x" * 1000, b"world"]
    for r in recs:
        check(lib, lib.MXRecordIOWriterWriteRecord(
            ctypes.byref(h), r, ctypes.c_size_t(len(r))))
    pos = ctypes.c_size_t()
    check(lib, lib.MXRecordIOWriterTell(ctypes.byref(h), ctypes.byref(pos)))
    assert pos.value > 0
    check(lib, lib.MXRecordIOWriterFree(h))

    check(lib, lib.MXRecordIOReaderCreate(uri, ctypes.byref(h)))
    buf = ctypes.c_char_p()
    size = ctypes.c_size_t()
    got = []
    while True:
        check(lib, lib.MXRecordIOReaderReadRecord(
            ctypes.byref(h), ctypes.byref(buf), ctypes.byref(size)))
        if size.value == 0:
            break
        got.append(ctypes.string_at(buf, size.value))
    assert got == recs
    check(lib, lib.MXRecordIOReaderFree(ctypes.byref(h)))


def test_c_api_kvstore_updater_callback(lib):
    h = ctypes.c_void_p()
    check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(h)))
    t = ctypes.c_char_p()
    check(lib, lib.MXKVStoreGetType(h, ctypes.byref(t)))
    assert t.value == b"local"
    r = ctypes.c_int()
    check(lib, lib.MXKVStoreGetRank(h, ctypes.byref(r)))
    assert r.value == 0
    check(lib, lib.MXKVStoreGetGroupSize(h, ctypes.byref(r)))
    assert r.value >= 1
    check(lib, lib.MXKVStoreIsWorkerNode(ctypes.byref(r)))
    assert r.value == 1

    keys = (ctypes.c_int * 1)(3)
    init = _nd_from_np(lib, np.zeros((4,)))
    vals = (ctypes.c_void_p * 1)(init)
    check(lib, lib.MXKVStoreInit(h, 1, keys, vals))

    seen = []
    UPDATER = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                               ctypes.c_void_p, ctypes.c_void_p)

    @UPDATER
    def updater(key, recv, local, _):
        seen.append(key)
        # local += recv, performed through the C ABI itself. ctypes hands
        # pointer params to the callback as plain ints — rewrap before
        # re-passing or they truncate to 32 bits.
        recv = ctypes.c_void_p(recv)
        local = ctypes.c_void_p(local)
        g = _nd_to_np(lib, recv, (4,))
        w = _nd_to_np(lib, local, (4,))
        w += g
        arr = np.ascontiguousarray(w, np.float32)
        check(lib, lib.MXNDArraySyncCopyFromCPU(
            local, arr.ctypes.data_as(ctypes.c_void_p), arr.size))

    check(lib, lib.MXKVStoreSetUpdater(h, updater, None))
    push = _nd_from_np(lib, np.ones((4,)) * 2)
    vals2 = (ctypes.c_void_p * 1)(push)
    check(lib, lib.MXKVStorePush(h, 1, keys, vals2, 0))
    outnd = _nd_from_np(lib, np.zeros((4,)))
    vals3 = (ctypes.c_void_p * 1)(outnd)
    check(lib, lib.MXKVStorePull(h, 1, keys, vals3, 0))
    np.testing.assert_allclose(_nd_to_np(lib, outnd, (4,)), np.full(4, 2.0))
    assert seen == [3]
    check(lib, lib.MXKVStoreBarrier(h))
    dead = ctypes.c_int(-1)
    check(lib, lib.MXKVStoreGetNumDeadNode(h, -1, ctypes.byref(dead), 5))
    assert dead.value == 0
    lib.MXKVStoreFree(h)


def test_c_api_dataiter(lib):
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXListDataIters(ctypes.byref(n), ctypes.byref(arr)))
    names = {arr[i] for i in range(n.value)}
    assert b"MNISTIter" in names
    keys = _mk_strarr(["batch_size", "num_synthetic", "seed", "shuffle"])
    vals = _mk_strarr(["32", "128", "1", "False"])
    it = ctypes.c_void_p()
    check(lib, lib.MXDataIterCreateIter(
        b"MNISTIter", 4, keys, vals, ctypes.byref(it)))
    more = ctypes.c_int()
    nb = 0
    check(lib, lib.MXDataIterBeforeFirst(it))
    while True:
        check(lib, lib.MXDataIterNext(it, ctypes.byref(more)))
        if not more.value:
            break
        nb += 1
        d = ctypes.c_void_p()
        check(lib, lib.MXDataIterGetData(it, ctypes.byref(d)))
        dat = _nd_to_np(lib, d, (32, 1, 28, 28))
        assert dat.max() <= 1.0
        lib.MXNDArrayFree(d)
        pad = ctypes.c_int(-1)
        check(lib, lib.MXDataIterGetPadNum(it, ctypes.byref(pad)))
        assert pad.value == 0
    assert nb == 4
    lib.MXDataIterFree(it)


def test_c_api_optimizer(lib):
    creator = ctypes.c_char_p()
    check(lib, lib.MXOptimizerFindCreator(b"sgd", ctypes.byref(creator)))
    keys = _mk_strarr(["momentum"])
    vals = _mk_strarr(["0.0"])
    opt = ctypes.c_void_p()
    check(lib, lib.MXOptimizerCreateOptimizer(
        b"sgd", 1, keys, vals, ctypes.byref(opt)))
    w = _nd_from_np(lib, np.ones((4,)))
    g = _nd_from_np(lib, np.ones((4,)))
    lib.MXOptimizerUpdate.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_float, ctypes.c_float]
    check(lib, lib.MXOptimizerUpdate(opt, 0, w, g, 0.5, 0.0))
    np.testing.assert_allclose(_nd_to_np(lib, w, (4,)), np.full(4, 0.5))
    lib.MXOptimizerFree(opt)


def test_c_api_rtc(lib):
    x = _nd_from_np(lib, np.full((8,), 1.0))
    y = _nd_from_np(lib, np.zeros((8,)))
    ins = (ctypes.c_void_p * 1)(x)
    outs = (ctypes.c_void_p * 1)(y)
    in_names = _mk_strarr(["x"])
    out_names = _mk_strarr(["y"])
    h = ctypes.c_void_p()
    check(lib, lib.MXRtcCreate(
        b"k", 1, 1, ctypes.cast(in_names, ctypes.POINTER(ctypes.c_char_p)),
        ctypes.cast(out_names, ctypes.POINTER(ctypes.c_char_p)),
        ins, outs, b"y[...] = jnp.exp(x[...] * 2.0)", ctypes.byref(h)))
    check(lib, lib.MXRtcPush(h, 1, 1, ins, outs, 1, 1, 1, 8, 1, 1))
    np.testing.assert_allclose(_nd_to_np(lib, y, (8,)),
                               np.full(8, np.exp(2.0)), rtol=1e-5)
    lib.MXRtcFree(h)


class _CustomOpInfo(ctypes.Structure):
    _FWD = ctypes.CFUNCTYPE(
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_uint), ctypes.POINTER(ctypes.c_uint),
        ctypes.c_void_p)
    _BWD = ctypes.CFUNCTYPE(
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_uint), ctypes.POINTER(ctypes.c_uint),
        ctypes.c_void_p)
    _SHP = ctypes.CFUNCTYPE(
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_uint),
        ctypes.POINTER(ctypes.c_uint), ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint), ctypes.POINTER(ctypes.c_uint),
        ctypes.c_void_p)
    _fields_ = [
        ("forward", _FWD), ("backward", _BWD), ("infer_shape", _SHP),
        ("num_inputs", ctypes.c_int), ("num_outputs", ctypes.c_int),
        ("user", ctypes.c_void_p),
    ]


def test_c_api_custom_op_register(lib):
    """A C-native doubling op: forward y = 2x, backward dx = 2dy —
    registered through MXCustomOpRegister and driven through the Python
    symbol layer, proving out-of-tree foreign-language ops (the SSD
    multibox scenario, SURVEY §2.B.5)."""

    @_CustomOpInfo._FWD
    def fwd(num_in, in_data, num_out, out_data, shapes, ndims, user):
        total = 1
        for d in range(ndims[0]):
            total *= shapes[d]
        for i in range(total):
            out_data[0][i] = in_data[0][i] * 2.0
        return 0

    @_CustomOpInfo._BWD
    def bwd(num_in, in_data, out_grad, in_grad, shapes, ndims, user):
        total = 1
        for d in range(ndims[0]):
            total *= shapes[d]
        for i in range(total):
            in_grad[0][i] = out_grad[0][i] * 2.0
        return 0

    info = _CustomOpInfo(forward=fwd, backward=bwd,
                         infer_shape=_CustomOpInfo._SHP(),
                         num_inputs=1, num_outputs=1, user=None)
    check(lib, lib.MXCustomOpRegister(b"c_double", ctypes.byref(info)))

    data = mx.sym.Variable("data")
    out = mx.sym.Custom(data=data, op_type="c_double")
    x = mx.nd.array(np.arange(6.0).reshape(2, 3))
    gx = mx.nd.zeros((2, 3))
    exe = out.bind(mx.cpu(0), {"data": x}, args_grad={"data": gx})
    exe.forward(is_train=True)
    np.testing.assert_allclose(exe.outputs[0].asnumpy(),
                               np.arange(6.0).reshape(2, 3) * 2)
    exe.backward([mx.nd.array(np.ones((2, 3)))])
    np.testing.assert_allclose(gx.asnumpy(), np.full((2, 3), 2.0))


def _build_lenet_via_c(lib):
    data = _variable(lib, "data")
    label = _variable(lib, "softmax_label")
    c1 = _compose(lib, _atomic(lib, "Convolution", kernel="(5, 5)",
                               num_filter=8), "conv1", data=data)
    a1 = _compose(lib, _atomic(lib, "Activation", act_type="tanh"),
                  "act1", data=c1)
    p1 = _compose(lib, _atomic(lib, "Pooling", pool_type="max",
                               kernel="(2, 2)", stride="(2, 2)"),
                  "pool1", data=a1)
    c2 = _compose(lib, _atomic(lib, "Convolution", kernel="(5, 5)",
                               num_filter=16), "conv2", data=p1)
    a2 = _compose(lib, _atomic(lib, "Activation", act_type="tanh"),
                  "act2", data=c2)
    p2 = _compose(lib, _atomic(lib, "Pooling", pool_type="max",
                               kernel="(2, 2)", stride="(2, 2)"),
                  "pool2", data=a2)
    fl = _compose(lib, _atomic(lib, "Flatten"), "flat", data=p2)
    f1 = _compose(lib, _atomic(lib, "FullyConnected", num_hidden=64),
                  "fc1", data=fl)
    a3 = _compose(lib, _atomic(lib, "Activation", act_type="tanh"),
                  "act3", data=f1)
    f2 = _compose(lib, _atomic(lib, "FullyConnected", num_hidden=10),
                  "fc2", data=a3)
    sm = _compose(lib, _atomic(lib, "SoftmaxOutput"), "softmax",
                  data=f2, label=label)
    return sm


def test_c_api_train_lenet_end_to_end(lib):
    """The VERDICT r1 'done' criterion for the C API: LeNet trained to
    >0.9 accuracy on synthetic MNIST purely through the C ABI — symbol
    compose, shape inference, executor bind/forward/backward, DataIter
    batches, optimizer updates, predictions — no Python-frontend calls."""
    bs = 64
    sm = _build_lenet_via_c(lib)

    # arguments + shapes through the C ABI
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXSymbolListArguments(sm, ctypes.byref(n),
                                         ctypes.byref(arr)))
    arg_names = [arr[i].decode() for i in range(n.value)]
    keys = _mk_strarr(["data", "softmax_label"])
    indptr = (ctypes.c_uint * 3)(0, 4, 5)
    sdata = (ctypes.c_uint * 5)(bs, 1, 28, 28, bs)
    sizes = [ctypes.c_uint() for _ in range(3)]
    ndims = [ctypes.POINTER(ctypes.c_uint)() for _ in range(3)]
    datas = [ctypes.POINTER(ctypes.POINTER(ctypes.c_uint))() for _ in range(3)]
    complete = ctypes.c_int()
    check(lib, lib.MXSymbolInferShape(
        sm, 2, keys, indptr, sdata,
        ctypes.byref(sizes[0]), ctypes.byref(ndims[0]), ctypes.byref(datas[0]),
        ctypes.byref(sizes[1]), ctypes.byref(ndims[1]), ctypes.byref(datas[1]),
        ctypes.byref(sizes[2]), ctypes.byref(ndims[2]), ctypes.byref(datas[2]),
        ctypes.byref(complete)))
    assert complete.value == 1
    arg_shapes = []
    for i in range(sizes[0].value):
        arg_shapes.append(tuple(datas[0][i][d] for d in range(ndims[0][i])))

    # parameter/grad arrays
    rng = np.random.RandomState(0)
    args, grads, reqs = [], [], []
    for name, shp in zip(arg_names, arg_shapes):
        if name in ("data", "softmax_label"):
            args.append(_nd_from_np(lib, np.zeros(shp)))
            grads.append(None)
            reqs.append(0)
        else:
            fan_in = float(np.prod(shp[1:])) if len(shp) > 1 else shp[0]
            scale = np.sqrt(3.0 / max(fan_in, 1.0))
            init = (rng.uniform(-scale, scale, shp)
                    if not name.endswith("bias") else np.zeros(shp))
            args.append(_nd_from_np(lib, init))
            grads.append(_nd_from_np(lib, np.zeros(shp)))
            reqs.append(1)
    arg_arr = (ctypes.c_void_p * len(args))(*args)
    grad_arr = (ctypes.c_void_p * len(args))(
        *[g if g is not None else None for g in grads])
    req_arr = (ctypes.c_uint * len(args))(*reqs)
    exe = ctypes.c_void_p()
    check(lib, lib.MXExecutorBind(
        sm, 1, 0, len(args), arg_arr, grad_arr, req_arr, 0, None,
        ctypes.byref(exe)))

    # data iterator
    ikeys = _mk_strarr(["batch_size", "num_synthetic", "seed"])
    ivals = _mk_strarr([str(bs), "512", "1"])
    it = ctypes.c_void_p()
    check(lib, lib.MXDataIterCreateIter(
        b"MNISTIter", 3, ikeys, ivals, ctypes.byref(it)))

    # optimizer; rescale_grad=1/batch as FeedForward/_create_kvstore does
    # (loss heads sum gradients over the batch, ref model.py:117)
    okeys = _mk_strarr(["momentum", "rescale_grad"])
    ovals = _mk_strarr(["0.9", str(1.0 / bs)])
    opt = ctypes.c_void_p()
    check(lib, lib.MXOptimizerCreateOptimizer(
        b"sgd", 2, okeys, ovals, ctypes.byref(opt)))
    lib.MXOptimizerUpdate.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_float, ctypes.c_float]

    data_idx = arg_names.index("data")
    label_idx = arg_names.index("softmax_label")
    param_idx = [i for i, r in enumerate(reqs) if r == 1]

    def run_epoch(train):
        more = ctypes.c_int()
        correct = total = 0
        check(lib, lib.MXDataIterBeforeFirst(it))
        while True:
            check(lib, lib.MXDataIterNext(it, ctypes.byref(more)))
            if not more.value:
                break
            d = ctypes.c_void_p(); l = ctypes.c_void_p()
            check(lib, lib.MXDataIterGetData(it, ctypes.byref(d)))
            check(lib, lib.MXDataIterGetLabel(it, ctypes.byref(l)))
            dat = _nd_to_np(lib, d, (bs, 1, 28, 28))
            lab = _nd_to_np(lib, l, (bs,))
            lib.MXNDArrayFree(d); lib.MXNDArrayFree(l)
            check(lib, lib.MXNDArraySyncCopyFromCPU(
                args[data_idx], dat.ctypes.data_as(ctypes.c_void_p), dat.size))
            check(lib, lib.MXNDArraySyncCopyFromCPU(
                args[label_idx], lab.ctypes.data_as(ctypes.c_void_p), lab.size))
            check(lib, lib.MXExecutorForward(exe, 1 if train else 0))
            n_out = ctypes.c_uint()
            outs = ctypes.POINTER(ctypes.c_void_p)()
            check(lib, lib.MXExecutorOutputs(exe, ctypes.byref(n_out),
                                             ctypes.byref(outs)))
            probs = _nd_to_np(lib, ctypes.c_void_p(outs[0]), (bs, 10))
            for i in range(n_out.value):
                lib.MXNDArrayFree(ctypes.c_void_p(outs[i]))
            correct += int((probs.argmax(1) == lab).sum())
            total += bs
            if train:
                check(lib, lib.MXExecutorBackward(exe, 0, None))
                for i in param_idx:
                    check(lib, lib.MXOptimizerUpdate(
                        opt, i, args[i], grads[i], 0.1, 0.0))
        return correct / total

    acc = 0.0
    for epoch in range(6):
        acc = run_epoch(train=True)
        if acc > 0.95:
            break
    assert acc > 0.9, "C-ABI LeNet failed to train: acc=%.3f" % acc

    # executor report exists
    rep = ctypes.c_char_p()
    check(lib, lib.MXExecutorPrint(exe, ctypes.byref(rep)))
    assert b"Total argument memory" in rep.value
    lib.MXExecutorFree(exe)
    lib.MXDataIterFree(it)
    lib.MXOptimizerFree(opt)


def test_cpp_binding_trains_lenet(lib, tmp_path):
    """Compile bindings/cpp/train_lenet.cc against libc_api.so and run it
    as a standalone process — non-Python code training LeNet end-to-end
    (VERDICT r1 'ship one real binding' criterion)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    src = os.path.join(repo, "bindings", "cpp", "train_lenet.cc")
    natdir = os.path.join(repo, "mxnet_tpu", "_native")
    exe = str(tmp_path / "train_lenet")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", src, "-o", exe,
         "-L" + natdir, "-lc_api", "-Wl,-rpath," + natdir],
        check=True, capture_output=True, timeout=120)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # hermetic CPU run
    r = subprocess.run([exe], env=env, capture_output=True, timeout=600)
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()
    assert b"trained through libc_api.so OK" in r.stdout


def test_cpp_api_package_trains_checkpoints_reloads(lib, tmp_path):
    """The C++ API PACKAGE (bindings/cpp/include/mxnet_cpp.hpp): LeNet
    built with the Operator factory, trained via FeedForward.Fit
    (optimizer + metric inside), checkpointed to the Python-compatible
    prefix-symbol.json/-0000.params format, reloaded, and re-scored —
    binding-at-training-parity, the mx.model.FeedForward.create bar
    (VERDICT r2 item 6; ref R-package/R/model.R:391)."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    src = os.path.join(repo, "bindings", "cpp", "lenet_api.cc")
    natdir = os.path.join(repo, "mxnet_tpu", "_native")
    exe = str(tmp_path / "lenet_api")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", src, "-o", exe,
         "-L" + natdir, "-lc_api", "-Wl,-rpath," + natdir],
        check=True, capture_output=True, timeout=180)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([exe, str(tmp_path)], env=env, capture_output=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()
    assert b"train + checkpoint + reload OK" in r.stdout
    # the checkpoint is byte-compatible with the Python frontend
    import mxnet_tpu as mx

    sym, arg_params, aux_params = mx.model.load_checkpoint(
        str(tmp_path / "lenet_cpp"), 0)
    assert "fc2_weight" in arg_params


def test_c_api_custom_op_infer_shape_callback(lib):
    """Exercise the MX_CUSTOM_OP_MAX_NDIM fixed-stride infer_shape
    protocol: a row-sum op mapping (n, m) -> (n, 1)."""

    @_CustomOpInfo._FWD
    def fwd(num_in, in_data, num_out, out_data, shapes, ndims, user):
        n, m = shapes[0], shapes[1]
        for i in range(n):
            s = 0.0
            for j in range(m):
                s += in_data[0][i * m + j]
            out_data[0][i] = s
        return 0

    @_CustomOpInfo._SHP
    def shp(num_in, in_flat, in_ndims, num_out, out_flat, out_ndims, user):
        # input 0 is (n, m); output 0 is (n, 1), written at stride slot 0
        out_flat[0] = in_flat[0]
        out_flat[1] = 1
        out_ndims[0] = 2
        return 0

    info = _CustomOpInfo(forward=fwd, backward=_CustomOpInfo._BWD(),
                         infer_shape=shp, num_inputs=1, num_outputs=1,
                         user=None)
    check(lib, lib.MXCustomOpRegister(b"c_rowsum", ctypes.byref(info)))

    data = mx.sym.Variable("data")
    out = mx.sym.Custom(data=data, op_type="c_rowsum")
    _, out_shapes, _ = out.infer_shape(data=(3, 4))
    assert tuple(out_shapes[0]) == (3, 1)
    x = np.arange(12.0).reshape(3, 4).astype(np.float32)
    exe = out.bind(mx.cpu(0), {"data": mx.nd.array(x)}, grad_req="null")
    exe.forward(is_train=False)
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), x.sum(1, keepdims=True))


def test_c_api_infer_shape_partial_complete_flag(lib):
    """Partial inference with unknowns must report complete=0 (the
    reference's MXSymbolInferShapePartial contract)."""
    sym = mx.sym.FullyConnected(data=mx.sym.Variable("data"), num_hidden=4)
    h = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateFromJSON(sym.tojson().encode(),
                                          ctypes.byref(h)))
    indptr = (ctypes.c_uint * 1)(0)
    sz = [ctypes.c_uint() for _ in range(3)]
    nd = [c_uint_p() for _ in range(3)]
    da = [ctypes.POINTER(c_uint_p)() for _ in range(3)]
    comp = ctypes.c_int(-1)
    check(lib, lib.MXSymbolInferShapePartial(
        h, 0, None, indptr, None,
        ctypes.byref(sz[0]), ctypes.byref(nd[0]), ctypes.byref(da[0]),
        ctypes.byref(sz[1]), ctypes.byref(nd[1]), ctypes.byref(da[1]),
        ctypes.byref(sz[2]), ctypes.byref(nd[2]), ctypes.byref(da[2]),
        ctypes.byref(comp)))
    assert comp.value == 0
    lib.MXSymbolFree(h)
