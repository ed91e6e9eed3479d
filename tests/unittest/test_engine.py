"""Dependency-engine tests: semantics + random-workload fuzz.

Mirrors the reference's engine test strategy (ref:
tests/cpp/threaded_engine_test.cc:20-60 — random read/write workloads run
through every engine implementation, results checked for equivalence) plus
unit checks of the ThreadedVar ordering rules (threaded_engine.h:87-189).
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine as eng
from mxnet_tpu.base import MXNetError


def make_engine(engine_type):
    e = eng.Engine(engine_type=engine_type)
    if engine_type != "NaiveEngine" and not e.is_native:
        pytest.skip("native engine unavailable")
    return e


@pytest.mark.parametrize("etype", ["NaiveEngine", "ThreadedEngine"])
def test_push_and_wait(etype):
    e = make_engine(etype)
    v = e.new_variable()
    out = []
    e.push(lambda: out.append(1), mutable_vars=[v])
    e.push(lambda: out.append(2), mutable_vars=[v])
    e.wait_for_var(v)
    assert out == [1, 2]
    e.wait_for_all()


def test_write_after_read_ordering():
    """Reads granted before a write must drain before the write runs;
    the write must finish before later reads (threaded_engine.h:87-189)."""
    e = make_engine("ThreadedEngine")
    v = e.new_variable()
    log = []
    lock = threading.Lock()

    def reader(tag, delay):
        def fn():
            time.sleep(delay)
            with lock:
                log.append(tag)
        return fn

    for i in range(4):
        e.push(reader(("r1", i), 0.02), const_vars=[v])
    e.push(reader(("w", 0), 0.0), mutable_vars=[v])
    for i in range(4):
        e.push(reader(("r2", i), 0.0), const_vars=[v])
    e.wait_for_all()
    kinds = [k for k, _ in log]
    assert kinds.index("w") == 4  # after every r1, before every r2
    assert all(k == "r1" for k in kinds[:4])
    assert all(k == "r2" for k in kinds[5:])


def test_concurrent_reads_overlap():
    e = make_engine("ThreadedEngine")
    v = e.new_variable()
    barrier = threading.Barrier(2, timeout=10)

    def fn():
        barrier.wait()  # both readers must be in flight at once

    e.push(fn, const_vars=[v])
    e.push(fn, const_vars=[v])
    e.wait_for_all()


def test_duplicate_var_is_error():
    e = make_engine("ThreadedEngine")
    v = e.new_variable()
    with pytest.raises(MXNetError):
        e.push(lambda: None, const_vars=[v], mutable_vars=[v])
    e.wait_for_all()


def test_async_push():
    """PushAsync: completion is signalled by the op, not by return
    (ref: engine.h:142-146)."""
    e = make_engine("ThreadedEngine")
    v = e.new_variable()
    fired = []

    def fn(on_complete):
        def later():
            time.sleep(0.05)
            fired.append(True)
            on_complete()
        threading.Thread(target=later).start()

    e.push_async(fn, mutable_vars=[v])
    saw = []
    e.push(lambda: saw.append(bool(fired)), const_vars=[v])
    e.wait_for_all()
    assert saw == [True]  # successor saw the async op's effect


def test_exception_propagates_on_wait():
    e = make_engine("ThreadedEngine")
    v = e.new_variable()

    def bad():
        raise ValueError("boom")

    e.push(bad, mutable_vars=[v])
    with pytest.raises(ValueError):
        e.wait_for_all()
    e.wait_for_all()  # engine still usable


def test_delete_variable_deferred():
    e = make_engine("ThreadedEngine")
    v = e.new_variable()
    out = []
    e.push(lambda: (time.sleep(0.02), out.append(1)), mutable_vars=[v])
    e.delete_variable(v)  # must not tear down the pending op
    e.wait_for_all()
    assert out == [1]


def test_dropped_engine_dies_without_the_collector():
    """An engine is in no reference cycle of its own: it is closed where
    its last reference goes, not at some later allocation on whatever
    thread the cyclic collector then runs on (Engine.close)."""
    import gc
    import weakref

    was_enabled = gc.isenabled()
    gc.disable()
    try:
        e = make_engine("ThreadedEngine")
        v = e.new_variable()
        out = []
        e.push(lambda: out.append(1), mutable_vars=[v])
        e.wait_for_all()
        gone = weakref.ref(e)
        del e, v
        assert gone() is None
        assert out == [1]
    finally:
        if was_enabled:
            gc.enable()


def _run_workload(e, n_vars, ops):
    """Run a random read/write workload; each op writes
    vals[w] = sum(vals[r] for r in reads) + op_index."""
    vals = np.zeros(n_vars)
    hvars = [e.new_variable() for _ in range(n_vars)]

    def make(reads, w, idx):
        def fn():
            vals[w] = sum(vals[r] for r in reads) + idx
        return fn

    for idx, (reads, w) in enumerate(ops):
        e.push(make(reads, w, idx),
               const_vars=[hvars[r] for r in reads],
               mutable_vars=[hvars[w]])
    e.wait_for_all()
    return vals


def test_fuzz_engines_agree():
    """Random workloads produce identical results across engines and match
    sequential execution (the reference's engine fuzz check)."""
    rng = np.random.RandomState(0)
    n_vars = 8
    for trial in range(5):
        ops = []
        for _ in range(100):
            w = int(rng.randint(n_vars))
            nreads = int(rng.randint(0, 4))
            reads = [int(r) for r in rng.choice(
                [i for i in range(n_vars) if i != w],
                size=nreads, replace=False)]
            ops.append((reads, w))
        # sequential ground truth
        expect = np.zeros(n_vars)
        for idx, (reads, w) in enumerate(ops):
            expect[w] = sum(expect[r] for r in reads) + idx
        for etype in ["NaiveEngine", "ThreadedEngine"]:
            got = _run_workload(make_engine(etype), n_vars, ops)
            np.testing.assert_allclose(got, expect, err_msg=etype)


def test_fuzz_traces_verify_clean():
    """The random fuzz workloads, re-run under the mxlint engine
    recorder: the captured read/write-var traces must verify hazard-free
    (the static counterpart of the result-equivalence check above)."""
    from mxnet_tpu.analysis import engine_verify as ev

    rng = np.random.RandomState(7)
    n_vars = 8
    ops = []
    for _ in range(100):
        w = int(rng.randint(n_vars))
        nreads = int(rng.randint(0, 4))
        reads = [int(r) for r in rng.choice(
            [i for i in range(n_vars) if i != w],
            size=nreads, replace=False)]
        ops.append((reads, w))
    for etype in ["NaiveEngine", "ThreadedEngine"]:
        e = make_engine(etype)
        with ev.recording(e) as trace:
            _run_workload(e, n_vars, ops)
        assert len(trace.events) == len(ops), etype
        assert ev.verify(trace) == [], etype


def test_engine_singleton_and_module_api():
    e1 = eng.get()
    e2 = eng.Engine.get()
    assert e1 is e2
    v = e1.new_variable()
    out = []
    eng.push(lambda: out.append(1), mutable_vars=[v])
    eng.wait_for_all()
    assert out == [1]
