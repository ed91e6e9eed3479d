"""mxtel observability subsystem tests: registry semantics, histogram
percentiles vs the numpy reference, span nesting (same-thread and
cross-thread), journal round-trip through tools/telemetry_report.py,
the off-by-default guard, and the FeedForward.fit acceptance smoke
(engine/kvstore/io/executor metrics + nested epoch/batch spans in one
journal)."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry.registry import Histogram, Registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(ROOT, "tools") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "tools"))

import telemetry_report  # noqa: E402


def _enable(monkeypatch, journal=None):
    """Turn mxtel on for this test (the conftest fixture re-reads the
    restored env afterwards)."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    if journal is not None:
        monkeypatch.setenv("MXNET_TELEMETRY_JOURNAL", str(journal))
    else:
        monkeypatch.delenv("MXNET_TELEMETRY_JOURNAL", raising=False)
    telemetry.reset()
    assert telemetry.reload() is True


# -- registry semantics --------------------------------------------------------
def test_counter_and_gauge_semantics():
    reg = Registry()
    c = reg.counter("a.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("a.count") is c  # get-or-create returns the same
    g = reg.gauge("a.depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2.0
    snap = reg.snapshot()
    assert snap["counters"]["a.count"] == 5
    assert snap["gauges"]["a.depth"] == 2.0
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_registry_kind_conflict_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_counter_thread_safety():
    reg = Registry()
    c = reg.counter("n")

    def work():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000


# -- histogram percentiles vs numpy --------------------------------------------
@pytest.mark.parametrize("n", [1, 7, 100, 2048])
def test_histogram_percentiles_match_numpy(n):
    rng = np.random.RandomState(n)
    vals = rng.lognormal(size=n)
    h = Histogram("h", capacity=4096)  # no wrap: window == full stream
    for v in vals:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            np.percentile(vals, q), rel=1e-12)
    s = h.summary()
    assert s["count"] == n
    assert s["sum"] == pytest.approx(vals.sum())
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())


def test_histogram_ring_buffer_window():
    """Past capacity, percentiles cover exactly the newest `capacity`
    observations while count/sum/min/max cover the full stream."""
    cap = 64
    h = Histogram("h", capacity=cap)
    vals = np.arange(1000, dtype=np.float64)
    for v in vals:
        h.observe(v)
    window = vals[-cap:]
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(window, q))
    assert h.count == 1000
    assert h.sum == pytest.approx(vals.sum())
    assert h.summary()["min"] == 0.0  # stream min, not window min


def test_histogram_empty():
    h = Histogram("h")
    assert h.percentile(50) is None
    s = h.summary()
    assert s["count"] == 0 and s["p50"] is None


# -- spans ---------------------------------------------------------------------
def test_span_nesting_same_thread(monkeypatch):
    _enable(monkeypatch)
    with telemetry.span("outer"):
        outer_id = telemetry.current_span()
        with telemetry.span("inner"):
            assert telemetry.current_span() != outer_id
        assert telemetry.current_span() == outer_id
    assert telemetry.current_span() is None
    tail = {r["name"]: r for r in telemetry.span_tail()}
    assert tail["inner"]["parent"] == tail["outer"]["id"]
    assert tail["outer"]["parent"] is None
    assert tail["inner"]["dur"] <= tail["outer"]["dur"]
    aggs = telemetry.span_aggregates()
    assert aggs["outer"]["count"] == 1 and aggs["inner"]["count"] == 1


def test_span_nesting_across_threads(monkeypatch):
    """Cross-thread propagation is explicit: the dispatching side
    captures current_span() and the worker passes it as parent."""
    _enable(monkeypatch)
    done = threading.Event()
    with telemetry.span("dispatch"):
        parent = telemetry.current_span()

        def worker():
            with telemetry.span("work", parent=parent):
                pass
            # a fresh thread with no explicit parent starts a new root
            with telemetry.span("orphan"):
                pass
            done.set()

        t = threading.Thread(target=worker, name="mxtel-test-worker")
        t.start()
        t.join(10)
    assert done.is_set()
    tail = {r["name"]: r for r in telemetry.span_tail()}
    assert tail["work"]["parent"] == tail["dispatch"]["id"]
    assert tail["orphan"]["parent"] is None
    assert tail["work"]["thread"] == "mxtel-test-worker"


def test_span_forwards_into_the_profiler_whoever_captures(monkeypatch):
    """A span's name lands in the xplane timeline through
    profiler.scope(), with its step number, whether or not the capture
    was started through mx.profiler (a TraceAnnotation with no capture
    running is one C++ check): the capture may be the benchmark's or
    TensorBoard's. tests/unittest/test_scope_times.py reads one back."""
    import contextlib

    from mxnet_tpu import profiler

    _enable(monkeypatch)
    seen = []

    @contextlib.contextmanager
    def fake_scope(name, step=None):
        seen.append((name, step))
        yield

    monkeypatch.setattr(profiler, "scope", fake_scope)
    assert profiler.state() == "stop"
    with telemetry.span("fit.chunk", step=4):
        with telemetry.span("fit.feed"):
            pass
    assert seen == [("fit.chunk", 4), ("fit.feed", None)]


def test_span_exception_still_recorded(monkeypatch):
    _enable(monkeypatch)
    with pytest.raises(RuntimeError):
        with telemetry.span("boom"):
            raise RuntimeError("x")
    assert telemetry.span_aggregates()["boom"]["count"] == 1
    assert telemetry.current_span() is None  # stack unwound


# -- off-by-default guard ------------------------------------------------------
def test_disabled_span_is_shared_null_context(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.reload()
    assert telemetry.ENABLED is False
    s1 = telemetry.span("a")
    s2 = telemetry.span("b")
    assert s1 is s2  # one shared nullcontext: no per-span allocation
    with s1:
        pass
    assert telemetry.span_aggregates() == {}


def test_disabled_instrumented_paths_do_no_counter_work(monkeypatch,
                                                        tmp_path):
    """With MXNET_TELEMETRY unset, exercising every instrumented layer
    must register NOTHING (the hot paths reduce to a boolean check) and
    write no journal file."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    monkeypatch.delenv("MXNET_TELEMETRY_JOURNAL", raising=False)
    telemetry.reset()
    telemetry.reload()
    assert telemetry.journal_path() is None

    # engine: push + wait
    from mxnet_tpu import engine
    ran = []
    engine.push(lambda: ran.append(1))
    engine.wait_for_all()
    assert ran == [1]
    # io: iterate a batch
    X = np.random.RandomState(0).rand(16, 4).astype("f")
    it = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=8)
    it.next()
    # executor: bind + forward + backward
    sym = mx.sym.SoftmaxOutput(mx.sym.Variable("data"), name="softmax")
    exe = sym.simple_bind(mx.cpu(), data=(2, 3), grad_req="write")
    exe.forward(is_train=True)
    exe.backward()
    # kvstore: init/push/pull
    kv = mx.kvstore.create("local")
    kv.init(0, mx.nd.zeros((2, 2)))
    kv.push(0, mx.nd.ones((2, 2)))
    kv.pull(0, out=mx.nd.zeros((2, 2)))

    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}
    assert telemetry.span_aggregates() == {}
    assert list(tmp_path.iterdir()) == []  # and nothing journaled


# -- journal + report round trip -----------------------------------------------
def _write_demo_journal(monkeypatch, journal):
    _enable(monkeypatch, journal=journal)
    with telemetry.span("epoch"):
        for _ in range(3):
            with telemetry.span("batch"):
                pass
    telemetry.counter("engine.push_total").inc(7)
    telemetry.gauge("train.samples_per_sec").set(1000.0)
    for v in range(100):
        telemetry.histogram("train.step_secs").observe(0.01 * (v + 1))
    telemetry.flush(mark="t0")
    telemetry.gauge("train.samples_per_sec").set(4000.0)
    telemetry.flush(mark="t1")


def test_journal_roundtrip_through_report(monkeypatch, tmp_path):
    journal = tmp_path / "run.jsonl"
    _write_demo_journal(monkeypatch, journal)
    records = telemetry_report.load(str(journal))
    spans = [r for r in records if r["kind"] == "span"]
    assert {s["name"] for s in spans} == {"epoch", "batch"}
    epoch_id = [s for s in spans if s["name"] == "epoch"][0]["id"]
    assert all(s["parent"] == epoch_id
               for s in spans if s["name"] == "batch")

    # the report renders a throughput timeline, top spans, percentiles
    report = telemetry_report.render_report(records)
    assert "throughput timeline" in report
    assert "1000.00" in report and "4000.00" in report
    assert "top spans by total time" in report
    assert "batch" in report and "epoch" in report
    assert "percentile tables" in report
    assert "train.step_secs" in report
    # p50 over 0.01..1.00 is ~0.505; check the row carries real numbers
    final = telemetry_report.final_metrics(records)
    assert final["histograms"]["train.step_secs"]["p50"] == pytest.approx(
        np.percentile(0.01 * np.arange(1, 101), 50))
    assert final["counters"]["engine.push_total"] == 7


def test_report_cli_subprocess(monkeypatch, tmp_path):
    journal = tmp_path / "run.jsonl"
    _write_demo_journal(monkeypatch, journal)
    env = dict(os.environ)
    env.pop("MXNET_TELEMETRY", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(journal)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "top spans by total time" in proc.stdout
    assert "percentile tables" in proc.stdout


def test_unwritable_journal_disables_journaling(monkeypatch, tmp_path):
    """An unwritable journal path must disable journaling (not buffer
    spans in memory forever waiting for a flusher that never starts)."""
    blocker = tmp_path / "file"
    blocker.write_text("x")  # a file where the journal's DIRECTORY goes
    journal = blocker / "sub" / "run.jsonl"
    _enable(monkeypatch, journal=journal)
    from mxnet_tpu.telemetry import export
    for _ in range(10):
        with telemetry.span("s"):
            pass
    assert telemetry.journal_path() is None  # gave up on first record
    assert export._buffer == []              # and dropped the backlog
    assert telemetry.ENABLED  # metrics stay available in-process
    telemetry.flush(mark="x")  # and flushing is a safe no-op


def test_journal_tolerates_torn_tail(monkeypatch, tmp_path):
    journal = tmp_path / "run.jsonl"
    _write_demo_journal(monkeypatch, journal)
    with open(journal, "a") as f:
        f.write('{"kind": "span", "name": "torn')  # killed mid-write
    records = telemetry_report.load(str(journal))
    assert all(r["name"] != "torn" for r in records if r["kind"] == "span")
    assert telemetry_report.render_report(records)


def test_prometheus_text_and_console_summary(monkeypatch):
    _enable(monkeypatch)
    telemetry.counter("engine.push_total").inc(3)
    telemetry.gauge("io.prefetch_queue_depth").set(2)
    telemetry.histogram("engine.task_secs").observe(0.5)
    with telemetry.span("epoch"):
        pass
    prom = telemetry.prometheus_text()
    assert "# TYPE mxtpu_engine_push_total counter" in prom
    assert "mxtpu_engine_push_total 3" in prom
    assert "# TYPE mxtpu_io_prefetch_queue_depth gauge" in prom
    assert 'mxtpu_engine_task_secs{quantile="0.5"}' in prom
    assert "mxtpu_engine_task_secs_count 1" in prom
    summary = telemetry.console_summary()
    assert "engine.push_total" in summary
    assert "top spans by total time" in summary and "epoch" in summary


# -- layer instrumentation (enabled) -------------------------------------------
def test_engine_metrics_enabled(monkeypatch):
    _enable(monkeypatch)
    from mxnet_tpu import engine
    eng = engine.get()
    before = telemetry.counter("engine.push_total").value
    eng.push(lambda: None)
    eng.wait_for_all()
    snap = telemetry.snapshot()
    assert snap["counters"]["engine.push_total"] == before + 1
    assert snap["counters"]["engine.waits_total"] >= 1
    assert snap["histograms"]["engine.task_secs"]["count"] >= 1


def test_kvstore_metrics_enabled(monkeypatch):
    _enable(monkeypatch)
    kv = mx.kvstore.create("local")
    kv.init(3, mx.nd.zeros((4, 4)))
    kv.push(3, mx.nd.ones((4, 4)))
    kv.pull(3, out=mx.nd.zeros((4, 4)))
    snap = telemetry.snapshot()
    assert snap["counters"]["kvstore.push_total"] == 1
    assert snap["counters"]["kvstore.push_bytes_total"] == 4 * 4 * 4
    assert snap["counters"]["kvstore.pull_bytes_total"] == 4 * 4 * 4


def test_io_and_recordio_metrics_enabled(monkeypatch, tmp_path):
    _enable(monkeypatch)
    X = np.random.RandomState(0).rand(16, 4).astype("f")
    it = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=8)
    it.next()
    snap = telemetry.snapshot()
    assert snap["histograms"]["io.batch_fetch_secs"]["count"] >= 1

    # corrupt-skip resyncs feed io.records_skipped_total
    from mxnet_tpu import recordio
    path = str(tmp_path / "x.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(4):
        w.write(b"payload-%d" % i)
    w.close()
    raw = bytearray(open(path, "rb").read())
    raw[5] ^= 0xFF  # flip a byte in record 0's framing
    open(path, "wb").write(bytes(raw))
    r = recordio.MXRecordIO(path, "r", corrupt="skip")
    while r.read() is not None:
        pass
    assert r.num_skipped >= 1
    assert telemetry.counter("io.records_skipped_total").value \
        == r.num_skipped


def test_retry_counter_enabled(monkeypatch):
    _enable(monkeypatch)
    from mxnet_tpu.resilience.retry import RetryPolicy
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return "ok"

    policy = RetryPolicy(max_attempts=5, base_delay=0.0, jitter=0.0,
                         sleep=lambda _s: None, seed=0)
    assert policy.call(flaky) == "ok"
    assert telemetry.counter("retry.retries_total").value == 2


def test_fault_fire_counters_enabled(monkeypatch):
    _enable(monkeypatch)
    from mxnet_tpu.resilience import faults
    faults.inject("ckpt.write:error:count=1")
    with pytest.raises(faults.FaultInjected):
        faults.point("ckpt.write")
    faults.point("ckpt.write")  # count exhausted: no fire, no count
    snap = telemetry.snapshot()
    assert snap["counters"]["faults.fired_total"] == 1
    assert snap["counters"]["faults.fired.ckpt.write"] == 1


def test_speedometer_zero_elapsed_interval(monkeypatch, caplog):
    """Two ticks inside one clock quantum must not ZeroDivisionError
    (satellite: fast synthetic iterators)."""
    import logging

    from mxnet_tpu.model import BatchEndParam
    sp = mx.callback.Speedometer(batch_size=4, frequent=2)
    fake_now = [1000.0]
    monkeypatch.setattr("mxnet_tpu.callback.time",
                        type("T", (), {"time": staticmethod(
                            lambda: fake_now[0])}))
    sp(BatchEndParam(epoch=0, nbatch=1, eval_metric=None, locals=None))
    # elapsed == 0.0: no speed line, no ZeroDivisionError
    with caplog.at_level(logging.INFO):
        sp(BatchEndParam(epoch=0, nbatch=2, eval_metric=None, locals=None))
    assert "samples/sec" not in caplog.text
    fake_now[0] += 0.5
    with caplog.at_level(logging.INFO):
        sp(BatchEndParam(epoch=0, nbatch=4, eval_metric=None, locals=None))
    assert "samples/sec" in caplog.text  # measurable interval reports


def test_speedometer_reports_speed_gauge(monkeypatch):
    _enable(monkeypatch)
    from mxnet_tpu.model import BatchEndParam
    sp = mx.callback.Speedometer(batch_size=10, frequent=1)
    fake_now = [1000.0]
    monkeypatch.setattr("mxnet_tpu.callback.time",
                        type("T", (), {"time": staticmethod(
                            lambda: fake_now[0])}))
    sp(BatchEndParam(epoch=0, nbatch=0, eval_metric=None, locals=None))
    fake_now[0] += 2.0
    sp(BatchEndParam(epoch=0, nbatch=1, eval_metric=None, locals=None))
    # 1 batch * 10 samples / 2s = 5 samples/sec
    assert telemetry.gauge("train.samples_per_sec").value \
        == pytest.approx(5.0)


# -- acceptance: FeedForward.fit smoke journal ---------------------------------
def _fit_mlp(tmp_path):
    rng = np.random.RandomState(3)
    X = rng.rand(64, 8).astype("f")
    Y = (X[:, 0] > 0.5).astype("f")
    train = mx.io.NDArrayIter(X, Y, batch_size=16)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc")
    sym = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    model = mx.FeedForward(sym, ctx=mx.cpu(), num_epoch=2,
                           learning_rate=0.1)
    # an explicit KVStore instance forces the per-batch loop through
    # kvstore push/pull; do_checkpoint exercises the engine's async
    # checkpoint push
    kv = mx.kvstore.create("local")
    model.fit(X=train, kvstore=kv,
              epoch_end_callback=mx.callback.do_checkpoint(
                  str(tmp_path / "ckpt")))


def test_fit_smoke_produces_full_journal(monkeypatch, tmp_path):
    """ISSUE acceptance: one FeedForward.fit run with MXNET_TELEMETRY=1
    journals engine, kvstore, io and executor metrics plus nested
    epoch/batch spans, and the report tool renders percentile tables
    and top spans from it."""
    journal = tmp_path / "fit.jsonl"
    _enable(monkeypatch, journal=journal)
    _fit_mlp(tmp_path)
    telemetry.flush(mark="final")

    records = telemetry_report.load(str(journal))
    final = telemetry_report.final_metrics(records)
    counters, hists = final["counters"], final["histograms"]
    # every runtime layer reported in
    assert counters["engine.push_total"] >= 1          # async checkpoints
    assert counters["engine.waits_total"] >= 1         # end-of-fit fence
    assert counters["kvstore.push_total"] >= 2         # per batch+key
    assert counters["kvstore.push_bytes_total"] > 0
    assert counters["kvstore.pull_bytes_total"] > 0
    assert hists["io.batch_fetch_secs"]["count"] >= 8  # 4 batches x 2 epochs
    assert hists["executor.forward_secs"]["count"] >= 8
    assert hists["executor.backward_secs"]["count"] >= 8
    assert hists["train.step_secs"]["count"] >= 8
    assert final["gauges"]["train.samples_per_sec"] > 0

    # nested epoch/batch spans: every batch span hangs off an epoch span
    spans = [r for r in records if r["kind"] == "span"]
    epochs = {s["id"] for s in spans if s["name"] == "epoch"}
    batches = [s for s in spans if s["name"] == "batch"]
    assert len(epochs) == 2 and len(batches) >= 8
    assert all(b["parent"] in epochs for b in batches)

    report = telemetry_report.render_report(records)
    assert "top spans by total time" in report
    assert "epoch" in report and "batch" in report
    assert "percentile tables" in report
    assert "executor.forward_secs" in report
    assert "train.step_secs" in report


def test_fit_disabled_writes_no_journal(tmp_path, monkeypatch):
    """ISSUE acceptance (flip side): default-off fit leaves no journal
    and registers no metrics."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    monkeypatch.delenv("MXNET_TELEMETRY_JOURNAL", raising=False)
    telemetry.reset()
    telemetry.reload()
    _fit_mlp(tmp_path)
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}
    assert not [p for p in tmp_path.iterdir()
                if p.suffix == ".jsonl"]


def test_conftest_fixture_contract():
    """The suite fixture must leave each test a clean slate: this test
    registers state; its teardown (plus every other test's) relies on
    telemetry.reset() + reload() — verify reset really drops both
    metric and span state."""
    telemetry.counter("leak.check").inc()
    telemetry.reset()
    assert telemetry.snapshot()["counters"] == {}
    assert telemetry.span_aggregates() == {}
