"""mxprof tests (ISSUE 13): continuous performance & memory attribution.

The load-bearing acceptance properties:

- **off by default, zero overhead**: with ``MXNET_PROF`` unset a fit
  registers no ``prof.*`` metrics, attributes no programs and emits no
  ``prof`` journal records;
- **analytic-vs-XLA agreement**: the jax-free Symbol-DAG cost model
  (``prof.graph_cost``) and XLA's ``cost_analysis()`` agree within a
  small band on the model zoo's forward programs;
- **step-breakdown schema**: ``prof.step_breakdown`` journal records
  carry path / phases / boundedness, and the ``prof.*`` histograms
  land in the registry;
- **`/profilez` round-trip**: scraped MID-``FeedForward.fit`` the
  endpoint serves per-program cost/memory attribution and derived
  MFU/roofline fields;
- satellites: real Prometheus histogram families on ``/metrics``,
  ``tracez:<span>:p99`` metrics for mxctl rules (colon-safe rule
  parsing), merged per-rank prof rows, report-tool profiling section.
"""
import json
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import prof


def _enable(monkeypatch, journal=None, http=None, prof_on=True):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    if prof_on:
        monkeypatch.setenv("MXNET_PROF", "1")
    else:
        monkeypatch.delenv("MXNET_PROF", raising=False)
    if journal is not None:
        monkeypatch.setenv("MXNET_TELEMETRY_JOURNAL", str(journal))
    else:
        monkeypatch.delenv("MXNET_TELEMETRY_JOURNAL", raising=False)
    if http is not None:
        monkeypatch.setenv("MXNET_TELEMETRY_HTTP", str(http))
    else:
        monkeypatch.delenv("MXNET_TELEMETRY_HTTP", raising=False)
    telemetry.reset()
    telemetry.reload()


def _mlp_sym():
    net = mx.sym.Variable("data")
    net = mx.sym.Activation(mx.sym.FullyConnected(
        data=net, num_hidden=16, name="fc1"), act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        data=net, num_hidden=2, name="fc2"), name="softmax")


def _fit(num_epoch=2, batch=16, n=96, d=8):
    rng = np.random.RandomState(3)
    X = rng.rand(n, d).astype("f")
    Y = (X[:, 0] > 0.5).astype("f")
    train = mx.io.NDArrayIter(X, Y, batch_size=batch)
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=num_epoch,
                           learning_rate=0.1)
    return model, train


def _journal_lines(path):
    telemetry.flush()
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# -- off-by-default guards -----------------------------------------------------
class TestOffByDefault:
    def test_disabled_without_env(self, monkeypatch):
        monkeypatch.delenv("MXNET_PROF", raising=False)
        telemetry.reload()
        assert prof.ENABLED is False
        assert prof.snapshot()["enabled"] is False

    def test_fit_adds_no_prof_work(self, monkeypatch, tmp_path):
        """MXNET_PROF unset: a full fit attributes nothing — no prof.*
        metrics, no program records, no prof journal records (the
        zero-instrumentation acceptance guard)."""
        journal = tmp_path / "run.jsonl"
        _enable(monkeypatch, journal=journal, prof_on=False)
        model, train = _fit()
        model.fit(X=train, kvstore=None)
        snap = telemetry.snapshot()
        assert not any(k.startswith("prof.") for k in snap["histograms"])
        assert not any(k.startswith("prof.") for k in snap["gauges"])
        assert prof.program_records() == []
        assert prof.step_summary() == {}
        recs = _journal_lines(journal)
        assert not any(r.get("kind") == "prof" for r in recs)

    def test_note_step_noop_when_off(self, monkeypatch):
        monkeypatch.delenv("MXNET_PROF", raising=False)
        telemetry.reload()
        assert prof.note_step("x", {"host": 1.0}) is None
        assert prof.step_summary() == {}


def test_unlisted_device_kind_has_no_peak(monkeypatch):
    """Peaks come from prof.PEAKS keyed by device_kind: the CPU is not
    in it, so no MFU/roofline is derived against another chip's peak;
    the env overrides still name one."""
    monkeypatch.delenv("MXNET_PROF_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_PROF_HBM_GBPS", raising=False)
    assert prof.PEAKS["TPU v5 lite"] == (197e12, 819.0)
    assert prof.peak_flops() is None and prof.hbm_gbps() is None
    monkeypatch.setitem(prof._programs, "k", {
        "calls": 2, "device_secs": 1.0, "flops": 1e9,
        "bytes_accessed": 1e6})
    d = prof.derived()
    assert d["tflops"] == pytest.approx(2e-3)
    assert d["mfu"] is None and d["roofline_pct"] is None
    monkeypatch.setenv("MXNET_PROF_PEAK_FLOPS", "2e10")
    monkeypatch.setenv("MXNET_PROF_HBM_GBPS", "1")
    d = prof.derived()
    assert d["mfu"] == pytest.approx(0.1)
    assert d["roofline_pct"] == pytest.approx(0.2)


# -- analytic cost model -------------------------------------------------------
class TestGraphCost:
    def test_mlp_flops_exact(self):
        gc = prof.graph_cost(_mlp_sym(), {"data": (32, 8),
                                          "softmax_label": (32,)})
        by_name = {r["name"]: r for r in gc["nodes"]}
        assert by_name["fc1"]["flops"] == 2 * 32 * 16 * 8
        assert by_name["fc2"]["flops"] == 2 * 32 * 2 * 16
        assert gc["flops_train"] == 3 * gc["flops"]
        assert gc["unresolved"] == 0
        # weight footprint: fc1 (8x16 + 16) + fc2 (16x2 + 2) floats
        assert gc["params_bytes"] == 4 * (8 * 16 + 16 + 16 * 2 + 2)

    def test_conv_flops(self):
        from mxnet_tpu.models import get_lenet

        sym = get_lenet()
        gc = prof.graph_cost(sym, {"data": (4, 1, 28, 28),
                                   "softmax_label": (4,)})
        convs = [r for r in gc["nodes"] if r["op"] == "Convolution"]
        assert len(convs) >= 2
        # first conv: out 4x20x24x24, 1 in-ch, 5x5 kernel
        c0 = max(convs, key=lambda r: r["flops"] if r["out_shape"][2] == 24
                 else 0)
        assert c0["flops"] == 2 * (4 * 20 * 24 * 24) * 1 * 25

    def test_same_shapes_different_graphs_not_aliased(self, monkeypatch):
        """attribute_jit's memo is keyed by GRAPH identity, not just
        shapes: two models with identical names/shapes but different op
        params (relu vs tanh) must get distinct compiled programs and
        distinct outputs (regression: the memo once handed the second
        model the first model's executable)."""
        _enable(monkeypatch)

        def build(act):
            net = mx.sym.FullyConnected(data=mx.sym.Variable("data"),
                                        num_hidden=8, name="fc1")
            return mx.sym.Activation(net, act_type=act, name="act")

        X = np.random.RandomState(0).rand(4, 8).astype("f")
        outs = {}
        for act in ("relu", "tanh"):
            exe = build(act).simple_bind(mx.cpu(), grad_req="null",
                                         data=(4, 8))
            exe.arg_dict["data"][:] = X
            exe.arg_dict["fc1_weight"][:] = np.ones((8, 8), "f") * 0.1
            exe.arg_dict["fc1_bias"][:] = 0.0
            exe.forward(is_train=False)
            outs[act] = exe.outputs[0].asnumpy()
        assert not np.allclose(outs["relu"], outs["tanh"])
        keys = [r["key"] for r in prof.program_records()]
        assert len(set(keys)) == 2
        assert prof.symbol_fingerprint(build("relu")) != \
            prof.symbol_fingerprint(build("tanh"))
        # identical graphs DO share one record (that is the point of
        # the memo: one program, one entry)
        assert prof.symbol_fingerprint(build("relu")) == \
            prof.symbol_fingerprint(build("relu"))

    @pytest.mark.parametrize("zoo", ["mlp", "lenet"])
    def test_analytic_vs_xla_agreement(self, monkeypatch, zoo):
        """The analytic forward FLOPs and XLA's cost_analysis agree
        within a 3x band on the zoo's inference programs (same 2·M·N·K
        counting for the matmul/conv bulk; the band absorbs XLA's
        elementwise bookkeeping differences)."""
        _enable(monkeypatch)
        if zoo == "mlp":
            from mxnet_tpu.models import get_mlp

            sym = get_mlp()
            shapes = {"data": (16, 64), "softmax_label": (16,)}
        else:
            from mxnet_tpu.models import get_lenet

            sym = get_lenet()
            shapes = {"data": (4, 1, 28, 28), "softmax_label": (4,)}
        exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
        exe.forward(is_train=False)
        recs = [r for r in prof.program_records()
                if r["site"] == "executor.fwd_infer"]
        assert recs, "inference program was not attributed"
        rec = recs[0]
        assert rec.get("flops"), "XLA cost analysis carried no flops"
        analytic = rec["analytic"]["flops"]
        ratio = rec["flops"] / analytic
        assert 1 / 3 <= ratio <= 3, (
            "analytic %s vs XLA %s (ratio %.3f) out of band"
            % (analytic, rec["flops"], ratio))
        # memory analysis: a real static footprint
        assert rec["memory"]["static_peak"] > 0


# -- step breakdown + journal schema ------------------------------------------
class TestStepBreakdown:
    def test_scanned_fit_records(self, monkeypatch, tmp_path):
        journal = tmp_path / "run.jsonl"
        # the CPU has no row in prof.PEAKS: name a peak, or no MFU gauge
        monkeypatch.setenv("MXNET_PROF_PEAK_FLOPS", "1.97e14")
        _enable(monkeypatch, journal=journal)
        model, train = _fit()
        model.fit(X=train, kvstore=None)
        recs = _journal_lines(journal)
        steps = [r for r in recs if r.get("kind") == "prof"
                 and r.get("event") == "step_breakdown"]
        assert steps, "no step_breakdown records in the journal"
        for r in steps:
            assert r["path"] == "train.scanned"
            assert set(r["phases"]) == {"host", "dispatch", "device", "d2h"}
            assert all(v >= 0 for v in r["phases"].values())
            assert r["total_s"] == pytest.approx(
                sum(r["phases"].values()))
            assert r["bound"] in ("input", "compute", "host")
            assert r["batches"] >= 1
            assert r["key"].startswith("v1|")  # the jit-cache config key
        progs = [r for r in recs if r.get("kind") == "prof"
                 and r.get("event") == "program"]
        assert any(p["site"] == "fit_trainer.scan" for p in progs)
        # histograms landed
        hists = telemetry.snapshot()["histograms"]
        assert "prof.step_secs" in hists
        assert "prof.step.host_secs" in hists
        # derived gauges refreshed
        gauges = telemetry.snapshot()["gauges"]
        assert "prof.mfu" in gauges and gauges["prof.mfu"] > 0
        # device-time accounting reached the program record
        rec = next(r for r in prof.program_records()
                   if r["site"] == "fit_trainer.scan")
        assert rec["calls"] == len(steps)

    def test_per_batch_path_records(self, monkeypatch):
        """MXNET_SCAN_TRAIN=0 forces the per-batch loop — its records
        carry the update phase the scanned path doesn't have."""
        monkeypatch.setenv("MXNET_SCAN_TRAIN", "0")
        _enable(monkeypatch)
        model, train = _fit(num_epoch=1)
        model.fit(X=train, kvstore=None)
        summary = prof.step_summary()
        assert "train.batch" in summary
        st = summary["train.batch"]
        assert st["count"] >= 1
        assert {"host", "dispatch", "update", "d2h"} <= set(st["phases_s"])
        assert st["bound"] in ("input", "compute", "host")
        # executor programs attributed on this path
        assert any(r["site"].startswith("executor.")
                   for r in prof.program_records())

    def test_serving_step_records(self, monkeypatch):
        import jax

        from mxnet_tpu.models.transformer import (TransformerConfig,
                                                  init_params)
        from mxnet_tpu.serving import PagedKVPool
        from mxnet_tpu.serving.model import ServingModel

        _enable(monkeypatch)
        cfg = TransformerConfig(vocab_size=31, num_layers=1, d_model=16,
                                num_heads=2, d_ff=32, max_seq_len=64,
                                dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        pool = PagedKVPool(cfg.num_layers, cfg.num_heads,
                           cfg.d_model // cfg.num_heads, num_blocks=9,
                           block_size=4)
        m = ServingModel(cfg, block_size=4, max_blocks_per_req=4,
                         batch_buckets=(2,), chunk_buckets=(8,))
        bt = np.zeros((1, 4), np.int32)
        bt[0] = [1, 2, 3, 4]
        # first step carries the attribution compile and is deliberately
        # NOT recorded as a breakdown; the second is steady state. The
        # pools are donated — thread the returned kp/vp through, as the
        # engine's pool.swap does
        kp, vp = pool.k, pool.v
        for _ in range(2):
            nxt, kp, vp = m.step(
                params, kp, vp, np.asarray([[1, 2, 3]], np.int32),
                np.zeros((1,), np.int32), np.asarray([3], np.int32), bt,
                np.ones((1,), bool))
        summary = prof.step_summary()
        assert "serve.prefill" in summary
        assert summary["serve.prefill"]["count"] == 1  # compile step skipped
        recs = [r for r in prof.program_records()
                if r["site"] == "serving.step"]
        assert recs and recs[0]["calls"] == 1
        assert recs[0]["meta"] == {"batch_bucket": 2, "chunk_bucket": 8}


# -- /profilez ----------------------------------------------------------------
class TestProfilez:
    def test_scrape_mid_fit(self, monkeypatch):
        """The acceptance scrape: during a FeedForward.fit, /profilez
        serves per-program cost/memory attribution and the derived
        MFU/roofline fields."""
        monkeypatch.setenv("MXNET_PROF_PEAK_FLOPS", "1.97e14")
        _enable(monkeypatch, http="0")
        seen = {}

        def scrape_cb(param):
            # scrape from epoch 1 on: epoch 0's chunks carry the
            # attribution compile (their breakdowns are deliberately
            # dropped), so steady-state step records exist by now
            if seen or param.epoch < 1:
                return
            port = telemetry.server.port()
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/profilez" % port, timeout=10) as r:
                seen["profilez"] = json.loads(r.read().decode())

        model, train = _fit(num_epoch=3)
        model.fit(X=train, kvstore=None, batch_end_callback=scrape_cb)
        assert seen, "callback never scraped"
        p = seen["profilez"]
        assert p["enabled"] is True
        assert p["programs"], "no programs attributed mid-fit"
        top = p["programs"][0]
        assert top["site"] == "fit_trainer.scan"
        assert top.get("flops") and top["memory"]["static_peak"] > 0
        assert top["analytic"]["flops"] > 0
        assert p["steps"]["train.scanned"]["count"] >= 1
        assert p["derived"]["peak_flops"] > 0
        assert p["derived"]["mfu"] is None or p["derived"]["mfu"] >= 0
        assert p["hbm"]["peak_bytes"] is None or p["hbm"]["peak_bytes"] > 0
        assert p["config_key"].startswith("v1|")

    def test_profilez_off_answers_disabled(self, monkeypatch):
        _enable(monkeypatch, http="0", prof_on=False)
        port = telemetry.server.port()
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/profilez" % port, timeout=10) as r:
            p = json.loads(r.read().decode())
        assert p["enabled"] is False and p["programs"] == []


# -- satellites ---------------------------------------------------------------
class TestPrometheusHistograms:
    def test_bucket_families(self, monkeypatch):
        _enable(monkeypatch, prof_on=False)
        h = telemetry.histogram("io.batch_fetch_secs")
        for v in (0.0004, 0.003, 0.003, 0.04, 2.0, 1000.0):
            h.observe(v)
        buckets = dict(h.bucket_counts())
        assert buckets[0.0005] == 1
        assert buckets[0.005] == 3
        assert buckets[0.05] == 4
        assert buckets[float("inf")] == 6  # +Inf carries the total
        text = telemetry.prometheus_text()
        assert "# TYPE mxtpu_io_batch_fetch_secs histogram" in text
        assert 'mxtpu_io_batch_fetch_secs_bucket{le="0.005"} 3' in text
        assert 'mxtpu_io_batch_fetch_secs_bucket{le="+Inf"} 6' in text
        assert "mxtpu_io_batch_fetch_secs_count 6" in text
        # backward-compat quantile gauges still present
        assert 'mxtpu_io_batch_fetch_secs{quantile="0.5"}' in text

    def test_bucket_counts_survive_ring_wrap(self, monkeypatch):
        _enable(monkeypatch, prof_on=False)
        from mxnet_tpu.telemetry.registry import Histogram

        h = Histogram("x.y", capacity=4)
        for _ in range(100):
            h.observe(0.01)
        assert dict(h.bucket_counts())[float("inf")] == 100


class TestTracezRules:
    def test_colon_metric_rule_parses(self):
        from mxnet_tpu.control.rules import parse_rules

        (r,) = parse_rules(
            "tracez:elastic.rpc.pull:p99>0.5:for=3:"
            "action=restart_replica:cooldown=15")
        assert r.metric == "tracez:elastic.rpc.pull:p99"
        assert r.op == ">" and r.threshold == 0.5
        assert r.for_count == 3 and r.cooldown == 15.0
        # plain rules and malformed rules behave as before
        (r2,) = parse_rules("alive<1:for=3:action=x")
        assert r2.metric == "alive"
        from mxnet_tpu.control.rules import RuleSyntaxError

        with pytest.raises(RuleSyntaxError):
            parse_rules("tracez:elastic.rpc.pull:p99:for=1:action=x")

    def test_tracez_metrics_mapping(self):
        from mxnet_tpu.control.probes import tracez_metrics

        payload = {"recent": [
            {"name": "elastic.rpc.pull", "dur": d / 100.0}
            for d in range(100)
        ] + [{"name": "serve.decode", "dur": 0.004}]}
        m = tracez_metrics(payload)
        assert m["tracez:elastic.rpc.pull:count"] == 100.0
        assert m["tracez:elastic.rpc.pull:p50"] == pytest.approx(0.495)
        assert m["tracez:elastic.rpc.pull:p99"] == pytest.approx(0.9801)
        assert m["tracez:serve.decode:p99"] == pytest.approx(0.004)
        assert tracez_metrics(None) == {}

    def test_rule_fires_on_tracez_metric(self):
        """A /tracez-derived latency percentile drives a rule through
        the hysteresis machine exactly like an engine-local metric (the
        mxctl follow-up from the PR 12 sketch)."""
        from mxnet_tpu.control.probes import tracez_metrics
        from mxnet_tpu.control.rules import RuleEngine, parse_rules

        eng = RuleEngine(parse_rules(
            "tracez:elastic.rpc.pull:p99>0.1:for=2:action=restart_replica"))
        sample = tracez_metrics({"recent": [
            {"name": "elastic.rpc.pull", "dur": 0.5}] * 10})
        assert eng.evaluate("r0", sample, now=0.0) == []   # streak 1
        (dec,) = eng.evaluate("r0", sample, now=1.0)       # fires at 2
        assert dec.rule.action == "restart_replica"
        assert dec.value == pytest.approx(0.5)

    def test_live_probe_carries_tracez_metrics(self, monkeypatch):
        """HttpProbe against a live mxdash server picks up the span
        percentiles under the tracez: namespace."""
        from mxnet_tpu.control.probes import HttpProbe

        _enable(monkeypatch, http="0", prof_on=False)
        with telemetry.span("elastic.rpc.pull"):
            pass
        url = "http://127.0.0.1:%d" % telemetry.server.port()
        s = HttpProbe("r0", url, tracez=True).sample()
        assert s.metrics["alive"] == 1.0
        assert "tracez:elastic.rpc.pull:p99" in s.metrics
        # tracez scraping is opt-in: the default probe skips the fetch
        s2 = HttpProbe("r0", url).sample()
        assert not any(k.startswith("tracez:") for k in s2.metrics)


class TestMergeAndReport:
    def _write_journal(self, path, rank, bound_phase):
        phases = {"host": 0.001, "dispatch": 0.002, "device": 0.001,
                  "d2h": 0.001}
        phases[bound_phase] = 0.05
        with open(path, "w", encoding="utf-8") as f:
            f.write(json.dumps({"kind": "meta", "t": 0.0, "pid": rank,
                                "rank": rank, "world": 2}) + "\n")
            for i in range(3):
                f.write(json.dumps({
                    "kind": "prof", "event": "step_breakdown",
                    "t": float(i), "path": "train.scanned", "batches": 8,
                    "total_s": sum(phases.values()),
                    "phases": phases,
                    "bound": {"host": "input", "device": "compute"}[
                        bound_phase]}) + "\n")

    def test_prof_rows_cross_rank(self, tmp_path):
        from mxnet_tpu.telemetry import merge as m

        j0, j1 = tmp_path / "r0.jsonl", tmp_path / "r1.jsonl"
        self._write_journal(j0, 0, "host")
        self._write_journal(j1, 1, "device")
        merged = m.merge([str(j0), str(j1)])
        rows = m.prof_rows(merged)
        assert [r["rank"] for r in rows] == [0, 1]
        assert rows[0]["bound"] == "input"
        assert rows[1]["bound"] == "compute"
        assert rows[0]["phase_share"]["host"] > 0.8
        summary = "\n".join(m.render_summary(merged))
        assert "per-rank step decomposition (mxprof)" in summary

    def test_report_profiling_section(self, monkeypatch, tmp_path,
                                      capsys):
        """telemetry_report renders the profiling section from a real
        prof journal: breakdown table, top programs, derived line."""
        journal = tmp_path / "run.jsonl"
        _enable(monkeypatch, journal=journal)
        model, train = _fit()
        model.fit(X=train, kvstore=None)
        telemetry.flush(mark="exit")
        import telemetry_report

        rc = telemetry_report.main([str(journal)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "-- profiling (mxprof) --" in out
        assert "train.scanned" in out
        assert "fit_trainer.scan" in out
        assert "top programs by device time" in out
