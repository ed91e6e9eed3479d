"""Compile-layer tests: rewrite passes, golden equivalence across the
model zoo, the measure-and-cache autotuner, and the persistent jit
cache (docs/how_to/compilation.md).

Equivalence discipline follows the pass contracts: fuse/fold rewrites
must be BIT-IDENTICAL to the unrewritten graph (same jnp calls, same
order); layout/precision rewrites are tolerance-bounded (reduction
order and accumulation dtype legitimately change). Off-by-default
zero-overhead guards match the guardian/telemetry test style.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.compile as mxc
from mxnet_tpu.compile import autotune, fold, fuse, ir, jit_cache, pipeline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _reset_compile():
    """Compile-layer isolation: pytest restores monkeypatched
    MXNET_COMPILE_* before this teardown (same ordering contract as
    conftest._reset_telemetry); re-read them so one test's config never
    leaks into the next."""
    yield
    mxc.reload()


@pytest.fixture()
def compile_on(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_OPT", "1")
    mxc.reload()
    yield


@pytest.fixture()
def jit_cache_isolated(monkeypatch):
    """The suite runs with the persistent cache off (conftest.py); let a
    test place one, and undo the process-global jax config after it.
    jax decides at a process's first compile whether it caches, so the
    decision is reset on both sides of the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cc.reset_cache()
    yield
    import jax

    jax.config.update("jax_compilation_cache_dir", None)
    jit_cache._ensured_dir = None
    cc.reset_cache()


def _chain_sym():
    """data -> (+1) -> relu -> (*2) ... a 3-op fusible chain."""
    data = mx.sym.Variable("data")
    s = data + 1.0
    s = mx.sym.Activation(data=s, act_type="relu")
    s = s * 2.0
    return s


def _conv_sym():
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data=data, num_filter=8, kernel=(3, 3),
                            pad=(1, 1), name="c1")
    bn = mx.sym.BatchNorm(data=c1, name="bn")
    act = mx.sym.Activation(data=bn, act_type="relu")
    c2 = mx.sym.Convolution(data=act, num_filter=8, kernel=(3, 3),
                            pad=(1, 1), name="c2")
    s = mx.sym.Activation(data=c2 + c1, act_type="relu")
    p = mx.sym.Pooling(data=s, kernel=(2, 2), stride=(2, 2),
                       pool_type="max")
    fc = mx.sym.FullyConnected(data=mx.sym.Flatten(data=p), num_hidden=10,
                               name="fc")
    return mx.sym.SoftmaxOutput(data=fc, name="softmax")


# -- IR walk -------------------------------------------------------------------

def test_find_fusible_chains_linear():
    chains = ir.find_fusible_chains(_chain_sym())
    assert len(chains) == 1
    assert [n.op.name for n in chains[0]] == [
        "_plus_scalar", "Activation", "_mul_scalar"]


def test_chain_breaks_at_multi_consumer():
    data = mx.sym.Variable("data")
    a = data + 1.0
    out = mx.sym.Group([a * 2.0, a * 3.0])  # a has two consumers
    chains = ir.find_fusible_chains(out)
    assert chains == []


def test_chain_excludes_heads_interior():
    data = mx.sym.Variable("data")
    a = data + 1.0
    b = mx.sym.Activation(data=a, act_type="relu")
    out = mx.sym.Group([a, b])  # a is itself a head
    assert ir.find_fusible_chains(out) == []


def test_elementwise_classification():
    data = mx.sym.Variable("data")
    relu = mx.sym.Activation(data=data, act_type="relu")
    conv = mx.sym.Convolution(data=data, num_filter=4, kernel=(3, 3))
    drop = mx.sym.Dropout(data=data, p=0.5)
    assert ir.is_elementwise(relu._outputs[0][0])
    assert not ir.is_elementwise(conv._outputs[0][0])   # custom shape
    assert not ir.is_elementwise(drop._outputs[0][0])   # needs RNG


# -- fuse pass -----------------------------------------------------------------

def test_fuse_bit_identical():
    sym = _chain_sym()
    new, n = fuse.apply(sym)
    assert n == 1
    ops = [nd.op.name for nd in new.nodes if not nd.is_variable]
    assert len(ops) == 1 and ops[0].startswith(fuse.FUSED_OP_PREFIX)
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0).rand(4, 6).astype(np.float32))
    (ref,) = pipeline._eval_graph(sym, {"data": x})
    (opt,) = pipeline._eval_graph(new, {"data": x})
    assert np.array_equal(np.asarray(ref), np.asarray(opt))


def test_fuse_binary_op_external_input():
    data = mx.sym.Variable("data")
    other = mx.sym.Variable("other")
    s = mx.sym.Activation(data=data + other, act_type="relu") * 0.5
    new, n = fuse.apply(s)
    assert n == 1
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    vals = {"data": jnp.asarray(rng.rand(3, 5).astype(np.float32) - 0.5),
            "other": jnp.asarray(rng.rand(3, 5).astype(np.float32) - 0.5)}
    (ref,) = pipeline._eval_graph(s, vals)
    (opt,) = pipeline._eval_graph(new, vals)
    assert np.array_equal(np.asarray(ref), np.asarray(opt))


# -- fold pass -----------------------------------------------------------------

def test_fold_frozen_params():
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    out = data * ((w + 1.0) * 0.5)
    wv = np.arange(6, dtype=np.float32).reshape(2, 3)
    new, n = fold.apply(out, frozen_params={"w": wv})
    assert n == 1
    assert "w" not in new.list_arguments()
    assert any((not nd.is_variable) and nd.op.name == fold.CONST_OP
               for nd in new.nodes)
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(2).rand(2, 3).astype(np.float32))
    (ref,) = pipeline._eval_graph(out, {"data": x, "w": jnp.asarray(wv)})
    (opt,) = pipeline._eval_graph(new, {"data": x})
    assert np.array_equal(np.asarray(ref), np.asarray(opt))


def test_fold_training_executor_never_bakes_weights(compile_on):
    """The training bind has no frozen params: every weight stays a
    live argument (the optimizer mutates them in place)."""
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    out = data * (w + 1.0)
    exe = out.bind(mx.cpu(), {"data": mx.nd.ones((2, 2)),
                              "w": mx.nd.ones((2, 2))})
    assert "w" in exe._exec_symbol.list_arguments()
    assert not any((not nd.is_variable) and nd.op.name == fold.CONST_OP
                   for nd in exe._exec_symbol.nodes)


def test_predictor_folds_param_subexpression(compile_on):
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    out = mx.sym.FullyConnected(data=data, weight=w * 2.0, no_bias=True,
                                num_hidden=4, name="fc")
    rng = np.random.RandomState(3)
    wv = rng.rand(4, 8).astype(np.float32)
    params = {"arg:w": mx.nd.array(wv)}
    from mxnet_tpu.predictor import Predictor

    pred = Predictor(out.tojson(), params, ctx=mx.cpu(),
                     input_shapes={"data": (2, 8)})
    x = rng.rand(2, 8).astype(np.float32)
    pred.forward(data=x)
    got = pred.get_output(0)
    assert mxc.last_report().get("fold", 0) >= 1
    assert np.allclose(got, x @ (wv * 2.0).T, rtol=1e-5, atol=1e-5)


# -- layout pass ---------------------------------------------------------------

def _run_exe(sym, shapes, seed=3):
    mx.random.seed(0)
    rng = np.random.RandomState(seed)
    exe = sym.simple_bind(mx.cpu(), grad_req="write", **shapes)
    for name, arr in exe.arg_dict.items():
        if name in shapes:
            if "label" in name:
                arr[:] = rng.randint(0, 9, arr.shape).astype(np.float32)
            else:
                arr[:] = rng.rand(*arr.shape).astype(np.float32)
        else:
            arr[:] = rng.normal(0, 0.05, arr.shape).astype(np.float32)
    outs = [o.asnumpy() for o in exe.forward(is_train=True)]
    exe.backward()
    grads = {n: g.asnumpy() for n, g in exe.grad_dict.items()
             if g is not None}
    return outs, grads


def test_layout_transposes_hoisted(compile_on, monkeypatch):
    """One region over the conv trunk: exactly one NCHW->NHWC at the
    data input and one NHWC->NCHW before Flatten — no interior
    transposes (the hoisting)."""
    monkeypatch.setenv("MXNET_COMPILE_PASSES", "layout")
    mxc.reload()
    sym = _conv_sym()
    exe = sym.simple_bind(mx.cpu(), data=(2, 3, 8, 8), softmax_label=(2,))
    from mxnet_tpu.compile import layout as L

    names = [nd.op.name for nd in exe._exec_symbol.nodes
             if not nd.is_variable]
    assert names.count(L.TO_NHWC) == 1
    assert names.count(L.TO_NCHW) == 1
    assert names.count(L.CONV_NHWC) == 2
    assert names.count(L.BN_NHWC) == 1
    assert names.count(L.POOL_NHWC) == 1


@pytest.mark.parametrize("name", ["mlp", "lenet", "resnet_small"])
def test_golden_equivalence_model_zoo(name, monkeypatch):
    """Outputs and gradients of the rewritten graph match the
    unrewritten one across the model zoo — exact when only fuse/fold
    applied, tolerance-bounded when layout rewrites reductions."""
    from mxnet_tpu import models

    sym, shapes = {
        "mlp": (models.get_mlp(), {"data": (8, 784), "softmax_label": (8,)}),
        "lenet": (models.get_lenet(),
                  {"data": (4, 1, 28, 28), "softmax_label": (4,)}),
        "resnet_small": (models.get_resnet_small(num_classes=10),
                         {"data": (2, 3, 32, 32), "softmax_label": (2,)}),
    }[name]
    o_ref, g_ref = _run_exe(sym, shapes)
    monkeypatch.setenv("MXNET_COMPILE_OPT", "1")
    mxc.reload()
    o_opt, g_opt = _run_exe(sym, shapes)
    loose = mxc.last_report().get("layout", 0) > 0
    rtol = atol = 2e-3 if loose else 0.0
    for a, b in zip(o_ref, o_opt):
        assert a.shape == b.shape
        assert np.allclose(a, b, rtol=rtol, atol=atol), (
            name, float(np.max(np.abs(a - b))))
    assert set(g_ref) == set(g_opt)
    for k in g_ref:
        scale = max(1.0, float(np.max(np.abs(g_ref[k]))))
        assert np.allclose(g_ref[k], g_opt[k], rtol=rtol,
                           atol=atol * scale), (name, k)


def test_pass_level_verify_catches_divergence():
    data = mx.sym.Variable("data")
    ref = data * 2.0
    bad = data * 3.0
    with pytest.raises(mxc.CompileVerifyError):
        pipeline.check_equivalence(ref, bad, {"data": (2, 2)})
    # and the tolerance path accepts small drift
    pipeline.check_equivalence(ref, ref, {"data": (2, 2)}, loose=True)


def test_verify_mode_runs_clean_on_rewrite(compile_on, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_VERIFY", "1")
    mxc.reload()
    sym = _conv_sym()
    # bind succeeds: every pass output agrees with the reference graph
    sym.simple_bind(mx.cpu(), data=(2, 3, 8, 8), softmax_label=(2,))


def test_verify_mode_with_data_only_shapes(compile_on, monkeypatch):
    """The documented quick-check: Symbol.optimize with just the
    data/label shapes under MXNET_COMPILE_VERIFY=1 — weight shapes are
    inferred by the verify harness, not demanded (review finding,
    PR 6)."""
    monkeypatch.setenv("MXNET_COMPILE_VERIFY", "1")
    mxc.reload()
    from mxnet_tpu import models

    sym = models.get_resnet_small(num_classes=10)
    opt = sym.optimize(input_shapes={"data": (2, 3, 32, 32),
                                     "softmax_label": (2,)})
    assert opt is not sym
    assert mxc.last_report().get("layout", 0) > 0


def test_tuner_dtype_propagates_to_interior_convs(tmp_path):
    """Tuning keys carry the dtype each conv ACTUALLY computes in —
    propagated from the bound arguments, not looked up by the producer
    node's name (review finding, PR 6)."""
    from mxnet_tpu.compile import layout

    recorded = []

    class SpyTuner:
        def pick_conv_layout(self, params, dshape, dtype):
            recorded.append(dtype)
            return "nhwc"

    sym = _conv_sym()
    arg_shapes, _, _ = sym.infer_shape(data=(2, 3, 8, 8),
                                       softmax_label=(2,))
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    types = {n: np.dtype(np.float32) for n in shapes}
    layout.apply(sym, input_shapes=shapes, input_types=types,
                 tuner=SpyTuner())
    assert len(recorded) == 2  # both convs consulted
    assert all(t == np.dtype(np.float32) for t in recorded), recorded


def test_verify_mode_with_frozen_fold(compile_on, monkeypatch):
    """The verify harness must feed the reference graph the SAME frozen
    values the fold pass baked — random stand-ins would diverge by
    construction (review finding, PR 6)."""
    monkeypatch.setenv("MXNET_COMPILE_VERIFY", "1")
    mxc.reload()
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    out = mx.sym.FullyConnected(data=data, weight=w * 2.0, no_bias=True,
                                num_hidden=4, name="fc")
    rng = np.random.RandomState(3)
    from mxnet_tpu.predictor import Predictor

    pred = Predictor(out.tojson(),
                     {"arg:w": mx.nd.array(rng.rand(4, 8).astype(np.float32))},
                     ctx=mx.cpu(), input_shapes={"data": (2, 8)})
    assert mxc.last_report().get("fold", 0) >= 1
    pred.forward(data=rng.rand(2, 8).astype(np.float32))


# -- precision pass ------------------------------------------------------------

def test_matmul_precision_explicit_fast(compile_on, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_MATMUL_PREC", "fast")
    mxc.reload()
    sym = mx.sym.SoftmaxOutput(
        data=mx.sym.FullyConnected(data=mx.sym.Variable("data"),
                                   num_hidden=8, name="fc"),
        name="softmax")
    shapes = {"data": (4, 16), "softmax_label": (4,)}
    o_opt, _ = _run_exe(sym, shapes)
    assert mxc.last_report().get("precision", 0) == 1
    monkeypatch.delenv("MXNET_COMPILE_OPT")
    monkeypatch.delenv("MXNET_COMPILE_MATMUL_PREC")
    mxc.reload()
    o_ref, _ = _run_exe(sym, shapes)
    for a, b in zip(o_ref, o_opt):
        assert np.allclose(a, b, rtol=2e-3, atol=2e-3)


# -- config plumbing -----------------------------------------------------------

def test_off_by_default_zero_overhead():
    """The zero-overhead contract: disabled, the executor binds the
    user's graph object itself — no rewrite, no pass imports on the
    bind path, optimize() is identity."""
    assert not mxc.enabled()
    sym = _chain_sym()
    assert mxc.optimize(sym) is sym
    exe = sym.bind(mx.cpu(), {"data": mx.nd.ones((2, 2))})
    assert exe._exec_symbol is sym


def test_passes_individually_disableable(compile_on, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_PASSES", "fuse")
    mxc.reload()
    assert mxc.active_passes() == ("fuse",)
    sym = _conv_sym()
    exe = sym.simple_bind(mx.cpu(), data=(2, 3, 8, 8), softmax_label=(2,))
    names = [nd.op.name for nd in exe._exec_symbol.nodes
             if not nd.is_variable]
    assert not any(n.startswith("_mxc_to_") for n in names)  # no layout
    assert any(n.startswith(fuse.FUSED_OP_PREFIX) for n in names)
    with pytest.raises(ValueError):
        monkeypatch.setenv("MXNET_COMPILE_PASSES", "fuse,warp")
        mxc.reload()


def test_config_key_tracks_configuration(monkeypatch):
    k0 = mxc.config_key()
    monkeypatch.setenv("MXNET_COMPILE_OPT", "1")
    mxc.reload()
    k1 = mxc.config_key()
    monkeypatch.setenv("MXNET_COMPILE_PASSES", "fold")
    mxc.reload()
    k2 = mxc.config_key()
    assert len({k0, k1, k2}) == 3


# -- autotuner -----------------------------------------------------------------

def test_tuning_db_roundtrip_and_corruption(tmp_path):
    path = str(tmp_path / "tuning.json")
    db = autotune.TuningDB(path)
    db.put("k1", {"choice": "a", "timings": {"a": 0.1}})
    assert autotune.TuningDB(path).get("k1")["choice"] == "a"
    # bit-flip the file: fresh load must quarantine + start empty,
    # counting the corruption — never crash
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    before = autotune.CORRUPT
    db2 = autotune.TuningDB(path)
    assert db2.get("k1") is None
    assert autotune.CORRUPT == before + 1
    assert os.path.exists(path + ".corrupt")
    # and the db keeps working after the fallback
    db2.put("k2", {"choice": "b"})
    assert autotune.TuningDB(path).get("k2")["choice"] == "b"


def test_tuner_measures_once_then_reads(tmp_path):
    db = autotune.TuningDB(str(tmp_path / "t.json"))
    calls = []

    def mk(name, secs):
        def run():
            calls.append(name)
            return secs
        return run

    t = autotune.Tuner(db, measure_enabled=True, backend="cpu")
    assert t.pick("k", {"a": mk("a", 0.2), "b": mk("b", 0.1)},
                  default="a") == "b"
    assert calls == ["a", "b"]
    # second tuner (fresh process analog): recorded winner, no trials
    t2 = autotune.Tuner(db, measure_enabled=True, backend="cpu")
    assert t2.pick("k", {"a": mk("a", 0.2), "b": mk("b", 0.1)},
                   default="a") == "b"
    assert calls == ["a", "b"]
    # read-only tuner without a record: default, no measurement
    t3 = autotune.Tuner(db, measure_enabled=False, backend="cpu")
    assert t3.pick("k2", {"a": mk("a", 0.1)}, default="a") == "a"
    assert calls == ["a", "b"]


def test_conv_layout_tuning_on_device(tmp_path):
    db = autotune.TuningDB(str(tmp_path / "t.json"))
    t = autotune.Tuner(db, measure_enabled=True)
    before = autotune.TRIALS
    params = {"kernel": (3, 3), "stride": (1, 1), "pad": (1, 1),
              "num_filter": 8, "num_group": 1, "dilate": None}
    choice = t.pick_conv_layout(params, (2, 4, 8, 8))
    assert choice in ("nchw", "nhwc")
    assert autotune.TRIALS == before + 2  # both candidates timed
    assert len(db) == 1


# -- persistent jit cache ------------------------------------------------------

def test_jit_cache_populates_and_bitflip_falls_back(
        tmp_path, monkeypatch, jit_cache_isolated):
    assert mxc.ensure_jit_cache() is None  # nothing placed: cache off
    target = jit_cache.enable(str(tmp_path))
    assert target == str(tmp_path) == mxc.ensure_jit_cache()
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64))
    r0 = np.asarray(jax.jit(lambda v: jnp.sin(v) @ v.T)(x))
    entries = [f for f in os.listdir(target) if f.endswith("-cache")]
    assert entries, "no cache entries written"
    # flip one byte of an entry's frame header: jax writes zstd frames
    # without a content checksum, so only damage to the frame's own
    # structure is certain to stop it decoding (a flip inside a literal
    # run decodes, and is left to jax's deserialization fallback)
    victim = os.path.join(target, entries[0])
    raw = bytearray(open(victim, "rb").read())
    raw[0] ^= 0xFF
    open(victim, "wb").write(bytes(raw))
    before = jit_cache.CORRUPT
    checked, removed = jit_cache.verify_cache_dir(target)
    assert checked >= 1 and removed == 1
    assert jit_cache.CORRUPT == before + 1
    assert not os.path.exists(victim)
    # recompile instead of crash: a fresh jit of the same program
    # (miss after the sweep) reproduces the result
    r1 = np.asarray(jax.jit(lambda v: jnp.sin(v) @ v.T)(x))
    assert np.array_equal(r0, r1)


def test_jit_cache_placed_by_jax_env_var_stays_there(
        tmp_path, monkeypatch, jit_cache_isolated):
    """JAX_COMPILATION_CACHE_DIR wins: where jax's own wiring placed the
    cache, enable() moves nothing and no sub-directory is keyed under
    it (the path is part of the cache key)."""
    import jax

    placed, other = tmp_path / "placed", tmp_path / "other"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    # what jax's flag wiring did when it was imported with the variable
    jax.config.update("jax_compilation_cache_dir", str(placed))
    assert jit_cache.enable(str(other)) == str(placed)
    assert jit_cache.cache_dir() == str(placed)
    monkeypatch.setenv("MXNET_COMPILE_OPT", "1")
    mxc.reload()
    assert mxc.ensure_jit_cache() == str(placed)
    assert os.listdir(str(placed)) == [] and not other.exists()


_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.models import get_mlp
sym = get_mlp()
exe = sym.simple_bind(mx.cpu(), data=(4, 784), softmax_label=(4,))
exe.forward(is_train=True)
exe.backward()
from mxnet_tpu.compile import jit_cache
print(json.dumps(jit_cache.stats()))
"""


def test_cold_start_cache_hits_across_processes(tmp_path):
    """The acceptance probe: a second process binding the same model
    with the same cache dir must HIT (compile.cache_hits_total > 0) —
    cold-start jit builds survive process restarts."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               MXNET_COMPILE_OPT="1",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("MXNET_ENGINE_VERIFY", None)

    def run():
        out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run()
    assert first["misses"] > 0 and first["hits"] == 0
    second = run()
    assert second["hits"] > 0, second
    assert second["misses"] == 0, second


# -- telemetry counters --------------------------------------------------------

def test_compile_counters(compile_on, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu import telemetry as tel

    tel.reload()
    sym = _conv_sym()
    sym.simple_bind(mx.cpu(), data=(2, 3, 8, 8), softmax_label=(2,))
    snap = tel.default_registry().snapshot()["counters"]
    assert snap.get("compile.passes_applied_total", 0) >= 2  # layout+fuse


# -- mxlint fusible-chain ------------------------------------------------------

def test_lint_reports_fusible_chain():
    findings = _chain_sym().lint()
    fc = [f for f in findings if f.code == "fusible-chain"]
    assert len(fc) == 1
    assert fc[0].severity == "info"
    assert "3 elementwise ops" in fc[0].message
    # info findings never trip the default CLI gate
    from mxnet_tpu.analysis.findings import max_severity

    assert max_severity(fc) == "info"


def test_lint_fusible_chain_cross_references_padding():
    data = mx.sym.Variable("data", shape=(4, 50))
    fc = mx.sym.FullyConnected(data=data, num_hidden=100, name="fc100")
    s = mx.sym.Activation(data=fc + 1.0, act_type="relu")
    findings = s.lint()
    pads = [f for f in findings if f.code == "tpu-pad"]
    chains = [f for f in findings if f.code == "fusible-chain"]
    assert pads and chains
    assert "fc100" in chains[0].message  # the padded feeder is named


def test_lint_clean_graph_has_no_chain_finding():
    data = mx.sym.Variable("data")
    s = mx.sym.Activation(data=data, act_type="relu")  # single op: no chain
    assert [f for f in s.lint() if f.code == "fusible-chain"] == []


# -- end-to-end fit ------------------------------------------------------------

def test_fit_trains_under_compile_opt(compile_on):
    """FeedForward.fit (scanned path) over a conv net with the rewrite
    passes on: runs to completion and learns the toy task."""
    mx.random.seed(5)
    np.random.seed(5)
    n = 128
    Y = (np.arange(n) % 2).astype(np.float32)
    X = np.random.rand(n, 1, 8, 8).astype(np.float32)
    X[Y == 1] += 0.5  # planted brightness signal, comfortably learnable
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data=data, num_filter=4, kernel=(3, 3),
                           pad=(1, 1), name="c")
    a = mx.sym.Activation(data=c, act_type="relu")
    p = mx.sym.Pooling(data=a, kernel=(2, 2), stride=(2, 2),
                       pool_type="avg")
    fc = mx.sym.FullyConnected(data=mx.sym.Flatten(data=p), num_hidden=2,
                               name="fc")
    sym = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    train = mx.io.NDArrayIter(X, Y, batch_size=16, shuffle=True)
    model = mx.FeedForward(sym, ctx=mx.cpu(), num_epoch=6,
                           learning_rate=0.1, momentum=0.9,
                           initializer=mx.initializer.Xavier())
    model.fit(X=train)
    acc = model.score(mx.io.NDArrayIter(X, Y, batch_size=16))
    assert acc > 0.8, acc
    for v in model.arg_params.values():
        assert np.isfinite(v.asnumpy()).all()
