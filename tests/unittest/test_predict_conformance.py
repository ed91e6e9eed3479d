"""Cross-binding predict conformance (VERDICT r3 item 9): one
checkpoint + input + expected-logits fixture
(tests/fixtures/predict_conformance, built by
tools/gen_predict_fixture.py) consumed by the C++, Java, R and MATLAB
binding tests. The C++ consumer compiles and RUNS here (g++ is in the
image); Java/R/MATLAB consumers run when their toolchains exist and are
structurally checked otherwise.
"""
import os
import shutil
import subprocess

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIX = os.path.join(ROOT, "tests", "fixtures", "predict_conformance")


def read_tensor(path):
    with open(path) as f:
        shape = tuple(int(d) for d in f.readline().split())
        vals = np.array([float(l) for l in f], np.float32)
    return vals.reshape(shape)


def test_fixture_self_consistent():
    """The Python frontend reproduces expected.txt from the checkpoint —
    the ground truth every other binding is compared against."""
    import mxnet_tpu as mx

    x = read_tensor(os.path.join(FIX, "input.txt"))
    want = read_tensor(os.path.join(FIX, "expected.txt"))
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        os.path.join(FIX, "model"), 1)
    exe = sym.simple_bind(mx.cpu(0), grad_req="null",
                          data=x.shape, softmax_label=(x.shape[0],))
    exe.copy_params_from(arg_params, aux_params)
    exe.arg_dict["data"][:] = x
    exe.forward(is_train=False)
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), want,
                               rtol=1e-4, atol=1e-6)


def test_cpp_consumer_passes(tmp_path):
    src = os.path.join(ROOT, "bindings", "cpp", "predict_fixture.cc")
    natdir = os.path.join(ROOT, "mxnet_tpu", "_native")
    import mxnet_tpu._native as native

    native.load("c_api")  # ensure the library is built
    exe = str(tmp_path / "predict_fixture")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", src, "-o", exe,
         "-L" + natdir, "-lc_api", "-Wl,-rpath," + natdir],
        check=True, capture_output=True, timeout=120)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([exe, FIX], env=env, capture_output=True, timeout=300)
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()
    assert b"PASSED" in r.stdout


def test_all_four_consumers_exist():
    """Each binding ships a consumer of the SAME fixture dir."""
    consumers = [
        os.path.join(ROOT, "bindings", "cpp", "predict_fixture.cc"),
        os.path.join(ROOT, "bindings", "jvm", "examples",
                     "PredictFixture.java"),
        os.path.join(ROOT, "bindings", "R-package", "tests",
                     "predict_fixture.R"),
        os.path.join(ROOT, "bindings", "matlab", "test_fixture.m"),
    ]
    for c in consumers:
        assert os.path.exists(c), c
        assert "predict_conformance" in open(c).read(), c


@pytest.mark.skipif(shutil.which("javac") is None,
                    reason="no JDK in this image")
def test_java_consumer_passes():
    jvm = os.path.join(ROOT, "bindings", "jvm")
    subprocess.run(["bash", os.path.join(jvm, "build.sh")], check=True)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        ["java", "-cp", os.path.join(jvm, "build"), "PredictFixture", FIX],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASSED" in r.stdout


@pytest.mark.skipif(shutil.which("Rscript") is None,
                    reason="no R in this image")
def test_r_consumer_passes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        ["Rscript", os.path.join(ROOT, "bindings", "R-package", "tests",
                                 "predict_fixture.R")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASSED" in r.stdout
