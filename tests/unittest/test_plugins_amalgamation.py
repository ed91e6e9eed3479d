"""OpenCV facade, SFrame gate, and amalgamation packer tests
(ref: plugin/opencv/cv_api.cc, plugin/sframe/iter_sframe.cc,
amalgamation/ — SURVEY §2.20-2.21)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cv_resize_shapes_and_values():
    img = mx.nd.array(np.arange(2 * 2 * 3, dtype=np.uint8).reshape(2, 2, 3))
    out = mx.cv.resize(img, (4, 4), interp=0)  # nearest
    assert out.shape == (4, 4, 3)
    # nearest-neighbor keeps original values
    assert set(np.unique(out.asnumpy())) <= set(np.arange(12))
    out2 = mx.cv.resize(img, (3, 5), interp=1)
    assert out2.shape == (5, 3, 3)
    assert out2.dtype == np.uint8


def test_cv_copy_make_border_modes():
    img = mx.nd.array(np.ones((2, 2, 1), np.float32))
    out = mx.cv.copyMakeBorder(img, 1, 1, 2, 2,
                               mx.cv.BORDER_CONSTANT, value=7.0)
    assert out.shape == (4, 6, 1)
    a = out.asnumpy()
    assert a[0, 0, 0] == 7.0 and a[1, 2, 0] == 1.0
    rep = mx.cv.copyMakeBorder(img, 1, 0, 0, 0, mx.cv.BORDER_REPLICATE)
    assert rep.asnumpy()[0, 0, 0] == 1.0
    with pytest.raises(MXNetError):
        mx.cv.copyMakeBorder(img, 1, 1, 1, 1, border_type=99)


def test_cv_imdecode_gate_or_roundtrip():
    try:
        from PIL import Image  # noqa: F401

        import io as _io

        buf = _io.BytesIO()
        Image.fromarray(
            np.zeros((8, 8, 3), np.uint8)).save(buf, format="PNG")
        img = mx.cv.imdecode(buf.getvalue())
        assert img.shape == (8, 8, 3)
        gray = mx.cv.imdecode(buf.getvalue(), flag=mx.cv.IMREAD_GRAYSCALE)
        assert gray.shape == (8, 8, 1)
    except ImportError:
        with pytest.raises(MXNetError):
            mx.cv.imdecode(b"notanimage")


def test_sframe_gate():
    from mxnet_tpu.sframe_plugin import SFrameIter, sframe_available

    if not sframe_available():
        with pytest.raises(MXNetError):
            SFrameIter(None, data_field="x")
    else:  # pragma: no cover - sframe not in this image
        pass


def test_amalgamation_pack_and_run(tmp_path):
    """Train one epoch, pack to a single artifact, run it in a fresh
    process that imports the artifact loader only."""
    mx.random.seed(0)
    train = mx.io.MNISTIter(batch_size=64, num_synthetic=512, seed=1)
    model = mx.FeedForward(
        mx.models.get_lenet(), ctx=mx.cpu(0), num_epoch=1,
        learning_rate=0.1, initializer=mx.initializer.Xavier())
    model.fit(X=train)
    prefix = str(tmp_path / "m")
    model.save(prefix, epoch=1)

    art = str(tmp_path / "m.mxtc")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "amalgamate.py"),
         "pack", prefix, "1", art, "--input", "data=2,1,28,28"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert os.path.getsize(art) > 1000

    x = np.random.RandomState(0).rand(2, 1, 28, 28).astype(np.float32)
    np.save(str(tmp_path / "x.npy"), x)
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "amalgamate.py"),
         "run", art, "--input", "data=@%s" % (tmp_path / "x.npy")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r2.returncode == 0, r2.stderr
    assert "output[0] shape=(2, 10)" in r2.stdout
