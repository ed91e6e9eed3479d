"""Pallas kernel parity tests (interpret mode on CPU).

Mirrors the reference's cuDNN-vs-plain consistency checks
(tests/python/gpu/test_operator_gpu.py check_consistency): the Pallas fast
path must agree with the plain XLA implementation.
"""
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "1")


def test_flash_attention_matches_reference():
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    b, h, t, d = 2, 3, 256, 64
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    for causal in (True, False):
        out = pk.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        ref = pk._attention_reference(q, k, v, causal, 1.0 / d**0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grad_matches_reference():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(1)
    b, h, t, d = 1, 2, 256, 32
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss_fast(q, k, v):
        # 128 is the smallest block that lowers on hardware (the lse/dcap
        # stats blocks put block_q in the lane dim); t=256 keeps multiple
        # q blocks in play for the grad reconstruction
        return pk.flash_attention(q, k, v, causal=True, block_q=128, block_k=128).sum()

    def loss_ref(q, k, v):
        return pk._attention_reference(q, k, v, True, 1.0 / d**0.5).sum()

    g_fast = jax.grad(loss_fast, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_fast, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_flash_attention_bwd_kernel_parity_multiblock():
    """The Pallas backward (dq + dkv kernels, round 4) must match the
    dense vjp across block boundaries, both causal and not, with
    non-uniform head gradients (exercises the lse/D reconstruction)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    b, h, t, d = 2, 2, 256, 32
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    g = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    for causal in (True, False):
        def fast(q, k, v):
            return pk.flash_attention(q, k, v, causal=causal,
                                      block_q=128, block_k=128)

        def ref(q, k, v):
            return pk._attention_reference(q, k, v, causal, 1.0 / d**0.5)

        out_f, pull_f = jax.vjp(fast, q, k, v)
        out_r, pull_r = jax.vjp(ref, q, k, v)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   atol=2e-5)
        for a, b_ in zip(pull_f(g), pull_r(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4)


def test_flash_attention_dense_bwd_probe_path(monkeypatch):
    """MXNET_FLASH_DENSE_BWD=1 keeps the dense-recompute backward for
    A/B probes; it must agree with the kernel backward."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(6)
    b, h, t, d = 1, 2, 128, 32
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128).sum()

    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("MXNET_FLASH_DENSE_BWD", "1")
    g_dense = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_kernel, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_flash_attention_block_divisor_shrink(monkeypatch):
    """T divisible by 128 but not by the 512 default must stay on the
    kernel (block shrinks to a divisor) and malformed env knobs fall
    back silently (review r4)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(9)
    t = 640  # not divisible by 512; tiles at 128
    q = jnp.asarray(rng.randn(1, 1, t, 32), jnp.float32)
    out = pk.flash_attention(q, q, q, causal=True)
    ref = pk._attention_reference(q, q, q, True, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    for bad in ("", "0", "notanint"):
        monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", bad)
        monkeypatch.setenv("MXNET_FLASH_MIN_T", bad)
        out = pk.flash_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)


def test_flash_block_selection_rules():
    """Block selection must only emit hardware-legal tilings: block_q
    rides the lane dim of the stats blocks, so it must be a multiple of
    128 or the full q length (advisor r4); the default is shape-keyed
    (1024 at T>=8192)."""
    from mxnet_tpu.ops import pallas_kernels as pk

    assert pk._select_blocks(8192, 8192) == (1024, 512, True)
    assert pk._select_blocks(16384, 16384) == (1024, 512, True)
    assert pk._select_blocks(4096, 4096) == (512, 512, True)
    # block_k is hard-capped at 512 (1024 fails to compile on chip)
    assert pk._select_blocks(8192, 8192, block_k=1024) == (1024, 512, True)
    # divisor shrink keeps tileable lengths on the kernel, scanning all
    # 128-multiples (8320 = 128*65 tiles at 640, not a power-of-two)
    assert pk._select_blocks(640, 640) == (128, 128, True)
    assert pk._select_blocks(1280, 1280) == (256, 256, True)
    assert pk._select_blocks(8320, 8320) == (640, 128, True)
    # a sub-128 request rounds up to a legal block instead of going dense
    assert pk._select_blocks(8192, 8192, block_q=64) == (128, 512, True)
    # off-128 lengths have NO legal tiling — probed on real Mosaic (r5):
    # even a full-dim off-128 block fails, because the backward kernels'
    # dynamic lane slices need a provable 128-multiple start index. Such
    # shapes (including any T < 128) must fall back to dense, never emit
    # a block that raises a lowering error on chip.
    for tq, tk in ((192, 256), (544, 544), (1088, 1088), (8256, 8256),
                   (64, 64), (1090, 1090)):
        bq, bk, ok = pk._select_blocks(tq, tk)
        assert not ok, (tq, tk)
    # an explicit sub-128 block_q is rounded up to the legal 128 tiling
    # rather than lowered as-is or dropped to dense
    assert pk._select_blocks(256, 256, block_q=64) == (128, 256, True)
    # a non-128-multiple request re-scans for a legal divisor instead of
    # going dense (192 @ 4992 -> 128, 320 @ 1280 -> 256); the k side
    # scans the same way (4992 = 13*384)
    assert pk._select_blocks(4992, 4992, block_q=192) == (128, 384, True)
    assert pk._select_blocks(1280, 1280, block_q=320) == (256, 256, True)


def test_flash_attention_fallback_odd_shapes():
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 1, 37, 16), jnp.float32)  # 37 not tileable
    out = pk.flash_attention(q, q, q, causal=True)
    ref = pk._attention_reference(q, q, q, True, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("shape", [
    (64, 1000),    # one full-height block
    (100, 1000),   # rows not a multiple of 8 (the examples' batch of 100)
    (300, 129),    # several row blocks, the last one ragged
    (7, 10),
])
def test_fused_softmax_matches_jax(shape):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape) * 3, jnp.float32)
    routed = dict(pk.FALLBACKS)
    out = pk.fused_softmax(x)
    assert pk.FALLBACKS == routed  # the kernel took it
    ref = jax.nn.softmax(x, axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_kernel_fallbacks_are_counted(monkeypatch):
    """A shape a kernel cannot take goes to XLA by rule, and is counted
    by (kernel, reason) — never in silence."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    def routed(fn, *args):
        before = dict(pk.FALLBACKS)
        fn(*args)
        return {k: v - before.get(k, 0) for k, v in pk.FALLBACKS.items()
                if v != before.get(k, 0)}

    q = jnp.zeros((1, 1, 37, 16), jnp.float32)  # 37 does not tile
    assert routed(pk.flash_attention, q, q, q) == {
        ("flash_attention", "untileable"): 1}
    wide = jnp.zeros((8, 200192), jnp.float32)  # 8 rows overflow VMEM
    assert routed(pk.fused_softmax, wide) == {("fused_softmax", "vmem"): 1}
    monkeypatch.setenv("MXNET_PALLAS", "0")
    assert routed(pk.fused_softmax, jnp.zeros((8, 16))) == {
        ("fused_softmax", "disabled"): 1}


def test_softmax_output_op_under_pallas():
    """SoftmaxOutput forward routes through fused_softmax; numerics parity."""
    import mxnet_tpu as mx

    rng = np.random.RandomState(4)
    x = rng.randn(16, 10).astype(np.float32)
    data = mx.symbol.Variable("data")
    label = mx.symbol.Variable("label")
    sym = mx.symbol.SoftmaxOutput(data=data, label=label)
    ex = sym.simple_bind(mx.cpu(), data=(16, 10), label=(16,))
    ex.arg_dict["data"][:] = x
    ex.arg_dict["label"][:] = rng.randint(0, 10, (16,)).astype(np.float32)
    out = ex.forward()[0].asnumpy()
    e = np.exp(x - x.max(1, keepdims=True))
    np.testing.assert_allclose(out, e / e.sum(1, keepdims=True), atol=1e-5)
