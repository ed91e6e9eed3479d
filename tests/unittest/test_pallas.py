"""Pallas kernel parity tests (interpret mode on CPU).

Mirrors the reference's cuDNN-vs-plain consistency checks
(tests/python/gpu/test_operator_gpu.py check_consistency): the Pallas fast
path must agree with the plain XLA implementation.
"""
import functools

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


@pytest.mark.parametrize("backward", ["dqkv", "pair"])
def test_flash_attention_bwd_kernel_parity_multiblock(monkeypatch, backward):
    """The Pallas backward must match the dense vjp across block
    boundaries, both causal and not, with non-uniform head gradients
    (exercises the lse/D reconstruction): the one ``flash_bwd_dqkv``
    kernel, and the ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair, which the
    plan keeps where only the pair's footprint is under ``_VMEM_CAP``
    (here a cap set between the two)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    b, h, t, d = 2, 2, 256, 32
    if backward == "pair":
        mib = 2 ** 20
        limits = [-int(-pk._VMEM_MARGIN * pk._flash_vmem(
            t, t, d, d, 128, 128, 4, fused=fused) // mib) * mib
            for fused in (True, False)]
        assert limits[0] > limits[1]
        monkeypatch.setattr(pk, "_VMEM_LIMIT", 0)
        monkeypatch.setattr(pk, "_VMEM_CAP", limits[1])
    assert pk._flash_plan(t, t, d, d, 128, 128)[2:] == (
        None, None if backward == "dqkv" else limits[1], backward == "dqkv")
    took = dict(pk.FLASH_CALLS)
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
    names = {key[0] for key, n in pk.FLASH_CALLS.items()
             if n != took.get(key, 0)}
    assert names == ({"flash_fwd", "flash_bwd_dqkv"} if backward == "dqkv"
                     else {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})


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
        out = pk.flash_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)


def test_flash_block_selection_rules():
    """Block selection must only emit hardware-legal tilings: both blocks
    ride the lane dim of score tiles and stats blocks, so each must be a
    multiple of 128 (advisor r4), and block_k must divide block_q (a
    causal kernel unrolls its own block's block_q // block_k diagonal
    steps); the defaults are 1024 and 512 at every length (PR 28)."""
    from mxnet_tpu.ops import pallas_kernels as pk

    assert pk._select_blocks(8192, 8192) == (1024, 512, True)
    assert pk._select_blocks(16384, 16384) == (1024, 512, True)
    assert pk._select_blocks(4096, 4096) == (1024, 512, True)
    assert pk._select_blocks(1024, 1024) == (1024, 512, True)
    assert pk._select_blocks(512, 512) == (512, 512, True)
    # block_k is hard-capped at 512 (a 1024 x 1024 float32 score tile is
    # 4 MiB and the kernels hold two or three)
    assert pk._select_blocks(8192, 8192, block_k=1024) == (1024, 512, True)
    # divisor shrink keeps tileable lengths on the kernel, scanning all
    # 128-multiples (8320 = 128*65 tiles at 640, not a power-of-two),
    # and block_k shrinks on to a divisor of block_q
    assert pk._select_blocks(640, 640) == (640, 128, True)
    assert pk._select_blocks(1280, 1280) == (640, 128, True)
    assert pk._select_blocks(1536, 1536) == (768, 384, True)
    assert pk._select_blocks(8320, 8320) == (640, 128, True)
    # a sub-128 request rounds up to a legal block instead of going dense
    assert pk._select_blocks(8192, 8192, block_q=64) == (128, 128, True)
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
    assert pk._select_blocks(256, 256, block_q=64) == (128, 128, True)
    # a non-128-multiple request re-scans for a legal divisor instead of
    # going dense (192 @ 4992 -> 128, 320 @ 1280 -> 256); the k side
    # scans the same way, among the divisors of block_q
    assert pk._select_blocks(4992, 4992, block_q=192) == (128, 128, True)
    assert pk._select_blocks(1280, 1280, block_q=320) == (256, 256, True)
    assert pk._select_blocks(512, 512, block_q=256, block_k=512) == (
        256, 256, True)
    # the resolution is a fixed point: the dkv kernel asks again with the
    # blocks the forward was given
    for t in (640, 1024, 1536, 8320):
        bq, bk, _ = pk._select_blocks(t, t)
        assert pk._select_blocks(t, t, bq, bk) == (bq, bk, True)


def test_flash_plan_halves_the_default_block(monkeypatch):
    """A block_q the caller did not name is halved while the operands
    overflow the scoped VMEM; a named one is refused while a smaller block
    would fit; operands that fit at no block stay on the kernels under a
    limit of their own (the next test), and go to XLA past the cap. The
    backward is the pair where it fits at larger blocks than the one
    kernel."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert pk._flash_plan(1024, 1024, 128, 128, itemsize=2) == (
        1024, 512, None, None, True)
    # the one backward kernel fits these at smaller blocks than the pair
    # alone (its dQ accumulator does not shrink with the block), so the
    # backward is the pair, at the blocks it has had
    assert pk._flash_plan(4096, 4096, 128, 128, itemsize=2) == (
        1024, 512, None, None, False)
    assert pk._flash_plan(2048, 2048, 128, 128, itemsize=4) == (
        1024, 512, None, None, False)
    assert pk._flash_plan(4096, 4096, 128, 128, itemsize=4) == (
        512, 512, None, None, False)
    assert pk._flash_plan(4096, 4096, 128, 128, block_q=1024,
                          itemsize=4)[2] == "vmem"
    assert pk._flash_plan(32768, 32768, 128, 128, itemsize=4)[2:4] == (
        "vmem", None)
    monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", "1024")  # a probe is a name
    assert pk._flash_plan(4096, 4096, 128, 128, itemsize=4)[2] == "vmem"


def test_flash_plan_names_a_limit_where_no_block_fits(monkeypatch):
    """256-wide keys AND values at T = 8192 (GLM-4.7-Flash's latent
    attention): the whole-length operands alone are past Mosaic's default
    16 MiB at every block, so the plan keeps ``_select_blocks``' blocks and
    names the scoped VMEM the calls ask for: the footprint and a quarter,
    in whole MiB, under the cap. Since the backward is one kernel that
    holds dQ's [T, d] accumulator and block beside the whole q side,
    Kimi-Linear's 192/128 head and the grouped heads of Mellum2 and
    Laguna at T = 8192 fit at no block either, and run at the same
    blocks under a limit of their own; the dense LM at T = 1024 names
    none."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setenv("MXNET_PALLAS", "1")
    for block in (1024, 512, 256, 128):
        assert pk._flash_vmem(8192, 8192, 256, 256, block, min(block, 512),
                              2) > pk._VMEM_LIMIT
    bq, bk, refusal, limit, fused = pk._flash_plan(8192, 8192, 256, 256,
                                                   itemsize=2)
    assert (bq, bk, refusal, fused) == pk._select_blocks(8192, 8192)[:2] + (
        None, True)
    need = pk._flash_vmem(8192, 8192, 256, 256, bq, bk, 2)
    assert pk._VMEM_MARGIN * need <= limit < pk._VMEM_MARGIN * need + 2 ** 20
    assert limit % 2 ** 20 == 0 and pk._VMEM_LIMIT < limit <= pk._VMEM_CAP
    # a probe's named blocks are kept too: nothing smaller would fit
    assert pk._flash_plan(8192, 8192, 256, 256, 512, 512, itemsize=2)[:3] == (
        512, 512, None)
    assert pk.flash_kernel_usable(8192, 8192, 256, 256, itemsize=2)
    # float32 at T = 8192 and 128-wide heads went to XLA until PR 36
    assert pk._flash_plan(8192, 8192, 128, 128, itemsize=4)[2] is None
    for d, dv, group in ((192, 128, 1), (128, 128, 8), (128, 128, 6)):
        plan = pk._flash_plan(8192, 8192, d, dv, itemsize=2, group=group)
        assert plan[:3] == (1024, 512, None) and plan[4], (d, dv, group)
        assert pk._VMEM_LIMIT < plan[3] <= pk._VMEM_CAP
    # a block fits, no limit is named
    assert pk._flash_plan(1024, 1024, 64, 64, itemsize=2) == (
        1024, 512, None, None, True)


#: (T, block_q, block_k): one tile; several tiles with block_q != block_k;
#: a square the diagonal crosses on some visited tiles and not on others
FLASH_TILINGS = {"one_tile": (128, 128, 128),
                 "wide_k": (256, 128, 256),
                 "tall_q": (512, 256, 128),
                 "diagonal": (512, 128, 128)}
#: tolerance of (the forward, the gradients): float32 absolute, as the
#: float32 tests above; bfloat16 as a share of the reference's largest
#: value (its outputs and its p / ds operands keep 8 bits of mantissa)
FLASH_TOLERANCE = {"float32": (2e-5, 3e-4), "bfloat16": (1.5e-2, 1.5e-2)}


@pytest.mark.parametrize("tiling", sorted(FLASH_TILINGS))
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_by_type_and_tiling(dtype, causal, tiling):
    """Forward and jax.grad against the dense reference computed in
    float32 from the same (rounded) inputs."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    t, bq, bk = FLASH_TILINGS[tiling]
    rng = np.random.RandomState(11)
    d = 64
    q, k, v, g = (jnp.asarray(rng.randn(1, 2, t, d), dtype)
                  for _ in range(4))

    def fast(q, k, v):
        return pk.flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk)

    def ref(q, k, v):
        return pk._attention_reference(q, k, v, causal, d ** -0.5)

    routed = dict(pk.FALLBACKS)
    out, pull = jax.vjp(fast, q, k, v)
    got = (out,) + pull(g)
    assert pk.FALLBACKS == routed  # the kernels took it
    assert all(a.dtype == q.dtype for a in got)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, pull = jax.vjp(ref, *f32)
    want = (want,) + pull(g.astype(jnp.float32))
    for i, (a, b) in enumerate(zip(got, want)):
        tol = FLASH_TOLERANCE[dtype][i > 0]
        if dtype == "bfloat16":
            tol *= float(np.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=0)


@pytest.mark.parametrize("tiling", ["tall_q", "diagonal"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_at_256_wide_keys_and_values(dtype, tiling):
    """GLM-4.7-Flash's head: d = dv = 256 at a scale of 1 / 16 (a power of
    two, folded into the block), causal, over several blocks: the forward
    and the three gradients against the dense reference in float32."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    t, bq, bk = FLASH_TILINGS[tiling]
    rng = np.random.RandomState(13)
    q, k, v, g = (jnp.asarray(rng.randn(1, 2, t, 256) * 0.5, dtype)
                  for _ in range(4))
    assert pk._fold_scale(q.dtype, 0.0625)

    def fast(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, scale=0.0625,
                                  block_q=bq, block_k=bk)

    routed = dict(pk.FALLBACKS)
    out, pull = jax.vjp(fast, q, k, v)
    got = (out,) + pull(g)
    assert pk.FALLBACKS == routed
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, pull = jax.vjp(
        lambda q, k, v: pk._attention_reference(q, k, v, True, 0.0625), *f32)
    want = (want,) + pull(g.astype(jnp.float32))
    for i, (a, b) in enumerate(zip(got, want)):
        tol = FLASH_TOLERANCE[dtype][i > 0]
        if dtype == "bfloat16":
            tol *= float(np.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=0)


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, loops, calls and kernels included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_products_take_the_inputs_type(dtype):
    """bfloat16 q, k, v: every product of the two kernels is fed
    bfloat16 and accumulates in float32; float32 inputs keep float32
    products. Read from the kernels' jaxprs: 2 + 5 products, each once in
    the loop's tile body and once in the diagonal step's: the backward
    builds a tile's S and dP once for dQ, dK and dV."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    q = jnp.zeros((1, 1, 256, 64), dtype)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128).astype(jnp.float32).sum()

    outer = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    kernels = {
        eqn.params["name"]: [e for e in _eqns(eqn.params["jaxpr"])
                             if e.primitive.name == "dot_general"]
        for eqn in _eqns(outer.jaxpr) if eqn.primitive.name == "pallas_call"}
    assert {name: len(dots) for name, dots in kernels.items()} == {
        "flash_fwd": 4, "flash_bwd_dqkv": 10}
    for name, dots in kernels.items():
        for eqn in dots:
            operands = {str(var.aval.dtype) for var in eqn.invars}
            assert operands == {dtype}, (name, eqn)
            assert eqn.params["preferred_element_type"] == jnp.float32, (
                name, eqn)


def test_flash_calls_are_counted():
    """Every call site that takes the kernels is counted with the operand
    type of its products and the tiles it visits / masks / of the square
    (a tile wholly under the diagonal takes the body without a mask)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    def took(shape, dtype, **kw):
        before = dict(pk.FLASH_CALLS)
        x = jax.ShapeDtypeStruct(shape, dtype)
        jax.eval_shape(jax.grad(
            lambda q, k, v: pk.flash_attention(q, k, v, **kw).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
        return {key: n - before.get(key, 0)
                for key, n in pk.FLASH_CALLS.items()
                if n != before.get(key, 0)}

    kernels = ("flash_fwd", "flash_bwd_dqkv")
    # the benchmark cell's attention: as it runs (1024 x 512), at 256 x 256
    assert took((8, 16, 1024, 64), jnp.bfloat16, causal=True) == {
        (name, "bfloat16", (3, 2, 4)): 1 for name in kernels}
    assert took((8, 16, 1024, 64), jnp.bfloat16, causal=True, block_q=256,
                block_k=256) == {
        (name, "bfloat16", (10, 4, 16)): 1 for name in kernels}
    assert took((1, 1, 512, 64), jnp.float32, causal=True, block_q=256,
                block_k=128) == {
        (name, "float32", (10, 4, 16)): 1 for name in kernels}
    assert took((1, 1, 512, 64), jnp.float32, causal=False, block_q=256,
                block_k=128) == {
        (name, "float32", (16, 0, 16)): 1 for name in kernels}


@pytest.mark.parametrize("t,step", [(1024, 512), (1024, 256), (1152, 128)])
def test_flash_tile_counts_match_the_mask(t, step):
    """What is counted is the causal square itself: the step x step tiles
    with a visible element are visited, those that also hold a hidden
    one (the diagonal's) are masked."""
    from mxnet_tpu.ops import pallas_kernels as pk

    seen = (np.arange(t)[None, :] <= np.arange(t)[:, None]).reshape(
        t // step, step, t // step, step).transpose(0, 2, 1, 3)
    visited = seen.any(axis=(2, 3))
    masked = visited & ~seen.all(axis=(2, 3))
    assert pk._tile_counts(t, t, step, True) == (
        visited.sum(), masked.sum(), visited.size)


#: window (None: none) -> what it is at T = 512 under blocks of 256 and
#: steps of 128: a multiple of the step, NOT a multiple of the step (and
#: narrower than a step), wider than a block, wider than T (no window)
FLASH_WINDOWS = {"none": None, "step_multiple": 256, "ragged": 100,
                 "wide": 300, "over_T": 1000}


@pytest.mark.parametrize("window", sorted(FLASH_WINDOWS))
@pytest.mark.parametrize("group", [1, 6, 8])
def test_flash_grouped_queries_and_window(group, window):
    """Forward and all three gradients against the dense reference, several
    blocks long: ``group`` query heads read one key/value head (dk and dv
    summed over the group), a causal query sees its last ``window`` keys.
    The window kernels are the ones taken, under their own names."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    w = FLASH_WINDOWS[window]
    t, d, hkv = 512, 64, 2 if group == 1 else 1
    rng = np.random.RandomState(5)
    q, g = (jnp.asarray(rng.randn(1, hkv * group, t, d), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, hkv, t, d), jnp.float32)
            for _ in range(2))

    def fast(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, window=w,
                                  block_q=256, block_k=128)

    def ref(q, k, v):
        return pk._attention_reference(q, k, v, True, d ** -0.5, w)

    routed, took = dict(pk.FALLBACKS), dict(pk.FLASH_CALLS)
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(fast, q, k, v)
        got = (out,) + pull(g)
        want, pull = jax.vjp(ref, q, k, v)
        want = (want,) + pull(g)
    assert pk.FALLBACKS == routed
    names = {key[0] for key, n in pk.FLASH_CALLS.items()
             if n != took.get(key, 0)}
    windowed = w is not None and w < t
    assert names == {("flash_win_" if windowed else "flash_") + side
                     for side in ("fwd", "bwd_dqkv")}
    assert [a.shape for a in got] == [q.shape, q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=0)
    if windowed:  # the reference itself saw a window
        full = pk._attention_reference(q, k, v, True, d ** -0.5)
        assert float(jnp.max(jnp.abs(full - want[0]))) > 1e-2


def test_flash_grouped_queries_bfloat16_sum_in_float32():
    """bfloat16 operands under a group of 8: dk and dv are the float32 sum
    of the eight heads' parts rounded once, so they come as near the
    float32 reference as a single head's do."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(6)
    q, g = (jnp.asarray(rng.randn(1, 8, 256, 64), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, 1, 256, 64), jnp.bfloat16)
            for _ in range(2))
    _, pull = jax.vjp(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, window=96, block_q=128, block_k=128), q, k, v)
    got = pull(g)
    assert [a.dtype for a in got] == [jnp.bfloat16] * 3
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    _, pull = jax.vjp(lambda q, k, v: pk._attention_reference(
        q, k, v, True, 0.125, 96), *f32)
    for a, b in zip(got, pull(g.astype(jnp.float32))):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=1.5e-2 * float(np.abs(b).max()),
                                   rtol=0)
    with pytest.raises(ValueError):
        pk.flash_attention(q[:, :3], k.repeat(2, 1), v.repeat(2, 1))
    with pytest.raises(ValueError):
        pk.flash_attention(q, k, v, causal=False, window=96)


@pytest.mark.parametrize("k_side", [False, True], ids=["q_side", "k_side"])
@pytest.mark.parametrize("t,block,step,window", [
    (1024, 256, 128, 256), (1024, 512, 256, 200), (1024, 256, 256, 700),
    (512, 128, 128, 64), (2048, 512, 256, 1024), (8192, 512, 512, 1024)])
def test_flash_tile_counts_with_a_window_match_the_mask(t, block, step,
                                                        window, k_side):
    """The windowed kernels' loops against a count by hand from the mask
    itself: a block's steps outside its own ``block // step`` are visited
    iff the step x block strip holds a visible element and masked iff it
    also holds a hidden one; the block's own steps are each one strip from
    the diagonal on, masked over the diagonal tile alone unless the
    window's edge crosses the strip. No strip wholly outside the window is
    visited."""
    from mxnet_tpu.ops import pallas_kernels as pk

    own, other = np.arange(t)[:, None], np.arange(t)[None, :]
    if k_side:  # rows: the block's k positions; columns: q positions
        seen = (own <= other) & (own > other - window)
    else:
        seen = (other <= own) & (other > own - window)
    wide = block // step
    visited = masked = 0
    for at in range(0, t, block):
        rows = seen[at:at + block]
        for s in range(t // step):
            strip = rows[:, s * step:(s + 1) * step]
            if at <= s * step < at + block:  # one of the block's own steps
                # the strip from the diagonal on: the rows (or columns)
                # that can see the step at all
                lo = s * step - at
                part = strip[:lo + step] if k_side else strip[lo:]
                tiles = part.shape[0] // step
                visited += tiles
                hidden_past_diagonal = not (
                    part[:-step] if k_side else part[step:]).all()
                masked += tiles if hidden_past_diagonal else 1
            elif strip.any():
                visited += wide
                masked += 0 if strip.all() else wide
    assert pk._tile_counts(t, t, step, True, window, block, k_side) == (
        visited, masked, (t // step) ** 2)
    # never more than the causal kernels, which visit all under the
    # diagonal, and fewer once the window is shorter than T less a block
    causal = pk._tile_counts(t, t, step, True)[0]
    assert visited <= causal and (visited < causal or window > t - 2 * block)


def test_flash_window_calls_are_counted_with_what_they_visit():
    """A traced call under a window is counted under the window kernels'
    names with the tiles their loops visit and the limit the calls name:
    at the benchmark cell's shapes, 1024 x 512 blocks, 52 of the 136 tiles
    under the diagonal (45 at 512 x 512)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    before = dict(pk.FLASH_CALLS)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True, window=1024).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, k, k)
    new = {key: n - before.get(key, 0) for key, n in pk.FLASH_CALLS.items()
           if n != before.get(key, 0)}
    plan = pk._flash_plan(8192, 8192, 128, 128, itemsize=2, group=8)
    assert plan[:2] == (1024, 512)
    assert new == {(name, "bfloat16", (52, 44, 256), plan[3]): 1
                   for name in ("flash_win_fwd", "flash_win_bwd_dqkv")}
    assert pk._tile_counts(8192, 8192, 512, True, 1024, 512) == (
        45, 30, 256)
    assert pk._tile_counts(8192, 8192, 512, True)[0] == 136


@pytest.mark.parametrize("tq,tk", [(128, 256), (384, 128)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_causal_over_unequal_lengths_goes_to_xla(dtype, tq, tk):
    """The causal kernels unroll each block's own diagonal steps, which
    exist only where tq == tk: a causal rectangle is routed, and counted.
    Without the mask a rectangle either way stays on the kernels, forward
    and backward (the k-side kernel fits the two blocks to the other
    length), against the dense reference in float32."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(1, 1, tq, 32), dtype)
    k = jnp.asarray(rng.randn(1, 1, tk, 32), dtype)
    before = pk.FALLBACKS.get(("flash_attention", "causal_rectangle"), 0)
    out = pk.flash_attention(q, k, k, causal=True)
    assert pk.FALLBACKS[("flash_attention", "causal_rectangle")] == before + 1
    ref = pk._attention_reference(q, k, k, True, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-5)
    routed = dict(pk.FALLBACKS)
    out, pull = jax.vjp(
        lambda q, k, v: pk.flash_attention(q, k, v, causal=False), q, k, k)
    got = (out,) + pull(out)
    assert pk.FALLBACKS == routed
    f32 = [x.astype(jnp.float32) for x in (q, k, k, out)]
    want, pull_ref = jax.vjp(
        lambda q, k, v: pk._attention_reference(q, k, v, False, 32 ** -0.5),
        *f32[:3])
    want = (want,) + pull_ref(f32[3])
    for i, (a, b) in enumerate(zip(got, want)):
        tol = FLASH_TOLERANCE[dtype][i > 0]
        if dtype == "bfloat16":
            tol *= float(np.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=0)


@pytest.mark.parametrize("t,d,dtype", [(16384, 256, "bfloat16"),
                                        (4096, 128, "float32")],
                         ids=["only_the_pair_fits", "the_pair_fits_larger"])
def test_flash_backward_keeps_the_pair_where_only_it_fits(t, d, dtype):
    """T = 16384 at 256-wide keys and values: the one backward kernel's
    footprint (dQ's float32 [T, d] accumulator and its block on top of
    the whole q side) is past ``_VMEM_CAP`` and the pair's is not, so the
    backward stays on ``flash_bwd_dq`` + ``flash_bwd_dkv`` under a limit
    of their own, and nothing goes to XLA's dense path. f32 at T = 4096
    and 128-wide heads: the one kernel fits the default limit at 128 x
    128 alone, the pair at 512 x 512, so the backward is the pair at the
    larger blocks, under no limit of its own."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    itemsize = jnp.dtype(dtype).itemsize
    plan = pk._flash_plan(t, t, d, d, itemsize=itemsize)
    assert plan[2] is None and not plan[4]
    fused = functools.partial(pk._flash_vmem, t, t, d, d, itemsize=itemsize)
    if t == 16384:
        assert pk._VMEM_LIMIT < plan[3] <= pk._VMEM_CAP
        assert pk._VMEM_MARGIN * fused(*plan[:2]) > pk._VMEM_CAP
    else:
        assert plan[:4] == (512, 512, None, None)
        assert fused(256, 256) > pk._VMEM_LIMIT >= fused(128, 128)
    routed, before = dict(pk.FALLBACKS), dict(pk.FLASH_CALLS)
    x = jax.ShapeDtypeStruct((1, 2, t, d), dtype)
    jax.eval_shape(jax.grad(
        lambda q, k, v: pk.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
    new = {key: n - before.get(key, 0) for key, n in pk.FLASH_CALLS.items()
           if n != before.get(key, 0)}
    key = (dtype, pk._tile_counts(t, t, plan[1], True)) + (
        () if plan[3] is None else (plan[3],))
    assert new == {(name,) + key: 1 for name in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert pk.FALLBACKS == routed


def test_flash_kernel_names_are_read_by_the_benchmark():
    """Every name ``_flash_call`` gives is read by the benchmark's readers
    as they stand: ``flash_roofline`` (its ``KERNELS`` substrings) counts
    the kernels outside a window and none inside one, and
    ``window_attn_roofline`` (its ``flash_win_`` filter) counts the window
    kernels and no other. A name neither reads would drop that kernel's
    time from its roofline."""
    import importlib.util
    import os
    import sys

    from mxnet_tpu.ops import pallas_kernels as pk

    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")
    sys.path.insert(0, bench)  # the readers import ``flops`` beside them
    try:
        readers = {}
        for name in ("flash_roofline", "window_attn_roofline"):
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(bench, "layer_metrics", name + ".py"))
            readers[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(readers[name])
    finally:
        sys.path.remove(bench)

    class Reference:  # the work both readers ask a configuration for
        attention_work = window_attention_work = staticmethod(
            lambda config, traffic: (1e9, 1e6))

    run = {"reference": Reference, "config": {}, "traffic": {},
           "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}}

    def read(reader, op):
        trace = {"ops": {op + "_bf16_20_8192_256_": 1.0},
                 "module_runs": {"step": 1}}
        return readers[reader].compute(trace, {}, run) is not None

    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dqkv")
    for name in names:
        for window in (None, 512):
            kernel = pk._kernel_name(name, window)
            assert read("flash_roofline", kernel) == (window is None), kernel
            assert read("window_attn_roofline", kernel) == (
                window is not None), kernel
    assert not read("flash_roofline", "fusion")


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
