"""The main path's Pallas kernels must compile for the chip, not only run
under the interpreter: each is AOT-compiled here, with no chip attached,
by the TPU compiler for a described ``v5e:2x2`` topology. Interpret mode
accepted a block of 4 rows and a 31 MiB VMEM footprint; Mosaic refuses
both (PR 22).

One file, on purpose: only one process at a time may hold the TPU
library, so the topology is described inside a module-scoped fixture (a
worker that is not handed this file never loads it) and every compile
runs in the test's own process.
"""
import functools

import pytest

#: [batch, heads, T, head_dim] of the LM bench at T=1024 and T=8192, and
#: of the benchmark's own cell (gpt2m-train-t1024: 16 heads of 64)
FLASH_SHAPES = [(16, 8, 1024, 128), (2, 8, 8192, 128), (8, 16, 1024, 64)]
FLASH_IDS = ["T1024", "T8192", "cell"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compile_for_chip(monkeypatch):
    """Kernels on, interpret mode off (the code under test asks the
    default device, which is the CPU here), and the persistent cache off
    around the compiles: an entry written for a described chip cannot be
    read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(fn, *shapes):
    """Compile ``fn`` for the described chip; the kernel-carrying lines
    of its HLO."""
    import jax

    text = jax.jit(fn).lower(*shapes).compile().as_text()
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_forward_compiles(one_chip, shape):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    routed = dict(pk.FALLBACKS)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    calls = _kernels(functools.partial(pk.flash_attention, causal=True),
                     q, q, q)
    assert len(calls) == 1 and "/flash_fwd/" in calls[0]
    assert pk.FALLBACKS == routed  # nothing was routed to XLA


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_backward_compiles(one_chip, shape):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    calls = _kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert len(calls) == 2
    for name in ("flash_fwd", "flash_bwd_dqkv"):
        assert sum("/%s/" % name in ln for ln in calls) == 1, name


def test_flash_vmem_rule_refuses_what_the_compiler_refuses(one_chip):
    """f32 operands at T=8192, d=128 passed the old dtype-blind 8 MB rule
    and then failed in Mosaic ("Scoped allocation ... 18.06M and limit
    16.00M"); the rule routed them to XLA until PR 36 and now, since they
    fit under the default limit at no block, names the limit their calls
    ask for, under which both kernels compile; past the cap (T=32768)
    they still go to XLA, and that is counted. f32 at T=4096 overflows
    the default limit at 1024 x 512, 512 x 512 and 256 x 256 once the
    one backward kernel holds dQ's accumulator (Mosaic: 25.95M, 19.96M,
    16.85M; AOT), and the pair at 1024 x 512 only (16.76M in dq):
    the plan keeps the pair at the larger 512 x 512 and its three
    kernels compile, no limit named. 256-wide keys and values in bfloat16
    at T=8192: Mosaic refuses the smallest block under its default
    (17.50M in dkv alone), which is why the rule names a limit there and
    shrinks nothing."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    assert pk.flash_kernel_usable(8192, 8192, 128, 128, itemsize=2)
    assert pk._flash_plan(8192, 8192, 128, 128, itemsize=4)[3] > pk._VMEM_LIMIT
    before = pk.FALLBACKS.get(("flash_attention", "vmem"), 0)
    q = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.float32,
                             sharding=one_chip)
    assert len(_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)) == 2
    assert pk.FALLBACKS.get(("flash_attention", "vmem"), 0) == before
    assert not pk.flash_kernel_usable(32768, 32768, 128, 128, itemsize=4)
    q = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.float32,
                             sharding=one_chip)
    calls = _kernels(functools.partial(pk.flash_attention, causal=True),
                     q, q, q)
    assert calls == []
    assert pk.FALLBACKS[("flash_attention", "vmem")] == before + 1

    assert pk._flash_plan(4096, 4096, 128, 128, itemsize=4) == (
        512, 512, None, None, False)
    q = jax.ShapeDtypeStruct((4, 8, 4096, 128), jnp.float32,
                             sharding=one_chip)
    assert len(_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)) == 3
    assert pk.FALLBACKS[("flash_attention", "vmem")] == before + 1

    wide = jax.ShapeDtypeStruct((2, 8192, 256), jnp.bfloat16,
                                sharding=one_chip)
    stats = jax.ShapeDtypeStruct((2, 8, 8192), jnp.float32,
                                 sharding=one_chip)
    call = pk._flash_call("flash_bwd_dkv", "bfloat16", 2, 8192, 8192, 256,
                          256, True, 0.0625, 128, 128, False)
    with pytest.raises(Exception, match="Scoped allocation with size 17.50M"):
        call.lower(wide, wide, wide, wide, stats, stats).compile()


@pytest.mark.parametrize("shape,dtype", [
    ((128, 1000), "float32"),      # ResNet-50 head, rows a multiple of 8
    ((100, 1000), "float32"),      # the reference examples' batch of 100
    ((100, 1000), "bfloat16"),
    ((16384, 32000), "float32"),   # the LM's [B*T, vocab]
])
def test_fused_softmax_compiles(one_chip, shape, dtype):
    import jax

    from mxnet_tpu.ops import pallas_kernels as pk

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    calls = _kernels(pk.fused_softmax, x)
    assert len(calls) == 1 and "fused_softmax" in calls[0]


def test_rtc_kernel_compiles(one_chip):
    """A user kernel through mx.rtc.Rtc lowers without interpret mode."""
    import jax

    import mxnet_tpu as mx

    x = mx.nd.zeros((256, 512))
    k = mx.rtc.Rtc("axpy", [("x", x)], [("y", x)],
                   "y[...] = x[...] * 2.0 + 1.0")
    spec = ((128, 512), lambda i: (i, 0))
    prog = k._compile((2,), ((spec,), (spec,)))
    shape = jax.ShapeDtypeStruct((256, 512), "float32", sharding=one_chip)
    text = prog.lower(shape).compile().as_text()
    assert "tpu_custom_call" in text


def _compiles_as_one_backward(one_chip, heads, kv_heads, d, dv, window):
    """An LM cell's attention call at T = 8192, forward and backward: one
    ``flash_fwd`` and ONE ``flash_bwd_dqkv`` call (``flash_win_*`` under a
    window), counted with the limit the plan names, nothing routed to
    XLA, the plan at ``_select_blocks``' blocks under a limit of its own
    at or under ``_VMEM_CAP``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    def attend(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, window=window)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    plan = pk._flash_plan(8192, 8192, d, dv, itemsize=2,
                          group=heads // kv_heads)
    assert plan[:3] == pk._select_blocks(8192, 8192)[:2] + (None,)
    assert pk._VMEM_LIMIT < plan[3] <= pk._VMEM_CAP and plan[4]
    routed, took = dict(pk.FALLBACKS), dict(pk.FLASH_CALLS)
    q = jax.ShapeDtypeStruct((1, heads, 8192, d), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, kv_heads, 8192, d), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, kv_heads, 8192, dv), jnp.bfloat16,
                             sharding=one_chip)
    prefix = "flash_" if window is None else "flash_win_"
    calls = _kernels(attend, q, k, v)
    assert len(calls) == 1 and "/%sfwd/" % prefix in calls[0]
    calls = _kernels(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert len(calls) == 2 and pk.FALLBACKS == routed
    for name in ("fwd", "bwd_dqkv"):
        assert sum("/%s%s/" % (prefix, name) in ln for ln in calls) == 1, name
    new = [key for key, n in pk.FLASH_CALLS.items() if n != took.get(key, 0)]
    assert sorted(key[0] for key in new) == [prefix + "bwd_dqkv",
                                             prefix + "fwd"]
    assert all(key[3:] == (plan[3],) for key in new)


@pytest.mark.parametrize("heads,d,dv", [(32, 192, 128), (20, 256, 256)],
                         ids=["kimi-192-128", "glm-256-256"])
def test_latent_attention_shape_compiles(one_chip, heads, d, dv):
    """The hybrid LMs' latent-attention calls at T = 8192: Kimi-Linear's
    32 heads of 192-wide keys and 128-wide values (a 192-wide block fills
    256 lanes in VMEM), GLM-4.7-Flash's 20 heads of 256-wide keys AND
    values. GLM's fit under Mosaic's default 16 MiB at no block whatever
    the backward (``_flash_vmem`` read 18.0 MB at 128 x 128 for the pair,
    Mosaic 17.50M; AOT); Kimi's ran at 256 x 256 under it until
    the backward held dQ's float32 [T, d] accumulator and block beside
    the whole q side, and now names a limit at 1024 x 512 as GLM's does
    (AOT)."""
    _compiles_as_one_backward(one_chip, heads, heads, d, dv, None)


@pytest.mark.parametrize("heads,kv_heads,window", [
    (32, 4, None), (32, 4, 1024), (48, 8, None), (64, 8, 512)],
    ids=["full", "window", "laguna-full", "laguna-window"])
def test_grouped_query_shapes_compile(one_chip, heads, kv_heads, window):
    """The grouped-query cells' attention calls at T = 8192, heads of 128:
    Mellum2's 32 query heads over 4 key/value heads with the 1,024 window
    and without, Laguna's 48 over 8 in its full layers and 64 over 8 under
    its 512 window. They ran at 512 x 512 under the default limit (at
    1024 x 512 dkv's float32 per-head results took 16.69M of 16.00M inside
    the whole step; AOT); with dQ's accumulator and block in the
    one backward kernel no block fits the default, so they run at 1024 x
    512 under a limit of their own (AOT)."""
    _compiles_as_one_backward(one_chip, heads, kv_heads, 128, 128, window)


def test_flash_backward_pair_compiles_where_only_it_fits(one_chip):
    """T = 16384 at 256-wide keys and values: the one backward kernel's
    footprint is past ``_VMEM_CAP``, the pair's is not, and the pair
    compiles under the limit the plan names, with the forward."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    plan = pk._flash_plan(16384, 16384, 256, 256, itemsize=2)
    assert plan[2] is None and not plan[4]

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    routed = dict(pk.FALLBACKS)
    q = jax.ShapeDtypeStruct((1, 2, 16384, 256), jnp.bfloat16,
                             sharding=one_chip)
    calls = _kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert len(calls) == 3 and pk.FALLBACKS == routed
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum("/%s/" % name in ln for ln in calls) == 1, name


def test_kda_kernels_compile(one_chip):
    """The gated delta rule's two stages, forward and backward, at the
    benchmark cell's shapes (B=1, T=8192, 32 heads of 128) and operand
    type: one kernel each, counted, and nothing routed to XLA."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda
    from mxnet_tpu.ops import pallas_kernels as pk

    def loss(q, k, v, g, beta):
        return kda.kda_attention(q, k, v, g, beta, dtype=jnp.bfloat16).sum()

    routed, took = dict(pk.FALLBACKS), dict(kda.KDA_CALLS)
    x = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32, sharding=one_chip)
    calls = _kernels(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x, x, x, x, b)
    for kernel in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_state_fwd",
                   "kda_state_bwd"):
        assert sum(kernel in ln for ln in calls) == 1, kernel
        assert kda.KDA_CALLS[(kernel, "bfloat16")] == took.get(
            (kernel, "bfloat16"), 0) + 1, kernel
    assert pk.FALLBACKS == routed


#: rows, the model's width, an expert's width, experts held: the sorted
#: bucket of the benchmark's four MoE cells
GROUPED_CELLS = {"mellum2": (65536, 2304, 896, 16),
                 "kimi": (16384, 2304, 1024, 8),
                 "glm": (32768, 2048, 1536, 8),
                 "laguna": (65536, 2048, 512, 32)}


def _grouped_operands(one_chip, m, k, n, g):
    import jax
    import jax.numpy as jnp

    return (jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("product", ["up", "down", "mlp"])
@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_grouped_products_compile(one_chip, cell, product):
    """The expert layer's grouped products at the four cells' shapes, each
    in all its directions at the tiles ``_plan`` picks, counted, nothing
    routed to XLA. ``up``: one product at gate's or up's shape ([rows, d]
    x [g, d, f]); ``down``: one at the down product's ([rows, f] x [g, f,
    d]); ``mlp``: ``expert_mlp``, as ``moe_share_ffn`` runs it: the
    tokens' rows gathered, gate and up, the down product fed ``silu(gate)
    * up`` as it loads them, the routing weight in its store, rebuilt with
    the ``scale_cotangent`` epilogue for the weight's cotangent (its own
    plan: the cotangent's float32 block beside the operands) and in the
    down weights' gradient, the hidden's cotangent written as gate's and
    up's through the SiLU, and the input's cotangent through gate and up
    as ONE float32 ``moe_gmm_pair``. Forward results float32; a bfloat16
    input's cotangent (the right operand read transposed) and the weights'
    gradient in bfloat16, rounded in the kernels' stores."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    m, d, f, g = GROUPED_CELLS[cell]
    k, n = (f, d) if product == "down" else (d, f)
    lhs, rhs, sizes = _grouped_operands(one_chip, m, k, n, g)
    scale = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=one_chip)
    if product == "mlp":  # the tokens' rows; the bucket's are gathered
        lhs = jax.ShapeDtypeStruct((8192, d), jnp.float32, sharding=one_chip)
    tok = jnp.arange(m, dtype=jnp.int32) % 8192

    def loss(a, b, scale, sizes):
        if product == "mlp":
            out = gm.expert_mlp(a, tok, jnp.sum(sizes), b, jnp.flip(b, 0),
                                jnp.swapaxes(b, 1, 2), sizes, scale)
        else:
            out = gm.grouped_matmul(a, b, sizes)
        return jnp.sum(out), out

    routed, took = dict(pk.FALLBACKS), dict(gm.GMM_CALLS)
    calls = _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                        has_aux=True), lhs, rhs, scale, sizes)
    new = {key: count - took.get(key, 0)
           for key, count in gm.GMM_CALLS.items() if count != took.get(key)}
    bf, f32 = "bfloat16", "float32"
    forward = gm._plan(m, k, n, g, 2)[0]
    to_rhs = gm._plan(m, k, n, g, 2, "moe_tgmm", out_itemsize=2)[0]
    if product == "mlp":
        fused = gm.SWIGLU + "+" + gm.SCALE_COTANGENT
        want = {("moe_gmm", bf, f32, False, None, forward): 2,
                ("moe_gmm", bf, f32, True, gm.SWIGLU, gm._plan(
                    m, n, k, g, 2, scaled=True, swiglu=True)[0]): 1,
                ("moe_gmm", bf, bf, True, fused, gm._plan(
                    m, n, k, g, 2, out_itemsize=2, scaled=True, swiglu=True,
                    epilogue=gm.SCALE_COTANGENT)[0]): 1,
                ("moe_gmm", bf, bf, False, gm.SWIGLU_COTANGENT, gm._plan(
                    m, k, n, g, 2, out_itemsize=2,
                    epilogue=gm.SWIGLU_COTANGENT)[0]): 1,
                ("moe_gmm_pair", bf, f32, False, None,
                 gm._plan(m, n, k, g, 2, "moe_gmm_pair")[0]): 1,
                ("moe_tgmm", bf, bf, False, None, to_rhs): 2,
                ("moe_tgmm", bf, bf, False, gm.SWIGLU, gm._plan(
                    m, n, k, g, 2, "moe_tgmm", out_itemsize=2,
                    swiglu=True)[0]): 1,
                ("moe_take", f32, bf, False, None, gm._take_plan(
                    8192, d, m, jnp.float32, jnp.bfloat16)[0]): 1,
                ("moe_combine", f32, f32, False, None, gm._combine_plan(
                    8192, d, m, jnp.float32)[0]): 1}
    else:
        want = {("moe_gmm", bf, f32, False, None, forward): 1,
                ("moe_gmm", bf, bf, False, None,
                 gm._plan(m, n, k, g, 2, out_itemsize=2)[0]): 1,
                ("moe_tgmm", bf, bf, False, None, to_rhs): 1}
    assert new == want
    # by the instruction's own name: a line may take another kernel's
    # result as an operand, and name it there
    named = {kernel: sum(kernel in ln.split("=")[0] for ln in calls)
             for kernel in ("moe_gmm", "moe_gmm_pair", "moe_tgmm")}
    named["moe_gmm"] -= named["moe_gmm_pair"]  # a pair's line names both
    assert named == {kernel: sum(count for key, count in want.items()
                                 if key[0] == kernel) for kernel in named}
    assert len(calls) == sum(want.values())
    assert pk.FALLBACKS == routed


@pytest.mark.parametrize("kernel,plan,result,size", [
    ("moe_gmm", (512, 2304, 896), "float32", "18.46M"),  # the operand whole
    ("moe_gmm", (1024, 768, 896), "float32", "16.12M"),
    ("moe_gmm", (512, 2304, 896), "bfloat16", "16.96M"),
    ("moe_tgmm", (256, 2304, 896), "float32", "18.88M"),
    ("moe_tgmm", (1024, 1152, 896), "float32", "21.30M"),
    ("moe_tgmm", (256, 2304, 896), "bfloat16", "18.88M"),
    # the input's cotangent through gate and up, [rows, f] x [g, d, f]^T
    ("moe_gmm_pair", (512, 896, 1152), "float32", "19.00M"),
    ("moe_gmm_pair", (512, 896, 1152), "bfloat16", "16.75M"),
    ("moe_gmm_pair", (128, 896, 2304), "bfloat16", "17.75M"),
])
def test_grouped_vmem_rule_refuses_what_the_compiler_refuses(
        one_chip, kernel, plan, result, size):
    """Tiles past the scoped VMEM at the Mellum2 cell's gate/up product and
    its paired cotangent: ``_vmem`` counts them over the limit, so
    ``_plan`` never offers them, and Mosaic refuses them when handed them
    all the same."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    m, k, n, g = 65536, 2304, 896, 16
    pair = kernel == "moe_gmm_pair"
    if pair:
        k, n = n, k
    out = jnp.dtype(result).itemsize
    assert gm._vmem(kernel, *plan, 2, k // plan[1], out) > pk._VMEM_LIMIT
    assert gm._plan(m, k, n, g, 2, kernel, out_itemsize=out)[0] != plan
    lhs, rhs, sizes = _grouped_operands(one_chip, m, k, n, g)
    operands = (lhs, rhs)
    if kernel == "moe_tgmm":
        operands = (lhs, jax.ShapeDtypeStruct((m, n), jnp.bfloat16,
                                              sharding=one_chip))
    elif pair:
        operands = 2 * (lhs, jax.ShapeDtypeStruct(
            (g, n, k), jnp.bfloat16, sharding=one_chip))
    call = gm._call(kernel, "bfloat16", result, m, k, n, g, plan, pair,
                    False, False)
    with pytest.raises(Exception, match="Scoped allocation with size " + size):
        call.lower(sizes, *operands).compile()


#: tokens, width and bucket rows of the four MoE cells' sums back to the
#: tokens
COMBINE_CELLS = {"mellum2": (8192, 2304, 65536),
                 "kimi": (8192, 2304, 16384),
                 "glm": (8192, 2048, 32768),
                 "laguna": (8192, 2048, 65536)}


@pytest.mark.parametrize("cell", sorted(COMBINE_CELLS))
def test_combine_compiles(one_chip, cell):
    """``moe_combine`` at the four cells' shapes, as ``combine``'s forward,
    stopping at a landed count, under the limit its plan names (the whole
    width's sum in VMEM, past Mosaic's default); ``moe_take`` as
    ``combine``'s transpose, under the limit its plan names (the whole
    width of the tokens' rows in VMEM)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    n, d, rows = COMBINE_CELLS[cell]
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((rows, d), f32, sharding=one_chip),
              jax.ShapeDtypeStruct((n, d), f32, sharding=one_chip),
              jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
              jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))

    def loss(values, weight, tok, landed):
        return jnp.sum(gm.combine(values, tok, n, landed) * weight)

    routed = dict(pk.FALLBACKS)
    calls = _kernels(jax.value_and_grad(loss), *shapes)
    assert len(calls) == 2
    assert sum("moe_combine" in ln for ln in calls) == 1
    assert sum("moe_take" in ln for ln in calls) == 1
    assert gm._combine_plan(n, d, rows, f32)[1] > pk._VMEM_LIMIT
    for out in (f32, jnp.bfloat16):
        assert gm._take_plan(n, d, rows, f32, out)[1] > pk._VMEM_LIMIT
    assert pk.FALLBACKS == routed


def test_a_mellum2_layer_sums_back_by_the_kernel(one_chip):
    """One Mellum2 expert layer's value and gradient as the cell holds it
    (checkpointed, ``moe_sort`` and ``moe_hidden`` kept), compiled: the
    forward's sum back to the tokens and the input's cotangent's are the
    two ``moe_combine`` calls, and no scatter over [8192, 2304] is left
    (XLA's sort, permute and sorted scatter); the gathers are ``moe_take``
    calls, and nothing over the whole bucket is XLA's: no gather of
    [65536, 2304] rows, no reduction of the routing weight's cotangent
    over [65536] rows and no fusion over the [65536, 896] hidden (the SiLU
    and its gradient are in the products' loads and stores)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    held, top_k = (0, 16), 8
    params = jax.eval_shape(lambda key: moe.init_share_params(
        key, 64, held, 2304, 896, 0, score="softmax"),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((8192, 2304), jnp.float32, sharding=one_chip)
    layer = jax.checkpoint(
        lambda p, x: moe.moe_share_ffn(p, x, top_k, held, dtype="bfloat16",
                                       score="softmax")[0],
        policy=jax.checkpoint_policies.save_only_these_names(
            "moe_sort", "moe_hidden"))
    text = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(layer(p, x) ** 2), argnums=(0, 1))).lower(
            params, x).compile().as_text()
    lines = text.splitlines()
    assert sum("tpu_custom_call" in ln and "moe_combine" in ln
               for ln in lines) == 2
    assert not [ln for ln in lines
                if " scatter(" in ln and "f32[8192,2304]" in ln]
    assert sum("tpu_custom_call" in ln and "moe_take" in ln
               for ln in lines) >= 2
    assert not [ln for ln in lines if " gather(" in ln and "[65536,2304]" in ln]
    assert not [ln for ln in lines if " reduce(" in ln and "f32[65536]" in ln]
    assert not [ln for ln in lines if " fusion(" in ln
                and "[65536,896]" in ln.split(" fusion(")[0]]
