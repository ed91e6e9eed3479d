"""``ops/grouped_matmul.py``: the ``moe_gmm`` / ``moe_tgmm`` kernels in
interpret mode against ``lax.ragged_dot``, values and both gradients; a
result in the operands' type, the rows' scale in the store and the paired
cotangent against the float32 results they replace; the visit list they
walk; the plan and what it routes to XLA; ``moe_combine`` and its
transpose pair against the scatter-add and the gather they replace."""
import numpy as np
import pytest

M, K, N = 640, 256, 128  # five row tiles of 128

#: how 640 rows fall into groups: name -> sizes (they add up to M)
LAYOUTS = {
    "inside_tiles": [100, 200, 77, 263],
    "on_tile_edges": [128, 256, 128, 128],
    "empty_first": [0, 300, 40, 300],
    "empty_middle": [250, 0, 0, 390],
    "empty_last": [320, 319, 1, 0],
    "one_group": [0, 0, 640, 0],
    "tail_in_last": [3, 0, 5, 632],  # as moe_share_ffn sends its empty rows
    "one_row_each": [1, 1, 1, 637],
}


@pytest.fixture(autouse=True)
def _kernels_on(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "1")


def _operands(dtype, m=M, k=K, n=N, g=4):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    return (jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype),
            jax.random.normal(keys[1], (g, k, n), jnp.float32).astype(dtype),
            jax.random.normal(keys[2], (m, n), jnp.float32))


def _value_and_grads(product, lhs, rhs, sizes, weight):
    import jax
    import jax.numpy as jnp

    def loss(a, b):
        out = product(a, b, sizes)
        return jnp.sum(out * weight), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(lhs, rhs)
    return [np.asarray(x, np.float64) for x in (out,) + grads]


def _ragged_dot(a, b, sizes):
    import jax.numpy as jnp
    from jax import lax

    return lax.ragged_dot(a, b, sizes, preferred_element_type=jnp.float32)


def _close(got, want, rel):
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= rel * scale


@pytest.mark.parametrize("dtype,rel", [("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_values_and_gradients_match_ragged_dot(layout, dtype, rel):
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    routed = dict(pk.FALLBACKS)
    lhs, rhs, weight = _operands(dtype)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    got = _value_and_grads(gm.grouped_matmul, lhs, rhs, sizes, weight)
    want = _value_and_grads(_ragged_dot, lhs, rhs, sizes, weight)
    assert got[0].dtype == np.float64 and pk.FALLBACKS == routed
    _close(got[0], want[0], 1e-5)  # float32 accumulation in either type
    _close(got[1], want[1], rel)
    _close(got[2], want[2], rel)
    # an expert that gets nothing: its gradient is written, as zeros
    for group, size in enumerate(LAYOUTS[layout]):
        if size == 0:
            assert not got[2][group].any()


@pytest.mark.parametrize("layout", ["inside_tiles", "empty_middle",
                                    "tail_in_last"])
def test_tiles_that_step_through_both_widths(layout, monkeypatch):
    """The plan the benchmark's shapes force, forced here: the contraction
    in steps over an accumulator, more than one column tile, the weights'
    gradient in tiles of both widths."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_plan",
                        lambda *a, **kw: ((128, 128, 128), None))
    lhs, rhs, weight = _operands("float32", n=256)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    got = _value_and_grads(gm.grouped_matmul, lhs, rhs, sizes, weight)
    want = _value_and_grads(_ragged_dot, lhs, rhs, sizes, weight)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("end", ["every_row", "landed", "none"])
@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_row_is_visited_once_and_the_list_is_static(layout, tm, end):
    """The visit list: ``m // tm + g - 1`` long whatever the sizes, tiles
    never going back, every group at least once, and the masks of the real
    visits cover each grouped row exactly once, under its own group. Where
    the groups end before the last row (``landed``: the 640 rows of the
    layout in 768; ``none``: nothing lands), the real visits end at the
    tile of the last grouped row (tile 0 when there is none) and cover no
    row past it; the surplus visits repeat that tile and group."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    sizes = np.asarray(LAYOUTS[layout])
    if end == "every_row":
        sizes[-1] += 768 - M  # six tiles of 128, three of 256
    elif end == "none":
        sizes[:] = 0
    offsets, groups, tiles, real = (np.asarray(x) for x in gm._visits(
        jnp.asarray(sizes, jnp.int32), 768, tm))
    assert len(groups) == len(tiles) == 768 // tm + len(sizes) - 1
    assert np.array_equal(offsets, np.concatenate([[0], np.cumsum(sizes)]))
    assert (np.diff(tiles) >= 0).all() and (np.diff(groups) >= 0).all()
    real = int(real[0])
    assert set(groups[:real]) == set(range(len(sizes)))
    assert (groups[real:] == groups[real - 1]).all()
    assert (tiles[real:] == tiles[real - 1]).all()
    assert tiles[real - 1] == max(int(sizes.sum()) - 1, 0) // tm
    owner = np.full(768, -1)
    for v in range(real):
        row = tiles[v] * tm + np.arange(tm)
        mine = (row >= offsets[groups[v]]) & (row < offsets[groups[v] + 1])
        assert (owner[row[mine]] == -1).all()
        owner[row[mine]] = groups[v]
    want = np.full(768, -1)
    want[:sizes.sum()] = np.repeat(np.arange(len(sizes)), sizes)
    assert np.array_equal(owner, want)


def test_inside_a_cond_the_kernels_run_as_outside():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import grouped_matmul as gm

    lhs, rhs, weight = _operands("float32")
    sizes = jnp.asarray(LAYOUTS["inside_tiles"], jnp.int32)

    def under_cond(a, b, sizes):
        return lax.cond(jnp.sum(sizes) <= M,
                        lambda: gm.grouped_matmul(a, b, sizes),
                        lambda: jnp.zeros((M, N), jnp.float32))

    took = dict(gm.GMM_CALLS)
    got = _value_and_grads(jax.jit(under_cond), lhs, rhs, sizes, weight)
    want = _value_and_grads(_ragged_dot, lhs, rhs, sizes, weight)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)
    assert gm.GMM_CALLS != took


@pytest.mark.parametrize("shape,switch,reason", [
    ((M, K, 96), "1", "untileable"),    # a width that is no multiple of 128
    ((M, 200, N), "1", "untileable"),
    ((100, K, N), "1", "untileable"),
    ((M, K, N), "0", "disabled"),
])
def test_what_the_kernels_cannot_take_goes_to_xla_and_is_counted(
        shape, switch, reason, monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", switch)
    m, k, n = shape
    lhs, rhs, weight = _operands("float32", m, k, n)
    sizes = jnp.asarray([m // 4, 0, m // 2, m - m // 4 - m // 2], jnp.int32)
    before = pk.FALLBACKS.get(("moe_gmm", reason), 0)
    took = dict(gm.GMM_CALLS)
    got = _value_and_grads(gm.grouped_matmul, lhs, rhs, sizes, weight)
    want = _value_and_grads(_ragged_dot, lhs, rhs, sizes, weight)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert pk.FALLBACKS[("moe_gmm", reason)] == before + 1
    assert gm.GMM_CALLS == took


def _new_calls(took):
    from mxnet_tpu.ops import grouped_matmul as gm

    return {key: n - took.get(key, 0) for key, n in gm.GMM_CALLS.items()
            if n != took.get(key, 0)}


@pytest.mark.parametrize("entry", ["plain", "mlp"])
def test_gmm_calls_records_plan_types_and_scale_of_each_direction(entry):
    """The forward products write float32, every cotangent the operands'
    type. ``mlp``: ``expert_mlp``'s down product's forward store scales,
    fed ``silu(gate) * up`` as it loads them, and its rebuilt twin stores
    no product: its ``scale_cotangent`` epilogue writes the cotangent times
    the scale in the operands' type (and the scale's cotangent beside it);
    the hidden's cotangent is written through the SiLU; the input's
    cotangent through gate and up is ONE call over two right-hand
    blocks."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    lhs, rhs, weight = _operands("bfloat16")
    sizes = jnp.asarray(LAYOUTS["inside_tiles"], jnp.int32)
    scale = jnp.linspace(0.0, 1.0, M)
    bf, f32 = "bfloat16", "float32"
    took = dict(gm.GMM_CALLS)
    if entry == "mlp":
        x = lhs[:160].astype(jnp.float32)  # 160 tokens of width K
        tok = jnp.arange(M, dtype=jnp.int32) % 160
        jax.make_jaxpr(jax.grad(lambda x, b, s: jnp.sum(gm.expert_mlp(
            x, tok, jnp.int32(M), b, jnp.flip(b, 0), jnp.swapaxes(b, 1, 2),
            sizes, s)), argnums=(0, 1, 2)))(x, rhs, scale)

        def tiles(k, n, kernel="moe_gmm", **how):
            return gm._plan(M, k, n, 4, 2, kernel, **how)[0]

        want = {("moe_gmm", bf, f32, False, None, tiles(K, N)): 2,
                ("moe_gmm", bf, f32, True, gm.SWIGLU,
                 tiles(N, K, scaled=True, swiglu=True)): 1,
                ("moe_gmm", bf, bf, True, gm.SWIGLU + "+" + gm.SCALE_COTANGENT,
                 tiles(N, K, out_itemsize=2, scaled=True, swiglu=True,
                       epilogue=gm.SCALE_COTANGENT)): 1,
                ("moe_gmm", bf, bf, False, gm.SWIGLU_COTANGENT,
                 tiles(K, N, out_itemsize=2,
                       epilogue=gm.SWIGLU_COTANGENT)): 1,
                ("moe_gmm_pair", bf, f32, False, None,
                 tiles(N, K, "moe_gmm_pair")): 1,
                ("moe_tgmm", bf, bf, False, None,
                 tiles(K, N, "moe_tgmm", out_itemsize=2)): 2,
                ("moe_tgmm", bf, bf, False, gm.SWIGLU,
                 tiles(N, K, "moe_tgmm", out_itemsize=2, swiglu=True)): 1,
                ("moe_take", f32, bf, False, None,
                 gm._take_plan(160, K, M, jnp.float32, bf)[0]): 1,
                ("moe_combine", f32, f32, False, None,
                 gm._combine_plan(160, K, M, jnp.float32)[0]): 1}
    else:
        jax.make_jaxpr(jax.grad(lambda a, b: jnp.sum(gm.grouped_matmul(
            a, b, sizes) * weight), argnums=(0, 1)))(lhs, rhs)
        want = {("moe_gmm", bf, f32, False, None, (128, K, N)): 1,  # fwd
                ("moe_gmm", bf, bf, False, None, (128, N, K)): 1,  # d lhs
                ("moe_tgmm", bf, bf, False, None, (128, K, N)): 1}  # d rhs
    assert _new_calls(took) == want


#: tokens, width, an expert's width, experts, held, top k, router, shared
#: experts: the expert layer of the benchmark's four MoE cells
CELLS = {"mellum2": (8192, 2304, 896, 64, (0, 16), 8, "softmax", 0),
         "glm": (8192, 2048, 1536, 64, (0, 8), 4, "sigmoid", 1),
         "kimi": (8192, 2304, 1024, 256, (0, 8), 8, "sigmoid", 1),
         "laguna": (8192, 2048, 512, 256, (0, 32), 8, "sigmoid", 1)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_layer_writes_each_result_as_its_consumer_reads_it(cell):
    """``moe_share_ffn``'s gradient traced (nothing runs) at a cell's
    shapes. Float32: the three forward products (the down product's with
    its scale, fed ``silu(gate) * up`` as it loads gate and up: the hidden
    is never stored), the hidden's cotangent written through the SiLU as
    gate's and up's cotangents in the operands' type
    (``swiglu_cotangent``), and the input's cotangent through gate and up,
    ONE pair whose consumer, the sum back to the float32 tokens, reads
    float32. In the operands' type: the down product rebuilt with the
    ``scale_cotangent`` epilogue (the cotangent times the routing weight,
    and the weight's cotangent beside it: no float32 product is stored)
    and every weights' gradient. Both sums back to the tokens, the
    forward's and the input's cotangent's, are ``moe_combine``; both
    gathers, the tokens' rows in the operands' type and the output's
    cotangent's rows in float32, are ``moe_take``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import moe

    tokens, d, ff, experts, held, top_k, score, shared = CELLS[cell]
    params = jax.eval_shape(
        lambda key: moe.init_share_params(key, experts, held, d, ff, shared,
                                          score=score), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((tokens, d), jnp.float32)
    took, routed = dict(gm.GMM_CALLS), dict(pk.FALLBACKS)
    jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.moe_share_ffn(
        p, x, top_k, held, dtype="bfloat16", score=score)[0]),
        argnums=(0, 1)))(params, x)
    m = moe.share_bucket_rows(tokens, experts, held, top_k)
    g = held[1] - held[0]
    bf, f32 = "bfloat16", "float32"

    def tiles(k, n, kernel="moe_gmm", **how):
        return gm._plan(m, k, n, g, 2, kernel, **how)[0]

    down = tiles(ff, d, scaled=True, swiglu=True)
    rebuilt = tiles(ff, d, scaled=True, out_itemsize=2, swiglu=True,
                    epilogue=gm.SCALE_COTANGENT)
    combine = gm._combine_plan(tokens, d, m, jnp.float32)[0]
    new = _new_calls(took)
    # counted per trace: twice where the sorted path is a ``lax.cond``'s
    # branch (the Kimi cell's bucket is smaller than all that could land)
    traces = new[("moe_gmm", bf, f32, True, gm.SWIGLU, down)]
    assert traces == (2 if cell == "kimi" else 1)
    assert {key: n / traces for key, n in new.items()} == {
        ("moe_gmm", bf, f32, False, None, tiles(d, ff)): 2,    # gate, up
        ("moe_gmm", bf, f32, True, gm.SWIGLU, down): 1,        # down
        ("moe_gmm", bf, bf, True, gm.SWIGLU + "+" + gm.SCALE_COTANGENT,
         rebuilt): 1,
        ("moe_gmm", bf, bf, False, gm.SWIGLU_COTANGENT,
         tiles(d, ff, out_itemsize=2, epilogue=gm.SWIGLU_COTANGENT)): 1,
        ("moe_gmm_pair", bf, f32, False, None,
         tiles(ff, d, "moe_gmm_pair")): 1,
        ("moe_tgmm", bf, bf, False, None,
         tiles(d, ff, "moe_tgmm", out_itemsize=2)): 2,
        ("moe_tgmm", bf, bf, False, gm.SWIGLU,
         tiles(ff, d, "moe_tgmm", out_itemsize=2, swiglu=True)): 1,
        # the forward's sum back to the tokens and the input's cotangent's
        ("moe_combine", f32, f32, False, None, combine): 2,
        # the tokens' rows gathered in the operands' type, and the
        # output's cotangent gathered to the bucket's rows
        ("moe_take", f32, bf, False, None,
         gm._take_plan(tokens, d, m, jnp.float32, bf)[0]): 1,
        ("moe_take", f32, f32, False, None,
         gm._take_plan(tokens, d, m, jnp.float32, f32)[0]): 1}
    assert pk.FALLBACKS == routed


#: how many of a token's top 4 of 16 experts land on the 4 held: name ->
#: (tokens whose four all land, tokens with one landing) of 256
SHARE_FILLS = {"nothing_lands": (0, 0), "typical": (40, 100),
               "full_bucket": (256, 0)}


@pytest.mark.parametrize("fill", sorted(SHARE_FILLS))
def test_the_layer_with_kernels_is_the_layer_without(fill, monkeypatch):
    """``moe_share_ffn`` (4 of 16 experts held, top 4, a bucket of every
    assignment that could land) with the kernels on, stopping at the landed
    rows, and off, ``lax.ragged_dot`` and the scatter-add over the whole
    bucket: the same counts, and the value and every gradient alike, where
    nothing lands, at a typical fill and with the bucket full."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel import moe

    tokens, d, ff, experts, held, top_k = 256, 128, 128, 16, (0, 4), 4
    assert moe.share_bucket_rows(tokens, experts, held, top_k) == (
        tokens * top_k)
    params = moe.init_share_params(jax.random.PRNGKey(11), experts, held, d,
                                   ff)
    params["router"] = jnp.eye(d, experts)  # the first 16 channels route
    params["router_bias"] = jnp.zeros(experts)
    every, one = SHARE_FILLS[fill]
    rng = np.random.RandomState(12)
    logits = np.full((tokens, experts), -3.0, np.float32)
    for t in range(tokens):
        chosen = (np.arange(4) if t < every else
                  np.concatenate([[t % 4], 4 + rng.permutation(12)[:3]])
                  if t < every + one else 4 + rng.permutation(12)[:4])
        logits[t, chosen] = 3.0 + rng.rand(4)
    x = jax.random.normal(jax.random.PRNGKey(13), (tokens, d))
    x = x.at[:, :experts].set(jnp.asarray(logits))
    weight = jax.random.normal(jax.random.PRNGKey(14), (tokens, d))

    def run(switch):
        monkeypatch.setenv("MXNET_PALLAS", switch)

        def loss(p, x):
            y, counts = moe.moe_share_ffn(p, x, top_k, held)
            return jnp.sum(y * weight), (y, counts)

        (_, (y, counts)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        return y, counts, grads

    took = dict(gm.GMM_CALLS)
    y, counts, grads = run("1")
    assert gm.GMM_CALLS != took
    want_y, want_counts, want_grads = run("0")
    assert np.array_equal(counts, want_counts)
    assert int(np.sum(counts)) == 4 * every + one
    _close(np.asarray(y), np.asarray(want_y), 1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert np.isfinite(np.asarray(got)).all()
        _close(np.asarray(got), np.asarray(want), 1e-4)


#: a plan of one step over the contraction and one that steps through both
#: widths (an accumulator in ``moe_gmm``, tiles of both widths in ``moe_tgmm``)
PLANS = {"one_step": None, "stepped": (128, 128, 128)}


def _direction(kind, lhs, rhs, g_out):
    """(kernel, operands, transposed, the plan's (m, k, n)) of one of the
    three products a layer's gradient needs."""
    if kind == "gmm":
        return "moe_gmm", (lhs, rhs), False, (M, K, rhs.shape[2])
    if kind == "gmm_t":
        return "moe_gmm", (g_out, rhs), True, (M, rhs.shape[2], K)
    return "moe_tgmm", (lhs, g_out), False, (M, K, rhs.shape[2])


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("kind", ["gmm", "gmm_t", "tgmm"])
@pytest.mark.parametrize("layout", ["tail_in_last", "empty_middle",
                                    "inside_tiles"])
def test_a_bfloat16_result_is_the_float32_result_rounded(layout, kind, plan):
    """Float32 accumulation and ONE rounding at the store: to the last bit
    what ``astype`` makes of the float32 result, in every group and in an
    empty group's zeros."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    lhs, rhs, g_out = _operands("bfloat16", n=256)
    g_out = g_out.astype(jnp.bfloat16)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    kernel, operands, transposed, (m, k, n) = _direction(kind, lhs, rhs, g_out)
    tiles = PLANS[plan] or gm._plan(m, k, n, 4, 2, kernel, out_itemsize=2)[0]
    assert (k // tiles[1] > 1) == (plan == "stepped")
    wide, narrow = (gm._product(kernel, operands, sizes, tiles, dtype,
                                transposed=transposed)
                    for dtype in (jnp.float32, jnp.bfloat16))
    assert wide.dtype == jnp.float32 and narrow.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(wide.astype(jnp.bfloat16), np.float32),
                          np.asarray(narrow, np.float32))
    assert np.asarray(narrow, np.float32).any()


#: groups that end before the last row: name -> sizes (they add up to less
#: than M), the rows past their end as ``moe_share_ffn`` leaves them
LANDED = {
    "cut_inside_a_tile": [100, 0, 77, 130],  # ends at 307, in tile 2 of 128
    "on_a_tile_edge": [128, 0, 0, 256],      # ends at 384
    "one_row": [0, 1, 0, 0],
    "nothing": [0, 0, 0, 0],
    "every_row": [100, 200, 77, 263],       # all 640: nothing past the end
}


def _past_the_end(x, end, fill):
    """``x`` with its rows from ``end`` on set to ``fill``."""
    import jax.numpy as jnp

    past = (jnp.arange(x.shape[0]) >= end).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(past, jnp.asarray(fill, x.dtype), x)


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("layout", sorted(LANDED))
def test_rows_past_the_groups_end_are_never_read(layout, plan, monkeypatch):
    """Groups that end at a landed count, with every operand's rows past it
    (the left operand, the cotangent) NaN: the value and the left operand's
    cotangent on the landed rows, and the weights' gradient, are those of
    the rule before, the rest of the rows in the last group under zeros,
    and the weights' gradient is finite: no kernel read a row past the
    count (``expert_mlp``'s passes: its own test below)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    if PLANS[plan]:
        monkeypatch.setattr(gm, "_plan",
                            lambda *a, **kw: (PLANS[plan], None))
    sizes = jnp.asarray(LANDED[layout], jnp.int32)
    end = int(sizes.sum())
    padded = sizes.at[-1].add(M - end)
    lhs, rhs, weight = _operands("bfloat16", n=256)

    def value_and_grads(fill, sizes):
        def loss(a, b):
            out = gm.grouped_matmul(a, b, sizes)
            return jnp.sum(out * _past_the_end(weight, end, fill)), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(
            _past_the_end(lhs, end, fill), rhs)
        return [np.asarray(x, np.float64) for x in (out,) + grads]

    routed = dict(pk.FALLBACKS)
    got = value_and_grads(np.nan, sizes)
    want = value_and_grads(0.0, padded)
    assert pk.FALLBACKS == routed
    (out, d_lhs, d_rhs), (w_out, w_lhs, w_rhs) = got, want
    assert np.array_equal(out[:end], w_out[:end])
    assert np.array_equal(d_lhs[:end], w_lhs[:end])
    assert np.isfinite(d_rhs).all() and np.array_equal(d_rhs, w_rhs)


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("layout", sorted(LANDED))
def test_the_expert_mlp_is_the_composition_it_replaces(layout, plan,
                                                       monkeypatch):
    """``expert_mlp``: the gathered rows, gate and up, ``silu(gate) * up``
    and the down product under the rows' scale, against ``x[tok]`` and
    ``lax.ragged_dot`` over the hidden rounded to the weights' type: the
    value on the landed rows, and the cotangents of the tokens, the three
    weights and the scale (float32 accumulation either way; the kernels'
    SiLU may round a hidden element to its other bfloat16 neighbour, and
    the hidden's cotangent is not rounded before the SiLU's gradient here,
    hence the tolerances), with the scale and the output's cotangent NaN
    past the
    count: every gradient is finite, so nothing read a row past it. Every
    pass by a kernel: the hidden formed on load, the rebuilt product's and
    the hidden cotangent's epilogues, both gathers and the sum back."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    if PLANS[plan]:
        monkeypatch.setattr(gm, "_plan",
                            lambda *a, **kw: (PLANS[plan], None))
    sizes = jnp.asarray(LANDED[layout], jnp.int32)
    end, n = int(sizes.sum()), 160
    padded = sizes.at[-1].add(M - end)
    _, w_gate, weight = _operands("bfloat16", n=256)
    w_up = jnp.flip(w_gate, axis=0) * 0.5
    w_down = jnp.swapaxes(w_gate, 1, 2) * 0.25
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    x = jax.random.normal(keys[0], (n, K))
    scale = jax.random.uniform(keys[1], (M,), jnp.float32)
    tok = jnp.asarray(np.random.RandomState(16).randint(0, n, M), jnp.int32)

    def fused(x, a, b, c, s, sizes):
        return gm.expert_mlp(x, tok, jnp.int32(end), a, b, c, sizes, s)

    def xla(x, a, b, c, s, sizes):
        rows = x[tok].astype(a.dtype)
        gate, up = _ragged_dot(rows, a, sizes), _ragged_dot(rows, b, sizes)
        hidden = (jax.nn.silu(gate) * up).astype(c.dtype)
        return _ragged_dot(hidden, c, sizes) * s[:, None]

    def value_and_grads(mlp, fill, sizes):
        def loss(x, a, b, c, s):
            out = mlp(x, a, b, c, s, sizes)
            return jnp.sum(out * _past_the_end(weight, end, fill)), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                x, w_gate, w_up, w_down, _past_the_end(scale, end, fill))
        return [np.asarray(v, np.float64) for v in (out,) + grads]

    routed, took = dict(pk.FALLBACKS), dict(gm.GMM_CALLS)
    got = value_and_grads(fused, np.nan, sizes)
    assert pk.FALLBACKS == routed
    new = _new_calls(took)
    assert {key[4] for key in new} == {
        None, gm.SWIGLU, gm.SWIGLU_COTANGENT,
        gm.SWIGLU + "+" + gm.SCALE_COTANGENT}
    assert {key[:3] for key in new if key[0] in ("moe_take", "moe_combine")
            } == {("moe_take", "float32", "bfloat16"),
                  ("moe_combine", "float32", "float32")}
    want = value_and_grads(xla, 0.0, padded)
    for a, b in zip(got[1:5], want[1:5]):  # the tokens' and weights'
        assert np.isfinite(a).all()
        _close(a, b, 1e-2)
    if end:
        _close(got[0][:end], want[0][:end], 1e-3)
        _close(got[5][:end], want[5][:end], 1e-3)


def _scaled_ragged_dot(a, b, sizes, scale):
    return _ragged_dot(a, b, sizes) * scale[:, None]


@pytest.mark.parametrize("dtype,rel", [("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("layout", ["tail_in_last", "empty_middle",
                                    "inside_tiles"])
def test_the_rows_scale_in_the_store(layout, dtype, rel):
    """The rows' scale in ``moe_gmm``'s store (``expert_mlp``'s down
    product): the bits of the unscaled product times the scale (the same
    float32 multiply, made before the one write), zero rows under a zero
    scale, and ``lax.ragged_dot``'s value under the plain multiply."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    lhs, rhs, _ = _operands(dtype)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    scale = jax.random.uniform(jax.random.PRNGKey(5), (M,), jnp.float32)
    scale = jnp.where(jnp.arange(M) < M - 100, scale, 0.0)  # an empty tail
    size = jnp.dtype(dtype).itemsize
    took = dict(gm.GMM_CALLS)
    got = np.asarray(gm._product(
        "moe_gmm", (lhs, rhs), sizes, gm._plan(M, K, N, 4, size,
                                               scaled=True)[0],
        jnp.float32, scale=scale), np.float64)
    assert {key[3] for key in _new_calls(took)} == {True}
    plain = np.asarray(gm._product(
        "moe_gmm", (lhs, rhs), sizes, gm._plan(M, K, N, 4, size)[0],
        jnp.float32) * scale[:, None], np.float64)
    assert np.array_equal(got, plain) and not got[M - 100:].any()
    _close(got, np.asarray(_scaled_ragged_dot(lhs, rhs, sizes, scale),
                           np.float64), 1e-5)


@pytest.mark.parametrize("arrived", ["bfloat16", "float32"])
@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("layout", ["tail_in_last", "empty_middle",
                                    "inside_tiles"])
def test_the_pairs_input_cotangent_is_the_float32_sum_written_once(
        layout, plan, arrived):
    """``moe_gmm_pair`` (the input's cotangent through gate and up in
    ``expert_mlp``): the float32 sum of the two products ``g_a . rhs_a^T +
    g_b . rhs_b^T`` in the type the input ARRIVED in: for float32 rows that
    sum itself, for bfloat16 rows the sum rounded once (to the last bit
    where the contraction is one step: the sum is then the same float32
    addition), nearer the float32 sum than two roundings and an add
    are."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    bf = jnp.bfloat16
    _, rhs_a, weight_a = _operands("bfloat16", n=256)
    rhs_b = jnp.flip(rhs_a, axis=0) * 0.5
    weight_b = jnp.flip(weight_a, axis=1)
    g_a, g_b = weight_a.astype(bf), weight_b.astype(bf)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    arrived = jnp.dtype(arrived)
    tiles = PLANS[plan] or gm._plan(M, 256, K, 4, 2, "moe_gmm_pair",
                                    out_itemsize=arrived.itemsize)[0]
    assert (256 // tiles[1] > 1) == (plan == "stepped")
    took = dict(gm.GMM_CALLS)
    d_lhs = gm._product("moe_gmm_pair", (g_a, rhs_a, g_b, rhs_b), sizes,
                        tiles, arrived, transposed=True)
    assert {key[2]: n for key, n in _new_calls(took).items()
            if key[0] == "moe_gmm_pair"} == {arrived.name: 1}
    assert d_lhs.dtype == arrived and d_lhs.shape == (M, K)
    one = gm._plan(M, 256, K, 4, 2, out_itemsize=2)[0]
    halves = [gm._product("moe_gmm", (g, rhs), sizes, one, jnp.float32,
                          transposed=True)
              for g, rhs in ((g_a, rhs_a), (g_b, rhs_b))]
    exact = np.asarray((halves[0] + halves[1]).astype(arrived), np.float32)
    d_lhs = np.asarray(d_lhs, np.float32)
    if plan == "one_step":
        assert np.array_equal(d_lhs, exact)
    _close(d_lhs, exact, 1e-2 if arrived == bf else 1e-5)
    wide = np.asarray(halves[0] + halves[1], np.float64)
    twice = np.asarray(halves[0].astype(bf) + halves[1].astype(bf),
                       np.float64)
    assert np.abs(d_lhs - wide).sum() <= np.abs(twice - wide).sum()


@pytest.mark.parametrize("layout", sorted(LANDED))
@pytest.mark.parametrize("switch,reason", [("0", "disabled"),
                                           ("1", "untileable")])
def test_scale_and_pair_off_the_kernels_are_ragged_dots(switch, reason,
                                                        layout, monkeypatch):
    """``expert_mlp`` off the kernels is its formula in XLA: the gather of
    every row, gate and up as two ``lax.ragged_dot``, the SiLU, the down
    product's ``lax.ragged_dot`` and the scale a plain multiply; the value
    on the landed rows and every gradient to the bit, the rows past the
    count (scale and output cotangent NaN there) read as zeros, so every
    gradient is finite; the refusal counted ONCE, under ``moe_gmm``, and no
    kernel taken."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", switch)
    f = N if reason == "disabled" else 96
    sizes = jnp.asarray(LANDED[layout], jnp.int32)
    end, n = int(sizes.sum()), 160
    keys = jax.random.split(jax.random.PRNGKey(18), 5)
    x = jax.random.normal(keys[0], (n, K))
    w_gate, w_up = (jax.random.normal(k, (4, K, f)) for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (4, f, K))
    scale = _past_the_end(jax.random.uniform(keys[4], (M,)), end, np.nan)
    weight = _past_the_end(jnp.ones((M, K)), end, np.nan)
    tok = jnp.asarray(np.random.RandomState(19).randint(0, n, M), jnp.int32)
    live = (jnp.arange(M) < end)[:, None]

    def xla(x, a, b, c, s):
        rows = jnp.where(live, x[tok], 0.0)
        gate, up = (jnp.where(live, _ragged_dot(rows, w, sizes), 0.0)
                    for w in (a, b))
        out = jnp.where(live, _ragged_dot(jax.nn.silu(gate) * up, c, sizes),
                        0.0)
        return out * s[:, None]

    def value_and_grads(mlp):
        def loss(*operands):
            out = mlp(*operands)
            return jnp.sum(jnp.where(live, out * weight, 0.0)), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                x, w_gate, w_up, w_down, scale)
        return [np.asarray(v) for v in (out,) + grads]

    before = pk.FALLBACKS.get(("moe_gmm", reason), 0)
    routed, took = dict(pk.FALLBACKS), dict(gm.GMM_CALLS)
    got = value_and_grads(lambda x, a, b, c, s: gm.expert_mlp(
        x, tok, jnp.int32(end), a, b, c, sizes, s))
    assert pk.FALLBACKS[("moe_gmm", reason)] == before + 1
    assert {key: n - routed.get(key, 0) for key, n in pk.FALLBACKS.items()
            if n != routed.get(key, 0)} == {("moe_gmm", reason): 1}
    assert gm.GMM_CALLS == took
    want = value_and_grads(xla)
    assert np.array_equal(got[0][:end], want[0][:end])
    for a, b in zip(got[1:5], want[1:5]):  # the tokens' and weights'
        assert np.isfinite(a).all() and np.array_equal(a, b)
    assert np.array_equal(got[5][:end], want[5][:end])


@pytest.mark.parametrize("shape,kernel,want", [
    # the Mellum2 cell: gate and up, down, and the weights' gradients
    ((65536, 2304, 896, 16), "moe_gmm", (256, 2304, 896)),
    ((65536, 896, 2304, 16), "moe_gmm", (256, 896, 2304)),
    ((65536, 2304, 896, 16), "moe_tgmm", (512, 1152, 896)),
    ((65536, 896, 2304, 16), "moe_tgmm", (512, 896, 1152)),
    # the Kimi cell
    ((16384, 2304, 1024, 8), "moe_gmm", (256, 2304, 1024)),
    ((16384, 1024, 2304, 8), "moe_gmm", (256, 1024, 2304)),
    ((16384, 2304, 1024, 8), "moe_tgmm", (256, 1152, 1024)),
    ((16384, 1024, 2304, 8), "moe_tgmm", (256, 1024, 1152)),
])
def test_the_plan_at_the_benchmark_cells_shapes(shape, kernel, want):
    """The right operand whole where a row tile of 256 leaves it room (it
    is then read once a group), the weights' gradient in the largest
    blocks that fit: what ``_plan``'s docstring measured."""
    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    plan, refusal = gm._plan(*shape, 2, kernel)
    assert refusal is None and plan == want
    steps = shape[1] // plan[1]
    assert gm._vmem(kernel, *plan, 2, steps) <= pk._VMEM_LIMIT


def test_no_tiles_fit_is_a_refusal(monkeypatch):
    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_VMEM_LIMIT", 64 * 1024)
    assert gm._plan(M, K, N, 4, 4) == (None, "vmem")


#: how the expert layer's bucket is filled: name -> (tokens' top k expert
#: ids [tokens, top_k] from a RandomState, bucket rows), for 256 tokens of
#: top 4 over 8 experts of which experts 0-3 are held, as
#: ``moe_share_ffn`` sorts them (held first, by expert; the rows a batch
#: leaves empty carry the sort's tail)
ROUTINGS = {
    # expert 1 gets nothing: an empty group in the middle
    "empty_middle": (lambda rng: np.stack([rng.permutation(
        [0, 2, 3, 4, 5, 6, 7])[:4] for _ in range(256)]), 1024),
    # every token on experts 0-3: all its top 4 rows held
    "all_held": (lambda rng: np.tile(np.arange(4), (256, 1)), 1024),
    # one expert of the held takes every token, the rest lie elsewhere:
    # three quarters of the bucket is the zero-weight tail
    "one_expert": (lambda rng: np.tile([2, 4, 5, 6], (256, 1)), 1024),
    # a bucket smaller than the assignments (the Kimi cell's shape): a
    # token has 0-4 rows in it
    "smaller_bucket": (lambda rng: np.stack([rng.permutation(8)[:4]
                                             for _ in range(256)]), 512),
}


def _bucket(routing, d=256, seed=0):
    """(rows [R, d] float32, tok [R], tokens, the landed count) as
    ``moe_share_ffn`` makes them for ``routing``; rows past the landed ones
    are zero, as the fallback's down product writes them under a zero
    weight."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    pick, rows = ROUTINGS[routing]
    idx = pick(rng)
    key = np.where(idx < 4, idx, 4).reshape(-1)
    sel = np.argsort(key, kind="stable")[:rows]
    landed = int(np.sum(key < 4))
    values = rng.randn(rows, d).astype(np.float32)
    values[landed:] = 0.0
    return (jnp.asarray(values), jnp.asarray(sel // 4, jnp.int32),
            idx.shape[0], landed)


def _scatter_add(rows, tok, n):
    import jax.numpy as jnp

    return jnp.zeros((n, rows.shape[1]), rows.dtype).at[tok].add(rows)


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_combine_pair_is_the_scatter_add_and_the_gather(routing):
    """Counting every row: ``combine`` gives the value of
    ``zeros.at[tok].add(rows)`` and its rows' cotangent is the gather
    ``g[tok]``; the sum by ``moe_combine``, the gather by ``moe_take``,
    counted, nothing routed to XLA."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    rows, tok, n, _ = _bucket(routing)
    every = jnp.int32(rows.shape[0])
    routed, took = dict(pk.FALLBACKS), dict(gm.GMM_CALLS)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, rows.shape[1]))
    got, back = jax.vjp(lambda r: gm.combine(r, tok, n, every), rows)
    want, want_back = jax.vjp(lambda r: _scatter_add(r, tok, n), rows)
    _close(np.asarray(got), np.asarray(want), 1e-6)
    assert np.array_equal(back(g)[0], want_back(g)[0])
    # a token none of whose rows is in the bucket gets zeros
    empty = np.setdiff1d(np.arange(n), np.asarray(tok))
    assert not np.asarray(got)[empty].any()
    plan = gm._combine_plan(n, rows.shape[1], rows.shape[0],
                            jnp.float32)[0]
    take = gm._take_plan(n, rows.shape[1], rows.shape[0], jnp.float32,
                         jnp.float32)[0]
    assert _new_calls(took) == {
        ("moe_combine", "float32", "float32", False, None, plan): 1,
        ("moe_take", "float32", "float32", False, None, take): 1}
    assert pk.FALLBACKS == routed


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_combine_pair_stops_at_the_landed_count(routing):
    """Given the landed count, with the rows past it NaN: ``combine`` is the
    scatter-add of the landed rows and its cotangent on them the gather
    (the rest unspecified). Both by the kernels."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    rows, tok, n, landed = _bucket(routing)
    nan = _past_the_end(rows, landed, np.nan)
    count = jnp.int32(landed)
    routed, took = dict(pk.FALLBACKS), dict(gm.GMM_CALLS)
    got, back = jax.vjp(lambda r: gm.combine(r, tok, n, count), nan)
    want = _scatter_add(rows[:landed], tok[:landed], n)
    _close(np.asarray(got), np.asarray(want), 1e-6)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, rows.shape[1]))
    assert np.array_equal(back(g)[0][:landed], g[tok][:landed])
    plan = gm._combine_plan(n, rows.shape[1], rows.shape[0],
                            jnp.float32)[0]
    take = gm._take_plan(n, rows.shape[1], rows.shape[0], jnp.float32,
                         jnp.float32)[0]
    assert _new_calls(took) == {
        ("moe_combine", "float32", "float32", False, None, plan): 1,
        ("moe_take", "float32", "float32", False, None, take): 1}
    assert pk.FALLBACKS == routed


@pytest.mark.parametrize("routing", ["empty_middle", "all_held"])
def test_off_the_kernel_the_landed_count_still_holds(routing, monkeypatch):
    """Kernels off: the scatter-add, counted, reads no row past the count
    either (they are sent out of range and dropped), and its transpose is
    the gather ``g[tok]``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "0")
    rows, tok, n, landed = _bucket(routing)
    nan = _past_the_end(rows, landed, np.nan)
    before = pk.FALLBACKS.get(("moe_combine", "disabled"), 0)
    took = dict(gm.GMM_CALLS)
    want = _scatter_add(rows[:landed], tok[:landed], n)
    got, back = jax.vjp(lambda r: gm.combine(r, tok, n, jnp.int32(landed)),
                        nan)
    assert np.array_equal(got, want)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, rows.shape[1]))
    assert np.array_equal(back(g)[0][:landed], g[tok][:landed])
    assert pk.FALLBACKS[("moe_combine", "disabled")] == before + 1
    assert gm.GMM_CALLS == took


def test_inside_a_cond_the_combine_runs_as_outside():
    """The Kimi cell's bucket is smaller than every assignment, so its
    sorted path is a ``lax.cond``'s branch."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import grouped_matmul as gm

    rows, tok, n, _ = _bucket("smaller_bucket")
    weight = jax.random.normal(jax.random.PRNGKey(9), (n, rows.shape[1]))

    def loss(combine, take):
        def of(r, x):
            y = lax.cond(jnp.sum(tok) >= 0,
                         lambda: combine(r + take(x) * r),
                         lambda: jnp.zeros((n, r.shape[1])))
            return jnp.sum(y * weight)
        return of

    every = jnp.int32(rows.shape[0])
    kernel = loss(lambda r: gm.combine(r, tok, n, every), lambda x: x[tok])
    xla = loss(lambda r: _scatter_add(r, tok, n), lambda x: x[tok])
    x = jax.random.normal(jax.random.PRNGKey(10), (n, rows.shape[1]))
    took = dict(gm.GMM_CALLS)
    got = jax.jit(jax.value_and_grad(kernel, argnums=(0, 1)))(rows, x)
    want = jax.value_and_grad(xla, argnums=(0, 1))(rows, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(np.asarray(a), np.asarray(b), 1e-5)
    assert gm.GMM_CALLS != took


def _row_blocks_read(call, rows, tok, count, which=0):
    """The row block a ``moe_combine`` call's index map gives each step of
    its grid, slab by slab (``which``: the block mapping, -1 the result's
    of a ``moe_take`` call): the ``pallas_call``'s own map, evaluated."""
    import jax
    from jax._src.state.discharge import discharge_state

    def found(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", None)
                if inner is not None and found(inner) is not None:
                    return found(inner)
        return None

    mapping = found(jax.make_jaxpr(call)(rows, tok, count).jaxpr).params[
        "grid_mapping"]
    index_map = mapping.block_mappings[which].index_map_jaxpr
    jaxpr, consts = discharge_state(index_map.jaxpr, index_map.consts)
    slabs, blocks = mapping.grid
    return [[int(jax.core.eval_jaxpr(jaxpr, consts, s, b, tok, count)[0])
             for b in range(blocks)] for s in range(slabs)]


@pytest.mark.parametrize("routing", ["empty_middle", "one_expert",
                                     "all_held"])
def test_the_combines_grid_is_static_and_it_reads_the_landed_blocks(
        routing, monkeypatch):
    """One shape, three routings: the same grid of (column slabs, row
    blocks), but the row blocks an index map fetches stop at the block of
    the last landed row (each later step holds it, so nothing more is
    read), and the rows past the count, NaN here, are not added: the sum
    is the scatter-add of the landed rows in every slab (VMEM made scarce
    here, so that a slab is a third of the width; blocks of 128 rows)."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_VMEM_LIMIT", 1024 * 1024)
    monkeypatch.setattr(gm, "_COMBINE_CAP", 1024 * 1024)
    rows, tok, n, landed = _bucket(routing, d=384)
    (tb, dc), limit, _ = gm._combine_plan(n, 384, rows.shape[0], jnp.float32)
    assert (tb, dc) == (512, 128)
    tb = 128
    call = gm._combine_call(n, 384, rows.shape[0], (tb, dc), limit, True)
    count = jnp.full((1,), landed, jnp.int32)
    text = str(jax.make_jaxpr(call)(rows, tok, count))
    grid = tuple(int(g) for g in re.search(
        r"grid=\((\d+), (\d+)\)", text).groups())
    assert "block_shape=(Blocked(block_size=%d), Blocked(block_size=%d))" % (
        tb, dc) in text
    assert grid == (3, 8)  # three slabs of the 384 columns, eight blocks
    read = _row_blocks_read(call, rows, tok, count)
    last = (landed - 1) // tb
    assert read == [[min(b, last) for b in range(8)]] * 3
    assert 0 < landed <= rows.shape[0] and (
        (last + 1) * tb < rows.shape[0]) == (routing != "all_held")
    nan = _past_the_end(rows, landed, np.nan)
    _close(np.asarray(call(nan, tok, count)),
           np.asarray(_scatter_add(rows[:landed], tok[:landed], n)), 1e-6)


#: landed counts of a 1024-row bucket in blocks of 128: name -> count
TAKE_COUNTS = {"nothing": 0, "cuts_a_block": 300, "every_row": 1024}


@pytest.mark.parametrize("out", ["float32", "bfloat16"])
@pytest.mark.parametrize("count", sorted(TAKE_COUNTS))
def test_the_take_kernel_writes_the_landed_rows_alone(count, out,
                                                      monkeypatch):
    """``moe_take``: the first ``count`` rows are ``x[tok]`` in the
    result's type to the last bit (one rounding of the float32 row), in
    every column slab; the grid is static, and the row blocks its result's
    index map writes stop at the block of the last landed row (each later
    step holds it, so nothing more is written back; VMEM made scarce here,
    so that a slab is a third of the width; blocks of 128 rows)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_VMEM_LIMIT", 1024 * 1024)
    monkeypatch.setattr(gm, "_COMBINE_CAP", 1024 * 1024)
    rows, tok, n, _ = _bucket("empty_middle", d=384)
    R, landed = rows.shape[0], TAKE_COUNTS[count]
    (tb, dc), limit, _ = gm._take_plan(n, 384, R, jnp.float32, out)
    assert dc == 128
    call = gm._take_call(n, 384, R, out, (128, dc), limit, True)
    x = rows[:n] * 3.0 + 0.1
    at = jnp.full((1,), landed, jnp.int32)
    got = call(x, tok, at)
    assert got.shape == (R, 384) and got.dtype == jnp.dtype(out)
    assert np.array_equal(np.asarray(got[:landed], np.float32),
                          np.asarray(x[tok][:landed].astype(out), np.float32))
    last = max(landed - 1, 0) // 128
    assert _row_blocks_read(call, x, tok, at, which=-1) == [
        [min(b, last) for b in range(R // 128)]] * 3


@pytest.mark.parametrize("switch,d,dtype,reason", [
    ("0", 256, "float32", "disabled"),
    ("1", 96, "float32", "untileable"),    # no multiple of 128 wide
    ("1", 256, "bfloat16", "untileable"),  # the kernel sums float32 rows
])
def test_what_the_combine_cannot_take_is_the_scatter_add_counted(
        switch, d, dtype, reason, monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", switch)
    rows, tok, n, _ = _bucket("empty_middle", d=d)
    rows = rows.astype(dtype)
    before = pk.FALLBACKS.get(("moe_combine", reason), 0)
    took = dict(gm.GMM_CALLS)
    g = jnp.ones((n, d), dtype)
    every = jnp.int32(rows.shape[0])
    got, back = jax.vjp(lambda r: gm.combine(r, tok, n, every), rows)
    want, want_back = jax.vjp(lambda r: _scatter_add(r, tok, n), rows)
    assert np.array_equal(got, want)
    assert np.array_equal(back(g)[0], want_back(g)[0])
    assert pk.FALLBACKS[("moe_combine", reason)] == before + 1
    assert gm.GMM_CALLS == took


@pytest.mark.parametrize("shape,want", [
    # the three hybrid cells: tokens, width, bucket rows
    ((8192, 2304, 65536), ((512, 2304), 81)),   # Mellum2
    ((8192, 2048, 32768), ((512, 2048), 72)),   # GLM
    ((8192, 2304, 16384), ((512, 2304), 81)),   # Kimi
])
def test_the_combine_plan_at_the_benchmark_cells_shapes(shape, want):
    """The whole width in one slab under a limit the call names in whole
    MiB: what ``_combine_plan``'s docstring measured; a cap too small for
    any slab is a refusal."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    plan, limit, refusal = gm._combine_plan(*shape, jnp.float32)
    assert refusal is None and (plan, limit // (1024 * 1024)) == want
    assert gm._combine_vmem(shape[0], *plan) <= limit <= gm._COMBINE_CAP


def test_no_slab_fits_is_a_refusal(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_COMBINE_CAP", 32 * 1024 * 1024)
    assert gm._combine_plan(65536, 2304, 65536, jnp.float32) == (
        None, None, "vmem")
