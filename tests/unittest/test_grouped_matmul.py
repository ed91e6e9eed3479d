"""``ops/grouped_matmul.py``: the ``moe_gmm`` / ``moe_tgmm`` kernels in
interpret mode against ``lax.ragged_dot``, values and both gradients; the
visit list they walk; the plan and what it routes to XLA."""
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


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_row_is_visited_once_and_the_list_is_static(layout, tm):
    """The visit list: ``m // tm + g - 1`` long whatever the sizes, tiles
    never going back, every group at least once, and the masks of the real
    visits cover each row exactly once, under its own group."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    sizes = np.asarray(LAYOUTS[layout])
    sizes[-1] += 768 - M  # six tiles of 128, three of 256
    offsets, groups, tiles, real = (np.asarray(x) for x in gm._visits(
        jnp.asarray(sizes, jnp.int32), 768, tm))
    assert len(groups) == len(tiles) == 768 // tm + len(sizes) - 1
    assert np.array_equal(offsets, np.concatenate([[0], np.cumsum(sizes)]))
    assert (np.diff(tiles) >= 0).all() and (np.diff(groups) >= 0).all()
    real = int(real[0])
    assert set(groups[:real]) == set(range(len(sizes)))
    assert (groups[real:] == groups[real - 1]).all()
    assert (tiles[real:] == tiles[real - 1]).all()
    owner = np.full(768, -1)
    for v in range(real):
        row = tiles[v] * tm + np.arange(tm)
        mine = (row >= offsets[groups[v]]) & (row < offsets[groups[v] + 1])
        assert (owner[row[mine]] == -1).all()
        owner[row[mine]] = groups[v]
    assert np.array_equal(owner, np.repeat(np.arange(len(sizes)), sizes))


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


def test_gmm_calls_records_the_plan_of_each_direction():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    lhs, rhs, weight = _operands("bfloat16")
    sizes = jnp.asarray(LAYOUTS["inside_tiles"], jnp.int32)
    took = dict(gm.GMM_CALLS)
    jax.make_jaxpr(jax.grad(lambda a, b: jnp.sum(
        gm.grouped_matmul(a, b, sizes) * weight), argnums=(0, 1)))(lhs, rhs)
    new = {key: n - took.get(key, 0) for key, n in gm.GMM_CALLS.items()
           if n != took.get(key, 0)}
    assert new == {
        ("moe_gmm", "bfloat16", (128, K, N)): 1,   # forward
        ("moe_gmm", "bfloat16", (128, N, K)): 1,   # the input's cotangent
        ("moe_tgmm", "bfloat16", (128, K, N)): 1}  # the weights' gradient


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
