"""The hybrid LM family (``models/hybrid_lm.py``) against the benchmark's
plain reference (``benchmark/references/kimi-linear-48b-a3b.py``: float32
``jax.numpy``, the token-by-token recurrence, masked softmax, a loop over
experts), at small sizes on the CPU with seeded weights:

* KDA's chunked path (plain XLA, and both stages as kernels in interpret
  mode: ``kda_chunk_fwd`` / ``kda_chunk_bwd``, ``kda_state_fwd`` /
  ``kda_state_bwd``) against the recurrence, values and all five
  gradients, over several chunks with gates near both ends of their range;
  the in-chunk kernels against the XLA stage they replace, output by
  output and gradient by gradient;
* latent attention against the reference's;
* the share-aware expert layer against the reference's loop, in a typical
  batch, in one where every token names the same held expert (more than the
  sorted bucket holds: nothing may be dropped) and in one where no
  assignment lands here;
* the share test: the parts that all the shares give, the shared expert
  counted once, add up to the uncut layer;
* the whole model's loss and every leaf's gradient, and a few steps through
  ``parallel.make_train_step`` + ``optax.adam``;
* what ``forward`` keeps by name for the backward pass (``KEPT``): each
  kernel's forward once a layer in the gradient's jaxpr, the same loss and
  gradients as with nothing kept, and the bytes the gauges report.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference module, found by its file's name."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "reference_kimi_linear",
        os.path.join(BENCH, "references", "kimi-linear-48b-a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _highest():
    import jax

    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(**over):
    """A config in the shape of ``benchmark/configs/kimi-linear-48b-a3b.json``
    at test size: three layers, KDA, KDA, MLA, the first MLP dense, so that
    every kind of half-block is there once and the compile stays short."""
    config = {
        "hidden_size": 64, "num_layers": 3, "num_attention_heads": 2,
        "intermediate_size": 128, "first_k_dense_replace": 1,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rms_norm_eps": 1e-5,
        "linear_attn_config": {"full_attn_layers": [3, 6], "head_dim": 16,
                               "num_heads": 2, "short_conv_kernel_size": 4},
        "moe_intermediate_size": 32, "num_experts": 4, "experts_held": [4, 8],
        "published": {"num_experts": 16}, "num_experts_per_token": 4,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446,
        "moe_renormalize": True, "vocab_size": 384, "dtype": "float32"}
    config.update(over)
    return config


def program_config(ref, config):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "driver_train_hybrid_lm",
        os.path.join(BENCH, "drivers", "train_hybrid_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.model_config(config, ref.sizes(config))


def close(got, want, rel=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= rel * scale, (
        float(np.max(np.abs(got - want))), scale)


# -- KDA ---------------------------------------------------------------------------


def _kda_inputs(T=256, H=2, D=128):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(3), 6)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (1, T, H, D))) * D ** -0.5
    # keys with a common part, as SiLU's positive mean gives them
    k = unit(jax.random.normal(ks[1], (1, T, H, D)) + 0.5)
    v = jax.random.normal(ks[2], (1, T, H, D))
    # log-decays from -1e-4 (almost none) to -8 a step (e^-512 a chunk)
    g = -jnp.exp(jax.random.uniform(ks[3], (1, T, H, D), minval=np.log(1e-4),
                                    maxval=np.log(8.0)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (1, T, H, D))


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_kda_chunked_matches_the_recurrence(ref, path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda, pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "1" if path == "kernels" else "0")
    args, weight = _kda_inputs()
    took, routed = dict(kda.KDA_CALLS), dict(pk.FALLBACKS)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    want = ref.delta_rule(*args)
    got = kda.kda_attention(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, want, 1e-5)
    want_grads = jax.grad(loss(ref.delta_rule), argnums=range(5))(*args)
    got_grads = jax.grad(loss(kda.kda_attention), argnums=range(5))(*args)
    for got_g, want_g in zip(got_grads, want_grads):
        close(got_g, want_g, 2e-5)
    if path == "kernels":  # both stages' kernels were taken, nothing routed
        for kernel in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_state_fwd",
                       "kda_state_bwd"):
            assert kda.KDA_CALLS.get((kernel, "float32"), 0) > took.get(
                (kernel, "float32"), 0), kernel
        assert pk.FALLBACKS == routed
    else:  # both stages counted as routed to XLA
        for stage in ("kda", "kda_chunk"):
            assert pk.FALLBACKS[(stage, "disabled")] > routed.get(
                (stage, "disabled"), 0)


def test_kda_narrow_head_is_routed_to_xla_and_counted(ref, monkeypatch):
    """A head that does not fill 128 lanes cannot ride the kernels' blocks:
    XLA takes it, counted, and the result is the recurrence's."""
    from mxnet_tpu.ops import kda, pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "1")
    args, _ = _kda_inputs(T=128, D=16)
    before = dict(pk.FALLBACKS)
    close(kda.kda_attention(*args), ref.delta_rule(*args), 1e-5)
    for stage in ("kda", "kda_chunk"):
        assert pk.FALLBACKS[(stage, "untileable")] == before.get(
            (stage, "untileable"), 0) + 1
    with pytest.raises(ValueError):
        kda.kda_attention(*(a[:, :100] for a in args))
    # a 256-wide head overflows the in-chunk backward's VMEM on the chip
    assert kda._plan(1, 2048, 4, 256, 256) == (0, "vmem")
    assert kda._plan(1, 8192, 32, 128, 128) == (8, None)


def test_kda_bfloat16_operands_stay_close(ref):
    """bfloat16 operands, float32 state and solve: within bfloat16's
    rounding of the recurrence, at gates near both ends."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    args, _ = _kda_inputs()
    close(kda.kda_attention(*args, dtype=jnp.bfloat16),
          ref.delta_rule(*args), 3e-2)


PREPARED = ["w", "u0", "qg", "kd", "aqk", "decay"]
INPUTS = ["q", "k", "v", "g", "beta"]


@pytest.fixture(scope="module")
def chunk_stages():
    """``kind -> (outputs, gradients)`` of the in-chunk stage as the
    kernels (interpret mode) and as the XLA stage they replace, under the
    same dense cotangents: 16 chunks of a 128-wide head, two grid steps a
    head. Computed once a kind; each output and gradient is a case."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    cache = {}

    def stages(kind):
        if kind in cache:
            return cache[kind]
        dtype = jnp.dtype("bfloat16" if kind == "bfloat16" else "float32")
        args, _ = _kda_inputs(T=1024)
        if kind == "hard":  # every third position: e^G underflows in a chunk
            third = jnp.arange(1024)[None, :, None, None] % 3 == 0
            args = args[:3] + (jnp.where(third, -20.0, args[3]),) + args[4:]

        def xla(*a):
            return kda._prepare(*(x.transpose(0, 2, 1, 3) for x in a[:4]),
                                a[4].transpose(0, 2, 1), dtype)

        def kernels(*a):
            return kda.chunk_stage(*a, dtype=dtype)

        with pytest.MonkeyPatch.context() as mp, \
                jax.default_matmul_precision("highest"):
            mp.setenv("MXNET_PALLAS", "1")
            took = dict(kda.KDA_CALLS)
            want, pull_want = jax.vjp(xla, *args)
            got, pull_got = jax.vjp(kernels, *args)
            keys = jax.random.split(jax.random.PRNGKey(11), len(want))
            cts = tuple(jax.random.normal(key, o.shape, jnp.float32).astype(
                o.dtype) for key, o in zip(keys, want))
            grads = pull_got(cts), pull_want(cts)
        for kernel in ("kda_chunk_fwd", "kda_chunk_bwd"):
            assert kda.KDA_CALLS[(kernel, dtype.name)] == took.get(
                (kernel, dtype.name), 0) + 1
        cache[kind] = (got, want), grads
        return cache[kind]

    return stages


#: float32 operands: rounding alone; bfloat16: the band of
#: ``test_kda_bfloat16_operands_stay_close``; hard gates: a cumulative gate
#: of -500 is known to 6e-5 in float32, and so is any factor e^(G - G')
BANDS = {"float32": 1e-5, "bfloat16": 3e-2, "hard": 1e-4}


@pytest.mark.parametrize("kind", list(BANDS))
@pytest.mark.parametrize("name", PREPARED)
def test_kda_chunk_kernel_outputs_match_xla(chunk_stages, kind, name):
    (got, want), _ = chunk_stages(kind)
    at = PREPARED.index(name)
    assert got[at].shape == want[at].shape
    assert got[at].dtype == want[at].dtype
    assert bool(np.all(np.isfinite(np.asarray(got[at], np.float32))))
    close(got[at], want[at], BANDS[kind])


@pytest.mark.parametrize("kind", list(BANDS))
@pytest.mark.parametrize("name", INPUTS)
def test_kda_chunk_kernel_gradients_match_xla(chunk_stages, kind, name):
    _, (got, want) = chunk_stages(kind)
    at = INPUTS.index(name)
    assert got[at].shape == want[at].shape
    assert bool(np.all(np.isfinite(np.asarray(got[at]))))
    close(got[at], want[at], BANDS[kind])


def test_kda_hard_gates_stay_finite_and_match_the_recurrence(ref, monkeypatch):
    """Gates of -20 a position and harder: e^G underflows within a chunk,
    and no factor above 1 may be formed on the way. Both stages as kernels
    give the recurrence's values, finite."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    monkeypatch.setenv("MXNET_PALLAS", "1")
    args, _ = _kda_inputs()
    args = args[:3] + (jnp.minimum(args[3] * 4.0, -20.0),) + args[4:]
    got = kda.kda_attention(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, ref.delta_rule(*args), 1e-5)


# -- MLA, MoE ----------------------------------------------------------------------


def test_mla_matches_the_reference(ref):
    import jax

    from mxnet_tpu.models import hybrid_lm

    config = tiny_config()
    sz = ref.sizes(config)
    cfg = program_config(ref, config)
    p = ref._draw(sz, jax.random.PRNGKey(5))["layers"][2]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, sz["d"]))
    close(hybrid_lm.mla_layer(x, p, cfg), ref._mla(x, p, sz, lambda a: a))


def _moe_case(ref, case):
    """(x [N, d], params, sizes, tokens N, top_k) of one routing case."""
    import jax
    import jax.numpy as jnp

    if case == "typical":
        config = tiny_config()
        N = 256
    else:  # a bucket smaller than the worst case, so the overflow path exists
        config = tiny_config(num_experts=2, experts_held=[0, 2],
                             published={"num_experts": 64},
                             num_experts_per_token=2)
        N = 2048
    sz = ref.sizes(config)
    p = ref._draw(sz, jax.random.PRNGKey(7))["layers"][1]["mlp"]
    bias = np.zeros(sz["E"], np.float32)
    if case == "all_on_one":
        bias[0] = 10.0  # every token names held expert 0
    elif case == "none_here":
        bias[:2] = -10.0  # no token names a held expert
    p = dict(p, router_bias=jnp.asarray(bias))
    x = jax.random.normal(jax.random.PRNGKey(8), (N, sz["d"]))
    return x, p, sz


@pytest.mark.parametrize("case", ["typical", "all_on_one", "none_here"])
def test_share_layer_matches_the_reference_and_drops_nothing(ref, case):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    x, p, sz = _moe_case(ref, case)
    N = x.shape[0]

    def program(x, p):
        return moe.moe_share_ffn(p, x, sz["top_k"], sz["held"],
                                 sz["route_scale"])

    def reference(x, p):
        return ref.moe(x, p, sz)

    (got, counts), (want, want_counts) = program(x, p), reference(x, p)
    close(got, want)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    rows = moe.share_bucket_rows(N, sz["E"], sz["held"], sz["top_k"])
    if case == "all_on_one":  # more than the bucket holds, and all counted
        assert int(counts[0]) == N > rows
    elif case == "none_here":
        assert int(jnp.sum(counts)) == 0
    else:
        assert 0 < int(jnp.sum(counts)) <= rows
    weight = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    got_g = jax.grad(lambda x, p: jnp.sum(program(x, p)[0] * weight),
                     argnums=(0, 1))(x, p)
    want_g = jax.grad(lambda x, p: jnp.sum(reference(x, p)[0] * weight),
                      argnums=(0, 1))(x, p)
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_g),
                                   jax.tree.leaves(want_g)):
        close(got_leaf, want_leaf)


@pytest.mark.parametrize("case", ["typical", "none_here"])
def test_grouped_products_run_over_the_whole_bucket(ref, monkeypatch, case):
    """Whatever lands here, the groups the products are given add up to the
    bucket's rows: the rows a batch leaves empty ride in the last group, so
    a step's work does not follow the routing."""
    import jax

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel import moe

    x, p, sz = _moe_case(ref, case)
    rows = moe.share_bucket_rows(x.shape[0], sz["E"], sz["held"], sz["top_k"])
    seen = []
    grouped = gm.grouped_matmul

    def watched(a, b, sizes, **scale):  # traced inside ``lax.cond``
        jax.debug.callback(
            lambda sizes, n_rows=a.shape[0]: seen.append(
                (n_rows, np.asarray(sizes))), sizes)
        return grouped(a, b, sizes, **scale)

    monkeypatch.setattr(gm, "grouped_matmul", watched)
    _, counts = moe.moe_share_ffn(p, x, sz["top_k"], sz["held"],
                                  sz["route_scale"])
    counts = np.asarray(jax.block_until_ready(counts))
    jax.effects_barrier()
    assert len(seen) == 3 and int(counts.sum()) < rows
    for n_rows, sizes in seen:
        assert n_rows == rows == int(sizes.sum())
        assert np.array_equal(sizes[:-1], counts[:-1])


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_empty_rows_carry_large_tokens_under_a_zero_weight(path, score,
                                                           monkeypatch):
    """No mask over the gathered rows: the rows a batch leaves empty hold
    the tokens of the argsort's tail and meet a zero weight. With those
    tokens ten thousand times the others, the layer's value and every
    gradient are the dense path's (``every_token``), on the small tokens to
    the small tokens' own scale: nothing of an empty row leaks."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel import moe

    monkeypatch.setenv("MXNET_PALLAS", "1" if path == "kernels" else "0")
    N, d, ff, E, held, top_k = 256, 128, 128, 16, (2, 14), 4
    n = held[1] - held[0]
    p = moe.init_share_params(jax.random.PRNGKey(21), E, held, d, ff,
                              shared=0, score=score)
    # the router reads eight channels: the others grow without moving a choice
    p["router"] = p["router"].at[8:].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(22), (N, d))
    rows = moe.share_bucket_rows(N, E, held, top_k)
    assert rows == N * top_k  # the sorted path alone, no ``lax.cond``
    idx, _ = moe.route_top_k(p, x, top_k, score=score)
    local = np.asarray(idx) - held[0]
    key = np.where((local >= 0) & (local < n), local, n).reshape(-1)
    landed = int((key < n).sum())
    fill = np.unique(np.argsort(key, kind="stable")[landed:rows] // top_k)
    assert 128 < landed < rows and 0 < len(fill) < N
    x = x.at[fill, 8:].multiply(1e4)
    small = np.setdiff1d(np.arange(N), fill)
    weight = jax.random.normal(jax.random.PRNGKey(23), (N, d))

    def value_and_grads():
        def loss(x, p):
            y, counts = moe.moe_share_ffn(p, x, top_k, held, score=score)
            return jnp.sum(y * weight), (y, counts)

        (_, (y, counts)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, p)
        assert int(jnp.sum(counts)) == landed
        return y, grads

    took = dict(gm.GMM_CALLS)
    got, got_g = value_and_grads()
    assert (gm.GMM_CALLS != took) == (path == "kernels")
    with monkeypatch.context() as mp:  # a bucket under the load: every_token
        mp.setattr(moe, "share_bucket_rows", lambda *a: 128)
        want, want_g = value_and_grads()
    for a, b in [(got, want), (got_g[0], want_g[0])]:
        close(a, b)
        close(a[small], b[small])
    assert float(jnp.max(jnp.abs(want[small]))) < 1e-3 * float(
        jnp.max(jnp.abs(want)))
    for a, b in zip(jax.tree.leaves(got_g[1]), jax.tree.leaves(want_g[1])):
        close(a, b)


def test_shares_add_up_to_the_uncut_layer(ref):
    """16 experts, top 4, four shares of 4: what the shares give, the
    shared expert counted once, is what the uncut reference gives for the
    whole layer."""
    import jax

    from mxnet_tpu.parallel import moe

    whole = tiny_config(num_experts=16, experts_held=[0, 16])
    sz = ref.sizes(whole)
    p = ref._draw(sz, jax.random.PRNGKey(11))["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(12), (128, sz["d"]))
    want, want_counts = ref.moe(x, p, sz)
    total, counts = 0.0, []
    for share in range(4):
        lo, hi = 4 * share, 4 * share + 4
        mine = {"router": p["router"], "router_bias": p["router_bias"],
                "experts": jax.tree.map(lambda a: a[lo:hi], p["experts"])}
        if share == 0:  # what every chip computes alike: once
            mine["shared"] = p["shared"]
        y, n = moe.moe_share_ffn(mine, x, sz["top_k"], (lo, hi),
                                 sz["route_scale"])
        total = total + y
        counts.append(np.asarray(n))
    close(total, want)
    counts = np.concatenate(counts)
    assert np.array_equal(counts, np.asarray(want_counts))
    assert counts.sum() == 128 * sz["top_k"]  # every assignment, once


# -- the whole model ---------------------------------------------------------------


def test_model_loss_and_every_gradient_match_the_reference(ref):
    import jax

    from mxnet_tpu.models import hybrid_lm

    config = tiny_config()
    sz = ref.sizes(config)
    cfg = program_config(ref, config)
    params = ref._draw(sz, jax.random.PRNGKey(13))
    tokens = jax.random.randint(jax.random.PRNGKey(14), (2, 129), 0, sz["V"])
    want, want_grad = ref.loss_and_grad(params, tokens, sz)
    (got, counts), got_grad = jax.value_and_grad(
        hybrid_lm.loss_fn(cfg), has_aux=True)(params, {"tokens": tokens}, None)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert counts.shape == (2, 4) and int(counts.sum()) > 0
    assert np.array_equal(np.asarray(counts), np.asarray(
        ref.forward(params, tokens[:, :-1], sz)[1]))
    names = ref.leaf_names(config)
    got_leaves, want_leaves = jax.tree.leaves(got_grad), jax.tree.leaves(
        want_grad)
    assert len(names) == len(got_leaves) == len(want_leaves)
    for name, got_leaf, want_leaf in zip(names, got_leaves, want_leaves):
        if name.endswith("router_bias"):  # a buffer: no gradient reaches it
            assert not np.any(np.asarray(got_leaf))
            continue
        close(got_leaf, want_leaf, 5e-4)
    # the program's own initializer draws the same tree
    own = hybrid_lm.init_params(cfg, jax.random.PRNGKey(1))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(own)] == [
        a.shape for a in jax.tree.leaves(params)]
    assert jax.tree.structure(hybrid_lm.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))


def test_partition_specs_on_a_mesh_give_the_same_loss_and_gradients(ref):
    """``param_partition_specs`` on a (2, 2) mesh of CPU devices with a
    tensor and an expert axis: the model placed by them computes what the
    unplaced one does (XLA partitions by the annotations alone; the Pallas
    kernels are off on the CPU)."""
    import jax
    from jax.sharding import NamedSharding

    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.parallel import create_mesh

    config = tiny_config()
    cfg = program_config(ref, config)
    params = ref._draw(ref.sizes(config), jax.random.PRNGKey(21))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(22), (2, 129), 0, cfg.vocab_size)}
    grad = jax.jit(jax.value_and_grad(hybrid_lm.loss_fn(cfg), has_aux=True))
    (want, want_counts), want_grad = grad(params, batch, None)

    mesh = create_mesh((2, 2), (cfg.tensor_axis, cfg.expert_axis))
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        hybrid_lm.param_partition_specs(cfg),
        is_leaf=lambda x: hasattr(x, "shape"))
    assert len(placed["layers"][1]["mlp"]["experts"]["w_up"]
               .sharding.device_set) == 4
    (got, got_counts), got_grad = grad(placed, batch, None)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert np.array_equal(np.asarray(got_counts), np.asarray(want_counts))
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_grad),
                                   jax.tree.leaves(want_grad)):
        close(got_leaf, want_leaf, 5e-4)


def test_trains_through_make_train_step(ref):
    import jax
    import optax

    from mxnet_tpu import parallel
    from mxnet_tpu.models import hybrid_lm

    config = tiny_config(dtype="bfloat16")
    cfg = program_config(ref, config)
    params = hybrid_lm.init_params(cfg, jax.random.PRNGKey(2))
    step, init_state = parallel.make_train_step(
        hybrid_lm.loss_fn(cfg), optax.adam(3e-3), has_aux=True)
    opt_state = init_state(params)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(15), (2, 65), 0, cfg.vocab_size)}
    losses = []
    for _ in range(4):
        params, opt_state, loss, counts = step(params, opt_state, batch, None)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))
    assert counts.shape == (cfg.moe_layers, 4)
    ratio = hybrid_lm.record_routing(np.asarray(counts)[None], 128, cfg)
    assert ratio >= 1.0


# -- what the forward keeps for the backward pass ----------------------------------


def kernel_config(**over):
    """The smallest model whose shapes the kernels admit (KDA heads 128
    wide, T = 256 = one grid step of four chunks, flash blocks of 256) and
    whose expert layers sort under ``lax.cond``: 4 of 64 experts held, so
    the bucket (512 rows) is smaller than what could land (1,024)."""
    from mxnet_tpu.models import hybrid_lm

    kw = dict(vocab_size=256, d_model=64, attention=("kda", "kda", "mla"),
              mlp=("dense", "moe", "moe"), kda_heads=2, kda_head_dim=128,
              num_heads=2, kv_lora_rank=32, qk_nope_dim=64, qk_rope_dim=64,
              v_head_dim=128, d_ff=128, moe_d_ff=32, num_experts=64,
              experts_per_token=4, experts_held=(0, 4), dtype="bfloat16")
    kw.update(over)
    return hybrid_lm.HybridConfig(**kw)


def _call_sites(jaxpr, found):
    """``found[name] += 1`` for every Pallas kernel and every sort in
    ``jaxpr`` and the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        elif eqn.primitive.name in ("sort", "ragged_dot_general"):
            name = eqn.primitive.name
            found[name] = found.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _call_sites(sub, found)
    return found


@pytest.fixture(scope="module")
def kept_traces():
    """The gradient of ``loss_fn`` traced abstractly (nothing runs) at
    :func:`kernel_config`, with ``forward``'s policy and with nothing kept:
    ``(call sites, call sites with nothing kept, saved residuals' text,
    bytes the gauges read)``."""
    import io
    from contextlib import redirect_stdout

    import jax
    import jax.ad_checkpoint
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.models import hybrid_lm

    cfg = kernel_config()
    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 257), jnp.int32)}

    def loss(params, batch):
        return hybrid_lm.loss_fn(cfg)(params, batch, None)[0]

    def sites():
        return _call_sites(jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr,
                           {})

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MXNET_PALLAS", "1")
        mp.setenv("MXNET_TELEMETRY", "1")
        telemetry.reset()
        telemetry.reload()
        kept = sites()
        gauges = {name: telemetry.snapshot()["gauges"].get(
            "remat.saved_bytes.%s" % name) for name in hybrid_lm.KEPT}
        text = io.StringIO()
        with redirect_stdout(text):
            jax.ad_checkpoint.print_saved_residuals(loss, params, batch)
        mp.setattr(hybrid_lm, "KEPT", ())
        bare = sites()
    telemetry.reload()
    return cfg, kept, bare, text.getvalue(), gauges


#: kernel (or operation) -> (the name its results are kept under, which
#: layers of :func:`kernel_config` hold it, its call sites a layer in the
#: gradient with the policy and with nothing kept, how the kept value reads
#: among the saved residuals: what the sorted path keeps leaves its
#: ``lax.cond`` as that operation's own results); of an expert layer's
#: twelve grouped products the gate's and the up's forward are kept
KEPT_FORWARDS = {
    "flash_fwd": ("flash", "mla", 1, 2, "named 'flash'"),
    "kda_chunk_fwd": ("kda_chunk", "kda", 1, 2, "named 'kda_chunk'"),
    "sort": ("moe_sort", "moe", 1, 2, "i32[512] output of cond"),
    "ragged_dot_general": ("moe_hidden", "moe", 10, 12,
                           "f32[512,32] output of cond")}


@pytest.mark.parametrize("kernel", list(KEPT_FORWARDS))
def test_a_kept_forward_runs_once_a_layer(kept_traces, kernel):
    """A bare ``jax.checkpoint`` runs a kernel's forward again in the
    backward pass to get its residuals back; kept by name, it runs once."""
    from mxnet_tpu.models import hybrid_lm

    cfg, kept, bare, residuals, _ = kept_traces
    name, kind, once, twice, saved_as = KEPT_FORWARDS[kernel]
    layers = sum(k == kind for k in cfg.attention + cfg.mlp)
    assert layers and name in hybrid_lm.KEPT
    assert kept[kernel] == once * layers and bare[kernel] == twice * layers
    assert saved_as in residuals
    # what is not named is rebuilt as before: the state's pass runs twice
    assert kept["kda_state_fwd"] == bare["kda_state_fwd"] == 4
    assert all(kept[k] == bare[k] for k in bare if k.endswith(
        ("_bwd", "_dq", "_dkv")))


@pytest.mark.parametrize(
    "name", ["flash", "kda_chunk", "moe_sort", "moe_hidden"])
def test_saved_bytes_gauges_read_what_the_layers_keep(kept_traces, name):
    """``remat.saved_bytes.<name>`` against the bytes reckoned from the
    shapes: a later change that keeps more is seen here."""
    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.ops.kda import CHUNK
    from mxnet_tpu.parallel import moe

    cfg, _, _, _, gauges = kept_traces
    B, T = 1, 256
    H, D = cfg.kda_heads, cfg.kda_head_dim
    rows = moe.share_bucket_rows(B * T, cfg.num_experts, cfg.experts_held,
                                 cfg.experts_per_token)
    want = {
        # o bfloat16 and the log-sum-exp, float32 on eight sublanes
        "flash": B * cfg.num_heads * T * (2 * cfg.v_head_dim + 8 * 4),
        # w, u0, qg, kd [T, D] and aqk [T, C] bfloat16; the inverse [T, C]
        # and a chunk's decay [T / C, D] float32
        "kda_chunk": 2 * B * H * (T * (4 * D * 2 + CHUNK * 2 + CHUNK * 4)
                                  + T // CHUNK * D * 4),
        # two expert layers: a bucket row's assignment, int32, and its
        # gate and up products, float32
        "moe_sort": 2 * 4 * rows,
        "moe_hidden": 2 * rows * 2 * cfg.moe_d_ff * 4,
    }
    assert set(want) == set(hybrid_lm.KEPT)
    assert gauges[name] == want[name]


def test_keeping_residuals_changes_no_value(monkeypatch):
    """The loss and every gradient leaf with ``forward``'s policy equal
    those with nothing kept (each half rebuilt whole, as before PR 33): the
    kernels in interpret mode at the smallest shapes they admit, the expert
    layers on their sorted path under ``lax.cond``."""
    import jax

    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.ops import kda

    monkeypatch.setenv("MXNET_PALLAS", "1")
    cfg = kernel_config(attention=("kda", "mla"), mlp=("moe", "moe"))
    params = hybrid_lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (1, 257), 0, cfg.vocab_size)}

    def run():
        took = dict(kda.KDA_CALLS)
        out = jax.jit(jax.value_and_grad(
            hybrid_lm.loss_fn(cfg), has_aux=True))(params, batch, None)
        return out, kda.KDA_CALLS[("kda_chunk_fwd", "bfloat16")] - took.get(
            ("kda_chunk_fwd", "bfloat16"), 0)

    ((got, counts), got_grad), kept_sites = run()
    monkeypatch.setattr(hybrid_lm, "KEPT", ())
    ((want, _), want_grad), bare_sites = run()
    # the kernels ran, and the sorted path (a bucket of 512 rows) took both
    # layers' assignments
    assert kept_sites == bare_sites == 2
    assert 0 < int(counts.sum(axis=-1).max()) <= 512
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_grad),
                                   jax.tree.leaves(want_grad)):
        close(got_leaf, want_leaf, 1e-6)
