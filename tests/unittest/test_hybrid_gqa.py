"""The hybrid LM family's grouped-query layers (``models/hybrid_lm.py``:
``"swa"``, ``"full"``, the softmax router) against the benchmark's plain
reference (``benchmark/references/mellum2-12b-a2.5b.py``: float32
``jax.numpy``, k and v repeated per group, a masked softmax in blocks of
rows, both rotations written out, a loop over experts), at small sizes on
the CPU with seeded weights:

* both rotations' frequencies at the published parameters, and a rotated
  q.k that depends on ``i - j`` alone;
* a window layer and a full layer against the reference's;
* the softmax share layer against the reference's loop, in a typical batch,
  in one where every token names the same held expert (more than a sorted
  bucket smaller than the worst case holds: nothing may be dropped) and in
  one where no assignment lands here; the four shares of 16 add up to the
  uncut 64-expert layer;
* the whole model's loss and every leaf's gradient, a few steps through
  ``parallel.make_train_step`` + ``optax.adam``, and the same loss and
  gradients on a (2, 2) mesh under ``param_partition_specs``;
* under ``forward``'s policy a window kernel's forward runs once a layer, and
  a share of a quarter leaves no ``cond`` in the step;
* the reference's own counts of a step's work against counts by hand.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _load(name, *parts):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference module, found by its file's name."""
    return _load("reference_mellum2", "references", "mellum2-12b-a2.5b.py")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def _highest():
    import jax

    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(source, **over):
    """A config in the shape of ``benchmark/configs/mellum2-12b-a2.5b.json``
    at test size: a window layer and a full layer, 4 query heads over 2
    key/value heads, 4 of 16 experts held."""
    config = {
        "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
        "layer_types": ["sliding_attention", "full_attention"],
        "mlp_layer_types": ["sparse", "sparse"], "sliding_window": 24,
        "rope_parameters": source["rope_parameters"],
        "moe_intermediate_size": 32, "num_experts": 4, "experts_held": [4, 8],
        "published": {"num_experts": 16}, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "vocab_size": 384, "dtype": "float32"}
    config.update(over)
    return config


def program_config(ref, config):
    driver = _load("driver_train_hybrid_gqa", "drivers",
                   "train_hybrid_gqa.py")
    return driver.model_config(config, ref.sizes(config))


def close(got, want, rel=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= rel * scale, (
        float(np.max(np.abs(got - want))), scale)


# -- the rotations -----------------------------------------------------------------


def test_rotations_at_the_published_parameters(ref, published):
    """``low 18``, ``high 35`` and the factor of the published YaRN; the
    plain rotation's frequencies; the program's table is the reference's."""
    import math

    from mxnet_tpu.models import hybrid_lm

    sz = ref.sizes(published)
    assert sz["kinds"] == ("swa", "swa", "swa", "full") and sz["D"] == 128
    plain, one = ref.inv_freq(sz["rope"]["swa"], 128)
    m = np.arange(64)
    np.testing.assert_allclose(plain, 500000.0 ** (-2 * m / 128.0), rtol=1e-6)
    assert one == 1.0

    yarn, factor = ref.inv_freq(sz["rope"]["full"], 128)
    assert factor == 1.2772588722239782
    assert abs(0.1 * math.log(16) + 1 - factor) < 1e-15
    dim = [128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(5e5))
           for r in (32, 1)]
    assert abs(dim[0] - 18.08) < 0.01 and abs(dim[1] - 34.98) < 0.01
    ramp = np.clip((m - 18) / 17.0, 0, 1)
    np.testing.assert_allclose(
        yarn, (1 - ramp) * plain + ramp * plain / 16, rtol=1e-6)
    # the fast channels keep their frequency, the slow ones a sixteenth
    np.testing.assert_array_equal(yarn[:19], plain[:19])
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all((yarn[19:35] < plain[19:35])
                  & (yarn[19:35] > plain[19:35] / 16))

    cfg = program_config(ref, published)
    for kind, want in (("swa", (plain, 1.0)), ("full", (yarn, factor))):
        got, got_factor = hybrid_lm.rope_inv_freq(cfg, kind)
        np.testing.assert_array_equal(got, want[0])
        assert got_factor == want[1]


@pytest.mark.parametrize("kind", ["swa", "full"])
def test_rotated_products_depend_on_the_distance_alone(ref, published, kind):
    """One q and one k vector laid at every position: after the rotation
    ``q_i . k_j`` is a function of ``i - j`` (times the factor squared)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    cfg = program_config(ref, published)
    T, D = 64, cfg.head_dim
    q1, k1 = jax.random.normal(jax.random.PRNGKey(0), (2, D))
    inv_freq, factor = hybrid_lm.rope_inv_freq(cfg, kind)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    q = hybrid_lm._rotate(jnp.broadcast_to(q1, (1, T, 1, D)), cos, sin)
    k = hybrid_lm._rotate(jnp.broadcast_to(k1, (1, T, 1, D)), cos, sin)
    scores = np.asarray(jnp.einsum("id,jd->ij", q[0, :, 0], k[0, :, 0]))
    for offset in (0, 1, 7, 40):
        band = np.diagonal(scores, -offset)
        assert np.ptp(band) <= 2e-4 * np.abs(scores).max(), offset
    assert abs(scores[0, 0] / float(q1 @ k1) - factor ** 2) < 1e-5
    assert np.ptp(scores[:, 0]) > 1e-2 * np.abs(scores).max()  # it turns
    # and the reference turns the same way
    sz = ref.sizes(published)
    close(ref.rotate(jnp.broadcast_to(q1, (1, T, 1, D)), sz["rope"][kind], D),
          q, 1e-6)


# -- the layers --------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["swa", "full"])
def test_attention_layers_match_the_reference(ref, published, kind):
    import jax

    from mxnet_tpu.models import hybrid_lm

    config = tiny_config(published)
    sz = ref.sizes(config)
    cfg = program_config(ref, config)
    assert (cfg.num_heads, cfg.kv_heads, cfg.window) == (4, 2, 24)
    p = ref._draw(sz, jax.random.PRNGKey(5))["layers"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, sz["d"]))
    want = ref.attention(x, p, sz, kind)
    close(hybrid_lm.gqa_layer(x, p, cfg, kind), want)
    # the window and the rotation are in what was compared
    other = ref.attention(x, p, sz, kind, window=False, yarn=False)
    assert float(np.max(np.abs(np.asarray(other - want)))) > 1e-2


def _moe_case(ref, published, case):
    """(x [N, d], params, sizes) of one routing case. The router has no
    bias: a constant first feature of x and its row of the router stand in
    for one."""
    import jax

    if case == "typical":
        config = tiny_config(published)
        N = 256
    else:  # a bucket smaller than the worst case, so the overflow path exists
        config = tiny_config(published, num_experts=2, experts_held=[0, 2],
                             published={"num_experts": 64},
                             num_experts_per_tok=2)
        N = 2048
    sz = ref.sizes(config)
    p = ref._draw(sz, jax.random.PRNGKey(7))["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(8), (N, sz["d"]))
    if case != "typical":
        x = x.at[:, 0].set(1.0)
        lean = np.zeros(sz["E"], np.float32)
        if case == "all_on_one":
            lean[0] = 40.0  # every token names held expert 0
        else:
            lean[:2] = -40.0  # no token names a held expert
        p = dict(p, router=p["router"].at[0].set(lean))
    return x, p, sz


@pytest.mark.parametrize("case", ["typical", "all_on_one", "none_here"])
def test_softmax_share_layer_matches_the_reference_and_drops_nothing(
        ref, published, case):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    x, p, sz = _moe_case(ref, published, case)
    N = x.shape[0]
    assert "router_bias" not in p

    def program(x, p):
        return moe.moe_share_ffn(p, x, sz["top_k"], sz["held"],
                                 renormalize=sz["renormalize"],
                                 score="softmax")

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


def test_softmax_weights_are_the_chosen_probabilities_renormalised(ref,
                                                                   published):
    import jax

    from mxnet_tpu.parallel import moe

    x, p, sz = _moe_case(ref, published, "typical")
    idx, w = moe.route_top_k(p, x, sz["top_k"], score="softmax")
    prob = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1), np.float64)
    order = np.argsort(-prob, axis=-1, kind="stable")[:, :sz["top_k"]]
    assert np.array_equal(np.asarray(idx), order)
    chosen = np.take_along_axis(prob, order, axis=-1)
    close(w, chosen / chosen.sum(-1, keepdims=True), 1e-5)
    _, raw = moe.route_top_k(p, x, sz["top_k"], renormalize=False,
                             score="softmax")
    close(raw, chosen, 1e-5)
    with pytest.raises(ValueError):
        moe.route_top_k(p, x, sz["top_k"], score="tanh")


def test_four_shares_of_sixteen_add_up_to_the_uncut_layer(ref, published):
    """64 experts, top 8, four shares of 16 (the cell's deployment): what
    the shares give is what the uncut reference gives for the whole layer,
    every assignment counted once."""
    import jax

    from mxnet_tpu.parallel import moe

    whole = tiny_config(published, num_experts=64, experts_held=[0, 64],
                        published={"num_experts": 64}, num_experts_per_tok=8)
    sz = ref.sizes(whole)
    p = ref._draw(sz, jax.random.PRNGKey(11))["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(12), (128, sz["d"]))
    want, want_counts = ref.moe(x, p, sz)
    total, counts = 0.0, []
    for lo in range(0, 64, 16):
        held = (lo, lo + 16)
        mine = {"router": p["router"],
                "experts": jax.tree.map(lambda a: a[lo:lo + 16],
                                        p["experts"])}
        # a quarter under top 8: the bucket is everything that could land
        assert moe.share_bucket_rows(128, 64, held, 8) == 128 * 8
        y, n = moe.moe_share_ffn(mine, x, 8, held, score="softmax")
        total = total + y
        counts.append(np.asarray(n))
    close(total, want)
    counts = np.concatenate(counts)
    assert np.array_equal(counts, np.asarray(want_counts))
    assert counts.sum() == 128 * 8  # every assignment, once


# -- the whole model ---------------------------------------------------------------


def test_model_loss_and_every_gradient_match_the_reference(ref, published):
    import jax

    from mxnet_tpu.models import hybrid_lm

    config = tiny_config(published)
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
    assert not any("router_bias" in name or "shared" in name
                   for name in names)
    for got_leaf, want_leaf in zip(got_leaves, want_leaves):
        close(got_leaf, want_leaf, 5e-4)
    # each planted fault moves the loss: they are in what is compared
    for fault in ref.EXTRA_CONTROLS:
        broken = float(ref.loss_fn(params, tokens, sz, fault))
        assert abs(broken - float(want)) > 1e-5 * float(want), fault
    # the program's own initializer draws the same tree
    own = hybrid_lm.init_params(cfg, jax.random.PRNGKey(1))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(own)] == [
        a.shape for a in jax.tree.leaves(params)]
    assert jax.tree.structure(hybrid_lm.param_partition_specs(cfg)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))


def test_partition_specs_on_a_mesh_give_the_same_loss_and_gradients(
        ref, published):
    """``param_partition_specs`` on a (2, 2) mesh of CPU devices with a
    tensor and an expert axis: Wq / Wk / Wv by columns, Wo by rows, the
    held experts on the expert axis (XLA partitions by the annotations
    alone; the Pallas kernels are off on the CPU)."""
    import jax
    from jax.sharding import NamedSharding

    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.parallel import create_mesh

    config = tiny_config(published)
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
    attn = placed["layers"][0]["attn"]
    assert attn["wk"].sharding.shard_shape(attn["wk"].shape) == (64, 16)
    assert attn["wo"].sharding.shard_shape(attn["wo"].shape) == (32, 64)
    assert len(placed["layers"][1]["mlp"]["experts"]["w_up"]
               .sharding.device_set) == 4
    (got, got_counts), got_grad = grad(placed, batch, None)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert np.array_equal(np.asarray(got_counts), np.asarray(want_counts))
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_grad),
                                   jax.tree.leaves(want_grad)):
        close(got_leaf, want_leaf, 5e-4)


def test_trains_through_make_train_step(ref, published):
    import jax
    import optax

    from mxnet_tpu import parallel
    from mxnet_tpu.models import hybrid_lm

    cfg = program_config(ref, tiny_config(published, dtype="bfloat16"))
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
    assert hybrid_lm.record_routing(np.asarray(counts)[None], 128, cfg) >= 1.0


def test_config_refuses_what_it_cannot_build():
    from mxnet_tpu.models import hybrid_lm

    with pytest.raises(ValueError):
        hybrid_lm.HybridConfig(attention=("swa",), mlp=("moe",), num_heads=4,
                               num_kv_heads=3)
    with pytest.raises(ValueError):
        hybrid_lm.HybridConfig(router="tanh")
    with pytest.raises(ValueError):
        hybrid_lm.HybridConfig(attention=("local",), mlp=("moe",))


# -- what the forward keeps, and what the step holds -------------------------------


def _primitives(jaxpr, found):
    """``found[name] += 1`` for every Pallas kernel (by its name; what its
    body holds, a ``pl.when``'s ``cond`` say, is not the program's) and
    every other primitive in ``jaxpr`` and the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) + 1
            continue
        found[name] = found.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, found)
    return found


@pytest.fixture(scope="module")
def gradient_sites():
    """Primitives of the gradient of ``loss_fn``, traced abstractly with the
    kernels on, at the smallest shapes they admit: three window layers and a
    full one, 16 of 64 experts held; with ``forward``'s policy and with
    nothing kept."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    cfg = hybrid_lm.HybridConfig(
        vocab_size=256, d_model=128, attention=("swa", "swa", "swa", "full"),
        mlp=("moe",) * 4, num_heads=4, num_kv_heads=2, head_dim=64,
        window=128, rope_theta=5e5, yarn_factor=16.0, moe_d_ff=128,
        num_experts=64, experts_per_token=8, experts_held=(0, 16),
        num_shared_experts=0, router="softmax", dtype="bfloat16")
    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 257), jnp.int32)}

    def sites():
        return _primitives(jax.make_jaxpr(jax.grad(
            lambda p, b: hybrid_lm.loss_fn(cfg)(p, b, None)[0]))(
                params, batch).jaxpr, {})

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MXNET_PALLAS", "1")
        kept = sites()
        mp.setattr(hybrid_lm, "KEPT", ())
        bare = sites()
    return kept, bare


@pytest.mark.parametrize("kernel,layers", [("flash_win_fwd", 3),
                                           ("flash_fwd", 1)])
def test_a_window_kernels_forward_runs_once_a_layer(gradient_sites, kernel,
                                                    layers):
    kept, bare = gradient_sites
    assert kept[kernel] == layers and bare[kernel] == 2 * layers
    for name in ("bwd_dq", "bwd_dkv"):
        backward = kernel.replace("fwd", name)
        assert kept[backward] == bare[backward] == layers


def test_a_share_of_a_quarter_leaves_no_cond_in_the_step(gradient_sites):
    """The bucket is everything that could land (``share_bucket_rows``
    returns its worst case), so the program holds the sorted path alone:
    eleven grouped products a layer with nothing kept, nine with the gate's
    and the up's forward kept (five ``moe_gmm``: three forward, the down
    product rebuilt without its scale, the hidden's cotangent; one
    ``moe_gmm_pair``: the input's cotangent through gate and up; three
    ``moe_tgmm``), none routed to XLA, and no ``cond``."""
    kept, bare = gradient_sites
    assert "cond" not in kept and "cond" not in bare
    assert "ragged_dot_general" not in kept and "ragged_dot_general" not in bare
    assert (kept["moe_gmm"], kept["moe_gmm_pair"], kept["moe_tgmm"]) == (
        4 * 5, 4, 4 * 3)
    assert (bare["moe_gmm"], bare["moe_gmm_pair"], bare["moe_tgmm"]) == (
        4 * 7, 4, 4 * 3)
    assert kept["sort"] == 4 and bare["sort"] == 8


# -- the reference's own counts ----------------------------------------------------


def test_the_references_counts_against_counts_by_hand(ref, published):
    mix = {"batch": 1, "seq_len": 8192}
    T, W, d = 8192, 1024, 2304
    assert ref.window_pairs(T, W) == 7864832 == sum(
        min(i + 1, W) for i in range(T))
    assert T * (T + 1) // 2 == 33558528
    proj = 2 * d * 4096 * 2 + 2 * 2 * d * 512          # q, o; k, v
    full = 32 * 4 * 128 * 33558528 / T                   # a token, forward
    swa = 32 * 4 * 128 * 7864832 / T
    moe = 2 * d * 64 + 8 * 16 / 64 * 3 * 2 * d * 896    # two land here
    head = 2 * d * 24576
    token = 4 * (proj + moe) + full + 3 * swa + head
    assert ref.train_flops(published, mix) == 3 * T * token
    assert abs(3 * token / 1e9 - 1.49) < 0.01           # GFLOP a token
    work, nbytes = ref.attention_work(published, mix)
    assert work == 3 * 32 * 33558528 * 4 * 128
    assert nbytes == T * 128 * 2 * (32 * 6 + 4 * 6)
    work, nbytes = ref.window_attention_work(published, mix)
    assert work == 3 * (3 * 32 * 7864832 * 4 * 128)
    assert nbytes == 3 * T * 128 * 2 * (32 * 6 + 4 * 6)
    # the parameters the cut holds, as the configuration's file reckons them
    import jax

    sz = ref.sizes(published)
    shapes = jax.eval_shape(lambda k: ref._draw(sz, k), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 595153152
