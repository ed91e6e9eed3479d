"""The hybrid LM family's rotated latent attention with a query latent and its
multi-token-prediction module (``models/hybrid_lm.py``: ``"mla"`` under
``q_lora_rank`` / ``mla_rope_theta``, ``mtp_modules``) against the
benchmark's plain reference (``benchmark/references/glm-4.7-flash.py``:
float32 ``jax.numpy``, the rotation written out, a masked softmax in blocks
of rows, a loop over experts, the module and both losses), at small sizes on
the CPU with seeded weights:

* the rotated latent layer against the reference's, with the query latent
  and without; a rotated q.k that depends on ``i - j`` alone; with neither
  the layer is the parent's to the bit;
* the module's logits against the reference's; the position whose target
  does not exist moves no gradient; the shared embedding's and head's
  gradients are the sum of the two losses'; a weight of 0 gives the main
  model's gradients;
* the whole model's two losses and every leaf's gradient in a typical batch,
  in one where every token names the same held expert and in one where no
  assignment lands here; the eight shares of 8 experts add up to the uncut
  64-expert layer;
* a few steps through ``parallel.make_train_step`` + ``optax.adam``, and the
  same loss and gradients on a (2, 2) mesh under ``param_partition_specs``;
* the parameters the cell's cut holds, and the reference's own counts of a
  step's work against counts by hand;
* with a module the two losses' [T, V] tensors are not residuals of the
  step; without one the loss's jaxpr is what it was.
"""
import dataclasses
import functools
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
    return _load("reference_glm47flash", "references", "glm-4.7-flash.py")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def _highest():
    import jax

    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(source, **over):
    """A config in the shape of ``benchmark/configs/glm-4.7-flash.json`` at
    test size: a dense layer and an MoE layer of latent attention, the
    module, 4 of 16 experts held under top 4."""
    config = dict(source)
    config.pop("rehearsal")
    config.update({
        "hidden_size": 64, "num_layers": 2, "num_attention_heads": 2,
        "intermediate_size": 128, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_routed_experts": 4,
        "experts_held": [4, 8], "published": {"n_routed_experts": 16},
        "vocab_size": 384, "dtype": "float32"})
    config.update(over)
    return config


def program_config(ref, config, **over):
    driver = _load("driver_train_hybrid_mla", "drivers",
                   "train_hybrid_mla.py")
    return dataclasses.replace(
        driver.model_config(config, ref.sizes(config)), **over)


def _text(jaxpr):
    """A jaxpr as text, less the addresses of the functions it names."""
    import re

    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def _primitives(jaxpr, found):
    """``found[name] += 1`` for every Pallas kernel (by its name) and every
    other primitive in ``jaxpr`` and the jaxprs its equations hold."""
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


def close(got, want, rel=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= rel * scale, (
        float(np.max(np.abs(got - want))), scale)


# -- the latent layer --------------------------------------------------------------


def test_the_rotation_at_the_published_parameters(ref, published):
    """theta 1e6 over the 64 rotated channels, all of them turning
    (``partial_rotary_factor`` 1): the program's table is the reference's,
    and a rotated q.k depends on ``i - j`` alone."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    sz = ref.sizes(published)
    cfg = program_config(ref, published)
    assert (cfg.mla_rope_theta, cfg.qk_rope_dim, cfg.q_lora_rank) == (
        1e6, 64, 768)
    inv_freq = hybrid_lm._plain_inv_freq(cfg.mla_rope_theta, 64)
    np.testing.assert_allclose(
        inv_freq, 1e6 ** (-2 * np.arange(32) / 64.0), rtol=1e-12)
    T = 48
    cos, sin = hybrid_lm._rope_table(T, inv_freq)
    q1, k1 = jax.random.normal(jax.random.PRNGKey(1), (2, 64))
    q = hybrid_lm._rotate(jnp.broadcast_to(q1, (1, T, 1, 64)), cos, sin)
    k = hybrid_lm._rotate(jnp.broadcast_to(k1, (1, T, 1, 64)), cos, sin)
    close(q, ref.rotate(jnp.broadcast_to(q1, (1, T, 1, 64)), sz["theta"]),
          1e-5)
    scores = np.asarray(jnp.einsum("id,jd->ij", q[0, :, 0], k[0, :, 0]))
    for lag in (0, 1, 7, 30):
        along = np.diagonal(scores, -lag)
        assert np.max(np.abs(along - along[0])) <= 2e-4 * np.abs(
            scores).max(), lag
    assert np.abs(scores[7, 0] - scores[0, 0]) > 1e-2 * np.abs(scores).max()


@pytest.mark.parametrize("query", ["latent", "direct"])
def test_rotated_latent_layer_matches_the_reference(ref, published, query):
    """The layer against the reference's, through the query latent as
    published and with the query straight from x: the reference is then
    given an identity for ``Wq_a`` over rows of unit mean square, which its
    norm leaves as they are."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    config = tiny_config(published)
    if query == "direct":
        config["q_lora_rank"] = config["hidden_size"]
    sz = ref.sizes(config)
    cfg = program_config(ref, config)
    p = ref._draw(sz, jax.random.PRNGKey(5))["layers"][1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, sz["d"]))
    mine = p
    if query == "direct":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
        p = dict(p, wq_a=jnp.eye(sz["d"]))
        cfg = dataclasses.replace(cfg, q_lora_rank=0)
        mine = {k: v for k, v in p.items() if k not in ("wq_a", "q_norm",
                                                         "wq_b")}
        mine["wq"] = p["wq_b"]
    want = ref._mla(x, p, sz, lambda a: a)
    close(hybrid_lm.mla_layer(x, mine, cfg), want)
    # the rotation is in what is compared
    flat = ref._mla(x, p, sz, lambda a: a, rope=False)
    assert float(jnp.max(jnp.abs(flat - want))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))
    close(hybrid_lm.mla_layer(
        x, mine, dataclasses.replace(cfg, mla_rope_theta=0.0)), flat)


def _parent_mla_layer(x, p, cfg):
    """``hybrid_lm.mla_layer`` as it stood before the query latent and the
    rotation (PR 35), kept here to hold the new one to it."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.hybrid_lm import _mm, _rms_norm
    from mxnet_tpu.ops.pallas_kernels import flash_attention

    B, T, _ = x.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    dtype = jnp.dtype(cfg.dtype)
    mm = functools.partial(_mm, dtype=dtype)
    with jax.named_scope("mla"):
        q = mm(x, p["wq"]).reshape(B, T, H, dn + dr)
        kva = mm(x, p["wkva"])
        kvb = mm(_rms_norm(kva[..., :r], p["kv_norm"], cfg.rms_eps),
                 p["wkvb"]).reshape(B, T, H, dn + dv)
        k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
            kva[:, :, None, r:], (B, T, H, dr))], axis=-1)

        def heads(t):
            return t.astype(dtype).transpose(0, 2, 1, 3)

        o = flash_attention(heads(q), heads(k), heads(kvb[..., dn:]),
                            causal=True, scale=(dn + dr) ** -0.5)
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * dv), p["wo"])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_without_latent_and_rotation_it_is_the_parents_layer(dtype):
    """``q_lora_rank`` 0 and ``mla_rope_theta`` 0 (Kimi-Linear's NoPE
    layer): the same equations in the same order as before, the same
    values to the bit."""
    import jax

    from mxnet_tpu.models import hybrid_lm

    cfg = hybrid_lm.HybridConfig(
        d_model=64, attention=("mla",), mlp=("dense",), num_heads=2,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        dtype=dtype)
    assert cfg.q_lora_rank == 0 and cfg.mla_rope_theta == 0
    p = hybrid_lm.init_params(cfg, jax.random.PRNGKey(3))["layers"][0]["attn"]
    assert sorted(p) == ["kv_norm", "wkva", "wkvb", "wo", "wq"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    new = jax.make_jaxpr(lambda x, p: hybrid_lm.mla_layer(x, p, cfg))(x, p)
    old = jax.make_jaxpr(lambda x, p: _parent_mla_layer(x, p, cfg))(x, p)
    assert _text(new) == _text(old)
    np.testing.assert_array_equal(
        np.asarray(hybrid_lm.mla_layer(x, p, cfg), np.float32),
        np.asarray(_parent_mla_layer(x, p, cfg), np.float32))


# -- the expert layer's share ------------------------------------------------------


def test_eight_shares_of_eight_add_up_to_the_uncut_layer(ref, published):
    """64 experts, top 4, eight shares of 8 (the cell's deployment): what
    the shares give, the shared expert counted once, is what the uncut
    reference gives for the whole layer, every assignment counted once."""
    import jax

    from mxnet_tpu.parallel import moe

    whole = tiny_config(published, n_routed_experts=64, experts_held=[0, 64],
                        published={"n_routed_experts": 64})
    sz = ref.sizes(whole)
    assert (sz["top_k"], sz["route_scale"], sz["shared"]) == (4, 1.8, 1)
    p = ref._draw(sz, jax.random.PRNGKey(11))["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(12), (128, sz["d"]))
    want, want_counts = ref.moe(x, p, sz)
    total, counts = 0.0, []
    for lo in range(0, 64, 8):
        held = (lo, lo + 8)
        mine = {"router": p["router"], "router_bias": p["router_bias"],
                "experts": jax.tree.map(lambda a: a[lo:lo + 8],
                                        p["experts"])}
        if lo == 0:  # what every chip computes alike: once
            mine["shared"] = p["shared"]
        # an eighth under top 4: the bucket is everything that could land
        assert moe.share_bucket_rows(128, 64, held, 4) == 128 * 4
        y, n = moe.moe_share_ffn(mine, x, 4, held, sz["route_scale"],
                                 sz["renormalize"])
        total = total + y
        counts.append(np.asarray(n))
    close(total, want)
    counts = np.concatenate(counts)
    assert np.array_equal(counts, np.asarray(want_counts))
    assert counts.sum() == 128 * 4  # every assignment, once
    # the cell's bucket and the other cells' are what they were
    assert moe.share_bucket_rows(8192, 64, (0, 8), 4) == 32768
    assert moe.share_bucket_rows(8192, 256, (0, 8), 8) == 16384
    assert moe.share_bucket_rows(8192, 64, (0, 16), 8) == 65536


# -- the module --------------------------------------------------------------------


def _model_case(ref, published, case="typical", seed=13):
    """(config, sizes, program config, params, tokens [2, 130]) with the
    MoE blocks' routing leaning as ``case`` says."""
    import jax
    import jax.numpy as jnp

    config = tiny_config(published)
    sz = ref.sizes(config)
    cfg = program_config(ref, config)
    params = ref._draw(sz, jax.random.PRNGKey(seed))
    if case != "typical":
        bias = np.zeros(sz["E"], np.float32)
        if case == "all_on_one":
            bias[4] = 10.0   # every token names held expert 4
        else:
            bias[4:8] = -10.0  # no token names a held expert
        lean = jnp.asarray(bias)
        params["layers"][1]["mlp"]["router_bias"] = lean
        params["mtp"]["block"]["mlp"]["router_bias"] = lean
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 130), 0,
                                sz["V"])
    return config, sz, cfg, params, tokens


def test_the_modules_logits_match_the_reference(ref, published):
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    _, sz, cfg, params, tokens = _model_case(ref, published)
    h, z, counts = hybrid_lm._run(params, tokens[:, :-1], cfg, tokens[:, 1:])
    close(jnp.dot(z, params["lm_head"]), ref.mtp_logits(params, tokens, sz))
    logits, main_counts = hybrid_lm.forward(params, tokens[:, :-1], cfg)
    close(logits, ref.forward(params, tokens[:, :-1], sz)[0])
    close(jnp.dot(h, params["lm_head"]), logits, 1e-6)
    # the main model's counts, then the module's row
    assert len(counts) == cfg.moe_blocks == 2 and cfg.moe_layers == 1
    assert np.array_equal(np.asarray(counts[0]), np.asarray(main_counts[0]))
    want = ref.hidden(params, tokens[:, :-1], sz, None, tokens[:, 1:])[2]
    assert np.array_equal(np.asarray(jnp.stack(counts)), np.asarray(want))
    # embedding token i instead of i + 1 is another model
    shifted = hybrid_lm._run(params, tokens[:, :-1], cfg, tokens[:, :-1])[1]
    assert float(jnp.max(jnp.abs(shifted - z))) > 1e-2


def test_the_position_without_a_target_moves_no_gradient(ref, published):
    """The module runs over all T positions; the last one's weight is 0:
    no gradient reaches its hidden state, and the loss is the reference's
    mean over the T - 1 positions that have a target."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    _, sz, cfg, params, tokens = _model_case(ref, published)
    batch = {"tokens": tokens}
    main, mtp, _ = hybrid_lm.losses(params, batch, cfg)
    want_main, want_mtp = ref.losses(params, tokens, sz)
    assert abs(float(main) - float(want_main)) <= 1e-5 * float(want_main)
    assert abs(float(mtp) - float(want_mtp)) <= 1e-5 * float(want_mtp)
    B, T = tokens.shape[0], tokens.shape[1] - 1
    z = jax.random.normal(jax.random.PRNGKey(2), (B, T, sz["d"]))
    weights = jnp.broadcast_to((jnp.arange(T) < T - 1).astype(jnp.float32),
                               (B, T))
    targets = jnp.pad(tokens[:, 2:], ((0, 0), (0, 1)))
    dz = jax.grad(lambda z: hybrid_lm._head_loss(
        z, params["lm_head"], targets, weights, "float32"))(z)
    assert float(jnp.max(jnp.abs(dz[:, -1]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(dz[:, :-1]), axis=-1))) > 0.0


def test_shared_embedding_and_head_gradients_are_the_two_losses_sum(
        ref, published):
    """Each loss alone, then both: the embedding's and the head's gradients
    add; a weight of 0 gives the main model's gradients exactly, and the
    module's own leaves none."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    _, sz, cfg, params, tokens = _model_case(ref, published)
    batch = {"tokens": tokens}
    g_main = jax.grad(lambda p: hybrid_lm.losses(p, batch, cfg)[0])(params)
    g_mtp = jax.grad(lambda p: hybrid_lm.losses(p, batch, cfg)[1])(params)
    g_both = jax.grad(lambda p: hybrid_lm.loss_fn(cfg)(p, batch, None)[0])(
        params)
    assert cfg.mtp_weight == 0.3
    for name in ("embed", "lm_head"):
        assert float(jnp.max(jnp.abs(g_main[name]))) > 0
        assert float(jnp.max(jnp.abs(g_mtp[name]))) > 0
        close(g_both[name], g_main[name] + 0.3 * g_mtp[name], 1e-5)
    # the main loss reaches nothing of the module, the module's loss does
    assert all(float(jnp.max(jnp.abs(a))) == 0
               for a in jax.tree.leaves(g_main["mtp"]))
    assert float(jnp.max(jnp.abs(g_mtp["mtp"]["eh_proj"]))) > 0
    off = dataclasses.replace(cfg, mtp_weight=0.0)
    g_off = jax.grad(lambda p: hybrid_lm.loss_fn(off)(p, batch, None)[0])(
        params)
    for got, want in zip(jax.tree.leaves(g_off), jax.tree.leaves(g_main)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and they are the gradients of the model built without a module
    bare = dataclasses.replace(cfg, mtp_modules=0)
    lone = {k: v for k, v in params.items() if k != "mtp"}
    (loss, counts), g_bare = jax.value_and_grad(
        hybrid_lm.loss_fn(bare), has_aux=True)(lone, batch, None)
    assert counts.shape == (1, 4)
    close(loss, hybrid_lm.losses(params, batch, cfg)[0], 1e-6)
    for got, want in zip(jax.tree.leaves(g_bare), jax.tree.leaves(
            {k: v for k, v in g_main.items() if k != "mtp"})):
        close(got, want, 1e-5)


# -- the whole model ---------------------------------------------------------------


@pytest.mark.parametrize("case", ["typical", "all_on_one", "none_here"])
def test_model_loss_and_every_gradient_match_the_reference(ref, published,
                                                           case):
    import jax

    from mxnet_tpu.models import hybrid_lm

    config, sz, cfg, params, tokens = _model_case(ref, published, case)
    want, want_grad = ref.loss_and_grad(params, tokens, sz)
    (got, counts), got_grad = jax.value_and_grad(
        hybrid_lm.loss_fn(cfg), has_aux=True)(params, {"tokens": tokens}, None)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert counts.shape == (2, 4)
    landed = np.asarray(counts).sum(axis=-1)
    N = tokens.shape[0] * (tokens.shape[1] - 1)
    if case == "all_on_one":
        assert np.array_equal(np.asarray(counts)[:, 0], [N, N])
    elif case == "none_here":
        assert landed.sum() == 0
    else:
        assert np.all(landed > 0)
    names = ref.leaf_names(config)
    got_leaves, want_leaves = jax.tree.leaves(got_grad), jax.tree.leaves(
        want_grad)
    assert len(names) == len(got_leaves) == len(want_leaves)
    assert sum(name.startswith("mtp.") for name in names) == 21
    for got_leaf, want_leaf in zip(got_leaves, want_leaves):
        close(got_leaf, want_leaf, 5e-4)
    if case != "typical":
        return
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
    tensor and an expert axis: Wq_b and Wkv_b by columns, Wo by rows, the
    two latents' down-projections and norms replicated, the held experts
    on the expert axis, the module's block like a layer."""
    import jax
    from jax.sharding import NamedSharding

    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.parallel import create_mesh

    _, _, cfg, params, tokens = _model_case(ref, published, seed=21)
    batch = {"tokens": tokens}
    grad = jax.jit(jax.value_and_grad(hybrid_lm.loss_fn(cfg), has_aux=True))
    (want, want_counts), want_grad = grad(params, batch, None)

    mesh = create_mesh((2, 2), (cfg.tensor_axis, cfg.expert_axis))
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        hybrid_lm.param_partition_specs(cfg),
        is_leaf=lambda x: hasattr(x, "shape"))
    attn = placed["mtp"]["block"]["attn"]
    assert attn["wq_a"].sharding.shard_shape(attn["wq_a"].shape) == (64, 24)
    assert attn["wq_b"].sharding.shard_shape(attn["wq_b"].shape) == (24, 24)
    assert attn["wo"].sharding.shard_shape(attn["wo"].shape) == (16, 64)
    assert len(placed["mtp"]["block"]["mlp"]["experts"]["w_up"]
               .sharding.device_set) == 4
    (got, got_counts), got_grad = grad(placed, batch, None)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert np.array_equal(np.asarray(got_counts), np.asarray(want_counts))
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_grad),
                                   jax.tree.leaves(want_grad)):
        close(got_leaf, want_leaf, 5e-4)


def test_trains_through_make_train_step(ref, published, monkeypatch):
    import jax
    import optax

    from mxnet_tpu import parallel, telemetry
    from mxnet_tpu.models import hybrid_lm

    cfg = program_config(ref, tiny_config(published, dtype="bfloat16"))
    params = hybrid_lm.init_params(cfg, jax.random.PRNGKey(2))
    step, init_state = parallel.make_train_step(
        hybrid_lm.loss_fn(cfg), optax.adam(3e-3), has_aux=True)
    opt_state = init_state(params)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(15), (2, 66), 0, cfg.vocab_size)}
    first = hybrid_lm.losses(params, batch, cfg)
    losses = []
    for _ in range(4):
        params, opt_state, loss, counts = step(params, opt_state, batch, None)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))
    assert abs(losses[0] - float(first[0]) - 0.3 * float(first[1])) < 1e-2
    assert counts.shape == (cfg.moe_blocks, 4) == (2, 4)
    assert hybrid_lm.record_routing(np.asarray(counts)[None], 130, cfg) >= 1.0
    # both cross-entropies fell, and a host that reads them can gauge them
    main, mtp, _ = hybrid_lm.losses(params, batch, cfg)
    assert float(main) < float(first[0]) and float(mtp) < float(first[1])
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset()
    telemetry.reload()
    try:
        hybrid_lm.record_losses(main, mtp)
        gauges = telemetry.snapshot()["gauges"]
        assert gauges["loss.main"] == float(main)
        assert gauges["loss.mtp"] == float(mtp)
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY")
        telemetry.reset()
        telemetry.reload()


def test_config_refuses_what_it_cannot_build():
    from mxnet_tpu.models import hybrid_lm

    with pytest.raises(ValueError):
        hybrid_lm.HybridConfig(mtp_modules=2)
    with pytest.raises(ValueError):
        hybrid_lm.HybridConfig(mla_rope_theta=1e4, qk_rope_dim=7)


# -- the cell's size, and the reference's own counts -------------------------------


def test_the_cells_parameters_and_counts_against_counts_by_hand(ref,
                                                                published):
    """706.9 M parameters as built at the cell's sizes (shapes only), by the
    program's initializer and the reference's draw alike; the reference's
    ``train_flops`` and ``attention_work`` against counts by hand."""
    import jax

    from mxnet_tpu.models import hybrid_lm

    sz = ref.sizes(published)
    cfg = program_config(ref, published)
    d, V, T = 2048, 19456, 8192
    attn = d * 768 + 768 + 768 * 5120 + d * 576 + 512 + 512 * 8960 + 5120 * d
    expert = 3 * d * 1536
    moe = 8 * expert + expert + d * 64 + 64
    layer = attn + 2 * d + moe
    dense = attn + 2 * d + 3 * d * 10240
    module = layer + 2 * d * d + 3 * d
    total = dense + 4 * layer + module + 2 * V * d + d
    assert total == 706912064 and abs(total / 1e6 - 706.9) < 0.05
    for shapes in (
            jax.eval_shape(lambda k: ref._draw(sz, k), jax.random.PRNGKey(0)),
            jax.eval_shape(lambda k: hybrid_lm.init_params(cfg, k),
                           jax.random.PRNGKey(0))):
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total
    assert cfg.attention == ("mla",) * 5 and cfg.mlp == ("dense",) + (
        "moe",) * 4 and cfg.mtp_modules == 1 and cfg.experts_held == (0, 8)
    assert cfg.kept == ("flash", "moe_sort")  # the file's assumed.kept

    mix = {"batch": 1, "seq_len": T}
    pairs = T * (T + 1) // 2
    assert pairs == 33558528
    proj = 2 * (d * 768 + 768 * 5120 + d * 576 + 512 * 8960 + 5120 * d)
    scores = 20 * (2 * 256 + 2 * 256) * pairs / T          # a token, forward
    block = 2 * d * 64 + (1 + 4 * 8 / 64) * 3 * 2 * d * 1536
    token = 6 * (proj + scores) + 3 * 2 * d * 10240 + 5 * block \
        + 2 * (2 * d * V) + 2 * 2 * d * d
    assert ref.train_flops(published, mix) == 3 * T * token
    assert abs(3 * token / 1e9 - 3.63) < 0.01               # GFLOP a token
    assert abs(3 * 6 * scores / 1e9 - 1.51) < 0.01
    work, nbytes = ref.attention_work(published, mix)
    assert work == 6 * 3 * 20 * pairs * (2 * 256 + 2 * 256)
    assert nbytes == 6 * 20 * T * 2 * (3 * 256 + 256 + 4 * 256 + 4 * 256)


# -- what the step holds -----------------------------------------------------------


def _trace_config():
    from mxnet_tpu.models import hybrid_lm

    return hybrid_lm.HybridConfig(
        vocab_size=640, d_model=128, attention=("mla", "mla"),
        mlp=("dense", "moe"), num_heads=2, kv_lora_rank=64, qk_nope_dim=96,
        qk_rope_dim=32, v_head_dim=128, q_lora_rank=48, mla_rope_theta=1e6,
        d_ff=256, moe_d_ff=128, num_experts=64, experts_per_token=4,
        experts_held=(0, 8), route_scale=1.8, mtp_modules=1,
        dtype="bfloat16")


def _saved(cfg, batch=(1, 257)):
    """The saved residuals of the differentiated loss, as
    ``print_saved_residuals`` lists them (nothing runs)."""
    import io
    from contextlib import redirect_stdout

    import jax
    import jax.ad_checkpoint
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    tokens = {"tokens": jax.ShapeDtypeStruct(batch, jnp.int32)}
    text = io.StringIO()
    with redirect_stdout(text):
        jax.ad_checkpoint.print_saved_residuals(
            lambda p, b: hybrid_lm.loss_fn(cfg)(p, b, None)[0], params,
            tokens)
    return text.getvalue()


def test_two_losses_keep_no_logits_and_one_loss_keeps_what_it_kept(
        monkeypatch):
    """With a module each loss's head product and log-softmax sits under
    its own ``jax.checkpoint``: no [T, V] float32 tensor is a residual of
    the step (two pairs alive together were 2.55 GB at the cell's size).
    Without one the loss is the parent's: its logits stay residuals, and
    the step holds the flash kernels three times a block, a module's block
    included, counted under the limit-free key at these narrow shapes."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "1")
    cfg = _trace_config()
    took = dict(pk.FLASH_CALLS)
    with_module = _saved(cfg)
    assert "f32[1,256,640]" not in with_module
    assert "named 'flash'" in with_module
    new = {key: n - took.get(key, 0) for key, n in pk.FLASH_CALLS.items()
           if n != took.get(key, 0)}
    assert {key[0] for key in new} == {"flash_fwd"} and all(
        len(key) == 3 for key in new)
    without = _saved(dataclasses.replace(cfg, mtp_modules=0))
    assert "f32[1,256,640]" in without

    # the step with a module: three blocks' kernels, no cond, no ragged dot
    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 257), jnp.int32)}
    sites = _primitives(jax.make_jaxpr(jax.grad(
        lambda p, b: hybrid_lm.loss_fn(cfg)(p, b, None)[0]))(
            params, batch).jaxpr, {})
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sites[kernel] == 3, kernel
    assert "cond" not in sites and "ragged_dot_general" not in sites
    assert (sites["moe_gmm"], sites["moe_gmm_pair"], sites["moe_tgmm"]) == (
        2 * 5, 2, 2 * 3)


def test_kept_without_moe_hidden_changes_no_value(monkeypatch):
    """``cfg.kept`` is the trainer's setting (None: ``hybrid_lm.KEPT``). The
    benchmark's cell names ``("flash", "moe_sort")``: the bucket's gate and
    up products are then no residuals of the step and their gauge reads 0,
    two more grouped products a block run in the backward pass, and the
    loss and every gradient are what they were."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset()
    telemetry.reload()
    try:
        cfg = _trace_config()
        assert cfg.kept is None
        lean = dataclasses.replace(cfg, kept=("flash", "moe_sort"))
        rows = 4 * 256  # everything that could land: 8 of 64 under top 4
        hidden = "f32[%d,128]" % rows
        assert hidden in _saved(cfg)
        assert telemetry.snapshot()["gauges"][
            "remat.saved_bytes.moe_hidden"] == 2 * rows * 2 * 128 * 4
        text = _saved(lean)
        assert hidden not in text and "named 'flash'" in text
        gauges = telemetry.snapshot()["gauges"]
        assert gauges["remat.saved_bytes.moe_hidden"] == 0
        assert gauges["remat.saved_bytes.flash"] == 3 * 2 * 256 * (
            2 * 128 + 32)
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY")
        telemetry.reset()
        telemetry.reload()
    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 257), jnp.int32)}

    def sites(c):
        return _primitives(jax.make_jaxpr(jax.grad(
            lambda p, b: hybrid_lm.loss_fn(c)(p, b, None)[0]))(
                params, batch).jaxpr, {})

    assert sites(lean)["moe_gmm"] == sites(cfg)["moe_gmm"] + 2 * 2
    monkeypatch.setenv("MXNET_PALLAS", "0")
    params = hybrid_lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (1, 257), 0, cfg.vocab_size)}
    (want, _), want_grad = jax.value_and_grad(
        hybrid_lm.loss_fn(cfg), has_aux=True)(params, batch, None)
    (got, _), got_grad = jax.value_and_grad(
        hybrid_lm.loss_fn(lean), has_aux=True)(params, batch, None)
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    for got_leaf, want_leaf in zip(jax.tree.leaves(got_grad),
                                   jax.tree.leaves(want_grad)):
        close(got_leaf, want_leaf, 1e-6)


def test_without_a_module_the_loss_is_the_parents(monkeypatch):
    """``mtp_modules`` 0, ``q_lora_rank`` 0, ``mla_rope_theta`` 0: the
    loss's jaxpr holds what the parent's held, equation for equation: no
    checkpointed head loss (one ``checkpoint`` a half and no more), the
    log-softmax outside any, no rotation."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_lm

    cfg = hybrid_lm.HybridConfig(
        vocab_size=256, d_model=64, attention=("mla", "mla"),
        mlp=("dense", "moe"), num_heads=2, kv_lora_rank=32, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32, num_experts=16,
        experts_held=(0, 4), dtype="float32")
    params = jax.eval_shape(lambda key: hybrid_lm.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 65), jnp.int32)}

    def parent_loss(params, batch):
        """``loss_fn``'s body as it stood (PR 35)."""
        tokens = batch["tokens"]
        logits, counts = hybrid_lm.forward(params, tokens[:, :-1], cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(nll), counts

    new = jax.make_jaxpr(
        lambda p, b: hybrid_lm.loss_fn(cfg)(p, b, None))(params, batch)
    old = jax.make_jaxpr(parent_loss)(params, batch)
    assert _text(new) == _text(old)
    sites = _primitives(new.jaxpr, {})
    assert sites["remat2"] == 4 and "cos" not in sites
