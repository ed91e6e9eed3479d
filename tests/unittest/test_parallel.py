"""TPU parallelism tests: mesh train steps, tensor parallel, ring attention.
These exercise the virtual 8-device CPU mesh (conftest) — the same code
runs on a real TPU slice."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import create_mesh, make_train_step, ShardedTrainer
from mxnet_tpu.parallel.ring_attention import make_ring_attention, ring_attention


def _dense_attention(q, k, v, causal=True, q_offset=0):
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        iq = np.arange(q.shape[2])[:, None] + q_offset
        ik = np.arange(k.shape[2])[None, :]
        scores = np.where(ik <= iq, scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


def test_mesh_creation():
    import jax

    mesh = create_mesh((2, 4), ("data", "model"))
    assert mesh.shape == {"data": 2, "model": 4}
    mesh1 = create_mesh((8,), ("data",))
    assert mesh1.devices.size == 8


def test_data_parallel_step_matches_single_device():
    import jax
    import jax.numpy as jnp
    import optax

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.RandomState(0)
    w0 = rng.rand(4, 3).astype("f")
    x = rng.rand(16, 4).astype("f")
    y = rng.rand(16, 3).astype("f")

    # single device
    step1, init1 = make_train_step(loss_fn, optax.sgd(0.1), donate=False)
    p1 = {"w": jnp.array(w0)}
    s1 = init1(p1)
    p1, s1, l1 = step1(p1, s1, {"x": x, "y": y}, jax.random.PRNGKey(0))

    # 8-way data parallel
    mesh = create_mesh((8,), ("data",))
    step8, init8 = make_train_step(loss_fn, optax.sgd(0.1), mesh=mesh, donate=False)
    p8 = {"w": jnp.array(w0)}
    s8 = init8(p8)
    p8, s8, l8 = step8(p8, s8, {"x": x, "y": y}, jax.random.PRNGKey(0))

    assert np.allclose(float(l1), float(l8), atol=1e-6)
    assert np.allclose(np.array(p1["w"]), np.array(p8["w"]), atol=1e-6)


def test_sharded_trainer_loss_decreases():
    import jax.numpy as jnp
    import optax

    def loss_fn(params, batch, rng):
        h = jnp.maximum(batch["x"] @ params["w1"], 0)
        pred = h @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.RandomState(1)
    params = {"w1": rng.rand(6, 16).astype("f") * 0.3,
              "w2": rng.rand(16, 1).astype("f") * 0.3}
    mesh = create_mesh((4,), ("data",))
    trainer = ShardedTrainer(loss_fn, params, optax.adam(1e-2), mesh=mesh)
    x = rng.rand(32, 6).astype("f")
    y = (x.sum(1, keepdims=True) > 3).astype("f")
    losses = [float(trainer.step({"x": x, "y": y})) for _ in range(40)]
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_ring_attention_matches_dense():
    import jax

    mesh = create_mesh((4,), ("seq",))
    B, H, T, D = 2, 2, 16, 8
    rng = np.random.RandomState(3)
    q = rng.randn(B, H, T, D).astype("f")
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    ring = make_ring_attention(mesh, seq_axis="seq", causal=True)
    out = np.array(ring(q, k, v))
    ref = _dense_attention(q, k, v, causal=True)
    assert np.allclose(out, ref, atol=1e-4), np.abs(out - ref).max()


def test_ring_attention_q_offset_chunked_prefill():
    """The serving chunked-prefill geometry: queries are the LAST C
    tokens of a longer key sequence (q_offset = prefix length). Ring
    with q_offset must match dense offset-causal attention for every
    chunk position."""
    mesh = create_mesh((4,), ("seq",))
    B, H, D = 1, 2, 8
    C, T = 16, 48  # chunk length, full key length
    rng = np.random.RandomState(11)
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    for off in (0, 16, 32):
        q = rng.randn(B, H, C, D).astype("f")
        ring = make_ring_attention(mesh, seq_axis="seq", causal=True,
                                   q_offset=off)
        out = np.array(ring(q, k[:, :, :off + C], v[:, :, :off + C]))
        ref = _dense_attention(q, k[:, :, :off + C], v[:, :, :off + C],
                               causal=True, q_offset=off)
        assert np.allclose(out, ref, atol=1e-4), (off,
                                                  np.abs(out - ref).max())


def test_ulysses_q_offset_matches_ring():
    """Both context-parallel schemes agree on the rectangular
    chunked-prefill case (q shorter than k, offset causal masking)."""
    from mxnet_tpu.parallel import make_ulysses_attention

    mesh = create_mesh((2,), ("seq",))
    B, H, D = 1, 2, 8
    C, off = 8, 16
    rng = np.random.RandomState(12)
    q = rng.randn(B, H, C, D).astype("f")
    k = rng.randn(B, H, off + C, D).astype("f")
    v = rng.randn(B, H, off + C, D).astype("f")
    uly = make_ulysses_attention(mesh, seq_axis="seq", causal=True,
                                 q_offset=off)
    ring = make_ring_attention(mesh, seq_axis="seq", causal=True,
                               q_offset=off)
    out_u = np.array(uly(q, k, v))
    out_r = np.array(ring(q, k, v))
    ref = _dense_attention(q, k, v, causal=True, q_offset=off)
    assert np.allclose(out_u, ref, atol=1e-4)
    assert np.allclose(out_u, out_r, atol=1e-4)


def test_cp_prefill_kv_matches_forward():
    """serving.cp_prefill_kv (chunked context-parallel prefill over the
    mesh) reproduces the training forward's final-position logits and
    next token for both schemes."""
    import jax

    from mxnet_tpu.models.transformer import (TransformerConfig, forward,
                                              init_params)
    from mxnet_tpu.serving import cp_prefill_kv

    mesh = create_mesh((4,), ("seq",))
    cfg = TransformerConfig(vocab_size=61, num_layers=2, d_model=32,
                            num_heads=4, d_ff=64, max_seq_len=96,
                            dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, 61, (32,)).astype(np.int32)
    ref = np.asarray(forward(params, prompt[None], cfg))[0, -1]
    embed = np.asarray(params["embed"], np.float32)
    for kind in ("ring", "ulysses"):
        k, v, x_last = cp_prefill_kv(params, cfg, prompt, mesh, kind=kind,
                                     chunk=16)
        logits = x_last @ embed.T
        assert np.allclose(logits, ref, atol=2e-4), (
            kind, np.abs(logits - ref).max())
        assert int(np.argmax(logits)) == int(np.argmax(ref))
        assert k.shape == (2, 32, 4, 8)


def test_ring_attention_non_causal():
    mesh = create_mesh((2,), ("seq",))
    B, H, T, D = 1, 1, 8, 4
    rng = np.random.RandomState(4)
    q = rng.randn(B, H, T, D).astype("f")
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    ring = make_ring_attention(mesh, seq_axis="seq", causal=False)
    out = np.array(ring(q, k, v))
    ref = _dense_attention(q, k, v, causal=False)
    assert np.allclose(out, ref, atol=1e-4)


def test_transformer_tensor_parallel_forward():
    """TP-sharded transformer forward == replicated forward."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, num_layers=2, d_model=32, num_heads=4, d_ff=64,
        max_seq_len=32, dtype="float32",
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, 64, (2, 16)).astype("i")

    logits_ref = np.array(tfm.forward(params, tokens, cfg))

    mesh = create_mesh((2, 4), ("data", "model"))
    specs = tfm.param_partition_specs(cfg)
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: hasattr(x, "shape"),
    )
    fwd = jax.jit(lambda p, t: tfm.forward(p, t, cfg))
    logits_tp = np.array(fwd(sharded, tokens))
    assert np.allclose(logits_ref, logits_tp, atol=1e-3)


def test_transformer_train_step_dp_tp():
    """2x4 dp×tp mesh training step runs and loss is finite."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=32, num_layers=1, d_model=16, num_heads=2, d_ff=32,
        max_seq_len=16, dtype="float32",
    )
    mesh = create_mesh((2, 4), ("data", "model"))
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    specs = tfm.param_partition_specs(cfg)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, param_shardings,
        is_leaf=lambda x: hasattr(x, "shape"),
    )
    step, init = make_train_step(
        tfm.loss_fn(cfg, mesh=mesh), optax.adam(1e-3), mesh=mesh,
        batch_spec={"tokens": NamedSharding(mesh, P("data", None))},
        donate=False,
    )
    opt_state = init(params)
    tokens = np.random.RandomState(1).randint(0, 32, (8, 16)).astype("i")
    params, opt_state, loss = step(params, opt_state, {"tokens": tokens},
                                   jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


def test_ulysses_attention_matches_dense():
    """All-to-all sequence parallelism == dense attention (the Ulysses
    counterpart of the ring test; heads divisible by axis size)."""
    from mxnet_tpu.parallel import make_ulysses_attention

    mesh = create_mesh((4,), ("seq",))
    B, H, T, D = 2, 4, 16, 8
    rng = np.random.RandomState(5)
    q = rng.randn(B, H, T, D).astype("f")
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    uly = make_ulysses_attention(mesh, seq_axis="seq", causal=True)
    out = np.array(uly(q, k, v))
    ref = _dense_attention(q, k, v, causal=True)
    assert np.allclose(out, ref, atol=1e-4), np.abs(out - ref).max()


def test_ulysses_matches_ring():
    """Both context-parallel schemes compute the same attention."""
    from mxnet_tpu.parallel import make_ulysses_attention
    from mxnet_tpu.parallel.ring_attention import make_ring_attention

    mesh = create_mesh((2,), ("seq",))
    B, H, T, D = 1, 2, 12, 4
    rng = np.random.RandomState(6)
    q = rng.randn(B, H, T, D).astype("f")
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    uly = make_ulysses_attention(mesh, seq_axis="seq", causal=False)
    ring = make_ring_attention(mesh, seq_axis="seq", causal=False)
    np.testing.assert_allclose(np.array(uly(q, k, v)),
                               np.array(ring(q, k, v)), atol=1e-4)


def test_ulysses_flash_kernel_path(monkeypatch):
    """At tiling lengths the Ulysses local attention rides the Pallas
    flash kernel (interpret mode on CPU) — parity vs dense, and the
    custom-vjp backward flows gradients through the all-to-alls (the
    property ring attention cannot get from the kernel: its cross-step
    LSE combine would need the kernel's internals)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import make_ulysses_attention
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXNET_PALLAS", "1")
    mesh = create_mesh((2,), ("seq",))
    B, H, T, D = 1, 2, 256, 16  # T_global=256 tiles (128-multiples)
    assert pk.flash_kernel_usable(T, T, D, D)
    rng = np.random.RandomState(7)
    q = rng.randn(B, H, T, D).astype("f")
    k = rng.randn(B, H, T, D).astype("f")
    v = rng.randn(B, H, T, D).astype("f")
    uly = make_ulysses_attention(mesh, seq_axis="seq", causal=True)
    # pin the PATH, not just the numerics: the Pallas forward must fire
    # (otherwise a gate regression would silently re-test the fallback)
    calls = []
    orig = pk._flash_attention_pallas

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(pk, "_flash_attention_pallas", counting)
    out = np.array(uly(q, k, v))
    assert calls, "Ulysses did not take the Pallas kernel path"
    monkeypatch.setattr(pk, "_flash_attention_pallas", orig)
    ref = _dense_attention(q, k, v, causal=True)
    assert np.allclose(out, ref, atol=2e-4), np.abs(out - ref).max()

    def loss(q):
        return jnp.sum(uly(q, jnp.asarray(k), jnp.asarray(v)) ** 2)

    g = jax.grad(loss)(jnp.asarray(q))
    assert np.isfinite(np.asarray(g)).all() and float(
        np.abs(np.asarray(g)).max()) > 0


def test_ulysses_head_divisibility_error():
    from mxnet_tpu.parallel import make_ulysses_attention

    mesh = create_mesh((4,), ("seq",))
    uly = make_ulysses_attention(mesh, seq_axis="seq")
    q = np.zeros((1, 2, 8, 4), "f")  # 2 heads, 4-way axis
    with pytest.raises(Exception, match="divide"):
        uly(q, q, q)


def test_moe_expert_parallel_matches_replicated():
    """Expert-sharded MoE == unsharded MoE (XLA inserts the collectives
    from sharding annotations)."""
    import jax
    from mxnet_tpu.parallel.moe import (
        init_moe_params, moe_ffn, shard_moe_params)

    params = init_moe_params(jax.random.PRNGKey(0), num_experts=8,
                             d_model=16, d_ff=32)
    x = np.random.RandomState(0).randn(4, 6, 16).astype("f")
    ref, aux_ref = jax.jit(moe_ffn)(params, x)

    mesh = create_mesh((4,), ("expert",))
    sharded = shard_moe_params(params, mesh)
    out, aux = jax.jit(moe_ffn)(sharded, x)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_moe_topk_routing_properties():
    import jax
    from mxnet_tpu.parallel.moe import init_moe_params, moe_ffn

    params = init_moe_params(jax.random.PRNGKey(1), num_experts=4,
                             d_model=8, d_ff=16)
    x = np.random.RandomState(1).randn(10, 8).astype("f")
    out1, _ = moe_ffn(params, x, top_k=1)
    out4, _ = moe_ffn(params, x, top_k=4)
    assert out1.shape == x.shape
    # top_k=all == dense mixture; differs from top-1 routing
    assert not np.allclose(np.array(out1), np.array(out4))


def test_pipeline_matches_sequential():
    """4-stage GPipe schedule over the pipe axis == applying the stages
    in sequence."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.pipeline import make_pipeline

    S, M, mb, d = 4, 6, 2, 8
    rng = np.random.RandomState(2)
    ws = rng.randn(S, d, d).astype("f") * 0.3
    bs = rng.randn(S, d).astype("f") * 0.1
    x = rng.randn(M, mb, d).astype("f")

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    mesh = create_mesh((S,), ("pipe",))
    pipe = make_pipeline(mesh, stage_fn, pipe_axis="pipe", n_microbatches=M)
    out = np.array(pipe({"w": ws, "b": bs}, x))

    ref = x.copy()
    for s in range(S):
        ref = np.tanh(ref @ ws[s] + bs[s])
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_pipeline_differentiable():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.pipeline import make_pipeline

    S, M, mb, d = 2, 3, 2, 4
    rng = np.random.RandomState(3)
    ws = rng.randn(S, d, d).astype("f") * 0.3
    x = rng.randn(M, mb, d).astype("f")

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"])

    mesh = create_mesh((S,), ("pipe",))
    pipe = make_pipeline(mesh, stage_fn, pipe_axis="pipe", n_microbatches=M)

    def loss(params):
        return jnp.sum(pipe(params, x) ** 2)

    g = jax.grad(loss)({"w": ws})
    assert np.isfinite(np.array(g["w"])).all()
    assert float(np.abs(np.array(g["w"])).max()) > 0


def test_pipeline_stage_count_mismatch_rejected():
    """4 stacked stages on a 2-device pipe mesh must error, not silently
    run stages [0, 2]."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.pipeline import make_pipeline

    mesh = create_mesh((2,), ("pipe",))
    pipe = make_pipeline(mesh, lambda p, a: jnp.tanh(a @ p["w"]),
                         pipe_axis="pipe", n_microbatches=2)
    ws = {"w": np.zeros((4, 4, 4), "f")}
    with pytest.raises(ValueError, match="stage"):
        pipe(ws, np.zeros((2, 2, 4), "f"))
