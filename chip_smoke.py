#!/usr/bin/env python
"""chip_smoke.py: does the system still start on the chip?

Drives the main paths once, through the entry points a user calls, at the
full width of the models the repo supports, with random weights made from
a seed, and checks by the repo's own means that what comes out is right:

  a. op parity chip vs host — the cases of tools/check_tpu_consistency.py
  b. the public trainer     — mx.FeedForward.fit, ResNet-50 s2d, bs 128,
                              bf16, the scanned K-step path
  c. the LM trainer         — models/transformer.py, 1024 wide, 12 layers,
                              through parallel.make_train_step, with the
                              Pallas flash kernels in the compiled step
  d. serving                — serving.Engine over the same width: submit/
                              stream and generate, speculative decoding
                              off then on, against transformer.forward

One process (a chip belongs to one process), no network, nothing left
running. It needs an accelerator: with none it exits 2 at once and
prints no result. Everything worth seeing goes on earlier lines; the LAST
line of stdout is the result,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs ONLY the two paths that exist across chips, each
against the same steps on one chip: the LM step on a ("data","model") =
(2,2) mesh, and Module.fit over four contexts with kvstore="device".

``--rehearse`` is for a sandbox without a chip: tiny sizes, the CPU
allowed (start it with JAX_PLATFORMS=cpu and one virtual device more than
chips, XLA_FLAGS=--xla_force_host_platform_device_count=2 or =5, so that
no "chip" is the host's own device), Pallas kernels in interpret mode. It
finds wrong paths and arguments; its last line names the CPU, so it cannot
pass for a chip run.

The compile cache is where JAX_COMPILATION_CACHE_DIR says, else at
<checkout>/.jax_cache; a second run on the same checkout shows hits.
Numbers printed here are information, not records.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: the sizes of a real run, and of the CPU rehearsal of the same code
FULL = {
    "resnet": dict(image=224, batch=128, scan_k=16, chunks=3),
    "lm": dict(d_model=1024, num_layers=12, num_heads=8, d_ff=4096,
               vocab_size=32000, seq=1024, batch=16, steps=6),
    # pool: 1024 blocks x 16 tokens = 16k tokens of KV (768 MiB in bf16
    # at 12 layers) — sixteen full-length requests of this model
    "serve": dict(draft_layers=2, num_blocks=1024, block_size=16,
                  max_batch=4, prefill_chunk=128, spec_k=4,
                  prompts=(9, 40, 100, 200), new_tokens=12),
    "module": dict(image=224, batch=128, batches=4, epochs=3,
                   first_step_tol=0.05),
}
TINY = {
    "resnet": dict(image=32, batch=32, scan_k=4, chunks=6),
    "lm": dict(d_model=64, num_layers=2, num_heads=2, d_ff=128,
               vocab_size=512, seq=128, batch=4, steps=4),
    "serve": dict(draft_layers=1, num_blocks=64, block_size=8,
                  max_batch=4, prefill_chunk=16, spec_k=2,
                  prompts=(3, 9, 20, 33), new_tokens=6),
    # 8 images a context: batch statistics far from the whole batch's
    "module": dict(image=32, batch=32, batches=4, epochs=3,
                   first_step_tol=0.5),
}

#: a greedy token may differ from the reference's argmax only where the
#: reference itself all but ties: its logit for the engine's token lies
#: within this of its maximum. Logits here are O(1) and bf16 resolves
#: 2^-7 of them; the paged step and forward() sum in different orders.
NEAR_TIE = 0.0625

#: SGD rate of the ResNet-50 phases (momentum 0.9). From a Xavier start
#: 0.05 spikes the loss for a dozen steps before it falls,
#: and 0.01 still wanders in float32; at this rate the rehearsals fall
#: step after step over several seeds, which is what a smoke can check.
STEADY_LR = 0.002


def say(fmt, *args):
    print(fmt % args if args else fmt, flush=True)


def cache_usage():
    """(entries, MiB) of the persistent compile cache's directory."""
    from mxnet_tpu.compile import jit_cache

    path = jit_cache.cache_dir()
    names = [f for f in os.listdir(path) if f.endswith("-cache")]
    return len(names), sum(
        os.path.getsize(os.path.join(path, f)) for f in names) / 2.0 ** 20


@contextlib.contextmanager
def phase(name):
    """Announce a phase, its wall time and what it left in the compile
    cache. A phase that raises ends the run: nothing here catches."""
    from mxnet_tpu.compile import jit_cache

    say("[%s] ...", name)
    t0 = time.perf_counter()
    before = dict(jit_cache.stats())
    yield
    after = jit_cache.stats()
    say("[%s] ok in %.1fs; compile cache: %d hits, %d misses, now %d "
        "entries / %.1f MiB", name, time.perf_counter() - t0,
        after["hits"] - before["hits"], after["misses"] - before["misses"],
        *cache_usage())


def check(cond, fmt, *args):
    if not cond:
        raise AssertionError(fmt % args if args else fmt)


def assert_clean_jit(what):
    """No compile past a boundary's budget and no hot-path D2H over its
    byte budget since the run began (mxjit, MXNET_JIT_VERIFY=record)."""
    from mxnet_tpu.analysis import compile_verify as cv

    check(not cv.unexpected(), "%s: unexpected recompiles: %s", what,
          cv.unexpected())
    check(not cv.d2h_violations(), "%s: D2H over budget: %s", what,
          cv.d2h_violations())


def compiles_by_boundary(prefix):
    from mxnet_tpu.analysis import compile_verify as cv

    return {name: rec["compiles"]
            for name, rec in cv.summary()["boundaries"].items()
            if name.startswith(prefix) and rec["compiles"]}


def device_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


# -- a. op parity --------------------------------------------------------------
def phase_a(mx, host_ctx, chip_ctx):
    sys.path.insert(0, os.path.join(HERE, "tools"))
    import check_tpu_consistency as consistency

    from mxnet_tpu.ops import pallas_kernels as pk

    routed = dict(pk.FALLBACKS)
    failures = consistency.run_cases([{"ctx": host_ctx}, {"ctx": chip_ctx}])
    check(not failures, "op parity failed: %s", failures)
    # SoftmaxOutput's head went through the kernel on both batch sizes
    check(pk.FALLBACKS == routed, "kernels routed to XLA: %s", pk.FALLBACKS)


# -- b. the public trainer -----------------------------------------------------
def learnable_pool(mx, batch, image, ctx, seed, pool=4, classes=10):
    """A fixed pool of device-resident batches,
    in which the label can be read off the image: each of ``classes``
    labels has its own pattern under the noise. A trainer that works
    drives the loss on it down within a few dozen steps."""
    rng = np.random.RandomState(seed)
    patterns = rng.rand(classes, 3, image, image).astype(np.float32)
    out = []
    for _ in range(pool):
        labels = rng.randint(0, classes, (batch,))
        data = 0.5 * patterns[labels] + 0.5 * rng.rand(
            batch, 3, image, image).astype(np.float32)
        # spread the labels over the 1000-way head
        out.append((mx.nd.array(data, ctx=ctx),
                    mx.nd.array((labels * 100).astype(np.float32), ctx=ctx)))
    return out


def pool_iter(mx, pool, batch, image, num_batches):
    class PoolIter(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = batch
            self.provide_data = [("data", (batch, 3, image, image))]
            self.provide_label = [("softmax_label", (batch,))]
            self._i = 0

        def reset(self):
            self._i = 0

        def iter_next(self):
            self._i += 1
            return self._i <= num_batches

        def getdata(self):
            return [pool[(self._i - 1) % len(pool)][0]]

        def getlabel(self):
            return [pool[(self._i - 1) % len(pool)][1]]

        def getpad(self):
            return 0

        def getindex(self):
            return None

    return PoolIter()


def step_loss_metric(mx, losses):
    """An eval metric that keeps every step's cross-entropy."""
    def ce(label, pred):
        p = pred[np.arange(label.shape[0]), label.astype(np.int64)]
        losses.append(float(-np.log(p.astype(np.float64) + 1e-12).mean()))
        return losses[-1]

    return mx.metric.np(ce)


def phase_b(mx, size, ctx, seed):
    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel.fit_trainer import FitTrainer

    image, batch, K = size["image"], size["batch"], size["scan_k"]
    steps = K * size["chunks"]
    # steps per dispatch of the scanned fit path
    os.environ["MXNET_TRAIN_SCAN_K"] = str(K)
    mx.random.seed(seed)  # the initializer draws from these
    np.random.seed(seed)
    pool = learnable_pool(mx, batch, image, ctx, seed)
    losses, placed = [], []

    def at_last_batch(param):
        # the trainer lives inside fit(): look at where its state is
        # while it is alive
        if param.nbatch == steps:
            for obj in gc.get_objects():
                if isinstance(obj, FitTrainer):
                    placed.extend((n, a.devices())
                                  for n, a in obj.params.items())

    t0 = time.perf_counter()
    model = mx.FeedForward(
        get_resnet(num_classes=1000, num_layers=50, stem="s2d", image=image),
        ctx=ctx, num_epoch=1, optimizer="sgd", learning_rate=STEADY_LR,
        momentum=0.9, initializer=mx.initializer.Xavier(),
        compute_dtype="bfloat16")
    model.fit(X=pool_iter(mx, pool, batch, image, steps),
              eval_metric=step_loss_metric(mx, losses),
              batch_end_callback=at_last_batch)
    say("  resnet50 s2d %dx3x%dx%d bf16: %d steps (K=%d) in %.1fs, "
        "compile included", batch, image, image, steps, K,
        time.perf_counter() - t0)
    say("  loss %s", " ".join("%.3f" % v for v in losses))
    check(len(losses) == steps, "expected %d steps, saw %d", steps,
          len(losses))
    check(np.all(np.isfinite(losses)), "non-finite loss")
    n = len(pool)  # one pass over the pool at either end
    first, last = np.mean(losses[:n]), np.mean(losses[-n:])
    check(last < first, "no sign of learning: loss %.4f -> %.4f", first,
          last)
    scanned = compiles_by_boundary("fit_trainer.loop")
    check(scanned == {"fit_trainer.loop|K=%d" % K: 1},
          "the scanned K-step path did not run as one program: %s", scanned)
    dev = ctx.jax_device
    check(placed and all(d == {dev} for _, d in placed),
          "parameters not on %s: %s", dev,
          [(n, d) for n, d in placed if d != {dev}][:4])
    say("  %d parameters on %s; device bytes in use / peak: %s / %s",
        len(placed), dev, *device_bytes(dev))
    assert_clean_jit("phase b")


# -- c. the LM trainer ---------------------------------------------------------
def lm_config(size):
    from mxnet_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=size["vocab_size"], num_layers=size["num_layers"],
        d_model=size["d_model"], num_heads=size["num_heads"],
        d_ff=size["d_ff"], max_seq_len=size["seq"], dtype="bfloat16")


def lm_batch(size, seed):
    rng = np.random.RandomState(seed)
    # seq+1: loss_fn shifts tokens for next-token prediction. One fixed
    # batch, so a working optimizer must drive its loss down.
    return {"tokens": rng.randint(
        0, size["vocab_size"],
        (size["batch"], size["seq"] + 1)).astype(np.int32)}


def flash_parity(jax, cfg, seed):
    """One layer's attention on this device: the kernel's output and
    gradients against the dense reference, within bf16 tolerance."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    B, H, T, D = 2, cfg.num_heads, cfg.max_seq_len, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
                  .astype(cfg.dtype) for kk in keys)
    scale = 1.0 / float(D) ** 0.5

    def run(attn):
        out, pull = jax.vjp(attn, q, k, v)
        return (out,) + pull(g)

    got = jax.jit(lambda: run(
        lambda q, k, v: pk.flash_attention(q, k, v, causal=True)))()
    want = jax.jit(lambda: run(
        lambda q, k, v: pk._attention_reference(q, k, v, True, scale)))()
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = float(np.abs(a - b).max())
        tol = 0.03 * float(np.abs(b).max())
        say("  flash %-3s max|kernel - reference| = %.4g (tolerance %.4g)",
            name, err, tol)
        check(err <= tol, "flash %s disagrees with the reference", name)


def phase_c(jax, size, seed, on_chip):
    import optax

    from mxnet_tpu.analysis import compile_verify as cv
    from mxnet_tpu.models.transformer import init_params, loss_fn
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import make_train_step

    cfg = lm_config(size)
    routed = dict(pk.FALLBACKS)
    took = dict(pk.FLASH_CALLS)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    step, init_state = make_train_step(loss_fn(cfg), optax.adam(1e-4))
    opt_state = init_state(params)
    batch = lm_batch(size, seed)
    key = jax.random.PRNGKey(seed + 1)
    losses = []
    t0 = time.perf_counter()
    for i in range(size["steps"]):
        key, sub = jax.random.split(key)
        params, opt_state, loss = step(params, opt_state, batch, sub)
        losses.append(float(loss))
        if i == 0:
            say("  first step (compile included) %.1fs",
                time.perf_counter() - t0)
    say("  lm d%d L%d H%d ff%d V%d T%d bs%d bf16 adam, %d steps in %.1fs: "
        "loss %s", cfg.d_model, cfg.num_layers, cfg.num_heads, cfg.d_ff,
        cfg.vocab_size, size["seq"], size["batch"], len(losses),
        time.perf_counter() - t0, " ".join("%.4f" % v for v in losses))
    check(np.all(np.isfinite(losses)), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not fall: %s", losses)
    check(pk.FALLBACKS == routed,
          "attention was routed off the kernel: %s", pk.FALLBACKS)
    # what the step's kernels are: bf16 into the MXU (the model's dtype),
    # the mask on fewer tiles than are visited, one call site a layer
    took = {key: n - took.get(key, 0) for key, n in pk.FLASH_CALLS.items()
            if n != took.get(key, 0)}
    say("  flash kernels taken (kernel, operands, tiles visited/masked/"
        "square): %s", took)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        sites = {key: n for key, n in took.items() if key[0] == name}
        check(sum(sites.values()) == cfg.num_layers
              and all(operand == str(np.dtype(cfg.dtype))
                      and (masked < visited < square or square == 1)
                      for _, operand, (visited, masked, square) in sites),
              "%s: not the kernels this model should hold: %s", name, took)
    if on_chip:
        # the same program again, ahead of time (jax hands back the
        # executable it already built): its text says which kernels the
        # step really holds
        text = cv.unwrap(step.jitted).lower(
            params, opt_state, batch, key).compile().as_text()
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        found = {name: sum("/%s/" % name in ln for ln in calls)
                 for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
        say("  tpu_custom_call in the compiled step: %d %s", len(calls),
            found)
        # forward() unrolls its layers: one of each kernel per layer
        check(all(n == cfg.num_layers for n in found.values()),
              "flash kernels missing from the compiled step: %s", found)
    del params, opt_state
    flash_parity(jax, cfg, seed)
    assert_clean_jit("phase c")


# -- d. serving ----------------------------------------------------------------
def make_reference(jax, cfg):
    """transformer.forward, jitted once, reduced on the device to what
    the check reads: at every position the reference's best logit, its
    argmax, and its logit for the token that actually follows."""
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import forward

    def reference(params, toks):
        logits = forward(params, toks, cfg).astype(jnp.float32)
        follows = jnp.roll(toks, -1, axis=1)[..., None]
        return (logits.max(-1), logits.argmax(-1),
                jnp.take_along_axis(logits, follows, axis=-1)[..., 0])

    return jax.jit(reference)


def reference_check(reference, params, cfg, prompts, streams, what):
    """Hold greedy streams to transformer.forward: at every generated
    position the engine's token is the reference's argmax, or sits
    within NEAR_TIE of it (counted and printed). Teacher-forced: one
    forward over prompt + stream, padded to the model's length —
    causal attention never looks right of a position."""
    toks = np.zeros((len(prompts), cfg.max_seq_len), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        toks[i, :len(p) + len(s)] = np.concatenate([p, s])
    best, argmax, chosen = (np.asarray(a) for a in reference(params, toks))
    exact = ties = 0
    worst = 0.0
    for i, (p, s) in enumerate(zip(prompts, streams)):
        for j, tok in enumerate(s):
            at = len(p) + j - 1  # the position that predicts s[j]
            if tok == argmax[i, at]:
                exact += 1
                continue
            gap = float(best[i, at] - chosen[i, at])
            ties += 1
            worst = max(worst, gap)
            check(gap <= NEAR_TIE,
                  "%s: prompt %d token %d: engine chose %d, %.4f below the "
                  "reference's argmax %d", what, i, j, tok, gap,
                  argmax[i, at])
    say("  %s: %d tokens equal the reference argmax, %d differ only at "
        "near-ties (largest gap %.4f, allowed %.4f)", what, exact, ties,
        worst, NEAR_TIE)


def phase_d(jax, lm_size, size, seed):
    from mxnet_tpu.analysis import compile_verify as cv
    from mxnet_tpu.models.transformer import init_params
    from mxnet_tpu.serving import Engine, ServingConfig

    cfg = lm_config(lm_size)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    # a layer-truncated draft: the target's first layers under its own
    # embeddings and final norm
    n_draft = size["draft_layers"]
    draft_params = {"embed": params["embed"],
                    "pos_embed": params["pos_embed"],
                    "layers": params["layers"][:n_draft],
                    "ln_f": params["ln_f"]}
    draft_cfg = dataclasses.replace(cfg, num_layers=n_draft)
    engine = Engine(params, cfg, ServingConfig(
        block_size=size["block_size"], num_blocks=size["num_blocks"],
        max_batch=size["max_batch"], max_active=size["max_batch"],
        prefill_chunk=size["prefill_chunk"],
        # room for one chunk of every request beside a full decode
        # batch: the prompts prefill together and decode together, so a
        # handful of bucket programs is compiled, not the cross-product
        token_budget=size["max_batch"] * (
            size["prefill_chunk"] + 1 + size["spec_k"]),
        spec=True, spec_k=size["spec_k"]), draft_params=draft_params,
        draft_cfg=draft_cfg)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in size["prompts"]]
    new = size["new_tokens"]
    reference = make_reference(jax, cfg)

    engine.set_spec(False)
    t0 = time.perf_counter()
    plain = engine.generate(prompts, max_new_tokens=new)
    say("  generate, speculation off: %d prompts of %s tokens, %d new "
        "each, %.1fs compile included", len(prompts),
        list(size["prompts"]), new, time.perf_counter() - t0)
    check(all(len(s) == new for s in plain), "short streams: %s", plain)
    reference_check(reference, params, cfg, prompts, plain,
                    "speculation off")

    # the streaming surface: submit, then pull tokens as they land
    handles = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.run_until_idle()
    streamed = [list(h.tokens(timeout=60)) for h in handles]
    check(streamed == plain, "submit/stream differs from generate: "
          "%s vs %s", streamed, plain)

    engine.set_spec(True)
    t0 = time.perf_counter()
    spec = engine.generate(prompts, max_new_tokens=new)
    stats = engine.stats()
    say("  generate, speculation on (K=%d, %d-layer draft): %.1fs compile "
        "included; %d turns, accept rate %.3f", size["spec_k"], n_draft,
        time.perf_counter() - t0, stats["spec_turns"],
        stats["spec_accept_rate"] or 0.0)
    check(stats["spec_turns"] > 0, "no speculative turn ran")
    check(all(len(s) == new for s in spec), "short streams: %s", spec)
    reference_check(reference, params, cfg, prompts, spec,
                    "speculation on")
    say("  streams equal with speculation on and off: %d of %d",
        sum(a == b for a, b in zip(plain, spec)), len(prompts))

    # every decode step pulled its token vector and nothing more: the
    # ledger is budgeted at 4 bytes a lane (engine.py, serve.decode_step)
    sites = cv.observed_d2h_sites()
    pulls = sites.get("mxnet_tpu/serving/model.py::ServingModel.step")
    check(pulls and pulls["count"] > 0, "no decode D2H was observed: %s",
          sites)
    say("  D2H: %s", {k.split("::")[-1]: v for k, v in sites.items()})
    say("  programs compiled: %s", compiles_by_boundary("serve."))
    engine.note_idle()
    assert_clean_jit("phase d")


# -- four chips ----------------------------------------------------------------
def phase_mesh_lm(jax, size, seed, devices):
    """The LM step on a ("data","model") = (2,2) mesh with the Megatron
    partition specs, against the same steps on one chip."""
    import optax
    from jax.sharding import NamedSharding

    from mxnet_tpu.models.transformer import (init_params, loss_fn,
                                              param_partition_specs)
    from mxnet_tpu.parallel import ShardedTrainer, create_mesh

    cfg = lm_config(size)
    batch = lm_batch(size, seed)
    host_params = jax.tree.map(
        np.asarray, init_params(cfg, jax.random.PRNGKey(seed)))

    def run(mesh, param_spec):
        # fresh copies: the step donates its params
        trainer = ShardedTrainer(
            loss_fn(cfg, mesh=mesh),
            jax.tree.map(jax.numpy.asarray, host_params),
            optax.adam(1e-4), mesh=mesh, param_spec=param_spec, seed=seed)
        losses = [float(trainer.step(batch)) for _ in range(size["steps"])]
        return trainer, losses

    _, one = run(None, None)
    say("  one chip : loss %s", " ".join("%.4f" % v for v in one))
    gc.collect()
    mesh = create_mesh((2, 2), ("data", "model"), devices=devices)
    specs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         param_partition_specs(cfg))
    trainer, four = run(mesh, specs)
    say("  2x2 mesh : loss %s", " ".join("%.4f" % v for v in four))
    check(np.all(np.isfinite(four)), "non-finite loss on the mesh")
    check(four[-1] < four[0], "loss did not fall on the mesh: %s", four)
    # bf16 matmuls reduce in another order across the model axis
    worst = max(abs(a - b) for a, b in zip(one, four))
    check(worst <= 0.02 * abs(one[0]),
          "mesh and one-chip losses differ by %.4f", worst)
    check(all(leaf.sharding.device_set == set(devices)
              for leaf in jax.tree.leaves(trainer.params)),
          "parameters do not span the four devices")
    sharded = trainer.params["layers"][0]["wqkv"]
    check(sharded.addressable_shards[0].data.shape[1] * 2
          == sharded.shape[1], "wqkv is not split over the model axis")
    for d in devices:
        used, _ = device_bytes(d)
        say("  %s bytes in use: %s", d, used)
        check(used is None or used > sharded.nbytes // 2,
              "%s holds next to nothing", d)
    assert_clean_jit("mesh lm")


def phase_module_dp(mx, size, seed, ctx_of, n_dev):
    """Module.fit over four contexts with kvstore="device", against the
    same batches on one context."""
    from mxnet_tpu.models import get_resnet

    image, batch, nb = size["image"], size["batch"], size["batches"]
    sym = get_resnet(num_classes=1000, num_layers=50, stem="s2d",
                     image=image)

    def run(ctxs):
        mx.random.seed(seed)  # the same initial weights in both runs
        np.random.seed(seed)
        pool = learnable_pool(mx, batch, image, mx.cpu(0), seed, pool=nb)
        losses = []
        mod = mx.module.Module(sym, context=ctxs)
        mod.fit(pool_iter(mx, pool, batch, image, nb),
                eval_metric=step_loss_metric(mx, losses), kvstore="device",
                optimizer="sgd",
                optimizer_params=(("learning_rate", STEADY_LR),
                                  ("momentum", 0.9)),
                initializer=mx.initializer.Xavier(),
                num_epoch=size["epochs"])
        # the metric sees each context's slice of a batch on its own
        return mod, np.reshape(losses, (-1, len(ctxs))).mean(axis=1)

    _, one = run([ctx_of(0)])
    say("  1 context : loss %s", " ".join("%.3f" % v for v in one))
    gc.collect()
    ctxs = [ctx_of(i) for i in range(n_dev)]
    mod, four = run(ctxs)
    say("  %d contexts: loss %s", n_dev, " ".join("%.3f" % v for v in four))
    check(len(four) == len(one) == size["epochs"] * nb
          and np.all(np.isfinite(four)),
          "bad losses over %d contexts: %s", n_dev, four)
    check(np.mean(four[-nb:]) < np.mean(four[:nb]),
          "no sign of learning over %d contexts: %s", n_dev, four)
    # Before the first update the two runs hold the same weights and see
    # the same batch; each context normalises its own quarter of it
    # (BatchNorm statistics are per device, as in the reference), so
    # they agree closely but not to the bit. Later steps part ways as
    # training amplifies that: they are held to the sign of learning.
    first = abs(four[0] - one[0]) / abs(one[0])
    check(first <= size["first_step_tol"],
          "first-step loss over %d contexts differs from one context's by "
          "%.1f%%", n_dev, 100 * first)
    for arrays in mod._param_arrays():  # one list of per-context copies
        placed = [a._data.devices() for a in arrays]
        check(placed == [{c.jax_device} for c in ctxs],
              "a parameter is not on every context: %s", placed)
    for c in ctxs:
        used, _ = device_bytes(c.jax_device)
        say("  %s bytes in use: %s", c, used)
        check(used is None or used > 50e6, "%s holds next to nothing", c)
    assert_clean_jit("module dp")


# -- entry ---------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, CPU allowed: a rehearsal, not a run")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print("chip_smoke: no accelerator: jax.devices() = %s" % devices,
              file=sys.stderr)
        return 2
    if len(devices) != args.chips and not (
            args.rehearse and len(devices) > args.chips):
        print("chip_smoke: --chips %d but jax found %d device(s): %s"
              % (args.chips, len(devices), devices), file=sys.stderr)
        return 2
    # rehearsing, the "chips" are the LAST CPU devices: given one device
    # more than chips, none of them is the host's cpu(0), as on a real
    # machine — what leaks onto the default device then shows
    first = len(devices) - args.chips
    devices = devices[first:]

    # count every compile against its boundary's budget and every hot
    # D2H against its ledger; the phases assert both stayed clean
    os.environ.setdefault("MXNET_JIT_VERIFY", "record")
    if not on_chip:
        os.environ.setdefault("MXNET_PALLAS", "1")  # interpret mode
    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.compile import jit_cache
    from mxnet_tpu.ops import pallas_kernels as pk

    cache = jit_cache.enable()
    say("device: %s x%d (%s); jax %s", dev.device_kind, len(devices),
        dev.platform, jax.__version__)
    # jax evicts least-recently-used entries past the cap: a run that
    # writes more than the cap can never come back warm
    say("compile cache: %s, %d entries / %.1f MiB, cap %s bytes", cache,
        *cache_usage(), jax.config.jax_compilation_cache_max_size)
    say("native components: %s", {
        n: _native.load(n) is not None
        for n in ("engine", "recordio", "imagedec", "c_api")})
    check(on_chip != pk._interpret(), "interpret mode on a TPU")

    size = TINY if args.rehearse else FULL

    def ctx_of(i):
        return mx.tpu(i) if on_chip else mx.cpu(first + i)

    t0 = time.perf_counter()
    if args.chips == 4:
        with phase("4.i lm step on a (data, model) = (2, 2) mesh"):
            phase_mesh_lm(jax, size["lm"], args.seed, devices)
        gc.collect()
        with phase("4.ii Module.fit over 4 contexts, kvstore=device"):
            phase_module_dp(mx, size["module"], args.seed, ctx_of, 4)
    else:
        chip = ctx_of(0)
        with phase("a. op parity %s vs %s" % (mx.cpu(0), chip)):
            phase_a(mx, mx.cpu(0), chip)
        with phase("b. FeedForward.fit resnet50"):
            phase_b(mx, size["resnet"], chip, args.seed)
        gc.collect()
        with phase("c. lm train step"):
            phase_c(jax, size["lm"], args.seed, on_chip)
        gc.collect()
        with phase("d. serving engine"):
            phase_d(jax, size["lm"], size["serve"], args.seed)
    say("kernels routed to XLA: %s", pk.FALLBACKS or "none")
    say("compile cache: %s, %d entries / %.1f MiB; all phases %.1fs",
        jit_cache.stats(), *cache_usage(), time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
