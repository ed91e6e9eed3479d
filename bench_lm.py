#!/usr/bin/env python
"""Benchmark: decoder-only transformer LM training on one TPU chip.

The MXU-bound companion to bench.py's HBM-bound ResNet-50 (VERDICT r3
item 1: the TPU-native claim needs a measured MFU number). Trains
mxnet_tpu/models/transformer.py — Pallas flash attention on the real
chip — with the same methodology as bench.py: K steps fused into one
``lax.scan`` dispatch, donated state, device-resident token batches,
and a ``block_until_ready`` fence around the timed window.

Prints ONE JSON line: {"metric", "value" (tokens/s), "unit",
"vs_baseline", "mfu", "tflops"}.

Baseline: the 2016 reference has no transformer and publishes no LM
throughput, so there is no reference number to beat; ``vs_baseline``
is measured MFU / 0.40 — the MXU-utilisation target set for this
flagship (≥1.0 meets it). MFU = model FLOPs / wall time / 197 TFLOP/s
bf16 peak (v5e), with model FLOPs counted explicitly below.

FLOP accounting (per token, matmuls only — the standard MFU convention):
  linear:   3 x (L·24·d² + 2·d·V)   (qkv 6d², attn out 2d², mlp 16d²,
            logits 2dV; backward doubles each matmul)
  attention: L·12·T·d — fwd 4Td (scores + pv), bwd 8Td — the
            Megatron/PaLM "model FLOPs" convention: no credit for the
            kernel backward's score recomputes and no causal discount.
            (r4 counted the recomputes too, 18Td; once r5's block_k
            tuning let the causal block-skip bite, that convention
            reported >100% "MFU" at T=8192 — recompute credit is
            throughput-inflating and is gone. Causal skipping means
            the kernel EXECUTES ~half the counted attention FLOPs, so
            long-context MFU here is conservative, as the convention
            intends.)

Env knobs: BENCH_LM_{DMODEL,LAYERS,HEADS,DFF,VOCAB,SEQ,BATCH,SCAN,
STEPS,WARMUP}, BENCH_LM_ATTN=flash|dense (dense forces the plain XLA
attention for A/B), BENCH_LM_OPT=sgd|adam.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

MFU_TARGET = 0.40


def _peak_bf16():
    # the attached chip's peak from the library's table (prof.PEAKS,
    # keyed by device_kind), so this leg, /profilez and
    # tools/perf_gate.py share one MFU denominator. Imported lazily:
    # bench.py's cold-start leg must not inherit a module-level
    # mxnet_tpu import from this module.
    from mxnet_tpu.telemetry import prof

    peak = prof.peak_flops()
    if peak is None:
        raise SystemExit("bench_lm: no peak for this device in prof.PEAKS "
                         "(set MXNET_PROF_PEAK_FLOPS to name one)")
    return peak


def model_flops_per_token(cfg, seq_len):
    d, L, V, T = cfg.d_model, cfg.num_layers, cfg.vocab_size, seq_len
    linear = 3 * (L * 24 * d * d + 2 * d * V)
    attention = L * 12 * T * d  # see module docstring
    return linear + attention


def main():
    d_model = int(os.environ.get("BENCH_LM_DMODEL", "1024"))
    layers = int(os.environ.get("BENCH_LM_LAYERS", "12"))
    heads = int(os.environ.get("BENCH_LM_HEADS", "8"))  # head_dim 128: lane-aligned
    d_ff = int(os.environ.get("BENCH_LM_DFF", "4096"))
    vocab = int(os.environ.get("BENCH_LM_VOCAB", "32000"))
    seq = int(os.environ.get("BENCH_LM_SEQ", "1024"))
    batch = int(os.environ.get("BENCH_LM_BATCH", "16"))
    scan_k = int(os.environ.get("BENCH_LM_SCAN", "8"))
    steps = int(os.environ.get("BENCH_LM_STEPS", "32"))
    warmup = int(os.environ.get("BENCH_LM_WARMUP", "1"))
    # auto = production gate (dense below MXNET_FLASH_MIN_T, flash above);
    # flash/dense force one path for A/B probes
    attn = os.environ.get("BENCH_LM_ATTN", "auto")
    opt_name = os.environ.get("BENCH_LM_OPT", "adam")

    if attn == "dense":
        os.environ["MXNET_PALLAS"] = "0"  # flash_attention falls back to XLA
    elif attn == "flash":
        os.environ.setdefault("MXNET_FLASH_MIN_T", "0")

    import jax
    import optax
    from jax import lax

    from mxnet_tpu.compile import jit_cache
    from mxnet_tpu.models.transformer import (TransformerConfig, init_params,
                                              loss_fn)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_lm: no TPU attached (jax.devices() = %s)"
                         % jax.devices())
    jit_cache.enable()
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, d_model=d_model,
        num_heads=heads, d_ff=d_ff, max_seq_len=seq, dtype="bfloat16",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    optimizer = (optax.adam(1e-4) if opt_name == "adam"
                 else optax.sgd(0.01, momentum=0.9))
    opt_state = optimizer.init(params)
    loss = loss_fn(cfg)

    def body(carry, xs):
        params, opt_state = carry
        tokens, rng = xs
        l, grads = jax.value_and_grad(loss)(params, {"tokens": tokens}, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), l

    def loop(params, opt_state, tokens, rngs):
        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), (tokens, rngs))
        return params, opt_state, losses

    loop = jax.jit(loop, donate_argnums=(0, 1))

    rng = np.random.RandomState(0)
    # seq+1: loss_fn shifts tokens for next-token prediction
    tokens = jax.device_put(rng.randint(
        0, vocab, (scan_k, batch, seq + 1)).astype(np.int32))
    key = jax.random.PRNGKey(1)

    fence = jax.block_until_ready

    n_disp = max(1, steps // scan_k)
    for _ in range(warmup):
        key, sub = jax.random.split(key)
        params, opt_state, losses = loop(
            params, opt_state, tokens, jax.random.split(sub, scan_k))
    fence(params)

    t0 = time.perf_counter()
    for _ in range(n_disp):
        key, sub = jax.random.split(key)
        params, opt_state, losses = loop(
            params, opt_state, tokens, jax.random.split(sub, scan_k))
    fence(params)
    dt = time.perf_counter() - t0

    steps_run = n_disp * scan_k
    # loss_fn trains on seq tokens per row (tokens[:, :-1] -> targets)
    tokens_per_step = batch * seq
    tok_s = tokens_per_step * steps_run / dt
    flops = model_flops_per_token(cfg, seq) * tok_s
    mfu = flops / _peak_bf16()
    print(json.dumps({
        "metric": "transformer_lm_train_throughput",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / MFU_TARGET, 3),
        "mfu": round(mfu, 4),
        "tflops": round(flops / 1e12, 2),
        "attn": attn,
        "config": {"d_model": d_model, "layers": layers, "heads": heads,
                   "d_ff": d_ff, "vocab": vocab, "seq": seq,
                   "batch": batch, "final_loss": round(float(losses[-1]), 4)},
    }))


if __name__ == "__main__":
    main()
