#!/usr/bin/env python
"""Layout probe: measure ResNet-50-shaped train-step throughput under
the conv layout strategies on the real chip, to decide the framework's
internal layout policy (VERDICT r1 weak #2: NCHW model at 14% MFU).

  A. logical NCHW end-to-end (what the Symbol graph runs by default)
  B. logical NHWC end-to-end (TPU-preferred channels-last)
  C. NCHW graph but each conv runs NHWC internally via a transpose
     sandwich (what a per-op layout shim would produce)
  D. the PRODUCTION path: the real ResNet-50 Symbol graph through the
     compile layer's layout pass (MXNET_COMPILE_OPT, compile/layout.py)
     vs the same graph unrewritten — D is what this probe's A/B/C
     experiment grew into; keep it here as the regression check that
     the pass's hoisted-transpose rewrite still tracks hand-rolled
     NHWC (B), not the naive sandwich (C).

A/B/C are hand-rolled conv/BN/relu ResNet-50 fwd+bwd+SGD in pure jax —
no Symbol machinery — so the difference isolates layout, not the
framework. Prints img/s for each.
"""
from __future__ import annotations

import time
import sys

import numpy as np
import jax
import jax.numpy as jnp


UNITS = [3, 4, 6, 3]
FILTERS = [256, 512, 1024, 2048]


def init_params(rng, layout):
    params = {}
    idx = [0]

    def conv_w(cin, cout, k):
        i = idx[0]
        idx[0] += 1
        w = rng.normal(0, np.sqrt(2.0 / (k * k * cin)), (cout, cin, k, k))
        if layout == "NHWC":
            w = w.transpose(2, 3, 1, 0)  # OIHW -> HWIO
        params["w%d" % i] = jnp.asarray(w, jnp.float32)
        params["g%d" % i] = jnp.ones((cout,), jnp.float32)
        params["b%d" % i] = jnp.zeros((cout,), jnp.float32)
        return i

    # mirror the symbol_resnet topology
    conv_w(3, 64, 7)
    cin = 64
    for stage, (n, f) in enumerate(zip(UNITS, FILTERS)):
        for u in range(n):
            conv_w(cin if u == 0 else f, f // 4, 1)
            conv_w(f // 4, f // 4, 3)
            conv_w(f // 4, f, 1)
            if u == 0:
                conv_w(cin, f, 1)
            cin = f
    params["fc_w"] = jnp.asarray(rng.normal(0, 0.01, (1000, 2048)), jnp.float32)
    params["fc_b"] = jnp.zeros((1000,), jnp.float32)
    return params


def make_fwd(layout, sandwich=False):
    if layout == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
        caxis = 3
    else:
        dn = ("NCHW", "OIHW", "NCHW")
        caxis = 1

    def conv(x, w, stride, pad):
        if sandwich and layout == "NCHW":
            xt = jnp.transpose(x, (0, 2, 3, 1))
            wt = jnp.transpose(w, (2, 3, 1, 0))
            o = jax.lax.conv_general_dilated(
                xt, wt, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return jnp.transpose(o, (0, 3, 1, 2))
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=dn)

    def bn_relu(x, g, b, relu=True):
        axes = tuple(i for i in range(4) if i != caxis)
        xf = x.astype(jnp.float32)
        m = xf.mean(axes, keepdims=True)
        v = xf.var(axes, keepdims=True)
        shape = [1] * 4
        shape[caxis] = -1
        o = (xf - m) * jax.lax.rsqrt(v + 2e-5)
        o = o * g.reshape(shape) + b.reshape(shape)
        o = o.astype(x.dtype)
        return jnp.maximum(o, 0) if relu else o

    def fwd(params, x, labels):
        i = [0]

        def cbr(x, stride, pad, relu=True):
            j = i[0]
            i[0] += 1
            o = conv(x, params["w%d" % j].astype(x.dtype), stride, pad)
            return bn_relu(o, params["g%d" % j], params["b%d" % j], relu)

        x = cbr(x, 2, 3)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1, 1, 3, 3) if caxis == 1 else (1, 3, 3, 1),
            (1, 1, 2, 2) if caxis == 1 else (1, 2, 2, 1),
            [(0, 0), (0, 0), (1, 1), (1, 1)] if caxis == 1
            else [(0, 0), (1, 1), (1, 1), (0, 0)])
        for stage, (n, f) in enumerate(zip(UNITS, FILTERS)):
            for u in range(n):
                stride = 2 if (stage > 0 and u == 0) else 1
                y = cbr(x, stride, 0)
                y = cbr(y, 1, 1)
                y = cbr(y, 1, 0, relu=False)
                if u == 0:
                    sc = cbr(x, stride, 0, relu=False)
                else:
                    sc = x
                x = jnp.maximum(y + sc, 0)
        x = x.mean(axis=(2, 3) if caxis == 1 else (1, 2))
        logits = jnp.dot(x, params["fc_w"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32) + params["fc_b"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

    return fwd


def bench_variant(name, layout, sandwich, batch=128, steps=10, warmup=2):
    rng = np.random.RandomState(0)
    params = init_params(rng, layout)
    shape = (batch, 3, 224, 224) if layout == "NCHW" else (batch, 224, 224, 3)
    x = jnp.asarray(rng.rand(*shape), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, batch), jnp.int32)
    fwd = make_fwd(layout, sandwich)

    @jax.jit
    def step(params, x, labels):
        loss, grads = jax.value_and_grad(fwd)(params, x, labels)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, loss

    for _ in range(warmup):
        params, loss = step(params, x, labels)
    jax.block_until_ready(loss)
    float(loss)  # hard D2H fence
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, x, labels)
    float(loss)
    dt = time.perf_counter() - t0
    print("%-28s %8.1f img/s  (loss %.3f)" % (name, batch * steps / dt, float(loss)))
    sys.stdout.flush()


def bench_symbol_variant(name, compile_on, batch=128, steps=10, warmup=2,
                         image=224):
    """Variant D: the framework's own ResNet-50 Symbol graph through
    make_symbol_train_step, with the compile layer's layout pass on or
    off — the production path the A/B/C experiment was promoted into."""
    import os

    import optax

    import mxnet_tpu as mx
    import mxnet_tpu.compile as mxc
    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel.symbol_trainer import make_symbol_train_step

    saved = {k: os.environ.get(k)
             for k in ("MXNET_COMPILE_OPT", "MXNET_COMPILE_PASSES")}
    if compile_on:
        os.environ["MXNET_COMPILE_OPT"] = "1"
        os.environ.setdefault("MXNET_COMPILE_PASSES", "layout,fuse")
    else:
        os.environ.pop("MXNET_COMPILE_OPT", None)
    mxc.reload()
    try:
        sym = get_resnet(num_classes=1000, num_layers=50, stem="conv7",
                         image=image)
        step, state = make_symbol_train_step(
            sym,
            input_shapes={"data": (batch, 3, image, image),
                          "softmax_label": (batch,)},
            optimizer=optax.sgd(0.05, momentum=0.9),
            compute_dtype="bfloat16", ctx=mx.tpu(0),
        )
        rng = np.random.RandomState(0)
        batch_vals = {
            "data": rng.rand(batch, 3, image, image)
            .astype(np.float32).astype(jnp.bfloat16),
            "softmax_label": rng.randint(0, 1000, (batch,))
            .astype(np.float32),
        }
        key = jax.random.PRNGKey(0)
        for _ in range(warmup):
            key, sub = jax.random.split(key)
            state, _outs = step(state, batch_vals, sub)
        leaf = jax.tree_util.tree_leaves(state["params"])[0]
        float(np.asarray(leaf).ravel()[0])  # hard D2H fence
        t0 = time.perf_counter()
        for _ in range(steps):
            key, sub = jax.random.split(key)
            state, _outs = step(state, batch_vals, sub)
        float(np.asarray(jax.tree_util.tree_leaves(state["params"])[0]
                         ).ravel()[0])
        dt = time.perf_counter() - t0
        print("%-28s %8.1f img/s  (passes: %s)"
              % (name, batch * steps / dt,
                 {k: v for k, v in mxc.last_report().items() if k != "secs"}
                 if compile_on else "off"))
        sys.stdout.flush()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        mxc.reload()


if __name__ == "__main__":
    print("devices:", jax.devices())
    bench_variant("A: logical NCHW", "NCHW", False)
    bench_variant("B: logical NHWC", "NHWC", False)
    bench_variant("C: NCHW + sandwich", "NCHW", True)
    bench_symbol_variant("D0: Symbol graph, pass off", False)
    bench_symbol_variant("D1: Symbol graph, layout pass", True)
