#!/usr/bin/env python
"""TPU vs CPU numeric consistency sweep.

The TPU-era instance of the reference's GPU↔CPU consistency suite
(ref: tests/python/gpu/test_operator_gpu.py via check_consistency,
python/mxnet/test_utils.py:615 — SURVEY §4.4 calls it the template for
TPU-vs-CPU parity). Binds the same symbols under cpu(0) and tpu(0) and
asserts outputs and gradients agree within per-dtype tolerance.

Run on a machine with a TPU attached:  python tools/check_tpu_consistency.py
Exits nonzero on any mismatch, and with no TPU attached (``mx.tpu(0)``
raises); prints one line per case. ``chip_smoke.py`` runs the same case
list as its first phase.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import symbol as sym  # noqa: E402
from mxnet_tpu.test_utils import check_consistency  # noqa: E402


def cases():
    data = sym.Variable("data")
    yield ("Convolution", sym.Convolution(
        data=data, kernel=(3, 3), num_filter=8, pad=(1, 1), name="op"),
        {"data": (2, 3, 16, 16)})
    yield ("FullyConnected", sym.FullyConnected(
        data=data, num_hidden=16, name="op"), {"data": (4, 32)})
    yield ("Pooling", sym.Pooling(
        data=data, kernel=(2, 2), stride=(2, 2), pool_type="max", name="op"),
        {"data": (2, 3, 8, 8)})
    yield ("BatchNorm", sym.BatchNorm(data=data, name="op"),
           {"data": (4, 3, 8, 8)})
    yield ("SoftmaxActivation", sym.SoftmaxActivation(data=data, name="op"),
           {"data": (4, 10)})
    yield ("Deconvolution", sym.Deconvolution(
        data=data, kernel=(4, 4), stride=(2, 2), pad=(1, 1), num_filter=4,
        name="op"), {"data": (2, 3, 8, 8)})
    yield ("act-chain", sym.Activation(sym.exp(data * 0.1), act_type="tanh"), {"data": (8, 8)})
    # every Symbol model's head: rows a multiple of 8, and the reference
    # examples' batch of 100, which is not (the fused_softmax kernel)
    for batch in (128, 100):
        yield ("SoftmaxOutput-%dx1000" % batch,
               sym.SoftmaxOutput(data=data, name="op"),
               {"data": (batch, 1000), "op_label": (batch,)})


def run_cases(ctx_list):
    """check_consistency over every case; returns the failures as
    ``[(name, exception), ...]`` and prints one line per case."""
    failures = []
    for name, s, shapes in cases():
        try:
            check_consistency(
                s, [dict(c, **shapes) for c in ctx_list], grad_req="write")
            print("%-22s OK" % name)
        except Exception as e:  # report every case, fail at the end
            failures.append((name, e))
            print("%-22s FAIL: %s" % (name, e))
    return failures


def main():
    mx.tpu(0).jax_device  # raises at once with no chip: nothing to compare
    return 1 if run_cases([{"ctx": mx.cpu(0)}, {"ctx": mx.tpu(0)}]) else 0


if __name__ == "__main__":
    sys.exit(main())
