#!/usr/bin/env python3
"""Device time of the three flash-attention kernels alone, on the chip.

    python tools/flash_probe.py [--root DIR] [--shapes 8x16x1024x64,...]
        [--blocks default,256x256,...] [--dtype bfloat16] [--iters 10]
        [--tag NAME] [--out chiprun_out/flash_probe.jsonl]

For every shape ``BxHxTxD`` and every ``block_q x block_k`` (``default`` =
what ``_select_blocks`` picks) it jits ``jax.grad`` of causal
``flash_attention``, runs it ``--iters`` times under the profiler and sums
the device time of ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``
from the trace (``benchmark/trace_reduce.py``, as ``flash_roofline`` does
for a cell): milliseconds a call, and at the cell's own shape
(``8x16x1024x64``) times 24 layers the milliseconds of a gpt2-medium step.
``--root`` takes ``mxnet_tpu`` from another checkout (a parent commit
unpacked under ``.parent/``), so both sides of a comparison are read the
same way in one chip call. No chip: exit 2, nothing printed.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--shapes", default="8x16x1024x64,1x16x8192x64")
    ap.add_argument("--blocks", default="default")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "flash_probe.jsonl"))
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import jax
    import jax.numpy as jnp

    import trace_reduce
    from mxnet_tpu.ops import pallas_kernels as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return 2
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    for shape in args.shapes.split(","):
        b, h, t, d = (int(x) for x in shape.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.float32)
                   .astype(args.dtype) for kk in keys)
        for blocks in args.blocks.split(","):
            bq, bk = (None, None) if blocks == "default" else (
                int(x) for x in blocks.split("x"))

            def loss(q, k, v):
                return pk.flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk
                ).astype(jnp.float32).sum()

            row = {"tag": args.tag, "shape": shape, "dtype": args.dtype,
                   "blocks": "x".join(str(x) for x in pk._select_blocks(
                       t, t, bq, bk)[:2]),
                   "device_kind": dev.device_kind}
            try:
                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                jax.block_until_ready(grad(q, k, v))
                trace_dir = tempfile.mkdtemp(prefix="flash_probe_")
                jax.profiler.start_trace(trace_dir)
                for _ in range(args.iters):
                    out = grad(q, k, v)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ops = trace_reduce.reduce_dir(trace_dir)["ops"]
                shutil.rmtree(trace_dir, ignore_errors=True)
                for kernel in KERNELS:
                    row[kernel + "_ms"] = 1e3 / args.iters * sum(
                        sec for name, sec in ops.items()
                        if kernel in name)
                row["sum_ms"] = sum(row[kernel + "_ms"] for kernel in KERNELS)
                row["fallbacks"] = {"%s.%s" % key: n
                                    for key, n in pk.FALLBACKS.items()}
            except Exception as e:  # a refused block must not end the sweep
                row["error"] = "%s: %s" % (type(e).__name__, str(e)[:300])
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    return 0 if all("error" not in row for row in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
