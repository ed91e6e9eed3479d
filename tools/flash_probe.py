#!/usr/bin/env python3
"""Device time of the flash-attention kernels alone, on the chip.

    python tools/flash_probe.py [--root DIR] [--shapes 8x16x1024x64,...]
        [--blocks default,256x256,...] [--dtype bfloat16] [--iters 10]
        [--tag NAME] [--out chiprun_out/flash_probe.jsonl]

For every shape ``BxHxTxD[xDV][/HKV][@W]`` (values ``DV`` wide, ``HKV``
key/value heads, a window of ``W``) and every ``block_q x block_k``
(``default`` = what ``_flash_plan`` picks; a named pair may still be
given a VMEM limit of its own) it jits ``jax.grad`` of causal
``flash_attention``, runs it ``--iters`` times under the profiler and sums
the device time of each kernel from the trace
(``benchmark/trace_reduce.py``, as ``flash_roofline`` does for a cell):
milliseconds a call under the kernel's name, a window's kernels under
their names without ``win_``, and at the cell's own shape
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
#: every name ``_flash_call`` gives, ``flash_win_`` read as ``flash_``; an
#: operation counts under the longest that it contains
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dqkv")


def kernel_of(op):
    """The kernel an operation of the trace is, or None."""
    op = op.replace("flash_win_", "flash_")
    return max((k for k in KERNELS if k in op), key=len, default=None)


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
        shape_, _, window = shape.partition("@")
        shape_, _, hkv = shape_.partition("/")
        b, h, t, d, *dv = (int(x) for x in shape_.split("x"))
        dv, hkv = (dv or [d])[0], int(hkv or h)
        window = int(window) if window else None
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, dims, jnp.float32).astype(args.dtype)
                   for kk, dims in zip(keys, ((b, h, t, d), (b, hkv, t, d),
                                              (b, hkv, t, dv))))
        for blocks in args.blocks.split(","):
            bq, bk = (None, None) if blocks == "default" else (
                int(x) for x in blocks.split("x"))

            def loss(q, k, v):
                return pk.flash_attention(
                    q, k, v, causal=True, window=window, block_q=bq,
                    block_k=bk).astype(jnp.float32).sum()

            plan = pk._flash_plan(t, t, d, dv, bq, bk,
                                  jnp.dtype(args.dtype).itemsize, h // hkv)
            row = {"tag": args.tag, "shape": shape, "dtype": args.dtype,
                   "blocks": "%dx%d" % plan[:2], "vmem_limit": plan[3],
                   # one backward kernel (a tree from before it: the pair)
                   "fused": len(plan) > 4 and plan[4],
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
                for name, sec in ops.items():
                    kernel = kernel_of(name)
                    if kernel is not None:
                        row[kernel + "_ms"] = row.get(
                            kernel + "_ms", 0.0) + 1e3 / args.iters * sec
                row["sum_ms"] = sum(row.get(kernel + "_ms", 0.0)
                                    for kernel in KERNELS)
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
