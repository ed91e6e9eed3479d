#!/usr/bin/env python
"""Feasibility probe: fused matmul+stats Pallas kernel vs XLA.

The ResNet profile (docs/perf_analysis.md) charges ~BN-stats one extra
HBM read of each conv output. A conv whose epilogue accumulates
sum/sum-of-squares per channel IN VMEM removes that read. 1x1 convs are
matmuls; this probe measures, on real ResNet-50 shapes, whether a
Pallas matmul-with-stats-epilogue can beat XLA's (matmul ; stats)
sequence — the go/no-go for wiring it into the executor.
"""
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    interpret = jax.default_backend() != "tpu"

    def fence(x):
        return float(jnp.sum(x.ravel()[0:1]))

    def xla_ref(x, w):
        y = jnp.dot(x, w)  # bf16 in/out, f32 MXU accumulation
        y32 = y.astype(jnp.float32)
        return y, jnp.sum(y32, 0), jnp.sum(jnp.square(y32), 0)

    def make_pallas(M, K, N, bm):
        def kernel(x_ref, w_ref, y_ref, s_ref, s2_ref):
            i = pl.program_id(0)
            x = x_ref[...]
            w = w_ref[...]
            acc = jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            y_ref[...] = acc.astype(y_ref.dtype)

            @pl.when(i == 0)
            def _init():
                s_ref[...] = jnp.zeros_like(s_ref)
                s2_ref[...] = jnp.zeros_like(s2_ref)

            s_ref[...] += jnp.sum(acc, 0, keepdims=True)
            s2_ref[...] += jnp.sum(jnp.square(acc), 0, keepdims=True)

        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, N), jnp.float32),
                jax.ShapeDtypeStruct((1, N), jnp.float32),
            ),
            grid=(M // bm,),
            in_specs=[
                pl.BlockSpec((bm, K), lambda i: (i, 0)),
                pl.BlockSpec((K, N), lambda i: (0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((bm, N), lambda i: (i, 0)),
                pl.BlockSpec((1, N), lambda i: (0, 0)),
                pl.BlockSpec((1, N), lambda i: (0, 0)),
            ),
            interpret=interpret,
        )

    shapes = [
        # (M, K, N)  -- ResNet-50 1x1 conv bodies at bs=128 as matmuls
        (128 * 56 * 56, 64, 256),
        (128 * 56 * 56, 256, 64),
        (128 * 28 * 28, 512, 128),
        (128 * 14 * 14, 1024, 256),
    ]
    iters = int(os.environ.get("PROBE_ITERS", "30"))
    for M, K, N in shapes:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
        w = jnp.asarray(rng.randn(K, N) * 0.05, jnp.bfloat16)

        ref = jax.jit(xla_ref)
        bm = 512
        pk = make_pallas(M, K, N, bm)
        pkj = jax.jit(lambda x, w: pk(x, w))
        mm = jax.jit(lambda x, w: jnp.dot(x, w))

        # correctness
        y0, s0, q0 = ref(x, w)
        y1, s1, q1 = pkj(x, w)
        np.testing.assert_allclose(np.asarray(s1).ravel(),
                                   np.asarray(s0), rtol=2e-2, atol=2e2)
        np.testing.assert_allclose(np.asarray(q1).ravel(),
                                   np.asarray(q0), rtol=2e-2,
                                   atol=np.abs(np.asarray(q0)).max() * 2e-2)
        np.testing.assert_allclose(np.asarray(y1, np.float32),
                                   np.asarray(y0, np.float32),
                                   rtol=2e-2, atol=1e-1)

        def timeit(f, needs_stats):
            # MARGINAL cost via the scan-length slope (dispatch + fence
            # carry a fixed overhead that a single timing cannot tell
            # from the kernel's own time). The
            # scalar feedback (s[0]*1e-20 into x) defeats CSE/hoisting;
            # its elementwise add costs one x-pass in BOTH variants.
            def body(xc, _):
                out = f(xc, w)
                if needs_stats:
                    y, s, _q = out
                    s0 = s.ravel()[0]
                else:
                    y = out
                    s0 = y.ravel()[0].astype(jnp.float32)
                xc = xc + (s0 * 1e-20).astype(xc.dtype)
                return xc, y.ravel()[0]

            def wall(length, reps=3):
                loop = jax.jit(functools.partial(
                    lambda x0, n: jax.lax.scan(body, x0, None, length=n),
                    n=length))
                loop(x)
                fence(loop(x)[1])
                best = 1e9
                for _ in range(reps):
                    t0 = time.perf_counter()
                    fence(loop(x)[1])
                    best = min(best, time.perf_counter() - t0)
                return best

            lo, hi = 4, 4 + iters
            return (wall(hi) - wall(lo)) / (hi - lo) * 1e3

        t_ref = timeit(xla_ref, True)
        t_pal = timeit(lambda a, b: pk(a, b), True)
        t_mm = timeit(lambda a, b: jnp.dot(a, b), False)
        print("M=%8d K=%4d N=%4d  xla(mm+stats)=%6.3fms  pallas=%6.3fms  "
              "mm-only=%6.3fms  speedup=%.2fx" %
              (M, K, N, t_ref, t_pal, t_mm, t_ref / t_pal))


if __name__ == "__main__":
    main()
