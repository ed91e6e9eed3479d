"""Runtime kernel compilation: the TPU-native equivalent of MXRtc.

The reference lets users write a raw CUDA kernel *body* in a Python string,
compiles it at runtime with NVRTC, and launches it on NDArrays
(ref: python/mxnet/rtc.py:8-95, include/mxnet/mxrtc.h:24-83,
src/common/mxrtc.cc). The TPU analog of "runtime-compiled user kernel" is a
Pallas kernel: the user writes the kernel body as Python source operating on
named memory refs; we decorate it into a function, compile it through
``pl.pallas_call`` + XLA at first ``push``, and cache the compiled program
(mirroring ``MXRtc::kernel_registry``, mxrtc.h:66).

Correspondence with the CUDA surface:

- kernel body string   → Python/Pallas source; input/output names become
  ``pl.Ref`` arguments, so ``y[...] = x[...] * 2`` replaces
  ``y[threadIdx.x] = x[threadIdx.x] * 2``.
- grid_dims            → the Pallas ``grid``; ``pl.program_id(axis)``
  replaces ``blockIdx``.
- block_dims           → no TPU equivalent (the VPU vectorises over lanes
  implicitly; tiling is expressed with BlockSpecs, see ``block_shapes``).
  Accepted and ignored for API compatibility.

Example::

    x = mx.nd.array(np.arange(10))
    y = mx.nd.zeros((10,))
    k = mx.rtc.Rtc('axpy', [('x', x)], [('y', y)],
                   "y[...] = x[...] * 2.0 + 1.0")
    k.push([x], [y], (1, 1, 1), (1, 1, 1))

The body executes with ``pl``(jax.experimental.pallas), ``pltpu``, ``jnp``,
``lax``, and ``jax`` in scope. A Python callable ``kernel(in_refs...,
out_refs...)`` is also accepted in place of source. Off-TPU the kernel runs
in Pallas interpret mode so the same user code is testable on CPU; with a
TPU as the default device it is compiled by Mosaic, never interpreted —
same contract as the rest of mxnet_tpu's Pallas fast paths.
"""
from __future__ import annotations

import textwrap

__all__ = ["Rtc"]

# compiled-program cache shared across Rtc instances, keyed by
# (source, shapes, dtypes, grid) — the kernel_registry analog (mxrtc.h:66)
_program_cache = {}


def _decorate(name, in_names, out_names, body):
    """Wrap the user kernel body into a Pallas kernel function — the
    analog of MXRtc::decorate (src/common/mxrtc.cc) which wraps the CUDA
    body in ``extern "C" __global__ name(float* ...)``."""
    args = ", ".join(list(in_names) + list(out_names))
    src = "def {}({}):\n{}\n".format(
        name, args, textwrap.indent(textwrap.dedent(body), "    ") or "    pass"
    )
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scope = {"jax": jax, "jnp": jnp, "lax": lax, "pl": pl, "pltpu": pltpu}
    ns = {}
    exec(compile(src, "<mxrtc:%s>" % name, "exec"), scope, ns)
    return ns[name]


class Rtc:
    """Runtime-compiled user kernel on NDArrays (ref: python/mxnet/rtc.py:8).

    Parameters
    ----------
    name : str
        Kernel name.
    inputs : list of (str, NDArray)
        Input names and template arrays (fix shapes/dtypes, like the
        reference's decoration baking ``x_dims`` into the source).
    outputs : list of (str, NDArray)
        Output names and template arrays.
    kernel : str or callable
        Kernel body source (Python/Pallas, see module docstring) or a
        ready kernel function taking input refs then output refs.
    """

    def __init__(self, name, inputs, outputs, kernel):
        if not inputs or not outputs:
            raise ValueError("Rtc requires at least one input and one output")
        self.name = name
        self._in_names = [n for n, _ in inputs]
        self._out_names = [n for n, _ in outputs]
        self._in_shapes = [tuple(a.shape) for _, a in inputs]
        self._in_dtypes = [a.dtype for _, a in inputs]
        self._out_shapes = [tuple(a.shape) for _, a in outputs]
        self._out_dtypes = [a.dtype for _, a in outputs]
        if callable(kernel):
            self._source = getattr(kernel, "__name__", repr(kernel))
            self._kernel = kernel
        else:
            self._source = kernel
            self._kernel = _decorate(name, self._in_names, self._out_names, kernel)

    def _compile(self, grid, block_shapes):
        key = (
            self.name,
            self._source,
            tuple(self._in_shapes),
            tuple(str(d) for d in self._in_dtypes),
            tuple(self._out_shapes),
            tuple(str(d) for d in self._out_dtypes),
            grid,
            block_shapes,
        )
        prog = _program_cache.get(key)
        if prog is not None:
            return prog
        import jax
        from jax.experimental import pallas as pl

        from .ops.pallas_kernels import _interpret

        out_shape = [
            jax.ShapeDtypeStruct(s, d)
            for s, d in zip(self._out_shapes, self._out_dtypes)
        ]
        kwargs = {}
        if grid is not None:
            kwargs["grid"] = grid
        if block_shapes is not None:
            in_specs, out_specs = block_shapes
            kwargs["in_specs"] = [pl.BlockSpec(*spec) for spec in in_specs]
            kwargs["out_specs"] = [pl.BlockSpec(*spec) for spec in out_specs]
        call = pl.pallas_call(
            self._kernel, out_shape=out_shape, interpret=_interpret(), **kwargs
        )
        prog = jax.jit(call)
        _program_cache[key] = prog
        return prog

    def push(self, inputs, outputs, grid_dims=(1, 1, 1), block_dims=None,
             block_shapes=None):
        """Run the kernel (ref: python/mxnet/rtc.py push:61-95).

        ``inputs``/``outputs`` may differ from the constructor arrays but
        must match their shapes and order (same contract as the reference).
        ``grid_dims`` maps to the Pallas grid (trailing 1s dropped);
        ``block_dims`` is accepted for compatibility and ignored.
        ``block_shapes``, when given, is ``(in_specs, out_specs)`` of
        BlockSpec constructor tuples for explicit VMEM tiling.
        """
        del block_dims  # no TPU analog; see module docstring
        if len(inputs) != len(self._in_shapes):
            raise ValueError("kernel takes %d inputs, got %d"
                             % (len(self._in_shapes), len(inputs)))
        if len(outputs) != len(self._out_shapes):
            raise ValueError("kernel produces %d outputs, got %d arrays"
                             % (len(self._out_shapes), len(outputs)))
        for arr, shape in zip(inputs, self._in_shapes):
            if tuple(arr.shape) != shape:
                raise ValueError(
                    "input shape %s does not match kernel template %s"
                    % (tuple(arr.shape), shape)
                )
        for arr, shape in zip(outputs, self._out_shapes):
            if tuple(arr.shape) != shape:
                raise ValueError(
                    "output shape %s does not match kernel template %s"
                    % (tuple(arr.shape), shape)
                )
        grid = tuple(int(g) for g in grid_dims)
        while grid and grid[-1] == 1:
            grid = grid[:-1]
        prog = self._compile(grid if grid else None, block_shapes)
        results = prog(*[a._data for a in inputs])
        if not isinstance(results, (list, tuple)):
            results = [results]
        for out_nd, val in zip(outputs, results):
            out_nd._set_data(val)
