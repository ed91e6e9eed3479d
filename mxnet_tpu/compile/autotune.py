"""Measure-and-cache autotuner for contested compilation choices.

Some rewrite decisions have no safe static answer — per-conv layout
(tiny spatial dims or odd channel counts can favor NCHW on some
backends), elementwise segment boundaries, and the matmul accumulation
flag all depend on the actual device. The TVM recipe (PAPERS.md) is to
*measure* the candidates once on the real hardware and remember the
winner: each contested choice is timed as a small jitted program
(compiled, warmed up, best-of-N wall clock fenced with
``block_until_ready``), and the winner is persisted in an
on-disk tuning database keyed by ``(choice-kind, op, shapes, dtype,
backend)``.

Database format (``tuning.json`` beside the persistent jit cache's
entries, ``jit_cache.cache_dir()``)::

    {"version": 1,
     "entries": {"<key>": {"choice": "...", "timings": {...},
                           "backend": "...", "ts": ...}}}

Reads are cheap and happen on every optimize(); measurement only runs
under ``MXNET_COMPILE_TUNE=1`` (a tuning run is a deliberate,
device-occupying act). A corrupt database never crashes a run: it is
quarantined to ``tuning.json.corrupt`` and counted via
``compile.cache_corrupt_total`` (same fallback contract as the jit
cache, docs/how_to/compilation.md).
"""
from __future__ import annotations

import json
import os
import time

import numpy as _np

from .. import telemetry as _tel

__all__ = ["TuningDB", "Tuner", "make_tuner"]

DB_VERSION = 1

#: process-lifetime counters (exact mirrors of the mxtel counters, kept
#: as plain ints so subprocess probes can report without telemetry on)
TRIALS = 0
CORRUPT = 0


def _count_corrupt():
    global CORRUPT
    CORRUPT += 1
    if _tel.ENABLED:
        _tel.counter("compile.cache_corrupt_total").inc()


def _count_trial():
    global TRIALS
    TRIALS += 1
    if _tel.ENABLED:
        _tel.counter("compile.tuning_trials_total").inc()


class TuningDB:
    """On-disk choice database with crash/corruption-safe semantics:
    atomic replace on write, quarantine + empty-start on unreadable or
    malformed content."""

    def __init__(self, path):
        self.path = path
        self._entries = None

    def _load(self):
        if self._entries is not None:
            return self._entries
        self._entries = {}
        if not os.path.exists(self.path):
            return self._entries
        try:
            with open(self.path, "r") as f:
                data = json.load(f)
            if (not isinstance(data, dict)
                    or data.get("version") != DB_VERSION
                    or not isinstance(data.get("entries"), dict)):
                raise ValueError("malformed tuning db")
            self._entries = dict(data["entries"])
        except (OSError, ValueError) as e:
            # truncated write, bit-flip, wrong version: recompute-able
            # state, so quarantine and start empty — never crash the run
            _count_corrupt()
            try:
                os.replace(self.path, self.path + ".corrupt")
            except OSError:
                pass
            import logging

            logging.getLogger("mxnet_tpu.compile").warning(
                "tuning db %s unreadable (%s); starting empty "
                "(quarantined to .corrupt)", self.path, e)
            self._entries = {}
        return self._entries

    def get(self, key):
        return self._load().get(key)

    def put(self, key, record):
        entries = self._load()
        entries[key] = record
        tmp = "%s.tmp.%d" % (self.path, os.getpid())
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": DB_VERSION, "entries": entries}, f,
                      indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def __len__(self):
        return len(self._load())


def measure(fn, args, warmup=2, iters=5):
    """Best-of-N wall time of ``jit(fn)(*args)``, each call fenced.
    One call = one tuning trial (counted)."""
    import jax

    return measure_calls(jax.jit(fn), args, warmup=warmup, iters=iters)


def measure_calls(f, args, warmup=2, iters=5):
    """Time an already-prepared callable (jitted program or a chain of
    dispatches) best-of-N with warmup, each call fenced with
    ``block_until_ready``. One call = one tuning trial (counted)."""
    import jax

    _count_trial()
    for _ in range(max(1, warmup)):
        jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


class Tuner:
    """Decision point used by the rewrite passes.

    ``measure_enabled=False`` (the default outside MXNET_COMPILE_TUNE=1)
    makes the tuner read-only: recorded winners are honored, unknown
    keys fall back to ``default`` without touching the device."""

    def __init__(self, db, measure_enabled=False, backend=None):
        self.db = db
        self.measure_enabled = measure_enabled
        self._backend = backend

    @property
    def backend(self):
        if self._backend is None:
            import jax

            self._backend = jax.default_backend()
        return self._backend

    def pick(self, key, candidates, default):
        """``candidates``: dict choice-name -> zero-arg thunk returning
        measured seconds. Returns the winning choice name."""
        rec = self.db.get(key) if self.db is not None else None
        if rec is not None and rec.get("choice") in candidates:
            return rec["choice"]
        if not self.measure_enabled:
            return default
        timings = {}
        for name, thunk in candidates.items():
            try:
                timings[name] = thunk()
            except Exception as e:
                import logging

                logging.getLogger("mxnet_tpu.compile").warning(
                    "tuning candidate %s for %s failed (%s: %s); skipped",
                    name, key, type(e).__name__, e)
        if not timings:
            return default
        choice = min(timings, key=timings.get)
        if self.db is not None:
            self.db.put(key, {
                "choice": choice,
                "timings": {k: round(v, 6) for k, v in timings.items()},
                "backend": self.backend,
                "ts": time.time(),
            })
        return choice

    # -- the contested choices -------------------------------------------------
    def pick_conv_layout(self, params, dshape, dtype=None):
        """'nhwc' or 'nchw' for one Convolution, keyed by its full
        problem statement. Measures fwd+bwd (training is the dominant
        consumer) of the bare conv in each layout."""
        if dshape is None:
            return "nchw"
        dt = str(_np.dtype(dtype)) if dtype is not None else "float32"
        k = tuple(params.get("kernel") or ())
        key = "conv_layout|d=%s|k=%s|s=%s|p=%s|dl=%s|f=%s|g=%s|dt=%s|b=%s" % (
            tuple(dshape), k, tuple(params.get("stride") or ()),
            tuple(params.get("pad") or ()),
            tuple(params.get("dilate") or ()), params.get("num_filter"),
            params.get("num_group", 1), dt, self.backend)

        def _variant(nhwc):
            def run():
                import jax
                import jax.numpy as jnp

                from ..ops import nn as _nn

                rng = _np.random.RandomState(0)
                nsp = len(dshape) - 2
                kk = _nn._pair(k, nsp)
                cin = dshape[1]
                nf = int(params.get("num_filter"))
                g = int(params.get("num_group", 1) or 1)
                w = jnp.asarray(
                    rng.rand(nf, cin // g, *kk), _np.dtype(dt))
                x_nchw = rng.rand(*dshape).astype(_np.dtype(dt))
                stride = _nn._pair(params.get("stride") or (1,) * nsp, nsp)
                pad = _nn._pair(params.get("pad") or (0,) * nsp, nsp)
                dil = _nn._pair(params.get("dilate") or (1,) * nsp, nsp)
                if nhwc:
                    x = jnp.asarray(x_nchw.transpose(0, 2, 3, 1))
                    wt = jnp.transpose(w, (2, 3, 1, 0))
                    dn = ("NHWC", "HWIO", "NHWC")
                else:
                    x = jnp.asarray(x_nchw)
                    wt = w
                    dn = ("NCHW", "OIHW", "NCHW")

                def loss(wt_):
                    import jax.lax as lax

                    o = lax.conv_general_dilated(
                        x, wt_, stride, [(p, p) for p in pad],
                        rhs_dilation=dil, dimension_numbers=dn,
                        feature_group_count=g)
                    return jnp.sum(o * o)

                def step(wt_):
                    import jax

                    return jax.value_and_grad(loss)(wt_)

                return measure(step, (wt,))
            return run

        return self.pick(key, {"nchw": _variant(False),
                               "nhwc": _variant(True)}, default="nhwc")

    def pick_segment_boundary(self, op_names, shape):
        """'whole' or 'split' for an elementwise chain: fuse the chain
        into one segment or split it at the midpoint. Keyed by the op
        signature and shape."""
        key = "seg_boundary|ops=%s|d=%s|b=%s" % (
            "+".join(op_names), tuple(shape), self.backend)

        def _variant(split):
            def run():
                import jax
                import jax.numpy as jnp

                x = jnp.asarray(
                    _np.random.RandomState(0).rand(*shape), _np.float32)
                n = len(op_names)

                def chain(v, count):
                    for i in range(count):
                        v = jnp.tanh(v) if i % 2 else jnp.maximum(v, 0) * 1.01
                    return v

                if split:
                    # two separate dispatches — the segment-boundary cost
                    # being contested; an outer jit would fuse them away
                    f1 = jax.jit(lambda v: chain(v, n // 2))
                    f2 = jax.jit(lambda v: chain(v, n - n // 2))
                    return measure_calls(lambda v: f2(f1(v)), (x,))
                return measure(lambda v: chain(v, n), (x,))
            return run

        return self.pick(key, {"whole": _variant(False),
                               "split": _variant(True)}, default="whole")

    def pick_matmul_precision(self, dshape, num_hidden, dtype=None):
        """'f32' (preferred_element_type=float32, the framework default)
        or 'fast' (backend-default accumulation) for one FullyConnected
        problem."""
        dt = str(_np.dtype(dtype)) if dtype is not None else "float32"
        key = "matmul_prec|d=%s|h=%s|dt=%s|b=%s" % (
            tuple(dshape), num_hidden, dt, self.backend)

        def _variant(f32):
            def run():
                import jax.numpy as jnp

                rng = _np.random.RandomState(0)
                flat = int(_np.prod(dshape[1:]))
                x = jnp.asarray(rng.rand(dshape[0], flat), _np.dtype(dt))
                w = jnp.asarray(rng.rand(num_hidden, flat), _np.dtype(dt))

                def f(x_, w_):
                    if f32:
                        return jnp.dot(x_, w_.T,
                                       preferred_element_type=jnp.float32)
                    return jnp.dot(x_, w_.T)

                return measure(f, (x, w))
            return run

        return self.pick(key, {"f32": _variant(True),
                               "fast": _variant(False)}, default="f32")


def make_tuner(cache_dir, measure_enabled):
    """Build the pipeline's tuner, or None when there is nowhere to
    persist decisions and measurement is off (a memory-only tuner that
    re-times every process would violate the measure-ONCE contract)."""
    if cache_dir:
        db = TuningDB(os.path.join(cache_dir, "tuning.json"))
        return Tuner(db, measure_enabled=measure_enabled)
    if measure_enabled:
        return Tuner(TuningDB(os.path.join(
            os.path.expanduser("~"), ".cache", "mxnet_tpu", "tuning.json")),
            measure_enabled=True)
    return None
