"""mxc: the compilation layer — graph rewrites, autotuning, jit cache.

Three cooperating pieces close the compiler-shaped half of the roofline
gap (ROADMAP "Compilation layer"; ground: PAPERS.md TVM):

1. **Graph-rewrite passes** over the Symbol graph before executor
   lowering — constant folding (fold.py), NCHW→NHWC layout selection
   with transpose hoisting (layout.py), elementwise-chain fusion
   (fuse.py) and the
   tuned matmul-accumulation flag (precision.py). Each pass is a
   separate module sharing the ir.py walk utilities with
   ``analysis/graph_lint.py``, individually disableable, and checked
   against the unrewritten graph (pipeline.check_equivalence).
2. **A measure-and-cache autotuner** (autotune.py) for contested
   choices — per-conv layout, segment boundaries, matmul precision —
   timed once on the real device, winner persisted on disk keyed by
   (op, shapes, dtype, backend).
3. **A persistent compilation cache** (jit_cache.py): traced/lowered
   executables survive process restarts via jax's compilation cache,
   placed by ``JAX_COMPILATION_CACHE_DIR``.

Enablement contract (off by default, the repo's established style)::

    MXNET_COMPILE_OPT=1               # master switch for the passes
    MXNET_COMPILE_PASSES=...          # subset of fold,layout,fuse,precision
    JAX_COMPILATION_CACHE_DIR=/path   # persistent jit cache + tuning db
    MXNET_COMPILE_TUNE=1              # allow on-device tuning trials
    MXNET_COMPILE_VERIFY=1            # golden-check every optimize()
    MXNET_COMPILE_MATMUL_PREC=auto    # auto | f32 | fast

The cache is independent of the passes: ``JAX_COMPILATION_CACHE_DIR``
alone turns cold-start jit builds into loads with zero graph changes.
Off, the only cost at bind time is one module attribute test.
mxtel counters: ``compile.passes_applied_total``,
``compile.cache_hits_total``/``misses_total``/``corrupt_total``,
``compile.tuning_trials_total``; spans: ``compile.optimize``,
``compile.pass.<name>``. Docs: docs/how_to/compilation.md.
"""
from __future__ import annotations

import os

from .. import telemetry as _tel
from ..base import MXNetError

__all__ = [
    "ENABLED", "enabled", "reload", "optimize", "ensure_jit_cache",
    "active_passes", "config_key", "last_report", "CompileVerifyError",
]


class CompileVerifyError(MXNetError):
    """A rewritten graph diverged from the unrewritten reference under
    ``MXNET_COMPILE_VERIFY=1``. Never swallowed by the bind-time
    fallback — a wrong rewrite must not train silently."""

#: Master switch for the rewrite passes. The executor reads this ONE
#: attribute on every bind; everything else loads lazily behind it.
ENABLED = False

PASS_ORDER = ("fold", "layout", "fuse", "precision")

_passes = PASS_ORDER
_verify = False
_tune = False
_matmul_prec = "auto"


def _env_on(name):
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "off", "no")


def reload():
    """Re-read the MXNET_COMPILE_* environment (import-time default;
    tests call it after monkeypatching)."""
    global ENABLED, _passes, _verify, _tune, _matmul_prec
    ENABLED = _env_on("MXNET_COMPILE_OPT")
    raw = os.environ.get("MXNET_COMPILE_PASSES", "").strip()
    if raw:
        wanted = {p.strip() for p in raw.split(",") if p.strip()}
        unknown = wanted - set(PASS_ORDER)
        if unknown:
            raise ValueError(
                "MXNET_COMPILE_PASSES: unknown pass(es) %s (know: %s)"
                % (sorted(unknown), list(PASS_ORDER)))
        _passes = tuple(p for p in PASS_ORDER if p in wanted)
    else:
        _passes = PASS_ORDER
    _verify = _env_on("MXNET_COMPILE_VERIFY")
    _tune = _env_on("MXNET_COMPILE_TUNE")
    _matmul_prec = (os.environ.get("MXNET_COMPILE_MATMUL_PREC", "auto")
                    .strip().lower() or "auto")
    if _matmul_prec not in ("auto", "f32", "fast"):
        raise ValueError(
            "MXNET_COMPILE_MATMUL_PREC=%r (know: auto, f32, fast)"
            % (_matmul_prec,))


def enabled():
    return ENABLED


def active_passes():
    return _passes


def config_key():
    """Stable string describing the rewrite configuration — the prefix
    of mxprof's program-record keys, so records taken under different
    pass configurations never alias."""
    return "v1|opt=%d|passes=%s|prec=%s" % (
        int(ENABLED), ",".join(_passes) if ENABLED else "-", _matmul_prec)


def optimize(sym, input_shapes=None, input_types=None, frozen_params=None):
    """Run the active passes over ``sym``; returns the rewritten Symbol
    (``sym`` unchanged when nothing applies). Callers treat the result
    as an executor-internal artifact: it shares variable nodes with the
    original by identity and its fused/layout ops are not registry ops,
    so it must never be serialized."""
    if not ENABLED:
        return sym
    from . import autotune, pipeline
    from .jit_cache import cache_dir

    tuner = autotune.make_tuner(cache_dir(), measure_enabled=_tune)
    with _tel.span("compile.optimize"):
        return pipeline.run(
            sym, _passes, input_shapes=input_shapes,
            input_types=input_types, frozen_params=frozen_params,
            tuner=tuner, matmul_prec=_matmul_prec, verify=_verify)


def ensure_jit_cache():
    """Wire the persistent jit cache when one is placed
    (JAX_COMPILATION_CACHE_DIR, or an entry point's jit_cache.enable());
    safe no-op otherwise. Every compile entry point calls this before
    building programs."""
    from . import jit_cache

    return jit_cache.ensure()


def last_report():
    """The most recent optimize() pass report (test/tools hook)."""
    from . import pipeline

    return dict(pipeline.LAST_REPORT)


try:
    reload()
except ValueError as _e:  # a typo'd env var must not break import;
    import logging as _logging  # explicit reload() still raises for tests

    _logging.getLogger("mxnet_tpu.compile").warning(
        "MXNET_COMPILE_* misconfigured (%s); compile layer disabled", _e)
    ENABLED = False
