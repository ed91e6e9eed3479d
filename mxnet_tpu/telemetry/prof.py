"""mxprof: continuous performance & memory attribution (``MXNET_PROF=1``).

mxtel/mxdash record *that* time passed — spans, counters, merged rank
timelines — but nothing attributes *where* a training or serving step's
time and HBM actually go. mxprof is that attribution layer, and like the
rest of the telemetry subsystem it is always available and **off by
default**: with ``MXNET_PROF`` unset every instrumented site reduces to
one module-bool check (the same contract as ``telemetry.ENABLED``).

Three views, all keyed consistently:

1. **Per-program cost records.** Call sites that hold a jitted program
   and its example arguments (the Executor's fused fwd+bwd, the scanned
   fit trainer's K-step loop, the serving model's bucketed ragged step)
   hand them to :func:`attribute_jit`, which AOT-lowers and compiles
   ONCE, folds in XLA's ``compiled.cost_analysis()`` (flops, bytes
   accessed) and ``compiled.memory_analysis()`` (argument/output/temp
   bytes — the program's static HBM footprint), and returns the
   compiled callable so the attribution compile IS the program's one
   compile (no double build). Records are keyed
   ``<compile.config_key()>|<site signature>``, so records taken under
   different rewrite-pass configurations never alias.

2. **Analytic graph cost.** :func:`graph_cost` walks a Symbol DAG with
   the jax-free IR utilities (``compile/ir.py``: shape/dtype sweeps)
   and computes per-node FLOPs/bytes from the op metadata alone — no
   device, no jax import. The per-op table is what `/profilez` and the
   report tool render; the totals cross-check XLA's numbers (the
   analytic-vs-XLA agreement gate in tests/unittest/test_mxprof.py).

3. **Step-time decomposition.** The train and serving step paths feed
   :func:`note_step` fenced sub-phase durations — ``host`` (input
   prep/staging), ``dispatch`` (submitting the compiled program),
   ``device`` (block-until-ready delta: time truly blocked on the
   accelerator), ``d2h`` (result pull + metric fence), ``update``
   (optimizer/kvstore, per-batch path only). Each call lands a
   ``{"kind": "prof", "event": "step_breakdown"}`` journal record plus
   ``prof.step.<phase>_secs`` histograms, and classifies the step as
   input-/compute-/host-bound — a first-class per-rank signal
   ``tools/trace_merge.py`` merges (``prof_rows``).

Derived headline metrics — MFU against the chip's bf16 peak and
roofline% against the HBM-bandwidth bound — live here
(:func:`peak_flops`, :func:`hbm_gbps`, :func:`derived`) so `/profilez`
and ``tools/telemetry_report.py`` share one definition. The peaks come from
one table keyed by ``device_kind`` (:data:`PEAKS`); a device that is not
in it (the CPU included) has no peak, and no MFU/roofline is derived.

Enablement::

    MXNET_PROF=1                    # master switch (off by default)
    MXNET_PROF_PEAK_FLOPS=1.97e14   # optional: chip peak override
    MXNET_PROF_HBM_GBPS=819         # optional: HBM bandwidth override

With ``MXNET_TELEMETRY=1`` as well, prof metrics land in the registry /
journal / ``/profilez``; prof alone still accumulates its in-process
program and step tables (``snapshot()``).
"""
from __future__ import annotations

import logging
import os
import threading
import time

__all__ = [
    "ENABLED", "reload", "reset",
    "graph_cost", "attribute_jit", "program_records",
    "note_step", "step_summary",
    "peak_flops", "hbm_gbps", "hbm_stats", "derived", "snapshot",
    "PEAKS",
    "PHASES",
]

log = logging.getLogger("mxnet_tpu.prof")

#: Published per-chip peaks keyed by ``jax.Device.device_kind``:
#: ``(bf16 FLOP/s, HBM GB/s)`` — the MFU and roofline denominators every
#: consumer shares. Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 819 GB/s HBM); a v5e chip reports itself as
#: "TPU v5 lite". An unlisted kind has no peak (see peak_flops).
PEAKS = {
    "TPU v5 lite": (197e12, 819.0),
}

#: the fenced sub-phases a step decomposes into (note_step keys)
PHASES = ("host", "dispatch", "device", "d2h", "update")

#: phase -> boundedness verdict when it dominates the step
_BOUND_BY_PHASE = {
    "host": "input",      # staging/input prep dominates: input-bound
    "dispatch": "host",   # python dispatch overhead dominates
    "device": "compute",  # blocked on the accelerator: compute-bound
    "d2h": "host",        # result pull / metric fence dominates
    "update": "host",
}

ENABLED = False

_lock = threading.Lock()
#: key -> program record dict (attribute_jit)
_programs = {}
#: key -> compiled callable (attribute_jit memo; separate from the
#: json-able record so snapshot() never trips over an executable)
_compiled = {}
#: path -> {"count", "batches", "phases": {p: total}, "total": s,
#:          "bound": {verdict: count}}
_steps = {}
#: monotonic stamp of the last derived-gauge refresh (note_step
#: throttles the derived()/hbm_stats() recomputation — a per-decode-
#: step program-table scan + device memory_stats query would tax
#: ms-scale steps for a gauge nobody reads faster than ~1 Hz)
_GAUGE_REFRESH_SECS = 1.0
_last_gauge_t = 0.0
#: fresh attribute_jit compiles performed (NOT memo hits). Step
#: instrumentation snapshots this around a step and skips the
#: breakdown record when it advanced: a first-dispatch XLA compile
#: (seconds) inside the timed window would otherwise dominate the
#: phase shares and misclassify short runs as input/host-bound.
_attr_compiles = 0


def attribution_count():
    """Number of fresh AOT compiles attribute_jit has performed —
    call sites bracket a step with it to drop compile-polluted
    breakdown records."""
    return _attr_compiles


def _env_on(name):
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "off", "no")


def reload():
    """Re-read ``MXNET_PROF``; called from ``telemetry.reload()`` so
    tests toggle via monkeypatch.setenv + telemetry.reload()."""
    global ENABLED
    ENABLED = _env_on("MXNET_PROF")
    return ENABLED


def reset():
    """Drop program/step state (test isolation; rides
    ``telemetry.reset()``)."""
    global _last_gauge_t
    with _lock:
        _programs.clear()
        _compiled.clear()
        _steps.clear()
        _last_gauge_t = 0.0  # next note_step refreshes the gauges


# -- derived-metric constants -------------------------------------------------
def _peak(index, env):
    """One column of PEAKS for the default device, unless ``env``
    overrides it; None when the device's kind is not listed."""
    raw = os.environ.get(env, "").strip()
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    from ..context import default_jax_device

    row = PEAKS.get(default_jax_device().device_kind)
    return None if row is None else row[index]


def peak_flops():
    """The default device's peak bf16 FLOP/s for MFU derivation:
    ``MXNET_PROF_PEAK_FLOPS`` override, else its PEAKS row, else None —
    an unlisted device (a CPU, a chip nobody entered) gets no MFU
    rather than one measured against another chip's peak."""
    return _peak(0, "MXNET_PROF_PEAK_FLOPS")


def hbm_gbps():
    """HBM bandwidth (GB/s) for roofline%: ``MXNET_PROF_HBM_GBPS``
    override, else the device's PEAKS row, else None."""
    return _peak(1, "MXNET_PROF_HBM_GBPS")


# -- analytic graph cost ------------------------------------------------------
def _prod(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _itemsize(dt):
    try:
        import numpy as np

        return int(np.dtype(dt).itemsize)
    except Exception:
        return 4


def _node_flops(n, out_shape, in_shapes):
    """Forward FLOPs for one node from its op metadata + shapes (the
    standard conventions: 2·M·N·K for matmuls/convs, a few ops per
    element for normalization/softmax, one per element otherwise)."""
    op = n.op.name
    p = n.params
    if out_shape is None:
        return 0
    size = _prod(out_shape)
    if op in ("Convolution", "Deconvolution"):
        kernel = p.get("kernel") or ()
        group = int(p.get("num_group") or 1)
        # in channels from the data input's shape (NCHW)
        cin = None
        if in_shapes and in_shapes[0] is not None and len(in_shapes[0]) >= 2:
            cin = int(in_shapes[0][1])
        if cin is None or not kernel:
            return 2 * size  # underdetermined: be cheap, not wrong-sign
        return 2 * size * (cin // max(group, 1)) * _prod(kernel)
    if op == "FullyConnected":
        if in_shapes and in_shapes[0] is not None:
            d_in = _prod(in_shapes[0][1:])
            return 2 * size * d_in
        return 2 * size
    if op == "BatchNorm":
        return 8 * size
    if op == "Pooling":
        kernel = p.get("kernel") or ()
        if p.get("global_pool") and in_shapes and in_shapes[0] is not None:
            return _prod(in_shapes[0])
        return size * max(1, _prod(kernel))
    if op in ("SoftmaxOutput", "Softmax", "SoftmaxActivation",
              "LogisticRegressionOutput", "LinearRegressionOutput",
              "MAERegressionOutput", "log_softmax", "softmax"):
        return 5 * size
    if op in ("Concat", "Reshape", "Flatten", "transpose", "SliceChannel",
              "expand_dims", "BlockGrad", "Cast", "_copy"):
        return 0  # pure data movement: bytes, not flops
    return size


def graph_cost(symbol, input_shapes, input_types=None):
    """Analytic per-node FLOPs/bytes for a Symbol graph — jax-free.

    ``input_shapes``: {arg name: shape} seeding the bidirectional shape
    sweep (``compile/ir.py``). Returns::

        {"nodes": [{"name", "op", "flops", "bytes", "out_shape"}...],
         "flops": <forward total>, "flops_train": <~3x forward>,
         "bytes": <total moved>, "params_bytes": <weight footprint>,
         "unresolved": <nodes whose shapes stayed unknown>}

    Nodes whose shapes cannot be recovered contribute zero (and are
    counted in ``unresolved``) — the walk must work on whatever the
    sweep can infer, same contract as graph_lint's shape pass.
    """
    from ..compile import ir

    nodes = symbol.nodes
    name_to_var = {n.name: n for n in nodes if n.is_variable}
    seed = {}
    for name, shape in (input_shapes or {}).items():
        v = name_to_var.get(name)
        if v is not None and shape is not None:
            seed[(id(v), 0)] = tuple(shape)
    shapes = ir.propagate_shapes(nodes, seed)
    tseed = {}
    if input_types:
        import numpy as np

        for name, t in input_types.items():
            v = name_to_var.get(name)
            if v is not None and t is not None:
                tseed[(id(v), 0)] = np.dtype(t)
    dtypes = ir.propagate_dtypes(nodes, tseed)

    out = []
    total_flops = 0
    total_bytes = 0
    unresolved = 0
    params_bytes = 0
    input_names = set(input_shapes or ())
    for n in nodes:
        if n.is_variable:
            s = shapes.get((id(n), 0))
            if s is not None and n.name not in input_names:
                params_bytes += _prod(s) * _itemsize(
                    dtypes.get((id(n), 0), "float32"))
            continue
        out_shape = shapes.get((id(n), 0))
        in_shapes = [shapes.get((id(s), i)) for s, i in n.inputs]
        if out_shape is None:
            unresolved += 1
        flops = _node_flops(n, out_shape, in_shapes)
        nbytes = 0
        for (s, i), sh in zip(n.inputs, in_shapes):
            if sh is not None:
                nbytes += _prod(sh) * _itemsize(
                    dtypes.get((id(s), i), "float32"))
        n_out = len(n.op.list_outputs(n.params))
        for i in range(n_out):
            sh = shapes.get((id(n), i))
            if sh is not None:
                nbytes += _prod(sh) * _itemsize(
                    dtypes.get((id(n), i), "float32"))
        total_flops += flops
        total_bytes += nbytes
        out.append({
            "name": n.name, "op": n.op.name, "flops": int(flops),
            "bytes": int(nbytes),
            "out_shape": list(out_shape) if out_shape is not None else None,
        })
    out.sort(key=lambda r: -r["flops"])
    return {
        "nodes": out,
        "flops": int(total_flops),
        # fwd+bwd ≈ 3x fwd for matmul-dominated graphs (the standard
        # training-FLOPs convention)
        "flops_train": int(3 * total_flops),
        "bytes": int(total_bytes),
        "params_bytes": int(params_bytes),
        "unresolved": unresolved,
    }


# -- XLA program attribution --------------------------------------------------
def config_key_prefix():
    """The PR 6 jit-cache configuration key — program records carry it
    so a record and the persistent-cache entry of the same executable
    share a key root."""
    try:
        from .. import compile as _compile

        return _compile.config_key()
    except Exception:
        return "v1|opt=?"


def _cost_dict(compiled):
    """Normalize ``compiled.cost_analysis()`` across jax versions
    (dict, or a 1-list of dicts) to {"flops", "bytes_accessed"}."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


def _memory_dict(compiled):
    ma = compiled.memory_analysis()
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field[:-len("_in_bytes")]] = int(v)
    if out:
        # static footprint while the program runs: args + outputs +
        # scratch (aliased/donated buffers counted once, on the
        # argument side)
        out["static_peak"] = (out.get("argument_size", 0)
                              + out.get("output_size", 0)
                              + out.get("temp_size", 0)
                              - out.get("alias_size", 0))
    return out


def graph_hash(text):
    """Short stable hash of a graph-identity string (a
    :func:`symbol_fingerprint`, a config repr) — the component of a
    program key that distinguishes two programs whose shape signatures
    coincide."""
    import hashlib

    return hashlib.sha1(str(text).encode("utf-8", "replace")) \
        .hexdigest()[:12]


def symbol_fingerprint(sym):
    """Graph-identity hash of a Symbol: op names, node names, FULL op
    params and wiring. ``debug_str`` deliberately omits params — but
    two graphs differing only in a param (``act_type=relu`` vs
    ``tanh``) are different programs, and the attribute_jit memo must
    never alias them."""
    lines = []
    for n in sym.nodes:
        ins = ",".join("%s[%d]" % (s.name, i) for s, i in n.inputs)
        if n.is_variable:
            lines.append("var %s %r" % (n.name, sorted(n.attrs.items())))
        else:
            lines.append("%s %s %r %r (%s)" % (
                n.op.name, n.name, sorted(n.params.items()),
                sorted(n.attrs.items()), ins))
    return graph_hash("\n".join(lines))


def attribute_jit(key, jitted, args=(), kwargs=None, site="",
                  analytic=None, meta=None, graph_key=None):
    """AOT-compile ``jitted`` for ``args`` once, record its XLA cost and
    memory analysis under ``<config_key>|<key>[|g=<graph_key>]``, and
    return the compiled callable — so attribution reuses the program's
    one compile instead of adding a second. Any failure (backend
    without the AOT API, analysis unimplemented) falls back to
    returning ``jitted`` unchanged with whatever partial record could
    be built; this function never raises into a training or serving
    step.

    ``graph_key`` is REQUIRED for correctness whenever two different
    programs could share a shape signature: the memo returns the cached
    compiled executable for a repeated key, so the key must capture the
    program's identity (graph structure / config), not just its
    argument shapes — callers pass :func:`graph_hash` of the symbol's
    ``debug_str`` or the model config. ``analytic``: an optional
    :func:`graph_cost` result to fold into the record (the per-op table
    `/profilez` renders). ``meta``: free-form json-able context
    (shapes, bucket, K).
    """
    full_key = "%s|%s" % (config_key_prefix(), key)
    if graph_key:
        full_key += "|g=%s" % graph_key
    with _lock:
        cached = _compiled.get(full_key)
    if cached is not None:
        return cached
    global _attr_compiles
    _attr_compiles += 1
    rec = {
        "key": full_key, "site": site or key, "t": time.time(),
        "calls": 0, "device_secs": 0.0,
        "meta": dict(meta or {}),
    }
    fn = jitted
    try:
        lowered = jitted.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
        fn = compiled
        try:
            rec.update(_cost_dict(compiled))
        except Exception as e:
            rec["cost_error"] = "%s: %s" % (type(e).__name__, e)
        try:
            rec["memory"] = _memory_dict(compiled)
        except Exception as e:
            rec["memory_error"] = "%s: %s" % (type(e).__name__, e)
    except Exception as e:
        # no AOT path (or tracing rejected the args): keep the jitted
        # callable, record what we know
        rec["lower_error"] = "%s: %s" % (type(e).__name__, e)
        log.debug("mxprof: attribute_jit(%s) fell back to the jitted "
                  "callable: %s", key, e)
    if analytic is not None:
        rec["analytic"] = {
            "flops": analytic.get("flops"),
            "flops_train": analytic.get("flops_train"),
            "bytes": analytic.get("bytes"),
            "params_bytes": analytic.get("params_bytes"),
            "top_ops": analytic.get("nodes", [])[:12],
        }
    with _lock:
        _programs[full_key] = rec
        _compiled[full_key] = fn
    _emit(dict(rec, kind="prof", event="program"))
    return fn


def program_records(top=None):
    """Program records sorted by accumulated device seconds (then
    flops) — the `/profilez` "top programs" table."""
    with _lock:
        recs = [dict(r) for r in _programs.values()]
    recs.sort(key=lambda r: (-r.get("device_secs", 0.0),
                             -(r.get("flops") or 0)))
    return recs if top is None else recs[:top]


def program_key_for(key, graph_key=None):
    """The full (config-prefixed) key attribute_jit stored ``key``
    under (same ``graph_key`` as the attribute_jit call) — call sites
    pass it back to :func:`note_step`."""
    full_key = "%s|%s" % (config_key_prefix(), key)
    if graph_key:
        full_key += "|g=%s" % graph_key
    return full_key


# -- step-time decomposition --------------------------------------------------
def _emit(record):
    from . import export as _export

    _export.emit(record)


def note_step(path, phases, key=None, batches=1, samples=None,
              tokens=None, d2h_bytes=None):
    """Record one decomposed step (or K-batch chunk).

    ``phases``: {phase: seconds} with phases from :data:`PHASES` —
    absent phases simply don't apply to this path. Accumulates the
    per-path aggregate, attributes the ``device`` phase to the program
    record under ``key``, observes ``prof.step.<phase>_secs`` +
    ``prof.step_secs`` histograms and refreshes the derived gauges
    (``prof.mfu`` etc.) when telemetry is on, and emits one
    ``step_breakdown`` journal record. ``d2h_bytes`` (optional) is the
    number of result bytes the step actually pulled device->host — the
    serving decode path journals it so the "logits never leave the
    device" contract is mechanically checkable (ISSUE 15: a decode
    step's pull is the token vector, not a [B, V] logits array).
    Callers guard on :data:`ENABLED`; calling this with prof off is a
    no-op."""
    if not ENABLED:
        return None
    total = sum(phases.values())
    dominant = max(phases, key=lambda p: phases[p]) if phases else None
    bound = _BOUND_BY_PHASE.get(dominant, "unknown")
    with _lock:
        st = _steps.get(path)
        if st is None:
            st = _steps[path] = {
                "count": 0, "batches": 0, "total": 0.0,
                "phases": {}, "bound": {},
            }
        st["count"] += 1
        st["batches"] += int(batches)
        st["total"] += total
        for p, v in phases.items():
            st["phases"][p] = st["phases"].get(p, 0.0) + float(v)
        st["bound"][bound] = st["bound"].get(bound, 0) + 1
        if key is not None:
            prog = _programs.get(key)
            if prog is not None:
                prog["calls"] += 1
                prog["device_secs"] += float(phases.get("device", 0.0))
    from .. import telemetry as _tel

    if _tel.ENABLED:
        _tel.histogram("prof.step_secs").observe(total)
        for p, v in phases.items():
            _tel.histogram("prof.step.%s_secs" % p).observe(v)
        global _last_gauge_t
        now = time.monotonic()
        with _lock:
            # the throttle stamp is written under the module lock
            # everywhere (reset() holds it too); the derived()/
            # memory_stats work below stays outside the critical
            # section — only the claim of this refresh window is locked
            refresh = now - _last_gauge_t >= _GAUGE_REFRESH_SECS
            if refresh:
                _last_gauge_t = now
        if refresh:
            d = derived()
            if d.get("mfu") is not None:
                _tel.gauge("prof.mfu").set(d["mfu"])
            if d.get("roofline_pct") is not None:
                _tel.gauge("prof.roofline_pct").set(d["roofline_pct"])
            hbm = hbm_stats()
            if hbm.get("live_bytes") is not None:
                _tel.gauge("prof.hbm_live_bytes").set(hbm["live_bytes"])
            if hbm.get("peak_bytes") is not None:
                _tel.gauge("prof.hbm_peak_bytes").set(hbm["peak_bytes"])
    rec = {
        "kind": "prof", "event": "step_breakdown", "t": time.time(),
        "path": path, "batches": int(batches), "total_s": total,
        "phases": {p: float(v) for p, v in phases.items()},
        "bound": bound,
    }
    if key is not None:
        rec["key"] = key
    if samples is not None and total > 0:
        rec["samples_per_s"] = samples / total
    if tokens is not None and total > 0:
        rec["tokens_per_s"] = tokens / total
    if d2h_bytes is not None:
        rec["d2h_bytes"] = int(d2h_bytes)
    _emit(rec)
    return rec


def step_summary():
    """{path: aggregate} — per-path phase totals, mean shares, and the
    majority boundedness verdict."""
    with _lock:
        out = {}
        for path, st in _steps.items():
            total = st["total"] or 1e-12
            shares = {p: v / total for p, v in st["phases"].items()}
            verdict = max(st["bound"], key=lambda b: st["bound"][b]) \
                if st["bound"] else None
            out[path] = {
                "count": st["count"], "batches": st["batches"],
                "total_s": st["total"],
                "phases_s": dict(st["phases"]),
                "phase_share": shares,
                "bound": verdict,
                "bound_votes": dict(st["bound"]),
            }
        return out


# -- derived metrics + HBM ----------------------------------------------------
def hbm_stats():
    """{"live_bytes", "peak_bytes", "source"} — the device allocator's
    view when the backend exposes ``memory_stats()`` (TPU/GPU), else a
    static estimate from the attributed programs' memory analyses
    (args+outputs+temp of the largest program)."""
    try:
        import jax

        dev = jax.local_devices()[0]
        ms = dev.memory_stats()
        if ms and "bytes_in_use" in ms:
            return {
                "live_bytes": int(ms.get("bytes_in_use", 0)),
                "peak_bytes": int(ms.get("peak_bytes_in_use",
                                         ms.get("bytes_in_use", 0))),
                "source": "device",
            }
    except Exception:
        pass
    with _lock:
        peaks = [r.get("memory", {}).get("static_peak")
                 for r in _programs.values()]
    peaks = [p for p in peaks if p]
    if peaks:
        return {"live_bytes": None, "peak_bytes": max(peaks),
                "source": "static_estimate"}
    return {"live_bytes": None, "peak_bytes": None, "source": "none"}


def derived():
    """Headline derivations over the attributed programs:

    - ``mfu``: executed FLOPs / device seconds / chip peak, over every
      program with measured device time;
    - ``roofline_pct``: achieved bytes/s as % of HBM bandwidth over
      whatever ran;
    - per-program ``mfu`` on the top entry.
    """
    with _lock:
        recs = [dict(r) for r in _programs.values()]
    flops_done = 0.0
    bytes_done = 0.0
    dev_secs = 0.0
    for r in recs:
        calls, ds = r.get("calls", 0), r.get("device_secs", 0.0)
        if not calls or ds <= 0:
            continue
        if r.get("flops"):
            flops_done += r["flops"] * calls
        if r.get("bytes_accessed"):
            bytes_done += r["bytes_accessed"] * calls
        dev_secs += ds
    peak, hbm = peak_flops(), hbm_gbps()
    out = {
        "peak_flops": peak,
        "hbm_gbps": hbm,
        "device_secs": dev_secs,
        "mfu": None,
        "roofline_pct": None,
    }
    if dev_secs > 0 and flops_done > 0:
        out["tflops"] = flops_done / dev_secs / 1e12
        if peak:
            out["mfu"] = flops_done / dev_secs / peak
    if dev_secs > 0 and bytes_done > 0 and hbm:
        out["roofline_pct"] = 100.0 * bytes_done / dev_secs / (hbm * 1e9)
    return out


def snapshot(top=20):
    """The `/profilez` body: program table, step decomposition, derived
    MFU/roofline, HBM view. Valid (``enabled: false``) when prof is
    off — introspection never errors."""
    return {
        "enabled": ENABLED,
        "config_key": config_key_prefix(),
        "programs": program_records(top=top),
        "steps": step_summary(),
        "derived": derived(),
        "hbm": hbm_stats(),
    }


reload()
