"""Low-precision wire codec for gradient synchronization.

EQuARX-style quantized collectives (PAPERS.md, arXiv 2506.17615): a
gradient crossing the dist kvstore or the elastic aggregator is encoded
as one low-precision payload per value — 1-byte codes plus one float32
scale per ~1024-element block — and decoded (or dequant-summed) on the
far side. Per-block scales keep an outlier in one block from crushing
another block's resolution; stochastic rounding keeps the codec
unbiased, so quantization noise averages out across steps instead of
accumulating as drift.

Scope discipline (docs/how_to/low_precision_comms.md):

- GRADIENTS may be quantized — pushes, merged-gradient returns (the
  second shot of a two-shot quantized all-reduce), and shard-update
  merged-grad hand-outs.
- WEIGHTS are never quantized: a weight re-rounded every step drifts;
  a gradient re-rounded once per step is one bounded unbiased
  perturbation.

Poison transparency: the training-run guardian rides the *dequantized*
values, so a non-finite contribution must survive the codec. A block
containing NaN/Inf keeps a non-finite scale with zeroed codes —
``0 * NaN = NaN`` / ``0 * Inf = NaN`` on decode poisons exactly that
block, and the server guard sees it (tests/unittest/test_quantize.py).

Everything is off by default behind ``MXNET_KV_QUANTIZE`` (unset/``0``
= full-precision wire, bit-exact — the zero-overhead contract). The
module is importable without jax (numpy core; the jnp helpers for the
XLA collective path import lazily) so light worker processes and the
jax-free elastic coordinator can use it.
"""
from __future__ import annotations

import os

import numpy as _np

from .base import MXNetError

__all__ = [
    "mode", "block_size", "rounding", "is_encoded", "encode", "decode",
    "encode_maybe", "wire_nbytes", "logical_nbytes", "rel_error_bound",
    "guard_norm_scale", "max_block_rel_error", "default_rng",
]

MODES = ("int8", "fp8")

# payload marker key: payloads are plain picklable dicts so they cross
# the elastic TCP protocol and coordinator snapshots unchanged
_WIRE_KEY = "__mxq__"

# int8 symmetric range: +/-127 (the -128 code is unused so the range is
# symmetric and scale derivation is a single maxabs)
_INT8_LEVELS = 127.0
# float8_e4m3 finite max (ml_dtypes.float8_e4m3fn)
_FP8_MAX = 448.0

_QUANTIZABLE = ("float32", "float16", "bfloat16")


def _env(name, default):
    return os.environ.get(name, default) or default


def mode():
    """The configured wire mode: ``None`` (full precision), ``'int8'``
    or ``'fp8'``. Read live per use (consistent with the other
    MXNET_KV_* knobs) so tests and late configuration work."""
    raw = os.environ.get("MXNET_KV_QUANTIZE", "").strip().lower()
    if raw in ("", "0", "false", "off", "no"):
        return None
    if raw in ("1", "true", "on", "yes"):
        return "int8"  # bare enable picks the production default
    if raw not in MODES:
        raise MXNetError(
            "MXNET_KV_QUANTIZE must be one of %s (or 0/unset), got %r"
            % (MODES, raw))
    return raw


def block_size():
    """Elements per scale block (default 1024 — ISSUE 7's ~1024-elem
    blocks: 0.4%% scale overhead at 4 bytes per 1024 codes)."""
    return max(8, int(_env("MXNET_KV_QUANTIZE_BLOCK", "1024")))


def rounding():
    """``'stochastic'`` (default: unbiased dither) or ``'nearest'``
    (cheaper, biased within half a quantum). fp8 casts round to
    nearest regardless — the e4m3 mantissa has no cheap dither."""
    r = _env("MXNET_KV_QUANTIZE_ROUND", "stochastic").strip().lower()
    if r not in ("stochastic", "nearest"):
        raise MXNetError(
            "MXNET_KV_QUANTIZE_ROUND must be stochastic|nearest, got %r" % r)
    return r


def min_bytes():
    """Values smaller than this stay full-precision: a 64-float bias
    padded to one 1024-code block plus a scale would GROW on the wire."""
    return int(_env("MXNET_KV_QUANTIZE_MIN_BYTES", "4096"))


def default_rng(rank=0):
    """Deterministic per-rank dither stream (chaos-bisect contract:
    same seed, same codes). MXNET_KV_QUANTIZE_SEED offsets the base.
    SFC64, not the default PCG64: the dither burns one uniform draw
    per gradient element on the push hot path, SFC64 generates floats
    ~2x faster, and statistical quality far beyond a dither's needs."""
    seed = int(_env("MXNET_KV_QUANTIZE_SEED", "0"))
    return _np.random.Generator(_np.random.SFC64(
        int(_np.uint64(0x9E3779B9) * _np.uint64(rank + 1)
            + _np.uint64(seed))))


def is_encoded(obj):
    return isinstance(obj, dict) and _WIRE_KEY in obj


def logical_nbytes(payload_or_arr):
    """Full-precision bytes the value represents (fp32-equivalent for
    the compression-ratio accounting)."""
    if is_encoded(payload_or_arr):
        n = 1
        for d in payload_or_arr["shape"]:
            n *= d
        return n * _np.dtype(payload_or_arr["dtype"]).itemsize
    return payload_or_arr.size * payload_or_arr.dtype.itemsize


def wire_nbytes(payload_or_arr):
    """Bytes the value actually occupies on the wire."""
    if is_encoded(payload_or_arr):
        return (payload_or_arr["q"].nbytes + payload_or_arr["scale"].nbytes)
    return payload_or_arr.size * payload_or_arr.dtype.itemsize


def rel_error_bound(mode_=None):
    """Worst-case per-element error relative to the block's maxabs.
    int8: one quantum is maxabs/127 — stochastic rounding errs up to a
    full quantum, nearest up to half. fp8 e4m3: 3 mantissa bits, unit
    roundoff 2^-4. 0.0 when quantization is off."""
    m = mode() if mode_ is None else mode_
    if m is None:
        return 0.0
    if m == "int8":
        return (1.0 if rounding() == "stochastic" else 0.5) / _INT8_LEVELS
    return 2.0 ** -4  # fp8 e4m3


def guard_norm_scale():
    """Inflation factor for the guardian's *absolute* norm bounds when
    quantization is on: a gradient at the bound must not trip the
    sentinel from quantization noise alone. Worst case the norm grows
    by the relative error bound per element; the margin (default 8)
    covers the gap between per-block and per-element normalization.
    1.0 when quantization is off (guardian thresholds unchanged)."""
    b = rel_error_bound()
    if b == 0.0:
        return 1.0
    margin = float(_env("MXNET_KV_QUANT_GUARD_MARGIN", "8"))
    return 1.0 + margin * b


def _block_view(flat, block):
    """(padded 2-D block view, pad) for a flat f32 array."""
    pad = (-flat.size) % block
    if pad:
        flat = _np.concatenate(
            [flat, _np.zeros(pad, dtype=flat.dtype)])
    return flat.reshape(-1, block), pad


def _scales(vb, levels):
    """Per-block scale = maxabs/levels. A block with any non-finite
    element gets a non-finite scale (NaN stays NaN; Inf maxabs stays
    Inf) — the poison-transparency contract."""
    with _np.errstate(invalid="ignore"):
        return (_np.max(_np.abs(vb), axis=1) / levels).astype(_np.float32)


def encode(arr, rng=None, rounding_=None, mode_=None, block=None):
    """Encode one numpy array as a low-precision wire payload dict.

    The payload is self-describing (mode, shape, dtype, pad) so mixed
    raw/encoded streams decode safely — on the ELASTIC transport a
    worker with quantization off talking to the same coordinator is a
    supported configuration. The XLA dist path has no such tolerance
    (the wire mode selects the SPMD program) and enforces group
    agreement instead (KVStore._check_wire_agreement)."""
    m = mode() if mode_ is None else mode_
    if m is None:
        raise MXNetError("quantize.encode called with quantization off")
    blk = block_size() if block is None else int(block)
    r = rounding() if rounding_ is None else rounding_
    src_dtype = str(arr.dtype)
    flat = _np.asarray(arr, dtype=_np.float32).reshape(-1)
    levels = _INT8_LEVELS if m == "int8" else _FP8_MAX
    vb, pad = _block_view(flat, blk)
    scale = _scales(vb, levels)
    # zero blocks (scale 0) and non-finite blocks (scale NaN/Inf) both
    # take inv 0: codes 0, and decode resurrects exact zeros / NaNs
    clean = bool(_np.isfinite(scale).all())
    with _np.errstate(divide="ignore", invalid="ignore"):
        inv = _np.where(scale > 0, 1.0 / scale, 0.0).astype(_np.float32)
        # non-finite elements times inv produce NaN here (silenced) and
        # are zeroed below; the block's scale carries the poison instead
        scaled = vb * inv[:, None]
    if m == "int8":
        if r == "stochastic":
            if rng is None:
                rng = default_rng()
            # in-place from here down: encode runs per push on the hot
            # gradient path, and each avoided 4-bytes/elem temporary is
            # a real slice of the round time on a CPU-bound host
            _np.add(scaled, rng.random(vb.shape, dtype=_np.float32),
                    out=scaled)
            _np.floor(scaled, out=scaled)
        else:
            _np.rint(scaled, out=scaled)
        if not clean:
            # non-finite elements (Inf * inv=0 -> NaN) must not reach
            # the int cast (UB); their block scale already carries the
            # poison. A finite-scale input cannot produce them — the
            # common case skips this scrub entirely.
            scaled = _np.where(_np.isfinite(scaled), scaled, 0.0)
        _np.clip(scaled, -_INT8_LEVELS, _INT8_LEVELS, out=scaled)
        q = scaled.astype(_np.int8)
    else:
        import ml_dtypes  # jax dependency, always present

        if not clean:
            scaled = _np.where(_np.isfinite(scaled), scaled, 0.0)
        q = scaled.astype(ml_dtypes.float8_e4m3fn)
    return {
        _WIRE_KEY: m, "q": q.reshape(-1), "scale": scale,
        "shape": tuple(arr.shape), "dtype": src_dtype, "pad": int(pad),
        "block": blk,
    }


def encode_maybe(arr, rng=None):
    """``encode(arr)`` when the configured mode applies to this value;
    ``None`` when it must stay full precision (quantization off,
    non-float dtype, or too small to win on the wire)."""
    m = mode()
    if m is None:
        return None
    if str(arr.dtype) not in _QUANTIZABLE:
        return None
    if arr.size * arr.dtype.itemsize < min_bytes():
        return None
    return encode(arr, rng=rng, mode_=m)


def decode(payload, dtype=None):
    """Decode a wire payload back to a dense array (the dequantized
    values the guardian and the optimizer ride)."""
    if not is_encoded(payload):
        return payload
    blk = int(payload["block"])
    q = payload["q"].reshape(-1, blk).astype(_np.float32)
    with _np.errstate(invalid="ignore"):
        # in-place: decode runs per contribution on the server's hot
        # path — q is our own fresh temporary, safe to scale in place
        _np.multiply(q, payload["scale"][:, None], out=q)
    out = q.reshape(-1)
    pad = int(payload["pad"])
    if pad:
        out = out[:-pad]
    out_dtype = payload["dtype"] if dtype is None else dtype
    return out.reshape(payload["shape"]).astype(out_dtype, copy=False)


def max_block_rel_error(arr, payload):
    """Max over blocks of (max |decode - x| within the block) relative
    to the block's maxabs — the ``kvstore.quant_error`` gauge. Blocks
    that are all-zero or non-finite are excluded (no meaningful
    denominator)."""
    flat = _np.asarray(arr, dtype=_np.float32).reshape(-1)
    deq = _np.asarray(
        decode(payload, dtype=_np.float32), dtype=_np.float32).reshape(-1)
    vb, _ = _block_view(flat, int(payload["block"]))
    db, _ = _block_view(deq, int(payload["block"]))
    maxabs = _np.max(_np.abs(vb), axis=1)
    ok = _np.isfinite(maxabs) & (maxabs > 0)
    if not _np.any(ok):
        return 0.0
    err = _np.max(_np.abs(db - vb), axis=1)
    return float(_np.max(err[ok] / maxabs[ok]))


# -- jnp helpers (device-side, for the XLA collective path) --------------------

def jnp_block_quant(x, key=None, levels=_INT8_LEVELS, block=None):
    """Device-side per-block int8 quantization of a flat f32 array whose
    size is a multiple of the block. Returns (codes int8, scales f32).
    ``key`` enables stochastic rounding (jax PRNG); None rounds to
    nearest. Non-finite blocks poison through their scale, exactly like
    the numpy codec."""
    import jax
    import jax.numpy as jnp

    blk = block_size() if block is None else int(block)
    vb = x.reshape(-1, blk)
    scale = jnp.max(jnp.abs(vb), axis=1, keepdims=True) / levels
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale == 0, 1.0, scale), 0.0)
    scaled = vb * inv
    if key is not None:
        scaled = jnp.floor(scaled + jax.random.uniform(key, vb.shape))
    else:
        scaled = jnp.rint(scaled)
    scaled = jnp.where(jnp.isfinite(scaled), scaled, 0.0)
    q = jnp.clip(scaled, -levels, levels).astype(jnp.int8)
    return q.reshape(x.shape), scale.reshape(-1).astype(jnp.float32)


def jnp_block_dequant(q, scale, block=None):
    """Inverse of :func:`jnp_block_quant` (f32 out; poisoned scales
    propagate as NaN)."""
    import jax.numpy as jnp

    blk = block_size() if block is None else int(block)
    vb = q.reshape(-1, blk).astype(jnp.float32)
    return (vb * scale.reshape(-1, 1)).reshape(q.shape)


def make_quantized_allreduce(mesh, axis, nper, block=None, stochastic=False):
    """Two-shot quantized mean-all-reduce over one mesh axis, the
    EQuARX structure: quantize -> all_to_all (the reduce-scatter shot)
    -> local dequant-sum -> requantize -> all_gather (the broadcast
    shot) -> dequant. Wire bytes per device per call:
    ``2*(n-1)/n * (nper/4 + 4*nper/block)`` versus the fp32 ring's
    ``2*(n-1)/n * 4*nper`` — a ~0.25x wire ratio for block 1024.

    ``nper`` is the per-device element count and must be divisible by
    ``n * block``. Returns a jitted fn ``(x, key) -> mean`` over
    arrays of global shape ``(n, nper)`` sharded on ``axis``; ``key``
    is ignored unless ``stochastic``. Used by tools/bandwidth/measure.py
    (the XLA int8 leg) and available to multi-process dist stores."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    blk = block_size() if block is None else int(block)
    n = mesh.shape[axis]
    if nper % (n * blk):
        raise MXNetError(
            "quantized allreduce needs per-device elements (%d) divisible "
            "by world*block (%d*%d)" % (nper, n, blk))

    def body(x, key):
        x = x.reshape(-1)
        if stochastic:
            key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
            k1, k2 = jax.random.split(key)
        else:
            k1 = k2 = None
        xs = x.reshape(n, nper // n)
        q, s = jnp_block_quant(xs, key=k1, block=blk)
        qt = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
        st = jax.lax.all_to_all(
            s.reshape(n, -1), axis, split_axis=0, concat_axis=0)
        partial = jnp_block_dequant(
            qt.reshape(n, nper // n), st.reshape(-1), block=blk).sum(0) / n
        q2, s2 = jnp_block_quant(partial, key=k2, block=blk)
        qg = jax.lax.all_gather(q2, axis)
        sg = jax.lax.all_gather(s2, axis)
        return jnp_block_dequant(
            qg.reshape(-1), sg.reshape(-1), block=blk).reshape(1, nper)

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis, None), P(None)),
                   out_specs=P(axis, None))
    return jax.jit(fn)
