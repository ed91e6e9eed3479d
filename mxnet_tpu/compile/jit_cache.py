"""Persistent compilation cache: jit builds survive process restarts.

Every process pays every XLA compile from scratch — mxtel's
``executor.jit_builds_total`` counts them, and for a serving cold start
or a chip call they ARE the latency floor. jax's persistent compilation
cache keeps compiled executables on disk keyed by their HLO + compile
options, so the next process that builds the same program LOADS instead
of compiling. This module does not place that cache; it makes the most
of one that is placed:

Placement: ``JAX_COMPILATION_CACHE_DIR`` says where, and jax's own
wiring is then the only thing that places it — nothing here overrides
the directory or keys a sub-directory under it (the path is part of the
cache key, so a directory that moves never hits). With the variable
unset the library runs without a cache; the repo's entry points
(``chip_smoke.py``, ``benchmark/run.py``, ``serving.fleet.replica``) call
:func:`enable`, which then places it at one fixed path inside the
checkout (:data:`REPO_CACHE_DIR`). The tuning database
(``compile/autotune.py``) lives beside the entries.

:func:`ensure` — called from every compile entry point (Executor, the
scanned trainers, Predictor, the serving engine) — lifts jax's
size/time thresholds so the small programs a cold start is made of are
cached too, and wires the accounting below.

Robustness: a truncated or bit-flipped cache entry must cost a
recompile, never a crash. jax's own read path already demotes
undecodable entries to a miss (``_cache_read`` catches and warns);
``verify_cache_dir`` goes further and sweeps the directory at ensure()
time, deleting entries whose compressed payload no longer decodes and
counting them via ``compile.cache_corrupt_total`` — so one poisoned
entry costs exactly one recompile and disappears. (The frames carry no
content checksum: the sweep catches truncation and damage to a frame's
structure; a flip inside a literal run still decodes and is left to
jax's own fallback.) An entry is decoded ONCE, not once per process: the
sweep keeps the size and modification time of every entry it has decoded
in ``.verified`` beside the entries and skips an entry that still reads
the same; nothing is stamped that was not decoded, so what a process
writes is decoded by the next one to start. Before that, every start paid
for reading and decompressing every program any run had ever left in the
directory (76 MB/s: 0.2 s for 15 MB, seconds for a checkout that holds a
few models' steps), which grew every entry point's start-up with every
model added beside it.

Hit/miss accounting rides jax's monitoring events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``) into both
mxtel counters (``compile.cache_hits_total`` / ``misses_total``) and
module-level plain ints readable without telemetry (chip_smoke.py and
the benchmark's ``compile_misses`` read them from a bare process).
"""
from __future__ import annotations

import json
import os
import zlib

from .. import telemetry as _tel

__all__ = ["enable", "ensure", "verify_cache_dir", "cache_dir", "stats",
           "REPO_CACHE_DIR"]

#: where :func:`enable` puts the cache when JAX_COMPILATION_CACHE_DIR is
#: unset: fixed, inside the checkout, listed in .gitignore
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: process-lifetime counters (mirrors of the mxtel counters; plain ints
#: so subprocesses can report them without enabling telemetry)
HITS = 0
MISSES = 0
CORRUPT = 0

_ensured_dir = None
_listener_on = False

#: beside the entries: {entry name: [size, mtime_ns]} as last found sound
_STAMPS = ".verified"


def cache_dir():
    """The directory jax's persistent cache is using, or None (off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def enable(path=REPO_CACHE_DIR):
    """Turn the persistent cache on for this process; entry points call
    it before their first compile. Where ``JAX_COMPILATION_CACHE_DIR``
    is set jax has already placed the cache and ``path`` is ignored.
    Returns the active directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return ensure()


def stats():
    return {"hits": HITS, "misses": MISSES, "corrupt": CORRUPT}


def _on_event(event, **kwargs):
    global HITS, MISSES
    if event == "/jax/compilation_cache/cache_hits":
        HITS += 1
        if _tel.ENABLED:
            _tel.counter("compile.cache_hits_total").inc()
    elif event == "/jax/compilation_cache/cache_misses":
        MISSES += 1
        if _tel.ENABLED:
            _tel.counter("compile.cache_misses_total").inc()


def _register_listener():
    global _listener_on
    if _listener_on:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    _listener_on = True


def _decompress_ok(payload):
    """True iff a cache entry's payload decodes with the compressor jax
    writes with (zstandard when installed, zlib otherwise — mirror of
    compilation_cache.compress_executable)."""
    try:
        import zstandard
    except ImportError:
        zstandard = None
    try:
        if zstandard is not None:
            zstandard.ZstdDecompressor().decompress(
                payload, max_output_size=1 << 31)
        else:
            zlib.decompress(payload)
        return True
    except Exception:
        return False


def _read_stamps(path):
    try:
        with open(os.path.join(path, _STAMPS)) as f:
            stamps = json.load(f)
        return stamps if isinstance(stamps, dict) else {}
    except (OSError, ValueError):
        return {}


def _write_stamps(path, stamps):
    """Atomically; a lost race with another process costs one more decode."""
    tmp = os.path.join(path, "%s.%d" % (_STAMPS, os.getpid()))
    try:
        with open(tmp, "w") as f:
            json.dump(stamps, f)
        os.replace(tmp, os.path.join(path, _STAMPS))
    except OSError:
        pass


def _stamp_of(fpath):
    st = os.stat(fpath)
    return [st.st_size, st.st_mtime_ns]


def verify_cache_dir(path):
    """Sweep ``path`` for undecodable ``*-cache`` entries; delete them
    (recompile beats crash-or-warn-forever) and count each via
    ``compile.cache_corrupt_total``. An entry whose size and modification
    time are as ``.verified`` has them was decoded before and is skipped.
    Returns (n_decoded, n_removed)."""
    global CORRUPT
    checked = removed = 0
    try:
        names = os.listdir(path)
    except OSError:
        return 0, 0
    stamps = _read_stamps(path)
    sound = {}
    for name in names:
        if not name.endswith("-cache"):
            continue
        fpath = os.path.join(path, name)
        try:
            stamp = _stamp_of(fpath)
        except OSError:
            continue
        if stamps.get(name) == stamp:
            sound[name] = stamp
            continue
        checked += 1
        try:
            with open(fpath, "rb") as f:
                payload = f.read()
            ok = _decompress_ok(payload)
        except OSError:
            ok = False
        if ok:
            sound[name] = stamp
        else:
            removed += 1
            CORRUPT += 1
            if _tel.ENABLED:
                _tel.counter("compile.cache_corrupt_total").inc()
            try:
                os.remove(fpath)
                # the atime sidecar of a removed entry is dead weight
                sidecar = fpath[:-len("-cache")] + "-atime"
                if os.path.exists(sidecar):
                    os.remove(sidecar)
            except OSError:
                pass
    if sound != stamps:
        _write_stamps(path, sound)
    return checked, removed


def ensure():
    """Make the most of a persistent cache that is placed (by
    ``JAX_COMPILATION_CACHE_DIR`` or :func:`enable`): sweep it for
    corrupt entries, cache small and fast-to-build programs too, count
    hits and misses. Returns the active directory, or None when the
    cache is off. Called from every compile entry point (Executor bind,
    the scanned trainers, Predictor, the serving engine) — after the
    first call it is one string compare."""
    global _ensured_dir
    path = cache_dir()
    if path is None or path == _ensured_dir:
        return path
    os.makedirs(path, exist_ok=True)
    verify_cache_dir(path)
    import jax

    # default thresholds skip exactly the small fast-to-build programs
    # a cold start is made of; cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _register_listener()
    _ensured_dir = path
    return path
