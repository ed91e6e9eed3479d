"""KVStore: key-value synchronization of parameters across devices/hosts.

TPU-native redesign of the reference KVStore stack (ref:
include/mxnet/kvstore.h:26-303, src/kvstore/kvstore_local.h:22-127,
src/kvstore/comm.h, kvstore_dist.h, python/mxnet/kvstore.py:1-379).

Semantics preserved exactly (validated by tests mirroring
tests/python/unittest/test_kvstore.py):
- init: store value per key (duplicate init faults)
- push: group by key, REDUCE (sum) the per-device values, then
  ``local = merged`` when no updater, else ``updater(key, merged, local)``
  (ref: kvstore_local.h:58-73)
- pull: broadcast stored value into every destination array
- set_optimizer: installs optimizer.get_updater — the analog of shipping
  the pickled optimizer to the server (ref: python/mxnet/kvstore.py:231)

Transport redesign (SURVEY §5.8): the reference staged reductions through
pinned CPU (CommCPU) or CUDA P2P (CommDevice), and crossed hosts via
ps-lite/ZMQ. On TPU, in-process multi-device reduce is a jnp sum over
device-committed arrays (XLA issues ICI transfers); cross-host types
('dist_sync'/'dist_async') report rank/size from jax.distributed and reduce
over all processes via a psum on a global mesh when multi-process — on a
single process they degrade to local semantics, matching how the reference
behaves when DMLC_ROLE is unset (kvstore.h:173).
"""
from __future__ import annotations

import os
import pickle
import threading
import time
import warnings

import numpy as _np

from . import quantize as _quant
from . import telemetry as _tel
from .base import MXNetError
from .context import cpu
from .ndarray import NDArray
from .resilience import faults as _faults
from .resilience.retry import DeadlineExceeded, RetryPolicy, run_with_deadline

__all__ = ["KVStore", "create"]


# one shared policy per MXNET_KV_RETRIES value: _coord_call sits on
# fence/pull polling paths, and rebuilding a policy (Random() init,
# env parse) per RPC is pure churn — the policy is configuration, its
# RNG only feeds jitter (benign under concurrent use)
_COORD_POLICIES = {}


def _coord_call(fn, what="kv-coordinator op"):
    """Run one coordination-service RPC under the resilience discipline:
    the ``kv.coord`` injection point, then MXNET_KV_RETRIES attempts of
    exponential backoff with jitter. A transient coordinator hiccup (an
    expected event on a busy multi-host job, SURVEY §5.8) heals here
    instead of failing the train step; a persistent outage still
    surfaces after the attempt budget. Retries log via RetryPolicy's
    default warning, which names `what` through the wrapper."""
    def _op():
        _faults.point("kv.coord")
        return fn()

    _op.__name__ = what
    attempts = max(1, int(os.environ.get("MXNET_KV_RETRIES", "4")))
    policy = _COORD_POLICIES.get(attempts)
    if policy is None:
        policy = _COORD_POLICIES[attempts] = RetryPolicy(
            max_attempts=attempts, base_delay=0.05, max_delay=1.0,
            jitter=0.25)
    return policy.call(_op)


def _ctypes_key(key):
    return key


def _nd_bytes(arr):
    """Payload size of one NDArray/numpy value (telemetry byte counters)."""
    return int(_np.prod(arr.shape)) * _np.dtype(arr.dtype).itemsize


def _pull_wait():
    """Long-poll budget forwarded with elastic pull/barrier_wait
    requests (lazy import: the elastic package loads only on the
    elastic code paths)."""
    from .elastic.client import _pull_wait as _pw

    return _pw()


def _shard_update_on():
    """MXNET_KV_SHARD_UPDATE: cross-replica sharding of the weight
    update (ZeRO-1, arXiv 2004.13336). Read live per use, like the
    other MXNET_KV_* knobs."""
    return os.environ.get("MXNET_KV_SHARD_UPDATE", "0").strip().lower() \
        not in ("", "0", "false", "off", "no")


# gradient dtypes that fuse into one f32 bucket: bf16/f16 keys are
# upcast into the fused buffer, so low-precision gradients get a full-
# precision accumulation (dequant-sum) instead of falling back to
# per-key collectives in their storage dtype
_FUSABLE_DTYPES = ("float32", "float16", "bfloat16")


class KVStore:
    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._barrier_count = 0
        self._start_heartbeat()

    # -- liveness (ref: ps-lite heartbeats, kvstore_dist.h:149-156) ------------
    def _start_heartbeat(self):
        """Publish a per-rank heartbeat through the jax.distributed
        coordinator's key-value store — the role ps-lite's Postoffice
        heartbeats played. Runs only for multi-process dist stores."""
        self._hb_client = None
        if not self.type.startswith("dist"):
            return
        import jax

        if jax.process_count() <= 1:
            return
        client = _coordination_client()
        if client is None:
            return
        self._hb_client = client
        self._hb_interval = float(
            os.environ.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "2"))
        self._hb_stop = threading.Event()
        rank = self.rank

        def _publish(ts):
            try:
                client.key_value_set("mxtpu_hb/%d" % rank, repr(ts),
                                     allow_overwrite=True)
                return True
            except TypeError:
                # client without allow_overwrite can only ever write the
                # key once — repeated beats would fail and a silent
                # beat-thread death reads as the whole cluster dying.
                # Degrade to no-heartbeat. Caught HERE, inside the
                # retried callable: a missing capability is definitive,
                # not a transient to burn the backoff budget on.
                return False

        def _set(ts):
            try:
                ok = _coord_call(lambda: _publish(ts),
                                 what="heartbeat publish")
            except Exception:
                return False
            if ok and _tel.ENABLED:
                _tel.counter("kvstore.heartbeat_publish_total").inc()
            return ok

        if not _set(time.time()):
            self._hb_client = None
            return

        # capture locals, not self: a closure over self would pin the
        # KVStore (and its device-resident _store) alive for the daemon
        # thread's whole life even after the user drops the store
        stop, interval = self._hb_stop, self._hb_interval

        def _beat():
            while not stop.wait(interval):
                # transient coordinator errors must not kill the beat
                # thread (a healthy rank would read as dead forever);
                # the capability probe already ran above, so just retry
                # on the next interval
                _set(time.time())

        self._hb_thread = threading.Thread(
            target=_beat, name="mxtpu-kvstore-heartbeat", daemon=True)
        self._hb_thread.start()
        # when the store is garbage-collected without an explicit
        # stop_heartbeat(), stop beating so a dead object can't keep
        # masquerading as a live rank
        import weakref

        weakref.finalize(self, stop.set)

    def stop_heartbeat(self):
        """Stop publishing this rank's liveness (test hook / shutdown)."""
        if getattr(self, "_hb_client", None) is not None:
            self._hb_stop.set()

    # -- identity --------------------------------------------------------------
    @property
    def rank(self):
        """ref: kvstore.py:286 / kvstore.h get_rank."""
        if self.type.startswith("dist"):
            import jax

            return jax.process_index()
        return 0

    @property
    def num_workers(self):
        """ref: kvstore.py:298 / kvstore.h get_group_size."""
        if self.type.startswith("dist"):
            import jax

            return jax.process_count()
        return 1

    # -- init/push/pull --------------------------------------------------------
    def init(self, key, value):
        """ref: python/mxnet/kvstore.py:55."""
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                raise MXNetError("duplicate init of key %s" % k)
            self._store[k] = v.copyto(v.context)

    def push(self, key, value, priority=0):
        """ref: python/mxnet/kvstore.py:102; semantics of kvstore_local.h:49.

        Dist push is BUCKETED: local per-key merges happen first, then
        all keys of the push cross the network in O(#buckets) fused
        collectives instead of O(#keys) tiny ones — the role of the
        reference's big-array striping + batched sends
        (kvstore_dist.h:260-300), redesigned for the all-reduce path."""
        keys, values = self._key_value(key, value, allow_list_per_key=True)
        grouped = {}
        order = []
        for k, v in zip(keys, values):
            if k not in grouped:
                grouped[k] = []
                order.append(k)
            if isinstance(v, (list, tuple)):
                grouped[k].extend(v)
            else:
                grouped[k].append(v)
        merged_list = []
        for k in order:
            vals = grouped[k]
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            merged_list.append(self._reduce(vals, self._store[k]))
        if _tel.ENABLED:
            _tel.counter("kvstore.push_total").inc()
            _tel.counter("kvstore.push_bytes_total").inc(
                sum(_nd_bytes(m) for m in merged_list))
        merged_list = self._global_reduce_many(merged_list)
        shard = self._updater is not None and self._shard_active()
        if shard:
            self._ensure_shard_map()
        for k, merged in zip(order, merged_list):
            if self._updater is not None:
                if shard and self._shard_map.get(k) != self.rank:
                    # another rank owns this key's optimizer update;
                    # its weight arrives in the all-gather below
                    continue
                stored = self._store[k]
                if stored.context != merged.context:
                    # the stored weight follows the merged gradient to
                    # its device and stays there (ref kvstore_local.h
                    # Push: "local = local.Copy(merged.ctx())") — init()
                    # keeps it where the caller's parameters were, the
                    # host, while gradients are reduced on the first
                    # pushing device
                    stored = self._store[k] = stored.copyto(merged.context)
                self._updater(_key_int(k), merged, stored)
            else:
                self._store[k] = merged
        if shard:
            self._shard_allgather(order)

    def pull(self, key, out=None, priority=0):
        """ref: python/mxnet/kvstore.py:168."""
        assert out is not None
        keys, outs = self._key_value(key, out, allow_list_per_key=True)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                self._store[k].copyto(t)
        if _tel.ENABLED:
            _tel.counter("kvstore.pull_total").inc()
            _tel.counter("kvstore.pull_bytes_total").inc(sum(
                _nd_bytes(self._store[k])
                * (len(o) if isinstance(o, (list, tuple)) else 1)
                for k, o in zip(keys, outs)))

    def _reduce(self, vals, stored):
        """Sum values (possibly on different devices) onto the first value's
        device — the CommDevice/CommCPU reduce (ref: src/kvstore/comm.h)."""
        import jax

        if len(vals) == 1:
            merged = vals[0]
            return NDArray(vals[0]._data, vals[0].context)
        dev = vals[0].context
        acc = vals[0]._data
        for v in vals[1:]:
            acc = acc + jax.device_put(v._data, dev.jax_device)
        return NDArray(acc, dev)

    def _global_reduce(self, merged):
        """Cross-process sum for dist types — the DCN/ICI all-reduce that
        replaces the ps-lite server aggregation (ref: sync server merge,
        kvstore_dist_server.h:164-198; SURVEY §5.8). Every worker pushes
        the same keys in the same order (SPMD), the reduced value is
        replicated, and the updater runs identically in each process —
        the 'server' role distributed onto all workers.

        Implementation: each process contributes its copy as one shard of
        a process-axis global array; a jitted sum with replicated output
        sharding lowers to a real XLA all-reduce over DCN/ICI — 1x data
        movement, reduction on device (not an N-replica host gather)."""
        if not self.type.startswith("dist"):
            return merged
        import jax

        if jax.process_count() <= 1:
            return merged
        self._ensure_proc_mesh()
        # zero host round trips: place the local contribution on this
        # process's mesh device, assemble the global array shard-wise,
        # reduce on device, wrap the replicated local shard directly
        local = jax.device_put(merged._data[None, ...], self._local_mesh_dev)
        garr = jax.make_array_from_single_device_arrays(
            (jax.process_count(),) + tuple(merged._data.shape),
            self._proc_sharding, [local])
        summed = self._reduce_fn(garr)
        # bring the replicated shard back to the pushing context's device
        # (device-to-device; the mesh device may differ from e.g. cpu(0))
        out = jax.device_put(summed.addressable_data(0),
                             merged.context.jax_device)
        return NDArray(out, merged.context)

    def _ensure_proc_mesh(self):
        """One-device-per-process mesh shared by the fp32 reduce, the
        quantized reduce and the shard-update weight all-gather."""
        if hasattr(self, "_proc_mesh"):
            return
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        # one device per process carries that process's contribution
        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        devs = [by_proc[p] for p in sorted(by_proc)]
        self._proc_mesh = Mesh(_np.array(devs), ("p",))
        self._proc_sharding = NamedSharding(self._proc_mesh, P("p"))
        self._local_mesh_dev = by_proc[jax.process_index()]
        self._reduce_fn = jax.jit(
            lambda x: x.sum(axis=0),
            out_shardings=NamedSharding(self._proc_mesh, P()))
        self._qreduce_fns = {}

    def _check_wire_agreement(self):
        """One-time group-agreement check for ``MXNET_KV_QUANTIZE`` on
        the XLA dist path. The elastic TCP transport tolerates mixed
        codec settings (payloads are self-describing), but here the
        wire mode selects the SPMD program: a rank entering the
        quantized reduce while another runs the plain f32 sum executes
        divergent computations over the shared process mesh and
        deadlocks inside XLA. Same loud-failure contract as the shard
        flag and the async transport decision: rank 0 publishes its
        mode through the coordination KV, everyone else must match or
        raise."""
        if getattr(self, "_wire_checked", False):
            return
        self._wire_checked = True
        client = _coordination_client()
        if client is None:
            return
        import jax

        global _WIRE_AGREE_COUNT
        _WIRE_AGREE_COUNT += 1
        mode = _quant.mode() or "off"
        # the counter keeps the key fresh per store (creation order is
        # SPMD-consistent, like the async transport decision)
        key = "mxtpu_q/wire/%d" % _WIRE_AGREE_COUNT
        if jax.process_index() == 0:
            client.key_value_set(key, mode)
            return
        v = client.blocking_key_value_get(key, 60_000)
        if v != mode:
            raise MXNetError(
                "MXNET_KV_QUANTIZE mismatch: rank %d has %r but rank 0 "
                "published %r — the quantized and plain reduces are "
                "different SPMD programs and would deadlock; export the "
                "same value on every worker "
                "(docs/how_to/low_precision_comms.md)"
                % (jax.process_index(), mode, v))

    def _global_reduce_quant(self, merged):
        """Quantized cross-process reduce of one flat f32 bucket
        (``MXNET_KV_QUANTIZE``): quantize the local contribution to
        int8 codes + per-block f32 scales on device, assemble the
        global (world, ...) code/scale arrays, and jit a dequant-sum
        with replicated output — only the 1-byte codes and the ~0.4%%
        scales cross DCN/ICI, and the accumulation runs in f32 on the
        dequantized values (the guardian's contract). The fp8 wire
        mode applies to the host/elastic transport; on the XLA
        collective path it falls back to these int8 codes
        (docs/how_to/low_precision_comms.md)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._ensure_proc_mesh()
        blk = _quant.block_size()
        flat = merged._data.ravel()
        n = int(flat.shape[0])
        pad = (-n) % blk
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        key = None
        if _quant.rounding() == "stochastic":
            if not hasattr(self, "_quant_base_key"):
                seed = int(os.environ.get("MXNET_KV_QUANTIZE_SEED", "0"))
                self._quant_base_key = jax.random.PRNGKey(
                    seed * 1000003 + self.rank)
                self._quant_step = 0
            self._quant_step += 1
            key = jax.random.fold_in(self._quant_base_key, self._quant_step)
        q, scales = _quant.jnp_block_quant(flat, key=key, block=blk)
        nproc = self._proc_mesh.shape["p"]
        qloc = jax.device_put(q[None, ...], self._local_mesh_dev)
        sloc = jax.device_put(scales[None, ...], self._local_mesh_dev)
        qg = jax.make_array_from_single_device_arrays(
            (nproc,) + tuple(q.shape), self._proc_sharding, [qloc])
        sg = jax.make_array_from_single_device_arrays(
            (nproc,) + tuple(scales.shape), self._proc_sharding, [sloc])
        fn = self._qreduce_fns.get((int(q.shape[0]), blk))
        if fn is None:
            def _dequant_sum(codes, scl):
                deq = codes.reshape(nproc, -1, blk).astype(jnp.float32) \
                    * scl.reshape(nproc, -1, 1)
                return deq.sum(axis=0).reshape(-1)

            fn = jax.jit(_dequant_sum, out_shardings=NamedSharding(
                self._proc_mesh, P()))
            self._qreduce_fns[(int(q.shape[0]), blk)] = fn
        summed = fn(qg, sg)
        out = jax.device_put(summed.addressable_data(0)[:n],
                             merged.context.jax_device)
        return NDArray(out, merged.context)

    @property
    def _BUCKET_BYTES(self):
        """Gradient bucket size for fused dist collectives; mirrors the
        role (inverted) of MXNET_KVSTORE_BIGARRAY_BOUND (comm.h:50).
        Read per use so setting the env var after import still works
        (consistent with MXNET_KVSTORE_HEARTBEAT_INTERVAL)."""
        return int(os.environ.get("MXNET_KVSTORE_BUCKET_BYTES",
                                  64 * 1024 * 1024))

    def _global_reduce_many(self, merged_list, wire_ok=True):
        """Bucketed cross-process reduce: flatten+concat the push's keys
        into ~_BUCKET_BYTES device buffers, one all-reduce per bucket,
        split back. A ResNet push goes from hundreds of small DCN
        collectives to a handful of fused ones.

        float32/float16/bfloat16 keys sharing a context fuse — the
        fused buffer is ALWAYS f32 (and _BUCKET_BYTES is accounted in
        the f32 upcast bytes it will actually allocate), so
        mixed-precision pushes get a full-precision accumulation
        (dequant-sum) and cast back to their storage dtype instead of
        falling back to per-key collectives. Integer/f64
        keys keep the per-key path — fusing would reduce in the wrong
        dtype (int32 sums past 2^24, f64 precision).

        With ``MXNET_KV_QUANTIZE`` set (and ``wire_ok``), each fused
        bucket crosses the wire as int8 codes + per-block scales
        through :meth:`_global_reduce_quant`. ``wire_ok=False`` marks
        WEIGHT traffic (the shard-update all-gather), which is never
        quantized."""
        if not self.type.startswith("dist"):
            return merged_list
        import jax

        if jax.process_count() <= 1:
            return merged_list
        import jax.numpy as jnp

        self._check_wire_agreement()
        quant_on = wire_ok and _quant.mode() is not None
        if len(merged_list) == 1 and not quant_on and \
                merged_list[0].dtype == _np.float32:
            return [self._global_reduce(merged_list[0])]

        out = [None] * len(merged_list)
        groups = {}  # (device_key,) -> [idx]
        for idx, m in enumerate(merged_list):
            if str(m.dtype) in _FUSABLE_DTYPES:
                groups.setdefault(str(m.context), []).append(idx)
            else:
                out[idx] = self._global_reduce(m)

        bucket_bytes = self._BUCKET_BYTES  # one env read per push, not per key
        wire_bytes = logical_bytes = 0
        for idxs in groups.values():
            buckets = []
            cur, cur_bytes = [], 0
            for idx in idxs:
                m = merged_list[idx]
                # capacity is the FUSED buffer's bytes: the bucket
                # concatenates in f32 whatever the storage dtype, so a
                # bf16 key costs 4 bytes/elem here — sizing by storage
                # itemsize would let two half-precision buckets
                # allocate 2x _BUCKET_BYTES on device
                nbytes = int(_np.prod(m.shape)) * 4
                if cur and cur_bytes + nbytes > bucket_bytes:
                    buckets.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(idx)
                cur_bytes += nbytes
            if cur:
                buckets.append(cur)
            for bucket in buckets:
                parts = [merged_list[i] for i in bucket]
                if len(bucket) == 1 and not quant_on and \
                        parts[0].dtype == _np.float32:
                    out[bucket[0]] = self._global_reduce(parts[0])
                    continue
                ctx = parts[0].context
                flat = jnp.concatenate(
                    [p._data.astype(jnp.float32).ravel() for p in parts])
                nd_flat = NDArray(flat, ctx)
                if quant_on:
                    fused = self._global_reduce_quant(nd_flat)
                    if _tel.ENABLED and wire_ok:
                        n = int(flat.shape[0])
                        blk = _quant.block_size()
                        npad = n + ((-n) % blk)
                        logical_bytes += n * 4
                        wire_bytes += npad + 4 * (npad // blk)
                else:
                    fused = self._global_reduce(nd_flat)
                    if _tel.ENABLED and wire_ok:
                        logical_bytes += int(flat.shape[0]) * 4
                        wire_bytes += int(flat.shape[0]) * 4
                off = 0
                for i, p in zip(bucket, parts):
                    n = int(_np.prod(p.shape))
                    piece = fused._data[off:off + n].reshape(p.shape)
                    if p.dtype != _np.float32:
                        piece = piece.astype(p._data.dtype)
                    out[i] = NDArray(piece, p.context)
                    off += n
        if _tel.ENABLED and logical_bytes:
            self._account_wire(wire_bytes, logical_bytes)
        return out

    def _account_wire(self, wire, logical, quant_err=None):
        """Fold one transfer into the compression accounting: the
        ``kvstore.wire_bytes_total`` / ``kvstore.logical_bytes_total``
        counters, the running compression-ratio gauge, and (host paths
        only, where it is already computed) the max per-block relative
        quantization error gauge."""
        self._wire_total = getattr(self, "_wire_total", 0) + int(wire)
        self._logical_total = getattr(self, "_logical_total", 0) + \
            int(logical)
        _tel.counter("kvstore.wire_bytes_total").inc(int(wire))
        _tel.counter("kvstore.logical_bytes_total").inc(int(logical))
        _tel.gauge("kvstore.compression_ratio").set(
            self._wire_total / float(self._logical_total))
        if quant_err is not None:
            self._quant_err_max = max(
                getattr(self, "_quant_err_max", 0.0), float(quant_err))
            _tel.gauge("kvstore.quant_error").set(self._quant_err_max)

    # -- optimizer/updater -----------------------------------------------------
    def set_optimizer(self, optimizer):
        """ref: python/mxnet/kvstore.py:231 — on dist the reference pickles
        the optimizer to the server process; here the updater runs in-process
        over the reduced gradient (round-trip through pickle kept so custom
        optimizers fail early if unpicklable, like the reference).

        With ``MXNET_KV_SHARD_UPDATE=1`` on a multi-process dist store,
        ``push`` runs this updater only for the keys this rank OWNS
        (greedy byte-balanced partition) and all-gathers the updated
        weights — optimizer state (momenta etc.) is created lazily per
        updated key, so per-rank state memory scales ~1/world (ZeRO-1,
        docs/how_to/low_precision_comms.md)."""
        from . import optimizer as opt

        pickle.loads(pickle.dumps(optimizer))
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    def _set_updater(self, updater):
        """ref: python/mxnet/kvstore.py:255 _set_updater. A custom
        updater participates in MXNET_KV_SHARD_UPDATE the same way the
        optimizer-built one does: push consults key ownership before
        calling it."""
        self._updater = updater

    set_updater = _set_updater

    # -- cross-replica sharded weight update (ZeRO-1) --------------------------
    def _shard_active(self):
        """Shard the optimizer update across ranks only when there is
        more than one process to shard across."""
        if not _shard_update_on() or not self.type.startswith("dist"):
            return False
        import jax

        return jax.process_count() > 1

    def _ensure_shard_map(self):
        """key->owner-rank partition over the current key set, greedy
        by bytes (largest first onto the least-loaded rank) — the same
        deterministic assignment on every rank, recomputed when keys
        are added."""
        keys = tuple(sorted(self._store, key=str))
        if getattr(self, "_shard_keys", None) == keys:
            return
        from .elastic.server import Aggregator  # jax-free, reused greedy

        self._shard_map = Aggregator.shard_map_for(
            {k: self._store[k]._data for k in keys},
            set(range(self.num_workers)))
        self._shard_keys = keys

    def _shard_allgather(self, keys):
        """Broadcast each key's updated weight from its owner: every
        rank contributes its weight for owned keys and zeros elsewhere,
        and the existing fused reduce (each key has exactly one nonzero
        contributor, so sum == owner's value, exactly in f32) assembles
        the full set — the all-gather half of the ZeRO-1 exchange.
        Weights are never quantized (``wire_ok=False``)."""
        import jax.numpy as jnp

        vals = []
        for k in keys:
            w = self._store[k]
            if self._shard_map.get(k) == self.rank:
                vals.append(w)
            else:
                vals.append(NDArray(jnp.zeros_like(w._data), w.context))
        gathered = self._global_reduce_many(vals, wire_ok=False)
        for k, g in zip(keys, gathered):
            self._store[k] = g
        if _tel.ENABLED:
            from . import optimizer as opt

            _tel.counter("kvstore.shard_weight_bytes_total").inc(
                sum(_nd_bytes(self._store[k]) for k in keys))
            _tel.gauge("kvstore.optimizer_state_bytes").set(
                opt.state_nbytes(self._updater))

    # -- cluster control -------------------------------------------------------
    def barrier(self):
        """ref: kvstore.h:190 Barrier. Multi-process dist: a real global
        rendezvous over jax.distributed; single-process: no-op. With
        ``MXNET_KV_BARRIER_TIMEOUT=<secs>`` set, a rendezvous that does
        not complete in time raises a diagnostic MXNetError naming the
        unresponsive ranks (via heartbeat ages) instead of hanging the
        healthy ranks forever."""
        self._barrier_count += 1
        if self.type.startswith("dist"):
            import jax

            if jax.process_count() > 1:
                if _tel.ENABLED:
                    t0 = time.monotonic()
                    try:
                        self._barrier_rendezvous()
                    finally:
                        _tel.histogram("kvstore.barrier_wait_secs").observe(
                            time.monotonic() - t0)
                else:
                    self._barrier_rendezvous()

    def _barrier_sync(self):
        """The blocking rendezvous body (separated so the deadline
        wrapper — and tests — can intercept it)."""
        from jax.experimental import multihost_utils

        _faults.point("kv.barrier")
        multihost_utils.sync_global_devices(
            "mxnet_kvstore_barrier_%d" % self._barrier_count)

    def _barrier_rendezvous(self):
        timeout = _barrier_timeout()
        if timeout <= 0:
            self._barrier_sync()
            return
        try:
            run_with_deadline(self._barrier_sync, timeout,
                              what="kvstore barrier #%d" % self._barrier_count)
        except DeadlineExceeded:
            hb_to = max(1.0, 3.0 * float(
                os.environ.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "2")))
            if getattr(self, "_hb_client", None) is None:
                who = "unknown (heartbeats unavailable)"
            else:
                dead = self.dead_ranks(timeout=hb_to)
                who = ("ranks %s (heartbeat older than %.0fs)"
                       % (sorted(dead), hb_to)) if dead else \
                    "none dead by heartbeat — likely a straggler or a " \
                    "rank that skipped this barrier"
            raise MXNetError(
                "kvstore barrier #%d timed out after %.1fs on rank %d of "
                "%d; unresponsive: %s (MXNET_KV_BARRIER_TIMEOUT; see "
                "docs/how_to/fault_tolerance.md)"
                % (self._barrier_count, timeout, self.rank,
                   self.num_workers, who))

    def send_command_to_servers(self, head, body):
        """ref: kvstore.py:318. No server processes exist on TPU; commands
        apply locally (matching single-process reference behavior). A
        controller installed by MXKVStoreRunServer takes precedence, as
        the reference's server-side controller would."""
        ctrl = getattr(self, "_server_controller", None)
        if ctrl is not None:
            ctrl(head, body)
            return
        if head == 0:  # kController optimizer command (body is a pickle)
            if isinstance(body, str):
                body = body.encode("latin-1")
            self.set_optimizer(pickle.loads(body))

    def get_num_dead_node(self, node_id=-1, timeout=60):
        """Count workers whose heartbeat is older than `timeout` seconds
        (ref: kvstore.h:235 get_num_dead_node, ps-lite heartbeats
        kvstore_dist.h:149-156). node_id is accepted for ABI parity; with
        no server/scheduler roles every node is a worker, so any id
        queries the whole group. Returns 0 for non-dist stores (no
        cluster, nothing can be dead — matches single-process reference
        behavior)."""
        return len(self.dead_ranks(node_id=node_id, timeout=timeout))

    def dead_ranks(self, node_id=-1, timeout=60):
        """The rank ids behind :meth:`get_num_dead_node`'s count — the
        barrier-timeout diagnostic needs *names*, not a number."""
        client = getattr(self, "_hb_client", None)
        if client is None:
            return []
        # Staleness is judged by VALUE CHANGE against the local clock,
        # not by comparing the sender's embedded wall time — cross-host
        # clock skew would otherwise fabricate dead/alive verdicts.
        now = time.monotonic()
        seen = getattr(self, "_hb_seen", None)
        if seen is None:
            seen = self._hb_seen = {}
        dead = []
        for r in range(self.num_workers):
            try:
                v = client.key_value_try_get("mxtpu_hb/%d" % r)
            except Exception:
                v = None
            # a missing key participates in the same timeout discipline:
            # a rank still starting up gets the full grace period before
            # being declared dead (no startup-race false positives)
            prev = seen.get(r)
            if prev is None:
                # First observation: change detection has no baseline yet,
                # so a one-shot health check (construct, query once) would
                # always report 0. Fall back to the sender-embedded wall
                # time for ranks that stopped beating long ago. The slack
                # absorbing cross-host clock skew has an absolute floor:
                # 2*timeout alone is no protection when timeout is small
                # (a 0.3s test interval would let sub-second skew
                # fabricate dead verdicts from the sender's clock). The
                # baseline is back-dated by the observed age so follow-up
                # polls keep reporting the rank dead (no alive-flap) until
                # its value actually changes.
                base = now
                try:
                    sent = float(v)
                except (TypeError, ValueError):
                    sent = None
                if sent is not None:
                    age = time.time() - sent
                    if age > max(2 * timeout, 30.0):
                        dead.append(r)
                        base = now - age
                seen[r] = (v, base)
            elif prev[0] != v:
                seen[r] = (v, now)  # state change observed locally
            elif now - prev[1] > timeout:
                dead.append(r)
        return dead

    def guardian_vote(self, step, poisoned):
        """Group skip verdict for one optimizer step (the training-run
        guardian's coordinated skip: docs/how_to/guardrails.md). True
        when ANY rank voted poisoned — every rank then skips the same
        step, so replicas never diverge. Single-process stores answer
        with the local verdict. The multi-process dist implementation
        rides the jax.distributed coordination KV under the usual
        ``kv.coord`` + retry discipline: publish this rank's vote, read
        everyone else's (votes are write-once per round, so reads are
        race-free)."""
        if not self.type.startswith("dist"):
            return bool(poisoned)
        import jax

        if jax.process_count() <= 1:
            return bool(poisoned)
        client = _coordination_client()
        if client is None:
            warnings.warn(
                "guardian_vote: no coordination client; falling back to "
                "the local verdict (ranks may diverge)", stacklevel=2)
            return bool(poisoned)
        self._guard_round = getattr(self, "_guard_round", 0) + 1
        base = "mxtpu_guard/%d" % self._guard_round
        # GC: the vote is a collective, so every rank reaching round R
        # has finished reading round R-1 — round R-2's keys are dead on
        # every rank and this rank can free its own (bounded KV growth:
        # at most 2 rounds x world keys live at any time). Best-effort:
        # a failed delete only delays the free to a later round.
        if self._guard_round > 2:
            try:
                client.key_value_delete(
                    "mxtpu_guard/%d/%d" % (self._guard_round - 2, self.rank))
            except Exception:
                pass
        _coord_call(
            lambda: client.key_value_set(
                "%s/%d" % (base, self.rank), "1" if poisoned else "0"),
            what="guardian vote publish")
        timeout_ms = int(max(_barrier_timeout() or 300.0, 1.0) * 1000)
        any_poisoned = bool(poisoned)
        for r in range(self.num_workers):
            if r == self.rank:
                continue
            try:
                v = client.blocking_key_value_get(
                    "%s/%d" % (base, r), timeout_ms)
            except Exception as e:
                raise MXNetError(
                    "guardian_vote: rank %d's vote for step %s unreadable "
                    "on rank %d (%s) — cannot skip consistently"
                    % (r, step, self.rank, e))
            any_poisoned = any_poisoned or v == "1"
        return any_poisoned

    @property
    def barrier_before_exit(self):
        """ref: kvstore.h:194 — settable via MXKVStoreSetBarrierBeforeExit."""
        return getattr(self, "_barrier_before_exit", True)

    def save_optimizer_states(self, fname):
        assert self._optimizer is not None
        with open(fname, "wb") as f:
            f.write(pickle.dumps(self._optimizer))

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            self.set_optimizer(pickle.loads(f.read()))

    # -- helpers ---------------------------------------------------------------
    def _key_value(self, key, value, allow_list_per_key=False):
        if isinstance(key, (int, str)):
            return [key], [value]
        assert isinstance(key, (list, tuple))
        if len(key) != len(value):
            raise MXNetError("mismatched key/value lengths")
        return list(key), list(value)


def _key_int(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _barrier_timeout():
    """MXNET_KV_BARRIER_TIMEOUT in seconds (0 = no deadline), validated
    once for both the collective and the elastic barrier paths."""
    raw = os.environ.get("MXNET_KV_BARRIER_TIMEOUT", "0") or "0"
    try:
        return float(raw)
    except ValueError:
        raise MXNetError(
            "MXNET_KV_BARRIER_TIMEOUT must be a number of seconds, "
            "got %r" % raw)


def create(name="local"):
    """Create a KVStore (ref: python/mxnet/kvstore.py:349, factory
    src/kvstore/kvstore.cc:17-45). Types: local / local_allreduce_cpu /
    local_allreduce_device / device / dist_sync / dist_async / dist."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    known = (
        "local", "local_allreduce_cpu", "local_allreduce_device", "device",
        "dist", "dist_sync", "dist_async", "dist_sync_device", "dist_async_device",
    )
    if name not in known:
        raise MXNetError("unknown KVStore type %s (known: %s)" % (name, known))
    if name.startswith("dist"):
        if os.environ.get("MXNET_KV_ELASTIC", "0") not in ("", "0"):
            if os.environ.get("MXNET_ELASTIC_COORD"):
                if "async" in name:
                    warnings.warn(
                        "MXNET_KV_ELASTIC=1: elastic aggregation is "
                        "synchronous; %s degrades to dist_sync semantics "
                        "(docs/how_to/elastic_training.md)" % name,
                        stacklevel=2)
                return _ElasticDistKVStore(name)
            warnings.warn(
                "MXNET_KV_ELASTIC=1 but MXNET_ELASTIC_COORD is unset; "
                "falling back to the non-elastic %s store (tools/launch.py "
                "--elastic exports the coordinator address)" % name,
                stacklevel=2)
        _maybe_init_distributed()
    if name.startswith("dist_async"):
        import jax

        if jax.process_count() > 1:
            client = _coordination_client()
            if client is not None and _async_transport_ok(client):
                return _AsyncDistKVStore(name, client)
            # No P2P transport available: fall back to lock-step
            # all-reduce semantics (a superset of async's convergence
            # guarantees, minus straggler tolerance) and say so.
            warnings.warn(
                "dist_async: coordination-service transport unavailable; "
                "falling back to synchronous all-reduce semantics "
                "(updates in lock-step, not on-arrival; see "
                "docs/distributed.md).", stacklevel=2)
    return KVStore(name)


# dist_async creates are SPMD, so every rank's Nth create shares one
# decision key — the counter keys successive creates apart
_ASYNC_DECIDE_COUNT = 0
_WIRE_AGREE_COUNT = 0


def _async_transport_ok(client):
    """Rank 0 probes overwrite support and PUBLISHES the verdict; other
    ranks read it. A transient coordinator error during the probe on one
    rank must not make it fall back to the synchronous store while the
    rest build _AsyncDistKVStore — the sync rank's psum collectives
    would then wait on processes that never join, hanging the job."""
    import jax

    global _ASYNC_DECIDE_COUNT
    _ASYNC_DECIDE_COUNT += 1
    key = "mxtpu_as/transport/%d" % _ASYNC_DECIDE_COUNT
    if jax.process_index() == 0:
        ok = _supports_overwrite(client)
        try:
            client.key_value_set(key, "async" if ok else "sync")
        except Exception:
            # decision unpublishable -> nobody can go async; the plain
            # set (no overwrite) is safe because the counter makes the
            # key fresh per create
            return False
        return ok
    # An unreadable verdict must RAISE, not default to sync: silently
    # diverging to the synchronous store on one rank while the rest
    # build _AsyncDistKVStore recreates the exact split-store hang this
    # function exists to prevent. Failing the job loudly is the only
    # consistent outcome when this rank cannot learn the decision.
    try:
        v = client.blocking_key_value_get(key, 60_000)
    except Exception as e:
        raise MXNetError(
            "dist_async: transport decision unreadable on rank %d (%s); "
            "cannot safely choose a store type" % (jax.process_index(), e))
    return v == "async"


def _coordination_client():
    """The jax.distributed coordination-service client, or None."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:  # pragma: no cover - jax internals moved
        return None


def _supports_overwrite(client):
    """Probe for key_value_set(..., allow_overwrite=True) support."""
    try:
        client.key_value_set("mxtpu_probe/ow", "1", allow_overwrite=True)
        client.key_value_set("mxtpu_probe/ow", "2", allow_overwrite=True)
        return True
    except Exception:
        return False


def _b64(obj):
    import base64

    return base64.b64encode(pickle.dumps(obj)).decode("ascii")


def _unb64(s):
    import base64

    return pickle.loads(base64.b64decode(s))


# rank 0's live async server (at most one per process; a new dist_async
# store retires the previous generation's server)
_ASYNC_SERVER = None


class _AsyncServer:
    """The reference's parameter-server role (kvstore_dist_server.h),
    hosted as a thread on rank 0. Applies each worker's gradient group ON
    ARRIVAL (ref kvstore_dist_server.h:200-207 async UpdateBuf: no
    cross-worker aggregation, no barrier) and republishes weights; the
    jax.distributed coordination KV is the ZMQ van's role.

    Per-rank apply order is preserved (groups consumed in sequence
    number order); cross-rank order is whatever arrival order the poll
    observes — exactly the reference's async contract."""

    POLL_S = 0.005

    def __init__(self, client, nworkers, ns="mxtpu_as"):
        self._client = client
        self._ns = ns
        self._n = nworkers
        # _mu guards the weight/version dict structure and the updater
        # swap: init_key runs on rank 0's MAIN thread while _run polls
        # from the server thread — an unguarded init racing an apply on
        # a freshly-initialized key could publish a version for a
        # weight it never saw (found by the mxrace audit sweep; the
        # server thread stays the only mutator of weight CONTENTS, so
        # the updater math itself runs outside the lock)
        self._mu = threading.Lock()
        from .analysis.engine_verify import maybe_trace_lock

        self._mu = maybe_trace_lock(self._mu, "kvstore._AsyncServer._mu")
        self._weights = {}           # key(str) -> NDArray (cpu)
        self._versions = {}          # key(str) -> int
        self._applied = [0] * nworkers
        self._updater = None
        self._optv = 0
        self._failed = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-kvstore-async-server", daemon=True)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()

    def init_key(self, key, arr):
        """Rank-0 direct init (program order guarantees this precedes any
        of rank 0's own pushes; other ranks block in init until the
        publish lands)."""
        with self._mu:
            self._weights[key] = NDArray(arr, cpu(0))
            self._versions[key] = 0
        self._publish(key)

    def _publish(self, key):
        # snapshot under the lock; the D2H + pickle + network write run
        # outside it. A concurrent apply bumping the version between the
        # snapshot and the send only means the NEXT publish re-asserts
        # newer state — publishes are idempotent last-writer-wins
        with self._mu:
            ver, w = self._versions[key], self._weights[key]
        self._client.key_value_set(
            "%s/w/%s" % (self._ns, key),
            _b64((ver, w.asnumpy())),
            allow_overwrite=True)

    def _try_get(self, k):
        try:
            return self._client.key_value_try_get(k)
        except Exception:
            return None

    def _check_optimizer(self):
        v = self._try_get("%s/optv" % self._ns)
        if v is None or int(v) == self._optv:
            return
        blob = self._try_get("%s/opt" % self._ns)
        if blob is None:
            return
        from . import optimizer as opt

        updater = opt.get_updater(_unb64(blob))  # decode outside the lock
        with self._mu:
            self._optv = int(v)
            self._updater = updater

    def _run(self):
        # Failure discipline: _applied[r] advances IMMEDIATELY after a
        # group's updater calls, before any network write, so a transient
        # publish/ack error can never cause the same gradient to be
        # applied twice. Publishes and acks are idempotent re-asserted
        # state (dirty set / applied counters), so a failed write heals
        # on the next poll instead of wedging async_fence forever.
        dirty = set()
        acked = [0] * self._n
        err_published = 0
        while not self._stop.wait(self.POLL_S):
            try:
                self._check_optimizer()
            except Exception:  # pragma: no cover - keep serving
                import logging

                logging.exception("async server optimizer check failed")
            for r in range(self._n):
                s = self._try_get("%s/s/%d" % (self._ns, r))
                if s is None:
                    continue
                s = int(s)
                while self._applied[r] < s and not self._stop.is_set():
                    n = self._applied[r] + 1
                    blob = self._try_get("%s/g/%d/%d" % (self._ns, r, n))
                    if blob is None:
                        break  # seq bumped before payload landed
                    try:
                        for key, grad in _unb64(blob):
                            with self._mu:
                                w = self._weights.get(key)
                                updater = self._updater
                            if w is None:
                                continue  # push raced an unknown key
                            g = NDArray(grad, cpu(0))
                            # updater math outside the lock: this thread
                            # is the only weight-CONTENT mutator
                            if updater is not None:
                                updater(_key_int(key), g, w)
                            else:
                                # no optimizer: per-arrival assign, the
                                # sync path's "store = merged" analog
                                w[:] = g.asnumpy()
                            with self._mu:
                                self._versions[key] += 1
                            dirty.add(key)
                    except Exception:  # pragma: no cover - poison group
                        import logging

                        logging.exception(
                            "async server failed applying group %d/%d; "
                            "skipping it", r, n)
                        # _applied still advances (a poison group must
                        # not wedge the stream); count the loss —
                        # async_fence/ack alone would report the dropped
                        # update as fully applied. Published below in
                        # the poll loop (retried like acks, so one
                        # transient publish error can't hide it forever).
                        self._failed += 1
                    self._applied[r] = n
                    try:  # consumed: free the coordinator's copy
                        self._client.key_value_delete(
                            "%s/g/%d/%d" % (self._ns, r, n))
                    except Exception:
                        pass
            for key in list(dirty):
                try:
                    self._publish(key)
                    dirty.discard(key)
                except Exception:
                    pass  # retry next poll
            if err_published != self._failed:
                try:
                    self._client.key_value_set(
                        "%s/err" % self._ns, str(self._failed),
                        allow_overwrite=True)
                    err_published = self._failed
                except Exception:
                    pass  # retry next poll
            for r in range(self._n):
                if acked[r] != self._applied[r] and not dirty:
                    try:
                        self._client.key_value_set(
                            "%s/a/%d" % (self._ns, r), str(self._applied[r]),
                            allow_overwrite=True)
                        acked[r] = self._applied[r]
                    except Exception:
                        pass  # retry next poll


class _AsyncDistKVStore(KVStore):
    """dist_async with REAL apply-on-arrival semantics (VERDICT r1 §7).

    Worker push = serialize the locally merged gradient group and hand it
    to the rank-0 server thread through the coordination KV, returning
    immediately — no collective, no lock-step. Worker pull = read the
    latest published weights (possibly missing other workers' in-flight
    updates: async staleness by design). `async_fence()` waits for the
    server to drain every rank's published pushes (test/shutdown hook;
    the reference exposed the same need as ps-lite's Wait on push
    timestamps).

    Transport note: coordination-KV messages are base64-pickled host
    arrays — correctness-first plumbing sized for modest parameter sets;
    bandwidth-critical jobs should use dist_sync's fused device
    collectives (docs/distributed.md)."""

    def __init__(self, kv_type, client):
        self._client = client
        self._seq = 0
        self._server = None
        super().__init__(kv_type)
        import jax

        self._rank = jax.process_index()
        self._nworkers = jax.process_count()
        # Generation-scoped key namespace: a second dist_async store in
        # the same job must not see the previous store's published
        # weights/sequence counters (stale-init + double-server races).
        # Rank 0 bumps the generation, retires any previous server
        # thread, and starts a fresh one; the constructor barrier makes
        # the new generation visible before any rank proceeds (create()
        # is SPMD — every rank constructs the store together).
        if self._rank == 0:
            global _ASYNC_SERVER
            if _ASYNC_SERVER is not None:
                _ASYNC_SERVER.stop()
            st, g = self._read_kv("mxtpu_as/gen")
            if st == "error":
                # defaulting to gen 1 on a transient read error would
                # collide with a previous generation's stale keys — the
                # exact bug the namespace exists to prevent
                raise MXNetError("dist_async: generation key unreadable")
            gen = (int(g) + 1) if st == "ok" and g is not None else 1
            client.key_value_set("mxtpu_as/gen", str(gen),
                                 allow_overwrite=True)
            self._ns = "mxtpu_as%d" % gen
            self._server = _AsyncServer(client, self._nworkers, self._ns)
            _ASYNC_SERVER = self._server
            self._server.start()
            import weakref

            weakref.finalize(self, self._server._stop.set)
        self.barrier()
        if self._rank != 0:
            st, g = self._read_kv("mxtpu_as/gen")
            if st != "ok" or g is None:
                raise MXNetError("dist_async: generation key unreadable")
            self._ns = "mxtpu_as%s" % g
        # second barrier: rank 0 must not proceed (and possibly start
        # constructing a NEXT store that bumps the generation) until
        # every rank has captured THIS generation
        self.barrier()

    # -- API overrides ---------------------------------------------------------
    def init(self, key, value):
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            k = str(k)
            if k in self._store:
                raise MXNetError("duplicate init of key %s" % k)
            self._store[k] = v.copyto(v.context)
            if self._rank == 0:
                self._server.init_key(k, v.asnumpy())
            else:
                self._wait_key("%s/w/%s" % (self._ns, k))

    def push(self, key, value, priority=0):
        keys, values = self._key_value(key, value, allow_list_per_key=True)
        group = []
        for k, v in zip(keys, values):
            k = str(k)
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            vals = v if isinstance(v, (list, tuple)) else [v]
            merged = self._reduce(list(vals), self._store[k])
            group.append((k, merged.asnumpy()))
        if _tel.ENABLED:
            _tel.counter("kvstore.push_total").inc()
            _tel.counter("kvstore.push_bytes_total").inc(
                sum(arr.nbytes for _k, arr in group))
        self._seq += 1
        # payload first, then the sequence bump that makes it visible;
        # both retried — a transient coordinator error on a push must
        # not kill the step (and a payload that landed without its seq
        # bump is invisible, so the retry cannot double-apply).
        # allow_overwrite makes the payload retry idempotent when the
        # first set committed but its ack was lost — the value for a
        # given (rank, seq) is deterministic, and this store type only
        # exists when the client supports overwrite (_async_transport_ok)
        _coord_call(
            lambda: self._client.key_value_set(
                "%s/g/%d/%d" % (self._ns, self._rank, self._seq),
                _b64(group), allow_overwrite=True),
            what="async push payload")
        _coord_call(
            lambda: self._client.key_value_set(
                "%s/s/%d" % (self._ns, self._rank), str(self._seq),
                allow_overwrite=True),
            what="async push seq bump")

    def pull(self, key, out=None, priority=0):
        assert out is not None
        keys, outs = self._key_value(key, out, allow_list_per_key=True)
        pulled_bytes = 0
        for k, o in zip(keys, outs):
            k = str(k)
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            st, blob = self._read_kv("%s/w/%s" % (self._ns, k))
            if st == "absent" or blob is None:
                raise MXNetError("async weight for key %s not published" % k)
            if st == "error":
                raise MXNetError(
                    "async pull of key %s failed: coordination service "
                    "unreachable" % k)
            _, arr = _unb64(blob)
            nd = NDArray(arr, cpu(0))
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                nd.copyto(t)
            pulled_bytes += arr.nbytes * len(targets)
        if _tel.ENABLED:
            # one inc per CALL, matching the sync store's semantics
            _tel.counter("kvstore.pull_total").inc()
            _tel.counter("kvstore.pull_bytes_total").inc(pulled_bytes)

    def set_optimizer(self, optimizer):
        """Ship the pickled optimizer to the server (the reference's
        kController command, python/mxnet/kvstore.py:231) instead of
        installing a local updater."""
        blob = pickle.dumps(optimizer)
        pickle.loads(blob)  # fail early if unpicklable, like the reference
        self._optimizer = optimizer
        if self._rank == 0:
            v = int(time.time() * 1e6)
            self._client.key_value_set("%s/opt" % self._ns, _b64(optimizer),
                                       allow_overwrite=True)
            self._client.key_value_set("%s/optv" % self._ns, str(v),
                                       allow_overwrite=True)
            # Block until the server thread installed the updater:
            # returning earlier would let a racing push be applied with
            # ASSIGN semantics.
            deadline = time.monotonic() + 10.0
            while self._server._optv != v:
                if time.monotonic() > deadline:
                    raise MXNetError("async server did not install optimizer")
                time.sleep(0.005)
        # set_optimizer is SPMD (every rank's Module.init_optimizer /
        # model._create_kvstore calls it); without this barrier a
        # non-zero rank could push before rank 0's server installed the
        # updater, and that push would be applied with assign semantics
        # (w[:] = grad), silently replacing weights with raw gradients.
        self.barrier()

    def num_failed_groups(self):
        """Gradient groups the server dropped because deserialize/apply
        raised (each logged server-side). The ack counters deliberately
        advance past poison groups so one bad push cannot wedge the
        stream — this counter is how training code distinguishes
        'quiesced' from 'quiesced but updates were lost'."""
        st, v = self._read_kv("%s/err" % self._ns)
        if st == "error":
            raise MXNetError(
                "num_failed_groups: coordination service unreachable")
        return int(v) if st == "ok" and v is not None else 0

    def async_fence(self, timeout=60.0):
        """Block until the server has applied every push published by
        every rank at call time. Call after barrier() for a global
        quiescence point."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            done = True
            for r in range(self._nworkers):
                # NOT_FOUND means the rank truly never pushed (done);
                # any other error is UNKNOWN state, not "no pushes" —
                # returning early on a transient coordinator error would
                # be exactly the lost-update the fence prevents
                ss, s = self._read_kv("%s/s/%d" % (self._ns, r))
                if ss == "absent":
                    continue
                sa, a = self._read_kv("%s/a/%d" % (self._ns, r))
                if ss == "error" or sa == "error" or int(s) > int(a or 0):
                    done = False
                    break
            if done:
                return
            time.sleep(0.01)
        raise MXNetError("async_fence timed out after %.1fs" % timeout)

    # -- helpers ---------------------------------------------------------------
    def _try_get(self, k):
        try:
            return self._client.key_value_try_get(k)
        except Exception:
            return None

    def _read_kv(self, k):
        """('ok', value) | ('absent', None) — only on NOT_FOUND — |
        ('error', None) once the retry budget is exhausted. NOT_FOUND
        is a definitive answer and is never retried (fence/init loops
        poll absent keys at high frequency); anything else is a
        transient coordinator failure and backs off under
        MXNET_KV_RETRIES before becoming 'error'."""
        def _get():
            try:
                return "ok", self._client.key_value_try_get(k)
            except Exception as e:
                if "NOT_FOUND" in str(e):
                    return "absent", None
                raise
        try:
            return _coord_call(_get, what="coordinator get %s" % k)
        except Exception:
            return "error", None

    def _wait_key(self, k, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._try_get(k) is not None:
                return
            time.sleep(0.01)
        raise MXNetError("timed out waiting for %s" % k)


#: exit code of the fail-fast eviction policy below — the supervisor
#: side (control/supervisor.py EVICTED_EXIT_CODE) keys respawns on "any
#: nonzero exit", so the value only matters for log forensics
_EVICTED_EXIT_CODE = 43


def _maybe_exit_on_evict(rank):
    """``MXNET_ELASTIC_EXIT_ON_EVICT=1``: an evicted rank exits (code
    43) instead of transparently rejoining, so its supervisor
    (tools/launch.py ``--max-restarts``, or mxctl's evict-and-replace
    loop) spawns a fresh incarnation. ``os._exit`` on purpose: the
    rejoin can trigger from the heartbeat thread, where ``sys.exit``
    would kill only that thread and leave a zombie member training on.
    The journal is flushed first (best effort) so the eviction survives
    into the chaos report."""
    if os.environ.get("MXNET_ELASTIC_EXIT_ON_EVICT", "").strip().lower() \
            in ("", "0", "false", "off", "no"):
        return
    warnings.warn(
        "elastic kvstore: rank %d evicted — exiting for supervised "
        "replacement (MXNET_ELASTIC_EXIT_ON_EVICT)" % rank, stacklevel=2)
    try:
        _tel.flush(mark="exit")
    except Exception:  # noqa: BLE001 - exiting anyway
        pass
    os._exit(_EVICTED_EXIT_CODE)


class _ElasticDistKVStore(KVStore):
    """dist_sync with elastic membership (``MXNET_KV_ELASTIC=1``).

    The synchronous dist store reduces over **all** jax processes with
    an XLA collective — a program that can never survive a dead member.
    This store replaces the collective with the elastic coordinator
    (mxnet_tpu.elastic): a server-side parameter service holding the
    authoritative weights and optimizer, a live-rank **group view** with
    a monotonically increasing membership epoch, and per-key gradient
    rounds that complete against the *current* live set. A worker whose
    heartbeat lapses past ``MXNET_KV_EVICT_AFTER`` is evicted (epoch
    bump, in-flight contributions dropped, aggregation rescaled by
    ``world/contributors``); survivors' pulls and barriers re-evaluate
    on the reduced group instead of deadlocking. A restarted worker
    re-registers, adopts the server's current weights + pickled
    optimizer, resyncs its round counters, and participates from the
    next round — the rejoin path. jax.distributed is never initialized:
    elastic workers are independent processes (``MXNET_PROC_ID`` /
    ``MXNET_NUM_PROCS`` name the rank and nominal world size).
    """

    def __init__(self, kv_type):
        from .elastic import ElasticClient

        addr = os.environ.get("MXNET_ELASTIC_COORD")
        if not addr:
            raise MXNetError(
                "MXNET_KV_ELASTIC=1 requires MXNET_ELASTIC_COORD=host:port "
                "(tools/launch.py --elastic exports it)")
        self._rank = int(os.environ.get("MXNET_PROC_ID", "0"))
        self._world = int(os.environ.get("MXNET_NUM_PROCS", "1"))
        self._client = ElasticClient(addr, self._rank)
        self._rounds = {}        # key -> last round this worker synced to
        self._epoch = 0
        self._last_counters = {}
        self._left = False
        self._shard_updater = None   # local optimizer (shard-update mode)
        super().__init__(kv_type)
        resp = self._client.register()
        self._absorb_view(resp)
        self._rounds = self._aligned_rounds(resp)

    # -- identity (env-derived: no jax.distributed in elastic mode) ------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        """Nominal world size — data sharding and the dist_sync
        batch-size rescale stay stable across evictions; the *live*
        count is group_view()."""
        return self._world

    def group_view(self):
        """(membership epoch, live rank list) from the coordinator."""
        resp = self._client.view()
        self._absorb_view(resp)
        return resp["epoch"], list(resp["live"])

    # -- view/counter bookkeeping ----------------------------------------------
    def _absorb_view(self, resp):
        """Track the epoch and mirror the coordinator's eviction/rejoin/
        degraded totals into this worker's telemetry counters (delta
        increments — counters are monotonic on both sides)."""
        self._epoch = max(self._epoch, int(resp.get("epoch", 0)))
        counters = resp.get("counters")
        if not counters:
            return
        for src, name in (("evictions", "kvstore.evictions_total"),
                          ("rejoins", "kvstore.rejoins_total"),
                          ("degraded", "kvstore.degraded_steps_total"),
                          # the coordinator's guardian skips surface in
                          # every worker's journal. Unit: KEY-ROUNDS —
                          # the aggregator guards per key per round, so
                          # one poisoned step on a P-key model counts up
                          # to P skipped rounds (hence the *_rounds
                          # names; the step-granular guardian.*_steps
                          # counters stay strictly step-denominated)
                          ("guard_skips", "guardian.skipped_rounds"),
                          ("guard_nonfinite", "guardian.nonfinite_rounds")):
            cur = int(counters.get(src, 0))
            delta = cur - self._last_counters.get(src, 0)
            if delta > 0:
                self._last_counters[src] = cur
                if _tel.ENABLED:
                    # mxtel-metrics: kvstore.evictions_total
                    # mxtel-metrics: kvstore.rejoins_total
                    # mxtel-metrics: kvstore.degraded_steps_total
                    # mxtel-metrics: guardian.skipped_rounds
                    # mxtel-metrics: guardian.nonfinite_rounds
                    _tel.counter(name).inc(delta)

    @staticmethod
    def _aligned_rounds(resp):
        """Round counters for a (re)joiner: the MINIMUM done round across
        keys, for every key. Admission can land mid-step, when the
        server's per-key rounds are non-uniform (keys before the group's
        frontier already at R+1, the frontier key still at R). Starting
        from the per-key map would let the joiner's sweep pull a round
        ahead of the frontier before it ever contributes the frontier
        key — a distributed deadlock (joiner waits on survivors, the
        survivors on the joiner). From the minimum, the sweep
        fast-forwards through completed rounds via idempotent 'stale'
        pushes and lands exactly on the frontier, unblocking the group."""
        rounds = resp.get("rounds", {})
        if not rounds:
            return {}
        floor = min(rounds.values())
        return {k: floor for k in rounds}

    def _rejoin(self):
        """Re-enter the group after the coordinator reports this rank
        evicted (a zombie that outlived its heartbeat lapse, or any op
        racing a restart): re-register, adopt the server's weights and
        round counters, and continue at the next round. Runs under the
        ``kv.rejoin`` fault point + retry policy, so an injected or
        transient rejoin failure backs off instead of dying.

        With ``MXNET_ELASTIC_EXIT_ON_EVICT=1`` the transparent rejoin
        is replaced by fail-fast replacement: the process exits (code
        43) so its supervisor — ``tools/launch.py --max-restarts`` or
        the mxctl controller — respawns a FRESH incarnation that
        re-registers. An admin eviction (a straggling or misbehaving
        rank the control plane removed on purpose) must produce a new
        process, not the same wedged one sneaking back in."""
        _maybe_exit_on_evict(self._rank)

        def _do():
            _faults.point("kv.rejoin")
            return self._client.register()

        _do.__name__ = "elastic rejoin (rank %d)" % self._rank
        resp = self._client._policy.call(_do)
        self._absorb_view(resp)
        self._rounds = self._aligned_rounds(resp)
        # refresh any locally-held weights: the group trained on while
        # this rank was out
        for k in list(self._store):
            got = self._client.call("pull", key=k, min_round=0)
            if got.get("status") == "ok":
                # all-reduce mode may serve the round's pinned wire
                # payload even to a codec-off puller (replica
                # consistency) — decode is a no-op on raw values
                self._store[k] = NDArray(_quant.decode(got["value"]),
                                         self._store[k].context)
        warnings.warn(
            "elastic kvstore: rank %d rejoined the group at epoch %d"
            % (self._rank, self._epoch), stacklevel=3)

    def _op(self, op, **fields):
        """One coordinator op with transparent rejoin-on-eviction."""
        resp = self._client.call(op, **fields)
        if resp.get("status") == "evicted":
            self._rejoin()
            resp = self._client.call(op, **fields)
            if resp.get("status") == "evicted":
                raise MXNetError(
                    "elastic kvstore: rank %d evicted and rejoin did not "
                    "restore membership (op %s)" % (self._rank, op))
        self._absorb_view(resp)
        return resp

    # -- liveness --------------------------------------------------------------
    def _start_heartbeat(self):
        """Beat through the elastic coordinator instead of the
        jax.distributed KV. Same discipline as the base store: capture
        locals (not self), stop on finalize."""
        self._hb_client = self._client
        interval = float(
            os.environ.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "2"))
        self._hb_stop = threading.Event()
        client, stop = self._client, self._hb_stop

        def _beat():
            while not stop.wait(interval):
                try:
                    client.beat()
                    if _tel.ENABLED:
                        _tel.counter(
                            "kvstore.heartbeat_publish_total").inc()
                except Exception:
                    # transient coordinator outage: keep beating; the
                    # eviction clock is the coordinator's problem
                    pass

        self._hb_thread = threading.Thread(
            target=_beat, name="mxtpu-elastic-heartbeat", daemon=True)
        self._hb_thread.start()
        import weakref

        weakref.finalize(self, stop.set)

    def dead_ranks(self, node_id=-1, timeout=None):
        """Evicted ranks per the coordinator's group view (the heartbeat
        staleness judgment moved server-side with the membership)."""
        resp = self._client.view()
        self._absorb_view(resp)
        return sorted(resp.get("evicted", []))

    def get_num_dead_node(self, node_id=-1, timeout=60):
        return len(self.dead_ranks())

    # -- data plane ------------------------------------------------------------
    def init(self, key, value):
        """First init wins server-side; every other rank (and every
        rejoiner) adopts the server copy — the reference dist server's
        init semantics, which is also what makes restart-with-current-
        weights automatic."""
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                raise MXNetError("duplicate init of key %s" % k)
            resp = self._op("init", key=k, value=v.asnumpy())
            self._store[k] = NDArray(resp["value"], v.context)
            self._rounds.setdefault(k, int(resp["round"]))

    def push(self, key, value, priority=0):
        keys, values = self._key_value(key, value, allow_list_per_key=True)
        # duplicate keys in one call merge locally first, exactly like
        # the base store's grouped push — two contributions for one
        # round would otherwise collide server-side
        grouped, order = {}, []
        for k, v in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            if k not in grouped:
                grouped[k] = []
                order.append(k)
            if isinstance(v, (list, tuple)):
                grouped[k].extend(v)
            else:
                grouped[k].append(v)
        push_bytes = 0
        for k in order:
            merged = self._reduce(grouped[k], self._store[k])
            arr = merged.asnumpy()
            push_bytes += arr.nbytes
            # low-precision wire (MXNET_KV_QUANTIZE): the gradient
            # crosses the coordinator TCP socket as int8/fp8 codes +
            # per-block scales, encoded ONCE (the resync replay below
            # re-ships identical bytes — deterministic under chaos)
            payload = self._client.encode_grad(arr)
            value = arr if payload is None else payload
            rnd = self._rounds.get(k, 0) + 1
            resp = self._op("push", key=k, round=rnd, value=value)
            status = resp.get("status")
            if status == "stale":
                # round already completed (idempotent retry, or a rejoin
                # raced the group forward): adopt the server's round so
                # the next push contributes instead of trailing stale
                rnd = max(rnd, int(resp.get("round", rnd)))
            elif status == "resync":
                # coordinator restarted from a snapshot behind our
                # progress: fall back to its round and replay this
                # step's gradient there (the gap is snapshot-cadence
                # data loss, accepted by the restart-resume contract)
                rnd = int(resp.get("round", 0)) + 1
                resp = self._op("push", key=k, round=rnd, value=value)
            self._rounds[k] = rnd
            if _tel.ENABLED:
                if payload is None:
                    self._account_wire(arr.nbytes, arr.nbytes)
                else:
                    # the quant-error gauge needs a full decode of the
                    # payload (~the cost of the encode itself), so it
                    # samples 1-in-32 pushes per store instead of
                    # doubling the codec bill on every key — the gauge
                    # tracks the max over the run either way
                    self._quant_err_tick = getattr(
                        self, "_quant_err_tick", -1) + 1
                    err = (_quant.max_block_rel_error(arr, payload)
                           if self._quant_err_tick % 32 == 0 else None)
                    self._account_wire(
                        _quant.wire_nbytes(payload), arr.nbytes,
                        quant_err=err)
        if _tel.ENABLED:
            _tel.counter("kvstore.push_total").inc()
            _tel.counter("kvstore.push_bytes_total").inc(push_bytes)

    def pull(self, key, out=None, priority=0):
        assert out is not None
        keys, outs = self._key_value(key, out, allow_list_per_key=True)
        pulled_bytes = 0
        evict_after = float(os.environ.get("MXNET_KV_EVICT_AFTER", "10"))
        deadline = time.monotonic() + max(60.0, 6.0 * evict_after)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % k)
            # the round_wait record is the straggler signal: time this
            # rank spent blocked on the round completing (i.e. on its
            # slowest peer) — tools/trace_merge.py's per-epoch
            # barrier-wait-vs-compute attribution sums it. Owner-side
            # shard updates running inside the poll loop are COMPUTE,
            # not wait, so their time is subtracted; the record is
            # emitted with explicit timestamps (tracing.event) for the
            # same reason — its duration is not the loop's wall time.
            tel_on = _tel.ENABLED
            if tel_on:
                ctx = _tel.wire_context()
                wall0, t_wait, shard_s = time.time(), time.monotonic(), 0.0
            while True:
                # re-read the floor every poll: a rejoin inside _op
                # resyncs _rounds, and the pre-eviction floor may
                # name a round whose only missing contribution was
                # OURS (dropped at eviction) — a floor that can
                # never be satisfied
                min_round = self._rounds.get(k, 0)
                resp = self._op(
                    "pull", **self._client.pull_fields(k, min_round))
                status = resp.get("status")
                if status == "ok":
                    break
                if status == "update":
                    # shard-update mode: this rank owns the key and
                    # the merged gradient is waiting — run the
                    # optimizer locally, land the weight, then
                    # re-poll (the poll re-adopts the server copy
                    # even if a reassigned owner's put raced ours,
                    # so replicas never fork)
                    t_upd = time.monotonic() if tel_on else 0.0
                    self._shard_apply_update(k, resp)
                    if tel_on:
                        shard_s += time.monotonic() - t_upd
                    continue
                if time.monotonic() > deadline:
                    raise MXNetError(
                        "elastic pull of key %s round %d timed out on "
                        "rank %d (epoch %d) — no eviction unblocked "
                        "the round; check the coordinator (docs/how_to/"
                        "elastic_training.md)"
                        % (k, min_round, self._rank, self._epoch))
                time.sleep(0.005)
            if tel_on:
                waited = max(0.0, time.monotonic() - t_wait - shard_s)
                _tel.event("kvstore.round_wait", t=wall0, dur=waited,
                           trace=ctx["trace"] if ctx else None,
                           parent=ctx["span"] if ctx else None)
                _tel.histogram("kvstore.round_wait_secs").observe(waited)
            # rejoin may have advanced our floor past min_round
            self._rounds[k] = max(self._rounds.get(k, 0), int(resp["round"]))
            value = resp["value"]
            if _quant.is_encoded(value):
                # all-reduce mode (no optimizer): the merged gradient
                # came back requantized — the second shot of the
                # two-shot quantized all-reduce
                if _tel.ENABLED:
                    self._account_wire(_quant.wire_nbytes(value),
                                       _quant.logical_nbytes(value))
                value = _quant.decode(value)
            nd = NDArray(value, self._store[k].context)
            self._store[k] = nd
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                nd.copyto(t)
            pulled_bytes += value.nbytes * len(targets)
        if _tel.ENABLED:
            _tel.counter("kvstore.pull_total").inc()
            _tel.counter("kvstore.pull_bytes_total").inc(pulled_bytes)

    # the guardian reads this: coordinator guard totals already mirror
    # into this worker's guardian.* counters (_absorb_view), so local
    # vote-path accounting must not double-count the same round
    _guardian_mirrors_skips = True

    def guardian_vote(self, step, poisoned):
        """Elastic skip coordination is SERVER-side: every rank's
        gradient rides the aggregation round, and the coordinator's
        guard skips applying a poisoned merged round for the whole
        group at once (Aggregator guard; mirrored into
        ``guardian.skipped_steps`` via the view counters). A unilateral
        local skip would leave the round waiting for this rank's
        contribution until the eviction sweeper fired — so the local
        verdict never suppresses a push here."""
        return False

    def _shard_apply_update(self, k, resp):
        """Owner half of the sharded weight update: decode the merged
        gradient (the guardian-relevant dequantized value), apply the
        LOCAL optimizer to this rank's weight copy, and land the
        result via put_weight. A 'stale' reply (a reassigned owner's
        put beat ours after an eviction race) is fine — the caller
        re-polls and adopts the server's authoritative copy."""
        if self._shard_updater is None:
            raise MXNetError(
                "elastic kvstore: coordinator handed rank %d a shard "
                "update for key %r but no optimizer was set — call "
                "set_optimizer with MXNET_KV_SHARD_UPDATE=1 on every "
                "worker" % (self._rank, k))
        rnd = int(resp["round"])
        value = resp["value"]
        if _quant.is_encoded(value):
            if _tel.ENABLED:
                self._account_wire(_quant.wire_nbytes(value),
                                   _quant.logical_nbytes(value))
            value = _quant.decode(value)
        w = self._store[k]
        grad = NDArray(_np.asarray(value, dtype=_np.float32), w.context)
        self._shard_updater(_key_int(k), grad, w)
        arr = w.asnumpy()
        self._op("put_weight", key=k, round=rnd, value=arr)
        if _tel.ENABLED:
            from . import optimizer as opt

            _tel.counter("kvstore.shard_updates_total").inc()
            _tel.counter("kvstore.shard_weight_bytes_total").inc(arr.nbytes)
            _tel.gauge("kvstore.optimizer_state_bytes").set(
                opt.state_nbytes(self._shard_updater))

    # -- control plane ---------------------------------------------------------
    def set_optimizer(self, optimizer):
        """Ship the pickled optimizer to the coordinator (the reference's
        kController command) — the server runs the updater, which is
        what lets a rejoiner pull optimizer state it never had.

        With ``MXNET_KV_SHARD_UPDATE=1`` the blob is shipped with the
        shard flag: the coordinator only keeps it for rejoiners, the
        update itself runs on each key's owner through a LOCAL updater
        installed here — per-rank optimizer state scales ~1/world
        because state is created lazily only for owned keys. The flag
        must agree across the group (the coordinator's installed mode
        is authoritative; a mismatch raises instead of half the group
        waiting on server updates that never come)."""
        blob = pickle.dumps(optimizer)
        pickle.loads(blob)  # fail early if unpicklable, like the reference
        self._optimizer = optimizer
        shard = _shard_update_on()
        resp = self._op("set_optimizer", blob=blob, shard=shard)
        server_shard = bool(resp.get("shard", False))
        if server_shard != shard:
            raise MXNetError(
                "elastic kvstore: MXNET_KV_SHARD_UPDATE mismatch — rank "
                "%d has it %s but the coordinator group installed %s; "
                "export the same value on every worker "
                "(docs/how_to/low_precision_comms.md)"
                % (self._rank, "on" if shard else "off",
                   "sharded" if server_shard else "server-side"))
        if shard:
            from . import optimizer as opt

            # inject_faults=False: the grad.nan/loss.spike chaos points
            # already fire on the PUSH path for stores with no local
            # _updater (model.py) — drawing again inside the owner's
            # updater would double-consume the seeded pattern
            self._shard_updater = opt.get_updater(
                optimizer, inject_faults=False)

    def barrier(self):
        """Epoch-aware rendezvous on the *live* group: arrivals are a
        server-side generation set re-checked on every membership
        change, so survivors pass when the dead rank is evicted instead
        of waiting for a corpse. ``MXNET_KV_BARRIER_TIMEOUT`` keeps its
        base-store meaning."""
        self._barrier_count += 1
        timeout = _barrier_timeout()
        _faults.point("kv.barrier")
        t0 = time.monotonic()
        # named wait span: trace_merge attributes barrier rendezvous
        # time (blocked on peers) separately from compute per epoch
        _wait_span = _tel.span("kvstore.barrier_wait")
        _wait_span.__enter__()
        try:
            resp = self._op("barrier", count=self._barrier_count)
            gen = int(resp["gen"])
            done = bool(resp.get("done"))
            while not done:
                if timeout > 0 and time.monotonic() - t0 > timeout:
                    raise MXNetError(
                        "elastic kvstore barrier #%d timed out after %.1fs "
                        "on rank %d (epoch %d, dead: %s) — "
                        "MXNET_KV_BARRIER_TIMEOUT"
                        % (self._barrier_count, timeout, self._rank,
                           self._epoch, self.dead_ranks()))
                # long-poll: the server parks this request on its
                # condition until the generation advances (or its wait
                # budget lapses), so a barrier costs one connection per
                # outcome instead of a 5ms poll storm. With the budget
                # disabled (MXNET_KV_PULL_WAIT=0) fall back to paced
                # client-side polling.
                budget = _pull_wait()
                if not budget:
                    time.sleep(0.005)
                wait = self._client.call("barrier_wait", gen=gen,
                                         wait=budget)
                done = bool(wait.get("done"))
        finally:
            _wait_span.__exit__(None, None, None)
            # observed on EVERY outcome: the pathological waits are the
            # percentiles this histogram exists to expose
            if _tel.ENABLED:
                _tel.histogram("kvstore.barrier_wait_secs").observe(
                    time.monotonic() - t0)

    def leave(self):
        """Graceful exit from the group view (end of training): the
        departing rank leaves every completion condition without being
        counted as a casualty, so stragglers/rejoiners still training
        are not blocked on a finished worker. Idempotent."""
        if self._left:
            return
        self._left = True
        self.stop_heartbeat()
        try:
            self._client.leave()
        except Exception:
            pass  # coordinator already gone — nothing left to leave

    def __del__(self):
        try:
            self.leave()
        except Exception:
            pass


def _maybe_init_distributed():
    """Rendezvous through jax.distributed using the env exported by
    tools/launch.py — the role the dmlc tracker's DMLC_PS_ROOT_URI env
    played for ps-lite (ref: include/mxnet/kvstore.h:158-164). No-op when
    single-process or already initialized."""
    import os

    nprocs = int(os.environ.get("MXNET_NUM_PROCS", "1"))
    if nprocs <= 1:
        return
    import jax

    # NB: must not touch jax.process_count()/devices() here — that would
    # initialize the local backend and make distributed init impossible.
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=os.environ.get("MXNET_COORDINATOR", "127.0.0.1:9876"),
        num_processes=nprocs,
        process_id=int(os.environ.get("MXNET_PROC_ID", "0")),
    )
