#!/usr/bin/env python
"""Measure gradient-synchronization bandwidth, full-precision vs int8.

TPU-native port of the reference comm benchmark (ref:
tools/bandwidth/measure.py, whose README reports GB/s per GPU for
kvstore reduce on ResNet grads — BASELINE.md's 11.10 GB/s (2 GPU) /
4.41 GB/s (8 GPU) rows). Two transports, each with an fp32 and an int8
leg (MXNET_KV_QUANTIZE, docs/how_to/low_precision_comms.md):

- ``--transport xla``: ICI/DCN all-reduce (`psum` under shard_map over
  a Mesh) — what kvstore('device')/dist lowers to — against the
  two-shot quantized all-reduce (quantize -> all_to_all -> dequant-sum
  -> requantize -> all_gather, the EQuARX structure,
  ``mxnet_tpu.quantize.make_quantized_allreduce``). The int8 wire
  model moves ~0.25x the bytes; the CPU backend shows no *time* win
  (its "collectives" are shared-memory copies, so the codec math
  dominates) — the wire ratio is the hardware-portable number there.
- ``--transport dist``: the elastic coordinator TCP transport (the
  dist path that runs everywhere, including this container): N worker
  processes push gradient rounds through a real ElasticCoordinator and
  pull the merged result back, fp32 versus int8 codes both ways (the
  merged gradient is requantized server-side — the second shot). The
  wire bytes are literal TCP bytes. ``--link-mbps`` (default 200)
  paces each worker's gradient transfers to a fixed per-NIC rate,
  emulating a comms-bound cross-host link — the regime this codec
  targets. Unpaced loopback (``--link-mbps 0``) measures the host's
  memory bus + pickle stack instead of a network; on a host whose
  CPU is slower than its loopback, the codec *cannot* win there by
  construction (quantize math costs more than the memcpy it saves),
  which is a statement about the host, not the wire. The paced rate
  is recorded in every JSON record (``link_mbps``) so no number is
  comparable to a differently-paced one.

Every leg emits one JSON line (``metric``, ``value`` = the median of
``--repeats`` windows, ``unit``, min/median/max/spread, logical vs wire
bytes per round).

Smoke runs on CPU::

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python tools/bandwidth/measure.py --transport xla --size-mb 64
  JAX_PLATFORMS=cpu python tools/bandwidth/measure.py --transport dist \\
    --size-mb 16 --workers 4
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# BASELINE.md KVStore device all-reduce rows (ResNet-200 grads)
_BASELINE_GBS = {2: 11.10, 8: 4.41}


def _emit(metric, unit, rates, extra=None, baseline=None):
    """One record: median headline + spread over the repeated
    steady-state windows."""
    med = statistics.median(rates)
    rec = {
        "metric": metric,
        "value": round(med, 3),
        "unit": unit,
        "min": round(min(rates), 3),
        "median": round(med, 3),
        "max": round(max(rates), 3),
        "spread_pct": round(
            100.0 * (max(rates) - min(rates)) / med, 2) if med else 0.0,
        "repeats": len(rates),
    }
    if baseline:
        rec["vs_baseline"] = round(med / baseline, 3)
    rec.update(extra or {})
    print(json.dumps(rec))
    return rec


# -- XLA collective legs -------------------------------------------------------

def run_xla(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map

    from mxnet_tpu import quantize

    devices = jax.devices()
    n = len(devices)
    if n == 1:
        print(json.dumps({
            "metric": "comm_allreduce_fp32", "value": 0.0,
            "unit": "GB/s/device",
            "note": "1 device: no collective traffic exists"}))
        return []
    mesh = Mesh(np.asarray(devices), ("dp",))
    blk = quantize.block_size()
    elems = int(args.size_mb * 1e6 / 4) // (n * blk) * (n * blk)
    size_mb = elems * 4 / 1e6
    x = jax.device_put(
        jnp.ones((n, elems), jnp.float32) * 0.001,
        NamedSharding(mesh, P("dp", None)))

    @jax.jit
    def allreduce(v):
        def f(v):
            # mean, not sum: the timed loop chains outputs back in as
            # inputs for a serialization dependency, and a raw psum
            # would grow values by n each iteration into f32 inf
            return jax.lax.psum(v, "dp") / n

        return shard_map(f, mesh=mesh, in_specs=P("dp", None),
                         out_specs=P("dp", None))(v)

    stoch = quantize.rounding() == "stochastic"
    qallreduce = quantize.make_quantized_allreduce(
        mesh, "dp", elems, block=blk, stochastic=stoch)
    keys = jax.device_put(jax.random.split(jax.random.PRNGKey(0), 1),
                          NamedSharding(mesh, P(None)))

    fence = jax.block_until_ready

    # ring all-reduce moves 2*(n-1)/n of the buffer per device
    ring = 2.0 * (n - 1) / n
    fp32_wire = int(ring * elems * 4)
    int8_wire = int(ring * (elems + 4 * (elems // blk)))
    records = []
    for name, fn, wire in (
            ("comm_allreduce_fp32", lambda v: allreduce(v), fp32_wire),
            ("comm_allreduce_int8", lambda v: qallreduce(v, keys),
             int8_wire)):
        out = fn(x)
        fence(out)
        rates = []
        for _rep in range(args.repeats):
            o = x
            t0 = time.perf_counter()
            for _ in range(args.iters):
                o = fn(o)
            fence(o)
            dt = (time.perf_counter() - t0) / args.iters
            rates.append(size_mb / 1e3 * ring / dt)
        records.append(_emit(
            name, "GB/s/device", rates,
            baseline=_BASELINE_GBS.get(n),
            extra={"devices": n, "size_mb": round(size_mb, 1),
                   "logical_bytes_per_round": int(ring * elems * 4),
                   "wire_bytes_per_round": wire,
                   "wire_ratio": round(wire / (ring * elems * 4), 3)}))
    return records


# -- elastic TCP transport legs ------------------------------------------------

_DIST_KEY = "g"


def _dist_worker():
    """One bandwidth worker (subprocess): push gradient rounds through
    the coordinator and pull the merged result, lockstep. The wire
    mode comes from MXNET_KV_QUANTIZE exactly as in production."""
    import numpy as np

    from mxnet_tpu import quantize
    from mxnet_tpu.elastic.client import ElasticClient

    rank = int(os.environ["MEASURE_RANK"])
    rounds = int(os.environ["MEASURE_ROUNDS"])
    elems = int(os.environ["MEASURE_ELEMS"])
    link_mbps = float(os.environ.get("MEASURE_LINK_MBPS", "0"))

    def pace(nbytes, t0):
        """Emulate a ``link_mbps`` NIC: a transfer of ``nbytes`` may
        not complete faster than the link would carry it. Pacing
        covers only the tensor transfers (the thing the codec
        shrinks), not the server's merge time."""
        if link_mbps > 0:
            left = nbytes * 8.0 / (link_mbps * 1e6) \
                - (time.perf_counter() - t0)
            if left > 0:
                time.sleep(left)

    client = ElasticClient(os.environ["MEASURE_COORD"], rank)
    client.wait_ready(60.0)
    client.register()
    grad = (np.random.RandomState(rank).rand(elems).astype(np.float32)
            * 0.01)
    client.call("init", key=_DIST_KEY, value=np.zeros(elems, np.float32))
    for rnd in range(1, rounds + 1):
        t0 = time.perf_counter()
        resp, payload = client.push_grad(_DIST_KEY, rnd, grad)
        pace(grad.nbytes if payload is None
             else quantize.wire_nbytes(payload), t0)
        while True:
            t0 = time.perf_counter()
            got = client.pull_weights(_DIST_KEY, rnd)
            if got.get("status") == "ok":
                break
            time.sleep(0.002)
        pace(quantize.wire_nbytes(got["value"]), t0)
        quantize.decode(got["value"])  # the dequantize is part of the path
    client.leave()


def _spawn_workers(addr, nworkers, rounds, elems, quant, link_mbps):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "MEASURE_COORD": "%s:%d" % addr,
        "MEASURE_ROUNDS": str(rounds),
        "MEASURE_ELEMS": str(elems),
        "MEASURE_LINK_MBPS": str(link_mbps),
        "MXNET_KV_EVICT_AFTER": "600",  # a slow-importing worker is not dead
    })
    env.pop("MXNET_TELEMETRY", None)
    if quant:
        env["MXNET_KV_QUANTIZE"] = quant
    else:
        env.pop("MXNET_KV_QUANTIZE", None)
    procs = []
    for r in range(nworkers):
        env_r = dict(env, MEASURE_RANK=str(r))
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--dist-worker"],
            env=env_r, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    return procs


def _dist_leg(quant, args):
    """One transport leg: in-process coordinator, N worker subprocesses,
    round-completion timestamps observed server-side (one clock, no
    cross-process skew). Returns (per-window GB/s/rank rates, wire
    bytes per round per rank)."""
    import numpy as np

    from mxnet_tpu import quantize
    from mxnet_tpu.elastic import ElasticCoordinator

    blk = quantize.block_size()
    elems = max(blk, int(args.size_mb * 1e6 / 4) // blk * blk)
    rounds = args.warmup + args.repeats * args.rounds
    coord = ElasticCoordinator(world=args.workers, bind=("127.0.0.1", 0),
                               evict_after=600).start()
    procs = _spawn_workers(coord.addr, args.workers, rounds, elems, quant,
                           args.link_mbps)
    deadline = time.monotonic() + args.timeout
    marks = {}
    want = [args.warmup + i * args.rounds for i in range(args.repeats + 1)]
    try:
        while time.monotonic() < deadline:
            done = coord.agg.done.get(_DIST_KEY, 0)
            for w in want:
                if done >= w and w not in marks:
                    marks[w] = time.monotonic()
            if done >= rounds:
                break
            # 10ms granularity: ~3% of a round, and a 1ms spin here
            # steals a meaningful slice of a small host's cores from
            # the processes being measured
            time.sleep(0.01)
        else:
            raise RuntimeError(
                "dist leg (%s) timed out at round %d/%d"
                % (quant or "fp32", coord.agg.done.get(_DIST_KEY, 0),
                   rounds))
        for p in procs:
            p.wait(timeout=60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        err = "\n".join((p.stderr.read() or "")[-500:] for p in procs
                        if p.poll() not in (0, None))
        coord.stop()
    if err.strip():
        print("measure.py dist worker stderr:\n%s" % err, file=sys.stderr)
    size_gb = elems * 4 / 1e9
    rates = []
    for a, b in zip(want, want[1:]):
        # floor at the 10ms poll granularity: an unpaced tiny leg can
        # land two window marks in the same poll (dt would be 0) — the
        # reported rate is then a lower bound at measurement resolution
        dt = max((marks[b] - marks[a]) / args.rounds, 0.01 / args.rounds)
        rates.append(size_gb / dt)
    # wire bytes per rank per round: the pushed payload up, the merged
    # result down (requantized server-side on the int8 leg)
    probe = np.random.RandomState(0).rand(elems).astype(np.float32)
    if quant:
        payload = quantize.encode(probe, rng=np.random.default_rng(0),
                                  mode_=quant)
        wire = 2 * quantize.wire_nbytes(payload)
    else:
        wire = 2 * probe.nbytes
    return rates, wire, elems


def run_dist(args):
    records = []
    fp32_rates, fp32_wire, elems = _dist_leg(None, args)
    logical = 2 * elems * 4
    common = {"workers": args.workers, "size_mb": round(elems * 4 / 1e6, 1),
              "logical_bytes_per_round": logical,
              "link_mbps": args.link_mbps,
              "transport": "elastic-tcp"}
    records.append(_emit(
        "comm_dist_allreduce_fp32", "GB/s/rank", fp32_rates,
        extra=dict(common, wire_bytes_per_round=fp32_wire,
                   wire_ratio=round(fp32_wire / logical, 3))))
    int8_rates, int8_wire, _ = _dist_leg("int8", args)
    records.append(_emit(
        "comm_dist_allreduce_int8", "GB/s/rank", int8_rates,
        extra=dict(common, wire_bytes_per_round=int8_wire,
                   wire_ratio=round(int8_wire / logical, 3),
                   speedup_vs_fp32=round(
                       statistics.median(int8_rates)
                       / statistics.median(fp32_rates), 3))))
    return records


def main(argv=None):
    if "--dist-worker" in (argv or sys.argv[1:]):
        return _dist_worker()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--transport", choices=["xla", "dist", "all"],
                   default="all")
    p.add_argument("--size-mb", type=float, default=64,
                   help="gradient bytes per device/rank (f32)")
    p.add_argument("--iters", type=int, default=10,
                   help="xla: timed all-reduces per window")
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--repeats", type=int, default=3,
                   help="steady-state windows (median is the headline)")
    p.add_argument("--workers", type=int, default=4,
                   help="dist: worker processes")
    p.add_argument("--rounds", type=int, default=6,
                   help="dist: timed rounds per window")
    p.add_argument("--link-mbps", type=float, default=200.0,
                   help="dist: pace each worker's tensor transfers to "
                        "this NIC rate (emulates a comms-bound "
                        "cross-host link); 0 = raw loopback")
    p.add_argument("--timeout", type=float, default=600.0)
    args = p.parse_args(argv)

    if args.transport in ("xla", "all"):
        run_xla(args)
    if args.transport in ("dist", "all"):
        run_dist(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
