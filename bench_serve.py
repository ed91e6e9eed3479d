#!/usr/bin/env python
"""Benchmark: continuous-batching serving vs static batching under a
Poisson open-loop load — plus, with ``--spec``, speculative decoding
vs the plain continuous engine.

The serving companion to bench.py / bench_lm.py: drives the SAME seeded
arrival trace (Poisson interarrivals, mixed prompt lengths, a
short/long output-length mixture — the traffic shape where static
batching bleeds) through ``mxnet_tpu.serving.Engine`` twice — once with
``policy="static"`` (classic batching: admit only when the previous
batch fully drains, KV reserved for the worst case) and once with
``policy="continuous"`` (per-step admit/evict over the paged KV pool) —
and prints ONE JSON line:

    {"metric": "serving_continuous_vs_static", "value": <tokens/s
     ratio>, "unit": "x", "vs_baseline": value / 2.0, ...}

``vs_baseline`` >= 1.0 is the acceptance gate (ISSUE 8: continuous
>= 2x static tokens/s at equal-or-better p99 TTFT). Each leg's record
carries tokens/s, p50/p99 TTFT, p99 per-token latency, KV-pool peak
utilization, and the admitted/completed/evicted/rejected counters, so
the paged-pool behavior is self-certifying in the BENCH JSON.

Methodology notes:

- **same trace**: both legs replay identical (arrival time, prompt,
  max_new_tokens) tuples; arrival times are scheduled against the real
  clock (open loop — the load does not wait for the server).
- **tokens/s** is completed tokens / makespan (first submit -> last
  token). Under heavy traffic the static leg saturates at its padded
  capacity while continuous keeps the decode batch full of *live*
  requests, which is the whole point.
- **calibration**: the arrival rate is derived from a measured decode
  step so the offered load lands at ``BENCH_SERVE_LOAD`` (default 1.5)
  x the continuous engine's full-batch token capacity — deliberate
  overload, the "heavy traffic" regime the subsystem exists for: the
  queue builds, both legs saturate, and tokens/s compares the two
  systems' delivered capacity rather than the arrival process. A
  hardcoded rate would mean different pressure on different machines.
- **pool pressure**: both legs get the same deliberately tight pool
  (default 48 usable blocks), so static's worst-case reservation cuts
  its batch while continuous overcommits and pays with counted
  evictions (recompute-style, stream-lossless).
- jit warmup (all bucketed shapes) happens before the clock starts;
  with the persistent jit cache warm the warmup is a disk load (PR 6).

Env knobs: BENCH_SERVE_{DMODEL,LAYERS,HEADS,DFF,VOCAB,REQUESTS,SEED,
BLOCK_SIZE,KV_BLOCKS,MAX_BATCH,PREFILL_CHUNK,LOAD,TIMEOUT}.

The ``--spec`` leg (ISSUE 15)
-----------------------------

``python bench_serve.py --spec`` replays the same seeded open-loop
overload trace through the continuous engine twice — plain, and with
draft-model speculative decoding — alternating repeats, median-of-3
headline::

    {"metric": "serving_spec_vs_continuous", "value": <tokens/s ratio>,
     "vs_baseline": value / 1.25, "accept_rate": ...,
     "accepted_tokens_per_step": ..., "repeat_ratios": [...], ...}

The acceptance gate is ``value >= 1.25`` with every per-repeat ratio
>= 1.1. Draft construction: the bench has no trained models, so the
draft/target relationship a deployment gets from distillation is
manufactured structurally — the draft is the target's FIRST
``BENCH_SERVE_SPEC_DRAFT_LAYERS`` layers (embeddings shared; well
under 1/4 of the target's parameters, ``draft_param_frac`` in the
JSON), and the target's remaining layers carry residual weights scaled
by ``BENCH_SERVE_SPEC_RESID`` so the truncation approximates the full
model the way a distilled draft approximates its target. The target
still executes every layer (its step cost is real); the accept rate
this construction yields is MEASURED and reported, and the headline is
only meaningful alongside it — push RESID up to see speculation turn
into a loss (the mxctl accept-rate rule exists for exactly that,
docs/how_to/control_plane.md). Extra spec knobs:
BENCH_SERVE_SPEC_{K,TARGET_LAYERS,DRAFT_LAYERS,RESID}.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _env_int(name, default):
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else default


def _env_float(name, default):
    raw = os.environ.get(name, "").strip()
    return float(raw) if raw else default


def make_trace(n, rate, vocab, rng):
    """Seeded open-loop trace: Poisson arrivals, short prompts (the
    decode-bound serving shape), bimodal output lengths (75% short
    6-16, 25% long 80-96 — mean ~30, max 96): the ragged mixture
    continuous batching exists for. A static batch drains at the pace
    of its slowest member while its short requests' slots sit dead; the
    paged pool also lets continuous admit MORE concurrent requests from
    the same memory (static must reserve every request's worst case)."""
    t = 0.0
    trace = []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.randint(4, 14))
        if rng.rand() < 0.25:
            mnew = int(rng.randint(80, 97))
        else:
            mnew = int(rng.randint(6, 17))
        trace.append((t, rng.randint(0, vocab, (plen,)).astype(np.int32),
                      mnew))
    return trace


#: mean output tokens of make_trace's bimodal mixture (0.75 * U[6,16]
#: + 0.25 * U[80,96]) — the calibration denominator both legs share
TRACE_MEAN_TOKENS = 0.75 * 11.0 + 0.25 * 88.0


def median_leg(legs):
    """The median-tokens/s leg, annotated with the min/max across
    repeats (bench.py convention, PR 3)."""
    mid = sorted(legs, key=lambda l: l["tokens_per_s"])[len(legs) // 2]
    tps = [l["tokens_per_s"] for l in legs]
    mid = dict(mid)
    mid["tokens_per_s_min"] = min(tps)
    mid["tokens_per_s_max"] = max(tps)
    return mid


def run_leg(eng, trace, timeout):
    """Replay one arrival trace through a (reused, pre-warmed) engine;
    metrics are per-window deltas so repeats don't pollute each other."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving import QueueFullError

    st0 = eng.stats()
    ttft0, lat0 = eng.latency_samples()
    i = 0
    makespan = None
    t0 = time.monotonic()
    deadline = t0 + timeout
    while True:
        now = time.monotonic() - t0
        while i < len(trace) and trace[i][0] <= now:
            _, prompt, mnew = trace[i]
            i += 1
            try:
                eng.submit(prompt, max_new_tokens=mnew)
            except (QueueFullError, MXNetError):
                pass  # counted by the engine as rejected
        worked = eng.step()
        if not worked:
            if i >= len(trace):
                break
            # idle until the next arrival
            time.sleep(min(0.005, max(0.0, trace[i][0] - (
                time.monotonic() - t0))))
        if time.monotonic() > deadline:
            # drain the backlog OUTSIDE the measured window so a reused
            # engine never leaks this leg's requests into the next
            # repeat's deltas: cancel everything still in flight, then
            # let the scheduler sweep and free their blocks
            makespan = time.monotonic() - t0
            for req in (list(eng.sched.queue) + list(eng.sched.active)):
                eng.cancel(req)
            eng.run_until_idle()
            break
    if makespan is None:
        makespan = time.monotonic() - t0
    eng.note_idle()
    st = eng.stats()
    ttft, lat = eng.latency_samples()
    ttft, lat = ttft[len(ttft0):], lat[len(lat0):]
    tokens = st["tokens_emitted"] - st0["tokens_emitted"]
    leg = {
        "policy": eng.cfg.policy,
        "tokens_per_s": round(tokens / makespan, 2),
        "makespan_s": round(makespan, 3),
        "tokens_emitted": tokens,
        "ttft_p50_s": _pct(ttft, 50),
        "ttft_p99_s": _pct(ttft, 99),
        "token_latency_p99_s": _pct(lat, 99),
        "kv_pool_peak_utilization": round(
            st["kv_pool_hwm_blocks"] / float(eng.pool.capacity), 4),
        "kv_pool_final_utilization": round(st["kv_pool_utilization"], 4),
        "requests_admitted": st["admitted"] - st0["admitted"],
        "requests_completed": st["completed"] - st0["completed"],
        "requests_evicted": st["evicted"] - st0["evicted"],
        "requests_rejected": st["rejected"] - st0["rejected"],
        "steps": st["steps"] - st0["steps"],
    }
    turns = st["spec_turns"] - st0["spec_turns"]
    if turns:
        drafted = st["spec_tokens_drafted"] - st0["spec_tokens_drafted"]
        accepted = st["spec_tokens_accepted"] - st0["spec_tokens_accepted"]
        leg["policy"] = "continuous+spec"
        leg["spec_turns"] = turns
        leg["spec_tokens_drafted"] = drafted
        leg["spec_tokens_accepted"] = accepted
        leg["spec_accept_rate"] = round(accepted / max(drafted, 1), 4)
        leg["spec_accepted_tokens_per_turn"] = round(
            accepted / float(turns), 3)
    return leg


def _pct(xs, q):
    if not xs:
        return None
    return round(float(np.percentile(np.asarray(xs), q)), 4)


def warmup(eng, params):
    """Compile every bucketed (batch, chunk) program off the clock."""
    for b in eng.model.batch_buckets:
        eng.model.warmup(params, eng.pool, batch_sizes=[b])
        for c in eng.model.chunk_buckets:
            bt = np.zeros((b, eng.model.max_blocks), np.int32)
            nxt, kp, vp = eng.model.step(
                params, eng.pool.k, eng.pool.v, np.zeros((b, c), np.int32),
                np.zeros((b,), np.int32), np.ones((b,), np.int32), bt,
                np.zeros((b,), bool))
            eng.pool.swap(kp, vp)
    if eng.draft_model is not None:
        # every speculative program bucket (draft prefill mirror,
        # draft_turn, verify), then a real spec workload so the
        # shrinking-batch tail shapes are warm too (stats are windowed
        # deltas — warmup traffic never pollutes a leg)
        eng.warmup_spec()
        prompts = [np.zeros((6,), np.int32)
                   for _ in range(eng.cfg.max_batch)]
        eng.generate(prompts, max_new_tokens=2 * eng.cfg.spec_k + 4)
        eng.note_idle()


def calibrate_rate(params, model_cfg, mk_cfg, mean_tokens, load):
    """Measured decode-step time -> arrival rate hitting ``load`` x the
    continuous engine's token capacity."""
    from mxnet_tpu.serving import Engine

    eng = Engine(params, model_cfg, mk_cfg("continuous"))
    warmup(eng, params)
    B = eng.cfg.max_batch
    prompts = [np.zeros((8,), np.int32) for _ in range(B)]
    for p in prompts:
        eng.submit(p, max_new_tokens=64)
    while any(r.state != "decode" for r in eng.sched.active):
        eng.step()
    t0 = time.monotonic()
    steps = 10
    for _ in range(steps):
        eng.step()
    step_s = (time.monotonic() - t0) / steps
    capacity_tps = B / step_s
    eng.note_idle()  # abandoned probe engine: zero its gauges
    return load * capacity_tps / mean_tokens, capacity_tps


def make_draft(params, model_cfg, draft_layers, resid_scale):
    """Structurally-coupled draft for the spec leg: the target keeps
    its full depth but its tail layers' residual contributions are
    scaled by ``resid_scale`` (the target params are MUTATED — both
    legs must serve the same model); the draft is the first
    ``draft_layers`` layers with shared embeddings. Returns
    (draft_params, draft_cfg, draft_param_frac)."""
    import dataclasses as _dc

    for lp in params["layers"][draft_layers:]:
        lp["wo"] = lp["wo"] * resid_scale
        lp["w2"] = lp["w2"] * resid_scale
    draft_params = {
        "embed": params["embed"], "pos_embed": params["pos_embed"],
        "layers": params["layers"][:draft_layers], "ln_f": params["ln_f"],
    }

    def nparams(tree):
        if hasattr(tree, "size"):
            return int(tree.size)
        if isinstance(tree, dict):
            return sum(nparams(v) for v in tree.values())
        return sum(nparams(v) for v in tree)

    frac = nparams(draft_params) / float(nparams(params))
    draft_cfg = _dc.replace(model_cfg, num_layers=draft_layers)
    return draft_params, draft_cfg, frac


def main_spec():
    """The --spec leg: continuous vs continuous+speculative decoding,
    same trace, alternating repeats, median headline (gate >= 1.25x,
    every repeat pair >= 1.1x).

    Model defaults differ from the classic leg: speculation's win
    condition is a deep-enough target that one target step costs
    visibly more than a draft step, at dims where verifying K+1
    positions is close to the cost of verifying one (the
    memory-/overhead-bound regime real accelerators live in) — d64 x 8
    layers with a 1-layer shared-embedding draft (~24% of target
    params) and a measured ~0.9 accept rate at the default RESID."""
    d_model = _env_int("BENCH_SERVE_DMODEL", 64)
    layers = _env_int("BENCH_SERVE_SPEC_TARGET_LAYERS", 8)
    heads = _env_int("BENCH_SERVE_HEADS", 2)
    d_ff = _env_int("BENCH_SERVE_DFF", 128)
    vocab = _env_int("BENCH_SERVE_VOCAB", 512)
    n_req = _env_int("BENCH_SERVE_REQUESTS", 40)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    block_size = _env_int("BENCH_SERVE_BLOCK_SIZE", 16)
    kv_blocks = _env_int("BENCH_SERVE_KV_BLOCKS", 129)
    max_batch = _env_int("BENCH_SERVE_MAX_BATCH", 8)
    prefill_chunk = _env_int("BENCH_SERVE_PREFILL_CHUNK", 32)
    load = _env_float("BENCH_SERVE_LOAD", 1.5)
    timeout = _env_float("BENCH_SERVE_TIMEOUT", 240.0)
    spec_k = _env_int("BENCH_SERVE_SPEC_K", 8)
    draft_layers = _env_int("BENCH_SERVE_SPEC_DRAFT_LAYERS", 1)
    resid = _env_float("BENCH_SERVE_SPEC_RESID", 0.005)
    repeats = _env_int("BENCH_SERVE_REPEATS", 3)

    import jax

    from mxnet_tpu.models.transformer import TransformerConfig, init_params
    from mxnet_tpu.serving import Engine, ServingConfig

    model_cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, d_model=d_model,
        num_heads=heads, d_ff=d_ff, max_seq_len=128, dtype="float32")
    params = init_params(model_cfg, jax.random.PRNGKey(seed))
    draft_params, draft_cfg, frac = make_draft(params, model_cfg,
                                               draft_layers, resid)

    def mk_cfg(spec):
        return ServingConfig(
            block_size=block_size, num_blocks=kv_blocks,
            max_batch=max_batch, prefill_chunk=prefill_chunk,
            max_queue_depth=4 * n_req, policy="continuous", spec=spec,
            spec_k=spec_k,
            token_budget=max_batch * (1 + spec_k) + prefill_chunk)

    rng = np.random.RandomState(seed)
    rate, capacity = calibrate_rate(params, model_cfg,
                                    lambda p: mk_cfg(False),
                                    TRACE_MEAN_TOKENS, load)
    trace = make_trace(n_req, rate, vocab, rng)

    engines = {
        "continuous": Engine(params, model_cfg, mk_cfg(False)),
        "spec": Engine(params, model_cfg, mk_cfg(True),
                       draft_params=draft_params, draft_cfg=draft_cfg),
    }
    for eng in engines.values():
        warmup(eng, params)
        # shakeout lap: one unmeasured replay of the REAL trace — the
        # first pass of live traffic through a fresh engine pays
        # dispatch-fastpath/allocator warm-in that no program-level
        # warmup covers (observed: first spec repeat ~2x slower with
        # zero compiles in the window), and the per-repeat >= 1.1x
        # gate must measure steady state
        run_leg(eng, trace, timeout)

    runs = {"continuous": [], "spec": []}
    for rep in range(max(1, repeats)):
        for leg_name in ("continuous", "spec"):
            leg = run_leg(engines[leg_name], trace, timeout)
            runs[leg_name].append(leg)
            print("bench_serve[%d]: %s: %.1f tok/s, accept %.2f"
                  % (rep, leg["policy"], leg["tokens_per_s"],
                     leg.get("spec_accept_rate", -1)), file=sys.stderr)

    c_leg = median_leg(runs["continuous"])
    s_leg = median_leg(runs["spec"])
    ratio = s_leg["tokens_per_s"] / max(c_leg["tokens_per_s"], 1e-9)
    repeat_ratios = [
        round(s["tokens_per_s"] / max(c["tokens_per_s"], 1e-9), 3)
        for s, c in zip(runs["spec"], runs["continuous"])]
    print(json.dumps({
        "metric": "serving_spec_vs_continuous",
        "value": round(ratio, 3),
        "unit": "x tokens/s",
        "vs_baseline": round(ratio / 1.25, 3),  # >= 1.0 meets the gate
        "repeat_ratios": repeat_ratios,          # every one >= 1.1
        "accept_rate": s_leg.get("spec_accept_rate"),
        "accepted_tokens_per_step": s_leg.get(
            "spec_accepted_tokens_per_turn"),
        # top-level fields tools/perf_gate.py lifts from a judged
        # BENCH record (docs/how_to/profiling.md gate workflow)
        "tokens_per_s": s_leg["tokens_per_s"],
        "ttft_p99_s": s_leg["ttft_p99_s"],
        "spec_accept_rate": s_leg.get("spec_accept_rate"),
        "draft_param_frac": round(frac, 4),
        "offered_load_req_s": round(rate, 3),
        "decode_capacity_tokens_s": round(capacity, 1),
        "repeats": repeats,
        "continuous": c_leg,
        "spec": s_leg,
        "config": {"d_model": d_model, "layers": layers, "heads": heads,
                   "d_ff": d_ff, "vocab": vocab, "requests": n_req,
                   "block_size": block_size, "kv_blocks": kv_blocks,
                   "max_batch": max_batch, "prefill_chunk": prefill_chunk,
                   "load": load, "seed": seed, "spec_k": spec_k,
                   "draft_layers": draft_layers, "resid_scale": resid},
    }))


def main():
    # a small decoder LM (the bench_lm.py model family, serving-sized so
    # the CPU container finishes in minutes; on TPU crank the dims)
    d_model = _env_int("BENCH_SERVE_DMODEL", 128)
    layers = _env_int("BENCH_SERVE_LAYERS", 2)
    heads = _env_int("BENCH_SERVE_HEADS", 2)
    d_ff = _env_int("BENCH_SERVE_DFF", 256)
    vocab = _env_int("BENCH_SERVE_VOCAB", 512)
    n_req = _env_int("BENCH_SERVE_REQUESTS", 40)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    block_size = _env_int("BENCH_SERVE_BLOCK_SIZE", 16)
    kv_blocks = _env_int("BENCH_SERVE_KV_BLOCKS", 49)
    max_batch = _env_int("BENCH_SERVE_MAX_BATCH", 8)
    prefill_chunk = _env_int("BENCH_SERVE_PREFILL_CHUNK", 32)
    load = _env_float("BENCH_SERVE_LOAD", 1.5)
    timeout = _env_float("BENCH_SERVE_TIMEOUT", 240.0)

    import jax

    from mxnet_tpu.models.transformer import TransformerConfig, init_params
    from mxnet_tpu.serving import ServingConfig

    model_cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, d_model=d_model,
        num_heads=heads, d_ff=d_ff, max_seq_len=128, dtype="float32")
    params = init_params(model_cfg, jax.random.PRNGKey(seed))

    def mk_cfg(policy):
        return ServingConfig(
            block_size=block_size, num_blocks=kv_blocks,
            max_batch=max_batch, prefill_chunk=prefill_chunk,
            max_queue_depth=4 * n_req, policy=policy)

    repeats = _env_int("BENCH_SERVE_REPEATS", 3)

    rng = np.random.RandomState(seed)
    rate, capacity = calibrate_rate(params, model_cfg, mk_cfg,
                                    TRACE_MEAN_TOKENS, load)
    trace = make_trace(n_req, rate, vocab, rng)

    from mxnet_tpu.serving import Engine

    engines = {}
    for policy in ("static", "continuous"):
        engines[policy] = Engine(params, model_cfg, mk_cfg(policy))
        warmup(engines[policy], params)

    # legs alternate static/continuous each repeat so machine-speed
    # drift (a real hazard in shared containers) cancels; the headline
    # is the median repeat, bench.py convention (PR 3)
    runs = {"static": [], "continuous": []}
    for rep in range(max(1, repeats)):
        for policy in ("static", "continuous"):
            leg = run_leg(engines[policy], trace, timeout)
            runs[policy].append(leg)
            print("bench_serve[%d]: %s: %.1f tok/s, p99 TTFT %.3fs"
                  % (rep, policy, leg["tokens_per_s"],
                     leg["ttft_p99_s"] or -1), file=sys.stderr)

    s_leg = median_leg(runs["static"])
    c_leg = median_leg(runs["continuous"])
    ratio = c_leg["tokens_per_s"] / max(s_leg["tokens_per_s"], 1e-9)
    ttft_ok = (c_leg["ttft_p99_s"] or 0) <= (s_leg["ttft_p99_s"] or 0)
    print(json.dumps({
        "metric": "serving_continuous_vs_static",
        "value": round(ratio, 3),
        "unit": "x tokens/s",
        "vs_baseline": round(ratio / 2.0, 3),  # >= 1.0 meets the 2x gate
        "ttft_p99_equal_or_better": bool(ttft_ok),
        "offered_load_req_s": round(rate, 3),
        "decode_capacity_tokens_s": round(capacity, 1),
        "repeats": repeats,
        "static": s_leg,
        "continuous": c_leg,
        "config": {"d_model": d_model, "layers": layers, "heads": heads,
                   "d_ff": d_ff, "vocab": vocab, "requests": n_req,
                   "block_size": block_size, "kv_blocks": kv_blocks,
                   "max_batch": max_batch, "prefill_chunk": prefill_chunk,
                   "load": load, "seed": seed},
    }))


def _submit_trace_fleet(router, trace, kill_t=None, on_kill=None):
    """Open-loop replay of the arrival trace through the router; at
    ``kill_t`` (trace-relative seconds) ``on_kill`` fires once —
    mid-flight, like a real SIGKILL. Returns (streams, rejected, t0)."""
    from mxnet_tpu.serving import QueueFullError

    streams, rejected = [], 0
    killed = kill_t is None
    t0 = time.monotonic()
    i = 0
    while i < len(trace):
        now = time.monotonic() - t0
        if not killed and now >= kill_t:
            on_kill()
            killed = True
        if trace[i][0] <= now:
            _, prompt, mnew = trace[i]
            i += 1
            try:
                streams.append(router.submit(prompt, max_new_tokens=mnew))
            except QueueFullError:
                rejected += 1
                streams.append(None)
            continue
        time.sleep(min(0.002, trace[i][0] - now))
    if not killed:
        on_kill()
    return streams, rejected, t0


def run_fleet_leg(engines, reps, trace, timeout, inflight_cap,
                  kill_frac=None):
    """One fleet replay over (reused, warm) engines behind a FRESH
    router (per-leg metric windows for free). ``kill_frac`` kills the
    highest-named replica that far into the trace's arrival window.
    Returns (leg dict, per-request token lists — None = rejected)."""
    import queue as _queue

    from mxnet_tpu.serving.fleet import Router

    router = Router(bind=None, pending_max=8 * len(trace),
                    inflight_cap=inflight_cap, health_interval=0.2)
    for r in reps:
        router.register_local(r.name, r)
    for e in engines:
        e.start()
    router.start(interval=0.002)

    victim = {"name": None}

    def kill():
        name = sorted(router._replicas)[-1]
        victim["name"] = name
        engines[[r.name for r in reps].index(name)].stop()

        class _Dead:
            def __getattr__(self, _):
                def boom(*a, **k):
                    raise ConnectionError("SIGKILL stand-in")
                return boom

        ent = router._replicas[name]
        ent.client = _Dead()
        ent.last_scrape_t = 0.0

    kill_t = None
    if kill_frac is not None:
        kill_t = trace[int(len(trace) * kill_frac)][0]
    streams, rejected, t0 = _submit_trace_fleet(
        router, trace, kill_t=kill_t,
        on_kill=(kill if kill_frac is not None else None))
    deadline = t0 + timeout
    outs, total_tokens, incomplete = [], 0, 0
    for s in streams:
        if s is None:
            outs.append(None)
            continue
        try:
            toks = s.result(timeout=max(1.0,
                                        deadline - time.monotonic()))
        except _queue.Empty:
            incomplete += 1
            toks = None
        outs.append(toks)
        total_tokens += len(toks or ())
    makespan = time.monotonic() - t0
    st = router.stats()
    router.close()
    for e in engines:
        e.stop()
        e.note_idle()
    leg = {
        "replicas": len(reps),
        "tokens_per_s": round(total_tokens / makespan, 2),
        "makespan_s": round(makespan, 3),
        "tokens_emitted": total_tokens,
        "ttft_p50_s": (round(st["ttft_p50_s"], 4)
                       if st["ttft_p50_s"] is not None else None),
        "ttft_p99_s": (round(st["ttft_p99_s"], 4)
                       if st["ttft_p99_s"] is not None else None),
        "requests_completed": st["completed"],
        "requests_rejected": rejected,
        "requests_incomplete": incomplete,
        "redeliveries": st["redelivered"],
        "evictions": st["evictions"],
    }
    if victim["name"] is not None:
        leg["killed_replica"] = victim["name"]
    return leg, outs


def run_singles_leg(engines, trace, timeout):
    """The no-router baseline: the same trace round-robined straight
    onto N independent engines (what you'd get from N processes behind
    a dumb splitter) — the fleet's routing/journal overhead is the
    delta against this."""
    import queue as _queue

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving import QueueFullError

    ttft0 = {id(e): len(e.latency_samples()[0]) for e in engines}
    for e in engines:
        e.start()
    handles, rejected = [], 0
    t0 = time.monotonic()
    i = 0
    while i < len(trace):
        now = time.monotonic() - t0
        if trace[i][0] <= now:
            _, prompt, mnew = trace[i]
            eng = engines[i % len(engines)]
            i += 1
            try:
                handles.append(eng.submit(prompt, max_new_tokens=mnew))
            except (QueueFullError, MXNetError):
                rejected += 1
            continue
        time.sleep(min(0.002, trace[i][0] - now))
    deadline = t0 + timeout
    total_tokens, incomplete = 0, 0
    for h in handles:
        try:
            total_tokens += len(h.result(
                timeout=max(1.0, deadline - time.monotonic())))
        except _queue.Empty:
            incomplete += 1
    makespan = time.monotonic() - t0
    ttfts = []
    for e in engines:
        samples = e.latency_samples()[0]
        ttfts.extend(samples[ttft0[id(e)]:])
        e.stop()
        e.note_idle()
    return {
        "engines": len(engines),
        "tokens_per_s": round(total_tokens / makespan, 2),
        "makespan_s": round(makespan, 3),
        "tokens_emitted": total_tokens,
        "ttft_p50_s": _pct(ttfts, 50),
        "ttft_p99_s": _pct(ttfts, 99),
        "requests_rejected": rejected,
        "requests_incomplete": incomplete,
    }


def main_fleet():
    """The --fleet leg (ISSUE 20): N socketless replicas behind the
    fleet router vs the same N engines driven directly, same seeded
    open-loop trace, plus a recovery-under-kill replay::

        {"metric": "serving_fleet_vs_direct", "value": <tokens/s
         ratio>, "fleet_tokens_per_s": ..., "fleet_ttft_p99_s": ...,
         "recovery": {"byte_identical": true, "requests_lost": 0, ...}}

    The ratio is the router's overhead story (>= ~0.9 of direct);
    ``recovery`` replays the SAME trace with a SIGKILL stand-in 40% in
    and checks every accepted request completed with a byte-identical
    stream vs the uninterrupted leg (greedy + identically-seeded
    replicas => redelivery must be invisible). Run with
    MXNET_TELEMETRY=1 + a journal to feed tools/perf_gate.py
    (fleet_tokens_per_s / fleet_ttft_p99_s, baseline
    tools/baselines/fleet_perf.json)."""
    n_reps = _env_int("BENCH_FLEET_REPLICAS", 4)
    d_model = _env_int("BENCH_SERVE_DMODEL", 64)
    layers = _env_int("BENCH_SERVE_LAYERS", 2)
    heads = _env_int("BENCH_SERVE_HEADS", 2)
    d_ff = _env_int("BENCH_SERVE_DFF", 128)
    vocab = _env_int("BENCH_SERVE_VOCAB", 512)
    n_req = _env_int("BENCH_SERVE_REQUESTS", 32)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    block_size = _env_int("BENCH_SERVE_BLOCK_SIZE", 16)
    kv_blocks = _env_int("BENCH_SERVE_KV_BLOCKS", 49)
    max_batch = _env_int("BENCH_SERVE_MAX_BATCH", 4)
    prefill_chunk = _env_int("BENCH_SERVE_PREFILL_CHUNK", 32)
    load = _env_float("BENCH_SERVE_LOAD", 1.2)
    timeout = _env_float("BENCH_SERVE_TIMEOUT", 240.0)
    kill_frac = _env_float("BENCH_FLEET_KILL_FRAC", 0.4)

    import jax

    from mxnet_tpu import telemetry as _tel
    from mxnet_tpu.models.transformer import TransformerConfig, init_params
    from mxnet_tpu.serving import Engine, ServingConfig
    from mxnet_tpu.serving.fleet import ReplicaServer

    model_cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, d_model=d_model,
        num_heads=heads, d_ff=d_ff, max_seq_len=128, dtype="float32")
    # ONE params tree shared by every replica (the fleet contract:
    # identically-seeded replicas, so any survivor continues any
    # stream byte-identically)
    params = init_params(model_cfg, jax.random.PRNGKey(seed))

    def mk_cfg(policy):
        return ServingConfig(
            block_size=block_size, num_blocks=kv_blocks,
            max_batch=max_batch, prefill_chunk=prefill_chunk,
            max_queue_depth=4 * n_req, policy=policy)

    rng = np.random.RandomState(seed)
    rate1, capacity = calibrate_rate(params, model_cfg, mk_cfg,
                                     TRACE_MEAN_TOKENS, load)
    trace = make_trace(n_req, rate1 * n_reps, vocab, rng)

    engines, reps = [], []
    for i in range(n_reps):
        eng = Engine(params, model_cfg, mk_cfg("continuous"))
        warmup(eng, params)
        engines.append(eng)
        reps.append(ReplicaServer(eng, name="replica%d" % i, bind=None))
    inflight_cap = 2 * max_batch

    fleet_leg, fleet_outs = run_fleet_leg(engines, reps, trace, timeout,
                                          inflight_cap)
    print("bench_serve[fleet]: %.1f tok/s, p99 TTFT %.3fs, %d completed"
          % (fleet_leg["tokens_per_s"], fleet_leg["ttft_p99_s"] or -1,
             fleet_leg["requests_completed"]), file=sys.stderr)
    direct_leg = run_singles_leg(engines, trace, timeout)
    print("bench_serve[direct]: %.1f tok/s, p99 TTFT %.3fs"
          % (direct_leg["tokens_per_s"], direct_leg["ttft_p99_s"] or -1),
          file=sys.stderr)
    kill_leg, kill_outs = run_fleet_leg(engines[:], reps, trace, timeout,
                                        inflight_cap,
                                        kill_frac=kill_frac)
    # lossless recovery: every request BOTH legs accepted must match
    # byte for byte; the kill leg must lose nothing it accepted
    lost = sum(1 for o in kill_outs if o is None)
    mismatches = sum(
        1 for a, b in zip(fleet_outs, kill_outs)
        if a is not None and b is not None and a != b)
    kill_leg.update({
        "requests_lost": lost - kill_leg["requests_rejected"],
        "byte_identical": mismatches == 0,
        "stream_mismatches": mismatches,
    })
    print("bench_serve[kill]: %.1f tok/s, redeliveries %d, lost %d, "
          "byte_identical %s"
          % (kill_leg["tokens_per_s"], kill_leg["redeliveries"],
             kill_leg["requests_lost"], kill_leg["byte_identical"]),
          file=sys.stderr)

    ratio = fleet_leg["tokens_per_s"] / max(direct_leg["tokens_per_s"],
                                            1e-9)
    if _tel.ENABLED:
        _tel.flush(mark="bench_fleet")
    print(json.dumps({
        "metric": "serving_fleet_vs_direct",
        "value": round(ratio, 3),
        "unit": "x tokens/s",
        "vs_baseline": round(ratio / 0.9, 3),  # >= 1.0: overhead < 10%
        # top-level fields tools/perf_gate.py lifts from a judged record
        "fleet_tokens_per_s": fleet_leg["tokens_per_s"],
        "fleet_ttft_p99_s": fleet_leg["ttft_p99_s"],
        "offered_load_req_s": round(rate1 * n_reps, 3),
        "decode_capacity_tokens_s_per_replica": round(capacity, 1),
        "fleet": fleet_leg,
        "direct": direct_leg,
        "recovery": kill_leg,
        "config": {"replicas": n_reps, "d_model": d_model,
                   "layers": layers, "heads": heads, "d_ff": d_ff,
                   "vocab": vocab, "requests": n_req,
                   "block_size": block_size, "kv_blocks": kv_blocks,
                   "max_batch": max_batch,
                   "prefill_chunk": prefill_chunk, "load": load,
                   "seed": seed, "kill_frac": kill_frac},
    }))


if __name__ == "__main__":
    if "--spec" in sys.argv[1:]:
        main_spec()
    elif "--fleet" in sys.argv[1:]:
        main_fleet()
    else:
        main()
