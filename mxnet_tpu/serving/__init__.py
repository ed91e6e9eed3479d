"""mxserve: paged-KV continuous-batching inference.

The production serving story the "millions of users" north star needs
(ROADMAP.md; PAPERS.md "Ragged Paged Attention"), sitting next to the
single-request fixed-shape ``Predictor``:

- :mod:`.kv_cache` — paged KV-cache allocator: fixed-size blocks in one
  preallocated device pool, per-request block tables, OOM backpressure
  (plus ``mirror()`` — the draft model's lockstep pool);
- :mod:`.model` — ragged batches assembled into fixed bucketed shapes
  over ``models/transformer.py`` params: one jitted step covers prefill
  chunks and single-token decode, plus the speculative draft-turn and
  verify programs, warm across processes via the PR 6 persistent jit
  cache;
- :mod:`.sampling` — fused on-device sampling (temperature/top-k/top-p,
  position-keyed per-request PRNG, speculative rejection-resampling):
  logits never leave the device;
- :mod:`.scheduler` — continuous batching: admit/evict per decode step
  against a token budget (speculative slots cost their whole verify
  chunk), prefill/decode split, recompute-style preemption;
- :mod:`.engine` — the request front-end: ``Engine.submit(prompt) ->
  stream of tokens``, a synchronous ``generate`` batch API,
  cancellation, max-queue-depth admission control, draft-model
  speculative decoding (``MXNET_SERVE_SPEC``, off by default), and the
  ``serving.*`` mxtel catalog.

On the chip the engine runs in ``chip_smoke.py``; the benchmark has no
serving cell yet (PERF.md section 7). Guide: docs/how_to/serving.md.
"""
from __future__ import annotations

from .engine import (Engine, QueueFullError, ServingConfig, StreamHandle,
                     live_engines)
from .kv_cache import PagedKVPool, blocks_for_tokens
from .model import ServingModel, cp_prefill_kv
from .scheduler import Request, Scheduler, StepPlan

__all__ = [
    "Engine", "ServingConfig", "StreamHandle", "QueueFullError",
    "PagedKVPool", "blocks_for_tokens", "ServingModel", "cp_prefill_kv",
    "Request", "Scheduler", "StepPlan", "live_engines",
]
