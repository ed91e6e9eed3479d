"""Speculative decoding + fused on-device sampling (ISSUE 15).

The load-bearing parity contract, extending the PR 8 pinning style:
**speculation must never change what a client stream sees at
temperature 0** — spec-decode byte-matches greedy decode through the
plain full-sequence ``transformer.forward`` across partial accepts,
evictions, cancels, and chunked prefill; and the fused on-device
sampler byte-matches the host-side reference sampler given the same
seed. The off-by-default contract is structural: no draft pool, no
draft/verify programs, no spec metrics unless ``ServingConfig.spec``.
"""
import json

import numpy as np
import pytest

import mxnet_tpu.telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (Engine, PagedKVPool, Request, Scheduler,
                               ServingConfig)
from mxnet_tpu.serving import sampling as samp


# -- shared tiny models (module scope: jit compiles amortized) ----------------
@pytest.fixture(scope="module")
def model():
    import jax

    from mxnet_tpu.models.transformer import (TransformerConfig, forward,
                                              init_params)

    cfg = TransformerConfig(vocab_size=61, num_layers=2, d_model=32,
                            num_heads=2, d_ff=64, max_seq_len=96,
                            dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def greedy_ref(prompt, n):
        seq = [int(t) for t in prompt]
        out = []
        for _ in range(n):
            logits = forward(params, np.asarray([seq], np.int32), cfg)
            t = int(np.argmax(np.asarray(logits)[0, -1]))
            out.append(t)
            seq.append(t)
        return out

    return cfg, params, greedy_ref


@pytest.fixture(scope="module")
def draft(model):
    """An independent random draft (the adversarial case: essentially
    every proposal is rejected — parity must hold regardless)."""
    import jax

    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_params)

    cfg = TransformerConfig(vocab_size=61, num_layers=1, d_model=16,
                            num_heads=2, d_ff=32, max_seq_len=96,
                            dtype="float32")
    return init_params(cfg, jax.random.PRNGKey(7)), cfg


@pytest.fixture(scope="module")
def aligned_draft(model):
    """A draft truncated from the target (shared embeddings, first
    layer) — agrees often, exercising real partial-accept paths."""
    import dataclasses

    cfg, params, _ = model
    dparams = {"embed": params["embed"], "pos_embed": params["pos_embed"],
               "layers": params["layers"][:1], "ln_f": params["ln_f"]}
    return dparams, dataclasses.replace(cfg, num_layers=1)


def _mk_spec_engine(model, draft_pair, spec_k=3, **kw):
    cfg, params, _ = model
    dparams, dcfg = draft_pair
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 33)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("token_budget", 64)
    return Engine(params, cfg,
                  ServingConfig(spec=True, spec_k=spec_k, **kw),
                  draft_params=dparams, draft_cfg=dcfg)


def _prompts(rng, n, vocab, lo=5, hi=20):
    return [rng.randint(0, vocab, (int(rng.randint(lo, hi)),)
                        ).astype(np.int32) for _ in range(n)]


# -- greedy byte-match parity -------------------------------------------------
class TestSpecGreedyParity:
    def test_random_draft_byte_match(self, model, draft):
        """Near-zero accept rate (independent random draft): every
        emitted token still comes from the target's argmax."""
        cfg, params, greedy_ref = model
        eng = _mk_spec_engine(model, draft)
        rng = np.random.RandomState(3)
        prompts = _prompts(rng, 3, cfg.vocab_size)
        outs = eng.generate(prompts, max_new_tokens=8)
        for p, o in zip(prompts, outs):
            assert o == greedy_ref(p, 8)
        st = eng.stats()
        assert st["spec_turns"] > 0 and st["spec_tokens_drafted"] > 0

    def test_aligned_draft_partial_accepts_byte_match(self, model,
                                                      aligned_draft):
        """A truncation-of-target draft accepts a real fraction of
        proposals — the partial-accept rollback path — with the stream
        still byte-identical to full greedy."""
        cfg, params, greedy_ref = model
        eng = _mk_spec_engine(model, aligned_draft)
        rng = np.random.RandomState(4)
        prompts = _prompts(rng, 4, cfg.vocab_size)
        outs = eng.generate(prompts, max_new_tokens=10)
        for p, o in zip(prompts, outs):
            assert o == greedy_ref(p, 10)

    def test_identical_draft_accepts_everything(self, model):
        """draft == target: every proposal verifies (q == p bit-exact),
        the turn emits k+1 tokens, and the stream is still the greedy
        stream."""
        cfg, params, greedy_ref = model
        eng = _mk_spec_engine(model, (params, cfg))
        rng = np.random.RandomState(5)
        prompts = _prompts(rng, 2, cfg.vocab_size)
        outs = eng.generate(prompts, max_new_tokens=9)
        for p, o in zip(prompts, outs):
            assert o == greedy_ref(p, 9)
        st = eng.stats()
        assert st["spec_tokens_accepted"] == st["spec_tokens_drafted"] > 0
        assert st["spec_accept_rate"] == 1.0

    def test_eviction_recompute_spec_parity(self, model, aligned_draft):
        """Preemption under KV pressure: both block tables drop, the
        recompute context re-prefills BOTH pools, and the stream is
        unchanged. Pool lockstep holds throughout and both pools drain
        to zero."""
        cfg, params, greedy_ref = model
        rng = np.random.RandomState(6)
        prompts = _prompts(rng, 4, cfg.vocab_size, lo=8, hi=16)
        eng = _mk_spec_engine(model, aligned_draft, num_blocks=12)
        outs = eng.generate(prompts, max_new_tokens=10)
        assert eng.stats()["evicted"] > 0, "pool was meant to force evictions"
        for p, o in zip(prompts, outs):
            assert o == greedy_ref(p, 10)
        assert eng.pool.num_used == 0
        assert eng.draft_pool.num_used == 0

    def test_chunked_prefill_then_spec(self, model, aligned_draft):
        """A prompt longer than prefill_chunk prefills over several
        steps (draft pool mirrored chunk by chunk), then spec-decodes
        — byte-identical to full greedy."""
        cfg, params, greedy_ref = model
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, cfg.vocab_size, (40,)).astype(np.int32)
        eng = _mk_spec_engine(model, aligned_draft, prefill_chunk=16)
        out = eng.generate([prompt], max_new_tokens=6)[0]
        assert out == greedy_ref(prompt, 6)

    def test_mid_decode_cancel_frees_both_pools(self, model, aligned_draft):
        cfg, params, _ = model
        eng = _mk_spec_engine(model, aligned_draft)
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        h = eng.submit(prompt, max_new_tokens=50)
        for _ in range(4):
            eng.step()
        assert eng.pool.num_used > 0 and eng.draft_pool.num_used > 0
        h.cancel()
        eng.run_until_idle()
        toks = h.result(timeout=5)
        assert h.status == "cancelled"
        assert 0 < len(toks) < 50
        assert eng.pool.num_used == 0
        assert eng.draft_pool.num_used == 0
        # lockstep invariant never broke: both pools drained equal
        assert eng.pool.num_free == eng.pool.capacity
        assert eng.draft_pool.num_free == eng.draft_pool.capacity

    def test_runtime_toggle_and_catchup(self, model, aligned_draft):
        """set_spec(False) mid-request falls back to plain fused
        decode; re-enabling catches the draft pool up past the lag —
        the stream stays byte-identical throughout."""
        cfg, params, greedy_ref = model
        eng = _mk_spec_engine(model, aligned_draft)
        rng = np.random.RandomState(10)
        prompt = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
        h = eng.submit(prompt, max_new_tokens=14)
        for _ in range(2):
            eng.step()
        eng.set_spec(False)
        assert not eng.spec_enabled
        turns0 = eng.stats()["spec_turns"]
        for _ in range(4):
            eng.step()
        assert eng.stats()["spec_turns"] == turns0  # plain decode only
        eng.set_spec(True)
        eng.run_until_idle()
        assert h.result() == greedy_ref(prompt, 14)
        assert eng.stats()["spec_turns"] > turns0

    def test_set_spec_requires_configuration(self, model):
        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=33, max_batch=4))
        with pytest.raises(MXNetError):
            eng.set_spec(True)

    def test_invalid_sampling_params_rejected(self, model):
        """top_p <= 0 would mask every token (NaN distribution) —
        submit rejects bad sampling params loudly instead of sampling
        garbage silently."""
        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=33, max_batch=4))
        p = np.zeros((4,), np.int32)
        for kw in ({"temperature": 1.0, "top_p": 0.0},
                   {"temperature": -0.5}, {"top_k": -1},
                   {"top_p": 1.5}):
            with pytest.raises(MXNetError):
                eng.submit(p, max_new_tokens=2, **kw)
        assert eng.stats()["rejected"] == 4

    def test_spec_default_token_budget_leaves_prefill_headroom(self):
        """The spec-aware budget default: a full decode batch's verify
        chunks must not consume the whole step budget (prefill would
        starve for the life of the batch)."""
        plain = ServingConfig(block_size=8, num_blocks=33)
        spec = ServingConfig(block_size=8, num_blocks=33, spec=True,
                             spec_k=4)
        assert plain.token_budget == plain.max_batch + plain.prefill_chunk
        assert spec.token_budget == (spec.max_batch * 5
                                     + spec.prefill_chunk)


# -- fused sampler ------------------------------------------------------------
class TestFusedSampler:
    @pytest.mark.parametrize("temp,top_k,top_p",
                             [(0.8, 0, 1.0), (1.3, 10, 1.0),
                              (0.9, 0, 0.8), (1.0, 7, 0.9)])
    def test_device_sampler_matches_host_reference(self, model, temp,
                                                   top_k, top_p):
        """The on-device fused sampler and the numpy host reference
        draw IDENTICAL tokens given the same (seed, position) — pinned
        per filtering mode."""
        from mxnet_tpu.models.transformer import forward

        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=65, max_batch=4, prefill_chunk=16))
        rng = np.random.RandomState(11)
        prompts = _prompts(rng, 3, cfg.vocab_size)
        hs = [eng.submit(p, max_new_tokens=6, temperature=temp,
                         top_k=top_k, top_p=top_p, seed=21 + i)
              for i, p in enumerate(prompts)]
        eng.run_until_idle()
        for i, (p, h) in enumerate(zip(prompts, hs)):
            seq = [int(t) for t in p]
            ref = []
            for _ in range(6):
                logits = np.asarray(forward(
                    params, np.asarray([seq], np.int32), cfg))[0, -1]
                t = samp.host_sample(logits, temp, top_k, top_p, 21 + i,
                                     len(seq))
                ref.append(t)
                seq.append(t)
            assert h.result() == ref

    def test_sampled_spec_deterministic_and_seeded(self, model,
                                                   aligned_draft):
        """Position-keyed PRNG: the same seed replays the same sampled
        stream through the SPECULATIVE path (two fresh engines), and a
        different seed diverges."""
        cfg, params, _ = model
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)

        def run(seed):
            eng = _mk_spec_engine(model, aligned_draft)
            h = eng.submit(prompt, max_new_tokens=10, temperature=0.9,
                           seed=seed)
            eng.run_until_idle()
            return h.result()

        a, b = run(33), run(33)
        assert a == b
        assert run(34) != a  # vanishing-probability collision aside

    def test_identical_draft_sampled_accepts_everything(self, model):
        """q == p bit-exact => accept ratio 1 => rejection sampling
        accepts every draft even at temperature > 0 (the accept-path
        correctness anchor)."""
        cfg, params, _ = model
        eng = _mk_spec_engine(model, (params, cfg))
        rng = np.random.RandomState(13)
        prompts = _prompts(rng, 2, cfg.vocab_size)
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=8, temperature=1.1, seed=40 + i)
        eng.run_until_idle()
        st = eng.stats()
        assert st["spec_tokens_accepted"] == st["spec_tokens_drafted"] > 0

    def test_plain_eviction_replays_identical_samples(self, model):
        """Draws keyed by (seed, position): on the PLAIN fused-sampling
        path an evicted+recomputed request emits the same sampled
        stream an un-evicted run does — eviction is invisible to the
        client even with temperature on.

        (Speculative mode guarantees this only at temperature 0: a
        shifted turn alignment changes which salt stream a position
        draws from — accepted draft vs residual vs bonus — which is
        distribution-preserving by the rejection-sampling construction
        but not byte-stable. Spec determinism for a FIXED schedule is
        pinned by test_sampled_spec_deterministic_and_seeded.)"""
        cfg, params, _ = model
        rng = np.random.RandomState(14)
        prompts = _prompts(rng, 4, cfg.vocab_size, lo=8, hi=16)

        def run(num_blocks):
            eng = Engine(params, cfg, ServingConfig(
                block_size=8, num_blocks=num_blocks, max_batch=4,
                prefill_chunk=16))
            hs = [eng.submit(p, max_new_tokens=10, temperature=0.8,
                             seed=50 + i) for i, p in enumerate(prompts)]
            eng.run_until_idle()
            return [h.result() for h in hs], eng.stats()["evicted"]

        tight, evicted = run(12)
        roomy, _ = run(65)
        assert evicted > 0
        assert tight == roomy


# -- off-by-default zero overhead ---------------------------------------------
class TestSpecOffByDefault:
    def test_env_default_off(self):
        assert ServingConfig(block_size=8, num_blocks=4).spec is False

    def test_no_draft_pool_no_extra_programs(self, model):
        """Without spec: no draft objects exist and every compiled
        program is a plain 'step' — the structural zero-overhead
        guarantee."""
        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=33, max_batch=4, prefill_chunk=16))
        eng.generate(_prompts(np.random.RandomState(1), 2,
                              cfg.vocab_size), max_new_tokens=4)
        assert eng.draft_model is None and eng.draft_pool is None
        assert all(k[0] == "step" for k in eng.model._jitted)
        st = eng.stats()
        assert st["spec_turns"] == 0 and st["spec_accept_rate"] is None

    def test_draft_without_spec_rejected(self, model, draft):
        cfg, params, _ = model
        dparams, dcfg = draft
        with pytest.raises(MXNetError):
            Engine(params, cfg, ServingConfig(block_size=8, num_blocks=33),
                   draft_params=dparams, draft_cfg=dcfg)

    def test_no_spec_metrics_registered(self, model, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        tel.reset()
        tel.reload()
        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=33, max_batch=4))
        eng.generate([np.zeros((4,), np.int32)], max_new_tokens=3)
        snap = tel.snapshot()
        assert not any(k.startswith("serving.spec")
                       for k in list(snap["counters"]) + list(snap["gauges"]))


# -- telemetry + zero-logits-D2H proof ----------------------------------------
class TestSpecTelemetry:
    def test_spec_catalog_and_d2h_bytes(self, model, aligned_draft,
                                        monkeypatch, tmp_path):
        """With telemetry+prof on: the serving.spec_* catalog lands,
        the step breakdown carries the draft/verify split, and every
        steady-state decode record's d2h_bytes is token-sized — a
        logits pull would be >= 4 * vocab * batch bytes (the
        zero-logits-D2H acceptance gate)."""
        journal = tmp_path / "spec.jsonl"
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_TELEMETRY_JOURNAL", str(journal))
        monkeypatch.setenv("MXNET_PROF", "1")
        tel.reset()
        tel.reload()
        from mxnet_tpu.telemetry import prof
        prof.reload()
        prof.reset()
        try:
            cfg, params, _ = model
            eng = _mk_spec_engine(model, aligned_draft)
            rng = np.random.RandomState(15)
            eng.generate(_prompts(rng, 4, cfg.vocab_size),
                         max_new_tokens=12)
            snap = tel.snapshot()
            c, g, h = (snap["counters"], snap["gauges"],
                       snap["histograms"])
            assert c["serving.spec_turns"] > 0
            assert c["serving.spec_tokens_drafted"] > 0
            assert "serving.spec_accept_rate" in g
            assert h["serving.spec_accepted_tokens"]["count"] > 0
            # draft/verify step-time split via the prof step breakdown
            steps = prof.step_summary()
            assert "serve.spec_draft" in steps
            assert "serve.spec_verify" in steps
            tel.flush(mark="final")
            recs = [json.loads(l) for l in
                    journal.read_text().splitlines() if l.strip()]
            bds = [r for r in recs if r.get("kind") == "prof"
                   and r.get("event") == "step_breakdown"
                   and r.get("path") in ("serve.decode",
                                         "serve.spec_verify")
                   and "d2h_bytes" in r]
            assert bds, "no decode step breakdowns journaled"
            logits_floor = 4 * cfg.vocab_size  # one f32 logits ROW
            for r in bds:
                assert r["d2h_bytes"] < logits_floor, r
        finally:
            monkeypatch.undo()
            tel.reset()
            tel.reload()
            from mxnet_tpu.telemetry import prof
            prof.reload()

    def test_probe_metrics_expose_accept_rate(self, model, aligned_draft):
        """mxctl's serving_metrics mapping (control/probes.py) surfaces
        spec_accept_rate so rules can actuate on it."""
        from mxnet_tpu.control.probes import serving_metrics

        cfg, params, _ = model
        eng = _mk_spec_engine(model, aligned_draft)
        eng.generate(_prompts(np.random.RandomState(16), 2,
                              cfg.vocab_size), max_new_tokens=8)
        payload = {"engines": [eng.introspect()]}
        out = serving_metrics(payload)
        assert "spec_accept_rate" in out
        assert 0.0 <= out["spec_accept_rate"] <= 1.0
        # the probe reads the WINDOWED rate (current draft quality;
        # the lifetime average goes inert with uptime) — fresh run, so
        # the two coincide
        st = eng.stats()
        assert st["spec_accept_rate_window"] == pytest.approx(
            out["spec_accept_rate"])
        assert st["spec_window_drafted"] == st["spec_tokens_drafted"]


# -- scheduler: spec budget + event ring --------------------------------------
class TestSchedulerSpec:
    def test_spec_token_budget_caps_decode(self):
        """Each speculative slot costs 1 + spec_k budget tokens: a
        budget of 10 at spec_k=4 admits two decode rows per step, not
        max_batch."""
        pool = PagedKVPool(1, 1, 4, num_blocks=65, block_size=4)
        dpool = pool.mirror(1, 1, 4)
        sched = Scheduler(pool, max_batch=8, prefill_chunk=8,
                          token_budget=10, draft_pool=dpool, spec_k=4,
                          max_active=8)
        reqs = [Request(np.zeros(3, np.int32), max_new_tokens=20)
                for _ in range(4)]
        for r in reqs:
            sched.submit(r)
        plan = sched.plan()
        for req, _, clen in plan.prefill:
            sched.note_prefilled(req, clen)
            req.generated.append(0)
        plan = sched.plan()
        assert len(plan.decode) == 2          # 2 * (1+4) = 10 = budget
        assert all(plan.spec_k[r.rid] == 4 for r in plan.decode)

    def test_tight_budget_shrinks_chain_instead_of_starving(self):
        """A budget that can't fit a full spec_k chain shrinks the
        row's draft count (down to plain decode at cost 1) rather than
        starving every row behind the first misfit — head-of-line
        decode starvation under a legacy-sized explicit budget."""
        pool = PagedKVPool(1, 1, 4, num_blocks=65, block_size=4)
        dpool = pool.mirror(1, 1, 4)
        sched = Scheduler(pool, max_batch=4, prefill_chunk=8,
                          token_budget=7, draft_pool=dpool, spec_k=4,
                          max_active=4)
        reqs = [Request(np.zeros(3, np.int32), max_new_tokens=20)
                for _ in range(3)]
        for r in reqs:
            sched.submit(r)
        plan = sched.plan()
        for req, _, clen in plan.prefill:
            sched.note_prefilled(req, clen)
            req.generated.append(0)
        plan = sched.plan()
        ks = [plan.spec_k[r.rid] for r in plan.decode]
        # 1+4 then 1+1 consumes the 7-token budget exactly; the third
        # row waits (left == 0), nothing behind a misfit starves
        assert ks == [4, 1]

    def test_final_token_rides_plain_decode(self):
        """remaining == 1 => k == 0: the last token of a request never
        pays a draft chain."""
        pool = PagedKVPool(1, 1, 4, num_blocks=65, block_size=4)
        dpool = pool.mirror(1, 1, 4)
        sched = Scheduler(pool, max_batch=4, prefill_chunk=8,
                          token_budget=32, draft_pool=dpool, spec_k=4)
        r = Request(np.zeros(3, np.int32), max_new_tokens=3)
        sched.submit(r)
        plan = sched.plan()
        sched.note_prefilled(r, 3)
        r.generated.extend([0, 0])            # remaining == 1
        plan = sched.plan()
        assert plan.decode == [r] and plan.spec_k[r.rid] == 0

    def test_trim_blocks_rolls_back_both_tables(self):
        pool = PagedKVPool(1, 1, 4, num_blocks=65, block_size=4)
        dpool = pool.mirror(1, 1, 4)
        sched = Scheduler(pool, max_batch=4, prefill_chunk=8,
                          token_budget=32, draft_pool=dpool, spec_k=4)
        r = Request(np.zeros(4, np.int32), max_new_tokens=20)
        sched.submit(r)
        sched.plan()
        sched.note_prefilled(r, 4)
        r.generated.append(0)
        plan = sched.plan()                    # horizon alloc for k=4
        assert plan.spec_k[r.rid] == 4
        held = len(r.blocks)
        assert held == len(r.draft_blocks) >= 3  # covers pos 4+4-1=8
        # only 1 draft accepted -> 2 tokens emitted; roll back
        r.generated.extend([0, 0])
        sched.trim_blocks(r)
        assert len(r.blocks) == len(r.draft_blocks) == 2  # pos 6 -> 2
        assert pool.num_free == dpool.num_free

    def test_events_ring_bounded_with_total(self):
        """Regression: the deterministic event log is a ring — a
        long-lived scheduler's memory no longer grows without bound,
        while events_total keeps the true count and introspection
        renders the tail."""
        pool = PagedKVPool(1, 1, 4, num_blocks=65, block_size=4)
        sched = Scheduler(pool, max_batch=2, prefill_chunk=8,
                          events_max=16)
        for i in range(30):
            r = Request(np.zeros(2, np.int32), max_new_tokens=1)
            sched.submit(r)
            sched.plan()
            sched.note_prefilled(r, 2)
            r.generated.append(0)
            sched.finish(r)
        assert len(sched.events) == 16
        assert sched.events_total == 60       # 30 admits + 30 completes
        assert sched.counts["admit"] == 30    # counters unaffected

    def test_engine_introspect_event_tail(self, model):
        cfg, params, _ = model
        eng = Engine(params, cfg, ServingConfig(
            block_size=8, num_blocks=33, max_batch=4, events_max=8))
        eng.generate(_prompts(np.random.RandomState(17), 6,
                              cfg.vocab_size), max_new_tokens=3)
        out = eng.introspect(event_tail=5)
        assert len(out["events"]) <= 5
        assert out["events_total"] == eng.sched.events_total > 8
        assert len(eng.sched.events) <= 8
