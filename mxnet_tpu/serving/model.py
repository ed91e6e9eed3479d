"""Decode-model adapter: bucketed ragged batches over a paged KV pool.

Bridges ``models/transformer.py`` (pure-function training forward) to
the serving engine's incremental decode. One jitted *step* function
covers both phases:

- **prefill chunk**: ``C`` prompt tokens per request enter at arbitrary
  start offsets, attend causally to their own chunk plus everything the
  request already has in the paged pool, and write their K/V into the
  pool blocks named by the request's block table;
- **decode**: the same function at ``C == 1`` — one new token per
  request per step.

Three program kinds share the transformer body (ISSUE 15):

- ``step`` — prefill/decode with the fused sampler
  (:mod:`.sampling`): the sampled next token is computed ON DEVICE, so
  the only per-step D2H is the ``[B]`` token vector (the old path
  pulled the full ``[B, V]`` logits every decode step);
- ``propose`` — the draft model's proposal step: sampled token plus the
  filtered draft distribution ``q`` (kept on device for the verifier);
- ``verify`` — the speculative verify: target logits at ALL ``K+1``
  chunk positions, accept/reject against the draft proposals, and
  rejection-resampling / bonus sampling, all inside one program. A
  ``chunk_len == 1`` row degenerates to plain sampled decode, which is
  how non-spec rows ride the same math.

Ragged batches (every request at a different length) are assembled into
**fixed bucketed shapes**: batch rows pad to the next configured batch
bucket, chunk lengths pad to the next chunk bucket, and the block-table
width is a compile-time constant — so the number of distinct XLA
programs is bounded by ``len(kinds) x len(batch_buckets) x
len(chunk_buckets)`` and warm across processes via the PR 6 persistent
jit cache (``JAX_COMPILATION_CACHE_DIR``); the jit/prof cache keys fold
the program KIND alongside the bucket, so a verify program can never
alias a plain step at the same shapes. Padded lanes redirect their K/V
writes to the pool's scratch block 0 and are masked out of attention
reads, so padding never corrupts real state.

Numerical contract: a token decoded through the paged path produces the
same logits as ``transformer.forward`` over the whole sequence would at
that position (same op order, same f32 softmax accumulation), which is
what makes continuous batching a pure scheduling win — and at
``temperature == 0`` the fused sampler is exact argmax, so greedy
parity (spec or not) is byte-for-byte.

Long-context prefill on a mesh reuses the context-parallel attention in
``parallel/ring_attention.py`` / ``parallel/ulysses.py``: chunked
prefill is exactly their new ``q_offset`` form (queries are a suffix of
the key sequence), see :func:`cp_prefill_kv`.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from ..analysis import compile_verify as _cv
from ..models.transformer import TransformerConfig, _layer_norm
from . import sampling as _samp

__all__ = ["ServingModel", "bucket_for", "cp_prefill_kv"]


def bucket_for(n, buckets):
    """Smallest bucket >= n (buckets sorted ascending); raises when n
    exceeds every bucket — the caller sized its batch wrong."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError("no bucket fits %d (buckets %s)" % (n, list(buckets)))


class ServingModel:
    """Jitted paged-attention step functions over transformer params.

    Parameters
    ----------
    cfg : TransformerConfig
        Model geometry (the config object ``models/transformer.py`` trains).
    block_size : int
        Paged-pool tokens per block.
    max_blocks_per_req : int
        Block-table width ``W`` — a compile-time constant; a request
        can span at most ``W * block_size`` total tokens.
    batch_buckets, chunk_buckets : tuple of int
        Padded batch sizes / chunk lengths (ascending). Decode always
        uses chunk bucket 1 (its own program).
    """

    def __init__(self, cfg: TransformerConfig, block_size,
                 max_blocks_per_req, batch_buckets=(1, 2, 4, 8),
                 chunk_buckets=(32, 64, 128)):
        self.cfg = cfg
        self.block_size = int(block_size)
        self.max_blocks = int(max_blocks_per_req)
        self.batch_buckets = tuple(sorted(set(int(b) for b in batch_buckets)))
        self.chunk_buckets = tuple(sorted(set(int(c) for c in chunk_buckets)))
        self._jitted = {}  # (kind, B, C) -> compiled program
        self._prof_keys = {}  # (kind, B, C) -> mxprof program key
        # bucket-derived compile budgets: decode (C=1) plus one program
        # per (batch, chunk) bucket pair; draft_turn/verify budgets are
        # per (batch, K) but K is static per engine — bound by batches
        n_bc = len(self.batch_buckets) * (len(self.chunk_buckets) + 1)
        _cv.declare_budget("serve.step", n_bc)
        _cv.declare_budget("serve.draft_turn", n_bc)
        _cv.declare_budget("serve.verify", len(self.batch_buckets))

    # -- the transformer body ------------------------------------------------
    def _body(self, params, kpool, vpool, tokens, start, chunk_len,
              block_tables, active):
        """One fused forward over ``C`` new tokens per request.

        tokens [B, C] int32, start [B] int32 (global position of
        tokens[:, 0]), chunk_len [B] int32 (real tokens this chunk, 0
        for padded rows), block_tables [B, W] int32, active [B] bool.
        Returns (x [B, C, d_model] post-ln_f hidden states, kpool,
        vpool).
        """
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        B, C = tokens.shape
        W, bs = self.max_blocks, self.block_size
        S = W * bs
        H, D = cfg.num_heads, cfg.head_dim
        scale = 1.0 / float(D) ** 0.5

        pos = start[:, None] + jnp.arange(C)[None, :]            # [B, C]
        in_chunk = jnp.arange(C)[None, :] < chunk_len[:, None]   # [B, C]
        valid = in_chunk & active[:, None]
        # pos_embed rows are clipped for padded lanes (jnp.take clips);
        # their outputs are never read back
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + jnp.take(params["pos_embed"], jnp.minimum(
            pos, cfg.max_seq_len - 1), axis=0).astype(x.dtype)

        # K/V write coordinates: padded / inactive lanes redirect to the
        # scratch block 0 (kv_cache.py module docstring)
        blk_idx = jnp.clip(pos // bs, 0, W - 1)                  # [B, C]
        table_blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
        write_blk = jnp.where(valid, table_blk, 0)               # [B, C]
        write_slot = jnp.where(valid, pos % bs, 0)               # [B, C]

        # pool key positions: slot (w, i) of a request's table holds its
        # token w*bs + i
        key_pos = jnp.arange(S)                                  # [S]
        # keys already in the pool are those strictly before this
        # chunk's first token; the chunk attends to itself causally
        pool_mask = key_pos[None, None, :] < start[:, None, None]  # [B,1,S]
        pool_mask = jnp.broadcast_to(pool_mask, (B, C, S))
        chunk_mask = (jnp.arange(C)[None, :, None] >=
                      jnp.arange(C)[None, None, :]) & in_chunk[:, None, :]
        chunk_mask = jnp.broadcast_to(chunk_mask, (B, C, C))
        full_mask = jnp.concatenate([pool_mask, chunk_mask], axis=2)
        neg = jnp.asarray(-1e30, jnp.float32)

        for li, lp in enumerate(params["layers"]):
            h = _layer_norm(x, lp["ln1"])
            qkv = jnp.einsum("bcd,de->bce", h, lp["wqkv"])
            q, k, v = jnp.split(qkv, 3, axis=-1)
            k = k.reshape(B, C, H, D)
            v = v.reshape(B, C, H, D)
            # write this chunk's K/V into the pool (scatter; scratch
            # absorbs padded lanes)
            kpool = kpool.at[li, write_blk, write_slot].set(
                k.astype(kpool.dtype))
            vpool = vpool.at[li, write_blk, write_slot].set(
                v.astype(vpool.dtype))
            # gather the request's paged history [B, S, H, D]
            k_hist = kpool[li][block_tables].reshape(B, S, H, D)
            v_hist = vpool[li][block_tables].reshape(B, S, H, D)
            k_all = jnp.concatenate([k_hist.astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([v_hist.astype(v.dtype), v], axis=1)

            qh = q.reshape(B, C, H, D)
            scores = jnp.einsum("bchd,bshd->bhcs", qh, k_all) * scale
            scores = jnp.where(full_mask[:, None], scores.astype(jnp.float32),
                               neg)
            m = jnp.max(scores, axis=-1, keepdims=True)
            p = jnp.exp(scores - m)
            p = p * jnp.any(full_mask[:, None], axis=-1,
                            keepdims=True).astype(p.dtype)
            l = jnp.sum(p, axis=-1, keepdims=True)
            p = p / jnp.maximum(l, 1e-30)
            o = jnp.einsum("bhcs,bshd->bchd", p.astype(v_all.dtype), v_all)
            o = o.reshape(B, C, H * D)
            x = x + jnp.einsum("bcd,de->bce", o, lp["wo"])
            h = _layer_norm(x, lp["ln2"])
            ff = jax.nn.gelu(jnp.einsum("bcd,df->bcf", h, lp["w1"]))
            x = x + jnp.einsum("bcf,fd->bcd", ff, lp["w2"])

        return _layer_norm(x, params["ln_f"]), kpool, vpool

    def _last_logits(self, params, x, chunk_len):
        """Logits at each row's last real chunk position — the one spot
        the next token can be sampled from. [B, V] f32."""
        import jax.numpy as jnp

        C = x.shape[1]
        last = jnp.clip(chunk_len - 1, 0, C - 1)                 # [B]
        x_last = jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return jnp.einsum("bd,vd->bv", x_last,
                          params["embed"]).astype(jnp.float32)

    # -- program kinds -------------------------------------------------------
    def _step_impl(self, params, kpool, vpool, tokens, start, chunk_len,
                   block_tables, active, temp, top_k, top_p, seed):
        """Prefill/decode with the fused sampler: the sampled token for
        global position ``start + chunk_len`` per row."""
        x, kpool, vpool = self._body(params, kpool, vpool, tokens, start,
                                     chunk_len, block_tables, active)
        logits = self._last_logits(params, x, chunk_len)
        tok, _ = _samp.sample_tokens(logits, temp, top_k, top_p, seed,
                                     start + chunk_len, _samp.SALT_TARGET)
        return tok, kpool, vpool

    def _draft_turn_impl(self, params, kpool, vpool, tokens, start,
                         chunk_len, block_tables, active, temp, top_k,
                         top_p, seed, ks, K=1):
        """The whole draft phase as ONE program: ingest the catch-up
        chunk (the 1-2 stream tokens the draft pool is missing) and
        chain ``K`` proposals, each feeding the previous sample back in
        — no host round-trip, one dispatch. The K-1 follow-up proposals
        are a ``lax.scan`` over one single-token body, so the program
        (and its XLA compile time) stays one-body-sized at any K — the
        unrolled form took tens of seconds PER BUCKET to compile on
        CPU. ``ks`` [B] is the per-row draft budget: rows past theirs
        go inactive (writes to scratch, outputs masked later by the
        verify chunk_len). Returns (draft_toks [B, K], qdists
        [B, K, V], kpool, vpool)."""
        import jax
        import jax.numpy as jnp

        x, kpool, vpool = self._body(params, kpool, vpool, tokens, start,
                                     chunk_len, block_tables, active)
        logits = self._last_logits(params, x, chunk_len)
        P0 = start + chunk_len          # global position of proposal d_0
        tok0, q0 = _samp.sample_tokens(logits, temp, top_k, top_p, seed,
                                       P0, _samp.SALT_DRAFT)
        if K == 1:
            return tok0[:, None], q0[:, None], kpool, vpool
        ones = jnp.ones_like(start)

        def propose(carry, j):
            kpool, vpool, tok = carry
            act_j = active & (ks > j)
            x, kpool, vpool = self._body(params, kpool, vpool,
                                         tok[:, None], P0 + j - 1, ones,
                                         block_tables, act_j)
            lg = self._last_logits(params, x, ones)
            tok, q = _samp.sample_tokens(lg, temp, top_k, top_p, seed,
                                         P0 + j, _samp.SALT_DRAFT)
            return (kpool, vpool, tok), (tok, q)

        (kpool, vpool, _), (toks, qs) = jax.lax.scan(
            propose, (kpool, vpool, tok0), jnp.arange(1, K))
        draft = jnp.concatenate(
            [tok0[:, None], jnp.swapaxes(toks, 0, 1)], axis=1)
        qd = jnp.concatenate(
            [q0[:, None], jnp.swapaxes(qs, 0, 1)], axis=1)
        return draft, qd, kpool, vpool

    def _verify_impl(self, params, kpool, vpool, prev, draft_toks, qdists,
                     start, chunk_len, block_tables, active, temp, top_k,
                     top_p, seed):
        """Speculative verify over a [B, C] chunk, C = K + 1.

        Row layout: ``prev`` [B, 1] is the request's last emitted token
        (global position ``start``), ``draft_toks[:, j]`` the draft
        proposal ``d_j`` for position ``start + 1 + j``; a row proposes
        ``k_i = chunk_len - 1`` drafts (``k_i == 0`` = plain sampled
        decode). Returns (n_accept [B] int32 — leading drafts accepted,
        tok [B] int32 — the one non-draft token to emit after them: the
        rejection resample, or the bonus/plain sample on full
        acceptance, kpool, vpool). Logits never leave the program.
        """
        import jax
        import jax.numpy as jnp

        tokens = jnp.concatenate([prev, draft_toks], axis=1)
        x, kpool, vpool = self._body(params, kpool, vpool, tokens, start,
                                     chunk_len, block_tables, active)
        B, C = tokens.shape
        K = C - 1
        V = self.cfg.vocab_size
        logits = jnp.einsum("bcd,vd->bcv", x,
                            params["embed"]).astype(jnp.float32)  # [B,C,V]
        masked, pdist = _samp.filter_dist(
            jnp, logits, temp[:, None], top_k[:, None], top_p[:, None])
        argm = jnp.argmax(logits, axis=-1)                       # [B, C]
        k_i = chunk_len - 1                                      # [B]
        is_sampled = jnp.asarray(temp, jnp.float32) > 0          # [B]

        any_sampled = jnp.any(is_sampled)

        # -- accept/reject the K draft positions -----------------------------
        pos_k = start[:, None] + 1 + jnp.arange(K)[None, :]      # [B, K]
        d = jnp.clip(draft_toks, 0, V - 1).astype(jnp.int32)
        p_d = jnp.take_along_axis(pdist[:, :K], d[..., None],
                                  axis=-1)[..., 0]               # [B, K]
        q_d = jnp.take_along_axis(qdists, d[..., None], axis=-1)[..., 0]

        def accept_draw(_):
            keys_u = _samp.fold_keys(jnp.repeat(seed, K),
                                     pos_k.reshape(-1), _samp.SALT_ACCEPT)
            u = jax.vmap(lambda k: jax.random.uniform(k, (), jnp.float32))(
                keys_u).reshape(B, K)
            return u < jnp.minimum(p_d / jnp.maximum(q_d, 1e-20), 1.0)

        acc_greedy = d == argm[:, :K]
        # all-greedy batches skip every random draw in this program
        # (threefry is real per-step cost); the conds below mirror this
        acc_sampled = jax.lax.cond(any_sampled, accept_draw,
                                   lambda _: acc_greedy, 0)
        accept = jnp.where(is_sampled[:, None], acc_sampled, acc_greedy)
        accept = accept & (jnp.arange(K)[None, :] < k_i[:, None])
        stop = ~accept
        n = jnp.where(stop.any(axis=1),
                      jnp.argmax(stop, axis=1), K).astype(jnp.int32)

        # -- the one non-draft token ------------------------------------------
        # full acceptance -> bonus sample from position start + chunk_len
        # with the TARGET salt: exactly the draw plain decode would make
        bon_masked = jnp.take_along_axis(
            masked, k_i[:, None, None], axis=1)[:, 0]            # [B, V]
        bon_greedy = jnp.take_along_axis(argm, k_i[:, None], axis=1)[:, 0]

        def bonus_draw(m):
            keys_b = _samp.fold_keys(seed, start + chunk_len,
                                     _samp.SALT_TARGET)
            g = jax.vmap(lambda k: jax.random.gumbel(
                k, (V,), jnp.float32))(keys_b)
            return jnp.argmax(m + g, axis=-1)

        bon_sampled = jax.lax.cond(any_sampled, bonus_draw,
                                   lambda m: bon_greedy, bon_masked)
        bonus = jnp.where(is_sampled, bon_sampled, bon_greedy)
        # rejection at draft index n -> resample from max(p - q, 0)
        nc = jnp.clip(n, 0, C - 1)
        p_n = jnp.take_along_axis(pdist, nc[:, None, None], axis=1)[:, 0]
        q_n = jnp.take_along_axis(qdists,
                                  jnp.clip(n, 0, K - 1)[:, None, None],
                                  axis=1)[:, 0]
        res_greedy = jnp.take_along_axis(argm, nc[:, None], axis=1)[:, 0]

        def residual_draw(_):
            r = jnp.maximum(p_n - q_n, 0.0)
            rs = jnp.sum(r, axis=-1, keepdims=True)
            r = jnp.where(rs > 1e-12, r / jnp.maximum(rs, 1e-12), p_n)
            r_logits = jnp.where(r > 0, jnp.log(jnp.maximum(r, 1e-30)),
                                 jnp.float32(-1e30))
            keys_r = _samp.fold_keys(seed, start + 1 + n,
                                     _samp.SALT_RESIDUAL)
            g = jax.vmap(lambda k: jax.random.gumbel(
                k, (V,), jnp.float32))(keys_r)
            return jnp.argmax(r_logits + g, axis=-1)

        res_sampled = jax.lax.cond(any_sampled, residual_draw,
                                   lambda _: res_greedy, 0)
        resample = jnp.where(is_sampled, res_sampled, res_greedy)

        tok = jnp.where(n >= k_i, bonus, resample).astype(jnp.int32)
        return n, tok, kpool, vpool

    _KIND_IMPLS = {"step": "_step_impl", "draft_turn": "_draft_turn_impl",
                   "verify": "_verify_impl"}

    def _compiled(self, key):
        """key = (kind, *static shape params) — the jit/prof cache key
        surface: program KIND and bucket shapes together, so e.g. a
        verify program can never alias a step program at equal
        shapes."""
        fn = self._jitted.get(key)
        if fn is None:
            import jax

            impl = getattr(self, self._KIND_IMPLS[key[0]])
            if key[0] == "draft_turn":
                impl = functools.partial(impl, K=key[3])
            # the K/V pools are donated: the step updates them in place
            fn = jax.jit(impl, donate_argnums=(1, 2))
            # one compile per memo entry — the key IS the bucket; a
            # second compile behind the same key is a broken contract
            # the verifier names by arg-diff (MXNET_JIT_VERIFY)
            fn = _cv.wrap("serve.%s|%s" % (key[0], "|".join(
                str(k) for k in key[1:])), fn, budget=1,
                group="serve.%s" % key[0])
            self._jitted[key] = fn
        return fn

    def _sampling_arrays(self, B, B_real, temperature, top_k, top_p, seed):
        """Pad per-request sampling params to the batch bucket (padded
        rows greedy/seed-0: their draws are never read)."""
        def pad(vals, dtype, default):
            a = np.full((B,), default, dtype)
            if vals is not None:
                a[:B_real] = np.asarray(vals, dtype)
            return a

        return (pad(temperature, np.float32, 0.0),
                pad(top_k, np.int32, 0),
                pad(top_p, np.float32, 1.0),
                pad(seed, np.uint32, 0))

    def _attribute(self, key, fn, args, meta):
        """mxprof: attribute this bucket's program (AOT compile = the
        bucket's one compile); the compiled callable replaces the
        jitted one in the bucket cache. Returns the (possibly compiled)
        callable and whether attribution happened on this call."""
        from ..telemetry import prof as _prof

        if not _prof.ENABLED or key in self._prof_keys:
            return fn, False
        cfg = self.cfg
        kind = key[0]
        name = "serve.%s|%s" % (kind, "|".join(str(k) for k in key[1:]))
        # graph identity: the program KIND plus the FULL model geometry
        # (heads/d_ff/vocab included — two configs sharing L and
        # d_model are still different programs) + the paged-pool layout
        ghash = _prof.graph_hash("%s|%r|bs=%d|W=%d" % (
            kind, cfg, self.block_size, self.max_blocks))
        # attribution AOT-compiles and replaces the program: rebind the
        # verifier boundary's inner callable so compile counting
        # survives (the AOT compile is the bucket's budgeted one)
        compiled = _prof.attribute_jit(
            name, _cv.unwrap(fn), args, site="serving.%s" % kind,
            meta=meta, graph_key=ghash)
        fn = _cv.rebind(fn, compiled)
        self._jitted[key] = fn
        self._prof_keys[key] = _prof.program_key_for(name, graph_key=ghash)
        return fn, True

    # -- host-facing API -----------------------------------------------------
    def step(self, params, kpool, vpool, tokens, start, chunk_len,
             block_tables, active, temperature=None, top_k=None,
             top_p=None, seed=None):
        """Run one bucketed step over host-side (numpy) batch inputs.

        Inputs are RAGGED: ``tokens`` is [B, C_real<=bucket] already
        padded per-row by the caller via ``chunk_len``; this method pads
        the batch and chunk dims to their buckets and slices the result
        back down. Sampling params default to greedy (temperature 0).

        Returns (next_token [B_real] int32 numpy, kpool, vpool) — the
        token vector is the ONLY device->host transfer; logits stay on
        device (the fused-sampler contract, asserted via the mxprof
        ``d2h_bytes`` channel).
        """
        B_real, C_real = tokens.shape
        B = bucket_for(B_real, self.batch_buckets)
        C = 1 if C_real == 1 else bucket_for(C_real, self.chunk_buckets)

        def padb(a, fill=0):
            if a.shape[0] == B:
                return a
            pad = np.full((B - a.shape[0],) + a.shape[1:], fill, a.dtype)
            return np.concatenate([a, pad], axis=0)

        from ..telemetry import prof as _prof

        prof_on = _prof.ENABLED
        t0 = time.monotonic() if prof_on else 0.0
        tok = np.zeros((B, C), np.int32)
        tok[:B_real, :C_real] = tokens
        start = padb(np.asarray(start, np.int32))
        chunk_len = padb(np.asarray(chunk_len, np.int32))
        bt = np.zeros((B, self.max_blocks), np.int32)
        bt[:B_real] = block_tables
        act = np.zeros((B,), bool)
        act[:B_real] = active
        temp, tk, tp, sd = self._sampling_arrays(
            B, B_real, temperature, top_k, top_p, seed)
        fn = self._compiled(("step", B, C))
        args = (params, kpool, vpool, tok, start, chunk_len, bt, act,
                temp, tk, tp, sd)
        attributed_now = False
        if prof_on:
            fn, attributed_now = self._attribute(
                ("step", B, C), fn, args,
                meta={"batch_bucket": B, "chunk_bucket": C})
        t1 = time.monotonic() if prof_on else 0.0
        nxt, kp, vp = fn(*args)
        if prof_on:
            t2 = time.monotonic()
            bur = getattr(nxt, "block_until_ready", None)
            if bur is not None:
                bur()
            t3 = time.monotonic()
        host_nxt = np.asarray(nxt)  # the step's ONE pull: token vector
        _cv.note_d2h(host_nxt.nbytes,
                     "mxnet_tpu/serving/model.py::ServingModel.step")
        out_tok = host_nxt[:B_real]
        if prof_on and not attributed_now:
            # the bucket's first step carried the attribution compile —
            # recording it would drown the steady-state phase shares
            _prof.note_step(
                "serve.decode" if C == 1 else "serve.prefill",
                {"host": t1 - t0, "dispatch": t2 - t1,
                 "device": t3 - t2, "d2h": time.monotonic() - t3},
                key=self._prof_keys.get(("step", B, C)),
                tokens=int(np.sum(np.asarray(chunk_len)[:B_real])),
                d2h_bytes=int(out_tok.nbytes))
        return out_tok, kp, vp

    def _pad_device(self, arr, B, fill=0):
        """Pad a device array's batch dim to the bucket."""
        import jax.numpy as jnp

        a = jnp.asarray(arr)
        if a.shape[0] == B:
            return a
        pad = jnp.full((B - a.shape[0],) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, pad], axis=0)

    def _padb_host(self, a, B):
        a = np.asarray(a)
        if a.shape[0] == B:
            return a
        return np.concatenate(
            [a, np.zeros((B - a.shape[0],) + a.shape[1:], a.dtype)])

    def draft_turn(self, params, kpool, vpool, tokens, start, chunk_len,
                   block_tables, active, ks, K, temperature=None,
                   top_k=None, top_p=None, seed=None):
        """The whole draft phase in one dispatch: ingest + K chained
        proposals. ``tokens`` [B_real, Cin] is the per-row catch-up
        chunk (``chunk_len`` real tokens each), ``ks`` the per-row
        draft budgets, ``K`` the static chain length (>= max ks).
        Returns (draft_toks [B_real, K], qdists [B_real, K, V], kpool,
        vpool) — all still on device."""
        B_real, C_real = np.shape(tokens)
        B = bucket_for(B_real, self.batch_buckets)
        C = 1 if C_real == 1 else bucket_for(C_real, self.chunk_buckets)
        tok = np.zeros((B, C), np.int32)
        tok[:B_real, :C_real] = tokens
        start = self._padb_host(np.asarray(start, np.int32), B)
        chunk_len = self._padb_host(np.asarray(chunk_len, np.int32), B)
        ks = self._padb_host(np.asarray(ks, np.int32), B)
        bt = np.zeros((B, self.max_blocks), np.int32)
        bt[:B_real] = block_tables
        act = np.zeros((B,), bool)
        act[:B_real] = active
        temp, tk, tp, sd = self._sampling_arrays(
            B, B_real, temperature, top_k, top_p, seed)
        key = ("draft_turn", B, C, int(K))
        fn = self._compiled(key)
        args = (params, kpool, vpool, tok, start, chunk_len, bt, act,
                temp, tk, tp, sd, ks)
        fn, _ = self._attribute(key, fn, args,
                                meta={"batch_bucket": B, "chunk_bucket": C,
                                      "spec_k": int(K)})
        d, q, kp, vp = fn(*args)
        return d[:B_real], q[:B_real], kp, vp

    def verify(self, params, kpool, vpool, prev_tokens, draft_tokens,
               qdists, start, chunk_len, block_tables, active,
               temperature=None, top_k=None, top_p=None, seed=None):
        """The speculative verify step: ``prev_tokens`` [B_real, 1]
        host ints, ``draft_tokens`` [B_real, K] / ``qdists``
        [B_real, K, V] device arrays from the draft turn (assembled
        into the [B, K+1] chunk INSIDE the program — no eager glue).
        Returns (n_accept [B_real], tok [B_real], kpool, vpool) with
        the small int outputs still on device — the caller pulls them
        in one fence."""
        B_real, K = np.shape(draft_tokens)
        B = bucket_for(B_real, self.batch_buckets)
        prev = self._pad_device(np.asarray(prev_tokens, np.int32), B)
        d = self._pad_device(draft_tokens, B)
        q = self._pad_device(qdists, B, fill=1.0)
        start = self._padb_host(np.asarray(start, np.int32), B)
        chunk_len = self._padb_host(np.asarray(chunk_len, np.int32), B)
        # padded rows: chunk_len 0 would make k_i negative — clamp to 1
        chunk_len = np.maximum(chunk_len, 1)
        bt = np.zeros((B, self.max_blocks), np.int32)
        bt[:B_real] = block_tables
        act = np.zeros((B,), bool)
        act[:B_real] = active
        temp, tk, tp, sd = self._sampling_arrays(
            B, B_real, temperature, top_k, top_p, seed)
        key = ("verify", B, K)
        fn = self._compiled(key)
        args = (params, kpool, vpool, prev, d, q, start, chunk_len, bt,
                act, temp, tk, tp, sd)
        fn, _ = self._attribute(key, fn, args,
                                meta={"batch_bucket": B, "spec_k": K})
        n, t, kp, vp = fn(*args)
        return n[:B_real], t[:B_real], kp, vp

    def warmup(self, params, pool, batch_sizes=None):
        """Pre-compile the decode programs (and let the persistent jit
        cache serve them next process). Prefill buckets compile on first
        use."""
        for B in (batch_sizes or self.batch_buckets):
            bt = np.zeros((B, self.max_blocks), np.int32)
            nxt, kp, vp = self.step(
                params, pool.k, pool.v, np.zeros((B, 1), np.int32),
                np.zeros((B,), np.int32), np.ones((B,), np.int32), bt,
                np.zeros((B,), bool))
            pool.swap(kp, vp)


def cp_prefill_kv(params, cfg, tokens, mesh, kind="ring", chunk=None,
                  seq_axis="seq"):
    """Context-parallel chunked prefill: per-layer K/V for one long
    prompt, computed over a mesh with ring or Ulysses attention.

    This is the long-context prefill path the engine uses for prompts
    big enough to matter (engine ``cp_min_tokens``): activations for a
    ``chunk``-token slice are materialized at a time (bounding memory to
    O(chunk x d) instead of O(T x d) scores), and each chunk's queries
    attend to the full accumulated prefix via the sequence-parallel
    attention in parallel/ring_attention.py / parallel/ulysses.py using
    their ``q_offset`` form — queries are a suffix of the key sequence,
    exactly the chunked-prefill geometry. Both the chunk length and
    every prefix length must divide by the mesh axis size.

    tokens: [T] or [1, T] int32. Returns (k [L, T, H, D], v likewise,
    x_last [d_model] final-position hidden state) as host arrays.
    """
    import jax.numpy as jnp

    from ..parallel.ring_attention import make_ring_attention
    from ..parallel.ulysses import make_ulysses_attention

    tokens = np.asarray(tokens, np.int32).reshape(1, -1)
    T = tokens.shape[1]
    n = mesh.shape[seq_axis]
    if chunk is None:
        chunk = T
    if chunk % n or T % chunk:
        raise ValueError(
            "cp prefill: chunk %d must divide by mesh axis %d and T %d "
            "by chunk" % (chunk, n, T))
    H, D = cfg.num_heads, cfg.head_dim
    L = cfg.num_layers
    factory = {"ring": make_ring_attention,
               "ulysses": make_ulysses_attention}[kind]

    k_out = np.zeros((L, T, H, D), np.float32)
    v_out = np.zeros((L, T, H, D), np.float32)
    x_last = None
    # dense per-layer K/V accumulated on host; each chunk re-enters the
    # layer stack with its predecessors' K/V as the attention prefix
    for c0 in range(0, T, chunk):
        c1 = c0 + chunk
        x = jnp.take(params["embed"], jnp.asarray(tokens[:, c0:c1]), axis=0)
        x = x + params["pos_embed"][c0:c1][None].astype(x.dtype)
        attn = factory(mesh, seq_axis=seq_axis, causal=True, q_offset=c0)
        for li, lp in enumerate(params["layers"]):
            h = _layer_norm(x, lp["ln1"])
            qkv = jnp.einsum("btd,de->bte", h, lp["wqkv"])
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(t):
                return t.reshape(1, t.shape[1], H, D).transpose(0, 2, 1, 3)

            k_out[li, c0:c1] = np.asarray(
                k.reshape(chunk, H, D), np.float32)
            v_out[li, c0:c1] = np.asarray(
                v.reshape(chunk, H, D), np.float32)
            k_full = jnp.asarray(k_out[li, :c1][None]).astype(x.dtype)
            v_full = jnp.asarray(v_out[li, :c1][None]).astype(x.dtype)
            o = attn(heads(q),
                     k_full.transpose(0, 2, 1, 3),
                     v_full.transpose(0, 2, 1, 3))
            o = o.transpose(0, 2, 1, 3).reshape(1, chunk, H * D)
            x = x + jnp.einsum("btd,de->bte", o, lp["wo"])
            h = _layer_norm(x, lp["ln2"])
            import jax

            ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lp["w1"]))
            x = x + jnp.einsum("btf,fd->btd", ff, lp["w2"])
        x_last = np.asarray(
            _layer_norm(x, params["ln_f"])[0, -1], np.float32)
    return k_out, v_out, x_last
