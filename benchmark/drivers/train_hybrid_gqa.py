"""Driver ``train_hybrid_gqa``: training a hybrid decoder whose layers are
grouped-query softmax attention under rotary positions (a window in some
layers, YaRN full attention in the others) over softmax-routed experts
(``models/hybrid_lm.py``: ``"swa"``, ``"full"``, ``router="softmax"``),
through the library's ``parallel.make_train_step``.

``train_hybrid_lm``'s driver with another model under it: its ``window``,
``gaps`` and ``reference_readings`` as they are. Its own: ``setup`` (this
model's ``HybridConfig``; no KDA layer, so no probe of KDA's in-chunk stage
and nothing timed in set-up beside the first steps) and the faults a model
of these layers can have.
"""
from __future__ import annotations

import time

import numpy as np

import run as harness
import traffic as traffic_gen

hybrid = harness.load_module("drivers", "train_hybrid_lm")
CHECK_STEPS = hybrid.CHECK_STEPS


def model_config(config, sz):
    """The program's own configuration object, from the benchmark's file."""
    from mxnet_tpu.models import hybrid_lm

    yarn = sz["rope"]["full"]
    return hybrid_lm.HybridConfig(
        vocab_size=sz["V"], d_model=sz["d"], attention=sz["kinds"],
        mlp=sz["mlps"], rms_eps=sz["eps"], num_heads=sz["H"],
        num_kv_heads=sz["G"], head_dim=sz["D"], window=sz["window"],
        rope_theta=float(sz["rope"]["swa"]["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_length=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=float(yarn["attention_factor"]),
        moe_d_ff=sz["eff"], num_experts=sz["E"],
        experts_per_token=sz["top_k"], experts_held=sz["held"],
        num_shared_experts=0, renormalize=sz["renormalize"],
        router="softmax", dtype=config["dtype"])


class Driver(hybrid.Driver):
    # ``window``, ``gaps`` and ``reference_readings`` are train_hybrid_lm's;
    # ``program_memory``, ``release``, ``check`` and ``program_readings``
    # train_lm's

    #: no KDA layer: nothing for ``kda_chunk_share`` to read
    chunk_ms = None

    def setup(self):
        import jax
        import optax

        from mxnet_tpu import parallel
        from mxnet_tpu.models import hybrid_lm

        sz = self.ref.sizes(self.config)
        self.cfg = cfg = model_config(self.config, sz)
        step_fn, init_state = parallel.make_train_step(
            hybrid_lm.loss_fn(cfg),
            optax.adam(float(self.mix["learning_rate"])), has_aux=True)
        self.step_fn = step_fn

        t0 = time.perf_counter()
        params = self.ref.make_params(self.config, self.seed)
        opt_state = init_state(params)
        # ids from the vocabulary held HERE (24,576 rows at the cell's size;
        # the traffic file's note quotes another cell's 20,480)
        pool = traffic_gen.token_batches(
            self.mix, sz["V"], self.seed, int(self.mix["pool_batches"]))
        self.first_batches = pool[:CHECK_STEPS]
        self.pool = [{"tokens": jax.device_put(b)} for b in pool]
        self.rng = jax.random.PRNGKey(0)  # the loss draws nothing from it
        norms = jax.jit(self.ref.leaf_norms)

        @jax.jit
        def change_norms(params, start):
            return self.ref.leaf_norms(
                jax.tree.map(lambda a, b: a - b, params, start))

        jax.block_until_ready(params)
        self.log("  train_hybrid_gqa: weights and pool on the device %.2f s",
                 time.perf_counter() - t0)
        t0 = time.perf_counter()
        losses, mu_norm = [], None
        for i in range(CHECK_STEPS):
            params, opt_state, loss, _ = step_fn(
                params, opt_state, self.pool[i], self.rng)
            losses.append(loss)
            if i == 0:
                # Adam's first moment after one step is (1 - b1) * g
                mu_norm = norms(opt_state[0].mu)
        change = change_norms(
            params, self.ref.make_params(self.config, self.seed))
        self.got = dict(
            loss=np.asarray([float(v) for v in losses]),
            grad_norm=np.asarray(mu_norm, np.float64) / (
                1.0 - self.ref.ADAM["b1"]),
            change_norm=np.asarray(change, np.float64))
        self.state = (params, opt_state)
        self.steps_done = CHECK_STEPS
        self.log("  train_hybrid_gqa: first %d steps (compile or cache load) "
                 "and their readings %.2f s", CHECK_STEPS,
                 time.perf_counter() - t0)


def _break_window(monkeypatch, fault):
    """``window_left_out``: the window layers attend to every earlier key."""
    from mxnet_tpu.ops import pallas_kernels

    whole = pallas_kernels.flash_attention

    def no_window(q, k, v, causal=True, scale=None, window=None, **kw):
        return whole(q, k, v, causal=causal, scale=scale, **kw)

    monkeypatch.setattr(pallas_kernels, "flash_attention", no_window)


def _break_yarn(monkeypatch, fault):
    """``yarn_left_out``: the full layer rotates as the window layers do."""
    from mxnet_tpu.models import hybrid_lm

    rotation = hybrid_lm.rope_inv_freq
    monkeypatch.setattr(hybrid_lm, "rope_inv_freq",
                        lambda cfg, kind: rotation(cfg, "swa"))


#: the faults this driver's cells can have, each planted under the timed path
FAULTS = {"state_unchanged": hybrid.FAULTS["state_unchanged"],
          "half_batch": hybrid.FAULTS["half_batch"],
          "assignments_dropped": hybrid.FAULTS["assignments_dropped"],
          "window_left_out": _break_window, "yarn_left_out": _break_yarn}
