"""Driver ``train_hybrid_mla``: training a hybrid decoder whose every layer is
latent attention with a query latent and a rotated shared key part over
sigmoid-routed experts and a shared one, with a multi-token-prediction
module trained beside it (``models/hybrid_lm.py``: ``"mla"`` with
``q_lora_rank`` and ``mla_rope_theta``, ``mtp_modules``), through the
library's ``parallel.make_train_step``.

``train_hybrid_lm``'s driver with another model under it: its ``window``,
``gaps`` and ``reference_readings`` as they are. Its own: ``setup`` (this
model's ``HybridConfig``; no KDA layer, so no probe of KDA's in-chunk stage
and nothing timed in set-up beside the first steps) and the faults a model
of these layers can have.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import run as harness
import traffic as traffic_gen

hybrid = harness.load_module("drivers", "train_hybrid_lm")
CHECK_STEPS = hybrid.CHECK_STEPS


def model_config(config, sz):
    """The program's own configuration object, from the benchmark's file."""
    from mxnet_tpu.models import hybrid_lm

    return hybrid_lm.HybridConfig(
        vocab_size=sz["V"], d_model=sz["d"], attention=("mla",) * sz["L"],
        mlp=sz["mlps"], rms_eps=sz["eps"], num_heads=sz["H"],
        kv_lora_rank=sz["r"], qk_nope_dim=sz["dn"], qk_rope_dim=sz["dr"],
        v_head_dim=sz["dv"], q_lora_rank=sz["rq"],
        mla_rope_theta=sz["theta"], d_ff=sz["ff"], moe_d_ff=sz["eff"],
        num_experts=sz["E"], experts_per_token=sz["top_k"],
        experts_held=sz["held"], num_shared_experts=sz["shared"],
        route_scale=sz["route_scale"], renormalize=sz["renormalize"],
        router="sigmoid", mtp_modules=sz["mtp"],
        mtp_weight=sz["mtp_weight"], kept=sz["kept"], dtype=config["dtype"])


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
        # ids from the vocabulary held HERE (19,456 rows at the cell's size;
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
        self.log("  train_hybrid_mla: weights and pool on the device %.2f s",
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
        self.log("  train_hybrid_mla: first %d steps (compile or cache load) "
                 "and their readings %.2f s", CHECK_STEPS,
                 time.perf_counter() - t0)


def _break_rope(monkeypatch, fault):
    """``rope_left_out``: the two 64-wide parts are not turned."""
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.setattr(hybrid_lm, "_rotate", lambda x, cos, sin: x)


def _break_mtp_weight(monkeypatch, fault):
    """``mtp_left_out``: the second loss's weight is 0."""
    from mxnet_tpu.models import hybrid_lm

    whole = hybrid_lm.loss_fn
    monkeypatch.setattr(
        hybrid_lm, "loss_fn",
        lambda cfg: whole(dataclasses.replace(cfg, mtp_weight=0.0)))


def _break_mtp_shift(monkeypatch, fault):
    """``mtp_unshifted``: the module embeds token i instead of i + 1."""
    from mxnet_tpu.models import hybrid_lm

    run = hybrid_lm._run

    def unshifted(params, tokens, cfg, next_tokens=None):
        return run(params, tokens, cfg,
                   None if next_tokens is None else tokens)

    monkeypatch.setattr(hybrid_lm, "_run", unshifted)


#: the faults this driver's cells can have, each planted under the timed path
FAULTS = {"state_unchanged": hybrid.FAULTS["state_unchanged"],
          "half_batch": hybrid.FAULTS["half_batch"],
          "assignments_dropped": hybrid.FAULTS["assignments_dropped"],
          "rope_left_out": _break_rope, "mtp_left_out": _break_mtp_weight,
          "mtp_unshifted": _break_mtp_shift}
