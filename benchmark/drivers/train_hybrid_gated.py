"""Driver ``train_hybrid_gated``: training a hybrid decoder whose layers are
grouped-query softmax attention with query heads, rotation base and turned
channels set by layer kind and a sigmoid gate on the output (a window in
some layers, YaRN on part of each head in the others), a dense first layer,
then sigmoid-routed experts beside a shared one (``models/hybrid_lm.py``:
``"swa"``, ``"full"``, ``swa_heads``, ``full_rope_theta``,
``full_rotary_dim``, ``attn_gate``), through the library's
``parallel.make_train_step``.

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
gqa = harness.load_module("drivers", "train_hybrid_gqa")
CHECK_STEPS = hybrid.CHECK_STEPS


def model_config(config, sz):
    """The program's own configuration object, from the benchmark's file."""
    from mxnet_tpu.models import hybrid_lm

    swa, full = sz["rope"]["swa"], sz["rope"]["full"]
    if sz["rotary"]["swa"] != sz["D"]:
        raise ValueError("the program turns a window layer's whole head, "
                         "not %d of its %d channels"
                         % (sz["rotary"]["swa"], sz["D"]))
    return hybrid_lm.HybridConfig(
        vocab_size=sz["V"], d_model=sz["d"], attention=sz["kinds"],
        mlp=sz["mlps"], rms_eps=sz["eps"], num_heads=sz["H"]["full"],
        num_kv_heads=sz["G"], head_dim=sz["D"], window=sz["window"],
        swa_heads=sz["H"]["swa"], rope_theta=float(swa["rope_theta"]),
        full_rope_theta=float(full["rope_theta"]),
        full_rotary_dim=sz["rotary"]["full"],
        yarn_factor=float(full["factor"]),
        yarn_original_length=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        attn_gate=sz["gate"], d_ff=sz["ff"], moe_d_ff=sz["eff"],
        num_experts=sz["E"], experts_per_token=sz["top_k"],
        experts_held=sz["held"], num_shared_experts=sz["shared"],
        route_scale=sz["route_scale"], renormalize=sz["renormalize"],
        router="sigmoid", kept=sz["kept"], dtype=config["dtype"])


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
        # ids from the vocabulary held HERE (12,544 rows at the cell's size;
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
        self.log("  train_hybrid_gated: weights and pool on the device %.2f "
                 "s", time.perf_counter() - t0)
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
        self.log("  train_hybrid_gated: first %d steps (compile or cache "
                 "load) and their readings %.2f s", CHECK_STEPS,
                 time.perf_counter() - t0)


def _break_config(monkeypatch, fault):
    """``gate_left_out``: no output gate; ``rotary_whole``: the full layers
    turn all of a head's channels (YaRN placed over ``head_dim``)."""
    from mxnet_tpu.models import hybrid_lm

    change = {"gate_left_out": dict(attn_gate=False),
              "rotary_whole": dict(full_rotary_dim=0)}[fault]
    whole = hybrid_lm.loss_fn
    monkeypatch.setattr(hybrid_lm, "loss_fn", lambda cfg: whole(
        dataclasses.replace(cfg, **change)))


def _break_shared(monkeypatch, fault):
    """``shared_left_out``: the expert layer adds no shared expert."""
    from mxnet_tpu.parallel import moe

    whole = moe.moe_share_ffn

    def unshared(params, x, *args, **kw):
        return whole({k: v for k, v in params.items() if k != "shared"}, x,
                     *args, **kw)

    monkeypatch.setattr(moe, "moe_share_ffn", unshared)


#: the faults this driver's cells can have, each planted under the timed path
FAULTS = {"state_unchanged": hybrid.FAULTS["state_unchanged"],
          "half_batch": hybrid.FAULTS["half_batch"],
          "assignments_dropped": hybrid.FAULTS["assignments_dropped"],
          "window_left_out": gqa.FAULTS["window_left_out"],
          "yarn_left_out": gqa.FAULTS["yarn_left_out"],
          "gate_left_out": _break_config, "rotary_whole": _break_config,
          "shared_left_out": _break_shared}
