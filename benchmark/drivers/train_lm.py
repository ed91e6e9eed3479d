"""Driver ``train_lm``: language-model training through the library's
``parallel.make_train_step``.

The entry the window drives is ``step_fn(params, opt_state, batch, rng)``,
one call a step, over ``models/transformer.py``'s loss at the
configuration's sizes with ``optax.adam``. Set-up builds that one object,
drives it through its first three steps (which compile it, and whose
losses, first gradient and parameter change are what ``correct`` compares)
and hands the same object and state to the window.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import traffic as traffic_gen

CHECK_STEPS = 3


class Driver:
    def __init__(self, config, traffic, seed, reference, devices, rehearse,
                 log=None):
        self.config, self.mix, self.seed = config, traffic, int(seed)
        self.log = log or (lambda *a: None)
        self.ref = reference
        self.got = None
        self.state = None

    # -- set-up ----------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        import optax

        from mxnet_tpu import parallel
        from mxnet_tpu.models import transformer

        sz = self.ref.sizes(self.config)
        cfg = transformer.TransformerConfig(
            vocab_size=sz["V"], num_layers=sz["L"], d_model=sz["d"],
            num_heads=sz["H"], d_ff=sz["ff"], max_seq_len=sz["P"],
            dtype=self.config["dtype"])
        lr = float(self.mix["learning_rate"])
        step_fn, init_state = parallel.make_train_step(
            transformer.loss_fn(cfg), optax.adam(lr))
        self.step_fn = step_fn

        # weights on the device in one jitted call, from the seed
        t0 = time.perf_counter()
        params = self.ref.make_params(self.config, self.seed)
        opt_state = init_state(params)
        # the pool of batches lives on the device; ids from the real
        # vocabulary, not its padding
        pool = traffic_gen.token_batches(
            self.mix, sz["vocab"], self.seed, int(self.mix["pool_batches"]))
        self.first_batches = pool[:CHECK_STEPS]
        self.pool = [{"tokens": jax.device_put(b)} for b in pool]
        self.rng = jax.random.PRNGKey(0)  # the loss draws nothing from it

        norms = jax.jit(self.ref.leaf_norms)

        @jax.jit
        def change_norms(params, key):
            start = self.ref.unstack(self.ref._stacked(
                sz, key, jnp.dtype(self.config["dtype"])), sz["L"])
            return self.ref.leaf_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                params, start))

        jax.block_until_ready(params)
        self.log("  train_lm: weights and pool on the device %.2f s",
                 time.perf_counter() - t0)
        t0 = time.perf_counter()
        losses, mu_norm = [], None
        for i in range(CHECK_STEPS):
            params, opt_state, loss = step_fn(
                params, opt_state, self.pool[i], self.rng)
            losses.append(loss)
            if i == 0:
                # Adam's first moment after one step is (1 - b1) * g
                mu_norm = norms(opt_state[0].mu)
        change = change_norms(params, traffic_gen.key_of(self.seed))
        self.got = dict(
            loss=np.asarray([float(v) for v in losses]),
            grad_norm=np.asarray(mu_norm, np.float64) / (
                1.0 - self.ref.ADAM["b1"]),
            change_norm=np.asarray(change, np.float64))
        self.state = (params, opt_state)
        self.steps_done = CHECK_STEPS
        self.log("  train_lm: first %d steps (compile or cache load) and "
                 "their readings %.2f s", CHECK_STEPS,
                 time.perf_counter() - t0)

    # -- the measured window ---------------------------------------------------
    def window(self, seconds):
        import jax

        params, opt_state = self.state
        self.state = None
        ahead = int(self.mix["in_flight"])
        pool, n_pool = self.pool, len(self.pool)
        step_fn, rng = self.step_fn, self.rng
        losses = []
        jax.block_until_ready(params)  # fence: the window opens on an idle chip
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt_state, loss = step_fn(
                    params, opt_state, pool[(self.steps_done + len(losses))
                                            % n_pool], rng)
            losses.append(loss)
            if len(losses) > ahead:
                with jax.profiler.TraceAnnotation("fence"):
                    losses[-1 - ahead].block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("fence"):
            jax.block_until_ready((params, loss))
        window_s = time.perf_counter() - t0
        steps = len(losses)
        # read only now: nothing was pulled inside the window
        values = np.asarray([float(v) for v in losses])
        self.state = (params, opt_state)
        tokens = int(self.mix["batch"]) * int(self.mix["seq_len"])
        return {
            "window_s": window_s,
            "attempted": steps,
            "failed": int(np.sum(~np.isfinite(values))),
            "metrics": {"train_step_ms": 1e3 * window_s / steps},
            "counters": {"steps": steps, "tokens_per_step": tokens,
                         "last_loss": float(values[-1])},
        }

    def program_memory(self):
        """Bytes the compiled step holds while it runs, beyond the buffers
        that are live between steps: its temporaries and what it returns
        without reusing a donated argument, by the executable's own
        ``memory_analysis()``. ``step_fn.jitted`` is the program the window
        drove; lowered on the shapes it was called with, it comes back from
        the compile cache."""
        import jax

        if self.state is None:
            return None
        t0 = time.perf_counter()
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (self.state[0], self.state[1], self.pool[0], self.rng))
        mem = self.step_fn.jitted.lower(*shapes).compile().memory_analysis()
        if mem is None:
            return None
        running = mem.temp_size_in_bytes + max(
            mem.output_size_in_bytes - mem.alias_size_in_bytes, 0)
        self.log("  train_lm: the step holds %d bytes of temporaries while it "
                 "runs (arguments %d, outputs %d, aliased %d; read in %.2f s)",
                 running, mem.argument_size_in_bytes, mem.output_size_in_bytes,
                 mem.alias_size_in_bytes, time.perf_counter() - t0)
        return running

    def release(self):
        self.state = None
        self.pool = None

    # -- correct ---------------------------------------------------------------
    def reference_readings(self, quant=None, keep_rows=None):
        return self.ref.train_readings(
            self.config, self.seed, self.first_batches,
            float(self.mix["learning_rate"]), quant=quant,
            keep_rows=keep_rows)

    gaps = staticmethod(compare.training_gaps)

    def check(self, say=None):
        return self.gaps(self.got, self.reference_readings(),
                         self.ref.leaf_names(self.config), say)

    def program_readings(self):
        """For ``calibrate.py``: what ``check`` compares, with no window."""
        self.setup()
        self.release()
        return self.got


def _break(monkeypatch, fault):
    """Plant ``fault`` under the timed path (``tests/test_correct.py``)."""
    from mxnet_tpu import parallel

    make = parallel.make_train_step

    def broken(loss_fn, optimizer=None, **kw):
        step_fn, init_state = make(loss_fn, optimizer, donate=False, **kw)

        def step(params, opt_state, batch, rng):
            if fault == "half_batch":
                rows = batch["tokens"].shape[0] // 2
                batch = {"tokens": batch["tokens"][:rows]}
            new = step_fn(params, opt_state, batch, rng)
            if fault == "state_unchanged":
                return params, opt_state, new[2]
            return new

        step.jitted = step_fn.jitted
        return step, init_state

    monkeypatch.setattr(parallel, "make_train_step", broken)


#: the faults this driver's cells can have (one chip: no exchange to leave
#: out; no token is produced), each planted under the timed path
FAULTS = {"state_unchanged": _break, "half_batch": _break}
