"""Driver ``fit_symbol``: Symbol-model training through the public
``mx.FeedForward(...).fit`` on one chip, the scanned K-step path
(``parallel/fit_trainer.py``, one dispatch per K batches).

One ``fit`` call of two epochs on one trainer and one compiled loop. Epoch 0
is exactly one chunk of K steps: it compiles the loop, and its per-step
losses and the parameters ``fit`` writes back at its end are what
``correct`` compares. Epoch 1 is the measured window: it opens when the
iterator is asked for its first batch (the chip is idle: the write-back was
a fence), runs whole chunks until ``--seconds`` have passed, and closes at
the last ``batch_end_callback``, which ``fit`` fires after it has pulled the
last chunk's outputs. ``fit`` runs on a thread of its own so that set-up can
return between the two epochs; the main thread only waits.
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

import compare
import traffic as traffic_gen


class _Refuse:
    """A logger that turns the trainer's fall-back to the per-batch loop
    into a failure: the cell times the scanned path or nothing."""

    def __init__(self):
        self.said = []

    def _log(self, msg, *args):
        text = msg % args if args else msg
        self.said.append(text)
        if "scanned fit" in text:
            raise RuntimeError("fit left the scanned path: " + text)

    debug = info = warning = error = _log


class Driver:
    def __init__(self, config, traffic, seed, reference, devices, rehearse,
                 log=None):
        self.config, self.mix, self.seed = config, traffic, int(seed)
        self.log = log or (lambda *a: None)
        self.ref, self.rehearse = reference, rehearse
        self.K = int(self.mix["scan_k"])
        self.losses = []          # every step's cross-entropy, host side
        self.marks = {}
        self.error = None
        self.ready = threading.Event()   # epoch 0 done, window may open
        self.go = threading.Event()
        self.seconds = None
        self.got = None

    # -- the iterator ----------------------------------------------------------
    def _iterator(self, mx, images, labels):
        drv = self
        batch, image = int(self.mix["batch"]), int(self.mix["image"])
        pool = [(mx.nd.NDArray(images[i], drv.ctx),
                 mx.nd.NDArray(labels[i], drv.ctx))
                for i in range(images.shape[0])]

        class PoolIter(mx.io.DataIter):
            """A device-resident pool served round-robin (bench_fit.py's
            iterator), with the two epochs' lengths as set out above."""

            def __init__(self):
                super().__init__()
                self.batch_size = batch
                self.provide_data = [("data", (batch, 3, image, image))]
                self.provide_label = [("softmax_label", (batch,))]
                self.phase = -1
                self.i = 0
                self.served = 0

            def reset(self):
                self.phase += 1
                self.i = 0

            def iter_next(self):
                if self.phase == 0:
                    more = self.i < drv.K
                elif self.phase == 1:
                    if self.i == 0:
                        drv.ready.set()
                        drv.go.wait()
                        drv.marks["open"] = time.perf_counter()
                    more = not (self.i % drv.K == 0 and self.i > 0 and
                                time.perf_counter() - drv.marks["open"]
                                >= drv.seconds)
                else:
                    more = False
                if more:
                    self.i += 1
                    self.served += 1
                else:
                    drv.marks["steps_%d" % self.phase] = self.i
                return more

            def getdata(self):
                return [pool[(self.served - 1) % len(pool)][0]]

            def getlabel(self):
                return [pool[(self.served - 1) % len(pool)][1]]

            def getpad(self):
                return 0

            def getindex(self):
                return None

        return PoolIter()

    # -- set-up ----------------------------------------------------------------
    def setup(self):
        import mxnet_tpu as mx
        from mxnet_tpu.models import get_resnet

        os.environ["MXNET_TRAIN_SCAN_K"] = str(self.K)
        self.ctx = mx.cpu(0) if self.rehearse else mx.tpu(0)
        mix, config = self.mix, self.config
        t0 = time.perf_counter()
        images, labels = traffic_gen.image_batches(mix, self.seed)
        params = self.ref.make_params(config, self.seed)
        self.start = {n: np.asarray(v) for n, v in params.items()}
        symbol = get_resnet(
            num_classes=int(config["num_classes"]),
            num_layers=int(config["num_layers"]), stem=config["stem"],
            image=int(mix["image"]))
        model = mx.FeedForward(
            symbol, ctx=self.ctx, num_epoch=2, optimizer="sgd",
            learning_rate=float(mix["learning_rate"]),
            momentum=float(mix["momentum"]), wd=float(mix["weight_decay"]),
            initializer=mx.initializer.Xavier(),
            arg_params={n: mx.nd.NDArray(v, self.ctx)
                        for n, v in params.items()},
            aux_params={n: mx.nd.array(v, ctx=self.ctx)
                        for n, v in self.ref.make_aux(config).items()},
            compute_dtype=config["compute_dtype"])
        del params
        train = self._iterator(mx, images, labels)
        self.log("  fit_symbol: pool, weights and FeedForward built %.2f s",
                 time.perf_counter() - t0)
        t0 = time.perf_counter()
        names = self.ref.leaf_names(config)

        def step_loss(label, pred):
            picked = pred[np.arange(label.shape[0]), label.astype(np.int64)]
            self.losses.append(float(
                -np.log(picked.astype(np.float64) + 1e-30).mean()))
            return self.losses[-1]

        def batch_end(param):
            self.marks["last_batch"] = time.perf_counter()

        def epoch_end(epoch, symbol, arg_params, aux_params):
            if epoch == 0:
                self.got = dict(
                    loss=np.asarray(self.losses[:self.K]),
                    change_norm=np.asarray([np.linalg.norm(
                        (arg_params[n].asnumpy().astype(np.float64)
                         - self.start[n]).ravel()) for n in names]))

        def run():
            try:
                model.fit(X=train, eval_metric=mx.metric.np(step_loss),
                          batch_end_callback=batch_end,
                          epoch_end_callback=epoch_end, logger=_Refuse())
            except BaseException as e:  # handed to the main thread
                self.error = e
            finally:
                self.ready.set()
                self.done.set()

        self.done = threading.Event()
        self.thread = threading.Thread(target=run, name="fit", daemon=True)
        self.thread.start()
        self.ready.wait()
        self._raise()
        self.log("  fit_symbol: fit's bind and init, the first chunk of %d "
                 "steps (compile or cache load) and its write-back %.2f s",
                 self.K, time.perf_counter() - t0)

    def _raise(self):
        if self.error is not None:
            raise self.error

    # -- the measured window ---------------------------------------------------
    def window(self, seconds):
        self.seconds = float(seconds)
        self.go.set()
        self.done.wait()
        self.thread.join()
        self._raise()
        steps = self.marks["steps_1"]
        window_s = self.marks["last_batch"] - self.marks["open"]
        timed = np.asarray(self.losses[self.K:])
        return {
            "window_s": window_s,
            "attempted": steps,
            "failed": int(steps - np.sum(np.isfinite(timed))),
            "metrics": {"train_step_ms": 1e3 * window_s / steps},
            "counters": {"steps": steps, "scan_k": self.K,
                         "last_loss": float(timed[-1])},
        }

    def release(self):
        self.thread = None
        self.start = None

    # -- correct ---------------------------------------------------------------
    def reference_readings(self, quant=None, keep_rows=None):
        mix = self.mix
        images, labels = traffic_gen.image_batches(mix, self.seed)
        return self.ref.train_readings(
            self.config, self.seed, images, labels, self.K,
            float(mix["learning_rate"]), float(mix["momentum"]),
            float(mix["weight_decay"]), quant=quant, keep_rows=keep_rows)

    gaps = staticmethod(compare.fit_gaps)

    def check(self, say=None):
        return self.gaps(self.got, self.reference_readings(),
                         self.ref.leaf_names(self.config), say)

    def program_readings(self):
        """For ``calibrate.py``: what ``check`` compares; the window is
        opened for an instant so that ``fit`` returns."""
        self.setup()
        self.window(0.0)
        self.release()
        return self.got


def _break(monkeypatch, fault):
    """Plant ``fault`` under the timed path (``tests/test_correct.py``)."""
    from mxnet_tpu import optimizer
    from mxnet_tpu.parallel import fit_trainer

    if fault == "state_unchanged":
        monkeypatch.setattr(optimizer.SGD, "update",
                            lambda self, index, weight, grad, state: None)
        return
    stage = fit_trainer.FitTrainer.stage_chunk

    def half(self, batch_list):
        K, staged = stage(self, batch_list)
        for name, v in staged.items():
            rows = v.shape[1] // 2
            staged[name] = v.at[:, rows:2 * rows].set(v[:, :rows])
        return K, staged

    monkeypatch.setattr(fit_trainer.FitTrainer, "stage_chunk", half)


#: the faults this driver's cells can have (one chip: no exchange to leave
#: out; no token is produced), each planted under the timed path
FAULTS = {"state_unchanged": _break, "half_batch": _break}
