"""``mfu.train``: the whole training step's share of the chip's peak.
Model FLOPs of one step, as the configuration's own reference counts them
(``references/<config>.py: train_flops(config, traffic)``: forward and
backward, nothing recomputed), over the step time of the traced window,
over the chips' bf16 peak. A configuration whose reference does not say
gives nothing to read."""


def compute(trace, counters, run):
    count = getattr(run["reference"], "train_flops", None)
    step_ms = run["metrics"].get("train_step_ms")
    if count is None or not step_ms or run["peaks"] is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * count(run["config"], run["traffic"]) / (
        step_ms * 1e-3) / peak
