"""``flash_roofline``: the flash-attention kernels' share of their roofline.
Device time of ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` in the
trace against the least time the chip could take for one step's attention
as the configuration's own reference counts it
(``references/<config>.py: attention_work(config, traffic)``: FLOPs and
least bytes, forward and backward, every layer), every traced step. Nothing
to read (no such kernel in the trace, no trace, or a configuration without
attention) returns nothing."""
import flops

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def compute(trace, counters, run):
    count = getattr(run["reference"], "attention_work", None)
    if trace is None or count is None or run["peaks"] is None:
        return None
    spent = sum(sec for name, sec in trace["ops"].items()
                if any(k in name for k in KERNELS))
    steps = max(trace["module_runs"].values(), default=0)
    if spent <= 0 or not steps:
        return None
    work, nbytes = count(run["config"], run["traffic"])
    least, _ = flops.roofline_seconds(work, nbytes, run["peaks"])
    return 100.0 * least * steps / spent
