"""``window_attn_roofline``: the share of their roofline that the windowed
flash-attention kernels reach. Device time of the kernels whose names
contain ``flash_win_`` in the trace (``flash_win_fwd``, ``flash_win_bwd_dq``,
``flash_win_bwd_dkv``, ``ops/pallas_kernels.py``: the layers that see a
window) against the least time the chip could take for one step's windowed
attention as the configuration's own reference counts it
(``references/<config>.py: window_attention_work(config, traffic)``: the
pairs the window lets through, forward and backward, every window layer, and
the least bytes), every traced step. The work is the algorithm's: tiles the
kernels visit beyond the window's pairs are time and no work, so it cannot
read over 100 %. Nothing to read (no such kernel in the trace, no trace, or
a configuration whose reference counts no such work) returns nothing."""
import flops


def compute(trace, counters, run):
    count = getattr(run["reference"], "window_attention_work", None)
    if trace is None or count is None or run["peaks"] is None:
        return None
    spent = sum(sec for name, sec in trace["ops"].items()
                if "flash_win_" in name)
    steps = max(trace["module_runs"].values(), default=0)
    if spent <= 0 or not steps:
        return None
    work, nbytes = count(run["config"], run["traffic"])
    least, _ = flops.roofline_seconds(work, nbytes, run["peaks"])
    return 100.0 * least * steps / spent
