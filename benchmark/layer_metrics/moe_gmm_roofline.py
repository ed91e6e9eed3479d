"""``moe_gmm_roofline``: the share of their roofline that the expert layer's
grouped-product kernels reach. Device time of the kernels whose names
contain ``moe_gmm`` (``moe_gmm``, ``moe_gmm_pair``) or ``moe_tgmm`` in the
trace (``ops/grouped_matmul.py``) against the least time the chip could take
for one step's grouped products over the assignments that LANDED on the
held experts, as the configuration's own reference counts it
(``references/<config>.py: expert_work(config, traffic, landed_rows)``:
forward and backward, every expert layer, and the least bytes), every
traced step. The landed rows are the step's routing counts, which the
driver reads after the window (``assignments_held`` over ``steps``: a
step's mean, summed over the layers). The kernels run over the whole sorted
bucket; its rows past the landed ones are time and no work, so a kernel that
skips them reads higher and none can read over 100 %. Nothing to read (no
such kernel in the trace, no trace, no routing counted, or a configuration
whose reference counts no such work) returns nothing."""
import flops


def compute(trace, counters, run):
    count = getattr(run["reference"], "expert_work", None)
    landed, ran = counters.get("assignments_held"), counters.get("steps")
    if trace is None or count is None or run["peaks"] is None \
            or not landed or not ran:
        return None
    spent = sum(sec for name, sec in trace["ops"].items()
                if "moe_gmm" in name or "moe_tgmm" in name)
    steps = max(trace["module_runs"].values(), default=0)
    if spent <= 0 or not steps:
        return None
    work, nbytes = count(run["config"], run["traffic"], landed / ran)
    least, _ = flops.roofline_seconds(work, nbytes, run["peaks"])
    return 100.0 * least * steps / spent
