"""``dispatch_gap_ms.train``: median idle gap on the device between
consecutive step (or scanned-loop) programs, in milliseconds."""
import statistics


def compute(trace, counters, run):
    if trace is None or len(trace["module_gaps_s"]) < 2:
        return None
    return 1e3 * statistics.median(trace["module_gaps_s"])
