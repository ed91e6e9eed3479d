"""From a profiler trace (``.xplane.pb``) to three things: the device's busy
union and idle share, device time by operation name, and the gaps between
consecutive step programs. Read with ``jax.profiler.ProfileData`` alone.

What a v5e trace looks like (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` holds one event per executed
program (named ``jit_<fn>(<fingerprint>)``) and whose line ``XLA Ops`` holds
one event per HLO operation inside them (``fusion.12``, ``copy.3``, a Pallas
kernel under its kernel ``name``); host threads live on ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans appear under their names.

A gap is attributed only by the benchmark's own annotations around ITS calls
(``dispatch``, ``fence``): the one that covers
most of the gap names it, anything else is ``unattributed``. Attribution by
the program's own host activity needs spans inside the program (PERF.md,
Open questions).

    python benchmark/trace_reduce.py <trace dir or .xplane.pb> [--dump]
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the benchmark writes around its own calls
ANNOTATIONS = ("dispatch", "fence")
#: how many of the longest gaps are attributed (the rest are microseconds)
ATTRIBUTED_GAPS = 1000
#: operations that only enclose others (a scanned loop is one ``while``):
#: their children are in the trace too, so they are left out of the sums
CONTAINERS = ("while", "conditional", "call")


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return found[-1]


def read_planes(path):
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
    return planes


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def op_name(name):
    """The operation's own name without its instance number: the event
    name is the HLO line (``%fusion.123 = bf16[8,1024]{1,0:T(8,128)} ...``,
    ``jit_step(138...)``), so instances of one kind sum together and a
    kernel or a program keeps its name."""
    own = name.split(" = ")[0].split("(")[0].strip().lstrip("%")
    return re.sub(r"[.:]\d+$", "", own)


def op_label(name):
    """``fusion bf16[50304,1024]``: kind and result shape, for the
    breakdown (the same fusion of every layer sums under one label)."""
    kind = op_name(name)
    shape = re.match(r"\(*([a-z0-9]+\[[0-9,]*\])", name.split(" = ", 1)[-1]
                     ) if " = " in name else None
    return "%s %s" % (kind, shape.group(1)) if shape else kind


def _busy_between(merged, starts, prefix, a, b):
    """Busy nanoseconds of the merged intervals inside [a, b]."""
    if b <= a or not merged:
        return 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    j = bisect.bisect_left(starts, b)
    total = prefix[j] - prefix[i]
    if merged[i][1] > a >= merged[i][0]:
        total -= a - merged[i][0]
    elif merged[i][1] <= a:
        total -= merged[i][1] - merged[i][0]
    if j > 0 and merged[j - 1][1] > b:
        total -= merged[j - 1][1] - b
    return max(total, 0.0)


def step_gaps(merged, progs):
    """Idle seconds between consecutive runs of the program that takes most
    of the device's time (the step, or the scanned loop): the time from one
    run's end to the next one's start in which no operation ran at all."""
    by_name = {}
    for name, start, dur in progs:
        by_name.setdefault(op_name(name), []).append((start, start + dur))
    if not by_name:
        return []
    runs = sorted(max(by_name.values(),
                      key=lambda r: sum(e - s for s, e in r)))
    starts = [m[0] for m in merged]
    prefix = [0.0]
    for s, e in merged:
        prefix.append(prefix[-1] + (e - s))
    return [max(((b[0] - a[1]) - _busy_between(merged, starts, prefix,
                                               a[1], b[0])) * 1e-9, 0.0)
            for a, b in zip(runs, runs[1:])]


def _attribute(gap, spans):
    start, end = gap
    best, best_cover = "unattributed", 0.0
    for name, s, d in spans:
        cover = min(end, s + d) - max(start, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best if best_cover >= 0.5 * (end - start) else "unattributed"


def reduce_planes(planes, chips=1):
    """The reduction: seconds throughout.

    ``busy_s``: union of the intervals in which an operation ran, averaged
    over the ``chips`` device planes; ``window_s``: the traced window on the
    device's own clock, from the first device operation's start to the last
    one's end (in a training cell: the first step program to the last);
    ``ops``: {name: seconds} summed over instances (per chip average);
    ``modules`` / ``module_runs``: seconds and executions of each program;
    ``module_gaps_s``: idle between consecutive runs of the dominant
    program on chip 0 (``step_gaps``);
    ``top_ops`` / ``top_gaps``: the ledger-shaped breakdown."""
    device = sorted((int(DEVICE_PLANE.match(n).group(1)), n)
                    for n in planes if DEVICE_PLANE.match(n))[:chips]
    if not device:
        raise ValueError("no /device:TPU:<n> plane in the trace (planes: %s)"
                         % sorted(planes))
    host_spans = [ev for name, lines in planes.items()
                  if not DEVICE_PLANE.match(name)
                  for events in lines.values()
                  for ev in events if ev[0] in ANNOTATIONS]
    busy, span, ops, labelled, modules, runs = 0.0, 0.0, {}, {}, {}, {}
    gaps, module_gaps = [], []
    for index, name in device:
        events = planes[name].get(OPS_LINE, [])
        merged = union((s, s + d) for _, s, d in events)
        busy += sum(e - s for s, e in merged) * 1e-9
        if merged:
            span = max(span, (merged[-1][1] - merged[0][0]) * 1e-9)
        for ev_name, _, d in events:
            key, label = op_name(ev_name), op_label(ev_name)
            if key in CONTAINERS:
                continue
            ops[key] = ops.get(key, 0.0) + d * 1e-9 / len(device)
            labelled[label] = labelled.get(label, 0.0) + d * 1e-9 / len(device)
        progs = sorted(planes[name].get(MODULES_LINE, []), key=lambda e: e[1])
        for ev_name, _, d in progs:
            key = op_name(ev_name)
            modules[key] = modules.get(key, 0.0) + d * 1e-9 / len(device)
            if index == device[0][0]:
                runs[key] = runs.get(key, 0) + 1
        if index == device[0][0]:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
            module_gaps = step_gaps(merged, progs)
    gaps.sort(key=lambda g: g[0] - g[1])  # longest first
    top_gaps = {}
    for gap in gaps[:ATTRIBUTED_GAPS]:
        key = _attribute(gap, host_spans)
        top_gaps[key] = top_gaps.get(key, 0.0) + (gap[1] - gap[0]) * 1e-9
    top_ops = sorted(labelled.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy / len(device),
        "window_s": span,
        "ops": ops,
        "modules": modules,
        "module_runs": runs,
        "module_gaps_s": module_gaps,
        "top_ops": [[k, v] for k, v in top_ops[:10]],
        "top_gaps": [[k, v] for k, v in sorted(
            top_gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce_dir(path, chips=1):
    return reduce_planes(read_planes(path), chips)


def main(argv):
    if not argv:
        raise SystemExit(__doc__)
    planes = read_planes(argv[0])
    if "--dump" in argv:
        for name, lines in planes.items():
            print("plane %r" % name)
            for line, events in lines.items():
                names = {}
                for ev in events:
                    names[op_name(ev[0])] = names.get(op_name(ev[0]), 0) + 1
                top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
                print("  line %r: %d events; %s" % (line, len(events), top))
        return 0
    out = reduce_planes(planes)
    out.pop("ops")
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
