"""The comparisons that decide ``correct``.

Every number is a gap between what the timed path produced and what the
plain reference gives for the same seed; ``run.py`` holds each to the limit
that ``workloads/<cell>.json`` gives it (how the limits were set: PERF.md).
"""
from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding: Adam moves it by round-off alone, so it
#: is left out of the change comparison
ZERO_GRAD_SHARE = 1e-3


def norm_gap(got, want):
    """Worst leaf's gap between two lists of leaf norms: |got - want| over
    the larger of the reference's norm of that leaf and of the median
    leaf. Returns (gap, index)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = max(float(np.median(want)), 1e-30)
    gaps = np.abs(got - want) / np.maximum(want, floor)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst


def training_gaps(got, want, names=None, say=None):
    """``got``/``want``: dicts with ``loss`` [steps], ``grad_norm``
    [leaves], ``change_norm`` [leaves], from the program and from the
    reference. Returns the three numbers a training cell compares."""
    loss_got = np.asarray(got["loss"], np.float64)
    loss_want = np.asarray(want["loss"], np.float64)
    loss_gap = float(np.max(np.abs(loss_got - loss_want) / np.abs(loss_want)))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    grad_gap, gi = norm_gap(got["grad_norm"], want["grad_norm"])
    ref_grad = np.asarray(want["grad_norm"], np.float64)
    moved = ref_grad >= ZERO_GRAD_SHARE * np.median(ref_grad)
    change_gap, ci = norm_gap(np.asarray(got["change_norm"])[moved],
                              np.asarray(want["change_norm"])[moved])
    if say is not None and names is not None:
        kept = [n for n, m in zip(names, moved) if m]
        say("worst leaves: grad %s, change %s; %d of %d leaves compared",
            names[gi], kept[ci], len(kept), len(names))
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap}


def fit_gaps(got, want, names=None, say=None, first=3):
    """A scanned ``fit`` shows no state inside a chunk of K steps, so what
    its cell compares is the parameters' change over the whole first chunk,
    leaf by leaf as norms, in three numbers (PERF.md section 2 has the look
    and the readings behind each):

    ``change_global_gap``: the gap of the whole update's norm (the root of
    the leaves' squared change norms): the steadiest reading, and the one
    that separates the operands' precision best.
    ``change_l2_gap``: the root of the leaves' squared gaps of norms over
    the whole update's norm: a wrong update of ANY one leaf shows here in
    proportion to the share of the update it carries.
    ``change_median_gap``: the median leaf's gap by the training measure
    (against the larger of that leaf's and the median leaf's reference
    norm): half of the leaves wrong, however small, show here.

    The worst leaf's gap by that measure is printed and not compared: over
    K = 16 steps of a 53-BatchNorm network it is the noise of one 64-wide
    gamma or beta (sound runs and the control read alike, 0.2 to 0.44).
    Nor are the first steps' losses: neither the control nor a fault reads
    three times what sound runs do. Leaves with a nought gradient are left
    out, as above."""
    loss_got = np.asarray(got["loss"], np.float64)[:first]
    loss_want = np.asarray(want["loss"], np.float64)[:first]
    loss_gap = float(np.max(np.abs(loss_got - loss_want) / np.abs(loss_want)))
    ref_grad = np.asarray(want["grad_norm"], np.float64)
    moved = ref_grad >= ZERO_GRAD_SHARE * np.median(ref_grad)
    change_got = np.asarray(got["change_norm"], np.float64)[moved]
    change_want = np.asarray(want["change_norm"], np.float64)[moved]
    whole_got = float(np.sqrt(np.sum(np.square(change_got))))
    whole = max(float(np.sqrt(np.sum(np.square(change_want)))), 1e-30)
    floor = max(float(np.median(change_want)), 1e-30)
    diffs = np.abs(change_got - change_want)
    gaps = diffs / np.maximum(change_want, floor)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    out = {"change_global_gap": abs(whole_got - whole) / whole,
           "change_l2_gap": float(np.sqrt(np.sum(np.square(diffs)))) / whole,
           "change_median_gap": float(np.median(gaps))}
    out = {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}
    if say is not None and names is not None:
        kept = [n for n, m in zip(names, moved) if m]
        say("not compared: loss gap of the first %d steps %.4g, worst leaf's "
            "change gap %.4g (%s); %d of %d leaves compared", first, loss_gap,
            float(np.max(gaps)), kept[int(np.argmax(gaps))], len(kept),
            len(names))
    return out
