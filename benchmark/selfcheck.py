#!/usr/bin/env python3
"""The benchmark's check of itself: seconds, on the CPU, no chip.

    python3 benchmark/selfcheck.py

1. ``BENCHMARK.json`` keeps to its contract's limits (keys, names, units,
   bounds, every name leads to its files), so a refusal "before a single
   run" is found here first.
2. ``trace_reduce`` on the recorded v5e trace ``fixtures/tiny_v5e.xplane.pb``
   (five runs of one small program, 30 ms of sleep between them) gives the
   numbers that trace is known to hold.
3. ``flops.py`` equals ``bench_lm.model_flops_per_token`` at the old
   12-layer shape (less the masked half of attention, which that script
   counts) and a count by hand at gpt2-medium; the references' own counts
   (``train_flops``, ``attention_work``) equal counts by hand.
4. ``traffic.py`` gives the same seed the same inputs, rows that all
   differ; seeds above 2**31 work.
5. ``run.py`` on a machine without a TPU exits non-zero and prints nothing
   on standard output (``--rehearse`` is the only way to run on a CPU, and
   its line carries no metric).

Not part of tier-1 (nothing under ``tests/``): run it by hand after a change
to the benchmark. ``benchmark/tests/`` holds the controls of ``correct``.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "head_size", "expansion", "experts_per_tok")


def check(cond, fmt, *args):
    if not cond:
        raise AssertionError(fmt % args if args else fmt)


def line_ok(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def check_spec():
    path = os.path.join(ROOT, "BENCHMARK.json")
    check(os.path.getsize(path) <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    with open(path) as f:
        spec = json.load(f)
    check(set(spec) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json keys: %s", sorted(spec))
    check(1 <= len(spec["paths"]) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        for p in spec["paths"]), "paths: %s", spec["paths"])
    check(1 <= len(spec["command"]) <= 32 and all(
        line_ok(w) for w in spec["command"]), "command")
    check(isinstance(spec["run_seconds"], int)
          and 1 <= spec["run_seconds"] <= 51, "run_seconds")
    # a full check of 24 cells has to fit into 43200 s
    cells = 24
    budget = (2 + 14 * cells) * (spec["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200
    check(budget <= 43200, "run_seconds %d: a full check of %d cells needs "
          "%d s of 43200", spec["run_seconds"], cells, budget)

    def under_paths(p):
        return any(p == d or p.startswith(d.rstrip("/") + "/")
                   for d in spec["paths"])

    configs = {}
    files = set()
    check(1 <= len(spec["configs"]) <= 24, "configs count")
    for c in spec["configs"]:
        check(set(c) == {"name", "source", "file", "reduced", "why"},
              "config keys %s", sorted(c))
        check(NAME.match(c["name"]) and c["name"] not in configs,
              "config name %r", c["name"])
        check(line_ok(c["source"]) and line_ok(c["why"]), "config text")
        check(under_paths(c["file"]) and c["file"] not in files
              and os.path.isfile(os.path.join(ROOT, c["file"])),
              "config file %r", c["file"])
        check(len(c["reduced"]) <= 16 and all(
            NAME.match(k) and not k.endswith(("_dim", "_rank"))
            and not any(w in k for w in WIDTH_WORDS)
            for k in c["reduced"]), "reduced names a width: %s", c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        check(sorted(body.get("reduced", [])) == sorted(c["reduced"]),
              "%s: reduced differs between BENCHMARK.json and the file",
              c["name"])
        check(os.path.isfile(os.path.join(
            HERE, "references", c["name"] + ".py")),
            "no reference for %s", c["name"])
        files.add(c["file"])
        configs[c["name"]] = c

    cells, pairs, used = {}, set(), set()
    check(1 <= len(spec["workloads"]) <= 24, "workloads count")
    for w in spec["workloads"]:
        check(set(w) == {"name", "config", "traffic", "chips", "why"},
              "workload keys %s", sorted(w))
        check(NAME.match(w["name"]) and w["name"] not in cells,
              "workload name %r", w["name"])
        check(w["config"] in configs and NAME.match(w["traffic"]),
              "workload %s: config/traffic", w["name"])
        check((w["config"], w["traffic"]) not in pairs, "pair twice")
        check(w["chips"] in (1, 4) and line_ok(w["why"]), "chips/why")
        for kind, name in (("traffic", w["traffic"]),
                           ("workloads", w["name"])):
            check(os.path.isfile(os.path.join(HERE, kind, name + ".json")),
                  "no %s/%s.json", kind, name)
        with open(os.path.join(HERE, "workloads", w["name"] + ".json")) as f:
            body = json.load(f)
        check(os.path.isfile(os.path.join(
            HERE, "drivers", body["driver"] + ".py")),
            "no driver %s", body["driver"])
        check(body["limits"], "%s: no limits of correct", w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cells[w["name"]] = w
    check(used == set(configs), "a configuration no cell uses: %s",
          set(configs) - used)
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    check(four <= max(1, len(cells) // 4), "too many 4-chip cells")

    names = set()
    e2e = {}
    check(1 <= len(spec["end_to_end"]) <= 16, "end_to_end count")
    for m in spec["end_to_end"]:
        check(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}, "metric keys %s", m)
        check(NAME.match(m["name"]) and m["name"] not in names
              and UNIT.match(m["unit"]), "metric %r", m["name"])
        check(m["better"] in ("lower", "higher"), "better")
        check(m["source"] in ("host_clock", "device_trace"), "e2e source")
        check(0.01 <= m["bound"] <= 0.1, "bound of %s", m["name"])
        check(all(c in cells for c in m.get("workloads", [])), "cells")
        names.add(m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
    check("setup_s" in e2e and e2e["setup_s"] == set(cells),
          "setup_s is reported by every cell")
    for c in cells:
        check(sum(1 for n, ws in e2e.items() if c in ws and n != "setup_s"),
              "%s reports no end-to-end metric besides setup_s", c)

    check(1 <= len(spec["per_layer"]) <= 128, "per_layer count")
    covered = set()
    for m in spec["per_layer"]:
        check(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}, "keys %s", m)
        check(NAME.match(m["name"]) and m["name"] not in names
              and UNIT.match(m["unit"]), "metric %r", m["name"])
        check(m["better"] in ("lower", "higher")
              and m["source"] in SOURCES and line_ok(m["layer"]),
              "per-layer %s", m["name"])
        check(m["moves"] in e2e, "%s moves %r", m["name"], m["moves"])
        for c in m.get("workloads", e2e[m["moves"]]):
            check(c in e2e[m["moves"]], "%s lists %s, which does not "
                  "report %s", m["name"], c, m["moves"])
            covered.add(c)
        check(os.path.isfile(os.path.join(
            HERE, "layer_metrics", m["name"] + ".py")),
            "no layer_metrics/%s.py", m["name"])
        names.add(m["name"])
    check(covered == set(cells), "cells without a per-layer metric: %s",
          set(cells) - covered)
    for top, _, found in os.walk(HERE):
        if "__pycache__" in top:
            continue
        for name in found:
            check(re.match(r"^[A-Za-z0-9_.\-]+$", name), "file name %r", name)
    return spec


def check_trace():
    import trace_reduce

    out = trace_reduce.reduce_dir(
        os.path.join(HERE, "fixtures", "tiny_v5e.xplane.pb"))
    check(abs(out["busy_s"] - 3.7492e-05) < 1e-9, "busy %r", out["busy_s"])
    check(abs(out["window_s"] - 0.126222265) < 1e-8, "window %r",
          out["window_s"])
    check(out["module_runs"] == {"jit_tiny_step": 5}, "runs %r",
          out["module_runs"])
    gaps = sorted(out["module_gaps_s"])
    check(len(gaps) == 4 and abs(gaps[0] - 0.031434892) < 1e-8
          and abs(gaps[-1] - 0.031728069) < 1e-8, "gaps %r", gaps)
    check(out["top_ops"][0][0] == "convolution_tanh_fusion bf16[512,512]"
          and abs(out["top_ops"][0][1] - 3.7412e-05) < 1e-9,
          "top op %r", out["top_ops"][0])
    idle = 1.0 - out["busy_s"] / out["window_s"]
    check(abs(idle - 0.99970297) < 1e-6, "idle share %r", idle)
    check(out["top_gaps"][0][0] == "unattributed", "gaps are the sleeps")
    check(trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]],
          "union")
    check(trace_reduce.op_name("%fusion.12 = bf16[8,4]{1,0} fusion(...)")
          == "fusion" and trace_reduce.op_name(
              "jit_step(1382403862533162480)") == "jit_step", "op_name")


def check_flops(spec):
    import flops
    import run as harness

    d, L, V, T = 1024, 12, 32000, 1024
    old = 3 * (L * 24 * d * d + 2 * d * V) + L * 12 * T * d  # bench_lm.py
    masked = 6 * L * d * (T - 1)  # the half of attention a causal mask skips
    got = flops.lm_train_flops_per_token(d, L, 4 * d, V, T)
    check(got + masked == old, "flops.py %r, bench_lm's formula %r less the "
          "masked half %r", got, old, masked)
    sys.path.insert(0, ROOT)
    try:
        import bench_lm
    except ImportError:
        bench_lm = None
    if bench_lm is not None:
        class Cfg:
            d_model, num_layers, vocab_size = d, L, V
        check(bench_lm.model_flops_per_token(Cfg, T) == old, "bench_lm")
    work, nbytes = flops.causal_attention_work(8, 16, 1024, 64)
    check(work == 8 * 16 * 12 * (1024 * 1025 / 2) * 64, "attention flops")
    check(nbytes == 8 * 16 * 1024 * 64 * 2 * 12, "attention bytes")
    # each configuration's own count, against a count by hand
    hand = {
        # 24 x (8 + 16) x 1024^2 + 2 x 1024 x 50304 + 24 x 4 x 1024 x 512.5
        # (causal) a token forward, x 3, x 8 x 1024 tokens a step
        "gpt2m-train-t1024": 3 * (24 * 24 * 1024 ** 2 + 2 * 1024 * 50304
                                  + 24 * 4 * 1024 * 512.5) * 8 * 1024,
        # 4.089 GMAC an image forward (stride on the 3x3), x 6, x 128
        "resnet50-fit-bs128": 6 * 4089184256 * 128,
    }
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"])
        count = getattr(cell.reference, "train_flops", None)
        if count is not None and w["name"] in hand:
            check(count(cell.config, cell.traffic) == hand[w["name"]],
                  "%s: train_flops %r, by hand %r", w["name"],
                  count(cell.config, cell.traffic), hand[w["name"]])
    cell = harness.Cell("gpt2m-train-t1024")
    check(cell.reference.attention_work(cell.config, cell.traffic)
          == (24 * work, 24 * nbytes), "gpt2-medium attention work")


def check_traffic():
    import numpy as np

    import traffic

    mix = {"batch": 4, "seq_len": 16}
    t = traffic.token_batches(mix, 500, 2 ** 31 + 5, 3)
    check(t.shape == (3, 4, 17) and t.max() < 500 and len(
        {row.tobytes() for row in t.reshape(-1, 17)}) == 12, "token rows")
    check(np.array_equal(t, traffic.token_batches(mix, 500, 2 ** 31 + 5, 3))
          and not np.array_equal(t, traffic.token_batches(mix, 500, 6, 3)),
          "same seed other tokens, or another seed the same")


def check_no_chip(spec):
    cell = spec["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)
    check(out.returncode != 0, "run.py exited 0 without a TPU")
    check(out.stdout.strip() == "", "run.py printed a result without a TPU: "
          "%r", out.stdout[-300:])


def main():
    spec = check_spec()
    print("BENCHMARK.json: %d configs, %d cells, %d + %d metrics ok" % (
        len(spec["configs"]), len(spec["workloads"]),
        len(spec["end_to_end"]), len(spec["per_layer"])))
    check_trace()
    print("trace_reduce on the v5e fixture ok")
    check_flops(spec)
    print("flops.py ok")
    check_traffic()
    print("traffic.py ok")
    check_no_chip(spec)
    print("run.py refuses a machine without a TPU ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
