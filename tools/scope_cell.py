#!/usr/bin/env python3
"""One traced run of a benchmark cell with its device time by named scope.

    python tools/scope_cell.py --workload <cell> --seed <n> [--seconds 10]
                               [--out <dir>] [--top 25]

The benchmark's own command (``benchmark/run.py --trace 1``) with three
things switched on around it, none of them an edit to the benchmark: the
capture goes through ``mx.profiler`` (so the step hands over its program and
``scopes.json`` is written beside the trace), ``MXNET_TELEMETRY=1`` (so
``train.step`` / ``fit.*`` spans are in the capture) and the trace is kept
until it has been read. Prints the benchmark's line, then the table of
``tools/telemetry_report.py --xplane``; ``--out`` also gets ``<cell>.json``
(``mx.profiler.scope_times``' result) and ``<cell>.txt`` (the table).

This is the builder's reading until the benchmark reports
``breakdown.device_scopes`` itself (ROADMAP, the next ``benchmark`` issue):
the run is a TRACED run with telemetry on, so its ``train_step_ms`` is not
the cell's number. Chip only, like the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="directory for <cell>.json and <cell>.txt")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["BENCHMARK_KEEP_TRACE"] = "1"
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import jax

    import run as harness
    import telemetry_report
    from mxnet_tpu import profiler

    # jax keys its compile cache without the metadata: an entry written by
    # a tree that opened other scopes must not stand in for this one's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    real = jax.profiler.start_trace, jax.profiler.stop_trace

    def through_profiler(state, log_dir=None):
        # the harness starts and stops the capture itself: route both
        # through mx.profiler, which the step hands its program to
        patched = jax.profiler.start_trace, jax.profiler.stop_trace
        jax.profiler.start_trace, jax.profiler.stop_trace = real
        try:
            if log_dir is not None:
                profiler.profiler_set_config(filename=log_dir)
            profiler.profiler_set_state(state)
        finally:
            jax.profiler.start_trace, jax.profiler.stop_trace = patched

    jax.profiler.start_trace = lambda log_dir, *a, **k: through_profiler(
        "run", log_dir)
    jax.profiler.stop_trace = lambda: through_profiler("stop")
    run_args = ["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"]
    harness.main(run_args + (["--rehearse"] if args.rehearse else []))

    trace_dir = os.path.join(harness.OUT_DIR, "trace", args.workload)
    table = profiler.scope_times(trace_dir)
    text = "\n".join(telemetry_report.scope_section(
        table, args.top, args.workload))
    print(text)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
            json.dump(table, f)
        with open(os.path.join(args.out, args.workload + ".txt"), "w") as f:
            f.write(text + "\n")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
