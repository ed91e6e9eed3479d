#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md section 2).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3,... \\
        [--controls 3] [--out chiprun_out/calibrate.json] [--config-set key=value]

One process, on the chip, at the cell's own size: for every seed the
program's numbers against the reference (the lower reading is their largest),
and for the first ``--controls`` seeds the control (the reference computed in
the nearest lower precision) and each planted fault against the same
reference (the upper reading is their smallest). A training cell needs no
measured window for this. ``--config-set compute_dtype=float32`` reads the
program on another of its own paths (a second witness beside the reference;
never a cell). A driver serves it with ``program_readings()``,
``reference_readings(quant=, keep_rows=)`` and ``gaps``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def readings(driver, with_controls):
    """One seed's row: the program against the reference, and with
    ``with_controls`` the control and the half-batch fault (both the
    reference, put in the program's place) against the same reference.
    ``detail_*`` keeps every reading, so that another number can be tried
    on them without another chip run."""
    got = driver.program_readings()
    names = driver.ref.leaf_names(driver.config)
    want = driver.reference_readings()
    row = {"program": driver.gaps(got, want, names, driver.log)}
    detail = {"got": got, "want": want}
    if with_controls:
        controls = [("control_fp8", dict(quant="fp8"))] + [
            ("control_" + quant, dict(quant=quant))
            for quant in getattr(driver.ref, "EXTRA_CONTROLS", ())] + [
            ("fault_half_batch", dict(
                keep_rows=int(driver.mix["batch"]) // 2))]
        for key, kw in controls:
            detail[key] = driver.reference_readings(**kw)
            row[key] = driver.gaps(detail[key], want, names, driver.log)
    for key, read in detail.items():
        row["detail_" + key] = {k: [float(x) for x in v]
                                for k, v in read.items()}
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        harness.ROOT, "chiprun_out", "calibrate.json"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--config-set", action="append", default=[],
                    metavar="KEY=VALUE", help="run the program and the "
                    "reference on the configuration with this key changed")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload, args.rehearse)
    for item in args.config_set:
        key, value = item.split("=", 1)
        cell.config[key] = value
    jax = harness.configure_jax(args.rehearse)
    peaks = harness.load_json(HERE, "peaks.json")["device_kinds"]
    devices, _ = harness.find_devices(jax, cell, peaks)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = cell.driver_module.Driver(
            config=cell.config, traffic=cell.traffic, seed=seed,
            reference=cell.reference, devices=devices,
            rehearse=args.rehearse, log=harness.say)
        row = readings(driver, with_controls=i < args.controls)
        row["seed"] = seed
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        harness.say("seed %d: %s", seed, json.dumps(
            {k: v for k, v in row.items() if not k.startswith("detail")}))
        del driver
        gc.collect()
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f)
    print(json.dumps({"workload": args.workload, "rows": [
        {k: v for k, v in r.items() if not k.startswith("detail")}
        for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
