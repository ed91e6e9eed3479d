"""The controls of ``correct``, at a size a test run can hold (the CPU, the
sizes each data file gives under ``"rehearsal"``).

    python3 -m pytest benchmark/tests -q

Not collected by tier-1 (it runs ``tests/``). Two things are kept here, for
every cell of ``BENCHMARK.json``:

* the control: the plain reference put in the program's place and computed
  in the nearest precision below the one the configuration states (fp8
  operands for bfloat16) comes out NOT correct under the cell's limits,
  while the program at the same size passes them;
* the timed path broken underneath: with the harness's look for a chip
  skipped (``--rehearse``) and the rest of a run driven as always,
  ``correct`` comes out false for each fault the cell can have, as its
  driver's file lists and plants them (``FAULTS``): today a step that
  returns its state unchanged, and half of the batch left out with the mean
  taken over the rest. (One chip: there is no exchange to leave out. No
  token is produced: these are training cells.)

The readings of the same control and faults on the chip at the cells' own
sizes are in PERF.md section 2, where each limit is set.
"""
from __future__ import annotations

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_PALLAS", "0")

import calibrate  # noqa: E402
import run as harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SEED = 2 ** 31 + 77


def run_cell(cell, seed=SEED):
    """Drive one whole run of the harness in this process; its last line."""
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    return line


def failing(line):
    return [n for n, c in line["compared"].items() if not c["ok"]]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_control_is_not(cell):
    assert run_cell(cell)["correct"] is True
    spec = harness.Cell(cell, rehearse=True)
    jax = harness.configure_jax(True)
    driver = spec.driver_module.Driver(
        config=spec.config, traffic=spec.traffic, seed=SEED,
        reference=spec.reference, devices=jax.devices()[:1], rehearse=True)
    row = calibrate.readings(driver, with_controls=True)
    over = {n: v / spec.limits[n] for n, v in row["control_fp8"].items()}
    assert max(over.values()) > 1.0, (
        "the fp8 control passes every limit: %s" % row["control_fp8"])
    # and it is the control that the limit separates, not noise: it reads
    # well above the program at this size too (on the chip at the cell's
    # own size: three times and more, PERF.md section 2)
    worst = max(over, key=over.get)
    assert row["control_fp8"][worst] > 1.5 * row["program"][worst]


def faults_of(cell):
    """The faults a cell can have, as its driver's file lists them
    (``FAULTS``: name -> a function that plants it with ``monkeypatch``)."""
    return harness.Cell(cell, rehearse=True).driver_module.FAULTS


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS for fault in sorted(faults_of(cell))])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    faults_of(cell)[fault](monkeypatch, fault)
    line = run_cell(cell)
    assert line["correct"] is False, line["compared"]
    assert failing(line), line["compared"]
