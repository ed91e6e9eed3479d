#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found BY NAME from ``BENCHMARK.json``
(see ``benchmark/README.md``): the configuration's file, the traffic mix's
file, ``workloads/<cell>.json`` (driver, limits of ``correct``),
``drivers/<driver>.py``, ``references/<config>.py`` and one
``layer_metrics/<metric>.py`` per per-layer metric. Nothing cell-specific
lives in this file: it parses the arguments, refuses a machine without the
chips the cell asks for or a ``device_kind`` that ``peaks.json`` does not
list, owns the clock of set-up, the trace and the last line.

One process, one run: set-up (weights from the seed, warm-up of exactly the
cell's shapes; compile or cache load) -> the measured window -> the device's
peak memory read, the program's state freed -> the reference over what the
window produced -> one JSON line, the last line of standard output.

``--rehearse`` is for a sandbox without a chip: the same code at the tiny
sizes each data file gives under ``"rehearsal"``, on the CPU. Its line says
``"platform": "cpu"`` and carries no metric: a CPU time is never printed
under the name of a device number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what a run leaves behind, inside the checkout and git-ignored
OUT_DIR = os.path.join(ROOT, "benchmark_out")
CACHE_DIR = os.path.join(OUT_DIR, "jax_cache")
#: the longest window a ``--trace 1`` run traces, unless the cell's
#: workload file says otherwise (traces are large; the reduction is Python)
TRACE_SECONDS = 4.0


def say(fmt, *args):
    print(fmt % args if args else fmt, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: no %s file %s" % (kind, path))
    mod_name = "benchmark_%s_%s" % (kind, "".join(
        c if c.isalnum() else "_" for c in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def with_rehearsal(data, rehearse):
    """A data file's sizes, with its ``"rehearsal"`` overrides applied."""
    data = dict(data)
    tiny = data.pop("rehearsal", {})
    if rehearse:
        for key, val in tiny.items():
            if isinstance(val, dict) and isinstance(data.get(key), dict):
                data[key] = dict(data[key], **val)
            else:
                data[key] = val
    return data


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, name, rehearse=False):
        self.spec = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise SystemExit("benchmark: no workload %r in BENCHMARK.json "
                             "(has: %s)" % (name, ", ".join(sorted(cells))))
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        config = {c["name"]: c for c in self.spec["configs"]}[
            self.entry["config"]]
        self.config = with_rehearsal(load_json(ROOT, config["file"]), rehearse)
        self.traffic = with_rehearsal(
            load_json(HERE, "traffic", self.entry["traffic"] + ".json"),
            rehearse)
        self.workload = with_rehearsal(
            load_json(HERE, "workloads", name + ".json"), rehearse)
        self.limits = self.workload["limits"]
        self.reference = load_module("references", self.entry["config"])
        self.driver_module = load_module("drivers", self.workload["driver"])
        self.rehearse = rehearse

    def metrics(self, group):
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.spec[group]
                if "workloads" not in m or self.name in m["workloads"]]


def configure_jax(rehearse):
    """The compile cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept in it whatever
    its size or compile time, no cap: the chip machine's 192 MiB cap evicts
    a 24-layer step as soon as it is written."""
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MXNET_PALLAS", "0")
    import jax

    from mxnet_tpu.compile import jit_cache

    os.makedirs(CACHE_DIR, exist_ok=True)
    jit_cache.enable(CACHE_DIR)  # leaves the place to the variable if set
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def find_devices(jax, cell, peaks):
    devices = jax.devices()
    dev = devices[0]
    if cell.rehearse:
        return devices[:cell.chips], None
    if dev.platform != "tpu":
        raise SystemExit("benchmark: no accelerator (jax found %r); a CPU "
                         "time is not a device number" % dev.platform)
    if len(devices) < cell.chips:
        raise SystemExit("benchmark: %s asks for %d chips, jax found %d"
                         % (cell.name, cell.chips, len(devices)))
    if dev.device_kind not in peaks:
        raise SystemExit("benchmark: no peaks for device kind %r in "
                         "peaks.json" % dev.device_kind)
    return devices[:cell.chips], peaks[dev.device_kind]


class CompileWatch:
    """Counts what jax compiles or loads from its cache while armed."""

    EVENTS = ("/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self, jax):
        self.armed = False
        self.seen = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if self.armed and event in self.EVENTS:
            self.seen.append(event)

    def _duration(self, event, secs, **kw):
        if self.armed and event in self.DURATIONS:
            self.seen.append(event)


def memory_read(devices, key):
    """The runtime's counter ``key`` on the fullest chip."""
    reads = [(d.memory_stats() or {}).get(key) for d in devices]
    reads = [r for r in reads if r is not None]
    return max(reads) if reads else None


def memory_peak(devices, driver, say):
    """Peak device memory of the program, read once the window has closed.

    The runtime's ``peak_bytes_in_use`` counts buffers and leaves out the
    temporaries a running program holds (a 24-layer LM step read 2.40 GB
    there against 9.3 GB live by the compiler's own analysis, PERF.md). A
    driver that can reach the compiled step says what the step holds
    while it runs (``program_memory()``: the executable's
    ``memory_analysis()``); the peak is then what is live between steps
    plus that, where it is more than the counter saw."""
    counter = memory_read(devices, "peak_bytes_in_use")
    held = memory_read(devices, "bytes_in_use")
    parts = {"counter_peak_bytes": counter, "in_use_after_window_bytes": held}
    running = getattr(driver, "program_memory", lambda: None)()
    if running is not None and held is not None:
        parts["program_running_bytes"] = int(running)
        counter = max(counter or 0, held + int(running))
    say("  peak device memory %s bytes %s", counter, json.dumps(parts))
    return counter, parts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)  # the system under test: mxnet_tpu
    sys.path.insert(0, HERE)
    cell = Cell(args.workload, args.rehearse)
    jax = configure_jax(args.rehearse)
    import trace_reduce

    peaks_table = load_json(HERE, "peaks.json")["device_kinds"]
    devices, peaks = find_devices(jax, cell, peaks_table)
    watch = CompileWatch(jax)

    driver = cell.driver_module.Driver(
        config=cell.config, traffic=cell.traffic, seed=args.seed,
        reference=cell.reference, devices=devices, rehearse=args.rehearse,
        log=say)
    driver.setup()
    from mxnet_tpu.compile import jit_cache

    cache = dict(jit_cache.stats())
    setup_s = time.perf_counter() - T_START
    say("[%s] set-up %.2f s (compile cache: %d hits, %d misses)", cell.name,
        setup_s, cache["hits"], cache["misses"])

    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, float(
            cell.workload.get("trace_seconds", TRACE_SECONDS)))
        trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    watch.armed = True
    try:
        window = driver.window(seconds)
    finally:
        watch.armed = False
        if args.trace:
            jax.profiler.stop_trace()
    say("[%s] window %.3f s", cell.name, window["window_s"])
    mem_peak, mem_parts = memory_peak(devices, driver, say)

    trace = None
    if args.trace:
        trace = trace_reduce.reduce_dir(trace_dir, len(devices))
        if not os.environ.get("BENCHMARK_KEEP_TRACE"):  # for a fixture
            shutil.rmtree(trace_dir, ignore_errors=True)

    # only now the reference: the program's state is freed first, so the
    # peak above is the program's own
    driver.release()
    gc.collect()
    t_ref = time.perf_counter()
    checks = dict(driver.check(say))
    checks["compiles_in_window"] = float(len(watch.seen))
    say("[%s] reference and comparison %.2f s", cell.name,
        time.perf_counter() - t_ref)
    limits = dict(cell.limits, compiles_in_window=0.0)
    compared = {}
    correct = True
    for name, value in checks.items():
        if name not in limits:
            raise SystemExit("benchmark: %s compares %r, which has no limit "
                             "in workloads/%s.json" % (cell.name, name,
                                                       cell.name))
        ok = value is not None and value == value and value <= limits[name]
        correct = correct and ok
        compared[name] = {"value": value, "limit": limits[name], "ok": ok}

    measured = dict(window["metrics"], setup_s=setup_s)
    counters = dict(window.get("counters", {}),
                    compile_misses=cache["misses"],
                    compile_hits=cache["hits"])
    run = dict(config=cell.config, traffic=cell.traffic, peaks=peaks,
               window_s=window["window_s"], metrics=measured,
               chips=len(devices), reference=cell.reference)
    metrics = {}
    if args.trace:
        for m in cell.metrics("per_layer"):
            value = load_module("layer_metrics", m["name"]).compute(
                trace, counters, run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak,
              "memory_parts": mem_parts}
    line = {"correct": bool(correct), "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["top_ops"][:10],
                             "idle_gaps": trace["top_gaps"][:10]}
    if args.rehearse:
        say("[%s] rehearsal on the CPU, not device numbers: %s", cell.name,
            json.dumps(metrics))
        line["metrics"] = {}
        line["rehearsal"] = True
    line["compared"] = compared
    for name, c in compared.items():
        say("compared %-24s %.6g (limit %.6g) %s", name,
            float("nan") if c["value"] is None else c["value"], c["limit"],
            "ok" if c["ok"] else "NOT OK")
    say("correct: %s", correct)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
