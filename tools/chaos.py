#!/usr/bin/env python
"""Chaos harness: run the test suite under a randomized-but-seeded
fault spec and print a survival report.

The point is not "all tests pass" — injected faults make fault-naive
tests fail by design. The point is the two guarantees the resilience
layer actually promises under fire:

  1. zero hangs   — the run completes inside --timeout (watchdogs and
                    barrier deadlines convert deadlocks into errors);
  2. zero corrupt — no checkpoint file is ever half-written in place
                    (atomic-rename discipline); the report scans for
                    torn .params files after the run.

Usage::

    python tools/chaos.py --seed 0 --points ckpt.write,rio.read
    python tools/chaos.py --seed 3 --points engine.task,kv.coord --full
    python tools/chaos.py --elastic     # SIGKILL/rejoin survival legs
    python tools/chaos.py --guardian    # grad.nan/loss.spike survival legs
    python tools/chaos.py --schedules   # thread-schedule survival legs
    python tools/chaos.py --proto       # protocol message-schedule legs
    python tools/chaos.py --jit         # mxjit compile/transfer legs
    python tools/chaos.py --controller  # mxctl closed-loop autonomy legs
    python tools/chaos.py --wsync       # live weight-sync survival legs

The spec is derived deterministically from --seed: per point, a fire
probability in [0.02, 0.15] and a per-point RNG seed. Same seed, same
spec, same casualty list — a chaos failure is bisectable.

The suite runs with mxtel enabled (MXNET_TELEMETRY=1 + a journal in the
scratch dir); the survival report folds the journal's fault-fire /
retry / watchdog counters in, so a chaos run *proves* the resilience
paths actually exercised — "0 injected faults surfaced" with a non-zero
fire counter means failures were healed silently (retries), which is
the success story, not a blind spot.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import re
import struct
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fast, fault-relevant subset: exercises recordio, checkpoints, engine,
# kvstore and the resilience layer itself without the full 15-min tier-1
SMOKE_TESTS = [
    "tests/unittest/test_resilience.py",
    "tests/unittest/test_recordio.py",
    "tests/unittest/test_engine.py",
    "tests/unittest/test_kvstore.py",
    "tests/unittest/test_model_module.py",
]

_ND_MAGIC = 0x112
# dtype code -> itemsize (mxnet_tpu/ndarray.py dtype codes)
_ITEMSIZE = {0: 4, 1: 8, 2: 2, 3: 1, 4: 4, 5: 1, 6: 8}


def _iter_params_records(f):
    """Walk one .params stream (pure struct, no jax): yields a
    (dtype_code, payload_bytes) pair per tensor, raising ValueError on
    any malformed structure. ONE parser for both the torn-file scan
    (_params_ok) and the guardian's non-finite value scan
    (_params_nonfinite) — a format change updated in one and not the
    other would silently void whichever scan lagged."""
    head = f.read(24)
    if len(head) < 24:
        raise ValueError("short header")
    magic, _, count = struct.unpack("<QQQ", head)
    if magic != _ND_MAGIC:
        raise ValueError("bad magic")
    raw = f.read(8)
    if len(raw) < 8:
        raise ValueError("short name count")
    (n_names,) = struct.unpack("<Q", raw)
    for _ in range(n_names):
        raw = f.read(8)
        if len(raw) < 8:
            raise ValueError("short name length")
        (ln,) = struct.unpack("<Q", raw)
        if len(f.read(ln)) < ln:
            raise ValueError("short name")
    for _ in range(count):
        raw = f.read(4)
        if len(raw) < 4:
            raise ValueError("short ndim")
        (ndim,) = struct.unpack("<I", raw)
        shape = f.read(4 * ndim)
        if len(shape) < 4 * ndim:
            raise ValueError("short shape")
        dims = struct.unpack("<%dI" % ndim, shape) if ndim else ()
        raw = f.read(4)
        if len(raw) < 4:
            raise ValueError("short dtype")
        (code,) = struct.unpack("<I", raw)
        if code not in _ITEMSIZE:
            raise ValueError("unknown dtype code %d" % code)
        n = 1
        for d in dims:
            n *= d
        nbytes = n * _ITEMSIZE[code]
        payload = f.read(nbytes)
        if len(payload) < nbytes:
            raise ValueError("short payload")
        yield code, payload
    if f.read(1) != b"":
        raise ValueError("trailing garbage")  # torn too


def _params_ok(path):
    """Structurally validate a .params file: the header, every name,
    and every tensor must parse to exactly EOF."""
    try:
        with open(path, "rb") as f:
            for _code, _payload in _iter_params_records(f):
                pass
        return True
    except (OSError, ValueError):
        return False


def build_spec(seed, points, mode):
    """Deterministic spec from a seed: per-point probability + RNG seed."""
    rng = random.Random(seed)
    rules = []
    for pt in points:
        p = round(rng.uniform(0.02, 0.15), 3)
        pt_seed = rng.randrange(1 << 16)
        if mode == "delay":
            rules.append("%s:delay=%.3f:p=%s:seed=%d"
                         % (pt, rng.uniform(0.01, 0.1), p, pt_seed))
        else:
            rules.append("%s:error:p=%s:seed=%d" % (pt, p, pt_seed))
    return ";".join(rules)


def fold_telemetry(journal_path):
    """Sum counters across the journal's per-test snapshots.

    The suite's conftest fixture flushes a ``mark="test_end"`` metrics
    record before resetting the registry between tests, and the final
    ``mark="exit"`` record covers activity after the last teardown —
    summing exactly those marks totals each window once (periodic
    snapshots are cumulative within a window and must not be summed)."""
    totals = {}
    try:
        with open(journal_path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "metrics" or \
                        rec.get("mark") not in ("test_end", "exit"):
                    continue
                for name, v in rec.get("counters", {}).items():
                    totals[name] = totals.get(name, 0) + v
    except OSError:
        return {}
    return totals


def fold_gauges(journal_path):
    """Last observed value per gauge across the journal. Gauges are
    point-in-time (compression ratio, optimizer-state bytes) — unlike
    counters the latest record wins, never a sum."""
    gauges = {}
    try:
        with open(journal_path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "metrics":
                    continue
                gauges.update(rec.get("gauges", {}))
    except OSError:
        return {}
    return gauges


def scan_torn_params(root):
    """Find .params files that do not parse past their header — a torn
    in-place write. .tmp leftovers from injected crashes are EXPECTED
    (they are the proof the rename never happened) and not counted."""
    torn = []
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".params") and not _params_ok(
                    os.path.join(dirpath, fn)):
                torn.append(os.path.join(dirpath, fn))
    return torn


def _params_nonfinite(path):
    """Count non-finite floats in a .params file — the guardian
    acceptance scan (a guarded run must never write NaN/Inf into a
    checkpoint). Non-float tensors are skipped; a file that does not
    parse returns -1 (structural corruption is _params_ok's job)."""
    import numpy as np

    _FLOATS = {0: np.float32, 1: np.float64, 2: np.float16}
    bad = 0
    try:
        with open(path, "rb") as f:
            for code, payload in _iter_params_records(f):
                if code in _FLOATS:
                    arr = np.frombuffer(payload, dtype=_FLOATS[code])
                    bad += int(arr.size - np.count_nonzero(np.isfinite(arr)))
        return bad
    except (OSError, ValueError):
        return -1


def scan_nonfinite_params(root):
    """(files_scanned, files_with_nonfinite, total_bad_values) over every
    .params under root. A file that fails to parse counts as bad too —
    an unverifiable checkpoint must never read as a clean one."""
    scanned, files_bad, total = 0, 0, 0
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if not fn.endswith(".params"):
                continue
            scanned += 1
            bad = _params_nonfinite(os.path.join(dirpath, fn))
            if bad != 0:
                files_bad += 1
                total += max(bad, 0)
    return scanned, files_bad, total


# -- guardian survival legs ----------------------------------------------------
# The ISSUE-5 acceptance contract: with grad.nan:p=0.02 plus one forced
# loss spike injected mid-Module.fit, a MXNET_GUARDIAN=1 run completes
# within accuracy tolerance of the fault-free baseline, never writes a
# non-finite value into any checkpoint, and its journal proves the
# recovery fired (guardian.nonfinite_steps > 0, guardian.rollbacks >= 1);
# the SAME injection with the guardian off demonstrably corrupts the run
# (negative control). The elastic 4-proc leg proves the coordinated
# skip: every rank finishes, with guardian.skipped_steps mirrored from
# the coordinator's round-protocol guard.

_GUARDIAN_ACC_TOL = 0.15
_GUARDIAN_OK_RE = re.compile(r"guardian fit OK acc=([0-9.]+) finite=([01])")


def _run_guardian_leg(tag, scratch, timeout, extra_env=None):
    """One single-process guardian_fit.py run in its own checkpoint dir.
    Returns (rc, acc|None, finite|None, counters, ckpt_dir, output)."""
    ckpt_dir = os.path.join(scratch, tag + "-ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)
    journal = os.path.join(scratch, tag + "-journal.jsonl")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": journal,
        "GUARDIAN_TEST_PREFIX": os.path.join(ckpt_dir, "guard"),
        "TMPDIR": scratch,
    })
    env.pop("MXNET_FAULT_SPEC", None)
    env.pop("MXNET_GUARDIAN", None)
    env.update(extra_env or {})
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tests", "nightly", "guardian_fit.py")],
            cwd=REPO, env=env, timeout=timeout, capture_output=True,
            text=True)
        out, rc = proc.stdout + proc.stderr, proc.returncode
    except subprocess.TimeoutExpired as exc:
        out = str(exc.stdout or "") + "\n<HUNG: exceeded %.0fs>" % timeout
        rc = -1
    m = _GUARDIAN_OK_RE.search(out)
    acc = float(m.group(1)) if m else None
    finite = bool(int(m.group(2))) if m else None
    return rc, acc, finite, fold_telemetry(journal), ckpt_dir, out


def run_guardian(args):
    """The guardian survival legs: baseline, guarded-under-fire,
    negative control, then the elastic 4-proc coordinated-skip leg."""
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-guardian-")
    per_leg = args.timeout / 5.0
    failures = []
    seed = args.seed
    spec = ("grad.nan:error:p=0.02:seed=%d;"
            "loss.spike:error:count=1:skip=40:seed=%d"
            % (seed + 11, seed + 12))

    print("chaos --guardian: baseline (fault-free)")
    rc0, acc0, fin0, _c0, _d0, out0 = _run_guardian_leg(
        "base", scratch, per_leg)
    if rc0 != 0 or acc0 is None or not fin0:
        failures.append("baseline leg failed (rc=%d acc=%s)\n%s"
                        % (rc0, acc0, out0[-2000:]))
        base_acc = None
    else:
        base_acc = acc0

    print("chaos --guardian: guarded leg (MXNET_GUARDIAN=1, spec=%r)"
          % spec)
    rc1, acc1, fin1, c1, ckpt1, out1 = _run_guardian_leg(
        "guarded", scratch, per_leg, extra_env={
            "MXNET_GUARDIAN": "1",
            "MXNET_FAULT_SPEC": spec,
            "MXNET_GUARDIAN_SNAPSHOT_STEPS": "10",
        })
    if rc1 != 0 or acc1 is None:
        failures.append("guarded leg did not complete (rc=%d)\n%s"
                        % (rc1, out1[-2000:]))
    else:
        if not fin1:
            failures.append("guarded leg finished with non-finite params")
        if base_acc is not None and base_acc - acc1 > _GUARDIAN_ACC_TOL:
            failures.append(
                "guarded accuracy %.3f fell more than %.2f below "
                "fault-free %.3f" % (acc1, _GUARDIAN_ACC_TOL, base_acc))
        if c1.get("guardian.nonfinite_steps", 0) < 1:
            failures.append("guarded leg: no non-finite step recorded "
                            "(counters: %s)" % c1)
        if c1.get("guardian.rollbacks", 0) < 1:
            failures.append("guarded leg: no rollback recorded "
                            "(counters: %s)" % c1)
        scanned, files_bad, bad = scan_nonfinite_params(ckpt1)
        if scanned < 1:
            failures.append("guarded leg wrote no checkpoints to scan")
        elif files_bad:
            failures.append(
                "guarded leg wrote non-finite values into %d checkpoint "
                "file(s) (%d values) — the sentinel leaked poison to disk"
                % (files_bad, bad))

    print("chaos --guardian: negative control (guardian OFF, same spec)")
    rc2, acc2, fin2, _c2, ckpt2, out2 = _run_guardian_leg(
        "control", scratch, per_leg, extra_env={
            "MXNET_GUARDIAN": "0",
            "MXNET_FAULT_SPEC": spec,
        })
    _scanned2, files_bad2, _bad2 = scan_nonfinite_params(ckpt2)
    corrupted = (rc2 != 0 or fin2 is False or files_bad2 > 0
                 or (acc2 is not None and base_acc is not None
                     and base_acc - acc2 > _GUARDIAN_ACC_TOL))
    if not corrupted:
        failures.append(
            "negative control: the same injection did NOT corrupt the "
            "unguarded run (rc=%d acc=%s finite=%s) — the guardian legs "
            "prove nothing" % (rc2, acc2, fin2))

    print("chaos --guardian: elastic legs (4 workers, coordinated skip)")
    port = 29620 + (seed % 97) * 3
    rc3, accs3, _c3, out3 = _run_elastic_leg(
        "gbase", scratch, port, per_leg)
    if rc3 != 0 or len(accs3) != _ELASTIC_N:
        failures.append("elastic baseline failed (rc=%d done=%s)\n%s"
                        % (rc3, sorted(accs3), out3[-2000:]))
        ebase = None
    else:
        ebase = sum(accs3.values()) / len(accs3)
    rc4, accs4, c4, out4 = _run_elastic_leg(
        "gfault", scratch, port + 1, per_leg, extra_env={
            "MXNET_GUARDIAN": "1",
            "MXNET_FAULT_SPEC": "grad.nan:error:p=0.02:seed=%d" % (seed + 13),
        })
    if rc4 != 0 or len(accs4) != _ELASTIC_N:
        failures.append("elastic guardian leg: not every rank finished "
                        "(rc=%d done=%s)\n%s"
                        % (rc4, sorted(accs4), out4[-2000:]))
    else:
        if c4.get("guardian.skipped_rounds", 0) < 1:
            failures.append("elastic guardian leg: no coordinated skip "
                            "recorded (counters: %s)" % c4)
        if ebase is not None:
            worst = min(accs4.values())
            if ebase - worst > _GUARDIAN_ACC_TOL:
                failures.append(
                    "elastic guardian leg: accuracy %.3f fell more than "
                    "%.2f below fault-free %.3f"
                    % (worst, _GUARDIAN_ACC_TOL, ebase))

    print("\n=== guardian survival report ===")
    print("spec             : %s" % spec)
    print("baseline acc     : %s"
          % ("%.4f" % base_acc if base_acc is not None else "FAILED"))
    print("guarded leg      : rc=%d acc=%s finite=%s" % (rc1, acc1, fin1))
    print("guarded counters : nonfinite=%d skipped=%d anomaly=%d "
          "rollbacks=%d"
          % (c1.get("guardian.nonfinite_steps", 0),
             c1.get("guardian.skipped_steps", 0),
             c1.get("guardian.anomaly_steps", 0),
             c1.get("guardian.rollbacks", 0)))
    print("negative control : rc=%d acc=%s finite=%s corrupt=%s"
          % (rc2, acc2, fin2, corrupted))
    print("elastic guardian : rc=%d finished=%s skipped_rounds=%d"
          % (rc4, sorted(accs4), c4.get("guardian.skipped_rounds", 0)))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 5
    print("\nRESULT: SURVIVED — poisoned gradients were suppressed, "
          "skipped and rolled back within %.2f accuracy of fault-free; "
          "no checkpoint ever carried a non-finite value; the unguarded "
          "control demonstrably corrupted." % _GUARDIAN_ACC_TOL)
    return 0


# -- elastic survival legs -----------------------------------------------------
# The ISSUE-4 acceptance contract: with MXNET_KV_ELASTIC=1, SIGKILLing
# 1 of 4 workers mid-Module.fit neither hangs nor crashes the survivors
# (they finish with accuracy comparable to the fault-free run), and a
# restarted worker rejoins and participates — both proven by exit codes
# AND the kvstore.evictions/rejoins/degraded journal counters.

_ELASTIC_N = 4
_ELASTIC_ACC_TOL = 0.15
_OK_RE = re.compile(r"rank (\d+)/%d: elastic fit OK acc=([0-9.]+)"
                    % _ELASTIC_N)


def _load_budget():
    """mxnet_tpu/elastic/budget.py by file path (the trace_merge
    pattern): the harness must not pay the jax import to do timeout
    arithmetic."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxtpu_chaos_budget",
        os.path.join(REPO, "mxnet_tpu", "elastic", "budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_TIMING = None


def _elastic_timing():
    """(env dict, restart_delay): the elastic legs' heartbeat/evict
    budget, with the evict window scaled by PREFLIGHT-MEASURED
    scheduler jitter instead of a hardcoded 3s. On a contended box a
    healthy rank's heartbeats land late by the scheduler's latency;
    sizing the window below misses x period + that slack evicts
    healthy ranks in the fault-free baseline leg — the documented
    spurious-eviction flake, now prevented by construction (the
    budget.evict_after_floor invariant the mxlint --proto lattice also
    checks)."""
    global _TIMING
    if _TIMING is None:
        budget = _load_budget()
        hb = 0.3
        jitter = budget.measure_scheduler_jitter()
        # 6x headroom over the instantaneous measurement: the box can
        # always get busier than the preflight burst saw (the legs
        # themselves add 4 workers + a coordinator of load)
        slack = max(0.5, 6.0 * jitter)
        evict = max(3.0, budget.evict_after_floor(hb, slack=slack,
                                                  misses=3))
        print("chaos: preflight scheduler jitter %.0fms -> jitter "
              "slack %.2fs, evict window %.2fs (%.1fs heartbeat x 3 "
              "tolerated misses + slack)" % (jitter * 1e3, slack,
                                             evict, hb))
        # restart hold: eviction lands at worst evict_after + one sweep
        # interval (the sweeper runs every evict/4) + scheduling slack;
        # a flat +2s margin would re-race the sweep for windows > 8s
        restart_delay = evict + max(2.0, evict / 4.0 + slack)
        _TIMING = ({
            "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "%g" % hb,
            "MXNET_KV_EVICT_AFTER": "%.2f" % evict,
            "MXNET_KV_EVICT_JITTER_SLACK": "%.2f" % slack,
        }, restart_delay)
    return _TIMING


def _run_elastic_leg(tag, scratch, port, timeout, extra_env=None,
                     launch_args=()):
    """One tools/launch.py --elastic run of dist_elastic_fit.py.
    Returns (returncode, {rank: acc}, folded journal counters, output)."""
    timing_env, _restart_delay = _elastic_timing()
    env = dict(os.environ)
    env.update(timing_env)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY": "1",
        # per-rank journals: launch.py expands {rank}
        "MXNET_TELEMETRY_JOURNAL": os.path.join(
            scratch, tag + "-journal-{rank}.jsonl"),
        # tight flush cadence: a SIGKILLed rank must leave mid-run spans
        # on disk for the trace_merge attribution leg (buffered records
        # die with the process)
        "MXNET_TELEMETRY_FLUSH_SECS": "2",
    })
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(_ELASTIC_N), "--launcher", "local", "--elastic",
           "--coordinator", "127.0.0.1:%d" % port] + list(launch_args) + \
        ["--", sys.executable,
         os.path.join(REPO, "tests", "nightly", "dist_elastic_fit.py")]
    # own session + killpg on timeout: killing only launch.py would
    # orphan the coordinator (holding the leg's port forever) and four
    # workers busy-polling the box the remaining legs need
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        import signal

        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        out, _ = proc.communicate()
        out = (out or "") + "\n<HUNG: exceeded %.0fs>" % timeout
        rc = -1
    accs = {int(r): float(a) for r, a in _OK_RE.findall(out)}
    # each worker mirrors the coordinator's monotonic totals; the
    # best-informed journal (max) is the cluster view
    counters = {}
    for rank in range(_ELASTIC_N):
        folded = fold_telemetry(os.path.join(
            scratch, "%s-journal-%d.jsonl" % (tag, rank)))
        for k, v in folded.items():
            counters[k] = max(counters.get(k, 0), v)
    return rc, accs, counters, out


def _elastic_snapshot_leg(scratch):
    """Live-coordinator snapshot RPC: an in-process coordinator started
    with a snapshot prefix is asked to dump NOW through
    ``ElasticClient.snapshot()`` — the feed a wsync CheckpointWatcher
    publishes from (docs/how_to/weight_sync.md) — and the ``.params``
    file that lands must pass the same structural scan the torn-file
    check uses. Returns a failure string, or None."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from mxnet_tpu.elastic.client import ElasticClient
    from mxnet_tpu.elastic.server import ElasticCoordinator

    prefix = os.path.join(scratch, "coord-snap")
    coord = ElasticCoordinator(world=1, bind=("127.0.0.1", 0),
                               snapshot_prefix=prefix)
    coord.start()
    try:
        client = ElasticClient("%s:%d" % coord.addr, rank=0)
        client.wait_ready(20.0)
        client.register()
        resp = client.snapshot()
        if resp.get("status") != "ok":
            return "snapshot leg: coordinator answered %r" % (resp,)
        # assert the files BEFORE stop(): the final-snapshot-on-stop
        # path must not be what makes this leg pass
        missing = [p for p in (prefix + ".params", prefix + ".meta")
                   if not os.path.exists(p)]
        if missing:
            return ("snapshot leg: snapshot RPC answered ok but wrote "
                    "no %s" % ", ".join(missing))
        if not _params_ok(prefix + ".params"):
            return ("snapshot leg: snapshot .params failed the "
                    "structural (torn-file) scan")
        client.leave()
    except Exception as e:  # noqa: BLE001 - any RPC failure fails the leg
        return "snapshot leg: %s: %s" % (type(e).__name__, e)
    finally:
        coord.stop()
    return None


def _run_trace_merge(scratch, tag):
    """tools/trace_merge.py over one leg's per-rank journals. Returns
    (output, parsed report dict or None). The Perfetto trace lands next
    to the journals (ISSUE 10 acceptance: clock-aligned merged timeline
    + trace-event JSON from a real chaos run)."""
    journals = [os.path.join(scratch, "%s-journal-%d.jsonl" % (tag, r))
                for r in range(_ELASTIC_N)]
    chrome = os.path.join(scratch, "%s-merged-trace.json" % tag)
    cmd = [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
           *journals, "--chrome", chrome, "--json"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, text=True,
                              capture_output=True, timeout=120)
    except subprocess.TimeoutExpired:
        # a wedged merge is a leg FAILURE, not a harness crash — the
        # survival report (and the other legs' verdicts) must still land
        return "<trace_merge HUNG: exceeded 120s>", None
    if proc.returncode != 0:
        return proc.stdout + proc.stderr, None
    try:
        return proc.stdout + proc.stderr, json.loads(proc.stdout)
    except ValueError:
        return proc.stdout + proc.stderr, None


def run_elastic(args):
    """The two elastic survival legs (plus a fault-free baseline)."""
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-elastic-")
    port = 29520 + (args.seed % 97) * 3
    per_leg = args.timeout / 3.0
    failures = []

    print("chaos --elastic: baseline (fault-free, %d workers)" % _ELASTIC_N)
    rc0, accs0, _c0, out0 = _run_elastic_leg(
        "base", scratch, port, per_leg)
    if rc0 != 0 or len(accs0) != _ELASTIC_N:
        failures.append("baseline leg failed (rc=%d, ranks done=%s)\n%s"
                        % (rc0, sorted(accs0), out0[-2000:]))
        base_acc = None
    else:
        base_acc = sum(accs0.values()) / len(accs0)

    print("chaos --elastic: evict leg (SIGKILL rank 3 mid-fit, "
          "no restart)")
    rc1, accs1, c1, out1 = _run_elastic_leg(
        "evict", scratch, port + 1, per_leg,
        extra_env={"MXNET_ELASTIC_TEST_DIE_RANK": "3",
                   "MXNET_ELASTIC_TEST_DIE_AT": "15"},
        launch_args=["--tolerate", "1"])
    survivors = {r: a for r, a in accs1.items() if r != 3}
    if rc1 != 0 or len(survivors) != _ELASTIC_N - 1:
        failures.append("evict leg: survivors did not all finish "
                        "(rc=%d, done=%s)\n%s"
                        % (rc1, sorted(accs1), out1[-2000:]))
    if c1.get("kvstore.evictions_total", 0) < 1:
        failures.append("evict leg: no eviction recorded in the journal "
                        "(counters: %s)" % c1)
    if survivors and base_acc is not None:
        worst = min(survivors.values())
        if base_acc - worst > _ELASTIC_ACC_TOL:
            failures.append(
                "evict leg: survivor accuracy %.3f fell more than %.2f "
                "below fault-free %.3f" % (worst, _ELASTIC_ACC_TOL,
                                           base_acc))

    print("chaos --elastic: rejoin leg (SIGKILL rank 3, restart held past "
          "the evict window, rejoin)")
    mark = tempfile.mkdtemp(prefix="mark-", dir=scratch)
    # --restart-delay > the (jitter-scaled) MXNET_KV_EVICT_AFTER plus
    # sweep cadence: the dead incarnation is always EVICTED before the
    # respawn re-registers, so rejoins_total >= 1 is deterministic.
    # Without the hold, warm jit caches respawn the worker inside the
    # evict window and its register is a plain (uncounted)
    # re-admission — the pre-existing rejoin-leg flake (PR 9 NB).
    _timing_env, restart_delay = _elastic_timing()
    rc2, accs2, c2, out2 = _run_elastic_leg(
        "rejoin", scratch, port + 2, per_leg,
        extra_env={"MXNET_ELASTIC_TEST_DIE_RANK": "3",
                   "MXNET_ELASTIC_TEST_DIE_AT": "15",
                   "MXNET_ELASTIC_TEST_MARK": mark},
        launch_args=["--max-restarts", "1",
                     "--restart-delay", "%.1f" % restart_delay])
    if rc2 != 0 or len(accs2) != _ELASTIC_N:
        failures.append("rejoin leg: not every rank (incl. the restarted "
                        "one) finished (rc=%d, done=%s)\n%s"
                        % (rc2, sorted(accs2), out2[-2000:]))
    if c2.get("kvstore.rejoins_total", 0) < 1:
        failures.append("rejoin leg: no rejoin recorded in the journal "
                        "(counters: %s)" % c2)

    print("chaos --elastic: trace-merge leg (merged timeline over the "
          "evict leg's %d journals)" % _ELASTIC_N)
    merge_out, merge_rep = _run_trace_merge(scratch, "evict")
    if merge_rep is None:
        failures.append("trace-merge leg: tools/trace_merge.py failed\n%s"
                        % merge_out[-2000:])
    else:
        if merge_rep.get("report", {}).get("straggler") != 3:
            failures.append(
                "trace-merge leg: attribution did not identify killed "
                "rank 3 (report: %s)" % merge_rep.get("report"))
        chrome = os.path.join(scratch, "evict-merged-trace.json")
        try:
            with open(chrome) as f:
                n_events = len(json.load(f)["traceEvents"])
        except (OSError, ValueError, KeyError) as e:
            n_events = 0
            failures.append("trace-merge leg: Perfetto trace unreadable "
                            "(%s)" % e)

    print("chaos --elastic: snapshot leg (live ElasticClient.snapshot "
          "RPC against a prefix-armed coordinator)")
    snap_fail = _elastic_snapshot_leg(scratch)
    if snap_fail:
        failures.append(snap_fail)

    print("\n=== elastic survival report ===")
    timing_env, _rd = _elastic_timing()
    print("evict window    : %ss (jitter slack %ss)"
          % (timing_env["MXNET_KV_EVICT_AFTER"],
             timing_env["MXNET_KV_EVICT_JITTER_SLACK"]))
    print("baseline acc    : %s"
          % ("%.4f" % base_acc if base_acc is not None else "FAILED"))
    print("evict leg       : rc=%d survivors=%s accs=%s"
          % (rc1, sorted(survivors), {r: round(a, 3)
                                      for r, a in survivors.items()}))
    print("rejoin leg      : rc=%d finished=%s" % (rc2, sorted(accs2)))
    print("snapshot leg    : %s" % ("FAILED" if snap_fail
                                    else "ok (snapshot RPC wrote a "
                                         "structurally valid .params)"))
    if merge_rep is not None:
        rep = merge_rep.get("report", {})
        print("trace merge     : straggler=rank %s truncated=%s "
              "incomplete=%s perfetto_events=%d"
              % (rep.get("straggler"), rep.get("truncated"),
                 rep.get("incomplete"), n_events))
    for name, counters in (("evict", c1), ("rejoin", c2)):
        print("%-6s counters : evictions=%d rejoins=%d degraded_steps=%d"
              % (name,
                 counters.get("kvstore.evictions_total", 0),
                 counters.get("kvstore.rejoins_total", 0),
                 counters.get("kvstore.degraded_steps_total", 0)))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 4
    print("\nRESULT: SURVIVED — eviction left the reduced group training "
          "to completion, and the restarted worker rejoined; accuracy "
          "within %.2f of fault-free." % _ELASTIC_ACC_TOL)
    return 0


# -- quantized comms + sharded weight update survival legs ---------------------
# The ISSUE-7 acceptance contract: with MXNET_KV_QUANTIZE=int8 (+
# MXNET_KV_SHARD_UPDATE=1), the elastic SIGKILL-1-of-4 leg still reaches
# baseline-tolerance accuracy; wire bytes measurably shrink; per-rank
# optimizer-state bytes scale ~1/world; and the guardian counts POISONED
# rounds (grad.nan) while counting NOTHING on a clean quantized run —
# quantization noise and poisoning stay distinguishable.

def _rank_gauges(scratch, tag):
    return [fold_gauges(os.path.join(
        scratch, "%s-journal-%d.jsonl" % (tag, r)))
        for r in range(_ELASTIC_N)]


def run_quantized(args):
    sys.path.insert(0, REPO)
    from mxnet_tpu import quantize

    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-quant-")
    port = 29720 + (args.seed % 97) * 4
    per_leg = args.timeout / 4.0
    failures = []
    qenv = {"MXNET_KV_QUANTIZE": "int8", "MXNET_KV_SHARD_UPDATE": "1"}

    print("chaos --quantized: baseline (fp32 wire, server update, "
          "fault-free, %d workers)" % _ELASTIC_N)
    rc0, accs0, _c0, out0 = _run_elastic_leg("qbase", scratch, port, per_leg)
    if rc0 != 0 or len(accs0) != _ELASTIC_N:
        failures.append("fp32 baseline failed (rc=%d done=%s)\n%s"
                        % (rc0, sorted(accs0), out0[-2000:]))
        base_acc = None
    else:
        base_acc = sum(accs0.values()) / len(accs0)

    print("chaos --quantized: int8+shard leg (fault-free, guardian armed "
          "— must count NOTHING)")
    rc1, accs1, c1, out1 = _run_elastic_leg(
        "qshard", scratch, port + 1, per_leg,
        extra_env=dict(qenv, MXNET_GUARDIAN="1"))
    ratio = None
    if rc1 != 0 or len(accs1) != _ELASTIC_N:
        failures.append("int8+shard leg: not every rank finished "
                        "(rc=%d done=%s)\n%s"
                        % (rc1, sorted(accs1), out1[-2000:]))
    else:
        if base_acc is not None and \
                base_acc - min(accs1.values()) > _ELASTIC_ACC_TOL:
            failures.append(
                "int8+shard accuracy %.3f fell more than %.2f below fp32 "
                "%.3f" % (min(accs1.values()), _ELASTIC_ACC_TOL, base_acc))
        # quantization noise must NOT read as poisoning: zero guard skips
        if c1.get("guardian.skipped_rounds", 0) or \
                c1.get("guardian.nonfinite_rounds", 0):
            failures.append(
                "clean quantized run tripped the guardian (%s) — the "
                "quant-error floor is miscalibrated" % c1)
        wire = c1.get("kvstore.wire_bytes_total", 0)
        logical = c1.get("kvstore.logical_bytes_total", 0)
        if not logical or wire >= 0.30 * logical:
            failures.append(
                "int8 wire bytes %d not <= 0.30x logical %d"
                % (wire, logical))
        else:
            ratio = wire / float(logical)
        gauges = _rank_gauges(scratch, "qshard")
        states = [g.get("kvstore.optimizer_state_bytes", 0) for g in gauges]
        qerr = max(g.get("kvstore.quant_error", 0.0) for g in gauges)
        if min(states) <= 0:
            failures.append("a rank materialized no optimizer state "
                            "(gauges: %s) — sharding never engaged"
                            % states)
        # the memory invariant behind "~1/world": ZERO replication —
        # every key's optimizer state lives on exactly one rank, so
        # the per-rank bound is max(balanced share, largest layer)
        # instead of a full replica each. (The exact 1/world fraction
        # is asserted over uniform keys in tests/unittest/
        # test_quantize.py; this MLP's fc1 dominates its byte total,
        # so its best-possible split is layer-bound.)
        elif max(states) >= sum(states):
            failures.append(
                "one rank holds the ENTIRE optimizer state %s — "
                "key partitioning never happened" % states)
        if qerr > quantize.rel_error_bound("int8") + 1e-7:
            failures.append("kvstore.quant_error %.5f exceeds the codec "
                            "bound %.5f"
                            % (qerr, quantize.rel_error_bound("int8")))

    print("chaos --quantized: int8+shard SIGKILL leg (rank 3 dies "
          "mid-fit, survivors finish)")
    rc2, accs2, c2, out2 = _run_elastic_leg(
        "qevict", scratch, port + 2, per_leg,
        extra_env=dict(qenv, MXNET_ELASTIC_TEST_DIE_RANK="3",
                       MXNET_ELASTIC_TEST_DIE_AT="15"),
        launch_args=["--tolerate", "1"])
    survivors = {r: a for r, a in accs2.items() if r != 3}
    if rc2 != 0 or len(survivors) != _ELASTIC_N - 1:
        failures.append("int8+shard evict leg: survivors did not all "
                        "finish (rc=%d done=%s)\n%s"
                        % (rc2, sorted(accs2), out2[-2000:]))
    else:
        if c2.get("kvstore.evictions_total", 0) < 1:
            failures.append("evict leg: no eviction recorded (counters: "
                            "%s)" % c2)
        if base_acc is not None and \
                base_acc - min(survivors.values()) > _ELASTIC_ACC_TOL:
            failures.append(
                "int8+shard survivor accuracy %.3f fell more than %.2f "
                "below fp32 baseline %.3f"
                % (min(survivors.values()), _ELASTIC_ACC_TOL, base_acc))

    print("chaos --quantized: grad.nan leg (guardian must count the "
          "poisoned rounds on the quantized path)")
    rc3, accs3, c3, out3 = _run_elastic_leg(
        "qnan", scratch, port + 3, per_leg,
        extra_env={"MXNET_KV_QUANTIZE": "int8", "MXNET_GUARDIAN": "1",
                   "MXNET_FAULT_SPEC":
                       "grad.nan:error:p=0.02:seed=%d" % (args.seed + 17)})
    if rc3 != 0 or len(accs3) != _ELASTIC_N:
        failures.append("grad.nan leg: not every rank finished "
                        "(rc=%d done=%s)\n%s"
                        % (rc3, sorted(accs3), out3[-2000:]))
    else:
        if c3.get("guardian.skipped_rounds", 0) < 1:
            failures.append(
                "grad.nan leg: guardian counted no skipped rounds — the "
                "poison was invisible through the codec (counters: %s)"
                % c3)
        if base_acc is not None and \
                base_acc - min(accs3.values()) > _ELASTIC_ACC_TOL:
            failures.append(
                "grad.nan guarded accuracy %.3f fell more than %.2f "
                "below fp32 baseline %.3f"
                % (min(accs3.values()), _ELASTIC_ACC_TOL, base_acc))

    print("\n=== quantized comms survival report ===")
    print("fp32 baseline acc : %s"
          % ("%.4f" % base_acc if base_acc is not None else "FAILED"))
    print("int8+shard clean  : rc=%d accs=%s wire/logical=%s"
          % (rc1, {r: round(a, 3) for r, a in sorted(accs1.items())},
             "%.3f" % ratio if ratio is not None else "n/a"))
    print("int8+shard evict  : rc=%d survivors=%s evictions=%d"
          % (rc2, sorted(survivors),
             c2.get("kvstore.evictions_total", 0)))
    print("grad.nan guarded  : rc=%d finished=%s skipped_rounds=%d "
          "nonfinite_rounds=%d"
          % (rc3, sorted(accs3), c3.get("guardian.skipped_rounds", 0),
             c3.get("guardian.nonfinite_rounds", 0)))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 6
    print("\nRESULT: SURVIVED — int8 wire + sharded update trained to "
          "baseline-tolerance accuracy through a SIGKILL, wire bytes "
          "<= 0.30x logical, optimizer state ~1/world per rank, and the "
          "guardian counted injected poison but nothing on the clean "
          "quantized run.")
    return 0


# -- thread-schedule survival legs ---------------------------------------------
# The ISSUE-9 acceptance contract: the mxrace interleaving explorer
# (mxnet_tpu/analysis/schedule.py) deterministically finds BOTH seeded
# races (the lost-update counter and the unlocked elastic-aggregator
# protocol, the latter at line granularity inside elastic/server.py) and
# replays each from its printed seed; the serving engine's
# submit/cancel/step loop and the aggregator under the coordinator's
# lock then survive every explored schedule with zero deadlocks and
# zero invariant violations. Chaos testing for thread schedules: same
# survival-report shape as the fault legs, but the adversary is the
# scheduler, not the network.

def run_schedules(args):
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import time as _time

    from mxnet_tpu.analysis.schedule import survival_suite

    budget = int(os.environ.get("MXRACE_SCHEDULES", "0") or 0) or 50
    print("chaos --schedules: seed=%d, %d schedules per leg"
          % (args.seed, budget))
    t0 = _time.time()
    findings, lines = survival_suite(seed=args.seed, schedules=budget)
    wall = _time.time() - t0

    print("\n=== schedule survival report ===")
    print("seed            : %d" % args.seed)
    print("wall time       : %.1fs" % wall)
    for ln in lines:
        print(ln)
    if findings:
        print("\nRESULT: FAIL")
        for f in findings:
            print(" - %s" % f)
        return 7
    print("\nRESULT: SURVIVED — both seeded races were found and "
          "replayed from their seeds; the serving submit/cancel/step "
          "loop and the elastic aggregator round protocol survived "
          "every explored schedule (no deadlock, no invariant "
          "violation). Rerun with the same --seed to reproduce.")
    return 0


# -- protocol message-schedule survival legs -----------------------------------
# The ISSUE-11 acceptance contract: the mxproto simulator
# (mxnet_tpu/analysis/protosim.py) runs the REAL coordinator dispatch
# state machine under explorable delivery orders, reply losses,
# duplicate deliveries, crashes, evictions and restarts; both seeded
# protocol mutants (epoch-regress-on-rejoin, unguarded round
# completion) must be found and replayed from their (seed, index)
# pair, then the all-reduce, barrier and shard-update workloads must
# survive every explored schedule. Runs with telemetry on so the
# survival report folds the simulator's message/perturbation counters
# from the journal.

def run_proto(args):
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-proto-")
    journal = os.path.join(scratch, "proto-journal.jsonl")
    # env set BEFORE the mxnet_tpu import: telemetry reads it at load
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_TELEMETRY_JOURNAL"] = journal
    import time as _time

    from mxnet_tpu import telemetry
    from mxnet_tpu.analysis.datasim import data_survival_suite
    from mxnet_tpu.analysis.protosim import survival_suite

    budget = int(os.environ.get("MXPROTO_SCHEDULES", "0") or 0) or 50
    print("chaos --proto: seed=%d, %d message schedules per leg"
          % (args.seed, budget))
    t0 = _time.time()
    findings, lines = survival_suite(seed=args.seed, schedules=budget)
    dfs, dlines = data_survival_suite(seed=args.seed, schedules=budget)
    findings.extend(dfs)
    lines.extend(dlines)
    wall = _time.time() - t0
    telemetry.flush(mark="exit")
    counters = fold_telemetry(journal)

    print("\n=== protocol survival report ===")
    print("seed            : %d" % args.seed)
    print("wall time       : %.1fs" % wall)
    for ln in lines:
        print(ln)
    print("-- simulator counters (mxtel journal) --")
    if counters:
        print("schedules       : %d explored, %d messages delivered"
              % (counters.get("mxproto.schedules_total", 0),
                 counters.get("mxproto.messages_total", 0)))
        print("perturbations   : %d replies lost, %d duplicated, "
              "%d crashes, %d restarts, %d evictions, %d snapshot "
              "round-trips"
              % (counters.get("mxproto.replies_lost_total", 0),
                 counters.get("mxproto.dup_deliveries_total", 0),
                 counters.get("mxproto.crashes_total", 0),
                 counters.get("mxproto.restarts_total", 0),
                 counters.get("mxproto.evictions_total", 0),
                 counters.get("mxproto.snapshot_checks_total", 0)))
        print("mutants found   : %d"
              % counters.get("mxproto.mutants_found_total", 0))
    else:
        print("(no journal counters — telemetry produced no snapshots)")
    if findings:
        print("\nRESULT: FAIL")
        for f in findings:
            print(" - %s" % f)
        return 8
    print("\nRESULT: SURVIVED — all four seeded protocol mutants "
          "(elastic epoch-regress + unguarded completion, data-service "
          "double-delivery + frontier-regress) were found and replayed "
          "from their (seed, index) pairs; the all-reduce, barrier, "
          "shard-update and data-stream workloads survived every "
          "explored message schedule (delivery reorder, reply loss, "
          "duplication, crash, eviction, restart, snapshot "
          "round-trip). Rerun with the same --seed to reproduce.")
    return 0


# -- mxjit compile/transfer survival legs --------------------------------------
# The ISSUE-16 contract: the runtime verifier must CATCH a seeded
# recompile storm (naming the argument that varied) and a seeded
# over-budget hot-region D2H pull — and a real serving decode loop under
# the same verifier must produce ZERO findings (positive control). The
# report folds the jit.* counters from the mxtel journal.

def run_jit(args):
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-jit-")
    journal = os.path.join(scratch, "jit-journal.jsonl")
    # env set BEFORE the mxnet_tpu import: telemetry + verifier read it
    # at load
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_TELEMETRY_JOURNAL"] = journal
    os.environ["MXNET_JIT_VERIFY"] = "record"
    import time as _time

    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.analysis import compile_verify
    from mxnet_tpu.analysis.jit_lint import lint_targets

    compile_verify.reload()
    failures = []
    t0 = _time.time()

    # leg 1: seeded recompile storm (negative control). A budget-1
    # boundary fed five distinct shapes must be caught four times, each
    # violation's arg-signature diff naming the shape that varied.
    import jax
    import jax.numpy as jnp

    storm = compile_verify.wrap(
        "chaos.jit_storm", jax.jit(lambda x: x * 2.0),
        budget=1, group="chaos.jit_storm")
    with compile_verify.expecting_violations() as caught:
        for n in range(2, 7):
            storm(jnp.zeros((n,), jnp.float32))
    named = [v for v in caught
             if any("shape" in d for d in v.get("diff", []))]
    print("storm leg       : %d over-budget compiles caught, %d diffs "
          "name the varying shape" % (len(caught), len(named)))
    if len(caught) != 4 or len(named) != len(caught):
        failures.append("recompile storm: expected 4 caught violations "
                        "all naming the shape, got %d/%d"
                        % (len(caught), len(named)))

    # leg 2: seeded hot-region D2H overflow (negative control). A
    # region budgeted for one token vector fed a fat pull must close
    # over budget, attributing the bytes to the seeded site.
    with compile_verify.expecting_violations() as d2h_caught:
        with compile_verify.d2h_region("chaos.hot", budget_bytes=8):
            compile_verify.note_d2h(4096, "tools/chaos.py::seeded_pull")
    print("d2h leg         : %d over-budget regions caught"
          % len(d2h_caught))
    if len(d2h_caught) != 1 or \
            d2h_caught[0].get("bytes") != 4096 or \
            "tools/chaos.py::seeded_pull" not in d2h_caught[0].get(
                "sites", {}):
        failures.append("d2h overflow: expected 1 caught violation of "
                        "4096 bytes at the seeded site, got %r"
                        % (d2h_caught,))

    # leg 3 (positive control): a real serving decode loop under the
    # token-vector-only ledger — bucketed shapes, budgeted boundaries,
    # one 4*B-byte pull per step — must produce ZERO ambient findings.
    from mxnet_tpu.models.transformer import TransformerConfig, init_params
    from mxnet_tpu.serving import PagedKVPool
    from mxnet_tpu.serving.model import ServingModel

    cfg = TransformerConfig(vocab_size=31, num_layers=1, d_model=16,
                            num_heads=2, d_ff=32, max_seq_len=64,
                            dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    pool = PagedKVPool(cfg.num_layers, cfg.num_heads,
                       cfg.d_model // cfg.num_heads, num_blocks=9,
                       block_size=4)
    m = ServingModel(cfg, block_size=4, max_blocks_per_req=4,
                     batch_buckets=(2,), chunk_buckets=(8,))
    bt = np.zeros((1, 4), np.int32)
    bt[0] = [1, 2, 3, 4]
    kp, vp = pool.k, pool.v
    steps = 6
    for i in range(steps):
        with compile_verify.d2h_region("serve.decode_step",
                                       budget_bytes=4 * 2):
            nxt, kp, vp = m.step(
                params, kp, vp, np.asarray([[1, 2, 3]], np.int32),
                np.zeros((1,), np.int32),
                np.asarray([3 + i], np.int32), bt,
                np.ones((1,), bool))
    amb_rc = compile_verify.unexpected()
    amb_d2h = compile_verify.d2h_violations()
    print("decode leg      : %d steps, %d unexpected recompiles, %d "
          "D2H violations" % (steps, len(amb_rc), len(amb_d2h)))
    if amb_rc or amb_d2h:
        failures.append("clean decode loop tripped the verifier: %r %r"
                        % (amb_rc, amb_d2h))

    # leg 4: static clean-repo gate — mxlint --jit over the live tree
    bad = [f for f in lint_targets()
           if f.severity in ("error", "warning")]
    print("static leg      : mxlint --jit -> %d error/warning finding(s)"
          % len(bad))
    if bad:
        failures.append("mxlint --jit clean-repo gate: %s"
                        % "; ".join(str(f) for f in bad))

    wall = _time.time() - t0
    telemetry.flush(mark="exit")
    counters = fold_telemetry(journal)

    print("\n=== mxjit survival report ===")
    print("seed            : %d" % args.seed)
    print("wall time       : %.1fs" % wall)
    print("-- jit.* counters (mxtel journal) --")
    jit_counters = {k: v for k, v in sorted(counters.items())
                    if k.startswith("jit.") or
                    k == "compile.recompiles_total"}
    for name, v in jit_counters.items():
        print("%-32s: %d" % (name, v))
    if not jit_counters.get("jit.verify_compiles_total"):
        failures.append("journal carries no jit.verify_compiles_total — "
                        "the verifier observed nothing")
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 8
    print("\nRESULT: SURVIVED — the verifier caught the seeded "
          "recompile storm (naming the varying shape) and the seeded "
          "over-budget D2H pull; a real bucketed serving decode loop "
          "ran clean under the same budgets; and the static jit pass "
          "reports a clean repo. Rerun with the same --seed to "
          "reproduce.")
    return 0


# -- data-service survival legs ------------------------------------------------
# The ISSUE-14 acceptance contract: with the sharded streaming input
# service hosting the dataset (tools/launch.py --data-service,
# docs/how_to/data_service.md), SIGKILLing 1 of 4 consumers mid-pass
# must leave the coordinator's ACKED record stream byte-identical to an
# uninterrupted baseline (per-shard contiguous, duplicate-free, with
# mxdata.shards_rebalanced >= 1 proving the shards actually moved), and
# a coordinator SIGTERM + restart must restore shard assignments from
# the frontier snapshot and finish the run with ZERO duplicate
# acknowledged records.

_DATA_N = 4
_DATA_RECORDS = 512
_DATA_BATCH = 8
_DATA_DIM = 8
_DATA_OK_RE = re.compile(
    r"rank (\d+)/(\d+): data service OK batches=(\d+) records=(\d+)")


def _make_data_pack(scratch, n_records=_DATA_RECORDS, dim=_DATA_DIM):
    """Deterministic packed .rec whose payload slot 0 is the global
    record id — the byte-level identity the exactness assertions ride."""
    sys.path.insert(0, REPO)
    import numpy as np

    from mxnet_tpu import recordio

    rec_path = os.path.join(scratch, "data.rec")
    writer = recordio.MXRecordIO(rec_path, "w")
    for i in range(n_records):
        payload = np.full(dim, float(i), np.float32)
        writer.write(recordio.pack(
            recordio.IRHeader(0, float(i % 10), i, 0), payload.tobytes()))
    writer.close()
    return rec_path


def _fold_mxdata_acks(journal_paths):
    """{(pass, shard): [(lo, hi), ...]} in journal order from the data
    coordinator's mxdata ack records — THE authoritative acked record
    stream (a worker killed between consuming and acking legitimately
    re-consumes its tail; the acked stream never duplicates)."""
    acks = {}
    for path in journal_paths:
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "mxdata" and \
                            rec.get("event") == "ack":
                        key = (int(rec.get("pass", 0)),
                               int(rec["shard"]))
                        acks.setdefault(key, []).append(
                            (int(rec["lo"]), int(rec["hi"])))
        except OSError:
            pass
    return acks


def _check_ack_stream(acks, n_records, label, failures, passes=(0,)):
    """Every asserted pass must be contiguous, duplicate-free, and
    cover all records across shards."""
    for p in passes:
        covered = []
        for (dpass, _sid), ranges in sorted(acks.items()):
            if dpass != p:
                continue
            last = None
            for lo, hi in ranges:
                if last is not None and lo < last:
                    failures.append(
                        "%s: pass %d shard %d acked [%d,%d) after "
                        "frontier %d — DUPLICATE records"
                        % (label, p, _sid, lo, hi, last))
                last = hi
                covered.extend(range(lo, hi))
        if sorted(covered) != list(range(n_records)):
            missing = sorted(set(range(n_records)) - set(covered))
            dups = sorted({i for i in covered
                           if covered.count(i) > 1}) if \
                len(covered) != len(set(covered)) else []
            failures.append(
                "%s: pass %d acked stream is not the exact record "
                "sequence (missing %s..., dup %s...)"
                % (label, p, missing[:10], dups[:10]))


def _run_data_leg(tag, scratch, rec_path, port, timeout, n=_DATA_N,
                  extra_env=None, launch_args=()):
    """One tools/launch.py --data-service run of data_service_consume.py.
    Returns (rc, {rank: records}, coordinator journal counters, acks,
    output)."""
    out_dir = os.path.join(scratch, tag + "-out")
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": os.path.join(
            scratch, tag + "-journal-{rank}.jsonl"),
        "MXNET_TELEMETRY_FLUSH_SECS": "1",
        "MXNET_DATA_TEST_OUT": out_dir,
        "MXNET_DATA_TEST_DIM": str(_DATA_DIM),
    })
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(n), "--launcher", "local", "--data-service",
           "--data-bind", "127.0.0.1:%d" % port,
           "--data-files", rec_path, "--data-batch", str(_DATA_BATCH)] + \
        list(launch_args) + \
        ["--", sys.executable,
         os.path.join(REPO, "tests", "nightly", "data_service_consume.py")]
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        import signal

        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        out, _ = proc.communicate()
        out = (out or "") + "\n<HUNG: exceeded %.0fs>" % timeout
        rc = -1
    done = {int(r): int(recs) for r, _w, _b, recs in
            _DATA_OK_RE.findall(out)}
    coord_journal = os.path.join(scratch,
                                 tag + "-journal-datacoord.jsonl")
    counters = fold_telemetry(coord_journal)
    acks = _fold_mxdata_acks([coord_journal])
    return rc, done, counters, acks, out


def run_data(args):
    """The data-service survival legs (ISSUE 14)."""
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-data-")
    rec_path = _make_data_pack(scratch)
    port = 29720 + (args.seed % 97) * 3
    per_leg = args.timeout / 3.0
    failures = []
    timing_env, restart_delay = _elastic_timing()
    # the data plane reads its own evict knob; reuse the jitter-scaled
    # elastic window so a contended box cannot evict healthy consumers
    data_env = {"MXNET_DATA_EVICT_AFTER":
                timing_env["MXNET_KV_EVICT_AFTER"]}

    print("chaos --data: baseline (fault-free, %d consumers, %d records)"
          % (_DATA_N, _DATA_RECORDS))
    rc0, done0, _c0, acks0, out0 = _run_data_leg(
        "base", scratch, rec_path, port, per_leg, extra_env=data_env)
    if rc0 != 0 or len(done0) != _DATA_N:
        failures.append("baseline leg failed (rc=%d, ranks done=%s)\n%s"
                        % (rc0, sorted(done0), out0[-2000:]))
    _check_ack_stream(acks0, _DATA_RECORDS, "baseline", failures)

    print("chaos --data: kill leg (SIGKILL rank 3 mid-pass, restart "
          "held past the evict window, exact resume)")
    mark = tempfile.mkdtemp(prefix="mark-", dir=scratch)
    rc1, done1, c1, acks1, out1 = _run_data_leg(
        "kill", scratch, rec_path, port + 1, per_leg,
        extra_env=dict(data_env, **{
            "MXNET_DATA_TEST_DIE_RANK": "3",
            "MXNET_DATA_TEST_DIE_AT": "4",
            "MXNET_DATA_TEST_MARK": mark,
        }),
        launch_args=["--max-restarts", "1",
                     "--restart-delay", "%.1f" % restart_delay])
    if rc1 != 0 or len(done1) != _DATA_N:
        failures.append("kill leg: not every rank (incl. the restarted "
                        "one) finished (rc=%d, done=%s)\n%s"
                        % (rc1, sorted(done1), out1[-2000:]))
    _check_ack_stream(acks1, _DATA_RECORDS, "kill", failures)
    if c1.get("mxdata.shards_rebalanced_total", 0) < 1:
        failures.append("kill leg: no shard rebalance recorded in the "
                        "coordinator journal (counters: %s)" % c1)
    # the whole point: the interrupted run's acked pass-0 stream is
    # IDENTICAL to the uninterrupted baseline's — same shards, same
    # ranges, same order
    base_p0 = {k: v for k, v in acks0.items() if k[0] == 0}
    kill_p0 = {k: v for k, v in acks1.items() if k[0] == 0}
    if base_p0 and kill_p0 and base_p0 != kill_p0:
        diff = [k for k in set(base_p0) | set(kill_p0)
                if base_p0.get(k) != kill_p0.get(k)]
        failures.append(
            "kill leg: acked record sequence DIFFERS from the "
            "uninterrupted baseline on %d shard(s): %s"
            % (len(diff), diff[:4]))

    print("chaos --data: coordinator-restart leg (SIGTERM the "
          "coordinator mid-stream, restore from the frontier snapshot)")
    rc2 = _run_coord_restart_leg(scratch, rec_path, port + 2, per_leg,
                                 failures)

    print("\n=== data-service survival report ===")
    print("records         : %d (batch %d, %d consumers)"
          % (_DATA_RECORDS, _DATA_BATCH, _DATA_N))
    print("baseline leg    : rc=%d consumed=%s" % (rc0, done0))
    print("kill leg        : rc=%d consumed=%s" % (rc1, done1))
    print("kill counters   : streamed=%d rebalanced=%d checkpoints=%d "
          "stalls=%d"
          % (c1.get("mxdata.batches_streamed_total", 0),
             c1.get("mxdata.shards_rebalanced_total", 0),
             c1.get("mxdata.frontier_checkpoints_total", 0),
             c1.get("mxdata.flow_control_stalls_total", 0)))
    print("restart leg     : %s" % ("OK" if rc2 == 0 else "FAILED"))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 9
    print("\nRESULT: SURVIVED — the SIGKILLed consumer's shards "
          "rebalanced and the rejoined rank resumed at the exact "
          "frontier (acked record stream identical to the "
          "uninterrupted baseline), and the restarted coordinator "
          "restored assignments from its snapshot with zero duplicate "
          "acknowledged records.")
    return 0


def _run_coord_restart_leg(scratch, rec_path, port, timeout, failures):
    """Harness-managed coordinator: SIGTERM it mid-stream (graceful =
    final frontier snapshot), respawn from the snapshot, assert the
    appended journal's acked stream has zero duplicates and full
    coverage, and that the respawn actually restored (its log says so)."""
    import signal as _signal

    addr = "127.0.0.1:%d" % port
    prefix = os.path.join(scratch, "restart-snap")
    journal = os.path.join(scratch, "restart-journal-datacoord.jsonl")
    coord_log = os.path.join(scratch, "restart-coord.log")
    coord_env = dict(os.environ)
    coord_env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + coord_env.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": journal,
        "MXNET_TELEMETRY_FLUSH_SECS": "1",
    })
    coord_cmd = [sys.executable, "-m", "mxnet_tpu.data_service",
                 "--world", "2", "--bind", addr,
                 "--files", rec_path, "--batch-size", str(_DATA_BATCH),
                 "--snapshot-prefix", prefix]

    def _spawn_coord(log_f):
        return subprocess.Popen(coord_cmd, cwd=REPO, env=coord_env,
                                stdout=log_f, stderr=log_f, text=True)

    out_dir = os.path.join(scratch, "restart-out")
    os.makedirs(out_dir, exist_ok=True)
    worker_env = dict(os.environ)
    worker_env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep +
        worker_env.get("PYTHONPATH", ""),
        "MXNET_DATA_COORD": addr,
        "MXNET_DATA_TEST_OUT": out_dir,
        "MXNET_DATA_TEST_DIM": str(_DATA_DIM),
        "MXNET_DATA_TEST_PASSES": "2",
        "MXNET_DATA_TEST_SLEEP": "0.03",
        # the workers must ride out the coordinator outage on retries
        "MXNET_KV_RETRIES": "15",
    })
    worker_cmd = [sys.executable,
                  os.path.join(REPO, "tools", "launch.py"),
                  "-n", "2", "--launcher", "local", "--",
                  sys.executable,
                  os.path.join(REPO, "tests", "nightly",
                               "data_service_consume.py")]
    log_f = open(coord_log, "a")
    coord = _spawn_coord(log_f)
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            try:
                import socket as _socket

                with _socket.create_connection(
                        ("127.0.0.1", port), timeout=1.0):
                    break
            except OSError:
                time.sleep(0.1)
        workers = subprocess.Popen(worker_cmd, cwd=REPO, env=worker_env,
                                   text=True, stdout=subprocess.PIPE,
                                   stderr=subprocess.STDOUT,
                                   start_new_session=True)
        time.sleep(3.0)  # mid-stream (paced at ~0.03s/batch x 2 ranks)
        coord.send_signal(_signal.SIGTERM)
        coord.wait(timeout=30)
        coord = _spawn_coord(log_f)
        try:
            wout, _ = workers.communicate(timeout=timeout)
            wrc = workers.returncode
        except subprocess.TimeoutExpired:
            try:
                os.killpg(workers.pid, _signal.SIGKILL)
            except OSError:
                pass
            wout, _ = workers.communicate()
            wout = (wout or "") + "\n<HUNG>"
            wrc = -1
    finally:
        try:
            coord.send_signal(_signal.SIGTERM)
            coord.wait(timeout=30)
        except Exception:
            coord.kill()
        log_f.close()
    done = {int(r): int(recs) for r, _w, _b, recs in
            _DATA_OK_RE.findall(wout)}
    rc = 0
    if wrc != 0 or len(done) != 2:
        failures.append("restart leg: workers did not finish across the "
                        "coordinator restart (rc=%d, done=%s)\n%s"
                        % (wrc, sorted(done), wout[-2000:]))
        rc = 1
    with open(coord_log, encoding="utf-8") as f:
        log_text = f.read()
    if "restored frontier snapshot" not in log_text:
        failures.append("restart leg: the respawned coordinator did not "
                        "restore from the snapshot\n%s" % log_text[-1500:])
        rc = 1
    acks = _fold_mxdata_acks([journal])
    _check_ack_stream(acks, _DATA_RECORDS, "restart", failures,
                      passes=(0, 1))
    return rc


# -- mxctl closed-loop control-plane survival legs -----------------------------
# The ISSUE-12 acceptance contract: the mxctl controller
# (python -m mxnet_tpu.control, docs/how_to/control_plane.md) must
# close the loop end-to-end, asserted entirely from journals:
#   (a) SIGKILL a serving replica -> the liveness rule fires, the
#       restart_replica actuator respawns it, capacity and the
#       queue-depth SLO recover within a bounded window
#       (mxctl.actions_total >= 1, mxctl.recovery event with its
#       duration in the report);
#   (b) an injected persistent training straggler -> trace_merge
#       attribution names it, the controller admin-evicts it through
#       the elastic coordinator, the worker exits
#       (MXNET_ELASTIC_EXIT_ON_EVICT) and the launcher respawns a
#       healthy incarnation that rejoins; survivors finish within
#       accuracy tolerance;
#   (c) flap-guard negative control: a noisy-but-healthy replica
#       (readiness dips shorter than every rule's for= window) breaches
#       rules but triggers ZERO actions — hysteresis holds.

def _http_ok(url, timeout=2.0):
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status == 200
    except Exception:  # noqa: BLE001 - any failure = not serving
        return False


def _wait_until(fn, deadline_s, interval=0.5):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def _read_state(path):
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _journal_events(path, prefix="mxctl."):
    """The controller's decision journal: every span/event record whose
    name starts with ``prefix``, in file order."""
    out = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "span" and \
                        str(rec.get("name", "")).startswith(prefix):
                    out.append(rec)
    except OSError:
        pass
    return out


def _stop_proc(proc, log_path, grace=30.0):
    """SIGTERM -> wait -> killpg. Returns (rc, log text). The
    controller and its replicas write to a LOG FILE, never a pipe the
    harness forgets to drain — a supervised child blocking on a full
    pipe buffer is indistinguishable from the wedged replica the
    controller hunts (found the hard way)."""
    import signal as _signal

    hung = ""
    if proc.poll() is None:
        proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
        hung = "\n<controller HUNG: SIGKILLed>"
    try:
        with open(log_path, "r", encoding="utf-8", errors="replace") as f:
            out = f.read()
    except OSError:
        out = ""
    return proc.returncode, out + hung


def _spawn_logged(cmd, env, log_path):
    log_f = open(log_path, "ab")
    try:
        return subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log_f,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log_f.close()


def _controller_env(scratch, tag, extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": os.path.join(
            scratch, tag + "-mxctl-journal.jsonl"),
        "MXNET_TELEMETRY_FLUSH_SECS": "1",
        "MXCTL_STATE": os.path.join(scratch, tag + "-state.json"),
        "MXCTL_REPLICA_LOG": os.path.join(scratch, tag + "-{name}.log"),
        # respawns must come back warm: a shared persistent jit cache
        # is what makes restart-recovery fast enough to matter
        "JAX_COMPILATION_CACHE_DIR": os.path.join(scratch, "jit-cache"),
    })
    for k in list(env):
        if k.startswith("MXCTL_") and k not in ("MXCTL_STATE",):
            if k not in extra:
                del env[k]
    env.update(extra)
    return env


def _replica_ready(port):
    """Truly ready: /readyz answers 200 (the replica passed warmup and
    called mark_ready) AND /servingz lists a live engine. /readyz alone
    is not enough — a process still importing reports the default
    process-level ready with no engine behind it."""
    import urllib.request

    if not _http_ok("http://127.0.0.1:%d/readyz" % port):
        return False
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/servingz" % port, timeout=2) as r:
            return bool(json.load(r).get("engines"))
    except Exception:  # noqa: BLE001
        return False


def _serving_leg(scratch, base_port, per_leg, failures):
    """Leg (a): SIGKILL a serving replica; the controller restores it."""
    tag = "serve"
    serve = os.path.join(REPO, "tests", "nightly", "serve_replica.py")
    targets = {"r0": base_port, "r1": base_port + 1}
    env = _controller_env(scratch, tag, {
        "MXCTL_TARGETS": ",".join(
            "%s=http://127.0.0.1:%d" % (n, p)
            for n, p in sorted(targets.items())),
        "MXCTL_RULES": "alive<1:for=3:action=restart_replica:cooldown=10",
        "MXCTL_INTERVAL": "0.4",
        # a contended box can hold a cold import past the default 10s
        # grace; a startup restart is harmless but muddies the report
        "MXCTL_STARTUP_GRACE": "45",
        "MXCTL_REPLICA_JOURNAL": os.path.join(
            scratch, tag + "-{name}-journal.jsonl"),
    })
    cmd = [sys.executable, "-m", "mxnet_tpu.control"]
    for n in sorted(targets):
        cmd += ["--replica", "%s=%s %s" % (n, sys.executable, serve)]
    print("chaos --controller: serving leg (SIGKILL replica r1, "
          "controller restores capacity)")
    t_start = time.time()
    ctl_log = os.path.join(scratch, tag + "-controller.log")
    proc = _spawn_logged(cmd, env, ctl_log)
    state_path = env["MXCTL_STATE"]
    journal = env["MXNET_TELEMETRY_JOURNAL"]
    try:
        ready = _wait_until(
            lambda: all(_replica_ready(p) for p in targets.values()),
            min(0.6 * per_leg, 240))
        if not ready:
            failures.append("serving leg: replicas never became ready")
            return {}
        warm_s = time.time() - t_start
        old_pid = _read_state(state_path).get(
            "replicas", {}).get("r1", {}).get("pid")
        if not old_pid:
            failures.append("serving leg: no r1 pid in the state file")
            return {}
        os.kill(int(old_pid), 9)  # the chaos injection
        t_kill = time.time()
        recovered = _wait_until(
            lambda: (_http_ok("http://127.0.0.1:%d/healthz"
                              % targets["r1"])
                     and _read_state(state_path).get("replicas", {})
                     .get("r1", {}).get("pid") not in (None, old_pid)),
            min(0.35 * per_leg, 150))
        recovery_wall = time.time() - t_kill
        if not recovered:
            failures.append("serving leg: controller did not restore "
                            "replica r1 within %.0fs"
                            % min(0.35 * per_leg, 150))
        # wait for the respawned incarnation to finish warmup (fast —
        # the shared jit cache), then let it actually serve: the
        # SLO-recovery assertions below read ITS journal, which only
        # lands if the graceful teardown reaches a warmed replica
        if not _wait_until(lambda: _replica_ready(targets["r1"]),
                           min(0.25 * per_leg, 120)):
            failures.append("serving leg: restored r1 never became "
                            "ready again")
        time.sleep(3)  # let the restored replica serve
    finally:
        rc, out = _stop_proc(proc, ctl_log)
    if rc != 0:
        failures.append("serving leg: controller exited %d\n%s"
                        % (rc, out[-2000:]))
    counters = fold_telemetry(journal)
    if counters.get("mxctl.actions_total", 0) < 1:
        failures.append("serving leg: mxctl.actions_total=0 — the loop "
                        "never closed (counters: %s)" % counters)
    events = _journal_events(journal)
    actions = [e for e in events if e["name"] == "mxctl.action"
               and e.get("outcome") == "ok"]
    if not any(e.get("action") == "restart_replica"
               and e.get("target") == "r1" for e in actions):
        failures.append("serving leg: no successful restart_replica "
                        "action on r1 in the journal (%s)"
                        % [(e.get("action"), e.get("target"),
                            e.get("outcome")) for e in events
                           if e["name"] == "mxctl.action"])
    recoveries = [e for e in events if e["name"] == "mxctl.recovery"
                  and e.get("target") == "r1"]
    if not recoveries:
        failures.append("serving leg: no mxctl.recovery event for r1 — "
                        "the SLO never came back")
    rec_s = recoveries[0]["dur"] if recoveries else None
    if rec_s is not None and rec_s > 60.0:
        failures.append("serving leg: recovery took %.1fs (> 60s bound)"
                        % rec_s)
    # the rule trace must link detect->act->recover as ONE causal chain
    rules_fired = [e for e in events if e["name"] == "mxctl.rule"
                   and e.get("target") == "r1"]
    if rules_fired and actions:
        traces = {e.get("trace") for e in rules_fired}
        if not any(a.get("trace") in traces for a in actions):
            failures.append("serving leg: action events do not share the "
                            "firing rule's trace id")
    # SLO recovery from the REPLICA's journal: the respawned
    # incarnation admitted work and its queue is not saturated
    rj = os.path.join(scratch, tag + "-r1-journal.jsonl")
    rcounters = fold_telemetry(rj)
    if rcounters.get("serving.requests_admitted", 0) < 1:
        failures.append("serving leg: restored r1 admitted no requests "
                        "(journal %s: %s)" % (rj, rcounters))
    qd = fold_gauges(rj).get("serving.queue_depth")
    if qd is not None and qd >= 64:
        failures.append("serving leg: restored r1's queue is saturated "
                        "(depth %g)" % qd)
    return {"warm_s": warm_s, "recovery_s": rec_s,
            "recovery_wall_s": recovery_wall, "counters": counters}


def _straggler_leg(scratch, port, per_leg, base_acc, failures):
    """Leg (b): persistent training straggler -> evict-and-replace."""
    tag = "straggler"
    mark = tempfile.mkdtemp(prefix="slowmark-", dir=scratch)
    env = _controller_env(scratch, tag, {
        "MXCTL_COORD": "127.0.0.1:%d" % port,
        # digit-only glob: the coordinator's own journal (-coord) must
        # never enter worker straggler attribution
        "MXCTL_JOURNALS": os.path.join(scratch,
                                       tag + "-journal-[0-9]*.jsonl"),
        "MXCTL_RULES": ("straggler>0:for=3:action=evict_replace"
                        ":cooldown=300:scope=training:max=1"),
        "MXCTL_INTERVAL": "1.5",
        "MXCTL_STRAGGLER_MIN_WAIT": "3.0",
    })
    print("chaos --controller: straggler leg (rank 2 drags every round; "
          "controller evicts, launcher replaces)")
    ctl_log = os.path.join(scratch, tag + "-controller.log")
    ctl = _spawn_logged([sys.executable, "-m", "mxnet_tpu.control"],
                        env, ctl_log)
    try:
        rc, accs, c, out = _run_elastic_leg(
            tag, scratch, port, per_leg,
            extra_env={
                "MXNET_ELASTIC_TEST_SLOW_RANK": "2",
                "MXNET_ELASTIC_TEST_SLOW_SECS": "0.4",
                "MXNET_ELASTIC_TEST_MARK": mark,
                "MXNET_ELASTIC_EXIT_ON_EVICT": "1",
            },
            launch_args=["--max-restarts", "1", "--restart-delay", "1"])
    finally:
        ctl_rc, ctl_out = _stop_proc(ctl, ctl_log)
    if rc != 0 or len(accs) != _ELASTIC_N:
        failures.append("straggler leg: not every rank (incl. the "
                        "replaced straggler) finished (rc=%d done=%s)\n%s"
                        % (rc, sorted(accs), out[-2000:]))
    if base_acc is not None and accs and \
            base_acc - min(accs.values()) > _ELASTIC_ACC_TOL:
        failures.append("straggler leg: accuracy %.3f fell more than "
                        "%.2f below fault-free %.3f"
                        % (min(accs.values()), _ELASTIC_ACC_TOL, base_acc))
    journal = env["MXNET_TELEMETRY_JOURNAL"]
    counters = fold_telemetry(journal)
    events = _journal_events(journal)
    evicts = [e for e in events if e["name"] == "mxctl.action"
              and e.get("action") == "evict_replace"
              and e.get("outcome") == "ok"]
    if not evicts:
        failures.append(
            "straggler leg: no successful evict_replace action in the "
            "controller journal (rc=%d, events: %s)\n%s"
            % (ctl_rc, [(e.get("name"), e.get("action"), e.get("target"),
                         e.get("outcome")) for e in events],
               ctl_out[-1500:]))
    elif evicts[0].get("target") != "rank2":
        failures.append("straggler leg: controller evicted %s, not the "
                        "injected straggler rank2"
                        % evicts[0].get("target"))
    if c.get("kvstore.evictions_total", 0) < 1:
        failures.append("straggler leg: workers saw no eviction "
                        "(counters: %s)" % c)
    if c.get("kvstore.rejoins_total", 0) < 1:
        failures.append("straggler leg: the replacement never rejoined "
                        "(counters: %s)" % c)
    return {"counters": counters, "worker_counters": c,
            "accs": accs, "evict_target": (evicts[0].get("target")
                                           if evicts else None)}


def _flap_leg(scratch, port, per_leg, failures):
    """Leg (c): noisy-but-healthy replica -> zero actions."""
    tag = "flap"
    serve = os.path.join(REPO, "tests", "nightly", "serve_replica.py")
    env = _controller_env(scratch, tag, {
        "MXCTL_TARGETS": "r0=http://127.0.0.1:%d" % port,
        # for=10 @ 0.5s = 5s sustained: the injected dips are ~0.6-1.5s
        # (flap thread sleep granularity + GIL stalls), leaving >3x
        # margin on a busy box while every dip still lands >=1 probe
        "MXCTL_RULES": ("ready<1:for=10:action=restart_replica:cooldown=30;"
                        "alive<1:for=10:action=restart_replica:cooldown=30"),
        "MXCTL_INTERVAL": "0.5",
        "MXCTL_STARTUP_GRACE": "45",
        "MXCTL_REPLICA_JOURNAL": os.path.join(
            scratch, tag + "-{name}-journal.jsonl"),
        # drain for 0.6s every 2.5s via the replica's dedicated flap
        # thread: readiness dips 1-3 probes long, never 10 consecutive
        "SERVE_REPLICA_FLAP": "2.5,0.6",
        # lighter load: fewer distinct late-compiling shapes churning
        # the GIL while the negative control measures
        "SERVE_REPLICA_LOAD": "2,0.4,6",
    })
    print("chaos --controller: flap-guard leg (readiness flaps, "
          "hysteresis must hold: zero actions)")
    ctl_log = os.path.join(scratch, tag + "-controller.log")
    proc = _spawn_logged(
        [sys.executable, "-m", "mxnet_tpu.control",
         "--replica", "r0=%s %s" % (sys.executable, serve)],
        env, ctl_log)
    try:
        ready = _wait_until(lambda: _replica_ready(port),
                            min(0.6 * per_leg, 240))
        if ready:
            time.sleep(20)  # measure across ~6 flap cycles, ~40 probes
        else:
            failures.append("flap leg: replica never came up")
    finally:
        rc, out = _stop_proc(proc, ctl_log)
    if rc != 0:
        failures.append("flap leg: controller exited %d\n%s"
                        % (rc, out[-2000:]))
    counters = fold_telemetry(env["MXNET_TELEMETRY_JOURNAL"])
    if counters.get("mxctl.breaches_total", 0) < 1:
        failures.append("flap leg: the replica never actually breached "
                        "(counters: %s) — the negative control proves "
                        "nothing" % counters)
    acted = (counters.get("mxctl.actions_total", 0)
             + counters.get("mxctl.actions_dryrun_total", 0)
             + counters.get("mxctl.actions_failed_total", 0))
    if acted:
        failures.append("flap leg: a noisy-but-healthy replica drew %d "
                        "action(s) — hysteresis failed (counters: %s)"
                        % (acted, counters))
    return {"counters": counters}


def run_controller(args):
    """The mxctl closed-loop survival legs (ISSUE 12)."""
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-mxctl-")
    base_port = 29820 + (args.seed % 97) * 8
    legs = [s.strip() for s in (args.controller_legs or "all").split(",")]
    run_all = "all" in legs
    per_leg = args.timeout / 4.0
    failures = []
    serve_rep = strag_rep = flap_rep = None
    base_acc = None

    if run_all or "serving" in legs:
        serve_rep = _serving_leg(scratch, base_port, per_leg, failures)
    if run_all or "straggler" in legs:
        print("chaos --controller: straggler baseline (fault-free)")
        rc0, accs0, _c0, out0 = _run_elastic_leg(
            "cbase", scratch, base_port + 2, per_leg)
        if rc0 != 0 or len(accs0) != _ELASTIC_N:
            failures.append("straggler baseline failed (rc=%d done=%s)\n%s"
                            % (rc0, sorted(accs0), out0[-2000:]))
        else:
            base_acc = sum(accs0.values()) / len(accs0)
        strag_rep = _straggler_leg(scratch, base_port + 3, per_leg,
                                   base_acc, failures)
    if run_all or "flap" in legs:
        flap_rep = _flap_leg(scratch, base_port + 7, per_leg, failures)

    print("\n=== controller survival report ===")
    if serve_rep is not None:
        c = serve_rep.get("counters", {})
        print("serving leg     : warm %.1fs, recovery %s (wall %.1fs), "
              "probes=%d actions=%d failed=%d recoveries=%d"
              % (serve_rep.get("warm_s", -1),
                 "%.1fs" % serve_rep["recovery_s"]
                 if serve_rep.get("recovery_s") is not None else "NONE",
                 serve_rep.get("recovery_wall_s", -1),
                 c.get("mxctl.probes_total", 0),
                 c.get("mxctl.actions_total", 0),
                 c.get("mxctl.actions_failed_total", 0),
                 c.get("mxctl.recoveries_total", 0)))
    if strag_rep is not None:
        c = strag_rep.get("counters", {})
        w = strag_rep.get("worker_counters", {})
        print("straggler leg   : evicted=%s actions=%d evictions=%d "
              "rejoins=%d accs=%s (baseline %s)"
              % (strag_rep.get("evict_target"),
                 c.get("mxctl.actions_total", 0),
                 w.get("kvstore.evictions_total", 0),
                 w.get("kvstore.rejoins_total", 0),
                 {r: round(a, 3)
                  for r, a in sorted(strag_rep.get("accs", {}).items())},
                 "%.4f" % base_acc if base_acc is not None else "FAILED"))
    if flap_rep is not None:
        c = flap_rep.get("counters", {})
        print("flap leg        : breaches=%d fired=%d actions=%d "
              "(zero required)"
              % (c.get("mxctl.breaches_total", 0),
                 c.get("mxctl.rules_fired_total", 0),
                 c.get("mxctl.actions_total", 0)
                 + c.get("mxctl.actions_dryrun_total", 0)))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 9
    proofs = []
    if serve_rep is not None:
        proofs.append("detected a SIGKILLed serving replica and "
                      "restored capacity within the SLO window")
    if strag_rep is not None:
        proofs.append("attributed and evict-replaced a persistent "
                      "training straggler (survivors within %.2f of "
                      "fault-free accuracy)" % _ELASTIC_ACC_TOL)
    if flap_rep is not None:
        proofs.append("held every action back from a noisy-but-healthy "
                      "replica")
    print("\nRESULT: SURVIVED — the controller %s — all proven from "
          "the mxctl decision journal." % "; ".join(proofs))
    return 0


# -- live weight-sync survival legs (ISSUE 17) --------------------------------
# The wsync acceptance contract (docs/how_to/weight_sync.md): a LOADED
# engine hot-swaps published versions with p99 TTFT inside 1.10x its own
# no-sync baseline and lands byte-identical to a cold engine started
# from the same version's checkpoint; a publisher SIGKILLed mid-stream
# leaves the engine on its last complete version with zero non-finite
# live params; a NaN-poisoned version is refused end to end
# (wsync.rejected_total >= 1); and a cratered spec-accept window drives
# the mxctl rollback_weights rule back to the prior version — all
# asserted from the {"kind": "wsync"} journal records and wsync.*
# counters, one trace id per transaction.


def _wsync_events(path, event=None):
    """Every ``{"kind": "wsync"}`` journal record (optionally one
    event type), in file order."""
    out = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "wsync":
                    continue
                if event is not None and rec.get("event") != event:
                    continue
                out.append(rec)
    except OSError:
        pass
    return out


def run_wsync(args):
    """The gated live trainer->serving weight-sync survival legs."""
    import dataclasses
    import signal
    import threading

    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-wsync-")
    port = 29920 + (args.seed % 97) * 3
    journal = os.path.join(scratch, "wsync-journal.jsonl")
    # env BEFORE the mxnet_tpu import: the in-process engine, publisher,
    # subscriber and controller all journal into ONE file
    os.environ.update({
        "JAX_PLATFORMS": "cpu",
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": journal,
        "MXNET_WSYNC": "1",
    })
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import jax
    import numpy as np

    import mxnet_tpu.telemetry as tel
    tel.reload()
    from mxnet_tpu.control.config import ControlConfig
    from mxnet_tpu.control.controller import Controller
    from mxnet_tpu.control.probes import TargetSample, serving_metrics
    from mxnet_tpu.control.rules import parse_rules
    from mxnet_tpu.models.transformer import TransformerConfig, init_params
    from mxnet_tpu.serving import Engine, ServingConfig
    from mxnet_tpu.wsync import common as wc
    from mxnet_tpu.wsync.publisher import WeightPublisher
    from mxnet_tpu.wsync.subscriber import WeightSubscriber

    failures = []
    rng = np.random.default_rng(args.seed)
    cfg = TransformerConfig(vocab_size=61, num_layers=2, d_model=32,
                            num_heads=2, d_ff=64, max_seq_len=96,
                            dtype="float32")
    params0 = init_params(cfg, jax.random.PRNGKey(0))
    dcfg = dataclasses.replace(cfg, num_layers=1)

    def draft_of(params):
        # aligned draft (shared embeddings + first target layer): the
        # spec accept rate stays HIGH on every healthy version, so the
        # rollback leg's crater is unambiguous
        return {"embed": params["embed"], "pos_embed": params["pos_embed"],
                "layers": params["layers"][:1], "ln_f": params["ln_f"]}

    def perturb(tree, scale):
        flat = {}
        for k, v in wc.flatten_params(tree).items():
            a = np.asarray(v)
            if np.issubdtype(a.dtype, np.floating):
                a = a + rng.standard_normal(a.shape).astype(a.dtype) * scale
            flat[k] = a
        return wc.unflatten_params(flat)

    def fp_diff(flat_a, flat_b):
        keys = sorted(set(flat_a) | set(flat_b))
        return [k for k in keys
                if k not in flat_a or k not in flat_b
                or wc.fingerprint(np.asarray(flat_a[k]))
                != wc.fingerprint(np.asarray(flat_b[k]))]

    scfg = ServingConfig(block_size=8, num_blocks=33, max_batch=4,
                         prefill_chunk=16, token_budget=64,
                         spec=True, spec_k=3)
    eng = Engine(params0, cfg, scfg, draft_params=draft_of(params0),
                 draft_cfg=dcfg)
    eng.start()

    def load(n, max_new=8):
        hs = []
        for i in range(n):
            prompt = np.asarray([(5 * i + j) % 50 + 1 for j in range(6)],
                                np.int32)
            hs.append(eng.submit(prompt, max_new_tokens=max_new))
        return [h.result(timeout=120) for h in hs]

    versions = {v: perturb(params0, 0.02 * v) for v in (1, 2, 3)}

    # -- leg a: loaded sync (TTFT degradation under live swaps) --------
    print("chaos --wsync: loaded-sync leg (3 versions hot-swapped under "
          "load; p99 TTFT vs the engine's own no-sync baseline)")
    # warm the jit cache FIRST, at the same concurrency profile the
    # measured windows use: compile time is not serving TTFT, and a
    # narrower warmup leaves batch buckets compiling inside the baseline
    load(24)
    n_warm = len(eng.latency_samples()[0])
    pub = WeightPublisher(bind=("127.0.0.1", port))
    pub.start()
    sub = WeightSubscriber(eng, "127.0.0.1:%d" % port, rank=0)
    stop_load = threading.Event()

    def pump():
        while not stop_load.is_set():
            try:
                load(2)
            except Exception:  # noqa: BLE001 - a dead pump = no sync TTFTs, asserted below
                return

    pump_t = threading.Thread(target=pump, daemon=True)
    pump_t.start()
    applied = []
    try:
        # the no-sync baseline window: the SAME pump load the sync
        # windows see, so the two p99s differ only by the swaps
        time.sleep(3.0)
        base_ttfts = eng.latency_samples()[0][n_warm:]
        base_p99 = (float(np.percentile(np.asarray(base_ttfts), 99))
                    if base_ttfts else None)
        for v in (1, 2, 3):
            pub.publish(versions[v], draft_of(versions[v]))
            applied.append(sub.sync_once(wait=10.0))
            time.sleep(1.2)   # serve inside the post-swap TTFT window
    finally:
        stop_load.set()
        pump_t.join(timeout=120)
    if applied != [1, 2, 3]:
        failures.append("loaded-sync leg: applied versions %s, expected "
                        "[1, 2, 3]" % (applied,))
    sync_p99 = eng.stats()["ttft_sync_p99_s"]
    if sync_p99 is None or base_p99 is None:
        failures.append("loaded-sync leg: missing TTFT samples "
                        "(baseline %s, during-sync %s)"
                        % (base_p99, sync_p99))
    # 25ms absolute floor: at this tiny model's millisecond TTFTs a
    # shared box's scheduler jitter dwarfs 10% — the ratio gate applies
    # above it
    elif sync_p99 > base_p99 * 1.10 + 0.025:
        failures.append("loaded-sync leg: p99 TTFT during sync %.4fs "
                        "exceeds 1.10x the no-sync baseline %.4fs"
                        % (sync_p99, base_p99))

    # -- leg b: NaN-poisoned version refused ---------------------------
    print("chaos --wsync: poisoned-version leg (NaN tensor refused by "
          "the finiteness gate, live params untouched)")
    pflat = wc.flatten_params(perturb(versions[3], 0.01))
    k0 = sorted(k for k in pflat
                if np.issubdtype(np.asarray(pflat[k]).dtype,
                                 np.floating))[0]
    poisoned = np.array(pflat[k0], copy=True)
    poisoned.flat[0] = np.nan
    pflat[k0] = poisoned
    pub.publish(wc.unflatten_params(pflat), draft_of(versions[3]),
                version=4)
    got4 = sub.sync_once(wait=5.0)
    if got4 is not None:
        failures.append("poisoned leg: version 4 applied (%s) despite "
                        "the NaN in %s" % (got4, k0))
    if eng.weight_version() != 3:
        failures.append("poisoned leg: engine moved to version %s, "
                        "expected to stay on 3" % (eng.weight_version(),))
    bad = wc.nonfinite_keys(wc.combine_draft(eng.params, eng.draft_params))
    if bad:
        failures.append("poisoned leg: non-finite LIVE params after the "
                        "refusal: %s" % sorted(bad))

    # -- leg c: cratered spec accept -> mxctl rollback_weights ---------
    print("chaos --wsync: rollback leg (garbage weights crater the "
          "spec-accept window; the mxctl rule must fire "
          "rollback_weights)")
    garbage = wc.unflatten_params({
        k: (rng.standard_normal(np.shape(np.asarray(v)))
            .astype(np.asarray(v).dtype)
            if np.issubdtype(np.asarray(v).dtype, np.floating)
            else np.asarray(v))
        for k, v in wc.flatten_params(versions[3]).items()})
    # the OLD draft rides along: target garbage vs a draft aligned to
    # the previous target = near-zero accept rate, the signal the
    # shipped rule recipe (docs/how_to/control_plane.md) reads
    pub.publish(garbage, draft_of(versions[3]), version=5)
    got5 = sub.sync_once(wait=5.0)
    if got5 != 5:
        failures.append("rollback leg: garbage version 5 did not apply "
                        "(%s) — the crater needs it live" % (got5,))
    load(8)  # populate the spec accept window on the garbage weights

    class _EngineProbe:
        def sample(self, now=None):
            m = serving_metrics({"engines": [eng.introspect()]})
            m.update({"alive": 1.0, "ready": 1.0})
            return TargetSample("serving0", "serving", m,
                                {"url": "chaos://in-process"})

    ctl = Controller(
        ControlConfig(
            targets={},
            rules=parse_rules("spec_accept_rate<0.5:for=3:"
                              "action=rollback_weights:scope=serving:"
                              "cooldown=60"),
            interval=0.2,
            state_path=os.path.join(scratch, "mxctl-state.json")),
        probes=[_EngineProbe()])
    fired = False
    for _ in range(8):
        load(4)
        if any(d.rule.action == "rollback_weights" for d in ctl.step()):
            fired = True
            break
        time.sleep(0.2)
    if not fired:
        failures.append("rollback leg: the spec_accept_rate rule never "
                        "fired (window rate %s)"
                        % (eng.stats()["spec_accept_rate_window"],))
    if eng.weight_version() != 3:
        failures.append("rollback leg: engine on version %s after the "
                        "rollback, expected the prior good version 3"
                        % (eng.weight_version(),))
    else:
        diff = fp_diff(wc.flatten_params(eng.params),
                       wc.flatten_params(versions[3]))
        if diff:
            failures.append("rollback leg: restored params differ from "
                            "version 3 on %d tensors (e.g. %s)"
                            % (len(diff), diff[:3]))

    # -- leg d: byte parity vs a cold engine from the checkpoint -------
    print("chaos --wsync: byte-parity leg (hot-swapped+rolled-back "
          "engine vs a cold engine from the version-3 checkpoint)")
    ck = os.path.join(scratch, "parity-ck")
    wc.save_weights_checkpoint(ck, 3, versions[3], draft_of(versions[3]))
    cold_params, cold_draft = wc.load_weights_checkpoint(ck, 3)
    cold = Engine(cold_params, cfg, scfg, draft_params=cold_draft,
                  draft_cfg=dcfg)
    diff = fp_diff(wc.combine_draft(eng.params, eng.draft_params),
                   wc.combine_draft(cold.params, cold.draft_params))
    if diff:
        failures.append("byte-parity leg: %d tensors differ between the "
                        "hot and cold engines (e.g. %s)"
                        % (len(diff), diff[:3]))
    parity_prompt = np.asarray([7, 11, 13, 17, 19, 23], np.int32)
    hot_toks = eng.submit(parity_prompt,
                          max_new_tokens=12).result(timeout=120)
    cold_toks = cold.generate([parity_prompt], max_new_tokens=12)[0]
    if list(hot_toks) != list(cold_toks):
        failures.append("byte-parity leg: greedy streams diverge — hot "
                        "%s vs cold %s" % (hot_toks, cold_toks))

    # -- leg e: publisher SIGKILL mid-stream ---------------------------
    print("chaos --wsync: publisher-SIGKILL leg (throttled stream "
          "killed mid-fetch; the engine must stay on the last "
          "complete version)")
    ck2 = os.path.join(scratch, "stream-ck")
    v1p, v2p = perturb(params0, 0.015), perturb(params0, 0.025)
    wc.save_weights_checkpoint(ck2, 1, v1p, draft_of(v1p))
    eng2 = Engine(params0, cfg, scfg, draft_params=draft_of(params0),
                  draft_cfg=dcfg)
    n_keys = len(wc.combine_draft(v1p, draft_of(v1p)))
    throttle = 0.08
    penv = dict(os.environ)
    penv.update({
        "PYTHONPATH": REPO + os.pathsep + penv.get("PYTHONPATH", ""),
        "MXNET_TELEMETRY_JOURNAL": os.path.join(
            scratch, "wsync-pub-journal.jsonl"),
        "MXNET_TELEMETRY_FLUSH_SECS": "1",
    })
    plog = os.path.join(scratch, "wsync-pub.log")
    pproc = _spawn_logged(
        [sys.executable, "-m", "mxnet_tpu.wsync.publisher",
         "--bind", "127.0.0.1:%d" % (port + 1),
         "--watch", ck2, "--interval", "0.2",
         "--throttle", "%g" % throttle], penv, plog)
    sub2 = WeightSubscriber(eng2, "127.0.0.1:%d" % (port + 1), rank=1)
    got1 = None
    deadline = time.time() + max(60.0, 4 * throttle * n_keys)
    while got1 is None and time.time() < deadline:
        try:
            got1 = sub2.sync_once(wait=2.0)
        except Exception:  # noqa: BLE001 - publisher still importing
            time.sleep(0.3)
    if got1 != 1:
        failures.append("publisher-SIGKILL leg: version 1 never applied "
                        "(got %s) — publisher log tail:\n%s"
                        % (got1, _stop_proc(pproc, plog,
                                            grace=5.0)[1][-1500:]))
    else:
        wc.save_weights_checkpoint(ck2, 2, v2p, draft_of(v2p))
        holder = {}

        def fetch_v2():
            try:
                holder["v"] = sub2.sync_once(wait=15.0)
            except Exception as e:  # noqa: BLE001 - asserted below
                holder["err"] = e

        t2 = threading.Thread(target=fetch_v2, daemon=True)
        t2.start()
        # ~40% through the throttled transfer: mid-stream by
        # construction (watch poll 0.2s + the manifest fetch land well
        # inside the first second; the transfer takes throttle*n_keys)
        time.sleep(1.0 + 0.4 * throttle * n_keys)
        try:
            os.killpg(pproc.pid, signal.SIGKILL)
        except OSError:
            pass
        t2.join(timeout=120)
        pproc.wait()
        if t2.is_alive():
            failures.append("publisher-SIGKILL leg: subscriber hung "
                            "after the kill (no abort)")
        elif holder.get("v") is not None:
            failures.append("publisher-SIGKILL leg: torn version 2 "
                            "reported applied (%s)" % (holder["v"],))
        if eng2.weight_version() != 1:
            failures.append("publisher-SIGKILL leg: engine on version "
                            "%s, not the last complete version 1"
                            % (eng2.weight_version(),))
        bad = wc.nonfinite_keys(wc.combine_draft(eng2.params,
                                                 eng2.draft_params))
        if bad:
            failures.append("publisher-SIGKILL leg: non-finite live "
                            "params after the torn fetch: %s"
                            % sorted(bad))
        want1 = wc.combine_draft(*wc.load_weights_checkpoint(ck2, 1))
        diff = fp_diff(wc.combine_draft(eng2.params, eng2.draft_params),
                       want1)
        if diff:
            failures.append("publisher-SIGKILL leg: live params differ "
                            "from the complete version-1 checkpoint on "
                            "%d tensors" % len(diff))

    # -- journal assertions (the chaos contract: prove it from disk) ---
    eng.stop()
    pub.close()
    tel.flush(mark="exit")
    counters = fold_telemetry(journal)
    events = _wsync_events(journal)
    # one trace id per transaction: every applied record must pair with
    # a staged record carrying the SAME (version, trace) — version alone
    # is not enough (two engines each stage their own version 1)
    staged_pairs = {(e.get("version"), e.get("trace"))
                    for e in events if e.get("event") == "staged"}
    for e in events:
        if e.get("event") != "applied":
            continue
        if (e.get("trace") is None
                or (e.get("version"), e.get("trace")) not in staged_pairs):
            failures.append("journal: applied version %s does not carry "
                            "its staged transaction's trace id"
                            % e.get("version"))
    rejected4 = [e for e in events if e.get("event") == "rejected"
                 and e.get("version") == 4]
    if not rejected4 or "non-finite" not in str(
            rejected4[0].get("reason", "")):
        failures.append("journal: no non-finite 'rejected' record for "
                        "version 4 (%s)" % rejected4)
    aborted2 = [e for e in events if e.get("event") == "aborted"
                and e.get("version") == 2]
    if not aborted2:
        failures.append("journal: no 'aborted' record for the torn "
                        "version-2 transaction")
    elif not any(e.get("fetched", 0) >= 1 for e in aborted2):
        failures.append("journal: the version-2 abort shows 0 fetched "
                        "tensors — the kill missed the stream window")
    rolled = [e for e in events if e.get("event") == "rolled_back"]
    if not any(e.get("from_version") == 5 for e in rolled):
        failures.append("journal: no 'rolled_back' record from version "
                        "5 (%s)" % rolled)
    for name, floor in (("wsync.versions_published_total", 5),
                        ("wsync.versions_applied_total", 4),
                        ("wsync.rejected_total", 1),
                        ("wsync.aborted_total", 1),
                        ("wsync.rollbacks_total", 1),
                        ("wsync.acks_total", 4),
                        ("wsync.tensors_fetched_total", n_keys)):
        if counters.get(name, 0) < floor:
            failures.append("journal: counter %s=%s below the expected "
                            "floor %d" % (name, counters.get(name, 0),
                                          floor))
    # the SIGKILLed publisher flushed periodically (1s cadence): its own
    # journal must still show the version-1 publish it completed
    pub_published = _wsync_events(penv["MXNET_TELEMETRY_JOURNAL"],
                                  event="published")
    if not pub_published:
        failures.append("journal: the SIGKILLed publisher's own journal "
                        "recorded no 'published' transitions")

    print("\n=== wsync survival report ===")
    print("loaded sync     : applied=%s p99 TTFT %.4fs during sync vs "
          "%.4fs baseline (bound 1.10x + 25ms jitter floor)"
          % (applied, sync_p99 or -1, base_p99 or -1))
    print("poisoned v4     : %s"
          % ("refused" if got4 is None else "APPLIED (%s)" % got4))
    print("rollback        : rule fired=%s, engine back on version %s"
          % (fired, eng.weight_version()))
    print("publisher kill  : engine2 on version %s after the torn fetch"
          % (eng2.weight_version(),))
    print("counters        : published=%d applied=%d rejected=%d "
          "aborted=%d rollbacks=%d acks=%d tensors=%d bytes=%d"
          % (counters.get("wsync.versions_published_total", 0),
             counters.get("wsync.versions_applied_total", 0),
             counters.get("wsync.rejected_total", 0),
             counters.get("wsync.aborted_total", 0),
             counters.get("wsync.rollbacks_total", 0),
             counters.get("wsync.acks_total", 0),
             counters.get("wsync.tensors_fetched_total", 0),
             counters.get("wsync.bytes_fetched_total", 0)))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 10
    print("\nRESULT: SURVIVED — live weight sync swapped versions under "
          "load inside the TTFT bound, refused the poisoned version, "
          "stayed on the last complete version through a mid-stream "
          "publisher SIGKILL, rolled back a quality crater via the "
          "mxctl rule, and byte-matched a cold engine — all proven "
          "from the journal.")
    return 0


def run_fleet(args):
    """The mxfleet fault-isolated serving fleet survival legs (ISSUE 20)."""
    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-fleet-")
    base_port = 30100 + (args.seed % 97) * 2
    journal = os.path.join(scratch, "fleet-journal.jsonl")
    # env BEFORE the mxnet_tpu import: the in-process router + controller
    # journal into ONE file; replica subprocesses get their own journals
    # via MXCTL_REPLICA_JOURNAL templating and share the jit cache so a
    # respawned replica comes back warm
    os.environ.update({
        "JAX_PLATFORMS": "cpu",
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": journal,
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_COMPILATION_CACHE_DIR": os.path.join(scratch, "jit-cache"),
    })
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import numpy as np

    import mxnet_tpu.telemetry as tel
    tel.reload()
    from mxnet_tpu.control.config import ControlConfig
    from mxnet_tpu.control.controller import Controller
    from mxnet_tpu.control.probes import FleetProbe
    from mxnet_tpu.control.rules import parse_rules
    from mxnet_tpu.control.supervisor import Supervisor
    from mxnet_tpu.serving.fleet import Router

    failures = []
    rng = np.random.RandomState(args.seed)
    router = Router(bind=("127.0.0.1", base_port), inflight_cap=4,
                    pending_max=256, health_interval=0.5)
    router.serve()
    router.start(interval=0.01)
    template = ("%s -m mxnet_tpu.serving.fleet.replica "
                "--router 127.0.0.1:%d --name {name} --bind 127.0.0.1:0 "
                "--seed %d" % (sys.executable, base_port, args.seed))
    sup = Supervisor()

    def mk_ctl(rules):
        return Controller(
            ControlConfig(
                targets={}, rules=parse_rules(rules), interval=0.3,
                state_path=os.path.join(scratch, "mxctl-state.json"),
                replica_journal=os.path.join(
                    scratch, "fleet-{name}-journal.jsonl"),
                replica_log=os.path.join(scratch, "fleet-{name}.log"),
                drain_grace=120.0, startup_grace=120.0,
                replica_template=template, fleet_min=4, fleet_max=5),
            probes=[FleetProbe(router)], supervisor=sup)

    def accepting():
        return router.stats()["replicas_accepting"]

    def submit_batch(prompts, max_new):
        return [router.submit(p, max_new_tokens=max_new) for p in prompts]

    def collect(streams, timeout=300.0):
        deadline = time.time() + timeout
        out = []
        for s in streams:
            try:
                out.append(s.result(timeout=max(1.0,
                                                deadline - time.time())))
            except Exception:  # noqa: BLE001 - a lost stream = the finding
                out.append(None)
        return out

    def mk_prompts(n):
        return [rng.randint(1, 50,
                            size=int(rng.randint(4, 9))).tolist()
                for _ in range(n)]

    # -- bring-up: 4 supervised replicas via the scale_up actuator ------
    print("chaos --fleet: bring-up (4 supervised replicas via scale_up, "
          "readyz-gated registration)")
    boot = mk_ctl("alive<1:for=3:action=restart_replica:cooldown=20")
    for _ in range(4):
        boot.actuators.get("scale_up").execute(None, boot)
    if not _wait_until(lambda: accepting() >= 4, 420):
        tail = ""
        log0 = os.path.join(scratch, "fleet-replica0.log")
        try:
            with open(log0, "r", encoding="utf-8", errors="replace") as f:
                tail = f.read()[-1500:]
        except OSError:
            pass
        print("RESULT: FAIL\n - fleet never reached 4 accepting replicas "
              "(stats: %s)\nreplica0 log tail:\n%s"
              % (router.stats(), tail))
        sup.stop_all()
        router.close()
        return 10
    report = {}

    # -- leg a: SIGKILL 1 of 4 mid-decode, zero lost requests ----------
    print("chaos --fleet: kill leg (SIGKILL 1 of 4 mid-decode; streams "
          "must be byte-identical to an uninterrupted run, and the "
          "liveness rule must respawn the replica)")
    boot.start()
    prompts = mk_prompts(12)
    ref = collect(submit_batch(prompts, 48))
    if any(r is None or len(r) != 48 for r in ref):
        failures.append("kill leg: the uninterrupted reference run lost "
                        "requests (%s)"
                        % [None if r is None else len(r) for r in ref])
    st0 = router.stats()
    streams = submit_batch(prompts, 48)

    def pick_victim():
        # a replica with a request actively mid-stream (< half done):
        # killing it forces a redelivery whose recompute prefill folds
        # the already-streamed tokens
        with router._lock:
            for _rid, e in sorted(router._requests.items()):
                if (e.replica is not None and e.placed_tokens == 0
                        and 1 <= len(e.tokens) < e.max_new // 2):
                    return e.replica
        return None

    victim, deadline = None, time.time() + 120
    while victim is None and time.time() < deadline:
        victim = pick_victim()
        if victim is None:
            time.sleep(0.005)
    if victim is None:
        failures.append("kill leg: no replica was ever mid-stream — the "
                        "kill window never opened")
        collect(streams)
    else:
        vic_pid = sup.pid(victim)
        os.kill(int(vic_pid), 9)  # the chaos injection
        t_kill = time.time()
        got = collect(streams)
        lost = sum(1 for g in got if g is None)
        if lost:
            failures.append("kill leg: %d of %d requests lost after the "
                            "SIGKILL" % (lost, len(got)))
        mism = [i for i, (a, b) in enumerate(zip(ref, got))
                if b is not None and a != b]
        if mism:
            failures.append("kill leg: %d stream(s) diverged from the "
                            "uninterrupted run (e.g. request %d: %s vs "
                            "%s)" % (len(mism), mism[0], ref[mism[0]][:8],
                                     got[mism[0]][:8]))
        st1 = router.stats()
        if st1["evictions"] - st0["evictions"] < 1:
            failures.append("kill leg: no eviction recorded (counts %s "
                            "-> %s)" % (st0["evictions"], st1["evictions"]))
        if st1["redelivered"] - st0["redelivered"] < 1:
            failures.append("kill leg: no redelivery recorded — the kill "
                            "missed every in-flight request")
        if st1["completed"] - st0["completed"] != len(prompts):
            failures.append("kill leg: completed %d of %d"
                            % (st1["completed"] - st0["completed"],
                               len(prompts)))
        # the controller must respawn the SIGKILLed replica and the new
        # incarnation must re-register (alive AND accepting again)
        if not _wait_until(
                lambda: (router.stats()["replicas"].get(victim, {})
                         .get("alive")
                         and router.stats()["replicas"][victim]
                         ["accepting"]), 300):
            failures.append("kill leg: %s never came back after the "
                            "restart_replica respawn" % victim)
        recovery_wall = time.time() - t_kill
        report["kill"] = {
            "victim": victim, "lost": lost,
            "redelivered": st1["redelivered"] - st0["redelivered"],
            "evictions": st1["evictions"] - st0["evictions"],
            "respawn_wall_s": round(recovery_wall, 1),
        }
    boot.stop()

    # -- leg b: load ramp fires scale_up and the SLO recovers ----------
    print("chaos --fleet: ramp leg (admission backlog sustains "
          "pending>4; the scale_up rule must add replica4 and the "
          "backlog must drain — SLO recovery journaled)")
    ramp = mk_ctl("pending>4:for=2:action=scale_up:scope=serving:"
                  "cooldown=120")
    ramp.start()
    st0 = router.stats()
    burst = collect(submit_batch(mk_prompts(64), 32), timeout=420.0)
    if not _wait_until(lambda: accepting() >= 5, 300):
        failures.append("ramp leg: replica4 never became accepting "
                        "(stats: %s)" % router.stats())
    lost = sum(1 for g in burst if g is None)
    if lost:
        failures.append("ramp leg: %d of %d burst requests lost"
                        % (lost, len(burst)))
    time.sleep(1.5)  # >= 2 probe cycles AFTER the backlog drained: the
    ramp.stop()      # recovery record lands on a healthy probe
    report["ramp"] = {"burst": len(burst), "lost": lost,
                      "replicas_accepting": accepting()}

    # -- leg c: scale_down drains losslessly (retire, not death) -------
    print("chaos --fleet: drain leg (replicas>4 fires scale_down under "
          "live streams; the victim drains, leaves, retires — zero "
          "dropped streams, zero evictions)")
    st0 = router.stats()
    drain = mk_ctl("replicas>4:for=2:action=scale_down:scope=serving:"
                   "cooldown=120")
    d_prompts = mk_prompts(10)
    d_streams = submit_batch(d_prompts, 48)
    drain.start()
    d_got = collect(d_streams)
    if not _wait_until(lambda: "replica4" not in sup.names(), 240):
        failures.append("drain leg: replica4 was never retired from "
                        "supervision (names: %s)" % sup.names())
    drain.stop()
    lost = sum(1 for g in d_got if g is None)
    if lost:
        failures.append("drain leg: %d of %d in-flight streams dropped "
                        "by the drain" % (lost, len(d_got)))
    # byte-check: replay the same prompts on the settled 4-replica
    # fleet — identically seeded replicas must reproduce every stream
    d_ref = collect(submit_batch(d_prompts, 48))
    mism = [i for i, (a, b) in enumerate(zip(d_ref, d_got))
            if a is not None and b is not None and a != b]
    if mism:
        failures.append("drain leg: %d stream(s) served across the "
                        "drain diverge from the settled-fleet replay"
                        % len(mism))
    st1 = router.stats()
    if st1["left"] - st0["left"] < 1:
        failures.append("drain leg: no graceful leave recorded")
    if st1["evictions"] - st0["evictions"] != 0:
        failures.append("drain leg: the drain EVICTED instead of "
                        "draining (%d evictions)"
                        % (st1["evictions"] - st0["evictions"]))
    if router.stats()["replicas_accepting"] != 4:
        failures.append("drain leg: fleet settled at %d accepting "
                        "replicas, expected 4"
                        % router.stats()["replicas_accepting"])
    report["drain"] = {"streams": len(d_got), "lost": lost,
                       "left": st1["left"] - st0["left"]}

    # -- teardown + journal assertions (prove it from disk) ------------
    final = router.stats()
    sup.stop_all(wait=60.0)
    router.close()
    tel.flush(mark="exit")
    counters = fold_telemetry(journal)
    events = _journal_events(journal, prefix="fleet.")
    # one trace id per redelivery transaction: every fleet.redeliver
    # must share its trace with the re-placement's fleet.request.place
    place_traces = {e.get("trace") for e in events
                    if e["name"] == "fleet.request.place"}
    redelivers = [e for e in events if e["name"] == "fleet.redeliver"]
    if not redelivers:
        failures.append("journal: no fleet.redeliver events — the kill "
                        "leg left no redelivery evidence")
    for e in redelivers:
        if e.get("trace") is None or e["trace"] not in place_traces:
            failures.append("journal: redelivery of rid %s does not "
                            "share a trace with its re-placement"
                            % e.get("rid"))
    mxctl_events = _journal_events(journal)
    restarts = [e for e in mxctl_events if e["name"] == "mxctl.action"
                and e.get("action") == "restart_replica"
                and e.get("outcome") == "ok"]
    if report.get("kill") and not any(
            e.get("target") == report["kill"]["victim"]
            for e in restarts):
        failures.append("journal: no successful restart_replica on the "
                        "SIGKILLed %s" % report["kill"]["victim"])
    ups = [e for e in mxctl_events if e["name"] == "mxctl.action"
           and e.get("action") == "scale_up"
           and e.get("outcome") == "ok" and e.get("replica") == "replica4"]
    if not ups:
        failures.append("journal: no successful scale_up action spawning "
                        "replica4")
    downs = [e for e in mxctl_events if e["name"] == "mxctl.action"
             and e.get("action") == "scale_down"
             and e.get("outcome") == "ok"]
    if not any(e.get("victim") == "replica4" and e.get("rc") == 0
               for e in downs):
        failures.append("journal: no successful scale_down retiring "
                        "replica4 with rc=0 (%s)"
                        % [(e.get("victim"), e.get("rc")) for e in downs])
    # the ramp SLO proof: a recovery record for the pending rule on the
    # fleet target, with its restore duration
    recoveries = [e for e in mxctl_events if e["name"] == "mxctl.recovery"
                  and e.get("target") == "fleet"]
    if not any(e.get("action") == "scale_up" for e in recoveries):
        failures.append("journal: no mxctl.recovery for the scale_up "
                        "rule — the backlog SLO never provably recovered")
    for name, floor in (("fleet.requests_total", 98),
                        ("fleet.requests_completed", 98),
                        ("fleet.redeliveries_total", 1),
                        ("fleet.replica_evictions_total", 1),
                        ("fleet.replicas_registered_total", 6),
                        ("fleet.replicas_left_total", 1),
                        ("mxctl.actions_total", 3)):
        if counters.get(name, 0) < floor:
            failures.append("journal: counter %s=%s below the expected "
                            "floor %d"
                            % (name, counters.get(name, 0), floor))

    print("\n=== fleet survival report ===")
    if report.get("kill"):
        k = report["kill"]
        print("kill 1-of-4   : victim=%s lost=%d redelivered=%d "
              "evictions=%d respawn %.1fs"
              % (k["victim"], k["lost"], k["redelivered"],
                 k["evictions"], k["respawn_wall_s"]))
    print("load ramp     : %d requests, %d lost, fleet grew to %d "
          "accepting" % (report["ramp"]["burst"], report["ramp"]["lost"],
                         report["ramp"]["replicas_accepting"]))
    print("drain         : %d live streams across scale_down, %d lost, "
          "%d graceful leave(s)"
          % (report["drain"]["streams"], report["drain"]["lost"],
             report["drain"]["left"]))
    print("router counts : submitted=%d completed=%d redelivered=%d "
          "evictions=%d registered=%d left=%d rejected=%d"
          % (final["submitted"], final["completed"], final["redelivered"],
             final["evictions"], final["registered"], final["left"],
             final["rejected"]))
    if failures:
        print("\nRESULT: FAIL")
        for f in failures:
            print(" - %s" % f)
        return 10
    print("\nRESULT: SURVIVED — a SIGKILLed replica lost zero requests "
          "and zero tokens (byte-identical greedy streams vs the "
          "uninterrupted run) while the liveness rule respawned it; the "
          "admission backlog fired scale_up and provably recovered; "
          "scale_down drained a live replica losslessly into "
          "retirement — all asserted from the fleet.* / mxctl.* journal.")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="run the test suite under a seeded fault spec")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points", default="ckpt.write,rio.read",
                    help="comma-separated injection points")
    ap.add_argument("--mode", choices=["error", "delay"], default="error")
    ap.add_argument("--spec", default=None,
                    help="explicit MXNET_FAULT_SPEC (overrides --seed/--points)")
    ap.add_argument("--full", action="store_true",
                    help="run the whole tier-1 'not slow' suite, not the smoke set")
    ap.add_argument("--timeout", type=float, default=870.0,
                    help="hang budget in seconds (default: tier-1's 870)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic survival legs instead of the "
                         "fault-spec suite: SIGKILL 1 of 4 workers "
                         "mid-Module.fit (survivors finish), then "
                         "restart-and-rejoin; asserts exit codes, "
                         "accuracy tolerance, and journal counters")
    ap.add_argument("--guardian", action="store_true",
                    help="run the training-run-guardian survival legs: "
                         "grad.nan + loss.spike injected mid-Module.fit "
                         "with MXNET_GUARDIAN=1 (must survive within "
                         "accuracy tolerance, with skip/rollback journal "
                         "counters and nan-free checkpoints), the same "
                         "spec unguarded (negative control), and the "
                         "elastic 4-proc coordinated-skip leg")
    ap.add_argument("--quantized", action="store_true",
                    help="run the low-precision-comms survival legs "
                         "(ISSUE 7): elastic SIGKILL-1-of-4 with "
                         "MXNET_KV_QUANTIZE=int8 + MXNET_KV_SHARD_UPDATE=1 "
                         "reaching baseline-tolerance accuracy with "
                         "wire <= 0.30x logical bytes and ~1/world "
                         "per-rank optimizer state, plus a grad.nan leg "
                         "proving the guardian counts poisoned rounds "
                         "(and nothing on a clean quantized run)")
    ap.add_argument("--schedules", action="store_true",
                    help="run the mxrace thread-schedule survival legs "
                         "(ISSUE 9): the interleaving explorer must "
                         "find + replay both seeded races, then the "
                         "serving submit/cancel/step loop and the "
                         "elastic aggregator round protocol must "
                         "survive every explored schedule (MXRACE_"
                         "SCHEDULES overrides the per-leg budget)")
    ap.add_argument("--proto", action="store_true",
                    help="run the mxproto message-schedule survival "
                         "legs (ISSUE 11): the protocol simulator must "
                         "find + replay both seeded protocol mutants, "
                         "then the all-reduce, barrier and shard-update "
                         "workloads must survive every explored "
                         "delivery/loss/duplication/crash/restart "
                         "schedule (MXPROTO_SCHEDULES overrides the "
                         "per-leg budget)")
    ap.add_argument("--jit", action="store_true",
                    help="run the mxjit compile/transfer survival legs "
                         "(ISSUE 16): the runtime verifier must catch a "
                         "seeded recompile storm (naming the argument "
                         "that varied) and a seeded over-budget hot-"
                         "region D2H pull, a real serving decode loop "
                         "must run clean under the same budgets, and "
                         "mxlint --jit must report a clean repo; folds "
                         "the jit.* counters from the mxtel journal")
    ap.add_argument("--data", action="store_true",
                    help="run the data-service survival legs (ISSUE "
                         "14): SIGKILL 1 of 4 streaming consumers "
                         "mid-pass — the rejoined rank must resume at "
                         "the exact frontier (acked record stream "
                         "identical to an uninterrupted baseline, "
                         "shards rebalanced), then SIGTERM + restart "
                         "the coordinator — assignments restored from "
                         "the frontier snapshot, zero duplicate records")
    ap.add_argument("--controller", action="store_true",
                    help="run the mxctl closed-loop survival legs "
                         "(ISSUE 12): SIGKILL a serving replica -> the "
                         "controller restores capacity and the SLO "
                         "recovers; an injected training straggler is "
                         "attributed, evicted and replaced; a noisy-but-"
                         "healthy replica draws ZERO actions (hysteresis "
                         "negative control) — all asserted from the "
                         "mxctl.* decision journal")
    ap.add_argument("--wsync", action="store_true",
                    help="run the live weight-sync survival legs "
                         "(ISSUE 17): a loaded engine hot-swaps "
                         "published versions inside 1.10x its no-sync "
                         "p99 TTFT and byte-matches a cold engine from "
                         "the same version's checkpoint; a publisher "
                         "SIGKILLed mid-stream leaves the last complete "
                         "version live; a NaN-poisoned version is "
                         "refused (wsync.rejected_total >= 1); a "
                         "cratered spec-accept window fires the mxctl "
                         "rollback_weights rule — all asserted from "
                         "the wsync journal records and counters")
    ap.add_argument("--fleet", action="store_true",
                    help="run the mxfleet serving-fleet survival legs "
                         "(ISSUE 20): SIGKILL 1 of 4 replicas mid-decode "
                         "— zero lost requests, byte-identical greedy "
                         "streams vs an uninterrupted run, redeliveries "
                         "trace-paired with their re-placements, and the "
                         "liveness rule respawns the replica; a load "
                         "ramp fires the scale_up rule and the backlog "
                         "SLO provably recovers; scale_down drains a "
                         "replica losslessly into retirement — all "
                         "asserted from the fleet.*/mxctl.* journal")
    ap.add_argument("--controller-legs", default="all",
                    metavar="LEGS",
                    help="comma subset of the --controller legs: "
                         "serving,straggler,flap (default all)")
    ap.add_argument("tests", nargs="*",
                    help="explicit test paths (default: smoke set)")
    args = ap.parse_args(argv)

    if args.fleet:
        return run_fleet(args)
    if args.wsync:
        return run_wsync(args)
    if args.controller:
        return run_controller(args)
    if args.data:
        return run_data(args)
    if args.jit:
        return run_jit(args)
    if args.elastic:
        return run_elastic(args)
    if args.guardian:
        return run_guardian(args)
    if args.quantized:
        return run_quantized(args)
    if args.schedules:
        return run_schedules(args)
    if args.proto:
        return run_proto(args)

    points = [p.strip() for p in args.points.split(",") if p.strip()]
    spec = args.spec or build_spec(args.seed, points, args.mode)

    targets = args.tests or (["tests/"] if args.full else SMOKE_TESTS)
    targets = [t for t in targets
               if os.path.exists(os.path.join(REPO, t)) or args.tests]

    scratch = tempfile.mkdtemp(prefix="mxtpu-chaos-")
    journal = os.path.join(scratch, "chaos-journal.jsonl")
    env = dict(os.environ)
    env.update({
        "MXNET_FAULT_SPEC": spec,
        "JAX_PLATFORMS": "cpu",
        "TMPDIR": scratch,  # checkpoint/tmp artifacts land here for the scan
        # mxtel on: the journal's fault/retry/watchdog counters prove
        # which resilience paths the run exercised (folded in below)
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_JOURNAL": journal,
    })

    cmd = [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
           "--continue-on-collection-errors", "-p", "no:cacheprovider",
           "-p", "no:xdist", "-p", "no:randomly"] + targets
    print("chaos: seed=%d spec=%r" % (args.seed, spec))
    print("chaos: %s" % " ".join(cmd))
    sys.stdout.flush()

    t0 = time.time()
    hung = False
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, timeout=args.timeout,
                              capture_output=True, text=True)
        out, rc = proc.stdout + proc.stderr, proc.returncode
    except subprocess.TimeoutExpired as exc:
        out = ((exc.stdout or b"").decode("utf-8", "replace")
               if isinstance(exc.stdout, bytes) else (exc.stdout or ""))
        rc, hung = -1, True
    wall = time.time() - t0

    m = re.findall(r"(\d+) passed", out)
    passed = int(m[-1]) if m else 0
    m = re.findall(r"(\d+) failed", out)
    failed = int(m[-1]) if m else 0
    m = re.findall(r"(\d+) error", out)
    errors = int(m[-1]) if m else 0
    injected = out.count("injected fault at point")
    torn = scan_torn_params(scratch)
    counters = fold_telemetry(journal)

    print("\n=== chaos survival report ===")
    print("spec            : %s" % spec)
    print("wall time       : %.1fs (budget %.0fs)" % (wall, args.timeout))
    print("hang            : %s" % ("YES — run exceeded budget" if hung
                                    else "no"))
    print("passed/failed   : %d passed, %d failed, %d errors"
          % (passed, failed, errors))
    print("injected faults : %d surfaced in output" % injected)
    print("torn .params    : %d %s" % (len(torn), torn if torn else ""))
    print("-- resilience counters (mxtel journal) --")
    if counters:
        fired = {k: v for k, v in sorted(counters.items())
                 if k.startswith("faults.fired.")}
        for k, v in fired.items():
            print("%-16s: %d fires at %s"
                  % ("fault fired", v, k[len("faults.fired."):]))
        if not fired:
            print("fault fires     : 0 (no armed point hit)")
        print("retries         : %d healed transients (retry.retries_total)"
              % counters.get("retry.retries_total", 0))
        print("watchdog fires  : %d (engine.watchdog_fires_total)"
              % counters.get("engine.watchdog_fires_total", 0))
        print("records skipped : %d (io.records_skipped_total)"
              % counters.get("io.records_skipped_total", 0))
    else:
        print("(no journal counters — telemetry produced no snapshots)")
    if hung:
        print("\nRESULT: FAIL — the suite hung under faults (a watchdog "
              "or deadline is missing). Last output:\n%s" % out[-2000:])
        return 2
    if torn:
        print("\nRESULT: FAIL — in-place-corrupted checkpoint file(s): "
              "atomic-rename discipline violated.")
        return 3
    print("\nRESULT: SURVIVED — completed with zero hangs and zero "
          "in-place-corrupted checkpoints. "
          "Failures above are injected casualties; rerun with the same "
          "--seed to reproduce them.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
