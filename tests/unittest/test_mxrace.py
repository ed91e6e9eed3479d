"""mxrace concurrency-analysis tests (lock_lint + schedule explorer +
engine_verify lock events).

Covers the tentpole end to end: every detector catches its seeded-bad
fixture at the right severity, the repo's own 14 lock-using modules
lint clean (the clean-repo gate CI relies on), runtime lock traces
catch observed inversions and cross-check against the static graph,
and the interleaving explorer deterministically finds seeded races,
replays them from the printed seed, detects deadlocks, and certifies
the serving + elastic-aggregator schedules race-free.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.analysis import engine_verify, lock_lint
from mxnet_tpu.analysis import schedule as msched
from mxnet_tpu.analysis.cli import main as mxlint_main

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "tests", "fixtures")


def fixture(name):
    return os.path.join(FIXTURES, name + ".py")


def codes(findings):
    return [f.code for f in findings]


def by_sev(findings, sev):
    return [f for f in findings if f.severity == sev]


# -- lock-discipline lint: seeded-bad fixtures ---------------------------------

def test_inversion_fixture_two_cycles_right_severity():
    fs = lock_lint.lint_file(fixture("mxrace_bad_inversion"))
    assert codes(fs) == ["lock-inversion", "lock-inversion"]
    assert all(f.severity == "error" for f in fs)
    wheres = " | ".join(f.where for f in fs)
    # the module-level A<->B cycle and the interprocedural Teller cycle
    assert ":A" in wheres and ":B" in wheres
    assert "Teller._book" in wheres and "Teller._till" in wheres
    # C is consistently ordered and must not appear in any cycle
    assert ":C" not in wheres


def test_blocking_fixture_every_class_flagged_once():
    fs = lock_lint.lint_file(fixture("mxrace_bad_blocking"))
    assert all(f.code == "blocking-under-lock" for f in fs)
    assert all(f.severity == "warning" for f in fs)
    msgs = " ".join(f.message for f in fs)
    for op in ("time.sleep", "pickle encode", "socket recv",
               "device sync", "device->host copy"):
        assert op in msgs, "missing blocking class %r" % op
    # 5 direct + 1 interprocedural (publish -> _ship -> pickle);
    # the pragma'd sleep and the Condition.wait are NOT flagged
    assert len(fs) == 6
    assert "call into Server._ship" in msgs


def test_unguarded_fixture_write_warns_read_infos():
    fs = lock_lint.lint_file(fixture("mxrace_bad_unguarded"))
    assert codes(by_sev(fs, "warning")) == ["unguarded-field"]
    assert codes(by_sev(fs, "info")) == ["unguarded-field"]
    assert "Meter.reset" in by_sev(fs, "warning")[0].message
    assert "Meter.peek" in by_sev(fs, "info")[0].message
    # __init__, the _locked helper, the locked-context-only helper and
    # the pragma'd read contribute nothing
    assert len(fs) == 2


def test_cv_fixture_three_misuses():
    fs = lock_lint.lint_file(fixture("mxrace_bad_cv"))
    got = {(f.code, f.severity) for f in fs}
    assert got == {("cv-wait-no-loop", "error"),
                   ("cv-notify-unlocked", "error"),
                   ("cv-wait-timeout", "warning")}
    [t] = [f for f in fs if f.code == "cv-wait-timeout"]
    assert "35" in t.message and "30" in t.message


def test_pragma_suppresses_lock_findings():
    src = (
        "import threading, time\n"
        "L = threading.Lock()\n"
        "def f():\n"
        "    with L:\n"
        "        time.sleep(1)\n")
    assert codes(lock_lint.lint_source(src)) == ["blocking-under-lock"]
    src2 = src.replace("time.sleep(1)",
                       "time.sleep(1)  # mxlint: disable")
    assert lock_lint.lint_source(src2) == []


def test_droplock_idiom_not_flagged():
    """release() before the blocking op and re-acquire() in finally —
    the PR 7 encode-outside-the-lock pattern — is clean; the SAME op
    without the release is flagged."""
    src = (
        "import threading, pickle\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def encode(self, v):\n"
        "        self._lock.release()\n"
        "        try:\n"
        "            p = pickle.dumps(v)\n"
        "        finally:\n"
        "            self._lock.acquire()\n"
        "        return p\n")
    assert lock_lint.lint_source(src) == []
    held = (
        "import threading, pickle\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def encode(self, v):\n"
        "        with self._lock:\n"
        "            return pickle.dumps(v)\n")
    assert codes(lock_lint.lint_source(held)) == ["blocking-under-lock"]


def test_condition_aliases_its_lock():
    """Holding the Condition built over a lock IS holding the lock:
    notify under `with cond:` is clean, and no false inversion edge
    appears between the condition and its lock."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cond = threading.Condition(self._lock)\n"
        "        self.x = 0\n"
        "    def poke(self):\n"
        "        with self._cond:\n"
        "            self.x += 1\n"
        "            self._cond.notify_all()\n"
        "    def poke2(self):\n"
        "        with self._lock:\n"
        "            self.x += 1\n")
    assert lock_lint.lint_source(src) == []


def test_traced_lock_wrapper_still_registers_as_lock():
    """self._lock = maybe_trace_lock(threading.RLock(), ...) — the
    subsystem wiring idiom — must still be seen as a lock."""
    src = (
        "import threading, time\n"
        "from mxnet_tpu.analysis.engine_verify import maybe_trace_lock\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = maybe_trace_lock(threading.RLock(), 'x')\n"
        "    def nap(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n")
    assert codes(lock_lint.lint_source(src)) == ["blocking-under-lock"]


# -- clean-repo gates ----------------------------------------------------------

def test_repo_lock_lint_clean():
    """The audit-and-fix sweep contract: zero errors and zero warnings
    over every module in the package (info-level deliberate racy reads
    are allowed — that is what the severity tier is for)."""
    fs = lock_lint.lint_package()
    bad = [f for f in fs if f.severity in ("error", "warning")]
    assert bad == [], "\n".join(str(f) for f in bad)


def test_cli_locks_clean_on_repo_and_nonzero_on_fixtures(capsys):
    assert mxlint_main(["--locks"]) == 0
    assert mxlint_main(["--locks", fixture("mxrace_bad_inversion")]) == 1
    assert mxlint_main(["--locks", fixture("mxrace_bad_blocking"),
                        "--fail-on", "warning"]) == 1
    # blocking findings are warnings: default --fail-on error passes
    assert mxlint_main(["--locks", fixture("mxrace_bad_blocking")]) == 0
    out = capsys.readouterr().out
    assert "lock-inversion" in out and "blocking-under-lock" in out


def test_cli_locks_json(capsys):
    assert mxlint_main(["--locks", fixture("mxrace_bad_cv"),
                        "--json"]) == 1
    recs = json.loads(capsys.readouterr().out)
    assert {r["code"] for r in recs} == {
        "cv-wait-no-loop", "cv-notify-unlocked", "cv-wait-timeout"}
    assert all(r["pass"] == "locks" for r in recs)


# -- engine_verify: runtime lock events ----------------------------------------

def test_observed_inversion_is_a_lock_order_error():
    t = engine_verify.EngineTrace()
    t.lock_acquire("A", thread=1)
    t.lock_acquire("B", thread=1)   # A -> B
    t.lock_release("B", thread=1)
    t.lock_release("A", thread=1)
    t.lock_acquire("B", thread=2)
    t.lock_acquire("A", thread=2)   # B -> A: inversion
    fs = engine_verify.verify(t)
    assert codes(fs) == ["lock-order"]
    assert fs[0].severity == "error"
    assert "A" in fs[0].where and "B" in fs[0].where


def test_consistent_order_and_reentry_are_clean():
    t = engine_verify.EngineTrace()
    for tid in (1, 2):
        t.lock_acquire("A", thread=tid)
        t.lock_acquire("A", thread=tid)   # RLock re-entry: no self edge
        t.lock_acquire("B", thread=tid)
        t.lock_release("B", thread=tid)
        t.lock_release("A", thread=tid)
        t.lock_release("A", thread=tid)
    assert engine_verify.verify(t) == []
    assert ("A", "B") in t.lock_edges and ("B", "A") not in t.lock_edges


def test_lock_events_roundtrip_json():
    t = engine_verify.EngineTrace()
    t.lock_acquire("A", thread=1)
    t.lock_acquire("B", thread=1)
    t.lock_acquire("B", thread=2)
    t.lock_acquire("A", thread=2)
    t2 = engine_verify.EngineTrace.from_json(t.to_json())
    assert t2.lock_edges == t.lock_edges
    assert codes(engine_verify.verify(t2)) == ["lock-order"]


def test_traced_lock_records_into_ambient_trace():
    import threading

    trace = engine_verify.EngineTrace()
    prev = engine_verify.set_ambient_trace(trace)
    try:
        a = engine_verify.TracedLock(threading.Lock(), "outer")
        b = engine_verify.TracedLock(threading.RLock(), "inner")
        with a:
            with b:
                pass
        assert ("outer", "inner") in trace.lock_edges
        # a Condition over a traced RLock works end to end
        cond = threading.Condition(b)
        with cond:
            cond.notify_all()
    finally:
        engine_verify.set_ambient_trace(prev)


class _LocksWhenFinalized:
    """Garbage whose finalizer takes a lock, as Engine.__del__ does."""

    def __init__(self, lock):
        self.lock = lock

    def __del__(self):
        with self.lock:
            pass


@pytest.mark.parametrize("garbage", ["engine", "traced_lock"])
def test_finalizer_inside_the_recorder_does_not_deadlock(garbage,
                                                         monkeypatch):
    """A finalizer runs on whatever thread allocates next, so also on
    the thread that is inside the recorder's critical section. Neither
    an Engine that dies there nor a finalizer that takes a TracedLock
    there may block on the trace's own lock, and no record may share or
    overtake a seq."""
    import gc
    import threading
    import weakref

    monkeypatch.setenv("MXNET_ENGINE_VERIFY", "1")
    trace = engine_verify.EngineTrace()
    next_seq, collected = trace._next_seq, []

    def next_seq_after_a_collection():
        if not collected:   # once, and inside the critical section
            collected.append(gc.collect())
        return next_seq()

    trace._next_seq = next_seq_after_a_collection
    prev = engine_verify.set_ambient_trace(trace)
    was_enabled = gc.isenabled()
    gc.disable()    # the garbage must last until the collection above
    try:
        if garbage == "engine":
            from mxnet_tpu.engine import Engine

            junk = Engine()
            assert isinstance(junk._live_lock, engine_verify.TracedLock)
        else:
            junk = _LocksWhenFinalized(
                engine_verify.TracedLock(threading.Lock(), "finalizer"))
        junk.cycle = junk   # only the cyclic collector can free it
        gone = weakref.ref(junk)
        del junk
        t = threading.Thread(target=trace.lock_acquire, args=("outer",),
                             daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), \
            "the recorder waits for a lock its own thread holds"
    finally:
        if was_enabled:
            gc.enable()
        engine_verify.set_ambient_trace(prev)
    assert gone() is None   # finalized, and inside the critical section
    mine = [e for e in trace.lock_events
            if e[2] in ("outer", "finalizer", "engine.Engine._live_lock")]
    seqs = [e[0] for e in trace.lock_events]
    assert seqs == sorted(set(seqs))
    # the engine's finalizer is no program action and records nothing;
    # a finalizer that does record lands whole, after the record it
    # interrupted
    assert [e[2:] for e in mine] == {
        "engine": [("outer", "acquire")],
        "traced_lock": [("outer", "acquire"), ("finalizer", "acquire"),
                        ("finalizer", "release")],
    }[garbage]


def test_maybe_trace_lock_env_gating(monkeypatch):
    import threading

    monkeypatch.setenv("MXNET_ENGINE_VERIFY", "0")
    raw = threading.Lock()
    assert engine_verify.maybe_trace_lock(raw, "x") is raw
    monkeypatch.setenv("MXNET_ENGINE_VERIFY", "1")
    wrapped = engine_verify.maybe_trace_lock(raw, "x")
    assert isinstance(wrapped, engine_verify.TracedLock)


def test_cross_check_static_vs_observed():
    static = {("m:S._a", "m:S._b"): [("m.py", 10, "S.f")]}
    # same order observed: clean
    assert lock_lint.cross_check(static, {("S._a", "S._b"): 5}) == []
    # observed the REVERSE of a static edge: error
    fs = lock_lint.cross_check(static, {("S._b", "S._a"): 5})
    assert codes(fs) == ["lock-order"] and fs[0].severity == "error"
    # an edge the lint never saw: blind-spot warning
    fs = lock_lint.cross_check(static, {("S._x", "S._y"): 5})
    assert codes(fs) == ["lock-order"] and fs[0].severity == "warning"


def test_live_subsystem_locks_cross_check_against_static_graph():
    """Drive the real serving engine under a fresh ambient trace; every
    observed acquisition order must be consistent with (or at least not
    invert) the static lock graph of the serving module."""
    trace = engine_verify.EngineTrace()
    prev = engine_verify.set_ambient_trace(trace)
    try:
        eng = msched._stub_serving_engine()
        [tokens] = eng.generate([[1, 2, 3]], max_new_tokens=2)
        assert len(tokens) == 2
    finally:
        engine_verify.set_ambient_trace(prev)
    observed = engine_verify.observed_lock_edges(trace)
    assert observed, "no lock events recorded — the serving engine's " \
        "locks are not TracedLock-wrapped under MXNET_ENGINE_VERIFY"
    # no observed inversion at all
    assert [f for f in engine_verify.verify(trace)
            if f.code == "lock-order"] == []
    static = lock_lint.build_lock_graph(
        os.path.join(ROOT, "mxnet_tpu", "serving"))
    errors = [f for f in lock_lint.cross_check(static, observed)
              if f.severity == "error"]
    assert errors == [], "\n".join(str(f) for f in errors)


# -- schedule explorer ---------------------------------------------------------

def test_explorer_finds_seeded_race_and_replays():
    """The acceptance contract: the seeded race is found in <= N
    schedules, the printed seed replays it, and the fixed (locked)
    version survives the same budget."""
    wl = msched.racy_counter_workload(locked=False)
    r = msched.explore(wl, schedules=25, seed=0)
    assert not r.ok, "seeded race not found in 25 schedules"
    f = r.first_failure()
    assert f.kind == "check" and "lost update" in f.message
    assert "replay" in f.replay_hint()
    rep = msched.replay(wl, seed=0, index=f.index)
    assert rep is not None and "lost update" in rep.message
    fixed = msched.explore(msched.racy_counter_workload(locked=True),
                           schedules=25, seed=0)
    assert fixed.ok, fixed.first_failure()


def test_explorer_dfs_strategy_finds_race_and_replays_from_choices():
    wl = msched.racy_counter_workload(locked=False)
    r = msched.explore(wl, schedules=40, seed=0, strategy="dfs",
                       max_switches=2)
    assert not r.ok and "lost update" in r.first_failure().message
    f = r.first_failure()
    # DFS schedules are defined by their choice prefix — the hint must
    # carry the choices, and replaying them must reproduce
    assert "choices=" in f.replay_hint()
    rep = msched.replay(wl, seed=0, index=f.index, choices=f.choices)
    assert rep is not None and "lost update" in rep.message


def test_coop_lock_timed_acquire_can_time_out():
    """acquire(timeout=...) must be able to RETURN FALSE under some
    schedule (the scheduler firing the timeout) — the timeout-fallback
    path is explorable, not dead code."""
    seen = []

    def wl(ctl):
        lk = ctl.lock("L")

        def holder():
            with lk:
                for _ in range(6):
                    ctl.checkpoint()

        def contender():
            got = lk.acquire(timeout=0.01)
            if got:
                lk.release()
            seen.append(got)

        return [holder, contender], None

    wl.__name__ = "timed_acquire"
    r = msched.explore(wl, schedules=30, seed=0, stop_on_first=True)
    assert r.ok, r.first_failure()
    assert False in seen, "no schedule ever fired the acquire timeout"
    assert True in seen, "no schedule ever granted the timed acquire"


def test_explorer_detects_ab_ba_deadlock():
    def make(ctl):
        a, b = ctl.lock("A"), ctl.lock("B")

        def t1():
            with a:
                ctl.checkpoint()
                with b:
                    pass

        def t2():
            with b:
                ctl.checkpoint()
                with a:
                    pass

        return [t1, t2], None

    make.__name__ = "ab_ba"
    r = msched.explore(make, schedules=40, seed=0)
    assert not r.ok
    f = r.first_failure()
    assert f.kind == "deadlock"
    assert "A" in f.message and "B" in f.message


def test_explorer_detects_self_deadlock_instead_of_hanging():
    def make(ctl):
        a = ctl.lock("A")

        def t():
            with a:
                with a:   # non-reentrant: classic self-deadlock
                    pass

        return [t], None

    make.__name__ = "self_deadlock"
    r = msched.explore(make, schedules=1, seed=0)
    assert not r.ok and r.first_failure().kind == "deadlock"


def test_explorer_condition_timeout_path_is_explored():
    """A waiter with a timeout and no notifier must terminate via the
    scheduler firing the timeout — never a deadlock report."""
    def make(ctl):
        lock = ctl.lock("L")
        cond = ctl.condition(lock, "C")
        seen = []

        def waiter():
            with cond:
                got = True
                while not seen and got:
                    got = cond.wait(timeout=0.01)
            seen.append("done")

        return [waiter], None

    make.__name__ = "timed_wait"
    r = msched.explore(make, schedules=5, seed=0)
    assert r.ok, r.first_failure()


def test_instrument_patches_threading_primitives():
    import threading as _th

    sched = msched._Scheduler(lambda en, s: en[0], 1000)
    ctl = msched.Controller(sched)
    with ctl.instrument():
        lk = _th.Lock()
        rl = _th.RLock()
        cv = _th.Condition()
        assert isinstance(lk, msched._CoopLock)
        assert isinstance(rl, msched._CoopRLock)
        assert isinstance(cv, msched._CoopCondition)
    assert not isinstance(_th.Lock(), msched._CoopLock)  # restored


def test_explorer_aggregator_race_found_and_locked_survives():
    """The elastic Aggregator round protocol: deprived of the
    coordinator's lock (line-granularity preemption inside
    elastic/server.py) the explorer reproduces a real race — double
    round completion — and the locked discipline survives."""
    r = msched.explore(msched.aggregator_workload(locked=False),
                       schedules=30, seed=1,
                       trace_files=msched.AGGREGATOR_TRACE_FILES())
    assert not r.ok, "unlocked aggregator race not found"
    assert r.first_failure().kind in ("exception", "check")
    r2 = msched.explore(msched.aggregator_workload(locked=True),
                        schedules=15, seed=1)
    assert r2.ok, r2.first_failure()


def test_explorer_serving_submit_cancel_step_survives():
    r = msched.explore(msched.serving_workload(), schedules=10, seed=2)
    assert r.ok, r.first_failure()


def test_survival_suite_smoke():
    fs, lines = msched.survival_suite(seed=0, schedules=6)
    assert fs == [], "\n".join(str(f) for f in fs)
    assert any("race found" in ln for ln in lines)
    assert any("survived" in ln for ln in lines)


def test_cli_schedules_leg(capsys):
    assert mxlint_main(["--schedules", "--schedule-count", "6",
                        "--schedule-seed", "4"]) == 0
    err = capsys.readouterr().err
    assert "race found" in err and "survived" in err


# -- CLI end-to-end ------------------------------------------------------------

def test_cli_end_to_end_subprocess_locks():
    """The checkout-tree launcher running the concurrency lint over the
    package — the CI gate invocation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         "--locks"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "0 error(s), 0 warning(s)" in res.stdout
