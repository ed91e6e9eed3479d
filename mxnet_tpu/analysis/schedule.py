"""mxrace schedule explorer: deterministic interleaving exploration for
the threaded runtime.

Chaos testing for thread schedules. The lock lint (lock_lint.py) proves
discipline statically; this module attacks the residue dynamically: a
cooperative scheduler serializes a multi-threaded workload so that
exactly ONE controlled thread runs at a time, with scheduling decisions
taken at every preemption point — lock/condition operations, explicit
``ctl.checkpoint()`` calls, and (optionally) every traced source line
of chosen files. The decision sequence is driven either by a seeded
random walk or by bounded context-switch exhaustion (CHESS-style DFS),
so every explored interleaving is **replayable from its seed**: an
assertion, exception, or deadlock prints the exact schedule that
produced it, and :func:`replay` runs that one schedule again.

Controlled primitives are *logical* locks layered on the serialization:
a controlled thread that would block reports BLOCKED to the scheduler
(which then runs someone else) instead of blocking the OS thread — so
the explorer also detects real deadlocks (every live thread blocked,
none timed) and self-deadlocks (non-reentrant lock re-acquired),
reporting the cycle instead of hanging.

Two ways to get controlled primitives into a workload:

- surgical: build the system under test normally, then rebind its lock
  attributes to ``ctl.lock()/ctl.rlock()/ctl.condition()`` (what the
  serving-engine workload does);
- wholesale: construct inside ``with ctl.instrument():`` — the context
  manager patches ``threading.Lock/RLock/Condition/Thread`` so every
  primitive created in the window is cooperative (``queue.Queue`` built
  there becomes cooperative too).

Built-in workloads (the mxlint --schedules / chaos --schedules legs):

- :func:`racy_counter_workload` — a seeded lost-update race (negative
  control: the explorer must FIND it) and its locked fix;
- :func:`serving_workload` — the serving engine's submit/cancel/step
  loop (real Engine/Scheduler/StreamHandle code, stubbed compute
  kernel) driven by concurrent client + driver threads;
- :func:`aggregator_workload` — the elastic Aggregator round protocol
  under the coordinator's lock (and, as a seeded race, without it,
  with line-granularity preemption inside elastic/server.py).

Env knobs (docs/env_vars.md): ``MXRACE_SCHEDULES`` (default schedule
budget), ``MXRACE_SEED`` (base seed) — read by the CLI legs, not here.
"""
from __future__ import annotations

import os
import random
import sys
import threading
import traceback as _tb

__all__ = ["Controller", "Explorer", "ExploreResult", "FailureReport",
           "explore", "replay", "racy_counter_workload",
           "serving_workload", "aggregator_workload",
           "wsync_swap_workload", "fleet_router_workload"]

_GATE_TIMEOUT = 120.0     # guard: a wedged scheduler raises, never hangs CI
_THIS_FILE = os.path.abspath(__file__)

RUNNABLE, BLOCKED, DONE = "runnable", "blocked", "done"


class _Abort(BaseException):
    """Unwinds a controlled thread when its schedule is abandoned.
    BaseException so ``except Exception`` in workload code can't eat it."""


class SchedulerWedged(RuntimeError):
    """A gate wait exceeded the guard timeout — a bug in the harness or
    a controlled thread physically blocked outside the explorer's
    knowledge (e.g. real I/O on an uncontrolled primitive)."""


class _ThreadCtl:
    __slots__ = ("tid", "name", "status", "gate", "parked", "waiting_on",
                 "timed", "woken_by_timeout", "thread", "started")

    def __init__(self, tid, name):
        self.tid = tid
        self.name = name
        self.status = RUNNABLE
        self.gate = threading.Event()
        self.parked = False
        self.waiting_on = None     # _CoopLock | _CoopCondition | None
        self.timed = False         # blocked with a timeout (wakeable)
        self.woken_by_timeout = False
        self.thread = None
        self.started = False


class _Scheduler:
    """Token-passing serializer: one controlled thread runs at a time;
    every preemption point parks the thread and hands the token back."""

    def __init__(self, chooser, max_steps, trace_files=()):
        self.chooser = chooser
        self.max_steps = int(max_steps)
        self.trace_files = tuple(os.path.abspath(f) for f in trace_files)
        self.threads = []          # [_ThreadCtl]
        self._tls = threading.local()
        self._sched_gate = threading.Event()
        self._reg_lock = threading.Lock()
        self.active = False
        self.aborting = False
        self.steps = 0
        self.choices = []          # [tid] — the replayable schedule
        self.failure = None        # (kind, message, traceback-or-None)

    # -- registration ----------------------------------------------------------
    def current(self):
        return getattr(self._tls, "ctl", None)

    def spawn(self, fn, name=None):
        """Register + start a controlled thread running ``fn`` (parked
        until scheduled). Safe mid-run (dynamic registration: a
        subsystem may spawn its own workers)."""
        with self._reg_lock:
            ctl = _ThreadCtl(len(self.threads), name or "t%d"
                             % len(self.threads))
            self.threads.append(ctl)

        def body():
            self._tls.ctl = ctl
            tracer = self._make_tracer() if self.trace_files else None
            try:
                self._park(ctl)          # wait for the first grant
                if tracer:
                    sys.settrace(tracer)
                fn()
            except _Abort:
                pass
            except BaseException as e:  # noqa: BLE001 — the product
                self._record_failure(
                    "exception",
                    "%s in thread %r: %s" % (type(e).__name__, ctl.name, e),
                    "".join(_tb.format_exception(type(e), e,
                                                 e.__traceback__)))
            finally:
                if tracer:
                    sys.settrace(None)
                ctl.status = DONE
                ctl.parked = True
                self._sched_gate.set()

        ctl.thread = threading.Thread(target=body, name="mxrace-" + ctl.name,
                                      daemon=True)
        ctl.started = True
        ctl.thread.start()
        return ctl

    def _make_tracer(self):
        sched = self

        def tracer(frame, event, arg):
            if event != "call":
                return None
            fname = frame.f_code.co_filename
            if fname == _THIS_FILE:
                return None
            if not any(os.path.abspath(fname) == f for f in sched.trace_files):
                return None

            def line_tracer(fr, ev, a):
                if ev == "line" and not sched.aborting:
                    sched.preempt()
                return line_tracer

            return line_tracer

        return tracer

    # -- controlled-thread side ------------------------------------------------
    def _park(self, ctl):
        ctl.parked = True
        self._sched_gate.set()
        if not ctl.gate.wait(_GATE_TIMEOUT):
            raise SchedulerWedged("thread %r never re-granted" % ctl.name)
        ctl.gate.clear()
        ctl.parked = False
        if self.aborting:
            raise _Abort()

    def preempt(self):
        """A scheduling point: park and wait to be granted again."""
        ctl = self.current()
        if ctl is None or not self.active or self.aborting:
            return
        ctl.status = RUNNABLE
        self._park(ctl)

    def block_on(self, resource, timed=False):
        """Park as BLOCKED on ``resource`` until someone unblocks us (or
        the scheduler fires our timeout). Returns True when woken by
        the resource, False on a timeout wake."""
        ctl = self.current()
        if ctl is None or not self.active or self.aborting:
            return True
        ctl.status = BLOCKED
        ctl.waiting_on = resource
        ctl.timed = timed
        ctl.woken_by_timeout = False
        self._park(ctl)
        ctl.waiting_on = None
        ctl.timed = False
        return not ctl.woken_by_timeout

    def unblock(self, ctl, by_timeout=False):
        if ctl.status == BLOCKED:
            ctl.status = RUNNABLE
            ctl.woken_by_timeout = by_timeout
            ctl.waiting_on = None

    def _record_failure(self, kind, message, tb=None):
        if self.failure is None:
            self.failure = (kind, message, tb)

    # -- driver side -----------------------------------------------------------
    def _snapshot(self):
        """Stable view of the thread list: spawn() appends from
        controlled threads (dynamic registration) while the driver
        iterates."""
        with self._reg_lock:
            return list(self.threads)

    def _all_parked(self):
        return all(t.parked or t.status == DONE for t in self._snapshot())

    def _wait_quiescent(self):
        deadline = _GATE_TIMEOUT
        while True:
            if not self._sched_gate.wait(deadline):
                raise SchedulerWedged(
                    "controlled threads never quiesced (running: %s)"
                    % [t.name for t in self._snapshot() if not t.parked
                       and t.status != DONE])
            self._sched_gate.clear()
            if self._all_parked():
                return

    def run(self):
        """Drive scheduling decisions until every thread is DONE (or a
        failure aborts the schedule). Returns the recorded choices."""
        self.active = True
        try:
            while True:
                self._wait_quiescent()
                live = [t for t in self._snapshot() if t.status != DONE]
                if not live or self.failure is not None:
                    break
                enabled = [t for t in live
                           if t.status == RUNNABLE
                           or (t.status == BLOCKED and t.timed)]
                if not enabled:
                    self._record_failure(
                        "deadlock",
                        "deadlock: every live thread is blocked — "
                        + "; ".join(
                            "%s waits on %s" % (t.name,
                                                getattr(t.waiting_on,
                                                        "name", t.waiting_on))
                            for t in live))
                    break
                if self.steps >= self.max_steps:
                    self._record_failure(
                        "step-budget",
                        "schedule exceeded max_steps=%d (livelock or an "
                        "undersized budget)" % self.max_steps)
                    break
                chosen = self.chooser(enabled, self)
                self.steps += 1
                self.choices.append(chosen.tid)
                if chosen.status == BLOCKED:  # timed wake (timeout fires)
                    src = chosen.waiting_on
                    if src is not None and hasattr(src, "_drop_waiter"):
                        src._drop_waiter(chosen)
                    self.unblock(chosen, by_timeout=True)
                chosen.gate.set()
        finally:
            self._abort_all()
            self.active = False
        return self.choices

    def _abort_all(self):
        self.aborting = True
        deadline = _GATE_TIMEOUT
        for _ in range(10000):
            live = [t for t in self._snapshot() if t.status != DONE]
            if not live:
                return
            for t in live:
                t.gate.set()
            self._sched_gate.wait(0.01)
            self._sched_gate.clear()
        for t in self._snapshot():
            if t.status != DONE and t.thread is not None:
                t.thread.join(deadline / 100.0)


# -- cooperative primitives ----------------------------------------------------

class _CoopLock:
    """Logical mutual exclusion on top of the serialization."""

    reentrant = False

    def __init__(self, sched, name):
        self._sched = sched
        self.name = name
        self._owner = None       # _ThreadCtl
        self._count = 0
        self._waiters = []       # [_ThreadCtl]

    def acquire(self, blocking=True, timeout=-1):
        sched = self._sched
        ctl = sched.current()
        if ctl is None or not sched.active or sched.aborting:
            return True  # outside a run: vacuous (single driver thread)
        sched.preempt()  # decision point before the acquire
        timed = blocking and timeout is not None and timeout >= 0
        while self._owner is not None and self._owner is not ctl:
            if not blocking:
                return False
            self._waiters.append(ctl)
            # block_on's return value is the wake verdict; the waiter
            # list may already be cleaned by the scheduler's timed-wake
            # path (_drop_waiter), so it cannot carry that signal
            notified = sched.block_on(self, timed=timed)
            if ctl in self._waiters:
                self._waiters.remove(ctl)
            if timed and not notified:
                return False  # the scheduler fired the timeout
        if self._owner is ctl and not self.reentrant:
            # self-deadlock on a non-reentrant lock: report, don't hang
            self._waiters.append(ctl)
            sched.block_on(self)
            return True  # only reachable via abort-unwind
        self._owner = ctl
        self._count += 1
        return True

    def release(self):
        sched = self._sched
        ctl = sched.current()
        if ctl is None or not sched.active or sched.aborting:
            return
        if self._owner is not ctl:
            raise RuntimeError("release of %s by non-owner %s"
                               % (self.name, ctl.name))
        self._count -= 1
        if self._count == 0:
            self._owner = None
            for w in self._waiters:
                sched.unblock(w)
        sched.preempt()  # decision point after the release

    def _drop_waiter(self, ctl):
        if ctl in self._waiters:
            self._waiters.remove(ctl)

    def locked(self):
        return self._owner is not None

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.release()
        return False

    # threading.Condition private protocol (so a REAL threading.Condition
    # built over a coop lock still works, and vice versa)
    def _release_save(self):
        count, self._count = self._count, 0
        owner, self._owner = self._owner, None
        sched = self._sched
        if sched.active and not sched.aborting:
            for w in self._waiters:
                sched.unblock(w)
        return (count, owner)

    def _acquire_restore(self, state):
        count, owner = state
        sched = self._sched
        ctl = sched.current()
        if ctl is not None and sched.active and not sched.aborting:
            while self._owner is not None and self._owner is not ctl:
                self._waiters.append(ctl)
                sched.block_on(self)
                if ctl in self._waiters:
                    self._waiters.remove(ctl)
        self._owner = owner if ctl is None else ctl
        self._count = count

    def _is_owned(self):
        ctl = self._sched.current()
        if not self._sched.active:
            return self._owner is not None
        return self._owner is ctl


class _CoopRLock(_CoopLock):
    reentrant = True


class _CoopCondition:
    """Condition over a coop lock, with scheduler-controlled timed
    wakes: a ``wait(timeout)`` parks TIMED — the scheduler may fire the
    timeout as one of its choices, which is exactly how a schedule
    explores the timeout path deterministically."""

    def __init__(self, sched, lock=None, name=None):
        self._sched = sched
        self._lock = lock if lock is not None else _CoopRLock(
            sched, (name or "cond") + ".lock")
        self.name = name or "cond"
        self._waiters = []
        # delegate the lock interface
        self.acquire = self._lock.acquire
        self.release = self._lock.release

    def __enter__(self):
        self._lock.acquire()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._lock.release()
        return False

    def _is_owned(self):
        return self._lock._is_owned()

    def wait(self, timeout=None):
        sched = self._sched
        ctl = sched.current()
        if ctl is None or not sched.active or sched.aborting:
            return True
        if not self._is_owned():
            raise RuntimeError("cannot wait on un-acquired condition %s"
                               % self.name)
        state = self._lock._release_save()
        self._waiters.append(ctl)
        notified = sched.block_on(self, timed=timeout is not None)
        if ctl in self._waiters:
            self._waiters.remove(ctl)
        self._lock._acquire_restore(state)
        return notified

    def wait_for(self, predicate, timeout=None):
        result = predicate()
        while not result:
            if not self.wait(timeout):
                return predicate()
            result = predicate()
        return result

    def notify(self, n=1):
        if self._sched.active and not self._sched.aborting \
                and not self._is_owned():
            raise RuntimeError("cannot notify on un-acquired condition %s"
                               % self.name)
        woken = self._waiters[:n]
        del self._waiters[:n]
        for w in woken:
            self._sched.unblock(w)

    def notify_all(self):
        self.notify(len(self._waiters))

    def _drop_waiter(self, ctl):
        if ctl in self._waiters:
            self._waiters.remove(ctl)


class Controller:
    """The workload's handle on the explorer: cooperative primitive
    factories, explicit preemption points, and wholesale threading
    instrumentation."""

    def __init__(self, sched):
        self._sched = sched

    def lock(self, name="lock"):
        return _CoopLock(self._sched, name)

    def rlock(self, name="rlock"):
        return _CoopRLock(self._sched, name)

    def condition(self, lock=None, name="cond"):
        return _CoopCondition(self._sched, lock, name)

    def checkpoint(self):
        """An explicit preemption point — put one between the read and
        the write of a suspected racy read-modify-write."""
        self._sched.preempt()

    def instrument(self):
        """Context manager patching threading.Lock/RLock/Condition (and
        Thread) so every primitive created inside the window is
        cooperative. Construct the system under test inside it; keep
        the window NARROW (third-party code creating locks inside it
        becomes part of the explored schedule space)."""
        sched = self._sched
        ctl = self

        class _InstrumentedThread(threading.Thread):
            def start(self):
                target = self.run
                sched.spawn(target, name=self.name)

        class _Patch:
            def __enter__(self):
                self._saved = (threading.Lock, threading.RLock,
                               threading.Condition, threading.Thread)
                threading.Lock = lambda: _CoopLock(sched, "lock")
                threading.RLock = lambda: _CoopRLock(sched, "rlock")
                threading.Condition = \
                    lambda lock=None: _CoopCondition(sched, lock)
                threading.Thread = _InstrumentedThread
                return ctl

            def __exit__(self, exc_type, exc, tb):
                (threading.Lock, threading.RLock,
                 threading.Condition, threading.Thread) = self._saved
                return False

        return _Patch()


class FailureReport:
    """One failed schedule, replayable from (workload, seed, index)."""

    def __init__(self, name, strategy, base_seed, index, schedule_seed,
                 choices, kind, message, tb=None):
        self.workload = name
        self.strategy = strategy
        self.base_seed = base_seed
        self.index = index
        self.schedule_seed = schedule_seed
        self.choices = list(choices)
        self.kind = kind            # 'exception' | 'deadlock' | 'check' ...
        self.message = message
        self.traceback = tb

    def replay_hint(self):
        if self.strategy == "random":
            return ("replay: mxnet_tpu.analysis.schedule.replay("
                    "<workload>, seed=%d, index=%d)  # schedule_seed=%d, "
                    "%d decisions"
                    % (self.base_seed, self.index, self.schedule_seed,
                       len(self.choices)))
        # DFS schedules are defined by their choice prefix, not a
        # derived seed — replay from the recorded decisions
        return ("replay: mxnet_tpu.analysis.schedule.replay(<workload>, "
                "seed=%d, index=%d, choices=%r)"
                % (self.base_seed, self.index, self.choices))

    def __str__(self):
        s = "[%s] schedule #%d of %r (seed %d): %s\n  %s" % (
            self.kind, self.index, self.workload, self.base_seed,
            self.message, self.replay_hint())
        if self.traceback:
            s += "\n" + self.traceback
        return s


class ExploreResult:
    def __init__(self, name, strategy, seed, explored, failures):
        self.workload = name
        self.strategy = strategy
        self.seed = seed
        self.explored = explored
        self.failures = failures

    @property
    def ok(self):
        return not self.failures

    def first_failure(self):
        return self.failures[0] if self.failures else None

    def __str__(self):
        if self.ok:
            return ("%r survived %d %s schedules (seed %d)"
                    % (self.workload, self.explored, self.strategy,
                       self.seed))
        return ("%r FAILED %d/%d %s schedules (seed %d); first: %s"
                % (self.workload, len(self.failures), self.explored,
                   self.strategy, self.seed, self.failures[0]))


def _schedule_seed(base_seed, index):
    return (base_seed * 1_000_003 + index * 7919 + 1) & 0x7FFFFFFF


def _random_chooser(rng):
    def choose(enabled, _sched):
        return enabled[rng.randrange(len(enabled))]
    return choose


def _scripted_chooser(script):
    """Follow a recorded choice list (by tid); beyond it — or when the
    scripted tid is not enabled — fall back to the default policy (keep
    the current thread running, else lowest tid)."""
    state = {"i": 0, "last": None}

    def choose(enabled, _sched):
        want = None
        if state["i"] < len(script):
            want = script[state["i"]]
        state["i"] += 1
        by_tid = {t.tid: t for t in enabled}
        if want is not None and want in by_tid:
            chosen = by_tid[want]
        elif state["last"] in by_tid:
            chosen = by_tid[state["last"]]
        else:
            chosen = min(enabled, key=lambda t: t.tid)
        state["last"] = chosen.tid
        return chosen
    return choose


def _run_one_schedule(make_workload, chooser, max_steps, trace_files,
                      name):
    """One schedule: build the workload, run it, run its check.
    Returns (failure-tuple-or-None, choices, enabled_log)."""
    sched = _Scheduler(chooser, max_steps, trace_files)
    ctl = Controller(sched)
    built = make_workload(ctl)
    thread_fns, check = built
    for i, fn in enumerate(thread_fns):
        sched.spawn(fn, name="w%d" % i)
    choices = sched.run()
    failure = sched.failure
    if failure is None and check is not None:
        try:
            check()
        except BaseException as e:  # noqa: BLE001 — invariant checks
            failure = ("check",
                       "%s: %s" % (type(e).__name__, e),
                       "".join(_tb.format_exception(type(e), e,
                                                    e.__traceback__)))
    return failure, choices


class Explorer:
    """Drive ``make_workload`` through many schedules.

    Parameters
    ----------
    make_workload : callable(ctl) -> ([thread_fn, ...], check_fn|None)
        Builds ONE fresh instance of the workload; called once per
        schedule. ``check_fn`` runs after all threads finish and
        asserts the cross-thread invariants.
    schedules : int
        Budget: random walks run exactly this many; DFS stops at it.
    strategy : 'random' | 'dfs'
        Seeded uniform walks, or bounded context-switch exhaustion
        (deviate from the run-current-thread default at up to
        ``max_switches`` points, enumerated systematically).
    """

    def __init__(self, make_workload, schedules=50, seed=0,
                 strategy="random", max_steps=20000, max_switches=3,
                 trace_files=(), name=None, stop_on_first=True):
        if strategy not in ("random", "dfs"):
            raise ValueError("unknown strategy %r" % (strategy,))
        self.make_workload = make_workload
        self.schedules = int(schedules)
        self.seed = int(seed)
        self.strategy = strategy
        self.max_steps = int(max_steps)
        self.max_switches = int(max_switches)
        self.trace_files = tuple(trace_files)
        self.name = name or getattr(make_workload, "__name__", "workload")
        self.stop_on_first = stop_on_first

    def run(self):
        if self.strategy == "random":
            return self._run_random()
        return self._run_dfs()

    def _report(self, index, sseed, choices, failure):
        kind, message, tb = failure
        return FailureReport(self.name, self.strategy, self.seed, index,
                             sseed, choices, kind, message, tb)

    def _run_random(self):
        failures, explored = [], 0
        for i in range(self.schedules):
            sseed = _schedule_seed(self.seed, i)
            rng = random.Random(sseed)
            failure, choices = _run_one_schedule(
                self.make_workload, _random_chooser(rng), self.max_steps,
                self.trace_files, self.name)
            explored += 1
            if failure is not None:
                failures.append(self._report(i, sseed, choices, failure))
                if self.stop_on_first:
                    break
        return ExploreResult(self.name, "random", self.seed, explored,
                             failures)

    def _run_dfs(self):
        """Bounded context-switch exhaustion: run the all-default
        schedule, then systematically deviate at each decision point
        (up to max_switches deviations per schedule), lazily expanding
        the prefix tree."""
        failures, explored = [], 0
        # each stack entry: (prefix choices, switches used)
        stack = [((), 0)]
        seen = set()
        while stack and explored < self.schedules:
            prefix, switches = stack.pop()
            if prefix in seen:
                continue
            seen.add(prefix)
            enabled_log = []

            def chooser(enabled, sched, _p=prefix, _log=enabled_log):
                i = len(sched.choices)
                by_tid = {t.tid: t for t in enabled}
                _log.append(sorted(by_tid))
                if i < len(_p) and _p[i] in by_tid:
                    return by_tid[_p[i]]
                last = sched.choices[-1] if sched.choices else None
                if last in by_tid:
                    return by_tid[last]
                return min(enabled, key=lambda t: t.tid)

            failure, choices = _run_one_schedule(
                self.make_workload, chooser, self.max_steps,
                self.trace_files, self.name)
            explored += 1
            if failure is not None:
                failures.append(self._report(
                    explored - 1, 0, choices, failure))
                if self.stop_on_first:
                    break
            if switches >= self.max_switches:
                continue
            # expand alternatives beyond the prescribed prefix
            for i in range(len(prefix), len(enabled_log)):
                taken = choices[i] if i < len(choices) else None
                for alt in enabled_log[i]:
                    if alt == taken:
                        continue
                    stack.append(
                        (tuple(choices[:i]) + (alt,), switches + 1))
        return ExploreResult(self.name, "dfs", self.seed, explored,
                             failures)


def explore(make_workload, **kwargs):
    """One-shot :class:`Explorer` run; returns :class:`ExploreResult`."""
    return Explorer(make_workload, **kwargs).run()


def replay(make_workload, seed, index, strategy="random",
           max_steps=20000, trace_files=(), choices=None, name=None):
    """Re-run exactly one schedule (the one a FailureReport names).
    Returns the FailureReport it reproduces, or None if it passes —
    after a fix, None IS the green light."""
    nm = name or getattr(make_workload, "__name__", "workload")
    if choices is not None:
        chooser = _scripted_chooser(list(choices))
        sseed = 0
    else:
        sseed = _schedule_seed(seed, index)
        chooser = _random_chooser(random.Random(sseed))
    failure, got = _run_one_schedule(make_workload, chooser, max_steps,
                                     trace_files, nm)
    if failure is None:
        return None
    kind, message, tb = failure
    return FailureReport(nm, strategy, seed, index, sseed, got, kind,
                         message, tb)


# -- built-in workloads --------------------------------------------------------

def racy_counter_workload(locked=True, increments=3):
    """Two threads read-modify-write one shared counter ``increments``
    times each, with a preemption point inside the window. With
    ``locked=False`` this is the SEEDED RACE (negative control): the
    explorer must find the lost update in a handful of schedules; with
    the lock it must survive every schedule."""

    def make(ctl):
        state = {"n": 0}
        lock = ctl.lock("counter")

        def worker():
            for _ in range(increments):
                if locked:
                    with lock:
                        v = state["n"]
                        ctl.checkpoint()   # the racy window
                        state["n"] = v + 1
                else:
                    v = state["n"]
                    ctl.checkpoint()       # the racy window
                    state["n"] = v + 1

        def check():
            want = 2 * increments
            assert state["n"] == want, (
                "lost update: counter %d != %d" % (state["n"], want))

        return [worker, worker], check

    make.__name__ = "racy_counter(locked=%s)" % locked
    return make


def _stub_serving_engine():
    """A real serving Engine (real Scheduler, pool, stream plumbing)
    whose model.step is a deterministic numpy stub — the concurrency
    surface under test is the engine/scheduler bookkeeping, not the
    math, and a stub keeps each schedule at sub-millisecond cost."""
    import numpy as np

    from ..models.transformer import TransformerConfig
    from ..serving.engine import Engine, ServingConfig

    mcfg = TransformerConfig(vocab_size=64, num_layers=1, d_model=8,
                             num_heads=2, d_ff=16, max_seq_len=64,
                             dtype="float32")
    scfg = ServingConfig(block_size=4, num_blocks=16, max_batch=2,
                         max_active=4, prefill_chunk=8, token_budget=10,
                         max_queue_depth=8)
    eng = Engine({"embed": np.zeros((64, 8), np.float32)}, mcfg, scfg)

    def stub_step(params, k, v, tokens, start, chunk_len, tables, active,
                  temperature=None, top_k=None, top_p=None, seed=None):
        t = np.asarray(tokens)
        nxt = ((t[:, -1] + np.asarray(start) + 1) % 61 + 1).astype(np.int32)
        return nxt, k, v

    eng.model.step = stub_step
    return eng


def serving_workload(n_requests=4, cancel=True):
    """The serving engine's submit/cancel/step loop under adversarial
    schedules: a client thread submits (and cancels one of) ``n``
    requests while a driver thread pumps ``step()`` — the exact
    concurrent surface ``start()``'s background loop exposes, driven
    deterministically. Invariants: every admitted request ends exactly
    once (completed or cancelled), every stream terminates, and the KV
    pool drains to zero."""

    def make(ctl):
        eng = _stub_serving_engine()
        eng._lock = ctl.rlock("serving.Engine._lock")
        eng._step_lock = ctl.lock("serving.Engine._step_lock")
        eng._work = ctl.condition(eng._lock, "serving.Engine._work")
        handles = []
        client_done = []

        def client():
            for i in range(n_requests):
                handles.append(eng.submit([1, 2, 3], max_new_tokens=3))
                ctl.checkpoint()
            if cancel and handles:
                handles[0].cancel()
            client_done.append(True)

        def driver():
            for _ in range(400):
                ctl.checkpoint()
                worked = eng.step()
                if worked or not client_done:
                    continue
                if not (eng.sched.queue or eng.sched.active):
                    break

        def check():
            st = eng.stats()
            assert st["queue_depth"] == 0 and st["active"] == 0, st
            # a request cancelled while still QUEUED is never admitted,
            # so admitted may legitimately trail the submit count — but
            # every request must end exactly once, and nothing may end
            # both ways
            assert st["completed"] + st["cancelled"] == n_requests, st
            assert st["completed"] <= st["admitted"] <= n_requests, st
            assert eng.pool.utilization() == 0.0, (
                "leaked KV blocks: utilization %.3f"
                % eng.pool.utilization())
            for h in handles:
                assert h.status in ("finished", "cancelled"), (
                    "stream %d never terminated (status %r)"
                    % (h.request_id, h.status))

        return [client, driver], check

    make.__name__ = "serving_submit_cancel_step"
    return make


def wsync_swap_workload(n_requests=3, staged=True):
    """Engine hot-swap safety under adversarial schedules (ISSUE 17,
    riding PR 12's drain contract): a client thread submits/cancels, a
    drain thread flips drain()/resume(), a driver pumps step(), and a
    sync thread swaps the params mid-traffic. With ``staged=True`` the
    swap goes through ``install_weights`` + ``rollback_weights`` (the
    wsync discipline) and every schedule must survive with the
    serving invariants intact AND the params identity equal to the
    installed token. With ``staged=False`` — the SEEDED RACE (negative
    control) — the sync thread rebinds ``eng.params`` directly, and
    the explorer must catch step()'s unstaged-write guard firing."""

    def make(ctl):
        import numpy as np

        eng = _stub_serving_engine()
        eng._lock = ctl.rlock("serving.Engine._lock")
        eng._step_lock = ctl.lock("serving.Engine._step_lock")
        eng._work = ctl.condition(eng._lock, "serving.Engine._work")
        old_params = eng.params
        new_params = {"embed": np.ones((64, 8), np.float32)}
        handles = []
        client_done = []

        def client():
            from ..serving.engine import QueueFullError

            for _ in range(n_requests):
                try:
                    handles.append(eng.submit([1, 2, 3],
                                              max_new_tokens=3))
                except QueueFullError:
                    pass   # submit raced a drain window — by design
                ctl.checkpoint()
            if handles:
                handles[0].cancel()
            client_done.append(True)

        def syncer():
            ctl.checkpoint()
            if staged:
                eng.install_weights(1, new_params)
                ctl.checkpoint()
                eng.rollback_weights()
            else:
                # the unstaged direct write the step() guard must catch
                eng.params = new_params
            ctl.checkpoint()

        def drainer():
            ctl.checkpoint()
            eng.drain()
            ctl.checkpoint()
            eng.resume()

        def driver():
            for _ in range(400):
                ctl.checkpoint()
                worked = eng.step()
                if worked or not client_done:
                    continue
                if not (eng.sched.queue or eng.sched.active):
                    break

        def check():
            st = eng.stats()
            assert st["queue_depth"] == 0 and st["active"] == 0, st
            # a drain window may have shed some submits — every stream
            # that exists still ends exactly once
            assert st["completed"] + st["cancelled"] == len(handles), st
            for h in handles:
                assert h.status in ("finished", "cancelled"), (
                    "stream %d never terminated (status %r)"
                    % (h.request_id, h.status))
            assert eng.pool.utilization() == 0.0, (
                "leaked KV blocks: %.3f" % eng.pool.utilization())
            # the swap discipline: after install+rollback the live set
            # is the ORIGINAL params object and the identity token
            # matches — no torn/unblessed rebind survived the schedule
            assert eng.params is eng._installed_params, (
                "params rebound without install_weights")
            assert eng.params is old_params, "rollback lost the ring set"
            assert eng.weight_version() is None, eng.weight_version()

        return [client, syncer, drainer, driver], check

    make.__name__ = "wsync_swap(staged=%s)" % staged
    return make


def aggregator_workload(world=3, rounds=2, locked=True):
    """The elastic Aggregator round protocol driven by ``world``
    concurrent contributor threads serialized — or, with
    ``locked=False``, NOT serialized — by the coordinator's lock. Pair
    ``locked=False`` with line-granularity preemption inside
    elastic/server.py (see :data:`AGGREGATOR_TRACE_FILES`) and the
    explorer interleaves threads mid-``contribute``: double round
    completion (two threads both pass the coverage check) shows up as
    a KeyError or a wrong round counter. The locked variant must
    survive every schedule — it is the coordinator's actual
    discipline."""
    import contextlib

    import numpy as np

    from ..elastic.server import Aggregator

    def make(ctl):
        agg = Aggregator(world)
        agg.init_key("w", np.zeros(4, np.float32))
        lock = ctl.lock("coordinator._lock") if locked else None
        live = set(range(world))

        def worker(rank):
            def body():
                for rnd in range(1, rounds + 1):
                    grad = np.full(4, float(rank + 1), np.float32)
                    guard = lock if locked else contextlib.nullcontext()
                    with guard:
                        agg.contribute("w", rank, rnd, grad)
                        agg.complete_ready(live)
                    # sync workers pull round rnd before pushing rnd+1
                    for _ in range(2000):
                        with (lock if locked
                              else contextlib.nullcontext()):
                            done = agg.done["w"]
                        if done >= rnd:
                            break
                        ctl.checkpoint()
            return body

        def check():
            assert agg.done["w"] == rounds, (
                "round counter %d != %d (a completion ran twice or got "
                "lost)" % (agg.done["w"], rounds))
            # no optimizer installed: the stored value IS the merged
            # gradient of the last round = sum of every rank's grad
            want = sum(range(1, world + 1))
            assert np.allclose(agg.weights["w"], want), (
                "merged weight %r != %r" % (agg.weights["w"], want))
            assert not agg.pending, "contributions leaked: %r" % agg.pending

        return [worker(r) for r in range(world)], check

    make.__name__ = "aggregator_rounds(locked=%s)" % locked
    return make


def AGGREGATOR_TRACE_FILES():
    """Line-granularity preemption targets for the aggregator race leg."""
    from ..elastic import server as _srv

    return (_srv.__file__,)


class _NullLock:
    """A reentrant no-op lock — the seeded-race stand-in for a routing
    table lock someone forgot (fleet negative control)."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def acquire(self, *a, **k):
        return True

    def release(self):
        pass


class _StubFleetReplica:
    """A socketless fleet replica answering the ``fleet_*`` arms with a
    deterministic token function of the prompt (one token per stream
    poll, so every request spans many router steps). ``dead=True``
    makes every dispatch raise — the SIGKILL stand-in the router's
    transport-error path turns into an eviction."""

    def __init__(self, name):
        import itertools as _it

        self.name = name
        self.dead = False
        self._rids = _it.count()
        self._reqs = {}

    @staticmethod
    def expected(prompt, max_new):
        base = int(sum(prompt))
        return [(base + i) % 50 for i in range(int(max_new))]

    def _dispatch(self, req):
        if self.dead:
            raise ConnectionError("replica %s is dead" % self.name)
        op = req.get("op")
        if op == "fleet_submit":
            rid = next(self._rids)
            toks = self.expected(req["prompt"], req["max_new"])
            # a redelivery prefix = tokens the client already saw on a
            # dead replica: resume past them (PR 8 recompute semantics)
            self._reqs[rid] = {"toks": toks,
                               "sent": len(req.get("prefix") or [])}
            return {"status": "ok", "rid": rid, "name": self.name}
        if op == "fleet_stream":
            rec = self._reqs[req["rid"]]
            out = []
            if rec["sent"] < len(rec["toks"]):
                out = [rec["toks"][rec["sent"]]]
                rec["sent"] += 1
            done = rec["sent"] >= len(rec["toks"])
            return {"status": "ok", "tokens": out, "done": done,
                    "final_status": "finished"}
        if op == "fleet_cancel":
            return {"status": "ok", "known": req["rid"] in self._reqs}
        if op == "fleet_stats":
            return {"status": "ok", "name": self.name, "accepting": True,
                    "stats": {"queue_depth": 0}}
        return {"status": "error", "message": "unknown op %r" % (op,)}


def fleet_router_workload(locked=True, failover=True, n_requests=3,
                          max_new=4):
    """The fleet router's submit/place/poll bookkeeping under
    adversarial schedules (ISSUE 20).

    ``locked=True`` (the shipped discipline): two submitter threads
    race a driver pumping ``Router.step()`` over two stub replicas,
    with — when ``failover`` — a killer thread blowing one replica away
    mid-stream. Invariants: every stream terminates with EXACTLY its
    expected token sequence (redelivery is invisible), the journal
    drains, and no replica ever exceeds its in-flight cap.

    ``locked=False`` is the SEEDED RACE (negative control): the
    router's lock is replaced with a no-op, and two submitters race
    the admission check-then-append window against a tiny
    ``pending_max``. Paired with line-granularity preemption over
    router.py (:func:`FLEET_TRACE_FILES`) the explorer must FIND the
    cap violation and REPLAY it — proving the lock is load-bearing,
    not decorative."""

    def make(ctl):
        from ..serving.engine import QueueFullError
        from ..serving.fleet.router import Router

        if not locked:
            router = Router(bind=None, pending_max=2, inflight_cap=2,
                            health_interval=0.0)
            router._lock = _NullLock()
            accepted = []

            def submitter():
                for i in range(2):
                    try:
                        router.submit([1, 2, 3], max_new_tokens=2)
                    except QueueFullError:
                        continue
                    accepted.append(1)

            def check():
                assert len(router._pending) <= router.pending_max, (
                    "admission cap breached: %d pending > pending_max %d "
                    "(check-then-append raced)"
                    % (len(router._pending), router.pending_max))

            return [submitter, submitter], check

        router = Router(bind=None, pending_max=16, inflight_cap=2,
                        health_interval=0.0)
        router._lock = ctl.rlock("fleet.Router._lock")
        reps = [_StubFleetReplica("rep0"), _StubFleetReplica("rep1")]
        for r in reps:
            router.register_local(r.name, r)
        prompts = [[1 + i, 2, 3] for i in range(n_requests)]
        streams = []
        submitters_done = []

        def submitter(lo, hi):
            def body():
                for i in range(lo, hi):
                    streams.append((i, router.submit(
                        prompts[i], max_new_tokens=max_new)))
                    ctl.checkpoint()
                submitters_done.append(True)
            return body

        killer_done = []

        def killer():
            ctl.checkpoint()
            reps[0].dead = True
            killer_done.append(True)

        def driver():
            for _ in range(400):
                ctl.checkpoint()
                worked = router.step()
                if worked or len(submitters_done) < 2:
                    continue
                if failover and not killer_done:
                    continue
                if not router._requests:
                    break

        def check():
            assert not router._requests, (
                "journal leaked %d entries" % len(router._requests))
            assert not router._pending, "pending leaked"
            got = sorted((i, _drain_stream(s)) for i, s in streams)
            assert len(got) == n_requests, got
            for i, toks in got:
                want = _StubFleetReplica.expected(prompts[i], max_new)
                assert toks == want, (
                    "stream %d not byte-identical after %s: %r != %r"
                    % (i, "failover" if failover else "routing",
                       toks, want))
            for rep in router._replicas.values():
                assert not rep.inflight, (
                    "replica %s leaked inflight %r"
                    % (rep.name, rep.inflight))
            if failover:
                assert not router._replicas["rep0"].alive, (
                    "dead replica was never evicted")

        threads = [submitter(0, n_requests // 2),
                   submitter(n_requests // 2, n_requests), driver]
        if failover:
            threads.append(killer)
        return threads, check

    make.__name__ = "fleet_router(locked=%s)" % locked
    return make


def _drain_stream(stream):
    """Collect a FleetStream's delivered tokens without blocking (the
    coop scheduler owns the threads — a real Queue.get wait would
    wedge it)."""
    import queue as _q

    out = []
    while True:
        try:
            item = stream._q.get_nowait()
        except _q.Empty:
            return out
        if item is None or item.__class__ is not int:
            return out
        out.append(item)


def FLEET_TRACE_FILES():
    """Line-granularity preemption targets for the fleet race leg."""
    from ..serving.fleet import router as _rt

    return (_rt.__file__,)


def survival_suite(seed=0, schedules=None, include_serving=True):
    """The ``mxlint --schedules`` / ``chaos --schedules`` legs.

    Two negative controls prove the explorer actually works (it must
    FIND the seeded lost-update race, and the line-traced unlocked
    aggregator race, and replay them from their seeds); then the real
    discipline legs — the locked counter, the elastic Aggregator round
    protocol under the coordinator's lock, and the serving engine's
    submit/cancel/step loop — must survive every explored schedule.

    Returns (findings, report_lines): findings use the shared mxlint
    Finding model (pass ``schedule``), report lines are human-readable
    survival summary rows.
    """
    from .findings import Finding

    if schedules is None:
        schedules = int(os.environ.get("MXRACE_SCHEDULES", "25") or 25)
    findings, lines = [], []

    def control(name, wl, budget, trace_files=()):
        r = explore(wl, schedules=budget, seed=seed,
                    trace_files=trace_files)
        if r.ok:
            findings.append(Finding(
                "schedule", "control-miss", "error", name,
                "the explorer failed to find the SEEDED race %r in %d "
                "schedules — schedule exploration is not actually "
                "exploring" % (r.workload, r.explored)))
            lines.append("%-18s: MISSED its seeded race (%d schedules)"
                         % (name, r.explored))
            return
        f = r.first_failure()
        rep = replay(wl, seed=seed, index=f.index,
                     trace_files=trace_files)
        if rep is None:
            findings.append(Finding(
                "schedule", "replay-miss", "error", name,
                "failing schedule #%d of %r did not reproduce on "
                "replay — schedules are not deterministic"
                % (f.index, r.workload)))
            lines.append("%-18s: race found but replay MISSED" % name)
        else:
            lines.append("%-18s: race found at schedule #%d (%s), "
                         "replayed from its seed" % (name, f.index, f.kind))

    control("control/counter", racy_counter_workload(locked=False),
            schedules)
    control("control/aggregator", aggregator_workload(locked=False),
            min(schedules, 20), trace_files=AGGREGATOR_TRACE_FILES())
    if include_serving:
        # the unstaged direct param write MUST be caught by step()'s
        # installed-identity guard — if the explorer can't surface it,
        # the wsync swap discipline is unenforced
        control("control/wsync-unstaged", wsync_swap_workload(staged=False),
                min(schedules, 10))
        # the unlocked routing table is the fleet's seeded race: the
        # admission check-then-append window must be findable under
        # line preemption, or the router lock is unproven
        control("control/fleet-unlocked",
                fleet_router_workload(locked=False),
                min(schedules, 20), trace_files=FLEET_TRACE_FILES())

    legs = [("counter-locked", racy_counter_workload(locked=True), ()),
            ("aggregator", aggregator_workload(locked=True), ())]
    if include_serving:
        legs.append(("serving", serving_workload(), ()))
        legs.append(("wsync-swap", wsync_swap_workload(staged=True), ()))
        legs.append(("fleet-router", fleet_router_workload(locked=True),
                     ()))
    for name, wl, trace_files in legs:
        r = explore(wl, schedules=schedules, seed=seed,
                    trace_files=trace_files)
        if r.ok:
            lines.append("%-18s: survived %d schedules"
                         % (name, r.explored))
        else:
            f = r.first_failure()
            findings.append(Finding(
                "schedule", "schedule-race", "error",
                "%s schedule #%d" % (name, f.index),
                "%s under an adversarial schedule: %s — %s"
                % (f.kind, f.message, f.replay_hint())))
            lines.append("%-18s: FAILED at schedule #%d (%s)"
                         % (name, f.index, f.kind))
    return findings, lines
