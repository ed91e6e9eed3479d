"""Dependency engine: host-task scheduler with read/write-var ordering.

Re-design of the reference engine (ref: include/mxnet/engine.h:74-226,
src/engine/threaded_engine.h:87-189, src/engine/engine.cc:13-39 —
SURVEY §2.1). On TPU, XLA already orders device work per stream, so this
engine schedules *host-side* tasks — IO/prefetch stages, checkpoint
writes, host reductions, custom-op callbacks — with the reference's exact
dependency semantics: reads on a variable run concurrently, a write waits
for prior reads to drain and runs alone, later ops queue in program order.

The scheduler core is native C++ (src/engine.cc, loaded via ctypes); a
pure-Python NaiveEngine fallback runs every op inline when native code is
unavailable or MXNET_NATIVE=0 — the same role the reference's NaiveEngine
plays for debugging (ref: src/engine/naive_engine.cc).

Engine choice follows the reference env protocol (src/engine/engine.cc:13):
MXNET_ENGINE_TYPE = ThreadedEngine | ThreadedEnginePerDevice (default) |
NaiveEngine. Worker count: MXNET_CPU_WORKER_NTHREADS.
"""
from __future__ import annotations

import atexit
import ctypes
import itertools
import logging
import os
import threading
import time
from contextlib import nullcontext as _null_context

from . import _native
from . import telemetry as _tel
from .base import MXNetError
from .resilience import faults as _faults

__all__ = ["Engine", "get", "push", "wait_for_all"]

_ENGINE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)


def _wait_timeout():
    """MXNET_ENGINE_WAIT_TIMEOUT in seconds, or None when the watchdog
    is off. Read per wait so tests (and operators attaching to a hung
    job) can arm it at any time."""
    raw = os.environ.get("MXNET_ENGINE_WAIT_TIMEOUT", "").strip()
    if not raw:
        return None
    try:
        t = float(raw)
    except ValueError:
        raise MXNetError(
            "MXNET_ENGINE_WAIT_TIMEOUT must be a number of seconds, "
            "got %r" % raw)
    return t if t > 0 else None


def _engine_lib():
    lib = _native.load("engine")
    if lib is None or getattr(lib, "_eng_configured", False):
        return lib
    c = ctypes
    lib.EngineCreate.restype = c.c_void_p
    lib.EngineCreate.argtypes = [c.c_int, c.c_int]
    lib.EngineDestroy.argtypes = [c.c_void_p]
    lib.EngineNewVariable.restype = c.c_void_p
    lib.EngineNewVariable.argtypes = [c.c_void_p]
    lib.EngineDeleteVariable.argtypes = [c.c_void_p, c.c_void_p]
    lib.EnginePush.restype = c.c_int
    lib.EnginePush.argtypes = [
        c.c_void_p, _ENGINE_FN, c.c_void_p,
        c.POINTER(c.c_void_p), c.c_int,
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_int,
    ]
    lib.EngineOprComplete.argtypes = [c.c_void_p]
    lib.EngineWaitForVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.EngineWaitForAll.argtypes = [c.c_void_p]
    lib.EnginePendingCount.restype = c.c_int64
    lib.EnginePendingCount.argtypes = [c.c_void_p]
    lib.EngineLastError.restype = c.c_char_p
    lib.EngineLastError.argtypes = [c.c_void_p]
    lib._eng_configured = True
    return lib


class VarHandle:
    """Opaque engine variable (ref: engine.h VarHandle). ``_uid`` is a
    stable process-wide id used by the verify/record trace (the native
    pointer is recycled by the allocator, uids never are)."""

    __slots__ = ("_ptr", "_engine", "_uid")

    _uids = itertools.count(1)

    def __init__(self, ptr, engine):
        self._ptr = ptr
        self._engine = engine
        self._uid = next(VarHandle._uids)


class Engine:
    """Singleton scheduler. API parity: engine.h:74-226."""

    _instance = None
    _instance_lock = threading.Lock()

    def __init__(self, engine_type=None, num_workers=None):
        if engine_type is None:
            engine_type = os.environ.get(
                "MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
        if num_workers is None:
            num_workers = int(os.environ.get("MXNET_CPU_WORKER_NTHREADS", "0"))
        self.engine_type = engine_type
        # MXNET_ENGINE_INFO: log each push (ref: threaded_engine.h:253)
        self._verbose = os.environ.get("MXNET_ENGINE_INFO", "").strip() \
            not in ("", "0", "false")
        # MXNET_ENGINE_VERIFY: record every push's read/write var sets and
        # statically verify the trace (use-after-free, wait-cycles) on each
        # wait, raising on findings — see analysis/engine_verify.py
        self._verify = os.environ.get("MXNET_ENGINE_VERIFY", "").strip() \
            not in ("", "0", "false")
        self._trace = None
        if self._verify:
            from .analysis.engine_verify import EngineTrace, maybe_trace_lock

            self._trace = EngineTrace()
        threaded = 0 if engine_type == "NaiveEngine" else 1
        self._lib = _engine_lib()
        self._handle = None
        if self._lib is not None:
            self._handle = ctypes.c_void_p(
                self._lib.EngineCreate(threaded, num_workers))
        # keep callback objects alive until their op completes
        self._live = {}
        self._live_lock = threading.Lock()
        if self._verify:
            # runtime lock-order recording (analysis/engine_verify.py):
            # acquires/releases land in the ambient lock trace, whose
            # observed edges are checked for inversions and
            # cross-checked against lock_lint's static graph
            self._live_lock = maybe_trace_lock(
                self._live_lock, "engine.Engine._live_lock")
        self._next_key = 1
        self._errors = []
        # key -> fn name for ops dispatched to a worker but not yet
        # completed (the wait watchdog's "in-flight" dump; _live alone
        # cannot name them — its entry is popped at dispatch)
        self._inflight = {}
        lib = self._lib
        # The trampoline reaches the engine's state, never the engine:
        # no reference cycle, so an engine dies where its last reference
        # goes and not at some later allocation on some other thread
        # (close() says why that matters). Not through a weak reference:
        # that is dead by the time __del__ runs, and close() drains the
        # pending ops through this very callback.
        live, inflight, errors = self._live, self._inflight, self._errors
        live_lock = self._live_lock

        def _trampoline(argp, token):
            key = argp  # void* cast back to the int key
            with live_lock:
                fn, is_async, ev, ev_trace = live.pop(key)
                inflight[key] = getattr(fn, "__name__", None) or "fn"
            # pair ev with the trace it was recorded into at push time:
            # if a recording() block ended while this op was in flight,
            # the now-attached trace must not adopt a foreign seq as its
            # op context (waits would misattribute their waiter)
            ctx = ev_trace.op_context(ev) if ev is not None \
                else _null_context()
            t0 = time.monotonic() if _tel.ENABLED else 0.0
            if is_async:
                called = [False]

                def on_complete(_tok=token, _key=key):
                    if not called[0]:
                        called[0] = True
                        with live_lock:
                            inflight.pop(_key, None)
                        lib.EngineOprComplete(_tok)

                try:
                    with ctx:
                        _faults.point("engine.task")
                        fn(on_complete)
                except BaseException as e:  # surface on next wait()
                    with live_lock:
                        errors.append(e)
                    on_complete()
            else:
                try:
                    with ctx:
                        _faults.point("engine.task")
                        fn()
                except BaseException as e:
                    with live_lock:
                        errors.append(e)
                finally:
                    with live_lock:
                        inflight.pop(key, None)
            if _tel.ENABLED:
                # async latency covers fn's dispatch body (durability is
                # on_complete's clock, which may outlive this frame)
                _tel.histogram("engine.task_secs").observe(
                    time.monotonic() - t0)

        self._trampoline = _ENGINE_FN(_trampoline) if lib is not None else None

    def close(self):
        """Drain pending work and free the native engine + worker pool.

        Contract: close() must only run once all threads that push to or
        wait on this engine have quiesced (it is invoked from __del__ and
        interpreter exit). The locked swap makes the handle hand-off
        atomic — a thread that starts a push AFTER the swap falls back to
        inline execution — but a native call already in flight when
        EngineDestroy runs is undefined, same as the reference engine's
        shutdown (threaded_engine destructor joins its workers without
        fencing producers). Holding _live_lock across EngineDestroy is
        not an option: the worker-thread trampoline takes _live_lock, so
        destroy's drain would deadlock.

        As a finalizer it relies on two things. __del__ takes the plain
        lock under the TracedLock, so it records nothing: a finalizer is
        no program action (lock_lint exempts __del__ too), and it runs
        on whatever thread drops or collects the engine, which may be
        inside the recorder. And the engine is in no reference cycle of
        its own making (see _trampoline), so it dies where its last
        reference goes: for the caller that kept the contract above,
        after its last wait, never on one of its own workers. The
        recorder is safe against finalizers that do record
        (EngineTrace._record); the engine does not lean on that."""
        with self._live_lock:
            h, self._handle = self._handle, None
        if h is not None and self._lib is not None:
            self._lib.EngineDestroy(h)

    def __del__(self):
        try:
            self._live_lock = getattr(self._live_lock, "inner",
                                      self._live_lock)
            self.close()
        except Exception:
            pass

    # -- factory ---------------------------------------------------------------
    @classmethod
    def get(cls):
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @property
    def is_native(self):
        return self._handle is not None

    def _handle_snapshot(self):
        """Read the handle once under the lock; callers use the snapshot
        for the whole native call so a concurrent close() can never turn
        a passed None-check into a NULL dereference."""
        with self._live_lock:
            return self._handle

    # -- variables -------------------------------------------------------------
    def new_variable(self):
        h = self._handle_snapshot()
        if h is None:
            return VarHandle(None, self)
        return VarHandle(self._lib.EngineNewVariable(h), self)

    def delete_variable(self, var):
        """Deferred deletion after all pending ops (ref: engine.h:148-160)."""
        trace = self._trace
        if trace is not None:
            trace.delete_var(var._uid)
        h = self._handle_snapshot()
        if h is not None and var._ptr:
            self._lib.EngineDeleteVariable(h, var._ptr)
            var._ptr = None

    # -- record / verify -------------------------------------------------------
    def attach_trace(self, trace):
        """Attach an analysis.engine_verify.EngineTrace (or None) for
        recording; returns the previously attached trace. Programmatic
        counterpart of MXNET_ENGINE_VERIFY=1 — prefer the
        ``engine_verify.recording(engine)`` context manager. Verify
        progress lives ON the trace (verify_seq/verify_reported), so
        re-attaching a previous trace — recording() restoring it — must
        not re-raise hazards that were already reported once."""
        prev, self._trace = self._trace, trace
        return prev

    def _maybe_verify(self):
        """In MXNET_ENGINE_VERIFY mode, statically check the trace on
        each wait and raise the first new findings as MXNetError. Runs
        BEFORE the blocking native wait so a wait-cycle raises instead
        of deadlocking the worker pool."""
        trace = self._trace
        if not self._verify or trace is None:
            return
        from .analysis.engine_verify import verify

        # snapshot before verifying: a worker pushing concurrently must
        # not land inside [since_seq, verify_seq) unchecked. Taken under
        # the trace lock — an unlocked read could observe a seq whose
        # event is not yet appended, and that event would then be
        # skipped by every later incremental verify.
        snap = trace.last_seq()
        findings = verify(trace, since_seq=trace.verify_seq)
        trace.verify_seq = snap + 1
        new = [f for f in findings if f.key() not in trace.verify_reported]
        if not new:
            return
        trace.verify_reported.update(f.key() for f in new)
        raise MXNetError(
            "engine verify: %d hazard(s) detected:\n%s"
            % (len(new), "\n".join(str(f) for f in new)))

    # -- push ------------------------------------------------------------------
    def _check_dup(self, const_vars, mutable_vars):
        seen = set()
        for v in list(const_vars) + list(mutable_vars):
            if id(v) in seen:
                raise MXNetError(
                    "duplicate variable in const/mutable lists "
                    "(ref: threaded_engine.cc:205 CheckDuplicate)")
            seen.add(id(v))

    def push(self, fn, const_vars=(), mutable_vars=(), priority=0):
        """PushSync (ref: engine.h:197-207): fn() runs once deps are met;
        completion is automatic when it returns."""
        self._push(fn, const_vars, mutable_vars, priority, is_async=False)

    def push_async(self, fn, const_vars=(), mutable_vars=(), priority=0):
        """PushAsync (ref: engine.h:142-146): fn(on_complete) must invoke
        on_complete() when the op's effects are durable."""
        self._push(fn, const_vars, mutable_vars, priority, is_async=True)

    def _push(self, fn, const_vars, mutable_vars, priority, is_async):
        self._check_dup(const_vars, mutable_vars)
        if self._verbose:
            logging.info(
                "engine: push %s const=%d mutable=%d priority=%d async=%s",
                getattr(fn, "__name__", "fn"), len(const_vars),
                len(mutable_vars), priority, is_async)
        with self._live_lock:
            handle = self._handle
        for v in list(const_vars) + list(mutable_vars):
            if handle is not None and not v._ptr:
                raise MXNetError("engine variable used after delete_variable")
        trace = self._trace
        ev = None
        if trace is not None:
            ev = trace.push(getattr(fn, "__name__", None) or "fn",
                            [v._uid for v in const_vars],
                            [v._uid for v in mutable_vars])
        if _tel.ENABLED:
            _tel.counter("engine.push_total").inc()
        if handle is None:  # NaiveEngine fallback: run inline
            t0 = time.monotonic() if _tel.ENABLED else 0.0
            ctx = trace.op_context(ev) if ev is not None else _null_context()
            with ctx:
                _faults.point("engine.task")
                if is_async:
                    done = threading.Event()
                    fn(done.set)
                    done.wait()
                else:
                    fn()
            if _tel.ENABLED:
                _tel.histogram("engine.task_secs").observe(
                    time.monotonic() - t0)
            return
        with self._live_lock:
            key = self._next_key
            self._next_key += 1
            self._live[key] = (fn, is_async, ev, trace)
        n_c, n_m = len(const_vars), len(mutable_vars)
        c_arr = (ctypes.c_void_p * max(n_c, 1))(
            *[v._ptr for v in const_vars])
        m_arr = (ctypes.c_void_p * max(n_m, 1))(
            *[v._ptr for v in mutable_vars])
        rc = self._lib.EnginePush(
            handle, self._trampoline, ctypes.c_void_p(key),
            c_arr, n_c, m_arr, n_m, priority, 0 if is_async else 1)
        if _tel.ENABLED and rc == 0:
            _tel.gauge("engine.queue_depth").set(
                self._lib.EnginePendingCount(handle))
        if rc != 0:
            with self._live_lock:
                self._live.pop(key, None)
            if trace is not None and ev is not None:
                # roll back the recorded push: a phantom op that never
                # ran must not create happens-before edges in the trace
                trace.discard(ev)
            raise MXNetError(
                self._lib.EngineLastError(handle).decode())

    # -- sync ------------------------------------------------------------------
    def wait_for_var(self, var):
        """ref: engine.h:166 WaitForVar. With MXNET_ENGINE_WAIT_TIMEOUT
        set, a sentinel read op on the var bounds the wait: if it has
        not run by the deadline, raise the pending-op dump instead of
        blocking forever behind a task that never completes."""
        trace = self._trace
        if trace is not None:
            trace.wait(var._uid)
        if _tel.ENABLED:
            _tel.counter("engine.waits_total").inc()
        self._maybe_verify()
        h = self._handle_snapshot()
        if h is not None and var._ptr:
            timeout = _wait_timeout()
            if timeout is None:
                self._lib.EngineWaitForVar(h, var._ptr)
            else:
                reached = threading.Event()

                def __engine_wait_sentinel__():
                    reached.set()

                # ordinary read push: runs once every op queued on the
                # var before this wait has drained — exactly WaitForVar's
                # contract (ref: threaded_engine.cc:300)
                self.push(__engine_wait_sentinel__, const_vars=[var],
                          priority=1 << 20)
                if not reached.wait(timeout):
                    if _tel.ENABLED:
                        _tel.counter("engine.watchdog_fires_total").inc()
                    # a deferred task error is the likely ROOT CAUSE of
                    # the wedge (fn raised before calling on_complete);
                    # surface it in preference to the generic timeout
                    self._raise_pending()
                    raise MXNetError(
                        "engine wait_for_var exceeded "
                        "MXNET_ENGINE_WAIT_TIMEOUT=%gs\n%s"
                        % (timeout, self.pending_dump()))
        self._raise_pending()

    def wait_for_all(self):
        """ref: engine.h:170 WaitForAll. With MXNET_ENGINE_WAIT_TIMEOUT
        set, polls the pending count with a deadline and raises the
        pending-op dump instead of deadlocking."""
        trace = self._trace
        if trace is not None:
            trace.wait(None)
        if _tel.ENABLED:
            _tel.counter("engine.waits_total").inc()
        self._maybe_verify()
        h = self._handle_snapshot()
        if h is not None:
            timeout = _wait_timeout()
            if timeout is None:
                self._lib.EngineWaitForAll(h)
            elif not self._poll_pending(h, timeout):
                if _tel.ENABLED:
                    _tel.counter("engine.watchdog_fires_total").inc()
                self._raise_pending()  # root cause beats generic timeout
                raise MXNetError(
                    "engine wait_for_all exceeded "
                    "MXNET_ENGINE_WAIT_TIMEOUT=%gs\n%s"
                    % (timeout, self.pending_dump()))
        self._raise_pending()

    def _poll_pending(self, h, timeout):
        """Watchdog wait body: poll the native pending count until it
        drains (True) or the deadline passes (False)."""
        deadline = time.monotonic() + timeout
        while self._lib.EnginePendingCount(h) > 0:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.002)
        return True

    def pending_count(self):
        h = self._handle_snapshot()
        if h is None:
            return 0
        return self._lib.EnginePendingCount(h)

    def pending_snapshot(self):
        """Structured pending-work snapshot: native pending count plus
        the queued (pushed, not yet dispatched) and in-flight
        (dispatched, not yet completed) task names. The wait watchdog's
        dump and the /enginez introspection endpoint both read this."""
        with self._live_lock:
            queued = [getattr(fn, "__name__", None) or "fn"
                      for fn, _a, _e, _t in self._live.values()]
            inflight = list(self._inflight.values())
        return {"pending": self.pending_count(), "queued": queued,
                "in_flight": inflight}

    def pending_dump(self):
        """Diagnostic snapshot for the wait watchdog: how many ops the
        native engine still counts pending, which tasks are queued
        (pushed, not yet dispatched), which are in flight (dispatched,
        on_complete never called), and — when a verify/record trace is
        attached (MXNET_ENGINE_VERIFY=1) — the trace tail with each
        op's declared var sets, which names the dependency chain the
        wait is stuck behind."""
        snap = self.pending_snapshot()
        lines = ["pending ops: %d native; queued: %s; in-flight: %s"
                 % (snap["pending"],
                    ", ".join(snap["queued"]) or "(none)",
                    ", ".join(snap["in_flight"]) or "(none)")]
        trace = self._trace
        if trace is not None and trace.events:
            tail = sorted(trace.events, key=lambda e: e.seq)[-8:]
            lines.append("verify-trace tail:")
            lines.extend("  %s const=%s mutable=%s"
                         % (e.label(), list(e.const), list(e.mutable))
                         for e in tail)
        lines.append(
            "likely cause: an async task never invoked on_complete, or a "
            "host task is blocked; see docs/how_to/fault_tolerance.md")
        return "\n".join(lines)

    def _raise_pending(self):
        with self._live_lock:
            if not self._errors:
                return
            err = self._errors[0]
            dropped = self._errors[1:]
            self._errors.clear()
        # Raise the first failure; the rest must not vanish silently
        # (two async checkpoint writes can both fail in one wait).
        for extra in dropped:
            logging.error("engine: additional deferred task error "
                          "(raised error takes precedence): %r", extra)
        raise err


@atexit.register
def _drain_at_exit():
    """Fence pending host tasks (async checkpoints etc.) at interpreter
    exit; a swallowed worker-thread error must not vanish silently.
    Honors MXNET_ENGINE_WAIT_TIMEOUT: a task wedged at exit logs the
    pending-op dump instead of hanging interpreter shutdown forever."""
    e = Engine._instance
    if e is None or e._handle is None:
        _tel.flush_at_exit()  # journal final flush rides the drain hook
        return
    try:
        timeout = _wait_timeout()
        if timeout is None:
            e._lib.EngineWaitForAll(e._handle)
        elif not e._poll_pending(e._handle, timeout):
            if _tel.ENABLED:
                _tel.counter("engine.watchdog_fires_total").inc()
            logging.error(
                "engine: exit drain exceeded "
                "MXNET_ENGINE_WAIT_TIMEOUT=%gs\n%s",
                timeout, e.pending_dump())
    except Exception:
        _tel.flush_at_exit()
        return
    for err in e._errors:
        logging.error("engine: pending task failed: %r", err)
    # metrics recorded by tasks that completed during the drain are now
    # final — flush them before the interpreter tears the journal down
    _tel.flush_at_exit()


def get():
    return Engine.get()


def push(fn, const_vars=(), mutable_vars=(), priority=0):
    Engine.get().push(fn, const_vars, mutable_vars, priority)


def wait_for_all():
    Engine.get().wait_for_all()
