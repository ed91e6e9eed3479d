"""Engine hazard detector: record push traces, verify the dependency
discipline statically.

The dependency engine (mxnet_tpu/engine.py, src/engine.cc) orders host
tasks by read/write var sets: reads on a var run concurrently, a write
waits for prior accesses to drain and runs alone. That discipline is
only as good as the var sets the pushing code declares — a task that
mutates a buffer it never declared races silently, and a WaitForVar
issued from *inside* an engine op can deadlock the worker pool. The
reference only ever fuzz-tested this at runtime
(tests/cpp/threaded_engine_test.cc); here we record every push's
read/write var sets and check the trace statically.

Checks (all 'engine' pass):

- ``use-after-free`` (error) — an op pushed, or a wait issued, after
  ``delete_variable`` on one of its vars. Deferred deletion of vars
  with *pending* ops is legal (ref: engine.h:148-160); touching the var
  in a *later* push is not.
- ``ww-hazard`` / ``rw-hazard`` (error) — two ops touch the same data
  tag (at least one writing) with NO happens-before path between them
  in the var-dependency graph: the scheduler is free to interleave
  them. Data tags name what a task actually touches (buffers, files)
  and come from the programmatic API — the engine's var sets alone
  cannot reveal an undeclared write, which is exactly why this is a
  lint and not a runtime assert.
- ``wait-cycle`` (error) — a wait recorded inside engine op A on a var
  whose pending ops include A itself or any op that (transitively)
  depends on A: A waits on work that cannot start until A completes.
  ``wait_for_all`` inside any engine op is an immediate cycle.
- ``lock-order`` (error) — the trace also carries runtime lock
  acquire/release events (``lock_acquire``/``lock_release``, recorded
  by :class:`TracedLock` wrappers that the concurrent subsystems
  install around their state locks under ``MXNET_ENGINE_VERIFY=1``).
  Per-thread held stacks replay the events into an observed
  acquisition-order edge set; two locks observed in both orders are a
  deadlock cycle that actually happened order-wise at runtime. The
  observed edges also cross-check the static graph from
  ``lock_lint.build_lock_graph`` (``lock_lint.cross_check``): an edge
  the static lint cannot see is a blind spot worth auditing.

Record mode is engaged by ``MXNET_ENGINE_VERIFY=1`` (the engine then
self-verifies on every wait and raises on findings) or programmatically:

    from mxnet_tpu.analysis import engine_verify
    with engine_verify.recording(engine) as trace:
        ... push work ...
    findings = engine_verify.verify(trace)

Synthetic traces can be built directly with the same ``EngineTrace``
builder methods the engine hooks call, and round-trip through
``to_json``/``from_json`` for the mxlint CLI (--engine-trace).
"""
from __future__ import annotations

import json
import os
import threading
from contextlib import contextmanager

from .findings import Finding

__all__ = ["TraceOp", "EngineTrace", "verify", "recording",
           "TracedLock", "maybe_trace_lock", "ambient_trace",
           "set_ambient_trace", "observed_lock_edges"]

# lock events kept verbatim per trace (diagnostics + JSON round-trip);
# the ORDER EDGES are folded incrementally so a suite-long ambient
# trace stays O(distinct lock pairs), not O(acquisitions)
_LOCK_EVENT_TAIL = 4096


def _fold_lock_event(held, edges, seq, tid, name, kind):
    """THE observed-lock-order edge semantics, shared by live recording
    (lock_acquire/lock_release) and events-only JSON replay (from_json):
    an acquire adds an edge from every lock the thread already holds
    (self-edges — RLock re-entry — skipped; first seq wins), a release
    pops the thread's innermost matching hold."""
    stack = held.setdefault(tid, [])
    if kind == "acquire":
        for h in stack:
            if h != name:
                edges.setdefault((h, name), seq)
        stack.append(name)
    else:
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                break


class TraceOp:
    """One recorded push."""

    __slots__ = ("seq", "name", "const", "mutable", "reads_data", "writes_data")

    def __init__(self, seq, name, const, mutable, reads_data=(), writes_data=()):
        self.seq = seq
        self.name = name
        self.const = tuple(const)
        self.mutable = tuple(mutable)
        self.reads_data = tuple(reads_data)
        self.writes_data = tuple(writes_data)

    def vars(self):
        return self.const + self.mutable

    def label(self):
        return "op#%d(%s)" % (self.seq, self.name)

    def __repr__(self):
        return "<TraceOp %s const=%s mutable=%s>" % (
            self.label(), list(self.const), list(self.mutable))


class EngineTrace:
    """Append-only record of pushes / deletes / waits, with one shared
    monotonic seq so the three streams interleave deterministically.
    Thread-safe: the engine records from pushing threads and workers.

    Safe to enter from a finalizer. A ``__del__`` runs on whatever
    thread allocates next (the cyclic collector), so also on the thread
    that is inside this trace's critical section, holding ``_lock``. No
    builder blocks there: entered again on that thread it queues its
    record, and the outer call stamps and appends the queue after its
    own record, before it lets go — each record whole, with a seq of
    its own, in order (``_record``; an op pushed that way reads seq 0
    until then). What this cannot cover is a finalizer that WAITS for
    another thread which records (that thread queues behind ``_lock``
    like any other): the per-test time limit in tests/conftest.py
    bounds that one."""

    def __init__(self):
        self.events = []    # [TraceOp]
        self.deletes = []   # [(seq, var)]
        self.waits = []     # [(seq, var-or-None, ctx-op-seq-or-None)]
        # runtime lock discipline: bounded raw event tail + the folded
        # observed-order edge set {(held, acquired): first seq}
        self.lock_events = []   # [(seq, thread_id, name, 'acquire'|'release')]
        self.lock_edges = {}
        self._held = {}         # thread_id -> [lock name] stack
        self._lock = threading.RLock()
        self._busy = False      # a thread is committing in _record()
        self._nested = []       # records queued by finalizers run there
        self._seq = 0
        self._tls = threading.local()
        # live-verify progress, owned by the engine that records into
        # this trace (kept here so detaching/re-attaching a trace — the
        # recording() save/restore — carries its progress with it)
        self.verify_seq = 0
        self.verify_reported = set()

    def _next_seq(self):
        self._seq += 1
        return self._seq

    def _record(self, commit):
        """Run ``commit(seq)`` under ``_lock`` with the next seq.
        ``_lock`` is re-entrant, so a finalizer run on the thread that
        is in here gets in, finds the trace ``_busy`` and queues its
        record; the outer call commits the queue after its own record,
        on its way out. Nothing blocks, and no record lands in the
        middle of another."""
        with self._lock:
            if self._busy:
                self._nested.append(commit)
                return
            self._busy = True
            try:
                commit(self._next_seq())
                while self._nested:
                    self._nested.pop(0)(self._next_seq())
            finally:
                self._busy = False

    def last_seq(self):
        """The newest seq whose record is in the trace (read under the
        lock: a seq is never visible before its record)."""
        with self._lock:
            return self._seq

    # -- builders (engine hooks AND synthetic-trace construction) -------------
    def push(self, name, const=(), mutable=(), reads_data=(), writes_data=()):
        ev = TraceOp(0, name, const, mutable, reads_data, writes_data)

        def commit(seq):
            ev.seq = seq
            self.events.append(ev)

        self._record(commit)
        return ev

    def discard(self, ev):
        """Roll back a recorded push whose submission to the native
        engine failed — the op never ran and must not contribute
        happens-before edges."""
        with self._lock:
            try:
                self.events.remove(ev)
            except ValueError:
                pass

    def delete_var(self, var):
        self._record(lambda seq: self.deletes.append((seq, var)))

    def wait(self, var=None, inside=None):
        """Record wait_for_var (or wait_for_all when var is None).
        ``inside`` is the TraceOp (or seq) of the engine op the wait was
        issued from; defaults to the recorded thread context."""
        if inside is None:
            inside = self.current_op()
        ctx = inside.seq if isinstance(inside, TraceOp) else inside
        self._record(lambda seq: self.waits.append((seq, var, ctx)))

    # -- runtime lock events (TracedLock wrappers) -----------------------------
    def lock_acquire(self, name, thread=None):
        """Record that ``thread`` acquired lock ``name``. Folds the
        observed-order edges (every currently held lock -> name)
        immediately so the edge set stays bounded for suite-long
        ambient traces; the raw event tail is capped."""
        self._lock_event(name, "acquire", thread)

    def lock_release(self, name, thread=None):
        self._lock_event(name, "release", thread)

    def _lock_event(self, name, kind, thread=None):
        tid = threading.get_ident() if thread is None else thread

        def commit(seq):
            _fold_lock_event(self._held, self.lock_edges,
                             seq, tid, name, kind)
            self.lock_events.append((seq, tid, name, kind))
            if len(self.lock_events) > _LOCK_EVENT_TAIL:
                del self.lock_events[:_LOCK_EVENT_TAIL // 2]

        self._record(commit)

    # -- executing-op context (set by the engine around fn execution) ----------
    @contextmanager
    def op_context(self, op):
        prev = getattr(self._tls, "op", None)
        self._tls.op = op
        try:
            yield
        finally:
            self._tls.op = prev

    def current_op(self):
        return getattr(self._tls, "op", None)

    # -- serialization ---------------------------------------------------------
    def to_json(self):
        with self._lock:
            return self._to_json_locked()

    def _to_json_locked(self):
        return json.dumps({
            "events": [{
                "seq": e.seq, "name": e.name,
                "const": list(e.const), "mutable": list(e.mutable),
                "reads_data": list(e.reads_data),
                "writes_data": list(e.writes_data),
            } for e in self.events],
            "deletes": [[s, v] for s, v in self.deletes],
            "waits": [[s, v, c] for s, v, c in self.waits],
            "lock_events": [list(e) for e in self.lock_events],
            "lock_edges": [[a, b, s]
                           for (a, b), s in sorted(self.lock_edges.items())],
        }, indent=2)

    @classmethod
    def from_json(cls, json_str):
        """Raises ValueError on malformed input (bad JSON text or bad
        trace structure) — the CLI's load-error contract."""
        data = json.loads(json_str)
        t = cls()
        try:
            for je in data.get("events", []):
                ev = TraceOp(int(je["seq"]), je.get("name", "fn"),
                             je.get("const", ()), je.get("mutable", ()),
                             je.get("reads_data", ()), je.get("writes_data", ()))
                t.events.append(ev)
                t._seq = max(t._seq, ev.seq)
            for s, v in data.get("deletes", []):
                t.deletes.append((int(s), v))
                t._seq = max(t._seq, int(s))
            for w in data.get("waits", []):
                s, v, c = (list(w) + [None, None])[:3]
                t.waits.append((int(s), v, c))
                t._seq = max(t._seq, int(s))
            for ev in data.get("lock_events", []):
                s, tid, name, kind = ev
                if kind not in ("acquire", "release"):
                    raise ValueError("bad lock event kind %r" % (kind,))
                t.lock_events.append((int(s), int(tid), name, kind))
                t._seq = max(t._seq, int(s))
            for a, b, s in data.get("lock_edges", []):
                t.lock_edges[(a, b)] = int(s)
            if t.lock_events and not t.lock_edges:
                # events-only trace (hand-built JSON): replay through
                # the SAME fold as live recording — one edge semantics
                held = {}
                for s, tid, name, kind in sorted(t.lock_events):
                    _fold_lock_event(held, t.lock_edges, s, tid, name,
                                     kind)
        except (KeyError, TypeError, AttributeError) as e:
            raise ValueError(
                "malformed trace JSON: %s: %s" % (type(e).__name__, e)) \
                from None
        return t


def _happens_before(events):
    """Adjacency seq -> set(succ seq) from the reference queue semantics:
    a write depends on the previous write and every read granted since;
    a read depends on the previous write."""
    adj = {e.seq: set() for e in events}
    last_write = {}   # var -> TraceOp
    readers = {}      # var -> [TraceOp] since last write
    for e in sorted(events, key=lambda x: x.seq):
        for v in e.const:
            w = last_write.get(v)
            if w is not None:
                adj[w.seq].add(e.seq)
            readers.setdefault(v, []).append(e)
        for v in e.mutable:
            w = last_write.get(v)
            if w is not None:
                adj[w.seq].add(e.seq)
            for r in readers.get(v, ()):
                adj[r.seq].add(e.seq)
            last_write[v] = e
            readers[v] = []
    return adj


def _reachable(adj, src, dst):
    if src == dst:
        return True
    seen, stack = {src}, [src]
    while stack:
        n = stack.pop()
        for m in adj.get(n, ()):
            if m == dst:
                return True
            if m not in seen:
                seen.add(m)
                stack.append(m)
    return False


def verify(trace, since_seq=0):
    """Statically check a trace; returns findings whose triggering event
    has seq >= since_seq (for incremental live verification)."""
    findings = []
    events = sorted(trace.events, key=lambda e: e.seq)
    by_seq = {e.seq: e for e in events}
    adj = _happens_before(events)

    # -- use-after-free --------------------------------------------------------
    first_delete = {}
    for s, v in trace.deletes:
        if v not in first_delete:
            first_delete[v] = s
    for e in events:
        if e.seq < since_seq:
            continue
        for v in e.vars():
            d = first_delete.get(v)
            if d is not None and e.seq > d:
                findings.append(Finding(
                    "engine", "use-after-free", "error", e.label(),
                    "references var %r deleted at seq %d (push after "
                    "delete_variable)" % (v, d)))
    for s, v, _ctx in trace.waits:
        if s < since_seq or v is None:
            continue
        d = first_delete.get(v)
        if d is not None and s > d:
            findings.append(Finding(
                "engine", "use-after-free", "error", "wait#%d" % s,
                "wait_for_var on var %r deleted at seq %d" % (v, d)))

    # -- data hazards (need data tags; live var-only traces skip) --------------
    tag_acc = {}
    for e in events:
        for t in e.reads_data:
            tag_acc.setdefault(t, []).append((e, False))
        for t in e.writes_data:
            tag_acc.setdefault(t, []).append((e, True))
    for tag, acc in tag_acc.items():
        for i in range(len(acc)):
            for j in range(i + 1, len(acc)):
                (a, aw), (b, bw) = acc[i], acc[j]
                if a is b or not (aw or bw):
                    continue
                if max(a.seq, b.seq) < since_seq:
                    continue
                if (_reachable(adj, a.seq, b.seq)
                        or _reachable(adj, b.seq, a.seq)):
                    continue
                code = "ww-hazard" if (aw and bw) else "rw-hazard"
                findings.append(Finding(
                    "engine", code, "error",
                    "%s <-> %s" % (a.label(), b.label()),
                    "both touch data %r (%s) but share no engine var: no "
                    "ordering edge exists and the scheduler may interleave "
                    "them" % (tag, "write/write" if aw and bw
                              else "read/write")))

    # -- wait cycles -----------------------------------------------------------
    for s, v, ctx in trace.waits:
        if s < since_seq or ctx is None or ctx not in by_seq:
            continue
        waiter = by_seq[ctx]
        if v is None:
            findings.append(Finding(
                "engine", "wait-cycle", "error", waiter.label(),
                "wait_for_all issued inside an engine op: the op waits for "
                "its own completion"))
            continue
        pending = [e for e in events if e.seq < s and v in e.vars()]
        for e in pending:
            if e is waiter:
                findings.append(Finding(
                    "engine", "wait-cycle", "error", waiter.label(),
                    "waits on var %r which it reads/writes itself: the op "
                    "waits for its own completion" % (v,)))
            elif _reachable(adj, waiter.seq, e.seq):
                findings.append(Finding(
                    "engine", "wait-cycle", "error",
                    "%s -> %s" % (waiter.label(), e.label()),
                    "waits on var %r pending in %s, which depends on the "
                    "waiter — deadlock" % (v, e.label())))

    # -- observed lock-order inversions ----------------------------------------
    for (a, b), seq_ab in sorted(trace.lock_edges.items()):
        if a >= b:
            continue  # report each unordered pair once (from its
            #            lexicographically first direction)
        seq_ba = trace.lock_edges.get((b, a))
        if seq_ba is None or max(seq_ab, seq_ba) < since_seq:
            continue
        findings.append(Finding(
            "engine", "lock-order", "error",
            "%s <-> %s" % (a, b),
            "runtime lock trace observed %r acquired while holding %r "
            "(seq %d) AND the reverse (seq %d): a deadlock cycle — two "
            "threads taking the two paths concurrently wedge forever"
            % (b, a, seq_ab, seq_ba)))
    return findings


@contextmanager
def recording(engine):
    """Attach a fresh trace to ``engine`` for the duration of the block."""
    trace = EngineTrace()
    prev = engine.attach_trace(trace)
    try:
        yield trace
    finally:
        engine.attach_trace(prev)


# -- runtime lock tracing ------------------------------------------------------
#
# The concurrent subsystems (serving engine, elastic coordinator, the
# dependency engine itself) wrap their state locks in TracedLock under
# MXNET_ENGINE_VERIFY=1: every acquire/release lands in the process
# AMBIENT trace, whose folded edge set is the *observed* lock-order
# graph — checked for inversions by verify() and cross-checked against
# the static graph from lock_lint.build_lock_graph.

_ambient = None
_ambient_lock = threading.Lock()


def _verify_env_on():
    return os.environ.get("MXNET_ENGINE_VERIFY", "").strip() \
        not in ("", "0", "false")


def ambient_trace(create=None):
    """The process-wide lock trace. Created lazily when
    MXNET_ENGINE_VERIFY=1 (or ``create=True``); None otherwise."""
    global _ambient
    # double-checked creation: the unlocked fast-path read is the point
    # (this sits on every traced acquire) — a racing reader either sees
    # the published trace or takes the lock
    if _ambient is None and (create or (create is None  # mxlint: disable
                                        and _verify_env_on())):
        with _ambient_lock:
            if _ambient is None:
                _ambient = EngineTrace()
    return _ambient  # mxlint: disable (same deliberate unlocked read)


def set_ambient_trace(trace):
    """Swap the ambient lock trace (tests); returns the previous one."""
    global _ambient
    with _ambient_lock:
        prev, _ambient = _ambient, trace
    return prev


class TracedLock:
    """A Lock/RLock/Condition proxy that records acquire/release into a
    trace (default: the ambient trace at call time, so a test swapping
    the ambient trace observes locks wrapped long before).

    The proxy forwards everything else to the wrapped primitive —
    including the private ``_release_save``/``_acquire_restore`` pair
    ``threading.Condition`` uses, so a Condition built OVER a traced
    lock works; the wait-window release/reacquire goes unrecorded
    through those private hooks, which keeps the held-stack replay
    consistent (the window is invisible, not torn)."""

    __slots__ = ("_inner", "_name", "_trace")

    def __init__(self, inner, name, trace=None):
        self._inner = inner
        self._name = name
        self._trace = trace

    def _t(self):
        return self._trace if self._trace is not None else ambient_trace()

    @property
    def name(self):
        return self._name

    @property
    def inner(self):
        """The wrapped primitive: what a finalizer takes, so that it
        records nothing (engine.Engine.__del__)."""
        return self._inner

    def acquire(self, *args, **kwargs):
        got = self._inner.acquire(*args, **kwargs)
        if got:
            t = self._t()
            if t is not None:
                t.lock_acquire(self._name)
        return got

    def release(self):
        t = self._t()
        if t is not None:
            t.lock_release(self._name)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    def __getattr__(self, attr):
        # _is_owned / _release_save / _acquire_restore / notify / wait …
        return getattr(self._inner, attr)

    def __repr__(self):
        return "<TracedLock %s %r>" % (self._name, self._inner)


def maybe_trace_lock(lock, name):
    """Wrap ``lock`` in a TracedLock when MXNET_ENGINE_VERIFY=1; return
    it untouched otherwise — the zero-overhead-by-default wiring the
    subsystems call at construction time."""
    if _verify_env_on():
        return TracedLock(lock, name)
    return lock


def observed_lock_edges(trace=None):
    """{(held, acquired): first seq} from a trace (default ambient).
    Feed to ``lock_lint.cross_check`` against the static graph."""
    trace = trace if trace is not None else ambient_trace(create=False)
    if trace is None:
        return {}
    with trace._lock:
        return dict(trace.lock_edges)
