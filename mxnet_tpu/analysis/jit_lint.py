"""jit-boundary lint — mxjit (mxlint ``--jit``).

The MFU arc and the serving tokens/s headline both live or die at the
jit boundary: an accidental recompile, a lost donation, or a stray
device->host sync inside a per-step loop silently costs 10-30% and no
test catches it until a bench regresses.  This pass makes the repo's
jit-boundary conventions *checkable artifacts* (the TVM stance on
schedule/layout decisions) over every jit-dispatching module:

``recompile-hazard`` (error)
    A per-call-varying Python value or an unbucketed runtime shape
    reaching a traced signature — the compile-count-per-bucket contract
    made checkable.  Two static forms: a ``jax.jit`` call inside a
    steady-state loop without a memo guard (every iteration builds and
    traces a fresh program), and a raw ``.shape``-derived value (never
    laundered through ``bucket_for``) flowing into a jit-memo key or a
    traced closure.  The *dynamic* form — same structure, varying
    value — is the runtime verifier's half (compile_verify.py).

``donation-hazard``
    error: caller reuse of a buffer after it was passed at a
    ``donate_argnums`` position — the executable now owns that memory;
    reading it is a use-after-free that XLA only sometimes catches.
    Reuse means a read after the dispatch without rebinding, or a loop
    that re-dispatches the same donated name without threading the
    returned buffer back (the pool.swap discipline).  warning: a
    steady-state loop dispatching pool-like buffers through a program
    built with *no* donation at all — every step pays a device-side
    copy that donation would elide.  A conditional carve-out
    (``donate = () if <cond> else (...)``) is donation for analysis
    purposes, never a finding: the buffers ARE donated on one branch,
    so caller reuse is still an error.

``hot-d2h`` (error)
    ``.asnumpy()`` / ``np.asarray`` / ``float()`` / ``.item()`` /
    ``jax.device_get`` / ``.block_until_ready()`` inside a per-step /
    per-token loop — the loop-aware escalation of ast_lint's host-sync
    taint.  A loop is *hot* when it (transitively, within the module)
    dispatches a jitted program; functions called from a hot loop are
    hot too, so a drain helper's pulls are attributed to the loop that
    calls it.  Sanctioned (info, and exported as the runtime D2H
    ledger's expected-site set): the one-fence-per-chunk idiom
    (``bur = getattr(o, "block_until_ready", None)``), syncs guarded
    under a profiling/telemetry ``ENABLED`` check, the single
    post-fence chunk pull, and ``# mxlint: disable`` pragma lines.

``weak-cache-key`` (error)
    A config input reaching a jitted program body that is NOT folded
    into its jit-cache / attribution key — the PR 13/15 aliasing bug
    class (two different graphs sharing a shape-only key), checked by
    diffing the traced closure's reaching-config set against the
    key-construction site.  Also mechanical: any ``attribute_jit``
    call without ``graph_key=`` (the exact hole PR 13 patched).

The pass is interprocedural *within a module*: memo dicts holding
jitted programs, builder methods returning them, and the dispatch
sites calling them are linked so donation positions and cache keys
survive the repo's ``fn = self._compiled(key); fn(*args)`` idiom.

Suppression: a ``# mxlint: disable`` comment on the offending line
(ast_lint's pragma).  Suppressed and fence-sanctioned D2H sites are
still *collected* — ``sanctioned_d2h_sites()`` exports them as the
static half of compile_verify's observed-vs-expected cross-check
(the lock_lint ``cross_check`` pattern).
"""
from __future__ import annotations

import ast
import os

from .findings import Finding

__all__ = ["lint_source", "lint_file", "lint_targets", "cross_check",
           "sanctioned_d2h_sites", "DEFAULT_TARGETS", "DEFAULT_PACKAGE"]

DEFAULT_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the jit-dispatching surface: every module that builds or dispatches
#: a traced program (the set the MFU/tokens-per-s roadmap items churn)
DEFAULT_TARGETS = (
    "executor.py",
    "model.py",
    os.path.join("serving", "model.py"),
    os.path.join("serving", "engine.py"),
    os.path.join("serving", "scheduler.py"),
    os.path.join("parallel", "fit_trainer.py"),
    os.path.join("parallel", "trainer.py"),
    os.path.join("telemetry", "prof.py"),
    "compile",
)

_PRAGMA = "mxlint: disable"

#: attribute calls that are a device->host sync (or a fence) by name
_SYNC_ATTRS = frozenset(("asnumpy", "item", "tolist", "block_until_ready"))
#: module roots whose ``.asarray`` is a host materialization (jnp is
#: device-side and deliberately absent)
_NP_ROOTS = frozenset(("np", "numpy", "_np", "onp"))
#: builtins that force a host scalar out of a device value
_HOST_CASTS = frozenset(("float", "int", "bool"))
#: method names that dispatch a jitted program on any receiver
_DISPATCH_HINT_ANY = frozenset(("run_chunk", "draft_turn", "verify"))
#: method names that dispatch only on model/executor-ish receivers
#: (``step``/``forward`` are too generic to hint on every object)
_DISPATCH_HINT_RECV = frozenset(("step", "forward", "backward"))
_DISPATCH_RECEIVERS = frozenset(("model", "draft_model", "exe", "exec",
                                 "_exec", "executor", "trainer", "m"))
#: argument names that look like steady-state device pools/state — the
#: un-donated-loop warning's heuristic surface
_POOLISH = frozenset(("params", "opt_state", "opt_states"))

_LOOPS = (ast.For, ast.While, ast.AsyncFor)
_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)


# -- small AST helpers ---------------------------------------------------------

def _parent_links(tree):
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._mxjit_p = node


def _ancestors(node):
    while getattr(node, "_mxjit_p", None) is not None:
        node = node._mxjit_p
        yield node


def _dotted(node):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _has_call_to(node, names):
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if d is not None and d.split(".")[-1] in names:
                return True
    return False


def _stmt_of(node):
    """The statement containing ``node`` (for ordering comparisons)."""
    cur = node
    for anc in _ancestors(node):
        if isinstance(anc, (ast.stmt, ast.Module)):
            if isinstance(anc, ast.Module):
                return cur
            return anc
        cur = anc
    return cur


class _Pragmas:
    def __init__(self, src):
        self.lines = src.splitlines()

    def __contains__(self, lineno):
        if 1 <= lineno <= len(self.lines):
            return _PRAGMA in self.lines[lineno - 1]
        return False


# -- module model --------------------------------------------------------------

class _JitInfo:
    """What analysis knows about one compiled-program handle."""

    __slots__ = ("node", "donated", "conditional", "has_donate",
                 "traced", "builder")

    def __init__(self, node, donated=(), conditional=False,
                 has_donate=False, traced=None, builder=None):
        self.node = node              # the jax.jit Call
        self.donated = tuple(donated)
        self.conditional = conditional
        self.has_donate = has_donate
        self.traced = traced          # expr passed to jax.jit
        self.builder = builder        # enclosing FunctionDef


class _Module:
    def __init__(self, tree, relpath, src):
        self.tree = tree
        self.relpath = relpath
        self.pragmas = _Pragmas(src)
        self.funcs = {}          # qualname -> FunctionDef
        self.func_of = {}        # FunctionDef -> qualname
        self.classes = {}        # name -> ClassDef
        self.jit_memos = {}      # dotted memo path -> _JitInfo
        self.jitted_paths = {}   # dotted attr path -> _JitInfo
        self.returns_jitted = {}  # qualname -> _JitInfo
        self.creations = []      # (_JitInfo, loop_depth, guarded)
        self.class_attr_writers = {}   # class -> {attr: set(method names)}
        self.class_creators = {}       # class -> {method names w/ jax.jit}
        self.str_dicts = {}      # class-level {const: method-name} dicts
        _parent_links(tree)
        self._index()
        self._collect_jits()

    # -- indexing -------------------------------------------------------------
    def _index(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                writers, creators, sdicts = {}, set(), {}
                for item in node.body:
                    if isinstance(item, _FUNCS):
                        for sub in ast.walk(item):
                            if (isinstance(sub, ast.Attribute)
                                    and isinstance(sub.ctx, ast.Store)
                                    and isinstance(sub.value, ast.Name)
                                    and sub.value.id == "self"):
                                writers.setdefault(sub.attr,
                                                   set()).add(item.name)
                            if (isinstance(sub, ast.Call)
                                    and _is_jax_jit(sub)):
                                creators.add(item.name)
                    elif isinstance(item, ast.Assign):
                        # class-level {"kind": "_impl_method"} tables
                        if (isinstance(item.value, ast.Dict)
                                and len(item.targets) == 1
                                and isinstance(item.targets[0], ast.Name)):
                            vals = [v.value for v in item.value.values
                                    if isinstance(v, ast.Constant)
                                    and isinstance(v.value, str)]
                            if vals and len(vals) == len(item.value.values):
                                sdicts[item.targets[0].id] = vals
                self.class_attr_writers[node] = writers
                self.class_creators[node] = creators
                self.str_dicts.update(
                    {(node.name, k): v for k, v in sdicts.items()})
            elif isinstance(node, _FUNCS):
                qual = self._qualname(node)
                self.funcs[qual] = node
                self.func_of[node] = qual

    def _qualname(self, fn):
        parts = [fn.name]
        for anc in _ancestors(fn):
            if isinstance(anc, ast.ClassDef):
                parts.append(anc.name)
            elif isinstance(anc, _FUNCS):
                parts.append(anc.name)
        return ".".join(reversed(parts))

    def enclosing_func(self, node):
        for anc in _ancestors(node):
            if isinstance(anc, _FUNCS):
                return anc
        return None

    def enclosing_class(self, node):
        for anc in _ancestors(node):
            if isinstance(anc, ast.ClassDef):
                return anc
        return None

    def loop_depth(self, node, stop=None):
        d = 0
        for anc in _ancestors(node):
            if anc is stop:
                break
            if isinstance(anc, _LOOPS):
                d += 1
            if isinstance(anc, _FUNCS):
                break
        return d

    # -- jit creation + linkage ------------------------------------------------
    def _collect_jits(self):
        # first sweep: every jax.jit call, its donation spec, and every
        # direct target (name / attribute / memo subscript / return)
        local_jitted = {}  # (func, name) -> _JitInfo
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call) and _is_jax_jit(node)):
                continue
            fn = self.enclosing_func(node)
            donated, conditional, has_donate = _donation_spec(node, fn)
            info = _JitInfo(node, donated, conditional, has_donate,
                            traced=node.args[0] if node.args else None,
                            builder=fn)
            guarded = _memo_guarded(node)
            self.creations.append((info, self.loop_depth(node, stop=fn),
                                   guarded))
            self._record_target(node, info, fn, local_jitted)
        # second sweep: names assigned FROM jit memos / builder methods
        # become jitted handles too (fn = self._compiled(key)), and
        # builder-call results stored into memos link the memo to the
        # builder's jit info (self._jit_cache[K] = self._make_loop(K))
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Assign):
                continue
            info = self._jitinfo_of_expr(node.value)
            if info is None:
                continue
            for tgt in node.targets:
                d = _dotted(tgt)
                if d is not None:
                    self.jitted_paths.setdefault(d, info)
                elif isinstance(tgt, ast.Subscript):
                    base = _dotted(tgt.value)
                    if base is not None:
                        self.jit_memos.setdefault(base, info)

    def _record_target(self, call, info, fn, local_jitted):
        parent = getattr(call, "_mxjit_p", None)
        if isinstance(parent, ast.Assign):
            for tgt in parent.targets:
                if isinstance(tgt, ast.Subscript):
                    base = _dotted(tgt.value)
                    if base is not None:
                        self.jit_memos[base] = info
                else:
                    d = _dotted(tgt)
                    if d is not None:
                        self.jitted_paths[d] = info
                        if fn is not None and isinstance(tgt, ast.Name):
                            local_jitted[(fn, tgt.id)] = info
        elif isinstance(parent, ast.Return) and fn is not None:
            self.returns_jitted[self.func_of[fn]] = info
        # fn = jax.jit(...); self._jitted[key] = fn; return fn
        if fn is not None:
            self._propagate_local(fn, local_jitted)

    def _propagate_local(self, fn, local_jitted):
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Name):
                info = local_jitted.get((fn, node.value.id))
                if info is None:
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Subscript):
                        base = _dotted(tgt.value)
                        if base is not None:
                            self.jit_memos.setdefault(base, info)
                    else:
                        d = _dotted(tgt)
                        if d is not None:
                            self.jitted_paths.setdefault(d, info)
            elif (isinstance(node, ast.Return)
                    and isinstance(node.value, ast.Name)):
                info = local_jitted.get((fn, node.value.id))
                if info is not None:
                    self.returns_jitted.setdefault(self.func_of[fn], info)

    def _jitinfo_of_expr(self, expr):
        """_JitInfo when ``expr`` evaluates to a jitted handle: a memo
        read, a jitted attr path, or a builder-method call."""
        if isinstance(expr, ast.Subscript):
            base = _dotted(expr.value)
            if base in self.jit_memos:
                return self.jit_memos[base]
        d = _dotted(expr)
        if d in self.jitted_paths:
            return self.jitted_paths[d]
        if isinstance(expr, ast.Call):
            cd = _dotted(expr.func)
            if cd is not None:
                tail = cd.split(".")[-1]
                for qual, info in self.returns_jitted.items():
                    if qual.split(".")[-1] == tail:
                        return info
            # memo.get(key)
            if (isinstance(expr.func, ast.Attribute)
                    and expr.func.attr == "get"):
                base = _dotted(expr.func.value)
                if base in self.jit_memos:
                    return self.jit_memos[base]
        return None

    def dispatch_info(self, call):
        """_JitInfo when ``call`` dispatches a linkable jitted handle."""
        func = call.func
        if isinstance(func, ast.Subscript):
            base = _dotted(func.value)
            if base in self.jit_memos:
                return self.jit_memos[base]
            return None
        d = _dotted(func)
        if d is None:
            return None
        if d in self.jitted_paths:
            return self.jitted_paths[d]
        fn = self.enclosing_func(call)
        if fn is not None and isinstance(func, ast.Name):
            # a local rebound from a memo/builder earlier in the function
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and any(isinstance(t, ast.Name) and t.id == func.id
                                for t in node.targets)):
                    info = self._jitinfo_of_expr(node.value)
                    if info is not None:
                        return info
        return None


def _is_jax_jit(call):
    d = _dotted(call.func)
    return d in ("jax.jit", "jit") and bool(call.args)


def _tuple_ints(node):
    if isinstance(node, ast.Tuple):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    return None


def _donation_spec(call, fn):
    """(donated positions, conditional?, donate kwarg present?) for a
    jax.jit call — resolving a carve-out ternary
    (``() if <cond> else (1, 2)``) to the donating branch: where the
    condition is false the buffers ARE donated."""
    kw = next((k for k in call.keywords if k.arg == "donate_argnums"), None)
    if kw is None:
        return (), False, False
    node = kw.value
    if isinstance(node, ast.Name) and fn is not None:
        for n in ast.walk(fn):
            if (isinstance(n, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == node.id
                            for t in n.targets)):
                node = n.value
                break
    if isinstance(node, ast.IfExp):
        body = _tuple_ints(node.body) or ()
        orelse = _tuple_ints(node.orelse) or ()
        chosen = body if len(body) >= len(orelse) else orelse
        return chosen, True, True
    got = _tuple_ints(node)
    return (got or ()), False, True


def _memo_guarded(call):
    """True when a jax.jit call's result is memoized: stored under a
    subscript, or built inside an ``if key not in cache`` /
    ``if fn is None`` (post-``cache.get``) guard."""
    parent = getattr(call, "_mxjit_p", None)
    if isinstance(parent, ast.Assign) and any(
            isinstance(t, ast.Subscript) for t in parent.targets):
        return True
    for anc in _ancestors(call):
        if isinstance(anc, ast.If):
            for cmp_ in ast.walk(anc.test):
                if isinstance(cmp_, ast.Compare) and any(
                        isinstance(op, (ast.NotIn, ast.Is))
                        for op in cmp_.ops):
                    return True
        if isinstance(anc, _FUNCS):
            break
    return False


# -- detector: recompile-hazard ------------------------------------------------

def _detect_recompile(mod, findings):
    for info, depth, guarded in mod.creations:
        node = info.node
        if node.lineno in mod.pragmas:
            continue
        if depth > 0 and not guarded:
            findings.append(Finding(
                "jit", "recompile-hazard", "error",
                "%s:%d" % (mod.relpath, node.lineno),
                "jax.jit built inside a steady-state loop with no memo "
                "guard — every iteration traces and compiles a fresh "
                "program; hoist it or memoize under the loop's static "
                "key (the compile-count-per-bucket contract)"))
    # raw-shape taint per function: .shape-derived values must pass
    # through bucket_for before touching a memo key or traced closure
    for fn in mod.func_of:
        _shape_taint_func(mod, fn, findings)


def _shape_taint_func(mod, fn, findings):
    tainted = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        val = node.value
        from_shape = any(
            isinstance(s, ast.Attribute) and s.attr == "shape"
            for s in ast.walk(val))
        laundered = _has_call_to(val, ("bucket_for",))
        refs_taint = bool(_names_in(val) & tainted)
        for tgt in node.targets:
            names = ([tgt.id] if isinstance(tgt, ast.Name)
                     else [e.id for e in tgt.elts
                           if isinstance(e, ast.Name)]
                     if isinstance(tgt, ast.Tuple) else [])
            for nm in names:
                if laundered:
                    tainted.discard(nm)
                elif from_shape or refs_taint:
                    tainted.add(nm)
    if not tainted:
        return
    for node in ast.walk(fn):
        hit = None
        if isinstance(node, ast.Subscript) and _dotted(node.value) in \
                mod.jit_memos:
            bad = _names_in(node.slice) & tainted
            if bad:
                hit = ("jit-memo key", bad)
        elif isinstance(node, ast.Call) and _is_jax_jit(node):
            bad = set()
            for arg in node.args + [k.value for k in node.keywords
                                    if k.arg != "donate_argnums"]:
                bad |= _names_in(arg) & tainted
            if bad:
                hit = ("traced closure", bad)
        if hit is None or node.lineno in mod.pragmas:
            continue
        kind, bad = hit
        findings.append(Finding(
            "jit", "recompile-hazard", "error",
            "%s:%d" % (mod.relpath, node.lineno),
            "raw runtime shape %s reaches the %s in %s without passing "
            "through bucket_for — every distinct batch shape compiles a "
            "new program instead of hitting its bucket"
            % (sorted(bad), kind, mod.func_of[fn])))


# -- detector: donation-hazard -------------------------------------------------

def _positional_args(mod, call):
    """Resolved positional args (Starred *args expanded when the tuple
    is a visible local assignment)."""
    out = []
    fn = mod.enclosing_func(call)
    for arg in call.args:
        if isinstance(arg, ast.Starred):
            elts = None
            if isinstance(arg.value, ast.Name) and fn is not None:
                for node in ast.walk(fn):
                    if (isinstance(node, ast.Assign)
                            and any(isinstance(t, ast.Name)
                                    and t.id == arg.value.id
                                    for t in node.targets)
                            and isinstance(node.value, ast.Tuple)):
                        elts = node.value.elts
            if elts is None:
                return None  # opaque *args: give up on positions
            out.extend(elts)
        else:
            out.append(arg)
    return out


def _detect_donation(mod, findings):
    for call in ast.walk(mod.tree):
        if not isinstance(call, ast.Call):
            continue
        info = mod.dispatch_info(call)
        if info is None or call.lineno in mod.pragmas:
            continue
        fn = mod.enclosing_func(call)
        args = _positional_args(mod, call)
        in_loop = mod.loop_depth(call, stop=fn) > 0
        if info.donated and args is not None:
            stmt = _stmt_of(call)
            rebound = _rebound_targets(stmt, call)
            for pos in info.donated:
                if pos >= len(args):
                    continue
                path = _dotted(args[pos])
                if path is None or path in rebound:
                    continue
                use = _read_after(fn, stmt, path)
                if use is not None:
                    findings.append(Finding(
                        "jit", "donation-hazard", "error",
                        "%s:%d" % (mod.relpath, use.lineno),
                        "%r is read after being DONATED (argnum %d) to "
                        "the dispatch at line %d — the executable owns "
                        "that buffer now; thread the returned array "
                        "back instead (use-after-donate)"
                        % (path, pos, call.lineno)))
                elif in_loop and not _stored_in_loop(call, path, fn):
                    findings.append(Finding(
                        "jit", "donation-hazard", "error",
                        "%s:%d" % (mod.relpath, call.lineno),
                        "loop re-dispatches with %r at donated argnum "
                        "%d without rebinding it from the result — the "
                        "second iteration passes a buffer the first "
                        "donated away (thread it through, the "
                        "pool.swap discipline)" % (path, pos)))
        elif (not info.has_donate and in_loop and args is not None):
            poolish = sorted(
                p for p in (_dotted(a) for a in args) if p is not None
                and (p.split(".")[-1] in _POOLISH
                     or "pool" in p.split(".")[-1].lower()))
            if poolish:
                findings.append(Finding(
                    "jit", "donation-hazard", "warning",
                    "%s:%d" % (mod.relpath, call.lineno),
                    "steady-state loop dispatches %s through a program "
                    "built with no donate_argnums — every step pays a "
                    "device-side copy donation would elide"
                    % (poolish,)))


def _rebound_targets(stmt, call):
    """Dotted paths rebound by the very statement holding the dispatch
    (the donation-safe caller pattern: outputs replace inputs)."""
    out = set()
    if isinstance(stmt, ast.Assign) and _contains(stmt.value, call):
        for tgt in stmt.targets:
            todo = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
            for t in todo:
                d = _dotted(t)
                if d is not None:
                    out.add(d)
    return out


def _contains(root, node):
    return any(n is node for n in ast.walk(root))


def _read_after(fn, stmt, path):
    """First Load of ``path`` after ``stmt`` (and before any re-store)
    inside ``fn``; None when it is stored first or never touched."""
    if fn is None:
        return None
    after = (stmt.end_lineno or stmt.lineno, getattr(stmt, "end_col_offset",
                                                     0) or 0)
    first_load = first_store = None
    for node in ast.walk(fn):
        pos = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
        if pos <= after:
            continue
        d = _dotted(node) if isinstance(node, (ast.Name,
                                               ast.Attribute)) else None
        if d != path:
            continue
        is_store = isinstance(getattr(node, "ctx", None), ast.Store)
        if is_store:
            if first_store is None or pos < first_store[0]:
                first_store = (pos, node)
        else:
            # skip the chain interior of a longer dotted store
            anc = getattr(node, "_mxjit_p", None)
            if isinstance(anc, ast.Attribute) and isinstance(
                    getattr(anc, "ctx", None), ast.Store):
                continue
            if first_load is None or pos < first_load[0]:
                first_load = (pos, node)
    if first_load is None:
        return None
    if first_store is not None and first_store[0] < first_load[0]:
        return None
    return first_load[1]


def _stored_in_loop(call, path, fn):
    loop = None
    for anc in _ancestors(call):
        if isinstance(anc, _LOOPS):
            loop = anc
            break
        if isinstance(anc, _FUNCS):
            break
    if loop is None:
        return True
    for node in ast.walk(loop):
        if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
                getattr(node, "ctx", None), ast.Store):
            if _dotted(node) == path:
                return True
    return False


# -- detector: hot-d2h ---------------------------------------------------------

def _is_dispatch_hint(mod, call):
    if mod.dispatch_info(call) is not None:
        return True
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        if attr in _DISPATCH_HINT_ANY:
            return True
        if attr in _DISPATCH_HINT_RECV:
            recv = _dotted(call.func.value)
            if recv is not None and recv.split(".")[-1] in \
                    _DISPATCH_RECEIVERS:
                return True
    return False


def _dispatcher_funcs(mod):
    """Functions that (transitively, same module) dispatch a program."""
    direct = set()
    for qual, fn in mod.funcs.items():
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and _is_dispatch_hint(mod, node):
                direct.add(qual)
                break
    # fixpoint over same-module calls by trailing name
    tails = {q.split(".")[-1]: q for q in mod.funcs}
    changed = True
    while changed:
        changed = False
        for qual, fn in mod.funcs.items():
            if qual in direct:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                if d is None:
                    continue
                callee = tails.get(d.split(".")[-1])
                if callee in direct:
                    direct.add(qual)
                    changed = True
                    break
    return direct


def _hot_regions(mod):
    """(hot loops, hot functions): loops that dispatch, plus functions
    reachable from them within the module (depth-limited — a drain
    helper two calls away still belongs to the per-step loop)."""
    dispatchers = _dispatcher_funcs(mod)
    tails = {q.split(".")[-1]: q for q in mod.funcs}
    hot_loops = []
    seeds = set()
    for node in ast.walk(mod.tree):
        if not isinstance(node, _LOOPS):
            continue
        for sub in ast.walk(node):
            called = None
            if isinstance(sub, ast.Call):
                if _is_dispatch_hint(mod, sub):
                    hot_loops.append(node)
                    break
                d = _dotted(sub.func)
                called = d and tails.get(d.split(".")[-1])
            if called in dispatchers:
                hot_loops.append(node)
                break
    for loop in hot_loops:
        for sub in ast.walk(loop):
            if isinstance(sub, ast.Call):
                d = _dotted(sub.func)
                q = d and tails.get(d.split(".")[-1])
                if q:
                    seeds.add(q)
    hot_funcs = set(seeds)
    frontier = set(seeds)
    for _ in range(2):  # bounded call-through escalation
        nxt = set()
        for qual in frontier:
            for node in ast.walk(mod.funcs[qual]):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    q = d and tails.get(d.split(".")[-1])
                    if q and q not in hot_funcs:
                        nxt.add(q)
        hot_funcs |= nxt
        frontier = nxt
    return hot_loops, hot_funcs


def _sync_call(mod, call, device_tainted):
    """Short sync label when ``call`` is a device->host sync."""
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr in _SYNC_ATTRS:
        return ".%s()" % f.attr
    d = _dotted(f)
    if d is not None:
        parts = d.split(".")
        if parts[-1] == "asarray" and parts[0] in _NP_ROOTS:
            # np.asarray over a Python list/scalar literal is H2D
            # staging, not a sync; only a device-flowing argument
            # (dispatch-result taint, or the _dev naming convention)
            # makes it a D2H pull
            if not call.args:
                return None
            arg = call.args[0]
            names = _names_in(arg)
            if names & device_tainted or any(
                    n.endswith("_dev") for n in names):
                return "np.asarray"
            # instance device state: self.params / pool attrs are
            # resident arrays, pulling them is a real transfer
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Attribute) and (
                        sub.attr in ("params", "draft_params")
                        or "pool" in sub.attr):
                    return "np.asarray"
            return None
        if d == "jax.device_get":
            return "jax.device_get"
        if d in _HOST_CASTS and call.args:
            if _names_in(call.args[0]) & device_tainted:
                return "%s()" % d
    return None


def _fence_names(fn):
    """Names assigned via the one-fence-per-chunk idiom:
    ``bur = getattr(o, "block_until_ready", None)`` — the module's
    explicit marker that the next pull is the chunk's single fence."""
    names, linenos = set(), []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _dotted(node.value.func) == "getattr"
                and len(node.value.args) >= 2
                and isinstance(node.value.args[1], ast.Constant)
                and node.value.args[1].value == "block_until_ready"):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                    linenos.append(node.lineno)
    return names, linenos


def _prof_guarded(node):
    for anc in _ancestors(node):
        if isinstance(anc, ast.If):
            test_names = {n.attr for n in ast.walk(anc.test)
                          if isinstance(anc.test, ast.AST)
                          and isinstance(n, ast.Attribute)}
            test_names |= _names_in(anc.test)
            if test_names & {"ENABLED", "prof_on", "enabled", "prof_ctx",
                             "prof_t"}:
                return True
        if isinstance(anc, _FUNCS):
            break
    return False


def _detect_hot_d2h(mod, findings, sanctioned):
    hot_loops, hot_funcs = _hot_regions(mod)
    seen = set()
    for fn_qual in sorted(set(hot_funcs) | {
            mod.func_of[mod.enclosing_func(lp)]
            for lp in hot_loops if mod.enclosing_func(lp) is not None}):
        fn = mod.funcs[fn_qual]
        fences, fence_lines = _fence_names(fn)
        device_tainted = _device_tainted(mod, fn)
        for call in ast.walk(fn):
            if not isinstance(call, ast.Call) or id(call) in seen:
                continue
            label = _sync_call(mod, call, device_tainted)
            if label is None:
                continue
            in_hot_loop = any(_contains(lp, call) for lp in hot_loops)
            if not in_hot_loop and fn_qual not in hot_funcs:
                continue
            seen.add(id(call))
            site = "%s::%s" % (mod.relpath, fn_qual)
            if call.lineno in mod.pragmas:
                sanctioned[site] = call.lineno
                continue
            # fence-idiom call: bur() where bur came from the getattr
            if (isinstance(call.func, ast.Name)
                    and call.func.id in fences):
                sanctioned[site] = call.lineno
                findings.append(Finding(
                    "jit", "hot-d2h", "info",
                    "%s:%d" % (mod.relpath, call.lineno),
                    "one-fence-per-chunk fence in %s (sanctioned)"
                    % fn_qual))
                continue
            if _prof_guarded(call):
                sanctioned[site] = call.lineno
                findings.append(Finding(
                    "jit", "hot-d2h", "info",
                    "%s:%d" % (mod.relpath, call.lineno),
                    "%s under a profiling/telemetry ENABLED guard in %s "
                    "(off-by-default, sanctioned)" % (label, fn_qual)))
                continue
            if (label in ("np.asarray", ".asnumpy()") and fence_lines
                    and min(fence_lines) < call.lineno):
                sanctioned[site] = call.lineno
                findings.append(Finding(
                    "jit", "hot-d2h", "info",
                    "%s:%d" % (mod.relpath, call.lineno),
                    "post-fence chunk pull in %s — one D2H per drained "
                    "chunk (sanctioned)" % fn_qual))
                continue
            where_note = ("inside the per-step loop"
                          if in_hot_loop else
                          "in %s, called from a per-step loop" % fn_qual)
            findings.append(Finding(
                "jit", "hot-d2h", "error",
                "%s:%d" % (mod.relpath, call.lineno),
                "%s %s — a device->host sync on the hot path stalls "
                "the dispatch pipeline every step; keep it on device, "
                "batch it behind the chunk fence, or pragma a "
                "deliberate accounted pull" % (label, where_note)))


def _device_tainted(mod, fn):
    """Names holding device values: dispatch results, closed over
    simple flow (``n = fix(n_dev)`` keeps the taint)."""
    out = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            val = node.value
            is_disp = (isinstance(val, ast.Call)
                       and _is_dispatch_hint(mod, val))
            if not is_disp:
                # a host-materializing call (asarray/.item()/float())
                # ENDS the taint: its result lives on the host
                if isinstance(val, ast.Call):
                    d = _dotted(val.func)
                    tail = d.split(".")[-1] if d else ""
                    if (tail in ("asarray", "device_get", "item",
                                 "tolist", "asnumpy")
                            or d in _HOST_CASTS):
                        continue
                if not (_names_in(val) & out):
                    continue
            for tgt in node.targets:
                todo = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                for t in todo:
                    if isinstance(t, ast.Name) and t.id not in out:
                        out.add(t.id)
                        changed = True
    return out


# -- detector: weak-cache-key --------------------------------------------------

def _detect_weak_key(mod, findings):
    # mechanical half: attribute_jit without graph_key — the exact
    # shape-only aliasing hole PR 13 patched
    for call in ast.walk(mod.tree):
        if not isinstance(call, ast.Call):
            continue
        d = _dotted(call.func)
        if d is None or d.split(".")[-1] != "attribute_jit":
            continue
        if call.lineno in mod.pragmas:
            continue
        if not any(k.arg == "graph_key" for k in call.keywords):
            findings.append(Finding(
                "jit", "weak-cache-key", "error",
                "%s:%d" % (mod.relpath, call.lineno),
                "attribute_jit called without graph_key= — a shape-only "
                "attribution key aliases different graphs at equal "
                "shapes (the PR 13 bug class); fold a graph_hash of the "
                "program's structural identity into the key"))
    # closure half: builder inputs reaching the traced body must be
    # folded into the memo key
    for info, _depth, _guarded in mod.creations:
        _check_closure_key(mod, info, findings)


def _key_expr_for(mod, info):
    """The memo-key expression(s) + builder-call arg mapping for a jit
    creation: the store site in the builder itself, or a caller storing
    the builder's return into a memo."""
    keys = []
    fn = info.builder
    if fn is not None:
        jit_names = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            # only stores whose VALUE is this jitted program (directly,
            # or via a local bound from it) are memo-key sites — an
            # arbitrary ``d[k] = v`` in the builder is not a cache
            is_this = (node.value is info.node
                       or (isinstance(node.value, ast.Name)
                           and node.value.id in jit_names))
            if node.value is info.node:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jit_names.add(t.id)
            if not is_this:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript):
                    keys.append((node, tgt.slice, None))
    if fn is not None and mod.func_of.get(fn) in mod.returns_jitted:
        tail = mod.func_of[fn].split(".")[-1]
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            val = node.value
            if not (isinstance(val, ast.Call) and _dotted(val.func)
                    and _dotted(val.func).split(".")[-1] == tail):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript):
                    keys.append((node, tgt.slice, val))
    return keys


def _key_derived(fn, key_slice):
    """Names in the key expr, closed over simple rebindings
    (``kind, B, C = key`` makes all three key-derived)."""
    derived = _names_in(key_slice)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if not (_names_in(node.value) and
                    _names_in(node.value) <= derived | {"self"}):
                continue
            for tgt in node.targets:
                todo = (tgt.elts if isinstance(tgt, ast.Tuple) else [tgt])
                for t in todo:
                    if isinstance(t, ast.Name) and t.id not in derived:
                        derived.add(t.id)
                        changed = True
    return derived


def _traced_bodies(mod, info):
    """AST bodies jax.jit will trace for this creation: a lambda, a
    nested def, or class methods (incl. the class-level kind->method
    string-table indirection)."""
    expr = info.traced
    fn = info.builder
    bodies = []
    bound_names = set()

    def resolve(e):
        if isinstance(e, ast.Lambda):
            bodies.append(e)
        elif isinstance(e, ast.Call):
            d = _dotted(e.func)
            if d is not None and d.split(".")[-1] == "partial" and e.args:
                resolve(e.args[0])
                for a in e.args[1:]:
                    bound_names.update(_names_in(a))
                for k in e.keywords:
                    bound_names.update(_names_in(k.value))
            elif d == "getattr" and len(e.args) >= 2:
                cls = mod.enclosing_class(info.node)
                arg = e.args[1]
                if (cls is not None and isinstance(arg, ast.Subscript)):
                    base = _dotted(arg.value)
                    if base is not None:
                        names = mod.str_dicts.get(
                            (cls.name, base.split(".")[-1]), [])
                        for mname in names:
                            m = mod.funcs.get("%s.%s" % (cls.name, mname))
                            if m is not None:
                                bodies.append(m)
        elif isinstance(e, ast.Name):
            if fn is not None:
                for node in ast.walk(fn):
                    if isinstance(node, _FUNCS) and node.name == e.id:
                        bodies.append(node)
                        return
                for node in ast.walk(fn):
                    if (isinstance(node, ast.Assign)
                            and any(isinstance(t, ast.Name)
                                    and t.id == e.id
                                    for t in node.targets)):
                        resolve(node.value)
                        return
        elif isinstance(e, ast.Attribute):
            d = _dotted(e)
            cls = mod.enclosing_class(info.node)
            if (d is not None and d.startswith("self.")
                    and cls is not None):
                m = mod.funcs.get("%s.%s" % (cls.name, d[5:]))
                if m is not None:
                    bodies.append(m)

    if expr is not None:
        resolve(expr)
    return bodies, bound_names


def _check_closure_key(mod, info, findings):
    fn = info.builder
    if fn is None or info.node.lineno in mod.pragmas:
        return
    keys = _key_expr_for(mod, info)
    if not keys:
        return  # no memo: a build-once program has no key to weaken
    bodies, bound = _traced_bodies(mod, info)
    if not bodies:
        return
    params = {a.arg for a in fn.args.args if a.arg != "self"}
    params |= {a.arg for a in fn.args.kwonlyargs}
    free_reads = set()
    for body in bodies:
        own = set()
        if isinstance(body, _FUNCS):
            own = {a.arg for a in body.args.args} | {
                a.arg for a in body.args.kwonlyargs}
            if body.args.vararg:
                own.add(body.args.vararg.arg)
        elif isinstance(body, ast.Lambda):
            own = {a.arg for a in body.args.args}
        for node in ast.walk(body):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in params and node.id not in own):
                free_reads.add(node.id)
    free_reads |= (bound & params)
    if not free_reads:
        _check_self_reads(mod, info, bodies, keys, findings)
        return
    for store, key_slice, builder_call in keys:
        derived = _key_derived(fn, key_slice)
        if builder_call is not None:
            # caller maps builder params -> arg exprs: a param is keyed
            # when its arg expression shares a name with the key
            keyed = set()
            pnames = [a.arg for a in fn.args.args if a.arg != "self"]
            for i, a in enumerate(builder_call.args):
                if i < len(pnames) and (_names_in(a) & derived):
                    keyed.add(pnames[i])
            derived = derived | keyed
        leaked = sorted(free_reads - derived)
        if leaked:
            findings.append(Finding(
                "jit", "weak-cache-key", "error",
                "%s:%d" % (mod.relpath, info.node.lineno),
                "config input(s) %s reach the traced program body but "
                "are not folded into the jit-cache key at line %d — two "
                "different configurations alias one compiled program "
                "(the PR 13/15 bug class); fold them into the key or "
                "the graph hash" % (leaked, store.lineno)))
    _check_self_reads(mod, info, bodies, keys, findings)


def _check_self_reads(mod, info, bodies, keys, findings):
    cls = mod.enclosing_class(info.node)
    if cls is None:
        return
    writers = mod.class_attr_writers.get(cls, {})
    creators = mod.class_creators.get(cls, set())
    key_names = set()
    for _store, key_slice, _bc in keys:
        key_names |= _names_in(key_slice)
        for n in ast.walk(key_slice):
            if isinstance(n, ast.Attribute):
                key_names.add(n.attr)
    mutable_reads = set()
    for body in bodies:
        for node in ast.walk(body):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                who = writers.get(node.attr, set())
                if (who - {"__init__"} - creators
                        and node.attr not in key_names):
                    mutable_reads.add(node.attr)
    if mutable_reads and info.node.lineno not in mod.pragmas:
        findings.append(Finding(
            "jit", "weak-cache-key", "error",
            "%s:%d" % (mod.relpath, info.node.lineno),
            "traced body reads mutable instance config %s (reassigned "
            "outside __init__) without folding it into the jit-cache "
            "key — the program bakes a stale value and never recompiles "
            "when it changes" % sorted(mutable_reads)))


# -- public API ----------------------------------------------------------------

def lint_source(src, relpath="<string>", _sanctioned=None):
    tree = ast.parse(src)
    mod = _Module(tree, relpath, src)
    findings = []
    sanctioned = {} if _sanctioned is None else _sanctioned
    _detect_recompile(mod, findings)
    _detect_donation(mod, findings)
    _detect_hot_d2h(mod, findings, sanctioned)
    _detect_weak_key(mod, findings)
    return findings


def lint_file(path, root=None, _sanctioned=None):
    root = root or os.path.dirname(DEFAULT_PACKAGE)
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    rel = os.path.relpath(path, root)
    return lint_source(src, rel, _sanctioned=_sanctioned)


def _iter_targets(path):
    if os.path.isfile(path):
        yield path
        return
    for dirpath, dirs, files in os.walk(path):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fname in sorted(files):
            if fname.endswith(".py"):
                yield os.path.join(dirpath, fname)


def lint_targets(path=None, _sanctioned=None):
    """Lint ``path`` (file or dir), or the DEFAULT_TARGETS surface of
    the package when None — the clean-repo gate entry point."""
    findings = []
    if path:
        for p in _iter_targets(path):
            findings.extend(lint_file(p, _sanctioned=_sanctioned))
        return findings
    for rel in DEFAULT_TARGETS:
        p = os.path.join(DEFAULT_PACKAGE, rel)
        if not os.path.exists(p):
            continue
        for f in _iter_targets(p):
            findings.extend(lint_file(f, _sanctioned=_sanctioned))
    return findings


def sanctioned_d2h_sites(path=None):
    """The static half of the runtime cross-check: every hot-path D2H
    site the lint sanctioned (pragma'd, fence-idiom, prof-guarded or
    post-fence pulls), keyed ``relpath::qualname``.  compile_verify's
    observed ledger is diffed against this set."""
    sanctioned = {}
    lint_targets(path, _sanctioned=sanctioned)
    return sanctioned


def cross_check(static_sites, observed_sites):
    """Diff observed runtime D2H ledger sites against the lint's
    sanctioned set (the lock_lint cross_check pattern): an observed
    pull the lint never sanctioned is an error (an unaccounted hot-path
    transfer crept in past the static pass); a sanctioned site never
    observed is an info (dead sanction — audit whether the pragma still
    earns its place)."""
    findings = []
    static_funcs = {s.split("::", 1)[-1].split(":")[0] if "::" not in s
                    else s for s in static_sites}
    for site in sorted(observed_sites):
        base = site.split(":")[0] + "::" + site.split("::", 1)[-1] \
            if "::" in site else site
        if site in static_sites or base in static_funcs:
            continue
        findings.append(Finding(
            "jit", "hot-d2h", "error", site,
            "runtime D2H ledger observed a device->host pull at a site "
            "the static lint never sanctioned — an unaccounted hot-path "
            "transfer (add it to the contract or remove it)"))
    for site in sorted(static_sites):
        if site not in observed_sites:
            findings.append(Finding(
                "jit", "hot-d2h", "info", site,
                "sanctioned D2H site never observed by the runtime "
                "ledger this run — dead sanction or an unexercised "
                "path"))
    return findings
