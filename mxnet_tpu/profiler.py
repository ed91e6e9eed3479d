"""Profiler: xplane trace capture, per-op annotation, and device time by
named scope.

The 2016 reference has no dedicated profiler (SURVEY §5.1): its
observability is the Monitor per-op callback (python/mxnet/monitor.py),
the Speedometer samples/sec log, and `MXNET_ENGINE_INFO` engine debug.
This module supplies the piece the reference lacks, as SURVEY §5.1's TPU
plan prescribes: the jax/XLA profiler (xplane traces viewable in
TensorBoard/Perfetto, including TPU HLO timelines) behind an mxnet-style
start/stop surface. Monitor stays the per-op numeric hook; this is the
timeline hook.

Usage::

    mx.profiler.profiler_set_config(filename="/tmp/traces")
    mx.profiler.profiler_set_state("run")
    ... training steps ...
    mx.profiler.profiler_set_state("stop")   # writes the xplane trace

    with mx.profiler.scope("data-load"):     # named trace region
        batch = next(it)

    @mx.profiler.annotate("fwd-step")        # annotate a function
    def step(...): ...

Whose time it was (docs/how_to/profiling.md, "Device time by scope"). A
v5e capture names a device operation by its HLO instruction and carries no
``op_name``, so the ``jax.named_scope`` an operation was traced under has
to come from the program: :func:`scope_map` reads it off the compiled
step's text, :func:`scope_times` joins such maps to a capture, and a
capture made through this module writes the maps of the programs it was
handed (:func:`note_program`) beside the trace, as ``scopes.json``::

    compiled = step_fn.jitted.lower(params, opt_state, batch, rng).compile()
    table = mx.profiler.scope_times(trace_dir, [compiled])
    python tools/telemetry_report.py --xplane <trace_dir>
"""
from __future__ import annotations

import bisect
import glob
import json
import logging
import os
import re
import statistics

__all__ = [
    "profiler_set_config", "profiler_set_state", "scope", "annotate", "state",
    "scope_map", "program_name", "note_program", "scope_times",
    "SCOPES_FILE", "SPAN_MARK",
]

_config = {"filename": "profile_output"}
_state = "stop"
#: what ``note_program`` was handed since the capture started:
#: ``[(program, abstract arguments or None)]`` and the ids already there
_noted = []
_noted_ids = set()

#: the file ``profiler_set_state("stop")`` writes beside the trace
SCOPES_FILE = "scopes.json"
#: the stat every :func:`scope` event carries in a capture: how
#: :func:`scope_times` tells the program's spans from jax's own host events
SPAN_MARK = "mx_span"


def profiler_set_config(mode="all", filename="profile_output"):
    """Configure the trace output directory (mirrors the later-era
    MXSetProfilerConfig surface; `mode` accepted for compatibility)."""
    del mode
    _config["filename"] = filename


def profiler_set_state(new_state="stop"):
    """'run' starts capture, 'stop' ends it and writes the trace
    (mirrors MXSetProfilerState) and, beside it, ``scopes.json``: the
    scope maps of the programs handed over while it ran."""
    global _state
    import jax

    if new_state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if new_state == _state:
        return
    if new_state == "run":
        os.makedirs(_config["filename"], exist_ok=True)
        del _noted[:]
        _noted_ids.clear()
        jax.profiler.start_trace(_config["filename"])
    else:
        jax.profiler.stop_trace()
        _write_scopes(_config["filename"])
    _state = new_state


def state():
    return _state


def scope(name, step=None):
    """Named region visible in the trace timeline (``TraceAnnotation``;
    with ``step`` a ``StepTraceAnnotation`` carrying the step's number, so
    the profiler's own tools group device work by step). The event carries
    the stat ``mx_span``: :func:`scope_times` names idle gaps by such
    events. Costs one C++ check while no capture runs."""
    import jax

    if step is None:
        return jax.profiler.TraceAnnotation(name, **{SPAN_MARK: 1})
    return jax.profiler.StepTraceAnnotation(name, step_num=step,
                                            **{SPAN_MARK: 1})


def annotate(name=None):
    """Decorator: wrap a function in a named trace region."""
    def deco(fn):
        import functools

        label = name or fn.__name__

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with scope(label):
                return fn(*a, **k)

        return wrapped

    return deco


# -- the scope map: read off the compiled program ------------------------------

#: parts of an ``op_name`` that jax writes itself, around the scopes a
#: program opened: the primitives that hold a sub-computation
_JAX_PARTS = frozenset((
    "checkpoint", "remat", "remat2", "while", "body", "cond", "body_fun",
    "cond_fun", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin", "pallas_call",
    "shard_map", "named_call", "scan", "xla_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
#: what a scope's name is made of; ``jnp.einsum`` pushes its subscripts
#: (``btd,de->bte``), which are no scope
_SCOPE_PART = re.compile(r"^[\w.\-]+$")
#: ``jit(step)``: what the parentheses hold is a function's name, no scope
_NAMES_A_FUNCTION = frozenset(("jit", "pjit", "xla_call"))
_CALL = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$", re.S)
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->.*)?\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)\]}])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_NAMED = re.compile(r"%([\w.\-]+)")
#: the opcodes that ARE a fusion's work: a fused instruction of another
#: scope makes the fusion ``mixed`` only if it is one of these (a
#: broadcast constant of the loss's ``1 / n`` rides in every fusion)
_WORK = frozenset((
    "dot", "convolution", "reduce", "reduce-window", "scatter", "gather",
    "sort", "select-and-scatter", "custom-call"))
#: how far a compiler-made instruction looks for the scope it works for
_INHERIT_HOPS = 6
_INHERIT_WIDTH = 64

PASSES = ("forward", "backward", "rebuilt", "update")


def _split(path):
    """``path`` cut at the slashes outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(path):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def parse_op_name(op_name):
    """``(scope, pass)`` of one instruction's ``op_name``: the named-scope
    path with jax's own wrappers taken off, ``"unscoped"`` where nothing is
    left, and ``forward`` / ``backward`` (under a ``transpose(``) /
    ``rebuilt`` (under ``rematted_computation``) / ``update`` (the path
    starts at ``optimizer``). The last part is the primitive and is
    dropped; ``None`` gives ``("unscoped", "unknown")``."""
    if not op_name:
        return "unscoped", "unknown"
    scopes = []
    seen = {"backward": False, "rebuilt": False}

    def walk(parts):
        for part in parts:
            called = _CALL.match(part)
            if called:
                fn, inner = called.groups()
                if fn == "transpose":
                    seen["backward"] = True
                if fn not in _NAMES_A_FUNCTION:
                    walk(_split(inner))
            elif part == "rematted_computation":
                seen["rebuilt"] = True
            elif (_SCOPE_PART.match(part) and part not in _JAX_PARTS
                  and not _BRANCH.match(part) and scopes[-1:] != [part]):
                # (a checkpoint traced inside a scope repeats the scope in
                # its backward pass: ``transpose(jvp(mtp))/checkpoint/mtp/..``)
                scopes.append(part)

    walk(_split(op_name)[:-1])
    if seen["rebuilt"]:
        which = "rebuilt"
    elif scopes and scopes[0] == "optimizer":
        which = "update"
    else:
        which = "backward" if seen["backward"] else "forward"
    return "/".join(scopes) or "unscoped", which


def _as_text(program):
    if isinstance(program, str):
        return program
    return program.as_text()


def parse_hlo(text):
    """The instructions of an HLO module's text: ``(module name,
    [{"name", "opcode", "computation", "op_name", "calls", "operands"}])``,
    ``calls`` the computations an instruction names (a fusion's, a loop's
    body), ``operands`` every ``%name`` its line reads."""
    module, computation, found = None, None, []
    for line in text.splitlines():
        if computation is None:
            if module is None:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
                    continue
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        opcode = _OPCODE.search(rest)
        op_name = _OP_NAME.search(rest)
        calls = _CALLED.findall(rest)
        branches = _BRANCHES.search(rest)
        if branches:
            calls += [b.strip().lstrip("%")
                      for b in branches.group(1).split(",") if b.strip()]
        found.append({"name": name, "computation": computation,
                      "opcode": opcode.group(1) if opcode else "",
                      "op_name": op_name.group(1) if op_name else None,
                      "calls": calls, "operands": _NAMED.findall(rest)})
    return module, found


def program_name(program):
    """The module name of a compiled program or its text (``jit_step``):
    what a capture's ``XLA Modules`` events are named by."""
    m = _MODULE.match(_as_text(program))
    return m.group(1) if m else None


def scope_map(program):
    """``{instruction name: (scope, pass)}`` for every instruction of every
    computation of ``program`` (a ``jax.stages.Compiled`` or its text).

    A fusion takes its own instruction's metadata; one that carries none
    (the TPU compiler's multi-output fusions) takes the scope most of its
    fused instructions agree on. Where the fused dots, convolutions,
    reductions and kernels disagree on the FIRST scope component the scope
    reads ``mixed(a+b)``, the fusion's own component first: a matmul with
    a norm's reduction as its epilogue is ``mixed(mlp+norm)``, a weight's
    gradient with Adam's update behind it ``mixed(mlp+optimizer)``. An
    instruction under no scope, as a rule one the compiler made (a layout
    copy, a prefetch, a bitcast: no metadata, or a parameter's name),
    works for whoever reads it, and takes the scope and pass of its
    nearest scoped user, else operand. Nothing is compiled here."""
    _, instructions = parse_hlo(_as_text(program))
    members, users = {}, {}
    for ins in instructions:
        members.setdefault(ins["computation"], []).append(ins)
        for name in ins["operands"]:
            users.setdefault(name, []).append(ins)

    def fused(ins, seen):
        """(scope, pass, opcode) of every scoped instruction in a fusion."""
        out = []
        for comp in ins["calls"]:
            if comp in seen:
                continue
            seen.add(comp)
            for inner in members.get(comp, ()):
                got = parse_op_name(inner["op_name"])
                if got[0] != "unscoped":
                    out.append(got + (inner["opcode"],))
                out.extend(fused(inner, seen))
        return out

    mapped = {}
    for ins in instructions:
        own = parse_op_name(ins["op_name"])
        if ins["opcode"] == "fusion":
            inside = fused(ins, set())
            work = [(s, p) for s, p, op in inside if op in _WORK]
            if own[0] == "unscoped" and inside:
                votes = work or [(s, p) for s, p, _ in inside]
                own = max(set(votes), key=votes.count)
            first = own[0].split("/")[0]
            # the optimizer's update is elementwise: fused into a weight's
            # gradient it is that fusion's memory traffic, and is named
            others = sorted({s.split("/")[0] for s, p in work + [
                (s, p) for s, p, _ in inside if p == "update" != own[1]]}
                - {first})
            if others:
                own = ("mixed(%s)" % "+".join([first] + others), own[1])
        mapped[ins["name"]] = own

    by_name = {ins["name"]: ins for ins in instructions}
    for ins in instructions:
        if ins["opcode"] in ("parameter", "constant") or mapped[
                ins["name"]][0] != "unscoped":
            continue
        for nearby in (lambda i: users.get(i["name"], ()),
                       lambda i: [by_name[n] for n in i["operands"]
                                  if n in by_name]):
            frontier, found = [ins], None
            for _ in range(_INHERIT_HOPS):
                frontier = list({n["name"]: n for i in frontier
                                 for n in nearby(i)}.values())[:_INHERIT_WIDTH]
                found = next((mapped[n["name"]] for n in frontier
                              if mapped[n["name"]][0] != "unscoped"), None)
                if found or not frontier:
                    break
            if found:
                mapped[ins["name"]] = found
                break
    return mapped


def note_program(program, *args):
    """Hand the running capture a program it will see, so that
    ``profiler_set_state("stop")`` writes its scope map beside the trace.
    ``program``: a ``jax.stages.Compiled``, HLO text, or a jitted function
    with the arguments it is called with (arrays or ``ShapeDtypeStruct``s:
    only their shapes, types and shardings are kept). A jitted function is
    lowered when the capture STOPS, outside the traced window; with a
    compile cache placed its executable is loaded, not built again. Does
    nothing unless a capture through this module is running, and once a
    program a capture. Returns whether the program was taken."""
    if _state != "run" or id(program) in _noted_ids:
        return False
    if hasattr(program, "as_text") or isinstance(program, str):
        args = ()
    if args:
        import jax

        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
            args)
    _noted_ids.add(id(program))
    _noted.append((program, args or None))
    return True


def _write_scopes(trace_dir):
    programs = {}
    for program, args in _noted:
        try:
            if args is not None:
                program = program.lower(*args).compile()
            text = _as_text(program)
            mapped = programs[program_name(text)] = scope_map(text)
            bare = sum(s == "unscoped" for s, _ in mapped.values())
            if 2 * bare > len(mapped):
                # jax keys its compile cache without the metadata: an
                # executable loaded from an entry that another version of
                # the source wrote carries THAT version's scopes
                logging.warning(
                    "profiler: %d of %d instructions of %s carry no scope: "
                    "if the program opens scopes, its executable came from "
                    "a compile cache written before they were there (clear "
                    "it, or set jax_compilation_cache_include_metadata_in_"
                    "key)", bare, len(mapped), program_name(text))
        except Exception as e:  # the trace itself is already written
            logging.warning("profiler: no scope map for %r (%s: %s)",
                            program, type(e).__name__, e)
    del _noted[:]
    _noted_ids.clear()
    with open(os.path.join(trace_dir, SCOPES_FILE), "w") as f:
        json.dump({"programs": programs}, f)


# -- the reader: a capture joined to the maps ----------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: gaps longer than the rest are named; the rest are microseconds
_NAMED_GAPS = 1000
#: a run of the step this much over the median run is reported
_SLOW_RUN = 1.2


def _find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return found[-1]


def read_capture(path):
    """A capture as plain data: ``{"devices": [{"ops": [(event name,
    start ns, duration ns)], "modules": [...]}], "spans": [(name, start
    ns, duration ns)]}``: each chip's ``XLA Ops`` and ``XLA Modules``
    lines and the host events that carry ``mx_span``. A capture with no
    TPU plane (made on the CPU) gives one "device" from the host's
    ``hlo_op`` events, its runs from their ``run_id``: for trying the
    reader, never a device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_find_xplane(path))
    devices, spans, host_ops = [], [], {}
    for plane in data.planes:
        on_device = _DEVICE_PLANE.match(plane.name)
        if on_device:
            lines = {line.name: [(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns))
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            devices.append((int(on_device.group(1)), {
                "ops": lines.get("XLA Ops", []),
                "modules": lines.get("XLA Modules", [])}))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith("$"):  # the Python tracer's own
                    continue
                stats = dict(ev.stats)
                if SPAN_MARK in stats:
                    spans.append((name, float(ev.start_ns),
                                  float(ev.duration_ns)))
                elif "hlo_op" in stats and "hlo_module" in stats:
                    host_ops.setdefault(
                        (stats["hlo_module"], stats.get("run_id")),
                        []).append((stats["hlo_op"], float(ev.start_ns),
                                    float(ev.duration_ns)))
    devices = [d for _, d in sorted(devices, key=lambda d: d[0])]
    if not devices and host_ops:
        ops, modules = [], []
        for (module, _), events in host_ops.items():
            start = min(s for _, s, _ in events)
            end = max(s + d for _, s, d in events)
            modules.append((module, start, end - start))
            ops.extend(events)
        devices = [{"ops": ops, "modules": modules}]
    return {"devices": devices, "spans": spans}


def _instruction(event_name):
    """``fusion.12`` of ``%fusion.12 = bf16[8,1024]{1,0} fusion(...)``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def _label(event_name):
    """``fusion bf16[8,1024]``: kind and result shape, instances summed
    (as ``benchmark/trace_reduce.py: op_label`` names a line)."""
    kind = re.sub(r"[.:]\d+$", "", _instruction(event_name).split("(")[0])
    shape = re.match(r"\(*([a-z0-9]+\[[0-9,]*\])",
                     event_name.split(" = ", 1)[-1]
                     ) if " = " in event_name else None
    return "%s %s" % (kind, shape.group(1)) if shape else kind


def _module(event_name):
    """``jit_step`` of ``jit_step(1382...)``."""
    return event_name.split("(")[0].strip()


def _self_times(events):
    """Nanoseconds each event covers that no event inside it does. Events
    of one line nest or follow each other: an enclosing ``while`` or
    ``conditional`` is left with what its children do not cover, so the
    self times sum to the line's busy union."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [ev[2] for ev in events]
    stack = []  # (end, index) of the events still open
    for i in order:
        _, start, dur = events[i]
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent_end, parent = stack[-1]
            own[parent] -= min(end, parent_end) - start
        stack.append((end, i))
    return [max(v, 0.0) for v in own]


def _union(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _name_gap(gap, spans, starts, longest):
    """The innermost span that covers half of ``gap``."""
    start, end = gap
    best, best_dur = "unattributed", None
    i = bisect.bisect_left(starts, start - longest)
    while i < len(spans) and spans[i][1] < end:
        name, s, d = spans[i]
        cover = min(end, s + d) - max(start, s)
        if cover >= 0.5 * (end - start) and (best_dur is None
                                             or d < best_dur):
            best, best_dur = name, d
        i += 1
    return best


def _load_programs(trace_dir, programs):
    """``{module name: scope map}`` from what the caller handed over (a
    dict of maps, or a list of compiled programs / texts) or, with
    nothing, from the ``scopes.json`` beside the trace."""
    if programs is None:
        path = trace_dir if os.path.isdir(trace_dir) else os.path.dirname(
            trace_dir)
        found = sorted(glob.glob(os.path.join(path, "**", SCOPES_FILE),
                                 recursive=True))
        if not found:
            return {}
        with open(found[-1]) as f:
            return {k: {i: tuple(v) for i, v in m.items()}
                    for k, m in json.load(f)["programs"].items()}
    if isinstance(programs, dict):
        return programs
    out = {}
    for program in programs:
        text = _as_text(program)
        out[program_name(text)] = scope_map(text)
    return out


def scope_times(trace_dir, programs=None, capture=None):
    """Device time of a capture by named scope and pass. Seconds.

    ``programs``: the scope maps to join, ``{module name: scope_map(...)}``
    or a list of compiled programs (or texts); ``None`` reads the
    ``scopes.json`` that ``profiler_set_state("stop")`` left beside the
    trace. ``capture``: the planes as :func:`read_capture` gives them,
    instead of reading ``trace_dir``.

    Every ``XLA Ops`` event is looked up, by instruction name, in the map
    of the program (``XLA Modules``) whose run encloses it, and counts its
    SELF time: an enclosing ``while``, ``conditional`` or ``cond`` is left
    with what its children do not cover, so the rows sum to ``busy_s`` and
    nothing is counted twice. An event of a program with no map is
    ``unmapped``. Averaged over the chips' planes.

    Returns ``busy_s``, ``window_s`` (first operation's start to last
    one's end), ``by_scope`` ``[[scope, pass, seconds, calls]]`` and
    ``by_op`` ``[[scope, pass, label, seconds, calls]]`` longest first,
    ``unscoped_share`` / ``rebuilt_share`` of ``busy_s``, ``programs``
    ``{name: {"runs", "seconds"}}`` and, for chip 0: ``step`` (the program
    that takes most of the time: every run's duration ``runs_s``, the
    median, per-run seconds by scope ``per_run`` ``{"scope|pass":
    [..]}``, and ``slow_runs``: a run over 1.2 x the median with the rows
    that grew against their own medians, ``(idle)`` where no operation
    ran) and ``gaps``: idle seconds between operations, named by the
    innermost ``mx_span`` host event that covers half of the gap, and the
    ten longest with the operations they lie between."""
    capture = capture or read_capture(trace_dir)
    maps = _load_programs(trace_dir, programs)
    devices = capture["devices"]
    if not devices:
        raise ValueError("no device operations in the capture under %s"
                         % trace_dir)
    chips = float(len(devices))
    by_scope, by_op, progs = {}, {}, {}
    named = {}  # event name -> (instruction, label): names repeat a run
    busy = window = 0.0
    step, gaps = None, None
    for index, dev in enumerate(devices):
        ops = dev["ops"]
        runs = sorted(((s, s + d, _module(n)) for n, s, d in dev["modules"]))
        run_starts = [r[0] for r in runs]
        merged = _union((s, s + d) for _, s, d in ops)
        busy += sum(e - s for s, e in merged) * 1e-9 / chips
        if merged:
            window = max(window, (merged[-1][1] - merged[0][0]) * 1e-9)
        per_run = [dict() for _ in runs]
        for (name, start, _), own in zip(ops, _self_times(ops)):
            r = bisect.bisect_right(run_starts, start) - 1
            if r >= 0 and start < runs[r][1]:
                mapped = maps.get(runs[r][2])
            else:
                r, mapped = None, None
            if name not in named:
                named[name] = _instruction(name), _label(name)
            instruction, label = named[name]
            scoped = ("unmapped", "unknown") if mapped is None else \
                mapped.get(instruction, ("unscoped", "unknown"))
            secs = own * 1e-9
            for table, key in ((by_scope, scoped),
                               (by_op, scoped + (label,))):
                row = table.setdefault(key, [0.0, 0])
                row[0] += secs / chips
                row[1] += 1.0 / chips
            if r is not None:
                per_run[r][scoped] = per_run[r].get(scoped, 0.0) + secs
        for s, e, name in runs:
            p = progs.setdefault(name, {"runs": 0, "seconds": 0.0})
            p["runs"] += 1.0 / chips
            p["seconds"] += (e - s) * 1e-9 / chips
        if index == 0:
            step = _step_report(runs, per_run)
            gaps = _gap_report(merged, capture["spans"], ops)
    total = busy or 1.0
    share = {p: sum(v[0] for k, v in by_scope.items() if k[1] == p) / total
             for p in PASSES}

    def share_of(scope):
        return sum(v[0] for k, v in by_scope.items() if k[0] == scope) / total

    return {
        "busy_s": busy, "window_s": window,
        "by_scope": sorted(([s, p, v[0], v[1]] for (s, p), v
                            in by_scope.items()), key=lambda r: -r[2]),
        "by_op": sorted(([s, p, l, v[0], v[1]] for (s, p, l), v
                         in by_op.items()), key=lambda r: -r[3]),
        "unscoped_share": share_of("unscoped"),
        "unmapped_share": share_of("unmapped"),
        "rebuilt_share": share["rebuilt"], "pass_share": share,
        "programs": progs, "step": step, "gaps": gaps,
    }


def _step_report(runs, per_run):
    """The runs of the program that takes most of the time."""
    totals = {}
    for s, e, name in runs:
        totals[name] = totals.get(name, 0.0) + (e - s)
    if not totals:
        return None
    name = max(totals, key=totals.get)
    mine = [i for i, r in enumerate(runs) if r[2] == name]
    secs = [(runs[i][1] - runs[i][0]) * 1e-9 for i in mine]
    keys = sorted({k for i in mine for k in per_run[i]})
    table = {k: [per_run[i].get(k, 0.0) for i in mine] for k in keys}
    idle = [secs[j] - sum(table[k][j] for k in keys)
            for j in range(len(mine))]
    median = statistics.median(secs)
    medians = {k: statistics.median(v) for k, v in table.items()}
    slow = []
    for j, s in enumerate(secs):
        if s <= _SLOW_RUN * median:
            continue
        grew = [[k[0], k[1], table[k][j] - medians[k]] for k in keys
                if table[k][j] - medians[k] > 0.01 * median]
        stalled = idle[j] - statistics.median(idle)
        if stalled > 0.01 * median:
            grew.append(["(idle)", "", stalled])
        slow.append({"run": j, "seconds": s,
                     "grew": sorted(grew, key=lambda g: -g[2])})
    return {"program": name, "runs_s": secs, "median_s": median,
            "per_run": {"%s|%s" % k: v for k, v in table.items()},
            "slow_runs": slow}


def _gap_report(merged, spans, ops):
    """Idle between operations on one chip, by the span that covers it;
    ``longest``: the ten longest gaps ``[seconds, span, the operation
    that ended before it, the one that started after it]``."""
    gaps = sorted(((a[1], b[0]) for a, b in zip(merged, merged[1:])),
                  key=lambda g: g[0] - g[1])
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max([s[2] for s in spans], default=0.0)
    ended = {s + d: n for n, s, d in ops}
    started = {s: n for n, s, d in ops}
    named, top = {}, []
    for gap in gaps[:_NAMED_GAPS]:
        name = _name_gap(gap, spans, starts, longest)
        row = named.setdefault(name, [0.0, 0])
        row[0] += (gap[1] - gap[0]) * 1e-9
        row[1] += 1
        if len(top) < 10:
            top.append([(gap[1] - gap[0]) * 1e-9, name,
                        _label(ended.get(gap[0], "?")),
                        _label(started.get(gap[1], "?"))])
    return {"idle_s": sum(g[1] - g[0] for g in gaps) * 1e-9,
            "by_span": sorted(([k, v[0], v[1]] for k, v in named.items()),
                              key=lambda r: -r[1]),
            "longest": top}
