"""Test harness config: run everything on a virtual 8-device CPU mesh.

This mirrors the reference's testing trick of using plural device ids in
one process to simulate multi-worker setups (SURVEY §4.3) — here we force
JAX onto CPU with 8 virtual devices so sharding/kvstore/model-parallel
tests exercise real multi-device code paths without TPU hardware.
Must run before jax is imported anywhere.
"""
import os
import sys

# Force CPU: on a machine with a chip attached jax would otherwise pick
# it. Tests must run on the virtual 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
# ... and without the persistent compilation cache: the suite's
# behaviour and time must not depend on what an earlier run left on
# disk (the cache tests place their own, test_compile.py)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# Run the whole suite under the engine hazard verifier (mxlint's engine
# pass): every push's read/write var sets are recorded and statically
# checked on each wait, so a use-after-free or a wait-cycle in any
# test's engine usage fails that test at the wait, before it blocks.
# The verifier finds the hazards it can see in the trace; it bounds no
# hang: _time_limit below does, with one limit for every test. What the
# recorder keeps is that it is safe to enter from a finalizer (which
# runs on whatever thread allocates next): a record made on the thread
# that is already inside the trace's critical section is queued, never
# waited for (EngineTrace._record). The full trace is kept in memory
# and re-checked per wait: a debug mode, not a production one — see
# docs/how_to/static_analysis.md.
#
# The same switch also arms the mxrace runtime lock recorder: the
# serving engine, elastic coordinator, dependency engine and async
# kvstore server wrap their state locks in TracedLock, so every
# acquire/release the suite performs lands in the ambient lock trace.
# pytest_sessionfinish (below) is the suite-wide gate over it.
os.environ.setdefault("MXNET_ENGINE_VERIFY", "1")

# Run the suite under the mxjit compile/transfer verifier in RECORD
# mode: every jit boundary counts compiles against its bucket-derived
# budget and every hot-region D2H pull lands in the byte ledger.
# Record (not raise): an unexpected recompile anywhere in the suite is
# gated suite-wide in pytest_sessionfinish below with the full
# arg-signature diff, instead of crashing the one test that happened
# to trip it. Individual tests flip to raise-mode explicitly.
os.environ.setdefault("MXNET_JIT_VERIFY", "record")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Pin the default device to CPU so every uncommitted op and jit lands
# on the virtual CPU mesh, whatever plugins the process has loaded.
import jax  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])

# Meshes built without explicit devices should use the virtual CPU mesh,
# not the single real TPU chip.
from mxnet_tpu.parallel import mesh as _mesh  # noqa: E402

_mesh.set_default_devices(jax.devices("cpu"))

import signal  # noqa: E402

import pytest  # noqa: E402

# Seconds one test may take, set-up and teardown included. The slowest
# test of the suite takes 76 s and the slowest file 192 s (ISSUE 27's
# probe), so 300 only ever cuts a hang.
TEST_TIME_LIMIT = 300


@pytest.fixture(autouse=True)
def _time_limit():
    """A hang costs one failed test, not the run: SIGALRM on the main
    thread (where pytest and every xdist worker run tests) raises into
    the test after TEST_TIME_LIMIT, and again every tenth of it, since a
    raise that lands inside a finalizer or an ``except BaseException``
    is swallowed there; for that case the handler also leaves a mark,
    and the teardown fails the test by it. Lock, queue and socket waits
    are interruptible; a wait inside native code is not, and there
    ``faulthandler_timeout`` (pyproject.toml) at least prints where."""
    fired = []

    def on_alarm(signum, frame):
        fired.append(signum)
        pytest.fail("test passed its time limit of %d s" % TEST_TIME_LIMIT)

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT,
                     TEST_TIME_LIMIT / 10)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)
    if fired:
        pytest.fail("test passed its time limit of %d s (the alarm fired "
                    "%d time(s))" % (TEST_TIME_LIMIT, len(fired)))


@pytest.fixture(autouse=True)
def _clear_fault_specs():
    """No fault-spec leakage across tests: rules armed by a test (the
    `faulty` marker) or left over from a chaos run's MXNET_FAULT_SPEC
    are dropped after every test; the env spec re-arms with fresh RNG
    state on the next injection-point hit, so chaos runs replay the
    same seeded pattern per test instead of a drifting global one."""
    yield
    from mxnet_tpu.resilience import faults

    faults.clear()


def pytest_sessionfinish(session, exitstatus):
    """Suite-wide mxrace clean-repo gate (the PR 1 engine-verify
    pattern, lock edition): after the whole suite ran with TracedLock
    recording on, the ambient lock trace's OBSERVED acquisition orders
    must contain no inversion. An inversion here means two subsystems
    really took two locks in both orders at runtime somewhere in the
    suite — a deadlock in waiting that no single test owns, so it is
    raised at session scope where the evidence lives.

    The same hook runs the mxproto clean-repo gate: the elastic RPC
    substrate's client call sites, server dispatch arms and timeout
    lattice must diff clean (pure AST, ~ms) — a protocol drift
    introduced by any change in the session fails the session, not
    some later distributed job. env={} pins the lattice to the SHIPPED
    defaults: an exported elastic knob (a chaos run's evict window)
    must not fail an unrelated session — the coordinator clamps a
    misconfigured window at startup, and `mxlint --proto` run by hand
    still checks the live environment."""
    from mxnet_tpu.analysis import engine_verify
    from mxnet_tpu.analysis.proto_lint import lint_protocol

    proto_bad = [f for f in lint_protocol(env={})
                 if f.severity in ("error", "warning")]
    if proto_bad:
        raise pytest.UsageError(
            "mxproto suite-wide protocol gate: %d schema/lattice "
            "finding(s) on the elastic RPC substrate:\n%s"
            % (len(proto_bad), "\n".join(str(f) for f in proto_bad)))
    # mxjit suite-wide compile/transfer gate: the whole session ran
    # under MXNET_JIT_VERIFY=record (see top of file), so any compile
    # past a boundary's bucket budget and any hot-region D2H ledger
    # over its byte budget is ambient evidence here — with the exact
    # arg-signature diff naming what varied. Negative-control tests
    # divert their seeded storms via expecting_violations().
    from mxnet_tpu.analysis import compile_verify

    jit_bad = compile_verify.unexpected()
    d2h_bad = compile_verify.d2h_violations()
    if jit_bad or d2h_bad:
        lines = ["%s: compile %s past budget %s — %s"
                 % (r["name"], r["compiles"], r["budget"],
                    "; ".join(r["diff"])) for r in jit_bad]
        lines += ["region %s: %d bytes over budget %d (sites: %s)"
                  % (r["region"], r["bytes"], r["budget_bytes"],
                     sorted(r["sites"])) for r in d2h_bad]
        raise pytest.UsageError(
            "mxjit suite-wide compile/transfer gate: %d unexpected "
            "recompile(s), %d D2H budget violation(s) across the "
            "session:\n%s"
            % (len(jit_bad), len(d2h_bad), "\n".join(lines)))
    trace = engine_verify.ambient_trace(create=False)
    if trace is None:
        return
    findings = [f for f in engine_verify.verify(trace)
                if f.code == "lock-order"]
    if findings:
        raise pytest.UsageError(
            "mxrace suite-wide lock-order gate: %d observed inversion(s) "
            "across the session:\n%s"
            % (len(findings), "\n".join(str(f) for f in findings)))


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """mxtel isolation: metrics/spans recorded by one test must not leak
    into the next. When a journal is active (chaos runs set
    MXNET_TELEMETRY process-wide) the teardown first flushes a
    ``mark="test_end"`` snapshot — tools/chaos.py sums exactly those
    marks to total counters across per-test resets — then resets the
    registry and re-reads the env (dropping any monkeypatched
    MXNET_TELEMETRY*, which pytest restored before this teardown)."""
    yield
    from mxnet_tpu import telemetry

    telemetry.flush(mark="test_end")
    telemetry.reset()
    telemetry.reload()
