"""Device time by named scope (``mx.profiler``): the scope map read off a
compiled step, the reader that joins it to a capture, and the scopes the
training paths open. On the CPU: what is checked is structure, never a
time."""
import collections
import functools
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "tools"), os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import telemetry_report  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "tiny_v5e.xplane.pb")


# -- op_name -> (scope, pass) ----------------------------------------------------
@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/jvp(mla)/dot_general", ("mla", "forward")),
    ("jit(step)/jvp(attn)/attn.qkv/btd,de->bte/dot_general",
     ("attn/attn.qkv", "forward")),
    ("jit(step)/transpose(jvp(attn))/attn.out/dot_general",
     ("attn/attn.out", "backward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/mla/rope/mul",
     ("mla/rope", "backward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "attn.swa/jit(wrapped)/flash_win_fwd/pallas_call",
     ("attn.swa/flash_win_fwd", "rebuilt")),
    ("jit(step)/optimizer/jit(_where)/select_n", ("optimizer", "update")),
    ("jit(step)/jvp(moe.experts)/cond/branch_1_fun/while/body/closed_call/"
     "checkpoint/dot_general", ("moe.experts", "forward")),
    ("jit(loop)/while/body/transpose(jvp(sym.BatchNorm))/bn0/reduce_sum",
     ("sym.BatchNorm/bn0", "backward")),
    ("jit(step)/transpose(jvp())/while/body/add", ("unscoped", "backward")),
    # a checkpoint traced inside a scope repeats it in its backward pass
    ("jit(step)/transpose(jvp(mtp))/checkpoint/mtp/mla/rope/mul",
     ("mtp/mla/rope", "backward")),
    ("params['embed']", ("unscoped", "forward")),
    (None, ("unscoped", "unknown")),
])
def test_parse_op_name(op_name, expected):
    assert profiler.parse_op_name(op_name) == expected


# -- the scope map of a toy step -------------------------------------------------
def _toy_step():
    import jax
    import jax.numpy as jnp
    import optax

    def loss(p, x):
        def block(x, w1, w2):
            with jax.named_scope("norm"):
                x = x * jax.lax.rsqrt(
                    jnp.mean(x * x, -1, keepdims=True) + 1e-5)
            with jax.named_scope("mlp"):
                return jnp.tanh(x @ w1) @ w2

        x = jax.checkpoint(block)(x, p["a"], p["b"])
        with jax.named_scope("loss"):
            return jnp.mean(x * x)

    opt = optax.sgd(1e-3)

    def step(p, s, x):
        value, grads = jax.value_and_grad(loss)(p, x)
        with jax.named_scope("optimizer"):
            updates, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, updates)
        return p, s, value

    p = {"a": jnp.ones((8, 8)), "b": jnp.ones((8, 8))}
    return jax.jit(step).lower(p, opt.init(p), jnp.ones((4, 8)))


@functools.lru_cache(None)
def _toy_maps():
    lowered = _toy_step()
    # as traced: on the CPU the compiler merges a checkpoint's rebuilt
    # equations with the forward ones (the chip keeps the barrier)
    traced = lowered.as_text(dialect="hlo", debug_info=True)
    return profiler.scope_map(traced), profiler.scope_map(lowered.compile())


@pytest.mark.parametrize("scoped", [
    ("norm", "forward"), ("mlp", "forward"), ("loss", "forward"),
    ("norm", "rebuilt"), ("mlp", "rebuilt"), ("mlp", "backward"),
    ("loss", "backward"), ("optimizer", "update")])
def test_scope_map_of_a_checkpointed_step(scoped):
    traced, compiled = _toy_maps()
    assert scoped in set(traced.values())
    if scoped[1] != "forward" or scoped[0] == "loss":
        assert scoped in set(compiled.values())


def test_scope_map_takes_a_compiled_program_or_its_text():
    compiled = _toy_step().compile()
    assert profiler.scope_map(compiled) == profiler.scope_map(
        compiled.as_text())
    assert profiler.program_name(compiled) == "jit_step"


_HAND = """HloModule jit_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%fused_computation.1 (p0: bf16[8,16], p1: bf16[16,4]) -> (f32[4], bf16[8,4]) {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %p1 = bf16[16,4]{1,0} parameter(1)
  %convolution.1 = bf16[8,4]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(mlp))/dot_general" stack_frame_id=3}
  %broadcast.2 = f32[8,4]{1,0} broadcast(%c), dimensions={}, metadata={op_name="jit(step)/transpose(jvp(loss))/broadcast_in_dim"}
  %convert.1 = f32[8,4]{1,0} convert(%convolution.1), metadata={op_name="jit(step)/transpose(jvp(norm))/convert_element_type"}
  %reduce.1 = f32[4]{0} reduce(%convert.1, %c), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/transpose(jvp(norm))/reduce_sum"}
  ROOT %tuple.1 = (f32[4]{0}, bf16[8,4]{1,0}) tuple(%reduce.1, %convolution.1)
}

%fused_computation.2 (p0: f32[8,4]) -> f32[8,4] {
  %p0.1 = f32[8,4]{1,0} parameter(0)
  ROOT %tanh.1 = f32[8,4]{1,0} tanh(%p0.1), metadata={op_name="jit(step)/jvp(mlp)/tanh"}
}

%fused_computation.3 (p0: bf16[8,16], p1: bf16[8,4], p2: f32[16,4]) -> f32[16,4] {
  %p0.2 = bf16[8,16]{1,0} parameter(0)
  %p1.2 = bf16[8,4]{1,0} parameter(1)
  %p2.2 = f32[16,4]{1,0} parameter(2)
  %convolution.2 = f32[16,4]{1,0} convolution(%p0.2, %p1.2), dim_labels=fb_io->bf, metadata={op_name="jit(step)/transpose(jvp(mlp))/dot_general"}
  %multiply.9 = f32[16,4]{1,0} multiply(%convolution.2, %convolution.2), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %subtract.9 = f32[16,4]{1,0} subtract(%p2.2, %multiply.9), metadata={op_name="jit(step)/optimizer/sub"}
}

ENTRY %main.9 (x: bf16[8,16], w: bf16[16,4]) -> f32[4] {
  %x = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="x"}
  %w = bf16[16,4]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="w"}
  %copy-start = (bf16[16,4]{1,0:T(8,128)(2,1)S(1)}, bf16[16,4]{1,0}, u32[]{:S(2)}) copy-start(%w)
  %copy-done = bf16[16,4]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start)
  %fusion.7 = (f32[4]{0:T(128)}, bf16[8,4]{1,0:T(8,128)(2,1)}) fusion(%x, %copy-done), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(mlp))/dot_general" stack_frame_id=3}
  %fusion.8 = f32[8,4]{1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2
  %copy.3 = f32[8,4]{0,1} copy(%fusion.8)
  %fusion.9 = f32[16,4]{1,0} fusion(%x, %fusion.7, %w), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(step)/transpose(jvp(mlp))/dot_general"}
  ROOT %get-tuple-element.1 = f32[4]{0} get-tuple-element(%fusion.7), index=0
}
"""


@pytest.mark.parametrize("name,expected", [
    # a matmul with a norm's reduction as its epilogue reads as what it is;
    # the loss's broadcast constant riding along does not count
    ("fusion.7", ("mixed(mlp+norm)", "backward")),
    # a fusion with no metadata of its own takes its fused instructions'
    ("fusion.8", ("mlp", "forward")),
    # a weight's gradient with the optimizer's elementwise update behind it
    ("fusion.9", ("mixed(mlp+optimizer)", "backward")),
    # what the compiler made works for whoever reads it, else feeds it
    ("copy-start", ("mixed(mlp+norm)", "backward")),
    ("copy-done", ("mixed(mlp+norm)", "backward")),
    ("copy.3", ("mlp", "forward")),
    ("x", ("unscoped", "forward")),
    ("convolution.1", ("mlp", "backward")),
])
def test_scope_map_fusions_and_compiler_made_instructions(name, expected):
    assert profiler.scope_map(_HAND)[name] == expected


def test_parse_hlo_reads_opcodes_and_called_computations():
    module, instructions = profiler.parse_hlo(_HAND)
    by_name = {i["name"]: i for i in instructions}
    assert module == "jit_step"
    assert by_name["fusion.7"]["opcode"] == "fusion"
    assert by_name["fusion.7"]["calls"] == ["fused_computation.1"]
    assert by_name["reduce.1"]["calls"] == ["region_0.1"]
    assert by_name["copy-start"]["opcode"] == "copy-start"
    assert by_name["copy-start"]["op_name"] is None
    assert by_name["reduce.1"]["computation"] == "fused_computation.1"


# -- the reader on synthetic planes ----------------------------------------------
def _ev(name, start_us, dur_us):
    return ("%%%s = f32[8,4]{1,0} fusion(%%x)" % name, start_us * 1e3,
            dur_us * 1e3)


def _synthetic():
    """Four runs of ``jit_step``, 100 us apart: a ``while`` (40 us) that
    holds two body operations (15 + 15), then a ``conditional`` (30 us)
    whose branch runs one operation (20); the third run's ``experts``
    operation takes 60 us more. A 50 us gap before the last run lies under
    a ``fit.metric`` span, itself inside a long ``epoch`` span."""
    ops, modules = [], []
    t = 0.0
    for run in range(4):
        if run == 3:
            t += 50.0
        grown = 60.0 if run == 2 else 0.0
        start = t
        ops += [_ev("while.1", t, 40), _ev("norm.1", t + 2, 15),
                _ev("mlp.1", t + 20, 15)]
        t += 40
        ops += [_ev("cond.2", t, 30 + grown),
                _ev("experts.1", t + 5, 20 + grown)]
        t += 30 + grown
        modules.append(("jit_step(123)", start * 1e3, (t - start) * 1e3))
        t += 1.0
    last = modules[-1][1]
    spans = [("epoch", 0.0, 1e6), ("fit.metric", last - 45e3, 42e3),
             ("fit.callbacks", last - 10e3, 2e3)]
    maps = {"jit_step": {
        "while.1": ("unscoped", "forward"), "norm.1": ("norm", "forward"),
        "mlp.1": ("mlp", "backward"), "cond.2": ("moe.experts", "forward"),
        "experts.1": ("moe.experts", "rebuilt")}}
    return {"devices": [{"ops": ops, "modules": modules}],
            "spans": spans}, maps


@pytest.fixture(scope="module")
def synthetic_table():
    capture, maps = _synthetic()
    return profiler.scope_times("unused", maps, capture=capture)


@pytest.mark.parametrize("scoped,seconds,calls", [
    (("norm", "forward"), 4 * 15e-6, 4),
    (("mlp", "backward"), 4 * 15e-6, 4),
    # the loop keeps what its body does not cover: 40 - 30
    (("unscoped", "forward"), 4 * 10e-6, 4),
    # the conditional keeps 10 us a run, its branch's operation the rest
    (("moe.experts", "forward"), 4 * 10e-6, 4),
    (("moe.experts", "rebuilt"), 4 * 20e-6 + 60e-6, 4),
])
def test_scope_times_counts_self_time(synthetic_table, scoped, seconds,
                                      calls):
    rows = {(s, p): (v, c) for s, p, v, c in synthetic_table["by_scope"]}
    assert rows[scoped][0] == pytest.approx(seconds, rel=1e-9)
    assert rows[scoped][1] == calls


def test_scope_times_sums_to_the_busy_union(synthetic_table):
    total = sum(r[2] for r in synthetic_table["by_scope"])
    assert synthetic_table["busy_s"] == pytest.approx(4 * 70e-6 + 60e-6)
    assert total == pytest.approx(synthetic_table["busy_s"], rel=1e-9)
    assert sum(r[3] for r in synthetic_table["by_op"]) == pytest.approx(total)
    assert synthetic_table["rebuilt_share"] == pytest.approx(
        140e-6 / 340e-6)
    assert synthetic_table["unscoped_share"] == pytest.approx(40e-6 / 340e-6)


def test_scope_times_flags_the_slow_run_and_what_grew(synthetic_table):
    step = synthetic_table["step"]
    assert step["program"] == "jit_step"
    assert step["runs_s"] == pytest.approx([70e-6, 70e-6, 130e-6, 70e-6])
    (slow,) = step["slow_runs"]
    assert slow["run"] == 2
    (grew,) = slow["grew"]
    assert grew[:2] == ["moe.experts", "rebuilt"]
    assert grew[2] == pytest.approx(60e-6)


def test_scope_times_reports_a_stall_as_no_scope():
    capture, maps = _synthetic()
    dev = capture["devices"][0]
    name, start, dur = dev["modules"][1]
    dev["modules"][1] = (name, start, dur + 200e3)  # the run, not its ops
    dev["modules"][2:] = [(n, s + 200e3, d) for n, s, d in dev["modules"][2:]]
    dev["ops"] = [(n, s + (200e3 if s >= dev["modules"][2][1] - 200e3
                           else 0.0), d) for n, s, d in dev["ops"]]
    step = profiler.scope_times("unused", maps, capture=capture)["step"]
    stalled = [s for s in step["slow_runs"] if s["run"] == 1]
    assert stalled and stalled[0]["grew"][0][0] == "(idle)"


def test_scope_times_names_a_gap_by_the_innermost_covering_span(
        synthetic_table):
    gaps = synthetic_table["gaps"]
    named = {name: (secs, count) for name, secs, count in gaps["by_span"]}
    # the 51 us before the last run: fit.metric covers 42 of them and is
    # inside epoch; fit.callbacks covers 2
    assert named["fit.metric"] == (pytest.approx(51e-6), 1)
    # the 1 us between the other runs: only the epoch span covers them
    assert named["epoch"] == (pytest.approx(2e-6), 2)
    assert "fit.callbacks" not in named
    assert gaps["idle_s"] == pytest.approx(53e-6)
    seconds, span, before, after = gaps["longest"][0]
    assert (span, before, after) == ("fit.metric", "cond f32[8,4]",
                                     "while f32[8,4]")
    assert seconds == pytest.approx(51e-6)


def test_scope_times_without_a_map_says_unmapped():
    capture, _ = _synthetic()
    table = profiler.scope_times("unused", {}, capture=capture)
    assert table["unmapped_share"] == pytest.approx(1.0)
    assert {r[0] for r in table["by_scope"]} == {"unmapped"}


# -- the recorded v5e capture ----------------------------------------------------
def test_fixture_joined_with_a_hand_made_map_sums_to_its_busy_time():
    """``benchmark/fixtures/tiny_v5e.xplane.pb``: five runs of one program,
    two fusions, a prefetch's start and its end each; the benchmark's
    selfcheck knows its busy time as 37.492 us."""
    maps = {"jit_tiny_step": {
        "convolution_tanh_fusion.2": ("mlp", "forward"),
        "convolution_tanh_fusion": ("mlp", "forward"),
        "copy-start": ("mlp", "forward")}}
    table = profiler.scope_times(FIXTURE, maps)
    assert table["busy_s"] == pytest.approx(3.7492e-05, rel=1e-6)
    assert sum(r[2] for r in table["by_scope"]) == pytest.approx(
        table["busy_s"], rel=5e-3)
    rows = {(s, p): (v, c) for s, p, v, c in table["by_scope"]}
    assert rows[("mlp", "forward")][1] == 15  # 2 fusions + a start, 5 runs
    assert rows[("unscoped", "unknown")][1] == 5  # copy-done: not in the map
    assert table["unscoped_share"] < 0.001
    assert table["step"]["program"] == "jit_tiny_step"
    assert len(table["step"]["runs_s"]) == 5
    assert table["step"]["slow_runs"] == []
    # 30 ms of host sleep between the runs, under no span of the program
    assert table["gaps"]["by_span"][0][0] == "unattributed"
    assert table["gaps"]["idle_s"] == pytest.approx(0.1262, rel=1e-2)
    text = "\n".join(telemetry_report.scope_section(table, top=3))
    assert "mlp" in text and "5 runs of jit_tiny_step" in text


# -- every equation of the training paths under a scope --------------------------

#: the opcodes that take device time
_HEAVY = ("fusion", "dot", "convolution", "reduce", "reduce-window",
          "custom-call", "scatter", "gather", "sort", "select-and-scatter")
#: what may stay unscoped: the scan's own slicing of the staged batches, its
#: counter and its output buffers (the loop is jax's, not the program's), the donated
#: arguments' copies and whatever else the compiler makes (no metadata)
_ALLOWED = ("while/body/dynamic_slice", "while/body/dynamic_update_slice",
            "while/body/add", "while/cond/lt", "while/body/squeeze",
            "while/body/broadcast_in_dim", "while/body/select_n",
            "while/body/lt", "jit(loop)/broadcast_in_dim",
            # a checkpoint's barrier, copied
            "/remat2")


def _unscoped(compiled):
    """The heavy instructions of the program's own equations (those that
    carry an ``op_name``) that no scope covers."""
    _, instructions = profiler.parse_hlo(compiled.as_text())
    out = []
    for ins in instructions:
        if ins["opcode"] not in _HEAVY or "/" not in (ins["op_name"] or ""):
            continue  # no metadata, or an argument's name: a layout copy
        if profiler.parse_op_name(ins["op_name"])[0] != "unscoped":
            continue
        if not ins["op_name"].endswith(_ALLOWED):
            out.append((ins["name"], ins["op_name"]))
    return out


def _lm_step(cell_name):
    """The compiled step of one of the benchmark's LM cells at its
    rehearsal size: the model and the optimizer as the cell's driver
    builds them, lowered on shapes (nothing runs)."""
    import jax
    import jax.numpy as jnp
    import optax

    import run as harness
    from mxnet_tpu import parallel
    from mxnet_tpu.analysis import compile_verify

    cell = harness.Cell(cell_name, rehearse=True)
    ref, config, mix = cell.reference, cell.config, cell.traffic
    sz = ref.sizes(config)
    if cell.workload["driver"] == "train_lm":
        from mxnet_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab_size=sz["V"], num_layers=sz["L"], d_model=sz["d"],
            num_heads=sz["H"], d_ff=sz["ff"], max_seq_len=sz["P"],
            dtype=config["dtype"])
        loss, aux = transformer.loss_fn(cfg), False
    else:
        from mxnet_tpu.models import hybrid_lm

        cfg = cell.driver_module.model_config(config, sz)
        loss, aux = hybrid_lm.loss_fn(cfg), True
    step_fn, init_state = parallel.make_train_step(
        loss, optax.adam(1e-4), has_aux=aux)
    params = jax.eval_shape(lambda: ref.make_params(config, 0))
    tokens = jax.ShapeDtypeStruct(
        (int(mix["batch"]), int(mix["seq_len"]) + 1), jnp.int32)
    return compile_verify.unwrap(step_fn.jitted).lower(
        params, jax.eval_shape(init_state, params), {"tokens": tokens},
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()


def _fit_loop():
    """The scanned trainer's compiled K-step loop over a small ResNet: the
    Symbol executor's lowering and ``fit_trainer``'s step."""
    import jax

    from mxnet_tpu.parallel import fit_trainer

    symbol = mx.models.get_resnet_small(num_classes=10)
    shapes = {"data": (4, 3, 16, 16), "softmax_label": (4,)}
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    names = symbol.list_arguments()
    params = {n: mx.nd.ones(s) * 0.1 for n, s in zip(names, arg_shapes)
              if n not in shapes}
    aux = {n: mx.nd.ones(s) for n, s in
           zip(symbol.list_auxiliary_states(), aux_shapes)}
    trainer = fit_trainer.make_fit_trainer(
        symbol, mx.cpu(0), shapes, mx.optimizer.SGD(
            learning_rate=0.1, momentum=0.9, wd=1e-4), params, aux,
        sorted(params), compute_dtype="bfloat16")
    K = 2
    batch = {"data": np.zeros(shapes["data"], "f"),
             "softmax_label": np.zeros(shapes["softmax_label"], "f")}
    _, staged = trainer.stage_chunk([batch] * K)
    loop = trainer._make_loop(K)
    rngs = jax.random.split(jax.random.PRNGKey(0), K)
    return loop.lower(
        trainer.params, trainer.opt_states, trainer.aux, staged,
        np.ones((K,), np.float32), np.arange(1, K + 1, dtype=np.int32),
        rngs, np.ones((K,), np.float32)).compile()


@pytest.mark.parametrize("path", [
    "gpt2m-train-t1024", "kimi-linear-train-t8192", "mellum2-train-t8192",
    "glm-4.7-flash-train-t8192", "fit"])
def test_every_equation_of_a_training_step_is_under_a_scope(path):
    compiled = _fit_loop() if path == "fit" else _lm_step(path)
    assert _unscoped(compiled) == []
    scopes = {s.split("/")[0] for s, _ in profiler.scope_map(
        compiled).values()}
    expected = {
        "gpt2m-train-t1024": {"embed", "norm", "attn", "mlp", "head",
                              "loss", "optimizer"},
        "kimi-linear-train-t8192": {"embed", "norm", "kda", "mla",
                                    "mlp.dense", "moe.route", "moe.experts",
                                    "moe.shared", "residual", "head", "loss",
                                    "optimizer"},
        "mellum2-train-t8192": {"embed", "norm", "attn.swa", "attn.full",
                                "moe.route", "moe.experts", "residual",
                                "head", "loss", "optimizer"},
        "glm-4.7-flash-train-t8192": {"embed", "norm", "mla", "mtp",
                                      "mlp.dense", "moe.experts", "head",
                                      "loss", "optimizer"},
        "fit": {"sym.Convolution", "sym.BatchNorm", "sym.SoftmaxOutput",
                "cast", "optimizer"},
    }[path]
    assert expected <= scopes, expected - scopes
    passes = {p for _, p in profiler.scope_map(compiled).values()}
    assert {"forward", "backward", "update"} <= passes


# -- a capture, end to end -------------------------------------------------------
def _enable(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_TELEMETRY_JOURNAL", raising=False)
    telemetry.reset()
    assert telemetry.reload() is True


def _tiny_lm():
    import jax
    import jax.numpy as jnp
    import optax

    from mxnet_tpu import parallel
    from mxnet_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=64, num_layers=1, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=16, dtype="float32")
    step_fn, init_state = parallel.make_train_step(
        transformer.loss_fn(cfg), optax.sgd(1e-2))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 17), jnp.int32)}
    return step_fn, params, init_state(params), batch, jax.random.PRNGKey(0)


def test_a_span_reaches_a_capture_started_by_jax_directly(monkeypatch,
                                                          tmp_path):
    """Whoever starts the capture (the benchmark's own
    ``jax.profiler.start_trace``, TensorBoard's dialog) gets the program's
    spans: ``profiler.state()`` is "stop" throughout."""
    import jax

    _enable(monkeypatch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("fit.chunk", step=7):
            with telemetry.span("fit.feed"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert profiler.state() == "stop"
    spans = {name for name, _, _ in profiler.read_capture(
        str(tmp_path))["spans"]}
    assert {"fit.chunk", "fit.feed"} <= spans
    assert [r["step"] for r in telemetry.span_tail()
            if r["name"] == "fit.chunk"] == [7]


def test_a_capture_through_the_profiler_writes_the_steps_scope_map(
        monkeypatch, tmp_path, capsys):
    """``profiler_set_state("run")`` ... ``("stop")`` with telemetry on:
    ``step_fn`` hands over its program, ``scopes.json`` lands beside the
    trace, and the report renders from the directory alone."""
    _enable(monkeypatch)
    step_fn, params, opt_state, batch, rng = _tiny_lm()
    profiler.profiler_set_config(filename=str(tmp_path))
    profiler.profiler_set_state("run")
    try:
        for _ in range(3):
            params, opt_state, loss = step_fn(params, opt_state, batch, rng)
        loss.block_until_ready()
    finally:
        profiler.profiler_set_state("stop")
    with open(tmp_path / profiler.SCOPES_FILE) as f:
        programs = json.load(f)["programs"]
    assert list(programs) == ["jit_step"]
    assert ["optimizer", "update"] in programs["jit_step"].values()
    assert telemetry.span_aggregates()["train.step"]["count"] == 3
    assert [r["step"] for r in telemetry.span_tail()
            if r["name"] == "train.step"] == [0, 1, 2]
    assert telemetry_report.main(["--xplane", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "device time by scope" in out and "optimizer" in out
    table = profiler.scope_times(str(tmp_path))
    assert table["unmapped_share"] < 0.5
    by_pass = collections.Counter(p for _, p, _, _ in table["by_scope"])
    assert by_pass["backward"] and by_pass["update"]


def test_note_program_outside_a_capture_keeps_nothing():
    assert profiler.state() == "stop"
    assert profiler.note_program("HloModule jit_x\n") is False
    assert profiler._noted == []


# -- telemetry off: one boolean, nothing else ------------------------------------
def test_disabled_step_and_scanned_loop_open_nothing(monkeypatch):
    """With MXNET_TELEMETRY unset ``step_fn``, the scanned ``fit`` loop and
    ``telemetry.span`` reach neither the profiler nor the span tables."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.reload()
    assert telemetry.ENABLED is False

    def refuse(*a, **k):
        raise AssertionError("the profiler was reached with telemetry off")

    monkeypatch.setattr(profiler, "scope", refuse)
    monkeypatch.setattr(profiler, "note_program", refuse)
    step_fn, params, opt_state, batch, rng = _tiny_lm()
    for _ in range(2):
        params, opt_state, _ = step_fn(params, opt_state, batch, rng)
    monkeypatch.setenv("MXNET_TRAIN_SCAN_K", "2")
    X = np.random.RandomState(0).rand(16, 8).astype("f")
    y = (X.sum(axis=1) > 4).astype("f")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc"), name="softmax")
    seen = []
    model = mx.FeedForward(net, ctx=mx.cpu(0), num_epoch=1,
                           learning_rate=0.1)
    model.fit(X=mx.io.NDArrayIter(X, y, batch_size=4),
              batch_end_callback=lambda p: seen.append(p.nbatch))
    assert seen  # the scanned loop ran its callbacks
    assert telemetry.span_aggregates() == {}
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}


def test_enabled_scanned_fit_opens_the_fit_spans(monkeypatch):
    _enable(monkeypatch)
    monkeypatch.setenv("MXNET_TRAIN_SCAN_K", "2")
    X = np.random.RandomState(0).rand(16, 8).astype("f")
    y = (X.sum(axis=1) > 4).astype("f")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc"), name="softmax")
    model = mx.FeedForward(net, ctx=mx.cpu(0), num_epoch=1,
                           learning_rate=0.1)
    model.fit(X=mx.io.NDArrayIter(X, y, batch_size=4),
              batch_end_callback=lambda p: None)
    agg = telemetry.span_aggregates()
    assert agg["fit.chunk"]["count"] == 2
    assert agg["fit.metric"]["count"] == 2
    assert agg["fit.callbacks"]["count"] == 4
    assert agg["fit.feed"]["count"] == 5  # four batches and the end
    chunks = [r for r in telemetry.span_tail() if r["name"] == "fit.chunk"]
    assert [r["step"] for r in chunks] == [0, 2]
