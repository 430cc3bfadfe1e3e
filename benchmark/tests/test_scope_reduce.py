"""The join of a trace's instruction names to the executable's ``op_name``
metadata, the phase rule and the sums: on a dozen events and a few lines
of HLO made by hand, and on a tiny step compiled here."""
import importlib.util
import os

import pytest

import scope_reduce as sr
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "jit(step_fn)"
BLOCK = "GPT/blocks.0:Block"

# the optimized HLO as ``hlo_modules()[0].to_string()`` prints it: fused
# computations first, a while body, then the entry computation
HLO = f"""HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %mul.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{STEP}/jvp(loss)/{BLOCK}/q:Linear/mul"}}
}}

%fused_computation.2 (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  ROOT %copy.9 = f32[8]{{0}} copy(%p0.1), metadata={{op_name="{STEP}/transpose(jvp(loss))/{BLOCK}/q:Linear/transpose"}}
}}

%fused_computation.3 (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %copy.10 = f32[8]{{0}} copy(%p0.2)
}}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %chunk.1 = f32[8]{{0}} fusion(%t), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/jvp(loss)/linear_cross_entropy/while/body/closed_call/dot_general"}}
  ROOT %tuple.1 = (s32[], f32[8]) tuple(%i, %chunk.1)
}}

ENTRY %main.1 (a: f32[8]) -> f32[8] {{
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/jvp(loss)/{BLOCK}/q:Linear/dot_general" stack_frame_id=3}}
  %flash_fwd.2 = f32[8]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(loss)/{BLOCK}/scaled_dot_product_attention/flash_fwd/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"TUzv"}}}}
  %while.1 = (s32[], f32[8]) while(%t0), condition=%cond, body=%body, metadata={{op_name="{STEP}/jvp(loss)/linear_cross_entropy/while"}}
  %flash_fwd.3 = f32[8]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(loss))/GPT/jvp(loss)/GPT/checkpoint/rematted_computation/blocks.0:Block/scaled_dot_product_attention/flash_fwd/pallas_call"}}
  %flash_bwd_dq.1 = f32[8]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(loss))/GPT/jvp(loss)/GPT/checkpoint/blocks.0:Block/scaled_dot_product_attention/flash_bwd_dq/pallas_call"}}
  %fusion.2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.3
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/grad_clip/reduce_sum"}}
  %fusion.5 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/transpose(jvp(loss))/mul"}}
  %copy-start.1 = f32[8]{{0}} copy-start(%a)
  ROOT %fusion.6 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/jvp(jit(_take))/gather"}}
}}
"""

MOSAIC = ', custom_call_target="tpu_custom_call"'
# (trace text, start ns, duration ns): two executions of the step; the
# while holds its body's two chunks
EVENTS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0, 100),
    ("%flash_fwd.2 = f32[8]{0} custom-call(f32[8]{0} %fusion.1)" + MOSAIC,
     100, 200),
    ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t0), body=%body",
     300, 500),
    ("%chunk.1 = f32[8]{0} fusion(f32[8]{0} %t), kind=kOutput", 320, 200),
    ("%chunk.1 = f32[8]{0} fusion(f32[8]{0} %t), kind=kOutput", 540, 200),
    ("%flash_fwd.3 = f32[8]{0} custom-call(f32[8]{0} %fusion.1)" + MOSAIC,
     800, 210),
    ("%flash_bwd_dq.1 = f32[8]{0} custom-call(f32[8]{0} %fusion.1)"
     + MOSAIC, 1010, 300),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1310, 40),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1350, 30),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1380, 120),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1500, 50),
    ("%copy-start.1 = f32[8]{0} copy-start(f32[8]{0} %a)", 1550, 10),
    ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1560, 40),
]
STEP_NS = 1600


def _reduced():
    ops = [(t, s + k * 2000, d) for k in (0, 1) for t, s, d in EVENTS]
    trace = {"devices": {"/device:TPU:0": {
        "ops": [(t, s, d, {}) for t, s, d in ops],
        "modules": [("jit_step_fn(7)", k * 2000, STEP_NS, {})
                    for k in (0, 1)]}}, "host": []}
    return tr.reduce_planes(trace, module="jit_step_fn")


def _rows(hlo=HLO):
    reduced = _reduced()
    rows, matched = sr.split(reduced["op_seconds"], reduced["module_runs"],
                             sr.op_names(hlo))
    return {r["instruction"]: r for r in rows}, matched, reduced


@pytest.mark.parametrize("op_name,phase", [
    (f"{STEP}/jvp(loss)/{BLOCK}/q:Linear/dot_general", "forward"),
    (f"{STEP}/jvp(loss)/linear_cross_entropy/while/body/mul", "forward"),
    (f"{STEP}/loss/{BLOCK}/tok:Embedding/embedding/gather", "forward"),
    # the static Executor's nodes: an op type under the loss phase
    ("jit(train_fn)/jvp(loss)/linear/dot_general", "forward"),
    ("jit(train_fn)/transpose(jvp(loss))/linear/dot_general", "backward"),
    (f"{STEP}/transpose(jvp(loss))/{BLOCK}/q:Linear/transpose", "backward"),
    (f"{STEP}/transpose(jvp(loss))/mul", "backward"),
    (f"{STEP}/transpose(jvp(loss))/GPT/jvp(loss)/GPT/checkpoint/"
     "rematted_computation/blocks.0:Block/gelu/mul", "recompute"),
    (f"{STEP}/optimizer/mul", "optimizer"),
    (f"{STEP}/optimizer/grad_clip/reduce_sum", "optimizer"),
    (f"{STEP}/unscale/mul", "optimizer"),
    (f"{STEP}/scaler/select_n", "optimizer"),
    # the optimizer's phases win over jax's wrappers around them
    (f"{STEP}/transpose(jvp(loss))/grad_clip/mul", "optimizer"),
    # jax's own names alone claim nothing: the parent's executable
    (f"{STEP}/jvp(jit(_take))/gather", "unscoped"),
    (f"{STEP}/add", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_the_phase_rule(op_name, phase):
    assert sr.phase_of(op_name) == phase


def test_instructions_take_their_own_op_name_or_their_computations():
    names = sr.op_names(HLO)
    assert names["fusion.1"].endswith("q:Linear/dot_general")
    assert names["flash_fwd.2"].endswith("flash_fwd/pallas_call")
    # an instruction inside a fused computation keeps its own
    assert names["mul.1"].endswith("q:Linear/mul")
    # a fusion without metadata takes its computation's root's ...
    assert names["fusion.2"].endswith("q:Linear/transpose")
    # ... and stays nameless where that has none either
    assert names["fusion.3"] == "" and names["copy-start.1"] == ""
    assert "HloModule" not in names and "main.1" not in names


def test_phases_and_the_unscoped_rest_add_up_to_the_busy_time():
    rows, matched, reduced = _rows()
    assert matched == len(rows) == 12             # 2 of them nameless
    assert sum(not r["op_name"] for r in rows.values()) == 2
    by_phase = {p: sum(r["ms"] for r in rows.values() if r["phase"] == p)
                for p in sr.PHASES}
    busy_ms = reduced["busy_s"] / reduced["module_runs"] * 1000
    assert sum(by_phase.values()) == pytest.approx(busy_ms)
    assert busy_ms == pytest.approx(STEP_NS / 1e6)
    # fusion.1 + flash_fwd.2 + the while with its body
    assert by_phase["forward"] == pytest.approx(800e-6)
    assert by_phase["recompute"] == pytest.approx(210e-6)
    # flash_bwd_dq.1 + fusion.2 + fusion.5
    assert by_phase["backward"] == pytest.approx(390e-6)
    assert by_phase["optimizer"] == pytest.approx(120e-6)
    # fusion.3, copy-start.1 and jax's own jvp(jit(_take))
    assert by_phase["unscoped"] == pytest.approx(80e-6)


def test_a_fusion_and_a_nested_while_body_count_once():
    rows, _, _ = _rows()
    # the while keeps its own 100 ns, the body's chunks their 2 x 200
    assert rows["while.1"]["ms"] == pytest.approx(100e-6)
    assert rows["chunk.1"]["ms"] == pytest.approx(400e-6)
    head = sum(r["ms"] for r in rows.values()
               if sr.under(r, (sr.HEAD_LOSS,)))
    assert head == pytest.approx(500e-6)
    # mul.1 sits in three fusions' computation and in no sum of its own
    assert "mul.1" not in rows


def test_kernels_are_read_by_scope_and_only_where_mosaic():
    rows, _, _ = _rows()
    flash_fwd = sum(r["ms"] for r in rows.values()
                    if r["mosaic"] and sr.under(r, sr.FLASH_FWD))
    flash_bwd = sum(r["ms"] for r in rows.values()
                    if r["mosaic"] and sr.under(r, sr.FLASH_BWD))
    assert flash_fwd == pytest.approx(410e-6)     # first and replayed
    assert flash_bwd == pytest.approx(300e-6)
    attention = sum(r["ms"] for r in rows.values()
                    if sr.under(r, (sr.ATTENTION,)))
    assert attention == pytest.approx(flash_fwd + flash_bwd)
    mosaic = tr.mosaic_seconds_per_run(_reduced()) * 1000
    assert flash_fwd + flash_bwd == pytest.approx(mosaic)


class _Runner:
    EXECUTABLE = "jit_step_fn"


def _ctx(reduced, log):
    return {"trace": reduced, "runner": _Runner, "log": log}


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SCOPE_METRICS = ["fwd_ms", "bwd_ms", "recompute_ms", "optimizer_ms",
                 "attention_ms", "head_loss_ms", "unscoped_share",
                 "flash_fwd_ms", "flash_bwd_ms"]


class _Executable:
    def __init__(self, text, name="jit_step_fn"):
        self._text, self.name = text, name

    def hlo_modules(self):
        return [self]

    def to_string(self):
        return self._text


@pytest.fixture
def live(monkeypatch):
    """Put hand-made executables in the place of the backend's live
    ones."""
    import jax

    def put(*texts):
        client = type("Client", (), {
            "live_executables": lambda self: [_Executable(t)
                                              for t in texts]})()
        device = type("Device", (), {"client": client})()
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
    return put


def test_every_scope_metric_reads_the_joined_table(live):
    live("HloModule jit_step_fn\n", HLO)      # the better match is taken
    lines = []
    ctx = _ctx(_reduced(), lines.append)
    got = {n: _metric(n).read(ctx) for n in SCOPE_METRICS}
    assert got["fwd_ms"] == pytest.approx(800e-6)
    assert got["bwd_ms"] == pytest.approx(390e-6)
    assert got["recompute_ms"] == pytest.approx(210e-6)
    assert got["optimizer_ms"] == pytest.approx(120e-6)
    assert got["attention_ms"] == pytest.approx(710e-6)
    assert got["head_loss_ms"] == pytest.approx(500e-6)
    assert got["unscoped_share"] == pytest.approx(80 / 1600 * 100)
    assert got["flash_fwd_ms"] == pytest.approx(410e-6)
    assert got["flash_bwd_ms"] == pytest.approx(300e-6)
    # joined once, and the log names the kernels and the unscoped rest
    assert sum("traced instruction names are in it" in l
               for l in lines) == 1
    assert any("flash_bwd_dq 0.000" in l and "flash_fwd 0.000" in l
               for l in lines)
    assert any("the unscoped time by instruction kind" in l
               and "fusion [jit(step_fn)/jvp(jit(_take))/gather]" in l
               and "copy-start [no op_name]" in l for l in lines)


# what the parent commit's executable, or one that the persistent cache
# answered from before the scopes existed, looks like: jax's own names
STALE = HLO.replace("jvp(loss)", "jvp(jit(_take))").replace(
    "/optimizer/grad_clip/", "/").replace("linear_cross_entropy/", "")


@pytest.mark.parametrize("texts,why", [
    ((STALE,), "carry none of the program's scopes"),
    ((), "no live executable named jit_step_fn"),
    (("HloModule jit_step_fn\n%x.1 = f32[] add(), metadata={op_name="
      '"jit(step_fn)/jvp(loss)/add"}\n',), "under half"),
], ids=["stale-cache", "no-executable", "another-program"])
def test_nothing_is_read_and_the_log_says_why(live, texts, why):
    live(*texts)
    lines = []
    ctx = _ctx(_reduced(), lines.append)
    assert [_metric(n).read(ctx) for n in SCOPE_METRICS] == [None] * 9
    assert sum(why in l for l in lines) == 1      # said once, not nine times


def test_no_traced_execution_reads_nothing(live):
    live(HLO)
    reduced = dict(_reduced(), module_runs=0)
    lines = []
    assert _metric("fwd_ms").read(_ctx(reduced, lines.append)) is None
    assert "no traced execution" in lines[0]


def test_counter_metrics_read_the_registry_or_nothing():
    from paddle_tpu.utils import monitor
    names = ["import_s", "param_init_s", "opt_state_init_s",
             "trace_lower_s", "step_python_ms"]
    saved = monitor.all_stats()
    lines = []
    ctx = {"log": lines.append}
    try:
        monitor.stat_reset()
        # a program from before the counters: nothing, and no exception
        assert [_metric(n).read(ctx) for n in names] == [None] * 5
        for stat, v in [("setup.import_s", 3.5), ("setup.param_init_s", 2.0),
                        ("setup.param_init_count", 292),
                        ("setup.opt_state_init_s", 1.25),
                        ("setup.trace_s", 10.0), ("setup.lower_s", 4.0),
                        ("train_step.python_ns", 9_000_000),
                        ("train_step.calls", 6)]:
            monitor.stat_set(stat, v)
        assert [_metric(n).read(ctx) for n in names] == [
            3.5, 2.0, 1.25, 14.0, 1.5]
        assert any("292 parameters" in l for l in lines)
    finally:
        monitor.stat_reset()
        for k, v in saved.items():
            monitor.stat_set(k, v)


def test_the_join_reads_a_step_compiled_here():
    """The real printer's format: a tiny ``TrainStep`` compiled on this
    backend, every instruction of its HLO given a made-up self time."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 8)

        def forward(self, x):
            return F.gelu(self.fc(x))

    paddle.seed(0)
    net = Net()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = jnp.ones((4, 8), jnp.float32)
    step(x, x)
    texts = [ex.hlo_modules()[0].to_string()
             for ex in jax.devices()[0].client.live_executables()
             if ex.hlo_modules()[0].name == "jit_step_fn"]
    names = {}
    for text in texts:          # other tests' steps may still be alive
        found = sr.op_names(text)
        if any("fc:Linear" in n for n in found.values()):
            names = found
    assert names, "the step's executable carries no fc:Linear scope"
    reduced = {"module_runs": 1, "busy_s": len(names) * 1e-6,
               "op_seconds": {k + " f32[8]": 1e-6 for k in names}}
    lines = []
    ctx = _ctx(reduced, lines.append)
    rows = sr.table(ctx)
    assert rows is not None, lines
    phases = {r["phase"] for r in rows}
    assert {"forward", "backward", "optimizer"} <= phases
    parts = [_metric(n).read(ctx) for n in
             ("fwd_ms", "bwd_ms", "recompute_ms", "optimizer_ms")]
    loose = _metric("unscoped_share").read(ctx) / 100 * len(names) * 1e-3
    assert sum(parts) + loose == pytest.approx(len(names) * 1e-3)
