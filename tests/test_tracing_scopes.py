"""What the program names for a trace: ``jax.named_scope`` phases and
components in the step's ``op_name`` metadata, a name on every Pallas
kernel, ``pt:`` host spans on the profiler's clock through one
primitive, and the always-on set-up and host-step counters
(observability/scopes.py; PERF.md section 3 says which metric reads
which)."""
import glob
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import amp, nn, observability, optimizer, profiler
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.bind import buffer_arrays
from paddle_tpu.observability import scopes
from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
from paddle_tpu.parallel import recompute
from paddle_tpu.utils import monitor

H, V = 16, 32


class Blk(nn.Layer):
    def __init__(self):
        super().__init__()
        self.ln1 = nn.LayerNorm(H)
        self.q = nn.Linear(H, H)
        self.drop = nn.Dropout(0.1)

    def forward(self, x):
        B, S = x.shape[0], x.shape[1]
        q = self.q(self.ln1(x)).reshape([B, S, 2, H // 2])
        a = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        return x + self.drop(F.gelu(a.reshape([B, S, H])))


class Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.tok = nn.Embedding(V, H)
        self.blocks = nn.LayerList([Blk() for _ in range(2)])
        self.head = nn.Linear(H, V)

    def forward(self, ids):
        x = self.tok(ids)
        for blk in self.blocks:
            x = recompute(blk, x)
        return x


def _head_loss(net):
    def loss_fn(out, labels):
        return F.linear_cross_entropy(
            out.reshape([-1, H]), net.head.weight, net.head.bias,
            labels.reshape([-1]))
    return loss_fn


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.fixture(scope="module")
def step_op_names():
    """``op_name``s of a tiny step that has every phase: per-block
    recompute, AMP O2 in float16 under a GradScaler, a global-norm
    clip."""
    paddle.seed(0)
    net = Net()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          grad_clip=ClipGradByGlobalNorm(1.0),
                          multi_precision=True)
    net, opt = amp.decorate(net, opt, level="O2", dtype="float16")
    step = TrainStep(net, _head_loss(net), opt,
                     scaler=amp.GradScaler(init_loss_scaling=128.0))
    ids = jnp.zeros((2, 8), jnp.int32)
    step(ids, ids)
    lowered = step._compiled[True].lower(
        step._param_arrays(), tuple(buffer_arrays(net)), step._opt_state,
        step._scaler_state, step._lr_device, (ids,), (ids,))
    assert step._make_step_fn().__name__ == "step_fn"
    return _op_names(lowered.compile().as_text())


def _segments(op_names):
    return {seg for n in op_names for seg in n.split("/")}


@pytest.mark.parametrize("phase", scopes.PHASES)
def test_train_step_carries_every_phase(step_op_names, phase):
    segs = _segments(step_op_names)
    if phase == scopes.LOSS:
        # jax wraps the differentiated phase itself
        assert "jvp(loss)" in segs and "transpose(jvp(loss))" in segs
    else:
        assert phase in segs
    # the clip runs inside the update
    assert any("/optimizer/grad_clip/" in n for n in step_op_names)


@pytest.mark.parametrize("scope", [
    "Net", "tok:Embedding", "blocks.0:Blk", "blocks.1:Blk", "ln1:LayerNorm",
    "q:Linear", "drop:Dropout", scopes.ATTENTION,
    scopes.LINEAR_CROSS_ENTROPY, scopes.GELU, scopes.LAYER_NORM,
    scopes.EMBEDDING, scopes.DROPOUT])
def test_train_step_carries_the_component_scopes(step_op_names, scope):
    assert scope in _segments(step_op_names)


def test_recompute_enters_the_layers_scope_in_every_pass(step_op_names):
    """``recompute`` calls ``forward``, not ``__call__``: the block's
    scope must be in the forward, the replayed forward and the backward
    all the same."""
    block = [n for n in step_op_names if "/blocks.1:Blk/q:Linear/" in n]
    assert any("rematted_computation" in n for n in block)
    assert any(n.startswith("jit(step_fn)/jvp(loss)/") for n in block)
    assert any(n.startswith("jit(step_fn)/transpose(jvp(loss))/")
               and "rematted_computation" not in n for n in block)


def test_the_head_makes_its_gradients_in_the_forward_pass(step_op_names):
    """No replay under ``linear_cross_entropy``: its three products (the
    logits, ``dh``, ``dw``) sit in the forward pass's one scan, the two
    gradient products under jax's name for the chunk's own transposition,
    and the backward pass holds the scaling by the cotangent alone.  All
    of it carries the scope, so ``head_loss_ms`` reads all of the head."""
    head = [n.split("/") for n in step_op_names
            if scopes.LINEAR_CROSS_ENTROPY in n.split("/")]
    assert head and not any("rematted_computation" in s for s in head)
    products = [s for s in head if s[-1] == "dot_general"]
    assert products and all(
        s[:3] == ["jit(step_fn)", "jvp(loss)", scopes.LINEAR_CROSS_ENTROPY]
        and "while" in s for s in products)
    assert any("transpose(jvp())" in s for s in products)
    assert any("jvp()" in s for s in products)
    backward = [s for s in head if s[1] == "transpose(jvp(loss))"]
    assert backward and not any("while" in s for s in backward)
    assert {s[-1] for s in backward} <= {"mul", "convert_element_type"}


def test_the_head_counts_where_it_made_its_gradients():
    """``linear_cross_entropy.grads_in_forward`` counts the forward rule
    at trace time, beside ``.calls``: a ``TrainStep`` reads both the same,
    ``eval_step`` takes the value path and counts no gradients."""
    paddle.seed(0)
    net = Net()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStep(net, _head_loss(net), opt)
    ids = jnp.zeros((2, 8), jnp.int32)
    monitor.stat_reset()
    step.eval_step(ids, ids)
    assert monitor.get_stat("linear_cross_entropy.calls") == 1
    assert "linear_cross_entropy.grads_in_forward" not in monitor.all_stats()
    step(ids, ids)
    assert monitor.get_stat("linear_cross_entropy.calls") == 2
    assert monitor.get_stat("linear_cross_entropy.grads_in_forward") == 1


def test_layers_name_themselves_as_named_parameters_does():
    net = Net()
    got = {path: layer._scope_name()
           for path, layer in net.named_sublayers(include_self=True)}
    assert got[""] == "Net"                     # nobody holds the root
    assert got["blocks.1"] == "blocks.1:Blk"    # a list's items carry it
    assert got["blocks.1.q"] == "q:Linear"
    net.blocks.append(Blk())
    net.blocks[0] = Blk()
    assert net.blocks[2]._scope_name() == "blocks.2:Blk"
    assert net.blocks[0]._scope_name() == "blocks.0:Blk"
    assert len(net.blocks[1:]) == 2             # a slice renames nothing
    assert net.blocks[1]._scope_name() == "blocks.1:Blk"
    seq = nn.Sequential(nn.Linear(2, 2), nn.ReLU())
    assert seq[0]._scope_name() == "0:Linear"


def test_scopes_in_eager_mode_compile_nothing_new():
    """The same op under another layer's scope reuses its executable."""
    compiles = []

    def on(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        a, b = nn.Linear(4, 4), nn.Linear(4, 4)
        holder = nn.LayerList([a, b])
        holder._set_scope("pair")
        x = paddle.ones([2, 4])
        a(x)
        n = len(compiles)
        b(x)
        assert len(compiles) == n
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


# ------------------------------------------------------- kernel names --
def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        str(eqn.source_info.name_stack)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, out)
    return out


@pytest.fixture(scope="module")
def kernel_calls():
    """(name, name stack) of every ``pallas_call`` equation the
    twenty-one sites trace, in interpret mode: no chip needed."""
    from paddle_tpu.ops.pallas.collective_matmul import chunk_matmul
    from paddle_tpu.ops.pallas.fused_adam import fused_adam_update
    from paddle_tpu.ops.pallas.fused_epilogue import fused_linear_epilogue
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_decode
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    mp = pytest.MonkeyPatch()
    mp.setattr(fa, "_interpret", lambda: True)
    try:
        found = []
        q = jnp.ones((1, 256, 2, 64), jnp.float32)
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v,
                                                       causal=True)),
            (0, 1, 2)))(q, q, q).jaxpr, [])
        # eva_fwd, eva_bwd_dq, and flash_bwd_dkv once more (the exact
        # keys' gradients, windows folded into the heads)
        eva = importlib.import_module("paddle_tpu.ops.pallas.eva_attention")
        q, vec = jnp.ones((1, 64, 2, 16), jnp.float32), jnp.ones((2, 16))
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda q, k, v, mu, phi: jnp.sum(eva.eva_attention(
                q, k, v, mu, phi, 16, 4)),
            (0, 1, 2, 3, 4)))(q, q, q, vec, vec).jaxpr, [])
        # the indexer's two selection kernels, the two of the attention
        # under its mask (the backward is one walk), the KL term with its
        # gradient
        sa = importlib.import_module(
            "paddle_tpu.ops.pallas.sparse_attention")
        mp.setattr(sa, "_interpret", lambda: True)
        q, kv = jnp.ones((1, 32, 4, 16)), jnp.ones((1, 32, 2, 16))
        qi, ki, wi = (jnp.ones((1, 32, 2, 8)), jnp.ones((1, 32, 8)),
                      jnp.ones((1, 32, 2)))

        def sparse(q, k, v, qi, ki, wi):
            mask, idx_lse = sa.dsa_select(qi, wi, ki, 8)
            out, lse = sa.sparse_attention(q, k, v, mask)
            return jnp.sum(out) + sa.dsa_kl(qi, wi, ki, mask, idx_lse, q, k,
                                            lse)
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            sparse, (0, 1, 2, 3, 4, 5)))(q, kv, kv, qi, ki, wi).jaxpr, [])
        # the state-space scan: the forward rule's kernel and the backward's
        ssd = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
        x, bc = jnp.ones((1, 128, 8, 64)), jnp.ones((1, 128, 1, 128))
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(ssd.ssd_scan(*a)), range(6)))(
            x, jnp.ones((1, 128, 8)), -jnp.ones((8,)), bc, bc,
            jnp.ones((8,))).jaxpr, [])
        # a mixer's convolution: the forward kernel and the backward's
        conv = importlib.import_module("paddle_tpu.ops.pallas.causal_conv")
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda *a: sum(jnp.sum(o) for o in conv.causal_conv1d(
                *a, "silu", 128, (128, 128))), (0, 1, 2)))(
            jnp.ones((1, 32, 512)), jnp.ones((4, 256)),
            jnp.ones((256,))).jaxpr, [])
        # a convolution mixer's gated operator: its forward kernel and
        # its backward's
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(conv.gated_short_conv(*a)), (0, 1)))(
            jnp.ones((1, 32, 384)), jnp.ones((3, 128))).jaxpr, [])
        # an expert layer's two sums over a token's held slots: the
        # weighted one forward, the plain one in the dispatch's transpose
        moe = importlib.import_module("paddle_tpu.ops.moe")
        mc = importlib.import_module("paddle_tpu.ops.pallas.moe_combine")
        mp.setattr(mc, "_interpret", lambda: True)
        mp.setattr(moe, "choose_kernel", lambda name, ok: ok)
        x, w = jnp.ones((128, 128)), jnp.ones((1, 128, 128))
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda x, g: jnp.sum(moe.moe_experts(
                x, g, jnp.tile(jnp.arange(2), (128, 1)), w, w, w, rows=128)),
            (0, 1)))(x, jnp.ones((128, 2))).jaxpr, [])
        x, w = jnp.ones((16, 16)), jnp.ones((16, 128))
        found += _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda x, w, b: jnp.sum(fused_linear_epilogue(
                x, w, b, (("gelu", True),), interpret=True)),
            (0, 1, 2)))(x, w, jnp.ones((128,))).jaxpr, [])
        p = jnp.ones((8, 128))
        found += _pallas_calls(jax.make_jaxpr(
            lambda p, g, m, v: fused_adam_update(
                p, g, m, v, 1e-3, 1.0, interpret=True))(p, p, p, p).jaxpr,
            [])
        pool = jnp.ones((12, 8, 2, 128))
        found += _pallas_calls(jax.make_jaxpr(
            lambda q, k, v, t, n: paged_attention_decode(
                q, k, v, t, n, interpret=True))(
            jnp.ones((3, 4, 128)), pool, pool,
            jnp.zeros((3, 4), jnp.int32),
            jnp.asarray([1, 13, 32], jnp.int32)).jaxpr, [])
        found += _pallas_calls(jax.make_jaxpr(
            lambda x, w: chunk_matmul(x, w, interpret=True))(
            jnp.ones((16, 128)), jnp.ones((128, 128))).jaxpr, [])
    finally:
        mp.undo()
    return found


@pytest.mark.parametrize("kernel", scopes.KERNELS)
def test_every_pallas_call_site_carries_its_name(kernel_calls, kernel):
    stacks = [stack for name, stack in kernel_calls if name == kernel]
    assert stacks, f"no pallas_call named {kernel!r} in {kernel_calls}"
    # jax puts the call under a scope of the kernel's name itself
    assert all(kernel in stack for stack in stacks)


def test_no_pallas_call_is_left_without_a_name(kernel_calls):
    assert len(kernel_calls) == 23
    assert {name for name, _ in kernel_calls} == set(scopes.KERNELS)
    src = os.path.join(os.path.dirname(paddle.__file__), "ops", "pallas")
    for path in glob.glob(os.path.join(src, "*.py")):
        text = open(path).read()
        assert (len(re.findall(r"pl\.pallas_call\(", text))
                == len(re.findall(r"\bname=scopes\.[A-Z_]+", text))), path


# --------------------------------------------- the executables' names --
def _live_executable_names():
    return {ex.hlo_modules()[0].name
            for ex in jax.devices()[0].client.live_executables()}


def test_the_jitted_steps_keep_their_names():
    """The benchmark finds the step by these (runners/*.py::EXECUTABLE)."""
    paddle.seed(0)
    net = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    assert "jit_step_fn" in _live_executable_names()

    paddle.enable_static()
    try:
        prog = paddle.static.Program()
        with paddle.static.program_guard(prog):
            x = paddle.static.data("x", [2, 4], "float32")
            loss = nn.Linear(4, 2)(x).mean()
            optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = paddle.static.Executor()
        exe.run(prog, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[loss])
        assert "jit_train_fn" in _live_executable_names()
        text = "\n".join(
            ex.hlo_modules()[0].to_string()
            for ex in jax.devices()[0].client.live_executables()
            if ex.hlo_modules()[0].name == "jit_train_fn")
        segs = _segments(_op_names(text))
        # each replayed node under its op type, forward under ``loss``
        assert {"jvp(loss)", "transpose(jvp(loss))", "optimizer",
                "linear", "mean"} <= segs
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()


# ---------------------------------------------------------------- spans --
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    events = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns, ev.start_ns
                            + ev.duration_ns) for ev in line.events
                           if ev.name.startswith("pt:")]
    return events


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One CPU profiler capture, ring on, around both span forms, a
    compiled train step and a static Executor run."""
    paddle.seed(0)
    net = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    x, y = jnp.ones((2, 4)), jnp.ones((2, 2))
    step(x, y)                      # compile outside the capture
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    tracer = observability.enable()
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            with observability.span("outer", tag=1):
                with profiler.RecordEvent("inner"):
                    float(step(x, y))
        finally:
            jax.profiler.stop_trace()
        ring = [e for e in tracer.events() if e["kind"] == "span"]
    finally:
        observability.disable()
    return _host_events(trace_dir), ring


@pytest.mark.parametrize("name", [
    "outer", "inner", "train_step.prepare", "train_step.execute",
    "train_step.writeback"])
def test_spans_reach_the_profiler_trace_and_the_ring(captured, name):
    host, ring = captured
    assert [n for n, _, _ in host].count("pt:" + name) == 1
    assert [e["name"] for e in ring].count(name) == 1


def test_span_parents_hold_in_the_ring_and_nest_in_the_trace(captured):
    host, ring = captured
    by_name = {e["name"]: e for e in ring}
    assert "parent" not in by_name["outer"]
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    for part in ("prepare", "execute", "writeback"):
        assert (by_name["train_step." + part]["parent"]
                == by_name["inner"]["id"])
    assert by_name["outer"]["args"] == {"tag": 1}
    at = {n: (s, e) for n, s, e in host}
    for part in ("prepare", "execute", "writeback"):
        s, e = at["pt:train_step." + part]
        assert at["pt:inner"][0] <= s and e <= at["pt:inner"][1]
    assert (at["pt:train_step.prepare"][1]
            <= at["pt:train_step.execute"][0])


def test_spans_cost_no_ring_when_it_is_off():
    assert not observability.enabled()
    with observability.span("quiet") as sid:
        assert sid is None
    ev = profiler.RecordEvent("quiet")
    ev.end()                # never begun: a no-op
    ev.begin()
    ev.end()
    ev.end()                # idempotent


def test_trace_annotation_has_one_call_site():
    root = os.path.dirname(paddle.__file__)
    sites = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        for i, line in enumerate(open(path), 1):
            if re.search(r"TraceAnnotation\(", line) and "``" not in line:
                sites.append(f"{os.path.relpath(path, root)}:{i}")
    assert len(sites) == 1 and sites[0].startswith(
        "observability/__init__.py"), sites


def test_executor_run_spans_go_through_the_same_primitive():
    paddle.enable_static()
    tracer = observability.enable()
    try:
        prog = paddle.static.Program()
        with paddle.static.program_guard(prog):
            x = paddle.static.data("x", [2, 4], "float32")
            out = nn.Linear(4, 2)(x)
        exe = paddle.static.Executor()
        exe.run(prog, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[out])
        spans = {e["name"]: e for e in tracer.events()
                 if e["kind"] == "span"}
        run = spans["executor.run"]
        assert spans["executor.feed"]["parent"] == run["id"]
        # a key's first run puts its set-up span between the two
        first = spans["executor.first_run"]
        assert first["parent"] == run["id"]
        assert spans["executor.build"]["parent"] == first["id"]
        assert spans["executor.execute"]["parent"] == first["id"]
        exe.run(prog, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[out])
        again = [e for e in tracer.events() if e["kind"] == "span"][-3:]
        assert [e["name"] for e in again] == [
            "executor.feed", "executor.execute", "executor.run"]
        assert again[1]["parent"] == again[2]["id"]
        exe.close()
    finally:
        observability.disable()
        paddle.disable_static()
        paddle.static.reset_default_programs()


# ------------------------------------------------------------- counters --
def test_setup_counters_are_on_after_one_compiled_step():
    before = monitor.all_stats()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    x, y = jnp.ones((2, 4)), jnp.ones((2, 2))
    for _ in range(3):
        step(x, y)
    after = monitor.all_stats()

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)

    assert grew("setup.param_init_count") == 4
    for name in ("setup.param_init_s", "setup.opt_state_init_s",
                 "setup.trace_s", "setup.lower_s"):
        assert grew(name) > 0, name
    assert grew("train_step.calls") == 3
    # the host's share of a step: more than nothing, far under a second,
    # and without the one-off optimizer-state initialisation
    assert 0 < grew("train_step.python_ns") < 3 * 1e9


def test_import_counter_is_set_by_the_package():
    """Set by the last line of ``paddle_tpu/__init__.py``; other tests
    reset the registry, so read it from a fresh interpreter."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import paddle_tpu; from paddle_tpu.utils import monitor; "
         "print(monitor.get_stat('setup.import_s'))"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(paddle.__file__)))
    assert out.returncode == 0, out.stderr
    assert 0 < float(out.stdout.strip().splitlines()[-1]) < 300


# ------------------------- a mixture of experts under sparse attention --
@pytest.fixture(scope="module")
def keye_step():
    """``op_name``s and counters of the keye_vl2 cell's model at its
    rehearsal widths, through the train_step runner, on the XLA paths."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import run as harness
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        "keye_vl2_30b_a3b.train_bf16_b4_s8192", rehearse=True)
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=3)
    monitor.stat_reset()
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        step = state["step"]
        runner.dispatch(state, runner.feed(state, *ring[0]))
        ids = jnp.asarray(ring[0][0])
        lowered = step._compiled[True].lower(
            step._param_arrays(), (), step._opt_state, step._scaler_state,
            step._lr_device, (ids,), (ids,))
        return (_op_names(lowered.compile().as_text()),
                dict(monitor.all_stats()), cfg)
    finally:
        runner.close(state)


@pytest.mark.parametrize("scope", [
    scopes.MOE, scopes.MOE_ROUTER, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS,
    scopes.DSA_INDEXER, scopes.DSA_SELECT, scopes.SPARSE_ATTENTION,
    scopes.QK_NORM, scopes.RMS_NORM, scopes.ROPE, "moe:MoELayer",
    "blocks.1:Block"])
def test_the_expert_and_sparse_attention_scopes_are_in_the_step(keye_step,
                                                                scope):
    assert scope in _segments(keye_step[0])


def test_the_expert_and_sparse_attention_counters(keye_step):
    _, stats, cfg = keye_step
    L = cfg["num_hidden_layers"]
    assert stats["moe.experts_held"] == cfg["num_experts"]
    assert stats["moe.experts_total"] == cfg["published"]["num_experts"]
    assert stats["moe.top_k"] == cfg["num_experts_per_tok"]
    # once a layer in the forward pass and once in its replay
    assert stats["moe.ragged_dot_path"] >= L
    assert stats["sparse_attention.xla_path"] >= L
    assert stats["dsa_indexer.xla_path"] >= L
    assert "pallas.selected.sparse_attention" not in stats


# ------------- latent attention, a sigmoid router, an MTP module (PR 32) --
@pytest.fixture(scope="module")
def joyai_step():
    """``op_name``s and counters of the joyai_llm_flash cell's model at
    its rehearsal widths, through the train_step runner, on the XLA
    paths."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import run as harness
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        "joyai_llm_flash.train_bf16_b2_s8192", rehearse=True)
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=3)
    monitor.stat_reset()
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        step = state["step"]
        runner.dispatch(state, runner.feed(state, *ring[0]))
        ids = jnp.asarray(ring[0][0])
        lowered = step._compiled[True].lower(
            step._param_arrays(), (), step._opt_state, step._scaler_state,
            step._lr_device, (ids,), (ids,))
        return (_op_names(lowered.compile().as_text()),
                dict(monitor.all_stats()), cfg)
    finally:
        runner.close(state)


@pytest.mark.parametrize("scope", [
    scopes.MLA_ATTENTION, scopes.MTP, scopes.MOE, scopes.MOE_ROUTER,
    scopes.MOE_DISPATCH, scopes.MOE_EXPERTS, scopes.MOE_SHARED,
    scopes.RMS_NORM, scopes.ROPE, scopes.LINEAR_CROSS_ENTROPY,
    "attn:MLAttention", "moe:MoELayer", "dense:Block", "blocks.1:Block",
    "mtp:MTPModule", "block:Block"])
def test_the_latent_attention_and_mtp_scopes_are_in_the_step(joyai_step,
                                                             scope):
    assert scope in _segments(joyai_step[0])


def test_the_mtp_scope_holds_its_block_and_its_head_in_every_pass(joyai_step):
    """``mtp_ms`` reads whole path segments named ``mtp``: the module's
    block (under ``parallel.recompute``) and its pass through the shared
    head must carry one in the forward, the replay and the backward."""
    names = joyai_step[0]
    for inner in (scopes.MLA_ATTENTION, scopes.MOE_SHARED,
                  scopes.LINEAR_CROSS_ENTROPY):
        under = [n.split("/") for n in names
                 if scopes.MTP in n.split("/") and inner in n.split("/")]
        assert any("jvp(loss)" in s and "rematted_computation" not in s
                   for s in under), inner
        assert any("transpose(jvp(loss))" in s for s in under), inner
    replay = [n for n in names if "/mtp/" in n and "rematted_computation" in n]
    assert any("/" + scopes.MLA_ATTENTION + "/" in n for n in replay)
    # the main model's blocks and its head are not under it
    assert not any("/mtp/" in n for n in names if "/blocks.0:Block/" in n)
    assert any(scopes.LINEAR_CROSS_ENTROPY in n.split("/")
               and scopes.MTP not in n.split("/") for n in names)


def test_the_latent_attention_and_mtp_counters(joyai_step):
    _, stats, cfg = joyai_step
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    assert stats["moe.experts_held"] == cfg["n_routed_experts"]
    assert stats["moe.experts_total"] == cfg["published"]["n_routed_experts"]
    assert stats["moe.top_k"] == cfg["num_experts_per_tok"]
    assert stats["mtp.modules"] == 1
    # once a block in the forward pass and once in its replay
    assert stats["mla_attention.xla_path"] >= blocks
    assert stats["moe.scoring_sigmoid"] >= blocks - 1
    assert stats["moe.shared_experts"] >= blocks - 1
    assert "pallas.selected.mla_attention" not in stats


def test_keyes_expert_layer_counts_no_sigmoid_and_no_shared_expert(keye_step):
    _, stats, _ = keye_step
    assert "moe.scoring_sigmoid" not in stats
    assert "moe.shared_experts" not in stats


# --------------- a looped stack, its exit gate and objective (PR 37) --
@pytest.fixture(scope="module")
def ouro_step():
    """``op_name``s, trace-time counters and device counters of the
    ouro_2_6b cell's model at its rehearsal widths, through the
    train_step runner, on the XLA paths."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import run as harness
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        "ouro_2_6b.train_bf16_b2_s4096", rehearse=True)
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=3)
    monitor.stat_reset()
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        step = state["step"]
        runner.dispatch(state, runner.feed(state, *ring[0]))
        runner.dispatch(state, runner.feed(state, *ring[1]))
        stats = dict(monitor.all_stats())
        counted = step.device_counters()
        ids = jnp.asarray(ring[0][0])
        lowered = step._compiled[True].lower(
            step._param_arrays(), (), step._opt_state, step._scaler_state,
            step._lr_device, (ids,), (ids,))
        return (_op_names(lowered.compile().as_text()), stats, cfg, counted)
    finally:
        runner.close(state)


@pytest.mark.parametrize("scope", [
    scopes.LOOP_STACK, scopes.LOOP_EXIT, scopes.LINEAR_CROSS_ENTROPY,
    scopes.RMS_NORM, scopes.ROPE, scopes.ATTENTION, "stack:LoopedStack",
    "exit_gate:LoopExitGate", "blocks.2:Block", "norm:StreamNorm"])
def test_the_looped_stack_and_exit_scopes_are_in_the_step(ouro_step, scope):
    assert scope in _segments(ouro_step[0])


def test_every_block_application_sits_under_loop_stack_in_every_pass(
        ouro_step):
    """``loop_exit_ms`` and a cut by hand read whole path segments: a
    block's instructions carry ``loop_stack`` in the forward, the replay
    and the backward; the head and the exit's own work do not, and the
    exit's scope holds the gate and the distribution in both directions."""
    names = ouro_step[0]
    blocks = [n.split("/") for n in names if "blocks.0:Block" in n.split("/")]
    assert blocks and all(scopes.LOOP_STACK in s for s in blocks)
    assert any("rematted_computation" in s for s in blocks)
    assert any("transpose(jvp(loss))" in s for s in blocks)
    head = [n.split("/") for n in names
            if scopes.LINEAR_CROSS_ENTROPY in n.split("/")]
    assert head and not any(scopes.LOOP_STACK in s or scopes.LOOP_EXIT in s
                            for s in head)
    exits = [n.split("/") for n in names if scopes.LOOP_EXIT in n.split("/")]
    assert any("jvp(loss)" in s for s in exits)
    assert any("transpose(jvp(loss))" in s for s in exits)
    assert any("exit_gate:LoopExitGate" in s for s in exits)
    assert not any(scopes.LOOP_STACK in s for s in exits)


def test_the_looped_models_counters(ouro_step):
    _, stats, cfg, counted = ouro_step
    T, L = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    assert stats["loop.steps"] == T
    assert stats["loop.block_calls"] == T * L
    assert stats["linear_cross_entropy.calls"] == 1
    assert stats["linear_cross_entropy.grads_in_forward"] == 1
    # once a block application in the forward pass and once in its replay
    assert stats["attention.xla_path"] >= T * L
    # the exit distribution as the two compiled steps counted it
    assert counted["loop.exit_share.steps"] == 2
    share = [counted[f"loop.exit_share.total.0.{t}"] / 2 for t in range(T)]
    assert sum(share) == pytest.approx(1.0, rel=1e-5)
    assert all(0.0 < s < 1.0 for s in share)
    last = [counted[f"loop.exit_share.last.0.{t}"] for t in range(T)]
    assert sum(last) == pytest.approx(1.0, rel=1e-5)
    # at a gate of 0.5 the distribution is 1/2, 1/4, 1/8, 1/8: 1.2130
    # nats; uniform over four is ln 4 = 1.3863, the most there is
    assert 1.0 < counted["loop.exit_entropy.last.0"] < 1.3863
    assert 2.0 < counted["loop.exit_entropy.total.0"] < 2 * 1.3863
    assert {scopes.LOOP_EXIT_SHARE, scopes.LOOP_EXIT_ENTROPY} <= set(
        scopes.DEVICE_COUNTERS)
