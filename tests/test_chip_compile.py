"""The Pallas tier compiled by the chip's own compiler, without the chip.

Interpret mode cannot see what Mosaic refuses (an op with no TPU
lowering, a kernel that wants more VMEM than it may use), so each
kernel of the main paths is compiled here for a *described* TPU v5e at
the widths ``chip_smoke.py`` runs: nothing executes, but the compiler
raises exactly what it would raise on the chip.  Every shape a gate
had to be tightened for sits beside the positives as a negative case:
its ``*_supported`` must say False.

The topology is described inside the module-scoped fixture below and
nowhere else — only one process may load the TPU library, and under
xdist every worker imports this file.  Compiles happen in the test's
own process; keep all of them in this one file.
"""
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    """Compile for the described chip; the Mosaic kernel must be in the
    executable (an interpret-mode lowering would leave none)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------- flash --
@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "drop"])
@pytest.mark.parametrize("shape,causal", [
    ((4, 512, 12, 64), False),      # BERT-base
    ((4, 1024, 16, 96), True),      # GPT-760M
    ((8, 2048, 16, 96), True),      # the gpt3_large.train_bf16_b8_s2048 cell
], ids=["bert", "gpt", "gpt_cell"])
def test_flash_attention_fwd_bwd(one_chip, monkeypatch, shape, causal,
                                 dropout):
    # the package re-exports a function under the module's name
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    # capability + profitability: the dispatcher takes the kernel at all
    # three, BERT's 512 included, with dropout or without
    assert fa.flash_attention_supported(shape, shape, jnp.bfloat16,
                                        dropout_p=dropout)

    def loss(q, k, v, seed):
        out = fa.flash_attention(q, k, v, causal=causal,
                                 dropout_p=dropout, seed=seed)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    qkv = _sds(one_chip, shape, jnp.bfloat16)
    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)), qkv, qkv, qkv,
                        _sds(one_chip, (1, 1), jnp.int32))
    _one_backward_kernel(compiled)


def _mosaic_kernels(compiled):
    """The names of the executable's Mosaic kernel calls, in order."""
    return re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())


def _kernel_vmem(compiled):
    """(name, bytes of scoped VMEM Mosaic took) of each kernel call."""
    return [(name, int(size)) for name, size in re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
        compiled.as_text())]


def _one_backward_kernel(compiled):
    """The forward kernel and the dK/dV walk that makes dQ too."""
    kernels = _mosaic_kernels(compiled)
    assert len(kernels) == 2 and "flash_fwd" in kernels[0], kernels
    assert "flash_bwd_dkv" in kernels[1], kernels


# ------------------------------------------------------------------ EVA --
def test_eva_attention_fwd_bwd(one_chip, monkeypatch):
    """The evabyte.train_bf16_b1_s8192 cell's attention: four windows of
    2048, chunks of 16, 32 heads of 128."""
    eva = importlib.import_module("paddle_tpu.ops.pallas.eva_attention")
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(eva, "_interpret", lambda: False)
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    shape = (1, 8192, 32, 128)
    assert eva.eva_attention_supported(shape, jnp.bfloat16, 2048, 16)
    # summary blocks of 8 rows do not fill a bfloat16 tile
    assert not eva.eva_attention_supported(shape, jnp.bfloat16, 2048, 256)
    assert not eva.eva_attention_supported((1, 8192, 32, 64), jnp.bfloat16,
                                           2048, 16)

    def loss(q, k, v, mu, phi):
        out = eva.eva_attention(q, k, v, mu, phi, 2048, 16)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    qkv = _sds(one_chip, shape, jnp.bfloat16)
    vec = _sds(one_chip, (32, 128), jnp.bfloat16)
    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2, 3, 4)),
                        qkv, qkv, qkv, vec, vec)
    # eva_fwd, eva_bwd_dq and the flash dk/dv kernel over folded windows
    assert compiled.as_text().count("tpu_custom_call") >= 3


def _cell_step(one_chip, monkeypatch, cell_name, kernel_modules):
    """A benchmark cell's whole step as the train_step runner builds it
    (model -> amp O2 -> AdamW with clip -> TrainStep(donate=True)), at the
    published widths, compiled for the described v5e.  -> (compiled,
    parameters, cfg, mix, footprint as benchmark/run.py counts it, the
    step)."""
    import json
    import sys
    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
    from paddle_tpu.ops.pallas import support
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import run as harness
    cell, cfg, mix, model_mod, _, _ = harness.load_parts(cell_name)
    # the code under the jit asks jax for the backend; here that is the CPU
    monkeypatch.setattr(support, "interpret_mode", lambda: False)
    for name in kernel_modules:
        mod = importlib.import_module("paddle_tpu.ops.pallas." + name)
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(support, "tier_enabled", lambda: True)
    # no seeded weights are needed to compile: zeros are quick
    for init in (nn.initializer.XavierNormal, nn.initializer.Normal):
        monkeypatch.setattr(init, "__call__",
                            lambda self, shape, dtype="float32":
                            jnp.zeros(shape, dtype))
    model, loss_fn = model_mod.build(cfg, cell["model_args"])
    o = cell["optimizer"]
    opt = optimizer.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["eps"], parameters=model.parameters(),
        weight_decay=o["weight_decay"],
        grad_clip=ClipGradByGlobalNorm(o["clip_global_norm"]),
        multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype=cell["dtype"])
    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)

    def sds(a):
        return _sds(one_chip, a.shape, a.dtype)

    params = tuple(sds(p.data) for p in step._params)
    n = sum(math.prod(p.shape) for p in params)
    opt_state = jax.tree.map(sds, jax.eval_shape(
        opt.functional_init, list(params)))
    ids = _sds(one_chip, (mix["batch"], mix["seq"]), jnp.int32)
    args = [params, (), opt_state,
            jax.tree.map(sds, step._init_scaler_state()),
            _sds(one_chip, (), jnp.float32), (ids,), (ids,)]
    # as ``__call__`` builds it: where the model emits device counters the
    # carry holds them from here on
    jitted = step._carry_counters(step._build(True), tuple(args))
    args[3] = jax.tree.map(sds, step._init_scaler_state())
    compiled = jitted.lower(*args).compile()
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes
                 + m.generated_code_size_in_bytes)
    print(f"{cell_name} step: {n} parameters, footprint {footprint} bytes, "
          f"{len(jax.tree.leaves(compiled.out_info))} outputs "
          f"({json.dumps({k: getattr(m, k) for k in dir(m) if k.endswith('_in_bytes') and not k.startswith('host')})})")
    return compiled, n, cfg, mix, footprint, step


def _same_program_as_without_counters(compiled, step, footprint, on_record):
    """A step whose model emits no device counter is the program it was
    before there were any: no carry for them, and the (output count,
    footprint) ``on_record`` (PR 33's tree, compiled by this test: under
    conftest's matmul precision, so not the benchmark's program to the
    byte; a PR that changes the cell's step reads the printed line and
    moves them)."""
    assert step._counter_spec == {}
    assert (len(jax.tree.leaves(compiled.out_info)),
            footprint) == on_record


def _carries_the_moe_counters(compiled, step, calls, chunks, live_peak):
    """The expert layers' counters are in the carry, a row a call, and
    the step's live peak is what it was without them (PR 33's tree) to
    1 MB.  The footprint as ``benchmark/run.py`` counts it is the heap as
    XLA packed it, which moved by more (PERF.md, PR 34)."""
    from paddle_tpu.observability import scopes
    assert {k: v.shape for k, v in step._counter_spec.items()} == {
        scopes.MOE_EXPERT_LOAD: (calls, 16),
        scopes.MOE_CHUNK_ASSIGNMENTS: (calls, chunks),
        scopes.MOE_FULL_BUFFER_CHUNKS: (calls,),
        scopes.MOE_FULLEST_EXPERT_LOAD: (calls,)}
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert abs(peak - live_peak) < 2 ** 20, peak


def _token_major_passes_walk_the_buffer(text, stats, layers, n, K, H,
                                        calls_a_layer=2):
    """Where a chunk's load fits the small buffer (``lax.cond``'s branch 1)
    nothing under ``moe`` holds a row of H for every slot, n * K of them:
    the sums over a token's slots run through ``moe_combine`` over the
    buffer's rows in token order, one call a layer in the forward pass
    (the replay's is dead code) and one in the backward, and the gates'
    gradient is made a row.  The overflow branch keeps XLA's form, and is
    where this search finds what it looks for.  ``calls_a_layer`` 3: a
    layer that norms the experts' sum on its way out needs that sum again
    in the backward pass, so its replay's call is live."""
    a_slot = re.compile(rf"= (?:bf16|f32)\[(?:{n * K},{H}|{n},{K},{H})\]")
    found = {"branch_0_fun": 0, "branch_1_fun": 0}
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        segs = name.group(1).split("/") if name else []
        # (outside the branches four rows of 8192 tokens are [n * K, H]
        # themselves where K is 4: the stream, not a row a slot)
        branch = next((s for s in segs if s.startswith("branch_")), None)
        if "moe" in segs and branch and a_slot.search(line):
            found[branch] += 1
    assert found["branch_0_fun"] and not found["branch_1_fun"], found
    assert _kernel_count(text, "moe_combine") == calls_a_layer * layers
    assert stats["pallas.selected.moe_combine"] >= 2 * layers
    assert "moe_combine.xla_path" not in stats
    assert stats["moe.token_major_rows"] == stats["moe.small_buffer_rows"] \
        == int(2.25 * n * K * stats["moe.experts_held"]
               / stats["moe.experts_total"]) < n * K


def _conv_hands_the_scan_its_operands(text, stats, mixers, B, T, W,
                                      conv_dim):
    """The mixers' convolution as its two kernels (a mixer's forward, its
    replay and its backward; the counter moves a traced call of the
    public entry, at least once a mixer), none on XLA's form; the projection's output laid in rows, no [B, T, conv_dim] or
    [B, T, W] array with T minor, no float32 [B, T, conv_dim] (the parent
    kept the pre-activation's gradient as one, 805 MB in Nemotron) and
    nothing between ``conv_fwd`` and ``ssd_fwd``: x, B and C reach the
    scan's kernel as the convolution's wrote them."""
    from paddle_tpu.observability import scopes
    assert (_kernel_count(text, scopes.CONV_FWD),
            _kernel_count(text, scopes.CONV_BWD)) == (2 * mixers, mixers)
    assert stats["pallas.selected.causal_conv1d"] >= mixers
    assert "causal_conv1d.xla_path" not in stats
    assert f"bf16[{B},{T},{W}]{{2,1,0" in text
    for gone in (f"[{B},{T},{conv_dim}]{{1,2,0", f"[{B},{T},{W}]{{1,2,0",
                 f"f32[{B},{T},{conv_dim}]"):
        assert gone not in text, gone
    made = set(re.findall(
        r"%([\w.-]+) = [^\n]*get-tuple-element\(%conv_fwd[.\d]*\)", text))
    scans = re.findall(
        r"%ssd_fwd[.\d]* = [^\n]*custom-call\(%([\w.-]+), %[\w.-]+, "
        r"%[\w.-]+, %([\w.-]+), %([\w.-]+),", text)
    assert len(scans) == 2 * mixers
    assert all(op in made for call in scans for op in call), scans


def _kept(stats):
    """name -> ``recompute.kept.<name>``: the ``scopes.RESIDUALS`` a
    step's replays are handed, and how many of each."""
    prefix = "recompute.kept."
    return {k[len(prefix):]: v for k, v in stats.items()
            if k.startswith(prefix)}


def _finds_a_head(compiled, stats, lies=0, transposed=0, vmem=None):
    """How the step's calls of the flash entries find a head's rows, one
    trace-time count a call (``flash_attention.as_it_lies`` /
    ``.transposed``: PR 44), and with ``vmem`` the scoped VMEM Mosaic took
    for the (forward kernel, backward walk) on the described v5e, as
    compiled by this test in PR 44: a block that is a lane block of
    [B, L, H*D] takes more than the same bytes of a [B, H, L, D] copy
    did (Ouro's pair 4,304,896 / 8,560,640 before; 6,668,288 /
    13,017,088 with q and k lying too), under the same limits."""
    counted = {k.rsplit(".", 1)[1]: v for k, v in stats.items()
               if k in ("flash_attention.as_it_lies",
                        "flash_attention.transposed")}
    assert counted == {k: v for k, v in (("as_it_lies", lies),
                                         ("transposed", transposed)) if v}
    if vmem:
        sizes = _kernel_vmem(compiled)
        assert tuple({size for name, size in sizes if name.startswith(kernel)}
                     for kernel in ("flash_fwd", "flash_bwd_dkv")) \
            == tuple({size} for size in vmem), sizes


def _kernel_count(text, kernel):
    return len(re.findall(rf"%{kernel}[.\d]* = ", text))


def _head_scans(text, H):
    """{(chunks, rows)} of the chunked head's scans in a compiled step:
    the [chunks, rows, H] ``dh`` (and hidden states) that the while loops
    under the head's scope carry.  The ``dw`` accumulator's shape is the
    weight's and says nothing of the chunk (ROADMAP S9, PR 45)."""
    from paddle_tpu.observability import scopes
    found = set()
    for line in text.splitlines():
        if " while(" in line and re.search(
                rf'op_name="[^"]*/{scopes.LINEAR_CROSS_ENTROPY}/while"', line):
            carried = line.split(" while(")[0]
            found |= {(int(n), int(rows)) for n, rows in re.findall(
                rf"(?:f32|bf16)\[(\d+),(\d+),{H}\]", carried)}
    return found


def _head_made_its_gradients_in_the_forward_pass(text, calls, H, chunks,
                                                 rows=1024, dh_rows=None):
    """The chunked head of a compiled step, ``calls`` of them traced: each
    took the forward rule (``linear_cross_entropy.grads_in_forward``), so
    nothing under the scope is a replay, its products sit in the forward
    pass's scan and the backward pass holds no loop of its own: at most
    the scaling by the cotangent, which XLA folds away where that is 1
    (BERT, GPT, Keye).  The scan walks ``chunks`` chunks of ``rows`` rows
    of ``H``, the rows the head chose (1024 in every cell: 2048 for the
    float32 heads was tried on the chip and lost in all of them, PR 50)
    and counted, and ``dh`` walks ``dh_rows`` rows a product (the chunk's,
    but 512 under Ouro's 49,152 columns)."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    assert monitor.get_stat("linear_cross_entropy.calls") == calls
    assert monitor.get_stat("linear_cross_entropy.grads_in_forward") == calls
    assert {k[len("linear_cross_entropy."):]: v
            for k, v in monitor.all_stats().items()
            if k.startswith(("linear_cross_entropy.rows.",
                             "linear_cross_entropy.dh_rows."))} \
        == {f"rows.{rows}": calls, f"dh_rows.{dh_rows or rows}": calls}
    assert _head_scans(text, H) == {(chunks, rows)}
    head = [n.split("/") for n in set(re.findall(r'op_name="([^"]*)"', text))
            if scopes.LINEAR_CROSS_ENTROPY in n.split("/")]
    assert head and not any("rematted_computation" in s for s in head)
    assert any("jvp(loss)" in s and "while" in s for s in head)
    assert not any("transpose(jvp(loss))" in s and "while" in s
                   for s in head)


def test_evabyte_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The cell's whole step at the published widths, for the described
    v5e: it compiles, holds the kernels, and its footprint is under the
    chip's 15.75 GiB."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "evabyte.train_bf16_b1_s8192",
        ("eva_attention", "flash_attention"))
    # the parent's program to the byte (PR 38): eight heads of 320
    # columns over a hidden width of 4096 keep the replay, because the
    # forward rule would hold a float32 ``dh`` of 134 MB a head from the
    # forward pass into the backward: 15,446,008,320 compiled that way
    _same_program_as_without_counters(compiled, step, footprint,
                                      (192, 14_658_201_088))
    assert cfg["hidden_size"] == 4096 and mix["seq"] == 8192
    assert 821e6 < n < 822e6
    text = compiled.as_text()
    # once a layer each: ``parallel.recompute`` keeps ``out`` and ``lse``,
    # so the replay holds no second ``eva_fwd``
    L = cfg["num_hidden_layers"]
    for kernel in ("eva_fwd", "eva_bwd_dq", "flash_bwd_dkv"):
        assert _kernel_count(text, kernel) == L, kernel
    # the windows ask the flash walk for dK and dV alone
    assert "pallas.flash.bwd_fused" not in monitor.all_stats()
    assert "pallas.sparse.bwd_fused" not in monitor.all_stats()
    # the policy keeps by name: nothing here carries the indexer's names
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: L, scopes.ATTN_LSE: L}
    # every layer's call chose the kernels, and the program says so
    assert stats["pallas.selected.eva_attention"] >= L
    assert "eva_attention.xla_path" not in stats
    assert stats["linear_cross_entropy.calls"] == cfg["num_pred_heads"] == 8
    assert stats["linear_cross_entropy.rows.1024"] == 8
    assert "linear_cross_entropy.grads_in_forward" not in stats
    assert any("rematted_computation" in n and "linear_cross_entropy" in n
               for n in re.findall(r'op_name="([^"]*)"', text))
    assert footprint < 15.75 * 2 ** 30


def test_keye_vl2_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The keye_vl2_30b_a3b.train_bf16_b4_s8192 cell's whole step (16 of
    128 experts, 4 layers, an eighth of the vocabulary; four rows of
    8192) for the described v5e: it compiles, holds the sparse-attention
    and indexer kernels and XLA's own grouped-matmul kernel, and fits."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "keye_vl2_30b_a3b.train_bf16_b4_s8192",
        ("sparse_attention", "flash_attention", "moe_combine"))
    # 14,553,615,872 before the expert layers' sums walked rows (PR 46)
    _carries_the_moe_counters(compiled, step, calls=4, chunks=4,
                              live_peak=14_488_347_648)
    # 15,950,521,344 since PR 46; 16,350,240,768 since the replay keeps the
    # mask, q, k and v of the sparse kernels (PR 41: 2.42 GB kept;
    # 15,589,112,320 with the mask alone); 13,306,718,208 before,
    # 13,305,999,360 without the counters.  The slack ends 0.9 GB under
    # the compiler's ceiling of 16,911,433,728
    assert footprint < 15_950_521_344 + 60e6 < 16_350_240_768
    assert cfg["hidden_size"] == 2048 and mix["seq"] == 8192
    assert 465e6 < n < 466e6
    text = compiled.as_text()
    L = cfg["num_hidden_layers"]
    # the replay keeps the attention kernel's out and lse, the three
    # gradients the loss's kernel makes with its value and the kernels'
    # operands, the selection's mask among them: one forward, one
    # ``dsa_kl`` and one selection a layer, all in the forward pass only,
    # and one backward kernel: the dK/dV walk makes dQ too
    for kernel, calls in (("sparse_fwd", L), ("sparse_bwd_dq", 0),
                          ("sparse_bwd_dkv", L), ("dsa_kl", L),
                          ("dsa_kl_bwd", 0),
                          ("dsa_scores", L), ("dsa_threshold", L)):
        assert _kernel_count(text, kernel) == calls, kernel
    # nothing upstream of the attention kernel that only it read is in the
    # replay: no selection, no ``v`` projection (q's and k's stay: their
    # norms' backward reads the projections' results)
    replayed = {n.split("rematted_computation/", 1)[1]
                for n in re.findall(r'op_name="([^"]*)"', text)
                if "rematted_computation/" in n}
    for scope in ("dsa_select", "dsa_indexer", "v:Linear", "idx_q:Linear",
                  "idx_w:Linear", "rope/concatenate"):
        assert not any(scope in n for n in replayed), scope
    assert any("q:Linear" in n for n in replayed)
    assert "ragged-dot" in text
    stats = monitor.all_stats()
    assert _kept(stats) == dict.fromkeys(scopes.RESIDUALS, L)
    assert stats["pallas.sparse.bwd_fused"] == L
    assert "pallas.flash.bwd_fused" not in stats
    # each of a layer's three functionals chose its kernels: the loss
    # over [4, 8192, 8192] would cost most if it fell to the XLA form
    for kernel, functional in (("dsa_indexer", "dsa_indexer"),
                               ("sparse_attention", "sparse_attention"),
                               ("dsa_kl", "dsa_indexer_loss")):
        assert stats[f"pallas.selected.{kernel}"] >= L, kernel
        assert f"{functional}.xla_path" not in stats, functional
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=32)
    _token_major_passes_walk_the_buffer(
        text, stats, L, mix["seq"], cfg["num_experts_per_tok"],
        cfg["hidden_size"])
    assert footprint < 15.75 * 2 ** 30


@pytest.mark.parametrize("q_shape,k_shape,dtype", [
    ((4, 32, 8192, 128), (4, 4, 8192, 128), jnp.bfloat16),   # the Keye cell
    ((1, 8, 4096, 128), (1, 8, 4096, 128), jnp.float32),     # a group of one
], ids=["keye_cell", "float32_no_group"])
def test_sparse_attention_backward_walk(one_chip, monkeypatch, q_shape,
                                        k_shape, dtype):
    """The sparse family's backward alone, one kernel for dq, dk and dv,
    at the largest rows the gate admits (T x D x itemsize = 2 MiB), under
    the file's stated ``_VMEM_LIMIT``.  What it holds at [.., 8192, 128]
    bfloat16, blocks of 512, two buffers a block as ``_staging`` counts:
    q, dO and the dQ block of a head 3 x 2 x 2 MiB; the dk and dv blocks
    of a group 2 x 2 x 2 MiB; the mask's [512, 8192] int8 strip 2 x 4
    MiB; lse and delta [8, 8192] float32 2 x 2 x 0.25 MiB; the k and v
    blocks 2 x 2 x 0.125 MiB; the float32 accumulators, dQ^T
    [16, 128, 512] 4 MiB and dk, dv [8192, 128] 4 + 4 MiB: 41.5 MiB
    before the [512, 512] float32 tiles (the head outside the key block;
    all 8 heads' accumulators resident with the head innermost would be
    over 80)."""
    sa = importlib.import_module("paddle_tpu.ops.pallas.sparse_attention")
    monkeypatch.setattr(sa, "_interpret", lambda: False)
    B, A, T, D = q_shape
    assert sa.sparse_attention_supported((B, T, A, D),
                                         (B, T, k_shape[1], D), dtype)
    assert sa._VMEM_LIMIT == 100 * 2 ** 20
    q, kv = _sds(one_chip, q_shape, dtype), _sds(one_chip, k_shape, dtype)

    def bwd(q, k, v, mask, out, lse, do):
        return sa._bwd(q, k, v, mask, out, lse, do, D ** -0.5, 512)

    compiled = _compile(bwd, q, kv, kv, _sds(one_chip, (B, T, T), jnp.int8),
                        q, _sds(one_chip, (B, A, T), jnp.float32), q)
    kernels = _mosaic_kernels(compiled)
    assert len(kernels) == 1 and "sparse_bwd_dkv" in kernels[0], kernels


def test_mla_flash_attention_fwd_bwd(one_chip, monkeypatch):
    """The joyai_llm_flash cell's attention: keys 128 + 64 wide (the 64
    one rotated key a position for all 32 heads), values 128, two rows of
    8192.  A head's K and V staged whole are 3 MiB + 2 MiB, over what
    Mosaic's default scoped VMEM takes with their double buffers: the
    calls state their own limit (`_staging`), and the gate admits that
    size for the shared-key call alone."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    qk, v = (2, 8192, 32, 192), (2, 8192, 32, 128)
    assert fa.flash_attention_supported(v, v, jnp.bfloat16, v_head_dim=128,
                                        shared_key_dim=64)
    assert fa._staging(8192, 128 + 64, 128, jnp.bfloat16) is not None
    # the shapes the cells had before keep Mosaic's defaults, in the
    # backward walk with a head's dQ block and accumulator too
    for dq_widths in ((), (96,)):
        assert fa._staging(2048, 96, 96, jnp.bfloat16, dq_widths) is None
    for dq_widths in ((), (64,)):
        assert fa._staging(512, 64, 64, jnp.bfloat16, dq_widths) is None
    # the largest plain shapes of the gate: the forward kernel (and EVA's
    # dK/dV-only call) as ever, the walk that holds dQ under a stated
    # limit, narrow heads too (64 or 32 lanes are laid out as 128)
    for D in (128, 64, 32):
        assert fa._staging(8192, D, D, jnp.bfloat16) is None
        assert fa._staging(8192, D, D, jnp.bfloat16, (D,)) is not None
    assert fa._staging(4096, 128, 128, jnp.bfloat16, (128,)) is None
    # a plain call of the cell's size or more is not the kernels'
    assert not fa.flash_attention_supported(qk, qk, jnp.bfloat16,
                                            v_head_dim=128)
    assert not fa.flash_attention_supported(qk, qk, jnp.bfloat16)

    # a value width of its own on the plain entry, at the most the gate
    # admits for it: heads of 192 with values of 128 at 6144 positions
    # (3.75 MiB a head), inside Mosaic's default scoped VMEM
    qk, vv = (1, 6144, 8, 192), (1, 6144, 8, 128)
    assert fa.flash_attention_supported(qk, qk, jnp.bfloat16, v_head_dim=128)
    assert fa._staging(6144, 192, 128, jnp.bfloat16) is None

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    for qk, vv in ((qk, vv), ((1, 8192, 8, 128),) * 2,
                   ((1, 4096, 8, 128),) * 2, ((1, 8192, 8, 64),) * 2,
                   ((1, 8192, 8, 32),) * 2):
        assert fa.flash_attention_supported(qk, qk, jnp.bfloat16,
                                            v_head_dim=vv[-1])
        _one_backward_kernel(_compile(
            jax.value_and_grad(loss, (0, 1, 2)),
            _sds(one_chip, qk, jnp.bfloat16),
            _sds(one_chip, qk, jnp.bfloat16),
            _sds(one_chip, vv, jnp.bfloat16)))

    # as the cell runs them: the rotated 64 of the key once a row, not
    # broadcast to the 32 heads and joined to their 128
    def loss_shared(q, qr, k, kr, v):
        out = fa.flash_attention_shared_key(q, qr, k, kr, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(jax.value_and_grad(loss_shared, (0, 1, 2, 3, 4)),
                        _sds(one_chip, v, jnp.bfloat16),
                        _sds(one_chip, (2, 8192, 32, 64), jnp.bfloat16),
                        _sds(one_chip, v, jnp.bfloat16),
                        _sds(one_chip, (2, 8192, 64), jnp.bfloat16),
                        _sds(one_chip, v, jnp.bfloat16))
    _one_backward_kernel(compiled)


def _one_backward_kernel_a_block(text, blocks):
    """A step over the flash family: a forward kernel a block (the replay
    keeps its ``out`` and ``lse``) and one backward kernel, the dK/dV walk
    that makes dQ too, counted where it was traced."""
    from paddle_tpu.utils import monitor
    for kernel, calls in (("flash_fwd", blocks), ("flash_bwd_dkv", blocks),
                          ("flash_bwd_dq", 0)):
        assert _kernel_count(text, kernel) == calls, kernel
    assert monitor.get_stat("pallas.flash.bwd_fused") == blocks
    assert "pallas.sparse.bwd_fused" not in monitor.all_stats()


@pytest.mark.parametrize(
    "cell_name,blocks,kept,parameters,on_record,finds,head", [
        # 15,094,667,264 and 14,093,140,992 before PR 38: ``dw`` and ``dh``
        # are allocated as the forward pass closes, not as the backward
        # opens.  96-wide heads keep the [B, H, L, D] copies (PR 44)
        ("gpt3_large.train_bf16_b8_s2048", 24, 24, 760e6,
         (1556, 15_109_924_352), dict(transposed=24),
         dict(H=1536, chunks=16)),
        # 14,193,097,728 before PR 44: a step without replay held every
        # layer's [64, 12, 512, 64] copies of q, k, v and out (64 lanes
        # laid out as 128) until its backward; two heads to a lane block,
        # the kernels read the projections' own [64, 512, 768]
        ("bert_base.train_bf16_b64_s512", 12, 0, 132e6,
         (796, 11_154_483_200),
         dict(lies=12, vmem=(2_138_112, 3_297_280)),
         dict(H=768, chunks=32)),
    ], ids=["gpt", "bert"])
def test_flash_cell_step_runs_one_backward_kernel(one_chip, monkeypatch,
                                                  cell_name, blocks, kept,
                                                  parameters, on_record,
                                                  finds, head):
    """The GPT and BERT cells' whole steps for the described v5e: causal
    at 2048 x 96 under per-block recompute, non-causal at 512 x 64 in one
    block a (batch, head), a grid step a pair of heads."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, cell_name, ("flash_attention",))
    _same_program_as_without_counters(compiled, step, footprint, on_record)
    assert 0.9 * parameters < n < 1.1 * parameters
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, blocks)
    _head_made_its_gradients_in_the_forward_pass(text, calls=1, **head)
    stats = monitor.all_stats()
    # BERT's step runs no replay, so nothing is kept for one
    assert _kept(stats) == ({scopes.ATTN_OUT: kept, scopes.ATTN_LSE: kept}
                            if kept else {})
    assert stats["pallas.selected.flash_attention"] >= blocks
    assert "attention.xla_path" not in stats
    _finds_a_head(compiled, stats, **finds)
    assert footprint < 15.75 * 2 ** 30


def test_joyai_llm_flash_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The joyai_llm_flash.train_bf16_b2_s8192 cell's whole step (one
    dense and four expert layers with 16 of 256 experts, the MTP module,
    an eighth of the vocabulary; two rows of 8192) for the described v5e:
    it compiles, holds the flash kernels over 192-wide keys and XLA's own
    grouped-matmul kernel, and fits."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "joyai_llm_flash.train_bf16_b2_s8192",
        ("flash_attention", "moe_combine"))
    # four expert layers and the MTP block's; 14,037,525,504 before PR 46
    _carries_the_moe_counters(compiled, step, calls=5, chunks=2,
                              live_peak=14_021_294_080)
    # 14,736,134,656 without the counters; 14,736,962,560 since PR 38
    # (with v, out, dO and dv of the shared-key call lying it compiled to
    # 14,848,201,728 and ran 1.1 ms slower on the chip: not shipped, PR 44);
    # 14,748,157,440 since the expert layers' sums walk rows (PR 46)
    assert abs(footprint - 14_748_157_440) < 2 * 2 ** 20
    assert cfg["hidden_size"] == 2048 and mix["seq"] == 8192
    assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == 192
    assert 680.3e6 < n < 680.5e6
    text = compiled.as_text()
    # five layers and the module's block; the replay keeps the kernel's
    # out and lse, so each block holds one forward kernel
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    _one_backward_kernel_a_block(text, blocks)
    # the main model's pass through the head and the MTP module's
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=2, H=cfg["hidden_size"], chunks=16)
    assert "ragged-dot" in text
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: blocks, scopes.ATTN_LSE: blocks}
    assert stats["pallas.selected.mla_attention"] >= blocks
    assert "mla_attention.xla_path" not in stats
    # the shared-key call keeps its [B, H, L, D] copies throughout
    _finds_a_head(compiled, stats, transposed=blocks)
    assert (stats["moe.experts_held"], stats["moe.experts_total"],
            stats["moe.top_k"]) == (16, 256, 8)
    assert stats["moe.scoring_sigmoid"] >= blocks - 1
    assert stats["moe.shared_experts"] >= blocks - 1
    assert stats["mtp.modules"] == 1
    _token_major_passes_walk_the_buffer(
        text, stats, blocks - 1, mix["seq"], cfg["num_experts_per_tok"],
        cfg["hidden_size"])
    assert footprint < 15.75 * 2 ** 30


def test_ouro_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The ouro_2_6b.train_bf16_b2_s4096 cell's whole step (8 layers run
    four times on shared weights, sandwich norms, the exit gate, the
    expected loss over four exits of the whole 49,152-wide head; two rows
    of 4096) for the described v5e: it compiles, holds a forward kernel
    and a backward walk a block APPLICATION at a head shape no other cell
    runs ([2, 4096, 16, 128] causal), and fits."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "ouro_2_6b.train_bf16_b2_s4096",
        ("flash_attention",))
    assert n == 612_438_017
    assert (cfg["hidden_size"], cfg["head_dim"], mix["seq"]) == (2048, 128,
                                                                 4096)
    T, L = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    assert (T, L) == (4, 8)
    # 8 blocks of parameters, 32 block bodies: each application keeps its
    # own ``out`` and ``lse``, so the replay holds no forward kernel
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, T * L)
    # one pass of the chunked head over the four exits stacked, reached
    # through ``F.loop_exit_loss`` with no chunk named: 32 chunks of 1024
    # rows (16 of 2048 ran 9 ms a step slower on the chip: XLA splits
    # ``dw``'s contraction there), and under 49,152 float32 columns ``dh``
    # is two products of 512 rows a chunk, which XLA leaves whole (the one
    # product of 1024 rows it cut in four, reading both operands twice);
    # the logits' cotangent is then made ONCE a chunk (at the benchmark's
    # default precision in the bfloat16 the products read; float32 under
    # conftest's), where ``dh`` and ``dw`` each remade it (PR 50: the head
    # 142.9 -> 131.5 ms a step on the chip)
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=32, dh_rows=512)
    in_head = [ln for ln in text.splitlines()
               if f"/{scopes.LINEAR_CROSS_ENTROPY}/while/body" in ln]
    assert sum(" fusion(" in ln and re.search(r"= f32\[512,2048\]", ln)
               is not None for ln in in_head) == 2
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: T * L, scopes.ATTN_LSE: T * L}
    assert (stats["loop.steps"], stats["loop.block_calls"]) == (T, T * L)
    assert stats["pallas.selected.flash_attention"] >= T * L
    assert "attention.xla_path" not in stats
    _finds_a_head(compiled, stats, lies=T * L, vmem=(4_571_136, 10_657_792))
    # the exit distribution and its entropy ride in the carry, float32
    assert {k: (v.shape, v.dtype) for k, v in step._counter_spec.items()} \
        == {scopes.LOOP_EXIT_SHARE: ((1, T), jnp.float32),
            scopes.LOOP_EXIT_ENTROPY: ((1,), jnp.float32)}
    # 14,589,980,672 bytes as this test compiled it in PR 38 (under
    # conftest's matmul precision: not the benchmark's program to the
    # byte); 14,592,852,992 in PR 37, which the chip laid out in
    # 14,463,649,792.  14,557,063,680 since PR 44 (v, out, dO and dv as
    # they lie: the temporaries 5,303,908,864 -> 5,254,384,640); with the
    # head in 16 chunks of 2048 rows it compiled to 15,361,608,192: four
    # [rows, 49152] float32 buffers live in a chunk (PR 50, not shipped);
    # 14,558,675,968 with ``dh`` in two products (PR 50)
    assert abs(footprint - 14_558_675_968) < 64 * 2 ** 20, footprint
    assert footprint < 15.75 * 2 ** 30


def test_nemotron_h_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The nemotron_3_nano_30b_a3b.train_bf16_b2_s8192 cell's whole step
    (MEMEM*EME: four Mamba-2 mixers, four relu2 expert layers with 8 of
    128 experts, one grouped-query attention block on 32 / 2 heads, an
    eighth of the vocabulary; two rows of 8192) for the described v5e: it
    compiles, holds ONE flash forward kernel and one backward walk at a
    head shape no other cell runs ([2, 8192, 32, 128] on [2, 8192, 2,
    128]), XLA's own grouped-matmul kernel, no gate product, the scan as
    its two kernels (a mixer's forward, its replay and its backward) with
    no [chunk, chunk] decay matrix left in HBM, and fits in less than it
    did with the scan in XLA."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch,
        "nemotron_3_nano_30b_a3b.train_bf16_b2_s8192",
        ("flash_attention", "ssd_scan", "causal_conv", "moe_combine"))
    assert n == cfg["parameters"] == 666_963_456
    assert (cfg["hidden_size"], mix["seq"]) == (2688, 8192)
    pattern = cfg["hybrid_override_pattern"]
    assert pattern == "MEMEM*EME"
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, pattern.count("*"))
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=16)
    assert "ragged-dot" in text
    stats = monitor.all_stats()
    experts, mixers = pattern.count("E"), pattern.count("M")
    assert _kept(stats) == {scopes.ATTN_OUT: 1, scopes.ATTN_LSE: 1}
    assert stats["pallas.selected.flash_attention"] >= 1
    assert "attention.xla_path" not in stats
    # 32 query heads on 2: heads in groups keep their copies
    _finds_a_head(compiled, stats, transposed=1)
    assert (_kernel_count(text, scopes.SSD_FWD),
            _kernel_count(text, scopes.SSD_BWD)) == (2 * mixers, mixers)
    assert stats["pallas.selected.ssd_scan"] >= mixers
    assert "ssd_scan.xla_path" not in stats
    assert "f32[2,64,8,8,128,128]" not in text
    _conv_hands_the_scan_its_operands(text, stats, mixers, 2, 8192, 10304,
                                      6144)
    assert (stats["moe.experts_held"], stats["moe.experts_total"],
            stats["moe.top_k"]) == (8, 128, 6)
    assert stats["moe.gateless_experts"] >= experts
    assert stats["moe.scoring_sigmoid"] >= experts
    _token_major_passes_walk_the_buffer(
        text, stats, experts, mix["seq"], cfg["num_experts_per_tok"],
        cfg["hidden_size"])
    # the experts' load and the mixers' two float32 readings ride in the
    # carry, a row a layer
    assert {k: (v.shape, v.dtype) for k, v in step._counter_spec.items()} \
        == {scopes.MOE_EXPERT_LOAD: ((experts, 8), jnp.int32),
            scopes.MOE_CHUNK_ASSIGNMENTS: ((experts, 2), jnp.int32),
            scopes.MOE_FULL_BUFFER_CHUNKS: ((experts,), jnp.int32),
            scopes.MOE_FULLEST_EXPERT_LOAD: ((experts,), jnp.int32),
            scopes.SSM_STATE_SHARE: ((mixers,), jnp.float32),
            scopes.SSM_MEAN_DECAY: ((mixers,), jnp.float32)}
    # PR 39's tree, the scan in XLA, compiled by this test: 15,272,153,600
    assert 0.25 * 16 * 2 ** 30 < footprint < 15_272_153_600, footprint
    # the kernels as on record (PR 40's, compiled by this test at PR 42):
    # head blocks (PR 43) leave a group of 8 heads one step's, the same
    # jaxpr and, source lines dropped, the same optimized step
    vmem = dict(_kernel_vmem(compiled))
    assert {size for name, size in vmem.items()
            if name.startswith(scopes.SSD_FWD)} == {2_560_000}
    # (the backward's 4,997,120 until its dB and dC went to the
    # convolution's kernel and not to XLA's pads: 12 KB of staging, PR 48)
    assert {size for name, size in vmem.items()
            if name.startswith(scopes.SSD_BWD)} == {4_984_832}
    assert all(size < 16 * 2 ** 20 for name, size in vmem.items()
               if name.startswith("conv_")), vmem
    # under conftest's matmul precision; 15,094,020,608 with the
    # convolution in XLA (PR 46 and 47: its float32 [2, 8192, 6144] lived
    # inside one mixer's backward, never at the step's peak; PR 48),
    # 15,083,288,064 before the expert layers' sums walked rows (PR 46;
    # with out and dO lying it compiled to 15,083,398,144 and ran 1.1 ms
    # slower on the chip: not shipped, PR 44)
    assert footprint == 15_087_344_640


def test_granite_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The granite_4_0_h_micro.train_bf16_b1_s8192 cell's whole step
    (MMMMM*MMMM: nine Mamba-2 mixers whose 64 heads share ONE group of B
    and C, one grouped-query attention layer on 32 / 8 heads of 64 at the
    family's multiplier, a SwiGLU of 8192 after every mixer, the tied
    head over an eighth of the vocabulary; one row of 8192) for the
    described v5e: it compiles, every scan runs as ``ssd_fwd`` /
    ``ssd_bwd`` (a mixer's forward, its replay and its backward; none
    falls to the XLA form), the attention as ONE flash forward kernel and
    one backward walk at a head shape no other cell runs ([1, 8192, 32,
    64] on [1, 8192, 8, 64]), and the footprint is the one on record,
    under 15.75 GiB."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "granite_4_0_h_micro.train_bf16_b1_s8192",
        ("flash_attention", "ssd_scan", "causal_conv"))
    assert n == cfg["parameters"] == 772_160_448
    assert (cfg["hidden_size"], mix["batch"], mix["seq"]) == (2048, 1, 8192)
    assert (cfg["mamba_n_heads"], cfg["mamba_n_groups"]) == (64, 1)
    kinds = cfg["layer_types"]
    mixers, attention = kinds.count("mamba"), kinds.count("attention")
    assert (mixers, attention) == (9, 1)
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, attention)
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=8)
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: 1, scopes.ATTN_LSE: 1}
    assert stats["pallas.selected.flash_attention"] >= 1
    assert "attention.xla_path" not in stats
    # 64-wide heads in groups of four: the [B, H, L, D] copies stay
    _finds_a_head(compiled, stats, transposed=1)
    assert (_kernel_count(text, scopes.SSD_FWD),
            _kernel_count(text, scopes.SSD_BWD)) == (2 * mixers, mixers)
    assert stats["pallas.selected.ssd_scan"] >= mixers
    assert "ssd_scan.xla_path" not in stats
    _conv_hands_the_scan_its_operands(text, stats, mixers, 1, 8192, 8512,
                                      4352)
    # the feed-forward layers run under their scope, in every phase
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = [nm for nm in names if scopes.FFN in nm.split("/")]
    assert any("rematted_computation" in nm for nm in under)
    assert any("transpose(" in nm for nm in under)
    assert {k: (v.shape, v.dtype) for k, v in step._counter_spec.items()} \
        == {scopes.SSM_STATE_SHARE: ((mixers,), jnp.float32),
            scopes.SSM_MEAN_DECAY: ((mixers,), jnp.float32)}
    # 13,209,466,880 bytes as this test compiled it in PR 48 (under
    # conftest's matmul precision: not the benchmark's program to the
    # byte; 13,298,342,912 with the convolution in XLA, PR 43 to 47),
    # 10.81 GB of it the state at 14 bytes a parameter
    assert footprint <= 13_298_342_912
    assert abs(footprint - 13_209_466_880) < 64 * 2 ** 20, footprint
    assert 0.25 * 16 * 2 ** 30 < footprint < 15.75 * 2 ** 30
    # a block of 16 heads of the one group: twice what a group of 8 takes
    vmem = dict(_kernel_vmem(compiled))
    assert all(size < 16 * 2 ** 20 for name, size in vmem.items()
               if name.startswith(("ssd_", "conv_"))), vmem


def test_trinity_mini_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The trinity_mini.train_bf16_b1_s16384 cell's whole step (one dense
    and four expert layers: sliding x 4 with rotary positions, one full
    layer with none; 32 query heads on 4 of 128, the gate on attention's
    output, 16 of 128 experts a layer, an eighth of the vocabulary; one
    row of 16,384) for the described v5e: it compiles, all five layers
    run the flash kernels (four of them windowed: the blocks below the
    window are skipped), at a head shape no other cell runs ([1, 16384, 32,
    128] on [1, 16384, 4, 128]: 8 MiB of K and V a head under the stated
    scoped-VMEM limit), and the footprint is the one on record, under
    15.75 GiB."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "trinity_mini.train_bf16_b1_s16384",
        ("flash_attention", "moe_combine"))
    assert n == cfg["parameters"] == 705_474_304
    assert (cfg["hidden_size"], mix["batch"], mix["seq"]) == (2048, 1, 16384)
    kinds = cfg["layer_types"]
    windows, fulls = (kinds.count("sliding_attention"),
                      kinds.count("full_attention"))
    assert (windows, fulls, cfg["sliding_window"]) == (4, 1, 2048)
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, len(kinds))
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=16)
    assert "ragged-dot" in text
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: 5, scopes.ATTN_LSE: 5}
    assert stats["pallas.selected.flash_attention"] >= len(kinds)
    assert "attention.xla_path" not in stats
    # 32 query heads on 4: heads in groups keep their copies
    _finds_a_head(compiled, stats, transposed=len(kinds))
    # the window calls and the gate sit under their scopes, in every phase
    names = [nm.split("/") for nm in
             set(re.findall(r'op_name="([^"]*)"', text))]
    for scope in (scopes.WINDOW_ATTENTION, scopes.ATTN_GATE):
        under = [nm for nm in names if scope in nm]
        assert any("rematted_computation" in nm for nm in under), scope
        assert any(s.startswith("transpose(") for nm in under for s in nm)
    calls = [nm for nm in names if "pallas_call" in nm
             and scopes.ATTENTION in nm]
    assert {scopes.WINDOW_ATTENTION in nm for nm in calls} == {True, False}
    # a window call runs 5 k blocks a q block (4 at the row's start) where
    # the causal triangle has up to 32: 150 of 528 a (batch, head), counted
    # a kernel traced: the forward, the replay's (dead code: out and lse
    # are kept) and the walk
    run = (stats["pallas.flash.window_blocks_full"]
           + stats["pallas.flash.window_blocks_masked"])
    skipped = stats["pallas.flash.window_blocks_skipped"]
    assert run + skipped == windows * 3 * 528
    assert run == windows * 3 * 150
    assert (stats["moe.experts_held"], stats["moe.experts_total"],
            stats["moe.top_k"]) == (16, 128, 8)
    assert stats["moe.scoring_sigmoid"] >= len(kinds) - 1
    assert stats["moe.shared_experts"] >= len(kinds) - 1
    _token_major_passes_walk_the_buffer(
        text, stats, len(kinds) - 1, mix["seq"], cfg["num_experts_per_tok"],
        cfg["hidden_size"], calls_a_layer=3)
    print("trinity kernels' scoped VMEM:", sorted(set(_kernel_vmem(compiled))))
    # 16,517,176,832 bytes as this test compiled it in PR 47 (under
    # conftest's matmul precision: not the benchmark's program to the
    # byte), 9.88 GB of it the state at 14 bytes a parameter; the chip's
    # own reading is PERF.md's
    assert abs(footprint - 16_517_176_832) < 64 * 2 ** 20, footprint
    assert 0.25 * 16 * 2 ** 30 < footprint < 15.75 * 2 ** 30, footprint


def test_lfm2_cell_step_fits_the_chip(one_chip, monkeypatch):
    """The lfm2_24b_a2b.train_bf16_b4_s8192 cell's whole step (a dense
    conv layer, then two attention and four conv layers with 8 of 64
    experts each, the tied head over an eighth of the vocabulary; four
    rows of 8192) for the described v5e: it compiles, every conv mixer
    runs the gated kernels (a forward, its replay and a backward a layer;
    none falls to XLA's form), the two attention layers the flash kernels
    at Granite's head shape with rotary positions, the expert layers the
    grouped products and the token-order sums, and the footprint is the
    one on record, under 15.75 GiB."""
    from paddle_tpu.observability import scopes
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    compiled, n, cfg, mix, footprint, step = _cell_step(
        one_chip, monkeypatch, "lfm2_24b_a2b.train_bf16_b4_s8192",
        ("flash_attention", "causal_conv", "moe_combine"))
    assert n == cfg["parameters"] == 647_819_904
    assert (cfg["hidden_size"], mix["batch"], mix["seq"]) == (2048, 4, 8192)
    kinds = cfg["layer_types"]
    convs, fulls = kinds.count("conv"), kinds.count("full_attention")
    assert (convs, fulls, cfg["conv_L_cache"]) == (5, 2, 3)
    text = compiled.as_text()
    _one_backward_kernel_a_block(text, fulls)
    _head_made_its_gradients_in_the_forward_pass(
        text, calls=1, H=cfg["hidden_size"], chunks=32)
    assert "ragged-dot" in text
    stats = monitor.all_stats()
    assert _kept(stats) == {scopes.ATTN_OUT: fulls, scopes.ATTN_LSE: fulls}
    assert stats["pallas.selected.flash_attention"] >= fulls
    assert "attention.xla_path" not in stats
    # 64-wide heads in groups of four: the [B, H, L, D] copies stay
    _finds_a_head(compiled, stats, transposed=fulls)
    assert (_kernel_count(text, scopes.SHORT_CONV_FWD),
            _kernel_count(text, scopes.SHORT_CONV_BWD)) == (2 * convs, convs)
    assert stats["pallas.selected.gated_short_conv"] >= convs
    assert "gated_short_conv.xla_path" not in stats
    # the in-projection's output and its cotangent in rows, nothing of
    # the operator in float32 at their shapes
    assert "bf16[4,8192,6144]{2,1,0" in text
    for gone in ("[4,8192,6144]{1,2,0", "f32[4,8192,6144]"):
        assert gone not in text, gone
    # the mixer and the operator sit under their scopes, in every phase
    names = [nm.split("/") for nm in
             set(re.findall(r'op_name="([^"]*)"', text))]
    for scope in (scopes.SHORT_CONV, scopes.SHORT_CONV_OP):
        under = [nm for nm in names if scope in nm]
        assert any("rematted_computation" in nm for nm in under), scope
        assert any(s.startswith("transpose(") for nm in under for s in nm)
    assert (stats["moe.experts_held"], stats["moe.experts_total"],
            stats["moe.top_k"]) == (8, 64, 4)
    assert stats["moe.scoring_sigmoid"] >= len(kinds) - 1
    assert "moe.shared_experts" not in stats
    _token_major_passes_walk_the_buffer(
        text, stats, len(kinds) - 1, mix["seq"], cfg["num_experts_per_tok"],
        cfg["hidden_size"], calls_a_layer=2)
    print("lfm2 kernels' scoped VMEM:", sorted(set(_kernel_vmem(compiled))))
    # 14,912,376,832 bytes as this test compiled it in PR 52 (under
    # conftest's matmul precision: not the benchmark's program to the
    # byte), 9.07 GB of it the state at 14 bytes a parameter; the chip's
    # own reading is PERF.md's
    assert abs(footprint - 14_912_376_832) < 64 * 2 ** 20, footprint
    assert 0.25 * 16 * 2 ** 30 < footprint < 15.75 * 2 ** 30, footprint


@pytest.mark.parametrize("window,on_record", [
    (2048, (2_949_120, 38_739_968)),
    (None, (2_469_888, 37_662_720)),
], ids=["window_2048", "full"])
def test_flash_at_16384_stages_a_head_under_the_stated_limit(
        one_chip, monkeypatch, window, on_record):
    """The Trinity cell's two attention calls alone, [1, 16384, 32 on 4,
    128] bf16 causal: the forward kernel and the backward walk compile for
    the described v5e with a head's K and V staged whole (8 MiB, the gate's
    `_STAGED_PLAIN`), under the stated 48 MiB, at the scoped VMEM on
    record (PR 47)."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q_shape, k_shape = (1, 16384, 32, 128), (1, 16384, 4, 128)
    assert fa.flash_attention_supported(q_shape, k_shape, jnp.bfloat16)
    # twice the row, or the same row in float32: the ring's or XLA's
    assert not fa.flash_attention_supported(
        (1, 32768, 32, 128), (1, 32768, 4, 128), jnp.bfloat16)
    assert not fa.flash_attention_supported(q_shape, k_shape, jnp.float32)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    q = _sds(one_chip, q_shape, jnp.bfloat16)
    kv = _sds(one_chip, k_shape, jnp.bfloat16)
    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)), q, kv, kv)
    _one_backward_kernel(compiled)
    sizes = tuple(size for _, size in _kernel_vmem(compiled))
    assert sizes == on_record, sizes
    assert max(sizes) < fa._VMEM_LIMIT_STAGED


@pytest.mark.parametrize("x_shape,groups", [
    ((2, 8192, 64, 64), 8),     # the Nemotron cell's: a group a step
    ((1, 8192, 64, 64), 1),     # the Granite cell's: 4 head blocks a group
], ids=["nemotron_cell", "granite_cell"])
def test_ssd_scan_fwd_bwd(one_chip, monkeypatch, x_shape, groups):
    """The scan's two kernels alone at the two state-space cells' shapes
    (rows of 8192, 64 heads of 64 on a state of 128; 8 groups of 8 heads,
    and ONE group of 64 walked in blocks of 16): both compile for the
    described v5e, inside Mosaic's default scoped VMEM."""
    ssd = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
    monkeypatch.setattr(ssd, "_interpret", lambda: False)
    b_shape = x_shape[:2] + (groups, 128)
    assert ssd.ssd_scan_supported(x_shape, b_shape, jnp.bfloat16, 128)
    assert not ssd.ssd_scan_supported(x_shape, b_shape, jnp.bfloat16, 256)

    def loss(*args):
        return jnp.sum(ssd.ssd_scan(*args).astype(jnp.float32) ** 2)

    args = [_sds(one_chip, shape, dtype) for shape, dtype in (
        (x_shape, jnp.bfloat16), (x_shape[:3], jnp.float32),
        ((64,), jnp.float32), (b_shape, jnp.bfloat16),
        (b_shape, jnp.bfloat16), ((64,), jnp.float32))]
    compiled = _compile(jax.value_and_grad(loss, range(6)), *args)
    kernels = _mosaic_kernels(compiled)
    assert len(kernels) == 2 and "ssd_fwd" in kernels[0], kernels
    assert "ssd_bwd" in kernels[1], kernels
    vmem = _kernel_vmem(compiled)
    print(f"ssd_scan at {list(x_shape)} / {list(b_shape)}: "
          + ", ".join(f"{name} {size} bytes of VMEM" for name, size in vmem))
    assert len(vmem) == 2 and all(size < 16 * 2 ** 20 for _, size in vmem)


@pytest.mark.parametrize("x_shape,parts", [
    ((2, 8192, 10304), (4096, 1024, 1024)),   # the Nemotron cell's mixers
    ((1, 8192, 8512), (4096, 128, 128)),      # the Granite cell's
], ids=["nemotron_cell", "granite_cell"])
def test_causal_conv_fwd_bwd(one_chip, monkeypatch, x_shape, parts):
    """The convolution's two kernels alone at the two state-space cells'
    shapes (the in-projection's output whole, the channels from 4096 on,
    x, B and C back apart): both compile for the described v5e inside
    Mosaic's default scoped VMEM; what the gate refuses sits beside."""
    conv = importlib.import_module("paddle_tpu.ops.pallas.causal_conv")
    monkeypatch.setattr(conv, "_interpret", lambda: False)
    C = sum(parts)
    w_shape = (4, C)

    def takes(x=x_shape, w=w_shape, dtype=jnp.bfloat16, first=4096, p=parts,
              activation="silu"):
        return conv.causal_conv1d_supported(x, w, dtype, first, p,
                                            activation)
    assert takes() and takes(activation=None) and takes(dtype=jnp.float32)
    assert not takes(activation="gelu")
    assert not takes(w=(9, C))
    assert not takes(first=4096 + 64) and not takes(first=4096 + 128)
    assert not takes(x=(x_shape[0], 8200, x_shape[2]))
    assert not takes(p=parts[:2] + (parts[2] - 64, 64))
    assert not takes(x=x_shape[:2] + (4096 + C - 128,))

    def loss(x, w, b):
        outs = conv.causal_conv1d(x, w, b, "silu", 4096, parts)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in outs)

    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)),
                        _sds(one_chip, x_shape, jnp.bfloat16),
                        _sds(one_chip, w_shape, jnp.bfloat16),
                        _sds(one_chip, (C,), jnp.bfloat16))
    kernels = _mosaic_kernels(compiled)
    assert len(kernels) == 2 and "conv_fwd" in kernels[0], kernels
    assert "conv_bwd" in kernels[1], kernels
    vmem = _kernel_vmem(compiled)
    print(f"causal_conv at {list(x_shape)} / {list(parts)}: "
          + ", ".join(f"{name} {size} bytes of VMEM" for name, size in vmem))
    assert len(vmem) == 2 and all(size < 16 * 2 ** 20 for _, size in vmem)
    # (alone, XLA lays the gradient it returns, the pad to the operand's
    # width, with T minor and copies d(xBC) for it; in a cell's step the
    # pad is an operand of the projection's products and stays in rows)
    assert f"f32[{x_shape[0]},8192,{C}]" not in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_gated_short_conv_fwd_bwd(one_chip, monkeypatch, dtype):
    """The gated short convolution's two kernels alone at the LFM2 cell's
    shape (the in-projection's output whole, [4, 8192, 3 x 2048], three
    taps): both compile for the described v5e inside Mosaic's default
    scoped VMEM, and the backward's one wide result is the operand's
    cotangent as it lies; what the gate refuses sits beside."""
    conv = importlib.import_module("paddle_tpu.ops.pallas.causal_conv")
    monkeypatch.setattr(conv, "_interpret", lambda: False)
    x_shape, w_shape = (4, 8192, 6144), (3, 2048)

    def takes(x=x_shape, w=w_shape, dt=dtype):
        return conv.gated_short_conv_supported(x, w, dt)
    assert takes()
    assert not takes(w=(9, 2048)) and not takes(x=(4, 8200, 6144))
    assert not takes(x=(4, 8192, 8192)) and not takes(dt=jnp.float16)
    assert not takes(x=(4, 8192, 3 * 1984), w=(3, 1984))

    def loss(x, w):
        return jnp.sum(conv.gated_short_conv(x, w).astype(jnp.float32) ** 2)

    compiled = _compile(jax.value_and_grad(loss, (0, 1)),
                        _sds(one_chip, x_shape, dtype),
                        _sds(one_chip, w_shape, dtype))
    kernels = _mosaic_kernels(compiled)
    assert len(kernels) == 2 and "short_conv_fwd" in kernels[0], kernels
    assert "short_conv_bwd" in kernels[1], kernels
    vmem = _kernel_vmem(compiled)
    print(f"gated_short_conv at {list(x_shape)} {dtype.__name__}: "
          + ", ".join(f"{name} {size} bytes of VMEM" for name, size in vmem))
    assert len(vmem) == 2 and all(size < 16 * 2 ** 20 for _, size in vmem)
    # nothing in float32 at the operand's or the result's shape
    if dtype == jnp.bfloat16:
        text = compiled.as_text()
        assert "f32[4,8192,6144]" not in text
        assert "f32[4,8192,2048]{2,1,0} fusion" not in text


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
@pytest.mark.parametrize("H,rows", [(2048, 18432), (2688, 6912)],
                         ids=["keye_cell", "nemotron_cell"])
def test_moe_combine(one_chip, monkeypatch, H, rows, weighted):
    """The expert layers' sum over a token's rows alone, at the widest and
    the narrowest of the three expert cells' small buffers (8192 tokens a
    chunk; 18,432 rows of 2048, 6,912 of 2688), as the forward pass calls
    it (gate-weighted, float32 out) and as the dispatch's transpose does
    (plain, out in the rows' type): one Mosaic kernel inside the default
    scoped VMEM, the grid's pairs made beside it in XLA."""
    mc = importlib.import_module("paddle_tpu.ops.pallas.moe_combine")
    monkeypatch.setattr(mc, "_interpret", lambda: False)
    n, dtype = 8192, jnp.bfloat16
    assert mc.moe_combine_supported(n, H, dtype)
    assert not mc.moe_combine_supported(n + 64, H, dtype)
    assert not mc.moe_combine_supported(n, H + 64, dtype)
    assert not mc.moe_combine_supported(n, H, jnp.float16)
    padded = mc.padded_rows(rows)
    assert padded >= rows and padded % 256 == 0
    args = [_sds(one_chip, (padded, H), dtype),
            _sds(one_chip, (padded,), jnp.int32)]
    if weighted:
        args.append(_sds(one_chip, (padded,), jnp.float32))
        compiled = _compile(
            lambda r, t, w: mc.moe_combine(r, t, w, n), *args)
    else:
        compiled = _compile(
            lambda r, t: mc.moe_combine(r, t, None, n, dtype), *args)
    (name, size), = _kernel_vmem(compiled)
    print(f"moe_combine at [{padded}, {H}] -> [{n}, {H}]: {size} bytes of "
          "VMEM")
    assert name.startswith("moe_combine") and size < 16 * 2 ** 20
    out, = jax.tree.leaves(compiled.out_info)
    assert (out.shape, out.dtype) == (
        (n, H), jnp.float32 if weighted else dtype)


# ------------------------------------------------------- fused epilogue --
_LN = ("layer_norm", 1e-5, True, True)
# (M, K, N, stages): the recipe the Executor realises on BERT-base
# (every block's proj+residual+LayerNorm) at chip_smoke's batches, and
# the other stage kinds at widths their gate admits
_EPILOGUES = {
    "bert_b64_add_ln": (64 * 512, 768, 768, (("add",), _LN)),
    "bert_b16_add_ln": (16 * 512, 768, 768, (("add",), _LN)),
    "gelu_tanh": (32768, 768, 1280, (("gelu", True),)),
    "gelu_exact": (32768, 768, 1280, (("gelu", False),)),
    "relu": (32768, 1024, 1024, (("relu",),)),
}


def _epilogue_operands(one_chip, m, n, stages, dtype):
    ops, shapes = [], []
    for st in stages:
        if st[0] == "add":
            shapes.append((m, n))
        elif st[0] == "layer_norm":
            shapes.extend([(n,)] * (int(st[2]) + int(st[3])))
    for s in shapes:
        ops.append(_sds(one_chip, s, dtype))
    return ops, shapes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_EPILOGUES))
def test_fused_epilogue_fwd_bwd(one_chip, case, dtype):
    from paddle_tpu.ops.pallas.fused_epilogue import (
        fused_epilogue_supported, fused_linear_epilogue)
    m, k, n, stages = _EPILOGUES[case]
    ops, op_shapes = _epilogue_operands(one_chip, m, n, stages, dtype)
    assert fused_epilogue_supported((m, k), (k, n), dtype,
                                    (("bias",),) + stages,
                                    [(n,)] + op_shapes)

    def loss(x, w, b, *operands):
        out = fused_linear_epilogue(x, w, b, stages, operands,
                                    interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _compile(jax.value_and_grad(loss, tuple(range(3 + len(ops)))),
             _sds(one_chip, (m, k), dtype), _sds(one_chip, (k, n), dtype),
             _sds(one_chip, (n,), dtype), *ops)


@pytest.mark.parametrize("m,k,n,stages,op_shapes", [
    # BERT's FFN weights: staged whole with their f32 dw accumulator,
    # they leave no room for a useful row block
    (32768, 768, 3072, (("gelu", True),), []),
    (32768, 3072, 768, (("add",), _LN), [(32768, 768), (768,), (768,)]),
    # a wide row: even the smallest row block does not fit
    (32768, 8, 131072, (("relu",),), []),
], ids=["ffn_up", "ffn_down", "wide_row"])
def test_fused_epilogue_gate_rejects(m, k, n, stages, op_shapes):
    from paddle_tpu.ops.pallas.fused_epilogue import \
        fused_epilogue_supported
    for dtype in (jnp.float32, jnp.bfloat16):
        assert not fused_epilogue_supported(
            (m, k), (k, n), dtype, (("bias",),) + stages,
            [(n,)] + op_shapes)


# ----------------------------------------------------------- fused Adam --
@pytest.mark.parametrize("shape", [(30522, 768), (768, 3072), (768,)],
                         ids=["embedding", "ffn", "bias"])
def test_fused_adam(one_chip, shape):
    from paddle_tpu.ops.pallas.fused_adam import (fused_adam_supported,
                                                  fused_adam_update)
    assert fused_adam_supported(shape, jnp.float32)
    assert not fused_adam_supported(shape, jnp.bfloat16)
    a = _sds(one_chip, shape, jnp.float32)
    s = _sds(one_chip, (), jnp.float32)
    _compile(lambda p, g, m, v, lr, step: fused_adam_update(
        p, g, m, v, lr, step, interpret=False), a, a, a, a, s, s)


# ------------------------------------------------------ paged attention --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_decode(one_chip, dtype):
    """chip_smoke's serve geometry: 8 slots, 16 heads over 4 KV heads,
    head_dim 128, page 16, 2048-token context, 8-layer stacked pool."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode, paged_decode_supported)
    slots, heads, kv_heads, d, page, pages = 8, 16, 4, 128, 16, 128
    pool = (8, slots * pages + 1, page, kv_heads, d)
    assert paged_decode_supported((slots, heads, d), pool, dtype, page)
    assert not paged_decode_supported((slots, heads, 64),
                                      pool[:-1] + (64,), dtype, page)
    kv = _sds(one_chip, pool, dtype)
    _compile(lambda q, k, v, table, lens: paged_attention_decode(
        q, k, v, table, lens, layer=3, interpret=False),
        _sds(one_chip, (slots, heads, d), dtype), kv, kv,
        _sds(one_chip, (slots, pages), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32))


# --------------------------------------------------------- chunk matmul --
@pytest.mark.parametrize("m,k,n,dtype", [
    (4096, 768, 3072, jnp.float32),     # BERT FFN chunk
    (2048, 1536, 6144, jnp.bfloat16),
], ids=["bert_ffn_f32", "wide_bf16"])
def test_chunk_matmul(one_chip, m, k, n, dtype):
    from paddle_tpu.ops.pallas.collective_matmul import (
        chunk_matmul, chunk_matmul_supported)
    assert chunk_matmul_supported((m, k), (k, n), dtype, dtype)
    _compile(lambda x, w: chunk_matmul(x, w, interpret=False),
             _sds(one_chip, (m, k), dtype), _sds(one_chip, (k, n), dtype))

