#!/usr/bin/env python
"""Standing proof that paddle_tpu's main paths start and run on the chip.

    python chip_smoke.py              # one TPU v5e chip: every phase below
    python chip_smoke.py --multichip  # four chips: the sharded phase only

One process, TPU only: without a TPU it exits non-zero and prints no
result.  Every phase raises on failure, so any failure is a non-zero
exit.  The phases drive the entry points a user calls, at full
published width (depth and weights are what they are: BERT-base is 12
layers, the weights are random from a seed):

  train      BERT-base b64 s512 bf16-O2 through nn.Layer -> amp.decorate
             -> jit.TrainStep(donate=True): one compile, finite and
             falling loss, the flash kernel in the executable
  static     the same BERT-base through paddle.static Program ->
             Executor, default flags: the fusion tier and the fused
             optimizer as a user on a TPU gets them
  serve      GenerationEngine over PagedDecoderLM (hidden 2048, head_dim
             128) behind the real HTTP plane, 8 /generate requests
             through serving.Client, tokens compared with the same model
             on the reference attention tier
  kernels    each Pallas kernel alone against its jnp reference
  callbacks  a to_static function with a traced print and assert

Timings printed here are SMOKE timings (one unrepeated reading each, on
a machine that was just handed over) — not benchmark numbers.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""
import argparse
import contextlib
import functools
import gc
import io
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# ---------------------------------------------------------------- widths --
# The two BERT phases build what the benchmark's cells build: the widths,
# the optimizer and the batch come from the cell's own files, the model
# from the cell's own builder (benchmark/models/), so what is proved here
# is what the ledger's numbers come from.
TRAIN_CELL = "bert_base.train_bf16_b64_s512"
STATIC_CELL = "bert_base.static_f32_b64_s512"
# the train cell runs without dropout (its reference cannot follow the
# masks); this phase keeps the published 0.1, the flash kernel's PRNG path
TRAIN_DROPOUT = 0.1
# the shapes the kernels phase hands each kernel alone: BERT-base's, at
# the two cells' batch
BERT_BASE = dict(vocab=30522, hidden=768, heads=12, ffn=3072, seq=512,
                 batch=64, dropout=TRAIN_DROPOUT)
# attention of the benchmark's GPT cell: gpt3_large at context 2048
GPT_CELL = dict(seq=2048, heads=16, head_dim=96)
SERVE = dict(vocab_size=32000, hidden=2048, num_layers=8, num_heads=16,
             num_kv_heads=4, ffn=8192, seed=0, dyadic=True)
SERVE_ENGINE = dict(num_slots=8, page_size=16, max_context=2048,
                    prompt_buckets=(64, 256, 1024, 2048))
SERVE_PROMPT_LENS = (16, 60, 130, 250, 400, 700, 1100, 1500)
SERVE_NEW_TOKENS = 32


def log(msg):
    print(msg, flush=True)


def load_parts(cell_name, rehearse=False):
    """(cell, cfg, mix, model module) of one benchmark cell, through
    benchmark/run.py::load_parts; ``rehearse`` takes the cell's tiny
    rehearsal sizes (for a dry run on the CPU)."""
    bench_dir = os.path.join(REPO, "benchmark")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    import run as harness
    return harness.load_parts(cell_name, rehearse=rehearse)[:4]


# ------------------------------------------------- what jax compiled/ran --
def compiled_so_far():
    """Every executable jax has built or loaded, as the program's set-up
    timeline has it (``observability.setup_report``, every owner):
    ({function name: (count, seconds)}, how many the persistent cache
    answered)."""
    from paddle_tpu import observability
    rep = observability.setup_report()
    built = {}
    for owner in rep["owners"].values():
        for phase in ("load", "compile"):
            fns = owner["phases"].get(phase, {}).get("functions", {})
            for name, f in fns.items():
                n, secs = built.get(name, (0, 0.0))
                built[name] = (n + f["count"], secs + f["seconds"])
    return built, sum(c["loads"] for c in rep["cache"].values())


def compiled_since(mark):
    """([(function name, count, seconds)], their seconds, cache hits)
    since ``mark`` (a ``compiled_so_far()``)."""
    built, hits = compiled_so_far()
    new = []
    for name, (n, secs) in built.items():
        n0, secs0 = mark[0].get(name, (0, 0.0))
        if n > n0:
            new.append((name, n - n0, secs - secs0))
    return new, sum(c[2] for c in new), hits - mark[1]


def executable_text(module_name):
    """Optimised HLO of the one live executable jax named
    ``module_name`` — what actually runs on the device, whether it was
    compiled in this process or loaded from the persistent cache."""
    import jax
    found = []
    for ex in jax.devices()[0].client.live_executables():
        mod = ex.hlo_modules()[0]
        if mod.name == module_name:
            found.append(mod.to_string())
    if len(found) != 1:
        raise AssertionError(
            f"expected one live executable named {module_name!r}, "
            f"found {len(found)}")
    return found[0]


def mosaic_kernels(text, at_least, what):
    """Count the Mosaic custom calls in an executable's text.  An
    interpret-mode kernel lowers to plain HLO and leaves none."""
    n = text.count('custom_call_target="tpu_custom_call"')
    if n < at_least:
        raise AssertionError(
            f"{what}: {n} Mosaic kernels in the executable, "
            f"expected at least {at_least}")
    return n


def selected_since(before):
    """Pallas kernels selected (at trace time) since ``before``."""
    from paddle_tpu.ops.pallas.support import kernel_selections
    return {k: v - before.get(k, 0) for k, v in kernel_selections.items()
            if v != before.get(k, 0)}


def selections():
    from paddle_tpu.ops.pallas.support import kernel_selections
    return dict(kernel_selections)


def peak_bytes():
    import jax
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def report(phase, mark, extra):
    compiles, secs, hits = compiled_since(mark)
    big = sorted(compiles, key=lambda c: -c[2])[:3]
    log(f"[{phase}] compiles={sum(c[1] for c in compiles)} "
        f"compile_s={secs:.1f} persistent_cache_hits={hits} "
        f"slowest={[(n, round(s, 1)) for n, _, s in big]}")
    log(f"[{phase}] peak_bytes_in_use(process so far)={peak_bytes()} "
        + " ".join(f"{k}={v}" for k, v in extra.items()))


def finite(values, what):
    if not all(np.isfinite(v) for v in values):
        raise AssertionError(f"{what}: non-finite value in {values}")


# ----------------------------------------------------------------- train --
def phase_train(steps=6, rehearse=False):
    """The BERT cell's construction (benchmark/runners/train_step.py):
    the cell's model and loss -> O2 decorate -> TrainStep, dropout on."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm

    cell, cfg, mix, model_mod = load_parts(TRAIN_CELL, rehearse)
    cfg = {**cfg, "hidden_dropout_prob": TRAIN_DROPOUT,
           "attention_probs_dropout_prob": TRAIN_DROPOUT}
    o = cell["optimizer"]
    mark = compiled_so_far()
    paddle.seed(2024)
    model, loss_fn = model_mod.build(cfg, cell["model_args"])
    opt = optimizer.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["eps"], parameters=model.parameters(),
        weight_decay=o["weight_decay"],
        grad_clip=ClipGradByGlobalNorm(o["clip_global_norm"]),
        multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype=cell["dtype"])
    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)
    rng = np.random.RandomState(0)
    shape, vocab = (mix["batch"], mix["seq"]), cfg["vocab_size"]
    x = jnp.asarray(rng.randint(0, vocab, shape, dtype=np.int32))
    y = jnp.asarray(rng.randint(0, vocab, shape, dtype=np.int32))

    losses, ms = [], []
    for i in range(steps):
        if i == 2:          # steps 0 and 1 may build helpers
            third = compiled_so_far()
        t0 = time.perf_counter()
        losses.append(float(step(x, y)))        # float() waits for the chip
        ms.append((time.perf_counter() - t0) * 1000)
    finite(losses, "train loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {losses}")
    n_step = sum(n for name, n, _ in compiled_since(mark)[0]
                 if "step_fn" in name)
    late = compiled_since(third)[0]
    if n_step != 1 or late:
        raise AssertionError(
            f"train step compiled {n_step} times (want 1); compiles "
            f"after step 1: {late}")
    # attention at seq 512 takes the flash kernel: fwd + 2 bwd kernels
    # per layer would be 36; one is enough to prove the tier
    n_kernels = mosaic_kernels(executable_text("jit_step_fn"), 3,
                               "train step")
    report("train", mark, {
        "losses": [round(v, 4) for v in losses],
        "first_step_ms(compile incl.)": round(ms[0]),
        "smoke_step_ms": [round(v, 1) for v in ms[2:]],
        # flash attention is the only kernel TrainStep can select
        "mosaic_kernels(flash fwd+bwd)": n_kernels})


# ---------------------------------------------------------------- static --
def build_static(cell, cfg, mix, model_mod):
    """A static cell's program, seeded, as the cell's own builder records
    it (benchmark/models/bert_static.py::build), and one seeded feed.
    Takes what ``load_parts`` returns; call under
    ``paddle.enable_static()``.  -> (program, loss variable, feed)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    paddle.seed(2024)
    prog, loss, _, constant_feeds = model_mod.build(
        cfg, cell["model_args"], mix["batch"], mix["seq"],
        cell["optimizer"])
    rng = np.random.RandomState(0)
    shape = (mix["batch"], mix["seq"])
    feed = {name: jnp.asarray(rng.randint(0, cfg["vocab_size"], shape,
                                          dtype=np.int64))
            for name in ("ids", "labels")}
    feed.update({k: jnp.asarray(v) for k, v in constant_feeds.items()})
    return prog, loss, feed


def phase_static(steps=3, rehearse=False):
    """The static cell's program through the Executor, default flags.
    Compiled for a described v5e it has 14.86 GiB live of the chip's
    15.75 (PERF.md, Findings), so it fits — as long as the phase before
    it left nothing on the device."""
    import paddle_tpu as paddle
    from paddle_tpu.observability import explain_compiles
    from paddle_tpu.utils import monitor

    mark, sel0 = compiled_so_far(), selections()
    paddle.enable_static()
    try:
        cell, cfg, mix, model_mod = load_parts(STATIC_CELL, rehearse)
        prog, loss, feed = build_static(cell, cfg, mix, model_mod)
        exe = paddle.static.Executor()
        losses, ms = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            out = exe.run(prog, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(out[0])))
            ms.append((time.perf_counter() - t0) * 1000)
        finite(losses, "static loss")
        if exe.compile_count != 1:
            raise AssertionError(
                f"Executor compiled {exe.compile_count} times, want 1")
        rec = [r for r in explain_compiles("executor")["records"]
               if r["identity"] == prog._serial][-1]
        kernels = list(rec.get("kernels") or ())
        n_epi = sum(k.startswith("fused_epilogue[") for k in kernels)
        # BERT-base: each block's proj+residual+LayerNorm fuses (the FFN
        # weights are past the kernel's gate), so one per layer
        layers = cfg["num_hidden_layers"]
        if n_epi < layers or "fused_adam" not in kernels:
            raise AssertionError(
                f"compile record should name >= {layers} fused "
                f"epilogues and fused_adam, has {kernels}")
        if monitor.get_stat("predicted.executor.errors"):
            raise AssertionError("the cost model failed on this program "
                                 "(predicted.executor.errors > 0)")
        # each epilogue is a forward and a backward kernel; the fused
        # Adam adds one kernel per parameter XLA did not fold away
        n_kernels = mosaic_kernels(executable_text("jit_train_fn"),
                                   2 * n_epi + 1, "static step")
        report("static", mark, {
            "losses": [round(v, 4) for v in losses],
            "first_step_ms(compile incl.)": round(ms[0]),
            "smoke_step_ms": [round(v, 1) for v in ms[1:]],
            "record_kernels": sorted(set(kernels)),
            "predicted_step_s": (rec.get("predicted") or {}).get(
                "predicted_step_s"),
            "mosaic_kernels": n_kernels,
            "selected": selected_since(sel0)})
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()


# ----------------------------------------------------------------- serve --
def phase_serve(model_cfg=SERVE, engine_cfg=SERVE_ENGINE,
                prompt_lens=SERVE_PROMPT_LENS, new_tokens=SERVE_NEW_TOKENS):
    """GenerationEngine behind serving.ServingServer, as tools/serve.py
    wires an engine: bind not-ready, warm up, mark ready, serve."""
    from paddle_tpu import serving
    from paddle_tpu.core.flags import get_flag, set_flags
    from paddle_tpu.ops import attention as attn

    mark, sel0 = compiled_so_far(), selections()
    model = serving.PagedDecoderLM(**model_cfg)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, model_cfg["vocab_size"], (n,)).tolist()
               for n in prompt_lens]

    eng = serving.GenerationEngine(model, **engine_cfg)
    log(f"[serve] weights float32, KV pool dtype {eng.config.dtype} "
        f"(serving/kv_cache.py default), pool {eng._pool.kv[0].shape}")
    srv = serving.ServingServer(None, host="127.0.0.1", port=0,
                                generation=eng, ready=False).start()
    try:
        t0 = time.perf_counter()
        variants = eng.warmup()
        warm_s = time.perf_counter() - t0
        srv.mark_ready()
        warm = compiled_so_far()

        results, errors, lat = {}, [], {}

        def one(i):
            try:
                t = time.perf_counter()
                results[i] = serving.Client(srv.url, timeout=600).generate(
                    prompts[i], max_new_tokens=new_tokens)
                lat[i] = (time.perf_counter() - t) * 1000
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append((i, e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0][1]
        if len(results) != len(prompts):
            raise AssertionError("a /generate request did not return")
        st = eng.stats()
        c = st["counters"]
        late = compiled_since(warm)[0]
        late = [x for x in late if "step_fn" in x[0] or "prefill" in x[0]]
        if st["recompiles_after_warmup"] or late:
            raise AssertionError(
                f"compiles after warmup: engine says "
                f"{st['recompiles_after_warmup']}, jax says {late}")
        if c["failed"] or c["decode_errors"] or c["decode_retries"]:
            raise AssertionError(f"engine errors: {dict(c)}")
        for i, toks in results.items():
            if len(toks) != new_tokens or not all(
                    0 <= t < model_cfg["vocab_size"] for t in toks):
                raise AssertionError(f"request {i}: bad tokens {toks}")
        # the engine holds its AOT executables itself, one per context
        # bucket: one paged kernel per layer in the widest decode step
        width = max(b for kind, b in eng._execs if kind == "decode")
        n_kernels = mosaic_kernels(
            eng._execs[("decode", width)].as_text(),
            model_cfg["num_layers"], "decode step")
    finally:
        srv.close()
        eng.close()

    # the same model on the reference tier (gather + jnp attention): the
    # only flag this script touches, restored right after
    prev = get_flag("use_pallas_kernels")
    set_flags({"use_pallas_kernels": False})
    attn.register_paged_attention_kernel(None)
    try:
        ref_eng = serving.GenerationEngine(model, **engine_cfg)
        try:
            ref = {i: ref_eng.generate_sync(prompts[i],
                                            max_new_tokens=new_tokens,
                                            timeout=900)
                   for i in range(len(prompts))}
            if any("tpu_custom_call" in ex.as_text()
                   for ex in ref_eng._execs.values()):
                raise AssertionError("the reference tier's executables "
                                     "hold a Mosaic kernel")
        finally:
            ref_eng.close()
    finally:
        set_flags({"use_pallas_kernels": prev})
        attn.register_paged_attention_kernel(None)
    same = [i for i in sorted(results) if results[i] == ref[i]]
    first_diff = {i: next(j for j, (a, b) in enumerate(
        zip(results[i], ref[i])) if a != b)
        for i in sorted(results) if i not in same}
    # The two tiers are not bitwise twins on the chip: the kernel
    # accumulates f32 at HIGHEST precision, the gather reference at the
    # backend's default (one bf16 pass), so over 32 greedy steps a
    # near-tie between the top two of 32000 logits can flip.  A broken
    # kernel diverges in every request within a token or two; a correct
    # one agrees on most.  At least two requests must agree on all 32.
    if len(same) < 2:
        raise AssertionError(
            f"kernel-tier tokens equal the reference tier's in only "
            f"{len(same)} of {len(results)} requests; first "
            f"differences at {first_diff}")
    report("serve", mark, {
        "warm_variants": variants, "warmup_s": round(warm_s, 1),
        "requests": len(results), "wall_s": round(wall, 2),
        "smoke_request_ms": [round(lat[i]) for i in sorted(lat)],
        "smoke_decode_step_ms_p50": (st["step_ms"] or {}).get("p50"),
        "tokens_equal_reference": f"{len(same)}/{len(results)}",
        "first_difference_at": first_diff,
        "mosaic_kernels_decode": n_kernels,
        "selected": selected_since(sel0)})


# --------------------------------------------------------------- kernels --
# Each Pallas kernel alone against the repo's jnp reference.  The
# references run at matmul precision 'highest': the kernels accumulate
# f32 exactly, the backend's default is one bf16 pass.  Tolerances are
# relative to max|reference|: 1e-4 for f32 (reduction order only), 3e-2
# for bf16 (the kernels keep the f32 accumulator through the epilogue,
# the references round between ops; gradients get 4x).
F32_TOL, BF16_TOL = 1e-4, 3e-2


def _close(got, want, tol, what):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    if not np.isfinite(err) or err > tol * scale:
        raise AssertionError(
            f"{what}: max |diff| {err:.3e} > {tol:g} x max|ref| {scale:.3e}")
    return float(f"{err / scale:.2e}")


def _rnd(i, shape, dtype, scale=1.0):
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(jax.random.fold_in(jax.random.key(0), i), shape,
                          jnp.float32)
    return (x * scale).astype(dtype)


def _sq(fn):
    import jax.numpy as jnp
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)


def check_flash(errs, bert, gpt=GPT_CELL):
    """Flash attention fwd+bwd at BERT-base heads and, causal, at the
    benchmark's GPT cell's, a slice of the batch each: the oracle holds
    the scores."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       mha_reference)
    heads = (bert["seq"], bert["heads"], bert["hidden"] // bert["heads"])
    for tag, shape, causal in (("flash", (4,) + heads, False),
                               ("flash_causal",
                                (2, gpt["seq"], gpt["heads"],
                                 gpt["head_dim"]), True)):
        q, k, v = (_rnd(i, shape, jnp.bfloat16) for i in (1, 2, 3))
        flash, plain = (functools.partial(f, causal=causal)
                        for f in (flash_attention, mha_reference))
        errs[f"{tag}_fwd"] = _close(jax.jit(flash)(q, k, v),
                                    jax.jit(plain)(q, k, v), BF16_TOL,
                                    f"{tag} fwd")
        got = jax.jit(jax.grad(_sq(flash), (0, 1, 2)))(q, k, v)
        ref = jax.jit(jax.grad(_sq(plain), (0, 1, 2)))(q, k, v)
        for n, a, b in zip("qkv", got, ref):
            errs[f"{tag}_d{n}"] = _close(a, b, 4 * BF16_TOL, f"{tag} d{n}")
    # the choice, not only the kernel: at BERT's shape sdpa must take it
    # (PERF.md, PR 27: 238.6 -> 230.2 ms a step in the BERT cell)
    import paddle_tpu.nn.functional as F
    from paddle_tpu.utils import monitor
    names = ("pallas.selected.flash_attention", "attention.xla_path")
    before = monitor.all_stats()
    q = _rnd(1, (4,) + heads, jnp.bfloat16)
    F.scaled_dot_product_attention(q, q, q)
    after = monitor.all_stats()
    took = tuple(after.get(n, 0) - before.get(n, 0) for n in names)
    if took != (1, 0):
        raise AssertionError(
            f"sdpa at BERT's shape {(4,) + heads} took {dict(zip(names, took))}: "
            "the kernel is expected (flash_attention.py::_KERNEL_FROM)")
    errs["sdpa_bert_path"] = "flash kernel"


def check_flash_dropout(errs, bert):
    """In-kernel dropout has no CPU oracle: the same seed must give the
    same bits (forward and gradients), another seed must not."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    shape = (4, bert["seq"], bert["heads"], bert["hidden"] // bert["heads"])
    q, k, v = (_rnd(i, shape, jnp.bfloat16) for i in (1, 2, 3))

    def drop(q, k, v, seed):
        out = flash_attention(q, k, v, dropout_p=bert["dropout"], seed=seed)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    vg = jax.jit(jax.value_and_grad(drop, (0, 1, 2), has_aux=True))
    s1, s2 = (jnp.full((1, 1), s, jnp.int32) for s in (11, 12))
    (_, o_a), g_a = vg(q, k, v, s1)
    (_, o_b), g_b = vg(q, k, v, s1)
    (_, o_c), _ = vg(q, k, v, s2)
    same = all(bool(jnp.array_equal(a, b))
               for a, b in zip((o_a,) + g_a, (o_b,) + g_b))
    if not same or bool(jnp.array_equal(o_a, o_c)):
        raise AssertionError("flash dropout is not a pure function of "
                             "its seed")
    finite([float(jnp.sum(g.astype(jnp.float32))) for g in g_a],
           "flash dropout grads")
    if bool(jnp.array_equal(o_a, jax.jit(flash_attention)(q, k, v))):
        raise AssertionError("flash dropout dropped nothing")
    errs["flash_dropout"] = "deterministic per seed"


def check_flash_grouped(errs, shape=(1, 2048, 32, 128), kv_heads=2,
                        scale=None, tag="flash_grouped", window=None,
                        heads_a_step=None):
    """Grouped key/value heads through the kernels' index maps: the
    Nemotron cell's 32 query heads on 2 (a quarter of its length), the
    Granite cell's own call, [1, 8192, 32 on 8, 64] at the family's
    ``scale`` 1/64, and the Trinity cell's two, [1, 16384, 32 on 4, 128]
    under a ``window`` of 2048 and full.  Forward and gradients against
    the oracle, which repeats k and v and runs ``heads_a_step`` query
    heads at a time (a key/value head's group by default) so that its
    scores fit."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       mha_reference)
    B, L, H, D = shape
    kv = (B, L, kv_heads, D)
    q, k, v = (_rnd(i, s, jnp.bfloat16)
               for i, s in ((1, shape), (2, kv), (3, kv)))
    flash = functools.partial(flash_attention, causal=True, scale=scale,
                              window=window)
    n = heads_a_step or H // kv_heads       # query heads an oracle step
    steps = H // n

    def plain(q, k, v):
        some = jax.checkpoint(lambda args: mha_reference(
            *args, causal=True, scale=scale, window=window))

        def by_step(a):                     # [B, L, H, D] -> [steps, .., n, D]
            return jnp.moveaxis(a.reshape(B, L, steps, n, D), 2, 0)
        out = jax.lax.map(some, (by_step(q), *(
            by_step(jnp.repeat(a, H // kv_heads, 2)) for a in (k, v))))
        return jnp.moveaxis(out, 0, 2).reshape(B, L, H, D)

    errs[f"{tag}_fwd"] = _close(
        jax.jit(flash)(q, k, v), jax.jit(plain)(q, k, v), BF16_TOL,
        f"{tag} fwd")
    got = jax.jit(jax.grad(_sq(flash), (0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(_sq(plain), (0, 1, 2)))(q, k, v)
    for n, a, b in zip("qkv", got, ref):
        assert a.shape == b.shape, (n, a.shape, b.shape)
        errs[f"{tag}_d{n}"] = _close(a, b, 4 * BF16_TOL, f"{tag} d{n}")


def check_ssd_scan(errs, shape=(2, 8192, 64, 64), groups=8, state=128,
                   chunk=128, tag="ssd"):
    """The state-space scan at the two state-space cells' shapes (the
    Nemotron cell's 8 groups of 8 heads, a group a kernel step; the
    Granite cell's ONE group of 64, walked in head blocks): bfloat16 x, B
    and C, float32 dt and A.  The two kernels (ops/pallas/ssd_scan.py)
    against the chunked form in XLA (ops/ssm.py), and that form's first
    row against the recurrence run a position at a time in float32;
    forward and every gradient each time."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ssd_scan as kernels
    from paddle_tpu.ops.ssm import ssd_scan
    B, T, H, P = shape
    x = _rnd(51, shape, jnp.bfloat16, 0.3)
    Bm, Cm = (_rnd(i, (B, T, groups, state), jnp.bfloat16, 0.3)
              for i in (52, 53))
    dt = jax.nn.softplus(_rnd(54, (B, T, H), jnp.float32) - 2.0)
    # a decay of about 0.99 a position: what a chunk's state starts from
    # is half of it a chunk later, so a scan that dropped it would show
    A = -jnp.exp(_rnd(55, (H,), jnp.float32, 0.02) - 3.0)
    D = 1.0 + _rnd(56, (H,), jnp.float32, 0.02)
    # the plain reference's recurrence (benchmark/reference/nemotron_h.py:
    # a two-level scan over positions, the inner one replayed), row by row
    bench_dir = os.path.join(REPO, "benchmark")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    import run as harness
    ref = harness.load_module("reference", "nemotron_h")
    f32 = jnp.float32

    def recurrence(x, dt, A, Bm, Cm, D):
        return jax.vmap(lambda x, dt, Bm, Cm: ref._scan(
            x.astype(f32), dt, A, Bm.astype(f32), Cm.astype(f32), D)[0])(
                x, dt, Bm, Cm)

    assert kernels.ssd_scan_supported(shape, Bm.shape, x.dtype, chunk)
    chunked = functools.partial(ssd_scan, chunk=chunk)
    args = (x, dt, A, Bm, Cm, D)
    row = (x[:1], dt[:1], A, Bm[:1], Cm[:1], D)
    for tag, got, want, operands in (
            (f"{tag}_kernels", kernels.ssd_scan, chunked, args),
            (f"{tag}_scan", chunked, recurrence, row)):
        errs[f"{tag}_fwd"] = _close(
            jax.jit(got)(*operands), jax.jit(want)(*operands), BF16_TOL,
            f"{tag} fwd")
        ours = jax.jit(jax.grad(_sq(got), range(6)))(*operands)
        theirs = jax.jit(jax.grad(_sq(want), range(6)))(*operands)
        for n, a, b in zip(("x", "dt", "A", "B", "C", "D"), ours, theirs):
            errs[f"{tag}_d{n}"] = _close(a, b, 4 * BF16_TOL, f"{tag} d{n}")


def _call_ms(fn, *args, calls=10):
    """Milliseconds a call of jitted ``fn``, on the host's clock over
    ``calls`` back to back after one that compiles: a smoke timing."""
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / calls * 1e3, 3)


def check_causal_conv(errs, shape=(2, 8192, 10304), first=4096,
                      parts=(4096, 1024, 1024), taps=4, tag="conv"):
    """A mixer's causal convolution at the two state-space cells' shapes
    (the in-projection's output whole in bfloat16, the channels from
    ``first`` on, x, B and C back apart): the two kernels
    (ops/pallas/causal_conv.py) and XLA's form over XLA's slices
    (ops/ssm.py) each against that form in float32, the parts and the
    gradients to the operand, the taps and the bias, worst element over
    the largest; and what a forward and a forward + backward of each take
    alone (PERF.md's table)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import causal_conv as kernels
    from paddle_tpu.ops.ssm import causal_conv1d
    C, f32 = sum(parts), jnp.float32
    x = _rnd(71, shape, jnp.bfloat16)
    w, b = _rnd(72, (taps, C), jnp.bfloat16, 0.5), _rnd(73, (C,),
                                                        jnp.bfloat16, 0.5)
    cts = [_rnd(74 + i, shape[:2] + (n,), f32) for i, n in enumerate(parts)]
    assert kernels.causal_conv1d_supported(shape, w.shape, x.dtype, first,
                                           parts, "silu")

    def ours(x, w, b):
        return kernels.causal_conv1d(x, w, b, "silu", first, parts)

    def xla(x, w, b):
        out = causal_conv1d(x[:, :, first:first + C], w, b, "silu")
        return tuple(jnp.split(out, np.cumsum(parts)[:-1], axis=2))

    def both(fn):
        def loss(x, w, b):
            outs = fn(x, w, b)
            return sum(jnp.sum(o.astype(f32) * c)
                       for o, c in zip(outs, cts)), outs
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    (_, want), want_g = both(xla)(x.astype(f32), w.astype(f32),
                                  b.astype(f32))
    names = [f"part{i}" for i in range(len(parts))] + ["dx", "dw", "db"]
    took = {}
    for side, fn in (("kernels", ours), ("xla", xla)):
        (_, got), got_g = both(fn)(x, w, b)
        for n, a, ref in zip(names, got + got_g, want + want_g):
            errs[f"{tag}_{side}_{n}"] = _close(
                a, ref, BF16_TOL if n.startswith("part") else 4 * BF16_TOL,
                f"{tag} {side} {n}")
        took[side] = (_call_ms(jax.jit(fn), x, w, b),
                      _call_ms(both(fn), x, w, b))
    log(f"[kernels] {tag} at {list(shape)} from {first}, parts "
        f"{list(parts)}: forward / forward + backward alone, ms a call: "
        f"the kernels {took['kernels'][0]} / {took['kernels'][1]}, XLA's "
        f"form {took['xla'][0]} / {took['xla'][1]}")


def check_gated_short_conv(errs, shape=(4, 8192, 6144), taps=3,
                           tag="short_conv"):
    """A convolution mixer's operator at the LFM2 cell's shape (the
    in-projection's output [B ; C ; z] whole in bfloat16, three taps): the
    two kernels (ops/pallas/causal_conv.py) and the plain form (ops/ssm.py:
    XLA's slices, products and shifted multiply-adds) each against that
    form in float32, y and the gradients dB, dC, dz and the taps', worst
    element over the largest; and what a forward and a forward + backward
    of each take alone (PERF.md's table)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import causal_conv as kernels
    from paddle_tpu.ops.ssm import gated_short_conv
    H, f32 = shape[2] // 3, jnp.float32
    x = _rnd(81, shape, jnp.bfloat16)
    w = (0.5 + _rnd(82, (taps, H), f32, 0.3)).astype(jnp.bfloat16)
    ct = _rnd(83, shape[:2] + (H,), f32)
    assert kernels.gated_short_conv_supported(shape, w.shape, x.dtype)

    def both(fn):
        def loss(x, w):
            y = fn(x, w)
            return jnp.sum(y.astype(f32) * ct), y
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))

    def parts(y, grads):
        dx, dw = grads
        return (y, dx[..., :H], dx[..., H:2 * H], dx[..., 2 * H:], dw)

    (_, want), want_g = both(gated_short_conv)(x.astype(f32), w.astype(f32))
    took = {}
    for side, fn in (("kernels", kernels.gated_short_conv),
                     ("xla", gated_short_conv)):
        (_, got), got_g = both(fn)(x, w)
        for n, a, ref in zip(("y", "dB", "dC", "dz", "dw"),
                             parts(got, got_g), parts(want, want_g)):
            errs[f"{tag}_{side}_{n}"] = _close(
                a, ref, BF16_TOL if n == "y" else 4 * BF16_TOL,
                f"{tag} {side} {n}")
        took[side] = (_call_ms(jax.jit(fn), x, w), _call_ms(both(fn), x, w))
    log(f"[kernels] {tag} at {list(shape)}, {taps} taps: forward / forward "
        f"+ backward alone, ms a call: the kernels {took['kernels'][0]} / "
        f"{took['kernels'][1]}, XLA's form {took['xla'][0]} / "
        f"{took['xla'][1]}")


def check_moe_combine(errs, n=8192, K=8, H=2048, F=768, held=16, total=128):
    """One chunk of the Keye cell's expert layers (8192 tokens, 8 of 128
    experts a token, 16 held, bfloat16 weights): with the small buffer the
    sums over a token's slots run through the ``moe_combine`` kernel over
    the rows in token order; with the full buffer they are XLA's gathers,
    a row a slot.  The result and the five gradients, one against the
    other."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe
    x32 = _rnd(61, (n, H), jnp.float32)
    gates, ids = jax.jit(lambda a, b: moe.moe_route(a, b, K))(
        x32, _rnd(62, (H, total), jnp.float32, H ** -0.5))
    local = jnp.where(ids < held, ids, held)
    small = moe._small_buffer(n, K, held, total)
    assert int(jnp.sum(local < held)) <= small < n * K
    args = (x32.astype(jnp.bfloat16), gates,
            *(_rnd(i, shape, jnp.bfloat16, shape[1] ** -0.5)
              for i, shape in ((63, (held, H, F)), (64, (held, H, F)),
                               (65, (held, F, H)))))
    sel0 = selections()
    got, want = (jax.jit(jax.value_and_grad(_sq(
        lambda x, g, *w, rows=rows: moe.moe_experts(x, g, local, *w, rows)),
        range(5)))(*args) for rows in (small, None))
    assert selected_since(sel0).get("moe_combine"), selected_since(sel0)
    errs["moe_combine_fwd"] = _close(got[0], want[0], BF16_TOL,
                                     "moe_combine fwd")
    for name, a, b in zip(("x", "gates", "w_gate", "w_up", "w_down"),
                          got[1], want[1]):
        errs[f"moe_combine_d{name}"] = _close(a, b, 4 * BF16_TOL,
                                              f"moe_combine d{name}")


def check_epilogue(errs, bert):
    """The recipe the Executor realises on BERT-base (proj + bias +
    residual + LayerNorm) at the two phases' rows: f32 as the static
    phase runs it, bf16 as the train phase would."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_epilogue import (
        fused_linear_epilogue, reference_epilogue)
    H, rows = bert["hidden"], bert["batch"] * bert["seq"]
    stages = (("add",), ("layer_norm", 1e-5, True, True))
    for dtype, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
        tag = jnp.dtype(dtype).name
        args = (_rnd(10, (rows, H), dtype),
                _rnd(11, (H, H), dtype, H ** -0.5),
                _rnd(12, (H,), dtype, 0.1),
                _rnd(13, (rows, H), dtype),
                (1 + _rnd(14, (H,), jnp.float32, 0.1)).astype(dtype),
                _rnd(15, (H,), dtype, 0.1))

        def fused(x, w, b, *ops):
            return fused_linear_epilogue(x, w, b, stages, ops)

        def plain(x, w, b, *ops):
            return reference_epilogue(x, w, b, stages, ops)

        errs[f"epilogue_{tag}"] = _close(
            jax.jit(fused)(*args), jax.jit(plain)(*args), tol,
            f"epilogue fwd {tag}")
        got = jax.jit(jax.grad(_sq(fused), tuple(range(6))))(*args)
        ref = jax.jit(jax.grad(_sq(plain), tuple(range(6))))(*args)
        for n, a, r in zip(("dx", "dw", "db", "dres", "dgamma", "dbeta"),
                           got, ref):
            errs[f"epilogue_{tag}_{n}"] = _close(
                a, r, 4 * tol, f"epilogue {n} {tag}")


def check_adam(errs, bert):
    """Fused Adam on the embedding-sized parameter vs optimizer.Adam."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_adam import fused_adam_update
    from paddle_tpu.optimizer.optimizer import Adam
    shape = (bert["vocab"], bert["hidden"])
    p, g = _rnd(20, shape, jnp.float32), _rnd(21, shape, jnp.float32, 0.01)
    opt = Adam(learning_rate=1e-3)
    slots = opt.init_slots(p)
    lr, step = jnp.float32(1e-3), jnp.float32(3.0)
    (ref_p,), (ref_s,) = jax.jit(
        lambda p, g, s, lr, step: opt.functional_update(
            [p], [g], [s], lr, step))(p, g, slots, lr, step)
    got = jax.jit(fused_adam_update)(p, g, slots["m"], slots["v"], lr, step)
    for n, a, r in zip("pmv", got, (ref_p, ref_s["m"], ref_s["v"])):
        errs[f"adam_{n}"] = _close(a, r, 1e-6, f"fused adam {n}")


def check_paged(errs, serve, engine):
    """Paged decode attention at the serve phase's geometry."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import paged_attention_reference
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_decode
    S, page = engine["num_slots"], engine["page_size"]
    P = engine["max_context"] // page
    hd = serve["hidden"] // serve["num_heads"]
    pool = (serve["num_layers"], S * P, page, serve["num_kv_heads"], hd)
    table = jnp.asarray(np.random.RandomState(3).permutation(
        S * P).reshape(S, P), jnp.int32)
    # lengths from one token to the full context, page edges included
    lens = jnp.asarray(np.linspace(1, P * page, S).astype(np.int32))
    lens = lens.at[1].set(page).at[2].set(page + 1)
    for dtype, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
        tag = jnp.dtype(dtype).name
        q = _rnd(30, (S, serve["num_heads"], hd), dtype)
        kp, vp = _rnd(31, pool, dtype), _rnd(32, pool, dtype)
        got = jax.jit(lambda *a: paged_attention_decode(*a, layer=1))(
            q, kp, vp, table, lens)
        ref = jax.jit(lambda *a: paged_attention_reference(*a, layer=1))(
            q, kp, vp, table, lens)
        errs[f"paged_{tag}"] = _close(got, ref, tol,
                                      f"paged attention {tag}")


def check_chunk_matmul(errs, bert):
    """The collective-matmul chunk kernel at a BERT FFN chunk."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.collective_matmul import chunk_matmul
    H = bert["hidden"]
    for dtype, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
        tag = jnp.dtype(dtype).name
        x = _rnd(40, (4096, H), dtype)
        w = _rnd(41, (H, bert["ffn"]), dtype, H ** -0.5)
        errs[f"chunk_mm_{tag}"] = _close(
            jax.jit(chunk_matmul)(x, w), jax.jit(jnp.matmul)(x, w), tol,
            f"chunk matmul {tag}")


def phase_kernels(bert=BERT_BASE, serve=SERVE, engine=SERVE_ENGINE):
    import jax

    from paddle_tpu.ops.pallas.support import interpret_mode
    if interpret_mode():
        raise AssertionError("Pallas interpret mode is on: not a TPU run")
    mark, sel0, errs = compiled_so_far(), selections(), {}
    with jax.default_matmul_precision("highest"):
        check_flash(errs, bert)
        check_flash_dropout(errs, bert)
        check_flash_grouped(errs)
        check_flash_grouped(errs, (1, 8192, 32, 64), kv_heads=8,
                            scale=1 / 64, tag="flash_grouped_d64")
        for window, tag in ((2048, "flash_window_16k"),
                            (None, "flash_full_16k")):
            check_flash_grouped(errs, (1, 16384, 32, 128), kv_heads=4,
                                tag=tag, window=window, heads_a_step=1)
        check_ssd_scan(errs)
        check_ssd_scan(errs, (1, 8192, 64, 64), groups=1, tag="ssd_one_group")
        check_causal_conv(errs)
        check_causal_conv(errs, (1, 8192, 8512), parts=(4096, 128, 128),
                          tag="conv_one_group")
        check_gated_short_conv(errs)
        check_moe_combine(errs)
        check_epilogue(errs, bert)
        check_adam(errs, bert)
        check_paged(errs, serve, engine)
        check_chunk_matmul(errs, bert)
    gc.collect()
    report("kernels", mark, {
        "tolerance": f"f32 {F32_TOL:g} / bf16 {BF16_TOL:g} (x max|ref|)",
        "rel_err": errs, "selected": selected_since(sel0)})


# ------------------------------------------------------------- callbacks --
def phase_callbacks():
    """Host callbacks on this backend: a traced print shows the runtime
    value, a traced assert checks it."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import jit

    mark = compiled_so_far()

    @jit.to_static
    def f(x):
        print("chip_smoke traced print:", x.sum())
        assert x.sum() > 0, "chip_smoke traced assert"
        return x * 2

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        out = f(paddle.to_tensor(np.array([1.5, 2.0], np.float32)))
        jax.effects_barrier()
    if "chip_smoke traced print: 3.5" not in buf.getvalue():
        raise AssertionError(f"traced print did not reach the host: "
                             f"{buf.getvalue()!r}")
    if not np.allclose(np.asarray(out.data), [3.0, 4.0]):
        raise AssertionError("to_static result wrong")
    try:
        f(paddle.to_tensor(np.array([-1.0, 0.5], np.float32)))
        jax.effects_barrier()
    except Exception as e:  # noqa: BLE001 - the runtime wraps the AssertionError
        if "chip_smoke traced assert" not in str(e):
            raise
    else:
        raise AssertionError("a failing traced assert did not raise")
    # the failed callback's token is still queued; left there, jax waits
    # on it again at exit and prints the same traceback under a passing
    # run (private name: there is no public way to drop a failed token)
    from jax._src import dispatch
    dispatch.runtime_tokens.clear()
    report("callbacks", mark, {"print": "ok", "assert": "ok"})


# ------------------------------------------------------------- multichip --
def phase_multichip(steps=3, rehearse=False):
    """README "Sharded training": fleet.init + sharding_rules on a
    {dp: 2, mp: 2} mesh through the Executor, against the same seeded
    program on one device of this host."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import get_mesh

    mark = compiled_so_far()

    def build(sharded):
        """The static cell's program; when sharded, its optimizer goes
        through fleet (the README recipe).  The builder has minimised
        already, and the Executor reads the strategy off the program's
        optimizer at its first run, so fleet takes that optimizer."""
        if sharded:
            strategy = dist.DistributedStrategy()
            strategy.tensor_parallel = True
            strategy.tensor_parallel_configs = {"tensor_parallel_degree": 2}
            strategy.sharding_rules = MP_RULES
            dist.fleet.init(is_collective=True, strategy=strategy)
        prog, loss, feed = build_static(*load_parts(STATIC_CELL, rehearse))
        if sharded:
            dist.fleet.distributed_optimizer(prog._optimizer[0])
        return prog, loss, feed

    def run(sharded):
        paddle.enable_static()
        try:
            prog, loss, feed = build(sharded)
            exe = paddle.static.Executor()
            losses, ms = [], []
            for i in range(steps):
                if i == 1:
                    warm = compiled_so_far()
                t0 = time.perf_counter()
                out = exe.run(prog, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(out[0])))
                ms.append((time.perf_counter() - t0) * 1000)
            placement = _placement(exe, prog) if sharded else None
            late = [c for c in compiled_since(warm)[0] if "train_fn" in c[0]]
            if exe.compile_count != 1 or late:
                raise AssertionError(
                    f"Executor compiled {exe.compile_count} times; jax "
                    f"compiled after the first step: {late}")
            exe.close()
            return losses, ms, placement
        finally:
            paddle.disable_static()
            paddle.static.reset_default_programs()

    one, one_ms, _ = run(False)
    gc.collect()
    four, four_ms, placement = run(True)
    finite(one + four, "multichip loss")
    tol = 2e-2      # bf16-pass matmuls, and the one-device run takes the
    #                 f32-exact fused epilogue the sharded run does not
    if abs(four[0] - one[0]) > tol * abs(one[0]):
        raise AssertionError(f"step-0 loss: 4 chips {four[0]} vs one "
                             f"device {one[0]} (tolerance {tol:g} rel)")
    if not (four[-1] < four[0] and one[-1] < one[0]):
        raise AssertionError(f"losses not falling: {four} / {one}")
    if dict(get_mesh().shape) != {"dp": 2, "mp": 2}:
        raise AssertionError("mesh is not {dp: 2, mp: 2}")
    report("multichip", mark, {
        "mesh": "{dp: 2, mp: 2}", "cell": STATIC_CELL,
        "one_device_losses": [round(v, 4) for v in one],
        "four_chip_losses": [round(v, 4) for v in four],
        "smoke_step_ms_one_device": [round(v, 1) for v in one_ms[1:]],
        "smoke_step_ms_four_chips": [round(v, 1) for v in four_ms[1:]],
        **placement})


# Ordered (regex, spec) rules over the static BERT's parameter names:
# both embeddings split their rows over 'mp', every Linear weight its
# columns, biases and LayerNorms replicate.  Every split halves an array
# over mp=2 — which is what `_placement` checks shard by shard.  (A
# valid layout, not the Megatron-minimal one: the recorded names do not
# tell q/k/v from proj.)  Specs as tuples, the form the rule engine
# takes besides PartitionSpec.
MP_RULES = [
    (r"embedding.*", ("mp", None)),
    (r"linear.*\.w_0$", (None, "mp")),
    (r".*", ()),
]


def _placement(exe, prog):
    """Where parameters and optimizer slots really live, read from
    ``addressable_shards`` — code that has only seen virtual CPU devices
    may put everything on device 0."""
    import jax
    state = exe._states[prog._serial]
    devices = {d.id for d in jax.devices()}
    per_dev = {d: 0 for d in devices}
    total = split = 0
    arrays = list(state.p_arrays)
    for slot in state.opt_state:
        arrays.extend(slot.values())
    for a in arrays:
        if a.ndim == 0:
            continue
        ids = set()
        for sh in a.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
            ids.add(sh.device.id)
        if ids != devices:
            raise AssertionError(
                f"an array of shape {a.shape} lives on devices {ids}, "
                f"not on all of {devices}")
        total += a.nbytes
        spec = getattr(a.sharding, "spec", ())
        if any(s is not None for s in spec):
            split += a.nbytes
            want = a.nbytes // 2           # every rule splits over mp=2
            got = a.addressable_shards[0].data.nbytes
            if got != want:
                raise AssertionError(
                    f"{a.shape} {spec}: shard holds {got} bytes, the "
                    f"rule implies {want}")
    # replicated arrays cost their full size on each device, mp-split
    # ones half: the per-device bytes must be exactly that
    want_dev = (total - split) + split // 2
    if len(set(per_dev.values())) != 1 or per_dev[min(devices)] != want_dev:
        raise AssertionError(f"per-device bytes {per_dev}, want {want_dev}")
    if split == 0:
        raise AssertionError("no parameter was split by the rules")
    return {"state_bytes_total": total, "state_bytes_mp_split": split,
            "state_bytes_per_device": want_dev, "devices": sorted(devices)}


# ------------------------------------------------------------------ main --
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip sharded-Executor phase "
                         "and the one-device run it is compared with")
    args = ap.parse_args(argv)

    from paddle_tpu.core.xla_env import place_compile_cache
    cache_dir = place_compile_cache()

    import jax
    import jaxlib
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.stderr.write(
            f"chip_smoke needs a TPU; jax found {devs[0].platform!r} "
            f"({devs[0].device_kind}). Nothing ran.\n")
        return 1
    want = 4 if args.multichip else 1
    if len(devs) != want:
        sys.stderr.write(f"chip_smoke{' --multichip' if args.multichip else ''}"
                         f" needs {want} chip(s), jax found {len(devs)}.\n")
        return 1
    from importlib.metadata import version
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {version('libtpu')}; {len(devs)} x {devs[0].device_kind}")
    log(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'set by core.xla_env.place_compile_cache'})")
    log("timings below are smoke timings, not benchmark numbers")

    begin, t0 = compiled_so_far(), time.perf_counter()
    phases = ([phase_multichip] if args.multichip else
              [phase_kernels, phase_callbacks, phase_train, phase_static,
               phase_serve])
    for phase in phases:
        t = time.perf_counter()
        phase()
        # the two BERT phases each need nearly the whole chip: what a
        # phase leaves behind is the next one's out-of-memory
        gc.collect()
        log(f"[{phase.__name__[6:]}] passed in "
            f"{time.perf_counter() - t:.1f}s; live device arrays after "
            f"it: {sum(a.nbytes for a in jax.live_arrays())} bytes")
    compiles, secs, hits = compiled_since(begin)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; "
        f"{sum(c[1] for c in compiles)} compiles ({secs:.1f}s), {hits} "
        f"answered by the persistent cache")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
