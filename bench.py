"""Benchmark harness for the BASELINE.json graded configs.

Run:  python bench.py [--steps N] [--profile DIR] [--small] [--suite S]

Prints ONE JSON line on stdout.  The primary metric is the flagship
BERT-base masked-LM pretraining step (BASELINE.json configs[2-3]: L=12,
H=768, A=12, FF=3072, seq=512); secondary suite results (ResNet-50 conv
path, configs[1]; LeNet dygraph smoke, configs[0]) are embedded under
``"extra"`` in the same line.

Every compiled benchmark runs the whole train step — forward, backward,
optimizer update, clip — as ONE donated-buffer XLA program
(paddle_tpu.jit.TrainStep), bf16 compute with fp32 master weights.
vs_baseline is measured MFU / 0.35 (the BASELINE.json north-star floor).
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


# bf16 peak FLOPs/s per chip by device kind (public specs)
_PEAK = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}

# ResNet-50 v1.5 @224x224: ~4.09 GFLOP/image forward (standard accounting);
# training step counted as 3x forward (fwd + 2x bwd)
_RESNET50_FWD_FLOPS = 4.089e9


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for k, v in sorted(_PEAK.items(), key=lambda kv: -len(kv[0])):
        if k.lower() in kind.lower():
            return v
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(f"no peak FLOP/s known for TPU device kind "
                         f"{kind!r}; add it to bench._PEAK")
    return 0.0  # CPU smoke path: MFU not defined


def build_model(vocab, hidden, layers, heads, ffn, seq, dropout):
    import paddle_tpu as paddle
    from paddle_tpu import nn

    class BertMLM(nn.Layer):
        """BERT-base-shaped encoder LM (reference shapes:
        nn/layer/transformer.py TransformerEncoder; PaddleNLP bert-base).
        Forward returns the normalized hidden states; the vocab
        projection fuses into the loss (F.linear_cross_entropy) so the
        [tokens, vocab] logits never materialize."""

        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(vocab, hidden)
            self.pos = nn.Embedding(seq, hidden)
            enc = nn.TransformerEncoderLayer(
                hidden, heads, ffn, dropout=dropout, activation="gelu",
                attn_dropout=dropout, act_dropout=dropout)
            self.encoder = nn.TransformerEncoder(enc, layers)
            self.norm = nn.LayerNorm(hidden)
            self.head = nn.Linear(hidden, vocab)

        def forward(self, ids):
            pos_ids = paddle.arange(ids.shape[1]).unsqueeze(0)
            x = self.tok(ids) + self.pos(pos_ids)
            x = self.encoder(x)
            return self.norm(x)

    return BertMLM()


def _with_counters(fn, *args):
    """Run a whole bench function and embed the monitor-counter DELTA
    its run produced (``monitor_counters``: compile counts, pad hits,
    fs/batch retries, ...) so a recorded trajectory explains a perf
    delta — "0.8x because 40 recompiles" — instead of just reporting
    it.  A failure is a failure: nothing is retried."""
    from paddle_tpu.utils import monitor
    before = monitor.all_stats()
    res = fn(*args)
    if isinstance(res, dict):
        after = monitor.all_stats()
        res["monitor_counters"] = {
            k: after[k] - before.get(k, 0) for k in sorted(after)
            if after[k] != before.get(k, 0)}
    return res


def _timed_steps(step, feeds, warmup, steps, profile_dir=None):
    for _ in range(max(warmup, 1)):  # >=1: compile outside timed region
        loss = step(*feeds)
    float(loss)  # sync
    if profile_dir:
        import jax
        jax.profiler.start_trace(profile_dir)
    t0 = time.perf_counter()
    if profile_dir:
        from paddle_tpu.profiler import RecordEvent
        for i in range(steps):
            with RecordEvent(f"train_step#{i}"):  # named host-track span
                loss = step(*feeds)
    else:  # unprofiled timing: no annotation overhead in the numbers
        for _ in range(steps):
            loss = step(*feeds)
    last = float(loss)  # device sync
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()
    return dt, last


def bench_bert(args, dev, on_tpu):
    import jax
    import jax.numpy as jnp

    if on_tpu:
        cfg = dict(vocab=30522, hidden=768, layers=12, heads=12, ffn=3072,
                   seq=512,
                   batch=int(os.environ.get("BENCH_BERT_BATCH", "64")),
                   dropout=0.1, attn_dropout=0.1)
        steps = args.steps or 20
        dtype = "bfloat16"
    else:
        cfg = dict(vocab=1000, hidden=128, layers=2, heads=4, ffn=512,
                   seq=128, batch=8, dropout=0.1, attn_dropout=0.1)
        steps = args.steps or 5
        dtype = "float32"

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm

    paddle.seed(2024)
    model = build_model(cfg["vocab"], cfg["hidden"], cfg["layers"],
                        cfg["heads"], cfg["ffn"], cfg["seq"], cfg["dropout"])
    opt = optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=ClipGradByGlobalNorm(1.0),
        multi_precision=(dtype != "float32"))
    if dtype != "float32":
        model, opt = amp.decorate(model, opt, level="O2", dtype=dtype)

    def loss_fn(out, labels):
        # fused chunked head+CE: same math as
        # cross_entropy(head(out), labels), logits stay chunk-local
        return F.linear_cross_entropy(
            out.reshape([-1, cfg["hidden"]]), model.head.weight,
            model.head.bias, labels.reshape([-1]))

    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, cfg["vocab"],
                                (cfg["batch"], cfg["seq"]), dtype=np.int32))
    y = jnp.asarray(rng.randint(0, cfg["vocab"],
                                (cfg["batch"], cfg["seq"]), dtype=np.int32))

    prof = args.profile or None
    dt, last = _timed_steps(step, (x, y), args.warmup, steps,
                            profile_dir=prof)

    steps_per_sec = steps / dt
    tokens = cfg["batch"] * cfg["seq"]

    # model FLOPs: 6*N*T for matmuls (fwd+bwd) + 12*L*B*S^2*H attention
    # scores/values (PaLM appendix-B accounting)
    n_params = sum(int(np.prod(p.shape_tuple)) for p in model.parameters())
    n_embed = cfg["vocab"] * cfg["hidden"] + cfg["seq"] * cfg["hidden"]
    n_dense = n_params - n_embed
    flops_per_step = (6 * n_dense * tokens
                      + 12 * cfg["layers"] * cfg["batch"]
                      * cfg["seq"] ** 2 * cfg["hidden"])
    peak = _peak_flops(dev)
    mfu = flops_per_step * steps_per_sec / peak if peak else 0.0

    return {
        "metric": ("bert_base_pretrain_tokens_per_sec_per_chip" if on_tpu
                   else "bert_tiny_cpu_smoke_tokens_per_sec"),
        "value": round(tokens * steps_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4) if peak else 0.0,
        "mfu": round(mfu, 4),
        "steps_per_sec": round(steps_per_sec, 4),
        "step_time_ms": round(1000 * dt / steps, 2),
        "model_flops_per_step": flops_per_step,
        "final_loss": round(last, 4),
        "config": cfg,
        "dtype": dtype,
        "donated": True,
        "profile_dir": prof,
    }


def build_gpt(vocab, hidden, layers, heads, ffn, seq, dropout):
    """GPT-shaped causal decoder LM (BASELINE.json configs[4] single-chip
    proxy; reference shapes: PaddleNLP gpt/modeling.py, fed by the fleet
    hybrid runtime section_worker.cc:128-165).  Pre-norm blocks, tied
    input/output embedding (the vocab projection reuses ``tok.weight`` via
    the fused chunked linear_cross_entropy loss), causal Pallas flash
    attention."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    import paddle_tpu.nn.functional as F

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = nn.LayerNorm(hidden)
            self.q = nn.Linear(hidden, hidden)
            self.k = nn.Linear(hidden, hidden)
            self.v = nn.Linear(hidden, hidden)
            self.proj = nn.Linear(hidden, hidden)
            self.ln2 = nn.LayerNorm(hidden)
            self.fc1 = nn.Linear(hidden, ffn)
            self.fc2 = nn.Linear(ffn, hidden)
            self.drop = nn.Dropout(dropout)

        def forward(self, x):
            B, S = x.shape[0], x.shape[1]
            h = self.ln1(x)
            hd = hidden // heads
            q = self.q(h).reshape([B, S, heads, hd])
            k = self.k(h).reshape([B, S, heads, hd])
            v = self.v(h).reshape([B, S, heads, hd])
            a = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=dropout,
                training=self.training)
            x = x + self.drop(self.proj(a.reshape([B, S, hidden])))
            h = self.ln2(x)
            x = x + self.drop(self.fc2(F.gelu(self.fc1(h),
                                              approximate=True)))
            return x

    # GPT-2 init: N(0, 0.02) embeddings — with the tied head this keeps
    # initial logits O(1) (paddle default N(0,1) embeddings would give
    # CE ~ 10x ln(V) at step 0 through the tied projection)
    emb_attr = paddle.ParamAttr(
        initializer=nn.initializer.Normal(0.0, 0.02))

    class GPT(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(vocab, hidden, weight_attr=emb_attr)
            self.pos = nn.Embedding(seq, hidden, weight_attr=emb_attr)
            self.drop = nn.Dropout(dropout)
            self.blocks = nn.LayerList([Block() for _ in range(layers)])
            self.ln_f = nn.LayerNorm(hidden)

        def forward(self, ids):
            from paddle_tpu.parallel import recompute
            pos_ids = paddle.arange(ids.shape[1]).unsqueeze(0)
            x = self.drop(self.tok(ids) + self.pos(pos_ids))
            for blk in self.blocks:
                # per-block remat: peak bwd memory = one block's
                # internals + per-block boundary activations (whole-model
                # jax.checkpoint would keep every layer's temps live in
                # one rematted backward — measured 21.8 GB at 760M)
                x = recompute(blk, x)
            return self.ln_f(x)

    return GPT()


# single-chip GPT presets: "largest that fits" on a 16 GB v5e with fp32
# AdamW state (param bf16 2B + master 4B + m 4B + v 4B = 14 B/param).
# 1.3B proper (H=2048 L=24) needs 18.4 GB of state alone — does not fit
# one chip; 760M-class is the largest standard GPT size that leaves
# activation/workspace headroom.  BASELINE configs[4] runs 1.3B across a
# pod; the multi-chip sharding for that is exercised in
# __graft_entry__.dryrun_multichip.
_GPT_PRESETS = {
    "760m": dict(vocab=50257, hidden=1536, layers=24, heads=16, ffn=6144,
                 seq=1024, dropout=0.1),
    "1b": dict(vocab=50257, hidden=1792, layers=24, heads=14, ffn=7168,
               seq=1024, dropout=0.1),
}


def bench_gpt(args, dev, on_tpu):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm

    if on_tpu:
        preset = os.environ.get("BENCH_GPT_PRESET", "760m")
        cfg = dict(_GPT_PRESETS[preset],
                   batch=int(os.environ.get("BENCH_GPT_BATCH", "16")))
        steps = args.steps or 10
        dtype = "bfloat16"
    else:
        preset = "cpu_smoke"
        cfg = dict(vocab=1000, hidden=128, layers=2, heads=4, ffn=512,
                   seq=128, dropout=0.1, batch=4)
        steps = args.steps or 3
        dtype = "float32"

    paddle.seed(2024)
    model = build_gpt(cfg["vocab"], cfg["hidden"], cfg["layers"],
                      cfg["heads"], cfg["ffn"], cfg["seq"], cfg["dropout"])
    opt = optimizer.AdamW(
        learning_rate=2e-4, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=ClipGradByGlobalNorm(1.0),
        multi_precision=(dtype != "float32"))
    if dtype != "float32":
        model, opt = amp.decorate(model, opt, level="O2", dtype=dtype)

    def loss_fn(out, labels):
        # tied head: logits = out @ tok.weight^T, fused+chunked so the
        # [tokens, 50257] logits never materialize
        w = paddle.transpose(model.tok.weight, [1, 0])
        bias = paddle.zeros([cfg["vocab"]], dtype=w.dtype)
        return F.linear_cross_entropy(
            out.reshape([-1, cfg["hidden"]]), w, bias, labels.reshape([-1]))

    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, cfg["vocab"],
                                (cfg["batch"], cfg["seq"]), dtype=np.int32))
    y = jnp.asarray(rng.randint(0, cfg["vocab"],
                                (cfg["batch"], cfg["seq"]), dtype=np.int32))

    # profile only when gpt is the selected suite (under --suite all the
    # trace dir belongs to the flagship bert run)
    prof = args.profile if args.suite == "gpt" else None
    dt, last = _timed_steps(step, (x, y), args.warmup, steps,
                            profile_dir=prof)
    steps_per_sec = steps / dt
    tokens = cfg["batch"] * cfg["seq"]

    n_params = sum(int(np.prod(p.shape_tuple)) for p in model.parameters())
    n_embed = (cfg["vocab"] + cfg["seq"]) * cfg["hidden"]
    # dense matmul FLOPs: the tied vocab projection does a real
    # [T,H]x[H,V] matmul in the loss, so add it back to the dense count;
    # causal attention does half the S^2 work (flash skips masked blocks)
    n_matmul = (n_params - n_embed) + cfg["vocab"] * cfg["hidden"]
    flops_per_step = (6 * n_matmul * tokens
                      + 6 * cfg["layers"] * cfg["batch"]
                      * cfg["seq"] ** 2 * cfg["hidden"])
    peak = _peak_flops(dev)
    mfu = flops_per_step * steps_per_sec / peak if peak else 0.0

    return {
        "metric": (f"gpt_{preset}_pretrain_tokens_per_sec_per_chip"
                   if on_tpu else "gpt_tiny_cpu_smoke_tokens_per_sec"),
        "value": round(tokens * steps_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4) if peak else 0.0,
        "mfu": round(mfu, 4),
        "steps_per_sec": round(steps_per_sec, 4),
        "step_time_ms": round(1000 * dt / steps, 2),
        "model_flops_per_step": flops_per_step,
        "n_params": n_params,
        "final_loss": round(last, 4),
        "config": cfg,
        "dtype": dtype,
        "recompute": "per_block",
        "tied_embedding": True,
        "flops_accounting": "6*N*T dense (+tied head) + causal attn S^2/2",
        "note": ("single-chip proxy of BASELINE configs[4]; 1.3B optimizer "
                 "state (18.4 GB fp32 AdamW) exceeds one 16 GB chip — "
                 "largest-that-fits preset; pod-scale hybrid sharding "
                 "exercised in dryrun_multichip"),
    }


def build_bert_static(vocab, hidden, layers, heads, ffn, seq, batch,
                      seed=2024, wrap_optimizer=None):
    """Record a BERT-shaped encoder masked-LM *static* training program
    (post-norm blocks, no dropout): the op chains the cost model ranks
    as fusion candidates — linear+gelu in the FFN, linear+add+layer_norm
    around each residual — exactly what the executor's Pallas
    epilogue-fusion pass realizes.  Static batch dim: the Executor
    compiles per feed signature anyway, and concrete avals let
    Program.analyze gate the kernels without a batch_size hint.
    ``wrap_optimizer`` (e.g. ``fleet.distributed_optimizer``) is applied
    to the Adam before ``minimize``.
    Returns (program, loss_var, feeds_builder)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn, optimizer

    paddle.seed(seed)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        ids = paddle.static.data("ids", [batch, seq], "int64")
        labels = paddle.static.data("labels", [batch, seq], "int64")
        tok = nn.Embedding(vocab, hidden)
        pos = nn.Embedding(seq, hidden)
        x = tok(ids) + pos(paddle.arange(seq).unsqueeze(0))
        hd = hidden // heads
        for _ in range(layers):
            wq = nn.Linear(hidden, hidden)
            wk = nn.Linear(hidden, hidden)
            wv = nn.Linear(hidden, hidden)
            proj = nn.Linear(hidden, hidden)
            ln1 = nn.LayerNorm(hidden)
            fc1 = nn.Linear(hidden, ffn)
            fc2 = nn.Linear(ffn, hidden)
            ln2 = nn.LayerNorm(hidden)
            q = wq(x).reshape([batch, seq, heads, hd])
            k = wk(x).reshape([batch, seq, heads, hd])
            v = wv(x).reshape([batch, seq, heads, hd])
            a = F.scaled_dot_product_attention(q, k, v)
            # linear+add+layer_norm chain (residual epilogue)
            x = ln1(proj(a.reshape([batch, seq, hidden])) + x)
            # linear+gelu chain (FFN epilogue)
            h = F.gelu(fc1(x), approximate=True)
            x = ln2(fc2(h) + x)
        head = nn.Linear(hidden, vocab)
        logits = head(x)
        loss = F.cross_entropy(logits.reshape([-1, vocab]),
                               labels.reshape([-1]))
        opt = optimizer.Adam(learning_rate=1e-4)
        if wrap_optimizer is not None:
            opt = wrap_optimizer(opt)
        opt.minimize(loss)

    def feeds(rng):
        return {
            "ids": jnp.asarray(rng.randint(
                0, vocab, (batch, seq), dtype=np.int64)),
            "labels": jnp.asarray(rng.randint(
                0, vocab, (batch, seq), dtype=np.int64)),
        }

    return main, loss, feeds


def build_gpt_static(vocab, hidden, layers, heads, ffn, seq, batch,
                     seed=2024):
    """GPT-shaped causal decoder LM as a static training program
    (pre-norm blocks, no dropout, untied head): the residual adds after
    ``proj``/``fc2`` and the ``fc1``+gelu FFN are the realized chains.
    Returns (program, loss_var, feeds_builder)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn, optimizer

    paddle.seed(seed)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        ids = paddle.static.data("ids", [batch, seq], "int64")
        labels = paddle.static.data("labels", [batch, seq], "int64")
        tok = nn.Embedding(vocab, hidden)
        pos = nn.Embedding(seq, hidden)
        x = tok(ids) + pos(paddle.arange(seq).unsqueeze(0))
        hd = hidden // heads
        for _ in range(layers):
            ln1 = nn.LayerNorm(hidden)
            wq = nn.Linear(hidden, hidden)
            wk = nn.Linear(hidden, hidden)
            wv = nn.Linear(hidden, hidden)
            proj = nn.Linear(hidden, hidden)
            ln2 = nn.LayerNorm(hidden)
            fc1 = nn.Linear(hidden, ffn)
            fc2 = nn.Linear(ffn, hidden)
            h = ln1(x)
            q = wq(h).reshape([batch, seq, heads, hd])
            k = wk(h).reshape([batch, seq, heads, hd])
            v = wv(h).reshape([batch, seq, heads, hd])
            a = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            # linear+add chain (residual epilogue on the projection)
            x = proj(a.reshape([batch, seq, hidden])) + x
            h = ln2(x)
            x = fc2(F.gelu(fc1(h), approximate=True)) + x
        lnf = nn.LayerNorm(hidden)
        head = nn.Linear(hidden, vocab)
        logits = head(lnf(x))
        loss = F.cross_entropy(logits.reshape([-1, vocab]),
                               labels.reshape([-1]))
        optimizer.Adam(learning_rate=1e-4).minimize(loss)

    def feeds(rng):
        return {
            "ids": jnp.asarray(rng.randint(
                0, vocab, (batch, seq), dtype=np.int64)),
            "labels": jnp.asarray(rng.randint(
                0, vocab, (batch, seq), dtype=np.int64)),
        }

    return main, loss, feeds


def bench_resnet50(args, dev, on_tpu):
    """Conv-path benchmark (BASELINE.json configs[1]): ResNet-50, synthetic
    ImageNet shapes, SGD+momentum, bf16 with fp32 master weights."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch, hw, steps, dtype = 128, 224, (args.steps or 20), "bfloat16"
    else:
        batch, hw, steps, dtype = 4, 64, (args.steps or 3), "float32"
    # NCHW vs NHWC measure identically on v5e (XLA's layout assignment
    # normalizes conv layouts); keep the paddle-default NCHW
    data_format = os.environ.get("BENCH_RESNET_FORMAT", "NCHW").upper()
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"BENCH_RESNET_FORMAT must be NCHW or NHWC, "
                         f"got {data_format!r}")

    paddle.seed(2024)
    model = resnet50(data_format=data_format)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters(),
                             multi_precision=(dtype != "float32"))
    if dtype != "float32":
        model, opt = amp.decorate(model, opt, level="O2", dtype=dtype)

    def loss_fn(out, labels):
        return F.cross_entropy(out, labels)

    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)
    rng = np.random.RandomState(0)
    shape = ((batch, hw, hw, 3) if data_format == "NHWC"
             else (batch, 3, hw, hw))
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    if dtype != "float32":
        x = x.astype(jnp.bfloat16)  # bf16 input pipeline, standard on TPU
    y = jnp.asarray(rng.randint(0, 1000, (batch,), dtype=np.int64))

    dt, last = _timed_steps(step, (x, y), args.warmup, steps)
    steps_per_sec = steps / dt
    imgs_per_sec = batch * steps_per_sec
    flops_per_step = 3 * _RESNET50_FWD_FLOPS * batch if hw == 224 else 0
    peak = _peak_flops(dev)
    mfu = flops_per_step * steps_per_sec / peak if peak else 0.0
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/s/chip",
        "mfu": round(mfu, 4),
        "step_time_ms": round(1000 * dt / steps, 2),
        "batch": batch,
        "image_size": hw,
        "data_format": data_format,
        "dtype": dtype,
        "flops_accounting": "3 x 4.089 GF/img (fwd x3 train)",
        "final_loss": round(last, 4),
    }


def _timed_static_loop(exe, main, loss, feed, steps, warmup=3):
    """Warmup (compile) + timed async loop (return_numpy=False, one sync
    at the end); returns (dt, last_loss)."""
    def step():
        return exe.run(main, feed=feed, fetch_list=[loss],
                       return_numpy=False)[0]
    for _ in range(max(warmup, 1)):
        last = step()
    float(np.asarray(last.data))
    t0 = time.perf_counter()
    for _ in range(steps):
        last = step()
    lv = float(np.asarray(last.data))
    return time.perf_counter() - t0, lv


def bench_static(args, dev, on_tpu):
    """Static-graph Executor hot path (ISSUE 2 tentpole): donated
    device-resident async dispatch, measured against the preserved
    pre-change host-loop path (Executor._run_legacy) on the SAME config.

    Two entries: ``static_mlp`` — the hot-path micro where per-step host
    work (feed NumPy round-trip, per-param write-back, scalar uploads,
    fetch sync) is comparable to device compute, so the speedup of the
    redesign is directly visible; ``static_lenet`` — the conv net from
    the tier-1 suite, tracking absolute static-path steps/sec and the
    compile count (must be 1 per feed signature)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.vision.models import LeNet

    if on_tpu:
        hidden, depth, batch, steps = 1024, 8, 256, (args.steps or 100)
        lenet_batch, lenet_steps = 256, (args.steps or 50)
    else:
        # deep-and-narrow: per-step host work (feeds, write-back, scalar
        # uploads, sync) is comparable to device compute, so the hot-path
        # redesign is visible above CPU timer noise
        hidden, depth, batch, steps = 128, 8, 32, (args.steps or 150)
        lenet_batch, lenet_steps = 16, (args.steps or 30)

    def build_mlp(seed):
        paddle.seed(seed)
        main = paddle.static.Program()
        with paddle.static.program_guard(main):
            x = paddle.static.data("x", [None, hidden], "float32")
            y = paddle.static.data("y", [None, 1], "float32")
            h = x
            for _ in range(depth):
                h = paddle.static.nn.fc(h, hidden, activation="relu")
            pred = paddle.static.nn.fc(h, 1)
            loss = F.mse_loss(pred, y)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, loss

    def build_lenet(seed):
        paddle.seed(seed)
        main = paddle.static.Program()
        with paddle.static.program_guard(main):
            x = paddle.static.data("x", [None, 1, 28, 28], "float32")
            y = paddle.static.data("y", [None], "int64")
            loss = F.cross_entropy(LeNet()(x), y)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, loss

    rng = np.random.RandomState(0)
    xs = rng.standard_normal((batch, hidden)).astype(np.float32)
    ys = rng.standard_normal((batch, 1)).astype(np.float32)

    paddle.enable_static()
    try:
        # fast path: jax feeds pass through, async fetch, donated state;
        # legacy: the preserved pre-change run loop on an identical
        # program.  The two loops are INTERLEAVED over `reps` rounds so
        # machine noise (CPU frequency, co-tenants) hits both equally.
        main, loss = build_mlp(7)
        exe = paddle.static.Executor()
        feed = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
        main2, loss2 = build_mlp(7)
        exe2 = paddle.static.Executor()
        np_feed = {"x": xs, "y": ys}

        for _ in range(3):  # compile + warm both paths
            last = exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)[0]
            exe2._run_legacy(main2, feed=np_feed, fetch_list=[loss2])
        float(np.asarray(last.data))
        compiles, converts = exe.compile_count, exe.host_feed_converts

        reps, dt_fast, dt_leg = 3, 0.0, 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                last = exe.run(main, feed=feed, fetch_list=[loss],
                               return_numpy=False)[0]
            float(np.asarray(last.data))  # sync once per round
            dt_fast += time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(steps):
                exe2._run_legacy(main2, feed=np_feed, fetch_list=[loss2])
            dt_leg += time.perf_counter() - t0

        # anomaly-sentry counters (ISSUE 15 gate): the fast loop above
        # ran with the default sentry-less step — time the identical
        # program with FLAGS_anomaly_sentry compiled IN, interleaved
        # round-for-round so machine noise hits both, and report the
        # overhead plus the device-side skipped-step counter (must be
        # 0 on clean data).  This micro is the sentry's WORST case:
        # host+tiny-device work dominates, so the per-grad finiteness
        # scans are visible here while they vanish under real model
        # math — which is exactly why the number is worth recording.
        main3, loss3 = build_mlp(7)
        exe3 = paddle.static.Executor()
        paddle.set_flags({"anomaly_sentry": True})
        try:
            for _ in range(3):
                last3 = exe3.run(main3, feed=feed, fetch_list=[loss3],
                                 return_numpy=False)[0]
            float(np.asarray(last3.data))
        finally:
            paddle.set_flags({"anomaly_sentry": False})
        dt_on = dt_off = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                last = exe.run(main, feed=feed, fetch_list=[loss],
                               return_numpy=False)[0]
            float(np.asarray(last.data))
            dt_off += time.perf_counter() - t0
            paddle.set_flags({"anomaly_sentry": True})
            try:
                t0 = time.perf_counter()
                for _ in range(steps):
                    last3 = exe3.run(main3, feed=feed,
                                     fetch_list=[loss3],
                                     return_numpy=False)[0]
                float(np.asarray(last3.data))
                dt_on += time.perf_counter() - t0
            finally:
                paddle.set_flags({"anomaly_sentry": False})
        sentry_block = {
            "skipped_steps": exe3.sentry_stats(main3)["skipped_steps"],
            "overhead_pct": round(100.0 * (dt_on / dt_off - 1.0), 2),
            "step_time_ms_on": round(1e3 * dt_on / (reps * steps), 3),
            "step_time_ms_off": round(1e3 * dt_off / (reps * steps), 3),
        }
        steps *= reps

        # conv entry: absolute static-path throughput tracking
        lx = jnp.asarray(rng.standard_normal(
            (lenet_batch, 1, 28, 28)).astype(np.float32))
        ly = jnp.asarray(rng.randint(0, 10, (lenet_batch,),
                                     dtype=np.int64))
        lmain, lloss = build_lenet(9)
        lexe = paddle.static.Executor()
        dt_lenet, lenet_loss = _timed_static_loop(
            lexe, lmain, lloss, {"x": lx, "y": ly}, lenet_steps)
        lenet_compiles = lexe.compile_count

        # static cost model (ISSUE 6): predicted FLOPs/peak-bytes next
        # to the measured numbers, so BENCH_r*.json tracks model
        # accuracy over time (predicted-vs-measured drift per round)
        def _predicted(prog, loss_var, bsz):
            rep = prog.analyze(fetch_list=[loss_var], batch_size=bsz)
            m = rep.memory
            return {
                "fwd_gflops_per_step": round(
                    rep.totals["flops_fwd"] / 1e9, 4),
                "train_gflops_per_step": round(
                    rep.totals["flops_train"] / 1e9, 4),
                "peak_mib_donated": round(
                    m.peak_bytes_donated / 2**20, 2),
                "peak_mib_no_donation": round(
                    m.peak_bytes_no_donation / 2**20, 2),
                "arithmetic_intensity": round(
                    rep.totals["arithmetic_intensity"], 2),
                "unmodeled_ops": rep.totals["unmodeled"]["count"],
                "fusion_candidates": len(rep.fusion_candidates),
            }

        mlp_pred = _predicted(main, loss, batch)
        lenet_pred = _predicted(lmain, lloss, lenet_batch)
        mlp_pred["achieved_gflops_per_sec"] = round(
            mlp_pred["train_gflops_per_step"] * steps / dt_fast, 2)
        lenet_pred["achieved_gflops_per_sec"] = round(
            lenet_pred["train_gflops_per_step"] * lenet_steps / dt_lenet,
            2)
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()

    return {
        "metric": "static_mlp_train_steps_per_sec",
        "value": round(steps / dt_fast, 2),
        "unit": "steps/s",
        "speedup_vs_legacy_executor": round(dt_leg / dt_fast, 3),
        "legacy_steps_per_sec": round(steps / dt_leg, 2),
        "step_time_ms": round(1000 * dt_fast / steps, 3),
        "compile_count": compiles,           # must be 1 (one feed sig)
        "host_feed_converts": converts,      # must be 0 (jax feeds)
        "donated": True,
        "sentry": sentry_block,              # anomaly sentry (ISSUE 15)
        "analyzer": mlp_pred,                # static cost model (ISSUE 6)
        "config": {"hidden": hidden, "depth": depth, "batch": batch,
                   "optimizer": "adam"},
        "static_lenet": {
            "metric": "static_lenet_train_steps_per_sec",
            "value": round(lenet_steps / dt_lenet, 2),
            "unit": "steps/s",
            "step_time_ms": round(1000 * dt_lenet / lenet_steps, 3),
            "compile_count": lenet_compiles,
            "batch": lenet_batch,
            "final_loss": round(lenet_loss, 4),
            "analyzer": lenet_pred,
        },
    }


def bench_serving(args, dev, on_tpu):
    """Serving-engine throughput (ISSUE 4 acceptance): a ragged stream of
    concurrent requests through the dynamic-batching InferenceEngine vs
    the same requests served one-by-one through sequential
    ``Predictor.run``.  Both paths are AOT-warmed (the sequential path
    rides the pad-to-bucket satellite, so neither side recompiles); the
    engine's win is batch coalescing — one XLA dispatch carries many
    requests.  Clients are closed-loop with pipelining depth 8 (each of
    the 8 client threads keeps up to 8 requests in flight, the shape of
    a real RPC frontend).  Sequential and concurrent rounds are
    INTERLEAVED so machine noise hits both equally.  Must show >= 2x at
    concurrency >= 8 on CPU with ``num_compiled_variants()`` flat after
    warmup."""
    import tempfile
    import threading

    import paddle_tpu as paddle
    from paddle_tpu import inference, jit, nn, serving
    from paddle_tpu.jit import InputSpec

    hidden, in_dim, out_dim = 128, 64, 32
    n_requests = args.steps or 240
    concurrency = int(os.environ.get("BENCH_SERVING_CLIENTS", "8"))
    window = int(os.environ.get("BENCH_SERVING_PIPELINE", "8"))
    max_batch = 32
    reps = 3

    paddle.seed(2024)
    model = nn.Sequential(nn.Linear(in_dim, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, out_dim))
    prefix = os.path.join(tempfile.mkdtemp(prefix="bench_serving_"), "m")
    jit.save(model, prefix,
             input_spec=[InputSpec([None, in_dim], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))

    rng = np.random.RandomState(0)
    reqs = [rng.standard_normal((int(rng.randint(1, 5)), in_dim))
            .astype(np.float32) for _ in range(n_requests)]
    rows_total = sum(r.shape[0] for r in reqs)

    # warm the sequential path across every ragged size (pad-to-bucket
    # compiles the pow2 buckets once) before timing
    for n in sorted({r.shape[0] for r in reqs}):
        np.asarray(pred.run([np.zeros((n, in_dim), np.float32)])[0])
    seq_variants = pred.num_compiled_variants()

    engine = serving.InferenceEngine(pred, max_batch_size=max_batch,
                                     batch_timeout_ms=2.0,
                                     max_queue=4 * n_requests)
    engine.warmup()

    errors = []

    def client(idx):
        try:
            pending = []
            for i in range(idx, n_requests, concurrency):
                pending.append(engine.infer([reqs[i]]))
                while len(pending) >= window:
                    pending.pop(0).result(120)
            for f in pending:
                f.result(120)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(f"{type(e).__name__}: {e}")

    dt_seq = dt_conc = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for r in reqs:
            np.asarray(pred.run([r])[0])    # per-request host sync, as
        dt_seq += time.perf_counter() - t0  # a single-caller server would

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt_conc += time.perf_counter() - t0
    n_requests *= reps
    rows_total *= reps
    stats = engine.stats()
    engine.close()
    if errors:
        raise RuntimeError(f"serving bench clients failed: {errors[:3]}")

    return {
        "metric": "serving_engine_requests_per_sec",
        "value": round(n_requests / dt_conc, 2),
        "unit": "requests/s",
        "speedup_vs_sequential_predictor": round(dt_seq / dt_conc, 3),
        "sequential_requests_per_sec": round(n_requests / dt_seq, 2),
        "rows_per_sec": round(rows_total / dt_conc, 2),
        "concurrency": concurrency,
        "pipeline_depth": window,
        "requests": n_requests,
        "mean_batch_occupancy": round(stats["mean_batch_occupancy"], 3),
        "requests_per_batch": round(stats["requests_per_batch"], 2),
        "padding_waste": round(stats["padding_waste"], 3),
        "latency_ms_p50": round(stats["latency_ms"]["p50"], 2),
        "latency_ms_p95": round(stats["latency_ms"]["p95"], 2),
        "latency_ms_p99": round(stats["latency_ms"]["p99"], 2),
        "compiled_variants_sequential_warm": seq_variants,
        "recompiles_after_warmup": stats["recompiles_after_warmup"],
        "max_batch_size": max_batch,
        "buckets": stats["buckets"],
        "config": {"model": f"mlp {in_dim}-{hidden}-{hidden}-{out_dim}",
                   "ragged_rows": "1-4", "batch_timeout_ms": 2.0},
    }


def bench_generation(args, dev, on_tpu):
    """Ragged-generation serving throughput (ISSUE 7 acceptance): a
    stream of generative requests (ragged prompt lengths AND ragged
    token budgets) through the continuous-batching ``GenerationEngine``
    (paged KV cache, token-level scheduling) vs the same requests
    generated ONE AT A TIME through ``nn.dynamic_decode`` over a dense
    padded KV cache (beam 1, compile-cached via ``cache=True`` so the
    baseline pays zero re-trace — the comparison isolates batching, not
    compile amnesia).  Both sides run the same transformer LM.

    Both sides provision the same serving max context (what the server
    *admits*, not what this stream happens to send): the dense baseline
    pays worst-case provisioning on every token — a [t_max] cache
    update plus dense attention over all t_max rows — while the paged
    engine allocates pages on demand and its context-bucketed decode
    step gathers only the live context.  That asymmetry is the paged
    KV cache's whole point (Ragged Paged Attention, PAPERS.md), on top
    of token-level batching (one compiled step carries ``num_slots``
    sequences, freed slots backfilled mid-flight).  The baseline is
    compile-cached at the single provisioned shape — the standard
    pre-paging deployment (bucketing the *time* dimension per request
    is exactly what the page table replaces).

    Gate: >= 3x token throughput inside the same p99 request-latency
    SLO (``latency_bound_ms``), zero steady-state decode recompiles."""
    import threading

    from paddle_tpu import nn, serving

    n_requests = args.steps or 48
    num_slots = 8
    reps = 2
    max_new_lo, max_new_hi = 16, 48
    prompt_lengths = [4, 6, 8, 12, 16, 24, 32]
    max_context = 512                  # what the server provisions for
    # per-request p99 SLO both paths must meet: a quiet-machine floor,
    # widened on loaded runners by the baseline's own measured tail (a
    # machine-speed proxy) — slot-sharing may not blow up the tail by
    # more than slo_vs_baseline x a dedicated per-request run
    slo_floor_ms = 900.0
    slo_vs_baseline = 3.5
    t_max_cells = max_context          # dense baseline cache rows

    model = serving.PagedDecoderLM(vocab_size=1024, hidden=256,
                                   num_layers=2, num_heads=8,
                                   ffn=2048, seed=7)
    EOS = model.vocab_size - 1
    rng = np.random.RandomState(42)
    prompts = [rng.randint(0, 128, rng.choice(prompt_lengths)).tolist()
               for _ in range(n_requests)]
    budgets = [int(rng.randint(max_new_lo, max_new_hi + 1))
               for _ in range(n_requests)]
    tokens_total = sum(budgets)
    t_decode_max = max_new_hi + 1      # budget tokens + the forced EOS

    # -- baseline: per-request dynamic_decode over a dense padded cache --
    cell = model.make_cell(EOS)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=EOS,
                               beam_size=1)

    def gen_one(prompt, limit):
        st = model.init_cell_state(prompt, t_max_cells)
        st["limit"] = np.full((1,), limit, np.int32)
        dec.start_token = int(prompt[-1])
        seq, _, lens = nn.dynamic_decode(dec, st,
                                         max_step_num=t_decode_max,
                                         return_length=True, cache=True)
        n = int(np.asarray(lens.numpy())[0, 0])
        return np.asarray(seq.numpy())[0, 0, :n]

    # warm both paths: every prompt-length shape for the baseline's
    # eager prefill, the cached decode loop, and the engine's buckets
    for L in sorted({len(p) for p in prompts}):
        gen_one(list(range(1, L + 1)), 2)
    # pool sized for what the slots can actually reserve (page demand
    # follows the traffic, not the advertised context — the paged
    # cache's memory win); prompt buckets cover the traffic mix
    engine = serving.GenerationEngine(model, num_slots=num_slots,
                                      page_size=8,
                                      max_context=max_context,
                                      num_pages=128,
                                      prompt_buckets=[8, 16, 32],
                                      max_queue=4 * n_requests)
    engine.warmup()

    errors = []
    conc_lat: list = []

    def client(idx):
        try:
            for i in range(idx, n_requests, num_slots):
                t0 = time.perf_counter()
                out = engine.generate_sync(prompts[i], timeout=300,
                                           max_new_tokens=budgets[i])
                conc_lat.append(time.perf_counter() - t0)
                if len(out) != budgets[i]:
                    errors.append(f"req {i}: {len(out)} tokens, "
                                  f"budget {budgets[i]}")
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(f"{type(e).__name__}: {e}")

    dt_seq = dt_conc = 0.0
    seq_lat: list = []
    for _ in range(reps):
        # sequential per-request generation, as a single-caller server
        t0 = time.perf_counter()
        for p, b in zip(prompts, budgets):
            t1 = time.perf_counter()
            out = gen_one(p, b)
            seq_lat.append(time.perf_counter() - t1)
            if len(out) != b + 1 or out[-1] != EOS:
                errors.append(f"baseline: {len(out)} tokens for "
                              f"budget {b}")
        dt_seq += time.perf_counter() - t0

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(num_slots)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt_conc += time.perf_counter() - t0
    stats = engine.stats()
    engine.close()
    if errors:
        raise RuntimeError(f"generation bench failed: {errors[:3]}")

    def p99(lat):
        return float(np.percentile(np.asarray(lat) * 1000.0, 99))

    toks = tokens_total * reps
    bound_ms = max(slo_floor_ms, slo_vs_baseline * p99(seq_lat))
    return {
        "metric": "serving_generation_tokens_per_sec",
        "value": round(toks / dt_conc, 2),
        "unit": "tokens/s",
        "speedup_vs_dynamic_decode": round(dt_seq / dt_conc, 3),
        "dynamic_decode_tokens_per_sec": round(toks / dt_seq, 2),
        "requests": n_requests * reps,
        "num_slots": num_slots,
        "latency_bound_ms": round(bound_ms, 2),
        "p99_latency_ms": round(p99(conc_lat), 2),
        "p99_latency_ms_baseline": round(p99(seq_lat), 2),
        "p99_within_bound": p99(conc_lat) <= bound_ms,
        "ttft_ms_p95": round(stats["ttft_ms"]["p95"], 2),
        "mean_slot_occupancy": round(stats["mean_slot_occupancy"], 3),
        "prefill_decode_ratio": round(stats["prefill_decode_ratio"], 3),
        "decode_steps": stats["counters"]["decode_steps"],
        "recompiles_after_warmup": stats["recompiles_after_warmup"],
        "page_pool_pages": stats["page_pool"]["num_pages"],
        "ctx_buckets": stats["ctx_buckets"],
        "config": {"model": "paged-lm 256h x2L 8H ffn2048", "vocab": 1024,
                   "prompt_lengths": prompt_lengths,
                   "max_new": [max_new_lo, max_new_hi],
                   "page_size": 8, "max_context": max_context},
    }


def bench_pallas(args, dev, on_tpu):
    """Pallas kernel tier (ISSUE 11): BERT and GPT *static* training
    suites timed with the tier ON vs OFF, interleaved on the SAME
    program/Executor — the tier state rides the compile cache key, so
    each flag flip dispatches its own cached executable and the donated
    state threads through both.  Reports step time + MFU per tier and
    the realized kernel list off the compile records, plus the serving
    decode suite with the paged-attention Pallas kernel registered vs
    the gather reference.  On CPU the kernels run in interpret mode
    (FLAGS_pallas_interpret) — the absolute numbers are meaningless
    there, the JSON *shape* and the realized-kernel evidence are what
    BENCH_* tracks; the speedups become real on TPU."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.flags import get_flag, set_flags
    from paddle_tpu.observability import explain_compiles

    if on_tpu:
        bert_cfg = dict(vocab=30522, hidden=768, layers=12, heads=12,
                        ffn=3072, seq=512, batch=16)
        gpt_cfg = dict(vocab=50257, hidden=1024, layers=8, heads=16,
                       ffn=4096, seq=1024, batch=8)
        steps, reps = (args.steps or 10), 2
    else:
        bert_cfg = dict(vocab=1000, hidden=128, layers=2, heads=4,
                        ffn=512, seq=128, batch=8)
        gpt_cfg = dict(vocab=1000, hidden=128, layers=2, heads=4,
                       ffn=512, seq=128, batch=4)
        steps, reps = (args.steps or 2), 2

    peak = _peak_flops(dev)
    prev_interpret = get_flag("pallas_interpret")
    prev_kernels = get_flag("use_pallas_kernels")
    paddle.enable_static()
    try:
        if not on_tpu:
            set_flags({"pallas_interpret": True})

        def run_suite(build, cfg):
            main, loss, feeds_fn = build(**cfg)
            exe = paddle.static.Executor()
            feed = feeds_fn(np.random.RandomState(0))
            tokens = cfg["batch"] * cfg["seq"]

            def loop(n):
                last = None
                for _ in range(n):
                    last = exe.run(main, feed=feed, fetch_list=[loss],
                                   return_numpy=False)[0]
                return float(np.asarray(last.data))

            # warm BOTH tier variants (each is its own cache entry)
            set_flags({"use_pallas_kernels": True})
            loop(2)
            set_flags({"use_pallas_kernels": False})
            loop(2)
            warm_compiles = exe.compile_count

            dt_on = dt_off = 0.0
            for _ in range(reps):
                set_flags({"use_pallas_kernels": True})
                t0 = time.perf_counter()
                loss_on = loop(steps)
                dt_on += time.perf_counter() - t0
                set_flags({"use_pallas_kernels": False})
                t0 = time.perf_counter()
                loss_off = loop(steps)
                dt_off += time.perf_counter() - t0
            n = steps * reps
            # analyze under the tier-ON flag state: the realized
            # marking is flag-gated exactly like the executor pass
            set_flags({"use_pallas_kernels": True})
            rep = main.analyze(fetch_list=[loss], top_k=None)
            flops = rep.totals["flops_train"]
            sps_on, sps_off = n / dt_on, n / dt_off
            recs = [r for r in explain_compiles("executor")["records"]
                    if r["identity"] == main._serial
                    and r.get("kernels")]
            kernels = recs[-1]["kernels"] if recs else []
            realized = [c["realized"] for c in rep.fusion_candidates
                        if c.get("realized")]
            out = {
                "step_time_ms_pallas_on": round(1000 * dt_on / n, 3),
                "step_time_ms_pallas_off": round(1000 * dt_off / n, 3),
                "speedup_pallas_on_vs_off": round(dt_off / dt_on, 3),
                "tokens_per_sec_on": round(tokens * sps_on, 2),
                "tokens_per_sec_off": round(tokens * sps_off, 2),
                "mfu_on": round(flops * sps_on / peak, 4) if peak else 0.0,
                "mfu_off": round(flops * sps_off / peak, 4) if peak
                else 0.0,
                "mfu_delta": round(flops * (sps_on - sps_off) / peak, 4)
                if peak else 0.0,
                "final_loss_on": round(loss_on, 4),
                "final_loss_off": round(loss_off, 4),
                "realized_kernels": kernels,
                "fusion_candidates_realized":
                    f"{len(realized)}/{len(rep.fusion_candidates)}",
                "compile_count": exe.compile_count,
                "recompiles_after_warmup":
                    exe.compile_count - warm_compiles,
                "config": dict(cfg),
            }
            exe.close()
            return out

        bert = run_suite(build_bert_static, bert_cfg)
        gpt = run_suite(build_gpt_static, gpt_cfg)
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
        set_flags({"pallas_interpret": prev_interpret,
                   "use_pallas_kernels": prev_kernels})

    decode = _bench_paged_decode(on_tpu)

    return {
        "metric": "pallas_tier_bert_static_speedup_on_vs_off",
        "value": bert["speedup_pallas_on_vs_off"],
        "unit": "x",
        "interpret_mode": not on_tpu,
        "bert_static": bert,
        "gpt_static": gpt,
        "generation_decode": decode,
    }


def _bench_paged_decode(on_tpu):
    """Decode tokens/s with the Pallas paged-attention kernel
    registered vs the gather reference (same ragged request mix, dyadic
    model => token parity is bitwise-checkable)."""
    from paddle_tpu import serving
    from paddle_tpu.core.flags import get_flag, set_flags
    from paddle_tpu.ops import attention as _attn

    n_requests, budget = (16, 24) if on_tpu else (6, 8)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 64, rng.choice([3, 5, 9])).tolist()
               for _ in range(n_requests)]
    prev_interpret = get_flag("pallas_interpret")
    prev_kernels = get_flag("use_pallas_kernels")

    def run(tier_on):
        set_flags({"use_pallas_kernels": tier_on,
                   "pallas_interpret": tier_on and not on_tpu})
        _attn.register_paged_attention_kernel(None)
        # head_dim = 256/2 = 128: the gate's 128-lane alignment
        model = serving.PagedDecoderLM(vocab_size=128, hidden=256,
                                       num_layers=2, num_heads=2,
                                       seed=7, dyadic=True)
        engine = serving.GenerationEngine(model, num_slots=4,
                                          page_size=8, max_context=64,
                                          num_pages=64)
        engine.warmup()
        t0 = time.perf_counter()
        outs = [engine.generate_sync(p, max_new_tokens=budget,
                                     timeout=600) for p in prompts]
        dt = time.perf_counter() - t0
        stats = engine.stats()
        engine.close()
        _attn.register_paged_attention_kernel(None)
        return outs, dt, stats

    try:
        ref_outs, dt_ref, _ = run(False)
        pal_outs, dt_pal, stats = run(True)
    finally:
        _attn.register_paged_attention_kernel(None)
        set_flags({"pallas_interpret": prev_interpret,
                   "use_pallas_kernels": prev_kernels})
    toks = n_requests * budget
    from paddle_tpu.ops.pallas.support import kernel_selections
    return {
        "tokens_per_sec_paged_kernel": round(toks / dt_pal, 2),
        "tokens_per_sec_reference": round(toks / dt_ref, 2),
        "token_parity": ref_outs == pal_outs,
        "kernel_selected": kernel_selections.get("paged_attention", 0) > 0,
        "recompiles_after_warmup": stats["recompiles_after_warmup"],
        "requests": n_requests,
        "budget_tokens": budget,
    }


def bench_lenet_dygraph(args):
    """Dygraph (eager, un-jitted) smoke benchmark (BASELINE.json
    configs[0]): LeNet/MNIST shapes on CPU, measuring per-op Python
    dispatch + tape overhead.  Runs in a subprocess so the CPU backend
    doesn't fight the TPU client in this process.  The parent already
    holds the chip here; that is harmless only because the child is
    pinned to the CPU — a child that needed the chip would fail or hang
    (one process per chip).  S0 decides whether this suite stays."""
    code = (
        "import sys, time, json; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "import paddle_tpu as paddle\n"
        "import paddle_tpu.nn.functional as F\n"
        "from paddle_tpu import optimizer\n"
        "from paddle_tpu.vision.models import LeNet\n"
        "paddle.seed(0)\n"
        "model = LeNet()\n"
        "opt = optimizer.Adam(learning_rate=1e-3,"
        " parameters=model.parameters())\n"
        "x = paddle.to_tensor(np.random.randn(64, 1, 28, 28)"
        ".astype('float32'))\n"
        "y = paddle.to_tensor(np.random.randint(0, 10, (64,))"
        ".astype('int64'))\n"
        "def one_step():\n"
        "    loss = F.cross_entropy(model(x), y)\n"
        "    loss.backward(); opt.step(); opt.clear_grad()\n"
        "    return float(loss)\n"
        "for _ in range(3): one_step()\n"
        "t0 = time.perf_counter(); n = 30\n"
        "for _ in range(n): last = one_step()\n"
        "dt = time.perf_counter() - t0\n"
        "from paddle_tpu import profiler as _prof\n"
        "p = _prof.Profiler(timer_only=True); p.start()\n"
        "for _ in range(5): one_step()  # separate profiled pass\n"
        "p.stop()\n"
        "top_ops = [[nm, c, round(ms, 2)]"
        " for nm, c, ms in p.key_averages()[:5]]\n"
        "import tempfile, os as _os\n"
        "from paddle_tpu import inference, jit\n"
        "from paddle_tpu.jit import InputSpec\n"
        "pfx = _os.path.join(tempfile.mkdtemp(), 'm')\n"
        "jit.save(model, pfx, input_spec=[InputSpec([None,1,28,28],"
        " 'float32')])\n"
        "pred = inference.create_predictor(inference.Config(pfx))\n"
        "xi = np.zeros((1, 1, 28, 28), 'float32')\n"
        "pred.run([xi])\n"
        "t0 = time.perf_counter()\n"
        "for _ in range(50): outs = pred.run([xi])\n"
        "float(np.asarray(outs[0]).sum())\n"
        "infer_ms = (time.perf_counter() - t0) / 50 * 1000\n"
        "print(json.dumps({'step_time_ms': round(1000 * dt / n, 3),"
        " 'steps_per_sec': round(n / dt, 2), 'final_loss': round(last, 4),"
        " 'predictor_latency_ms_bs1': round(infer_ms, 3),"
        " 'predictor_recompiles': pred.num_compiled_variants(),"
        " 'top_host_ops_ms': top_ops}))\n"
        % os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        line = out.stdout.strip().splitlines()[-1]
        res = json.loads(line)
    except Exception as e:  # pragma: no cover - defensive
        return {"metric": "lenet_mnist_dygraph_step_time_ms",
                "error": f"{type(e).__name__}: {e}"}
    res.update({"metric": "lenet_mnist_dygraph_step_time_ms",
                "unit": "ms/step", "batch": 64, "platform": "cpu",
                "mode": "eager"})
    return res


def bench_multichip(args):
    """Multichip GPT-tiny collective-efficiency + overlap run (ISSUE
    10/14/17 gates): tools/comm_smoke.py on 8 virtual CPU devices in a
    subprocess (this process's jax is already initialised with its own
    device count), comparing int8 block-scaled grad_comm against the
    fp32 wire baseline — wire bytes/step (measured == cost-model
    prediction), loss-trajectory parity under error feedback,
    recompiles — and overlap=auto against overlap=none: step time vs
    the max(compute, comm) bound, with the perf observatory's
    exposed-vs-hidden comm split embedded next to the wire-byte ratio
    (result key ``overlap_gate``).  ISSUE 17 adds the hybrid rows: a
    {dp:4, mp:2} tensor-parallel run with per-axis wire accounting
    (``hybrid`` key: dp/mp bytes each measured == predicted, plus the
    forward param-gather ledger) and a ZeRO-3 run with params sharded
    at rest (``zero3`` key: rscatter buckets + per-shard peak bytes
    vs the replicated baseline)."""
    # like bench_lenet_dygraph: a CPU-pinned child of a parent that may
    # hold the chip — harmless for the chip, never to be copied for a
    # child that needs it
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "comm_smoke.py"), "--json"]
    if args.steps:
        cmd += ["--steps", str(args.steps)]
    try:
        out = subprocess.run(cmd, env=env, capture_output=True,
                             text=True, timeout=600)
        line = out.stdout.strip().splitlines()[-1]
        res = json.loads(line)
        if out.returncode != 0:
            res["gate_failures"] = out.stderr.strip().splitlines()[-5:]
    except Exception as e:  # pragma: no cover - defensive
        return {"metric": "multichip_gpt_int8_wire_ratio_vs_fp32",
                "error": f"{type(e).__name__}: {e}"}
    res.update({"platform": "cpu", "devices": 8,
                "meshes": [{"dp": 8}, {"dp": 4, "mp": 2}]})
    hyb = res.get("hybrid_dp4_mp2") or {}
    z3 = res.get("zero3") or {}
    int8 = res.get("int8") or {}
    res["hybrid"] = {
        "mesh": {"dp": 4, "mp": 2},
        "axis_wire_bytes_per_step": hyb.get("axis_wire_bytes_per_step"),
        "predicted_axis_wire_bytes":
            hyb.get("predicted_axis_wire_bytes"),
        "gather_wire_bytes_per_step":
            hyb.get("gather_wire_bytes_per_step"),
        "gather_collectives_per_step":
            hyb.get("gather_collectives_per_step"),
        "step_ms_min": hyb.get("step_ms_min"),
        "compiles": hyb.get("compiles"),
    }
    res["zero3_summary"] = {
        "algorithms": z3.get("algorithms"),
        "peak_bytes_per_shard": z3.get("peak_bytes_per_shard"),
        "replicated_peak_bytes_per_shard":
            int8.get("peak_bytes_per_shard"),
        "wire_bytes_per_step": z3.get("wire_bytes_per_step"),
        "step_ms_min": z3.get("step_ms_min"),
        "compiles": z3.get("compiles"),
    }
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--profile", type=str, default=None,
                    help="directory for a jax profiler trace of timed steps")
    ap.add_argument("--small", action="store_true",
                    help="force the tiny CPU config")
    ap.add_argument("--suite", type=str, default="all",
                    choices=["all", "bert", "gpt", "resnet", "lenet",
                             "static", "serving", "multichip", "pallas"],
                    help="which benchmarks to run (default: all)")
    args = ap.parse_args()

    from paddle_tpu.core.xla_env import place_compile_cache
    place_compile_cache()

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu" and not args.small

    extra = {}
    if args.suite in ("all", "resnet"):
        try:
            extra["resnet50"] = _with_counters(bench_resnet50, args, dev,
                                               on_tpu)
        except Exception as e:
            extra["resnet50"] = {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "error": f"{type(e).__name__}: {e}"}
    if args.suite in ("all", "gpt"):
        try:
            extra["gpt"] = _with_counters(bench_gpt, args, dev, on_tpu)
        except Exception as e:
            extra["gpt"] = {
                "metric": "gpt_pretrain_tokens_per_sec_per_chip",
                "error": f"{type(e).__name__}: {e}"}
    if args.suite in ("all", "static"):
        try:
            extra["static"] = _with_counters(bench_static, args, dev, on_tpu)
        except Exception as e:
            extra["static"] = {
                "metric": "static_mlp_train_steps_per_sec",
                "error": f"{type(e).__name__}: {e}"}
    if args.suite in ("all", "serving"):
        try:
            extra["serving"] = _with_counters(bench_serving, args, dev,
                                              on_tpu)
        except Exception as e:
            extra["serving"] = {
                "metric": "serving_engine_requests_per_sec",
                "error": f"{type(e).__name__}: {e}"}
        try:
            extra["serving_generation"] = _with_counters(
                bench_generation, args, dev, on_tpu)
        except Exception as e:
            extra["serving_generation"] = {
                "metric": "serving_generation_tokens_per_sec",
                "error": f"{type(e).__name__}: {e}"}
    if args.suite in ("all", "pallas"):
        try:
            extra["pallas"] = _with_counters(bench_pallas, args, dev,
                                             on_tpu)
        except Exception as e:
            extra["pallas"] = {
                "metric": "pallas_tier_bert_static_speedup_on_vs_off",
                "error": f"{type(e).__name__}: {e}"}
    if args.suite in ("all", "multichip"):
        extra["multichip"] = bench_multichip(args)
    if args.suite in ("all", "lenet"):
        extra["lenet_dygraph"] = bench_lenet_dygraph(args)

    result = None
    if args.suite in ("all", "bert"):
        try:
            result = _with_counters(bench_bert, args, dev, on_tpu)
        except Exception as e:
            extra["bert_error"] = {"error": f"{type(e).__name__}: {e}"}
    if result is None:
        # never exit non-zero without a JSON line: promote the first
        # successful secondary result (round-4 lesson — rc=1 loses the
        # round's perf evidence entirely)
        for k in ("gpt", "resnet50", "static", "serving", "pallas",
                  "multichip", "lenet_dygraph"):
            if k in extra and "error" not in extra[k]:
                result = extra.pop(k)
                break
    if result is None:
        result = {"metric": "bench_failed", "value": 0.0, "unit": "none",
                  "vs_baseline": 0.0}

    result.setdefault("device", getattr(dev, "device_kind", dev.platform))
    result.setdefault("platform", dev.platform)
    if extra:
        result["extra"] = extra
    print(json.dumps(result))


if __name__ == "__main__":
    main()
