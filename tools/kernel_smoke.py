#!/usr/bin/env python
"""CI gate: the Pallas kernel tier must be numerically safe and actually
engaged — under ``JAX_PLATFORMS=cpu`` (interpret mode), the same gate a
TPU deployment relies on.

Asserts, in order:

1.  **Fused Adam trajectory** — the one-pass kernel tracks the unfused
    ``Adam.update_param`` within 1e-6 over a multi-step trajectory on
    ragged (pad-exercising) shapes;
2.  **MLP train parity + engagement** — a small MLP trains with the
    tier ON vs OFF to matching loss trajectories (1e-4 relative), the
    compile record names the selected kernels (fused epilogues + fused
    Adam), and 0 recompiles happen after warmup with the tier on;
3.  **BERT-tiny realization** — ``Program.analyze()`` on the benchmark's
    static BERT program at tiny widths marks >= 1 fusion candidate
    ``realized`` with a kernel name, and the executor's record agrees;
4.  **Clean composite fallback** — a program whose shapes fail the
    kernel gates (non-tile-aligned widths, AdamW) realizes NOTHING and
    reproduces the tier-off run bitwise;
5.  **Decode parity** — ``GenerationEngine`` decode over the Pallas
    paged-attention kernel emits bitwise-identical tokens to the gather
    reference (dyadic model), with 0 recompiles after warmup;
6.  **OFF contract** — with ``FLAGS_use_pallas_kernels`` disabled, zero
    Pallas kernels are selected anywhere.

Exit 0 on success, 1 with reasons on any violation.
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# BERT-tiny: laid over the benchmark's static BERT cell, whose own builder
# (benchmark/models/bert_static.py) records the program
BERT_TINY = dict(vocab_size=1000, hidden_size=128, num_hidden_layers=2,
                 num_attention_heads=4, intermediate_size=512,
                 max_position_embeddings=128)
BERT_TINY_MIX = dict(seq=128, batch=8)


def _build_mlp(hidden=128, depth=3, activation="relu", out_width=128):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer

    paddle.seed(7)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [None, hidden], "float32")
        y = paddle.static.data("y", [None, out_width], "float32")
        h = x
        for _ in range(depth):
            h = paddle.static.nn.fc(h, hidden, activation=activation)
        pred = paddle.static.nn.fc(h, out_width)
        loss = F.mse_loss(pred, y)
        optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, loss


def _train(main, loss, feed, steps):
    import numpy as np

    import paddle_tpu as paddle
    exe = paddle.static.Executor()
    losses = []
    for _ in range(steps):
        losses.append(float(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss])[0])))
    cc = exe.compile_count
    exe.close()
    return losses, cc


def _check_fused_adam(failures):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.fused_adam import fused_adam_update
    from paddle_tpu.optimizer.optimizer import Adam

    r = np.random.RandomState(0)
    opt = Adam(learning_rate=1e-3)
    for shape in [(33,), (257, 3), (128, 128)]:
        p = jnp.asarray(r.randn(*shape), jnp.float32)
        s = opt.init_slots(p)
        pf, mf, vf = p, s["m"], s["v"]
        pr, sr = p, dict(s)
        for step in range(1, 9):
            g = jnp.asarray(r.randn(*shape), jnp.float32)
            pf, mf, vf = fused_adam_update(pf, g, mf, vf, 1e-3,
                                           float(step), interpret=True)
            pr, sr = opt.update_param(
                pr, g, sr, jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(step, jnp.float32))
        err = float(jnp.max(jnp.abs(pf - pr)))
        if err > 1e-6:
            failures.append(
                f"fused Adam trajectory drifted {err:.2e} > 1e-6 on "
                f"shape {shape} after 8 steps")


def run_checks():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from chip_smoke import STATIC_CELL, build_static, load_parts
    from paddle_tpu import serving
    from paddle_tpu.core.flags import get_flag, set_flags
    from paddle_tpu.observability import explain_compiles
    from paddle_tpu.ops import attention as _attn
    from paddle_tpu.ops.pallas.support import kernel_selections

    failures: list = []
    _check_fused_adam(failures)

    prev = {k: get_flag(k) for k in ("use_pallas_kernels",
                                     "pallas_interpret")}
    paddle.enable_static()
    try:
        r = np.random.RandomState(0)
        feed = {"x": jnp.asarray(r.standard_normal(
                    (32, 128)).astype(np.float32)),
                "y": jnp.asarray(r.standard_normal(
                    (32, 128)).astype(np.float32))}

        # -- 6. OFF contract: no Pallas selection anywhere ------------
        set_flags({"use_pallas_kernels": False, "pallas_interpret": True})
        before_calls = dict(kernel_selections)
        main, loss = _build_mlp()
        off_losses, _ = _train(main, loss, feed, 6)
        if dict(kernel_selections) != before_calls:
            failures.append(
                f"FLAGS_use_pallas_kernels=False still selected Pallas "
                f"kernels: {kernel_selections} vs {before_calls}")

        # -- 2. MLP parity + engagement + 0 recompiles ----------------
        set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
        main_on, loss_on = _build_mlp()
        on_losses, cc = _train(main_on, loss_on, feed, 6)
        scale = max(abs(v) for v in off_losses) or 1.0
        drift = max(abs(a - b) for a, b in zip(on_losses, off_losses))
        if drift > 1e-4 * max(scale, 1.0):
            failures.append(
                f"MLP tier-on loss trajectory drifted {drift:.2e} from "
                f"tier-off (losses {on_losses} vs {off_losses})")
        if cc != 1:
            failures.append(
                f"MLP with the tier on recompiled: {cc} compiles for "
                f"one feed signature (expected 1 -> 0 after warmup)")
        recs = [rec for rec in explain_compiles("executor")["records"]
                if rec["identity"] == main_on._serial]
        kernels = recs[-1].get("kernels", []) if recs else []
        if not any(k.startswith("fused_epilogue") for k in kernels):
            failures.append(
                f"no fused epilogue on the MLP compile record: {kernels}")
        if "fused_adam" not in kernels:
            failures.append(
                f"fused Adam not selected on the MLP compile record: "
                f"{kernels}")

        # -- 3. BERT-tiny: >= 1 candidate realized --------------------
        cell, cfg, mix, model_mod = load_parts(STATIC_CELL)
        bmain, bloss, bfeed = build_static(
            cell, {**cfg, **BERT_TINY}, {**mix, **BERT_TINY_MIX}, model_mod)
        rep = bmain.analyze(fetch_list=[bloss], top_k=None)
        realized = [c for c in rep.fusion_candidates if c.get("realized")]
        if not realized:
            failures.append(
                "BERT-tiny: Program.analyze() marks no fusion candidate "
                "realized with the tier on")
        _, bcc = _train(bmain, bloss, bfeed, 3)
        brecs = [rec for rec in explain_compiles("executor")["records"]
                 if rec["identity"] == bmain._serial]
        bkernels = brecs[-1].get("kernels", []) if brecs else []
        if not any(k.startswith("fused_epilogue") for k in bkernels):
            failures.append(
                f"BERT-tiny compile record names no fused epilogue: "
                f"{bkernels}")
        if bcc != 1:
            failures.append(f"BERT-tiny recompiled: {bcc} compiles")

        # -- 4. gated-out shapes: clean composite fallback, bitwise --
        # width 100 fails the N%128 tile gate; AdamW (decoupled decay)
        # fails the fused-Adam eligibility -> tier-on == tier-off
        # bitwise because NOTHING may be selected
        import paddle_tpu.nn.functional as F
        from paddle_tpu import optimizer as _opt

        def build_gated():
            paddle.seed(9)
            m = paddle.static.Program()
            with paddle.static.program_guard(m):
                x = paddle.static.data("x", [None, 100], "float32")
                y = paddle.static.data("y", [None, 1], "float32")
                h = paddle.static.nn.fc(x, 100, activation="relu")
                l = F.mse_loss(paddle.static.nn.fc(h, 1), y)
                _opt.AdamW(learning_rate=1e-3,
                           weight_decay=0.01).minimize(l)
            return m, l

        gfeed = {"x": jnp.asarray(r.standard_normal(
                     (16, 100)).astype(np.float32)),
                 "y": jnp.asarray(r.standard_normal(
                     (16, 1)).astype(np.float32))}
        gm, gl = build_gated()
        g_on, _ = _train(gm, gl, gfeed, 4)
        grecs = [rec for rec in explain_compiles("executor")["records"]
                 if rec["identity"] == gm._serial]
        gk = grecs[-1].get("kernels", []) if grecs else []
        if gk:
            failures.append(
                f"gated-out program still selected kernels: {gk}")
        set_flags({"use_pallas_kernels": False})
        gm2, gl2 = build_gated()
        g_off, _ = _train(gm2, gl2, gfeed, 4)
        if g_on != g_off:
            failures.append(
                f"gated-out fallback is not bitwise: {g_on} vs {g_off}")

        # -- 5. decode parity over the paged kernel -------------------
        def decode_tokens(tier_on):
            set_flags({"use_pallas_kernels": tier_on,
                       "pallas_interpret": tier_on})
            _attn.register_paged_attention_kernel(None)
            model = serving.PagedDecoderLM(
                vocab_size=64, hidden=256, num_layers=2, num_heads=2,
                seed=5, dyadic=True)
            eng = serving.GenerationEngine(model, num_slots=2,
                                           page_size=8, max_context=64,
                                           num_pages=32)
            eng.warmup()
            outs = [eng.generate_sync([1, 2, 3], max_new_tokens=5,
                                      timeout=300),
                    eng.generate_sync([7, 8], max_new_tokens=5,
                                      timeout=300)]
            rc = eng.stats()["recompiles_after_warmup"]
            eng.close()
            _attn.register_paged_attention_kernel(None)
            return outs, rc

        ref_toks, _ = decode_tokens(False)
        calls0 = kernel_selections.get("paged_attention", 0)
        pal_toks, rc = decode_tokens(True)
        if kernel_selections.get("paged_attention", 0) <= calls0:
            failures.append("paged-attention kernel never selected "
                            "with the tier on")
        if pal_toks != ref_toks:
            failures.append(
                f"paged decode tokens diverge from the gather "
                f"reference: {pal_toks} vs {ref_toks}")
        if rc:
            failures.append(
                f"decode with the paged kernel recompiled after "
                f"warmup: {rc}")
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
        _attn.register_paged_attention_kernel(None)
        set_flags(prev)
    return failures


def main(argv=None):
    failures = run_checks()
    if failures:
        for f in failures:
            print(f"kernel_smoke: FAIL: {f}")
        return 1
    print("kernel_smoke: PASS — fused Adam 1e-6 trajectory, MLP/"
          "BERT-tiny candidates realized with 0 recompiles after "
          "warmup, bitwise composite fallback on gated-out shapes, "
          "bitwise paged-decode parity, zero Pallas selections with "
          "the tier off")
    return 0


if __name__ == "__main__":
    sys.exit(main())
