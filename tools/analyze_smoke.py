#!/usr/bin/env python
"""CI gate: the static cost model must stay honest on two small programs.

Builds an MLP (the Executor hot-path micro) and LeNet as static
Programs and asserts, in order:

1. predicted forward FLOPs within 20% of an INDEPENDENT hand count
   (per-layer 2*M*K*N matmuls + bias/activation terms, conv im2col
   dots — written out below, not derived from the analyzer's tables);
2. zero `unmodeled` ops/bytes on these programs — the op tables cover
   both programs whole;
3. liveness: peak memory with donation strictly below the no-donation
   bound (what PR 2's donation buys must be visible statically);
4. at least one ranked fusion candidate (the MPK-style selection the
   Pallas tier will consume), with positive traffic savings;
5. TPU-readiness hazard passes clean: no error- or warning-severity
   hazards (int64 label feeds are info, allowed);
6. `tools/analyze_program.py --format json` on the same MLP module
   parses and reproduces the in-process FLOP count exactly;
7. the Executor records the same prediction per compile
   (`explain_compiles()` record carries `predicted`, monitor gauges
   `predicted.executor.*` are set).

Exit 0 on success, 1 with a reason on any violation.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the two programs' sizes
MLP_HIDDEN, MLP_DEPTH, MLP_BATCH = 128, 8, 32
LENET_BATCH = 16

_MLP_MODULE = """
import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer

paddle.enable_static()
paddle.seed(7)
main = paddle.static.Program()
with paddle.static.program_guard(main):
    x = paddle.static.data("x", [None, {hidden}], "float32")
    y = paddle.static.data("y", [None, 1], "float32")
    h = x
    for _ in range({depth}):
        h = paddle.static.nn.fc(h, {hidden}, activation="relu")
    pred = paddle.static.nn.fc(h, 1)
    loss = F.mse_loss(pred, y)
    optimizer.Adam(learning_rate=1e-3).minimize(loss)
loss_name = loss.name
"""


def _fail(msg: str) -> int:
    print(f"analyze_smoke: FAIL - {msg}")
    return 1


def _mlp_hand_flops(batch: int) -> int:
    """Forward FLOPs of the MLP, counted from the layer algebra:
    each fc is a [B,K]x[K,N] matmul (2*B*K*N) + bias add (B*N); relu is
    one op per element; mse is a handful per output element."""
    h, fl = MLP_HIDDEN, 0
    for _ in range(MLP_DEPTH):
        fl += 2 * batch * h * h + batch * h + batch * h
    fl += 2 * batch * h * 1 + batch * 1   # head fc
    fl += 5 * batch * 1                   # mse (sub, square, mean)
    return fl


def _lenet_hand_flops(batch: int) -> int:
    """LeNet forward: conv dots are 2*out_elems*(Cin*kh*kw) + bias."""
    b, fl = batch, 0
    fl += 2 * b * 6 * 28 * 28 * (1 * 3 * 3) + b * 6 * 28 * 28  # conv1
    fl += b * 6 * 28 * 28                                      # relu
    fl += b * 6 * 14 * 14 * 4                                  # pool 2x2
    fl += 2 * b * 16 * 10 * 10 * (6 * 5 * 5) + b * 16 * 10 * 10
    fl += b * 16 * 10 * 10
    fl += b * 16 * 5 * 5 * 4
    fl += 2 * b * 120 * 400 + b * 120
    fl += 2 * b * 84 * 120 + b * 84
    fl += 2 * b * 10 * 84 + b * 10
    fl += 10 * b * 10                     # softmax + nll
    return fl


def main() -> int:
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.observability import explain_compiles
    from paddle_tpu.static.analysis import Diagnostic
    from paddle_tpu.utils import monitor
    from paddle_tpu.vision.models import LeNet

    paddle.enable_static()
    reports = {}
    try:
        paddle.seed(7)
        mlp = paddle.static.Program()
        with paddle.static.program_guard(mlp):
            x = paddle.static.data("x", [None, MLP_HIDDEN], "float32")
            y = paddle.static.data("y", [None, 1], "float32")
            h = x
            for _ in range(MLP_DEPTH):
                h = paddle.static.nn.fc(h, MLP_HIDDEN, activation="relu")
            pred = paddle.static.nn.fc(h, 1)
            mlp_loss = F.mse_loss(pred, y)
            optimizer.Adam(learning_rate=1e-3).minimize(mlp_loss)

        paddle.seed(9)
        lenet = paddle.static.Program()
        with paddle.static.program_guard(lenet):
            lx = paddle.static.data("x", [None, 1, 28, 28], "float32")
            ly = paddle.static.data("y", [None], "int64")
            lenet_loss = F.cross_entropy(LeNet()(lx), ly)
            optimizer.Adam(learning_rate=1e-3).minimize(lenet_loss)

        for name, prog, loss, batch, hand in (
                ("static_mlp", mlp, mlp_loss, MLP_BATCH,
                 _mlp_hand_flops(MLP_BATCH)),
                ("static_lenet", lenet, lenet_loss, LENET_BATCH,
                 _lenet_hand_flops(LENET_BATCH))):
            rep = prog.analyze(fetch_list=[loss], batch_size=batch)
            reports[name] = rep
            got = rep.totals["flops_fwd"]
            rel = abs(got - hand) / hand
            if rel > 0.20:
                return _fail(
                    f"{name}: predicted fwd FLOPs {got} vs hand-counted "
                    f"{hand} ({rel:.1%} off, gate is 20%)")
            print(f"analyze_smoke: {name} fwd FLOPs {got} "
                  f"(hand {hand}, {rel:.2%} off)")
            un = rep.totals["unmodeled"]
            if un["count"] or un["bytes"]:
                return _fail(f"{name}: unmodeled bucket not empty: {un}")
            m = rep.memory
            if not m.peak_bytes_donated < m.peak_bytes_no_donation:
                return _fail(
                    f"{name}: donated peak {m.peak_bytes_donated} not "
                    f"strictly below no-donation bound "
                    f"{m.peak_bytes_no_donation}")
            print(f"analyze_smoke: {name} peak "
                  f"{m.peak_bytes_donated}B donated < "
                  f"{m.peak_bytes_no_donation}B no-donation")
            if not rep.fusion_candidates:
                return _fail(f"{name}: no fusion candidates ranked")
            if rep.fusion_candidates[0]["saved_bytes"] <= 0:
                return _fail(f"{name}: top fusion candidate saves "
                             f"nothing")
            bad = [d for d in rep.hazards
                   if d.severity in (Diagnostic.ERROR,
                                     Diagnostic.WARNING)]
            if bad:
                return _fail(f"{name}: hazard passes not clean: "
                             + "; ".join(str(d) for d in bad))
            # the JSON surface round-trips with the load-bearing keys
            d = json.loads(rep.to_json())
            for k in ("per_op", "totals", "memory", "roofline",
                      "fusion_candidates", "hazards"):
                if k not in d:
                    return _fail(f"{name}: to_json missing {k!r}")

        # -- CLI reproduces the in-process numbers ------------------------
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import analyze_program
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))
        with tempfile.TemporaryDirectory(prefix="analyze_smoke_") as td:
            script = os.path.join(td, "mlp_module.py")
            with open(script, "w") as f:
                f.write(_MLP_MODULE.format(hidden=MLP_HIDDEN,
                                           depth=MLP_DEPTH))
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = analyze_program.main(
                    [script, "--fetch", "loss", "--format", "json",
                     "--batch-size", str(MLP_BATCH)])
            if rc != 0:
                return _fail(f"analyze_program CLI exited {rc}")
            cli = json.loads(buf.getvalue())
            cli_main = next(
                (p for p in cli["programs"] if p["name"] == "main"), None)
            if cli_main is None:
                return _fail("CLI JSON has no report for 'main'")
            cli_flops = cli_main["report"]["totals"]["flops_fwd"]
            want = reports["static_mlp"].totals["flops_fwd"]
            if cli_flops != want:
                return _fail(f"CLI fwd FLOPs {cli_flops} != in-process "
                             f"{want}")
            print(f"analyze_smoke: CLI JSON parses, flops_fwd "
                  f"{cli_flops} == in-process")

        # -- the Executor records the same prediction per compile ---------
        exe = paddle.static.Executor()
        feed = {"x": np.zeros((MLP_BATCH, MLP_HIDDEN), np.float32),
                "y": np.zeros((MLP_BATCH, 1), np.float32)}
        exe.run(mlp, feed=feed, fetch_list=[mlp_loss])
        recs = [r for r in explain_compiles("executor")["records"]
                if r["identity"] == mlp._serial]
        if not recs or "predicted" not in recs[-1]:
            return _fail("executor compile record carries no "
                         "'predicted' cost summary")
        pred = recs[-1]["predicted"]
        want_fwd = reports["static_mlp"].totals["flops_fwd"]
        # the per-compile summary uses recorded avals (batch placeholder
        # 1); forward FLOPs scale linearly with the batch in this MLP,
        # so the batched report must be exactly batch x the compile one
        if pred["flops_fwd"] * MLP_BATCH != want_fwd:
            return _fail(
                f"executor-predicted fwd FLOPs {pred['flops_fwd']} x "
                f"batch {MLP_BATCH} != analyze() {want_fwd}")
        if monitor.get_stat("predicted.executor.flops") != pred["flops"]:
            return _fail("monitor gauge predicted.executor.flops not "
                         "set to the compile prediction")
        if pred["peak_bytes"] >= \
                reports["static_mlp"].memory.peak_bytes_no_donation:
            return _fail("executor-predicted donated peak not below "
                         "the no-donation bound")
        exe.close()
        print("analyze_smoke: executor compile carries predicted "
              f"flops={pred['flops']} peak_bytes={pred['peak_bytes']}")
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()

    print("analyze_smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
