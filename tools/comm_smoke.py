#!/usr/bin/env python
"""Gradient-collective smoke gate: quantized grad_comm on multichip GPT.

The collective-efficiency AND compute-collective-overlap promises of
``paddle_tpu.distributed.grad_comm`` (ISSUE 10 + ISSUE 14 / ROADMAP
item 2), executably: the GPT-tiny causal LM from ``tools/shard_smoke``,
trained through ``fleet.distributed_optimizer`` + the static
``Executor`` on 8 virtual devices, eight configurations — fp32 wire
(the measured baseline), block-scaled int8 + error feedback with
``overlap="auto"``, the same int8 config with ``overlap="none"``
(comm barriered after backward), with ``overlap="ring"`` (the
ppermute-chunked lowering forced, so the explicit fallback path is
exercised end-to-end even on backends where auto picks the fused
form), on the hybrid ``{dp: 4, mp: 2}`` mesh with every 2-D weight
tensor-parallel (auto + none — forward mp gathers composed with the
dp reduction, ISSUE 17), and with ZeRO-3 (auto + none — params
dp-sharded at rest, grads reduce-scattered back to shards):

- **wire bytes**: int8 ``comm.wire_bytes``/step < 0.35x the fp32 run's
  (quantized payload + scales, both measured from monitor stats);
- **prediction closes**: measured wire bytes == the static cost model's
  ``predicted_wire_bytes`` exactly, in EVERY overlap mode — the plan is
  the single source of both numbers and the overlap lowering moves the
  same bytes; on hybrid/FSDP configs the same closure holds PER MESH
  AXIS (``comm.axis.<name>.wire_bytes`` == predicted
  ``axis_wire_bytes``) and for the forward param-gather schedule;
- **loss parity**: int8-with-error-feedback trajectories (ALL overlap
  modes — the ring's ascending accumulation keeps numerics) within
  2e-3 of the fp32 baseline after every step;
- **overlap**: median step time with ``overlap="auto"`` is at most
  1.15x max(compute, comm) estimated from the ``overlap="none"`` run's
  anatomy (compute = its measured step minus its predicted comm
  seconds) — at `none` the step pays compute + comm, at `auto` the
  wire hides behind backward;
- **exposed-vs-hidden split sanity**: the perf observatory reports
  hidden == 0 for the ``overlap="none"`` run (structural: the lowering
  barriers the stage) and a well-formed split for ``auto``;
- **0 steady-state recompiles** (one XLA compile per knob config),
  ``explain_compiles()`` reports no unexplained executor compiles, and
  every grad_comm compile record carries the auditable bucket schedule
  (size, algorithm, issue point, resolved overlap path);
- **bucketing + algorithm selection**: the small fuse budget forces
  multiple buckets, and every bucket records a psum/scatter choice.

Usage::

    python tools/comm_smoke.py [--steps 8] [--json] [--verbose]

``--json`` prints one JSON line (the exposed-vs-hidden split next to
the wire-byte ratio).  CI treats a non-zero exit as a regression.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# env BEFORE jax initialises: 8 virtual CPU devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from tools.shard_smoke import _feeds, build_gpt_tiny  # noqa: E402


_AXIS_STATS = ("comm.axis.dp.wire_bytes", "comm.axis.mp.wire_bytes",
               "comm.gather.wire_bytes", "comm.gather.collectives")


def _train(dtype, steps, overlap="auto", verbose=False,
           mesh_shape=None, zero3=False, mp_shard=False):
    """GPT-tiny on ``mesh_shape`` (default {dp: 8}) with the given
    grad_comm wire dtype and overlap mode.  ``zero3`` shards params
    over dp at rest (FSDP reduce-scatter grad route); ``mp_shard``
    shards every 2-D weight on its output dim over 'mp' (hybrid
    tensor-parallel gathers).  Returns a result dict (losses, wire
    stats incl. per-axis, prediction, per-step timing, perf comm
    split)."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist, optimizer
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.observability import (disable_perf, enable_perf,
                                          perf_report)
    from paddle_tpu.utils import monitor

    mesh_shape = dict(mesh_shape or {"dp": 8})
    init_mesh(mesh_shape)
    paddle.seed(7)
    main, loss, _ = build_gpt_tiny()
    with paddle.static.program_guard(main):
        f = dist.fleet
        strategy = dist.DistributedStrategy()
        # small fuse budget -> several buckets (overlap-shaped), low
        # threshold -> the big buckets take the bandwidth route
        strategy.fuse_grad_size_in_MB = 0.05
        strategy.grad_comm = {"dtype": dtype, "error_feedback": True,
                              "block_size": 256,
                              "scatter_threshold_KB": 4.0,
                              "overlap": overlap}
        if zero3:
            strategy.sharding = True
            strategy.sharding_configs = {"stage": 3,
                                         "min_shard_numel": 1}
        f.init(is_collective=True, strategy=strategy)
        opt = f.distributed_optimizer(optimizer.AdamW(learning_rate=1e-3))
        opt.minimize(loss)
    init_mesh(mesh_shape)  # fleet.init infers over ALL devices; pin it
    if mp_shard:
        # every 2-D weight tensor-parallel on its output dim; 1-D
        # params (biases, norms) replicate via the fallback rule
        main._sharding_rules = [
            (re.escape(p.name) + "$", (None, "mp"))
            for p in main.parameters() if len(p.data.shape) == 2
        ] + [(r".*", ())]
    exe = paddle.static.Executor()
    feed = _feeds("gpt")
    # fence every step: exposed-vs-hidden needs the device wall, and
    # this harness reads the fetch per step anyway
    enable_perf(sample_every=1, memory=False)
    w0 = monitor.get_stat("comm.wire_bytes") or 0
    c0 = monitor.get_stat("comm.collectives") or 0
    ax0 = {k: monitor.get_stat(k) or 0 for k in _AXIS_STATS}
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])]
    step_s = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        losses.append(float(exe.run(main, feed=feed,
                                    fetch_list=[loss])[0]))
        step_s.append(time.perf_counter() - t0)
    wire = ((monitor.get_stat("comm.wire_bytes") or 0) - w0) / steps
    colls = ((monitor.get_stat("comm.collectives") or 0) - c0) / steps
    ax = {k: ((monitor.get_stat(k) or 0) - ax0[k]) / steps
          for k in _AXIS_STATS}
    measured_axis = {k.split(".")[2]: v for k, v in ax.items()
                     if k.startswith("comm.axis.") and v}
    plan = exe._plan_for(main, main.parameters())
    rep = main.analyze(fetch_list=[loss], sharding=plan)
    comm = rep.totals["comm"]
    from paddle_tpu.static.analysis.cost import compile_summary
    cs = compile_summary(main, sharding=plan)
    # the executor identity's comm split as the observatory learned it
    perf = perf_report()
    split = next((r.get("comm") for r in perf.get("identities", [])
                  if r["component"] == "executor" and r.get("comm")),
                 None)
    disable_perf()
    state = exe._states[main._serial]
    out = {
        "losses": losses,
        "compiles": exe.compile_count,
        "wire_bytes_per_step": wire,
        "collectives_per_step": colls,
        "predicted_wire_bytes": comm["wire_bytes_per_step"],
        "predicted_fp32_wire_bytes": comm["fp32_wire_bytes_per_step"],
        "predicted_comm_s": cs.get("predicted_comm_s", 0.0),
        "axis_wire_bytes_per_step": measured_axis,
        "predicted_axis_wire_bytes": dict(
            comm.get("axis_wire_bytes") or {}),
        "gather_wire_bytes_per_step": ax["comm.gather.wire_bytes"],
        "predicted_gather_wire_bytes": comm.get(
            "gather_wire_bytes_per_step", 0),
        "gather_collectives_per_step": ax["comm.gather.collectives"],
        "peak_bytes_per_shard": cs.get("peak_bytes_per_shard"),
        "mesh_shape": mesh_shape,
        "overlap": overlap,
        "overlap_path": comm.get("overlap_path"),
        "buckets": len(comm["collectives"]),
        "algorithms": sorted({c["algorithm"]
                              for c in comm["collectives"]}),
        "residual_buckets": len(state.aux.get("grad_comm", [])),
        # anomaly sentry (FLAGS_anomaly_sentry, compiled into the
        # step): clean training must never skip — a false positive
        # here would silently stall convergence
        "sentry_skipped_steps": (exe.sentry_stats(main)
                                 or {}).get("skipped_steps"),
        "step_ms_median": statistics.median(step_s) * 1e3,
        # the overlap gate compares MINIMA: on oversubscribed CI hosts
        # the 8 virtual devices' thread scheduling adds multi-ms noise
        # to individual steps (measured +-35% between identical runs);
        # additive noise never makes a step faster, so the min is the
        # honest estimate of what the schedule costs
        "step_ms_min": min(step_s) * 1e3,
        "steps_per_sec": (steps - 1) / max(sum(step_s), 1e-9),
        "perf_comm": split,
    }
    if verbose:
        print(f"  {dtype}/{overlap}->{out['overlap_path']}: losses "
              f"{['%.4f' % v for v in losses]} wire {wire:.0f}B/step "
              f"({out['buckets']} buckets, {out['algorithms']}), "
              f"step {out['step_ms_median']:.2f} ms")
    exe.close()
    paddle.static.reset_default_programs()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Gradient-collective smoke gate: quantized grad_comm"
                    " + compute-collective overlap on multichip GPT.")
    ap.add_argument("--steps", type=int, default=16,
                    help="steps per config (>= 2: the first run compiles"
                         " and is excluded from the step timings)")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON result line on stdout")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.steps < 2:
        ap.error("--steps must be >= 2 (step 1 compiles; the timing "
                 "gates need at least one steady-state step)")

    import paddle_tpu as paddle
    from paddle_tpu.observability import explain_compiles

    problems = []
    paddle.enable_static()
    # the multichip suite runs as production would: with the anomaly
    # sentry compiled into every step — the overlap/wire gates then
    # also prove the sentry costs no recompiles and never false-fires
    old_sentry = paddle.get_flags("anomaly_sentry")
    paddle.set_flags({"anomaly_sentry": True})
    try:
        fp32 = _train("fp32", args.steps, verbose=args.verbose)
        int8 = _train("int8", args.steps, verbose=args.verbose)
        none = _train("int8", args.steps, overlap="none",
                      verbose=args.verbose)
        ring = _train("int8", args.steps, overlap="ring",
                      verbose=args.verbose)
        # hybrid {dp, mp}: every 2-D weight mp-sharded, forward param
        # gathers + bucketed dp reduction composed in one shard_map
        hyb = _train("int8", args.steps, verbose=args.verbose,
                     mesh_shape={"dp": 4, "mp": 2}, mp_shard=True)
        hyb_none = _train("int8", args.steps, overlap="none",
                          verbose=args.verbose,
                          mesh_shape={"dp": 4, "mp": 2}, mp_shard=True)
        # ZeRO-3: params sharded at rest, grads reduce-scatter back
        z3 = _train("int8", args.steps, zero3=True,
                    verbose=args.verbose)
        z3_none = _train("int8", args.steps, overlap="none", zero3=True,
                         verbose=args.verbose)
    finally:
        paddle.set_flags(old_sentry)
        paddle.disable_static()

    runs = (("fp32", fp32), ("int8", int8), ("int8/none", none),
            ("int8/ring", ring), ("hybrid", hyb),
            ("hybrid/none", hyb_none), ("zero3", z3),
            ("zero3/none", z3_none))
    for name, r in runs:
        if r["compiles"] != 1:
            problems.append(f"{name}: {r['compiles']} compiles for one "
                            f"feed signature — recompiles after warmup")
        if r["wire_bytes_per_step"] != r["predicted_wire_bytes"]:
            problems.append(
                f"{name}: measured wire bytes/step "
                f"{r['wire_bytes_per_step']} != predicted "
                f"{r['predicted_wire_bytes']} — the cost model and the "
                f"runtime disagree")
        if r["axis_wire_bytes_per_step"] != r["predicted_axis_wire_bytes"]:
            problems.append(
                f"{name}: per-axis wire bytes/step "
                f"{r['axis_wire_bytes_per_step']} != predicted "
                f"{r['predicted_axis_wire_bytes']} — an axis is "
                f"unaccounted")
        if r["gather_wire_bytes_per_step"] != \
                r["predicted_gather_wire_bytes"]:
            problems.append(
                f"{name}: forward gather bytes/step "
                f"{r['gather_wire_bytes_per_step']} != predicted "
                f"{r['predicted_gather_wire_bytes']}")
        if r["sentry_skipped_steps"] != 0:
            problems.append(
                f"{name}: anomaly sentry skipped "
                f"{r['sentry_skipped_steps']} step(s) of a CLEAN run "
                f"(false positive — or the sentry carry is missing)")
    # hybrid: the mp axis must actually carry gather traffic
    if "mp" not in hyb["axis_wire_bytes_per_step"]:
        problems.append("hybrid: no wire bytes measured on the 'mp' "
                        "axis — the tensor-parallel gathers did not run")
    if hyb["gather_collectives_per_step"] <= 0:
        problems.append("hybrid: no forward param gathers measured")
    # zero3: the FSDP route must be selected, and sharding params at
    # rest must shrink what one chip holds vs the replicated run
    if "rscatter" not in z3["algorithms"]:
        problems.append(f"zero3: no rscatter bucket in "
                        f"{z3['algorithms']} — the FSDP reduce-scatter "
                        f"route was not planned")
    if not (z3["peak_bytes_per_shard"] and int8["peak_bytes_per_shard"]
            and z3["peak_bytes_per_shard"]
            < int8["peak_bytes_per_shard"]):
        problems.append(
            f"zero3: peak_bytes_per_shard "
            f"{z3['peak_bytes_per_shard']} is not below the replicated "
            f"run's {int8['peak_bytes_per_shard']} — params are not "
            f"sharded at rest")
    ratio = int8["wire_bytes_per_step"] / max(fp32["wire_bytes_per_step"],
                                              1)
    if ratio >= 0.35:
        problems.append(f"int8 wire bytes are {ratio:.3f}x of fp32 "
                        f"(gate: < 0.35x)")
    delta = max(abs(a - b) for run in (int8, none, ring, hyb, hyb_none,
                                       z3, z3_none)
                for a, b in zip(fp32["losses"], run["losses"]))
    if delta > 2e-3:
        problems.append(f"int8+error-feedback loss trajectory diverges "
                        f"{delta:.2e} from fp32 (gate: <= 2e-3, all "
                        f"overlap modes AND axis layouts — hybrid/FSDP "
                        f"included)")
    if int8["buckets"] < 2:
        problems.append("fuse_grad_size_in_MB did not produce multiple "
                        "buckets — bucketing is inert")
    if int8["residual_buckets"] < 1:
        problems.append("error feedback on but no residual carry in the "
                        "donated state")

    # overlap gate: auto approaches max(compute, comm) estimated from
    # the none run's anatomy (its step = compute + comm by construction)
    # — on every axis layout, not just pure dp
    def overlap_gate(label, auto_r, none_r, slack=1.15):
        comm_s = none_r["predicted_comm_s"]
        none_s = none_r["step_ms_min"] / 1e3
        auto_s = auto_r["step_ms_min"] / 1e3
        compute_est = max(none_s - comm_s, 0.0)
        bound_s = slack * max(compute_est, comm_s)
        if auto_s > bound_s:
            problems.append(
                f"{label}: overlap=auto step {auto_s * 1e3:.2f} ms "
                f"exceeds {slack}x max(compute "
                f"{compute_est * 1e3:.2f}, comm {comm_s * 1e3:.2f}) = "
                f"{bound_s * 1e3:.2f} ms from the overlap=none "
                f"anatomy — the wire is not hiding")
        return auto_s, none_s, bound_s, comm_s

    auto_s, none_s, bound_s, comm_s = overlap_gate("dp", int8, none)
    # the hybrid/zero3 overlap gates share the anatomy check but run
    # with a looser multiplier: on the CPU smoke their comm term is
    # microseconds, so the bound degenerates to comparing two noisy
    # step minima — the precise 1.15x gate is already enforced on the
    # dp pair above, and the per-axis wire gates are exact regardless
    overlap_gate("hybrid", hyb, hyb_none, slack=1.6)
    overlap_gate("zero3", z3, z3_none, slack=1.6)
    if none["overlap_path"] != "none":
        problems.append(f"overlap='none' resolved to path "
                        f"{none['overlap_path']!r}")
    if int8["overlap_path"] not in ("xla", "ring"):
        problems.append(f"overlap='auto' resolved to path "
                        f"{int8['overlap_path']!r} — no overlap lowering")
    if ring["overlap_path"] != "ring":
        problems.append(f"overlap='ring' resolved to path "
                        f"{ring['overlap_path']!r} — the forced chunked "
                        f"lowering did not run")
    ns = none.get("perf_comm")
    if not ns:
        problems.append("perf observatory reported no comm split for "
                        "the overlap=none run")
    elif ns["hidden_ms"] != 0.0:
        problems.append(f"overlap=none hidden comm {ns['hidden_ms']} ms "
                        f"!= 0 — the split must be structural at none")
    if not int8.get("perf_comm"):
        problems.append("perf observatory reported no comm split for "
                        "the overlap=auto run")

    ec = explain_compiles("executor")
    unex = ec["by_cause"].get("executor.unexplained", 0)
    if unex:
        problems.append(f"{unex} unexplained executor compile(s)")
    scheduled = [r for r in ec["records"]
                 if r.get("comm", {}).get("buckets")]
    if len(scheduled) < 8:
        problems.append(f"only {len(scheduled)} executor compile "
                        f"record(s) carry the grad_comm bucket schedule "
                        f"(expected 8 — overlap decisions must be "
                        f"auditable on every axis layout)")

    result = {
        "metric": "multichip_gpt_int8_wire_ratio_vs_fp32",
        "value": round(ratio, 4),
        "unit": "x (lower is better; gate < 0.35)",
        "loss_delta_max": delta,
        "steps": args.steps,
        "fp32": {k: v for k, v in fp32.items() if k != "losses"},
        "int8": {k: v for k, v in int8.items() if k != "losses"},
        "int8_overlap_none": {k: v for k, v in none.items()
                              if k != "losses"},
        "int8_overlap_ring": {k: v for k, v in ring.items()
                              if k != "losses"},
        "hybrid_dp4_mp2": {k: v for k, v in hyb.items()
                           if k != "losses"},
        "hybrid_dp4_mp2_none": {k: v for k, v in hyb_none.items()
                                if k != "losses"},
        "zero3": {k: v for k, v in z3.items() if k != "losses"},
        "zero3_none": {k: v for k, v in z3_none.items()
                       if k != "losses"},
        "overlap_gate": {
            "auto_step_ms": round(auto_s * 1e3, 3),  # min over steps
            "none_step_ms": round(none_s * 1e3, 3),
            "predicted_comm_ms": round(comm_s * 1e3, 6),
            "bound_ms": round(bound_s * 1e3, 3),
            "auto_path": int8["overlap_path"],
            "exposed_hidden_auto": int8.get("perf_comm"),
            "exposed_hidden_none": none.get("perf_comm"),
        },
        "ok": not problems,
    }
    if args.json:
        print(json.dumps(result))
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    if not args.json:
        print(f"comm_smoke OK: int8 grad_comm wire bytes {ratio:.3f}x "
              f"of fp32 ({int8['wire_bytes_per_step']:.0f} vs "
              f"{fp32['wire_bytes_per_step']:.0f} B/step, predicted "
              f"exactly in every overlap mode), loss parity {delta:.1e} "
              f"<= 2e-3 with error feedback, {int8['buckets']} buckets "
              f"{int8['algorithms']}, overlap auto->"
              f"{int8['overlap_path']} step {auto_s * 1e3:.2f} ms <= "
              f"{bound_s * 1e3:.2f} ms bound (none: "
              f"{none_s * 1e3:.2f} ms), hidden==0 at none, 1 compile "
              f"each, schedules on all records; hybrid {{dp:4, mp:2}} "
              f"per-axis B/step {hyb['axis_wire_bytes_per_step']} == "
              f"predicted with "
              f"{hyb['gather_collectives_per_step']:.0f} gather(s)/"
              f"step; zero3 {z3['algorithms']} per-shard peak "
              f"{z3['peak_bytes_per_shard']} < replicated "
              f"{int8['peak_bytes_per_shard']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
