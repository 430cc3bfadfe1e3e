#!/usr/bin/env python3
"""How far a routing decision survives a lower precision: the share of
(token, slot) assignments of the keye_vl2 reference's forward pass that
stay the same, layer by layer, when every matmul operand is rounded to
bfloat16 (the noise a bfloat16 O2 program carries) and to fp8 (the
control's).  Routing is discrete: a token whose 8th and 9th expert lie
closer than the noise swaps one, and an expert's gradient then differs in
those tokens.  Also printed: how many assignments of the row fall on the
held experts, layer by layer.  Read once, on the chip, when the cell's
limits are set (PERF.md has the reading); not part of a benchmark run.

    python3 benchmark/routing_agreement.py --workload <cell> --seeds 1,2
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse and not harness.has_chips(args.workload):
        return 2
    harness.place_cache()
    import jax
    import jax.numpy as jnp
    import check
    cell, cfg, mix, _, ref, _ = harness.load_parts(
        args.workload, rehearse=args.rehearse)
    K = cfg["num_experts_per_tok"]

    def bf16(a):
        return jax.lax.reduce_precision(a, 8, 7)

    def chosen(qz):
        def one_row(params, ids):
            return ref.forward(params, ids[None], cfg, qz)[2][:, 0]
        return jax.jit(one_row)

    with jax.default_matmul_precision("highest"):
        runs = {"float32": chosen(lambda a: a), "bfloat16": chosen(bf16),
                "fp8": chosen(check._fp8)}
        for seed in (int(x) for x in args.seeds.split(",") if x):
            ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed)
            params, ids = theta0(), jnp.asarray(ring[0][0][0])
            got = {n: jnp.sort(f(params, ids), -1) for n, f in runs.items()}
            # the load of the held experts: a step's work follows it, so
            # it must not follow the seed (PERF.md section 6, PR 30)
            held = jnp.asarray(ref.held_ids(cfg))
            load = (got["float32"][..., None] == held).any(-1).sum((1, 2))
            even = ids.size * K * held.size // cfg["published"]["num_experts"]
            harness.log(
                f"[routing] seed {seed} float32, layer by layer: assignments "
                f"to the {held.size} held experts "
                + " ".join(str(int(x)) for x in load)
                + f" (an even router sends {even})")
            for name in ("bfloat16", "fp8"):
                # slots shared by the two sets of K experts of a token
                same = (got[name][..., :, None]
                        == got["float32"][..., None, :]).any(-1)
                share = same.mean((1, 2))
                whole = same.all(-1).mean(1)
                harness.log(
                    f"[routing] seed {seed} {name} against float32, layer "
                    "by layer: assignments that agree "
                    + " ".join(f"{float(x):.4f}" for x in share)
                    + f" (of {K} a token); tokens whose whole set agrees "
                    + " ".join(f"{float(x):.4f}" for x in whole))
    return 0


if __name__ == "__main__":
    sys.exit(main())
