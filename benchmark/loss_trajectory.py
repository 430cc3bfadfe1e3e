#!/usr/bin/env python3
"""The keye_vl2 reference's loss, part by part, over more steps than the
check follows, beside the program's: the float32 reference is trained
through ``--steps`` batches of the ring (``check.follow``, the cell's own
optimizer) and reports each step's cross-entropy and each layer's
indexer KL term; then the cell's step object is driven through the same
batches.  It shows whether the indexer's loss rises in the mathematics
itself while the ring is memorised, and whether the program still
follows the reference where the two-step check no longer looks.  Read
once, on the chip (PERF.md has the reading); not part of a benchmark run.

    python3 benchmark/loss_trajectory.py --workload <cell> --seed 1 --steps 32
"""
import argparse
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse and not harness.has_chips(args.workload):
        return 2
    harness.place_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import check
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        args.workload, rehearse=args.rehearse)
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, args.seed)
    batches = [ring[i % len(ring)] for i in range(args.steps)]
    block_rows = min(cell["check"]["reference_block_rows"], mix["batch"])
    blocks = mix["batch"] // block_rows
    parts = []          # a (cross-entropy, [L] KL terms) a block of rows

    def loss(params, ids, labels, cfg, variant, qz):
        """``ref.loss``, its two parts also sent to the host."""
        z, kl, _ = ref.forward(params, ids, cfg, qz)
        logp = jax.nn.log_softmax(
            (qz(z) @ qz(params["head.w"])).astype(jnp.float32), axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
        jax.debug.callback(
            lambda ce, kl: parts.append((float(ce), np.asarray(kl))), ce, kl)
        return ce + jnp.mean(kl)

    want = check.follow(types.SimpleNamespace(loss=loss), cfg,
                        cell["model_args"], theta0, batches,
                        cell["optimizer"], block_rows)
    jax.effects_barrier()
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    got = [float(runner.dispatch(state, runner.feed(state, *b)))
           for b in batches]
    runner.close(state)
    for t in range(args.steps):
        rows = parts[t * blocks:(t + 1) * blocks]
        ce = float(np.mean([r[0] for r in rows]))
        kl = np.mean([r[1] for r in rows], 0)
        followed = want["losses"][t]
        harness.log(
            f"[trajectory] seed {args.seed} step {t + 1}: reference "
            f"cross-entropy {ce:.5f}, KL by layer "
            + " ".join(f"{x:.5f}" for x in kl)
            + f", + mean {ce + kl.mean():.5f} (followed {followed:.5f}), "
            f"+ sum {ce + kl.sum():.5f}; program {got[t]:.5f}, gap "
            f"{abs(got[t] - followed) / abs(followed):.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
