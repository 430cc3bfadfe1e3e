"""The fullest held expert's load over the held experts' mean load, an
expert layer (``moe.fullest_expert_load`` over ``moe.expert_load``, both
counted by the compiled step and summed over every step of the process),
mean over the layers: 1 where the router spreads its tokens evenly over
the held experts.  The grouped products run at the pace of their largest
group's tiles, and an expert-parallel group waits for its fullest member.
Nothing to read on a program without device counters or a step without an
expert layer."""
import statistics

import moe_counters


def read(ctx):
    got = moe_counters.loads(ctx, "moe_load_imbalance")
    if got is None:
        return None
    ratios = [fullest * len(load) / sum(load)
              for fullest, load in zip(got["fullest"], got["load"])
              if sum(load)]
    if not ratios:
        ctx["log"]("[moe_load_imbalance] no held expert got an assignment")
        return None
    ctx["log"]("[moe_load_imbalance] a layer: "
               + ", ".join(f"{r:.4f}" for r in ratios))
    return statistics.mean(ratios)
