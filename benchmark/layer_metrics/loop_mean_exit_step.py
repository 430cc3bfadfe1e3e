"""The mean pass a token would leave a looped model after, as the
compiled step's own exit distribution has it: sum over t of t times
``loop.exit_share``[t] (the mean of p_t over a step's tokens, a float32
device counter in ``TrainStep``'s carry), averaged over every step of the
process (checked, warm-up and window).  Between 1 and the number of
passes: what the gate would let an adaptive exit skip, and the sign that
the gate collapsed if it reads the first or the last pass exactly.
Nothing to read on a program without device counters or a step without a
looped model's objective."""
import moe_counters

EXIT_SHARE = "loop.exit_share"        # [calls, passes]: the mean of p_t
EXIT_ENTROPY = "loop.exit_entropy"    # [calls]: the mean of H(p), nats


def read(ctx):
    log = ctx["log"]
    stats = moe_counters.registry(log, "loop_mean_exit_step")
    if stats is None:
        return None
    steps = stats.get(f"{EXIT_SHARE}.steps")
    if not steps:
        log(f"[loop_mean_exit_step] no step of this process counted an "
            f"exit distribution ({EXIT_SHARE}.steps = {steps}): nothing read")
        return None
    share = [total / steps
             for total in moe_counters.table(stats, EXIT_SHARE, "total")[0]]
    last = moe_counters.table(stats, EXIT_SHARE, "last")[0]
    entropy = moe_counters.table(stats, EXIT_ENTROPY, "total")[0][0] / steps
    log(f"[loop_mean_exit_step] {steps} steps; mean exit share a pass "
        + ", ".join(f"{s:.5f}" for s in share) + " (sum "
        f"{sum(share):.6f}); the newest step's "
        + ", ".join(f"{s:.5f}" for s in last)
        + f"; mean exit entropy {entropy:.5f} nats")
    return sum(t * s for t, s in enumerate(share, start=1))
