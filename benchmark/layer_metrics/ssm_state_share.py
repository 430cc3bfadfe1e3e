"""How much of the scan's output the recurrence carries: the RMS of the
state's part ``S_t C_t`` over the RMS of all of ``y_t = S_t C_t + D x_t``
(``ssm.state_share``, a float32 device counter a call of the mixer in
``TrainStep``'s carry), mean over the state-space layers and every step
of the process (checked, warm-up and window).  Near 0 the scan is
``D x`` and a comparison with the reference does not see the
recurrence; the log line gives each layer's share and its mean decay
``exp(dt A)`` (``ssm.mean_decay``: near 1 the state never moves, near 0
it forgets inside a chunk).  Nothing to read on a program without device
counters or a step without a state-space layer."""
import statistics

import moe_counters

STATE_SHARE = "ssm.state_share"       # [calls]: a state-space layer each
MEAN_DECAY = "ssm.mean_decay"         # [calls]


def read(ctx):
    log = ctx["log"]
    stats = moe_counters.registry(log, "ssm_state_share")
    if stats is None:
        return None
    steps = stats.get(f"{STATE_SHARE}.steps")
    if not steps:
        log(f"[ssm_state_share] no step of this process counted a "
            f"state-space layer ({STATE_SHARE}.steps = {steps}): nothing "
            "read")
        return None

    def means(name):
        return [row[0] / steps
                for row in moe_counters.table(stats, name, "total")]

    share, decay = means(STATE_SHARE), means(MEAN_DECAY)
    log(f"[ssm_state_share] {steps} steps; a layer, state share "
        + ", ".join(f"{s:.4f}" for s in share) + "; mean decay "
        + ", ".join(f"{d:.4f}" for d in decay))
    return statistics.mean(share)
