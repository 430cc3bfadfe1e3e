"""FLOPs one step needs (``train_flops_per_token`` of the cell's model
file; recomputed operations not counted) over the traced device step
time (as ``device_step_ms`` reads it) times the chip's bf16 peak, in
percent."""


def read(ctx):
    t, mix = ctx["trace"], ctx["mix"]
    if not t["module_runs"]:
        return None
    flops = (ctx["model"].train_flops_per_token(ctx["cfg"], mix["seq"])
             * mix["batch"] * mix["seq"])
    step_s = t["busy_s"] / t["module_runs"]
    return flops / step_s / ctx["peaks"]["bf16_flops_per_s"] * 100
