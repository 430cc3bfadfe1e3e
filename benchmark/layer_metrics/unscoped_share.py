"""Device time a step that no scope of the program claims (instructions
without ``op_name``, or under jax's wrappers alone), over the device step
time as ``device_step_ms`` reads it, in percent."""
import scope_reduce


def read(ctx):
    loose = scope_reduce.phase_ms(ctx, "unscoped")
    if loose is None:
        return None
    t = ctx["trace"]
    return loose / (t["busy_s"] / t["module_runs"] * 1000) * 100
