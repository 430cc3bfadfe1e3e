"""Device busy time inside the traced executions of the step's
executable, over their number.  From the device trace alone."""


def read(ctx):
    t = ctx["trace"]
    if not t["module_runs"]:
        return None
    return t["busy_s"] / t["module_runs"] * 1000
