"""1 - union of device-op intervals over the traced window, in percent.
From the device trace alone."""


def read(ctx):
    t = ctx["trace"]
    return (1 - t["busy_s"] / t["window_s"]) * 100
