"""Device time a step under the program's ``rms_norm`` scope, all
phases (the EvaByte cell's nine norms: float32 statistics over the
float32 residual stream and their backward).  Nothing to read where the
step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("rms_norm",)) or None
