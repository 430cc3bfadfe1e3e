"""Device time a step under the program's ``rope`` scope, all phases
(the rotary embedding of q and k in every block, forward, replayed and
backward).  Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("rope",)) or None
