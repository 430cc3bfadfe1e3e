"""Device time a step under the program's ``linear_cross_entropy`` scope
(the chunked vocabulary head and its loss), all phases."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, (scope_reduce.HEAD_LOSS,))
