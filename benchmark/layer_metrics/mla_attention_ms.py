"""Device time a step under the program's ``mla_attention`` scope, all
phases: the flash kernels over 128 + 64 key lanes (the 64 one rotated
key a position for all heads, staged once a row) and 128-wide values,
the layout changes around the kernels and their transposes, the sum of
the heads' parts of the shared key's gradient; in a program that
broadcasts that key to the heads and joins it to theirs, that too.
Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("mla_attention",)) or None
