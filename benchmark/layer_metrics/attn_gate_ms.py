"""Device time a step under the program's ``attn_gate`` scope, all
phases: the sigmoid gate on attention's output
(``F.attention_output_gate``: the sigmoid, the product, their replay and
their backward; the gate's projection is a ``Linear`` outside it).
Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("attn_gate",)) or None
