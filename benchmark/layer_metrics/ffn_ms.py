"""Device time a step under the program's ``ffn`` scope, all phases: the
gated feed-forward layers whole (``nn.GatedFFN``: the fused
in-projection, the gate's activation and the product, the
out-projection) with their replay and their backward.  Nothing to read
where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("ffn",)) or None
