"""Device time a step under the program's ``loop_exit`` scope, all
phases: a looped model's exit gate (a product and a sum over each exit
state), the exit distribution, its entropy and the weights the chunked
head takes, with their backward.  The head itself stays under
``linear_cross_entropy`` (``head_loss_ms``).  Nothing to read where the
step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("loop_exit",)) or None
