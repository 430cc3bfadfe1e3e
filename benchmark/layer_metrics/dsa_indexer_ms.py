"""Device time a step under the program's ``dsa_indexer`` and
``dsa_select`` scopes, all phases: the index scores, the top-k threshold
and the mask, the indexer's KL loss (its own QK pass over all heads) and
that loss's gradient.  The indexer's three projections are the layers',
not its.  Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(
        ctx, ("dsa_indexer", "dsa_select")) or None
