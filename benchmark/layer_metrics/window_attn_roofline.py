"""Roofline time of what the sliding-window attention calls need a step
(``window_attention_work`` of the cell's model file: the band's pairs
``W L - W (W - 1) / 2`` a head, two products a forward and five a
backward, q, k, v, out and their gradients once), over the Mosaic time
under the program's ``window_attention`` scope, in percent
(benchmark/flash_rooflines.py).  Nothing to read where the step holds no
such scope."""
import flash_rooflines


def read(ctx):
    return flash_rooflines.read(ctx, "window_attn_roofline", True,
                                "window_attention_work")
