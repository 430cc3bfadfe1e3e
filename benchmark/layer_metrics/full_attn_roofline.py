"""Roofline time of what the full causal attention calls of a model with
window layers need a step (``full_attention_work`` of the cell's model
file: half the square), over the Mosaic time under the program's
``scaled_dot_product_attention`` scope OUTSIDE its ``window_attention``
scope, in percent (benchmark/flash_rooflines.py).  Nothing to read where
the step holds no such kernel or the model file no such function."""
import flash_rooflines


def read(ctx):
    if not hasattr(ctx["model"], "full_attention_work"):
        return None
    return flash_rooflines.read(ctx, "full_attn_roofline", False,
                                "full_attention_work")
