"""``readers/grouped_dot_roofline.py`` for a layer of NON-gated experts: the
routed experts multiply their rows by two weight stacks (``w_in``, hidden x
expert width a group, and ``w_out``), so a layer runs 2 forward and 4 backward
grouped products, and the 2 forward again where the configuration's ``remat``
recomputes the block.  Everything else is the accepted reader's: the least time
from ``lib/grouped_dot.product_flops_bytes`` at the rows the program counted as
held, over the device time of the kernels (``custom-call``s) under the scope
``moe.experts``, or of the whole scope where it holds no kernel (the products
are then XLA's own fusions beside the shared expert's, which reads lower, never
higher); and nothing where the scope holds kernels and their count a layer is
not the products'."""

from lib import grouped_dot as gd, peaks as pk, phase_table as pt

FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 4


def read(ctx, entry, scope="moe.experts", kernel_kind="custom-call"):
    by_chip = pt.rows_by_chip(ctx, entry)
    model, held_rows = ctx.facts.get("model", {}), ctx.facts.get("moe_assignments_held")
    if (by_chip is None or not ctx.peaks or not held_rows
            or "hybrid_override_pattern" not in model):
        return None
    in_scope = [ev for ev, path, _, _ in by_chip[min(by_chip)] if scope in path]
    if not in_scope:
        return None
    kernels = [ev for ev in in_scope if ev[3].get("kind") == kernel_kind]
    layers = model["hybrid_override_pattern"].count("E")
    products = FORWARD_PRODUCTS + BACKWARD_PRODUCTS
    if model.get("remat"):
        products += FORWARD_PRODUCTS
    ran = len(kernels) / ctx.iterations / layers
    if kernels and ran != products:
        return None
    seconds = sum(ev[2] for ev in kernels or in_scope) / 1e9 / ctx.iterations
    flops, bytes_moved = gd.product_flops_bytes(
        held_rows / layers, model["hidden_size"], model["moe_intermediate_size"],
        model["n_routed_experts"])
    every = layers * products
    share = pk.roofline_share(every * flops, every * bytes_moved, seconds, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"], "ms_per_step": seconds * 1e3,
            "scope_ms_per_step": sum(ev[2] for ev in in_scope) / 1e6 / ctx.iterations,
            "kernels_per_layer": ran, "products_per_layer": products,
            "rows_per_layer": held_rows / layers}
