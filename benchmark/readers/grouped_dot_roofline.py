"""Share of the roofline the routed experts' grouped matrix products reach in
a training step: the least time the chip could take for them (lib/grouped_dot.py:
operations from the rows the program counted as held, bytes the held experts'
weights once a product plus the rows) over the device time of the kernels the
program ran them in.

The kernels are found by the program's scope ``moe.experts`` (lib/phase_table.py:
a kernel the compiler names itself, ``ragged-dot-none``, is joined to the scope
through its operands and readers), never by a kernel's name, so that whatever
implements the products is read by the same yardstick: of the operations under
the scope, the ``custom-call``s (the compiler's own grouped-product kernel
today, a Mosaic kernel tomorrow).  Where the scope holds no kernel at all (the
products are XLA's own fusions and cannot be told from the elementwise work
beside them) the whole scope's time is taken, which reads lower, never higher.

How many products a layer ran is a fact of the cell, not of the trace: what
the mathematics requires (3 forward + 6 backward), and the forward once more
where the configuration's ``remat`` recomputes the block.  The trace has to
agree: where the scope holds kernels and their count a layer is another (one
that fuses two products, one that only prepares tiles, some products left to
XLA's fusions), the operations and the time are no longer of the same work and
nothing is read."""

from lib import grouped_dot as gd, peaks as pk, phase_table as pt


def read(ctx, entry, scope="moe.experts", kernel_kind="custom-call"):
    by_chip = pt.rows_by_chip(ctx, entry)
    model, held_rows = ctx.facts.get("model", {}), ctx.facts.get("moe_assignments_held")
    if by_chip is None or not ctx.peaks or not held_rows or "mlp_layer_types" not in model:
        return None
    in_scope = [ev for ev, path, _, _ in by_chip[min(by_chip)] if scope in path]
    if not in_scope:
        return None
    kernels = [ev for ev in in_scope if ev[3].get("kind") == kernel_kind]
    layers = model["mlp_layer_types"].count("sparse")
    products = gd.FORWARD_PRODUCTS + gd.BACKWARD_PRODUCTS
    if model.get("remat"):
        products += gd.FORWARD_PRODUCTS
    ran = len(kernels) / ctx.iterations / layers
    if kernels and ran != products:
        return None
    seconds = sum(ev[2] for ev in kernels or in_scope) / 1e9 / ctx.iterations
    flops, bytes_moved = gd.product_flops_bytes(
        held_rows / layers, model["hidden_size"], model["moe_intermediate_size"],
        model["num_experts"])
    every = layers * products            # each product of a SwiGLU expert layer costs the same
    share = pk.roofline_share(every * flops, every * bytes_moved, seconds, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"], "ms_per_step": seconds * 1e3,
            "scope_ms_per_step": sum(ev[2] for ev in in_scope) / 1e6 / ctx.iterations,
            "kernels_per_layer": ran, "products_per_layer": products,
            "rows_per_layer": held_rows / layers}
