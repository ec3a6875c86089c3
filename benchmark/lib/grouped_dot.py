"""Operations and bytes of the grouped matrix products of a routed-expert
layer's training step, from shapes and from the rows the program counted.

A SwiGLU expert layer multiplies the rows routed to the experts held by three
weight stacks: ``gate`` and ``up`` (hidden x expert width a group) and ``down``
(expert width x hidden).  Each forward product has two products in the
backward pass, one for the rows' gradient and one for the weights', of the
same ``2 * rows * K * N`` operations: 3 forward and 6 backward a layer, and the
3 forward again where the block is rematerialized.  The rows are those that
landed on an expert held here (the program's ``moe_assignments_held``), not
the rows of the buffer they sit in."""

FORWARD_PRODUCTS = 3          # gate, up, down
BACKWARD_PRODUCTS = 6         # each one's rows' gradient and weights' gradient


def product_flops_bytes(rows: float, hidden: int, width: int, held: int,
                        dtype_bytes: int = 2) -> tuple:
    """One grouped product over ``rows`` rows in ``held`` groups: a multiply-add
    counts as two; bytes are the held experts' weight stack once (read, or for a
    weights' gradient written) and every row once on its way in and out, all in
    the compute type: the least any implementation moves."""
    return (2.0 * rows * hidden * width,
            (held * hidden * width + rows * (hidden + width)) * dtype_bytes)

