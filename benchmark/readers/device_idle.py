"""Idle share of the device over the traced steady stretch: 1 - (union of the
intervals in which an operation ran) / stretch, per chip, the worst chip."""

from lib import trace as tr


def read(ctx):
    if not ctx.ops or not ctx.stretch:
        return None
    lo, hi = ctx.stretch
    idle = {chip: 100.0 * (1.0 - tr.busy(ev, lo, hi) / (hi - lo)) for chip, ev in ctx.ops.items()}
    return {"value": max(idle.values()), "per_chip": idle}
