"""Milliseconds per step in collective operations during which no other
operation runs on that device, the worst chip."""

from lib import trace as tr


def read(ctx):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or len(ctx.ops) < 2:
        return None
    lo, hi = ctx.stretch
    per_chip = {chip: tr.exposed_collective_seconds(ev, tr.async_spans(ctx.trace, chip), lo, hi)
                * 1e3 / ctx.iterations for chip, ev in ctx.ops.items()}
    return {"value": max(per_chip.values()), "per_chip": per_chip}
