"""Device milliseconds per harness iteration of the instructions whose name
matches ``pattern`` (a Mosaic kernel's own name), on the first chip."""

from lib import trace as tr


def read(ctx, pattern):
    if not ctx.ops or not ctx.stretch or not ctx.iterations:
        return None
    seconds, calls = tr.kernel_seconds(ctx.ops[min(ctx.ops)], pattern, *ctx.stretch)
    if not calls:
        return None
    return {"value": seconds * 1e3 / ctx.iterations, "calls_per_iteration": calls / ctx.iterations}
