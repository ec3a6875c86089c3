"""Share of the HBM roofline the fused Adam kernel reaches: every state and
gradient byte read and written once (lib/flops.py) over its device time."""

from lib import flops, peaks as pk, trace as tr


def read(ctx, pattern="_adam_flat", grad_bytes=4):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    seconds, calls = tr.kernel_seconds(ctx.ops[min(ctx.ops)], pattern, *ctx.stretch)
    if not calls:
        return None
    by = flops.adam_bytes(ctx.facts["n_params"], grad_bytes=grad_bytes)
    share = pk.roofline_share(10.0 * ctx.facts["n_params"], by, seconds / ctx.iterations, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"],
            "ms_per_step": seconds * 1e3 / ctx.iterations}
