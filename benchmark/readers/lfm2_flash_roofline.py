"""Share of the roofline the flash-attention kernels reach in the ``lfm2_moe``
training step, whose attention layers are some of its layers: the least time
the chip could take for flash_fwd + flash_dq + flash_dkv (FLOPs of the visible
pairs and bytes from shapes with K/V at the heads the kernels are given,
lib/lfm2_flops.py, the forward counted as often as the trace shows it ran)
over their device time in the trace."""

from lib import lfm2_flops, peaks as pk, trace as tr


def read(ctx, pattern="flash_(fwd|dq|dkv)"):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    events = ctx.ops[min(ctx.ops)]
    seconds, calls = tr.kernel_seconds(events, pattern, *ctx.stretch)
    m, f = ctx.facts.get("model", {}), ctx.facts
    layers = m.get("layer_types", []).count(lfm2_flops.FULL)
    if not calls or not layers or "conv_L_cache" not in m:
        return None
    _, forwards = tr.kernel_seconds(events, "flash_fwd", *ctx.stretch)
    per_layer = forwards / ctx.iterations / layers
    fl, by = lfm2_flops.flash_train_flops_bytes(m, f["rows_per_step"] // ctx.cell.chips,
                                                f["seq_len"], forward_calls=per_layer)
    share = pk.roofline_share(fl, by, seconds / ctx.iterations, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"],
            "ms_per_step": seconds * 1e3 / ctx.iterations, "forward_calls_per_layer": per_layer}
