"""Share of the roofline the flash-attention kernels reach in a training step:
the least time the chip could take for flash_fwd + flash_dq + flash_dkv (FLOPs
and bytes from shapes, lib/flops.py) over their device time in the trace."""

from lib import flops, peaks as pk, trace as tr


def read(ctx, pattern="flash_(fwd|dq|dkv)", causal=False):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    seconds, calls = tr.kernel_seconds(ctx.ops[min(ctx.ops)], pattern, *ctx.stretch)
    if not calls:
        return None
    m, f = ctx.facts["model"], ctx.facts
    rows = f["rows_per_step"] // ctx.cell.chips
    heads = m["num_attention_heads"]
    fl, by = flops.flash_train_flops_bytes(rows, heads, f["seq_len"], m["hidden_size"] // heads,
                                           m["num_hidden_layers"], causal)
    share = pk.roofline_share(fl, by, seconds / ctx.iterations, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"],
            "ms_per_step": seconds * 1e3 / ctx.iterations}
