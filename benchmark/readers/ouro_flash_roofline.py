"""Share of the roofline the flash-attention kernels reach in the looped
decoder's training step, where every layer's kernels run once a pass: the
least time the chip could take for flash_fwd + flash_dq + flash_dkv (FLOPs of
the visible pairs and bytes from shapes with K/V at the heads the kernels are
given, lib/ouro_flops.py, over ``total_ut_steps x num_hidden_layers`` block
applications, the forward counted as often as the trace shows it ran) over
their device time in the trace."""

from lib import ouro_flops, peaks as pk, trace as tr


def read(ctx, pattern="flash_(fwd|dq|dkv)"):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    events = ctx.ops[min(ctx.ops)]
    seconds, calls = tr.kernel_seconds(events, pattern, *ctx.stretch)
    m, f = ctx.facts.get("model", {}), ctx.facts
    if not calls or "total_ut_steps" not in m:
        return None
    applications = m["total_ut_steps"] * m["num_hidden_layers"]
    _, forwards = tr.kernel_seconds(events, "flash_fwd", *ctx.stretch)
    per_application = forwards / ctx.iterations / applications
    fl, by = ouro_flops.flash_train_flops_bytes(m, f["rows_per_step"] // ctx.cell.chips,
                                                f["seq_len"], forward_calls=per_application)
    share = pk.roofline_share(fl, by, seconds / ctx.iterations, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"],
            "ms_per_step": seconds * 1e3 / ctx.iterations,
            "forward_calls_per_application": per_application}
