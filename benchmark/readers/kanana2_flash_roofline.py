"""Share of the roofline the flash-attention kernels reach in the
``deepseek_v3`` training step, every layer of which is latent attention: the
least time the chip could take for flash_fwd + flash_dq + flash_dkv at the
published score head (192) and value head (128), FLOPs of the visible pairs and
bytes from the unpadded shapes (lib/kanana2_flops.py, the forward counted as
often as the trace shows it ran), over their device time in the trace.  The
count is of the mathematics: a kernel that pads a head or repeats a key head
does more work for the same count and reads lower.  A program without the
configuration's keys or the kernels reads nothing."""

from lib import kanana2_flops, peaks as pk, trace as tr


def read(ctx, pattern="flash_(fwd|dq|dkv)"):
    if not ctx.ops or not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    events = ctx.ops[min(ctx.ops)]
    seconds, calls = tr.kernel_seconds(events, pattern, *ctx.stretch)
    m, f = ctx.facts.get("model", {}), ctx.facts
    if not calls or "kv_lora_rank" not in m:
        return None
    _, forwards = tr.kernel_seconds(events, "flash_fwd", *ctx.stretch)
    per_layer = forwards / ctx.iterations / m["num_hidden_layers"]
    fl, by = kanana2_flops.flash_train_flops_bytes(m, f["rows_per_step"] // ctx.cell.chips,
                                                   f["seq_len"], forward_calls=per_layer)
    share = pk.roofline_share(fl, by, seconds / ctx.iterations, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"],
            "ms_per_step": seconds * 1e3 / ctx.iterations, "forward_calls_per_layer": per_layer}
