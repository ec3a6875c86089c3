"""Model FLOP/s utilization of the looped decoder's training cell: the FLOPs
the forward and backward passes require per sequence (lib/ouro_flops.py: every
layer and the head once a pass, the gate, recomputation not counted) times
sequences per second per chip over the traced steady stretch, over the chip's
bf16 peak."""

from lib import ouro_flops


def read(ctx):
    f = ctx.facts
    if (not ctx.stretch or not ctx.iterations or not ctx.peaks or "tokens_per_step" not in f
            or "total_ut_steps" not in f.get("model", {})):
        return None
    seconds = (ctx.stretch[1] - ctx.stretch[0]) / 1e9
    per_chip = ctx.iterations * f["rows_per_step"] / seconds / ctx.cell.chips
    parts = ouro_flops.forward_flops_per_seq(f["model"], f["seq_len"])
    need = 3.0 * sum(parts.values())
    return {"value": 100.0 * need * per_chip / ctx.peaks["bf16_flops"],
            "samples_per_s_per_chip_in_stretch": per_chip, "flops_per_seq": need,
            "forward_share_by_part": {k: v / sum(parts.values()) for k, v in parts.items()}}
