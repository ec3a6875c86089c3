"""Model FLOP/s utilization of the ``deepseek_v3`` training cell: the FLOPs the
forward and backward passes require per sequence (lib/kanana2_flops.py: latent
attention's four projections and its causal scores at the published 192 / 128
once, the dense layer, routers and shared experts, the routed experts' share from
the program's counter of the rows they computed, the head over the vocabulary
slice; nothing recomputed) times sequences per second per chip over the traced
steady stretch, over the chip's bf16 peak."""

from lib import kanana2_flops


def read(ctx):
    f = ctx.facts
    if (not ctx.stretch or not ctx.iterations or not ctx.peaks or "tokens_per_step" not in f
            or "kv_lora_rank" not in f.get("model", {})):
        return None
    seconds = (ctx.stretch[1] - ctx.stretch[0]) / 1e9
    per_chip = ctx.iterations * f["rows_per_step"] / seconds / ctx.cell.chips
    held = (f.get("moe_assignments_held") or 0.0) / f["rows_per_step"]
    parts = kanana2_flops.forward_flops_per_seq(f["model"], f["seq_len"], held)
    need = 3.0 * sum(parts.values())
    return {"value": 100.0 * need * per_chip / ctx.peaks["bf16_flops"],
            "samples_per_s_per_chip_in_stretch": per_chip, "flops_per_seq": need,
            "forward_share_by_part": {k: v / sum(parts.values()) for k, v in parts.items()}}
