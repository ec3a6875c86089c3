"""Model FLOP/s utilization of a training cell: the FLOPs the forward and
backward passes require per sequence (lib/flops.py; recomputation not counted)
times sequences per second per chip over the traced steady stretch, over the
chip's bf16 peak."""

from lib import flops


def read(ctx):
    if not ctx.stretch or not ctx.iterations or not ctx.peaks:
        return None
    f = ctx.facts
    seconds = (ctx.stretch[1] - ctx.stretch[0]) / 1e9
    per_chip = ctx.iterations * f["rows_per_step"] / seconds / ctx.cell.chips
    need = flops.bert_train_flops_per_seq(f["model"], f["seq_len"])
    return {"value": 100.0 * need * per_chip / ctx.peaks["bf16_flops"],
            "samples_per_s_per_chip_in_stretch": per_chip, "flops_per_seq": need}
