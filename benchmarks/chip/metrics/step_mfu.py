"""Model FLOP utilization of the whole train step in the traced window:
model FLOP per step (``lagsbench.flops``) x steps, over the window's
host-clock seconds x chips x the chip's bf16 peak."""
from lagsbench import flops


def read(ctx):
    if ctx.peaks is None or ctx.trace.steps == 0:
        return None
    cell = ctx.cell
    per_step = flops.model_flops_per_step(
        cell["config"], int(cell["traffic"]["seq_len"]), ctx.tokens_per_step)
    return 100.0 * per_step * ctx.trace.steps / (
        ctx.trace.window_s * cell["chips"] * ctx.peaks["bf16_flops"])
