"""Milliseconds per step, mean over chips, in which a collective op
(all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all)
runs on a chip and no other op does."""


def read(ctx):
    t = ctx.trace
    if not t.device_ops or t.steps == 0 or ctx.cell["chips"] < 2:
        return None
    return 1e3 * t.collective_exposed_s() / t.steps
