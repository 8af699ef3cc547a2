"""Device milliseconds per step of the backward pass, remat recompute
included (ops under ``lags/fwd`` inside ``transpose(``), mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["bwd"]
