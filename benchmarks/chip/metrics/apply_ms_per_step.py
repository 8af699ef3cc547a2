"""Device milliseconds per step of the SGD apply and the loss mean (ops
under the ``lags/apply`` scope), mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["apply"]
