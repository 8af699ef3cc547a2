"""Device milliseconds per step of the forward pass (ops under the
``lags/fwd`` scope, outside autodiff's ``transpose(``), mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["fwd"]
