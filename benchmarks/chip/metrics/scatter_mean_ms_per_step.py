"""Device milliseconds per step of the scatter of the gathered payload
into the dense mean (ops under the ``lags/scatter_mean/<leaf>`` scopes),
mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["scatter_mean"]
