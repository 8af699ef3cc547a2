"""Device milliseconds per step of accumulate + select + residual (ops
under the ``lags/select/<leaf>`` scopes), mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["select"]
