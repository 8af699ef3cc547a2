"""Device milliseconds per step of the exchange (ops under the
``lags/exchange`` scope: the lr scaling, select, the collectives and the
scatter-mean), mean over chips."""
from lagsbench import phases


def read(ctx):
    ms = phases.per_step(ctx.trace)
    return None if ms is None else ms["exchange"]
