"""Device idle milliseconds per step that the training loop's loss read
leaves: the idle gap each ``lags/host/loss_sync`` span of
``Session.run`` returns into, mean over chips."""
from lagsbench import phases


def read(ctx):
    return phases.sync_idle_per_step(ctx.trace)
