"""Share (%) of the select phase's device time spent in the fused Mosaic
kernel: the self time of the ``tpu_custom_call`` ops whose op_name lies
under a ``lags/select/<leaf>`` scope, over the select phase's time
(``phases.per_step``), both summed over chips.  It says whether the
fused pass engages, not how fast the layer is: 0 where the select runs
as XLA's own passes, near 100 where the kernel does all of it (a faster
kernel lowers it; ``select_ms_per_step`` gives the speed).  None where
no op is in the select phase (a dense cell, or a program without the
phase scopes)."""
from lagsbench import phases, xplane

#: What the trace's ``XLA Ops`` event of a Pallas kernel on a TPU carries.
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _kernel_ms_per_step(trace) -> float:
    from repro.observe import names
    ws, we = trace.window
    total = 0.0
    for ops in trace.device_ops.values():
        clipped = [(max(s, ws), min(e, we), n) for s, e, n in ops
                   if e > ws and s < we]
        for _, _, n, st in xplane.self_times(clipped):
            if MOSAIC in n and names.phase_of(trace.op_names.get(
                    xplane.instruction(n), "")) == names.SELECT:
                total += st
    return 1e3 * total / (len(trace.device_ops) * trace.steps)


def read(ctx):
    ms = phases.per_step(ctx.trace)
    if ms is None or ms["select"] <= 0:
        return None
    return 100.0 * _kernel_ms_per_step(ctx.trace) / ms["select"]
