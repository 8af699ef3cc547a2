"""The train step's phase scopes and ``Session.run``'s host spans, as a
profiler sees them: the compiled step's ``op_name`` metadata carries a
``lags/<phase>`` scope for every phase the mode runs, and a
``jax.profiler`` trace of the loop holds one ``lags/step`` per step with
the ``lags/host/...`` spans inside it."""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import api, compat
from repro.configs import base
from repro.launch import mesh as M
from repro.observe import metrics as OM
from repro.observe import names


def _cfg():
    return dataclasses.replace(
        base.get_smoke_config("tinyllama_1_1b"), n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=64)


def _session(run):
    return api.Session(_cfg(), run, mesh=M.make_host_mesh(data=1, model=1))


LAGS = dict(mode="lags_dp", ratio=8.0, chunk=16, loss_chunk=16)
EXCHANGE = {"exchange", "select", "scatter_mean"}


@pytest.mark.parametrize("run,want", [
    (api.RunConfig(**LAGS), {"fwd", "bwd", "apply"} | EXCHANGE),
    # the taps run the exchange inside the backward pass: still exchange
    (api.RunConfig(**LAGS, pipeline="wave"),
     {"fwd", "bwd", "apply"} | EXCHANGE),
    (api.RunConfig(**LAGS, health_every=1),
     {"fwd", "bwd", "apply", "health"} | EXCHANGE),
    (api.RunConfig(mode="dense", chunk=16, loss_chunk=16),
     {"fwd", "bwd", "exchange", "apply"}),
], ids=["lags_dp-off", "lags_dp-wave", "lags_dp-health", "dense"])
def test_compiled_step_names_every_phase(run, want):
    sess = _session(run)
    step, specs, _ = sess.train_step()
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32,
                               sharding=NamedSharding(sess.mesh,
                                                      P("data", None)))
    with compat.set_mesh(sess.mesh):
        text = step.lower(specs, {"tokens": tok,
                                  "labels": tok}).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    got = {names.phase_of(n) for n in op_names} - {None}
    assert got == want
    labels = {m for n in op_names
              for m in re.findall(r"lags/(?:select|scatter_mean)/(l\d+)", n)}
    if "select" in want:          # one label per leaf, as the comm scopes
        assert len(labels) == len(jax.tree.leaves(specs["params"]))


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_session_run_writes_step_and_host_spans(tmp_path):
    sess = _session(api.RunConfig(**LAGS))
    state, _ = sess.init_state()
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    state, _ = sess.run(lambda t: batch, 1, state=state, log_every=0,
                        metrics=OM.MetricsRegistry())     # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run(lambda t: batch, 2, state=state, log_every=0,
                 metrics=OM.MetricsRegistry())
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = sorted((s, e) for s, e, n in events if n == names.STEP)
    assert len(steps) == 2
    for s, e in steps:
        inside = {n for s2, e2, n in events if s <= s2 and e2 <= e}
        for span in ("data", "dispatch", "loss_sync", "bookkeeping"):
            assert names.host_name(span) in inside
    order = sorted((s, n) for s, e, n in events
                   if n.startswith(names.HOST_PREFIX)
                   and steps[0][0] <= s and e <= steps[0][1])
    assert [names.parse(n)["span"] for _, n in order] == \
        list(names.HOST_SPANS)


@pytest.mark.parametrize("run", [api.RunConfig(**LAGS),
                                 api.RunConfig(mode="dense", chunk=16,
                                               loss_chunk=16)],
                         ids=["lags_dp", "dense"])
def test_comm_bytes_counter_is_per_step_payload(run):
    """Worked out once per plan, counted once per step."""
    from repro.api.session import _step_comm_bytes
    sess = _session(run)
    state, _ = sess.init_state()
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    reg = OM.MetricsRegistry()
    state, _ = sess.run(lambda t: batch, 3, state=state, log_every=0,
                        metrics=reg)
    per_step = _step_comm_bytes(sess.meta, state["params"])
    assert per_step > 0
    got = reg.get("train_comm_bytes_total")
    assert got.value(mode=sess.mode) == 3 * per_step
