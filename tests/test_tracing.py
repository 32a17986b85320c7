"""Host spans and device stage scopes: ``repro.tracing`` and where the
program uses it.

* spans nest, and a recorder sums their durations, calls and counts;
* ``FederatedRun.run`` puts ``fed.round`` and its steps, in order, once
  per round into the profiler's trace, and reads no device value;
* set-up (data preparation, state init, schedule build) is recorded;
* the compiled sparse round carries every ``bafdp.*`` stage scope in its
  ``op_name`` metadata.
"""
import functools
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import MLP_H1, FedConfig
from repro.core import bafdp, init_fed_state
from repro.core.async_engine import DelayModel
from repro.core.byzantine import byz_mask
from repro.core.privacy import gaussian_c3, perturb_inputs
from repro.core.schedule import FederatedRun, QuorumTrigger, build_schedule
from repro.data import build_windows, make_dataset
from repro.models.forecasting import init_forecaster, mse_loss

STAGES = ("bafdp.gather", "bafdp.local_step", "bafdp.attack", "bafdp.fold",
          "bafdp.dual", "bafdp.scatter", "bafdp.metrics")
C = 6


def test_recorder_sums_nested_spans_and_counts():
    with tracing.span("before"):      # no recorder: nothing is kept
        pass
    with tracing.recording() as tot:
        with tracing.span("outer", rows=2):
            with tracing.span("inner", bytes=10):
                pass
        with tracing.span("outer", rows=3):
            pass
        with tracing.step("loop", 7):
            pass
        with tracing.recording() as inner_tot:
            with tracing.span("inner"):
                pass
        with tracing.span("after"):
            pass
    assert sorted(tot) == ["after", "inner", "loop", "outer"]
    assert tot["outer"]["calls"] == 2
    assert tot["outer"]["counts"] == {"rows": 5}
    assert tot["inner"] == {"calls": 1, "seconds": tot["inner"]["seconds"],
                            "counts": {"bytes": 10}}
    assert tot["loop"]["counts"] == {}     # a step number is not a count
    assert tot["outer"]["seconds"] >= tot["inner"]["seconds"] > 0
    assert inner_tot["inner"]["calls"] == 1
    with tracing.span("later"):
        pass
    assert "later" not in tot


class DeviceValue:
    """Stands in for an array held on the device: its shape and size are
    host metadata, its value may not be read."""

    def __init__(self, *shape):
        self.shape = shape

    def _read(self, *_, **__):
        raise AssertionError("the host read a device value")

    __array__ = __float__ = __int__ = __index__ = __bool__ = _read
    __len__ = __iter__ = __getitem__ = _read


def _host_events(tmp_path, fn):
    """Names, starts, ends and stats of the main thread's host events
    while ``fn`` runs under the profiler."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                      for e in line.events]
            if any(n == "fed.round" for n, *_ in events):
                return sorted(events, key=lambda e: (e[1], -e[2]))
    raise AssertionError("no fed.round span in the trace")


def test_federated_run_spans_each_round_in_order(tmp_path):
    rounds = 4
    sched = build_schedule(rounds, DelayModel(n_clients=C, seed=1),
                           QuorumTrigger(active_frac=0.5))

    def step(state, batch, key, *, idx, stale, weight):
        return DeviceValue(C, 3), {"loss": DeviceValue()}

    run = FederatedRun(step=step, rounds=rounds, schedule=sched,
                       n_clients=C, round_impl="sparse")

    recorded = {}

    def go():
        with tracing.recording() as tot:
            run.run(DeviceValue(C, 3),
                    lambda t: (DeviceValue(C, 8, 22), DeviceValue(C, 8, 1)),
                    jax.random.PRNGKey(0), collect=("t",),
                    derive={"t": lambda s, m: 0},
                    on_round=lambda t, s, m: None)
        recorded.update(tot)

    events = _host_events(tmp_path, go)
    children = ["fed.schedule_row", "fed.key", "fed.batch", "fed.dispatch",
                "fed.hook", "fed.collect"]
    spans = [e for e in events if e[0].startswith("fed.")]
    per_round = [e for e in spans if e[0] == "fed.round"]
    assert [int(e[3]["step_num"]) for e in per_round] == list(range(rounds))
    for _, lo, hi, _ in per_round:
        inside = [e for e in spans if e[0] != "fed.round"
                  and lo <= e[1] and e[2] <= hi]
        assert [e[0] for e in inside] == children
    assert {k: recorded[k]["calls"] for k in recorded} == dict.fromkeys(
        ["fed.round"] + children, rounds)


def test_setup_spans_are_recorded():
    cfg = MLP_H1
    fed = FedConfig(n_clients=3)
    with tracing.recording() as tot:
        data = make_dataset("trento", 3, seed=0)
        build_windows(data, cfg)
        init_fed_state(jax.random.PRNGKey(0),
                       lambda k: init_forecaster(k, cfg), fed)
        build_schedule(5, DelayModel(n_clients=3, seed=0))
    assert {k: tot[k]["calls"] for k in tot} == dict.fromkeys(
        ["data.make_dataset", "data.build_windows", "fed.init_state",
         "schedule.build"], 1)


@pytest.mark.parametrize("impl", ["sparse", "dense_active"])
def test_round_carries_every_stage_scope(impl):
    fed = FedConfig(n_clients=C, consensus_scope="active")
    key = jax.random.PRNGKey(0)
    state = init_fed_state(key, lambda k: init_forecaster(k, MLP_H1), fed)
    X = jax.random.normal(key, (C, 4, MLP_H1.d_x))
    Y = X[..., :1]

    def local_loss(p, batch, k, eps):
        x, y = batch
        return mse_loss(p, perturb_inputs(k, x, eps, 0.02), y, MLP_H1)

    kw = dict(local_loss=local_loss, fed=fed, n_samples=100,
              c3=gaussian_c3(MLP_H1.d_x + MLP_H1.d_y, fed.dp_delta, 0.05),
              d_dim=MLP_H1.d_x + MLP_H1.d_y, byz_mask=byz_mask(C, 0))
    if impl == "sparse":
        fn = functools.partial(bafdp.bafdp_round_sparse, **kw)
        args = dict(idx=np.array([4, 0, 2, C], np.int32),
                    stale=np.zeros(4, np.float32),
                    weight=np.array([1, 1, 1, 0], np.float32))
    else:
        fn = functools.partial(bafdp.bafdp_round, **kw)
        args = dict(act=jnp.arange(C) % 2 == 0)
    hlo = jax.jit(fn).lower(state, (X, Y), key, **args).compile().as_text()
    # each op's innermost stage scope, as a device trace reads it
    names = {re.findall(r"bafdp\.[a-z_]+", op)[-1]
             for op in re.findall(r'op_name="([^"]*)"', hlo) if "bafdp." in op}
    assert names == set(STAGES)
