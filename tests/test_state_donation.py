"""``train_bafdp`` donates the federated state to its jitted round, so each
round updates the one resident copy of the fleet in place.

* a donated sparse run is bit-identical to the same rounds driven by an
  undonated jit of the same round;
* the compiled round aliases every state leaf to its output, on the sparse
  and on the dense path;
* the initial state and each state handed to ``on_round`` are consumed
  once the next round is dispatched, and no two state leaves share a
  buffer.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from benchmarks.common import BATCH, train_bafdp
from repro.configs import FedConfig
from repro.core.async_engine import DelayModel
from repro.core.schedule import (FederatedRun, QuorumTrigger,
                                 build_schedule)
from repro.data.windowing import client_batches, stage_rows

C, ROUNDS, SEED = 10, 3, 4


def schedule():
    return build_schedule(ROUNDS, DelayModel(n_clients=C, seed=SEED),
                          QuorumTrigger(active_frac=0.6))


def fed():
    return FedConfig(n_clients=C, active_frac=0.6)


def round_inputs(sched, impl):
    """A batch and the schedule kwargs of round 0, as the run feeds them."""
    train, _, _ = common.problem("milano", 1, C, SEED)
    rng = np.random.RandomState(SEED)
    if impl == "sparse":
        kw = dict(zip(("idx", "stale", "weight"), next(sched.padded_rows())))
        batch = stage_rows(rng, train, BATCH, kw["idx"])
    else:
        kw = dict(zip(("act", "stale"), next(sched.rows())))
        batch = client_batches(rng, train, BATCH)
    return tuple(jnp.asarray(a) for a in batch), kw


@pytest.fixture(scope="module", params=["sparse", "dense"])
def donated(request):
    """A donated run, with every state it exposed: the initial state
    ``train_bafdp`` built, and each state given to ``on_round`` beside
    what the hook saw of it and of the state before it."""
    impl = request.param
    sched = schedule()
    built, hooked, seen = [], [], []
    real_init = common.init_fed_state

    def init_spy(*a, **kw):
        built.append(real_init(*a, **kw))
        return built[-1]

    def deleted(state):
        return [l.is_deleted() for l in jax.tree.leaves(state)]

    def on_round(t, state, m):
        prev = hooked[-1] if hooked else built[0]
        seen.append((any(deleted(state)), all(deleted(prev))))
        hooked.append(state)

    common.init_fed_state = init_spy
    try:
        final, _, hist = train_bafdp(
            "milano", 1, fed(), ROUNDS, seed=SEED, schedule=sched,
            round_impl=impl, collect=("loss",), on_round=on_round)
    finally:
        common.init_fed_state = real_init
    return {"impl": impl, "sched": sched, "final": final, "hist": hist,
            "initial": built[0], "hooked": hooked, "seen": seen}


def test_donated_sparse_run_is_bit_identical_to_undonated():
    """The same rounds through an undonated jit of the round that ran,
    driven by the same ``FederatedRun`` wiring, give the same bits."""
    sched = schedule()
    final, cfg, hist = train_bafdp(
        "milano", 1, fed(), ROUNDS, seed=SEED, schedule=sched,
        round_impl="sparse", collect=("loss",))
    step = jax.jit(hist["round_fn"].__wrapped__)         # no donation
    # the settings train_bafdp runs with
    f = dataclasses.replace(fed(), omega_optimizer="adam", dro_weight=0.01,
                            consensus_scope="active")
    key = jax.random.PRNGKey(SEED)
    init = common.init_fed_state(
        key, lambda k: common.init_forecaster(k, cfg), f)
    train, _, _ = common.problem("milano", 1, C, SEED)
    rng = np.random.RandomState(SEED)

    def batch_fn(t):
        ids = np.full(sched.s_max, C, np.int64)
        won = sched.round_winners(t)
        ids[:won.size] = won
        return tuple(jnp.asarray(a)
                     for a in stage_rows(rng, train, BATCH, ids))

    run = FederatedRun(step=step, rounds=ROUNDS, schedule=sched,
                       n_clients=f.n_clients, round_impl="sparse")
    ref, ref_hist = run.run(init, batch_fn, key, collect=("loss",))
    assert not any(l.is_deleted() for l in jax.tree.leaves(init))
    assert hist["loss"] == ref_hist["loss"]
    for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_aliases_every_state_leaf(donated):
    """The compiled round writes each output state leaf into the buffer
    of the input leaf it replaces."""
    final, hist = donated["final"], donated["hist"]
    batch, kw = round_inputs(donated["sched"], donated["impl"])
    compiled = hist["round_fn"].lower(
        final, batch, jax.random.PRNGKey(0), **kw).compile()
    head = compiled.as_text().split("\n", 1)[0]
    aliased = {int(p) for p in re.findall(r"\{\d*\}: \((\d+), \{\}", head)}
    n_leaves = len(jax.tree.leaves(final))
    assert n_leaves >= 40
    assert aliased == set(range(n_leaves)), head[:400]


def test_states_are_consumed_by_the_next_round(donated):
    initial, hooked = donated["initial"], donated["hooked"]
    assert len(hooked) == ROUNDS
    assert all(l.is_deleted() for l in jax.tree.leaves(initial))
    # in each hook: its own state whole, the one before it consumed
    assert donated["seen"] == [(False, True)] * ROUNDS
    for state in hooked[:-1]:
        assert all(l.is_deleted() for l in jax.tree.leaves(state))
    assert hooked[-1] is donated["final"]
    assert not any(l.is_deleted() for l in jax.tree.leaves(hooked[-1]))


def test_no_two_state_leaves_share_a_buffer(donated):
    """A buffer donated twice is an error: every leaf is its own."""
    final = donated["final"]
    ptrs = [l.unsafe_buffer_pointer() for l in jax.tree.leaves(final)]
    assert len(set(ptrs)) == len(ptrs)


def test_init_state_leaves_have_their_own_buffers():
    from repro.core import init_fed_state
    from repro.models.forecasting import init_forecaster
    cfg = common.forecast_cfg("mlp", 24)
    f = FedConfig(n_clients=C, omega_optimizer="adam",
                  staleness_compensation="taylor")
    state = init_fed_state(jax.random.PRNGKey(0),
                           functools.partial(init_forecaster, cfg=cfg), f)
    ptrs = [l.unsafe_buffer_pointer() for l in jax.tree.leaves(state)]
    assert len(set(ptrs)) == len(ptrs)
