"""The check that decides ``correct``, driven end to end on the CPU at a
small fleet: the harness runs ``train_bafdp``'s sparse round through its
window (the look for a chip is skipped) and compares with the plain
reference.  A sound round passes; the control (the reference a step
lower in precision, put in the program's place) and each fault the cell
can have, planted in the round underneath, make ``correct`` false."""
import functools
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, control, harness, reference  # noqa: E402

N_CLIENTS = 12
SEED = 3_000_000_019          # above 2**31: seeds need not fit 32 bits


def _file(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def small(name):
    """A configuration's files, at a fleet the CPU holds."""
    cfg = _file("configs", name + ".json")
    cfg["fleet"]["n_clients"] = N_CLIENTS
    return (cfg, _file("traffic", "quorum-0.6.json"),
            _file("limits", name + ".json"))


def run(cfg, traffic, limits, program=None):
    return harness.run(cfg, traffic, limits, SEED, 0.3,
                       process_start=time.perf_counter(), program=program)


def planted(fault):
    """``train_bafdp`` with the sparse round broken underneath."""
    from benchmarks.common import train_bafdp
    from repro.core import bafdp
    real = bafdp.bafdp_round_sparse

    def broken(state, batch, key, **kw):
        if fault == "half_batch":
            batch = jax.tree.map(lambda l: l[:, : l.shape[1] // 2], batch)
        new, m = real(state, batch, key, **kw)
        if fault == "state_unchanged":
            return state, m
        if fault == "answer_altered":       # the consensus step doubled
            new = new._replace(z=jax.tree.map(
                lambda a, b: b + 2 * (a - b), new.z, state.z))
        return new, m

    @functools.wraps(train_bafdp)
    def program(*a, **kw):
        bafdp.bafdp_round_sparse = broken
        try:
            return train_bafdp(*a, **kw)
        finally:
            bafdp.bafdp_round_sparse = real
    return program


@pytest.mark.parametrize("name", ["milano-h1", "trento-h24"])
def test_reference_data_is_the_programs(name):
    """The reference's vectorised city and windows match the program's
    per-client loops bit for bit."""
    from benchmarks.common import forecast_cfg
    from repro.data import build_windows, make_dataset
    cfg, _, _ = small(name)
    seed = SEED % harness.SEED_MOD
    data = make_dataset(cfg["fleet"]["dataset"], N_CLIENTS, seed=seed)
    mine = reference.city(cfg["fleet"], seed)
    for k in data:
        np.testing.assert_array_equal(data[k], mine[k])
    train, _, _ = build_windows(
        data, forecast_cfg("mlp", cfg["model"]["horizon"]))
    x, y = reference.train_windows(mine, cfg["model"],
                                   cfg["fleet"]["test_days"])
    np.testing.assert_array_equal(train["x"], x)
    np.testing.assert_array_equal(train["y"], y)


def test_sound_round_is_correct():
    cfg, traffic, limits = small("milano-h1")
    rec = run(cfg, traffic, limits)
    assert rec["correct"], rec["numbers"]
    assert rec["rounds"] >= 1 and rec["compiles_in_window"] == 0
    assert rec["updates"] == sum(k for k, _ in rec["round_rows"])
    assert len(rec["round_done_s"]) == len(rec["interval_s"]) == rec["rounds"]
    assert rec["round_done_s"] == sorted(rec["round_done_s"])
    e2e = harness.end_to_end(rec)
    assert e2e["client_updates_per_s"] > 0 and e2e["round_ms_p95"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(fault):
    cfg, traffic, limits = small("milano-h1")
    rec = run(cfg, traffic, limits, program=planted(fault))
    assert not rec["correct"], (fault, rec["numbers"])


def test_control_fails_a_limit():
    """The bfloat16 control and the reference's planted faults each fail
    at least one of the cell's limits."""
    cfg, traffic, limits = small("trento-h24")
    out = control.numbers(cfg, traffic, SEED)
    assert set(out) == set(control.VARIANTS)
    for variant, nums in out.items():
        assert not check.judge(nums, limits), (variant, nums)


def test_reference_refuses_what_it_does_not_implement():
    cfg, traffic, _ = small("milano-h1")
    cfg["fed"]["robust_consensus"] = "median"
    with pytest.raises(NotImplementedError, match="robust_consensus"):
        reference.readings(cfg, 1, control.first_rows(cfg, traffic, 1),
                           dtype=jnp.float32)
