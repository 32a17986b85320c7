"""Benchmark-harness smoke: every suite produces CSV rows in --quick mode
with tiny round counts (the full run is benchmarks.run / bench_output.txt)."""
import numpy as np
import pytest

from benchmarks import (fig3_privacy_level, fig456_async_efficiency,
                        fig7_distributiveness, fig8_robust_convergence,
                        roofline_table, table4_byzantine,
                        theorem1_convergence)

SUITES = {
    "fig3": fig3_privacy_level.main,
    "table4": table4_byzantine.main,
    "fig7": fig7_distributiveness.main,
    "fig8": fig8_robust_convergence.main,
    "theorem1": theorem1_convergence.main,
    "roofline": roofline_table.main,
}


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(SUITES))
def test_suite_quick(name):
    rows = SUITES[name](rounds=8, quick=True)
    assert rows, name
    for r in rows:
        parts = r.split(",", 2)
        assert len(parts) == 3, r             # name,us_per_call,derived
        float(parts[1])


def test_short_mask_schedule_rejected():
    """Recycling a schedule shorter than the training horizon would rebuild
    the schedule/timestamp mismatch this plumbing removes — hard error,
    through both the deprecated dense shim and the sparse Schedule path."""
    from benchmarks.common import train_bafdp
    from repro.configs import FedConfig
    from repro.core.async_engine import DelayModel
    from repro.core.schedule import QuorumTrigger, build_schedule
    short = np.ones((3, 8), bool)
    with pytest.raises(ValueError, match="covers 3 rounds"):
        train_bafdp("milano", 1, FedConfig(n_clients=8), rounds=5,
                    active_masks=short)
    sched = build_schedule(3, DelayModel(n_clients=8, seed=0),
                           QuorumTrigger())
    with pytest.raises(ValueError, match="covers 3 rounds"):
        train_bafdp("milano", 1, FedConfig(n_clients=8), rounds=5,
                    schedule=sched)
    with pytest.raises(ValueError, match="not both"):
        train_bafdp("milano", 1, FedConfig(n_clients=8), rounds=3,
                    schedule=sched, active_masks=short)


def test_fedbuff_benchmark_smoke():
    """Tier-1 acceptance smoke: a FedBuff (K-arrivals) schedule trains
    end-to-end through FederatedRun via the fig456 scenario harness."""
    row, meta = fig456_async_efficiency.run_scenario(
        "fedbuff", "milano", rounds=4, with_meta=True)
    parts = row.split(",", 2)
    assert len(parts) == 3 and parts[0] == "fig456/milano:fedbuff"
    float(parts[1])
    # the buffer contract survives the full pipeline: K arrivals per round,
    # and the trainer saw exactly the schedule's distinct winners
    assert (meta["arrivals"] == 5).all()
    np.testing.assert_array_equal(meta["n_active"], meta["masks"].sum(1))
    assert (meta["staleness"][meta["masks"]] == 0).all()
    assert np.isfinite(meta["quorum"]).all()


def test_fedbuff_lr_norm_autofeeds_arrivals():
    """train_bafdp couples FedConfig.fedbuff_lr_norm to the schedule's
    realized per-round K automatically: on a schedule where a fast client
    delivered twice into one buffer (K > distinct actives), the default
    run must differ from one forced onto the sum(act) fallback — if the
    two match, the knob silently undercounted K."""
    import jax
    from benchmarks.common import train_bafdp
    from repro.configs import FedConfig
    from repro.core.async_engine import DelayModel
    from repro.core.schedule import FedBuffTrigger, build_schedule
    rounds = 4
    sched = build_schedule(rounds, DelayModel(n_clients=8, hetero=2.5,
                                              seed=3),
                           FedBuffTrigger(buffer_k=5))
    assert (sched.arrivals > sched.quorum).any()   # duplicates present
    fed = FedConfig(n_clients=8, fedbuff_lr_norm=True)
    st_auto, _, _ = train_bafdp("milano", 1, fed, rounds, schedule=sched)
    st_fallback, _, _ = train_bafdp("milano", 1, fed, rounds,
                                    schedule=sched, feed_arrivals=False)
    z_a = np.concatenate([np.asarray(l).ravel()
                          for l in jax.tree.leaves(st_auto.z)])
    z_f = np.concatenate([np.asarray(l).ravel()
                          for l in jax.tree.leaves(st_fallback.z)])
    assert not np.array_equal(z_a, z_f)


def test_million_client_schedule_smoke():
    """Tier-1 acceptance smoke (also wired into CI by name): the sparse
    streaming build handles a million-client fleet without ever allocating
    a dense (rounds, C) matrix — see test_schedule_api for the poisoned-
    allocation variant; this one exercises the benchmark-facing path."""
    from repro.core.async_engine import DelayModel
    from repro.core.schedule import FedBuffTrigger, build_schedule
    sched = build_schedule(
        3, DelayModel(n_clients=1_000_000, hetero=1.0, seed=0),
        FedBuffTrigger(buffer_k=128), stream=True)
    assert sched.winner_ids.size == 3 * 128
    assert (np.diff(sched.times) >= 0).all()


@pytest.mark.slow
def test_fig456_trains_on_simulator_masks():
    """The wall-clock rows and the training dynamics must come from ONE
    event-driven schedule: the per-round n_active the trainer observed has
    to equal the simulator masks' row sums."""
    rows, metas = fig456_async_efficiency.main(rounds=6, quick=True,
                                               with_meta=True)
    assert rows and len(metas) == 1
    for r in rows:
        parts = r.split(",", 2)
        assert len(parts) == 3 and parts[0].startswith("fig456/")
        float(parts[1])
    meta = metas[0]
    masks_a, masks_s = meta["masks_async"], meta["masks_sync"]
    # sync trained on active_frac=1.0 masks, async on S-of-M masks
    assert masks_s.all()
    C = masks_a.shape[1]
    s = max(1, int(round(C * meta["active_frac"])))
    assert (masks_a.sum(1) == s).all() and s < C
    np.testing.assert_array_equal(meta["n_active_async"], masks_a.sum(1))
    np.testing.assert_array_equal(meta["n_active_sync"], masks_s.sum(1))
    assert (meta["staleness_async"][masks_a] == 0).all()
    # scenario variants trained on their own schedules, same consistency
    assert set(meta["variants"]) == set(fig456_async_efficiency.SCENARIOS)
    for name, v in meta["variants"].items():
        np.testing.assert_array_equal(v["n_active"], v["masks"].sum(1),
                                      err_msg=name)
        np.testing.assert_array_equal(v["quorum"], v["masks"].sum(1),
                                      err_msg=name)
        assert (v["staleness"][v["masks"]] == 0).all(), name


def test_fig456_age_adaptive_scenario_bounds_staleness():
    """The fig456 ``age_adaptive`` scenario (age-aware selection +
    adaptive quorum) must bound max staleness over a long horizon, where
    the PR-1 fastest/fixed policy starves the slow tail."""
    from repro.core.async_engine import DelayModel
    from repro.core.schedule import QuorumTrigger, build_schedule
    dm_kw, trigger_fn, _ = fig456_async_efficiency.SCENARIOS["age_adaptive"]
    n, frac, rounds = 8, fig456_async_efficiency.ACTIVE_FRAC, 150
    dm = DelayModel(**{"n_clients": n, "hetero": 1.0, "seed": 0, **dm_kw})
    aged = build_schedule(rounds, dm, trigger_fn()).to_sim()
    fast = build_schedule(rounds, dm,
                          QuorumTrigger(active_frac=frac)).to_sim()
    s = max(1, int(round(n * frac)))
    thr = 2 * int(np.ceil(n / s))            # default age_threshold
    bound = thr + int(np.ceil(n / s))        # overdue admissions may queue
    assert aged.staleness.max() <= bound, aged.staleness.max()
    assert fast.staleness.max() > bound      # fastest/fixed really starves


def test_roofline_artifacts_complete():
    """All 40 pairs x 2 meshes present with coherent terms."""
    rows = roofline_table.rows_from_artifacts()
    if not rows:
        pytest.skip("dry-run artifacts not generated in this checkout")
    keys = {(r["arch"], r["shape"], r["mesh"]) for r in rows}
    assert len(keys) >= 80, len(keys)
    for r in rows:
        assert r["t_compute_s"] >= 0 and r["t_memory_s"] > 0
        assert r["dominant"] in ("compute", "memory", "collective")
        assert r["flops"] > 0
