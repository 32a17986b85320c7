"""Unit + behaviour tests for the BAFDP algorithm (Eq. 15-22)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FedConfig, MLP_H1
from repro.core import bafdp, init_fed_state
from repro.core.byzantine import byz_mask
from repro.core.privacy import gaussian_c3, perturb_inputs
from repro.models.forecasting import init_forecaster, mse_loss

CFG = MLP_H1


def make_problem(fed, seed=0, b=16):
    key = jax.random.PRNGKey(seed)
    state = init_fed_state(key, lambda k: init_forecaster(k, CFG), fed)
    X = jax.random.normal(key, (fed.n_clients, b, CFG.d_x))
    Y = jnp.sum(X[..., :3], -1, keepdims=True) * 0.5
    c3 = gaussian_c3(CFG.d_x + CFG.d_y, fed.dp_delta, fed.dp_sensitivity)

    def local_loss(p, batch, k, eps):
        x, y = batch
        return mse_loss(p, perturb_inputs(k, x, eps, 0.02), y, CFG)

    step = jax.jit(functools.partial(
        bafdp.bafdp_round, local_loss=local_loss, fed=fed, c3=c3,
        n_samples=200, d_dim=CFG.d_x + CFG.d_y,
        byz_mask=byz_mask(fed.n_clients, fed.n_byzantine)))
    return state, (X, Y), step, key


def run(fed, n_rounds=60, seed=0):
    state, batch, step, key = make_problem(fed, seed)
    losses = []
    for t in range(n_rounds):
        state, m = step(state, batch, jax.random.fold_in(key, t))
        losses.append(float(m["data_loss"]))
    return state, losses, m


def test_converges_clean():
    fed = FedConfig(n_clients=8, byzantine_frac=0.0, attack="none")
    _, losses, _ = run(fed)
    assert losses[-1] < losses[0] * 0.9
    assert np.isfinite(losses).all()


@pytest.mark.parametrize("attack", ["sign_flip", "gaussian", "same_value",
                                    "alie"])
def test_robust_under_attack(attack):
    fed = FedConfig(n_clients=8, byzantine_frac=0.25, attack=attack)
    _, losses, m = run(fed)
    assert np.isfinite(losses).all(), f"{attack}: diverged"
    assert losses[-1] < losses[0] * 1.05, f"{attack}: no progress"


def test_eps_stays_feasible():
    fed = FedConfig(n_clients=6, privacy_budget_a=20.0)
    state, _, m = run(fed, n_rounds=30)
    eps = np.asarray(state.eps)
    assert (eps >= fed.eps_min - 1e-6).all()
    assert (eps <= fed.privacy_budget_a + 1e-6).all()


def test_lambda_nonnegative():
    fed = FedConfig(n_clients=6)
    state, _, _ = run(fed, n_rounds=30)
    assert (np.asarray(state.lam) >= 0).all()


def test_consensus_gap_shrinks():
    fed = FedConfig(n_clients=8, psi=0.02, active_frac=1.0)
    state, batch, step, key = make_problem(fed)
    gaps = []
    for t in range(80):
        state, m = step(state, batch, jax.random.fold_in(key, t))
        gaps.append(float(m["consensus_gap"]))
    assert gaps[-1] < gaps[0], (gaps[0], gaps[-1])


def test_async_partial_participation():
    fed = FedConfig(n_clients=10, active_frac=0.3)
    state, batch, step, key = make_problem(fed)
    state, m = step(state, batch, key)
    assert int(m["n_active"]) == 3


def test_inactive_clients_frozen():
    fed = FedConfig(n_clients=10, active_frac=0.3)
    state, batch, step, key = make_problem(fed)
    new_state, m = step(state, batch, key)
    # at least one client kept exactly its old params (it was inactive)
    w0 = np.asarray(jax.tree.leaves(state.W)[0])
    w1 = np.asarray(jax.tree.leaves(new_state.W)[0])
    per_client_same = np.all(np.isclose(w0, w1), axis=tuple(
        range(1, w0.ndim)))
    assert per_client_same.sum() == 7      # 10 clients, 3 active


def test_reg_decay_setting1():
    # a^t = 1/(alpha (t+1)^{1/4}) is nonincreasing in t
    a = [float(bafdp.reg_decay(0.01, jnp.asarray(t), 0.25))
         for t in range(10)]
    assert all(a[i] >= a[i + 1] for i in range(len(a) - 1))
    np.testing.assert_allclose(a[0], 1 / 0.01, rtol=1e-6)


def test_adam_variant_runs():
    fed = FedConfig(n_clients=4, omega_optimizer="adam", alpha_w=1e-3)
    _, losses, _ = run(fed, n_rounds=40)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_local_steps_consensus_cadence():
    """K local steps: z must change only every K-th round."""
    fed = FedConfig(n_clients=4, local_steps=3, active_frac=1.0)
    state, batch, step, key = make_problem(fed)
    z_vals = [np.asarray(jax.tree.leaves(state.z)[0]).copy()]
    for t in range(6):
        state, _ = step(state, batch, jax.random.fold_in(key, t))
        z_vals.append(np.asarray(jax.tree.leaves(state.z)[0]).copy())
    changed = [not np.allclose(z_vals[i], z_vals[i + 1]) for i in range(6)]
    assert changed == [False, False, True, False, False, True]


def test_external_mask_is_strict_generalization():
    """Feeding bafdp_round the very mask its internal sampler would draw
    (constant staleness decay) reproduces the seed numerics exactly."""
    fed = FedConfig(n_clients=8, active_frac=0.5, staleness_decay="constant")
    state_a, batch, step, key = make_problem(fed)
    state_b = state_a
    for t in range(12):
        kt = jax.random.fold_in(key, t)
        # the internal path draws act from the first of three key splits
        k_act = jax.random.split(kt, 3)[0]
        mask = bafdp.active_mask(k_act, fed.n_clients, fed.active_frac)
        state_a, m_a = step(state_a, batch, kt)             # internal sampler
        state_b, m_b = step(state_b, batch, kt, act=mask)   # external mask
        np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                                   rtol=1e-6)
    for la, lb in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32), rtol=1e-6)


def test_external_mask_jit_stable():
    """Per-round masks are traced array args: compilation count must not
    grow with rounds."""
    fed = FedConfig(n_clients=6, active_frac=0.5)
    state, batch, _, key = make_problem(fed)
    from repro.core.byzantine import byz_mask
    from repro.core.privacy import gaussian_c3

    traces = {"n": 0}

    def counted_round(st, b, k, act):
        traces["n"] += 1
        return bafdp.bafdp_round(
            st, b, k, act=act,
            local_loss=lambda p, bb, kk, e: mse_loss(
                p, perturb_inputs(kk, bb[0], e, 0.02), bb[1], CFG),
            fed=fed, c3=gaussian_c3(CFG.d_x + CFG.d_y, fed.dp_delta,
                                    fed.dp_sensitivity),
            n_samples=200, d_dim=CFG.d_x + CFG.d_y,
            byz_mask=byz_mask(fed.n_clients, fed.n_byzantine))

    step = jax.jit(counted_round)
    rng = np.random.RandomState(0)
    for t in range(8):
        mask = jnp.asarray(rng.rand(fed.n_clients) < 0.5)
        state, _ = step(state, batch, jax.random.fold_in(key, t), mask)
    assert traces["n"] == 1, f"recompiled {traces['n']} times"


def test_staleness_weights_schedules():
    stale = jnp.asarray([0.0, 1.0, 4.0, 5.0, 9.0])
    const = bafdp.staleness_weights(
        stale, FedConfig(staleness_decay="constant"))
    np.testing.assert_allclose(np.asarray(const), 1.0)
    hinge = bafdp.staleness_weights(
        stale, FedConfig(staleness_decay="hinge",
                         staleness_hinge_a=10.0, staleness_hinge_b=4.0))
    # AFO hinge 1/(a (d - b) + 1): continuous at d = b
    np.testing.assert_allclose(np.asarray(hinge),
                               [1.0, 1.0, 1.0, 1 / 11.0, 1 / 51.0])
    poly = bafdp.staleness_weights(
        stale, FedConfig(staleness_decay="poly", staleness_poly_a=0.5))
    np.testing.assert_allclose(np.asarray(poly),
                               (np.asarray(stale) + 1.0) ** -0.5, rtol=1e-6)
    with pytest.raises(ValueError):
        bafdp.staleness_weights(stale, FedConfig(staleness_decay="exp"))


def test_tau_tracks_last_participation():
    fed = FedConfig(n_clients=6, active_frac=0.5)
    state, batch, step, key = make_problem(fed)
    last = np.zeros(6, np.int64)
    rng = np.random.RandomState(3)
    for t in range(7):
        mask = rng.rand(6) < 0.5
        state, m = step(state, batch, jax.random.fold_in(key, t),
                        act=jnp.asarray(mask))
        last[mask] = t
        np.testing.assert_array_equal(np.asarray(state.tau), last)
        # metric reports the pre-round staleness mean (t - tau before update)
        assert np.isfinite(float(m["staleness_mean"]))


@pytest.mark.parametrize("decay", ["hinge", "poly"])
def test_staleness_decay_variants_converge(decay):
    fed = FedConfig(n_clients=8, active_frac=0.4, staleness_decay=decay)
    _, losses, m = run(fed, n_rounds=60)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 1.05
    assert float(m["staleness_weight_mean"]) <= 1.0 + 1e-6


def test_sign_message_int8_composes_with_decay_and_compensation():
    """PR-4 lifts the old 'compress_signs requires constant decay'
    restriction: the int8 wire format carries the *weighted* message
    (payload = sign, per-client f32 scale = s(d)), so decay, Taylor
    compensation, and compression compose — and losslessly: the int8
    trajectory equals the f32 trajectory bit-for-bit."""
    outs = {}
    for msg in ("f32", "int8"):
        fed = FedConfig(n_clients=6, active_frac=0.5, staleness_decay="poly",
                        staleness_compensation="taylor", sign_message=msg)
        state, batch, step, key = make_problem(fed)
        rng = np.random.RandomState(5)
        for t in range(6):
            mask = jnp.asarray(rng.rand(6) < 0.5)
            state, m = step(state, batch, jax.random.fold_in(key, t),
                            act=mask)
        outs[msg] = np.concatenate([np.asarray(l).ravel()
                                    for l in jax.tree.leaves(state.z)])
        assert np.isfinite(outs[msg]).all()
    np.testing.assert_array_equal(outs["f32"], outs["int8"])


def test_compress_signs_alias_resolves_to_int8():
    """The deprecated compress_signs flag is a shim for sign_message='int8'
    and produces the identical round."""
    assert FedConfig(compress_signs=True).resolved_sign_message == "int8"
    assert FedConfig().resolved_sign_message == "f32"
    outs = {}
    for name, kw in (("alias", dict(compress_signs=True)),
                     ("knob", dict(sign_message="int8"))):
        fed = FedConfig(n_clients=5, active_frac=1.0, **kw)
        state, batch, step, key = make_problem(fed)
        state, _ = step(state, batch, key)
        outs[name] = np.concatenate([np.asarray(l).ravel()
                                     for l in jax.tree.leaves(state.z)])
    np.testing.assert_array_equal(outs["alias"], outs["knob"])


def test_sign_message_validation():
    fed = FedConfig(n_clients=4, sign_message="int4")
    state, batch, step, key = make_problem(fed)
    with pytest.raises(ValueError, match="sign_message"):
        step(state, batch, key)


# ---------------- FedBuff server-side LR normalization ----------------------
def test_fedbuff_lr_norm_scales_consensus_step():
    """With the knob on, the z step shrinks by exactly K/C relative to the
    unnormalized round (same dz, scaled AXPY)."""
    act = jnp.asarray([True, True, True, False, False, False])
    fed_n = FedConfig(n_clients=6, active_frac=0.5, fedbuff_lr_norm=True)
    fed_0 = FedConfig(n_clients=6, active_frac=0.5)
    state, batch, step_n, key = make_problem(fed_n)
    _, _, step_0, _ = make_problem(fed_0)
    out_n, _ = step_n(state, batch, key, act=act)
    out_0, _ = step_0(state, batch, key, act=act)
    for z0, zn, zp in zip(jax.tree.leaves(state.z),
                          jax.tree.leaves(out_n.z),
                          jax.tree.leaves(out_0.z)):
        np.testing.assert_allclose(
            np.asarray(zn) - np.asarray(z0),
            0.5 * (np.asarray(zp) - np.asarray(z0)),   # K/C = 3/6
            rtol=1e-5, atol=1e-7)


def test_fedbuff_lr_norm_arrivals_default_matches_quorum_path():
    """arrivals=None falls back to the distinct active count sum(act) — so
    feeding the explicit K of a duplicate-free (quorum, K = S) round is
    bit-identical to the derived path."""
    fed = FedConfig(n_clients=6, active_frac=0.5, fedbuff_lr_norm=True)
    state, batch, step, key = make_problem(fed)
    act = jnp.asarray([True, False, True, False, True, False])
    out_a, m_a = step(state, batch, key, act=act)
    out_b, m_b = step(state, batch, key, act=act, arrivals=np.int32(3))
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a FedBuff buffer with duplicate deliveries (K > S) steps further
    out_c, _ = step(state, batch, key, act=act, arrivals=np.int32(5))
    z_a = np.asarray(jax.tree.leaves(out_a.z)[0])
    z_c = np.asarray(jax.tree.leaves(out_c.z)[0])
    z_0 = np.asarray(jax.tree.leaves(state.z)[0])
    np.testing.assert_allclose(z_c - z_0, (5.0 / 3.0) * (z_a - z_0),
                               rtol=1e-5, atol=1e-7)


def test_fedbuff_lr_norm_off_ignores_arrivals():
    """Default off = bit-compat: the arrivals kwarg must not leak into the
    unnormalized round."""
    fed = FedConfig(n_clients=4, active_frac=1.0)
    state, batch, step, key = make_problem(fed)
    out_a, _ = step(state, batch, key)
    out_b, _ = step(state, batch, key, arrivals=np.int32(2))
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dual_step_damped_by_absence():
    """Eq. 22: a returning client's phi step shrinks with its absence
    length (pre-round t - tau), not with the consumption-age vector that is
    0 wherever the step applies."""
    fed = FedConfig(n_clients=4, active_frac=1.0, staleness_decay="poly",
                    staleness_poly_a=1.0)
    state, batch, step, key = make_problem(fed)
    state, _ = step(state, batch, key)      # t=1, tau=0 everywhere
    t10 = jnp.asarray(10, jnp.int32)
    fresh = state._replace(t=t10, tau=jnp.full((4,), 9, jnp.int32))
    absent = state._replace(t=t10, tau=jnp.zeros((4,), jnp.int32))
    act = jnp.ones((4,), bool)
    out_f, _ = step(fresh, batch, key, act=act)
    out_a, _ = step(absent, batch, key, act=act)

    def dphi(out, ref):
        return sum(float(np.abs(np.asarray(a, np.float32)
                                - np.asarray(b, np.float32)).sum())
                   for a, b in zip(jax.tree.leaves(out.phi),
                                   jax.tree.leaves(ref.phi)))

    assert 0 < dphi(out_a, absent) < dphi(out_f, fresh)


def test_external_stale_vector_override():
    """A supplied staleness vector changes the round under poly decay (and
    is a no-op under constant decay)."""
    fed = FedConfig(n_clients=6, active_frac=1.0, staleness_decay="poly",
                    staleness_poly_a=0.9)
    state, batch, step, key = make_problem(fed)
    warm, _ = step(state, batch, key)   # t=1, so decay weights differ from 1
    fresh = jnp.zeros((6,), jnp.float32)
    old = jnp.full((6,), 50.0, jnp.float32)
    s_fresh, _ = step(warm, batch, key, stale=fresh)
    s_old, _ = step(warm, batch, key, stale=old)
    z_fresh = np.asarray(jax.tree.leaves(s_fresh.z)[0])
    z_old = np.asarray(jax.tree.leaves(s_old.z)[0])
    assert not np.allclose(z_fresh, z_old)


def test_convergence_rate_order():
    """Theorem 1 sanity: rounds-to-threshold grows no faster than ~1/gap^2
    (we check T(0.5 gap) <= 6x T(gap) on a smooth problem)."""
    fed = FedConfig(n_clients=6, active_frac=1.0, attack="none",
                    alpha_w=5e-3)
    state, batch, step, key = make_problem(fed)
    gaps = []
    for t in range(200):
        state, m = step(state, batch, jax.random.fold_in(key, t))
        gaps.append(float(m["consensus_gap"]))
    g0 = gaps[5]

    def t_at(thresh):
        for i, g in enumerate(gaps):
            if g <= thresh:
                return i
        return len(gaps)

    t1, t2 = t_at(g0 * 0.5), t_at(g0 * 0.25)
    assert t2 <= max(6 * max(t1, 1), 40), (t1, t2)


# ---------------- internal age-aware sampler --------------------------------
def test_internal_age_aware_activates_overdue_clients():
    """Any client whose age reached the threshold at round start must be
    admitted (when the overdue set fits in S) — the sampler-level staleness
    bound, with no external schedule at all."""
    fed = FedConfig(n_clients=8, active_frac=0.5, internal_select="age_aware",
                    internal_age_threshold=3.0)
    state, batch, step, key = make_problem(fed)
    for t in range(30):
        age = np.asarray(state.t - state.tau)
        overdue = np.flatnonzero(age >= 3.0)
        state, m = step(state, batch, jax.random.fold_in(key, t))
        assert int(m["n_active"]) == 4
        if t == 0:
            continue          # round 0: tau==0 cannot identify the active set
        act = np.asarray(state.tau) == t          # tau resets on activation
        assert act.sum() == 4
        if overdue.size <= 4:
            assert act[overdue].all(), (t, overdue, act)


def test_internal_age_aware_bounds_staleness():
    """Over a long horizon the age-aware sampler keeps max age under
    threshold + ceil(C / S) (overdue admissions may queue for one sweep)."""
    fed = FedConfig(n_clients=10, active_frac=0.3,
                    internal_select="age_aware")
    thr = bafdp.default_age_threshold(10, 0.3)
    state, batch, step, key = make_problem(fed)
    max_age = 0
    for t in range(80):
        age = int(np.max(np.asarray(state.t - state.tau)))
        max_age = max(max_age, age)
        state, _ = step(state, batch, jax.random.fold_in(key, t))
    assert max_age <= thr + int(np.ceil(10 / 3)), (max_age, thr)


def test_internal_age_aware_jit_stable():
    """The age-aware branch traces once: t - tau is a traced argument,
    not a recompile trigger."""
    fed = FedConfig(n_clients=6, active_frac=0.5,
                    internal_select="age_aware")
    state, batch, _, key = make_problem(fed)
    from repro.core.privacy import gaussian_c3

    traces = {"n": 0}

    def counted(st, b, k):
        traces["n"] += 1
        return bafdp.bafdp_round(
            st, b, k,
            local_loss=lambda p, bb, kk, e: mse_loss(
                p, perturb_inputs(kk, bb[0], e, 0.02), bb[1], CFG),
            fed=fed, c3=gaussian_c3(CFG.d_x + CFG.d_y, fed.dp_delta,
                                    fed.dp_sensitivity),
            n_samples=200, d_dim=CFG.d_x + CFG.d_y,
            byz_mask=byz_mask(fed.n_clients, fed.n_byzantine))

    step = jax.jit(counted)
    for t in range(6):
        state, _ = step(state, batch, jax.random.fold_in(key, t))
    assert traces["n"] == 1


def test_internal_age_aware_tie_break_is_uniform():
    """Equally-overdue clients are admitted uniformly at random — a fused
    float32 score (age * 1e6 + u) would round the tie-break away past age
    ~7 and deterministically starve high client ids."""
    C, thr = 64, 4.0
    age = jnp.concatenate([jnp.full((32,), 8.0), jnp.zeros((32,))])
    counts = np.zeros(C)
    for seed in range(200):
        counts += np.asarray(bafdp.active_mask_age_aware(
            jax.random.PRNGKey(seed), C, 0.25, age, thr))
    # 16 slots, 32 equally-overdue candidates: ~100 wins each over 200
    assert counts[:32].min() > 60 and counts[:32].max() < 140, counts[:32]
    assert counts[32:].sum() == 0      # fresh never beat an overdue client


def test_internal_uniform_unchanged_and_unknown_select_raises():
    """internal_select='uniform' is bit-identical to the seed sampler; an
    unknown policy is a hard error."""
    fed_a = FedConfig(n_clients=8, active_frac=0.5)
    fed_b = FedConfig(n_clients=8, active_frac=0.5,
                      internal_select="uniform")
    state_a, batch, step_a, key = make_problem(fed_a)
    state_b, _, step_b, _ = make_problem(fed_b)
    for t in range(4):
        kt = jax.random.fold_in(key, t)
        state_a, m_a = step_a(state_a, batch, kt)
        state_b, m_b = step_b(state_b, batch, kt)
        np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                                   rtol=0)
    bad = FedConfig(n_clients=4, internal_select="round_robin")
    state, batch, step, key = make_problem(bad)
    with pytest.raises(ValueError, match="internal_select"):
        step(state, batch, key)


# ---------------- Taylor staleness compensation ----------------------------
def test_compensation_none_matches_pr1_numerics():
    """staleness_compensation='none' must reproduce the PR-1 round
    bit-for-bit: these losses pin the PR-1 trajectory (seed 0, fixed
    masks).  They were re-captured when JAX's default for
    ``jax_threefry_partitionable`` became True, which changed the random
    stream every key draws; under ``JAX_THREEFRY_PARTITIONABLE=0`` the
    original PR-1 capture (12.361677, 9.110292, ...) still holds."""
    ref = {
        "constant": [12.079330, 9.519585, 10.229588, 7.363866,
                     5.995084, 7.433078, 4.207879, 3.323254],
        "poly": [12.079330, 9.519585, 10.229588, 7.363866,
                 5.995103, 7.433189, 4.207888, 3.323286],
    }
    for decay, expect in ref.items():
        fed = FedConfig(n_clients=6, active_frac=0.5, byzantine_frac=0.2,
                        attack="sign_flip", staleness_decay=decay)
        state, batch, step, key = make_problem(fed)
        rng = np.random.RandomState(42)
        losses = []
        for t in range(8):
            mask = jnp.asarray(rng.rand(6) < 0.6)
            state, m = step(state, batch, jax.random.fold_in(key, t),
                            act=mask)
            losses.append(float(m["loss"]))
        np.testing.assert_allclose(losses, expect, rtol=1e-5,
                                   err_msg=f"decay={decay}")
        assert state.comp is None


def test_compensation_changes_stale_rounds():
    """With inactive (stale) clients, the Taylor correction must move the
    consensus relative to the uncompensated round."""
    base = FedConfig(n_clients=6, active_frac=0.5, staleness_decay="poly")
    taylor = FedConfig(n_clients=6, active_frac=0.5, staleness_decay="poly",
                       staleness_compensation="taylor")
    outs = {}
    for name, fed in (("none", base), ("taylor", taylor)):
        state, batch, step, key = make_problem(fed)
        rng = np.random.RandomState(5)
        for t in range(6):
            mask = jnp.asarray(rng.rand(6) < 0.5)
            state, m = step(state, batch, jax.random.fold_in(key, t),
                            act=mask)
        outs[name] = (np.asarray(jax.tree.leaves(state.z)[0]), m)
    assert not np.allclose(outs["none"][0], outs["taylor"][0])
    assert float(outs["taylor"][1]["compensation_norm"]) > 0
    assert float(outs["none"][1]["compensation_norm"]) == 0


def test_compensation_converges_under_attack():
    fed = FedConfig(n_clients=8, active_frac=0.4, byzantine_frac=0.25,
                    attack="sign_flip", staleness_decay="poly",
                    staleness_compensation="taylor")
    _, losses, m = run(fed, n_rounds=60)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 1.05
    assert np.isfinite(float(m["compensation_norm"]))


def test_compensation_cache_frozen_for_inactive():
    """The momentum proxy is per-client: inactive clients keep the cached
    direction from their last participation."""
    fed = FedConfig(n_clients=4, active_frac=1.0,
                    staleness_compensation="taylor")
    state, batch, step, key = make_problem(fed)
    state, _ = step(state, batch, key)                  # everyone active
    act = jnp.asarray([True, True, False, False])
    new, _ = step(state, batch, jax.random.fold_in(key, 1), act=act)
    for c0, c1 in zip(jax.tree.leaves(state.comp), jax.tree.leaves(new.comp)):
        a, b = np.asarray(c0), np.asarray(c1)
        changed = ~np.all(np.isclose(a, b), axis=tuple(range(1, a.ndim)))
        np.testing.assert_array_equal(changed, np.asarray(act))


def test_compensation_clipped_extrapolation():
    """Ages beyond compensation_clip must be treated as the clip: a stale
    vector of 50 and one of clip rounds give the identical round."""
    # constant decay isolates the compensation path: the only staleness-
    # dependent term is the Taylor correction, which must saturate at clip
    fed = FedConfig(n_clients=6, active_frac=1.0,
                    staleness_compensation="taylor", compensation_clip=5.0)
    state, batch, step, key = make_problem(fed)
    warm, _ = step(state, batch, key)
    clip_v = jnp.full((6,), 5.0, jnp.float32)
    huge_v = jnp.full((6,), 50.0, jnp.float32)
    out_c, _ = step(warm, batch, key, stale=clip_v)
    out_h, _ = step(warm, batch, key, stale=huge_v)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(out_c.z)[0]),
        np.asarray(jax.tree.leaves(out_h.z)[0]), rtol=1e-6)
    # below the clip the correction must still differ
    out_lo, _ = step(warm, batch, key, stale=jnp.full((6,), 1.0, jnp.float32))
    assert not np.allclose(np.asarray(jax.tree.leaves(out_lo.z)[0]),
                           np.asarray(jax.tree.leaves(out_c.z)[0]))


def test_compensation_validation():
    fed = FedConfig(n_clients=4, staleness_compensation="newton")
    state, batch, step, key = make_problem(fed)
    with pytest.raises(ValueError, match="staleness_compensation"):
        step(state, batch, key)
    # a taylor config needs a state initialized with the comp cache
    fed_none = FedConfig(n_clients=4)
    state_none, batch, _, key = make_problem(fed_none)
    fed_taylor = FedConfig(n_clients=4, staleness_compensation="taylor")
    _, _, step_taylor, _ = make_problem(fed_taylor)
    with pytest.raises(ValueError, match="FedState.comp"):
        step_taylor(state_none._replace(comp=None), batch, key)


def test_compensation_noop_when_fully_synchronous():
    """With full participation every round no client is ever stale: the
    taylor round must equal the uncompensated round bit-for-bit (the comp
    cache updates, but never feeds back)."""
    kw = dict(n_clients=5, active_frac=1.0, staleness_decay="constant")
    fed_n = FedConfig(**kw)
    fed_t = FedConfig(**kw, staleness_compensation="taylor")
    state_n, batch, step_n, key = make_problem(fed_n)
    state_t, _, step_t, _ = make_problem(fed_t)
    act = jnp.ones((5,), bool)
    for t in range(5):
        kt = jax.random.fold_in(key, t)
        state_n, m_n = step_n(state_n, batch, kt, act=act)
        state_t, m_t = step_t(state_t, batch, kt, act=act)
        np.testing.assert_allclose(float(m_n["loss"]), float(m_t["loss"]),
                                   rtol=1e-6)
    for a, b in zip(jax.tree.leaves((state_n.W, state_n.z, state_n.phi)),
                    jax.tree.leaves((state_t.W, state_t.z, state_t.phi))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)
