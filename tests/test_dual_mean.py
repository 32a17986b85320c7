"""The Eq. (22) dual mean of the fold (``ops.dual_mean``).

The XLA path is the row-order left-fold the dense<->sparse bit-parity
contract rests on; the TPU paths (``"pallas"``, ``"interpret"``) reduce
the leaf in one vectorised f32 pass.  This suite pins that the XLA path is
the left-fold bit for bit, that the vectorised pass agrees with it to f32
tolerance on the forecasters' leaf shapes (padding rows, a duplicated
client, an S that is not a multiple of 8) and holds no loop, and that a
round whose fold takes the TPU branch ends where the CPU round does, to
f32 tolerance.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FedConfig, MLP_H1
from repro.core import bafdp, init_fed_state
from repro.core.byzantine import byz_mask
from repro.core.privacy import gaussian_c3, perturb_inputs
from repro.kernels import ops, ref
from repro.models.forecasting import init_forecaster, mse_loss

# the MLP forecasters' leaf shapes past the client axis: layer-0 weight,
# hidden bias, layer-3 weight of MLP_H24 and of MLP_H1, the 1-wide bias
LEAVES = [(22, 128), (128,), (64, 24), (64, 1), (1,)]


def _block(S, leaf, seed=0):
    """An (S, *leaf) dual block and its (S,) weights: staleness-like
    weights in (0, 1], row 4 a duplicate delivery of row 2, and the last
    three rows zero-weight padding."""
    rng = np.random.RandomState(seed)
    phi = rng.randn(S, *leaf).astype(np.float32)
    phi[4] = phi[2]
    w = rng.uniform(0.1, 1.0, S).astype(np.float32)
    w[-3:] = 0.0
    return jnp.asarray(phi), jnp.asarray(w)


@pytest.mark.parametrize("S", [13, 40])
@pytest.mark.parametrize("leaf", LEAVES, ids=str)
def test_vectorised_matches_left_fold(S, leaf):
    phi, w = _block(S, leaf)
    n = 3 * S
    fold = ops.dual_mean(phi, w, n, impl="xla")
    vec = ops.dual_mean(phi, w, n, impl="interpret")
    assert vec.shape == fold.shape == (int(np.prod(leaf)),)
    assert vec.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(vec), np.asarray(fold),
                               rtol=1e-5, atol=1e-6)
    exact = np.einsum("s,sd->d", np.asarray(w, np.float64),
                      np.asarray(phi, np.float64).reshape(S, -1)) / n
    np.testing.assert_allclose(np.asarray(vec), exact, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("leaf", LEAVES, ids=str)
def test_xla_path_is_the_left_fold(leaf):
    """Bit for bit what the round computed inline before it called
    ``dual_mean``: the left-fold over the (S, D) view, over n, in a jit."""
    S, n = 13, 29
    phi, w = _block(S, leaf, seed=1)
    inline = jax.jit(
        lambda p, v: ref.fold_weighted_rowsum(p.reshape(S, -1), v) / n)
    np.testing.assert_array_equal(
        np.asarray(ops.dual_mean(phi, w, n, impl="xla")),
        np.asarray(inline(phi, w)))


def test_vectorised_path_holds_no_loop():
    phi, w = _block(13, (64, 24))

    def jaxpr(impl):
        return str(jax.make_jaxpr(
            lambda p, v: ops.dual_mean(p, v, 100, impl=impl))(phi, w))

    # the left-fold's fori_loop traces to a scan (a while once lowered)
    assert "scan" in jaxpr("xla")           # the check sees a loop
    vec = jaxpr("interpret")
    for prim in ("scan", "while", "dot_general"):
        assert prim not in vec, prim


# ---------------------------------------------------------------------------
# one round with the fold on the TPU branch
# ---------------------------------------------------------------------------
CFG = MLP_H1
C = 6


@pytest.fixture
def force_tpu_fold(monkeypatch):
    """Calling the fixture's value resolves ``impl="auto"`` to the TPU
    branch (Pallas in interpret mode) from then on.  Jitted wrappers cache
    their traces, so the caches are cleared when the patch goes on and
    again once it is undone: no trace made under it outlives the test."""
    def force():
        jax.clear_caches()
        monkeypatch.setattr(
            ops, "_resolve",
            lambda impl: "interpret" if impl == "auto" else impl)
    yield force
    monkeypatch.undo()
    jax.clear_caches()


def _round_problem():
    fed = FedConfig(n_clients=C, active_frac=0.5, staleness_decay="poly",
                    consensus_scope="active")
    key = jax.random.PRNGKey(3)
    state = init_fed_state(key, lambda k: init_forecaster(k, CFG), fed)
    X = jax.random.normal(key, (C, 8, CFG.d_x))
    Y = jnp.sum(X[..., :3], -1, keepdims=True) * 0.5
    c3 = gaussian_c3(CFG.d_x + CFG.d_y, fed.dp_delta, fed.dp_sensitivity)

    def local_loss(p, batch, k, eps):
        x, y = batch
        return mse_loss(p, perturb_inputs(k, x, eps, 0.02), y, CFG)

    kw = dict(local_loss=local_loss, fed=fed, c3=c3, n_samples=200,
              d_dim=CFG.d_x + CFG.d_y,
              byz_mask=byz_mask(fed.n_clients, fed.n_byzantine))
    return state, (X, Y), key, kw


# (idx, stale, weight) padded rows, shuffled; the second round delivers
# client 2 twice (FedBuff), which only the sparse round can carry
ROUNDS = [
    ([4, 1, 3, C, C], [2, 0, 1, 0, 0], [1, 1, 1, 0, 0]),
    ([0, 5, 2, 2, C], [1, 3, 2, 0, 0], [1, 1, 1, 1, 0]),
    ([1, 2, 3, 4, 5], [0, 1, 0, 2, 1], [1, 1, 1, 1, 1]),
]
UNIQUE_ROUNDS = [ROUNDS[0], ROUNDS[2]]


def _run(rounds, dense=False):
    state, batch, key, kw = _round_problem()
    step = jax.jit(functools.partial(
        bafdp.bafdp_round if dense else bafdp.bafdp_round_sparse, **kw))
    for t, (idx, stale, weight) in enumerate(rounds):
        kt = jax.random.fold_in(key, t)
        if dense:
            act = np.zeros(C, bool)
            stale_c = np.zeros(C, np.float32)
            for i, s, v in zip(idx, stale, weight):
                if v:
                    act[i], stale_c[i] = True, s
            state, _ = step(state, batch, kt, act=jnp.asarray(act),
                            stale=jnp.asarray(stale_c))
        else:
            state, _ = step(state, batch, kt,
                            idx=jnp.asarray(idx, jnp.int32),
                            stale=jnp.asarray(stale, jnp.float32),
                            weight=jnp.asarray(weight, jnp.float32))
    return jax.device_get(state)


def _assert_close(a, b, msg):
    for (pa, la), (_, lb) in zip(jax.tree_util.tree_leaves_with_path(a),
                                 jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_allclose(
            np.asarray(la, np.float64), np.asarray(lb, np.float64),
            rtol=1e-5, atol=1e-6, err_msg=f"{msg} {jax.tree_util.keystr(pa)}")


def test_round_on_the_tpu_fold_matches_the_cpu_round(force_tpu_fold):
    """Three sparse rounds (padding, a duplicate delivery, staleness
    decay) with the fold on the TPU branch end where the CPU rounds do, to
    f32 tolerance; and on that branch the masked dense round and the
    gathered sparse round still agree to f32 tolerance."""
    cpu = _run(ROUNDS)
    cpu_unique = _run(UNIQUE_ROUNDS)
    force_tpu_fold()
    state, batch, key, kw = _round_problem()
    idx, stale, weight = (jnp.asarray(a, jnp.float32) for a in ROUNDS[0])
    jaxpr = str(jax.make_jaxpr(functools.partial(
        bafdp.bafdp_round_sparse, **kw))(
            state, batch, key, idx=idx.astype(jnp.int32), stale=stale,
            weight=weight))
    assert "pallas_call" in jaxpr          # the seam reached the fold
    tpu = _run(ROUNDS)
    _assert_close(tpu, cpu, "sparse, TPU fold vs CPU fold:")
    tpu_unique = _run(UNIQUE_ROUNDS)
    _assert_close(tpu_unique, cpu_unique,
                  "duplicate-free, TPU fold vs CPU fold:")
    _assert_close(_run(UNIQUE_ROUNDS, dense=True), tpu_unique,
                  "TPU fold, dense vs sparse:")
