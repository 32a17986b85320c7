"""BAFDP — the paper's algorithm (Algorithm 1, Eq. 15-22), as one jittable
round function over stacked client pytrees.

Faithful pieces:
  * Step 1 (active clients): omega update Eq. (18) — grad of the local DRO
    objective ``g(w_i) + rho_i^t G(w_i)`` plus the Lagrangian terms
    ``-phi_i`` and the L1 subgradient ``psi sign(w_i - z)``; eps update
    Eq. (19) projected to [eps_min, a].
  * Step 2 (server): consensus update Eq. (20) with the **Byzantine clients'
    corrupted messages inside the sign sum**, dual update Eq. (21) with the
    ``a1^t`` regularizer of Eq. (17) / Setting 1.
  * Step 3 (active clients): pairwise dual update Eq. (22) with ``a2^t``.
  * Asynchrony: an active mask (S of M) freezes inactive clients; the server
    consumes their stale ``w_i`` exactly as Algorithm 1 does; active clients
    sync ``z_local`` only when activated (staleness is real, not cosmetic).
    The mask may be supplied externally (event-driven schedules from
    ``core/async_engine``); per-client staleness ``t - tau_i`` (Definition
    2's t-hat) is tracked in ``FedState.tau`` and can down-weight stale
    contributions via FedAsync-style decay (``FedConfig.staleness_decay``)
    and/or Taylor-correct them via DC-ASGD-style compensation
    (``FedConfig.staleness_compensation`` with the ``FedState.comp``
    momentum cache).

The Eq. (20) consensus update routes through ONE dispatch for every
sign-sum flavour — plain mean, staleness-decayed, and the quantized int8
wire format — :func:`repro.kernels.ops.sign_consensus`, which runs the
fused Pallas kernel on TPU and the XLA oracle elsewhere.  The wire format
(``FedConfig.sign_message``) composes freely with ``staleness_decay`` and
``staleness_compensation``: an int8 sign message is lossless (see
distributed/collectives.py), so there is nothing to forbid.

Beyond-paper options (recorded separately in EXPERIMENTS.md Section Perf):
``local_steps`` K>1 (consensus every K rounds), ``sign_message="int8"``
(1 byte/coordinate consensus collective), and ``fedbuff_lr_norm`` (scale
the consensus step of a K-arrivals buffered round by K/C).

Scale: :func:`bafdp_round_sparse` is the **active-subset round path** —
the same round in O(S) per-round compute/memory over the per-client
leaves (gather the S winner rows, update, scatter back), for S-of-many
fleets where O(C) per round is the wall (C=1M smoke in CI).  It requires
``FedConfig.consensus_scope="active"``; the dense round under that scope
runs the same code path over the full-width masked block and is the
bit-compat oracle (``tests/test_sparse_round.py``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import (
    AccumulationDtypeRule,
    MemoryContractRule,
    contract as fedlint_contract,
)
from repro.configs.base import FedConfig
from repro.core import aggregators as agg_lib
from repro.core import byzantine as byz_lib
from repro.core import dro
from repro.core.fed_state import (
    FedState,
    consensus_gap,
    gather_clients,
    scatter_clients,
)
from repro.core.privacy import eps_feasible
from repro.distributed import collectives
from repro.kernels import ops as kops
from repro.kernels import ref as kref

# local_loss(params_i, batch_i, key_i, eps_i) -> scalar
LocalLoss = Callable[[Any, Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def reg_decay(alpha: float, t, power: float) -> jnp.ndarray:
    """a^t = 1 / (alpha (t+1)^power)  (Setting 1)."""
    return 1.0 / (alpha * jnp.power(t.astype(jnp.float32) + 1.0, power))


def active_mask(key, n_clients: int, active_frac: float) -> jnp.ndarray:
    """S-of-M participation for this round (uniformly random active set)."""
    s = max(1, int(round(n_clients * active_frac)))
    perm = jax.random.permutation(key, n_clients)
    rank = jnp.argsort(perm)
    return rank < s


def default_age_threshold(n_clients: int, active_frac: float) -> int:
    """2 * ceil(C / S) — the same default the engine-side
    :class:`repro.core.schedule.AgeAwareSelection` resolves to."""
    s = max(1, int(round(n_clients * active_frac)))
    return 2 * math.ceil(n_clients / s)


def active_mask_age_aware(key, n_clients: int, active_frac: float,
                          age, age_threshold: float) -> jnp.ndarray:
    """Age-aware S-of-M sampler: clients whose age ``t - tau_i`` reached
    ``age_threshold`` are admitted first (oldest first), the remaining
    slots are filled uniformly at random — so internally-sampled training
    (no external schedule) also bounds max staleness at roughly
    ``age_threshold + ceil(C / S)``.  Jittable: ``age`` may be traced."""
    s = max(1, int(round(n_clients * active_frac)))
    u = jax.random.uniform(key, (n_clients,))
    agef = jnp.asarray(age).astype(jnp.float32)
    # two-key sort, NOT a single fused score: adding u to age * 1e6 in
    # float32 rounds the tie-break away past age ~7 and silently biases
    # selection toward low client ids.  Primary key: overdue clients
    # outrank every fresh one (fresh collapse to -1), older first;
    # secondary key: the uniform draw breaks ties, so equally-overdue
    # clients — and all fresh clients — are admitted uniformly at random.
    prim = jnp.where(agef >= age_threshold, agef, -1.0)
    idx = jnp.lexsort((u, -prim))
    return jnp.zeros((n_clients,), bool).at[idx[:s]].set(True)


def compensate_stale(W_msg: Any, comp: Any, age, fed: FedConfig) -> Any:
    """First-order Taylor correction of stale client messages (DC-ASGD
    flavour, arXiv:1609.08326, adapted to parameter messages).

    A client whose params the server consumes at age ``d`` missed ``d``
    local steps; extrapolate them along the cached per-client momentum
    proxy ``comp`` (EWMA of its last observed update direction):

        w~_i = w_i - alpha_w * compensation_scale * min(d, clip) * comp_i

    ``age`` is (C,); clients with age 0 are untouched.  Returns fp32 leaves.

    ``fed.compensation_scale_mode="per_client"`` additionally damps each
    row's extrapolation by ``ref / (rms_i + ref)`` where ``rms_i`` is the
    rms magnitude of that row's ``comp`` across all leaves: a client whose
    momentum proxy is large or noisy extrapolates less (its first-order
    direction is less trustworthy), a quiet client keeps the full global
    scale.  The damping reads only row i of ``comp`` — row-local, so the
    masked dense block and the gathered sparse block compute bit-identical
    scales (the dense<->sparse parity contract).
    """
    a = (jnp.minimum(age.astype(jnp.float32), fed.compensation_clip)
         * fed.alpha_w * fed.compensation_scale)
    if fed.compensation_scale_mode == "per_client":
        R = age.shape[0]
        sq = jnp.zeros((R,), jnp.float32)
        n_inner = 0
        for c in jax.tree.leaves(comp):
            cf = c.astype(jnp.float32).reshape(R, -1)
            sq = sq + jnp.sum(jnp.square(cf), axis=1)
            n_inner += cf.shape[1]
        rms = jnp.sqrt(sq / float(max(n_inner, 1)))
        a = a * (fed.compensation_ref / (rms + fed.compensation_ref))
    elif fed.compensation_scale_mode != "global":
        raise ValueError(
            f"unknown compensation_scale_mode: "
            f"{fed.compensation_scale_mode!r} "
            "(expected 'global' or 'per_client')")

    def f(w, c):
        al = a.reshape((-1,) + (1,) * (w.ndim - 1))
        return w.astype(jnp.float32) - al * c

    return jax.tree.map(f, W_msg, comp)


def staleness_weights(stale, fed: FedConfig) -> jnp.ndarray:
    """FedAsync staleness decay s(d), d = t - tau_i (arXiv:1903.03934 Sec 5.2).

    ``constant`` is exactly 1 (seed behaviour); ``hinge`` keeps full weight
    up to ``staleness_hinge_b`` rounds then decays as 1/(a (d - b) + 1);
    ``poly`` decays as (d + 1)^-a.
    """
    d = jnp.maximum(stale.astype(jnp.float32), 0.0)
    if fed.staleness_decay == "constant":
        return jnp.ones_like(d)
    if fed.staleness_decay == "hinge":
        # s = 1/(a (d - b) + 1) for d > b: continuous at d = b (AFO Sec 5.2)
        a, b = fed.staleness_hinge_a, fed.staleness_hinge_b
        return jnp.where(d <= b, 1.0, 1.0 / (a * (d - b) + 1.0))
    if fed.staleness_decay == "poly":
        return jnp.power(d + 1.0, -fed.staleness_poly_a)
    raise ValueError(f"unknown staleness_decay: {fed.staleness_decay!r}")


def _robust_broadcast(W_srv: Any, weight, z: Any, fed: FedConfig) -> Any:
    """``FedConfig.robust_consensus``: collapse the round's consensus
    messages to ONE weight-aware robust aggregate (``aggregators.
    robust_block``) and broadcast it to every block row.  The unchanged
    Eq. (20) fold then computes

        z - alpha_z * (phi_mean + psi * (sum_j s_j) * sign(z - w_rob) / C)

    so staleness decay, ``fedbuff_lr_norm`` and the int8 wire format
    compose untouched, and the masked-dense / gathered-sparse bit-parity
    contract holds (the aggregate is width-invariant; the broadcast rows
    fold identically)."""
    w_rob = agg_lib.robust_block(
        fed.robust_consensus, W_srv, weight, z,
        trim_frac=fed.robust_trim_frac, n_byzantine=fed.n_byzantine,
        clip_tau=fed.robust_clip_tau, clip_iters=fed.robust_clip_iters)
    return jax.tree.map(
        lambda w_l, r_l: jnp.broadcast_to(
            r_l.astype(jnp.float32)[None], w_l.shape).astype(w_l.dtype),
        W_srv, w_rob)


def _per_client_objective(local_loss: LocalLoss, fed: FedConfig, c3: float,
                          n_samples: int, d_dim: int):
    """Builds f(w_i, batch_i, key_i, eps_i, z_i, phi_i) = the differentiable
    part of client i's Lagrangian (everything in Eq. 16 that involves w)."""

    def obj(w_i, batch_i, key_i, eps_i):
        g = local_loss(w_i, batch_i, key_i, eps_i)
        G = dro.lipschitz_surrogate(w_i, fed.lipschitz_surrogate)
        rho_i = fed.dro_weight * dro.rho(eps_i, n_samples, d_dim, c3, fed)
        return g + rho_i * G, (g, G)

    return obj


def _client_block_updates(W, z_local, phi, eps, lam, opt, comp, batch,
                          noise_keys, cnt_inc, *, local_loss: LocalLoss,
                          fed: FedConfig, c3: float, n_samples: int,
                          d_dim: int, taylor: bool):
    """Steps 1 + 3-prep of Algorithm 1 over a stacked client block:
    per-client grads, DP-perturbed loss, optional Adam preconditioning,
    the Taylor-compensation EWMA proposal, and the Eq. (19) eps proposal.

    Every computation here is row-independent, so the leading axis may be
    the full fleet (C — the ``consensus_scope="all"`` dense round, which
    masks inactive rows afterwards; also the full-width masked block the
    ``"active"``-scope round runs) or a gathered active-subset block
    (S_max — the sparse round, which scatters the rows back): the same
    client's row produces bit-identical proposals either way, which is
    the dense<->sparse equivalence contract.  ``cnt_inc`` is the Adam
    step-count increment per row (the activity mask for the dense round,
    all-ones for a gathered block whose every row is active).

    Returns ``(W_prop, new_opt, comp_prop, eps_prop, loss_i, g_i, G_i,
    full_grad)`` — proposals for EVERY row, unmasked.
    """
    obj = _per_client_objective(local_loss, fed, c3, n_samples, d_dim)

    def client_grads(w_i, b_i, nk, eps_i):
        (loss, (g, G)), grads = jax.value_and_grad(obj, has_aux=True)(
            w_i, b_i, nk, eps_i)
        return grads, loss, g, G

    # grads of the smooth local objective g + rho*G; the Lagrangian terms
    # d/dw [phi_i (z - w_i)] = -phi_i and the L1 subgradient are exact and
    # added OUTSIDE the (optional) Adam preconditioner — normalizing the
    # constant-magnitude psi*sign term by sqrt(v) makes it dominate near
    # convergence (measured: +40 RMSE on Table I).
    grads, loss_i, g_i, G_i = jax.vmap(client_grads)(
        W, batch, noise_keys, eps)

    R = eps.shape[0]
    if fed.grad_clip:
        # per-client global-norm clip (LM-scale stability; the paper's MLP
        # doesn't need it, billion-parameter exp-gated archs do)
        sq = jnp.zeros((R,), jnp.float32)
        for g in jax.tree.leaves(grads):
            sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)),
                              axis=tuple(range(1, g.ndim)))
        scale = jnp.minimum(1.0, fed.grad_clip
                            / jnp.maximum(jnp.sqrt(sq), 1e-9))

        def clip(g):
            return g * scale.reshape((-1,) + (1,) * (g.ndim - 1)).astype(g.dtype)

        grads = jax.tree.map(clip, grads)

    # Lagrangian pieces of Eq. 18:  -phi_i + psi * sign(w_i - z_local_i)
    def lag_term(w, zl, phi_l):
        s = jnp.sign(w.astype(jnp.float32) - zl.astype(jnp.float32))
        return fed.psi * s - phi_l.astype(jnp.float32)

    lag_grad = jax.tree.map(lag_term, W, z_local, phi)
    full_grad = jax.tree.map(lambda a, b: a.astype(jnp.float32) + b,
                             grads, lag_grad)

    # omega step: plain SGD (faithful Eq. 18) or Adam (paper's Section V-D)
    new_opt = opt
    if fed.omega_optimizer == "adam" and opt is not None:
        cnt = opt["count"] + cnt_inc.astype(jnp.int32)
        b1, b2 = fed.adam_b1, fed.adam_b2

        def upd_m(m, g):
            return b1 * m + (1 - b1) * g.astype(jnp.float32)

        def upd_v(v, g):
            return b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32))

        m = jax.tree.map(upd_m, opt["m"], grads)
        v = jax.tree.map(upd_v, opt["v"], grads)
        bc1 = 1 - b1 ** jnp.maximum(cnt, 1).astype(jnp.float32)
        bc2 = 1 - b2 ** jnp.maximum(cnt, 1).astype(jnp.float32)

        def adam_step(w, m_l, v_l, lg):
            r1 = bc1.reshape((-1,) + (1,) * (w.ndim - 1))
            r2 = bc2.reshape((-1,) + (1,) * (w.ndim - 1))
            upd = (m_l / r1) / (jnp.sqrt(v_l / r2) + fed.adam_eps)
            # consensus terms stay linear (un-preconditioned)
            return w.astype(jnp.float32) - fed.alpha_w * (upd + lg)

        W_prop = jax.tree.map(adam_step, W, m, v, lag_grad)
        new_opt = {"m": m, "v": v, "count": cnt}
    else:
        W_prop = jax.tree.map(
            lambda w, g: w.astype(jnp.float32) - fed.alpha_w * g,
            W, full_grad)

    # momentum proxy for Taylor staleness compensation (EWMA proposal)
    comp_prop = None
    if taylor:
        cb = fed.compensation_beta
        comp_prop = jax.tree.map(lambda c, g: cb * c + (1.0 - cb) * g,
                                 comp, full_grad)

    # eps update (Eq. 19):  d/deps [ (eta + c3/eps) G ] = -c3 G / eps^2
    d_eps = -fed.dro_weight * c3 * G_i \
        / jnp.square(jnp.maximum(eps, fed.eps_min)) + lam
    eps_prop = eps_feasible(eps - fed.alpha_eps * d_eps, fed)

    return W_prop, new_opt, comp_prop, eps_prop, loss_i, g_i, G_i, full_grad


def bafdp_round(state: FedState, batch: Any, key, *, local_loss: LocalLoss,
                fed: FedConfig, c3: float, n_samples: int, d_dim: int,
                byz_mask: jnp.ndarray, act: Any = None,
                stale: Any = None,
                arrivals: Any = None) -> Tuple[FedState,
                                               Dict[str, jnp.ndarray]]:
    """One asynchronous BAFDP round. ``batch`` leaves: (C, b, ...).

    ``act`` (C,) bool: externally supplied active set — e.g. the event-driven
    schedule from :mod:`repro.core.async_engine` — so training dynamics and
    wall-clock bookkeeping share one schedule.  ``None`` falls back to the
    internal uniformly-random sampler (seed behaviour).  ``stale`` (C,)
    overrides the staleness vector weighting the Eq. (20) sign sum; by
    default it is the age of the parameters the server consumes this round —
    0 for clients active now, ``t - tau_i`` (Definition 2's t - t-hat) for
    the frozen params of inactive ones — matching ``SimResult.staleness``.
    The Eq. (22) dual step is instead damped by each *returning* client's
    absence length ``t - state.tau`` (always from the internal bookkeeping,
    since the consumption-age vector is 0 wherever that step applies).

    ``arrivals``: scalar count of updates this round consumed (a FedBuff
    buffer's realized K, counting duplicate deliveries) — only read when
    ``fed.fedbuff_lr_norm`` scales the consensus step by K/C; ``None``
    falls back to the distinct active count ``sum(act)``, which equals K
    whenever no client delivered twice (the quorum server).

    ``fed.consensus_scope`` selects what the Eq. (20) server consumes:
    ``"all"`` (default, seed bit-compat) sums every client's last
    message; ``"active"`` consumes only this round's delivered messages
    and runs as :func:`bafdp_round_sparse` over the full-width masked
    block — the bit-compat oracle of the O(S) gathered path (metrics
    then follow the sparse round's block semantics).
    """
    sign_message = fed.resolved_sign_message      # validates the knob
    dual_message = fed.resolved_dual_message      # validates the knob
    if fed.staleness_compensation not in ("none", "taylor"):
        raise ValueError(
            f"unknown staleness_compensation: {fed.staleness_compensation!r}")
    if fed.consensus_scope not in ("all", "active"):
        raise ValueError(
            f"unknown consensus_scope: {fed.consensus_scope!r} "
            "(expected 'all' or 'active')")
    if fed.consensus_streaming and fed.consensus_scope != "active":
        raise ValueError(
            "consensus_streaming streams the active-scope left-fold; the "
            "'all' scope reduces by mean — set consensus_scope='active'")
    if fed.robust_consensus not in agg_lib.ROBUST_CONSENSUS_RULES:
        raise ValueError(
            f"unknown robust_consensus: {fed.robust_consensus!r} "
            f"(expected one of {agg_lib.ROBUST_CONSENSUS_RULES})")
    taylor = fed.staleness_compensation == "taylor"
    if taylor and state.comp is None:
        raise ValueError(
            "staleness_compensation='taylor' needs FedState.comp — "
            "init_fed_state with the same FedConfig")
    C = byz_mask.shape[0]
    k_act, k_noise, k_byz = jax.random.split(key, 3)
    if act is None:
        if fed.internal_select == "uniform":
            act = active_mask(k_act, C, fed.active_frac)      # (C,) bool
        elif fed.internal_select == "age_aware":
            thr = fed.internal_age_threshold if \
                fed.internal_age_threshold > 0 \
                else default_age_threshold(C, fed.active_frac)
            act = active_mask_age_aware(k_act, C, fed.active_frac,
                                        state.t - state.tau, thr)
        else:
            raise ValueError(
                f"unknown internal_select: {fed.internal_select!r}")
    else:
        act = jnp.asarray(act).astype(bool)

    if fed.consensus_scope == "active":
        # the "dense masked round" of the active scope IS the sparse round
        # run over the full-width block: every client is a block row,
        # weight = the activity mask.  One code path means the O(C) masked
        # round and the O(S) gathered round cannot drift — the equivalence
        # suite pins them bit-for-bit.  (An independent dense
        # implementation of the same reductions is NOT bit-reproducible
        # on CPU XLA: structurally different programs fuse the per-client
        # elementwise chains differently and drift ~1 ulp.)
        return bafdp_round_sparse(
            state, batch, key, local_loss=local_loss, fed=fed, c3=c3,
            n_samples=n_samples, d_dim=d_dim, byz_mask=byz_mask,
            idx=jnp.arange(C, dtype=jnp.int32), stale=stale,
            weight=act.astype(jnp.float32), arrivals=arrivals)

    t = state.t
    tau_new = jnp.where(act, t, state.tau)
    stale_v = (t - tau_new).astype(jnp.float32) if stale is None \
        else jnp.asarray(stale).astype(jnp.float32)
    s_w = staleness_weights(stale_v, fed)                     # (C,) in (0, 1]
    s_w_dual = staleness_weights((t - state.tau).astype(jnp.float32), fed)

    # ---------------- Step 1: active clients update (w_i, eps_i) ----------
    # data-poisoning attacks corrupt the malicious clients' TRAINING
    # batches before the local step; message-level attacks apply later
    batch = byz_lib.poison_batch(fed.attack, batch, byz_mask,
                                 shift=fed.traffic_shift_steps)
    noise_keys = jax.random.split(k_noise, C)
    (W_prop, new_opt, comp_prop, eps_prop, loss_i, g_i, G_i,
     full_grad) = _client_block_updates(
        state.W, state.z_local, state.phi, state.eps, state.lam, state.opt,
        state.comp, batch, noise_keys, act, local_loss=local_loss, fed=fed,
        c3=c3, n_samples=n_samples, d_dim=d_dim, taylor=taylor)

    def mask_leaves(new, old):
        m = act.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old.astype(jnp.float32)).astype(old.dtype)

    W_new = jax.tree.map(mask_leaves, W_prop, state.W)
    if fed.omega_optimizer == "adam" and state.opt is not None:
        new_opt = {
            "m": jax.tree.map(mask_leaves, new_opt["m"], state.opt["m"]),
            "v": jax.tree.map(mask_leaves, new_opt["v"], state.opt["v"]),
            "count": new_opt["count"],
        }

    # momentum proxy for Taylor staleness compensation: active clients fold
    # this round's update direction into their EWMA; inactive clients keep
    # the cached direction from their last participation.
    new_comp = state.comp
    if taylor:
        new_comp = jax.tree.map(mask_leaves, comp_prop, state.comp)

    eps_new = jnp.where(act, eps_prop, state.eps)

    # ---------------- Step 2: server updates (z, lambda) -------------------
    # Byzantine clients corrupt the message the server sees in the sign
    # sum.  client_ids defaults to arange(C) here — the fleet-shaped block
    # — so randomized draws are per-client, matching the sparse path.
    W_sent = byz_lib.apply_attack(fed.attack, k_byz, W_new, byz_mask,
                                  scale=fed.attack_scale)

    if fed.local_steps == 0:
        # structurally consensus-free round (K-local-steps off-round): the
        # sign all-reduce must be ABSENT from the program — masking it with
        # jnp.where still emits the collective (measured: identical
        # roofline).  The trainer alternates this program with the
        # consensus one.
        a1_t = reg_decay(fed.alpha_lambda, t, fed.reg_decay_pow)
        lam_new = jnp.maximum(state.lam + fed.alpha_lambda * (
            (eps_new - fed.privacy_budget_a) - a1_t * state.lam), 0.0)
        new_state = FedState(W=W_new, z=state.z, z_local=state.z_local,
                             phi=state.phi, lam=lam_new, eps=eps_new,
                             t=t + 1, opt=new_opt, tau=tau_new, comp=new_comp)
        metrics = {
            "loss": jnp.sum(loss_i * act) / jnp.maximum(jnp.sum(act), 1),
            "data_loss": jnp.sum(g_i * act) / jnp.maximum(jnp.sum(act), 1),
            "lipschitz": jnp.mean(G_i),
            "eps_mean": jnp.mean(eps_new),
            "lambda_mean": jnp.mean(lam_new),
            "consensus_gap": jnp.zeros(()),
            "n_active": jnp.sum(act),
            "staleness_mean": jnp.mean(stale_v),
            "staleness_weight_mean": jnp.mean(s_w),
            "compensation_norm": jnp.zeros(()),  # no consensus message here
        }
        return new_state, metrics

    do_consensus = (t % fed.local_steps) == (fed.local_steps - 1)

    # Taylor-correct the stale messages the server is about to consume
    # (Eq. 20 path): each client's params are extrapolated by the age the
    # server sees them at — 0 for active clients, so only stale frozen
    # params move.  Applied to W_sent, i.e. AFTER the Byzantine corruption:
    # the server cannot tell honest from malicious messages apart.
    comp_norm = jnp.zeros(())
    W_srv = W_sent
    if taylor:
        W_srv = compensate_stale(W_sent, new_comp, stale_v, fed)
        num = sum(jnp.sum(jnp.abs(a - b.astype(jnp.float32)))
                  for a, b in zip(jax.tree.leaves(W_srv),
                                  jax.tree.leaves(W_sent)))
        den = float(sum(l.size for l in jax.tree.leaves(W_sent)))
        # off-rounds (local_steps > 1) consume no server message — report 0
        # there, like the structurally consensus-free branch above
        comp_norm = jnp.where(do_consensus, num / max(den, 1.0), 0.0)

    # Byzantine-robust pre-aggregation: collapse the C consumed messages
    # (this scope consumes every client's last message, so all rows are
    # valid) to one robust aggregate before the sign fold.
    if fed.robust_consensus != "none":
        W_srv = _robust_broadcast(W_srv, None, state.z, fed)

    # Eq. (20) consensus: every sign-sum flavour (plain mean / decayed /
    # int8 wire format) goes through ONE dispatch — the fused Pallas kernel
    # on TPU, the XLA oracle elsewhere.  The decayed sum divides by C (not
    # sum(s_i)), and the int8 message is lossless, so all branches agree
    # with the pre-dispatch numerics bit-for-bit.
    z_weights = None if fed.staleness_decay == "constant" else s_w
    if fed.fedbuff_lr_norm:
        # FedBuff server-side LR normalization: a buffered round carries K
        # fresh updates out of C clients — scale the consensus step by K/C.
        k_arr = jnp.sum(act).astype(jnp.float32) if arrivals is None \
            else jnp.asarray(arrivals).astype(jnp.float32)
        lr_scale = k_arr / C

    def z_step(z_l, w_l, phi_l):
        zf = z_l.ravel()
        if dual_message == "int8":
            # the server averages the DECODED dual uploads — all-scope
            # reduction, so a plain mean over the dequantized rows
            dec = collectives.decode_dual_message(
                collectives.encode_dual_message(phi_l.reshape(C, -1)))
            phi_m = jnp.mean(dec, axis=0)
        else:
            phi_m = jnp.mean(phi_l.astype(jnp.float32), axis=0).ravel()
        z_upd = kops.sign_consensus(zf, w_l.reshape(C, -1), phi_m,
                                    z_weights, fed.psi, fed.alpha_z,
                                    message=sign_message)
        if fed.fedbuff_lr_norm:
            z_upd = (zf.astype(jnp.float32) + lr_scale
                     * (z_upd.astype(jnp.float32) - zf.astype(jnp.float32))
                     ).astype(z_l.dtype)
        return jnp.where(do_consensus, z_upd, zf).reshape(z_l.shape)

    z_new = jax.tree.map(z_step, state.z, W_srv, state.phi)

    a1_t = reg_decay(fed.alpha_lambda, t, fed.reg_decay_pow)
    lam_new = state.lam + fed.alpha_lambda * (
        (eps_new - fed.privacy_budget_a) - a1_t * state.lam)
    lam_new = jnp.maximum(lam_new, 0.0)

    # ---------------- Step 3: active clients update phi, sync z -----------
    a2_t = reg_decay(fed.alpha_phi, t, fed.reg_decay_pow)

    # Eq. 22 path: couple the dual to the client's *projected* position.
    # A client returning after absence d = t - state.tau just took ONE
    # local step from its stale base, so its remaining lag is d - 1 —
    # in particular 0 for continuously-active clients, making taylor a
    # no-op in the fully-synchronous case.
    W_dual = W_new
    if taylor:
        lag = jnp.maximum((t - state.tau).astype(jnp.float32) - 1.0, 0.0)
        W_dual = compensate_stale(W_new, new_comp, lag, fed)

    def phi_step(phi_l, z_l, w_l):
        upd = (z_l[None].astype(jnp.float32) - w_l.astype(jnp.float32)) \
            - a2_t * phi_l.astype(jnp.float32)
        if fed.staleness_decay != "constant":
            # Eq. (22) dual step damped by s(t - tau_i) with tau from BEFORE
            # this round: a client returning after a long absence takes a
            # smaller pairwise-dual step, since its w_i lags the consensus
            # it is being coupled to.
            upd = upd * s_w_dual.reshape((-1,) + (1,) * (phi_l.ndim - 1))
        new = phi_l.astype(jnp.float32) + fed.alpha_phi * upd
        m = act.reshape((-1,) + (1,) * (phi_l.ndim - 1))
        return jnp.where(m, new, phi_l.astype(jnp.float32)).astype(phi_l.dtype)

    phi_new = jax.tree.map(phi_step, state.phi, z_new, W_dual)

    def zsync(zl_l, z_l):
        m = act.reshape((-1,) + (1,) * (zl_l.ndim - 1))
        return jnp.where(m, z_l[None].astype(jnp.float32),
                         zl_l.astype(jnp.float32)).astype(zl_l.dtype)

    z_local_new = jax.tree.map(zsync, state.z_local, z_new)

    new_state = FedState(W=W_new, z=z_new, z_local=z_local_new, phi=phi_new,
                         lam=lam_new, eps=eps_new, t=t + 1, opt=new_opt,
                         tau=tau_new, comp=new_comp)
    metrics = {
        "loss": jnp.sum(loss_i * act) / jnp.maximum(jnp.sum(act), 1),
        "data_loss": jnp.sum(g_i * act) / jnp.maximum(jnp.sum(act), 1),
        "lipschitz": jnp.mean(G_i),
        "eps_mean": jnp.mean(eps_new),
        "lambda_mean": jnp.mean(lam_new),
        "consensus_gap": consensus_gap(new_state),
        "n_active": jnp.sum(act),
        "staleness_mean": jnp.mean(stale_v),
        "staleness_weight_mean": jnp.mean(s_w),
        "compensation_norm": comp_norm,
    }
    return new_state, metrics


def _sparse_round_bindings(state, batch, key, **kw):
    """Call-time dimension bindings for the sparse round's fedlint
    contract.  The dense "active"-scope oracle legitimately delegates the
    FULL-width block here (idx = arange(C)), where a (C, D) gather IS the
    working set — so ``C`` is bound only for genuine sub-fleet blocks."""
    C = kw["byz_mask"].shape[0]
    idx = kw["idx"]
    S = idx.shape[0] if hasattr(idx, "shape") else len(idx)
    return {"C": int(C)} if S < C else {}


def _sparse_round_rules(bindings):
    rules = [AccumulationDtypeRule()]
    if "C" in bindings:
        # the O(S) contract: no dense (C, D) intermediate; the state
        # write-back scatters are the sanctioned O(C)-touching producers,
        # and min_inner_elems=3 exempts the (C, 2) key-split words
        rules.append(MemoryContractRule(
            "C", allow_primitives=("scatter", "scatter-add"),
            min_inner_elems=3))
    return rules


@fedlint_contract(rules=_sparse_round_rules, bindings=_sparse_round_bindings,
                  name="bafdp_round_sparse")
def bafdp_round_sparse(state: FedState, batch: Any, key, *,
                       local_loss: LocalLoss, fed: FedConfig, c3: float,
                       n_samples: int, d_dim: int, byz_mask: jnp.ndarray,
                       idx: Any, stale: Any = None, weight: Any = None,
                       arrivals: Any = None,
                       batch_gathered: bool = None) -> Tuple[
                           FedState, Dict[str, jnp.ndarray]]:
    """The active-subset round path: one BAFDP round in O(S) per-round
    compute and memory over the big per-client leaves.

    Where :func:`bafdp_round` vmaps gradients, Adam state, Taylor
    compensation and the dual steps over all C clients and masks the
    inactive rows, this round *gathers* only the round's S winner rows of
    every per-client leaf (``W``, ``z_local``, ``phi``, ``lam``, ``eps``,
    ``tau``, ``opt.{m,v,count}``, ``comp``) into (S_max, ...) blocks, runs
    the identical per-client math on those blocks, and *scatters* the
    results back.  Only the (C,)-shaped vectors (``lam``, ``eps``,
    ``tau``, Adam ``count``, the per-client noise keys) are touched
    fleet-wide — no dense (C, D) intermediate is ever materialized, which
    is what makes a C=1M round executable.

    Contract (the padded row format ``core/schedule.Schedule.padded_rows``
    emits):

    * ``idx``: (S_max,) int client ids; the sentinel ``C`` (== n_clients)
      marks padding.  S_max is static, so the round jits once.
    * ``stale``: (S_max,) consumption age of each delivered message
      (admission age ``d``); drives the FedAsync decay ``s(d)`` and the
      Taylor extrapolation exactly like the dense round's ``stale``.
      ``None`` = all-fresh.
    * ``weight``: (S_max,) validity weights — 1 for a real delivery, 0 for
      padding.  ``None`` = all-real.  Entries with ``weight == 0`` or
      ``idx >= C`` are padding: they contribute exact zeros to every
      reduction and never write back.

    Requires ``fed.consensus_scope == "active"`` (Eq. 20/22 consume only
    the S delivered messages; the ``"all"`` scope is inherently O(C)).
    Bit-parity: for a duplicate-free round this is bit-identical to the
    dense masked round — :func:`bafdp_round` with the ``"active"`` scope,
    which runs THIS function over the full-width block (``idx`` =
    arange(C), ``weight`` = the activity mask, an O(C) masked
    computation).  The contract holds because (a) rows are stably sorted
    by client id, so the consensus left-fold visits clients in ascending
    order in both calls, (b) zero-weight rows are exact no-ops in every
    fold (see ``kernels/ref.fold_weighted_rowsum``), and (c) the masked
    and the gathered call share one code path, so XLA cannot compile
    their per-row math differently the way two structurally distinct
    programs do.  Consequently the order of ``idx`` entries never
    matters.  The bit-parity is a property of the XLA path (the CPU and
    every ``impl="xla"`` fold).  On the TPU the fold's sign sum runs in
    the tiled Pallas kernel and its dual mean as one vectorised reduction
    (``kernels/ops.dual_mean``), whose additions are grouped by tile and
    lane, so there the dense and sparse rounds agree to f32 tolerance.

    FedBuff duplicate deliveries (the same client id twice in ``idx``)
    follow a left-fold semantics: every delivery enters the Eq. (20)
    consensus sum with its own admission-age decay weight (the stable
    sort preserves arrival order between equal ids), while the state
    write-back folds the deliveries in arrival order, so the LAST one
    wins — enforced explicitly (only each client's last occurrence
    scatters; XLA's repeated-index scatter order is unspecified).  With
    per-client batches duplicate rows write identical values anyway;
    with ``batch_gathered=True`` each delivery may carry its own data
    and the last delivery's update is the one kept.  EVERY attack in
    ``byzantine.ATTACKS`` matches the dense active-scope round
    bit-for-bit: randomized corruption keys off ``(key, leaf, client
    id)`` and ``alie``'s cross-client statistics are weight-masked
    left-folds (see ``byzantine.corrupt``), so the draw a client
    receives never depends on block width or padding.

    ``batch`` leaves may be per-client ``(C, b, ...)`` (gathered here) or
    pre-gathered ``(S_max, b, ...)`` (the million-client path, where a
    per-client batch cannot exist).  ``batch_gathered`` disambiguates:
    ``None`` infers from the leading dim — C means per-client, which
    wins when S_max == C (the dense-delegation case) — and ``True`` /
    ``False`` force the interpretation (pass ``True`` explicitly if you
    feed pre-gathered blocks on a fleet where S_max could equal C).
    Metrics are computed over the delivered block (``loss``,
    ``data_loss``, ``eps_mean``, ``lambda_mean``, ``n_active`` match the
    dense round bit-for-bit / to float tolerance).  Statistics whose
    fleet-wide versions would be O(C D) are reported as block statistics
    under explicitly suffixed keys — ``lipschitz_block``,
    ``consensus_gap_block``, ``staleness_mean_block``,
    ``staleness_weight_mean_block``, ``compensation_norm_block`` — with
    the realized divisor in ``metrics_k`` (``max(sum(weight), 1)``,
    duplicate deliveries included), so a sparse history can never be
    silently compared against the dense "all"-scope round's fleet-wide
    keys of the same name.
    """
    sign_message = fed.resolved_sign_message      # validates the knob
    dual_message = fed.resolved_dual_message      # validates the knob
    if fed.consensus_streaming and fed.consensus_chunk < 1:
        raise ValueError(
            f"consensus_chunk must be >= 1, got {fed.consensus_chunk}")
    if fed.staleness_compensation not in ("none", "taylor"):
        raise ValueError(
            f"unknown staleness_compensation: {fed.staleness_compensation!r}")
    if fed.consensus_scope != "active":
        raise ValueError(
            "bafdp_round_sparse needs consensus_scope='active' (the 'all' "
            "scope sums every client's last message — inherently O(C); use "
            "the dense bafdp_round for it)")
    if fed.robust_consensus not in agg_lib.ROBUST_CONSENSUS_RULES:
        raise ValueError(
            f"unknown robust_consensus: {fed.robust_consensus!r} "
            f"(expected one of {agg_lib.ROBUST_CONSENSUS_RULES})")
    taylor = fed.staleness_compensation == "taylor"
    if taylor and state.comp is None:
        raise ValueError(
            "staleness_compensation='taylor' needs FedState.comp — "
            "init_fed_state with the same FedConfig")
    C = byz_mask.shape[0]
    # each stage of the round runs under a named scope, which the
    # compiled program keeps in its ops' op_name metadata for a
    # device trace to read (gather_clients and scatter_clients
    # carry their own); a scope changes no op
    with jax.named_scope("bafdp.gather"):
        idx = jnp.asarray(idx).astype(jnp.int32)
        (S,) = idx.shape
        w_row = jnp.ones((S,), jnp.float32) if weight is None \
            else jnp.asarray(weight).astype(jnp.float32)
        stale_row = jnp.zeros((S,), jnp.float32) if stale is None \
            else jnp.asarray(stale).astype(jnp.float32)
        # normalize padding (out-of-range id OR zero weight; negative ids
        # would otherwise clip-gather client 0 into the consensus with full
        # weight while their write-back is dropped), then canonicalize to
        # ascending client id: the stable sort puts padding last, preserves
        # FedBuff arrival order between duplicate ids, and makes the consensus
        # fold visit clients in the dense round's ascending order — so row
        # order in idx can never change the result
        w_row = jnp.where((idx < 0) | (idx >= C), 0.0, w_row)
        idx = jnp.where(w_row > 0.0, idx, C)
        order = jnp.argsort(idx, stable=True)
        idx, stale_row, w_row = idx[order], stale_row[order], w_row[order]
        gid = jnp.minimum(idx, C - 1)        # clipped gather index for padding
        # deterministic left-fold write-back: only each client's LAST delivery
        # (arrival order; rows are stably sorted) writes state.  With
        # per-client batches duplicate rows are identical anyway, but
        # pre-gathered (batch_gathered=True) deliveries may carry distinct
        # data — and XLA's scatter order for repeated indices is unspecified,
        # so last-wins must be enforced, not assumed.
        is_last = jnp.concatenate([idx[:-1] != idx[1:],
                                   jnp.ones((1,), bool)]) if S > 1 \
            else jnp.ones((1,), bool)
        write_idx = jnp.where(is_last, idx, C)

        t = state.t
        stale_v = stale_row
        s_w = staleness_weights(stale_v, fed) * w_row      # (S,) decay+mask
        tau_g = jnp.take(state.tau, gid, axis=0, mode="clip")
        s_w_dual = staleness_weights((t - tau_g).astype(jnp.float32), fed)

        k_act, k_noise, k_byz = jax.random.split(key, 3)
        del k_act  # the active set IS idx; split kept so the noise/byz key
        #            stream matches the dense round bit-for-bit
        noise_keys = jax.random.split(k_noise, C)[gid]     # O(C) keys, (C,)
        byz_g = jnp.take(byz_mask, gid, axis=0, mode="clip") & (w_row > 0.0)

    # ---------------- gather the round's S rows of every big leaf ---------
    W_g = gather_clients(state.W, gid)
    zl_g = gather_clients(state.z_local, gid)
    phi_g = gather_clients(state.phi, gid)
    eps_g = gather_clients(state.eps, gid)
    lam_g = gather_clients(state.lam, gid)
    opt_g = None
    if state.opt is not None:
        opt_g = {"m": gather_clients(state.opt["m"], gid),
                 "v": gather_clients(state.opt["v"], gid),
                 "count": gather_clients(state.opt["count"], gid)}
    comp_g = gather_clients(state.comp, gid) if state.comp is not None \
        else None

    def pick_batch(l):
        if batch_gathered is None:
            per_client = l.shape[0] == C           # wins when S == C
            if not per_client and l.shape[0] != S:
                raise ValueError(
                    f"batch leaf leading dim {l.shape[0]} is neither "
                    f"n_clients={C} nor the padded block size {S}")
        else:
            per_client = not batch_gathered
            want = C if per_client else S
            if l.shape[0] != want:
                raise ValueError(
                    f"batch_gathered={batch_gathered}: expected batch leaf "
                    f"leading dim {want}, got {l.shape[0]}")
        if per_client:
            return jnp.take(l, gid, axis=0, mode="clip")
        # pre-gathered rows arrive in the ORIGINAL idx order — permute
        # them along with the canonicalized (sorted) rows
        return jnp.take(l, order, axis=0)

    with jax.named_scope("bafdp.gather"):
        batch_g = jax.tree.map(pick_batch, batch)
    # data-poisoning attacks corrupt the malicious rows' batches before the
    # local step (row-local + deterministic, so dense/sparse stay identical)
    with jax.named_scope("bafdp.attack"):
        batch_g = byz_lib.poison_batch(fed.attack, batch_g, byz_g,
                                       shift=fed.traffic_shift_steps)

    # ---------------- Step 1 on the gathered block ------------------------
    with jax.named_scope("bafdp.local_step"):
        (W_prop, opt_prop, comp_prop, eps_prop, loss_i, g_i, G_i,
         full_grad) = _client_block_updates(
            W_g, zl_g, phi_g, eps_g, lam_g, opt_g, comp_g, batch_g, noise_keys,
            jnp.ones((S,), jnp.int32), local_loss=local_loss, fed=fed, c3=c3,
            n_samples=n_samples, d_dim=d_dim, taylor=taylor)

    # ---------------- scatter state writes back ---------------------------
    tau_new = scatter_clients(state.tau, write_idx, t)
    W_new = scatter_clients(state.W, write_idx, W_prop)
    new_opt = state.opt
    if fed.omega_optimizer == "adam" and state.opt is not None:
        new_opt = {"m": scatter_clients(state.opt["m"], write_idx,
                                        opt_prop["m"]),
                   "v": scatter_clients(state.opt["v"], write_idx,
                                        opt_prop["v"]),
                   "count": scatter_clients(state.opt["count"], write_idx,
                                            opt_prop["count"])}
    new_comp = state.comp
    comp_blocks = comp_g
    if taylor:
        new_comp = scatter_clients(state.comp, write_idx, comp_prop)
        comp_blocks = comp_prop
    eps_new = scatter_clients(state.eps, write_idx, eps_prop)

    wsum_act = jnp.maximum(jnp.sum(w_row), 1.0)

    if fed.local_steps == 0:
        # structurally consensus-free round — same contract as the dense
        # branch: the sign all-reduce must be absent from the program
        a1_t = reg_decay(fed.alpha_lambda, t, fed.reg_decay_pow)
        lam_new = jnp.maximum(state.lam + fed.alpha_lambda * (
            (eps_new - fed.privacy_budget_a) - a1_t * state.lam), 0.0)
        new_state = FedState(W=W_new, z=state.z, z_local=state.z_local,
                             phi=state.phi, lam=lam_new, eps=eps_new,
                             t=t + 1, opt=new_opt, tau=tau_new,
                             comp=new_comp)
        metrics = {
            "loss": jnp.sum(loss_i * w_row) / wsum_act,
            "data_loss": jnp.sum(g_i * w_row) / wsum_act,
            "lipschitz_block": jnp.sum(G_i * w_row) / wsum_act,
            "eps_mean": jnp.mean(eps_new),
            "lambda_mean": jnp.mean(lam_new),
            "consensus_gap_block": jnp.zeros(()),
            "n_active": jnp.sum(w_row),
            "staleness_mean_block": jnp.sum(stale_v * w_row) / wsum_act,
            "staleness_weight_mean_block": jnp.sum(
                staleness_weights(stale_v, fed) * w_row) / wsum_act,
            "compensation_norm_block": jnp.zeros(()),
            "metrics_k": wsum_act,
        }
        return new_state, metrics

    do_consensus = (t % fed.local_steps) == (fed.local_steps - 1)

    # ---------------- Step 2: server consensus over the S messages --------
    # fleet-indexed corruption: client_ids=gid keys each row's draw off the
    # CLIENT id (padding rows draw client C-1's stream but byz_g already
    # zeroes them) and weight=w_row masks alie's cross-client statistics —
    # both are what make the attack width-independent (dense bit-parity)
    with jax.named_scope("bafdp.attack"):
        W_sent = byz_lib.apply_attack(fed.attack, k_byz, W_prop, byz_g,
                                      scale=fed.attack_scale, client_ids=gid,
                                      weight=w_row)
        comp_norm = jnp.zeros(())
        W_srv = W_sent
        if taylor:
            W_srv = compensate_stale(W_sent, comp_blocks, stale_v, fed)
            # delivered-weighted per-element movement: padding / zero-weight
            # rows drop out, so the statistic is block-width-invariant — the
            # full-width masked block and the gathered block report the same
            # value (the dense "all" scope keeps its fleet-wide formula)
            per_row = jnp.zeros((S,), jnp.float32)
            for a, b in zip(jax.tree.leaves(W_srv), jax.tree.leaves(W_sent)):
                per_row = per_row + jnp.sum(
                    jnp.abs(a - b.astype(jnp.float32)).reshape(S, -1), axis=1)
            den = float(sum(l.size for l in jax.tree.leaves(W_sent))) / S
            comp_norm = jnp.where(
                do_consensus,
                jnp.sum(per_row * w_row) / (wsum_act * max(den, 1.0)), 0.0)

        # Byzantine-robust pre-aggregation over the S delivered messages
        # (weight-aware: padding rows are invisible to the robust statistics)
        if fed.robust_consensus != "none":
            W_srv = _robust_broadcast(W_srv, w_row, state.z, fed)

    if fed.fedbuff_lr_norm:
        # the padded row carries the realized K natively (duplicate
        # deliveries included) — sum(weight) IS the arrivals count
        k_arr = jnp.sum(w_row) if arrivals is None \
            else jnp.asarray(arrivals).astype(jnp.float32)
        lr_scale = k_arr / C

    # streamed folds consume chunk-bounded arrival-event blocks; 0 keeps
    # the materialized (bit-identical) single-pass fold
    chunk = fed.consensus_chunk if fed.consensus_streaming else 0

    def z_step(z_l, w_l, phi_l):
        zf = z_l.ravel()
        # dual term over the consumed messages: sum_j w_j phi_j / C — the
        # left-fold the active-scope dense round runs over C rows on the
        # XLA path, one vectorised reduction on the TPU (ops.dual_mean).
        # dual_message="int8" folds the DECODED absmax-quantized uploads
        # (row-local quantizer — dense<->sparse parity is preserved).
        if dual_message == "int8":
            phi_m = kref.fold_dual_rowsum(phi_l.reshape(S, -1), w_row,
                                          chunk_size=chunk) / C
        elif chunk:
            phi_m = kref.fold_weighted_rowsum_stream(
                phi_l.reshape(S, -1), w_row, chunk) / C
        else:
            phi_m = kops.dual_mean(phi_l, w_row, C)
        z_upd = kops.sign_consensus(zf, w_l.reshape(S, -1), phi_m, s_w,
                                    fed.psi, fed.alpha_z,
                                    message=sign_message, n_total=C,
                                    streaming=fed.consensus_streaming,
                                    chunk_size=fed.consensus_chunk)
        if fed.fedbuff_lr_norm:
            z_upd = (zf.astype(jnp.float32) + lr_scale
                     * (z_upd.astype(jnp.float32) - zf.astype(jnp.float32))
                     ).astype(z_l.dtype)
        return jnp.where(do_consensus, z_upd, zf).reshape(z_l.shape)

    with jax.named_scope("bafdp.fold"):
        z_new = jax.tree.map(z_step, state.z, W_srv, phi_g)

    with jax.named_scope("bafdp.dual"):
        a1_t = reg_decay(fed.alpha_lambda, t, fed.reg_decay_pow)
        lam_new = state.lam + fed.alpha_lambda * (
            (eps_new - fed.privacy_budget_a) - a1_t * state.lam)
        lam_new = jnp.maximum(lam_new, 0.0)

        # ---------------- Step 3: delivered clients update phi, sync z ----
        a2_t = reg_decay(fed.alpha_phi, t, fed.reg_decay_pow)
        W_dual = W_prop
        if taylor:
            lag = jnp.maximum((t - tau_g).astype(jnp.float32) - 1.0, 0.0)
            W_dual = compensate_stale(W_prop, comp_blocks, lag, fed)

        def phi_step(phi_l, z_l, w_l):
            upd = (z_l[None].astype(jnp.float32) - w_l.astype(jnp.float32)) \
                - a2_t * phi_l.astype(jnp.float32)
            if fed.staleness_decay != "constant":
                upd = upd * s_w_dual.reshape((-1,) + (1,) * (phi_l.ndim - 1))
            return phi_l.astype(jnp.float32) + fed.alpha_phi * upd

        phi_blocks = jax.tree.map(phi_step, phi_g, z_new, W_dual)
        zl_blocks = jax.tree.map(
            lambda zl_l, z_l: jnp.broadcast_to(
                z_l[None].astype(jnp.float32), (S,) + z_l.shape),
            zl_g, z_new)
    phi_new = scatter_clients(state.phi, write_idx, phi_blocks)
    z_local_new = scatter_clients(state.z_local, write_idx, zl_blocks)

    new_state = FedState(W=W_new, z=z_new, z_local=z_local_new, phi=phi_new,
                         lam=lam_new, eps=eps_new, t=t + 1, opt=new_opt,
                         tau=tau_new, comp=new_comp)

    def subset_gap():
        sq, n = jnp.zeros(()), 0
        for z_l, w_l in zip(jax.tree.leaves(z_new), jax.tree.leaves(W_prop)):
            diff = z_l[None].astype(jnp.float32) - w_l.astype(jnp.float32)
            d = jnp.sum(jnp.square(diff), axis=tuple(range(1, w_l.ndim)))
            sq = sq + jnp.sum(d * w_row) / wsum_act
            n += z_l.size
        return sq / float(max(n, 1))

    # block-scope statistics carry the explicit ``_block`` suffix: they are
    # means over this round's DELIVERED rows (realized divisor
    # ``metrics_k``), not fleet-wide values — identically labeled and
    # identically valued between the dense active-scope round (which runs
    # THIS function over the full-width masked block) and the gathered
    # sparse round, so dense-vs-sparse histories compare key-for-key.
    with jax.named_scope("bafdp.metrics"):
        metrics = {
            "loss": jnp.sum(loss_i * w_row) / wsum_act,
            "data_loss": jnp.sum(g_i * w_row) / wsum_act,
            "lipschitz_block": jnp.sum(G_i * w_row) / wsum_act,
            "eps_mean": jnp.mean(eps_new),
            "lambda_mean": jnp.mean(lam_new),
            "consensus_gap_block": subset_gap(),   # over the delivered block
            "n_active": jnp.sum(w_row),
            "staleness_mean_block": jnp.sum(stale_v * w_row) / wsum_act,
            "staleness_weight_mean_block": jnp.sum(
                staleness_weights(stale_v, fed) * w_row) / wsum_act,
            "compensation_norm_block": comp_norm,
            "metrics_k": wsum_act,
        }
    return new_state, metrics


def make_round_fn(local_loss: LocalLoss, fed: FedConfig, c3: float,
                  n_samples: int, d_dim: int, byz_mask: jnp.ndarray):
    """Convenience: partial + jit."""
    f = functools.partial(bafdp_round, local_loss=local_loss, fed=fed, c3=c3,
                          n_samples=n_samples, d_dim=d_dim, byz_mask=byz_mask)
    return jax.jit(f)


def make_sparse_round_fn(local_loss: LocalLoss, fed: FedConfig, c3: float,
                         n_samples: int, d_dim: int, byz_mask: jnp.ndarray):
    """Convenience: partial + jit of the active-subset round."""
    f = functools.partial(bafdp_round_sparse, local_loss=local_loss, fed=fed,
                          c3=c3, n_samples=n_samples, d_dim=d_dim,
                          byz_mask=byz_mask)
    return jax.jit(f)
