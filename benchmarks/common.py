"""Shared benchmark utilities: one place that trains any method (BAFDP or
baseline) on any synthetic dataset and evaluates RMSE/MAE in raw units —
so every table/figure uses identical plumbing."""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs import FedConfig, ForecastConfig, MLP_H1, MLP_H24
from repro.configs.forecast import ForecastConfig as FC
from repro.core import bafdp, init_fed_state
from repro.core.byzantine import byz_mask
from repro.core.schedule import FederatedRun, Schedule
from repro.core.privacy import gaussian_c3, perturb_inputs
from repro.core.trainers import BaselineTrainer
from repro.data import build_windows, make_dataset
from repro.data.windowing import client_batches, rmse_mae, stage_rows
from repro.models.forecasting import (apply_forecaster, init_forecaster,
                                      mse_loss)

ROUNDS = int(os.environ.get("BENCH_ROUNDS", "150"))
N_CLIENTS = int(os.environ.get("BENCH_CLIENTS", "8"))
BATCH = 32

# paper method -> (trainer method, forecaster backbone, dp sigma)
METHODS = {
    "FedGRU": ("fedavg", "gru", 0.0),
    "Fed-NTP": ("fedavg", "lstm", 0.0),
    "FedAtt": ("fedatt", "attn", 0.0),
    "FedDA": ("fedda", "attn", 0.0),
    "AFL": ("afl", "mlp", 0.0),
    "ASPIRE-EASE": ("aspire", "mlp", 0.0),
    "UDP": ("udp", "mlp", 0.01),
    "NbAFL": ("nbafl", "mlp", 0.01),
    "RSA": ("rsa", "mlp", 0.0),
    "DP-RSA": ("dp_rsa", "mlp", 0.01),
    "FedAsync": ("fedasync", "mlp", 0.0),
    "BAFDP": ("bafdp", "mlp", 0.0),
}


def _check_schedule(arr, rounds: int, n_clients: int, name: str,
                    dtype=bool):
    """An external schedule must cover every trained round — recycling masks
    would silently decouple training from the simulator's timestamps, the
    exact mismatch the mask plumbing exists to eliminate."""
    if arr is None:
        return None
    out = jnp.asarray(np.asarray(arr)).astype(dtype)
    if out.ndim != 2 or out.shape[1] != n_clients:
        raise ValueError(
            f"{name} must be (rounds, {n_clients}), got {out.shape}")
    if out.shape[0] < rounds:
        raise ValueError(
            f"{name} covers {out.shape[0]} rounds < {rounds} trained;"
            " simulate() the full horizon instead of recycling a schedule")
    return out


def _check_masks(active_masks, rounds: int, n_clients: int):
    return _check_schedule(active_masks, rounds, n_clients, "active_masks")


def _legacy_round_kwargs(schedule, active_masks, staleness, rounds: int,
                         n_clients: int):
    """Deprecated dense ``active_masks=``/``staleness=`` arrays -> a
    per-round kwargs hook for :class:`FederatedRun` (bit-identical to the
    pre-policy-API loop).  Prefer passing a sparse ``schedule=``."""
    if active_masks is None and staleness is None:
        return None
    if schedule is not None:
        raise ValueError(
            "pass either schedule= or the deprecated active_masks=/"
            "staleness= arrays, not both")
    masks = _check_masks(active_masks, rounds, n_clients)
    stale_v = _check_schedule(staleness, rounds, n_clients, "staleness",
                              dtype=jnp.float32)

    def round_kwargs(t):
        kw = {} if masks is None else {"act": masks[t]}
        if stale_v is not None:
            kw["stale"] = stale_v[t]
        return kw

    return round_kwargs


def forecast_cfg(model: str, horizon: int) -> ForecastConfig:
    base = MLP_H1 if horizon == 1 else MLP_H24
    return dataclasses.replace(base, model=model,
                               name=f"{model}-h{horizon}")


@functools.lru_cache(maxsize=16)
def problem(dataset: str, horizon: int, n_clients: int = N_CLIENTS,
            seed: int = 0):
    data = make_dataset(dataset, n_clients, seed=seed)
    cfg = forecast_cfg("mlp", horizon)
    train, test, scalers = build_windows(data, cfg)
    return train, test, scalers


def eval_rmse_mae(params, cfg, test, scalers) -> Tuple[float, float]:
    preds, ys = [], []
    for c in range(test["x"].shape[0]):
        p = apply_forecaster(params, jnp.asarray(test["x"][c]), cfg)
        preds.append(scalers[c].inverse_y(np.asarray(p)))
        ys.append(test["y_raw"][c])
    return rmse_mae(np.concatenate(preds), np.concatenate(ys))


def eval_fed_state(state, cfg, test, scalers) -> Tuple[float, float]:
    """Algorithm 1's output is the per-client omega_i — each client serves
    its own cell with its own model (the consensus z is the Byzantine-
    robust anchor, not the deployment artifact)."""
    import jax
    preds, ys = [], []
    for c in range(test["x"].shape[0]):
        w_c = jax.tree.map(lambda l: l[c], state.W)
        p = apply_forecaster(w_c, jnp.asarray(test["x"][c]), cfg)
        preds.append(scalers[c].inverse_y(np.asarray(p)))
        ys.append(test["y_raw"][c])
    return rmse_mae(np.concatenate(preds), np.concatenate(ys))


def train_bafdp(dataset: str, horizon: int, fed: FedConfig,
                rounds: int = ROUNDS, seed: int = 0,
                input_sigma: float = 0.02,
                schedule: Optional[Schedule] = None,
                active_masks: Optional[np.ndarray] = None,
                staleness: Optional[np.ndarray] = None,
                collect: Tuple[str, ...] = (),
                optimizer: str = "adam",
                feed_arrivals: Optional[bool] = None,
                round_impl: str = "dense",
                ledger=None,
                on_round=None):
    """Returns (state, cfg, history dict).  ``history["round_fn"]`` is the
    jitted round that ran (lower it to see what it compiled to);
    ``on_round(t, state, metrics)`` is :meth:`FederatedRun.run`'s hook.

    The round donates its state (``donate_argnums=0``): each round
    updates the one resident copy of the fleet in place, and the state it
    was handed is consumed.  So the state given to ``on_round`` is valid
    only until the next round is dispatched; copy what must outlive it to
    the host inside the hook.  The returned state is the last round's and
    stays valid; running ``round_fn`` on it consumes it.

    ``schedule`` (a sparse :class:`repro.core.schedule.Schedule`, e.g.
    from ``build_schedule``) feeds the external event-driven schedule —
    per-round active masks AND consumption-age staleness vectors — into
    every round, so training dynamics match the simulator's wall-clock
    bookkeeping; ``None`` keeps the round function's internal sampler
    (``FedConfig.internal_select``).  ``active_masks``/``staleness`` are
    the deprecated dense ``(rounds, C)`` equivalents, kept as a shim.
    ``feed_arrivals`` (per-round admitted-update counts as ``arrivals=``)
    defaults to on exactly when ``fed.fedbuff_lr_norm`` needs them.

    ``round_impl="sparse"`` trains through the active-subset round path
    (``bafdp.bafdp_round_sparse`` fed ``Schedule.padded_rows``): O(S)
    per-round compute/memory over the per-client leaves, and per-delivery
    *admission* ages as the staleness input.  Needs a ``schedule=``;
    ``fed.consensus_scope`` is promoted to ``"active"`` automatically
    (the sparse path cannot consume inactive clients' frozen messages).
    Its batches are staged on the host for the round's delivered rows
    only (``windowing.stage_rows``: the schedule's winners in admission
    order, padded to ``schedule.s_max`` with the sentinel, the ``idx``
    row the round is fed) and handed over pre-gathered, ``(S_max, b,
    ...)``, under the span ``data.stage_rows`` (count ``rows``).  The
    dense path stages every client's batch (``client_batches``).

    ``ledger`` (a :class:`repro.core.privacy.EpsLedger`) turns on
    per-DELIVERY privacy accounting: every schedule row delivery charges
    the sending client's current ``eps``, so FedBuff duplicate deliveries
    spend budget twice; the history gains running worst-client
    ``dp_eps_basic`` / ``dp_eps_adv`` curves (composition at
    ``fed.dp_delta``).  Needs a ``schedule=``.

    Experimental setting per the paper Sec. V-D: Adam on the data/DRO
    gradient; grid-searched DRO scale (see FedConfig.dro_weight)."""
    fed = dataclasses.replace(fed, omega_optimizer=optimizer,
                              dro_weight=0.01)
    if round_impl not in ("dense", "sparse"):
        raise ValueError(f"unknown round_impl: {round_impl!r}")
    if round_impl == "sparse":
        if schedule is None:
            raise ValueError("round_impl='sparse' needs a schedule=")
        if fed.consensus_scope != "active":
            fed = dataclasses.replace(fed, consensus_scope="active")
    cfg = forecast_cfg("mlp", horizon)
    train, test, scalers = problem(dataset, horizon, fed.n_clients, seed)
    key = jax.random.PRNGKey(seed)
    c3 = gaussian_c3(cfg.d_x + cfg.d_y, fed.dp_delta, 0.05)

    def local_loss(p, batch, k, eps):
        x, y = batch
        return mse_loss(p, perturb_inputs(k, x, eps, input_sigma,
                                          fed.eps_min), y, cfg)

    sparse = round_impl == "sparse"
    if sparse:              # batch_fn stages the delivered rows only
        round_fn = functools.partial(bafdp.bafdp_round_sparse,
                                     batch_gathered=True)
    else:
        round_fn = bafdp.bafdp_round
    step = jax.jit(functools.partial(
        round_fn, local_loss=local_loss, fed=fed, c3=c3,
        n_samples=train["x"].shape[1], d_dim=cfg.d_x + cfg.d_y,
        byz_mask=byz_mask(fed.n_clients, fed.n_byzantine)),
        donate_argnums=0)
    rng = np.random.RandomState(seed)
    s_max = schedule.s_max if sparse else None

    def batch_fn(t):
        if sparse:
            ids = np.full(s_max, fed.n_clients, np.int64)
            won = schedule.round_winners(t)
            ids[:won.size] = won
            with tracing.span("data.stage_rows", rows=s_max):
                x, y = stage_rows(rng, train, BATCH, ids)
        else:
            x, y = client_batches(rng, train, BATCH)
        return jnp.asarray(x), jnp.asarray(y)

    # fedbuff_lr_norm needs the schedule's realized per-round K: feed it
    # whenever the knob is on (a sum(act) fallback would undercount rounds
    # where a fast client delivered twice into one buffer).  The sparse
    # rows carry K natively (sum of the weight row counts duplicates), so
    # the explicit arrivals feed is redundant there — but harmless.
    if feed_arrivals is None:
        feed_arrivals = fed.fedbuff_lr_norm and schedule is not None
    run = FederatedRun(
        step=step, rounds=rounds, schedule=schedule,
        n_clients=fed.n_clients, feed_arrivals=feed_arrivals,
        round_impl=round_impl, ledger=ledger, ledger_delta=fed.dp_delta,
        round_kwargs=_legacy_round_kwargs(schedule, active_masks, staleness,
                                          rounds, fed.n_clients))
    # no name holds the initial state: the first round consumes it
    state, hist = run.run(
        init_fed_state(key, lambda k: init_forecaster(k, cfg), fed),
        batch_fn, key, collect=collect, on_round=on_round,
        derive={
            "eps_all": lambda s, m: np.asarray(s.eps).copy(),
            "rmse": lambda s, m: eval_fed_state(s, cfg, test, scalers)[0],
            "mae": lambda s, m: eval_fed_state(s, cfg, test, scalers)[1],
        })
    hist["round_fn"] = step
    return state, cfg, hist


def train_baseline(method: str, dataset: str, horizon: int, fed: FedConfig,
                   rounds: int = ROUNDS, seed: int = 0,
                   collect: Tuple[str, ...] = (),
                   schedule: Optional[Schedule] = None,
                   active_masks: Optional[np.ndarray] = None):
    trainer_kind, backbone, dp_sigma = METHODS[method]
    assert trainer_kind != "bafdp"
    cfg = forecast_cfg(backbone, horizon)
    data = make_dataset(dataset, fed.n_clients, seed=seed)
    train, test, scalers = build_windows(data, cfg)
    key = jax.random.PRNGKey(seed)

    def loss(p, b, k):
        x, y = b
        return mse_loss(p, x, y, cfg)

    tr = BaselineTrainer(method=trainer_kind, loss=loss, fed=fed,
                         dp_sigma=dp_sigma)
    st = tr.init(init_forecaster(key, cfg))
    step = tr.jitted_round()
    rng = np.random.RandomState(seed)

    def batch_fn(t):
        x, y = client_batches(rng, train, BATCH)
        return jnp.asarray(x), jnp.asarray(y)

    # baseline rounds take act= but no stale= kwarg
    run = FederatedRun(
        step=step, rounds=rounds, schedule=schedule, feed_staleness=False,
        n_clients=fed.n_clients,
        round_kwargs=_legacy_round_kwargs(schedule, active_masks, None,
                                          rounds, fed.n_clients))
    st, hist = run.run(st, batch_fn, key, collect=collect,
                       skip_missing=True)
    return st["server"], cfg, (test, scalers), hist


def run_method(method: str, dataset: str, horizon: int,
               fed: Optional[FedConfig] = None, rounds: int = ROUNDS,
               seed: int = 0) -> Tuple[float, float]:
    """Train + evaluate; returns (RMSE, MAE) in raw traffic units."""
    fed = fed or FedConfig(n_clients=N_CLIENTS)
    if METHODS[method][0] == "bafdp":
        state, cfg, _ = train_bafdp(dataset, horizon, fed, rounds, seed)
        _, test, scalers = problem(dataset, horizon, fed.n_clients, seed)
        return eval_fed_state(state, cfg, test, scalers)
    params, cfg, (test, scalers), _ = train_baseline(
        method, dataset, horizon, fed, rounds, seed)
    return eval_rmse_mae(params, cfg, test, scalers)


def csv_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
