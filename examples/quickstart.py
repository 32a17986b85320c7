"""Quickstart: train the paper's MLP traffic predictor with BAFDP on the
synthetic Milano dataset, with Byzantine clients and LDP noise, then
evaluate RMSE/MAE on the last-7-days test split.

The federated loop runs through the policy API (``core/schedule``): an
event-driven client-latency simulation builds a sparse ``Schedule``
through a composable server trigger, and ``FederatedRun`` drives the
jitted BAFDP round over it — so the training dynamics and the wall-clock
estimate come from one schedule.  ``--server fedbuff`` swaps in the
FedBuff K-arrivals buffered server; ``--server sync`` waits for every
client each round.

    PYTHONPATH=src python examples/quickstart.py [--rounds 200]
        [--server quorum|fedbuff|sync]
"""
import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import FedConfig, MLP_H1
from repro.core import bafdp, init_fed_state
from repro.core.async_engine import DelayModel
from repro.core.byzantine import byz_mask
from repro.core.privacy import gaussian_c3, perturb_inputs, privacy_accountant
from repro.core.schedule import (AdaptiveQuorum, AgeAwareSelection,
                                 FedBuffTrigger, FederatedRun, QuorumTrigger,
                                 SyncTrigger, build_schedule)
from repro.data import build_windows, make_dataset
from repro.data.windowing import client_batches, rmse_mae
from repro.models.forecasting import apply_forecaster, init_forecaster, mse_loss


def make_trigger(server: str, active_frac: float):
    if server == "quorum":
        # adaptive quorum + age-aware selection: the bounded-staleness fleet
        return QuorumTrigger(active_frac=active_frac,
                             quorum=AdaptiveQuorum(s_min=2),
                             selection=AgeAwareSelection())
    if server == "fedbuff":
        return FedBuffTrigger(buffer_k=4)
    if server == "sync":
        return SyncTrigger()
    raise SystemExit(f"unknown --server {server!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--byzantine", type=float, default=0.2)
    ap.add_argument("--attack", default="sign_flip")
    ap.add_argument("--server", default="quorum",
                    choices=["quorum", "fedbuff", "sync"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = MLP_H1
    fed = FedConfig(n_clients=args.clients, byzantine_frac=args.byzantine,
                    attack=args.attack, active_frac=0.6,
                    privacy_budget_a=30.0, alpha_eps=5e-2,
                    eps_init_frac=0.05, staleness_decay="poly")
    print(f"BAFDP: {fed.n_normal} honest + {fed.n_byzantine} byzantine "
          f"({args.attack}), S/M={fed.active_frac}, server={args.server}")

    data = make_dataset("milano", fed.n_clients)
    train, test, scalers = build_windows(data, cfg)
    print(f"milano: {data['traffic'].shape[1]} hours x {fed.n_clients} "
          f"cells; train windows {train['x'].shape}, test {test['x'].shape}")

    # event-driven fleet: heterogeneous latencies -> sparse schedule
    dm = DelayModel(n_clients=fed.n_clients, hetero=1.0, seed=0)
    sched = build_schedule(args.rounds, dm,
                           make_trigger(args.server, fed.active_frac))
    if sched.n_rounds:
        print(f"schedule: {sched.n_rounds} rounds, mean quorum "
              f"{sched.quorum.mean():.1f}, "
              f"est. wall-clock {sched.times[-1]:.0f}s")

    key = jax.random.PRNGKey(0)
    c3 = gaussian_c3(cfg.d_x + cfg.d_y, fed.dp_delta, 0.05)

    def local_loss(p, batch, k, eps):
        x, y = batch
        return mse_loss(p, perturb_inputs(k, x, eps, 0.02), y, cfg)

    state = init_fed_state(key, lambda k: init_forecaster(k, cfg), fed)
    step = jax.jit(functools.partial(
        bafdp.bafdp_round, local_loss=local_loss, fed=fed, c3=c3,
        n_samples=train["x"].shape[1], d_dim=cfg.d_x + cfg.d_y,
        byz_mask=byz_mask(fed.n_clients, fed.n_byzantine)))

    rng = np.random.RandomState(0)

    def batch_fn(t):
        x, y = client_batches(rng, train, 32)
        return jnp.asarray(x), jnp.asarray(y)

    def on_round(t, st, m):
        if t % max(args.rounds // 10, 1) == 0:
            print(f"  round {t:4d}  loss={float(m['data_loss']):.4f} "
                  f"eps={float(jnp.mean(st.eps)):.3f}  "
                  f"gap={float(m['consensus_gap']):.2e}")

    run = FederatedRun(step=step, rounds=args.rounds, schedule=sched,
                       n_clients=fed.n_clients)
    state, hist = run.run(
        state, batch_fn, key, on_round=on_round, collect=("eps_mean",),
        derive={"eps_mean": lambda st, m: float(jnp.mean(st.eps))})

    preds, ys = [], []
    for c in range(fed.n_clients):
        p = apply_forecaster(state.z, jnp.asarray(test["x"][c]), cfg)
        preds.append(scalers[c].inverse_y(np.asarray(p)))
        ys.append(test["y_raw"][c])
    rmse, mae = rmse_mae(np.concatenate(preds), np.concatenate(ys))
    print(f"\nconsensus-model test RMSE={rmse:.3f}  MAE={mae:.3f} "
          f"(raw traffic units)")
    if hist["eps_mean"]:
        basic, adv = privacy_accountant(jnp.asarray(hist["eps_mean"]),
                                        fed.dp_delta)
        print(f"privacy over {args.rounds} rounds: basic eps={basic:.1f}, "
              f"advanced-composition eps={adv:.1f} "
              f"at delta'={fed.dp_delta:.0e}")


if __name__ == "__main__":
    main()
