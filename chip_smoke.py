"""Smoke test: the BAFDP trainer on one TPU chip, through its own entry points.

    python chip_smoke.py [--seed 0] [--clients 1000] [--rounds 5]

One process, two phases; it starts no child process.

1. Kernels at city width.  ``ops.sign_consensus(..., impl="pallas")`` runs
   the Eq. (20) fold for each sign-message flavour (f32 unweighted, f32
   weighted with ``n_total``, int8 with a per-client scale, int8 without)
   at C = 10,000 (Milano) and C = 16,575 (Milano with Trentino) clients and
   D = 16,384 (the MLP's widest leaf).  Inputs are drawn on the device
   from ``--seed``.  Each result must agree with ``impl="xla"`` on the same
   chip within ``ATOL``, and each compiled program must hold a
   ``tpu_custom_call``: the Pallas kernel, not the XLA oracle, is what ran.
2. Training.  ``benchmarks.common.train_bafdp`` trains the full-width MLP
   forecaster (``MLP_H1``: hidden 128/128/64, about 27.8k parameters per
   client) on synthetic Milano traffic, once on the default dense round and
   once on the sparse round over a ``QuorumTrigger(active_frac=0.6)``
   schedule.  Each run is evaluated once with ``eval_fed_state``; loss and
   RMSE must be finite, and the lowered round must hold a
   ``tpu_custom_call``.

The numbers printed are smoke readings taken once, with compilation in the
first round, not benchmark measurements.  The last line of standard output
is ``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
The script fails at once when the first device is not a TPU.

There is no four-chip phase: no path users run today spans chips.  No
trainer drives ``ShardingPlan.fed_state_specs``
(``src/repro/distributed/sharding.py``), ``launch/train.py`` builds a 1x1
host mesh, and the 256- and 512-chip meshes serve only the CPU dry-run
(``launch/dryrun.py``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import (BATCH, eval_fed_state, problem,  # noqa: E402
                               train_bafdp)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import FedConfig  # noqa: E402
from repro.core.async_engine import DelayModel  # noqa: E402
from repro.core.schedule import QuorumTrigger, build_schedule  # noqa: E402
from repro.data.windowing import client_batches, stage_rows  # noqa: E402
from repro.kernels import ops  # noqa: E402

KERNEL_CLIENTS = (10_000, 16_575)   # Milano; Milano with Trentino
KERNEL_D = 16_384                   # the MLP's 128x128 leaf
PSI, ALPHA_Z = 1.0, 0.1             # a step large enough to see
# |pallas - xla| bound on z'.  The two folds add the same terms in a
# different order; for |z| <~ 6 that moves z' by a few f32 ulps (~5e-7
# each), far below the mean step alpha_z * psi * |mean sign| (~1e-2).
ATOL = 1e-5

FLAVOURS = (  # name, message, weighted, n_total
    ("f32", "f32", False, False),
    ("f32_weighted_n_total", "f32", True, True),
    ("int8_scaled", "int8", True, False),
    ("int8", "int8", False, False),
)


def fail(msg: str) -> int:
    print(f"FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.as_text()


def kernel_phase(seed: int) -> list:
    """Phase 1; returns the failures."""
    bad = []
    key = jax.random.PRNGKey(seed)
    for C in KERNEL_CLIENTS:
        kz, kw, kp, ks, km = jax.random.split(jax.random.fold_in(key, C), 5)
        z = jax.random.normal(kz, (KERNEL_D,), jnp.float32)
        W = jax.random.normal(kw, (C, KERNEL_D), jnp.float32)
        phi = 0.01 * jax.random.normal(kp, (KERNEL_D,), jnp.float32)
        sw = jax.random.uniform(ks, (C,), minval=0.05, maxval=1.0)
        # the n_total flavour folds a padded block: ~40% rows at weight 0
        sw_pad = jnp.where(jax.random.uniform(km, (C,)) < 0.6, sw, 0.0)
        for name, message, weighted, padded in FLAVOURS:
            w = (sw_pad if padded else sw) if weighted else None
            kw_ = dict(message=message, n_total=C if padded else None)
            args = (z, W, phi, w, PSI, ALPHA_Z)
            t0 = time.perf_counter()
            lowered = ops.sign_consensus.lower(*args, impl="pallas", **kw_)
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            got = jax.block_until_ready(compiled(z, W, phi, w))
            run_s = time.perf_counter() - t0
            want = ops.sign_consensus(*args, impl="xla", **kw_)
            err = float(jnp.max(jnp.abs(got - want)))
            step = float(jnp.mean(jnp.abs(want - z)))
            kernel = has_kernel(lowered)
            ok = kernel and err <= ATOL and math.isfinite(err)
            print(f"smoke kernel C={C} D={KERNEL_D} {name}: "
                  f"max|pallas-xla|={err!r} (atol {ATOL}) "
                  f"mean|z'-z|={step!r} tpu_custom_call={kernel} "
                  f"compile_s={compile_s!r} first_call_s={run_s!r} "
                  f"{'ok' if ok else 'FAIL'}", flush=True)
            if not ok:
                bad.append(f"kernel C={C} {name}")
    return bad


def train_phase(name: str, fed: FedConfig, rounds: int, seed: int,
                **kw) -> list:
    """Phase 2 for one round implementation; returns the failures."""
    stamps = []

    def on_round(t, state, m):
        jax.block_until_ready(m)
        stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    # host data preparation; train_bafdp reuses this cached problem
    train, test, scalers = problem("milano", 1, fed.n_clients, seed)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, cfg, hist = train_bafdp("milano", 1, fed, rounds=rounds,
                                   seed=seed, collect=("loss",),
                                   on_round=on_round, **kw)
    wall_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    rmse, mae = map(float, eval_fed_state(state, cfg, test, scalers))
    eval_s = time.perf_counter() - t1
    round_s = np.diff([t0] + stamps).tolist()
    steady_s = float(np.median(round_s[1:])) if rounds > 1 else float("nan")
    loss = hist["loss"][-1]

    # lower the round that ran, on arguments of the shapes it ran with
    rng = np.random.RandomState(seed)
    rkw = {}
    if kw.get("round_impl") == "sparse":
        rkw = dict(zip(("idx", "stale", "weight"),
                       next(kw["schedule"].padded_rows())))
        batch = stage_rows(rng, train, BATCH, rkw["idx"])
    else:
        batch = client_batches(rng, train, BATCH)
    batch = tuple(jnp.asarray(a) for a in batch)
    kernel = has_kernel(hist["round_fn"].lower(
        state, batch, jax.random.PRNGKey(seed), **rkw))
    ok = kernel and math.isfinite(loss) and math.isfinite(rmse)
    # the first round carries state init and compilation
    print(f"smoke train {name}: clients={fed.n_clients} rounds={rounds} "
          f"data_s={data_s!r} wall_s={wall_s!r} "
          f"first_round_s={round_s[0]!r} steady_round_s={steady_s!r} "
          f"compile_s~={round_s[0] - steady_s!r} eval_s={eval_s!r} "
          f"loss={loss!r} rmse={rmse!r} mae={mae!r} "
          f"tpu_custom_call={kernel} {'ok' if ok else 'FAIL'}", flush=True)
    return [] if ok else [f"train {name}"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=1000)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"first device is {dev.platform!r}, not a TPU")
    enable_compile_cache()
    print(f"smoke device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)

    bad = kernel_phase(args.seed)
    if bad:
        return fail(", ".join(bad))

    fed = FedConfig(n_clients=args.clients)
    bad = train_phase("dense", fed, args.rounds, args.seed)
    sched = build_schedule(
        args.rounds, DelayModel(n_clients=args.clients, seed=args.seed),
        QuorumTrigger(active_frac=0.6))
    bad += train_phase("sparse", fed, args.rounds, args.seed,
                       schedule=sched, round_impl="sparse")
    if bad:
        return fail(", ".join(bad))

    stats = dev.memory_stats() or {}
    print(f"smoke peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
