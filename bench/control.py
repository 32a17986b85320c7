"""Readings of the check's control and of its planted faults.

The control is the reference put in the program's place and computed a
step lower than the configuration states (``float32`` -> ``bfloat16``);
each fault is the reference with one fault planted (``reference.FAULTS``).
Each is compared with the ``float32`` reference exactly as the program
is, so these readings are the upper ends the limits are set below:

    python3 bench/control.py --config milano-h1 --seeds 11 12 13

prints one JSON line per seed and variant.  It needs no program round:
only the traffic's schedule comes from the program's scheduler.  A fault
that leaves the state unchanged reads 1 by the check's measure and needs
no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))

VARIANTS = ("bfloat16",) + ("half_batch", "z_step_doubled")


def first_rows(cfg, traffic, seed):
    from bench import harness, traffic_gen
    sched = traffic_gen.schedule(traffic, cfg["fleet"]["n_clients"],
                                 seed % harness.SEED_MOD,
                                 harness.N_CHECK)
    return list(sched.padded_rows())


def numbers(cfg, traffic, seed, variants=VARIANTS):
    """{variant: the check's numbers} for one seed."""
    import jax.numpy as jnp
    from bench import check, harness, reference
    s = seed % harness.SEED_MOD
    rows = first_rows(cfg, traffic, seed)
    ref = reference.readings(cfg, s, rows)
    out = {}
    for v in variants:
        if v == "bfloat16":
            got = reference.readings(cfg, s, rows, dtype=jnp.bfloat16)
        else:
            got = reference.readings(cfg, s, rows, fault=v)
        out[v] = check.gaps(got, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="quorum-0.6")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "bench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           args.traffic + ".json")) as f:
        traffic = json.load(f)
    for seed in args.seeds:
        for v, nums in numbers(cfg, traffic, seed).items():
            print(json.dumps({"config": args.config, "seed": seed,
                              "variant": v, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
