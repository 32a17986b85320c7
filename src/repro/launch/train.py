"""Production training launcher: federated BAFDP over any model-zoo arch.

On real hardware this runs under the production mesh; on this container it
runs the same program on the host mesh at a reduced scale (or lowers only,
with --dry).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --shape train_4k --steps 50 --smoke            # executable on CPU
    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b \
        --shape train_4k --dry                         # lower+compile only
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + host mesh (CPU-executable)")
    ap.add_argument("--dry", action="store_true",
                    help="lower + compile on the production mesh, no run")
    ap.add_argument("--variant", default="")
    ap.add_argument("--byzantine", type=float, default=0.0)
    ap.add_argument("--attack", default="sign_flip")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.dry:
        # delegate to the dry-run module (which must own process start-up
        # because of the XLA device-count flag).  The child is spawned
        # before this process imports JAX: a process that has touched JAX
        # holds the chip, and a child that needs it would then fail or hang.
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape,
               "--multi-pod", "both"]
        if args.variant:
            cmd += ["--variant", args.variant]
        return subprocess.call(cmd, env={**os.environ})

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import Checkpointer
    from repro.compile_cache import enable_compile_cache
    from repro.configs import INPUT_SHAPES, get_arch, reduce_for_smoke
    from repro.core.fed_state import init_fed_state
    from repro.data.tokens import lm_batch
    from repro.distributed.context import set_mesh
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tr

    enable_compile_cache()
    cfg = get_arch(args.arch)
    shape = INPUT_SHAPES[args.shape]
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
        shape = dataclasses.replace(shape, seq_len=64, global_batch=4)
    if args.variant:
        from repro.launch.variants import get_variant
        cfg, _, _ = get_variant(args.variant).apply(cfg)

    mesh = make_host_mesh()
    set_mesh(mesh)
    n_clients = 2 if args.smoke else 4
    fed = steps_lib.fed_config_for(cfg, n_clients)
    fed = dataclasses.replace(fed, byzantine_frac=args.byzantine,
                              attack=args.attack, alpha_w=1e-2)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, fed))
    state = init_fed_state(jax.random.PRNGKey(0),
                           lambda k: tr.init_lm(k, cfg), fed)
    ck = Checkpointer(args.ckpt) if args.ckpt else None
    start = 0
    if ck:
        restored, s0 = ck.restore_latest(state)
        if restored is not None:
            state, start = restored, s0
            print(f"resumed at step {start}")

    rng = np.random.RandomState(0)
    b = shape.global_batch // n_clients
    t0 = time.time()
    m = {}
    for t in range(start, args.steps):
        raw = lm_batch(rng, cfg, n_clients * b, shape.seq_len)
        batch = {k: jnp.asarray(v).reshape((n_clients, b) + v.shape[1:])
                 for k, v in raw.items()}
        state, m = step_fn(state, batch, jnp.asarray(t))
        if t % args.log_every == 0:
            print(f"step {t:5d}  loss={float(m['data_loss']):.4f}  "
                  f"eps={float(m['eps_mean']):.2f}  "
                  f"gap={float(m['consensus_gap']):.2e}  "
                  f"{(time.time() - t0) / (t - start + 1):.2f}s/step",
                  flush=True)
        if ck and t and t % 50 == 0:
            ck.save(state, t)
    if ck:
        ck.save(state, args.steps)
    print(f"done. final loss {float(m['data_loss']):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
