"""Federated training state (a single pytree so it pjit-shards cleanly).

Every per-client quantity carries a leading client axis ``C`` — on the mesh
this axis is sharded over the federated axis (``"data"`` in mode A, ``"pod"``
in mode B; DESIGN.md Section 3).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import FedConfig


class FedState(NamedTuple):
    W: Any                 # stacked client params, leaves (C, ...)
    z: Any                 # consensus params, leaves (...)
    z_local: Any           # per-client last-received consensus (C, ...)
    phi: Any               # equality dual, leaves (C, ...)
    lam: jnp.ndarray       # (C,) inequality dual (eps <= a)
    eps: jnp.ndarray       # (C,) privacy levels
    t: jnp.ndarray         # scalar round counter
    opt: Any               # optional optimizer state for W (adam m, v)
    tau: jnp.ndarray       # (C,) last-participation round (Definition 2's
                           # t-hat); staleness of client i at round t is
                           # t - tau_i
    comp: Any = None       # per-client EWMA of the local update direction
                           # (momentum proxy for the Taylor staleness
                           # compensation), leaves (C, ...); None when
                           # FedConfig.staleness_compensation == "none"


def init_fed_state(key, init_params: Callable[[Any], Any],
                   fed: FedConfig, n_clients: Optional[int] = None) -> FedState:
    """``init_params(key) -> params`` builds one client's model."""
    with tracing.span("fed.init_state"):
        return _init_fed_state(key, init_params, fed,
                               n_clients or fed.n_clients)


def _init_fed_state(key, init_params, fed: FedConfig, C: int) -> FedState:
    keys = jax.random.split(key, C)
    W = jax.vmap(init_params)(keys)
    z = jax.tree.map(lambda l: l[0], W)
    z_local = jax.tree.map(lambda l: jnp.broadcast_to(l[None], (C,) + l.shape), z)
    phi = jax.tree.map(jnp.zeros_like, W)
    lam = jnp.zeros((C,), jnp.float32)
    eps = jnp.full((C,), max(fed.privacy_budget_a * fed.eps_init_frac,
                         fed.eps_min), jnp.float32)
    opt = None
    if fed.omega_optimizer == "adam":
        opt = {"m": jax.tree.map(jnp.zeros_like, W),
               "v": jax.tree.map(jnp.zeros_like, W),
               "count": jnp.zeros((C,), jnp.int32)}
    comp = None
    if fed.staleness_compensation != "none":
        # zeros_like, NOT zeros(..., float32): a non-f32 model (bf16 LM
        # configs) must keep the compensation cache in the leaf dtype —
        # the old f32 literal silently promoted it and broke dtype parity
        # with W (mask_leaves then downcast every round's EWMA write)
        comp = jax.tree.map(jnp.zeros_like, W)
    return FedState(W=W, z=z, z_local=z_local, phi=phi, lam=lam, eps=eps,
                    t=jnp.zeros((), jnp.int32), opt=opt,
                    tau=jnp.zeros((C,), jnp.int32), comp=comp)


def gather_clients(tree: Any, idx: jnp.ndarray) -> Any:
    """Gather rows ``idx`` of every (C, ...) leaf into an (S, ...) block.

    Pytree-generic: works on any stack of per-client leaves (``W``,
    ``phi``, the Adam ``m``/``v``, ``comp``, batches, ...).  ``idx`` is
    (S,) int; out-of-range indices (the padding sentinel ``C``) clip to
    the last row — padding rows must therefore be neutralized downstream
    (weight 0 in reductions, sentinel index at scatter time).  The gather
    is a pure XLA ``gather``: donation-friendly (the (C, ...) operand is
    read once) and the only O(C)-touching op on the sparse round's fast
    path.  Its ops run under the round's ``bafdp.gather`` scope.
    """
    with jax.named_scope("bafdp.gather"):
        return jax.tree.map(
            lambda l: jnp.take(l, idx, axis=0, mode="clip"), tree)


def scatter_clients(tree: Any, idx: jnp.ndarray, updates: Any) -> Any:
    """Scatter an (S, ...) block of updated rows back into the (C, ...)
    leaves.  Out-of-range indices (the padding sentinel ``C``) are
    dropped, so padded rows never write.  Updates are cast to each leaf's
    dtype (the round computes in f32).  ``benchmarks.common.train_bafdp``
    donates the state to its jitted round, so the scatter updates the
    resident stack in place — no (C, ...) copy.

    Duplicate in-bounds indices (FedBuff double deliveries) are allowed:
    the round computes every occurrence from the same pre-round state, so
    all duplicate writes carry identical values and the scatter is
    deterministic regardless of XLA's application order (the left-fold
    "last delivery wins" semantics, degenerate because the folds agree).
    Its ops run under the round's ``bafdp.scatter`` scope.
    """
    with jax.named_scope("bafdp.scatter"):
        return jax.tree.map(
            lambda l, u: l.at[idx].set(u.astype(l.dtype), mode="drop"),
            tree, updates)


def consensus_gap(state: FedState) -> jnp.ndarray:
    """mean_i ||z - w_i||^2 / D — convergence diagnostic."""
    sq, n = jnp.zeros(()), 0
    for z_l, w_l in zip(jax.tree.leaves(state.z), jax.tree.leaves(state.W)):
        diff = z_l[None].astype(jnp.float32) - w_l.astype(jnp.float32)
        sq = sq + jnp.sum(diff ** 2) / w_l.shape[0]
        n += z_l.size
    return sq / float(max(n, 1))   # float: n can exceed int32 (3B+ params)
