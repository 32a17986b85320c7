"""Jitted public wrappers around the Pallas kernels.

``impl`` selection:
  * "pallas"  — real TPU lowering (interpret=False);
  * "interpret" — Pallas interpret mode (CPU correctness testing);
  * "xla"    — the pure-jnp oracle from ref.py (the dry-run / fallback path);
  * "auto"   — pallas on TPU, xla elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import collectives
from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import ref
from repro.kernels import sign_agg as sa_k


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@functools.partial(jax.jit,
                   static_argnames=("psi", "alpha_z", "message", "impl",
                                    "n_total", "streaming", "chunk_size"))
def sign_consensus(z, W, phi_mean, weights, psi: float, alpha_z: float,
                   message: str = "f32", impl: str = "auto",
                   n_total: Optional[int] = None,
                   streaming: bool = False, chunk_size: int = 8):
    """The unified Eq. (20) consensus-path dispatch: every sign-sum flavour
    — plain mean (``weights=None``), staleness-decayed, and the int8 wire
    format — funnels through one entry point that picks the fused Pallas
    kernel on TPU and the XLA oracle elsewhere.

    z: (D,); W: (C, D) stacked client params (Byzantine corruption and any
    Taylor compensation already applied); phi_mean: (D,) mean dual;
    weights: (C,) staleness weights s(d) or None for the unweighted sum.
    ``message``: "f32" moves the 4-byte message; "int8" quantizes each
    client's s(d)*sign(z - w_i) to an int8 payload + per-client f32 scale
    (lossless for sign messages, 1 byte/coordinate on the wire).  Returns
    z' = z - alpha_z * (phi_mean + psi * sum_i s_i sign(z - w_i) / C).

    ``n_total`` is the weighted-sum-over-S variant (the active-subset
    round path): W may be a gathered (S_max, D) block — or the full
    (C, D) stack with inactive rows carrying weight 0 — and the sum is
    divided by ``n_total`` (the fleet size C) instead of ``W.shape[0]``.
    On the XLA path the reduction then runs as an order-canonical
    left-fold over rows (``ref.sign_agg_fold_ref``), which is what makes
    the masked dense round and the gathered sparse round bit-identical;
    that bit-parity is a property of the XLA path only.  On the TPU the
    fused kernels keep their tiled reduction and the dual mean beside
    them is one vectorised reduction (:func:`dual_mean`), so the whole
    fold agrees to f32 tolerance.  Requires ``weights`` (the
    padding/activity mask at minimum).

    ``streaming=True`` consumes the fold as an online reduction over
    arrival-event chunks of ``chunk_size`` rows
    (``ref.sign_agg_fold_stream_ref``): the server never materializes
    the full (S_max, D) message block — for ``message="int8"`` the wire
    payload exists only one chunk at a time.  Bit-identical to the
    materialized fold by construction (same left-fold order; chunk
    boundaries only split the scan carry).  Only defined for the
    active-subset fold, so it requires ``n_total``; ``impl`` is ignored
    (the fused Pallas kernel is already a one-pass tiled reduction — the
    streamed fold is the XLA-side arrival-event shape).
    """
    impl = _resolve(impl)
    if n_total is not None and weights is None:
        raise ValueError("n_total (active-subset reduction) needs weights "
                         "(the padding/activity mask at minimum)")
    if streaming:
        if n_total is None:
            raise ValueError(
                "streaming=True is the chunked active-subset left-fold — "
                "it needs n_total (and weights)")
        return ref.sign_agg_fold_stream_ref(z, W, phi_mean, weights, psi,
                                            alpha_z, n_total, chunk_size,
                                            message=message)
    if message == "int8":
        # client-side encode happens in f32 regardless of impl; the wire
        # format (and on TPU the server's HBM read) is what shrinks
        msg = collectives.encode_sign_message(z, W, weights)
        if impl == "xla":
            if n_total is not None:
                return ref.sign_agg_int8_fold_ref(z, msg.payload, msg.scale,
                                                  phi_mean, psi, alpha_z,
                                                  n_total)
            return ref.sign_agg_int8_ref(z, msg.payload, msg.scale,
                                         phi_mean, psi, alpha_z)
        return sa_k.sign_agg_weighted_int8(z, msg.payload, msg.scale,
                                           phi_mean, psi, alpha_z,
                                           n_total=n_total or 0,
                                           interpret=(impl == "interpret"))
    if message != "f32":
        raise ValueError(f"unknown sign message format: {message!r}")
    if n_total is not None:
        if impl == "xla":
            return ref.sign_agg_fold_ref(z, W, phi_mean, weights, psi,
                                         alpha_z, n_total)
        return sa_k.sign_agg_weighted(z, W, phi_mean, weights, psi, alpha_z,
                                      n_total=n_total,
                                      interpret=(impl == "interpret"))
    # impl is already resolved (idempotent through the wrappers' _resolve)
    if weights is None:
        return sign_agg(z, W, phi_mean, psi, alpha_z, impl=impl)
    return sign_agg_weighted(z, W, phi_mean, weights, psi, alpha_z,
                             impl=impl)


@functools.partial(jax.jit, static_argnames=("n_total", "impl"))
def dual_mean(phi_block, weights, n_total: int, impl: str = "auto"):
    """The Eq. (22) dual term of the fold: ``sum_j weights[j] * phi_j /
    n_total`` over the client axis of one (S, ...) leaf, raveled to (D,).

    On the XLA path it is ``ref.fold_weighted_rowsum`` — the row-order
    left-fold whose bits the masked dense round and the gathered sparse
    round share.  On the TPU paths (``"pallas"``, ``"interpret"``) it is
    one f32 multiply-and-sum over axis 0 of the leaf in its own shape: a
    VPU reduction that reads the block once (no matmul, which would round
    its inputs to bfloat16 at default precision, and no ``while`` loop of
    S trips).  Its additions are grouped by lane, so it agrees with the
    left-fold to f32 tolerance, as the tiled Pallas sign sum beside it
    does.
    """
    impl = _resolve(impl)
    S = phi_block.shape[0]
    if impl == "xla":
        return ref.fold_weighted_rowsum(phi_block.reshape(S, -1),
                                        weights) / n_total
    # reduce the leaf in its own shape: a reshape to (S, D) first can
    # cost a layout copy of the whole block on the TPU
    w = weights.astype(jnp.float32).reshape(
        (S,) + (1,) * (phi_block.ndim - 1))
    wsum = jnp.sum(w * phi_block.astype(jnp.float32), axis=0)
    return wsum.ravel() / n_total


@functools.partial(jax.jit, static_argnames=("psi", "alpha_z", "impl"))
def sign_agg(z, W, phi_mean, psi: float, alpha_z: float, impl: str = "auto"):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.sign_agg_ref(z, W, phi_mean, psi, alpha_z)
    return sa_k.sign_agg(z, W, phi_mean, psi, alpha_z,
                         interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("psi", "alpha_z", "impl"))
def sign_agg_weighted(z, W, phi_mean, weights, psi: float, alpha_z: float,
                      impl: str = "auto"):
    """Staleness-weighted consensus update (decayed Eq. 20 sum);
    ``weights``: (C,) per-client staleness weights s(t - tau_i)."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.sign_agg_weighted_ref(z, W, phi_mean, weights, psi,
                                         alpha_z)
    return sa_k.sign_agg_weighted(z, W, phi_mean, weights, psi, alpha_z,
                                  interpret=(impl == "interpret"))


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "impl", "bq", "bk"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    impl: str = "auto", bq: int = fa_k.DEFAULT_BQ,
                    bk: int = fa_k.DEFAULT_BK):
    """q: (B, Sq, H, D), k/v: (B, Sk, Hkv, D) — model layout; transposed to
    the kernel's (B, H, S, D) layout internally."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    out = fa_k.flash_attention(qT, kT, vT, causal=causal, window=window,
                               bq=bq, bk=bk,
                               interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("impl", "bl"))
def decode_attention(q, k, v, length, impl: str = "auto",
                     bl: int = dec_k.DEFAULT_BL):
    """q: (B, H, D); k/v: (B, L, Hkv, D) — model layout."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.decode_attention_ref(q, k, v, length)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    return dec_k.decode_attention(q, kT, vT, length, bl=bl,
                                  interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "bd"))
def ssm_scan(a, b, impl: str = "auto", chunk: int = 128, bd: int = 256):
    impl = _resolve(impl)
    if impl == "xla":
        B, S, D, N = a.shape
        h0 = jnp.zeros((B, D, N), jnp.float32)
        return ref.ssm_scan_ref(a, b, h0)
    return ssm_k_scan(a, b, chunk=chunk, bd=bd,
                      interpret=(impl == "interpret"))


from repro.kernels.ssm_scan import ssm_scan as ssm_k_scan  # noqa: E402
