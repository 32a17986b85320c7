"""Wire formats for the cross-client consensus collectives (Eq. 20 / 22).

Two message families cross the client axis each consensus round, with
different quantization guarantees:

**Sign messages (Eq. 20) — int8 is LOSSLESS.**  The server consumes
``m_i = s(d_i) * sign(z - w_i)`` — the staleness-decayed RSA sign message.
Because a sign message takes only the three values ``{-s_i, 0, +s_i}``, it
admits an *exact* int8 quantization: an int8 payload holding the sign in
``{-1, 0, +1}`` plus a single f32 per-client scale ``s_i`` (the absmax of
the message).  On the wire that is 1 byte per coordinate plus 4 bytes per
client instead of 4 bytes per coordinate — a 4x cut on the dominant term —
and the dequantization ``payload * s_i`` reproduces the f32 message
bit-for-bit, so decay, Taylor compensation, and compression compose with
no accuracy knob.

**Dual messages (Eq. 22) — int8 is TOLERANCE-PINNED, not lossless.**  The
phi_i uploads the server averages into its Eq. (20) step are full-range
floats, not ternary, so their int8 format is a deterministic per-client
absmax quantizer: payload ``round(phi / s)`` in ``[-127, 127]`` with one
f32 scale ``s = absmax(phi)/127`` per client.  The per-coordinate decode
error is at most half a quantization step, ``absmax * DUAL_INT8_REL_ERR``
(= absmax/254) — the pinned tolerance every parity test asserts against.
The quantizer is row-local (each client's scale depends only on its own
message), so the masked dense round and the gathered sparse round decode
identical per-client values and their order-canonical fold stays
bit-identical to each other, merely offset from the f32 wire by the
quantization error.

Reductions NEVER accumulate in the wire dtype: an int8 accumulator
silently wraps once ``|sum_i sign_i| >= 128``, i.e. for any fleet of
``C >= 128`` clients (the pre-PR-4 bug).  The unweighted sign sum
accumulates in int32 (exact for any realistic C); weighted sums
dequantize and accumulate in f32.

These helpers are the single source of truth for both formats: the XLA
oracles (``kernels/ref``), the fused Pallas kernel
(``kernels/sign_agg.sign_agg_weighted_int8``), and the wire byte
accounting (:func:`message_bytes`) all build on them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp


class SignMessage(NamedTuple):
    """The int8 consensus message crossing the client axis.

    ``payload``: (C, D) int8, the per-coordinate sign in {-1, 0, +1}.
    ``scale``:   (C,) f32 per-client dequantization scale — the staleness
                 weight ``s(d_i)`` — or ``None`` for the unweighted
                 (constant-decay) message, whose reduction then runs as an
                 exact int32 sum.
    """
    payload: jnp.ndarray
    scale: Optional[jnp.ndarray]


def encode_sign_message(z: jnp.ndarray, W: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None) -> SignMessage:
    """Client-side encode: quantize ``s_i * sign(z - w_i)`` to the int8
    wire format.  ``z``: (D,); ``W``: (C, D); ``weights``: (C,) or None.

    The quantizer is absmax per message: the payload is the sign (exactly
    representable in int8) and the scale is the message's magnitude
    ``s_i``.  Lossless — ``decode`` reproduces the f32 message bit-for-bit.
    """
    sgn = jnp.sign(z[None, :].astype(jnp.float32) - W.astype(jnp.float32))
    payload = sgn.astype(jnp.int8)
    scale = None if weights is None else weights.astype(jnp.float32)
    return SignMessage(payload=payload, scale=scale)


def decode_sign_message(msg: SignMessage) -> jnp.ndarray:
    """Dequantize back to the (C, D) f32 message ``s_i * sign(z - w_i)``."""
    m = msg.payload.astype(jnp.float32)
    if msg.scale is None:
        return m
    return m * msg.scale[:, None]


def sign_sum(msg: SignMessage, n_clients: int) -> jnp.ndarray:
    """Server-side reduce: ``sum_i s_i sign(z - w_i) / C`` from the wire
    format, accumulating OUTSIDE the int8 wire dtype.

    Unweighted messages sum in int32 — exact for any C (the int8
    accumulator of the pre-PR-4 path wrapped at C >= 128).  Weighted
    messages dequantize per client and accumulate in f32, which is
    bit-identical to the uncompressed decayed sum.
    """
    if msg.scale is None:
        s = jnp.sum(msg.payload.astype(jnp.int32), axis=0,
                    dtype=jnp.int32).astype(jnp.float32)
    else:
        s = jnp.sum(msg.payload.astype(jnp.float32) * msg.scale[:, None],
                    axis=0)
    return s / n_clients


def message_bytes(n_clients: int, dim: int, message: str,
                  weighted: bool = True) -> Tuple[int, int]:
    """(bytes moved across the client axis, per-client side-channel bytes)
    for one consensus round — the quantity the int8 format shrinks.
    The f32 scale column only rides along for weighted messages; the
    unweighted (constant-decay) format is pure int8 payload
    (``SignMessage.scale is None``).

    ``n_clients`` is the number of messages that actually cross the wire:
    the fleet size C under ``consensus_scope="all"``, but only the
    delivered-block size S_max under the active scope / sparse round —
    pass the right one.
    """
    if message == "f32":
        return n_clients * dim * 4, 0
    if message == "int8":
        return n_clients * dim * 1, n_clients * 4 if weighted else 0
    raise ValueError(f"unknown sign message format: {message!r}")


# ---------------------------------------------------------------------------
# Eq. (22) dual wire format — absmax int8, tolerance-pinned (NOT lossless)

# Per-coordinate decode error bound, relative to the client's absmax:
# |decode(encode(phi)) - phi| <= absmax(phi) * DUAL_INT8_REL_ERR.  Rounding
# to the nearest of 2*127 + 1 levels spaced absmax/127 apart errs by at
# most half a step.  Every dual-wire parity test pins against this.
DUAL_INT8_LEVELS = 127
DUAL_INT8_REL_ERR = 0.5 / DUAL_INT8_LEVELS


class DualMessage(NamedTuple):
    """The int8 Eq. (22) dual message crossing the client axis.

    ``payload``: (C, D) int8, ``round(phi_i / scale_i)`` in [-127, 127].
    ``scale``:   (C,) f32 per-client dequantization scale
                 ``absmax(phi_i) / 127`` (1.0 for an all-zero message,
                 whose payload is all zeros either way).
    """
    payload: jnp.ndarray
    scale: jnp.ndarray


def encode_dual_message(phi: jnp.ndarray) -> DualMessage:
    """Client-side encode: absmax-quantize the dual upload ``phi_i`` to the
    int8 wire format.  ``phi``: (C, D) — one row per client message.

    Deterministic and row-local: client i's scale is a pure function of
    its own message, so the masked dense block and the gathered sparse
    block encode identical per-row values — the dense<->sparse parity
    mechanism.  Tolerance-pinned, not lossless: see ``DUAL_INT8_REL_ERR``.
    """
    phif = phi.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(phif), axis=-1)
    # multiply by the f32 step 1/127 rather than divide by 127: XLA rewrites
    # a division by a constant into this multiply inside jit but not in an
    # eager op, and the two round differently — the scale must not depend
    # on where the encode is traced
    scale = jnp.where(absmax > 0.0,
                      absmax * jnp.float32(1.0 / DUAL_INT8_LEVELS), 1.0)
    # |phi|/scale <= 127 mathematically, but the f32-rounded scale can sit
    # a ulp low — clip so the int8 cast can never wrap at the extremes
    q = jnp.clip(jnp.round(phif / scale[..., None]),
                 -DUAL_INT8_LEVELS, DUAL_INT8_LEVELS)
    return DualMessage(payload=q.astype(jnp.int8), scale=scale)


def decode_dual_message(msg: DualMessage) -> jnp.ndarray:
    """Dequantize back to the (C, D) f32 dual messages (within the pinned
    per-coordinate tolerance ``absmax * DUAL_INT8_REL_ERR``)."""
    return msg.payload.astype(jnp.float32) * msg.scale[..., None]


def dual_message_bytes(n_clients: int, dim: int, message: str
                      ) -> Tuple[int, int]:
    """(bytes moved across the client axis, per-client side-channel bytes)
    for the Eq. (22) dual uploads of one consensus round.  As with
    :func:`message_bytes`, ``n_clients`` is the number of messages on the
    wire — S_max for a sparse/active-scope round, C for the "all" scope."""
    if message == "f32":
        return n_clients * dim * 4, 0
    if message == "int8":
        # the scale column always rides along: a dual message has no
        # unweighted variant (the scale IS the quantizer, not a decay)
        return n_clients * dim * 1, n_clients * 4
    raise ValueError(f"unknown dual message format: {message!r}")
