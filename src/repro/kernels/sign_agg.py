"""Pallas TPU kernel for the BAFDP/RSA server consensus update (Eq. 20).

    z' = z - alpha_z * ( mean_i(phi_i) + psi * mean_i sign(z - w_i) )

This is the paper's hot aggregation loop: elementwise sign over a (C, D)
stacked parameter matrix plus a cross-client reduction and an AXPY.  It is
purely memory-bound, so the TPU design goal is to read the (C, D) matrix
from HBM exactly once, in VPU-aligned tiles:

  grid = (cdiv(D, BLOCK), cdiv(C, block_c)), with block_c = BLOCK_C rows
  of f32 or BLOCK_C_INT8 rows of int8 messages.  Step (i, c) loads the
  (block_c, BLOCK) tile of W plus the (1, BLOCK) z and phi blocks, adds the
  tile's per-column sign sum into a (1, BLOCK) VMEM accumulator, and on the
  last client step fuses the AXPY and writes the updated z block.  The
  client axis is the last grid axis and is marked ``arbitrary`` (it carries
  the accumulator); only one (block_c, BLOCK) tile is resident per step, so
  the kernel fits the default scoped VMEM at any fleet size C.  The XLA
  fallback materializes sign(z - W) in HBM.

Neither axis is padded in HBM: the edge tiles of a C or D that is not a
multiple of the tile are partial.  Rows past C are masked to an exact zero
before they reach the accumulator; columns past D are computed and
discarded by the masked edge write.

``sign_agg_weighted`` is the staleness-weighted variant (the FedAsync-
decayed Eq. 20 sum ``sum_i s(t - tau_i) sign(z - w_i) / C``): same tiling,
with the (C, 1) per-client weight column tiled along the client axis.

``sign_agg_weighted_int8`` consumes the quantized wire format instead
(``distributed/collectives.SignMessage``): the (C, D) message matrix the
server streams from HBM is int8 — 1 byte/coordinate, a 4x cut on the
dominant traffic term — and the per-client f32 dequant scales are tiled
like the weight column.  Dequantization happens in VMEM; the reduction
accumulates in int32 (unweighted) or f32 (weighted), never in the int8
wire dtype, which would wrap at C >= 128.

Streaming note: the arrival-event streaming fold
(``ops.sign_consensus(streaming=True)``, PR 7) is an XLA-side chunked
left-fold over gathered active rows — see ``ref.sign_agg_fold_stream_ref``.
It is deliberately NOT a Pallas variant: these kernels already stream the
client axis through VMEM one tile at a time, so "streaming" buys nothing
on-chip; what it bounds is the HOST/XLA peak message block on the sparse
round path, where the kernel fallback would otherwise hold the full
(S_max, D) gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One message tile is 1 MiB in either wire format.  The v5e compiler takes
# up to 2 MiB f32 tiles (512 x 1024) in the default scoped VMEM and refuses
# 4 MiB ones, so 1 MiB leaves room for the double buffer and the f32
# temporaries of the sign and weight products.
BLOCK = 1024          # lanes (D columns) per grid step
BLOCK_C = 256         # f32 client rows per grid step
BLOCK_C_INT8 = 1024   # int8 rows per step, a multiple of the (32, 128) tile


def _sign_tile(z, w_ref, col_ref):
    return jnp.sign(z - w_ref[...].astype(jnp.float32))


def _weighted_tile(z, w_ref, col_ref):
    # (block_c, 1) weight column broadcasts over the lanes
    return jnp.sign(z - w_ref[...].astype(jnp.float32)) \
        * col_ref[...].astype(jnp.float32)


def _int8_weighted_tile(z, q_ref, col_ref):
    return q_ref[...].astype(jnp.float32) * col_ref[...].astype(jnp.float32)


def _int8_tile(z, q_ref, col_ref):
    # int32 accumulation: the int8 wire dtype wraps at |sum| >= 128
    return q_ref[...].astype(jnp.int32)


def _fold_kernel(*refs, tile, psi: float, alpha_z: float, n_div: int,
                 n_rows: int, block_c: int):
    if len(refs) == 6:
        z_ref, m_ref, phi_ref, col_ref, out_ref, acc_ref = refs
    else:
        (z_ref, m_ref, phi_ref, out_ref, acc_ref), col_ref = refs, None
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...].astype(jnp.float32)                  # (1, BLK)
    part = tile(z, m_ref, col_ref)                      # (block_c, BLK)
    if n_rows % block_c:
        # the last client tile is partial: its rows past C hold whatever
        # the edge DMA left there and must add exactly zero
        row = c * block_c + jax.lax.broadcasted_iota(
            jnp.int32, (block_c, 1), 0)
        part = jnp.where(row < n_rows, part, jnp.zeros_like(part))
    acc_ref[...] += jnp.sum(part, axis=0, keepdims=True)

    @pl.when(c == pl.num_programs(1) - 1)
    def _finish():
        phi = phi_ref[...].astype(jnp.float32)
        mean = acc_ref[...].astype(jnp.float32) / n_div
        out_ref[...] = (z - alpha_z * (phi + psi * mean)).astype(out_ref.dtype)


def _tiled_fold(tile, z, msg, phi_mean, col, psi, alpha_z, *, n_div: int,
                acc_dtype, block: int, block_c: int, interpret: bool):
    """Run ``tile`` over the (C, D) message ``msg`` on the (D, C) grid.

    ``col`` is the (C,) per-client column (weights or dequant scales) or
    None.  Returns z' (D,) = z - alpha_z * (phi + psi * sum(tiles) / n_div).
    """
    (D,) = z.shape
    C = msg.shape[0]
    # a block that spans a whole axis is legal at any size; a smaller one
    # must be a multiple of the dtype's (sublane, 128) tile
    bd = D if D <= block else block
    bc = C if C <= block_c else block_c
    in_specs = [
        pl.BlockSpec((1, bd), lambda i, c: (0, i)),
        pl.BlockSpec((bc, bd), lambda i, c: (c, i)),
        pl.BlockSpec((1, bd), lambda i, c: (0, i)),
    ]
    args = [z[None], msg, phi_mean[None]]
    if col is not None:
        in_specs.append(pl.BlockSpec((bc, 1), lambda i, c: (c, 0)))
        args.append(col.reshape(C, 1))
    out = pl.pallas_call(
        functools.partial(_fold_kernel, tile=tile, psi=psi, alpha_z=alpha_z,
                          n_div=n_div, n_rows=C, block_c=bc),
        grid=(pl.cdiv(D, bd), pl.cdiv(C, bc)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bd), lambda i, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), z.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[0]


def sign_agg(z: jnp.ndarray, W: jnp.ndarray, phi_mean: jnp.ndarray,
             psi: float, alpha_z: float, *, block: int = BLOCK,
             interpret: bool = True) -> jnp.ndarray:
    """z: (D,); W: (C, D); phi_mean: (D,). Returns updated z (D,)."""
    return _tiled_fold(_sign_tile, z, W, phi_mean, None, psi, alpha_z,
                       n_div=W.shape[0], acc_dtype=jnp.float32, block=block,
                       block_c=BLOCK_C, interpret=interpret)


def sign_agg_weighted(z: jnp.ndarray, W: jnp.ndarray, phi_mean: jnp.ndarray,
                      weights: jnp.ndarray, psi: float, alpha_z: float, *,
                      block: int = BLOCK, n_total: int = 0,
                      interpret: bool = True) -> jnp.ndarray:
    """Staleness-weighted consensus update (the FedAsync-decayed Eq. 20
    sum): client i's sign message is scaled by its staleness weight
    ``weights[i] = s(t - tau_i)`` inside the same one-pass fused tile loop
    as :func:`sign_agg` — the weight column is tiled with the client axis
    and broadcasts over the lane dimension, so the decayed reduction costs
    no extra HBM traffic over the unweighted kernel.

    z: (D,); W: (C, D); phi_mean: (D,); weights: (C,).  Returns z' (D,).
    ``n_total`` overrides the sum's divisor (default: the C rows of W) —
    the active-subset round reduces an (S_max, D) gathered block but still
    normalizes by the fleet size C.
    """
    return _tiled_fold(_weighted_tile, z, W, phi_mean, weights, psi, alpha_z,
                       n_div=n_total or W.shape[0], acc_dtype=jnp.float32,
                       block=block, block_c=BLOCK_C, interpret=interpret)


def sign_agg_weighted_int8(z: jnp.ndarray, payload: jnp.ndarray, scale,
                           phi_mean: jnp.ndarray, psi: float, alpha_z: float,
                           *, block: int = BLOCK, n_total: int = 0,
                           interpret: bool = True) -> jnp.ndarray:
    """Consensus update from the int8 wire format: the server reads the
    (C, D) message matrix as int8 (1 byte/coordinate of HBM traffic) and
    dequantizes in VMEM with the (C,) per-client f32 ``scale`` column.

    ``payload``: (C, D) int8 signs in {-1, 0, +1}; ``scale``: (C,) f32
    staleness weights or ``None`` for the unweighted message (exact int32
    reduction).  z: (D,); phi_mean: (D,).  Returns z' (D,).
    ``n_total`` overrides the divisor (fleet size C) when the payload is
    a gathered (S_max, D) active-subset block.
    """
    weighted = scale is not None
    return _tiled_fold(_int8_weighted_tile if weighted else _int8_tile,
                       z, payload, phi_mean, scale, psi, alpha_z,
                       n_div=n_total or payload.shape[0],
                       acc_dtype=jnp.float32 if weighted else jnp.int32,
                       block=block, block_c=BLOCK_C_INT8,
                       interpret=interpret)
