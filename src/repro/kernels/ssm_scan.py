"""Pallas TPU chunked diagonal-SSM scan:  h_t = a_t * h_{t-1} + b_t.

The GPU selective-scan kernel (Mamba) builds on warp shuffles for the
intra-warp scan; the TPU-idiomatic rethink is *chunked blocking*: the grid
walks (batch, channel-block, chunk) with the chunk axis innermost and
sequential; the carry ``h`` lives in VMEM scratch between chunk steps, and
within a chunk the recurrence runs as an in-VMEM fori_loop over time while
the (CH, BD, N) coefficient tiles stream from HBM once.  Sublane-aligned
channel blocks keep the VPU busy; no cross-chip traffic is involved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 128
DEFAULT_BD = 256


def _kernel(a_ref, b_ref, hs_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a_t = a_ref[0, t].astype(jnp.float32)     # (BD, N)
        b_t = b_ref[0, t].astype(jnp.float32)
        h = a_t * h + b_t
        hs_ref[0, t] = h.astype(hs_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h


def ssm_scan(a: jnp.ndarray, b: jnp.ndarray, *, chunk: int = DEFAULT_CHUNK,
             bd: int = DEFAULT_BD, interpret: bool = True) -> jnp.ndarray:
    """a, b: (B, S, D, N) -> hs: (B, S, D, N) with h_0 = 0 prior state."""
    B, S, D, N = a.shape
    chunk = min(chunk, S)
    bd = min(bd, D)
    assert S % chunk == 0 and D % bd == 0, (S, chunk, D, bd)
    n_c, n_d = S // chunk, D // bd

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, n_d, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda ib, idd, ic: (ib, ic, idd, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda ib, idd, ic: (ib, ic, idd, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd, N),
                               lambda ib, idd, ic: (ib, ic, idd, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
