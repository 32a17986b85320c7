"""Pallas TPU flash attention (train / prefill), GQA-aware.

Block-tiled online-softmax attention: grid = (B, H, nQ, nK) with the KV
axis innermost ("arbitrary" semantics — iterated sequentially on the TPU
core), accumulating (acc, m, l) in VMEM scratch and writing the output tile
once after the last KV block.  MXU-aligned tiles (q/k blocks multiples of
128 where the head dim allows).  GQA is handled in the index maps: query
head h reads KV head h // (H // Hkv) — no materialized KV repetition
(the XLA fallback broadcasts KV across the query-head group in HBM).

Causal + sliding-window masking is applied per tile from absolute indices;
fully-masked tiles are skipped with ``pl.when`` (the causal lower triangle
costs ~2x fewer tiles, exactly the win the roofline's compute term shows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, n_k: int, bq: int,
            bk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    # tile-level skip: in causal mode the whole KV tile is in the future
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window) \
            if causal else run

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= ki <= qi
        if window:
            ok &= ki > qi - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (BQ, BK)
        corr = jnp.exp(m_prev - m_new)                     # (BQ, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_q, n_k = Sq // bq, Sk // bk
    scale = float(1.0 / (D ** 0.5))

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, n_k=n_k, bq=bq, bk=bk)
    grid = (B, H, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),    # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
