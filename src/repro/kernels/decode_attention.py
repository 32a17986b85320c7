"""Pallas TPU flash-decode: one query token vs. a long KV cache.

Decode attention is purely HBM-bandwidth-bound (the KV cache is read once
per token; arithmetic intensity ~ 1 FLOP/byte).  The kernel tiles the cache
length into VMEM blocks, keeps the online-softmax running (acc, m, l) for
the whole query-head group of a KV head in VMEM scratch, and applies the
validity mask (``pos < length``) from absolute indices — so ragged batches
cost no extra HBM reads.

grid = (B, Hkv, nL), KV-length axis innermost/sequential.
q is laid out (B, Hkv, G, D) (G = query-head group size) so one grid step
services the entire GQA group of its KV head — the cache block is read
once, not G times.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BL = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, n_l: int, bl: int):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    start = il * bl

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (BL, D)
        v = v_ref[0, 0].astype(jnp.float32)               # (BL, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, BL)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(il == n_l - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     length: jnp.ndarray, *, bl: int = DEFAULT_BL,
                     interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, D); k, v: (B, Hkv, L, D); length: (B,) or scalar.

    Returns (B, H, D)."""
    B, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    G = H // Hkv
    bl = min(bl, L)
    assert L % bl == 0, (L, bl)
    n_l = L // bl
    scale = float(1.0 / (D ** 0.5))
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_kernel, scale=scale, n_l=n_l, bl=bl)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_l),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM, block_shape=(1,),
                         index_map=lambda b, h, il: (b,)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, il: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bl, D), lambda b, h, il: (b, h, il, 0)),
            pl.BlockSpec((1, 1, bl, D), lambda b, h, il: (b, h, il, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, il: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, D)
