"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernels are tested against (interpret=True
on CPU; real lowering on TPU).  They are also the XLA fallback path used by
the model code and the dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sign_agg_ref(z: jnp.ndarray, W: jnp.ndarray, phi_mean: jnp.ndarray,
                 psi: float, alpha_z: float) -> jnp.ndarray:
    """BAFDP/RSA server update (Eq. 20), flattened form.

    z: (D,) consensus; W: (C, D) stacked client params (already containing
    any Byzantine corruption); phi_mean: (D,) mean dual.
    Returns z - alpha_z * (phi_mean + psi * mean_i sign(z - w_i)).
    """
    sgn = jnp.sign(z[None, :].astype(jnp.float32) - W.astype(jnp.float32))
    dz = phi_mean.astype(jnp.float32) + psi * jnp.mean(sgn, axis=0)
    return (z.astype(jnp.float32) - alpha_z * dz).astype(z.dtype)


def sign_agg_weighted_ref(z: jnp.ndarray, W: jnp.ndarray,
                          phi_mean: jnp.ndarray, weights: jnp.ndarray,
                          psi: float, alpha_z: float) -> jnp.ndarray:
    """Staleness-weighted BAFDP server update: the FedAsync-decayed
    Eq. (20) sum, where client i's sign message is scaled by its
    staleness weight s(t - tau_i) before the cross-client reduction:

        z - alpha_z * (phi_mean + psi * sum_i s_i sign(z - w_i) / C)

    ``weights``: (C,) in (0, 1]; all-ones reduces to ``sign_agg_ref``.
    The sum is divided by C (not by sum(s_i)) — exactly the decayed sum
    ``bafdp_round`` computes when ``staleness_decay != "constant"``.
    """
    sgn = jnp.sign(z[None, :].astype(jnp.float32) - W.astype(jnp.float32))
    wsum = jnp.sum(sgn * weights[:, None].astype(jnp.float32),
                   axis=0) / W.shape[0]
    dz = phi_mean.astype(jnp.float32) + psi * wsum
    return (z.astype(jnp.float32) - alpha_z * dz).astype(z.dtype)


def fold_weighted_rowsum(X: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """``sum_j weights[j] * X[j]`` accumulated strictly in row order (a
    left-fold), in f32.

    XLA's vectorized reductions regroup terms by lane, so a masked sum
    over C rows and a compact sum over the S surviving rows of the same
    data do NOT agree bitwise.  A sequential fold does: adding a
    zero-weight row contributes an exact ``+-0.0`` (an IEEE-754 no-op for
    any accumulator this fold can produce), so folding C rows with S
    nonzero weights equals folding just those S rows in the same relative
    order.  This is the reduction the ``consensus_scope="active"`` dense
    round and the gathered sparse round share on the XLA path — the
    dense<->sparse bit-parity contract rests on it.  On the TPU the
    round's dual mean is one vectorised reduction instead
    (``ops.dual_mean``): on a TPU v5e the loop costs about 0.7 us a trip
    whatever the row's width, and the parity it buys holds only on the
    XLA path.
    """
    Xf = X.astype(jnp.float32)
    wf = weights.astype(jnp.float32)

    def body(j, acc):
        return acc + wf[j] * Xf[j]

    return jax.lax.fori_loop(0, X.shape[0], body,
                             jnp.zeros(X.shape[1:], jnp.float32))


def sign_agg_fold_ref(z: jnp.ndarray, W: jnp.ndarray, phi_mean: jnp.ndarray,
                      weights: jnp.ndarray, psi: float, alpha_z: float,
                      n_total: int) -> jnp.ndarray:
    """Order-canonical weighted consensus update — the active-scope /
    sparse-round oracle:

        z - alpha_z * (phi_mean + psi * fold_j w_j sign(z - W_j) / n_total)

    ``W``: (R, D) — R is C for the masked dense round (inactive rows carry
    weight 0) or the padded S_max for the gathered sparse block (padding
    rows carry weight 0); ``n_total`` is the fleet size C the sum is
    normalized by, independent of R.  Rows reduce strictly in order, so
    the masked C-row fold and the compact ascending-client-id fold are
    bit-identical (see :func:`fold_weighted_rowsum`).
    """
    zf = z.astype(jnp.float32)
    wf = weights.astype(jnp.float32)
    Wf = W.astype(jnp.float32)

    def body(j, acc):
        return acc + wf[j] * jnp.sign(zf - Wf[j])

    wsum = jax.lax.fori_loop(0, W.shape[0], body,
                             jnp.zeros_like(zf)) / n_total
    dz = phi_mean.astype(jnp.float32) + psi * wsum
    return (zf - alpha_z * dz).astype(z.dtype)


def _fold_chunks(R: int, chunk_size: int, fold_chunk, init):
    """Drive ``fold_chunk(start, size, acc)`` over ``[0, R)`` in row order:
    a ``lax.scan`` over the full ``chunk_size``-row chunks, then the static
    tail (R % chunk_size rows) as one short chunk.  Chunk boundaries never
    reorder a left-fold's additions, so the result is bit-identical to the
    single-pass fold for ANY chunk_size >= 1.  The tail is handled by a
    second call (R and chunk_size are static) instead of zero-padding, so
    no full-height (R, ...) intermediate is ever created."""
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    n_full, tail = divmod(R, chunk_size)

    acc = init
    if n_full:
        def body(acc, i):
            return fold_chunk(i * chunk_size, chunk_size, acc), None
        acc, _ = jax.lax.scan(body, acc, jnp.arange(n_full))
    if tail:
        acc = fold_chunk(jnp.asarray(n_full * chunk_size), tail, acc)
    return acc


def fold_weighted_rowsum_stream(X: jnp.ndarray, weights: jnp.ndarray,
                                chunk_size: int) -> jnp.ndarray:
    """Streaming :func:`fold_weighted_rowsum`: the identical left-fold,
    consumed ``chunk_size`` rows at a time (the FedBuff arrival-event
    shape).  Bit-identical to the materialized fold by construction — the
    row visit order is the same and a chunk boundary only splits the scan
    carry, never regroups an addition."""
    Xf = X.astype(jnp.float32)
    wf = weights.astype(jnp.float32)

    def fold_chunk(start, size, acc):
        Xc = jax.lax.dynamic_slice_in_dim(Xf, start, size)
        wc = jax.lax.dynamic_slice_in_dim(wf, start, size)

        def row(j, a):
            return a + wc[j] * Xc[j]

        return jax.lax.fori_loop(0, size, row, acc)

    return _fold_chunks(X.shape[0], chunk_size, fold_chunk,
                        jnp.zeros(X.shape[1:], jnp.float32))


def sign_agg_fold_stream_ref(z: jnp.ndarray, W: jnp.ndarray,
                             phi_mean: jnp.ndarray, weights: jnp.ndarray,
                             psi: float, alpha_z: float, n_total: int,
                             chunk_size: int,
                             message: str = "f32") -> jnp.ndarray:
    """Streaming :func:`sign_agg_fold_ref`: the order-canonical weighted
    consensus update consumed as an online reduction over arrival-event
    chunks of ``chunk_size`` rows — the server never holds more than one
    ``(chunk_size, D)`` message block at a time (jaxpr-asserted by the
    equivalence suite), instead of materializing all ``(S_max, D)``.

    ``message="int8"`` round-trips each chunk's signs through the int8
    wire format (a lossless quantization — the payload IS the sign), so
    the full int8 payload never exists either; bit-identical to both the
    f32 streaming fold and the materialized
    :func:`sign_agg_int8_fold_ref`."""
    if message not in ("f32", "int8"):
        raise ValueError(f"unknown sign message format: {message!r}")
    zf = z.astype(jnp.float32)
    wf = weights.astype(jnp.float32)
    Wf = W.astype(jnp.float32)

    def fold_chunk(start, size, acc):
        Wc = jax.lax.dynamic_slice_in_dim(Wf, start, size)
        wc = jax.lax.dynamic_slice_in_dim(wf, start, size)
        sgn = jnp.sign(zf[None, :] - Wc)
        if message == "int8":
            # chunk-local encode/decode: int8 is exact on a sign message
            sgn = sgn.astype(jnp.int8).astype(jnp.float32)

        def row(j, a):
            return a + wc[j] * sgn[j]

        return jax.lax.fori_loop(0, size, row, acc)

    wsum = _fold_chunks(W.shape[0], chunk_size, fold_chunk,
                        jnp.zeros_like(zf)) / n_total
    dz = phi_mean.astype(jnp.float32) + psi * wsum
    return (zf - alpha_z * dz).astype(z.dtype)


def fold_dual_rowsum(phi_rows: jnp.ndarray, weights: jnp.ndarray,
                     chunk_size: int = 0) -> jnp.ndarray:
    """``sum_j weights[j] * dequant(quant(phi_rows[j]))`` — the Eq. (22)
    dual-side left-fold through the int8 dual wire format
    (:mod:`repro.distributed.collectives`).  The absmax quantizer is
    row-local, so the masked dense block and the gathered sparse block
    fold identical decoded values — dense<->sparse bit-parity carries
    over to the quantized dual, offset from the f32 wire by at most the
    pinned per-coordinate tolerance.

    ``chunk_size=0`` materializes the decode; ``chunk_size>=1`` encodes,
    decodes, and folds one chunk of rows at a time (bit-identical — the
    quantizer is row-local and the fold order is unchanged)."""
    from repro.distributed import collectives

    if chunk_size == 0:
        dec = collectives.decode_dual_message(
            collectives.encode_dual_message(phi_rows))
        return fold_weighted_rowsum(dec, weights)
    phif = phi_rows.astype(jnp.float32)
    wf = weights.astype(jnp.float32)

    def fold_chunk(start, size, acc):
        pc = jax.lax.dynamic_slice_in_dim(phif, start, size)
        wc = jax.lax.dynamic_slice_in_dim(wf, start, size)
        dec = collectives.decode_dual_message(
            collectives.encode_dual_message(pc))

        def row(j, a):
            return a + wc[j] * dec[j]

        return jax.lax.fori_loop(0, size, row, acc)

    return _fold_chunks(phi_rows.shape[0], chunk_size, fold_chunk,
                        jnp.zeros(phi_rows.shape[1:], jnp.float32))


def sign_agg_int8_fold_ref(z: jnp.ndarray, payload: jnp.ndarray,
                           scale: jnp.ndarray, phi_mean: jnp.ndarray,
                           psi: float, alpha_z: float,
                           n_total: int) -> jnp.ndarray:
    """Order-canonical consensus update from the int8 wire format.  Each
    fold term is ``scale[j] * payload[j]`` with ``payload = sign(z - w_j)``
    exactly, i.e. the identical f32 value :func:`sign_agg_fold_ref` adds —
    the int8 message stays lossless under the active-scope reduction."""
    wsum = fold_weighted_rowsum(payload, scale) / n_total
    dz = phi_mean.astype(jnp.float32) + psi * wsum
    return (z.astype(jnp.float32) - alpha_z * dz).astype(z.dtype)


def sign_agg_int8_ref(z: jnp.ndarray, payload: jnp.ndarray,
                      scale, phi_mean: jnp.ndarray,
                      psi: float, alpha_z: float) -> jnp.ndarray:
    """BAFDP server update from the int8 wire format (the quantized
    Eq. (20) message, see :mod:`repro.distributed.collectives`).

    ``payload``: (C, D) int8 signs in {-1, 0, +1}; ``scale``: (C,) f32
    per-client dequant scales (the staleness weights s(d)) or ``None`` for
    the unweighted message.  The reduction accumulates in int32 (unweighted)
    or f32 (weighted) — NEVER in the int8 wire dtype, which wraps for
    C >= 128.  Given ``payload = sign(z - w_i)`` and ``scale = s``, this is
    bit-identical to :func:`sign_agg_weighted_ref` (the quantization of a
    sign message is lossless).
    """
    from repro.distributed.collectives import SignMessage, sign_sum
    ssum = sign_sum(SignMessage(payload=payload, scale=scale),
                    payload.shape[0])
    dz = phi_mean.astype(jnp.float32) + psi * ssum
    return (z.astype(jnp.float32) - alpha_z * dz).astype(z.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0) -> jnp.ndarray:
    """Plain softmax attention (GQA-aware).

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D). Returns (B, Sq, H, D) fp32-safe.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg * scale, kf)
    Sk = k.shape[1]
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)   # queries end-aligned with keys
    ki = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= ki <= qi
    if window:
        ok &= ki > qi - window
    logits = jnp.where(ok[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         length: jnp.ndarray) -> jnp.ndarray:
    """Single-token attention over a KV cache.

    q: (B, H, D); k, v: (B, L, Hkv, D); length: scalar or (B,) valid length.
    """
    B, H, D = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, D).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bkgd,bskd->bkgs", qg * scale, k.astype(jnp.float32))
    length = jnp.broadcast_to(jnp.asarray(length), (B,))
    valid = jnp.arange(L)[None, :] < length[:, None]            # (B, L)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def ssm_scan_ref(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray
                 ) -> jnp.ndarray:
    """Diagonal linear recurrence  h_t = a_t * h_{t-1} + b_t.

    a, b: (B, S, D, N); h0: (B, D, N). Returns hs: (B, S, D, N) (fp32).
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)

    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.transpose(1, 0, 2, 3), b.transpose(1, 0, 2, 3)))
    return hs.transpose(1, 0, 2, 3)
