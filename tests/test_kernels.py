"""Per-kernel allclose vs the ref.py oracles across shape/dtype sweeps
(interpret=True executes the kernel body on CPU; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# C = 300 is not a multiple of the kernels' client tile (256 f32 rows), so
# the last client tile is partial and its rows past C must add zero
@pytest.mark.parametrize("D", [128, 1024, 5000, 8193])
@pytest.mark.parametrize("C", [2, 16, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sign_agg(D, C, dtype):
    key = jax.random.PRNGKey(D + C)
    z = jax.random.normal(key, (D,), dtype)
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D), dtype)
    phi = (jax.random.normal(jax.random.fold_in(key, 2), (D,)) * 0.01
           ).astype(dtype)
    got = ops.sign_agg(z, W, phi, 0.005, 0.01, impl="interpret")
    want = ref.sign_agg_ref(z, W, phi, 0.005, 0.01)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n_pad", [0, 7])
@pytest.mark.parametrize("D", [128, 1024, 5000, 8193])
@pytest.mark.parametrize("C", [2, 16, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sign_agg_weighted(D, C, dtype, n_pad):
    """Pallas staleness-weighted sign reduction vs the jnp oracle.  With
    ``n_pad`` the block carries extra rows at weight 0 (the sparse round's
    padding), which must add exactly zero while the divisor stays C."""
    key = jax.random.PRNGKey(D * C)
    z = jax.random.normal(key, (D,), dtype)
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D), dtype)
    phi = (jax.random.normal(jax.random.fold_in(key, 2), (D,)) * 0.01
           ).astype(dtype)
    sw = jax.random.uniform(jax.random.fold_in(key, 3), (C,),
                            minval=0.05, maxval=1.0)
    if n_pad:
        W_pad = jnp.concatenate([W, jnp.full((n_pad, D), 1e9, dtype)])
        sw_pad = jnp.concatenate([sw, jnp.zeros((n_pad,))])
        got = ops.sign_consensus(z, W_pad, phi, sw_pad, 0.005, 0.01,
                                 impl="interpret", n_total=C)
    else:
        got = ops.sign_agg_weighted(z, W, phi, sw, 0.005, 0.01,
                                    impl="interpret")
    want = ref.sign_agg_weighted_ref(z, W, phi, sw, 0.005, 0.01)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_sign_agg_weighted_unit_weights_match_unweighted():
    """All-ones weights must reduce to the plain sign_agg kernel."""
    key = jax.random.PRNGKey(11)
    D, C = 2048, 8
    z = jax.random.normal(key, (D,))
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D))
    phi = jax.random.normal(jax.random.fold_in(key, 2), (D,)) * 0.01
    a = ops.sign_agg_weighted(z, W, phi, jnp.ones((C,)), 0.005, 0.01,
                              impl="interpret")
    b = ops.sign_agg(z, W, phi, 0.005, 0.01, impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_sign_agg_weighted_matches_bafdp_decayed_sum():
    """The kernel computes exactly the decayed Eq. 20 sum bafdp_round
    builds in plain XLA: sum_i s_i sign(z - w_i) / C (divided by C, not
    by sum(s_i))."""
    key = jax.random.PRNGKey(3)
    D, C, psi, a_z = 513, 6, 0.02, 0.05
    z = jax.random.normal(key, (D,))
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D))
    phi = jax.random.normal(jax.random.fold_in(key, 2), (D,)) * 0.01
    sw = jnp.asarray([1.0, 0.5, 0.25, 1.0, 0.1, 0.75])
    sgn = jnp.sign(z[None] - W)
    manual = z - a_z * (phi + psi * jnp.sum(sgn * sw[:, None], axis=0) / C)
    got = ops.sign_agg_weighted(z, W, phi, sw, psi, a_z, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(manual),
                               rtol=1e-5, atol=1e-6)


def test_sign_agg_weighted_bounded_influence_scales_with_weight():
    """RSA's bounded influence survives weighting: a corrupt client with
    staleness weight s moves the update by at most 2 psi alpha s / C."""
    key = jax.random.PRNGKey(7)
    D, C, psi, a = 512, 8, 0.01, 0.1
    z = jax.random.normal(key, (D,))
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D))
    phi = jnp.zeros((D,))
    sw = jnp.full((C,), 1.0).at[0].set(0.2)
    base = ref.sign_agg_weighted_ref(z, W, phi, sw, psi, a)
    evil = ref.sign_agg_weighted_ref(z, W.at[0].set(1e9), phi, sw, psi, a)
    assert float(jnp.max(jnp.abs(evil - base))) \
        <= 2 * psi * a * 0.2 / C + 1e-6


@pytest.mark.parametrize("S,H,Hkv,Dh", [(128, 4, 2, 64), (256, 2, 2, 128),
                                        (256, 6, 2, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention(S, H, Hkv, Dh, causal, window):
    key = jax.random.PRNGKey(S + H)
    B = 2
    q = jax.random.normal(key, (B, S, H, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh))
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="interpret", bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 4, 64), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 2, 64), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 2, 64), dtype)
    got = ops.flash_attention(q, k, v, impl="interpret", bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("L,H,Hkv,Dh,bl", [(256, 4, 2, 64, 64),
                                           (512, 8, 8, 128, 128),
                                           (1024, 2, 1, 64, 256)])
def test_decode_attention(L, H, Hkv, Dh, bl):
    key = jax.random.PRNGKey(L)
    B = 3
    q = jax.random.normal(key, (B, H, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, Hkv, Dh))
    length = jnp.array([1, L // 2, L], jnp.int32)
    got = ops.decode_attention(q, k, v, length, impl="interpret", bl=bl)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("S,D,N,chunk,bd", [(128, 64, 8, 32, 32),
                                            (256, 256, 16, 64, 128),
                                            (64, 128, 4, 64, 64)])
def test_ssm_scan(S, D, N, chunk, bd):
    key = jax.random.PRNGKey(S + D)
    B = 2
    a = jax.random.uniform(key, (B, S, D, N), minval=0.2, maxval=0.999)
    b = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D, N)) * 0.1
    got = ops.ssm_scan(a, b, impl="interpret", chunk=chunk, bd=bd)
    want = ref.ssm_scan_ref(a, b, jnp.zeros((B, D, N)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------- unified consensus-path dispatch ---------------------------
def _consensus_problem(D=1500, C=12, seed=0):
    key = jax.random.PRNGKey(seed)
    z = jax.random.normal(key, (D,))
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D))
    phi = jax.random.normal(jax.random.fold_in(key, 2), (D,)) * 0.01
    return z, W, phi


# C = 1100 leaves a partial last client tile for both wire formats
# (256 f32 rows, 1024 int8 rows per tile)
@pytest.mark.parametrize("C", [12, 1100])
@pytest.mark.parametrize("decay", ["constant", "hinge", "poly"])
@pytest.mark.parametrize("message", ["f32", "int8"])
def test_sign_consensus_dispatch_parity(decay, message, C):
    """Fused (interpret) vs XLA vs the ref oracles, for every
    staleness_decay mode and both wire formats: one dispatch, one result.
    The int8 wire format is lossless for sign messages, so the only
    tolerance is ulp-level program-structure noise (XLA lowers ``mean``
    vs ``sum / C`` differently across separately-jitted programs), NOT a
    quantization budget."""
    from repro.configs import FedConfig
    from repro.core.bafdp import staleness_weights

    z, W, phi = _consensus_problem(C=C)
    stale = jnp.arange(C, dtype=jnp.float32)
    weights = None if decay == "constant" else staleness_weights(
        stale, FedConfig(staleness_decay=decay))
    want = np.asarray(
        ref.sign_agg_weighted_ref(
            z, W, phi,
            jnp.ones((C,)) if weights is None else weights, 0.005, 0.01))
    for impl in ("xla", "interpret"):
        got = ops.sign_consensus(z, W, phi, weights, 0.005, 0.01,
                                 message=message, impl=impl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6,
                                   err_msg=f"{decay}/{message}/{impl}")
    # within one impl the int8 path must match the f32 path bit-for-bit:
    # dequantized messages ARE the f32 messages, same reduction structure
    np.testing.assert_array_equal(
        np.asarray(ops.sign_consensus(z, W, phi, weights, 0.005, 0.01,
                                      message="int8", impl="interpret")),
        np.asarray(ops.sign_consensus(z, W, phi, weights, 0.005, 0.01,
                                      message="int8", impl="xla")))


def test_sign_consensus_rejects_unknown_message():
    z, W, phi = _consensus_problem(D=128, C=4)
    with pytest.raises(ValueError, match="sign message"):
        ops.sign_consensus(z, W, phi, None, 0.005, 0.01, message="int4")


def test_int8_wire_format_round_trips_losslessly():
    """encode -> decode reproduces the f32 message bit-for-bit: the payload
    is the sign (exact in int8), the per-client f32 scale is the staleness
    weight."""
    from repro.distributed import collectives

    z, W, _ = _consensus_problem(D=700, C=9, seed=3)
    sw = jax.random.uniform(jax.random.PRNGKey(5), (9,), minval=0.05,
                            maxval=1.0)
    msg = collectives.encode_sign_message(z, W, sw)
    assert msg.payload.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(collectives.decode_sign_message(msg)),
        np.asarray(jnp.sign(z[None] - W) * sw[:, None]))
    # wire accounting: 1 byte/coordinate + 4 bytes/client (weighted only —
    # the unweighted message carries no scale column)
    assert collectives.message_bytes(9, 700, "int8") == (9 * 700, 36)
    assert collectives.message_bytes(9, 700, "int8", weighted=False) \
        == (9 * 700, 0)
    assert collectives.message_bytes(9, 700, "f32") == (9 * 700 * 4, 0)


def test_int8_sign_sum_accumulates_past_c128():
    """The overflow regression (C=200): every client on the same side of z
    drives |sum_i sign_i| = C past the int8 range.  The wire-format reduce
    accumulates in int32 and matches the f32 oracle exactly; the pre-fix
    int8-dtype accumulator provably wraps on the same input."""
    from repro.distributed import collectives

    C, D = 200, 600
    z = jax.random.normal(jax.random.PRNGKey(1), (D,))
    W = jnp.tile((z - 1000.0)[None], (C, 1))      # sign(z - w_i) = +1 all
    phi = jnp.zeros((D,))
    for impl in ("xla", "interpret"):
        got = ops.sign_consensus(z, W, phi, None, 0.005, 0.01,
                                 message="int8", impl=impl)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(ref.sign_agg_ref(z, W, phi, 0.005, 0.01)), impl)
    msg = collectives.encode_sign_message(z, W)
    np.testing.assert_array_equal(
        np.asarray(collectives.sign_sum(msg, C)), np.full(D, 1.0))
    # the old accumulator (dtype=int8) wraps 200 -> -56 on this exact input
    wrapped = jnp.sum(msg.payload, axis=0, dtype=jnp.int8)
    assert int(wrapped[0]) == 200 - 256, "C=200 no longer overflows int8?"


def test_sign_agg_bounded_influence():
    """The RSA property: one client's arbitrary corruption moves the update
    by at most psi*alpha/C per coordinate."""
    key = jax.random.PRNGKey(7)
    D, C, psi, a = 512, 8, 0.01, 0.1
    z = jax.random.normal(key, (D,))
    W = jax.random.normal(jax.random.fold_in(key, 1), (C, D))
    phi = jnp.zeros((D,))
    base = ref.sign_agg_ref(z, W, phi, psi, a)
    W_evil = W.at[0].set(1e9)
    evil = ref.sign_agg_ref(z, W_evil, phi, psi, a)
    assert float(jnp.max(jnp.abs(evil - base))) <= 2 * psi * a / C + 1e-6
