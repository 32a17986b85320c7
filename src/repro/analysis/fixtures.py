"""Seeded-violation fixtures: for every built-in rule, a deliberately
broken reference implementation it must catch, paired with a clean twin
it must pass.  A rule with no failing fixture is a rule that silently
rots — these run in ``tests/test_analysis.py`` and in the CLI's
``--selftest`` (a fail-first CI step), so a traversal or rule regression
can't land quietly.

The broken implementations are not strawmen: ``int8_wrapping_sign_sum``
is the pre-PR-4 accumulator that wrapped silently at C >= 128, and
``key_reusing_corrupt`` is the bug class the PR-6 fleet-indexed RNG
convention (fold_in per (leaf, client id)) exists to prevent.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.rules import (
    AccumulationDtypeRule,
    F64LeakageRule,
    HostSyncRule,
    MemoryContractRule,
    RngDisciplineRule,
    Rule,
)

C_FIX = 4096      # fleet width for the memory-contract fixture
D_FIX = 64
S_FIX = 8


# ---------------------------------------------------------------------------
# accumulation-dtype: the pre-PR-4 int8 sign-sum accumulator
# ---------------------------------------------------------------------------
def int8_wrapping_sign_sum(payload: jax.Array) -> jax.Array:
    """BROKEN (pre-PR-4): folds int8 sign messages in an int8 accumulator.
    |sum| can reach C, but int8 saturates at 127 — at C >= 128 the fold
    wraps and the consensus sign flips silently."""
    def body(j, acc):
        return acc + payload[j]                      # int8 + int8 -> int8
    acc0 = jnp.zeros(payload.shape[1:], jnp.int8)
    return jax.lax.fori_loop(0, payload.shape[0], body, acc0)


def int32_sign_sum(payload: jax.Array) -> jax.Array:
    """CLEAN (the PR-4 fix): widen per-message, accumulate in int32,
    narrow only at the wire boundary."""
    def body(j, acc):
        return acc + payload[j].astype(jnp.int32)
    acc0 = jnp.zeros(payload.shape[1:], jnp.int32)
    return jax.lax.fori_loop(0, payload.shape[0], body, acc0)


# ---------------------------------------------------------------------------
# rng-discipline: a key-reusing corrupt variant
# ---------------------------------------------------------------------------
def key_reusing_corrupt(key: jax.Array, w: jax.Array,
                        b: jax.Array) -> tuple:
    """BROKEN: draws the gaussian attack payload for every leaf from the
    SAME key — the 'random' corruption is perfectly correlated across
    leaves (and across clients if vmapped), which defeats the threat
    model the robust aggregator is evaluated against."""
    nw = 10.0 * jax.random.normal(key, w.shape, jnp.float32)
    nb = 10.0 * jax.random.normal(key, b.shape, jnp.float32)
    return nw, nb


def fleet_indexed_corrupt(key: jax.Array, w: jax.Array,
                          b: jax.Array) -> tuple:
    """CLEAN (the PR-6 convention, as in ``byzantine.corrupt``): one
    fold_in-derived subkey per leaf — same structure as the broken twin,
    differing only in key hygiene."""
    kw = jax.random.fold_in(key, 0)
    kb = jax.random.fold_in(key, 1)
    nw = 10.0 * jax.random.normal(kw, w.shape, jnp.float32)
    nb = 10.0 * jax.random.normal(kb, b.shape, jnp.float32)
    return nw, nb


# ---------------------------------------------------------------------------
# memory-contract: a densifying 'sparse' fold
# ---------------------------------------------------------------------------
def densifying_block_fold(W_all: jax.Array, idx: jax.Array) -> jax.Array:
    """BROKEN: folds an S-row active block by masking the full fleet
    state — materializes a (C, D) intermediate, exactly what the O(S)
    round contract forbids (at C=1M this is the 4 GB allocation the
    sparse path exists to avoid)."""
    mask = jnp.zeros((W_all.shape[0],), jnp.float32).at[idx].set(1.0)
    masked = W_all * mask[:, None]                   # (C, D) intermediate
    return jnp.sum(masked, axis=0)


def gathered_block_fold(W_all: jax.Array, idx: jax.Array) -> jax.Array:
    """CLEAN: gather the S active rows first; every intermediate after
    the gather is (S, D)."""
    block = W_all[idx]                               # (S, D)
    return jnp.sum(block, axis=0)


# ---------------------------------------------------------------------------
# host-sync: a debug print inside the round
# ---------------------------------------------------------------------------
def chatty_round_step(z: jax.Array) -> jax.Array:
    """BROKEN: a host callback inside the jitted step — every round
    synchronizes with the host."""
    z2 = z * 0.5
    jax.debug.print("z mean = {m}", m=z2.mean())
    return z2


def quiet_round_step(z: jax.Array) -> jax.Array:
    """CLEAN: returns the metric as a device value for the driver to
    log after the step."""
    z2 = z * 0.5
    return z2 + 0.0 * z2.mean()


# ---------------------------------------------------------------------------
# f64-leakage: an accidental float64 promotion
# ---------------------------------------------------------------------------
def f64_promoting_step(z: jax.Array) -> jax.Array:
    """BROKEN (only expressible with x64 enabled): a float64 numpy
    constant promotes the whole expression to f64."""
    scale = np.float64(0.125)
    return z * scale


def _trace_f64_broken():
    with jax.enable_x64(True):
        return jax.make_jaxpr(f64_promoting_step)(
            jax.ShapeDtypeStruct((D_FIX,), jnp.float64))


def _trace_f64_clean():
    return jax.make_jaxpr(lambda z: z * np.float32(0.125))(
        jax.ShapeDtypeStruct((D_FIX,), jnp.float32))


# ---------------------------------------------------------------------------
# fixture registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Fixture:
    name: str
    rule_id: str
    make_rule: Callable[[], Rule]
    bindings: Dict[str, int]
    trace_broken: Callable[[], object]   # () -> ClosedJaxpr
    trace_clean: Callable[[], object]


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mk(fn, *avals):
    return lambda: jax.make_jaxpr(fn)(*avals)


FIXTURES: List[Fixture] = [
    Fixture(
        name="int8-accumulating-fold",
        rule_id="accumulation-dtype",
        make_rule=AccumulationDtypeRule,
        bindings={},
        trace_broken=_mk(int8_wrapping_sign_sum,
                         _sds((256, D_FIX), jnp.int8)),
        trace_clean=_mk(int32_sign_sum, _sds((256, D_FIX), jnp.int8)),
    ),
    Fixture(
        name="key-reusing-corrupt",
        rule_id="rng-discipline",
        make_rule=RngDisciplineRule,
        bindings={},
        trace_broken=_mk(key_reusing_corrupt,
                         _sds((2,), jnp.uint32),
                         _sds((D_FIX, 4)), _sds((4,))),
        trace_clean=_mk(fleet_indexed_corrupt,
                        _sds((2,), jnp.uint32),
                        _sds((D_FIX, 4)), _sds((4,))),
    ),
    Fixture(
        name="densifying-block-fold",
        rule_id="memory-contract",
        make_rule=lambda: MemoryContractRule(
            "C", allow_primitives=("scatter", "scatter-add"),
            min_inner_elems=3),
        bindings={"C": C_FIX},
        trace_broken=_mk(densifying_block_fold,
                         _sds((C_FIX, D_FIX)), _sds((S_FIX,), jnp.int32)),
        trace_clean=_mk(gathered_block_fold,
                        _sds((C_FIX, D_FIX)), _sds((S_FIX,), jnp.int32)),
    ),
    Fixture(
        name="chatty-round-step",
        rule_id="host-sync",
        make_rule=HostSyncRule,
        bindings={},
        trace_broken=_mk(chatty_round_step, _sds((D_FIX,))),
        trace_clean=_mk(quiet_round_step, _sds((D_FIX,))),
    ),
    Fixture(
        name="f64-promoting-step",
        rule_id="f64-leakage",
        make_rule=F64LeakageRule,
        bindings={},
        trace_broken=_trace_f64_broken,
        trace_clean=_trace_f64_clean,
    ),
]


def run_selftest() -> List[str]:
    """Check every fixture: the broken jaxpr must trip its rule, the
    clean twin must not.  Returns a list of failure descriptions (empty
    == healthy)."""
    from repro.analysis.verify import lint_jaxpr
    problems: List[str] = []
    for fx in FIXTURES:
        rule = fx.make_rule()
        broken = lint_jaxpr(fx.trace_broken(), [rule], fx.bindings,
                            name=f"{fx.name}/broken")
        hits = [f for f in broken.findings if f.rule == fx.rule_id]
        if not hits:
            problems.append(
                f"{fx.name}: rule '{fx.rule_id}' MISSED its seeded "
                f"violation")
        clean = lint_jaxpr(fx.trace_clean(), [fx.make_rule()],
                           fx.bindings, name=f"{fx.name}/clean")
        false_pos = [f for f in clean.findings
                     if f.rule == fx.rule_id and f.severity == "error"]
        if false_pos:
            problems.append(
                f"{fx.name}: rule '{fx.rule_id}' false-positives on the "
                f"clean twin: {false_pos[0].format()}")
    return problems
