"""fedlint rule system: ``Rule.check(eqn, ctx) -> [Finding]`` plus the five
built-in rules, each grounded in a bug this repo actually shipped (or a
class of bug the round-path contracts forbid):

``memory-contract``
    No equation output whose leading dim is a *bound dimension symbol*
    (``C``, ``S_max``, ...) with a non-trivial inner size — the
    generalization of the PR-5 "no dense (C, D) intermediate in the sparse
    round" and PR-7 "no (S_max, D) message block in the streamed fold"
    assertions.  Dims are bound at call time, so one rule covers C=6 and
    C=1M alike.  Also supports a flat per-output byte budget.

``accumulation-dtype``
    No reduction or loop-carried accumulator in a narrow wire dtype
    (int8/uint8/f16/bf16) — the exact class of the PR-4 int8 sign-sum
    accumulator that silently wrapped at C >= 128.

``rng-discipline``
    Every PRNG key consumption must trace back to a distinct
    ``split``/``fold_in`` derivation: drawing bits twice from one key, or
    folding the same data into the same key twice, yields correlated
    streams — the contract behind the PR-6 fleet-indexed attack RNG
    (draws key off (key, leaf, client id), never off block position).

``host-sync``
    No host round-trip (``io_callback``/``debug_callback``/...) inside a
    jitted round: a million-client round that silently synchronizes with
    the host every step is a performance bug the profiler only shows you
    in production.

``f64-leakage``
    No float64/complex128 values under the repo-wide x64-disabled
    assumption (a stray f64 doubles the wire and HBM cost of whatever it
    touches, and TPUs emulate it).

Rules are deliberately *structural*: they inspect the jaxpr, never run it,
so a C=1M contract check allocates nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.traversal import (
    format_path,
    iter_eqns_with_path,
    out_avals,
    subjaxprs,
)

SEVERITIES = ("error", "warning")

# dtypes that are wire/storage formats, never safe accumulators
NARROW_DTYPES = ("int8", "uint8", "float16", "bfloat16")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, locatable and baseline-able."""
    rule: str                 # rule id, e.g. "memory-contract"
    severity: str             # "error" | "warning"
    message: str              # human sentence
    path: str                 # equation path (traversal.format_path)
    primitive: str            # offending primitive name ("" for global)
    detail: str = ""          # stable specifics (shape/dtype/key id)
    hint: str = ""            # how to fix it

    @property
    def fingerprint(self) -> str:
        """Stable identity used by the baseline-suppression file.  Path
        and primitive pin the location; ``detail`` pins the shape/dtype
        so a *new* violation at an old location is not silently absorbed."""
        return f"{self.rule}|{self.primitive}|{self.path}|{self.detail}"

    def format(self) -> str:
        loc = f" at {self.path}" if self.path else ""
        prim = f" [{self.primitive}]" if self.primitive else ""
        hint = f"\n      hint: {self.hint}" if self.hint else ""
        det = f" ({self.detail})" if self.detail else ""
        return (f"{self.severity.upper():7s} {self.rule}{prim}{loc}: "
                f"{self.message}{det}{hint}")


@dataclasses.dataclass
class RuleContext:
    """Call-time context a rule checks against.

    ``bindings`` maps dimension symbols to this entrypoint's concrete
    sizes (e.g. ``{"C": 1_000_000, "S_max": 8}``) — the mechanism that
    lets one ``memory-contract`` rule govern every fleet size.  ``path``
    is the current equation's enclosing-primitive path (set by the
    engine before each ``check`` call).
    """
    bindings: Mapping[str, int] = dataclasses.field(default_factory=dict)
    path: Tuple[str, ...] = ()

    def dim(self, symbol: str) -> Optional[int]:
        v = self.bindings.get(symbol)
        return int(v) if v is not None else None


class Rule:
    """Base rule: subclass and implement ``check(eqn, ctx)`` (called for
    every equation, sub-jaxprs included) or override ``analyze`` for
    whole-program rules (``rng-discipline`` needs a dataflow pass)."""
    rule_id: str = "rule"
    severity: str = "error"
    hint: str = ""

    def analyze(self, closed_jaxpr, ctx: RuleContext) -> List[Finding]:
        findings: List[Finding] = []
        for eqn, path in iter_eqns_with_path(closed_jaxpr):
            ctx.path = path
            findings.extend(self.check(eqn, ctx))
        return findings

    def check(self, eqn, ctx: RuleContext) -> List[Finding]:
        return []

    def finding(self, ctx: RuleContext, message: str, *, primitive: str = "",
                detail: str = "", severity: Optional[str] = None,
                path: Optional[str] = None) -> Finding:
        return Finding(rule=self.rule_id,
                       severity=severity or self.severity,
                       message=message,
                       path=format_path(ctx.path) if path is None else path,
                       primitive=primitive, detail=detail, hint=self.hint)


# ---------------------------------------------------------------------------
# memory-contract
# ---------------------------------------------------------------------------
class MemoryContractRule(Rule):
    """No equation output of shape ``(dim, inner...)`` with
    ``prod(inner) >= min_inner_elems`` — where ``dim`` is a *symbol* bound
    to a concrete size in the call-time ``RuleContext``.

    ``allow_primitives`` exempts the sanctioned producers (the sparse
    round's state write-back ``scatter``s); ``dtypes`` restricts the rule
    to specific dtypes (the streamed-round variant only forbids the int8
    *wire payload* at full width — f32 working blocks are the point of
    the gathered path); ``max_bytes`` adds a flat per-output byte budget
    that needs no binding.  If ``dim`` is unbound in the context the
    dimension check is skipped (the byte budget still applies) — this is
    what lets the sparse round's contract decorator no-op when the dense
    oracle runs it at full width.
    """
    rule_id = "memory-contract"
    hint = ("gather the S active rows before computing (fed_state."
            "gather_clients) and scatter results back; never materialize "
            "the full fleet-width intermediate")

    def __init__(self, dim: str, *, allow_primitives: Sequence[str] = (),
                 min_inner_elems: int = 1,
                 dtypes: Optional[Sequence[str]] = None,
                 max_bytes: Optional[int] = None,
                 severity: str = "error"):
        self.dim = dim
        self.allow = frozenset(allow_primitives)
        self.min_inner = int(min_inner_elems)
        self.dtypes = frozenset(dtypes) if dtypes is not None else None
        self.max_bytes = max_bytes
        self.severity = severity

    def _dtype_ok(self, aval) -> bool:
        dt = getattr(aval, "dtype", None)
        return self.dtypes is None or (dt is not None
                                       and str(dt) in self.dtypes)

    def check(self, eqn, ctx: RuleContext) -> List[Finding]:
        prim = eqn.primitive.name
        bound = ctx.dim(self.dim)
        findings: List[Finding] = []
        for aval in out_avals(eqn):
            shape = getattr(aval, "shape", ())
            if not shape:
                continue
            nbytes = None
            dt = getattr(aval, "dtype", None)
            if dt is not None and hasattr(dt, "itemsize"):
                nbytes = int(np.prod(shape)) * dt.itemsize
            if (bound is not None and prim not in self.allow
                    and len(shape) >= 2 and shape[0] == bound
                    and int(np.prod(shape[1:])) >= self.min_inner
                    and self._dtype_ok(aval)):
                findings.append(self.finding(
                    ctx, f"({self.dim}, ...) intermediate materialized "
                         f"({self.dim}={bound})",
                    primitive=prim, detail=f"shape={tuple(shape)} "
                                           f"dtype={dt}"))
            if (self.max_bytes is not None and nbytes is not None
                    and nbytes > self.max_bytes and prim not in self.allow):
                findings.append(self.finding(
                    ctx, f"output exceeds the {self.max_bytes}-byte "
                         f"budget ({nbytes} bytes)",
                    primitive=prim, detail=f"shape={tuple(shape)} "
                                           f"dtype={dt}"))
        return findings


# ---------------------------------------------------------------------------
# accumulation-dtype
# ---------------------------------------------------------------------------
class AccumulationDtypeRule(Rule):
    """No reduction and no loop-carried accumulator in a narrow wire dtype.

    Two detection paths, matching how the PR-4 wrap bug could have been
    written:

    * a reduce-class primitive (``reduce_sum``/``dot_general``/``cumsum``/
      ...) whose *output* is narrow — e.g. ``jnp.sum(x, dtype=jnp.int8)``;
    * a ``while``/``scan`` whose carry is narrow AND whose body performs
      arithmetic in that dtype — the ``fori_loop`` shape of the original
      int8 accumulator (wraps silently at C >= 128 messages).

    A narrow carry that is merely threaded through untouched (a payload
    riding a scan) is NOT flagged.
    """
    rule_id = "accumulation-dtype"
    hint = ("accumulate in int32/float32 and convert to the wire dtype "
            "only at the encode boundary (see kernels/ref.sign_agg_"
            "int8_ref: the post-PR-4 reduction)")

    REDUCE_PRIMS = frozenset((
        "reduce_sum", "reduce_prod", "cumsum", "cumprod",
        "reduce_window_sum", "dot_general", "reduce_precision_sum",
    ))
    ARITH_PRIMS = frozenset(("add", "sub", "mul", "add_any"))
    LOOP_PRIMS = frozenset(("while", "scan"))

    def __init__(self, narrow: Sequence[str] = NARROW_DTYPES):
        self.narrow = frozenset(narrow)

    def _narrow(self, aval) -> Optional[str]:
        dt = getattr(aval, "dtype", None)
        return str(dt) if dt is not None and str(dt) in self.narrow else None

    def check(self, eqn, ctx: RuleContext) -> List[Finding]:
        prim = eqn.primitive.name
        findings: List[Finding] = []
        if prim in self.REDUCE_PRIMS:
            for aval in out_avals(eqn):
                dt = self._narrow(aval)
                if dt:
                    findings.append(self.finding(
                        ctx, f"reduction accumulates in the wire dtype "
                             f"{dt}",
                        primitive=prim,
                        detail=f"shape={tuple(getattr(aval, 'shape', ()))} "
                               f"dtype={dt}"))
        elif prim in self.LOOP_PRIMS:
            avals = out_avals(eqn)
            if prim == "scan":
                n_carry = eqn.params.get("num_carry", len(avals))
                carries = avals[:n_carry]
            else:
                carries = avals
            narrow_carry = {dt for a in carries
                            if (dt := self._narrow(a))}
            if not narrow_carry:
                return findings
            hits = set()
            for _, sub in subjaxprs(eqn):
                for sub_eqn, _ in iter_eqns_with_path(sub):
                    if sub_eqn.primitive.name not in self.ARITH_PRIMS:
                        continue
                    for aval in out_avals(sub_eqn):
                        dt = self._narrow(aval)
                        if dt in narrow_carry:
                            hits.add((dt, sub_eqn.primitive.name))
            for dt, arith in sorted(hits):
                findings.append(self.finding(
                    ctx, f"loop carries a {dt} accumulator updated by "
                         f"'{arith}' — wraps/rounds silently "
                         f"(the pre-PR-4 int8 sign-sum class)",
                    primitive=prim, detail=f"carry_dtype={dt} via {arith}"))
        return findings


# ---------------------------------------------------------------------------
# rng-discipline
# ---------------------------------------------------------------------------
class RngDisciplineRule(Rule):
    """Every key consumption must be a distinct derivation.

    The pass value-numbers the jaxpr (inlining through ``jit``-style call
    primitives, conservative fresh values at ``scan``/``while``/``cond``
    boundaries, so a key carried into a loop is a fresh key per
    iteration), then groups the PRNG-consuming equations —
    ``random_bits``, ``random_split``, ``random_fold_in`` — by the value
    number of the key they consume:

    * two ``random_bits``/``random_split`` consumptions of one key value
      -> ERROR: the bit streams overlap (both start the counter at 0);
    * two ``fold_in`` of the same key with the SAME data value -> ERROR:
      identical derived keys;
    * ``fold_in`` of the same key with distinct data (the sanctioned
      per-leaf / per-client derivation in ``byzantine.corrupt``) is
      clean;
    * a key consumed by both bit-generation and derivation -> WARNING:
      the derived stream can collide with the drawn bits.
    """
    rule_id = "rng-discipline"
    hint = ("derive one subkey per consumer: jax.random.split once, or "
            "fold_in with distinct data per use (the fleet-indexed "
            "(key, leaf, client-id) convention of byzantine.corrupt)")

    CALL_PRIMS = frozenset((
        "jit", "closed_call", "call", "remat2", "custom_jvp_call",
        "custom_vjp_call", "custom_jvp_call_jaxpr",
    ))
    OPAQUE_PRIMS = frozenset(("scan", "while", "cond"))
    CONSUME_PRIMS = frozenset(("random_bits", "random_split",
                               "random_fold_in"))

    def analyze(self, closed_jaxpr, ctx: RuleContext) -> List[Finding]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        memo: Dict[Any, int] = {}
        counter = [0]
        # consumption records: key_vn -> list of (kind, data_vn, path)
        consumed: Dict[int, List[Tuple[str, Optional[int], str]]] = {}

        def fresh() -> int:
            counter[0] += 1
            return counter[0]

        def vn_of(key) -> int:
            if key not in memo:
                memo[key] = fresh()
            return memo[key]

        def lit_key(lit) -> Any:
            v = getattr(lit, "val", None)
            try:
                arr = np.asarray(v)
                if arr.size <= 16:
                    return ("lit", str(arr.dtype), arr.tobytes())
            except Exception:
                pass
            return ("lit-id", id(v))

        def hashable_params(params) -> Any:
            def conv(v):
                if isinstance(v, dict):
                    return tuple(sorted((k, conv(x)) for k, x in v.items()))
                if isinstance(v, (tuple, list)):
                    return tuple(conv(x) for x in v)
                try:
                    hash(v)
                    return v
                except TypeError:
                    return ("id", id(v))
            return conv(params)

        def eval_jaxpr(jx, invar_vns, const_vns, path):
            env: Dict[Any, int] = {}
            for var, vn in zip(jx.invars, invar_vns):
                env[var] = vn
            for var, vn in zip(jx.constvars, const_vns):
                env[var] = vn

            def read(atom) -> int:
                if hasattr(atom, "val"):          # Literal
                    return vn_of(lit_key(atom))
                if atom in env:
                    return env[atom]
                env[atom] = fresh()               # defensive: unseen var
                return env[atom]

            for eqn in jx.eqns:
                prim = eqn.primitive.name
                in_vns = tuple(read(a) for a in eqn.invars)
                epath = path + (prim,)
                if prim in self.CONSUME_PRIMS:
                    kind = {"random_bits": "bits",
                            "random_split": "split",
                            "random_fold_in": "fold_in"}[prim]
                    data_vn = in_vns[1] if (kind == "fold_in"
                                            and len(in_vns) > 1) else None
                    consumed.setdefault(in_vns[0], []).append(
                        (kind, data_vn, format_path(path)))
                subs = list(subjaxprs(eqn))
                if prim in self.CALL_PRIMS and len(subs) == 1:
                    sub = subs[0][1]
                    if len(sub.invars) == len(in_vns):
                        out_vns = eval_jaxpr(
                            sub, list(in_vns),
                            [vn_of(("const", id(sub), i))
                             for i in range(len(sub.constvars))], epath)
                        for var, vn in zip(eqn.outvars, out_vns):
                            env[var] = vn
                        continue
                if subs:
                    # control flow (or an unrecognized call layout):
                    # sub-jaxpr inputs are fresh values — a key entering a
                    # loop is a fresh key each iteration; reuse INSIDE one
                    # body iteration is still caught
                    for _, sub in subs:
                        eval_jaxpr(sub, [fresh() for _ in sub.invars],
                                   [fresh() for _ in sub.constvars], epath)
                    for var in eqn.outvars:
                        env[var] = fresh()
                    continue
                # pure equation: hash-cons so identical computations get
                # identical value numbers (this is what makes "the same
                # key consumed twice" detectable through wrap/slice chains)
                pkey = (prim, hashable_params(eqn.params), in_vns)
                for i, var in enumerate(eqn.outvars):
                    env[var] = vn_of(("eqn", pkey, i))
            return [read(a) for a in jx.outvars]

        eval_jaxpr(jaxpr,
                   [fresh() for _ in jaxpr.invars],
                   [fresh() for _ in jaxpr.constvars], ())

        findings: List[Finding] = []
        for key_vn, uses in consumed.items():
            bitsish = [u for u in uses if u[0] in ("bits", "split")]
            folds = [u for u in uses if u[0] == "fold_in"]
            if len(bitsish) > 1:
                kinds = "+".join(sorted(u[0] for u in bitsish))
                findings.append(Finding(
                    rule=self.rule_id, severity="error",
                    message=f"one key value consumed by "
                            f"{len(bitsish)} bit-generating ops "
                            f"({kinds}) — the streams overlap",
                    path=bitsish[1][2], primitive="random_bits",
                    detail=f"key_vn={key_vn} n={len(bitsish)}",
                    hint=self.hint))
            seen_data: Dict[Optional[int], str] = {}
            for kind, data_vn, path in folds:
                if data_vn in seen_data:
                    findings.append(Finding(
                        rule=self.rule_id, severity="error",
                        message="fold_in of the same key with identical "
                                "data — derived keys collide",
                        path=path, primitive="random_fold_in",
                        detail=f"key_vn={key_vn} data_vn={data_vn}",
                        hint=self.hint))
                else:
                    seen_data[data_vn] = path
            if bitsish and folds:
                findings.append(Finding(
                    rule=self.rule_id, severity="warning",
                    message="key is both consumed for bits/split and "
                            "fold_in-derived — derived streams may "
                            "collide with the drawn bits",
                    path=bitsish[0][2], primitive="",
                    detail=f"key_vn={key_vn}", hint=self.hint))
        return findings


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------
class HostSyncRule(Rule):
    """No host round-trip inside a jitted round function."""
    rule_id = "host-sync"
    hint = ("compute metrics as device values and log them from the "
            "driver after the step returns; remove jax.debug.print / "
            "io_callback from the round")

    HOST_PRIMS = frozenset((
        "io_callback", "pure_callback", "debug_callback", "callback",
        "outside_call", "host_callback_call", "infeed", "outfeed",
        "debug_print",
    ))

    def __init__(self, allow: Sequence[str] = ()):
        self.allow = frozenset(allow)

    def check(self, eqn, ctx: RuleContext) -> List[Finding]:
        prim = eqn.primitive.name
        if prim in self.HOST_PRIMS and prim not in self.allow:
            return [self.finding(
                ctx, "host round-trip inside a jitted computation",
                primitive=prim)]
        return []


# ---------------------------------------------------------------------------
# f64-leakage
# ---------------------------------------------------------------------------
class F64LeakageRule(Rule):
    """No float64/complex128 equation outputs (x64 is disabled repo-wide;
    a silent f64 promotion doubles bytes and de-optimizes TPUs)."""
    rule_id = "f64-leakage"
    hint = ("keep literals/np arrays in float32, or np.asarray(x, "
            "np.float32) at the boundary; x64 stays disabled fleet-wide")

    WIDE = frozenset(("float64", "complex128"))

    def check(self, eqn, ctx: RuleContext) -> List[Finding]:
        findings = []
        for aval in out_avals(eqn):
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) in self.WIDE:
                findings.append(self.finding(
                    ctx, f"{dt} value under the x64-disabled assumption",
                    primitive=eqn.primitive.name,
                    detail=f"shape={tuple(getattr(aval, 'shape', ()))} "
                           f"dtype={dt}"))
        return findings


DEFAULT_RULES = (AccumulationDtypeRule, RngDisciplineRule, HostSyncRule,
                 F64LeakageRule)


def default_rules() -> List[Rule]:
    """The binding-free built-ins (memory-contract needs a dimension
    symbol, so it is always constructed explicitly)."""
    return [cls() for cls in DEFAULT_RULES]
