"""The program's own spans and stage scopes, read from a profiler trace.

``bench.trace.reduce_profile`` sees the device and the harness; this
module adds what the program marks itself (``repro.tracing`` host spans,
``jax.named_scope`` stages of the round):

* ``host_spans``: each program span name on the harness's main thread,
  with its durations clipped to the traced window;
* ``idle_by_span``: the device's idle seconds in the window, each idle
  instant given to the innermost program span open on the main thread
  then, and to ``unattributed`` where none is;
* ``stage_time``: device seconds per stage of the round (``bafdp.*``, the
  innermost such scope in each op's ``op_name``) and ``unscoped``, each busy instant
  given to the innermost op running then: ``window`` over the whole
  window, all modules (it sums to the device's busy time), and
  ``per_round`` over the ``rounds`` executions of the round's module
  that lie wholly inside the window, divided by ``rounds``; and the
  unscoped ops that took most of the window (``unscoped_ops``), which
  are the ops XLA adds itself, such as copies.

The window, the device's busy time and its idle gaps are those of
``bench.trace.reduce_profile``, computed the same way.  A device op's
event carries only its HLO instruction; the instruction's ``op_name``
comes from the module's HLO, which the trace keeps in its
``/host:metadata`` plane (read here from the ``.xplane.pb`` bytes, since
``ProfileData`` does not expose that plane).  The round's module is the
one whose instructions carry the stage scopes.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace as trace_lib

PROGRAM_SPANS = ("fed.", "data.", "schedule.")   # repro.tracing's names
STAGE = re.compile(r"\bbafdp\.[a-z_]+")          # core/bafdp's scopes
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"


def span_name(name: str) -> str:
    """An annotation's name without metadata encoded into it
    (``name#k=v,...#``)."""
    return name.split("#", 1)[0]


def instruction(op_event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event ran: its name is the
    instruction's text, ``%fusion.3 = f32[...] fusion(...), ...``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


# -- what the trace keeps of each module's HLO ----------------------------
def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message: an int for
    a scalar, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _first(msg, num: int):
    return next((v for n, v in _fields(msg) if n == num), None)


def _text(msg, num: int) -> str:
    v = _first(msg, num)
    return bytes(v).decode() if v is not None else ""


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """For each module in the trace's metadata plane (keyed by its name as
    the ``XLA Modules`` line shows it, ``jit_f(<program id>)``), the stage
    scope of each of its instructions that has one.

    Field numbers: ``XSpace.planes`` 1; ``XPlane`` name 2, event_metadata
    4 (map entry: value 2), stat_metadata 5; ``XEventMetadata`` name 2,
    stats 5; ``XStat`` metadata_id 1, bytes_value 6; ``HloProto``
    hlo_module 1; ``HloModuleProto`` computations 3;
    ``HloComputationProto`` instructions 2; ``HloInstructionProto`` name
    1, metadata 7; ``OpMetadata`` op_name 2."""
    buf = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf):
        if num != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        stat_ids = set()
        for n, entry in _fields(plane):
            if n == 5:
                md = _first(entry, 2)
                if md is not None and _text(md, 2) == HLO_PROTO:
                    stat_ids.add(_first(md, 1) or 0)
        for n, entry in _fields(plane):
            if n != 4:
                continue
            md = _first(entry, 2)
            if md is None:
                continue
            for k, stat in _fields(md):
                if k == 5 and (_first(stat, 1) or 0) in stat_ids:
                    out[_text(md, 2)] = _module_scopes(_first(stat, 6))
    return out


def _module_scopes(hlo_proto) -> Dict[str, str]:
    scopes = {}
    module = _first(hlo_proto, 1) if hlo_proto is not None else None
    if module is None:
        return scopes
    for n, comp in _fields(module):
        if n != 3:
            continue
        for k, instr in _fields(comp):
            if k != 2:
                continue
            meta = _first(instr, 7)
            hits = STAGE.findall(_text(meta, 2)) if meta is not None else []
            if hits:
                scopes[_text(instr, 1)] = hits[-1]      # the innermost
    return scopes


# -- interval bookkeeping --------------------------------------------------
def innermost(spans: Sequence[Tuple[object, float, float]], lo: float,
              hi: float) -> List[Tuple[float, float, object]]:
    """[lo, hi] cut into segments, each labelled by the innermost span
    open over it (None where none is).  Spans nest, as events of one
    thread or one device line do; of two that overlap without nesting,
    the later one holds the overlap."""
    segs: List[Tuple[float, float, object]] = []
    stack: List[Tuple[object, float]] = []
    cur = lo

    def advance(to):
        nonlocal cur
        while stack and stack[-1][1] <= to:
            label, end = stack.pop()
            if end > cur:
                segs.append((cur, end, label))
                cur = end
        if to > cur:
            segs.append((cur, to, stack[-1][0] if stack else None))
            cur = to

    for label, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        advance(s)
        stack.append((label, e))
    advance(hi)
    return segs


def attribute(gaps: Sequence[Tuple[float, float]],
              segs: Sequence[Tuple[float, float, Optional[str]]]
              ) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``gaps`` under each segment's label
    (``unattributed`` for None)."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                key = name or UNATTRIBUTED
                out[key] = out.get(key, 0.0) + ov * 1e-9
            k += 1
    return out


# -- the reduction ---------------------------------------------------------
def _trace_parts(pd):
    """``bench.trace.reduce_profile``'s window and main thread, and each
    device's op and module events."""
    window, host, devices, dropped = None, [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = (), ()
            for line in plane.lines:
                if line.name == trace_lib.OPS_LINE:
                    ops = line.events
                elif line.name == MODULES_LINE:
                    modules = line.events
                dropped += [e.start_ns for e in line.events
                            if e.name == trace_lib.DROPPED]
            devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns)
                          for e in line.events]
                spans = [ev for ev in events
                         if ev[0] == trace_lib.WINDOW_SPAN]
                if spans:
                    window = spans[0][1:]
                    host = [ev for ev in events
                            if ev[0] != trace_lib.WINDOW_SPAN]
    if window is None:
        raise ValueError(
            f"trace holds no host span {trace_lib.WINDOW_SPAN!r}")
    lo, hi = window
    hi = min([hi] + [d for d in dropped if d > lo])
    return lo, hi, host, devices


def _device_stages(ops, modules, scopes, lo, hi):
    """(busy intervals, stage seconds over the window, stage seconds and
    count of the round's executions wholly inside it, seconds of each
    unscoped op over the window) for one device."""
    execs = sorted((e.start_ns, e.end_ns, e.name) for e in modules)
    starts = [x[0] for x in execs]
    whole = {x for x, (s, t, name) in enumerate(execs)
             if scopes.get(name) and lo <= s and t <= hi}
    scope_of: Dict[Tuple[str, str], str] = {}
    labels: Dict[tuple, tuple] = {}
    spans, busy = [], []
    for e in ops:
        s, t = e.start_ns, e.end_ns
        x = bisect.bisect_right(starts, s) - 1
        if x >= 0 and execs[x][1] < s:
            x = -1
        mod = execs[x][2] if x >= 0 else ""
        name = e.name
        scope = scope_of.get((mod, name))
        if scope is None:
            scope = scope_of[(mod, name)] = scopes.get(mod, {}).get(
                instruction(name), UNSCOPED)
        key = (scope, x, name if scope == UNSCOPED else None)
        label = labels.setdefault(key, key)
        spans.append((label, s, t))
        if min(t, hi) > max(s, lo):
            busy.append((max(s, lo), min(t, hi)))
    window: Dict[str, float] = {}
    rounds: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    for s, t, label in innermost(spans, lo, hi):
        if label is None:
            continue
        stage, x, name = label
        window[stage] = window.get(stage, 0.0) + (t - s) * 1e-9
        if x in whole:
            rounds[stage] = rounds.get(stage, 0.0) + (t - s) * 1e-9
        if name is not None:
            unscoped[name] = unscoped.get(name, 0.0) + (t - s) * 1e-9
    return trace_lib.merge(busy), window, rounds, len(whole), unscoped


def reduce_spans(path: str, top: int = 10) -> Dict:
    """``host_spans``, ``idle_by_span`` and ``stage_time`` of the
    ``.xplane.pb`` at ``path``; ``stage_time.unscoped_ops`` names the
    ``top`` unscoped ops by their seconds in the window."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    lo, hi, host, devices = _trace_parts(ProfileData.from_serialized_xspace(
        raw))
    spans = []
    host_spans: Dict[str, List[float]] = {}
    for name, s, e in host:
        name = span_name(name)
        s, e = max(s, lo), min(e, hi)
        if e > s and name.startswith(PROGRAM_SPANS):
            spans.append((name, s, e))
            host_spans.setdefault(name, []).append((e - s) * 1e-9)
    all_busy, window, per_round, rounds, unscoped = [], {}, {}, 0, {}
    for ops, modules in devices:
        busy, w, r, n, u = _device_stages(ops, modules, scopes, lo, hi)
        if not busy:
            continue
        all_busy += busy
        rounds += n
        for total, part in ((window, w), (per_round, r), (unscoped, u)):
            for k, v in part.items():
                total[k] = total.get(k, 0.0) + v
    idle = trace_lib.gaps(trace_lib.merge(all_busy), lo, hi)
    return {
        "host_spans": host_spans,
        "idle_by_span": attribute(idle, innermost(spans, lo, hi)),
        "stage_time": {
            "rounds": rounds,
            "window": window,
            "per_round": {k: v / rounds for k, v in per_round.items()}
            if rounds else {},
            "unscoped_ops": sorted(
                ([n[:trace_lib.NAME_CHARS], v] for n, v in unscoped.items()),
                key=lambda x: -x[1])[:top],
        },
    }
