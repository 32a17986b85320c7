"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device work
is every event on the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
the traced window is the host span ``bench.window`` that the harness
opens when the window starts, cut short where the device tracer reports
that it dropped events.
The thread that holds that span is the harness's main thread, and its
other events say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"   # the device tracer ran out of buffer
NAME_CHARS = 160                     # an op's name is its whole HLO line

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between the merged busy ones."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def _label(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """Name of the main-thread host event that overlaps the gap most;
    between equal overlaps the shortest (innermost) one wins."""
    best, key = "host:idle", (0.0, 0.0)
    for name, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = name, (ov, -(e - s))
    return best


def reduce_profile(pd, top: int = 10) -> Dict:
    """Summarise a ``jax.profiler.ProfileData``: window length, device
    busy time (averaged over the devices that ran anything), total time
    and count per device op name, the ops that took most time and the
    longest idle gaps, each labelled by the host."""
    window = None
    host: List[Tuple[str, float, float]] = []
    device_lines = []
    dropped: List[float] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append(line)
                dropped += [e.start_ns for e in line.events
                            if e.name == DROPPED]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns)
                          for e in line.events]
                spans = [ev for ev in events if ev[0] == WINDOW_SPAN]
                if spans:
                    window = spans[0][1:]
                    host = [ev for ev in events if ev[0] != WINDOW_SPAN]
    if window is None:
        raise ValueError(f"trace holds no host span {WINDOW_SPAN!r}")
    lo, hi = window
    # past a buffer drop the device line is empty, not idle: end there
    hi = min([hi] + [d for d in dropped if d > lo])
    op_time: Dict[str, List[float]] = {}
    busy_per_device = []
    all_busy: List[Interval] = []
    for line in device_lines:
        iv = []
        for e in line.events:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t <= s:
                continue
            iv.append((s, t))
            acc = op_time.setdefault(e.name, [0.0, 0])
            acc[0] += (t - s) * 1e-9
            acc[1] += 1
        if iv:
            merged = merge(iv)
            busy_per_device.append(sum(e - s for s, e in merged))
            all_busy += merged
    if not busy_per_device:
        raise ValueError("no device op ran inside the traced window")
    merged_all = merge(all_busy)
    idle = sorted(gaps(merged_all, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_per_device) / len(busy_per_device) * 1e-9,
        "n_devices": len(busy_per_device),
        "op_time": op_time,
        "device_ops": sorted(([n[:NAME_CHARS], v[0]]
                              for n, v in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) * 1e-9]
                      for g in idle[:top]],
    }


def kernel_time(summary: Dict, patterns: Sequence[str]
                ) -> Tuple[float, int]:
    """Summed device seconds and call count of the ops whose name holds
    any of ``patterns``."""
    hits = [v for n, v in summary["op_time"].items()
            if any(p in n for p in patterns)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def traced_rows(record: Dict) -> List[Tuple[int, int]]:
    """The ``round_rows`` of the window's rounds that completed inside the
    traced window, which opens ``trace_from_s`` into the run's window and
    lasts the trace's ``window_s`` (cut where the tracer dropped events)."""
    lo = record["trace_from_s"]
    hi = lo + record["trace"]["window_s"]
    return [row for row, done in zip(record["round_rows"],
                                     record["round_done_s"])
            if lo <= done <= hi]
