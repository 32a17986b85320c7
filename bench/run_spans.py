"""Run one cell traced, with the program's own spans and stage scopes.

    python3 bench/run_spans.py --workload trento-h24.quorum-0.6 \
        --seed 7 --seconds 51

This is ``bench/run.py --trace 1`` itself (its checks, harness call,
profiler window and result line), with two additions around the harness
call: the program's span totals are recorded (``repro.tracing.recording``:
data preparation, state init, schedule build, each round's host steps),
and the trace is also reduced by ``bench/spans.py``.  After run.py's line
it prints one more JSON line, last: the metrics of ``METRICS`` and the
tables they are read from (``program_spans``, the median of each host
span, ``idle_by_span``, ``stage_time``).  Exits as run.py does, printing
no line of its own, where run.py fails.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# run.py's set-up: process start, import paths, compile cache, TRACE_DIR
from bench import run  # noqa: E402

# read from the program's spans and the round's stage scopes
METRICS = ("data_prep_s", "host_batch_ms", "dispatch_idle_share",
           "fold_ms", "local_step_ms")


def main(argv=None) -> int:
    from bench import cells, harness, spans
    from bench import trace as trace_lib
    from repro import tracing

    records = []
    run_cell = harness.run

    def run_recorded(*args, trace_dir, **kwargs):
        """``harness.run`` with the recorder on; the trace is reduced
        before run.py removes it."""
        with tracing.recording() as totals:
            rec = run_cell(*args, trace_dir=trace_dir, **kwargs)
        rec["program_spans"] = totals
        t0 = time.perf_counter()
        rec["trace"].update(spans.reduce_spans(
            trace_lib.find_xplane(trace_dir)))
        rec["reduce_spans_s"] = time.perf_counter() - t0
        records.append(rec)
        return rec

    harness.run = run_recorded
    argv = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(argv + ["--trace", "1"])
    if rc or not records:
        return rc
    rec = records[0]
    tr = rec["trace"]
    print(json.dumps({
        "metrics": {n: cells.reader(n)(rec) for n in METRICS},
        "program_spans": rec["program_spans"],
        "host_span_median_ms": {k: statistics.median(v) * 1e3
                                for k, v in tr["host_spans"].items() if v},
        "idle_by_span": tr["idle_by_span"],
        "stage_time": tr["stage_time"],
        "detail": {"seed": rec["seed"],
                   "rounds_traced": len(trace_lib.traced_rows(rec)),
                   "reduce_spans_s": rec["reduce_spans_s"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
