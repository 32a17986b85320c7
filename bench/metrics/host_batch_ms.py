"""host_batch_ms: median duration of the program's ``fed.batch`` span
(staging one round's per-client batches) in the traced window."""
import statistics


def read(record):
    spans = (record.get("trace") or {}).get("host_spans") or {}
    durations = spans.get("fed.batch")
    if not durations:
        return None
    return statistics.median(durations) * 1e3
