"""round_hbm_share: the least bytes (bench.counts.round_min_bytes) of the
rounds that completed inside the traced window, over that window, over
the chip's HBM bandwidth."""
from bench import counts, peaks, trace


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    rows = trace.traced_rows(record)
    if not rows:
        return None
    dev = record["device"]
    moved = counts.window_totals(
        record["model"], rows, record["n_clients"],
        record["batch"], record["local_steps"])["bytes"]
    return 100.0 * moved / tr["window_s"] / (
        peaks.peak(dev["kind"], dev["platform"])["hbm_bytes_per_s"]
        * tr["n_devices"])
