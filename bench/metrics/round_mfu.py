"""round_mfu: the forecaster's forward and backward FLOPs of every update
delivered in the rounds that completed inside the traced window, over
that window, over the chip's bf16 peak."""
from bench import counts, peaks, trace


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    rows = trace.traced_rows(record)
    if not rows:
        return None
    dev = record["device"]
    flops = counts.window_totals(
        record["model"], rows, record["n_clients"],
        record["batch"], record["local_steps"])["flops"]
    return 100.0 * flops / tr["window_s"] / (
        peaks.peak(dev["kind"], dev["platform"])["bf16_flops_per_s"]
        * tr["n_devices"])
