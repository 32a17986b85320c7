"""fold_roofline: the Eq. (20) Pallas fold's least bytes
(bench.counts.fold_min_bytes, one call per parameter leaf) over its summed
device time in the trace, over the chip's HBM bandwidth.  The fold is
bound by bytes: it does one compare and one add per byte pair it reads."""
from bench import counts, peaks, trace

# the fold is the round's only Pallas kernel: its op is the custom call
# to the TPU kernel, named after the Python function where the name shows
KERNEL = ("_fold_kernel", "tpu_custom_call")


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    seconds, calls = trace.kernel_time(tr, KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    dev = record["device"]
    model = record["model"]
    moved = calls / counts.n_leaves(model) \
        * counts.fold_min_bytes(model, record["s_max"])
    return 100.0 * moved / seconds / \
        peaks.peak(dev["kind"], dev["platform"])["hbm_bytes_per_s"]
