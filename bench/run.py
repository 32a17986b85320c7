"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload milano-h1.quorum-0.6 --seed 7 \
        --seconds 10 --trace 0

Prints one JSON line last on standard output: ``correct``, ``attempted``
(rounds in the window), ``failed`` (rounds whose loss was not finite),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
``check``: each compared number beside its limit, which also ends
standard error.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache: where the environment says, else at
# a fixed path inside the checkout (the path is part of every cache key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
# every program goes into it, however fast it compiled, so that a run
# after the checkout's first compiles nothing
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells, peaks
    cell = cells.load(args.workload, ROOT)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX finds "
                    f"{len(devices)}")
    peaks.peak(dev.device_kind, dev.platform)   # an unknown chip is an error

    from bench import harness
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    try:
        rec = harness.run(cell.config, cell.traffic, cell.limits, args.seed,
                          args.seconds, process_start=PROCESS_START,
                          trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)

    if args.trace:
        readers = cell.readers()
        values = {n: readers[n](rec) for n in readers}
        units = {m["name"]: m["unit"] for m in cell.per_layer}
    else:
        values = harness.end_to_end(rec)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    metrics = {n: {"value": values[n], "unit": units[n]}
               for n in units if values.get(n) is not None}
    device = dict(rec["device"])
    out = {"correct": bool(rec["correct"]), "attempted": rec["rounds"],
           "failed": rec["failed_rounds"], "metrics": metrics,
           "device": device}
    if rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    nums = rec["numbers"]
    out["detail"] = {
        "seed": rec["seed"], "updates": rec["updates"],
        "window_s": rec["window_s"], "setup_parts": rec["setup_parts"],
        "compiles_in_window": rec["compiles_in_window"],
        "reference_s": rec["reference_s"],
        "worst_leaf": nums["worst_leaf"], "left_out": nums["left_out"],
        "loss": {"program": rec["readings"]["program"]["loss"],
                 "reference": rec["readings"]["reference"]["loss"]}}
    out["check"] = {n: {"value": nums[n], "limit": cell.limits[n]}
                    for n in ("loss_gap", "grad_gap", "change_gap")}
    from bench import check
    for line in check.lines(nums, cell.limits):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
