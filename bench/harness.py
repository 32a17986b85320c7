"""One run of one cell: set-up, the measured window, the check.

The window drives the program's own entry and nothing else:
``benchmarks.common.train_bafdp(..., round_impl="sparse", schedule=...)``
-> ``FederatedRun.run`` -> the jitted ``bafdp.bafdp_round_sparse`` ->
``ops.sign_consensus`` (the Pallas fold on a TPU).  The harness jits
nothing of its own on that path and allocates nothing on the device; it
watches through the ``on_round`` hook, and ends the call by raising from
the hook once the window's time is up.

Rounds 1 to 3 are the check's rounds and, with the rest of the warm-up,
lie outside the window: the hook copies what the check compares to the
host before the next round is dispatched.  The window starts when the
last warm-up round is ready.  In it, completion is observed with one
round of lag: ``on_round(t)`` runs once round t is dispatched and waits
for round t-1's metrics, so the host may run one round ahead, as the
program does on its own, and the stamps still mark when each round
finished.  The window ends when its last round's state is ready.  With a
trace, the profiler covers the window's first ``TRACE_SECONDS``.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, Optional

import jax
import numpy as np

from bench import check as check_lib
from bench import reference, traffic_gen
from bench import trace as trace_lib

WARMUP = 5               # rounds before the window; the first 3 are checked
N_CHECK = reference.N_ROUNDS
MIN_ROUND_S = 0.01       # the horizon has room for rounds this short
TRACE_SECONDS = 3.0      # the profiler covers the window's first seconds
SEED_MOD = 2_000_000_000  # program seeds stay inside int32 and RandomState


class WindowClosed(Exception):
    """Raised from ``on_round`` to end ``train_bafdp`` after the window."""


def _host(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(l) for k, l in flat}


class Observer:
    """The ``on_round`` hook: the check's readings, then the window."""

    def __init__(self, seconds: float, adam_b1: float,
                 trace_dir: Optional[str] = None):
        self.seconds = seconds
        self.b1 = adam_b1
        self.trace_dir = trace_dir
        self.readings = {"loss": [], "grad": None, "change": None}
        self._snap = None
        self.t0 = None
        self.stamps = []
        self.first = WARMUP
        self.last = None
        self.losses = []
        self.first_round_at = None
        self.compiles_in_window = 0
        self.trace_from = None
        self._window_span = None
        self._prev = None

    # -- the check's readings (set-up) ----------------------------------
    def _read(self, t, state, m):
        jax.block_until_ready(state)
        self.readings["loss"].append(float(m["loss"]))
        parts = {"W": state.W, "z": state.z, "phi": state.phi,
                 "eps": state.eps, "lam": state.lam}
        if t == 0:
            # norms of host copies: the device holds nothing of the check's
            self.readings["grad"] = {
                k: float(np.linalg.norm(v.ravel().astype(np.float64)))
                / (1.0 - self.b1) for k, v in _host(state.opt["m"]).items()}
            self._snap = {k: _host(v) for k, v in parts.items()}
        if t == N_CHECK - 1:
            change = {}
            for name, tree in parts.items():
                for k, v in _host(tree).items():
                    d = v.astype(np.float32) - self._snap[name][k]
                    change[name + k] = float(np.linalg.norm(d.ravel()))
            self.readings["change"] = change
            self._snap = None

    def on_compile(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration" \
                and self.t0 is not None and self.last is None:
            self.compiles_in_window += 1

    def __call__(self, t, state, m):
        if self.first_round_at is None:
            self.first_round_at = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.on_round"):
            if t < N_CHECK:
                self._read(t, state, m)
            if t < self.first - 1:
                return
            prev, self._prev = self._prev, m
            if t == self.first - 1:
                jax.block_until_ready(m)
                self.t0 = time.perf_counter()
                self.stamps = [self.t0]
                if self.trace_dir:
                    self._start_trace()
                return
            if t > self.first:
                # round t is queued behind round t-1: wait for t-1 only
                self._done(prev)
            if self.stamps[-1] - self.t0 >= self.seconds:
                jax.block_until_ready(state)
                self.close(t)
                raise WindowClosed

    def _done(self, m):
        jax.block_until_ready(m)
        self.stamps.append(time.perf_counter())
        self.losses.append(m["loss"])
        if self._window_span is not None \
                and self.stamps[-1] - self.t0 >= TRACE_SECONDS:
            self._stop_trace()

    def close(self, t):
        """Stamp round ``t``, the window's last, once it is ready."""
        self._done(self._prev)
        self._prev = None
        self.last = t
        if self._window_span is not None:
            self._stop_trace()

    def _start_trace(self):
        # host spans, no per-call Python tracing: it would slow the host
        # that drives the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window_span = jax.profiler.TraceAnnotation(
            trace_lib.WINDOW_SPAN)
        self.trace_from = time.perf_counter() - self.t0
        self._window_span.__enter__()

    def _stop_trace(self):
        self._window_span.__exit__(None, None, None)
        self._window_span = None
        jax.profiler.stop_trace()


def fed_config(cfg: Dict):
    from repro.configs import FedConfig
    return FedConfig(n_clients=cfg["fleet"]["n_clients"], **cfg["fed"])


def run(cfg: Dict, traffic: Dict, limits: Dict, seed: int, seconds: float,
        *, process_start: float, trace_dir: Optional[str] = None,
        program=None) -> Dict:
    """One run; returns the record that the metrics and the result line
    are made from.  ``program`` replaces ``train_bafdp`` (tests plant
    faults through it)."""
    from benchmarks.common import train_bafdp

    program = program or train_bafdp
    prog_seed = seed % SEED_MOD
    fed = fed_config(cfg)
    C = fed.n_clients
    t_sched = time.perf_counter()
    horizon = WARMUP + math.ceil(seconds / MIN_ROUND_S) + 1
    sched = traffic_gen.schedule(traffic, C, prog_seed, horizon)
    schedule_s = time.perf_counter() - t_sched

    obs = Observer(seconds, fed.adam_b1, trace_dir)
    jax.monitoring.register_event_duration_secs_listener(obs.on_compile)
    compile_s = [0.0]

    def count_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    t_call = time.perf_counter()
    try:
        final, _, _ = program(
            cfg["fleet"]["dataset"], cfg["model"]["horizon"], fed,
            rounds=horizon, seed=prog_seed,
            input_sigma=cfg["training"]["input_sigma"], schedule=sched,
            optimizer=fed.omega_optimizer, round_impl="sparse",
            on_round=obs)
        # a program fast enough to finish the horizon ends the window early
        jax.block_until_ready(final)
        obs.close(horizon - 1)
        del final
    except WindowClosed:
        pass
    finally:
        if obs._window_span is not None:
            obs._stop_trace()
        jax.monitoring.unregister_event_duration_listener(obs.on_compile)
        jax.monitoring.unregister_event_duration_listener(count_compile)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    trace_summary = None
    if trace_dir:
        trace_summary = trace_lib.reduce_profile(
            jax.profiler.ProfileData.from_file(
                trace_lib.find_xplane(trace_dir)))
    losses = [float(x) for x in obs.losses]
    obs.losses = []
    gc.collect()

    rounds = list(range(obs.first, obs.last + 1))
    intervals = np.diff(obs.stamps)
    window_s = obs.stamps[-1] - obs.t0
    rows = [(int(sched.arrivals[r]), int(np.unique(
        sched.round_winners(r)).size)) for r in rounds]
    updates = sum(k for k, _ in rows)

    t_ref = time.perf_counter()
    first_rows = [row for _, row in zip(range(N_CHECK), sched.padded_rows())]
    ref = reference.readings(cfg, prog_seed, first_rows)
    reference_s = time.perf_counter() - t_ref
    numbers = check_lib.gaps(obs.readings, ref)
    failed = sum(1 for x in losses if not math.isfinite(x))
    return {
        "seed": seed, "program_seed": prog_seed,
        "setup_s": obs.t0 - process_start,
        "setup_parts": {
            "before_schedule_s": t_sched - process_start,
            "schedule_s": schedule_s,
            "to_first_round_s": obs.first_round_at - t_call,
            "backend_compile_s": compile_s[0],
            "warmup_rounds_s": obs.t0 - obs.first_round_at,
        },
        "window_s": window_s,
        "rounds": len(rounds),
        "round_rows": rows,
        "updates": updates,
        "interval_s": intervals.tolist(),
        "round_done_s": [x - obs.t0 for x in obs.stamps[1:]],
        "trace_from_s": obs.trace_from,
        "compiles_in_window": obs.compiles_in_window,
        "failed_rounds": failed,
        "memory_peak_bytes": peak,
        "trace": trace_summary,
        "reference_s": reference_s,
        "numbers": numbers,
        "readings": {"program": obs.readings, "reference": ref},
        "correct": failed == 0 and check_lib.judge(numbers, limits),
        "model": cfg["model"], "n_clients": C,
        "batch": cfg["training"]["batch"], "local_steps": fed.local_steps,
        "s_max": sched.s_max,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
    }


def end_to_end(record: Dict) -> Dict[str, float]:
    """The end-to-end metrics, all from the host clock and the device's
    allocator, over every round and all the time of the window."""
    iv = np.asarray(record["interval_s"])
    return {
        "setup_s": record["setup_s"],
        "client_updates_per_s": record["updates"] / record["window_s"],
        "round_ms_p95": float(np.percentile(iv, 95)) * 1e3,
        "peak_hbm_gib": (record["memory_peak_bytes"] or float("nan"))
        / 2 ** 30,
    }
