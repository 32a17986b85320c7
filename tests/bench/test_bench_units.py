"""The benchmark's yardstick on the CPU: trace reduction, peaks, counts,
the comparison and the metric readers.  No topology is described and no
chip is needed."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, check, counts, peaks  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

MLP_H1 = {"closeness_len": 6, "period_len": 3, "n_meta": 9, "n_text": 4,
          "hidden": [128, 128, 64], "horizon": 1}
MLP_H24 = dict(MLP_H1, horizon=24)

# Two devices' worth of ops on one chip and the harness's host spans, in
# microseconds: window [10, 110]; ops [0, 20] (clipped to [10, 20]),
# [30, 50], [45, 60] (overlap), [70, 75] (the fold), [100, 130]
# (clipped to [100, 110]).  Busy 10 + 30 + 5 + 10 = 55 of 100.  Idle gaps
# [20, 30] under bench.on_round, [60, 70] and [75, 100] under a dispatch.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 30000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 45000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 70000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 30000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 130000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "gather.2" } }
  event_metadata { key: 3 value { id: 3 name: "_fold_kernel" } }
  event_metadata { key: 4 value { id: 4 name: "jit_round" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 18000000 duration_ps: 14000000 }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.on_round" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(round)" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace_lib.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_trace_busy_and_idle(summary):
    assert summary["window_s"] == pytest.approx(100e-6)
    assert summary["busy_s"] == pytest.approx(55e-6)
    assert summary["n_devices"] == 1
    assert cells.reader("device_idle_share")({"trace": summary}) \
        == pytest.approx(45.0)


def test_trace_gaps_are_labelled_by_host_spans(summary):
    gaps = summary["idle_gaps"]
    assert [g[0] for g in gaps] == ["PjitFunction(round)", "bench.on_round",
                                    "PjitFunction(round)"]
    assert [g[1] for g in gaps] == pytest.approx([25e-6, 10e-6, 10e-6])


def test_trace_op_times_and_kernel(summary):
    assert dict(summary["device_ops"])["fusion.1"] == pytest.approx(40e-6)
    assert trace_lib.kernel_time(summary, ["_fold_kernel"]) == (
        pytest.approx(5e-6), 1)
    assert trace_lib.kernel_time(summary, ["no_such_kernel"]) == (0, 0)


def test_trace_window_ends_where_the_device_tracer_dropped_events():
    from jax.profiler import ProfileData
    dropped = TRACE.replace(
        'lines { id: 2 name: "XLA Modules"',
        'lines { id: 3 name: "XLA TraceMe" timestamp_ns: 0\n'
        '    events { metadata_id: 5 offset_ps: 60000000 duration_ps: 1 } }\n'
        '  lines { id: 2 name: "XLA Modules"').replace(
        '  event_metadata { key: 4 value { id: 4 name: "jit_round" } }',
        '  event_metadata { key: 4 value { id: 4 name: "jit_round" } }\n'
        '  event_metadata { key: 5 value { id: 5 name: '
        '"Trace Buffers Dropped" } }')
    s = trace_lib.reduce_profile(ProfileData.from_text_proto(dropped))
    # window [10, 60]: busy [10, 20] and [30, 60]
    assert s["window_s"] == pytest.approx(50e-6)
    assert s["busy_s"] == pytest.approx(40e-6)


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    no_window = TRACE.replace('"bench.window"', '"other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace_lib.reduce_profile(ProfileData.from_text_proto(no_window))


def test_merge_and_gaps():
    assert trace_lib.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace_lib.gaps([(0, 3), (5, 6)], 0, 10) == [(3, 5), (6, 10)]


def test_peaks_refuse_cpu_and_unknown_kinds():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="platform"):
        peaks.peak("cpu", platform="cpu")
    with pytest.raises(ValueError, match="unknown device kind"):
        peaks.peak("TPU v9 imaginary")


@pytest.mark.parametrize("model, params, flops", [
    # 22*128 + 128*128 + 128*64 + 64*1 = 27,456 MACs, 2,816 in layer 0:
    # 2 * 32 * (2 * 27,456 + 24,640) = 5,091,328
    (MLP_H1, 27_777, 5_091_328),
    # 22*128 + 128*128 + 128*64 + 64*24 = 28,928 MACs:
    # 2 * 32 * (2 * 28,928 + 26,112) = 5,373,952
    (MLP_H24, 29_272, 5_373_952),
])
def test_update_flops_hand_worked(model, params, flops):
    assert counts.n_params(model) == params
    assert counts.update_flops(model, batch=32) == flops
    assert counts.update_flops(model, batch=32, local_steps=2) == 2 * flops


def test_round_bytes_hand_worked():
    # per client 10 * 27,777 * 4 + 32 * 23 * 4 = 1,114,024 bytes; per round
    # z read and written (2 * 27,777 * 4) and eps, lambda (3 * C * 4)
    want = 6000 * 1_114_024 + 222_216 + 120_000
    assert counts.round_min_bytes(MLP_H1, 6000, 10_000, 32) == want
    # the fold: (S + 3) * D * 4 per leaf plus the weight column per leaf
    assert counts.fold_min_bytes(MLP_H1, 6000) == \
        6003 * 27_777 * 4 + 8 * 6000 * 4


def test_window_readers_on_a_synthetic_record(summary):
    # a 2 s window of 20 rounds, one every 0.1 s; the trace opens 0.05 s
    # in and lasts 1 s, so rounds 1-10 completed inside it
    rec = {"trace": dict(summary, window_s=1.0), "model": MLP_H1,
           "round_rows": [(6000, 6000)] * 20,
           "round_done_s": [0.1 * i for i in range(1, 21)],
           "trace_from_s": 0.05, "n_clients": 10_000,
           "batch": 32, "local_steps": 1, "s_max": 6000,
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}}
    assert len(trace_lib.traced_rows(rec)) == 10
    mfu = cells.reader("round_mfu")(rec)
    assert mfu == pytest.approx(100 * 10 * 6000 * 5_091_328 / 197e12)
    hbm = cells.reader("round_hbm_share")(rec)
    assert hbm == pytest.approx(
        100 * 10 * counts.round_min_bytes(MLP_H1, 6000, 10_000, 32) / 819e9)
    fold = cells.reader("fold_roofline")(rec)
    assert fold == pytest.approx(
        100 * counts.fold_min_bytes(MLP_H1, 6000) / 8 / 5e-6 / 819e9)
    for name in ("round_mfu", "round_hbm_share", "fold_roofline",
                 "device_idle_share"):
        assert cells.reader(name)(dict(rec, trace=None)) is None
    # no round completed inside the trace: nothing to read
    late = dict(rec, trace_from_s=2.5)
    for name in ("round_mfu", "round_hbm_share"):
        assert cells.reader(name)(late) is None


def test_fold_reader_is_silent_without_the_kernel(summary):
    tr = dict(summary, op_time={"fusion.1": [1.0, 3]})
    assert cells.reader("fold_roofline")(dict(
        {"trace": dict(summary, op_time={
            '%custom-call.2 = custom-call(), '
            'custom_call_target="tpu_custom_call"': [2.0, 8]})},
        model=MLP_H1, s_max=10,
        device={"kind": "TPU v5 lite", "platform": "tpu"})) == \
        pytest.approx(100 * counts.fold_min_bytes(MLP_H1, 10) / 2.0 / 819e9)
    rec = {"trace": tr, "model": MLP_H1, "s_max": 10,
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}}
    assert cells.reader("fold_roofline")(rec) is None


def _readings(scale=1.0, loss=(0.5, 0.4, 0.3)):
    leaves = {"['l0']['w']": 2.0, "['l0']['b']": 1.0, "['l1']['w']": 3.0}
    change = {g + k: v * scale for g in check.GROUPS for k, v in
              leaves.items()}
    return {"loss": list(loss), "grad": dict(leaves), "change": change}


def test_check_gaps():
    ref = _readings()
    same = check.gaps(_readings(), ref)
    assert (same["loss_gap"], same["grad_gap"], same["change_gap"]) == (
        0, 0, 0)
    still = check.gaps(_readings(scale=0.0), ref)
    assert still["change_gap"] == pytest.approx(1.0)
    off = check.gaps(_readings(loss=(0.5, 0.4, 0.33)), ref)
    assert off["loss_gap"] == pytest.approx(0.1)
    limits = {n: 0.01 for n in check.NUMBERS}
    assert check.judge(same, limits)
    assert not check.judge(still, limits)
    for bad in ((float("nan"), 0.4, 0.3), (0.5, 0.4, float("nan")),
                (0.5, 0.4)):
        assert not check.judge(check.gaps(_readings(loss=bad), ref), limits)


def test_check_leaves_out_leaves_with_nought_gradient():
    ref = _readings()
    ref["grad"]["['l0']['b']"] = 1e-6
    prog = _readings()
    prog["grad"]["['l0']['b']"] = 5.0          # round-off, not compared
    for g in check.GROUPS:
        prog["change"][g + "['l0']['b']"] = 7.0
    out = check.gaps(prog, ref)
    assert out["left_out"] == ["['l0']['b']"]
    assert out["grad_gap"] == 0 and out["change_gap"] == 0


def test_check_missing_leaf_fails():
    prog = _readings()
    del prog["change"]["phi['l1']['w']"]
    out = check.gaps(prog, _readings())
    assert not check.judge(out, {n: 1.0 for n in check.NUMBERS})
