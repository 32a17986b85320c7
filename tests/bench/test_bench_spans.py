"""The reduction of the program's own spans and stage scopes
(``bench/spans.py``) and the readers built on it, on a small recorded
trace, on the CPU.  The device's summary (``bench.trace``) and its
readers read the same from the same trace with the new keys beside it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, spans  # noqa: E402
from bench import trace as trace_lib  # noqa: E402


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of length-delimited fields (number, bytes)."""
    return b"".join(_varint(num << 3 | 2) + _varint(len(v)) + v
                    for num, v in fields)


def _hlo(module, computations):
    """HloProto{hlo_module: {name, computations: [{name, instructions:
    [{name, metadata: {op_name}}]}]}}"""
    comps = []
    for cname, instrs in computations.items():
        fields = [(1, cname.encode())]
        for iname, op_name in instrs:
            ins = [(1, iname.encode())]
            if op_name:
                ins.append((7, _msg((2, op_name.encode()))))
            fields.append((2, _msg(*ins)))
        comps.append((3, _msg(*fields)))
    return _msg((1, _msg((1, module.encode()), *comps)))


def _octal(b):
    return "".join(f"\\{c:03o}" for c in b)


ROUND_HLO = _hlo("jit_round", {
    "main": [("while.1", "jit(round)/jit(main)/bafdp.fold/while"),
             ("fusion.3", "jit(round)/bafdp.local_step/dot_general"),
             ("copy.4", ""),
             ("scatter.5", "jit(round)/bafdp.dual/bafdp.scatter/scatter"),
             ("fusion.9", "jit(round)/bafdp.dual/mul")],
    "body": [("dyn.2", "jit(round)/bafdp.fold/dynamic_slice")]})
KEY_HLO = _hlo("jit_key", {"main": [("fusion.3", "jit(key)/fold_in")]})


def _ev(meta, start_us, end_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * 10**6} "
            f"duration_ps: {(end_us - start_us) * 10**6} }}")


def _line(lid, name, events):
    return (f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n    '
            + "\n    ".join(_ev(*e) for e in events) + " }")


def _metas(names):
    return "\n  ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}' for i, n in names.items())


# In microseconds.  Window [10, 110].  The round's module runs at [0, 11]
# (cut by the window), [12, 50] and [60, 105]; a key module at [52, 55]
# runs an instruction named like one of the round's.  Ops: fold while
# [12, 30] with a fold op nested in it [14, 20]; local step [30, 40];
# an unscoped copy [42, 48]; the key fusion [52, 54]; fold [60, 80];
# local step [85, 100]; scatter [100, 104]; a dual op [0, 11].
# Busy 1 + 18 + 10 + 6 + 2 + 20 + 15 + 4 = 76, idle 24.
DEVICE = "\n  ".join([
    _line(1, "XLA Modules", [(1, 0, 11), (1, 12, 50), (2, 52, 55),
                             (1, 60, 105)]),
    _line(2, "XLA Ops", [(17, 0, 11), (11, 12, 30), (12, 14, 20),
                         (13, 30, 40), (14, 42, 48), (13, 52, 54),
                         (11, 60, 80), (13, 85, 100), (15, 100, 104)]),
    _metas({1: "jit_round(7)", 2: "jit_key(3)",
            11: "%while.1 = (s32[]) while((s32[]) %t), body=%body",
            12: "%dyn.2 = f32[1,8] dynamic-slice(f32[4,8] %p)",
            13: "%fusion.3 = f32[4] fusion(f32[4] %a), kind=kLoop",
            14: "%copy.4 = f32[4] copy(f32[4] %a)",
            15: "%scatter.5 = f32[9] scatter(f32[9] %a)",
            17: "%fusion.9 = f32[4] fusion(f32[4] %b), kind=kLoop"})])
# The main thread: two rounds of program spans (names as the profiler may
# encode metadata into them), a host call nested in a dispatch, and the
# harness's own spans.
HOST = "\n  ".join([
    _line(1, "python", [
        (1, 10, 110), (2, 5, 56), (3, 5, 6), (4, 8, 44), (5, 44, 53),
        (6, 45, 52), (7, 53, 56), (8, 56, 58), (2, 58, 112), (4, 58, 84),
        (5, 84, 106)]),
    _metas({1: "bench.window", 2: "fed.round", 3: "fed.schedule_row",
            4: "fed.batch#rows=6,bytes=9#", 5: "fed.dispatch",
            6: "PjitFunction(round)", 7: "fed.hook", 8: "bench.on_round"})])
METADATA = "\n  ".join([
    f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" stats {{ '
    f'metadata_id: 1 bytes_value: "{_octal(h)}" }} }} }}'
    for i, n, h in ((1, "jit_round(7)", ROUND_HLO),
                    (2, "jit_key(3)", KEY_HLO))]
    + ['stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }'])
TRACE = (f'planes {{ id: 1 name: "/device:TPU:0"\n  {DEVICE}\n}}\n'
         f'planes {{ id: 2 name: "/host:CPU"\n  {HOST}\n}}\n'
         f'planes {{ id: 3 name: "/host:metadata"\n  {METADATA}\n}}\n')


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    from jax.profiler import ProfileData
    p = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return str(p)


@pytest.fixture(scope="module")
def reduced(path):
    return spans.reduce_spans(path)


@pytest.fixture(scope="module")
def summary(path):
    from jax.profiler import ProfileData
    return trace_lib.reduce_profile(ProfileData.from_file(path))


def test_op_scopes_come_from_the_metadata_plane(path):
    with open(path, "rb") as f:
        scopes = spans.op_scopes(f.read())
    assert scopes == {
        "jit_round(7)": {"while.1": "bafdp.fold", "dyn.2": "bafdp.fold",
                         "fusion.3": "bafdp.local_step",
                         "scatter.5": "bafdp.scatter",
                         "fusion.9": "bafdp.dual"},
        "jit_key(3)": {}}


def test_host_spans_are_clipped_to_the_window(reduced):
    got = {k: [round(v * 1e6, 6) for v in d]
           for k, d in reduced["host_spans"].items()}
    # fed.schedule_row [5, 6] lies before the window; metadata stripped
    assert got == {"fed.round": [46, 52], "fed.batch": [34, 26],
                   "fed.dispatch": [9, 22], "fed.hook": [3]}


def test_idle_goes_to_the_innermost_program_span(reduced, summary):
    idle = {k: v * 1e6 for k, v in reduced["idle_by_span"].items()}
    # [11,12] [40,42] [58,60] [80,84] batch; [48,52] (under the host call
    # in the dispatch) [84,85] [104,106] dispatch; [54,56] hook; [56,58]
    # between rounds; [106,110] the round's own time
    assert idle == pytest.approx({
        "fed.batch": 9, "fed.dispatch": 7, "fed.hook": 2,
        "unattributed": 2, "fed.round": 4})
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


def test_stage_time_by_innermost_op(reduced, summary):
    st = reduced["stage_time"]
    assert st["rounds"] == 2        # [0, 11] is cut by the window
    assert {k: v * 1e6 for k, v in st["window"].items()} == pytest.approx(
        {"bafdp.dual": 1, "bafdp.fold": 38, "bafdp.local_step": 25,
         "unscoped": 8, "bafdp.scatter": 4})
    assert sum(st["window"].values()) == pytest.approx(summary["busy_s"])
    assert {k: v * 1e6 for k, v in st["per_round"].items()} == \
        pytest.approx({"bafdp.fold": 19, "bafdp.local_step": 12.5,
                       "unscoped": 3, "bafdp.scatter": 2})
    # the copy in the round and the key module's op of the same name
    assert [[n.split(" = ")[0], v * 1e6] for n, v in st["unscoped_ops"]] \
        == [["%copy.4", pytest.approx(6)], ["%fusion.3", pytest.approx(2)]]


def test_device_summary_and_its_readers_are_unchanged(path, summary,
                                                      reduced):
    from jax.profiler import ProfileData
    again = trace_lib.reduce_profile(ProfileData.from_file(path))
    assert again == summary
    assert summary["window_s"] == pytest.approx(100e-6)
    assert summary["busy_s"] == pytest.approx(76e-6)
    rec = {"trace": summary, "model": {"closeness_len": 6, "period_len": 3,
           "n_meta": 9, "n_text": 4, "hidden": [128], "horizon": 1},
           "round_rows": [(5, 5)], "round_done_s": [50e-6],
           "trace_from_s": 0.0, "n_clients": 9, "batch": 4,
           "local_steps": 1, "s_max": 5,
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}}
    both = dict(rec, trace=dict(summary, **reduced))
    for name in ("device_idle_share", "round_mfu", "round_hbm_share",
                 "fold_roofline"):
        read = cells.reader(name)
        assert read(both) == read(rec)
    assert cells.reader("device_idle_share")(rec) == pytest.approx(24.0)


def test_new_readers(summary, reduced):
    rec = {"trace": dict(summary, **reduced), "program_spans": {
        "data.make_dataset": {"calls": 1, "seconds": 2.5, "counts": {}},
        "data.build_windows": {"calls": 1, "seconds": 70.0, "counts": {}},
        "fed.batch": {"calls": 9, "seconds": 1.0, "counts": {}}}}
    read = {n: cells.reader(n) for n in (
        "data_prep_s", "host_batch_ms", "dispatch_idle_share", "fold_ms",
        "local_step_ms")}
    assert read["data_prep_s"](rec) == pytest.approx(72.5)
    assert read["host_batch_ms"](rec) == pytest.approx(0.030)
    assert read["dispatch_idle_share"](rec) == pytest.approx(7.0)
    assert read["fold_ms"](rec) == pytest.approx(0.019)
    assert read["local_step_ms"](rec) == pytest.approx(0.0125)
    # a program without the spans and scopes, or an untraced run: nothing
    bare = {"trace": summary}
    for r in read.values():
        assert r(bare) is None
        assert r({"trace": None}) is None
