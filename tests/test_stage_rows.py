"""Host batch staging: ``windowing.stage_rows`` gathers only a round's
delivered rows from the draw ``client_batches`` makes for every client,
and ``train_bafdp``'s sparse path hands the round those rows
pre-gathered.

* the staged rows are the per-client batches' rows of ``ids`` (duplicates
  and the padding sentinel included), and the RandomState stream is the
  one ``client_batches`` leaves;
* ``client_batches`` is the ``take_along_axis`` formula it replaced, bit
  for bit;
* a sparse ``train_bafdp`` stages ``s_max`` rows a round under
  ``data.stage_rows`` and its round receives ``(s_max, b, ...)`` leaves.
"""
import numpy as np
import pytest

from repro import tracing
from repro.data.windowing import client_batches, stage_rows

C, N, D_X, H, B = 7, 13, 5, 3, 4


def fleet(seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.standard_normal((C, N, D_X)).astype(np.float32),
            "y": r.standard_normal((C, N, H)).astype(np.float32)}


@pytest.mark.parametrize("ids", [
    [3, 0, 3, 6, C, C],                 # a FedBuff duplicate, then padding
    [C, C, C],                          # an empty round
    list(range(C)),
], ids=["duplicates-and-padding", "all-padding", "every-client"])
def test_staged_rows_are_the_per_client_rows(ids):
    train = fleet()
    ids = np.asarray(ids, np.int32)
    rng_a, rng_b = np.random.RandomState(11), np.random.RandomState(11)
    x, y = stage_rows(rng_a, train, B, ids)
    xc, yc = client_batches(rng_b, train, B)
    gid = np.minimum(ids, C - 1)
    assert x.shape == (ids.size, B, D_X) and y.shape == (ids.size, B, H)
    np.testing.assert_array_equal(x, xc[gid])
    np.testing.assert_array_equal(y, yc[gid])
    # both drew the same (C, B) block, so the streams go on alike
    np.testing.assert_array_equal(rng_a.randint(0, 1 << 30, 8),
                                  rng_b.randint(0, 1 << 30, 8))


def test_client_batches_is_the_take_along_axis_formula():
    train = fleet(1)
    rng_a, rng_b = np.random.RandomState(5), np.random.RandomState(5)
    for _ in range(3):
        x, y = client_batches(rng_a, train, B)
        idx = rng_b.randint(0, N, size=(C, B))
        np.testing.assert_array_equal(
            x, np.take_along_axis(train["x"], idx[:, :, None], axis=1))
        np.testing.assert_array_equal(
            y, np.take_along_axis(train["y"], idx[:, :, None], axis=1))


def test_sparse_train_bafdp_stages_delivered_rows(monkeypatch):
    from benchmarks.common import BATCH, train_bafdp
    from repro.configs import FedConfig
    from repro.core import bafdp
    from repro.core.async_engine import DelayModel
    from repro.core.schedule import QuorumTrigger, build_schedule

    seen = []

    def round_spy(state, batch, key, *, batch_gathered=None, **kw):
        seen.append((batch_gathered, [l.shape for l in batch]))
        return state, {}

    monkeypatch.setattr(bafdp, "bafdp_round_sparse", round_spy)
    rounds, n = 3, 8
    sched = build_schedule(rounds, DelayModel(n_clients=n, seed=3),
                           QuorumTrigger(active_frac=0.5))
    with tracing.recording() as tot:
        train_bafdp("milano", 1, FedConfig(n_clients=n, active_frac=0.5),
                    rounds, schedule=sched, round_impl="sparse")
    s_max = sched.s_max
    assert s_max < n
    assert tot["data.stage_rows"]["calls"] == rounds
    assert tot["data.stage_rows"]["counts"] == {"rows": rounds * s_max}
    # traced once: the one shape of every round
    assert seen and all(g is True for g, _ in seen)
    assert all(shape[:2] == (s_max, BATCH) for _, shapes in seen
               for shape in shapes)
