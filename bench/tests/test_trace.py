"""The trace reduction on synthetic interval lists with known answers."""

import numpy as np
import pytest

from bench import trace as tr

OPS = [(0, 10, "a"), (5, 10, "b"), (30, 10, "a"), (50, 5, "all-gather.1"),
       (52, 10, "c"), (70, 10, "all-reduce.3")]


def _pairs(u):
    return [(float(s), float(e)) for s, e in zip(*u)]


def test_union_merges_and_clips():
    assert _pairs(tr.union(OPS, 0, 100)) == [(0, 15), (30, 40), (50, 62),
                                             (70, 80)]
    assert _pairs(tr.union(OPS, 8, 35)) == [(8, 15), (30, 35)]
    # out of order, nested and touching intervals
    ops = [(40, 5, "x"), (0, 20, "w"), (2, 3, "y"), (20, 4, "z")]
    assert _pairs(tr.union(ops, 0, 100)) == [(0, 24), (40, 45)]


def test_busy_and_idle_share():
    assert tr.busy_ns(OPS, 0, 100) == 15 + 10 + 12 + 10
    assert tr.idle_share(OPS, 0, 100) == pytest.approx(1 - 47 / 100)
    assert tr.busy_ns([], 0, 100) == 0


def test_op_totals():
    assert tr.op_totals(OPS) == {"a": 20, "b": 10, "all-gather.1": 5,
                                 "c": 10, "all-reduce.3": 10}
    assert tr.op_totals(OPS, 30, 60) == {"a": 10, "all-gather.1": 5,
                                         "c": 10}
    hlo = [(0, 100, "%while.84 = (s32[]) while(...)"),
           (1, 7, "%fusion.150 = s32[4096]{0} fusion(...)"),
           (9, 3, "%fusion.150 = s32[4096]{0} fusion(...)")]
    assert tr.op_totals(hlo) == {"fusion.150": 10}
    assert tr.busy_ns(hlo, 0, 200) == 100


def test_exposed_collective():
    # all-gather 50-55 lies under compute c (52-62) for 3 ns; all-reduce
    # 70-80 overlaps nothing
    assert tr.exposed_collective_ns(OPS, 0, 100) == 2 + 10
    assert tr.exposed_collective_ns(OPS, 0, 51) == 1


def test_idle_gaps_labelled_by_span():
    spans = [(14, 20, "plan"), (62, 5, "rollup")]
    gaps = tr.idle_gaps(OPS, spans, 0, 100)
    # gaps: 15-30 (under plan), 40-50 (no span), 62-70 (rollup), 80-100
    assert gaps == [["none", 20], ["plan", 15], ["none", 10],
                    ["rollup", 8]]
    assert len(tr.idle_gaps(OPS, spans, 0, 100, top=2)) == 2


def _reduced(monkeypatch, dropped=0):
    from bench import run as br
    # two whole calls: plan, engine, rollup each; the device runs the
    # engine's program (a while loop around two fusions) in each
    ops = tr.ops([(1010, 300, "%while.2 = w()"),
                  (1010, 100, "%fusion.1 = f()"),
                  (1150, 150, "%fusion.3 = f()"),
                  (1505, 400, "%while.2 = w()"),
                  (1505, 400, "%fusion.1 = f()")])
    spans = [(1000, 10, "plan"), (1010, 310, "engine"),
             (1320, 180, "rollup"), (1500, 5, "plan"),
             (1505, 405, "engine"), (1910, 90, "rollup")]
    monkeypatch.setattr(tr, "load", lambda d: ({"/device:TPU:0": ops},
                                               spans, dropped))
    return br, br.reduce_trace("unused")


def test_reduce_covers_whole_calls(monkeypatch):
    br, red = _reduced(monkeypatch)
    assert red["lo"] == 1000 and red["hi"] == 2000 and red["complete"]
    assert red["busy_ns"] == 700 and red["window_ns"] == 1000
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "rollup" and gaps[0][1] == pytest.approx(195e-9)
    assert [n for n, _ in red["breakdown"]["device_ops"]] == ["fusion.1",
                                                               "fusion.3"]


def _metric(br, name):
    from bench import plugins
    return plugins.load("metrics", name)


def test_layer_readers_on_whole_calls(monkeypatch):
    br, red = _reduced(monkeypatch)
    host = [("plan", 0.0, 0.25), ("engine", 0.25, 1.0),
            ("rollup", 1.0, 1.5), ("plan", 1.5, 1.75)]
    ctx = {"trace": red, "spans": host, "sims": 8, "batch": 4}
    # engine spans hold 300 + 400 ns of device time; 2 calls x 4 sims
    assert _metric(br, "engine_device_ms.fabric").read(ctx) == \
        pytest.approx(700e-6 / 8)
    assert _metric(br, "device_idle_share.fabric").read(ctx) == \
        pytest.approx(30.0)
    assert _metric(br, "plan_ms.fabric").read(ctx) == pytest.approx(
        1e3 * 0.5 / 8)
    assert _metric(br, "rollup_ms.fabric").read(ctx) == pytest.approx(
        1e3 * 0.5 / 8)
    # a reader that finds nothing returns nothing
    empty = {"trace": None, "spans": [], "sims": 0, "batch": 4}
    for name in ("engine_device_ms.fabric", "device_idle_share.fabric",
                 "plan_ms.fabric", "rollup_ms.fabric"):
        assert _metric(br, name).read(empty) is None
    assert np.isfinite(red["busy_ns"])


def test_a_trace_with_dropped_records_holds_no_whole_call(monkeypatch):
    """The profiler keeps the first records and drops the rest: the slice
    ends at the last operation kept, and the readers that need whole
    calls find nothing to read."""
    br, red = _reduced(monkeypatch, dropped=3)
    assert not red["complete"] and red["hi"] == 1905
    assert red["busy_ns"] == 700 and red["window_ns"] == 905
    ctx = {"trace": red, "spans": [], "sims": 8, "batch": 4}
    assert _metric(br, "engine_device_ms.fabric").read(ctx) is None
    assert _metric(br, "device_idle_share.fabric").read(ctx) is None
