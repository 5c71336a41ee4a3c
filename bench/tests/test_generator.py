"""The numpy traffic generator: semantics, determinism, and that every
seed's streams plan into the one shape bucket its cell warms (a split
bucket would compile inside the window, and ``run_batch`` refuses one)."""

import numpy as np
import pytest

from bench import generator
from bench import run as br

CELLS = ["mesh8x8.poisson256", "ring16.poisson64.batch32",
         "ring16.poisson64.serial"]
#: large seeds, as the driver draws them, and a few small ones
SEEDS = [0, 1, 7, 2**31 - 1, 2**31, 2**31 + 12345] + [
    3_000_000_000 + 7919 * k for k in range(8)]


def _mix(cell):
    p = br.cell_plan(cell)
    return p["config"], p["mix"]


def test_poisson_semantics():
    mix = {"pattern": "poisson", "events_per_chip": 500,
           "mean_gap_ns": 200.0}
    src, t, dest = generator.instance(mix, 16, 12345, 3)
    assert src.dtype == t.dtype == dest.dtype == np.int32
    assert len(src) == 16 * 500
    assert np.all(src == np.repeat(np.arange(16), 500))
    assert np.all(dest != src) and dest.min() >= 0 and dest.max() < 16
    tt = t.reshape(16, 500)
    assert np.all(np.diff(tt, axis=1) >= 0)
    gaps = np.diff(tt, axis=1)
    # truncated exponential gaps of mean 200 ns: mean ~199.5
    assert 190 < gaps.mean() < 210
    # every destination other than the source is drawn
    assert set(np.unique(dest[src == 0])) == set(range(1, 16))


def test_patterns_are_found_by_file_name():
    mix = {"pattern": "no_such_pattern", "events_per_chip": 4,
           "mean_gap_ns": 200.0}
    with pytest.raises(FileNotFoundError):
        generator.instance(mix, 16, 1, 0)
    from bench import plugins
    assert plugins.load("traffic", "poisson") is plugins.load("traffic",
                                                             "poisson")


def test_same_seed_same_streams_and_instances_differ():
    mix = {"pattern": "poisson", "events_per_chip": 8, "mean_gap_ns": 200.0}
    a = generator.instance(mix, 16, 2**31 + 5, 0)
    b = generator.instance(mix, 16, 2**31 + 5, 0)
    c = generator.instance(mix, 16, 2**31 + 5, 1)
    assert generator.digest([a]) == generator.digest([b])
    assert generator.digest([a]) != generator.digest([c])


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_plans_into_one_bucket(cell):
    from repro.core.traffic import TrafficSpec
    from bench.drivers.fabric import Driver
    cfg, mix = _mix(cell)
    drv = Driver(cfg, mix, 0, 1)
    fab = drv._fabric()
    n = fab.topo.n_chips
    per_seed = 6 if mix["events_per_chip"] <= 64 else 2
    buckets = set()
    for seed in SEEDS:
        for i in range(per_seed):
            s = TrafficSpec(*generator.instance(mix, n, seed, i))
            buckets.add(fab._plan(s, None).bucket)
    assert len(buckets) == 1, buckets
