"""Batched fabric execution: ``Fabric.run_batch`` / module ``run_batch``.

Contracts under test:

* B instances run as ONE batched computation and every instance is
  bit-exact with its solo ``fabric.run(spec)`` — on all three engines,
  including heterogeneous per-link timing, credit flow control and
  in-fabric multicast (the ring engine's early-exit while_loop is
  batch-aware: exit when ALL instances drain, per-instance carries
  frozen after their own drain);
* the batch compiles exactly once per (bucket, B) signature and a
  repeated same-shape batch adds ZERO cache entries
  (``batch_cache_size``);
* ``run_many`` dispatches same-bucket multi-spec calls to the batch
  path (``last_dispatch == "batch"``) and loops otherwise, bit-exact
  both ways;
* batches refuse mixed shape buckets, empty spec lists, fabric/spec
  count mismatches and AdaptiveRouting (sequential feedback);
* the route-cycle detector (``find_route_cycles``) reports exactly the
  (chip, dest) pairs whose walk never arrives; lossless flow modes
  refuse a broken table at construction only when the terminating
  routes' channel-dependency graph also carries a cycle (Dally–Seitz),
  otherwise the broken pairs are quarantined and traffic addressing
  them is refused at plan time (drop mode keeps the historical
  truncation behaviour);
* ``traffic.monte_carlo`` instance i is bit-identical to the solo
  generator under subkey i; ``telemetry.link_load_batch`` matches
  per-instance ``link_load``;
* the shard_map device path (``devices=``) is bit-exact with the
  unsharded batch and validates divisibility (multidevice lane).
"""

import jax
import numpy as np
import pytest

from repro.core import network as net
from repro.core import telemetry as tm
from repro.core import traffic as tr
from repro.core.adaptive import AdaptiveRouting
from repro.core.fabric import (EngineSpec, Fabric, MulticastPolicy,
                               QueuePolicy, StaticShortestPath,
                               batch_cache_size)
from repro.core.fabric import run_batch as run_batch_fn
from repro.core.link import PAPER_TIMING, SERIAL_LVDS_TIMING, per_link_timing
from repro.core.router import (AddressSpec, MulticastTable, RoutingTable,
                               Topology, find_route_cycles, ring_topology)
from tests._subproc import run_with_devices

assert_bit_exact = net.assert_results_equal


def _spec(key=3, n=8, epc=24):
    return tr.poisson(jax.random.PRNGKey(key), n, epc)


def _hot(key, n=8, epc=24):
    return tr.hot_spot(jax.random.PRNGKey(key), n, epc)


def _mixed_timing(n_links, slow=(0,)):
    cls = [0] * n_links
    for l in slow:
        cls[l] = 1
    return per_link_timing([PAPER_TIMING, SERIAL_LVDS_TIMING], cls)


def _mcast_spec(addr, n=24, seed=0):
    """Tagged stream from chip 0 plus unicast cross-traffic (the
    in-fabric replication exercise from the adaptive tests)."""
    rng = np.random.default_rng(seed)
    src = np.concatenate([np.zeros(n, np.int64), np.ones(n // 2, np.int64)])
    t = np.concatenate([np.sort(rng.integers(0, n * 40, n)),
                        10 + np.arange(n // 2) * 40])
    dest = np.concatenate([addr.pack_multicast(np.zeros(n, np.int64)),
                           addr.pack(np.full(n // 2, 3, np.int64))])
    order = np.argsort(t, kind="stable")
    ji = jax.numpy.int32
    return tr.TrafficSpec(src=jax.numpy.asarray(src[order], ji),
                          t=jax.numpy.asarray(t[order], ji),
                          dest=jax.numpy.asarray(dest[order], ji))


def _torus(n=4):
    """An n x n torus: 2·n·n links, every chip of degree 4, so with
    n = 4 the links and the in-edge ranks fill the ring engine's
    bucket (32 links, 4 streams per endpoint) with no padding."""
    links = [(r * n + c, r * n + (c + 1) % n)
             for r in range(n) for c in range(n)]
    links += [(r * n + c, ((r + 1) % n) * n + c)
              for r in range(n) for c in range(n)]
    return Topology(n * n, np.asarray(links, np.int32), name=f"torus{n}")


def _pairs(pairs, gap=40):
    """One event per ``(src, dest)`` pair, ``gap`` ns apart."""
    ji = jax.numpy.int32
    src, dest = np.asarray(pairs, np.int64).T
    return tr.TrafficSpec(src=jax.numpy.asarray(src, ji),
                          t=jax.numpy.asarray(np.arange(len(src)) * gap, ji),
                          dest=jax.numpy.asarray(dest, ji))


def _drops_mid_batch():
    """Drop mode with a small capacity on the middle instances only:
    their appends are dropped between two lossless instances."""
    topo = ring_topology(8)
    lossy = QueuePolicy(capacity=36, flow="drop")
    fabs = [Fabric(topo)] + [Fabric(topo, queues=lossy)] * 3 + [Fabric(topo)]
    specs = [_hot(k, 8, 32) for k in range(5)]
    return fabs, specs, [False, True, True, True, False]


def _top_stream_last_instance():
    """The last instance appends to the top queue (link 31, side 1) on
    its highest reachable in-edge stream (rank 2: 7 -> 3 -> 15), the
    stream entries right below the end of the batch's buffer."""
    topo = _torus()
    fabs = [Fabric(topo)] * 3
    top = [(7, 15), (2, 15), (0, 15)] * 12 + [(5, 10), (12, 1)] * 4
    specs = [_spec(1, 16, 8), _spec(2, 16, 8), _pairs(top)]
    return fabs, specs, [False, False, False]


class TestRunBatchBitExact:
    """The headline contract: instance i of a batch == solo run i."""

    @pytest.mark.parametrize("case", [_drops_mid_batch,
                                      _top_stream_last_instance],
                             ids=lambda f: f.__name__.lstrip("_"))
    def test_stream_buffer_offset_edges(self, case):
        """The batched ring engine keeps every instance's streams in
        one flat buffer, instance b at offset b·N: appends dropped in
        the middle of the batch, and the last instance's top stream,
        stay inside their own instance (bit-exact with solo runs)."""
        fabs, specs, drops = case()
        batch = run_batch_fn(fabs, specs)
        for i, (f, s) in enumerate(zip(fabs, specs)):
            r = batch.instance(i)
            assert (int(r.drops) > 0) == drops[i], (i, int(r.drops))
            assert_bit_exact(f.run(s), r, f"{case.__name__}/{i}")
        if case is _top_stream_last_instance:
            f, s = fabs[-1], specs[-1]
            L, D = f.topo.n_links, f._D
            quota = net._stream_quota(f.routing_table, f.topo.links,
                                      f._in_rank, np.asarray(s.src),
                                      np.asarray(s.dest), L, D)
            assert quota[2 * L - 1, D - 2] > 0
            bucket = f.compile(s).bucket
            assert (bucket[1], bucket[5]) == (L, D)   # no padding

    @pytest.mark.parametrize("engine", sorted(net.ENGINES))
    def test_batch_matches_solo_every_engine(self, engine):
        topo = ring_topology(8)
        specs = [_spec(k, 8, 24) for k in range(5)]
        fab = Fabric(topo, engine=EngineSpec(name=engine))
        batch = fab.run_batch(specs)
        assert batch.n_instances == 5
        solo = Fabric(topo, engine=EngineSpec(name=engine))
        for i, s in enumerate(specs):
            assert_bit_exact(solo.run(s), batch.instance(i),
                             f"batch/{engine}/{i}")

    @pytest.mark.parametrize("engine", sorted(net.ENGINES))
    def test_hetero_timing_batch(self, engine):
        """Per-link heterogeneous timing batches bit-exactly (timing is
        a dynamic operand, stacked per instance)."""
        topo = ring_topology(6)
        timing = _mixed_timing(topo.n_links, slow=(0, 3))
        specs = [_spec(k, 6, 20) for k in (2, 5, 9)]
        fab = Fabric(topo, timing=timing, engine=EngineSpec(name=engine))
        batch = fab.run_batch(specs)
        solo = Fabric(topo, timing=timing, engine=EngineSpec(name=engine))
        for i, s in enumerate(specs):
            assert_bit_exact(solo.run(s), batch.instance(i),
                             f"hetero/{engine}/{i}")

    def test_credit_flow_batch(self):
        """Lossless credit flow under a batch: zero drops per instance,
        bit-exact with solo (the stall/credit FSM is part of the
        vmapped carry)."""
        topo = ring_topology(8)
        q = QueuePolicy(capacity=6, flow="credit")
        specs = [_hot(k, 8, 24) for k in range(4)]
        fab = Fabric(topo, queues=q)
        batch = fab.run_batch(specs)
        solo = Fabric(topo, queues=q)
        for i, s in enumerate(specs):
            r = batch.instance(i)
            assert int(r.drops) == 0
            assert_bit_exact(solo.run(s), r, f"credit/{i}")

    def test_in_fabric_multicast_batch(self):
        """Tagged events replicate at branch points inside a batch,
        bit-exact with solo (replication tables are per-instance
        operands)."""
        addr = AddressSpec()
        members = np.zeros((1, 8), bool)
        members[0, 2:7] = True
        kw = dict(addr=addr,
                  mcast=MulticastPolicy("in_fabric", MulticastTable(members)))
        topo = ring_topology(8)
        specs = [_mcast_spec(addr, seed=s) for s in (0, 1)]
        fab = Fabric(topo, **kw)
        batch = fab.run_batch(specs)
        solo = Fabric(topo, **kw)
        for i, s in enumerate(specs):
            assert_bit_exact(solo.run(s), batch.instance(i), f"mcast/{i}")

    def test_cross_fabric_heterogeneous_batch(self):
        """Module-level run_batch accepts B distinct fabrics (same
        shape bucket, different timing contracts) in one dispatch."""
        topo = ring_topology(6)
        fabs = [Fabric(topo),
                Fabric(topo, timing=_mixed_timing(topo.n_links))]
        specs = [_spec(7, 6, 20), _spec(7, 6, 20)]
        batch = run_batch_fn(fabs, specs)
        for i, (f, s) in enumerate(zip(fabs, specs)):
            assert_bit_exact(Fabric(topo, timing=f.timing).run(s),
                             batch.instance(i), f"cross/{i}")

    def test_conservation_and_rollups(self):
        """Per-instance conservation + the batched roll-up helpers."""
        topo = ring_topology(8)
        fab = Fabric(topo, queues=QueuePolicy(capacity=48))
        specs = [_hot(k, 8, 32) for k in range(6)]
        batch = fab.run_batch(specs)
        assert any(int(batch.instance(i).drops) > 0
                   for i in range(batch.n_instances))
        for i in range(batch.n_instances):
            r = batch.instance(i)
            assert int(r.delivered) + int(r.drops) == r.injected
        thr = np.asarray(net.batch_throughput_mev_s(batch))
        assert thr.shape == (6,) and (thr > 0).all()
        stats = net.batch_latency_stats(batch)
        assert len(stats) == 6
        solo = net.latency_stats(Fabric(
            topo, queues=QueuePolicy(capacity=48)).run(specs[0]))
        assert stats[0] == solo


class TestBatchCompilation:
    def test_one_compile_flat_cache(self):
        """The perf contract: a batch traces once per (bucket, B)
        signature; repeated same-shape batches add ZERO entries."""
        fab = Fabric(ring_topology(8))
        specs = [_spec(k, 8, 24) for k in range(4)]
        cell = fab.sweep_batch(specs)
        n0 = batch_cache_size(cell.bucket)
        assert n0 >= 1  # >1 only if other same-bucket batch sizes ran
        assert cell.us_per_instance * len(specs) == \
            pytest.approx(cell.us_per_call)
        fab.run_batch([_spec(k + 10, 8, 24) for k in range(4)])
        assert batch_cache_size(cell.bucket) == n0

    def test_mixed_bucket_refused(self):
        """Slot engines key max_steps/E into the bucket: a mixed batch
        is refused with a pointer at run_many."""
        fab = Fabric(ring_topology(4), engine=EngineSpec(name="reference"))
        with pytest.raises(ValueError, match="ONE shape bucket"):
            fab.run_batch([_spec(1, 4, 8), _spec(1, 4, 12)])

    def test_empty_refused(self):
        with pytest.raises(ValueError, match="at least one"):
            Fabric(ring_topology(4)).run_batch([])

    def test_fabric_spec_count_mismatch(self):
        topo = ring_topology(4)
        with pytest.raises(ValueError, match="1:1"):
            run_batch_fn([Fabric(topo)], [_spec(1, 4, 8), _spec(2, 4, 8)])

    def test_adaptive_refused(self):
        """Epoch feedback is sequential; the batch path refuses it
        loudly instead of fusing wrong."""
        fab = Fabric(ring_topology(8), routing=AdaptiveRouting(epochs=2))
        with pytest.raises(NotImplementedError, match="AdaptiveRouting"):
            fab.run_batch([_hot(0), _hot(1)])


class TestRunManyDispatch:
    def test_same_bucket_dispatches_batch(self):
        topo = ring_topology(4)
        specs = [_spec(k, 4, 24) for k in range(4)]
        fab = Fabric(topo)
        results = fab.run_many(specs)
        assert fab.last_dispatch == "batch"
        for s, r in zip(specs, results):
            assert_bit_exact(net.simulate_fabric(topo, s), r, "many-batch")

    def test_single_spec_loops(self):
        fab = Fabric(ring_topology(4))
        fab.run_many([_spec(1, 4, 16)])
        assert fab.last_dispatch == "loop"

    def test_mixed_buckets_loop(self):
        topo = ring_topology(4)
        fab = Fabric(topo, engine=EngineSpec(name="reference"))
        specs = [_spec(1, 4, 8), _spec(1, 4, 12)]
        results = fab.run_many(specs)
        assert fab.last_dispatch == "loop"
        for s, r in zip(specs, results):
            assert_bit_exact(net.simulate_fabric(topo, s,
                                                 engine="reference"),
                             r, "many-loop")

    def test_adaptive_loops(self):
        fab = Fabric(ring_topology(8), routing=AdaptiveRouting(epochs=2))
        results = fab.run_many([_hot(0), _hot(1)])
        assert fab.last_dispatch == "loop"
        assert len(results) == 2


def jnp_i32(x):
    return np.asarray(x, np.int32)


def _cyclic_override(topo_, rt):
    """Bend dest-1 routing on ring(4) into the 2-cycle 0 <-> 3."""
    nl = rt.next_link.copy()
    os = rt.out_side.copy()
    nl[0, 1], os[0, 1] = 3, 1   # chip 0 -> link 3 -> chip 3
    nl[3, 1], os[3, 1] = 3, 0   # chip 3 -> link 3 -> chip 0
    return RoutingTable(next_link=nl, out_side=os, hops=rt.hops)


def _clockwise(topo_, rt):
    """All-clockwise table on ring(n): chip c always exits on link c."""
    n = rt.next_link.shape[0]
    nl = rt.next_link.copy()
    os = rt.out_side.copy()
    hops = rt.hops.copy()
    for c in range(n):
        for d in range(n):
            if c != d:
                nl[c, d], os[c, d], hops[c, d] = c, 0, (d - c) % n
    return RoutingTable(next_link=nl, out_side=os, hops=hops)


def _cw_broken(topo_, rt):
    """All-clockwise plus the dest-1 bend: the surviving routes still
    carry the full clockwise channel cycle, so lossless flow must
    refuse at construction."""
    cw = _clockwise(topo_, rt)
    nl = cw.next_link.copy()
    os = cw.out_side.copy()
    nl[0, 1], os[0, 1] = 3, 1
    nl[3, 1], os[3, 1] = 3, 0
    return RoutingTable(next_link=nl, out_side=os, hops=cw.hops)


class TestRouteCycleDetector:
    def test_bfs_table_is_acyclic(self):
        topo = ring_topology(8)
        assert len(find_route_cycles(topo, RoutingTable.build(topo))) == 0

    def test_reports_exact_pairs(self):
        topo = ring_topology(4)
        rt = _cyclic_override(topo, RoutingTable.build(topo))
        bad = find_route_cycles(topo, rt)
        assert {tuple(p) for p in bad.tolist()} == {(0, 1), (3, 1)}

    @pytest.mark.parametrize("flow,cap", [("credit", 4), ("onoff", 4)])
    def test_lossless_quarantines_acyclic_cdg_table(self, flow, cap):
        """On ring(4) the 0 <-> 3 bend leaves the terminating routes'
        channel-dependency graph acyclic, so the table is ADMITTED
        (Dally-Seitz: the stall chain cannot loop) with the broken
        pairs quarantined — clean traffic runs lossless, traffic
        addressing a quarantined pair is refused at plan time."""
        fab = Fabric(ring_topology(4),
                     routing=StaticShortestPath(
                         table_override=_cyclic_override),
                     queues=QueuePolicy(capacity=cap, flow=flow))
        clean = tr.TrafficSpec(
            src=jnp_i32([0, 1, 2, 3]), t=jnp_i32([0, 0, 0, 0]),
            dest=jnp_i32([2, 3, 0, 2]))  # avoids (0,1) and (3,1)
        res = fab.run(clean)
        assert int(res.delivered) == 4
        assert int(res.drops) == 0
        quarantined = tr.TrafficSpec(
            src=jnp_i32([0]), t=jnp_i32([0]), dest=jnp_i32([1]))
        with pytest.raises(ValueError, match=r"quarantined.*never "
                                             r"reaches"):
            fab.run(quarantined)

    @pytest.mark.parametrize("flow,cap", [("credit", 4), ("onoff", 4)])
    def test_lossless_refuses_cyclic_cdg_table(self, flow, cap):
        """When the surviving routes' channel-dependency graph is
        itself cyclic the table is refused at construction, naming a
        broken pair and the channel cycle."""
        with pytest.raises(ValueError, match=r"never reaches.*0->1"):
            Fabric(ring_topology(4),
                   routing=StaticShortestPath(
                       table_override=_cw_broken),
                   queues=QueuePolicy(capacity=cap, flow=flow))

    def test_drop_mode_keeps_cyclic_table(self):
        """Drop mode keeps the historical truncate/drop behaviour — the
        eager check only guards the lossless modes."""
        fab = Fabric(ring_topology(4),
                     routing=StaticShortestPath(
                         table_override=_cyclic_override))
        assert fab.queues.flow == "drop"

    def test_acyclic_override_passes_lossless(self):
        """A legal detour override still constructs under credit flow."""
        def long_way(topo_, rt):
            nl = rt.next_link.copy()
            os = rt.out_side.copy()
            hops = rt.hops.copy()
            nl[0, 1], os[0, 1], hops[0, 1] = 3, 1, 3
            nl[3, 1], os[3, 1], hops[3, 1] = 2, 1, 2
            return RoutingTable(next_link=nl, out_side=os, hops=hops)
        Fabric(ring_topology(4),
               routing=StaticShortestPath(table_override=long_way),
               queues=QueuePolicy(capacity=8, flow="credit"))


class TestDevices:
    def test_devices_one_is_unsharded(self):
        topo = ring_topology(8)
        specs = [_spec(k, 8, 24) for k in range(3)]
        a = Fabric(topo).run_batch(specs)
        b = Fabric(topo).run_batch(specs, devices=1)
        for i in range(3):
            assert_bit_exact(a.instance(i), b.instance(i), f"dev1/{i}")

    def test_too_many_devices_refused(self):
        fab = Fabric(ring_topology(4))
        with pytest.raises(ValueError, match="local"):
            fab.run_batch([_spec(1, 4, 16)] * 2,
                          devices=jax.local_device_count() + 1)


SHARD_CODE = """
import jax
import numpy as np
from repro.core import network as net
from repro.core import traffic as tr
from repro.core.fabric import Fabric
from repro.core.router import ring_topology

assert jax.local_device_count() == 4, jax.local_device_count()
topo = ring_topology(8)
specs = [tr.poisson(jax.random.PRNGKey(k), 8, 24) for k in range(8)]
sharded = Fabric(topo).run_batch(specs, devices="all")
plain = Fabric(topo).run_batch(specs)
for i in range(8):
    net.assert_results_equal(plain.instance(i), sharded.instance(i),
                             f"shard/{i}")
try:
    Fabric(topo).run_batch(specs[:6], devices=4)
except ValueError as e:
    assert "divisible" in str(e), e
else:
    raise AssertionError("expected divisibility ValueError")
print("SHARD_OK")
"""


@pytest.mark.multidevice
def test_shard_map_batch_bit_exact():
    """devices='all' shards the batch axis over 4 forced host devices
    and stays bit-exact with the unsharded batch; non-divisible batch
    sizes are refused."""
    out = run_with_devices(SHARD_CODE, 4)
    assert "SHARD_OK" in out


class TestMonteCarloTraffic:
    def test_instances_match_solo_subkeys(self):
        key = jax.random.PRNGKey(11)
        specs = tr.monte_carlo("hot_spot", key, 4, 8, 16)
        keys = jax.random.split(key, 4)
        for i, s in enumerate(specs):
            solo = tr.PATTERNS["hot_spot"](keys[i], 8, 16)
            for f in tr.TrafficSpec._fields:
                assert np.array_equal(np.asarray(getattr(s, f)),
                                      np.asarray(getattr(solo, f))), (i, f)

    def test_validation(self):
        key = jax.random.PRNGKey(0)
        with pytest.raises(ValueError, match="unknown pattern"):
            tr.monte_carlo("nope", key, 2, 4, 8)
        with pytest.raises(ValueError, match="batch"):
            tr.monte_carlo("poisson", key, 0, 4, 8)


class TestTelemetryBatch:
    def test_link_load_batch_matches_solo(self):
        topo = ring_topology(8)
        specs = [_hot(k, 8, 24) for k in range(3)]
        fab = Fabric(topo, queues=QueuePolicy(capacity=48))
        loads = tm.link_load_batch(fab.run_batch(specs))
        assert len(loads) == 3
        for i, s in enumerate(specs):
            solo = tm.link_load(Fabric(
                topo, queues=QueuePolicy(capacity=48)).run(s))
            for f in tm.LinkLoad._fields:
                assert np.array_equal(np.asarray(getattr(loads[i], f)),
                                      np.asarray(getattr(solo, f))), (i, f)
