"""The benchmark's reference against the program's engines, and its
control against the reference, at sizes a test run holds (CPU).

The reference (``bench/fabric_ref.py``) imports nothing of the program;
here it has to agree bit for bit with the program's ring and reference
engines, and the control (look-ahead off) has to fail the comparison a
run makes."""

import numpy as np
import pytest

from bench import control, fabric_ref, generator
from bench.drivers import fabric as drv

TIMING = {"t_sw_ns": 5, "t_sw2req_ns": 5, "t_req2req_ns": 31,
          "t_bidir_ns": 35, "e_event_pj": 11.0, "word_bits": 26}
CASES = [({"kind": "ring", "chips": 16}, 64),
         ({"kind": "ring", "chips": 5}, 40),
         ({"kind": "mesh", "rows": 4, "cols": 4}, 32),
         ({"kind": "mesh", "rows": 8, "cols": 8}, 6)]


def _cfg(topo):
    return {"topology": topo, "timing": TIMING, **fabric_ref.MODELLED}


def _program_fabric(topo, engine):
    from repro.core.fabric import Fabric
    from repro.core.link import LinkTiming
    from repro.core.router import mesh2d_topology, ring_topology
    t = (ring_topology(topo["chips"]) if topo["kind"] == "ring"
         else mesh2d_topology(topo["rows"], topo["cols"]))
    return Fabric(t, timing=LinkTiming(**TIMING), engine=engine)


@pytest.mark.parametrize("engine", ["ring", "reference"])
@pytest.mark.parametrize("topo,epc", CASES)
def test_reference_matches_program(topo, epc, engine):
    from repro.core.traffic import TrafficSpec
    fab = _program_fabric(topo, engine)
    mix = {"pattern": "poisson", "events_per_chip": epc,
           "mean_gap_ns": 200.0}
    streams = [generator.instance(mix, fab.topo.n_chips, 2**31 + 3, i)
               for i in range(2)]
    refs = fabric_ref.simulate(_cfg(topo), streams)
    for s, ref in zip(streams, refs):
        res = fab.run(TrafficSpec(*s))
        assert ref.complete and ref.delivered == len(s[0])
        stats = drv.reference_rollup(ref, 11.0)
        bad, gap = drv.compare(res, stats, ref, 11.0)
        assert bad == 0
        assert gap < 1e-6


@pytest.mark.parametrize("topo,build", [
    ({"kind": "mesh", "rows": 4, "cols": 6}, ("mesh2d_topology", 4, 6)),
    ({"kind": "mesh", "rows": 8, "cols": 8}, ("mesh2d_topology", 8, 8)),
    ({"kind": "ring", "chips": 16}, ("ring_topology", 16)),
    ({"kind": "ring", "chips": 2}, ("ring_topology", 2))])
def test_topologies_and_routes_match_the_program(topo, build):
    """The topology files give the links of the program's own
    constructors, and the reference's BFS the program's routing tables."""
    from repro.core import router
    n, links = fabric_ref.topology_links(topo)
    prog = getattr(router, build[0])(*build[1:])
    assert n == prog.n_chips and np.array_equal(links, prog.links)
    nl, osd, hops = fabric_ref.bfs_routes(n, links)
    rt = router.RoutingTable.build(prog)
    assert np.array_equal(nl, rt.next_link)
    assert np.array_equal(osd, rt.out_side)
    assert np.array_equal(hops, rt.hops)


@pytest.mark.parametrize("key,value", [
    ("routing", "adaptive"),
    ("queues", {"capacity": 64, "flow": "drop", "max_burst": 0,
                "initial_tx": 1}),
    ("queues", {"capacity": None, "flow": "drop", "max_burst": 4,
                "initial_tx": 1}),
    ("queues", {"capacity": None, "flow": "drop", "max_burst": 0,
                "initial_tx": 0})])
def test_reference_refuses_what_it_does_not_model(key, value):
    cfg = dict(_cfg({"kind": "ring", "chips": 5}), **{key: value})
    mix = {"pattern": "poisson", "events_per_chip": 4, "mean_gap_ns": 200.0}
    with pytest.raises(ValueError, match=key):
        fabric_ref.simulate(cfg, [generator.instance(mix, 5, 1, 0)])


@pytest.mark.parametrize("cell", ["ring16.poisson64.serial",
                                  "ring16.poisson64.batch32",
                                  "mesh8x8.poisson256"])
def test_control_run_comes_out_not_correct(cell):
    """The control in the program's place, through a whole run and the
    harness's ``correct`` decision (small traffic; the cells' own sizes
    are read on the chip by ``python3 -m bench.control``)."""
    from bench import run as br
    plan = br.cell_plan(cell)
    plan["mix"] = dict(plan["mix"], events_per_chip=16,
                       check=min(plan["mix"]["check"], 4))
    for seed in (11, 2**31 + 12):
        res = control.control_run(cell, seed, plan=plan, check_chips=False,
                                  log=lambda *a, **k: None)
        assert res["correct"] is False
        assert res["attempted"] == plan["mix"]["check"]
        c = res["checks"]
        assert c["mismatched_fields"]["value"] > c["mismatched_fields"][
            "limit"]
        assert c["rollup_rel_gap"]["value"] > c["rollup_rel_gap"]["limit"]
