"""Fabric sweep: N-chip AER fabrics x traffic patterns.

Sweeps ring fabrics of N in {2, 4, 8, 16} chips (plus a 4x4 mesh at
N = 16) under every ``traffic.PATTERNS`` generator, reporting delivery,
aggregate + per-link throughput, end-to-end latency percentiles, switch
counts and energy.  The N = 2 ring IS the paper's measured configuration,
so its saturated rows must land on the Table II figures — the sweep's
built-in calibration anchor, enforced to 0.1 % against the paper's
28.6 MEvents/s (Fig. 8) on every run.

The slow lane (``--slow`` / ``run(slow=True)``) adds the DYNAP-scale
rows the O(1) ring engine affords: N in {32, 64} rings and an 8x8 mesh.

Rows follow the repo convention ``(name, us_per_call, derived)``;
``run_structured`` returns the same rows as dicts with the engine tag
and parsed metrics for ``BENCH_fabric.json``.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core import network as net
from repro.core import traffic as tr
from repro.core.adaptive import AdaptiveRouting
from repro.core.fabric import Fabric, MulticastPolicy, QueuePolicy
from repro.core.link import (PAPER_TIMING, SERIAL_LVDS_TIMING,
                             per_link_timing)
from repro.core.router import (AddressSpec, MulticastTable, mesh2d_topology,
                               ring_topology)
from repro.runtime.compile_cache import enable_compile_cache

EVENTS_PER_CHIP = 48
SWEEP_N = (2, 4, 8, 16)
SLOW_SWEEP_N = (32, 64)      # slow lane: DYNAP-scale rings
ANCHOR_MEV_S = 28.6          # paper Fig. 8 worst-case bidirectional rate
ANCHOR_TOL = 0.001           # enforced relative error of the N=2 anchor
DEFAULT_ENGINE = "ring"

# The sampled workloads are a pure function of (pattern, n, epc, key) and
# the generator code itself, so they are memoized on disk keyed on all of
# those (the generator contributes a source hash — editing traffic.py
# invalidates the cache): regenerating them costs ~8 s of eager
# jax.random compiles per run — noise that has nothing to do with the
# fabric engine being benchmarked.
_TRAFFIC_CACHE = os.path.join(os.path.dirname(__file__), ".traffic_cache")


@functools.lru_cache(maxsize=None)
def _traffic_version() -> str:
    src = inspect.getsource(tr).encode()
    return hashlib.sha1(src).hexdigest()[:10]


def _spec_cached(pattern: str, key, n_chips: int, epc: int):
    tag = "-".join(str(int(w)) for w in np.asarray(key).ravel())
    path = os.path.join(
        _TRAFFIC_CACHE,
        f"{pattern}_n{n_chips}_e{epc}_k{tag}_v{_traffic_version()}.npz")
    if os.path.exists(path):
        z = np.load(path)
        return tr.TrafficSpec(src=jax.numpy.asarray(z["src"]),
                              t=jax.numpy.asarray(z["t"]),
                              dest=jax.numpy.asarray(z["dest"]))
    spec = tr.PATTERNS[pattern](key, n_chips, epc)
    os.makedirs(_TRAFFIC_CACHE, exist_ok=True)
    np.savez(path, src=np.asarray(spec.src), t=np.asarray(spec.t),
             dest=np.asarray(spec.dest))
    return spec


def _run_one(topo, spec, engine=DEFAULT_ENGINE, **kw):
    t0 = time.perf_counter()
    res = net.simulate_fabric(topo, spec, engine=engine, **kw)
    jax.block_until_ready(res.log_del)
    us = (time.perf_counter() - t0) * 1e6
    return res, us


def _metrics(res) -> dict:
    st = net.latency_stats(res)
    per_link = np.asarray(net.per_link_throughput_mev_s(res))
    return {
        "delivered": st["delivered"],
        "injected": st["injected"],
        "offered": st["offered"],
        "fanout": st["fanout"],
        "traversals": st["traversals"],
        "thr_mev_s": float(net.fabric_throughput_mev_s(res)),
        "max_link_mev_s": float(per_link.max()),
        "p50_ns": st["p50_ns"],
        "p99_ns": st["p99_ns"],
        "switches": int(np.asarray(res.n_switches).sum()),
        "energy_nj": float(net.fabric_energy_pj(res, PAPER_TIMING)) * 1e-3,
        "drops": int(res.drops),
        "stall_steps": (int(np.asarray(res.telemetry.stall_steps).sum())
                        if res.telemetry is not None else 0),
        "credit_waits": (int(np.asarray(res.telemetry.credit_waits).sum())
                         if res.telemetry is not None else 0),
    }


def _derived(m: dict) -> str:
    return (f"delivered={m['delivered']}/{m['injected']} "
            f"thr={m['thr_mev_s']:.1f}MEv/s "
            f"maxlink={m['max_link_mev_s']:.1f}MEv/s "
            f"p50={m['p50_ns']:.0f}ns p99={m['p99_ns']:.0f}ns "
            f"sw={m['switches']} trav={m['traversals']} "
            f"E={m['energy_nj']:.1f}nJ")


def _cell(name, us, derived, engine, metrics=None, lane="fast",
          api="simulate_fabric", tags=(), kernel="step") -> dict:
    return {"name": name, "us_per_call": us, "derived": derived,
            "engine": engine, "kernel": kernel, "lane": lane, "api": api,
            "tags": list(tags), "metrics": metrics or {}}


def stamp_env(cells):
    """Stamp every cell with the execution environment: the XLA backend
    actually running the sweep plus the jax/jaxlib versions.  Timings
    are only comparable within one backend (a CPU interpret-mode cell
    vs a TPU compiled cell differ by orders of magnitude), so the
    regression gate (``compare.py``) refuses cross-backend ratios."""
    import jaxlib
    backend = jax.default_backend()
    for c in cells:
        c["backend"] = backend
        c["jax_version"] = jax.__version__
        c["jaxlib_version"] = jaxlib.__version__
    return cells


def sweep_rings(engine=DEFAULT_ENGINE, slow=False):
    rows = []
    key = jax.random.PRNGKey(0)
    lanes = [(n, "fast") for n in SWEEP_N]
    if slow:
        lanes += [(n, "slow") for n in SLOW_SWEEP_N]
    for n, lane in lanes:
        topo = ring_topology(n)
        for name in sorted(tr.PATTERNS):
            key, cell_key = jax.random.split(key)
            spec = _spec_cached(name, cell_key, n, EVENTS_PER_CHIP)
            # ping-pong saturates; grant after each event as in Fig. 8
            mb = 1 if name == "ping_pong" else 0
            res, us = _run_one(topo, spec, engine=engine, max_burst=mb)
            m = _metrics(res)
            rows.append(_cell(f"fabric_{topo.name}_{name}", us,
                              _derived(m), engine, m, lane))
    return rows


def sweep_mesh(engine=DEFAULT_ENGINE, slow=False):
    rows = []
    shapes = [(4, 4, "fast")] + ([(8, 8, "slow")] if slow else [])
    for r, c, lane in shapes:
        topo = mesh2d_topology(r, c)
        spec = _spec_cached("poisson", jax.random.PRNGKey(1), topo.n_chips,
                            EVENTS_PER_CHIP)
        res, us = _run_one(topo, spec, engine=engine)
        m = _metrics(res)
        rows.append(_cell(f"fabric_{topo.name}_poisson", us,
                          _derived(m), engine, m, lane))
    return rows


def sweep_anchor(engine=DEFAULT_ENGINE):
    """N=2 ping-pong must reproduce the paper's 28.6 MEvents/s (Fig. 8),
    within ``ANCHOR_TOL`` — asserted, not just reported.  Runs through
    the declarative ``Fabric`` API, so the anchor also gates the new
    front door (not just the ``simulate_fabric`` wrapper)."""
    fab = Fabric(ring_topology(2), queues=QueuePolicy(max_burst=1),
                 engine=engine)
    spec = tr.ping_pong(2, 1024)
    t0 = time.perf_counter()
    res = fab.run(spec)
    jax.block_until_ready(res.log_del)
    us = (time.perf_counter() - t0) * 1e6
    thr = float(net.fabric_throughput_mev_s(res))
    err = abs(thr - ANCHOR_MEV_S) / ANCHOR_MEV_S
    if err > ANCHOR_TOL:  # a hard gate (assert would vanish under -O)
        raise RuntimeError(
            f"fabric anchor drifted off the paper: measured {thr:.3f} "
            f"MEv/s vs {ANCHOR_MEV_S} (err {err:.2%} > {ANCHOR_TOL:.1%})")
    m = {"thr_mev_s": thr, "paper_mev_s": ANCHOR_MEV_S, "err": err}
    return [_cell("fabric_ring2_anchor_fig8", us,
                  f"measured={thr:.2f}MEv/s paper={ANCHOR_MEV_S} "
                  f"err={err:.2%}", engine, m, api="fabric")]


def sweep_heterogeneous(engine=DEFAULT_ENGINE):
    """Per-link timing heterogeneity row: an 8-ring whose 7-0 edge is
    the bit-serial LVDS class (331 ns/event) next to paper-timing links,
    driven through ``Fabric.sweep`` so one compile serves both the
    uniform baseline and the mixed cell (they share a shape bucket)."""
    topo = ring_topology(8)
    spec = _spec_cached("poisson", jax.random.PRNGKey(7), 8,
                        EVENTS_PER_CHIP)
    mixed = per_link_timing(
        [PAPER_TIMING, SERIAL_LVDS_TIMING],
        [1 if l == topo.n_links - 1 else 0 for l in range(topo.n_links)])
    rows = []
    for tag, timing in (("uniform", PAPER_TIMING), ("hetero", mixed)):
        fab = Fabric(topo, timing=timing, engine=engine)
        # warm=False: us_per_call stays "wall-clock, compile + run" like
        # every other BENCH cell (the rows still share one engine
        # compilation — timing is a dynamic operand)
        (cell,) = fab.sweep([spec], warm=False)
        m = _metrics(cell.result)
        rows.append(_cell(f"fabric_{topo.name}_poisson_{tag}",
                          cell.us_per_call, _derived(m), engine, m,
                          api="fabric", tags=("hetero",)))
    return rows


def sweep_multicast(engine=DEFAULT_ENGINE):
    """Multicast A/B rows: the same fanout-7 tagged workload on an
    8-ring, transported by ``source_expand`` (one unicast copy per
    member at the source) vs ``in_fabric`` (tag routed, replicated at
    the Steiner-tree branch points).  Both rows report the delivery
    metrics plus ``traversals`` and ``fanout``; both modes share ONE
    ring-engine shape bucket (replication dims are bucketed), so the
    A/B cost is one compile.  The in-fabric row must save traversals —
    the CI-gated assertion lives in ``fabric_smoke.py``."""
    topo = ring_topology(8)
    addr = AddressSpec()
    mc = MulticastTable(np.ones((1, 8), bool))   # tag 0 = every chip
    rng = np.random.default_rng(5)
    n = 8 * EVENTS_PER_CHIP
    src = rng.integers(0, 8, n).astype(np.int32)
    t = np.sort(rng.integers(0, 80_000, n)).astype(np.int32)
    spec = tr.TrafficSpec(
        src=jax.numpy.asarray(src),
        t=jax.numpy.asarray(t),
        dest=jax.numpy.asarray(addr.pack_multicast(np.zeros(n, np.int64))))
    rows = []
    for tag, mode in (("source", "source_expand"), ("infabric",
                                                    "in_fabric")):
        fab = Fabric(topo, addr=addr, engine=engine,
                     mcast=MulticastPolicy(mode, mc))
        (cell,) = fab.sweep([spec], warm=False)
        m = _metrics(cell.result)
        rows.append(_cell(f"fabric_{topo.name}_mcast_{tag}",
                          cell.us_per_call, _derived(m), engine, m,
                          api="fabric", tags=("mcast",)))
    return rows


# Adaptive hot-spot A/B configuration (shared with the CI smoke gate in
# fabric_smoke.py: the gate asserts the ring row's strict win, the sweep
# reports both rows' metrics).  Static rows run ``run_epochs`` with the
# SAME epoch partition, so the only difference is the routing tables.
ADAPTIVE_RING = dict(n_chips=16, key=3, epc=EVENTS_PER_CHIP, capacity=48,
                     policy="min_backlog", epochs=4, alpha=4.0, ema=0.5)
ADAPTIVE_MESH = dict(rows=4, cols=4, key=5, epc=EVENTS_PER_CHIP,
                     hot_chip=5, capacity=40,
                     policy="min_backlog", epochs=4, alpha=0.5, ema=0.3)


def _hotspot_ab_rows(topo, spec, cfg, engine):
    """One static / adaptive A/B pair on a hot-spot workload.

    Both rows run the identical engine shape bucket (routing tables are
    dynamic operands), so it is pre-warmed ONCE before either row is
    timed — otherwise the first row would absorb the compile time and
    skew the A/B comparison the rows exist for."""
    from repro.core.adaptive import partition_epochs, shared_max_steps
    routing = AdaptiveRouting(policy=cfg["policy"], epochs=cfg["epochs"],
                              alpha=cfg["alpha"], ema=cfg["ema"])
    queues = QueuePolicy(capacity=cfg["capacity"])
    # warm with the first epoch slice UNDER THE SHARED STEP BOUND both
    # rows run with (the slot engines key their bucket on it): ONE
    # bucket for every epoch of both rows, the slice prefill fits the
    # per-epoch capacity, and static/adaptive see the identical bound
    parts = partition_epochs(spec, cfg["epochs"])
    warm_fab = Fabric(topo, queues=queues, engine=engine)
    ms = shared_max_steps(warm_fab, parts,
                          detour_factor=1.0 + cfg["alpha"])
    warm_fab.compile(parts[0], max_steps=ms)
    rows = []
    for tag, fab, runner in (
            ("static", Fabric(topo, queues=queues, engine=engine),
             lambda f: f.run_epochs(spec, epochs=cfg["epochs"],
                                    max_steps=ms)),
            ("adaptive", Fabric(topo, routing=routing, queues=queues,
                                engine=engine),
             lambda f: f.run(spec, max_steps=ms))):
        t0 = time.perf_counter()
        res = runner(fab)           # merge syncs: results land in numpy
        us = (time.perf_counter() - t0) * 1e6
        m = _metrics(res)
        m.update(epochs=cfg["epochs"], policy=cfg["policy"],
                 alpha=cfg["alpha"], ema=cfg["ema"],
                 capacity=cfg["capacity"])
        rows.append(_cell(f"fabric_{topo.name}_hotspot_{tag}", us,
                          _derived(m), engine, m, api="fabric",
                          tags=("adaptive",)))
    return rows


def sweep_adaptive(engine=DEFAULT_ENGINE):
    """Congestion-control A/B rows: identical hot-spot workloads routed
    statically (BFS shortest path, epoch-partitioned for a fair drain /
    capacity comparison) vs adaptively (per-epoch telemetry re-weighting
    the tables — ``core/adaptive.py``).  The adaptive ring row must
    strictly reduce drops AND p99 latency; that assertion is the CI gate
    in ``fabric_smoke.run_adaptive_gate``."""
    r = ADAPTIVE_RING
    ring_spec = tr.hot_spot(jax.random.PRNGKey(r["key"]), r["n_chips"],
                            r["epc"])
    rows = _hotspot_ab_rows(ring_topology(r["n_chips"]), ring_spec, r,
                            engine)
    m = ADAPTIVE_MESH
    mesh_spec = tr.hot_spot(jax.random.PRNGKey(m["key"]),
                            m["rows"] * m["cols"], m["epc"],
                            hot_chip=m["hot_chip"])
    rows += _hotspot_ab_rows(mesh2d_topology(m["rows"], m["cols"]),
                             mesh_spec, m, engine)
    return rows


# Lossless flow-control A/B configurations (shared with the CI gate in
# fabric_smoke.run_lossless_gate and examples/lossless_hotspot.py).  The
# engines are deterministic, so these fixed (key, config) points
# reproduce bit-for-bit in CI:
#
# - LOSSLESS_RING: mild overload.  Drop mode exhausts its one-shot
#   per-endpoint budget (hundreds of drops) while credit mode delivers
#   everything AND strictly wins the delivered-events p99 — the wasted
#   transmissions of doomed events in drop mode starve live traffic
#   under the max_burst=0 grant rule.
# - LOSSLESS_RING_HOT: saturating flood at the smallest drop-legal
#   capacity.  Credit backpressure demonstrably engages (stall_steps
#   > 0) and still delivers 100%; drop mode loses most of the offered
#   load, so its loss-inclusive p99 (a dropped event never arrives =
#   unbounded latency) is infinite.
LOSSLESS_RING = dict(n_chips=16, key=2, epc=EVENTS_PER_CHIP,
                     mean_gap_ns=300.0, hot_frac=0.65, capacity=64)
LOSSLESS_RING_HOT = dict(n_chips=16, key=0, epc=EVENTS_PER_CHIP,
                         mean_gap_ns=150.0, hot_frac=0.85, capacity=48)


def _lossless_spec(cfg):
    return tr.hot_spot(jax.random.PRNGKey(cfg["key"]), cfg["n_chips"],
                       cfg["epc"], mean_gap_ns=cfg["mean_gap_ns"],
                       hot_frac=cfg["hot_frac"])


def sweep_lossless(engine=DEFAULT_ENGINE):
    """Flow-control A/B rows: the identical hot-spot workload transported
    under every ``QueuePolicy.flow`` mode.  All modes share ONE engine
    shape bucket (the flow mode, capacity and xon threshold are dynamic
    operands), so the bucket is pre-warmed once and no row absorbs the
    compile.  The strict-win assertions live in
    ``fabric_smoke.run_lossless_gate``; the sweep reports the metrics
    (including the stall/credit-wait telemetry unique to the lossless
    modes)."""
    topo = ring_topology(LOSSLESS_RING["n_chips"])
    spec = _lossless_spec(LOSSLESS_RING)
    cap = LOSSLESS_RING["capacity"]
    Fabric(topo, queues=QueuePolicy(capacity=cap),
           engine=engine).compile(spec)
    rows = []
    for flow in ("drop", "credit", "onoff"):
        fab = Fabric(topo, queues=QueuePolicy(capacity=cap, flow=flow),
                     engine=engine)
        (cell,) = fab.sweep([spec], warm=False)
        m = _metrics(cell.result)
        m.update(flow=flow, capacity=cap)
        rows.append(_cell(f"fabric_{topo.name}_hotspot_{flow}",
                          cell.us_per_call,
                          _derived(m) + f" stalls={m['stall_steps']}",
                          engine, m, api="fabric", tags=("lossless",)))
    # the saturating point: credit backpressure engages (stalls > 0)
    # and the fabric still delivers 100% of a flood drop mode mostly
    # loses
    hot = LOSSLESS_RING_HOT
    spec_hot = _lossless_spec(hot)
    fab = Fabric(topo, queues=QueuePolicy(capacity=hot["capacity"],
                                          flow="credit"), engine=engine)
    (cell,) = fab.sweep([spec_hot], warm=False)
    m = _metrics(cell.result)
    m.update(flow="credit", capacity=hot["capacity"])
    rows.append(_cell(f"fabric_{topo.name}_hotspot_credit_hot",
                      cell.us_per_call,
                      _derived(m) + f" stalls={m['stall_steps']}",
                      engine, m, api="fabric", tags=("lossless",)))
    return rows


BATCH_RING = dict(n_chips=16, key=7, epc=EVENTS_PER_CHIP,
                  pattern="hot_spot")
BATCH_SIZES = (1, 8, 32)


# Closed-loop co-simulation configuration (shared with the CI gate in
# fabric_smoke.run_cosim_gate and sized like examples/closed_loop_snn.py):
# a recurrent SNN on the benchmark ring-16, credit flow control.  The
# sweep rows transport the OPEN-LOOP spike stream of this network (the
# traffic-bridge A/B against the synthetic fabric_ring16_* rows on the
# identical topology); the smoke gate closes the loop and asserts
# lossless delivery plus the open-vs-closed divergence floor.
COSIM_RING = dict(n_chips=16, key=9, epc=EVENTS_PER_CHIP, capacity=96,
                  input_rate=0.06, ticks=24)

# The bridge rollout is a pure function of (pattern, n, epc, key) and the
# cosim-layer + LIF-kernel code, so specs memoize on disk like the
# synthetic patterns — regenerating one costs an open-loop LIF rollout
# (seconds of jit compiles) that has nothing to do with the fabric
# engine being benchmarked.
@functools.lru_cache(maxsize=None)
def _snn_version() -> str:
    import repro.cosim.engine as _ce
    import repro.cosim.placement as _cp
    import repro.cosim.traffic_bridge as _cb
    import repro.kernels.ops as _ko
    src = b"".join(inspect.getsource(m).encode()
                   for m in (_cb, _ce, _cp, _ko))
    return hashlib.sha1(src).hexdigest()[:10]


def _snn_spec_cached(pattern: str, key, n_chips: int, epc: int):
    from repro.cosim.traffic_bridge import SNN_PATTERNS
    tag = "-".join(str(int(w)) for w in np.asarray(key).ravel())
    path = os.path.join(
        _TRAFFIC_CACHE,
        f"{pattern}_n{n_chips}_e{epc}_k{tag}_v{_snn_version()}.npz")
    if os.path.exists(path):
        z = np.load(path)
        return tr.TrafficSpec(src=jax.numpy.asarray(z["src"]),
                              t=jax.numpy.asarray(z["t"]),
                              dest=jax.numpy.asarray(z["dest"]))
    spec = SNN_PATTERNS[pattern](key, n_chips, epc)
    os.makedirs(_TRAFFIC_CACHE, exist_ok=True)
    np.savez(path, src=np.asarray(spec.src), t=np.asarray(spec.t),
             dest=np.asarray(spec.dest))
    return spec


def sweep_cosim(engine=DEFAULT_ENGINE):
    """Spike-driven traffic rows: the two ``SNN_PATTERNS`` bridge
    workloads (feedforward chain vs bidirectional recurrent coupling,
    sampled from real LIF rollouts on the ``COSIM_RING`` ring) run
    through the fabric exactly like any synthetic pattern — same
    topology, same event budget as the ``fabric_ring16_*`` rows, so the
    A/B between modelled and network-generated load is a straight row
    comparison.  SNN load is tick-phased and projection-structured;
    these rows pin how the fabric carries it."""
    cfg = COSIM_RING
    topo = ring_topology(cfg["n_chips"])
    from repro.cosim.traffic_bridge import SNN_PATTERNS
    rows = []
    key = jax.random.PRNGKey(cfg["key"])
    for name in sorted(SNN_PATTERNS):
        key, cell_key = jax.random.split(key)
        spec = _snn_spec_cached(name, cell_key, cfg["n_chips"],
                                cfg["epc"])
        fab = Fabric(topo, engine=engine)
        (cell,) = fab.sweep([spec], warm=False)
        m = _metrics(cell.result)
        rows.append(_cell(f"fabric_{name}", cell.us_per_call,
                          _derived(m), engine, m, api="fabric",
                          tags=("cosim",)))
    return rows


def sweep_batched(engine=DEFAULT_ENGINE):
    """Batched Monte-Carlo rows: B independently-seeded hot-spot ring-16
    instances as ONE compiled dispatch (``Fabric.sweep_batch``).

    The amortization curve is the row family's whole point:
    ``us_per_call`` grows sub-linearly in B while ``us_per_instance``
    falls — the per-dispatch overhead (argument marshalling, one XLA
    launch) is paid once for the whole batch instead of once per seed.
    Each row's bucket is pre-warmed (``warm=True``), so the timing is
    the steady-state dispatch, matching the other tagged families; the
    >= 3x per-instance strict win over the sequential loop is asserted
    in ``fabric_smoke.run_batch_gate`` — the sweep reports the curve.
    """
    topo = ring_topology(BATCH_RING["n_chips"])
    fab = Fabric(topo, engine=engine)
    specs = tr.monte_carlo(BATCH_RING["pattern"],
                           jax.random.PRNGKey(BATCH_RING["key"]),
                           max(BATCH_SIZES), BATCH_RING["n_chips"],
                           BATCH_RING["epc"])
    rows = []
    for b in BATCH_SIZES:
        cell = fab.sweep_batch(specs[:b])
        batch = cell.result
        m = _metrics(batch.instance(0))
        thr = np.asarray(net.batch_throughput_mev_s(batch))
        m.update(batch=b, us_per_instance=cell.us_per_instance,
                 delivered_total=int(np.asarray(batch.delivered).sum()),
                 thr_mean_mev_s=float(thr.mean()),
                 thr_min_mev_s=float(thr.min()))
        rows.append(_cell(
            f"fabric_{topo.name}_batch{b}", cell.us_per_call,
            f"B={b} us/inst={cell.us_per_instance:.1f} "
            f"delivered={m['delivered_total']} "
            f"thr={m['thr_mean_mev_s']:.1f}MEv/s(mean) "
            f"min={m['thr_min_mev_s']:.1f}MEv/s",
            engine, m, api="fabric", tags=("batch",)))
    return rows


def sweep_verify(engine=DEFAULT_ENGINE, slow=False):
    """Pre-flight lane: ``Fabric.verify()`` over every sweep config.

    Runs the static verifier (``repro.analysis.verify``) against each
    (fabric, spec) pair the other families execute — rings x patterns,
    mesh, heterogeneous timing, multicast modes, every lossless flow
    mode, the batch instances and the adaptive epoch slices — and
    HARD-FAILS if any config is not statically admitted: the sweep must
    never benchmark a workload the verifier can prove deadlocks or
    overflows the clock.  The single cell reports total configs, the
    certificate histogram and the whole lane's wall-time (the cost of
    pre-flighting an entire benchmark campaign, all setup-time numpy —
    no engine compile, no device dispatch).
    """
    t0 = time.perf_counter()
    certs: dict[str, int] = {}
    failures: list[str] = []
    checked = 0

    def check(label, fab, spec):
        nonlocal checked
        rep = fab.verify(spec)
        checked += 1
        cert = rep.certificate or "none"
        certs[cert] = certs.get(cert, 0) + 1
        if not rep.ok:
            failures.append(f"{label}: {rep.summary()}")

    # anchor + rings x patterns (same key schedule as sweep_rings, so
    # the disk-cached specs are shared, not regenerated)
    check("ring2/anchor", Fabric(ring_topology(2),
                                 queues=QueuePolicy(max_burst=1),
                                 engine=engine), tr.ping_pong(2, 1024))
    key = jax.random.PRNGKey(0)
    ns = SWEEP_N + (SLOW_SWEEP_N if slow else ())
    for n in ns:
        topo = ring_topology(n)
        for name in sorted(tr.PATTERNS):
            key, cell_key = jax.random.split(key)
            spec = _spec_cached(name, cell_key, n, EVENTS_PER_CHIP)
            check(f"ring{n}/{name}", Fabric(topo, engine=engine), spec)
    for r, c in ((4, 4),) + (((8, 8),) if slow else ()):
        topo = mesh2d_topology(r, c)
        spec = _spec_cached("poisson", jax.random.PRNGKey(1), topo.n_chips,
                            EVENTS_PER_CHIP)
        check(f"{topo.name}/poisson", Fabric(topo, engine=engine), spec)

    # heterogeneous per-link timing
    topo = ring_topology(8)
    spec = _spec_cached("poisson", jax.random.PRNGKey(7), 8,
                        EVENTS_PER_CHIP)
    mixed = per_link_timing(
        [PAPER_TIMING, SERIAL_LVDS_TIMING],
        [1 if l == topo.n_links - 1 else 0 for l in range(topo.n_links)])
    for tag, timing in (("uniform", PAPER_TIMING), ("hetero", mixed)):
        check(f"ring8/{tag}", Fabric(topo, timing=timing, engine=engine),
              spec)

    # multicast transport modes (the sweep_multicast workload)
    addr = AddressSpec()
    mc = MulticastTable(np.ones((1, 8), bool))
    rng = np.random.default_rng(5)
    n_ev = 8 * EVENTS_PER_CHIP
    src = rng.integers(0, 8, n_ev).astype(np.int32)
    t = np.sort(rng.integers(0, 80_000, n_ev)).astype(np.int32)
    mspec = tr.TrafficSpec(
        src=jax.numpy.asarray(src), t=jax.numpy.asarray(t),
        dest=jax.numpy.asarray(addr.pack_multicast(np.zeros(n_ev,
                                                            np.int64))))
    for mode in ("source_expand", "in_fabric"):
        check(f"ring8/mcast_{mode}",
              Fabric(topo, addr=addr, engine=engine,
                     mcast=MulticastPolicy(mode, mc)), mspec)

    # lossless flow modes on both hot-spot points
    topo16 = ring_topology(LOSSLESS_RING["n_chips"])
    spec16 = _lossless_spec(LOSSLESS_RING)
    for flow in ("drop", "credit", "onoff"):
        check(f"ring16/lossless_{flow}",
              Fabric(topo16, queues=QueuePolicy(
                  capacity=LOSSLESS_RING["capacity"], flow=flow),
                  engine=engine), spec16)
    check("ring16/lossless_credit_hot",
          Fabric(topo16, queues=QueuePolicy(
              capacity=LOSSLESS_RING_HOT["capacity"], flow="credit"),
              engine=engine), _lossless_spec(LOSSLESS_RING_HOT))

    # batch instances (each seeded spec is its own verification)
    bspecs = tr.monte_carlo(BATCH_RING["pattern"],
                            jax.random.PRNGKey(BATCH_RING["key"]),
                            max(BATCH_SIZES), BATCH_RING["n_chips"],
                            BATCH_RING["epc"])
    bfab = Fabric(ring_topology(BATCH_RING["n_chips"]), engine=engine)
    for i, bspec in enumerate(bspecs):
        check(f"ring16/batch_inst{i}", bfab, bspec)

    # spike-driven bridge workloads (sweep_cosim), both on the plain
    # benchmark ring AND on the closed-loop smoke gate's credit fabric —
    # the co-simulation must never run a config the verifier refuses
    from repro.cosim.traffic_bridge import SNN_PATTERNS
    ctopo = ring_topology(COSIM_RING["n_chips"])
    ckey = jax.random.PRNGKey(COSIM_RING["key"])
    for name in sorted(SNN_PATTERNS):
        ckey, cell_key = jax.random.split(ckey)
        cspec = _snn_spec_cached(name, cell_key, COSIM_RING["n_chips"],
                                 COSIM_RING["epc"])
        check(f"ring16/{name}", Fabric(ctopo, engine=engine), cspec)
        check(f"ring16/{name}_credit",
              Fabric(ctopo, queues=QueuePolicy(
                  capacity=COSIM_RING["capacity"], flow="credit"),
                  engine=engine), cspec)

    # adaptive A/B epoch slices (run_epochs executes per-slice, so the
    # slices are what must be admitted)
    from repro.core.adaptive import partition_epochs
    for cfg, topo_a in ((ADAPTIVE_RING,
                         ring_topology(ADAPTIVE_RING["n_chips"])),
                        (ADAPTIVE_MESH,
                         mesh2d_topology(ADAPTIVE_MESH["rows"],
                                         ADAPTIVE_MESH["cols"]))):
        hot = cfg.get("hot_chip")
        aspec = tr.hot_spot(jax.random.PRNGKey(cfg["key"]),
                            topo_a.n_chips, cfg["epc"],
                            **({"hot_chip": hot} if hot is not None
                               else {}))
        afab = Fabric(topo_a, queues=QueuePolicy(
            capacity=cfg["capacity"]), engine=engine)
        for e, part in enumerate(partition_epochs(aspec, cfg["epochs"])):
            check(f"{topo_a.name}/hotspot_epoch{e}", afab, part)

    if failures:
        raise RuntimeError(
            f"fabric pre-flight verification failed for "
            f"{len(failures)}/{checked} config(s):\n" +
            "\n".join(failures))
    us = (time.perf_counter() - t0) * 1e6
    cert_str = " ".join(f"{k}={v}" for k, v in sorted(certs.items()))
    m = {"configs": checked, "us_per_config": us / max(checked, 1),
         "certificates": certs}
    return [_cell("fabric_verify_preflight", us,
                  f"configs={checked} all-ok {cert_str}", engine, m,
                  api="fabric.verify", tags=("verify",))]


#: Every cell tag a sweep family can emit — the single source of truth
#: the CLIs validate ``--tags`` against.
KNOWN_TAGS = frozenset({"hetero", "mcast", "adaptive", "lossless",
                        "batch", "cosim", "verify"})


def run_structured(engine=DEFAULT_ENGINE, slow=False, tags=None):
    """All sweep cells as dicts (the ``BENCH_fabric.json`` payload).

    ``tags`` — optional iterable of tag names (``KNOWN_TAGS``): run only
    the sweep families whose cells carry one of them, and keep only the
    matching cells.  ``None`` runs everything (untagged families
    included).  Unknown tags raise — a typo must not produce an empty
    benchmark run that looks successful.
    """
    enable_compile_cache()
    wanted = frozenset(tags) if tags else None
    families = (
        (sweep_anchor, (engine,), frozenset()),
        (sweep_rings, (engine, slow), frozenset()),
        (sweep_mesh, (engine, slow), frozenset()),
        (sweep_heterogeneous, (engine,), frozenset({"hetero"})),
        (sweep_multicast, (engine,), frozenset({"mcast"})),
        (sweep_adaptive, (engine,), frozenset({"adaptive"})),
        (sweep_lossless, (engine,), frozenset({"lossless"})),
        (sweep_batched, (engine,), frozenset({"batch"})),
        (sweep_cosim, (engine,), frozenset({"cosim"})),
        (sweep_verify, (engine, slow), frozenset({"verify"})),
    )
    if wanted is not None and wanted - KNOWN_TAGS:
        raise ValueError(f"unknown sweep tags "
                         f"{sorted(wanted - KNOWN_TAGS)}; known tags: "
                         f"{sorted(KNOWN_TAGS)}")
    cells = []
    for fn, args, family_tags in families:
        if wanted is not None and not (wanted & family_tags):
            continue  # genuine selection: unselected families never run
        cells.extend(fn(*args))
    if wanted is not None:
        cells = [c for c in cells if wanted & set(c["tags"])]
    return stamp_env(cells)


def run(engine=DEFAULT_ENGINE, slow=False, tags=None):
    """Legacy row tuples for the CSV convention of ``benchmarks/run.py``."""
    return [(c["name"], c["us_per_call"], c["derived"])
            for c in run_structured(engine, slow, tags)]


if __name__ == "__main__":
    import argparse
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--engine", default=DEFAULT_ENGINE,
                   choices=sorted(net.ENGINES))
    p.add_argument("--slow", action="store_true",
                   help="add the N in {32, 64} ring and 8x8 mesh rows")
    p.add_argument("--tags", default=None,
                   help="comma-separated cell-tag filter (e.g. "
                        "'adaptive,mcast'): run only those families")
    args = p.parse_args()
    sel = args.tags.split(",") if args.tags else None
    try:
        rows = run(engine=args.engine, slow=args.slow, tags=sel)
    except ValueError as e:   # unknown --tags: fail loudly, not a trace
        p.error(str(e))
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
