"""Driver of the fabric cells: a designer's simulations, back to back.

The configuration (``bench/configs/<config>.json``) gives the fabric:
topology, link timing, routing, queues and engine.  The traffic mix
gives the events and how many instances one call carries: one call of
``instances_per_call == 1`` is ``Fabric.run`` of one stream, a larger
count is one ``Fabric.run_batch`` of that many streams.  Each call ends
with the designer's roll-up of every instance (latency percentiles,
throughput, per-link load, switches, energy), which reads the results
back to the host, so a call's time runs from its streams to its
statistics.

``correct``: a sample of the window's simulations, drawn from the seed,
is simulated again by the benchmark's own reference
(``bench/fabric_ref.py``).  Every field of the result and of its
telemetry has to be identical, and every rolled-up statistic within a
relative 1e-5 of the one the benchmark works out from the reference's
result (the program rolls up some statistics in float32).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import fabric_ref, generator

#: result fields compared bit for bit (log arrays up to ``delivered``)
RESULT_FIELDS = ("delivered", "log_inj", "log_del", "log_dest", "sent",
                 "n_switches", "t_link", "t_end", "drops")
TELEMETRY_FIELDS = ("busy_ns", "busy_steps", "q_drops", "stall_steps",
                    "credit_waits")
#: limits of the numbers compared (readings in PERF.md)
LIMIT_MISMATCHED = 0
LIMIT_ROLLUP_GAP = 1e-5


def reference_rollup(r: fabric_ref.Result, e_event_pj: float) -> dict:
    """The designer's statistics, worked out from a reference result."""
    lat = (r.log_del.astype(np.int64) - r.log_inj.astype(np.int64))
    per_link = np.where(r.t_link > 0, 1e3 * r.sent.sum(1)
                        / np.maximum(r.t_link, 1), 0.0)
    return {
        "delivered": r.delivered,
        "drops": r.drops,
        "traversals": int(r.sent.sum()),
        "thr_mev_s": 1e3 * r.delivered / r.t_end if r.t_end > 0 else 0.0,
        "max_link_mev_s": float(per_link.max()),
        "p50_ns": float(np.percentile(lat, 50)),
        "p99_ns": float(np.percentile(lat, 99)),
        "max_ns": int(lat.max()),
        "switches": int(r.n_switches.sum()),
        "energy_pj": float(r.sent.sum() * e_event_pj),
    }


def compare(res, stats: dict, ref: fabric_ref.Result, e_event_pj: float):
    """``(mismatched fields, largest relative roll-up gap)`` of one
    simulation (a program result, or a reference result standing in for
    one, which carries its telemetry fields at the top) against its
    reference."""
    bad = 0
    n = int(np.asarray(res.delivered))
    tel = getattr(res, "telemetry", res)
    for f in RESULT_FIELDS + TELEMETRY_FIELDS:
        a = getattr(tel if f in TELEMETRY_FIELDS else res, f, None)
        a = None if a is None else np.asarray(a)
        if a is not None and f.startswith("log"):
            a = a[:n]
        b = np.asarray(getattr(ref, f))
        bad += int(a is None or a.shape != b.shape
                   or not np.array_equal(a, b))
    want = reference_rollup(ref, e_event_pj)
    gap = 0.0
    for k, v in want.items():
        got = float(stats.get(k, np.nan))
        g = abs(got - v) / max(abs(v), 1e-30) if v != 0 else abs(got)
        gap = max(gap, g if np.isfinite(g) else np.inf)
    return bad, gap


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, n_chips: int,
                 log=print):
        self.cfg, self.mix, self.seed, self.log = cfg, mix, seed, log
        self.batch = int(mix["instances_per_call"])
        self.done: list[tuple[int, object, dict]] = []
        self.next_i = 0

    # --- set-up ----------------------------------------------------------

    def _fabric(self):
        """The program's fabric as the configuration states it: the
        topology's links from ``bench/topologies/<kind>.py`` (the same
        the reference reads), the program's default static shortest-path
        routing, the queues, the engine."""
        from repro.core.fabric import EngineSpec, Fabric, QueuePolicy
        from repro.core.link import LinkTiming
        from repro.core.router import Topology
        c = self.cfg
        if c["routing"] != "static_bfs":
            raise ValueError(f"routing {c['routing']!r}: the driver builds "
                             f"only the program's static BFS routes")
        n, links = fabric_ref.topology_links(c["topology"])
        t = Topology(n, links, name=c["name"])
        q = c["queues"]
        return Fabric(
            t, timing=LinkTiming(**c["timing"]),
            queues=QueuePolicy(capacity=q["capacity"],
                               max_burst=int(q["max_burst"]),
                               initial_tx=int(q["initial_tx"]),
                               flow=q["flow"]),
            engine=EngineSpec(name=c["engine"]["name"],
                              chunk_size=int(c["engine"]["chunk_size"])))

    def _spec(self, i: int):
        from repro.core.traffic import TrafficSpec
        while len(self.pool) <= i:
            self.pool.append(generator.instance(
                self.mix, self.n, self.seed, len(self.pool)))
        return TrafficSpec(*self.pool[i])

    def setup(self):
        from repro.core import network as net
        self.net = net
        self.fab = self._fabric()
        self.n = self.fab.topo.n_chips
        self.pool = [generator.instance(self.mix, self.n, self.seed, i)
                     for i in range(int(self.mix["pool"]))]
        self.log(f"traffic pool: {len(self.pool)} streams of "
                 f"{len(self.pool[0][0])} events, seed {self.seed}, "
                 f"digest {generator.digest(self.pool)}", file=sys.stderr)
        # warm-up: the cell's own bucket by a zero-event run, then one
        # call bounded to its first chunk of steps, which compiles the
        # call's program (the batch engine for B > 1) and gives results
        # of the window's shapes to warm the roll-up on
        specs = [self._spec(i) for i in range(self.batch)]
        t0 = time.perf_counter()
        bucket = self.fab.compile(specs[0]).bucket
        t1 = time.perf_counter()
        if self.batch == 1:
            results = [self.fab.run(specs[0], max_steps=1)]
        else:
            results = self.fab.run_batch(specs, max_steps=1).results()
        for r in results:
            self.rollup(r)
        self.log(f"warm-up: bucket {bucket} in {t1 - t0:.2f} s,"
                 f" one bounded call and its roll-up in "
                 f"{time.perf_counter() - t1:.2f} s", file=sys.stderr)
        self.next_i = self.batch   # warm-up streams are not reused

    # --- the timed call ----------------------------------------------------

    def rollup(self, res) -> dict:
        net = self.net
        st = net.latency_stats(res)
        per_link = np.asarray(net.per_link_throughput_mev_s(res))
        return {
            "delivered": st["delivered"],
            "drops": int(res.drops),
            "traversals": st["traversals"],
            "thr_mev_s": float(net.fabric_throughput_mev_s(res)),
            "max_link_mev_s": float(per_link.max()),
            "p50_ns": st["p50_ns"],
            "p99_ns": st["p99_ns"],
            "max_ns": st["max_ns"],
            "switches": int(np.asarray(res.n_switches).sum()),
            "energy_pj": float(net.fabric_energy_pj(res, self.fab.timing)),
        }

    def call(self, spans, split: bool = False) -> int:
        """One timed call: ``Fabric.run`` of one stream, or
        ``Fabric.run_batch`` of ``instances_per_call`` streams, then the
        roll-up.  With ``split`` (traced runs) the same work is done in
        the program's two steps, so that the benchmark's ``plan`` span
        holds the planning and its ``engine`` span the device program:
        ``Fabric._plan`` and ``Fabric.run`` (which finds that plan
        memoised), or ``fabric._plan_batch`` and ``fabric._execute_batch``
        (what ``run_batch`` calls)."""
        from repro.core import fabric as F
        i0 = self.next_i
        specs = [self._spec(i0 + k) for k in range(self.batch)]
        self.next_i += self.batch
        if self.batch == 1:
            if split:
                with spans("plan"):
                    self.fab._plan(specs[0], None)
            with spans("engine"):
                res = self.fab.run(specs[0])
                res.log_del.block_until_ready()
            results = [res]
        else:
            if split:
                fabs = [self.fab] * self.batch
                with spans("plan"):
                    plans = F._plan_batch(fabs, specs, None)
                with spans("engine"):
                    batch = F._execute_batch(
                        fabs, plans, F._resolve_devices(None, self.batch))
                    batch.log_del.block_until_ready()
            else:
                with spans("engine"):
                    batch = self.fab.run_batch(specs)
                    batch.log_del.block_until_ready()
        with spans("rollup"):
            if self.batch > 1:
                results = batch.results()
            stats = [self.rollup(r) for r in results]
        for k, (r, s) in enumerate(zip(results, stats)):
            self.done.append((i0 + k, r, s))
        return sum(int(r.injected) for r in results)

    # --- numbers ------------------------------------------------------------

    def end_to_end(self, events: int, elapsed: float) -> dict:
        return {"sim_events_per_s": events / elapsed}

    def layer_context(self) -> dict:
        """What the per-layer readers of the fabric cells read besides the
        spans and the trace: simulations in the window and per call."""
        return {"sims": len(self.done), "batch": self.batch}

    def attempted(self) -> int:
        """Simulations started in the window."""
        return self.done_count

    def failed(self) -> int:
        """Simulations in the window that did not carry every event to
        its delivery or drop (counted by ``release``)."""
        return self._failed

    def release(self):
        """Drop the program's compiled state; keep only the sampled
        results for the check."""
        n_check = min(int(self.mix["check"]), len(self.done))
        rng = np.random.default_rng([int(self.seed), 0xC4EC])
        pick = sorted(rng.choice(len(self.done), n_check, replace=False))
        self._failed = sum(
            int(np.asarray(r.delivered)) + int(np.asarray(r.drops))
            != int(r.injected) for _, r, _ in self.done)
        self.checked = [self.done[k] for k in pick]
        self.done_count = len(self.done)
        self.done = []
        self.fab = None

    def check(self) -> dict:
        """Compare the sampled simulations with the reference."""
        c = self.cfg
        streams = [self.pool[i] if i < len(self.pool)
                   else generator.instance(self.mix, self.n, self.seed, i)
                   for i, _, _ in self.checked]
        t0 = time.perf_counter()
        refs = fabric_ref.simulate(c, streams)
        t_ref = time.perf_counter() - t0
        bad, gap, incomplete = 0, 0.0, 0
        for (i, res, stats), ref in zip(self.checked, refs):
            b, g = compare(res, stats, ref, float(c["timing"]["e_event_pj"]))
            bad += b
            gap = max(gap, g)
            incomplete += int(not ref.complete)
        self.log(f"checked {len(refs)} of {self.done_count} simulations "
                 f"(instances {[i for i, _, _ in self.checked][:8]}) "
                 f"against the reference in {t_ref:.2f} s", file=sys.stderr)
        return {
            "unfinished_simulations": {"value": self._failed, "limit": 0,
                                       "ok": self._failed == 0},
            "mismatched_fields": {"value": bad, "limit": LIMIT_MISMATCHED,
                                  "ok": bad <= LIMIT_MISMATCHED
                                  and len(refs) > 0 and incomplete == 0},
            "rollup_rel_gap": {"value": gap, "limit": LIMIT_ROLLUP_GAP,
                               "ok": gap <= LIMIT_ROLLUP_GAP},
        }
