"""Epoch-based adaptive routing: the fabric's congestion control plane.

Static shortest-path routing serves uniform traffic well, but skewed
(hot-spot / convergecast) workloads saturate a few contended links while
parallel links idle — the congestion ceiling DYNAPs (Moradi et al. 2017)
and the core-interface optimization work (Su et al. 2023) identify as
the real limit of multi-core AER throughput.  This module closes the
loop over the telemetry plane (:mod:`repro.core.telemetry`):

1. A run is split into **epochs** — contiguous injection-time slices of
   the workload (:func:`partition_epochs`).  Each epoch simulates on the
   routing tables chosen *before* it started; between epochs the fabric
   drains (quasi-static reconfiguration, the standard model for updating
   neuromorphic routing fabrics in operation).
2. After an epoch, its per-link :class:`~repro.core.telemetry.LinkLoad`
   becomes a congestion signal, and the next epoch's tables are rebuilt
   by **congestion-weighted shortest paths**
   (``RoutingTable.build_weighted``: integer edge costs
   ``base + alpha * load``, deterministic tie-breaks) — including the
   per-``(source, tag)`` ``MulticastTree`` Steiner branchings, which are
   regrown on the new tables through the same replication-table operands
   the engines already consume.
3. Routing tables travel as *dynamic operands* through the engines'
   shape-bucketed jit cache, so every epoch of a run reuses ONE XLA
   compilation (``AdaptiveReport.cache_size == 1``; asserted in tests).

Contracts (all tested):

* epoch 0 is bit-exact with static routing on the same slice (the base
  tables ARE the static tables);
* ``alpha = 0`` (or a zero load signal) rebuilds tables bit-identical to
  BFS, so an adaptive run degenerates to ``Fabric.run_epochs`` under
  ``StaticShortestPath`` exactly;
* telemetry counters merge additively, and the merged result keeps
  ``delivered + drops == injected``.

Batched execution (``Fabric.run_batch`` / ``fabric.run_batch``) refuses
adaptive policies by design: the epoch loop is a *sequential feedback
control loop* — epoch ``k``'s telemetry re-weights epoch ``k + 1``'s
tables — so B adaptive instances cannot fuse into one feed-forward
computation without changing semantics.  Batch the static baseline
(``StaticShortestPath`` or prebuilt tables) instead, or run adaptive
specs through ``Fabric.run`` / ``run_epochs`` one at a time; the epoch
slices of those runs still share one compilation via the shape-bucketed
jit cache.

Policies (`AdaptiveRouting.policy`):

``"min_backlog"``
    Signal = normalized backlog-step integral + normalized weighted
    drops per link.  Reacts to *queueing* — prefer it for bursty or
    capacity-limited fabrics where drops and standing backlog mark the
    contended links.
``"weighted_bfs"``
    Signal = link traversal counts.  Reacts to *utilization* — prefer
    it for steady skewed load where you want flows spread by volume
    before queues ever build.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from .network import FabricResult
from .router import RoutingTable, Topology
from .telemetry import LinkLoad, link_load, merge_telemetry
from .traffic import TrafficSpec

__all__ = ["AdaptiveRouting", "AdaptiveReport", "EpochRecord",
           "partition_epochs", "merge_results", "run_epoched",
           "shared_max_steps"]

#: Integer quantisation of congestion-weighted edge costs: a base cost
#: of _COST_SCALE per link plus up to ``alpha * _COST_SCALE`` of
#: congestion penalty, rounded — reproducible across platforms, and a
#: zero penalty is *exactly* uniform (BFS-degenerate).
_COST_SCALE = 1024


@dataclass(frozen=True)
class AdaptiveRouting:
    """Congestion-adaptive routing policy (a ``fabric.RoutingPolicy``).

    ``policy`` — congestion signal: ``"min_backlog"`` (backlog + drops)
    or ``"weighted_bfs"`` (traversals); see the module docstring for
    when to prefer which.
    ``epochs`` — number of injection-time slices the run is split into;
    tables are recomputed between consecutive epochs.  ``epochs=1``
    never adapts (identical to static routing).
    ``alpha``  — congestion weight: next-epoch edge cost is
    ``1 + alpha * load / max(load)`` (quantised).  ``alpha=0`` is
    bit-exact static routing; ``alpha < 1`` only re-balances among
    equal-hop alternatives (a detour can never pay); larger values buy
    longer detours around contended links (a detour of ``k`` extra hops
    pays off once the contended link's normalized load exceeds
    ``k / alpha``).
    ``ema``    — congestion-signal smoothing in (0, 1]: the signal fed
    to the table rebuild is ``ema * this_epoch + (1 - ema) * previous
    signal``.  1.0 reacts instantly but can flip-flop all flows between
    alternatives epoch over epoch (the classic stale-signal
    oscillation); smaller values damp the swing and settle on a split.
    ``trigger`` — when tables are rebuilt between epochs.  ``"epoch"``
    (default): after every epoch, unconditionally.  ``"backlog_burst"``:
    event-driven — only when one link's congestion (backlog + stall +
    drop integral) bursts past ``threshold ×`` the fabric mean;
    quiescent or evenly-loaded epochs keep their tables, so a fabric
    under benign load never churns routes (and never pays the
    tree-regrow setup) while a hot-spot burst still reroutes within one
    epoch.  The EMA signal keeps folding every epoch either way, so a
    slow-building burst is judged on its full history when it crosses.
    ``threshold`` — the burst factor for ``trigger="backlog_burst"``;
    ``0`` rebuilds whenever any congestion exists at all.
    """
    policy: str = "min_backlog"
    epochs: int = 4
    alpha: float = 2.0
    ema: float = 0.5
    trigger: str = "epoch"
    threshold: float = 4.0

    POLICIES = ("min_backlog", "weighted_bfs")
    TRIGGERS = ("epoch", "backlog_burst")

    def __post_init__(self):
        if self.policy not in self.POLICIES:
            raise ValueError(f"unknown adaptive policy {self.policy!r}; "
                             f"expected one of {self.POLICIES}")
        if int(self.epochs) < 1:
            raise ValueError(f"epochs must be >= 1, got {self.epochs}")
        if float(self.alpha) < 0:
            raise ValueError(f"alpha must be >= 0, got {self.alpha}")
        if not 0.0 < float(self.ema) <= 1.0:
            raise ValueError(f"ema must be in (0, 1], got {self.ema}")
        if self.trigger not in self.TRIGGERS:
            raise ValueError(f"unknown trigger {self.trigger!r}; "
                             f"expected one of {self.TRIGGERS}")
        if float(self.threshold) < 0:
            raise ValueError(f"threshold must be >= 0, got "
                             f"{self.threshold}")

    # --- RoutingPolicy protocol: epoch-0 tables ARE the static tables --
    def build(self, topo: Topology) -> RoutingTable:
        return RoutingTable.build(topo)

    # --- the control loop's two pure functions -------------------------
    def load_signal(self, result: FabricResult) -> np.ndarray:
        """(L,) float congestion signal from one epoch's telemetry."""
        ll = link_load(result)
        if self.policy == "weighted_bfs":
            return ll.traversals.astype(np.float64)
        backlog = ll.backlog_steps.astype(np.float64)
        drops = ll.drops.astype(np.float64)
        stalls = ll.stalls.astype(np.float64)
        if backlog.max(initial=0) > 0:
            backlog = backlog / backlog.max()
        if drops.max(initial=0) > 0:
            drops = drops / drops.max()
        # flow-control stalls mark the links the lossless modes throttle
        # on — the congestion drops used to flag; zero in drop mode, so
        # historical drop-mode signals are untouched
        if stalls.max(initial=0) > 0:
            stalls = stalls / stalls.max()
        return backlog + drops + stalls

    def should_rebuild(self, load: LinkLoad) -> bool:
        """Event-driven rebuild gate: does this epoch's telemetry warrant
        new tables?  Always true under ``trigger="epoch"``; under
        ``"backlog_burst"``, true only when the hottest link's congestion
        integral bursts past ``threshold ×`` the fabric-wide mean."""
        if self.trigger == "epoch":
            return True
        hot = (load.backlog_steps.astype(np.float64)
               + load.stalls.astype(np.float64)
               + load.drops.astype(np.float64))
        mx = float(hot.max(initial=0.0))
        return mx > 0.0 and mx > float(self.threshold) * float(hot.mean())

    def next_table(self, topo: Topology, load: np.ndarray) -> RoutingTable:
        """Congestion-weighted shortest-path tables for the next epoch."""
        load = np.asarray(load, np.float64)
        mx = load.max(initial=0.0)
        if mx <= 0 or float(self.alpha) == 0.0:
            cost = np.full(topo.n_links, _COST_SCALE, np.int64)
        else:
            cost = np.rint(_COST_SCALE
                           * (1.0 + float(self.alpha) * load / mx)
                           ).astype(np.int64)
        return RoutingTable.build_weighted(topo, cost)


class EpochRecord(NamedTuple):
    """One epoch of an epoched run, as the report exposes it."""
    result: FabricResult        # the epoch's own FabricResult
    table: RoutingTable         # tables the epoch ran on
    load: LinkLoad              # the epoch's telemetry roll-up
    bucket: tuple               # engine shape bucket the epoch used
    cache_size: int             # jit entries in that bucket's engine
    rebuilt: bool = True        # tables rebuilt AFTER this epoch?


class AdaptiveReport(NamedTuple):
    """Side-channel record of one epoched run (``Fabric.last_report``).

    ``buckets`` is the ordered set of engine shape buckets the epochs
    used and ``cache_size`` the final jit-cache entry count of the
    shared engine.  The zero-recompile contract is :attr:`recompiled`
    ``== False``: one bucket, and the entry count flat from the first
    epoch on (epoch 0 pays the one compilation; in a fresh process the
    count is exactly 1, but an engine function can be shared by sibling
    buckets — e.g. a multicast-capable fabric of the same size — so
    *flatness*, not the absolute count, is the invariant).
    """
    records: tuple[EpochRecord, ...]
    buckets: tuple[tuple, ...]
    cache_size: int
    result: FabricResult

    @property
    def n_epochs(self) -> int:
        return len(self.records)

    @property
    def recompiled(self) -> bool:
        """True if any epoch after the first compiled anything new."""
        sizes = [r.cache_size for r in self.records]
        return len(self.buckets) != 1 or any(s != sizes[0] for s in sizes)


def partition_epochs(spec: TrafficSpec, epochs: int) -> list[TrafficSpec]:
    """Split a workload into ``epochs`` contiguous injection-time slices.

    Events are ranked by ``(t, original index)`` (stable) and cut into
    near-equal count slices (``i * n // epochs`` boundaries — exactly
    equal when ``n`` divides, which also keeps the slot engines on one
    shape bucket).  Within a slice the original event order is kept.
    Empty slices (more epochs than events) are omitted.
    """
    if int(epochs) < 1:
        raise ValueError(f"epochs must be >= 1, got {epochs}")
    t = np.asarray(spec.t)
    n = len(t)
    order = np.argsort(t, kind="stable")
    parts = []
    for i in range(int(epochs)):
        sel = order[i * n // epochs:(i + 1) * n // epochs]
        if not len(sel):
            continue
        idx = np.sort(sel)
        parts.append(TrafficSpec(src=spec.src[idx], t=spec.t[idx],
                                 dest=spec.dest[idx]))
    return parts


def merge_results(results: list[FabricResult], *,
                  offered: int) -> FabricResult:
    """Fold per-epoch results into one workload-level ``FabricResult``.

    Counters are extensive (summed); delivery logs concatenate in epoch
    order (each trimmed to its own ``delivered``); clocks take the
    elementwise maximum — injection times are absolute across the whole
    run, so the last epoch's clocks ARE the end of the run and
    latency/throughput roll-ups stay exact.
    """
    if not results:
        raise ValueError("no epoch results to merge")
    ns = [int(r.delivered) for r in results]
    cat = {f: np.concatenate([np.asarray(getattr(r, f))[:k]
                              for r, k in zip(results, ns)])
           for f in ("log_inj", "log_del", "log_dest")}
    return FabricResult(
        delivered=np.int32(sum(ns)),
        injected=sum(r.injected for r in results),
        log_inj=cat["log_inj"], log_del=cat["log_del"],
        log_dest=cat["log_dest"],
        sent=sum(np.asarray(r.sent, np.int64) for r in results),
        n_switches=sum(np.asarray(r.n_switches, np.int64)
                       for r in results),
        t_link=np.maximum.reduce([np.asarray(r.t_link) for r in results]),
        t_end=np.int32(max(int(r.t_end) for r in results)),
        drops=np.int64(sum(int(r.drops) for r in results)),
        offered=offered,
        telemetry=merge_telemetry([r.telemetry for r in results]),
        steps=np.int32(sum(int(r.steps) for r in results)))


def shared_max_steps(fabric, parts: list[TrafficSpec], *,
                     detour_factor: float = 1.0) -> int:
    """One step bound for every epoch, scaled for detour headroom.

    A congestion-weighted route can be longer than the static shortest
    path: a contended link costs up to ``(1 + alpha)`` base units while
    every hop costs at least one, so weighted path length is bounded by
    ``(1 + alpha) *`` static hops — and, since weighted routes are
    loop-free, by ``n_chips - 1`` hops absolutely.  The caller passes
    ``detour_factor = 1 + alpha`` (floored at 2 for legacy headroom) and
    the per-slice transmission estimate is scaled by it under the
    absolute hop cap, so an auto-computed bound can never bind on a
    completed adaptive epoch.  A single static value keeps the slot
    engines (which bake the scan length into their shape bucket) on ONE
    compilation across epochs.

    Unicast/source-expand slices use a direct estimate (the same
    ``4 * total_tx + 2 * E + 64 * (diameter + 2)`` formula ``_plan_impl``
    defaults to) so the full plan — prefill, stream-quota path walk — is
    built exactly once per slice, at run time; only in-fabric multicast
    slices need the tree-building plan to know their bound."""
    from .network import _expand
    rt = fabric.routing_table
    f = max(2.0, float(detour_factor))
    N = fabric.topo.n_chips
    ms = 0
    for p in parts:
        if fabric.mcast_policy.mode == "in_fabric":
            ms = max(ms, int(np.ceil(
                f * fabric._plan_impl(p, None).max_steps)))
            continue
        src, _t, dest = _expand(p, fabric.addr, fabric.mcast)
        total_tx = min(int(np.ceil(f * int(rt.hops[src, dest].sum()))),
                       len(src) * max(N - 1, 1))
        ms = max(ms, 4 * total_tx + 2 * len(src)
                 + 64 * (rt.diameter + 2))
    return ms


def run_epoched(fabric, spec: TrafficSpec, *, epochs: int,
                max_steps: int | None = None,
                policy: AdaptiveRouting | None = None) -> FabricResult:
    """Run ``spec`` in injection-time epochs on ``fabric``.

    With ``policy=None`` the fabric's own (static) tables serve every
    epoch — the fair A/B baseline for adaptive runs, sharing this exact
    partition/merge path.  With an :class:`AdaptiveRouting` policy, each
    epoch's telemetry re-weights the next epoch's tables (unicast AND
    multicast trees — the per-epoch fabric rebuilds its Steiner
    branchings from the new tables).  The merged ``FabricResult`` comes
    back; the per-epoch breakdown lands on ``fabric.last_report``.
    """
    parts = partition_epochs(spec, epochs)
    if not parts:
        raise ValueError("workload has no events")
    auto_bound = max_steps is None
    shared_ms = (int(max_steps) if max_steps is not None
                 else shared_max_steps(
                     fabric, parts,
                     detour_factor=1.0 + float(policy.alpha)
                     if policy is not None else 1.0))
    records: list[EpochRecord] = []
    results: list[FabricResult] = []
    epoch_fab = fabric
    table = fabric.routing_table
    signal = None  # EMA-smoothed congestion signal across epochs
    for e, part in enumerate(parts):
        res = epoch_fab._run_single(part, max_steps=shared_ms)
        if auto_bound and \
                int(res.delivered) + int(res.drops) != res.injected:
            # the auto bound must never bind: raising beats silently
            # under-reporting drops/latency (an EXPLICIT max_steps is
            # the caller's business and may truncate, as the engines
            # document)
            raise RuntimeError(
                f"epoch {e} truncated at the auto step bound "
                f"{shared_ms} ({int(res.delivered)} + {int(res.drops)} "
                f"of {res.injected} accounted); pass max_steps "
                f"explicitly to run_epochs/run")
        bucket = epoch_fab._plan(part, shared_ms).bucket
        cf = epoch_fab._get_compiled(bucket)
        load = link_load(res)
        rebuild = (policy is not None and e + 1 < len(parts)
                   and policy.should_rebuild(load))
        records.append(EpochRecord(result=res, table=table, load=load,
                                   bucket=bucket,
                                   cache_size=cf.cache_size(),
                                   rebuilt=rebuild))
        results.append(res)
        if policy is not None and e + 1 < len(parts):
            # the EMA signal folds every epoch (a slow-building burst is
            # judged on its history); the table rebuild itself waits for
            # the policy's trigger
            raw = policy.load_signal(res)
            signal = raw if signal is None else (
                float(policy.ema) * raw
                + (1.0 - float(policy.ema)) * signal)
            if rebuild:
                table = policy.next_table(fabric.topo, signal)
                epoch_fab = fabric._with_routing(table)
    merged = merge_results(results, offered=spec.n_events)
    fabric.last_report = AdaptiveReport(
        records=tuple(records),
        buckets=tuple(dict.fromkeys(r.bucket for r in records)),
        cache_size=records[-1].cache_size,
        result=merged)
    return merged
