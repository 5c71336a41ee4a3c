"""Declarative fabric front-end: composable policies + compile/run lifecycle.

``network.simulate_fabric`` grew one kwarg per feature; this module is the
redesigned front door.  A :class:`Fabric` is a *declaration* — topology
plus four orthogonal policies:

* ``routing`` — a :class:`RoutingPolicy`: ``StaticShortestPath`` (BFS
  tables + a ``table_override`` hook), a prebuilt ``RoutingTable``, or
  :class:`repro.core.adaptive.AdaptiveRouting` — the congestion control
  plane, which splits each ``run`` into epochs and re-weights the tables
  from per-link telemetry between them (``Fabric.run_epochs`` runs the
  same partition under static tables as the A/B baseline).
* ``timing``  — one scalar ``LinkTiming`` shared by every link, or a
  structure-of-arrays ``LinkTiming`` of shape (L,) mixing link classes
  (fast parallel on-board buses next to slow bit-serial LVDS inter-board
  links — see ``link.per_link_timing`` / ``link.SERIAL_LVDS_TIMING``).
* ``queues``  — :class:`QueuePolicy`: per-endpoint capacity, bounded-burst
  fairness, reset polarity.
* ``engine``  — :class:`EngineSpec`: which bit-exact event-transport
  engine runs the micro-transaction loop and its chunking.

Execution is an *explicit lifecycle*:

    fab = Fabric(ring_topology(8), timing=mixed, queues=QueuePolicy(max_burst=1))
    cf = fab.compile(spec)          # bind + pre-warm one shape bucket
    res = cf.run(spec)              # no compilation on this path
    results = fab.run_many(specs)   # one compile amortised over a sweep

``Fabric.compile`` makes the PR 2 shape-bucketed jit cache user-visible:
it returns a :class:`CompiledFabric` pinned to one bucket (the pow2-padded
static shape signature), whose ``warmup()`` populates the XLA cache with a
zero-event dummy run and whose ``cache_size()`` exposes the underlying jit
entry count — so tests and serving loops can *prove* a hot path never
recompiles.  ``Fabric.run`` routes each spec to the right bucket
automatically and caches ``CompiledFabric`` instances per bucket.

``simulate_fabric`` survives unchanged as a thin wrapper that builds a
one-shot ``Fabric`` and calls ``run`` — every historical call site keeps
working and stays bit-exact.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .link import PAPER_TIMING, LinkTiming, link_timing_arrays
from .network import (DEFAULT_CHUNK_SIZE, ENGINES, FabricBatchResult,
                      FabricResult, _BIG,
                      _RING_D_FLOOR, _RING_E_FLOOR, _RING_K_FLOOR,
                      _RING_L_FLOOR, _RING_N_FLOOR, _RING_R_FLOOR,
                      _RING_STREAM_FLOOR, _check_reachable, _expand,
                      _first_hop_queues, _in_edge_ranks, _overflow_guard,
                      _overflow_guard_routed, _pad_to, _pow2ceil,
                      _prefill, _ring_engine, _route_link_tx,
                      _ring_engine_batch, _routes_with_trees, _slot_engine,
                      _slot_engine_batch, _slot_engine_multistep,
                      _slot_engine_multistep_batch, _stream_quota,
                      _tree_stream_quota, _unicast_routes)
from .router import (AddressSpec, MulticastTable, MulticastTree,
                     RoutingTable, Topology, find_route_cycles)
from . import tracing
from .telemetry import Telemetry
from .traffic import TrafficSpec

__all__ = ["Fabric", "CompiledFabric", "QueuePolicy", "FLOW_MODES",
           "EngineSpec",
           "MulticastPolicy", "RoutingPolicy", "StaticShortestPath",
           "PrebuiltRouting", "SweepCell", "BatchSweepCell", "run_batch",
           "batch_cache_size"]


# -----------------------------------------------------------------------
# Policies
# -----------------------------------------------------------------------

#: flow-control modes, in engine encoding order (index = the dynamic
#: ``fc_mode`` scalar the engines receive)
FLOW_MODES = ("drop", "credit", "onoff")


@dataclass(frozen=True)
class QueuePolicy:
    """Per-endpoint queue behaviour of every link in the fabric.

    ``capacity``   — one-shot slot budget per endpoint (bounds the events
                     routed *through* an endpoint, not instantaneous
                     depth); ``None`` = lossless (the expanded event
                     count).  What happens when a forward would overflow
                     it is ``flow``'s call.
    ``max_burst``  — 0 = paper-faithful grant rule; B > 0 = bounded-burst
                     fairness (transmitter yields after B events when the
                     peer requests).
    ``initial_tx`` — scalar or (L,): which side of each link resets into
                     TX mode (the paper's chip-level global reset).
    ``flow``       — ``"drop"`` (default): overflowing forwards are
                     dropped and counted in ``FabricResult.drops``.
                     ``"credit"``: per-link credit counters — a sender
                     whose head would forward into a full downstream
                     queue *stalls in place* (no drop; credits return as
                     the downstream queue pops).  ``"onoff"``: threshold
                     xon/xoff — a queue crossing ``capacity`` asserts
                     xoff and releases it at ``xon``.  Both lossless
                     modes require ``capacity``; see the ``network``
                     module docstring for the exact gate semantics and
                     the cyclic-route deadlock caveat.
    ``xon``        — on/off mode's resume threshold (occupancy at or
                     below it deasserts xoff).  Default ``capacity // 2``;
                     ``xon = capacity - 1`` makes on/off coincide with
                     credit mode exactly.
    """
    capacity: int | None = None
    max_burst: int = 0
    initial_tx: int | np.ndarray = 1
    flow: str = "drop"
    xon: int | None = None

    def __post_init__(self):
        if self.capacity is not None and int(self.capacity) < 1:
            raise ValueError(f"queue capacity must be >= 1, got "
                             f"{self.capacity}")
        if int(self.max_burst) < 0:
            raise ValueError(f"max_burst must be >= 0, got {self.max_burst}")
        if self.flow not in FLOW_MODES:
            raise ValueError(f"unknown flow mode {self.flow!r}; expected "
                             f"one of {FLOW_MODES}")
        if self.flow != "drop" and self.capacity is None:
            raise ValueError(f"flow={self.flow!r} needs a finite queue "
                             f"capacity (capacity=None is already "
                             f"lossless)")
        if self.xon is not None:
            if self.flow != "onoff":
                raise ValueError("xon only applies to flow='onoff'")
            if not 0 <= int(self.xon) < int(self.capacity):
                raise ValueError(f"xon must satisfy 0 <= xon < capacity, "
                                 f"got xon={self.xon} with "
                                 f"capacity={self.capacity}")


@dataclass(frozen=True)
class EngineSpec:
    """Which bit-exact event-transport engine runs the simulation.

    ``name``       — ``"auto"`` (= ring), ``"ring"``, ``"reference"`` or
                     ``"pallas"`` (see ``network`` module docstring).
    ``chunk_size`` — ring engine: micro-transactions per ``lax.scan``
                     chunk between early-exit checks.  Pallas multi-step
                     kernel: micro-transactions fused per kernel launch.
    ``kernel``     — pallas engine only.  ``"step"`` (default) dispatches
                     the per-step scan/update kernel pair once per
                     micro-transaction; ``"multistep"`` runs the fused
                     multi-step kernel — ``chunk_size`` steps per launch
                     with the packed carry resident across steps, so a
                     run costs ``ceil(max_steps / chunk_size)`` dispatches
                     instead of ``2 * max_steps``.  Bit-exact with every
                     other engine; each kernel choice compiles its own
                     shape bucket (audited by ``cache_size()``).
    """
    name: str = "auto"
    chunk_size: int = DEFAULT_CHUNK_SIZE
    kernel: str = "step"

    KERNELS = ("step", "multistep")

    def __post_init__(self):
        resolved = "ring" if self.name == "auto" else self.name
        if resolved not in ENGINES:
            raise ValueError(f"unknown engine {self.name!r}; expected one "
                             f"of {ENGINES} (or 'auto')")
        if int(self.chunk_size) < 1:
            # a 0-step chunk would make the early-exit while_loop spin
            # forever
            raise ValueError(f"chunk_size must be >= 1, got "
                             f"{self.chunk_size}")
        if self.kernel not in self.KERNELS:
            raise ValueError(f"unknown kernel {self.kernel!r}; expected "
                             f"one of {self.KERNELS}")
        if self.kernel == "multistep" and resolved != "pallas":
            raise ValueError(
                f"kernel='multistep' is a pallas-engine knob (the fused "
                f"multi-step fabric kernel); engine {self.name!r} "
                f"resolves to {resolved!r}")

    @property
    def resolved(self) -> str:
        return "ring" if self.name == "auto" else self.name


@dataclass(frozen=True)
class MulticastPolicy:
    """How tagged (multicast) events traverse the fabric.

    ``mode``
        ``"source_expand"`` (default, the PR 1 semantics): a tag with
        fanout F becomes F independent unicast copies at the source —
        bit-exact with the historical behaviour, but F traversals of
        every shared link.

        ``"in_fabric"``: the event carries its tag through the fabric
        and is replicated only where the per-``(source, tag)``
        Steiner-branching tree diverges (``router.MulticastTree``) —
        one traversal per tree edge, the DYNAPs-style replication the
        paper's reserved multicast flag anticipates.

    ``table``
        The ``MulticastTable`` resolving tags to member-chip sets
        (required only when the traffic actually carries tagged events).

    Both modes deliver the identical destination multiset; ``in_fabric``
    strictly reduces link traversals whenever member paths share links.
    """
    mode: str = "source_expand"
    table: MulticastTable | None = None

    MODES = ("source_expand", "in_fabric")

    def __post_init__(self):
        if self.mode not in self.MODES:
            raise ValueError(f"unknown multicast mode {self.mode!r}; "
                             f"expected one of {self.MODES}")
        if self.table is not None and not isinstance(self.table,
                                                     MulticastTable):
            raise TypeError(f"table must be a MulticastTable, got "
                            f"{type(self.table).__name__}")


@runtime_checkable
class RoutingPolicy(Protocol):
    """Anything that turns a topology into next-hop tables."""

    def build(self, topo: Topology) -> RoutingTable: ...


def _validate_tables(topo: Topology, rt: RoutingTable) -> RoutingTable:
    n = topo.n_chips
    for name in ("next_link", "out_side", "hops"):
        a = np.asarray(getattr(rt, name))
        if a.shape != (n, n):
            raise ValueError(f"routing table {name} has shape {a.shape}, "
                             f"expected ({n}, {n})")
    nl = np.asarray(rt.next_link)
    if nl.max(initial=-1) >= topo.n_links:
        raise ValueError("routing table names a link id outside the "
                         "topology")
    return rt


@dataclass(frozen=True)
class StaticShortestPath:
    """Deterministic BFS shortest-path routing (the PR 1 tables).

    ``table_override`` — optional hook called with ``(topo, built_table)``
    returning a replacement ``RoutingTable``.  This is the landing pad
    for adaptive/congestion-aware routing policies: an override can bias
    next-hops off the shortest path (it is trusted to keep the tables
    consistent — every hop must make progress, or events cycle until the
    step bound binds).
    """
    table_override: Callable[[Topology, RoutingTable],
                             RoutingTable] | None = None

    def build(self, topo: Topology) -> RoutingTable:
        rt = RoutingTable.build(topo)
        if self.table_override is not None:
            rt = _validate_tables(topo, self.table_override(topo, rt))
        return rt


@dataclass(frozen=True)
class PrebuiltRouting:
    """Adapter: a ready-made ``RoutingTable`` as a ``RoutingPolicy``."""
    table: RoutingTable

    def build(self, topo: Topology) -> RoutingTable:
        return _validate_tables(topo, self.table)


# -----------------------------------------------------------------------
# Run planning (setup-time numpy; shared by compile and run)
# -----------------------------------------------------------------------

class _Plan(NamedTuple):
    """Everything one execution needs: routed traffic, prefilled
    queues, replication tables, dynamic scalars and the static shape
    bucket they fit.  ``E`` is the EXPECTED delivery count (fanout
    applied); ``offered`` the pre-fanout event count the ``fanout``
    metric reports against.  ``C`` is the *physical* slot width the
    engines allocate; ``cap``/``fc``/``xon`` the dynamic flow-control
    scalars (logical capacity, mode index into ``FLOW_MODES``, resume
    threshold) they receive as operands."""
    E: int
    C: int
    max_steps: int
    q_time: np.ndarray
    q_dest: np.ndarray      # route ids (dest chip | n_chips + tree)
    q_inj: np.ndarray
    sizes: np.ndarray
    route_out: np.ndarray   # (N, R, K) replication out-queues, -1 = none
    route_del: np.ndarray   # (N, R) local-deliver bits
    route_wt: np.ndarray    # (N, R, K) subtree delivery weights (drops)
    offered: int
    bucket: tuple
    cap: int = 1            # logical per-endpoint budget (dynamic scalar)
    fc: int = 0             # FLOW_MODES index (dynamic scalar)
    xon: int = 0            # on/off resume threshold (dynamic scalar)


class SweepCell(NamedTuple):
    result: FabricResult
    us_per_call: float
    bucket: tuple


class BatchSweepCell(NamedTuple):
    """Timing of one batched dispatch: ``us_per_call`` is the whole
    batch's wall-clock, ``us_per_instance`` the amortised per-fabric
    cost (the number the Monte-Carlo amortisation gate compares against
    sequential ``run``)."""
    result: FabricBatchResult
    us_per_call: float
    us_per_instance: float
    bucket: tuple


class Fabric:
    """A declarative N-chip AER fabric: topology + composable policies.

    See the module docstring for the lifecycle.  Construction resolves
    and validates every policy eagerly (routing tables are built once,
    timing is normalised to per-link cost vectors), so a ``Fabric`` held
    by a serving loop never re-runs setup-time numpy per call beyond the
    per-spec routing/prefill pass.
    """

    def __init__(self, topo: Topology, *,
                 routing: RoutingPolicy | RoutingTable | None = None,
                 timing: LinkTiming = PAPER_TIMING,
                 queues: QueuePolicy | None = None,
                 engine: EngineSpec | str | None = None,
                 addr: AddressSpec | None = None,
                 mcast: MulticastTable | MulticastPolicy | None = None):
        self.topo = topo
        if routing is None:
            policy: RoutingPolicy = StaticShortestPath()
        elif isinstance(routing, RoutingTable):
            policy = PrebuiltRouting(routing)
        elif isinstance(routing, RoutingPolicy):
            policy = routing
        else:
            raise TypeError(f"routing must be a RoutingPolicy or a "
                            f"RoutingTable, got {type(routing).__name__}")
        self.routing_policy = policy
        self.queues = queues if queues is not None else QueuePolicy()
        if engine is None:
            engine = EngineSpec()
        elif isinstance(engine, str):
            engine = EngineSpec(name=engine)
        self.engine = engine
        self.timing = timing
        self.addr = addr
        if mcast is None:
            self.mcast_policy = MulticastPolicy()
        elif isinstance(mcast, MulticastPolicy):
            self.mcast_policy = mcast
        elif isinstance(mcast, MulticastTable):
            self.mcast_policy = MulticastPolicy(table=mcast)
        else:
            raise TypeError(f"mcast must be a MulticastTable or a "
                            f"MulticastPolicy, got {type(mcast).__name__}")
        # legacy attribute: the bare table (what _expand consumes)
        self.mcast = self.mcast_policy.table

        L = topo.n_links
        # normalised per-link cost vectors: the engines' dynamic operands
        self.timing_arrays = link_timing_arrays(timing, L)
        tc, tv, ti = self.timing_arrays
        # per-link worst single-transmission cost (the tight routed
        # clock-budget guard) and its fabric-wide max (the documented
        # fallback bound when a broken table defeats the route walk)
        self._link_cost = tc.astype(np.int64) + np.maximum(tv, ti)
        self._worst_cost = int(self._link_cost.max(initial=1))
        self.routing_table = policy.build(topo)
        # Lossless flow control relies on every route making progress.
        # A next-hop cycle (possible only through table_override hooks
        # or prebuilt tables — BFS/Dijkstra tables are acyclic by
        # construction) breaks that for the pairs caught on it; PR 7
        # refused ANY such table outright.  The precise Dally–Seitz
        # criterion (repro.analysis.verify) is finer: what deadlocks a
        # stall chain is a cycle in the CHANNEL-DEPENDENCY graph of the
        # routes events actually ride.  So: when broken pairs exist but
        # the terminating routes' CDG is acyclic, the fabric is
        # admitted and the broken pairs are QUARANTINED — planning
        # refuses traffic that addresses them (see _plan_impl) while
        # everything else provably drains.  Only when the remaining
        # CDG itself carries a cycle is construction refused, with the
        # offending channel cycle named.  Drop mode keeps the
        # historical behaviour (events on a cyclic route are dropped
        # or truncated; pops are never gated, so no deadlock).  Note
        # a clean table (no broken pairs) may still have a cyclic CDG
        # (every ring >= 5 does) — that hazard is graded per-spec by
        # Fabric.verify(), which weighs channel demand against
        # capacity; it is not a construction error.
        self._nonterm_mask: np.ndarray | None = None
        if self.queues.flow != "drop":
            bad = find_route_cycles(topo, self.routing_table)
            if len(bad):
                from ..analysis.verify import channel_graph
                g = channel_graph(topo, self.routing_table,
                                  exclude_pairs=bad)
                cycle = g.find_cycle()
                shown = ", ".join(f"{c}->{d}" for c, d in bad[:4].tolist())
                if cycle is not None:
                    raise ValueError(
                        f"routing table has {len(bad)} (chip, dest) "
                        f"pair(s) whose route never reaches the "
                        f"destination (next-hop cycle or dead-end), "
                        f"e.g. {shown}, and the terminating routes' "
                        f"channel-dependency graph also carries a "
                        f"cycle ({g.describe_cycle(cycle)}); "
                        f"flow={self.queues.flow!r} would deadlock — "
                        f"fix the table or use flow='drop'")
                mask = np.zeros((topo.n_chips, topo.n_chips), bool)
                mask[bad[:, 0], bad[:, 1]] = True
                self._nonterm_mask = mask
        self._in_rank, self._D = _in_edge_ranks(topo)
        self._init_tx = np.broadcast_to(
            np.asarray(self.queues.initial_tx, np.int32), (L,))
        self._compiled: dict[tuple, "CompiledFabric"] = {}
        self._plan_memo: tuple | None = None  # (spec, max_steps, plan)
        #: per-epoch breakdown of the last epoched run (AdaptiveReport)
        self.last_report = None
        #: execution path the last ``run_many`` chose: "batch" | "loop"
        self.last_dispatch = None
        # in-fabric multicast setup caches: trees are a pure function of
        # (routing table, multicast table, src, tag) — all fixed per
        # Fabric — and the unicast replication tables of the routing
        # table alone
        self._tree_cache: dict[tuple[int, int], MulticastTree] = {}
        self._unicast_tables_np: tuple | None = None

    # --- declaration niceties ------------------------------------------

    @property
    def n_chips(self) -> int:
        return self.topo.n_chips

    @property
    def n_links(self) -> int:
        return self.topo.n_links

    @property
    def compiled_buckets(self) -> tuple[tuple, ...]:
        """Shape buckets this fabric has bound so far (compile order)."""
        return tuple(self._compiled)

    def __repr__(self) -> str:
        return (f"Fabric({self.topo.name}: {self.n_chips} chips, "
                f"{self.n_links} links, engine={self.engine.resolved!r}, "
                f"{len(self._compiled)} compiled bucket(s))")

    # --- lifecycle ------------------------------------------------------

    def verify(self, spec: TrafficSpec | None = None, *,
               max_steps: int | None = None):
        """Static pre-flight verification — prove properties, run nothing.

        Builds the channel-dependency graph of this fabric's routes
        (unicast + in-fabric multicast branchings), runs Dally–Seitz
        cycle detection, checks route termination / reachability /
        replication-table completeness, and bounds the worst-case int32
        clock against the ``BIG_NS`` sentinel (tight per-link budget).
        With ``spec`` the deadlock grading is demand-aware: a CDG cycle
        is an error only if every channel on some cycle can actually
        fill to capacity under the spec's routed traffic.

        Returns a :class:`repro.analysis.verify.VerifyReport`;
        ``report.raise_if_failed()`` turns error findings into the same
        ``ValueError`` refusal contract construction/planning uses.
        """
        from ..analysis.verify import verify_fabric
        return verify_fabric(self, spec, max_steps=max_steps)

    def compile(self, spec: TrafficSpec, *, max_steps: int | None = None,
                warm: bool = True) -> "CompiledFabric":
        """Bind the shape bucket that ``spec`` needs and return it.

        With ``warm=True`` (default) the bucket's XLA compilation is
        triggered immediately by a zero-event dummy run, so a subsequent
        ``run`` of any spec in the bucket pays zero compile time — the
        pre-warm hook a latency-sensitive serving loop wants.
        """
        plan = self._plan(spec, max_steps)
        cf = self._get_compiled(plan.bucket)
        if warm:
            cf.warmup()
        return cf

    def run(self, spec: TrafficSpec, *,
            max_steps: int | None = None) -> FabricResult:
        """Simulate one traffic spec (compiling its bucket on first use).

        Under an :class:`~repro.core.adaptive.AdaptiveRouting` policy the
        run is automatically split into the policy's epochs, telemetry
        re-weights the tables between them, and the merged result comes
        back (per-epoch breakdown on ``self.last_report``)."""
        from .adaptive import AdaptiveRouting, run_epoched
        with tracing.span("run"):
            if isinstance(self.routing_policy, AdaptiveRouting):
                return run_epoched(self, spec,
                                   epochs=self.routing_policy.epochs,
                                   max_steps=max_steps,
                                   policy=self.routing_policy)
            return self._run_single(spec, max_steps=max_steps)

    def run_epochs(self, spec: TrafficSpec, *, epochs: int,
                   max_steps: int | None = None) -> FabricResult:
        """Epoch-partitioned run under this fabric's own routing policy.

        With a static policy every epoch reuses the same tables — the
        fair A/B baseline for adaptive runs (identical partitioning,
        per-epoch drain and merge; only the tables differ).  With an
        adaptive policy, ``epochs`` overrides the policy's own epoch
        count.  Per-epoch breakdown lands on ``self.last_report``."""
        from .adaptive import AdaptiveRouting, run_epoched
        pol = (self.routing_policy
               if isinstance(self.routing_policy, AdaptiveRouting)
               else None)
        return run_epoched(self, spec, epochs=epochs,
                           max_steps=max_steps, policy=pol)

    def _run_single(self, spec: TrafficSpec, *,
                    max_steps: int | None = None) -> FabricResult:
        """One un-epoched simulation (the epoch loop's inner call)."""
        plan = self._plan(spec, max_steps)
        return self._get_compiled(plan.bucket)._execute(plan)

    def _with_routing(self, table: RoutingTable) -> "Fabric":
        """Clone with prebuilt routing tables — the adaptive control
        plane's per-epoch rebuild path.  Unicast tables come straight
        from ``table``; in-fabric multicast Steiner branchings regrow on
        it too (the clone's tree cache starts empty).  Compilations are
        shared process-wide by engine shape bucket, so a clone never
        recompiles an engine the original already traced."""
        return Fabric(self.topo, routing=PrebuiltRouting(table),
                      timing=self.timing, queues=self.queues,
                      engine=self.engine, addr=self.addr,
                      mcast=self.mcast_policy)

    def run_many(self, specs, *,
                 max_steps: int | None = None) -> list[FabricResult]:
        """Run a sequence of specs, amortising work across them.

        Dispatch (recorded on ``self.last_dispatch``): when every spec
        lands in ONE shape bucket and the routing policy is static, the
        whole sequence executes as a single batched computation via
        :meth:`run_batch` — one compilation AND one dispatch for the
        entire sweep (``"batch"``).  Otherwise — mixed buckets, an
        adaptive policy (a sequential feedback loop), or a single spec —
        it falls back to the per-spec loop (``"loop"``), which still
        amortises compiles across specs that bucket alike.

        Batch-path caveat: with ``max_steps=None`` the batch shares the
        max of the per-spec default step bounds.  That is bit-exact with
        solo runs whenever each run drains (the bound does not bind) —
        the universal case, since lossless-mode traffic on broken
        routes is refused at plan time (cyclic-CDG tables already at
        construction) and drop-mode routes always terminate.  Pass an
        explicit ``max_steps`` to pin the bound.
        """
        from .adaptive import AdaptiveRouting
        specs = list(specs)
        if (len(specs) > 1
                and not isinstance(self.routing_policy, AdaptiveRouting)):
            plans = [self._plan(s, max_steps) for s in specs]
            if len(dict.fromkeys(p.bucket for p in plans)) == 1:
                self.last_dispatch = "batch"
                return self.run_batch(specs,
                                      max_steps=max_steps).results()
        self.last_dispatch = "loop"
        return [self.run(s, max_steps=max_steps) for s in specs]

    def run_batch(self, specs, *, max_steps: int | None = None,
                  devices: int | str | None = None) -> FabricBatchResult:
        """Run B traffic specs as ONE batched computation on this fabric.

        Every spec must land in the same shape bucket (same topology by
        construction — one ``Fabric`` — and pow2-compatible event
        counts); the batch compiles once per (bucket, B, devices) and
        executes as a single device dispatch, with every per-instance
        quantity (traffic, replication tables, capacity, flow mode, step
        bound) travelling as a ``(B,)``-leading operand.  Results are
        bit-exact with ``[self.run(s) for s in specs]`` per instance on
        every engine.  To batch across *fabrics* (per-instance routing
        tables / timing contracts on one topology), use the module-level
        :func:`run_batch`.

        ``devices`` shards the batch axis across local devices via
        ``shard_map``: an int (count), ``"all"``, or ``None`` (no
        sharding).  B must divide evenly.

        With ``max_steps=None`` all instances share the max of their
        default step bounds (the slot engines bake the bound into their
        scan); a non-binding bound is invisible in the results, keeping
        solo bit-exactness.  Adaptive routing policies are refused —
        their epoch loop is sequential feedback (see ``run_epochs``).
        """
        return run_batch(self, specs, max_steps=max_steps,
                         devices=devices)

    def sweep_batch(self, specs, *, max_steps: int | None = None,
                    warm: bool = True,
                    devices: int | str | None = None) -> BatchSweepCell:
        """:meth:`run_batch` with wall-clock: optionally pre-warms the
        batched engine with a zero-event dummy batch of the same size
        (so compile time stays out of the measurement), then times the
        single batched dispatch.  ``us_per_instance`` is the amortised
        per-fabric cost — the number to compare against a sequential
        ``sweep``'s ``us_per_call``."""
        specs = list(specs)
        fabs = [self] * len(specs)
        plans = _plan_batch(fabs, specs, max_steps)
        n_dev = _resolve_devices(devices, len(plans))
        if warm:
            zero = _zero_event_plan(self, plans[0].bucket)
            dummy = _execute_batch(fabs, [zero] * len(plans), n_dev)
            jax.block_until_ready(dummy.drops)
        t0 = time.perf_counter()
        res = _execute_batch(fabs, plans, n_dev)
        jax.block_until_ready(res.log_del)
        us = (time.perf_counter() - t0) * 1e6
        return BatchSweepCell(result=res, us_per_call=us,
                              us_per_instance=us / max(len(plans), 1),
                              bucket=plans[0].bucket)

    def sweep(self, specs, *, max_steps: int | None = None,
              warm: bool = True) -> list[SweepCell]:
        """``run_many`` with per-cell wall-clock: pre-warms every distinct
        bucket first (unless ``warm=False``), then times each run — the
        benchmark-sweep pattern where compile time must not pollute
        per-cell numbers."""
        from .adaptive import (AdaptiveRouting, partition_epochs,
                               shared_max_steps)
        if isinstance(self.routing_policy, AdaptiveRouting):
            # the epoch loop owns execution: time whole epoched runs
            # (merge already synchronises, so the clock is honest).
            # warm=True honours the no-compile-in-cell contract here
            # too: each spec's FIRST epoch slice is compiled untimed
            # under the SAME shared step bound the epoched run will use
            # (the slot engines key their bucket on max_steps), so the
            # warmed bucket is exactly the one every epoch hits.
            bounds = {}
            if warm:
                for i, s in enumerate(specs):
                    parts = partition_epochs(
                        s, self.routing_policy.epochs)
                    if parts:
                        bounds[i] = (max_steps if max_steps is not None
                                     else shared_max_steps(
                                         self, parts,
                                         detour_factor=1.0 + float(
                                             self.routing_policy.alpha)))
                        self.compile(parts[0], max_steps=bounds[i])
            cells = []
            for i, s in enumerate(specs):
                t0 = time.perf_counter()
                # reuse the warm pass's step bound so the epoch loop
                # does not recompute it (and provably runs the warmed
                # bucket)
                res = self.run(s, max_steps=bounds.get(i, max_steps))
                us = (time.perf_counter() - t0) * 1e6
                cells.append(SweepCell(
                    result=res, us_per_call=us,
                    bucket=self.last_report.buckets[0]))
            return cells
        plans = [self._plan(s, max_steps) for s in specs]
        if warm:
            for b in dict.fromkeys(p.bucket for p in plans):
                self._get_compiled(b).warmup()
        cells = []
        for p in plans:
            t0 = time.perf_counter()
            res = self._get_compiled(p.bucket)._execute(p)
            jax.block_until_ready(res.log_del)
            us = (time.perf_counter() - t0) * 1e6
            cells.append(SweepCell(result=res, us_per_call=us,
                                   bucket=p.bucket))
        return cells

    # --- internals ------------------------------------------------------

    def _get_compiled(self, bucket: tuple) -> "CompiledFabric":
        cf = self._compiled.get(bucket)
        if cf is None:
            cf = CompiledFabric(self, bucket)
            self._compiled[bucket] = cf
        return cf

    def _plan(self, spec: TrafficSpec, max_steps: int | None) -> _Plan:
        # memoize the last plan by spec identity: the documented
        # compile(spec) -> run(spec) lifecycle (and repeated runs of one
        # spec) pays the setup-time numpy (expansion, route walking,
        # prefill) once, not per call
        with tracing.span("plan") as sp:
            memo = self._plan_memo
            hit = (memo is not None and memo[0] is spec
                   and memo[1] == max_steps)
            if hit:
                plan = memo[2]
            else:
                plan = self._plan_impl(spec, max_steps)
                self._plan_memo = (spec, max_steps, plan)
            sp.stat(events=plan.E, memo=int(hit))
        return plan

    def _unicast_tables(self):
        if self._unicast_tables_np is None:
            self._unicast_tables_np = _unicast_routes(self.topo,
                                                      self.routing_table)
        return self._unicast_tables_np

    def _tree(self, src: int, tag: int) -> MulticastTree:
        tree = self._tree_cache.get((src, tag))
        if tree is None:
            tree = MulticastTree.build(self.topo, self.routing_table, src,
                                       self.mcast_policy.table.expand(tag))
            self._tree_cache[(src, tag)] = tree
        return tree

    def _route_in_fabric(self, spec: TrafficSpec):
        """Setup for ``MulticastPolicy("in_fabric")``: split unicast from
        tagged events, build (and cache) one replication tree per unique
        ``(source, tag)`` pair, and emit the per-copy prefill stream —
        one copy per source out-edge of the tree — in original event
        order.  Returns everything ``_plan_impl`` needs."""
        topo, rt = self.topo, self.routing_table
        N = topo.n_chips
        src = np.asarray(spec.src, np.int32)
        t = np.asarray(spec.t, np.int32)
        dest = np.asarray(spec.dest, np.int32)
        if self.addr is not None:
            is_mc = np.asarray(self.addr.is_multicast(dest))
            chip_or_tag, _ = self.addr.unpack(dest)
        else:  # plain chip-id destinations: nothing to replicate
            is_mc = np.zeros(len(dest), bool)
            chip_or_tag = dest
        u_src, u_dest = src[~is_mc], chip_or_tag[~is_mc]
        if np.any(u_src == u_dest):
            raise ValueError("self-addressed events (src == dest)")
        _check_reachable(rt, u_src, u_dest)
        m_src, m_tag = src[is_mc], chip_or_tag[is_mc]
        if len(m_src) and self.mcast_policy.table is None:
            raise ValueError("multicast events but no MulticastTable")

        route_ev = chip_or_tag.astype(np.int64)   # unicast route = dest
        n_copies = np.ones(len(src), np.int64)    # prefill copies/event
        fanout_ev = np.ones(len(src), np.int64)   # deliveries/event
        if len(m_src):
            pairs, inv = np.unique(np.stack([m_src, m_tag], 1), axis=0,
                                   return_inverse=True)
            trees = [self._tree(int(s), int(g)) for s, g in pairs]
            tree_counts = np.bincount(inv, minlength=len(trees))
            roots = [tr.edges[tr.parent < 0] for tr in trees]
            root_qs = [(e[:, 1] * 2 + e[:, 2]).astype(np.int64)
                       for e in roots]
            route_ev[is_mc] = N + inv
            n_copies[is_mc] = np.array([len(q) for q in root_qs],
                                       np.int64)[inv]
            fanout_ev[is_mc] = np.array([tr.fanout for tr in trees],
                                        np.int64)[inv]
        else:
            trees, tree_counts, root_qs, inv = [], np.zeros(0, np.int64), \
                [], np.zeros(0, np.int64)

        # per-copy prefill stream, original event order (a tagged event's
        # source out-edges stay in tree-edge order)
        ev_idx = np.repeat(np.arange(len(src)), n_copies)
        is_mc_copy = is_mc[ev_idx]
        grp = np.empty(len(ev_idx), np.int64)
        grp[~is_mc_copy] = _first_hop_queues(rt, u_src, u_dest)
        if len(m_src):
            grp[is_mc_copy] = np.concatenate([root_qs[j] for j in inv])
        expected = int(fanout_ev.sum())   # 1/unicast + fanout/tagged
        total_tx = int(rt.hops[u_src, u_dest].sum()) + int(
            sum(tr.n_edges * int(c) for tr, c in zip(trees, tree_counts)))
        return (grp, t[ev_idx], route_ev[ev_idx].astype(np.int32),
                t[ev_idx], u_src, u_dest, trees, tree_counts,
                expected, total_tx)

    def _plan_impl(self, spec: TrafficSpec, max_steps: int | None) -> _Plan:
        topo, rt = self.topo, self.routing_table
        L = topo.n_links
        if self.mcast_policy.mode == "in_fabric":
            (grp, copy_t, copy_route, copy_inj, u_src, u_dest, trees,
             tree_counts, E, total_tx) = self._route_in_fabric(spec)
            route_out, route_del, route_wt = _routes_with_trees(
                topo, rt, trees)
        else:
            src, t, dest = _expand(spec, self.addr, self.mcast)
            if np.any(src == dest):
                raise ValueError("self-addressed events (src == dest)")
            # validate before route walking (_stream_quota follows paths)
            _check_reachable(rt, src, dest)
            route_out, route_del, route_wt = self._unicast_tables()
            grp = _first_hop_queues(rt, src, dest)
            copy_t = copy_inj = t
            copy_route = dest
            u_src, u_dest, trees, tree_counts = src, dest, [], []
            E = len(src)
            total_tx = int(rt.hops[src, dest].sum())
        if L == 0 or E == 0:
            raise ValueError("need at least one link and one event")
        # quarantined route pairs (broken walks admitted at construction
        # because the remaining CDG is acyclic): lossless flow refuses
        # traffic that would ride them — those events can never be
        # delivered, and their stall chain would wedge the run
        if self._nonterm_mask is not None:
            hit = self._nonterm_mask[u_src, u_dest]
            if np.any(hit):
                pairs = np.unique(np.stack([u_src[hit], u_dest[hit]], 1),
                                  axis=0)
                shown = ", ".join(f"{c}->{d}"
                                  for c, d in pairs[:4].tolist())
                raise ValueError(
                    f"traffic addresses quarantined route pair(s) "
                    f"{shown} whose walk never reaches the destination "
                    f"(next-hop cycle or dead-end); "
                    f"flow={self.queues.flow!r} would deadlock on them "
                    f"— re-route those events or use flow='drop'")

        # flow-control scalars: all dynamic operands, so switching between
        # drop/credit/onoff (or sweeping the capacity) NEVER adds a
        # compilation bucket for a fixed fabric shape
        cap_opt = self.queues.capacity
        cap = int(cap_opt) if cap_opt is not None else max(E, 1)
        fc = FLOW_MODES.index(self.queues.flow)
        xon = (int(self.queues.xon) if self.queues.xon is not None
               else (cap // 2 if fc == 2 else 0))
        # prefill overflow check: in drop mode the logical budget binds
        # the initial backlog too; the lossless modes legitimately buffer
        # above ``cap`` at the source (the gate throttles draining, not
        # buffering), so only the physical width binds there
        chk = cap if fc == 0 else max(E, 1)
        # physical slot width: always the expanded event count, so the
        # capacity stays OUT of the slot engines' shape bucket (extra
        # columns beyond the logical budget hold the BIG_NS sentinel —
        # semantically inert in drop mode, headroom in stall modes)
        C = max(E, 1)
        if max_steps is None:
            max_steps = 4 * total_tx + 2 * E + 64 * (rt.diameter + 2)
        # int32 clock budget vs the BIG_NS sentinel: charge each link
        # only the transmissions that actually cross it (tight bound —
        # slow links no longer tax traffic that avoids them); fall back
        # to the global worst-cost bound when a broken table defeats
        # the route walk (drop mode admits cyclic tables)
        t_max = int(copy_t.max(initial=0))
        link_tx, walk_ok = _route_link_tx(rt, topo.links, u_src, u_dest,
                                          L, topo.n_chips)
        if walk_ok:
            for tr, cnt in zip(trees, tree_counts):
                if tr.n_edges:
                    np.add.at(link_tx, tr.edges[:, 1], int(cnt))
            _overflow_guard_routed(t_max, link_tx, self._link_cost)
        else:
            _overflow_guard(t_max, total_tx, self._worst_cost)
        R, K = route_out.shape[1], route_out.shape[2]

        eng = self.engine.resolved
        if eng == "ring":
            quota = _stream_quota(rt, topo.links, self._in_rank, u_src,
                                  u_dest, L, self._D)
            if trees:
                quota = quota + _tree_stream_quota(trees, tree_counts,
                                                   self._in_rank, L,
                                                   self._D)
            qt, qd, qi, sizes = _prefill(L, grp, copy_t, copy_route,
                                         copy_inj, chk, width="auto")
            # Bucketed shapes (+1 = always-BIG_NS pad column for
            # head/tail gathers); logical E / C / max_burst / max_steps
            # and the timing vectors stay dynamic so cells share
            # compiles.  The replication-table dims (routes, branch
            # bound) are bucketed too, so ``source_expand`` (R = N,
            # K = 1) and a moderate ``in_fabric`` tree population land
            # in the SAME bucket and share one compilation.  The K
            # floor applies only to multicast-capable fabrics (a table
            # is declared): a pure-unicast fabric keeps the historical
            # single append lane per link on its hot path.
            k_floor = _RING_K_FLOOR if self.mcast_policy.table is not None \
                else 1
            C0 = qt.shape[2]
            Cf = _pow2ceil(max(int(quota.max(initial=1)),
                               _RING_STREAM_FLOOR)) + 1
            bucket = ("ring",
                      _pow2ceil(max(L, _RING_L_FLOOR)),
                      _pow2ceil(max(topo.n_chips, _RING_N_FLOOR)),
                      _pow2ceil(max(E, _RING_E_FLOOR)),
                      C0,
                      _pow2ceil(max(self._D, _RING_D_FLOOR)),
                      Cf,
                      _pow2ceil(max(R, _RING_R_FLOOR)),
                      _pow2ceil(max(K, k_floor)),
                      int(self.engine.chunk_size))
        else:
            qt, qd, qi, sizes = _prefill(L, grp, copy_t, copy_route,
                                         copy_inj, chk, width=C)
            # the slot engines bake max_steps/max_burst into the scan, so
            # they key the bucket too (R/K only shape the table operands).
            # The kernel choice is appended LAST so the positional
            # accesses above it stay stable; chunk keys the bucket only
            # for the multi-step kernel (it is baked into the fused
            # launch) — the per-step kernels ignore chunk_size, so
            # sweeping it never adds a step-kernel bucket.
            kern = self.engine.kernel if eng == "pallas" else "step"
            chunk = (int(self.engine.chunk_size) if kern == "multistep"
                     else 0)
            bucket = (eng, L, E, C, int(max_steps),
                      int(self.queues.max_burst), R, K, kern, chunk)
        return _Plan(E=E, C=C, max_steps=int(max_steps), q_time=qt,
                     q_dest=qd, q_inj=qi, sizes=sizes,
                     route_out=route_out, route_del=route_del,
                     route_wt=route_wt, offered=spec.n_events,
                     bucket=bucket, cap=cap, fc=fc, xon=xon)


class CompiledFabric:
    """A :class:`Fabric` bound to ONE engine shape bucket.

    The bucket is the static shape signature the engines compile for
    (pow2-padded link/event/queue dimensions for the ring engine; exact
    shapes plus the scan length for the slot engines).  Everything else —
    traffic, capacity, burst bound, step bound, per-link timing — travels
    as dynamic operands, so every ``run`` on the same bucket reuses one
    XLA executable.  ``cache_size()`` exposes the underlying jit entry
    count; a hot serving path can assert it stays flat.
    """

    def __init__(self, fabric: Fabric, bucket: tuple):
        self.fabric = fabric
        self.bucket = bucket
        self.n_runs = 0
        topo, rt = fabric.topo, fabric.routing_table
        L = topo.n_links
        tc, tv, ti = fabric.timing_arrays
        eng = bucket[0]
        if eng == "ring":
            _, Lp, Np, _Ep, C0, Dp, Cf, _Rp, _Kp, chunk = bucket
            self._fn = _ring_engine(Lp, _Ep, C0, Dp, Cf, chunk)
            # static gather tables + timing vectors, padded once per
            # bucket (dummy links park forever: empty queues, zero-cost
            # timing — semantically inert); the replication tables are
            # per-plan operands (they carry the spec's multicast trees)
            # and are padded in _execute
            self._tables = (
                jnp.asarray(_pad_to(fabric._init_tx, (Lp,), 1)),
                jnp.asarray(_pad_to(topo.links, (Lp, 2), 0), jnp.int32),
                jnp.asarray(_pad_to(fabric._in_rank, (Lp, 2), 0),
                            jnp.int32),
                jnp.asarray(_pad_to(tc, (Lp,), 0)),
                jnp.asarray(_pad_to(tv, (Lp,), 0)),
                jnp.asarray(_pad_to(ti, (Lp,), 0)),
            )
        else:
            _, _L, E, C, max_steps, mb, _R, _K, kern, chunk = bucket
            if kern == "multistep":
                self._fn = _slot_engine_multistep(L, E, C, max_steps, mb,
                                                  chunk)
            else:
                self._fn = _slot_engine(L, E, C, max_steps, mb,
                                        eng == "pallas")
            self._tables = (
                jnp.asarray(fabric._init_tx),
                jnp.asarray(topo.links, jnp.int32),
                jnp.asarray(tc), jnp.asarray(tv), jnp.asarray(ti),
            )
        self._warmed = False

    @property
    def engine_name(self) -> str:
        return self.bucket[0]

    def __repr__(self) -> str:
        return (f"CompiledFabric(engine={self.engine_name!r}, "
                f"bucket={self.bucket}, runs={self.n_runs})")

    def cache_size(self) -> int:
        """Entries in the underlying jit cache (-1 when unavailable).

        One entry per traced shape signature; a second ``run`` on this
        bucket must leave it unchanged — the no-recompile contract."""
        fn = self._fn
        try:
            return int(fn._cache_size())
        except AttributeError:  # pragma: no cover - older/newer jax
            return -1

    def run(self, spec: TrafficSpec, *,
            max_steps: int | None = None) -> FabricResult:
        """Run one spec, refusing specs that fall outside this bucket."""
        plan = self.fabric._plan(spec, max_steps)
        if plan.bucket != self.bucket:
            raise ValueError(
                f"spec needs shape bucket {plan.bucket} but this "
                f"CompiledFabric is bound to {self.bucket}; use "
                f"Fabric.run (auto-routes) or Fabric.compile the new "
                f"bucket")
        return self._execute(plan)

    def warmup(self) -> "CompiledFabric":
        """Trigger this bucket's XLA compilation with a zero-event run.

        The dummy run offers no traffic (all queue slots hold the
        ``BIG_NS`` sentinel, zero logical events).  On the ring engine —
        the hot path this hook exists for — the early-exit condition
        holds immediately, so the cost is one compilation plus
        microseconds of execution.  The slot engines have no early exit
        (``max_steps`` is baked into their scan), so their dummy run
        executes the full-length scan of settled no-op steps; compile
        time still dominates, but latency-critical slot-engine users may
        prefer ``warm=False``.  Idempotent."""
        if self._warmed:
            return self
        # a zero-event plan through the one real marshalling path
        # (_execute), so the engine call signature lives in one place
        L = self.fabric.topo.n_links
        N = self.fabric.topo.n_chips
        if self.bucket[0] == "ring":
            width = self.bucket[4]
            R, K = N, 1         # _execute pads to the bucket's (Rp, Kp)
        else:
            width = self.bucket[3]
            R, K = self.bucket[6], self.bucket[7]
        qt = np.full((L, 2, width), int(_BIG), np.int32)
        z = np.zeros((L, 2, width), np.int32)
        n_runs = self.n_runs
        res = self._execute(_Plan(
            E=0, C=width, max_steps=0, q_time=qt, q_dest=z, q_inj=z,
            sizes=np.zeros((L, 2), np.int32),
            route_out=np.full((N, R, K), -1, np.int32),
            route_del=np.zeros((N, R), np.int32),
            route_wt=np.zeros((N, R, K), np.int32),
            offered=0, bucket=self.bucket, cap=width, fc=0, xon=0))
        jax.block_until_ready(res.drops)
        self.n_runs = n_runs  # the dummy run is not a user run
        self._warmed = True
        return self

    def _execute(self, plan: _Plan) -> FabricResult:
        fab = self.fabric
        E, L = plan.E, fab.topo.n_links
        mb = int(fab.queues.max_burst)
        ring = self.bucket[0] == "ring"
        with tracing.span("marshal", instances=1) as sp:
            if ring:
                _, Lp, Np, Ep, C0, _Dp, _Cf, Rp, Kp, _chunk = self.bucket
                host = (_pad_to(plan.q_time, (Lp, 2, C0), int(_BIG)),
                        _pad_to(plan.q_dest, (Lp, 2, C0), 0),
                        _pad_to(plan.q_inj, (Lp, 2, C0), 0),
                        _pad_to(plan.sizes, (Lp, 2), 0),
                        _pad_to(plan.route_out, (Np, Rp, Kp), -1),
                        _pad_to(plan.route_del, (Np, Rp), 0),
                        _pad_to(plan.route_wt, (Np, Rp, Kp), 0))
                scalars = (plan.cap, E, mb, plan.max_steps, plan.fc,
                           plan.xon)
            else:
                C = plan.C
                host = (np.asarray(plan.q_time).reshape(2 * L, C),
                        np.asarray(plan.q_dest).reshape(2 * L, C),
                        np.asarray(plan.q_inj).reshape(2 * L, C),
                        plan.sizes, plan.route_out, plan.route_del,
                        plan.route_wt)
                scalars = (plan.cap, plan.fc, plan.xon)
            arrays = [jnp.asarray(a) for a in host]
            # both engines take (traffic, polarity, links, replication
            # tables, [in-edge ranks,] timing, scalars)
            tabs = self._tables
            operands = (*arrays[:4], *tabs[:2], *arrays[4:], *tabs[2:],
                        *(jnp.int32(v) for v in scalars))
            sp.stat(bytes=sum(np.asarray(a).nbytes for a in host)
                    + 4 * len(scalars))
        with tracing.span("dispatch", instances=1) as sp:
            n_cached = self.cache_size()
            out = self._fn(*operands)
            if ring:
                (log_n, log_inj, log_del, log_dest, sent, n_sw, t_link,
                 drops, busy_ns, busy_steps, q_drops, stall_steps,
                 credit_waits, steps) = out
                # trim the shape-bucket padding back to the real fabric
                log_inj, log_del, log_dest = (log_inj[:E], log_del[:E],
                                              log_dest[:E])
                sent, n_sw, t_link = sent[:L], n_sw[:L], t_link[:L]
                busy_ns, busy_steps, q_drops = (busy_ns[:L],
                                                busy_steps[:L],
                                                q_drops[:L])
                stall_steps, credit_waits = (stall_steps[:L],
                                             credit_waits[:L])
                t_end = jnp.max(t_link)
                # the engine counts whole chunks; the last may stop at
                # the bound
                steps = jnp.minimum(steps, plan.max_steps)
            else:
                (log_n, log_inj, log_del, log_dest, sent, n_sw, t_link,
                 t_end, drops, busy_ns, busy_steps, q_drops, stall_steps,
                 credit_waits) = out
                # the slot engines scan their whole static length
                steps = np.int32(self.bucket[4])
            sp.stat(compiled=int(self.cache_size() > n_cached))
        self.n_runs += 1
        self._warmed = True  # first real run compiles the bucket too
        return FabricResult(
            delivered=log_n, injected=E,
            log_inj=log_inj, log_del=log_del, log_dest=log_dest,
            sent=sent, n_switches=n_sw,
            t_link=t_link, t_end=t_end, drops=drops,
            offered=plan.offered,
            telemetry=Telemetry(busy_ns=busy_ns, busy_steps=busy_steps,
                                q_drops=q_drops, stall_steps=stall_steps,
                                credit_waits=credit_waits),
            steps=steps)


# -----------------------------------------------------------------------
# Batched execution: B fabric instances as ONE compiled computation
# -----------------------------------------------------------------------

def run_batch(fabrics, specs, *, max_steps: int | None = None,
              devices: int | str | None = None) -> FabricBatchResult:
    """Run B (fabric, spec) instances as one batched computation.

    ``fabrics`` is a single :class:`Fabric` (replicated across the
    batch — the Monte-Carlo-over-seeds case) or a sequence of B fabrics
    sharing one topology shape and shape bucket but free to differ in
    routing tables, timing contracts, queue policy scalars and initial
    polarity — every one of those is already a dynamic engine operand,
    so per-instance heterogeneity adds ZERO compilation buckets.  The
    batch compiles once per (bucket, B, devices) signature and runs as
    a single dispatch; each instance's result is bit-exact with its
    solo ``fabric.run(spec)``.

    ``devices``: shard the batch axis across this many local devices
    (``"all"`` = every local device) via ``shard_map``; ``None`` = no
    sharding.  B must be divisible by the device count.
    """
    specs = list(specs)
    if isinstance(fabrics, Fabric):
        fabs = [fabrics] * len(specs)
    else:
        fabs = list(fabrics)
    if len(fabs) != len(specs):
        raise ValueError(f"got {len(fabs)} fabrics for {len(specs)} "
                         f"specs; they must pair 1:1 (or pass a single "
                         f"Fabric to replicate)")
    with tracing.span("run_batch", instances=len(specs)):
        plans = _plan_batch(fabs, specs, max_steps)
        return _execute_batch(fabs, plans,
                              _resolve_devices(devices, len(plans)))


def _plan_batch(fabs: list[Fabric], specs, max_steps: int | None):
    """Per-instance plans under one shared step bound and ONE bucket.

    With ``max_steps=None`` the shared bound is the max over the
    per-spec defaults: the slot engines bake ``max_steps`` into their
    static scan (it keys their bucket), and the ring engine's batch
    drains by early exit anyway — a non-binding bound never changes
    results, so solo bit-exactness survives the sharing.  Ring plans
    just take the shared bound (their bucket ignores it); slot plans
    with a different default are re-planned under it.
    """
    if not specs:
        raise ValueError("run_batch needs at least one instance")
    from .adaptive import AdaptiveRouting
    for f in fabs:
        if isinstance(f.routing_policy, AdaptiveRouting):
            raise NotImplementedError(
                "run_batch under AdaptiveRouting is refused: the epoch "
                "loop is sequential feedback (epoch k's telemetry "
                "re-weights epoch k+1's tables), so instances cannot "
                "fuse into one computation. Run adaptive specs through "
                "Fabric.run / run_epochs; batch the static baseline.")
    L = fabs[0].topo.n_links
    for f in fabs[1:]:
        if f.topo.n_links != L:
            raise ValueError(f"all fabrics in a batch must share the "
                             f"link count, got {f.topo.n_links} vs {L}")
    plans = [f._plan(s, max_steps) for f, s in zip(fabs, specs)]
    if max_steps is None:
        shared = max(p.max_steps for p in plans)
        plans = [p._replace(max_steps=shared) if p.bucket[0] == "ring"
                 else (p if p.max_steps == shared else f._plan(s, shared))
                 for f, s, p in zip(fabs, specs, plans)]
    buckets = dict.fromkeys(p.bucket for p in plans)
    if len(buckets) != 1:
        raise ValueError(
            f"run_batch needs every instance in ONE shape bucket, got "
            f"{list(buckets)}; Fabric.run_many loops mixed buckets")
    return plans


def _resolve_devices(devices: int | str | None, batch: int) -> int:
    """Device count for the batch axis; validates divisibility."""
    if devices is None:
        return 1
    n = jax.local_device_count() if devices == "all" else int(devices)
    if n < 1:
        raise ValueError(f"devices must be >= 1, got {devices!r}")
    if n > jax.local_device_count():
        raise ValueError(f"asked for {n} devices but only "
                         f"{jax.local_device_count()} are local")
    if batch % n:
        raise ValueError(f"batch size {batch} is not divisible by "
                         f"{n} devices (shard_map needs equal shards)")
    return n


def _zero_event_plan(fab: Fabric, bucket: tuple) -> _Plan:
    """The zero-event dummy plan ``warmup`` runs (every queue slot holds
    the ``BIG_NS`` sentinel, zero logical events) — here as a batch
    pre-warm instance."""
    L, N = fab.topo.n_links, fab.topo.n_chips
    if bucket[0] == "ring":
        width = bucket[4]
        R, K = N, 1         # _execute_batch pads to the bucket's (Rp, Kp)
    else:
        width = bucket[3]
        R, K = bucket[6], bucket[7]
    qt = np.full((L, 2, width), int(_BIG), np.int32)
    z = np.zeros((L, 2, width), np.int32)
    return _Plan(E=0, C=width, max_steps=0, q_time=qt, q_dest=z, q_inj=z,
                 sizes=np.zeros((L, 2), np.int32),
                 route_out=np.full((N, R, K), -1, np.int32),
                 route_del=np.zeros((N, R), np.int32),
                 route_wt=np.zeros((N, R, K), np.int32),
                 offered=0, bucket=bucket, cap=width, fc=0, xon=0)


def _batch_engine_for(bucket: tuple, n_devices: int):
    """The lru-cached batched engine bound to one shape bucket."""
    if bucket[0] == "ring":
        _, Lp, _Np, Ep, C0, Dp, Cf, _Rp, _Kp, chunk = bucket
        return _ring_engine_batch(Lp, Ep, C0, Dp, Cf, chunk, n_devices)
    eng, L, E, C, ms, mb, _R, _K, kern, chunk = bucket
    if kern == "multistep":
        return _slot_engine_multistep_batch(L, E, C, ms, mb, chunk,
                                            n_devices)
    return _slot_engine_batch(L, E, C, ms, mb, eng == "pallas", n_devices)


def batch_cache_size(bucket: tuple, n_devices: int = 1) -> int:
    """Entries in the batched engine's jit cache for ``bucket`` (-1 when
    unavailable) — the batch path's no-recompile audit: one entry per
    traced (B, operand-shape) signature, so a repeated same-size batch
    must leave it unchanged (asserted by tests and the CI batch gate)."""
    fn = _batch_engine_for(bucket, n_devices)
    try:
        return int(fn._cache_size())
    except AttributeError:  # pragma: no cover - older/newer jax
        return -1


def _execute_batch(fabs: list[Fabric], plans: list[_Plan],
                   n_devices: int) -> FabricBatchResult:
    """Marshal B plans into (B,)-leading operands and run the batched
    engine — the batch mirror of ``CompiledFabric._execute``.  Static
    per-bucket tables (polarity, link endpoints, in-edge ranks, timing
    vectors) come from each instance's ``CompiledFabric`` (reusing its
    padding work and keeping the solo and batch paths marshalling-
    identical); stacking them per instance is what lets one batch mix
    timing contracts and polarities across fabrics."""
    bucket = plans[0].bucket
    fn = _batch_engine_for(bucket, n_devices)
    L = fabs[0].topo.n_links
    B = len(plans)
    ring = bucket[0] == "ring"
    with tracing.span("marshal", instances=B) as sp:
        tabs = [f._get_compiled(bucket)._tables for f in fabs]
        host = []       # every host array handed to the device

        def put(a):
            host.append(a)
            return jnp.asarray(a)

        def stk(i):
            return jnp.stack([t[i] for t in tabs])

        def per(fn):
            return jnp.stack([put(fn(p)) for p in plans])

        def vec(xs):
            return put(np.asarray(list(xs), np.int32))

        if ring:
            _, Lp, Np, _Ep, C0, _Dp, _Cf, Rp, Kp, _chunk = bucket
            arrays = (
                per(lambda p: _pad_to(p.q_time, (Lp, 2, C0), int(_BIG))),
                per(lambda p: _pad_to(p.q_dest, (Lp, 2, C0), 0)),
                per(lambda p: _pad_to(p.q_inj, (Lp, 2, C0), 0)),
                per(lambda p: _pad_to(p.sizes, (Lp, 2), 0)),
                per(lambda p: _pad_to(p.route_out, (Np, Rp, Kp), -1)),
                per(lambda p: _pad_to(p.route_del, (Np, Rp), 0)),
                per(lambda p: _pad_to(p.route_wt, (Np, Rp, Kp), 0)))
            scalars = (
                vec(p.cap for p in plans), vec(p.E for p in plans),
                vec(int(f.queues.max_burst) for f in fabs),
                # shared scalar step bound (aligned by _plan_batch) — the
                # batched runner keeps its chunk bookkeeping unbatched
                put(np.int32(max(p.max_steps for p in plans))),
                vec(p.fc for p in plans), vec(p.xon for p in plans))
        else:
            C = plans[0].C
            arrays = (
                per(lambda p: np.asarray(p.q_time).reshape(2 * L, C)),
                per(lambda p: np.asarray(p.q_dest).reshape(2 * L, C)),
                per(lambda p: np.asarray(p.q_inj).reshape(2 * L, C)),
                per(lambda p: p.sizes), per(lambda p: p.route_out),
                per(lambda p: p.route_del), per(lambda p: p.route_wt))
            scalars = (vec(p.cap for p in plans), vec(p.fc for p in plans),
                       vec(p.xon for p in plans))
        n_tabs = len(tabs[0])
        operands = (*arrays[:4], stk(0), stk(1), *arrays[4:],
                    *(stk(i) for i in range(2, n_tabs)), *scalars)
        sp.stat(bytes=sum(np.asarray(a).nbytes for a in host))
    with tracing.span("dispatch", instances=B) as sp:
        n_cached = batch_cache_size(bucket, n_devices)
        out = fn(*operands)
        if ring:
            (log_n, log_inj, log_del, log_dest, sent, n_sw, t_link, drops,
             busy_ns, busy_steps, q_drops, stall_steps, credit_waits,
             steps) = out
            e_max = max((p.E for p in plans), default=0)
            log_inj, log_del, log_dest = (log_inj[:, :e_max],
                                          log_del[:, :e_max],
                                          log_dest[:, :e_max])
            sent, n_sw, t_link = sent[:, :L], n_sw[:, :L], t_link[:, :L]
            busy_ns, busy_steps = busy_ns[:, :L], busy_steps[:, :L]
            q_drops = q_drops[:, :L]
            stall_steps, credit_waits = (stall_steps[:, :L],
                                         credit_waits[:, :L])
            t_end = jnp.max(t_link, axis=1)
            steps = jnp.minimum(steps, max(p.max_steps for p in plans))
        else:
            (log_n, log_inj, log_del, log_dest, sent, n_sw, t_link, t_end,
             drops, busy_ns, busy_steps, q_drops, stall_steps,
             credit_waits) = out
            # the slot engines scan their whole static length
            steps = np.full(B, bucket[4], np.int32)
        sp.stat(compiled=int(batch_cache_size(bucket, n_devices)
                             > n_cached))
    return FabricBatchResult(
        delivered=log_n,
        injected=np.asarray([p.E for p in plans], np.int64),
        log_inj=log_inj, log_del=log_del, log_dest=log_dest,
        sent=sent, n_switches=n_sw, t_link=t_link, t_end=t_end,
        drops=drops,
        offered=np.asarray([p.offered for p in plans], np.int64),
        telemetry=Telemetry(busy_ns=busy_ns, busy_steps=busy_steps,
                            q_drops=q_drops, stall_steps=stall_steps,
                            credit_waits=credit_waits),
        steps=steps)
