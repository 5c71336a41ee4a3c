"""N-chip AER fabric simulator: the paper's link pair, scaled out.

The paper measures ONE bi-directional transceiver pair on one shared AER
bus.  This module composes many such pairs into a multi-chip fabric
(line / ring / 2-D mesh — ``router.Topology``): every link of the fabric
is one paper-faithful ``protocol_sim.LinkState`` micro-transaction unit,
and one global step advances **all** links simultaneously via
``protocol_sim.link_step_batch`` — the LinkSim unit batches across links.

Event transport
---------------
Each link endpoint owns a fixed-capacity queue of
``(release_time, route_id, inject_time)`` entries.  Injected traffic
(``traffic.TrafficSpec``) is routed to its first-hop queue(s) at setup
time (numpy, sorted by time).  A *route id* is either a destination chip
(unicast: ``r < n_chips``) or a multicast replication tree
(``r = n_chips + tree``, in-fabric multicast — see below).  When a link
delivers an event, the receiving chip consults its *replication table*
row ``(chip, route)``: a local-deliver bit plus up to ``K`` out-queues
to copy the event onto.  For unicast routes the table degenerates to the
classic next-hop gather (one out-link everywhere, deliver exactly at the
destination); forwarded copies re-queue with release time equal to their
delivery time — multi-hop latency accumulates exactly.

Multicast events can travel in two modes (``fabric.MulticastPolicy``):

``source_expand`` (default, PR 1 semantics)
    A tag with fanout F becomes F independent unicast copies at the
    source — F traversals of every shared link.

``in_fabric``
    The tagged event carries its route id through the fabric and is
    replicated only where the per-``(source, tag)`` Steiner-branching
    tree (``router.MulticastTree``) diverges: one traversal per tree
    edge.  A replication step can deliver locally AND spawn several
    child events from one pop; drops are weighted by the subtree's
    delivery count so ``delivered + drops == expected`` stays exact.

An entry only *enters* the physical FIFO at its release time, so service
order is release-time order (FIFO among equal times): a forward that has
already arrived is never blocked behind a pre-routed injection that has
not happened yet.  Slots are one-shot (consumed entries are not reused),
so in the default ``"drop"`` flow mode ``queue_capacity`` bounds the
total events *through* an endpoint, not its instantaneous depth; the
lossless default (= expanded event count) can never drop.

Flow control (``fabric.QueuePolicy(flow=...)``)
-----------------------------------------------
The paper's four-phase req/ack handshake is inherently lossless — a
sender stalls until the receiver acks, it never silently discards an
event.  Three flow modes reproduce the design space (all three are a
*dynamic* scalar operand, so they share one compilation per shape):

``"drop"`` (default)
    Today's semantics: a forward into a full queue is discarded and
    counted (``FabricResult.drops``), weighted by the forfeited
    deliveries under in-fabric multicast.

``"credit"``
    Per-link credit counters: every endpoint queue tracks its occupancy
    ``n_ins - n_pop``; a pop whose head would forward into a queue at or
    above ``capacity`` *stalls in place* (the event stays at the stream
    head / slot, backlog telemetry keeps accruing, head-of-line blocking
    is modeled) until a downstream pop returns a credit.  Delivery-only
    pops (all replication targets local) are never gated, so
    convergecast sinks always drain and an acyclic route set cannot
    deadlock.  ``delivered == injected`` with ``drops == 0``.

``"onoff"``
    Threshold xon/xoff: the queue raises ``xoff`` when occupancy
    reaches ``capacity`` and clears it when occupancy falls back to
    ``xon`` (hysteresis) — senders gate on the latched bit rather than
    the instantaneous count.  ``xon = capacity - 1`` degenerates to
    credit mode exactly.

Because several upstream links can pop into one queue in the same
micro-transaction, instantaneous occupancy may transiently overshoot
``capacity`` by at most the chip in-degree; the overshoot is
deterministic and bit-exact across engines.  A *stalled* link is
excluded from the conservative horizon (its next insert is causally
gated on a downstream pop, which the downstream link's own ``na`` term
already bounds) and its parked clock rides the fabric-wide floor
upward, so the eventual transmit time — and therefore the event's
end-to-end latency — includes the full backpressure wait.  Cyclic
route dependency chains (e.g. all-clockwise ring traffic with tiny
capacities) can genuinely deadlock, exactly like real credit-based
fabrics; the step bound then binds and the run reports
``delivered + drops < injected`` instead of hanging.

Clocks are link-local, exactly as in ``protocol_sim.simulate``: a link
whose queues are empty *parks* (its clock holds) and wakes when a forward
lands.  Cross-link causality is kept by conservative lookahead against
the fabric-wide lower bound on future event releases (min over links of
"clock if work is pending, else own next arrival", plus one event cycle
for the insert bound): idle links never jump past it, and a busy link
pops an entry only once no future forward can precede it — so queues
serve in true release order and end-to-end latencies are exact.

Engines
-------
``simulate_fabric`` ships three interchangeable, bit-exact event-transport
engines (select with ``engine=``):

``"ring"`` (default)
    The O(1)-per-step hot path.  Each endpoint queue is decomposed into
    release-time-sorted streams — the static prefill (sorted at setup)
    plus one FIFO stream per in-edge of the chip (a link's delivery clock
    is monotone, so forwards from one link arrive in release order; this
    replaces the tail-insert + local-sift design with something strictly
    stronger: no sift is ever needed).  The per-step pending /
    next-arrival / pop computation then reads only the stream *heads* —
    O(deg) ≈ O(1) slots per endpoint instead of scanning all ``C`` — and
    pops compare ``(release, insertion_key)`` so service order matches
    the flat-slot argmin of the reference engine exactly.  The
    micro-transaction scan runs as chunked ``lax.scan`` inside
    ``lax.while_loop`` and exits within one chunk of
    ``delivered + drops == injected`` instead of padding to
    ``max_steps``, and the whole simulation is compiled once per shape
    signature through a jit cache (stream widths are bucketed to powers
    of two so sweep cells share compilations).

``"reference"``
    The flat one-shot slot-array engine (PR 1): every step re-scans all
    ``L x 2 x C`` slots.  O(max_steps · L · C) — kept verbatim as the
    semantics oracle; every other engine must reproduce its
    ``FabricResult`` bit-exactly.

``"pallas"``
    The reference slot layout with the per-step O(C) queue scan
    (released-count / min-release / next-arrival / argmin-pop) and the
    pop-consume + forward-append scatter fused into the Pallas kernels
    of ``kernels/fabric_queue.py`` (scatter-as-matmul, MXU-shaped; runs
    in interpret mode off-TPU).

When the step bound binds before delivery completes, the chunked ring
engine clamps its final chunk to the steps remaining, so it executes
exactly ``max_steps`` micro-transactions — bit-exact against a
reference scan of the same length (regression-tested in
``tests/test_fabric_engines.py``).

All engines take the timing contract as *dynamic* per-link (L,) cost
vectors (``link.link_timing_arrays``): a scalar ``LinkTiming`` broadcasts
uniformly (bit-exactly equal to the historical static-scalar path), and a
structure-of-arrays ``LinkTiming`` gives every link its own class — e.g.
fast on-board parallel buses next to slow bit-serial LVDS inter-board
links.  The conservative insert bound generalises to
``min(na + t_cycle)`` per link, which degenerates to the uniform
``min(na) + t_cycle`` exactly.

The declarative front door — composable routing/timing/queue/engine
policies with an explicit ``compile``/``run``/``run_many`` lifecycle —
lives in :mod:`repro.core.fabric`; ``simulate_fabric`` below is its
one-shot convenience wrapper.

The degenerate 2-chip fabric runs the identical ``link_step`` code path
with the identical pending/next-arrival semantics as
``protocol_sim.simulate`` and therefore reproduces its event departure
times, switch counts and ``t_end`` bit-exactly (tested in
``tests/test_fabric.py``).

Measurements: per-event latency log, per-link/direction transmission
counts, direction-switch counts, energy roll-up (every hop is one paper
event: ``e_event_pj``), aggregate + per-link throughput — plus the
congestion telemetry plane (:mod:`repro.core.telemetry`): per-link
``busy_ns`` / ``busy_steps`` / ``q_drops`` counters accumulated as scan
carry state inside every engine (bit-exact across engines, zero extra
compilation buckets), surfaced as ``FabricResult.telemetry`` and
consumed by the epoch-based adaptive routing control plane
(:mod:`repro.core.adaptive`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.indexing import XLA_INDEX
from .link import LinkTiming, PAPER_TIMING
from .protocol_sim import BIG_NS, LinkState, link_step_batch, reset_link
from .transceiver import XcvrState
from .router import (AddressSpec, MulticastTable, MulticastTree,
                     RoutingTable, Topology)
from . import tracing
from .telemetry import Telemetry
from .traffic import TrafficSpec

__all__ = ["FabricResult", "FabricBatchResult", "simulate_fabric",
           "reset_links",
           "fabric_throughput_mev_s", "fabric_energy_pj",
           "link_energy_pj",
           "per_link_throughput_mev_s", "delivered_latencies",
           "delivery_multiset", "latency_stats", "batch_latency_stats",
           "batch_throughput_mev_s", "ENGINES",
           "DEFAULT_CHUNK_SIZE", "RESULT_FIELDS", "assert_results_equal"]

_BIG = BIG_NS  # one sentinel shared with link_step's park/wake contract

#: Event-transport engines accepted by ``simulate_fabric(engine=...)``.
ENGINES = ("ring", "reference", "pallas")

#: Micro-transactions per ``lax.scan`` chunk of the ring engine.
DEFAULT_CHUNK_SIZE = 128

# Ring-engine shape buckets.  Every array dimension that would otherwise
# vary cell-to-cell in a sweep (links, events, chip count, queue widths,
# chip degree) is padded up to a floored power of two, and the logical
# event/capacity counts travel as *dynamic* scalars — so one XLA
# compilation serves every (topology, pattern) cell that fits the bucket,
# and the jit cache turns a 19-cell sweep into ~2 compiles.  Padding is
# semantically inert: dummy links have empty queues (they park forever
# and never constrain the conservative horizon), dummy queue slots hold
# the BIG_NS sentinel, and results are trimmed to the real sizes.
_RING_L_FLOOR = 32        # links
_RING_N_FLOOR = 64        # chips (routing-table side)
_RING_D_FLOOR = 4         # chip degree (forward streams per endpoint)
_RING_E_FLOOR = 2048      # expected deliveries (delivery-log length)
_RING_PREFILL_FLOOR = 2048  # prefill queue width
_RING_STREAM_FLOOR = 512  # forward-stream width
_RING_R_FLOOR = 64        # route ids (chips + multicast trees)
_RING_K_FLOOR = 4         # replication branch bound (out-copies per pop)


class FabricResult(NamedTuple):
    delivered: jnp.ndarray   # scalar int32
    injected: int            # static: expected deliveries (post-fanout)
    log_inj: jnp.ndarray     # (E,) valid up to ``delivered``
    log_del: jnp.ndarray
    log_dest: jnp.ndarray
    sent: jnp.ndarray        # (L, 2) per-link/direction transmissions
    n_switches: jnp.ndarray  # (L,) direction switches per link
    t_link: jnp.ndarray      # (L,) final link-local clocks
    t_end: jnp.ndarray       # scalar: max over links
    drops: jnp.ndarray       # scalar (subtree-weighted for in-fabric
    #                          multicast: delivered + drops == injected)
    offered: int = -1        # static: events offered pre-fanout (-1 =
    #                          legacy result without the field)
    telemetry: Telemetry | None = None  # per-link congestion counters
    #                          (accumulated as engine carry state; None
    #                          only on legacy hand-built results)
    steps: jnp.ndarray | int = -1  # micro-transactions the engine
    #                          executed: the ring engine's early exit
    #                          stops at a whole chunk (or ``max_steps``),
    #                          a batch at its slowest instance's; the
    #                          slot engines scan their whole length
    #                          (-1 = legacy result without the field)

    @property
    def traversals(self) -> int:
        """Actual link traversals (sum of per-link transmissions) — the
        quantity in-fabric multicast replication minimizes."""
        return int(np.asarray(self.sent).sum())

    @property
    def fanout(self) -> float:
        """Expected deliveries per offered event (1.0 = pure unicast)."""
        if self.offered <= 0:
            return 1.0
        return float(self.injected) / float(self.offered)


#: FabricResult fields the engines must agree on bit-for-bit (log arrays
#: compared up to ``delivered`` — beyond it is scratch space).
RESULT_FIELDS = ("delivered", "log_inj", "log_del", "log_dest", "sent",
                 "n_switches", "t_link", "t_end", "drops")


def assert_results_equal(a: FabricResult, b: FabricResult, ctx: str = ""):
    """The engines' bit-exactness contract, shared by tests and the CI
    bench smoke so the checked field list cannot drift apart."""
    assert a.injected == b.injected, ctx
    assert a.offered == b.offered, ctx
    n = int(a.delivered)
    for f in RESULT_FIELDS:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        if f.startswith("log"):
            x, y = x[:n], y[:n]
        if not np.array_equal(x, y):
            raise AssertionError(f"{ctx}: engines disagree on field {f}: "
                                 f"{x!r} != {y!r}")
    # the telemetry plane is part of the contract too: when both results
    # carry counters (every engine run does), they must agree bit-for-bit
    if a.telemetry is not None and b.telemetry is not None:
        for f in Telemetry._fields:
            x = np.asarray(getattr(a.telemetry, f))
            y = np.asarray(getattr(b.telemetry, f))
            if not np.array_equal(x, y):
                raise AssertionError(
                    f"{ctx}: engines disagree on telemetry field {f}: "
                    f"{x!r} != {y!r}")


class FabricBatchResult(NamedTuple):
    """Results of B fabric instances executed as ONE batched computation.

    Every array field is the solo :class:`FabricResult` field with a
    leading ``(B,)`` instance axis (telemetry leaves included); the
    static per-instance counters (``injected`` / ``offered``) become
    (B,) numpy vectors.  ``instance(i)`` materialises instance ``i`` as
    an ordinary :class:`FabricResult` — bit-exact with the same spec run
    solo on the same engine (the contract ``Fabric.run_batch`` tests and
    the CI batch gate enforce), so every existing roll-up
    (``latency_stats``, ``link_load``, ``fabric_throughput_mev_s``, ...)
    applies per instance unchanged.  Conservation holds per instance:
    ``delivered[i] + drops[i] == injected[i]``.
    """
    delivered: jnp.ndarray   # (B,) int32
    injected: np.ndarray     # (B,) static: expected deliveries/instance
    log_inj: jnp.ndarray     # (B, E) valid up to ``delivered[i]``
    log_del: jnp.ndarray     # (B, E)
    log_dest: jnp.ndarray    # (B, E)
    sent: jnp.ndarray        # (B, L, 2)
    n_switches: jnp.ndarray  # (B, L)
    t_link: jnp.ndarray      # (B, L)
    t_end: jnp.ndarray       # (B,)
    drops: jnp.ndarray       # (B,)
    offered: np.ndarray      # (B,) static: pre-fanout events/instance
    telemetry: Telemetry     # (B,)-leading leaves
    steps: jnp.ndarray | None = None  # (B,) micro-transactions executed:
    #                          one count shared by the whole batch (per
    #                          shard when the batch is sharded)

    @property
    def n_instances(self) -> int:
        return int(self.injected.shape[0])

    def instance(self, i: int) -> FabricResult:
        """Instance ``i`` as a solo-shaped :class:`FabricResult` (log
        arrays trimmed to the instance's own expected delivery count)."""
        with tracing.span("split", instances=1):
            return self._instance(i)

    def results(self) -> list[FabricResult]:
        """All instances as solo-shaped results, batch order."""
        with tracing.span("split", instances=self.n_instances):
            return [self._instance(i) for i in range(self.n_instances)]

    def _instance(self, i: int) -> FabricResult:
        e = int(self.injected[i])
        return FabricResult(
            delivered=self.delivered[i], injected=e,
            log_inj=self.log_inj[i, :e], log_del=self.log_del[i, :e],
            log_dest=self.log_dest[i, :e],
            sent=self.sent[i], n_switches=self.n_switches[i],
            t_link=self.t_link[i], t_end=self.t_end[i],
            drops=self.drops[i], offered=int(self.offered[i]),
            telemetry=Telemetry(*(getattr(self.telemetry, f)[i]
                                  for f in Telemetry._fields)),
            steps=-1 if self.steps is None else self.steps[i])


def batch_throughput_mev_s(batch: FabricBatchResult) -> jnp.ndarray:
    """(B,) delivered events per second per instance, MEvents/s."""
    return jnp.where(batch.t_end > 0,
                     1e3 * batch.delivered / batch.t_end, 0.0)


def batch_latency_stats(batch: FabricBatchResult) -> list[dict]:
    """Per-instance ``latency_stats`` dicts, batch order — the Monte-
    Carlo view: the spread of p50/p99 across seeds of one scenario."""
    return [latency_stats(r) for r in batch.results()]


def reset_links(initial_tx: np.ndarray) -> LinkState:
    """Batched ``protocol_sim.reset_link``: leaf shape (L,)."""
    return jax.vmap(reset_link)(jnp.asarray(initial_tx, jnp.int32))


# -----------------------------------------------------------------------
# Setup-time helpers (plain numpy)
# -----------------------------------------------------------------------

def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _check_reachable(rt: RoutingTable, src: np.ndarray, dest: np.ndarray):
    first_link = rt.next_link[src, dest]
    if np.any(first_link < 0):
        bad = np.flatnonzero(first_link < 0)[:4]
        raise ValueError(f"unreachable destinations, e.g. events {bad}: "
                         f"src={src[bad]} dest={dest[bad]}")


def _prefill(L: int, grp, t, route, inj,
             capacity: int, width: int | str | None = None):
    """Place injected copies into their first-hop queues (numpy, setup).

    ``grp`` is the flat first-hop queue id (``link * 2 + side``) of each
    copy, ``route`` its route id (destination chip or multicast tree)
    and ``inj`` the original injection time the delivery log reports.
    ``capacity`` is the logical per-endpoint budget (raises on overflow);
    ``width`` is the allocated column count of the returned arrays —
    ``None`` = ``capacity`` (the reference slot layout), ``"auto"`` = the
    max initial backlog bucketed to a power of two plus one
    always-empty pad column (the ring engine's prefill-only layout).
    """
    grp = np.asarray(grp, np.int64)
    t = np.asarray(t, np.int32)
    route = np.asarray(route, np.int32)
    inj = np.asarray(inj, np.int32)
    order = np.lexsort((np.arange(len(t)), t, grp))  # stable time order
    grp_s, t_s, route_s, inj_s = (grp[order], t[order], route[order],
                                  inj[order])

    sizes = np.bincount(grp, minlength=2 * L).astype(np.int32)
    if sizes.max(initial=0) > capacity:
        raise ValueError(f"queue capacity {capacity} < initial backlog "
                         f"{sizes.max()}; raise queue_capacity")
    if width == "auto":
        width = _pow2ceil(max(int(sizes.max(initial=1)),
                              _RING_PREFILL_FLOOR)) + 1
    elif width is None:
        width = capacity
    # within-queue slot = position since the queue's first event
    starts = np.zeros(2 * L + 1, np.int64)
    np.cumsum(sizes, out=starts[1:2 * L + 1])
    slot = np.arange(len(t)) - starts[grp_s]

    # empty slots hold the BIG_NS sentinel: "never released"
    q_time = np.full((2 * L, width), int(_BIG), np.int32)
    q_dest = np.zeros((2 * L, width), np.int32)
    q_inj = np.zeros((2 * L, width), np.int32)
    q_time[grp_s, slot] = t_s
    q_dest[grp_s, slot] = route_s
    q_inj[grp_s, slot] = inj_s
    return (q_time.reshape(L, 2, width), q_dest.reshape(L, 2, width),
            q_inj.reshape(L, 2, width), sizes.reshape(L, 2))


def _first_hop_queues(rt: RoutingTable, src, dest) -> np.ndarray:
    """Flat first-hop queue ids of unicast events (validated upstream)."""
    return rt.next_link[src, dest] * 2 + rt.out_side[src, dest]


# -----------------------------------------------------------------------
# Replication tables: one (node, route) -> out-copies/deliver contract
# shared by every engine.  Route id r < N is "unicast to chip r"; route
# id N + i is multicast tree i (router.MulticastTree).
# -----------------------------------------------------------------------

def _unicast_routes(topo: Topology, rt: RoutingTable):
    """(N, N, 1) out-queue / (N, N) deliver / (N, N, 1) drop-weight
    tables for the unicast route ids.  ``out_q`` holds the flat next-hop
    queue (``link * 2 + side``) or -1 (deliver here / unreachable);
    ``deliver`` is the identity (a unicast route delivers exactly at its
    destination chip); every forward carries drop weight 1."""
    nl, os_ = rt.next_link, rt.out_side
    out_q = np.where(nl >= 0, nl * 2 + os_, -1).astype(np.int32)[:, :, None]
    deliver = np.eye(topo.n_chips, dtype=np.int32)
    weight = (out_q >= 0).astype(np.int32)
    return out_q, deliver, weight


def _routes_with_trees(topo: Topology, rt: RoutingTable,
                       trees: list[MulticastTree]):
    """Stack the unicast tables with one route per multicast tree.

    Returns ``(out_q (N, R, K), deliver (N, R), weight (N, R, K))`` with
    ``R = n_chips + len(trees)`` and ``K`` the largest replication
    branch factor.  ``weight[c, r, k]`` is the number of final
    deliveries in the subtree fed by that out-copy — what a capacity
    drop at that point forfeits."""
    N = topo.n_chips
    uq, ud, uw = _unicast_routes(topo, rt)
    K = max([1] + [t.max_out_degree for t in trees])
    R = N + len(trees)
    out_q = np.full((N, R, K), -1, np.int32)
    deliver = np.zeros((N, R), np.int32)
    weight = np.zeros((N, R, K), np.int32)
    out_q[:, :N, :1] = uq
    deliver[:, :N] = ud
    weight[:, :N, :1] = uw
    for i, t in enumerate(trees):
        r = N + i
        deliver[:, r] = t.deliver
        k_next = np.zeros(N, np.int64)
        for e in range(t.n_edges):
            if t.parent[e] < 0:
                continue   # root edges are prefill, not replication (no
                #            copy ever arrives at the source on its own
                #            tree route — the source row stays empty)
            u, l, s, _v = (int(x) for x in t.edges[e])
            out_q[u, r, k_next[u]] = l * 2 + s
            weight[u, r, k_next[u]] = t.subtree[e]
            k_next[u] += 1
    return out_q, deliver, weight


def _expand(spec: TrafficSpec, addr: AddressSpec | None,
            mcast: MulticastTable | None):
    """Resolve packed/multicast destinations into unicast chip triples."""
    src = np.asarray(spec.src, np.int32)
    t = np.asarray(spec.t, np.int32)
    dest = np.asarray(spec.dest, np.int32)
    if addr is None:
        return src, t, dest
    is_mc = addr.is_multicast(dest)
    chip_or_tag, _ = addr.unpack(dest)
    out_s = [src[~is_mc]]
    out_t = [t[~is_mc]]
    out_d = [chip_or_tag[~is_mc]]
    if np.any(is_mc):
        if mcast is None:
            raise ValueError("multicast events but no MulticastTable")
        ms, mt, md = mcast.expand_stream(src[is_mc], t[is_mc],
                                         chip_or_tag[is_mc])
        out_s.append(ms)
        out_t.append(mt)
        out_d.append(md)
    return (np.concatenate(out_s), np.concatenate(out_t),
            np.concatenate(out_d))


def _in_edge_ranks(topo: Topology):
    """Per-chip enumeration of delivering links.

    ``rank[l, side]`` is the index of link ``l`` among the links incident
    to chip ``topo.links[l, side]`` (id order) — the forward-stream slot
    an event delivered over ``l`` into that chip appends to.  Returns
    ``(rank (L, 2) int32, D)`` with ``D`` the maximum chip degree.
    """
    L = topo.n_links
    rank = np.zeros((L, 2), np.int32)
    deg = np.zeros(topo.n_chips, np.int32)
    for l, (a, b) in enumerate(topo.links):
        rank[l, 0] = deg[a]
        deg[a] += 1
        rank[l, 1] = deg[b]
        deg[b] += 1
    return rank, max(int(deg.max(initial=1)), 1)


def _stream_quota(rt: RoutingTable, links: np.ndarray, in_rank: np.ndarray,
                  src: np.ndarray, dest: np.ndarray, L: int, D: int):
    """Static per-(queue, in-edge) forward-count upper bound.

    Routing is deterministic, so every event's full path is known at
    setup; walking all paths counts how many forwards each stream can
    ever receive (drops only shorten paths, so the no-drop count is an
    upper bound).  O(E · diameter) in numpy, off the hot path.
    """
    counts = np.zeros((2 * L, D), np.int64)
    c = src.astype(np.int64).copy()
    prev_l = np.full(len(src), -1, np.int64)
    prev_rx_side = np.zeros(len(src), np.int64)
    active = c != dest
    while active.any():
        l = np.where(active, rt.next_link[c, dest], 0)
        s = np.where(active, rt.out_side[c, dest], 0)
        m = active & (prev_l >= 0)
        if m.any():
            d = in_rank[prev_l[m], prev_rx_side[m]]
            np.add.at(counts, (l[m] * 2 + s[m], d), 1)
        prev_l = np.where(active, l, prev_l)
        prev_rx_side = np.where(active, 1 - s, prev_rx_side)
        c = np.where(active, links[l, 1 - s], c)
        active = c != dest
    return counts


def _tree_stream_quota(trees: list[MulticastTree], tree_counts,
                       in_rank: np.ndarray, L: int, D: int):
    """Static per-(queue, in-edge) forward-count bound for tree routes.

    Every non-root tree edge is one in-fabric forward: the copy arrives
    at ``u`` over the parent edge's link and is appended to the edge's
    out-queue on the parent link's in-edge stream — once per event
    riding the tree (``tree_counts``).  Root edges are prefill, not
    stream appends."""
    counts = np.zeros((2 * L, D), np.int64)
    for tree, n in zip(trees, tree_counts):
        for e in range(tree.n_edges):
            p = int(tree.parent[e])
            if p < 0:
                continue
            _u, l, s, _v = (int(x) for x in tree.edges[e])
            lp, sp = int(tree.edges[p][1]), int(tree.edges[p][2])
            d = int(in_rank[lp, 1 - sp])
            counts[l * 2 + s, d] += int(n)
    return counts


def _pad_to(a: np.ndarray, shape: tuple, fill) -> np.ndarray:
    """Embed ``a`` in a ``fill``-initialized array of ``shape``."""
    out = np.full(shape, fill, a.dtype)
    out[tuple(slice(n) for n in a.shape)] = a
    return out


def _overflow_guard(t_max: int, total_tx: int, worst_cost: int):
    """Refuse traffic that could push a clock past the ``BIG_NS`` sentinel.

    Empty queue slots hold ``BIG_NS`` ("never released"); once any
    link-local clock reaches it, empty slots would look released and the
    queue state would corrupt silently.  The clock only advances by
    jumping to an arrival (<= ``t_max``) or by paying one transmission
    cost, so ``t_max + total_tx * worst_cost`` bounds every clock (and
    the ``min(na + t_cycle)`` insert bound stays below int32 overflow a
    fortiori).  ``worst_cost`` is the maximum single-transmission cost
    over all links (per-link heterogeneous timing maximises over the
    fabric).

    This is the *global* bound — the documented fallback when per-route
    tables are unavailable or broken (a cyclic/dead-end override walks
    forever, so its per-link transmission counts are undefined).  When
    the routes do terminate, :func:`_overflow_guard_routed` charges each
    transmission its own link's cost instead of the fabric-wide worst —
    a strictly tighter bound on heterogeneous fabrics (slow LVDS links
    no longer tax traffic that never crosses them), so fewer false
    refusals.
    """
    bound = int(t_max) + int(total_tx) * int(worst_cost)
    if bound >= int(_BIG):
        raise ValueError(
            f"clock overflow risk: worst-case end time {bound} ns reaches "
            f"the BIG_NS sentinel ({int(_BIG)} ns). Long-running "
            f"simulations must keep max(t) + total_hops * "
            f"{worst_cost} ns below it; rebase injection times or split "
            f"the simulation.")


def _route_link_tx(rt: RoutingTable, links: np.ndarray, src: np.ndarray,
                   dest: np.ndarray, L: int, n_chips: int):
    """Per-link transmission counts along the actual unicast routes.

    Walks every event's deterministic path (the same O(E · diameter)
    numpy pattern as ``_stream_quota``, collapsed to links) and counts
    how many transmissions each link carries.  Returns ``(counts (L,)
    int64, ok)``; ``ok`` is False when some walk failed to terminate
    within ``n_chips - 1`` hops — a cyclic or dead-end override table,
    whose per-link counts are undefined (the caller falls back to the
    global :func:`_overflow_guard` bound).
    """
    counts = np.zeros(L, np.int64)
    c = np.asarray(src, np.int64).copy()
    dest = np.asarray(dest, np.int64)
    active = c != dest
    for _ in range(max(n_chips - 1, 0)):
        if not active.any():
            break
        l = np.where(active, rt.next_link[c, dest], -1)
        has = active & (l >= 0)
        l_g = np.maximum(l, 0)
        s_g = np.clip(np.where(has, rt.out_side[c, dest], 0), 0, 1)
        np.add.at(counts, l_g[has], 1)
        c = np.where(has, links[l_g, 1 - s_g], c)
        active = has & (c != dest)
    return counts, not bool(active.any())


def _clock_bound(t_max: int, link_tx: np.ndarray,
                 link_cost: np.ndarray) -> int:
    """Worst-case end-time bound with per-link transmission costs:
    ``t_max + sum_l link_tx[l] * link_cost[l]`` — each transmission pays
    its own link's worst single-transmission cost rather than the
    fabric-wide maximum."""
    return int(t_max) + int((np.asarray(link_tx, np.int64)
                             * np.asarray(link_cost, np.int64)).sum())


def _overflow_guard_routed(t_max: int, link_tx: np.ndarray,
                           link_cost: np.ndarray):
    """Route-aware ``BIG_NS`` guard: the tight per-link clock budget.

    Same refusal contract as :func:`_overflow_guard` (see there for why
    the sentinel must stay unreachable), but the bound charges each
    link only the transmissions that actually cross it under the
    routing tables — on fabrics mixing fast parallel and slow serial
    links this admits workloads the global worst-cost bound falsely
    refused.
    """
    bound = _clock_bound(t_max, link_tx, link_cost)
    if bound >= int(_BIG):
        worst = int(np.asarray(link_cost).max(initial=1))
        raise ValueError(
            f"clock overflow risk: worst-case end time {bound} ns "
            f"(routed per-link bound) reaches the BIG_NS sentinel "
            f"({int(_BIG)} ns). Long-running simulations must keep "
            f"max(t) + sum over links of transmissions * per-link cost "
            f"(<= {worst} ns each) below it; rebase injection times or "
            f"split the simulation.")


# -----------------------------------------------------------------------
# Per-step pieces shared verbatim by every engine body (the bit-exactness
# contract lives here: one implementation of delivery logging and of the
# simultaneous-forwards insertion ordering)
# -----------------------------------------------------------------------

def _log_deliveries(log_inj, log_del, log_dest, log_n,
                    deliver, ev_inj, t_del, ev_dest, n_slots: int,
                    ix=XLA_INDEX):
    """Append this step's deliveries to the packed log (order: link id)."""
    d32 = deliver.astype(jnp.int32)
    slot = jnp.where(deliver, log_n + ix.cumsum(d32) - d32, n_slots)
    return (ix.set(log_inj, slot, ev_inj),
            ix.set(log_del, slot, t_del),
            ix.set(log_dest, slot, ev_dest),
            log_n + jnp.sum(d32))


def _forward_slots(forward, fq, n_ins_flat, cap, n_queues: int,
                   ix=XLA_INDEX):
    """Insertion slots for this step's forward copies.

    ``forward`` / ``fq`` are flat (M,) candidate arrays in priority
    order — link-major, replica-minor (M = L for unicast, L·K with
    in-fabric replication) — so simultaneous appends into one queue are
    ordered by (link index, replica index).  The returned ``key`` is the
    queue's insertion index (the reference slot id and pop tie-break
    key).  Returns ``(fq_g, key, app, dropped)`` where ``app`` masks
    copies that fit under ``cap`` and ``dropped`` the ones that did not
    (the caller weighs them — an in-fabric multicast copy carries its
    whole subtree's deliveries).
    """
    idx = jnp.arange(forward.shape[0])
    fq_m = jnp.where(forward, fq, n_queues)   # sentinel for non-forwards
    # (int32 before the broadcast: Mosaic cannot reshape bool vectors)
    before = (fq_m[None, :] == fq_m[:, None]) \
        & (idx[None, :] < idx[:, None]) \
        & (forward.astype(jnp.int32)[None, :] > 0)
    offs = jnp.sum(before.astype(jnp.int32), axis=1)
    fq_g = jnp.where(forward, fq, 0)
    key = ix.take(n_ins_flat, fq_g) + offs    # next free slot
    cap_ok = key < cap
    app = forward & cap_ok
    return fq_g, key, app, forward & ~cap_ok


def _replicate(route_out_j, route_wt_j, rx_chip, ev_route, did,
               ix=XLA_INDEX):
    """Gather one step's forward copies from the replication tables.

    Returns flat (L·K,) ``(forward mask, queue id, drop weight)`` in the
    link-major / replica-minor priority order ``_forward_slots``
    expects.  With unicast-only tables (K = 1) this is exactly the
    historical single next-hop gather."""
    K = route_out_j.shape[-1]
    out_qk = ix.flat(jnp.stack(
        [ix.take_nr(route_out_j, rx_chip, ev_route, k) for k in range(K)],
        axis=1))                                         # (L·K,)
    wt_k = ix.flat(jnp.stack(
        [ix.take_nr(route_wt_j, rx_chip, ev_route, k) for k in range(K)],
        axis=1))
    fwd = (ix.repeat(did.astype(jnp.int32), K) > 0) & (out_qk >= 0)
    return fwd, jnp.maximum(out_qk, 0), wt_k


def _flow_gate(fc_mode, cap, xon, occ, xoff, cand_route, rx_chip_cand,
               route_out_j, ix=XLA_INDEX):
    """Flow-control admission gate for one micro-transaction.

    For every endpoint queue, looks up the downstream queues its head
    event would replicate onto (``route_out_j[rx_chip, route]``) and
    decides whether a pop must stall: in credit mode when any real
    target's occupancy ``n_ins - n_pop`` has reached ``cap``, in on/off
    mode when any real target has its latched ``xoff`` bit raised.
    Delivery-only heads (all targets -1) are never gated — destination
    sinks always drain, so acyclic route sets cannot deadlock.  The
    xon/xoff hysteresis state advances first (set at ``occ >= cap``,
    cleared at ``occ <= xon``) so both engines latch from the identical
    start-of-step occupancy.

    ``fc_mode`` / ``cap`` / ``xon`` are *dynamic* int32 scalars (0 =
    drop, 1 = credit, 2 = onoff) — the gate adds no compilation
    buckets, and in drop mode it is the constant ``False`` mask, which
    keeps the PR 5 semantics bit-exact.

    Shapes: ``occ`` / ``xoff`` / ``cand_route`` / ``rx_chip_cand`` are
    (L, 2); returns ``(blocked (L, 2) bool, xoff' (L, 2) int32)``.
    """
    xoff2 = jnp.where(occ >= cap, jnp.int32(1),
                      jnp.where(occ <= xon, jnp.int32(0), xoff))
    occ_f, xoff_f = ix.flat(occ), ix.flat(xoff2)
    full = off = jnp.zeros(occ.shape, bool)
    for k in range(route_out_j.shape[-1]):
        tgt = ix.take_nr(route_out_j, rx_chip_cand, cand_route, k)  # (L, 2)
        real = tgt >= 0
        tgt_g = jnp.maximum(tgt, 0)
        full = full | (real & (ix.take(occ_f, tgt_g) >= cap))
        off = off | (real & (ix.take(xoff_f, tgt_g) > 0))
    blocked = ((fc_mode == 1) & full) | ((fc_mode == 2) & off)
    return blocked, xoff2


# -----------------------------------------------------------------------
# Slot engines ("reference" and "pallas"): flat one-shot (Q, C) arrays
# -----------------------------------------------------------------------

class _SlotState(NamedTuple):
    link: LinkState         # (L,)-leaved LinkSim batch
    q_time: jnp.ndarray     # (Q, C) release times; BIG_NS = empty/consumed
    q_dest: jnp.ndarray     # (Q, C) route id (dest chip | multicast tree)
    q_inj: jnp.ndarray      # (Q, C) original injection time
    n_ins: jnp.ndarray      # (L, 2) entries ever inserted (next free slot)
    sent: jnp.ndarray       # (L, 2) transmissions per direction (0: L->R)
    prev_mode_l: jnp.ndarray  # (L,) for switch counting
    n_sw: jnp.ndarray       # (L,) mode_l transitions (excl. reset step)
    log_inj: jnp.ndarray    # (E,) delivery log: injection time
    log_del: jnp.ndarray    # (E,) delivery log: delivery time
    log_dest: jnp.ndarray   # (E,) delivery log: destination chip
    log_n: jnp.ndarray      # scalar: deliveries so far
    drops: jnp.ndarray      # scalar: forwards lost to a full queue
    busy_ns: jnp.ndarray    # (L,) telemetry: ns spent transmitting
    busy_steps: jnp.ndarray  # (L, 2) telemetry: steps with backlog
    q_drops: jnp.ndarray    # (L, 2) telemetry: weighted drops per queue
    n_pop: jnp.ndarray      # (L, 2) entries ever popped (credit returns)
    xoff: jnp.ndarray       # (L, 2) latched on/off backpressure bit
    in_stall: jnp.ndarray   # (L, 2) stalled last step (episode edges)
    stall_steps: jnp.ndarray  # (L, 2) telemetry: flow-control stalls
    credit_waits: jnp.ndarray  # (L, 2) telemetry: stall episodes


def _slot_init(L: int, E: int, q_time, q_dest, q_inj, sizes,
               init_tx) -> _SlotState:
    """Reset-time slot-engine carry (shared by the per-step scan and the
    multi-step kernel path, so both start from the identical state)."""
    link0 = reset_links(init_tx)
    return _SlotState(
        link=link0,
        q_time=q_time, q_dest=q_dest, q_inj=q_inj,
        n_ins=sizes,
        sent=jnp.zeros((L, 2), jnp.int32),
        prev_mode_l=link0.xl.mode,
        n_sw=jnp.zeros((L,), jnp.int32),
        log_inj=jnp.zeros((E,), jnp.int32),
        log_del=jnp.zeros((E,), jnp.int32),
        log_dest=jnp.zeros((E,), jnp.int32),
        log_n=jnp.zeros((), jnp.int32),
        drops=jnp.zeros((), jnp.int32),
        busy_ns=jnp.zeros((L,), jnp.int32),
        busy_steps=jnp.zeros((L, 2), jnp.int32),
        q_drops=jnp.zeros((L, 2), jnp.int32),
        n_pop=jnp.zeros((L, 2), jnp.int32),
        xoff=jnp.zeros((L, 2), jnp.int32),
        in_stall=jnp.zeros((L, 2), jnp.int32),
        stall_steps=jnp.zeros((L, 2), jnp.int32),
        credit_waits=jnp.zeros((L, 2), jnp.int32),
    )


def _slot_results(final: _SlotState):
    """The engine's 14-tuple result, read off the final carry."""
    return (final.log_n, final.log_inj, final.log_del, final.log_dest,
            final.sent, final.n_sw, final.link.t,
            jnp.max(final.link.t), final.drops,
            final.busy_ns, final.busy_steps, final.q_drops,
            final.stall_steps, final.credit_waits)


def _slot_step_body(L: int, E: int, C: int, max_burst: int,
                    scan_fn, update_fn,
                    links_j, route_out_j, route_del_j, route_wt_j,
                    t_cycle_v, t_rev_v, t_idle_v, cap, fc_mode, xon,
                    ix=XLA_INDEX):
    """Build the per-micro-transaction physics ``body(s, step_i) -> s'``.

    ONE implementation of the slot-engine step, closed over the dynamic
    operands, consumed by three callers: the reference engine
    (``scan_fn``/``update_fn`` = the pure-jnp oracles), the per-step
    pallas engine (= the jitted kernel wrappers), and the multi-step
    kernel body / its oracle (= the value-level kernel math) — which is
    what makes ``kernel="multistep"`` bit-exact by construction rather
    than by parallel maintenance.

    Every gather and scatter goes through ``ix``
    (:mod:`repro.kernels.indexing`): plain indexing in XLA, one-hot
    reductions inside the multi-step kernel, where the replication
    tables arrive in ``ix.table`` layout.
    """
    Q = 2 * L
    K = route_out_j.shape[-1]
    # the chip a pop over (link, side) would deliver into — the gate
    # needs it for both sides before the FSM picks a direction
    rx_chip_cand = jnp.stack([links_j[:, 1], links_j[:, 0]], axis=1)

    def body(s: _SlotState, step_i) -> _SlotState:
            t_now = s.link.t  # (L,)

            # --- pending & next-arrival per endpoint queue --------------
            # An entry is *in* the FIFO once its release time has passed;
            # empty/consumed slots hold BIG_NS and never match.  Service
            # order is release-time order (argmin; ties resolve to the
            # lowest slot, i.e. FIFO among simultaneous arrivals), which
            # for the sorted single-hop prefill is exactly simulate()'s
            # searchsorted count.
            t_q = ix.repeat(t_now, 2)                            # (Q,)
            pend_q, r_min_q, nxt_q, amin_q, busy_q, route_q = scan_fn(
                s.q_time, s.q_dest, t_q)
            pend = ix.unflat(pend_q, 2)
            # telemetry: backlog-present integral per endpoint queue
            busy_steps = s.busy_steps + ix.unflat(busy_q, 2)
            r_min = ix.unflat(r_min_q, 2)
            nxt2 = ix.unflat(nxt_q, 2)                           # (L, 2)

            # --- flow-control admission gate ----------------------------
            # Would this queue's head pop into a backpressured queue?
            # Gated BEFORE the FSM step so a stalled head simply presents
            # no pending work (the event stays in its slot, the link
            # idles — the 4-phase "receiver withholds ack" behaviour).
            occ = s.n_ins - s.n_pop
            cand_route = ix.unflat(route_q, 2)
            blocked, xoff = _flow_gate(fc_mode, cap, xon, occ, s.xoff,
                                       cand_route, rx_chip_cand,
                                       route_out_j, ix)
            stalled = (pend > 0) & blocked
            stall_steps = s.stall_steps + stalled.astype(jnp.int32)
            credit_waits = s.credit_waits + (
                stalled & (s.in_stall == 0)).astype(jnp.int32)

            # --- conservative clock synchronization ---------------------
            # A link acts no earlier than its clock (work pending) or its
            # own next arrival: ``na``.  Any *future* forward is released
            # at some link's next delivery — link ``l``'s next
            # transmission completes no earlier than ``na[l] +
            # t_cycle[l]`` (every transmit cost is >= its event cycle), so
            # ``min(na + t_cycle)`` lower-bounds every possible future
            # insert even under per-link heterogeneous timing (with
            # uniform timing it is exactly the old ``min(na) + t_cycle``).
            # Two consequences keep every queue in true release order:
            #   * idle links never jump past min(na), so a parked clock
            #     never overtakes a forward still in flight;
            #   * a busy link may pop its earliest released entry only if
            #     its release precedes every possible future insert
            #     (release <= min(na + t_cycle)) — otherwise it stalls
            #     until the rest of the fabric catches up (classic
            #     conservative lookahead).
            # With one link both guards are vacuous (its own bound is
            # always the loosest), so simulate() semantics are preserved
            # bit-exactly.
            #
            # Flow control refines the ``na`` term, per SIDE: a side with
            # ANY released entry is head-of-line gated by its earliest
            # released head (a shadowed later arrival can never act
            # before the head pops), so its next-action bound is the
            # clock when the head may pop — and when the head is *gated*,
            # the downstream chain instead: the stall only breaks after a
            # downstream pop, which that link's own ``na`` already
            # bounds, so the stalled side is excluded from the horizon
            # (else its parked clock would pin the fabric and a deep
            # stall chain could false-deadlock).  Its clock then rides
            # the fabric floor upward via the idle jump, so the eventual
            # post-stall transmit time (and the event's latency) includes
            # the backpressure wait.  Only sides with NO released work
            # contribute their future-arrival minimum — which is why the
            # idle-jump target ``t_next_g`` masks released sides too:
            # behind a released head the engines legitimately disagree on
            # shadowed arrival times (the ring engine sees only stream
            # heads), and head-of-line gating makes those times
            # irrelevant anyway.  In drop mode ``blocked`` is constant
            # False and every expression below collapses bit-exactly to
            # the historical link-level form.
            pend_b = pend > 0                                    # (L, 2)
            na_side = jnp.where(
                pend_b, jnp.where(blocked, _BIG, t_now[:, None]), nxt2)
            na = jnp.min(na_side, axis=1)                        # (L,)
            t_next_g = jnp.min(jnp.where(pend_b, _BIG, nxt2), axis=1)
            horizon = jnp.min(na)
            t_next_eff = jnp.minimum(t_next_g,
                                     jnp.maximum(horizon, t_now))
            safe = r_min <= jnp.min(na + t_cycle_v)              # (L,2)
            pend_safe = jnp.where(safe & ~blocked, pend, 0)

            # --- one micro-transaction on every link, batched -----------
            link, out = link_step_batch(
                s.link, pend_safe[:, 0], pend_safe[:, 1], t_next_eff,
                max_burst=max_burst,
                timing_arrays=(t_cycle_v, t_rev_v, t_idle_v))

            did = (out.tx_l + out.tx_r) > 0                      # (L,) bool
            did32 = did.astype(jnp.int32)
            # telemetry: a transmitting link's clock advances by exactly
            # the transmission cost, so the gated delta is bus-busy time
            busy_ns = s.busy_ns + jnp.where(did, link.t - t_now, 0)
            send_side = jnp.where(out.tx_l == 1, 0, 1)           # (L,)
            qid = jax.lax.broadcasted_iota(jnp.int32, (L,), 0) * 2 \
                + send_side                                      # (L,)
            pop_slot = ix.take(amin_q, qid)
            # == q_dest[qid, pop_slot] / q_inj[qid, pop_slot]
            ev_route = jnp.where(send_side == 0, cand_route[:, 0],
                                 cand_route[:, 1])
            ev_inj = ix.take(ix.pick(s.q_inj, amin_q), qid)
            # consume the popped slot (one-shot slots; no reuse) and
            # return its credit (occupancy = n_ins - n_pop drops by one)
            pop_q = jnp.where(did, qid, Q)
            sent_now = jnp.stack([1 - send_side, send_side],
                                 axis=1) * did32[:, None]
            sent = s.sent + sent_now
            n_pop = s.n_pop + sent_now

            # --- deliver and/or replicate -------------------------------
            # The receiving chip's replication-table row decides both: a
            # branch node of a multicast tree can deliver locally AND
            # spawn several child copies from this one pop.
            rx_chip = jnp.where(out.tx_l == 1, links_j[:, 1], links_j[:, 0])
            deliver = did & (ix.take_nr(route_del_j, rx_chip, ev_route) > 0)

            log_inj, log_del, log_dest, log_n = _log_deliveries(
                s.log_inj, s.log_del, s.log_dest, s.log_n,
                deliver, ev_inj, link.t, rx_chip, E, ix)

            fwd_f, fqk_f, wt_f = _replicate(route_out_j, route_wt_j,
                                            rx_chip, ev_route, did, ix)
            n_ins_f = ix.flat(s.n_ins)
            # drop mode enforces the logical budget at append time (the
            # historical one-shot total-through bound); the stall modes
            # never discard — physical width C always fits (cap == C in
            # the unbounded default, so this is bit-exactly PR 5 there)
            app_cap = jnp.where(fc_mode == 0, jnp.minimum(cap, C), C)
            fq_g, slot, app, dropped = _forward_slots(
                fwd_f, fqk_f, n_ins_f, app_cap, Q, ix)
            fq_s = jnp.where(app, fq_g, Q)         # drop non-appends
            q_time, q_dest, q_inj = update_fn(
                s.q_time, s.q_dest, s.q_inj, pop_q, pop_slot,
                fq_s, slot, ix.repeat(link.t, K),
                ix.repeat(ev_route, K), ix.repeat(ev_inj, K))
            n_ins = ix.unflat(ix.add(n_ins_f, fq_s, jnp.ones_like(fq_s)), 2)
            drop_wt = jnp.where(dropped, wt_f, 0)
            drops = s.drops + jnp.sum(drop_wt)
            # telemetry: charge each weighted drop to its target queue
            q_drops = ix.unflat(ix.add(ix.flat(s.q_drops),
                                       jnp.where(dropped, fq_g, Q),
                                       drop_wt), 2)

            # --- switch counting (matches SimResult.n_switches: mode_l
            # transitions between consecutive steps, reset excluded) -----
            n_sw = s.n_sw + jnp.where(
                step_i > 0,
                (link.xl.mode != s.prev_mode_l).astype(jnp.int32), 0)

            ns = _SlotState(
                link=link, q_time=q_time, q_dest=q_dest, q_inj=q_inj,
                n_ins=n_ins, sent=sent,
                prev_mode_l=link.xl.mode, n_sw=n_sw,
                log_inj=log_inj, log_del=log_del, log_dest=log_dest,
                log_n=log_n, drops=drops,
                busy_ns=busy_ns, busy_steps=busy_steps, q_drops=q_drops,
                n_pop=n_pop, xoff=xoff,
                in_stall=stalled.astype(jnp.int32),
                stall_steps=stall_steps, credit_waits=credit_waits)
            return ns

    return body


def _slot_run(L: int, E: int, C: int, max_steps: int,
              max_burst: int, use_kernels: bool):
    """Build the slot-scan ``run`` function for one static shape signature
    (uncompiled — ``_slot_engine`` jits it solo, ``_slot_engine_batch``
    vmaps it over a ``(B,)`` leading instance axis).

    Timing arrives as *dynamic* (L,) cost vectors (``t_cycle_v`` /
    ``t_rev_v`` / ``t_idle_v`` — see ``link.link_timing_arrays``), so one
    compilation serves every timing contract, uniform or per-link
    heterogeneous.  Routing arrives as the replication tables
    ``route_out/route_del/route_wt`` ((N, R, K) / (N, R) / (N, R, K)):
    one pop can deliver locally AND spawn up to K child copies, which
    for unicast-only tables (K = 1, identity deliver) reproduces the
    historical next-hop gather bit-exactly.

    ``C`` is the *physical* slot width (the expanded event count — every
    queue can always hold everything ever routed through it); the
    logical per-endpoint budget arrives as the dynamic scalar ``cap``
    together with the flow-control mode ``fc_mode`` and on/off low-water
    mark ``xon``, so drop, credit and on/off runs of every capacity
    share ONE compilation per shape signature.
    """
    from ..kernels import ops as kops
    from ..kernels import ref as kref
    if use_kernels:
        scan_fn = kops.fabric_queue_scan
        update_fn = kops.fabric_queue_update
    else:
        scan_fn = kref.fabric_queue_scan
        update_fn = kref.fabric_queue_update

    def run(q_time, q_dest, q_inj, sizes, init_tx,
            links_j, route_out_j, route_del_j, route_wt_j,
            t_cycle_v, t_rev_v, t_idle_v, cap, fc_mode, xon):
        init = _slot_init(L, E, q_time, q_dest, q_inj, sizes, init_tx)
        body = _slot_step_body(
            L, E, C, max_burst, scan_fn, update_fn,
            links_j, route_out_j, route_del_j, route_wt_j,
            t_cycle_v, t_rev_v, t_idle_v, cap, fc_mode, xon)

        def scan_body(s, step_i):
            return body(s, step_i), None

        final, _ = jax.lax.scan(scan_body, init, jnp.arange(max_steps))
        return _slot_results(final)

    return run


# -----------------------------------------------------------------------
# Multi-step slot engine (``kernel="multistep"``): the whole
# micro-transaction loop fused into chunked Pallas launches
# -----------------------------------------------------------------------

#: packed-lane channel order of the multi-step carry, (16, L) int32:
#: the link FSM pair + per-link engine bookkeeping.
_MS_LANES = ("t", "last_dir", "bus_busy", "prev_tx_l", "prev_tx_r",
             "xl.mode", "xl.sw_ack", "xl.rx_p", "xl.burst",
             "xr.mode", "xr.sw_ack", "xr.rx_p", "xr.burst",
             "prev_mode_l", "n_sw", "busy_ns")
#: packed per-endpoint-side channel order, (9, L, 2) int32.
_MS_SIDES = ("n_ins", "sent", "n_pop", "xoff", "in_stall",
             "stall_steps", "credit_waits", "busy_steps", "q_drops")


def _pack_slot_state(s: _SlotState):
    """``_SlotState`` -> the multi-step kernel's packed int32 carry.

    Seven arrays: the three (Q, C) slot planes, a (16, L) lane plane
    (``_MS_LANES``), a (9, L, 2) side plane (``_MS_SIDES``), a (3, E)
    delivery-log plane and a (2,) counter vector ``[log_n, drops]``.
    The packing is what the roofline model meters: bytes/step on the
    per-step path = this carry round-tripped through HBM twice per
    micro-transaction."""
    lk = s.link
    lanes = jnp.stack([
        lk.t, lk.last_dir, lk.bus_busy, lk.prev_tx_l, lk.prev_tx_r,
        lk.xl.mode, lk.xl.sw_ack, lk.xl.rx_p, lk.xl.burst,
        lk.xr.mode, lk.xr.sw_ack, lk.xr.rx_p, lk.xr.burst,
        s.prev_mode_l, s.n_sw, s.busy_ns])
    sides = jnp.stack([s.n_ins, s.sent, s.n_pop, s.xoff, s.in_stall,
                       s.stall_steps, s.credit_waits, s.busy_steps,
                       s.q_drops])
    logs = jnp.stack([s.log_inj, s.log_del, s.log_dest])
    counters = jnp.stack([s.log_n, s.drops])
    return (s.q_time, s.q_dest, s.q_inj, lanes, sides, logs, counters)


def _unpack_slot_state(carry) -> _SlotState:
    q_time, q_dest, q_inj, lanes, sides, logs, counters = carry
    link = LinkState(
        t=lanes[0], last_dir=lanes[1], bus_busy=lanes[2],
        prev_tx_l=lanes[3], prev_tx_r=lanes[4],
        xl=XcvrState(mode=lanes[5], sw_ack=lanes[6], rx_p=lanes[7],
                     burst=lanes[8]),
        xr=XcvrState(mode=lanes[9], sw_ack=lanes[10], rx_p=lanes[11],
                     burst=lanes[12]))
    return _SlotState(
        link=link, q_time=q_time, q_dest=q_dest, q_inj=q_inj,
        n_ins=sides[0], sent=sides[1],
        prev_mode_l=lanes[13], n_sw=lanes[14],
        log_inj=logs[0], log_del=logs[1], log_dest=logs[2],
        log_n=counters[0], drops=counters[1],
        busy_ns=lanes[15], busy_steps=sides[7], q_drops=sides[8],
        n_pop=sides[2], xoff=sides[3], in_stall=sides[4],
        stall_steps=sides[5], credit_waits=sides[6])


def slot_carry_bytes(L: int, E: int, C: int) -> int:
    """Bytes of the packed multi-step carry (the roofline traffic unit).

    ``3·(2L·C) + 16·L + 9·2L + 3·E + 2`` int32 words — exactly what the
    per-step engine round-trips through XLA/HBM per micro-transaction
    and the multi-step kernel keeps resident for ``chunk`` steps."""
    q = 2 * L
    words = 3 * q * C + len(_MS_LANES) * L + len(_MS_SIDES) * q + 3 * E + 2
    return 4 * words


def slot_step_temp_bytes(L: int, E: int, C: int, n_route_rows: int,
                         K: int) -> int:
    """Bytes of the largest one-hot temporaries of one slot step in its
    :class:`~repro.kernels.indexing.OneHotIndex` form, with ``M = L·K``
    forward copies: the delivery-log scatters (L, E), the forward-slot
    ranking (M, M), the replication-table gathers (L, N·R) and the
    append one-hots (M, C), int32 each."""
    M = L * K
    return 4 * (L * E + M * M + L * n_route_rows + M * C)


def _slot_run_multistep(L: int, E: int, C: int, max_steps: int,
                        max_burst: int, chunk: int):
    """Multi-step variant of :func:`_slot_run`: same operand contract,
    same 14-tuple result, but the scan over micro-transactions runs
    ``chunk`` steps at a time INSIDE one Pallas launch
    (``fabric_queue_multistep_pallas``) with the packed carry resident
    across steps, instead of dispatching two kernels + a full state
    round-trip per step.  The queue scan / pop / append inside the
    kernel body is the value-level scatter-as-matmul math
    (``scan_math`` / ``update_math``) — the same tile code the per-step
    kernels execute, now fused with the FSM/flow physics of
    :func:`_slot_step_body`, whose gathers and scatters run in their
    one-hot form (:class:`repro.kernels.indexing.OneHotIndex`).

    The final chunk's in-kernel loop bound is
    ``min(chunk, max_steps - base)``, so a binding ``max_steps`` is
    honoured exactly (post-bound steps never execute — they are not
    no-ops in general)."""
    from ..kernels import fabric_queue as fqk
    from ..kernels.indexing import OneHotIndex

    def run(q_time, q_dest, q_inj, sizes, init_tx,
            links_j, route_out_j, route_del_j, route_wt_j,
            t_cycle_v, t_rev_v, t_idle_v, cap, fc_mode, xon):
        init = _slot_init(L, E, q_time, q_dest, q_inj, sizes, init_tx)
        carry0 = _pack_slot_state(init)
        ix = OneHotIndex(route_out_j.shape[1])
        N, R, K = route_out_j.shape[-3:]
        temp_bytes = slot_step_temp_bytes(L, E, C, N * R, K)
        consts = (links_j, ix.table(route_out_j), ix.table(route_del_j),
                  ix.table(route_wt_j),
                  jnp.stack([t_cycle_v, t_rev_v, t_idle_v]),
                  jnp.stack([jnp.asarray(cap, jnp.int32),
                             jnp.asarray(fc_mode, jnp.int32),
                             jnp.asarray(xon, jnp.int32)]))

        def step_fn(car, con, step_i):
            links_c, rout_c, rdel_c, rwt_c, timing_c, par_c = con
            body = _slot_step_body(
                L, E, C, max_burst, fqk.scan_math, fqk.update_math,
                links_c, rout_c, rdel_c, rwt_c,
                timing_c[0], timing_c[1], timing_c[2],
                par_c[0], par_c[1], par_c[2], ix)
            return _pack_slot_state(body(_unpack_slot_state(car), step_i))

        # base rides an array derived from a batched operand (sizes) so
        # that under jax.vmap every pallas operand carries the batch
        # axis — the batching rule then has no unbatched inputs to
        # special-case.  Solo, the added term is exactly zero.
        base0 = jnp.zeros((1,), jnp.int32) + 0 * sizes[0, 0]
        n_chunks = -(-max_steps // chunk) if max_steps > 0 else 0

        def chunk_body(state, _):
            car, b = state
            out = fqk.fabric_queue_multistep_pallas(
                car, consts, b, step_fn=step_fn,
                chunk=chunk, max_steps=max_steps,
                step_temp_bytes=temp_bytes)
            return (tuple(out), b + chunk), None

        carry = carry0
        if n_chunks > 0:
            (carry, _b), _ = jax.lax.scan(
                chunk_body, (carry0, base0), None, length=n_chunks)
        return _slot_results(_unpack_slot_state(carry))

    return run


@functools.lru_cache(maxsize=None)
def _slot_engine_multistep(L: int, E: int, C: int, max_steps: int,
                           max_burst: int, chunk: int):
    """Compile-once multi-step slot engine (``engine="pallas"`` with
    ``kernel="multistep"``): ceil(max_steps / chunk) fused kernel
    launches per run instead of 2·max_steps."""
    return jax.jit(
        _slot_run_multistep(L, E, C, max_steps, max_burst, chunk))


@functools.lru_cache(maxsize=None)
def _slot_engine_multistep_batch(L: int, E: int, C: int, max_steps: int,
                                 max_burst: int, chunk: int,
                                 n_devices: int = 1):
    """Batched multi-step engine: ``jax.vmap`` over a ``(B,)`` instance
    axis; the fused kernel batches through ``pallas_call``'s batching
    rule (B independent carries per launch, interpret mode included)."""
    fn = jax.vmap(_slot_run_multistep(L, E, C, max_steps, max_burst,
                                      chunk))
    return jax.jit(_shard_over_batch(fn, n_devices))


@functools.lru_cache(maxsize=None)
def _slot_engine(L: int, E: int, C: int, max_steps: int,
                 max_burst: int, use_kernels: bool):
    """Compile-once slot-scan simulation for one static shape signature.

    Timing arrives as *dynamic* (L,) cost vectors and routing as the
    per-plan replication tables, so one compilation serves every timing
    contract, routing table and flow-control setting that fits the shape
    signature — see :func:`_slot_run` for the full operand contract.
    """
    return jax.jit(_slot_run(L, E, C, max_steps, max_burst, use_kernels))


def _shard_over_batch(fn, n_devices: int, n_args: int | None = None,
                      replicated: tuple = ()):
    """Split a batched engine's leading ``(B,)`` instance axis across
    devices via ``jax.shard_map`` over a one-axis ``batch`` mesh (an Auto
    axis, so the engine's indexing needs no sharding annotations).  Every
    operand and output
    carries the batch axis leading, so one ``PartitionSpec("batch")``
    covers the whole tree — except the positional args named in
    ``replicated`` (with ``n_args`` total), which are shared scalars
    (the ring batch's ``max_steps`` bound) and get the empty spec.
    Each shard runs its sub-batch independently — including the ring
    engine's early-exit ``while_loop``, which drains per-shard (a
    finished shard's devices idle instead of stepping the slowest
    instance globally).  ``n_devices <= 1`` is the identity."""
    if n_devices <= 1:
        return fn
    from jax.sharding import AxisType, PartitionSpec

    mesh = jax.make_mesh((int(n_devices),), ("batch",),
                         axis_types=(AxisType.Auto,))
    spec = PartitionSpec("batch")
    in_specs = (spec if not replicated else
                tuple(PartitionSpec() if i in replicated else spec
                      for i in range(n_args)))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)


@functools.lru_cache(maxsize=None)
def _slot_engine_batch(L: int, E: int, C: int, max_steps: int,
                       max_burst: int, use_kernels: bool,
                       n_devices: int = 1):
    """Batched slot engine: ONE compilation running B fabric instances.

    ``jax.vmap`` of :func:`_slot_run` over a leading ``(B,)`` instance
    axis on EVERY operand — traffic, routing/replication tables, timing
    vectors and the flow-control scalars are all per-instance, so a batch
    can mix seeds, tables and timing contracts freely within one shape
    signature.  The scan length is static (as in the solo engine), so all
    instances execute the same ``max_steps`` micro-transactions;
    post-completion steps are exact no-ops, keeping every instance
    bit-exact with its solo run.  The pallas variant batches through
    ``pallas_call``'s batching rule (interpret mode included).  With
    ``n_devices > 1`` the batch axis is additionally sharded across
    devices (see :func:`_shard_over_batch`)."""
    fn = jax.vmap(_slot_run(L, E, C, max_steps, max_burst, use_kernels))
    return jax.jit(_shard_over_batch(fn, n_devices))


# -----------------------------------------------------------------------
# Ring engine: release-time-sorted per-endpoint streams, O(1) per step
# -----------------------------------------------------------------------

class _RingState(NamedTuple):
    link: LinkState           # (L,)-leaved LinkSim batch
    h0: jnp.ndarray           # (L, 2) prefill head (also the pop tie key)
    fh: jnp.ndarray           # (L, 2, D) forward-stream heads
    ftl: jnp.ndarray          # (L, 2, D) forward-stream tails
    fqs: jnp.ndarray          # (L·2·D·Cf·4,) stream entries, packed
    #                           channels: 0 release time, 1 route id
    #                           (dest | mcast tree), 2 original injection
    #                           time, 3 reference-slot tie key.  One array
    #                           so each step is ONE head gather and ONE
    #                           tail scatter instead of four of each —
    #                           scatter/gather rows dominate the step on
    #                           CPU, and under vmap they serialize per
    #                           instance, so row count is the batch
    #                           throughput limit.  Flat, because as a
    #                           5-D array the head gather and the tail
    #                           scatter asked for different layouts and
    #                           XLA on the TPU copied the whole buffer
    #                           between them on every step.  The
    #                           batched runner keeps B instances' buffers
    #                           as ONE flat (B·N,) vector, not (B, N), for
    #                           the same reason (see _ring_run_batch); its
    #                           vmapped state holds None here.
    n_ins: jnp.ndarray        # (L, 2) entries ever inserted (capacity/key)
    sent: jnp.ndarray         # (L, 2)
    prev_mode_l: jnp.ndarray  # (L,)
    n_sw: jnp.ndarray         # (L,)
    log_pk: jnp.ndarray       # (E + L, 3) delivery log, packed (inj,
    #                           t_del, dest).  Delivery slots are
    #                           CONSECUTIVE (log_n + per-step cumsum), so
    #                           the append is a dynamic_update_slice of
    #                           one compacted (L, 3) block — a dense copy,
    #                           not a scatter; the L-row slack holds each
    #                           step's zeroed overhang rows.
    log_n: jnp.ndarray        # scalar
    drops: jnp.ndarray        # scalar
    busy_ns: jnp.ndarray      # (L,) telemetry: ns spent transmitting
    busy_steps: jnp.ndarray   # (L, 2) telemetry: steps with backlog
    q_drops: jnp.ndarray      # (L, 2) telemetry: weighted drops per queue
    n_pop: jnp.ndarray        # (L, 2) entries ever popped (credit returns)
    xoff: jnp.ndarray         # (L, 2) latched on/off backpressure bit
    in_stall: jnp.ndarray     # (L, 2) stalled last step (episode edges)
    stall_steps: jnp.ndarray  # (L, 2) telemetry: flow-control stalls
    credit_waits: jnp.ndarray  # (L, 2) telemetry: stall episodes


def _ring_run(L: int, E: int, C0: int, D: int, Cf: int, chunk: int):
    """Build the ring-stream ``run`` function for one static shape
    signature (uncompiled — ``_ring_engine`` jits it solo,
    ``_ring_engine_batch`` vmaps it over a ``(B,)`` instance axis).

    All dimensions are the *bucketed* ones (``_RING_*_FLOOR`` pow2
    padding): ``L`` links, ``E`` delivery-log slots, ``C0``/``Cf``
    prefill/stream widths (each with one always-``BIG_NS`` pad column so
    head/tail gathers never need bounds checks), ``D`` streams per
    endpoint.  The logical capacity, event count, burst bound and flow
    control arrive as dynamic scalars (``cap``, ``real_e``,
    ``max_burst``, ``fc_mode``, ``xon`` — the FSM's burst guard and the
    admission gate are pure arithmetic) and the timing contract as dynamic
    (L,) cost vectors (``t_cycle_v`` / ``t_rev_v`` / ``t_idle_v``,
    padded with zeros on dummy links — which park forever, so their
    ``na + t_cycle`` term is the inert ``BIG_NS``), so every fabric that
    fits the buckets shares ONE compilation regardless of traffic,
    capacity, fairness setting or per-link timing assignment.
    """
    Q = 2 * L
    lidx = jnp.arange(L)
    no_key = jnp.int32(2 ** 31 - 1)  # tie-break sentinel (keys are < cap)

    def start(q0_time, q0_dest, q0_inj, sizes, init_tx,
              links_j, route_out_j, route_del_j, route_wt_j, in_rank_j,
              t_cycle_v, t_rev_v, t_idle_v,
              cap, max_burst, fc_mode, xon):
        """Build ``(init, body)`` from one instance's operands — shared
        by the solo loop below and the batched loop
        (:func:`_ring_run_batch`), which vmaps ``body`` ALONE so the
        chunk bookkeeping stays scalar."""
        K = route_out_j.shape[2]
        # per-(link, side) delivery chip, both sides — the flow gate
        # inspects both heads before the FSM picks a direction.  Dummy
        # padded links point at chip 0 with empty queues: inert.
        with jax.named_scope("ring.fsm"):
            rx_chip_cand = jnp.stack([links_j[:, 1], links_j[:, 0]], axis=1)
        si2 = jnp.arange(2)[None, :]
        li2 = lidx[:, None]
        with jax.named_scope("ring.head"):
            # pack the prefill columns once per trace: the per-step head read
            # becomes one gather of (time, route, inj) triples
            q0_all = jnp.stack([q0_time, q0_dest, q0_inj], axis=-1)
        didx = jnp.arange(D, dtype=jnp.int32)
        qid = jnp.arange(Q, dtype=jnp.int32)[None, :]
        sid = jnp.arange(Q * D, dtype=jnp.int32)
        ch4 = jnp.arange(4, dtype=jnp.int32)[None, :]
        N = Q * D * Cf * 4   # stream-buffer entries of one instance
        with jax.named_scope("ring.init"):
            # the initial state, the stream buffer ``fqs`` included
            link0 = reset_links(init_tx)
            init = _RingState(
                link=link0,
                h0=jnp.zeros((L, 2), jnp.int32),
                fh=jnp.zeros((L, 2, D), jnp.int32),
                ftl=jnp.zeros((L, 2, D), jnp.int32),
                fqs=jnp.where(jnp.arange(N) % 4 == 0,
                              _BIG, 0).astype(jnp.int32),
                n_ins=sizes,
                sent=jnp.zeros((L, 2), jnp.int32),
                prev_mode_l=link0.xl.mode,
                n_sw=jnp.zeros((L,), jnp.int32),
                log_pk=jnp.zeros((E + L, 3), jnp.int32),
                log_n=jnp.zeros((), jnp.int32),
                drops=jnp.zeros((), jnp.int32),
                busy_ns=jnp.zeros((L,), jnp.int32),
                busy_steps=jnp.zeros((L, 2), jnp.int32),
                q_drops=jnp.zeros((L, 2), jnp.int32),
                n_pop=jnp.zeros((L, 2), jnp.int32),
                xoff=jnp.zeros((L, 2), jnp.int32),
                in_stall=jnp.zeros((L, 2), jnp.int32),
                stall_steps=jnp.zeros((L, 2), jnp.int32),
                credit_waits=jnp.zeros((L, 2), jnp.int32),
            )

        def body(s: _RingState, step_i, shared=None):
            """One micro-transaction: ``(next state, None)``.

            With ``shared = (fqs, off, drop_at)`` (the batched runner)
            the stream buffer is not ``s.fqs`` but this instance's
            ``N`` entries of ``fqs`` from ``off`` on: the head gather
            reads there, and the tail append is returned as
            ``(indices, entries)`` for the caller to scatter, in place
            of the ``None``, with ``fqs=None`` in the next state."""
            t_now = s.link.t  # (L,)

            with jax.named_scope("ring.head"):
                # --- O(1) queue reads: stream heads only --------------------
                # Every stream is sorted by (release, insertion key): the
                # prefill by construction, each forward stream because its
                # source link's delivery clock is monotone.  So per endpoint,
                # "any released entry", the earliest released release and the
                # earliest future arrival are all properties of the 1 + D
                # heads — no O(C) slot scan.
                p_head = jnp.take_along_axis(
                    q0_all, s.h0[:, :, None, None], axis=2)[:, :, 0]  # (L,2,3)
                f_at = (sid * Cf + s.fh.reshape(-1))[:, None] * 4 + ch4
                f_head = (s.fqs[f_at] if shared is None else
                          shared[0][f_at + shared[1]]
                          ).reshape(L, 2, D, 4)  # (L,2,D,4)
                p_t = p_head[..., 0]                                 # (L, 2)
                f_t = f_head[..., 0]  # (L, 2, D)
                p_rel = p_t <= t_now[:, None]
                f_rel = f_t <= t_now[:, None, None]
                pend_side = p_rel | jnp.any(f_rel, axis=2)           # (L, 2)
                r_min = jnp.minimum(
                    jnp.where(p_rel, p_t, _BIG),
                    jnp.min(jnp.where(f_rel, f_t, _BIG), axis=2))
                nxt = jnp.minimum(
                    jnp.where(p_rel, _BIG, p_t),
                    jnp.min(jnp.where(f_rel, _BIG, f_t), axis=2))    # (L, 2)

            with jax.named_scope("ring.fsm"):
                # --- the earliest (release, key) head, BOTH sides -----------
                # (release, insertion_key) lexicographic minimum in two int32
                # stages (keys are unique reference slot ids per queue, so the
                # key argmin over release ties is exact and matches the
                # reference argmin's lowest-slot rule).  Computed before the
                # FSM step because the flow-control gate must inspect each
                # head's downstream targets; the send side's values are
                # gathered out after the FSM picks a direction — identical
                # math to a post-step send-side-only selection.
                fk = f_head[..., 3]  # (L, 2, D)
                cand_t = jnp.concatenate(
                    [p_t[:, :, None], f_t], axis=2)  # (L,2,1+D)
                cand_k = jnp.concatenate(
                    [s.h0[:, :, None], fk], axis=2)
                rel_c = cand_t <= t_now[:, None, None]
                t_best = jnp.min(jnp.where(rel_c, cand_t, _BIG), axis=2)
                tie = rel_c & (cand_t == t_best[..., None])
                best = jnp.argmin(jnp.where(tie, cand_k, no_key),
                                  axis=2).astype(jnp.int32)          # (L, 2)
                from_pre = best == 0
                d_best = jnp.maximum(best - 1, 0)
                # the winning forward stream's head entry IS f_head at d_best
                # (f_head gathers AT s.fh), so no second stream gather
                best_head = f_head[li2, si2, d_best]  # (L, 2, 4)
                cand_route = jnp.where(
                    from_pre, p_head[..., 1], best_head[..., 1])
                cand_inj = jnp.where(
                    from_pre, p_head[..., 2], best_head[..., 2])

                # --- flow-control admission gate ----------------------------
                # Identical inputs and formulas to the slot engines: the
                # occupancy n_ins - n_pop is O(1) carry state, and the head
                # route is exactly the slot engines' q_dest[q, amin] gather.
                occ = s.n_ins - s.n_pop
                blocked, xoff = _flow_gate(fc_mode, cap, xon, occ, s.xoff,
                                           cand_route, rx_chip_cand,
                                           route_out_j)
                stalled = pend_side & blocked
                stall_steps = s.stall_steps + stalled.astype(jnp.int32)
                credit_waits = s.credit_waits + (
                    stalled & (s.in_stall == 0)).astype(jnp.int32)

                # --- conservative clock synchronization ---------------------
                # Identical contract to the reference engine (see
                # _slot_engine, including the per-link ``min(na + t_cycle)``
                # insert bound and the per-side head-of-line/stall rules);
                # head releases are exact stand-ins: a side with work pending
                # contributes the clock (gated: excluded), and a side with
                # none has every head unreleased, so the head minimum IS the
                # stream minimum — the one state where arrival times behind
                # heads would be invisible here is exactly the state the
                # head-of-line rule makes them irrelevant in.
                na_side = jnp.where(
                    pend_side, jnp.where(blocked, _BIG, t_now[:, None]), nxt)
                na = jnp.min(na_side, axis=1)                        # (L,)
                t_next_g = jnp.min(jnp.where(pend_side, _BIG, nxt), axis=1)
                horizon = jnp.min(na)
                t_next_eff = jnp.minimum(t_next_g,
                                         jnp.maximum(horizon, t_now))
                safe = r_min <= jnp.min(na + t_cycle_v)              # (L, 2)
                pend_safe = (pend_side & safe & ~blocked).astype(jnp.int32)

                # --- one micro-transaction on every link, batched -----------
                link, out = link_step_batch(
                    s.link, pend_safe[:, 0], pend_safe[:, 1], t_next_eff,
                    max_burst=max_burst,
                    timing_arrays=(t_cycle_v, t_rev_v, t_idle_v))

                did = (out.tx_l + out.tx_r) > 0  # (L,) bool
                did32 = did.astype(jnp.int32)
                send_side = jnp.where(out.tx_l == 1, 0, 1)           # (L,)
            with jax.named_scope("ring.telemetry"):
                # telemetry: backlog indicator + transmission-gated clock
                # delta — head properties only, so the O(1)-per-step contract
                # holds; bit-exact with the slot engines' (pend > 0) counter
                busy_steps = s.busy_steps + pend_side.astype(jnp.int32)
                busy_ns = s.busy_ns + jnp.where(did, link.t - t_now, 0)

            with jax.named_scope("ring.head"):
                # --- pop the send side's head, return its credit ------------
                fp_s = from_pre[lidx, send_side]                     # (L,)
                db_s = d_best[lidx, send_side]
                ev_route = cand_route[lidx, send_side]
                ev_inj = cand_inj[lidx, send_side]
                # single update per link row -> dense one-hot adds, not
                # scatters (XLA lowers small scatters to a per-row loop; under
                # vmap that loop serializes across the batch too)
                oh_side = si2 == send_side[:, None]                  # (L, 2)
                h0 = s.h0 + jnp.where(
                    oh_side, (did & fp_s).astype(jnp.int32)[:, None], 0)
                oh_d = oh_side[:, :, None] & (didx == db_s[:, None, None])
                fh = s.fh + jnp.where(
                    oh_d, (did & ~fp_s).astype(jnp.int32)[:, None, None], 0)
                sent = s.sent + jnp.where(oh_side, did32[:, None], 0)
                n_pop = s.n_pop + jnp.where(oh_side, did32[:, None], 0)

            with jax.named_scope("ring.log"):
                # --- deliver and/or replicate -------------------------------
                # The replication-table row of (rx_chip, route) decides both:
                # a multicast branch node can deliver locally AND spawn up to
                # K child copies from this one pop.
                rx_side = jnp.where(out.tx_l == 1, 1, 0)
                rx_chip = links_j[lidx, rx_side]
                deliver = did & (route_del_j[rx_chip, ev_route] > 0)

                # Delivery slots are consecutive from log_n (the same
                # log_n + cumsum slot rule as _log_deliveries), so instead of
                # three scatters the step compacts the delivering links to
                # the front — inv[p] is the (p+1)-th delivering link id,
                # counted densely — and writes ONE (L, 3) block with
                # dynamic_update_slice.  Rows at or past this step's delivery
                # count nd are forced to zero: the next step's block starts
                # exactly where this one's valid rows end, so overhang rows
                # are always overwritten by later valid rows, and the final
                # overhang leaves the same zeros an untouched buffer holds.
                # The buffer's L-row slack keeps the slice start (<= E) from
                # ever clamping.
                d32l = deliver.astype(jnp.int32)
                nd = jnp.sum(d32l)
                csum = jnp.cumsum(d32l)
                inv = jnp.minimum(jnp.sum(
                    (csum[None, :] <= lidx[:, None]).astype(jnp.int32),
                    axis=1), L - 1)                                  # (L,)
                blk = jnp.where(
                    (lidx < nd)[:, None],
                    jnp.stack([ev_inj[inv], link.t[inv], rx_chip[inv]],
                              axis=-1), 0)                           # (L, 3)
                log_pk = jax.lax.dynamic_update_slice(
                    s.log_pk, blk, (s.log_n, jnp.int32(0)))
                log_n = s.log_n + nd

            with jax.named_scope("ring.forward"):
                # --- forward append: tails of the delivering link's streams -
                # All K copies of one pop land at the SAME chip on K distinct
                # out-queues, so every active (queue, in-edge) target below
                # is unique and the multi-scatter is race-free.
                fwd_f, fqk_f, wt_f = _replicate(route_out_j, route_wt_j,
                                                rx_chip, ev_route, did)
                n_ins_f = s.n_ins.reshape(-1)
                # ``key`` is the reference slot id: the pop tie-break key.
                # Only drop mode discards at append time; the stall modes
                # are lossless and the stream quotas already bound storage.
                app_cap = jnp.where(fc_mode == 0, cap, jnp.int32(_BIG))
                fq_g, key, app, dropped = _forward_slots(
                    fwd_f, fqk_f, n_ins_f, app_cap, Q)
                d_ins = jnp.repeat(in_rank_j[lidx, rx_side], K)      # (L·K,)
                stream = fq_g * D + d_ins          # flat stream id
                stream_s = jnp.where(app, stream, Q * D)
                tail = s.ftl.reshape(-1)[stream]                     # (L·K,)
                # ONE packed append per step: all four channels of one entry
                # travel in a single (L·K, 4) scatter row
                upd = jnp.stack(
                    [jnp.repeat(link.t, K), jnp.repeat(ev_route, K),
                     jnp.repeat(ev_inj, K), key], axis=-1)           # (L·K, 4)
                a_at = (stream_s * Cf + tail)[:, None] * 4 + ch4
                if shared is None:
                    fqs = s.fqs.at[a_at].set(upd, mode="drop")
                else:
                    # the rows the solo scatter drops (unappended rows
                    # and any index past this instance's N entries) go
                    # to ``drop_at``, past every instance's range, never
                    # into the next instance's streams
                    fqs = None
                    append = (jnp.where(a_at < N, a_at + shared[1],
                                        shared[2]), upd)
                # counter bumps as dense one-hot sums over the tiny (Q,) and
                # (D,) index spaces — masked rows contribute zero everywhere
                eq_q = fq_g[:, None] == qid                          # (L·K, Q)
                app_q = (eq_q & app[:, None]).astype(jnp.int32)
                n_ins = (n_ins_f + jnp.sum(app_q, axis=0)).reshape(L, 2)
                eq_d = (d_ins[:, None] == didx[None, :]).astype(jnp.int32)
                ftl = (s.ftl.reshape(Q, D) + jnp.einsum(
                    'rq,rd->qd', app_q, eq_d)).reshape(L, 2, D)
                drop_wt = jnp.where(dropped, wt_f, 0)
                drops = s.drops + jnp.sum(drop_wt)
            with jax.named_scope("ring.telemetry"):
                # telemetry: charge each weighted drop to its target queue
                q_drops = (s.q_drops.reshape(-1) + jnp.sum(
                    eq_q.astype(jnp.int32) * drop_wt[:, None], axis=0)
                    ).reshape(L, 2)

                # --- switch counting (reset step excluded) ------------------
                n_sw = s.n_sw + jnp.where(
                    step_i > 0,
                    (link.xl.mode != s.prev_mode_l).astype(jnp.int32), 0)

            ns = _RingState(
                link=link, h0=h0, fh=fh, ftl=ftl,
                fqs=fqs, n_ins=n_ins, sent=sent,
                prev_mode_l=link.xl.mode, n_sw=n_sw,
                log_pk=log_pk, log_n=log_n, drops=drops,
                busy_ns=busy_ns, busy_steps=busy_steps, q_drops=q_drops,
                n_pop=n_pop, xoff=xoff,
                in_stall=stalled.astype(jnp.int32),
                stall_steps=stall_steps, credit_waits=credit_waits)
            return ns, (None if shared is None else append)

        return init, body

    def run(q0_time, q0_dest, q0_inj, sizes, init_tx,
            links_j, route_out_j, route_del_j, route_wt_j, in_rank_j,
            t_cycle_v, t_rev_v, t_idle_v,
            cap, real_e, max_burst, max_steps, fc_mode, xon):
        init, body = start(q0_time, q0_dest, q0_inj, sizes, init_tx,
                           links_j, route_out_j, route_del_j, route_wt_j,
                           in_rank_j, t_cycle_v, t_rev_v, t_idle_v,
                           cap, max_burst, fc_mode, xon)

        # --- chunked steps inside while_loop: exit within one chunk of
        # delivered + drops == injected.  Post-completion steps are
        # no-ops (no pending, parked clocks, settled FSMs), so stopping
        # at a chunk boundary is bit-exact vs. the padded reference scan.
        # The inner trip count is clamped to the steps remaining under
        # ``max_steps`` (a dynamic fori_loop bound — same lowering as the
        # fixed-length scan, no per-step masking cost), so when the step
        # bound binds mid-chunk the simulation still executes EXACTLY
        # ``max_steps`` micro-transactions — bit-exact against a
        # reference scan of the same length.
        def chunk_body(carry):
            st, base = carry
            this_chunk = jnp.minimum(jnp.int32(chunk), max_steps - base)
            st2 = jax.lax.fori_loop(
                jnp.int32(0), this_chunk,
                lambda i, s: body(s, base + i)[0], st)
            return st2, base + jnp.int32(chunk)

        def cond(carry):
            st, base = carry
            return (st.log_n + st.drops < real_e) & (base < max_steps)

        final, base = jax.lax.while_loop(cond, chunk_body,
                                         (init, jnp.int32(0)))
        # ``base``: the steps of the chunks run, in whole chunks; the
        # caller clamps it to ``max_steps`` (the last chunk's clamp),
        # outside the program, whose buffer layout this output would
        # otherwise move
        return (final.log_n, final.log_pk[:E, 0], final.log_pk[:E, 1],
                final.log_pk[:E, 2],
                final.sent, final.n_sw, final.link.t, final.drops,
                final.busy_ns, final.busy_steps, final.q_drops,
                final.stall_steps, final.credit_waits, base)

    run._start = start   # the batched runner reuses (init, body)
    return run


def _ring_run_batch(L: int, E: int, C0: int, D: int, Cf: int, chunk: int):
    """Build the BATCHED ring ``run``: B instances, one computation.

    Not a blind ``jax.vmap`` of the solo runner — that would batch the
    loop bookkeeping too, and JAX's while/fori batching rules then pay
    for it twice per micro-transaction: a batched inner trip count
    turns the chunk ``fori_loop`` into a masked ``while_loop`` that
    re-selects EVERY carry leaf (the full queue state) on EVERY step,
    an ~8x per-instance slowdown on CPU.  Instead only the step
    ``body`` is vmapped (gathers/scatters batch cleanly into one kernel
    each); ``base``/``max_steps``/``chunk`` stay scalar, so the inner
    ``fori_loop`` keeps the solo lowering, and the early exit is one
    ``jnp.any`` over the per-instance delivery deficits: the loop runs
    until ALL instances drain, finished instances executing
    post-completion micro-transactions that are exact no-ops (the same
    property the solo early exit relies on at chunk granularity).
    Bit-exactness per instance is asserted by the batch tests and the
    CI batch gate.

    The stream buffer ``fqs`` is not part of the vmapped state: the
    loop carries all B instances' buffers as ONE flat (B·N,) vector,
    instance b owning entries ``[b·N, (b+1)·N)``.  The vmapped ``body``
    gathers its stream heads from that vector at its own offset and
    returns its tail append, which the runner scatters for all B
    instances at once; rows the solo scatter would drop go to index
    B·N, past every instance.  Carried as a batched (B, N) array, the
    buffer sat in the head gather's tiled layout while the scatter
    wanted it flat, and XLA on the TPU re-laid all B buffers between
    the two on every step, instance by instance: a batched step of 32
    cost as much as ~63 solo steps.

    Signature matches the solo runner with every operand carrying a
    leading ``(B,)`` instance axis — including the dynamic scalars
    (``cap``/``real_e``/``max_burst``/``fc_mode``/``xon`` become (B,)
    vectors) — EXCEPT ``max_steps``, which is one shared scalar bound
    (``_plan_batch`` aligns the batch on it; a non-binding bound is
    invisible in the results).
    """
    start = _ring_run(L, E, C0, D, Cf, chunk)._start

    def run(q0_time, q0_dest, q0_inj, sizes, init_tx,
            links_j, route_out_j, route_del_j, route_wt_j, in_rank_j,
            t_cycle_v, t_rev_v, t_idle_v,
            cap, real_e, max_burst, max_steps, fc_mode, xon):
        ops = (q0_time, q0_dest, q0_inj, sizes, init_tx,
               links_j, route_out_j, route_del_j, route_wt_j, in_rank_j,
               t_cycle_v, t_rev_v, t_idle_v, cap, max_burst, fc_mode,
               xon)

        init = jax.vmap(lambda *o: start(*o)[0])(*ops)
        B, N = init.fqs.shape
        # instance b's streams are entries [b·N, (b+1)·N) of one flat
        # buffer; B·N is past them all, where dropped appends go
        fqs0 = init.fqs.reshape(B * N)
        off = jnp.arange(B, dtype=jnp.int32) * N
        drop_at = jnp.int32(B * N)

        def body_of(ops_i, s, step_i, fqs, off_i):
            return start(*ops_i)[1](s, step_i, (fqs, off_i, drop_at))

        vbody = jax.vmap(body_of, in_axes=(0, 0, None, None, 0))

        def step(i, carry):
            s, fqs = carry
            s2, (at, upd) = vbody(ops, s, i, fqs, off)
            with jax.named_scope("ring.forward"):
                fqs = fqs.at[at].set(upd, mode="drop")
            return s2, fqs

        def chunk_body(carry):
            st, fqs, base = carry
            this_chunk = jnp.minimum(jnp.int32(chunk), max_steps - base)
            st2, fqs2 = jax.lax.fori_loop(
                jnp.int32(0), this_chunk,
                lambda i, c: step(base + i, c), (st, fqs))
            return st2, fqs2, base + jnp.int32(chunk)

        def cond(carry):
            st, _, base = carry
            return (jnp.any(st.log_n + st.drops < real_e)
                    & (base < max_steps))

        final, _, base = jax.lax.while_loop(
            cond, chunk_body, (init._replace(fqs=None), fqs0, jnp.int32(0)))
        # one count of chunk steps for the whole batch (clamped by the
        # caller, as solo), carried by every instance: the instance axis
        # keeps it shardable with the other outputs
        steps = jnp.zeros_like(real_e) + base
        return (final.log_n, final.log_pk[:, :E, 0],
                final.log_pk[:, :E, 1], final.log_pk[:, :E, 2],
                final.sent, final.n_sw, final.link.t, final.drops,
                final.busy_ns, final.busy_steps, final.q_drops,
                final.stall_steps, final.credit_waits, steps)

    return run


@functools.lru_cache(maxsize=None)
def _ring_engine(L: int, E: int, C0: int, D: int, Cf: int, chunk: int):
    """Compile-once ring simulation for one static shape signature —
    :func:`_ring_run` jitted."""
    return jax.jit(_ring_run(L, E, C0, D, Cf, chunk))


@functools.lru_cache(maxsize=None)
def _ring_engine_batch(L: int, E: int, C0: int, D: int, Cf: int,
                       chunk: int, n_devices: int = 1):
    """Batched ring engine: ONE compilation running B fabric instances.

    ``jax.vmap`` of :func:`_ring_run` with every operand carrying a
    leading ``(B,)`` instance axis — per-instance traffic, tables, timing
    vectors AND per-instance dynamic scalars (``cap`` / ``real_e`` /
    ``max_burst`` / ``fc_mode`` / ``xon`` become (B,) vectors;
    ``max_steps`` is the one shared scalar bound).  The early-exit
    ``while_loop`` is batch-aware by construction (see
    :func:`_ring_run_batch`): it continues while ANY instance still has
    a delivery/drop deficit — the max-over-instances exit the batch
    semantics require — and finished instances execute exact-no-op
    micro-transactions (the property the solo early exit already relies
    on), so every instance stays bit-exact with its solo run.  With
    ``n_devices > 1`` the batch axis is sharded across devices and each
    shard drains independently (see :func:`_shard_over_batch`)."""
    fn = _ring_run_batch(L, E, C0, D, Cf, chunk)
    return jax.jit(_shard_over_batch(fn, n_devices, n_args=19,
                                     replicated=(16,)))


# -----------------------------------------------------------------------
# Public entry point
# -----------------------------------------------------------------------

def simulate_fabric(topo: Topology,
                    spec: TrafficSpec,
                    *,
                    routing: RoutingTable | None = None,
                    addr: AddressSpec | None = None,
                    mcast=None,
                    timing: LinkTiming = PAPER_TIMING,
                    max_burst: int = 0,
                    initial_tx: int | np.ndarray = 1,
                    max_steps: int | None = None,
                    queue_capacity: int | None = None,
                    flow_control: str = "drop",
                    xon: int | None = None,
                    engine: str = "auto",
                    chunk_size: int = DEFAULT_CHUNK_SIZE) -> FabricResult:
    """Simulate an N-chip fabric of bi-directional AER links.

    This is the stable *convenience wrapper* around the declarative
    :class:`repro.core.fabric.Fabric` object API: it folds the kwargs
    into the corresponding policy objects, builds a one-shot ``Fabric``
    and calls :meth:`Fabric.run`.  Code that reuses one fabric across
    many traffic specs (sweeps, serving loops) should hold a ``Fabric``
    and use its explicit ``compile``/``run``/``run_many`` lifecycle
    instead — the wrapper rebuilds routing tables every call and hides
    the shape-bucketed jit cache that makes repeat runs cheap.

    Args:
      topo:        fabric topology (``router.line/ring/mesh2d_topology``).
      spec:        injected traffic.  With ``addr`` given, ``spec.dest``
                   holds packed 26-bit AER words (multicast tags resolved
                   through ``mcast``); otherwise plain destination chip ids.
      routing:     prebuilt table (rebuilt from ``topo`` when omitted).
      mcast:       a ``MulticastTable`` (tags expanded at the source, the
                   historical default) or a ``fabric.MulticastPolicy``
                   selecting ``source_expand`` vs ``in_fabric``
                   replication.
      timing:      timing contract — one scalar ``LinkTiming`` shared by
                   all links, or a structure-of-arrays ``LinkTiming`` of
                   shape (L,) for per-link heterogeneity (see
                   ``link.per_link_timing``).
      max_burst:   0 = paper-faithful grant rule, B > 0 = bounded burst.
      initial_tx:  scalar or (L,) — which side of each link resets into TX.
      max_steps:   global micro-transaction count; default scales with the
                   total hop-transmissions the traffic needs.
      queue_capacity: per-endpoint budget.  In drop mode slots are
                   one-shot, so this bounds the total events routed
                   *through* an endpoint (defaults to the expanded event
                   count — lossless); smaller values may drop forwards,
                   counted in ``FabricResult.drops``.  In the stall
                   modes it bounds instantaneous occupancy instead.
      flow_control: ``"drop"`` (default, discard at full queues) |
                   ``"credit"`` (stall the upstream pop until occupancy
                   falls below ``queue_capacity``) | ``"onoff"``
                   (xon/xoff hysteresis on the latched threshold bit).
                   See the module docstring; the stall modes require a
                   finite ``queue_capacity`` and guarantee
                   ``drops == 0``.
      xon:         on/off low-water mark (``"onoff"`` only); defaults
                   to ``queue_capacity // 2``.
      engine:      ``"ring"`` (O(1)-per-step streams, early exit, the
                   default via ``"auto"``), ``"reference"`` (PR 1 flat
                   slot scan, the semantics oracle) or ``"pallas"``
                   (slot scan through the fused ``kernels/fabric_queue``
                   kernels).  All three are bit-exact.
      chunk_size:  ring engine only — micro-transactions per ``lax.scan``
                   chunk between early-exit checks.
    """
    from .fabric import EngineSpec, Fabric, QueuePolicy
    fab = Fabric(topo, routing=routing, timing=timing,
                 queues=QueuePolicy(capacity=queue_capacity,
                                    max_burst=max_burst,
                                    initial_tx=initial_tx,
                                    flow=flow_control, xon=xon),
                 engine=EngineSpec(name=engine, chunk_size=chunk_size),
                 addr=addr, mcast=mcast)
    return fab.run(spec, max_steps=max_steps)


# -----------------------------------------------------------------------
# Measurement roll-ups
# -----------------------------------------------------------------------

def fabric_throughput_mev_s(res: FabricResult) -> jnp.ndarray:
    """Delivered events per second across the fabric, MEvents/s."""
    return jnp.where(res.t_end > 0, 1e3 * res.delivered / res.t_end, 0.0)


def per_link_throughput_mev_s(res: FabricResult) -> jnp.ndarray:
    """(L,) per-link transmissions/s (both directions), MEvents/s."""
    n = jnp.sum(res.sent, axis=1)
    return jnp.where(res.t_link > 0, 1e3 * n / res.t_link, 0.0)


def link_energy_pj(sent, timing: LinkTiming = PAPER_TIMING) -> float:
    """THE link energy model: every transmission on link ``l`` moves one
    event at that link's ``e_event_pj`` (scalar timing: the paper's
    11 pJ everywhere; per-link timing: the link's own class figure).

    ``sent`` is per-link transmission counts — ``(L,)`` or ``(L, 2)``
    (trailing axes summed per link).  Shared by
    :func:`fabric_energy_pj` and the SNN report roll-ups
    (``models/snn.py``), so the fabric's billed energy and the
    application-level report can never drift apart."""
    sent = np.asarray(sent, np.float64)
    per_link = sent.sum(axis=tuple(range(1, sent.ndim)))
    e = np.broadcast_to(np.asarray(timing.e_event_pj, np.float64),
                        per_link.shape)
    return float((per_link * e).sum())


def fabric_energy_pj(res: FabricResult,
                     timing: LinkTiming = PAPER_TIMING) -> float:
    """Total link energy of one fabric run (see :func:`link_energy_pj`)."""
    return link_energy_pj(res.sent, timing)


def delivery_multiset(res: FabricResult) -> list:
    """Sorted (injection time, destination chip) pairs of all deliveries
    — the mode-independent multicast contract: ``source_expand`` and
    ``in_fabric`` transports of one workload must produce the identical
    multiset (asserted in tests and gated in the CI bench smoke)."""
    n = int(res.delivered)
    return sorted(zip(np.asarray(res.log_inj)[:n].tolist(),
                      np.asarray(res.log_dest)[:n].tolist()))


def delivered_latencies(res: FabricResult) -> np.ndarray:
    """End-to-end ns latencies of the delivered events (numpy)."""
    n = int(res.delivered)
    inj = np.asarray(res.log_inj)[:n]
    dlv = np.asarray(res.log_del)[:n]
    return (dlv - inj).astype(np.int64)


def latency_stats(res: FabricResult) -> dict:
    """p50/p90/p99/max end-to-end latency plus delivery counters.

    ``traversals`` counts actual link transmissions (the per-link
    weighted hop count energy is billed on) and ``fanout`` the expected
    deliveries per offered event — together they quantify what in-fabric
    multicast replication saves over source expansion."""
    lat = delivered_latencies(res)
    base = {
        "delivered": int(res.delivered),
        "injected": res.injected,
        "offered": res.offered,
        "fanout": res.fanout,
        "traversals": res.traversals,
    }
    if lat.size == 0:
        return {**base, "delivered": 0,
                "p50_ns": 0.0, "p90_ns": 0.0, "p99_ns": 0.0, "max_ns": 0}
    return {
        **base,
        "p50_ns": float(np.percentile(lat, 50)),
        "p90_ns": float(np.percentile(lat, 90)),
        "p99_ns": float(np.percentile(lat, 99)),
        "max_ns": int(lat.max()),
    }
