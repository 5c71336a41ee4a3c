"""Plain reference of the AER fabric simulation, kept with the benchmark.

It imports nothing of the program.  From a topology, a link timing and a
traffic stream it recomputes, in straightforward ``jax.numpy``, what a
fabric run must return: the delivery log, per-link counters, clocks and
telemetry.  The semantics are those of the paper's link pair scaled out
(arXiv 1908.07413 Figs. 1-2, Table I): every link is one shared
bi-directional bus with a transceiver FSM on each end, one global step is
one micro-transaction on every link, and a conservative look-ahead keeps
every queue in release order.  It covers what the benchmark's
configurations state: unicast shortest-path routing (BFS, ties to the
lowest chip and link), unbounded one-shot queues in drop mode, one
timing contract on every link, no burst bound, side 0 of every link
starting in TX.  A configuration that sets anything else is refused
(``refuse_unmodelled``), never simulated as if it had not.  Topologies
come from ``bench/topologies/<kind>.py``, one file each.

Layout: every endpoint queue is a row of one-shot slots (``BIG`` =
empty), scanned in full every step, the plainest form of the rule
"serve the earliest released entry, FIFO among equal times".  The row
width is the exact number of entries that queue receives over the run,
worked out from the routes, so nothing can overflow.

``lookahead=False`` drops the conservative look-ahead guard: a link may
serve an entry that a forward still in flight would precede.  That breaks
the configuration's guarantee of exact, release-ordered latencies, and is
the benchmark's control (it must come out as not correct).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import plugins

BIG = 2 ** 30          # "never released" sentinel, int32 ns
RX, TX = 0, 1
CHUNK = 128            # steps between completion checks


# --------------------------------------------------------------------------
# Topology and routing (numpy, set-up)
# --------------------------------------------------------------------------

#: the settings of a configuration this reference models; any other value
#: is refused, never ignored
MODELLED = {
    "routing": "static_bfs",
    "queues": {"capacity": None, "flow": "drop", "max_burst": 0,
               "initial_tx": 1},
}


def refuse_unmodelled(cfg: dict):
    """Raise unless every routing and queue setting of ``cfg`` is one the
    reference models: static BFS routes, unbounded drop-mode queues, no
    burst bound, side 0 of every link starting in TX."""
    for key, want in MODELLED.items():
        if cfg.get(key) != want:
            raise ValueError(f"the reference models {key} = {want!r}, the "
                             f"configuration sets {cfg.get(key)!r}")


def topology_links(topo: dict) -> tuple[int, np.ndarray]:
    """``(n_chips, links (L, 2))`` of the topology ``topo``, from
    ``bench/topologies/<kind>.py``: link l joins chip ``links[l, 0]``
    (side 0) to chip ``links[l, 1]`` (side 1)."""
    n, links = plugins.load("topologies", topo["kind"]).links(topo)
    return int(n), np.asarray(links, np.int32).reshape(-1, 2)


def bfs_routes(n: int, links: np.ndarray):
    """Next-hop ``(link, side)`` and hop count for every (chip, dest).

    Breadth-first search outward from each destination; a chip takes the
    first neighbour (lowest chip id, then link id) of the earliest
    frontier that reaches it."""
    adj = [[] for _ in range(n)]
    for l, (a, b) in enumerate(links.tolist()):
        adj[a].append((b, l, 0))
        adj[b].append((a, l, 1))
    for lst in adj:
        lst.sort()
    next_link = np.full((n, n), -1, np.int64)
    out_side = np.full((n, n), -1, np.int64)
    hops = np.full((n, n), -1, np.int64)
    for dst in range(n):
        hops[dst, dst] = 0
        frontier = [dst]
        while frontier:
            nxt = []
            for u in frontier:
                for v, l, side_u in adj[u]:
                    if hops[v, dst] == -1:
                        hops[v, dst] = hops[u, dst] + 1
                        next_link[v, dst] = l
                        out_side[v, dst] = 1 - side_u
                        nxt.append(v)
            frontier = sorted(nxt)
    return next_link, out_side, hops


class Setup(NamedTuple):
    """One instance, ready for the step loop (numpy arrays)."""
    q_time: np.ndarray     # (Q, C) release times, BIG = empty
    q_dest: np.ndarray     # (Q, C) destination chip
    q_inj: np.ndarray      # (Q, C) injection time
    n_ins: np.ndarray      # (Q,) entries placed so far
    width: int             # C: the most entries any queue receives
    max_steps: int


def prepare(n: int, links: np.ndarray, routes, src, t, dest) -> Setup:
    """Place each event in its first-hop queue, in time order (ties in
    stream order), and size every queue row for the whole run."""
    next_link, out_side, hops = routes
    src = np.asarray(src, np.int64)
    t = np.asarray(t, np.int64)
    dest = np.asarray(dest, np.int64)
    if np.any(src == dest) or np.any(hops[src, dest] < 0):
        raise ValueError("self-addressed or unreachable events")
    L = len(links)
    Q = 2 * L
    E = len(src)
    # every queue an event passes through, walked hop by hop
    through = np.zeros(Q, np.int64)
    at = src.copy()
    live = at != dest
    while np.any(live):
        q = next_link[at[live], dest[live]] * 2 + out_side[at[live],
                                                           dest[live]]
        np.add.at(through, q, 1)
        l, s = q // 2, q % 2
        at[live] = np.where(s == 0, links[l, 1], links[l, 0])
        live = at != dest
    width = int(max(through.max(initial=1), 1))
    grp = next_link[src, dest] * 2 + out_side[src, dest]
    order = np.lexsort((np.arange(E), t, grp))
    sizes = np.bincount(grp, minlength=Q)
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slot = np.arange(E) - start[grp[order]]
    q_time = np.full((Q, width), BIG, np.int32)
    q_dest = np.zeros((Q, width), np.int32)
    q_inj = np.zeros((Q, width), np.int32)
    q_time[grp[order], slot] = t[order]
    q_dest[grp[order], slot] = dest[order]
    q_inj[grp[order], slot] = t[order]
    total_tx = int(hops[src, dest].sum())
    diameter = int(hops.max())
    max_steps = 4 * total_tx + 2 * E + 64 * (diameter + 2)
    return Setup(q_time, q_dest, q_inj, sizes.astype(np.int32), width,
                 max_steps)


# --------------------------------------------------------------------------
# The step loop (jax.numpy)
# --------------------------------------------------------------------------

def _fsm(mode, ack, rx_p, sw_req, pending, rx_strobe):
    """One SW_Control evaluation of every block (paper Table I), no burst
    bound: request RX->TX iff RX, probed and events pending; grant TX->RX
    iff the peer requests and nothing is pending."""
    rx_p = jnp.where((mode == RX) & (rx_strobe == 1), 1, rx_p)
    want_req = (mode == RX) & (pending > 0) & (rx_p == 1)
    want_grant = (mode == TX) & (sw_req == 1) & (pending == 0)
    ack2 = jnp.where(mode == TX, jnp.where(want_grant, 0, 1),
                     jnp.where(want_req, 1, 0)).astype(jnp.int32)
    mode2 = jnp.where((ack2 == 1) & (sw_req == 0), TX,
                      jnp.where((ack2 == 0) & (sw_req == 1), RX, mode))
    switched = mode2 != mode
    rx_p = jnp.where(switched & (mode2 == RX), 0, rx_p)
    return mode2.astype(jnp.int32), ack2, rx_p.astype(jnp.int32)


class State(NamedTuple):
    t: jnp.ndarray          # (L,) link clocks
    mode: jnp.ndarray       # (L, 2) FSM mode of the side-0 / side-1 block
    ack: jnp.ndarray        # (L, 2) sw_ack wires
    rx_p: jnp.ndarray       # (L, 2) RX probes
    last_dir: jnp.ndarray   # (L,) 1 = last transmission went side 0 -> 1
    bus_busy: jnp.ndarray   # (L,)
    prev_tx: jnp.ndarray    # (L, 2) transmitted last step
    q_time: jnp.ndarray
    q_dest: jnp.ndarray
    q_inj: jnp.ndarray
    n_ins: jnp.ndarray      # (Q,)
    sent: jnp.ndarray       # (L, 2)
    n_sw: jnp.ndarray       # (L,) side-0 mode changes after step 0
    log_inj: jnp.ndarray
    log_del: jnp.ndarray
    log_dest: jnp.ndarray
    log_n: jnp.ndarray
    drops: jnp.ndarray
    busy_ns: jnp.ndarray    # (L,)
    busy_steps: jnp.ndarray  # (L, 2)
    step: jnp.ndarray


def _step(s: State, links, t_cycle, t_rev, t_idle, E: int,
          lookahead: bool) -> State:
    L = links.shape[0]
    Q = 2 * L
    t_now = s.t
    # released entries per queue: pending count, earliest release, the
    # slot to serve (lowest slot among the earliest), next arrival
    tq = jnp.repeat(t_now, 2)[:, None]
    rel = s.q_time <= tq
    pend = rel.sum(axis=1).astype(jnp.int32).reshape(L, 2)
    val = jnp.where(rel, s.q_time, BIG)
    r_min = val.min(axis=1).reshape(L, 2)
    head = jnp.argmin(val, axis=1).astype(jnp.int32)
    nxt = jnp.where(rel, BIG, s.q_time).min(axis=1).reshape(L, 2)
    busy_steps = s.busy_steps + (pend > 0).astype(jnp.int32)

    # conservative look-ahead: a link acts no earlier than its clock
    # (work pending) or its next arrival; every future forward lands no
    # earlier than min(na + t_cycle), and an entry released after that
    # bound must wait
    na = jnp.where(pend > 0, t_now[:, None], nxt).min(axis=1)
    t_next = jnp.where(pend > 0, BIG, nxt).min(axis=1)
    t_next = jnp.minimum(t_next, jnp.maximum(na.min(), t_now))
    if lookahead:
        ok = r_min <= (na + t_cycle).min()
        pend = jnp.where(ok, pend, 0)

    # the link pair's micro-transaction: FSMs settle in two passes (the
    # receive strobe feeds only the first), then at most one transmit
    mode, ack, rx_p = s.mode, s.ack, s.rx_p
    peer_ack = ack[:, ::-1]
    m1, a1, p1 = _fsm(mode, ack, rx_p, peer_ack, pend, s.prev_tx[:, ::-1])
    m2, a2, p2 = _fsm(m1, a1, p1, a1[:, ::-1], pend, jnp.zeros_like(pend))
    tx = (m2 == TX) & (m2[:, ::-1] == RX) & (pend > 0)       # (L, 2)
    do_tx = tx[:, 0] | tx[:, 1]
    dir_now = tx[:, 0].astype(jnp.int32)
    rev = dir_now != s.last_dir
    cost = t_cycle + jnp.where(rev & (s.bus_busy == 1), t_rev, 0) \
        + jnp.where(rev & (s.bus_busy == 0), t_idle, 0)
    settling = jnp.any((a2 != ack) | (m2 != mode), axis=1)
    idle = ~do_tx & ~settling
    t_new = jnp.where(do_tx, t_now + cost,
                      jnp.where(idle & (t_next < BIG), t_next, t_now))
    bus_busy = jnp.where(do_tx, 1, jnp.where(idle, 0, s.bus_busy))
    last_dir = jnp.where(do_tx, dir_now, s.last_dir)
    n_sw = s.n_sw + jnp.where(s.step > 0, (m2[:, 0] != mode[:, 0]), 0)
    busy_ns = s.busy_ns + jnp.where(do_tx, t_new - t_now, 0)

    # serve: the transmitting side's head leaves its queue
    side = jnp.where(tx[:, 0], 0, 1)
    qid = jnp.arange(L) * 2 + side
    slot = head[qid]
    ev_dest = s.q_dest[qid, slot]
    ev_inj = s.q_inj[qid, slot]
    pop_q = jnp.where(do_tx, qid, Q)
    q_time = s.q_time.at[pop_q, slot].set(BIG, mode="drop")
    sent = s.sent + jnp.stack([1 - side, side], 1) * do_tx[:, None]
    rx_chip = jnp.where(side == 0, links[:, 1], links[:, 0])

    # deliver at the destination, in link order
    dlv = do_tx & (rx_chip == ev_dest)
    d32 = dlv.astype(jnp.int32)
    lslot = jnp.where(dlv, s.log_n + jnp.cumsum(d32) - d32, E)
    log_inj = s.log_inj.at[lslot].set(ev_inj, mode="drop")
    log_del = s.log_del.at[lslot].set(t_new, mode="drop")
    log_dest = s.log_dest.at[lslot].set(rx_chip, mode="drop")
    log_n = s.log_n + d32.sum()
    return State(t_new, m2, a2, p2, last_dir, bus_busy,
                 tx.astype(jnp.int32) * do_tx[:, None], q_time, s.q_dest,
                 s.q_inj, s.n_ins, sent, n_sw, log_inj, log_del, log_dest,
                 log_n, s.drops, busy_ns, busy_steps, s.step + 1), \
        (do_tx & ~dlv, rx_chip, ev_dest, ev_inj, t_new)


def _forward(s: State, fwd, rx_chip, ev_dest, ev_inj, t_new, nl, osd,
             E: int) -> State:
    """Append every forwarded event to its next queue: simultaneous
    appends into one queue go in link order, each at the queue's next
    free slot; a slot past the logical capacity E is a drop."""
    Q = s.q_time.shape[0]
    M = fwd.shape[0]
    g = jnp.where(fwd, nl[rx_chip, ev_dest] * 2 + osd[rx_chip, ev_dest], Q)
    idx = jnp.arange(M)
    before = (g[None, :] == g[:, None]) & (idx[None, :] < idx[:, None])
    key = s.n_ins[jnp.minimum(g, Q - 1)] + before.sum(axis=1)
    ok = fwd & (key < E)
    gq = jnp.where(ok, g, Q)
    q_time = s.q_time.at[gq, key].set(t_new, mode="drop")
    q_dest = s.q_dest.at[gq, key].set(ev_dest, mode="drop")
    q_inj = s.q_inj.at[gq, key].set(ev_inj, mode="drop")
    n_ins = s.n_ins.at[gq].add(1, mode="drop")
    drops = s.drops + (fwd & ~ok).sum().astype(jnp.int32)
    return s._replace(q_time=q_time, q_dest=q_dest, q_inj=q_inj,
                      n_ins=n_ins, drops=drops)


@functools.partial(jax.jit, static_argnames=("E", "lookahead"))
def _run(q_time, q_dest, q_inj, n_ins, max_steps, links, nl, osd,
         t_cycle, t_rev, t_idle, *, E: int, lookahead: bool):
    L = links.shape[0]
    z = jnp.zeros((L,), jnp.int32)
    init_mode = jnp.stack([jnp.ones((L,), jnp.int32), z], 1)   # side 0 TX
    s = State(t=z, mode=init_mode, ack=init_mode, rx_p=1 - init_mode,
              last_dir=jnp.ones((L,), jnp.int32), bus_busy=z,
              prev_tx=jnp.zeros((L, 2), jnp.int32), q_time=q_time,
              q_dest=q_dest, q_inj=q_inj, n_ins=n_ins,
              sent=jnp.zeros((L, 2), jnp.int32), n_sw=z,
              log_inj=jnp.zeros((E,), jnp.int32),
              log_del=jnp.zeros((E,), jnp.int32),
              log_dest=jnp.zeros((E,), jnp.int32),
              log_n=jnp.zeros((), jnp.int32), drops=jnp.zeros((), jnp.int32),
              busy_ns=z, busy_steps=jnp.zeros((L, 2), jnp.int32),
              step=jnp.zeros((), jnp.int32))

    def one(s, _):
        s, (fwd, rx_chip, ev_dest, ev_inj, t_new) = _step(
            s, links, t_cycle, t_rev, t_idle, E, lookahead)
        return _forward(s, fwd, rx_chip, ev_dest, ev_inj, t_new, nl, osd,
                        E), None

    def cond(s):
        return (s.log_n + s.drops < E) & (s.step < max_steps)

    def chunk(s):
        return jax.lax.scan(one, s, None, length=CHUNK)[0]

    return jax.lax.while_loop(cond, chunk, s)


class Result(NamedTuple):
    """What a fabric run must report, as numpy arrays."""
    delivered: int
    log_inj: np.ndarray
    log_del: np.ndarray
    log_dest: np.ndarray
    sent: np.ndarray
    n_switches: np.ndarray
    t_link: np.ndarray
    t_end: int
    drops: int
    busy_ns: np.ndarray
    busy_steps: np.ndarray
    q_drops: np.ndarray
    stall_steps: np.ndarray
    credit_waits: np.ndarray
    steps: int
    complete: bool


def simulate(cfg: dict, specs, *, lookahead: bool = True) -> list[Result]:
    """Simulate each ``(src, t, dest)`` stream on the configuration's
    fabric, refusing settings the reference does not model.

    ``cfg["timing"]`` holds the paper's link contract in ns: ``t_req2req_ns``
    (event cycle), ``t_bidir_ns`` (alternating-direction cycle),
    ``t_sw_ns`` and ``t_sw2req_ns`` (idle switch).  Instances run one
    after another, each as one compiled loop."""
    refuse_unmodelled(cfg)
    timing = cfg["timing"]
    n, links = topology_links(cfg["topology"])
    routes = bfs_routes(n, links)
    nl, osd, _ = routes
    L = len(links)
    t_cycle = int(timing["t_req2req_ns"])
    t_rev = int(timing["t_bidir_ns"]) - t_cycle
    t_idle = int(timing["t_sw_ns"]) + int(timing["t_sw2req_ns"])
    consts = [jnp.asarray(x, jnp.int32)
              for x in (links, np.maximum(nl, 0), np.maximum(osd, 0))]
    out = []
    for src, t, dest in specs:
        st = prepare(n, links, routes, src, t, dest)
        E = len(src)
        # pad the slot width to a power of two, so that runs of similar
        # traffic share one compilation (extra slots stay empty)
        C = 1 << max(st.width - 1, 0).bit_length()
        pad = ((0, 0), (0, C - st.width))
        f = _run(np.pad(st.q_time, pad, constant_values=BIG),
                 np.pad(st.q_dest, pad), np.pad(st.q_inj, pad), st.n_ins,
                 np.int32(st.max_steps), *consts,
                 np.full(L, t_cycle, np.int32), np.full(L, t_rev, np.int32),
                 np.full(L, t_idle, np.int32), E=E, lookahead=lookahead)
        f = jax.device_get(f)
        k = int(f.log_n)
        zero = np.zeros((L, 2), np.int32)
        out.append(Result(
            delivered=k, log_inj=f.log_inj[:k], log_del=f.log_del[:k],
            log_dest=f.log_dest[:k], sent=f.sent, n_switches=f.n_sw,
            t_link=f.t, t_end=int(f.t.max()), drops=int(f.drops),
            busy_ns=f.busy_ns, busy_steps=f.busy_steps, q_drops=zero,
            stall_steps=zero, credit_waits=zero, steps=int(f.step),
            complete=bool(k + int(f.drops) == E)))
    return out
