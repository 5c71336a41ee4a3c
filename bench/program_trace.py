"""The program's own spans and the device operations' scopes in a traced
run, for the per-layer readers that need more than ``bench.trace.load``
keeps.

``bench.trace.load`` keeps the benchmark's ``bench:`` spans and the
device operations' intervals (``ctx["trace"]``).  The program marks its
own work too: ``repro.core.tracing`` annotates host work as
``fabric:<name>`` spans with integer stats (``plan``, ``marshal``,
``dispatch``, ``split``; ``events``, ``bytes``, ``compiled``,
``instances``), and the ring engine's step names its parts with
``jax.named_scope`` (``ring.init``, ``ring.head``, ``ring.fsm``,
``ring.forward``, ``ring.log``, ``ring.telemetry``), which XLA keeps
in each operation's ``op_name`` metadata.  This module reads both from
the newest trace under ``bench.run.TRACE_DIR``, once per run:

* the host planes' ``fabric:`` events, as ``(start_ns, dur_ns, stats)``
  on the device planes' clock;
* each device operation's stats, once per operation name: the trace
  keeps them in the event metadata the operation's events point to (on
  a v5e, ``tf_op`` holds the ``op_name`` path), so no device event is
  read a second time.  The operation's scope is the ``ring.*`` part of
  that path.

A program without these spans or scopes (an older one) gives empty
answers, and the readers then find nothing to read.
"""

from __future__ import annotations

import glob
import os
import re

#: prefix of the program's own spans
SPAN_PREFIX = "fabric:"

#: a ``ring.*`` scope in an ``op_name`` path: ``.../ring.head/gather``
#: solo, ``.../vmap(ring.head)/gather`` in the batched engine
RING = re.compile(r"(?:^|[/(])(ring\.[A-Za-z_]+)(?:[/)]|$)")

_cache: dict = {}


def _newest(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def scope_of(stats: dict) -> str:
    """The ``ring.*`` scope of one operation from its trace stats (the
    ``op_name`` path in ``tf_op``), ``""`` if none."""
    m = RING.search(str(stats.get("tf_op", "")))
    return m.group(1) if m else ""


def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b, i: int, end: int):
    """``(field number, value)`` of one protobuf message in ``b[i:end]``:
    an int for a varint, ``(start, end)`` for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield num, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode(errors="replace")


def _op_stats(path: str) -> dict:
    """Device operation name -> the stats of its event metadata.

    The profiler keeps an operation's ``tf_op`` (its ``op_name``) in the
    metadata its events point to, which ``jax.profiler.ProfileData`` does
    not expose, so the ``XSpace`` protobuf is walked here (``XSpace.planes``
    = 1; ``XPlane.name`` = 2, ``event_metadata`` = 4, ``stat_metadata`` =
    5; ``XEventMetadata.name`` = 2, ``display_name`` = 4, ``stats`` = 5;
    ``XStat.metadata_id`` = 1, values 3 / 4 / 5 / 7).  The planes' lines,
    which hold the events, are skipped unread."""
    import mmap
    out: dict[str, dict] = {}
    with open(path, "rb") as fh, \
            mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ) as b:
        for num, plane in _fields(b, 0, len(b)):
            if num != 1:
                continue
            name, metas, stat_names = "", [], {}
            for pn, pv in _fields(b, *plane):
                if pn == 2:
                    name = _text(b, pv)
                elif pn == 4:
                    metas.append(pv)
                elif pn == 5:
                    for en, ev in _fields(b, *pv):
                        if en == 2:
                            sm = dict(_fields(b, *ev))
                            if 2 in sm:
                                stat_names[sm.get(1, 0)] = _text(b, sm[2])
            if not name.startswith("/device:") or "CPU" in name:
                continue
            for entry in metas:
                for en, ev in _fields(b, *entry):
                    if en != 2:
                        continue
                    names, stats = [], {}
                    for mn, mv in _fields(b, *ev):
                        if mn in (2, 4):
                            names.append(_text(b, mv))
                        elif mn == 5:
                            st = dict(_fields(b, *mv))
                            key = stat_names.get(st.get(1, 0), "")
                            if 5 in st:
                                stats[key] = _text(b, st[5])
                            elif 7 in st:
                                stats[key] = stat_names.get(st[7], "")
                            else:
                                stats[key] = st.get(4, st.get(3))
                    for n in names:
                        out.setdefault(n, stats)
    return out


def _read(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(ev.name[len(SPAN_PREFIX):],
                                         []).append(
                            (ev.start_ns, ev.duration_ns,
                             {k: v for k, v, *_ in ev.stats}))
    for v in spans.values():
        v.sort(key=lambda s: s[0])
    return {"spans": spans,
            "scopes": {n: scope_of(st) for n, st in _op_stats(path).items()}}


def load(log_dir: str | None = None) -> dict:
    """``{"spans": {name: [(start_ns, dur_ns, stats), ...]}, "scopes":
    {device op name: ring scope or ""}}`` of the run's trace (empty where
    there is none), read once per trace file."""
    if log_dir is None:
        from bench import run as br
        log_dir = br.TRACE_DIR
    path = _newest(log_dir)
    if path is None:
        return {"spans": {}, "scopes": {}}
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(path)
    return _cache[key]


def spans(name: str) -> list:
    """The program's ``fabric:<name>`` spans, by start time."""
    return load()["spans"].get(name, [])


def scopes() -> dict:
    """Device operation name -> its ``ring.*`` scope (``""`` if none)."""
    return load()["scopes"]
