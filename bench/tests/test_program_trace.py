"""The readers of the program's own spans and scopes
(``bench/program_trace.py`` and the metrics that use it) on synthetic
traces with known answers, and the span reading on a real CPU profile."""

import pytest

from bench import plugins
from bench import program_trace as pt
from bench import trace as tr

US, MS = 1e3, 1e6       # ns


def _metric(name):
    return plugins.load("metrics", name)


def _red(intervals, lo, hi):
    return {"device_ops": {"/device:TPU:0": tr.ops(intervals)},
            "lo": lo, "hi": hi, "complete": True}


def _program(monkeypatch, spans=None, scopes=None):
    monkeypatch.setattr(pt, "load", lambda log_dir=None: {
        "spans": spans or {}, "scopes": scopes or {}})


SCOPES = {"fusion.1": "ring.head", "fusion.2": "ring.forward",
          "fusion.3": "ring.init", "fusion.9": "ring.fsm",
          "copy.1": ""}


def _steps(n, t0=0):
    """One call: init (10 ns), a hoisted fsm op (5 ns), then n steps of
    head (20 ns) + forward (30 ns) back to back, 100 ns apart, and an
    unscoped copy after each step."""
    ops = [(t0, 10, "fusion.3"), (t0 + 10, 5, "fusion.9")]
    for k in range(n):
        s = t0 + 20 + 100 * k
        ops += [(s, 20, "fusion.1"), (s + 20, 30, "fusion.2"),
                (s + 60, 10, "copy.1")]
    return ops


def test_engine_step_over_a_whole_call(monkeypatch):
    _program(monkeypatch, scopes=SCOPES)
    ctx = {"trace": _red(_steps(4), 0, 1000)}
    # ring ops 10 + 5 + 4 x 50 ns and the unscoped copy that runs once
    # per step, 4 x 10 ns; 4 steps (median of counts 4 and 4; the
    # hoisted fsm op ran once)
    assert _metric("engine_step_us.fabric").read(ctx) == pytest.approx(
        (15 + 200 + 40) / 4 / US)


def test_engine_step_on_a_partial_trace(monkeypatch):
    """The slice ends inside the 6th step's head: the step count is
    still the median of the step-scoped operations' executions."""
    _program(monkeypatch, scopes=SCOPES)
    ops = _steps(10)
    hi = 20 + 100 * 5 + 10
    ctx = {"trace": dict(_red(ops, 0, hi), complete=False)}
    # head ran 6 times (the last one cut to 10 ns), forward 5, the
    # hoisted fsm once: median 5; the copy ran 5 times
    busy = 15 + 5 * 50 + 10 + 5 * 10
    assert _metric("engine_step_us.fabric").read(ctx) == pytest.approx(
        busy / 5 / US)


def test_engine_step_leaves_out_work_outside_the_loop(monkeypatch):
    """Unscoped operations that run less than once per step (the trims
    and the roll-up's reads between calls) are not step work."""
    _program(monkeypatch, scopes=SCOPES)
    ops = _steps(4) + [(500, 40, "slice.1"), (600, 40, "slice.1")]
    ctx = {"trace": _red(ops, 0, 1000)}
    assert _metric("engine_step_us.fabric").read(ctx) == pytest.approx(
        (15 + 200 + 40) / 4 / US)


def test_engine_step_finds_nothing_without_scopes(monkeypatch):
    _program(monkeypatch, scopes={})
    ctx = {"trace": _red(_steps(4), 0, 1000)}
    assert _metric("engine_step_us.fabric").read(ctx) is None
    _program(monkeypatch, scopes={"fusion.3": "ring.init"})
    assert _metric("engine_step_us.fabric").read(ctx) is None
    assert _metric("engine_step_us.fabric").read({"trace": None}) is None


def _host(batch):
    """Two calls of ``batch`` simulations: marshal 2 ms, dispatch 1 ms,
    split 4 ms (batch only)."""
    spans = {"marshal": [(0, 2 * MS, {"instances": batch, "bytes": 8}),
                         (10 * MS, 2 * MS, {"instances": batch})],
             "dispatch": [(2 * MS, 1 * MS, {"instances": batch,
                                            "compiled": 0}),
                          (12 * MS, 1 * MS, {"instances": batch})]}
    if batch > 1:
        spans["split"] = [(5 * MS, 4 * MS, {"instances": batch}),
                          (15 * MS, 4 * MS, {"instances": batch})]
    return spans


@pytest.mark.parametrize("batch", [1, 32])
def test_host_spans_per_simulation(monkeypatch, batch):
    _program(monkeypatch, spans=_host(batch))
    ctx = {"trace": None}
    assert _metric("marshal_ms.fabric").read(ctx) == pytest.approx(
        4 / (2 * batch))
    split = _metric("split_ms.fabric").read(ctx)
    if batch == 1:
        assert split is None
    else:
        assert split == pytest.approx(8 / (2 * batch))


def test_feed_idle(monkeypatch):
    _program(monkeypatch, spans=_host(32))
    # the device runs 0.5 ms inside the first call's dispatch and 1 ms
    # inside the second call's marshal
    ops = [(2.5 * MS, 0.5 * MS, "fusion.1"), (11 * MS, 1 * MS, "copy.1"),
           (13 * MS, 5 * MS, "fusion.2")]
    ctx = {"trace": _red(ops, 0, 20 * MS)}
    idle_ms = (3 - 0.5) + (3 - 1)
    assert _metric("feed_idle_ms.fabric").read(ctx) == pytest.approx(
        idle_ms / 64)
    # a trace cut after the first call counts that call alone
    ctx = {"trace": dict(_red(ops, 0, 5 * MS), complete=False)}
    assert _metric("feed_idle_ms.fabric").read(ctx) == pytest.approx(
        2.5 / 32)


def test_no_program_spans_reads_nothing(monkeypatch):
    _program(monkeypatch)
    ctx = {"trace": _red(_steps(2), 0, 1000)}
    for name in ("marshal_ms.fabric", "split_ms.fabric",
                 "feed_idle_ms.fabric"):
        assert _metric(name).read(ctx) is None
    assert _metric("feed_idle_ms.fabric").read({"trace": None}) is None


def test_scope_of():
    assert pt.scope_of({"tf_op": "jit(run)/while/body/ring.head/gather"}) \
        == "ring.head"
    assert pt.scope_of({"tf_op": "jit(run)/while/body/while/body/"
                                 "vmap(ring.fsm)/vmap()/and"}) == "ring.fsm"
    assert pt.scope_of({"tf_op": "jit(run)/while/body/add"}) == ""
    assert pt.scope_of({"tf_op": "jit(run)/string.headers/add"}) == ""
    assert pt.scope_of({}) == ""


def test_spans_of_a_real_profile(tmp_path):
    """A CPU profile of one fabric run: the program's spans and their
    stats are read from the host plane."""
    import jax

    from repro.core import traffic
    from repro.core.fabric import Fabric
    from repro.core.router import ring_topology
    fab = Fabric(ring_topology(4))
    spec = traffic.poisson(jax.random.PRNGKey(0), 4, 4)
    fab.run(spec)
    with jax.profiler.trace(str(tmp_path)):
        fab.run(spec)
    got = pt.load(str(tmp_path))
    assert set(got["spans"]) == {"run", "plan", "marshal", "dispatch"}
    (_, dur, stats), = got["spans"]["marshal"]
    assert dur > 0 and stats["instances"] == 1 and stats["bytes"] > 0
    assert got["scopes"] == {}
