"""The fabric's own spans and counters (``repro.core.tracing``) and the
engine's executed-step count (``FabricResult.steps``).

Contracts under test:

* spans are kept only while ``tracing.enable()`` is in force, with their
  parent, call id and stats, and ``drain()`` clears them;
* ``Fabric.run`` emits ``plan`` -> ``marshal`` -> ``dispatch`` inside one
  ``run`` call, with the planned events, the host bytes handed to the
  device, and ``compiled`` set only on the call that grew the jit cache;
* ``FabricBatchResult.results()`` is one ``split`` span of B instances;
* a profile (``jax.profiler.trace``) holds the same spans, with their
  stats, on the clock the kept spans use;
* ``FabricResult.steps`` counts the micro-transactions the ring engine
  ran: 1 under ``max_steps=1``, else whole chunks or the bound; one
  count per batch, at least every solo count; the slot engines' scan
  length;
* every ``ring.*`` scope of the ring step reaches the compiled engine's
  op metadata.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import network as net
from repro.core import tracing
from repro.core import traffic as tr
from repro.core.fabric import EngineSpec, Fabric
from repro.core.router import ring_topology

RING_SCOPES = ("ring.init", "ring.head", "ring.fsm", "ring.forward",
               "ring.log", "ring.telemetry")


@pytest.fixture
def kept():
    """Tracing on for one test, off and drained after it."""
    tracing.drain()
    tracing.enable()
    yield
    tracing.enable(False)
    tracing.drain()


def _spec(seed, n=4, per_chip=8):
    return tr.poisson(jax.random.PRNGKey(seed), n, per_chip)


def test_off_by_default_nothing_is_kept():
    tracing.drain()
    with tracing.span("plan", events=3) as sp:
        sp.stat(memo=0)
    Fabric(ring_topology(4)).run(_spec(0))
    assert tracing.drain() == []


def test_nested_spans_keep_parent_call_and_stats(kept):
    with tracing.span("outer", a=1):
        with tracing.span("inner") as sp:
            sp.stat(b=2)
    with tracing.span("next"):
        pass
    outer, inner, nxt = tracing.drain()
    assert (outer.name, inner.name, nxt.name) == ("outer", "inner", "next")
    assert outer.parent is None and inner.parent == outer.id
    assert inner.call == outer.call and nxt.call != outer.call
    assert outer.stats == {"a": 1} and inner.stats == {"b": 2}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert tracing.drain() == []


def test_run_emits_plan_marshal_dispatch(kept):
    fab = Fabric(ring_topology(4))
    spec = _spec(1)
    fab.run(spec)
    spans = tracing.drain()
    assert [s.name for s in spans] == ["run", "plan", "marshal", "dispatch"]
    run, plan, marshal, dispatch = spans
    assert {s.call for s in spans} == {run.call}
    assert all(s.parent == run.id for s in spans[1:])
    assert plan.stats == {"events": spec.n_events, "memo": 0}
    assert marshal.stats["instances"] == dispatch.stats["instances"] == 1
    _, Lp, Np, _Ep, C0, _Dp, _Cf, Rp, Kp, _ = fab._plan(spec, None).bucket
    word = 4
    want = word * (3 * Lp * 2 * C0 + Lp * 2 + 2 * Np * Rp * Kp + Np * Rp
                   + 6)
    assert marshal.stats["bytes"] == want
    assert plan.end_ns <= marshal.start_ns <= marshal.end_ns \
        <= dispatch.start_ns
    # the same spec again: its plan is memoised
    tracing.drain()
    fab.run(spec)
    assert tracing.drain()[1].stats == {"events": spec.n_events, "memo": 1}


def test_compiled_marks_the_call_that_grew_the_cache(kept):
    # a chunk size no other test uses: a bucket of its own
    fab = Fabric(ring_topology(4), engine=EngineSpec("ring", chunk_size=37))
    fab.run(_spec(2))
    fab.run(_spec(3))
    compiled = [s.stats["compiled"] for s in tracing.drain()
                if s.name == "dispatch"]
    assert compiled == [1, 0]


def test_results_is_one_split_of_the_batch(kept):
    fab = Fabric(ring_topology(4))
    batch = fab.run_batch([_spec(k) for k in range(3)])
    names = [s.name for s in tracing.drain()]
    assert names == ["run_batch", "plan", "plan", "plan", "marshal",
                     "dispatch"]
    batch.results()
    split, = tracing.drain()
    assert split.name == "split" and split.stats == {"instances": 3}
    batch.instance(1)
    assert [s.stats for s in tracing.drain()] == [{"instances": 1}]


def test_profile_holds_the_kept_spans(kept, tmp_path):
    fab = Fabric(ring_topology(4))
    spec = _spec(4)
    fab.run(spec)
    tracing.drain()
    with jax.profiler.trace(str(tmp_path)):
        fab.run(spec)
    kept_spans = {s.name: s for s in tracing.drain()}
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                  recursive=True)[0])
    env = {}
    traced = {}
    for plane in pd.planes:
        env.update(dict(st[:2] for st in plane.stats))
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(tracing.PREFIX):
                    traced[ev.name[len(tracing.PREFIX):]] = ev
    assert set(traced) == {"run", "plan", "marshal", "dispatch"}
    assert dict(traced["marshal"].stats)["bytes"] == \
        kept_spans["marshal"].stats["bytes"]
    assert dict(traced["dispatch"].stats)["compiled"] == 0
    assert dict(traced["plan"].stats)["memo"] == 1
    # trace times count from the profile's start, on the kept spans' clock
    t0 = env["profile_start_time"]
    for name, ev in traced.items():
        assert abs(t0 + ev.start_ns - kept_spans[name].start_ns) < 5e6


def test_steps_is_one_under_a_one_step_bound():
    res = Fabric(ring_topology(4)).run(_spec(5), max_steps=1)
    assert int(res.steps) == 1


def test_steps_are_whole_chunks_or_the_bound():
    chunk = 16
    fab = Fabric(ring_topology(4), engine=EngineSpec("ring",
                                                     chunk_size=chunk))
    spec = _spec(6)
    res = fab.run(spec)
    bound = fab._plan(spec, None).max_steps
    steps = int(res.steps)
    assert 0 < steps <= bound
    assert steps % chunk == 0 or steps == bound
    # a bound that cuts the run mid-chunk is executed exactly
    cut = steps - chunk // 2
    assert int(fab.run(spec, max_steps=cut).steps) == cut


def test_batch_shares_one_step_count():
    fab = Fabric(ring_topology(4))
    specs = [_spec(k, per_chip=4 + 4 * k) for k in range(3)]
    solo = [int(fab.run(s).steps) for s in specs]
    batch = fab.run_batch(specs)
    counts = [int(r.steps) for r in batch.results()]
    assert len(set(counts)) == 1
    assert counts[0] >= max(solo)
    assert np.asarray(batch.steps).shape == (3,)


@pytest.mark.parametrize("engine", ["reference", "pallas"])
def test_slot_engines_report_their_scan_length(engine):
    fab = Fabric(ring_topology(4), engine=EngineSpec(engine))
    spec = _spec(7, per_chip=4)
    plan = fab._plan(spec, None)
    assert int(fab.run(spec).steps) == plan.max_steps
    batch = fab.run_batch([spec, spec])
    assert [int(r.steps) for r in batch.results()] == [plan.max_steps] * 2


def test_every_ring_scope_reaches_the_compiled_engine():
    fab = Fabric(ring_topology(4))
    _, Lp, Np, Ep, C0, Dp, Cf, Rp, Kp, chunk = fab._plan(_spec(8),
                                                         None).bucket

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    hlo = net._ring_engine(Lp, Ep, C0, Dp, Cf, chunk).lower(
        *[sds((Lp, 2, C0))] * 3, sds((Lp, 2)), sds((Lp,)), sds((Lp, 2)),
        sds((Np, Rp, Kp)), sds((Np, Rp)), sds((Np, Rp, Kp)), sds((Lp, 2)),
        sds((Lp,)), sds((Lp,)), sds((Lp,)), *[sds(())] * 6,
    ).compile().as_text()
    for scope in RING_SCOPES:
        assert f"/{scope}/" in hlo, scope
