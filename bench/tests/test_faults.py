"""A run with the timed path broken underneath has to come out as not
correct.  Each test skips only the harness's look for a chip and drives
the rest of a run (set-up, window, roll-up, check) on the CPU at a small
traffic size, with one fault planted in the program:

* an answer altered where it is produced (one delivery time off by 1 ns);
* a step that returns its state unchanged (the engine runs no step);
* half of the batch left out (``run_batch`` simulates the first half and
  hands its results out for the rest).
"""

import pytest

from bench import run as br

CELLS = ["mesh8x8.poisson256", "ring16.poisson64.batch32",
         "ring16.poisson64.serial"]


def _run(cell, seconds=0.3, trace=False):
    plan = br.cell_plan(cell)
    plan["mix"] = dict(plan["mix"], events_per_chip=4)
    return br.run(cell, 2**31 + 77, seconds, trace, plan=plan,
                  check_chips=False, log=lambda *a, **k: None)


@pytest.mark.parametrize("cell,trace", [("ring16.poisson64.serial", False),
                                        ("ring16.poisson64.serial", True),
                                        ("ring16.poisson64.batch32", True)])
def test_sound_run_is_correct(cell, trace, monkeypatch, tmp_path):
    """A sound run is correct, traced too: a traced run splits each call
    into the program's plan and engine steps (the CPU has no device
    plane to reduce, so only the host-clock readers report)."""
    monkeypatch.setattr(br, "TRACE_DIR", str(tmp_path / "trace"))
    res = _run(cell, trace=trace)
    assert res["correct"] and res["failed"] == 0
    assert list(res)[-1] == "checks"
    if trace:
        assert {"plan_ms.fabric", "rollup_ms.fabric"} <= set(res["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer(cell, monkeypatch):
    from repro.core import fabric as F
    real = F.CompiledFabric._execute

    def altered(self, plan):
        r = real(self, plan)
        if plan.E == 0:         # the zero-event warm-up run
            return r
        return r._replace(log_del=r.log_del.at[0].add(1))

    monkeypatch.setattr(F.CompiledFabric, "_execute", altered)
    if cell.endswith("batch32"):
        real_b = F._execute_batch

        def altered_b(fabs, plans, n_dev):
            r = real_b(fabs, plans, n_dev)
            return r._replace(log_del=r.log_del.at[:, 0].add(1))

        monkeypatch.setattr(F, "_execute_batch", altered_b)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_state_unchanged(cell, monkeypatch):
    from repro.core import fabric as F
    real = F.CompiledFabric._execute
    real_b = F._execute_batch
    monkeypatch.setattr(F.CompiledFabric, "_execute",
                        lambda self, plan: real(self,
                                                plan._replace(max_steps=0)))
    monkeypatch.setattr(
        F, "_execute_batch",
        lambda fabs, plans, n: real_b(fabs, [p._replace(max_steps=0)
                                             for p in plans], n))
    res = _run(cell)
    assert not res["correct"]


def test_half_the_batch_left_out(monkeypatch):
    from repro.core import fabric as F
    real = F.Fabric.run_batch

    def half(self, specs, **kw):
        specs = list(specs)
        h = len(specs) // 2
        r = real(self, specs[:h] * 2, **kw)
        return r

    monkeypatch.setattr(F.Fabric, "run_batch", half)
    res = _run("ring16.poisson64.batch32")
    assert not res["correct"]
    assert res["checks"]["mismatched_fields"]["value"] > 0
