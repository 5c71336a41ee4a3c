"""Host planning time per simulation, ms (host clock): the benchmark's
``plan`` spans of the traced run's window, over the simulations it
completed.  A traced run splits each call into the program's two steps
(``bench/drivers/fabric.py``, ``Driver.call``): ``Fabric._plan`` for one
stream, ``fabric._plan_batch`` for a batch (per call, so over B
simulations)."""


def read(ctx):
    sims = ctx.get("sims") or 0
    spans = [t1 - t0 for name, t0, t1 in ctx.get("spans", ())
             if name == "plan"]
    if not sims or not spans:
        return None
    return 1e3 * sum(spans) / sims
