"""Roll-up time per simulation, ms (host clock): the benchmark's
``rollup`` spans of the traced window (latency percentiles, throughput,
per-link load, switches, energy; for a batch also splitting it into
instances), over the simulations completed in the window."""


def read(ctx):
    sims = ctx.get("sims") or 0
    spans = [t1 - t0 for name, t0, t1 in ctx.get("spans", ())
             if name == "rollup"]
    if not sims or not spans:
        return None
    return 1e3 * sum(spans) / sims
