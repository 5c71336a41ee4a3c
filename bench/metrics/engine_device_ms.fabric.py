"""Device busy time of the engine per simulation, ms (device trace).

The traced calls are whole (``bench.run.TracedCalls``).  Inside each of
their ``engine`` spans (the benchmark's host span from the dispatch of
``Fabric.run`` / the batched engine to the completion of its results),
the union of the device operations' intervals is the engine's device
time, averaged over the devices used.  Summed over the traced calls and
divided by the simulations they carried (``instances_per_call`` each),
it is the engine's device time per simulation.  A trace that dropped
records (``red["complete"]`` false) holds no whole call: nothing to
read."""

from bench import trace as tr


def read(ctx):
    red, batch = ctx.get("trace"), ctx.get("batch") or 0
    if red is None or not red["complete"] or not batch:
        return None
    eng = [(s, s + d) for s, d, name in red["spans"] if name == "engine"]
    if not eng:
        return None
    busy = sum(sum(tr.busy_ns(o, a, b) for a, b in eng)
               for o in red["device_ops"].values())
    busy /= len(red["device_ops"])
    if busy <= 0:
        return None
    return 1e-6 * busy / (len(eng) * batch)
