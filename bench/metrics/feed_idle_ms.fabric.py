"""Device idle time while the host feeds the engine per simulation, ms
(device trace): inside the program's ``fabric:marshal`` and
``fabric:dispatch`` spans of the traced calls (operands built and handed
over, the engine dispatched), the time in which no operation ran on the
device, averaged over the devices used, summed, over the simulations
those calls carried (the ``instances`` stats of their ``marshal``
spans).  Where the trace dropped records only the calls fed before its
last operation count."""

from bench import program_trace as pt
from bench import trace as tr


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    marshal = pt.spans("marshal")
    dispatch = pt.spans("dispatch")
    hi = red["hi"]
    spans = [(s, s + d) for s, d, _ in marshal + dispatch if s + d <= hi]
    sims = sum(int(st.get("instances", 0)) for s, d, st in marshal
               if s + d <= hi)
    if not spans or not sims:
        return None
    fed = tr.union([(a, b - a, "feed") for a, b in spans], -float("inf"),
                   float("inf"))
    idle = 0.0
    for o in red["device_ops"].values():
        idle += sum((b - a) - tr.busy_ns(o, a, b) for a, b in zip(*fed))
    idle /= len(red["device_ops"])
    return 1e-6 * idle / sims
