"""Device time of one ring-engine step, us (device trace).

The ring engine names the parts of its step with ``jax.named_scope``
(``ring.head``, ``ring.fsm``, ``ring.forward``, ``ring.log``,
``ring.telemetry``; ``ring.init`` builds the initial state once per
call), and XLA keeps the scope in each operation's ``op_name``
(``bench/program_trace.py``).  Every operation of the step's loop body
runs once per step, so the traced slice's step count is the median of
the execution counts of the step-scoped operations: an operation XLA
hoisted out of the loop (once per call) or one inside a loop of its own
lies at either end and does not move the median.

The step's device time is the union, over the slice, of the operations
under a ``ring.*`` scope and of those the slice shows running at least
once per step.  The second set is the compiler's own work inside the
loop, which carries no ``op_name``: on a v5e the copies of the loop
carry between HBM and VMEM (on the 8x8 mesh the stream buffer's, a
large part of the step).  Operations outside the loop run a few times
per call, far fewer than the steps.  The union over the step count is
the value.  No whole call is needed, so the 8x8 mesh's partial trace is
read too.  For a batch a step is one batched step of all its instances.
Averaged over the devices used."""

import numpy as np

from bench import program_trace as pt
from bench import trace as tr


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    scope = pt.scopes()
    lo, hi = red["lo"], red["hi"]
    vals = []
    for o in red["device_ops"].values():
        sc = [scope.get(n, "") for n in o.names]
        ring = np.asarray([s.startswith("ring.") for s in sc], bool)
        step = ring & np.asarray([s != "ring.init" for s in sc], bool)
        inside = (o.start >= lo) & (o.start < hi)
        counts = np.bincount(o.name[inside], minlength=len(o.names))
        stepped = counts[step & (counts > 0)]
        if not len(stepped):
            continue
        steps = float(np.median(stepped))
        loop = ring | (counts >= steps - 1)
        keep = loop[o.name]
        busy = tr.busy_ns(tr.Ops(o.start[keep], o.end[keep], o.name[keep],
                                 o.names), lo, hi)
        vals.append(1e-3 * busy / steps)
    return sum(vals) / len(vals) if vals else None
