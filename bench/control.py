"""The control of the fabric cells' comparison: a run with it in the
program's place must come out as not correct.

    python3 -m bench.control --workload <cell> --seeds 11,12,13

The control is the benchmark's reference with its conservative look-ahead
switched off: a link may then serve an entry that a forward still in
flight would precede, which breaks the configuration's guarantee of
release-ordered, exact latencies (the step a faster engine would be
tempted by).  For each seed it makes one whole run of the cell through
``bench.run.run`` with :class:`ControlDriver` standing in for the
configuration's driver: set-up, a window of one call that simulates as
many of the cell's streams as a run compares, the same release and
check, and the same ``correct`` decision.  It prints each run's result
line; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from types import SimpleNamespace

from bench import fabric_ref, generator
from bench.drivers import fabric as drv


class ControlDriver(drv.Driver):
    """The fabric driver with the look-ahead-off reference in place of
    the program: one call simulates ``check`` streams of the cell."""

    def __init__(self, cfg, mix, seed, n_chips, log=print):
        super().__init__(cfg, mix, seed, n_chips, log=log)
        self.batch = int(mix["check"])

    def setup(self):
        self.n, _ = fabric_ref.topology_links(self.cfg["topology"])
        self.pool = [generator.instance(self.mix, self.n, self.seed, i)
                     for i in range(self.batch)]

    def call(self, spans, split: bool = False) -> int:
        i0 = self.next_i
        streams = [self.pool[i0 + k] if i0 + k < len(self.pool)
                   else generator.instance(self.mix, self.n, self.seed,
                                           i0 + k)
                   for k in range(self.batch)]
        self.next_i += self.batch
        with spans("engine"):
            outs = fabric_ref.simulate(self.cfg, streams, lookahead=False)
        e_pj = float(self.cfg["timing"]["e_event_pj"])
        for k, (st, r) in enumerate(zip(streams, outs)):
            res = SimpleNamespace(**r._asdict(), injected=len(st[0]))
            self.done.append((i0 + k, res, drv.reference_rollup(r, e_pj)))
        return sum(len(st[0]) for st in streams)


def control_run(workload: str, seed: int, *, plan: dict | None = None,
                check_chips: bool = True, log=print) -> dict:
    """One run of the cell with the control in the program's place."""
    from bench import run as br
    return br.run(workload, seed, 0.0, False, plan=plan,
                  check_chips=check_chips, driver_cls=ControlDriver,
                  log=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    for s in a.seeds.split(","):
        print(json.dumps(control_run(a.workload, int(s))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
